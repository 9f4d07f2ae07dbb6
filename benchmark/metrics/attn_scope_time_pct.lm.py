"""Share of the device's busy time in the traced window that the attention
took, every pass: projections, norms, rotary turns, changes of layout and
the kernels (rows of `kind` `attention` and `attention_kernel` in the map
the worker wrote beside the profile, `lib/scopes.py`; which scope is the
attention's is the program's to say)."""

from lib import scopes


def read(run):
    return scopes.share_pct(run, kinds=("attention", "attention_kernel"))
