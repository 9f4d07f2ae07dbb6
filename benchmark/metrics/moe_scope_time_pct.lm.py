"""Share of the device's busy time in the traced window that the routed
experts took, every pass: routing, the grouped loops (a loop and its body
once) and the shared experts (rows of `kind` `moe` in the map the worker
wrote beside the profile, `lib/scopes.py`)."""

from lib import scopes


def read(run):
    return scopes.share_pct(run, kinds=("moe",))
