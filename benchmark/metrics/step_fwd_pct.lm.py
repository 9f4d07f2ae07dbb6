"""Share of the device's busy time in the traced window that the step's
forward pass took: instructions whose `op_name` lies under `jvp(..)` and
under neither `transpose(..)` nor `rematted_computation`, by the map the
worker wrote beside the profile (`lib/scopes.py`). With `step_bwd_pct.lm`,
`step_remat_pct.lm`, `step_update_pct.lm` and `step_unscoped_pct.lm` it
adds up to 100."""

from lib import scopes


def read(run):
    return scopes.share_pct(run, phases=("fwd",))
