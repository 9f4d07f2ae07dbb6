"""Share of the device's busy time in the traced window that the step's
backward pass took: instructions whose `op_name` lies under
`transpose(jvp(..))` and not under `rematted_computation`, by the map the
worker wrote beside the profile (`lib/scopes.py`)."""

from lib import scopes


def read(run):
    return scopes.share_pct(run, phases=("bwd",))
