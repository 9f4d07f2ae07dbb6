"""Share of the device's busy time in the traced window that the optimizer
took, with whatever else lies outside the differentiated function:
instructions whose `op_name` lies under no `jvp(..)`, by the map the
worker wrote beside the profile (`lib/scopes.py`)."""

from lib import scopes


def read(run):
    return scopes.share_pct(run, phases=("update",))
