"""Share of the device's busy time in the traced window spent on forward
work that the backward pass runs again: instructions whose `op_name` lies
under `rematted_computation`, by the map the worker wrote beside the
profile (`lib/scopes.py`). 0 where the model rematerialises nothing."""

from lib import scopes


def read(run):
    return scopes.share_pct(run, phases=("remat",))
