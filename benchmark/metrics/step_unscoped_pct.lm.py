"""Share of the device's busy time in the traced window in which nothing
that carries an `op_name` ran: the compiler's own copies, prefetches and
collective waits where they are not hidden behind a named instruction, by
the map the worker wrote beside the profile (`lib/scopes.py`)."""

from lib import scopes


def read(run):
    return scopes.share_pct(run, phases=("none",))
