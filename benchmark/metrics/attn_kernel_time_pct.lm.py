"""Share of the device's busy time in the traced window that the Pallas
attention calls took: forward, backward and recomputed (rows of `kind`
`attention_kernel` in the map the worker wrote beside the profile,
`lib/scopes.py`)."""

from lib import scopes


def read(run):
    return scopes.share_pct(run, kinds=("attention_kernel",))
