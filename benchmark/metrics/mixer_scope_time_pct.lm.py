"""Share of the device's busy time in the traced window that the sequence
mixers other than attention took, every pass: the Mamba-2 mixer with its
scan, the gated short convolution (rows of `kind` `mixer` in the map the
worker wrote beside the profile, `lib/scopes.py`)."""

from lib import scopes


def read(run):
    return scopes.share_pct(run, kinds=("mixer",))
