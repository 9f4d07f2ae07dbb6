"""The chunked state-space scan's share of the device's busy time in the
trace, in the granite-4.0-h-micro cut: forward, backward and the
rematerialised forward of the four phases of
`layers/mamba2.py:ssd_chunked` in nine layers, known by tensors in the
chunked layout at this cut's shapes (`_granite_ops.py` says how)."""

from lib import cell


def read(run):
    ops = cell.load_module("metrics", "_granite_ops")
    return ops.share_of_busy_pct(run, (ops.scan_shape,))
