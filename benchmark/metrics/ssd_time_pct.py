"""The chunked state-space scan's share of the device's busy time in the
trace: forward, backward and the rematerialised forward of the four
phases of `layers/mamba2.py:ssd_chunked`, known by tensors still in the
chunked layout (`_model_ops.py` says how)."""

from lib import cell


def read(run):
    ops = cell.load_module("metrics", "_model_ops")
    return ops.share_of_busy_pct(run, (ops.scan_shape,))
