"""The flash kernels' share of the device's busy time in the trace, under
the band of the windowed layers: the forward and backward calls, known by
the instruction's own name (`band_flash_fwd`, `band_flash_bwd`; a
rematerialised forward counts: it is time the step spends)."""

from lib import cell


def read(run):
    ops = cell.load_module("metrics", "_mellum_ops")
    return ops.time_share_pct(run, ops.BAND_KERNELS)
