"""The causal flash kernels' share of the device's busy time in the trace,
in the Mellum 2 cut's full-attention layers: `flash_fwd` and `flash_bwd`
by the instruction's name (the windowed layers' calls carry `band_` before
it and are `band_attn_time_pct`'s)."""

from lib import cell


def read(run):
    ops = cell.load_module("metrics", "_mellum_ops")
    return ops.time_share_pct(run, ops.FULL_KERNELS)
