"""The gated short convolutions' share of the device's busy time in the
trace: in-projection, gates, convolution and their backward, known by the
in-projection's width (`_lfm2_ops.py` says how, and what of the
out-projection it cannot see)."""

from lib import cell


def read(run):
    ops = cell.load_module("metrics", "_lfm2_ops")
    return ops.share_of_busy_pct(run, (ops.shortconv_shape,))
