"""The Mamba-2 mixers' share of the device's busy time in the trace, in
the granite-4.0-h-micro cut: in-projection, convolution, scan, gated norm
and out-projection, their backward and weight gradients and a
rematerialised forward, known by the widths only a mixer has
(`_granite_ops.py` says how): the share the new mechanism holds. The
optimizer's update of the same weights is not counted."""

from lib import cell


def read(run):
    ops = cell.load_module("metrics", "_granite_ops")
    return ops.share_of_busy_pct(run, (ops.mixer_shape,))
