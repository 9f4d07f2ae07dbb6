"""The least time the chip could take for the needed work of the windowed
layers' attention calls in the trace over the device time they took.
Needed: W (W + 1) / 2 + (S - W) W scores a batch*head, 2 products forward
and 4 backward, nothing recomputed (a rematerialised forward call adds its
time and no needed work), and the tensors' bytes, against the bf16 and HBM
peaks: the same work whatever implements it, so the masked half of a
crossed tile and the backward's second QK^T read as lost. Prints which
roof binds."""

from lib import cell


def read(run):
    ops = cell.load_module("metrics", "_mellum_ops")
    return ops.roofline_pct(run, ops.BAND_KERNELS, "band_attn_roofline")
