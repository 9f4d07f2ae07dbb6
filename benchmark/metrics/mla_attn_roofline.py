"""The least time the chip could take for the needed work of the latent
attention's flash calls in the trace over the device time they took.
Needed: the causal half, S (S + 1) / 2 scores a batch*head, each 2 x (192 +
128) operations forward (QK^T over the key width, PV over the value width)
and twice that backward, nothing recomputed (a rematerialised forward call
adds its time and no needed work), and the bytes of the chosen form (q and
k as [rows, 192] operands, v and the output at 128), against the bf16 and
HBM peaks. Prints which roof binds."""

from lib import cell


def read(run):
    ops = cell.load_module("metrics", "_kanana_ops")
    return ops.roofline_pct(run, "mla_attn_roofline")
