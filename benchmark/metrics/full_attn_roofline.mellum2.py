"""The least time the chip could take for the needed work of the causal
flash kernels' calls in the Mellum 2 cut's full-attention layers
(`flash_fwd` / `flash_bwd` by the instruction's name) over the device time
they took. Needed: the causal half, S (S + 1) / 2 scores a batch*head, 2
products forward and 4 backward, nothing recomputed, and the tensors'
bytes, against the bf16 and HBM peaks: `band_attn_roofline`'s reckoning
under the other mask. Prints which roof binds."""

from lib import cell


def read(run):
    ops = cell.load_module("metrics", "_mellum_ops")
    return ops.roofline_pct(
        run, ops.FULL_KERNELS, "full_attn_roofline.mellum2")
