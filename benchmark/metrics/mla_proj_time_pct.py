"""What surrounds the latent attention's kernels, as a share of the
device's busy time in the trace: the query projection, the latent's down-
projection with its norm, its up-projection, the rope turn over 64 of a
head's 192 channels, the copy of the one rope key to the heads and the
changes of layout before and after the kernels, and the output projection,
forward and backward, known by this cut's shapes (`_kanana_ops.py` lists
them); the kernels' own calls and the optimizer's update are not counted."""

from lib import cell


def read(run):
    ops = cell.load_module("metrics", "_kanana_ops")
    return ops.share_of_busy_pct(
        run, (ops.projection_shape,), but_kernels=True)
