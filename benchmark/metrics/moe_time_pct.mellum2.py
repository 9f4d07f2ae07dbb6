"""The softmax-routed gated layers' share of the device's busy time in the
trace, in the Mellum 2 cut: routing (router scores, softmax, top-k, the
sort of the assignments and its inverse) and the grouped gated product over
the held experts (the `while` loops: gather, products, scatter-add a
block), known by this cut's shapes (`_mellum_ops.py` says how). The
optimizer's update of the same weights is not counted."""

from lib import cell


def read(run):
    ops = cell.load_module("metrics", "_mellum_ops")
    return ops.share_of_busy_pct(
        run, (ops.routing_shape, ops.grouped_shape))
