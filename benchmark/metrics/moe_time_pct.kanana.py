"""The sigmoid-routed gated layers' share of the device's busy time in the
trace, in the Kanana 2 cut: routing (router scores, sigmoid, top-k, the
sort of the assignments and its inverse), the grouped gated product over
the held experts (the `while` loops: gather, products, scatter-add a block)
and the shared experts' gated MLP over every row, known by this cut's
shapes (`_kanana_ops.py` says how). The optimizer's update of the same
weights is not counted."""

from lib import cell


def read(run):
    ops = cell.load_module("metrics", "_kanana_ops")
    return ops.share_of_busy_pct(
        run, (ops.routing_shape, ops.grouped_shape, ops.shared_shape))
