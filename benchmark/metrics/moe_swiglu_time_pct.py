"""The gated routed layers' share of the device's busy time in the trace:
routing (router scores, top-k, the sort of the assignments and its
inverse) and the grouped gated product over the held experts (the `while`
loops: gather, products, scatter-add a block), known by their shapes
(`_lfm2_ops.py` says how). The optimizer's update of the same weights is
not counted."""

from lib import cell


def read(run):
    ops = cell.load_module("metrics", "_lfm2_ops")
    return ops.share_of_busy_pct(
        run, (ops.routing_shape, ops.grouped_shape))
