"""The routed expert layers' share of the device's busy time in the trace:
routing (router scores, top-k, the sort of the assignments and its
inverse), the grouped product over the held experts (the `while` loops:
gather, products, scatter-add a block) and the shared expert, known by
their shapes (`_model_ops.py` says how). The optimizer's update of the
same weights is not counted."""

from lib import cell


def read(run):
    ops = cell.load_module("metrics", "_model_ops")
    return ops.share_of_busy_pct(
        run, (ops.routing_shape, ops.grouped_shape, ops.shared_shape))
