"""The least time the chip could take for the needed work of the masked
attention's calls in the trace over the device time they took. Needed: L
(L + b) scores a batch*head, 2 products forward and 4 backward, nothing
recomputed (a rematerialised forward call adds its time and no needed
work), and the tensors' bytes, against the bf16 and HBM peaks: the same
work whatever implements it, so skipped steps, the masked part of a
crossed tile and the backward's second QK^T all read as lost. Prints which
roof binds."""

import json

from lib import cell


def read(run):
    ops = cell.load_module("metrics", "_sdar_ops")
    z = ops.sizes(run)
    events = ops.kernel_events(run) if z else None
    if not events:
        return None
    took = sum(e[-1] for e in events) / 1e9
    by_kernel = {}
    for e in events:
        by_kernel.setdefault(e[0], []).append(e)
    # A step needs each layer's forward once: forward calls beyond the
    # backward's count are rematerialised twins.
    n_bwd = len(by_kernel.get(ops.BWD, []))
    needed = by_kernel.get(ops.BWD, []) + (
        by_kernel.get(ops.FWD, [])[:n_bwd] if n_bwd
        else by_kernel.get(ops.FWD, []))
    least, roofs = ops.least_seconds(run, needed)
    print(json.dumps({
        "reader": "bd_attn_roofline", "calls": len(events),
        "calls_needed": len(needed), "binding_roof_by_call": roofs,
        "mean_ms_by_kernel": {
            k: sum(e[-1] for e in v) / len(v) / 1e6
            for k, v in by_kernel.items()},
        "least_s": least, "took_s": took}), flush=True)
    return 100.0 * least / took if took > 0 else None
