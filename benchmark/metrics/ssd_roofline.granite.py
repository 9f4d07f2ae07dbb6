"""The least time the chip could take for the scan's needed work over the
time the scan's operations took, in the granite-4.0-h-micro cut. Needed, a
token of a scanning layer: the chunked form's products at the published
chunk (the lower triangles of C B^T and of the masked product, the states
in and out) at the bf16 peak against x, dt, B, C read and y written once
at the HBM peak, forward once and backward once (`_granite_ops.py` counts
them): the same work whatever implements it, so the decay mask written
and read, the upper triangle and a rematerialised forward all read as
lost. Tokens x scanning layers a step come from the step's own
`ssd_scan_tokens`; the steps in the trace are its length over the median
step (`_step_intervals.py`). Prints which roof binds."""

import json

from lib import cell


def read(run):
    ops = cell.load_module("metrics", "_granite_ops")
    z = ops.sizes(run)
    found = ops.share_of_busy_s(run, (ops.scan_shape,)) if z else None
    tokens = ops.scan_tokens_per_step(run) if found else None
    step_ms = cell.load_module(
        "metrics", "_step_intervals").percentile(run, 50) if tokens else None
    if not step_ms or not run.device.get("kind"):
        return None
    took, _ = found
    steps = run.trace["window_s"] / (step_ms / 1e3)
    per_token, roofs = ops.scan_least_seconds_per_token(
        z, run.device["kind"])
    least = steps * tokens * per_token
    print(json.dumps({
        "reader": "ssd_roofline.granite", "steps_in_trace": steps,
        "scan_tokens_per_step": tokens, "binding_roof_by_pass": roofs,
        "least_s": least, "took_s": took}), flush=True)
    return 100.0 * least / took
