"""Model FLOP/s utilisation of the Mellum 2 cut: this run's tokens a second
times the operations a token of the cut as run needs (forward and backward;
held experts at their share of a row's assignments; the windowed layers'
scores under the band, the full layer's causal half; nothing recomputed;
`_mellum_ops.py` counts them) over chips times the bf16 peak: needed work
over measured time, the share of the whole step's peak."""

from lib import cell, peaks


def read(run):
    ops = cell.load_module("metrics", "_mellum_ops")
    z = ops.sizes(run)
    rate = run.record_rate() if z else None
    if rate is None or not run.device.get("kind"):
        return None
    peak = peaks.peaks(run.device["kind"])["flops_bf16"]
    tokens = rate * int(run.config["record_tokens"])
    return 100.0 * tokens * ops.train_flops_per_token(z) / (
        run.device["count"] * peak)
