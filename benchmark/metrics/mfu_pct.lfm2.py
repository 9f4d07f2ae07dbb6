"""Model FLOP/s utilisation of the LFM2 cut: this run's tokens a second
times the operations a token of the cut as run needs (forward and
backward; held experts at their share of a token's assignments; causal
attention; nothing recomputed; `_lfm2_ops.py` counts them) over chips
times the bf16 peak."""

from lib import cell, peaks


def read(run):
    ops = cell.load_module("metrics", "_lfm2_ops")
    z = ops.sizes(run)
    rate = run.record_rate() if z else None
    if rate is None or not run.device.get("kind"):
        return None
    peak = peaks.peaks(run.device["kind"])["flops_bf16"]
    tokens = rate * int(run.config["record_tokens"])
    return 100.0 * tokens * ops.train_flops_per_token(z) / (
        run.device["count"] * peak)
