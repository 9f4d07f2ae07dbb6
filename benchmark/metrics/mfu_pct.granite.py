"""Model FLOP/s utilisation of the granite-4.0-h-micro cut: this run's
tokens a second times the operations a token of the cut as run needs
(forward and backward; six a multiplying parameter, the scan's products in
nine layers, causal attention in one; nothing recomputed; `_granite_ops.py`
counts them) over chips times the bf16 peak."""

from lib import cell, peaks


def read(run):
    ops = cell.load_module("metrics", "_granite_ops")
    z = ops.sizes(run)
    rate = run.record_rate() if z else None
    if rate is None or not run.device.get("kind"):
        return None
    peak = peaks.peaks(run.device["kind"])["flops_bf16"]
    tokens = rate * int(run.config["record_tokens"])
    return 100.0 * tokens * ops.train_flops_per_token(z) / (
        run.device["count"] * peak)
