"""Model FLOP/s utilisation of the Kanana 2 cut: this run's tokens a second
times the operations a token of the cut as run needs (forward and backward;
held experts at their share of a row's assignments, the shared experts and
the dense layer whole; the causal half of the scores at keys of 192 against
values of 128; nothing recomputed; `_kanana_ops.py` counts them) over chips
times the bf16 peak: needed work over measured time, the share of the whole
step's peak."""

from lib import cell, peaks


def read(run):
    ops = cell.load_module("metrics", "_kanana_ops")
    z = ops.sizes(run)
    rate = run.record_rate() if z else None
    if rate is None or not run.device.get("kind"):
        return None
    peak = peaks.peaks(run.device["kind"])["flops_bf16"]
    tokens = rate * int(run.config["record_tokens"])
    return 100.0 * tokens * ops.train_flops_per_token(z) / (
        run.device["count"] * peak)
