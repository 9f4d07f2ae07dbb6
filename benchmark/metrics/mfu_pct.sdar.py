"""Model FLOP/s utilisation of the SDAR cut under block-diffusion
training: this run's record tokens a second times the operations a record
token of the cut as run needs (forward and backward; two rows a token in
every layer, the head over the noised row; held experts at their share of
a row's assignments; L + b scores a token a head; nothing recomputed;
`_sdar_ops.py` counts them) over chips times the bf16 peak."""

from lib import cell, peaks


def read(run):
    ops = cell.load_module("metrics", "_sdar_ops")
    z = ops.sizes(run)
    rate = run.record_rate() if z else None
    if rate is None or not run.device.get("kind"):
        return None
    peak = peaks.peaks(run.device["kind"])["flops_bf16"]
    tokens = rate * int(run.config["record_tokens"])
    return 100.0 * tokens * ops.train_flops_per_token(z) / (
        run.device["count"] * peak)
