"""Model FLOP/s utilisation: this run's tokens a second times the
operations a token needs (forward and backward of the dense decoder,
causal attention, nothing recomputed) over chips times the bf16 peak."""

from lib import flops, peaks


def read(run):
    rate = run.record_rate()
    if rate is None or not run.device.get("kind"):
        return None
    m = run.config["model"]
    per_token = flops.decoder_train_flops_per_token(
        m["d_model"], m["n_layers"], m["ffn_mult"], m["vocab"],
        int(run.config["record_tokens"]))
    peak = peaks.peaks(run.device["kind"])["flops_bf16"]
    tokens = rate * int(run.config["record_tokens"])
    return 100.0 * tokens * per_token / (run.device["count"] * peak)
