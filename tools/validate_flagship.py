"""Validate the flagship transformer config on the real chip: >=100M params,
S>=4096, bf16 + Pallas flash attention + remat. Trains on synthetic Markov
sequences (data/gen/synthetic.py) whose token-CE floor is log(branching), and
prints one JSON line with param count, losses, and step time.

Run: python tools/validate_flagship.py  (writes FLAGSHIP_VALIDATION.json)
"""

import json
import os
import sys
import time

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))

import jax
import numpy as np

from elasticdl_tpu.data.gen.synthetic import synthetic_lm_tokens
from elasticdl_tpu.models.transformer import transformer_lm as tlm
from elasticdl_tpu.worker.trainer import LocalTrainer


def _flagship_mfu(cfg, n_params, tokens_per_sec):
    """Analytic MFU with attention FLOPs included (the PaLM accounting):
    6 FLOPs/token per matmul parameter (fwd 2 + bwd 4; embedding gathers
    excluded, LM head included) + 12*L*d*S per token for the attention
    score/value matmuls. Remat recompute is deliberately NOT counted —
    MFU measures model math retired, not hardware work."""
    from elasticdl_tpu.observability.mfu import peak_flops

    embed_params = cfg.vocab * cfg.d_model + cfg.max_len * cfg.d_model
    matmul_params = n_params - embed_params
    flops_per_token = (
        6 * matmul_params + 12 * cfg.n_layers * cfg.d_model * cfg.max_len
    )
    # An unknown device raises: no MFU against a guessed denominator.
    peak = peak_flops(jax.devices()[0].device_kind)
    return flops_per_token * tokens_per_sec / peak, flops_per_token


def main(batch=4, seq_len=4096, steps=30, profile_dir="", out_name=None):
    cfg = tlm.flagship_config(max_len=seq_len)
    model = tlm.custom_model(cfg)
    trainer = LocalTrainer(model, tlm.loss, tlm.optimizer())

    tokens = synthetic_lm_tokens(
        batch * 4, seq_len, vocab=cfg.vocab, branching=4, seed=0
    )
    losses = []
    profiling = False
    trace_start = min(10, max(1, steps - 2))
    t_first = time.perf_counter()
    for i in range(steps):
        sl = slice((i % 4) * batch, (i % 4 + 1) * batch)
        feats = tokens[sl, :-1]
        labels = tokens[sl, 1:]
        if profile_dir and i == trace_start:
            jax.profiler.start_trace(profile_dir)
            profiling = True
        _, _, loss = trainer.train_minibatch(feats, labels)
        if profiling and i >= trace_start + 3:
            float(loss)
            jax.profiler.stop_trace()
            profiling = False
        losses.append(loss)
        if i == 0:
            compile_s = time.perf_counter() - t_first
            float(loss)
            t_steady = time.perf_counter()
    if profiling:
        # Short runs end inside the window; an unclosed trace is empty.
        float(losses[-1])
        jax.profiler.stop_trace()
    losses = [float(l) for l in losses]  # forces completion of every step
    steady_s = time.perf_counter() - t_steady
    n_params = sum(
        int(np.prod(p.shape))
        for p in jax.tree_util.tree_leaves(trainer._variables["params"])
    )
    tokens_per_sec = batch * seq_len * (steps - 1) / steady_s
    mfu, flops_per_token = _flagship_mfu(cfg, n_params, tokens_per_sec)
    if profile_dir and out_name is None:
        # Tracing start/stop + its sync sit inside the timing window:
        # don't clobber the canonical (untraced) numbers by default.
        out_name = "FLAGSHIP_PROFILE.json"
    result = {
        "device": jax.devices()[0].device_kind,
        **({"profiled": True} if profile_dir else {}),
        "params": n_params,
        "batch": batch,
        "seq_len": seq_len,
        "steps": steps,
        "first_loss": round(losses[0], 4),
        "last_loss": round(losses[-1], 4),
        "loss_floor_log_branching": round(float(np.log(4)), 4),
        "step_time_s": round(steady_s / (steps - 1), 4),
        "tokens_per_sec": round(tokens_per_sec, 1),
        "model_flops_per_token": flops_per_token,
        **({"mfu": round(mfu, 4)} if mfu else {}),
        "compile_plus_first_step_s": round(compile_s, 1),
        "loss_decreasing": losses[-1] < losses[0],
    }
    print(json.dumps(result))
    out = os.path.join(
        os.path.dirname(__file__), "..",
        out_name or "FLAGSHIP_VALIDATION.json",
    )
    with open(out, "w") as f:
        json.dump(result, f, indent=1)
    return result


if __name__ == "__main__":
    import argparse

    p = argparse.ArgumentParser("validate_flagship")
    p.add_argument("--batch", type=int, default=4)
    p.add_argument("--seq_len", type=int, default=4096)
    p.add_argument("--steps", type=int, default=30)
    p.add_argument("--profile_dir", default="")
    p.add_argument("--out_name", default=None)
    a = p.parse_args()
    main(a.batch, a.seq_len, a.steps, a.profile_dir, a.out_name)
