"""The last unexplored single-chip flagship lever (VERDICT r4 #9): a
collective-free CHUNKED LM head at S=8192.

The flagship's head materializes logits [B, S, V] in f32 — at B=2,
S=8192, V=32768 that is 2.1 GB of HBM for one intermediate, which is why
the r4 S=8192 measurement was capped at batch 2. This probe computes the
CE loss in sequence chunks under jax.checkpoint (logits recomputed per
chunk in the backward), so the full logits tensor never exists, and
measures whether (a) the chunking itself wins step time at batch 2 and
(b) the freed memory admits batch 4 and wins throughput.

Run on the chip through the chip tool: python tools/head_probe.py
Prints one JSON object (a measured lever or a recorded negative result).
"""

import json
import os
import sys
import time

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))

import flax.linen as nn
import jax
import jax.numpy as jnp
import numpy as np
import optax

from elasticdl_tpu.data.gen.synthetic import synthetic_lm_tokens
from elasticdl_tpu.models.transformer import transformer_lm as tlm
from elasticdl_tpu.models.transformer.transformer_lm import (
    Block,
    embed_input,
)

CHUNK = 1024


def build(cfg, chunked):
    act_dtype = jnp.dtype(cfg.activation_dtype)

    class Trunk(nn.Module):
        @nn.compact
        def __call__(self, tokens, training=False):
            x = embed_input(cfg, tokens)
            for _ in range(cfg.n_layers):
                x = Block(cfg)(x, training)
            return nn.LayerNorm(dtype=act_dtype)(x)

    trunk = Trunk()

    def init_fn(rng, sample):
        r_t, r_h = jax.random.split(rng)
        trunk_p = trunk.init(r_t, sample)["params"]
        head_p = {
            "kernel": jax.nn.initializers.lecun_normal()(
                r_h, (cfg.d_model, cfg.vocab), jnp.float32
            ),
            "bias": jnp.zeros((cfg.vocab,), jnp.float32),
        }
        return {"trunk": trunk_p, "head": head_p}

    def full_loss(params, tokens, labels):
        h = trunk.apply({"params": params["trunk"]}, tokens, True)
        logits = (
            h.astype(jnp.float32) @ params["head"]["kernel"]
            + params["head"]["bias"]
        )
        return jnp.mean(
            optax.softmax_cross_entropy_with_integer_labels(
                logits, labels
            )
        )

    def chunked_loss(params, tokens, labels):
        h = trunk.apply({"params": params["trunk"]}, tokens, True)
        b, s, d = h.shape
        n = s // CHUNK
        hc = jnp.swapaxes(h.reshape(b, n, CHUNK, d), 0, 1)
        lc = jnp.swapaxes(labels.reshape(b, n, CHUNK), 0, 1)
        w = params["head"]["kernel"]
        bias = params["head"]["bias"]

        @jax.checkpoint
        def body(acc, xs):
            xh, xl = xs
            logits = xh.astype(jnp.float32) @ w + bias
            ce = jnp.sum(
                optax.softmax_cross_entropy_with_integer_labels(
                    logits, xl
                )
            )
            return acc + ce, None

        total, _ = jax.lax.scan(body, jnp.float32(0.0), (hc, lc))
        return total / (b * s)

    return init_fn, (chunked_loss if chunked else full_loss)


def run_config(cfg, batch, seq_len, chunked, steps=20, warmup=3):
    init_fn, loss_fn = build(cfg, chunked)
    opt = optax.adam(3e-4)
    tokens = synthetic_lm_tokens(
        batch * 2, seq_len, vocab=cfg.vocab, branching=4, seed=0
    )

    @jax.jit
    def step(params, opt_state, feats, labels):
        loss, grads = jax.value_and_grad(loss_fn)(params, feats, labels)
        updates, opt_state = opt.update(grads, opt_state, params)
        return optax.apply_updates(params, updates), opt_state, loss

    import statistics

    try:
        params = init_fn(
            jax.random.PRNGKey(0), jnp.asarray(tokens[:1, :seq_len])
        )
        opt_state = opt.init(params)
        # Per-step float(loss) materialization, median over steps: a
        # host read per step serialises dispatch, so rates from this
        # probe are comparable WITHIN a run, not against the
        # async-pipelined validate_flagship numbers.
        times = []
        for i in range(warmup + steps):
            sl = slice((i % 2) * batch, (i % 2) * batch + batch)
            t0 = time.perf_counter()
            params, opt_state, loss = step(
                params, opt_state,
                jnp.asarray(tokens[sl, :-1]),
                jnp.asarray(tokens[sl, 1:]),
            )
            loss_value = float(loss)
            times.append(time.perf_counter() - t0)
        dt = statistics.median(times[warmup:])
        stats = jax.local_devices()[0].memory_stats() or {}
        return {
            "tokens_per_sec": round(batch * seq_len / dt, 1),
            "step_time_ms": round(dt * 1e3, 1),
            "last_loss": round(loss_value, 4),
            "peak_bytes_in_use": stats.get("peak_bytes_in_use"),
        }
    except Exception as e:  # OOM etc.: record, don't die
        return {"error": type(e).__name__ + ": " + str(e)[:160]}


def main():
    assert jax.default_backend() != "cpu", jax.default_backend()
    seq_len = 8192
    cfg = tlm.flagship_config(max_len=seq_len)
    out = {"seq_len": seq_len, "chunk": CHUNK, "configs": {}}
    for name, batch, chunked in (
        ("full_head_b2", 2, False),
        ("chunked_head_b2", 2, True),
        ("full_head_b4", 4, False),
        ("chunked_head_b4", 4, True),
    ):
        out["configs"][name] = run_config(cfg, batch, seq_len, chunked)
        print(name, out["configs"][name], file=sys.stderr, flush=True)
    print(json.dumps(out, indent=1))


if __name__ == "__main__":
    main()
