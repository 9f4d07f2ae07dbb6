"""Plain reference of the Granite 4.0-H dense hybrid decoder and its
training step.

Forward, next-token cross-entropy, gradients (`jax.grad` of the plain
forward) and Adam in straightforward `jax.numpy`: float32 everywhere,
`jax.default_matmul_precision("highest")`, no kernel, no flax `apply`, no
optax, nothing of `elasticdl_tpu/layers` or `elasticdl_tpu/models`. Written
from the equations of the HF `granitemoehybrid` model with no routed
experts (ISSUE 46 lists them):

    h = embedding[ids] * embedding_multiplier
    h = h + residual_multiplier * mixer(RMSNorm(h)), by `layer_types`:
      mamba      z, xBC, dt = in_proj(u); xBC = silu(conv1d(xBC) + bias),
                 causal and depthwise; x [H, P], B [G, N], C [G, N] =
                 split(xBC); dt = softplus(dt + dt_bias); A = -exp(A_log);
                 s_t = exp(dt_t A) s_{t-1} + dt_t B_t x_t^T; y_t = C_t s_t
                 + D x_t; out_proj(weight * groupRMSNorm(y * silu(z)))
      attention  causal softmax attention, each key/value head serving
                 heads / kv query heads, scores times attention_multiplier
                 (NOT head_dim^-0.5), no position signal
    h = h + residual_multiplier * output_linear(silu(g) * u),
        g, u = split(input_linear(RMSNorm(h)))
    logits = (RMSNorm(h) @ embedding^T) / logits_scaling    (tied head)

Not as the program computes it: the state-space scan is the recurrence
itself, one token a step (`lax.scan` over time, checkpointed by stretches
of `mamba_chunk_size` tokens so that the backward pass fits); attention is
whole-row softmax by blocks of queries; the loss by blocks of tokens; every
layer is checkpointed (12.35 GB of float32 parameters, gradients and Adam
moments leave little room). It is given the program's share: the
vocabulary slice.

Inputs come from the seed alone: the records through the benchmark's own
generator, the initial weights through the program's own initialiser
(`model.init` under the trainer's key schedule).

`--fault no_carry` is a second control, a planted fault of the mechanism in
float32: the state entering every stretch of `mamba_chunk_size` tokens is
zero, which is what a chunked scan computes that loses its recurrence
between chunks.

`--precision fp8` is the control, one step below the stated bfloat16: both
operands of every matrix product, forward and backward, rounded to fp8
under per-tensor absmax scales (`references/lm_flagship.py:_fp8_product`).
The recurrence's own update (an outer product and a contraction a token,
elementwise in this form) stays float32 there too.

    python benchmark/references/granite_hybrid.py --config <file> --seed 3 \
        --minibatch 1 --steps 8,16 [--precision float32]
prints one JSON line {"losses": {"8": ..., "16": ...}, ...}; the loss of
step k is the loss before update k, as the worker logs it.
"""

import argparse
import functools
import json
import os
import sys
import time

HERE = os.path.dirname(os.path.abspath(__file__))
BENCH_DIR = os.path.dirname(HERE)
REPO = os.path.dirname(BENCH_DIR)
for _path in (REPO, BENCH_DIR):
    if _path not in sys.path:
        sys.path.insert(0, _path)

QUERY_BLOCK = 256
LOSS_BLOCK = 2048
FAULTS = ("no_carry",)


def _block(total, limit):
    """The largest divisor of `total` that is at most `limit`."""
    size = min(total, limit)
    while total % size:
        size -= 1
    return size


def make_loss(model_cfg, precision, fault=None):
    """loss(params, tokens [S], labels [S]) for ONE sequence.
    `fault="no_carry"` plants a fault for a control run: no state crosses
    from one stretch of `mamba_chunk_size` tokens into the next."""
    import jax
    import jax.numpy as jnp

    from lib import cell

    if precision == "float32":
        def mm(spec, a, b):
            return jnp.einsum(spec, a, b,
                              precision=jax.lax.Precision.HIGHEST)
    elif precision == "fp8":
        mm = cell.load_module("references", "lm_flagship")._fp8_product()
    else:
        raise ValueError(f"unknown precision {precision!r}")
    if fault not in (None, *FAULTS):
        raise ValueError(f"unknown fault {fault!r}")

    c = model_cfg
    layer_types = c["layer_types"]
    eps = float(c["rms_norm_eps"])
    residual = float(c["residual_multiplier"])

    def rms_norm(x, weight):
        return x * jax.lax.rsqrt(
            jnp.mean(x * x, -1, keepdims=True) + eps) * weight

    def mamba(u, p):
        heads, dim = int(c["mamba_n_heads"]), int(c["mamba_d_head"])
        groups, state = int(c["mamba_n_groups"]), int(c["mamba_d_state"])
        inner, s = heads * dim, u.shape[0]
        proj = mm("sd,df->sf", u, p["in_proj"]["kernel"])
        z = proj[:, :inner]
        xbc = proj[:, inner:inner + inner + 2 * groups * state]
        dt = proj[:, -heads:]
        taps = p["conv_kernel"]                       # [K, C]
        k = taps.shape[0]
        padded = jnp.concatenate(
            [jnp.zeros((k - 1, xbc.shape[1]), xbc.dtype), xbc])
        conv = sum(padded[j:j + s] * taps[j] for j in range(k))
        if c["mamba_conv_bias"]:
            conv = conv + p["conv_bias"]
        xbc = jax.nn.silu(conv)
        x = xbc[:, :inner]                             # [S, H * P]
        per = heads // groups
        b = xbc[:, inner:inner + groups * state].reshape(s, groups, state)
        cc = xbc[:, inner + groups * state:].reshape(s, groups, state)
        dt = jax.nn.softplus(dt + p["dt_bias"])
        a = -jnp.exp(p["A_log"]).reshape(groups, per)

        # The state of head (g, r) is h[g, r]: [P, N]; the heads of a group
        # read the group's B_t and C_t.
        def token(h, row):
            x_t, b_t, c_t, dt_t = row
            x_t = x_t.reshape(groups, per, dim)
            dt_t = dt_t.reshape(groups, per)
            h = jnp.exp(dt_t * a)[..., None, None] * h + (
                (dt_t[..., None] * x_t)[..., None]
                * b_t[:, None, None, :])
            y_t = jnp.sum(h * c_t[:, None, None, :], axis=-1)
            return h, y_t.reshape(inner)

        @jax.checkpoint
        def stretch(h, rows):
            if fault == "no_carry":
                h = jnp.zeros_like(h)
            return jax.lax.scan(token, h, rows)

        n = _block(s, int(c["mamba_chunk_size"]))
        rows = jax.tree_util.tree_map(
            lambda v: v.reshape(s // n, n, *v.shape[1:]), (x, b, cc, dt))
        _, y = jax.lax.scan(
            stretch, jnp.zeros((groups, per, dim, state), jnp.float32),
            rows)
        y = y.reshape(s, inner) + x * jnp.repeat(p["D"], dim)
        y = y * jax.nn.silu(z)
        y = rms_norm(y.reshape(s, groups, inner // groups), 1.0)
        return mm("sf,fd->sd", y.reshape(s, inner) * p["norm_weight"],
                  p["out_proj"]["kernel"])

    def attention(x, p):
        s = x.shape[0]
        kv = int(c["num_key_value_heads"])
        per = int(c["num_attention_heads"]) // kv
        dim = int(c["hidden_size"]) // int(c["num_attention_heads"])
        scale = float(c["attention_multiplier"])
        q = mm("sd,dhe->she", x, p["q_proj"]["kernel"]).reshape(
            s, kv, per, dim)
        k = mm("sd,dge->sge", x, p["k_proj"]["kernel"])
        v = mm("sd,dge->sge", x, p["v_proj"]["kernel"])
        n = _block(s, QUERY_BLOCK)

        @jax.checkpoint
        def rows(args):
            q_rows, first = args
            scores = mm("qgre,kge->grqk", q_rows, k) * scale
            seen = (first + jnp.arange(n))[:, None] >= jnp.arange(s)[None]
            weights = jax.nn.softmax(
                jnp.where(seen, scores, -1e30), axis=-1)
            return mm("grqk,kge->qgre", weights, v)

        out = jax.lax.map(
            rows, (q.reshape(s // n, n, kv, per, dim),
                   jnp.arange(0, s, n)))
        return mm("sf,fd->sd", out.reshape(s, kv * per * dim),
                  p["o_proj"]["kernel"])

    def mlp(x, p):
        both = mm("sd,df->sf", x, p["input_linear"]["kernel"])
        width = both.shape[1] // 2
        return mm("sf,fd->sd",
                  jax.nn.silu(both[:, :width]) * both[:, width:],
                  p["output_linear"]["kernel"])

    def layer(x, kind, p):
        u = rms_norm(x, p["input_layernorm"]["weight"])
        if kind == "mamba":
            x = x + residual * mamba(u, p["mamba"])
        else:
            x = x + residual * attention(u, p["self_attn"])
        u = rms_norm(x, p["post_attention_layernorm"]["weight"])
        return x + residual * mlp(u, p["shared_mlp"])

    def loss(params, tokens, labels):
        s = tokens.shape[0]
        table = params["embed_tokens"]["embedding"]
        x = table[tokens] * float(c["embedding_multiplier"])
        for i, kind in enumerate(layer_types):
            x = jax.checkpoint(layer, static_argnums=(1,))(
                x, kind, params[f"layers_{i}"])
        x = rms_norm(x, params["norm"]["weight"])
        n = _block(s, LOSS_BLOCK)
        scaling = float(c["logits_scaling"])

        @jax.checkpoint
        def picked(args):
            rows, want = args
            logp = jax.nn.log_softmax(
                mm("sd,vd->sv", rows, table) / scaling, axis=-1)
            return jnp.sum(jnp.take_along_axis(logp, want[:, None], -1))

        return -jnp.sum(jax.lax.map(
            picked, (x.reshape(s // n, n, -1), labels.reshape(s // n, n))
        )) / s

    return loss


def make_step(model_cfg, opt, precision, fault=None):
    """step(params, m, v, count, tokens [B, S], labels [B, S]) -> (loss
    before the update, params, m, v): batch mean of the sequence losses,
    its gradient, one Adam update (Kingma & Ba, bias-corrected, eps outside
    the square root)."""
    import jax
    import jax.numpy as jnp

    loss_one = make_loss(model_cfg, precision, fault)
    lr, b1, b2, eps = (float(opt[k]) for k in
                       ("learning_rate", "beta_1", "beta_2", "epsilon"))

    def batch_loss(params, tokens, labels):
        return jnp.mean(jax.lax.map(
            lambda row: loss_one(params, *row), (tokens, labels)))

    @functools.partial(jax.jit, donate_argnums=(0, 1, 2))
    def step(params, m, v, count, tokens, labels):
        loss, grads = jax.value_and_grad(batch_loss)(params, tokens, labels)
        t = count + 1
        c1, c2 = 1 - b1 ** t, 1 - b2 ** t
        tree_map = jax.tree_util.tree_map
        m = tree_map(lambda a, g: b1 * a + (1 - b1) * g, m, grads)
        v = tree_map(lambda a, g: b2 * a + (1 - b2) * g * g, v, grads)
        params = tree_map(
            lambda p, a, b: p - lr * (a / c1) / (jnp.sqrt(b / c2) + eps),
            params, m, v)
        return loss, params, m, v

    return step


def initial_params(model_def, seed, first_row):
    """The parameters the job starts from: the program's `model.init`
    under the trainer's key schedule (PRNGKey(seed), one split, the second
    half initialises; from one row)."""
    import jax
    import jax.numpy as jnp

    from elasticdl_tpu.common.model_utils import load_module

    model = load_module(model_def).custom_model()
    _, init_rng = jax.random.split(jax.random.PRNGKey(seed))
    variables = jax.jit(
        lambda rng, row: model.init(
            {"params": rng, "dropout": rng}, row, training=False)
    )(init_rng, jnp.asarray(first_row[:1]))
    return jax.tree_util.tree_map(
        lambda a: jnp.asarray(a, jnp.float32), dict(variables["params"]))


timing = {}  # of the last losses() call: init, first step, the rest


def losses(config, seed, minibatch, steps, precision="float32",
           fault=None):
    """{step: loss} at the asked steps (1-based, as the worker counts)."""
    import jax
    import jax.numpy as jnp

    from lib import cell

    datagen = cell.load_module("datagen", config["datagen"])
    last = max(steps)
    step = make_step(config["model"], config["optimizer"], precision, fault)
    out = {}
    clock = [time.time()]
    timing["precision"] = precision
    params = m = v = None
    with jax.default_matmul_precision("highest"):
        for k, (tokens, labels) in enumerate(datagen.batches(
                0, last, minibatch, seed, config["data"])):
            if params is None:
                params = initial_params(config["model_def"], seed, tokens)
                m = jax.tree_util.tree_map(jnp.zeros_like, params)
                v = jax.tree_util.tree_map(jnp.zeros_like, params)
                jax.block_until_ready(params)
                clock.append(time.time())
            loss, params, m, v = step(
                params, m, v, jnp.asarray(k, jnp.float32),
                jnp.asarray(tokens, jnp.int32),
                jnp.asarray(labels, jnp.int32))
            if k == 0:
                jax.block_until_ready(loss)
                clock.append(time.time())
            if k + 1 in steps:
                out[k + 1] = float(loss)
    clock.append(time.time())
    timing.update(init_s=clock[1] - clock[0],
                  first_step_s=clock[2] - clock[1],
                  other_steps_s=clock[3] - clock[2])
    return out


def main(argv=None):
    parser = argparse.ArgumentParser(description=__doc__.split("\n")[0])
    parser.add_argument("--config", required=True)
    parser.add_argument("--seed", type=int, required=True)
    parser.add_argument("--minibatch", type=int, required=True)
    parser.add_argument("--steps", required=True,
                        help="comma-separated 1-based steps to report")
    parser.add_argument("--precision", default="float32",
                        choices=("float32", "fp8"))
    parser.add_argument("--fault", default=None, choices=FAULTS,
                        help="a planted fault, for a control run")
    args = parser.parse_args(argv)
    with open(args.config) as f:
        config = json.load(f)
    import jax

    from elasticdl_tpu.common.compile_cache import ensure_compile_cache

    ensure_compile_cache()
    steps = sorted({int(s) for s in args.steps.split(",")})
    got = losses(config, args.seed, args.minibatch, steps, args.precision,
                 args.fault)
    dev = jax.devices()[0]
    print(json.dumps({
        "losses": {str(k): v for k, v in got.items()},
        "precision": args.precision, "fault": args.fault, "timing": timing,
        "device": {"platform": dev.platform, "kind": dev.device_kind},
    }), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
