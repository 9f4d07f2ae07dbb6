"""Plain reference of the LFM2 expert decoder and its training step.

Forward, next-token cross-entropy, gradients (`jax.grad` of the plain
forward) and Adam in straightforward `jax.numpy`: float32 everywhere,
`jax.default_matmul_precision("highest")`, no kernel, no flax `apply`, no
optax, nothing of `elasticdl_tpu/layers` or `elasticdl_tpu/models`. Written
from the equations of the HF `lfm2_moe` model (ISSUE 38 lists them):

    h = h + operator(RMSNorm(h)); h = h + ffn(RMSNorm(h)); after the last
    layer RMSNorm; logits through the embedding table; no bias.
    conv            B, C, x = split(in_proj(u), 3); z = B * x;
                    c_t = sum_j w[j] * z_{t-L+1+j} a channel, zeros before
                    the sequence; out_proj(C * c)
    full_attention  q, k, v projections; RMSNorm over each head of q and of
                    k; rotary over the whole head (x cos + rotate_half(x)
                    sin, theta^(-2i/d)); causal softmax attention at scale
                    d^-0.5, each key/value head serving heads / kv query
                    heads; out_proj
    dense ffn       w2(silu(w1 x) * w3 x)      (layers before
                                               num_dense_layers)
    routed ffn      s = sigmoid(W x); the top k of s + bias; weights s at
                    the chosen over (their sum + 1e-6), times the scaling
                    factor; experts w2(silu(w1 x) * w3 x); no shared expert.
                    Under `force_load_balancing` W x is replaced in the
                    forward pass by seeded uniform noise (Megatron-Core's
                    benchmark mode)

Not as the program computes it: the convolution is a sum of shifted
products; the experts are a loop over the held experts, each over every
token under a dense [S, E] gate matrix (no sort, no blocks); attention is
whole-row softmax by blocks of queries; the loss by blocks of tokens. It is
given the program's share: the experts `experts_held` of each routed layer
(what the others would add is left out) and the vocabulary slice.

Inputs come from the seed alone: the records through the benchmark's own
generator, the initial weights through the program's own initialiser
(`model.init` under the trainer's key schedule).

`--fault no_routed` is a second control, a planted fault in float32: the
routed experts add nothing.

`--precision fp8` is the control, one step below the stated bfloat16: both
operands of every matrix product, forward and backward, rounded to fp8
under per-tensor absmax scales (`references/lm_flagship.py:_fp8_product`).

    python benchmark/references/lfm2_moe.py --config <file> --seed 3 \
        --minibatch 2 --steps 8,16 [--precision float32]
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


def _block(total, limit):
    """The largest divisor of `total` that is at most `limit`."""
    size = min(total, limit)
    while total % size:
        size -= 1
    return size


def make_loss(model_cfg, precision, fault=None):
    """loss(params, buffers, tokens [S], labels [S], row, rows) for ONE
    sequence, row `row` of a batch of `rows`.
    `fault="no_routed"` plants a fault for a control run: the routed
    experts add nothing."""
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

    c = model_cfg
    layer_types = list(c["layer_types"])
    eps = float(c["norm_eps"])
    heads, kv = int(c["num_attention_heads"]), int(c["num_key_value_heads"])
    dim = int(c["hidden_size"]) // heads
    theta = float(c["rope_theta"])

    def rms_norm(x, weight):
        return x * jax.lax.rsqrt(
            jnp.mean(x * x, -1, keepdims=True) + eps) * weight

    def gated_mlp(x, w1, w3, w2):
        return mm("sf,fd->sd", jax.nn.silu(mm("sd,df->sf", x, w1))
                  * mm("sd,df->sf", x, w3), w2)

    def short_conv(u, p):
        s = u.shape[0]
        b, cc, x = jnp.split(
            mm("sd,df->sf", u, p["in_proj"]["kernel"]), 3, axis=-1)
        z = b * x
        taps = p["conv_kernel"]                       # [L, d]
        n = taps.shape[0]
        padded = jnp.concatenate(
            [jnp.zeros((n - 1, z.shape[1]), z.dtype), z])
        conv = sum(padded[j:j + s] * taps[j] for j in range(n))
        if "conv_bias" in p:
            conv = conv + p["conv_bias"]
        return mm("sf,fd->sd", cc * conv, p["out_proj"]["kernel"])

    def turned(x):
        """x [S, H, d] by its position: x cos + rotate_half(x) sin."""
        s = x.shape[0]
        inv_freq = 1.0 / theta ** (jnp.arange(0, dim, 2) / dim)
        angles = jnp.arange(s)[:, None] * inv_freq[None]
        angles = jnp.concatenate([angles, angles], -1)[:, None, :]
        half = jnp.concatenate(
            [-x[..., dim // 2:], x[..., :dim // 2]], axis=-1)
        return x * jnp.cos(angles) + half * jnp.sin(angles)

    def attention(x, p):
        s = x.shape[0]
        per = heads // kv
        q = mm("sd,dhe->she", x, p["q_proj"]["kernel"])
        k = mm("sd,dge->sge", x, p["k_proj"]["kernel"])
        v = mm("sd,dge->sge", x, p["v_proj"]["kernel"])
        q = turned(rms_norm(q, p["q_layernorm"])).reshape(s, kv, per, dim)
        k = turned(rms_norm(k, p["k_layernorm"]))
        n = _block(s, QUERY_BLOCK)

        @jax.checkpoint
        def rows(args):
            q_rows, first = args
            scores = mm("qgre,kge->grqk", q_rows, k) * dim ** -0.5
            seen = (first + jnp.arange(n))[:, None] >= jnp.arange(s)[None]
            weights = jax.nn.softmax(
                jnp.where(seen, scores, -1e30), axis=-1)
            return mm("grqk,kge->qgre", weights, v)

        out = jax.lax.map(
            rows, (q.reshape(s // n, n, kv, per, dim),
                   jnp.arange(0, s, n)))
        return mm("sf,fd->sd", out.reshape(s, heads * dim),
                  p["out_proj"]["kernel"])

    def experts(x, p, bias, noise):
        k = int(c["num_experts_per_tok"])
        first, count = c.get("experts_held") or (0, int(c["num_experts"]))
        width = int(c["moe_intermediate_size"])
        logits = mm("sd,ed->se", x, p["router"])
        if noise is not None:
            # The noise in the forward pass, the gradient to the router.
            logits = noise + logits - jax.lax.stop_gradient(logits)
        scores = jax.nn.sigmoid(logits)
        _, chosen = jax.lax.top_k(scores + bias, k)
        weights = jnp.take_along_axis(scores, chosen, axis=-1)
        if c["norm_topk_prob"]:
            weights = weights / (jnp.sum(weights, -1, keepdims=True) + 1e-6)
        weights = weights * float(c["routed_scaling_factor"])
        gates = jnp.sum(
            jax.nn.one_hot(chosen, scores.shape[1], dtype=x.dtype)
            * weights[..., None], axis=1)             # [S, E], dense
        out = jnp.zeros_like(x)
        for e in range(0 if fault == "no_routed" else count):
            # The program keeps w1 and w3 side by side in one matrix.
            w13 = p["w_gate_up"][e]
            out = out + gates[:, first + e, None] * gated_mlp(
                x, w13[:, :width], w13[:, width:], p["w_down"][e])
        return out

    def layer(h, i, p, bias, noise):
        u = rms_norm(h, p["operator_norm"]["weight"])
        if layer_types[i] == "conv":
            h = h + short_conv(u, p["conv"])
        else:
            h = h + attention(u, p["self_attn"])
        u = rms_norm(h, p["ffn_norm"]["weight"])
        f = p["feed_forward"]
        if i < int(c["num_dense_layers"]):
            return h + gated_mlp(u, f["w1"]["kernel"], f["w3"]["kernel"],
                                 f["w2"]["kernel"])
        return h + experts(u, f, bias, noise)

    def hidden(params, buffers, tokens, row, rows):
        """The last norm's output [S, d] for one sequence."""
        s = tokens.shape[0]
        h = params["embed_tokens"]["embedding"][tokens]
        for i in range(len(layer_types)):
            name = f"layers_{i}"
            bias = buffers.get(name, {}).get("feed_forward", {}).get(
                "e_score_correction_bias")
            noise = None
            if i >= int(c["num_dense_layers"]) and c.get(
                    "force_load_balancing"):
                # Row `row` of the batch's noise: layer i's seed is i.
                noise = jax.random.uniform(
                    jax.random.PRNGKey(i),
                    (rows, s, int(c["num_experts"])))[row]
            h = jax.checkpoint(layer, static_argnums=(1,))(
                h, i, params[name], bias, noise)
        return rms_norm(h, params["embedding_norm"]["weight"])

    def logits(params, buffers, tokens, row=0, rows=1):
        """[S, V] of one sequence, whole (the tests' sizes)."""
        return mm("sd,vd->sv", hidden(params, buffers, tokens, row, rows),
                  params["embed_tokens"]["embedding"])

    def loss(params, buffers, tokens, labels, row=0, rows=1):
        s = tokens.shape[0]
        table = params["embed_tokens"]["embedding"]
        h = hidden(params, buffers, tokens, row, rows)
        n = _block(s, LOSS_BLOCK)

        @jax.checkpoint
        def picked(args):
            rows, want = args
            logp = jax.nn.log_softmax(
                mm("sd,vd->sv", rows, table), axis=-1)
            return jnp.sum(jnp.take_along_axis(logp, want[:, None], -1))

        return -jnp.sum(jax.lax.map(
            picked, (h.reshape(s // n, n, -1), labels.reshape(s // n, n))
        )) / s

    loss.logits = logits
    return loss


def make_step(model_cfg, opt, precision, fault=None):
    """step(params, m, v, count, buffers, tokens [B, S], labels [B, S]) ->
    (loss before the update, params, m, v): batch mean of the sequence
    losses, its gradient, one Adam update (Kingma & Ba, bias-corrected,
    eps outside the square root)."""
    import jax
    import jax.numpy as jnp

    loss_one = make_loss(model_cfg, precision, fault)
    lr, b1, b2, eps = (float(opt[k]) for k in
                       ("learning_rate", "beta_1", "beta_2", "epsilon"))

    def batch_loss(params, buffers, tokens, labels):
        rows = tokens.shape[0]
        return jnp.mean(jax.lax.map(
            lambda row: loss_one(params, buffers, *row, rows),
            (tokens, labels, jnp.arange(rows))))

    @functools.partial(jax.jit, donate_argnums=(0, 1, 2))
    def step(params, m, v, count, buffers, tokens, labels):
        loss, grads = jax.value_and_grad(batch_loss)(
            params, buffers, tokens, labels)
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


def initial_variables(model_def, seed, first_row):
    """(params, buffers) the job starts from: the program's `model.init`
    under the trainer's key schedule (PRNGKey(seed), one split, the second
    half initialises; from one row)."""
    import jax
    import jax.numpy as jnp

    from elasticdl_tpu.common.model_utils import load_module

    model = load_module(model_def).custom_model()
    _, init_rng = jax.random.split(jax.random.PRNGKey(seed))
    variables = dict(jax.jit(
        lambda rng, row: model.init(
            {"params": rng, "dropout": rng}, row, training=False)
    )(init_rng, jnp.asarray(first_row[:1])))

    def plain(tree):
        return jax.tree_util.tree_map(
            lambda a: jnp.asarray(a, jnp.float32), tree)
    return plain(variables["params"]), plain(variables.get("buffers", {}))


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
    params = m = v = buffers = None
    with jax.default_matmul_precision("highest"):
        for k, (tokens, labels) in enumerate(datagen.batches(
                0, last, minibatch, seed, config["data"])):
            if params is None:
                params, buffers = initial_variables(
                    config["model_def"], seed, tokens)
                m = jax.tree_util.tree_map(jnp.zeros_like, params)
                v = jax.tree_util.tree_map(jnp.zeros_like, params)
                jax.block_until_ready(params)
                clock.append(time.time())
            loss, params, m, v = step(
                params, m, v, jnp.asarray(k, jnp.float32), buffers,
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
    parser.add_argument("--fault", default=None, choices=("no_routed",),
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
