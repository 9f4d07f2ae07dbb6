"""Plain reference of the Mellum 2 expert decoder and its training step.

Forward, next-token cross-entropy, gradients (`jax.grad` of the plain
forward) and Adam in straightforward `jax.numpy`: float32 everywhere,
`jax.default_matmul_precision("highest")`, no kernel, no flax `apply`, no
optax, nothing of `elasticdl_tpu/layers`, `elasticdl_tpu/models` or
`elasticdl_tpu/ops`. Written from the equations of the HF `mellum` model
(ISSUE 51 lists them), layer l of kind `layer_types[l]`:

    h = h + Wo A_l(rope_l(qn(Wq u)), rope_l(kn(Wk u)), Wv u),  u = RMSNorm(h)
        qn, kn an RMSNorm over each head's channels; rope over the whole
        head by the row's position (x cos + rotate_half(x) sin); A softmax
        attention at scale d^-0.5, each key/value head serving heads / kv
        query heads, row r seeing column c iff
          sliding_attention:  c <= r and r - c < sliding_window
          full_attention:     c <= r
        rope_l, sliding_attention: inv_freq_i = theta^(-2i/d), unscaled;
        full_attention: YaRN. With n(t) = d ln(L0 / (2 pi t)) / (2 ln
        theta), low = floor(n(beta_fast)), high = ceil(n(beta_slow))
        clamped to [0, d - 1], ramp_i = clip((i - low) / (high - low), 0,
        1): inv_freq_i = (1 - ramp_i) theta^(-2i/d) + ramp_i theta^(-2i/d)
        / factor, and cos and sin both times `attention_factor`
    h = h + sum over the top k of w_e W2_e(silu(W1_e u') * W3_e u')
        p = softmax(Wr u') over all experts; the k largest; w = p over
        the sum of the chosen p. Under `force_load_balancing` Wr u' is
        replaced in the forward pass by seeded uniform noise
        (Megatron-Core's benchmark mode)
    last RMSNorm, the untied head, mean next-token cross-entropy.

Not as the program computes it: both masks are built from the two
predicates above pair by pair and applied to whole rows of scores, a block
of query rows at a time (no tile is skipped, under the window either); the
YaRN table comes from the formulas above, in this file; the experts are a
loop over the held experts, each over every row under a dense [rows, E]
gate matrix (no sort, no blocks); the loss by blocks of rows. It is given
the program's share: the experts `experts_held` of each layer (what the
others would add is left out) and the vocabulary slice.

Inputs come from the seed alone: the records through the benchmark's own
generator, the initial weights through the program's own initialiser
(`model.init` under the trainer's key schedule).

`--fault` plants one fault of one mechanism, in float32: `window_unseen`
(the windowed layers run causal: a row sees everything before it) and
`yarn_off` (the full layers turn by the default table, unscaled).

`--precision fp8` is the control, one step below the stated bfloat16: both
operands of every matrix product, forward and backward, rounded to fp8
under per-tensor absmax scales (`references/lm_flagship.py:_fp8_product`).

    python benchmark/references/mellum_moe.py --config <file> --seed 3 \
        --minibatch 1 --steps 8,16 [--precision float32]
prints one JSON line {"losses": {"8": ..., "16": ...}, ...}; the loss of
step k is the loss before update k, as the worker logs it.
"""

import argparse
import functools
import json
import math
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
WINDOWED, FULL = "sliding_attention", "full_attention"
FAULTS = ("window_unseen", "yarn_off")


def _block(total, limit):
    """The largest divisor of `total` that is at most `limit`."""
    size = min(total, limit)
    while total % size:
        size -= 1
    return size


def rope_table(rope, dim):
    """(inv_freq [dim / 2] as float64, the factor of cos and sin) of one
    entry of `rope_parameters`, from the formulas in the docstring."""
    import numpy as np

    theta = float(rope["rope_theta"])
    own = np.array([theta ** (-2.0 * i / dim) for i in range(dim // 2)])
    if rope.get("rope_type", "default") == "default":
        return own, 1.0
    factor = float(rope["factor"])
    length = float(rope["original_max_position_embeddings"])

    def n(turns):
        return dim * math.log(length / (2 * math.pi * turns)) / (
            2 * math.log(theta))

    low = max(math.floor(n(float(rope["beta_fast"]))), 0)
    high = min(math.ceil(n(float(rope["beta_slow"]))), dim - 1)
    ramp = np.array([
        min(max((i - low) / (high - low), 0.0), 1.0)
        for i in range(dim // 2)])
    return ((1 - ramp) * own + ramp * own / factor,
            float(rope["attention_factor"]))


def make_loss(model_cfg, precision, fault=None):
    """loss(params, buffers, tokens [S], labels [S], row, rows) for ONE
    sequence, row `row` of a batch of `rows`."""
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
    if fault not in (None,) + FAULTS:
        raise ValueError(f"unknown fault {fault!r}")

    c = model_cfg
    layer_types = list(c["layer_types"])
    eps = float(c["rms_norm_eps"])
    heads, kv = int(c["num_attention_heads"]), int(c["num_key_value_heads"])
    dim = int(c["head_dim"])
    window = int(c["sliding_window"])
    ropes = {kind: rope_table(rope, dim)
             for kind, rope in c["rope_parameters"].items()}
    if fault == "yarn_off":
        ropes[FULL] = ropes[WINDOWED]

    def rms_norm(x, weight):
        return x * jax.lax.rsqrt(
            jnp.mean(x * x, -1, keepdims=True) + eps) * weight

    def gated_mlp(x, w1, w3, w2):
        return mm("sf,fd->sd", jax.nn.silu(mm("sd,df->sf", x, w1))
                  * mm("sd,df->sf", x, w3), w2)

    def turned(x, kind):
        """x [S, H, d] by its position: x cos + rotate_half(x) sin."""
        inv_freq, factor = ropes[kind]
        angles = jnp.arange(x.shape[0], dtype=jnp.float32)[:, None] \
            * jnp.asarray(inv_freq, jnp.float32)[None]
        angles = jnp.concatenate([angles, angles], -1)[:, None, :]
        half = jnp.concatenate(
            [-x[..., dim // 2:], x[..., :dim // 2]], axis=-1)
        return (x * (jnp.cos(angles) * factor)
                + half * (jnp.sin(angles) * factor))

    def may_attend(r, col, kind):
        """The mask, pair by pair: rows r [n, 1], columns col [1, S]."""
        if kind == FULL or fault == "window_unseen":
            return col <= r
        return (col <= r) & (r - col < window)

    def attention(x, p, kind):
        s = x.shape[0]
        per = heads // kv
        q = mm("sd,dhe->she", x, p["q_proj"]["kernel"])
        k = mm("sd,dge->sge", x, p["k_proj"]["kernel"])
        v = mm("sd,dge->sge", x, p["v_proj"]["kernel"])
        q = turned(rms_norm(q, p["q_norm"]), kind).reshape(s, kv, per, dim)
        k = turned(rms_norm(k, p["k_norm"]), kind)
        n = _block(s, QUERY_BLOCK)

        @jax.checkpoint
        def rows(args):
            q_rows, first = args
            scores = mm("qgre,kge->grqk", q_rows, k) * dim ** -0.5
            seen = may_attend((first + jnp.arange(n))[:, None],
                              jnp.arange(s)[None], kind)
            weights = jax.nn.softmax(
                jnp.where(seen, scores, -1e30), axis=-1)
            return mm("grqk,kge->qgre", weights, v)

        out = jax.lax.map(
            rows, (q.reshape(s // n, n, kv, per, dim),
                   jnp.arange(0, s, n)))
        return mm("sf,fd->sd", out.reshape(s, heads * dim),
                  p["o_proj"]["kernel"])

    def experts(x, p, noise):
        k = int(c["num_experts_per_tok"])
        first, count = c.get("experts_held") or (0, int(c["num_experts"]))
        width = int(c["moe_intermediate_size"])
        logits = mm("sd,ed->se", x, p["router"])
        if noise is not None:
            # The noise in the forward pass, the gradient to the router.
            logits = noise + logits - jax.lax.stop_gradient(logits)
        scores = jax.nn.softmax(logits, axis=-1)
        _, chosen = jax.lax.top_k(scores, k)
        weights = jnp.take_along_axis(scores, chosen, axis=-1)
        if c["norm_topk_prob"]:
            weights = weights / jnp.sum(weights, -1, keepdims=True)
        gates = jnp.sum(
            jax.nn.one_hot(chosen, scores.shape[1], dtype=x.dtype)
            * weights[..., None], axis=1)             # [S, E], dense

        @jax.checkpoint
        def expert_part(e):
            # The program keeps w1 and w3 side by side in one matrix.
            w13 = p["w_gate_up"][e]
            gate = jnp.take(gates, first + e, axis=1)[:, None]
            return gate * gated_mlp(
                x, w13[:, :width], w13[:, width:], p["w_down"][e])

        # One held expert after another, each over every row: a loop the
        # compiler sees once, each pass recomputed in the backward.
        return jax.lax.scan(
            lambda out, e: (out + expert_part(e), None),
            jnp.zeros_like(x), jnp.arange(count))[0]

    def layer(h, kind, p, noise):
        h = h + attention(
            rms_norm(h, p["input_layernorm"]["weight"]), p["self_attn"],
            kind)
        return h + experts(
            rms_norm(h, p["post_attention_layernorm"]["weight"]), p["mlp"],
            noise)

    def hidden(params, tokens, row, rows):
        """The last norm's output [S, d] for one sequence."""
        s = tokens.shape[0]
        h = params["embed_tokens"]["embedding"][tokens]
        for i, kind in enumerate(layer_types):
            noise = None
            if c.get("force_load_balancing"):
                # Row `row` of the batch's noise: layer i's seed is i.
                noise = jax.random.uniform(
                    jax.random.PRNGKey(i),
                    (rows, s, int(c["num_experts"])))[row]
            h = jax.checkpoint(layer, static_argnums=(1,))(
                h, kind, params[f"layers_{i}"], noise)
        return rms_norm(h, params["norm"]["weight"])

    def logits(params, buffers, tokens, row=0, rows=1):
        """[S, V] of one sequence, whole (the tests' sizes)."""
        return mm("sd,dv->sv", hidden(params, tokens, row, rows),
                  params["lm_head"]["kernel"])

    def loss(params, buffers, tokens, labels, row=0, rows=1):
        s = tokens.shape[0]
        head = params["lm_head"]["kernel"]
        h = hidden(params, tokens, row, rows)
        n = _block(s, LOSS_BLOCK)

        @jax.checkpoint
        def picked(args):
            rows_, want = args
            logp = jax.nn.log_softmax(mm("sd,dv->sv", rows_, head), axis=-1)
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
