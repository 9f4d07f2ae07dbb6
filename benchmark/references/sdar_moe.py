"""Plain reference of the SDAR expert decoder under masked block-diffusion
training, and its training step.

Forward, the weighted cross-entropy, gradients (`jax.grad` of the plain
forward) and Adam in straightforward `jax.numpy`: float32 everywhere,
`jax.default_matmul_precision("highest")`, no kernel, no flax `apply`, no
optax, nothing of `elasticdl_tpu/layers`, `elasticdl_tpu/models` or
`elasticdl_tpu/ops`. Written from the equations of the HF `sdar_moe` model
and of the objective (ISSUE 43 lists them):

    a record x_0 of L tokens, a level t[k] a block of b positions, a draw
    u[p] a position: x_t[p] = MASK where u[p] < t[p // b], else x_0[p].
    The model reads [x_0; x_t] as one sequence of 2L rows, both halves at
    positions 0 .. L - 1, through every layer:
    h = h + Wo A(rope(qn(Wq u)), rope(kn(Wk u)), Wv u),  u = RMSNorm(h)
        qn, kn an RMSNorm over each head's channels; rope over the whole
        head by the row's position (x cos + rotate_half(x) sin,
        theta^(-2i/d)); A softmax attention at scale d^-0.5, each
        key/value head serving heads / kv query heads, row r seeing
        column c iff (beta = position // b)
          r clean,  c clean:   beta(c) <= beta(r)
          r noised, c clean:   beta(c) <  beta(r)
          r noised, c noised:  beta(c) == beta(r)
          r clean,  c noised:  never
    h = h + sum over the top k of w_e W2_e(silu(W1_e u') * W3_e u')
        p = softmax(Wr u') over all experts; the k largest; w = p over
        the sum of the chosen p. Under `force_load_balancing` Wr u' is
        replaced in the forward pass by seeded uniform noise
        (Megatron-Core's benchmark mode)
    last RMSNorm and the untied head over the noised half;
    loss = 1 / L * sum over the masked p of CE(logits[p], x_0[p]) / t[p // b]

Not as the program computes it: the mask is built from the four lines
above pair by pair and applied to whole rows of scores, a block of query
rows at a time (no tile is skipped; the clean rows' scores are made over
the clean columns alone, since the fourth line gives them no other); the experts are a loop over the
held experts, each over every row under a dense [rows, E] gate matrix (no
sort, no blocks); the noising and the weights are made here, from t and u;
the loss by blocks of rows. It is given the program's share: the experts
`experts_held` of each layer (what the others would add is left out) and
the vocabulary slice.

Inputs come from the seed alone: the records through the benchmark's own
generator, the initial weights through the program's own initialiser
(`model.init` under the trainer's key schedule).

`--fault own_block_unseen` is a second control, a planted fault in
float32: a noised row does not see its own block (the third line of the
mask reads "never"), the smallest fault of the mechanism.

`--precision fp8` is the control, one step below the stated bfloat16: both
operands of every matrix product, forward and backward, rounded to fp8
under per-tensor absmax scales (`references/lm_flagship.py:_fp8_product`).

    python benchmark/references/sdar_moe.py --config <file> --seed 3 \
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
FAULTS = ("own_block_unseen",)


def _block(total, limit):
    """The largest divisor of `total` that is at most `limit`."""
    size = min(total, limit)
    while total % size:
        size -= 1
    return size


def make_loss(model_cfg, precision, fault=None):
    """loss(params, buffers, tokens [L], t [L / b], u [L], row, rows) for
    ONE record, row `row` of a batch of `rows`."""
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
    eps = float(c["rms_norm_eps"])
    heads, kv = int(c["num_attention_heads"]), int(c["num_key_value_heads"])
    dim = int(c["head_dim"])
    theta = float(c["rope_theta"])
    b = int(c["block_length"])
    mask_id = int(c["mask_token_id"])

    def rms_norm(x, weight):
        return x * jax.lax.rsqrt(
            jnp.mean(x * x, -1, keepdims=True) + eps) * weight

    def gated_mlp(x, w1, w3, w2):
        return mm("sf,fd->sd", jax.nn.silu(mm("sd,df->sf", x, w1))
                  * mm("sd,df->sf", x, w3), w2)

    def turned(x, positions):
        """x [S, H, d] by its row's position: x cos + rotate_half(x) sin."""
        inv_freq = 1.0 / theta ** (jnp.arange(0, dim, 2) / dim)
        angles = positions[:, None] * inv_freq[None]
        angles = jnp.concatenate([angles, angles], -1)[:, None, :]
        half = jnp.concatenate(
            [-x[..., dim // 2:], x[..., :dim // 2]], axis=-1)
        return x * jnp.cos(angles) + half * jnp.sin(angles)

    def may_attend(r, col, length):
        """The mask, pair by pair: rows r [n, 1], columns col [1, 2L]."""
        r_noised, c_noised = r >= length, col >= length
        beta_r = jnp.where(r_noised, r - length, r) // b
        beta_c = jnp.where(c_noised, col - length, col) // b
        own = False if fault == "own_block_unseen" else beta_c == beta_r
        return jnp.where(
            r_noised,
            jnp.where(c_noised, own, beta_c < beta_r),
            jnp.where(c_noised, False, beta_c <= beta_r))

    def attention(x, p, positions):
        s = x.shape[0]
        per = heads // kv
        q = mm("sd,dhe->she", x, p["q_proj"]["kernel"])
        k = mm("sd,dge->sge", x, p["k_proj"]["kernel"])
        v = mm("sd,dge->sge", x, p["v_proj"]["kernel"])
        q = turned(rms_norm(q, p["q_norm"]), positions).reshape(
            s, kv, per, dim)
        k = turned(rms_norm(k, p["k_norm"]), positions)
        length = s // 2
        n = _block(length, QUERY_BLOCK)

        def half(first_row, columns):
            """Rows [first_row, first_row + L) against columns [0,
            columns): whole rows of scores under the mask, n rows at a
            time."""
            @jax.checkpoint
            def rows(args):
                q_rows, first = args
                scores = mm("qgre,kge->grqk", q_rows, k[:columns]) \
                    * dim ** -0.5
                seen = may_attend((first + jnp.arange(n))[:, None],
                                  jnp.arange(columns)[None], length)
                weights = jax.nn.softmax(
                    jnp.where(seen, scores, -1e30), axis=-1)
                return mm("grqk,kge->qgre", weights, v[:columns])

            return jax.lax.map(rows, (
                q[first_row:first_row + length].reshape(
                    length // n, n, kv, per, dim),
                jnp.arange(first_row, first_row + length, n)))

        # A clean row sees no noised column (the mask's fourth line), so
        # its scores are made over the clean columns alone.
        out = jnp.concatenate([half(0, length), half(length, s)])
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
        # compiler sees once (unrolled, 16 experts in 6 layers took the
        # reference over 3 minutes to compile), each pass recomputed in
        # the backward (kept, 16 passes' intermediates overflow the chip).
        return jax.lax.scan(
            lambda out, e: (out + expert_part(e), None),
            jnp.zeros_like(x), jnp.arange(count))[0]

    def layer(h, p, positions, noise):
        h = h + attention(
            rms_norm(h, p["input_layernorm"]["weight"]), p["self_attn"],
            positions)
        return h + experts(
            rms_norm(h, p["post_attention_layernorm"]["weight"]), p["mlp"],
            noise)

    def noised_copy(tokens, t, u):
        """(x_t, the weights masked / t) from the record's own draw."""
        level = jnp.repeat(t, b)
        masked = u < level
        return (jnp.where(masked, mask_id, tokens),
                jnp.where(masked, 1.0 / level, 0.0))

    def hidden(params, tokens, noised, row, rows):
        """The last norm's output [L, d] over the noised half."""
        length = tokens.shape[0]
        positions = jnp.concatenate(
            [jnp.arange(length), jnp.arange(length)]).astype(jnp.float32)
        h = params["embed_tokens"]["embedding"][
            jnp.concatenate([tokens, noised])]
        for i in range(int(c["num_hidden_layers"])):
            noise = None
            if c.get("force_load_balancing"):
                # Row `row` of the batch's noise: layer i's seed is i.
                noise = jax.random.uniform(
                    jax.random.PRNGKey(i),
                    (rows, 2 * length, int(c["num_experts"])))[row]
            h = jax.checkpoint(layer)(
                h, params[f"layers_{i}"], positions, noise)
        return rms_norm(h[length:], params["norm"]["weight"])

    def logits(params, buffers, tokens, noised, row=0, rows=1):
        """[L, V] of one record's noised half, whole (the tests' sizes)."""
        return mm("sd,dv->sv", hidden(params, tokens, noised, row, rows),
                  params["lm_head"]["kernel"])

    def loss(params, buffers, tokens, t, u, row=0, rows=1):
        length = tokens.shape[0]
        noised, weights = noised_copy(tokens, t, u)
        head = params["lm_head"]["kernel"]
        h = hidden(params, tokens, noised, row, rows)
        n = _block(length, LOSS_BLOCK)

        @jax.checkpoint
        def picked(args):
            rows_, want, weigh = args
            logp = jax.nn.log_softmax(mm("sd,dv->sv", rows_, head), axis=-1)
            return jnp.sum(
                jnp.take_along_axis(logp, want[:, None], -1)[:, 0] * weigh)

        return -jnp.sum(jax.lax.map(
            picked, (h.reshape(length // n, n, -1),
                     tokens.reshape(length // n, n),
                     weights.reshape(length // n, n)))) / length

    loss.logits = logits
    loss.noised_copy = noised_copy
    return loss


def make_step(model_cfg, opt, precision, fault=None):
    """step(params, m, v, count, buffers, tokens [B, L], t, u) -> (loss
    before the update, params, m, v): batch mean of the records' losses,
    its gradient, one Adam update (Kingma & Ba, bias-corrected, eps
    outside the square root)."""
    import jax
    import jax.numpy as jnp

    loss_one = make_loss(model_cfg, precision, fault)
    lr, b1, b2, eps = (float(opt[k]) for k in
                       ("learning_rate", "beta_1", "beta_2", "epsilon"))

    def batch_loss(params, buffers, tokens, t, u):
        rows = tokens.shape[0]
        return jnp.mean(jax.lax.map(
            lambda row: loss_one(params, buffers, *row, rows),
            (tokens, t, u, jnp.arange(rows))))

    @functools.partial(jax.jit, donate_argnums=(0, 1, 2))
    def step(params, m, v, count, buffers, tokens, t, u):
        loss, grads = jax.value_and_grad(batch_loss)(
            params, buffers, tokens, t, u)
        n = count + 1
        c1, c2 = 1 - b1 ** n, 1 - b2 ** n
        tree_map = jax.tree_util.tree_map
        m = tree_map(lambda a, g: b1 * a + (1 - b1) * g, m, grads)
        v = tree_map(lambda a, g: b2 * a + (1 - b2) * g * g, v, grads)
        params = tree_map(
            lambda p, a, b: p - lr * (a / c1) / (jnp.sqrt(b / c2) + eps),
            params, m, v)
        return loss, params, m, v

    return step


def initial_variables(model_def, seed, first_tokens):
    """(params, buffers) the job starts from: the program's `model.init`
    under the trainer's key schedule (PRNGKey(seed), one split, the second
    half initialises; from one row)."""
    import jax
    import jax.numpy as jnp

    from elasticdl_tpu.common.model_utils import load_module

    model = load_module(model_def).custom_model()
    _, init_rng = jax.random.split(jax.random.PRNGKey(seed))
    row = jnp.asarray(first_tokens[:1], jnp.int32)
    variables = dict(jax.jit(
        lambda rng, row: model.init(
            {"params": rng, "dropout": rng},
            {"tokens": row, "noised": row}, training=False)
    )(init_rng, row))

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
        for k, (tokens, t, u) in enumerate(datagen.batches(
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
                jnp.asarray(tokens, jnp.int32), jnp.asarray(t, jnp.float32),
                jnp.asarray(u, jnp.float32))
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
