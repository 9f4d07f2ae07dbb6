"""Plain reference of the Kanana 2 expert decoder and its training step.

Forward, next-token cross-entropy, gradients (`jax.grad` of the plain
forward) and Adam in straightforward `jax.numpy`: float32 everywhere,
`jax.default_matmul_precision("highest")`, no kernel, no flax `apply`, no
optax, nothing of `elasticdl_tpu/layers`, `elasticdl_tpu/models` or
`elasticdl_tpu/ops`. Written from the equations of the HF `deepseek_v3`
model under this config's keys (ISSUE 55 lists them), layer l:

    h = h + Wo A(q, k, v),  u = RMSNorm(h), eps rms_norm_eps
        q = Wq u, [S, heads, nope + rope], split q_nope | q_rope
        Wkva u, [S, kv_lora_rank + rope], split c | k_rope: one rope key
        for all the heads; c = RMSNorm(c); Wkvb c, [S, heads, nope + v],
        split k_nope | v
        q_rope and k_rope turned by the row's position p over their rope
        channels, in the published pairing (2i, 2i + 1): the pair is the
        complex number x_2i + j x_2i+1, times exp(j p theta^(-2i / rope))
        (`rope_scaling` null: no YaRN, no mscale)
        scores q_nope k_nope^T + q_rope k_rope^T, times (nope + rope)^-0.5,
        row r seeing column c iff c <= r; softmax; times v; Wo
    h = h + ffn(RMSNorm(h))
        l < first_k_dense_replace: down(silu(gate x) * up x), width
        intermediate_size
        after them: s = sigmoid(Wg x) over all n_routed_experts; the
        num_experts_per_tok largest of s + e_score_correction_bias (n_group
        and topk_group 1: the group limit is the identity); w = s at the
        chosen over their sum (+ 1e-20), times routed_scaling_factor; sum
        over the chosen of w_e down_e(silu(gate_e x) * up_e x); plus, for
        every token, one gated MLP of width n_shared_experts x
        moe_intermediate_size (the shared experts as HF builds them). Under
        `force_load_balancing` Wg x is replaced in the forward pass by
        seeded uniform noise (Megatron-Core's benchmark mode)
    last RMSNorm, the untied head, mean next-token cross-entropy.

Not as the program computes it: the two parts of the scores are two
products and the rope key is never copied to the heads; the turn is the
complex product above, pair by pair in place (HF and the program bring the
pairs to the two halves first: q and k permuted alike, the same scores);
the mask is built pair by pair and applied to whole rows of scores, a
block of query rows at a time, against the keys up to the end of the
block's quarter of the sequence (`KEY_BANDS`: every later key is masked
for each of its rows, an exact zero after the softmax, so leaving those
products out regroups float32 sums and no more, and the child is a quarter
shorter); the experts are a loop over the held
experts, each over every row under a dense [rows, E] gate matrix (no sort,
no blocks); the loss by blocks of rows. It is given the program's share:
the experts `experts_held` of each routed layer (what the others would add
is left out) and the vocabulary slice. HF pads v to the key width for its
flash call and cuts the result back: mathematically nothing, left out.

Inputs come from the seed alone: the records through the benchmark's own
generator, the initial weights through the program's own initialiser
(`model.init` under the trainer's key schedule).

`--fault` plants one fault of one mechanism, in float32: `rope_off` (the
rope channels of q and k are not turned: no position enters the scores)
and `scale_128` (the scores are scaled by nope^-0.5, the width of the part
without position alone, not by (nope + rope)^-0.5).

`--precision fp8` is the control, one step below the stated bfloat16: both
operands of every matrix product, forward and backward, rounded to fp8
under per-tensor absmax scales (`references/lm_flagship.py:_fp8_product`).

    python benchmark/references/kanana_moe.py --config <file> --seed 3 \
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
KEY_BANDS = 4
LOSS_BLOCK = 2048
FAULTS = ("rope_off", "scale_128")


def _block(total, limit):
    """The largest divisor of `total` that is at most `limit`."""
    size = min(total, limit)
    while total % size:
        size -= 1
    return size


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
    eps = float(c["rms_norm_eps"])
    nope, rope = int(c["qk_nope_head_dim"]), int(c["qk_rope_head_dim"])
    rank = int(c["kv_lora_rank"])
    theta = float(c["rope_theta"])
    scale = (nope if fault == "scale_128" else nope + rope) ** -0.5

    def rms_norm(x, weight):
        return x * jax.lax.rsqrt(
            jnp.mean(x * x, -1, keepdims=True) + eps) * weight

    def gated_mlp(x, gate, up, down):
        return mm("sf,fd->sd", jax.nn.silu(mm("sd,df->sf", x, gate))
                  * mm("sd,df->sf", x, up), down)

    def turned(x):
        """x [S, ..., rope] by its position: each pair (2i, 2i + 1) as a
        complex number times exp(j p theta^(-2i / rope))."""
        if fault == "rope_off":
            return x
        inv_freq = jnp.asarray(
            [theta ** (-2.0 * i / rope) for i in range(rope // 2)],
            jnp.float32)
        angles = jnp.arange(x.shape[0], dtype=jnp.float32)[:, None] \
            * inv_freq[None]
        angles = angles.reshape(
            (x.shape[0],) + (1,) * (x.ndim - 2) + (rope // 2,))
        cos, sin = jnp.cos(angles), jnp.sin(angles)
        re, im = x[..., 0::2], x[..., 1::2]
        return jnp.stack(
            [re * cos - im * sin, im * cos + re * sin], axis=-1
        ).reshape(x.shape)

    def attention(x, p):
        s = x.shape[0]
        q = mm("sd,dhe->she", x, p["q_proj"]["kernel"])
        down = mm("sd,de->se", x, p["kv_a_proj_with_mqa"]["kernel"])
        latent = rms_norm(down[:, :rank], p["kv_a_layernorm"]["weight"])
        up = mm("sr,rhe->she", latent, p["kv_b_proj"]["kernel"])
        k_nope, v = up[..., :nope], up[..., nope:]
        q_nope, q_rope = q[..., :nope], turned(q[..., nope:])
        k_rope = turned(down[:, rank:])           # [S, rope]: one key
        n = _block(s, QUERY_BLOCK)
        bands = _block(s // n, KEY_BANDS)
        per = s // bands

        def band(start):
            """Rows [start, start + per) against the keys before the
            band's end: no row of the band sees a later one."""
            stop = start + per
            keys_nope, keys_rope, values = (
                k_nope[:stop], k_rope[:stop], v[:stop])

            @jax.checkpoint
            def rows(args):
                nope_rows, rope_rows, first = args
                scores = (mm("qhe,khe->hqk", nope_rows, keys_nope)
                          + mm("qhe,ke->hqk", rope_rows, keys_rope)) * scale
                seen = jnp.arange(stop)[None] <= (
                    first + jnp.arange(n))[:, None]
                weights = jax.nn.softmax(
                    jnp.where(seen, scores, -1e30), axis=-1)
                return mm("hqk,khe->qhe", weights, values)

            return jax.lax.map(rows, (
                q_nope[start:stop].reshape(per // n, n, *q_nope.shape[1:]),
                q_rope[start:stop].reshape(per // n, n, *q_rope.shape[1:]),
                jnp.arange(start, stop, n)))

        out = jnp.concatenate([band(start) for start in range(0, s, per)])
        return mm("sf,fd->sd", out.reshape(s, -1), p["o_proj"]["kernel"])

    def experts(x, p, bias, noise):
        k = int(c["num_experts_per_tok"])
        first, count = c.get("experts_held") or (
            0, int(c["n_routed_experts"]))
        width = int(c["moe_intermediate_size"])
        shared = int(c["n_shared_experts"]) * width
        logits = mm("sd,ed->se", x, p["router"])
        if noise is not None:
            # The noise in the forward pass, the gradient to the router.
            logits = noise + logits - jax.lax.stop_gradient(logits)
        scores = jax.nn.sigmoid(logits)
        _, chosen = jax.lax.top_k(scores + bias, k)
        weights = jnp.take_along_axis(scores, chosen, axis=-1)
        if c["norm_topk_prob"]:
            weights = weights / (
                jnp.sum(weights, -1, keepdims=True) + 1e-20)
        weights = weights * float(c["routed_scaling_factor"])
        gates = jnp.sum(
            jax.nn.one_hot(chosen, scores.shape[1], dtype=x.dtype)
            * weights[..., None], axis=1)             # [S, E], dense

        @jax.checkpoint
        def expert_part(e):
            # The program keeps gate and up side by side in one matrix.
            gu = p["w_gate_up"][e]
            gate = jnp.take(gates, first + e, axis=1)[:, None]
            return gate * gated_mlp(
                x, gu[:, :width], gu[:, width:], p["w_down"][e])

        # One held expert after another, each over every row: a loop the
        # compiler sees once, each pass recomputed in the backward.
        routed = jax.lax.scan(
            lambda out, e: (out + expert_part(e), None),
            jnp.zeros_like(x), jnp.arange(count))[0]
        gu = p["shared_gate_up"]["kernel"]
        return routed + gated_mlp(
            x, gu[:, :shared], gu[:, shared:], p["shared_down"]["kernel"])

    def layer(h, i, p, bias, noise):
        h = h + attention(
            rms_norm(h, p["input_layernorm"]["weight"]), p["self_attn"])
        u = rms_norm(h, p["post_attention_layernorm"]["weight"])
        if i < int(c["first_k_dense_replace"]):
            m = p["mlp"]
            return h + gated_mlp(
                u, m["gate_proj"]["kernel"], m["up_proj"]["kernel"],
                m["down_proj"]["kernel"])
        return h + experts(u, p["mlp"], bias, noise)

    def hidden(params, buffers, tokens, row, rows):
        """The last norm's output [S, d] for one sequence."""
        s = tokens.shape[0]
        h = params["embed_tokens"]["embedding"][tokens]
        for i in range(int(c["num_hidden_layers"])):
            noise = bias = None
            if i >= int(c["first_k_dense_replace"]):
                bias = buffers[f"layers_{i}"]["mlp"][
                    "e_score_correction_bias"]
                if c.get("force_load_balancing"):
                    # Row `row` of the batch's noise: layer i's seed is i.
                    noise = jax.random.uniform(
                        jax.random.PRNGKey(i),
                        (rows, s, int(c["n_routed_experts"])))[row]
            h = jax.checkpoint(layer, static_argnums=(1,))(
                h, i, params[f"layers_{i}"], bias, noise)
        return rms_norm(h, params["norm"]["weight"])

    def logits(params, buffers, tokens, row=0, rows=1):
        """[S, V] of one sequence, whole (the tests' sizes)."""
        return mm("sd,dv->sv", hidden(params, buffers, tokens, row, rows),
                  params["lm_head"]["kernel"])

    def loss(params, buffers, tokens, labels, row=0, rows=1):
        s = tokens.shape[0]
        head = params["lm_head"]["kernel"]
        h = hidden(params, buffers, tokens, row, rows)
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
    loss.experts = experts  # one routed layer, for the tests
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
