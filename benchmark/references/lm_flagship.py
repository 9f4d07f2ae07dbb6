"""Plain reference of the dense pre-LN decoder LM and its training step.

Forward, next-token cross-entropy, gradients (`jax.grad` of the plain
forward) and Adam, in straightforward `jax.numpy`: float32 everywhere,
`jax.default_matmul_precision("highest")`, the whole S x S score matrix,
no kernel, no flax `apply`, no optax. It follows Radford et al. 2019 as the
repository builds it: learned positions, LayerNorm (eps 1e-6) before each
sub-layer, fused QKV with biases, scores scaled by head_dim^-0.5, tanh
GELU, a final LayerNorm, an untied LM head with bias.

Inputs come from the seed alone: the records through the benchmark's own
generator, the initial weights through the program's own initialiser
(`model.init` under the trainer's key schedule; initialisation is not what
is under test). It loops over the sequences of a batch and rematerialises
each layer so that float32 at S = 4096 fits one chip.

`--precision` selects the arithmetic of every matrix product:
  float32   the reference (operands float32, precision highest)
  fp8       the control, one step below the stated bfloat16: both operands
            of every product, forward and backward, rounded to fp8 under a
            per-tensor absmax scale (`_fp8_product`). A bare `astype` under
            `jax.grad` would not do: it casts the cotangents to fp8 with
            no scale, they underflow, and nothing trains.

    python benchmark/references/lm_flagship.py --config <file> --seed 3 \
        --minibatch 4 --steps 8,16 [--precision float32]
prints one JSON line {"losses": {"8": ..., "16": ...}, ...}; the loss of
step k is the loss before update k, as the worker logs it.
"""

import argparse
import functools
import importlib
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


def _fp8_product():
    """einsum whose every product has both operands in fp8, as fp8
    training runs them (Micikevicius et al. 2022): float8_e4m3fn for the
    forward operands, which the backward products reuse, float8_e5m2 for
    the cotangent, each under a per-tensor absmax scale; accumulation and
    everything between the products stay float32."""
    import jax
    import jax.numpy as jnp

    def rounder(dtype):
        top = float(jnp.finfo(dtype).max)

        def to(x):
            scale = top / jnp.maximum(jnp.max(jnp.abs(x)), 1e-30)
            return (x * scale).astype(dtype).astype(jnp.float32) / scale
        return to

    operand, cotangent = rounder(jnp.float8_e4m3fn), rounder(jnp.float8_e5m2)

    def plain(spec, a, b):
        return jnp.einsum(spec, a, b, precision=jax.lax.Precision.HIGHEST)

    @functools.partial(jax.custom_vjp, nondiff_argnums=(0,))
    def product(spec, a, b):
        return plain(spec, operand(a), operand(b))

    def forward(spec, a, b):
        a, b = operand(a), operand(b)
        return plain(spec, a, b), (a, b)

    def backward(spec, rounded, g):
        _, pull = jax.vjp(functools.partial(plain, spec), *rounded)
        return pull(cotangent(g))

    product.defvjp(forward, backward)
    return product


def make_loss(model_cfg, precision):
    """loss(params, tokens [S], labels [S]) for ONE sequence."""
    import jax
    import jax.numpy as jnp

    if precision == "float32":
        def mm(spec, a, b):
            return jnp.einsum(spec, a, b,
                              precision=jax.lax.Precision.HIGHEST)
    elif precision == "fp8":
        mm = _fp8_product()
    else:
        raise ValueError(f"unknown precision {precision!r}")

    def layer_norm(x, p):
        mean = jnp.mean(x, -1, keepdims=True)
        var = jnp.mean(jnp.square(x - mean), -1, keepdims=True)
        return (x - mean) * jax.lax.rsqrt(var + 1e-6) * p["scale"] + p["bias"]

    def gelu_tanh(x):
        return 0.5 * x * (1.0 + jnp.tanh(
            0.7978845608028654 * (x + 0.044715 * x ** 3)))

    def block(x, p):
        s, d = x.shape
        h = layer_norm(x, p["LayerNorm_0"])
        att = p["MultiHeadAttention_0"]
        qkv = mm("sd,dthe->sthe", h, att["qkv"]["kernel"])
        qkv = qkv + att["qkv"]["bias"]
        q, k, v = qkv[:, 0], qkv[:, 1], qkv[:, 2]  # [S, H, Dh]
        scores = mm("qhe,khe->hqk", q, k) * (q.shape[-1] ** -0.5)
        causal = jnp.tril(jnp.ones((s, s), bool))
        scores = jnp.where(causal[None], scores, -1e30)
        weights = jax.nn.softmax(scores, axis=-1)
        out = mm("hqk,khe->qhe", weights, v).reshape(s, d)
        x = x + mm("sd,de->se", out, att["proj"]["kernel"]) \
            + att["proj"]["bias"]
        h = layer_norm(x, p["LayerNorm_1"])
        h = mm("sd,df->sf", h, p["Dense_0"]["kernel"]) + p["Dense_0"]["bias"]
        h = gelu_tanh(h)
        h = mm("sf,fd->sd", h, p["Dense_1"]["kernel"]) + p["Dense_1"]["bias"]
        return x + h

    def loss(params, tokens, labels):
        s = tokens.shape[0]
        x = params["tok_emb"]["embedding"][tokens] \
            + params["pos_emb"]["embedding"][:s]
        for i in range(int(model_cfg["n_layers"])):
            x = jax.checkpoint(block)(x, params[f"Block_{i}"])
        x = layer_norm(x, params["LayerNorm_0"])
        logits = mm("sd,dv->sv", x, params["lm_head"]["kernel"]) \
            + params["lm_head"]["bias"]
        logp = jax.nn.log_softmax(logits, axis=-1)
        picked = jnp.take_along_axis(logp, labels[:, None], axis=-1)
        return -jnp.mean(picked)

    return loss


def make_step(model_cfg, opt, precision):
    """step(params, m, v, count, tokens [B, S], labels [B, S]) ->
    (loss before the update, params, m, v): batch mean of the sequence
    losses, its gradient, one Adam update (Kingma & Ba, bias-corrected,
    eps outside the square root)."""
    import jax
    import jax.numpy as jnp

    loss_one = make_loss(model_cfg, precision)
    lr, b1, b2, eps = (float(opt[k]) for k in
                       ("learning_rate", "beta_1", "beta_2", "epsilon"))

    @functools.partial(jax.jit, donate_argnums=(0, 1, 2))
    def step(params, m, v, count, tokens, labels):
        batch = tokens.shape[0]

        def one(carry, row):
            loss, grad = jax.value_and_grad(loss_one)(params, *row)
            acc_loss, acc_grad = carry
            return (acc_loss + loss / batch, jax.tree_util.tree_map(
                lambda a, g: a + g / batch, acc_grad, grad)), None

        zeros = jax.tree_util.tree_map(jnp.zeros_like, params)
        (loss, grads), _ = jax.lax.scan(
            one, (jnp.zeros((), jnp.float32), zeros), (tokens, labels))
        t = count + 1
        m = jax.tree_util.tree_map(
            lambda a, g: b1 * a + (1 - b1) * g, m, grads)
        v = jax.tree_util.tree_map(
            lambda a, g: b2 * a + (1 - b2) * g * g, v, grads)
        c1 = 1 - b1 ** t
        c2 = 1 - b2 ** t
        params = jax.tree_util.tree_map(
            lambda p, a, b: p - lr * (a / c1) / (jnp.sqrt(b / c2) + eps),
            params, m, v)
        return loss, params, m, v

    return step


def initial_params(model_def, seed, first_row):
    """The weights the job starts from: the program's `model.init` under
    the trainer's key schedule (JaxTrainer: PRNGKey(seed), one split, the
    second half initialises; AllReduceTrainer: from one row)."""
    import jax
    import jax.numpy as jnp

    module = importlib.import_module(model_def)
    model = module.custom_model()
    _, init_rng = jax.random.split(jax.random.PRNGKey(seed))
    # One jitted call (the trainer runs the same initialisers eagerly,
    # which takes half a minute at flagship width).
    variables = jax.jit(
        lambda rng, row: model.init(
            {"params": rng, "dropout": rng}, row, training=False)
    )(init_rng, jnp.asarray(first_row[:1]))
    return jax.tree_util.tree_map(
        lambda a: jnp.asarray(a, jnp.float32), dict(variables)["params"])


timing = {}  # of the last losses() call: init, first step, the rest


def losses(config, seed, minibatch, steps, precision="float32"):
    """{step: loss} at the asked steps (1-based, as the worker counts)."""
    import jax
    import jax.numpy as jnp

    from lib import cell

    datagen = cell.load_module("datagen", config["datagen"])
    last = max(steps)
    step = make_step(config["model"], config["optimizer"], precision)
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
    args = parser.parse_args(argv)
    with open(args.config) as f:
        config = json.load(f)
    import jax

    from elasticdl_tpu.common.compile_cache import ensure_compile_cache

    ensure_compile_cache()
    steps = sorted({int(s) for s in args.steps.split(",")})
    got = losses(config, args.seed, args.minibatch, steps, args.precision)
    dev = jax.devices()[0]
    print(json.dumps({
        "losses": {str(k): v for k, v in got.items()},
        "precision": args.precision, "timing": timing,
        "device": {"platform": dev.platform, "kind": dev.device_kind},
    }), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
