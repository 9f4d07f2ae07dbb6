"""The LFM2 expert decoder against its plain reference on seeded weights
(logits, loss, every gradient leaf, one Adam step), rotary and the q / k
norm against written-out forms, and the public-config constructor."""

import dataclasses
import os
import sys

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from elasticdl_tpu.models.lfm2 import lfm2_moe as lm
from elasticdl_tpu.models.nemotron_h.nemotron_h import rms_norm

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
for _path in (REPO, os.path.join(REPO, "benchmark")):
    if _path not in sys.path:
        sys.path.insert(0, _path)

from lib import cell as cell_mod  # noqa: E402

OPT = {"learning_rate": 3e-4, "beta_1": 0.9, "beta_2": 0.999,
       "epsilon": 1e-8}
SHAPES = {
    "one_dense": dict(
        layer_types=("conv", "full_attention", "conv"), num_dense_layers=1),
    "two_dense": dict(
        layer_types=("conv", "conv", "full_attention", "conv"),
        num_dense_layers=2),
}


def config(shape, **more):
    return lm.Lfm2MoeConfig(
        hidden_size=32, vocab_size=64, num_attention_heads=4,
        num_key_value_heads=2, intermediate_size=48,
        moe_intermediate_size=16, num_experts=8, num_experts_per_tok=2,
        experts_held=(1, 5), expert_block_rows=8,
        activation_dtype="float32", **SHAPES[shape], **more)


def as_reference_config(cfg):
    out = dataclasses.asdict(cfg)
    out["layer_types"] = list(cfg.layer_types)
    return out


@pytest.fixture(scope="module")
def ref():
    return cell_mod.load_module("references", "lfm2_moe")


def seeded(cfg, seed=0, rows=3, seq=16):
    rng = np.random.default_rng(seed)
    tokens = jnp.asarray(rng.integers(0, cfg.vocab_size, (rows, seq)))
    labels = jnp.asarray(rng.integers(0, cfg.vocab_size, (rows, seq)))
    model = lm.custom_model(cfg)
    variables = model.init(
        {"params": jax.random.PRNGKey(seed)}, tokens, training=False)
    # Larger weights than the initialiser's: every layer moves the loss.
    params = jax.tree_util.tree_map(
        lambda a: a * 3.0 if a.ndim > 1 else a, variables["params"])
    return model, params, dict(variables["buffers"]), tokens, labels


@pytest.mark.parametrize("forced", [False, True])
@pytest.mark.parametrize("shape", sorted(SHAPES))
def test_logits_loss_and_gradients_against_the_reference(ref, shape, forced):
    cfg = config(shape, force_load_balancing=forced)
    model, params, buffers, tokens, labels = seeded(cfg)
    loss_one = ref.make_loss(as_reference_config(cfg), "float32")
    rows = tokens.shape[0]

    def program(p):
        out = model.apply({"params": p, "buffers": buffers}, tokens,
                          training=True)
        return lm.loss(labels, out), out["logits"]

    def reference(p):
        return jnp.mean(jnp.stack([
            loss_one(p, buffers, tokens[i], labels[i], i, rows)
            for i in range(rows)]))

    with jax.default_matmul_precision("highest"):
        (got, logits), got_grads = jax.value_and_grad(
            program, has_aux=True)(params)
        want, want_grads = jax.value_and_grad(reference)(params)
        want_logits = jnp.stack([
            loss_one.logits(params, buffers, tokens[i], i, rows)
            for i in range(rows)])
    np.testing.assert_allclose(logits, want_logits, rtol=2e-4, atol=2e-5)
    assert abs(float(got) - float(want)) < 2e-5
    assert set(got_grads) == set(want_grads)
    worst = jax.tree_util.tree_map(
        lambda a, b: float(jnp.max(jnp.abs(a - b))
                           / (jnp.max(jnp.abs(a)) + 1e-12)),
        want_grads, got_grads)
    for path, err in jax.tree_util.tree_leaves_with_path(worst):
        assert err < 2e-4, (jax.tree_util.keystr(path), err)
    # The head is the embedding table: no second matrix, and the table's
    # gradient carries the head's part (a row no token reads still moves).
    assert "lm_head" not in params
    unread = sorted(set(range(cfg.vocab_size))
                    - set(np.asarray(tokens).reshape(-1)))
    assert np.asarray(
        got_grads["embed_tokens"]["embedding"])[unread].any()
    first_routed = f"layers_{cfg.num_dense_layers}"
    assert np.asarray(want_grads[first_routed]["feed_forward"]["router"]).any()
    assert "w1" in params[f"layers_{cfg.num_dense_layers - 1}"]["feed_forward"]


def test_one_adam_step_against_the_reference(ref):
    from elasticdl_tpu.worker.trainer import LocalTrainer

    cfg = config("one_dense", force_load_balancing=True)
    model, _, _, tokens, labels = seeded(cfg)
    trainer = LocalTrainer(model, lm.loss, lm.optimizer(), seed=5)
    trainer.init_variables_if_needed(tokens[:1])
    start = jax.tree_util.tree_map(jnp.array, trainer._variables)
    step = ref.make_step(as_reference_config(cfg), OPT, "float32")
    zeros = jax.tree_util.tree_map(jnp.zeros_like, start["params"])
    with jax.default_matmul_precision("highest"):
        _, _, got_loss = trainer.train_minibatch(tokens, labels)
        want_loss, want, _, _ = step(
            jax.tree_util.tree_map(jnp.array, start["params"]), zeros,
            jax.tree_util.tree_map(jnp.array, zeros),
            jnp.asarray(0, jnp.float32), start["buffers"], tokens, labels)
    assert abs(float(got_loss) - float(want_loss)) < 2e-5
    moved = jax.tree_util.tree_map(
        lambda new, old, ref_new: (
            float(jnp.max(jnp.abs(new - ref_new))),
            float(jnp.max(jnp.abs(new - old)))),
        trainer._variables["params"], start["params"], want)
    for path, (off, step_size) in jax.tree_util.tree_leaves_with_path(
            moved, is_leaf=lambda x: isinstance(x, tuple)):
        # Adam's first step moves every weight by about the learning rate.
        assert step_size > 1e-4, jax.tree_util.keystr(path)
        assert off < 3e-5, (jax.tree_util.keystr(path), off)


def test_rotary_against_the_rotation_written_out():
    """Pair (i, i + d/2) of a head turns by position x theta^(-2i/d)."""
    rng = np.random.default_rng(0)
    x = rng.normal(size=(2, 7, 3, 8))
    theta, d = 1e6, 8
    want = np.empty_like(x)
    for t in range(7):
        for i in range(d // 2):
            angle = t * theta ** (-2.0 * i / d)
            a, b = x[:, t, :, i], x[:, t, :, i + d // 2]
            want[:, t, :, i] = a * np.cos(angle) - b * np.sin(angle)
            want[:, t, :, i + d // 2] = b * np.cos(angle) + a * np.sin(angle)
    got = lm.rotary(jnp.asarray(x, jnp.float32), theta)
    np.testing.assert_allclose(got, want, rtol=1e-5, atol=1e-5)
    # Position 0 is left as it is, and a turn keeps a pair's length.
    np.testing.assert_allclose(got[:, 0], x[:, 0], rtol=1e-6)
    np.testing.assert_allclose(
        np.linalg.norm(got, axis=-1), np.linalg.norm(x, axis=-1), rtol=1e-5)


def test_q_and_k_are_normed_a_head_before_they_are_turned():
    """Scaling one query head's projection leaves the output as it is (its
    RMSNorm takes the scale out again); the head's norm weight does not."""
    cfg = config("one_dense")
    model, params, buffers, tokens, _ = seeded(cfg)

    def logits(p):
        return model.apply({"params": p, "buffers": buffers}, tokens)

    attn = params["layers_1"]["self_attn"]
    assert attn["q_layernorm"].shape == attn["k_layernorm"].shape == (8,)

    def with_attn(**changed):
        return {**params, "layers_1": {
            **params["layers_1"], "self_attn": {**attn, **changed}}}

    scaled = {"kernel": attn["q_proj"]["kernel"].at[:, 2].multiply(7.0)}
    with jax.default_matmul_precision("highest"):
        base = logits(params)
        np.testing.assert_allclose(
            logits(with_attn(q_proj=scaled)), base, rtol=2e-3, atol=2e-4)
        moved = logits(with_attn(q_layernorm=attn["q_layernorm"] * 3.0))
    # (eps under the root keeps the first from being exact.)
    assert float(jnp.max(jnp.abs(moved - base))) > 1e-2
    x = jnp.asarray(np.random.default_rng(1).normal(size=(5, 8)))
    np.testing.assert_allclose(
        rms_norm(x, jnp.ones(8), 1e-5),
        x / np.sqrt(np.mean(np.square(x), -1, keepdims=True) + 1e-5),
        rtol=1e-5)


def test_from_public_keeps_the_named_layers_and_counts_the_dense_ones():
    from elasticdl_tpu.models.lfm2 import lfm2_24b_a2b_cut as cut

    cfg = cut.cut_config()
    assert cfg.layer_types == (
        "conv", "full_attention", "conv", "conv", "conv", "full_attention",
        "conv")
    assert (cfg.num_dense_layers, cfg.head_dim, cfg.rope_theta) == (
        1, 64, 1e6)
    whole = lm.Lfm2MoeConfig.from_public(cut.PUBLIC_CONFIG)
    assert len(whole.layer_types) == 40 and whole.num_dense_layers == 2
    assert whole.layer_types.count("full_attention") == 10
    with pytest.raises(ValueError, match="operators are"):
        lm.Lfm2MoeConfig(layer_types=("conv", "sliding"))
