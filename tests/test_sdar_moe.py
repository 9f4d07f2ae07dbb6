"""The SDAR expert decoder under block-diffusion training
(models/sdar/sdar_moe.py): what a logit may depend on, the softmax-scored
routed layer and its shares, rotary by positions, the public keys, the
feed's noising and the evaluation metrics."""

import dataclasses

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from elasticdl_tpu.layers import moe
from elasticdl_tpu.models.lfm2.lfm2_moe import rotary
from elasticdl_tpu.models.sdar import sdar_30b_a3b_cut as cut
from elasticdl_tpu.models.sdar import sdar_moe

LENGTH, BLOCK = 32, 4
CONFIG = sdar_moe.SdarMoeConfig(
    num_hidden_layers=2, hidden_size=64, vocab_size=256,
    num_attention_heads=4, num_key_value_heads=2, head_dim=32,
    moe_intermediate_size=32, num_experts=8, num_experts_per_tok=2,
    block_length=BLOCK, mask_token_id=255, expert_block_rows=16,
    activation_dtype="float32",
)


@pytest.fixture(scope="module")
def tiny():
    rng = np.random.default_rng(3)
    tokens = rng.integers(0, 255, (1, LENGTH)).astype(np.int32)
    noised = np.where(rng.random((1, LENGTH)) < 0.5, 255, tokens).astype(
        np.int32)
    model = sdar_moe.custom_model(CONFIG)
    variables = model.init(
        {"params": jax.random.PRNGKey(1)},
        {"tokens": tokens, "noised": noised})
    # Larger weights than the initialiser's, so that a change moves logits
    # by more than rounding.
    variables = dict(variables, params=jax.tree_util.tree_map(
        lambda a: a * 8.0 if a.ndim > 1 else a, variables["params"]))

    def hidden_and_logits(tokens, noised):
        """The noised half's logits, and the clean half's last hidden
        state (which no head reads: taken from the last layer)."""
        logits, state = model.apply(
            variables, {"tokens": tokens, "noised": noised},
            capture_intermediates=lambda mdl, _: mdl.name == "layers_1")
        h, _ = state["intermediates"]["layers_1"]["__call__"][0]
        return np.asarray(logits[0]), np.asarray(h[0, :LENGTH])

    return tokens, noised, hidden_and_logits


def moved(a, b):
    """Which blocks of positions differ between two [LENGTH, ...]."""
    differs = np.abs(a - b).reshape(LENGTH // BLOCK, -1).max(axis=1) > 0
    return set(np.flatnonzero(differs))


@pytest.mark.parametrize("block", [0, 3, 7])
def test_a_clean_token_moves_what_the_mask_lets_it(tiny, block):
    """Changing a clean token of block k moves no logit of noised blocks
    <= k and nothing of clean blocks < k; it does move the noised blocks
    after k (their clean past) and the clean blocks from k on."""
    tokens, noised, run = tiny
    logits, clean = run(tokens, noised)
    changed = tokens.copy()
    changed[0, block * BLOCK + 1] = (changed[0, block * BLOCK + 1] + 7) % 255
    logits2, clean2 = run(changed, noised)
    blocks = LENGTH // BLOCK
    assert moved(logits, logits2) == set(range(block + 1, blocks))
    assert moved(clean, clean2) == set(range(block, blocks))


@pytest.mark.parametrize("block", [0, 3, 7])
def test_a_noised_token_moves_its_own_block_alone(tiny, block):
    tokens, noised, run = tiny
    logits, clean = run(tokens, noised)
    changed = noised.copy()
    at = block * BLOCK + 2
    changed[0, at] = 255 if changed[0, at] != 255 else 17
    logits2, clean2 = run(tokens, changed)
    assert moved(logits, logits2) == {block}
    assert moved(clean, clean2) == set()
    # Both directions inside the block: the positions before `at` too.
    rows = np.abs(logits - logits2).max(axis=1) > 0
    assert rows[block * BLOCK:(block + 1) * BLOCK].all()


def test_both_halves_sit_at_positions_0_to_l_minus_1(tiny):
    """A noised copy equal to the clean one, position by position, reads
    the same keys at the same angles as the clean row does, except its own
    block; with a block of the whole record the clean past is empty and the
    two halves' rows see the same set: equal hidden states."""
    tokens, _, _ = tiny
    config = dataclasses.replace(CONFIG, block_length=LENGTH)
    model = sdar_moe.custom_model(config)
    features = {"tokens": tokens, "noised": tokens}
    variables = model.init({"params": jax.random.PRNGKey(2)}, features)
    _, state = model.apply(
        variables, features,
        capture_intermediates=lambda mdl, _: mdl.name == "layers_1")
    h, _ = state["intermediates"]["layers_1"]["__call__"][0]
    np.testing.assert_allclose(
        h[0, :LENGTH], h[0, LENGTH:], rtol=1e-5, atol=1e-6)


def test_rotary_takes_positions():
    x = jnp.asarray(np.random.default_rng(0).normal(
        size=(1, 8, 2, 16)).astype(np.float32))
    np.testing.assert_array_equal(
        rotary(x, 1e4), rotary(x, 1e4, jnp.arange(8)))
    twice = jnp.concatenate([x[:, :4], x[:, :4]], axis=1)
    turned = rotary(twice, 1e4, jnp.tile(jnp.arange(4), 2))
    np.testing.assert_array_equal(turned[:, :4], turned[:, 4:])
    np.testing.assert_array_equal(turned[:, :4], rotary(x[:, :4], 1e4))


# ---------- softmax-scored routing ----------

E, K, D, F = 16, 3, 12, 10


def layer(held=None, block=8, score="softmax"):
    return moe.RoutedExperts(
        num_experts=E, num_experts_per_tok=K, d_hidden=F, gated=True,
        score=score, held=held, topk_eps=0.0, block_rows=block,
        dtype="float32")


def whole_variables(seed=0):
    variables = layer().init(jax.random.PRNGKey(seed), jnp.zeros((1, 4, D)))
    return jax.tree_util.tree_map(lambda a: a * 20.0, variables)


def share_of(variables, first, count):
    p = dict(variables["params"])
    p["w_gate_up"] = p["w_gate_up"][first:first + count]
    p["w_down"] = p["w_down"][first:first + count]
    return {"params": p, "buffers": variables["buffers"]}


def plain_softmax_layer(params, x):
    """The equations, token by token and expert by expert: p = softmax over
    all E, the K largest, w = p / sum of the chosen p."""
    tokens = np.asarray(x, np.float64).reshape(-1, x.shape[-1])
    router = np.asarray(params["router"], np.float64)
    out = np.zeros_like(tokens)
    for t, row in enumerate(tokens):
        logits = router @ row
        p = np.exp(logits - logits.max())
        p /= p.sum()
        chosen = np.argsort(-p, kind="stable")[:K]
        for e in chosen:
            w13 = np.asarray(params["w_gate_up"][e], np.float64)
            w2 = np.asarray(params["w_down"][e], np.float64)
            g = row @ w13[:, :F]
            out[t] += p[e] / p[chosen].sum() * (
                (g / (1.0 + np.exp(-g))) * (row @ w13[:, F:])) @ w2
    return out.reshape(x.shape)


def test_all_eight_shares_of_the_softmax_layer_add_up_to_the_uncut_one():
    """16 experts over 8 chips, 2 each: the routed parts that the eight
    shares give are the whole layer, which is the plain loop over all 16
    under softmax scores."""
    variables = whole_variables()
    x = jnp.asarray(np.random.default_rng(5).normal(
        size=(2, 9, D)).astype(np.float32))
    with jax.default_matmul_precision("highest"):
        whole, whole_stats = layer().apply(variables, x)
        total = np.zeros(x.shape, np.float64)
        held = 0.0
        for first in range(0, E, 2):
            part, stats = layer((first, 2)).apply(
                share_of(variables, first, 2), x)
            total += np.asarray(part, np.float64)
            held += float(stats["moe_assignments_held"])
    np.testing.assert_allclose(total, whole, rtol=2e-4, atol=2e-5)
    np.testing.assert_allclose(
        whole, plain_softmax_layer(variables["params"], x), rtol=2e-4,
        atol=2e-5)
    assert held == float(whole_stats["moe_assignments"])


def test_softmax_scores_are_not_sigmoid_scores():
    """The same weights under the two scores: the same choice (both are
    monotone in the logit), other weights; an unknown score raises."""
    variables = whole_variables()
    x = jnp.asarray(np.random.default_rng(6).normal(
        size=(1, 7, D)).astype(np.float32))
    soft, _ = layer().apply(variables, x)
    sig, _ = layer(score="sigmoid").apply(variables, x)
    assert np.abs(np.asarray(soft) - np.asarray(sig)).max() > 1e-3
    with pytest.raises(ValueError, match="scores by"):
        layer(score="tanh").apply(variables, x)


def test_softmax_routing_gradients_reach_the_router():
    variables = share_of(whole_variables(), 4, 6)
    x = jnp.asarray(np.random.default_rng(2).normal(
        size=(2, 9, D)).astype(np.float32))

    def via_layer(params):
        return jnp.sum(layer((4, 6), 4).apply(
            {"params": params, "buffers": variables["buffers"]}, x)[0] ** 2)

    grads = jax.grad(via_layer)(variables["params"])
    assert all(np.asarray(g).any() for g in grads.values())


# ---------- the contract ----------


def test_from_public_takes_the_public_keys_and_head_dim_is_its_own():
    cfg = cut.cut_config()
    assert (cfg.hidden_size, cfg.num_attention_heads, cfg.head_dim) == (
        2048, 32, 128)
    assert cfg.head_dim != cfg.hidden_size // cfg.num_attention_heads
    assert (cfg.num_experts, cfg.num_experts_per_tok,
            cfg.moe_intermediate_size, cfg.experts_held) == (
        128, 8, 768, (0, 16))
    assert (cfg.num_hidden_layers, cfg.vocab_size, cfg.mask_token_id) == (
        6, 18992, 18991)
    assert cfg.rms_norm_eps == 1e-6 and cfg.rope_theta == 1e6
    assert cfg.force_load_balancing and cfg.block_length == 4
    with pytest.raises(ValueError, match="every layer"):
        sdar_moe.SdarMoeConfig.from_public(
            dict(cut.PUBLIC_CONFIG, mlp_only_layers=[0]))
    with pytest.raises(ValueError, match="no row of a vocabulary"):
        sdar_moe.SdarMoeConfig(vocab_size=16, mask_token_id=16)


def test_feed_noises_on_the_host_and_the_loss_is_the_weighted_sum():
    from elasticdl_tpu.data.example import encode_example

    rng = np.random.default_rng(4)
    tokens = rng.integers(0, 255, (3, 8)).astype(np.int32)
    t = rng.uniform(0.3, 0.8, (3, 2)).astype(np.float32)
    u = rng.random((3, 8), np.float32)
    records = [encode_example({"tokens": a, "t": b, "u": c})
               for a, b, c in zip(tokens, t, u)]
    features, labels = sdar_moe.make_feed(255)(records, "training", None)
    level = np.repeat(t, 4, axis=1)
    np.testing.assert_array_equal(
        features["noised"], np.where(u < level, 255, tokens))
    np.testing.assert_allclose(
        labels["weights"], (u < level) / level, rtol=1e-6)
    only, none = sdar_moe.make_feed(255)(records, "prediction", None)
    assert none is None and set(only) == {"tokens", "noised"}
    logits = jnp.asarray(rng.normal(size=(3, 8, 256)).astype(np.float32))
    logp = jax.nn.log_softmax(logits)
    by_hand = -np.mean(np.take_along_axis(
        np.asarray(logp), tokens[..., None], -1)[..., 0] * labels["weights"])
    got = sdar_moe.loss(
        {k: jnp.asarray(v) for k, v in labels.items()}, {"logits": logits})
    assert float(got) == pytest.approx(float(by_hand), rel=1e-5)
    # The evaluation metrics: the loss again, and accuracy over the masked.
    metrics = sdar_moe.eval_metrics_fn()
    for m in metrics.values():
        m.update(np.asarray(logits), labels)
    assert metrics["masked_ce"].result() == pytest.approx(
        float(by_hand), rel=1e-5)
    hits = (np.argmax(np.asarray(logits), -1) == tokens)[
        labels["weights"] > 0]
    assert metrics["masked_accuracy"].result() == pytest.approx(hits.mean())


# ---------- the q / k stage: ops/qk_rotary.py at the call site ----------


def _kernel_sized(layers=3):
    """A model whose heads the kernels tile (128 lanes), bfloat16
    activations, with its features and labels."""
    config = dataclasses.replace(
        CONFIG, num_hidden_layers=layers, head_dim=128,
        activation_dtype="bfloat16")
    rng = np.random.default_rng(5)
    tokens = rng.integers(0, 255, (2, LENGTH)).astype(np.int32)
    noised, weights = sdar_moe.noise(
        tokens, rng.uniform(0.1, 1, (2, LENGTH // BLOCK)),
        rng.random((2, LENGTH)), 255)
    features = {"tokens": tokens, "noised": noised}
    model = sdar_moe.custom_model(config)
    variables = model.init({"params": jax.random.PRNGKey(4)}, features)
    return (model, variables, features,
            {"targets": tokens, "weights": weights})


def _loss_and_gradients(model, variables, features, labels):
    def f(params):
        return sdar_moe.loss(labels, model.apply(
            dict(variables, params=params), features, training=True))

    return jax.jit(jax.value_and_grad(f))(variables["params"])


def test_the_loss_and_gradients_are_the_parents_call_sites_bits(monkeypatch):
    """Off the TPU the op is the parent's expression over tables built once:
    with `rotary(head_norm(.))`, the cast and the transpose put back at the
    call site (the parent of PR 53), the loss and every gradient keep their
    bits under a jit."""
    from elasticdl_tpu.models.nemotron_h.nemotron_h import rms_norm

    model, variables, features, labels = _kernel_sized()
    got = _loss_and_gradients(model, variables, features, labels)
    positions = jnp.tile(jnp.arange(LENGTH), 2)

    def parents(x, weight, eps, cos, sin):
        turned = rotary(rms_norm(x, weight, eps), CONFIG.rope_theta,
                        positions)
        return jnp.swapaxes(turned.astype(x.dtype), 1, 2)

    monkeypatch.setattr(sdar_moe, "qk_rotary", parents)
    want = _loss_and_gradients(model, variables, features, labels)
    for g, w in zip(jax.tree_util.tree_leaves(got),
                    jax.tree_util.tree_leaves(want)):
        np.testing.assert_array_equal(g, w)


@pytest.mark.parametrize("kernels", [False, True], ids=["cpu", "kernels"])
def test_the_rope_tables_are_built_once_a_model_call(monkeypatch, kernels):
    """One cos and one sin a step whatever the number of layers, over the
    doubled positions; where the kernels run, every layer's q and k go
    through `qk_rotary_fwd` and come back through `qk_rotary_bwd`, handed
    those tables as [1, 2L, head_dim]."""
    from elasticdl_tpu.ops import flash_attention as fa
    from test_ssd_scan import _equations

    model, variables, features, labels = _kernel_sized(layers=3)
    if kernels:
        monkeypatch.delenv("EDL_FORCE_PALLAS_INTERPRET", raising=False)
        monkeypatch.setattr(fa, "_use_pallas", lambda: True)
    jaxpr = jax.make_jaxpr(jax.grad(lambda p: sdar_moe.loss(
        labels, model.apply(dict(variables, params=p), features,
                            training=True))))(variables["params"]).jaxpr
    eqns = list(_equations(jaxpr))
    names = [e.primitive.name for e in eqns]
    assert names.count("cos") == 1 and names.count("sin") == 1
    calls = [e for e in eqns if e.primitive.name == "pallas_call"
             and e.params["name"].startswith("qk_rotary")]
    assert sorted(e.params["name"] for e in calls) == (
        6 * ["qk_rotary_bwd"] + 6 * ["qk_rotary_fwd"] if kernels else [])
    for call in calls:
        assert [v.aval.shape for v in call.invars[2:4]] == 2 * [
            (2, 2 * LENGTH, 128)]
