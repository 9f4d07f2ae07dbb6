"""The Granite 4.0-H dense hybrid (models/granite_hybrid): the public keys,
the four multipliers (none dropped, the attention one not head_dim^-0.5),
no position signal but order through the mixers, the tied head, what the
step counts, the remat policy, what the block refuses to build, and the
cut's size."""

import dataclasses

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from elasticdl_tpu.models.granite_hybrid import granite_4_0_h_micro_cut as cut
from elasticdl_tpu.models.granite_hybrid import granite_hybrid as gh
from elasticdl_tpu.ops import flash_attention as fa

LENGTH = 32
CONFIG = gh.GraniteHybridConfig(
    layer_types=("mamba", "mamba", "attention", "mamba"),
    hidden_size=64, vocab_size=256, num_attention_heads=4,
    num_key_value_heads=2, shared_intermediate_size=128,
    mamba_n_heads=8, mamba_d_head=16, mamba_n_groups=1, mamba_d_state=16,
    mamba_chunk_size=8, attention_multiplier=0.0625,
    embedding_multiplier=12.0, residual_multiplier=0.22,
    logits_scaling=8.0, activation_dtype="float32",
)
MULTIPLIERS = ("attention_multiplier", "embedding_multiplier",
               "residual_multiplier", "logits_scaling")


@pytest.fixture(scope="module")
def tiny():
    """(tokens, labels, params): weights larger than the initialiser's, so
    that a change moves the loss by more than rounding."""
    rng = np.random.default_rng(3)
    tokens = rng.integers(0, 256, (2, LENGTH + 1)).astype(np.int32)
    params = gh.custom_model(CONFIG).init(
        {"params": jax.random.PRNGKey(1)}, tokens[:, :-1])["params"]
    params = jax.tree_util.tree_map(
        lambda a: a * 16.0 if a.ndim > 1 else a, params)
    return tokens[:, :-1], tokens[:, 1:], params


def loss_of(config, tiny):
    tokens, labels, params = tiny
    out = gh.custom_model(config).apply(
        {"params": params}, tokens, training=True)
    return float(gh.loss(labels, out))


def test_from_public_on_the_catalogs_keys_gives_the_published_widths():
    cfg = gh.GraniteHybridConfig.from_public(cut.PUBLIC_CONFIG)
    assert len(cfg.layer_types) == 40
    assert cfg.layer_types.count("attention") == 4
    assert cfg.layer_types[:10] == ("mamba",) * 5 + ("attention",) + (
        "mamba",) * 4
    assert (cfg.hidden_size, cfg.vocab_size) == (2048, 100352)
    assert (cfg.num_attention_heads, cfg.num_key_value_heads,
            cfg.head_dim) == (32, 8, 64)
    assert (cfg.mamba_n_heads, cfg.mamba_d_head, cfg.mamba_n_groups,
            cfg.mamba_d_state, cfg.mamba_d_conv, cfg.mamba_chunk_size) == (
                64, 64, 1, 128, 4, 256)
    assert cfg.shared_intermediate_size == 8192
    assert (cfg.attention_multiplier, cfg.embedding_multiplier,
            cfg.residual_multiplier, cfg.logits_scaling) == (
                1 / 64, 12, 0.22, 8)
    # Not head_dim^-0.5, and what is left of it for q is a power of two.
    assert cfg.attention_multiplier != cfg.head_dim ** -0.5
    assert cfg.attention_multiplier * cfg.head_dim ** 0.5 == 0.125
    assert cfg.scanning_layers == 36 and cfg.rms_norm_eps == 1e-5


def test_the_cut_is_one_whole_period_at_an_eighth_of_the_vocabulary():
    cfg = cut.cut_config()
    assert cfg.layer_types == ("mamba",) * 5 + ("attention",) + (
        "mamba",) * 4
    assert cfg.vocab_size == 100352 // 8 == 12544
    public = gh.GraniteHybridConfig.from_public(cut.PUBLIC_CONFIG)
    for field in dataclasses.fields(cfg):
        if field.name not in ("layer_types", "vocab_size", "remat"):
            assert getattr(cfg, field.name) == getattr(public, field.name)
    row = jnp.zeros((1, 256), jnp.int32)
    shapes = jax.eval_shape(
        lambda rng, row: cut.custom_model().init(
            {"params": rng}, row, training=False),
        jax.random.PRNGKey(0), row)
    assert sum(int(np.prod(leaf.shape)) for leaf in
               jax.tree_util.tree_leaves(shapes["params"])) == 772_160_448
    assert "lm_head" not in shapes["params"]  # tied


@pytest.mark.parametrize("key, value, said", [
    ("num_local_experts", 8, "num_local_experts 8 is not built"),
    ("position_embedding_type", "rope", "position_embedding_type 'rope'"),
    ("tie_word_embeddings", False, "tie_word_embeddings False"),
    ("mamba_proj_bias", True, "mamba_proj_bias True"),
    ("attention_bias", True, "attention_bias True"),
    ("remat", "some", "one of"),
    ("layer_types", ("mamba", "conv"), "mixers are"),
    ("num_key_value_heads", 3, "do not split over 3 key/value heads"),
])
def test_what_the_block_does_not_build_is_refused(key, value, said):
    with pytest.raises(ValueError, match=said):
        dataclasses.replace(CONFIG, **{key: value})


def test_an_inner_width_that_is_not_expand_times_hidden_is_refused():
    public = dict(cut.PUBLIC_CONFIG, mamba_n_heads=48)
    with pytest.raises(ValueError, match="mamba_expand"):
        gh.GraniteHybridConfig.from_public(public)


@pytest.mark.parametrize("name", MULTIPLIERS)
def test_each_multiplier_set_to_one_changes_the_loss(tiny, name):
    """None of the four is dropped silently."""
    base = loss_of(CONFIG, tiny)
    assert np.isfinite(base)
    changed = loss_of(dataclasses.replace(CONFIG, **{name: 1.0}), tiny)
    assert abs(changed - base) > 1e-3, (name, base, changed)


def test_the_attention_multiplier_is_not_the_kernels_own_scale(tiny):
    """At head_dim^-0.5 (what the kernels apply by themselves) the loss is
    another: the published multiplier reaches the scores."""
    own = CONFIG.head_dim ** -0.5
    assert own != CONFIG.attention_multiplier
    assert abs(loss_of(dataclasses.replace(
        CONFIG, attention_multiplier=own), tiny) - loss_of(CONFIG, tiny)
    ) > 1e-3


@pytest.mark.parametrize("dtype, tolerance", [
    ("float32", 2e-5), ("bfloat16", 2e-2)])
def test_q_times_an_eighth_through_the_kernels_is_attention_at_a_64th(
        monkeypatch, dtype, tolerance):
    """The published head: 64 wide, attention_multiplier 1/64. The kernels
    scale by 64^-0.5 and take no other scale, so q goes in times 1/8,
    exactly: dense causal softmax(q k^T / 64) v comes out."""
    monkeypatch.setenv("EDL_FORCE_PALLAS_INTERPRET", "1")
    rng = np.random.default_rng(5)
    q, k, v = (jnp.asarray(
        rng.normal(size=(1, 2, 256, 64)).astype(np.float32) * 3.0
    ).astype(dtype) for _ in range(3))
    eighth = jnp.asarray(0.125, dtype)
    # Exact in either dtype: a power of two moves the exponent alone.
    np.testing.assert_array_equal(
        (q * eighth).astype(jnp.float32), q.astype(jnp.float32) / 8)
    got = fa.flash_attention(q * eighth, k, v, True)
    f32 = jnp.float32
    scores = jnp.einsum("bhqd,bhkd->bhqk", q.astype(f32), k.astype(f32),
                        precision="highest") / 64.0
    seen = jnp.tril(jnp.ones((256, 256), bool))
    want = jnp.einsum(
        "bhqk,bhkd->bhqd",
        jax.nn.softmax(jnp.where(seen, scores, -jnp.inf), axis=-1),
        v.astype(f32), precision="highest")
    np.testing.assert_allclose(
        got.astype(f32), want, atol=tolerance * float(jnp.max(jnp.abs(want))))
    # And it is not attention at the kernels' own 1/8.
    unscaled = fa.reference_attention(q.astype(f32), k.astype(f32),
                                      v.astype(f32), True)
    assert float(jnp.max(jnp.abs(unscaled - want))) > 0.1


def test_no_position_signal_but_the_mixers_carry_order(tiny):
    """Causal: a later token moves no earlier logit. An attention-only
    stack without positions would read a permuted past alike; with the
    mixers before it, swapping two past tokens moves the logits after
    them."""
    tokens, _, params = tiny
    model = gh.custom_model(CONFIG)
    logits = np.asarray(model.apply({"params": params}, tokens))
    later = tokens.copy()
    later[:, 20:] = (later[:, 20:] + 5) % 256
    np.testing.assert_allclose(
        np.asarray(model.apply({"params": params}, later))[:, :20],
        logits[:, :20], rtol=1e-5, atol=1e-5)
    swapped = tokens.copy()
    swapped[:, [3, 9]] = swapped[:, [9, 3]]
    moved = np.abs(np.asarray(
        model.apply({"params": params}, swapped)) - logits).max(axis=(0, 2))
    assert (moved[:3] == 0).all() and (moved[10:] > 1e-4).all()


def test_the_head_is_the_embedding_over_the_logits_scaling(tiny):
    tokens, _, params = tiny
    model = gh.custom_model(CONFIG)
    logits, state = model.apply(
        {"params": params}, tokens,
        capture_intermediates=lambda mdl, _: mdl.name == "norm")
    hidden = state["intermediates"]["norm"]["__call__"][0]
    with jax.default_matmul_precision("highest"):
        want = hidden @ params["embed_tokens"]["embedding"].T / 8.0
    np.testing.assert_allclose(logits, want, rtol=1e-4, atol=1e-4)


def test_the_step_counts_what_it_scans(tiny):
    tokens, _, params = tiny
    out = gh.custom_model(CONFIG).apply(
        {"params": params}, tokens, training=True)
    batch = tokens.shape[0]
    assert {k: float(v) for k, v in out["stats"].items()} == {
        "ssd_scan_tokens": batch * LENGTH * 3}
    # Evaluation hands back plain logits.
    assert gh.custom_model(CONFIG).apply(
        {"params": params}, tokens).shape == (batch, LENGTH, 256)


def test_where_the_kernels_run_every_scan_is_a_kernels(monkeypatch):
    """Where `ops/ssd_scan.py` runs its kernels (the TPU; here the
    interpreter), at sizes they tile, every scan of the compiled step is
    the kernels': under `SCAN_SCOPE` the step's scopes
    (`observability/step_scopes.py`, what a traced run writes beside its
    profile) hold `ssd_scan_fwd` and `ssd_scan_bwd` and nothing of
    `ssd_chunked`, which is all they hold where the kernels do not run;
    the statistics are the same either way, and the loss is the one the
    same model reads through `ssd_chunked`."""
    from elasticdl_tpu.layers.mamba2 import SCAN_SCOPE
    from elasticdl_tpu.observability import step_scopes

    config = dataclasses.replace(
        CONFIG, layer_types=("mamba", "attention", "mamba"),
        mamba_n_heads=8, mamba_d_head=64, mamba_d_state=128,
        mamba_chunk_size=128)
    rng = np.random.default_rng(4)
    tokens = rng.integers(0, 256, (2, 257)).astype(np.int32)
    model = gh.custom_model(config)
    params = model.init({"params": jax.random.PRNGKey(2)}, tokens[:, :-1])

    def loss_and_stats(p):
        out = model.apply(p, tokens[:, :-1], training=True)
        return gh.loss(tokens[:, 1:], out), out["stats"]

    def run():
        step = jax.jit(jax.value_and_grad(loss_and_stats, has_aux=True))
        ((loss, stats), _), text = step(params), step.lower(
            params).compile().as_text()
        scans = {}
        for row in step_scopes.rows_of(text)[1]:
            parts = row["scope"].split("/")
            if SCAN_SCOPE in parts:
                scans.setdefault(row["phase"], set()).update(
                    parts[parts.index(SCAN_SCOPE) + 1:])
                assert row["kind"] == step_scopes.MIXER
        return {k: float(v) for k, v in stats.items()}, float(loss), scans

    chunked_stats, chunked_loss, chunked_scans = run()
    monkeypatch.setenv("EDL_FORCE_PALLAS_INTERPRET", "1")
    stats, loss, scans = run()
    assert chunked_stats == stats == {"ssd_scan_tokens": 2 * 256 * 2}
    assert "ssd_chunked" in chunked_scans["fwd"] & chunked_scans["bwd"]
    assert not any("ssd_scan_" in s for s in set().union(
        *chunked_scans.values()))
    assert scans == {"fwd": {"ssd_scan_fwd"}, "bwd": {"ssd_scan_bwd"}}
    assert abs(loss - chunked_loss) < 1e-4 * abs(chunked_loss)


@pytest.mark.parametrize("dtype", ["float32", "bfloat16"])
def test_the_convolution_kernels_losses_are_the_default_stages(
        monkeypatch, dtype):
    """The tiny model's loss and gradients with the convolution stage as
    the kernels (interpreted) against the same model with the mixer's
    default stage, `conv_silu_split`, at the call site: float32 to its
    rounding; bfloat16 within what the default stage's own bfloat16 sums
    round (its d bias is a bfloat16 sum over every token)."""
    from elasticdl_tpu.layers import mamba2

    config = dataclasses.replace(
        CONFIG, layer_types=("mamba", "attention", "mamba"),
        mamba_n_heads=8, mamba_d_head=64, mamba_d_state=128,
        mamba_chunk_size=128, activation_dtype=dtype)
    rng = np.random.default_rng(5)
    tokens = rng.integers(0, 256, (2, 257)).astype(np.int32)
    model = gh.custom_model(config)
    params = model.init({"params": jax.random.PRNGKey(3)}, tokens[:, :-1])

    def run():
        return jax.value_and_grad(lambda p: gh.loss(
            tokens[:, 1:], model.apply(p, tokens[:, :-1], training=True))
        )(params)

    monkeypatch.setenv("EDL_FORCE_PALLAS_INTERPRET", "1")
    loss, grads = run()
    monkeypatch.setattr(gh, "causal_conv_silu", mamba2.conv_silu_split)
    want_loss, want_grads = run()
    tolerance = {"float32": 1e-4, "bfloat16": 6e-2}[dtype]
    assert abs(float(loss) - float(want_loss)) < tolerance * abs(
        float(want_loss))
    for got, want in zip(jax.tree_util.tree_leaves(grads),
                         jax.tree_util.tree_leaves(want_grads)):
        scale = float(jnp.max(jnp.abs(want))) + 1e-12
        assert float(jnp.max(jnp.abs(got - want))) <= tolerance * scale


def test_the_remat_policy_changes_no_loss_and_no_gradient(tiny):
    tokens, labels, params = tiny

    def grads(config):
        model = gh.custom_model(config)
        return jax.value_and_grad(lambda p: gh.loss(labels, model.apply(
            {"params": p}, tokens, training=True)))(params)

    want, want_grads = grads(CONFIG)
    got, got_grads = grads(dataclasses.replace(CONFIG, remat="dots"))
    assert float(got) == pytest.approx(float(want), rel=1e-6)
    for a, b in zip(jax.tree_util.tree_leaves(got_grads),
                    jax.tree_util.tree_leaves(want_grads)):
        np.testing.assert_allclose(
            a, b, rtol=1e-5, atol=1e-6 * float(jnp.max(jnp.abs(b)) + 1))


def test_the_model_def_module_keeps_the_model_spec_contract():
    from elasticdl_tpu.common.model_utils import get_model_spec

    spec = get_model_spec(cut.__name__)
    assert spec.module is cut
    assert cut.custom_model().config == cut.cut_config()
    assert cut.loss is gh.loss and cut.feed is gh.feed
