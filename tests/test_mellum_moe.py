"""The Mellum 2 expert decoder (models/mellum/mellum_moe.py): what a logit
may depend on by kind of layer, the YaRN table against values worked out by
hand, rotary under a table and a scale, the four shares of the routed
layer, the public keys, and the step's counters."""

import dataclasses
import math

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from elasticdl_tpu.layers import moe
from elasticdl_tpu.models.lfm2.lfm2_moe import rotary
from elasticdl_tpu.models.mellum import mellum2_12b_a2_5b_cut as cut
from elasticdl_tpu.models.mellum import mellum_moe
from elasticdl_tpu.models.mellum.mellum_moe import BAND, FULL

LENGTH, WINDOW = 48, 8


def tiny(layer_types):
    config = mellum_moe.MellumMoeConfig(
        layer_types=layer_types, sliding_window=WINDOW,
        expert_block_rows=16, activation_dtype="float32")
    model = mellum_moe.custom_model(config)
    tokens = np.random.default_rng(3).integers(
        0, 256, (1, LENGTH)).astype(np.int32)
    variables = model.init({"params": jax.random.PRNGKey(1)}, tokens)
    # Larger weights than the initialiser's, so that a change moves logits
    # by more than rounding.
    variables = dict(variables, params=jax.tree_util.tree_map(
        lambda a: a * 8.0 if a.ndim > 1 else a, variables["params"]))
    return model, variables, tokens


def moved_rows(model, variables, tokens, at):
    other = tokens.copy()
    other[0, at] = (other[0, at] + 1) % 256
    a = np.asarray(model.apply(variables, tokens))
    b = np.asarray(model.apply(variables, other))
    return np.flatnonzero(np.abs(a - b).max(axis=(0, 2)) > 1e-6)


@pytest.mark.parametrize("layers,reach", [
    ((BAND,), WINDOW - 1), ((BAND, BAND), 2 * (WINDOW - 1)),
    ((FULL,), LENGTH), ((BAND, FULL), LENGTH)])
def test_a_token_moves_what_the_layers_masks_let_it(layers, reach):
    """Under windowed layers alone a token reaches `window - 1` rows ahead
    a layer and none behind; one full layer lets it reach every later
    row."""
    model, variables, tokens = tiny(layers)
    at = 5
    rows = moved_rows(model, variables, tokens, at)
    assert rows.min() == at
    assert rows.max() == min(LENGTH - 1, at + reach)


# ---------- the rope tables ----------


def test_the_yarn_table_against_values_worked_out_by_hand():
    """`rope_parameters.full_attention` of the public config at head_dim
    128. n(t) = 128 ln(8192 / (2 pi t)) / (2 ln 500000): n(32) = 18.08,
    n(1) = 34.98, so the ramp runs from frequency 18 to 35 of 64."""
    rope = cut.PUBLIC_CONFIG["rope_parameters"]["full_attention"]
    ln_theta = math.log(500000)
    assert 128 * math.log(8192 / (2 * math.pi * 32)) / (2 * ln_theta) == \
        pytest.approx(18.081, abs=1e-3)
    assert 128 * math.log(8192 / (2 * math.pi)) / (2 * ln_theta) == \
        pytest.approx(34.984, abs=1e-3)
    table = mellum_moe.yarn_inv_freq(rope, 128)
    assert table.shape == (64,)

    def own(i):
        return 500000 ** (-2 * i / 128)

    # Below the ramp theta's own frequency, above it a sixteenth of it,
    # on it the mix: frequency 27 lies 9 / 17 of the way.
    assert table[0] == 1.0 and table[18] == pytest.approx(own(18), rel=1e-12)
    assert table[10] == pytest.approx(0.128687, rel=1e-5)
    assert table[10] == pytest.approx(own(10), rel=1e-12)
    assert table[35] == pytest.approx(own(35) / 16, rel=1e-12)
    assert table[63] == pytest.approx(own(63) / 16, rel=1e-12)
    assert table[63] == pytest.approx(1.53446e-7, rel=1e-5)
    ramp = 9 / 17
    assert table[27] == pytest.approx(
        (1 - ramp) * own(27) + ramp * own(27) / 16, rel=1e-12)
    assert table[27] == pytest.approx(0.00394228 * (1 - ramp * 15 / 16),
                                      rel=1e-5)
    assert (np.diff(table) < 0).all()
    inv_freq, scale = mellum_moe.rope_table(rope, 128)
    assert inv_freq.dtype == np.float32
    np.testing.assert_allclose(inv_freq, table, rtol=1e-7)
    # The config's own attention_factor, which is 0.1 ln 16 + 1.
    assert scale == 1.2772588722239782
    assert scale == pytest.approx(0.1 * math.log(16) + 1, rel=1e-15)
    assert mellum_moe.rope_table(
        dict(rope, attention_factor=None), 128)[1] == pytest.approx(scale)
    # The windowed layers' table is theta's own, unscaled.
    inv_freq, scale = mellum_moe.rope_table(
        cut.PUBLIC_CONFIG["rope_parameters"]["sliding_attention"], 128)
    assert scale is None
    np.testing.assert_allclose(
        inv_freq, [own(i) for i in range(64)], rtol=1e-7)
    with pytest.raises(ValueError, match="built are default and yarn"):
        mellum_moe.rope_table({"rope_type": "llama3", "rope_theta": 1.0}, 8)


def test_rotary_takes_a_table_and_a_scale():
    """One function: theta's table handed in is theta's rotary to the bit;
    a scale multiplies the result; another table turns by its own
    angles."""
    x = jnp.asarray(np.random.default_rng(0).normal(
        size=(1, 6, 2, 8)).astype(np.float32))
    theta = 10000.0
    own = theta ** (-jnp.arange(0, 8, 2, dtype=jnp.float32) / 8)
    np.testing.assert_array_equal(
        rotary(x, theta), rotary(x, None, inv_freq=own))
    np.testing.assert_allclose(
        rotary(x, None, inv_freq=own, scale=1.25), 1.25 * rotary(x, theta),
        rtol=1e-5, atol=1e-6)
    table = np.asarray([0.5, 0.25, 0.125, 0.0625], np.float32)
    got = np.asarray(rotary(x, None, inv_freq=table))
    angles = np.arange(6)[:, None] * table[None]
    cos, sin = (np.concatenate([f(angles)] * 2, -1)[None, :, None, :]
                for f in (np.cos, np.sin))
    x = np.asarray(x)
    half = np.concatenate([-x[..., 4:], x[..., :4]], -1)
    np.testing.assert_allclose(got, x * cos + half * sin, atol=1e-6)


def test_each_kind_of_layer_turns_by_its_own_table():
    """The same weights under the two kinds at a window over the sequence:
    the masks are the same, so the tables alone make the difference."""
    config = mellum_moe.MellumMoeConfig(
        layer_types=(BAND,), sliding_window=LENGTH,
        activation_dtype="float32")
    tokens = np.random.default_rng(3).integers(
        0, 256, (1, LENGTH)).astype(np.int32)
    band = mellum_moe.custom_model(config)
    variables = band.init({"params": jax.random.PRNGKey(1)}, tokens)
    variables = dict(variables, params=jax.tree_util.tree_map(
        lambda a: a * 8.0 if a.ndim > 1 else a, variables["params"]))
    full = mellum_moe.custom_model(
        dataclasses.replace(config, layer_types=(FULL,)))
    same_table = mellum_moe.custom_model(dataclasses.replace(
        config, layer_types=(FULL,), rope_parameters=mellum_moe._frozen(
            {FULL: mellum_moe.DEFAULT_ROPE[BAND]})))
    a, b, c = (np.asarray(m.apply(variables, tokens))
               for m in (band, full, same_table))
    assert np.abs(a - b).max() > 1e-3
    np.testing.assert_allclose(a, c, atol=1e-6)


# ---------- the routed layer's shares ----------

E, K, D, F = 64, 8, 16, 12


def layer(held=None, block=16):
    return moe.RoutedExperts(
        num_experts=E, num_experts_per_tok=K, d_hidden=F, gated=True,
        score="softmax", held=held, topk_eps=0.0, block_rows=block,
        dtype="float32")


def test_the_four_shares_of_the_routed_layer_add_up_to_the_uncut_one():
    """64 experts over 4 chips, 16 each, 8 a token, as the deployment of
    the cut has them: the parts that `experts_held = (16 c, 16)` give, c =
    0 .. 3, are the whole layer, and every assignment falls on one chip."""
    variables = layer().init(jax.random.PRNGKey(0), jnp.zeros((1, 4, D)))
    variables = jax.tree_util.tree_map(lambda a: a * 20.0, variables)
    x = jnp.asarray(np.random.default_rng(5).normal(
        size=(2, 24, D)).astype(np.float32))
    with jax.default_matmul_precision("highest"):
        whole, whole_stats = layer().apply(variables, x)
        total, held = np.zeros(x.shape, np.float64), 0.0
        for chip in range(4):
            p = dict(variables["params"])
            for name in ("w_gate_up", "w_down"):
                p[name] = p[name][16 * chip:16 * chip + 16]
            part, stats = layer((16 * chip, 16)).apply(
                {"params": p, "buffers": variables["buffers"]}, x)
            assert np.abs(np.asarray(part)).max() > 0
            total += np.asarray(part, np.float64)
            held += float(stats["moe_assignments_held"])
    np.testing.assert_allclose(total, whole, rtol=2e-4, atol=2e-5)
    assert held == float(whole_stats["moe_assignments"]) == 2 * 24 * K


# ---------- the contract ----------


def test_from_public_takes_the_public_keys_under_their_own_names():
    cfg = cut.cut_config()
    assert (cfg.hidden_size, cfg.num_attention_heads,
            cfg.num_key_value_heads, cfg.head_dim) == (2304, 32, 4, 128)
    assert cfg.head_dim != cfg.hidden_size // cfg.num_attention_heads
    assert (cfg.num_experts, cfg.num_experts_per_tok,
            cfg.moe_intermediate_size, cfg.experts_held) == (
        64, 8, 896, (0, 16))
    assert cfg.layer_types == (BAND, BAND, BAND, FULL)
    assert (cfg.num_hidden_layers, cfg.vocab_size, cfg.sliding_window) == (
        4, 24576, 1024)
    assert cfg.rms_norm_eps == 1e-6 and cfg.norm_topk_prob
    assert cfg.rope(FULL) == cut.PUBLIC_CONFIG["rope_parameters"][FULL]
    assert cfg.rope(BAND) == {"rope_type": "default", "rope_theta": 500000}
    assert cfg.force_load_balancing
    hash(cfg)  # a module attribute under nn.remat: hashable
    # The whole model: every published layer, its kind its own.
    whole = mellum_moe.MellumMoeConfig.from_public(cut.PUBLIC_CONFIG)
    assert whole.num_hidden_layers == 28
    assert whole.layer_types.count(FULL) == 7
    assert whole.layer_types[3::4] == (FULL,) * 7
    kept = mellum_moe.MellumMoeConfig.from_public(
        cut.PUBLIC_CONFIG, keep_layers=(2, 3, 4))
    assert kept.layer_types == (BAND, FULL, BAND)
    with pytest.raises(ValueError, match="every layer"):
        mellum_moe.MellumMoeConfig.from_public(
            dict(cut.PUBLIC_CONFIG, mlp_layer_types=["dense"] * 28))
    with pytest.raises(ValueError, match="kinds are"):
        mellum_moe.MellumMoeConfig(layer_types=("conv",))
    with pytest.raises(ValueError, match="do not split"):
        mellum_moe.MellumMoeConfig(
            num_attention_heads=4, num_key_value_heads=3)


def test_the_step_hands_back_the_bands_scores_needed_and_run():
    model, variables, tokens = tiny((BAND, BAND, FULL, BAND))
    out = model.apply(variables, np.repeat(tokens, 2, axis=0), training=True)
    stats = {k: float(v) for k, v in out["stats"].items()}
    calls = 2 * 4  # batch * heads
    band = WINDOW * (WINDOW + 1) // 2 + (LENGTH - WINDOW) * WINDOW
    assert stats["band_scores_needed"] == 3 * calls * band
    assert {k for k in stats if not k.startswith("moe_")} == {
        "band_scores_needed", "band_scores_run"}
    # Tiles of one window: each row of tiles runs two, but the first.
    tiles = 2 * (LENGTH // WINDOW) - 1
    assert stats["band_scores_run"] == 3 * calls * tiles * WINDOW * WINDOW
    assert stats["moe_assignments"] == 4 * 2 * LENGTH * 2
    assert {"moe_assignments_held", "moe_held_load_max",
            "moe_held_load_mean"} <= set(stats)
    assert out["logits"].shape == (2, LENGTH, 256)
    assert out["logits"].dtype == jnp.float32
    assert mellum_moe.attention_scores(cut.cut_config(), 1, 16384) == {
        "band_scores_needed": 3 * 32 * 16_253_440,
        "band_scores_run": 3 * 32 * 31 * 1024 * 1024}


def test_remat_gives_the_same_loss_and_gradients():
    model, variables, tokens = tiny((BAND, FULL))
    again = mellum_moe.custom_model(
        dataclasses.replace(model.config, remat_layers=(1,)))

    def loss_of(m):
        def f(params):
            out = m.apply(dict(variables, params=params), tokens[:, :-1],
                          training=True)
            return mellum_moe.loss(tokens[:, 1:], out)
        return jax.value_and_grad(f)(variables["params"])

    (a, ga), (b, gb) = loss_of(model), loss_of(again)
    assert float(a) == pytest.approx(float(b), rel=1e-6)
    for x, y in zip(jax.tree_util.tree_leaves(ga),
                    jax.tree_util.tree_leaves(gb)):
        np.testing.assert_allclose(x, y, atol=1e-5)


# ---------- the q / k stage: ops/qk_rotary.py at the call site ----------


def _kernel_sized(layer_types=(BAND, FULL, BAND), remat_layers=()):
    """A model whose heads the kernels tile (128 lanes), bfloat16
    activations."""
    config = mellum_moe.MellumMoeConfig(
        layer_types=layer_types, sliding_window=WINDOW, hidden_size=256,
        num_attention_heads=2, num_key_value_heads=1, head_dim=128,
        expert_block_rows=16, remat_layers=remat_layers)
    model = mellum_moe.custom_model(config)
    tokens = np.random.default_rng(5).integers(
        0, 256, (2, LENGTH)).astype(np.int32)
    variables = model.init({"params": jax.random.PRNGKey(4)}, tokens)
    return model, variables, tokens


def _loss_and_gradients(model, variables, tokens):
    def f(params):
        return mellum_moe.loss(tokens[:, 1:], model.apply(
            dict(variables, params=params), tokens[:, :-1], training=True))

    return jax.jit(jax.value_and_grad(f))(variables["params"])


@pytest.mark.parametrize("remat_layers", [(), (0, 1)], ids=["plain", "remat"])
def test_the_loss_and_gradients_are_the_parents_call_sites_bits(
        monkeypatch, remat_layers):
    """Off the TPU the op is the parent's expression over tables built once
    a kind of layer: with `rotary(head_norm(.))` by the layer's own table
    and scale, the cast and the transpose put back at the call site (the
    parent of PR 53), the loss and every gradient keep their bits under a
    jit, rematerialised layers or none."""
    from elasticdl_tpu.models.nemotron_h.nemotron_h import rms_norm

    model, variables, tokens = _kernel_sized(remat_layers=remat_layers)
    got = _loss_and_gradients(model, variables, tokens)

    def parents(x, weight, eps, inv_freq, scale):
        turned = rotary(rms_norm(x, weight, eps), None, inv_freq=inv_freq,
                        scale=None if scale is None else scale[0])
        return jnp.swapaxes(turned.astype(x.dtype), 1, 2)

    # The table's description in the tables' place, down to the call site.
    monkeypatch.setattr(
        mellum_moe, "rope_tables",
        lambda positions, inv_freq, scale: (
            jnp.asarray(inv_freq),
            None if scale is None else np.asarray([scale], np.float32)))
    monkeypatch.setattr(mellum_moe, "qk_rotary", parents)
    want = _loss_and_gradients(model, variables, tokens)
    for g, w in zip(jax.tree_util.tree_leaves(got),
                    jax.tree_util.tree_leaves(want)):
        np.testing.assert_array_equal(g, w)


@pytest.mark.parametrize("kernels", [False, True], ids=["cpu", "kernels"])
def test_the_rope_tables_are_built_once_a_kind_of_layer(monkeypatch, kernels):
    """Three windowed layers and two full ones: two cos and two sin a step
    (the default table's and YaRN's), not one a layer's q and k; where the
    kernels run, the layers of both kinds call one `qk_rotary_fwd` /
    `qk_rotary_bwd` pair a shape, the table an operand [1, S, head_dim]."""
    from elasticdl_tpu.ops import flash_attention as fa
    from test_ssd_scan import _equations

    model, variables, tokens = _kernel_sized(
        (BAND, FULL, BAND, BAND, FULL))
    if kernels:
        monkeypatch.delenv("EDL_FORCE_PALLAS_INTERPRET", raising=False)
        monkeypatch.setattr(fa, "_use_pallas", lambda: True)
    jaxpr = jax.make_jaxpr(jax.grad(lambda p: mellum_moe.loss(
        tokens, model.apply(
            dict(variables, params=p), tokens, training=True))))(
                variables["params"]).jaxpr
    eqns = list(_equations(jaxpr))
    names = [e.primitive.name for e in eqns]
    assert names.count("cos") == 2 and names.count("sin") == 2
    calls = [e for e in eqns if e.primitive.name == "pallas_call"
             and e.params["name"].startswith("qk_rotary")]
    assert sorted(e.params["name"] for e in calls) == (
        10 * ["qk_rotary_bwd"] + 10 * ["qk_rotary_fwd"] if kernels else [])
    for call in calls:
        assert [v.aval.shape for v in call.invars[2:4]] == 2 * [
            (2, LENGTH, 128)]
