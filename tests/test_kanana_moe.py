"""The Kanana 2 expert decoder (models/kanana/kanana_moe.py): the program
against the plain reference on seeded weights over two Adam steps, the rope
turn in the interleaved pairing against a complex rotation, the one rope key
that every head shares, the gated shared expert of `RoutedExperts`, the
eight shares of the routed layer against the uncut reference, the public
keys, and the step's counters."""

import dataclasses
import os
import sys

import jax
import jax.numpy as jnp
import numpy as np
import optax
import pytest

from elasticdl_tpu.layers import moe
from elasticdl_tpu.models.kanana import kanana_2_30b_a3b_cut as cut
from elasticdl_tpu.models.kanana import kanana_moe
from elasticdl_tpu.models.lfm2.lfm2_moe import rotary

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
LENGTH = 32


@pytest.fixture(scope="module")
def ref():
    """benchmark/references/kanana_moe.py: the plain reference."""
    import importlib.util

    bench = os.path.join(REPO, "benchmark")
    for path in (REPO, bench):
        if path not in sys.path:
            sys.path.insert(0, path)
    spec = importlib.util.spec_from_file_location(
        "edlbench_ref_kanana_moe_for_the_model",
        os.path.join(bench, "references", "kanana_moe.py"))
    module = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(module)
    return module


def tiny(**changes):
    """(model, variables, tokens): float32 activations, weights large
    enough that the scores are of the order of 1."""
    config = kanana_moe.KananaMoeConfig(**{
        "experts_held": (2, 4), "expert_block_rows": 16,
        "force_load_balancing": True, "activation_dtype": "float32",
        "initializer_range": 0.125, **changes})
    model = kanana_moe.custom_model(config)
    tokens = np.random.default_rng(3).integers(
        0, 256, (1, LENGTH + 1)).astype(np.int32)
    variables = model.init({"params": jax.random.PRNGKey(1)}, tokens[:, :-1])
    return model, variables, tokens


# ---------- program against reference ----------


def test_loss_and_gradients_against_the_reference_over_two_adam_steps(ref):
    """Seeded weights, one record, float32 activations on the program's
    side; the loss, every parameter's gradient and the parameters after the
    update, twice. Both sides compute in float32 at precision highest; what
    differs is the order of the sums (the program's fused softmax over
    [q_nope | q_rope] [k_nope | k_rope]^T as one product, its rope in
    halves, its grouped expert products and optax against two products,
    pairs in place, plain loops and Adam written out), so the tolerances
    are float32's over four layers: 2e-5 of a gradient's largest entry,
    2e-6 of the loss. bfloat16 activations miss the loss's by twenty
    times or more, which the assertion on `low` shows."""
    model, variables, tokens = tiny()
    x, y = jnp.asarray(tokens[:, :-1]), jnp.asarray(tokens[:, 1:])
    params, buffers = variables["params"], variables["buffers"]
    cfg = dataclasses.asdict(model.config)
    plain = ref.make_loss(cfg, "float32")
    rate = kanana_moe.optimizer().learning_rate
    assert rate == 3e-5
    opt = {"learning_rate": rate, "beta_1": 0.9, "beta_2": 0.999,
           "epsilon": 1e-8}
    plain_step = ref.make_step(cfg, opt, "float32")
    tx = kanana_moe.optimizer().to_optax()

    def program(p, m=model):
        out = m.apply({"params": p, "buffers": buffers}, x, training=True)
        return kanana_moe.loss(y, out)

    rounded = kanana_moe.custom_model(dataclasses.replace(
        model.config, activation_dtype="bfloat16"))
    theirs = jax.tree_util.tree_map(jnp.array, params)
    m = jax.tree_util.tree_map(jnp.zeros_like, params)
    v = jax.tree_util.tree_map(jnp.zeros_like, params)
    state = tx.init(params)
    with jax.default_matmul_precision("highest"):
        for count in range(2):
            got, got_grads = jax.value_and_grad(program)(params)
            want_grads = jax.grad(
                lambda p: plain(p, buffers, x[0], y[0]))(theirs)
            low = float(program(params, rounded))
            want, theirs, m, v = plain_step(
                theirs, m, v, jnp.asarray(count, jnp.float32), buffers, x, y)
            assert float(got) == pytest.approx(float(want), rel=2e-6)
            assert abs(low - float(want)) > 20 * 2e-6 * float(want)
            flat = jax.tree_util.tree_leaves_with_path(got_grads)
            assert len(flat) == len(
                jax.tree_util.tree_leaves(want_grads)) > 30
            for (path, a), b in zip(
                    flat, jax.tree_util.tree_leaves(want_grads)):
                scale = float(jnp.max(jnp.abs(b))) or 1.0
                np.testing.assert_allclose(
                    np.asarray(a) / scale, np.asarray(b) / scale,
                    atol=2e-5, err_msg=jax.tree_util.keystr(path))
            updates, state = tx.update(got_grads, state, params)
            params = optax.apply_updates(params, updates)
            # Adam's first steps move every entry by about the learning
            # rate, by its gradient's sign: the two updates agree to a
            # hundredth of a step, but for the entry in a thousand whose
            # gradient is rounding's own size (its sign is then anyone's,
            # and the two differ by at most the two steps).
            for a, b in zip(jax.tree_util.tree_leaves(params),
                            jax.tree_util.tree_leaves(theirs)):
                off = np.abs(np.asarray(a) - np.asarray(b))
                assert off.max() <= (count + 1) * 2.05 * rate
                assert np.mean(off > rate / 100) < 1e-3


@pytest.mark.parametrize("bands", [2, 4])
def test_the_reference_in_bands_of_keys_is_the_reference_in_one(
        ref, bands, monkeypatch):
    """The reference multiplies a band of query rows against the keys up
    to the band's end alone (every later key is masked for each of its
    rows: an exact zero after the softmax). At the tests' length one block
    is the sequence and there is one band, so blocks of 4 rows here: loss
    and gradient in `bands` bands against one band over all the keys:
    float32 sums regrouped and no more (1.5e-6 of an entry seen)."""
    model, variables, tokens = tiny()
    cfg = dataclasses.asdict(model.config)
    params, buffers = variables["params"], variables["buffers"]
    x, y = jnp.asarray(tokens[0, :-1]), jnp.asarray(tokens[0, 1:])
    monkeypatch.setattr(ref, "QUERY_BLOCK", 4)

    def loss_and_grad(n):
        monkeypatch.setattr(ref, "KEY_BANDS", n)
        return jax.value_and_grad(ref.make_loss(cfg, "float32"))(
            params, buffers, x, y)

    (want, d_want), (got, d_got) = loss_and_grad(1), loss_and_grad(bands)
    assert abs(float(got) - float(want)) < 1e-6
    for a, b in zip(jax.tree_util.tree_leaves(d_want),
                    jax.tree_util.tree_leaves(d_got)):
        np.testing.assert_allclose(
            b, a, rtol=1e-5, atol=1e-5 * np.abs(a).max())


@pytest.mark.parametrize("fault", ["rope_off", "scale_128"])
def test_each_planted_fault_moves_the_reference_logits(ref, fault):
    """Both faults are of the mechanism: without the turn no position
    enters the scores, and a scale of the width without position alone
    sharpens every row's softmax: each moves the logits of every row after
    the first (a first row sees itself alone, whatever its score)."""
    model, variables, tokens = tiny()
    cfg = dataclasses.asdict(model.config)
    x = jnp.asarray(tokens[0, :-1])
    args = variables["params"], variables["buffers"], x
    want = ref.make_loss(cfg, "float32").logits(*args)
    got = ref.make_loss(cfg, "float32", fault).logits(*args)
    moved = np.abs(np.asarray(got - want)).max(axis=-1)
    assert moved[0] < 1e-5
    assert moved[1:].max() > 1e-2
    with pytest.raises(ValueError, match="unknown fault"):
        ref.make_loss(cfg, "float32", "no_such")


# ---------- the rope turn ----------


def test_the_interleaved_turn_is_the_complex_rotation_of_the_pairs():
    """`rotary(.., interleave=True)`: channel pairs (2i, 2i + 1) as complex
    numbers times exp(j p theta^(-2i / d)), left in the two halves (real
    parts, then imaginary parts) as HF leaves them; without `interleave`
    the function is what it was."""
    rng = np.random.default_rng(0)
    x = rng.normal(size=(2, 6, 3, 8)).astype(np.float32)
    theta = 1e6
    got = np.asarray(rotary(jnp.asarray(x), theta, interleave=True))
    z = x[..., 0::2] + 1j * x[..., 1::2]
    angle = np.arange(6)[:, None] * theta ** (-np.arange(0, 8, 2) / 8)
    z = z * np.exp(1j * angle)[None, :, None, :]
    np.testing.assert_allclose(got[..., :4], z.real, atol=1e-6)
    np.testing.assert_allclose(got[..., 4:], z.imag, atol=1e-6)
    # The halves pairing (the other models') is another turn of the same x.
    halves = np.asarray(rotary(jnp.asarray(x), theta))
    assert np.abs(halves - got).max() > 0.1
    np.testing.assert_array_equal(
        halves, np.asarray(rotary(jnp.asarray(x), theta, interleave=False)))
    # q and k permuted alike: the products are the published pairing's.
    k = rng.normal(size=(2, 6, 3, 8)).astype(np.float32)
    zk = (k[..., 0::2] + 1j * k[..., 1::2]) * np.exp(
        1j * angle)[None, :, None, :]
    want = np.einsum("bqhd,bkhd->bhqk", z, zk.conj()).real
    turned_k = np.asarray(rotary(jnp.asarray(k), theta, interleave=True))
    np.testing.assert_allclose(
        np.einsum("bqhd,bkhd->bhqk", got, turned_k), want, atol=1e-4)


def test_only_the_rope_channels_carry_position():
    """Rotary over part of the head: over a sequence of one row repeated,
    the rope part of the scores, q_rope k_rope^T as the layer turns them,
    depends on the distance between row and key alone, and does depend on
    it; the part without position is the same for every pair."""
    model, variables, _ = tiny(num_hidden_layers=1)
    cfg = model.config
    p = variables["params"]["layers_0"]["self_attn"]
    x = jnp.tile(jax.random.normal(
        jax.random.PRNGKey(2), (1, 1, 64)), (1, LENGTH, 1))
    q = jnp.einsum("bsd,dhe->bshe", x, p["q_proj"]["kernel"])
    down = x @ p["kv_a_proj_with_mqa"]["kernel"]
    q_rope = rotary(q[..., cfg.qk_nope_head_dim:], cfg.rope_theta,
                    interleave=True)
    k_rope = rotary(down[:, :, None, cfg.kv_lora_rank:], cfg.rope_theta,
                    interleave=True)
    scores = np.asarray(jnp.einsum("bqhe,bkhe->bhqk", q_rope, k_rope))
    np.testing.assert_allclose(
        scores[0, :, 5, 3], scores[0, :, 12, 10], atol=1e-4)
    assert np.abs(scores[0, :, 5, 3] - scores[0, :, 5, 4]).max() > 1e-3
    q_nope = np.asarray(q[0, :, :, :cfg.qk_nope_head_dim])
    np.testing.assert_allclose(q_nope[5], q_nope[12], atol=1e-6)


def test_the_one_rope_key_serves_every_head():
    """`kv_a_proj_with_mqa`'s last `qk_rope_head_dim` columns are ONE key
    for all the heads: the attention by hand, head by head, each with the
    same rope key, is the layer's output; and a change to one of those
    columns moves every head's part of the output."""
    model, variables, _ = tiny(num_hidden_layers=1)
    cfg = model.config
    attention = kanana_moe.LatentAttention(cfg)
    p = variables["params"]["layers_0"]["self_attn"]
    x = jax.random.normal(jax.random.PRNGKey(4), (1, LENGTH, 64))
    tables = cfg.rope_tables(LENGTH)
    with jax.default_matmul_precision("highest"):
        got = np.asarray(attention.apply({"params": p}, x, tables))
        nope, rope, rank = (cfg.qk_nope_head_dim, cfg.qk_rope_head_dim,
                            cfg.kv_lora_rank)
        q = jnp.einsum("bsd,dhe->bshe", x, p["q_proj"]["kernel"])[0]
        down = (x @ p["kv_a_proj_with_mqa"]["kernel"])[0]
        latent = down[:, :rank]
        latent = latent * jax.lax.rsqrt(
            jnp.mean(latent * latent, -1, keepdims=True) + cfg.rms_norm_eps
        ) * p["kv_a_layernorm"]["weight"]
        up = jnp.einsum("sr,rhe->she", latent, p["kv_b_proj"]["kernel"])
        k_rope = rotary(down[None, :, None, rank:], cfg.rope_theta,
                        interleave=True)[0, :, 0]
        q_rope = rotary(q[None, ..., nope:], cfg.rope_theta,
                        interleave=True)[0]
        seen = np.tril(np.ones((LENGTH, LENGTH), bool))
        heads = []
        for h in range(cfg.num_attention_heads):
            scores = (q[:, h, :nope] @ up[:, h, :nope].T
                      + q_rope[:, h] @ k_rope.T) * (nope + rope) ** -0.5
            weights = jax.nn.softmax(jnp.where(seen, scores, -1e30), -1)
            heads.append(weights @ up[:, h, nope:])
        want = jnp.concatenate(heads, -1) @ p["o_proj"]["kernel"]
        np.testing.assert_allclose(got[0], want, atol=2e-5)
        # One rope column changed: every head's scores move.
        moved = jax.tree_util.tree_map(jnp.array, p)
        moved["kv_a_proj_with_mqa"]["kernel"] = (
            moved["kv_a_proj_with_mqa"]["kernel"].at[:, rank].add(0.5))
        o_by_head = p["o_proj"]["kernel"].reshape(
            cfg.num_attention_heads, cfg.v_head_dim, -1)
        for h in range(cfg.num_attention_heads):
            only = jnp.zeros_like(o_by_head).at[h].set(o_by_head[h])
            part = {**p, "o_proj": {"kernel": only.reshape(-1, 64)}}
            other = {**moved, "o_proj": part["o_proj"]}
            a = attention.apply({"params": part}, x, tables)
            b = attention.apply({"params": other}, x, tables)
            assert float(jnp.max(jnp.abs(a - b))) > 1e-4, h


# ---------- the routed layer ----------

E, K, D, F = 128, 6, 16, 12


def layer(held=None, d_shared=2 * F, gated=True):
    return moe.RoutedExperts(
        num_experts=E, num_experts_per_tok=K, d_hidden=F, gated=gated,
        d_shared=d_shared, score="sigmoid", held=held,
        routed_scaling_factor=2.448, block_rows=16, dtype="float32")


def shared_by_hand(params, x):
    g, u = jnp.split(x @ params["shared_gate_up"]["kernel"], 2, axis=-1)
    return (jax.nn.silu(g) * u) @ params["shared_down"]["kernel"]


def test_the_gated_form_builds_a_swiglu_shared_expert():
    """`gated=True` with `d_shared`: one gated MLP for every token under
    `shared_gate_up` (the gate's columns first) and `shared_down`, added to
    the routed part; the relu^2 form keeps its `shared_up`."""
    variables = layer().init(jax.random.PRNGKey(0), jnp.zeros((1, 4, D)))
    params = jax.tree_util.tree_map(lambda a: a * 20.0, variables["params"])
    assert params["shared_gate_up"]["kernel"].shape == (D, 4 * F)
    assert params["shared_down"]["kernel"].shape == (2 * F, D)
    assert "shared_up" not in params
    x = jnp.asarray(np.random.default_rng(5).normal(
        size=(2, 24, D)).astype(np.float32))
    run = {"params": params, "buffers": variables["buffers"]}
    with jax.default_matmul_precision("highest"):
        whole, stats = layer().apply(run, x)
        routed_only = {k: v for k, v in params.items()
                       if not k.startswith("shared_")}
        routed, _ = layer(d_shared=0).apply(
            {"params": routed_only, "buffers": variables["buffers"]}, x)
        np.testing.assert_allclose(
            whole - routed, shared_by_hand(params, x), rtol=2e-4, atol=2e-5)
    assert float(jnp.max(jnp.abs(shared_by_hand(params, x)))) > 0.1
    assert {"moe_block_rows_run", "moe_block_rows_real"} <= set(stats)
    relu2 = layer(gated=False).init(
        jax.random.PRNGKey(0), jnp.zeros((1, 4, D)))["params"]
    assert {"shared_up", "shared_down", "w_up"} <= set(relu2)
    assert "shared_gate_up" not in relu2


def test_the_eight_shares_add_up_to_the_uncut_references_layer(ref):
    """128 experts over 8 chips, 16 each, 6 a token, as the deployment of
    the cut has them: the parts that `experts_held = (16 c, 16)` give, c =
    0 .. 7, with the shared experts (which every chip computes alike)
    counted once, are the plain reference's whole layer, which holds every
    expert; and every assignment falls on one chip."""
    variables = layer().init(jax.random.PRNGKey(0), jnp.zeros((1, 4, D)))
    variables = jax.tree_util.tree_map(lambda a: a * 20.0, variables)
    params = variables["params"]
    x = jnp.asarray(np.random.default_rng(5).normal(
        size=(1, 48, D)).astype(np.float32))
    cfg = {"rms_norm_eps": 1e-6, "qk_nope_head_dim": 4,
           "qk_rope_head_dim": 2, "kv_lora_rank": 4, "rope_theta": 1e6,
           "n_routed_experts": E, "num_experts_per_tok": K,
           "moe_intermediate_size": F, "n_shared_experts": 2,
           "norm_topk_prob": True, "routed_scaling_factor": 2.448}
    bias = variables["buffers"]["e_score_correction_bias"]
    with jax.default_matmul_precision("highest"):
        whole = ref.make_loss(cfg, "float32").experts(
            x[0], params, bias, None)
        shared = shared_by_hand(params, x[0])
        total, held = np.zeros(x.shape[1:], np.float64), 0.0
        for chip in range(8):
            p = dict(params)
            for name in ("w_gate_up", "w_down"):
                p[name] = p[name][16 * chip:16 * chip + 16]
            part, stats = layer((16 * chip, 16)).apply(
                {"params": p, "buffers": variables["buffers"]}, x)
            routed = np.asarray(part[0], np.float64) - np.asarray(shared)
            assert np.abs(routed).max() > 0
            total += routed
            held += float(stats["moe_assignments_held"])
    assert float(jnp.max(jnp.abs(shared))) > 0.1
    np.testing.assert_allclose(
        total + np.asarray(shared), whole, rtol=2e-4, atol=5e-5)
    assert held == 48 * K


# ---------- the contract ----------


def test_from_public_takes_the_public_keys_under_their_own_names():
    cfg = cut.cut_config()
    assert (cfg.hidden_size, cfg.num_attention_heads, cfg.qk_nope_head_dim,
            cfg.qk_rope_head_dim, cfg.v_head_dim, cfg.kv_lora_rank) == (
        2048, 32, 128, 64, 128, 512)
    assert cfg.qk_nope_head_dim + cfg.qk_rope_head_dim == 192 != (
        cfg.v_head_dim)
    assert (cfg.n_routed_experts, cfg.num_experts_per_tok,
            cfg.moe_intermediate_size, cfg.n_shared_experts,
            cfg.intermediate_size, cfg.experts_held) == (
        128, 6, 768, 2, 6144, (0, 16))
    assert (cfg.num_hidden_layers, cfg.first_k_dense_replace,
            cfg.vocab_size) == (6, 1, 16032)
    assert cfg.routed_scaling_factor == 2.448 and cfg.norm_topk_prob
    assert cfg.rope_theta == 1000000 and cfg.rope_interleave
    assert cfg.rms_norm_eps == 1e-6 and cfg.scoring_func == "sigmoid"
    assert cfg.q_lora_rank is None and cfg.rope_scaling is None
    assert cfg.force_load_balancing
    hash(cfg)  # a module attribute under nn.remat: hashable
    # The whole model: every published layer, one of them dense.
    whole = kanana_moe.KananaMoeConfig.from_public(cut.PUBLIC_CONFIG)
    assert (whole.num_hidden_layers, whole.first_k_dense_replace,
            whole.vocab_size, whole.experts_held) == (48, 1, 128256, None)
    later = kanana_moe.KananaMoeConfig.from_public(
        cut.PUBLIC_CONFIG, keep_layers=(6, 7, 8))
    assert (later.num_hidden_layers, later.first_k_dense_replace) == (3, 0)
    for key, value in (("q_lora_rank", 1536), ("n_group", 8),
                       ("topk_group", 4), ("scoring_func", "softmax"),
                       ("rope_scaling", {"type": "yarn", "factor": 40})):
        with pytest.raises(ValueError, match="is not built"):
            kanana_moe.KananaMoeConfig.from_public(
                dict(cut.PUBLIC_CONFIG, **{key: value}))
    with pytest.raises(ValueError, match="is routed"):
        kanana_moe.KananaMoeConfig.from_public(
            dict(cut.PUBLIC_CONFIG, moe_layer_freq=2))
    with pytest.raises(ValueError, match="no pairs"):
        kanana_moe.KananaMoeConfig(qk_rope_head_dim=7)


def test_the_step_hands_back_the_routed_layers_counts():
    model, variables, tokens = tiny(
        num_hidden_layers=4, first_k_dense_replace=1)
    x = np.repeat(tokens[:, :-1], 2, axis=0)
    out = model.apply(variables, x, training=True)
    stats = {k: float(v) for k, v in out["stats"].items()}
    # Three routed layers, two experts a token; the dense layer counts
    # nothing.
    assert stats["moe_assignments"] == 3 * 2 * LENGTH * 2
    assert {"moe_assignments_held", "moe_held_load_max",
            "moe_held_load_mean", "moe_block_rows_run",
            "moe_block_rows_real"} <= set(stats)
    assert out["logits"].shape == (2, LENGTH, 256)
    assert out["logits"].dtype == jnp.float32
    assert "mlp" in variables["params"]["layers_0"]
    assert set(variables["params"]["layers_0"]["mlp"]) == {
        "gate_proj", "up_proj", "down_proj"}
    assert set(variables["params"]["layers_1"]["mlp"]) == {
        "router", "w_gate_up", "w_down", "shared_gate_up", "shared_down"}
    assert list(variables["buffers"]) == ["layers_1", "layers_2", "layers_3"]
    # Nothing but dense layers: plain logits' twin without stats.
    dense, dense_vars, _ = tiny(num_hidden_layers=1)
    assert set(dense.apply(dense_vars, x, training=True)) == {"logits"}


def _loss_and_gradients(model, variables, tokens):
    """params -> (loss, gradients) of one record through `model`."""
    def f(params):
        out = model.apply(dict(variables, params=params), tokens[:, :-1],
                          training=True)
        return kanana_moe.loss(tokens[:, 1:], out)
    return jax.value_and_grad(f)


def test_remat_gives_the_same_loss_and_gradients():
    """A rematerialised layer computes what the layer computes and keeps
    its attention's bits: the loss and every gradient leaf to the bit."""
    model, variables, tokens = tiny()
    again = kanana_moe.custom_model(
        dataclasses.replace(model.config, remat_layers=(0, 2)))
    (a, ga), (b, gb) = (
        _loss_and_gradients(m, variables, tokens)(variables["params"])
        for m in (model, again))
    assert float(a) == float(b)
    for x, y in zip(jax.tree_util.tree_leaves(ga),
                    jax.tree_util.tree_leaves(gb)):
        np.testing.assert_array_equal(x, y)


@pytest.mark.parametrize("remat_layers", [(), (0, 2), (0, 1, 2)])
def test_a_rematerialised_layer_keeps_its_attention_result(
        monkeypatch, remat_layers):
    """The step as the chip would trace it: one forward flash call a layer
    and one backward, however many of the layers are rematerialised (the
    policy at the remat site saves `flash_attention.KEPT`, so no layer's
    backward pass runs the forward kernel again)."""
    from elasticdl_tpu.ops import flash_attention as fa

    model, variables, tokens = tiny(remat_layers=remat_layers)
    monkeypatch.delenv("EDL_FORCE_PALLAS_INTERPRET", raising=False)
    monkeypatch.setattr(fa, "_use_pallas", lambda: True)
    text = str(jax.make_jaxpr(_loss_and_gradients(
        model, variables, tokens))(variables["params"]))
    layers = model.config.num_hidden_layers
    assert text.count("name=mla_flash_fwd") == layers
    assert text.count("name=mla_flash_bwd") == layers
