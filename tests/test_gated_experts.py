"""The gated (SwiGLU) form of the routed expert layer, in
tests/test_routed_experts.py's manner: the grouped product against a plain
loop, its hand-written backward against autodiff of a dense formulation
under uneven load, an expert with no assignment, a block that straddles two
experts, the shares test, and the epsilon under the normalising sum; and
the same at a width of whole lanes, where the loops carry y and dx as
[T, D / 128, 128]."""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from elasticdl_tpu.layers import moe

E, K, D, F = 16, 3, 12, 10
EPS = 1e-6


def layer(held=None, block=8, **more):
    return moe.RoutedExperts(
        num_experts=E, num_experts_per_tok=K, d_hidden=F, gated=True,
        held=held, topk_eps=EPS, block_rows=block, dtype="float32", **more)


def whole_variables(seed=0):
    variables = layer().init(jax.random.PRNGKey(seed), jnp.zeros((1, 4, D)))
    # Larger weights than the initialiser's, so that sums are not all
    # rounding.
    return jax.tree_util.tree_map(lambda a: a * 20.0, variables)


def share_of(variables, first, count):
    p = dict(variables["params"])
    p["w_gate_up"] = p["w_gate_up"][first:first + count]
    p["w_down"] = p["w_down"][first:first + count]
    return {"params": p, "buffers": variables["buffers"]}


def plain_layer(params, x, first, count, logits=None):
    """The equations, token by token and expert by expert, w1 and w3 apart;
    `logits` [T, E] stand in for the router's where given."""
    tokens = np.asarray(x, np.float64).reshape(-1, x.shape[-1])
    router = np.asarray(params["router"], np.float64)
    out = np.zeros_like(tokens)
    for t, row in enumerate(tokens):
        s = 1.0 / (1.0 + np.exp(
            -(router @ row if logits is None else logits[t])))
        chosen = np.argsort(-s, kind="stable")[:K]
        w = s[chosen] / (s[chosen].sum() + EPS)
        for e, weight in zip(chosen, w):
            if first <= e < first + count:
                w13 = np.asarray(params["w_gate_up"][e - first], np.float64)
                w1, w3 = w13[:, :F], w13[:, F:]
                w2 = np.asarray(params["w_down"][e - first], np.float64)
                g = row @ w1
                out[t] += weight * ((g / (1.0 + np.exp(-g))) * (row @ w3)) @ w2
    return out.reshape(x.shape)


def some_tokens(seed=1, shape=(2, 9, D)):
    return jnp.asarray(np.random.default_rng(seed).normal(
        size=shape).astype(np.float32))


@pytest.mark.parametrize("held", [None, (0, 4), (5, 3), (12, 4)])
@pytest.mark.parametrize("block", [1, 4, 64])
def test_gated_layer_matches_the_plain_loop(held, block):
    variables = whole_variables()
    first, count = held or (0, E)
    x = some_tokens()
    share = share_of(variables, first, count)
    assert share["params"]["w_gate_up"].shape == (count, D, 2 * F)
    with jax.default_matmul_precision("highest"):
        y, stats = layer(held, block).apply(share, x)
    np.testing.assert_allclose(
        y, plain_layer(share["params"], x, first, count), rtol=2e-4,
        atol=2e-5)
    tokens = x.shape[0] * x.shape[1]
    assert float(stats["moe_assignments"]) == tokens * K
    # The loops' rows: whole blocks, at least the assignments held.
    real, run = (float(stats[f"moe_block_rows_{n}"]) for n in ("real", "run"))
    assert real == float(stats["moe_assignments_held"])
    assert run % block == 0 and real <= run < real + count * block


def dense_experts(params, tokens, scores, first, count):
    """The held experts' part of the layer as dense products over every
    token, for autodiff to take apart."""
    chosen, w = moe.route_top_k(scores, jnp.zeros(E), K, True, 1.0, EPS)
    gates = jnp.sum(jax.nn.one_hot(chosen, E) * w[..., None], axis=1)
    out = jnp.zeros_like(tokens)
    for e in range(count):
        w1, w3 = jnp.split(params["w_gate_up"][e], 2, axis=-1)
        h = jax.nn.silu(tokens @ w1) * (tokens @ w3)
        out = out + gates[:, first + e, None] * (h @ params["w_down"][e])
    return out


def assert_trees_close(got, want):
    for g, w in zip(jax.tree_util.tree_leaves(got),
                    jax.tree_util.tree_leaves(want)):
        np.testing.assert_allclose(
            g, w, rtol=2e-4, atol=2e-5 * float(jnp.max(jnp.abs(w)) + 1))


def uneven_logits():
    """[T, E] that send 18 tokens' first choice to expert 5, their second
    to 6 or 7 by turns, and leave expert 8 without an assignment: with
    held (4, 6) and blocks of 4 rows expert 5 takes five blocks, the last
    half empty, and the runs of 6 and 7 start mid-way through `order`."""
    logits = np.full((18, E), -4.0)
    logits[:, 5] = 6.0
    logits[0::2, 6] = 3.0
    logits[1::2, 7] = 3.0
    logits[:, 9] = 1.0  # the third choice: held too
    logits += np.random.default_rng(0).normal(size=logits.shape) * 0.1
    return jnp.asarray(logits, jnp.float32)


def test_gated_gradients_match_autodiff_of_a_dense_formulation():
    """Uneven load, an expert with no assignment, blocks that end inside
    the next expert's run."""
    variables = share_of(whole_variables(), 4, 6)
    logits = uneven_logits()
    x = some_tokens(2, (2, 9, D))
    weight = some_tokens(3)
    chosen, _ = moe.route_top_k(
        jax.nn.sigmoid(logits), jnp.zeros(E), K, True, 1.0, EPS)
    plan = moe.plan_held_blocks(chosen, 4, 6, 4)
    counts = np.asarray(plan["counts"]).tolist()
    assert counts[1] == 18 and counts[4] == 0 and counts[2] == 9
    assert int(plan["n_blocks"]) > sum(counts) // 4
    # Block 4 is expert 5's last: two real rows, and its slice of `order`
    # runs on into expert 6's.
    e, start, _, _, real = moe._block_rows(plan, jnp.asarray(4), 4, K)
    assert int(e) == 1 and np.asarray(real).tolist() == [True, True, False,
                                                         False]
    assert int(start) + 4 > int(plan["group_start"][2])

    def dense(params, x, logits):
        return dense_experts(
            params, x.reshape(-1, D), jax.nn.sigmoid(logits), 4, 6
        ).reshape(x.shape)

    def grouped(params, x, logits):
        tokens = x.reshape(-1, D)
        chosen, w = moe.route_top_k(
            jax.nn.sigmoid(logits), jnp.zeros(E), K, True, 1.0, EPS)
        plan = moe.plan_held_blocks(chosen, 4, 6, 4)
        return moe.grouped_swiglu_experts(
            tokens, w, params["w_gate_up"], params["w_down"], plan, 4,
            K).reshape(x.shape)

    params = {k: variables["params"][k] for k in ("w_gate_up", "w_down")}
    with jax.default_matmul_precision("highest"):
        np.testing.assert_allclose(
            grouped(params, x, logits), dense(params, x, logits),
            rtol=2e-4, atol=2e-5)
        want = jax.grad(lambda *a: jnp.sum(dense(*a) * weight),
                        argnums=(0, 1, 2))(params, x, logits)
        got = jax.grad(lambda *a: jnp.sum(grouped(*a) * weight),
                       argnums=(0, 1, 2))(params, x, logits)
    assert_trees_close(got, want)
    # The expert without an assignment takes no gradient.
    assert not np.asarray(got[0]["w_gate_up"][4]).any()
    assert np.asarray(got[0]["w_gate_up"][1]).any()


# ---------- at a width of whole lanes: the carry in slabs of 128 ----------

WIDE, T, HELD, BLOCK = 256, 18, (4, 6), 4
# Which tokens each held expert (4 to 9) takes; a token's other choices
# fall on experts 0 to 2, which this share does not hold. A padded row of a
# block points at token 0.
LOADS = {
    # 5, 18, 7, 6, 3 and 1 rows: every last block has padding. Token 0 is
    # held by experts 4, 5 and 8 and not by 6, 7 and 9.
    "padding_in_every_last_block": {
        4: range(0, 5), 5: range(0, 18), 6: range(5, 12),
        7: range(12, 18), 8: range(0, 3), 9: [17]},
    # 8 and 4 rows end on a block's end (no padded row); 6 ends inside one.
    "runs_that_end_on_a_block_end": {
        4: range(0, 8), 5: range(8, 12), 6: range(2, 8), 7: range(12, 16)},
    # Experts 5 and 8 take nothing, between experts that do.
    "experts_without_an_assignment": {
        4: range(0, 7), 6: range(0, 18), 7: range(3, 9), 9: range(0, 2)},
    # No held expert takes token 0: what the padded rows add there is all
    # its row ever gets.
    "token_0_held_by_no_expert": {
        4: range(1, 6), 5: range(1, 18), 6: range(6, 9), 9: range(9, 12)},
}


def wide_case(load):
    """(variables of the share, x [2, 9, WIDE]) in which the router reads
    its logits off x's first E features, set so that each held expert takes
    the tokens `load` gives it."""
    chosen = [[e for e, ts in load.items() if t in ts] for t in range(T)]
    assert max(len(c) for c in chosen) <= K
    logits = np.full((T, E), -6.0) - 0.01 * np.arange(E)
    for t, held in enumerate(chosen):
        for rank, e in enumerate(held + [0, 1, 2][:K - len(held)]):
            logits[t, e] = 6.0 - 2.0 * rank
    x = np.random.default_rng(7).normal(size=(T, WIDE)).astype(np.float32)
    x[:, :E] = logits
    variables = layer().init(
        jax.random.PRNGKey(0), jnp.zeros((1, 4, WIDE)))
    variables = share_of(
        jax.tree_util.tree_map(lambda a: a * 20.0, variables), *HELD)
    variables["params"]["router"] = jnp.eye(E, WIDE)
    return variables, jnp.asarray(x.reshape(2, 9, WIDE))


@pytest.mark.parametrize("name", sorted(LOADS))
def test_gated_layer_in_slabs_matches_the_plain_loop(name):
    load = LOADS[name]
    variables, x = wide_case(load)
    experts, _ = moe.route_top_k(
        jax.nn.sigmoid(x.reshape(T, WIDE)[:, :E]), jnp.zeros(E), K, True,
        1.0, EPS)
    plan = moe.plan_held_blocks(experts, *HELD, BLOCK)
    counts = [len(load.get(HELD[0] + e, ())) for e in range(HELD[1])]
    assert np.asarray(plan["counts"]).tolist() == counts
    assert int(plan["n_blocks"]) == sum(-(-c // BLOCK) for c in counts)
    # The carry really is in slabs at this width.
    assert moe._row_slabs(jnp.zeros((T, WIDE))).shape == (T, 2, 128)
    with jax.default_matmul_precision("highest"):
        y, stats = layer(HELD, BLOCK).apply(variables, x)
    want = plain_layer(variables["params"], x, *HELD)
    # Sums of 256 terms that cancel: the tolerance follows the largest.
    np.testing.assert_allclose(
        y, want, rtol=2e-4, atol=2e-5 * (np.abs(want).max() + 1))
    assert float(stats["moe_block_rows_real"]) == sum(counts)
    assert float(stats["moe_block_rows_run"]) == BLOCK * int(plan["n_blocks"])
    if 0 not in {t for ts in load.values() for t in ts}:
        assert not np.asarray(y)[0, 0].any()


@pytest.mark.parametrize("name", sorted(LOADS))
def test_gated_gradients_in_slabs_match_the_dense_formulation(name):
    variables, x = wide_case(LOADS[name])
    weight = some_tokens(3, x.shape)

    def dense(params, x):
        tokens = x.reshape(T, WIDE)
        scores = jax.nn.sigmoid(jnp.einsum(
            "td,ed->te", tokens, params["router"],
            precision=jax.lax.Precision.HIGHEST))
        return dense_experts(params, tokens, scores, *HELD).reshape(x.shape)

    def via_layer(params, x):
        return layer(HELD, BLOCK).apply(
            {"params": params, "buffers": variables["buffers"]}, x)[0]

    with jax.default_matmul_precision("highest"):
        want = jax.grad(lambda p, x: jnp.sum(dense(p, x) * weight),
                        argnums=(0, 1))(variables["params"], x)
        got = jax.grad(lambda p, x: jnp.sum(via_layer(p, x) * weight),
                       argnums=(0, 1))(variables["params"], x)
    assert_trees_close(got, want)
    # An expert without an assignment takes no gradient; the others do.
    for e in range(HELD[1]):
        assert np.asarray(got[0]["w_gate_up"][e]).any() == bool(
            LOADS[name].get(HELD[0] + e))


def test_gated_layer_gradients_reach_the_router():
    variables = share_of(whole_variables(), 4, 6)
    x = some_tokens(2)

    def via_layer(params):
        return jnp.sum(layer((4, 6), 4).apply(
            {"params": params, "buffers": variables["buffers"]}, x)[0] ** 2)

    grads = jax.grad(via_layer)(variables["params"])
    assert set(grads) == {"router", "w_gate_up", "w_down"}
    assert all(np.asarray(g).any() for g in grads.values())


def test_all_eight_shares_add_up_to_the_uncut_layer():
    """16 experts over 8 chips, 2 each: the routed parts that the eight
    shares give (there is no shared expert to count once) are the whole
    layer, which is the plain loop over all 16."""
    variables = whole_variables()
    x = some_tokens(5)
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
        whole, plain_layer(variables["params"], x, 0, E), rtol=2e-4,
        atol=2e-5)
    assert held == float(whole_stats["moe_assignments"])


def test_the_epsilon_under_the_normalising_sum_is_an_argument():
    scores = jnp.asarray([[0.5, 0.25, 0.125, 0.0]])
    bias = jnp.zeros(4)
    _, as_before = moe.route_top_k(scores, bias, 2, True, 1.0)
    _, stated = moe.route_top_k(scores, bias, 2, True, 1.0, 1e-20)
    _, wide = moe.route_top_k(scores, bias, 2, True, 2.0, 0.25)
    assert np.asarray(as_before).tolist() == np.asarray(stated).tolist()
    np.testing.assert_allclose(as_before[0], [2 / 3, 1 / 3], rtol=1e-6)
    np.testing.assert_allclose(wide[0], [1.0, 0.5], rtol=1e-6)


def test_the_gated_form_takes_a_gated_shared_expert():
    """Since PR 55 `d_shared` in the gated form builds one SwiGLU MLP for
    every token (`shared_gate_up`, `shared_down`) where it raised;
    tests/test_kanana_moe.py holds it to the mathematics."""
    params = layer(d_shared=8).init(
        jax.random.PRNGKey(0), jnp.zeros((1, 2, D)))["params"]
    assert params["shared_gate_up"]["kernel"].shape == (D, 16)
    assert params["shared_down"]["kernel"].shape == (8, D)
    assert "shared_up" not in params
