"""The top-k routed expert layer against a plain loop; nothing dropped
under any skew; and the shares test: the routed parts of all the shares,
plus the shared expert once, add up to the uncut layer."""

import functools

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from elasticdl_tpu.layers import moe

E, K, D, F, FS = 16, 3, 12, 10, 20
SCALE = 2.5


def layer(held=None, block=8, d_shared=FS):
    return moe.RoutedExperts(
        num_experts=E, num_experts_per_tok=K, d_hidden=F, d_shared=d_shared,
        held=held, routed_scaling_factor=SCALE, block_rows=block,
        dtype="float32")


def whole_variables(seed=0):
    x = jnp.zeros((1, 4, D))
    variables = layer().init(jax.random.PRNGKey(seed), x)
    # Larger weights than the initialiser's, so that sums are not all
    # rounding.
    return jax.tree_util.tree_map(lambda a: a * 20.0, variables)


def share_of(variables, first, count):
    p = dict(variables["params"])
    p["w_up"] = p["w_up"][first:first + count]
    p["w_down"] = p["w_down"][first:first + count]
    return {"params": p, "buffers": variables["buffers"]}


def plain_layer(params, bias, x, first, count, shared=True, logits=None):
    """The equations, token by token and expert by expert; `logits` [T, E]
    stand in for the router's where given."""
    tokens = np.asarray(x, np.float64).reshape(-1, D)
    router = np.asarray(params["router"], np.float64)
    out = np.zeros_like(tokens)
    for t, row in enumerate(tokens):
        s = 1.0 / (1.0 + np.exp(
            -(router @ row if logits is None else logits[t])))
        chosen = np.argsort(-(s + np.asarray(bias)), kind="stable")[:K]
        w = s[chosen] / s[chosen].sum() * SCALE
        for e, weight in zip(chosen, w):
            if first <= e < first + count:
                up = np.asarray(params["w_up"][e - first], np.float64)
                down = np.asarray(params["w_down"][e - first], np.float64)
                out[t] += weight * (np.maximum(row @ up, 0) ** 2) @ down
        if shared:
            up = np.asarray(params["shared_up"]["kernel"], np.float64)
            down = np.asarray(params["shared_down"]["kernel"], np.float64)
            out[t] += (np.maximum(row @ up, 0) ** 2) @ down
    return out.reshape(x.shape)


def some_tokens(seed=1, shape=(2, 9, D)):
    return jnp.asarray(np.random.default_rng(seed).normal(
        size=shape).astype(np.float32))


@pytest.mark.parametrize("held", [None, (0, 4), (5, 3), (12, 4)])
@pytest.mark.parametrize("block", [1, 4, 64])
def test_layer_matches_the_plain_loop(held, block):
    variables = whole_variables()
    first, count = held or (0, E)
    x = some_tokens()
    with jax.default_matmul_precision("highest"):
        y, stats = layer(held, block).apply(
            share_of(variables, first, count), x)
    want = plain_layer(
        share_of(variables, first, count)["params"], np.zeros(E), x,
        first, count)
    np.testing.assert_allclose(y, want, rtol=2e-4, atol=2e-5)
    assert float(stats["moe_assignments"]) == x.shape[0] * x.shape[1] * K


def test_gradients_match_autodiff_of_a_dense_formulation():
    variables = share_of(whole_variables(), 4, 6)
    x = some_tokens(2)
    weight = some_tokens(3)

    def dense(params, x):
        tokens = x.reshape(-1, D)
        scores = jax.nn.sigmoid(jnp.einsum(
            "td,ed->te", tokens, params["router"]))
        chosen, w = moe.route_top_k(scores, jnp.zeros(E), K, True, SCALE)
        gates = jnp.sum(jax.nn.one_hot(chosen, E) * w[..., None], axis=1)
        out = jnp.square(jax.nn.relu(
            tokens @ params["shared_up"]["kernel"])
        ) @ params["shared_down"]["kernel"]
        for e in range(6):
            h = jnp.square(jax.nn.relu(tokens @ params["w_up"][e]))
            out = out + gates[:, 4 + e, None] * (h @ params["w_down"][e])
        return out.reshape(x.shape)

    def via_layer(params, x):
        return layer((4, 6), 4).apply(
            {"params": params, "buffers": variables["buffers"]}, x)[0]

    with jax.default_matmul_precision("highest"):
        want = jax.grad(lambda p, x: jnp.sum(dense(p, x) * weight),
                        argnums=(0, 1))(variables["params"], x)
        got = jax.grad(lambda p, x: jnp.sum(via_layer(p, x) * weight),
                       argnums=(0, 1))(variables["params"], x)
    flat_w, _ = jax.tree_util.tree_flatten(want)
    flat_g, _ = jax.tree_util.tree_flatten(got)
    for g, w in zip(flat_g, flat_w):
        np.testing.assert_allclose(
            g, w, rtol=2e-4, atol=2e-5 * float(jnp.max(jnp.abs(w)) + 1))


@pytest.mark.parametrize("block", [2, 8])
def test_every_token_on_one_expert_and_nothing_is_dropped(block):
    """A router whose scores send every token's first choice to expert 6:
    it takes all T assignments, far over any even share, and each token's
    output still carries that expert's part."""
    variables = whole_variables()
    params = dict(variables["params"])
    router = np.zeros((E, D), np.float32)
    router[6, 0] = 50.0   # expert 6 wins wherever x[..., 0] > 0
    router[7, 0] = 40.0
    router[8, 0] = 30.0
    params["router"] = jnp.asarray(router)
    x = jnp.abs(some_tokens(4, (1, 40, D))) + 0.1
    held = {"params": share_of({"params": params, "buffers":
                                variables["buffers"]}, 6, 1)["params"],
            "buffers": variables["buffers"]}
    with jax.default_matmul_precision("highest"):
        y, stats = layer((6, 1), block, d_shared=0).apply(
            {"params": {k: v for k, v in held["params"].items()
                        if not k.startswith("shared")},
             "buffers": variables["buffers"]}, x)
    assert float(stats["moe_assignments_held"]) == 40
    assert float(stats["moe_held_load_max"]) == 40
    want = plain_layer(held["params"], np.zeros(E), x, 6, 1, shared=False)
    np.testing.assert_allclose(y, want, rtol=2e-4, atol=2e-5)
    assert (np.abs(np.asarray(y)).sum(-1) > 0).all()


def test_the_shares_add_up_to_the_uncut_layer():
    """16 experts over 4 chips: the routed parts that the four shares
    give, plus the shared expert counted once, are the whole layer."""
    variables = whole_variables()
    x = some_tokens(5)
    with jax.default_matmul_precision("highest"):
        whole, whole_stats = layer().apply(variables, x)
        shared_only = plain_layer(
            variables["params"], np.zeros(E), x, 0, 0, shared=True)
        total = np.zeros(x.shape, np.float64)
        held = 0.0
        for first in range(0, E, 4):
            part, stats = layer((first, 4)).apply(
                share_of(variables, first, 4), x)
            total += np.asarray(part, np.float64) - shared_only
            held += float(stats["moe_assignments_held"])
    np.testing.assert_allclose(
        total + shared_only, whole, rtol=2e-4, atol=2e-5)
    # ... and they are the uncut reference layer.
    np.testing.assert_allclose(
        whole, plain_layer(variables["params"], np.zeros(E), x, 0, E),
        rtol=2e-4, atol=2e-5)
    # Every assignment fell on exactly one share.
    assert held == float(whole_stats["moe_assignments"])


def test_correction_bias_moves_the_choice_but_not_the_weights():
    scores = jnp.asarray([[0.9, 0.8, 0.1, 0.2]])
    chosen, w = moe.route_top_k(
        scores, jnp.asarray([0.0, 0.0, 1.0, 0.0]), 2, True, 1.0)
    assert sorted(np.asarray(chosen)[0].tolist()) == [0, 2]
    by_expert = dict(zip(np.asarray(chosen)[0].tolist(),
                         np.asarray(w)[0].tolist()))
    assert by_expert[0] == pytest.approx(0.9)
    assert by_expert[2] == pytest.approx(0.1)


def test_forced_load_balancing_routes_by_seeded_noise():
    """`force_balance_seed`: the choice and the weights follow uniform
    noise from the seed, whatever the router holds; every expert takes
    about its even share; the gradient still reaches the router."""
    variables = whole_variables()
    x = some_tokens(4, (4, 64, D))
    forced = layer(held=(4, 6)).clone(force_balance_seed=5)
    share = share_of(variables, 4, 6)
    y, stats = forced.apply(share, x)
    noise = np.asarray(jax.random.uniform(
        jax.random.PRNGKey(5), (4, 64, E)), np.float64).reshape(-1, E)
    want = plain_layer(share["params"], np.zeros(E), x, 4, 6, logits=noise)
    np.testing.assert_allclose(np.asarray(y), want, rtol=2e-4, atol=2e-4)
    even = 4 * 64 * K * 6 / E
    assert abs(float(stats["moe_assignments_held"]) - even) < 0.15 * even
    # Another router, the same routing.
    other = dict(share["params"], router=-share["params"]["router"])
    _, again = forced.apply(dict(share, params=other), x)
    assert float(again["moe_assignments_held"]) == float(
        stats["moe_assignments_held"])
    grads = jax.grad(lambda p: jnp.sum(forced.apply(
        dict(share, params=p), x)[0] ** 2))(share["params"])
    assert np.asarray(grads["router"]).any()


def test_plan_cuts_each_held_expert_into_blocks():
    experts = jnp.asarray([[0, 5], [5, 6], [5, 1], [6, 5], [2, 5]],
                          jnp.int32)
    plan = moe.plan_held_blocks(experts, first=5, count=2, block=2)
    assert np.asarray(plan["counts"]).tolist() == [5, 2]
    assert np.asarray(plan["group_start"]).tolist() == [0, 5]
    assert np.asarray(plan["block_end"]).tolist() == [3, 4]
    assert int(plan["n_blocks"]) == 4
    order = np.asarray(plan["order"])[:7].tolist()
    flat = np.asarray(experts).reshape(-1)
    assert [int(flat[i]) for i in order] == [5, 5, 5, 5, 5, 6, 6]


def test_an_expert_without_an_assignment_takes_no_block():
    experts = jnp.asarray([[0, 5], [5, 7], [5, 1]], jnp.int32)
    plan = moe.plan_held_blocks(experts, first=5, count=3, block=2)
    assert np.asarray(plan["counts"]).tolist() == [3, 0, 1]
    assert np.asarray(plan["block_end"]).tolist() == [2, 2, 3]
    assert int(plan["n_blocks"]) == 3
    # The block after expert 5's two is expert 7's: the empty one is
    # passed over.
    e, _, _, tokens, real = moe._block_rows(plan, jnp.asarray(2), 2, 2)
    assert int(e) == 2
    assert np.asarray(real).tolist() == [True, False]
    assert np.asarray(tokens).tolist() == [1, 0]


def test_held_experts_outside_the_router_are_refused():
    with pytest.raises(ValueError, match="not among"):
        layer((14, 4)).init(jax.random.PRNGKey(0), jnp.zeros((1, 2, D)))


# ---------- the pick of the chosen scores, against the gather ----------

# (experts, chosen a token, score): the SDAR, Mellum, LFM2 and Nemotron-H
# routers.
ROUTERS = [(128, 8, "softmax"), (64, 8, "sigmoid"), (64, 4, "sigmoid"),
           (128, 6, "sigmoid")]


def route_by_gather(scores, bias, k, norm_topk_prob, scaling_factor,
                    eps=1e-20):
    """`route_top_k` with the pick as `take_along_axis`, whose transpose
    is a scatter-add into zeros: what the layer ran before `pick_chosen`."""
    _, experts = jax.lax.top_k(scores + bias, k)
    weights = jnp.take_along_axis(scores, experts, axis=-1)
    if norm_topk_prob:
        weights = weights / (jnp.sum(weights, -1, keepdims=True) + eps)
    return experts.astype(jnp.int32), weights * scaling_factor


def router_case(e, k, score, signed, tokens=200):
    """Scores [T, E] as the router's activation gives them, or, `signed`,
    the raw logits (negative numbers among them), a correction bias that
    moves the choice, and a cotangent [T, k] with zeros of both signs."""
    rng = np.random.default_rng(e * 31 + k)
    logits = jnp.asarray(rng.normal(size=(tokens, e)) * 3.0, jnp.float32)
    scores = logits if signed else moe.SCORES[score](logits)
    bias = jnp.asarray(rng.normal(size=e) * 0.2, jnp.float32)
    cotangent = rng.normal(size=(tokens, k)).astype(np.float32)
    cotangent[::7, 0] = 0.0
    cotangent[3::11, -1] = -0.0
    return scores, bias, jnp.asarray(cotangent)


def same_bits(got, want):
    got, want = np.asarray(got), np.asarray(want)
    return (got.shape == want.shape and got.dtype == want.dtype
            and got.tobytes() == want.tobytes())


@pytest.mark.parametrize("signed", [False, True], ids=["scores", "signed"])
@pytest.mark.parametrize("e,k,score", ROUTERS)
def test_the_pick_is_the_gather_bit_for_bit_both_ways(e, k, score, signed):
    scores, bias, cotangent = router_case(e, k, score, signed)
    _, experts = jax.lax.top_k(scores + bias, k)
    # The bias moved some choice: the pick is not the k largest scores.
    assert not same_bits(experts, jax.lax.top_k(scores, k)[1])

    def gather(s):
        return jnp.take_along_axis(s, experts, axis=-1)

    def pick(s):
        return moe.pick_chosen(s, experts)

    for run in (lambda f: f, jax.jit):
        assert same_bits(run(pick)(scores), run(gather)(scores))
        want = run(jax.grad(lambda s: jnp.sum(gather(s) * cotangent)))(scores)
        got = run(jax.grad(lambda s: jnp.sum(pick(s) * cotangent)))(scores)
        assert same_bits(got, want)
    # What the scatter gave: a cotangent at each chosen place, +0.0 at
    # every other.
    assert int(np.count_nonzero(np.asarray(got))) <= scores.shape[0] * k
    assert not np.signbit(np.asarray(got)[np.asarray(got) == 0]).any()


def test_the_pick_keeps_the_choice_alone_for_its_backward():
    """Left to autodiff the select would keep its [T, k, E] mask (16.8 MB
    a layer at the SDAR shapes, six layers alive at once where the step
    has 40 MB of room); the rule keeps what the gather kept."""
    scores, bias, _ = router_case(64, 4, "sigmoid", False)
    _, experts = jax.lax.top_k(scores + bias, 4)
    _, back = jax.vjp(moe.pick_chosen, scores, experts)
    kept = [a.shape for a in jax.tree_util.tree_leaves(back)
            if hasattr(a, "shape")]
    assert kept == [experts.shape]


def test_the_pick_reads_an_index_outside_the_experts_as_the_gather_does():
    """Not what `top_k` gives, but what the gather's result was defined
    for: one below 0 counts from the end, one still outside gives NaN and
    takes no cotangent."""
    scores, _, cotangent = router_case(64, 4, "sigmoid", False, tokens=8)
    experts = jnp.asarray(
        [[0, 5, -1, 63], [64, 1, 2, 3], [-64, 7, -65, 9]] + 5 * [[4, 3, 2, 1]],
        jnp.int32)
    want, back = jax.vjp(
        lambda s: jnp.take_along_axis(s, experts, axis=-1), scores)
    got, back_pick = jax.vjp(lambda s: moe.pick_chosen(s, experts), scores)
    assert np.isnan(np.asarray(want)[1, 0]) and np.isnan(
        np.asarray(want)[2, 2])
    assert same_bits(got, want)
    assert same_bits(back_pick(cotangent)[0], back(cotangent)[0])


@functools.lru_cache(maxsize=None)
def _existing_route_cases():
    sixteen = jax.nn.sigmoid(jnp.einsum(
        "td,ed->te", some_tokens(2).reshape(-1, D),
        whole_variables()["params"]["router"]))
    uneven = np.full((18, E), -4.0)
    uneven[:, 5] = 6.0
    uneven[0::2, 6] = 3.0
    uneven[1::2, 7] = 3.0
    uneven[:, 9] = 1.0
    uneven += np.random.default_rng(0).normal(size=uneven.shape) * 0.1
    halves = jnp.asarray([[0.5, 0.25, 0.125, 0.0]])
    return {
        "dense_formulation": (sixteen, jnp.zeros(E), K, True, SCALE),
        "correction_bias": (jnp.asarray([[0.9, 0.8, 0.1, 0.2]]),
                            jnp.asarray([0.0, 0.0, 1.0, 0.0]), 2, True, 1.0),
        "gated_uneven": (jax.nn.sigmoid(jnp.asarray(uneven, jnp.float32)),
                         jnp.zeros(E), K, True, 1.0, 1e-6),
        "eps_as_before": (halves, jnp.zeros(4), 2, True, 1.0),
        "eps_wide": (halves, jnp.zeros(4), 2, True, 2.0, 0.25),
        "not_normed": (sixteen, jnp.zeros(E), K, False, SCALE),
    }


@pytest.mark.parametrize("case", sorted(_existing_route_cases()))
def test_route_top_k_is_unchanged_by_the_pick(case):
    """The calls of this file's and tests/test_gated_experts.py's tests:
    the choice, the weights and the weights' gradient to the scores are
    the gather's bits."""
    args = _existing_route_cases()[case]
    experts, weights = moe.route_top_k(*args)
    want_experts, want_weights = route_by_gather(*args)
    assert same_bits(experts, want_experts)
    assert same_bits(weights, want_weights)
    cotangent = jnp.asarray(np.random.default_rng(5).normal(
        size=weights.shape).astype(np.float32))
    scores, rest = args[0], args[1:]
    got, want = (
        jax.jit(jax.grad(
            lambda s: jnp.sum(route(s, *rest)[1] * cotangent)))(scores)
        for route in (moe.route_top_k, route_by_gather))
    assert same_bits(got, want)
