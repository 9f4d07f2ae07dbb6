"""The chunked (SSD) state-space scan against the token-by-token
recurrence, values and gradients, and the mixer's pieces."""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from elasticdl_tpu.layers import mamba2

B, S, H, P, G, N = 2, 32, 4, 8, 2, 16
DIMS = (B, S, H, P, G, N)
# The shape granite-4.0-h-micro first ran the scan at, small: one row a
# batch, one group for all heads, the published chunk of 256.
ONE_GROUP = (1, 512, 4, 8, 1, 16)


def inputs(seed=0, s=S, dims=DIMS):
    bsz, _, h, p, g, n = dims
    rng = np.random.default_rng(seed)
    x = rng.normal(size=(bsz, s, h, p)).astype(np.float32)
    dt = np.log1p(np.exp(rng.normal(size=(bsz, s, h)) - 1.0)).astype(
        np.float32)
    a = -np.exp(rng.uniform(0.0, 2.5, size=(h,))).astype(np.float32)
    b = rng.normal(size=(bsz, s, g, n)).astype(np.float32)
    c = rng.normal(size=(bsz, s, g, n)).astype(np.float32)
    return tuple(jnp.asarray(v) for v in (x, dt, a, b, c))


def recurrence(x, dt, a, b, c):
    """h_t = exp(dt_t a) h_{t-1} + dt_t B_t x_t^T; y_t = C_t h_t, one token
    a step."""
    (_, _, h, p), n = x.shape, b.shape[-1]
    per = h // b.shape[2]
    bh = jnp.repeat(b, per, axis=2)
    ch = jnp.repeat(c, per, axis=2)

    def token(h, row):
        x_t, dt_t, b_t, c_t = row
        h = jnp.exp(dt_t * a)[..., None, None] * h + (
            dt_t[..., None, None] * x_t[..., :, None] * b_t[..., None, :])
        return h, jnp.sum(h * c_t[..., None, :], -1)

    rows = tuple(jnp.moveaxis(v, 1, 0) for v in (x, dt, bh, ch))
    _, y = jax.lax.scan(token, jnp.zeros((x.shape[0], h, p, n)), rows)
    return jnp.moveaxis(y, 0, 1)


@pytest.mark.parametrize("dims, chunk", [
    *((DIMS, chunk) for chunk in (1, 4, 8, 16, 32)),
    (ONE_GROUP, 256), (ONE_GROUP, 8), ((1, 32, 4, 8, 2, 16), 8),
    ((2, 512, 4, 8, 2, 16), 256),
], ids=lambda v: "x".join(map(str, v)) if isinstance(v, tuple) else str(v))
def test_chunked_scan_matches_the_recurrence(dims, chunk):
    args = inputs(s=dims[1], dims=dims)
    with jax.default_matmul_precision("highest"):
        want = recurrence(*args)
        got = mamba2.ssd_chunked(*args, chunk=chunk)
    # A chunk of 256 sums 256 float32 terms where the recurrence adds one
    # a step: the rounding is held against the largest output (67 and 44
    # here; 1.2e-5 and 8e-6 of it measured), not against each element.
    scale = float(jnp.max(jnp.abs(want))) if chunk > 32 else 1.0
    np.testing.assert_allclose(got, want, rtol=2e-5, atol=2e-5 * scale)


@pytest.mark.parametrize("dims, chunk", [
    (DIMS, 4), (DIMS, 16), (ONE_GROUP, 256),
], ids=lambda v: "x".join(map(str, v)) if isinstance(v, tuple) else str(v))
def test_chunked_scan_gradients_match_the_recurrence(dims, chunk):
    args = inputs(1, s=dims[1], dims=dims)
    weight = jnp.asarray(np.random.default_rng(2).normal(
        size=args[0].shape).astype(np.float32))

    def of(fn):
        return jax.grad(
            lambda *a: jnp.sum(fn(*a) * weight), argnums=(0, 1, 2, 3, 4)
        )(*args)

    with jax.default_matmul_precision("highest"):
        want = of(recurrence)
        got = of(lambda *a: mamba2.ssd_chunked(*a, chunk=chunk))
    for g, w in zip(got, want):
        np.testing.assert_allclose(
            g, w, rtol=1e-4, atol=1e-4 * float(jnp.max(jnp.abs(w))))


def test_a_sequence_that_is_no_multiple_of_the_chunk_is_refused():
    args = inputs(s=24)
    with pytest.raises(ValueError, match="not a multiple of the chunk"):
        mamba2.ssd_chunked(*args, chunk=16)


def test_heads_that_do_not_split_over_the_groups_are_refused():
    x, dt, a, b, c = inputs()
    with pytest.raises(ValueError, match="groups"):
        mamba2.ssd_chunked(x, dt, a, b[:, :, :1].repeat(3, 2),
                           c[:, :, :1].repeat(3, 2), chunk=8)


def test_bfloat16_products_stay_near_the_float32_scan():
    args = inputs(3)
    want = mamba2.ssd_chunked(*args, chunk=8)
    got = mamba2.ssd_chunked(*args, chunk=8, dtype=jnp.bfloat16)
    assert got.dtype == jnp.float32
    scale = float(jnp.max(jnp.abs(want)))
    assert float(jnp.max(jnp.abs(got - want))) < 0.03 * scale


def test_causal_depthwise_conv_sees_only_the_past():
    rng = np.random.default_rng(0)
    x = jnp.asarray(rng.normal(size=(1, 12, 3)).astype(np.float32))
    w = jnp.asarray(rng.normal(size=(4, 3)).astype(np.float32))
    bias = jnp.asarray(rng.normal(size=(3,)).astype(np.float32))
    y = mamba2.causal_depthwise_conv(x, w, bias)
    want = np.zeros((12, 3), np.float32)
    for t in range(12):
        for j in range(4):
            if t - 3 + j >= 0:
                want[t] += np.asarray(w[j]) * np.asarray(x[0, t - 3 + j])
    np.testing.assert_allclose(y[0], want + np.asarray(bias), rtol=1e-5,
                               atol=1e-5)
    later = x.at[0, 7:].set(0.0)
    np.testing.assert_array_equal(
        mamba2.causal_depthwise_conv(later, w, bias)[0, :7], y[0, :7])


def test_gated_group_norm_normalises_each_group():
    rng = np.random.default_rng(0)
    y = jnp.asarray(rng.normal(size=(2, 5, 16)).astype(np.float32))
    z = jnp.asarray(rng.normal(size=(2, 5, 16)).astype(np.float32))
    out = mamba2.gated_group_rms_norm(y, z, jnp.ones((16,)), 4, 0.0)
    groups = np.asarray(out).reshape(2, 5, 4, 4)
    np.testing.assert_allclose(
        np.mean(groups ** 2, -1), np.ones((2, 5, 4)), rtol=1e-5)


def test_mixer_shapes_parameters_and_dt_init():
    mixer = mamba2.Mamba2Mixer(
        d_model=24, num_heads=H, head_dim=P, n_groups=G, state_size=N,
        chunk_size=8, dtype="float32")
    u = jnp.asarray(np.random.default_rng(0).normal(
        size=(2, 16, 24)).astype(np.float32))
    variables = mixer.init(jax.random.PRNGKey(0), u)
    p = variables["params"]
    inner, conv = H * P, H * P + 2 * G * N
    assert p["in_proj"]["kernel"].shape == (24, inner + conv + H)
    assert p["conv_kernel"].shape == (4, conv)
    assert p["out_proj"]["kernel"].shape == (inner, 24)
    assert "bias" not in p["in_proj"] and "bias" not in p["out_proj"]
    dt = np.asarray(jax.nn.softplus(p["dt_bias"]))
    assert (dt >= 1e-4 - 1e-9).all() and (dt <= 0.1 + 1e-6).all()
    assert mixer.apply(variables, u).shape == u.shape
    # Causal: a later token changes no earlier output.
    changed = u.at[:, 9:].add(1.0)
    np.testing.assert_allclose(
        mixer.apply(variables, changed)[:, :9],
        mixer.apply(variables, u)[:, :9], rtol=1e-5, atol=1e-6)
