"""The gated short convolution against a per-position loop, and its
causality: position t reads positions t-2..t only."""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from elasticdl_tpu.layers.short_conv import GatedShortConv

D = 8


def layer(taps=3, bias=False):
    return GatedShortConv(d_model=D, conv_kernel=taps, use_conv_bias=bias,
                          dtype="float32")


def some(seed, shape):
    return jnp.asarray(np.random.default_rng(seed).normal(
        size=shape).astype(np.float32))


def by_position(params, u):
    """The equations, one position and one tap at a time, in float64."""
    u = np.asarray(u, np.float64)
    w_in = np.asarray(params["in_proj"]["kernel"], np.float64)
    w_out = np.asarray(params["out_proj"]["kernel"], np.float64)
    taps = np.asarray(params["conv_kernel"], np.float64)      # [L, D]
    out = np.zeros_like(u)
    for row in range(u.shape[0]):
        bcx = u[row] @ w_in
        b, c, x = bcx[:, :D], bcx[:, D:2 * D], bcx[:, 2 * D:]
        z = b * x
        for t in range(u.shape[1]):
            conv = np.zeros(D)
            for j in range(taps.shape[0]):
                back = taps.shape[0] - 1 - j
                if t - back >= 0:
                    conv += taps[j] * z[t - back]
            if "conv_bias" in params:
                conv += np.asarray(params["conv_bias"], np.float64)
            out[row, t] = (c[t] * conv) @ w_out
    return out


@pytest.mark.parametrize("taps,bias", [(3, False), (3, True), (4, False)])
def test_matches_the_loop_over_positions(taps, bias):
    u = some(1, (2, 11, D))
    variables = layer(taps, bias).init(jax.random.PRNGKey(0), u)
    # Larger weights than the initialiser's: sums that are not all rounding.
    params = jax.tree_util.tree_map(lambda a: a * 10.0, variables["params"])
    assert params["conv_kernel"].shape == (taps, D)
    assert ("conv_bias" in params) == bias
    with jax.default_matmul_precision("highest"):
        got = layer(taps, bias).apply({"params": params}, u)
    np.testing.assert_allclose(got, by_position(params, u), rtol=2e-4,
                               atol=2e-5)


def test_position_t_reads_t_minus_2_to_t_only():
    u = some(2, (1, 12, D))
    variables = layer().init(jax.random.PRNGKey(3), u)

    def out_at(u, t):
        return jnp.sum(layer().apply(variables, u)[0, t])

    for t in (0, 1, 5, 11):
        reads = np.asarray(jnp.any(jax.grad(out_at)(u, t)[0] != 0, axis=-1))
        assert list(np.nonzero(reads)[0]) == list(range(max(0, t - 2), t + 1))
