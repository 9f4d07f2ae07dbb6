"""The mixer's convolution stage with its backward written out
(ops/causal_conv.py): the kernel pair interpreted on the CPU against
`conv_silu_split` and `jax.grad` of it, at two tilings; the zeros before a
sequence, the halo across a turn's and a tile's edge in both passes, d
weight and d bias summed over every lane group, turn, tile and row; what
the kernels refuse to tile; and which stage a mixer calls."""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from elasticdl_tpu.layers import mamba2
from elasticdl_tpu.ops import causal_conv as cc
from test_ssd_scan import _primitives

F32 = jnp.float32
# The time tile's lanes and a turn's (the chip's are 8192 and 4096), so
# that a short sequence has several tiles and a tile several turns. At 128
# lanes a turn is one lane group; at 256 it is two, so the backward's fold
# of d weight and d bias over the groups and the window of a turn past the
# tile's first (`at > 0`) run as on the chip.
TILINGS = {"tile256_turn128": (256, 128), "tile512_turn256": (512, 256)}
# batch, S, the widths of z, x, B, C and dt in `proj`, taps, bias: the
# granite cut's family (xBC 4352 = 4096 + 2 x 128 wide after a z of 4096,
# K 4, bias) at two tiles or four; two rows of three tiles or six at small
# widths; no bias and three taps.
FAMILIES = {
    "cut": (1, 1024, (4096, 4096, 128, 128, 64), 4, True),
    "batch2": (2, 1536, (128, 256, 128, 128, 128), 4, True),
    "no_bias": (1, 1024, (128, 128, 128, 128, 8), 3, False),
}
DTYPES = {"float32": jnp.float32, "bfloat16": jnp.bfloat16}
CASES = [(t, f, d) for t in TILINGS for f in FAMILIES for d in DTYPES]


@pytest.fixture()
def tiling(request, monkeypatch):
    """The kernels run, interpreted, where the CPU would run the
    expression, at the tiling the case names: (tile, turn)."""
    monkeypatch.setenv("EDL_FORCE_PALLAS_INTERPRET", "1")
    tile, turn = TILINGS[request.param]
    monkeypatch.setattr(cc, "TIME", tile)
    monkeypatch.setattr(cc, "LANES", turn)
    return tile, turn


at_each_tiling = pytest.mark.parametrize(
    "tiling", list(TILINGS), indirect=True)


def _inputs(family, dtype, seed=0):
    bsz, s, widths, taps, has_bias = FAMILIES[family]
    rng = np.random.default_rng(seed)
    conv = sum(widths[1:4])
    proj = jnp.asarray(rng.normal(size=(bsz, s, sum(widths))), dtype)
    weight = jnp.asarray(rng.uniform(-0.5, 0.5, (taps, conv)), F32)
    bias = jnp.asarray(rng.uniform(-0.5, 0.5, (conv,)), F32) if has_bias \
        else None
    cotangents = [jnp.asarray(rng.normal(size=(bsz, s, w)), F32)
                  for w in widths]
    return proj, widths, weight, bias, cotangents


def _value_and_grads(stage, proj, widths, weight, bias, cotangents):
    """The five results and the gradients of their weighted sum in proj,
    weight and (where there is one) bias, all in float32."""
    def loss(proj, weight, bias):
        outs = stage(proj, widths, weight, bias)
        return sum(jnp.sum(o.astype(F32) * g)
                   for o, g in zip(outs, cotangents)), outs

    grads, outs = jax.grad(
        loss, argnums=(0, 1) if bias is None else (0, 1, 2), has_aux=True
    )(proj, weight, bias)
    return ([o.astype(F32) for o in outs], [g.astype(F32) for g in grads])


def _worst(got, want):
    return max(float(jnp.max(jnp.abs(g - w)) / jnp.max(jnp.abs(w)))
               for g, w in zip(got, want))


@pytest.mark.parametrize("tiling, family, dtype", CASES,
                         indirect=["tiling"])
def test_the_stage_and_its_gradients_are_the_expressions(
        tiling, family, dtype):
    """x, B, C (z and dt beside them) and d proj, d weight, d bias against
    `conv_silu_split` on the same values in float32: to float32's
    rounding with float32 operands; with bfloat16 operands no further
    from it than the `jax.numpy` expression in bfloat16 is. Several tiles
    a sequence, several turns a tile, so every sum runs over them all."""
    proj, widths, weight, bias, cotangents = _inputs(family, DTYPES[dtype])
    assert proj.shape[1] // tiling[0] > 1 and tiling[0] // tiling[1] > 1
    exact = _value_and_grads(
        mamba2.conv_silu_split, proj.astype(F32), widths, weight, bias,
        cotangents)
    if dtype == "bfloat16":
        # The parameters as a bfloat16 stage takes them.
        rounded = [None if p is None else p.astype(jnp.bfloat16).astype(F32)
                   for p in (weight, bias)]
        exact = _value_and_grads(
            mamba2.conv_silu_split, proj.astype(F32), widths, *rounded,
            cotangents)
    got = _value_and_grads(
        cc.causal_conv_silu, proj, widths, weight, bias, cotangents)
    for part, want in zip(got[0] + got[1], exact[0] + exact[1]):
        assert part.shape == want.shape
    if dtype == "float32":
        assert _worst(got[0], exact[0]) < 1e-5
        assert _worst(got[1], exact[1]) < 1e-5
        return
    plain = _value_and_grads(
        mamba2.conv_silu_split, proj, widths, weight, bias, cotangents)
    for mine, theirs, want in zip(got, plain, exact):
        assert _worst(mine, want) <= 1.05 * _worst(theirs, want) + 1e-6
    # And z and dt are proj's own columns.
    np.testing.assert_array_equal(got[0][0], proj[..., :widths[0]])
    np.testing.assert_array_equal(
        got[0][4], proj[..., sum(widths[:4]):].astype(F32))


@at_each_tiling
def test_before_a_sequence_there_are_zeros(tiling):
    """The first K - 1 results see the zeros before the sequence, whatever
    the tile's own columns hold where a halo would lie (the halo's block
    is the tile's columns 0 .. 127 there), were it not a number: each row
    of a batch starts anew."""
    proj, widths, weight, bias, _ = _inputs("batch2", F32)
    taps, start = weight.shape[0], widths[0]
    # Where a first tile's halo is read from, and no tap of a result
    # before column 128 reaches.
    proj = proj.at[1, 125:128, start:start + widths[1]].set(jnp.inf)
    got = cc.causal_conv_silu(proj, widths, weight, bias)[1]
    want = mamba2.conv_silu_split(proj, widths, weight, bias)[1]
    assert bool(jnp.isfinite(got[:, :125]).all())
    np.testing.assert_allclose(
        got[:, :125], want[:, :125], rtol=1e-5, atol=1e-6)
    xbc = proj[..., start:start + widths[1]]
    pre = xbc[:, 0] * weight[taps - 1, :widths[1]] + bias[:widths[1]]
    np.testing.assert_allclose(
        got[:, 0], pre * jax.nn.sigmoid(pre), rtol=1e-5, atol=1e-6)


@pytest.mark.parametrize("edge", ["turn", "tile", "second_tile"])
@at_each_tiling
def test_the_halo_crosses_an_edge_in_both_passes(tiling, edge):
    """One column of x before a turn's or a tile's edge moves the K - 1
    results past the edge; one column of cotangent past it reaches the
    K - 1 columns of d proj before it: both as the expression has them."""
    tile, turn = tiling
    edge = {"turn": turn, "tile": tile, "second_tile": 2 * tile}[edge]
    proj, widths, weight, bias, _ = _inputs("batch2", F32)
    taps, start = weight.shape[0], widths[0]

    def x_of(stage, proj):
        return stage(proj, widths, weight, bias)[1]

    poked = proj.at[:, edge - 1, start:start + widths[1]].add(1.0)
    for fn in (cc.causal_conv_silu, mamba2.conv_silu_split):
        moved = jnp.abs(x_of(fn, poked) - x_of(fn, proj)).max(axis=(0, 2))
        assert (np.flatnonzero(moved > 1e-6) == np.arange(
            edge - 1, edge + taps - 1)).all()
    np.testing.assert_allclose(
        x_of(cc.causal_conv_silu, poked),
        x_of(mamba2.conv_silu_split, poked), rtol=1e-5, atol=1e-6)

    at_edge = jnp.zeros(proj.shape[:2] + (widths[1],)).at[:, edge].set(1.0)

    def d_proj(fn):
        return jax.grad(lambda p: jnp.sum(x_of(fn, p) * at_edge))(proj)

    got, want = d_proj(cc.causal_conv_silu), d_proj(mamba2.conv_silu_split)
    reached = jnp.abs(got).max(axis=(0, 2))
    assert (np.flatnonzero(reached > 0) == np.arange(
        edge - taps + 1, edge + 1)).all()
    np.testing.assert_allclose(got, want, rtol=1e-5, atol=1e-6)


@at_each_tiling
def test_d_weight_and_d_bias_are_summed_over_every_tile_and_row(tiling):
    """The kernels' d weight and d bias of the whole batch are the sums of
    those of each row's each tile taken alone with its halo (the
    expression's, on a window that starts K - 1 columns early)."""
    tile = tiling[0]
    proj, widths, weight, bias, cotangents = _inputs("batch2", F32)
    taps = weight.shape[0]

    def grads(stage, proj, cotangents):
        return _value_and_grads(
            stage, proj, widths, weight, bias, cotangents)[1][1:]

    whole = grads(cc.causal_conv_silu, proj, cotangents)
    summed = [jnp.zeros_like(g) for g in whole]
    for row in range(proj.shape[0]):
        for at in range(0, proj.shape[1], tile):
            window = slice(max(at - taps + 1, 0), at + tile)
            # The halo's own results carry no cotangent.
            cots = [g[row:row + 1, window].at[:, :at - window.start].set(0)
                    for g in cotangents]
            part = grads(mamba2.conv_silu_split, proj[row:row + 1, window],
                         cots)
            summed = [s + p for s, p in zip(summed, part)]
    for got, want in zip(whole, summed):
        np.testing.assert_allclose(got, want, rtol=1e-4, atol=1e-4)
        assert float(jnp.max(jnp.abs(want))) > 1.0


@pytest.mark.parametrize("shape, widths, taps, said", [
    ((1, 500, 640), (128, 128, 128, 128, 128), 4, "multiple of 128"),
    ((1, 512, 608), (128, 96, 128, 128, 128), 4, "128 channels"),
    ((1, 512, 576), (64, 128, 128, 128, 128), 4, "128 channels"),
    ((1, 512, 896), (128, 128, 256, 128, 256), 4, "last two equal"),
    ((1, 512, 640), (128, 128, 128, 128, 128), 8, "8 taps"),
], ids=["length500", "x96", "z64", "b_is_not_c", "taps8"])
def test_a_shape_the_kernels_cannot_tile_is_refused_by_name(
        monkeypatch, shape, widths, taps, said):
    monkeypatch.setenv("EDL_FORCE_PALLAS_INTERPRET", "1")
    proj = jnp.zeros(shape, F32)
    weight = jnp.zeros((taps, sum(widths[1:4])), F32)
    with pytest.raises(ValueError, match=said) as refused:
        cc.causal_conv_silu(proj, widths, weight, None)
    # The shape is in the message.
    assert str(tuple(shape)) in str(refused.value)


def test_off_the_tpu_the_same_call_is_the_expression(monkeypatch):
    """As `ssd_scan` runs `ssd_chunked` there: no kernel, no rule of its
    own, and a shape no tile serves is taken."""
    monkeypatch.delenv("EDL_FORCE_PALLAS_INTERPRET", raising=False)
    proj, widths, weight, bias, _ = _inputs("no_bias", jnp.bfloat16)
    names = _primitives(jax.make_jaxpr(
        lambda p, w: cc.causal_conv_silu(p, widths, w, bias))(
            proj, weight).jaxpr)
    assert "pallas_call" not in names
    assert not any(n.startswith("custom_vjp") for n in names)
    for got, want in zip(cc.causal_conv_silu(proj, widths, weight, bias),
                         mamba2.conv_silu_split(proj, widths, weight, bias)):
        np.testing.assert_array_equal(got, want)
    small = jnp.ones((1, 24, 40), F32)
    assert cc.causal_conv_silu(
        small, (8, 16, 4, 4, 8), jnp.ones((4, 24), F32), None)[1].shape == (
            1, 24, 16)


# ---------- which stage a mixer calls ----------


@pytest.mark.parametrize("fields, kernel", [
    ({}, False), ({"conv": mamba2.conv_silu_split}, False),
    ({"conv": cc.causal_conv_silu}, True),
], ids=["default", "expression", "kernel"])
def test_a_mixer_convolves_with_the_function_it_is_built_with(
        monkeypatch, fields, kernel):
    """`conv_silu_split` for every caller that says nothing (the hybrid's
    program), the kernels where the call site hands them in, chosen by
    nothing else: the interpret switch is on for all three."""
    monkeypatch.setenv("EDL_FORCE_PALLAS_INTERPRET", "1")
    mixer = mamba2.Mamba2Mixer(
        d_model=128, num_heads=2, head_dim=64, n_groups=1, state_size=128,
        chunk_size=128, dtype="float32", **fields)
    u = jnp.zeros((1, 256, 128), F32)
    params = jax.eval_shape(lambda: mixer.init(jax.random.PRNGKey(0), u))
    names = _primitives(jax.make_jaxpr(mixer.apply)(params, u).jaxpr)
    assert ("pallas_call" in names) == kernel
    assert ("custom_vjp_call" in names or "custom_vjp_call_jaxpr" in names
            ) == kernel


def test_the_hybrids_block_says_nothing_and_the_granite_block_hands_it_in():
    from elasticdl_tpu.models.granite_hybrid import granite_hybrid as gh
    from elasticdl_tpu.models.nemotron_h import nemotron_h

    assert not any("causal_conv" in str(getattr(v, "__name__", ""))
                   or "causal_conv" in str(getattr(v, "__module__", ""))
                   for v in vars(nemotron_h).values())
    assert gh.causal_conv_silu is cc.causal_conv_silu
