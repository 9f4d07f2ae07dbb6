"""The head norm, the rotary turn, the rounding and the change of layout as
Pallas kernels (ops/qk_rotary.py), in interpret mode on the CPU: the result
and both gradients against `rotary(rms_norm(.))` in float32 and against the
parent's bfloat16 expression, at q's heads and k's, at SDAR's doubled
positions and under a YaRN table with its scale; what it refuses to tile;
and what the set-up pays: no nested jit in a body or an index map, and one
trace of each body a shape, whatever the number of layers."""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from elasticdl_tpu.models.lfm2.lfm2_moe import rotary
from elasticdl_tpu.models.mellum import mellum_moe as mm
from elasticdl_tpu.models.nemotron_h.nemotron_h import rms_norm
from elasticdl_tpu.ops import flash_attention as fa
from elasticdl_tpu.ops import qk_rotary as qr
from test_ssd_scan import _equations, _primitives

S, DH, EPS = 64, 128, 1e-6
HEADS = {"q": 32, "k": 4}
YARN = {"rope_type": "yarn", "rope_theta": 10000.0, "factor": 4.0,
        "original_max_position_embeddings": 16, "beta_fast": 32,
        "beta_slow": 1, "attention_factor": 1.1386294361119891}
# name -> (positions, theta, inv_freq, scale): what `rotary` takes.
ROPES = {
    # Two copies of a record, both at positions 0 .. L - 1 (SDAR).
    "doubled": lambda: (jnp.tile(jnp.arange(S // 2), 2), 1e6, None, None),
    # Mellum's full layers: YaRN's table, cos and sin times its factor.
    "yarn": lambda: (None, None, *mm.rope_table(YARN, DH)),
}
CASES = [(h, r) for h in HEADS for r in ROPES]


@pytest.fixture()
def interpreted(monkeypatch):
    """The kernels run, interpreted, where the CPU would run the fallback."""
    monkeypatch.setenv("EDL_FORCE_PALLAS_INTERPRET", "1")


def _operands(heads, seed=0, bsz=1, s=S):
    rng = np.random.default_rng(seed)
    x = jnp.asarray(rng.normal(size=(bsz, s, HEADS[heads], DH)) * 3,
                    jnp.bfloat16)
    weight = jnp.asarray(1 + 0.2 * rng.normal(size=(DH,)), jnp.float32)
    cotangent = jnp.asarray(
        rng.normal(size=(bsz, HEADS[heads], s, DH)), jnp.float32)
    return x, weight, cotangent


def _tables(rope, s=S):
    positions, theta, inv_freq, scale = ROPES[rope]()
    if positions is None:
        positions = jnp.arange(s)
    if inv_freq is None:
        inv_freq = theta ** (-jnp.arange(0, DH, 2, dtype=jnp.float32) / DH)
    return qr.rope_tables(positions, inv_freq, scale)


def _parents(rope, dtype):
    """The parent's call site: rotary(head_norm(.)), the rounding, the
    layout; `dtype` float32 leaves the rounding out (and takes the
    projection widened)."""
    positions, theta, inv_freq, scale = ROPES[rope]()

    def expression(x, weight):
        turned = rotary(rms_norm(x.astype(dtype), weight, EPS), theta,
                        positions, inv_freq=inv_freq, scale=scale)
        return jnp.swapaxes(turned.astype(dtype), 1, 2)

    return expression


def _op(rope):
    cos, sin = _tables(rope)
    return lambda x, weight: qr.qk_rotary(x, weight, EPS, cos, sin)


def _gradients(fn, x, weight, cotangent):
    return jax.grad(
        lambda x, w: jnp.sum(fn(x, w).astype(jnp.float32) * cotangent),
        argnums=(0, 1))(x, weight)


def _ulps_apart(got, want):
    """The most bfloat16 steps between two bfloat16 arrays."""
    def ordered(v):
        bits = np.asarray(v).view(np.int16).astype(np.int32)
        return np.where(bits < 0, -(bits & 0x7FFF), bits)

    return int(np.max(np.abs(ordered(got) - ordered(want))))


def _a_step_apart(got, want, scale=2 ** -8):
    """d x against the parent's, both bfloat16: a bfloat16 step of the
    largest element apart at most (PR 52's chip run read 0.03125 at 7.97),
    where the norm's backward takes a small difference of large terms."""
    assert got.dtype == want.dtype == jnp.bfloat16
    got, want = np.asarray(got, np.float32), np.asarray(want, np.float32)
    assert np.max(np.abs(got - want)) <= scale * np.max(np.abs(want))


def _the_parents_bits(got, want):
    """The same float32 operations and one rounding: the parent's bits but
    where the interpreter's sum over a head's lanes, in another order than
    XLA's, falls on the other side of a rounding (2 of 262,144 measured),
    and then the next bfloat16."""
    assert got.dtype == want.dtype == jnp.bfloat16
    assert _ulps_apart(got, want) <= 1
    assert np.mean(np.asarray(got != want)) < 1e-4


@pytest.mark.parametrize("heads, rope", CASES)
def test_the_kernels_result_is_the_parents_expression(
        interpreted, heads, rope):
    """The parent's bfloat16 bits (the same float32 operations, one
    rounding), in the flash kernels' layout; and the float32 expression to
    that rounding."""
    x, weight, _ = _operands(heads)
    got = _op(rope)(x, weight)
    assert got.dtype == jnp.bfloat16
    assert got.shape == (1, HEADS[heads], S, DH)
    _the_parents_bits(got, _parents(rope, jnp.bfloat16)(x, weight))
    np.testing.assert_allclose(
        np.asarray(got, np.float32), _parents(rope, jnp.float32)(x, weight),
        rtol=2 ** -8, atol=1e-6)


@pytest.mark.parametrize("heads, rope", CASES)
def test_the_kernels_two_gradients_are_the_parents(interpreted, heads, rope):
    """d x (in the projection's dtype, rounded once) and d weight (float32,
    summed over rows, heads and grid blocks) against the parent's
    bfloat16 expression, a rounding step apart at most, and against the
    float32 expression."""
    x, weight, cotangent = _operands(heads, seed=1)
    dx, dw = _gradients(_op(rope), x, weight, cotangent)
    assert dx.dtype == jnp.bfloat16 and dx.shape == x.shape
    assert dw.dtype == jnp.float32 and dw.shape == weight.shape
    want_dx, want_dw = _gradients(
        _parents(rope, jnp.bfloat16), x, weight, cotangent)
    _a_step_apart(dx, want_dx)
    np.testing.assert_allclose(dw, want_dw, rtol=1e-4, atol=1e-4)
    exact_dx, exact_dw = _gradients(
        _parents(rope, jnp.float32), x, weight,
        # The bfloat16 result's cotangent is rounded on its way back.
        cotangent.astype(jnp.bfloat16).astype(jnp.float32))
    np.testing.assert_allclose(
        np.asarray(dx, np.float32), exact_dx, rtol=0,
        atol=2 ** -8 * float(jnp.max(jnp.abs(exact_dx))))
    np.testing.assert_allclose(dw, exact_dw, rtol=1e-4, atol=1e-4)


def test_several_blocks_and_a_batch_of_rows(interpreted, monkeypatch):
    """A sequence is many blocks long at the cell's size and a data mesh's
    shard may hold several rows: a small ROWS walks both grid axes here, d
    weight's partial sums a block with them."""
    monkeypatch.setattr(qr, "ROWS", 16)
    x, weight, cotangent = _operands("k", seed=2, bsz=2)
    t = qr._tiles(x.shape)
    assert (t.tile, t.tiles) == (16, 4)
    _the_parents_bits(_op("doubled")(x, weight),
                      _parents("doubled", jnp.bfloat16)(x, weight))
    dx, dw = _gradients(_op("doubled"), x, weight, cotangent)
    want_dx, want_dw = _gradients(
        _parents("doubled", jnp.bfloat16), x, weight, cotangent)
    _a_step_apart(dx, want_dx)
    np.testing.assert_allclose(dw, want_dw, rtol=1e-4, atol=1e-4)


@pytest.mark.parametrize("heads, rope", CASES)
def test_off_the_tpu_the_op_is_the_parents_expression_to_the_bit(heads, rope):
    """No kernel on the CPU test platform: the same float32 operations
    over the same tables, under a jit as a step runs them, result and
    gradients."""
    x, weight, cotangent = _operands(heads, seed=3)

    def both(fn):
        return jax.jit(lambda x, w: (
            fn(x, w), _gradients(fn, x, w, cotangent)))(x, weight)

    names = _primitives(jax.make_jaxpr(
        lambda x, w: _op(rope)(x, w))(x, weight).jaxpr)
    assert "pallas_call" not in names
    got = both(lambda x, w: _op(rope)(x, w))
    want = both(_parents(rope, jnp.bfloat16))
    for g, w in zip(jax.tree_util.tree_leaves(got),
                    jax.tree_util.tree_leaves(want)):
        np.testing.assert_array_equal(
            np.asarray(g, np.float32), np.asarray(w, np.float32))


def test_a_head_of_64_channels_raises_where_the_kernels_run(interpreted):
    x = jnp.zeros((2, 64, 32, 64), jnp.bfloat16)
    table = jnp.zeros((1, 64, 64), jnp.float32)
    with pytest.raises(ValueError, match=r"\(2, 64, 32, 64\).*64 channels"):
        qr.qk_rotary(x, jnp.ones((64,)), EPS, table, table)
    with pytest.raises(ValueError, match=r"\(1, 24, 4, 128\).*multiple"):
        qr.qk_rotary(jnp.zeros((1, 24, 4, 128), jnp.bfloat16),
                     jnp.ones((128,)), EPS, table, table)


# ---------- what the set-up pays ----------


def _kernel_calls(jaxpr):
    return [e for e in _equations(jaxpr) if e.primitive.name == "pallas_call"]


def test_the_kernels_bodies_trace_no_nested_jit(monkeypatch):
    """A `jnp` operator on a traced value is a nested jit to trace, and
    set-up seconds in every job (PERF.md section 6, PRs 44 and 53): the
    bodies and the index maps are `lax` primitives; and the calls carry
    the names a trace's ops table counts them by."""
    monkeypatch.delenv("EDL_FORCE_PALLAS_INTERPRET", raising=False)
    monkeypatch.setattr(fa, "_use_pallas", lambda: True)
    x, weight, cotangent = _operands("q")
    calls = _kernel_calls(jax.make_jaxpr(
        lambda x, w: _gradients(_op("yarn"), x, w, cotangent))(
            x, weight).jaxpr)
    assert sorted(c.params["name"] for c in calls) == [
        "qk_rotary_bwd", "qk_rotary_fwd"]
    for call in calls:
        maps = [m.index_map_jaxpr.jaxpr
                for m in call.params["grid_mapping"].block_mappings]
        assert len(maps) >= 5
        for jaxpr in (call.params["jaxpr"], *maps):
            names = _primitives(jaxpr)
            assert not {"pjit", "jit", "closed_call", "core_call"} & names, (
                sorted(names))


def test_a_six_layer_stack_traces_each_body_once_a_shape(monkeypatch):
    """q's and k's call in six layers, forward and backward (24 call
    sites), under tables of two kinds as the Mellum layers have them: each
    kernel's body is run by Python once for q's shape and once for k's (a
    jit of its own round each `pallas_call`, the tables operands), where a
    body traced a call site would count twelve and more. The eps is this
    test's own, so no other test's trace serves it."""
    monkeypatch.delenv("EDL_FORCE_PALLAS_INTERPRET", raising=False)
    monkeypatch.setattr(fa, "_use_pallas", lambda: True)
    counts = {"fwd": 0, "bwd": 0}

    def counted(name):
        body = getattr(qr, f"_{name}_kernel")

        def run(*args, **kwargs):
            counts[name] += 1
            return body(*args, **kwargs)

        monkeypatch.setattr(qr, f"_{name}_kernel", run)

    counted("fwd"), counted("bwd")
    eps = 1.2345e-6
    xq, wq, _ = _operands("q")
    xk, wk, _ = _operands("k")
    ropes = [_tables(r) for r in ("doubled", "yarn")]

    def stack(xq, wq, xk, wk):
        total = 0.0
        for layer in range(6):
            cos, sin = ropes[layer % 2]
            q = qr.qk_rotary(xq, wq, eps, cos, sin)
            k = qr.qk_rotary(xk, wk, eps, cos, sin)
            total = total + jnp.sum(q.astype(jnp.float32)) * jnp.sum(
                k.astype(jnp.float32))
            xq, xk = xq * 0.5, xk * 0.5
        return total

    jaxpr = jax.make_jaxpr(jax.grad(stack, argnums=(0, 1, 2, 3)))(
        xq, wq, xk, wk).jaxpr
    assert len(_kernel_calls(jaxpr)) == 24
    assert counts == {"fwd": 2, "bwd": 2}, counts
