"""What lies between the Kanana projections and the latent-attention
kernels as Pallas kernels (ops/mla_rotary.py), in interpret mode on the
CPU: q, k and v against the parent's lines of `LatentAttention` to the bit,
the three cotangents against `jax.grad` of those lines (d k_rope's sum over
the heads to one rounding of the float32 sum), several row blocks and a
batch of rows, what it refuses to tile, what keeps the expression; and what
the set-up pays: no nested jit in a body or an index map, and one trace of
each body a shape, whatever the number of layers."""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from elasticdl_tpu.models.lfm2.lfm2_moe import rotary
from elasticdl_tpu.ops import flash_attention as fa
from elasticdl_tpu.ops import mla_rotary as mr
from test_qk_rotary import _the_parents_bits
from test_ssd_scan import _equations, _primitives

S, THETA = 64, 1e6
# (heads, rope): nope = dv = 2 rope, the published 128 / 64 and a toy.
CASES = [(2, 8), (4, 8), (2, 64), (4, 64)]
KERNELS = ("q_fwd", "q_bwd", "kv_fwd", "kv_bwd")


@pytest.fixture()
def interpreted(monkeypatch):
    """The kernels run, interpreted, where the CPU would run the fallback."""
    monkeypatch.setenv("EDL_FORCE_PALLAS_INTERPRET", "1")


def _operands(heads, rope, seed=0, bsz=1, s=S, dtype=jnp.bfloat16):
    """(the three projections), (the three results' cotangents)."""
    rng = np.random.default_rng(seed)

    def normal(*shape, scale=1.0):
        return jnp.asarray(rng.normal(size=shape) * scale, dtype)

    return ((normal(bsz, s, heads, 3 * rope, scale=3),
             normal(bsz, s, heads, 4 * rope, scale=3),
             normal(bsz, s, rope, scale=3)),
            (normal(bsz, heads, s, 3 * rope), normal(bsz, heads, s, 3 * rope),
             normal(bsz, heads, s, 2 * rope)))


def _op(rope):
    """The op as a model calls it: the tables made inside the jit."""
    return lambda *x: mr.mla_rotary(
        *x, *mr.rope_tables(x[0].shape[1], THETA, rope))


def _parents(dtype):
    """`LatentAttention`'s lines before the op (PR 57), the turn rounded to
    `dtype`."""
    def fn(q_proj, kv_up, k_rope):
        rope = k_rope.shape[-1]
        q_nope, q_rope = jnp.split(q_proj, [2 * rope], axis=-1)
        k_nope, v = jnp.split(kv_up, [2 * rope], axis=-1)
        q_rope, k_rope = (
            rotary(t, THETA, interleave=True).astype(dtype)
            for t in (q_rope, k_rope[:, :, None, :]))
        q = jnp.swapaxes(jnp.concatenate(
            [q_nope.astype(dtype), q_rope], -1), 1, 2)
        k = jnp.swapaxes(jnp.concatenate(
            [k_nope.astype(dtype),
             jnp.broadcast_to(k_rope, q_rope.shape)], -1), 1, 2)
        return q, k, jnp.swapaxes(v.astype(dtype), 1, 2)

    return fn


def _cotangents(fn, operands, cotangents):
    """(d q_proj, d kv_up, d k_rope) under the results' cotangents, which
    are arguments of the jit (as constants of it the CPU compiler folds the
    interpreted kernels, and folds a 16-bit bitcast wrongly)."""
    return jax.jit(lambda x, c: jax.vjp(fn, *x)[1](c))(operands, cotangents)


@pytest.mark.parametrize("heads, rope", CASES)
def test_the_kernels_three_results_are_the_parents_to_the_bit(
        interpreted, heads, rope):
    """q and k [B, H, S, 3 rope] and v [B, H, S, 2 rope]: the rope lanes
    evens then odds, turned in float32 and rounded once, the one rope key in
    every head; q_nope, k_nope and v the projections' bits."""
    x, _ = _operands(heads, rope)
    got = jax.jit(_op(rope))(*x)
    want = jax.jit(_parents(jnp.bfloat16))(*x)
    assert [g.shape for g in got] == [
        (1, heads, S, 3 * rope), (1, heads, S, 3 * rope),
        (1, heads, S, 2 * rope)]
    for g, w in zip(got, want):
        assert g.dtype == jnp.bfloat16
        np.testing.assert_array_equal(
            np.asarray(g, np.float32), np.asarray(w, np.float32))
    for h in range(heads):
        np.testing.assert_array_equal(
            np.asarray(got[1][:, h, :, 2 * rope:], np.float32),
            np.asarray(got[1][:, 0, :, 2 * rope:], np.float32))


@pytest.mark.parametrize("heads, rope", CASES)
def test_the_kernels_three_cotangents_are_the_parents(
        interpreted, heads, rope):
    """The projections' cotangents are `jax.grad` of the parent's lines,
    the rope lanes turned back in float32 and rounded once. d k_rope is the
    heads' rope lanes SUMMED IN FLOAT32, turned back and rounded once: the
    float32 lines' sum to one rounding (2^-8 of the number itself); the
    parent's lines round the heads' sum to bfloat16 before they turn it
    back and round again, so they are held to 2^-7 of the largest."""
    x, cotangents = _operands(heads, rope, seed=1)
    dq, dkv, dkr = _cotangents(_op(rope), x, cotangents)
    want_dq, want_dkv, want_dkr = _cotangents(
        _parents(jnp.bfloat16), x, cotangents)
    assert dkr.dtype == jnp.bfloat16 and dkr.shape == x[2].shape
    _the_parents_bits(dq, want_dq)
    _the_parents_bits(dkv, want_dkv)
    *_, exact = _cotangents(
        _parents(jnp.float32), tuple(t.astype(jnp.float32) for t in x),
        tuple(c.astype(jnp.float32) for c in cotangents))
    largest = float(jnp.max(jnp.abs(exact)))
    got = np.asarray(dkr, np.float32)
    np.testing.assert_allclose(
        got, exact, rtol=2 ** -8, atol=2 ** -8 * 1e-3 * largest)
    np.testing.assert_allclose(
        got, np.asarray(want_dkr, np.float32), rtol=0,
        atol=2 ** -7 * largest)


def test_several_blocks_and_a_batch_of_rows(interpreted, monkeypatch):
    """A sequence is many blocks long at the cell's size and a data mesh's
    shard may hold several rows: a small ROWS walks both grid axes."""
    monkeypatch.setattr(mr, "ROWS", 16)
    x, cotangents = _operands(4, 64, seed=2, bsz=2)
    t = mr._tiles(*x, mr.rope_tables(S, THETA, 64)[0])
    assert (t.bsz, t.tile, t.tiles) == (2, 16, 4)
    for g, w in zip(jax.jit(_op(64))(*x),
                    jax.jit(_parents(jnp.bfloat16))(*x)):
        _the_parents_bits(g, w)
    got = _cotangents(_op(64), x, cotangents)
    want = _cotangents(_parents(jnp.bfloat16), x, cotangents)
    _the_parents_bits(got[0], want[0])
    _the_parents_bits(got[1], want[1])
    np.testing.assert_allclose(
        np.asarray(got[2], np.float32), np.asarray(want[2], np.float32),
        rtol=0, atol=2 ** -7 * float(jnp.max(jnp.abs(want[2]))))


@pytest.mark.parametrize("heads, rope", CASES)
def test_off_the_tpu_the_op_is_the_parents_expression_to_the_bit(heads, rope):
    """No kernel on the CPU test platform: the same float32 operations
    over the same tables, under a jit as a step runs them, results and
    cotangents."""
    x, cotangents = _operands(heads, rope, seed=3)
    names = _primitives(jax.make_jaxpr(_op(rope))(*x).jaxpr)
    assert "pallas_call" not in names
    got = (jax.jit(_op(rope))(*x), _cotangents(_op(rope), x, cotangents))
    want = (jax.jit(_parents(jnp.bfloat16))(*x),
            _cotangents(_parents(jnp.bfloat16), x, cotangents))
    for g, w in zip(jax.tree_util.tree_leaves(got),
                    jax.tree_util.tree_leaves(want)):
        np.testing.assert_array_equal(
            np.asarray(g, np.float32), np.asarray(w, np.float32))


def test_float32_activations_keep_the_expression_and_the_log_says_so_once(
        monkeypatch):
    """The matrix unit moves bfloat16 lanes exactly and no wider ones: a
    float32 model (the toy models of the tests) is the expression on the
    chip too, and the log names the path once a dtype, not once a call."""
    monkeypatch.delenv("EDL_FORCE_PALLAS_INTERPRET", raising=False)
    monkeypatch.setattr(fa, "_use_pallas", lambda: True)
    said = []
    monkeypatch.setattr(
        mr.logger, "warning", lambda text, *args: said.append(text % args))
    mr._say_the_expression_runs.cache_clear()
    x, _ = _operands(4, 64, dtype=jnp.float32)
    for _ in range(2):
        assert "pallas_call" not in _primitives(
            jax.make_jaxpr(_op(64))(*x).jaxpr)
    assert len(said) == 1 and "float32" in said[0], said
    assert "pallas_call" in _primitives(jax.make_jaxpr(_op(64))(
        *(t.astype(jnp.bfloat16) for t in x)).jaxpr)
    assert len(said) == 1


@pytest.mark.parametrize("what, shapes, why", [
    ("heads", ((1, 64, 3, 24), (1, 64, 3, 32), (1, 64, 8)), "not pairs"),
    ("widths", ((1, 64, 4, 40), (1, 64, 4, 48), (1, 64, 8)), "2 rope"),
    ("rows", ((1, 24, 4, 24), (1, 24, 4, 32), (1, 24, 8)), "multiple"),
])
def test_what_the_kernels_cannot_tile_raises(interpreted, what, shapes, why):
    x = [jnp.zeros(shape, jnp.bfloat16) for shape in shapes]
    tables = [jnp.zeros((1, shapes[0][1], 16), jnp.float32)] * 2
    with pytest.raises(ValueError, match=rf"mla_rotary.*{why}"):
        mr.mla_rotary(*x, *tables)


def test_a_rope_that_is_not_half_a_row_of_lanes_raises_on_the_chip(
        monkeypatch):
    monkeypatch.delenv("EDL_FORCE_PALLAS_INTERPRET", raising=False)
    monkeypatch.setattr(fa, "_use_pallas", lambda: True)
    x, _ = _operands(4, 8)
    with pytest.raises(ValueError, match=r"\(1, 64, 4, 24\).*128 lanes"):
        jax.make_jaxpr(_op(8))(*x)


# ---------- what the set-up pays ----------


def _kernel_calls(jaxpr):
    return [e for e in _equations(jaxpr) if e.primitive.name == "pallas_call"]


def _stack(layers, rope):
    """`layers` calls of the op, each on halved projections, under one pair
    of tables, as a model's layers make them."""
    def fn(q_proj, kv_up, k_rope):
        tables = mr.rope_tables(q_proj.shape[1], THETA, rope)
        total = 0.0
        for _ in range(layers):
            q, k, v = mr.mla_rotary(q_proj, kv_up, k_rope, *tables)
            total = total + jnp.sum(q.astype(jnp.float32)) * jnp.sum(
                k.astype(jnp.float32)) * jnp.sum(v.astype(jnp.float32))
            q_proj, kv_up, k_rope = q_proj * 0.5, kv_up * 0.5, k_rope * 0.5
        return total

    return fn


def test_the_kernels_bodies_trace_no_nested_jit(monkeypatch):
    """A `jnp` operator on a traced value is a nested jit to trace, and
    set-up seconds in every job (PERF.md section 6, PRs 44 and 53): the
    bodies and the index maps are `lax` primitives; and the calls carry
    the names a trace's ops table counts them by, none of them a flash
    kernel's."""
    monkeypatch.delenv("EDL_FORCE_PALLAS_INTERPRET", raising=False)
    monkeypatch.setattr(fa, "_use_pallas", lambda: True)
    x, _ = _operands(4, 64)
    calls = _kernel_calls(jax.make_jaxpr(
        jax.grad(_stack(1, 64), argnums=(0, 1, 2)))(*x).jaxpr)
    assert sorted(c.params["name"] for c in calls) == sorted(
        f"mla_rotary_{k}" for k in KERNELS)
    for call in calls:
        assert "flash_" not in call.params["name"]
        maps = [m.index_map_jaxpr.jaxpr
                for m in call.params["grid_mapping"].block_mappings]
        assert len(maps) >= 5
        for jaxpr in (call.params["jaxpr"], *maps):
            names = _primitives(jaxpr)
            assert not {"pjit", "jit", "closed_call", "core_call"} & names, (
                sorted(names))


def test_a_six_layer_stack_traces_each_body_once_a_shape(monkeypatch):
    """The op in six layers, forward and backward (24 calls): each of the
    four bodies is run by Python once (a jit of its own round each
    `pallas_call`, the tables operands), where a body traced a call site
    would count six. The sequence length is this test's own, so no other
    test's trace serves it."""
    monkeypatch.delenv("EDL_FORCE_PALLAS_INTERPRET", raising=False)
    monkeypatch.setattr(fa, "_use_pallas", lambda: True)
    counts = dict.fromkeys(KERNELS, 0)

    def counted(name):
        body = getattr(mr, f"_{name}_kernel")

        def run(*args, **kwargs):
            counts[name] += 1
            return body(*args, **kwargs)

        monkeypatch.setattr(mr, f"_{name}_kernel", run)

    for name in KERNELS:
        counted(name)
    x, _ = _operands(4, 64, s=96)
    jaxpr = jax.make_jaxpr(jax.grad(_stack(6, 64), argnums=(0, 1, 2)))(
        *x).jaxpr
    assert len(_kernel_calls(jaxpr)) == 24
    assert counts == dict.fromkeys(KERNELS, 1), counts
