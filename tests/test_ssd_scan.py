"""The chunked scan as Pallas kernels (ops/ssd_scan.py), in interpret mode
on the CPU: its result and its five gradients against `ssd_chunked` and
against the token-by-token recurrence, at the two shapes' families the
models scan at; the carried state; what it refuses to tile; which scan a
mixer calls; and what a trace's reader would know its operations by."""

import importlib.util
import os

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from elasticdl_tpu.layers import mamba2
from elasticdl_tpu.ops import flash_attention as fa
from elasticdl_tpu.ops import ssd_scan as ss
from test_mamba2 import inputs, recurrence

# (B, S, H, P, G, N), chunk: granite-4.0-h-micro's family (one row, one
# group for all heads, the published chunk of 256) and the Nemotron-H
# hybrid's (two rows, eight groups, chunk 128), at the published head
# width and state, few heads and chunks.
FAMILIES = {
    "granite": ((1, 768, 8, 64, 1, 128), 256),
    "hybrid": ((2, 256, 64, 64, 8, 128), 128),
}
DTYPES = {"float32": jnp.float32, "bfloat16": jnp.bfloat16}
# Against the float32 recurrence, of the largest element: the float32
# scan's own rounding at a chunk of 256 (tests/test_mamba2.py measured
# 1.2e-5 for `ssd_chunked`), and bfloat16 operands in every product.
TOLERANCE = {"float32": 1e-4, "bfloat16": 3e-2}
CASES = [(f, d) for f in FAMILIES for d in DTYPES]


@pytest.fixture()
def interpreted(monkeypatch):
    """The kernels run, interpreted, where the CPU would run the fallback."""
    monkeypatch.setenv("EDL_FORCE_PALLAS_INTERPRET", "1")


def _args(family, seed=1):
    dims, chunk = FAMILIES[family]
    return inputs(seed, s=dims[1], dims=dims), chunk


def _close(got, want, tolerance):
    scale = float(jnp.max(jnp.abs(want)))
    np.testing.assert_allclose(
        np.asarray(got, np.float32), np.asarray(want, np.float32),
        rtol=0, atol=tolerance * scale)


@pytest.mark.parametrize("family, dtype", CASES)
def test_the_kernels_result_is_the_chunked_scans_and_the_recurrences(
        interpreted, family, dtype):
    args, chunk = _args(family)
    got = ss.ssd_scan(*args, chunk, dtype=DTYPES[dtype])
    assert got.dtype == jnp.float32 and got.shape == args[0].shape
    with jax.default_matmul_precision("highest"):
        _close(got, recurrence(*args), TOLERANCE[dtype])
        _close(got, mamba2.ssd_chunked(*args, chunk, dtype=DTYPES[dtype]),
               TOLERANCE[dtype])


@pytest.mark.parametrize("family, dtype", CASES)
def test_the_kernels_five_gradients_are_the_chunked_scans_and_the_recurrences(
        interpreted, family, dtype):
    args, chunk = _args(family)
    weight = jnp.asarray(np.random.default_rng(2).normal(
        size=args[0].shape).astype(np.float32))

    def of(fn):
        return jax.grad(
            lambda *a: jnp.sum(fn(*a) * weight), argnums=(0, 1, 2, 3, 4)
        )(*args)

    got = of(lambda *a: ss.ssd_scan(*a, chunk, dtype=DTYPES[dtype]))
    with jax.default_matmul_precision("highest"):
        wanted = (of(recurrence), of(lambda *a: mamba2.ssd_chunked(
            *a, chunk, dtype=DTYPES[dtype])))
    for want in wanted:
        for g, w, arg in zip(got, want, args):
            assert g.shape == arg.shape and g.dtype == arg.dtype
            _close(g, w, TOLERANCE[dtype])


def test_the_state_is_carried_from_chunk_to_chunk(interpreted):
    """Three chunks as one sequence against the same three scanned apart
    (the state zero at each one's start): the first chunk the same, the
    later ones not, by what the carried state gives them."""
    (x, dt, a, b, c), chunk = _args("granite")
    whole = ss.ssd_scan(x, dt, a, b, c, chunk)
    apart = jnp.concatenate([
        ss.ssd_scan(*(v[:, at:at + chunk] for v in (x, dt)), a,
                    *(v[:, at:at + chunk] for v in (b, c)), chunk)
        for at in range(0, x.shape[1], chunk)], axis=1)
    np.testing.assert_allclose(whole[:, :chunk], apart[:, :chunk],
                               rtol=1e-5, atol=1e-5)
    lost = jnp.abs(whole - apart)[:, chunk:]
    assert float(jnp.max(lost)) > 1e-2 * float(jnp.max(jnp.abs(whole)))


@pytest.mark.parametrize("dims, chunk, said", [
    ((1, 576, 4, 64, 1, 128), 96, "multiple of 128"),
    ((1, 500, 4, 64, 1, 128), 250, "multiple of 128"),
    ((1, 384, 4, 64, 1, 128), 256, "multiple of the chunk"),
    ((1, 256, 4, 64, 1, 48), 128, "state size 48"),
    ((1, 256, 4, 24, 1, 128), 128, "head width 24"),
    ((1, 256, 6, 64, 4, 128), 128, "6 heads do not split over 4 groups"),
    ((1, 256, 12, 64, 1, 128), 128, "12 heads a group are not a multiple"),
], ids=["chunk96", "chunk250", "ragged", "state48", "head24", "groups",
        "heads12"])
def test_a_shape_the_kernels_cannot_tile_is_refused_by_name(
        interpreted, dims, chunk, said):
    args = inputs(0, s=dims[1], dims=(*dims[:4], 1, dims[5]))
    b = jnp.zeros((dims[0], dims[1], dims[4], dims[5]), jnp.float32)
    x, dt, a = args[:3]
    with pytest.raises(ValueError, match=said) as refused:
        ss.ssd_scan(x, dt, a, b, b, chunk)
    # The shapes are in the message.
    assert str(tuple(x.shape)) in str(refused.value)
    assert str(tuple(b.shape)) in str(refused.value)


def test_off_the_tpu_the_same_call_is_the_chunked_scan(monkeypatch):
    monkeypatch.delenv("EDL_FORCE_PALLAS_INTERPRET", raising=False)
    assert not ss.runs_as_kernel()
    args, chunk = _args("hybrid")
    np.testing.assert_array_equal(
        ss.ssd_scan(*args, chunk), mamba2.ssd_chunked(*args, chunk))
    # And a shape no tile serves is the fallback's to take.
    small = inputs()
    np.testing.assert_array_equal(
        ss.ssd_scan(*small, 8), mamba2.ssd_chunked(*small, 8))


# ---------- which scan a mixer calls ----------


def _mixer(**fields):
    return mamba2.Mamba2Mixer(
        d_model=128, num_heads=8, head_dim=64, n_groups=1, state_size=128,
        chunk_size=128, dtype="float32", **fields)


def _equations(jaxpr):
    """Every equation of a jaxpr and of what it calls, a kernel's call as
    one equation (its body left out)."""
    for eqn in jaxpr.eqns:
        yield eqn
        if eqn.primitive.name != "pallas_call":
            for sub in jax.core.jaxprs_in_params(eqn.params):
                yield from _equations(sub)


def _primitives(jaxpr):
    return {eqn.primitive.name for eqn in _equations(jaxpr)}


@pytest.mark.parametrize("fields, kernel", [
    ({}, False), ({"scan": mamba2.ssd_chunked}, False),
    ({"scan": ss.ssd_scan}, True),
], ids=["default", "chunked", "kernel"])
def test_a_mixer_scans_with_the_function_it_is_built_with(
        interpreted, fields, kernel):
    """`ssd_chunked` for every caller that says nothing (the hybrid's
    program), the kernels where the call site hands them in, chosen by
    nothing else: the interpret switch is on for all three."""
    mixer = _mixer(**fields)
    u = jnp.zeros((1, 256, 128), jnp.float32)
    params = jax.eval_shape(lambda: mixer.init(jax.random.PRNGKey(0), u))
    names = _primitives(jax.make_jaxpr(mixer.apply)(params, u).jaxpr)
    assert ("pallas_call" in names) == kernel


def test_the_hybrids_block_says_nothing_and_the_granite_block_hands_it_in():
    from elasticdl_tpu.models.granite_hybrid import granite_hybrid as gh
    from elasticdl_tpu.models.nemotron_h import nemotron_h

    assert "ssd_scan" not in vars(nemotron_h)
    assert gh.ssd_scan is ss.ssd_scan


# ---------- what a trace's reader knows the scan by ----------


def _load(path):
    spec = importlib.util.spec_from_file_location(
        os.path.basename(path)[:-3], path)
    module = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(module)
    return module


def _scoped(jaxpr, scope):
    """The equations under `scope` that call no jaxpr of their own."""
    return [
        eqn for eqn in _equations(jaxpr)
        if scope in str(eqn.source_info.name_stack) and (
            eqn.primitive.name == "pallas_call"
            or not tuple(jax.core.jaxprs_in_params(eqn.params)))]


def test_every_operation_of_the_scan_carries_a_shape_the_readers_take(
        monkeypatch):
    """`ssd_time_pct.granite` and `ssd_roofline.granite` know the scan's
    operations by shapes in their HLO lines
    (`benchmark/metrics/_granite_ops.py:scan_shape`). At the cell's sizes
    every kernel call, and every other operation the scan keeps round the
    calls that moves a token-sized tensor, forward and backward, has such
    a shape among its operands and results: a call the readers missed
    would read a roofline over 100. Reshapes and transposes are left out:
    they change a layout and, in the compiled step, move nothing
    (`tests/test_tpu_compile.py` holds the granite step to that)."""
    benchmark = os.path.join(
        os.path.dirname(os.path.abspath(__file__)), "..", "benchmark")
    monkeypatch.syspath_prepend(benchmark)  # its `lib`
    ops = _load(os.path.join(benchmark, "metrics", "_granite_ops.py"))
    z = {"batch": 1, "chunks": 32, "chunk": 256, "heads": 64, "groups": 1,
         "per": 64, "head_dim": 64, "state": 128}
    monkeypatch.delenv("EDL_FORCE_PALLAS_INTERPRET", raising=False)
    monkeypatch.setattr(fa, "_use_pallas", lambda: True)
    mixer = mamba2.Mamba2Mixer(
        d_model=2048, num_heads=64, head_dim=64, n_groups=1,
        state_size=128, chunk_size=256, scan=ss.ssd_scan)
    u = jax.ShapeDtypeStruct((1, 8192, 2048), jnp.bfloat16)
    params = jax.eval_shape(
        lambda: mixer.init(jax.random.PRNGKey(0), jnp.zeros(u.shape, u.dtype)))

    def loss(p, u):
        return jnp.sum(mixer.apply(p, u).astype(jnp.float32) ** 2)

    jaxpr = jax.make_jaxpr(jax.grad(loss, argnums=(0, 1)))(params, u).jaxpr
    eqns = _scoped(jaxpr, mamba2.SCAN_SCOPE)
    calls = [e for e in eqns if e.primitive.name == "pallas_call"]
    assert sorted(e.params["name"] for e in calls) == [
        "ssd_scan_bwd", "ssd_scan_fwd"]
    token_sized = z["batch"] * z["chunks"] * z["chunk"]
    moved, missed = 0, []
    for eqn in eqns:
        shapes = [tuple(v.aval.shape) for v in (*eqn.invars, *eqn.outvars)
                  if hasattr(v.aval, "shape")]
        if eqn.primitive.name in ("reshape", "transpose") or (
                max(map(np.prod, shapes), default=0) < token_sized):
            continue
        moved += 1
        if not any(ops.scan_shape(dims, z) for dims in shapes):
            missed.append((eqn.primitive.name, shapes))
    assert moved >= len(calls) + 4 and not missed, missed


def test_the_kernels_bodies_trace_no_nested_jit(monkeypatch):
    """A `jnp` operator on a traced value is a nested jit to trace, and
    set-up seconds in every job (PERF.md section 6, PR 44): the bodies and
    index maps are `lax` primitives."""
    monkeypatch.delenv("EDL_FORCE_PALLAS_INTERPRET", raising=False)
    monkeypatch.setattr(fa, "_use_pallas", lambda: True)
    (x, dt, a, b, c), chunk = _args("granite")

    def loss(*args):
        return jnp.sum(ss.ssd_scan(*args, chunk, dtype=jnp.bfloat16))

    jaxpr = jax.make_jaxpr(jax.grad(loss, argnums=(0, 1, 2, 3, 4)))(
        x, dt, a, b, c).jaxpr
    calls = [e for e in _scoped(jaxpr, mamba2.SCAN_SCOPE)
             if e.primitive.name == "pallas_call"]
    assert len(calls) == 2
    for call in calls:
        names = _primitives(call.params["jaxpr"])
        assert "pjit" not in names and "jit" not in names, sorted(names)
