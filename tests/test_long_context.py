"""Long-context parallelism tests on the 8-device CPU mesh: ring attention
and Ulysses all-to-all must reproduce full attention exactly (same math,
different schedule), including causal masking and gradients through the
sharded computation."""

import numpy as np
import pytest
import jax
import jax.numpy as jnp
from jax.sharding import NamedSharding, PartitionSpec as P

from elasticdl_tpu.ops.flash_attention import (
    flash_attention,
    reference_attention,
)
from elasticdl_tpu.parallel.mesh import make_mesh
from elasticdl_tpu.parallel.ring_attention import make_ring_attention
from elasticdl_tpu.parallel.ulysses import make_ulysses_attention

B, H, S, D = 2, 8, 256, 32


@pytest.fixture(scope="module")
def qkv():
    rng = np.random.default_rng(0)
    return tuple(
        jnp.asarray(rng.normal(size=(B, H, S, D)), jnp.float32)
        for _ in range(3)
    )


@pytest.fixture(scope="module")
def seq_mesh():
    return make_mesh({"seq": 8})


@pytest.mark.parametrize("causal", [False, True])
def test_ring_attention_matches_full(qkv, seq_mesh, causal):
    q, k, v = qkv
    ring = jax.jit(make_ring_attention(seq_mesh, causal=causal))
    sharding = NamedSharding(seq_mesh, P(None, None, "seq", None))
    args = [jax.device_put(x, sharding) for x in (q, k, v)]
    out = ring(*args)
    ref = reference_attention(q, k, v, causal=causal)
    np.testing.assert_allclose(np.asarray(out), np.asarray(ref), atol=5e-3)


@pytest.mark.parametrize("causal", [False, True])
def test_ulysses_attention_matches_full(qkv, seq_mesh, causal):
    q, k, v = qkv
    ulysses = jax.jit(
        make_ulysses_attention(seq_mesh, causal=causal)
    )
    sharding = NamedSharding(seq_mesh, P(None, None, "seq", None))
    args = [jax.device_put(x, sharding) for x in (q, k, v)]
    out = ulysses(*args)
    ref = reference_attention(q, k, v, causal=causal)
    np.testing.assert_allclose(np.asarray(out), np.asarray(ref), atol=5e-3)


def test_ring_attention_gradients(qkv, seq_mesh):
    """Gradients flow through ppermute/online-softmax identically to full
    attention."""
    q, k, v = qkv
    ring = make_ring_attention(seq_mesh, causal=True)

    def loss_ring(q, k, v):
        return jnp.sum(ring(q, k, v) ** 2)

    def loss_ref(q, k, v):
        return jnp.sum(reference_attention(q, k, v, causal=True) ** 2)

    g_ring = jax.jit(jax.grad(loss_ring, argnums=(0, 1, 2)))(q, k, v)
    g_ref = jax.grad(loss_ref, argnums=(0, 1, 2))(q, k, v)
    for a, b in zip(g_ring, g_ref):
        np.testing.assert_allclose(
            np.asarray(a), np.asarray(b), atol=5e-2
        )


def test_zigzag_ring_attention_matches_full(qkv, seq_mesh):
    """The balanced (zigzag half-chunk) causal ring is EXACT: relayout +
    per-pair masks reproduce full causal attention."""
    from elasticdl_tpu.parallel.ring_attention import (
        make_zigzag_ring_attention,
    )

    q, k, v = qkv
    zz = jax.jit(make_zigzag_ring_attention(seq_mesh, causal=True))
    sharding = NamedSharding(seq_mesh, P(None, None, "seq", None))
    args = [jax.device_put(x, sharding) for x in (q, k, v)]
    out = zz(*args)
    ref = reference_attention(q, k, v, causal=True)
    np.testing.assert_allclose(np.asarray(out), np.asarray(ref), atol=5e-3)


def test_zigzag_ring_attention_gradients(qkv, seq_mesh):
    from elasticdl_tpu.parallel.ring_attention import (
        make_zigzag_ring_attention,
    )

    q, k, v = qkv
    zz = make_zigzag_ring_attention(seq_mesh, causal=True)

    def loss_zz(q, k, v):
        return jnp.sum(zz(q, k, v) ** 2)

    def loss_ref(q, k, v):
        return jnp.sum(reference_attention(q, k, v, causal=True) ** 2)

    g_zz = jax.jit(jax.grad(loss_zz, argnums=(0, 1, 2)))(q, k, v)
    g_ref = jax.grad(loss_ref, argnums=(0, 1, 2))(q, k, v)
    for a, b in zip(g_zz, g_ref):
        np.testing.assert_allclose(
            np.asarray(a), np.asarray(b), atol=5e-2
        )


def test_flash_attention_kernel_interpret(qkv, monkeypatch):
    """The Pallas kernel logic (validated in interpret mode on CPU) matches
    the XLA fallback used off-TPU."""
    monkeypatch.setenv("EDL_FORCE_PALLAS_INTERPRET", "1")
    q, k, v = qkv
    for causal in (False, True):
        out = flash_attention(q, k, v, causal, 128, 128)
        ref = reference_attention(q, k, v, causal=causal)
        np.testing.assert_allclose(
            np.asarray(out), np.asarray(ref), atol=5e-3
        )


BF16_EPS = float(jnp.finfo(jnp.bfloat16).eps)


@pytest.mark.parametrize("dtype", ["float32", "bfloat16"])
@pytest.mark.parametrize("causal", [True, False], ids=["causal", "full"])
@pytest.mark.parametrize(
    "s,block_q,block_k",
    [
        (128, 128, 128),  # one tile a side: grid (bh, 1, 1)
        (256, 128, 128),
        (512, 128, 128),  # dq summed over 4 steps of the outer axis
        (512, 128, 256),
        (512, 256, 128),
    ],
)
def test_flash_attention_gradients(
    monkeypatch, causal, s, block_q, block_k, dtype
):
    """The one backward kernel (interpret mode) against the gradients of
    full attention: dq is summed across the outer grid axis and, causal,
    across the first_i clamp; float32 sums earn a float32 tolerance. With
    bfloat16 operands (what the flagship hands the kernels) the reference
    is float32 on the same bfloat16 values: the kernel's tiles are float32
    in VMEM either way, so what parts the two is o, dO and each gradient
    rounded to bfloat16 once, and `delta` read from the rounded o."""
    monkeypatch.setenv("EDL_FORCE_PALLAS_INTERPRET", "1")
    rng = np.random.default_rng(s + block_q)
    q, k, v = (
        jnp.asarray(rng.normal(size=(1, 2, s, 32)), dtype)
        for _ in range(3)
    )

    def loss_flash(q, k, v):
        out = flash_attention(q, k, v, causal, block_q, block_k)
        assert out.dtype == q.dtype
        return jnp.sum(out.astype(jnp.float32) ** 2)

    def loss_ref(q, k, v):
        return jnp.sum(reference_attention(q, k, v, causal=causal) ** 2)

    gf = jax.grad(loss_flash, argnums=(0, 1, 2))(q, k, v)
    gr = jax.grad(loss_ref, argnums=(0, 1, 2))(
        *(x.astype(jnp.float32) for x in (q, k, v))
    )
    for a, b in zip(gf, gr):
        assert a.dtype == jnp.dtype(dtype)
        a, b = np.asarray(a.astype(jnp.float32)), np.asarray(b)
        if dtype == "float32":
            np.testing.assert_allclose(a, b, atol=1e-4, rtol=1e-4)
        else:
            # The float32 tolerance times the epsilons' ratio (6.5) would
            # bound nothing. The roundings are half a bfloat16 epsilon
            # each, so a whole gradient stays within one epsilon of its
            # largest element (0.2 to 0.45 of that measured).
            assert np.abs(a - b).max() <= BF16_EPS * np.abs(b).max()


@pytest.mark.parametrize("causal", [True, False], ids=["causal", "full"])
def test_flash_kernel_bfloat16_output_is_one_rounding(qkv, monkeypatch, causal):
    """bfloat16 across the kernel's boundary (interpret mode): the output
    is the float32 reference on the same bfloat16 values, rounded to
    bfloat16 once by the kernel's own write: within one unit in the last
    place of the reference so rounded."""
    monkeypatch.setenv("EDL_FORCE_PALLAS_INTERPRET", "1")
    q, k, v = (x.astype(jnp.bfloat16) for x in qkv)
    out = flash_attention(q, k, v, causal, 128, 128)
    assert out.dtype == jnp.bfloat16
    ref = reference_attention(
        *(x.astype(jnp.float32) for x in (q, k, v)), causal=causal
    )
    out, ref = np.asarray(out.astype(jnp.float32)), np.asarray(ref)
    assert (np.abs(out - ref) <= BF16_EPS * np.abs(ref) + 1e-6).all()


@pytest.mark.parametrize("causal", [True, False], ids=["causal", "full"])
def test_fallback_runs_its_softmax_in_float32(qkv, causal):
    """Off the chip `flash_attention` is full attention in XLA. Scores and
    softmax in float32 is the op's promise on every backend, not the
    caller's: with bfloat16 operands the fallback is the float32 fallback
    on the same values rounded once, forward bit for bit, and the
    gradients those of float32 given the same rounded o and dO."""
    q, k, v = (x.astype(jnp.bfloat16) for x in qkv)
    q32, k32, v32 = (x.astype(jnp.float32) for x in (q, k, v))
    out, vjp = jax.vjp(lambda *a: flash_attention(*a, causal), q, k, v)
    out32, vjp32 = jax.vjp(
        lambda *a: flash_attention(*a, causal), q32, k32, v32
    )
    assert out.dtype == jnp.bfloat16
    np.testing.assert_array_equal(
        np.asarray(out.astype(jnp.float32)),
        np.asarray(out32.astype(jnp.bfloat16).astype(jnp.float32)),
    )
    # Scores in bfloat16 would not have come this close.
    plain = reference_attention(q, k, v, causal=causal)
    assert plain.dtype == jnp.bfloat16
    assert not np.array_equal(
        np.asarray(plain.astype(jnp.float32)),
        np.asarray(out.astype(jnp.float32)),
    )
    g = jnp.asarray(
        np.random.default_rng(1).normal(size=out.shape), jnp.bfloat16
    )
    for a, b in zip(vjp(g), vjp32(g.astype(jnp.float32))):
        assert a.dtype == jnp.bfloat16
        a, b = np.asarray(a.astype(jnp.float32)), np.asarray(b)
        assert np.abs(a - b).max() <= BF16_EPS * np.abs(b).max()


def test_block_fitting_keeps_pallas_for_512_multiples():
    """Raising the default block must not kick S=1536-style lengths off
    the Pallas kernel: blocks halve until they divide S."""
    from elasticdl_tpu.ops.flash_attention import _clamp_blocks

    assert _clamp_blocks(4096, 1024, 1024) == (1024, 1024)
    assert _clamp_blocks(1536, 1024, 1024) == (512, 512)
    assert _clamp_blocks(2560, 1024, 1024) == (512, 512)
    assert _clamp_blocks(384, 1024, 1024) == (384, 384)
    assert _clamp_blocks(96, 1024, 1024) == (96, 96)
