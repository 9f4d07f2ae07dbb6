"""Long-context parallelism tests on the 8-device CPU mesh: ring attention
and Ulysses all-to-all must reproduce full attention exactly (same math,
different schedule), including causal masking and gradients through the
sharded computation."""

import functools

import numpy as np
import pytest
import jax
import jax.numpy as jnp
from jax.experimental.pallas import tpu as pltpu
from jax.sharding import NamedSharding, PartitionSpec as P

from elasticdl_tpu.ops import flash_attention as fa
from elasticdl_tpu.ops.flash_attention import (
    causal_tile_kinds,
    flash_attention,
    reference_attention,
)
from elasticdl_tpu.parallel.mesh import make_mesh
from elasticdl_tpu.parallel.ring_attention import make_ring_attention
from elasticdl_tpu.parallel.ulysses import make_ulysses_attention
from tests.test_block_diffusion_attention import walk_the_decode

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
    ref = reference_attention(q, k, v, mask=causal)
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
    ref = reference_attention(q, k, v, mask=causal)
    np.testing.assert_allclose(np.asarray(out), np.asarray(ref), atol=5e-3)


def test_ring_attention_gradients(qkv, seq_mesh):
    """Gradients flow through ppermute/online-softmax identically to full
    attention."""
    q, k, v = qkv
    ring = make_ring_attention(seq_mesh, causal=True)

    def loss_ring(q, k, v):
        return jnp.sum(ring(q, k, v) ** 2)

    def loss_ref(q, k, v):
        return jnp.sum(reference_attention(q, k, v, mask=True) ** 2)

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
    ref = reference_attention(q, k, v, mask=True)
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
        return jnp.sum(reference_attention(q, k, v, mask=True) ** 2)

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
        ref = reference_attention(q, k, v, mask=causal)
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
        return jnp.sum(reference_attention(q, k, v, mask=causal) ** 2)

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
        *(x.astype(jnp.float32) for x in (q, k, v)), mask=causal
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
    plain = reference_attention(q, k, v, mask=causal)
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


# ---------- the oracle of the tile kinds: every run tile masked whole ----------
# The two kernels as they stood before a tile's work followed its place to
# the diagonal (one accumulate body each, the mask on every tile that
# runs) and before the grids ran over the run tiles alone: rectangular
# grids whose steps above the diagonal are skipped, under `pallas_call`s
# of their own.


def _last_kj(i, block_q, block_k, num_k_blocks):
    """Index of the last k tile the i-th q tile attends to."""
    return jnp.minimum(((i + 1) * block_q - 1) // block_k, num_k_blocks - 1)


def _first_qi(j, block_q, block_k):
    """Index of the first q tile that sees the j-th k tile."""
    return (j * block_k) // block_q


def _masked_whole(scores, i, j, block_q, block_k):
    q_pos = i * block_q + jax.lax.broadcasted_iota(
        jnp.int32, (block_q, block_k), 0
    )
    k_pos = j * block_k + jax.lax.broadcasted_iota(
        jnp.int32, (block_q, block_k), 1
    )
    return jnp.where(q_pos >= k_pos, scores, fa.NEG_INF)


def _oracle_fwd_kernel(
    q_ref, k_ref, v_ref, o_ref, lse_ref, m_scr, l_scr, acc_scr,
    *, block_q, block_k, num_k_blocks, scale,
):
    from jax.experimental import pallas as pl

    i = pl.program_id(1)
    j = pl.program_id(2)
    last_j = _last_kj(i, block_q, block_k, num_k_blocks)

    @pl.when(j == 0)
    def _init():
        m_scr[:] = jnp.full(m_scr.shape, fa.NEG_INF, jnp.float32)
        l_scr[:] = jnp.zeros(l_scr.shape, jnp.float32)
        acc_scr[:] = jnp.zeros(acc_scr.shape, jnp.float32)

    @pl.when(j <= last_j)
    def _accumulate():
        q = q_ref[:].astype(jnp.float32) * scale
        k = k_ref[:].astype(jnp.float32)
        v = v_ref[:].astype(jnp.float32)
        scores = jnp.dot(q, k.T, preferred_element_type=jnp.float32)
        scores = _masked_whole(scores, i, j, block_q, block_k)
        m_prev = m_scr[:, :1]
        l_prev = l_scr[:, :1]
        m_new = jnp.maximum(m_prev, jnp.max(scores, axis=1, keepdims=True))
        alpha = jnp.exp(m_prev - m_new)
        p = jnp.exp(scores - m_new)
        l_new = l_prev * alpha + jnp.sum(p, axis=1, keepdims=True)
        acc_scr[:] = acc_scr[:] * alpha + jnp.dot(
            p, v, preferred_element_type=jnp.float32
        )
        m_scr[:] = jnp.broadcast_to(m_new, m_scr.shape)
        l_scr[:] = jnp.broadcast_to(l_new, l_scr.shape)

    @pl.when(j == last_j)
    def _finalize():
        m = m_scr[:, :1]
        l = l_scr[:, :1]
        l_safe = jnp.where(l == 0.0, 1.0, l)
        o_ref[:] = (acc_scr[:] / l_safe).astype(o_ref.dtype)
        lse_ref[:] = jnp.broadcast_to(m + jnp.log(l_safe), lse_ref.shape)


def _oracle_bwd_kernel(
    q_ref, k_ref, v_ref, do_ref, lse_ref, delta_ref, dq_ref, dk_ref, dv_ref,
    dq_scr, dk_scr, dv_scr,
    *, block_q, block_k, num_q_blocks, num_k_blocks, scale,
):
    from jax.experimental import pallas as pl

    j = pl.program_id(1)
    i = pl.program_id(2)
    first_i = _first_qi(j, block_q, block_k)

    @pl.when((j == 0) & (i == 0))
    def _init_row():
        dq_scr[:] = jnp.zeros(dq_scr.shape, jnp.float32)

    @pl.when(i == 0)
    def _init():
        dk_scr[:] = jnp.zeros(dk_scr.shape, jnp.float32)
        dv_scr[:] = jnp.zeros(dv_scr.shape, jnp.float32)

    @pl.when(i >= first_i)
    def _accumulate():
        q = q_ref[:].astype(jnp.float32)
        k = k_ref[:].astype(jnp.float32)
        v = v_ref[:].astype(jnp.float32)
        do = do_ref[:].astype(jnp.float32)
        lse = lse_ref[:, :1]
        delta = delta_ref[:, :1]
        scores = scale * jnp.dot(q, k.T, preferred_element_type=jnp.float32)
        scores = _masked_whole(scores, i, j, block_q, block_k)
        p = jnp.exp(scores - lse)
        dv_scr[:] = dv_scr[:] + jnp.dot(
            p.T, do, preferred_element_type=jnp.float32
        )
        dp = jnp.dot(do, v.T, preferred_element_type=jnp.float32)
        ds = p * (dp - delta)
        dk_scr[:] = dk_scr[:] + scale * jnp.dot(
            ds.T, q, preferred_element_type=jnp.float32
        )
        rows = pl.ds(pl.multiple_of(i * block_q, block_q), block_q)
        dq_scr[rows, :] = dq_scr[rows, :] + scale * jnp.dot(
            ds, k, preferred_element_type=jnp.float32
        )

    @pl.when(i == num_q_blocks - 1)
    def _finalize():
        dk_ref[:] = dk_scr[:].astype(dk_ref.dtype)
        dv_ref[:] = dv_scr[:].astype(dv_ref.dtype)

    @pl.when((j == num_k_blocks - 1) & (i == num_q_blocks - 1))
    def _finalize_row():
        dq_ref[:] = dq_scr[:].astype(dq_ref.dtype)


def _oracle_forward(q, k, v, block_q, block_k):
    from jax.experimental import pallas as pl

    bh, s, d = q.shape
    num_q, num_k = s // block_q, s // block_k

    def q_spec(width):
        return pl.BlockSpec(
            (None, block_q, width), lambda b_, i, j: (b_, i, 0))

    def kv_index(b_, i, j):
        return (b_, jnp.minimum(j, _last_kj(i, block_q, block_k, num_k)), 0)

    k_spec = pl.BlockSpec((None, block_k, d), kv_index)
    return pl.pallas_call(
        functools.partial(
            _oracle_fwd_kernel, block_q=block_q, block_k=block_k,
            num_k_blocks=num_k, scale=d**-0.5),
        grid=(bh, num_q, num_k),
        in_specs=[q_spec(d), k_spec, k_spec],
        out_specs=[q_spec(d), q_spec(fa.LANES)],
        out_shape=[
            jax.ShapeDtypeStruct((bh, s, d), q.dtype),
            jax.ShapeDtypeStruct((bh, s, fa.LANES), jnp.float32),
        ],
        scratch_shapes=[
            pltpu.VMEM((block_q, fa.LANES), jnp.float32),
            pltpu.VMEM((block_q, fa.LANES), jnp.float32),
            pltpu.VMEM((block_q, d), jnp.float32),
        ],
        interpret=True,
    )(q, k, v)


def _oracle_backward(q, k, v, g, lse_fat, delta_fat, block_q, block_k):
    from jax.experimental import pallas as pl

    bh, s, d = q.shape
    num_q, num_k = s // block_q, s // block_k

    def q_spec(width):
        return pl.BlockSpec(
            (None, block_q, width),
            lambda b_, j, i: (
                b_, jnp.maximum(i, _first_qi(j, block_q, block_k)), 0))

    k_spec = pl.BlockSpec((None, block_k, d), lambda b_, j, i: (b_, j, 0))
    return pl.pallas_call(
        functools.partial(
            _oracle_bwd_kernel, block_q=block_q, block_k=block_k,
            num_q_blocks=num_q, num_k_blocks=num_k, scale=d**-0.5),
        grid=(bh, num_k, num_q),
        in_specs=[
            q_spec(d), k_spec, k_spec, q_spec(d),
            q_spec(fa.LANES), q_spec(fa.LANES),
        ],
        out_specs=[
            pl.BlockSpec((None, s, d), lambda b_, j, i: (b_, 0, 0)),
            k_spec, k_spec,
        ],
        out_shape=[jax.ShapeDtypeStruct((bh, s, d), q.dtype)] * 3,
        scratch_shapes=[
            pltpu.VMEM((s, d), jnp.float32),
            pltpu.VMEM((block_k, d), jnp.float32),
            pltpu.VMEM((block_k, d), jnp.float32),
        ],
        interpret=True,
    )(q, k, v, g, lse_fat, delta_fat)


def _oracle_both_passes(q, k, v, g, block_q, block_k):
    """The oracle kernels round the module's own kernels' boundary: one
    lane of lse between the passes, delta from the rounded o."""
    b, h, s, d = q.shape
    q3, k3, v3, g3 = (x.reshape(b * h, s, d) for x in (q, k, v, g))
    out, lse_fat = _oracle_forward(q3, k3, v3, block_q, block_k)
    delta = jnp.sum(
        g3.astype(jnp.float32) * out.astype(jnp.float32), axis=-1)
    lse = lse_fat[:, :, 0]
    fat = (b * h, s, fa.LANES)
    grads = _oracle_backward(
        q3, k3, v3, g3, jnp.broadcast_to(lse[:, :, None], fat),
        jnp.broadcast_to(delta[:, :, None], fat), block_q, block_k)
    return (out.reshape(q.shape), lse.reshape(b, h, s)) + tuple(
        x.reshape(q.shape) for x in grads)


def _both_passes(q, k, v, g, block_q, block_k):
    out, lse = fa._flash_forward(q, k, v, True, block_q, block_k, True)
    return (out, lse) + fa._flash_backward(
        q, k, v, out, lse, g, True, block_q, block_k
    )


def _bits(x):
    x = np.asarray(x)
    return x.view(np.uint16 if x.dtype == jnp.bfloat16 else np.uint32)


@pytest.mark.parametrize("tiles", [4, 8])
@pytest.mark.parametrize(
    "block_q,block_k",
    [(128, 128), (256, 256), (128, 256), (256, 128)],
    ids=["equal128", "equal256", "unequal_k", "unequal_q"],
)
@pytest.mark.parametrize("dtype", ["float32", "bfloat16"])
@pytest.mark.parametrize("head", [64, 128])
def test_tile_kinds_give_the_bits_of_masking_every_tile(
    monkeypatch, head, dtype, block_q, block_k, tiles
):
    """A tile below the diagonal runs with no mask, a crossed one masked,
    and a grid step is spent on no other tile: the same products on the
    same values in the same order, less the selects that return their
    input and the steps that did nothing. So o and lse are the oracle's bit for
    bit in every case, and dq, dk, dv at head 64. At head 128 the CPU
    backend (not Mosaic: on the chip all five are bit-equal at the cells'
    three shapes, PERF.md section 6, PR 42) contracts `scale * s - lse`
    into a fused multiply-add once the select between them is gone: the
    scale is 2^-3.5 there and the product rounds (at head 64 it is 2^-3
    and exact), so a gradient may differ by one float32 ulp, which a
    bfloat16 result shows as one of its own ulps in a few elements."""
    monkeypatch.setenv("EDL_FORCE_PALLAS_INTERPRET", "1")
    s = tiles * max(block_q, block_k)
    rng = np.random.default_rng(head + s + block_q)
    q, k, v, g = (
        jnp.asarray(rng.normal(size=(1, 1, s, head)), dtype)
        for _ in range(4)
    )
    got = _both_passes(q, k, v, g, block_q, block_k)
    want = _oracle_both_passes(q, k, v, g, block_q, block_k)
    for name, a, b in zip(("o", "lse", "dq", "dk", "dv"), got, want):
        assert a.dtype == b.dtype and a.shape == b.shape
        if head == 64 or name in ("o", "lse"):
            np.testing.assert_array_equal(_bits(a), _bits(b), err_msg=name)
            continue
        a, b = (np.asarray(x.astype(jnp.float32)) for x in (a, b))
        if dtype == "float32":
            np.testing.assert_allclose(a, b, rtol=0, atol=1e-6, err_msg=name)
        else:
            off = np.abs(a - b) > 1e-6
            assert off.mean() < 1e-3, name
            assert (np.abs(a - b) <= BF16_EPS * np.abs(b))[off].all(), name


CAUSAL_BLOCKS = [
    (128, 128), (256, 256), (512, 512), (1024, 1024), (128, 256),
    (256, 128), (128, 1024), (1024, 256), (512, 1024), (1024, 512),
]


@pytest.mark.parametrize("block_q,block_k", CAUSAL_BLOCKS)
@pytest.mark.parametrize("s", [1024, 2048, 4096, 8192])
def test_causal_tile_kinds_against_the_mask_itself(s, block_q, block_k):
    """The helper's counts against a brute-force count over the [S, S]
    mask: a tile runs if any of its scores is seen, lies below the
    diagonal if all are, is crossed otherwise; and with the skipped tiles
    the three kinds partition the grid."""
    num_q, num_k = s // block_q, s // block_k
    seen = np.tril(np.ones((s, s), bool)).reshape(
        num_q, block_q, num_k, block_k
    )
    some, every = seen.any(axis=(1, 3)), seen.all(axis=(1, 3))
    run, below, crossed = causal_tile_kinds(s, block_q, block_k)
    assert (run, below, crossed) == (
        some.sum(), every.sum(), (some & ~every).sum()
    )
    assert (~some).sum() + below + crossed == num_q * num_k
    # The kernels' own predicates, tile by tile.
    for i in range(num_q):
        for j in range(num_k):
            whole, (hit,) = fa._tile_kinds(True, i, j, block_q, block_k)
            assert some[i, j] == (whole or hit)
            assert every[i, j] == whole
            assert every[i, j] == fa._below_diagonal(i, j, block_q, block_k)


@pytest.mark.parametrize("block_q,block_k", CAUSAL_BLOCKS)
@pytest.mark.parametrize("s", [1024, 2048, 4096, 8192])
def test_the_decode_walks_the_causal_run_tiles_alone(s, block_q, block_k):
    """Both passes' grids under the causal mask, unequal blocks included
    (their masks read `(i, j)`, which the decode has to give them)."""
    walk_the_decode(
        True, s, block_q, block_k, np.tril(np.ones((s, s), bool)))


@pytest.mark.parametrize(
    "s,block_q,block_k",
    [(128, 128, 128), (1024, 128, 128), (1024, 256, 128), (1024, 128, 512)])
def test_the_decode_of_the_unmasked_grid_is_a_division(s, block_q, block_k):
    """Under `False` every tile of the grid runs, whole: the run list is
    the rectangle, and the decode divides the step by a row's tiles where
    the other descriptions walk a chain a row long."""
    walk_the_decode(False, s, block_q, block_k, np.ones((s, s), bool))
    run = fa._run_tiles(False, s, block_q, block_k)
    assert run.width == s // block_k and run.ways == ()
    jaxpr = jax.make_jaxpr(lambda t: fa._major_at(t, run))(jnp.int32(0))
    assert [eqn.primitive.name for eqn in jaxpr.eqns] == ["div"]


@pytest.mark.parametrize("block_k,constant", [(128, True), (256, False)])
def test_the_mask_of_equal_blocks_is_a_constant_of_the_trace(
    block_k, constant
):
    """With equal blocks a crossed tile lies on the diagonal, so its mask
    reads neither grid index: that is what lets the compiler drop the
    score blocks above the diagonal (PERF.md section 6, PR 42)."""
    jaxpr = jax.make_jaxpr(
        lambda s, i, j: fa._causal_mask_scores(s, i, j, 128, block_k)
    )(jnp.zeros((128, block_k), jnp.float32), 1, 1).jaxpr
    indices = jaxpr.invars[1:]
    read = any(
        v is index
        for eqn in jaxpr.eqns for v in eqn.invars for index in indices
    )
    assert read != constant


def test_causal_tile_kinds_of_the_cells():
    assert causal_tile_kinds(4096, 1024, 1024) == (10, 6, 4)
    assert causal_tile_kinds(8192, 1024, 1024) == (36, 28, 8)


def test_block_fitting_keeps_pallas_for_512_multiples():
    """Raising the default block must not kick S=1536-style lengths off
    the Pallas kernel: blocks halve until they divide S."""
    from elasticdl_tpu.ops.flash_attention import _clamp_blocks

    assert _clamp_blocks(4096, 1024, 1024) == (1024, 1024)
    assert _clamp_blocks(1536, 1024, 1024) == (512, 512)
    assert _clamp_blocks(2560, 1024, 1024) == (512, 512)
    assert _clamp_blocks(384, 1024, 1024) == (384, 384)
    assert _clamp_blocks(96, 1024, 1024) == (96, 96)
