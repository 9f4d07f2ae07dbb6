"""The flash kernels at a key width beside a value width
(ops/flash_attention.py: latent attention's keys of 192 = 128 without
position + 64 rotary against values of 128): the kernels in interpret mode
against the dense oracle, forward and all three gradients; equal widths as
the calls they were before the widths parted; the names the unequal calls
carry; what a rematerialised caller keeps by the names of `KEPT`; what
sequence parallelism refuses."""

import hashlib

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from elasticdl_tpu.ops import flash_attention as fa
from elasticdl_tpu.ops.flash_attention import (
    Band,
    BlockDiffusion,
    flash_attention,
    reference_attention,
)


def _qkvg(seed, s, dk, dv, dtype, bh=2):
    rng = np.random.default_rng(seed)
    return tuple(
        jnp.asarray(rng.normal(size=(1, bh, s, d)), dtype)
        for d in (dk, dk, dv, dv))


@pytest.mark.parametrize("mask", [True, False], ids=["causal", "unmasked"])
@pytest.mark.parametrize("tile", [128, 256])
@pytest.mark.parametrize("dk,dv", [(192, 128), (24, 16)],
                         ids=["192_128", "24_16"])
@pytest.mark.parametrize("dtype", ["float32", "bfloat16"])
def test_kernels_against_the_dense_oracle(
        monkeypatch, mask, tile, dk, dv, dtype):
    """Forward, dq, dk, dv of the kernels (interpret mode) at unequal
    widths against plain XLA: the output and its cotangent at the value
    width, dq and dk at the key width, the scale the key width's."""
    monkeypatch.setenv("EDL_FORCE_PALLAS_INTERPRET", "1")
    q, k, v, g = _qkvg(dk + tile, 512, dk, dv, dtype)

    def kernel(q, k, v):
        return flash_attention(q, k, v, mask, tile, tile)

    def oracle(q, k, v):
        return reference_attention(
            *(x.astype(jnp.float32) for x in (q, k, v)), mask)

    out, vjp = jax.vjp(kernel, q, k, v)
    want, want_vjp = jax.vjp(oracle, q, k, v)
    assert out.shape == (1, 2, 512, dv) and out.dtype == q.dtype
    tol = 2e-5 if dtype == "float32" else 3e-2
    np.testing.assert_allclose(
        np.asarray(out, np.float32), np.asarray(want), atol=tol, rtol=tol)
    for name, a, b, like in zip(
            ("dq", "dk", "dv"), vjp(g), want_vjp(g.astype(jnp.float32)),
            (q, k, v)):
        assert a.dtype == like.dtype and a.shape == like.shape
        np.testing.assert_allclose(
            np.asarray(a, np.float32), np.asarray(b, np.float32),
            atol=tol * 4, rtol=tol, err_msg=name)


def test_the_scale_is_the_key_widths():
    """Scores times Dk^-0.5 (192^-0.5 for the latent attention), not the
    value width's: on the XLA path and in the oracle alike."""
    q, k, v, _ = _qkvg(3, 16, 24, 16, "float32")
    scores = jnp.einsum("bhqd,bhkd->bhqk", q, k) * 24 ** -0.5
    seen = jnp.tril(jnp.ones((16, 16), bool))
    want = jnp.einsum(
        "bhqk,bhkd->bhqd",
        jax.nn.softmax(jnp.where(seen, scores, -1e30), -1), v)
    np.testing.assert_allclose(
        reference_attention(q, k, v, True), want, atol=1e-6)
    np.testing.assert_allclose(
        flash_attention(q, k, v, True), want, atol=1e-6)
    wrong = jnp.einsum(
        "bhqk,bhkd->bhqd", jax.nn.softmax(jnp.where(
            seen, scores * (24 / 16) ** 0.5, -1e30), -1), v)
    assert float(jnp.max(jnp.abs(wrong - want))) > 1e-2


def test_xla_path_takes_the_two_widths():
    """Off the chip the same call is full attention, forward and backward
    (`_fallback_attention`, `_bwd_xla`), at a key width beside a value
    width."""
    q, k, v, g = _qkvg(7, 64, 24, 16, "float32")
    out, vjp = jax.vjp(lambda *a: flash_attention(*a, True), q, k, v)
    want, want_vjp = jax.vjp(
        lambda *a: reference_attention(*a, True), q, k, v)
    np.testing.assert_allclose(out, want, atol=1e-6)
    for a, b in zip(vjp(g), want_vjp(g)):
        assert a.shape == b.shape
        np.testing.assert_allclose(a, b, atol=1e-5)


# ---------- equal widths: the calls as they were ----------


def _pallas_calls(jaxpr):
    for e in jaxpr.eqns:
        if e.primitive.name == "pallas_call":
            yield e
        for value in e.params.values():
            for sub in value if isinstance(value, (list, tuple)) else [value]:
                inner = getattr(sub, "jaxpr", sub)
                if hasattr(inner, "eqns"):
                    yield from _pallas_calls(inner)


def _calls_of(mask, s, dk, dv):
    """{kernel's name: (grid, equations of its body, digest of the body's
    text, its results, its VMEM limit)} of one forward and backward over
    128 x 128 tiles, bfloat16 [1, 2, s, .], traced as the chip would."""
    q = jnp.zeros((1, 2, s, dk), jnp.bfloat16)
    v = jnp.zeros((1, 2, s, dv), jnp.bfloat16)
    jaxpr = jax.make_jaxpr(jax.grad(
        lambda q, k, v: jnp.sum(flash_attention(
            q, k, v, mask, 128, 128).astype(jnp.float32)), (0, 1, 2)))(
                q, q, v).jaxpr
    found = {}
    for e in _pallas_calls(jaxpr):
        p = e.params
        mosaic = p["compiler_params"].get("mosaic_tpu")
        found[p["name"]] = (
            tuple(p["grid_mapping"].grid), len(p["jaxpr"].eqns),
            hashlib.sha256(str(p["jaxpr"]).encode()).hexdigest()[:16],
            tuple(str(var.aval) for var in e.outvars),
            None if mosaic is None else mosaic.vmem_limit_bytes)
    return found


def _three(shape):
    return (f"bfloat16[{shape}]",) * 3


# Recorded on the parent of the PR that parted the widths (commit cfa0502,
# jax 0.9.0): the same equation, so the same program and the same bits, on
# the chip and under the interpreter.
BEFORE_THE_WIDTHS_PARTED = {
    ("causal64", True, 512, 64): {
        "flash_fwd": ((2, 10), 50, "176a3fd2a13419c7",
                      ("bfloat16[2,512,64]", "float32[2,512,128]"), None),
        "flash_bwd": ((2, 10), 56, "d893be7fa78d9227", _three("2,512,64"),
                      1179648)},
    ("causal128", True, 512, 128): {
        "flash_fwd": ((2, 10), 50, "18206b65deb222d2",
                      ("bfloat16[2,512,128]", "float32[2,512,128]"), None),
        "flash_bwd": ((2, 10), 56, "d89f68e817470487", _three("2,512,128"),
                      None)},
    ("unmasked", False, 256, 64): {
        "flash_fwd": ((2, 4), 41, "6e477dcf571bbbfa",
                      ("bfloat16[2,256,64]", "float32[2,256,128]"), None),
        "flash_bwd": ((2, 4), 53, "9037b071f72da1e7", _three("2,256,64"),
                      None)},
    ("band", Band(256), 512, 128): {
        "band_flash_fwd": (
            (2, 9), 56, "2f91eb360a292186",
            ("bfloat16[2,512,128]", "float32[2,512,128]"), None),
        "band_flash_bwd": (
            (2, 9), 62, "29264307dabcad67", _three("2,512,128"), None)},
    ("block_diffusion", BlockDiffusion(4, 256), 512, 128): {
        "bd_flash_fwd": (
            (2, 8), 64, "f1128aa2b682c94b",
            ("bfloat16[2,512,128]", "float32[2,512,128]"), None),
        "bd_flash_bwd": (
            (2, 8), 64, "78d6924cbb053629", _three("2,512,128"), None)},
}


@pytest.mark.parametrize(
    "case", sorted(BEFORE_THE_WIDTHS_PARTED, key=lambda c: c[0]),
    ids=lambda c: c[0])
def test_equal_widths_make_the_calls_they_made_before(monkeypatch, case):
    """With Dk == Dv every mask that exists (causal, unmasked, `Band`,
    `BlockDiffusion`) traces the `pallas_call` it traced before the widths
    parted: the same name, grid, results and VMEM limit, and a body whose
    text has the same digest: the same bits, whatever runs it."""
    monkeypatch.delenv("EDL_FORCE_PALLAS_INTERPRET", raising=False)
    monkeypatch.setattr(fa, "_use_pallas", lambda: True)
    _, mask, s, d = case
    got = _calls_of(mask, s, d, d)
    want = BEFORE_THE_WIDTHS_PARTED[case]
    assert set(got) == set(want)
    for name, (grid, n_eqns, digest, results, vmem) in want.items():
        assert got[name][:2] == (grid, n_eqns), name
        assert got[name][3] == results, name
        if vmem is not None:
            assert got[name][4] == vmem, name
        assert got[name][2] == digest, name


def test_unequal_widths_carry_names_and_shapes_of_their_own(monkeypatch):
    """`mla_` before the mask's own name; the grid the causal one; q, k and
    dq, dk at the key width, v, o, dO and dv at the value width; the
    backward asks VMEM for both."""
    assert fa._kernel_name(True, "flash_fwd", True) == "mla_flash_fwd"
    assert fa._kernel_name(False, "flash_bwd", True) == "mla_flash_bwd"
    assert fa._kernel_name(Band(8), "flash_fwd", True) == (
        "mla_band_flash_fwd")
    assert fa._kernel_name(True, "flash_fwd") == "flash_fwd"
    assert fa._kernel_name(True, "flash_fwd", False) == "flash_fwd"
    monkeypatch.delenv("EDL_FORCE_PALLAS_INTERPRET", raising=False)
    monkeypatch.setattr(fa, "_use_pallas", lambda: True)
    got = _calls_of(True, 512, 192, 128)
    assert set(got) == {"mla_flash_fwd", "mla_flash_bwd"}
    assert got["mla_flash_fwd"][0] == got["mla_flash_bwd"][0] == (2, 10)
    assert got["mla_flash_fwd"][3] == (
        "bfloat16[2,512,128]", "float32[2,512,128]")
    assert got["mla_flash_bwd"][3] == (
        "bfloat16[2,512,192]", "bfloat16[2,512,192]", "bfloat16[2,512,128]")
    assert got["mla_flash_bwd"][4] == fa._bwd_vmem_bytes(
        512, 192, 128, 128, 128, 2)
    # One formula: at equal widths what it was (the parent's count at 64).
    assert fa._bwd_vmem_bytes(512, 64, 64, 128, 128, 2) == 1179648
    # The cell's call: S 16384 at 192 / 128 over 1024 x 1024 tiles.
    assert fa._bwd_vmem_bytes(16384, 192, 128, 1024, 1024, 2) == (
        6 * 4 * 2**20 + 2 * (2048 * 320 * 2 + 2 * 1024 * 128 * 4
                             + 1024 * 320 * 2)
        + 16384 * 192 * 8 + 1024 * 320 * 4)
    assert fa._bwd_vmem_bytes(
        16384, 192, 128, 1024, 1024, 2) < fa.VMEM_BUDGET_BYTES


# ---------- a rematerialised caller that keeps the kernel's result ----------


KEPT_CASES = [
    ("causal192_128", True, 192, 128), ("causal128", True, 128, 128),
    ("band", Band(128), 128, 128),
    ("block_diffusion", BlockDiffusion(4, 128), 128, 128),
]


def _forward_calls(jaxpr, path):
    """Attention forwards in a traced value-and-gradient: on the kernel's
    path the `pallas_call`s named `*flash_fwd`; on the XLA path, where a
    forward is two products and the one backward five, by the products."""
    if path == "kernel":
        return sum(e.params["name"].endswith("flash_fwd")
                   for e in _pallas_calls(jaxpr))
    return (str(jaxpr).count("dot_general") - 5) // 2


@pytest.mark.parametrize("path", ["kernel", "fallback"])
@pytest.mark.parametrize("case", KEPT_CASES, ids=lambda c: c[0])
def test_a_remat_that_keeps_the_names_runs_the_forward_once(
        monkeypatch, case, path):
    """Under `jax.checkpoint(f, policy=save_only_these_names(*KEPT))` the
    output and the compact lse (on the XLA path, where lse is None, the
    output alone) are saved, so the backward pass holds no forward call,
    where a plain `jax.checkpoint` runs it a second time; the gradients
    are those of no remat at all, to the bit, under every mask that
    exists. Counted as the chip would trace it, run interpreted."""
    _, mask, dk, dv = case
    q, k, v, _ = _qkvg(dk, 256, dk, dv, "bfloat16")
    policy = jax.checkpoint_policies.save_only_these_names(*fa.KEPT)

    def forms():  # anew a trace: `jax.checkpoint` keeps a function's
        def f(q, k, v):  # a product after the call, as a layer has
            out = flash_attention(q, k, v, mask, 128, 128)
            return jnp.sum(out.astype(jnp.float32) ** 2)

        return {"none": f, "plain": jax.checkpoint(f),
                "kept": jax.checkpoint(f, policy=policy)}

    monkeypatch.delenv("EDL_FORCE_PALLAS_INTERPRET", raising=False)
    monkeypatch.setattr(fa, "_use_pallas", lambda: path == "kernel")
    calls = {
        name: _forward_calls(jax.make_jaxpr(
            jax.value_and_grad(f, (0, 1, 2)))(q, k, v).jaxpr, path)
        for name, f in forms().items()}
    assert calls == {"none": 1, "plain": 2, "kept": 1}
    monkeypatch.undo()
    if path == "kernel":
        monkeypatch.setenv("EDL_FORCE_PALLAS_INTERPRET", "1")
    got = {name: jax.value_and_grad(f, (0, 1, 2))(q, k, v)
           for name, f in forms().items()}
    for name in ("plain", "kept"):
        for a, b in zip(jax.tree_util.tree_leaves(got[name]),
                        jax.tree_util.tree_leaves(got["none"])):
            assert a.dtype == b.dtype
            np.testing.assert_array_equal(
                np.asarray(a, np.float32), np.asarray(b, np.float32), name)


def test_unequal_widths_under_ring_or_ulysses_attention_raise():
    from elasticdl_tpu.parallel.ring_attention import (
        ring_attention,
        zigzag_ring_attention,
    )
    from elasticdl_tpu.parallel.ulysses import ulysses_attention

    q, v = jnp.zeros((1, 2, 16, 24)), jnp.zeros((1, 2, 16, 16))
    for attend in (ring_attention, zigzag_ring_attention, ulysses_attention):
        with pytest.raises(ValueError, match="one head width"):
            attend(q, q, v, "seq", causal=True)
