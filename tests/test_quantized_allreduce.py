"""Quantized cross-replica gradient reduction (parallel/quantized.py,
EQuARX-style int8 wire payloads): numeric error bounded by the per-block
quantization step, and a DP training loop using it still converges to
the same solution as exact reduction."""

import jax
import pytest
import jax.numpy as jnp
import numpy as np
import optax
from jax import shard_map
from jax.sharding import Mesh, PartitionSpec as P

from elasticdl_tpu.parallel.quantized import (
    quantized_pmean,
    quantized_psum_1d,
)

N = 8


def _mesh():
    return Mesh(np.array(jax.devices()[:N]), ("data",))


def test_quantized_psum_matches_exact_within_step():
    mesh = _mesh()
    rng = np.random.default_rng(0)
    # Per-replica distinct vectors (sharded over the axis).
    x = rng.normal(size=(N, 64 * N)).astype(np.float32)

    def exact(v):
        return jax.lax.psum(v, "data")

    def quant(v):
        return quantized_psum_1d(v, "data")

    run = lambda f: shard_map(  # noqa: E731
        f, mesh=mesh, in_specs=P("data"), out_specs=P("data"),
        check_vma=False,
    )
    want = np.asarray(run(lambda v: exact(v[0])[None])(x))
    got = np.asarray(run(lambda v: quant(v[0])[None])(x))
    # Two quantized wire legs: error <= 2 * (blockwise absmax of the
    # involved tensors) / 127 per element; bound it loosely but
    # meaningfully relative to the summed magnitudes.
    step = 2 * np.abs(x).max() * N / 127.0
    np.testing.assert_allclose(got, want, atol=step)
    assert not np.array_equal(got, want)  # it IS quantized


def test_quantized_pmean_tree_roundtrip():
    mesh = _mesh()
    rng = np.random.default_rng(1)
    tree = {
        "w": rng.normal(size=(N, 8, 3)).astype(np.float32),
        "b": rng.normal(size=(N, 5)).astype(np.float32),
    }

    def body(t):
        local = jax.tree_util.tree_map(lambda a: a[0], t)
        out = quantized_pmean(local, "data")
        return jax.tree_util.tree_map(lambda a: a[None], out)

    got = shard_map(
        body,
        mesh=mesh,
        in_specs=(jax.tree_util.tree_map(lambda _: P("data"), tree),),
        out_specs=jax.tree_util.tree_map(lambda _: P("data"), tree),
        check_vma=False,
    )(tree)
    for key in tree:
        want = tree[key].mean(axis=0)
        for r in range(N):
            np.testing.assert_allclose(
                np.asarray(got[key])[r], want, atol=0.05
            )


def test_quantized_pmean_psum_lanes_partial_auto():
    """The psum-lane formulation: (a) numerically tracks the exact mean
    within one int8 rounding step, (b) compiles inside a PARTIAL-auto
    shard_map (manual data axis, automatic model axis) — where the
    all_to_all wire hits a fatal SPMD-partitioner check, the crash behind
    the dp_tp_quantized drill's old xfail."""
    devices = np.array(jax.devices()[:8]).reshape(4, 2)
    mesh = Mesh(devices, ("data", "model"))
    rng = np.random.default_rng(3)
    tree = {
        "w": rng.normal(size=(4, 8, 6)).astype(np.float32),
        "b": rng.normal(size=(4, 10)).astype(np.float32),
    }

    def body(t):
        local = jax.tree_util.tree_map(lambda a: a[0], t)
        out = quantized_pmean(local, "data", collectives="psum_lanes")
        return jax.tree_util.tree_map(lambda a: a[None], out)

    specs = jax.tree_util.tree_map(lambda _: P("data"), tree)
    with mesh:
        got = jax.jit(shard_map(
            body, mesh=mesh, in_specs=(specs,), out_specs=specs,
            check_vma=False, axis_names={"data"},
        ))(tree)
    for key in tree:
        want = tree[key].mean(axis=0)
        step = np.abs(tree[key]).max() / 127.0
        for r in range(4):
            np.testing.assert_allclose(
                np.asarray(got[key])[r], want, atol=step + 1e-6
            )


@pytest.mark.slow
def test_dp_training_with_quantized_gradients_converges():
    """Explicit-gradient DP step: per-shard grads, quantized-allreduce
    mean, shared SGD update — converges to the same linear solution as
    exact reduction (quantization noise behaves like stochastic
    rounding, not bias).

    slow: this compile wedges XLA for minutes (occasionally SIGABRTs the
    interpreter) on a 1-core CPU host — run it on real hardware, not in
    the wall-clock-capped tier-1 lane."""
    mesh = _mesh()
    rng = np.random.default_rng(2)
    true_w = np.asarray([1.0, -2.0, 3.0, 0.5], np.float32)
    x = rng.normal(size=(64, 4)).astype(np.float32)
    y = (x @ true_w).astype(np.float32)

    def grads_of(w, xb, yb):
        def loss(w):
            return jnp.mean((xb @ w - yb) ** 2)

        return jax.grad(loss)(w)

    def make_step(reduce_fn):
        def step(w, xb, yb):
            g = grads_of(w, xb, yb)
            g = reduce_fn(g, "data")
            return w - 0.05 * g

        return shard_map(
            step,
            mesh=mesh,
            in_specs=(P(), P("data"), P("data")),
            out_specs=P(),
            check_vma=False,
        )

    quant_step = jax.jit(make_step(quantized_pmean))
    exact_step = jax.jit(
        make_step(lambda g, ax: jax.lax.pmean(g, ax))
    )
    wq = jnp.zeros(4)
    we = jnp.zeros(4)
    for _ in range(200):
        wq = quant_step(wq, x, y)
        we = exact_step(we, x, y)
    np.testing.assert_allclose(np.asarray(we), true_w, atol=1e-3)
    np.testing.assert_allclose(np.asarray(wq), true_w, atol=0.02)


def test_quantized_pmean_bf16_leaves():
    """bf16 gradient trees round-trip: accumulation runs in f32, outputs
    restore the leaf dtype."""
    mesh = _mesh()
    rng = np.random.default_rng(5)
    tree = {"w": jnp.asarray(
        rng.normal(size=(N, 16)).astype(np.float32), jnp.bfloat16
    )}

    def body(t):
        local = jax.tree_util.tree_map(lambda a: a[0], t)
        out = quantized_pmean(local, "data")
        return jax.tree_util.tree_map(lambda a: a[None], out)

    got = shard_map(
        body,
        mesh=mesh,
        in_specs=(jax.tree_util.tree_map(lambda _: P("data"), tree),),
        out_specs=jax.tree_util.tree_map(lambda _: P("data"), tree),
        check_vma=False,
    )(tree)
    assert got["w"].dtype == jnp.bfloat16
    want = np.asarray(tree["w"], np.float32).mean(axis=0)
    np.testing.assert_allclose(
        np.asarray(got["w"], np.float32)[0], want, atol=0.08
    )


def test_trainer_quantized_grads_close_to_exact_and_int8_on_wire():
    """--quantized_grads end to end in the AllReduce trainer: losses track
    the exact-f32 trainer within quantization noise while still going
    downhill. (Wire inspection lives in
    test_quantized_step_hlo_wire_bytes_reduction.)"""
    import tests.test_module as test_module
    from elasticdl_tpu.worker.allreduce_trainer import AllReduceTrainer
    from elasticdl_tpu.worker.master_client import MasterClient
    from tests.test_utils import start_master

    rng = np.random.default_rng(0)
    x = rng.normal(size=(16, test_module.FEATURE_DIM)).astype(np.float32)
    y = (x @ test_module.TRUE_W + test_module.TRUE_B).astype(np.float32)

    def run(quantized):
        with start_master(
            training_shards={"f": (0, 100)}, with_membership=True
        ) as m:
            mc = MasterClient(
                m["addr"], worker_id=0, worker_host="127.0.0.1"
            )
            t = AllReduceTrainer(
                test_module.custom_model(),
                test_module.loss,
                test_module.optimizer(),
                mc,
                seed=7,
                quantized_grads=quantized,
            )
            try:
                return [
                    float(jax.block_until_ready(
                        t.train_minibatch(x, y)[2]
                    ))
                    for _ in range(6)
                ]
            finally:
                t.close()
                mc.close()

    exact = run(False)
    quant = run(True)
    # Same downhill trajectory within int8-rounding noise.
    assert quant[0] == pytest.approx(exact[0], rel=0.05)
    assert quant[-1] < quant[0] * 0.8
    for a, b in zip(exact, quant):
        assert b == pytest.approx(a, rel=0.15), (exact, quant)


def test_quantized_step_hlo_wire_bytes_reduction():
    """Measured wire-byte accounting from compiled HLO: the quantized step's
    collective operand bytes must be well under half the exact step's
    (analytically ~4x less; scales and scalar syncs keep it from exactly
    4)."""
    import re

    import tests.test_module as test_module
    from elasticdl_tpu.worker.allreduce_trainer import AllReduceTrainer
    from elasticdl_tpu.worker.master_client import MasterClient
    from tests.test_utils import start_master

    rng = np.random.default_rng(0)
    x = rng.normal(size=(16, test_module.FEATURE_DIM)).astype(np.float32)
    y = (x @ test_module.TRUE_W + test_module.TRUE_B).astype(np.float32)

    _DTYPE_BYTES = {"f32": 4, "s32": 4, "u32": 4, "s8": 1, "u8": 1,
                    "f16": 2, "bf16": 2, "f64": 8, "s64": 8, "u64": 8,
                    "pred": 1}

    def collective_bytes(hlo):
        # Ring-wire accounting from each collective's RESULT type: an
        # all-reduce moves every byte twice (reduce-scatter leg +
        # all-gather leg), the explicit one-leg ops once. Shapes are
        # summed across the whole (possibly tuple) result — grad
        # allreduces lower to ONE tuple op over all leaves, and the type
        # may contain /*index=N*/ comments, so the parse walks everything
        # left of the op token rather than one dtype[dims] match.
        total = 0
        for line in hlo.splitlines():
            m = re.search(
                r"\s(all-reduce|all-gather|all-to-all|reduce-scatter|"
                r"collective-permute)\(",
                line,
            )
            if not m or "=" not in line[:m.start()]:
                continue
            factor = 2 if m.group(1) == "all-reduce" else 1
            head = line[line.index("=") + 1:m.start()]
            for dtype, dims in re.findall(r"(\w+)\[([\d,]*)\]", head):
                n = 1
                for d in dims.split(","):
                    if d:
                        n *= int(d)
                total += factor * n * _DTYPE_BYTES.get(dtype, 4)
        return total

    # A model with real parameter volume: on the 5-param linear toy the
    # per-block f32 scales and axis padding dominate and the measurement
    # says nothing (59 vs 24 bytes); at ~50k params the gradient payload
    # does.
    from elasticdl_tpu.models.transformer import transformer_lm as tlm

    cfg = tlm.LMConfig(
        vocab=64, d_model=32, n_heads=4, n_layers=1, max_len=16,
        activation_dtype="float32",
    )
    tokens = (np.arange(16 * 17).reshape(16, 17) * 5) % cfg.vocab
    f, l = tokens[:, :-1], tokens[:, 1:]

    def hlo_for(quantized):
        with start_master(
            training_shards={"f": (0, 100)}, with_membership=True
        ) as m:
            mc = MasterClient(
                m["addr"], worker_id=0, worker_host="127.0.0.1"
            )
            t = AllReduceTrainer(
                tlm.custom_model(cfg),
                tlm.loss,
                tlm.optimizer(),
                mc,
                seed=7,
                quantized_grads=quantized,
            )
            try:
                t.train_minibatch(f, l)
                (step,) = t._sharded_steps.values()
                return step.lower(
                    t._variables, t._opt_state, jax.random.PRNGKey(0),
                    jax.device_put(f), jax.device_put(l),
                ).compile().as_text()
            finally:
                t.close()
                mc.close()

    quant_hlo = hlo_for(True)
    assert "s8[" in quant_hlo, "no int8 on the quantized step's wire"
    exact_b = collective_bytes(hlo_for(False))
    quant_b = collective_bytes(quant_hlo)
    assert exact_b > 0 and quant_b > 0
    # The gradient payload quantizes 4x (f32 ring -> int8 both legs);
    # per-block scales and the loss sync keep the whole-program ratio a
    # bit above 1/4.
    assert quant_b < 0.35 * exact_b, (quant_b, exact_b)


def test_quantized_grads_on_multihost_zero1_mesh():
    """The advertised composition: a {data: 2, zero: 4} mesh (multi-host
    ZeRO-1 layout) with --quantized_grads — grads reduce exactly over the
    intra-host zero axis and through int8 over the cross-process data
    axis, while the optimizer state stays zero-sharded. Losses must track
    the exact-f32 two-axis trainer within quantization noise."""
    import tests.test_module as test_module
    from elasticdl_tpu.parallel.mesh import DATA_AXIS, ZERO_AXIS, make_mesh
    from elasticdl_tpu.worker.allreduce_trainer import AllReduceTrainer
    from elasticdl_tpu.worker.master_client import MasterClient
    from tests.test_utils import start_master

    rng = np.random.default_rng(0)
    x = rng.normal(size=(16, test_module.FEATURE_DIM)).astype(np.float32)
    y = (x @ test_module.TRUE_W + test_module.TRUE_B).astype(np.float32)

    def run(quantized):
        import os

        os.environ["EDL_TEST_OPT"] = "adam"  # real dim-0 moments to shard
        try:
            with start_master(
                training_shards={"f": (0, 100)}, with_membership=True
            ) as m:
                mc = MasterClient(
                    m["addr"], worker_id=0, worker_host="127.0.0.1"
                )
                t = AllReduceTrainer(
                    test_module.custom_model(),
                    test_module.loss,
                    test_module.optimizer(),
                    mc,
                    seed=7,
                    zero1=True,
                    quantized_grads=quantized,
                )
                t._make_world_mesh = lambda: make_mesh(
                    {DATA_AXIS: 2, ZERO_AXIS: 4}
                )
                try:
                    losses = [
                        float(jax.block_until_ready(
                            t.train_minibatch(x, y)[2]
                        ))
                        for _ in range(5)
                    ]
                    return losses, t._mesh
                finally:
                    t.close()
                    mc.close()
        finally:
            os.environ.pop("EDL_TEST_OPT", None)

    exact, mesh_e = run(False)
    quant, mesh_q = run(True)
    assert mesh_e.shape == mesh_q.shape == {"data": 2, "zero": 4}
    assert quant[-1] < quant[0]  # still learning
    for a, b in zip(exact, quant):
        assert b == pytest.approx(a, rel=0.15), (exact, quant)


def test_trainer_quantized_grads_compose_with_tp():
    """--quantized_grads --model_parallel_size 2 (VERDICT r4 #5): the
    data-axis mean of model-sharded grads quantizes while the model-axis
    collectives stay exact — losses track the exact DP x TP trainer
    within int8 noise, still converging, with the model axis really
    formed (no silent fallback or warn-and-ignore).

    (Previously slow-marked as "wedges/aborts XLA": the abort was the
    SPMD partitioner's fatal IsManualSubgroup check on all_to_all inside
    a partial-auto shard_map; the TP variant now reduces through
    quantized_pmean's psum-lane formulation and compiles in seconds.)"""
    import tests.test_module as test_module
    from elasticdl_tpu.worker.allreduce_trainer import AllReduceTrainer
    from elasticdl_tpu.worker.master_client import MasterClient
    from tests.test_utils import start_master

    rng = np.random.default_rng(0)
    x = rng.normal(size=(16, test_module.FEATURE_DIM)).astype(np.float32)
    y = (x @ test_module.TRUE_W + test_module.TRUE_B).astype(np.float32)

    def run(quantized):
        with start_master(
            training_shards={"f": (0, 100)}, with_membership=True
        ) as m:
            mc = MasterClient(
                m["addr"], worker_id=0, worker_host="127.0.0.1"
            )
            t = AllReduceTrainer(
                test_module.custom_model(),
                test_module.loss,
                test_module.optimizer(),
                mc,
                seed=7,
                model_parallel_size=2,
                param_specs_fn=test_module.param_specs,
                quantized_grads=quantized,
            )
            try:
                losses = [
                    float(jax.block_until_ready(
                        t.train_minibatch(x, y)[2]
                    ))
                    for _ in range(6)
                ]
                assert dict(t._mesh.shape) == {"data": 4, "model": 2}
                return losses
            finally:
                t.close()
                mc.close()

    exact = run(False)
    quant = run(True)
    assert quant[0] == pytest.approx(exact[0], rel=0.05)
    assert quant[-1] < quant[0] * 0.8
    for a, b in zip(exact, quant):
        assert b == pytest.approx(a, rel=0.15), (exact, quant)
