"""Elastic AllReduce trainer tests on the virtual 8-device CPU mesh.

Mirrors the reference's elastic-allreduce coverage (rendezvous re-init on
membership change + rank-0 broadcast, /root/reference/elasticdl/python/
worker/allreduce_trainer.py tests) in-process: real master gRPC server, real
Collective broadcast servers, no cluster.
"""

import numpy as np
import pytest

import tests.test_module as test_module
from elasticdl_tpu.worker.allreduce_trainer import AllReduceTrainer
from elasticdl_tpu.worker.master_client import MasterClient
from elasticdl_tpu.worker.trainer import LocalTrainer
from tests.test_utils import start_master


def _batch(n, seed):
    rng = np.random.default_rng(seed)
    x = rng.normal(size=(n, test_module.FEATURE_DIM)).astype(np.float32)
    y = (x @ test_module.TRUE_W + test_module.TRUE_B).astype(np.float32)
    return x, y


def _make_trainer(master, host, worker_id, **kw):
    mc = MasterClient(master["addr"], worker_id=worker_id, worker_host=host)
    t = AllReduceTrainer(
        test_module.custom_model(),
        test_module.loss,
        test_module.optimizer(),
        mc,
        **kw,
    )
    # The trainer rewrote worker_host to carry its bound broadcast port.
    assert mc.worker_host == f"{host.split(':')[0]}:{t.broadcast_port}"
    return t, mc


def test_sharded_step_matches_local_trainer():
    """Gradient averaging via batch sharding must reproduce the single-device
    step bit-for-bit (same global batch, replicated params)."""
    with start_master(
        training_shards={"f": (0, 100)}, with_membership=True
    ) as m:
        local = LocalTrainer(
            test_module.custom_model(),
            test_module.loss,
            test_module.optimizer(),
            seed=7,
        )
        dist, mc = _make_trainer(m, "127.0.0.1", 0, seed=7)
        try:
            for step in range(5):
                # Include a batch not divisible by the 8-device mesh (13) to
                # exercise pad+slice.
                n = 16 if step % 2 == 0 else 13
                x, y = _batch(n, seed=step)
                _, _, loss_l = local.train_minibatch(x, y)
                _, _, loss_d = dist.train_minibatch(x, y)
                assert loss_d == pytest.approx(loss_l, rel=1e-5), step
            lv = local.export_variables()["variables"]
            dv = dist.export_variables()["variables"]
            for a, b in zip(
                np.concatenate(
                    [np.ravel(v) for v in _leaves(lv)]
                ),
                np.concatenate(
                    [np.ravel(v) for v in _leaves(dv)]
                ),
            ):
                assert a == pytest.approx(b, rel=1e-4)
        finally:
            dist.close()
            mc.close()


def _leaves(tree):
    import jax

    return [np.asarray(x) for x in jax.tree_util.tree_leaves(tree)]


def test_world_change_triggers_remesh_and_state_survives():
    with start_master(
        training_shards={"f": (0, 100)}, with_membership=True
    ) as m:
        t, mc = _make_trainer(
            m, "127.0.0.1", 0, steps_per_world_check=2
        )
        try:
            x, y = _batch(16, seed=0)
            for _ in range(3):
                t.train_minibatch(x, y)
            version_before = t.get_model_version()
            epoch_before = t._group_id
            # A second worker "joins" (membership only): epoch bumps; the
            # trainer must detect it at the next world check and keep state.
            m["membership"].add_worker_host("10.0.0.2:9999")
            for _ in range(2):
                t.train_minibatch(x, y)
            assert t._group_id > epoch_before
            assert t.get_model_version() >= version_before + 2
            assert t.rank == 0 and t.world_size == 2
        finally:
            t.close()
            mc.close()


def test_joining_worker_pulls_rank0_state():
    """Second trainer joins mid-training and must adopt rank-0's exact
    (variables, opt_state, version) via the Collective broadcast pull —
    the Horovod broadcast_variables analog."""
    with start_master(
        training_shards={"f": (0, 100)}, with_membership=True
    ) as m:
        t0, mc0 = _make_trainer(m, "127.0.0.1", 0)
        try:
            x, y = _batch(16, seed=1)
            for _ in range(4):
                t0.train_minibatch(x, y)
            v0 = t0.get_model_version()

            t1, mc1 = _make_trainer(
                m, "127.0.0.2", 1, steps_per_world_check=1
            )
            try:
                # First minibatch: t1 initializes, joins the group, sees
                # rank 1, pulls t0's state before stepping.
                t1.init_variables_if_needed(x)
                t1.init_world_if_needed(force=True)
                assert t1.rank == 1
                assert t1.get_model_version() == v0
                w0 = _leaves(t0.export_variables()["variables"])
                w1 = _leaves(t1.export_variables()["variables"])
                for a, b in zip(w0, w1):
                    np.testing.assert_allclose(a, b)
            finally:
                t1.close()
                mc1.close()
        finally:
            t0.close()
            mc0.close()


def test_convergence_on_linear_problem():
    with start_master(
        training_shards={"f": (0, 100)}, with_membership=True
    ) as m:
        t, mc = _make_trainer(m, "127.0.0.1", 0)
        try:
            loss = None
            for step in range(60):
                x, y = _batch(32, seed=step)
                _, _, loss = t.train_minibatch(x, y)
            assert loss < 1e-2
        finally:
            t.close()
            mc.close()


def test_dp_tp_trainer_matches_pure_dp():
    """--model_parallel_size 2 + the transformer's param_specs hook: the
    hybrid DP x TP elastic trainer reproduces the pure-DP losses on
    identical batches (XLA inserts the Megatron collectives; semantics
    unchanged)."""
    from elasticdl_tpu.models.transformer import transformer_lm as tlm

    cfg = tlm.LMConfig(
        vocab=64, d_model=32, n_heads=4, n_layers=2, max_len=16,
        activation_dtype="float32",
    )
    rng = np.random.default_rng(0)
    batches = [
        rng.integers(0, cfg.vocab, size=(8, 17)).astype(np.int32)
        for _ in range(3)
    ]

    def run(mp):
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
                seed=3,
                model_parallel_size=mp,
                param_specs_fn=tlm.param_specs if mp > 1 else None,
            )
            try:
                losses = []
                for tok in batches:
                    _, _, loss = t.train_minibatch(
                        tok[:, :-1], tok[:, 1:]
                    )
                    losses.append(float(loss))
                if mp > 1:
                    assert "model" in t._mesh.shape
                    assert t._mesh.shape["model"] == mp
                return losses
            finally:
                t.close()
                mc.close()

    dp_losses = run(1)
    tp_losses = run(2)
    np.testing.assert_allclose(tp_losses, dp_losses, rtol=2e-4)


def test_tp_falls_back_when_indivisible():
    """model_parallel_size that doesn't divide the device count must not
    kill the job: the trainer drops to pure DP for that world (with the
    param_specs hook present, so the indivisibility branch — not the
    missing-hook branch — is what fires)."""
    from elasticdl_tpu.models.transformer import transformer_lm as tlm

    cfg = tlm.LMConfig(vocab=64, d_model=32, n_heads=4, n_layers=1,
                       max_len=16, activation_dtype="float32")
    with start_master(
        training_shards={"f": (0, 100)}, with_membership=True
    ) as m:
        mc = MasterClient(m["addr"], worker_id=0, worker_host="127.0.0.1")
        t = AllReduceTrainer(
            tlm.custom_model(cfg),
            tlm.loss,
            tlm.optimizer(),
            mc,
            model_parallel_size=3,  # 8 devices % 3 != 0
            param_specs_fn=tlm.param_specs,
        )
        try:
            tok = np.arange(8 * 17).reshape(8, 17).astype(np.int32) % 64
            ok, _, loss = t.train_minibatch(tok[:, :-1], tok[:, 1:])
            assert ok and np.isfinite(float(loss))
            assert "model" not in t._mesh.shape
        finally:
            t.close()
            mc.close()


def test_tp_falls_back_when_dims_indivisible():
    """mp divides the device count but not the model's sharded dims
    (n_heads=4 with mp=8): clear warning + a genuine pure-DP mesh (full
    data-axis width, no duplicated compute), not an opaque device_put
    crash."""
    from elasticdl_tpu.models.transformer import transformer_lm as tlm

    cfg = tlm.LMConfig(vocab=64, d_model=32, n_heads=4, n_layers=1,
                       max_len=16, activation_dtype="float32")
    with start_master(
        training_shards={"f": (0, 100)}, with_membership=True
    ) as m:
        mc = MasterClient(m["addr"], worker_id=0, worker_host="127.0.0.1")
        t = AllReduceTrainer(
            tlm.custom_model(cfg),
            tlm.loss,
            tlm.optimizer(),
            mc,
            model_parallel_size=8,  # divides devices; n_heads 4 % 8 != 0
            param_specs_fn=tlm.param_specs,
        )
        try:
            tok = np.arange(8 * 17).reshape(8, 17).astype(np.int32) % 64
            ok, _, loss = t.train_minibatch(tok[:, :-1], tok[:, 1:])
            assert ok and np.isfinite(float(loss))
            assert "model" not in t._mesh.shape
            assert t._mesh.shape["data"] == 8
        finally:
            t.close()
            mc.close()


def test_tp_guard_rails():
    """TP without a param_specs hook falls back to DP instead of
    duplicating compute across a useless model axis. (Multi-host TP is no
    longer rejected: the model axis is laid out inside each process —
    the 2-process drill in test_elasticity_drill.py proves that path.)"""
    with start_master(
        training_shards={"f": (0, 100)}, with_membership=True
    ) as m:
        mc = MasterClient(m["addr"], worker_id=0, worker_host="127.0.0.1")
        # mp=2 but no hook: mesh must stay pure-DP.
        t = AllReduceTrainer(
            test_module.custom_model(),
            test_module.loss,
            test_module.optimizer(),
            mc,
            model_parallel_size=2,
        )
        try:
            x, y = _batch(16, seed=0)
            ok, _, loss = t.train_minibatch(x, y)
            assert ok and np.isfinite(float(loss))
            assert "model" not in t._mesh.shape
        finally:
            t.close()
            mc.close()


def test_zero1_weight_update_sharding_matches_replicated():
    """ZeRO-1 (PAPERS.md arXiv:2004.13336): optimizer state shards over
    the data axis — per-chip moments shrink by the DP degree while the
    training math is unchanged. Losses must match the replicated-state
    trainer bit-for-bit, the state must actually be sharded, and an
    elastic re-mesh must carry it."""
    import jax

    from elasticdl_tpu.ops import optimizers

    # Separate masters: two trainers in one membership group would form
    # a world and broadcast state between themselves.
    with start_master(
        training_shards={"f": (0, 100)}, with_membership=True
    ) as m1, start_master(
        training_shards={"f": (0, 100)}, with_membership=True
    ) as m2:
        kw = dict(seed=7)
        base, _ = _make_trainer(m1, "127.0.0.1", 0, **kw)
        z1, _ = _make_trainer(m2, "127.0.0.2", 1, zero1=True, **kw)
        try:
            for step in range(4):
                x, y = _batch(16, seed=step)
                _, _, loss_b = base.train_minibatch(x, y)
                _, _, loss_z = z1.train_minibatch(x, y)
                assert float(loss_b) == float(loss_z), step
        finally:
            base.close()
            z1.close()

    # Layout + elastic re-mesh on a model whose dims divide the mesh
    # (the 4-wide linear model above has nothing to shard).
    from elasticdl_tpu.models.transformer import transformer_lm as tlm

    cfg = tlm.LMConfig(
        vocab=64, d_model=32, n_heads=4, n_layers=1, max_len=16,
        activation_dtype="float32",
    )
    with start_master(
        training_shards={"f": (0, 100)}, with_membership=True
    ) as m:
        mc = MasterClient(m["addr"], worker_id=0, worker_host="127.0.0.1")
        t = AllReduceTrainer(
            tlm.custom_model(cfg), tlm.loss, tlm.optimizer(), mc,
            zero1=True, seed=3,
        )
        try:
            tokens = (np.arange(16 * 17).reshape(16, 17) * 5) % cfg.vocab
            f, l = tokens[:, :-1], tokens[:, 1:]
            losses = [float(t.train_minibatch(f, l)[2]) for _ in range(4)]
            # Adam mu/nu (and every dim-0-divisible leaf) holds 1/n per
            # device.
            n_dev = t._mesh.shape["data"]
            sharded_leaves = 0
            for leaf in jax.tree_util.tree_leaves(t._opt_state):
                if leaf.ndim >= 1 and leaf.shape[0] % n_dev == 0:
                    shard = leaf.addressable_shards[0].data
                    assert shard.shape[0] == leaf.shape[0] // n_dev
                    sharded_leaves += 1
            assert sharded_leaves > 0
            # Elastic re-mesh: host snapshot gathers the sharded state,
            # re-placement re-shards it; training continues downhill.
            t.init_world_if_needed(force=True)
            for _ in range(3):
                losses.append(float(t.train_minibatch(f, l)[2]))
            assert losses[-1] < losses[0], losses
        finally:
            t.close()


def test_zero1_multihost_layout_matches_replicated():
    """The multi-host ZeRO-1 layout — a {data: n_proc, zero: local} mesh
    with the batch sharded over both axes and optimizer state sharded
    over "zero" only — must train numerically equivalently to the
    replicated baseline, keep every opt leaf fully addressable (the
    regroup snapshot's requirement), and actually shard over the zero
    axis. Emulated in one process by forcing the two-axis mesh the
    trainer builds when jax.process_count() > 1.

    "Numerically equivalently", not bit-identically: XLA lowers the
    same jitted step differently for the {data: 8} and
    {data: 2, zero: 4} meshes, and cross-device reduction ORDER is part
    of that lowering — on this image's CPU backend the losses drift at
    ~1e-7 relative by step 4 (pre-existing tier-1 failure, triaged in
    PR 5). A tight relative tolerance still catches every real layout
    bug (wrong shard math shows up at 1e-2, not 1e-7)."""
    import jax

    from elasticdl_tpu.models.transformer import transformer_lm as tlm
    from elasticdl_tpu.parallel.mesh import ZERO_AXIS, WorldTopology

    cfg = tlm.LMConfig(
        vocab=64, d_model=32, n_heads=4, n_layers=1, max_len=16,
        activation_dtype="float32",
    )
    tokens = (np.arange(16 * 17).reshape(16, 17) * 5) % cfg.vocab
    f, l = tokens[:, :-1], tokens[:, 1:]

    def run(zero1, force_two_axis):
        with start_master(
            training_shards={"f": (0, 100)}, with_membership=True
        ) as m:
            mc = MasterClient(
                m["addr"], worker_id=0, worker_host="127.0.0.1"
            )
            t = AllReduceTrainer(
                tlm.custom_model(cfg), tlm.loss, tlm.optimizer(), mc,
                zero1=zero1, seed=3,
            )
            if force_two_axis:
                # Stand in for a 2-process world of 4 local devices:
                # world resolution then factors pure DP into the
                # {data: 2, zero: 4} mesh exactly as a real multi-host
                # ZeRO-1 worker would build it.
                t._topo_override = WorldTopology(
                    n_devices=8, local_devices=4, n_processes=2
                )
            try:
                losses = [
                    float(t.train_minibatch(f, l)[2]) for _ in range(4)
                ]
                opt_state = t._opt_state
                snapshot = t._state_provider()  # must not be None/raise
                assert snapshot is not None
                return losses, opt_state, t._mesh
            finally:
                t.close()
                mc.close()

    base_losses, _, _ = run(zero1=False, force_two_axis=False)
    z_losses, opt_state, mesh = run(zero1=True, force_two_axis=True)
    np.testing.assert_allclose(base_losses, z_losses, rtol=1e-5)
    assert mesh.shape == {"data": 2, "zero": 4}
    sharded = 0
    for leaf in jax.tree_util.tree_leaves(opt_state):
        if getattr(leaf, "ndim", 0) >= 1 and leaf.shape[0] % 4 == 0:
            assert leaf.is_fully_addressable
            shard = leaf.addressable_shards[0].data
            # Sharded over zero (4) only — NOT over data * zero (8).
            assert shard.shape[0] == leaf.shape[0] // 4
            sharded += 1
    assert sharded > 0


def test_multihost_eval_host_copy_cached_per_version(monkeypatch):
    """Multi-host eval pulls ONE host copy per (world, version), not one
    per minibatch: an eval task's many minibatches would otherwise each
    re-download the whole model (~0.9 GB for the flagship). Train steps
    and checkpoint restores must invalidate the cache."""
    import jax

    with start_master(
        training_shards={"f": (0, 100)}, with_membership=True
    ) as m:
        t, mc = _make_trainer(m, "127.0.0.1", 0)
        try:
            x, y = _batch(8, 0)
            assert t.train_minibatch(x, y)[0]

            real_device_get = jax.device_get
            calls = {"n": 0}

            def counting_device_get(tree):
                calls["n"] += 1
                return real_device_get(tree)

            # Force the multi-host eval branch; the trainer's own mesh /
            # training path is already built, so only evaluate_minibatch
            # sees the patched world size.
            monkeypatch.setattr(jax, "process_count", lambda: 2)
            monkeypatch.setattr(jax, "device_get", counting_device_get)
            out1 = t.evaluate_minibatch(x)
            assert calls["n"] == 1
            for _ in range(3):
                t.evaluate_minibatch(x)
            assert calls["n"] == 1  # cached: no further transfers
            # A train step bumps the version -> one fresh transfer.
            monkeypatch.setattr(jax, "process_count", lambda: 1)
            t.train_minibatch(x, y)
            monkeypatch.setattr(jax, "process_count", lambda: 2)
            t.evaluate_minibatch(x)
            t.evaluate_minibatch(x)
            assert calls["n"] == 2
            # Checkpoint restore invalidates even at an equal version.
            exported = {
                "variables": real_device_get(t._variables),
                "opt_state": real_device_get(t._opt_state),
                "rng": np.asarray(t._rng),
                "version": t._version,
            }
            t.restore_variables(exported)
            t.evaluate_minibatch(x)
            assert calls["n"] == 3
            # Output sanity: eval still returns the model's outputs.
            assert np.asarray(out1).shape[0] == 8
        finally:
            t.close()
            mc.close()


@pytest.mark.parametrize("world_size, waits", [(1, 0), (2, 1)])
def test_a_sync_step_waits_for_the_device_only_with_a_peer(
    monkeypatch, world_size, waits
):
    """The wait after a sync step's dispatch is there to catch a lost
    collective inside the retry block; a world of one has none to lose,
    and keeps its device fed."""
    import jax

    with start_master(
        training_shards={"f": (0, 100)}, with_membership=True
    ) as m:
        t, mc = _make_trainer(m, "127.0.0.1", 0, steps_per_world_check=1)
        try:
            x, y = _batch(16, seed=0)
            t.train_minibatch(x, y)
            assert t.world_size == 1
            t._world_size = world_size
            blocked = []
            real = jax.block_until_ready
            monkeypatch.setattr(
                jax, "block_until_ready",
                lambda tree: blocked.append(1) or real(tree))
            t.train_minibatch(x, y)
            assert len(blocked) == waits
        finally:
            t.close()
            mc.close()


def _fake_mesh(platform, **axes):
    """What `step_plan.dp_overlap_for` looks at of a mesh, for devices
    this sandbox does not have."""
    import types

    n = int(np.prod(list(axes.values())))
    device = types.SimpleNamespace(platform=platform)
    return types.SimpleNamespace(
        shape=dict(axes),
        devices=np.array([device] * n, dtype=object).reshape(
            tuple(axes.values())
        ),
    )


@pytest.mark.parametrize(
    "platform, axes, zero1, overlapped",
    [
        ("tpu", {"data": 4}, False, True),
        ("tpu", {"data": 2, "zero": 2}, False, True),
        ("tpu", {"data": 1}, False, False),
        ("cpu", {"data": 8}, False, False),
        ("tpu", {"data": 2, "model": 2}, False, False),
        ("tpu", {"data": 2, "stage": 2}, False, False),
        ("tpu", {"data": 2, "model": 1}, False, True),
        ("tpu", {"data": 4}, True, False),
    ],
    ids=[
        "tpu_dp4", "tpu_data_x_zero", "one_tpu", "cpu_dp8", "tpu_dp_x_tp",
        "tpu_dp_x_stage", "tpu_model_axis_of_1", "tpu_zero1",
    ],
)
def test_dp_overlap_is_decided_by_the_mesh(platform, axes, zero1,
                                           overlapped):
    """The overlapped gradient all-reduce is taken exactly for pure data
    parallelism over more than one TPU; no knob enters."""
    from elasticdl_tpu.parallel import step_plan

    mesh = _fake_mesh(platform, **axes)
    assert step_plan.dp_overlap_for(mesh, zero1) is overlapped


def test_step_plan_imports_nothing_from_the_worker():
    """`parallel/` lies below `worker/`: the step's builder is told what
    it needs of the trainer (`StepModel`) and imports none of it."""
    import ast

    from elasticdl_tpu.parallel import step_plan

    with open(step_plan.__file__) as f:
        tree = ast.parse(f.read())
    imported = set()
    for node in ast.walk(tree):
        if isinstance(node, ast.Import):
            imported.update(a.name for a in node.names)
        elif isinstance(node, ast.ImportFrom):
            imported.update(f"{node.module}.{a.name}" for a in node.names)
    assert any(n.startswith("elasticdl_tpu.parallel") for n in imported)
    assert not [n for n in imported if n.startswith("elasticdl_tpu.worker")]


@pytest.mark.parametrize(
    "overlap, kw, axes",
    [
        (False, {}, {"data": 8}),
        (True, {}, {"data": 8}),
        (False, {"zero1": True}, {"data": 8}),
        (False, {"quantized_grads": True}, {"data": 8}),
        (False, {"model_parallel_size": 2,
                 "param_specs_fn": test_module.param_specs},
         {"data": 4, "model": 2}),
        (False, {"model_parallel_size": 2, "quantized_grads": True,
                 "param_specs_fn": test_module.param_specs},
         {"data": 4, "model": 2}),
    ],
    ids=["as_decided_here", "as_on_tpus", "zero1", "quantized_grads",
         "dp_x_tp", "dp_x_tp_quantized"],
)
def test_live_build_and_planner_hand_the_jit_the_same_arguments(
    monkeypatch, tmp_path, overlap, kw, axes
):
    """`_sharded_step_for` and `plan_step_for_spec` both call
    `step_plan.build_step`, one with the live world and one with a
    spec's: for the live spec the speculator's executable is lowered
    from the jit arguments a local compile gets (shardings, donation,
    compiler options), and the step's compile event says which form it
    took. A CPU mesh of several devices takes no TPU option (its compiler
    would refuse one) and trains as before."""
    from elasticdl_tpu.observability import events as obs_events
    from elasticdl_tpu.observability import profiling
    from elasticdl_tpu.parallel import step_plan

    seen = []
    real = profiling.tracked_jit

    def recording(fn, **kwargs):
        seen.append(dict(kwargs))
        # A CPU compiler refuses the TPU compiler's options.
        kwargs.pop("compiler_options", None)
        return real(fn, **kwargs)

    monkeypatch.setattr(profiling, "tracked_jit", recording)
    if overlap:
        monkeypatch.setattr(
            step_plan, "dp_overlap_for", lambda mesh, zero1: True
        )
    log = obs_events.EventLog(
        str(tmp_path / "events.jsonl"), job="t", role="test"
    )
    prev = obs_events.get_event_log()
    obs_events.set_event_log(log)
    try:
        with start_master(
            training_shards={"f": (0, 100)}, with_membership=True
        ) as m:
            t, mc = _make_trainer(m, "127.0.0.1", 0, seed=3, **kw)
            try:
                losses = []
                for step in range(3):
                    x, y = _batch(16, seed=step)
                    _, _, loss = t.train_minibatch(x, y)
                    losses.append(float(loss))
                assert losses[-1] < losses[0]
                assert dict(t._mesh.shape) == axes
                (live,) = [k for k in seen if k["name"] == "allreduce_step"]
                plan = t.plan_step_for_spec(t._world_spec, 16)
                assert plan is not None
                planned = [
                    k for k in seen if k["name"] == "allreduce_step"
                ][-1]
            finally:
                t.close()
                mc.close()
    finally:
        obs_events.set_event_log(prev)
        log.close()
    assert planned is not live
    assert planned == live
    # A world of eight devices: the update is not held apart (that is
    # for one device, where nothing else stands before it).
    assert live["event_fields"] == {
        "dp_overlap": overlap, "update_apart": False,
    }
    if overlap:
        assert (
            live["compiler_options"] == step_plan.DP_OVERLAP_COMPILER_OPTIONS
        )
    else:
        assert "compiler_options" not in live
    step_events = [
        e
        for e in obs_events.read_events(str(tmp_path / "events.jsonl"))
        if e["kind"] in ("compile", "compile_cache_hit")
        and e["fn"] == "allreduce_step"
    ]
    assert step_events
    assert all(e["dp_overlap"] is overlap for e in step_events)
    assert all(e["update_apart"] is False for e in step_events)
