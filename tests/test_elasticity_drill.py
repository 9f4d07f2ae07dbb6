"""The signature elasticity drill: a REAL `edl train` job loses a worker to
SIGKILL mid-epoch and must detect, recover its tasks, relaunch, rejoin, and
complete with an intact model (reference behavior:
k8s_instance_manager.py:391-404 relaunch + task recovery, proven here for
workers the way worker_ps_interaction_test.py:363-416 proved it for the
PS). Also exercises the multi-host jax.distributed path with two real OS
processes."""

import json
import os
import subprocess
import sys
import textwrap

import numpy as np
import pytest

import test_module
from test_utils import CPU_COLLECTIVE_TIMEOUT_FLAG

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path.insert(0, os.path.join(REPO, "tools"))

from elastic_drill import run_drill  # noqa: E402


@pytest.mark.parametrize(
    "strategy,num_ps",
    [
        # PS strategy: the reference's signature drill shape.
        ("ParameterServerStrategy", 1),
        # Elastic AllReduce: membership epoch drops the dead worker, the
        # replacement rejoins the comm group (new epoch + rank-0 state
        # pull) — the reference's headline elastic-allreduce behavior
        # (allreduce/report.md) proven at process level.
        ("AllreduceStrategy", 0),
    ],
)
def test_kill_worker_mid_job_drill(tmp_path, strategy, num_ps):
    from elasticdl_tpu.data.recordfile import RecordFileWriter

    data = str(tmp_path / "linear.edlr")
    with RecordFileWriter(data) as w:
        for r in test_module.make_linear_records(256):
            w.write(r)
    output = str(tmp_path / "model.npz")
    obs_dir = str(tmp_path / "obs")
    result = run_drill(
        data,
        model_zoo=os.path.join(REPO, "tests"),
        model_def="test_module",
        num_workers=2,
        num_ps=num_ps,
        strategy=strategy,
        # Enough work that the job outlives the replacement worker's
        # startup, so the rejoin is observable.
        num_epochs=400,
        extra_args=("--output", output),
        env_overrides={
            "JAX_PLATFORMS": "cpu",
            "ELASTICDL_OBS_DIR": obs_dir,
        },
        timeout=300,
    )
    assert result["completed"], result.get("log_tail", "")[-1500:]
    assert result["relaunched"], "worker was never relaunched"
    # run_drill SIGSTOPped the victim and verified it owned an in-flight
    # task before the SIGKILL, so recovery must log; on failure, show the
    # master's queue state at kill time so a real regression is
    # distinguishable from drill slowness.
    assert result["recovered_tasks"], (
        "dead worker's tasks not recovered; "
        f"status_at_kill={result.get('status_at_kill')} "
        f"victim_task_observed={result.get('victim_task_observed')}\n"
        f"{result.get('log_tail', '')[-1500:]}"
    )
    assert result["rejoin_s"] is not None, result
    # Elastic rejoin: detection + relaunch + re-init + first RPC. Bound it
    # loosely (CI boxes vary) — the metric's existence and sanity is the
    # assertion; no seconds are claimed from a CPU. The lower bound
    # guards against mis-attributed survivor progress faking a rejoin.
    assert 0.5 < result["rejoin_s"] < 120
    # Loss continuity: the kill must not corrupt the model — the exported
    # weights still solve the linear problem (for AllReduce this proves
    # the replacement's rank-0 state pull delivered usable state).
    with np.load(output) as d:
        kernel = d["params/Dense_0/kernel"].reshape(-1)
    np.testing.assert_allclose(kernel, test_module.TRUE_W, atol=0.1)
    # The observability event log reconstructs the drill's elasticity
    # timeline: the victim's launch precedes its kill-exit, which precedes
    # its relaunch — and a replacement launch follows.
    from elasticdl_tpu.observability.events import read_events

    records = read_events(os.path.join(obs_dir, "events.jsonl"))
    victims = [
        r
        for r in records
        if r.get("instance", "").startswith("worker-")
        and r["kind"].startswith("pod_")
    ]
    by_instance = {}
    for r in victims:
        by_instance.setdefault(r["instance"], []).append(r["kind"])
    relaunched_instance = next(
        (k for k, kinds in by_instance.items() if "pod_relaunch" in kinds),
        None,
    )
    assert relaunched_instance, by_instance
    kinds = by_instance[relaunched_instance]
    assert kinds.index("pod_launch") < kinds.index("pod_exit"), kinds
    assert kinds.index("pod_exit") < kinds.index("pod_relaunch"), kinds
    assert "pod_launch" in kinds[kinds.index("pod_relaunch"):], kinds
    assert any(r["kind"] == "task_create" for r in records)


_MH_CHILD = textwrap.dedent(
    """
    import sys, os
    os.environ["JAX_PLATFORMS"] = "cpu"
    sys.path.insert(0, %(repo)r)
    rank, world, coord = int(sys.argv[1]), int(sys.argv[2]), sys.argv[3]
    import jax
    import jax.numpy as jnp
    import numpy as np
    from jax.sharding import Mesh, NamedSharding, PartitionSpec as P
    from elasticdl_tpu.parallel import distributed

    # Touch the backend BEFORE joining, like a trainer that built params
    # before discovering its world: ensure_world must clear the cached
    # single-process backend or jax.distributed.initialize refuses.
    _ = float(jnp.ones(3).sum())

    # Membership epoch 1: join the 2-process world.
    distributed.ensure_world(coord, world, rank, epoch=1)
    assert jax.device_count() == world, jax.devices()

    # A DP gradient step over the global mesh, GSPMD-style (jit with
    # shardings — the same formulation the AllReduce trainer compiles):
    # per-process batch shards, the compiler-inserted cross-process
    # collective must yield the full-batch gradient on every rank.
    mesh = Mesh(np.array(jax.devices()), ("data",))
    batch_sh = NamedSharding(mesh, P("data", None))
    repl = NamedSharding(mesh, P())
    full = np.arange(8, dtype=np.float32).reshape(8, 1)
    local = full[rank * 4 : rank * 4 + 4]
    w = jax.device_put(jnp.ones((1, 1)), repl)

    def loss(w, x):
        return jnp.mean((x @ w) ** 2)

    dp_grad = jax.jit(
        jax.grad(loss), in_shardings=(repl, batch_sh), out_shardings=repl
    )
    from jax.experimental import multihost_utils

    x_global = multihost_utils.host_local_array_to_global_array(
        local, mesh, batch_sh.spec
    )
    g = dp_grad(w, x_global)
    expected = jax.grad(loss)(jnp.ones((1, 1)), jnp.asarray(full))
    np.testing.assert_allclose(
        np.asarray(jax.device_get(g)), np.asarray(expected), rtol=1e-6
    )

    # Membership epoch 2 (elastic regroup): re-init must work and the
    # world must function again.
    distributed.ensure_world(coord2, world, rank, epoch=2)
    assert jax.device_count() == world
    distributed.leave_world()
    print("MH_OK", rank)
    """
)


def test_multi_host_two_process_world(tmp_path):
    """Two real OS processes join a jax.distributed world via
    ensure_world, run a cross-process DP psum step, then survive a
    membership-epoch re-init (the elastic AllReduce regroup path)."""
    import socket

    def free_port():
        s = socket.socket()
        s.bind(("127.0.0.1", 0))
        port = s.getsockname()[1]
        s.close()
        return port

    coord = f"127.0.0.1:{free_port()}"
    coord2 = f"127.0.0.1:{free_port()}"
    child = _MH_CHILD % {"repo": REPO}
    child = child.replace("coord2", repr(coord2))
    script = tmp_path / "mh_child.py"
    script.write_text(child)
    env = dict(os.environ)
    env["PYTHONPATH"] = REPO
    # conftest's 8-virtual-device XLA flag must not leak into the
    # children: each process is ONE host with one local device here.
    env["XLA_FLAGS"] = CPU_COLLECTIVE_TIMEOUT_FLAG
    procs = [
        subprocess.Popen(
            [sys.executable, str(script), str(rank), "2", coord],
            stdout=subprocess.PIPE,
            stderr=subprocess.STDOUT,
            text=True,
            env=env,
        )
        for rank in range(2)
    ]
    outs = []
    try:
        for p in procs:
            out, _ = p.communicate(timeout=180)
            outs.append(out)
    finally:
        # Pass or fail, neither rank outlives the test.
        for p in procs:
            if p.poll() is None:
                p.kill()
                p.communicate()
    for rank, (p, out) in enumerate(zip(procs, outs)):
        assert p.returncode == 0, f"rank {rank}:\n{out[-3000:]}"
        assert f"MH_OK {rank}" in out


def test_worker_kill_warm_cache_is_recompile_free(tmp_path):
    """The recompile-free elasticity acceptance drill (ISSUE 15): a
    worker-kill with the persistent compile cache armed must show

    - `edl_compile_total{cause="mesh_change"}` FLAT — no elastic epoch
      (the kill, the rejoin) re-lowers any survivor's step, because the
      world resolves to the same WorldSpec and the fast regroup path
      keeps the compiled steps;
    - the survivor absorbing membership through `elastic_regroup`
      events with mode="fast";
    - the RELAUNCHED worker rehydrating its step from the disk cache
      its first incarnation populated (`compile_cache_hit` events)
      instead of paying a cold XLA compile — compile is no longer the
      rejoin."""
    from elasticdl_tpu.data.recordfile import RecordFileWriter
    from elasticdl_tpu.observability.events import read_events

    data = str(tmp_path / "linear.edlr")
    with RecordFileWriter(data) as w:
        for r in test_module.make_linear_records(256):
            w.write(r)
    obs_dir = str(tmp_path / "obs")
    cache_dir = str(tmp_path / "compile_cache")
    result = run_drill(
        data,
        model_zoo=os.path.join(REPO, "tests"),
        model_def="test_module",
        num_workers=2,
        num_ps=0,
        strategy="AllreduceStrategy",
        num_epochs=300,
        env_overrides={
            "JAX_PLATFORMS": "cpu",
            "ELASTICDL_OBS_DIR": obs_dir,
            "JAX_COMPILATION_CACHE_DIR": cache_dir,
        },
        timeout=300,
    )
    assert result["completed"], result.get("log_tail", "")[-1500:]
    assert result["relaunched"], "worker was never relaunched"
    records = read_events(os.path.join(obs_dir, "events.jsonl"))

    # 1) mesh_change flat: NO lowering in the whole drill was caused by
    # a world change — membership epochs no longer reshape the mesh.
    mesh_changes = [
        r for r in records
        if r["kind"] == "compile" and r.get("cause") == "mesh_change"
    ]
    assert mesh_changes == [], mesh_changes

    # 2) the survivors absorbed the kill/rejoin epochs on the fast path.
    fast = [
        r for r in records
        if r["kind"] == "elastic_regroup" and r.get("mode") == "fast"
    ]
    assert fast, [r for r in records if r["kind"] == "elastic_regroup"]

    # 3) the relaunched worker rehydrated from the warm cache: its
    # re-lowerings landed as compile_cache_hit, and its training step
    # specifically never cold-compiled a second time. (Worker roles
    # each appear once per incarnation; the cache was populated by the
    # first incarnations before the SIGKILL.)
    hits = [r for r in records if r["kind"] == "compile_cache_hit"]
    assert any(r.get("fn") == "allreduce_step" for r in hits), (
        [r for r in records if r["kind"].startswith("compile")][-20:]
    )
