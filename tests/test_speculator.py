"""Recompile-free elasticity: the regroup fast path, the speculative
AOT world compiler, and the persistent compilation cache.

The contract under test (ISSUE 15 / docs/ELASTICITY.md): a membership
epoch that does not reshape the mesh re-lowers NOTHING; a reshaping
regroup consumes a speculatively prebuilt executable when the guess
landed (with donation preserved), abandons it cleanly when it did not,
and never blocks the step loop on a background compile; a relaunched
process with a warm cache dir rehydrates its step from disk instead of
cold-compiling."""

import json
import os
import subprocess
import sys
import threading
import time

import numpy as np

import tests.test_module as test_module
from elasticdl_tpu.observability import profiling
from elasticdl_tpu.parallel.mesh import WorldTopology
from elasticdl_tpu.worker.allreduce_trainer import AllReduceTrainer
from elasticdl_tpu.worker.master_client import MasterClient
from elasticdl_tpu.worker.world_speculator import SpeculativeWorldCompiler
from tests.test_utils import start_master

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


def _batch(n, seed=0):
    rng = np.random.default_rng(seed)
    x = rng.normal(size=(n, test_module.FEATURE_DIM)).astype(np.float32)
    y = (x @ test_module.TRUE_W + test_module.TRUE_B).astype(np.float32)
    return x, y


def _trainer(master, **kw):
    mc = MasterClient(
        master["addr"], worker_id=0, worker_host="127.0.0.1"
    )
    t = AllReduceTrainer(
        test_module.custom_model(),
        test_module.loss,
        test_module.optimizer(),
        mc,
        steps_per_world_check=1,
        **kw,
    )
    return t, mc


def test_fast_regroup_keeps_compiled_steps():
    """Epoch bump, same spec: the steps dict is untouched (same jitted
    objects), the compile tracker records nothing, and training carries
    state straight through."""
    with start_master(
        training_shards={"f": (0, 100)}, with_membership=True
    ) as m:
        t, mc = _trainer(m)
        try:
            x, y = _batch(16)
            t.train_minibatch(x, y)
            version = t.get_model_version()
            steps_before = dict(t._sharded_steps)
            compiles_before = profiling.tracker().snapshot()[0]
            m["membership"].add_worker_host("10.0.0.2:9999")
            t.train_minibatch(x, y)
            t.train_minibatch(x, y)
            assert t.world_size == 2
            for key, step in steps_before.items():
                assert t._sharded_steps[key] is step
            assert profiling.tracker().snapshot()[0] == compiles_before
            assert t.get_model_version() == version + 2
        finally:
            t.close()
            mc.close()


def test_speculative_compile_consumed_on_regroup():
    """The trainer guesses the 8-device world while training in a
    7-device one; the regroup back to 8 consumes the prebuilt
    executable — no synchronous compile, donation intact."""
    with start_master(
        training_shards={"f": (0, 100)}, with_membership=True
    ) as m:
        t, mc = _trainer(m)
        try:
            x, y = _batch(16)
            t._topo_override = WorldTopology(7, 7, 1)
            t._topo_candidates = [WorldTopology(8, 8, 1)]
            t.train_minibatch(x, y)
            assert t._speculator.drain(90), "speculator never idled"
            assert ("data=8", (16, 16)) in t._speculator.prebuilt_keys()
            # Timing baseline: warm steps in the current world.
            for _ in range(2):
                t.train_minibatch(x, y)
            t0 = time.perf_counter()
            for _ in range(3):
                import jax

                jax.block_until_ready(t.train_minibatch(x, y)[2])
            warm_step = (time.perf_counter() - t0) / 3
            # Regroup to the guessed world.
            t._topo_override = WorldTopology(8, 8, 1)
            m["membership"].add_worker_host("10.0.0.2:9999")
            compiles_before = profiling.tracker().snapshot()[0]
            t.train_minibatch(x, y)
            assert dict(t._mesh.shape) == {"data": 8}
            assert profiling.tracker().snapshot()[0] == compiles_before, (
                "regroup into the speculated world still compiled"
            )
            assert t._speculator.stats["consumed"] == 1
            # Donation preserved through the AOT path: the consumed
            # executable aliases (variables, opt_state) in place.
            v_before = t._variables
            import jax

            jax.block_until_ready(t.train_minibatch(x, y)[2])
            assert all(
                a.is_deleted()
                for a in jax.tree_util.tree_leaves(v_before)
            ), "consumed step did not donate its state inputs"
            # ms/step sanity: the consumed executable performs like a
            # locally compiled one (a per-call retrace pathology would
            # be orders of magnitude off; the bound is deliberately
            # loose for loaded CI boxes).
            t0 = time.perf_counter()
            for _ in range(3):
                jax.block_until_ready(t.train_minibatch(x, y)[2])
            consumed_step = (time.perf_counter() - t0) / 3
            assert consumed_step < max(25 * warm_step, 0.5), (
                consumed_step, warm_step,
            )
        finally:
            t.close()
            mc.close()


def test_wrong_world_guess_abandoned_cleanly():
    """A prebuilt executable for a world that never forms is dropped on
    the next regroup and can never be consumed."""
    with start_master(
        training_shards={"f": (0, 100)}, with_membership=True
    ) as m:
        t, mc = _trainer(m)
        try:
            x, y = _batch(16)
            t._topo_override = WorldTopology(8, 8, 1)
            t._topo_candidates = [WorldTopology(6, 6, 1)]  # wrong guess
            t.train_minibatch(x, y)
            assert t._speculator.drain(90)
            assert t._speculator.stats["built"] == 1
            # The world that actually forms is 7 devices, not 6.
            t._topo_override = WorldTopology(7, 7, 1)
            t._topo_candidates = []
            m["membership"].add_worker_host("10.0.0.2:9999")
            t.train_minibatch(x, y)
            assert dict(t._mesh.shape) == {"data": 7}
            assert t._speculator.prebuilt_keys() == []
            assert t._speculator.stats["abandoned"] >= 1
            assert t._speculator.stats["consumed"] == 0
            # Training is undisturbed.
            ok, _, loss = t.train_minibatch(x, y)
            assert ok and np.isfinite(float(loss))
        finally:
            t.close()
            mc.close()


def test_world_change_mid_compile_cancels_without_blocking():
    """cancel() during an in-flight speculative compile returns
    immediately; the compile's result is discarded when it finishes
    (XLA compiles cannot be interrupted), never installed."""
    started = threading.Event()
    release = threading.Event()

    class FakeSpec:
        def fingerprint(self):
            return "w1"

    class Step:
        def lower(self, *a):
            return self

        def compile(self):
            started.set()
            release.wait(10)
            return object()

    s = SpeculativeWorldCompiler(lambda spec, n: ((n, n), Step(), ()))
    try:
        s.submit([FakeSpec()], 16)
        assert started.wait(5), "speculative compile never started"
        t0 = time.perf_counter()
        s.cancel(keep_fingerprint="w2")  # the world moved mid-compile
        assert time.perf_counter() - t0 < 0.5, (
            "cancel blocked on the in-flight compile"
        )
        release.set()
        assert s.drain(10)
        assert s.take("w1", (16, 16)) is None
        assert s.stats["abandoned"] == 1
        assert s.prebuilt_keys() == []
    finally:
        release.set()
        s.stop()


def test_in_flight_guess_for_the_kept_world_survives_cancel():
    """A regroup lands on the world whose compile is still in flight:
    cancel(keep=that world) must NOT discard the finishing executable —
    it is exactly what the next step wants."""
    started = threading.Event()
    release = threading.Event()

    class FakeSpec:
        def fingerprint(self):
            return "w1"

    class Step:
        def lower(self, *a):
            return self

        def compile(self):
            started.set()
            release.wait(10)
            return object()

    s = SpeculativeWorldCompiler(lambda spec, n: ((n, n), Step(), ()))
    try:
        s.submit([FakeSpec()], 16)
        assert started.wait(5)
        s.cancel(keep_fingerprint="w1")  # the guess WAS right
        release.set()
        assert s.drain(10)
        assert s.take("w1", (16, 16)) is not None
        assert s.stats["built"] == 1
    finally:
        release.set()
        s.stop()


def test_world_hint_polled_and_front_loaded(monkeypatch):
    """The master announces the next world on the WorldHintBoard; the
    trainer's throttled get_world_hint poll picks it up over real gRPC
    and _candidate_topologies compiles the ANNOUNCED world first —
    before any N±delta guess, and never duplicated by them."""
    from elasticdl_tpu.master.policy import WorldHintBoard

    monkeypatch.setenv("ELASTICDL_POLICY_HINT_POLL_SECONDS", "0.01")
    with start_master(
        training_shards={"f": (0, 100)}, with_membership=True
    ) as m:
        board = WorldHintBoard()
        m["servicer"].bind_job_context(world_hints=board)
        t, mc = _trainer(m)
        try:
            t._poll_world_hint()  # nothing announced yet
            assert t._hinted_world == 0
            board.announce(5, "deadline overshoot")
            time.sleep(0.02)
            t._poll_world_hint()
            assert t._hint_seq_seen == 1
            assert t._hinted_world == 5
            # Candidate ordering: the hinted world leads, the guesses
            # skip it.
            t._multi_host = True
            t._world_size = 2
            candidates = t._candidate_topologies()
            assert candidates[0].n_processes == 5
            assert [c.n_processes for c in candidates].count(5) == 1
            # A re-announcement advances the hint; a stale one doesn't.
            board.announce(3, "scale back")
            time.sleep(0.02)
            t._poll_world_hint()
            assert t._hinted_world == 3
        finally:
            t._multi_host = False
            t.close()
            mc.close()


def test_world_hint_unimplemented_stops_polling():
    """Pre-policy master without the RPC: the first UNIMPLEMENTED
    permanently disables hint polling instead of retrying forever."""
    import grpc

    with start_master(
        training_shards={"f": (0, 100)}, with_membership=True
    ) as m:
        t, mc = _trainer(m)
        try:
            class _Unimplemented(grpc.RpcError):
                def code(self):
                    return grpc.StatusCode.UNIMPLEMENTED

            def boom():
                raise _Unimplemented()

            orig = mc.get_world_hint
            mc.get_world_hint = boom
            t._poll_world_hint()
            assert t._hint_poll_s == 0.0
            # Disabled: later polls never touch the RPC again.
            mc.get_world_hint = orig
            t._poll_world_hint()
            assert t._hint_seq_seen == 0
        finally:
            t.close()
            mc.close()


def test_hinted_world_compiled_and_consumed(tmp_path, monkeypatch):
    """The full world-hint contract: announce -> poll -> speculative AOT
    of the hinted world (with ZERO guessing budget, so only the hint
    explains the prebuild) -> the regroup into that world consumes the
    executable without a synchronous compile, and the event log carries
    the causal pair (world_hint, then aot_consumed on the hinted
    spec)."""
    import jax

    from elasticdl_tpu.master.policy import WorldHintBoard
    from elasticdl_tpu.observability.events import (
        EventLog,
        read_events,
        set_event_log,
    )

    monkeypatch.setenv("ELASTICDL_POLICY_HINT_POLL_SECONDS", "0.01")
    monkeypatch.setenv("ELASTICDL_AOT_WORLDS", "0")
    events_path = str(tmp_path / "events.jsonl")
    log = EventLog(events_path, job="hint-test", role="worker-0")
    set_event_log(log)
    with start_master(
        training_shards={"f": (0, 100)}, with_membership=True
    ) as m:
        board = WorldHintBoard()
        m["servicer"].bind_job_context(world_hints=board)
        t, mc = _trainer(m)
        try:
            x, y = _batch(16)
            t._topo_override = WorldTopology(7, 7, 1)
            t.train_minibatch(x, y)
            # The master decides to scale: 8 single-device processes.
            board.announce(8, "eta overshoots deadline")
            time.sleep(0.02)
            # Pose as a rank of a 7-process multi-host world so the
            # candidate path (hint included) is live; the hinted world
            # is 8 x 1-device processes, so local_device_count must
            # read 1 while the candidate resolves.
            t._multi_host = True
            t._world_size = 7
            orig_local = jax.local_device_count
            jax.local_device_count = lambda: 1
            try:
                t._maybe_speculate()
            finally:
                jax.local_device_count = orig_local
                t._multi_host = False
            assert t._hinted_world == 8
            assert t._speculator.drain(90), "speculator never idled"
            # The hinted world is 8 devices across 8 processes, so its
            # fingerprint carries the process suffix ("data=8|p8").
            assert any(
                fp.startswith("data=8") and shape == (16, 16)
                for fp, shape in t._speculator.prebuilt_keys()
            ), t._speculator.prebuilt_keys()
            # Regroup into the ANNOUNCED world: consumed, not compiled.
            t._topo_override = WorldTopology(8, 1, 8)
            m["membership"].add_worker_host("10.0.0.2:9999")
            compiles_before = profiling.tracker().snapshot()[0]
            t.train_minibatch(x, y)
            assert dict(t._mesh.shape) == {"data": 8}
            assert profiling.tracker().snapshot()[0] == compiles_before, (
                "regroup into the hinted world still compiled"
            )
            assert t._speculator.stats["consumed"] == 1
            # The event log proves causality: the hint precedes the
            # consumption, and the consumed spec is the live world's.
            records = read_events(events_path)
            hint_ev = next(
                r for r in records if r["kind"] == "world_hint"
            )
            consumed_ev = next(
                r for r in records if r["kind"] == "aot_consumed"
            )
            assert hint_ev["target_world_size"] == 8
            assert hint_ev["seq"] < consumed_ev["seq"]
            assert consumed_ev["spec"] == t._world_spec.fingerprint()
        finally:
            set_event_log(None)
            log.close()
            t.close()
            mc.close()


def test_relaunch_with_warm_cache_skips_cold_compile(tmp_path):
    """Two incarnations of the same training process share one cache
    dir: the first cold-compiles (a `compile` event), the second
    rehydrates from disk (`compile_cache_hit`, no compile event for the
    step) — the relaunched-worker rejoin path."""
    cache = str(tmp_path / "cache")
    code = """
import json, os, sys
sys.path.insert(0, {repo!r})
sys.path.insert(0, os.path.join({repo!r}, "tests"))
os.environ["JAX_PLATFORMS"] = "cpu"
import numpy as np
import test_module
from elasticdl_tpu.observability import profiling
from elasticdl_tpu.worker.trainer import LocalTrainer

t = LocalTrainer(
    test_module.custom_model(), test_module.loss, test_module.optimizer()
)
rng = np.random.default_rng(0)
x = rng.normal(size=(16, test_module.FEATURE_DIM)).astype(np.float32)
y = (x @ test_module.TRUE_W + test_module.TRUE_B).astype(np.float32)
t.train_minibatch(x, y)
recent = [
    e for e in profiling.tracker().recent() if e["fn"] == "train_step"
]
print("RESULT:" + json.dumps(recent))
""".format(repo=REPO)
    env = dict(os.environ)
    env["JAX_COMPILATION_CACHE_DIR"] = cache

    def run():
        out = subprocess.run(
            [sys.executable, "-c", code],
            capture_output=True,
            text=True,
            env=env,
            timeout=180,
        )
        assert out.returncode == 0, out.stderr[-2000:]
        line = [
            ln for ln in out.stdout.splitlines()
            if ln.startswith("RESULT:")
        ][0]
        return json.loads(line[len("RESULT:"):])

    first = run()
    assert first and not any(e.get("cache_hit") for e in first), first
    second = run()
    assert second, "second incarnation recorded no lowering at all"
    assert all(e.get("cache_hit") for e in second), (
        "relaunch with a warm cache still cold-compiled", second,
    )
    assert os.path.isdir(cache) and os.listdir(cache)
