"""Deep profiling plane: compile tracker (cause attribution, metrics,
events, spans), memory accountant, on-demand device profiles
(/debug/profile + StartProfile fan-out), and the offline step report —
all jax-on-CPU, inside the tier-1 window."""

import json
import os
import threading
import time
import urllib.request
import uuid

import numpy as np

import jax
import jax.numpy as jnp

from elasticdl_tpu.observability import events as obs_events
from elasticdl_tpu.observability import memory as obs_memory
from elasticdl_tpu.observability import profiling, tracing
from elasticdl_tpu.observability.exporter import MetricsExporter
from elasticdl_tpu.observability.metrics import default_registry

from test_utils import start_master

import pytest

# These tests read the compile tracker's cold/rebuild/mesh_change causes:
# every lowering must really compile, whatever an earlier run left in the
# persistent cache.
pytestmark = pytest.mark.usefixtures("no_persistent_compile_cache")


def _fresh_name():
    return f"t_{uuid.uuid4().hex[:8]}"


def _compiles_for(fn_name):
    """{cause: count} of tracked compiles recorded for one fn name."""
    metric = default_registry().get("edl_compile_total")
    out = {}
    for (fn, cause), child in metric._children.items():
        if fn == fn_name and child.value:
            out[cause] = child.value
    return out


def _seconds_for(fn_name):
    metric = default_registry().get("edl_compile_seconds_total")
    return sum(
        child.value
        for (fn, _), child in metric._children.items()
        if fn == fn_name
    )


class _EventCapture:
    """Installs a real EventLog in tmp dir; yields parsed events."""

    def __init__(self, tmp_path):
        self.path = str(tmp_path / "events.jsonl")
        self.log = obs_events.EventLog(self.path, job="t", role="test")

    def __enter__(self):
        self._prev = obs_events.get_event_log()
        obs_events.set_event_log(self.log)
        return self

    def __exit__(self, *exc):
        obs_events.set_event_log(self._prev)
        self.log.close()
        return False

    def events(self, kind=None):
        out = obs_events.read_events(self.path)
        if kind:
            out = [e for e in out if e["kind"] == kind]
        return out


def test_tracked_jit_cause_attribution(tmp_path):
    name = _fresh_name()
    with _EventCapture(tmp_path) as cap:
        try:
            f = profiling.tracked_jit(lambda x: x * 3, name=name)
            f(jnp.ones(3))
            f(jnp.ones(3))  # warm: no new compile
            f(jnp.ones(5))  # shape change
            profiling.note_mesh("epochX:{'data': 2}", world_size=2)
            f(jnp.ones(7))  # mesh change
        finally:
            profiling.note_mesh("", world_size=0)
    causes = _compiles_for(name)
    assert causes == {"cold": 1, "shape_change": 1, "mesh_change": 1}
    assert _seconds_for(name) > 0
    compile_events = cap.events("compile")
    assert [e["cause"] for e in compile_events] == [
        "cold", "shape_change", "mesh_change",
    ]
    assert compile_events[-1]["world_size"] == 2
    assert all(e["fn"] == name for e in compile_events)


def test_tracked_jit_records_compile_span(tmp_path):
    name = _fresh_name()
    rec = tracing.SpanRecorder(
        str(tmp_path / "trace.jsonl"), process_name="test"
    )
    prev = tracing.get_recorder()
    tracing.set_recorder(rec)
    try:
        f = profiling.tracked_jit(lambda x: x + 1, name=name)
        f(jnp.ones(2))
    finally:
        tracing.set_recorder(prev)
        rec.close()
    spans = [
        json.loads(line)
        for line in open(tmp_path / "trace.jsonl")
        if line.strip()
    ]
    compile_spans = [
        s for s in spans if s.get("name") == f"compile:{name}"
    ]
    assert compile_spans, spans
    assert compile_spans[0]["cat"] == "compile"
    assert compile_spans[0]["args"]["cause"] == "cold"
    assert compile_spans[0]["dur"] > 0


def test_tracked_jit_forwards_aot_surface():
    f = profiling.tracked_jit(lambda x: x @ x.T, name=_fresh_name())
    spec = jax.ShapeDtypeStruct((4, 4), jnp.float32)
    analysis = f.lower(spec).compile().cost_analysis()
    if isinstance(analysis, (list, tuple)):
        analysis = analysis[0]
    assert analysis.get("flops", 0) > 0


def test_tracked_jit_rebuild_cause():
    """A rebuilt jit object re-lowering a signature this process already
    compiled is attributed `rebuild` (restore / forward rebuild), not a
    spurious shape change."""
    name = _fresh_name()
    body = lambda x: x * 2  # noqa: E731
    profiling.tracked_jit(body, name=name)(jnp.ones(3))
    profiling.tracked_jit(body, name=name)(jnp.ones(3))
    assert _compiles_for(name) == {"cold": 1, "rebuild": 1}


def test_tracker_disabled_returns_plain_jit(monkeypatch):
    monkeypatch.setenv("ELASTICDL_COMPILE_TRACKER", "0")
    f = profiling.tracked_jit(lambda x: x, name=_fresh_name())
    assert not isinstance(f, profiling.TrackedFunction)


# ---------------------------------------------------------------------------
# memory accountant
# ---------------------------------------------------------------------------


def test_memory_accountant_sample_and_watermark(tmp_path):
    acc = obs_memory.MemoryAccountant(watermark_ratio=1.05)
    keep = [jnp.ones((64,), jnp.float32)]
    with _EventCapture(tmp_path) as cap:
        first = acc.sample()
        assert first["device_live_bytes"] > 0
        assert first["host_rss_bytes"] > 0
        assert first["host_peak_rss_bytes"] > 0
        # A much larger allocation must move the peak and emit the
        # high-watermark breadcrumb.
        keep.append(jnp.ones((1 << 20,), jnp.float32))
        second = acc.sample()
        assert second["device_live_bytes"] > first["device_live_bytes"]
        marks = cap.events("mem_high_watermark")
    assert marks and marks[-1]["bytes"] >= (1 << 22)
    assert marks[-1]["ratio"] > 1.05
    assert acc.device_peak_bytes == second["device_live_bytes"]
    del keep


def test_memory_accountant_providers():
    acc = obs_memory.MemoryAccountant()
    acc.add_provider(lambda: {"thing": 1234})
    acc.add_provider(lambda: (_ for _ in ()).throw(RuntimeError()))
    sample = acc.sample()
    assert sample["components"]["thing"] == 1234
    gauge = default_registry().get("edl_mem_component_bytes")
    assert gauge.labels(component="thing").value == 1234


def test_ps_shard_registers_embedding_bytes():
    from elasticdl_tpu.ps.embedding_table import EmbeddingTable
    from elasticdl_tpu.ps.parameters import Parameters

    params = Parameters()
    params.dense["w"] = np.zeros((10, 4), dtype=np.float32)
    params.embedding_tables["emb"] = EmbeddingTable("emb", 8)
    params.embedding_tables["emb"].lookup(np.arange(5, dtype=np.int64))
    provider = obs_memory.embedding_bytes_provider(params)
    sizes = provider()
    assert sizes["ps_dense_params"] == 10 * 4 * 4
    assert sizes["ps_embedding:emb"] == 5 * 8 * 4


# ---------------------------------------------------------------------------
# on-demand device profiles
# ---------------------------------------------------------------------------


def test_debug_profile_endpoint_returns_nonempty_capture(tmp_path):
    exporter = MetricsExporter(
        default_registry(), port=0, host="127.0.0.1"
    )
    exporter.profile_provider = profiling.profile_provider(
        str(tmp_path), "testrole"
    )
    stop = threading.Event()

    def busy():
        # A step every 5 ms: at most a hundred in the capture. A loop
        # that spins instead is traced call by call (thousands of steps,
        # megabytes), goes on spinning while `stop_trace` writes them
        # out, and holds the GIL against the handler's thread the whole
        # time: on one shared core that took the request past its 30 s.
        g = jax.jit(lambda x: (x * x).sum())
        x = jnp.ones((256,))
        while not stop.wait(0.005):
            g(x).block_until_ready()

    worker = threading.Thread(target=busy, daemon=True)
    worker.start()
    try:
        body = urllib.request.urlopen(
            f"http://127.0.0.1:{exporter.port}/debug/profile?seconds=0.5",
            timeout=30,
        ).read()
    finally:
        stop.set()
        worker.join(timeout=5)
        exporter.close()
    result = json.loads(body.decode())
    assert result["bytes"] > 0, result
    assert result["files"], result
    assert os.path.isdir(result["dir"])
    assert str(tmp_path) in result["dir"]


def test_start_profile_rpc_fans_out_over_endpoints(tmp_path):
    """MasterServicer.start_profile hits every advertised endpoint's
    /debug/profile and aggregates the capture summaries."""
    from elasticdl_tpu.proto import elasticdl_tpu_pb2 as pb

    exporter = MetricsExporter(
        default_registry(), port=0, host="127.0.0.1"
    )
    exporter.profile_provider = profiling.profile_provider(
        str(tmp_path), "worker-0"
    )

    class FakeAggregator:
        def discover_endpoints(self):
            return [
                {
                    "role": "worker-0",
                    "host": "127.0.0.1",
                    "port": exporter.port,
                },
                {"role": "ps-0", "host": "127.0.0.1", "port": 1},
            ]

    with start_master(training_shards={"f": (0, 10)}) as m:
        m["servicer"].bind_job_context(aggregator=FakeAggregator())
        try:
            resp = m["servicer"].start_profile(
                pb.StartProfileRequest(seconds=0.3), None
            )
        finally:
            exporter.close()
    results = json.loads(resp.results_json)
    assert resp.captured == 1
    assert results["worker-0"]["bytes"] > 0
    assert "error" in results["ps-0"]  # dead endpoint reported, not raised


def test_profile_capture_rejects_concurrent_runs(tmp_path):
    done = {}

    def first():
        done["first"] = profiling.capture_device_profile(
            0.8, str(tmp_path)
        )

    t = threading.Thread(target=first)
    t.start()
    time.sleep(0.3)
    try:
        profiling.capture_device_profile(0.2, str(tmp_path))
        raise AssertionError("second concurrent capture must raise")
    except RuntimeError:
        pass
    t.join()
    assert done["first"]["seconds"] == 0.8


def test_step_report_from_obs_dir(tmp_path):
    import sys

    sys.path.insert(
        0, os.path.join(os.path.dirname(os.path.dirname(__file__)))
    )
    from tools import step_report

    spans = [
        {"ph": "M", "name": "process_name", "pid": 1, "tid": 0},
        {"ph": "X", "name": "batch_process", "ts": 0, "dur": 10e6,
         "pid": 1, "tid": 1},
        {"ph": "X", "name": "ps_push_serialize", "ts": 0, "dur": 1e6,
         "pid": 1, "tid": 1},
        {"ph": "X", "name": "ps_push_wait", "ts": 0, "dur": 2e6,
         "pid": 1, "tid": 1},
        {"ph": "X", "name": "compile:train_step", "ts": 0, "dur": 3e6,
         "pid": 1, "tid": 1},
    ]
    with open(tmp_path / "trace_worker-0.jsonl", "w") as f:
        for s in spans:
            f.write(json.dumps(s) + "\n")
    with open(tmp_path / "events.jsonl", "w") as f:
        f.write(
            json.dumps(
                {"ts": 1.0, "kind": "compile", "fn": "train_step",
                 "cause": "mesh_change", "seconds": 3.0, "seq": 1}
            )
            + "\n"
        )
    data = step_report.collect(str(tmp_path))
    row = data["roles"]["worker-0"]
    assert row["serialize"] == 0.1
    assert row["ps_wire"] == 0.2
    assert row["recompile"] == 0.3
    assert abs(row["compute"] - 0.4) < 1e-9
    report = step_report.render_report(str(tmp_path))
    assert "worker-0" in report
    assert "mesh_change=1" in report


# ---------------------------------------------------------------------------
# the elastic acceptance path: a world change that RESHAPES the mesh shows
# up as a mesh_change compile with nonzero compile seconds on the master's
# aggregated view — while an epoch bump that resolves to the same world
# spec re-lowers NOTHING (the recompile-free fast path)
# ---------------------------------------------------------------------------


def test_world_change_emits_mesh_change_compile(tmp_path):
    import tests.test_module as test_module
    from elasticdl_tpu.observability.aggregator import (
        TelemetryAggregator,
    )
    from elasticdl_tpu.parallel.mesh import WorldTopology
    from elasticdl_tpu.worker.allreduce_trainer import AllReduceTrainer
    from elasticdl_tpu.worker.master_client import MasterClient

    rng = np.random.default_rng(0)
    x = rng.normal(size=(16, test_module.FEATURE_DIM)).astype(np.float32)
    y = (x @ test_module.TRUE_W + test_module.TRUE_B).astype(np.float32)

    baseline_seconds = _seconds_for("allreduce_step")
    with _EventCapture(tmp_path) as cap:
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
                steps_per_world_check=1,
            )
            try:
                t.train_minibatch(x, y)
                epoch_before = t._group_id
                compiles_before = profiling.tracker().snapshot()[0]
                # A second worker joins: membership epoch bumps, but the
                # world resolves to the SAME spec on this single-host
                # backend — the fast path must keep the compiled step.
                m["membership"].add_worker_host("10.0.0.2:9999")
                t.train_minibatch(x, y)
                assert t._group_id > epoch_before
                assert (
                    profiling.tracker().snapshot()[0] == compiles_before
                ), "same-spec world change re-lowered the step"
                # Now the world RESHAPES (stand-in for a device-count
                # change): 8 -> 7 devices; the rebuild re-lowers with
                # cause=mesh_change.
                t._topo_override = WorldTopology(7, 7, 1)
                m["membership"].add_worker_host("10.0.0.3:9999")
                t.train_minibatch(x, y)
                t.train_minibatch(x, y)
            finally:
                profiling.note_mesh("", world_size=0)
                t.close()
                mc.close()
        mesh_events = [
            e
            for e in cap.events("compile")
            if e["cause"] == "mesh_change"
        ]
        regroups = cap.events("elastic_regroup")
    assert mesh_events, cap.events("compile")
    assert any(e["fn"] == "allreduce_step" for e in mesh_events)
    # Both regroup paths were taken, in order: the same-spec epoch bump
    # absorbed fast, the reshaped world rebuilt.
    assert [r["mode"] for r in regroups] == ["rebuild", "fast", "rebuild"]
    assert _seconds_for("allreduce_step") > baseline_seconds

    # The master's aggregated view: scraping this worker's registry must
    # surface nonzero edl_compile_seconds_total in the compiles block.
    agg = TelemetryAggregator(obs_dir=str(tmp_path), job="t")
    now = time.time()
    assert agg._ingest("worker-0", default_registry().expose(), now)
    agg._derive(now, {"worker-0"})
    compiles = agg.summary()["compiles"]
    assert compiles["edl_compile_seconds_total"] > 0
    assert compiles["by_cause"].get("mesh_change", 0) >= 1


def test_join_gate_budget_derives_from_measured_compiles(monkeypatch):
    """The elastic join gate scales with the longest compile this
    process has actually measured (the fixed 90 s gate lost to ~6.5 s
    step compiles on loaded 1-core boxes); the registered knob
    overrides."""
    from elasticdl_tpu.worker.allreduce_trainer import join_gate_budget

    monkeypatch.delenv("ELASTICDL_JOIN_GATE_SECONDS", raising=False)
    monkeypatch.setattr(profiling.tracker(), "peak_seconds", 0.0)
    assert join_gate_budget() == 90.0  # floor before any compile
    monkeypatch.setattr(profiling.tracker(), "peak_seconds", 6.5)
    assert join_gate_budget() == 130.0  # 20x the measured compile
    monkeypatch.setattr(profiling.tracker(), "peak_seconds", 300.0)
    assert join_gate_budget() == 600.0  # capped: minutes, not hours
    monkeypatch.setenv("ELASTICDL_JOIN_GATE_SECONDS", "42")
    assert join_gate_budget() == 42.0  # explicit knob wins
