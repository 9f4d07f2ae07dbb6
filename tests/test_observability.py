"""Observability: timing accumulators, metrics JSONL/TensorBoard export,
the job-status RPC behind `edl top`, and the unified observability plane
(Prometheus registry + /metrics endpoint, cross-process tracing, the
elasticity event log)."""

import json
import os
import signal
import subprocess
import sys
import time
import urllib.request

from elasticdl_tpu.common import rpc
from elasticdl_tpu.common.timing import Timing
from elasticdl_tpu.master.metrics_service import MetricsService
from elasticdl_tpu.observability import events as obs_events
from elasticdl_tpu.observability import tracing
from elasticdl_tpu.observability.exporter import MetricsExporter
from elasticdl_tpu.observability.metrics import MetricsRegistry
from elasticdl_tpu.proto import elasticdl_tpu_pb2 as pb

from test_utils import start_master

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path.insert(0, os.path.join(REPO, "tools"))


def test_timing_accumulates_and_reports():
    t = Timing()
    for _ in range(3):
        with t.record("phase_a"):
            time.sleep(0.01)
    t.add("phase_b", 1.5)
    s = t.summary()
    assert s["phase_a"]["count"] == 3
    assert s["phase_a"]["total_s"] >= 0.03
    assert abs(s["phase_a"]["mean_s"] - s["phase_a"]["total_s"] / 3) < 1e-9
    assert s["phase_b"]["total_s"] == 1.5
    t.reset()
    assert t.summary() == {}


def test_timing_disabled_is_free():
    t = Timing(enabled=False)
    with t.record("x"):
        pass
    t.add("y", 1.0)
    assert t.summary() == {}


def test_metrics_service_writes_jsonl_and_tb(tmp_path):
    ms = MetricsService(str(tmp_path))
    ms.log_scalars("train", 10, {"records_per_sec": 123.4, "epoch": 1})
    ms.on_evaluation_results(20, {"accuracy": 0.75})
    ms.close()
    lines = [
        json.loads(line)
        for line in (tmp_path / "metrics.jsonl").read_text().splitlines()
    ]
    assert lines[0]["group"] == "train" and lines[0]["step"] == 10
    assert lines[0]["records_per_sec"] == 123.4
    assert lines[1]["group"] == "eval" and lines[1]["accuracy"] == 0.75
    # TensorBoard event files appear when a SummaryWriter is available
    # (torch.utils.tensorboard in this image).
    assert any(
        "tfevents" in p.name for p in tmp_path.iterdir()
    ), "expected TB event file alongside metrics.jsonl"


def test_get_job_status_rpc():
    with start_master(
        training_shards={"f": (0, 40)}, records_per_task=20
    ) as m:
        stub = rpc.Stub(rpc.build_channel(m["addr"]), rpc.MASTER_SERVICE)
        status = stub.get_job_status(pb.GetJobStatusRequest())
        assert status.todo_tasks == 2 and status.doing_tasks == 0
        assert status.epoch == 1 and not status.finished

        task = stub.get_task(pb.GetTaskRequest(worker_id=3))
        status = stub.get_job_status(pb.GetJobStatusRequest())
        assert status.todo_tasks == 1 and status.doing_tasks == 1
        assert status.alive_workers == 1  # worker 3 touched liveness

        stub.report_task_result(
            pb.ReportTaskResultRequest(task_id=task.task_id)
        )
        status = stub.get_job_status(pb.GetJobStatusRequest())
        assert status.records_done == 20

        task2 = stub.get_task(pb.GetTaskRequest(worker_id=3))
        stub.report_task_result(
            pb.ReportTaskResultRequest(task_id=task2.task_id)
        )
        status = stub.get_job_status(pb.GetJobStatusRequest())
        assert status.finished and status.records_done == 40


def test_metrics_service_metadata_collision(tmp_path):
    """A user metric named like a record metadata field must not clobber
    ts/group/step."""
    ms = MetricsService(str(tmp_path), tensorboard=False)
    ms.log_scalars("eval", 7, {"step": 0.99, "accuracy": 0.5})
    ms.close()
    line = json.loads((tmp_path / "metrics.jsonl").read_text())
    assert line["step"] == 7  # the model version, not the metric
    assert line["metric_step"] == 0.99
    assert line["accuracy"] == 0.5


def test_timing_nested_and_exception_safety():
    t = Timing()
    try:
        with t.record("outer"):
            with t.record("inner"):
                raise RuntimeError("boom")
    except RuntimeError:
        pass
    s = t.summary()
    # Both phases recorded despite the exception escaping.
    assert s["outer"]["count"] == 1 and s["inner"]["count"] == 1


# ---------- unified observability plane ----------


def test_metrics_registry_exposition():
    reg = MetricsRegistry()
    c = reg.counter("edl_x_total", "help text")
    c.inc()
    c.inc(2)
    g = reg.gauge("edl_g", "gauge", labelnames=("kind",))
    g.labels(kind="a").set(1.5)
    g.labels(kind="b").set(2)
    h = reg.histogram(
        "edl_d_seconds", "hist", buckets=(0.1, 1.0, 10.0)
    )
    for v in (0.05, 0.5, 5.0, 50.0):
        h.observe(v)
    text = reg.expose()
    assert "# TYPE edl_x_total counter" in text
    assert "edl_x_total 3" in text
    assert 'edl_g{kind="a"} 1.5' in text
    assert 'edl_g{kind="b"} 2' in text
    # Cumulative buckets + +Inf + sum/count.
    assert 'edl_d_seconds_bucket{le="0.1"} 1' in text
    assert 'edl_d_seconds_bucket{le="1"} 2' in text
    assert 'edl_d_seconds_bucket{le="10"} 3' in text
    assert 'edl_d_seconds_bucket{le="+Inf"} 4' in text
    assert "edl_d_seconds_count 4" in text
    # Bounded-reservoir quantiles answer without unbounded growth.
    assert h.quantile(0.5) in (0.5, 5.0)
    # Re-registration returns the same metric; conflicts are rejected.
    assert reg.counter("edl_x_total") is c
    try:
        reg.gauge("edl_x_total")
        assert False, "type conflict must raise"
    except ValueError:
        pass


def test_metrics_exporter_scrape_and_healthz():
    reg = MetricsRegistry()
    reg.counter("edl_scraped_total", "x").inc(7)
    exporter = MetricsExporter(reg, port=0)
    try:
        base = f"http://127.0.0.1:{exporter.port}"
        body = urllib.request.urlopen(f"{base}/metrics", timeout=5)
        assert body.status == 200
        text = body.read().decode()
        assert "edl_scraped_total 7" in text
        health = urllib.request.urlopen(f"{base}/healthz", timeout=5)
        assert health.read() == b"ok\n"
    finally:
        exporter.close()


def test_timing_min_max_percentiles_and_histogram_mirror():
    reg = MetricsRegistry()
    hist = reg.histogram(
        "edl_phase_seconds_test", "x", labelnames=("phase",)
    )
    t = Timing().bind_histogram(hist)
    for ms in (1, 2, 3, 4, 100):
        t.add("pull", ms / 1000.0)
    s = t.summary()["pull"]
    assert s["count"] == 5
    assert abs(s["min_s"] - 0.001) < 1e-9
    assert abs(s["max_s"] - 0.1) < 1e-9
    assert s["p50_s"] <= s["p99_s"] <= s["max_s"]
    assert abs(s["p99_s"] - 0.1) < 1e-9  # reservoir holds all 5 samples
    # Samples mirrored into the labeled histogram for /metrics.
    assert hist.labels(phase="pull").count == 5


def test_trace_context_propagates_across_real_grpc_hop(tmp_path):
    """A REAL in-process gRPC hop (client interceptor -> server
    interceptor): the server-side span must carry the caller's trace id,
    task id, and lease epoch, and the dispatch instant must carry the
    dispatched task's id."""
    rec = tracing.SpanRecorder(
        str(tmp_path / "trace_test.jsonl"), "test-proc"
    )
    tracing.set_recorder(rec)
    try:
        with start_master(
            training_shards={"f": (0, 40)}, records_per_task=20
        ) as m:
            stub = rpc.Stub(
                rpc.build_channel(m["addr"]), rpc.MASTER_SERVICE
            )
            ctx = tracing.set_context(task_id=777, lease_epoch=3)
            task = stub.get_task(pb.GetTaskRequest(worker_id=1))
            assert task.task_id >= 0
    finally:
        tracing.set_recorder(None)
        rec.close()
        tracing.clear_context()
    lines = [
        json.loads(line)
        for line in (tmp_path / "trace_test.jsonl").read_text().splitlines()
    ]
    server_spans = [
        l for l in lines if l.get("name", "").startswith("rpc_server/")
    ]
    client_spans = [
        l for l in lines if l.get("name", "").startswith("rpc_client/")
    ]
    assert server_spans and client_spans
    args = server_spans[0]["args"]
    assert args["trace_id"] == ctx.trace_id
    assert args["task_id"] == 777
    assert args["lease_epoch"] == 3
    assert client_spans[0]["args"]["trace_id"] == ctx.trace_id
    dispatch = [l for l in lines if l.get("name") == "dispatch_task"]
    assert dispatch and dispatch[0]["args"]["task_id"] == task.task_id
    # The metadata-level codec round-trips standalone too.
    try:
        ctx2 = tracing.set_context(task_id=9, lease_epoch=2, job="j")
        restored = tracing.context_from_metadata(tracing._inject(()))
        assert restored.trace_id == ctx2.trace_id
        assert restored.task_id == 9
        assert restored.lease_epoch == 2
        assert restored.job == "j"
    finally:
        tracing.clear_context()


def test_event_log_order_and_noop_when_unconfigured(tmp_path):
    path = str(tmp_path / "events.jsonl")
    log = obs_events.EventLog(path, job="j", role="master")
    obs_events.set_event_log(log)
    try:
        obs_events.emit("pod_launch", instance="worker-0")
        obs_events.emit("pod_exit", instance="worker-0", exit_code=-9)
        obs_events.emit("pod_relaunch", instance="worker-0", attempt=1)
    finally:
        obs_events.set_event_log(None)
        log.close()
    # Unconfigured emission must be a silent no-op.
    obs_events.emit("dropped", x=1)
    records = obs_events.read_events(path)
    assert [r["kind"] for r in records] == [
        "pod_launch", "pod_exit", "pod_relaunch",
    ]
    seqs = [r["seq"] for r in records]
    assert seqs == sorted(seqs) and len(set(seqs)) == 3
    assert all(r["job"] == "j" and r["role"] == "master" for r in records)


def test_log_utils_env_level_and_json_format(capsys):
    from elasticdl_tpu.common import log_utils

    old_level = os.environ.pop("ELASTICDL_LOG_LEVEL", None)
    old_format = os.environ.pop("ELASTICDL_LOG_FORMAT", None)
    try:
        os.environ["ELASTICDL_LOG_LEVEL"] = "WARNING"
        os.environ["ELASTICDL_LOG_FORMAT"] = "json"
        log_utils.configure(force=True)
        log_utils.set_identity(job="jobx", role="worker-1")
        logger = log_utils.get_logger("test.json")
        logger.info("invisible at WARNING")
        logger.warning("structured %s", "payload")
        err = capsys.readouterr().err
        lines = [l for l in err.strip().splitlines() if l]
        assert len(lines) == 1, lines
        record = json.loads(lines[0])
        assert record["level"] == "WARNING"
        assert record["msg"] == "structured payload"
        assert record["job"] == "jobx" and record["role"] == "worker-1"
        assert record["logger"] == "elasticdl_tpu.test.json"
    finally:
        for key, old in (
            ("ELASTICDL_LOG_LEVEL", old_level),
            ("ELASTICDL_LOG_FORMAT", old_format),
        ):
            if old is None:
                os.environ.pop(key, None)
            else:
                os.environ[key] = old
        log_utils.configure(force=True)


def test_trace_report_merges_and_summarizes(tmp_path):
    import trace_report

    a = tmp_path / "trace_master.jsonl"
    b = tmp_path / "trace_worker-0.jsonl"
    a.write_text(
        "\n".join(
            [
                json.dumps(
                    {
                        "ph": "M", "name": "process_name", "pid": 1,
                        "tid": 0, "args": {"name": "j/master"},
                    }
                ),
                json.dumps(
                    {
                        "ph": "i", "name": "dispatch_task", "pid": 1,
                        "tid": 0, "ts": 100.0,
                        "args": {"task_id": 5},
                    }
                ),
            ]
        )
        + "\n"
    )
    b.write_text(
        "\n".join(
            [
                json.dumps(
                    {
                        "ph": "M", "name": "process_name", "pid": 2,
                        "tid": 0, "args": {"name": "j/worker-0"},
                    }
                ),
                json.dumps(
                    {
                        "ph": "X", "name": "task_process", "pid": 2,
                        "tid": 0, "ts": 200.0, "dur": 5000.0,
                        "args": {"task_id": 5},
                    }
                ),
                '{"torn line'  # killed process: must be skipped, not fatal
            ]
        )
    )
    events, names = trace_report.load_events([str(tmp_path)])
    assert names == {1: "j/master", 2: "j/worker-0"}
    summary = trace_report.summarize(events, names)
    assert summary[("j/worker-0", "task_process")]["count"] == 1
    assert summary[("j/worker-0", "task_process")]["total_ms"] == 5.0
    chain = trace_report.task_chain(events, names, 5)
    assert [h["process"] for h in chain] == ["j/master", "j/worker-0"]
    out = tmp_path / "merged.json"
    rc = trace_report.main([str(tmp_path), "--out", str(out), "--json"])
    assert rc == 0
    merged = json.loads(out.read_text())
    assert len(merged["traceEvents"]) == 4


def _poll(deadline_s, predicate, interval=0.5):
    deadline = time.time() + deadline_s
    while time.time() < deadline:
        value = predicate()
        if value:
            return value
        time.sleep(interval)
    return None


def _scrape(port):
    return (
        urllib.request.urlopen(
            f"http://127.0.0.1:{port}/metrics", timeout=5
        )
        .read()
        .decode()
    )


def _metric_value(text, name):
    """First sample value of `name` (any labels) in exposition text."""
    total = 0.0
    found = False
    for line in text.splitlines():
        if line.startswith(name) and not line.startswith("# "):
            rest = line[len(name):]
            if rest[:1] not in ("", " ", "{"):
                continue  # longer metric name sharing the prefix
            total += float(line.rsplit(" ", 1)[1])
            found = True
    return total if found else None


def test_observability_e2e_two_workers_two_ps(tmp_path):
    """The acceptance drill for the unified observability plane: a REAL
    `edl train` job (2 workers + 2 PS local processes) must produce
    (1) per-process /metrics endpoints with nonzero task-dispatch and PS
    push/pull byte counters, (2) per-process trace files whose merge shows
    one task's spans crossing >= 3 processes, and (3) an events.jsonl that
    reconstructs the elasticity timeline launch -> kill -> relaunch."""
    import test_module
    from elasticdl_tpu.data.recordfile import RecordFileWriter

    data = str(tmp_path / "linear.edlr")
    with RecordFileWriter(data) as w:
        for r in test_module.make_linear_records(512):
            w.write(r)
    obs_dir = str(tmp_path / "obs")
    env = dict(os.environ)
    env["PYTHONPATH"] = f"{REPO}:{REPO}/tests"
    env["JAX_PLATFORMS"] = "cpu"
    env["ELASTICDL_OBS_DIR"] = obs_dir
    env.pop("ELASTICDL_METRICS_PORT", None)
    env.pop("XLA_FLAGS", None)  # children are plain 1-device CPU worlds
    log_path = str(tmp_path / "job.log")
    proc = subprocess.Popen(
        [
            sys.executable, "-m", "elasticdl_tpu.client.main", "train",
            "--model_zoo", f"{REPO}/tests",
            "--model_def", "test_module",
            "--training_data", data,
            "--num_epochs", "600",
            "--records_per_task", "64",
            "--minibatch_size", "32",
            "--num_workers", "2",
            "--num_ps", "2",
            "--distribution_strategy", "ParameterServerStrategy",
            "--instance_backend", "local_process",
            "--master_port", "0",
            "--job_name", "obs-e2e",
        ],
        stdout=open(log_path, "w"),
        stderr=subprocess.STDOUT,
        env=env,
        cwd=REPO,
    )
    endpoints_dir = os.path.join(obs_dir, "endpoints")
    roles = ("master", "ps-0", "ps-1", "worker-0", "worker-1")
    try:
        # --- every process advertises its scrape endpoint ---
        assert _poll(
            150,
            lambda: all(
                os.path.exists(os.path.join(endpoints_dir, f"{r}.json"))
                for r in roles
            ),
        ), f"missing endpoints; log tail:\n{open(log_path).read()[-3000:]}"
        endpoints = {
            r: json.load(open(os.path.join(endpoints_dir, f"{r}.json")))
            for r in roles
        }

        # --- /metrics scrapes show live, nonzero counters ---
        def master_busy():
            text = _scrape(endpoints["master"]["port"])
            return (_metric_value(text, "edl_tasks_dispatched_total") or 0) > 0
        assert _poll(90, master_busy), "master never dispatched tasks"

        def ps_busy():
            # Every shard serves pulls; pushes go to the shard(s) owning
            # the params (the 2-param linear model can hash both onto one
            # shard), so pushes are asserted in aggregate.
            push_total = 0.0
            for r in ("ps-0", "ps-1"):
                text = _scrape(endpoints[r]["port"])
                if not (_metric_value(text, "edl_ps_pull_bytes_total") or 0):
                    return False
                push_total += (
                    _metric_value(text, "edl_ps_push_bytes_total") or 0
                )
            return push_total > 0
        assert _poll(90, ps_busy), "PS push/pull byte counters stayed zero"

        def workers_busy():
            return all(
                (
                    _metric_value(
                        _scrape(endpoints[r]["port"]),
                        "edl_worker_steps_total",
                    )
                    or 0
                )
                > 0
                for r in ("worker-0", "worker-1")
            )
        assert _poll(90, workers_busy), "worker step counters stayed zero"

        # --- elasticity: SIGKILL worker-0, await relaunch in the log ---
        victim_pid = endpoints["worker-0"]["pid"]
        os.kill(victim_pid, signal.SIGKILL)
        events_path = os.path.join(obs_dir, "events.jsonl")

        def relaunched():
            if not os.path.exists(events_path):
                return False
            kinds = [
                (e["kind"], e.get("instance"))
                for e in obs_events.read_events(events_path)
            ]
            return ("pod_relaunch", "worker-0") in kinds
        assert _poll(120, relaunched), (
            "no relaunch event; log tail:\n"
            + open(log_path).read()[-3000:]
        )
    finally:
        proc.terminate()
        try:
            proc.wait(30)
        except subprocess.TimeoutExpired:
            proc.kill()
            proc.wait(10)

    # --- events.jsonl reconstructs launch -> kill -> relaunch in order ---
    records = obs_events.read_events(
        os.path.join(obs_dir, "events.jsonl")
    )
    w0 = [
        r for r in records if r.get("instance") == "worker-0"
    ]
    kinds = [r["kind"] for r in w0]
    launch = kinds.index("pod_launch")
    exit_ = kinds.index("pod_exit")
    relaunch = kinds.index("pod_relaunch")
    assert launch < exit_ < relaunch, kinds
    assert "pod_launch" in kinds[relaunch:], kinds  # the replacement
    seqs = [r["seq"] for r in w0]
    assert seqs == sorted(seqs)
    # The dead worker's in-flight tasks were reassigned.
    assert any(
        r["kind"] == "task_reassign" and r.get("worker") == 0
        for r in records
    ), [r["kind"] for r in records]
    assert any(r["kind"] == "task_create" for r in records)

    # --- merged trace: one task's spans cross >= 3 processes ---
    import trace_report

    events, names = trace_report.load_events([obs_dir])
    assert len(names) >= 5, names  # master + 2 PS + 2 workers
    by_task = {}
    for e in events:
        task_id = e.get("args", {}).get("task_id")
        if task_id is not None and e.get("ph") in ("X", "i"):
            by_task.setdefault(task_id, set()).add(e["pid"])
    crossing = {t: pids for t, pids in by_task.items() if len(pids) >= 3}
    assert crossing, {
        t: sorted(names.get(p, p) for p in pids)
        for t, pids in by_task.items()
    }
    merged = str(tmp_path / "merged.json")
    assert trace_report.main([obs_dir, "--out", merged, "--json"]) == 0
    assert json.load(open(merged))["traceEvents"]
