"""Job-level telemetry (ISSUE 3): the promtext parser round-trip, straggler
scoring, the alert rules engine, the in-process aggregation pipeline, the
exporter's HEAD//api/summary surface, the dashboard renderer, the worker
MFU estimator — and, chaos-marked, the end-to-end straggler drill (one
worker slowed by role-targeted chaos latency must be flagged on the
master's /metrics and /api/summary while the job still completes)."""

import json
import os
import sys
import urllib.request

import pytest

from elasticdl_tpu.observability import alerts as alerts_mod
from elasticdl_tpu.observability import events as obs_events
from elasticdl_tpu.observability import promtext
from elasticdl_tpu.observability.aggregator import (
    TelemetryAggregator,
    histogram_quantile,
    skew_scores,
)
from elasticdl_tpu.observability.exporter import MetricsExporter
from elasticdl_tpu.observability.metrics import MetricsRegistry

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path.insert(0, os.path.join(REPO, "tools"))


def _rich_registry():
    reg = MetricsRegistry()
    c = reg.counter("edl_rt_total", "counter help")
    c.inc(3)
    lc = reg.counter(
        "edl_rt_labeled_total", "labeled counter", labelnames=("kind",)
    )
    lc.labels(kind="a").inc(2)
    lc.labels(kind='esc"ape\\n\new').inc(5)  # quotes/backslash/newline
    g = reg.gauge("edl_rt_gauge", "gauge", labelnames=("x", "y"))
    g.labels(x="1", y="2").set(1.5)
    h = reg.histogram(
        "edl_rt_seconds", "hist", labelnames=("phase",),
        buckets=(0.1, 1.0, 10.0),
    )
    for v in (0.05, 0.5, 5.0, 50.0):
        h.labels(phase="p").observe(v)
    return reg


# ---------- promtext: the exact inverse of expose() ----------


def test_promtext_roundtrip_identical_text():
    text = _rich_registry().expose()
    families = promtext.parse(text)
    # Byte-identical re-serialization is the strongest inverse property:
    # every sample, label escape, value format, and ordering survived.
    assert promtext.to_text(families) == text
    # And a second round trip is a fixed point.
    assert promtext.to_text(promtext.parse(promtext.to_text(families))) \
        == text


def test_promtext_parse_structure_and_escapes():
    text = _rich_registry().expose()
    families = promtext.parse(text)
    assert families["edl_rt_total"].type == "counter"
    assert families["edl_rt_total"].help == "counter help"
    assert promtext.sample_value(families, "edl_rt_total") == 3
    # Escaped label values decode back to the original string.
    assert promtext.sample_value(
        families, "edl_rt_labeled_total",
        {"kind": 'esc"ape\\n\new'},
    ) == 5
    assert promtext.sample_value(
        families, "edl_rt_gauge", {"x": "1", "y": "2"}
    ) == 1.5
    # Histogram _bucket/_sum/_count lines belong to the base family.
    hist = families["edl_rt_seconds"]
    assert hist.type == "histogram"
    names = {s.name for s in hist.samples}
    assert names == {
        "edl_rt_seconds_bucket",
        "edl_rt_seconds_sum",
        "edl_rt_seconds_count",
    }
    assert promtext.sample_value(
        families, "edl_rt_seconds_bucket", {"le": "+Inf", "phase": "p"}
    ) == 4
    flat = promtext.samples(text)
    assert ("edl_rt_total", {}, 3.0) in flat


def test_promtext_roundtrip_datapath_families():
    """The data-plane families (stage-labeled counter + histogram,
    queue gauges) survive expose->parse->to_text byte-identically —
    the property the aggregator's scrape ingestion rests on."""
    reg = MetricsRegistry()
    sec = reg.counter(
        "edl_datapath_seconds_total", "stage seconds",
        labelnames=("stage",),
    )
    for stage, v in (
        ("task", 0.01), ("read", 0.2), ("decode", 0.05),
        ("h2d", 0.02), ("starve", 0.5),
    ):
        sec.labels(stage=stage).inc(v)
    hist = reg.histogram(
        "edl_datapath_stage_seconds", "per-op stage latency",
        labelnames=("stage",), buckets=(0.001, 0.01, 0.1, 1.0),
    )
    for v in (0.0005, 0.05, 0.5):
        hist.labels(stage="read").observe(v)
    reg.counter("edl_datapath_records_total", "records").inc(640)
    reg.gauge(
        "edl_datapath_queue_depth", "depth", labelnames=("queue",)
    ).labels(queue="prefetch").set(17)
    text = reg.expose()
    families = promtext.parse(text)
    assert promtext.to_text(families) == text
    assert families["edl_datapath_stage_seconds"].type == "histogram"
    assert promtext.sample_value(
        families, "edl_datapath_stage_seconds_bucket",
        {"le": "+Inf", "stage": "read"},
    ) == 3
    assert promtext.sample_value(
        families, "edl_datapath_stage_seconds_bucket",
        {"le": "0.001", "stage": "read"},
    ) == 1
    assert promtext.sample_value(
        families, "edl_datapath_seconds_total", {"stage": "starve"}
    ) == 0.5
    assert promtext.sample_value(
        families, "edl_datapath_records_total"
    ) == 640


def test_promtext_rejects_garbage():
    with pytest.raises(promtext.ParseError):
        promtext.parse("edl_x{unterminated 1\n")
    with pytest.raises(promtext.ParseError):
        promtext.parse("edl_x notanumber\n")


# ---------- straggler scoring + quantile estimation ----------


def test_skew_scores_flags_the_slow_worker():
    scores = skew_scores(
        {"worker-0": 0.30, "worker-1": 0.010, "worker-2": 0.012}
    )
    assert scores["worker-0"] == pytest.approx(0.30 / 0.012)
    assert scores["worker-1"] <= scores["worker-2"] < 2.0
    # Two-worker fleet (the drill's world): the low median keeps the
    # baseline on the healthy worker, so the straggler's score is large
    # instead of asymptoting to 2.0.
    two = skew_scores({"worker-0": 0.25, "worker-1": 0.005})
    assert two["worker-0"] == pytest.approx(50.0)
    assert two["worker-1"] == pytest.approx(1.0)


def test_skew_scores_degenerate_inputs():
    assert skew_scores({}) == {}
    assert skew_scores({"w": 1.0}) == {}  # one subject: no fleet
    assert skew_scores({"a": 0.0, "b": 0.0}) == {}  # degenerate median
    assert skew_scores({"a": None, "b": 1.0}) == {}


def test_histogram_quantile():
    buckets = [(0.1, 10), (1.0, 90), (10.0, 99), (float("inf"), 100)]
    assert histogram_quantile(buckets, 0.05) == 0.1
    assert histogram_quantile(buckets, 0.5) == 1.0
    assert histogram_quantile(buckets, 0.95) == 10.0
    # The +Inf bucket answers with the largest finite bound.
    assert histogram_quantile(buckets, 0.999) == 10.0
    assert histogram_quantile([], 0.5) is None
    assert histogram_quantile([(1.0, 0)], 0.5) is None


# ---------- alert rules ----------


def test_threshold_rule():
    rule = alerts_mod.ThresholdRule("abandoned", "tasks_abandoned", 1)
    assert rule.evaluate({"tasks_abandoned": 0}, 0) == {}
    assert rule.evaluate({}, 0) == {}
    hit = rule.evaluate({"tasks_abandoned": 2}, 0)
    assert hit["tasks_abandoned"]["value"] == 2


def test_skew_rule():
    rule = alerts_mod.SkewRule("straggler", "straggler_scores", 2.0)
    assert rule.evaluate({"straggler_scores": {}}, 0) == {}
    hit = rule.evaluate(
        {"straggler_scores": {"worker-0": 5.0, "worker-1": 1.0}}, 0
    )
    assert list(hit) == ["worker-0"]
    assert hit["worker-0"]["score"] == 5.0


def test_stall_rule():
    rule = alerts_mod.StallRule(
        "stall", progress="records_done", gate="tasks_doing", seconds=30
    )
    assert rule.evaluate({"records_done": 100, "tasks_doing": 2}, 0) == {}
    # Progress frozen but not yet long enough.
    assert rule.evaluate({"records_done": 100, "tasks_doing": 2}, 10) == {}
    hit = rule.evaluate({"records_done": 100, "tasks_doing": 2}, 45)
    assert hit["records_done"]["stalled_seconds"] == 45
    # Progress resumes: re-arms.
    assert rule.evaluate({"records_done": 160, "tasks_doing": 2}, 50) == {}
    # Frozen with an EMPTY queue is idleness, not a stall.
    assert rule.evaluate({"records_done": 160, "tasks_doing": 0}, 200) == {}
    assert rule.evaluate({"records_done": 160, "tasks_doing": 0}, 400) == {}


def test_alert_engine_edge_trigger_and_events(tmp_path):
    log = obs_events.EventLog(str(tmp_path / "events.jsonl"), job="j")
    obs_events.set_event_log(log)
    reg = MetricsRegistry()
    try:
        engine = alerts_mod.AlertEngine(
            rules=[
                alerts_mod.SkewRule("straggler", "straggler_scores", 2.0)
            ],
            registry=reg,
        )
        bad = {"straggler_scores": {"worker-0": 4.0, "worker-1": 1.0}}
        fired = engine.evaluate(bad, now=1)
        assert [a["subject"] for a in fired] == ["worker-0"]
        # Still bad on the next tick: edge-triggered, nothing new fires.
        assert engine.evaluate(bad, now=2) == []
        assert engine.fired_total == 1
        assert engine.active_subjects("straggler") == ["worker-0"]
        text = reg.expose()
        assert 'edl_alerts_total{rule="straggler"} 1' in text
        assert 'edl_alerts_active{rule="straggler"} 1' in text
        # Condition clears -> resolved event + re-armed.
        assert engine.evaluate({"straggler_scores": {}}, now=3) == []
        assert engine.active() == []
        fired = engine.evaluate(bad, now=4)
        assert len(fired) == 1 and engine.fired_total == 2
    finally:
        obs_events.set_event_log(None)
        log.close()
    kinds = [
        (e["kind"], e.get("rule"), e.get("subject"))
        for e in obs_events.read_events(str(tmp_path / "events.jsonl"))
    ]
    assert kinds == [
        ("alert", "straggler", "worker-0"),
        ("alert_resolved", "straggler", "worker-0"),
        ("alert", "straggler", "worker-0"),
    ]


# ---------- in-process aggregation pipeline ----------


def _write_endpoint(obs_dir, role, port):
    endpoints = os.path.join(obs_dir, "endpoints")
    os.makedirs(endpoints, exist_ok=True)
    with open(os.path.join(endpoints, f"{role}.json"), "w") as f:
        json.dump(
            {"role": role, "port": port, "pid": 0, "host": "127.0.0.1"},
            f,
        )


def test_aggregator_scrapes_derives_and_exports(tmp_path):
    """Two fake workers (one 20x slower) + two fake PS shards behind real
    exporters; the aggregator must flag the slow worker, export edl_job_*
    gauges on the master registry, emit the alert event, and publish a
    coherent /api/summary dict."""
    obs_dir = str(tmp_path)
    worker_regs = {}
    exporters = []
    step_time = {"worker-0": 0.2, "worker-1": 0.01}
    for role in ("worker-0", "worker-1"):
        reg = MetricsRegistry()
        reg.histogram(
            "edl_phase_seconds", "phases", labelnames=("phase",),
        )
        worker_regs[role] = reg
        exporter = MetricsExporter(reg, port=0, host="127.0.0.1")
        exporters.append(exporter)
        _write_endpoint(obs_dir, role, exporter.port)
    ps_regs = {}
    for role in ("ps-0", "ps-1"):
        reg = MetricsRegistry()
        reg.counter(
            "edl_ps_push_bytes_total", "push", labelnames=("shard",)
        )
        ps_regs[role] = reg
        exporter = MetricsExporter(reg, port=0, host="127.0.0.1")
        exporters.append(exporter)
        _write_endpoint(obs_dir, role, exporter.port)
    master_reg = MetricsRegistry()
    records = master_reg.gauge("edl_records_done", "records")
    todo = master_reg.gauge("edl_tasks_todo", "todo")
    master_reg.gauge("edl_tasks_doing", "doing").set(2)
    reported = master_reg.counter(
        "edl_tasks_reported_total", "reported", labelnames=("result",)
    )
    log = obs_events.EventLog(str(tmp_path / "events.jsonl"), job="agg")
    obs_events.set_event_log(log)
    agg = TelemetryAggregator(
        obs_dir, registry=master_reg, job="agg", interval=1.0
    )
    try:
        def tick(n_steps, t):
            for role, reg in worker_regs.items():
                h = reg.get("edl_phase_seconds").labels(
                    phase="batch_process"
                )
                for _ in range(n_steps):
                    h.observe(step_time[role])
            ps_regs["ps-0"].get("edl_ps_push_bytes_total").labels(
                shard="0"
            ).inc(9000)
            ps_regs["ps-1"].get("edl_ps_push_bytes_total").labels(
                shard="1"
            ).inc(1000)
            agg.poll_once(now=t)

        records.set(0)
        todo.set(100)
        reported.labels(result="success").inc(0)  # series born at t0
        reported.labels(result="failure").inc(0)
        tick(5, 1000.0)
        records.set(500)
        todo.set(90)
        reported.labels(result="success").inc(10)
        # Failures requeue — they must NOT count as queue drain.
        reported.labels(result="failure").inc(30)
        tick(5, 1010.0)

        text = master_reg.expose()
        assert "edl_job_records_per_second 50" in text
        assert 'edl_job_straggler{worker="worker-0"} 1' in text
        assert 'edl_job_straggler{worker="worker-1"} 0' in text
        assert 'edl_job_step_seconds{worker="worker-0",stat="mean"}' \
            in text
        assert 'edl_job_ps_bytes_per_second{' in text
        summary = agg.summary()
        assert summary["records_per_second"] == pytest.approx(50.0)
        assert summary["stragglers"] == ["worker-0"]
        assert summary["workers"]["worker-0"]["straggler"] is True
        assert summary["workers"]["worker-0"]["mean"] == pytest.approx(
            0.2, rel=0.01
        )
        assert summary["workers"]["worker-1"]["straggler"] is False
        assert summary["ps"]["ps-0"]["load_ratio"] >= 1.0
        assert summary["tasks"]["todo"] == 90
        assert summary["tasks"]["drain_per_second"] == pytest.approx(1.0)
        assert summary["tasks"]["eta_seconds"] == pytest.approx(92.0)
        assert summary["alerts_fired"] >= 1
        assert agg.stragglers() == ["worker-0"]
        # The whole summary must be JSON-able (it backs /api/summary).
        json.dumps(summary)

        # worker-0 stops reporting (scaled away / dead): its series ages
        # out of the rate window, the flag clears on BOTH surfaces —
        # /metrics must not pin edl_job_straggler{worker-0} at 1 forever.
        for t in (1035.0, 1045.0):
            worker_regs["worker-1"].get("edl_phase_seconds").labels(
                phase="batch_process"
            ).observe(step_time["worker-1"])
            agg.poll_once(now=t)
        text = master_reg.expose()
        assert 'edl_job_straggler{worker="worker-0"} 0' in text
        assert agg.stragglers() == []
        assert agg.summary()["stragglers"] == []
    finally:
        obs_events.set_event_log(None)
        log.close()
        agg.close()
        for exporter in exporters:
            exporter.close()
    events = obs_events.read_events(str(tmp_path / "events.jsonl"))
    assert any(
        e["kind"] == "alert"
        and e["rule"] == "straggler"
        and e["subject"] == "worker-0"
        for e in events
    ), events


def test_aggregator_datapath_rollup_and_starvation_alert(tmp_path):
    """Two workers reporting edl_datapath_* series, one spending half
    its wall time on an empty feed: the aggregator must roll up fleet
    stage rates, name the dominant stage, fire the input_starvation
    alert for exactly the starved worker (both /metrics surfaces), and
    publish the datapath block /api/summary and `edl dash` consume."""
    obs_dir = str(tmp_path)
    regs = {}
    exporters = []
    starve_s = {"worker-0": 5.0, "worker-1": 0.1}
    for role in ("worker-0", "worker-1"):
        reg = MetricsRegistry()
        reg.counter(
            "edl_datapath_seconds_total", "stage seconds",
            labelnames=("stage",),
        )
        reg.counter("edl_datapath_records_total", "records")
        reg.gauge(
            "edl_datapath_queue_depth", "depth", labelnames=("queue",)
        )
        reg.counter(
            "edl_datapath_backpressure_total", "bp",
            labelnames=("queue",),
        )
        regs[role] = reg
        exporter = MetricsExporter(reg, port=0, host="127.0.0.1")
        exporters.append(exporter)
        _write_endpoint(obs_dir, role, exporter.port)
    master_reg = MetricsRegistry()
    log = obs_events.EventLog(str(tmp_path / "events.jsonl"), job="dp")
    obs_events.set_event_log(log)
    agg = TelemetryAggregator(
        obs_dir, registry=master_reg, job="dp", interval=1.0
    )
    try:
        def tick(t):
            for role, reg in regs.items():
                sec = reg.get("edl_datapath_seconds_total")
                sec.labels(stage="read").inc(0.2)
                sec.labels(stage="decode").inc(0.1)
                sec.labels(stage="starve").inc(starve_s[role])
                reg.get("edl_datapath_records_total").inc(250)
                reg.get("edl_datapath_queue_depth").labels(
                    queue="prefetch"
                ).set(3)
            regs["worker-0"].get(
                "edl_datapath_backpressure_total"
            ).labels(queue="prefetch").inc()
            agg.poll_once(now=t)

        tick(1000.0)
        tick(1010.0)
        summary = agg.summary()
        dp = summary["datapath"]
        # 5s of starve per 10s wall on worker-0 -> 0.5 share, dominant.
        assert dp["dominant_stage"] == "starve"
        assert dp["starve_shares"]["worker-0"] == pytest.approx(
            0.5, rel=0.05
        )
        assert dp["starve_shares"]["worker-1"] == pytest.approx(
            0.01, rel=0.05
        )
        assert dp["starved"] == ["worker-0"]
        assert set(dp["stages"]) == {"read", "decode", "starve"}
        # 250 records per worker per 10s tick, two workers -> 50/s.
        assert dp["records_per_second"] == pytest.approx(50.0)
        assert dp["queue_depth"]["worker-0/prefetch"] == 3
        assert dp["backpressure_total"] == 2
        json.dumps(summary)  # backs /api/summary
        text = master_reg.expose()
        assert 'edl_job_input_starved{worker="worker-0"} 1' in text
        assert 'edl_job_input_starved{worker="worker-1"} 0' in text
        assert 'edl_job_datapath_stage_share{stage="starve"}' in text
        assert "edl_job_datapath_records_per_second 50" in text
    finally:
        obs_events.set_event_log(None)
        log.close()
        agg.close()
        for exporter in exporters:
            exporter.close()
    events = obs_events.read_events(str(tmp_path / "events.jsonl"))
    assert any(
        e["kind"] == "alert"
        and e.get("rule") == "input_starvation"
        and e.get("subject") == "worker-0"
        for e in events
    ), [e["kind"] for e in events]


# ---------- exporter surface ----------


def test_exporter_head_requests_and_api_summary():
    reg = MetricsRegistry()
    reg.counter("edl_probe_total", "x").inc(1)
    exporter = MetricsExporter(reg, port=0, host="127.0.0.1")
    exporter.summary_provider = lambda: {"job": "j", "ok": True}
    try:
        base = f"http://127.0.0.1:{exporter.port}"
        for path in ("/metrics", "/healthz"):
            req = urllib.request.Request(base + path, method="HEAD")
            res = urllib.request.urlopen(req, timeout=5)
            assert res.status == 200
            assert res.read() == b""  # HEAD: headers only
            assert int(res.headers["Content-Length"]) > 0
        body = urllib.request.urlopen(
            f"{base}/api/summary", timeout=5
        ).read()
        assert json.loads(body) == {"job": "j", "ok": True}
    finally:
        exporter.close()


def test_exporter_summary_absent_without_provider():
    reg = MetricsRegistry()
    exporter = MetricsExporter(reg, port=0, host="127.0.0.1")
    try:
        with pytest.raises(urllib.error.HTTPError) as err:
            urllib.request.urlopen(
                f"http://127.0.0.1:{exporter.port}/api/summary", timeout=5
            )
        assert err.value.code == 404
    finally:
        exporter.close()


def test_exporter_host_env(monkeypatch):
    from elasticdl_tpu.observability.exporter import METRICS_HOST_ENV

    monkeypatch.setenv(METRICS_HOST_ENV, "127.0.0.1")
    exporter = MetricsExporter(MetricsRegistry(), port=0)
    try:
        assert exporter._server.server_address[0] == "127.0.0.1"
    finally:
        exporter.close()


# ---------- dashboard renderer ----------


def test_dashboard_render_synthetic_summary():
    from elasticdl_tpu.observability import dashboard

    summary = {
        "job": "demo",
        "records_per_second": 1234.5,
        "records_done": 9999,
        "throughput_history": [(1, 100.0), (2, 900.0), (3, 1234.5)],
        "workers": {
            "worker-0": {
                "mean": 0.21, "p50": 0.2, "p99": 0.4, "ewma": 0.22,
                "straggler": True, "straggler_score": 8.5, "mfu": 0.31,
            },
            "worker-1": {
                "mean": 0.02, "p50": 0.02, "p99": 0.03, "ewma": 0.02,
                "straggler": False,
            },
        },
        "ps": {
            "ps-0": {
                "push_bytes_per_second": 9e6,
                "pull_bytes_per_second": 1e6,
                "load_ratio": 1.8,
            },
        },
        "tasks": {
            "todo": 10, "doing": 2, "drain_per_second": 1.5,
            "eta_seconds": 8.0, "abandoned": 0, "recovered": 1,
        },
        "alerts": [
            {"rule": "straggler", "subject": "worker-0", "score": 8.5},
        ],
        "alerts_fired": 2,
        "membership_epoch": 3,
    }
    frame = dashboard.render(summary, width=100)
    assert "job demo" in frame
    assert "STRAGGLER" in frame
    assert "worker-0" in frame and "worker-1" in frame
    assert "ps-0" in frame
    assert "straggler" in frame  # the alert line
    assert "mfu=31.0%" in frame
    assert dashboard.sparkline([1, 2, 3]) != ""
    # Empty summary (aggregator warming up) must still render.
    assert "job ?" in dashboard.render({}, width=80)


def test_dashboard_render_datapath_panel():
    from elasticdl_tpu.observability import dashboard

    summary = {
        "job": "demo",
        "datapath": {
            "stages": {"read": 0.04, "decode": 0.02, "starve": 0.51},
            "dominant_stage": "starve",
            "records_per_second": 5000.0,
            "starve_shares": {"worker-0": 0.5, "worker-1": 0.0},
            "starved": ["worker-0"],
            "queue_depth": {"worker-0/prefetch": 3},
            "backpressure_total": 2,
        },
    }
    frame = dashboard.render(summary, width=100)
    assert "data plane" in frame
    assert "slowest stage: starve" in frame
    assert "backpressure=2" in frame
    assert "STARVED" in frame and "worker-0" in frame
    # The healthy worker's zero-share row is suppressed, not rendered.
    assert "worker-1" not in frame
    assert "queue depth: worker-0/prefetch=3" in frame
    # No datapath block (old workers, ELASTICDL_DATAPATH=0): no panel.
    assert "data plane" not in dashboard.render({"job": "x"}, width=100)


# ---------- worker MFU estimator ----------


def test_step_cost_model_records_flops_and_mfu(monkeypatch):
    jax = pytest.importorskip("jax")
    import jax.numpy as jnp

    from elasticdl_tpu.observability import mfu
    from elasticdl_tpu.observability.metrics import default_registry

    monkeypatch.setenv(mfu.MFU_ENV, "1")
    # The CPU has no entry in the peak table; give the test device one
    # so the MFU gauge is exercised end to end.
    monkeypatch.setitem(
        mfu.PEAK_BF16_FLOPS_BY_KIND, jax.devices()[0].device_kind, 1e12
    )
    model = mfu.StepCostModel()
    step = jax.jit(lambda x: (x @ x).sum())
    x = jnp.ones((32, 32))
    reg = default_registry()
    # The analysis runs on a background thread; keep stepping until its
    # result lands on the gauges (the steady-state behavior).
    import time as _time

    deadline = _time.time() + 30
    while _time.time() < deadline:
        model.observe(step, (x,))
        if reg.get("edl_worker_step_flops").value > 0:
            break
        _time.sleep(0.02)
    assert reg.get("edl_worker_step_flops").value > 0
    assert reg.get("edl_worker_mfu").value > 0
    assert reg.get("edl_worker_step_period_seconds").value > 0


def test_step_cost_model_degrades_without_analysis(monkeypatch):
    from elasticdl_tpu.observability import mfu

    monkeypatch.setenv(mfu.MFU_ENV, "1")
    model = mfu.StepCostModel()
    assert model._peak is None  # the CPU is not in the peak table

    class Unlowerable:
        def lower(self, *a, **k):
            raise RuntimeError("no cost analysis on this backend")

    # Never raises; gauges simply stay unset for this shape (a bare
    # float has no .shape, so the spec build fails synchronously).
    model.observe(Unlowerable(), (1.0,))
    model.observe(Unlowerable(), (1.0,))
    assert list(model._flops.values()) == [None]  # cached, no retries


def test_peak_table_is_keyed_by_device_kind_and_unknown_raises():
    from elasticdl_tpu.observability import mfu

    assert mfu.peak_flops("TPU v5 lite") == 197e12
    with pytest.raises(mfu.UnknownDeviceError, match="cpu"):
        mfu.peak_flops("cpu")


def test_step_cost_model_disabled(monkeypatch):
    from elasticdl_tpu.observability import mfu

    monkeypatch.setenv(mfu.MFU_ENV, "0")
    model = mfu.StepCostModel()

    class Exploding:
        def lower(self, *a, **k):
            raise AssertionError("must not lower when disabled")

    model.observe(Exploding(), (1.0,))
    assert model._flops == {}


def test_step_cost_model_auto_gate(monkeypatch):
    """Default 'auto': without a configured observability plane the model
    never lowers (bare trainer unit tests pay nothing); an explicit 1
    forces it on."""
    from elasticdl_tpu import observability
    from elasticdl_tpu.observability import mfu

    monkeypatch.delenv(mfu.MFU_ENV, raising=False)
    # In-process masters elsewhere in the suite may have configured (and
    # later closed) the plane; only assert the gate when it's truly off.
    if observability.current_handle() is None:
        assert mfu.enabled() is False
    monkeypatch.setenv(mfu.MFU_ENV, "0")
    assert mfu.enabled() is False
    monkeypatch.setenv(mfu.MFU_ENV, "1")
    assert mfu.enabled() is True


# ---------- chaos role targeting ----------


def test_fault_rule_role_matching(monkeypatch):
    """Exact-match semantics: role='worker-1' must not also hit
    worker-10..19; a trailing '*' opts into prefix matching."""
    from elasticdl_tpu.chaos.injection import FaultRule

    exact = FaultRule(method="", kind="latency", role="worker-1")
    monkeypatch.setenv("ELASTICDL_ROLE", "worker-1")
    assert exact.matches_role()
    monkeypatch.setenv("ELASTICDL_ROLE", "worker-10")
    assert not exact.matches_role()
    prefix = FaultRule(method="", kind="latency", role="worker-*")
    assert prefix.matches_role()
    monkeypatch.setenv("ELASTICDL_ROLE", "ps-0")
    assert not prefix.matches_role()
    monkeypatch.delenv("ELASTICDL_ROLE", raising=False)
    assert FaultRule(method="", kind="latency").matches_role()
    assert not exact.matches_role()


# ---------- end-to-end straggler drill (chaos lane) ----------


@pytest.mark.chaos
@pytest.mark.slow
def test_scenario_straggler(tmp_path):
    """A real 2w+2PS job with role-targeted latency on worker-0's RPCs:
    the master's aggregated /metrics must expose
    edl_job_straggler{worker="worker-0"} 1, /api/summary must name the
    same worker with nonzero throughput, an alert event must land in
    events.jsonl, `edl dash --once` must render against the live job —
    and the job must still complete with full records_done."""
    import test_module
    from elasticdl_tpu.data.recordfile import RecordFileWriter

    from elastic_drill import run_drill

    records = 256
    num_epochs = 40
    data = str(tmp_path / "linear.edlr")
    with RecordFileWriter(data) as w:
        for r in test_module.make_linear_records(records):
            w.write(r)
    obs_dir = str(tmp_path / "obs")
    result = run_drill(
        data,
        model_zoo=os.path.join(REPO, "tests"),
        model_def="test_module",
        num_workers=2,
        num_ps=2,
        num_epochs=num_epochs,
        scenario="straggler",
        obs_dir=obs_dir,
        env_overrides={
            "JAX_PLATFORMS": "cpu",
            "ELASTICDL_OBS_DIR": obs_dir,
        },
        timeout=420,
    )
    tail = result.get("log_tail", "")[-1500:]
    assert result["completed"], tail
    assert result["leftover_procs"] == [], result["leftover_procs"]
    assert result["records_done"] == records * num_epochs, (
        result["records_done"], tail,
    )
    # The aggregator flagged the slowed worker on the master's /metrics...
    assert result["straggler_flagged"] == "worker-0", result
    # ...and /api/summary names it too, with the job still moving.
    assert "worker-0" in result["summary_stragglers"], result
    assert (result["summary_throughput"] or 0) > 0, result
    # The alert landed in the elasticity event log.
    events = obs_events.read_events(os.path.join(obs_dir, "events.jsonl"))
    assert any(
        e["kind"] == "alert"
        and e.get("rule") == "straggler"
        and e.get("subject") == "worker-0"
        for e in events
    ), [e["kind"] for e in events]
    # The live dashboard rendered against the running job.
    assert result.get("dash_rc") == 0, result.get("dash_snapshot")
    snapshot = result.get("dash_snapshot", "")
    assert "worker-0" in snapshot and "STRAGGLER" in snapshot, snapshot


# ---------- end-to-end input-starvation drill (chaos lane) ----------


@pytest.mark.chaos
@pytest.mark.slow
def test_scenario_input_starve(tmp_path):
    """A real 2w+2PS job with per-record latency injected into
    worker-0's reader (the datapath.read local chaos point): the
    data-plane telemetry must attribute the slowdown — the
    input_starvation alert fires for exactly worker-0 on the master's
    /metrics and /api/summary, the datapath event trail lands in
    events.jsonl, the summary's data-plane block blames the injected
    stage, `edl dash --once --json` returns a machine-readable snapshot
    carrying the block — and the job must still complete with full
    records_done."""
    import test_module
    from elasticdl_tpu.data.recordfile import RecordFileWriter

    from elastic_drill import run_drill

    records = 256
    num_epochs = 40
    data = str(tmp_path / "linear.edlr")
    with RecordFileWriter(data) as w:
        for r in test_module.make_linear_records(records):
            w.write(r)
    obs_dir = str(tmp_path / "obs")
    result = run_drill(
        data,
        model_zoo=os.path.join(REPO, "tests"),
        model_def="test_module",
        num_workers=2,
        num_ps=2,
        num_epochs=num_epochs,
        scenario="input-starve",
        obs_dir=obs_dir,
        env_overrides={
            "JAX_PLATFORMS": "cpu",
            "ELASTICDL_OBS_DIR": obs_dir,
        },
        timeout=420,
    )
    tail = result.get("log_tail", "")[-1500:]
    assert result["completed"], tail
    assert result["leftover_procs"] == [], result["leftover_procs"]
    assert result["records_done"] == records * num_epochs, (
        result["records_done"], tail,
    )
    # The alert named EXACTLY the faulted worker on both surfaces.
    assert result["starved_flagged"] == "worker-0", result
    assert result["starved_workers"] == ["worker-0"], result
    # The attribution blames the injected stage: a slow reader surfaces
    # as producer `read` seconds and consumer `starve` seconds.
    assert result["dominant_stage"] in ("read", "starve"), result
    dp = result["datapath_summary"]
    assert dp["starve_shares"].get("worker-0", 0) > 0, dp
    # The per-task datapath event trail landed in events.jsonl.
    assert result["datapath_event"] is not None, result
    assert result["datapath_event"].get("records"), result
    # The alert event too (rising edge, rule + subject).
    events = obs_events.read_events(os.path.join(obs_dir, "events.jsonl"))
    assert any(
        e["kind"] == "alert"
        and e.get("rule") == "input_starvation"
        and e.get("subject") == "worker-0"
        for e in events
    ), [e["kind"] for e in events]
    # Machine-readable dashboard snapshot against the live job.
    assert result.get("dash_json_rc") == 0, result
    assert result.get("dash_json_has_datapath") is True, result
