"""The worker's host spans: one span primitive that reaches the
profiler's trace while a session is open, the spans a profiled job
writes, and the step-done clock."""

import glob
import json
import os
import subprocess
import sys
import threading
import time

import pytest

import test_module
from elasticdl_tpu.common.constants import JobType
from elasticdl_tpu.common.model_utils import get_model_spec
from elasticdl_tpu.data.reader import InMemoryReader
from elasticdl_tpu.observability import events as obs_events
from elasticdl_tpu.observability import tracing
from elasticdl_tpu.observability.metrics import default_registry
from elasticdl_tpu.worker.master_client import MasterClient
from elasticdl_tpu.worker.step_clock import StepDoneClock
from elasticdl_tpu.worker.worker import Worker

from test_utils import start_master

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


def test_span_without_a_session_constructs_no_annotation_and_no_jax():
    """The master and the PS import observability.tracing: a span there
    must never pull jax in."""
    code = (
        "import sys\n"
        "from elasticdl_tpu.observability import tracing\n"
        "with tracing.span('x', step_num=3) as s:\n"
        "    pass\n"
        "assert s._annotation is None and s.dur >= 0\n"
        "assert tracing._annotations is None\n"
        "assert not [m for m in sys.modules if m == 'jax' or "
        "m.startswith('jax.')], 'jax was imported'\n"
    )
    res = subprocess.run(
        [sys.executable, "-c", code], capture_output=True, text=True,
        cwd=REPO, timeout=120,
    )
    assert res.returncode == 0, res.stderr[-2000:]


class _FakeAnnotation:
    made = []

    def __init__(self, name, **kwargs):
        self.name, self.kwargs, self.open = name, kwargs, None
        _FakeAnnotation.made.append(self)

    def __enter__(self):
        self.open = True

    def __exit__(self, *exc):
        self.open = False


class _FakeStepAnnotation(_FakeAnnotation):
    pass


@pytest.fixture
def fake_session(monkeypatch):
    _FakeAnnotation.made = []
    monkeypatch.setattr(
        tracing, "_annotations", (_FakeAnnotation, _FakeStepAnnotation)
    )
    return _FakeAnnotation.made


def test_span_in_a_session_enters_one_annotation_and_one_clock_pair(
    fake_session,
):
    seen = []
    sink = lambda *a: seen.append(a)  # noqa: E731
    tracing.add_sink(sink)
    try:
        with tracing.span("trainer.dispatch", cat="c", k=1) as s:
            assert fake_session[0].open is True
            time.sleep(0.01)
        with pytest.raises(RuntimeError):
            with tracing.span("worker.step", step_num=7):
                raise RuntimeError("the body's error propagates")
    finally:
        tracing.remove_sink(sink)
    plain, step = fake_session
    assert (plain.name, plain.kwargs, plain.open) == (
        "trainer.dispatch", {}, False)
    assert type(step) is _FakeStepAnnotation and step.open is False
    assert step.kwargs == {"step_num": 7}
    # The sink saw the same clock pair the caller reads off the span.
    assert seen[0] == ("trainer.dispatch", s.start, s.dur, "c", {"k": 1})
    assert s.dur >= 0.01 and seen[1][0] == "worker.step"


def test_a_span_opened_before_the_session_closes_without_annotation(
    monkeypatch,
):
    monkeypatch.setattr(tracing, "_annotations", None)
    sp = tracing.span("worker.step", step_num=1)
    with sp:
        monkeypatch.setattr(
            tracing, "_annotations", (_FakeAnnotation, _FakeStepAnnotation))
    assert sp._annotation is None


def test_datapath_stage_feeds_its_counters_from_the_spans_duration(
    fake_session,
):
    from elasticdl_tpu.observability import datapath

    dp = datapath.Datapath(enabled=True)
    seen = []
    sink = lambda *a: seen.append(a)  # noqa: E731
    tracing.add_sink(sink)
    try:
        with dp.stage("h2d") as st:
            st.records = 3
            time.sleep(0.005)
    finally:
        tracing.remove_sink(sink)
    (name, _, dur, cat, _), = seen
    assert (name, cat) == ("datapath.h2d", "datapath")
    assert fake_session[0].name == "datapath.h2d"
    assert dp._acc == {"h2d": dur} and dp._acc_records == 3


@pytest.mark.parametrize("stage", ["task", "read", "decode", "collate",
                                   "h2d", "starve"])
def test_a_stage_lands_on_the_call_sites_timing_as_input_phase(stage):
    """The trainers pass `timing=` to the h2d stage: the phase a reader
    of `Timing.summary()` buckets under input_wait is `input_<stage>`,
    one sample a stage, and a stage given no Timing writes none."""
    from elasticdl_tpu.common.timing import Timing
    from elasticdl_tpu.observability import datapath

    assert stage in datapath.STAGES and len(datapath.STAGES) == 6
    dp, timing = datapath.Datapath(enabled=True), Timing()
    with dp.stage(stage, timing=timing):
        pass
    with dp.stage(stage):
        pass
    summary = timing.summary()
    assert list(summary) == ["input_" + stage]
    assert summary["input_" + stage]["count"] == 1
    off = Timing()
    with datapath.Datapath(enabled=False).stage(stage, timing=off):
        pass
    assert off.summary() == {}


# ---------- a profiled job ----------

NESTED_IN_STEP = ("datapath.decode", "trainer.world_check", "datapath.h2d",
                  "trainer.dispatch", "worker.loss_fence")
OUTSIDE_STEP = ("datapath.task", "worker.report_task", "datapath.read",
                "task_process", "batch_process")


def _host_lines(path):
    from jax.profiler import ProfileData

    lines = []
    for plane in ProfileData.from_file(path).planes:
        if plane.name.startswith("/host:"):
            for line in plane.lines:
                lines.append([
                    (e.name, e.start_ns, e.start_ns + e.duration_ns,
                     dict(e.stats))
                    for e in line.events if not e.name.startswith("$")])
    return lines


def test_a_profiled_job_writes_every_span_into_the_profilers_host_plane(
    tmp_path,
):
    """Two traced steps of a toy AllReduce job on the CPU backend: the
    host plane of the .xplane.pb holds every span of the table in
    docs/OBSERVABILITY.md, nested as the table says, and the worker
    says where it wrote the trace."""
    from elasticdl_tpu.worker.allreduce_trainer import AllReduceTrainer

    records = test_module.make_linear_records(96)
    reader = InMemoryReader(records)
    profile_dir = str(tmp_path / "prof")
    log = obs_events.EventLog(str(tmp_path / "events.jsonl"), job="j")
    obs_events.set_event_log(log)
    try:
        with start_master(
            training_shards=reader.create_shards(), records_per_task=16,
            with_membership=True,  # one step a task
        ) as m:
            mc = MasterClient(m["addr"], 0, worker_host="127.0.0.1:0")
            trainer = AllReduceTrainer(
                test_module.custom_model(), test_module.loss,
                test_module.optimizer(), mc, steps_per_world_check=2,
            )
            try:
                Worker(
                    0, mc, reader, get_model_spec("test_module"), trainer,
                    minibatch_size=16, job_type=JobType.TRAINING_ONLY,
                    log_loss_steps=2, profile_dir=profile_dir,
                    profile_start_step=3, profile_steps=2,
                ).run()
            finally:
                trainer.close()
    finally:
        obs_events.set_event_log(None)
        log.close()
    assert tracing._annotations is None  # the session flag was cleared
    events = obs_events.read_events(str(tmp_path / "events.jsonl"))
    written, = [e for e in events if e["kind"] == "profile_written"]
    assert written["dir"] == profile_dir
    assert (written["first_step"], written["last_step"]) == (3, 4)
    assert written["t_start"] < written["t_stop"] <= written["ts"]
    path, = glob.glob(
        os.path.join(written["dir"], "**", "*.xplane.pb"), recursive=True)
    main, = [line for line in _host_lines(path)
             if any(e[0] == "trainer.dispatch" for e in line)]
    names = [e[0] for e in main]
    for name in NESTED_IN_STEP + OUTSIDE_STEP + ("worker.step",):
        assert name in names, f"{name} is not in the host plane: {names}"
    steps = [e for e in main if e[0] == "worker.step"]
    assert [e[3]["step_num"] for e in steps] == [3, 4]
    for name, start, end, _ in main:
        inside = any(s[1] <= start and end <= s[2] for s in steps)
        if name in NESTED_IN_STEP:
            assert inside, f"{name} lies outside every worker.step"
        elif name in OUTSIDE_STEP:
            assert not inside, f"{name} lies inside a worker.step"
    def order(step):
        return [e[0] for e in main
                if step[1] <= e[1] and e[2] <= step[2]
                and e[0] in NESTED_IN_STEP]

    # Step 2 was a logged one: its loss is read in step 3, once step 3
    # is dispatched behind it.
    assert order(steps[0]) == ["datapath.decode", "datapath.h2d",
                               "trainer.dispatch", "worker.loss_fence"]
    # Step 4 is a sync step (the world check before the dispatch; a
    # world of one does not wait for the device after it) and a logged
    # one, read in step 5, after the trace.
    assert order(steps[1]) == ["datapath.decode", "trainer.world_check",
                               "datapath.h2d", "trainer.dispatch"]


# ---------- the step-done clock ----------


class _FakeLoss:
    def __init__(self, fail=False):
        self.ready = threading.Event()
        self.fail = fail

    def block_until_ready(self):
        assert self.ready.wait(10)
        if self.fail:
            raise RuntimeError("the step failed on the device")
        return self


def _steps_done(path):
    return [e for e in obs_events.read_events(path)
            if e["kind"] == "steps_done"]


def test_step_done_clock_stamps_each_step_when_its_loss_is_ready(tmp_path):
    path = str(tmp_path / "events.jsonl")
    log = obs_events.EventLog(path, job="j")
    obs_events.set_event_log(log)
    counter = default_registry().get("edl_worker_steps_done_total")
    before = counter.value
    clock = StepDoneClock(emit_interval=0.0)
    try:
        losses = [_FakeLoss() for _ in range(4)] + [_FakeLoss(fail=True),
                                                    _FakeLoss(), 0.25]
        for step, loss in enumerate(losses, start=5):
            clock.dispatched(step, loss)
        thread = clock._thread
        assert thread.daemon and thread.is_alive()
        time.sleep(0.05)
        assert counter.value == before  # none before the array is ready
        released = []
        for loss in losses[:-1]:
            released.append(time.time())
            loss.ready.set()
            time.sleep(0.01)
        clock.close(timeout=10)
        assert not thread.is_alive()  # the thread ends at shutdown
    finally:
        obs_events.set_event_log(None)
        log.close()
    # One stamp a step, none for the failed step 9; a plain float (a
    # trainer that returns no array) is stamped as it comes.
    assert counter.value == before + 6
    got = {}
    for e in _steps_done(path):
        for i, ts in enumerate(e["stamps"]):
            got[e["first_step"] + i] = ts
    assert sorted(got) == [5, 6, 7, 8, 10, 11]
    stamps = [got[s] for s in sorted(got)]
    assert stamps == sorted(stamps)
    for step, t_release in zip((5, 6, 7, 8), released):
        assert got[step] >= t_release - 1e-3


def test_step_done_clock_writes_at_most_one_event_an_interval(tmp_path):
    path = str(tmp_path / "events.jsonl")
    log = obs_events.EventLog(path, job="j")
    obs_events.set_event_log(log)
    clock = StepDoneClock(emit_interval=3600.0)
    try:
        for step in range(1, 41):
            clock.dispatched(step, 0.0)
        clock.close(timeout=10)
        clock.close(timeout=10)  # closing twice is harmless
    finally:
        obs_events.set_event_log(None)
        log.close()
    events = _steps_done(path)
    # The first stamp goes out at once, the rest at the close.
    assert [(e["first_step"], len(e["stamps"])) for e in events] == [
        (1, 1), (2, 39)]


def test_the_new_counter_passes_the_metric_name_check():
    res = subprocess.run(
        [sys.executable, "-m", "tools.edl_lint", "--rule", "metric-names"],
        capture_output=True, text=True, cwd=REPO, timeout=300,
    )
    assert res.returncode == 0, res.stdout[-2000:] + res.stderr[-2000:]
    with open(os.path.join(
            REPO, "elasticdl_tpu", "worker", "step_clock.py")) as f:
        assert '"edl_worker_steps_done_total"' in f.read()
