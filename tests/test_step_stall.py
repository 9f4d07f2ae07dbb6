"""The step-done clock as the watcher of the step path (the drought and
slow rules, the record of a stall), and the watchdog's record of a slow
firing."""

import json
import logging
import threading
import time
import types

import pytest

from elasticdl_tpu.master.task_dispatcher import TaskDispatcher
from elasticdl_tpu.observability import events as obs_events
from elasticdl_tpu.observability import flightrec, profiling
from elasticdl_tpu.observability.metrics import default_registry
from elasticdl_tpu.worker import step_clock
from elasticdl_tpu.worker.step_clock import StepDoneClock


class _Loss:
    """A stand-in for a step's lazy loss: ready when the test says."""

    def __init__(self):
        self.ready = threading.Event()

    def block_until_ready(self):
        assert self.ready.wait(20), "the test never released this step"


@pytest.fixture
def event_log(tmp_path):
    path = str(tmp_path / "events.jsonl")
    log = obs_events.EventLog(path, job="j", role="worker-0")
    obs_events.set_event_log(log)
    yield lambda kind: [
        e for e in obs_events.read_events(path) if e["kind"] == kind]
    obs_events.set_event_log(None)
    log.close()


def _stalls(cause):
    reg = default_registry()
    return (
        reg.get("edl_worker_step_stalls_total").labels(cause=cause).value,
        reg.get("edl_worker_step_stall_seconds_total").labels(
            cause=cause).value,
    )


def _stamped_steps(read):
    return [e["first_step"] + i for e in read("steps_done")
            for i in range(len(e["stamps"]))]


def _as_dispatcher(body):
    """Run `body` on a thread named as a worker's main loop would be
    found: whoever calls `dispatched` first is the dispatching thread."""
    thread = threading.Thread(target=body, name="edl-test-dispatcher")
    thread.start()
    thread.join(30)
    assert not thread.is_alive()


def _held_up_here(seconds):
    time.sleep(seconds)


def _steps_done():
    return default_registry().get("edl_worker_steps_done_total").value


def _until_stamped(more, since, within=20.0):
    """Wait until the clock has stamped `more` steps past `since` (a
    reading of `_steps_done`). A dispatcher that sleeps only after this
    measures its drought from the stamp, as the clock does, and not from
    a hand-over the clock's thread may wake to late."""
    deadline = time.monotonic() + within
    while _steps_done() < since + more:
        assert time.monotonic() < deadline, "the clock stamped no step"
        time.sleep(0.001)


class _DeviceTime:
    """Stands in for the clock module's `time`: `time()` is where the
    newest finished step of the test's device put it, so the intervals
    the slow rule sees are the test's own, whatever the scheduler does to
    the threads. Waits (`monotonic`) stay real."""

    monotonic = staticmethod(time.monotonic)

    def __init__(self):
        self.now = 1000.0

    def time(self):
        return self.now


class _TimedLoss(_Loss):
    """Done at `done_at` on the device's time; the thread that waited
    for it gets to run `stamp_late` after that."""

    def __init__(self, device, done_at, stamp_late=0.0):
        super().__init__()
        self._device, self._at = device, done_at + stamp_late

    def block_until_ready(self):
        super().block_until_ready()
        self._device.now = self._at


def _device_steps(monkeypatch, intervals, late=()):
    """One loss a step on a device time of the test's own, which the
    clock module reads from here on: step i is done `intervals[i]` after
    the step before it; the stamps of the steps in `late` come 0.06 s
    after that."""
    device = _DeviceTime()
    monkeypatch.setattr(step_clock, "time", device)
    at, losses = device.now, []
    for i, interval in enumerate(intervals):
        at += interval
        losses.append(_TimedLoss(device, at, 0.06 if i in late else 0.0))
    return losses


def _queued_then_done(clock, losses):
    """The whole run queued on the device before its first step is done:
    the clock never finds the queue empty behind a stamped step."""
    def body():
        for step, loss in enumerate(losses, start=1):
            clock.dispatched(step, loss)
        for loss in losses:
            loss.ready.set()

    _as_dispatcher(body)
    clock.close()


def test_ordinary_gaps_write_no_stall(event_log):
    before = [_stalls(c) for c in ("dry", "slow", "late_stamp", "profile")]
    clock = StepDoneClock(emit_interval=0.0)

    def body():
        # A host-bound loop: every step finds the queue behind it empty,
        # for far less than the rule; then a device-bound one, the queue
        # kept two ahead.
        for step in range(1, 31):
            clock.dispatched(step, 0.25)
            time.sleep(0.004)
        losses = [_Loss() for _ in range(20)]
        clock.dispatched(31, losses[0])
        for i in range(1, 20):
            clock.dispatched(31 + i, losses[i])
            time.sleep(0.004)
            losses[i - 1].ready.set()
        losses[-1].ready.set()

    _as_dispatcher(body)
    clock.close()
    assert event_log("step_stall") == []
    assert [_stalls(c) for c in (
        "dry", "slow", "late_stamp", "profile")] == before
    assert _stamped_steps(event_log) == list(range(1, 51))


def test_a_drought_over_the_rule_writes_one_stall_with_the_dispatchers_stack(
        event_log, tmp_path):
    rec = flightrec.install("worker-0", capacity=64, dump_dir=str(tmp_path),
                            arm_signals=False)
    count, seconds = _stalls("dry")
    clock = StepDoneClock(emit_interval=0.0)

    def body():
        for step in range(1, 13):
            clock.dispatched(step, 0.25)
            time.sleep(0.01)
        rec.on_span("trainer.dispatch", time.time(), 0.001, "edl", None)
        with profiling.open_compile("speculative_compile"):
            _held_up_here(0.35)
            clock.dispatched(13, 0.25)
            time.sleep(0.02)
        for step in range(14, 18):
            clock.dispatched(step, 0.25)
            time.sleep(0.01)

    try:
        _as_dispatcher(body)
        clock.close()
    finally:
        flightrec.uninstall()
    (stall,) = event_log("step_stall")
    assert stall["step"] == 13 and stall["cause"] == "dry"
    assert stall["role"] == "worker-0"
    assert 0.3 <= stall["dry_s"] <= stall["interval_s"] + 1e-3 < 1.0
    assert 0.005 < stall["median_s"] < 0.05
    # The clock's own timed waits came back on time: the process was not
    # frozen, the dispatcher was held up.
    assert 0.0 <= stall["wake_late_s"] < 0.05
    first = stall["samples"][0]
    assert 0.1 <= first["at_s"] < 0.2
    (dispatcher,) = [t for t in first["threads"] if t["dispatcher"]]
    assert dispatcher["thread"] == "edl-test-dispatcher"
    assert any("_held_up_here" in f and "test_step_stall.py:" in f
               for f in dispatcher["frames"])
    assert all(t["thread"] != "edl-step-done" for t in first["threads"])
    # Sampled again while it lasted (0.1 and 0.3 s), the dispatching
    # thread alone.
    assert len(stall["samples"]) == 2
    assert [t["dispatcher"] for t in stall["samples"][1]["threads"]] == [
        True]
    assert stall["recent_spans"][-1]["name"] == "trainer.dispatch"
    assert [c["name"] for c in stall["open_compiles"]] == [
        "speculative_compile"]
    assert len(stall["gc_collections"]) == 3
    after = _stalls("dry")
    assert after[0] == count + 1
    assert after[1] - seconds == pytest.approx(stall["dry_s"], abs=1e-3)
    assert _stamped_steps(event_log) == list(range(1, 18))


def test_a_slow_device_with_work_queued_writes_a_stall_without_stacks(
        event_log, monkeypatch):
    count, seconds = _stalls("slow")
    clock = StepDoneClock(emit_interval=0.0)
    _queued_then_done(clock, _device_steps(
        monkeypatch, [0.15 if i == 12 else 0.01 for i in range(16)]))
    (stall,) = event_log("step_stall")
    assert stall["step"] == 13 and stall["cause"] == "slow"
    assert stall["dry_s"] == 0.0 and "samples" not in stall
    assert "wake_late_s" not in stall
    assert stall["interval_s"] == pytest.approx(0.15, abs=1e-5)
    assert stall["median_s"] == pytest.approx(0.01, abs=1e-5)
    # The step after it came a step later, not sooner: the device was
    # slow, the stamp was not late.
    assert stall["next_interval_s"] == pytest.approx(0.01, abs=1e-5)
    after = _stalls("slow")
    assert after[0] == count + 1
    assert after[1] - seconds == pytest.approx(
        stall["interval_s"] - stall["median_s"], abs=1e-3)
    assert _stamped_steps(event_log) == list(range(1, 17))


def test_a_late_stamp_is_told_from_a_slow_step_by_the_step_after_it(
        event_log, monkeypatch):
    """Step 13 is ready on time, but the waiting thread is kept from
    running for 60 ms after it (a held GIL): the stamp is late, the step
    was not, and step 14's stamp comes that much sooner."""
    slow, late = _stalls("slow")[0], _stalls("late_stamp")[0]
    clock = StepDoneClock(emit_interval=0.0)
    # The device: a step every 80 ms, on time.
    _queued_then_done(clock, _device_steps(
        monkeypatch, [0.08] * 16, late={12}))
    (stall,) = event_log("step_stall")
    assert stall["step"] == 13 and stall["cause"] == "late_stamp"
    assert stall["interval_s"] == pytest.approx(0.14, abs=1e-5)
    assert stall["interval_s"] > 1.5 * stall["median_s"]
    assert stall["interval_s"] + stall["next_interval_s"] == pytest.approx(
        2 * stall["median_s"], abs=1e-5)
    assert _stalls("late_stamp")[0] == late + 1
    assert _stalls("slow")[0] == slow
    assert _stamped_steps(event_log) == list(range(1, 17))


def test_a_step_that_does_not_come_has_every_threads_stack_dumped(
        event_log, monkeypatch):
    """faulthandler's watchdog needs no GIL: it sees what no Python
    thread can sample while one of them holds it."""
    monkeypatch.setattr(step_clock, "FROZEN_RULE_SECONDS", 0.1)
    clock = StepDoneClock(emit_interval=0.0)
    losses = [_Loss() for _ in range(14)]

    def body():
        for step, loss in enumerate(losses, start=1):
            clock.dispatched(step, loss)
        for loss in losses[:12]:
            time.sleep(0.01)
            loss.ready.set()
        _held_up_here(0.3)
        for loss in losses[12:]:
            loss.ready.set()
            time.sleep(0.01)

    _as_dispatcher(body)
    clock.close()
    # A 10 ms sleep that the scheduler stretches past the slow rule's
    # 50 ms writes a record of its own step: this test reads step 13's.
    (stall,) = [e for e in event_log("step_stall") if e["step"] == 13]
    assert stall["cause"] == "slow"
    dumped = stall["frozen_stacks"]
    assert dumped[0].startswith("Timeout (0:00:00.1")
    heads = [x for x in dumped if x.startswith(("Thread", "Current"))]
    (dispatcher,) = [x for x in heads if x.endswith("(dispatcher)")]
    assert "edl-test-dispatcher" in dispatcher
    at = dumped.index(dispatcher)
    assert "_held_up_here" in dumped[at + 1] + dumped[at + 2]
    assert any("edl-step-done" in x for x in heads)
    assert _stamped_steps(event_log) == list(range(1, 15))


def test_a_slow_last_step_is_written_at_the_close(event_log, monkeypatch):
    clock = StepDoneClock(emit_interval=0.0)
    _queued_then_done(clock, _device_steps(
        monkeypatch, [0.15 if i == 11 else 0.01 for i in range(12)]))
    (stall,) = event_log("step_stall")
    assert (stall["step"], stall["cause"]) == (12, "slow")
    assert stall["next_interval_s"] is None


def test_the_first_steps_and_a_broken_run_reckon_no_interval(event_log):
    clock = StepDoneClock(emit_interval=0.0)

    since = _steps_done()

    def body():
        clock.dispatched(1, 0.25)
        _until_stamped(1, since)
        time.sleep(0.5)  # a compile: no median yet, but a drought
        clock.dispatched(2, 0.25)
        time.sleep(0.02)
        clock.dispatched(7, 0.25)  # not the next step: no interval
        time.sleep(0.02)

    _as_dispatcher(body)
    clock.close()
    (stall,) = event_log("step_stall")
    assert stall["step"] == 2 and stall["median_s"] is None
    assert _stamped_steps(event_log) == [1, 2, 7]


def test_stalls_past_the_cap_are_counted_and_not_written(
        event_log, monkeypatch):
    """Every step but the first comes after a drought ten times the
    rule, reckoned from the stamp of the step before it. Should the
    clock's thread lose its turn for all of that between a stamp and its
    look at the queue, that one drought goes unseen: so what is held is
    the cap, the order and the count, with five droughts to spare, and
    not that each step has a record."""
    monkeypatch.setattr(step_clock, "DRY_RULE_SECONDS", 0.005)
    count, _ = _stalls("dry")
    clock = StepDoneClock(emit_interval=3600.0)
    cap = step_clock.MAX_STALL_EVENTS
    total = cap + 5
    since = _steps_done()

    def body():
        clock.dispatched(1, 0.25)
        for step in range(2, total + 2):
            _until_stamped(step - 1, since)
            time.sleep(0.05)
            clock.dispatched(step, 0.25)

    _as_dispatcher(body)
    clock.close()
    written = event_log("step_stall")
    steps = [e["step"] for e in written]
    assert len(written) == cap and {e["cause"] for e in written} == {"dry"}
    assert steps == sorted(set(steps)) and steps[0] >= 2
    # The first `cap` droughts seen are the ones written, in order; the
    # ones after them are counted and not written.
    counted = _stalls("dry")[0] - count
    assert cap < counted <= total
    assert steps[-1] <= total + 1 - (counted - cap)
    assert _stamped_steps(event_log) == list(range(1, total + 2))


def test_a_stall_across_a_profile_call_is_marked_profile(event_log):
    dry, prof = _stalls("dry")[0], _stalls("profile")[0]
    clock = StepDoneClock(emit_interval=0.0)

    def body():
        for step in range(1, 11):
            clock.dispatched(step, 0.25)
            time.sleep(0.01)
        with clock.profile_call():  # jax.profiler.start_trace / stop_trace
            time.sleep(0.25)
        clock.dispatched(11, 0.25)
        time.sleep(0.02)
        clock.dispatched(12, 0.25)
        time.sleep(0.25)  # and one that is the host's own
        clock.dispatched(13, 0.25)

    _as_dispatcher(body)
    clock.close()
    first, second = event_log("step_stall")
    assert (first["step"], first["cause"]) == (11, "profile")
    assert first["dry_s"] >= 0.2 and first["samples"]
    assert (second["step"], second["cause"]) == (13, "dry")
    assert _stalls("profile")[0] == prof + 1
    assert _stalls("dry")[0] == dry + 1


def test_the_stall_counters_pass_the_metric_name_check_source():
    # The lint's own run is in tests/test_host_spans.py; here: the names
    # the benchmark's reader and the docs rely on.
    reg = default_registry()
    for name in ("edl_worker_step_stalls_total",
                 "edl_worker_step_stall_seconds_total",
                 "edl_setup_phase_seconds"):
        assert reg.get(name) is not None
    assert 'edl_worker_step_stalls_total{cause="dry"}' in reg.expose()


# ---------- the watchdog's record of a slow firing ----------


def _dispatcher_with_mean(seconds_each=0.01, done=6):
    task_d = TaskDispatcher(
        training_shards={"f": (0, 100)}, records_per_task=10, num_epochs=1,
        shuffle=False)
    for _ in range(done):
        tid, _ = task_d.get(1)
        time.sleep(seconds_each)
        task_d.report(tid, True)
    return task_d


def test_the_slow_rule_says_what_it_saw():
    task_d = _dispatcher_with_mean()
    assert task_d.doing_tasks_over_timeout() == {}
    tid, task = task_d.get(7)
    assert task_d.doing_tasks_over_timeout() == {}  # young
    wid, task, start = task_d._doing[tid]
    task_d._doing[tid] = (wid, task, start - 5.0)
    seen = task_d.doing_tasks_over_timeout()
    assert set(seen) == {7}
    rec = seen[7]
    assert set(rec) == {"task_id", "task_type", "age_s", "mean_s",
                        "samples", "threshold_s"}
    assert rec["task_id"] == tid and rec["task_type"] == "TRAINING"
    assert rec["samples"] == 6 and 0.005 < rec["mean_s"] < 0.5
    # The rule itself: 3 x the mean, floor 1e-3 s.
    assert rec["threshold_s"] == pytest.approx(
        3.0 * max(rec["mean_s"], 1e-3), rel=1e-3)
    assert 5.0 <= rec["age_s"] < 6.0 and rec["age_s"] > rec["threshold_s"]
    # Fewer samples than the rule asks for: it does not fire.
    assert task_d.doing_tasks_over_timeout(min_samples=7) == {}


class _Lines(logging.Handler):
    def __init__(self):
        super().__init__(logging.WARNING)
        self.lines = []

    def emit(self, record):
        self.lines.append(record.getMessage())


def test_the_watchdog_puts_the_rules_record_into_event_and_log(event_log):
    from elasticdl_tpu.master import master as master_mod

    Master = master_mod.Master

    task_d = _dispatcher_with_mean()
    tid, _ = task_d.get(7)
    wid, task, start = task_d._doing[tid]
    task_d._doing[tid] = (wid, task, start - 5.0)
    forgotten = []
    master = types.SimpleNamespace(
        task_d=task_d,
        args=types.SimpleNamespace(worker_liveness_timeout_seconds=3600.0),
        servicer=types.SimpleNamespace(
            snapshot_liveness=lambda: {7: time.time(), 8: 0.0},
            forget_worker=forgotten.append),
        membership=None,
    )
    heard = _Lines()
    master_mod.logger.addHandler(heard)
    try:
        Master._run_watchdog(master)
    finally:
        master_mod.logger.removeHandler(heard)
    by_worker = {e["worker"]: e for e in event_log("task_timeout")}
    assert set(by_worker) == {7, 8} and sorted(forgotten) == [7, 8]
    slow = by_worker[7]
    assert slow["reason"] == "slow" and slow["task_id"] == tid
    assert slow["task_type"] == "TRAINING" and slow["samples"] == 6
    # Both sides are rounded to 1e-6: 3 x 5e-7 on the mean, 5e-7 on the
    # threshold.
    assert slow["age_s"] > slow["threshold_s"] >= 3 * slow["mean_s"] - 3e-6
    # The silent rule has no task to name.
    assert by_worker[8]["reason"] == "silent" and "age_s" not in by_worker[8]
    line = next(x for x in heard.lines
                if x.startswith("Watchdog:") and "slow worker 7" in x)
    for key in ("task_id", "task_type", "age_s", "mean_s", "samples",
                "threshold_s"):
        assert f" {key}={slow[key]}" in line
    assert task_d._doing == {} or 7 not in {
        w for w, _, _ in task_d._doing.values()}
    json.dumps(slow)  # plain fields
