"""The host spans against the device's idle gaps: on hand-made spans and
gaps, and on a small recorded trace of the flagship job round a loss
fence (testdata/, from a TPU v5e, PR 25). And the step-done intervals on
hand-made `steps_done` events."""

import gzip
import json
import os

import pytest

import bench_helpers as h
from lib import hostspans, trace

RECORDED = os.path.join(h.BENCH, "testdata", "flagship_fence_trace.json.gz")
LAYERS = h.cell_mod.load_module("metrics", "_host_span_layers")
MS = 1e6  # ns


def span(name, start_ms, end_ms, step=None):
    return [name, start_ms * MS, end_ms * MS, step]


def thread(*spans, line="python"):
    ordered = sorted(spans, key=lambda s: (s[1], -s[2]))
    return {"plane": "/host:CPU", "line": line, "spans": ordered}


def device(*busy_ms):
    """One device's reduction from its busy intervals [(start, end)]."""
    return trace.reduce_device(
        [["op", a * MS, (b - a) * MS] for a, b in busy_ms])


# One worker thread: two steps, a loss fence in the first, the task
# plane between them; the device is busy 10-100 and 130-230.
WORKER = thread(
    span("task_process", 0, 104),
    span("batch_process", 1, 103),
    span("worker.step", 2, 102, step=7),
    span("datapath.decode", 3, 4),
    span("datapath.h2d", 5, 6),
    span("trainer.dispatch", 6, 8),
    span("worker.loss_fence", 10, 101),
    span("worker.report_task", 105, 111),
    span("worker.report_version", 111, 113),
    span("datapath.task", 113, 116),
    span("task_process", 117, 240),
    span("datapath.starve", 117.5, 119.5),
    span("batch_process", 120, 135),
    span("worker.step", 120, 134, step=8),
    span("datapath.decode", 120.5, 121.5),
    span("datapath.h2d", 124, 125),
    span("trainer.dispatch", 126, 131),
)
PRODUCER = thread(span("datapath.read", 99, 129), line="prefetch")
DEVICES = {"/device:TPU:0": device((10, 100), (130, 230))}
# run ids 1 and 2 are the step's program, enqueued inside the dispatches;
# the keys are "<device ordinal>:<run id>" (each device counts its own).
RUNS = {"/device:TPU:0": [[1, 10 * MS, 100 * MS], [2, 130 * MS, 230 * MS]]}
ENQUEUED = {"0:1": 7.5 * MS, "0:2": 128 * MS, "1:1": 7.6 * MS,
            "1:2": 128.1 * MS}


def attribute(devices=DEVICES, lines=(WORKER, PRODUCER), runs=RUNS,
              enqueued=ENQUEUED):
    return hostspans.attribute_loaded(
        devices, list(lines), runs, enqueued, LAYERS.DISPATCH)


def test_flatten_gives_each_instant_to_the_deepest_span():
    flat = hostspans.flatten(WORKER["spans"])
    at = lambda ms: next(  # noqa: E731
        (n, st) for a, b, n, st in flat if a <= ms * MS < b)
    assert at(0.5) == ("task_process", None)
    assert at(1.5) == ("batch_process", None)
    assert at(2.5) == ("worker.step", 7)       # python between the stages
    assert at(3.5) == ("datapath.decode", 7)   # inherits the step's number
    assert at(50) == ("worker.loss_fence", 7)
    assert at(101.5) == ("worker.step", 7)
    assert at(112) == ("worker.report_version", None)
    assert at(127) == ("trainer.dispatch", 8)
    assert not [1 for a, b, _, _ in flat if a <= 116.5 * MS < b]
    # Segments never overlap and are in order.
    assert all(x[1] <= y[0] for x, y in zip(flat, flat[1:]))
    assert sum(b - a for a, b, _, _ in flat) == pytest.approx(
        (104 - 0 + 116 - 105 + 240 - 117) * MS)


def test_the_largest_cover_labels_a_gap_and_the_parts_keep_every_span():
    found, why = attribute()
    assert why is None
    (plane, start, length, label, step, parts), = found["gaps"]
    assert (plane, start, length) == ("/device:TPU:0", 100 * MS, 30 * MS)
    # 100-130: fence 1, step 1, batch 1, task_process 1, nothing 1,
    # report_task 6, report_version 2, task 3, nothing 1, task_process
    # 0.5 + 0.5, starve 2, step 8 alone 0.5 + 2.5 + 1, decode 1, h2d 1,
    # dispatch 4.
    assert label == "worker.report_task" and step is None
    want = {"worker.loss_fence": 1, "worker.step": 5, "batch_process": 1,
            "task_process": 2, "unattributed": 2, "worker.report_task": 6,
            "worker.report_version": 2, "datapath.task": 3,
            "datapath.starve": 2, "datapath.decode": 1, "datapath.h2d": 1,
            "trainer.dispatch": 4}
    assert {k: v / MS for k, v in parts.items()} == pytest.approx(want)
    assert sum(parts.values()) == pytest.approx(length)


def test_a_gap_under_the_step_alone_is_the_steps_not_unattributed():
    devices = {"/device:TPU:0": device((10, 121.6), (123.6, 230))}
    found, _ = attribute(devices=devices)
    (_, _, _, label, step, parts), = found["gaps"]
    assert (label, step) == ("worker.step", 8)
    assert parts == {"worker.step": pytest.approx(2 * MS)}
    assert LAYERS.layer_of("worker.step") == LAYERS.WORKER_LOOP


def test_spans_of_other_threads_are_listed_and_label_nothing():
    found, _ = attribute()
    assert found["other_threads"] == [
        {"line": "prefetch", "spans": {"datapath.read": 1}}]
    assert "datapath.read" not in found["gaps"][0][5]
    # The thread is the one that dispatches, wherever it stands.
    again, _ = attribute(lines=(PRODUCER, WORKER))
    assert again["gaps"] == found["gaps"]
    assert hostspans.thread_of([PRODUCER], LAYERS.DISPATCH) is None
    nothing, why = attribute(lines=(PRODUCER,))
    assert nothing is None and "trainer.dispatch" in why


def test_a_device_line_that_runs_ahead_is_moved_by_what_causality_needs():
    # The device says step 8 started at 130; the host enqueued it at
    # 131.5: the device line is 1.5 ms early, and so is its gap.
    found, why = attribute(enqueued={"0:1": 7.5 * MS, "0:2": 131.5 * MS})
    assert why is None
    assert found["clock_skew_ms"] == {"/device:TPU:0": pytest.approx(1.5)}
    (_, start, length, _, _, parts), = found["gaps"]
    assert (start, length) == (100 * MS, 30 * MS)  # as the device gave it
    assert "worker.loss_fence" not in parts  # 101.5-131.5 now
    assert parts["trainer.dispatch"] == pytest.approx(5 * MS)
    assert parts["worker.step"] == pytest.approx((0.5 + 4 + 0.5) * MS)


@pytest.mark.parametrize("case", ["seconds_apart", "before_its_dispatch",
                                  "dispatches_do_not_pair"])
def test_a_broken_clock_gives_nothing_and_says_why(case):
    if case == "seconds_apart":
        found, why = attribute(enqueued={"0:1": 7.5 * MS, "0:2": 2130 * MS})
        assert "not one clock" in why
    elif case == "before_its_dispatch":
        early = {"/device:TPU:0": [[1, 10 * MS, 100 * MS],
                                   [2, 125 * MS, 225 * MS]]}
        found, why = attribute(runs=early,
                               enqueued={"0:1": 7.5 * MS, "0:2": 124 * MS})
        assert "before its trainer.dispatch span starts" in why
    else:
        found, why = attribute(
            enqueued={**ENQUEUED, "0:3": 229 * MS},
            runs={"/device:TPU:0": RUNS["/device:TPU:0"] + [
                [3, 231 * MS, 331 * MS]]})
        assert "3 executions" in why and "2 trainer.dispatch" in why
    assert found is None


class FakeRun:
    """What the readers touch of a RunView."""

    def __init__(self, events=(), devices=None, t0=0.0, t1=40.0,
                 t_traced=None):
        self.events, self.t0, self.t1 = list(events), t0, t1
        self.t_traced = t_traced
        self.trace = None if devices is None else trace.reduce(
            {plane: {"XLA Ops": dev["events"]}
             for plane, dev in devices.items()})

    def events_of(self, kinds, role_prefix=None, since=None, until=None):
        kinds = (kinds,) if isinstance(kinds, str) else kinds
        return [e for e in self.events if e["kind"] in kinds and str(
            e.get("role", "")).startswith(role_prefix or "")]


def read(name, run):
    return h.cell_mod.load_module("metrics", name).read(run)


IDLE_SHARES = ("idle_task_pct.lm", "idle_input_pct.lm",
               "idle_trainer_pct.lm", "idle_unattributed_pct.lm")


def test_the_four_shares_add_up_to_the_idle_share(monkeypatch, capsys):
    two = {"/device:TPU:0": device((10, 100), (130, 230)),
           "/device:TPU:1": device((10, 99), (131, 228))}
    run = FakeRun(devices=two)
    runs = dict(RUNS, **{"/device:TPU:1": [[1, 10 * MS, 99 * MS],
                                           [2, 131 * MS, 228 * MS]]})
    monkeypatch.setattr(
        hostspans, "attribute",
        lambda r, is_span, dispatch: hostspans.attribute_loaded(
            r.trace["devices"], [WORKER, PRODUCER], runs, ENQUEUED,
            dispatch)[0])
    shares = {name: read(name, run) for name in IDLE_SHARES}
    idle = read("device_idle_pct.lm", run)
    assert idle == pytest.approx(100 * 62 / 438)
    assert sum(shares.values()) == pytest.approx(idle, abs=1e-9)
    # Task plane: report_task 6, report_version 2, task 3 on both chips.
    assert shares["idle_task_pct.lm"] == pytest.approx(100 * 22 / 438)
    assert shares["idle_trainer_pct.lm"] == pytest.approx(
        100 * (4 + 5) / 438)
    assert shares["idle_unattributed_pct.lm"] == pytest.approx(
        100 * 4 / 438)
    # The breakdown's entries keep length and order and get the names.
    assert run.trace["idle_gaps"] == [
        ["worker.report_task", pytest.approx(0.032)],
        ["worker.report_task", pytest.approx(0.030)]]
    line = json.loads(capsys.readouterr().out.strip().splitlines()[-1])
    assert line["reader"] == "idle_by_span"
    assert line["gaps"][0] == ["worker.report_task", pytest.approx(0.032),
                               None]
    assert line["by_span_s"]["worker.report_task"] == pytest.approx(0.006)
    assert sum(line["by_span_s"].values()) == pytest.approx(0.031)


def test_an_older_program_gives_none_and_raises_nothing(capsys):
    """The parent of PR 25 writes no profile_written event and no
    steps_done event: every new reader finds nothing to read."""
    run = FakeRun(devices=DEVICES, events=[
        {"kind": "datapath", "role": "worker-0", "ts": 1.0}])
    for name in IDLE_SHARES + ("step_ms_p50.lm", "step_ms_p90.lm"):
        assert read(name, run) is None
    assert run.trace["idle_gaps"] == [["unattributed", pytest.approx(0.03)]]
    assert "no profile_written event" in capsys.readouterr().out
    untraced = FakeRun()
    for name in IDLE_SHARES:
        assert read(name, untraced) is None


def test_layers_of_the_span_names():
    for name, layer in (
            ("datapath.task", LAYERS.TASK_PLANE),
            ("worker.report_task", LAYERS.TASK_PLANE),
            ("datapath.starve", LAYERS.WORKER_LOOP),
            ("datapath.h2d", LAYERS.WORKER_LOOP),
            ("worker.loss_fence", LAYERS.WORKER_LOOP),
            ("batch_process", LAYERS.WORKER_LOOP),
            ("trainer.dispatch", LAYERS.TRAINER),
            ("trainer.world_check", LAYERS.TRAINER),
            ("unattributed", LAYERS.DEVICE)):
        assert LAYERS.layer_of(name) == layer
        assert name == "unattributed" or LAYERS.is_span(name)
    for other in ("$worker.py:12 run", "PjitFunction(step_fn)", "worker"):
        assert not LAYERS.is_span(other)
    layers = {m["name"]: m["layer"] for m in h.manifest()["per_layer"]}
    assert [layers[n] for n in IDLE_SHARES] == [
        LAYERS.TASK_PLANE, LAYERS.WORKER_LOOP, LAYERS.TRAINER,
        LAYERS.DEVICE]


# ---------- the recorded trace ----------


@pytest.fixture(scope="module")
def recorded():
    with gzip.open(RECORDED, "rt") as f:
        return json.load(f)


def test_recorded_fence_trace_gets_the_labels_read_by_hand(recorded):
    devices = {
        plane: trace.reduce_device(lines["XLA Ops"])
        for plane, lines in recorded["planes"].items()}
    found, why = hostspans.attribute_loaded(
        devices, recorded["host_lines"], recorded["runs"],
        recorded["enqueued"], LAYERS.DISPATCH)
    assert why is None
    skew = found["clock_skew_ms"]["/device:TPU:0"]
    assert skew == pytest.approx(recorded["expect"]["clock_skew_ms"])
    assert 0 < skew < 5
    longest = sorted(found["gaps"], key=lambda g: -g[2])
    got = [[g[3], round(g[2] / 1e6, 3), g[4]]
           for g in longest[:len(recorded["expect"]["gaps"])]]
    assert got == recorded["expect"]["gaps"]
    # Every millisecond of the long gaps lies under a span of the program.
    named = sum(ns for g in longest[:4] for name, ns in g[5].items()
                if name != hostspans.UNATTRIBUTED)
    assert named >= 0.9 * sum(g[2] for g in longest[:4])
    steps = [s for s in hostspans.thread_of(
        recorded["host_lines"], LAYERS.DISPATCH)["spans"]
        if s[0] == "worker.step"]
    assert [s[3] for s in steps] == recorded["expect"]["steps"]


# ---------- the step-done intervals ----------


def done(first_step, stamps, role="worker-0"):
    return {"kind": "steps_done", "role": role, "first_step": first_step,
            "stamps": list(stamps)}


def ticks(first, n, period=0.228, start=0.0):
    return done(first, [start + i * period for i in range(n)])


def test_step_intervals_percentiles_window_edges_and_count(capsys):
    # 31 stamps from t = 10: steps 100..130, one slow step at 120.
    stamps = [10 + 0.228 * i + (0.05 if i >= 20 else 0) for i in range(31)]
    events = [done(100, stamps[:12]), done(112, stamps[12:]),
              done(100, [1.0, 99.0], role="worker-1")]
    run = FakeRun(events=events, t0=0.0, t1=40.0)
    assert read("step_ms_p50.lm", run) == pytest.approx(228.0)
    p90 = read("step_ms_p90.lm", run)
    assert p90 == pytest.approx(228.0)  # 1 slow interval of 30: beyond it
    said = json.loads(capsys.readouterr().out.strip().splitlines()[-1])
    assert said == {"reader": "step_intervals", "count": 30,
                    "longest_ms": pytest.approx(278.0),
                    "longest_at_step": 120}
    # The window cuts both ends: stamps outside it make no interval.
    helper = h.cell_mod.load_module("metrics", "_step_intervals")
    inside = FakeRun(events=events, t0=stamps[3] - 0.001,
                     t1=stamps[27] + 0.001)
    got = helper.intervals(inside)
    assert [s for _, s in got] == list(range(104, 128))
    # A traced run counts from the moment the trace was written.
    traced = FakeRun(events=events, t0=0.0, t1=40.0,
                     t_traced=stamps[8] + 0.001)
    assert [s for _, s in helper.intervals(traced)][0] == 110
    # A step that stamped nothing (it failed) makes no interval either.
    holed = FakeRun(events=[done(100, stamps[:5]), done(106, stamps[6:])])
    assert [s for _, s in helper.intervals(holed)] == (
        [101, 102, 103, 104] + list(range(107, 131)))


@pytest.mark.parametrize("n,expect_none", [(20, True), (21, False)])
def test_fewer_than_twenty_intervals_give_none(n, expect_none):
    run = FakeRun(events=[ticks(1, n, start=1.0)])
    for name in ("step_ms_p50.lm", "step_ms_p90.lm"):
        value = read(name, run)
        assert (value is None) == expect_none
        assert expect_none or value == pytest.approx(228.0)
