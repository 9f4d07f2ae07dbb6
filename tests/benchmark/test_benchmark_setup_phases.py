"""The readers of the run outside the traced window: the set-up phases
(`setup_phase` events) and the stalls (`step_stall` events), on one toy
job through `edl train --instance_backend local_process` and on
hand-made event lists."""

import os
import shutil
import tempfile
import time

import pytest

import bench_helpers as h
from lib import job as job_mod
from lib import measure
from lib import view as view_mod

SETUP_READERS = ("master_up_s", "worker_boot_s", "chip_open_s",
                 "model_init_s", "world_init_s", "warmup_s",
                 "setup_unnamed_s")
NINE = SETUP_READERS + ("host_stall_pct.lm", "step_ms_max.lm")
# Span -> the span it must lie inside (same role).
PARENTS = {
    "setup.model_spec@master": "setup.master",
    "setup.task_create@master": "setup.master",
    "setup.snapshot_state@worker-0": "setup.world_init",
    "setup.place_variables@worker-0": "setup.world_init",
    "setup.place_opt_state@worker-0": "setup.world_init",
}
ONCE_A_LIFE = {
    "master": {"setup.client", "setup.master", "setup.model_spec",
               "setup.task_create", "setup.spawn"},
    "worker-0": {"setup.imports", "setup.open_devices", "setup.model_spec",
                 "setup.build_trainer", "setup.first_task",
                 "setup.model_init", "setup.world_init",
                 "setup.snapshot_state", "setup.place_variables",
                 "setup.place_opt_state", "setup.first_dispatch"},
}


def read(name, run):
    return h.cell_mod.load_module("metrics", name).read(run)


@pytest.fixture(scope="module")
def toy_run():
    """One toy LM job, launched, warmed up, measured and stopped as
    run.py does it."""
    t_start = time.time()
    cell = h.tiny_cell("steady")
    run_mod = h.run_module()
    workdir = tempfile.mkdtemp(prefix="edlbench_phases_")
    job = None
    try:
        datagen = h.cell_mod.load_module("datagen", cell.config["datagen"])
        data_path = os.path.join(workdir, "train.edlr")
        datagen.write_records(
            data_path, run_mod.planned_records(cell.traffic, 3.0), 11,
            cell.config["data"])
        job = job_mod.Job(
            h.REPO, workdir, run_mod.train_args(cell, data_path, 11, None),
            dict(cell.traffic["env"]))
        measured = measure.measure(job, cell.traffic, 3.0)
        job.stop()
        yield view_mod.RunView(
            cell, 11, 3.0, t_start, job.t_launch, measured, job.events(),
            job.log_text())
    finally:
        if job is not None:
            job.stop()
        shutil.rmtree(workdir, ignore_errors=True)


def _phases(run):
    out = {}
    for e in run.events_of("setup_phase"):
        out.setdefault(e["role"], []).append(e)
    return out


def test_every_setup_phase_is_written_once_a_process_life(toy_run):
    by_role = _phases(toy_run)
    assert set(by_role) == {"master", "worker-0"}
    for role, want in ONCE_A_LIFE.items():
        names = [e["name"] for e in by_role[role]]
        # The native library may or may not need building here.
        names = [n for n in names if n != "setup.native_build"]
        assert sorted(names) == sorted(want), role
    for events in by_role.values():
        for e in events:
            assert e["seconds"] >= 0 and e["start"] > toy_run.t_launch - 1
            # The event is written when the span closes.
            assert e["ts"] >= e["start"] + e["seconds"] - 1e-3
    spawn = next(e for e in by_role["master"] if e["name"] == "setup.spawn")
    assert spawn["instance"] == "worker-0"
    world = next(e for e in by_role["worker-0"]
                 if e["name"] == "setup.world_init")
    assert world["epoch"] >= 1
    first = next(e for e in by_role["worker-0"]
                 if e["name"] == "setup.first_dispatch")
    assert first["fn"] == "allreduce_step"


def test_children_lie_inside_their_parents_and_the_union_inside_the_wall(
        toy_run):
    spans = {f"{e['name']}@{e['role']}": (
        e["start"], e["start"] + e["seconds"])
        for e in toy_run.events_of("setup_phase")}
    for child, parent in PARENTS.items():
        role = child.split("@")[1]
        (a, b), (pa, pb) = spans[child], spans[f"{parent}@{role}"]
        assert pa - 1e-3 <= a <= b <= pb + 1e-3, (child, parent)
    # The step's compile event is the child of the first dispatch.
    a, b = spans["setup.first_dispatch@worker-0"]
    (compiled,) = [e for e in toy_run.events_of(
        ("compile", "compile_cache_hit"), "worker")
        if e["fn"] == "allreduce_step"]
    assert a <= compiled["ts"] <= b + 0.05
    phases = h.cell_mod.load_module("metrics", "_setup_phases")
    worker = [v for k, v in spans.items() if k.endswith("@worker-0")]
    covered = sum(e - s for s, e in phases.union(worker))
    launched = toy_run.events_of("pod_launch")[0]["ts"]
    first_done = toy_run.events_of("steps_done", "worker")[0]["stamps"][0]
    assert 0 < covered <= first_done - launched
    # ... and most of that wall is under a span.
    assert covered >= 0.8 * (first_done - launched)


def test_the_setup_readers_add_up_on_the_toy_run(toy_run):
    got = {name: read(name, toy_run) for name in NINE}
    assert all(v is not None for v in got.values()), got
    assert all(got[name] >= 0 for name in NINE)
    launch = read("launch_s", toy_run)
    assert abs(got["master_up_s"] + got["worker_boot_s"]
               + got["chip_open_s"] - launch) < 0.5
    whole = toy_run.t0 - toy_run.t_launch
    assert got["setup_unnamed_s"] < 0.15 * whole
    named = sum(got[n] for n in SETUP_READERS)
    # Readers overlap nothing, and miss only the first task's fetch and
    # the step's load.
    load = read("step_load_s", toy_run)
    assert named <= whole + 1e-6 and named + load + 2.0 > whole
    assert got["warmup_s"] < whole / 2
    # A clean toy run: no drought, and the longest step is a step.
    assert got["host_stall_pct.lm"] == 0.0
    assert got["step_ms_max.lm"] >= read("step_ms_p90.lm", toy_run)
    assert toy_run.worker_series[
        'edl_setup_phase_seconds{phase="setup.open_devices"}'
    ] == pytest.approx(got["chip_open_s"], abs=1e-3)


# ---------- hand-made event lists ----------

T = 1_000_000.0


def _phase(name, start, seconds, role="worker-0", **more):
    return {"kind": "setup_phase", "role": role, "name": name,
            "start": T + start, "seconds": seconds,
            "ts": T + start + seconds, **more}


def _view(events, series=None, t_traced=None):
    measured = {"t0": T + 40.0, "t1": T + 80.0, "samples": [], "last": {},
                "worker_series": series or {}}
    if t_traced is not None:
        measured["t_traced"] = t_traced
    events = sorted(events, key=lambda e: e["ts"])
    return view_mod.RunView(
        h.tiny_cell("steady"), 1, 40.0, T - 2.0, T, measured, events, "")


GAPLESS = [
    _phase("setup.client", 0.0, 1.0, "master"),
    _phase("setup.master", 1.0, 3.0, "master"),
    _phase("setup.model_spec", 1.5, 2.0, "master"),
    _phase("setup.spawn", 4.0, 0.5, "master", instance="worker-0"),
    _phase("setup.imports", 4.5, 1.5),
    _phase("setup.open_devices", 6.0, 10.0),
    _phase("setup.model_spec", 16.0, 1.0),
    _phase("setup.build_trainer", 17.0, 1.0),
    _phase("setup.first_task", 18.0, 0.5),
    _phase("setup.model_init", 18.5, 3.5),
    _phase("setup.world_init", 22.0, 4.0, epoch=1),
    _phase("setup.place_variables", 23.0, 1.0),
    _phase("setup.first_dispatch", 26.0, 10.0, fn="allreduce_step"),
]


def test_the_readers_on_a_gapless_list():
    run = _view(GAPLESS + [
        # A second worker, a relaunch after the window and a regroup
        # inside it are not the first life's set-up.
        _phase("setup.open_devices", 7.0, 30.0, "worker-1"),
        _phase("setup.world_init", 50.0, 2.0, epoch=2),
        _phase("setup.open_devices", 60.0, 9.0),
    ])
    assert read("master_up_s", run) == pytest.approx(4.0)
    assert read("worker_boot_s", run) == pytest.approx(2.0)
    assert read("chip_open_s", run) == pytest.approx(10.0)
    assert read("model_init_s", run) == pytest.approx(5.5)
    assert read("world_init_s", run) == pytest.approx(4.0)
    assert read("warmup_s", run) == pytest.approx(4.0)
    assert read("setup_unnamed_s", run) == pytest.approx(0.0, abs=1e-6)


def test_unnamed_seconds_are_the_gaps_of_the_union(capsys):
    events = [e for e in GAPLESS if e["name"] not in (
        "setup.imports", "setup.first_task")]
    run = _view(events)
    assert read("setup_unnamed_s", run) == pytest.approx(2.0)
    said = capsys.readouterr().out
    assert '"longest_unnamed"' in said and '"setup.spawn"' in said
    assert read("worker_boot_s", run) is None
    assert read("model_init_s", run) == pytest.approx(5.5)


@pytest.mark.parametrize("name", SETUP_READERS)
def test_a_program_without_the_spans_reads_none(name):
    run = _view([
        {"kind": "worker_devices", "role": "worker-0", "ts": T + 16.0},
        {"kind": "compile_cache_hit", "role": "worker-0", "ts": T + 36.0,
         "fn": "allreduce_step", "seconds": 10.0},
    ])
    assert read(name, run) is None


def _stall(at, dry_s, cause="dry", **more):
    return {"kind": "step_stall", "role": "worker-0", "ts": T + at,
            "step": 100, "cause": cause, "dry_s": dry_s,
            "interval_s": dry_s + 0.18, "median_s": 0.18, **more}


WATCHING = {'edl_worker_step_stalls_total{cause="dry"}': 0.0}


def test_host_stall_pct_sums_the_dry_seconds_of_the_window():
    events = [
        _stall(30.0, 5.0),                     # before the window
        _stall(50.0, 1.2, wake_late_s=0.0),
        _stall(60.0, 0.0, cause="slow"),       # the device itself
        _stall(70.0, 2.0, cause="profile"),    # by design
        _stall(90.0, 3.0),                     # after it
    ]
    assert read("host_stall_pct.lm", _view(events, WATCHING)) == \
        pytest.approx(100.0 * 1.2 / 40.0)
    assert read("host_stall_pct.lm", _view([], WATCHING)) == 0.0
    # A traced run counts what follows the written trace.
    traced = _view(events, WATCHING, t_traced=T + 55.0)
    assert read("host_stall_pct.lm", traced) == 0.0
    # A program whose clock does not watch says nothing, stalled or not.
    assert read("host_stall_pct.lm", _view(events)) is None


def test_step_ms_max_is_the_longest_interval_of_the_window():
    stamps = [T + 41.0 + 0.2 * i for i in range(30)]
    stamps[20:] = [s + 0.7 for s in stamps[20:]]
    events = [
        {"kind": "steps_done", "role": "worker-0", "ts": T + 39.0,
         "first_step": 1, "stamps": [T + 30.0, T + 39.0]},
        {"kind": "steps_done", "role": "worker-0", "ts": T + 50.0,
         "first_step": 3, "stamps": stamps},
    ]
    assert read("step_ms_max.lm", _view(events)) == pytest.approx(900.0)
    assert read("step_ms_max.lm", _view(events[:1])) is None
    # A stamp the program's clock calls late (the step was on time)
    # lengthens one interval and shortens the next: both are left out.
    even = [T + 41.0 + 0.2 * i for i in range(30)]
    even[10] += 0.1

    def with_stall(*stall):
        return _view([dict(events[1], stamps=even), *stall])

    assert read("step_ms_max.lm", with_stall()) == pytest.approx(300.0)
    late = dict(_stall(43.2, 0.0, cause="late_stamp"), step=13)
    assert read("step_ms_max.lm", with_stall(late)) == pytest.approx(200.0)
    slow = dict(_stall(43.2, 0.0, cause="slow"), step=13)
    assert read("step_ms_max.lm", with_stall(slow)) == pytest.approx(300.0)
