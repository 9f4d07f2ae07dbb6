"""The arithmetic between a run's raw observations and its metrics, on
hand-made observations (no job, no chip)."""

import pytest

import bench_helpers as h
from lib import measure, view

T = 1000.0  # the window opens here


def step_log(steps):
    """Worker log lines `Step N ... loss` at the given (seconds after the
    window opens, step)."""
    import datetime

    lines = []
    for after, step in steps:
        at = datetime.datetime.fromtimestamp(T + after)
        stamp = (at.strftime("%Y-%m-%d %H:%M:%S")
                 + f",{at.microsecond // 1000:03d}")
        lines.append(f"[{stamp}] [INFO] [w:415] Step {step} (version "
                     f"{step}) loss 10.5")
    return "\n".join(lines)


def make_run(samples, events=(), series=None, status=None,
             traffic="steady", seconds=40.0, log=""):
    cell = h.cell_mod.Cell(next(
        w["name"] for w in h.manifest()["workloads"]
        if w["traffic"] == traffic))
    measured = {
        "samples": samples, "t0": T, "t1": T + seconds,
        "worker_series": series or {},
        "last": status or {
            "records_done": samples[-1][1] if samples else 0,
            "relaunches": 0, "tasks_recovered": 0, "tasks_abandoned": 0,
            "todo_tasks": 3, "doing_tasks": 1, "alive_workers": 1,
            "finished": False, "job_failed": False},
    }
    run = view.RunView(cell, 1, seconds, T - 42.0, T - 40.0, measured,
                       list(events), log)
    run.device = {"platform": "tpu", "kind": "TPU v5 lite", "count": 1}
    return run


def read(name, run):
    return h.cell_mod.load_module("metrics", name).read(run)


def test_window_ends_snap_to_task_completions():
    samples = [(T - 3, 56), (T, 64), (T + 0.5, 72), (T + 39.7, 704),
               (T + 40.2, 712)]
    assert measure.window_ends(samples, T, T + 40) == (
        (T, 64), (T + 39.7, 704))
    assert measure.window_ends(samples[:2], T, T + 40) is None


def test_tokens_per_s_and_mfu_between_fenced_steps_of_the_window():
    # Step lines at 0.5 s (step 24) .. 39.5 s (step 184); the ones before
    # the window and after it do not count. 160 steps x 4 records x 4096
    # tokens in 39.0 s = 67,216 tokens/s; x 1.4093e9 operations a token /
    # 197e12 = 48.09 % of one chip's bf16 peak. The master's count, which
    # runs ahead of the device, plays no part.
    log = step_log([(-1.5, 16), (0.5, 24), (20.0, 104), (39.5, 184),
                    (41.4, 192)])
    run = make_run([(T, 64), (T + 20, 400), (T + 39.7, 760)], log=log)
    assert run.fenced_steps() == [
        (pytest.approx(T + 0.5), 24), (pytest.approx(T + 20.0), 104),
        (pytest.approx(T + 39.5), 184)]
    assert run.record_rate() == pytest.approx(640 / 39.0)
    assert read("tokens_per_s", run) == pytest.approx(67216.4, rel=1e-5)
    assert read("mfu_pct", run) == pytest.approx(48.09, abs=0.01)
    assert read("setup_s", run) == pytest.approx(42.0)
    run.device["kind"] = "TPU v9 imaginary"
    with pytest.raises(KeyError):
        read("mfu_pct", run)
    # A traced run: the lines before the trace was written do not count.
    run.t_traced = T + 5.0
    assert run.record_rate() == pytest.approx(320 / 19.5)
    one = make_run([(T, 64), (T + 39, 700)], log=step_log([(0.5, 24)]))
    assert read("tokens_per_s", one) is None


def test_records_are_sized_for_the_rate():
    run = h.run_module()
    t = {"warmup_records": 64, "records_per_task": 8,
         "records_per_second_sized_for": 25.0}
    assert run.planned_records(t, 40) == 1064  # 64 + 1000, a task multiple
    assert run.planned_records(t, 40.1) == 1072


def event(kind, ts, role="worker-0", **fields):
    return dict(kind=kind, ts=ts, role=role, **fields)


def test_launch_step_load_and_window_compiles_from_events():
    events = [
        event("pod_launch", T - 35, role="master"),
        event("worker_devices", T - 22, platform="tpu", count=1,
              device_kind="TPU v5 lite"),
        event("compile_cache_hit", T - 6, fn="allreduce_step",
              seconds=10.25),
        event("compile", T - 5, fn="forward", seconds=0.4),
        event("compile", T + 7, fn="train_step", seconds=2.0),
    ]
    run = make_run([(T, 64), (T + 39, 700)], events)
    assert read("launch_s", run) == pytest.approx(18.0)
    assert read("step_load_s", run) == pytest.approx(10.25)
    assert read("window_compiles.lm", run) == 1.0
    # Loaded again inside the window (a worker that came back): not set-up.
    events.append(event("compile_cache_hit", T + 9, fn="allreduce_step",
                        seconds=10.4))
    assert read("step_load_s", make_run([(T, 64)], events)) == \
        pytest.approx(10.25)


def test_stage_shares_over_the_tasks_that_ended_in_the_window():
    events = [
        event("datapath", T + 1, task_s=9.0, starve_s=9.0),  # opens the span
        event("datapath", T + 11, task_s=0.05, starve_s=0.1, read_s=0.02,
              decode_s=0.03, h2d_s=0.05, collate_s=5.0),
        event("datapath", T + 21, task_s=0.05, starve_s=0.2),
        event("datapath", T + 50, task_s=7.0),  # after the window
    ]
    run = make_run([(T + 1, 64), (T + 21, 500)], events)
    assert read("task_wait_pct.lm", run) == pytest.approx(100 * 0.10 / 20)
    assert read("input_wait_pct.lm", run) == pytest.approx(100 * 0.40 / 20)
    assert read("task_wait_pct.lm", make_run([(T, 64), (T + 9, 70)])) is None


def test_attempted_and_failed_tasks():
    run_mod = h.run_module()
    steady = make_run([(T, 64), (T + 20, 384), (T + 39.7, 704)])
    assert run_mod.count_tasks(steady) == (80, 0)
    events = [event("task_reassign", T + 3, role="master", count=2),
              event("task_failed", T + 20, role="master", task_id=9),
              event("task_timeout", T - 5, role="master", task_id=2)]
    # 80 done; two requeued and one failed back inside the window; the
    # timeout during warm-up is not the window's.
    rough = make_run([(T, 64), (T + 20, 384), (T + 39.7, 704)], events)
    assert run_mod.count_tasks(rough) == (83, 3)


def test_memory_peak_from_exit_reports_and_the_metrics_endpoint():
    run_mod = h.run_module()
    series = {'edl_mem_device_stats_bytes{device="tpu:0",stat='
              '"peak_bytes_in_use"}': 4462216704.0,
              'edl_mem_device_stats_bytes{device="tpu:0",stat='
              '"bytes_in_use"}': 2.8e9}
    run = make_run([(T, 64), (T + 39, 700)], series=series)
    assert run_mod.memory_peak_bytes(run) == 4462216704
    run = make_run([(T, 64), (T + 39, 700)], [event(
        "worker_exit_memory", T + 41, device_stats={
            "tpu:0": {"peak_bytes_in_use": 5.0e9},
            "tpu:1": {"peak_bytes_in_use": 5.1e9}})])
    assert run_mod.memory_peak_bytes(run) == 5100000000
    assert run_mod.memory_peak_bytes(make_run([(T, 64)])) is None


REFERENCE = {8: 10.49051, 16: 10.35322, 24: 10.31307, 32: 10.29925}


@pytest.mark.parametrize("off,ok", [
    ((1e-5, -2e-5, 3e-4, -2.5e-4), True),   # noise of either sign
    ((3e-4, 3.5e-4, 2.9e-4, 3.2e-4), False),  # each small, all one way
    ((-3e-4, -3.5e-4, -2.9e-4, -3.2e-4), False),
    ((1e-5, 2.8e-3, 1e-5, -2.8e-3), False),  # gross at a step, mean near 0
    ((1e-5, None, 1e-5, 1e-5), False),      # a compared step never logged
    ((1e-5, float("nan"), 1e-5, 1e-5), False),
])
def test_losses_against_the_reference(off, ok, capsys):
    run_mod = h.run_module()
    logged = {step: None if d is None else want + d
              for (step, want), d in zip(REFERENCE.items(), off)}
    log = step_log([(-30 + i, step) for i, (step, loss)
                    in enumerate(logged.items()) if loss is not None])
    for step, loss in logged.items():
        log = log.replace(f"Step {step} (version {step}) loss 10.5",
                          f"Step {step} (version {step}) loss {loss}")
    run = make_run([(T, 64), (T + 39, 700)], log=log)
    assert run.config["reference"]["loss_abs_limit"] == 0.002
    assert run.config["reference"]["loss_mean_limit"] == 0.0003
    assert run_mod.check_losses(run, REFERENCE) is ok
    capsys.readouterr()


@pytest.mark.parametrize("steps,done,doing,relaunches,abandoned,ok", [
    (176.0, 696, 1, 0, 0, True),    # 8 ahead: mid-task
    (178.0, 696, 1, 0, 0, True),    # 16 ahead: a report in flight
    (180.0, 696, 1, 0, 0, False),   # 24 ahead: records were lost
    (173.0, 696, 1, 0, 0, False),   # behind: counted twice
    (None, 696, 1, 0, 0, False),    # the worker's counter was not read
    (176.0, 696, 1, 1, 0, False),   # a worker was relaunched: no fault here
    (176.0, 696, 1, 0, 1, False),   # a task was abandoned
])
def test_accounting_of_a_stopped_job(steps, done, doing, relaunches,
                                     abandoned, ok, capsys):
    run_mod = h.run_module()
    series = {} if steps is None else {"edl_worker_steps_total": steps}
    run = make_run([(T, 64), (T + 39, done)], series=series)
    run.status.update(doing_tasks=doing, relaunches=relaunches,
                      tasks_abandoned=abandoned)
    assert run_mod.check_accounting(run) is ok
    capsys.readouterr()
