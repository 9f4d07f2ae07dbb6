#!/usr/bin/env python3
"""Run one cell of the benchmark once.

    python benchmark/run.py --workload <name> --seed <n> --seconds <s> \
        --trace <0|1>

One run is one new process that never initialises a jax backend itself:
it makes the data from --seed, launches one real job through `python -m
elasticdl_tpu.client.main train ... --instance_backend local_process`
(master in that process, worker and PS shards its children, only the
worker opens the chip), lets it warm up, measures for --seconds, stops the
job, reaps its process group, runs the configuration's plain
reference in a child once the chip is free, prints the numbers it
compared and, as its last line, one JSON object. Without a TPU it fails
before any job starts: there is no CPU mode (the tests call the same
functions at tiny sizes).

The cell's configuration, traffic mix, data generator, reference and
metric readers are files found by the names in BENCHMARK.json; see
benchmark/README.md.
"""

import argparse
import json
import math
import os
import shutil
import subprocess
import sys
import tempfile
import time

T_START = time.time()
HERE = os.path.dirname(os.path.abspath(__file__))
REPO = os.path.dirname(HERE)
sys.path.insert(0, HERE)
sys.path.insert(0, REPO)

from lib import cell as cell_mod  # noqa: E402
from lib import job as job_mod  # noqa: E402
from lib import measure  # noqa: E402
from lib import trace as trace_mod  # noqa: E402
from lib import view as view_mod  # noqa: E402


def say(obj):
    print(json.dumps(obj), flush=True)


def tpu_chips_present():
    """TPU chips on this machine's PCI bus, without opening one (the
    worker must be the only process that does)."""
    from jax._src import hardware_utils

    count, _ = hardware_utils.num_available_tpu_chips_and_device_id()
    return int(count)


def planned_records(traffic, seconds):
    per_task = int(traffic["records_per_task"])
    need = int(traffic["warmup_records"]) + math.ceil(
        float(traffic["records_per_second_sized_for"]) * seconds)
    return per_task * math.ceil(need / per_task)


def train_args(cell, data_path, seed, profile_dir):
    t = cell.traffic
    args = [
        "--model_zoo", REPO,
        "--model_def", cell.config["model_def"],
        "--training_data", data_path,
        "--num_epochs", "1",
        "--minibatch_size", str(t["minibatch"]),
        "--records_per_task", str(t["records_per_task"]),
        "--num_workers", str(t.get("workers", 1)),
        "--num_ps", str(t.get("ps_shards", 0)),
        "--distribution_strategy", t["strategy"],
        "--log_loss_steps", str(t["log_loss_steps"]),
        "--seed", str(seed),
        *t.get("train_args", []),
    ]
    if profile_dir:
        warm_steps = int(t["warmup_records"]) // int(t["minibatch"])
        args += [
            "--profile_dir", profile_dir,
            # Inside the window, a few steps after it opens.
            "--profile_start_step", str(warm_steps + 4),
            "--profile_steps", str(t.get("profile_steps", 5)),
        ]
    return args


def run_reference(cell, seed, precision="float32", timeout=900):
    """The plain reference's losses at the traffic's compare_steps, from a
    child that opens the chip after the job has let go of it."""
    ref = cell.config["reference"]
    path = os.path.join(HERE, "references", f"{ref['module']}.py")
    steps = ",".join(str(s) for s in cell.traffic["compare_steps"])
    t_ref = time.time()
    for attempt in range(3):
        res = subprocess.run(
            [sys.executable, path, "--config", cell.config_path,
             "--seed", str(seed),
             "--minibatch", str(cell.traffic["minibatch"]),
             "--steps", steps, "--precision", precision],
            capture_output=True, text=True, timeout=timeout, cwd=REPO,
        )
        # The job's chips can stay busy a moment after its processes are
        # gone (seen once on four chips): the chip is one process's at a
        # time, so wait and ask again.
        if res.returncode == 0 or "Unable to initialize backend" not in \
                res.stderr:
            break
        time.sleep(5 * (attempt + 1))
    if res.returncode != 0:
        raise RuntimeError(f"the reference failed: {res.stderr[-2000:]}")
    out = json.loads(res.stdout.strip().splitlines()[-1])
    say({"reference": out, "seconds": time.time() - t_ref})
    return {int(k): v for k, v in out["losses"].items()}, out["device"]


def compare_losses(program, reference, abs_limit, mean_limit):
    """({step: loss} of the program, of the reference) -> (rows, mean of
    the signed differences, ok). Each step within `abs_limit` (gross
    faults) and the mean within `mean_limit` (precision: at the stated
    precision a step's difference is noise of either sign; one precision
    down the loss runs steadily higher; PERF.md section 2)."""
    rows = []
    for step, want in sorted(reference.items()):
        got = program.get(step)
        rows.append({"step": step, "program": got, "reference": want,
                     "diff": None if got is None else got - want,
                     "abs_limit": abs_limit})
    diffs = [r["diff"] for r in rows]
    if not diffs or None in diffs:
        return rows, None, False
    mean = sum(diffs) / len(diffs)
    ok = abs(mean) <= mean_limit and all(abs(d) <= abs_limit for d in diffs)
    return rows, mean, ok


def check_losses(run, reference):
    """(a) the first worker's logged losses at the compared steps against
    the reference, (b) every logged loss finite."""
    logged = view_mod.step_losses(run.log)
    first = {}
    for _, step, loss in logged:
        first.setdefault(step, loss)
    ref = run.config["reference"]
    mean_limit = float(ref["loss_mean_limit"])
    rows, mean, ok = compare_losses(
        first, reference, float(ref["loss_abs_limit"]), mean_limit)
    finite = bool(logged) and all(math.isfinite(x[2]) for x in logged)
    ok = ok and finite
    say({"check": "loss_vs_reference", "rows": rows, "mean_diff": mean,
         "mean_limit": mean_limit, "logged_losses": len(logged),
         "all_finite": finite, "ok": ok})
    return ok


def check_accounting(run):
    """(c) the master's records against the worker's steps."""
    t, s = run.traffic, run.status
    steps = run.worker_series.get("edl_worker_steps_total")
    # A stop lands mid-task: the worker is at most the tasks it holds, and
    # one whose report is on the wire, ahead of the master's count.
    ahead = None if steps is None else (
        steps * t["minibatch"] - s["records_done"])
    ahead_limit = t["records_per_task"] * (1 + s["doing_tasks"])
    ok = (s["tasks_abandoned"] == 0 and s["relaunches"] == 0
          and ahead is not None and 0 <= ahead <= ahead_limit)
    say({"check": "accounting", "records_done": s["records_done"],
         "tasks_abandoned": s["tasks_abandoned"],
         "relaunches": s["relaunches"], "worker_steps": steps,
         "minibatch": t["minibatch"], "records_ahead": ahead,
         "ahead_limit": ahead_limit, "ok": ok})
    return ok


def check_devices(run, job, chips, platform):
    """(d) only worker roles opened a backend, and on what."""
    roles = job.backends_by_role()
    ok = "master" in roles and all(
        opened == role.startswith("worker")
        for role, opened in roles.items())
    seen = run.events_of("worker_devices", "worker")
    want_kind = None
    for e in seen:
        want_kind = want_kind or e["device_kind"]
        ok = ok and (e["platform"] == platform and e["count"] == chips
                     and e["device_kind"] == want_kind)
    ok = ok and bool(seen)
    say({"check": "devices", "backend_initialized_by_role": roles,
         "worker_devices": [
             {k: e[k] for k in ("platform", "device_kind", "count")}
             for e in seen], "chips": chips, "ok": ok})
    return ok


def memory_peak_bytes(run):
    """Peak bytes on the fullest chip, by the runtime's own count: the
    workers' exit reports and the first worker's /metrics."""
    peaks = [
        float(stats.get("peak_bytes_in_use", 0))
        for e in run.events_of("worker_exit_memory", "worker")
        for stats in (e.get("device_stats") or {}).values()
    ]
    peaks += [
        v for k, v in run.worker_series.items()
        if k.startswith("edl_mem_device_stats_bytes")
        and 'stat="peak_bytes_in_use"' in k
    ]
    return int(max(peaks)) if peaks else None


def count_tasks(run):
    """attempted = tasks whose lease ended in the window (done or
    failed); failed = tasks failed back, timed out, requeued or abandoned
    there."""
    per_task = int(run.traffic["records_per_task"])
    inside = [r for t, r in run.samples if run.t0 <= t <= run.t1]
    done = (inside[-1] - inside[0]) // per_task if len(inside) > 1 else 0
    bad = run.events_of(
        ("task_failed", "task_timeout", "task_abandoned", "job_failed"),
        since=run.t0, until=run.t1)
    requeued = sum(
        int(e.get("count", 1))
        for e in run.events_of("task_reassign", since=run.t0, until=run.t1))
    failed = len(bad) + requeued
    return int(done + failed), int(failed)


def read_metrics(cell, run, metrics):
    out = {}
    for m in metrics:
        reader = cell_mod.load_module("metrics", m["name"])
        value = reader.read(run)
        if value is None:
            continue
        out[m["name"]] = {"value": float(value), "unit": m["unit"]}
    return out


def main(argv=None):
    parser = argparse.ArgumentParser(description=__doc__.split("\n")[0])
    parser.add_argument("--workload", required=True)
    parser.add_argument("--seed", type=int, required=True)
    parser.add_argument("--seconds", type=float, required=True)
    parser.add_argument("--trace", type=int, choices=(0, 1), default=0)
    args = parser.parse_args(argv)

    if not os.path.isdir(os.path.join(REPO, "elasticdl_tpu")):
        raise SystemExit(
            "benchmark/run.py: no elasticdl_tpu package beside benchmark/; "
            "it drives the program, it is not the program")
    cell = cell_mod.Cell(args.workload)
    found = tpu_chips_present()
    if found < cell.chips:
        raise SystemExit(
            f"benchmark/run.py: {args.workload} needs {cell.chips} TPU "
            f"chip(s), this machine has {found}; there is no CPU mode")
    return run_cell(cell, args)


def run_cell(cell, args, expect_platform="tpu"):
    workdir = tempfile.mkdtemp(prefix="edlbench_")
    job = None
    try:
        datagen = cell_mod.load_module("datagen", cell.config["datagen"])
        data_path = os.path.join(workdir, "train.edlr")
        planned = planned_records(cell.traffic, args.seconds)
        t_data = time.time()
        wrote = datagen.write_records(
            data_path, planned, args.seed, cell.config["data"])
        say({"data": wrote, "seconds": time.time() - t_data,
             "bytes": os.path.getsize(data_path)})
        profile_dir = (
            os.path.join(workdir, "profile") if args.trace else None)
        env = dict(cell.traffic.get("env") or {})
        job = job_mod.Job(
            REPO, workdir,
            train_args(cell, data_path, args.seed, profile_dir), env)

        def right_devices():
            for e in job.events():
                if e.get("kind") == "worker_devices" and (
                        e["platform"] != expect_platform
                        or e["count"] != cell.chips):
                    raise RuntimeError(
                        f"the cell needs {cell.chips} {expect_platform} "
                        f"chip(s); the worker opened {e['count']} x "
                        f"{e['device_kind']} ({e['platform']})")

        measured = measure.measure(
            job, cell.traffic, args.seconds, warmup_check=right_devices)
        left = job.stop()
        events, log = job.events(), job.log_text()
        reduced = None
        if args.trace:
            files = measure.trace_files(profile_dir)
            if not files:
                raise RuntimeError("the traced run wrote no trace")
            measured["t_traced"] = os.path.getmtime(files[0])
            reduced = trace_mod.reduce(trace_mod.load(files[0]))
            if reduced is None:
                raise RuntimeError("no device operation in the trace")
        run = view_mod.RunView(
            cell, args.seed, args.seconds, T_START, job.t_launch, measured,
            events, log, reduced)
        devs = run.events_of("worker_devices", "worker")
        if not devs or devs[0]["platform"] != expect_platform:
            raise RuntimeError(
                f"the worker did not run on a {expect_platform}: {devs[:1]}")
        run.device = {
            "platform": devs[0]["platform"], "kind": devs[0]["device_kind"],
            "count": int(devs[0]["count"]),
            "memory_peak_bytes": memory_peak_bytes(run),
        }
        if (run.device["memory_peak_bytes"] is None
                and expect_platform == "tpu"):
            raise RuntimeError("the worker reported no device memory")
        say({"window": {
            "opened_after_s": run.t0 - T_START,
            "ends": run.window_ends(), "samples": len(run.samples),
            "status": run.status,
            "leftover_processes": left}})
        end_to_end = read_metrics(cell, run, cell.end_to_end)
        per_layer = (
            read_metrics(cell, run, cell.per_layer) if args.trace else {})
        reference, ref_device = run_reference(cell, args.seed)
        correct = all([
            check_losses(run, reference),
            check_accounting(run),
            check_devices(run, job, cell.chips, expect_platform),
            not left,
            ref_device["platform"] == run.device["platform"],
        ])
        attempted, failed = count_tasks(run)
        missing = [m["name"] for m in cell.end_to_end
                   if m["name"] not in end_to_end]
        if missing:
            raise RuntimeError(f"no value for end-to-end {missing}")
        result = {
            "correct": bool(correct), "attempted": attempted,
            "failed": failed,
            "metrics": per_layer if args.trace else end_to_end,
            "device": run.device,
        }
        if args.trace:
            result["device"]["busy_s"] = reduced["busy_s"]
            result["device"]["window_s"] = reduced["window_s"]
            result["breakdown"] = {
                "device_ops": reduced["device_ops"],
                "idle_gaps": reduced["idle_gaps"],
            }
            say({"end_to_end_in_traced_run": end_to_end})
        print(json.dumps(result), flush=True)
        return 0
    except Exception as e:
        if job is not None:
            job.stop()
            tail = job.log_text()[-6000:]
            print(f"--- job log tail ---\n{tail}", file=sys.stderr)
        print(f"benchmark/run.py: FAILED: {e!r}", file=sys.stderr)
        return 1
    finally:
        shutil.rmtree(workdir, ignore_errors=True)


if __name__ == "__main__":
    sys.exit(main())
