"""Bench orchestration: run the suite, always emit the one JSON line.

Invariants this module owns:

- ONE result line on stdout, always — even when benchmarks time out,
  raise, or the budget truncates the run. The line is assembled
  incrementally and printed in a ``finally``; a wedged benchmark costs
  its own slot ({"error": ..., "timed_out": true}), never the line.
- every benchmark runs under the hard per-benchmark watchdog AND a soft
  shared BudgetClock that workloads consult between timed windows
  (degrading sample counts instead of dying).
- a flight recorder is armed for the whole run (role "bench"): SIGTERM,
  a crash, or a watchdog timeout dumps the last spans + the currently
  open phase to flightrec-bench.json, so a dead run leaves attributable
  evidence instead of an rc=124.
- the result carries a significance verdict vs the newest parseable
  checked-in BENCH_*.json (stats.compare_records): CIs from this run's
  windows vs the baseline's samples, device-kind guarded.

This module itself never imports jax — workloads load lazily — so the
emission/verdict machinery is testable in milliseconds.
"""

import json
import os
import sys
import time

from elasticdl_tpu.bench import attribution, stats
from elasticdl_tpu.bench.budget import BudgetClock, run_with_watchdog
from elasticdl_tpu.common import knobs
from elasticdl_tpu.observability import flightrec, profiling

REPO_ROOT = os.path.dirname(
    os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
)

# The one-line result schema (docs/BENCHMARKS.md documents it; the
# stats tests validate emitted lines against it).
RESULT_KEYS = ("metric", "value", "unit", "vs_baseline", "details")


def validate_result(obj):
    """Raise ValueError unless ``obj`` is a schema-valid result line."""
    if not isinstance(obj, dict):
        raise ValueError("result line must be a JSON object")
    missing = [k for k in RESULT_KEYS if k not in obj]
    if missing:
        raise ValueError(f"result line missing keys: {missing}")
    if not isinstance(obj["details"], dict):
        raise ValueError("details must be an object")
    return obj


def _round_if_ok(result):
    if not isinstance(result, dict) or "error" in result:
        return result
    return {
        k: (round(v, 4) if isinstance(v, float) else v)
        for k, v in result.items()
    }


def _emit(result, out_path=None):
    line = json.dumps(validate_result(result))
    print(line)
    if out_path:
        tmp = f"{out_path}.tmp.{os.getpid()}"
        with open(tmp, "w") as f:
            f.write(line + "\n")
        os.replace(tmp, out_path)


def attach_verdict(details, min_effect=None, baseline_path=None):
    """Compare this run against the newest parseable BENCH_*.json and
    fold the verdict into ``details``. Never raises — a broken baseline
    file becomes a recorded note, not a dead run."""
    if min_effect is None:
        min_effect = knobs.get_float("ELASTICDL_BENCH_MIN_EFFECT")
    try:
        if baseline_path is None:
            baseline_path = knobs.get_str("ELASTICDL_BENCH_BASELINE")
        if baseline_path:
            baseline = stats.load_bench_file(baseline_path)
            pairs = [(baseline_path, baseline)] if baseline else []
        else:
            pairs = stats.find_baselines(REPO_ROOT)
        if not pairs:
            details["verdict"] = {"overall": "no-baseline"}
            return details
        path, baseline = stats.select_baseline(
            pairs, details.get("device_kind") or ""
        )
        candidate = {"metric": "candidate", "details": details}
        verdict = stats.compare_records(
            baseline, candidate, min_effect=min_effect
        )
        verdict["baseline_file"] = os.path.basename(path)
        details["verdict"] = verdict
    except Exception as e:  # evidence machinery must not sink the run
        details["verdict"] = {
            "overall": "error", "error": str(e)[:200]
        }
    return details


def _arm_flightrec():
    try:
        flightrec.install("bench")
    except Exception:
        pass


def _watchdog(name, fn, timeout_s):
    with flightrec.phase(name):
        return run_with_watchdog(
            name, fn, timeout_s,
            on_timeout=lambda n: flightrec.dump(f"watchdog-timeout:{n}"),
        )


def _measured(name, fn, timeout_s, measured, key):
    """Run one bench under the watchdog while measuring its wall clock
    and the compile-tracker seconds delta — the inputs the step-time
    attribution table (bench/attribution.py) needs per workload."""
    t0 = time.perf_counter()
    c0 = profiling.tracker().snapshot()[1]
    result = _watchdog(name, fn, timeout_s)
    wall = time.perf_counter() - t0
    compile_s = max(0.0, profiling.tracker().snapshot()[1] - c0)
    measured[key] = (result, wall, compile_s)
    return result


def _attach_attribution(details, measured):
    """Fold the per-workload attribution into the result details and
    print the human table to stderr (stdout stays the one JSON line)."""
    try:
        table = attribution.build_all(measured)
        if table:
            details["attribution"] = table
        print(attribution.render_table(table), file=sys.stderr)
    except Exception as e:  # evidence machinery must not sink the run
        details["attribution_error"] = str(e)[:200]


def run_full(watchdog_s=None, budget_s=None, with_matrix=True,
             out_path=None):
    """The full suite. Returns the process exit code."""
    import jax  # the full suite is meaningless without a backend

    from elasticdl_tpu.bench import fleet as fleet_bench
    from elasticdl_tpu.bench import matrix, workloads

    if watchdog_s is None:
        watchdog_s = knobs.get_float("ELASTICDL_BENCH_WATCHDOG_S")
    if budget_s is None:
        budget_s = knobs.get_float("ELASTICDL_BENCH_BUDGET_S")
    _arm_flightrec()
    clock = BudgetClock(budget_s)
    windows = knobs.get_int("ELASTICDL_BENCH_WINDOWS")
    device = jax.devices()[0]
    details = {
        "platform": device.platform,
        "device_kind": device.device_kind,
        "n_devices": jax.local_device_count(),
    }
    if device.platform != "tpu":
        # A measurement path that finds no chip fails: four rounds of
        # CPU runs were once checked in under device-metric names.
        details["error"] = (
            f"no TPU: jax found platform {device.platform!r} "
            f"({device.device_kind}); the full bench measures the chip "
            "and has no CPU mode (use --smoke for the harness check)"
        )
        print(details["error"], file=sys.stderr)
        _emit(
            {
                "metric": "bench (full)", "value": None, "unit": None,
                "vs_baseline": None, "details": details,
            },
            out_path,
        )
        return 2
    if budget_s:
        details["budget_s"] = budget_s
    # Suite order: recsys + PS benches and the rejoin drill FIRST, the
    # conv backbones LAST. A conv bench that blows its watchdog leaves
    # an unkillable abandoned compile thread burning CPU; on a CPU-only
    # host that thread would contaminate every measurement taken after
    # it — so nothing measurable runs after the convs. (On TPU the
    # order is irrelevant: convs finish in seconds.)
    #
    # The matrix and the rejoin drill get a floored watchdog: both are
    # many-part benchmarks (8 cells x repeats / two full kill-rejoin
    # jobs) that degrade themselves against the budget clock — a
    # watchdog sized for ONE workload would kill them mid-flight and
    # discard the parts that already ran. 0 still disables.
    suite = [
        (
            "deepfm_criteo", "deepfm_criteo",
            lambda: workloads.bench_deepfm_criteo(
                windows=windows, clock=clock
            ),
            watchdog_s, True,
        ),
        (
            "deepfm_ps_mode", "deepfm_ps",
            lambda: workloads.bench_deepfm_ps(clock=clock),
            watchdog_s, False,
        ),
        # Fleet cells run EARLY: jax-free (simulated pods, real control
        # plane), ~2-3 min total, and they must not be squeezed by
        # whatever budget the matrix/rejoin leave over — a mid-A/B
        # watchdog kill discards both sides of the comparison. Still a
        # many-part bench, so the floored watchdog applies.
        (
            "fleet", "fleet",
            lambda: fleet_bench.bench_fleet(clock=clock),
            watchdog_s and max(watchdog_s, 600), False,
        ),
    ]
    if with_matrix:
        suite.append(
            (
                "ps_matrix", "ps_matrix",
                lambda: matrix.bench_ps_matrix(clock=clock),
                watchdog_s and max(watchdog_s, 600), False,
            )
        )
    suite += [
        (
            "elastic_rejoin", "elastic_rejoin",
            workloads.bench_elastic_rejoin,
            watchdog_s and max(watchdog_s, 600), False,
        ),
        (
            "resnet50", "resnet50",
            lambda: workloads.bench_resnet50(
                windows=windows, clock=clock
            ),
            watchdog_s, True,
        ),
        (
            "mobilenetv2", "mobilenetv2",
            lambda: workloads.bench_mobilenetv2(
                windows=windows, clock=clock
            ),
            watchdog_s, True,
        ),
    ]
    measured = {}
    failures = 0
    try:
        for key, name, fn, timeout_s, round_result in suite:
            # A spent budget SKIPS remaining benchmarks instead of
            # starting them: the one JSON line must reach stdout before
            # whatever outer wall (the driver's ~870 s timeout that
            # produced the evidence-free BENCH_r05) kills the process.
            # Each skip is recorded — truncation is visible, not silent.
            if clock.expired:
                details[key] = {"skipped": "budget"}
                continue
            # Cap the watchdog by the REMAINING budget: a bench that
            # starts with 90 s of budget left must not get its full
            # 600 s bound — the whole point of the budget is that the
            # result line lands before the outer wall, and one wedged
            # late benchmark running out its uncapped watchdog would
            # overshoot the budget by up to that watchdog. (The 1 s
            # floor keeps the cap from becoming 0 = watchdog disabled.)
            if timeout_s and clock.total_s:
                timeout_s = min(timeout_s, max(clock.remaining(), 1.0))
            result = _measured(name, fn, timeout_s, measured, key)
            details[key] = _round_if_ok(result) if round_result else result
            if not isinstance(result, dict) or "error" in result:
                failures += 1
    finally:
        _attach_attribution(details, measured)
        deepfm = details.get("deepfm_criteo") or {}
        if isinstance(deepfm, dict) and "examples_per_sec" in deepfm:
            details["deepfm_examples_per_sec_chip"] = round(
                deepfm["examples_per_sec"], 2
            )
        if budget_s:
            details["budget_elapsed_s"] = round(clock.elapsed(), 2)
        details["failures"] = failures
        attach_verdict(details)
        # LocalTrainer's jitted step runs on exactly one device, so its
        # examples/sec IS the per-chip figure regardless of how many
        # chips the host exposes.
        resnet = details.get("resnet50") or {}
        per_chip = (
            resnet.get("examples_per_sec", 0.0)
            if isinstance(resnet, dict)
            else 0.0
        )
        baseline_img_per_sec = 145.0  # reference ResNet50, 1x P100
        _emit(
            {
                "metric": (
                    "examples/sec/chip (ResNet50, bf16, 224x224, "
                    "batch 128)"
                ),
                "value": round(per_chip, 2),
                "unit": "examples/sec",
                "vs_baseline": round(
                    per_chip / baseline_img_per_sec, 3
                ),
                "details": details,
            },
            out_path,
        )
    # The JSON line is out; a workload that failed still fails the run.
    return 1 if failures else 0


def run_smoke(watchdog_s=None, budget_s=None, out_path=None,
              benches=None):
    """CPU-safe tiny-shape pass (< 60 s): exercises the bench pipelines —
    windowed jitted loop (with CI fields), PS-resident loop over a real
    localhost shard with the push serialize/wire/apply breakdown —
    without TPU-scale shapes or the elastic drill. This is the CI guard
    for the bench subsystem itself: a hang or crash in the harness shows
    up here in seconds, not at the end of a multi-hour TPU session.

    ``benches`` overrides the registry ({name: fn}) — the truncated-run
    emission tests inject deliberately wedged/raising workloads."""
    if watchdog_s is None:
        watchdog_s = 50.0
    if budget_s is None:
        budget_s = knobs.get_float("ELASTICDL_BENCH_BUDGET_S")
    _arm_flightrec()
    clock = BudgetClock(budget_s)
    if benches is None:
        from elasticdl_tpu.bench import matrix, workloads

        # Conv backbones are out: their CPU compile alone blows the
        # budget. The DeepFM benches still cover both execution
        # pipelines (the windowed jitted loop — 3 windows, so CI fields
        # are present — and the PS pull/train/push loop with the push
        # sub-span breakdown), and a 2-cell matrix slice proves the
        # shard-count axis plumbing without TPU-scale shapes.
        benches = {
            "deepfm_criteo_b256": lambda: workloads.bench_deepfm_criteo(
                batch_size=256, steps_per_window=2, windows=3, warmup=1,
                clock=clock,
            ),
            "deepfm_ps_b128": lambda: workloads.bench_deepfm_ps(
                batch_size=128, steps=2, warmup=1, num_ps=1, repeats=1,
                clock=clock,
            ),
            # float32 + int8 codecs: the int8 cell keeps the quantized
            # packed wire (block codec + error feedback) covered in the
            # <60 s path; no prefetch-off control at smoke scale.
            "ps_matrix_tiny": lambda: matrix.bench_ps_matrix(
                batch_size=128, steps=2, warmup=1, repeats=1,
                shard_counts=(1, 2), codecs=("float32", "int8"),
                pipelining=(False,), prefetch_controls=(), clock=clock,
            ),
        }
    details = {}
    failures = 0
    measured = {}
    start = time.perf_counter()
    try:
        for name, fn in benches.items():
            if clock.expired:
                details[name] = {"skipped": "budget"}
                continue
            timeout_s = watchdog_s
            if timeout_s and clock.total_s:
                timeout_s = min(timeout_s, max(clock.remaining(), 1.0))
            result = _measured(name, fn, timeout_s, measured, name)
            details[name] = _round_if_ok(result)
            if not isinstance(result, dict) or "error" in result:
                failures += 1
    finally:
        _attach_attribution(details, measured)
        elapsed = time.perf_counter() - start
        details["elapsed_s"] = round(elapsed, 2)
        details["failures"] = failures
        _emit(
            {
                "metric": "bench smoke (tiny shapes, CPU-safe)",
                "value": round(elapsed, 2),
                "unit": "seconds",
                "vs_baseline": None,
                "details": details,
            },
            out_path,
        )
    return 1 if failures else 0
