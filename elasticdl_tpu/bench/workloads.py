"""The model benchmarks (imports jax; only the runner loads this).

Moved from the old repo-root ``bench.py`` with one methodological
change: instead of a single timed loop per benchmark, the step loop
runs as REPEATED TIMED WINDOWS (same total step count, split into
``windows`` chunks), so every benchmark yields a sample set —
examples/s per window — that ``stats.summarize`` can put a bootstrap
CI around and ``stats.significance_verdict`` can compare across runs.
A run-to-run drift claim needs within-run variance to stand on.

Budget awareness: each workload takes an optional BudgetClock and stops
opening new windows when the budget is gone — degrading the sample
count (marked ``truncated``) instead of dying with nothing.

Method is otherwise unchanged: the batch is placed on device once and
the jitted train step runs with donated buffers (synthetic-data-
resident mode) — measuring the training step, not host dataloading.
"""

import json
import os
import time

import jax
import numpy as np

from elasticdl_tpu.bench import matrix as _matrix
from elasticdl_tpu.bench import stats
from elasticdl_tpu.observability.mfu import peak_flops

DEFAULT_WINDOWS = 5


def _timed_windows(trainer, features, labels, steps_per_window, windows,
                   warmup, clock=None):
    """Build the trainer's jitted step, park the batch on device, run
    ``windows`` timed windows of ``steps_per_window`` steps each with
    donated buffers. Returns (per-window elapsed list, flops_per_step or
    None, truncated). At least one window always runs — a blown budget
    degrades evidence, it doesn't zero it (the hard watchdog above this
    owns the truly-wedged case)."""
    trainer.init_variables_if_needed(features)
    step = trainer._train_step
    variables, opt_state = trainer._variables, trainer._opt_state
    rng = jax.random.PRNGKey(0)
    dev_f = jax.device_put(features)
    dev_l = jax.device_put(labels)

    cost = step.lower(
        variables, opt_state, rng, dev_f, dev_l
    ).compile().cost_analysis()
    flops = float((cost or {}).get("flops", 0.0)) or None

    loss = None
    for _ in range(warmup):
        variables, opt_state, loss = step(
            variables, opt_state, rng, dev_f, dev_l
        )
    # Fence: wait for the warm-up chain before the clock starts.
    # (warmup=0 skips it: the first window then absorbs the compile,
    # which is what asking for no warmup means.)
    if loss is not None:
        jax.block_until_ready(loss)

    elapsed = []
    truncated = False
    for w in range(windows):
        if w > 0 and clock is not None and clock.expired:
            truncated = True
            break
        start = time.perf_counter()
        for _ in range(steps_per_window):
            variables, opt_state, loss = step(
                variables, opt_state, rng, dev_f, dev_l
            )
        jax.block_until_ready(loss)  # the window's whole chain
        elapsed.append(time.perf_counter() - start)
    return elapsed, flops, truncated


def _window_result(elapsed, batch_size, steps_per_window, truncated,
                   flops=None):
    """Per-window elapsed -> the benchmark's reported dict: median
    examples/s with samples + CI, step time, optional TFLOP/s + MFU."""
    samples = [
        batch_size * steps_per_window / e for e in elapsed
    ]
    summary = stats.summarize(samples)
    total = sum(elapsed)
    steps = steps_per_window * len(elapsed)
    out = {
        "examples_per_sec": summary["median"],
        "samples": [round(s, 1) for s in samples],
        "step_time_ms": total / steps * 1e3,
        "windows": len(elapsed),
        "steps_per_window": steps_per_window,
    }
    if "ci95" in summary:
        out["examples_per_sec_ci95"] = [
            round(summary["ci95"][0], 1),
            round(summary["ci95"][1], 1),
        ]
    if truncated:
        out["truncated"] = True
    if flops:
        out["model_tflops_per_sec"] = flops * steps / total / 1e12
        device = jax.devices()[0]
        if device.platform != "cpu":
            # An accelerator the peak table does not list raises here:
            # no MFU is printed against a guessed denominator. The CPU
            # (the --smoke harness check) prints none.
            out["mfu"] = (
                flops * steps / total / peak_flops(device.device_kind)
            )
    return out


def _bench_image_model(model_def, batch_size, steps_per_window, windows,
                       warmup, clock=None):
    """Shared ImageNet-shape image benchmark: examples/sec with CI, step
    time, and (when XLA cost analysis yields flops) TFLOP/s + MFU."""
    from elasticdl_tpu.common.model_utils import get_model_spec
    from elasticdl_tpu.worker.trainer import LocalTrainer

    spec = get_model_spec(model_def)
    trainer = LocalTrainer(
        spec.build_model(), spec.loss, spec.build_optimizer_spec()
    )
    rng = np.random.default_rng(0)
    features = rng.normal(size=(batch_size, 224, 224, 3)).astype(np.float32)
    labels = rng.integers(0, 1000, batch_size).astype(np.int64)
    elapsed, flops, truncated = _timed_windows(
        trainer, features, labels, steps_per_window, windows, warmup,
        clock,
    )
    return _window_result(
        elapsed, batch_size, steps_per_window, truncated, flops
    )


def bench_resnet50(batch_size=128, steps_per_window=6,
                   windows=DEFAULT_WINDOWS, warmup=5, clock=None):
    return _bench_image_model(
        "elasticdl_tpu.models.resnet50.resnet50", batch_size,
        steps_per_window, windows, warmup, clock,
    )


def bench_mobilenetv2(batch_size=256, steps_per_window=6,
                      windows=DEFAULT_WINDOWS, warmup=5, clock=None):
    """Second image benchmark of the reference's table: MobileNetV2 at
    150 img/s on one P100 (ftlib_benchmark.md:138-156)."""
    out = _bench_image_model(
        "elasticdl_tpu.models.mobilenetv2.mobilenetv2", batch_size,
        steps_per_window, windows, warmup, clock,
    )
    out["vs_p100_150img_s"] = out["examples_per_sec"] / 150.0
    return out


def bench_deepfm_criteo(batch_size=32768, steps_per_window=6,
                        windows=DEFAULT_WINDOWS, warmup=5, clock=None):
    """Batch 32768: measured sweep on TPU v5e — 197k ex/s @8192, 199k
    @16384, 211k @32768 (embedding gathers amortize better at width);
    large batches are the normal recsys regime on TPU."""
    from elasticdl_tpu.common.model_utils import get_model_spec
    from elasticdl_tpu.models.dac_ctr.transform import NUM_FIELDS, TOTAL_IDS
    from elasticdl_tpu.worker.trainer import LocalTrainer

    spec = get_model_spec("elasticdl_tpu.models.dac_ctr.deepfm")
    trainer = LocalTrainer(
        spec.build_model(), spec.loss, spec.build_optimizer_spec()
    )
    rng = np.random.default_rng(0)
    features = {
        "dense": rng.normal(size=(batch_size, 13)).astype(np.float32),
        "ids": rng.integers(
            0, TOTAL_IDS, size=(batch_size, NUM_FIELDS)
        ).astype(np.int32),
    }
    labels = rng.integers(0, 2, batch_size).astype(np.int64)
    elapsed, _, truncated = _timed_windows(
        trainer, features, labels, steps_per_window, windows, warmup,
        clock,
    )
    return _window_result(
        elapsed, batch_size, steps_per_window, truncated
    )


def _device_transfer_mb_per_s(mb=8):
    """One d2h round of `mb` MB, recorded beside the PS cells as
    context: rows and row-gradients cross the host<->device hop every
    step. None on the CPU, where there is no hop; on a device a failure
    is a failure."""
    import jax.numpy as jnp

    if jax.default_backend() == "cpu":
        return None
    n = mb * (1 << 20) // 4
    best = float("inf")
    for i in range(2):
        x = jax.block_until_ready(jnp.ones((n,), jnp.float32) * (i + 1))
        t0 = time.perf_counter()
        np.asarray(x)
        best = min(best, time.perf_counter() - t0)
    return round(mb / best, 1)


def bench_deepfm_ps(batch_size=16384, steps=6, warmup=4, num_ps=2,
                    repeats=3, clock=None):
    # warmup=4 covers each of the 4 distinct id batches once, so measured
    # steps hit warm PS rows (the r4 run-to-run spread — 3.6k vs 7.2k on
    # identical configs — was cold-row lazy init landing inside the timed
    # window of whichever run compiled first). Batch 16384: the
    # push-thread overlap needs enough per-step RPC work to amortize its
    # contention with prefetch on a single-core host.
    """The other half of the DeepFM north star (BASELINE.json: "large
    embedding_service + elastic worker preemption"): DeepFM with its
    wide/deep tables PS-RESIDENT on real localhost PS shards, one worker
    pulling rows / pushing IndexedSlices per step. The four legacy
    configs — (serial | overlapped push) x (f32 | bf16 wire) at
    ``num_ps`` shards — are the fixed-shard slice of the full
    ``matrix.bench_ps_matrix``; the matrix adds the shard-count axis.
    Each config's headline is the median over ``repeats`` runs with the
    phase breakdown (now including the serialize/wire/apply split inside
    push_gradients) from the run closest to the median."""
    batches = _matrix.make_batches(batch_size)
    configs = (
        ("serialized", False, "float32"),
        ("serialized_bf16_wire", False, "bfloat16"),
        ("pipelined", True, "float32"),
        ("pipelined_bf16_wire", True, "bfloat16"),
        # The quantized wire: int8 block-scaled dense grads (error
        # feedback) + bf16 embedding legs, on the packed transport.
        ("pipelined_int8_wire", True, "int8"),
    )
    out = {
        "repeats": repeats,
        "loadavg_start": os.getloadavg()[0],
        # Context for flagged runs: rows and row-grads cross the
        # host<->device hop every step — record it like loadavg.
        "device_transfer_mb_per_s": _device_transfer_mb_per_s(),
    }
    for name, pipelined, wire in configs:
        if clock is not None and clock.expired and name != "serialized":
            out[name] = {"skipped": "budget"}
            continue
        out[name] = _matrix._run_cell(
            batches, steps, warmup, num_ps, pipelined, wire, repeats,
            clock,
        )
    out["loadavg_end"] = os.getloadavg()[0]
    if out.get("serialized", {}).get("examples_per_sec"):
        # Derived ratios inherit contamination: a flagged/truncated
        # median must not silently feed a clean-looking headline
        # speedup.
        def ratio(num, den):
            if not out.get(num, {}).get("examples_per_sec"):
                return None, False
            value = (
                out[num]["examples_per_sec"]
                / out[den]["examples_per_sec"]
            )
            flagged = any(
                out[c].get("truncated") or out[c].get("run_spread", 1)
                > 1.25
                for c in (num, den)
            )
            return value, flagged

        speedup, flagged = ratio("pipelined", "serialized")
        if speedup:
            out["overlap_speedup"] = speedup
            if flagged:
                out["overlap_speedup_contaminated"] = True
        speedup, flagged = ratio("serialized_bf16_wire", "serialized")
        if speedup:
            out["bf16_wire_speedup"] = speedup
            if flagged:
                out["bf16_wire_speedup_contaminated"] = True
        speedup, flagged = ratio("pipelined_int8_wire", "pipelined")
        if speedup:
            out["int8_wire_speedup"] = speedup
            if flagged:
                out["int8_wire_speedup_contaminated"] = True
    return out


def bench_elastic_rejoin():
    """The third north-star metric (BASELINE.json): seconds for a job that
    loses a worker to SIGKILL to have its replacement back in the job
    (detection + task recovery + relaunch + re-init + first RPC).
    Runs the real CLI cluster on the CPU platform — this process holds
    the chip, and a chip belongs to one process — so every number of
    this cell is a CPU measurement and says so (`"platform": "cpu"`).
    Rejoin on the chip is what `chip_smoke.py` phase B observes.

    Cells (the recompile-free-elasticity additions):
      rejoin_s              cold relaunch, best-of-2, no compile cache —
                            comparable with every earlier round;
      rejoin_warm_cache_s   one more drill with the persistent compile
                            cache on (common/compile_cache.py): the
                            replacement worker rehydrates its step from
                            the disk entries its first incarnation
                            wrote, so the rejoin no longer contains an
                            XLA compile;
      regroup_cold_s /      in-process world-RESHAPE latency (see
      regroup_warm_s        bench/regroup.py): what a SURVIVOR pays to
                            step in a changed world, with and without a
                            speculatively prebuilt executable.
    """
    import subprocess
    import sys
    import tempfile

    repo = os.path.dirname(
        os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
    )
    try:
        sys.path.insert(0, os.path.join(repo, "tools"))
        sys.path.insert(0, os.path.join(repo, "tests"))
        import test_module
        from elastic_drill import run_drill

        from elasticdl_tpu.data.recordfile import RecordFileWriter

        out = {"platform": "cpu"}
        with tempfile.TemporaryDirectory() as d:
            data = os.path.join(d, "linear.edlr")
            with RecordFileWriter(data) as w:
                for r in test_module.make_linear_records(256):
                    w.write(r)
            # Best-of-2: rejoin time is control-plane latency on a shared
            # single-core host; one run can absorb seconds of unrelated
            # load (VERDICT r3 asked every host-bound bench for best-of-N).
            results = [
                run_drill(
                    data,
                    model_zoo=os.path.join(repo, "tests"),
                    model_def="test_module",
                    num_workers=2,
                    num_ps=1,
                    num_epochs=300,
                    # Cold must be COLD: jax's own switch turns the
                    # persistent cache off (rejoin_s is the historical
                    # cold series).
                    env_overrides={
                        "JAX_PLATFORMS": "cpu",
                        "JAX_ENABLE_COMPILATION_CACHE": "false",
                    },
                    timeout=600,
                )
                for _ in range(2)
            ]
            ok = [r for r in results if r.get("rejoin_s") is not None]
            best = (
                min(ok, key=lambda r: r["rejoin_s"]) if ok else results[0]
            )
            out.update(
                {
                    "rejoin_s": best.get("rejoin_s"),
                    "rejoin_s_runs": [
                        r.get("rejoin_s") for r in results
                    ],
                    "best_of_n": 2,
                    "completed": best.get("completed"),
                    "relaunched": best.get("relaunched"),
                }
            )
            # Warm-cache drill: the job's own pre-kill compiles populate
            # the cache (at the one place every process resolves —
            # a path that moved between runs would never hit); the
            # SIGKILLed worker's replacement rehydrates.
            warm = run_drill(
                data,
                model_zoo=os.path.join(repo, "tests"),
                model_def="test_module",
                num_workers=2,
                num_ps=1,
                num_epochs=300,
                env_overrides={"JAX_PLATFORMS": "cpu"},
                timeout=600,
            )
            out["rejoin_warm_cache_s"] = warm.get("rejoin_s")
            out["rejoin_warm_completed"] = warm.get("completed")
        # In-process regroup cells, in their own virtual-8-device
        # subprocess so this process's backend stays untouched.
        env = dict(os.environ)
        env["JAX_PLATFORMS"] = "cpu"
        env["XLA_FLAGS"] = "--xla_force_host_platform_device_count=8"
        # Cold must be COLD: no persistent cache for the subprocess.
        env["JAX_ENABLE_COMPILATION_CACHE"] = "false"
        try:
            proc = subprocess.run(
                [sys.executable, "-m", "elasticdl_tpu.bench.regroup"],
                capture_output=True,
                text=True,
                env=env,
                cwd=repo,
                timeout=300,
            )
            line = next(
                (
                    ln
                    for ln in proc.stdout.splitlines()
                    if ln.startswith("REGROUP_RESULT ")
                ),
                None,
            )
            if line:
                regroup = json.loads(line[len("REGROUP_RESULT "):])
                for key in (
                    "regroup_cold_s",
                    "regroup_warm_s",
                    "speculative_consumed",
                    "error",
                ):
                    if key in regroup:
                        out[key] = regroup[key]
            else:
                out["regroup_error"] = (proc.stderr or "no output")[
                    -200:
                ]
        except Exception as e:
            out["regroup_error"] = str(e)[:200]
        return out
    except Exception as e:  # never let the drill sink the whole bench
        return {"rejoin_s": None, "error": str(e)[:200]}
