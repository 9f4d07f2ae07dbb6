#!/usr/bin/env python3
"""The quickest proof that the elastic training job still starts on the chip.

    python chip_smoke.py            # one chip: phases A, B, C
    python chip_smoke.py --chips 4  # four chips: the four-chip phase only

Every phase is a real job through `edl train` (`elasticdl_tpu.client.main`,
`--instance_backend local_process`): the master runs in the `edl train`
process, the worker (and the PS shards) are its children, and ONLY the
worker opens the accelerator — a chip belongs to one process. This script
therefore never initialises a jax backend of its own: the device facts come
from a throwaway child that exits before the first job starts, and from the
workers' own `worker_devices` reports.

  A  AllReduce, flagship LM (vocab 32768, d_model 1024, 8 x 128 heads,
     12 layers, S=4096, bf16 activations, minibatch 4) over seeded Markov
     tokens: loss finite and falling, the step contains the Pallas call,
     records accounted exactly.
  B  the same job with its worker SIGKILLed after a few steps
     (tools/elastic_drill.run_drill): the master relaunches it, the
     replacement gets the chip back and rehydrates its step from the
     persistent compile cache, the job finishes with the planned records.
  C  ParameterServerStrategy, 2 PS shards + 1 worker, DeepFM at its Criteo
     shapes, minibatch 16384: the worker's step runs on the TPU, the PS
     shards and the master never open a device.

Each phase prints one JSON line. The LAST line is
`{"ok": true, "device": {"platform": "tpu", "kind": ..., "count": N}}` and
is printed only when every phase passed on a TPU; on a machine without one
the script fails before any job starts. There is no CPU mode: the CPU
rehearsal (tests/test_chip_smoke.py) calls the same phase functions with
tiny sizes.
"""

import argparse
import datetime
import glob
import json
import math
import os
import re
import subprocess
import sys
import tempfile
import time

REPO = os.path.dirname(os.path.abspath(__file__))
sys.path.insert(0, REPO)
sys.path.insert(0, os.path.join(REPO, "tools"))

FLAGSHIP_LM = "elasticdl_tpu.models.transformer.transformer_lm_flagship"
DEEPFM_PS = "elasticdl_tpu.models.dac_ctr.deepfm_ps"
# Per-step loss agreement, four chips vs one device, same seed and global
# batch: bf16 activations and a different reduction order (mean of four
# shard means vs one batch mean) move the loss in its third digit.
FOUR_CHIP_REL_TOL = 0.02

_STEP_LINE = re.compile(
    r"^\[(\d{4}-\d\d-\d\d \d\d:\d\d:\d\d),(\d{3})\].*"
    r"Step (\d+) \((?:version|lease) \d+\) loss ([-+0-9.eE]+|nan|inf)"
)


class PhaseFailed(Exception):
    pass


def check(cond, what):
    if not cond:
        raise PhaseFailed(what)


# ---------- data, from a seed ----------


def write_lm_records(path, num_sequences, seq_len, vocab, seed):
    from elasticdl_tpu.data.example import encode_example
    from elasticdl_tpu.data.gen.synthetic import synthetic_lm_tokens
    from elasticdl_tpu.data.recordfile import RecordFileWriter

    tokens = synthetic_lm_tokens(
        num_sequences, seq_len, vocab=vocab, branching=4, seed=seed
    )
    with RecordFileWriter(path) as w:
        for seq in tokens:
            w.write(encode_example({"tokens": seq}))


def lm_data(workdir, steps, minibatch, seq_len, vocab, seed):
    """(record file, record count) for `steps` minibatches of LM data."""
    os.makedirs(workdir, exist_ok=True)
    data = os.path.join(workdir, "lm.edlr")
    write_lm_records(data, steps * minibatch, seq_len, vocab, seed)
    return data, steps * minibatch


def write_criteo_records(path, num_examples, seed):
    from elasticdl_tpu.data.gen.criteo import iter_criteo_records
    from elasticdl_tpu.data.recordfile import RecordFileWriter

    with RecordFileWriter(path) as w:
        for record in iter_criteo_records(num_examples, seed=seed):
            w.write(record)


# ---------- one job ----------


# Run in EVERY python process of a job (it is that job's sitecustomize):
# each role keeps a file saying whether it has initialised a jax backend.
# A thread rewrites it twice a second, because roles end by SIGTERM or
# SIGKILL as often as by returning, and atexit sees neither.
_ROLE_HOOK = '''
import atexit, json, os, sys, threading, time

_seen = [False]  # sticky: a worker drops its backends before it exits

def _report():
    # Never import here: this thread must not race the role's own imports.
    bridge = sys.modules.get("jax._src.xla_bridge")
    probe = getattr(bridge, "backends_are_initialized", None)
    up = _seen[0] = _seen[0] or bool(probe and probe())
    path = os.path.join(os.environ["CHIP_SMOKE_ROLES"], "%d.json" % os.getpid())
    with open(path + ".tmp", "w") as f:
        json.dump({"role": os.environ.get("ELASTICDL_ROLE", "master"),
                   "jax_imported": "jax" in sys.modules,
                   "backend_initialized": up}, f)
    os.replace(path + ".tmp", path)

def _loop():
    while True:
        time.sleep(0.5)
        _report()

if os.environ.get("CHIP_SMOKE_ROLES"):
    atexit.register(_report)
    threading.Thread(target=_loop, daemon=True).start()
'''


def job_env(workdir):
    """What a phase adds to the job's environment: where the roles report
    (events, and the backend watch above), and where jax/XLA dump the
    programs they lower/compile — both dumps are outside the
    compile-cache key."""
    hook = os.path.join(workdir, "hook")
    roles = os.path.join(workdir, "roles")
    os.makedirs(hook, exist_ok=True)
    os.makedirs(roles, exist_ok=True)
    with open(os.path.join(hook, "sitecustomize.py"), "w") as f:
        f.write(_ROLE_HOOK)
    return {
        "PYTHONPATH": f"{hook}:{REPO}",
        "CHIP_SMOKE_ROLES": roles,
        "ELASTICDL_OBS_DIR": os.path.join(workdir, "obs"),
        "JAX_DUMP_IR_TO": os.path.join(workdir, "ir"),
        "XLA_FLAGS": (
            os.environ.get("XLA_FLAGS", "")
            + f" --xla_dump_to={os.path.join(workdir, 'hlo')}"
            " --xla_dump_hlo_as_text --xla_dump_hlo_module_re=.*step.*"
        ).strip(),
    }


def run_job(workdir, model_def, data, minibatch, strategy, num_ps, seed,
            timeout, extra_env=None, scenario="none"):
    """One `edl train` job to its end under tools/elastic_drill.run_drill
    (which polls the master's status, accounts records, keeps the log,
    injects the scenario's fault, and reaps the job's process group).
    Returns (drill result, whole log text)."""
    from elastic_drill import run_drill

    env = job_env(workdir)
    env.update(extra_env or {})
    log_path = os.path.join(workdir, "job.log")
    failure = None
    try:
        result = run_drill(
            data, model_zoo=REPO, model_def=model_def, num_workers=1,
            num_ps=num_ps, num_epochs=1, minibatch_size=minibatch,
            records_per_task=2 * minibatch, strategy=strategy,
            extra_args=("--no_shuffle_shards", "--log_loss_steps", "1",
                        "--seed", str(seed)),
            env_overrides=env, timeout=timeout, scenario=scenario,
            log_path=log_path,
        )
    except (RuntimeError, subprocess.TimeoutExpired) as e:
        failure = e  # the job wedged or died: say what its log says
    with open(log_path) as f:
        log = f.read()
    keep_log(workdir, log)
    check(failure is None,
          f"{failure!r}; log tail: {log[-3000:]}")
    check(result["completed"],
          f"edl train did not complete; log tail: {log[-1500:]}")
    check(not result["leftover_procs"],
          f"job processes outlived the job: {result['leftover_procs']}")
    return result, log


def keep_log(workdir, log):
    """The end of every job's log under chiprun_out/ (what the chip tool
    brings back; the work directory itself is temporary)."""
    out_dir = os.path.join(REPO, "chiprun_out", "chip_smoke")
    os.makedirs(out_dir, exist_ok=True)
    name = os.path.basename(os.path.normpath(workdir)) + ".log"
    with open(os.path.join(out_dir, name), "w") as f:
        f.write(log[-400_000:])


# ---------- what the roles reported ----------


def read_events(workdir):
    path = os.path.join(workdir, "obs", "events.jsonl")
    if not os.path.exists(path):
        return []
    with open(path) as f:
        return [json.loads(line) for line in f if line.strip()]


def _log_epoch(date, millis):
    dt = datetime.datetime.strptime(date, "%Y-%m-%d %H:%M:%S")
    return dt.timestamp() + int(millis) / 1000.0


def step_losses(log_text):
    """[(epoch seconds, step, loss)] from the worker's per-step log."""
    out = []
    for line in log_text.splitlines():
        m = _STEP_LINE.match(line)
        if m:
            out.append(
                (_log_epoch(m.group(1), m.group(2)), int(m.group(3)),
                 float(m.group(4)))
            )
    return out


def worker_report(workdir, log_text, since=0.0):
    """The facts a phase prints, from the worker's own events and log,
    restricted to what happened after `since` (a replacement worker)."""
    events = [e for e in read_events(workdir) if e.get("ts", 0) >= since]
    workers = [
        e for e in events if str(e.get("role", "")).startswith("worker")
    ]
    devices = [e for e in workers if e["kind"] == "worker_devices"]
    compiles = [e for e in workers if e["kind"] == "compile"]
    hits = [e for e in workers if e["kind"] == "compile_cache_hit"]
    memory = [e for e in workers if e["kind"] == "worker_exit_memory"]
    losses = [x for x in step_losses(log_text) if x[0] >= since]
    report = {
        "steps": len(losses),
        "loss_first": losses[0][2] if losses else None,
        "loss_last": losses[-1][2] if losses else None,
        "losses": [round(x[2], 4) for x in losses],
        "compile_seconds": round(sum(e["seconds"] for e in compiles), 2),
        "cache_misses": len(compiles),
        "cache_hits": len(hits),
        "step_compile": [
            {"cache_hit": e["kind"] == "compile_cache_hit",
             "seconds": e["seconds"]}
            for e in workers
            if e["kind"] in ("compile", "compile_cache_hit")
            and e.get("fn", "").endswith("_step")
        ],
        "platform": devices[-1]["platform"] if devices else None,
        "device_kind": devices[-1]["device_kind"] if devices else None,
        "device_count": devices[-1]["count"] if devices else None,
    }
    if memory:
        stats = memory[-1].get("device_stats") or {}
        report["device_bytes_in_use"] = {
            d: s.get("bytes_in_use") for d, s in stats.items()
        }
        report["peak_device_bytes"] = max(
            (s.get("peak_bytes_in_use", 0) for s in stats.values()),
            default=None,
        )
    return report


def program_dumps(workdir):
    """What jax lowered and XLA compiled for the job's training step
    (the one jitted function with "step" in its name): whether the
    Pallas call and an all-reduce are in it. The lowered
    StableHLO is dumped on every lowering; compiled HLO only when XLA
    really compiled (a persistent-cache hit compiles nothing)."""
    def text(pattern, skip=()):
        parts = []
        for path in glob.glob(os.path.join(workdir, pattern)):
            if not any(word in path for word in skip):
                with open(path) as f:
                    parts.append(f.read())
        return "".join(parts)

    lowered = text("ir/*step*")
    compiled = text(
        "hlo/*step*after_optimizations*.txt",
        skip=("buffer-assignment", "memory-usage"),
    )
    return {
        "lowered_has_pallas_call": "tpu_custom_call" in lowered,
        "compiled_has_pallas_call": (
            "tpu_custom_call" in compiled if compiled else None
        ),
        "compiled_has_all_reduce": (
            "all-reduce" in compiled if compiled else None
        ),
        "lowered_batch_sharded": bool(
            re.search(r'sdy\.sharding = #sdy\.sharding<@mesh, \[\{"data"',
                      lowered)
        ),
    }


def check_training(report, records_done, records_planned, falling=True):
    check(report["steps"] > 0, "the worker logged no step")
    check(
        all(math.isfinite(x) for x in report["losses"]),
        f"non-finite loss: {report['losses']}",
    )
    if falling:
        check(
            report["loss_last"] < report["loss_first"],
            f"loss did not fall: {report['losses']}",
        )
    check(
        records_done == records_planned,
        f"records done {records_done} != planned {records_planned}",
    )


def role_backends(workdir):
    """{role: did it initialise a jax backend} over every process the
    job ran, from inside those processes (see _ROLE_HOOK)."""
    out = {}
    for path in glob.glob(os.path.join(workdir, "roles", "*.json")):
        with open(path) as f:
            rec = json.load(f)
        out[rec["role"]] = out.get(rec["role"], False) or (
            rec["backend_initialized"]
        )
    return out


def check_only_workers_opened_devices(out, workdir):
    out["backend_initialized_by_role"] = role_backends(workdir)
    roles = out["backend_initialized_by_role"]
    check("master" in roles, f"the master never reported: {roles}")
    for role, opened in roles.items():
        check(
            opened == role.startswith("worker"),
            f"only workers may open a device, and must: {roles}",
        )


# ---------- phases ----------


def phase_allreduce_lm(workdir, model_def=FLAGSHIP_LM, seq_len=4096,
                       vocab=32768, minibatch=4, steps=20, seed=0,
                       timeout=360, strategy="AllreduceStrategy",
                       extra_env=None, name="A_allreduce_flagship_lm"):
    data, records = lm_data(workdir, steps, minibatch, seq_len, vocab, seed)
    t0 = time.time()
    result, log = run_job(workdir, model_def, data, minibatch, strategy, 0,
                          seed, timeout, extra_env)
    out = {"phase": name, "job_seconds": round(time.time() - t0, 1),
           "records_planned": records,
           "records_done": result["records_done"]}
    out.update(worker_report(workdir, log))
    out.update(program_dumps(workdir))
    check_training(out, out["records_done"], records)
    check_only_workers_opened_devices(out, workdir)
    return out


def phase_kill(workdir, model_def=FLAGSHIP_LM, seq_len=4096, vocab=32768,
               minibatch=4, steps=20, seed=0, timeout=360,
               extra_env=None):
    data, records = lm_data(workdir, steps, minibatch, seq_len, vocab, seed)
    result, log = run_job(workdir, model_def, data, minibatch,
                          "AllreduceStrategy", 0, seed, timeout, extra_env,
                          scenario="worker-kill")
    killed_at = result.get("killed_at") or 0.0
    after = worker_report(workdir, log, since=killed_at)
    first_step_after = [x for x in step_losses(log) if x[0] >= killed_at]
    out = {
        "phase": "B_worker_kill",
        "relaunched": result["relaunched"],
        "killed_worker_pid": result["killed_worker"],
        "replacement_worker_pid": result.get("replacement_worker"),
        "records_at_kill": result["records_at_kill"],
        "records_planned": records,
        "records_done": result["records_done"],
        "rejoin_first_rpc_seconds": result["rejoin_s"],
        "kill_to_first_step_seconds": (
            round(first_step_after[0][0] - killed_at, 2)
            if first_step_after and killed_at else None
        ),
        "replacement": after,
    }
    out.update(program_dumps(workdir))
    check(result["relaunched"], "the master never relaunched the worker")
    check_training(after, result["records_done"], records, falling=False)
    check(
        after["step_compile"]
        and all(c["cache_hit"] for c in after["step_compile"]),
        "the replacement worker compiled its step cold: "
        f"{after['step_compile']}",
    )
    check_only_workers_opened_devices(out, workdir)
    return out


def phase_ps(workdir, minibatch=16384, steps=6, seed=0, timeout=360,
             extra_env=None):
    os.makedirs(workdir, exist_ok=True)
    data = os.path.join(workdir, "criteo.edlr")
    records = steps * minibatch
    write_criteo_records(data, records, seed)
    t0 = time.time()
    result, log = run_job(workdir, DEEPFM_PS, data, minibatch,
                          "ParameterServerStrategy", 2, seed, timeout,
                          extra_env)
    out = {"phase": "C_parameter_server_deepfm",
           "job_seconds": round(time.time() - t0, 1),
           "records_planned": records,
           "records_done": result["records_done"]}
    out.update(worker_report(workdir, log))
    out["native_kernels_loaded"] = "Loaded native kernels" in log
    check_training(out, out["records_done"], records, falling=False)
    check(out["native_kernels_loaded"], "the PS ran without native kernels")
    check_only_workers_opened_devices(out, workdir)
    check(
        {"ps-0", "ps-1"} <= set(out["backend_initialized_by_role"]),
        f"a PS never reported: {out['backend_initialized_by_role']}",
    )
    return out


def phase_four_chips(workdir, steps=6, seed=0, timeout=300, **sizes):
    """Phase A's job on all local chips at global minibatch 16 (4 per
    chip), and the comparison: the same seed and global batch on four
    chips and on one device of the same host (the plain Local trainer,
    which runs on the first device only). A global batch of 16 does not
    fit one 16 GB device at these widths, so the pair runs at 4."""
    full = phase_allreduce_lm(
        os.path.join(workdir, "four_b16"), minibatch=16, steps=steps,
        seed=seed, timeout=timeout, name="four_chips_global_batch_16",
        # Compile for real, so the compiled step can be read.
        extra_env={"JAX_ENABLE_COMPILATION_CACHE": "false"}, **sizes,
    )
    emit(full)
    check(full["compiled_has_all_reduce"],
          "no all-reduce in the compiled step")
    in_use = full.get("device_bytes_in_use") or {}
    check(
        len(in_use) == full["device_count"]
        and all(v and v > 0 for v in in_use.values()),
        f"not every device holds data: {in_use}",
    )
    four = phase_allreduce_lm(
        os.path.join(workdir, "four_b4"), minibatch=4, steps=steps,
        seed=seed, timeout=timeout, name="four_chips_global_batch_4",
        **sizes,
    )
    emit(four)
    one = phase_allreduce_lm(
        os.path.join(workdir, "one_b4"), minibatch=4, steps=steps,
        seed=seed, timeout=timeout, strategy="Local",
        name="one_device_global_batch_4", **sizes,
    )
    emit(one)
    check(len(four["losses"]) == len(one["losses"]),
          "the two runs logged different step counts")
    worst = max(
        abs(a - b) / max(abs(b), 1e-9)
        for a, b in zip(four["losses"], one["losses"])
    )
    check(worst <= FOUR_CHIP_REL_TOL,
          f"per-step losses disagree: worst rel diff {worst:.4f}")
    return {"phase": "four_vs_one", "rel_tolerance": FOUR_CHIP_REL_TOL,
            "worst_rel_diff": round(worst, 5),
            "losses_four": four["losses"], "losses_one": one["losses"],
            "jobs": [full, four, one]}


# ---------- the device, and the last line ----------


def query_device():
    """What jax finds, asked in a child that exits before any job
    starts: this process must never hold the chip its workers need."""
    probe = subprocess.run(
        [sys.executable, "-c",
         "import json, jax; d = jax.devices(); print(json.dumps("
         "{'platform': d[0].platform, 'kind': d[0].device_kind, "
         "'count': len(d)}))"],
        capture_output=True, text=True, timeout=300,
    )
    if probe.returncode != 0:
        raise SystemExit(
            f"chip_smoke: jax could not open a device: {probe.stderr[-800:]}"
        )
    return json.loads(probe.stdout.strip().splitlines()[-1])


def final_line(device):
    """The one line the driver reads; refuses anything but a TPU."""
    if device["platform"] != "tpu":
        raise SystemExit(
            "chip_smoke: no TPU — jax found platform "
            f"{device['platform']!r} ({device['kind']}); this script has "
            "no CPU mode"
        )
    return json.dumps({
        "ok": True,
        "device": {"platform": device["platform"], "kind": device["kind"],
                   "count": device["count"]},
    })


def emit(obj):
    print(json.dumps(obj), flush=True)


def main(argv=None):
    parser = argparse.ArgumentParser(description=__doc__.split("\n")[0])
    parser.add_argument("--chips", type=int, choices=(1, 4), default=1)
    parser.add_argument("--seed", type=int, default=0)
    args = parser.parse_args(argv)

    if not os.path.isdir(os.path.join(REPO, "elasticdl_tpu")):
        raise SystemExit(
            "chip_smoke: no elasticdl_tpu package beside this script; it "
            "drives the program, it is not the program"
        )
    device = query_device()
    final_line(device)  # fail before any job starts when there is no TPU
    if device["count"] != args.chips:
        raise SystemExit(
            f"chip_smoke: --chips {args.chips} but jax sees "
            f"{device['count']} device(s)"
        )
    from elasticdl_tpu.common.compile_cache import resolve_cache_dir

    emit({"device": device, "compile_cache_dir": resolve_cache_dir()})
    failed = []

    def run(phase, workdir):
        try:
            out = phase(workdir, seed=args.seed)
        except PhaseFailed as e:
            emit({"phase": phase.__name__, "ok": False, "error": str(e)})
            failed.append(phase.__name__)
            return
        jobs = out.pop("jobs", [out])
        emit(out)
        for job in jobs:
            # What the worker itself reported, against what jax found.
            facts = job.get("replacement", job)
            saw = {"platform": facts["platform"],
                   "kind": facts["device_kind"],
                   "count": facts["device_count"]}
            if saw != device:
                failed.append(f"{job['phase']}: worker ran on {saw}")
            if job.get("lowered_has_pallas_call") is False:
                failed.append(f"{job['phase']}: no Pallas call in the step")

    with tempfile.TemporaryDirectory(prefix="chip_smoke_") as tmp:
        if args.chips == 4:
            run(phase_four_chips, os.path.join(tmp, "four"))
        else:
            for phase, sub in ((phase_allreduce_lm, "a"),
                               (phase_kill, "b"), (phase_ps, "c")):
                run(phase, os.path.join(tmp, sub))
    if failed:
        raise SystemExit(f"chip_smoke: FAILED: {failed}")
    print(final_line(device), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
