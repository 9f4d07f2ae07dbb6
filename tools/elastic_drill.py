"""Elasticity drills: inject a fault into a REAL local job, measure recovery.

The BASELINE third north-star metric is elastic rejoin time — how long a
job takes to resume making progress after losing a worker (the reference's
headline capability, benchmarked in docs/benchmark/report_cn.md:66-96 as
elastic-vs-gang job time). This tool grew from that single drill into a
chaos-scenario runner (docs/ROBUSTNESS.md keeps the catalog):

  none          no fault: the job runs to its end under the same
                observation (status polls, records accounting, log,
                zero-leftover check) — the control every drill is read
                against, and how chip_smoke.py runs its plain jobs.
  worker-kill   SIGKILL a worker that provably owns an in-flight task;
                assert task recovery + relaunch + rejoin (the original
                drill, unchanged).
  ps-flap       SIGKILL a parameter server mid-job; the workers must ride
                the outage on the rpc retry plane, the master must relaunch
                the PS, and the re-seed path must restore its shard.
  rpc-brownout  no process dies: a seeded ELASTICDL_CHAOS schedule injects
                UNAVAILABLE/latency faults into the job's own RPC plane;
                the job must complete with nonzero rpc_retries_total.
  master-stall  SIGSTOP the master (the `edl train` process) for several
                seconds with shrunk control-plane deadlines; workers must
                retry through the stall instead of hanging or dying.

Every scenario runs a real `edl train` job (local_process backend) as a
subprocess, polls get_job_status, injects its fault once training
provably progresses, drains to completion, scrapes rpc retry/breaker
counters from each role's advertised /metrics endpoint, and checks for
leftover processes at exit. Usable standalone
(`python tools/elastic_drill.py --scenario ps-flap`) and from the e2e
tests.
"""

import argparse
import json
import os
import re
import signal
import socket
import subprocess
import sys
import tempfile
import time
import urllib.request

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path.insert(0, REPO)

from elasticdl_tpu.common import knobs  # noqa: E402

SCENARIOS = (
    "none",
    "worker-kill",
    "ps-flap",
    "rpc-brownout",
    "master-stall",
    "straggler",
    "straggler-recovery",
    "backup-task",
    "deadline-scale",
    "preemption-wave",
    "input-starve",
    "master-kill",
    "master-kill-during-scale",
)

# Scenarios that close the loop through the policy engine: they need the
# master's aggregator (obs_dir) because that is the engine's input.
POLICY_SCENARIOS = (
    "straggler-recovery",
    "backup-task",
    "deadline-scale",
)

# Scenarios that SIGKILL the master itself (via the deterministic local
# chaos kill fault) and relaunch it over the journal: they need obs_dir
# both for the journal directory and the recovery event trail.
MASTER_KILL_SCENARIOS = (
    "master-kill",
    "master-kill-during-scale",
)


def _policy_env(**overrides):
    """ELASTICDL_POLICY_* knobs tightened for drill time budgets: 1 s
    ticks, 2-tick hysteresis, decisions allowed every 10 s."""
    env = {
        "ELASTICDL_POLICY": "1",
        "ELASTICDL_POLICY_INTERVAL": "1.0",
        "ELASTICDL_POLICY_HYSTERESIS": "2",
        "ELASTICDL_POLICY_COOLDOWN_SECONDS": "10",
        "ELASTICDL_AGGREGATOR_INTERVAL": "1.0",
    }
    env.update({k: str(v) for k, v in overrides.items()})
    return env


def _free_port():
    s = socket.socket()
    s.bind(("127.0.0.1", 0))
    port = s.getsockname()[1]
    s.close()
    return port


def free_coordinator_block(width=16, attempts=64, lane=0, lanes=1):
    """A base port whose whole [base, base+width) rotation block binds
    clean right now. Fixed well-known coordinator ports poison drill
    reruns: a failed run's orphan can sit in RegisterTask on the old
    block and absorb the next run's rendezvous.

    The block is probed and RELEASED before the job binds it, so two
    callers probing at once could both win the same block: concurrent
    callers (pytest-xdist workers) each pass their own `lane` of `lanes`
    and draw from disjoint slices of the range."""
    import random

    # Stay BELOW the kernel ephemeral range (32768+): _free_port draws the
    # master port from it, and a master port landing inside the rotation
    # block trips validate_args' overlap rejection.
    lo, hi = 20000, 32700 - width
    span = (hi - lo) // lanes
    lo += (lane % lanes) * span
    for _ in range(attempts):
        base = random.randrange(lo, lo + span)
        ok = True
        for p in range(base, base + width):
            s = socket.socket()
            try:
                s.bind(("127.0.0.1", p))
            except OSError:
                ok = False
                break
            finally:
                s.close()
        if ok:
            return base
    raise RuntimeError("no free coordinator port block found")


def scenario_env(scenario):
    """Extra environment a scenario injects into the JOB's processes (the
    drill process itself stays fault-free)."""
    if scenario == "rpc-brownout":
        # Seeded schedule, replayed identically by every rerun: server-side
        # UNAVAILABLE windows on the PS data plane (long enough to exhaust
        # one retry budget and exercise the degraded-shard re-seed path),
        # latency on gradient pushes, and client-side UNAVAILABLE on the
        # workers' task pulls.
        schedule = {
            "seed": 20260803,
            "rules": [
                {
                    "method": "pull_dense_parameters",
                    "kind": "unavailable",
                    "start": 6,
                    "count": 8,
                    "side": "server",
                },
                {
                    "method": "push_gradients",
                    "kind": "latency",
                    "latency_s": 0.1,
                    "start": 4,
                    "count": 30,
                    "side": "server",
                },
                {
                    "method": "get_task",
                    "kind": "unavailable",
                    "start": 5,
                    "count": 6,
                    "side": "client",
                },
            ],
        }
        return {"ELASTICDL_CHAOS": json.dumps(schedule)}
    if scenario == "straggler":
        # No process dies and nothing fails: worker-0's data-plane RPCs
        # just get slow (role-targeted client-side latency), making it a
        # straggler the master's telemetry aggregator must FLAG — the
        # brownout drill proved the job survives faults; this one proves
        # the framework *tells you who is slow*. A fast aggregation
        # interval keeps the detection well inside the drill budget.
        schedule = {
            "seed": 20260803,
            "rules": [
                {
                    "method": "push_gradients",
                    "kind": "latency",
                    "latency_s": 0.25,
                    "start": 0,
                    "count": -1,
                    "side": "client",
                    "role": "worker-0",
                },
                {
                    "method": "pull_dense_parameters",
                    "kind": "latency",
                    "latency_s": 0.1,
                    "start": 0,
                    "count": -1,
                    "side": "client",
                    "role": "worker-0",
                },
            ],
        }
        return {
            "ELASTICDL_CHAOS": json.dumps(schedule),
            "ELASTICDL_AGGREGATOR_INTERVAL": "1.0",
        }
    if scenario == "straggler-recovery":
        # Same role-targeted slowdown as `straggler`, but starting only
        # after a healthy preamble (the drill measures the pre-fault
        # throughput baseline there) — and the policy engine is ON: the
        # master must blacklist the straggler, recover its tasks, and
        # throughput must RETURN, not just be flagged.
        schedule = {
            "seed": 20260807,
            "rules": [
                {
                    "method": "push_gradients",
                    "kind": "latency",
                    "latency_s": 0.3,
                    "start": 30,
                    "count": -1,
                    "side": "client",
                    "role": "worker-0",
                },
                {
                    "method": "pull_dense_parameters",
                    "kind": "latency",
                    "latency_s": 0.15,
                    "start": 30,
                    "count": -1,
                    "side": "client",
                    "role": "worker-0",
                },
            ],
        }
        env = _policy_env(
            ELASTICDL_POLICY_STRAGGLER_SCORE="2.5",
            ELASTICDL_POLICY_BLACKLIST_SECONDS="300",
            ELASTICDL_POLICY_MAX_BACKUPS="0",
        )
        env["ELASTICDL_CHAOS"] = json.dumps(schedule)
        return env
    if scenario == "backup-task":
        # No chaos schedule: the drill SIGSTOPs a worker holding a task;
        # the backup rule must dispatch a speculative copy and the copy
        # must win (exactly-once accounting checked via records_done).
        # The straggler rule is parked so the frozen worker isn't
        # blacklisted out from under the backup race.
        return _policy_env(
            ELASTICDL_POLICY_MAX_BACKUPS="1",
            ELASTICDL_POLICY_BACKUP_FACTOR="2.5",
            ELASTICDL_POLICY_STRAGGLER_SCORE="1e9",
        )
    if scenario == "deadline-scale":
        # An ETA that provably overshoots the deadline: the policy must
        # announce the next world (world_hint) and scale workers up.
        return _policy_env(
            ELASTICDL_JOB_DEADLINE_SECONDS="20",
            ELASTICDL_POLICY_SCALE_STEP="1",
            ELASTICDL_POLICY_MAX_WORKERS="4",
            ELASTICDL_POLICY_STRAGGLER_SCORE="1e9",
            ELASTICDL_POLICY_MAX_BACKUPS="0",
        )
    if scenario == "input-starve":
        # A slow READER, not a slow network: per-record latency injected
        # at the data plane's local chaos point (datapath.read) on
        # worker-0 only. The trainer side starves on an empty prefetch
        # queue, the datapath telemetry must attribute it (read/starve
        # dominant, starvation alert on exactly worker-0) while the job
        # still completes with full records_done.
        schedule = {
            "seed": 20260807,
            "rules": [
                {
                    "method": "datapath.read",
                    "kind": "latency",
                    "latency_s": 0.008,
                    "start": 0,
                    "count": -1,
                    "side": "client",
                    "role": "worker-0",
                },
            ],
        }
        return {
            "ELASTICDL_CHAOS": json.dumps(schedule),
            "ELASTICDL_AGGREGATOR_INTERVAL": "1.0",
        }
    if scenario == "master-kill":
        # Deterministic master crash: the kill fault fires at the Nth
        # task dispatch (inject_local("master.dispatch") in the servicer,
        # counted across get_task + get_task_batch calls). start is high
        # enough that training provably progressed — and low enough that
        # plenty of work remains for the relaunched master to finish.
        schedule = {
            "seed": 20260807,
            "rules": [
                {
                    "method": "master.dispatch",
                    "kind": "kill",
                    "start": 40,
                    "count": 1,
                    "side": "client",
                },
            ],
        }
        return {"ELASTICDL_CHAOS": json.dumps(schedule)}
    if scenario == "master-kill-during-scale":
        # The nastier window: crash BETWEEN the world-hint announce
        # (journaled + emitted) and the scale actuation. The recovered
        # hint board must resume from the journaled seq, never regress.
        # The deadline is set far below any achievable drain time so the
        # overshoot condition holds on every policy tick once throughput
        # data exists — a generous deadline made the scale decision (and
        # therefore the kill) a race against fast workers.
        env = _policy_env(
            ELASTICDL_JOB_DEADLINE_SECONDS="5",
            ELASTICDL_POLICY_SCALE_STEP="1",
            ELASTICDL_POLICY_MAX_WORKERS="4",
            ELASTICDL_POLICY_STRAGGLER_SCORE="1e9",
            ELASTICDL_POLICY_MAX_BACKUPS="0",
        )
        env["ELASTICDL_CHAOS"] = json.dumps({
            "seed": 20260807,
            "rules": [
                {
                    "method": "master.scale",
                    "kind": "kill",
                    "start": 0,
                    "count": 1,
                    "side": "client",
                },
            ],
        })
        return env
    if scenario == "master-stall":
        # Shrink the control-plane deadlines below the stall length so the
        # workers' calls fail fast and RETRY through the stall (instead of
        # parking inside one long deadline and proving nothing).
        return {
            "ELASTICDL_RPC_DEADLINES": json.dumps(
                {
                    "get_task": 3.0,
                    "report_task_result": 3.0,
                    "report_version": 3.0,
                    "report_worker_liveness": 3.0,
                }
            )
        }
    return {}


class MetricsScraper:
    """Polls every advertised /metrics endpoint of a job and keeps the
    per-role high-water mark of the rpc retry/breaker/chaos counters
    (relaunched processes restart their counters at zero, so a plain last
    read would undercount)."""

    _COUNTERS = (
        "edl_rpc_retries_total",
        "edl_rpc_breaker_trips_total",
        "edl_chaos_injected_total",
    )

    def __init__(self, obs_dir):
        self._endpoints_dir = os.path.join(obs_dir, "endpoints")
        self._high = {}  # (role, counter) -> max summed value seen

    def scrape(self):
        if not os.path.isdir(self._endpoints_dir):
            return
        for entry in os.listdir(self._endpoints_dir):
            if not entry.endswith(".json"):
                continue
            try:
                with open(os.path.join(self._endpoints_dir, entry)) as f:
                    port = json.load(f).get("port")
                if not port:
                    continue
                body = urllib.request.urlopen(
                    f"http://127.0.0.1:{port}/metrics", timeout=1
                ).read().decode()
            except (OSError, ValueError):
                continue  # endpoint mid-rewrite or process mid-restart
            role = entry[: -len(".json")]
            for counter in self._COUNTERS:
                total = 0.0
                for m in re.finditer(
                    rf"^{counter}(?:{{[^}}]*}})? ([0-9.eE+-]+)$",
                    body,
                    re.M,
                ):
                    total += float(m.group(1))
                key = (role, counter)
                self._high[key] = max(self._high.get(key, 0.0), total)

    def totals(self):
        out = {}
        for (_, counter), value in self._high.items():
            out[counter] = out.get(counter, 0.0) + value
        return {k: round(v, 3) for k, v in out.items()}


def run_drill(
    data_path,
    model_zoo,
    model_def,
    num_workers=2,
    num_ps=1,
    num_epochs=8,
    minibatch_size=32,
    records_per_task=64,
    strategy=None,
    extra_args=(),
    env_overrides=None,
    timeout=300,
    require_victim_task=True,
    scenario="worker-kill",
    obs_dir=None,
    stall_seconds=8.0,
    wave_fraction=0.5,
    log_path=None,
):
    """strategy: explicit --distribution_strategy name; default derives
    from num_ps (ParameterServerStrategy when PS shards are requested,
    Local otherwise). Pass "AllreduceStrategy" to drill the elastic
    membership/broadcast path.

    require_victim_task: gate the SIGKILL on the victim provably owning an
    in-flight task (see the freeze loop below) so task recovery is
    deterministic. Disable for multi-host lease drills: a SIGSTOPped rank
    stalls the whole SPMD world's collectives, and those drills assert
    rejoin, not per-task recovery.

    scenario: one of SCENARIOS; obs_dir enables the metrics scraper (and
    is exported to the job as ELASTICDL_OBS_DIR when the caller didn't).

    log_path: keep the job's whole log there (default: a temporary
    file; the result carries only its tail).

    timeout bounds the WHOLE drill: every wait below draws on one
    deadline, so a job that wedges fails the drill `timeout` seconds
    after it started, and the finally-block reaps its process group."""
    import grpc

    from elasticdl_tpu.chaos import process as chaos_process
    from elasticdl_tpu.common import rpc
    from elasticdl_tpu.proto import elasticdl_tpu_pb2 as pb

    if scenario not in SCENARIOS:
        raise ValueError(f"unknown scenario {scenario!r}; one of {SCENARIOS}")
    if scenario in ("straggler", "input-starve") and not obs_dir:
        raise ValueError(
            f"the {scenario} scenario needs --obs_dir: detection is "
            "read from the master's aggregated /metrics and /api/summary"
        )
    if scenario in POLICY_SCENARIOS and not obs_dir:
        raise ValueError(
            f"the {scenario} scenario needs --obs_dir: the policy "
            "engine's input is the master's telemetry aggregator, and "
            "the decision trail is read from events.jsonl"
        )
    if scenario in MASTER_KILL_SCENARIOS and not obs_dir:
        raise ValueError(
            f"the {scenario} scenario needs --obs_dir: it hosts the "
            "master journal and the master_recovered event trail"
        )
    t_end = time.time() + timeout

    def left():
        return max(1.0, t_end - time.time())

    port = _free_port()
    env = dict(os.environ)
    # The job's import path is exactly the repo and the model zoo.
    env["PYTHONPATH"] = f"{REPO}:{model_zoo}"
    env.update(scenario_env(scenario))
    env.update(env_overrides or {})
    if obs_dir and "ELASTICDL_OBS_DIR" not in (env_overrides or {}):
        env["ELASTICDL_OBS_DIR"] = obs_dir
    if scenario in MASTER_KILL_SCENARIOS:
        env.setdefault(
            "ELASTICDL_MASTER_JOURNAL_DIR",
            os.path.join(obs_dir, "journal"),
        )
    scraper = MetricsScraper(obs_dir) if obs_dir else None
    train_cmd = [
        sys.executable, "-m", "elasticdl_tpu.client.main", "train",
        "--model_zoo", model_zoo,
        "--model_def", model_def,
        "--training_data", data_path,
        "--num_epochs", str(num_epochs),
        "--records_per_task", str(records_per_task),
        "--minibatch_size", str(minibatch_size),
        "--num_workers", str(num_workers),
        "--num_ps", str(num_ps),
        "--distribution_strategy",
        strategy
        or ("ParameterServerStrategy" if num_ps else "Local"),
        "--instance_backend", "local_process",
        "--master_port", str(port),
        *extra_args,
    ]
    # The job logs to a FILE, never a pipe: nobody reads while the drill
    # polls, and a job whose log outgrows a pipe's 64 KiB blocks in
    # write() with every role at ~0% CPU — under load (more retries,
    # more log lines) that wedged whole multi-process worlds. A file
    # also cannot block the final read when an orphan still holds it.
    job_log = (
        open(log_path, "w+") if log_path
        else tempfile.TemporaryFile(mode="w+")
    )
    train = subprocess.Popen(
        train_cmd,
        stdout=job_log,
        stderr=subprocess.STDOUT,
        env=env,
        cwd=REPO,
        # Own process group: teardown must reap the master's worker/PS
        # children too — an orphaned worker blocked in a rendezvous
        # poisons every later drill that lands on the same ports.
        start_new_session=True,
    )
    result = {
        "scenario": scenario,
        "completed": False,
        "killed_worker": None,
        "rejoin_s": None,
        "records_at_kill": None,
        "records_done": None,
    }
    try:
        # The channel-ready wait now lives in common/rpc (build_channel
        # probes by default); the drill keeps its own probe loop only to
        # abort early when the job process dies before ever binding.
        rpc.wait_channel_ready(
            f"127.0.0.1:{port}",
            left(),
            abort_check=lambda: train.poll() is not None,
        )
        stub = rpc.Stub(
            rpc.build_channel(f"127.0.0.1:{port}", ready_timeout=0),
            rpc.MASTER_SERVICE,
        )

        def status(deadline):
            while time.time() < deadline:
                try:
                    return stub.get_job_status(pb.GetJobStatusRequest())
                except grpc.RpcError:
                    if train.poll() is not None:
                        return None
                    time.sleep(0.2)
            return None

        # Wait until training actually progresses.
        deadline = t_end
        while True:
            s = status(deadline)
            if s is None:
                if (
                    scenario in MASTER_KILL_SCENARIOS
                    and train.poll() is not None
                ):
                    break  # injected SIGKILL beat the first observation
                raise RuntimeError("job never started making progress")
            if s.records_done > 0 and s.alive_workers >= num_workers:
                break
            time.sleep(0.2)

        if scenario == "worker-kill":
            s = _do_worker_kill(
                train, stub, status, s, port, result,
                require_victim_task, chaos_process,
            )
        elif scenario == "ps-flap":
            victim = chaos_process.kill_role("ps", 0, port)
            result["killed_ps"] = victim
            result["records_at_kill"] = int(s.records_done)
            # The flap is complete once a REPLACEMENT PS process exists.
            t_kill = time.time()
            try:
                replacement = victim
                while replacement == victim:
                    replacement = chaos_process.find_role_pid(
                        "ps", 0, port, timeout=60
                    )
                    time.sleep(0.1)
                result["replacement_ps"] = replacement
                result["ps_relaunch_s"] = round(time.time() - t_kill, 3)
            except RuntimeError:
                # Job drained (or failed) before the relaunch was
                # observed: report it structurally, don't crash the drill.
                result["replacement_ps"] = None
        elif scenario == "master-stall":
            result["records_at_kill"] = int(s.records_done)
            result["stalled_s"] = stall_seconds
            # The master runs inside the `edl train` process (local
            # backend); freezing it stalls the whole control plane while
            # workers and PS keep running.
            chaos_process.stall(train.pid, stall_seconds)
        elif scenario == "straggler":
            s = _do_straggler_watch(
                status, s, port, obs_dir, result, left(), env
            )
        elif scenario == "input-starve":
            s = _do_input_starve_watch(
                status, s, port, obs_dir, result, left(), env
            )
        elif scenario == "straggler-recovery":
            s = _do_straggler_recovery(
                status, s, obs_dir, result, left()
            )
        elif scenario == "backup-task":
            s = _do_backup_task(
                status, s, port, obs_dir, result, left(),
                chaos_process,
            )
        elif scenario == "deadline-scale":
            s = _do_deadline_scale(status, s, obs_dir, result, left())
        elif scenario == "preemption-wave":
            result["records_at_kill"] = int(s.records_done)
            result["wave_killed"] = chaos_process.preemption_wave(
                num_workers, port, fraction=wave_fraction, seed=20260807
            )
        elif scenario in MASTER_KILL_SCENARIOS:
            s = _do_master_kill(
                train, train_cmd, status, s, port, obs_dir, result,
                left(), env, scenario, chaos_process,
            )
        # rpc-brownout: nothing to do here — the chaos schedule shipped in
        # the environment is already injecting faults. none: no fault.

        # Drain to completion, scraping metrics endpoints as we go.
        while time.time() < t_end:
            if scraper is not None:
                scraper.scrape()
            s2 = status(time.time() + 10)
            if s2 is None:
                break
            s = s2
            if s.finished or s.job_failed:
                break
            time.sleep(0.3)

        train.wait(timeout=left())
        result["completed"] = train.returncode == 0
        if scenario in MASTER_KILL_SCENARIOS:
            # The original master is SUPPOSED to die (SIGKILL); the job's
            # verdict is the relaunched master's.
            result["completed"] = bool(result.get("relaunch_completed"))
        job_log.seek(0)
        out = job_log.read()
        result["relaunched"] = "Relaunching worker 0" in out
        result["ps_relaunched"] = "Relaunching ps 0" in out
        result["recovered_tasks"] = "Recovered" in out
        result["reseeded"] = (
            "re-seeding from local" in out
            or "Model initialized from worker push" in out
        )
        # Mesh layouts the workers actually built (lets drills assert a
        # TP/ZeRO world really formed rather than silently falling back).
        result["mesh_axes_seen"] = sorted(
            set(re.findall(r"Mesh axes: (\{[^}]*\})", out))
        )
        result["log_tail"] = out[-2000:]
        if s is not None:
            result["records_done"] = int(s.records_done)
            result["tasks_abandoned"] = int(s.tasks_abandoned)
        if (
            scenario in MASTER_KILL_SCENARIOS
            and result.get("records_done_journal") is not None
        ):
            # The journal the successor closed over is authoritative:
            # the drill's last status observation can be stale when the
            # recovered master drains and exits between polls.
            result["records_done"] = result["records_done_journal"]
        if scraper is not None:
            result["metrics"] = scraper.totals()
        return result
    finally:
        if train.poll() is None:
            train.kill()
        try:
            os.killpg(train.pid, signal.SIGKILL)
        except (ProcessLookupError, PermissionError, OSError):
            pass
        job_log.close()
        # Zero-leftover invariant: nothing of this job may outlive the
        # drill (an orphan wedged in a retry loop would poison later runs
        # AND falsify "the job survived"). Record, then reap.
        time.sleep(0.2)
        leftovers = chaos_process.find_job_pids(port)
        result["leftover_procs"] = [line for _, line in leftovers]
        for pid, _ in leftovers:
            chaos_process.deliver(pid, signal.SIGKILL)
        # Heartbeat-driven sweep for trees from EARLIER crashed drills
        # (this drill's own master heartbeat is fresh or already gone).
        try:
            from reap_orphans import reap as reap_heartbeats

            heartbeat_dir = knobs.get_str("ELASTICDL_HEARTBEAT_DIR")
            if heartbeat_dir:
                reap_heartbeats(heartbeat_dir)
        except Exception:
            pass


def _master_endpoint(obs_dir):
    try:
        with open(
            os.path.join(obs_dir, "endpoints", "master.json")
        ) as f:
            return json.load(f)
    except (OSError, ValueError):
        return None


def _do_straggler_watch(status, s, port, obs_dir, result, timeout, env):
    """Watch the master's aggregated telemetry until it flags the slowed
    worker: `edl_job_straggler{worker="worker-0"} 1` on the master's own
    /metrics, the same worker named by /api/summary (with nonzero
    throughput), and — while the job is still live — one `edl dash
    --once` frame captured as proof the dashboard renders against a real
    running job."""
    deadline = time.time() + timeout
    result["straggler_flagged"] = None
    result["summary_throughput"] = None
    result["summary_stragglers"] = []
    while time.time() < deadline:
        info = _master_endpoint(obs_dir)
        if info is not None:
            try:
                body = urllib.request.urlopen(
                    f"http://127.0.0.1:{info['port']}/metrics", timeout=2
                ).read().decode()
                m = re.search(
                    r'^edl_job_straggler\{worker="([^"]+)"\} 1$',
                    body,
                    re.M,
                )
                if m:
                    result["straggler_flagged"] = m.group(1)
                    summary = json.loads(
                        urllib.request.urlopen(
                            f"http://127.0.0.1:{info['port']}/api/summary",
                            timeout=2,
                        ).read().decode()
                    )
                    result["summary_throughput"] = summary.get(
                        "records_per_second"
                    )
                    result["summary_stragglers"] = summary.get(
                        "stragglers", []
                    )
                    break
            except (OSError, ValueError):
                pass  # master mid-setup; poll again
        s2 = status(time.time() + 5)
        if s2 is None:
            break
        s = s2
        if s.finished or s.job_failed:
            break
        time.sleep(0.5)
    if result["straggler_flagged"]:
        # Dashboard snapshot against the LIVE job (the chaos schedule is
        # stripped: the dash process is an observer, not a test subject).
        dash_env = {
            k: v for k, v in env.items() if k != "ELASTICDL_CHAOS"
        }
        try:
            dash = subprocess.run(
                [
                    sys.executable, "-m", "elasticdl_tpu.client.main",
                    "dash", "--master_addr", f"127.0.0.1:{port}",
                    "--once",
                ],
                capture_output=True,
                text=True,
                timeout=60,
                env=dash_env,
                cwd=REPO,
            )
            result["dash_snapshot"] = dash.stdout
            result["dash_rc"] = dash.returncode
        except subprocess.TimeoutExpired:
            result["dash_snapshot"] = ""
            result["dash_rc"] = -1
    return s


def _do_input_starve_watch(status, s, port, obs_dir, result, timeout,
                           env):
    """Watch the master's data-plane rollups until they attribute the
    injected slow reader: `edl_job_input_starved{worker="worker-0"} 1`
    on the master's /metrics (the input_starvation alert, re-exported),
    the /api/summary datapath block naming a dominant stage, the
    `datapath` event trail in events.jsonl, and — while the job is still
    live — one `edl dash --once --json` machine-readable snapshot."""
    deadline = time.time() + timeout
    result["starved_flagged"] = None
    result["datapath_summary"] = None
    result["dominant_stage"] = None
    while time.time() < deadline:
        info = _master_endpoint(obs_dir)
        if info is not None:
            try:
                body = urllib.request.urlopen(
                    f"http://127.0.0.1:{info['port']}/metrics", timeout=2
                ).read().decode()
                m = re.search(
                    r'^edl_job_input_starved\{worker="([^"]+)"\} 1$',
                    body,
                    re.M,
                )
                if m:
                    result["starved_flagged"] = m.group(1)
                    summary = json.loads(
                        urllib.request.urlopen(
                            f"http://127.0.0.1:{info['port']}/api/summary",
                            timeout=2,
                        ).read().decode()
                    )
                    dp = summary.get("datapath") or {}
                    result["datapath_summary"] = dp
                    result["dominant_stage"] = dp.get("dominant_stage")
                    result["starved_workers"] = dp.get("starved")
                    break
            except (OSError, ValueError):
                pass  # master mid-setup; poll again
        s2 = status(time.time() + 5)
        if s2 is None:
            break
        s = s2
        if s.finished or s.job_failed:
            break
        time.sleep(0.5)
    result["datapath_event"] = _find_event(obs_dir, "datapath")
    if result["starved_flagged"]:
        # Machine-readable dashboard snapshot against the LIVE job (the
        # chaos schedule is stripped: the dash process is an observer).
        dash_env = {
            k: v for k, v in env.items() if k != "ELASTICDL_CHAOS"
        }
        try:
            dash = subprocess.run(
                [
                    sys.executable, "-m", "elasticdl_tpu.client.main",
                    "dash", "--master_addr", f"127.0.0.1:{port}",
                    "--once", "--json",
                ],
                capture_output=True,
                text=True,
                timeout=60,
                env=dash_env,
                cwd=REPO,
            )
            result["dash_json_rc"] = dash.returncode
            try:
                snap = json.loads(dash.stdout)
                result["dash_json_has_datapath"] = bool(
                    snap.get("datapath")
                )
            except ValueError:
                result["dash_json_has_datapath"] = False
        except subprocess.TimeoutExpired:
            result["dash_json_rc"] = -1
            result["dash_json_has_datapath"] = False
    return s


def _policy_decisions(obs_dir):
    """All policy_decision events logged so far (the causal trail)."""
    from elasticdl_tpu.observability.events import read_events

    path = os.path.join(obs_dir, "events.jsonl")
    if not os.path.exists(path):
        return []
    return [
        r for r in read_events(path)
        if r.get("kind") == "policy_decision"
    ]


def _find_event(obs_dir, kind):
    from elasticdl_tpu.observability.events import read_events

    path = os.path.join(obs_dir, "events.jsonl")
    if not os.path.exists(path):
        return None
    for r in read_events(path):
        if r.get("kind") == kind:
            return r
    return None


def _find_policy_decision(obs_dir, action, outcome="applied"):
    for r in _policy_decisions(obs_dir):
        if r.get("action") == action and r.get("outcome") == outcome:
            return r
    return None


def _measure_rps(status, seconds):
    """(records/s over the window, last status). None rps when the master
    went away mid-window."""
    s0 = status(time.time() + 10)
    if s0 is None:
        return None, None
    t0 = time.time()
    time.sleep(seconds)
    s1 = status(time.time() + 10)
    if s1 is None:
        return None, s0
    dt = max(time.time() - t0, 1e-6)
    return (int(s1.records_done) - int(s0.records_done)) / dt, s1


def _do_straggler_recovery(status, s, obs_dir, result, timeout,
                           tolerance=0.5, recovery_window=90.0):
    """The closed loop, end to end: pre-fault baseline -> straggler
    slows -> policy blacklists + recovers + restarts -> records/s back
    within `tolerance` of the baseline inside `recovery_window` seconds
    of the decision. Recovery is measured, not inferred from flags."""
    # 1. The chaos latency rules burn a per-rule call budget before they
    #    start; this window is the healthy pre-fault baseline.
    baseline, s2 = _measure_rps(status, 3.0)
    if s2 is not None:
        s = s2
    result["baseline_rps"] = round(baseline, 2) if baseline else baseline
    # 2. The decision: an APPLIED straggler_blacklist in events.jsonl.
    deadline = time.time() + timeout
    decision = None
    while time.time() < deadline:
        decision = _find_policy_decision(obs_dir, "straggler_blacklist")
        if decision is not None:
            break
        s2 = status(time.time() + 10)
        if s2 is None:
            break
        s = s2
        if s.finished or s.job_failed:
            break
        time.sleep(0.5)
    result["decision"] = decision
    result["decision_trail"] = _policy_decisions(obs_dir)
    if decision is None or not baseline:
        return s
    # 3. Bounded recovery: throughput back within tolerance, or the job
    #    drains first (a drained queue IS recovery for a short job).
    t_decision = time.time()
    recovered_rps = None
    while time.time() - t_decision < recovery_window:
        rps, s2 = _measure_rps(status, 3.0)
        if s2 is not None:
            s = s2
        if s2 is None or s.finished or s.job_failed:
            break
        if rps is not None and rps >= tolerance * baseline:
            recovered_rps = rps
            result["recovery_s"] = round(time.time() - t_decision, 3)
            break
    result["recovered_rps"] = (
        round(recovered_rps, 2) if recovered_rps else recovered_rps
    )
    result["recovered"] = bool(
        recovered_rps is not None or (s is not None and s.finished)
    )
    return s


def _do_backup_task(status, s, port, obs_dir, result, timeout,
                    chaos_process):
    """Freeze a worker that provably owns an in-flight task (same
    SIGSTOP gate as worker-kill, but the victim never dies): the backup
    rule must dispatch a speculative copy, the copy must WIN, and the
    thawed loser's late report must be discarded without double-counting
    (checked by the caller via --expect_records)."""
    victim = chaos_process.find_role_pid("worker", 0, port)
    freeze_deadline = time.time() + 30
    try:
        while True:
            os.kill(victim, signal.SIGSTOP)
            time.sleep(0.1)  # drain any in-flight report RPC
            fresh = status(time.time() + 10)
            if fresh is not None:
                s = fresh
            if (
                fresh is not None
                and dict(fresh.worker_doing_tasks).get(0, 0) > 0
            ):
                break
            if fresh is None or time.time() > freeze_deadline:
                result["victim_task_observed"] = False
                break
            os.kill(victim, signal.SIGCONT)
            time.sleep(0.05)
    except ProcessLookupError:
        result["victim_task_observed"] = False
    result.setdefault("victim_task_observed", True)
    result["frozen_worker"] = victim
    # The decision + the win, while the victim stays frozen.
    deadline = time.time() + timeout
    decision = None
    try:
        while time.time() < deadline:
            if decision is None:
                decision = _find_policy_decision(obs_dir, "backup_task")
            s2 = status(time.time() + 10)
            if s2 is None:
                break
            s = s2
            if decision is not None and s.backup_wins >= 1:
                break
            if s.finished or s.job_failed:
                break
            time.sleep(0.5)
    finally:
        # Thaw: the loser reports late into the ack-discard path.
        try:
            os.kill(victim, signal.SIGCONT)
        except ProcessLookupError:
            pass
    result["backup_decision"] = decision
    result["decision_trail"] = _policy_decisions(obs_dir)
    result["backup_wins"] = int(s.backup_wins) if s is not None else 0
    return s


def _do_deadline_scale(status, s, obs_dir, result, timeout):
    """ETA overshoots ELASTICDL_JOB_DEADLINE_SECONDS: the policy must
    announce the next world FIRST (world_hint) and then scale up; the
    drill watches the new worker actually join (alive_workers)."""
    workers_at_start = int(s.alive_workers)
    result["workers_at_start"] = workers_at_start
    deadline = time.time() + timeout
    decision = None
    hint = None
    while time.time() < deadline:
        if decision is None:
            decision = _find_policy_decision(obs_dir, "scale_up")
        if hint is None:
            hint = _find_event(obs_dir, "world_hint")
        s2 = status(time.time() + 10)
        if s2 is None:
            break
        s = s2
        if decision is not None and s.alive_workers > workers_at_start:
            break
        if s.finished or s.job_failed:
            break
        time.sleep(0.5)
    result["scale_decision"] = decision
    result["world_hint"] = hint
    result["decision_trail"] = _policy_decisions(obs_dir)
    result["workers_after"] = (
        int(s.alive_workers) if s is not None else None
    )
    return s


def _do_master_kill(train, train_cmd, status, s, port, obs_dir, result,
                    timeout, env, scenario, chaos_process):
    """The survivable-control-plane drill: the chaos kill fault SIGKILLs
    the master (the `edl train` process, local backend) mid-job; the
    drill relaunches `elasticdl_tpu.master.main` over the SAME journal
    dir and port (orphaned workers ride their master-patience window and
    re-register with the bumped incarnation), and the recovered job must
    drain to completion with exactly-once records accounting (checked by
    the caller via --expect_records)."""
    import grpc

    from elasticdl_tpu.common import rpc
    from elasticdl_tpu.proto import elasticdl_tpu_pb2 as pb

    # `timeout` is what the caller's one deadline has left; every wait
    # below draws on it.
    t_end = time.time() + timeout

    def left():
        return max(1.0, t_end - time.time())

    # 1. Wait for the injected SIGKILL to land.
    while train.poll() is None and time.time() < t_end:
        s2 = status(time.time() + 2)
        if s2 is not None:
            s = s2
            if s.finished or s.job_failed:
                break
        time.sleep(0.1)
    result["master_killed"] = train.poll() is not None
    result["train_returncode"] = train.poll()
    if s is not None:
        result["records_at_kill"] = int(s.records_done)
    pre_hint = _find_event(obs_dir, "world_hint")
    # The hint's own sequence number lives under hint_seq — the bare
    # `seq` on the record is the event-log envelope counter (file
    # order), a different series entirely.
    result["hint_seq_at_kill"] = (
        int(pre_hint.get("hint_seq", 0)) if pre_hint else 0
    )
    if train.poll() is None:
        return s  # the kill never fired; the ok-gate fails on master_killed

    # 2. Relaunch the master over the same journal: master.main takes the
    #    same argv the client forwarded, with --instance_backend none —
    #    the original workers are alive, riding the patience window
    #    toward the fixed --master_port; spawning a second cohort would
    #    double the world. Chaos is stripped so the successor does not
    #    re-kill itself at the next matching dispatch.
    master_args = list(train_cmd[train_cmd.index("train") + 1:])
    backend_at = master_args.index("--instance_backend")
    master_args[backend_at + 1] = "none"
    relaunch_env = {
        k: v for k, v in env.items() if k != "ELASTICDL_CHAOS"
    }
    t_relaunch = time.time()
    master2_log = tempfile.TemporaryFile(mode="w+")
    master2 = subprocess.Popen(
        [sys.executable, "-m", "elasticdl_tpu.master.main"]
        + master_args,
        stdout=master2_log,
        stderr=subprocess.STDOUT,
        env=relaunch_env,
        cwd=REPO,
        start_new_session=True,
    )
    result["relaunched_master"] = master2.pid
    try:
        rpc.wait_channel_ready(
            f"127.0.0.1:{port}",
            left(),
            abort_check=lambda: master2.poll() is not None,
        )
        # The drill's own per-peer circuit breaker tripped during the
        # dead window; its 5s half-open cadence can eat the successor's
        # whole serving window on a fast recovery. The port provably
        # accepts again — reset the breakers and observe immediately.
        rpc.reload_config()
        stub2 = rpc.Stub(
            rpc.build_channel(f"127.0.0.1:{port}", ready_timeout=0),
            rpc.MASTER_SERVICE,
        )

        def status2(poll_deadline):
            while time.time() < poll_deadline:
                try:
                    return stub2.get_job_status(pb.GetJobStatusRequest())
                except grpc.RpcError:
                    if master2.poll() is not None:
                        return None
                    time.sleep(0.2)
            return None

        s2 = status2(time.time() + 30)
        if s2 is not None:
            s = s2
            result["master_incarnation"] = int(
                getattr(s2, "master_incarnation", 0)
            )
            result["records_after_replay"] = int(s2.records_done)
        if scenario == "master-kill-during-scale":
            # hint_seq monotonicity across incarnations: the recovered
            # board must resume at (or beyond) the pre-crash seq.
            try:
                hint = stub2.get_world_hint(
                    pb.GetWorldHintRequest(worker_id=0)
                )
                result["hint_seq_recovered"] = int(hint.hint_seq)
            except grpc.RpcError:
                result["hint_seq_recovered"] = None

        # 3. Drain the recovered job to completion.
        while time.time() < t_end:
            s2 = status2(time.time() + 10)
            if s2 is None:
                break
            s = s2
            if s2.finished or s2.job_failed:
                break
            time.sleep(0.3)
        master2.wait(timeout=left())
        result["recovery_s"] = round(time.time() - t_relaunch, 3)
        # Exit code 0 is itself the completion verdict: the master's run
        # loop returns 0 only once the job finished without failure. A
        # fast recovery can drain and exit between two status polls, so
        # "the drill observed finished" is sufficient but not necessary.
        result["relaunch_completed"] = master2.returncode == 0 or (
            s is not None and bool(s.finished) and not s.job_failed
        )
        master2_log.seek(0)
        out2 = master2_log.read()
        result["relaunch_log_tail"] = out2[-2000:]
        # Authoritative records accounting comes from the journal the
        # successor just closed over — immune to the status-poll race
        # above and exactly what the exactly-once claim is about.
        jdir = env.get("ELASTICDL_MASTER_JOURNAL_DIR")
        if jdir:
            try:
                from elasticdl_tpu.master import journal as mjournal

                snap, ops = mjournal.Journal(jdir).load()
                jstate = mjournal.replay(snap, ops)
                result["records_done_journal"] = int(
                    jstate.get("records_done", 0)
                )
                result["incarnation_journal"] = int(
                    jstate.get("incarnation", 0)
                )
                # Status-poll fallbacks, same staleness rationale.
                if "master_incarnation" not in result:
                    result["master_incarnation"] = result[
                        "incarnation_journal"
                    ]
                if result.get("hint_seq_recovered") is None:
                    result["hint_seq_recovered"] = (
                        int(jstate.get("hint_seq", 0)) or None
                    )
            except Exception as e:  # observation plane must not fail the drill
                result["journal_read_error"] = repr(e)
    finally:
        if master2.poll() is None:
            master2.kill()
        try:
            os.killpg(master2.pid, signal.SIGKILL)
        except (ProcessLookupError, PermissionError, OSError):
            pass
        master2_log.close()
    # The recovery event trail (events.jsonl is append-mode, so both
    # incarnations land in one file).
    result["master_recovered_event"] = _find_event(
        obs_dir, "master_recovered"
    )
    result["lease_reissued_event"] = _find_event(
        obs_dir, "lease_reissued"
    )
    # 4. The orphaned workers exit on the finished signal; reap anything
    #    that missed it so the caller's stdout drain and zero-leftover
    #    check don't hang on the shared pipe.
    wait_deadline = time.time() + 20
    while time.time() < wait_deadline:
        if not chaos_process.find_job_pids(port):
            break
        time.sleep(0.5)
    for pid, _ in chaos_process.find_job_pids(port):
        chaos_process.deliver(pid, signal.SIGKILL)
    return s


def _do_worker_kill(train, stub, status, s, port, result,
                    require_victim_task, chaos_process):
    """The original drill: SIGKILL worker 0 (preemption) and measure the
    rejoin. Returns the last observed status."""
    # When the caller wants the kill to provably strand recoverable work
    # (require_victim_task), freeze the victim FIRST and only deliver the
    # SIGKILL once the master shows it owning an in-flight task: tasks on
    # this tiny model finish in milliseconds, so an unsynchronized kill
    # can land in the report-done -> next-get_task window where the worker
    # owns nothing — then there is nothing to recover and the drill's
    # "Recovered" assertion is timing-flaky under host load (the exact
    # round-4 full-suite failure). SIGSTOP makes the observation stable: a
    # stopped worker can't complete the task out from under the check (a
    # brief settle lets an already-in-flight report-done land before the
    # ownership read).
    victim = chaos_process.find_role_pid("worker", 0, port)
    t_freeze = None
    if require_victim_task:
        freeze_deadline = time.time() + 30
        try:
            while True:
                # The master's detection clock starts when heartbeats
                # stop — at the SIGSTOP, not at the later SIGKILL; the
                # rejoin metric must be measured from here.
                t_freeze = time.time()
                os.kill(victim, signal.SIGSTOP)
                time.sleep(0.1)  # drain any in-flight report RPC
                fresh = status(time.time() + 10)
                if fresh is not None:
                    s = fresh
                # Only a FRESH post-freeze observation proves the victim
                # holds recoverable work; a stale snapshot (or an
                # unreachable/drained master) must not satisfy the gate —
                # mark unobserved and kill anyway.
                if (
                    fresh is not None
                    and dict(fresh.worker_doing_tasks).get(0, 0) > 0
                ):
                    break
                if fresh is None or time.time() > freeze_deadline:
                    result["victim_task_observed"] = False
                    break
                os.kill(victim, signal.SIGCONT)
                time.sleep(0.05)
        except ProcessLookupError:
            # The victim exited during a CONT window (e.g. the job
            # drained): nothing left to freeze or prove.
            result["victim_task_observed"] = False
        result.setdefault("victim_task_observed", True)
        result["status_at_kill"] = {
            "todo": int(s.todo_tasks),
            "doing": int(s.doing_tasks),
            "worker_doing_tasks": dict(s.worker_doing_tasks),
        }
    try:
        os.kill(victim, signal.SIGKILL)
    except ProcessLookupError:
        pass  # already gone; the relaunch checks below still apply
    # Freeze-gated kills were last SIGSTOPped (never resumed) at
    # t_freeze — the instant the worker went silent.
    t_kill = t_freeze if t_freeze is not None else time.time()
    result["killed_worker"] = victim
    result["killed_at"] = t_kill
    result["records_at_kill"] = int(s.records_done)

    # Rejoin = the REPLACEMENT worker back in the job: a new worker-0
    # process exists (detection + relaunch) and worker 0's last-seen
    # age shows an RPC made AFTER the relaunch (its re-init + first
    # task pull) — attributed per worker, so survivors' concurrent
    # progress can't fake it.
    try:
        replacement = victim
        while replacement == victim:
            replacement = chaos_process.find_role_pid(
                "worker", 0, port, timeout=60
            )
            time.sleep(0.1)
        result["replacement_worker"] = replacement
        t_relaunch = time.time()
        while True:
            s2 = status(time.time() + 30)
            if s2 is None:
                break
            s = s2
            if s.finished:
                break
            age = dict(s.worker_last_seen_ago).get(0)
            if age is not None and time.time() - age >= t_relaunch:
                result["rejoin_s"] = round(time.time() - t_kill, 3)
                break
            time.sleep(0.1)
    except RuntimeError:
        pass  # job drained before the relaunch was observed
    return s


def main():
    p = argparse.ArgumentParser("elastic_drill")
    p.add_argument("--training_data", required=True)
    p.add_argument("--model_zoo", default=os.path.join(REPO, "tests"))
    p.add_argument("--model_def", default="test_module")
    p.add_argument("--num_workers", type=int, default=2)
    p.add_argument("--num_ps", type=int, default=1)
    p.add_argument("--num_epochs", type=int, default=8)
    p.add_argument(
        "--scenario",
        default="worker-kill",
        choices=SCENARIOS,
        help="which fault to inject (docs/ROBUSTNESS.md catalog)",
    )
    p.add_argument(
        "--obs_dir",
        default="",
        help="observability dir (enables the rpc-metrics scraper)",
    )
    p.add_argument("--stall_seconds", type=float, default=8.0)
    p.add_argument(
        "--wave_fraction",
        type=float,
        default=0.5,
        help="fraction of workers killed by the preemption-wave scenario",
    )
    p.add_argument(
        "--expect_records",
        type=int,
        default=0,
        help="fail unless records_done reaches this count",
    )
    p.add_argument(
        "--strategy",
        default=None,
        help="explicit distribution strategy (default from --num_ps)",
    )
    args = p.parse_args()
    if args.strategy and args.strategy != "ParameterServerStrategy":
        if args.num_ps:
            print(
                f"note: --strategy {args.strategy} ignores parameter "
                f"servers; overriding --num_ps {args.num_ps} -> 0",
                file=sys.stderr,
            )
        args.num_ps = 0
    obs_dir = args.obs_dir or None
    needs_obs = (
        args.scenario in ("straggler", "input-starve")
        or args.scenario in POLICY_SCENARIOS
        or args.scenario in MASTER_KILL_SCENARIOS
    )
    if needs_obs and not obs_dir:
        import tempfile

        obs_dir = tempfile.mkdtemp(prefix="edl_drill_obs_")
        print(f"note: --obs_dir defaulted to {obs_dir}", file=sys.stderr)
    result = run_drill(
        args.training_data,
        args.model_zoo,
        args.model_def,
        num_workers=args.num_workers,
        num_ps=args.num_ps,
        num_epochs=args.num_epochs,
        strategy=args.strategy,
        scenario=args.scenario,
        obs_dir=obs_dir,
        stall_seconds=args.stall_seconds,
        wave_fraction=args.wave_fraction,
    )
    result.pop("log_tail", None)
    result.pop("dash_snapshot", None)
    print(json.dumps(result, default=str))
    ok = result["completed"] and not result["leftover_procs"]
    if args.scenario == "straggler":
        ok = ok and bool(result.get("straggler_flagged"))
    elif args.scenario == "input-starve":
        # The alert must name EXACTLY the faulted worker, the datapath
        # event trail must exist, and the summary's data-plane block
        # must blame the injected stage (the slow read surfaces as
        # producer `read` time and consumer `starve` time).
        ok = ok and result.get("starved_flagged") == "worker-0"
        ok = ok and result.get("starved_workers") == ["worker-0"]
        ok = ok and result.get("datapath_event") is not None
        ok = ok and result.get("dominant_stage") in ("read", "starve")
    elif args.scenario == "straggler-recovery":
        ok = ok and result.get("decision") is not None
        ok = ok and bool(result.get("recovered"))
    elif args.scenario == "backup-task":
        ok = ok and result.get("backup_decision") is not None
        ok = ok and result.get("backup_wins", 0) >= 1
    elif args.scenario == "deadline-scale":
        ok = ok and result.get("scale_decision") is not None
        ok = ok and result.get("world_hint") is not None
        ok = (
            ok
            and result.get("workers_after") is not None
            and result["workers_after"] > result.get("workers_at_start", 0)
        )
    elif args.scenario == "preemption-wave":
        ok = ok and bool(result.get("wave_killed"))
    elif args.scenario in MASTER_KILL_SCENARIOS:
        ok = ok and bool(result.get("master_killed"))
        ok = ok and result.get("master_incarnation", 0) >= 2
        rec = result.get("master_recovered_event")
        ok = ok and rec is not None
        # The re-lease trail exists whenever the crash stranded in-flight
        # leases (a crash that caught both workers between tasks strands
        # none — then an empty trail is correct).
        ok = ok and (
            result.get("lease_reissued_event") is not None
            or int((rec or {}).get("leases", 0)) == 0
        )
        if args.scenario == "master-kill-during-scale":
            ok = ok and result.get("hint_seq_at_kill", 0) >= 1
            ok = ok and (
                (result.get("hint_seq_recovered") or 0)
                >= result.get("hint_seq_at_kill", 0)
            )
    if args.expect_records:
        ok = ok and result.get("records_done") == args.expect_records
    return 0 if ok else 1


if __name__ == "__main__":
    sys.exit(main())
