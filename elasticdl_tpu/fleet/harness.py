"""Fleet harness internals: SimPod, relay tree, FleetMaster, FleetHarness.

Execution model: N pods are sharded over a handful of carrier threads;
each carrier sweeps its pods once per tick interval. A pod tick is a few
registry mutations plus at most one task RPC — cheap enough that one
process carries 500 pods while the master under test does real work.
The master is real: a TaskDispatcher + MasterServicer behind rpc.serve,
a TelemetryAggregator ticked by its own thread, and a MetricsExporter
answering /api/summary, all on the process-default registry (which is
exactly where the edl_master_* control-plane series live).

Chaos: the harness asks the shared FaultSchedule once per pod per tick
with the synthetic method name "fleet.tick.pod-NNNN", so rules select
pods by method substring and windows count in ticks. `unavailable`
means dead for the window (pull mode leaves the advert behind — the
stale-endpoint path — and the pod relaunches after the window with a
new incarnation pid); `latency` inflates the pod's simulated step time
for the window (a straggler). Role-targeted rules don't apply here:
FaultRule.matches_role reads the process-global ELASTICDL_ROLE, and
every simulated pod shares this process.
"""

import json
import math
import os
import threading
import time
import random

from elasticdl_tpu.chaos.injection import FaultSchedule
from elasticdl_tpu.common import rpc
from elasticdl_tpu.common.log_utils import get_logger
from elasticdl_tpu.master.servicer import MasterServicer
from elasticdl_tpu.master.task_dispatcher import TaskDispatcher
from elasticdl_tpu.observability.aggregator import TelemetryAggregator
from elasticdl_tpu.observability.exporter import MetricsExporter
from elasticdl_tpu.observability.metrics import (
    MetricsRegistry,
    default_registry,
)
from elasticdl_tpu.observability.push import TelemetryPusher
from elasticdl_tpu.proto import elasticdl_tpu_pb2 as pb

logger = get_logger("fleet.harness")

# Simulated pods never collide with real pids: fake pid space starts at
# 10_000_000 + index * 1000 + incarnation.
_PID_BASE = 10_000_000


def pod_method_name(index):
    """The synthetic 'method' a pod's tick presents to the FaultSchedule
    (rules select pods by substring of this)."""
    return f"fleet.tick.pod-{index:04d}"


def churn_schedule(n_pods, kills=0, stragglers=0, start_tick=5,
                   window_ticks=6, straggler_factor=None, seed=0):
    """A seeded FaultSchedule for a fleet: `kills` pods go dead for
    `window_ticks` ticks (then relaunch), `stragglers` pods run slow for
    a window. Deterministic in (n_pods, counts, seed)."""
    rng = random.Random(seed)
    victims = rng.sample(range(n_pods), min(n_pods, kills + stragglers))
    rules = []
    for i, pod in enumerate(victims):
        kind = "unavailable" if i < kills else "latency"
        rules.append(
            {
                "method": f"pod-{pod:04d}",
                "kind": kind,
                "start": start_tick + rng.randrange(window_ticks),
                "count": window_ticks,
                "side": "client",
            }
        )
    return FaultSchedule(rules, seed=seed)


def preemption_wave_schedule(n_pods, fraction=0.2, at_tick=5,
                             window_ticks=6, seed=0):
    """A seeded FaultSchedule killing fraction*n_pods pods in ONE tick
    (the spot/maintenance preemption wave), all relaunching together
    after `window_ticks`. Deterministic in (n_pods, fraction, seed)."""
    rng = random.Random(seed)
    n_victims = max(1, int(round(n_pods * fraction)))
    victims = rng.sample(range(n_pods), min(n_pods, n_victims))
    rules = [
        {
            "method": f"pod-{pod:04d}",
            "kind": "unavailable",
            "start": at_tick,
            "count": window_ticks,
            "side": "client",
        }
        for pod in victims
    ]
    return FaultSchedule(rules, seed=seed)


class Relay:
    """One stage of the push-aggregation tree: buffers snapshots and
    forwards them to `sink` (another Relay's submit, or the root's RPC)
    once `batch` have gathered — callers also flush() on a cadence so a
    quiet subtree never strands a snapshot."""

    def __init__(self, sink, batch=16):
        self._sink = sink
        self._batch = max(1, batch)
        self._buf = []
        self._lock = threading.Lock()
        self.forwards = 0

    def submit(self, snapshots):
        flush_now = None
        with self._lock:
            self._buf.extend(snapshots)
            if len(self._buf) >= self._batch:
                flush_now, self._buf = self._buf, []
        if flush_now:
            self.forwards += 1
            self._sink(flush_now)

    def flush(self):
        with self._lock:
            pending, self._buf = self._buf, []
        if pending:
            self.forwards += 1
            self._sink(pending)


def build_relay_chain(report, n_leaves, fanout=16):
    """Relay levels for n_leaves pushers: leaves feed level-1 relays,
    each level batches `fanout` and feeds the next, the root forwards
    to `report` (the ReportTelemetry call). Depth is ceil(log_fanout n)
    — the O(log n) fan-in inversion. Returns (leaf_relays, all_relays);
    flush bottom-up via the `all_relays` list order."""
    fanout = max(2, fanout)
    levels = max(
        1, math.ceil(math.log(max(2, n_leaves), fanout))
    )
    all_relays = []
    root = Relay(report, batch=fanout)
    all_relays.append(root)
    current = [root]
    for _ in range(levels - 1):
        wanted = min(n_leaves, len(current) * fanout)
        nxt = [
            Relay(current[i % len(current)].submit, batch=fanout)
            for i in range(wanted)
        ]
        # Prepend: flushing all_relays in order must drain leaves first.
        all_relays[:0] = nxt
        current = nxt
    return current, all_relays


class SimPod:
    """One simulated worker or PS: a real registry with the families the
    aggregator derives from, plus the real task protocol for workers."""

    def __init__(self, index, role, harness, incarnation=0):
        self.index = index
        self.role = role
        self.is_worker = role.startswith("worker")
        self.harness = harness
        self.incarnation = incarnation
        self.pid = _PID_BASE + index * 1000 + incarnation
        self.alive = True
        self.straggler_factor = 1.0
        self.task_id = None
        self.leased = []  # batched-lease buffer (lease_batch > 1)
        self.unreported = []  # completed ids awaiting a batch report
        self.last_push = 0.0
        self._rng = random.Random(
            (harness.seed << 20) ^ (index << 4) ^ incarnation
        )
        self.registry = MetricsRegistry()
        if self.is_worker:
            self._h_phase = self.registry.histogram(
                "edl_phase_seconds",
                "Worker phase latency",
                labelnames=("phase",),
            )
            self._c_steps = self.registry.counter(
                "edl_steps_total", "Steps simulated"
            )
            # Data-plane families, same shapes as observability.datapath
            # (the metric-names lint enforces one shape per name): the
            # simulated feed path splits each step into read/decode with
            # a small starve tail, so the aggregator's datapath rollup
            # has fleet-scale input to derive from.
            self._c_dp_seconds = self.registry.counter(
                "edl_datapath_seconds_total",
                "Input pipeline time by stage (simulated)",
                labelnames=("stage",),
            )
            self._c_dp_records = self.registry.counter(
                "edl_datapath_records_total",
                "Records delivered to the training loop (simulated)",
            )
            self._g_dp_queue = self.registry.gauge(
                "edl_datapath_queue_depth",
                "Bounded feed queue occupancy (simulated)",
                labelnames=("queue",),
            )
        else:
            # Same labelnames as the real PS servicer: pods share no
            # registry with it, but the aggregator's per-shard derive
            # (and the metric-names lint) expects one shape per metric.
            self._c_push_b = self.registry.counter(
                "edl_ps_push_bytes_total",
                "Gradient push request bytes received, by shard",
                labelnames=("shard",),
            )
            self._c_pull_b = self.registry.counter(
                "edl_ps_pull_bytes_total",
                "Parameter/embedding pull response bytes sent",
                labelnames=("rpc", "shard"),
            )
        self.exporter = None
        self.pusher = None
        if harness.mode == "pull":
            self.exporter = MetricsExporter(
                self.registry, port=0, host="127.0.0.1"
            )
            self._advertise()
        else:
            self.pusher = TelemetryPusher(
                self.registry,
                self.role,
                full_every=harness.push_full_every,
            )
            # object identity is not enough once a pod relaunches: the
            # pusher's pid must track the incarnation.
            self.pusher.pid = self.pid

    # -- endpoint advertisement (pull mode), mirrors observability.setup --

    def _advert_path(self):
        return os.path.join(
            self.harness.endpoints_dir, f"{self.role}.json"
        )

    def _advertise(self):
        os.makedirs(self.harness.endpoints_dir, exist_ok=True)
        info = {
            "role": self.role,
            "job": self.harness.job,
            "pid": self.pid,
            "port": self.exporter.port,
            "host": "127.0.0.1",
        }
        tmp = f"{self._advert_path()}.tmp.{self.pid}"
        with open(tmp, "w") as f:
            json.dump(info, f)
        os.replace(tmp, self._advert_path())

    # -- lifecycle (chaos) --

    def kill(self):
        """SIGKILL semantics: the endpoint dies, the advert survives —
        exactly the stale-endpoint case the aggregator must absorb."""
        self.alive = False
        self.task_id = None
        self.leased = []
        self.unreported = []
        if self.exporter is not None:
            self.exporter.close()
            self.exporter = None

    def relaunch(self):
        """Come back as a fresh incarnation (new pid, empty registry) —
        the advert rewrite is what flips the endpoints-dir mtime."""
        self.__init__(
            self.index,
            self.role,
            self.harness,
            incarnation=self.incarnation + 1,
        )

    def close(self):
        if self.exporter is not None:
            self.exporter.close()
            self.exporter = None
        # Clean leave withdraws the advert (observability.close parity).
        if self.harness.mode == "pull":
            try:
                os.unlink(self._advert_path())
            except OSError:
                pass

    # -- one scheduler tick --

    def tick(self, now):
        if not self.alive:
            return
        step = self.harness.base_step_s * self.straggler_factor
        if self.is_worker:
            # Simulated work: the histogram moves like a real worker's,
            # no wall-clock is actually burned.
            draw = max(
                1e-4, self._rng.gauss(step, 0.15 * step)
            )
            self._h_phase.labels(phase="batch_process").observe(draw)
            self._c_steps.inc()
            # Feed-path attribution moves with the step: a straggler's
            # slowdown surfaces as starve seconds (its feed can't keep
            # up), which is exactly what the starvation alert watches.
            self._c_dp_seconds.labels(stage="read").inc(0.25 * draw)
            self._c_dp_seconds.labels(stage="decode").inc(0.15 * draw)
            starve = max(0.0, (self.straggler_factor - 1.0) * step)
            if starve:
                self._c_dp_seconds.labels(stage="starve").inc(starve)
            self._c_dp_records.inc(64)
            self._g_dp_queue.labels(queue="prefetch").set(
                self._rng.randint(0, 64)
            )
            self._task_rpc()
        else:
            shard = str(self.index)
            self._c_push_b.labels(shard=shard).inc(
                int(self._rng.uniform(0.5, 1.5) * 65536)
            )
            self._c_pull_b.labels(
                rpc="pull_parameters", shard=shard
            ).inc(int(self._rng.uniform(0.5, 1.5) * 65536))
        if self.pusher is not None and (
            now - self.last_push
            >= self.harness.push_interval
            * self._rng.uniform(0.9, 1.1)
        ):
            self.last_push = now
            self.harness.submit_push(self, self.pusher.snapshot())

    def _task_rpc(self):
        if self.harness.lease_batch > 1:
            return self._task_rpc_batched()
        stub = self.harness.stub
        try:
            if self.task_id is None:
                res = stub.get_task(
                    pb.GetTaskRequest(worker_id=self.index)
                )
                if res.task_id >= 0 and res.type != pb.WAIT:
                    self.task_id = res.task_id
                    self.harness.count("dispatched")
            else:
                stub.report_task_result(
                    pb.ReportTaskResultRequest(task_id=self.task_id)
                )
                self.task_id = None
                self.harness.count("reported")
        except Exception:
            self.harness.count("rpc_errors")

    def _task_rpc_batched(self):
        """Batched lease protocol, still at most ONE task RPC per tick:
        an empty buffer refills with get_task_batch; otherwise one task
        'completes' per tick and a full unreported buffer flushes as one
        report_task_results — so each RPC moves lease_batch tasks."""
        stub = self.harness.stub
        batch = self.harness.lease_batch
        try:
            if self.unreported and (
                len(self.unreported) >= batch or not self.leased
            ):
                req = pb.ReportTaskResultsRequest()
                for tid in self.unreported:
                    req.results.add(task_id=tid)
                stub.report_task_results(req)
                self.harness.count("reported", len(self.unreported))
                self.unreported = []
            elif not self.leased:
                res = stub.get_task_batch(
                    pb.GetTaskRequest(
                        worker_id=self.index, max_tasks=batch
                    )
                )
                if res.tasks:
                    self.leased = [t.task_id for t in res.tasks]
                    self.harness.count("dispatched", len(res.tasks))
            else:
                self.unreported.append(self.leased.pop(0))
        except Exception:
            self.harness.count("rpc_errors")


class FleetMaster:
    """The real master control plane under test: dispatcher + servicer
    behind gRPC, aggregator, /api/summary exporter."""

    def __init__(self, obs_dir, job="fleet", n_records=1 << 20,
                 records_per_task=64, interval=0.5, policy=False,
                 policy_kwargs=None, journal_dir=None,
                 snapshot_every=None):
        self.job = job
        self.task_d = TaskDispatcher(
            {"fleet": (0, n_records)},
            records_per_task=records_per_task,
            # The harness measures steady-state dispatch, not job
            # completion: enough epochs that the queue never drains.
            num_epochs=1_000_000,
            shuffle=False,
        )
        # Optional journal plane, wired exactly like the real Master:
        # restore-then-attach, providers registered before the
        # snapshot-on-start, incarnation bumped on recovery. This is what
        # the fleet-scale master-restart drill exercises.
        self.master_incarnation = 1
        self.journal = None
        if journal_dir:
            from elasticdl_tpu.master.journal import MasterJournal

            self.journal = MasterJournal(
                journal_dir, snapshot_every=snapshot_every
            )
            state = self.journal.load()
            if state["incarnation"] > 0:
                self.master_incarnation = state["incarnation"] + 1
                self.task_d.restore_state(state)
            self.task_d.attach_journal(self.journal)
            self.journal.add_state_provider(self.task_d.export_state)
            self.journal.add_state_provider(
                lambda: {"incarnation": self.master_incarnation}
            )
            self.journal.record(
                {"op": "incarnation", "value": self.master_incarnation}
            )
            self.journal.compact()
        self.servicer = MasterServicer(self.task_d)
        self._server, self.port = rpc.serve(
            self.servicer, rpc.MASTER_SERVICE, port=0
        )
        self.aggregator = TelemetryAggregator(
            obs_dir,
            registry=default_registry(),
            job=job,
            interval=interval,
        )
        self.policy = None
        self.world_hints = None
        if policy:
            # The REAL policy engine against the simulated fleet: same
            # summary input, same dispatcher actuators. No instance
            # manager (pods aren't processes), so the straggler rule's
            # blacklist+recover applies while restart/scale no-op. The
            # harness master loop ticks it synchronously — deterministic
            # decision timing instead of a second clock.
            from elasticdl_tpu.master.policy import (
                PolicyEngine,
                WorldHintBoard,
            )

            self.world_hints = WorldHintBoard()
            self.policy = PolicyEngine(
                self.aggregator.summary,
                self.task_d,
                world_hints=self.world_hints,
                **(policy_kwargs or {}),
            )
        self.servicer.bind_job_context(
            aggregator=self.aggregator,
            policy=self.policy,
            world_hints=self.world_hints,
            master_incarnation=self.master_incarnation,
        )
        self.exporter = MetricsExporter(
            default_registry(), port=0, host="127.0.0.1"
        )
        self.exporter.summary_provider = self._summary

    def _summary(self):
        summary = self.aggregator.summary()
        if self.policy is not None:
            summary["policy"] = self.policy.summary()
        return summary

    def close(self, crash=False):
        """Tear down; crash=True models SIGKILL — the gRPC server dies but
        the journal is NOT cleanly closed (no final snapshot), so whatever
        the WAL tail holds is exactly what a relaunch replays."""
        self.exporter.close()
        self.aggregator.close()
        stopped = self._server.stop(0 if crash else 1)
        if self.journal is not None and not crash:
            self.journal.close()
        return stopped


class FleetHarness:
    """N simulated pods + one real master, swept by carrier threads."""

    def __init__(self, n_workers=50, n_ps=0, obs_dir=None, mode="push",
                 tick_interval=0.25, push_interval=0.5,
                 push_full_every=16, relay_fanout=16, schedule=None,
                 seed=0, carriers=8, base_step_s=0.05,
                 aggregator_interval=0.5, job="fleet", lease_batch=1,
                 policy=False, policy_kwargs=None, journal_dir=None,
                 master_snapshot_every=None):
        assert mode in ("push", "pull"), mode
        if obs_dir is None:
            import tempfile

            obs_dir = tempfile.mkdtemp(prefix="edl-fleet-")
        self.obs_dir = obs_dir
        self.endpoints_dir = os.path.join(obs_dir, "endpoints")
        self.mode = mode
        self.job = job
        self.tick_interval = tick_interval
        self.push_interval = push_interval
        self.push_full_every = push_full_every
        self.base_step_s = base_step_s
        self.schedule = schedule
        self.seed = seed
        self.n_workers = n_workers
        self.n_ps = n_ps
        self.lease_batch = max(1, lease_batch)
        self._policy = policy
        self._policy_kwargs = policy_kwargs
        self._journal_dir = journal_dir
        self._master_snapshot_every = master_snapshot_every
        self.policy_decisions = []
        self._n_carriers = max(1, min(carriers, n_workers + n_ps))
        self._relay_fanout = relay_fanout
        self._agg_interval = aggregator_interval
        self._counts = {
            "dispatched": 0,
            "reported": 0,
            "rpc_errors": 0,
            "kills": 0,
            "relaunches": 0,
            "straggler_ticks": 0,
            "pushes": 0,
            "push_batches": 0,
            "need_full": 0,
        }
        self._count_lock = threading.Lock()
        self._stop = threading.Event()
        self._threads = []
        self.master = None
        self.stub = None
        self.pods = []
        self._leaf_relays = []
        self._all_relays = []
        self.master_tick_seconds = []
        self.ticks = 0

    # -- shared accounting --

    def count(self, key, n=1):
        with self._count_lock:
            self._counts[key] = self._counts.get(key, 0) + n

    def submit_push(self, pod, snapshot):
        self.count("pushes")
        if self._leaf_relays:
            relay = self._leaf_relays[
                pod.index % len(self._leaf_relays)
            ]
            relay.submit([snapshot])
        else:
            self._report_batch([snapshot])

    def _report_batch(self, snapshots):
        self.count("push_batches")
        try:
            req = pb.ReportTelemetryRequest(origin="fleet-relay")
            for snap in snapshots:
                req.snapshots.add(**snap)
            resp = self.stub.report_telemetry(req)
        except Exception:
            self.count("rpc_errors")
            return
        for role in resp.need_full:
            self.count("need_full")
            pod = self._pods_by_role.get(role)
            if pod is not None and pod.pusher is not None:
                pod.pusher.reset()

    # -- lifecycle --

    def start(self):
        if self.mode == "pull":
            self._raise_nofile(self.n_workers + self.n_ps)
        self.master = FleetMaster(
            self.obs_dir,
            job=self.job,
            interval=self._agg_interval,
            policy=self._policy,
            policy_kwargs=self._policy_kwargs,
            journal_dir=self._journal_dir,
            snapshot_every=self._master_snapshot_every,
        )
        self._channel = rpc.build_channel(f"127.0.0.1:{self.master.port}")
        self.stub = rpc.Stub(self._channel, rpc.MASTER_SERVICE)
        self.pods = [
            SimPod(i, f"worker-{i}", self)
            for i in range(self.n_workers)
        ] + [
            SimPod(self.n_workers + j, f"ps-{j}", self)
            for j in range(self.n_ps)
        ]
        self._pods_by_role = {p.role: p for p in self.pods}
        if self.mode == "push":
            self._leaf_relays, self._all_relays = build_relay_chain(
                self._report_batch,
                len(self.pods),
                fanout=self._relay_fanout,
            )
        for c in range(self._n_carriers):
            t = threading.Thread(
                target=self._carrier,
                args=(self.pods[c::self._n_carriers],),
                name=f"fleet-carrier-{c}",
                daemon=True,
            )
            t.start()
            self._threads.append(t)
        t = threading.Thread(
            target=self._master_loop, name="fleet-master-tick",
            daemon=True,
        )
        t.start()
        self._threads.append(t)
        self._started_at = time.monotonic()
        return self

    @staticmethod
    def _raise_nofile(n_pods):
        # ~3 fds per pull exporter (listen socket + transient accepts):
        # bump the soft limit toward the hard one when 500 pods need it.
        try:
            import resource

            soft, hard = resource.getrlimit(resource.RLIMIT_NOFILE)
            want = n_pods * 4 + 512
            if soft < want:
                resource.setrlimit(
                    resource.RLIMIT_NOFILE, (min(want, hard), hard)
                )
        except (ImportError, ValueError, OSError):
            pass

    def _carrier(self, pods):
        while not self._stop.is_set():
            sweep_start = time.monotonic()
            now = time.time()
            for pod in pods:
                self._apply_chaos(pod)
                pod.tick(now)
                if self._stop.is_set():
                    return
            # The carrier owning pod 0 flushes the relay tree once per
            # sweep (bottom-up: build_relay_chain orders leaves first)
            # so buffered snapshots never outlive a tick.
            if pods and pods[0].index == 0:
                for relay in self._all_relays:
                    relay.flush()
            elapsed = time.monotonic() - sweep_start
            self._stop.wait(max(0.005, self.tick_interval - elapsed))

    def _apply_chaos(self, pod):
        if self.schedule is None:
            faults = ()
        else:
            faults = self.schedule.decide(
                pod_method_name(pod.index), "client"
            )
        dead = any(r.kind == "unavailable" for r in faults)
        slow = any(r.kind == "latency" for r in faults)
        if dead and pod.alive:
            pod.kill()
            self.count("kills")
        elif not dead and not pod.alive:
            pod.relaunch()
            self.count("relaunches")
        if slow and pod.alive:
            pod.straggler_factor = 4.0
            self.count("straggler_ticks")
        elif pod.alive:
            pod.straggler_factor = 1.0

    def _master_loop(self):
        while not self._stop.is_set():
            t0 = time.perf_counter()
            try:
                self.master.aggregator.poll_once()
                if self.master.policy is not None:
                    # Policy rides the same tick as the aggregator:
                    # decisions follow directly from the rollup the tick
                    # just produced (deterministic causality for tests).
                    self.policy_decisions.extend(
                        self.master.policy.tick()
                    )
                if self.master.journal is not None:
                    # Journal maintenance outside every dispatcher/
                    # provider lock — same placement rule as the real
                    # master's watchdog tick (MasterJournal.maybe_compact).
                    self.master.journal.maybe_compact()
            except Exception:
                logger.warning("fleet master tick failed", exc_info=True)
            self.master_tick_seconds.append(time.perf_counter() - t0)
            self.ticks += 1
            self._stop.wait(self._agg_interval)

    def run(self, seconds):
        deadline = time.monotonic() + seconds
        while time.monotonic() < deadline and not self._stop.is_set():
            time.sleep(0.05)
        return self

    def restart_master(self):
        """Kill the master mid-run (SIGKILL semantics: no journal close,
        no final snapshot) and bring up a replacement over the same
        journal dir. Pods keep ticking throughout — their RPCs against
        the dead endpoint land in rpc_errors, exactly like a real
        restart — and the harness re-points its shared stub at the new
        port once replay finishes. Requires journal_dir (a journal-less
        master would come back with an empty queue and re-dispatch
        everything)."""
        assert self._journal_dir, "restart_master needs journal_dir"
        old = self.master
        stopped = old.close(crash=True)
        # Let in-flight handlers drain so the old journal handle cannot
        # interleave a final append with the successor's WAL writes.
        stopped.wait(timeout=10.0)
        old.journal.close()
        self.count("master_restarts")
        self.master = FleetMaster(
            self.obs_dir,
            job=self.job,
            interval=self._agg_interval,
            policy=self._policy,
            policy_kwargs=self._policy_kwargs,
            journal_dir=self._journal_dir,
            snapshot_every=self._master_snapshot_every,
        )
        old_channel = self._channel
        self._channel = rpc.build_channel(
            f"127.0.0.1:{self.master.port}"
        )
        self.stub = rpc.Stub(self._channel, rpc.MASTER_SERVICE)
        if old_channel is not None:
            old_channel.close()
        return self.master

    def stats(self):
        with self._count_lock:
            counts = dict(self._counts)
        summary = (
            self.master.aggregator.summary() if self.master else {}
        )
        ticks = sorted(self.master_tick_seconds)
        elapsed = time.monotonic() - getattr(
            self, "_started_at", time.monotonic()
        )
        out = {
            "mode": self.mode,
            "pods": len(self.pods),
            "counts": counts,
            "lease_batch": self.lease_batch,
            "dispatch_tasks_per_s": (
                counts.get("reported", 0) / elapsed if elapsed > 0 else 0.0
            ),
            "master_ticks": len(ticks),
            "master_tick_p50_s": ticks[len(ticks) // 2] if ticks else None,
            "master_tick_max_s": ticks[-1] if ticks else None,
            "fleet": summary.get("fleet") or {},
            "datapath": summary.get("datapath") or {},
            "roles_scraped": len(summary.get("roles_scraped") or ()),
            "summary_ts": summary.get("ts"),
        }
        if self.master is not None and self.master.policy is not None:
            out["policy"] = self.master.policy.summary()
            out["policy_decisions"] = list(self.policy_decisions)
        return out

    def stop(self):
        self._stop.set()
        for t in self._threads:
            t.join(timeout=10.0)
        self._threads = []
        # Exporter shutdown blocks up to the HTTP server's poll
        # interval; serially that makes a 500-pod pull fleet take
        # minutes to tear down. Close in parallel.
        if self.pods:
            from concurrent.futures import ThreadPoolExecutor

            with ThreadPoolExecutor(max_workers=64) as pool:
                list(pool.map(lambda p: p.close(), self.pods))
        if self.master is not None:
            self.master.close()
            self.master = None
        if getattr(self, "_channel", None) is not None:
            self._channel.close()
            self._channel = None
