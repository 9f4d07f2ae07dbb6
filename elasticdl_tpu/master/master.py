"""The master orchestrator: control plane of one elastic training job.

Reference counterpart: /root/reference/elasticdl/python/master/
master.py:97-509. Builds the task dispatcher from the dataset shards, serves
the Master gRPC service, spawns PS + worker instances through an instance
manager backend, and runs the poll loop: job completion, all-workers-failed
abort, the task-timeout watchdog (a task running > 3x the rolling mean
completion time gets its worker's tasks recovered and its membership entry
dropped, master.py:487-509), and the worker-liveness timeout
(servicer.py:93-94,131-148).
"""

import os
import sys
import time

from elasticdl_tpu import observability
from elasticdl_tpu.common import knobs, rpc
from elasticdl_tpu.common.args import build_arguments_from_parsed_result
from elasticdl_tpu.common.constants import DistributionStrategy
from elasticdl_tpu.common.log_utils import get_logger
from elasticdl_tpu.common.model_utils import get_model_spec
from elasticdl_tpu.data.reader import create_data_reader
from elasticdl_tpu.master.evaluation_service import EvaluationService
from elasticdl_tpu.master.instance_manager import (
    LocalProcessInstanceManager,
)
from elasticdl_tpu.master.membership import MembershipManager
from elasticdl_tpu.master.servicer import MasterServicer
from elasticdl_tpu.master.task_dispatcher import TaskDispatcher
from elasticdl_tpu.observability import tracing

logger = get_logger("master.master")

# Flag subsets relayed into spawned processes — each must stay within what
# the receiving parser (worker_parser / ps_parser) actually accepts.
_WORKER_RELAY_ARGS = [
    "job_name",
    "model_zoo",
    "model_def",
    "distribution_strategy",
    "minibatch_size",
    "get_model_steps",
    "ps_wire_dtype",
    "log_loss_steps",
    "seed",
    "model_parallel_size",
    "pipeline_stages",
    "pipeline_schedule",
    "pipeline_microbatches",
    "pipeline_virtual_stages",
    "context_parallel_size",
    "context_parallel_impl",
    "multi_host",
    "zero1",
    "quantized_grads",
    "training_data",
    "validation_data",
    "prediction_data",
    "records_per_task",
    "num_epochs",
    "prefetch_records",
    "profile_dir",
    "profile_start_step",
    "profile_steps",
]
_PS_RELAY_ARGS = [
    "job_name",
    "model_zoo",
    "model_def",
    "seed",
]


class Master:
    def __init__(self, args):
        self.args = args
        # The set-up phase `setup.master` runs from here to the port
        # bound in prepare().
        self.setup_started = time.time()
        # The observability plane comes up FIRST so task creation, instance
        # launches, and every later lifecycle transition land in the event
        # log/registry. Spawned worker/PS processes find the same obs dir
        # (and the job identity) through the environment.
        obs_dir = getattr(args, "metrics_dir", "") or knobs.get_str(
            observability.OBS_DIR_ENV
        )
        if obs_dir:
            os.environ[observability.OBS_DIR_ENV] = obs_dir
        os.environ[observability.JOB_NAME_ENV] = args.job_name
        self.obs = observability.setup(
            role="master", job=args.job_name, obs_dir=obs_dir
        )
        # A fixed metrics port is the master's alone; local children must
        # bind ephemeral ports or they'd all collide on this host.
        os.environ.pop(observability.METRICS_PORT_ENV, None)
        # Children of `setup.master`: the model module's imports (jax and
        # flax among them, no backend), then the shards read off the
        # record files and cut into tasks.
        with tracing.span("setup.model_spec", cat=tracing.SETUP):
            if args.model_zoo:
                sys.path.insert(0, args.model_zoo)
            self.spec = get_model_spec(args.model_def)

        # --- data shards -> task dispatcher (reference master.py:61-94) ---
        tasks_started = time.time()
        reader_factory = self.spec.create_data_reader or create_data_reader
        training_shards = (
            reader_factory(args.training_data).create_shards()
            if args.training_data
            else {}
        )
        evaluation_shards = (
            reader_factory(args.validation_data).create_shards()
            if args.validation_data
            else {}
        )
        prediction_shards = (
            reader_factory(args.prediction_data).create_shards()
            if args.prediction_data
            else {}
        )
        self.task_d = TaskDispatcher(
            training_shards,
            evaluation_shards,
            prediction_shards,
            records_per_task=args.records_per_task,
            num_epochs=args.num_epochs,
            shuffle=args.shuffle_shards,
            seed=args.seed,
        )
        tracing.record_span(
            "setup.task_create", tasks_started,
            time.time() - tasks_started, cat=tracing.SETUP,
        )

        if args.checkpoint_dir_for_init and training_shards:
            # Restart-from-checkpoint: don't re-dispatch already-trained
            # records (reference master.py:185-201 restores the completed
            # step count from the checkpoint version).
            from elasticdl_tpu.ps.checkpoint import (
                latest_complete_version,
                read_total_records,
            )

            version = latest_complete_version(args.checkpoint_dir_for_init)
            if version:
                # The checkpoint carries the exact number of training
                # records consumed (version alone is ambiguous: a sync
                # window merges a variable number of pushes, and tasks end
                # in partial batches). Fall back to a version-based
                # estimate for pre-field checkpoints.
                records = read_total_records(
                    args.checkpoint_dir_for_init, version
                )
                if not records:
                    records = (
                        version
                        * (
                            1
                            if args.use_async
                            else max(args.grads_to_wait, 1)
                        )
                        * args.minibatch_size
                    )
                self.task_d.set_completed_records(records)

        self.metrics_service = None
        if getattr(args, "metrics_dir", ""):
            from elasticdl_tpu.master.metrics_service import MetricsService

            self.metrics_service = MetricsService(args.metrics_dir)

        self.evaluation_service = None
        if evaluation_shards:
            self.evaluation_service = EvaluationService(
                self.task_d,
                self.spec.build_metrics
                if self.spec.eval_metrics_fn
                else dict,
                eval_steps=args.evaluation_steps,
                on_results=(
                    self.metrics_service.on_evaluation_results
                    if self.metrics_service
                    else None
                ),
            )

        self.membership = (
            MembershipManager(coordinator_port=args.coordinator_port)
            if args.distribution_strategy == DistributionStrategy.ALLREDUCE
            else None
        )
        if args.output and training_shards:
            # Arm the final export task (reference: SavedModel export via a
            # train-end callback task, master/callbacks.py:38-66).
            self.task_d.enable_train_end_task()

        # --- survivable control plane (ELASTICDL_MASTER_JOURNAL_DIR) ---
        # Replay snapshot+WAL, restore the dispatcher/membership state,
        # bump the incarnation, and mirror every mutation from here on.
        # All init-time dispatcher setup above (task creation, checkpoint
        # fast-forward, train-end arming) happens BEFORE the attach, so
        # the WAL only ever holds post-start ops (prepare() snapshots the
        # merged state right before serving).
        from elasticdl_tpu.master.journal import open_master_journal

        self.journal = open_master_journal()
        self.master_incarnation = 1
        self._recovered_state = None
        self._recovered_leases = []
        if self.journal is not None:
            state = self.journal.load()
            # Every journaled master records its incarnation at startup,
            # so a nonzero replayed incarnation means a previous life.
            if state["incarnation"] > 0:
                self._recovered_state = state
                self.master_incarnation = state["incarnation"] + 1
                self.task_d.restore_state(state)
                self._recovered_leases = self.task_d.inflight_leases()
                if self.membership is not None:
                    self.membership.restore_state(state)
                logger.warning(
                    "Master journal replayed: incarnation %d, "
                    "records_done=%d, %d in-flight leases restored, "
                    "hint_seq=%d",
                    self.master_incarnation,
                    state["records_done"],
                    len(self._recovered_leases),
                    state["hint_seq"],
                )
            self.task_d.attach_journal(self.journal)
            self.journal.add_state_provider(self.task_d.export_state)
            if self.membership is not None:
                self.membership.attach_journal(self.journal)
                self.journal.add_state_provider(
                    self.membership.export_state
                )
            self.journal.add_state_provider(
                lambda: {"incarnation": self.master_incarnation}
            )
            self.journal.record({
                "op": "incarnation", "value": self.master_incarnation,
            })
        self.step_leases = None
        if self.membership is not None and getattr(
            args, "multi_host", False
        ):
            from elasticdl_tpu.master.step_lease import StepLeaseManager

            self.step_leases = StepLeaseManager(
                self.task_d, self.membership
            )
        self.servicer = MasterServicer(
            self.task_d,
            self.evaluation_service,
            self.membership,
            worker_liveness_timeout=args.worker_liveness_timeout_seconds,
            step_lease_manager=self.step_leases,
        )
        self._server = None
        self.port = None
        self.aggregator = None
        self.policy = None
        self.world_hints = None
        self.instance_manager = self._build_instance_manager(args)

    # ---------- instance manager wiring ----------

    def _build_instance_manager(self, args):
        if args.instance_backend == "none" or (
            args.num_workers == 0 and args.num_ps == 0
        ):
            return None
        if args.instance_backend == "local_process":
            return LocalProcessInstanceManager(
                self._command_for,
                num_workers=args.num_workers,
                num_ps=args.num_ps,
                task_dispatcher=self.task_d,
                membership=self.membership,
                max_relaunches=args.max_relaunches,
            )
        if args.instance_backend == "k8s":
            from elasticdl_tpu.master.k8s_instance_manager import (
                K8sInstanceManager,
            )

            envs = {observability.JOB_NAME_ENV: args.job_name}
            if knobs.is_set(observability.OBS_DIR_ENV):
                envs[observability.OBS_DIR_ENV] = knobs.raw(
                    observability.OBS_DIR_ENV
                )
            # Log identity/format follows the master into the pods so a
            # chaos run's JSON logs correlate across roles; the compile
            # cache dir follows so every pod of the job shares ONE
            # persistent cache (a relaunched pod rehydrates executables
            # its predecessor or peers already compiled).
            for var in ("ELASTICDL_LOG_LEVEL", "ELASTICDL_LOG_FORMAT"):
                if knobs.is_set(var):
                    envs[var] = knobs.raw(var)
            for var in (
                "JAX_COMPILATION_CACHE_DIR",
                "JAX_ENABLE_COMPILATION_CACHE",
            ):
                if os.environ.get(var):
                    envs[var] = os.environ[var]
            return K8sInstanceManager(
                args.namespace,
                args.job_name,
                args.image_name,
                self._command_for,
                num_workers=args.num_workers,
                num_ps=args.num_ps,
                task_dispatcher=self.task_d,
                membership=self.membership,
                worker_resources=args.worker_resources,
                ps_resources=args.ps_resources,
                worker_priority=args.worker_pod_priority,
                volumes=args.volume,
                max_relaunches=args.max_relaunches,
                envs=envs,
            )
        raise ValueError(f"unknown backend {args.instance_backend!r}")

    def _master_addr(self):
        host = os.environ.get("MY_POD_IP", "127.0.0.1")
        return f"{host}:{self.port}"

    PS_SERVICE_PORT = 50002

    def _ps_addr(self, ps_id):
        # Local backend: PS picks port ps_base+ps_id on this host; k8s
        # backend: stable per-PS service names (created by the k8s instance
        # manager) on PS_SERVICE_PORT. master_port 0 means "bind any" for
        # the master itself and cannot seed PS ports — fall back to the
        # default base so PS ports stay valid.
        if self.args.instance_backend == "k8s":
            return (
                f"{self.args.job_name}-ps-{ps_id}:{self.PS_SERVICE_PORT}"
            )
        # With --master_port 0, derive from the ACTUALLY BOUND master port
        # (prepare() runs before any instance spawns) so two concurrent
        # jobs on one host don't collide on a fixed base.
        base = self.args.master_port or self.port or 50001
        return f"127.0.0.1:{base + 1 + ps_id}"

    def ps_addrs(self):
        return ",".join(
            self._ps_addr(i) for i in range(self.args.num_ps)
        )

    def _command_for(self, kind, instance_id):
        """argv for a spawned instance (reference master.py:424-476 builds
        worker/PS pod command lines the same way)."""
        relay = build_arguments_from_parsed_result(
            self.args,
            filter_args=(
                _WORKER_RELAY_ARGS if kind == "worker" else _PS_RELAY_ARGS
            ),
        )
        if kind == "worker":
            argv = [
                sys.executable,
                "-m",
                "elasticdl_tpu.worker.main",
                "--worker_id",
                str(instance_id),
                "--master_addr",
                self._master_addr(),
            ]
            if self.args.num_ps:
                argv += ["--ps_addrs", self.ps_addrs()]
            if self.args.training_data:
                if self.args.validation_data:
                    argv += ["--job_type", "training_with_evaluation"]
            elif self.args.validation_data:
                argv += ["--job_type", "evaluation_only"]
            elif self.args.prediction_data:
                argv += ["--job_type", "prediction_only"]
            for flag in ("output", "checkpoint_dir_for_init"):
                value = getattr(self.args, flag, "")
                if value:
                    argv += [f"--{flag}", str(value)]
            return argv + relay
        if kind == "ps":
            ps_port = int(self._ps_addr(instance_id).rsplit(":", 1)[1])
            argv = [
                sys.executable,
                "-m",
                "elasticdl_tpu.ps.main",
                "--ps_id",
                str(instance_id),
                "--num_ps",
                str(self.args.num_ps),
                "--port",
                str(ps_port),
                "--master_addr",
                self._master_addr(),
            ]
            for flag in (
                "checkpoint_dir",
                "checkpoint_steps",
                "keep_checkpoint_max",
                "checkpoint_dir_for_init",
                "grads_to_wait",
                "sync_version_tolerance",
                "sync_window_timeout",
            ):
                value = getattr(self.args, flag, None)
                # `is not None` so explicit numeric zeros (e.g.
                # --sync_window_timeout 0) still relay; empty-string
                # defaults for the path flags stay dropped.
                if value is not None and value != "":
                    argv += [f"--{flag}", str(value)]
            if not self.args.use_async:
                argv += ["--use_sync"]
            if self.args.lr_staleness_modulation:
                argv += ["--lr_staleness_modulation"]
            return argv + relay
        raise ValueError(kind)

    # ---------- lifecycle ----------

    def prepare(self):
        # Orphan-reaper beacon: while this file stays fresh the job's
        # process group is alive on purpose; once it goes stale,
        # tools/reap_orphans.py may SIGKILL the whole group.
        from elasticdl_tpu.common.heartbeat import HeartbeatWriter

        self._heartbeat = HeartbeatWriter(job=self.args.job_name).start()
        if self.obs.metrics_port:
            logger.info(
                "Prometheus metrics on :%d/metrics", self.obs.metrics_port
            )
        if self.obs.obs_dir:
            # Job-level telemetry: scrape every advertised per-role
            # endpoint, derive throughput/straggler/imbalance signals,
            # re-export them as edl_job_* gauges + /api/summary, and run
            # the alert rules. Needs the obs dir (endpoint discovery);
            # without one there is nothing to aggregate.
            from elasticdl_tpu.observability.aggregator import (
                TelemetryAggregator,
            )

            self.aggregator = TelemetryAggregator(
                self.obs.obs_dir, job=self.args.job_name
            ).start()
            if self.obs.exporter is not None:
                self.obs.exporter.summary_provider = (
                    self.aggregator.summary
                )
        from elasticdl_tpu.master.policy import (
            PolicyEngine,
            WorldHintBoard,
            policy_enabled,
        )

        self.world_hints = WorldHintBoard()
        if self.journal is not None:
            # hint_seq survives the restart: a board resuming from 0 would
            # make trainers silently ignore every post-restart hint.
            if self._recovered_state is not None:
                self.world_hints.restore_state(self._recovered_state)
            self.world_hints.attach_journal(self.journal)
            self.journal.add_state_provider(self.world_hints.export_state)
        if policy_enabled() and self.aggregator is not None:
            # The closed loop: aggregator signals -> rules -> actuators.
            # Scale decisions announce through the world-hint board first
            # so workers AOT-compile the announced world before it forms.
            self.policy = PolicyEngine(
                self.aggregator.summary,
                self.task_d,
                instance_manager=self.instance_manager,
                world_hints=self.world_hints,
            )
            if self.journal is not None:
                # Resume without re-firing already-applied decisions:
                # restored cooldowns keep them suppressed.
                if self._recovered_state is not None:
                    self.policy.restore_state(self._recovered_state)
                self.policy.attach_journal(self.journal)
                self.journal.add_state_provider(self.policy.export_state)
            self.policy.start()
            if self.obs.exporter is not None:
                self.obs.exporter.summary_provider = self._summary
        self.servicer.bind_job_context(
            instance_manager=self.instance_manager,
            metrics_port=self.obs.metrics_port,
            aggregator=self.aggregator,
            policy=self.policy,
            world_hints=self.world_hints,
            master_incarnation=self.master_incarnation,
        )
        if self.journal is not None:
            # Snapshot-on-start: fold the replayed (or fresh) state of
            # every provider into snapshot.json and truncate the WAL, so
            # replay time is bounded by post-start activity only.
            self.journal.compact()
        if self._recovered_state is not None:
            # Re-lease trail: owners that reappear within the liveness
            # window keep their restored leases (seed_liveness grants the
            # grace); the watchdog sweeps the rest back to the queue.
            owners = sorted({
                wid for _, wid, _ in self._recovered_leases
            })
            self.servicer.seed_liveness(owners)
            observability.emit_event(
                "master_recovered",
                incarnation=self.master_incarnation,
                records_done=self._recovered_state["records_done"],
                leases=len(self._recovered_leases),
                hint_seq=self._recovered_state["hint_seq"],
                membership_epoch=self._recovered_state[
                    "membership_epoch"
                ],
            )
            for tid, wid, task in self._recovered_leases:
                observability.emit_event(
                    "lease_reissued",
                    task_id=tid,
                    worker=wid,
                    shard=task.shard_name,
                    start=task.start,
                    end=task.end,
                )
        # Bind the port LAST: the first RPC any client can land must
        # already see the recovered world — bumped incarnation in
        # JobStatusResponse, restored hint board, seeded liveness. A
        # master that serves while still wiring recovery shows a
        # regressed hint_seq/incarnation window to riding workers.
        self._server, self.port = rpc.serve(
            self.servicer, rpc.MASTER_SERVICE, port=self.args.master_port
        )
        logger.info("Master serving on port %d", self.port)
        tracing.record_span(
            "setup.master", self.setup_started,
            time.time() - self.setup_started, cat=tracing.SETUP,
        )
        if self.instance_manager is not None:
            if self.args.num_ps:
                self.instance_manager.start_parameter_servers()
            self.instance_manager.start_workers()
        if (
            self.metrics_service is not None
            and self.args.instance_backend == "k8s"
        ):
            # In-cluster TensorBoard exposure (reference
            # k8s_tensorboard_client.py:22-66): a LoadBalancer service
            # pointing at this master pod; `edl tensorboard
            # --logdir <metrics_dir>` serves behind it.
            try:
                client = getattr(self.instance_manager, "_client", None)
                if client is not None:
                    client.create_tensorboard_service()
                    logger.info(
                        "Created TensorBoard LoadBalancer service "
                        "tensorboard-%s", self.args.job_name,
                    )
            except Exception:
                logger.warning(
                    "TensorBoard service creation failed", exc_info=True
                )

    def _summary(self):
        """Aggregator summary with the policy plane merged in, so
        /api/summary (and `edl dash`) shows decisions next to signals."""
        summary = self.aggregator.summary()
        if self.policy is not None:
            summary["policy"] = self.policy.summary()
        return summary

    def run(self, poll_seconds=None):
        """Poll until done/failed (reference master.py:238-263). Returns the
        process exit code."""
        poll = poll_seconds or min(
            5.0, self.args.task_timeout_check_seconds
        )
        last_watchdog = time.time()
        last_metrics = time.time()
        last_records = self.task_d.stats()["records_done"]
        # Brief linger before the server stops on ANY terminal path, so
        # monitors polling get_job_status can observe the terminal state
        # (finished OR failed) instead of an ambiguous UNAVAILABLE.
        def linger():
            time.sleep(
                getattr(self.args, "shutdown_linger_seconds", 2.0)
            )

        try:
            while True:
                if self.task_d.finished():
                    logger.info("All tasks complete; job done")
                    linger()
                    return 1 if self.task_d.job_failed else 0
                if self.task_d.job_failed:
                    logger.error("Job failed (task retries exhausted)")
                    linger()
                    return 1
                if self.instance_manager is not None:
                    if self.instance_manager.all_workers_failed():
                        logger.error("All workers failed; aborting job")
                        return 1
                    if self.instance_manager.all_workers_done():
                        # Every worker reached a terminal state yet tasks
                        # remain (finished() was checked above): nothing can
                        # make progress.
                        logger.error(
                            "All workers exited but tasks remain; "
                            "aborting job"
                        )
                        return 1
                now = time.time()
                if (
                    now - last_watchdog
                    >= self.args.task_timeout_check_seconds
                ):
                    last_watchdog = now
                    self._run_watchdog()
                    # Journal maintenance rides the watchdog tick: this
                    # thread holds no dispatcher/policy lock here, which
                    # compaction requires (it calls back into the state
                    # providers — see MasterJournal.maybe_compact).
                    if self.journal is not None:
                        self.journal.maybe_compact()
                if self.metrics_service and now - last_metrics >= 30.0:
                    stats = self.task_d.stats()
                    elapsed = now - last_metrics
                    self.metrics_service.log_scalars(
                        "train",
                        self.servicer.max_model_version,
                        {
                            "records_per_sec": (
                                stats["records_done"] - last_records
                            ) / elapsed,
                            "records_done": stats["records_done"],
                            "epoch": stats["epoch"],
                            "todo_tasks": stats["todo"],
                            "doing_tasks": stats["doing"],
                        },
                    )
                    last_metrics = now
                    last_records = stats["records_done"]
                time.sleep(poll)
        finally:
            self.stop()

    def _run_watchdog(self):
        """Task-timeout + liveness watchdog (reference master.py:487-509)."""
        from elasticdl_tpu.master.step_lease import is_lease_owner

        # Synthetic lease owners are excluded: lease lifetime is governed
        # by membership epochs (step_lease.py aborts stale leases), and a
        # watchdog recovery here would yank tasks out from under a live
        # world mid-lease.
        slow = {
            wid: seen
            for wid, seen in self.task_d.doing_tasks_over_timeout().items()
            if not is_lease_owner(wid)
        }
        deadline = (
            time.time() - self.args.worker_liveness_timeout_seconds
        )
        silent = {
            wid
            for wid, ts in self.servicer.snapshot_liveness().items()
            if ts < deadline
        }
        for worker_id in set(slow) | silent:
            why = "slow" if worker_id in slow else "silent"
            # What the slow rule saw (task, age, mean, threshold): a
            # firing can be told from a host pause by its record alone.
            seen = slow.get(worker_id, {})
            logger.warning(
                "Watchdog: recovering tasks of %s worker %d%s",
                why,
                worker_id,
                "".join(f" {k}={v}" for k, v in seen.items()),
            )
            observability.emit_event(
                "task_timeout", worker=worker_id, reason=why, **seen
            )
            self.task_d.recover_tasks(worker_id)
            self.servicer.forget_worker(worker_id)
            if self.membership is not None:
                # Drop it from the comm group so survivors re-mesh instead
                # of blocking on the dead rank's next collective.
                self.membership.remove_worker(worker_id)

    def stop(self):
        heartbeat = getattr(self, "_heartbeat", None)
        if heartbeat is not None:
            heartbeat.close()
            self._heartbeat = None
        if self.policy is not None:
            self.policy.close()
            self.policy = None
        if self.aggregator is not None:
            self.aggregator.close()
            self.aggregator = None
        if self.instance_manager is not None:
            self.instance_manager.stop()
        if self.metrics_service is not None:
            # Final snapshot so short jobs (ending inside the periodic
            # interval) still leave a record.
            stats = self.task_d.stats()
            self.metrics_service.log_scalars(
                "train",
                self.servicer.max_model_version,
                {
                    "records_done": stats["records_done"],
                    "epoch": stats["epoch"],
                    "todo_tasks": stats["todo"],
                    "doing_tasks": stats["doing"],
                },
            )
            self.metrics_service.close()
        if self._server is not None:
            self._server.stop(2)
        if getattr(self, "journal", None) is not None:
            self.journal.close()
            self.journal = None
        # Flush + release the per-process trace/event files so a monitor
        # reading them right after exit sees complete lines; also resets
        # the process-global handle for in-process tests that run several
        # masters in one interpreter.
        self.obs.close()
