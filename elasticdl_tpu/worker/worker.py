"""The worker loop: pull tasks, train/evaluate/predict minibatches, report.

Reference counterpart (/root/reference/elasticdl/python/worker/
worker.py:42-444): job-type dispatch, per-minibatch retry (<=64), evaluation
tasks interleaved into training, prediction output processing, train-end
callback task handling.
"""

import time
import traceback

import grpc

from elasticdl_tpu.common.constants import (
    DEFAULT_MAX_MINIBATCH_RETRY_NUM,
    JobType,
)
from elasticdl_tpu.common.log_utils import get_logger
from elasticdl_tpu.common.model_utils import Modes
from elasticdl_tpu.common.timing import Timing
from elasticdl_tpu.observability import datapath, emit_event, tracing
from elasticdl_tpu.observability.metrics import default_registry
from elasticdl_tpu.proto import elasticdl_tpu_pb2 as pb
from elasticdl_tpu.worker.step_clock import StepDoneClock
from elasticdl_tpu.worker.task_data_service import TaskDataService

logger = get_logger("worker.worker")

_REG = default_registry()
_STEPS = _REG.counter(
    "edl_worker_steps_total", "Minibatch steps this worker dispatched"
)
_TASKS = _REG.counter(
    "edl_worker_tasks_total",
    "Tasks this worker processed, by result",
    labelnames=("result",),
)
_MODEL_STATS_TOTAL = _REG.counter(
    "edl_model_stats_total",
    "What the model's step reported (a routed layer's assignment counts, "
    "...), summed over the steps whose loss was read",
    labelnames=("name",),
)
_MODEL_STATS = _REG.gauge(
    "edl_model_stats",
    "What the model's step reported, newest step whose loss was read",
    labelnames=("name",),
)
_PHASE_SECONDS = _REG.histogram(
    "edl_phase_seconds",
    "Worker phase latency (batch_process + trainer phases)",
    labelnames=("phase",),
)


class Worker:
    def __init__(
        self,
        worker_id,
        master_client,
        data_reader,
        model_spec,
        trainer,
        minibatch_size=64,
        job_type=JobType.TRAINING_ONLY,
        log_loss_steps=100,
        max_minibatch_retries=DEFAULT_MAX_MINIBATCH_RETRY_NUM,
        extra_callbacks=(),
        profile_dir="",
        profile_start_step=10,
        profile_steps=5,
        lease_mode=False,
    ):
        self._worker_id = worker_id
        self._mc = master_client
        self._tds = TaskDataService(master_client, data_reader)
        self._spec = model_spec
        self._trainer = trainer
        self._minibatch_size = minibatch_size
        self._job_type = job_type
        self._log_loss_steps = log_loss_steps
        self._max_minibatch_retries = max_minibatch_retries
        self._metadata = data_reader.metadata
        # Step-synchronized lease mode (multi-host AllReduce): training is
        # driven by whole-world leases instead of independent task pulls.
        self._lease_mode = lease_mode
        self._steps = 0
        # The newest logged step whose loss is still unread: (step,
        # version, loss, model stats). It is read once the next step is
        # on the device's queue (_log_unread_loss).
        self._unread_loss = None
        self._timing = Timing().bind_histogram(_PHASE_SECONDS)
        # When each dispatched step left the device, without a fence.
        self._step_clock = StepDoneClock()
        trainer_timing = getattr(trainer, "timing", None)
        if trainer_timing is not None:
            # Trainer phases (pull/step/push) reach /metrics through the
            # same labeled histogram.
            trainer_timing.bind_histogram(_PHASE_SECONDS)
        # One-shot device trace of steady-state steps (past the compile):
        # [profile_start_step, profile_start_step + profile_steps), written
        # as a TensorBoard trace-viewer profile. The reference's deepest
        # tracing is wall-clock Timing (timing_utils.py:17-48); on TPU the
        # XLA-level trace is the tool that actually explains a step.
        self._profile_dir = profile_dir
        self._profile_start_step = profile_start_step
        self._profile_steps = profile_steps
        self._profiling = False
        self._profile_first_step = 0
        self._profile_started = 0.0
        # When run() began, until the first batch is in hand (the set-up
        # phase `setup.first_task`); None before and after.
        self._run_started = None
        self._callbacks = (
            model_spec.callbacks() if model_spec.callbacks else []
        ) + list(extra_callbacks)

    # ---------- public ----------

    def run(self):
        self._run_started = time.time()
        try:
            if self._profile_dir and self._job_type in (
                JobType.EVALUATION_ONLY,
                JobType.PREDICTION_ONLY,
            ):
                # The trace window opens on the training minibatch path
                # only; say so instead of silently writing nothing.
                logger.warning(
                    "--profile_dir is only honored for training jobs; "
                    "no trace will be captured for job type %s",
                    self._job_type,
                )
            if self._job_type in (
                JobType.TRAINING_ONLY,
                JobType.TRAINING_WITH_EVALUATION,
            ):
                if self._lease_mode:
                    # Leases cover TRAINING work only; the regular loop
                    # afterwards drains evaluation and train-end tasks.
                    self._train_leases()
                self._train_and_evaluate()
            elif self._job_type == JobType.EVALUATION_ONLY:
                self._evaluate_only()
            elif self._job_type == JobType.PREDICTION_ONLY:
                self._predict_only()
            else:
                raise ValueError(f"unknown job type {self._job_type}")
        finally:
            # A short job can end inside the profiled window; an unclosed
            # trace would be empty on disk.
            self._stop_profile_if_running()
            self._step_clock.close()

    # ---------- job loops ----------

    def _train_and_evaluate(self):
        while True:
            task = self._tds.get_task()
            if task is None:
                self._log_unread_loss()
                # Batched leases: results buffered past the last fetch
                # must land before the loop exits.
                self._tds.flush_reports()
                logger.info("Worker %d: no more tasks", self._worker_id)
                break
            if task.type == pb.TRAINING:
                self._run_task(task, self._process_train_batch)
                # In local/AllReduce modes the worker is the version source
                # (the PS plays that role in PS mode): reporting after each
                # training task drives version-triggered evaluation. A lost
                # report only delays the next eval trigger — never worth a
                # worker's life during a master blip.
                try:
                    with tracing.span("worker.report_version"):
                        self._mc.report_version(
                            self._trainer.get_model_version()
                        )
                except grpc.RpcError:
                    logger.warning(
                        "report_version failed (master unreachable?); "
                        "continuing",
                    )
                # Interleave pending evaluation tasks between training tasks
                # (reference worker.py:343-349).
                if self._job_type == JobType.TRAINING_WITH_EVALUATION:
                    self._drain_eval_tasks()
            elif task.type == pb.EVALUATION:
                self._run_task(task, self._process_eval_batch)
            elif task.type == pb.TRAIN_END_CALLBACK:
                self._run_train_end_callbacks(task)
            else:
                logger.warning("Skipping unexpected task %s", task)
                self._tds.report_task(task.task_id)

    def _train_leases(self):
        """Step-synchronized lease loop (multi-host AllReduce): every rank
        of the current membership epoch runs exactly lease.n_steps SPMD
        minibatches, then the lease's tasks complete; a comm failure or a
        membership change abandons the lease (the master requeues it). The
        loop returns when training work is exhausted — evaluation and
        train-end tasks drain through the regular task loop after."""
        import jax

        while True:
            lease = self._mc.lease_steps(self._minibatch_size)
            if lease.status == pb.LeaseStepsResponse.FINISHED:
                logger.info(
                    "Worker %d: training leases exhausted", self._worker_id
                )
                return
            if lease.status == pb.LeaseStepsResponse.WAIT:
                # Not in the group yet, peers still finishing the active
                # lease, or no mintable work: announce ourselves, drain any
                # pending evaluation work, and poll again.
                self._mc.report_liveness()
                if self._job_type == JobType.TRAINING_WITH_EVALUATION:
                    self._drain_eval_tasks()
                time.sleep(0.5)
                continue
            try:
                records = self._read_lease_records(lease.ranges)
            except Exception as e:
                logger.error("Lease %d data read failed: %s", lease.lease_id, e)
                self._mc.report_lease(
                    lease.lease_id, lease.rank, False, str(e)
                )
                continue
            if not records:
                self._mc.report_lease(
                    lease.lease_id, lease.rank, False, "empty lease ranges"
                )
                continue
            B = self._minibatch_size
            first = self._spec.feed(
                records[:B], Modes.TRAINING, self._metadata
            )
            if self._run_started is not None:
                self._first_batch_in_hand()
            self._trainer.init_variables_if_needed(first[0])
            self._trainer.init_world_if_needed()
            if (
                self._trainer.group_id != lease.epoch
                or self._trainer.rank != lease.rank
                or self._trainer.world_size != lease.world_size
            ):
                # The world moved between minting and joining; the master
                # aborts this lease on its next epoch observation.
                logger.info(
                    "Worker %d: lease %d is for epoch %d but this worker "
                    "is at epoch %d (rank %d/%d); refetching",
                    self._worker_id,
                    lease.lease_id,
                    lease.epoch,
                    self._trainer.group_id,
                    self._trainer.rank,
                    self._trainer.world_size,
                )
                continue
            tracing.set_context(lease_epoch=lease.epoch)
            try:
                loss = None
                for i in range(lease.n_steps):
                    with tracing.span(
                        "worker.step", step_num=self._steps + 1
                    ):
                        loss = self._lease_step(records, i, lease.lease_id)
                # Async dispatch: a peer failure surfaces at
                # materialization. Block before reporting so "success"
                # means the steps actually ran.
                if loss is not None:
                    jax.block_until_ready(loss)
            except Exception as e:
                logger.warning(
                    "Lease %d failed mid-steps; re-checking world",
                    lease.lease_id,
                    exc_info=True,
                )
                old_epoch = self._trainer.group_id
                try:
                    self._trainer.init_world_if_needed(force=True)
                except Exception:
                    logger.warning(
                        "World re-init failed; will retry on next lease",
                        exc_info=True,
                    )
                if self._trainer.group_id == old_epoch:
                    # Same membership epoch: this was a deterministic
                    # failure (bad feed, NaN'd compile, ...), not an
                    # elastic event — report it so the master's retry
                    # ladder can bound it instead of silently re-minting
                    # the same doomed lease forever.
                    self._mc.report_lease(
                        lease.lease_id, lease.rank, False, str(e)
                    )
                    time.sleep(0.5)
                continue
            self._mc.report_lease(lease.lease_id, lease.rank, True)
            self._mc.report_version(self._trainer.get_model_version())

    def _lease_step(self, records, i, lease_id):
        """Step `i` of a lease over this rank's `records`."""
        B = self._minibatch_size
        dp = datapath.get()
        # Cycle this rank's records to fill every batch: all ranks must
        # dispatch identically-shaped steps.
        with dp.stage("collate"):
            rows = [records[(i * B + j) % len(records)] for j in range(B)]
        with dp.stage("decode"):
            features, labels = self._spec.feed(
                rows, Modes.TRAINING, self._metadata
            )
        loss = self._trainer.train_lease_minibatch(features, labels)
        self._steps += 1
        _STEPS.inc()
        self._step_clock.dispatched(self._steps, loss)
        if self._steps % self._log_loss_steps == 0:
            logger.info(
                "Step %d (lease %d) loss %.6f",
                self._steps,
                lease_id,
                self._fenced(
                    self._steps,
                    loss,
                    getattr(self._trainer, "last_step_stats", None),
                ),
            )
        return loss

    def _read_lease_records(self, ranges):
        records = []
        for r in ranges:
            records.extend(self._tds.read_range(r))
        return records

    def _evaluate_only(self):
        while True:
            task = self._tds.get_task(pb.EVALUATION)
            if task is None:
                break
            self._run_task(task, self._process_eval_batch)

    def _predict_only(self):
        processor = self._spec.prediction_outputs_processor
        while True:
            task = self._tds.get_task(pb.PREDICTION)
            if task is None:
                break
            self._run_task(
                task,
                lambda records, task=task: self._process_predict_batch(
                    records, processor
                ),
            )
        # Optional end-of-stream hook: buffering processors (e.g. the
        # ODPS writer's) flush their tail here.
        close = getattr(processor, "close", None)
        if close is not None:
            close()

    def _drain_eval_tasks(self):
        while True:
            task = self._tds.try_get_eval_task()
            if task is None:
                return
            self._run_task(task, self._process_eval_batch)

    # ---------- task/batch processing ----------

    def _run_task(self, task, process_batch):
        # Re-key this thread's trace context to the task: every span and
        # RPC from here to report_task_result (PS pulls/pushes included)
        # carries the task id and one fresh trace id, which is what lets
        # trace_report.py stitch the task's cross-process chain together.
        tracing.set_context(task_id=task.task_id)
        try:
            if task.type != pb.TRAINING:
                # No training step will follow the last one soon.
                self._log_unread_loss()
            with tracing.span(
                "task_process",
                task_type=pb.TaskType.Name(task.type),
            ):
                for records in self._tds.read_batches(
                    task, self._minibatch_size
                ):
                    with tracing.span("batch_process") as batch:
                        self._process_with_retries(process_batch, records)
                    # The aggregator's straggler score reads this phase.
                    self._timing.add("batch_process", batch.dur)
            with tracing.span("worker.report_task"):
                self._tds.report_task(task.task_id)
            _TASKS.labels(result="success").inc()
        except Exception as e:
            logger.error(
                "Task %d failed: %s\n%s",
                task.task_id,
                e,
                traceback.format_exc(),
            )
            self._tds.report_task(task.task_id, err_message=str(e))
            _TASKS.labels(result="failure").inc()
        finally:
            # Per-task phase breakdown at DEBUG (reference worker.py:380-382
            # reports get_model/report_gradient/batch_process the same way);
            # in the finally so a failed task's time can't leak into the
            # next task's report.
            self._timing.report(logger, reset=True)
            trainer_timing = getattr(self._trainer, "timing", None)
            if trainer_timing is not None:
                trainer_timing.report(logger, reset=True)
            # One `datapath` event per task: the per-stage seconds this
            # task spent in the feed path, keyed by task id.
            datapath.get().flush_event(task_id=task.task_id)

    def _process_with_retries(self, process_batch, records):
        """Per-minibatch retry (reference worker.py:165-218): transient
        failures (PS restart, comm regroup) retry up to the cap; then the
        whole task is failed back to the master for re-dispatch."""
        for attempt in range(self._max_minibatch_retries):
            try:
                process_batch(records)
                return
            except Exception:
                if attempt == self._max_minibatch_retries - 1:
                    raise
                logger.warning(
                    "Minibatch failed (attempt %d):\n%s",
                    attempt + 1,
                    traceback.format_exc(),
                )

    def _process_train_batch(self, records):
        if self._profile_dir:
            # Before the step's span opens and before its dispatch, so
            # the trace window covers exactly the steps the log names.
            self._maybe_profile(self._steps + 1)
        # One iteration, decode to the end of any fence: in a profiler
        # session this is the step marker of the host plane.
        with tracing.span("worker.step", step_num=self._steps + 1):
            with datapath.get().stage("decode"):
                features, labels = self._spec.feed(
                    records, Modes.TRAINING, self._metadata
                )
            if self._run_started is not None:
                self._first_batch_in_hand()
            accepted, version, loss = self._trainer.train_minibatch(
                features, labels
            )
            # With this step queued behind it: the device goes from the
            # logged step to this one while the host wakes up and logs.
            self._log_unread_loss()
            if not accepted:
                return
            self._steps += 1
            _STEPS.inc()
            self._step_clock.dispatched(self._steps, loss)
            if self._steps % self._log_loss_steps == 0:
                # Only materialize the (lazy, on-device) loss when logging;
                # every other step stays dispatch-ahead.
                self._unread_loss = (
                    self._steps,
                    version,
                    loss,
                    getattr(self._trainer, "last_step_stats", None),
                )

    def _first_batch_in_hand(self):
        """Close the set-up phase that began with run(): the first
        `get_task`, the reader's open, the first decode."""
        started, self._run_started = self._run_started, None
        tracing.record_span(
            "setup.first_task", started, time.time() - started,
            cat=tracing.SETUP,
        )
        # A profile taken from outside this loop (`/debug/profile`) asks
        # here for the step it saw. Said once, down here and not in the
        # constructor: the lines above a step's call are in the compile
        # cache's key (`profiling.open_compile`); imported here likewise.
        from elasticdl_tpu.observability import step_scopes

        step_scopes.note_running_step(self._running_step)

    def _running_step(self):
        """(the trainer's training step, its arguments' shapes, the
        context it is called in) or None: what
        `observability/step_scopes.py` makes its map of."""
        ask = getattr(self._trainer, "step_for_scopes", None)
        return ask() if ask is not None else None

    def _log_unread_loss(self):
        """Read and log the loss of the last logging step. Called once
        the step after it is dispatched (and where none will be: before
        an evaluation task, at the end of the job), so that the task
        report, the next task's fetch and the wake-up from the wait all
        happen while the device works: read at its own step, a loss cost
        the device an idle gap at every fence, and a busy host made that
        gap several times longer (PERF.md section 6, PR 27)."""
        if self._unread_loss is None:
            return
        (step, version, loss, stats), self._unread_loss = (
            self._unread_loss, None)
        logger.info(
            "Step %d (version %d) loss %.6f",
            step,
            version,
            self._fenced(step, loss, stats),
        )

    def _fenced(self, step, loss, stats):
        """The loss of `step` as a float: waits for the device to finish
        every step up to it, with the dispatch loop stalled meanwhile.
        What the model reported of that step is ready then too (one
        program wrote both), and is published here and nowhere else."""
        with tracing.span("worker.loss_fence"):
            value = float(loss)
        if stats is not None:
            self._publish_model_stats(step, stats)
        return value

    def _publish_model_stats(self, step, stats):
        import jax

        stats = {k: float(v) for k, v in jax.device_get(stats).items()}
        emit_event("model_stats", step=step, **stats)
        for name, value in stats.items():
            _MODEL_STATS_TOTAL.labels(name=name).inc(value)
            _MODEL_STATS.labels(name=name).set(value)

    def _maybe_profile(self, next_step):
        """Open/close the trace window around `next_step` (the step about
        to be dispatched). Window = [start, start + steps); >= comparisons
        so a start below the current counter (e.g. --profile_start_step 0)
        still captures a window instead of silently never matching."""
        end = self._profile_start_step + self._profile_steps
        if (
            not self._profiling
            and self._profile_start_step <= next_step < end
        ):
            import jax

            self._profiling = True
            self._profile_first_step = next_step
            self._profile_started = time.time()
            with self._step_clock.profile_call():
                jax.profiler.start_trace(self._profile_dir)
            # From here tracing.span() also writes into this trace.
            tracing.set_profiler_session(True)
            logger.info(
                "Profiling steps %d-%d to %s",
                next_step,
                end - 1,
                self._profile_dir,
            )
        elif self._profiling and next_step >= end:
            self._stop_profile_if_running(last=False)

    # The thread that writes the profiled step's scopes, in a profiled run
    # and from the window's end until its file is written; else None.
    _scopes_writer = None

    def _stop_profile_if_running(self, last=True):
        """Close the trace window if it is open. `last`: the run ends
        here, so the thread that writes the step's scopes is waited for."""
        if self._profiling:
            self._close_profile()
        if last and self._scopes_writer is not None:
            # Seconds of work; a map that hangs must not hold the job.
            self._scopes_writer.join(timeout=120)
            self._scopes_writer = None

    def _start_step_scopes(self):
        """Which scope every instruction of the step belongs to, beside
        the trace: written on a thread of its own while this one
        dispatches on. A trainer that cannot say is a warning, never the
        profile's failure."""
        from elasticdl_tpu.observability import step_scopes

        try:
            step = self._running_step()
            if step is not None:
                self._scopes_writer = step_scopes.start_writing(
                    self._profile_dir, *step)
        except Exception:
            logger.warning("No step scopes for this profile", exc_info=True)

    def _close_profile(self):
        import jax

        self._profiling = False
        tracing.set_profiler_session(False)
        stopped = time.time()
        try:
            with self._step_clock.profile_call():
                jax.profiler.stop_trace()
                self._start_step_scopes()
            logger.info(
                "Profile written to %s (view: tensorboard --logdir %s)",
                self._profile_dir,
                self._profile_dir,
            )
            # Where the trace is and what it covers, for a reader that
            # has the event log and not this process's log.
            emit_event(
                "profile_written",
                dir=self._profile_dir,
                first_step=self._profile_first_step,
                last_step=self._steps,
                t_start=self._profile_started,
                t_stop=stopped,
            )
        except Exception:
            logger.warning("Failed to finalize profile", exc_info=True)

    def _process_eval_batch(self, records):
        with datapath.get().stage("decode"):
            features, labels = self._spec.feed(
                records, Modes.EVALUATION, self._metadata
            )
        outputs = self._trainer.evaluate_minibatch(features)
        self._mc.report_evaluation_metrics(outputs, labels)

    def _process_predict_batch(self, records, processor):
        with datapath.get().stage("decode"):
            features, _ = self._spec.feed(
                records, Modes.PREDICTION, self._metadata
            )
        outputs = self._trainer.predict_minibatch(features)
        if processor is not None:
            processor.process(outputs, self._worker_id)

    def _run_train_end_callbacks(self, task):
        try:
            for cb in self._callbacks:
                on_train_end = getattr(cb, "on_train_end", None)
                if on_train_end:
                    on_train_end(self._trainer)
            self._tds.report_task(task.task_id)
        except Exception as e:
            self._tds.report_task(task.task_id, err_message=str(e))

    @property
    def steps(self):
        return self._steps

    @property
    def trainer(self):
        return self._trainer
