"""`python -m elasticdl_tpu.worker.main` — worker process entrypoint
(reference /root/reference/elasticdl/python/worker/main.py:28-82)."""

import os
import sys
import time

# The set-up phase `setup.imports` begins here, before the package's
# imports; main() closes it once the observability plane is up.
_T_IMPORTS = time.time()

from elasticdl_tpu import observability  # noqa: E402
from elasticdl_tpu.common.args import (  # noqa: E402
    validate_args,
    worker_parser,
)
from elasticdl_tpu.common.constants import (  # noqa: E402
    DistributionStrategy,
    JobType,
)
from elasticdl_tpu.common.log_utils import get_logger  # noqa: E402
from elasticdl_tpu.common.model_utils import get_model_spec  # noqa: E402
from elasticdl_tpu.data.reader import create_data_reader  # noqa: E402
from elasticdl_tpu.observability import memory, tracing  # noqa: E402
from elasticdl_tpu.worker.master_client import MasterClient  # noqa: E402
from elasticdl_tpu.worker.worker import Worker  # noqa: E402

logger = get_logger("worker.main")

_JOB_TYPES = {
    "training_only": JobType.TRAINING_ONLY,
    "training_with_evaluation": JobType.TRAINING_WITH_EVALUATION,
    "evaluation_only": JobType.EVALUATION_ONLY,
    "prediction_only": JobType.PREDICTION_ONLY,
}


def build_trainer(args, spec, master_client):
    model = spec.build_model()
    optimizer_spec = spec.build_optimizer_spec()
    strategy = args.distribution_strategy
    if strategy == DistributionStrategy.PARAMETER_SERVER:
        from elasticdl_tpu.worker.ps_client import PSClient
        from elasticdl_tpu.worker.ps_trainer import ParameterServerTrainer

        if not args.ps_addrs:
            raise ValueError("ParameterServerStrategy requires --ps_addrs")
        return ParameterServerTrainer(
            model,
            spec.loss,
            optimizer_spec,
            PSClient(
                args.ps_addrs.split(","),
                worker_id=args.worker_id,
                wire_dtype=args.ps_wire_dtype,
            ),
            embedding_inputs=getattr(spec.module, "embedding_inputs", None),
            embedding_threshold_bytes=getattr(
                spec.module, "embedding_threshold_bytes", None
            ),
            embedding_device_capacity_bytes=getattr(
                spec.module, "embedding_device_capacity_bytes", 0
            ),
            seed=args.seed,
            model_steps=args.get_model_steps,
        )
    if strategy == DistributionStrategy.ALLREDUCE:
        from elasticdl_tpu.worker.allreduce_trainer import AllReduceTrainer

        return AllReduceTrainer(
            model,
            spec.loss,
            optimizer_spec,
            master_client,
            multi_host=args.multi_host,
            seed=args.seed,
            model_parallel_size=args.model_parallel_size,
            param_specs_fn=getattr(spec.module, "param_specs", None),
            zero1=args.zero1,
            quantized_grads=args.quantized_grads,
            pipeline_stages=args.pipeline_stages,
            pipeline_schedule=args.pipeline_schedule,
            pipeline_microbatches=args.pipeline_microbatches,
            pipeline_virtual_stages=args.pipeline_virtual_stages,
            pipeline_spec_fn=getattr(spec.module, "pipeline_spec", None),
            context_parallel_size=args.context_parallel_size,
            context_parallel_impl=args.context_parallel_impl,
            context_parallel_model_fn=getattr(
                spec.module, "context_parallel_model", None
            ),
        )
    from elasticdl_tpu.worker.trainer import LocalTrainer

    return LocalTrainer(model, spec.loss, optimizer_spec, seed=args.seed)


def open_devices():
    """Initialise the jax backend — the ONE place a job touches the
    accelerator (the master and the PS never do; a chip belongs to one
    process). What this worker got is logged and lands as a
    `worker_devices` event, so a job's record says which platform its
    steps ran on. A backend that cannot be opened — typically another
    process holding the chip — ends the worker here, at once, with the
    cause named; the master's relaunch budget is the retry."""
    with tracing.span("setup.open_devices", cat=tracing.SETUP):
        import jax

        try:
            devices = jax.devices()
        except RuntimeError as e:
            raise RuntimeError(
                "worker could not open its accelerator (requested "
                "platforms JAX_PLATFORMS="
                f"{os.environ.get('JAX_PLATFORMS', '')!r}). If the error "
                "below names the libtpu lockfile or says the TPU is in "
                "use, another process on this host holds the chip: one "
                f"process per chip. Backend error: {e}"
            ) from e
    first = devices[0]
    logger.info(
        "Worker devices: %d x %s (platform %s)",
        len(devices), first.device_kind, first.platform,
    )
    observability.emit_event(
        "worker_devices",
        platform=first.platform,
        device_kind=first.device_kind,
        count=len(devices),
    )


def main(argv=None):
    args = worker_parser().parse_args(argv)
    validate_args(args)
    obs = observability.setup(
        role=f"worker-{args.worker_id}", job=args.job_name
    )
    tracing.record_span(
        "setup.imports", _T_IMPORTS, time.time() - _T_IMPORTS,
        cat=tracing.SETUP,
    )
    if not args.multi_host:
        # A multi-host world initialises jax.distributed first (the
        # trainer's regroup owns that order); every other worker opens
        # its devices up front so a busy chip fails before any work.
        open_devices()
    with tracing.span("setup.model_spec", cat=tracing.SETUP):
        if args.model_zoo:
            sys.path.insert(0, args.model_zoo)
        spec = get_model_spec(args.model_def)
    job_type = _JOB_TYPES[args.job_type]
    reader_factory = spec.create_data_reader or create_data_reader
    if job_type == JobType.PREDICTION_ONLY:
        origins = [args.prediction_data]
    else:
        origins = [
            o for o in (args.training_data, args.validation_data) if o
        ]
    if len(origins) == 1:
        reader = reader_factory(origins[0])
    else:
        # Training + validation are distinct origins: route each task to
        # the reader owning its shard (see CompositeReader).
        from elasticdl_tpu.data.reader import CompositeReader

        reader = CompositeReader([reader_factory(o) for o in origins])
    if args.prefetch_records > 0:
        from elasticdl_tpu.data.prefetch import PrefetchReader

        reader = PrefetchReader(reader, buffer_records=args.prefetch_records)
    mc = MasterClient(
        args.master_addr, args.worker_id, worker_host=args.worker_host
    )
    with tracing.span("setup.build_trainer", cat=tracing.SETUP):
        trainer = build_trainer(args, spec, mc)
    extra_callbacks = []
    if args.output:
        from elasticdl_tpu.common.save_utils import ExportModelCallback

        extra_callbacks.append(ExportModelCallback(args.output))
    if args.checkpoint_dir_for_init and args.distribution_strategy != (
        DistributionStrategy.PARAMETER_SERVER
    ):
        # Worker-side restore for local/AllReduce: the PS strategy restores
        # server-side instead (ps/checkpoint.py). Applied right after the
        # trainer's lazy init on the first batch.
        trainer.restore_on_init = args.checkpoint_dir_for_init
    profile_dir = ""
    if args.profile_dir:
        # Per-worker subdir: concurrent workers on one host must not
        # interleave trace events in a single profile directory.
        profile_dir = os.path.join(
            args.profile_dir, f"worker{args.worker_id}"
        )
    worker = Worker(
        args.worker_id,
        mc,
        reader,
        spec,
        trainer,
        minibatch_size=args.minibatch_size,
        job_type=job_type,
        log_loss_steps=args.log_loss_steps,
        extra_callbacks=extra_callbacks,
        profile_dir=profile_dir,
        profile_start_step=args.profile_start_step,
        profile_steps=args.profile_steps,
        # Multi-host AllReduce trains through step-synchronized leases:
        # every process of the SPMD world must run the same step count.
        lease_mode=(
            args.distribution_strategy == DistributionStrategy.ALLREDUCE
            and args.multi_host
        ),
    )
    # Push-based telemetry (opt-in via ELASTICDL_TELEMETRY_PUSH_INTERVAL):
    # while the reporter's pushes stay fresh the master's aggregator stops
    # pull-scraping this worker's /metrics endpoint.
    from elasticdl_tpu.observability.metrics import default_registry
    from elasticdl_tpu.observability.push import TelemetryReporter

    reporter = TelemetryReporter(
        mc.report_telemetry,
        default_registry(),
        role=f"worker-{args.worker_id}",
        seed=args.worker_id,
    ).start()
    try:
        worker.run()
        # What the devices held when the work was done, by the
        # runtime's own count (empty on the CPU).
        observability.emit_event(
            "worker_exit_memory",
            device_stats=memory.device_memory_stats(),
        )
    finally:
        # Leave any distributed world deterministically: interpreter-exit
        # shutdown from N processes at scattered times fails the shutdown
        # barrier and crashes the slowest peer.
        close = getattr(trainer, "close", None)
        if close is not None:
            close()
        reporter.close()
        obs.close()
    logger.info("Worker %d exiting", args.worker_id)
    return 0


if __name__ == "__main__":
    sys.exit(main())
