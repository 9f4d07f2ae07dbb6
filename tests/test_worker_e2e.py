"""End-to-end slice: real master over gRPC + real Worker with a jitted JAX
trainer, training to convergence and interleaving evaluation (the reference's
distributed_train_and_evaluate pattern,
/root/reference/elasticdl/python/tests/test_utils.py:286-433)."""

import numpy as np

import test_module
from elasticdl_tpu.common.constants import JobType
from elasticdl_tpu.common.model_utils import get_model_spec
from elasticdl_tpu.data.reader import InMemoryReader
from elasticdl_tpu.worker.master_client import MasterClient
from elasticdl_tpu.worker.prediction_outputs_processor import (
    BasePredictionOutputsProcessor,
)
from elasticdl_tpu.worker.trainer import LocalTrainer
from elasticdl_tpu.worker.worker import Worker

from test_utils import start_master


def make_worker(master_addr, reader, job_type, worker_id=0, minibatch=16):
    spec = get_model_spec("test_module")
    trainer = LocalTrainer(
        spec.build_model(), spec.loss, spec.build_optimizer_spec()
    )
    mc = MasterClient(master_addr, worker_id)
    return Worker(
        worker_id,
        mc,
        reader,
        spec,
        trainer,
        minibatch_size=minibatch,
        job_type=job_type,
        log_loss_steps=10,
    )


def test_local_training_converges():
    records = test_module.make_linear_records(256)
    reader = InMemoryReader(records)
    with start_master(
        training_shards=reader.create_shards(),
        records_per_task=64,
        num_epochs=8,
    ) as m:
        worker = make_worker(m["addr"], reader, JobType.TRAINING_ONLY)
        worker.run()
        assert m["task_d"].finished() and not m["task_d"].job_failed
        assert worker.steps == (256 // 16) * 8
        # The learned weights recover TRUE_W / TRUE_B.
        variables = worker.trainer.export_variables()["variables"]
        dense = variables["params"]["Dense_0"]
        np.testing.assert_allclose(
            np.asarray(dense["kernel"]).reshape(-1),
            test_module.TRUE_W,
            atol=0.05,
        )
        np.testing.assert_allclose(
            float(np.asarray(dense["bias"])[0]), test_module.TRUE_B, atol=0.05
        )


def test_training_with_interleaved_evaluation():
    records = test_module.make_linear_records(128)
    eval_records = test_module.make_linear_records(64, seed=1)
    reader = InMemoryReader(records)

    class CombinedReader(InMemoryReader):
        """Routes eval-shard reads to the eval records."""

        def read_records(self, task):
            if task.shard_name == "eval":
                yield from eval_records[task.start : task.end]
            else:
                yield from records[task.start : task.end]

    combined = CombinedReader(records)
    with start_master(
        training_shards={"memory": (0, 128)},
        evaluation_shards={"eval": (0, 64)},
        records_per_task=32,
        num_epochs=2,
        eval_metrics_factory=lambda: test_module.eval_metrics_fn(),
        eval_steps=4,
    ) as m:
        worker = make_worker(
            m["addr"], combined, JobType.TRAINING_WITH_EVALUATION
        )
        worker.run()
        assert m["task_d"].finished() and not m["task_d"].job_failed
        results = m["evaluation_service"].completed_results
        assert results, "version-triggered evaluation never completed"
        last_version, metrics = results[-1]
        assert "mse" in metrics
        # Trained model should evaluate well on held-out data.
        assert metrics["mse"] < 1.0


def test_prediction_job_routes_outputs_to_processor():
    records = test_module.make_linear_records(40)
    reader = InMemoryReader(records)
    collected = []

    class Collector(BasePredictionOutputsProcessor):
        def process(self, predictions, worker_id):
            collected.append(np.asarray(predictions))

    with start_master(
        prediction_shards={"memory": (0, 40)}, records_per_task=20
    ) as m:
        spec = get_model_spec("test_module")
        spec.prediction_outputs_processor = Collector()
        trainer = LocalTrainer(
            spec.build_model(), spec.loss, spec.build_optimizer_spec()
        )
        worker = Worker(
            0,
            MasterClient(m["addr"], 0),
            reader,
            spec,
            trainer,
            minibatch_size=16,
            job_type=JobType.PREDICTION_ONLY,
        )
        worker.run()
        assert m["task_d"].finished()
        assert sum(len(c) for c in collected) == 40


def test_minibatch_retry_then_task_failure_requeue():
    """A flaky trainer: fails its first 2 minibatch calls, then works.
    The worker retries within the same task and the job still completes."""
    records = test_module.make_linear_records(32)
    reader = InMemoryReader(records)
    with start_master(
        training_shards=reader.create_shards(), records_per_task=32
    ) as m:
        worker = make_worker(m["addr"], reader, JobType.TRAINING_ONLY)
        real_train = worker.trainer.train_minibatch
        calls = {"n": 0}

        def flaky(features, labels):
            calls["n"] += 1
            if calls["n"] <= 2:
                raise RuntimeError("transient comm failure")
            return real_train(features, labels)

        worker.trainer.train_minibatch = flaky
        worker.run()
        assert m["task_d"].finished() and not m["task_d"].job_failed
        assert calls["n"] == 4  # 2 failures + 2 successful batches


def test_profile_dir_captures_trace(tmp_path):
    """--profile_dir: the worker writes one TensorBoard trace-viewer
    profile of steady-state steps and closes it even when the job ends
    inside the window."""
    import os

    records = test_module.make_linear_records(64)
    reader = InMemoryReader(records)
    profile_dir = str(tmp_path / "prof")
    with start_master(
        training_shards=reader.create_shards(),
        records_per_task=32,
        num_epochs=2,
    ) as m:
        spec = get_model_spec("test_module")
        trainer = LocalTrainer(
            spec.build_model(), spec.loss, spec.build_optimizer_spec()
        )
        worker = Worker(
            0,
            MasterClient(m["addr"], 0),
            reader,
            spec,
            trainer,
            minibatch_size=16,
            job_type=JobType.TRAINING_ONLY,
            profile_dir=profile_dir,
            profile_start_step=2,
            profile_steps=2,
        )
        worker.run()
    found = []
    for root, _, files in os.walk(profile_dir):
        found += [f for f in files if f.endswith((".xplane.pb", ".json.gz",
                                                  ".trace.json.gz"))]
    assert found, f"no trace artifacts under {profile_dir}"


def test_profile_start_step_zero_still_captures(tmp_path):
    """--profile_start_step 0 (capture from the very first step) must not
    silently skip the window."""
    import os

    records = test_module.make_linear_records(48)
    reader = InMemoryReader(records)
    profile_dir = str(tmp_path / "prof0")
    with start_master(
        training_shards=reader.create_shards(),
        records_per_task=48,
        num_epochs=1,
    ) as m:
        spec = get_model_spec("test_module")
        trainer = LocalTrainer(
            spec.build_model(), spec.loss, spec.build_optimizer_spec()
        )
        Worker(
            0,
            MasterClient(m["addr"], 0),
            reader,
            spec,
            trainer,
            minibatch_size=16,
            job_type=JobType.TRAINING_ONLY,
            profile_dir=profile_dir,
            profile_start_step=0,
            profile_steps=2,
        ).run()
    found = []
    for root, _, files in os.walk(profile_dir):
        found += [f for f in files if f.endswith(".xplane.pb")]
    assert found, f"no trace artifacts under {profile_dir}"


class _OrderedLoss:
    """A loss that notes when it is read."""

    def __init__(self, step, seen):
        self.step, self.seen = step, seen

    def __float__(self):
        self.seen.append(("read", self.step))
        return 0.5

    def block_until_ready(self):
        return self


def test_a_logged_loss_is_read_once_the_next_step_is_dispatched():
    """The device never drains for a log line: the loss of a logging step
    is read after the step behind it is queued, the last one at the end
    of the job, each once and under its own step number."""
    import logging

    records = test_module.make_linear_records(96)
    reader = InMemoryReader(records)
    seen = []
    logged = []
    handler = logging.Handler()
    handler.emit = lambda r: logged.append(r.getMessage())
    log = logging.getLogger("elasticdl_tpu.worker.worker")
    log.addHandler(handler)
    try:
        _run_ordered_job(reader, seen)
    finally:
        log.removeHandler(handler)
    assert seen == [
        ("dispatch", 1), ("dispatch", 2), ("dispatch", 3), ("read", 2),
        ("dispatch", 4), ("dispatch", 5), ("read", 4), ("dispatch", 6),
        ("read", 6),
    ]
    assert [line for line in logged if line.startswith("Step ")] == [
        f"Step {s} (version {s}) loss 0.500000" for s in (2, 4, 6)]


def _run_ordered_job(reader, seen):
    with start_master(
        training_shards=reader.create_shards(), records_per_task=32,
    ) as m:
        worker = make_worker(m["addr"], reader, JobType.TRAINING_ONLY)
        worker._log_loss_steps = 2
        real = worker.trainer.train_minibatch

        def train_minibatch(features, labels):
            accepted, version, _ = real(features, labels)
            seen.append(("dispatch", version))
            return accepted, version, _OrderedLoss(version, seen)

        worker.trainer.train_minibatch = train_minibatch
        worker.run()
        assert worker.steps == 6
