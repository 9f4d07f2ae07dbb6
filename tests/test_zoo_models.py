"""Contract + convergence smoke tests for every zoo model family
(reference model_zoo coverage, SURVEY.md §2.9): build the spec, feed
synthetic records, run train steps, assert the loss drops."""

import numpy as np
import pytest

from elasticdl_tpu.common.model_utils import Modes, get_model_spec
from elasticdl_tpu.worker.trainer import LocalTrainer


def _records_for(spec_name, n):
    if spec_name == "elasticdl_tpu.models.cifar10.cifar10_cnn":
        from elasticdl_tpu.data.gen.synthetic import (
            synthetic_classification_arrays,
        )
        from elasticdl_tpu.data.example import encode_example

        images, labels = synthetic_classification_arrays(
            n, image_shape=(32, 32, 3), noise=0.1, seed=5
        )
        return [
            encode_example({"image": images[i], "label": labels[i]})
            for i in range(n)
        ]
    module = get_model_spec(spec_name).module
    return module.make_records(n, seed=4)


CONVERGING_MODELS = [
    # (spec module, steps, required loss ratio)
    ("elasticdl_tpu.models.cifar10.cifar10_cnn", 8, 0.8),
    ("elasticdl_tpu.models.census.wide_deep", 30, 0.7),
    ("elasticdl_tpu.models.census.dnn", 60, 0.8),
    ("elasticdl_tpu.models.deepfm.deepfm_functional", 30, 0.7),
    ("elasticdl_tpu.models.heart.heart_model", 30, 0.8),
    ("elasticdl_tpu.models.census_fc.wide_deep_fc", 30, 0.8),
]


@pytest.mark.parametrize(
    "spec_name,steps,ratio", CONVERGING_MODELS, ids=lambda p: str(p)
)
def test_zoo_model_trains(spec_name, steps, ratio):
    spec = get_model_spec(spec_name)
    trainer = LocalTrainer(
        spec.build_model(), spec.loss, spec.build_optimizer_spec()
    )
    records = _records_for(spec_name, 64)
    features, labels = spec.feed(records, Modes.TRAINING, None)
    losses = []
    for _ in range(steps):
        _, _, loss = trainer.train_minibatch(features, labels)
        losses.append(loss)
    assert np.isfinite(losses).all()
    assert losses[-1] < losses[0] * ratio, (losses[0], losses[-1])
    # Metrics contract.
    outputs = trainer.evaluate_minibatch(features)
    for metric in spec.build_metrics().values():
        metric.update(outputs, labels)
        assert np.isfinite(metric.result())


@pytest.mark.slow  # one conv-net train-step compile: ~50 s of XLA:CPU cold
def test_resnet50_builds_and_steps():
    """ResNet50 is too heavy for a CPU convergence test; one step with
    finite loss + the expected parameter count validates the architecture.
    """
    spec = get_model_spec("elasticdl_tpu.models.resnet50.resnet50")
    trainer = LocalTrainer(
        spec.build_model(), spec.loss, spec.build_optimizer_spec()
    )
    rng = np.random.default_rng(0)
    features = rng.normal(size=(2, 64, 64, 3)).astype(np.float32)
    labels = rng.integers(0, 1000, 2).astype(np.int64)
    _, _, loss = trainer.train_minibatch(features, labels)
    assert np.isfinite(loss)
    import jax

    n_params = sum(
        int(np.prod(p.shape))
        for p in jax.tree_util.tree_leaves(
            trainer.export_variables()["variables"]["params"]
        )
    )
    # ResNet-50 has ~25.6M params at 1000 classes.
    assert 24e6 < n_params < 27e6, n_params


def test_iris_csv_pipeline(tmp_path):
    from elasticdl_tpu.data.reader import CSVDataReader
    from elasticdl_tpu.models.iris import iris_dnn

    path = iris_dnn.make_csv(str(tmp_path / "iris.csv"), n=90)
    reader = CSVDataReader(path)
    shards = reader.create_shards()
    assert shards[path] == (0, 90)
    spec = get_model_spec("elasticdl_tpu.models.iris.iris_dnn")
    trainer = LocalTrainer(
        spec.build_model(), spec.loss, spec.build_optimizer_spec()
    )

    class _T:
        shard_name, start, end = path, 0, 90

    records = list(reader.read_records(_T))
    features, labels = spec.feed(records, Modes.TRAINING, None)
    losses = [
        trainer.train_minibatch(features, labels)[2] for _ in range(60)
    ]
    assert losses[-1] < losses[0] * 0.3, (losses[0], losses[-1])


@pytest.mark.parametrize("use_async", [False, True], ids=["sync", "async"])
def test_deepfm_distributed_with_ps(use_async):
    """The PS-resident DeepFM trains against real parameter servers as its
    device-resident twin (`deepfm_functional`) does on the same records.

    Sync mode has an exactness contract: every step's loss lies within the
    band the twin's own initial weights span (0.011 between seeds at step
    25). Async mode pipelines one push and serves embedding rows from the
    worker's row cache for ELASTICDL_PREFETCH_CACHE_STALENESS versions, and
    the cache does not see the worker's own pushes: on one batch fed again
    and again (every id a hit) the loss falls in stairs, one every
    staleness + 2 steps. Bounded staleness then means: never further behind
    the twin than that many steps."""
    from elasticdl_tpu.common import knobs
    from elasticdl_tpu.ps.parameter_server import ParameterServer
    from elasticdl_tpu.worker.ps_client import PSClient
    from elasticdl_tpu.worker.ps_trainer import ParameterServerTrainer

    spec = get_model_spec(
        "elasticdl_tpu.models.deepfm.deepfm_distributed"
    )
    twin_spec = get_model_spec(
        "elasticdl_tpu.models.deepfm.deepfm_functional"
    )
    records = spec.module.make_records(128, seed=2)
    features, labels = spec.feed(records, Modes.TRAINING, None)
    twin = LocalTrainer(
        twin_spec.build_model(), twin_spec.loss,
        twin_spec.build_optimizer_spec(),
    )
    twin_losses = [
        float(twin.train_minibatch(features, labels)[2]) for _ in range(25)
    ]
    servers = [
        ParameterServer(
            i, 2, optimizer_spec=spec.build_optimizer_spec(),
            use_async=use_async,
        )
        for i in range(2)
    ]
    try:
        trainer = ParameterServerTrainer(
            spec.build_model(),
            spec.loss,
            spec.build_optimizer_spec(),
            PSClient([s.addr for s in servers], worker_id=0),
            embedding_inputs=spec.module.embedding_inputs,
            use_async=use_async,
        )
        losses = [
            float(trainer.train_minibatch(features, labels)[2])
            for _ in range(25)
        ]
        lag = (
            knobs.get_int("ELASTICDL_PREFETCH_CACHE_STALENESS") + 2
            if use_async else 0
        )
        band = 0.02
        for step, loss in enumerate(losses):
            assert loss < twin_losses[max(0, step - lag)] + band, (
                step, losses, twin_losses)
            assert loss > twin_losses[step] - band, (
                step, losses, twin_losses)
        # Both PS shards hold rows of both tables.
        for s in servers:
            assert set(s.parameters.embedding_tables) == {
                "fm_linear",
                "fm_factors",
            }
    finally:
        for s in servers:
            s.stop()


@pytest.mark.slow  # one conv-net train-step compile: ~60 s of XLA:CPU cold
def test_mobilenetv2_builds_and_steps():
    """MobileNetV2 (reference benchmark model, ftlib_benchmark.md:138-156):
    one finite step + the expected ~3.5M parameter count."""
    spec = get_model_spec("elasticdl_tpu.models.mobilenetv2.mobilenetv2")
    trainer = LocalTrainer(
        spec.build_model(), spec.loss, spec.build_optimizer_spec()
    )
    rng = np.random.default_rng(0)
    features = rng.normal(size=(2, 64, 64, 3)).astype(np.float32)
    labels = rng.integers(0, 1000, 2).astype(np.int64)
    _, _, loss = trainer.train_minibatch(features, labels)
    assert np.isfinite(loss)
    import jax

    n_params = sum(
        int(np.prod(p.shape))
        for p in jax.tree_util.tree_leaves(
            trainer.export_variables()["variables"]["params"]
        )
    )
    # MobileNetV2 1.0x has ~3.5M params at 1000 classes.
    assert 3.0e6 < n_params < 4.0e6, n_params
