"""End-to-end CLI jobs whose workers form ONE multi-process SPMD world
(`--multi_host`, step-synchronized leases) — split from
test_cli_local_cluster.py so `--dist loadfile` can balance them. Every
job here runs through test_utils.run_edl: its own process group, reaped
at its own time limit."""

import os

import numpy as np
import pytest

import test_module
from elasticdl_tpu.data.recordfile import RecordFileWriter
from test_utils import coordinator_block, run_edl

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


@pytest.fixture(scope="module")
def linear_data(tmp_path_factory):
    d = tmp_path_factory.mktemp("data")
    path = str(d / "linear.edlr")
    with RecordFileWriter(path) as w:
        for r in test_module.make_linear_records(128):
            w.write(r)
    return path


def test_multihost_lease_mode_with_evaluation(tmp_path, linear_data):
    """Lease-mode training interleaved with version-triggered evaluation
    (TRAINING_WITH_EVALUATION under --multi_host): leases drain the
    training work, eval tasks drain through the WAIT branch and the
    post-lease task loop, and the job completes with an export."""
    output = str(tmp_path / "model.npz")
    res = run_edl(
        "train",
        "--model_zoo", f"{REPO}/tests",
        "--model_def", "test_module",
        "--training_data", linear_data,
        "--validation_data", linear_data,
        "--evaluation_steps", "6",
        "--num_epochs", "10",
        "--records_per_task", "32",
        "--minibatch_size", "32",
        "--num_workers", "1",
        "--distribution_strategy", "AllreduceStrategy",
        "--multi_host",
        "--instance_backend", "local_process",
        "--master_port", "0",
        "--coordinator_port", str(coordinator_block()),
        "--output", output,
    )
    assert res.returncode == 0, res.stderr[-3000:]
    assert "Minted lease" in res.stderr
    assert "evaluation" in res.stderr.lower()
    with np.load(output) as data:
        kernel = data["params/Dense_0/kernel"].reshape(-1)
    np.testing.assert_allclose(kernel, test_module.TRUE_W, atol=0.1)


def test_multihost_two_workers_with_evaluation(tmp_path, linear_data):
    """TWO worker processes in one SPMD world with validation data: the
    multi-host evaluate_minibatch path (host-copy + process-local
    forward — a global-mesh forward would need every process) runs on
    whichever worker draws the eval tasks, while training stays
    lease-synchronized. Completes with a converged export."""
    output = str(tmp_path / "model.npz")
    res = run_edl(
        "train",
        "--model_zoo", f"{REPO}/tests",
        "--model_def", "test_module",
        "--training_data", linear_data,
        "--validation_data", linear_data,
        "--evaluation_steps", "8",
        "--num_epochs", "16",
        "--records_per_task", "32",
        "--minibatch_size", "16",
        "--num_workers", "2",
        "--distribution_strategy", "AllreduceStrategy",
        "--multi_host",
        "--instance_backend", "local_process",
        "--master_port", "0",
        "--coordinator_port", str(coordinator_block()),
        "--output", output,
        timeout=420,
    )
    assert res.returncode == 0, res.stderr[-3000:]
    assert "Minted lease" in res.stderr
    assert "world 2" in res.stderr  # both processes in one lease world
    with np.load(output) as data:
        kernel = data["params/Dense_0/kernel"].reshape(-1)
    np.testing.assert_allclose(kernel, test_module.TRUE_W, atol=0.1)


# slow: two processes x four virtual devices x a pipelined step is the
# world that wedges gloo on a loaded box (6.5 min pinned to two cores,
# ending in "All workers failed" after three 60 s collective timeouts;
# 31 s idle). The single-process 1F1B job (test_cli_lm_parallel.py) and
# the two-worker DP jobs here stay in tier-1.
@pytest.mark.slow
def test_multihost_two_workers_pipeline_1f1b(tmp_path, monkeypatch):
    """TWO worker processes form one SPMD world and train the flagship LM
    through the 1F1B pipeline schedule: {data: 2 procs, stage: 2 intra-
    process} — the full multi-host composition invariant for the stage
    axis, through the real CLI and step-synchronized leases."""
    # The two ranks (and any relaunch) lower the identical SPMD program:
    # they share the job's one persistent compile cache
    # (common/compile_cache.py), so under full-suite load the compile
    # floor — and with it the auto-derived join gate — shrinks to
    # trace+lower after the first rank's misses. The registered gate
    # knob stays pinned at 240 s for the cold-cache worst case.
    monkeypatch.setenv("ELASTICDL_JOIN_GATE_SECONDS", "240")

    from test_utils import write_lm_records

    data = str(tmp_path / "lm.edlr")
    write_lm_records(data, n=96, seed=3)
    res = run_edl(
        "train",
        "--model_def",
        "elasticdl_tpu.models.transformer.transformer_lm",
        "--training_data", data,
        "--num_epochs", "2",
        "--records_per_task", "32",
        "--minibatch_size", "16",
        "--num_workers", "2",
        "--distribution_strategy", "AllreduceStrategy",
        "--multi_host",
        "--coordinator_port", str(coordinator_block()),
        "--pipeline_stages", "2",
        "--pipeline_schedule", "1f1b",
        "--pipeline_microbatches", "2",
        "--instance_backend", "local_process",
        "--master_port", "0",
        timeout=420,
    )
    assert res.returncode == 0, res.stderr[-3000:]
    assert "Minted lease" in res.stderr
    # The composed mesh really formed (stage axis intra-process; the
    # data-axis size depends on the inherited per-process device count,
    # so assert the invariant, not the number) in a genuine 2-process
    # world.
    assert "'stage': 2" in res.stderr, res.stderr[-2000:]
    assert "world 2" in res.stderr
    assert "Initialized pipelined model" in res.stderr
