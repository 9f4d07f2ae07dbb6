"""End-to-end CLI job: `edl train` with the local-process instance backend —
the in-repo analog of the reference's minikube client_test.sh jobs
(/root/reference/scripts/client_test.sh:24-141), swapping pods for local
subprocesses. Exercises: master orchestration, worker subprocess spawn,
record-file reading, train-end export task, evaluate-from-checkpoint."""

import os
import subprocess
import sys

import numpy as np
import pytest

import test_module
from elasticdl_tpu.data.recordfile import RecordFileWriter

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


@pytest.fixture(scope="module")
def linear_data(tmp_path_factory):
    d = tmp_path_factory.mktemp("data")
    path = str(d / "linear.edlr")
    with RecordFileWriter(path) as w:
        for r in test_module.make_linear_records(128):
            w.write(r)
    return path


from test_utils import run_edl  # noqa: E402  (shared CLI-launch recipe)


def test_train_then_evaluate_local_cluster(tmp_path, linear_data):
    output = str(tmp_path / "model.npz")
    res = run_edl(
        "train",
        "--model_zoo", f"{REPO}/tests",
        "--model_def", "test_module",
        "--training_data", linear_data,
        "--num_epochs", "12",
        "--records_per_task", "32",
        "--minibatch_size", "32",
        "--num_workers", "1",
        "--distribution_strategy", "Local",
        "--instance_backend", "local_process",
        "--master_port", "0",
        "--output", output,
    )
    assert res.returncode == 0, res.stderr[-3000:]
    assert os.path.exists(output)
    with np.load(output) as data:
        assert "params/Dense_0/kernel" in data.files
        kernel = data["params/Dense_0/kernel"].reshape(-1)
    np.testing.assert_allclose(kernel, test_module.TRUE_W, atol=0.1)

    res = run_edl(
        "evaluate",
        "--model_zoo", f"{REPO}/tests",
        "--model_def", "test_module",
        "--validation_data", linear_data,
        "--checkpoint_dir_for_init", output,
        "--num_workers", "1",
        "--distribution_strategy", "Local",
        "--instance_backend", "local_process",
        "--master_port", "0",
        "--records_per_task", "64",
    )
    assert res.returncode == 0, res.stderr[-3000:]
    assert "Restored model checkpoint" in res.stderr


def test_yaml_dump_mode(tmp_path, linear_data):
    yaml_path = str(tmp_path / "master.json")
    res = run_edl(
        "train",
        "--model_def", "test_module",
        "--training_data", linear_data,
        "--num_workers", "2",
        "--instance_backend", "k8s",
        "--image_name", "example/image:latest",
        "--yaml", yaml_path,
    )
    assert res.returncode == 0, res.stderr[-2000:]
    import json

    with open(yaml_path) as f:
        manifest = json.load(f)
    command = manifest["spec"]["containers"][0]["command"]
    assert "--yaml" not in command and yaml_path not in command
    assert manifest["spec"]["serviceAccountName"] == "elasticdl-master"


def test_metrics_dir_and_top_monitor(tmp_path, linear_data):
    """`edl train --metrics_dir` publishes metrics.jsonl + TB events, and
    `edl top` polls the live master's job-status RPC until completion."""
    import json
    import signal
    import socket
    import subprocess as sp
    import time

    sock = socket.socket()
    sock.bind(("127.0.0.1", 0))
    port = sock.getsockname()[1]
    sock.close()

    metrics_dir = str(tmp_path / "metrics")
    env = dict(os.environ)
    env["PYTHONPATH"] = f"{REPO}:{REPO}/tests"
    env["JAX_PLATFORMS"] = "cpu"
    # The job logs to files: nobody drains a pipe while `edl top` runs,
    # and a job whose log outgrows one blocks in write() for good.
    job_out = open(tmp_path / "train.out", "w+")
    job_err = open(tmp_path / "train.err", "w+")
    train = sp.Popen(
        [
            sys.executable, "-m", "elasticdl_tpu.client.main", "train",
            "--model_zoo", f"{REPO}/tests",
            "--model_def", "test_module",
            "--training_data", linear_data,
            "--num_epochs", "8",
            "--records_per_task", "32",
            "--minibatch_size", "32",
            "--num_workers", "1",
            "--distribution_strategy", "Local",
            "--instance_backend", "local_process",
            "--master_port", str(port),
            "--metrics_dir", metrics_dir,
        ],
        stdout=job_out,
        stderr=job_err,
        env=env,
        cwd=REPO,
        start_new_session=True,
    )
    try:
        # Wait for the master port, then monitor until the job ends.
        deadline = time.time() + 120
        while time.time() < deadline:
            try:
                probe = socket.create_connection(
                    ("127.0.0.1", port), timeout=1
                )
                probe.close()
                break
            except OSError:
                time.sleep(0.5)
        top = sp.run(
            [
                sys.executable, "-m", "elasticdl_tpu.client.main", "top",
                "--master_addr", f"127.0.0.1:{port}",
                "--interval", "0.5",
            ],
            capture_output=True,
            text=True,
            timeout=180,
            env=env,
            cwd=REPO,
        )
        assert top.returncode == 0, top.stderr[-2000:]
        # The master lingers briefly after completion, so a monitor at
        # sub-second polling must observe the terminal state.
        assert "epoch" in top.stdout and "FINISHED" in top.stdout
        train.wait(timeout=120)
        job_err.seek(0)
        assert train.returncode == 0, job_err.read()[-3000:]
    finally:
        # Pass or fail, the whole job (master and worker) ends here.
        try:
            os.killpg(train.pid, signal.SIGKILL)
        except ProcessLookupError:
            pass
        train.wait()
        job_out.close()
        job_err.close()
    lines = [
        json.loads(line)
        for line in open(os.path.join(metrics_dir, "metrics.jsonl"))
    ]
    assert any(line["group"] == "train" for line in lines)


def test_predict_from_checkpoint(tmp_path, linear_data):
    """`edl predict` loads an exported model and routes outputs through the
    module's prediction_outputs_processor (the reference's mnist predict
    CI job, client_test.sh)."""
    output = str(tmp_path / "model.npz")
    res = run_edl(
        "train",
        "--model_zoo", f"{REPO}/tests",
        "--model_def", "test_module",
        "--training_data", linear_data,
        "--num_epochs", "10",
        "--records_per_task", "64",
        "--minibatch_size", "32",
        "--num_workers", "1",
        "--distribution_strategy", "Local",
        "--instance_backend", "local_process",
        "--master_port", "0",
        "--output", output,
    )
    assert res.returncode == 0, res.stderr[-2000:]

    predictions_out = str(tmp_path / "predictions.txt")
    env = dict(os.environ)
    env["PYTHONPATH"] = f"{REPO}:{REPO}/tests"
    env["JAX_PLATFORMS"] = "cpu"
    env["EDL_TEST_PREDICTIONS_OUT"] = predictions_out
    res = subprocess.run(
        [
            sys.executable, "-m", "elasticdl_tpu.client.main", "predict",
            "--model_zoo", f"{REPO}/tests",
            "--model_def", "test_module",
            "--prediction_data", linear_data,
            "--checkpoint_dir_for_init", output,
            "--num_workers", "1",
            "--distribution_strategy", "Local",
            "--instance_backend", "local_process",
            "--master_port", "0",
            "--records_per_task", "64",
        ],
        capture_output=True,
        text=True,
        timeout=240,
        env=env,
        cwd=REPO,
    )
    assert res.returncode == 0, res.stderr[-3000:]
    predictions = [
        float(line) for line in open(predictions_out).read().splitlines()
    ]
    assert len(predictions) == 128  # every record predicted exactly once
    # The restored model predicts the linear target closely.
    import test_module as tm

    _, labels = tm.feed(tm.make_linear_records(128), "evaluation", None)
    mse = float(np.mean((np.sort(predictions) - np.sort(labels)) ** 2))
    assert mse < 0.05, mse


def test_ps_strategy_two_ps_auto_embedding_cli(tmp_path):
    """The reference's signature CI job shape (client_test.sh: deepfm with
    2 PS + 1 worker submitted through the CLI): `edl train` with
    ParameterServerStrategy, two PS processes, and a stock nn.Embed model
    the ModelHandler auto-swaps to the PS — job completes and exports."""
    import auto_embedding_test_module as aem

    data = str(tmp_path / "emb.edlr")
    with RecordFileWriter(data) as w:
        for r in aem.make_records(96):
            w.write(r)
    output = str(tmp_path / "model.npz")
    ckpt_dir = str(tmp_path / "ps_ckpt")
    res = run_edl(
        "train",
        "--model_zoo", f"{REPO}/tests",
        "--model_def", "auto_embedding_test_module",
        "--training_data", data,
        "--num_epochs", "3",
        "--records_per_task", "32",
        "--minibatch_size", "16",
        "--num_workers", "1",
        "--num_ps", "2",
        "--distribution_strategy", "ParameterServerStrategy",
        "--instance_backend", "local_process",
        "--master_port", "0",
        "--checkpoint_dir", ckpt_dir,
        "--checkpoint_steps", "4",
        "--output", output,
    )
    assert res.returncode == 0, res.stderr[-3000:]
    with np.load(output) as d:
        # The exported model carries the reverse-swapped embedding table.
        emb = [k for k in d.files if "item_emb" in k]
        assert emb, d.files
    # Discriminating check: the table must have lived ON the PS during
    # training (a silently failed auto-swap would still train locally and
    # still export an item_emb key). The PS-side checkpoints record it as
    # an EMBEDDING TABLE, which only exists when the swap happened.
    from elasticdl_tpu.ps import checkpoint as ckpt
    from elasticdl_tpu.ps.parameters import Parameters

    version = ckpt.latest_complete_version(ckpt_dir)
    assert version is not None, os.listdir(ckpt_dir)
    table_ids = 0
    for ps_id in range(2):
        params = Parameters()
        ckpt.restore_shard(ckpt_dir, version, params, ps_id, 2)
        if "item_emb" in params.embedding_tables:
            table_ids += len(params.embedding_tables["item_emb"])
    assert table_ids > 0, "item_emb never reached the PS embedding store"
