"""Shared helpers for in-process distributed tests: boot a REAL master gRPC
server on a free localhost port (the reference's signature test pattern,
/root/reference/elasticdl/python/tests/mock_service.py:34-43)."""

import contextlib

from elasticdl_tpu.common import rpc
from elasticdl_tpu.master.evaluation_service import EvaluationService
from elasticdl_tpu.master.membership import MembershipManager
from elasticdl_tpu.master.servicer import MasterServicer
from elasticdl_tpu.master.task_dispatcher import TaskDispatcher


@contextlib.contextmanager
def start_master(
    training_shards=None,
    evaluation_shards=None,
    prediction_shards=None,
    records_per_task=10,
    num_epochs=1,
    shuffle=False,
    eval_metrics_factory=None,
    eval_steps=0,
    with_membership=False,
):
    task_d = TaskDispatcher(
        training_shards or {},
        evaluation_shards,
        prediction_shards,
        records_per_task=records_per_task,
        num_epochs=num_epochs,
        shuffle=shuffle,
    )
    evaluation_service = None
    if eval_metrics_factory is not None:
        evaluation_service = EvaluationService(
            task_d, eval_metrics_factory, eval_steps=eval_steps
        )
    membership = MembershipManager() if with_membership else None
    servicer = MasterServicer(task_d, evaluation_service, membership)
    server, port = rpc.serve(servicer, rpc.MASTER_SERVICE, port=0)
    try:
        yield {
            "addr": f"localhost:{port}",
            "task_d": task_d,
            "servicer": servicer,
            "evaluation_service": evaluation_service,
            "membership": membership,
        }
    finally:
        server.stop(0)


# Multi-process worlds on the CPU reduce through gloo, whose operations
# wait 30 minutes by default: under load two ranks can enter independent
# collectives in different orders and then both sit there, the whole job
# at ~0% CPU, until the suite is cut. A bounded wait turns that wedge into
# a failed step, which the trainer's regroup path retries.
CPU_COLLECTIVE_TIMEOUT_FLAG = "--xla_cpu_collective_timeout_seconds=60"
MULTIHOST_XLA_FLAGS = (
    f"--xla_force_host_platform_device_count=4 {CPU_COLLECTIVE_TIMEOUT_FLAG}"
)


def coordinator_block():
    """A free coordinator port block from this xdist worker's own slice
    of the range (two workers can otherwise win the same block)."""
    import os
    import sys

    repo = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
    sys.path.insert(0, os.path.join(repo, "tools"))
    from elastic_drill import free_coordinator_block

    worker = os.environ.get("PYTEST_XDIST_WORKER", "gw0")
    return free_coordinator_block(lane=int(worker[2:] or 0), lanes=16)


def run_edl(*argv, timeout=240, include_tests_on_path=True,
            extra_env=None):
    """Run the `edl` CLI as a subprocess on the virtual CPU platform (the
    outer environment may point JAX at the real TPU). One definition so
    the CLI-launch recipe can't drift between test files.

    The job is its own process group and logs to files: at `timeout` the
    whole group is killed (the master's workers too — no orphans), and
    no pipe can block a role or the final read. Raises
    subprocess.TimeoutExpired with the output so far."""
    import os
    import signal
    import subprocess
    import sys
    import tempfile

    repo = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
    env = dict(os.environ)
    env["PYTHONPATH"] = (
        f"{repo}:{repo}/tests" if include_tests_on_path else repo
    )
    env["JAX_PLATFORMS"] = "cpu"
    env.update(extra_env or {})
    if CPU_COLLECTIVE_TIMEOUT_FLAG not in env.get("XLA_FLAGS", ""):
        env["XLA_FLAGS"] = (
            env.get("XLA_FLAGS", "") + " " + CPU_COLLECTIVE_TIMEOUT_FLAG
        ).strip()
    cmd = [sys.executable, "-m", "elasticdl_tpu.client.main", *argv]
    with tempfile.TemporaryFile("w+") as out, tempfile.TemporaryFile(
        "w+"
    ) as err:
        job = subprocess.Popen(
            cmd, stdout=out, stderr=err, env=env, cwd=repo,
            start_new_session=True,
        )
        timed_out = False
        try:
            job.wait(timeout=timeout)
        except subprocess.TimeoutExpired:
            timed_out = True
        finally:
            try:
                os.killpg(job.pid, signal.SIGKILL)
            except (ProcessLookupError, PermissionError):
                pass
            job.wait()
        out.seek(0)
        err.seek(0)
        stdout, stderr = out.read(), err.read()
    if timed_out:
        raise subprocess.TimeoutExpired(
            cmd, timeout, output=stdout, stderr=stderr[-3000:]
        )
    return subprocess.CompletedProcess(
        cmd, job.returncode, stdout, stderr
    )


def write_lm_records(path, n=96, seed=0, vocab=256, seq_plus_one=33):
    """Synthetic successor-sequence LM records (token[t+1] = token[t]+1
    mod vocab) shared by the LM CLI e2e tests."""
    import numpy as np

    from elasticdl_tpu.data.example import encode_example
    from elasticdl_tpu.data.recordfile import RecordFileWriter

    rng = np.random.default_rng(seed)
    with RecordFileWriter(path) as w:
        for _ in range(n):
            start = int(rng.integers(0, vocab))
            seq = (start + np.arange(seq_plus_one)) % vocab
            w.write(encode_example({"tokens": seq.astype(np.int32)}))
