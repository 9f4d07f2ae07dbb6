"""The CPU rehearsal of chip_smoke.py, and the rules the bring-up set.

chip_smoke.py has no CPU mode; these tests import its phase functions and
pass tiny sizes in (the toy LM instead of the flagship, minibatch 64
instead of 16384). What they pin: the per-phase JSON, that the master and
the PS never initialise a jax backend (asserted from inside those
processes), where the compile cache lives, and that nothing that measures
the chip quietly runs without one.
"""

import json
import os
import subprocess
import sys

import pytest

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path.insert(0, REPO)

import chip_smoke  # noqa: E402

TOY_LM = dict(
    model_def="elasticdl_tpu.models.transformer.transformer_lm",
    seq_len=32, vocab=256,
)
# A phase's whole job, set-up and teardown included, is bounded by this:
# no case outlives it, pass or fail (run_drill's one deadline).
JOB_TIMEOUT = 240


def test_phase_a_with_the_kill_rehearsal(tmp_path):
    """Phase A's job with phase B's SIGKILL: one tiny job covers both."""
    out = chip_smoke.phase_kill(
        str(tmp_path), minibatch=4, steps=60, timeout=JOB_TIMEOUT, **TOY_LM
    )
    assert out["phase"] == "B_worker_kill"
    assert out["relaunched"]
    assert out["killed_worker_pid"] != out["replacement_worker_pid"]
    assert out["records_done"] == out["records_planned"] == 240
    assert out["kill_to_first_step_seconds"] > 0
    # The replacement rehydrated the step its predecessor compiled.
    assert out["replacement"]["step_compile"] == [
        {"cache_hit": True,
         "seconds": out["replacement"]["step_compile"][0]["seconds"]}
    ]
    rep = out["replacement"]
    assert rep["platform"] == "cpu" and rep["device_count"] >= 1
    assert rep["loss_last"] < rep["loss_first"]
    # No kernel on the CPU; the key is what the chip run reads.
    assert out["lowered_has_pallas_call"] is False
    assert out["backend_initialized_by_role"] == {
        "master": False, "worker-0": True,
    }
    json.dumps(out)


def test_phase_c_rehearsal_master_and_ps_never_open_a_backend(tmp_path):
    out = chip_smoke.phase_ps(
        str(tmp_path), minibatch=64, steps=4, timeout=JOB_TIMEOUT
    )
    assert out["phase"] == "C_parameter_server_deepfm"
    assert out["records_done"] == 256 and out["steps"] == 4
    assert out["native_kernels_loaded"] is True
    # From inside every role of the job (chip_smoke._ROLE_HOOK): each
    # imported jax — through the model module — and only the worker
    # initialised a backend.
    assert out["backend_initialized_by_role"] == {
        "master": False, "ps-0": False, "ps-1": False, "worker-0": True,
    }
    json.dumps(out)


def test_final_line_refuses_a_non_tpu_platform():
    with pytest.raises(SystemExit, match="no TPU"):
        chip_smoke.final_line(
            {"platform": "cpu", "kind": "cpu", "count": 8}
        )
    line = chip_smoke.final_line(
        {"platform": "tpu", "kind": "TPU v5 lite", "count": 1}
    )
    assert json.loads(line) == {
        "ok": True,
        "device": {"platform": "tpu", "kind": "TPU v5 lite", "count": 1},
    }


def _run(argv, env=None, timeout=120, cwd=REPO):
    full = dict(os.environ)
    full.pop("JAX_COMPILATION_CACHE_DIR", None)
    full["JAX_PLATFORMS"] = "cpu"
    full.update(env or {})
    return subprocess.run(
        argv, capture_output=True, text=True, env=full, timeout=timeout,
        cwd=cwd,
    )


def test_chip_smoke_script_fails_without_a_tpu():
    res = _run([sys.executable, "chip_smoke.py"])
    assert res.returncode != 0
    assert "no TPU" in res.stderr and "'cpu'" in res.stderr
    assert '"ok"' not in res.stdout


_CACHE_PROBE = """
import json, os, subprocess, sys
import jax
from elasticdl_tpu.common import compile_cache
before = jax.config.jax_compilation_cache_dir
resolved = compile_cache.ensure_compile_cache()
out = {"before": before, "resolved": resolved,
       "config": jax.config.jax_compilation_cache_dir}
if len(sys.argv) > 1:  # one level of children, as the master launches
    child = subprocess.run([sys.executable, "-c", sys.argv[1]],
                           capture_output=True, text=True)
    out["child"] = json.loads(child.stdout.strip().splitlines()[-1])
print(json.dumps(out))
"""


def _probe_cache(env):
    res = _run(
        [sys.executable, "-c", _CACHE_PROBE, _CACHE_PROBE],
        env={"PYTHONPATH": REPO, **env},
    )
    assert res.returncode == 0, res.stderr[-2000:]
    return json.loads(res.stdout.strip().splitlines()[-1])


def test_cache_dir_from_the_environment_is_the_only_one(tmp_path):
    """JAX_COMPILATION_CACHE_DIR set: jax reads it itself, the code sets
    no directory, and children end up with the same one."""
    placed = str(tmp_path / "placed")
    got = _probe_cache({"JAX_COMPILATION_CACHE_DIR": placed})
    for proc in (got, got["child"]):
        assert proc["before"] == placed  # jax's own reading of the env
        assert proc["resolved"] == proc["config"] == placed
    assert os.path.isdir(placed)


def test_cache_dir_defaults_to_one_fixed_path_in_the_checkout():
    fixed = os.path.join(REPO, ".jax_cache")
    got = _probe_cache({})
    for proc in (got, got["child"]):
        assert proc["before"] is None
        assert proc["resolved"] == proc["config"] == fixed


def test_cache_dir_that_cannot_be_made_raises(tmp_path):
    blocker = tmp_path / "file"
    blocker.write_text("not a directory")
    res = _run(
        [sys.executable, "-c", _CACHE_PROBE],
        env={"PYTHONPATH": REPO,
             "JAX_COMPILATION_CACHE_DIR": str(blocker / "cache")},
    )
    assert res.returncode != 0
    assert "NotADirectoryError" in res.stderr


def test_worker_that_cannot_open_its_device_fails_the_job_loudly(tmp_path):
    """A worker that cannot get its accelerator (on the chip: another
    process holds it) ends at once with the cause named; the master
    relaunches it `--max_relaunches` times, reports, and gives up — in
    bounded time, without probing any device itself."""
    from elasticdl_tpu.data.recordfile import RecordFileWriter

    import test_module

    data = str(tmp_path / "linear.edlr")
    with RecordFileWriter(data) as w:
        for r in test_module.make_linear_records(64):
            w.write(r)
    from test_utils import run_edl

    res = run_edl(
        "train",
        "--model_zoo", f"{REPO}/tests", "--model_def", "test_module",
        "--training_data", data, "--minibatch_size", "16",
        "--num_workers", "1", "--max_relaunches", "1",
        "--distribution_strategy", "AllreduceStrategy",
        "--instance_backend", "local_process", "--master_port", "0",
        extra_env={"JAX_PLATFORMS": "no_such_platform"},
        timeout=JOB_TIMEOUT,
    )
    assert res.returncode != 0
    assert "worker could not open its accelerator" in res.stderr
    assert "one process per chip" in res.stderr
    assert res.stderr.count("Launched worker 0") == 2  # 1 + 1 relaunch
