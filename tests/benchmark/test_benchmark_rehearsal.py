"""The CPU rehearsal of benchmark/run.py: the same functions, the
committed traffic files, a toy LM. And what run.py must refuse."""

import json
import os
import shutil
import subprocess
import sys

import pytest

import bench_helpers as h

TRAFFIC = sorted({w["traffic"] for w in h.manifest()["workloads"]})


def rehearse(traffic, capsys, seconds=3.0, seed=2**31 + 7):
    cell = h.tiny_cell(traffic)
    run = h.run_module()
    rc = run.run_cell(cell, h.run_args(cell, seed, seconds),
                      expect_platform="cpu")
    out = capsys.readouterr().out.strip().splitlines()
    assert rc == 0, out[-3:]
    return cell, [json.loads(line) for line in out]


@pytest.mark.parametrize("traffic", TRAFFIC)
def test_rehearsal_of_each_traffic_mix(traffic, capsys):
    cell, lines = rehearse(traffic, capsys)
    result = lines[-1]
    assert set(result) == {"correct", "attempted", "failed", "metrics",
                           "device"}
    assert result["correct"] is True, lines
    assert result["failed"] == 0 and result["attempted"] > 0
    assert set(result["metrics"]) == {m["name"] for m in cell.end_to_end}
    for m in cell.end_to_end:
        got = result["metrics"][m["name"]]
        assert got["unit"] == m["unit"] and got["value"] > 0
    assert result["device"]["platform"] == "cpu"
    assert result["device"]["count"] == cell.chips
    checks = {x["check"]: x for x in lines if "check" in x}
    assert set(checks) == {"loss_vs_reference", "accounting", "devices"}
    for row in checks["loss_vs_reference"]["rows"]:
        assert abs(row["diff"]) <= row["abs_limit"]
    assert abs(checks["loss_vs_reference"]["mean_diff"]) <= \
        checks["loss_vs_reference"]["mean_limit"]
    roles = checks["devices"]["backend_initialized_by_role"]
    assert roles == {"master": False, "worker-0": True}
    window = next(x["window"] for x in lines if "window" in x)
    assert window["leftover_processes"] == []


def test_a_steady_job_that_runs_out_of_records_fails_the_run(capsys):
    cell = h.tiny_cell("steady")
    cell.traffic["records_per_second_sized_for"] = 30  # far too few
    run = h.run_module()
    rc = run.run_cell(cell, h.run_args(cell, 5, 3.0), expect_platform="cpu")
    captured = capsys.readouterr()
    assert rc != 0 and "FAILED" in captured.err
    assert '"correct"' not in captured.out


def _run_py(cwd, *argv):
    env = dict(os.environ, JAX_PLATFORMS="cpu")
    return subprocess.run(
        [sys.executable, os.path.join(cwd, "benchmark", "run.py"), *argv],
        capture_output=True, text=True, env=env, cwd=cwd, timeout=120)


ARGV = ("--workload", h.manifest()["workloads"][0]["name"], "--seed", "1",
        "--seconds", "2", "--trace", "0")


def test_run_py_fails_without_a_tpu_before_any_job():
    res = _run_py(h.REPO, *ARGV)
    assert res.returncode != 0
    assert "TPU" in res.stderr and "no CPU mode" in res.stderr
    assert '"correct"' not in res.stdout and "data" not in res.stdout


def test_run_py_fails_where_the_program_is_missing(tmp_path):
    shutil.copy(os.path.join(h.REPO, "BENCHMARK.json"), tmp_path)
    for path in h.manifest()["paths"]:
        shutil.copytree(os.path.join(h.REPO, path), tmp_path / path,
                        ignore=shutil.ignore_patterns("__pycache__"))
    res = _run_py(str(tmp_path), *ARGV)
    assert res.returncode != 0 and '"correct"' not in res.stdout
    assert "elasticdl_tpu" in res.stderr


def test_run_py_refuses_an_unknown_workload():
    res = _run_py(h.REPO, "--workload", "no.such", "--seed", "1",
                  "--seconds", "2", "--trace", "0")
    assert res.returncode != 0 and "no workload" in res.stderr
