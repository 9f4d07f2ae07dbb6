"""Shared by the benchmark's tests: the harness's modules, and tiny cells.

The harness has no CPU mode. The tests call the functions `run.py` is made
of, on a toy configuration (tiny_lm.json, beside this file) and with the
committed traffic files cut to toy rates.
"""

import argparse
import copy
import importlib.util
import json
import os
import sys

REPO = os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))
BENCH = os.path.join(REPO, "benchmark")
for path in (REPO, BENCH):
    if path not in sys.path:
        sys.path.insert(0, path)

from lib import cell as cell_mod  # noqa: E402


def load_file(path, name):
    spec = importlib.util.spec_from_file_location(name, path)
    module = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(module)
    return module


def manifest():
    with open(os.path.join(REPO, "BENCHMARK.json")) as f:
        return json.load(f)


def run_module():
    return load_file(os.path.join(BENCH, "run.py"), "edlbench_run")


def tiny_config():
    with open(os.path.join(REPO, "tests", "benchmark", "tiny_lm.json")) as f:
        return json.load(f)


def tiny_cell(traffic):
    """The committed traffic file `traffic` over the toy LM: the metrics
    of the flagship cell with that traffic, rates cut to what a CPU does."""
    m = copy.deepcopy(manifest())
    like = next(w for w in m["workloads"] if w["traffic"] == traffic)
    name = f"tiny_lm.{traffic}"
    m["configs"] = [{"name": "tiny_lm", "source": "toy", "reduced": [],
                     "file": "tests/benchmark/tiny_lm.json", "why": "toy"}]
    m["workloads"] = [dict(like, name=name, config="tiny_lm")]
    for metric in m["end_to_end"] + m["per_layer"]:
        if "workloads" in metric:
            metric["workloads"] = (
                [name] if like["name"] in metric["workloads"] else [])
    cell = cell_mod.Cell(name, m)
    cell.traffic = dict(cell.traffic)
    cell.traffic["records_per_second_sized_for"] = 4000
    # The suite's own environment gives every process 8 virtual devices.
    cell.traffic["env"] = {
        "JAX_PLATFORMS": "cpu",
        "XLA_FLAGS": "--xla_force_host_platform_device_count="
                     f"{cell.chips}",
    }
    return cell


def run_args(cell, seed, seconds, trace=0):
    return argparse.Namespace(
        workload=cell.name, seed=seed, seconds=seconds, trace=trace)
