"""BENCHMARK.json against the contract's static rules, and every file a
cell's names lead to."""

import os
import re

import pytest

import bench_helpers as h

NAME = re.compile(r"^[A-Za-z0-9_][A-Za-z0-9_.\-]{0,63}$")
UNIT = re.compile(r"^[A-Za-z0-9_/%.\-]{1,16}$")
SOURCES = {"device_trace", "program_span", "program_counter", "host_clock"}
M = h.manifest()
CELLS = [w["name"] for w in M["workloads"]]


def test_top_level_keys_and_command():
    assert set(M) == {"command", "paths", "run_seconds", "configs",
                      "workloads", "end_to_end", "per_layer"}
    assert M["command"] == ["python3", "benchmark/run.py"]
    assert 1 <= M["run_seconds"] <= 51 and isinstance(M["run_seconds"], int)
    for path in M["paths"]:
        assert os.path.isdir(os.path.join(h.REPO, path))
    # 2 + 14 x cells runs of run_seconds + 60 s, 180 s a cell to compile,
    # 1200 s spare, within 43200 s, at the full 24 cells.
    assert (2 + 14 * 24) * (M["run_seconds"] + 60) + 24 * 180 + 1200 <= 43200


@pytest.mark.parametrize("metric", M["end_to_end"] + M["per_layer"],
                         ids=lambda m: m["name"])
def test_metric_entry(metric):
    assert NAME.match(metric["name"]) and UNIT.match(metric["unit"])
    assert metric["better"] in ("lower", "higher")
    assert metric["source"] in SOURCES
    for cell in metric.get("workloads", []):
        assert cell in CELLS
    if "bound" in metric:  # end to end
        assert set(metric) <= {"name", "unit", "better", "bound", "source",
                               "workloads"}
        assert 0.01 <= metric["bound"] <= 0.1
        assert metric["source"] in ("host_clock", "device_trace")
    else:
        assert set(metric) <= {"name", "unit", "better", "source", "layer",
                               "moves", "workloads"}
        assert metric["moves"] in {m["name"] for m in M["end_to_end"]}
        assert "\n" not in metric["layer"] and len(metric["layer"]) <= 200
    reader = os.path.join(h.BENCH, "metrics", metric["name"] + ".py")
    assert os.path.exists(reader), f"no reader {reader}"
    assert callable(h.load_file(reader, "reader").read)


def test_names_are_unique_and_setup_is_everywhere():
    for group in ("configs", "workloads"):
        names = [x["name"] for x in M[group]]
        assert len(set(names)) == len(names)
    metrics = [m["name"] for m in M["end_to_end"] + M["per_layer"]]
    assert len(set(metrics)) == len(metrics)
    setup = next(m for m in M["end_to_end"] if m["name"] == "setup_s")
    assert "workloads" not in setup and setup["bound"] <= 0.1
    pairs = [(w["config"], w["traffic"]) for w in M["workloads"]]
    assert len(set(pairs)) == len(pairs)
    four = sum(1 for w in M["workloads"] if w["chips"] == 4)
    assert four <= max(1, len(CELLS) // 4)


@pytest.mark.parametrize("name", CELLS)
def test_cell_files_exist_and_it_reports_enough(name):
    cell = h.cell_mod.Cell(name)
    entry = cell.entry
    assert set(entry) == {"name", "config", "traffic", "chips", "why"}
    assert NAME.match(name) and NAME.match(entry["traffic"])
    assert entry["chips"] in (1, 4) and len(entry["why"]) <= 200
    assert entry["chips"] == cell.traffic["chips"]
    for kind, module in (("datagen", cell.config["datagen"]),
                         ("references",
                          cell.config["reference"]["module"])):
        assert os.path.exists(
            os.path.join(h.BENCH, kind, module + ".py"))
    reported = {m["name"] for m in cell.end_to_end}
    assert "setup_s" in reported and len(reported) >= 2
    assert cell.per_layer, "a cell reports at least one per-layer metric"
    for key in ("loss_abs_limit", "loss_mean_limit"):
        limit = cell.config["reference"][key]
        assert isinstance(limit, float) and limit > 0
    assert len(cell.traffic["compare_steps"]) >= 2
    for step in cell.traffic["compare_steps"]:
        assert step % cell.traffic["log_loss_steps"] == 0  # it is logged


@pytest.mark.parametrize("config", M["configs"], ids=lambda c: c["name"])
def test_config_entry(config):
    assert set(config) == {"name", "source", "file", "reduced", "why"}
    assert NAME.match(config["name"]) and len(config["source"]) <= 200
    assert config["file"].startswith(tuple(M["paths"]))
    body = h.cell_mod.load_json(os.path.join(h.REPO, config["file"]))
    for key in ("source", "reduced", "assumed", "model_def", "datagen",
                "reference", "precision"):
        assert key in body, key
    assert body["reduced"] == config["reduced"]
    assert any(w["config"] == config["name"] for w in M["workloads"])


def test_the_harness_names_no_cell_config_or_metric():
    """run.py and lib/ are general: what belongs to one cell, one
    configuration or one metric is a file found by name."""
    names = {x["name"] for g in ("configs", "workloads", "end_to_end",
                                 "per_layer") for x in M[g]}
    names |= {w["traffic"] for w in M["workloads"]}
    sources = [os.path.join(h.BENCH, "run.py")] + [
        os.path.join(h.BENCH, "lib", f)
        for f in os.listdir(os.path.join(h.BENCH, "lib"))
        if f.endswith(".py")]
    for path in sources:
        with open(path) as f:
            text = f.read()
        for name in names:
            assert not re.search(
                rf"(?<![A-Za-z0-9_.]){re.escape(name)}(?![A-Za-z0-9_])",
                text), f"{path} names {name!r}"
