"""BENCHMARK.json, and the files a cell's names lead to."""

import importlib.util
import json
import os

BENCH_DIR = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
REPO = os.path.dirname(BENCH_DIR)


def load_json(path):
    with open(path) as f:
        return json.load(f)


def load_module(kind, name):
    """benchmark/<kind>/<name>.py as a module (names may hold dots)."""
    path = os.path.join(BENCH_DIR, kind, f"{name}.py")
    if not os.path.exists(path):
        raise FileNotFoundError(
            f"no {kind} file for {name!r}: expected {path}"
        )
    spec = importlib.util.spec_from_file_location(
        f"edlbench_{kind}_{name.replace('.', '_')}", path
    )
    module = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(module)
    return module


def metric_applies(metric, cell_name, all_cells):
    listed = metric.get("workloads")
    return cell_name in (listed if listed is not None else all_cells)


class Cell:
    """One entry of `workloads`, with its configuration, its traffic mix
    and the metrics it reports."""

    def __init__(self, name, manifest=None):
        self.manifest = manifest or load_json(
            os.path.join(REPO, "BENCHMARK.json")
        )
        cells = {w["name"]: w for w in self.manifest["workloads"]}
        if name not in cells:
            raise KeyError(
                f"no workload {name!r} in BENCHMARK.json; there are "
                f"{sorted(cells)}"
            )
        self.name = name
        self.entry = cells[name]
        self.chips = int(self.entry["chips"])
        config_entry = next(
            c for c in self.manifest["configs"]
            if c["name"] == self.entry["config"]
        )
        self.config_path = os.path.join(REPO, config_entry["file"])
        self.config = load_json(self.config_path)
        self.traffic_path = os.path.join(
            BENCH_DIR, "traffic", f"{self.entry['traffic']}.json"
        )
        self.traffic = load_json(self.traffic_path)
        names = list(cells)
        self.end_to_end = [
            m for m in self.manifest["end_to_end"]
            if metric_applies(m, name, names)
        ]
        reported = {m["name"] for m in self.end_to_end}
        self.per_layer = [
            m for m in self.manifest["per_layer"]
            if metric_applies(m, name, names) and m["moves"] in reported
        ]
