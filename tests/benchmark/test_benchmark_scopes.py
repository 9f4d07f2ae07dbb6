"""The reader of the step's scope map (`benchmark/lib/scopes.py`) and the
nine metrics that sum it: on hand-made events, and on the recorded trace of
one Mellum training step with the map the worker wrote beside it
(testdata/, from a TPU v5e, PR 57)."""

import gzip
import json
import os
import types

import pytest

import bench_helpers as h
from lib import scopes

M = h.manifest()
TRACE = os.path.join(h.BENCH, "testdata", "mellum_step_scopes_trace.json.gz")
MAP = os.path.join(h.BENCH, "testdata", "mellum_step_scopes.json.gz")
NEW = ["step_fwd_pct.lm", "step_bwd_pct.lm", "step_remat_pct.lm",
       "step_update_pct.lm", "step_unscoped_pct.lm",
       "attn_scope_time_pct.lm", "attn_kernel_time_pct.lm",
       "moe_scope_time_pct.lm", "mixer_scope_time_pct.lm"]
STEP = NEW[:5]
DEVICE = "/device:TPU:0"


def _json(path):
    with gzip.open(path, "rt") as f:
        return json.load(f)


@pytest.fixture(scope="module")
def recorded():
    trace = _json(TRACE)
    return trace["ops"], trace["modules"], _json(MAP)


def row(name, opcode, phase, kind, layer=None, scope="", **more):
    return dict(name=name, opcode=opcode, phase=phase, kind=kind,
                layer=layer, scope=scope, **more)


def ev(name, shape, opcode, start, end, tail=""):
    return (f"%{name} = {shape} {opcode}(%a, %b){tail}", float(start),
            float(end))


ROWS = [
    row("while.1", "while", "fwd", "moe", 1, "layers_1/mlp/moe_grouped"),
    row("fusion.2", "fusion", "fwd", "moe", 1, "layers_1/mlp/moe_grouped",
        crosses=True),
    row("copy-start.3", "copy-start", "none", "other"),
    row("fusion.9", "fusion", "update", "update", crosses=False),
    row("flash_fwd.1", "custom-call", "remat", "attention_kernel", 0,
        "layers_0/self_attn/flash_fwd"),
    row("fusion.4", "fusion", "bwd", "attention", 0, "layers_0/self_attn",
        crosses=False),
]
EVENTS = [
    ev("while.1", "(f32[8])", "while", 0, 100),
    ev("fusion.2", "f32[8]", "fusion", 10, 40, ", kind=kLoop"),
    # A prefetch beside the body's fusion and past it: the loop's time.
    ev("copy-start.3", "(f32[8], f32[8], u32[])", "copy-start", 30, 60),
    # The same prefetch with nothing named beside it: unscoped.
    ev("copy-start.3", "(f32[8], f32[8], u32[])", "copy-start", 100, 120),
    ev("fusion.9", "f32[8]", "fusion", 110, 150, ", kind=kLoop"),
    ev("flash_fwd.1", "(bf16[2,8,8], f32[2,8,8])", "custom-call", 160, 200,
       ', custom_call_target="tpu_custom_call"'),
    ev("fusion.4", "f32[8]", "fusion", 200, 260, ", kind=kOutput"),
]
MODULES = {DEVICE: [("jit_step_fn(7)", 0.0, 300.0)]}


def booked(events=EVENTS, rows=ROWS, modules=MODULES, module="jit_step_fn"):
    return scopes.book({DEVICE: list(events)}, modules,
                       {"fn": "allreduce_step", "hlo_module": module,
                        "rows": rows})


def test_every_instant_of_the_busy_time_is_booked_once_by_hand():
    got = booked()
    ns = 1e-9
    assert got["busy_s"] == pytest.approx(250 * ns)  # 0..150, 160..260
    assert got["seconds"] == {
        # The loop and its body once: 100, whatever ran inside it.
        ("fwd", "moe", 1): pytest.approx(100 * ns),
        ("none", "other", None): pytest.approx(10 * ns),  # 100..110
        ("update", "update", None): pytest.approx(40 * ns),
        ("remat", "attention_kernel", 0): pytest.approx(40 * ns),
        ("bwd", "attention", 0): pytest.approx(60 * ns),
    }
    assert sum(got["seconds"].values()) == pytest.approx(got["busy_s"])
    assert got["found_share"] == 1.0 and got["unfound"] == {}
    # Booked to the fusion that crosses a boundary: its own 30 ns.
    assert got["crossing_s"] == pytest.approx(30 * ns)
    assert got["by_scope"][("layers_1/mlp/moe_grouped", "fwd")] == (
        pytest.approx(100 * ns))


def test_a_while_and_its_body_count_once_and_an_unnamed_body_is_the_loops():
    rows = [row("while.1", "while", "bwd", "moe", 2, "layers_2/mlp"),
            row("fusion.5", "fusion", "bwd", "moe", 2, "layers_2/mlp/x",
                crosses=False),
            row("copy.6", "copy", "none", "other")]
    events = [ev("while.1", "(f32[8])", "while", 0, 90)]
    for turn in range(3):
        events.append(ev("fusion.5", "f32[8]", "fusion", 30 * turn,
                         30 * turn + 20, ", kind=kLoop"))
        events.append(ev("copy.6", "f32[8]", "copy", 30 * turn + 20,
                         30 * turn + 30))
    got = booked(events, rows)
    assert got["seconds"] == {("bwd", "moe", 2): pytest.approx(90e-9)}
    assert got["by_scope"] == {
        ("layers_2/mlp/x", "bwd"): pytest.approx(60e-9),
        ("layers_2/mlp", "bwd"): pytest.approx(30e-9)}


def test_a_map_of_another_executable_gives_none_and_never_a_part():
    other = [dict(r, name="other_" + r["name"]) for r in ROWS]
    assert booked(rows=other) is None
    # The names fit and the opcodes do not: another program's `fusion.2`.
    assert booked(rows=[dict(r, opcode="copy") for r in ROWS]) is None
    # The names fit, but the map's module never ran in the trace.
    assert booked(modules={DEVICE: [("jit_other(1)", 0.0, 300.0)]}) is None
    # 2% of the busy time without a row is under the limit's 1%.
    stray = EVENTS + [ev("stray.1", "f32[2]", "add", 300, 306)]
    assert booked(stray) is None
    almost = booked(EVENTS + [ev("stray.1", "f32[2]", "add", 300, 301)])
    assert almost["unfound"] == {"stray.1": pytest.approx(1e-9)}
    assert almost["found_share"] == pytest.approx(250 / 251)


class FakeRun:
    """What a reader is given, as far as these readers look."""

    def __init__(self, tmp_path, events=EVENTS, rows=ROWS, written=True):
        self.trace = {"busy_s": 1.0}
        self._path = str(tmp_path / "step_scopes.json")
        self._written = written
        with open(self._path, "w") as f:
            json.dump({"fn": "allreduce_step", "hlo_module": "jit_step_fn",
                       "rows": rows}, f)
        self._ops = {DEVICE: list(events)}

    def events_of(self, kinds, role_prefix=None, since=None, until=None):
        if kinds == "step_scopes_written" and self._written:
            return [{"kind": kinds, "path": self._path}]
        if kinds == "profile_written":
            return [{"kind": kinds, "dir": os.path.dirname(self._path)}]
        return []


@pytest.fixture()
def read_by_hand(monkeypatch, tmp_path):
    """`read(run)` of each new metric over a hand-made run."""
    def read(**kwargs):
        run = FakeRun(tmp_path, **kwargs)
        monkeypatch.setattr(
            scopes.hostspans, "profile_file", lambda r: "trace.xplane.pb")
        monkeypatch.setattr(
            scopes, "read_trace", lambda path: (run._ops, MODULES))
        return {name: h.cell_mod.load_module("metrics", name).read(run)
                for name in NEW}
    return read


def test_the_nine_metrics_sum_the_rows_and_hold_no_name(read_by_hand, capsys):
    got = read_by_hand()
    assert got == {
        "step_fwd_pct.lm": pytest.approx(40.0),
        "step_bwd_pct.lm": pytest.approx(24.0),
        "step_remat_pct.lm": pytest.approx(16.0),
        "step_update_pct.lm": pytest.approx(16.0),
        "step_unscoped_pct.lm": pytest.approx(4.0),
        "attn_scope_time_pct.lm": pytest.approx(40.0),
        "attn_kernel_time_pct.lm": pytest.approx(16.0),
        "moe_scope_time_pct.lm": pytest.approx(40.0),
        "mixer_scope_time_pct.lm": 0.0,
    }
    assert sum(got[name] for name in STEP) == pytest.approx(100.0, abs=0.01)
    # The table is printed once a run, as the other readers print theirs.
    said = [json.loads(line) for line in capsys.readouterr().out.splitlines()
            if '"reader": "scopes"' in line]
    assert len(said) == 1 and said[0]["found_pct"] == 100.0
    assert said[0]["phase_by_kind_pct"]["remat"] == {"attention_kernel": 16.0}
    assert said[0]["heaviest_scopes_pct"][0] == [
        "layers_1/mlp/moe_grouped", "fwd", 40.0]
    # The benchmark's side knows no scope of any model: only kinds.
    for name in NEW + ["../lib/scopes"]:
        with open(os.path.join(h.BENCH, "metrics", name + ".py")) as f:
            text = f.read()
        for scope in ("moe_routing", "ssd_scan", "self_attn", "kanana",
                      "granite", "bd_attention", "short_conv"):
            assert scope not in text.replace("`ssd_scan", ""), (name, scope)


def test_without_the_event_or_with_another_map_all_nine_give_none(
        read_by_hand):
    assert set(read_by_hand(written=False).values()) == {None}
    other = [dict(r, name="other_" + r["name"]) for r in ROWS]
    assert set(read_by_hand(rows=other).values()) == {None}
    run = types.SimpleNamespace(trace=None)
    assert scopes.booked(run) is None  # an untraced run reads nothing


@pytest.mark.parametrize("name", NEW)
def test_the_new_entries_pass_the_manifests_own_test(name):
    import test_benchmark_manifest as manifest

    metric = next(m for m in M["per_layer"] if m["name"] == name)
    manifest.test_metric_entry(metric)
    assert (metric["unit"], metric["source"], metric["moves"],
            metric["better"]) == ("%", "device_trace", "tokens_per_s",
                                  "lower")
    assert "workloads" in metric
    # The entries are additions: the accepted ones stand before them.
    names = [m["name"] for m in M["per_layer"]]
    assert names.index(name) > names.index("mfu_pct.kanana")


def test_the_step_shares_list_every_cell_and_the_others_their_models():
    cells = [w["name"] for w in M["workloads"]]
    listed = {m["name"]: m["workloads"] for m in M["per_layer"]
              if m["name"] in NEW}
    for name in STEP + ["attn_scope_time_pct.lm", "attn_kernel_time_pct.lm"]:
        assert listed[name] == cells
    held = next(m for m in M["per_layer"]
                if m["name"] == "moe_held_share_pct")["workloads"]
    assert listed["moe_scope_time_pct.lm"] == held
    assert [c.split(".")[0] for c in listed["mixer_scope_time_pct.lm"]] == [
        "nemotron_twotower_30b_a3b", "lfm2_24b_a2b", "granite_4_0_h_micro"]


# ---------- the recorded step ----------


def test_the_recorded_step_books_all_its_busy_time_and_adds_up(recorded):
    ops, modules, scope_map = recorded
    got = scopes.book(ops, modules, scope_map)
    assert got is not None and got["found_share"] >= 0.999
    assert sum(got["seconds"].values()) == pytest.approx(
        got["busy_s"], rel=1e-9)
    shares = {}
    for (phase, _, _), s in got["seconds"].items():
        shares[phase] = shares.get(phase, 0.0) + 100.0 * s / got["busy_s"]
    assert set(shares) == {"fwd", "bwd", "remat", "update", "none"}
    assert sum(shares.values()) == pytest.approx(100.0, abs=0.01)
    # The Mellum cut rematerialises two of its four layers and holds no
    # mixer; what runs with nothing named beside it is the compiler's
    # layout copies and broadcasts, 5.9% of this step.
    assert {layer for (phase, _, layer) in got["seconds"]
            if phase == "remat"} - {None} == {0, 1}
    assert not [k for k in got["seconds"] if k[1] == "mixer"]
    assert 5.0 < shares["none"] < shares["remat"] + 1 < shares["update"] + 2
    assert shares["update"] < shares["fwd"] < shares["bwd"]
    assert max(got["unscoped"], key=got["unscoped"].get) == "copy"


def test_the_recorded_steps_loops_count_once(recorded):
    """A grouped loop's event covers its body's: summing durations would
    book the routed experts' time twice."""
    ops, modules, scope_map = recorded
    rows = {r["name"]: r for r in scope_map["rows"]}
    got = scopes.book(ops, modules, scope_map)
    moe = sum(s for (_, kind, _), s in got["seconds"].items()
              if kind == "moe")
    durations = sum(
        (end - start) / 1e9 for events in ops.values()
        for line, start, end in events
        if rows.get(scopes.instruction(line)[0], {}).get("kind") == "moe")
    whiles = [line for events in ops.values() for line, _, _ in events
              if scopes.instruction(line)[1] == "while"]
    assert whiles and moe < 0.7 * durations
    kernels = sum(s for (_, kind, _), s in got["seconds"].items()
                  if kind == "attention_kernel")
    by_name = sum(
        (end - start) / 1e9 for events in ops.values()
        for line, start, end in events
        if "flash_" in scopes.instruction(line)[0])
    # Nothing named runs inside a kernel's call: its share is its events'.
    assert kernels == pytest.approx(by_name, rel=1e-6)


def test_the_recorded_step_against_a_map_of_other_names_gives_none(recorded):
    ops, modules, scope_map = recorded
    other = dict(scope_map, rows=[
        dict(r, name="x_" + r["name"]) for r in scope_map["rows"]])
    assert scopes.book(ops, modules, other) is None
