"""The trace reduction: on hand-made events, and on the small recorded
trace of one flagship training step (testdata/, from a TPU v5e, PR 24)."""

import gzip
import json
import os

import pytest

import bench_helpers as h
from lib import trace, view

RECORDED = os.path.join(h.BENCH, "testdata", "flagship_step_trace.json.gz")


@pytest.fixture(scope="module")
def recorded():
    with gzip.open(RECORDED, "rt") as f:
        return json.load(f)


def ev(name, start, dur):
    return [name, float(start), float(dur)]


def test_compact_keeps_what_tells_operations_apart():
    line = ('%MultiHeadAttention_0.71 = (f32[32,4096,128]{2,1,0:T(8,128)}, '
            'f32[32,4096,128]{2,1,0:T(8,128)}) custom-call(f32[32,4096,128]'
            '{2,1,0:T(8,128)} %bitcast.1, f32[32,4096,128]{2,1,0} %b.2, '
            'f32[32,4096,128]{2,1,0:T(8,128)S(1)} %b.3), custom_call_target='
            '"tpu_custom_call", operand_layout_constraints={f32[32,4096,128]'
            '{2,1,0}}, frontend_attributes={kernel_metadata={}}')
    short = trace.compact(line)
    assert short == ("MultiHeadAttention_0.71 = (f32[32,4096,128], "
                     "f32[32,4096,128]) custom-call(3) tpu_custom_call")
    assert trace.parse(short) == (
        "MultiHeadAttention_0.71", "(f32[32,4096,128], f32[32,4096,128])",
        "custom-call", 3, "tpu_custom_call")
    assert trace.family(short) == (
        "custom-call:tpu_custom_call -> "
        "(f32[32,4096,128], f32[32,4096,128])")
    fused = trace.compact(
        "%fusion.7 = bf16[4,4096]{1,0:T(4,128)(2,1)} fusion(f32[4]{0} %a, "
        "f32[4]{0} %b), kind=kLoop, calls=%fused_computation.7")
    assert fused == "fusion.7 = bf16[4,4096] fusion(2) kLoop"
    assert trace.compact("jit_step_fn(123)") == "jit_step_fn(123)"
    assert trace.parse("jit_step_fn(123)") is None


def test_union_self_time_gaps_and_exposed_time_by_hand():
    assert trace.union_ns([(0, 10), (5, 20), (30, 40)]) == 30
    events = [ev("while.1 = s32[] while(1)", 0, 100),
              ev("a.1 = f32[2] fusion(1) kLoop", 10, 30),
              ev("all-reduce.1 = f32[2] all-reduce(1)", 50, 20),
              ev("b.1 = f32[2] fusion(1) kLoop", 120, 30)]
    own = dict((n, s) for n, _, s in trace.self_times(events))
    assert own == {"a.1 = f32[2] fusion(1) kLoop": 30,
                   "all-reduce.1 = f32[2] all-reduce(1)": 20,
                   "while.1 = s32[] while(1)": 50,
                   "b.1 = f32[2] fusion(1) kLoop": 30}
    red = trace.reduce({"/device:TPU:0": {"XLA Ops": events}})
    assert red["busy_s"] == pytest.approx(130e-9)
    assert red["window_s"] == pytest.approx(150e-9)
    assert red["idle_gaps"] == [["unattributed", pytest.approx(20e-9)]]
    assert red["device_ops"][:2] == [
        ["fusion:kLoop -> f32[2]", pytest.approx(60e-9)],  # a.1 and b.1
        ["while -> s32[]", pytest.approx(50e-9)]]
    # The all-reduce runs inside the while, so none of it is exposed...
    is_ar = lambda n: trace.parse(n)[2].startswith("all-reduce")  # noqa
    assert trace.exposed_seconds(red, is_ar) == 0
    # ...and alone on its device, all of it is.
    alone = trace.reduce({"/device:TPU:0": {"XLA Ops": [
        ev("a.1 = f32[2] fusion(1) kLoop", 0, 10),
        ev("all-reduce-done.1 = f32[2] all-reduce-done(1)", 10, 5),
        ev("b.1 = f32[2] fusion(1) kLoop", 15, 10)]}})
    assert trace.exposed_seconds(alone, is_ar) == pytest.approx(5e-9)
    assert trace.reduce({"/device:TPU:0": {"XLA Ops": []}}) is None


def test_two_devices_are_averaged():
    red = trace.reduce({
        "/device:TPU:0": {"XLA Ops": [ev("a.1 = f32[2] add(2)", 0, 10)]},
        "/device:TPU:1": {"XLA Ops": [ev("a.1 = f32[2] add(2)", 0, 30)]},
    })
    assert red["busy_s"] == pytest.approx(20e-9)
    assert red["device_ops"] == [["add -> f32[2]", pytest.approx(20e-9)]]
    assert len(trace.matching(red, lambda n: True)) == 2


def test_recorded_step_reduces_to_what_was_read_by_hand(recorded):
    red = trace.reduce(recorded["planes"])
    events = recorded["planes"]["/device:TPU:0"]["XLA Ops"]
    assert len(events) == 3643
    # One core runs one operation at a time: busy is the plain sum.
    assert red["busy_s"] * 1e9 == pytest.approx(
        sum(e[2] for e in events), rel=1e-9)
    assert recorded["step_ns"] / 1e6 == pytest.approx(228.148, abs=0.01)
    assert red["busy_s"] * 1e3 == pytest.approx(228.11, abs=0.01)
    assert red["window_s"] <= recorded["step_ns"] / 1e9
    idle = 1 - red["busy_s"] / red["window_s"]
    assert 0 <= idle < 0.001  # inside a step the core never waits
    top = red["device_ops"][0]
    assert top[0] == ("custom-call:tpu_custom_call -> "
                      "(f32[32,4096,128], f32[32,4096,128])")
    assert top[1] * 1e3 == pytest.approx(51.84, abs=0.01)
    assert len(red["device_ops"]) == 10 and len(red["idle_gaps"]) <= 10


def test_flash_readers_on_the_recorded_step(recorded, capsys):
    """12 layers x (forward, dq, dk/dv) = 36 Pallas calls a step; each
    needs 2 products over the causal half of [32, 4096, 128]:
    2 * 2 * 32 * 128 * 8,390,656 = 1.3747e11 operations = 0.698 ms at
    197 TFLOP/s, against 268 to 470 MB = 0.33 to 0.57 ms of bytes."""
    helper = h.cell_mod.load_module("metrics", "_pallas_attention")
    assert helper.classify(
        "x.1 = (f32[32,4096,128], f32[32,4096,128]) custom-call(3) "
        "tpu_custom_call") == ("forward", 32, 4096, 128, 4)
    assert helper.classify(
        "x.2 = f32[32,4096,128] custom-call(6) tpu_custom_call")[0] == "dq"
    assert helper.classify(
        "x.3 = (bf16[32,4096,128], bf16[32,4096,128]) custom-call(6) "
        "tpu_custom_call") == ("dkv", 32, 4096, 128, 2)
    assert helper.classify("x.4 = f32[8] custom-call(1) Sharding") is None
    assert helper.classify("fusion.7 = bf16[4] fusion(2) kLoop") is None

    class Run:
        trace = None
        device = {"kind": "TPU v5 lite", "count": 1}

    run = Run()
    run.trace = trace.reduce(recorded["planes"])
    events = helper.kernel_events(run)
    kinds = [e[0] for e in events]
    assert len(events) == 36
    assert {k: kinds.count(k) for k in set(kinds)} == {
        "forward": 12, "dq": 12, "dkv": 12}
    least, roofs = helper.least_seconds(run, events)
    assert roofs == {"compute": 36}
    assert least == pytest.approx(36 * 1.3747e11 / 197e12, rel=1e-3)
    roofline = h.cell_mod.load_module("metrics", "flash_roofline").read(run)
    share = h.cell_mod.load_module("metrics", "flash_time_pct").read(run)
    printed = json.loads(capsys.readouterr().out.strip().splitlines()[-1])
    assert printed["calls"] == 36 and printed["binding_roof_by_call"] == {
        "compute": 36}
    took = sum(e[-1] for e in events) / 1e9
    assert took * 1e3 == pytest.approx(75.94, abs=0.05)
    assert roofline == pytest.approx(100 * least / took)
    assert 30 < roofline < 36 and roofline <= 100
    assert share == pytest.approx(100 * took / run.trace["busy_s"])
    assert 33 < share < 34
    run.trace = None
    assert h.cell_mod.load_module("metrics", "flash_roofline").read(run) \
        is None


def test_step_lines_are_read_from_the_workers_log():
    log = ("[2026-09-27 08:09:39,271] [INFO] [elasticdl_tpu.worker.worker"
           ":415] Step 48 (version 48) loss 10.281269\n"
           "noise\n"
           "[2026-09-27 08:09:41,112] [INFO] [w:415] Step 56 (lease 7) "
           "loss nan\n")
    got = view.step_losses(log)
    assert [(s, str(x)) for _, s, x in got] == [(48, "10.281269"),
                                                (56, "nan")]
    assert got[1][0] - got[0][0] == pytest.approx(1.841)
