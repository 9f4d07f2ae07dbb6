"""The LFM2 reference against the program at a tiny size on the CPU, the
fp8 control and the planted fault, the configuration file against the
model-def module, the new readers on synthetic traces and events, and the
CPU rehearsal of the cell's traffic with the toy model through `edl
train`."""

import copy
import dataclasses
import json

import numpy as np
import pytest

import bench_helpers as h

CELL = "lfm2_24b_a2b.steady_s8192_mb2"
STEPS = [8, 16, 24, 32]
MINIBATCH = 2
NEW_READERS = ("shortconv_time_pct", "moe_swiglu_time_pct",
               "moe_block_fill_pct", "mfu_pct.lfm2")


def tiny_config():
    with open(h.os.path.join(
            h.REPO, "tests", "benchmark", "tiny_lfm2.json")) as f:
        return json.load(f)


@pytest.fixture(scope="module")
def ref():
    return h.load_file(
        h.os.path.join(h.BENCH, "references", "lfm2_moe.py"),
        "edlbench_ref_lfm2_moe")


@pytest.fixture(scope="module")
def model_def():
    from elasticdl_tpu.common.model_utils import load_module

    return load_module(h.os.path.join(h.REPO, tiny_config()["model_def"]))


@pytest.fixture(scope="module")
def ops():
    return h.cell_mod.load_module("metrics", "_lfm2_ops")


def stated_equals_built(stated, built, skip=()):
    for key, value in stated.items():
        if key in skip:
            continue
        got = built[key]
        assert (list(got) if isinstance(got, tuple) else got) == value, key


def test_the_tiny_configuration_file_states_the_tiny_model(model_def):
    stated_equals_built(
        tiny_config()["model"], dataclasses.asdict(model_def.CONFIG))


def test_the_cut_configuration_file_states_the_model_def():
    """benchmark/configs/lfm2_24b_a2b.json against the model-def module
    `edl train` runs, against the catalog's rule (every width as published,
    three keys reduced) and against the initialised tree's size."""
    import jax
    import jax.numpy as jnp

    from elasticdl_tpu.models.lfm2 import lfm2_24b_a2b_cut as m

    cfg = h.cell_mod.Cell(CELL).config
    assert cfg["model_def"] == m.__name__
    stated_equals_built(
        cfg["model"], dataclasses.asdict(m.cut_config()),
        skip=("param_dtype", "parameters", "remat_reason", "kept_layers",
              "expert_block_rows_reason"))
    assert cfg["model"]["kept_layers"] == list(m.KEEP_LAYERS)
    public = dict(m.PUBLIC_CONFIG)
    differs = {k for k, v in public.items() if cfg.get(k) != v}
    assert differs == set(cfg["reduced"]) == {
        "num_hidden_layers", "num_experts", "vocab_size"}
    for key in cfg["reduced"]:
        assert cfg["published"][key] == public[key]
    for width in ("hidden_size", "intermediate_size",
                  "moe_intermediate_size", "num_attention_heads",
                  "num_key_value_heads", "num_experts_per_tok",
                  "conv_L_cache"):
        assert cfg["model"][width] == public[width], width
    assert cfg["model"]["num_experts"] == public["num_experts"] == 64
    assert cfg["model"]["rope_theta"] == \
        public["rope_parameters"]["rope_theta"]
    assert cfg["num_hidden_layers"] == len(cfg["model"]["layer_types"]) == 7
    assert cfg["data"]["vocab"] == cfg["vocab_size"] == 8192
    for key in ("deployment", "cut", "assumed", "departures"):
        assert cfg[key], key
    shapes = jax.eval_shape(
        lambda rng, row: m.custom_model().init(
            {"params": rng}, row, training=False),
        jax.random.PRNGKey(0), jnp.zeros((1, 8), jnp.int32))
    counted = sum(int(np.prod(leaf.shape))
                  for leaf in jax.tree_util.tree_leaves(shapes["params"]))
    assert counted == cfg["model"]["parameters"] == 647819520
    assert "647,819,520" in cfg["cut"]["parameters"]


def program_losses(seed, cfg, model_def):
    from elasticdl_tpu.worker.trainer import LocalTrainer

    datagen = h.cell_mod.load_module("datagen", cfg["datagen"])
    trainer = LocalTrainer(model_def.custom_model(), model_def.loss,
                           model_def.optimizer(), seed=seed)
    out, stats = {}, None
    for k, (x, y) in enumerate(datagen.batches(
            0, max(STEPS), MINIBATCH, seed, cfg["data"])):
        if k == 0:
            trainer.init_variables_if_needed(x[:1])
        _, _, loss = trainer.train_minibatch(x, y)
        stats = trainer.last_step_stats
        if k + 1 in STEPS:
            out[k + 1] = float(loss)
    return out, stats


def limits(cfg):
    return (cfg["reference"]["loss_abs_limit"],
            cfg["reference"]["loss_mean_limit"])


@pytest.mark.parametrize("seed", [101, 104, 2**31 + 11])
def test_program_passes_and_the_fp8_control_fails(ref, model_def, seed):
    cfg = tiny_config()
    compare = h.run_module().compare_losses
    want = ref.losses(cfg, seed, MINIBATCH, STEPS, "float32")
    got, stats = program_losses(seed, cfg, model_def)
    rows, mean, ok = compare(got, want, *limits(cfg))
    assert ok, (rows, mean)
    # The step hands the routed layers' counts back beside the loss: three
    # routed layers, two experts a token.
    made = float(stats["moe_assignments"])
    assert made == 3 * MINIBATCH * cfg["data"]["seq_len"] * 2
    assert 0 < float(stats["moe_assignments_held"]) < made
    assert float(stats["moe_block_rows_real"]) == float(
        stats["moe_assignments_held"])
    assert float(stats["moe_block_rows_run"]) >= float(
        stats["moe_block_rows_real"])
    control = ref.losses(cfg, seed, MINIBATCH, STEPS, "fp8")
    rows, mean, ok = compare(control, want, *limits(cfg))
    assert not ok, (rows, mean)


def test_a_reference_without_its_routed_experts_fails_the_limits(ref):
    """The planted fault: the comparison that decides `correct` sees the
    routed part of the layer."""
    cfg = tiny_config()
    want = ref.losses(cfg, 101, MINIBATCH, STEPS, "float32")
    fault = ref.losses(cfg, 101, MINIBATCH, STEPS, "float32", "no_routed")
    rows, mean, ok = h.run_module().compare_losses(
        fault, want, *limits(cfg))
    assert not ok, (rows, mean)


def tiny_lfm2_cell():
    """The committed cell's traffic and metrics over the toy model."""
    m = copy.deepcopy(h.manifest())
    like = next(w for w in m["workloads"] if w["name"] == CELL)
    name = "tiny_lfm2.steady_s8192_mb2"
    m["configs"] = [{"name": "tiny_lfm2", "source": "toy", "reduced": [],
                     "why": "toy",
                     "file": "tests/benchmark/tiny_lfm2.json"}]
    m["workloads"] = [dict(like, name=name, config="tiny_lfm2")]
    for metric in m["end_to_end"] + m["per_layer"]:
        if "workloads" in metric:
            metric["workloads"] = (
                [name] if CELL in metric["workloads"] else [])
    cell = h.cell_mod.Cell(name, m)
    cell.traffic = dict(cell.traffic)
    cell.traffic["records_per_second_sized_for"] = 1500
    cell.traffic["env"] = {
        "JAX_PLATFORMS": "cpu",
        "XLA_FLAGS": "--xla_force_host_platform_device_count=1",
    }
    return cell


def test_the_cell_reports_the_new_metrics_and_the_shared_ones():
    cell = h.cell_mod.Cell(CELL)
    reported = {m["name"] for m in cell.per_layer}
    assert set(NEW_READERS) <= reported
    assert {"flash_roofline", "flash_time_pct", "moe_held_share_pct",
            "moe_held_load_max_over_mean", "device_idle_pct.lm",
            "step_ms_p50.lm"} <= reported
    # Readers of other models' keys are not given this cell.
    assert not {"mfu_pct", "moe_time_pct", "ssd_time_pct"} & reported
    for m in h.manifest()["per_layer"]:
        if m["name"] in NEW_READERS:
            assert m["workloads"] == [CELL] and m["moves"] == "tokens_per_s"


def test_rehearsal_of_the_cell_with_the_toy_model(capsys):
    """The normal path: `edl train` on the local backend, the cell's
    traffic, the toy model; `correct`, and the routed layers' statistics,
    the two new counters among them, one event a fence."""
    cell = tiny_lfm2_cell()
    run = h.run_module()
    seen = {}
    read_metrics = run.read_metrics

    def keep(cell_, view, metrics):
        seen["run"] = view
        return read_metrics(cell_, view, metrics)

    run.read_metrics = keep
    rc = run.run_cell(cell, h.run_args(cell, 2**31 + 9, 3.0),
                      expect_platform="cpu")
    out = capsys.readouterr().out.strip().splitlines()
    assert rc == 0, out[-3:]
    result = json.loads(out[-1])
    assert result["correct"] is True and result["failed"] == 0, out
    view = seen["run"]
    events = view.events_of("model_stats", "worker")
    assert events
    assert all(e["step"] % cell.traffic["log_loss_steps"] == 0
               for e in events)
    tokens = cell.traffic["minibatch"] * cell.config["record_tokens"]
    assert events[0]["moe_assignments"] == 3 * tokens * 2
    assert events[0]["moe_block_rows_real"] == \
        events[0]["moe_assignments_held"]
    read = {n: h.cell_mod.load_module("metrics", n).read
            for n in NEW_READERS + ("moe_held_share_pct",)}
    fill = read["moe_block_fill_pct"](view)
    assert 0 < fill <= 100
    assert fill == pytest.approx(
        100 * sum(e["moe_block_rows_real"] for e in view.events_of(
            "model_stats", "worker", since=view.t0, until=view.t1))
        / sum(e["moe_block_rows_run"] for e in view.events_of(
            "model_stats", "worker", since=view.t0, until=view.t1)))
    assert 0 < read["moe_held_share_pct"](view) < 100
    # No trace in this run: the device-trace readers find nothing to read
    # and say so with None.
    assert read["shortconv_time_pct"](view) is None
    assert read["moe_swiglu_time_pct"](view) is None


def test_the_new_readers_find_nothing_in_another_models_run():
    """A program without the counters, a configuration of another model
    (the parent's cells): None, not an exception."""
    for other in ("lm_flagship.steady",
                  "nemotron_twotower_30b_a3b.steady_mb2"):
        cell = h.cell_mod.Cell(other)

        class View:
            t0, t1, trace = 10.0, 50.0, {"busy_s": 1.0, "devices": {"d": {}}}
            config, traffic = cell.config, cell.traffic
            device = {"kind": "TPU v5 lite", "count": 1}
            _raw_device_events = {"d": [(CHIP_LINES["grouped"][0], 0., 9.)]}

            def events_of(self, kinds, role_prefix=None, since=None,
                          until=None):
                return []

            def record_rate(self):
                return 5.0

        for name in NEW_READERS:
            assert h.cell_mod.load_module(
                "metrics", name).read(View()) is None, (other, name)


# HLO lines in the form the chip's profiler names `XLA Ops` events
# (tests/benchmark/test_benchmark_nemotron_h.py has the hybrid's, read from
# a chip trace), at this cell's shapes, cut after the first operands.
CHIP_LINES = {
    "shortconv": [
        "%fusion.31 = bf16[2,8192,6144]{2,1,0:T(8,128)(2,1)} fusion("
        "bf16[2,8192,2048]{2,1,0} %remat.4, f32[2048,6144]{1,0} %copy.7), "
        "kind=kOutput",
        "%fusion.40 = bf16[2,8192,2048]{2,1,0:T(8,128)(2,1)} fusion("
        "bf16[2,8192,6144]{2,1,0} %fusion.31, f32[3,2048]{1,0} %p), "
        "kind=kLoop",
        "%fusion.77 = f32[2048,6144]{1,0:T(8,128)} fusion("
        "bf16[16384,2048]{1,0} %bitcast.3, bf16[16384,6144]{1,0} "
        "%bitcast.9), kind=kOutput",
    ],
    "routing": [
        "%sort.3 = (f32[16384,64]{1,0}, s32[16384,64]{1,0}) sort("
        "f32[16384,64]{1,0} %a, s32[16384,64]{1,0} %iota), dimensions={1}",
        "%sort.9 = (s32[65536]{0}, s32[65536]{0}) sort(s32[65536]{0} %l, "
        "s32[65536]{0} %i), dimensions={0}",
    ],
    "grouped": [
        "%while.100 = (s32[]{:T(128)}, f32[16384,2048]{1,0:T(8,128)}, "
        "bf16[8,2048,3072]{2,1,0:T(8,128)(2,1)}, bf16[8,1536,2048]{2,1,0}, "
        "f32[66688]{0:T(1024)}) while(%tuple.5), condition=%cond, body=%b",
    ],
    "neither": [
        "%fusion.958 = bf16[2,8192,11776]{2,1,0:T(8,128)(2,1)} fusion("
        "bf16[2,8192,2048]{2,1,0} %remat2.208, f32[2048,11776]{1,0} %w1)",
        "%flash_fwd.3 = (bf16[64,8192,64]{2,1,0}, f32[64,8192,128]{2,1,0})"
        " custom-call(bf16[64,8192,64]{2,1,0} %bitcast.174), "
        "custom_call_target=\"tpu_custom_call\"",
        "%fusion.12 = bf16[2,8192,2048]{2,1,0} fusion(bf16[2,8192,2048]"
        "{2,1,0} %x, f32[2048,2048]{1,0} %out_proj), kind=kOutput",
        # The optimizer's update of the expert weights is not the layer's.
        "%fusion.918 = (f32[8,2048,3072]{2,1,0}, f32[8,2048,3072]{2,1,0}, "
        "f32[8,2048,3072]{2,1,0}) fusion(f32[8,2048,3072]{2,1,0} %w, "
        "f32[8,2048,3072]{2,1,0} %opt_state_0__nu__layers_1__w_gate_up)",
    ],
}


def cell_view():
    cell = h.cell_mod.Cell(CELL)

    class View:
        config, traffic = cell.config, cell.traffic

    return View


@pytest.mark.parametrize("kind", sorted(CHIP_LINES))
def test_lfm2_ops_are_told_by_the_configurations_shapes(ops, kind):
    matches = h.cell_mod.load_module("metrics", "_model_ops").matches
    z = ops.sizes(cell_view()())
    assert (z["tokens"], z["assignments"], z["held"], z["block"]) == (
        16384, 65536, 8, cell_view().config["model"]["expert_block_rows"])
    for line in CHIP_LINES[kind]:
        told = {
            "shortconv": matches(line, (ops.shortconv_shape,), z),
            "routing": matches(line, (ops.routing_shape,), z),
            "grouped": matches(line, (ops.grouped_shape,), z),
        }
        if kind == "grouped":
            # The backward loop also carries the gates' gradient in sorted
            # order, an array over the padded assignments: routing's shape.
            # The one share that reads both takes their union.
            told.pop("routing")
        assert told == {k: k == kind for k in told}, line
    padded = f"%sort.1 = s32[{65536 + z['block']}]{{0}} sort(s32[] %x)"
    assert matches(padded, (ops.routing_shape,), z)


def test_a_share_counts_a_loop_and_its_body_once(ops):
    loop = CHIP_LINES["grouped"][0]
    body = "%fusion.2160 = f32[1024,3072]{1,0} fusion(bf16[8,2048,3072]" \
        "{2,1,0} %w, s32[] %e), kind=kOutput"

    class View(cell_view()):
        trace = {"busy_s": 1e-6, "devices": {"/device:TPU:0": {}}}
        _raw_device_events = {"/device:TPU:0": [
            (loop, 0.0, 400.0), (body, 100.0, 200.0), (body, 250.0, 300.0),
            (CHIP_LINES["neither"][0], 500.0, 900.0),
            (CHIP_LINES["shortconv"][0], 900.0, 1000.0)]}

    read = {n: h.cell_mod.load_module("metrics", n).read
            for n in NEW_READERS}
    assert read["moe_swiglu_time_pct"](View()) == pytest.approx(40.0)
    assert read["shortconv_time_pct"](View()) == pytest.approx(10.0)
    assert ops.share_of_busy_pct(View(), (lambda dims, z: False,)) is None


def test_block_fill_adds_up_the_windows_events():
    class View(cell_view()):
        t0, t1 = 10.0, 50.0

        def events_of(self, kinds, role_prefix=None, since=None,
                      until=None):
            assert (kinds, since, until) == ("model_stats", 10.0, 50.0)
            return [{"moe_block_rows_run": 55296.0,
                     "moe_block_rows_real": 49119.0}] * 3

    fill = h.cell_mod.load_module("metrics", "moe_block_fill_pct").read
    assert fill(View()) == pytest.approx(100 * 49119 / 55296)


def test_mfu_counts_the_cut_as_run_by_hand(ops):
    """Multiplying parameters a token, written out: five convolutions,
    two attention layers, the dense feed-forward, six routed layers at
    half an assignment a token on the held experts, the tied head."""
    z = ops.sizes(cell_view()())
    conv = 2048 * 6144 + 2048 * 2048
    attention = 2 * 2048 * 2048 + 2 * 2048 * 8 * 64
    dense = 3 * 2048 * 11776
    routed = 2048 * 64 + 0.5 * 3 * 2048 * 1536
    head = 2048 * 8192
    by_hand = 5 * conv + 2 * attention + dense + 6 * routed + head
    assert by_hand == 223084544
    assert ops.multiplying_params_per_token(z) == by_hand
    # Causal attention: 2 layers x (QK^T and PV, 2 x 2048 operations a
    # key) x 4096.5 keys a query on average x 3 (forward + backward).
    attention_flops = 2 * (2 * 2 * 2048 * 4096.5) * 3
    assert ops.train_flops_per_token(z) == 6 * by_hand + attention_flops
    assert ops.train_flops_per_token(z) == pytest.approx(1.54e9, rel=1e-3)

    class View(cell_view()):
        device = {"kind": "TPU v5 lite", "count": 1}

        def record_rate(self):
            return 6.0  # records of 8192 tokens a second

    mfu = h.cell_mod.load_module("metrics", "mfu_pct.lfm2").read(View())
    assert mfu == pytest.approx(
        100 * 6.0 * 8192 * (6 * by_hand + attention_flops) / 197e12)
    assert 0 < mfu < 100
