"""The Kanana 2 reference against the program at a tiny size on the CPU, the
fp8 control and the two planted faults, the configuration file against the
model-def module, the catalog's rule and the initialised tree, the work
functions by hand, the new readers on synthetic traces and events, and the
CPU rehearsal of the cell's traffic with the toy model through `edl
train`."""

import copy
import dataclasses
import json
import re

import numpy as np
import pytest

import bench_helpers as h

CONFIG = "kanana_2_30b_a3b"
CELL = "kanana_2_30b_a3b.steady_mla_s16384_mb1"
STEPS = [8, 16]
MINIBATCH = 1
NEW_READERS = ("mla_attn_time_pct", "mla_attn_roofline",
               "mla_proj_time_pct", "moe_time_pct.kanana",
               "moe_block_fill_pct.kanana", "mfu_pct.kanana")


def tiny_config():
    with open(h.os.path.join(
            h.REPO, "tests", "benchmark", "tiny_kanana2.json")) as f:
        return json.load(f)


@pytest.fixture(scope="module")
def ref():
    return h.load_file(
        h.os.path.join(h.BENCH, "references", "kanana_moe.py"),
        "edlbench_ref_kanana_moe")


@pytest.fixture(scope="module")
def model_def():
    from elasticdl_tpu.common.model_utils import load_module

    return load_module(h.os.path.join(h.REPO, tiny_config()["model_def"]))


@pytest.fixture(scope="module")
def ops():
    return h.cell_mod.load_module("metrics", "_kanana_ops")


def built(config):
    """A model configuration as a configuration file states one."""
    return {k: list(v) if isinstance(v, tuple) else v
            for k, v in dataclasses.asdict(config).items()}


def test_the_tiny_configuration_file_states_the_tiny_model(model_def):
    cfg = tiny_config()
    got = built(model_def.CONFIG)
    for key, value in cfg["model"].items():
        assert got[key] == value, key
    m = cfg["model"]
    assert m["qk_nope_head_dim"] + m["qk_rope_head_dim"] != m["v_head_dim"]
    assert 0 < m["first_k_dense_replace"] < m["num_hidden_layers"]
    assert cfg["data"]["vocab"] == m["vocab_size"]
    assert cfg["data"]["seq_len"] == cfg["record_tokens"]


def test_the_cut_configuration_file_states_the_model_def():
    """benchmark/configs/kanana_2_30b_a3b.json against the model-def module
    `edl train` runs, against the catalog's rule (every key the public
    config's own but the three reduced) and against the initialised tree's
    size."""
    import jax
    import jax.numpy as jnp

    from elasticdl_tpu.models.kanana import kanana_2_30b_a3b_cut as m

    cfg = h.cell_mod.Cell(CELL).config
    assert cfg["model_def"] == m.__name__
    got = built(m.cut_config())
    skip = {"param_dtype", "parameters", "remat_reason", "kept_layers",
            "expert_block_rows_reason"}
    for key, value in cfg["model"].items():
        if key not in skip:
            assert got[key] == value, key
    assert cfg["model"]["kept_layers"] == list(m.KEEP_LAYERS)
    assert cfg["model"]["remat_layers"] == list(m.REMAT_LAYERS)
    assert cfg["model"]["expert_block_rows"] == m.EXPERT_BLOCK_ROWS
    public = json.loads(json.dumps(m.PUBLIC_CONFIG))
    differs = {k for k, v in public.items() if cfg.get(k, "absent") != v}
    assert differs == set(cfg["reduced"]) == {
        "num_hidden_layers", "n_routed_experts", "vocab_size"}
    for key in cfg["reduced"]:
        assert cfg["published"][key] == public[key]
    for width in ("hidden_size", "num_attention_heads", "qk_nope_head_dim",
                  "qk_rope_head_dim", "v_head_dim", "kv_lora_rank",
                  "q_lora_rank", "intermediate_size",
                  "moe_intermediate_size", "num_experts_per_tok",
                  "n_shared_experts", "routed_scaling_factor", "rope_theta",
                  "rope_interleave", "rope_scaling", "rms_norm_eps",
                  "n_group", "topk_group", "norm_topk_prob",
                  "scoring_func", "first_k_dense_replace"):
        assert cfg["model"][width] == public[width], width
    assert cfg["model"]["n_routed_experts"] == public[
        "n_routed_experts"] == 128
    assert (cfg["qk_nope_head_dim"], cfg["qk_rope_head_dim"],
            cfg["v_head_dim"], cfg["qk_head_dim"]) == (128, 64, 128, 192)
    assert cfg["num_hidden_layers"] == cfg["model"]["num_hidden_layers"] == 6
    assert cfg["model"]["kept_layers"] == [0, 1, 2, 3, 4, 5]
    assert cfg["n_routed_experts"] == cfg["model"]["experts_held"][1] == 16
    assert cfg["vocab_size"] == cfg["model"]["vocab_size"] == 128256 // 8
    assert cfg["data"]["vocab"] == cfg["vocab_size"] == m.VOCAB_ROWS == 16032
    assert cfg["data"]["seq_len"] == cfg["record_tokens"] == 16384
    assert cfg["record_tokens"] <= public["max_position_embeddings"]
    for key in ("deployment", "cut", "assumed", "departures", "published"):
        assert cfg[key], key
    for key in ("initializer", "latent_norm_dtype", "v_padding",
                "rope_layout", "expert_bias", "optimizer"):
        assert cfg["assumed"][key], key
    for key in ("depth", "parameters", "distorts"):
        assert cfg["cut"][key], key
    assert "8 chips share each layer" in cfg["deployment"]
    row = jnp.zeros((1, 8), jnp.int32)
    shapes = jax.eval_shape(
        lambda rng, row: m.custom_model().init(
            {"params": rng}, row, training=False),
        jax.random.PRNGKey(0), row)
    counted = sum(int(np.prod(leaf.shape))
                  for leaf in jax.tree_util.tree_leaves(shapes["params"]))
    # ISSUE 55's arithmetic.
    attention = 2048 * 6144 + 2048 * 576 + 512 + 512 * 8192 + 4096 * 2048
    outside = attention + 4096 + 128 * 2048 + 3 * 2048 * 1536
    routed = outside + 16 * 3 * 2048 * 768
    dense = attention + 4096 + 3 * 2048 * 6144
    by_hand = 5 * routed + dense + 2 * 16032 * 2048 + 2048
    assert (attention, outside, routed, dense) == (
        26_345_984, 36_049_408, 111_546_880, 64_098_816)
    assert counted == cfg["model"]["parameters"] == by_hand == 687_502_336
    assert "687,502,336" in cfg["cut"]["parameters"]
    ref = cfg["reference"]
    assert 0 < ref["loss_mean_limit"] <= ref["loss_abs_limit"]
    assert "rope_off" in ref["control"] and "scale_128" in ref["control"]


def test_the_reference_imports_nothing_of_the_programs_layers():
    with open(h.os.path.join(h.BENCH, "references", "kanana_moe.py")) as f:
        source = f.read()
    imported = set(re.findall(r"from (elasticdl_tpu[\w.]*) import", source))
    assert imported == {"elasticdl_tpu.common.model_utils",
                        "elasticdl_tpu.common.compile_cache"}
    assert "import flax" not in source and "import optax" not in source
    assert "rotary(" not in source and "flash_attention" not in source
    assert 'default_matmul_precision("highest")' in source


# ---------- reference against program ----------


def program_losses(seed, cfg, model_def):
    from elasticdl_tpu.worker.trainer import LocalTrainer

    datagen = h.cell_mod.load_module("datagen", cfg["datagen"])
    trainer = LocalTrainer(model_def.custom_model(), model_def.loss,
                           model_def.optimizer(), seed=seed)
    out, stats = {}, None
    for k, (x, y) in enumerate(datagen.batches(
            0, max(STEPS), MINIBATCH, seed, cfg["data"])):
        if k == 0:
            trainer.init_variables_if_needed(x)
        _, _, loss = trainer.train_minibatch(x, y)
        stats = trainer.last_step_stats
        if k + 1 in STEPS:
            out[k + 1] = float(loss)
    return out, stats


def limits(cfg):
    return (cfg["reference"]["loss_abs_limit"],
            cfg["reference"]["loss_mean_limit"])


@pytest.mark.parametrize("seed", [104, 7, 2**31 + 11])
def test_program_passes_and_the_controls_fail(ref, model_def, seed):
    """The toy model through the trainer against the reference's own Adam:
    correct; the fp8 control and each planted fault move the loss past the
    limits."""
    cfg = tiny_config()
    compare = h.run_module().compare_losses
    want = ref.losses(cfg, seed, MINIBATCH, STEPS, "float32")
    got, stats = program_losses(seed, cfg, model_def)
    rows, mean, ok = compare(got, want, *limits(cfg))
    assert ok, (rows, mean)
    # Three routed layers, two experts a token.
    assert float(stats["moe_assignments"]) == 3 * cfg["data"]["seq_len"] * 2
    control = ref.losses(cfg, seed, MINIBATCH, STEPS, "fp8")
    rows, mean, ok = compare(control, want, *limits(cfg))
    assert not ok, (rows, mean)
    for fault in ref.FAULTS:
        planted = ref.losses(cfg, seed, MINIBATCH, STEPS, "float32", fault)
        rows, mean, ok = compare(planted, want, *limits(cfg))
        assert not ok, (fault, rows, mean)


# ---------- the cell and its readers ----------


def tiny_kanana_cell():
    """The committed cell's traffic and metrics over the toy model."""
    m = copy.deepcopy(h.manifest())
    like = next(w for w in m["workloads"] if w["name"] == CELL)
    name = "tiny_kanana2.steady_mla_s16384_mb1"
    m["configs"] = [{"name": "tiny_kanana2", "source": "toy", "reduced": [],
                     "why": "toy",
                     "file": "tests/benchmark/tiny_kanana2.json"}]
    m["workloads"] = [dict(like, name=name, config="tiny_kanana2")]
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
    assert cell.chips == 1 and cell.traffic["minibatch"] == 1
    assert cell.traffic["strategy"] == "AllreduceStrategy"
    assert (cell.traffic["workers"], cell.traffic["ps_shards"]) == (1, 0)
    assert (cell.traffic["records_per_task"], cell.traffic["log_loss_steps"],
            cell.traffic["warmup_records"]) == (8, 8, 16)
    assert cell.traffic["train_args"] == ["--no_shuffle_shards"]
    assert cell.entry["traffic"] == cell.traffic["name"] == \
        "steady_mla_s16384_mb1"
    assert set(cell.traffic["compare_steps"]) <= {8, 16, 24, 32}
    reported = {m["name"] for m in cell.per_layer}
    assert set(NEW_READERS) <= reported
    assert {"moe_held_share_pct", "moe_held_load_max_over_mean",
            "device_idle_pct.lm", "idle_input_pct.lm", "idle_task_pct.lm",
            "idle_trainer_pct.lm", "idle_unattributed_pct.lm",
            "task_wait_pct.lm", "input_wait_pct.lm", "window_compiles.lm",
            "warmup_s", "launch_s", "step_load_s", "chip_open_s",
            "host_stall_pct.lm"} <= reported
    # `_step_intervals.py` wants 20 intervals after the trace is written:
    # at 1.05 s a step a traced 40 s window holds fewer, so the readers
    # find nothing to read here and the cell is on none of their lists.
    assert not {"step_ms_p50.lm", "step_ms_p90.lm",
                "step_ms_max.lm"} & reported
    # The causal kernels' readers know a call by operand counts and reckon
    # one head width: a call at 192 / 128 would read wrong. Readers of other
    # models' keys are not given this cell.
    assert not {"flash_roofline", "flash_time_pct", "moe_block_fill_pct",
                "mfu_pct", "mfu_pct.lfm2", "mfu_pct.sdar", "mfu_pct.granite",
                "mfu_pct.mellum2", "moe_time_pct", "moe_time_pct.sdar",
                "moe_time_pct.mellum2", "moe_swiglu_time_pct",
                "bd_attn_roofline", "band_attn_roofline", "ssd_time_pct",
                "full_attn_roofline.mellum2", "shortconv_time_pct",
                "allreduce_exposed_pct"} & reported
    assert {m["name"] for m in cell.end_to_end} == {
        "setup_s", "tokens_per_s"}
    for m in h.manifest()["per_layer"]:
        if m["name"] in NEW_READERS:
            assert m["workloads"] == [CELL] and m["moves"] == "tokens_per_s"
    cells = h.manifest()["workloads"]
    assert sum(w["chips"] == 4 for w in cells) <= max(1, len(cells) // 4)
    assert "attention over share" in cell.entry["why"]


def test_the_manifests_entries_keep_its_form(monkeypatch):
    """`test_benchmark_granite.py`'s rules for the entries a model_config
    PR appends (texts within 200 printable characters, names and units in
    their characters, just the keys an entry may have, a PR's entries after
    those of the PRs before it), run for this PR's; no position is
    pinned."""
    import test_benchmark_granite as granite

    monkeypatch.setitem(granite.ADDED, CONFIG, (CELL, NEW_READERS))
    granite.test_the_manifests_entries_keep_its_form(CONFIG)
    manifest = h.manifest()
    config, = (c for c in manifest["configs"] if c["name"] == CONFIG)
    assert "layers 0-5 of 48, 16 of 128 experts, 1/8 vocabulary, 1 of 8 " \
        "chips a layer" in config["source"]
    assert config["source"].startswith(
        h.cell_mod.Cell(CELL).config["source"])
    assert config["reduced"] == h.cell_mod.Cell(CELL).config["reduced"]
    layers = {m["name"]: m["layer"] for m in manifest["per_layer"]}
    known = {m["layer"] for m in manifest["per_layer"]
             if m["name"] not in NEW_READERS}
    assert {layers[name] for name in NEW_READERS} <= known


def test_rehearsal_of_the_cell_with_the_toy_model(capsys):
    """The normal path at minibatch 1: `edl train` on the local backend,
    the cell's traffic, the toy model; `correct`, and the step's
    statistics, one event a fence. The window is 10 s (a 3 s window beside
    five other xdist workers is the D9 family's: the job's fenced steps
    have to fall inside it; at 6 s this case failed once in a whole run
    on a loaded machine and passed alone)."""
    cell = tiny_kanana_cell()
    run = h.run_module()
    seen = {}
    read_metrics = run.read_metrics

    def keep(cell_, view, metrics):
        seen["run"] = view
        return read_metrics(cell_, view, metrics)

    run.read_metrics = keep
    rc = run.run_cell(cell, h.run_args(cell, 2**31 + 9, 10.0),
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
    length = cell.config["record_tokens"]
    assert events[0]["moe_assignments"] == 3 * length * 2
    read = {n: h.cell_mod.load_module("metrics", n).read
            for n in NEW_READERS + ("moe_held_share_pct",)}
    assert 0 < read["moe_held_share_pct"](view) < 100
    # No trace in this run: the device-trace readers find nothing to read
    # and say so with None.
    for name in ("mla_attn_time_pct", "mla_attn_roofline",
                 "mla_proj_time_pct", "moe_time_pct.kanana"):
        assert read[name](view) is None
    block_fill = read["moe_block_fill_pct.kanana"](view)
    assert block_fill == pytest.approx(
        100 * events[0]["moe_block_rows_real"]
        / events[0]["moe_block_rows_run"])
    assert 0 < block_fill <= 100
    # tokens/s x FLOP a token over a peak the CPU is not in the table of.
    view.device = dict(view.device, kind="TPU v5 lite")
    assert read["mfu_pct.kanana"](view) > 0


def compact(line):
    from lib import trace

    return trace.compact(line)


# HLO lines in the form the chip's profiler names `XLA Ops` events, at this
# cell's shapes, cut after the first operands. The kernels' instruction
# names are what the compiler gave them (AOT for the described v5e).
CHIP_LINES = {
    "mla_fwd": [
        "%jvp_mla_flash_fwd_.1 = (bf16[32,16384,128]{2,1,0}, "
        "f32[32,16384,128]{2,1,0}) custom-call(bf16[32,16384,192]{2,1,0} "
        "%bitcast.174, bf16[32,16384,192]{2,1,0} %b, bf16[32,16384,128]"
        "{2,1,0} %c), custom_call_target=\"tpu_custom_call\"",
        "%checkpoint_mla_flash_fwd.3 = (bf16[32,16384,128]{2,1,0}, "
        "f32[32,16384,128]{2,1,0}) custom-call(bf16[32,16384,192]{2,1,0} "
        "%bitcast.174), custom_call_target=\"tpu_custom_call\"",
    ],
    "mla_bwd": [
        "%transpose_jvp_mla_flash_bwd__.1 = (bf16[32,16384,192]{2,1,0}, "
        "bf16[32,16384,192]{2,1,0}, bf16[32,16384,128]{2,1,0}) custom-call("
        "bf16[32,16384,192]{2,1,0} %q), "
        "custom_call_target=\"tpu_custom_call\"",
    ],
    "causal_of_another_model": [
        "%jvp_flash_fwd_.3 = (bf16[32,16384,128]{2,1,0}, "
        "f32[32,16384,128]{2,1,0}) custom-call(bf16[32,16384,128]{2,1,0} "
        "%bitcast.174), custom_call_target=\"tpu_custom_call\"",
    ],
    "projection": [
        "%fusion.41 = bf16[1,16384,32,192]{3,2,1,0} fusion(bf16[1,16384,2048]"
        "{2,1,0} %x, f32[2048,32,192]{2,1,0} %q_proj), kind=kOutput",
        "%fusion.42 = bf16[1,16384,576]{2,1,0} fusion(bf16[1,16384,2048]"
        "{2,1,0} %x, f32[2048,576]{1,0} %kv_a), kind=kOutput",
        "%fusion.43 = bf16[1,16384,32,256]{3,2,1,0} fusion(bf16[1,16384,512]"
        "{2,1,0} %c, f32[512,32,256]{2,1,0} %kv_b), kind=kOutput",
        "%fusion.44 = bf16[1,32,16384,192]{3,2,1,0} fusion(f32[1,16384,32,64]"
        "{3,2,1,0} %q_rope, bf16[1,16384,32,128]{3,2,1,0} %q_nope)",
        "%fusion.45 = f32[1,16384,1,64]{3,2,1,0} fusion(bf16[16384,64]{1,0} "
        "%k_rope), kind=kLoop",
        "%fusion.46 = bf16[1,16384,2048]{2,1,0} fusion(bf16[1,16384,4096]"
        "{2,1,0} %o, f32[4096,2048]{1,0} %o_proj), kind=kOutput",
    ],
    "routing": [
        "%sort.3 = (f32[16384,128]{1,0}, s32[16384,128]{1,0}) sort("
        "f32[16384,128]{1,0} %a, s32[16384,128]{1,0} %iota), dimensions={1}",
        "%sort.9 = (s32[98304]{0}, s32[98304]{0}) sort(s32[98304]{0} %l, "
        "s32[98304]{0} %i), dimensions={0}",
    ],
    "grouped": [
        "%while.100 = (s32[]{:T(128)}, f32[16384,16,128]{2,1,0:T(8,128)}, "
        "bf16[16,2048,1536]{2,1,0:T(8,128)(2,1)}, bf16[16,768,2048]{2,1,0}"
        ") while(%tuple.5), condition=%cond, body=%b",
    ],
    "shared": [
        "%fusion.77 = bf16[16384,3072]{1,0} fusion(bf16[16384,2048]{1,0} "
        "%x, f32[2048,3072]{1,0} %shared_gate_up), kind=kOutput",
        "%fusion.78 = f32[16384,2048]{1,0} fusion(bf16[16384,1536]{1,0} "
        "%h, f32[1536,2048]{1,0} %shared_down), kind=kOutput",
    ],
    "neither": [
        # The dense layer's MLP, the head, and the optimizer's update of
        # the latent attention's own weights.
        "%fusion.12 = bf16[1,16384,6144]{2,1,0} fusion(bf16[1,16384,2048]"
        "{2,1,0} %x, f32[2048,6144]{1,0} %gate_proj), kind=kOutput",
        "%fusion.13 = f32[1,16384,16032]{2,1,0} fusion(bf16[1,16384,2048]"
        "{2,1,0} %x, f32[2048,16032]{1,0} %lm_head), kind=kOutput",
        "%fusion.918 = (f32[2048,32,192]{2,1,0}, f32[2048,32,192]{2,1,0}, "
        "f32[2048,32,192]{2,1,0}) fusion(f32[2048,32,192]{2,1,0} %w, "
        "f32[2048,32,192]{2,1,0} %opt_state_0__nu__layers_1__q_proj)",
    ],
}


def cell_view():
    cell = h.cell_mod.Cell(CELL)

    class View:
        config, traffic = cell.config, cell.traffic
        device = {"kind": "TPU v5 lite", "count": 1}

    return View


@pytest.mark.parametrize("kind", sorted(CHIP_LINES))
def test_kanana_ops_are_told_by_name_and_by_the_configurations_shapes(
        ops, kind):
    matches = h.cell_mod.load_module("metrics", "_model_ops").matches
    z = ops.sizes(cell_view()())
    assert (z["rows"], z["assignments"], z["held"], z["block"]) == (
        16384, 98304, 16,
        cell_view().config["model"]["expert_block_rows"])
    assert (z["dk"], z["dv"], z["rank"], z["shared"], z["layers"],
            z["routed_layers"]) == (192, 128, 512, 1536, 6, 5)
    for line in CHIP_LINES[kind]:
        told = {
            "projection": matches(line, (ops.projection_shape,), z)
            and not ops.is_flash_call(line),
            "routing": matches(line, (ops.routing_shape,), z),
            "grouped": matches(line, (ops.grouped_shape,), z),
            "shared": matches(line, (ops.shared_shape,), z),
        }
        kernel = ops.classify(compact(line))
        told["mla_fwd"] = kernel is not None and kernel[0] == ops.FWD
        told["mla_bwd"] = kernel is not None and kernel[0] == ops.BWD
        assert told == {k: k == kind for k in told}, line
        if kernel:
            assert kernel[1:] == (32, 16384, 2)


def test_attention_readers_on_a_made_up_trace(ops, capsys):
    """Two steps of one layer under remat (forward, its rematerialised
    twin, backward), a fusion and another model's causal call that are
    neither's. The time share counts every call; the roofline's needed work
    counts the forward once, the causal half at 192 + 128 a score."""
    line = {k: compact(v[0]) for k, v in CHIP_LINES.items()}
    ms = 1e6
    events, at = [], 0.0
    for _ in range(2):
        for name, dur in ((line["mla_fwd"], 22 * ms),
                          (line["neither"], 20 * ms),
                          (line["mla_fwd"], 22 * ms),
                          (line["mla_bwd"], 66 * ms),
                          (line["causal_of_another_model"], 10 * ms)):
            events.append([name, at, dur])
            at += dur

    class View(cell_view()):
        trace = {"busy_s": at / 1e9,
                 "devices": {"/device:TPU:0": {"events": events}}}

    read = {n: h.cell_mod.load_module("metrics", n).read
            for n in NEW_READERS}
    assert read["mla_attn_time_pct"](View()) == pytest.approx(
        100 * 110 / 140)
    causal = 16384 * 16385 // 2
    flops_fwd = 2 * 32 * causal * (192 + 128)
    assert flops_fwd == pytest.approx(2.75e12, rel=2e-3)
    assert ops.kernel_flops(ops.FWD, 32, 16384, 192, 128) == flops_fwd
    assert ops.kernel_flops(ops.BWD, 32, 16384, 192, 128) == 2 * flops_fwd
    # Compute binds: 134 M scores x 320 against 0.5 GB of operands.
    assert ops.kernel_bytes(ops.FWD, 32, 16384, 192, 128, 2) == (
        32 * 16384 * (640 * 2 + 4))
    assert ops.kernel_bytes(ops.BWD, 32, 16384, 192, 128, 2) == (
        32 * 16384 * (1152 * 2 + 8))
    assert ops.kernel_bytes(ops.BWD, 32, 16384, 192, 128, 2) / 819e9 < \
        flops_fwd / 197e12
    least = 2 * 3 * flops_fwd / 197e12
    roof = read["mla_attn_roofline"](View())
    assert roof == pytest.approx(100 * least / (2 * 110e-3))
    assert 0 < roof < 100
    said = json.loads(capsys.readouterr().out.strip().splitlines()[-1])
    assert said["reader"] == "mla_attn_roofline"
    assert said["calls"] == 6 and said["calls_needed"] == 4
    assert said["binding_roof_by_call"] == {"compute": 4}
    # The run tiles hold 136 x 1024 x 1024 scores a batch*head where the
    # mask lets 134,225,920 through: the reader counts the latter.
    assert causal == 134_225_920 < 136 * 1024 * 1024


def test_the_shares_count_a_loop_and_its_body_once_and_not_the_kernels(ops):
    loop = CHIP_LINES["grouped"][0]
    body = "%fusion.2160 = f32[896,1536]{1,0} fusion(bf16[16,2048,1536]" \
        "{2,1,0} %w, s32[] %e), kind=kOutput"

    class View(cell_view()):
        trace = {"busy_s": 2e-6, "devices": {"/device:TPU:0": {}}}
        _raw_device_events = {"/device:TPU:0": [
            (loop, 0.0, 400.0), (body, 100.0, 200.0), (body, 250.0, 300.0),
            (CHIP_LINES["neither"][2], 500.0, 900.0),
            (CHIP_LINES["routing"][0], 900.0, 1000.0),
            (CHIP_LINES["shared"][0], 1000.0, 1100.0),
            (CHIP_LINES["projection"][0], 1100.0, 1300.0),
            (CHIP_LINES["projection"][3], 1250.0, 1400.0),
            (CHIP_LINES["mla_fwd"][0], 1400.0, 2000.0)]}

    read = {n: h.cell_mod.load_module("metrics", n).read
            for n in ("moe_time_pct.kanana", "mla_proj_time_pct")}
    assert read["moe_time_pct.kanana"](View()) == pytest.approx(30.0)
    # The kernel's own call holds the projections' shapes and is left out.
    assert read["mla_proj_time_pct"](View()) == pytest.approx(15.0)
    assert ops.share_of_busy_pct(View(), (lambda dims, z: False,)) is None


def test_block_fill_adds_up_the_windows_events():
    class View(cell_view()):
        t0, t1 = 10.0, 50.0

        def events_of(self, kinds, role_prefix=None, since=None,
                      until=None):
            assert (kinds, since, until) == ("model_stats", 10.0, 50.0)
            return [{"moe_block_rows_real": 61234.0,
                     "moe_block_rows_run": 5 * 16 * 896.0}] * 3

    fill = h.cell_mod.load_module(
        "metrics", "moe_block_fill_pct.kanana").read
    assert fill(View()) == pytest.approx(100 * 61234 / 71680)
    assert fill(View()) == pytest.approx(85.4, abs=0.05)


def test_mfu_counts_the_cut_as_run_by_hand(ops):
    """Multiplying parameters a row, written out (ISSUE 55's count): the
    latent attention's four projections in six layers; the dense layer's
    MLP; in five routed layers the router, the shared experts and three
    quarters of an expert (6 a token x 16 / 128); the head; attention's
    needed scores a token: 8,192.5 in each of six layers at 192 + 128."""
    z = ops.sizes(cell_view()())
    attention = 2048 * 6144 + 2048 * 576 + 512 * 8192 + 4096 * 2048
    assert attention == 26_345_472 == ops.attention_params(z)
    routed = 2048 * 128 + 3 * 2048 * 1536 + 0.75 * 3 * 2048 * 768
    per_row = 6 * attention + 3 * 2048 * 6144 + 5 * routed
    assert per_row == ops.multiplying_params_per_row(z)
    assert per_row == pytest.approx(262.0e6, rel=1e-3)
    head = 2048 * 16032
    products = 6 * (per_row + head)
    attention_flops = 6 * 32 * 320 * 6 * 16385 / 2
    assert ops.train_flops_per_token(z) == pytest.approx(
        products + attention_flops, rel=1e-12)
    # The new mechanism's scores are 63% of the needed work, its
    # projections another 20%.
    total = products + attention_flops
    assert attention_flops / total == pytest.approx(0.631, abs=0.002)
    assert 6 * 6 * attention / total == pytest.approx(0.198, abs=0.002)
    assert total * 16384 == pytest.approx(7.85e13, rel=2e-3)

    class View(cell_view()):
        def record_rate(self):
            return 0.8  # records of 16,384 tokens a second

    mfu = h.cell_mod.load_module("metrics", "mfu_pct.kanana").read(View())
    assert mfu == pytest.approx(100 * 0.8 * 16384 * total / 197e12)
    assert 0 < mfu < 100


def test_the_new_readers_find_nothing_in_another_models_run():
    """A program without the counters or the kernels' names, a
    configuration of another model (the parent's cells): None, not an
    exception."""
    for other in ("lm_flagship.steady", "lfm2_24b_a2b.steady_s8192_mb2",
                  "sdar_30b_a3b.steady_s8192_mb1",
                  "granite_4_0_h_micro.steady_causal_s8192_mb1",
                  "mellum2_12b_a2_5b.steady_s16384_mb1"):
        cell = h.cell_mod.Cell(other)

        class View:
            t0, t1 = 10.0, 50.0
            trace = {"busy_s": 1.0, "devices": {"d": {"events": [
                [compact(CHIP_LINES["mla_fwd"][0]), 0.0, 9.0]]}}}
            config, traffic = cell.config, cell.traffic
            device = {"kind": "TPU v5 lite", "count": 1}
            _raw_device_events = {"d": [(CHIP_LINES["grouped"][0], 0., 9.)]}

            def events_of(self, kinds, role_prefix=None, since=None,
                          until=None):
                return [{"moe_block_rows_real": 1.0,
                         "moe_block_rows_run": 2.0}]

            def record_rate(self):
                return 5.0

        for name in NEW_READERS:
            assert h.cell_mod.load_module(
                "metrics", name).read(View()) is None, (other, name)


def test_a_program_without_the_kernels_gives_the_kernel_readers_nothing():
    """This cell's configuration over a program that has no `mla_` kernel
    and no such shapes (the parent's): the trace readers return None."""
    class View(cell_view()):
        trace = {"busy_s": 1.0, "devices": {"d": {"events": [
            [compact(CHIP_LINES["causal_of_another_model"][0]), 0.0, 9.0]]}}}
        _raw_device_events = {"d": [(CHIP_LINES["neither"][0], 0.0, 9.0)]}

    for name in ("mla_attn_time_pct", "mla_attn_roofline",
                 "mla_proj_time_pct", "moe_time_pct.kanana"):
        assert h.cell_mod.load_module(
            "metrics", name).read(View()) is None, name
