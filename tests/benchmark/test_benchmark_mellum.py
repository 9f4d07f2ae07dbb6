"""The Mellum 2 reference against the program at a tiny size on the CPU,
the fp8 control and the two planted faults, the configuration file against
the model-def module and the catalog's rule, the work functions against a
brute-force count of the dense mask, the new readers on synthetic traces
and events, and the CPU rehearsal of the cell's traffic with the toy model
through `edl train`."""

import copy
import dataclasses
import json
import re

import numpy as np
import pytest

import bench_helpers as h

CONFIG = "mellum2_12b_a2_5b"
CELL = "mellum2_12b_a2_5b.steady_s16384_mb1"
STEPS = [8, 16]
MINIBATCH = 1
NEW_READERS = ("band_attn_time_pct", "band_attn_roofline",
               "band_attn_tile_fill_pct", "full_attn_time_pct.mellum2",
               "moe_time_pct.mellum2", "mfu_pct.mellum2",
               "moe_block_fill_pct.mellum2", "full_attn_roofline.mellum2")
BAND, FULL = "sliding_attention", "full_attention"


def tiny_config():
    with open(h.os.path.join(
            h.REPO, "tests", "benchmark", "tiny_mellum2.json")) as f:
        return json.load(f)


@pytest.fixture(scope="module")
def ref():
    return h.load_file(
        h.os.path.join(h.BENCH, "references", "mellum_moe.py"),
        "edlbench_ref_mellum_moe")


@pytest.fixture(scope="module")
def model_def():
    from elasticdl_tpu.common.model_utils import load_module

    return load_module(h.os.path.join(h.REPO, tiny_config()["model_def"]))


@pytest.fixture(scope="module")
def ops():
    return h.cell_mod.load_module("metrics", "_mellum_ops")


def built(config):
    """A model configuration as a configuration file states one."""
    out = dataclasses.asdict(config)
    out["rope_parameters"] = {
        kind: config.rope(kind) for kind in sorted(set(config.layer_types))}
    return {k: list(v) if isinstance(v, tuple) else v
            for k, v in out.items()}


def test_the_tiny_configuration_file_states_the_tiny_model(model_def):
    cfg = tiny_config()
    got = built(model_def.CONFIG)
    for key, value in cfg["model"].items():
        assert got[key] == value, key
    assert cfg["model"]["layer_types"] == [BAND, BAND, BAND, FULL] * 2
    assert cfg["model"]["sliding_window"] < cfg["record_tokens"]
    assert cfg["data"]["vocab"] == cfg["model"]["vocab_size"]


def test_the_cut_configuration_file_states_the_model_def():
    """benchmark/configs/mellum2_12b_a2_5b.json against the model-def
    module `edl train` runs, against the catalog's rule (every key the
    public config's own but the three reduced, nested groups whole) and
    against the initialised tree's size."""
    import jax
    import jax.numpy as jnp

    from elasticdl_tpu.models.mellum import mellum2_12b_a2_5b_cut as m

    cfg = h.cell_mod.Cell(CELL).config
    assert cfg["model_def"] == m.__name__
    got = built(m.cut_config())
    skip = {"param_dtype", "parameters", "remat_reason", "kept_layers",
            "expert_block_rows_reason", "num_hidden_layers"}
    for key, value in cfg["model"].items():
        if key not in skip:
            assert got[key] == value, key
    assert cfg["model"]["kept_layers"] == list(m.KEEP_LAYERS)
    public = json.loads(json.dumps(m.PUBLIC_CONFIG))
    differs = {k for k, v in public.items() if cfg.get(k, "absent") != v}
    assert differs == set(cfg["reduced"]) == {
        "num_hidden_layers", "num_experts", "vocab_size"}
    for key in cfg["reduced"]:
        assert cfg["published"][key] == public[key]
    for width in ("hidden_size", "head_dim", "moe_intermediate_size",
                  "num_attention_heads", "num_key_value_heads",
                  "num_experts_per_tok", "sliding_window", "rms_norm_eps",
                  "rope_parameters"):
        assert cfg["model"][width] == public[width], width
    assert cfg["model"]["num_experts"] == public["num_experts"] == 64
    # head_dim is a key of its own: not hidden / heads.
    assert cfg["head_dim"] * cfg["num_attention_heads"] != cfg["hidden_size"]
    assert cfg["num_hidden_layers"] == cfg["model"]["num_hidden_layers"] == 4
    # One whole period of the published pattern, its first.
    assert cfg["model"]["layer_types"] == public["layer_types"][:4] == [
        BAND, BAND, BAND, FULL]
    assert public["layer_types"] == [BAND, BAND, BAND, FULL] * 7
    assert cfg["num_experts"] == cfg["model"]["experts_held"][1] == 16
    assert cfg["vocab_size"] == cfg["model"]["vocab_size"] == 98304 // 4
    assert cfg["data"]["vocab"] == cfg["vocab_size"]
    assert cfg["data"]["seq_len"] == cfg["record_tokens"] == 16384
    assert cfg["record_tokens"] == 16 * cfg["model"]["sliding_window"]
    for key in ("deployment", "cut", "assumed", "departures", "published"):
        assert cfg[key], key
    for key in ("qk_norm", "routing_order", "initializer", "optimizer",
                "not_built"):
        assert cfg["assumed"][key], key
    row = jnp.zeros((1, 8), jnp.int32)
    shapes = jax.eval_shape(
        lambda rng, row: m.custom_model().init(
            {"params": rng}, row, training=False),
        jax.random.PRNGKey(0), row)
    counted = sum(int(np.prod(leaf.shape))
                  for leaf in jax.tree_util.tree_leaves(shapes["params"]))
    # ISSUE 51's arithmetic: a layer outside its experts, 16 experts, four
    # layers, the embedding's and the head's slices, the last norm.
    outside = 2304 * 4096 + 2 * 2304 * 512 + 4096 * 2304 + 256 \
        + 64 * 2304 + 2 * 2304
    by_hand = 4 * (outside + 16 * 3 * 2304 * 896) + 2 * 24576 * 2304 + 2304
    assert outside == 21_385_984 and by_hand == 595_154_176
    assert counted == cfg["model"]["parameters"] == by_hand
    assert "595,154,176" in cfg["cut"]["parameters"]


def test_the_reference_imports_nothing_of_the_programs_layers():
    with open(h.os.path.join(h.BENCH, "references", "mellum_moe.py")) as f:
        source = f.read()
    imported = set(re.findall(r"from (elasticdl_tpu[\w.]*) import", source))
    assert imported == {"elasticdl_tpu.common.model_utils",
                        "elasticdl_tpu.common.compile_cache"}
    assert "import flax" not in source and "import optax" not in source
    assert "dense_mask" not in source and "yarn_inv_freq" not in source
    assert 'default_matmul_precision("highest")' in source


def test_the_references_tables_are_the_programs(ref):
    """Two codes, one table: the reference's YaRN and default tables (its
    own loop over the formulas) against the model module's, at the cut's
    `rope_parameters` and head."""
    from elasticdl_tpu.models.mellum import mellum_moe

    ropes = h.cell_mod.Cell(CELL).config["model"]["rope_parameters"]
    for kind in (BAND, FULL):
        want, want_scale = mellum_moe.rope_table(ropes[kind], 128)
        got, scale = ref.rope_table(ropes[kind], 128)
        np.testing.assert_array_equal(got.astype(np.float32), want)
        assert scale == (want_scale or 1.0)
    assert ref.rope_table(ropes[FULL], 128)[1] == 1.2772588722239782


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
    cfg = tiny_config()
    compare = h.run_module().compare_losses
    want = ref.losses(cfg, seed, MINIBATCH, STEPS, "float32")
    got, stats = program_losses(seed, cfg, model_def)
    rows, mean, ok = compare(got, want, *limits(cfg))
    assert ok, (rows, mean)
    # The step hands its counts back beside the loss: eight routed layers,
    # two experts a token; six windowed layers, four heads.
    length, window = cfg["data"]["seq_len"], cfg["model"]["sliding_window"]
    assert float(stats["moe_assignments"]) == 8 * length * 2
    band = window * (window + 1) // 2 + (length - window) * window
    assert float(stats["band_scores_needed"]) == 6 * 4 * band
    assert float(stats["band_scores_run"]) > float(
        stats["band_scores_needed"])
    control = ref.losses(cfg, seed, MINIBATCH, STEPS, "fp8")
    rows, mean, ok = compare(control, want, *limits(cfg))
    assert not ok, (rows, mean)
    for fault in ref.FAULTS:
        planted = ref.losses(cfg, seed, MINIBATCH, STEPS, "float32", fault)
        rows, mean, ok = compare(planted, want, *limits(cfg))
        assert not ok, (fault, rows, mean)


def test_the_tiny_models_logits_loss_and_gradients_against_the_reference(
        ref, model_def):
    """One record, seeded weights, float32 activations on the program's
    side: the logits, the loss and every parameter's gradient, over two
    periods of the pattern at a window a quarter of the record. The
    tolerances are float32's over eight layers (both sides compute in
    float32 at precision highest; what differs is the order of the sums:
    the program's fused softmax and grouped products against the plain
    loops) and would not pass bfloat16, which the last assertion shows:
    the same program at its stated bfloat16 misses the logits' tolerance
    by more than ten times."""
    import jax
    import jax.numpy as jnp

    from elasticdl_tpu.models.mellum import mellum_moe

    cfg = tiny_config()
    gen = h.cell_mod.load_module("datagen", cfg["datagen"])
    (tokens, labels), = gen.batches(3, 1, 1, 11, cfg["data"])
    config = dataclasses.replace(
        model_def.CONFIG, activation_dtype="float32")
    model = mellum_moe.custom_model(config)
    x, y = jnp.asarray(tokens), jnp.asarray(labels)
    variables = model.init({"params": jax.random.PRNGKey(5)}, x)
    params, buffers = variables["params"], variables["buffers"]
    # Larger weights than the initialiser's: logits that move.
    params = jax.tree_util.tree_map(
        lambda a: a * 4.0 if a.ndim > 1 else a, params)

    def program(p, m=model):
        out = m.apply({"params": p, "buffers": buffers}, x, training=True)
        return mellum_moe.loss(y, out), out["logits"]

    plain = ref.make_loss(cfg["model"], "float32")

    def reference(p):
        return plain(p, buffers, x[0], y[0])

    with jax.default_matmul_precision("highest"):
        (got, logits), got_grads = jax.value_and_grad(
            program, has_aux=True)(params)
        want, want_grads = jax.value_and_grad(reference)(params)
        want_logits = plain.logits(params, buffers, x[0])
        rounded = program(params, mellum_moe.custom_model(
            dataclasses.replace(config, activation_dtype="bfloat16")))[1]
    scale = float(jnp.max(jnp.abs(want_logits)))
    assert scale > 1.0
    np.testing.assert_allclose(
        np.asarray(logits[0]) / scale, np.asarray(want_logits) / scale,
        atol=2e-5)
    assert float(jnp.max(jnp.abs(rounded[0] - want_logits))) / scale > 2e-4
    assert float(got) == pytest.approx(float(want), rel=2e-6)
    flat_got = jax.tree_util.tree_leaves_with_path(got_grads)
    flat_want = jax.tree_util.tree_leaves(want_grads)
    assert len(flat_got) == len(flat_want) > 0
    for (path, a), b in zip(flat_got, flat_want):
        scale = float(jnp.max(jnp.abs(b))) or 1.0
        np.testing.assert_allclose(
            np.asarray(a) / scale, np.asarray(b) / scale, atol=2e-5,
            err_msg=jax.tree_util.keystr(path))


def test_each_planted_fault_moves_the_reference_logits(ref):
    """`window_unseen` and `yarn_off` are faults of the two mechanisms: each
    changes the tiny model's logits, from the first row the mechanism
    reaches (a row past the window; any row after the first)."""
    import jax
    import jax.numpy as jnp

    cfg = tiny_config()
    gen = h.cell_mod.load_module("datagen", cfg["datagen"])
    (tokens, _), = gen.batches(0, 1, 1, 5, cfg["data"])
    params, buffers = ref.initial_variables(cfg["model_def"], 5, tokens)
    params = jax.tree_util.tree_map(
        lambda a: a * 4.0 if a.ndim > 1 else a, params)
    x = jnp.asarray(tokens[0])
    want = ref.make_loss(cfg["model"], "float32").logits(params, buffers, x)
    window = cfg["model"]["sliding_window"]
    for fault, first in (("window_unseen", window), ("yarn_off", 1)):
        got = ref.make_loss(cfg["model"], "float32", fault).logits(
            params, buffers, x)
        moved = np.abs(np.asarray(got - want)).max(axis=-1)
        assert moved[:first].max() < 1e-5, fault
        assert moved[first:].max() > 1e-3, fault
    with pytest.raises(ValueError, match="unknown fault"):
        ref.make_loss(cfg["model"], "float32", "no_such")


# ---------- the work functions ----------


@pytest.mark.parametrize("rows,window", [(64, 8), (64, 64), (48, 16),
                                         (32, 48), (40, 1)])
def test_needed_scores_against_a_brute_force_count_of_the_dense_mask(
        ops, rows, window):
    seen = np.zeros((rows, rows), bool)
    for r in range(rows):
        for c in range(rows):
            seen[r, c] = c <= r and r - c < window
    assert ops.band_needed_scores(rows, window) == seen.sum()
    assert ops.causal_needed_scores(rows) == np.tril(
        np.ones((rows, rows), bool)).sum()
    for kernel, products in ((ops.BAND_FWD, 2), (ops.BAND_BWD, 4)):
        assert ops.kernel_flops(kernel, 3, rows, 16, window) == (
            products * 2 * 3 * seen.sum() * 16)
    for kernel, products in ((ops.FULL_FWD, 2), (ops.FULL_BWD, 4)):
        assert ops.kernel_flops(kernel, 3, rows, 16, window) == (
            products * 2 * 3 * (rows * (rows + 1) // 2) * 16)


def test_the_work_functions_count_what_the_program_counts(ops):
    from elasticdl_tpu.ops.flash_attention import band_scores

    assert ops.band_needed_scores(16384, 1024) == band_scores(
        16384, 1024)[0] == 16_253_440


# ---------- the cell and its readers ----------


def tiny_mellum_cell():
    """The committed cell's traffic and metrics over the toy model."""
    m = copy.deepcopy(h.manifest())
    like = next(w for w in m["workloads"] if w["name"] == CELL)
    name = "tiny_mellum2.steady_s16384_mb1"
    m["configs"] = [{"name": "tiny_mellum2", "source": "toy", "reduced": [],
                     "why": "toy",
                     "file": "tests/benchmark/tiny_mellum2.json"}]
    m["workloads"] = [dict(like, name=name, config="tiny_mellum2")]
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
    assert (cell.traffic["records_per_task"], cell.traffic["log_loss_steps"],
            cell.traffic["warmup_records"]) == (8, 8, 16)
    assert cell.traffic["train_args"] == ["--no_shuffle_shards"]
    assert cell.entry["traffic"] == cell.traffic["name"] == \
        "steady_s16384_mb1"
    reported = {m["name"] for m in cell.per_layer}
    assert set(NEW_READERS) <= reported
    assert {"moe_held_share_pct", "moe_held_load_max_over_mean",
            "device_idle_pct.lm", "idle_input_pct.lm", "window_compiles.lm",
            "step_ms_p50.lm", "warmup_s", "launch_s", "step_load_s",
            "chip_open_s", "host_stall_pct.lm"} <= reported
    # The causal kernels' readers know a call by operand counts and reckon
    # causal work: a band call would read as 8.3 times its work. Readers of
    # other models' keys are not given this cell; the LFM2 cell's test
    # pins `moe_block_fill_pct` to that cell alone, so this cell's fill is
    # `moe_block_fill_pct.mellum2`'s.
    assert not {"flash_roofline", "flash_time_pct", "moe_block_fill_pct",
                "mfu_pct", "mfu_pct.lfm2", "mfu_pct.sdar", "mfu_pct.granite",
                "moe_time_pct", "moe_time_pct.sdar", "moe_swiglu_time_pct",
                "bd_attn_roofline", "ssd_time_pct", "shortconv_time_pct",
                "allreduce_exposed_pct"} & reported
    assert {m["name"] for m in cell.end_to_end} == {
        "setup_s", "tokens_per_s"}
    for m in h.manifest()["per_layer"]:
        if m["name"] in NEW_READERS:
            assert m["workloads"] == [CELL] and m["moves"] == "tokens_per_s"
    cells = h.manifest()["workloads"]
    assert sum(w["chips"] == 4 for w in cells) <= max(1, len(cells) // 4)


def test_the_manifests_entries_keep_its_form(monkeypatch):
    """`test_benchmark_granite.py`'s rules for the entries a model_config
    PR appends (texts within 200 printable characters, names and units in
    their characters, just the keys an entry may have, a PR's entries after
    those of the PRs before it), run for this PR's."""
    import test_benchmark_granite as granite

    monkeypatch.setitem(granite.ADDED, CONFIG, (CELL, NEW_READERS))
    assert list(granite.ADDED)[-1] == CONFIG
    granite.test_the_manifests_entries_keep_its_form(CONFIG)
    manifest = h.manifest()
    config, = (c for c in manifest["configs"] if c["name"] == CONFIG)
    assert "layers 0-3 of 28, 16 of 64 experts, 1/4 vocabulary, 1 of 4 " \
        "chips a layer" in config["source"]
    assert config["reduced"] == h.cell_mod.Cell(CELL).config["reduced"]


def test_rehearsal_of_the_cell_with_the_toy_model(capsys):
    """The normal path at minibatch 1: `edl train` on the local backend,
    the cell's traffic, the toy model; `correct`, and the step's
    statistics, the three attention counters among them, one event a
    fence. The window is 6 s (a 3 s window beside five other xdist workers
    is the D9 family's: the job's fenced steps have to fall inside it)."""
    cell = tiny_mellum_cell()
    run = h.run_module()
    seen = {}
    read_metrics = run.read_metrics

    def keep(cell_, view, metrics):
        seen["run"] = view
        return read_metrics(cell_, view, metrics)

    run.read_metrics = keep
    rc = run.run_cell(cell, h.run_args(cell, 2**31 + 9, 6.0),
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
    window = cell.config["model"]["sliding_window"]
    band = window * (window + 1) // 2 + (length - window) * window
    assert events[0]["moe_assignments"] == 8 * length * 2
    assert events[0]["band_scores_needed"] == 6 * 4 * band
    read = {n: h.cell_mod.load_module("metrics", n).read
            for n in NEW_READERS + ("moe_held_share_pct",)}
    fill = read["band_attn_tile_fill_pct"](view)
    assert fill == pytest.approx(
        100 * events[0]["band_scores_needed"]
        / events[0]["band_scores_run"])
    assert 0 < fill <= 100
    assert 0 < read["moe_held_share_pct"](view) < 100
    # No trace in this run: the device-trace readers find nothing to read
    # and say so with None.
    for name in ("band_attn_time_pct", "band_attn_roofline",
                 "full_attn_time_pct.mellum2", "full_attn_roofline.mellum2",
                 "moe_time_pct.mellum2"):
        assert read[name](view) is None
    block_fill = read["moe_block_fill_pct.mellum2"](view)
    assert block_fill == pytest.approx(
        100 * events[0]["moe_block_rows_real"]
        / events[0]["moe_block_rows_run"])
    assert 0 < block_fill <= 100


def compact(line):
    from lib import trace

    return trace.compact(line)


# HLO lines in the form the chip's profiler names `XLA Ops` events, at
# this cell's shapes, cut after the first operands. The kernels'
# instruction names are what the compiler gave them (AOT for the described
# v5e).
CHIP_LINES = {
    "band_fwd": [
        "%jvp_band_flash_fwd_.1 = (bf16[32,16384,128]{2,1,0}, "
        "f32[32,16384,128]{2,1,0}) custom-call(bf16[32,16384,128]{2,1,0} "
        "%bitcast.174, bf16[32,16384,128]{2,1,0} %b, bf16[32,16384,128]"
        "{2,1,0} %c), custom_call_target=\"tpu_custom_call\"",
        "%checkpoint_band_flash_fwd.3 = (bf16[32,16384,128]{2,1,0}, "
        "f32[32,16384,128]{2,1,0}) custom-call(bf16[32,16384,128]{2,1,0} "
        "%bitcast.174), custom_call_target=\"tpu_custom_call\"",
    ],
    "band_bwd": [
        "%transpose_jvp_band_flash_bwd__.1 = (bf16[32,16384,128]{2,1,0}, "
        "bf16[32,16384,128]{2,1,0}, bf16[32,16384,128]{2,1,0}) custom-call("
        "bf16[32,16384,128]{2,1,0} %q), "
        "custom_call_target=\"tpu_custom_call\"",
    ],
    "full_fwd": [
        "%jvp_flash_fwd_.3 = (bf16[32,16384,128]{2,1,0}, "
        "f32[32,16384,128]{2,1,0}) custom-call(bf16[32,16384,128]{2,1,0} "
        "%bitcast.174), custom_call_target=\"tpu_custom_call\"",
    ],
    "full_bwd": [
        "%transpose_jvp_flash_bwd__.3 = (bf16[32,16384,128]{2,1,0}, "
        "bf16[32,16384,128]{2,1,0}, bf16[32,16384,128]{2,1,0}) custom-call("
        "bf16[32,16384,128]{2,1,0} %q), "
        "custom_call_target=\"tpu_custom_call\"",
    ],
    "block_diffusion": [
        "%jvp_bd_flash_fwd_.1 = (bf16[32,16384,128]{2,1,0}, "
        "f32[32,16384,128]{2,1,0}) custom-call(bf16[32,16384,128]{2,1,0} "
        "%bitcast.174), custom_call_target=\"tpu_custom_call\"",
    ],
    "routing": [
        "%sort.3 = (f32[16384,64]{1,0}, s32[16384,64]{1,0}) sort("
        "f32[16384,64]{1,0} %a, s32[16384,64]{1,0} %iota), dimensions={1}",
        "%sort.9 = (s32[131072]{0}, s32[131072]{0}) sort(s32[131072]{0} %l, "
        "s32[131072]{0} %i), dimensions={0}",
    ],
    "grouped": [
        "%while.100 = (s32[]{:T(128)}, f32[16384,18,128]{2,1,0:T(8,128)}, "
        "bf16[16,2304,1792]{2,1,0:T(8,128)(2,1)}, bf16[16,896,2304]{2,1,0}, "
        "f32[132224]{0:T(1024)}) while(%tuple.5), condition=%cond, body=%b",
    ],
    "neither": [
        "%fusion.12 = bf16[1,16384,2304]{2,1,0} fusion(bf16[1,16384,4096]"
        "{2,1,0} %x, f32[4096,2304]{1,0} %o_proj), kind=kOutput",
        # The optimizer's update of the expert weights is not the layer's.
        "%fusion.918 = (f32[16,2304,1792]{2,1,0}, f32[16,2304,1792]{2,1,0}, "
        "f32[16,2304,1792]{2,1,0}) fusion(f32[16,2304,1792]{2,1,0} %w, "
        "f32[16,2304,1792]{2,1,0} %opt_state_0__nu__layers_1__w_gate_up)",
    ],
}
KERNEL_OF = {"band_fwd": "band_flash_fwd", "band_bwd": "band_flash_bwd",
             "full_fwd": "flash_fwd", "full_bwd": "flash_bwd"}


def cell_view():
    cell = h.cell_mod.Cell(CELL)

    class View:
        config, traffic = cell.config, cell.traffic
        device = {"kind": "TPU v5 lite", "count": 1}

    return View


@pytest.mark.parametrize("kind", sorted(CHIP_LINES))
def test_mellum_ops_are_told_by_name_and_by_the_configurations_shapes(
        ops, kind):
    matches = h.cell_mod.load_module("metrics", "_model_ops").matches
    z = ops.sizes(cell_view()())
    assert (z["rows"], z["assignments"], z["held"], z["block"]) == (
        16384, 131072, 16,
        cell_view().config["model"]["expert_block_rows"])
    assert (z["band_layers"], z["full_layers"], z["window"]) == (3, 1, 1024)
    for line in CHIP_LINES[kind]:
        told = {
            "routing": matches(line, (ops.routing_shape,), z),
            "grouped": matches(line, (ops.grouped_shape,), z),
        }
        kernel = ops.classify(compact(line))
        for name, want in KERNEL_OF.items():
            told[name] = kernel is not None and kernel[0] == want
        if kind == "grouped":
            told.pop("routing")  # the padded assignments ride in the loop
        assert told == {k: k == kind for k in told}, line
        if kernel:
            assert kernel[1:] == (32, 16384, 128, 2)


def test_attention_readers_on_a_made_up_trace(ops, capsys):
    """Two steps: a windowed layer under remat (forward, its rematerialised
    twin, backward), the full layer's forward and backward, a fusion and a
    block-diffusion call that is neither's. The time shares count every
    call of their kind; the roofline's needed work counts the forward
    once, the band's scores and not the causal half."""
    line = {k: compact(v[0]) for k, v in CHIP_LINES.items()}
    ms = 1e6
    events, at = [], 0.0
    for _ in range(2):
        for name, dur in ((line["band_fwd"], 4 * ms),
                          (line["neither"], 20 * ms),
                          (line["full_fwd"], 18 * ms),
                          (line["full_bwd"], 36 * ms),
                          (line["band_fwd"], 4 * ms),
                          (line["band_bwd"], 8 * ms),
                          (line["block_diffusion"], 10 * ms)):
            events.append([name, at, dur])
            at += dur

    class View(cell_view()):
        trace = {"busy_s": at / 1e9,
                 "devices": {"/device:TPU:0": {"events": events}}}

    read = {n: h.cell_mod.load_module("metrics", n).read
            for n in NEW_READERS}
    assert read["band_attn_time_pct"](View()) == pytest.approx(
        100 * 16 / 100)
    assert read["full_attn_time_pct.mellum2"](View()) == pytest.approx(
        100 * 54 / 100)
    needed = 16_253_440
    flops_fwd = 2 * 2 * 32 * needed * 128
    assert ops.kernel_flops(ops.BAND_FWD, 32, 16384, 128, 1024) == flops_fwd
    assert ops.kernel_flops(
        ops.BAND_BWD, 32, 16384, 128, 1024) == 2 * flops_fwd
    # Compute binds both: 16.3 M scores x 128 against 134 MB a tensor.
    least = 2 * 3 * flops_fwd / 197e12
    assert ops.kernel_bytes(ops.BAND_FWD, 32, 16384, 128, 2) / 819e9 < \
        flops_fwd / 197e12
    roof = read["band_attn_roofline"](View())
    assert roof == pytest.approx(100 * least / (2 * 16e-3))
    assert 0 < roof < 100
    said = json.loads(capsys.readouterr().out.strip().splitlines()[-1])
    assert said["calls"] == 6 and said["calls_needed"] == 4
    assert said["binding_roof_by_call"] == {"compute": 4}
    # The full layer's calls: the causal half, 8.3 times the band's scores.
    causal = 16384 * 16385 // 2
    assert ops.kernel_flops(
        ops.FULL_BWD, 32, 16384, 128, 1024) == 4 * 2 * 32 * causal * 128
    roof = read["full_attn_roofline.mellum2"](View())
    assert roof == pytest.approx(
        100 * (2 * 3 * 2 * 2 * 32 * causal * 128 / 197e12) / (2 * 54e-3))
    assert 0 < roof < 100
    said = json.loads(capsys.readouterr().out.strip().splitlines()[-1])
    assert said["reader"] == "full_attn_roofline.mellum2"
    assert said["calls"] == said["calls_needed"] == 4


def test_a_share_counts_a_loop_and_its_body_once(ops):
    loop = CHIP_LINES["grouped"][0]
    body = "%fusion.2160 = f32[1152,1792]{1,0} fusion(bf16[16,2304,1792]" \
        "{2,1,0} %w, s32[] %e), kind=kOutput"

    class View(cell_view()):
        trace = {"busy_s": 1e-6, "devices": {"/device:TPU:0": {}}}
        _raw_device_events = {"/device:TPU:0": [
            (loop, 0.0, 400.0), (body, 100.0, 200.0), (body, 250.0, 300.0),
            (CHIP_LINES["neither"][1], 500.0, 900.0),
            (CHIP_LINES["routing"][0], 900.0, 1000.0)]}

    read = h.cell_mod.load_module("metrics", "moe_time_pct.mellum2").read
    assert read(View()) == pytest.approx(50.0)
    assert ops.share_of_busy_pct(View(), (lambda dims, z: False,)) is None


def test_tile_fill_adds_up_the_windows_events():
    class View(cell_view()):
        t0, t1 = 10.0, 50.0

        def events_of(self, kinds, role_prefix=None, since=None,
                      until=None):
            assert (kinds, since, until) == ("model_stats", 10.0, 50.0)
            return [{"band_scores_needed": 3 * 32 * 16253440.0,
                     "band_scores_run": 3 * 32 * 31 * 1048576.0}] * 3

    fill = h.cell_mod.load_module("metrics", "band_attn_tile_fill_pct").read
    assert fill(View()) == pytest.approx(100 * 16253440 / (31 * 1048576))
    assert fill(View()) == pytest.approx(50.0, abs=0.01)


def test_block_fill_adds_up_the_windows_events():
    class View(cell_view()):
        t0, t1 = 10.0, 50.0

        def events_of(self, kinds, role_prefix=None, since=None,
                      until=None):
            assert (kinds, since, until) == ("model_stats", 10.0, 50.0)
            return [{"moe_block_rows_real": 130768.0,
                     "moe_block_rows_run": 4 * 16 * 2176.0}] * 3

    fill = h.cell_mod.load_module(
        "metrics", "moe_block_fill_pct.mellum2").read
    assert fill(View()) == pytest.approx(100 * 130768 / 139264)
    assert fill(View()) == pytest.approx(93.9, abs=0.01)


def test_mfu_counts_the_cut_as_run_by_hand(ops):
    """Multiplying parameters a row, written out (ISSUE 51's count): the
    four projections, the router, two experts' worth of the held experts
    (8 a token x 16 / 64); four layers and the head; attention's needed
    scores a token: 992.0 under the band in three layers, 8,192.5 in the
    full one."""
    z = ops.sizes(cell_view()())
    attention = 2304 * 4096 + 2 * 2304 * 512 + 4096 * 2304
    per_row = attention + 2304 * 64 + 2.0 * 3 * 2304 * 896
    assert per_row == 33_767_424
    assert ops.multiplying_params_per_row(z) == per_row
    head = 2304 * 24576
    products = 6 * (4 * per_row + head)
    band_a_token = 16_253_440 / 16384
    assert band_a_token == pytest.approx(992.03, abs=0.01)
    attention_flops = 12 * 32 * 128 * (3 * band_a_token + 16385 / 2)
    assert products == pytest.approx(1.150e9, rel=2e-3)
    assert attention_flops == pytest.approx(5.49e8, rel=2e-3)
    assert ops.train_flops_per_token(z) == pytest.approx(
        products + attention_flops, rel=1e-12)
    # With causal masks in all four layers attention would need 2.9 times
    # as much: the three windowed layers need 27% of the attention's work.
    causal = 12 * 32 * 128 * 4 * 16385 / 2
    assert causal / attention_flops == pytest.approx(2.93, abs=0.01)

    class View(cell_view()):
        def record_rate(self):
            return 3.0  # records of 16,384 tokens a second

    mfu = h.cell_mod.load_module("metrics", "mfu_pct.mellum2").read(View())
    assert mfu == pytest.approx(
        100 * 3.0 * 16384 * (products + attention_flops) / 197e12)
    assert 0 < mfu < 100


def test_the_new_readers_find_nothing_in_another_models_run():
    """A program without the counters or the kernels' names, a
    configuration of another model (the parent's cells): None, not an
    exception."""
    for other in ("lm_flagship.steady", "lfm2_24b_a2b.steady_s8192_mb2",
                  "sdar_30b_a3b.steady_s8192_mb1",
                  "granite_4_0_h_micro.steady_causal_s8192_mb1"):
        cell = h.cell_mod.Cell(other)

        class View:
            t0, t1 = 10.0, 50.0
            trace = {"busy_s": 1.0, "devices": {"d": {"events": [
                [compact(CHIP_LINES["full_fwd"][0]), 0.0, 9.0]]}}}
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
