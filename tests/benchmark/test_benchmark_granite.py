"""The Granite hybrid reference against the program at a tiny size on the
CPU, the fp8 control and the planted fault, the configuration file against
the model-def module and the catalog's rule, the new readers on synthetic
traces and events, the manifest's form, and the CPU rehearsal of the cell's
traffic with the toy model through `edl train`."""

import copy
import dataclasses
import json
import re

import numpy as np
import pytest

import bench_helpers as h

CELL = "granite_4_0_h_micro.steady_causal_s8192_mb1"
STEPS = [8, 16, 24, 32]
MINIBATCH = 1
NEW_READERS = ("ssd_time_pct.granite", "ssd_roofline.granite",
               "mixer_time_pct.granite", "mfu_pct.granite")
# What each model_config PR appended to the manifest: its configuration,
# its cell, its per-layer metrics (test_the_manifests_entries_keep_its_form).
ADDED = {
    "sdar_30b_a3b": (
        "sdar_30b_a3b.steady_s8192_mb1",
        ("bd_attn_time_pct", "bd_attn_roofline", "bd_attn_tile_fill_pct",
         "moe_time_pct.sdar", "mfu_pct.sdar")),
    "granite_4_0_h_micro": (CELL, NEW_READERS),
}


def tiny_config():
    with open(h.os.path.join(
            h.REPO, "tests", "benchmark", "tiny_granite.json")) as f:
        return json.load(f)


@pytest.fixture(scope="module")
def ref():
    return h.load_file(
        h.os.path.join(h.BENCH, "references", "granite_hybrid.py"),
        "edlbench_ref_granite_hybrid")


@pytest.fixture(scope="module")
def model_def():
    from elasticdl_tpu.common.model_utils import load_module

    return load_module(h.os.path.join(h.REPO, tiny_config()["model_def"]))


@pytest.fixture(scope="module")
def ops():
    return h.cell_mod.load_module("metrics", "_granite_ops")


def stated_equals_built(stated, built, skip=()):
    for key, value in stated.items():
        if key in skip:
            continue
        got = built[key]
        assert (list(got) if isinstance(got, tuple) else got) == value, key


def test_the_tiny_configuration_file_states_the_tiny_model(model_def):
    cfg = tiny_config()
    stated_equals_built(cfg["model"], dataclasses.asdict(model_def.CONFIG))
    assert cfg["data"]["vocab"] == cfg["model"]["vocab_size"]
    assert cfg["data"]["seq_len"] == cfg["record_tokens"]
    # None of the toy's multipliers is 1, and its attention multiplier is
    # not the kernels' own scale.
    m = cfg["model"]
    head_dim = m["hidden_size"] // m["num_attention_heads"]
    assert m["attention_multiplier"] != head_dim ** -0.5
    assert 1 not in (m["attention_multiplier"], m["embedding_multiplier"],
                     m["residual_multiplier"], m["logits_scaling"])


def test_the_cut_configuration_file_states_the_model_def():
    """benchmark/configs/granite_4_0_h_micro.json against the model-def
    module `edl train` runs, against the catalog's rule (every width as
    published, two keys reduced) and against the initialised tree's
    size."""
    import jax
    import jax.numpy as jnp

    from elasticdl_tpu.models.granite_hybrid import (
        granite_4_0_h_micro_cut as m,
    )

    cfg = h.cell_mod.Cell(CELL).config
    assert cfg["model_def"] == m.__name__
    stated_equals_built(
        cfg["model"], dataclasses.asdict(m.cut_config()),
        skip=("param_dtype", "parameters", "remat_reason"))
    public = dict(m.PUBLIC_CONFIG)
    differs = {k for k, v in public.items() if cfg.get(k, "absent") != v}
    assert differs == set(cfg["reduced"]) == {
        "num_hidden_layers", "vocab_size"}
    for key in cfg["reduced"]:
        assert cfg["published"][key] == public[key]
    for width in ("hidden_size", "num_attention_heads",
                  "num_key_value_heads", "shared_intermediate_size",
                  "mamba_n_heads", "mamba_d_head", "mamba_n_groups",
                  "mamba_d_state", "mamba_d_conv", "mamba_chunk_size",
                  "attention_multiplier", "embedding_multiplier",
                  "residual_multiplier", "logits_scaling", "rms_norm_eps"):
        assert cfg["model"][width] == public[width] == cfg[width], width
    # Ten layers in the published order, the attention layer sixth.
    assert cfg["num_hidden_layers"] == len(cfg["model"]["layer_types"]) == 10
    assert cfg["model"]["layer_types"] == public["layer_types"][:10]
    assert cfg["model"]["layer_types"].index("attention") == 5
    assert cfg["model"]["layer_types"].count("mamba") == 9
    assert len(cfg["layer_types"]) == cfg["published"]["num_hidden_layers"]
    assert cfg["vocab_size"] == cfg["model"]["vocab_size"] == \
        cfg["data"]["vocab"] == 100352 // 8 == 12544
    assert cfg["data"]["seq_len"] == cfg["record_tokens"] == 8192
    assert cfg["record_tokens"] % cfg["mamba_chunk_size"] == 0
    for key in ("deployment", "cut", "assumed", "departures"):
        assert cfg[key], key
    row = jnp.zeros((1, 256), jnp.int32)
    shapes = jax.eval_shape(
        lambda rng, row: m.custom_model().init(
            {"params": rng}, row, training=False),
        jax.random.PRNGKey(0), row)
    counted = sum(int(np.prod(leaf.shape))
                  for leaf in jax.tree_util.tree_leaves(shapes["params"]))
    # ISSUE 46's arithmetic: a mixer, the MLP, a Mamba layer, the
    # attention layer, one period, the embedding's slice, the last norm.
    mixer = 2048 * 8512 + 4 * 4352 + 4352 + 192 + 4096 + 4096 * 2048
    mlp = 2048 * 16384 + 8192 * 2048
    attention = 2 * 2048 * 2048 + 2 * 2048 * 512
    assert (mixer, mlp, attention) == (25_847_232, 50_331_648, 10_485_760)
    by_hand = 9 * (mixer + mlp + 4096) + (attention + mlp + 4096) \
        + 12544 * 2048 + 2048
    assert by_hand == 772_160_448
    assert counted == cfg["model"]["parameters"] == by_hand
    assert "772,160,448" in cfg["cut"]["parameters"]


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


@pytest.mark.parametrize("seed", [104, 8, 2**31 + 11])
def test_program_passes_and_the_controls_fail(ref, model_def, seed):
    cfg = tiny_config()
    compare = h.run_module().compare_losses
    want = ref.losses(cfg, seed, MINIBATCH, STEPS, "float32")
    got, stats = program_losses(seed, cfg, model_def)
    rows, mean, ok = compare(got, want, *limits(cfg))
    assert ok, (rows, mean)
    # The step hands its count back beside the loss: three scanning
    # layers.
    assert {k: float(v) for k, v in stats.items()} == {
        "ssd_scan_tokens": 3 * cfg["data"]["seq_len"]}
    control = ref.losses(cfg, seed, MINIBATCH, STEPS, "fp8")
    rows, mean, ok = compare(control, want, *limits(cfg))
    assert not ok, (rows, mean)
    fault = ref.losses(cfg, seed, MINIBATCH, STEPS, "float32", "no_carry")
    rows, mean, ok = compare(fault, want, *limits(cfg))
    assert not ok, (rows, mean)


def test_the_tiny_models_loss_and_gradients_against_the_reference(
        ref, model_def):
    """One record, seeded weights, float32 activations on the program's
    side: the loss and every parameter's gradient, the chunked scan
    against the recurrence among them."""
    import jax
    import jax.numpy as jnp

    from elasticdl_tpu.models.granite_hybrid import granite_hybrid

    cfg = tiny_config()
    gen = h.cell_mod.load_module("datagen", cfg["datagen"])
    (tokens, labels), = gen.batches(3, 1, 1, 11, cfg["data"])
    config = dataclasses.replace(
        model_def.CONFIG, activation_dtype="float32")
    model = granite_hybrid.custom_model(config)
    x, y = jnp.asarray(tokens), jnp.asarray(labels)
    params = model.init({"params": jax.random.PRNGKey(5)}, x)["params"]

    def program(p):
        return granite_hybrid.loss(
            y, model.apply({"params": p}, x, training=True))

    plain = ref.make_loss(cfg["model"], "float32")

    def reference(p):
        return plain(p, x[0], y[0])

    with jax.default_matmul_precision("highest"):
        got, got_grads = jax.value_and_grad(program)(params)
        want, want_grads = jax.value_and_grad(reference)(params)
        lost, _ = jax.value_and_grad(
            lambda p: ref.make_loss(cfg["model"], "float32", "no_carry")(
                p, x[0], y[0]))(params)
    assert float(got) == pytest.approx(float(want), rel=2e-6)
    # The planted fault is another function of the same weights.
    assert abs(float(lost) - float(want)) > 1e-4
    flat_got = jax.tree_util.tree_leaves_with_path(got_grads)
    flat_want = jax.tree_util.tree_leaves(want_grads)
    assert len(flat_got) == len(flat_want) > 0
    for (path, a), b in zip(flat_got, flat_want):
        scale = float(jnp.max(jnp.abs(b))) or 1.0
        np.testing.assert_allclose(
            np.asarray(a) / scale, np.asarray(b) / scale, atol=2e-5,
            err_msg=jax.tree_util.keystr(path))


def test_the_reference_imports_nothing_of_the_programs_layers():
    with open(h.os.path.join(
            h.BENCH, "references", "granite_hybrid.py")) as f:
        source = f.read()
    imported = set(re.findall(r"from (elasticdl_tpu[\w.]*) import", source))
    assert imported == {"elasticdl_tpu.common.model_utils",
                        "elasticdl_tpu.common.compile_cache"}
    assert "import flax" not in source and "import optax" not in source
    assert 'default_matmul_precision("highest")' in source


# ---------- the cell and its readers ----------


def tiny_granite_cell():
    """The committed cell's traffic and metrics over the toy model."""
    m = copy.deepcopy(h.manifest())
    like = next(w for w in m["workloads"] if w["name"] == CELL)
    name = "tiny_granite.steady_causal_s8192_mb1"
    m["configs"] = [{"name": "tiny_granite", "source": "toy", "reduced": [],
                     "why": "toy",
                     "file": "tests/benchmark/tiny_granite.json"}]
    m["workloads"] = [dict(like, name=name, config="tiny_granite")]
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
    reported = {m["name"] for m in cell.per_layer}
    assert set(NEW_READERS) <= reported
    # The causal kernels run as they are, at a shape their readers know.
    assert {"flash_roofline", "flash_time_pct", "device_idle_pct.lm",
            "idle_input_pct.lm", "window_compiles.lm", "step_ms_p50.lm",
            "warmup_s", "launch_s", "step_load_s"} <= reported
    # Readers of other models' keys and counters are not given this cell.
    assert not {"mfu_pct", "mfu_pct.lfm2", "mfu_pct.sdar", "moe_time_pct",
                "moe_held_share_pct", "ssd_time_pct", "shortconv_time_pct",
                "bd_attn_roofline", "allreduce_exposed_pct"} & reported
    assert {m["name"] for m in cell.end_to_end} == {
        "setup_s", "tokens_per_s"}
    for m in h.manifest()["per_layer"]:
        if m["name"] in NEW_READERS:
            assert m["workloads"] == [CELL] and m["moves"] == "tokens_per_s"
    # At most a quarter of the cells, or one, asks for four chips.
    cells = h.manifest()["workloads"]
    assert sum(w["chips"] == 4 for w in cells) <= max(1, len(cells) // 4)


@pytest.mark.parametrize("config_name", sorted(ADDED))
def test_the_manifests_entries_keep_its_form(config_name):
    """What the driver refuses before any run, for the entries each
    model_config PR appended (the rules of `test_benchmark_sdar.py`'s
    test of the same name, which also held its entries to be the last): a
    text of an entry over 200 characters, on two lines or not printable; a
    name or unit outside its characters; a key an entry may not have; a
    PR's entries put before an earlier PR's. Nothing here counts the
    manifest's entries or says which are its last, so what a later PR
    appends leaves this test as it is."""
    name = re.compile(r"[A-Za-z0-9_][A-Za-z0-9_.\-]{0,63}\Z")
    unit = re.compile(r"[A-Za-z0-9_/%.\-]{1,16}\Z")
    manifest = h.manifest()
    cell_name, readers = ADDED[config_name]
    config, = (c for c in manifest["configs"] if c["name"] == config_name)
    cell, = (w for w in manifest["workloads"] if w["name"] == cell_name)
    metrics = [m for m in manifest["per_layer"] if m["name"] in readers]
    assert [m["name"] for m in metrics] == list(readers)
    # A PR's metrics lie together, and its entries after those of the PRs
    # that landed before it (`ADDED` is in that order).
    first = manifest["per_layer"].index(metrics[0])
    assert manifest["per_layer"][first:first + len(metrics)] == metrics
    landed = list(ADDED)
    for earlier in landed[:landed.index(config_name)]:
        for entries, mine, theirs in (
                (manifest["configs"], config_name, earlier),
                (manifest["workloads"], cell_name, ADDED[earlier][0]),
                (manifest["per_layer"], readers[0], ADDED[earlier][1][-1])):
            names = [e["name"] for e in entries]
            assert names.index(theirs) < names.index(mine)
    assert set(config) == {"name", "source", "file", "reduced", "why"}
    assert set(cell) == {"name", "config", "traffic", "chips", "why"}
    for text in (config["source"], config["why"], config["file"],
                 cell["why"], *(m["layer"] for m in metrics)):
        assert 1 <= len(text) <= 200, text
        assert text.isascii() and text.isprintable(), text
    for word in (config["name"], *config["reduced"], cell["name"],
                 cell["config"], cell["traffic"],
                 *(m["name"] for m in metrics)):
        assert name.match(word), word
    for m in metrics:
        assert set(m) == {"name", "unit", "better", "source", "layer",
                          "moves", "workloads"}
        assert unit.match(m["unit"]) and m["better"] in ("lower", "higher")
        assert m["source"] in ("device_trace", "program_span",
                               "program_counter", "host_clock")
        assert m["workloads"] == [cell_name]
    assert len(json.dumps(manifest, indent=1)) <= 64 * 1024


def test_rehearsal_of_the_cell_with_the_toy_model(capsys):
    """The normal path at minibatch 1: `edl train` on the local backend,
    the cell's traffic, the toy model; `correct`, and the step's counter,
    one event a fence."""
    cell = tiny_granite_cell()
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
    length = cell.config["record_tokens"]
    assert events[0]["ssd_scan_tokens"] == 3 * length
    ops = h.cell_mod.load_module("metrics", "_granite_ops")
    assert ops.scan_tokens_per_step(view) == 3 * length
    read = {n: h.cell_mod.load_module("metrics", n).read
            for n in NEW_READERS}
    # tokens/s x FLOP a token over a peak the CPU is not in the table of.
    view.device = dict(view.device, kind="TPU v5 lite")
    assert read["mfu_pct.granite"](view) > 0
    # No trace in this run: the device-trace readers find nothing to read
    # and say so with None.
    for name in ("ssd_time_pct.granite", "ssd_roofline.granite",
                 "mixer_time_pct.granite"):
        assert read[name](view) is None


def test_the_new_readers_find_nothing_in_another_models_run():
    """A program without the counters, a configuration of another model
    (the parent's cells): None, not an exception."""
    for other in ("lm_flagship.steady", "lfm2_24b_a2b.steady_s8192_mb2",
                  "nemotron_twotower_30b_a3b.steady_mb2",
                  "sdar_30b_a3b.steady_s8192_mb1"):
        cell = h.cell_mod.Cell(other)

        class View:
            t0, t1, t_traced = 10.0, 50.0, None
            trace = {"busy_s": 1.0, "window_s": 1.0,
                     "devices": {"d": {"events": []}}}
            config, traffic = cell.config, cell.traffic
            device = {"kind": "TPU v5 lite", "count": 1}
            _raw_device_events = {"d": [(CHIP_LINES["scan"][0], 0., 9.)]}

            def events_of(self, kinds, role_prefix=None, since=None,
                          until=None):
                return []

            def record_rate(self):
                return 5.0

        for name in NEW_READERS:
            assert h.cell_mod.load_module(
                "metrics", name).read(View()) is None, (other, name)


# HLO lines in the form the chip's profiler names `XLA Ops` events (the
# operands with their shapes), at this cell's shapes: the results and the
# instruction names are the step's as compiled for the described v5e (PR
# 46), the operands cut short.
CHIP_LINES = {
    "scan": [
        # C B^T of a chunk, the unit axes dropped.
        "%fusion.2253 = f32[32,256,256]{2,1,0:T(8,128)S(1)} fusion("
        "bf16[32,256,128]{2,1,0} %bitcast.4642, bf16[32,256,128]{2,1,0} "
        "%bitcast.4570), kind=kOutput",
        # The states entering each chunk, and one of their transposes.
        "%fusion.1437 = bf16[32,64,64,128]{3,0,2,1:T(8,128)(2,1)S(1)} "
        "fusion(f32[64,32,32]{2,1,0} %fusion.1086), kind=kOutput",
        "%fusion.1521 = bf16[64,64,128,32]{2,1,0,3:T(8,128)(2,1)S(1)} "
        "fusion(f32[32,64,64,128]{3,2,1,0} %custom-call.158), kind=kOutput",
        # The masked product, still in the chunked layout.
        "%fusion.388 = f32[1,32,256,1,64,64]{2,5,4,1,3,0:T(8,128)} fusion("
        "f32[32,256,256]{2,1,0} %fusion.2253), kind=kOutput",
        "%copy.77 = f32[8,8,32,256]{3,2,1,0:T(8,128)} copy("
        "f32[8,8,32,256]{1,0,3,2} %bitcast.9)",
        "%fusion.9 = f32[32,64,256]{2,1,0} fusion(f32[1,8192,64]{2,1,0} "
        "%dt), kind=kLoop",
    ],
    "mixer": [
        "%convolution_bitcast_fusion.37 = bf16[1,8192,8512]{1,2,0:T(8,128)"
        "(2,1)} fusion(f32[2048,8512]{1,0} %in_proj_kernel, "
        "bf16[1,8192,2048]{2,1,0} %copy-done.869), kind=kOutput",
        "%fusion.1399 = f32[2048,8512]{0,1:T(8,128)} fusion("
        "bf16[1,8192,2048]{2,1,0} %copy-done.379), kind=kOutput",
        "%fusion.61 = bf16[1,8192,4352]{2,1,0} fusion(bf16[1,8192,4352]"
        "{2,1,0} %slice.3, bf16[4,4352]{1,0} %convert.9), kind=kLoop",
        "%fusion.573 = (f32[4096]{0:T(1024)S(1)}, f32[8192]{0:T(1024)S(1)},"
        " bf16[8192,4096]{0,1:T(8,128)(2,1)}) fusion(f32[1,8192,4096]"
        "{2,1,0} %get-tuple-element.702), kind=kOutput",
        # The out-projection: told by its operand of the inner width.
        "%fusion.90 = bf16[1,8192,2048]{2,1,0} fusion(bf16[8192,4096]{1,0} "
        "%y, f32[4096,2048]{1,0} %out_proj_kernel), kind=kOutput",
        "%broadcast.1365 = f32[8192,64,64]{0,2,1:T(8,128)} broadcast("
        "f32[1,8192,64]{2,1,0} %get-tuple-element.797), dimensions={0,1}",
    ],
    "neither": [
        "%convolution_bitcast_fusion.19 = bf16[1,8192,16384]{2,1,0:T(8,128)"
        "(2,1)} fusion(f32[2048,16384]{1,0} %input_linear_kernel, "
        "bf16[1,8192,2048]{2,1,0} %get-tuple-element.754), kind=kOutput",
        "%fusion.1176 = f32[2048,16384]{1,0:T(8,128)} fusion("
        "bf16[1,8192,8192]{2,1,0} %get-tuple-element.787), kind=kOutput",
        "%flash_fwd.2 = (bf16[32,8192,64]{2,1,0:T(8,128)(2,1)}, "
        "f32[32,8192,128]{2,1,0:T(8,128)}) custom-call(bf16[32,8192,64]"
        "{2,1,0} %fusion.1230, bf16[32,8192,64]{2,1,0} %bitcast.4565, "
        "bf16[32,8192,64]{2,1,0} %bitcast.4564), "
        "custom_call_target=\"tpu_custom_call\"",
        "%fusion.7 = bf16[1,8192,32,64]{3,2,1,0} fusion(f32[2048,32,64]"
        "{2,1,0} %q_proj_kernel, bf16[1,8192,2048]{2,1,0} %u), kind=kOutput",
        "%fusion.3 = (f32[8192]{0}, f32[8192,12544]{1,0}) fusion("
        "bf16[8192,2048]{1,0} %h, bf16[12544,2048]{1,0} %table), "
        "kind=kOutput",
        # The optimizer's update of the mixer's weights is not the layer's.
        "%fusion.918 = (f32[2048,8512]{1,0}, f32[2048,8512]{1,0}, "
        "f32[2048,8512]{1,0}) fusion(f32[2048,8512]{1,0} %w, "
        "f32[2048,8512]{1,0} %opt_state_0__nu__layers_1__mamba__in_proj)",
    ],
}


def cell_view():
    cell = h.cell_mod.Cell(CELL)

    class View:
        config, traffic = cell.config, cell.traffic
        device = {"kind": "TPU v5 lite", "count": 1}

    return View


@pytest.mark.parametrize("kind", sorted(CHIP_LINES))
def test_granite_ops_are_told_by_the_configurations_shapes(ops, kind):
    matches = h.cell_mod.load_module("metrics", "_model_ops").matches
    z = ops.sizes(cell_view()())
    assert (z["batch"], z["chunks"], z["chunk"], z["heads"], z["groups"],
            z["in_proj"], z["conv"], z["inner"], z["scanning_layers"]) == (
                1, 32, 256, 64, 1, 8512, 4352, 4096, 9)
    for line in CHIP_LINES[kind]:
        scan = matches(line, (ops.scan_shape,), z)
        mixer = matches(line, (ops.mixer_shape,), z)
        assert scan == (kind == "scan"), line
        # The scan is part of the mixer.
        assert mixer == (kind in ("scan", "mixer")), line
    # The hybrid cell's reader, by its own keys, gives this cell nothing.
    assert h.cell_mod.load_module("metrics", "_model_ops").share_of_busy_pct(
        type("V", (cell_view(),), {
            "trace": {"busy_s": 1.0, "devices": {"d": {}}},
            "_raw_device_events": {"d": [(CHIP_LINES["scan"][0], 0., 9.)]},
        })(), (ops.scan_shape,)) is None


def scan_view(scan_ns, mixer_ns, other_ns, steps=2):
    """A made-up trace of `steps` steps: scan, mixer and other operations
    one after another, and the events the roofline's reader needs."""
    lines, at = [], 0.0
    for _ in range(steps):
        for name, dur in ((CHIP_LINES["scan"][0], scan_ns),
                          (CHIP_LINES["mixer"][0], mixer_ns),
                          (CHIP_LINES["neither"][0], other_ns),
                          (CHIP_LINES["neither"][5], other_ns)):
            lines.append((name, at, at + dur))
            at += dur
    step_s = at / steps / 1e9

    class View(cell_view()):
        t0, t1, t_traced = 100.0, 100.0 + 40 * step_s, None
        trace = {"busy_s": at / 1e9, "window_s": at / 1e9,
                 "devices": {"/device:TPU:0": {}}}
        _raw_device_events = {"/device:TPU:0": lines}

        def events_of(self, kinds, role_prefix=None, since=None,
                      until=None):
            if kinds == "model_stats":
                return [{"ssd_scan_tokens": 9 * 8192.0}] * 3
            if kinds == "steps_done":
                return [{"role": "worker-0", "first_step": 17, "stamps": [
                    100.0 + k * step_s for k in range(30)]}]
            return []

    return View(), step_s


def test_the_time_shares_on_a_made_up_trace(ops):
    view, _ = scan_view(20e6, 30e6, 25e6)
    read = {n: h.cell_mod.load_module("metrics", n).read
            for n in NEW_READERS}
    assert read["ssd_time_pct.granite"](view) == pytest.approx(20.0)
    # The mixers hold the scan; the optimizer's update is nobody's.
    assert read["mixer_time_pct.granite"](view) == pytest.approx(50.0)
    assert ops.share_of_busy_pct(view, (lambda dims, z: False,)) is None


def test_the_scans_roofline_by_hand(ops, capsys):
    """A token of a scanning layer, forward: the lower triangles of C B^T
    (128 wide) and of the masked product (64 heads of 64), the states in
    and out (64 x 64 x 128 each); backward twice that; x, B, C, y in
    bfloat16 and dt in float32 once, backward twice that. Memory binds
    both passes on a v5e."""
    z = ops.sizes(cell_view()())
    row = 257 / 2
    by_hand = 2 * (128 * row + 4096 * row + 2 * 4096 * 128)
    assert ops.scan_flops_per_token(z) == by_hand == 3_182_720
    assert ops.scan_bytes_per_token(z) == 2 * 4096 * 2 + 64 * 4 \
        + 2 * 128 * 2 == 17_152
    least, roofs = ops.scan_least_seconds_per_token(z, "TPU v5 lite")
    assert roofs == {"forward": "memory", "backward": "memory"}
    assert least == pytest.approx(3 * 17_152 / 819e9)
    assert 3 * by_hand / 197e12 < least
    # Two steps of 100 ms, 20 ms of scan each.
    view, step_s = scan_view(20e6, 30e6, 25e6)
    assert step_s == pytest.approx(0.1)
    roof = h.cell_mod.load_module("metrics", "ssd_roofline.granite").read(
        view)
    assert roof == pytest.approx(100 * 9 * 8192 * least / 20e-3)
    assert 0 < roof < 100
    said = json.loads(capsys.readouterr().out.strip().splitlines()[-1])
    assert said["steps_in_trace"] == pytest.approx(2.0)
    assert said["scan_tokens_per_step"] == 9 * 8192
    assert said["took_s"] == pytest.approx(0.04)
    # No faster scan can read over 100: at the roof itself it reads 100.
    at_roof, _ = scan_view(9 * 8192 * least * 1e9, 30e6, 25e6)
    assert h.cell_mod.load_module(
        "metrics", "ssd_roofline.granite").read(at_roof) == pytest.approx(
            100.0)


def test_mfu_counts_the_cut_as_run_by_hand(ops):
    """Multiplying parameters written out (ISSUE 46's count): a mixer's two
    projections or attention's four, the MLP's two matrices, the tied
    head; the scan's products in nine layers; causal attention in one."""
    z = ops.sizes(cell_view()())
    mixer = 2048 * 8512 + 4096 * 2048
    mlp = 2048 * 16384 + 8192 * 2048
    attention = 2 * 2048 * 2048 + 2 * 2048 * 512
    params = 9 * (mixer + mlp) + (attention + mlp) + 2048 * 12544
    assert params == 771_883_008 == ops.multiplying_params(z)
    scan = 9 * 3 * 3_182_720
    causal = 3 * 2 * 2 * 2048 * (8192 + 1) / 2
    assert scan == 85_933_440 and causal == 100_675_584
    by_hand = 6 * params + scan + causal
    assert ops.train_flops_per_token(z) == by_hand
    assert by_hand == pytest.approx(4.82e9, rel=2e-3)
    # The scan's products are 1.8% of the needed arithmetic.
    assert scan / by_hand == pytest.approx(0.018, abs=0.001)

    class View(cell_view()):
        def record_rate(self):
            return 2.0  # records of 8192 tokens a second

    mfu = h.cell_mod.load_module("metrics", "mfu_pct.granite").read(View())
    assert mfu == pytest.approx(100 * 2.0 * 8192 * by_hand / 197e12)
    assert 0 < mfu < 100
