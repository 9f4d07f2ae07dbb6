"""The SDAR reference against the program at a tiny size on the CPU, the
fp8 control and the planted fault, the data generator, the configuration
file against the model-def module, the new readers on synthetic traces and
events, and the CPU rehearsal of the cell's traffic with the toy model
through `edl train`."""

import copy
import dataclasses
import json

import numpy as np
import pytest

import bench_helpers as h

CELL = "sdar_30b_a3b.steady_s8192_mb1"
STEPS = [8, 16, 24, 32]
MINIBATCH = 1
NEW_READERS = ("bd_attn_time_pct", "bd_attn_roofline",
               "bd_attn_tile_fill_pct", "moe_time_pct.sdar", "mfu_pct.sdar")


def tiny_config():
    with open(h.os.path.join(
            h.REPO, "tests", "benchmark", "tiny_sdar.json")) as f:
        return json.load(f)


@pytest.fixture(scope="module")
def ref():
    return h.load_file(
        h.os.path.join(h.BENCH, "references", "sdar_moe.py"),
        "edlbench_ref_sdar_moe")


@pytest.fixture(scope="module")
def model_def():
    from elasticdl_tpu.common.model_utils import load_module

    return load_module(h.os.path.join(h.REPO, tiny_config()["model_def"]))


@pytest.fixture(scope="module")
def ops():
    return h.cell_mod.load_module("metrics", "_sdar_ops")


def stated_equals_built(stated, built, skip=()):
    for key, value in stated.items():
        if key in skip:
            continue
        got = built[key]
        assert (list(got) if isinstance(got, tuple) else got) == value, key


def test_the_tiny_configuration_file_states_the_tiny_model(model_def):
    cfg = tiny_config()
    stated_equals_built(cfg["model"], dataclasses.asdict(model_def.CONFIG))
    assert cfg["data"]["mask_token_id"] == cfg["model"]["mask_token_id"]
    assert cfg["data"]["block_length"] == cfg["model"]["block_length"]
    assert cfg["data"]["vocab"] == cfg["model"]["mask_token_id"]


def test_the_cut_configuration_file_states_the_model_def():
    """benchmark/configs/sdar_30b_a3b.json against the model-def module
    `edl train` runs, against the catalog's rule (every width as published,
    three keys reduced) and against the initialised tree's size."""
    import jax
    import jax.numpy as jnp

    from elasticdl_tpu.models.sdar import sdar_30b_a3b_cut as m

    cfg = h.cell_mod.Cell(CELL).config
    assert cfg["model_def"] == m.__name__
    stated_equals_built(
        cfg["model"], dataclasses.asdict(m.cut_config()),
        skip=("param_dtype", "parameters", "remat_reason", "kept_layers",
              "expert_block_rows_reason"))
    assert cfg["model"]["kept_layers"] == list(m.KEEP_LAYERS)
    public = dict(m.PUBLIC_CONFIG)
    differs = {k for k, v in public.items() if cfg.get(k, "absent") != v}
    assert differs == set(cfg["reduced"]) == {
        "num_hidden_layers", "num_experts", "vocab_size"}
    for key in cfg["reduced"]:
        assert cfg["published"][key] == public[key]
    for width in ("hidden_size", "head_dim", "moe_intermediate_size",
                  "num_attention_heads", "num_key_value_heads",
                  "num_experts_per_tok", "rope_theta", "rms_norm_eps"):
        assert cfg["model"][width] == public[width], width
    assert cfg["model"]["num_experts"] == public["num_experts"] == 128
    # head_dim is a key of its own: not hidden / heads.
    assert cfg["head_dim"] * cfg["num_attention_heads"] != cfg["hidden_size"]
    assert cfg["num_hidden_layers"] == cfg["model"]["num_hidden_layers"] == 6
    assert cfg["num_experts"] == cfg["model"]["experts_held"][1] == 16
    assert cfg["vocab_size"] == cfg["model"]["vocab_size"] == 151936 // 8
    assert cfg["data"]["vocab"] == cfg["data"]["mask_token_id"] == \
        cfg["model"]["mask_token_id"] == cfg["vocab_size"] - 1
    assert cfg["data"]["block_length"] == cfg["model"]["block_length"] == 4
    assert cfg["data"]["seq_len"] == cfg["record_tokens"] == 8192
    for key in ("deployment", "cut", "assumed", "departures"):
        assert cfg[key], key
    row = jnp.zeros((1, 8), jnp.int32)
    shapes = jax.eval_shape(
        lambda rng, row: m.custom_model().init(
            {"params": rng}, {"tokens": row, "noised": row},
            training=False),
        jax.random.PRNGKey(0), row)
    counted = sum(int(np.prod(leaf.shape))
                  for leaf in jax.tree_util.tree_leaves(shapes["params"]))
    # ISSUE 43's arithmetic: a layer outside its experts, 16 experts, six
    # layers, the embedding's and the head's slices, the last norm.
    outside = 2048 * 4096 + 2 * 2048 * 512 + 4096 * 2048 + 256 \
        + 128 * 2048 + 2 * 2048
    by_hand = 6 * (outside + 16 * 3 * 2048 * 768) + 2 * 18992 * 2048 + 2048
    assert outside == 19_140_864 and by_hand == 645_623_296
    assert counted == cfg["model"]["parameters"] == by_hand
    assert "645,623,296" in cfg["cut"]["parameters"]


# ---------- the data ----------


def test_datagen_twice_from_one_seed_and_its_noise():
    cfg = tiny_config()
    gen = h.cell_mod.load_module("datagen", cfg["datagen"])
    data = cfg["data"]
    a = list(gen.batches(0, 70, 2, 2**31 + 5, data))
    b = list(gen.batches(0, 70, 2, 2**31 + 5, data))
    c = list(gen.batches(0, 70, 2, 2**31 + 6, data))
    for (ta, la, ua), (tb, lb, ub) in zip(a, b):
        for x, y in ((ta, tb), (la, lb), (ua, ub)):
            np.testing.assert_array_equal(x, y)
    assert any((x[0] != y[0]).any() for x, y in zip(a, c))
    # A later step's batch does not depend on how many came before.
    (tokens, t, u), = gen.batches(37, 1, 2, 2**31 + 5, data)
    np.testing.assert_array_equal(tokens, a[37][0])
    np.testing.assert_array_equal(u, a[37][2])
    tokens, t, u = (np.concatenate(x) for x in zip(*a))
    assert tokens.shape == (140, 32) and tokens.dtype == np.int32
    assert t.shape == (140, 8) and u.shape == (140, 32)
    assert t.dtype == u.dtype == np.float32
    assert 0 <= tokens.min() and tokens.max() < data["mask_token_id"]
    assert data["t_low"] <= t.min() and t.max() <= data["t_high"]
    assert 0 <= u.min() and u.max() < 1
    # Masked with probability t: about (0.3 + 0.8) / 2 of the positions.
    masked = u < np.repeat(t, data["block_length"], axis=1)
    assert 0.5 < masked.mean() < 0.6


def test_the_record_file_is_what_the_feed_noises(tmp_path, model_def):
    """`write_records` through the program's own reader and `feed`: the
    noised copy, the targets and the weights masked / t."""
    from elasticdl_tpu.data.recordfile import RecordFile

    cfg = tiny_config()
    gen = h.cell_mod.load_module("datagen", cfg["datagen"])
    path = str(tmp_path / "train.edlr")
    assert gen.write_records(path, 5, 9, cfg["data"]) == {
        "records": 5, "distinct_records": 5}
    with RecordFile(path) as r:
        records = list(r.read(0, 5))
    features, labels = model_def.feed(records, "training", None)
    (tokens, t, u), = gen.batches(0, 1, 5, 9, cfg["data"])
    level = np.repeat(t, 4, axis=1)
    masked = u < level
    np.testing.assert_array_equal(features["tokens"], tokens)
    np.testing.assert_array_equal(labels["targets"], tokens)
    np.testing.assert_array_equal(
        features["noised"], np.where(masked, 255, tokens))
    np.testing.assert_allclose(
        labels["weights"], np.where(masked, 1 / level, 0), rtol=1e-6)
    assert features["noised"].dtype == np.int32
    assert labels["weights"].dtype == np.float32


# ---------- reference against program ----------


def program_losses(seed, cfg, model_def):
    from elasticdl_tpu.models.sdar.sdar_moe import noise
    from elasticdl_tpu.worker.trainer import LocalTrainer

    datagen = h.cell_mod.load_module("datagen", cfg["datagen"])
    trainer = LocalTrainer(model_def.custom_model(), model_def.loss,
                           model_def.optimizer(), seed=seed)
    out, stats = {}, None
    for k, (tokens, t, u) in enumerate(datagen.batches(
            0, max(STEPS), MINIBATCH, seed, cfg["data"])):
        noised, weights = noise(tokens, t, u, cfg["data"]["mask_token_id"])
        x = {"tokens": tokens, "noised": noised}
        y = {"targets": tokens, "weights": weights}
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
    # The step hands its counts back beside the loss: two routed layers,
    # two experts a token, over both copies' rows; the objective's own.
    length = cfg["data"]["seq_len"]
    assert float(stats["moe_assignments"]) == 2 * 2 * length * 2
    assert float(stats["bd_positions"]) == MINIBATCH * length
    assert 0 < float(stats["bd_positions_masked"]) < length
    heads, layers = 4, 2
    assert float(stats["attn_scores_needed"]) == (
        layers * heads * length * (length + 4))
    assert float(stats["attn_scores_run"]) >= float(
        stats["attn_scores_needed"])
    control = ref.losses(cfg, seed, MINIBATCH, STEPS, "fp8")
    rows, mean, ok = compare(control, want, *limits(cfg))
    assert not ok, (rows, mean)
    fault = ref.losses(
        cfg, seed, MINIBATCH, STEPS, "float32", "own_block_unseen")
    rows, mean, ok = compare(fault, want, *limits(cfg))
    assert not ok, (rows, mean)


def test_the_tiny_models_loss_and_gradients_against_the_reference(
        ref, model_def):
    """One record, seeded weights, float32 activations on the program's
    side: the loss and every parameter's gradient."""
    import jax
    import jax.numpy as jnp

    from elasticdl_tpu.models.sdar import sdar_moe

    cfg = tiny_config()
    gen = h.cell_mod.load_module("datagen", cfg["datagen"])
    (tokens, t, u), = gen.batches(3, 1, 1, 11, cfg["data"])
    config = dataclasses.replace(
        model_def.CONFIG, activation_dtype="float32")
    model = sdar_moe.custom_model(config)
    noised, weights = sdar_moe.noise(tokens, t, u, config.mask_token_id)
    x = {"tokens": jnp.asarray(tokens), "noised": jnp.asarray(noised)}
    y = {"targets": jnp.asarray(tokens), "weights": jnp.asarray(weights)}
    variables = model.init({"params": jax.random.PRNGKey(5)}, x)
    params, buffers = variables["params"], variables["buffers"]

    def program(p):
        out = model.apply({"params": p, "buffers": buffers}, x,
                          training=True)
        return sdar_moe.loss(y, out)

    plain = ref.make_loss(cfg["model"], "float32")

    def reference(p):
        return plain(p, buffers, jnp.asarray(tokens[0]), jnp.asarray(t[0]),
                     jnp.asarray(u[0]))

    with jax.default_matmul_precision("highest"):
        got, got_grads = jax.value_and_grad(program)(params)
        want, want_grads = jax.value_and_grad(reference)(params)
    assert float(got) == pytest.approx(float(want), rel=2e-6)
    flat_got = jax.tree_util.tree_leaves_with_path(got_grads)
    flat_want = jax.tree_util.tree_leaves(want_grads)
    assert len(flat_got) == len(flat_want) > 0
    for (path, a), b in zip(flat_got, flat_want):
        scale = float(jnp.max(jnp.abs(b))) or 1.0
        np.testing.assert_allclose(
            np.asarray(a) / scale, np.asarray(b) / scale, atol=2e-5,
            err_msg=jax.tree_util.keystr(path))


# ---------- the cell and its readers ----------


def tiny_sdar_cell():
    """The committed cell's traffic and metrics over the toy model."""
    m = copy.deepcopy(h.manifest())
    like = next(w for w in m["workloads"] if w["name"] == CELL)
    name = "tiny_sdar.steady_s8192_mb1"
    m["configs"] = [{"name": "tiny_sdar", "source": "toy", "reduced": [],
                     "why": "toy",
                     "file": "tests/benchmark/tiny_sdar.json"}]
    m["workloads"] = [dict(like, name=name, config="tiny_sdar")]
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
    reported = {m["name"] for m in cell.per_layer}
    assert set(NEW_READERS) <= reported
    assert {"moe_held_share_pct", "moe_held_load_max_over_mean",
            "device_idle_pct.lm", "idle_input_pct.lm",
            "window_compiles.lm", "step_ms_p50.lm", "warmup_s"} <= reported
    # The causal kernels' readers count a causal half and the forward
    # alone; readers of other models' keys are not given this cell; the
    # LFM2 cell's test pins `moe_block_fill_pct` to that cell alone.
    assert not {"flash_roofline", "flash_time_pct", "moe_block_fill_pct",
                "mfu_pct",
                "mfu_pct.lfm2", "moe_time_pct", "moe_swiglu_time_pct",
                "ssd_time_pct", "shortconv_time_pct"} & reported
    assert {m["name"] for m in cell.end_to_end} == {
        "setup_s", "tokens_per_s"}
    for m in h.manifest()["per_layer"]:
        if m["name"] in NEW_READERS:
            assert m["workloads"] == [CELL] and m["moves"] == "tokens_per_s"


def test_the_manifests_new_entries_keep_its_form():
    """What the driver refuses before any run: a text of an entry over 200
    characters, on two lines or not printable; a name or unit outside its
    characters; a key an entry may not have."""
    import re

    name = re.compile(r"[A-Za-z0-9_][A-Za-z0-9_.\-]{0,63}\Z")
    unit = re.compile(r"[A-Za-z0-9_/%.\-]{1,16}\Z")
    manifest = h.manifest()
    config, = (c for c in manifest["configs"] if c["name"] == "sdar_30b_a3b")
    cell, = (w for w in manifest["workloads"] if w["name"] == CELL)
    metrics = [m for m in manifest["per_layer"] if m["name"] in NEW_READERS]
    assert manifest["configs"][-1] is config
    assert manifest["workloads"][-1] is cell
    assert manifest["per_layer"][-len(metrics):] == metrics
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
    assert len(json.dumps(manifest, indent=1)) <= 64 * 1024


def test_rehearsal_of_the_cell_with_the_toy_model(capsys):
    """The normal path at minibatch 1: `edl train` on the local backend,
    the cell's traffic, the toy model, features and labels that are trees;
    `correct`, and the step's statistics, the four new counters among
    them, one event a fence."""
    cell = tiny_sdar_cell()
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
    assert events[0]["bd_positions"] == length
    assert 0 < events[0]["bd_positions_masked"] < length
    assert events[0]["moe_assignments"] == 2 * 2 * length * 2
    assert events[0]["attn_scores_needed"] == 2 * 4 * length * (length + 4)
    read = {n: h.cell_mod.load_module("metrics", n).read
            for n in NEW_READERS + ("moe_held_share_pct",)}
    fill = read["bd_attn_tile_fill_pct"](view)
    assert fill == pytest.approx(
        100 * events[0]["attn_scores_needed"]
        / events[0]["attn_scores_run"])
    assert 0 < fill <= 100
    assert 0 < read["moe_held_share_pct"](view) < 100
    # No trace in this run: the device-trace readers find nothing to read
    # and say so with None.
    for name in ("bd_attn_time_pct", "bd_attn_roofline",
                 "moe_time_pct.sdar"):
        assert read[name](view) is None


def test_the_new_readers_find_nothing_in_another_models_run():
    """A program without the counters or the kernels' names, a
    configuration of another model (the parent's cells): None, not an
    exception."""
    for other in ("lm_flagship.steady", "lfm2_24b_a2b.steady_s8192_mb2",
                  "nemotron_twotower_30b_a3b.steady_mb2"):
        cell = h.cell_mod.Cell(other)

        class View:
            t0, t1 = 10.0, 50.0
            trace = {"busy_s": 1.0, "devices": {"d": {"events": [
                [CHIP_LINES["causal"][0], 0.0, 9.0]]}}}
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


def compact(line):
    from lib import trace

    return trace.compact(line)


# HLO lines in the form the chip's profiler names `XLA Ops` events, at
# this cell's shapes, cut after the first operands. The kernels'
# instruction names are what the compiler gave them (AOT for the described
# v5e: `%jvp_bd_flash_fwd_.1`, `%transpose_jvp_bd_flash_bwd__.1`).
CHIP_LINES = {
    "fwd": [
        "%jvp_bd_flash_fwd_.1 = (bf16[32,16384,128]{2,1,0}, "
        "f32[32,16384,128]{2,1,0}) custom-call(bf16[32,16384,128]{2,1,0} "
        "%bitcast.174, bf16[32,16384,128]{2,1,0} %b, bf16[32,16384,128]"
        "{2,1,0} %c), custom_call_target=\"tpu_custom_call\"",
    ],
    "bwd": [
        "%transpose_jvp_bd_flash_bwd__.1 = (bf16[32,16384,128]{2,1,0}, "
        "bf16[32,16384,128]{2,1,0}, bf16[32,16384,128]{2,1,0}) custom-call("
        "bf16[32,16384,128]{2,1,0} %q), "
        "custom_call_target=\"tpu_custom_call\"",
    ],
    "causal": [
        "%flash_fwd.3 = (bf16[64,8192,64]{2,1,0}, f32[64,8192,128]{2,1,0})"
        " custom-call(bf16[64,8192,64]{2,1,0} %bitcast.174), "
        "custom_call_target=\"tpu_custom_call\"",
    ],
    "routing": [
        "%sort.3 = (f32[16384,128]{1,0}, s32[16384,128]{1,0}) sort("
        "f32[16384,128]{1,0} %a, s32[16384,128]{1,0} %iota), dimensions={1}",
        "%sort.9 = (s32[131072]{0}, s32[131072]{0}) sort(s32[131072]{0} %l, "
        "s32[131072]{0} %i), dimensions={0}",
    ],
    "grouped": [
        "%while.100 = (s32[]{:T(128)}, f32[16384,16,128]{2,1,0:T(8,128)}, "
        "bf16[16,2048,1536]{2,1,0:T(8,128)(2,1)}, bf16[16,768,2048]{2,1,0}, "
        "f32[132224]{0:T(1024)}) while(%tuple.5), condition=%cond, body=%b",
    ],
    "neither": [
        "%fusion.12 = bf16[1,16384,2048]{2,1,0} fusion(bf16[1,16384,4096]"
        "{2,1,0} %x, f32[4096,2048]{1,0} %o_proj), kind=kOutput",
        # The optimizer's update of the expert weights is not the layer's.
        "%fusion.918 = (f32[16,2048,1536]{2,1,0}, f32[16,2048,1536]{2,1,0}, "
        "f32[16,2048,1536]{2,1,0}) fusion(f32[16,2048,1536]{2,1,0} %w, "
        "f32[16,2048,1536]{2,1,0} %opt_state_0__nu__layers_1__w_gate_up)",
    ],
}


def cell_view():
    cell = h.cell_mod.Cell(CELL)

    class View:
        config, traffic = cell.config, cell.traffic
        device = {"kind": "TPU v5 lite", "count": 1}

    return View


@pytest.mark.parametrize("kind", sorted(CHIP_LINES))
def test_sdar_ops_are_told_by_name_and_by_the_configurations_shapes(
        ops, kind):
    matches = h.cell_mod.load_module("metrics", "_model_ops").matches
    z = ops.sizes(cell_view()())
    assert (z["rows"], z["assignments"], z["held"], z["block"]) == (
        16384, 131072, 16,
        cell_view().config["model"]["expert_block_rows"])
    for line in CHIP_LINES[kind]:
        told = {
            "routing": matches(line, (ops.routing_shape,), z),
            "grouped": matches(line, (ops.grouped_shape,), z),
        }
        kernel = ops.classify(compact(line))
        told["fwd"] = kernel is not None and kernel[0] == ops.FWD
        told["bwd"] = kernel is not None and kernel[0] == ops.BWD
        if kind == "grouped":
            told.pop("routing")  # the padded assignments ride in the loop
        assert told == {k: k == kind for k in told}, line
        if kernel:
            assert kernel[1:] == (32, 16384, 128, 2)


def test_attention_readers_on_a_made_up_trace(ops, capsys):
    """Two steps of one layer under remat: forward, its rematerialised
    twin and the backward, beside a causal call and a fusion. The time
    share counts all three calls a step; the roofline's needed work counts
    the forward once."""
    fwd, bwd = compact(CHIP_LINES["fwd"][0]), compact(CHIP_LINES["bwd"][0])
    other = compact(CHIP_LINES["neither"][0])
    causal = compact(CHIP_LINES["causal"][0])
    ms = 1e6
    events, at = [], 0.0
    for _ in range(2):
        for name, dur in ((fwd, 13 * ms), (other, 20 * ms), (fwd, 13 * ms),
                          (bwd, 24 * ms), (causal, 30 * ms)):
            events.append([name, at, dur])
            at += dur

    class View(cell_view()):
        trace = {"busy_s": at / 1e9,
                 "devices": {"/device:TPU:0": {"events": events}}}

    read = {n: h.cell_mod.load_module("metrics", n).read
            for n in NEW_READERS}
    assert read["bd_attn_time_pct"](View()) == pytest.approx(
        100 * 50 / 100)
    needed = 8192 * (8192 + 4)
    flops_fwd = 2 * 2 * 32 * needed * 128
    assert ops.kernel_flops(ops.FWD, 32, 16384, 128, 4) == flops_fwd
    assert ops.kernel_flops(ops.BWD, 32, 16384, 128, 4) == 2 * flops_fwd
    # Compute binds both: 67 M scores x 128 against 134 MB a tensor.
    least = 2 * 3 * flops_fwd / 197e12
    assert ops.kernel_bytes(ops.FWD, 32, 16384, 128, 2) / 819e9 < \
        flops_fwd / 197e12
    roof = read["bd_attn_roofline"](View())
    assert roof == pytest.approx(100 * least / (2 * 50e-3))
    assert 0 < roof < 100
    said = json.loads(capsys.readouterr().out.strip().splitlines()[-1])
    assert said["calls"] == 6 and said["calls_needed"] == 4
    assert said["binding_roof_by_call"] == {"compute": 4}


def test_a_share_counts_a_loop_and_its_body_once(ops):
    loop = CHIP_LINES["grouped"][0]
    body = "%fusion.2160 = f32[1152,1536]{1,0} fusion(bf16[16,2048,1536]" \
        "{2,1,0} %w, s32[] %e), kind=kOutput"

    class View(cell_view()):
        trace = {"busy_s": 1e-6, "devices": {"/device:TPU:0": {}}}
        _raw_device_events = {"/device:TPU:0": [
            (loop, 0.0, 400.0), (body, 100.0, 200.0), (body, 250.0, 300.0),
            (CHIP_LINES["neither"][1], 500.0, 900.0),
            (CHIP_LINES["routing"][0], 900.0, 1000.0)]}

    read = h.cell_mod.load_module("metrics", "moe_time_pct.sdar").read
    assert read(View()) == pytest.approx(50.0)
    assert ops.share_of_busy_pct(View(), (lambda dims, z: False,)) is None


def test_tile_fill_adds_up_the_windows_events():
    class View(cell_view()):
        t0, t1 = 10.0, 50.0

        def events_of(self, kinds, role_prefix=None, since=None,
                      until=None):
            assert (kinds, since, until) == ("model_stats", 10.0, 50.0)
            return [{"attn_scores_needed": 6 * 32 * 67141632.0,
                     "attn_scores_run": 6 * 32 * 83886080.0}] * 3

    fill = h.cell_mod.load_module("metrics", "bd_attn_tile_fill_pct").read
    assert fill(View()) == pytest.approx(100 * 67141632 / 83886080)
    assert fill(View()) == pytest.approx(80.04, abs=0.01)


def test_mfu_counts_the_cut_as_run_by_hand(ops):
    """Multiplying parameters a row, written out (ISSUE 43's count): the
    four projections, the router, one expert's worth of the held experts;
    two rows a record token in six layers, the head over the noised row;
    the masked attention's L + b scores a token a head."""
    z = ops.sizes(cell_view()())
    attention = 2048 * 4096 + 2 * 2048 * 512 + 4096 * 2048
    per_row = attention + 2048 * 128 + 1.0 * 3 * 2048 * 768
    assert per_row == 23_855_104
    assert ops.multiplying_params_per_row(z) == per_row
    head = 2048 * 18992
    assert head == 38_895_616
    products = 6 * (2 * 6 * per_row + head)
    attention_flops = 12 * 6 * 32 * 128 * (8192 + 4)
    assert products == pytest.approx(1.95e9, rel=2e-3)
    assert attention_flops == pytest.approx(2.42e9, rel=2e-3)
    assert ops.train_flops_per_token(z) == products + attention_flops
    # The masked attention is 55% of the needed arithmetic.
    assert attention_flops / (products + attention_flops) == pytest.approx(
        0.55, abs=0.005)

    class View(cell_view()):
        def record_rate(self):
            return 2.0  # records of 8192 tokens a second

    mfu = h.cell_mod.load_module("metrics", "mfu_pct.sdar").read(View())
    assert mfu == pytest.approx(
        100 * 2.0 * 8192 * (products + attention_flops) / 197e12)
    assert 0 < mfu < 100
