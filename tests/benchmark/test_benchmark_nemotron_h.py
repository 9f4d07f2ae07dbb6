"""The Nemotron-H reference against the program at a tiny size on the CPU,
the fp8 control, and the CPU rehearsal of the cell's traffic with the toy
hybrid model through `edl train`: model statistics and counters present."""

import copy
import dataclasses
import json

import numpy as np
import pytest

import bench_helpers as h

CELL = "nemotron_twotower_30b_a3b.steady_mb2"
STEPS = [8, 16, 24, 32]
MINIBATCH = 2


def tiny_config():
    with open(h.os.path.join(
            h.REPO, "tests", "benchmark", "tiny_nemotron_h.json")) as f:
        return json.load(f)


@pytest.fixture(scope="module")
def ref():
    return h.load_file(
        h.os.path.join(h.BENCH, "references", "nemotron_h.py"),
        "edlbench_ref_nemotron_h")


@pytest.fixture(scope="module")
def model_def():
    from elasticdl_tpu.common.model_utils import load_module

    return load_module(h.os.path.join(h.REPO, tiny_config()["model_def"]))


def test_the_tiny_configuration_file_states_the_tiny_model(model_def):
    stated = tiny_config()["model"]
    built = dataclasses.asdict(model_def.CONFIG)
    for key, value in stated.items():
        got = built[key]
        assert (list(got) if isinstance(got, tuple) else got) == value, key


def test_the_cut_configuration_file_states_the_model_def():
    """benchmark/configs/nemotron_twotower_30b_a3b.json against the
    model-def module `edl train` runs, and against the catalog's rule:
    every width as published, three keys reduced."""
    from elasticdl_tpu.models.nemotron_h import nemotron_h_twotower_cut as m

    cell = h.cell_mod.Cell(CELL)
    cfg = cell.config
    assert cfg["model_def"] == m.__name__
    built = dataclasses.asdict(m.cut_config())
    for key, value in cfg["model"].items():
        if key in ("param_dtype", "parameters", "remat_reason"):
            continue
        got = built[key]
        assert (list(got) if isinstance(got, tuple) else got) == value, key
    differs = {k for k, v in m.PUBLIC_CONFIG.items() if cfg.get(k) != v}
    assert differs == set(cfg["reduced"]) == {
        "num_hidden_layers", "n_routed_experts", "vocab_size"}
    assert cfg["published"]["n_routed_experts"] == \
        cfg["model"]["n_routed_experts"] == 128
    assert cfg["data"]["vocab"] == cfg["vocab_size"] == 16384
    assert "denoiser" in cfg["not_built"]


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


@pytest.mark.parametrize("seed", [101, 104, 2**31 + 11])
def test_program_passes_and_the_fp8_control_fails(ref, model_def, seed):
    cfg = tiny_config()
    limits = (cfg["reference"]["loss_abs_limit"],
              cfg["reference"]["loss_mean_limit"])
    compare = h.run_module().compare_losses
    want = ref.losses(cfg, seed, MINIBATCH, STEPS, "float32")
    got, stats = program_losses(seed, cfg, model_def)
    rows, mean, ok = compare(got, want, *limits)
    assert ok, (rows, mean)
    # The step hands the routed layers' counts back beside the loss.
    made = float(stats["moe_assignments"])
    assert made == 2 * MINIBATCH * cfg["data"]["seq_len"] * 2
    assert 0 < float(stats["moe_assignments_held"]) < made
    control = ref.losses(cfg, seed, MINIBATCH, STEPS, "fp8")
    rows, mean, ok = compare(control, want, *limits)
    assert not ok, (rows, mean)


def test_a_reference_without_its_routed_experts_fails_the_limits(ref):
    """The planted fault: the comparison that decides `correct` sees the
    routed part of the layer."""
    cfg = tiny_config()
    limits = (cfg["reference"]["loss_abs_limit"],
              cfg["reference"]["loss_mean_limit"])
    want = ref.losses(cfg, 101, MINIBATCH, STEPS, "float32")
    fault = ref.losses(cfg, 101, MINIBATCH, STEPS, "float32", "no_routed")
    rows, mean, ok = h.run_module().compare_losses(fault, want, *limits)
    assert not ok, (rows, mean)


@pytest.mark.parametrize("forced", [False, True])
def test_whole_model_loss_and_gradients_against_the_reference(
        ref, model_def, forced):
    """Seeded weights, float32 activations on both sides: the program's
    layers (chunked scan, sorted grouped experts, broadcast GQA) against
    the plain equations, value and every gradient; with the routers'
    own logits and, as the cut runs it, with `force_load_balancing`."""
    import jax
    import jax.numpy as jnp

    from elasticdl_tpu.models.nemotron_h import nemotron_h as nh

    cfg = tiny_config()
    datagen = h.cell_mod.load_module("datagen", cfg["datagen"])
    x, y = next(datagen.batches(0, 1, 4, 7, cfg["data"]))
    params, buffers = ref.initial_variables(cfg["model_def"], 7, x)
    model = nh.custom_model(dataclasses.replace(
        model_def.CONFIG, activation_dtype="float32",
        force_load_balancing=forced))
    loss_one = ref.make_loss(
        dict(cfg["model"], force_load_balancing=forced), "float32")

    def program(p):
        out, _ = model.apply(
            {"params": p, "buffers": buffers}, jnp.asarray(x),
            training=True, mutable=["buffers"])
        return model_def.loss(jnp.asarray(y), out)

    def reference(p):
        return jnp.mean(jnp.stack([
            loss_one(p, buffers, jnp.asarray(x[i]), jnp.asarray(y[i]), i,
                     len(x))
            for i in range(len(x))]))

    with jax.default_matmul_precision("highest"):
        want, want_grads = jax.value_and_grad(reference)(params)
        got, got_grads = jax.value_and_grad(program)(params)
    assert abs(float(got) - float(want)) < 2e-5
    worst = jax.tree_util.tree_map(
        lambda a, b: float(jnp.max(jnp.abs(a - b))
                           / (jnp.max(jnp.abs(a)) + 1e-12)),
        want_grads, got_grads)
    for path, err in jax.tree_util.tree_leaves_with_path(worst):
        assert err < 2e-4, (jax.tree_util.keystr(path), err)
    # Straight through the noise, the router still takes a gradient.
    assert np.asarray(want_grads["layers_1"]["mixer"]["router"]).any()


def tiny_nemotron_cell():
    """The committed cell's traffic and metrics over the toy hybrid."""
    m = copy.deepcopy(h.manifest())
    like = next(w for w in m["workloads"] if w["name"] == CELL)
    name = "tiny_nemotron_h.steady_mb2"
    m["configs"] = [{"name": "tiny_nemotron_h", "source": "toy",
                     "reduced": [], "why": "toy",
                     "file": "tests/benchmark/tiny_nemotron_h.json"}]
    m["workloads"] = [dict(like, name=name, config="tiny_nemotron_h")]
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


def test_rehearsal_of_the_cell_with_the_toy_hybrid(capsys):
    """The normal path: `edl train` on the local backend, the cell's
    traffic, the toy hybrid; `correct`, and the routed layers' statistics
    where the issue puts them (one event a fence; the readers add up the
    window's)."""
    cell = tiny_nemotron_cell()
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
    fences = [s for _, s, _ in h.load_file(
        h.os.path.join(h.BENCH, "lib", "view.py"), "v").step_losses(view.log)]
    assert events and len(events) <= len(fences)
    assert all(e["step"] % cell.traffic["log_loss_steps"] == 0
               for e in events)
    tokens = cell.traffic["minibatch"] * cell.config["record_tokens"]
    assert events[0]["moe_assignments"] == 2 * tokens * 2  # 2 E layers, k 2
    readers = {n: h.cell_mod.load_module("metrics", n) for n in (
        "moe_held_share_pct", "moe_held_load_max_over_mean",
        "ssd_time_pct", "moe_time_pct")}
    share = readers["moe_held_share_pct"].read(view)
    assert 0 < share < 100
    assert readers["moe_held_load_max_over_mean"].read(view) >= 1.0
    # No trace in this run: the device-trace readers find nothing to read
    # and say so with None.
    assert readers["ssd_time_pct"].read(view) is None
    assert readers["moe_time_pct"].read(view) is None


def test_readers_of_the_new_metrics_find_nothing_in_a_dense_lm_run():
    """A program without the counters (the parent, or the flagship):
    None, not an exception."""
    class View:
        t0, t1, trace = 10.0, 50.0, None

        def events_of(self, kinds, role_prefix=None, since=None,
                      until=None):
            return []

    for name in ("moe_held_share_pct", "moe_held_load_max_over_mean",
                 "ssd_time_pct", "moe_time_pct"):
        assert h.cell_mod.load_module("metrics", name).read(View()) is None


# HLO lines of `XLA Ops` events as the chip's profiler names them (read
# from this cell's trace, PR 27), cut after the first operands.
CHIP_LINES = {
    "scan": [
        "%fusion.757 = f32[2,64,8,8,64,128]{5,4,3,2,1,0:T(8,128)} fusion("
        "f32[2,64,128,8,8,64]{2,1,5,4,3,0:T(8,128)} %bitcast.472, "
        "f32[2,64,128,8,8]{2,4,3,1,0:T(8,128)} %fusion.761), kind=kOutput",
        "%copy.2901 = f32[1024,8,64,128]{3,2,1,0:T(8,128)} copy("
        "f32[1024,8,64,128]{2,3,1,0:T(8,128)} %bitcast.9)",
        "%fusion.12 = (f32[2,8,8,64]{3,2,1,0}, f32[2,8,8,64]{3,2,1,0}) "
        "fusion(f32[2,8192,64]{2,1,0} %x), kind=kOutput",
        # The boundary: reads a chunked tensor, writes the mixer's layout.
        "%reshape.77 = f32[2,8192,4096]{2,1,0} reshape("
        "f32[2,64,128,8,8,64]{5,4,3,2,1,0} %fusion.800)",
    ],
    "moe": [
        "%while.100 = (s32[]{:T(128)}, f32[16384,2688]{1,0:T(8,128)}, "
        "f32[8,2688,1856]{1,2,0:T(8,128)}, f32[8,1856,2688]{2,1,0}, "
        "f32[99328]{0:T(1024)}) while(%tuple.5), condition=%cond, body=%b",
        "%compare_select_fusion.16 = bf16[16384,3712]{1,0:T(8,128)(2,1)} "
        "fusion(bf16[16384,3712]{1,0} %fusion.1272, bf16[16384,2688]{1,0} "
        "%bitcast.1897, f32[3712,2688]{1,0} %copy.3283), kind=kOutput",
        "%sort.3 = (f32[16384,128]{1,0}, s32[16384,128]{1,0}) sort("
        "f32[16384,128]{1,0} %a, s32[16384,128]{1,0} %iota), dimensions={1}",
        "%sort.9 = (s32[98304]{0}, s32[98304]{0}) sort(s32[98304]{0} %l, "
        "s32[98304]{0} %i), dimensions={0}",
    ],
    "neither": [
        "%fusion.958 = bf16[2,8192,10304]{1,2,0:T(8,128)(2,1)} fusion("
        "bf16[2,8192,2688]{2,1,0} %remat2.208, f32[2688,10304]{0,1} "
        "%variables__params____layers_0____mixer____in_proj____kernel)",
        "%flash_fwd.3 = (f32[64,8192,128]{2,1,0}, f32[64,8192,128]{2,1,0})"
        " custom-call(f32[64,8192,128]{2,1,0} %bitcast.174), "
        "custom_call_target=\"tpu_custom_call\"",
        "%broadcast.5 = f32[2,2,16,8192,128]{4,3,2,1,0} broadcast("
        "f32[2,2,8192,128]{3,2,1,0} %k)",
        # The optimizer's update of the expert weights is not the layer's.
        "%fusion.918 = (f32[8,2688,1856]{1,2,0}, f32[8,2688,1856]{1,2,0}, "
        "f32[8,2688,1856]{1,2,0}) fusion(f32[8,2688,1856]{1,2,0} %w, "
        "f32[8,2688,1856]{1,2,0} %opt_state_0__nu__layers_1__w_up)",
    ],
}


@pytest.mark.parametrize("kind", sorted(CHIP_LINES))
def test_model_ops_are_told_by_the_configurations_shapes(kind):
    ops = h.cell_mod.load_module("metrics", "_model_ops")
    cell = h.cell_mod.Cell(CELL)

    class View:
        config, traffic = cell.config, cell.traffic

    z = ops._sizes(View())
    assert (z["chunks"], z["assignments"], z["held"]) == (64, 98304, 8)
    for line in CHIP_LINES[kind]:
        scan = ops.matches(line, (ops.scan_shape,), z)
        moe = ops.matches(line, (ops.routing_shape, ops.grouped_shape,
                                 ops.shared_shape), z)
        assert (scan, moe) == (kind == "scan", kind == "moe"), line


def test_a_share_counts_a_loop_and_its_body_once():
    ops = h.cell_mod.load_module("metrics", "_model_ops")
    cell = h.cell_mod.Cell(CELL)
    loop = CHIP_LINES["moe"][0]
    body = "%fusion.2160 = f32[1024,1856]{1,0} fusion(bf16[8,2688,1856]" \
        "{2,1,0} %w, s32[] %e), kind=kOutput"

    class View:
        config, traffic = cell.config, cell.traffic
        trace = {"busy_s": 1e-6, "devices": {"/device:TPU:0": {}}}
        _raw_device_events = {"/device:TPU:0": [
            (loop, 0.0, 400.0), (body, 100.0, 200.0), (body, 250.0, 300.0),
            (CHIP_LINES["neither"][0], 500.0, 900.0),
            (CHIP_LINES["scan"][0], 900.0, 1000.0)]}

    assert ops.share_of_busy_pct(
        View(), (ops.grouped_shape,)) == pytest.approx(40.0)
    assert ops.share_of_busy_pct(
        View(), (ops.scan_shape,)) == pytest.approx(10.0)
    assert ops.share_of_busy_pct(View(), (ops.shared_shape,)) is None
