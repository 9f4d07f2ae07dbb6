"""The map of a compiled step's scopes (`observability/step_scopes.py`):
what it reads out of the compiled text, who writes it and when, and the
hazard that forbids renaming a scope (the compile cache's key does not
see names)."""

import glob
import json
import os
import re
import threading

import flax.linen as nn
import jax
import jax.numpy as jnp
import optax
import pytest

import test_module
from elasticdl_tpu.common.constants import JobType
from elasticdl_tpu.common.model_utils import get_model_spec
from elasticdl_tpu.data.reader import InMemoryReader
from elasticdl_tpu.observability import events as obs_events
from elasticdl_tpu.observability import step_scopes as ss
from elasticdl_tpu.worker.master_client import MasterClient
from elasticdl_tpu.worker.worker import Worker

from test_utils import start_master

ATTENTION_SCOPE, MLP_SCOPE = "toy_attention", "toy_mlp"
TABLES = ({ATTENTION_SCOPE: ss.ATTENTION, MLP_SCOPE: ss.MLP}, {})


class Block(nn.Module):
    attention_scope: str = ATTENTION_SCOPE

    @nn.compact
    def __call__(self, x):
        with jax.named_scope(self.attention_scope):
            x = x + jnp.tanh(nn.Dense(16, name="q_proj")(x))
        with jax.named_scope(MLP_SCOPE):
            x = x + nn.Dense(16, name="up")(jax.nn.relu(x))
        return x


class Toy(nn.Module):
    attention_scope: str = ATTENTION_SCOPE

    @nn.compact
    def __call__(self, x):
        x = nn.Dense(16, name="embed")(x)
        x = Block(self.attention_scope, name="layers_0")(x)
        x = nn.remat(Block)(self.attention_scope, name="layers_1")(x)
        return nn.Dense(4, name="head")(x)


def _toy_step(attention_scope=ATTENTION_SCOPE):
    """(jitted step, its arguments' shapes): the toy model under Adam,
    the update behind a barrier as the one-device steps have it."""
    model, tx = Toy(attention_scope), optax.adam(1e-3)
    x, y = jnp.ones((8, 16)), jnp.ones((8, 4))
    params = model.init(jax.random.PRNGKey(0), x)

    def step(params, opt_state, x, y):
        def loss(p):
            return jnp.mean((model.apply(p, x) - y) ** 2)

        value, grads = jax.value_and_grad(loss)(params)
        grads = jax.tree_util.tree_map(jax.lax.optimization_barrier, grads)
        updates, opt_state = tx.update(grads, opt_state, params)
        return optax.apply_updates(params, updates), opt_state, value

    return jax.jit(step), ss.abstract_of((params, tx.init(params), x, y))


@pytest.fixture(scope="module")
def toy_rows():
    step, shapes = _toy_step()
    text = step.lower(*shapes).compile().as_text()
    return text, ss.rows_of(text, TABLES)


def test_every_instruction_of_the_compiled_text_has_a_row(toy_rows):
    text, (module, rows) = toy_rows
    assert module.startswith("jit_step")
    names = [r["name"] for r in rows]
    assert len(set(names)) == len(names)
    fused = set(re.findall(r"calls=%?([^\s,)}]+)", text))
    computation, missing = None, []
    for line in text.splitlines():
        head = re.match(r"^(?:ENTRY )?%?([^\s(]+) \(.*\) -> .* \{$", line)
        if head:
            computation = head.group(1)
        elif line.startswith("}"):
            computation = None
        elif computation and computation not in fused:
            inst = re.match(r"^\s+(?:ROOT )?%?(\S+) = ", line)
            if inst and inst.group(1) not in names:
                missing.append(inst.group(1))
    assert not missing
    assert set(rows[0]) == {"name", "opcode", "phase", "layer", "scope",
                            "kind"}


def test_the_rows_hold_all_four_phases_and_the_toys_kinds(toy_rows):
    _, (_, rows) = toy_rows
    named = [r for r in rows if r["phase"] != ss.NONE]
    assert {r["phase"] for r in named} == {
        ss.FWD, ss.BWD, ss.REMAT, ss.UPDATE}
    # Only the rematerialised layer runs its forward again.
    assert {r["layer"] for r in named if r["phase"] == ss.REMAT} == {1}
    assert {r["layer"] for r in named} == {0, 1, None}
    by_kind = {}
    for r in named:
        by_kind.setdefault(r["kind"], set()).add(r["scope"])
    assert set(by_kind) >= {ss.ATTENTION, ss.MLP, ss.EMBED_HEAD_LOSS,
                            ss.UPDATE}
    assert all(ATTENTION_SCOPE in s for s in by_kind[ss.ATTENTION])
    assert all(MLP_SCOPE in s for s in by_kind[ss.MLP])
    assert by_kind[ss.UPDATE] == {""}
    # Transforms, `checkpoint` and the primitive are no part of a scope.
    scopes = {r["scope"] for r in named}
    assert f"layers_1/{ATTENTION_SCOPE}/q_proj" in scopes
    assert not any(re.search(r"jvp|transpose|checkpoint|remat|jit\(", s)
                   for s in scopes)
    for r in rows:
        assert ("crosses" in r) == (r["opcode"] == "fusion")
        if r["phase"] == ss.NONE:
            assert (r["kind"], r["layer"], r["scope"]) == (ss.OTHER, None, "")


@pytest.mark.parametrize("op_name,phase,layer,scope", [
    ("jit(step_fn)/jvp(M)/layers_1/self_attn/a_scope/mla_flash_fwd/"
     "pallas_call", ss.FWD, 1, "layers_1/self_attn/a_scope/mla_flash_fwd"),
    ("jit(step_fn)/transpose(jvp(M))/jvp(M)/checkpoint/layers_2/mlp/"
     "moe_grouped/while/body/closed_call/dot_general", ss.BWD, 2,
     "layers_2/mlp/moe_grouped"),
    ("jit(step_fn)/transpose(jvp(M))/jvp(M)/checkpoint/"
     "rematted_computation/layers_3/mixer/jit(relu)/max", ss.REMAT, 3,
     "layers_3/mixer"),
    ("jit(step_fn)/transpose(jvp(M))/transpose(jvp(layers_3._call))/jvp(M)/"
     "layers_3.shared_forward_fn/jvp(layers_3._call)/checkpoint/"
     "rematted_computation/layers_3/self_attn/a_scope/mul", ss.REMAT, 3,
     "layers_3/self_attn/a_scope"),
    ("jit(step_fn)/jvp(M)/Block_11/Dense_0/dot_general;"
     "jit(step_fn)/jvp(M)/Block_11/Dense_0/add", ss.FWD, 11,
     "Block_11/Dense_0"),
    ("jit(step_fn)/mul", ss.UPDATE, None, ""),
    ("variables['params']['layers_5']['kernel']", ss.NONE, None, ""),
    ("reduce_sum", ss.NONE, None, ""),
    ("", ss.NONE, None, ""),
])
def test_what_an_op_name_says(op_name, phase, layer, scope):
    read = ss._Scoped(op_name)
    assert (read.phase, read.layer, "/".join(read.path)) == (
        phase, layer, scope)


def test_the_models_scope_constants_all_stand_in_the_table():
    """Every `*_SCOPE` a layer or a model exports has a kind: a new one
    that the table does not know fails here, not on the chip."""
    import importlib

    scopes, modules = ss.scope_kinds()
    kinds = {ss.ATTENTION, ss.MOE, ss.MIXER, ss.MLP}
    assert set(scopes.values()) <= kinds and set(modules.values()) <= kinds
    for name in ("layers.moe", "layers.mamba2", "layers.short_conv",
                 "models.sdar.sdar_moe", "models.lfm2.lfm2_moe",
                 "models.granite_hybrid.granite_hybrid",
                 "models.mellum.mellum_moe", "models.kanana.kanana_moe"):
        module = importlib.import_module(f"elasticdl_tpu.{name}")
        for key, value in vars(module).items():
            if key.endswith("_SCOPE") and isinstance(value, str):
                assert value in scopes, f"{name}.{key}"
            elif key == "SCOPES":
                assert set(value.values()) <= set(scopes)


def test_a_layers_module_takes_the_kind_its_scoped_instructions_agree_on():
    """The hybrid calls whatever a layer holds `mixer`, and the routed
    layers' `mlp` is a dense MLP elsewhere: an instruction outside every
    scope is of the kind the scoped ones of its module agree on, and only
    then of its module's name."""
    tables = ({"moe_routing": ss.MOE, "ssd_scan": ss.MIXER},
              {"mlp": ss.MLP, "q_proj": ss.ATTENTION})

    def kinds(*op_names, kernel=()):
        said = [ss._Scoped(f"jit(s)/jvp(M)/{n}", n in kernel)
                for n in op_names]
        ss._set_kinds(said, tables)
        return [s.kind for s in said]

    assert kinds("layers_0/mlp/moe_routing/sort", "layers_0/mlp/add",
                 "layers_1/mlp/add", "layers_2/mixer/ssd_scan/exp",
                 "layers_2/mixer/in_proj/dot_general",
                 "layers_3/mixer/q_proj/dot_general",
                 "layers_3/mixer/convert_element_type",
                 "layers_3/mixer/flash_fwd/pallas_call",
                 "layers_4/norm/mul", "lm_head/dot_general",
                 kernel=("layers_3/mixer/flash_fwd/pallas_call",)) == [
        ss.MOE, ss.MOE, ss.MLP, ss.MIXER, ss.MIXER, ss.ATTENTION,
        ss.ATTENTION, ss.ATTENTION_KERNEL, ss.OTHER, ss.EMBED_HEAD_LOSS]


def test_asking_twice_gives_the_same_rows():
    step, shapes = _toy_step()
    step(*jax.tree_util.tree_map(
        lambda s: jnp.ones(s.shape, s.dtype), shapes))
    first = ss.step_scope_map(step, shapes)
    assert first == ss.step_scope_map(step, shapes)
    assert first["fn"] == "step" and first["hlo_module"] == "jit_step"
    assert json.loads(json.dumps(first)) == first


def test_a_scopes_name_alone_does_not_move_the_compile_caches_key():
    """Why this PR renames no scope: jax takes the persistent cache's key
    with debug information stripped, so a step that differs from a cached
    one in its `named_scope`s alone loads the cached executable, whose
    `op_name`s are the OLDER tree's. If a jax upgrade makes this fail,
    names have entered the key and the hazard is gone: say so in
    `docs/OBSERVABILITY.md`."""
    import numpy as np
    from jax._src import cache_key, compiler

    def key(scope):
        step, shapes = _toy_step(scope)
        module = step.lower(*shapes).compiler_ir("stablehlo")
        assert f"{scope}/q_proj" in module.operation.get_asm(
            enable_debug_info=True)
        device = jax.devices()[0]
        return cache_key.get(
            module, np.array([device]),
            compiler.get_compile_options(num_replicas=1, num_partitions=1),
            device.client)

    assert not jax.config.jax_compilation_cache_include_metadata_in_key
    assert key("toy_attention") == key("toy_attention_renamed")
    assert key("toy_attention") != key("toy_attention") + "x"


_HEARD = []


def _hear(event, duration, **kwargs):
    _HEARD.append(event)


def test_the_map_of_a_running_step_costs_no_second_trace_or_compile():
    """The trainer hands over the mesh its step is called in: lowered
    inside it, with shapes that carry the arrays' shardings, `lower` finds
    what the step traced and lowered and `compile` the executable it runs
    with. (Outside it, or with bare shapes, jax's key differs and the
    whole step is traced, lowered and compiled again: tens of seconds of
    the worker's CPU at a cell's size, beside the loop that feeds the
    chip; PR 57's first chip run did that.)"""
    from elasticdl_tpu.worker.allreduce_trainer import AllReduceTrainer

    records = test_module.make_linear_records(32)
    reader = InMemoryReader(records)
    features, labels = test_module.feed(records[:16], "training", None)
    with start_master(
        training_shards=reader.create_shards(), records_per_task=16,
        with_membership=True,
    ) as m:
        mc = MasterClient(m["addr"], 0, worker_host="127.0.0.1:0")
        trainer = AllReduceTrainer(
            test_module.custom_model(), test_module.loss,
            test_module.optimizer(), mc)
        try:
            assert trainer.step_for_scopes() is None
            for _ in range(2):
                trainer.train_minibatch(features, labels)
            step, shapes, mesh = trainer.step_for_scopes()
            assert mesh is trainer._mesh
            if not _HEARD:
                jax.monitoring.register_event_duration_secs_listener(_hear)
            done = {}

            def on_a_thread(context):
                del _HEARD[:]
                done["map"] = ss.step_scope_map(step, shapes, context)
                done["heard"] = [e for e in _HEARD if e.endswith((
                    "jaxpr_to_mlir_module_duration",
                    "backend_compile_duration"))]

            for context, again in ((mesh, False), (None, True)):
                thread = threading.Thread(
                    target=on_a_thread, args=(context,))
                thread.start()
                thread.join(120)
                assert not thread.is_alive()
                assert bool(done.pop("heard")) == again, context
                assert done.pop("map")["fn"] == "allreduce_step"
        finally:
            trainer.close()


def _run_worker(tmp_path, profile_dir):
    from elasticdl_tpu.worker.allreduce_trainer import AllReduceTrainer

    records = test_module.make_linear_records(96)
    reader = InMemoryReader(records)
    log = obs_events.EventLog(str(tmp_path / "events.jsonl"), job="j")
    obs_events.set_event_log(log)
    before = {t.ident for t in threading.enumerate()}
    seen = []
    start = threading.Thread.start

    def noted(thread):
        seen.append(thread.name)
        return start(thread)

    threading.Thread.start = noted
    try:
        with start_master(
            training_shards=reader.create_shards(), records_per_task=16,
            with_membership=True,
        ) as m:
            mc = MasterClient(m["addr"], 0, worker_host="127.0.0.1:0")
            trainer = AllReduceTrainer(
                test_module.custom_model(), test_module.loss,
                test_module.optimizer(), mc, steps_per_world_check=2,
            )
            try:
                Worker(
                    0, mc, reader, get_model_spec("test_module"), trainer,
                    minibatch_size=16, job_type=JobType.TRAINING_ONLY,
                    log_loss_steps=2, profile_dir=profile_dir,
                    profile_start_step=3, profile_steps=2,
                ).run()
            finally:
                trainer.close()
    finally:
        threading.Thread.start = start
        obs_events.set_event_log(None)
        log.close()
        ss.note_running_step(None)
    assert before  # the threads of the process were listed
    return obs_events.read_events(str(tmp_path / "events.jsonl")), seen


def test_the_worker_writes_the_map_at_the_end_of_a_profile_window(tmp_path):
    profile_dir = str(tmp_path / "prof")
    events, threads = _run_worker(tmp_path, profile_dir)
    written, = [e for e in events if e["kind"] == "step_scopes_written"]
    assert written["path"] == os.path.join(profile_dir, ss.FILE_NAME)
    assert written["fn"] == "allreduce_step" and written["seconds"] > 0
    with open(written["path"]) as f:
        scopes = json.load(f)
    assert set(scopes) == {"fn", "hlo_module", "rows"}
    assert scopes["hlo_module"].startswith("jit_")
    assert written["instructions"] == len(scopes["rows"])
    assert 0 < written["with_op_name"] == sum(
        1 for r in scopes["rows"] if r["phase"] != ss.NONE)
    assert {r["phase"] for r in scopes["rows"]} >= {
        ss.FWD, ss.BWD, ss.UPDATE}
    # The trace it stands beside was written first.
    profile, = [e for e in events if e["kind"] == "profile_written"]
    assert profile["ts"] <= written["ts"]
    assert glob.glob(
        os.path.join(profile_dir, "**", "*.xplane.pb"), recursive=True)
    assert threads.count("edl-step-scopes") == 1
    assert not os.path.exists(written["path"] + ".part")


def test_without_a_profile_dir_the_worker_writes_no_map(tmp_path):
    events, threads = _run_worker(tmp_path, "")
    assert not [e for e in events if e["kind"] == "step_scopes_written"]
    assert "edl-step-scopes" not in threads
    assert not glob.glob(str(tmp_path / "**" / ss.FILE_NAME), recursive=True)


def test_an_on_demand_profile_carries_the_map_of_the_running_step(tmp_path):
    """`/debug/profile` of a worker: `capture_device_profile` writes the
    map of the step the worker said it runs into the capture's directory;
    a process that runs no step writes a trace and no map; a step that
    cannot be lowered is a warning, never the capture's failure."""
    from elasticdl_tpu.observability import profiling

    step, shapes = _toy_step()
    log = obs_events.EventLog(str(tmp_path / "events.jsonl"), job="j")
    obs_events.set_event_log(log)
    try:
        bare = profiling.capture_device_profile(0.1, str(tmp_path / "bare"))
        ss.note_running_step(lambda: (step, shapes, None))
        summary = profiling.capture_device_profile(
            0.1, str(tmp_path / "worker"))
        ss.note_running_step(lambda: (step, shapes[:1], None))
        broken = profiling.capture_device_profile(
            0.1, str(tmp_path / "broken"))
    finally:
        ss.note_running_step(None)
        obs_events.set_event_log(None)
        log.close()
    assert ss.FILE_NAME in summary["files"]
    assert ss.FILE_NAME not in bare["files"] + broken["files"]
    assert any(f.endswith(".xplane.pb") for f in broken["files"])
    written, = [e for e in obs_events.read_events(
        str(tmp_path / "events.jsonl")) if e["kind"] == "step_scopes_written"]
    assert written["path"] == os.path.join(summary["dir"], ss.FILE_NAME)
    assert written["fn"] == "step"


@pytest.mark.parametrize("collecting", [True, False])
def test_the_collector_rests_while_the_rows_are_made(
        tmp_path, monkeypatch, collecting):
    """The rows are enough small objects to start a full collection, which
    holds the interpreter while the worker's loop needs it (dp4's device
    ran dry for 0.2 s so): the map is made with the cyclic collector off,
    and the state it found is put back, also after a failure."""
    import gc

    step, shapes = _toy_step()
    seen = []
    real = ss.step_scope_map

    def watched(*args, **kwargs):
        seen.append(gc.isenabled())
        if len(seen) == 2:
            raise RuntimeError("no text")
        return real(*args, **kwargs)

    monkeypatch.setattr(ss, "step_scope_map", watched)
    was = gc.isenabled()
    (gc.enable if collecting else gc.disable)()
    try:
        assert ss.write_step_scopes(str(tmp_path), step, shapes)
        assert gc.isenabled() == collecting
        assert ss.write_step_scopes(str(tmp_path), step, shapes) is None
        assert gc.isenabled() == collecting
    finally:
        (gc.enable if was else gc.disable)()
    assert seen == [False, False]
