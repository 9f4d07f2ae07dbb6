"""AOT compiles for a DESCRIBED TPU v5e — no chip, so never a chip run.

The TPU compiler is installed here and compiles for a topology that is
described, not attached: what it refuses (an unaligned tile, too much
VMEM, a program over 16 GB, a kernel the partitioner cannot split) costs
no chip time. Kept to this one file on purpose: only one process may load
the TPU library, so the topology is described inside a module-scoped
fixture — never at import, in a skipif, in parametrize or in conftest —
and every compile runs in this process. The persistent compile cache is
off around them (an AOT entry cannot be read back without a chip).
"""

import contextlib
import os
import re
from typing import Any, NamedTuple

import jax
import jax.numpy as jnp
import numpy as np
import pytest
from jax.sharding import NamedSharding, PartitionSpec as P
from jax.sharding import SingleDeviceSharding

from elasticdl_tpu.ops import causal_conv as cc
from elasticdl_tpu.ops import flash_attention as fa
from elasticdl_tpu.ops import qk_rotary as qr
from elasticdl_tpu.ops import ssd_scan as ss
from elasticdl_tpu.parallel import step_plan

pytestmark = pytest.mark.usefixtures("no_persistent_compile_cache")

HBM_BYTES = 16 * 2**30

# [B, H, S, D]: the flagship (8 x 128 heads, S=4096, minibatch 4), its
# S=8192 sibling, the 64-wide-head variant, and the LFM2 cut's own call
# (32 heads of 64, minibatch 2 x S 8192).
FLAGSHIP_SHAPES = [
    (4, 8, 4096, 128),
    (2, 8, 8192, 128),
    (4, 16, 4096, 64),
    (2, 32, 8192, 64),
]


_HLO_OP = re.compile(r"\s*(?:ROOT )?%\S+ = (.*?) ([a-z][a-z0-9\-]*)\(")
_HLO_ARRAY = re.compile(r"(bf16|f32)\[([0-9,]*)\]")
_THREE_OF_ONE_F32 = re.compile(r"\((f32\[[0-9,]+\]), \1, \1\)")


def _without_layouts(hlo_shape):
    return re.sub(r"\{[^{}]*\}", "", hlo_shape)


@pytest.fixture(scope="module")
def topo():
    os.environ.setdefault("TPU_LOG_DIR", "disabled")
    from jax.experimental import topologies

    try:
        return topologies.get_topology_desc(
            platform="tpu", topology_name="v5e:2x2"
        )
    except Exception as e:
        pytest.skip(f"no v5e:2x2 topology can be described here: {e}")


@pytest.fixture(scope="module")
def one_chip(topo):
    return SingleDeviceSharding(topo.devices[0])


def _steer_to_the_kernel(monkeypatch):
    """jax.default_backend() is the CPU here, so the dispatch would take
    its CPU branch; steer it as the chip would (in the test, not through
    an option of the program)."""
    monkeypatch.delenv("EDL_FORCE_PALLAS_INTERPRET", raising=False)
    monkeypatch.setattr(fa, "_use_pallas", lambda: True)


@pytest.fixture()
def kernel_on(monkeypatch):
    _steer_to_the_kernel(monkeypatch)


def _qkv(shape, sharding, dtype=jnp.float32):
    return [jax.ShapeDtypeStruct(shape, dtype, sharding=sharding)] * 3


# What crosses the kernels' boundary: float32 (the hybrid's call site and
# the context-parallel wrappers) or the activation dtype (the flagship).
OPERAND_DTYPES = ["float32", "bfloat16"]


_OPERAND_LAYOUTS = re.compile(
    r"operand_layout_constraints=\{((?:[^{}]|\{[^{}]*\})*)\}"
)


def _scatter_results(hlo_text):
    """The result shapes of the scatters in a compiled text, without
    layouts."""
    return [
        _without_layouts(m.group(1))
        for m in map(_HLO_OP.match, hlo_text.split("\n"))
        if m and m.group(2) == "scatter"
    ]


def _kernel_calls(hlo_text, named=""):
    """[(result shapes, [operand shapes])] of the Mosaic calls in a
    compiled text, without layouts: all of them, or those whose
    instruction's name (the kernel's own) holds `named`."""
    calls = []
    for line in hlo_text.split("\n"):
        m = _HLO_OP.match(line)
        if (m and m.group(2) == "custom-call" and "tpu_custom_call" in line
                and named in line.split(" = ")[0]):
            operands = _OPERAND_LAYOUTS.search(line).group(1)
            calls.append((
                _without_layouts(m.group(1)),
                [f"{t}[{dims}]" for t, dims in _HLO_ARRAY.findall(operands)],
            ))
    return calls


@pytest.mark.parametrize("dtype", OPERAND_DTYPES)
@pytest.mark.parametrize("shape", FLAGSHIP_SHAPES, ids=str)
def test_flash_forward_compiles_for_v5e(one_chip, kernel_on, shape, dtype):
    compiled = (
        jax.jit(lambda q, k, v: fa.flash_attention(q, k, v, True))
        .lower(*_qkv(shape, one_chip, dtype))
        .compile()
    )
    assert compiled.as_text().count("tpu_custom_call") == 1


@pytest.mark.parametrize("dtype", OPERAND_DTYPES)
@pytest.mark.parametrize("shape", FLAGSHIP_SHAPES, ids=str)
def test_flash_forward_backward_compiles_for_v5e(
    one_chip, kernel_on, shape, dtype
):
    def loss(q, k, v):
        return jnp.sum(fa.flash_attention(q, k, v, True))

    compiled = (
        jax.jit(jax.grad(loss, argnums=(0, 1, 2)))
        .lower(*_qkv(shape, one_chip, dtype))
        .compile()
    )
    # forward (with lse) and the one backward kernel; S 8192 is the case
    # that asks the most VMEM (dq's float32 row is 4 MiB there).
    b, h, s, d = shape
    hlo = {"float32": "f32", "bfloat16": "bf16"}[dtype]
    block = f"{hlo}[{b * h},{s},{d}]"
    lse = f"f32[{b * h},{s},{fa.LANES}]"
    assert sorted(r for r, _ in _kernel_calls(compiled.as_text())) == sorted(
        [f"({block}, {lse})", f"({block}, {block}, {block})"]
    )


def test_flash_kernel_partitions_over_a_data_mesh(topo, kernel_on):
    """A multi-device jit refuses a bare Mosaic kernel ("cannot be
    automatically partitioned"); under the trainer's abstract mesh each
    batch shard runs the kernel on its own rows."""
    mesh = jax.sharding.Mesh(np.array(topo.devices), ("data",))
    sharded = NamedSharding(mesh, P("data"))

    def loss(q, k, v):
        with jax.sharding.use_abstract_mesh(mesh.abstract_mesh):
            return jnp.sum(fa.flash_attention(q, k, v, True))

    compiled = (
        jax.jit(jax.grad(loss, argnums=(0, 1, 2)))
        .lower(*_qkv((16, 8, 4096, 128), sharded))
        .compile()
    )
    text = compiled.as_text()
    assert text.count("tpu_custom_call") == 2
    # Per device: 4 of the 16 rows.
    assert "f32[4,8,4096,128]" in text


# x [B, S, H, P], state, chunk: the granite cut's scan (one group).
GRANITE_SCAN = ((1, 8192, 64, 64), 128, 256)


def _scan_operands(sharding, shape=GRANITE_SCAN[0], groups=1,
                   state=GRANITE_SCAN[1]):
    bsz, s, h, _ = shape

    def of(dims, dtype):
        return jax.ShapeDtypeStruct(dims, dtype, sharding=sharding)

    replicated = (NamedSharding(sharding.mesh, P())
                  if isinstance(sharding, NamedSharding) else sharding)
    return (of(shape, jnp.bfloat16), of((bsz, s, h), jnp.float32),
            jax.ShapeDtypeStruct((h,), jnp.float32, sharding=replicated),
            of((bsz, s, groups, state), jnp.bfloat16),
            of((bsz, s, groups, state), jnp.bfloat16))


def _scan_loss(*operands):
    return jnp.sum(ss.ssd_scan(
        *operands, GRANITE_SCAN[2], dtype=jnp.bfloat16) ** 2)


@pytest.mark.parametrize("backward", [False, True],
                         ids=["forward", "backward"])
def test_ssd_scan_kernels_compile_for_v5e(one_chip, kernel_on, backward):
    """The chunked scan's two kernels at the granite cell's shape ([1,
    8192, 64, 64], state 128, chunk 256, bfloat16 operands): the VMEM
    they ask, their [128, 128] mask tiles, the heads' traced indices and
    the transposes of eight heads' rows are the chip's
    compiler's to refuse. Forward: y. Backward: y and the entering
    states, then dx, dB, dC, d(dt a) and dt's part through x * dt."""
    fn = jax.grad(_scan_loss, argnums=(0, 1, 2, 3, 4)) if backward else (
        _scan_loss)
    text = jax.jit(fn).lower(*_scan_operands(one_chip)).compile().as_text()
    y, x = "f32[1,1,64,64,8192]", "bf16[1,1,64,64,8192]"
    b, dt = "bf16[1,1,128,8192]", "f32[1,1,64,8192]"
    calls = [f"({y}, f32[1,32,1,4096,128])", f"({x}, {b}, {b}, {dt}, {dt})"]
    assert sorted(r for r, _ in _kernel_calls(text)) == sorted(
        calls if backward else [y])
    assert ("ssd_scan_bwd" in text) == backward and "ssd_scan_fwd" in text


def test_ssd_scan_partitions_over_a_data_mesh(topo, kernel_on):
    """As the flash kernels: under the trainer's abstract mesh each batch
    shard scans its own rows (no cell runs the scan across chips)."""
    mesh = jax.sharding.Mesh(np.array(topo.devices), ("data",))
    sharded = NamedSharding(mesh, P("data"))

    def loss(*operands):
        with jax.sharding.use_abstract_mesh(mesh.abstract_mesh):
            return _scan_loss(*operands)

    text = (
        jax.jit(jax.grad(loss, argnums=(0, 1, 2, 3, 4)))
        .lower(*_scan_operands(sharded, shape=(4, 8192, 64, 64)))
        .compile().as_text()
    )
    assert text.count("tpu_custom_call") == 2
    # Per device: 1 of the 4 rows.
    assert "f32[1,1,64,64,8192]" in text and "f32[4,1,64,64" not in text


# proj [B, S, W] and the widths of z, x, B, C and dt in it: the granite
# cut's in-projection (4 taps, bias).
GRANITE_CONV = ((1, 8192, 8512), (4096, 4096, 128, 128, 64))
GRANITE_CONV_CALLS = {
    "forward": "(bf16[1,4096,8192], bf16[1,128,8192], bf16[1,128,8192])",
    "backward": "(bf16[1,4352,8192], f32[1,4,4352], f32[1,1,4352])",
}


def _conv_operands(sharding, shape=GRANITE_CONV[0]):
    replicated = (NamedSharding(sharding.mesh, P())
                  if isinstance(sharding, NamedSharding) else sharding)
    conv = sum(GRANITE_CONV[1][1:4])
    return (jax.ShapeDtypeStruct(shape, jnp.bfloat16, sharding=sharding),
            jax.ShapeDtypeStruct((4, conv), jnp.float32,
                                 sharding=replicated),
            jax.ShapeDtypeStruct((conv,), jnp.float32, sharding=replicated))


def _conv_loss(proj, weight, bias):
    return sum(jnp.sum(part.astype(jnp.float32) ** 2)
               for part in cc.causal_conv_silu(
                   proj, GRANITE_CONV[1], weight, bias))


@pytest.mark.parametrize("backward", [False, True],
                         ids=["forward", "backward"])
def test_causal_conv_kernels_compile_for_v5e(one_chip, kernel_on, backward):
    """The convolution stage's two kernels at the granite cell's shape
    (xBC rows 4096 .. 8447 of [1, 8512, 8192], 4 taps, bfloat16): the
    rotations along the lanes, the turns' traced rows and the [128, 128]
    squares turned over are the chip's compiler's to refuse. Forward: x,
    B and C apart. Backward: those again, then d xBC, d weight and d
    bias."""
    fn = jax.grad(_conv_loss, argnums=(0, 1, 2)) if backward else _conv_loss
    text = jax.jit(fn).lower(*_conv_operands(one_chip)).compile().as_text()
    calls = [GRANITE_CONV_CALLS["forward"]] + (
        [GRANITE_CONV_CALLS["backward"]] if backward else [])
    assert sorted(r for r, _ in _kernel_calls(text)) == sorted(calls)
    assert ("causal_conv_bwd" in text) == backward
    assert "causal_conv_fwd" in text


def test_causal_conv_partitions_over_a_data_mesh(topo, kernel_on):
    """As the scan's kernels: under the trainer's abstract mesh each batch
    shard convolves its own rows, and d weight and d bias are summed over
    the shards by the program (the taps cross the boundary a copy a
    row)."""
    mesh = jax.sharding.Mesh(np.array(topo.devices), ("data",))
    sharded = NamedSharding(mesh, P("data"))

    def loss(*operands):
        with jax.sharding.use_abstract_mesh(mesh.abstract_mesh):
            return _conv_loss(*operands)

    text = (
        jax.jit(jax.grad(loss, argnums=(0, 1, 2)))
        .lower(*_conv_operands(sharded, shape=(4, 8192, 8512)))
        .compile().as_text()
    )
    assert text.count("tpu_custom_call") == 2
    # Per device: 1 of the 4 rows, and the taps' gradient reduced.
    assert "bf16[1,4352,8192]" in text and "bf16[4,4352,8192]" not in text
    assert "all-reduce" in text


# The SDAR and the Mellum cells' projections [B, S, H, Dh]: q's 32 heads
# and k's 4 at 16,384 rows, and the tables [1, S, Dh]. A layer's four
# calls by their results: q and k turned, and their way back with d
# weight's partial sums a grid block.
QK_ROTARY_ROWS = 16384
QK_ROTARY_CALLS = [
    "bf16[1,32,16384,128]", "bf16[1,4,16384,128]",
    "(bf16[1,16384,4096], f32[1,64,8,128])",
    "(bf16[1,16384,512], f32[1,64,8,128])"]


def _qk_operands(sharding, heads, bsz=1):
    replicated = (NamedSharding(sharding.mesh, P())
                  if isinstance(sharding, NamedSharding) else sharding)
    table = jax.ShapeDtypeStruct(
        (1, QK_ROTARY_ROWS, 128), jnp.float32, sharding=replicated)
    return (jax.ShapeDtypeStruct((bsz, QK_ROTARY_ROWS, heads, 128),
                                 jnp.bfloat16, sharding=sharding),
            jax.ShapeDtypeStruct((128,), jnp.float32, sharding=replicated),
            table, table)


def _qk_loss(x, weight, cos, sin):
    return jnp.sum(
        qr.qk_rotary(x, weight, 1e-6, cos, sin).astype(jnp.float32) ** 2)


@pytest.mark.parametrize("backward", [False, True],
                         ids=["forward", "backward"])
@pytest.mark.parametrize("heads", [32, 4], ids=["q32", "k4"])
def test_qk_rotary_kernels_compile_for_v5e(one_chip, kernel_on, heads,
                                           backward):
    """The head norm and the rotary turn's two kernels at the SDAR and the
    Mellum cells' shapes ([1, 16384, 32, 128] and [1, 16384, 4, 128],
    bfloat16): the rotation by half a head, the sums over a head's lanes,
    the blocks' VMEM and the fold of d weight a sublane apart are the
    chip's compiler's to refuse. Forward: the turned heads [1, H, S, Dh].
    Backward: those again, then the projection's cotangent [1, S, H * Dh]
    and d weight's partial sums a grid block."""
    fn = jax.grad(_qk_loss, argnums=(0, 1)) if backward else _qk_loss
    text = jax.jit(fn).lower(
        *_qk_operands(one_chip, heads)).compile().as_text()
    tiles = qr._tiles((1, QK_ROTARY_ROWS, heads, 128)).tiles
    turned = f"bf16[1,{heads},16384,128]"
    back = f"(bf16[1,16384,{heads * 128}], f32[1,{tiles},8,128])"
    assert sorted(r for r, _ in _kernel_calls(text)) == sorted(
        [turned, back] if backward else [turned])
    assert ("qk_rotary_bwd" in text) == backward and "qk_rotary_fwd" in text


def test_qk_rotary_partitions_over_a_data_mesh(topo, kernel_on):
    """As the scan's kernels: under the trainer's abstract mesh each batch
    shard turns its own rows, and d weight is summed over the shards by
    the program (the weight and the tables cross the boundary a copy a
    row)."""
    mesh = jax.sharding.Mesh(np.array(topo.devices), ("data",))
    sharded = NamedSharding(mesh, P("data"))

    def loss(*operands):
        with jax.sharding.use_abstract_mesh(mesh.abstract_mesh):
            return _qk_loss(*operands)

    text = (
        jax.jit(jax.grad(loss, argnums=(0, 1)))
        .lower(*_qk_operands(sharded, 32, bsz=4))
        .compile().as_text()
    )
    assert text.count("tpu_custom_call") == 2
    # Per device: 1 of the 4 rows, and the weight's gradient reduced.
    assert "bf16[1,32,16384,128]" in text
    assert "bf16[4,32,16384,128]" not in text
    assert "all-reduce" in text


def test_unservable_sequence_raises_where_the_kernel_runs(kernel_on):
    q = jnp.zeros((1, 1, 1100, 128), jnp.float32)
    with pytest.raises(ValueError, match="not a multiple"):
        jax.eval_shape(lambda q: fa.flash_attention(q, q, q, True), q)


def test_overlong_dq_row_raises_where_the_kernel_runs(kernel_on):
    """The backward holds one [S, D] float32 row of dq in VMEM; a sequence
    whose row cannot be held names the sequence-parallel paths instead of
    taking a second one in silence. The forward still serves it."""
    q = jax.ShapeDtypeStruct((1, 1, 65536, 128), jnp.float32)

    def loss(q):
        return jnp.sum(fa.flash_attention(q, q, q, True))

    assert jax.eval_shape(loss, q).shape == ()
    with pytest.raises(ValueError, match="ring or Ulysses"):
        jax.eval_shape(jax.grad(loss), q)
    # The longest row the budget admits at these blocks is served.
    q = jax.ShapeDtypeStruct((1, 1, 32768, 128), jnp.float32)
    assert jax.eval_shape(jax.grad(loss), q).shape == q.shape


def _plan_step(m, rows_a_chip, seq, topo, monkeypatch, n_devices=1,
               batch_of=None):
    """The training step of AllReduceTrainer for model-def module `m` as
    the speculator plans it for the first `n_devices` described chips:
    (trainer, step, abstract args, mesh). The caller closes the trainer.
    `batch_of(batch, seq)` gives the abstract (features, labels) where
    they are not token rows."""
    from elasticdl_tpu.parallel.mesh import WorldTopology, resolve_world_spec
    from elasticdl_tpu.worker.allreduce_trainer import AllReduceTrainer

    class NoMaster:
        worker_host = "127.0.0.1"

    batch = rows_a_chip * n_devices
    trainer = AllReduceTrainer(
        m.custom_model(), m.loss, m.optimizer(), NoMaster()
    )
    try:
        tokens = jax.ShapeDtypeStruct((batch, seq), jnp.int32)
        features, labels = (
            batch_of(batch, seq) if batch_of else (tokens, tokens))
        rng = jax.random.PRNGKey(0)
        variables = jax.eval_shape(
            lambda r, f: dict(
                trainer._model.init(
                    {"params": r, "dropout": r}, f, training=False
                )
            ),
            rng, features,
        )
        trainer._variables = variables
        trainer._opt_state = jax.eval_shape(
            trainer._optax.init, variables["params"]
        )
        trainer._step_rng_base = rng
        trainer._note_batch_abstract(features, labels, batch)
        # The trainer builds its mesh from jax.devices(): hand it the
        # described chips.
        devices = list(topo.devices)[:n_devices]
        monkeypatch.setattr(jax, "devices", lambda *a, **k: devices)
        spec = resolve_world_spec(
            trainer._parallel_config(),
            WorldTopology(
                n_devices=n_devices, local_devices=n_devices, n_processes=1
            ),
            param_check=trainer._param_check,
        )
        _, step, abstract = trainer.plan_step_for_spec(spec, batch)
        return trainer, step, abstract, spec.build_mesh()
    except BaseException:
        trainer.close()
        raise


def _plan_flagship_step(topo, monkeypatch, n_devices):
    """The flagship at `flagship_config()` widths, 4 rows of 4096 a chip:
    the program `edl train` runs in `lm_flagship.steady` and `.dp4`."""
    from elasticdl_tpu.models.transformer import transformer_lm_flagship as m

    planned = _plan_step(m, 4, 4096, topo, monkeypatch, n_devices)
    n_params = sum(
        int(np.prod(p.shape))
        for p in jax.tree_util.tree_leaves(planned[0]._variables["params"])
    )
    if n_params < 200e6:  # 151M transformer + embeddings + head
        planned[0].close()
        raise AssertionError(f"not the flagship: {n_params} parameters")
    return planned


def _resident_bytes(compiled):
    mem = compiled.memory_analysis()
    return (
        mem.argument_size_in_bytes + mem.output_size_in_bytes
        - mem.alias_size_in_bytes + mem.temp_size_in_bytes
    )


_TRACE_EVENT = "/jax/core/compile/jaxpr_trace_duration"
_traces = {"heard": 0, "listening": False}


def _hear_a_trace(event, duration, **kwargs):
    if event == _TRACE_EVENT:
        _traces["heard"] += 1


@contextlib.contextmanager
def _counted_traces():
    """The number (a list of one) of jaxprs traced inside: the step's own
    and every nested jit's, a `jnp` operator on a traced value among them
    (what `step_load_s` sums the seconds of). Every cache is emptied
    first, so the count is a new process's, whatever ran before."""
    jax.clear_caches()
    if not _traces["listening"]:
        jax.monitoring.register_event_duration_secs_listener(_hear_a_trace)
        _traces["listening"] = True
    before, count = _traces["heard"], [0]
    try:
        yield count
    finally:
        count[0] = _traces["heard"] - before


class _CompiledStep(NamedTuple):
    """A planned step, lowered and compiled once a module (30 to 60 s a
    compile): what the tests below read of it."""

    mesh: Any
    weights: Any  # {"f32[rows,columns]"}: the shapes of the parameters
    n_grad_leaves: int
    out_tree: Any
    lowered: str  # step.lower(...).as_text(): what the program asked for
    text: str  # compiled.as_text(): what the TPU compiler made of it
    resident: int
    argument_bytes: int
    traces: int  # jaxprs traced by step.lower(...)


def _compile_planned(plan):
    """`plan(monkeypatch)` -> (trainer, step, abstract, mesh), under a
    patch of its own that ends with the compile."""
    with pytest.MonkeyPatch.context() as mp:
        _steer_to_the_kernel(mp)
        trainer, step, abstract, mesh = plan(mp)
        try:
            with _counted_traces() as traces:
                lowered = step.lower(*abstract)
            compiled = lowered.compile()
            params = jax.tree_util.tree_leaves(
                trainer._variables["params"]
            )
        finally:
            trainer.close()
    return _CompiledStep(
        mesh=mesh,
        weights={
            f"f32[{','.join(str(d) for d in p.shape)}]" for p in params
        },
        n_grad_leaves=len(params),
        out_tree=jax.tree_util.tree_structure(lowered.out_info),
        lowered=lowered.as_text(),
        text=compiled.as_text(),
        resident=_resident_bytes(compiled),
        argument_bytes=compiled.memory_analysis().argument_size_in_bytes,
        traces=traces[0],
    )


@pytest.fixture(scope="module")
def flagship_one_chip(topo, no_persistent_compile_cache_in_module):
    return _compile_planned(lambda mp: _plan_flagship_step(topo, mp, 1))


@pytest.fixture(scope="module")
def flagship_dp4(topo, no_persistent_compile_cache_in_module):
    return _compile_planned(lambda mp: _plan_flagship_step(topo, mp, 4))


@pytest.fixture(scope="module")
def nemotron_h_cut_one_chip(topo, no_persistent_compile_cache_in_module):
    from elasticdl_tpu.models.nemotron_h import nemotron_h_twotower_cut as m

    return _compile_planned(lambda mp: _plan_step(m, 2, 8192, topo, mp))


@pytest.fixture(scope="module")
def lfm2_cut_one_chip(topo, no_persistent_compile_cache_in_module):
    from elasticdl_tpu.models.lfm2 import lfm2_24b_a2b_cut as m

    return _compile_planned(lambda mp: _plan_step(m, 2, 8192, topo, mp))


def _block_diffusion_batch(batch, seq):
    """SDAR's features and labels: a record's clean and noised copies;
    its targets and weights."""
    ids = jax.ShapeDtypeStruct((batch, seq), jnp.int32)
    weights = jax.ShapeDtypeStruct((batch, seq), jnp.float32)
    return ({"tokens": ids, "noised": ids},
            {"targets": ids, "weights": weights})


@pytest.fixture(scope="module")
def sdar_cut_one_chip(topo, no_persistent_compile_cache_in_module):
    from elasticdl_tpu.models.sdar import sdar_30b_a3b_cut as m

    return _compile_planned(lambda mp: _plan_step(
        m, 1, 8192, topo, mp, batch_of=_block_diffusion_batch))


@pytest.fixture(scope="module")
def granite_cut_one_chip(topo, no_persistent_compile_cache_in_module):
    from elasticdl_tpu.models.granite_hybrid import (
        granite_4_0_h_micro_cut as m,
    )

    return _compile_planned(lambda mp: _plan_step(m, 1, 8192, topo, mp))


@pytest.fixture(scope="module")
def mellum_cut_one_chip(topo, no_persistent_compile_cache_in_module):
    from elasticdl_tpu.models.mellum import mellum2_12b_a2_5b_cut as m

    return _compile_planned(lambda mp: _plan_step(m, 1, 16384, topo, mp))


@pytest.fixture(scope="module")
def kanana_cut_one_chip(topo, no_persistent_compile_cache_in_module):
    from elasticdl_tpu.models.kanana import kanana_2_30b_a3b_cut as m

    return _compile_planned(lambda mp: _plan_step(m, 1, 16384, topo, mp))


def test_flagship_step_compiles_and_fits_one_v5e(flagship_one_chip):
    """The WHOLE flagship training step of AllReduceTrainer — the program
    `edl train` runs at `flagship_config()` widths, minibatch 4 — for one
    described chip: it contains the Pallas calls and fits 16 GB."""
    # 12 layers x (flash_fwd, flash_bwd)
    assert flagship_one_chip.text.count("tpu_custom_call") == 24
    resident = flagship_one_chip.resident
    assert resident < HBM_BYTES, f"{resident / 2**30:.2f} GiB"


@pytest.mark.parametrize(
    "fixture,resident_with_f32_operands",
    # Resident bytes a chip at the parent of PR 36, which handed the
    # kernels float32 copies of q, k, v and kept them and o as residuals.
    [("flagship_one_chip", 12_017_048_576), ("flagship_dp4", 11_908_217_344)],
)
def test_flagship_step_hands_its_kernels_the_activation_dtype(
    request, fixture, resident_with_f32_operands
):
    """q, k, v cross the flash kernels' boundary as the bfloat16 the
    configuration states and o, dq, dk, dv come back as it, on one chip
    and inside dp4's per-shard `shard_map` alike: the float32 the softmax
    needs is made tile by tile in VMEM. What that takes off the step: the
    float32 residuals of 12 layers (1.6 GB) and, between the kernels,
    every `convert` that rounded a kernel's result."""
    step = request.getfixturevalue(fixture)
    block, lse = "bf16[32,4096,128]", "f32[32,4096,128]"
    # flash_fwd: q, k, v -> o, lse; flash_bwd: q, k, v, dO and the
    # lane-replicated lse and delta -> dq, dk, dv.
    assert sorted(_kernel_calls(step.text)) == sorted(
        12 * [(f"({block}, {lse})", 3 * [block])]
        + 12 * [(f"({block}, {block}, {block})", 4 * [block] + 2 * [lse])]
    )
    entry = step.text[step.text.index("\nENTRY"):]
    rounded = [
        line for line in entry.split("\n")
        if (m := _HLO_OP.match(line)) and m.group(2) == "convert"
        and _without_layouts(m.group(1)) == block
    ]
    assert not rounded, rounded[:3]
    assert step.resident <= resident_with_f32_operands - 1.4e9, step.resident


@pytest.mark.parametrize(
    "fixture", ["flagship_one_chip", "nemotron_h_cut_one_chip",
                "lfm2_cut_one_chip"])
def test_the_steps_kernels_keep_their_operand_and_result_counts(
    request, fixture
):
    """What the benchmark's readers key on (`benchmark/metrics/
    _pallas_attention.py:classify`, behind `flash_roofline` and
    `flash_time_pct` in four cells): the causal forward is a
    `tpu_custom_call` of 3 operands and 2 results, the backward of 6 and
    3. A table operand for the grid's decode would take `flash_fwd` out of
    both readers; the decode is arithmetic over constants instead."""
    step = request.getfixturevalue(fixture)
    counts = {
        (len(operands), len(_HLO_ARRAY.findall(results)))
        for results, operands in _kernel_calls(step.text)
    }
    assert counts == {(3, 2), (6, 3)}


@pytest.mark.parametrize(
    "mask,s,steps",
    [(True, 4096, 10), (True, 8192, 36), (True, 16384, 136),
     (fa.BlockDiffusion(4, 8192), 16384, 80), (False, 4096, 16)],
    ids=["causal4096", "causal8192", "causal16384", "sdar", "unmasked"],
)
def test_the_kernels_grids_hold_the_run_tiles_alone(mask, s, steps):
    """Both passes' `pallas_call`s, as traced at the cells' shapes over
    1024 x 1024 tiles: the grid is (batch*heads, run tiles), so no grid
    step is spent on a tile that does not run; `grid_steps` is the static
    counter of it."""
    assert fa.grid_steps(mask, s, 1024, 1024) == steps
    x = jax.ShapeDtypeStruct((1, 2, s, 128), jnp.bfloat16)

    def both(q, k, v, g):
        o, lse = fa._flash_forward(q, k, v, mask, 1024, 1024, True)
        return fa._flash_backward(q, k, v, o, lse, g, mask, 1024, 1024)

    calls = [
        eqn for eqn in jax.make_jaxpr(both)(x, x, x, x).jaxpr.eqns
        if eqn.primitive.name == "pallas_call"
    ]
    prefix = "bd_" if isinstance(mask, fa.BlockDiffusion) else ""
    assert [
        (c.params["name"], c.params["grid_mapping"].grid,
         len(c.invars), len(c.outvars))
        for c in calls
    ] == [
        (prefix + "flash_fwd", (2, steps), 3, 2),
        (prefix + "flash_bwd", (2, steps), 6, 3),
    ]


def test_nemotron_h_cut_step_still_hands_its_kernels_float32(
    nemotron_h_cut_one_chip,
):
    """The hybrid's call site (`models/nemotron_h/nemotron_h.py`) keeps
    its upcasts: that program is the parent's, so its cell's thin loss
    limits are not played by a changed rounding."""
    calls = _kernel_calls(nemotron_h_cut_one_chip.text)
    assert calls
    for results, operands in calls:
        assert results.startswith("(f32[64,8192,128], "), results
        assert set(operands) == {"f32[64,8192,128]"}, operands


def _update_fusions(step):
    """{fusion kind: [weight shapes]} of the entry computation's fusions
    whose result is a tuple of three float32 arrays of one weight's
    shape: the new parameter and both Adam moments of that weight.
    `kOutput` is a fusion that ends in a product (the weight's gradient,
    with the update pulled in behind it), `kLoop` the elementwise update
    alone."""
    found = {}
    entry = step.text[step.text.index("\nENTRY"):]
    for line in entry.split("\n"):
        m = _HLO_OP.match(line)
        if m is None or m.group(2) != "fusion":
            continue
        shape = _THREE_OF_ONE_F32.fullmatch(_without_layouts(m.group(1)))
        if shape is not None and shape.group(1) in step.weights:
            kind = re.search(r"kind=(\w+)", line).group(1)
            found.setdefault(kind, []).append(shape.group(1))
    return found


def test_flagship_one_chip_step_compiles_its_update_apart(
    flagship_one_chip,
):
    """On one device nothing stands between the backward and the
    optimizer, and the TPU compiler then pulls Adam into the output
    fusion of each weight-gradient product (49 of them at the parent:
    four matrices a layer and the LM head), where the product runs at 53
    to 84% of its roof (PERF.md section 6, PR 31). With one
    `optimization_barrier` a gradient leaf the products compile alone and
    the update as loop fusions."""
    step = flagship_one_chip
    assert step_plan.update_apart_for(step.mesh)
    assert step.lowered.count("optimization_barrier") == step.n_grad_leaves
    fusions = _update_fusions(step)
    assert "kOutput" not in fusions, fusions["kOutput"]
    # Every matrix's update is still there, as an elementwise loop.
    assert len(fusions["kLoop"]) >= 49
    assert "f32[1024,32768]" in fusions["kLoop"]


def test_dp4_step_is_lowered_without_a_barrier(flagship_dp4):
    """Over several devices GSPMD's all-reduce already stands between
    each product and the update, so the products compile alone; a barrier
    there only perturbs the schedule (the compiler's own estimate of the
    dp4 step rises 3% with one). The lowered text is read: the compiled
    text keeps no trace of a barrier."""
    assert not step_plan.update_apart_for(flagship_dp4.mesh)
    assert "optimization_barrier" not in flagship_dp4.lowered
    assert "kOutput" not in _update_fusions(flagship_dp4)


def _all_reduces(hlo_text):
    """(blocking, fused): the `all-reduce` operations of the entry
    computation, which hold the core for their whole length, and those the
    compiler placed inside compute fusions (`%async_collective_fusion.N`),
    whose steps run beside the fusions' own work; each as {result shape
    without layouts: bytes}. A fused one is split over several such
    fusions, each naming the whole buffer, so it is counted once a
    shape."""
    blocking, fused = {}, {}
    computation = None
    for line in hlo_text.split("\n"):
        if line.startswith(("%", "ENTRY")):
            computation = line.split(" ")[0]
            continue
        m = _HLO_OP.match(line)
        if m is None or m.group(2) != "all-reduce":
            continue
        shape = _without_layouts(m.group(1))
        size = sum(
            int(np.prod([int(d) for d in dims.split(",") if d]))
            * (2 if dtype == "bf16" else 4)
            for dtype, dims in _HLO_ARRAY.findall(shape)
        )
        if computation == "ENTRY":
            blocking[shape] = blocking.get(shape, 0) + size
        elif computation.startswith("%async_collective_fusion"):
            fused[shape] = size
    return blocking, fused


def test_flagship_dp4_step_overlaps_its_gradient_all_reduces(flagship_dp4):
    """The flagship step over the described v5e:2x2 (global minibatch 16,
    `lm_flagship.dp4`): it compiles, fits one chip's 16 GB, keeps its 24
    kernels, and the all-reduces the TPU compiler can overlap run inside
    compute fusions. Without `dp_overlap_for`'s options this program
    holds six blocking all-reduces of 512 MB a step in its entry
    computation. An `all-reduce` there that merely carries
    `async_collective_name` is one the compiler made asynchronous, found
    no fusion for and folded back, so the assertion is on where the
    operations are, not on that attribute. The compiler fuses an
    all-reduce of one array only: what stays blocking is the combiner's
    tuples (the layers' bf16 matrices, the biases, the norm vectors)."""
    assert step_plan.dp_overlap_for(flagship_dp4.mesh, zero1=False)
    text = flagship_dp4.text
    assert text.count("tpu_custom_call") == 24
    resident = flagship_dp4.resident
    assert resident < HBM_BYTES, f"{resident / 2**30:.2f} GiB"
    blocking, fused = _all_reduces(text)
    # The LM head's float32 weight gradient (134 MB, the largest single
    # reduction of the step) and the embedding's scatter-add (67 MB).
    assert fused == {
        "f32[1024,32768]": 1024 * 32768 * 4,
        "bf16[32768,1024]": 32768 * 1024 * 2,
    }
    # No all-reduce of a single array is left holding the core.
    assert blocking and all(s.startswith("(") for s in blocking), blocking
    assert sum(blocking.values()) < 320e6


def test_flagship_one_chip_step_takes_no_option(topo, kernel_on,
                                                monkeypatch):
    """A world of one device has no all-reduce: `dp_overlap_for` says no,
    the step's jit gets no compiler option, and what it lowers is text for
    text the plain `jax.jit` of the same step body (the one with its
    update apart, which is all that parts it from the step of a world of
    several devices)."""
    from elasticdl_tpu.observability import profiling

    seen = []
    real = profiling.tracked_jit

    def recording(fn, **kwargs):
        seen.append(kwargs)
        return real(fn, **kwargs)

    monkeypatch.setattr(profiling, "tracked_jit", recording)
    trainer, step, abstract, mesh = _plan_flagship_step(topo, monkeypatch, 1)
    try:
        assert not step_plan.dp_overlap_for(mesh, zero1=False)
        (kwargs,) = seen
        assert "compiler_options" not in kwargs
        assert kwargs["event_fields"] == {
            "dp_overlap": False, "update_apart": True,
        }
        planned = step.lower(*abstract).as_text()
        repl = NamedSharding(mesh, P())
        data = NamedSharding(mesh, P("data"))
        plain = jax.jit(
            step_plan.dp_step_fn(
                trainer._step_model(), mesh, abstract[3].shape[0],
                update_apart=True,
            ),
            in_shardings=(repl, repl, repl, data, data),
            out_shardings=(repl, repl, repl),
            donate_argnums=(0, 1),
        ).lower(*abstract).as_text()
    finally:
        trainer.close()
    # A Mosaic kernel's serialized body carries the call stack it was
    # traced under (jax 0.9.0 keeps debug locations there), which differs
    # between the two jit objects; everything else has to be the same.
    payload = re.compile(r"[A-Za-z0-9+/=]{64,}")
    assert planned.count("tpu_custom_call") == 24
    assert payload.sub("KERNEL", planned) == payload.sub("KERNEL", plain)


def test_nemotron_h_cut_step_compiles_and_fits_one_v5e(
    nemotron_h_cut_one_chip
):
    """The WHOLE training step of the Nemotron-H cut (667 M parameters at
    16 bytes each, minibatch 2 x S 8192, as `edl train` runs
    `nemotron_h_twotower_cut`) for one described chip: the flash kernels
    at S 8192 under 32 broadcast heads, the chunked scan, the dynamic
    loop of the grouped expert product; it fits 16 GB with the remat the
    model-def states, and hands its statistics back beside the loss."""
    step = nemotron_h_cut_one_chip
    # The third output is {"loss", "stats"}: 1 + 4 scalars.
    assert step.out_tree.children()[2].num_leaves == 5
    # One attention layer: flash_fwd (and its rematerialised twin) and
    # flash_bwd.
    assert 2 <= step.text.count("tpu_custom_call") <= 3
    # Its mixers say nothing of the scan: `ssd_chunked`, the program it
    # had, decay mask and all; nor of the convolution stage: the module
    # imports nothing of `ops/causal_conv.py`, and no call of it is in
    # the step.
    assert "ssd_scan_fwd" not in step.text
    from elasticdl_tpu.models.nemotron_h import nemotron_h

    assert "causal_conv" not in step.text
    assert not any(
        "causal_conv" in f"{getattr(v, '__name__', '')} "
        f"{getattr(v, '__module__', '')}" for v in vars(nemotron_h).values())
    assert "tensor<2x64x8x8x128x128xf32>" in step.lowered
    assert step.resident < HBM_BYTES, f"{step.resident / 2**30:.2f} GiB"
    # params + Adam m and v
    assert step.argument_bytes > 7.9e9


def test_nemotron_h_cut_step_compiles_its_update_apart(
    nemotron_h_cut_one_chip
):
    """The same family is the hybrid cell's first too (the mixers'
    `in_proj` `f32[2688,10304] x3`, the head's `f32[2688,16384] x3`): with
    a barrier a gradient leaf no weight-gradient product is compiled with
    Adam inside it. Per-leaf barriers do not make the gradients live
    together: the step still fits (the test above)."""
    step = nemotron_h_cut_one_chip
    # One a gradient leaf, beside the one `jax.checkpoint` gives each of
    # the nine rematerialised blocks, and beside the routed layers' own:
    # `layers/moe.py` hands the router's pick over flat behind one, T * k
    # numbers forward (again in the block's rematerialised twin) and
    # T * E backward, in each of the four routed layers.
    own = re.findall(
        r"optimization_barrier [^\n]*: tensor<(98304|2097152)xf32>",
        step.lowered)
    assert sorted(own) == 4 * ["2097152"] + 8 * ["98304"]
    assert step.lowered.count("optimization_barrier") == (
        step.n_grad_leaves + 9 + len(own))
    fusions = _update_fusions(step)
    assert "kOutput" not in fusions, fusions["kOutput"]
    assert {"f32[2688,10304]", "f32[2688,16384]"} <= set(fusions["kLoop"])



def test_lfm2_cut_step_compiles_and_fits_one_v5e(lfm2_cut_one_chip):
    """The WHOLE training step of the LFM2-24B-A2B cut (648 M parameters at
    16 bytes each, minibatch 2 x S 8192, as `edl train` runs
    `lfm2_24b_a2b_cut`) for one described chip: the flash kernels at head
    64, half a lane row, handed the activation dtype; the dynamic loops of
    the gated grouped product, which put a block's rows back into a carry
    of whole tiles a token, `[16384, 16, 128]`, and never into `[16384,
    2048]` (a row there is one sublane of 16 tiles it shares with seven
    other tokens); it fits 16 GB with the remat the model-def states, and
    hands six counters back beside the loss."""
    step = lfm2_cut_one_chip
    assert step.out_tree.children()[2].num_leaves == 7
    # Two attention layers: flash_fwd, its rematerialised twin, flash_bwd.
    calls = _kernel_calls(step.text)
    assert 4 <= len(calls) <= 6
    for results, operands in calls:
        assert results.startswith("(bf16[64,8192,64], "), results
        assert set(operands) <= {"bf16[64,8192,64]", "f32[64,8192,128]"}
    assert step.resident < HBM_BYTES, f"{step.resident / 2**30:.2f} GiB"
    # params + Adam m and v
    assert step.argument_bytes > 7.7e9
    assert {"f32[8,2048,3072]", "f32[8,1536,2048]", "f32[2048,6144]",
            "f32[8192,2048]"} <= step.weights
    # Six routed layers, a loop forward and a loop backward each.
    scatters = _scatter_results(step.text)
    assert scatters.count("f32[16384,16,128]") == 12, scatters
    assert "f32[16384,2048]" not in scatters


# Bytes, by this compile at the parent of PR 54.
SDAR_RESIDENT_BEFORE_THE_PICK = 16_744_711_168


def test_sdar_cut_step_compiles_and_fits_one_v5e(sdar_cut_one_chip):
    """The WHOLE training step of the SDAR-30B-A3B cut (645.6 M parameters
    at 16 bytes each, minibatch 1 x 8192 record tokens = 16,384 rows, as
    `edl train` runs `sdar_30b_a3b_cut`) for one described chip: the flash
    kernels under the block-diffusion mask at `[32, 16384, 128]`, handed
    the activation dtype and carrying their own names; the gated grouped
    product over 16 held experts; the untied head over the noised half
    alone; it fits 16 GB with the remat the model-def states, and hands
    ten counters back beside the loss."""
    step = sdar_cut_one_chip
    assert step.out_tree.children()[2].num_leaves == 11
    calls = _kernel_calls(step.text, "flash_")
    # Six layers: bd_flash_fwd (and a rematerialised twin), bd_flash_bwd.
    assert 12 <= len(calls) <= 18
    for results, operands in calls:
        assert results.startswith("(bf16[32,16384,128], "), results
        assert set(operands) <= {"bf16[32,16384,128]", "f32[32,16384,128]"}
    # q and k of six layers, turned by `qk_rotary_fwd` into the bfloat16
    # the flash kernels take, and `qk_rotary_bwd` back: what a traced
    # run's ops table counts a step.
    assert sorted(r for r, _ in _kernel_calls(step.text, "qk_rotary_")) == (
        sorted(6 * QK_ROTARY_CALLS))
    assert len(_kernel_calls(step.text)) == len(calls) + 24
    assert step.text.count("bd_flash_fwd") >= 6
    assert step.text.count("bd_flash_bwd") >= 6
    assert step.resident < HBM_BYTES, f"{step.resident / 2**30:.2f} GiB"
    # params + Adam m and v
    assert step.argument_bytes > 7.7e9
    assert {"f32[16,2048,1536]", "f32[16,768,2048]", "f32[128,2048]",
            "f32[2048,32,128]", "f32[2048,4,128]", "f32[4096,2048]",
            "f32[18992,2048]", "f32[2048,18992]"} <= step.weights
    # The head runs over the noised half: 8192 rows of logits, not 16,384.
    assert "tensor<1x8192x18992xf32>" in step.lowered
    assert "tensor<1x16384x18992xf32>" not in step.lowered
    # The thinnest step of the seven (15.75 GiB usable). The router's pick
    # as a select keeps what the gather kept ([T, k] ints a layer); the
    # compiler packs the step 3,129,856 bytes looser all the same.
    assert step.resident <= SDAR_RESIDENT_BEFORE_THE_PICK + 4 * 2**20, (
        step.resident)
    print(f"sdar cut: resident {step.resident / 2**30:.2f} GiB "
          f"({step.resident} bytes)")


def _routed_layer_at(fixture):
    """(the `RoutedExperts` a block of the cell's model builds, the cut's
    configuration)."""
    from elasticdl_tpu.layers.moe import RoutedExperts

    if fixture == "sdar_cut_one_chip":
        from elasticdl_tpu.models.sdar import sdar_30b_a3b_cut as m
    else:
        from elasticdl_tpu.models.mellum import mellum2_12b_a2_5b_cut as m
    c = m.cut_config()
    return RoutedExperts(
        num_experts=c.num_experts,
        num_experts_per_tok=c.num_experts_per_tok,
        d_hidden=c.moe_intermediate_size, gated=True, score="softmax",
        held=c.experts_held, norm_topk_prob=c.norm_topk_prob, topk_eps=0.0,
        block_rows=c.expert_block_rows, force_balance_seed=0,
        dtype=c.activation_dtype), c


def _moves(jaxpr):
    """(primitive, shapes of its operands and results) of every gather and
    scatter in a jaxpr, those inside its loops and rules too."""
    found = []
    for eqn in jaxpr.eqns:
        if "gather" in eqn.primitive.name or "scatter" in eqn.primitive.name:
            found.append((eqn.primitive.name, [
                tuple(v.aval.shape) for v in (*eqn.invars, *eqn.outvars)]))
        for sub in jax.core.jaxprs_in_params(eqn.params):
            found += _moves(sub)
    return found


_REDUCE = re.compile(
    r"\s*(?:ROOT )?%\S+ = \S+ reduce\(%(\S+?), .*?dimensions=\{([0-9,]*)\}"
    r'.*?op_name="([^"]*)"')
_F32_DEFINED = re.compile(r"\s*(?:ROOT )?%(\S+) = (f32\[[0-9,]*\]\{[0-9,]*)")


def _sums_over_the_chosen(hlo_text, rows, chosen):
    """{(operand's shape and minor-to-major order, reduced dimensions)} of
    the routing stage's float reductions over an array of one number a
    (token, choice), whichever way round it lies."""
    defined = dict(
        m.groups() for m in map(_F32_DEFINED.match, hlo_text.split("\n"))
        if m)
    return {
        (defined[m.group(1)], m.group(2))
        for m in map(_REDUCE.match, hlo_text.split("\n"))
        if m and "moe_routing" in m.group(3)
        and defined.get(m.group(1), "").split("{")[0] in (
            f"f32[{rows},{chosen}]", f"f32[{chosen},{rows}]")}


# The minor-to-major order in which the parent of PR 54 kept a token's k
# weights where it summed them (this compile at the parent).
CHOSEN_LAYOUT_BEFORE_THE_PICK = {
    "sdar_cut_one_chip": "{1,0", "mellum_cut_one_chip": "{0,1"}


@pytest.mark.parametrize("fixture", sorted(CHOSEN_LAYOUT_BEFORE_THE_PICK))
def test_the_routing_stage_moves_no_single_numbers(request, fixture):
    """The pick of a token's k scores out of its E was `take_along_axis`:
    on the chip a gather of T * k single float32 numbers at 10 ns each
    (1.34 ms a layer call) and, backward, their scatter into a zeroed
    [T, E] (0.87 to 1.14 ms), 3% of either cell's step (PERF.md section 6,
    PR 54). `pick_chosen` is a compare, a select and a reduction: the
    layer's forward and backward at the cell's shapes hold no gather and no
    scatter over [T, E] or [T, k], and neither does the compiled step; the
    grouped loops' row gathers and scatter-adds are still what they were."""
    layer, c = _routed_layer_at(fixture)
    rows, hidden = 16384, c.hidden_size
    experts, chosen = c.num_experts, c.num_experts_per_tok
    x = jax.ShapeDtypeStruct((1, rows, hidden), jnp.bfloat16)
    variables = jax.eval_shape(layer.init, jax.random.PRNGKey(0), x)

    def loss(params, x):
        y, _ = layer.apply(
            {"params": params, "buffers": variables["buffers"]}, x)
        return jnp.sum(y.astype(jnp.float32))

    moves = _moves(jax.make_jaxpr(jax.grad(loss, argnums=(0, 1)))(
        variables["params"], x).jaxpr)
    # The walk is not blind: a block's rows are gathered from [T, D].
    assert any((rows, hidden) in shapes for _, shapes in moves), moves
    routing = [(name, shapes) for name, shapes in moves
               if {(rows, experts), (rows, chosen)} & set(shapes)]
    assert not routing, routing
    # Nor does the compiler make one of what it is given.
    step = request.getfixturevalue(fixture)
    single = {f"f32[{rows},{experts}]", f"f32[{rows},{chosen}]"}
    made = [
        (m.group(2), _without_layouts(m.group(1)))
        for m in map(_HLO_OP.match, step.text.split("\n"))
        if m and m.group(2) in ("gather", "scatter")
        and _without_layouts(m.group(1)) in single]
    assert not made, made
    # The sums over a token's k weights (under the normalised weights, and
    # in their backward) add in the order their operand's layout gives,
    # and the compiler lays the operand out by what hands it over. It has
    # to stay the parent's: with the pick made tokens-last the
    # block-diffusion step summed `f32[8,16384]{1,0` and `f32[16384,8]{0,1`
    # and every logged loss of the cell moved; handed over flat with no
    # select over the indices' range, the Mellum step summed k-minor
    # (PERF.md section 6, PR 54).
    assert _sums_over_the_chosen(step.text, rows, chosen) == {
        (f"f32[{rows},{chosen}]" + CHOSEN_LAYOUT_BEFORE_THE_PICK[fixture],
         "1")}


# jaxprs traced by `step.lower(...)` at the parent of PR 53 (the q / k
# stage as `rotary(rms_norm(.))`, every cache empty), and what a later
# change may add before this fails: a kernel whose body is traced a call
# site, or written in `jnp` operators, adds hundreds (PR 52: 10.6 s of
# `step_load_s`, PERF.md section 6).
PARENT_TRACES = {"sdar_cut_one_chip": 3235, "mellum_cut_one_chip": 2221,
                 "kanana_cut_one_chip": 3153}
TRACES_MARGIN = 40


@pytest.mark.parametrize("fixture", sorted(PARENT_TRACES))
def test_the_steps_trace_fires_no_more_traces_than_before_the_qk_kernels(
        request, fixture):
    """Set-up seconds are traces: each nested jit a step's trace enters is
    2.5 to 4.4 ms of every job's start (PERF.md section 6, PR 44), on a
    compile-cache hit too. With each `qk_rotary` kernel under a jit of its
    own and its body in `lax` primitives the two steps trace fewer jaxprs
    than with the expression (2 tables a step, not 2 a layer's q and k);
    so does the Kanana step with `mla_rotary`'s four (PR 58: 3,153 at its
    parent)."""
    step = request.getfixturevalue(fixture)
    ceiling = PARENT_TRACES[fixture] + TRACES_MARGIN
    assert step.traces <= ceiling, (
        f"{fixture}: step.lower traced {step.traces} jaxprs; the parent of "
        f"PR 53 traced {PARENT_TRACES[fixture]} (+{TRACES_MARGIN} allowed)")
    print(f"{fixture}: {step.traces} jaxprs traced")


# The parent's step (PR 48) by the same compile: the convolution stage's
# kernels may not add to it.
GRANITE_RESIDENT_BEFORE_THE_CONV_KERNELS = 14.44 * 2**30


def _granite_reader_sizes():
    from elasticdl_tpu.models.granite_hybrid import (
        granite_4_0_h_micro_cut as m,
    )

    c = m.cut_config()
    inner = c.mamba_n_heads * c.mamba_d_head
    conv = inner + 2 * c.mamba_n_groups * c.mamba_d_state
    return {"batch": 1, "chunks": 8192 // c.mamba_chunk_size,
            "chunk": c.mamba_chunk_size, "heads": c.mamba_n_heads,
            "groups": c.mamba_n_groups,
            "per": c.mamba_n_heads // c.mamba_n_groups,
            "head_dim": c.mamba_d_head, "state": c.mamba_d_state,
            "inner": inner, "conv": conv,
            "in_proj": inner + conv + c.mamba_n_heads,
            "hidden": c.hidden_size}


def test_granite_cut_step_compiles_and_fits_one_v5e(granite_cut_one_chip):
    """The WHOLE training step of the granite-4.0-h-micro cut (772.2 M
    parameters at 16 bytes each, the largest state any cell holds;
    minibatch 1 x S 8192, as `edl train` runs `granite_4_0_h_micro_cut`)
    for one described chip: nine chunked scans at one group, chunk 256 and
    batch 1 as the kernels of `ops/ssd_scan.py`, the causal flash kernels at `[32, 8192, 64]` handed the
    activation dtype with q already times 1/8, the tied head over 12,544
    rows; it fits 16 GB with the remat the model-def states, compiles its
    update apart from the weight-gradient products, and hands its
    counter back beside the loss."""
    from elasticdl_tpu.models.granite_hybrid import (
        granite_4_0_h_micro_cut as m,
    )

    step = granite_cut_one_chip
    # The loss and the one counter (`ssd_scan_tokens`).
    assert step.out_tree.children()[2].num_leaves == 2
    calls = _kernel_calls(step.text)
    # One attention layer: flash_fwd (and a rematerialised twin) and
    # flash_bwd, the kernels every causal cell runs.
    flash = [c for c in calls if c[0].startswith("(bf16[32,8192,64], ")]
    assert 2 <= len(flash) <= 3
    for _, operands in flash:
        assert set(operands) <= {"bf16[32,8192,64]", "f32[32,8192,128]"}
    assert step.text.count("flash_fwd") >= 1
    assert step.text.count("flash_bwd") >= 1
    # Nine mixers: the convolution stage's forward kernel twice a layer
    # (once rematerialised under `dots`, as the scan's) and its backward
    # once.
    convs = [c for c in calls if c[0] in GRANITE_CONV_CALLS.values()]
    assert sum(results == GRANITE_CONV_CALLS["forward"]
               for results, _ in convs) == 18
    assert sum(results == GRANITE_CONV_CALLS["backward"]
               for results, _ in convs) == 9
    for results, operands in convs:
        # xBC is read where it lies in the in-projection's result.
        assert operands[0] == "bf16[1,8512,8192]"
    # And the scan's forward kernel, its rematerialised twin and its
    # backward kernel in each, every one known to the benchmark's readers
    # by the chunked layout among its operands and results.
    scans = [c for c in calls if c not in flash and c not in convs]
    assert len(scans) == 27
    assert len(flash) + len(scans) + len(convs) == len(calls)
    forward = "(f32[1,1,64,64,8192], f32[1,32,1,4096,128])"
    assert sum(results == forward for results, _ in scans) == 18
    for results, operands in scans:
        assert "bf16[1,1,64,64,8192]" in (results, *operands), results
    # The transposes round the calls move nothing: the compiler keeps
    # these activations with the time minor already, so no copy, reshape,
    # transpose or slice of a tensor of x's size (xBC's and the
    # in-projection's result are larger; or B's, under the scan's scope)
    # is an operation of the step. (By way of a [.., chunks, chunk]
    # shape they were: two copies each of x, y, dy and dx, 30 ms a step on
    # the chip; and xBC was a slice a layer, 0.22 ms.)
    moved = []
    for line in step.text[step.text.index("ENTRY"):].split("\n"):
        op = _HLO_OP.match(line)
        if not op or op.group(2) not in (
                "copy", "reshape", "transpose", "slice"):
            continue
        sizes = [int(np.prod([int(d) for d in dims.split(",")]))
                 for _, dims in _HLO_ARRAY.findall(op.group(1)) if dims]
        at_least = 8192 * 128 if "/ssd_scan/" in line else 8192 * 4096
        if sizes and at_least <= max(sizes) < 8192 * 12544:
            moved.append(line.split(", metadata=")[0])
    assert not moved, moved[:3]
    assert step.resident <= GRANITE_RESIDENT_BEFORE_THE_CONV_KERNELS, (
        f"{step.resident / 2**30:.2f} GiB")
    # params + Adam m and v: 772,160,448 x 12 B.
    assert step.argument_bytes > 9.2e9
    assert {"f32[2048,8512]", "f32[4,4352]", "f32[4096,2048]",
            "f32[2048,16384]", "f32[8192,2048]", "f32[2048,32,64]",
            "f32[2048,8,64]", "f32[12544,2048]"} <= step.weights
    # Tied: no head of its own.
    assert "f32[2048,12544]" not in step.weights
    # The scan ran at one group, chunk 256, batch 1, as the kernels: 32
    # chunks' entering states, and no decay mask in the program.
    assert "tensor<1x32x1x4096x128xf32>" in step.lowered
    assert "tensor<1x32x1x256x256xf32>" not in step.lowered
    # The update apart: a barrier a gradient leaf, beside the one
    # `jax.checkpoint` gives each rematerialised block.
    assert step_plan.update_apart_for(step.mesh)
    rematerialised = {"none": 0, "dots": 10}
    assert step.lowered.count("optimization_barrier") == (
        step.n_grad_leaves + rematerialised[m.REMAT])
    fusions = _update_fusions(step)
    assert "kOutput" not in fusions, fusions["kOutput"]
    assert {"f32[2048,8512]", "f32[2048,16384]", "f32[12544,2048]"} <= set(
        fusions["kLoop"])
    print(f"granite cut ({m.REMAT}): resident {step.resident / 2**30:.2f} "
          "GiB")


def test_the_readers_take_the_conv_calls_for_the_mixers_and_not_the_scans(
        granite_cut_one_chip, monkeypatch):
    """`mixer_time_pct.granite` and `ssd_time_pct.granite` know an
    operation by the shapes in its HLO line, results and operands alike
    (`benchmark/metrics/_granite_ops.py`). Each of the compiled step's 27
    calls of the convolution stage holds a shape that `mixer_shape` takes
    (the taps, `[1, 4, 4352]`) and none that `scan_shape` takes (x is
    `[1, 4096, 8192]` there, its `[1, 1, 64, 64, 8192]` view a bitcast
    outside the call): counted with the mixers, and never charged to the
    scan's roofline. The scan's own calls are still the scan's."""
    from test_ssd_scan import _load

    benchmark = os.path.join(
        os.path.dirname(os.path.abspath(__file__)), "..", "benchmark")
    monkeypatch.syspath_prepend(benchmark)  # its `lib`
    ops = _load(os.path.join(benchmark, "metrics", "_granite_ops.py"))
    z = _granite_reader_sizes()

    def taken(call, test):
        results, operands = call
        return any(
            test(tuple(int(d) for d in dims.split(",")), z)
            for _, dims in _HLO_ARRAY.findall(" ".join([results, *operands]))
            if dims)

    calls = _kernel_calls(granite_cut_one_chip.text)
    convs = [c for c in calls if c[0] in GRANITE_CONV_CALLS.values()]
    assert len(convs) == 27
    for call in convs:
        assert taken(call, ops.mixer_shape), call
        assert not taken(call, ops.scan_shape), call
    scans = [c for c in calls if "f32[1,1,64,64,8192]" in (c[0], *c[1])
             or "bf16[1,1,64,64,8192]" in (c[0], *c[1])]
    assert len(scans) == 27 and all(
        taken(c, ops.scan_shape) for c in scans)


def test_mellum_cut_step_compiles_and_fits_one_v5e(mellum_cut_one_chip):
    """The WHOLE training step of the Mellum2-12B-A2.5B cut (595.2 M
    parameters at 16 bytes each, minibatch 1 x S 16384, as `edl train` runs
    `mellum2_12b_a2_5b_cut`) for one described chip: the flash kernels
    under the band in three layers and under the causal mask in the
    fourth, all at `[32, 16384, 128]`, handed the activation dtype, the
    band's calls under names of their own; the gated grouped product over
    16 held experts; the untied head over a quarter of the vocabulary; it
    fits 16 GB with the remat the model-def states, and hands eight
    counters back beside the loss."""
    step = mellum_cut_one_chip
    assert step.out_tree.children()[2].num_leaves == 9
    calls = _kernel_calls(step.text, "flash_")
    # Four layers: a forward (and a rematerialised twin) and a backward.
    assert 8 <= len(calls) <= 12
    for results, operands in calls:
        assert results.startswith("(bf16[32,16384,128], "), results
        assert set(operands) <= {"bf16[32,16384,128]", "f32[32,16384,128]"}
    # q and k of four layers turned for them (the two rematerialised
    # layers' twice) and turned back: 8 + 4 and 8 calls a step, under the
    # windowed layers' table and the full layer's alike.
    turns = [r for r, _ in _kernel_calls(step.text, "qk_rotary_")]
    assert set(turns) == set(QK_ROTARY_CALLS)
    assert sorted(map(turns.count, QK_ROTARY_CALLS)) == [4, 4, 6, 6]
    # What the benchmark's readers key on: the names, and the counts the
    # causal calls have (3 / 2 forward, 6 / 3 backward), under the band too.
    named = {}
    for line in step.text.split("\n"):
        m = _HLO_OP.match(line)
        if (m and m.group(2) == "custom-call" and "tpu_custom_call" in line
                and "flash_" in line.split(" = ")[0]):
            kernel = re.search(r"(band_)?flash_(fwd|bwd)", line).group(0)
            operands = _HLO_ARRAY.findall(
                _OPERAND_LAYOUTS.search(line).group(1))
            named.setdefault(kernel, set()).add(
                (len(operands), len(_HLO_ARRAY.findall(m.group(1)))))
    assert named == {
        "band_flash_fwd": {(3, 2)}, "band_flash_bwd": {(6, 3)},
        "flash_fwd": {(3, 2)}, "flash_bwd": {(6, 3)}}
    assert len(re.findall(r"= [^=]*custom-call[^\n]*band_flash_bwd",
                          step.text)) == 3
    assert step.resident < HBM_BYTES, f"{step.resident / 2**30:.2f} GiB"
    # params + Adam m and v
    assert step.argument_bytes > 7.1e9
    assert {"f32[16,2304,1792]", "f32[16,896,2304]", "f32[64,2304]",
            "f32[2304,32,128]", "f32[2304,4,128]", "f32[4096,2304]",
            "f32[24576,2304]", "f32[2304,24576]"} <= step.weights
    assert "tensor<1x16384x24576xf32>" in step.lowered
    print(f"mellum cut: resident {step.resident / 2**30:.2f} GiB")


@pytest.mark.parametrize("tile,steps", [(1024, 31), (512, 93)])
def test_the_bands_grids_hold_the_run_tiles_alone(tile, steps):
    """Both passes' `pallas_call`s under `Band(1024)` at the cell's shape:
    the grid is (batch*heads, run tiles), the calls carry the band's names
    and the causal calls' operand and result counts (no table operand)."""
    mask = fa.Band(1024)
    assert fa.grid_steps(mask, 16384, tile, tile) == steps
    x = jax.ShapeDtypeStruct((1, 2, 16384, 128), jnp.bfloat16)

    def both(q, k, v, g):
        o, lse = fa._flash_forward(q, k, v, mask, tile, tile, True)
        return fa._flash_backward(q, k, v, o, lse, g, mask, tile, tile)

    calls = [
        eqn for eqn in jax.make_jaxpr(both)(x, x, x, x).jaxpr.eqns
        if eqn.primitive.name == "pallas_call"
    ]
    assert [
        (c.params["name"], c.params["grid_mapping"].grid,
         len(c.invars), len(c.outvars))
        for c in calls
    ] == [
        ("band_flash_fwd", (2, steps), 3, 2),
        ("band_flash_bwd", (2, steps), 6, 3),
    ]


@pytest.mark.parametrize("tile", [1024, 512])
def test_band_kernels_compile_for_v5e(one_chip, kernel_on, tile):
    """Forward and backward under `Band(1024)` at `[1, 32, 16384, 128]`
    for the described chip, at both tiles the cut chose between."""
    shape = (1, 32, 16384, 128)

    def loss(q, k, v):
        return fa.flash_attention(
            q, k, v, fa.Band(1024), tile, tile).astype(jnp.float32).sum()

    compiled = jax.jit(jax.grad(loss, argnums=(0, 1, 2))).lower(
        *_qkv(shape, one_chip, jnp.bfloat16)).compile()
    text = compiled.as_text()
    assert "band_flash_fwd" in text and "band_flash_bwd" in text


def test_band_kernels_partition_over_a_data_mesh(topo, kernel_on):
    """The band's calls under the trainer's abstract mesh over the four
    described chips: each batch shard runs them on its own rows, as the
    causal calls do (`_per_batch_shard` knows no mask)."""
    mesh = jax.sharding.Mesh(np.array(topo.devices), ("data",))
    sharded = NamedSharding(mesh, P("data"))

    def loss(q, k, v):
        with jax.sharding.use_abstract_mesh(mesh.abstract_mesh):
            return jnp.sum(fa.flash_attention(q, k, v, fa.Band(1024)))

    text = (
        jax.jit(jax.grad(loss, argnums=(0, 1, 2)))
        .lower(*_qkv((4, 8, 4096, 128), sharded, jnp.bfloat16))
        .compile().as_text()
    )
    assert text.count("tpu_custom_call") == 2
    assert "band_flash_fwd" in text and "band_flash_bwd" in text
    # Per device: 1 of the 4 rows.
    assert "bf16[1,8,4096,128]" in text


# ---------- latent attention: a key width beside a value width ----------


def _latent_qkv(sharding, s=16384, bsz=1, heads=32):
    q = jax.ShapeDtypeStruct((bsz, heads, s, 192), jnp.bfloat16,
                             sharding=sharding)
    v = jax.ShapeDtypeStruct((bsz, heads, s, 128), jnp.bfloat16,
                             sharding=sharding)
    return q, q, v


def test_latent_kernels_compile_for_v5e(one_chip, kernel_on):
    """Forward and backward at keys of 192 against values of 128,
    `[1, 32, 16384, .]`, for the described chip: q and k enter as one
    [1024, 192] operand each (a block's last dimension may be the array's
    whole one), the output and dv at 128, dq and dk at 192, under names of
    their own."""
    def loss(q, k, v):
        return fa.flash_attention(q, k, v, True).astype(jnp.float32).sum()

    compiled = jax.jit(jax.grad(loss, argnums=(0, 1, 2))).lower(
        *_latent_qkv(one_chip)).compile()
    fwd, = _kernel_calls(compiled.as_text(), "mla_flash_fwd")
    bwd, = _kernel_calls(compiled.as_text(), "mla_flash_bwd")
    assert fwd[0].startswith("(bf16[32,16384,128], f32[32,16384,128]")
    assert fwd[1] == ["bf16[32,16384,192]"] * 2 + ["bf16[32,16384,128]"]
    assert bwd[0].startswith(
        "(bf16[32,16384,192], bf16[32,16384,192], bf16[32,16384,128]")
    assert bwd[1][:4] == ["bf16[32,16384,192]"] * 2 + [
        "bf16[32,16384,128]"] * 2
    assert len(_kernel_calls(compiled.as_text())) == 2


def test_latent_kernels_partition_over_a_data_mesh(topo, kernel_on):
    """The unequal widths under the trainer's abstract mesh over the four
    described chips: each batch shard runs the calls on its own rows."""
    mesh = jax.sharding.Mesh(np.array(topo.devices), ("data",))
    sharded = NamedSharding(mesh, P("data"))

    def loss(q, k, v):
        with jax.sharding.use_abstract_mesh(mesh.abstract_mesh):
            return jnp.sum(fa.flash_attention(q, k, v, True))

    text = (
        jax.jit(jax.grad(loss, argnums=(0, 1, 2)))
        .lower(*_latent_qkv(sharded, s=4096, bsz=4, heads=8))
        .compile().as_text()
    )
    assert text.count("tpu_custom_call") == 2
    assert "mla_flash_fwd" in text and "mla_flash_bwd" in text
    assert "bf16[1,8,4096,192]" in text and "bf16[1,8,4096,128]" in text


def test_kanana_cut_step_compiles_and_fits_one_v5e(kanana_cut_one_chip):
    """The WHOLE training step of the kanana-2-30b-a3b cut (687.5 M
    parameters at 16 bytes each, minibatch 1 x S 16384, as `edl train` runs
    `kanana_2_30b_a3b_cut`) for one described chip: the flash kernels at
    keys of 192 against values of 128 in all six layers, handed the
    activation dtype, under names of their own and no causal call of one
    width beside them; the gated grouped product over 16 held experts and
    the shared experts' gated MLP in five layers, the dense MLP in the
    first; the untied head over an eighth of the vocabulary; it fits 16 GB
    with the remat the model-def states, whose five rematerialised layers
    each keep their attention's output (128 MiB) and compact lse (2 MiB)
    by the names of `flash_attention.KEPT`: 14.87 GiB where the
    configuration file's `model.remat_reason` read 14.49 with nothing
    kept, and no more since (PR 58: 14.83); what lies between the projections and those kernels is
    `ops/mla_rotary.py`'s two passes each way (PR 58) and none of the
    fusions and copies it was; and hands six counters back beside the
    loss."""
    step = kanana_cut_one_chip
    assert step.out_tree.children()[2].num_leaves == 7  # the loss and six
    # q's pass and k's and v's: six layers forward, five of them again
    # under remat (q, k and v are what a rematerialised layer recomputes),
    # six backward.
    assert {name: len(_kernel_calls(step.text, f"mla_rotary_{name}"))
            for name in ("q_fwd", "kv_fwd", "q_bwd", "kv_bwd")} == {
                "q_fwd": 11, "kv_fwd": 11, "q_bwd": 6, "kv_bwd": 6}
    assert _kernel_calls(step.text, "mla_rotary_q_fwd")[0] == (
        "bf16[1,32,16384,192]",
        ["bf16[1,16384,6144]", "bf16[2,128,128]", "f32[1,16384,128]",
         "f32[1,16384,128]"])
    assert _kernel_calls(step.text, "mla_rotary_kv_bwd")[0] == (
        "(bf16[1,16384,8192], bf16[1,16384,64])",
        ["bf16[1,32,16384,192]", "bf16[1,32,16384,128]", "bf16[2,128,128]",
         "f32[1,16384,128]", "f32[1,16384,128]"])
    # Under the rope's scope nothing is left but those calls, their
    # results' pieces and what the compiler moves for them: none of the
    # slices, float32 turns, joins, broadcasts and copies over
    # [16384, 32, w] the stage was as XLA's (PERF.md section 6, PR 58).
    under_rope = [m for m in map(_HLO_OP.match, step.text.split("\n"))
                  if m and "kanana_rope" in m.string]
    assert len(under_rope) >= 34
    assert {m.group(2) for m in under_rope} <= {
        "custom-call", "get-tuple-element", "copy", "constant",
        "copy-start", "copy-done", "bitcast"}, sorted(
            {m.group(2) for m in under_rope})
    for gone in ("f32[32,8192,32]", "f32[8192,32,64]", "f32[1,16384,32,64]",
                 "f32[1,16384,32,32]", "f32[32,16384,32]",
                 "f32[16384,32,64]", "bf16[1,16384,32,64]",
                 "bf16[16384,32,64]"):
        assert gone not in step.text, gone
    calls = _kernel_calls(step.text, "flash_")
    assert calls == _kernel_calls(step.text, "mla_flash_")
    # Six layers, a forward and a backward each: no rematerialised layer
    # runs the forward kernel a second time.
    assert len(_kernel_calls(step.text, "mla_flash_bwd")) == 6
    assert len(_kernel_calls(step.text, "mla_flash_fwd")) == 6
    for results, operands in calls:
        assert results.startswith((
            "(bf16[32,16384,128], f32[32,16384,128]",
            "(bf16[32,16384,192], bf16[32,16384,192], bf16[32,16384,128]",
        )), results
        assert set(operands) <= {
            "bf16[32,16384,192]", "bf16[32,16384,128]",
            "f32[32,16384,128]"}
    assert step.resident < HBM_BYTES, f"{step.resident / 2**30:.2f} GiB"
    # The chip gives a program 15.75 GiB. AOT read 15,971,736,576 B (14.87
    # GiB) before PR 58 and reads 15,927,114,752 (14.83) with it; 64 MiB
    # allowed above the parent's reading, as PR 56 set it. It holds because
    # every layer and the head hand their cotangents out together
    # (`kanana_moe._cotangents_together`, one barrier each in the backward
    # pass): without them the scheduler puts the head's weight gradient
    # three layers' backward later, the logits' cotangent `bf16[1,16032,
    # 16384]` (501 MiB) alive until then, and the last layer's to the
    # step's end, and the step reads 15.59 GiB (PERF.md section 6, PR 58).
    assert step.resident < 14.94 * 2**30, f"{step.resident / 2**30:.2f} GiB"
    # One barrier a gradient leaf, the routed layers' own over one flat
    # array each, `jax.checkpoint`'s over a rematerialised layer's 19
    # inputs, and the model's: the head's kernel and input, a layer's
    # parameters (10 in the dense layer, 12 in a routed one) and input.
    barriers = [operands.count("tensor<") for operands in re.findall(
        r"optimization_barrier [^\n]*: (tensor<[^\n]*)", step.lowered)]
    assert sorted(n for n in barriers if n > 1) == (
        [2, 11] + 5 * [13] + 5 * [19])
    assert barriers.count(1) == step.n_grad_leaves + 20
    # params + Adam m and v
    assert step.argument_bytes > 8.2e9
    assert {"f32[16,2048,1536]", "f32[16,768,2048]", "f32[128,2048]",
            "f32[2048,3072]", "f32[1536,2048]", "f32[2048,32,192]",
            "f32[2048,576]", "f32[512,32,256]", "f32[4096,2048]",
            "f32[2048,6144]", "f32[6144,2048]", "f32[16032,2048]",
            "f32[2048,16032]"} <= step.weights
    assert "tensor<1x16384x16032xf32>" in step.lowered
    print(f"kanana cut: resident {step.resident / 2**30:.2f} GiB")


# ---------- the map of a step's scopes, over the steps compiled above ----------

_COMMON_KINDS = {"attention", "attention_kernel", "embed_head_loss", "update",
                 "other"}
# fixture -> (the kinds its model has beside the common ones, the layers it
# rematerialises, the most fusions the compiler may leave without a name).
SCOPES_OF = {
    "flagship_one_chip": ({"mlp"}, set(), 0),
    "flagship_dp4": ({"mlp"}, set(), 0),
    "nemotron_h_cut_one_chip": ({"mixer", "moe"}, set(range(9)), 4),
    "lfm2_cut_one_chip": ({"mixer", "moe", "mlp"}, set(), 6),
    "sdar_cut_one_chip": ({"moe"}, set(), 8),
    "granite_cut_one_chip": ({"mixer", "mlp"}, set(range(10)), 2),
    "mellum_cut_one_chip": ({"moe"}, {0, 1}, 6),
    "kanana_cut_one_chip": ({"moe", "mlp"}, {1, 2, 3, 4, 5}, 7),
}
# What the compiler makes and names for no line of the program: prefetches
# and their waits, parameters, reshapes of layout, the pieces of tuples,
# and the scalar comparators and reducers that sorts and reductions call.
UNNAMED_OPCODES = {
    "slice-start", "slice-done", "copy-start", "copy-done", "copy",
    "parameter", "bitcast", "bitcast-convert", "get-tuple-element", "tuple",
    "custom-call", "fusion", "constant", "iota", "broadcast", "reshape",
    "add", "and", "xor", "compare", "select", "reduce", "reduce-window",
}
# The Pallas calls, by the name each `pallas_call` was given, and the kind
# and the passes the map has to put them in. The head norm and rotary
# turn's calls (`ops/qk_rotary.py`) and the latent attention's passes
# (`ops/mla_rotary.py`) are the attention's and no attention call:
# `attention_kernel` is `ops/flash_attention.py`'s alone, so that its share
# is what the by-name readers of the flash calls read.
_HLO_NAME = re.compile(r"\s*(?:ROOT )?%(\S+) = ")
PALLAS_CALLS = {
    "flash_fwd": ("attention_kernel", {"fwd", "remat"}),
    "flash_bwd": ("attention_kernel", {"bwd"}),
    "qk_rotary_fwd": ("attention", {"fwd", "remat"}),
    "qk_rotary_bwd": ("attention", {"bwd"}),
    "mla_rotary_q_fwd": ("attention", {"fwd", "remat"}),
    "mla_rotary_kv_fwd": ("attention", {"fwd", "remat"}),
    "mla_rotary_q_bwd": ("attention", {"bwd"}),
    "mla_rotary_kv_bwd": ("attention", {"bwd"}),
    "ssd_scan_fwd": ("mixer", {"fwd", "remat"}),
    "ssd_scan_bwd": ("mixer", {"bwd"}),
    "causal_conv_fwd": ("mixer", {"fwd", "remat"}),
    "causal_conv_bwd": ("mixer", {"bwd"}),
}


@pytest.mark.parametrize("fixture", sorted(SCOPES_OF))
def test_the_steps_scopes_tell_every_kernel_kind_and_pass(request, fixture):
    """`observability/step_scopes.py` over what the v5e compiler made of
    each cell's step: every instruction a row, every Pallas call of the
    kind and pass its name says, the kinds the model has and no other, the
    rematerialised layers and no other, and nothing that takes time left
    without a name beyond the compiler's own."""
    from elasticdl_tpu.observability import step_scopes

    kinds, remat_layers, unnamed_fusions = SCOPES_OF[fixture]
    text = request.getfixturevalue(fixture).text
    module, rows = step_scopes.rows_of(text)
    assert module == "jit_step_fn"
    names = [r["name"] for r in rows]
    assert len(set(names)) == len(names)
    named = [r for r in rows if r["phase"] != "none"]
    assert len(named) > 1400 and len(rows) > 4000

    pallas = {
        _HLO_NAME.match(line).group(1) for line in text.splitlines()
        if 'custom_call_target="tpu_custom_call"' in line}
    calls = [r for r in rows if r["name"] in pallas]
    assert len(calls) == len(pallas) >= 3
    for row in calls:
        call = row["scope"].split("/")[-1]
        known = next(k for k in PALLAS_CALLS if call.endswith(k))
        kind, phases = PALLAS_CALLS[known]
        assert (row["kind"], row["phase"] in phases) == (kind, True), row
        assert row["layer"] is not None

    assert {r["kind"] for r in named} == _COMMON_KINDS | kinds
    assert {r["kind"] for r in rows if r["phase"] == "none"} == {"other"}
    assert {r["layer"] for r in rows
            if r["phase"] == "remat"} - {None} == remat_layers
    assert {r["kind"] for r in named if r["phase"] == "update"} == {
        "update"}

    unnamed = [r for r in rows if r["phase"] == "none"]
    assert {r["opcode"] for r in unnamed} <= UNNAMED_OPCODES
    assert not [r for r in unnamed if r["opcode"] in (
        "while", "call", "conditional")]
    assert sum(r["opcode"] == "fusion"
               for r in unnamed) <= unnamed_fusions
    if fixture == "kanana_cut_one_chip":
        # PR 56's policy through the map: a rematerialised layer runs
        # everything again but the attention's kernel.
        again = {r["scope"].split("/")[2] for r in named
                 if r["phase"] == "remat" and r["scope"].count("/") >= 2}
        assert not [r for r in named if r["phase"] == "remat"
                    and r["kind"] == "attention_kernel"]
        assert "kanana_latent_attention" not in again
        assert {"kanana_q_proj", "kanana_kv_down", "kanana_kv_up",
                "kanana_rope", "kanana_o_proj", "mlp"} <= again
        # PR 58's passes through the map: under the rope's scope, the
        # forward ones in every layer and again in the rematerialised.
        passes = {}
        for r in calls:
            scope = r["scope"].split("/")
            if scope[-1].startswith("mla_rotary_"):
                assert scope[1:3] == ["self_attn", "kanana_rope"], r
                passes.setdefault(scope[-1], []).append(r["phase"])
        assert {k: sorted(set(v)) for k, v in passes.items()} == {
            "mla_rotary_q_fwd": ["fwd", "remat"],
            "mla_rotary_kv_fwd": ["fwd", "remat"],
            "mla_rotary_q_bwd": ["bwd"], "mla_rotary_kv_bwd": ["bwd"]}
        assert sorted(map(len, passes.values())) == [6, 6, 11, 11]
