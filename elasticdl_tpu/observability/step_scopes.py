"""Which scope every instruction of a compiled step belongs to.

A device profile names an operation by its HLO instruction (`%fusion.123 =
...`) and carries no metadata; the compiled step's own text does:
`metadata={op_name="jit(step_fn)/transpose(jvp(M))/layers_1/self_attn/..."}`
holds the pass (`jvp` forward, `transpose(jvp` backward, under
`rematted_computation` the forward work the backward runs again, none of
them the update), the layer (the flax module path) and the
`jax.named_scope`s. `step_scope_map` reads that text into one row an
instruction, and `write_step_scopes` writes the rows beside a profile
(`<profile dir>/step_scopes.json`) and says so with a
`step_scopes_written` event, so that a reader of the profile joins each
device event to its row by the instruction's name.

Nothing here runs unless a profile was asked for: the worker calls
`start_writing` at the end of a `--profile_dir` window and
`profiling.capture_device_profile` calls `write_for_running_step` at the
end of an on-demand capture. The text comes from
`step.lower(*shapes).compile().as_text()`. Lowered inside the context the
step is called in (the trainer's mesh) with shapes that carry the arrays'
shardings, `lower` and `compile` find what the step traced and the
executable it runs with (milliseconds; a step built with jit-level
compiler options is loaded again from the compile cache); anywhere else
jax's keys differ and the whole step is traced, lowered and compiled
again. `as_text()` of a step of some 8,000 instructions takes seconds on
the TPU and the parse tenths of one, so the worker's loop hands the work
to a short-lived thread and dispatches on.

`kind` is decided here, from the scope names the models export and, where
a model puts its attention or its MLP under no `named_scope`, from its
flax module names; a reader only sums.
"""

import contextlib
import gc
import json
import os
import re
import threading
import time

from elasticdl_tpu.common.log_utils import get_logger
from elasticdl_tpu.observability import events as _events

logger = get_logger("observability.step_scopes")

FILE_NAME = "step_scopes.json"

FWD, BWD, REMAT, UPDATE, NONE = "fwd", "bwd", "remat", "update", "none"

ATTENTION = "attention"
ATTENTION_KERNEL = "attention_kernel"
MOE = "moe"
MIXER = "mixer"
MLP = "mlp"
EMBED_HEAD_LOSS = "embed_head_loss"
OTHER = "other"  # and `UPDATE`: the update is a phase and a kind

# The Pallas attention calls of `ops/flash_attention.py` whatever their
# mask (`flash_fwd`, `bd_flash_bwd`, `band_flash_fwd`, `mla_flash_bwd`).
_ATTENTION_KERNELS = ("flash_fwd", "flash_bwd")
_KERNEL_TARGET = 'custom_call_target="tpu_custom_call"'

_COMPUTATION = re.compile(r"^(ENTRY )?%?([^\s(]+) \(.*\) -> .* \{$")
_INSTRUCTION = re.compile(
    r"^\s+(?:ROOT )?%?(\S+) = .*? ([a-z][a-z0-9\-]*)\(")
_OP_NAME = re.compile(r'metadata=\{[^{}]*?op_name="((?:[^"\\]|\\.)*)"')
_FUSED = re.compile(r"\bcalls=%?([^\s,)}]+)")
_MODULE = re.compile(r"^HloModule ([^\s,]+)")
_LAYER = re.compile(r"^(?:layers|Block)_(\d+)$")
# What a transform or a control-flow primitive leaves in a name, and no
# scope of the program's: `jit(f)`, `jvp(M)`, `transpose(jvp(M))`,
# `checkpoint`, `rematted_computation`, `while`, `body`, `cond`,
# `branch_1_fun`, `custom_vjp_call`, `shard_map`, `pallas_call` and the like;
# `layers_1.shared_forward_fn` is flax's lifted `custom_vjp` round a module.
_WRAPPER = re.compile(
    r"^(?:\w+\(.*\)|checkpoint|rematted_computation|while|body|cond"
    r"|body_fun|cond_fun|branch_\d+_fun|custom_[a-z_]+|pallas_call"
    r"|closed_call|core_call|shard_map|\w+\.shared_forward_fn)$")


def scope_kinds():
    """{scope or flax module name: kind}, strongest first in each of two
    tables: the `named_scope`s the layers and models export, then the flax
    module names that stand for a kind where no scope does (the flagship's
    and the hybrid's attention and MLP; the dense layers of the others).
    Imported when a map is made, never with this module."""
    from elasticdl_tpu.layers import mamba2, moe, short_conv
    from elasticdl_tpu.models.granite_hybrid import granite_hybrid
    from elasticdl_tpu.models.kanana import kanana_moe
    from elasticdl_tpu.models.lfm2 import lfm2_moe
    from elasticdl_tpu.models.mellum import mellum_moe
    from elasticdl_tpu.models.sdar import sdar_moe

    scopes = {
        moe.ROUTING_SCOPE: MOE,
        moe.GROUPED_SCOPE: MOE,
        moe.SHARED_SCOPE: MOE,
        mamba2.SCAN_SCOPE: MIXER,
        short_conv.CONV_SCOPE: MIXER,
        sdar_moe.ATTENTION_SCOPE: ATTENTION,
        lfm2_moe.ROTARY_SCOPE: ATTENTION,
        granite_hybrid.MIXER_SCOPE: MIXER,
        granite_hybrid.ATTENTION_SCOPE: ATTENTION,
        granite_hybrid.MLP_SCOPE: MLP,
        mellum_moe.MOE_SCOPE: MOE,
        kanana_moe.Q_SCOPE: ATTENTION,
        kanana_moe.KV_DOWN_SCOPE: ATTENTION,
        kanana_moe.KV_UP_SCOPE: ATTENTION,
        kanana_moe.ROPE_SCOPE: ATTENTION,
        kanana_moe.ATTENTION_SCOPE: ATTENTION,
        kanana_moe.O_SCOPE: ATTENTION,
        kanana_moe.MOE_SCOPE: MOE,
    }
    scopes.update({name: ATTENTION for name in mellum_moe.SCOPES.values()})
    modules = {
        # Every model with named layers calls its attention `self_attn`;
        # the flagship's is flax's own name for an unnamed module.
        "self_attn": ATTENTION,
        "MultiHeadAttention_0": ATTENTION,
        # The hybrid calls whatever a layer holds `mixer`: a layer whose
        # scopes say nothing (its attention) is told by its projections.
        "q_proj": ATTENTION,
        "k_proj": ATTENTION,
        "v_proj": ATTENTION,
        "o_proj": ATTENTION,
        # Dense feed-forwards: the flagship's two unnamed products, the
        # first Kanana layer's and the first LFM2 layers' gated MLP. A
        # routed layer under the same name is told by its scopes first.
        "Dense_0": MLP,
        "Dense_1": MLP,
        "mlp": MLP,
        "feed_forward": MLP,
        "shared_mlp": MLP,
    }
    return scopes, modules


def _phase(op_name):
    if not op_name.startswith("jit("):
        # An argument's own name (`variables['params'][...]`) or a called
        # computation's primitive (`reduce_sum`): nothing of the step's.
        return NONE
    if "/rematted_computation/" in op_name:
        return REMAT
    if "transpose(jvp(" in op_name:
        return BWD
    if "jvp(" in op_name:
        return FWD
    return UPDATE


def _path(op_name):
    """The scope components of an `op_name`: wrappers and the final
    primitive taken off. A fused name (`a;b`) reads as its first part."""
    parts = op_name.split(";")[0].split("/")
    return [p for p in parts[:-1] if p and not _WRAPPER.match(p)]


def _layer(path):
    for part in path:
        found = _LAYER.match(part)
        if found:
            return int(found.group(1))
    return None


def _direct_kind(path, table):
    for part in reversed(path):
        kind = table.get(part)
        if kind is not None:
            return kind
    return None


class _Scoped:
    """What an instruction's `op_name` says; `kind` once `_set_kinds` has
    seen the whole module."""

    __slots__ = ("phase", "path", "layer", "kernel", "kind")

    def __init__(self, op_name, is_kernel=False):
        self.phase = _phase(op_name) if op_name else NONE
        self.path = _path(op_name) if self.phase != NONE else []
        self.layer = _layer(self.path)
        self.kernel = is_kernel
        self.kind = None

    def module(self):
        """The layer's module the instruction stands in
        (`layers_3/mixer`), or None outside every layer."""
        for i, part in enumerate(self.path):
            if _LAYER.match(part):
                return tuple(self.path[i:i + 2])
        return None

    def fields(self):
        return {"phase": self.phase, "layer": self.layer,
                "scope": "/".join(self.path), "kind": self.kind}


def _set_kinds(instructions, tables):
    """The kind of every instruction of a module: by the innermost scope
    the models export; else what the scoped instructions of the same
    module of the same layer agree on (the hybrid's `mixer`, the routed
    layers' `mlp`); else by the module's own name; an instruction of the
    model outside every layer is the embedding's, the head's or the
    loss's."""
    scopes, modules = tables
    agreed = {}  # {a layer's module: the kinds its scopes gave}
    for s in instructions:
        if s.phase == UPDATE:
            s.kind = UPDATE
        elif s.phase == NONE:
            s.kind = OTHER
        elif s.kernel and s.path[-1].endswith(_ATTENTION_KERNELS):
            s.kind = ATTENTION_KERNEL
            agreed.setdefault(s.module(), set()).add(ATTENTION)
        else:
            s.kind = _direct_kind(s.path, scopes)
            if s.kind is not None:
                agreed.setdefault(s.module(), set()).add(s.kind)
    agreed.pop(None, None)  # outside every layer nothing is inherited
    for s in instructions:
        if s.kind is None:
            kinds = agreed.get(s.module(), ())
            s.kind = next(iter(kinds)) if len(kinds) == 1 else (
                _direct_kind(s.path, modules)
                or (EMBED_HEAD_LOSS if s.layer is None else OTHER))


def rows_of(hlo_text, tables=None):
    """(module name, rows) of a compiled module's text: one row an
    instruction of every computation but the fused ones, whose
    instructions only say whether their fusion `crosses` a boundary."""
    module = None
    computations = {}  # {name: [(instruction, opcode, the computation it
    #                            fuses or None, what its op_name says)]}
    current = None
    for line in hlo_text.splitlines():
        if current is None:
            found = _COMPUTATION.match(line)
            if found:
                current = computations.setdefault(found.group(2), [])
            elif module is None:
                named = _MODULE.match(line)
                module = named.group(1) if named else None
            continue
        if line.startswith("}"):
            current = None
            continue
        found = _INSTRUCTION.match(line)
        if not found:
            continue
        name, opcode = found.groups()
        op_name = _OP_NAME.search(line)
        fused = _FUSED.search(line) if opcode == "fusion" else None
        current.append((
            name, opcode, fused.group(1) if fused else None,
            _Scoped(op_name.group(1) if op_name else "",
                    opcode == "custom-call" and _KERNEL_TARGET in line)))
    _set_kinds(
        [inst[3] for insts in computations.values() for inst in insts],
        tables or scope_kinds())
    fused_computations = {
        fused for insts in computations.values()
        for _, _, fused, _ in insts if fused}
    rows = []
    for computation, insts in computations.items():
        if computation in fused_computations:
            continue
        for name, opcode, fused, said in insts:
            row = {"name": name, "opcode": opcode, **said.fields()}
            if opcode == "fusion":
                inside = [s for _, _, _, s in computations.get(fused, ())
                          if s.phase != NONE]
                if said.phase == NONE and inside:
                    # The compiler named the fusion for none of its parts
                    # (an update fused round its all-reduce): it is booked
                    # to the named instruction nearest its root.
                    row.update(inside[-1].fields())
                row["crosses"] = len(
                    {(s.phase, s.kind) for s in inside}) > 1
            rows.append(row)
    return module, rows


def abstract_of(tree):
    """The shapes of a tree of arrays: what `lower` needs, never the live
    buffers, which the running step has been given. A shape carries its
    array's sharding where that is bound to a mesh: the sharding is part
    of what jax keys a trace on, and a `lower` that misses it traces,
    lowers and compiles the whole step again."""
    import jax
    from jax.sharding import NamedSharding

    def shape_of(a):
        sharding = getattr(a, "sharding", None)
        return jax.ShapeDtypeStruct(
            tuple(a.shape), a.dtype,
            sharding=sharding if isinstance(sharding, NamedSharding)
            else None)

    return jax.tree_util.tree_map(shape_of, tree)


def step_scope_map(step, shapes, context=None, timing=None):
    """{"fn", "hlo_module", "rows"} of the tracked step `step` as it is
    compiled for `shapes` (its arguments as `ShapeDtypeStruct`s), lowered
    inside `context`: the one the step is called in (the trainer's mesh),
    which is part of jax's key for what it has traced and lowered; outside
    it the whole step is traced and compiled again. `timing`, a dict, is
    told what each stage took."""
    marks = [time.perf_counter()]

    def lap(value):
        marks.append(time.perf_counter())
        return value

    with context if context is not None else contextlib.nullcontext():
        compiled = lap(lap(step.lower(*shapes)).compile())
    module, rows = lap(rows_of(lap(compiled.as_text())))
    if timing is not None:
        timing.update(zip(
            ("lower_s", "executable_s", "text_s", "rows_s"),
            (round(b - a, 4) for a, b in zip(marks, marks[1:]))))
    return {
        "fn": getattr(step, "_name", None) or getattr(
            step, "__name__", "step"),
        "hlo_module": module, "rows": rows,
    }


def write_step_scopes(directory, step, shapes, context=None):
    """Write `<directory>/step_scopes.json` for `step` at `shapes` (as
    `step_scope_map` takes them) and emit `step_scopes_written`; the
    file's path. A failure is a warning and no file: it is never the
    caller's."""
    timing = {}
    # The rows are a hundred thousand small objects, enough to start a
    # full collection, which holds the interpreter for its whole pass over
    # the process's heap: 0.2 s of a worker's loop that stood behind a
    # loss fence, its device dry meanwhile (PERF.md section 6, PR 57). So
    # the cyclic collector rests while the rows are made and written.
    collecting = gc.isenabled()
    gc.disable()
    try:
        t0 = time.perf_counter()
        scopes = step_scope_map(step, shapes, context, timing)
        os.makedirs(directory, exist_ok=True)
        path = os.path.join(directory, FILE_NAME)
        with open(path + ".part", "w") as f:
            json.dump(scopes, f, separators=(",", ":"))
        os.replace(path + ".part", path)
        written = {
            "path": path, "fn": scopes["fn"],
            "instructions": len(scopes["rows"]),
            "with_op_name": sum(
                1 for r in scopes["rows"] if r["phase"] != NONE)}
        del scopes
    except Exception:
        logger.warning(
            "Failed to write the step's scopes (%s)", timing, exc_info=True)
        return None
    finally:
        if collecting:
            gc.enable()
    seconds = round(time.perf_counter() - t0, 4)
    logger.info("Step scopes written to %s in %.2fs (%s)",
                path, seconds, timing)
    _events.emit("step_scopes_written", seconds=seconds, **written)
    return path


def start_writing(directory, step, shapes, context=None):
    """`write_step_scopes` on a short-lived thread: the caller dispatches
    on, and joins the thread it is handed when its run ends."""
    thread = threading.Thread(
        target=write_step_scopes, args=(directory, step, shapes, context),
        name="edl-step-scopes", daemon=True)
    thread.start()
    return thread


# The process's training step, for a profile taken from outside the
# worker's loop (`/debug/profile`): () -> (step, shapes, context) or None.
_running_step = None


def note_running_step(provider):
    """The worker says how to ask its trainer for the step it runs."""
    global _running_step
    _running_step = provider


def write_for_running_step(directory):
    """`write_step_scopes` for the step this process trains with, on the
    caller's thread; None where no worker said, or no step has run."""
    try:
        found = _running_step() if _running_step is not None else None
    except Exception:  # a trainer between two worlds: the capture goes on
        logger.warning("No step to map", exc_info=True)
        return None
    return write_step_scopes(directory, *found) if found else None
