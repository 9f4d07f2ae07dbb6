"""Device mesh construction, batch sharding helpers, and the WORLD SPEC —
the single deterministic map from (parallel config, world topology) to the
mesh an elastic trainer builds.

The reference's allreduce path gets its topology from Horovod's Gloo ring
(/root/reference/elasticdl/python/worker/allreduce_trainer.py:77-83). The
TPU-native equivalent is a named `jax.sharding.Mesh`: data parallelism is the
"data" axis, tensor/model parallelism "model", sequence/context parallelism
"seq". XLA lowers psum/all_gather over the mesh to ICI collectives on real
hardware; nothing here is CPU/TPU specific.

World spec (`resolve_world_spec`): every parallel feature — ZeRO-1
(parallel/zero1.py), tensor parallelism (tensor_parallel.py), pipelining
(pipeline*.py), sequence parallelism (ring_attention.py / ulysses.py) —
contributes an `AxisDemand` naming the mesh axis it needs; the resolver
composes them under one precedence policy (stage excludes model/seq; seq
drops before model; zero only factors pure DP) into a `WorldSpec`. The
spec is a pure function of `(ParallelConfig, WorldTopology)`: given the
same config, an N-device world always maps to the same axes — which is
what lets a trainer compile the step of a world it is NOT in yet
(speculative AOT, worker/world_speculator.py) and recognize a membership
epoch bump that does not change the mesh at all (the recompile-free
regroup fast path). Mesh construction anywhere else in the tree is
rejected by the `mesh-spec-consistency` lint rule: the spec API here is
the only place a Mesh may be born.
"""

import math
from typing import Callable, NamedTuple, Optional

import jax
import numpy as np
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

DATA_AXIS = "data"
MODEL_AXIS = "model"
SEQ_AXIS = "seq"
# Pipeline-parallel stage axis (parallel/pipeline.py): stacked per-stage
# params shard their leading dim over it. Like MODEL_AXIS it never crosses
# process boundaries (the multi-host composition invariant documented in
# worker/allreduce_trainer.py).
STAGE_AXIS = "stage"
# Intra-process slice of the data dimension, used by multi-host ZeRO-1:
# optimizer state shards over it while staying replicated across processes,
# so every process keeps a fully-addressable copy (elastic regroups can
# snapshot/broadcast it without the dead world's participation).
ZERO_AXIS = "zero"


def process_grouped_devices():
    """All global devices ordered so each process's devices are contiguous
    (sorted by (process_index, id)). A flat reshape over this list keeps
    any trailing mesh axis whose size divides local_device_count entirely
    inside one process — the invariant multi-host TP/ZeRO-1 rely on for
    fully-addressable parameters."""
    return sorted(jax.devices(), key=lambda d: (d.process_index, d.id))


def batch_axes(mesh: Mesh):
    """The mesh axes a batch's leading dim shards over: the data axis plus
    the intra-process zero axis when present (a {data, zero} mesh is pure
    data parallelism expressed as two factors)."""
    axes = [a for a in (DATA_AXIS, ZERO_AXIS) if a in mesh.shape]
    return tuple(axes)


def data_parallel_size(mesh: Mesh):
    import math as _math

    return _math.prod(mesh.shape[a] for a in batch_axes(mesh))


def make_mesh(axis_sizes=None, devices=None) -> Mesh:
    """Build a Mesh over `devices` (default: all visible, which under
    jax.distributed is the *global* device set across hosts).

    axis_sizes: ordered {axis_name: size} dict; a single size of -1 (or a
    missing remainder) absorbs all remaining devices. Default: 1-D data mesh.
    """
    explicit_devices = devices is not None
    if devices is None:
        devices = jax.devices()
    devices = np.asarray(devices)
    if axis_sizes is None:
        axis_sizes = {DATA_AXIS: len(devices)}
    names = tuple(axis_sizes)
    sizes = list(axis_sizes.values())
    n_fill = sizes.count(-1)
    if n_fill > 1:
        raise ValueError("at most one axis may have size -1")
    if n_fill == 1:
        known = math.prod(s for s in sizes if s != -1)
        if len(devices) % known:
            raise ValueError(
                f"{len(devices)} devices not divisible by fixed axes {known}"
            )
        sizes[sizes.index(-1)] = len(devices) // known
    total = math.prod(sizes)
    if total > len(devices):
        raise ValueError(
            f"mesh {dict(zip(names, sizes))} wants {total} devices, "
            f"only {len(devices)} visible"
        )
    chosen = devices[:total]
    if not explicit_devices and total == len(devices):
        # Let mesh_utils lay the logical axes onto the physical ICI
        # topology (torus-neighbor rings per axis) instead of a flat
        # device-id reshape — on real multi-chip slices this is the
        # difference between collectives riding nearest-neighbor ICI
        # links and hopping across the torus. Only when the caller did
        # not pass an explicit device list (mesh_utils reorders, which
        # would silently discard a deliberate ordering); falls back to
        # the plain reshape off-TPU or for partial meshes.
        try:
            from jax.experimental import mesh_utils

            arr = mesh_utils.create_device_mesh(
                tuple(sizes), devices=list(chosen)
            )
            return Mesh(arr, axis_names=names)
        except (
            ImportError,
            ValueError,
            NotImplementedError,
            # mesh_utils' TPU topology code bounds-checks with bare
            # asserts and raises RuntimeError on exotic slice shapes;
            # the flat reshape below is always a working layout.
            AssertionError,
            RuntimeError,
        ) as e:
            from elasticdl_tpu.common.log_utils import get_logger

            get_logger("parallel.mesh").warning(
                "Physical-topology mesh layout unavailable (%s); using "
                "flat device-id reshape — multi-chip collectives may "
                "cross non-neighbor ICI links", e,
            )
    return Mesh(chosen.reshape(sizes), axis_names=names)


def data_sharding(mesh: Mesh, axis=None) -> NamedSharding:
    """Leading-dim batch sharding over the data axis (plus the zero axis
    when the mesh factors data parallelism into two axes). Pass an explicit
    axis name or tuple to override."""
    if axis is None:
        axis = batch_axes(mesh) or DATA_AXIS
    return NamedSharding(mesh, P(axis))


def replicated_sharding(mesh: Mesh) -> NamedSharding:
    return NamedSharding(mesh, P())


def pad_batch_to_multiple(batch, multiple):
    """Pad a numpy batch pytree's leading dim up to a multiple by cyclic
    repetition. Returns (padded_batch, real_n). The training step slices
    outputs back to real_n before the loss so padding rows never contribute
    gradient signal.
    """
    leaves = jax.tree_util.tree_leaves(batch)
    if not leaves:
        return batch, 0
    real_n = leaves[0].shape[0]
    padded_n = -(-real_n // multiple) * multiple
    if padded_n == real_n:
        return batch, real_n
    idx = np.arange(padded_n) % real_n
    padded = jax.tree_util.tree_map(
        lambda x: np.take(x, idx, axis=0), batch
    )
    return padded, real_n


# ---------------------------------------------------------------------------
# World spec: deterministic (config, topology) -> mesh resolution
# ---------------------------------------------------------------------------


class WorldTopology(NamedTuple):
    """The device shape of one world: everything mesh resolution may
    depend on. A speculating trainer builds topologies for worlds it is
    not in yet (e.g. the N-1-process world after a preemption)."""

    n_devices: int
    local_devices: int
    n_processes: int

    @staticmethod
    def current():
        return WorldTopology(
            n_devices=len(jax.devices()),
            local_devices=jax.local_device_count(),
            n_processes=jax.process_count(),
        )

    @property
    def multi_process(self):
        return self.n_processes > 1


class AxisDemand(NamedTuple):
    """One parallel feature's request for a mesh axis. Feature modules
    (zero1 / tensor_parallel / pipeline / ring_attention) construct
    these; the resolver composes them. `intra_process` demands must lie
    entirely inside one process's device slice in multi-process worlds —
    the composition invariant that keeps (variables, opt_state) fully
    addressable on every host for elastic regroup snapshots."""

    axis: str
    size: int
    intra_process: bool = True

    def infeasible_reason(self, topo: WorldTopology, trailing: int = 1):
        """Why this demand cannot be laid out on `topo` (None = it can).
        `trailing` is the product of other already-granted trailing-axis
        sizes it must co-divide with (e.g. model x seq)."""
        want = self.size * trailing
        if topo.n_devices % want:
            return (
                f"{self.axis} axis of {self.size} (x{trailing} trailing) "
                f"does not divide {topo.n_devices} devices"
            )
        if (
            self.intra_process
            and topo.multi_process
            and topo.local_devices % want
        ):
            return (
                f"{self.axis} axis of {self.size} (x{trailing} trailing) "
                f"does not divide the {topo.local_devices} local devices "
                f"of each process (intra-process axis)"
            )
        return None


class ParallelConfig(NamedTuple):
    """The trainer-config slice world resolution consumes. Hook PRESENCE
    is a bool (the hooks themselves stay on the trainer); `sp_suspended`
    carries the per-world ulysses/ring downgrade bit."""

    model_parallel: int = 1
    has_param_specs: bool = False
    zero1: bool = False
    pipeline_stages: int = 1
    has_pipeline_spec: bool = False
    context_parallel: int = 1
    has_context_parallel_model: bool = False
    sp_suspended: bool = False


class WorldSpec:
    """A resolved world: ordered mesh axes + which features are active.

    Hashable by `fingerprint()` — the identity the compile tracker, the
    speculative AOT store, and the regroup fast path all key on: two
    worlds with the same fingerprint compile byte-identical step
    programs, so a membership epoch bump that resolves to the same
    fingerprint needs NO re-lowering."""

    __slots__ = (
        "axes",
        "process_grouped",
        "topology",
        "tp",
        "sp",
        "pp",
        "zero1",
        "notes",
    )

    def __init__(self, axes, process_grouped, topology, tp=1, sp=1, pp=1,
                 zero1=False, notes=()):
        self.axes = tuple(axes)  # ((name, size), ...) ordered
        self.process_grouped = bool(process_grouped)
        self.topology = topology
        self.tp = tp
        self.sp = sp
        self.pp = pp
        self.zero1 = zero1
        self.notes = tuple(notes)

    def fingerprint(self):
        # Process structure is part of the program identity, not just
        # the axes: the compiled step branches on the process count
        # (loss slicing, buffer donation — single-process only), so an
        # 8-device/1-process and an 8-device/2-process pure-DP world
        # must NOT share a fingerprint even though their meshes match.
        body = ",".join(f"{name}={size}" for name, size in self.axes)
        if self.process_grouped:
            body += "|pg"
        if self.topology.n_processes > 1:
            body += f"|p{self.topology.n_processes}"
        return body

    def axis_sizes(self):
        return dict(self.axes)

    def __eq__(self, other):
        return (
            isinstance(other, WorldSpec)
            and self.fingerprint() == other.fingerprint()
        )

    def __hash__(self):
        return hash(self.fingerprint())

    def __repr__(self):
        return f"WorldSpec({self.fingerprint()})"

    def build_mesh(self) -> Mesh:
        """Materialize the spec on the live backend. The spec's device
        count may be a PREFIX of the visible devices (a speculated
        smaller world compiles over the surviving prefix of the current
        global device set)."""
        total = math.prod(s for _, s in self.axes)
        visible = jax.devices()
        if total > len(visible):
            raise ValueError(
                f"world spec {self.fingerprint()} wants {total} devices; "
                f"only {len(visible)} visible"
            )
        if self.process_grouped:
            return make_mesh(
                dict(self.axes),
                devices=process_grouped_devices()[:total],
            )
        if total == len(visible):
            # No explicit device list: make_mesh may then lay the axes
            # onto the physical ICI topology (torus-neighbor rings).
            return make_mesh(dict(self.axes))
        return make_mesh(dict(self.axes), devices=visible[:total])


def resolve_world_spec(
    config: ParallelConfig,
    topo: WorldTopology,
    param_check: Optional[Callable[[int], list]] = None,
) -> WorldSpec:
    """The one deterministic (config, topology) -> WorldSpec map.

    Precedence ladder (unchanged semantics from the pre-spec trainer):
    the stage axis excludes model/seq (both lay out the intra-process
    slice); seq drops before model when their product stops dividing;
    zero only factors pure multi-process DP. Every degrade lands in
    `spec.notes` as a human sentence — the trainer logs them, so the
    fallback behavior stays as loud as the ad-hoc ladder was.

    `param_check(mp) -> [violation messages]` lets the caller veto TP
    with knowledge the resolver lacks (live param shapes vs the model
    axis); resolution stays deterministic for a fixed check outcome.
    """
    notes = []
    n, local_n = topo.n_devices, topo.local_devices
    multi = topo.multi_process

    def _dp(extra_note=None):
        if extra_note:
            notes.append(extra_note)
        return WorldSpec(
            ((DATA_AXIS, n),), False, topo, notes=notes
        )

    pp = config.pipeline_stages
    if pp > 1 and config.has_pipeline_spec:
        from elasticdl_tpu.parallel.pipeline import stage_axis_demand

        demand = stage_axis_demand(pp)
        why = demand.infeasible_reason(topo)
        if why is None:
            return WorldSpec(
                ((DATA_AXIS, n // pp), (demand.axis, pp)),
                multi,
                topo,
                pp=pp,
                notes=notes,
            )
        notes.append(
            f"pipeline_stages {pp} infeasible on this world ({why}); "
            "running the staged model sequentially under pure data "
            "parallelism for this world"
        )
        return _dp()

    mp_eff = 1
    mp = config.model_parallel
    if mp > 1:
        if not config.has_param_specs:
            notes.append(
                f"model_parallel_size {mp} requested but the model spec "
                "has no param_specs hook; falling back to pure data "
                "parallelism"
            )
        else:
            from elasticdl_tpu.parallel.tensor_parallel import (
                model_axis_demand,
            )

            demand = model_axis_demand(mp)
            why = demand.infeasible_reason(topo)
            bad = param_check(mp) if param_check is not None and not why \
                else []
            if why is not None:
                notes.append(
                    f"model_parallel_size {mp} infeasible on this world "
                    f"({why}); falling back to pure data parallelism "
                    "for this world"
                )
            elif bad:
                notes.append(
                    f"param_specs incompatible with model_parallel_size "
                    f"{mp} ({'; '.join(bad[:3])}); falling back to pure "
                    "data parallelism"
                )
            else:
                mp_eff = mp

    sp_eff = 1
    sp = config.context_parallel
    if sp > 1 and config.has_context_parallel_model and not (
        config.sp_suspended
    ):
        from elasticdl_tpu.parallel.ring_attention import seq_axis_demand

        demand = seq_axis_demand(sp)
        why = demand.infeasible_reason(topo, trailing=mp_eff)
        if why is None:
            sp_eff = sp
        else:
            notes.append(
                f"context_parallel_size {sp} (x model_parallel "
                f"{mp_eff}) infeasible on this world ({why}); running "
                "without sequence parallelism for this world"
            )

    if mp_eff > 1 or sp_eff > 1:
        axes = [(DATA_AXIS, n // (mp_eff * sp_eff))]
        if mp_eff > 1:
            axes.append((MODEL_AXIS, mp_eff))
        if sp_eff > 1:
            axes.append((SEQ_AXIS, sp_eff))
        return WorldSpec(
            axes, multi, topo, tp=mp_eff, sp=sp_eff, notes=notes
        )

    if config.zero1 and multi:
        from elasticdl_tpu.parallel.zero1 import zero_axis_demand

        demand = zero_axis_demand(local_n)
        if local_n > 1 and demand.infeasible_reason(topo) is None:
            # Factor pure DP into (data across processes, zero within):
            # the batch shards over both; optimizer state shards over
            # "zero" only, staying replicated across processes.
            return WorldSpec(
                ((DATA_AXIS, topo.n_processes), (demand.axis, local_n)),
                True,
                topo,
                zero1=True,
                notes=notes,
            )
        notes.append(
            "zero1 has no effect in this world: there is no "
            "intra-process axis to shard optimizer state over, so it "
            "stays replicated"
        )
    return _dp()


def shard_batch(batch, mesh: Mesh, axis=None):
    """Place a host batch onto the mesh, sharded along the data axis.

    Single-host: plain device_put. Multi-host (jax.process_count() > 1): each
    process holds its local slice of the global batch and contributes it via
    make_array_from_process_local_data — the global array's leading dim is
    world_batch = local_batch * num_processes.
    """
    sharding = data_sharding(mesh, axis)
    if jax.process_count() > 1:
        return jax.tree_util.tree_map(
            lambda x: jax.make_array_from_process_local_data(sharding, x),
            batch,
        )
    return jax.device_put(batch, sharding)
