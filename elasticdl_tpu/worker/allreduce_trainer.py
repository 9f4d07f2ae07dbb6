"""Elastic data-parallel trainer over a jax.sharding Mesh.

Reference counterpart: the Horovod AllReduce trainer
(/root/reference/elasticdl/python/worker/allreduce_trainer.py:39-184) and its
rendezvous manager. TPU-first redesign:

- The allreduce itself is NOT hand-written: the train step is jitted with the
  batch sharded along the mesh "data" axis and parameters replicated, so XLA
  inserts the gradient all-reduce as an ICI collective. There is no Horovod
  tape wrapper — gradient averaging falls out of the sharding.
- Elastic membership: the worker polls the master's get_comm_rank every
  `steps_per_world_check` steps (reference checks every 20,
  allreduce_trainer.py:141-148). A changed rendezvous_id means the world
  changed: re-init jax.distributed over the new (coordinator, world, rank),
  rebuild the mesh, recompile, and refresh state from rank 0.
- Rank-0 broadcast: instead of Horovod broadcast_variables, every worker
  runs a tiny gRPC Collective service; after a regroup, non-zero ranks pull
  (variables, opt_state, version) from the rank-0 worker's service
  (parallel/broadcast.py) and overwrite local state.
- Comm failures retry with re-init, up to `max_comm_retries` (reference
  retries <=5 on Horovod UnknownError, allreduce_trainer.py:125-139).
- Hybrid DP x TP (extension; the reference is DP-only): with
  `model_parallel_size > 1` and a model-spec `param_specs(variables)` hook
  (e.g. parallel/tensor_parallel.transformer_param_specs), the mesh gains a
  "model" axis and parameters are laid out by those PartitionSpecs instead
  of replicated — XLA inserts the Megatron-style collectives. Optimizer
  state is left to GSPMD sharding propagation (it mirrors the param layout
  after the first step). If an elastic world change leaves the device count
  indivisible by the model-parallel size, the trainer falls back to pure DP
  for that epoch rather than failing the job.

- Multi-host composition invariant: sharding axes other than "data" NEVER
  cross process boundaries. In a multi-process world the model axis (TP)
  and the zero axis (ZeRO-1) are laid out over each process's LOCAL
  devices (the mesh is built over process-grouped device order), while
  the data axis spans processes. Consequences, both deliberate:
  (1) every process always holds a fully-addressable copy of (variables,
  opt_state), so the elastic regroup machinery — host snapshot +
  broadcast_one_to_all — is untouched by TP/ZeRO-1, and any SURVIVOR can
  re-seed a joiner (cross-process shards would die with the process that
  owned them, which no broadcast can undo); (2) TP collectives ride the
  dense intra-host ICI rather than DCN, the standard placement for tensor
  parallelism at multi-host scale. The tradeoff is that ZeRO-1's memory
  saving is the local chip count, not the global DP degree.
"""

import itertools
import threading
import time

import grpc
import jax
import numpy as np

from elasticdl_tpu.common import knobs
from elasticdl_tpu.common.log_utils import get_logger
from elasticdl_tpu.observability import datapath, emit_event, tracing
from elasticdl_tpu.observability.metrics import default_registry
from elasticdl_tpu.parallel import broadcast, distributed, step_plan
from elasticdl_tpu.parallel.mesh import (
    DATA_AXIS,
    MODEL_AXIS,
    SEQ_AXIS,
    ParallelConfig,
    WorldTopology,
    pad_batch_to_multiple,
    resolve_world_spec,
    shard_batch,
)
from elasticdl_tpu.worker.trainer import JaxTrainer, split_stats
from elasticdl_tpu.worker.world_speculator import (
    SpeculativeWorldCompiler,
    speculation_enabled,
    world_deltas,
)

logger = get_logger("worker.allreduce_trainer")

# Elastic regroups by how much work they had to do: "fast" = the new
# world resolved to the SAME world spec on a stable backend, so the
# compiled steps (and state placement) were kept verbatim — the
# recompile-free path; "rebuild" = mesh + steps rebuilt.
_C_REGROUPS = default_registry().counter(
    "edl_regroups_total",
    "Elastic world changes absorbed, by path (fast = no re-mesh / no "
    "re-lowering; rebuild = mesh and steps rebuilt)",
    labelnames=("mode",),
)

DEFAULT_STEPS_PER_WORLD_CHECK = 20
DEFAULT_MAX_COMM_RETRIES = 5

# What counts as a communication/runtime failure worth a re-mesh + retry.
# XLA/distributed-runtime errors surface as RuntimeError subclasses
# (XlaRuntimeError); master RPCs fail as grpc.RpcError. User-code bugs
# (TypeError/ValueError from tracing a bad model or loss) must NOT retry —
# the reference similarly retried only Horovod comm errors
# (allreduce_trainer.py:125-139).
RETRYABLE_ERRORS = (grpc.RpcError, RuntimeError)

# Per-instance salt for the compile tracker's mesh fingerprint. The
# tracker's per-fn history is process-global (it must survive wrapper
# rebuilds), so two trainer INSTANCES in one process — bench matrix
# cells, back-to-back tests — would otherwise reproduce identical
# `epochN:{axes}` tokens and have a fresh trainer's mesh change
# misclassified as `rebuild` against the previous instance's history.
# A monotonic counter (not id(): CPython reuses ids after GC) keeps
# tokens unique across instances while staying constant within one, so
# same-instance rebuild detection is unaffected.
_trainer_seq = itertools.count(1)


def join_gate_budget():
    """The join-gate wait budget for an elastic regroup.

    Explicit ELASTICDL_JOIN_GATE_SECONDS wins; unset/0 derives from a
    measured-compile-time floor: a peer that must re-lower its step
    (~6.5 s per compile on a loaded 1-core box, per the compile
    tracker) can burn many multiples of that before reaching the gate,
    which is exactly how the old fixed 90 s gate lost to load and
    churned membership (epoch 14+ in the 1f1b flake)."""
    budget = knobs.get_float("ELASTICDL_JOIN_GATE_SECONDS")
    if budget > 0:
        return budget
    from elasticdl_tpu.observability import profiling

    # Capped: the gate's timeout fall-through exists for masters that
    # never answer world_ready (predating the gate) — one long flagship
    # compile must widen the wait to minutes, not hours.
    return min(
        max(90.0, 20.0 * profiling.peak_compile_seconds()), 600.0
    )


class AllReduceTrainer(JaxTrainer):
    def __init__(
        self,
        model,
        loss_fn,
        optimizer_spec,
        master_client,
        steps_per_world_check=DEFAULT_STEPS_PER_WORLD_CHECK,
        max_comm_retries=DEFAULT_MAX_COMM_RETRIES,
        multi_host=False,
        broadcast_port=0,
        seed=0,
        model_parallel_size=1,
        param_specs_fn=None,
        zero1=False,
        quantized_grads=False,
        pipeline_stages=1,
        pipeline_schedule="1f1b",
        pipeline_microbatches=0,
        pipeline_virtual_stages=2,
        pipeline_spec_fn=None,
        context_parallel_size=1,
        context_parallel_impl="zigzag",
        context_parallel_model_fn=None,
    ):
        super().__init__(model, loss_fn, optimizer_spec, seed=seed)
        self._mesh_salt = next(_trainer_seq)
        self._model_parallel_size = max(1, int(model_parallel_size or 1))
        self._param_specs_fn = param_specs_fn
        # Pipeline parallelism (parallel/pipeline.py): the model spec's
        # pipeline_spec hook builds the staged step; the mesh gains a
        # "stage" axis laid out like the model axis (intra-process in
        # multi-host worlds — the composition invariant above). The staged
        # param tree replaces the monolithic one, so ALL of the elastic
        # machinery (snapshot, broadcast, checkpoint) carries it untouched;
        # worlds that can't host the stage axis degrade to running the
        # same staged tree sequentially under pure DP (the schedule-free
        # apply in the PipelineBuild), keeping state intact.
        self._pipeline_stages = max(1, int(pipeline_stages or 1))
        self._pipeline_schedule = pipeline_schedule
        self._pipeline_microbatches = int(pipeline_microbatches or 0) or (
            2 * self._pipeline_stages
        )
        self._pipeline_vstages = max(1, int(pipeline_virtual_stages or 1))
        self._pipeline_spec_fn = pipeline_spec_fn
        self._pipeline_build = None
        if self._pipeline_stages > 1 and pipeline_spec_fn is None:
            logger.warning(
                "pipeline_stages %d requested but the model spec has no "
                "pipeline_spec hook; running unpipelined",
                self._pipeline_stages,
            )
            self._pipeline_stages = 1
        if self._pipeline_stages > 1:
            if self._model_parallel_size > 1:
                raise ValueError(
                    "pipeline_stages and model_parallel_size cannot be "
                    "combined (both lay out the intra-process device "
                    "slice); pick one"
                )
            if zero1:
                logger.warning(
                    "zero1 is ignored under pipeline parallelism (stage "
                    "params already shard over the stage axis; the "
                    "optimizer layout follows them)"
                )
                zero1 = False
            if quantized_grads:
                logger.warning(
                    "quantized_grads is ignored under pipeline "
                    "parallelism (the data-axis reduction happens inside "
                    "the pipeline's shard_map, which has no quantized "
                    "variant yet)"
                )
                quantized_grads = False
        # Sequence/context parallelism (parallel/ring_attention.py,
        # parallel/ulysses.py): the mesh gains a "seq" axis (intra-process
        # in multi-host worlds, like model/stage) and the TRAIN step runs
        # a mesh-bound variant of the model whose attention is the ring /
        # Ulysses callable from the model spec's context_parallel_model
        # hook. The param tree is identical to the plain model's (the
        # attention carries no params), so init, evaluation, checkpoints
        # and elastic transitions all keep using self._model untouched.
        self._context_parallel_size = max(
            1, int(context_parallel_size or 1)
        )
        self._context_parallel_impl = context_parallel_impl
        self._context_parallel_model_fn = context_parallel_model_fn
        self._sp_model = None  # mesh-bound train model, rebuilt per world
        if (
            self._context_parallel_size > 1
            and context_parallel_model_fn is None
        ):
            logger.warning(
                "context_parallel_size %d requested but the model spec "
                "has no context_parallel_model hook; running without "
                "sequence parallelism", self._context_parallel_size,
            )
            self._context_parallel_size = 1
        # Per-world downgrade bit: a hook rejection that depends on the
        # CURRENT mesh (e.g. ulysses under an active TP head axis) drops
        # the seq axis for that world only — the next world change
        # retries (unlike the pipeline hook, whose rejections are
        # config-determined and permanent).
        self._sp_suspend_once = False
        if self._context_parallel_size > 1:
            if self._pipeline_stages > 1:
                raise ValueError(
                    "context_parallel_size and pipeline_stages cannot "
                    "be combined (no model spec stages a "
                    "sequence-parallel attention); pick one"
                )
            # zero1/quantized_grads are SUSPENDED while the seq axis is
            # active (the SP attention runs its own shard_map, which
            # neither the quantized data-axis step nor the zero-axis
            # factoring nests with yet) — not zeroed: a world where SP
            # drops (indivisible devices) gets them back.
            if zero1:
                logger.warning(
                    "zero1 is suspended while the seq axis is active; "
                    "it applies again in worlds that cannot host "
                    "sequence parallelism"
                )
            if quantized_grads:
                logger.warning(
                    "quantized_grads is suspended while the seq axis "
                    "is active; it applies again in worlds that cannot "
                    "host sequence parallelism"
                )
        # Cross-replica weight-update sharding (ZeRO-1, parallel/zero1.py):
        # optimizer state shards over the data axis (single process) or the
        # intra-process "zero" axis (multi-host — see the module docstring's
        # composition invariant); GSPMD compiles the update as
        # reduce-scatter -> shard-local math -> all-gather. Pure-DP meshes
        # only (under TP the opt layout follows the params).
        self._zero1 = bool(zero1)
        if zero1 and self._model_parallel_size > 1:
            logger.warning(
                "zero1 is ignored when tensor parallelism is active "
                "(the optimizer layout follows the param layout); "
                "per-chip optimizer memory will NOT drop"
            )
        # EQuARX-style int8 gradient allreduce (parallel/quantized.py):
        # the DP step is formulated with shard_map so the data-axis
        # gradient reduction goes through quantized_pmean (int8 wire both
        # legs) instead of XLA's f32 collective. On a {data, zero} mesh
        # only the cross-process data leg quantizes — the intra-host zero
        # reduction stays exact f32 on ICI, which is precisely the
        # EQuARX deployment shape (quantize DCN, not ICI). Composes with
        # TP: shard_map goes manual over the data axis ONLY, the model
        # axis stays automatic so GSPMD keeps the exact Megatron
        # collectives while the data-axis mean of the model-sharded grads
        # quantizes (step_plan.quantized_step_fn) — the flagship's multi-host
        # DP x intra-host TP shape quantizes exactly its DCN leg.
        self._quantized_grads = bool(quantized_grads)
        self._step_rng_base = jax.random.fold_in(
            jax.random.PRNGKey(seed), 0x5EED
        )
        self._mc = master_client
        self._steps_per_world_check = steps_per_world_check
        self._max_comm_retries = max_comm_retries
        self._multi_host = multi_host
        self._group_id = -1
        self._rank = -1
        self._world_size = 0
        self._mesh = None
        # The resolved WorldSpec of the current mesh: the deterministic
        # identity regroups, compile tokens and speculation key on.
        self._world_spec = None
        # Test/bench seams: pin the topology the resolver sees, and the
        # candidate topologies the speculator guesses (production derives
        # both from the live backend / world size).
        self._topo_override = None
        self._topo_candidates = None
        # Master-announced next world (policy scale events): polled from
        # get_world_hint, consumed as the FIRST speculation candidate so
        # the regroup that follows a policy scale finds its executable
        # prebuilt. 0 = no hint ever seen; poll interval 0 disables.
        self._hint_poll_s = knobs.get_float(
            "ELASTICDL_POLICY_HINT_POLL_SECONDS"
        )
        self._last_hint_poll = 0.0
        self._hint_seq_seen = 0
        self._hinted_world = 0
        self._speculated = set()  # (fingerprint, real_n) already queued
        self._last_batch_abstract = None  # (feat_abs, label_abs, real_n)
        self._speculator = SpeculativeWorldCompiler(self.plan_step_for_spec)
        self._sharded_steps = {}  # real_n -> jitted step
        self._local_forward = None  # multi-host eval path, built lazily
        # Multi-host eval host copy, keyed on (group_id, version): an eval
        # task runs many minibatches against ONE model version, and a
        # fresh jax.device_get per minibatch re-downloads the whole model
        # each time (~0.9 GB for the flagship). One transfer per version.
        self._eval_host_cache = None  # ((group_id, version), host_vars)
        self._steps_since_check = 0
        # Guards the (variables, opt_state, version) triple: the broadcast
        # server reads it from gRPC threads while the training thread swaps
        # it, and a torn read would hand a joiner step-N+1 weights with
        # step-N optimizer moments.
        self._state_lock = threading.Lock()
        # Every worker serves its state; only the rank-0 instance gets pulled
        # from. Port 0 binds an ephemeral port that the worker advertises as
        # part of its host string: the master hands that "ip:port" string out
        # verbatim as coordinator_addr, which is where regrouping workers
        # dial their broadcast pulls.
        self._broadcast_server = broadcast.BroadcastServer(
            self._state_provider, port=broadcast_port
        )
        ip = (master_client.worker_host or "127.0.0.1").split(":")[0]
        master_client.worker_host = f"{ip}:{self._broadcast_server.port}"

    @property
    def broadcast_port(self):
        return self._broadcast_server.port

    @property
    def rank(self):
        return self._rank

    @property
    def world_size(self):
        return self._world_size

    @property
    def group_id(self):
        """Membership epoch this trainer last joined."""
        return self._group_id

    def restore_variables(self, exported):
        # The broadcast server reads (variables, opt_state, version) from
        # gRPC threads; a checkpoint restore swaps all three, so it must
        # hold the same lock or a regrouping peer could pull checkpoint
        # weights paired with init-time optimizer moments.
        with self._state_lock:
            super().restore_variables(exported)
            # The restored version can collide with the cached one (e.g.
            # resuming the same step the cache was made at, with different
            # weights on disk): drop the eval host copy unconditionally.
            self._eval_host_cache = None
            if self._mesh is not None:
                # Re-shard the restored state per the unified world
                # spec: the base restore places leaves uncommitted
                # (single-device default), which would silently demote a
                # ZeRO-1/TP layout — and cost a first-step reshard —
                # after every checkpoint resume. With the placement done
                # here, a rejoin that restores from checkpoint dispatches
                # its first step against warm executables immediately.
                self._variables = self._place_variables(self._variables)
                self._opt_state = self._place_opt_state(self._opt_state)

    def _state_provider(self):
        # Bounded retry: with buffer donation on the step path there is a
        # microsecond-scale window each step — execution enqueue (which
        # consumes the donated inputs) to the under-lock swap — where the
        # attributes still name deleted arrays. A read landing there
        # succeeds on the next attempt, once the swap publishes the new
        # arrays. Only genuinely poisoned state (async collective
        # failure) exhausts the retries.
        for attempt in range(3):
            with self._state_lock:
                if self._variables is None:
                    return None
                try:
                    return (
                        jax.device_get(self._variables),
                        jax.device_get(self._opt_state),
                        self._version,
                    )
                except Exception:
                    if attempt == 2:
                        # Device arrays poisoned by an async collective
                        # failure: treat local state as lost. Regroup
                        # then falls back to a rank-0 pull (or data
                        # re-seed), instead of crashing the recovery
                        # path itself.
                        logger.warning(
                            "Local state unreadable (poisoned by a "
                            "failed step); discarding for recovery",
                            exc_info=True,
                        )
                        return None
            # Lock RELEASED between attempts: the training thread needs
            # it to complete the swap this read is waiting out.
            time.sleep(0.05 * (attempt + 1))
        return None

    # ---------- world management ----------

    def init_world_if_needed(self, force=False):
        """Poll the master for the current comm world; on membership-epoch
        change, rejoin + rebuild mesh + refresh state from rank 0."""
        resp = self._mc.get_comm_rank()
        if resp.rank_id < 0:
            # Not registered in the group yet: announce and re-poll.
            self._mc.report_liveness()
            resp = self._mc.get_comm_rank()
        if resp.rank_id < 0:
            raise RuntimeError("master did not admit this worker to the group")
        if resp.rendezvous_id == self._group_id and not force:
            return
        # A set-up phase: the first world of this process, and every
        # regroup after it, with the epoch it joins.
        with tracing.span(
            "setup.world_init", cat=tracing.SETUP,
            epoch=resp.rendezvous_id,
        ):
            self._join_world(resp, force)

    def _join_world(self, resp, force):
        """Join the world `resp` describes: gate, mesh, state from rank 0
        or from this process's own snapshot, placed on the new mesh."""
        logger.info(
            "World change: epoch %d -> %d (rank %d of %d)",
            self._group_id,
            resp.rendezvous_id,
            resp.rank_id,
            resp.world_size,
        )
        if self._multi_host and resp.world_size > 1:
            # Two-phase join: wait at the master's gate until EVERY rank
            # of this epoch is about to initialize, so nobody blocks at a
            # stale epoch's coordination port while a peer is still busy
            # (the missed-rendezvous churn that killed workers with fatal
            # RegisterTask deadlines). If membership moves while waiting,
            # follow it to the new epoch.
            resp = self._await_join_gate(resp)
        self._rank = resp.rank_id
        self._world_size = resp.world_size
        if not force and self._try_fast_regroup(resp):
            return
        # Snapshot to host BEFORE any distributed teardown: device arrays of
        # the old world are unusable once jax.distributed re-initializes.
        with tracing.span("setup.snapshot_state", cat=tracing.SETUP):
            host_state = self._state_provider()
        if self._multi_host:
            # Quiesce the speculator BEFORE the backend teardown: an XLA
            # compile still executing on the old PJRT client when
            # ensure_world clears backends is a use-after-teardown race.
            # cancel() first so the drained result is discarded, then a
            # bounded wait for the in-flight compile to finish (compiles
            # cannot be interrupted; the bound mirrors the scale the
            # join gate already tolerates for peers' compiles).
            self._speculator.cancel()
            if not self._speculator.drain(timeout=120.0):
                logger.warning(
                    "A speculative compile is still in flight at "
                    "distributed re-init; proceeding — the stale "
                    "result will be discarded"
                )
            coordinator_ip = resp.coordinator_addr.rsplit(":", 1)[0]
            distributed.ensure_world(
                f"{coordinator_ip}:{resp.rendezvous_port}",
                resp.world_size,
                resp.rank_id,
                epoch=resp.rendezvous_id,
            )
        self._mesh = self._make_world_mesh()
        logger.info("Mesh axes: %s", dict(self._mesh.shape))
        self._sharded_steps = {}
        self._local_forward = None  # compiled against the torn-down backend
        self._rebuild_pipeline_build()
        self._rebind_sp_model()
        # Stamp the new world's fingerprint BEFORE any step (re)lowering:
        # the compile tracker attributes what follows to this regroup
        # (cause=mesh_change) instead of to shape drift. The token is the
        # SPEC fingerprint, not the membership epoch — a later epoch that
        # resolves to a mesh this process already compiled re-lowers as
        # `rebuild` (accurate: the mesh shape did not change), and
        # usually rehydrates from the persistent cache anyway.
        from elasticdl_tpu.observability import profiling

        profiling.note_mesh(
            f"t{self._mesh_salt}:{self._spec_token()}",
            world_size=resp.world_size,
        )
        if self._multi_host and jax.process_count() > 1:
            # SPMD world: sync state through an on-mesh collective that
            # EVERY member executes right after the rendezvous, instead of
            # a host gRPC pull. The pull deadlocks here: rank 0's device
            # stream can already be blocked inside the new world's first
            # collective, so its broadcast server can't serve device reads
            # (single-process-world regroups keep the gRPC path below —
            # they have no shared world to collective over).
            host_state = self._sync_state_over_world(host_state)
        elif self._rank != 0 and resp.coordinator_addr:
            pulled = self._pull_from_rank0(resp.coordinator_addr)
            if pulled is not None:
                host_state = pulled
        if host_state is not None:
            variables, opt_state, version = host_state
            with self._state_lock:
                self._variables = self._place_variables(variables)
                self._opt_state = self._place_opt_state(opt_state)
                self._version = version
        elif self._variables is not None:
            # Local device state was unreadable (poisoned by a failed
            # collective) and nothing could be pulled from rank 0: drop it
            # so init_variables_if_needed re-seeds from data instead of
            # replaying poisoned buffers into every retry.
            logger.warning(
                "No recoverable state after world change; re-seeding "
                "variables from data (version %d kept)", self._version,
            )
            with self._state_lock:
                self._variables = None
                self._opt_state = None
        self._group_id = resp.rendezvous_id
        _C_REGROUPS.labels(mode="rebuild").inc()
        emit_event(
            "elastic_regroup",
            mode="rebuild",
            epoch=resp.rendezvous_id,
            spec=self._spec_token(),
            world_size=resp.world_size,
        )
        # Re-aim the speculator at this world's neighbors: guesses for
        # worlds that did NOT form are dropped (a mid-compile guess is
        # discarded when it finishes — never waited on). Prebuilt
        # executables matching the world that DID form survive for
        # _sharded_step_for to consume — but ONLY when the backend was
        # not torn down: a multi-host regroup re-initializes
        # jax.distributed (ensure_world clears all backends), which
        # invalidates every live executable, so there the prebuilts are
        # dropped wholesale and speculation's value is the warm DISK
        # cache entries those compiles wrote.
        self._speculated.clear()
        keep = None if self._multi_host else self._spec_token()
        self._speculator.cancel(keep_fingerprint=keep)
        self._maybe_speculate()

    def _spec_token(self):
        """The current world's spec fingerprint — with a fallback to the
        raw mesh axes for tests that monkeypatch `_make_world_mesh` past
        the spec resolution."""
        if self._world_spec is not None:
            return self._world_spec.fingerprint()
        return str(dict(self._mesh.shape)) if self._mesh else ""

    def _try_fast_regroup(self, resp):
        """The recompile-free regroup: membership moved but the world
        resolves to the SAME spec on a stable backend (no jax.distributed
        re-init), so mesh, compiled steps, and state placement are all
        still valid — adopt the epoch, sync state if this rank is a
        (re)joiner, and keep training. This is the common case for every
        single-host elastic event (peer died / peer joined): the epoch
        bump used to cost a full ~compile-time re-lowering for nothing.
        """
        if self._mesh is None or self._world_spec is None:
            return False
        backend_stable = not self._multi_host or (
            resp.world_size <= 1 and not distributed.is_live()
        )
        if not backend_stable:
            return False
        new_spec = self._resolve_spec()
        if new_spec.fingerprint() != self._world_spec.fingerprint():
            return False
        # A non-zero rank still aligns state with rank 0 — membership
        # changed even though the mesh did not (this worker may BE the
        # rejoiner, or rank 0 may have moved).
        if self._rank != 0 and resp.coordinator_addr:
            pulled = self._pull_from_rank0(resp.coordinator_addr)
            if pulled is not None:
                variables, opt_state, version = pulled
                with self._state_lock:
                    self._variables = self._place_variables(variables)
                    self._opt_state = self._place_opt_state(opt_state)
                    self._version = version
        self._group_id = resp.rendezvous_id
        # Refresh the tracker's world_size with the SAME token: later
        # compile/compile_cache_hit events carry the new membership
        # without perturbing mesh_change attribution (the token is what
        # classification keys on, and it did not change).
        from elasticdl_tpu.observability import profiling

        profiling.note_mesh(
            f"t{self._mesh_salt}:{self._spec_token()}",
            world_size=resp.world_size,
        )
        _C_REGROUPS.labels(mode="fast").inc()
        emit_event(
            "elastic_regroup",
            mode="fast",
            epoch=resp.rendezvous_id,
            spec=new_spec.fingerprint(),
            world_size=resp.world_size,
        )
        logger.info(
            "World change to epoch %d absorbed without re-mesh "
            "(spec %s unchanged): compiled steps kept",
            resp.rendezvous_id,
            new_spec.fingerprint(),
        )
        self._maybe_speculate()
        return True

    def _await_join_gate(self, resp, timeout=None, poll_seconds=0.25):
        """Poll the master's join gate until the whole world of
        resp.rendezvous_id has arrived (world_ready), following any epoch
        bump to the newest world. Falls through with a warning after
        the budget (e.g. a master predating the gate always answers
        world_ready=False) — the jax.distributed initialization timeout
        then remains the backstop, as before the gate existed.

        timeout=None reads join_gate_budget(): the registered knob, or
        a floor scaled to the longest compile this process has measured
        (the fixed 90 s default lost to ~6.5 s step compiles on loaded
        1-core boxes)."""
        if timeout is None:
            timeout = join_gate_budget()
        deadline = time.time() + timeout
        last_liveness = 0.0
        while time.time() < deadline:
            # The gate can outlast the master's silent-worker watchdog
            # window; an actively-polling worker must not look dead
            # (re-register with the same host is a membership no-op).
            if time.time() - last_liveness > 5.0:
                self._mc.report_liveness()
                last_liveness = time.time()
            gated = self._mc.get_comm_rank(
                ready_epoch=resp.rendezvous_id
            )
            if gated.rendezvous_id != resp.rendezvous_id:
                if gated.rank_id < 0:
                    # Dropped from the group mid-gate (e.g. liveness
                    # timeout); announce and rejoin — paced, not a hot
                    # loop against the master while it churns.
                    self._mc.report_liveness()
                    time.sleep(poll_seconds)
                    continue
                logger.info(
                    "Membership moved at the join gate: epoch %d -> %d "
                    "(rank %d of %d)",
                    resp.rendezvous_id,
                    gated.rendezvous_id,
                    gated.rank_id,
                    gated.world_size,
                )
                resp = gated
                if resp.world_size <= 1:
                    return resp
                continue
            if gated.world_ready:
                return resp
            time.sleep(poll_seconds)
        logger.warning(
            "Join gate for epoch %d did not fill within %.0fs; "
            "proceeding to the rendezvous anyway",
            resp.rendezvous_id,
            timeout,
        )
        return resp

    def _sync_state_over_world(self, host_state):
        """Collective state broadcast from (new-world) rank 0: the TPU-first
        analog of the reference's `broadcast_variables(rank 0)` after a
        Horovod re-rendezvous (allreduce_trainer.py:150-152), expressed as
        XLA collectives over the fresh mesh rather than host RPC. Every
        process contributes its snapshot (zeros when it has none — a fresh
        joiner initialized params from data just for the shapes) and
        receives rank 0's (variables, opt_state, version) triple."""
        from jax.experimental import multihost_utils

        if host_state is None:
            # Poisoned local state (unreadable device buffers). The
            # broadcast is a collective, so this process must still
            # participate — with a zero template of the right shapes it
            # receives rank 0's state like any joiner. Without variables
            # at all there are no shapes to offer; every member hits the
            # same branch only at cold start, where data re-seed follows.
            if self._variables is None:
                return None
            variables = jax.tree_util.tree_map(
                lambda a: np.zeros(a.shape, a.dtype), self._variables
            )
            opt_state = jax.tree_util.tree_map(
                lambda a: np.zeros(
                    getattr(a, "shape", ()), getattr(a, "dtype", np.float32)
                ),
                self._opt_state,
            )
            host_state = (variables, opt_state, 0)
        variables, opt_state, version = host_state
        is_source = jax.process_index() == 0
        synced_vars, synced_opt, synced_version = (
            multihost_utils.broadcast_one_to_all(
                (variables, opt_state, np.int64(version)),
                is_source=is_source,
            )
        )
        version = int(synced_version)
        logger.info(
            "Collective state sync complete (version %d, source rank 0, "
            "this rank %d)",
            version,
            self._rank,
        )
        return (
            jax.tree_util.tree_map(np.asarray, synced_vars),
            jax.tree_util.tree_map(np.asarray, synced_opt),
            version,
        )

    def _pull_from_rank0(self, coordinator_addr):
        if self._variables is None:
            return None  # nothing local to align; init will seed from data
        # treedefs describe containers only — no device transfer needed.
        v_treedef = jax.tree_util.tree_structure(self._variables)
        o_treedef = jax.tree_util.tree_structure(self._opt_state)
        try:
            state = broadcast.pull_state(
                coordinator_addr, v_treedef, o_treedef
            )
        except Exception as e:
            logger.warning(
                "Broadcast pull from %s failed (%s); keeping local state",
                coordinator_addr,
                e,
            )
            return None
        if state is not None:
            logger.info(
                "Pulled rank-0 state (version %d) from %s",
                state[2],
                coordinator_addr,
            )
        return state

    # ---------- mesh / sharding layout (via the unified world spec) ----------

    def _world_topology(self):
        """The topology world resolution sees: the live backend, unless
        a test/bench pinned `_topo_override` to stand in for a world
        this process is not in."""
        if self._topo_override is not None:
            return self._topo_override
        return WorldTopology.current()

    def _parallel_config(self):
        """This trainer's parallel dimensions as the pure config slice
        `resolve_world_spec` consumes — hook presence as booleans, the
        per-world SP downgrade bit included."""
        return ParallelConfig(
            model_parallel=self._model_parallel_size,
            has_param_specs=self._param_specs_fn is not None,
            zero1=self._zero1,
            pipeline_stages=self._pipeline_stages,
            has_pipeline_spec=self._pipeline_spec_fn is not None,
            context_parallel=self._context_parallel_size,
            has_context_parallel_model=(
                self._context_parallel_model_fn is not None
            ),
            sp_suspended=self._sp_suspend_once,
        )

    def _param_check(self, mp):
        if self._variables is None:
            return []
        return step_plan.spec_violations(
            self._param_specs_fn, self._variables, mp
        )

    def _resolve_spec(self, topo=None):
        """Deterministically resolve the WorldSpec for `topo` (default:
        the current topology) under this trainer's config. Same config +
        same topology always yields the same fingerprint — the property
        the fast regroup path and the speculator are built on."""
        return resolve_world_spec(
            self._parallel_config(),
            topo if topo is not None else self._world_topology(),
            param_check=self._param_check,
        )

    def _make_world_mesh(self):
        spec = self._resolve_spec()
        for note in spec.notes:
            # Degrades stay as loud as the old ad-hoc ladder's warnings:
            # a silently dropped axis is duplicated compute.
            logger.warning("%s", note)
        self._world_spec = spec
        return spec.build_mesh()

    def _step_model(self):
        """What the step's builder (parallel/step_plan.py) is told of the
        model, hooks as bound to the current world."""
        return step_plan.StepModel(
            step_body=self._step_body,
            apply_train=self._apply_train,
            loss_fn=self._loss_fn,
            optax=self._optax,
            param_specs_fn=self._param_specs_fn,
            zero1=self._zero1,
            quantized_grads=self._quantized_grads,
            pipeline_build=self._pipeline_build,
            pipeline_microbatches=self._pipeline_microbatches,
            sp_model=self._sp_model,
        )

    def _n_processes(self):
        """The process count of the world the mesh was resolved for. The
        fallback is for tests that monkeypatch `_make_world_mesh` past
        the spec resolution."""
        if self._world_spec is not None:
            return self._world_spec.topology.n_processes
        return jax.process_count()

    def _place_variables(self, variables):
        """`variables` on the current mesh, laid out as the step takes
        them (the model spec's param_specs under TP, else replicated)."""
        with tracing.span("setup.place_variables", cat=tracing.SETUP):
            return jax.device_put(
                variables,
                step_plan.variables_sharding(
                    self._step_model(), self._mesh, variables
                ),
            )

    def _place_opt_state(self, opt_state):
        """`opt_state` on the current mesh (ZeRO-1 shards or replicated).
        Callers place and publish the variables first: with the old copy
        of one tree dropped before the next is made, a model that fills
        the chip has room (placing both and then publishing both read a
        peak of 16.0 GB for the hybrid cell's 13.3)."""
        with tracing.span("setup.place_opt_state", cat=tracing.SETUP):
            return jax.device_put(
                opt_state,
                step_plan.opt_placement(
                    self._step_model(), self._mesh, self._n_processes(),
                    opt_state,
                ),
            )

    def _tp_active(self):
        return step_plan.tp_active(self._step_model(), self._mesh)

    def _pp_active(self):
        return step_plan.pp_active(self._step_model(), self._mesh)

    def _rebind_sp_model(self):
        """(Re)bind the model spec's context_parallel_model hook to the
        current mesh's seq axis. Only the TRAIN step uses the bound
        model; init/eval/export keep self._model — same param tree, no
        sharding constraints on arbitrary eval batch shapes."""
        self._sp_model = None
        if (
            self._context_parallel_size <= 1
            or self._context_parallel_model_fn is None
            or SEQ_AXIS not in self._mesh.shape
            or self._mesh.shape[SEQ_AXIS] <= 1
        ):
            return
        head_axis = MODEL_AXIS if self._tp_active() else None
        try:
            self._sp_model = self._context_parallel_model_fn(
                mesh=self._mesh,
                axis_name=SEQ_AXIS,
                batch_axis=DATA_AXIS,
                head_axis=head_axis,
                impl=self._context_parallel_impl,
            )
        except ValueError as e:
            # World-scoped, not permanent: the rejection can depend on
            # this mesh (head_axis only exists when TP is active here);
            # the next world change retries the hook fresh.
            logger.warning(
                "context_parallel_model hook rejected this world's "
                "configuration (%s); running without sequence "
                "parallelism for this world — rebuilding a mesh "
                "without the seq axis", e,
            )
            self._sp_suspend_once = True
            try:
                self._mesh = self._make_world_mesh()
            finally:
                self._sp_suspend_once = False
            self._sharded_steps = {}
            logger.info("Mesh axes: %s", dict(self._mesh.shape))

    def _rebuild_pipeline_build(self):
        """(Re)bind the model spec's pipeline_spec hook to the current
        mesh. Runs on every world change — the factories close over the
        mesh. A hook that rejects the configuration (e.g. layer count not
        divisible by the stage count) downgrades to the monolithic model
        permanently: the rejection is config-determined, so every world
        would reject it the same way and the param tree stays consistent
        across regroups."""
        self._pipeline_build = None
        if self._pipeline_stages <= 1 or self._pipeline_spec_fn is None:
            return
        try:
            self._pipeline_build = self._pipeline_spec_fn(
                mesh=self._mesh,
                n_stages=self._pipeline_stages,
                num_microbatches=self._pipeline_microbatches,
                schedule=self._pipeline_schedule,
                batch_axis=DATA_AXIS,
                virtual_stages=self._pipeline_vstages,
            )
        except ValueError as e:
            logger.warning(
                "pipeline_spec hook rejected the configuration (%s); "
                "running the monolithic model data-parallel", e,
            )
            self._pipeline_stages = 1
            # The mesh just built may carry a stage axis the monolithic
            # step would duplicate compute over; rebuild without it (and
            # re-log, so the earlier "Mesh axes" line can't read as
            # pipelining being active).
            self._mesh = self._make_world_mesh()
            self._sharded_steps = {}
            logger.info("Mesh axes: %s", dict(self._mesh.shape))

    # ---------- sharded step ----------

    def _sharded_step_for(self, real_n, padded_n):
        # One compiled program per distinct (real_n, padded_n): full batches
        # share one entry; only the final partial minibatch of a task adds
        # variants, so the cache stays small in practice.
        key = (real_n, padded_n)
        step = self._sharded_steps.get(key)
        if step is None and self._world_spec is not None:
            # A speculative guess for exactly this world may already be
            # compiled: consume the executable instead of cold-compiling.
            fingerprint = self._world_spec.fingerprint()
            step = self._speculator.take(fingerprint, key)
            if step is not None:
                logger.info(
                    "Consuming speculatively compiled step for world %s "
                    "%s", fingerprint, key,
                )
                emit_event(
                    "aot_consumed", spec=fingerprint, shape_key=list(key)
                )
        if step is None:
            key, step = step_plan.build_step(
                self._step_model(), self._mesh, self._n_processes(),
                real_n, self._variables, self._opt_state,
            )
        self._sharded_steps[key] = step
        return step

    def plan_step_for_spec(self, spec, real_n):
        """AOT plan for a world this trainer is NOT currently in — the
        speculator's callback. Returns (shape_key, jitted step, abstract
        args) or None when the candidate world's step cannot be planned
        off-world: the pipeline/SP paths are bound to per-world hook
        state (their builds close over the live mesh, ROADMAP D3), and
        nothing can be planned before the first batch reveals its
        shapes."""
        if self._pipeline_build is not None or self._sp_model is not None:
            return None
        if spec.pp > 1 or spec.sp > 1:
            return None
        if self._variables is None or self._last_batch_abstract is None:
            return None
        key, step = step_plan.build_step(
            self._step_model(), spec.build_mesh(),
            spec.topology.n_processes, real_n,
            self._variables, self._opt_state,
        )
        abstract = self._abstract_step_args(key[1])
        if abstract is None:
            return None
        return key, step, abstract

    def _abstract_step_args(self, padded_n):
        """ShapeDtypeStruct tree for (variables, opt_state, rng,
        features, labels) with the batch re-padded to the candidate
        world's multiple — what `.lower()` needs to compile a step
        without concrete arrays."""

        def abs_of(a):
            shape = tuple(getattr(a, "shape", ()))
            dtype = getattr(a, "dtype", np.float32)
            return jax.ShapeDtypeStruct(shape, dtype)

        def repad(s):
            return jax.ShapeDtypeStruct(
                (padded_n,) + tuple(s.shape[1:]), s.dtype
            )

        feat_abs, label_abs, _ = self._last_batch_abstract
        try:
            return (
                jax.tree_util.tree_map(abs_of, self._variables),
                jax.tree_util.tree_map(abs_of, self._opt_state),
                abs_of(
                    jax.random.fold_in(self._step_rng_base, 0)
                ),
                jax.tree_util.tree_map(repad, feat_abs),
                jax.tree_util.tree_map(repad, label_abs),
            )
        except Exception:  # deleted/odd leaves mid-transition
            return None

    def _note_batch_abstract(self, features, labels, real_n):
        def abs_of(a):
            return jax.ShapeDtypeStruct(tuple(a.shape), a.dtype)

        self._last_batch_abstract = (
            jax.tree_util.tree_map(abs_of, features),
            jax.tree_util.tree_map(abs_of, labels),
            real_n,
        )

    def _maybe_speculate(self):
        """Queue AOT compiles for the worlds a regroup is most likely to
        land on next. Cheap when there is nothing to do: candidates are
        deduped per (spec, batch shape) and single-host worlds have no
        candidates at all (their spec is membership-invariant — the fast
        regroup path absorbs epoch bumps for free)."""
        if not speculation_enabled():
            return
        if self._world_spec is None or self._last_batch_abstract is None:
            return
        self._poll_world_hint()
        real_n = self._last_batch_abstract[2]
        current = self._world_spec.fingerprint()
        specs = []
        for topo in self._candidate_topologies():
            # Dedup on (topology, shape) BEFORE resolving: this runs
            # every step, and resolution under TP walks the whole
            # parameter tree (param_check) — pay that once per new
            # candidate, not per minibatch.
            tag = (topo, real_n)
            if tag in self._speculated:
                continue
            self._speculated.add(tag)
            if topo.n_devices < 1 or topo.n_devices > len(jax.devices()):
                # Worlds bigger than the live backend can't be built
                # here; their regroup is covered by the persistent
                # compilation cache instead.
                continue
            try:
                spec = self._resolve_spec(topo)
            except Exception:
                continue
            if spec.fingerprint() == current:
                continue
            specs.append(spec)
        if specs:
            self._speculator.submit(specs, real_n)

    def _poll_world_hint(self):
        """Throttled get_world_hint poll. A new announcement (hint_seq
        advanced) records the target world so _candidate_topologies
        front-loads it — the announced world beats the N±delta guesses."""
        if self._hint_poll_s <= 0:
            return
        now = time.time()
        if now - self._last_hint_poll < self._hint_poll_s:
            return
        self._last_hint_poll = now
        try:
            hint = self._mc.get_world_hint()
        except grpc.RpcError as e:
            code = e.code() if hasattr(e, "code") else None
            if code == grpc.StatusCode.UNIMPLEMENTED:
                # Pre-policy master: stop asking.
                self._hint_poll_s = 0.0
            return
        except Exception:
            return
        if hint.hint_seq > self._hint_seq_seen:
            self._hint_seq_seen = hint.hint_seq
            self._hinted_world = hint.target_world_size
            logger.info(
                "World hint #%d: target world %d (%s)",
                hint.hint_seq, hint.target_world_size, hint.reason,
            )

    def _candidate_topologies(self):
        if self._topo_candidates is not None:
            return list(self._topo_candidates)
        if not self._multi_host or self._world_size <= 1:
            # Single-host worlds: the mesh is device-determined; every
            # membership epoch resolves to the same spec, so there is
            # nothing to guess.
            return []
        local = jax.local_device_count()
        out = []
        hinted = self._hinted_world
        if hinted >= 1 and hinted != self._world_size:
            # The master TOLD us the next world; compile it first.
            out.append(WorldTopology(hinted * local, local, hinted))
        for delta in range(1, world_deltas() + 1):
            for w in (
                self._world_size - delta, self._world_size + delta
            ):
                if w >= 1 and w != self._world_size and w != hinted:
                    out.append(WorldTopology(w * local, local, w))
        return out

    def _init_pipeline_variables(self, features):
        """Lazy init for pipeline mode: params come from the build's
        init_fn (staged tree), not self._model.init."""
        import jax.numpy as jnp

        self._rng, init_rng = jax.random.split(self._rng)
        params = self._pipeline_build.init_fn(
            init_rng, jnp.asarray(np.asarray(features))
        )
        variables = {"params": params}
        with self._state_lock:
            self._variables = self._place_variables(variables)
            self._opt_state = self._place_opt_state(
                self._optax.init(self._variables["params"])
            )
        n_params = sum(
            int(np.prod(p.shape))
            for p in jax.tree_util.tree_leaves(params)
        )
        logger.info(
            "Initialized pipelined model with %d parameters "
            "(%d stage rows, schedule %s)",
            n_params,
            jax.tree_util.tree_leaves(params["stages"])[0].shape[0],
            self._pipeline_schedule if self._pp_active() else "sequential",
        )
        self._forward = self._build_forward()
        if self.restore_on_init:
            from elasticdl_tpu.common.save_utils import (
                restore_trainer_checkpoint,
            )

            path, self.restore_on_init = self.restore_on_init, None
            restore_trainer_checkpoint(self, path)

    def _build_forward(self):
        if self._pipeline_build is not None:
            from elasticdl_tpu.observability.profiling import tracked_jit

            apply_fn = self._pipeline_build.apply_fn

            def forward(variables, features):
                return apply_fn(
                    variables["params"], features, training=False
                )

            return tracked_jit(
                forward, name="pipeline_forward", key_argnums=(1,)
            )
        return super()._build_forward()

    # ---------- Trainer interface ----------

    def init_variables_if_needed(self, features):
        if self._pipeline_stages > 1:
            if self._mesh is None:
                self.init_world_if_needed(force=True)
                if self._variables is not None:
                    # Restored-before-world state (checkpoint resume):
                    # any forward built before the pipeline build existed
                    # compiled against the monolithic tree — rebuild.
                    self._forward = self._build_forward()
            if self._pipeline_build is not None:
                if self._variables is None:
                    with tracing.span("setup.model_init", cat=tracing.SETUP):
                        self._init_pipeline_variables(features)
                return
            # The hook rejected the config during world init: fall through
            # to the monolithic path below (stages was reset to 1).
        first_init = self._variables is None
        if first_init:
            # Parameter shapes do not depend on the batch: initialise
            # from ONE row. The base class runs model.init eagerly on
            # the default device, and the global batch of a multi-chip
            # world (16 x 4096 tokens of flagship logits) does not fit
            # one chip — found on the first four-chip run.
            features = jax.tree_util.tree_map(lambda a: a[:1], features)
            with tracing.span("setup.model_init", cat=tracing.SETUP):
                super().init_variables_if_needed(features)
        if self._mesh is None:
            self.init_world_if_needed(force=True)
        elif first_init:
            # The broadcast server's _state_provider reads (variables,
            # opt_state) as a pair from gRPC threads; replacing them one
            # by one outside the lock can serve a regrouping peer fresh
            # variables paired with stale optimizer moments.
            with self._state_lock:
                self._variables = self._place_variables(self._variables)
                self._opt_state = self._place_opt_state(self._opt_state)

    def train_minibatch(self, features, labels):
        self.init_variables_if_needed(features)
        self._steps_since_check += 1
        sync_step = self._steps_since_check >= self._steps_per_world_check
        if sync_step:
            self._steps_since_check = 0
            with tracing.span("trainer.world_check"):
                self.init_world_if_needed()
        features = jax.tree_util.tree_map(np.asarray, features)
        labels = jax.tree_util.tree_map(np.asarray, labels)
        for attempt in range(self._max_comm_retries):
            try:
                loss = self._run_sharded_step(features, labels)
                if sync_step and self._world_size > 1:
                    # Async dispatch means a collective failure surfaces on
                    # materialization, not dispatch. Block here — on the
                    # same cadence as the world check, which already costs
                    # a host round trip — so comm errors land inside this
                    # try block and the re-mesh/retry path below runs,
                    # instead of exploding later at a logging float().
                    # A world of one has no peer to lose a collective to,
                    # and the wait would drain its device for nothing
                    # (PERF.md section 6, PR 27).
                    with tracing.span("trainer.world_check"):
                        # edl-lint: disable=hot-path-sync
                        jax.block_until_ready(loss)
                return True, self._version, loss
            except RETRYABLE_ERRORS:
                if attempt == self._max_comm_retries - 1:
                    raise
                logger.warning(
                    "Sharded step failed (attempt %d); re-checking world",
                    attempt + 1,
                    exc_info=True,
                )
                time.sleep(min(3, 0.1 * 2**attempt))
                self.init_world_if_needed(force=True)

    def train_lease_minibatch(self, features, labels):
        """One SPMD step with NO world check and NO internal retry: in
        step-lease mode every member of the world must dispatch exactly the
        same step sequence, so recovery decisions belong to the lease loop
        (which abandons the lease and re-rendezvouses), not to a per-step
        retry that would desynchronize this rank from its peers."""
        self.init_variables_if_needed(features)
        features = jax.tree_util.tree_map(np.asarray, features)
        labels = jax.tree_util.tree_map(np.asarray, labels)
        return self._run_sharded_step(features, labels)

    def _run_sharded_step(self, features, labels):
        multiple = step_plan.batch_multiple(self._step_model(), self._mesh)
        padded_f, real_n = pad_batch_to_multiple(features, multiple)
        padded_l, _ = pad_batch_to_multiple(labels, multiple)
        padded_n = jax.tree_util.tree_leaves(padded_f)[0].shape[0]
        # Remember this batch's shape signature and (maybe) queue AOT
        # compiles for neighboring worlds — both are cheap bookkeeping;
        # actual speculative compilation runs in the background thread.
        self._note_batch_abstract(features, labels, real_n)
        self._maybe_speculate()
        step = self._sharded_step_for(real_n, padded_n)
        # Derive the dropout key from the SHARED model version, not a local
        # split chain: a joining worker's split count differs from the
        # incumbents', and in multi-host runs the step rng is a replicated
        # jit input that must be bit-identical across processes. version is
        # part of the rank-0 broadcast state, so fold_in(base, version) is
        # history-independent and agrees everywhere.
        step_rng = jax.random.fold_in(self._step_rng_base, self._version)
        # The step call stays OUTSIDE the state lock: a fresh
        # (real_n, padded_n) key compiles here (seconds), and holding
        # the lock across it would stall the broadcast provider past a
        # regrouping peer's pull budget. Donation is still safe: the
        # donated inputs are consumed at execution ENQUEUE — after
        # compile, microseconds before the under-lock swap below — and
        # _state_provider retries across exactly that window.
        with self._mesh:
            with datapath.get().stage("h2d"):
                device_f = shard_batch(padded_f, self._mesh)
                device_l = shard_batch(padded_l, self._mesh)
            # The enqueue; a compile, when there is one, is inside it.
            with tracing.span("trainer.dispatch"):
                new_variables, new_opt_state, loss = step(
                    self._variables,
                    self._opt_state,
                    step_rng,
                    device_f,
                    device_l,
                )
        with self._state_lock:
            self._variables = new_variables
            self._opt_state = new_opt_state
            loss, self.last_step_stats = split_stats(loss)
            self._version += 1
            # The eval host copy is stale from this step on; free it now
            # rather than pinning ~model-size host RAM until the next
            # eval task happens to overwrite it.
            self._eval_host_cache = None
        return loss

    def evaluate_minibatch(self, features, model_version=-1):
        if jax.process_count() <= 1:
            return super().evaluate_minibatch(features, model_version)
        # Same lazy-init guard as the base path: a relaunched worker can
        # draw an evaluation task before its first training lease.
        self.init_variables_if_needed(features)
        # Multi-host: the training variables live sharded across the global
        # mesh, but evaluation tasks are dispatched to ONE worker — a
        # global-mesh forward would need every process to participate.
        # Pull a host copy and run the forward on this process's local
        # devices only. The copy is cached keyed on (group_id, version):
        # an eval task's many minibatches all see one model version, and
        # re-downloading the model per minibatch is ~0.9 GB of host
        # transfer each for the flagship. A world change bumps group_id
        # (old-world device arrays are torn down), a train step bumps
        # version — either invalidates.
        with self._state_lock:
            key = (self._group_id, self._version)
            if (
                self._eval_host_cache is not None
                and self._eval_host_cache[0] == key
            ):
                host_vars = self._eval_host_cache[1]
            else:
                host_vars = jax.device_get(self._variables)
                self._eval_host_cache = (key, host_vars)
        if self._local_forward is None:
            from elasticdl_tpu.observability.profiling import tracked_jit

            if self._pipeline_build is not None:
                apply_fn = self._pipeline_build.apply_fn
                self._local_forward = tracked_jit(
                    lambda v, f: apply_fn(
                        v["params"], f, training=False
                    ),
                    name="allreduce_local_forward",
                    key_argnums=(1,),
                )
            else:
                self._local_forward = tracked_jit(
                    lambda v, f: self._model.apply(v, f, training=False),
                    name="allreduce_local_forward",
                    key_argnums=(1,),
                )
        outputs = self._local_forward(
            host_vars, jax.tree_util.tree_map(np.asarray, features)
        )
        return jax.tree_util.tree_map(np.asarray, outputs)

    def step_for_scopes(self):
        """(the sharded step of the newest batch, the shapes of its
        arguments, the mesh it is called in) for a map of its scopes
        (`observability/step_scopes.py`), or None before the first step.
        Each shape carries the sharding its array is placed with, the
        batch's as `shard_batch` places it: with the mesh, that is what
        jax keys a trace on, so that `lower` finds the step as it runs."""
        from elasticdl_tpu.observability.step_scopes import abstract_of
        from elasticdl_tpu.parallel.mesh import data_sharding

        if self._variables is None or self._last_batch_abstract is None:
            return None
        feat_abs, label_abs, real_n = self._last_batch_abstract
        multiple = step_plan.batch_multiple(self._step_model(), self._mesh)
        padded_n = -(-real_n // multiple) * multiple
        step = self._sharded_steps.get((real_n, padded_n))
        if step is None:
            return None
        data = data_sharding(self._mesh)

        def placed(s):
            return jax.ShapeDtypeStruct(
                (padded_n,) + tuple(s.shape[1:]),
                jax.dtypes.canonicalize_dtype(s.dtype), sharding=data)

        return step, (
            abstract_of(self._variables), abstract_of(self._opt_state),
            abstract_of(self._step_rng_base),
            jax.tree_util.tree_map(placed, feat_abs),
            jax.tree_util.tree_map(placed, label_abs),
        ), self._mesh

    def close(self):
        self._speculator.stop()
        self._broadcast_server.stop()
        if self._multi_host:
            distributed.leave_world()
