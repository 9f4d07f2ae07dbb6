"""Parameter-server-strategy trainer.

Reference counterpart: /root/reference/elasticdl/python/worker/
ps_trainer.py:36-441. Behaviors kept:

- pull dense params before stepping; a shard answering initialized=False is
  re-seeded by pushing local weights (the PS crash-recovery path,
  ps_trainer.py:149-184) — verified by test_ps_restart_reseed.
- fwd/bwd is one jitted function; embedding rows are prefetched OUTSIDE the
  step and differentiated as inputs (see layers/embedding.py for why this
  replaces the reference's mid-forward py_function RPC under XLA).
- gradients partition/merge/push via PSClient; a sync-mode rejection
  (stale version) re-pulls and recomputes the minibatch
  (ps_trainer.py:372-386).

Worker-side params are a cache of PS state (async SGD): the PS owns the
model version. With get_model_steps > 1 the worker additionally advances
its CACHED params through its own optimizer between pulls (the
reference's train_with_local_model) — the next successful pull overwrites
that local drift, so the PS remains the source of truth.
"""


import grpc
import jax
import jax.numpy as jnp
import numpy as np

from elasticdl_tpu.common import knobs
from elasticdl_tpu.common.log_utils import get_logger
from elasticdl_tpu.common.pytree_utils import (
    flatten_params,
    nest_at as _nest_at,
    unflatten_like,
    walk_dict as _walk_dict,
)
from elasticdl_tpu.layers.embedding import EMBEDDING_COLLECTION
from elasticdl_tpu.observability import datapath
from elasticdl_tpu.proto import elasticdl_tpu_pb2 as pb
from elasticdl_tpu.worker.trainer import JaxTrainer, _to_device_batch

logger = get_logger("worker.ps_trainer")

DEFAULT_MAX_PUSH_RETRIES = 3

def _unique_inverse(flat):
    """np.unique(flat, return_inverse=True) in the ids' NATIVE dtype —
    sorting 640k int32 ids costs ~2/3 of sorting their int64 widening
    (measured; a bitmap + rank-cumsum alternative measured slower) —
    with the unique set widened to the int64 the wire contract needs."""
    unique, inverse = np.unique(flat, return_inverse=True)
    return np.ascontiguousarray(unique, dtype=np.int64), inverse


class ParameterServerTrainer(JaxTrainer):
    def __init__(
        self,
        model,
        loss_fn,
        optimizer_spec,
        ps_client,
        embedding_inputs=None,
        embedding_threshold_bytes=None,
        embedding_device_capacity_bytes=0,
        use_async=True,
        max_push_retries=DEFAULT_MAX_PUSH_RETRIES,
        seed=0,
        pipeline_pushes=None,
        model_steps=1,
        prefetch_overlap=None,
    ):
        super().__init__(model, loss_fn, optimizer_spec, seed=seed)
        self._ps = ps_client
        # Bind this trainer's Timing to the client so push_gradients
        # decomposes into push_serialize/push_wire/push_apply sub-phases
        # alongside the trainer's own pull/prefetch/step/push phases
        # (Timing is thread-safe: the pipelined path pushes from the
        # background thread).
        if getattr(ps_client, "timing", None) is None:
            ps_client.timing = self.timing
        # bf16 wire dtype extends ACROSS the host<->device hop, not just
        # the TCP wire: prefetched rows upload as bf16 (widened to f32 on
        # the chip — exact) and the jitted step hands embedding grads
        # back as bf16 (the cast runs on device), so both transfer legs
        # move half the bytes, and the halving frees host
        # memcpy/serialize time as well (tools/ps_push_probe.py
        # decomposes the phase; what the hops cost on the current
        # machine is not measured). Precision:
        # rows already crossed the wire in bf16 (no new loss); grads
        # round to bf16 before the client's f32 dedup-sum instead of
        # after — the same order the wire cast imposes on single-
        # occurrence ids, now uniform for duplicates too.
        self._bf16_wire = bool(getattr(ps_client, "bf16_wire", False))
        # Pipelined pushes (async SGD only): the gradient device_get +
        # partition + RPC runs on a background thread while this thread
        # pulls/prefetches the NEXT batch — so the per-step critical path
        # is max(device_step, rpc) instead of their sum. One push in
        # flight keeps ordering and bounds the extra staleness at one
        # version (the same delay another worker's concurrent push would
        # cause; async SGD absorbs it by design). Sync mode keeps the
        # inline path: its stale-rejection handshake must complete before
        # the next pull.
        if pipeline_pushes is None:
            pipeline_pushes = use_async
        self._pipeline_pushes = pipeline_pushes and use_async
        self._push_executor = None
        self._push_future = None
        # Prefetch overlap (async SGD only — sync mode's exactness
        # contract excludes stale rows): the embedding lookup leaves the
        # critical path two ways. (1) Lookahead: when the caller passes
        # next_features, the NEXT batch's pull RPCs are issued right
        # after this step dispatches, so they run while the device
        # computes. (2) A versioned row cache (worker/row_cache.py)
        # serves recently pulled rows within a bounded version-staleness
        # budget — the same staleness class the pipelined push already
        # introduces. Both default on via ELASTICDL_PREFETCH_DEPTH /
        # ELASTICDL_PREFETCH_CACHE_ROWS.
        if prefetch_overlap is None:
            prefetch_overlap = (
                knobs.get_int("ELASTICDL_PREFETCH_DEPTH") > 0
            )
        self._prefetch_overlap = bool(prefetch_overlap) and use_async
        self._row_cache = None
        if (
            self._prefetch_overlap
            and knobs.get_int("ELASTICDL_PREFETCH_CACHE_ROWS") > 0
        ):
            from elasticdl_tpu.worker.row_cache import EmbeddingRowCache

            self._row_cache = EmbeddingRowCache()
        # One lookahead prefetch in flight: (features object, handle).
        self._pending_prefetch = None
        # get_model_steps (reference worker.py:314-327): pull fresh PS
        # params only every N training minibatches; in between, train
        # with the LOCAL model — gradients apply locally through the
        # worker's own optimizer while still being pushed every step.
        # Cuts the pull RPC (and its host decode) to 1/N.
        self._model_steps = max(1, int(model_steps or 1))
        self._since_pull = self._model_steps  # force a pull first
        self._local_step = None  # jitted local apply, built lazily
        # callable(features) -> {table_name: ids ndarray}. Optional: when
        # omitted, the ModelHandler auto-swaps oversized nn.Embed tables
        # to the PS and derives the feed by id capture (init below).
        self._embedding_inputs = embedding_inputs
        self._embedding_threshold_bytes = embedding_threshold_bytes
        # Upper placement tier: tables at or under this stay DEVICE-side
        # (row-sharded over the mesh on multi-device runs) instead of
        # PS-resident — see PSWrappedModel's tier table.
        self._embedding_device_capacity_bytes = (
            embedding_device_capacity_bytes
        )
        self._use_async = use_async
        self._max_push_retries = max_push_retries
        # Budget for _sync_model's re-seed/backoff loop on a degraded
        # shard before failing the minibatch up the retry ladder. The
        # bound applies between attempts: one in-flight pull can still
        # take up to its own rpc retry budget (deadline x attempts) on a
        # TCP-accepting-but-wedged peer, so the worst case is this budget
        # plus one pull's budget.
        self._degraded_block_seconds = knobs.get_float(
            "ELASTICDL_PS_DEGRADED_BLOCK_SECONDS"
        )
        self._param_names = None
        self._embedding_dims = {}  # table -> dim, derived at init
        # table -> module-scope path inside the edl_embedding collection
        # (flax nests collection entries under the owning module's path).
        self._embedding_paths = {}
        self._ps_step = None
        self._ps_forward = None
        # Set when the ModelHandler wrapped the user model (auto embedding
        # placement); export unwraps back to this original module's tree.
        self._inner_model = None
        self._embedding_vocab = {}  # table -> declared vocab (auto mode)

    # ---------- init ----------

    def init_variables_if_needed(self, features):
        if self._variables is not None:
            return
        auto = self._embedding_inputs is None
        if auto:
            # ModelHandler pass (common/model_handler.py): reroute any
            # nn.Embed over the size threshold to the PS. The wrapper is
            # discarded below if nothing swapped, so small models keep
            # their unprefixed param tree.
            from elasticdl_tpu.common.model_handler import (
                DEFAULT_THRESHOLD_BYTES,
                discover_tables,
                wrap_model_for_ps,
            )

            self._inner_model = self._model
            self._model = wrap_model_for_ps(
                self._model,
                self._embedding_threshold_bytes
                or DEFAULT_THRESHOLD_BYTES,
                device_capacity_bytes=(
                    self._embedding_device_capacity_bytes
                ),
            )
            with discover_tables() as discovered:
                super().init_variables_if_needed(features)
            # {table: (dim, vocab)} — vocab sizes the export reverse-swap.
            self._embedding_vocab = {
                t: vocab for t, (_, vocab) in discovered.items()
            }
        else:
            super().init_variables_if_needed(features)
        # The init-created embedding collection only carried shapes; rows
        # arrive per-batch. Record each table's dim and scope path, then
        # drop the collection from state.
        emb = self._variables.pop(EMBEDDING_COLLECTION, {})
        for path, leaf in _walk_dict(emb):
            table = path[-1]  # innermost key is the table_name
            self._embedding_dims[table] = int(leaf.shape[-1])
            self._embedding_paths[table] = path
        if auto and not self._embedding_dims:
            # Nothing swapped and no DistributedEmbedding layers: drop the
            # wrapper. It added exactly one 'inner' nesting level and no
            # params of its own, so stripping that level (instead of a
            # second full init/trace) restores the unprefixed tree.
            self._model = self._inner_model
            self._inner_model = None
            self._variables = {
                k: (v["inner"] if hasattr(v, "keys") and "inner" in v else v)
                for k, v in self._variables.items()
            }
            self._opt_state = self._optax.init(self._variables["params"])
            self._train_step = self._build_train_step()
            self._forward = self._build_forward()
        if self._embedding_dims and self._embedding_inputs is None:
            # Derive the feed the reference's ModelHandler made implicit:
            # capture which ids each table consumed on this first batch
            # and match them back to feature leaves.
            from elasticdl_tpu.common.model_handler import (
                derive_embedding_inputs,
            )

            self._embedding_inputs = derive_embedding_inputs(
                self._model, self._variables, features
            )
            if self._embedding_inputs is None:
                raise ValueError(
                    "model has PS-resident embedding tables "
                    f"{sorted(self._embedding_dims)} but the ids feed "
                    "could not be derived; provide embedding_inputs in "
                    "the model spec"
                )
        _, self._param_names = flatten_params(self._variables["params"])
        # First worker seeds the PS; later pushes are ignored there.
        self._push_local_model()
        self._ps_step = self._build_ps_step()
        self._ps_forward = self._build_ps_forward()

    def _embedding_infos(self):
        return [
            pb.EmbeddingTableInfo(
                name=name, dim=dim, initializer="uniform", dtype=pb.DT_FLOAT32
            )
            for name, dim in sorted(self._embedding_dims.items())
        ]

    def _push_local_model(self, only_unseeded=False):
        """only_unseeded: re-seed fan-out targets just the shards the last
        pull found uninitialized/unreachable — healthy shards would only
        discard the re-shipped model, and an outage's backoff loop calls
        this repeatedly."""
        named, _ = flatten_params(jax.device_get(self._variables["params"]))
        only_shards = None
        if only_unseeded and self._ps.unseeded_shards:
            only_shards = set(self._ps.unseeded_shards)
        self._ps.push_model(
            named,
            self._embedding_infos(),
            version=self._version,
            only_shards=only_shards,
        )

    # ---------- PS sync ----------

    def _maybe_sync_model(self):
        """Pull from the PS only when the local model is stale
        (get_model_steps-style local training): fresh pull resets the
        counter; between pulls the local optimizer keeps the dense params
        moving."""
        if self._since_pull >= self._model_steps:
            self._sync_model()
            return True
        self._since_pull += 1
        return False

    def _apply_local(self, param_grads):
        """Advance the LOCAL dense params with this step's grads (the
        reference's _update_local_model) so the next minibatch's forward
        doesn't need a pull. The PS still owns the truth — the next pull
        overwrites any local drift."""
        if self._local_step is None:
            from elasticdl_tpu.observability.profiling import tracked_jit

            def apply(params, opt_state, grads):
                updates, opt_state = self._optax.update(
                    grads, opt_state, params
                )
                import optax as _optax

                return _optax.apply_updates(params, updates), opt_state

            # key_argnums=(): params/opt_state/grads shapes are static
            # after init, and hashing three full trees per step is the
            # cost the train-step key deliberately avoids.
            # donate (params, opt_state): the caller replaces both with
            # the results, so XLA updates in place instead of
            # re-allocating a params+moments copy every local step.
            # grads are NOT donated — the pipelined path hands them to
            # the push thread after this apply.
            self._local_step = tracked_jit(
                apply, name="ps_local_apply", key_argnums=(),
                donate_argnums=(0, 1),
            )
        self._variables["params"], self._opt_state = self._local_step(
            self._variables["params"], self._opt_state, param_grads
        )

    def _sync_model(self):
        """Pull dense params; re-seed any uninitialized shard from local
        weights (that IS the PS fault-tolerance path).

        Dense pulls BLOCK with bounded backoff through a shard outage: an
        unreachable shard reports as uninitialized (PSClient marks it
        degraded instead of raising), this loop re-seeds + re-pulls with
        growing sleeps until the shard answers or the budget runs out,
        and only then raises — which hands recovery to the worker's
        minibatch retry ladder and, past that, the master's task retries."""
        import time as _time

        deadline = _time.time() + self._degraded_block_seconds
        backoff = 0.5
        while True:
            # The PSClient tracks per-shard pull cursors: a shard only
            # re-sends params newer than this client's last pull from it.
            initialized, version, named = self._ps.pull_dense_parameters(
                self._param_names
            )
            if initialized:
                break
            logger.info(
                "Uninitialized/degraded PS shard found; re-seeding from "
                "local (degraded=%s)",
                sorted(self._ps.degraded_shards),
            )
            try:
                self._push_local_model(only_unseeded=True)
                initialized, version, named = (
                    self._ps.pull_dense_parameters(self._param_names)
                )
                if initialized:
                    break
            except grpc.RpcError:
                # Every shard refused the re-seed: still mid-outage; keep
                # backing off until the budget runs out.
                pass
            if _time.time() >= deadline:
                raise RuntimeError(
                    "PS still uninitialized after re-seed (degraded "
                    f"shards: {sorted(self._ps.degraded_shards)})"
                )
            _time.sleep(backoff)
            backoff = min(backoff * 2, 4.0)
        if version < self._version:
            # Version consistency check for the relaunch path: a shard
            # that came back BEHIND this worker was restored from an older
            # checkpoint (or freshly re-seeded at a lower version). The PS
            # owns the model version — adopt its clock so this worker's
            # pushes don't arrive "from the future" forever.
            logger.warning(
                "PS model version regressed to %d (< local %d) — "
                "checkpoint-restored shard; adopting the PS version",
                version,
                self._version,
            )
            self._version = version
        if named:
            self._variables["params"] = unflatten_like(
                self._variables["params"],
                {k: jnp.asarray(v) for k, v in named.items()},
            )
        self._version = max(self._version, version)
        if self._row_cache is not None:
            self._row_cache.note_version(self._version)
        # Reset the local-training cadence only on a SUCCESSFUL pull: a
        # transient PS failure must not suppress re-pull attempts for the
        # next model_steps-1 minibatches.
        self._since_pull = 1

    def _start_prefetch(self, features, use_cache=True):
        """Issue the embedding pulls for one batch WITHOUT waiting.

        Per table: dedup the batch's ids, serve what the row cache can
        (within its staleness budget), and fire the pull RPC fan-out for
        the misses only. Returns an opaque handle for _finish_prefetch.
        The split is the overlap point: between start and finish the
        caller runs the dense pull — or, on the lookahead path, the
        whole previous step's device compute."""
        if not self._embedding_dims:
            return {}
        cache = self._row_cache if use_cache else None
        handle = {}
        # Tables often key off the SAME ids array (DeepFM's wide/deep
        # share one id space); dedup that work once per distinct array.
        uniq_memo = {}
        for table, ids in self._embedding_inputs(features).items():
            memo_key = id(ids)
            if memo_key in uniq_memo:
                flat, unique, inverse = uniq_memo[memo_key]
            else:
                # flat keeps the feature dtype (int32 ids sort faster);
                # the push path widens to int64 at the wire boundary.
                flat = np.asarray(ids).reshape(-1)
                unique, inverse = _unique_inverse(flat)
                uniq_memo[memo_key] = (flat, unique, inverse)
            hit, cached_rows = (None, None)
            miss_ids = unique
            if cache is not None:
                hit, cached_rows = cache.lookup(table, unique)
                miss_ids = unique[~hit]
            # bf16 wire: pull the rows AS bf16 and widen on the chip
            # (exact) — half the bytes across the host->device hop.
            pending = None
            if miss_ids.size:
                pending = self._ps.pull_embedding_vectors_async(
                    table, miss_ids, keep_wire_dtype=self._bf16_wire
                )
            handle[table] = (
                flat, unique, inverse, hit, cached_rows, miss_ids, pending
            )
        return handle

    def _finish_prefetch(self, handle, use_cache=True):
        """Harvest a _start_prefetch handle -> (rows pytree, flat_ids).
        Pulled miss rows enter the row cache stamped with the current
        version."""
        cache = self._row_cache if use_cache else None
        by_path, flat_ids = {}, {}
        for table, (
            flat, unique, inverse, hit, cached_rows, miss_ids, pending
        ) in handle.items():
            pulled = pending.result() if pending is not None else None
            if hit is None:  # cache not in play
                rows = pulled
            else:
                if cache is not None and pulled is not None:
                    cache.insert(table, miss_ids, pulled)
                if pulled is None:
                    rows = cached_rows  # every id hit, in unique order
                elif cached_rows is None:
                    rows = pulled  # every id missed
                else:
                    rows = np.empty(
                        (unique.size,) + pulled.shape[1:], pulled.dtype
                    )
                    rows[hit] = cached_rows
                    rows[~hit] = pulled
            by_path[self._embedding_paths[table]] = jnp.asarray(
                rows[inverse]
            )
            flat_ids[table] = flat
        return _nest_at(by_path), flat_ids

    def _prefetch_embeddings(self, features, use_cache=True):
        """features -> (rows {table: [n_positions, dim]}, flat_ids
        {table: [n_positions]}). Pulls unique ids only; expands back by
        inverse so the in-jit layer does a plain reshape. (The blocking
        wrapper over _start/_finish_prefetch — eval uses it.)"""
        if not self._embedding_dims:
            return {}, {}
        return self._finish_prefetch(
            self._start_prefetch(features, use_cache=use_cache),
            use_cache=use_cache,
        )

    def _take_pending_prefetch(self, features):
        """The lookahead handle issued for `features` last step, if the
        caller's hint matched (object identity — the hot loops hand the
        same batch objects back); a mismatch discards the handle (its
        futures complete harmlessly server-side)."""
        pending, self._pending_prefetch = self._pending_prefetch, None
        if pending is not None and pending[0] is features:
            return pending[1]
        return None

    # ---------- jitted steps ----------

    def _widen_rows(self, rows):
        """bf16-uploaded rows -> f32 on the chip (exact; the model's
        embedding math stays f32 regardless of the wire dtype)."""
        if not self._bf16_wire:
            return rows
        return jax.tree_util.tree_map(
            lambda r: r.astype(jnp.float32), rows
        )

    def _build_ps_step(self):
        def step(params, state, emb_rows, rng, features, labels):
            def loss_of(p, rows):
                mutable = [k for k in state]
                out = self._model.apply(
                    {
                        "params": p,
                        **state,
                        EMBEDDING_COLLECTION: self._widen_rows(rows),
                    },
                    features,
                    training=True,
                    rngs={"dropout": rng},
                    mutable=mutable if mutable else False,
                )
                outputs, new_state = out if mutable else (out, state)
                return self._loss_fn(labels, outputs), new_state

            # Differentiating through the bf16->f32 widen makes the row
            # cotangents come out bf16 automatically: the device casts,
            # and device_get in the push moves half the bytes.
            # (Design note: expanding unique rows by the batch inverse
            # INSIDE the jit — so the backward would segment-sum the
            # cotangents into pre-deduped [n_unique, dim] grads — was
            # tried and reverted: XLA's scatter-add costs ~5x the native
            # hash dedup on a CPU host. Host-side dedup stays.)
            (loss, new_state), grads = jax.value_and_grad(
                loss_of, argnums=(0, 1), has_aux=True
            )(params, emb_rows)
            return loss, grads[0], grads[1], new_state

        # Keyed on (emb_rows, features, labels): per-batch embedding row
        # counts are the shape axis that actually varies in PS mode.
        from elasticdl_tpu.observability.profiling import tracked_jit

        # Donate the mutable-state collections (new_state aliases state)
        # and the prefetched embedding rows (the row cotangents have the
        # rows' exact shape and dtype — the bf16 wire keeps both legs
        # bf16 — so XLA writes the grads into the rows' buffers instead
        # of allocating a second copy of the step's largest input).
        # params/features/labels stay un-donated: params live on in
        # self._variables between pulls, and the sync-mode retry loop
        # re-feeds the same device batch after a stale rejection.
        return tracked_jit(
            step, name="ps_step", key_argnums=(2, 4, 5),
            donate_argnums=(1, 2),
        )

    def _build_ps_forward(self):
        from elasticdl_tpu.observability.profiling import tracked_jit

        def forward(params, state, emb_rows, features):
            return self._model.apply(
                {
                    "params": params,
                    **state,
                    EMBEDDING_COLLECTION: self._widen_rows(emb_rows),
                },
                features,
                training=False,
            )

        return tracked_jit(
            forward, name="ps_forward", key_argnums=(2, 3)
        )

    # ---------- Trainer interface ----------

    def _push_payload(self, param_grads, emb_grads, flat_ids, version,
                      batch_size):
        """Materialize grads off-device, partition, and push. Runs inline
        (sync mode) or on the push thread (pipelined async mode), where
        the device_get doubles as the wait for the step's compute."""
        with self.timing.record("push_gradients"):
            # ONE batched D2H for the whole gradient tree: the per-leaf
            # np.asarray below used to issue a separate blocking
            # transfer per embedding table (hot-path-sync).
            param_grads, emb_grads = jax.device_get(
                (param_grads, emb_grads)
            )
            dense_named, _ = flatten_params(param_grads)
            sparse = {}
            for path, g in _walk_dict(emb_grads):
                table = path[-1]
                sparse[table] = (
                    np.asarray(g).reshape(
                        -1, self._embedding_dims[table]
                    ),
                    flat_ids[table],
                )
            accepted, version = self._ps.push_gradients(
                dense_named,
                sparse,
                version=version,
                batch_size=batch_size,
            )
        self._version = max(self._version, version)
        if self._row_cache is not None:
            # Our apply bumped the PS clock: age the cache so rows drop
            # out once they exceed the staleness budget. (Thread-safe —
            # this runs on the push thread in pipelined mode.)
            self._row_cache.note_version(self._version)
        return accepted, version

    def _flush_pushes(self):
        """Wait for the in-flight background push (read-your-writes for
        eval/export pulls; also the error-propagation point — a failed
        push raises here and the worker's retry machinery takes over)."""
        future, self._push_future = self._push_future, None
        if future is not None:
            future.result()

    def train_minibatch(self, features, labels, next_features=None):
        """next_features: optional hint — the NEXT batch the caller will
        train on. With prefetch overlap on (async pipelined mode), its
        embedding pulls are issued while this step's device compute and
        push run, taking the lookup off the next call's critical path."""
        self.init_variables_if_needed(features)
        if self._pipeline_pushes:
            return self._train_minibatch_pipelined(
                features, labels, next_features
            )
        with datapath.get().stage("h2d", timing=self.timing):
            device_features = _to_device_batch(features)
            device_labels = _to_device_batch(labels)
        for attempt in range(self._max_push_retries):
            # Issue the embedding pulls BEFORE the dense pull waits:
            # both fan-outs ride the wire together instead of in series.
            with self.timing.record("prefetch_issue"):
                handle = self._start_prefetch(features)
            with self.timing.record("pull_model"):
                if attempt == 0:
                    self._maybe_sync_model()
                else:
                    # A stale rejection means the local model diverged
                    # from the PS: the retry must re-pull regardless of
                    # the local-training cadence.
                    self._sync_model()
            with self.timing.record("prefetch_embeddings"):
                emb_rows, flat_ids = self._finish_prefetch(handle)
            self._rng, step_rng = jax.random.split(self._rng)
            state = {
                k: v for k, v in self._variables.items() if k != "params"
            }
            step_args = (
                self._variables["params"],
                state,
                emb_rows,
                step_rng,
                device_features,
                device_labels,
            )
            self.step_cost.observe(
                self._ps_step, step_args, key_args=step_args[4:]
            )
            with self.timing.record("train_step"):
                loss, param_grads, emb_grads, new_state = self._ps_step(
                    *step_args
                )
            self._variables.update(new_state)
            accepted, _ = self._push_payload(
                param_grads,
                emb_grads,
                flat_ids,
                self._version,
                int(np.asarray(labels).shape[0]),
            )
            if accepted:
                # Local apply only for ACCEPTED steps: a stale-rejected
                # attempt re-pulls anyway, and folding its grads into the
                # local Adam moments once per retry would bias them.
                if self._model_steps > 1:
                    self._apply_local(param_grads)
                # Lazy loss (Trainer contract): float() here would block
                # the host on the device every step; callers materialize
                # at the logging boundary.
                return True, self._version, loss
            logger.info(
                "Gradient push rejected as stale (attempt %d); re-pulling",
                attempt + 1,
            )
        return False, self._version, loss

    def _train_minibatch_pipelined(self, features, labels,
                                   next_features=None):
        """Async-SGD step with the push AND the embedding lookup off the
        critical path: while the device still computes step N, this
        thread already pulls params for step N+1, and step N+1's
        embedding pulls were issued LAST call (lookahead) — the
        reference's hot loop serializes a pull, a mid-forward lookup
        RPC, the step, and the push (ps_trainer.py:372-401)."""
        import concurrent.futures

        if self._push_executor is None:
            self._push_executor = concurrent.futures.ThreadPoolExecutor(
                max_workers=1, thread_name_prefix="edl-ps-push"
            )
        with datapath.get().stage("h2d", timing=self.timing):
            device_features = _to_device_batch(features)
            device_labels = _to_device_batch(labels)
        # These RPCs overlap the PREVIOUS step's device compute.
        handle = self._take_pending_prefetch(features)
        if handle is None:
            with self.timing.record("prefetch_issue"):
                handle = self._start_prefetch(features)
        with self.timing.record("pull_model"):
            self._maybe_sync_model()
        with self.timing.record("prefetch_embeddings"):
            emb_rows, flat_ids = self._finish_prefetch(handle)
        self._rng, step_rng = jax.random.split(self._rng)
        state = {
            k: v for k, v in self._variables.items() if k != "params"
        }
        step_args = (
            self._variables["params"],
            state,
            emb_rows,
            step_rng,
            device_features,
            device_labels,
        )
        self.step_cost.observe(
            self._ps_step, step_args, key_args=step_args[4:]
        )
        with self.timing.record("train_step_dispatch"):
            loss, param_grads, emb_grads, new_state = self._ps_step(
                *step_args
            )
        self._variables.update(new_state)
        if self._model_steps > 1:
            self._apply_local(param_grads)
        # One push in flight: wait out the previous (raising its errors),
        # then hand this step's grads to the push thread. Its device_get
        # blocks there until the step's compute finishes.
        self._flush_pushes()
        self._push_future = self._push_executor.submit(
            self._push_payload,
            param_grads,
            emb_grads,
            flat_ids,
            self._version,
            int(np.asarray(labels).shape[0]),
        )
        # Lookahead: issue the NEXT batch's embedding pulls now — they
        # ride the wire while this step's device compute and push finish,
        # so the next call's prefetch phase is just a harvest.
        if self._prefetch_overlap and next_features is not None:
            with self.timing.record("prefetch_issue"):
                self._pending_prefetch = (
                    next_features, self._start_prefetch(next_features)
                )
        # Lazy loss: materializing here would re-serialize the pipeline.
        return True, self._version, loss

    def evaluate_minibatch(self, features, model_version=-1):
        self.init_variables_if_needed(features)
        self._flush_pushes()  # read-your-writes for the eval pull
        self._sync_model()
        # use_cache=False: eval reads the freshest rows — the bounded
        # staleness the training loop absorbs has no place in metrics.
        emb_rows, _ = self._prefetch_embeddings(features, use_cache=False)
        state = {k: v for k, v in self._variables.items() if k != "params"}
        outputs = self._ps_forward(
            self._variables["params"],
            state,
            emb_rows,
            _to_device_batch(features),
        )
        return jax.tree_util.tree_map(np.asarray, outputs)

    def get_model_version(self):
        return self._version

    def close(self):
        try:
            self._flush_pushes()
        finally:
            if self._push_executor is not None:
                self._push_executor.shutdown(wait=True)
                self._push_executor = None

    def export_variables(self):
        """Export with the reverse swap (reference model_handler.py:242-268):
        pull final dense params AND full embedding tables from the PS, stuff
        tables back into the ORIGINAL model's param tree as plain
        `embedding` params, and strip the ModelHandler wrapper's nesting so
        the checkpoint loads into the user's stock model."""
        if self._variables is None:
            return None
        self._flush_pushes()  # the export must include the last push
        self._sync_model()
        variables = jax.device_get(dict(self._variables))
        params = variables["params"]
        if self._inner_model is not None:
            params = params.get("inner", params)
            ps_tables = {}
            for table, dim in self._embedding_dims.items():
                ids, values = self._ps.pull_embedding_table(
                    table, dim=dim
                )
                if values is not None:
                    ps_tables[table] = (ids, values)
            from elasticdl_tpu.common.model_handler import (
                stuff_export_params,
            )

            params = stuff_export_params(
                params, ps_tables, default_vocab=self._embedding_vocab
            )
            variables = {
                k: (v.get("inner", v) if hasattr(v, "get") else v)
                for k, v in variables.items()
            }
        variables["params"] = params
        return {"variables": variables, "version": self._version}
