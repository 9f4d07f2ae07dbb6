"""Worker-side client for the sharded parameter servers.

Reference counterpart: /root/reference/elasticdl/python/worker/
ps_client.py:32-246. Partitioning kept bit-compatible with the store:
dense parameters by sha256(name) mod N, embedding ids by id mod N
(common/hash_utils.py). All fan-outs use gRPC futures so the N shards work
in parallel; sparse grads are merged/deduplicated *before* the wire
(ps_client.py:135-232).
"""

import os
import time

import grpc
import numpy as np

from elasticdl_tpu.common import hash_utils, knobs, rpc, tensor_utils
from elasticdl_tpu.common.log_utils import get_logger
from elasticdl_tpu.observability import emit_event, tracing
from elasticdl_tpu.observability.metrics import default_registry
from elasticdl_tpu.proto import elasticdl_tpu_pb2 as pb

logger = get_logger("worker.ps_client")


class _PendingVectorPull:
    """In-flight pull_embedding_vectors fan-out; result() harvests."""

    def __init__(self, client, ids, futures, keep_wire_dtype):
        self._client = client
        self._ids = ids
        self._futures = futures
        self._keep_wire_dtype = keep_wire_dtype

    def result(self):
        out = None
        for ps_id, (positions, f) in self._futures.items():
            try:
                result = f.result()
            except grpc.RpcError as e:
                # Embedding rows are REQUIRED for this batch — no partial
                # answer is usable. Mark the shard and raise; the worker's
                # minibatch retry ladder re-pulls once the shard returns.
                self._client._mark_degraded(ps_id, e)
                raise
            self._client._mark_healthy(ps_id)
            values = tensor_utils.tensor_pb_to_ndarray(result)
            if values.dtype != np.float32 and not self._keep_wire_dtype:
                values = values.astype(np.float32)
            if out is None:
                out = np.empty(
                    (len(self._ids), values.shape[1]), dtype=values.dtype
                )
            out[positions] = values
        return out

_REG = default_registry()
_DEGRADED = _REG.gauge(
    "edl_ps_shards_degraded", "PS shards this worker currently sees as down"
)
_DROPPED_PUSHES = _REG.counter(
    "edl_ps_grad_pushes_dropped_total",
    "Per-shard gradient pushes dropped because the shard was unreachable",
)


class PSClient:
    def __init__(self, ps_addrs, worker_id=-1, wire_dtype=None):
        """ps_addrs: list of "host:port", index = ps_id.

        wire_dtype: wire codec, one of "float32" / "bfloat16" / "int8"
        (None reads the ELASTICDL_WIRE_DTYPE knob). bf16 halves the
        sparse hot path's pull/push bandwidth; int8 additionally
        block-quantizes DENSE gradients (EQuARX-style absmax blocks,
        ELASTICDL_WIRE_BLOCK_SIZE) with worker-side error-feedback
        residuals so the quantization error stays out of the training
        trajectory — embedding values/grads travel bf16 under int8
        (per-id residuals for sparse rows would need a table-sized
        shadow). Dense PARAMETER pulls always travel f32: the optimizer
        moments live in f32 on the PS and params are pulled once per
        model_steps, not per step."""
        if wire_dtype is None or wire_dtype == "":
            wire_dtype = knobs.get_str("ELASTICDL_WIRE_DTYPE")
        if wire_dtype not in ("float32", "bfloat16", "int8"):
            raise ValueError(f"unsupported wire_dtype {wire_dtype!r}")
        self.wire_dtype = wire_dtype
        # Public: the trainer keys its device-side dtype plumbing off
        # the wire dtype (bf16 rows/grads stay bf16 across the
        # host<->device hop too). int8 keeps the bf16 embedding legs.
        self.bf16_wire = wire_dtype in ("bfloat16", "int8")
        self.int8_dense = wire_dtype == "int8"
        self._block_size = knobs.get_int("ELASTICDL_WIRE_BLOCK_SIZE")
        # Error-feedback residuals, one per dense grad name: what the
        # last quantization rounded away, re-injected into the next push.
        self._ef_residual = {}
        # Packed-push chunking: sub-requests of one push share a push_id
        # (salted by pid so anonymous workers on one host can't collide
        # in the PS's reassembly map).
        self._max_push_bytes = knobs.get_int("ELASTICDL_PS_MAX_PUSH_BYTES")
        self._push_salt = (os.getpid() & 0xFFFFFFFF) << 24
        self._push_seq = 0
        # Optional common.timing.Timing: when bound (the PS trainer binds
        # its own), push_gradients records its serialize/wire/apply
        # sub-phases there — the decomposition tools/ps_push_probe.py and
        # tools/step_report.py need to attribute the dominant phase.
        self.timing = None
        self._addrs = list(ps_addrs)
        self._worker_id = worker_id
        # Readiness-probe all shards CONCURRENTLY, then build channels
        # without re-probing: serial probing would cost num_dead * timeout
        # at worker startup when shards are mid-relaunch, exactly when a
        # relaunched worker should be back serving the healthy shards.
        self._probe_ready_concurrently()
        self._channels = [
            rpc.build_channel(a, ready_timeout=0) for a in self._addrs
        ]
        self._stubs = [
            rpc.Stub(ch, rpc.PSERVER_SERVICE) for ch in self._channels
        ]
        self.num_ps = len(self._stubs)
        # Per-shard pull cursors: each shard's version advances independently
        # (only pushes touching it bump it), so "what have I already got"
        # must be tracked per shard, not as one global number.
        self._dense_versions = [-1] * self.num_ps
        # Shard-failure awareness: a shard whose RPCs fail (after the rpc
        # plane's retries) is marked degraded instead of crashing the
        # worker. Degraded shards skip gradient pushes (async SGD absorbs
        # the lost update), report as uninitialized on dense pulls (the
        # trainer's re-seed path owns recovery), and flip back to healthy
        # on the first successful call.
        self._degraded = set()
        # Shards whose last dense pull answered initialized=False (or was
        # unreachable) — the targets a re-seed push actually needs; a
        # full-fan-out re-seed would re-ship every healthy shard a model
        # it ignores, on every backoff iteration of an outage.
        self.unseeded_shards = set()

    def close(self):
        for ch in self._channels:
            ch.close()

    def _probe_ready_concurrently(self):
        import concurrent.futures

        timeout = rpc.ready_timeout()
        if timeout <= 0 or not self._addrs:
            return
        with concurrent.futures.ThreadPoolExecutor(
            max_workers=len(self._addrs)
        ) as pool:
            ready = list(
                pool.map(
                    lambda a: rpc.wait_channel_ready(a, timeout),
                    self._addrs,
                )
            )
        for ps_id, ok in enumerate(ready):
            if not ok:
                logger.warning(
                    "PS shard %d (%s) not accepting connections after "
                    "%.0fs; proceeding (retries/degradation take over)",
                    ps_id,
                    self._addrs[ps_id],
                    timeout,
                )

    # ---------- shard health ----------

    @property
    def degraded_shards(self):
        return set(self._degraded)

    def _mark_degraded(self, ps_id, err):
        if ps_id not in self._degraded:
            self._degraded.add(ps_id)
            _DEGRADED.set(len(self._degraded))
            code = err.code() if hasattr(err, "code") else None
            logger.warning(
                "PS shard %d (%s) degraded: %s",
                ps_id,
                self._addrs[ps_id],
                getattr(code, "name", code),
            )
            emit_event(
                "ps_shard_degraded",
                ps=ps_id,
                addr=self._addrs[ps_id],
                code=str(getattr(code, "name", code)),
            )

    def _mark_healthy(self, ps_id):
        if ps_id in self._degraded:
            self._degraded.discard(ps_id)
            _DEGRADED.set(len(self._degraded))
            logger.info(
                "PS shard %d (%s) healthy again",
                ps_id,
                self._addrs[ps_id],
            )
            emit_event(
                "ps_shard_recovered", ps=ps_id, addr=self._addrs[ps_id]
            )

    # ---------- partitioning ----------

    def partition_dense_names(self, names):
        """{ps_id: [names]} by stable name hash."""
        parts = {}
        for name in names:
            parts.setdefault(
                hash_utils.string_to_id(name, self.num_ps), []
            ).append(name)
        return parts

    # ---------- model init / re-seed ----------

    def push_model(self, dense_params, embedding_infos=None, version=0,
                   only_shards=None):
        """Push each PS its shard of the dense params + all table infos
        (first-worker init AND the PS-restart re-seed path).

        only_shards: restrict the fan-out to these ps_ids (the re-seed
        path targets just the unseeded shards instead of re-shipping the
        model to healthy ones that ignore it).

        A shard that rejects the push (still down mid-relaunch) is marked
        degraded and skipped — the next _sync_model re-seed retries it;
        only when EVERY targeted shard fails does the error propagate
        (nothing was seeded, so the caller cannot make progress). Returns
        the set of shards seeded."""
        parts = self.partition_dense_names(dense_params)
        futures = []
        for ps_id, stub in enumerate(self._stubs):
            if only_shards is not None and ps_id not in only_shards:
                continue
            model = pb.Model(version=version)
            for name in parts.get(ps_id, []):
                model.dense_parameters.append(
                    tensor_utils.ndarray_to_tensor_pb(
                        np.ascontiguousarray(
                            dense_params[name], dtype=np.float32
                        ),
                        name,
                    )
                )
            for info in embedding_infos or []:
                model.embedding_table_infos.append(info)
            futures.append((ps_id, stub.push_model.future(model)))
        seeded, last_err = set(), None
        for ps_id, f in futures:
            try:
                f.result()
            except grpc.RpcError as e:
                last_err = e
                self._mark_degraded(ps_id, e)
                continue
            self._mark_healthy(ps_id)
            seeded.add(ps_id)
        if not seeded and last_err is not None:
            raise last_err
        return seeded

    def push_embedding_table_infos(self, infos):
        model = pb.Model()
        model.embedding_table_infos.extend(infos)
        futures = [
            (ps_id, stub.push_embedding_table_infos.future(model))
            for ps_id, stub in enumerate(self._stubs)
        ]
        last_err, delivered = None, 0
        for ps_id, f in futures:
            try:
                f.result()
            except grpc.RpcError as e:
                # A shard that misses the infos serves no embeddings; the
                # re-seed path replays them (push_model carries the infos).
                last_err = e
                self._mark_degraded(ps_id, e)
                continue
            self._mark_healthy(ps_id)
            delivered += 1
        if not delivered and last_err is not None:
            raise last_err

    # ---------- pulls ----------

    def pull_dense_parameters(self, names, version=None):
        """Pull the given dense params from their shards.

        version=None uses the internal per-shard cursors (each shard only
        re-sends params newer than what this client already pulled);
        an explicit version overrides for all shards.

        Returns (all_initialized, max_version, {name: ndarray}); params is
        partial when some shard reported initialized=False (that shard needs
        a re-seed via push_model) OR was unreachable (marked degraded here;
        the caller's re-seed/backoff loop owns recovery — a dense pull
        blocks-with-backoff rather than crashing the worker)."""
        parts = self.partition_dense_names(names)
        futures = {
            ps_id: self._stubs[ps_id].pull_dense_parameters.future(
                pb.PullDenseParametersRequest(
                    version=self._dense_versions[ps_id]
                    if version is None
                    else version
                )
            )
            for ps_id in range(self.num_ps)
        }
        params, initialized, max_version = {}, True, 0
        for ps_id, f in futures.items():
            try:
                res = f.result()
            except grpc.RpcError as e:
                self._mark_degraded(ps_id, e)
                initialized = False
                self.unseeded_shards.add(ps_id)
                self._dense_versions[ps_id] = -1
                continue
            self._mark_healthy(ps_id)
            if not res.initialized:
                initialized = False
                self.unseeded_shards.add(ps_id)
                # Force a full re-pull from this shard once it comes back.
                self._dense_versions[ps_id] = -1
                continue
            self.unseeded_shards.discard(ps_id)
            self._dense_versions[ps_id] = res.version
            max_version = max(max_version, res.version)
            wanted = set(parts.get(ps_id, []))
            for t in res.dense_parameters:
                if t.name in wanted:
                    params[t.name] = tensor_utils.tensor_pb_to_ndarray(t)
        return initialized, max_version, params

    def pull_embedding_vectors(self, name, ids, keep_wire_dtype=False):
        """ids [k] -> [k, dim] rows, gathered across shards by id modulo and
        restored to input order.

        keep_wire_dtype=True hands bf16-wire rows back AS bf16 instead of
        widening to f32 on the host: bf16 -> f32 is exact, so a caller
        that uploads the rows to a device (the PS trainer's prefetch) can
        defer the widening to the chip and move half the bytes across the
        host->device hop (tools/ps_push_probe.py decomposes the phase;
        what that hop costs on the current machine is not measured)."""
        pending = self.pull_embedding_vectors_async(
            name, ids, keep_wire_dtype=keep_wire_dtype
        )
        return pending.result() if pending is not None else None

    def pull_embedding_vectors_async(self, name, ids,
                                     keep_wire_dtype=False):
        """Issue the per-shard pull fan-out and return a handle whose
        ``result()`` harvests it — the prefetch-overlap path issues these
        for several tables (and for the NEXT batch) while the device is
        still busy with the current step. Returns None for empty ids."""
        ids = np.asarray(ids, dtype=np.int64)
        if ids.size == 0:
            return None
        scattered = hash_utils.scatter_embedding_ids(ids, self.num_ps)
        value_dtype = pb.DT_BFLOAT16 if self.bf16_wire else pb.DT_INVALID
        futures = {
            ps_id: (
                positions,
                self._stubs[ps_id].pull_embedding_vectors.future(
                    pb.PullEmbeddingVectorsRequest(
                        name=name,
                        ids_bytes=tensor_utils.ids_to_bytes(shard_ids),
                        value_dtype=value_dtype,
                    )
                ),
            )
            for ps_id, (shard_ids, positions) in scattered.items()
        }
        return _PendingVectorPull(self, ids, futures, keep_wire_dtype)

    def pull_embedding_table(self, name, page_bytes=64 << 20, dim=None):
        """Every materialized (id, row) of a table, merged across shards —
        the export reverse-swap. Pulled in pages so a CTR-scale table
        never has to fit one gRPC message (256 MB cap); pass `dim` so the
        FIRST page is bounded too (wide tables would otherwise blow the
        cap before the row size is known). Returns (ids [n],
        values [n, dim]); (empty, None) if no rows exist."""
        if dim:
            first_page = max(1, page_bytes // (int(dim) * 4))
        else:
            first_page = 65536
        all_ids, all_values = [], []
        for ps_id, stub in enumerate(self._stubs):
            start, requested = 0, first_page
            while True:
                try:
                    res = stub.pull_embedding_table(
                        pb.PullEmbeddingTableRequest(
                            name=name, start_row=start, max_rows=requested
                        )
                    )
                except grpc.RpcError as e:
                    # Export needs every shard's rows; a partial table
                    # would silently corrupt the exported model.
                    self._mark_degraded(ps_id, e)
                    raise
                values, ids = tensor_utils.indexed_slices_pb_to_ndarrays(
                    res
                )
                if ids.size:
                    all_ids.append(ids)
                    all_values.append(values)
                if ids.size < requested:  # short page = last page
                    break
                start += ids.size
                row_bytes = values.dtype.itemsize * values.shape[1]
                requested = max(1, page_bytes // max(row_bytes, 1))
        if not all_ids:
            return np.empty(0, np.int64), None
        return np.concatenate(all_ids), np.concatenate(all_values)

    # ---------- gradient push ----------

    def push_gradients(
        self, dense_grads, sparse_grads, version, learning_rate=0.0,
        batch_size=0,
    ):
        """dense_grads: {name: ndarray}; sparse_grads:
        {table_name: (values [k, dim], ids [k])} — deduplicated here before
        partitioning. batch_size = records in the minibatch behind this
        push (feeds the checkpoint's exact consumed-record counter).
        Returns (accepted_all, max_version).

        The push travels the PACKED wire (push_gradients_packed): a slim
        span header plus one out-of-band payload assembled from zero-copy
        views over the gradient arrays — no per-tensor tobytes, no proto
        CopyFrom. Payloads over ELASTICDL_PS_MAX_PUSH_BYTES split into
        chunked sub-requests so one giant embedding slice can't stall the
        channel past its per-method deadline.

        Sub-span attribution (when ``self.timing`` is bound): the push
        splits into push_serialize (host-side dedup + quantize + span
        packing), push_apply (the slowest shard's optimizer apply,
        reported back on PushGradientsResponse.apply_seconds — shards
        apply concurrently, so the max is what gated the wait), and
        push_wire (the remaining RPC wait: serialize-join, TCP, and
        payload decode on both ends)."""
        serialize_start = time.perf_counter()
        with tracing.span("ps_push_serialize"):
            requests = self._build_packed_requests(
                dense_grads, sparse_grads, version, learning_rate,
                batch_size,
            )
        serialize_s = time.perf_counter() - serialize_start
        wait_start = time.perf_counter()
        apply_s = 0.0
        with tracing.span("ps_push_wait"):
            futures = [
                (
                    ps_id,
                    [
                        self._stubs[ps_id].push_gradients_packed.future(r)
                        for r in reqs
                    ],
                )
                for ps_id, reqs in requests.items()
            ]
            accepted, max_version = True, 0
            delivered, last_err = 0, None
            for ps_id, shard_futures in futures:
                shard_err = None
                for f in shard_futures:
                    try:
                        res = f.result()
                    except grpc.RpcError as e:
                        # Degraded shard: drop its slice of this step's
                        # gradients (async SGD tolerates a lost update
                        # the same way it tolerates staleness) and keep
                        # the healthy shards' updates. A failed CHUNK
                        # fails the whole shard slice — the PS GC's the
                        # partial reassembly by age.
                        shard_err = e
                        break
                    accepted = accepted and res.accepted
                    max_version = max(max_version, res.version)
                    apply_s = max(apply_s, res.apply_seconds)
                if shard_err is not None:
                    last_err = shard_err
                    self._mark_degraded(ps_id, shard_err)
                    _DROPPED_PUSHES.inc()
                    continue
                self._mark_healthy(ps_id)
                delivered += 1
        if self.timing is not None:
            wait_s = time.perf_counter() - wait_start
            self.timing.add("push_serialize", serialize_s)
            self.timing.add("push_apply", apply_s)
            self.timing.add("push_wire", max(wait_s - apply_s, 0.0))
        if not delivered and last_err is not None:
            # Every shard refused: no progress is being recorded anywhere;
            # surface the failure so the retry ladder (and ultimately the
            # master's task retry accounting) sees it.
            raise last_err
        return accepted, max_version

    def _build_packed_requests(self, dense_grads, sparse_grads, version,
                               learning_rate, batch_size):
        """{ps_id: [PackedPushRequest, ...]} for one gradient push.

        Dense grads pack as f32 views (zero host copies) or, under the
        int8 codec, as block-quantized spans with error feedback: the
        residual the last quantization rounded away joins this step's
        grad before quantizing, and the new round-off becomes the next
        residual — the EQuARX recipe that keeps low-bit wire codecs from
        biasing convergence. Sparse grads dedup once, then bucket by
        id-sorted shard order with ONE gather for all shards — each
        shard's rows are a contiguous block whose span is a view, where
        the proto path gathered + copied per shard."""
        worker_id_plus_one = (
            self._worker_id + 1 if self._worker_id >= 0 else 0
        )
        headers, payloads = {}, {}

        def ensure(ps_id):
            if ps_id not in headers:
                headers[ps_id] = pb.PushGradientsPackedRequest(
                    version=version,
                    learning_rate=learning_rate,
                    worker_id_plus_one=worker_id_plus_one,
                    batch_size=batch_size,
                    chunk_count=1,
                )
                payloads[ps_id] = tensor_utils.PackedPayload()
            return headers[ps_id], payloads[ps_id]

        for ps_id, names in self.partition_dense_names(
            dense_grads
        ).items():
            header, payload = ensure(ps_id)
            for name in names:
                arr = np.ascontiguousarray(
                    dense_grads[name], dtype=np.float32
                )
                if self.int8_dense:
                    residual = self._ef_residual.get(name)
                    if residual is not None:
                        arr = arr + residual
                    q, scales = tensor_utils.quantize_int8_blocks(
                        arr, self._block_size
                    )
                    dq = tensor_utils.dequantize_int8_blocks(
                        q, scales, self._block_size
                    ).reshape(arr.shape)
                    self._ef_residual[name] = arr - dq
                    header.dense.append(
                        tensor_utils.pack_quantized_span(
                            name, arr.shape, q, scales,
                            self._block_size, payload,
                        )
                    )
                else:
                    header.dense.append(
                        tensor_utils.pack_tensor_span(name, arr, payload)
                    )
        # Tables that share one input ids array (DeepFM wide/deep) dedup
        # to identical id sets: the shard bucketing (lexsort + bounds) is
        # computed once and reused across them.
        bucket_memo = {}
        for table, (values, ids) in sparse_grads.items():
            memo_key = id(ids)
            values, ids = tensor_utils.deduplicate_indexed_slices(
                np.asarray(values, dtype=np.float32),
                np.asarray(ids, dtype=np.int64),
            )
            if self.bf16_wire and values.dtype != tensor_utils.bfloat16:
                values = values.astype(tensor_utils.bfloat16)
            if self.num_ps == 1:
                # One shard: no bucketing, no gather — the deduped
                # values/ids ship as-is (spans are views over them).
                header, payload = ensure(0)
                header.sparse.append(
                    tensor_utils.pack_slices_span(
                        table, values, ids, payload
                    )
                )
                continue
            memo = bucket_memo.get(memo_key)
            if memo is not None and np.array_equal(memo[0], ids):
                ids_sorted, order, bounds = memo[1], memo[2], memo[3]
            else:
                shard = ids % self.num_ps
                order = np.lexsort((ids, shard))
                ids_sorted = ids[order]
                bounds = np.searchsorted(
                    shard[order], np.arange(self.num_ps + 1)
                )
                bucket_memo[memo_key] = (ids, ids_sorted, order, bounds)
            values_sorted = values[order]
            for ps_id in range(self.num_ps):
                lo, hi = int(bounds[ps_id]), int(bounds[ps_id + 1])
                if lo == hi:
                    continue
                header, payload = ensure(ps_id)
                header.sparse.append(
                    tensor_utils.pack_slices_span(
                        table, values_sorted[lo:hi], ids_sorted[lo:hi],
                        payload,
                    )
                )
        requests = {}
        for ps_id, header in headers.items():
            payload = payloads[ps_id]
            header.payload_total_bytes = payload.nbytes
            max_bytes = self._max_push_bytes
            if max_bytes <= 0 or payload.nbytes <= max_bytes:
                requests[ps_id] = [
                    tensor_utils.PackedPushRequest(
                        header, payload.parts, payload.nbytes
                    )
                ]
                continue
            n_chunks = -(-payload.nbytes // max_bytes)
            self._push_seq += 1
            push_id = self._push_salt | (self._push_seq & 0xFFFFFF)
            header.push_id = push_id
            header.chunk_count = n_chunks
            reqs = []
            for i in range(n_chunks):
                start = i * max_bytes
                end = min(start + max_bytes, payload.nbytes)
                if i == 0:
                    chunk_header = header  # spans ride the first chunk
                else:
                    chunk_header = pb.PushGradientsPackedRequest(
                        worker_id_plus_one=worker_id_plus_one,
                        push_id=push_id,
                        chunk_index=i,
                        chunk_count=n_chunks,
                        payload_offset=start,
                        payload_total_bytes=payload.nbytes,
                    )
                reqs.append(
                    tensor_utils.PackedPushRequest(
                        chunk_header,
                        payload.slice_parts(start, end),
                        end - start,
                    )
                )
            requests[ps_id] = reqs
        return requests
