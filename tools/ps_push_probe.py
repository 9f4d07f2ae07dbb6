"""Decompose the PS push phase into its limiters.

A PS-mode DeepFM job spends most of its step in `push_gradients` while
the device step is a fraction of a millisecond. This probe measures
every component of that phase IN ISOLATION, with the exact shapes such
a job pushes (batch 16384 x 39 Criteo fields, wide [V,1] + deep [V,8]
adam tables on 2 shards), so the PS cell can carry a limiter
decomposition (PERF.md, "Where the time goes"):

  1. client prep      - dedup (native radix), per-shard scatter, tobytes
  2. wire bytes       - ids + values + proto overhead, per shard
  3. proto serialize  - PushGradientsRequest.SerializeToString()
  4. loopback TCP     - raw socket throughput at those sizes, reader in a
                        SECOND process (the bench reality: every byte
                        crosses processes that share this host's core)
  5. grpc echo        - the same payload through a real grpc
                        server in a second process (framing + HTTP/2 +
                        python buffer copies, no application work)
  6. proto decode     - FromString + frombuffer back to ndarrays
  7. native apply     - servicer._apply_model_pb on a warm store (adam
                        sparse via native idmap kernels)

Run: `python tools/ps_push_probe.py [--batch 16384]`. Prints one JSON
object; no TPU needed (the probe covers the host/RPC side — the device
step is for a PS cell of the benchmark to measure).
"""

import argparse
import json
import multiprocessing
import os
import socket
import sys
import time

import numpy as np

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))

from elasticdl_tpu.common import hash_utils, tensor_utils  # noqa: E402
from elasticdl_tpu.proto import elasticdl_tpu_pb2 as pb  # noqa: E402

NUM_PS = 2
DEEP_DIM = 8


def _bench_push_arrays(batch, seed=0):
    """The per-step sparse gradient payload the bench's worker produces:
    both tables key off the same [batch, 39] id matrix."""
    from elasticdl_tpu.models.dac_ctr.transform import (
        NUM_FIELDS,
        TOTAL_IDS,
    )

    rng = np.random.default_rng(seed)
    ids = rng.integers(
        0, TOTAL_IDS, size=(batch, NUM_FIELDS)
    ).astype(np.int64).reshape(-1)
    deep_vals = rng.normal(size=(ids.size, DEEP_DIM)).astype(np.float32)
    wide_vals = rng.normal(size=(ids.size, 1)).astype(np.float32)
    dense = {
        f"dense_{i}": rng.normal(size=(16, 16)).astype(np.float32)
        for i in range(6)
    }
    return ids, {"deep": deep_vals, "wide": wide_vals}, dense


def _timeit(fn, repeats=5):
    best = float("inf")
    for _ in range(repeats):
        t0 = time.perf_counter()
        fn()
        best = min(best, time.perf_counter() - t0)
    return best


def build_shard_requests(ids, sparse, dense, batch=16384):
    """Mirror PSClient.push_gradients: dedup, scatter, pb-encode."""
    shard_models = {
        ps: pb.Model(version=1) for ps in range(NUM_PS)
    }
    for name, arr in dense.items():
        ps = hash_utils.string_to_id(name, NUM_PS)
        shard_models[ps].dense_parameters.append(
            tensor_utils.ndarray_to_tensor_pb(arr, name)
        )
    for table, values in sparse.items():
        v, i = tensor_utils.deduplicate_indexed_slices(values, ids)
        for ps, (shard_ids, positions) in hash_utils.scatter_embedding_ids(
            i, NUM_PS
        ).items():
            shard_models[ps].embedding_tables[table].CopyFrom(
                tensor_utils.ndarray_to_indexed_slices_pb(
                    np.ascontiguousarray(v[positions]), shard_ids, table
                )
            )
    return {
        ps: pb.PushGradientsRequest(
            gradients=m, worker_id_plus_one=1, batch_size=batch
        )
        for ps, m in shard_models.items()
    }


# ---------- loopback TCP (reader in a second process) ----------


def _tcp_reader(port_q, nbytes):
    srv = socket.socket()
    srv.setsockopt(socket.SOL_SOCKET, socket.SO_REUSEADDR, 1)
    srv.bind(("127.0.0.1", 0))
    srv.listen(1)
    port_q.put(srv.getsockname()[1])
    conn, _ = srv.accept()
    got = 0
    while got < nbytes:
        chunk = conn.recv(1 << 20)
        if not chunk:
            break
        got += len(chunk)
    conn.send(b"k")
    conn.close()
    srv.close()


def measure_loopback_tcp(nbytes, rounds=3):
    """Send `nbytes` to a reader process and wait for its ack: both ends
    share this host's single core, exactly like worker->PS."""
    payload = b"\x00" * (1 << 20)
    best = float("inf")
    for _ in range(rounds):
        q = multiprocessing.Queue()
        proc = multiprocessing.Process(
            target=_tcp_reader, args=(q, nbytes)
        )
        proc.start()
        port = q.get()
        s = socket.create_connection(("127.0.0.1", port))
        t0 = time.perf_counter()
        sent = 0
        while sent < nbytes:
            s.sendall(payload[: min(len(payload), nbytes - sent)])
            sent += len(payload)
        s.recv(1)
        best = min(best, time.perf_counter() - t0)
        s.close()
        proc.join()
    return best


# ---------- grpc echo (server in a second process) ----------

_ECHO_CHILD = """
import sys, concurrent.futures
sys.path.insert(0, %(repo)r)
import grpc
from elasticdl_tpu.common import rpc
from elasticdl_tpu.proto import elasticdl_tpu_pb2 as pb

class Echo:
    # Touch nothing: transport + framing + proto decode only (grpc
    # decodes the request before handing it over).
    pass

def _handler(res_cls):
    def h(self, request, context):
        return res_cls()
    return h

for m, (_req, res_cls) in rpc.PSERVER_SERVICE.methods.items():
    setattr(Echo, m, _handler(res_cls))

server, port = rpc.serve(Echo(), rpc.PSERVER_SERVICE, port=0)
print(port, flush=True)
server.wait_for_termination()
"""


def measure_grpc_echo(requests, rounds=6):
    """Round-trip the REAL per-shard push payloads through a no-op grpc
    service in a second process: everything the wire costs except the
    optimizer apply."""
    import subprocess

    from elasticdl_tpu.common import rpc

    repo = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
    proc = subprocess.Popen(
        [sys.executable, "-c", _ECHO_CHILD % {"repo": repo}],
        stdout=subprocess.PIPE,
        text=True,
    )
    try:
        port = int(proc.stdout.readline())
        channel = rpc.build_channel(f"127.0.0.1:{port}")
        stub = rpc.Stub(channel, rpc.PSERVER_SERVICE)
        # Warm the channel.
        stub.push_gradients(pb.PushGradientsRequest())
        best = float("inf")
        for _ in range(rounds):
            t0 = time.perf_counter()
            futures = [
                stub.push_gradients.future(req)
                for req in requests.values()
            ]
            for f in futures:
                f.result()
            best = min(best, time.perf_counter() - t0)
        channel.close()
        return best
    finally:
        proc.kill()


# ---------- native apply on a warm store ----------


def measure_apply(requests, optimizer="adam", rounds=3):
    from elasticdl_tpu.ops.optimizers import adam
    from elasticdl_tpu.ps.optimizer import PSOptimizer
    from elasticdl_tpu.ps.parameters import Parameters
    from elasticdl_tpu.ps.servicer import PserverServicer

    per_shard = []
    for ps, req in requests.items():
        params = Parameters()
        model = pb.Model(version=0)
        for t in req.gradients.dense_parameters:
            model.dense_parameters.append(t)
        for table in ("wide", "deep"):
            model.embedding_table_infos.append(
                pb.EmbeddingTableInfo(
                    name=table,
                    dim=1 if table == "wide" else DEEP_DIM,
                    initializer="uniform",
                )
            )
        params.init_from_model_pb(model)
        servicer = PserverServicer(
            params, PSOptimizer(adam(learning_rate=1e-3))
        )
        # Warm rows: first apply pays lazy init; measure the steady state
        # like the bench (its warmup covers every distinct id batch).
        servicer._apply_model_pb(req.gradients)
        best = _timeit(
            lambda: servicer._apply_model_pb(req.gradients), rounds
        )
        per_shard.append(best)
    return per_shard


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--batch", type=int, default=16384)
    args = ap.parse_args()

    ids, sparse, dense = _bench_push_arrays(args.batch)
    out = {"batch": args.batch, "nproc": os.cpu_count()}

    # 1. client prep.
    out["client_prep_s"] = _timeit(
        lambda: build_shard_requests(ids, sparse, dense, args.batch)
    )
    requests = build_shard_requests(ids, sparse, dense, args.batch)

    # 2. wire bytes.
    sizes = {ps: req.ByteSize() for ps, req in requests.items()}
    n_unique = tensor_utils.deduplicate_indexed_slices(
        sparse["wide"], ids
    )[1].size
    out["unique_ids"] = int(n_unique)
    out["wire_bytes_per_shard"] = sizes
    out["wire_bytes_total"] = int(sum(sizes.values()))
    out["payload_breakdown_bytes"] = {
        "ids_int64_x2_tables": int(n_unique * 8 * 2),
        "deep_values_f32": int(n_unique * DEEP_DIM * 4),
        "wide_values_f32": int(n_unique * 4),
        "dense": int(sum(a.nbytes for a in dense.values())),
    }

    # 3. proto serialize.
    payloads = {
        ps: req.SerializeToString() for ps, req in requests.items()
    }
    out["serialize_s"] = _timeit(
        lambda: [req.SerializeToString() for req in requests.values()]
    )

    # 4. loopback TCP at the same volume.
    total = sum(len(p) for p in payloads.values())
    tcp_s = measure_loopback_tcp(total)
    out["loopback_tcp_s"] = tcp_s
    out["loopback_tcp_gbytes_per_s"] = total / tcp_s / 1e9

    # 5. grpc echo of the real payloads (decode included server-side).
    out["grpc_echo_s"] = measure_grpc_echo(requests)

    # 6. decode (FromString + frombuffer) — the server-side unpack.
    def decode():
        for p in payloads.values():
            req = pb.PushGradientsRequest.FromString(p)
            for t in req.gradients.dense_parameters:
                tensor_utils.tensor_pb_to_ndarray(t)
            for name, slices in req.gradients.embedding_tables.items():
                tensor_utils.indexed_slices_pb_to_ndarrays(slices)

    out["decode_s"] = _timeit(decode)

    # 7. native optimizer apply, warm rows, per shard (the two shards run
    # concurrently in the bench but share one core: sum them).
    apply_shards = measure_apply(requests)
    out["apply_per_shard_s"] = apply_shards
    out["apply_total_s"] = sum(apply_shards)

    # Roofline: on one core the phases serialize (GIL or core, either
    # way); grpc_echo already contains serialize+wire+decode once.
    out["floor_sum_s"] = (
        out["client_prep_s"] + out["grpc_echo_s"] + out["apply_total_s"]
    )
    print(json.dumps(out, indent=1))


if __name__ == "__main__":
    main()
