"""Native PS serving path: id->row map, bulk lazy init, dedup, wire ids.

Round-4 work: the per-id Python loop in EmbeddingTable.rows_for_ids and the
np.unique dedup were the hot spots of the PS strategy's pull and push
on the host; they now run in native/idmap.cc. These
tests pin the semantics the Python paths had.
"""

import numpy as np
import pytest

from elasticdl_tpu import native
from elasticdl_tpu.common import tensor_utils
from elasticdl_tpu.proto import elasticdl_tpu_pb2 as pb
from elasticdl_tpu.ps.embedding_table import EmbeddingTable

pytestmark = pytest.mark.skipif(
    not native.available(), reason="native kernels unavailable"
)


def _fallback_table(monkeypatch, *args, **kwargs):
    monkeypatch.setattr(native, "lib", lambda: None)
    try:
        return EmbeddingTable(*args, **kwargs)
    finally:
        monkeypatch.undo()


def test_native_map_matches_python_dict_semantics(monkeypatch):
    rng = np.random.default_rng(0)
    ids = rng.integers(0, 5000, 20000).astype(np.int64)
    t_native = EmbeddingTable("a", 4, seed=3)
    t_py = _fallback_table(monkeypatch, "a", 4, seed=3)
    # Same rows, same insertion order, same length — regardless of backend.
    rows_n = t_native.rows_for_ids(ids)
    monkeypatch.setattr(native, "lib", lambda: None)
    rows_p = t_py.rows_for_ids(ids)
    monkeypatch.undo()
    assert np.array_equal(rows_n, rows_p)
    assert len(t_native) == len(t_py)
    assert np.array_equal(t_native.ids, t_py.ids)


def test_native_map_create_missing_false(monkeypatch):
    t = EmbeddingTable("a", 4)
    t.rows_for_ids(np.array([10, 20], dtype=np.int64))
    rows = t.rows_for_ids(
        np.array([20, 99, 10], dtype=np.int64), create_missing=False
    )
    assert rows.tolist() == [1, -1, 0]
    assert len(t) == 2  # the miss did not create a row


def test_bulk_init_bitwise_matches_per_row_native_init():
    # The bulk kernel must reproduce the exact per-row stream the old
    # one-ctypes-call-per-row path produced (same seed schedule, same
    # xorshift64* generator) — checkpoints that re-init unseen ids depend
    # on this being stable.
    import ctypes

    lib = native.lib()
    t = EmbeddingTable("u", 8, initializer="uniform", seed=7)
    t.rows_for_ids(np.arange(1000, dtype=np.int64))
    row = np.empty((1, 8), np.float32)
    for r in (0, 1, 999):
        seed = (7 * 0x9E3779B1 + r + 1) & 0xFFFFFFFFFFFFFFFF
        lib.edl_uniform_init(
            native._f32p(row), 8, ctypes.c_float(-0.05),
            ctypes.c_float(0.05), ctypes.c_uint64(seed),
        )
        assert np.array_equal(t.slab[r], row[0])


def test_native_normal_init_deterministic_and_truncated():
    a = EmbeddingTable("n", 16, initializer="truncated_normal(0,0.1)", seed=3)
    b = EmbeddingTable("n", 16, initializer="truncated_normal(0,0.1)", seed=3)
    ids = np.arange(2000, dtype=np.int64)
    va, vb = a.lookup(ids), b.lookup(ids)
    assert np.array_equal(va, vb)
    assert np.abs(va).max() <= 0.2 + 1e-6  # mean +/- 2*std truncation
    assert 0.07 < va.std() < 0.1
    # Different seed -> different stream.
    c = EmbeddingTable("n", 16, initializer="normal(0,0.1)", seed=4)
    assert not np.array_equal(va, c.lookup(ids))


def test_native_dedup_matches_numpy(monkeypatch):
    rng = np.random.default_rng(1)
    ids = rng.integers(0, 300, 5000).astype(np.int64)
    values = rng.normal(size=(5000, 6)).astype(np.float32)
    got_v, got_i = tensor_utils.deduplicate_indexed_slices(values, ids)
    monkeypatch.setattr(native, "lib", lambda: None)
    want_v, want_i = tensor_utils.deduplicate_indexed_slices(values, ids)
    monkeypatch.undo()
    assert np.array_equal(got_i, want_i)  # sorted unique, like np.unique
    np.testing.assert_allclose(got_v, want_v, atol=1e-4)


def test_indexed_slices_raw_ids_roundtrip_and_legacy_decode():
    values = np.arange(12, dtype=np.float32).reshape(4, 3)
    ids = np.array([5, 1, 5, 9], dtype=np.int64)
    msg = tensor_utils.ndarray_to_indexed_slices_pb(values, ids, "t")
    assert msg.ids_bytes and not msg.ids  # new writers use raw bytes
    v2, i2 = tensor_utils.indexed_slices_pb_to_ndarrays(
        pb.IndexedSlices.FromString(msg.SerializeToString())
    )
    assert np.array_equal(v2, values) and np.array_equal(i2, ids)
    # A message from an old writer (repeated ids) still decodes.
    legacy = pb.IndexedSlices(
        concat_tensors=tensor_utils.ndarray_to_tensor_pb(values, "t"),
        ids=ids.tolist(),
    )
    v3, i3 = tensor_utils.indexed_slices_pb_to_ndarrays(legacy)
    assert np.array_equal(v3, values) and np.array_equal(i3, ids)


def test_export_rows_pages_are_contiguous_slab_slices():
    t = EmbeddingTable("e", 4, initializer="uniform", seed=0)
    ids = np.array([42, 7, 13, 99, 7, 42, 1], dtype=np.int64)
    t.lookup(ids)
    got_ids, got_vals = t.export_rows(1, 3)
    assert got_ids.tolist() == [7, 13, 99]  # insertion order
    assert np.array_equal(got_vals, t.slab[1:4])
    # Past-the-end page is empty, not an error.
    empty_ids, empty_vals = t.export_rows(100, 5)
    assert empty_ids.size == 0 and empty_vals.shape == (0, 4)
