"""The operation and byte functions against values worked by hand at the
flagship's shapes, and the peaks table."""

import pytest

import bench_helpers as h  # noqa: F401  (puts benchmark/ on the path)
from lib import flops, peaks

D, L, FFN, V, S = 1024, 12, 4, 32768, 4096


def test_multiplying_parameters_of_the_flagship():
    # Per block 3 d^2 (qkv) + d^2 (proj) + 8 d^2 (ffn) = 12 * 1024^2
    # = 12,582,912; 12 blocks = 150,994,944; the head 1024 * 32768 =
    # 33,554,432. The two embedding tables (37.7 M) multiply nothing.
    assert flops.decoder_multiplying_params(D, L, FFN, V) == \
        150_994_944 + 33_554_432 == 184_549_376


def test_attention_flops_per_token_count_the_causal_half():
    # A query sees (4096 + 1) / 2 keys; QK^T and PV cost 2 * 1024 each a
    # key; backward twice the forward: 3 * 4 * 1024 * 2048.5 a layer.
    per_layer = 3 * 4 * 1024 * 2048.5
    assert flops.causal_attention_flops_per_token(D, L, S) == \
        pytest.approx(12 * per_layer)
    assert 12 * per_layer == pytest.approx(0.302e9, rel=0.01)
    assert flops.causal_attention_flops_per_token(
        D, L, S, backward=False) == pytest.approx(12 * per_layer / 3)


def test_train_flops_per_token_is_1p41_gflop():
    got = flops.decoder_train_flops_per_token(D, L, FFN, V, S)
    assert got == pytest.approx(6 * 184_549_376 + 12 * 3 * 4 * 1024 * 2048.5)
    assert got == pytest.approx(1.409e9, rel=0.002)


@pytest.mark.parametrize("kernel,tensors", [
    ("forward", 4), ("dq", 6), ("dkv", 7)])
def test_attention_kernel_flops_and_bytes_at_batch_4(kernel, tensors):
    # [4 x 8, 4096, 128]: the causal half holds 4096 * 4097 / 2 = 8,390,656
    # query-key pairs a head; one product costs 2 * 128 a pair; every
    # kernel needs two products (the recomputed ones are not counted).
    pairs = 4096 * 4097 // 2
    assert pairs == 8_390_656
    want = 2 * 2 * 128 * pairs * 32
    assert flops.causal_attention_kernel_flops(32, S, 128, kernel) == want
    assert want == pytest.approx(1.3747e11, rel=1e-4)
    # The full S^2 count would be 2 * 4096 / 4097 of it, about twice.
    assert (2 * 2 * 128 * S * S * 32) / want == pytest.approx(2.0, rel=1e-3)
    tensor = 32 * 4096 * 128 * 4  # float32
    lse = 32 * 4096 * 4
    assert flops.attention_kernel_bytes(32, S, 128, kernel, 4) == \
        tensors * tensor + lse


def test_the_compute_roof_binds_the_forward_kernel():
    p = peaks.peaks("TPU v5 lite")
    nbytes = flops.attention_kernel_bytes(32, S, 128, "forward", 4)
    assert nbytes == 268_959_744
    seconds, roof = flops.roofline_seconds(
        flops.causal_attention_kernel_flops(32, S, 128, "forward"),
        nbytes, p["flops_bf16"], p["hbm_bytes_per_s"])
    # 1.375e11 operations / 197e12 = 0.698 ms against 0.328 ms of bytes.
    assert roof == "compute" and seconds == pytest.approx(6.98e-4, rel=0.01)
    assert flops.roofline_seconds(1e9, 1e9, 197e12, 819e9)[1] == "memory"


def test_peaks_table_refuses_an_unknown_device():
    p = peaks.peaks("TPU v5 lite")
    assert p["flops_bf16"] == 197e12 and p["hbm_bytes_per_s"] == 819e9
    with pytest.raises(KeyError, match="no peaks for device kind"):
        peaks.peaks("TPU v9 imaginary")
