"""Operations and bytes the mathematics needs, from shapes alone.

A multiply-add counts as two operations. Nothing here counts recomputed
work: a rematerialised forward, or the score matrix that a flash backward
builds a second time, is the implementation's cost and not the model's.
"""


def decoder_multiplying_params(d_model, n_layers, ffn_mult, vocab):
    """Parameters that take part in a matrix multiplication in a dense
    pre-LN decoder: per block QKV (3 d^2), the output projection (d^2) and
    the two feed-forward matrices (2 * ffn_mult * d^2), plus the LM head
    (d * vocab; a head tied to the embedding table multiplies all the
    same). Embedding look-ups, biases and norms multiply nothing."""
    per_block = (4 + 2 * ffn_mult) * d_model * d_model
    return n_layers * per_block + d_model * vocab


def causal_attention_flops_per_token(d_model, n_layers, seq_len,
                                     backward=True):
    """Attention's score and value products for one token of a causal
    sequence of seq_len: a query sees (seq_len + 1) / 2 keys on average,
    QK^T and PV each cost 2 * d_model operations a key (all heads
    together), and the backward pass needs twice the forward's."""
    keys = (seq_len + 1) / 2.0
    forward = 2 * 2 * d_model * keys
    return n_layers * forward * (3 if backward else 1)


def decoder_train_flops_per_token(d_model, n_layers, ffn_mult, vocab,
                                  seq_len):
    """Forward and backward of a dense decoder, per trained token: six
    operations a multiplying parameter, plus causal attention."""
    dense = 6 * decoder_multiplying_params(
        d_model, n_layers, ffn_mult, vocab)
    return dense + causal_attention_flops_per_token(
        d_model, n_layers, seq_len
    )


# What each kernel of a flash attention pass needs, in products of one
# [S, d] x [d, S] shape over the causal half, and in [B*H, S, d] tensors it
# has to read or write at the least (plus one float32 log-sum-exp a row):
#   forward  QK^T, PV;                     reads q k v, writes o
#   dq       dP = dO V^T, dQ = dS K;       reads q k v o dO, writes dq
#   dkv      dV = P^T dO, dK = dS^T Q;     reads q k v o dO, writes dk dv
# The second QK^T (and, in dkv, the second dP) that a flash backward builds
# is recompute and is not counted.
ATTENTION_KERNELS = {
    "forward": {"products": 2, "tensors": 4},
    "dq": {"products": 2, "tensors": 6},
    "dkv": {"products": 2, "tensors": 7},
}


def causal_attention_kernel_flops(batch_heads, seq_len, head_dim, kernel):
    """Needed operations of one kernel call over [batch_heads, seq_len,
    head_dim], causal half only."""
    half = seq_len * (seq_len + 1) / 2.0
    return (ATTENTION_KERNELS[kernel]["products"] * 2.0 * batch_heads
            * half * head_dim)


def attention_kernel_bytes(batch_heads, seq_len, head_dim, kernel,
                           itemsize):
    """Bytes one kernel call has to move at the least."""
    tensor = batch_heads * seq_len * head_dim * itemsize
    return (ATTENTION_KERNELS[kernel]["tensors"] * tensor
            + batch_heads * seq_len * 4)


def roofline_seconds(flops, nbytes, peak_flops, peak_bytes_per_s):
    """(least seconds, which roof binds)."""
    compute = flops / peak_flops
    memory = nbytes / peak_bytes_per_s
    return (compute, "compute") if compute >= memory else (memory, "memory")
