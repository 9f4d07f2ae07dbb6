"""Seeded records for masked block-diffusion training: a token sequence
and the noise it is trained under, in chunks.

A record is {"tokens": int32[L], "t": float32[L / b], "u": float32[L]}:
the tokens are `markov_tokens.py`'s order-1 Markov sequences (each token
has `branching` equally likely successors) over the ids below
`mask_token_id`; `t` is the noise level of each block of `block_length`
positions, uniform on [t_low, t_high]; `u` is one uniform draw a position.
Position p is masked where u[p] < t[p // block_length]: the noise is
drawn when the records are written, so it is data, and the program and the
plain reference read the same bits. Chunk c of seed s depends on (s, c)
alone.
"""

import os
import sys

import numpy as np

BENCH_DIR = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
if BENCH_DIR not in sys.path:
    sys.path.insert(0, BENCH_DIR)

from lib import cell  # noqa: E402

CHUNK = 64


def _tokens(first, count, seed, data):
    """int32 [count, seq_len] from the Markov generator (which makes one
    token more than it is asked for: a next-token target)."""
    markov = cell.load_module("datagen", "markov_tokens")
    assert markov.CHUNK == CHUNK
    return markov.sequences(first, count, seed, {
        "vocab": int(data["vocab"]), "branching": int(data["branching"]),
        "seq_len": int(data["seq_len"]) - 1})


def noise_draws(first, count, seed, data):
    """(t float32 [count, L / b], u float32 [count, L]) of records [first,
    first + count)."""
    seq_len, block = int(data["seq_len"]), int(data["block_length"])
    low, high = float(data["t_low"]), float(data["t_high"])
    ts, us = [], []
    for chunk in range(first // CHUNK, (first + count - 1) // CHUNK + 1):
        rng = np.random.default_rng([int(seed), chunk, 1])
        ts.append(rng.uniform(
            low, high, (CHUNK, seq_len // block)).astype(np.float32))
        us.append(rng.random((CHUNK, seq_len), np.float32))
    skip = first - (first // CHUNK) * CHUNK
    return (np.concatenate(ts)[skip:skip + count],
            np.concatenate(us)[skip:skip + count])


def batches(first_step, steps, minibatch, seed, data):
    """What the job's feed is handed in file order, before it noises: per
    step (tokens [B, L], t [B, L / b], u [B, L])."""
    first, count = first_step * minibatch, steps * minibatch
    tokens = _tokens(first, count, seed, data)
    t, u = noise_draws(first, count, seed, data)
    for k in range(steps):
        rows = slice(k * minibatch, (k + 1) * minibatch)
        yield tokens[rows], t[rows], u[rows]


def write_records(path, count, seed, data):
    """The record file the job trains on, in the program's own record
    format."""
    from elasticdl_tpu.data.example import encode_example
    from elasticdl_tpu.data.recordfile import RecordFileWriter

    with RecordFileWriter(path) as w:
        for first in range(0, count, CHUNK):
            n = min(CHUNK, count - first)
            tokens = _tokens(first, n, seed, data)
            t, u = noise_draws(first, n, seed, data)
            for row in range(n):
                w.write(encode_example(
                    {"tokens": tokens[row], "t": t[row], "u": u[row]}))
    return {"records": count, "distinct_records": count}
