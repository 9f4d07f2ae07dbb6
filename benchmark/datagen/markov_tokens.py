"""Seeded order-1 Markov token sequences, in chunks.

A copy of `elasticdl_tpu/data/gen/synthetic.py:synthetic_lm_tokens`
(each token has `branching` equally likely successors, so a trained LM's
loss floor is log(branching)), generated chunk by chunk so that the plain
reference can make the first sequences without making them all: chunk c of
seed s depends on (s, c) alone, and the successor table on s alone.
"""

import numpy as np

CHUNK = 64


def _successors(seed, vocab, branching):
    rng = np.random.default_rng([int(seed), 0])
    return rng.integers(0, vocab, size=(vocab, branching))


def sequences(first, count, seed, data):
    """Sequences [first, first + count) as int32 [count, seq_len + 1]."""
    vocab, branching = int(data["vocab"]), int(data["branching"])
    seq_len = int(data["seq_len"])
    succ = _successors(seed, vocab, branching)
    out = []
    for chunk in range(first // CHUNK, (first + count - 1) // CHUNK + 1):
        rng = np.random.default_rng([int(seed), 1 + chunk])
        seqs = np.empty((CHUNK, seq_len + 1), np.int32)
        state = rng.integers(0, vocab, CHUNK)
        choices = rng.integers(0, branching, (seq_len + 1, CHUNK))
        for t in range(seq_len + 1):
            seqs[:, t] = state
            state = succ[state, choices[t]]
        out.append(seqs)
    flat = np.concatenate(out)
    skip = first - (first // CHUNK) * CHUNK
    return flat[skip:skip + count]


def batches(first_step, steps, minibatch, seed, data):
    """What the job's feed makes of records in file order: per step
    (features [B, S], labels [B, S])."""
    seqs = sequences(first_step * minibatch, steps * minibatch, seed, data)
    for k in range(steps):
        rows = seqs[k * minibatch:(k + 1) * minibatch]
        yield rows[:, :-1], rows[:, 1:]


def write_records(path, count, seed, data):
    """The record file the job trains on: `count` examples
    {"tokens": int32[seq_len + 1]} in the program's own record format."""
    from elasticdl_tpu.data.example import encode_example
    from elasticdl_tpu.data.recordfile import RecordFileWriter

    with RecordFileWriter(path) as w:
        for first in range(0, count, CHUNK):
            n = min(CHUNK, count - first)
            for seq in sequences(first, n, seed, data):
                w.write(encode_example({"tokens": seq}))
    return {"records": count, "distinct_records": count}
