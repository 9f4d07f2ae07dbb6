"""The granite-4.0-h-micro cut's own operations in a device trace, told by
shapes that only they have, and the operations and bytes its scan and its
whole step need.

As `_model_ops.py` (which says why shapes and not scope names: an `XLA Ops`
event carries its HLO line and three timing statistics, nothing else), with
this model's sizes, taken from the cell's configuration. At batch 1 the
compiler drops the chunked layout's unit axes in places (read from the
step compiled for a described v5e, PR 46), so the scan is told by both:

  scan    a tensor still in the chunked layout, [batch, chunks, ...] of
          rank 5 or more, or with its unit axes dropped: [chunks, chunk,
          ...] (x, B, C, the decay mask and C B^T of a chunk), [chunks, .,
          chunk] and [.., chunks, chunk] (the same, transposed), the
          states [chunks, heads, head_dim, state] and [batch, groups,
          heads a group, ...]
  mixer   the scan, and what carries the in-projection's width (z, xBC
          and dt side by side: [.., 8512]), the convolution's ([.., 4352])
          or the inner width ([.., 4096]: y, z, the gated norm, the
          out-projection's operand and the weight [4096, hidden]) or x by
          heads ([.., 64, 64]): the
          products, their weight gradients and what the compiler fused to
          them

What reads `opt_state` (the optimizer's update of the same weights) is left
out, as there. A share is of the union of the matching intervals. A
configuration of another model, a program without such operations or a run
without a trace gives None.

The scan's needed work is the chunked form's at the published chunk,
whatever implements it: a token of a scanning layer needs, forward, the
lower triangle of C B^T and of the masked [Q, Q] product and the states'
two products (B^T x into the chunk's state, C times the entering state);
backward twice that; x, dt, B and C read once and y written once forward,
and they, dy and the five gradients backward. Nothing recomputed, no score
above the diagonal, the mask never written.
"""

from lib import cell, flops, peaks, trace


def sizes(run):
    """The cut's sizes, or None for a configuration of another model."""
    m, t = run.config["model"], run.traffic
    try:
        batch, seq = int(t["minibatch"]), int(run.config["record_tokens"])
        heads, groups = int(m["mamba_n_heads"]), int(m["mamba_n_groups"])
        chunk = int(m["mamba_chunk_size"])
        inner = heads * int(m["mamba_d_head"])
        conv = inner + 2 * groups * int(m["mamba_d_state"])
        return {
            "batch": batch, "seq": seq,
            "chunk": chunk, "chunks": seq // chunk,
            "heads": heads, "groups": groups, "per": heads // groups,
            "head_dim": int(m["mamba_d_head"]),
            "state": int(m["mamba_d_state"]),
            "inner": inner, "conv": conv, "in_proj": inner + conv + heads,
            "hidden": int(m["hidden_size"]),
            "mlp": int(m["shared_intermediate_size"]),
            "attention_heads": int(m["num_attention_heads"]),
            "kv_heads": int(m["num_key_value_heads"]),
            "vocab": int(m["vocab_size"]),
            "layer_types": list(m["layer_types"]),
            "scanning_layers": sum(
                kind == "mamba" for kind in m["layer_types"]),
        }
    except KeyError:
        return None


# ---------- which operation is whose ----------


def scan_shape(dims, z):
    b, c, q = z["batch"], z["chunks"], z["chunk"]
    if len(dims) >= 5 and dims[:2] == (b, c):
        return True
    if len(dims) >= 3 and (dims[:2] == (c, q) or dims[-2:] == (c, q)
                           or (dims[0], dims[-1]) == (c, q)):
        return True
    states = (c, z["heads"], z["head_dim"], z["state"])
    if sorted(dims) == sorted(states) or dims == (
            b * c * z["groups"], z["per"], z["head_dim"], z["state"]):
        return True
    return len(dims) >= 4 and dims[:3] == (b, z["groups"], z["per"])


def mixer_shape(dims, z):
    if scan_shape(dims, z):
        return True
    if len(dims) >= 2 and dims[-1] in (z["in_proj"], z["conv"], z["inner"]):
        return True
    if len(dims) >= 3 and dims[-2:] == (z["heads"], z["head_dim"]):
        return True
    return dims == (z["inner"], z["hidden"])


def share_of_busy_s(run, tests):
    """(seconds a device spent in the operations that `tests` take: the
    union of their intervals, mean over the devices; the device's busy
    seconds in the traced window), or None when nothing matches."""
    ops = cell.load_module("metrics", "_model_ops")
    z = sizes(run)
    events = ops.raw_events(run) if z else None
    if not events or not run.trace["busy_s"]:
        return None
    total = sum(
        trace.union_ns([(start, end) for name, start, end in spans
                        if ops.matches(name, tests, z)])
        for spans in events.values())
    if not total:
        return None
    return total / len(run.trace["devices"]) / 1e9, run.trace["busy_s"]


def share_of_busy_pct(run, tests):
    found = share_of_busy_s(run, tests)
    return None if found is None else 100.0 * found[0] / found[1]


# ---------- the scan's needed work ----------

# Backward over forward: each product has two gradient products; x, dt, B
# and C are read again with dy, and their gradients written.
BACKWARD_FLOPS, BACKWARD_BYTES = 2.0, 2.0


def scan_flops_per_token(z):
    """Operations one token of one scanning layer needs, forward: a
    multiply-add is two. A token's row of the [Q, Q] lower triangle holds
    (Q + 1) / 2 entries on average."""
    row = (z["chunk"] + 1) / 2.0
    heads_width = z["heads"] * z["head_dim"]
    return 2.0 * (
        z["groups"] * z["state"] * row            # C B^T
        + heads_width * row                       # (mask * C B^T) x
        + 2 * heads_width * z["state"])           # the states in and out


def scan_bytes_per_token(z, itemsize=2):
    """Bytes one token of one scanning layer has to move, forward: x, B, C
    read and y written in the activation dtype, dt in float32."""
    heads_width = z["heads"] * z["head_dim"]
    return (2 * heads_width * itemsize + z["heads"] * 4
            + 2 * z["groups"] * z["state"] * itemsize)


def scan_least_seconds_per_token(z, device_kind):
    """The least seconds one token of one scanning layer takes, forward
    plus backward, and which roof binds each pass."""
    p = peaks.peaks(device_kind)
    total, roofs = 0.0, {}
    for name, f, b in (("forward", 1.0, 1.0),
                       ("backward", BACKWARD_FLOPS, BACKWARD_BYTES)):
        seconds, roof = flops.roofline_seconds(
            f * scan_flops_per_token(z), b * scan_bytes_per_token(z),
            p["flops_bf16"], p["hbm_bytes_per_s"])
        total += seconds
        roofs[name] = roof
    return total, roofs


def scan_tokens_per_step(run):
    """Tokens x scanning layers of a step, from the window's `model_stats`
    events (`ssd_scan_tokens`); None without the counter."""
    events = [e for e in run.events_of(
        "model_stats", "worker", since=run.t0, until=run.t1)
        if "ssd_scan_tokens" in e]
    if not events:
        return None
    return sum(float(e["ssd_scan_tokens"]) for e in events) / len(events)


# ---------- the whole step ----------


def multiplying_params(z):
    """Parameters a token is multiplied with in the cut as run: a mixer's
    two projections or attention's four, the MLP's two matrices, the tied
    head (the embedding's look-up multiplies nothing)."""
    d = z["hidden"]
    mixer = d * z["in_proj"] + z["inner"] * d
    dh = d // z["attention_heads"]
    attention = 2 * d * d + 2 * d * z["kv_heads"] * dh
    mlp = d * 2 * z["mlp"] + z["mlp"] * d
    scans = z["scanning_layers"]
    others = len(z["layer_types"]) - scans
    return (scans * (mixer + mlp) + others * (attention + mlp)
            + d * z["vocab"])


def train_flops_per_token(z):
    """Forward and backward, per token: six operations a multiplying
    parameter, the scan's products in every scanning layer (forward and
    twice that backward) and causal attention in the others; nothing
    recomputed."""
    scans = z["scanning_layers"]
    others = len(z["layer_types"]) - scans
    return (6.0 * multiplying_params(z)
            + scans * (1 + BACKWARD_FLOPS) * scan_flops_per_token(z)
            + flops.causal_attention_flops_per_token(
                z["hidden"], others, z["seq"]))
