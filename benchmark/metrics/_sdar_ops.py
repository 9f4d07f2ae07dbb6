"""The SDAR cut's own operations in a device trace, and the operations a
record token of it needs under block-diffusion training.

The flash kernels under the block-diffusion mask carry names of their own
(`bd_flash_fwd`, `bd_flash_bwd`: the HLO instruction is named after the
`pallas_call`, e.g. `%jvp_bd_flash_fwd_.1`), so a call is known by its
instruction's name, and its [batch*heads, rows, head] by its first result.
The routed layers are known by shapes, as `_lfm2_ops.py` knows the LFM2
cut's (and says why shapes): [rows, routed experts] and the
one-dimensional arrays over the assignments for the routing, the expert
weights [held, d, 2 width] and [held, width, d] for the grouped loops; the
rows of a step are twice its record tokens (a clean and a noised copy).
What reads `opt_state` is left out.

The needed work is the mathematics', whatever implements it: a query at
position p sees L + b keys in all over its two rows (its clean row the
blocks up to its own, its noised row the blocks before its own and its own
block), so a batch*head needs L (L + b) scores; nothing recomputed, no
score of a tile's masked part. A configuration of another model, a program
without such operations or a run without a trace gives None.
"""

import re

from lib import cell, flops, peaks, trace

FWD, BWD = "bd_flash_fwd", "bd_flash_bwd"
_RESULT = re.compile(r"(bf16|f16|f32)\[(\d+),(\d+),(\d+)\]")
_ITEMSIZE = {"bf16": 2, "f16": 2, "f32": 4}
# Products of one [rows, d] x [d, keys] shape over the needed scores, and
# [batch*heads, rows, d] tensors read or written at the least (plus one
# float32 log-sum-exp a row): forward QK^T, PV; reads q k v, writes o.
# Backward dP, dQ, dV, dK; reads q k v o dO, writes dq dk dv.
KERNELS = {FWD: {"products": 2, "tensors": 4},
           BWD: {"products": 4, "tensors": 8}}


def sizes(run):
    """The cut's sizes, or None for a configuration of another model."""
    m, t = run.config["model"], run.traffic
    try:
        batch, seq = int(t["minibatch"]), int(run.config["record_tokens"])
        held = (m.get("experts_held") or [0, m["num_experts"]])[1]
        return {
            "batch": batch, "seq": seq, "rows": 2 * batch * seq,
            "block_length": int(m["block_length"]),
            "hidden": int(m["hidden_size"]),
            "heads": int(m["num_attention_heads"]),
            "kv_heads": int(m["num_key_value_heads"]),
            "head_dim": int(m["head_dim"]),
            "width": int(m["moe_intermediate_size"]),
            "experts": int(m["num_experts"]),
            "per_token": int(m["num_experts_per_tok"]),
            "assignments": 2 * batch * seq * int(m["num_experts_per_tok"]),
            "block": int(m.get("expert_block_rows", 0)),
            "held": int(held), "vocab": int(m["vocab_size"]),
            "layers": int(m["num_hidden_layers"]),
        }
    except KeyError:
        return None


# ---------- the masked attention ----------


def classify(name):
    """(kernel, batch_heads, rows, head_dim, itemsize) of a compact event
    name, or None for another operation."""
    p = trace.parse(name)
    if p is None or p[2] != "custom-call":
        return None
    kernel = next((k for k in (FWD, BWD) if k in p[0]), None)
    found = _RESULT.search(p[1])
    if kernel is None or found is None:
        return None
    dtype, bh, rows, d = found.groups()
    return kernel, int(bh), int(rows), int(d), _ITEMSIZE[dtype]


def kernel_events(run):
    """[(kernel, batch_heads, rows, head_dim, itemsize, duration_ns)] over
    all devices, or []."""
    if not run.trace:
        return []
    return [(*classify(name), dur) for name, dur in trace.matching(
        run.trace, lambda n: classify(n) is not None)]


def needed_scores(half, block):
    """Scores one batch*head needs under the block-diffusion mask."""
    return half * (half + block)


def kernel_flops(kernel, batch_heads, rows, head_dim, block):
    """Needed operations of one call over [batch_heads, rows, head_dim],
    rows two halves."""
    return (KERNELS[kernel]["products"] * 2.0 * batch_heads
            * needed_scores(rows // 2, block) * head_dim)


def kernel_bytes(kernel, batch_heads, rows, head_dim, itemsize):
    """Bytes one call has to move at the least."""
    tensor = batch_heads * rows * head_dim * itemsize
    return KERNELS[kernel]["tensors"] * tensor + batch_heads * rows * 4


def least_seconds(run, events):
    """Sum of each call's roofline time on this device, and how the calls
    split between the two roofs."""
    z = sizes(run)
    p = peaks.peaks(run.device["kind"])
    total, roofs = 0.0, {}
    for kernel, bh, rows, d, itemsize, _ in events:
        seconds, roof = flops.roofline_seconds(
            kernel_flops(kernel, bh, rows, d, z["block_length"]),
            kernel_bytes(kernel, bh, rows, d, itemsize),
            p["flops_bf16"], p["hbm_bytes_per_s"])
        total += seconds
        roofs[roof] = roofs.get(roof, 0) + 1
    return total, roofs


def window_scores(run):
    """(scores needed, scores the run tiles held) over the window's
    `model_stats` events, or None without the counters."""
    events = run.events_of("model_stats", "worker", since=run.t0,
                           until=run.t1)
    ran = sum(float(e.get("attn_scores_run", 0.0)) for e in events)
    if not ran:
        return None
    return sum(float(e.get("attn_scores_needed", 0.0))
               for e in events), ran


# ---------- the routed layers ----------


def routing_shape(dims, z):
    return dims == (z["rows"], z["experts"]) or dims in (
        (z["assignments"],), (z["assignments"] + z["block"],))


def grouped_shape(dims, z):
    return dims in ((z["held"], z["hidden"], 2 * z["width"]),
                    (z["held"], z["width"], z["hidden"]))


def share_of_busy_pct(run, tests):
    """Device time of the operations that `tests` take (union of their
    intervals, mean over the devices) as a share of the device's busy
    time in the traced window; None when nothing matches."""
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
    seconds = total / len(run.trace["devices"]) / 1e9
    return 100.0 * seconds / run.trace["busy_s"]


# ---------- the whole step ----------


def multiplying_params_per_row(z):
    """Parameters one of a layer's rows is multiplied with, in the cut as
    run: attention's four projections, the router, and the held experts'
    share of the row's assignments (held / experts x experts a token: 1 at
    16 of 128 and 8)."""
    d, dh = z["hidden"], z["head_dim"]
    attention = 2 * d * z["heads"] * dh + 2 * d * z["kv_heads"] * dh
    held_per_row = z["per_token"] * z["held"] / z["experts"]
    return attention + d * z["experts"] + held_per_row * 3 * d * z["width"]


def train_flops_per_token(z):
    """Forward and backward, per RECORD token: six operations a multiplying
    parameter over the two rows a token has in every layer and the head
    over its noised row alone, plus the masked attention (12 x head_dim a
    needed score: QK^T and PV forward, four products backward; L + b
    scores a token a head); nothing recomputed."""
    products = 6 * (2 * z["layers"] * multiplying_params_per_row(z)
                    + z["hidden"] * z["vocab"])
    attention = (12 * z["layers"] * z["heads"] * z["head_dim"]
                 * (z["seq"] + z["block_length"]))
    return products + attention
