"""The Mellum 2 cut's own operations in a device trace, and the operations
a token of it needs in training.

The flash kernels carry their names in the HLO instruction (named after
the `pallas_call`: `%jvp_band_flash_fwd_.1`, `%flash_bwd.7`), so a call is
known by its instruction's name: `band_flash_fwd` / `band_flash_bwd` under
the window, `flash_fwd` / `flash_bwd` (with neither `band_` nor `bd_`
before them) in the full layers; its [batch*heads, rows, head] by its first
result. The routed layers are known by shapes, by `_sdar_ops.py`'s own two
tests over this cut's sizes (`_model_ops.py` says why shapes): [rows, routed experts] and
the one-dimensional arrays over the assignments for the routing, the expert
weights [held, d, 2 width] and [held, width, d] for the grouped loops. What
reads `opt_state` is left out.

The needed work is the mathematics', whatever implements it: under a window
of W a row r sees min(r + 1, W) keys, W (W + 1) / 2 + (S - W) W scores a
batch*head; in a full layer the causal half, S (S + 1) / 2; nothing
recomputed, no score of a tile's masked part. A configuration of another
model, a program without such operations or a run without a trace gives
None.
"""

import json
import re

from lib import cell, flops, peaks, trace

BAND_FWD, BAND_BWD = "band_flash_fwd", "band_flash_bwd"
FULL_FWD, FULL_BWD = "flash_fwd", "flash_bwd"
BAND, FULL = "sliding_attention", "full_attention"
_NAME = re.compile(r"(band_|bd_)?(flash_fwd|flash_bwd)")
_RESULT = re.compile(r"(bf16|f16|f32)\[(\d+),(\d+),(\d+)\]")
_ITEMSIZE = {"bf16": 2, "f16": 2, "f32": 4}
# Products of one [rows, d] x [d, keys] shape over the needed scores, and
# [batch*heads, rows, d] tensors read or written at the least (plus one
# float32 log-sum-exp a row): forward QK^T, PV; reads q k v, writes o.
# Backward dP, dQ, dV, dK; reads q k v o dO, writes dq dk dv.
KERNELS = {BAND_FWD: {"products": 2, "tensors": 4},
           BAND_BWD: {"products": 4, "tensors": 8},
           FULL_FWD: {"products": 2, "tensors": 4},
           FULL_BWD: {"products": 4, "tensors": 8}}
BAND_KERNELS, FULL_KERNELS = (BAND_FWD, BAND_BWD), (FULL_FWD, FULL_BWD)


def sizes(run):
    """The cut's sizes, or None for a configuration of another model."""
    m, t = run.config["model"], run.traffic
    try:
        batch, seq = int(t["minibatch"]), int(run.config["record_tokens"])
        held = (m.get("experts_held") or [0, m["num_experts"]])[1]
        kinds = list(m["layer_types"])
        m["rope_parameters"]
        return {
            "batch": batch, "seq": seq, "rows": batch * seq,
            "window": int(m["sliding_window"]),
            "band_layers": kinds.count(BAND),
            "full_layers": kinds.count(FULL), "layers": len(kinds),
            "hidden": int(m["hidden_size"]),
            "heads": int(m["num_attention_heads"]),
            "kv_heads": int(m["num_key_value_heads"]),
            "head_dim": int(m["head_dim"]),
            "width": int(m["moe_intermediate_size"]),
            "experts": int(m["num_experts"]),
            "per_token": int(m["num_experts_per_tok"]),
            "assignments": batch * seq * int(m["num_experts_per_tok"]),
            "block": int(m.get("expert_block_rows", 0)),
            "held": int(held), "vocab": int(m["vocab_size"]),
        }
    except KeyError:
        return None


# ---------- attention, by kind of layer ----------


def classify(name):
    """(kernel, batch_heads, rows, head_dim, itemsize) of a compact event
    name, or None for another operation (a block-diffusion call too)."""
    p = trace.parse(name)
    if p is None or p[2] != "custom-call":
        return None
    named, found = _NAME.search(p[0]), _RESULT.search(p[1])
    if named is None or found is None or named.group(1) == "bd_":
        return None
    dtype, bh, rows, d = found.groups()
    return ((named.group(1) or "") + named.group(2), int(bh), int(rows),
            int(d), _ITEMSIZE[dtype])


def kernel_events(run, kernels):
    """[(kernel, batch_heads, rows, head_dim, itemsize, duration_ns)] of
    the calls of `kernels` over all devices, or []."""
    if not run.trace:
        return []
    return [(*classify(name), dur) for name, dur in trace.matching(
        run.trace,
        lambda n: (classify(n) or (None,))[0] in kernels)]


def band_needed_scores(rows, window):
    """Scores one batch*head needs under a window: row r sees min(r + 1,
    window) keys."""
    w = min(rows, window)
    return w * (w + 1) // 2 + (rows - w) * w


def causal_needed_scores(rows):
    return rows * (rows + 1) // 2


def needed_scores(kernel, rows, window):
    if kernel in BAND_KERNELS:
        return band_needed_scores(rows, window)
    return causal_needed_scores(rows)


def kernel_flops(kernel, batch_heads, rows, head_dim, window):
    """Needed operations of one call over [batch_heads, rows, head_dim]."""
    return (KERNELS[kernel]["products"] * 2.0 * batch_heads
            * needed_scores(kernel, rows, window) * head_dim)


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
            kernel_flops(kernel, bh, rows, d, z["window"]),
            kernel_bytes(kernel, bh, rows, d, itemsize),
            p["flops_bf16"], p["hbm_bytes_per_s"])
        total += seconds
        roofs[roof] = roofs.get(roof, 0) + 1
    return total, roofs


def roofline_pct(run, kernels, reader):
    """The least time for the needed work of the calls of `kernels` (a
    forward and its backward) over the device time they took, all of them.
    A step needs each layer's forward once: forward calls beyond the
    backward's count are rematerialised twins, which add their time and no
    needed work. Prints which roof binds."""
    events = kernel_events(run, kernels) if sizes(run) else None
    if not events:
        return None
    took = sum(e[-1] for e in events) / 1e9
    by_kernel = {}
    for e in events:
        by_kernel.setdefault(e[0], []).append(e)
    fwd, bwd = (by_kernel.get(k, []) for k in kernels)
    needed = bwd + (fwd[:len(bwd)] if bwd else fwd)
    least, roofs = least_seconds(run, needed)
    print(json.dumps({
        "reader": reader, "calls": len(events),
        "calls_needed": len(needed), "binding_roof_by_call": roofs,
        "mean_ms_by_kernel": {
            k: sum(e[-1] for e in v) / len(v) / 1e6
            for k, v in by_kernel.items()},
        "least_s": least, "took_s": took}), flush=True)
    return 100.0 * least / took if took > 0 else None


def time_share_pct(run, kernels):
    """The calls' share of the device's busy time in the trace (a
    rematerialised forward counts: it is time the step spends)."""
    events = kernel_events(run, kernels) if sizes(run) else None
    if not events or not run.trace["busy_s"]:
        return None
    per_device = sum(e[-1] for e in events) / len(run.trace["devices"])
    return 100.0 * per_device / 1e9 / run.trace["busy_s"]


def window_scores(run):
    """(scores needed, scores the run tiles held) under the band over the
    window's `model_stats` events, or None without the counters."""
    events = run.events_of("model_stats", "worker", since=run.t0,
                           until=run.t1)
    ran = sum(float(e.get("band_scores_run", 0.0)) for e in events)
    if not ran:
        return None
    return sum(float(e.get("band_scores_needed", 0.0))
               for e in events), ran


# ---------- the routed layers ----------


# The same layer as the SDAR cut's (`RoutedExperts(score="softmax",
# gated=True)`), told by the same shapes over this cut's sizes.
_sdar_ops = cell.load_module("metrics", "_sdar_ops")
routing_shape, grouped_shape = (
    _sdar_ops.routing_shape, _sdar_ops.grouped_shape)


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
    share of the row's assignments (held / experts x experts a token: 2 at
    16 of 64 and 8)."""
    d, dh = z["hidden"], z["head_dim"]
    attention = 2 * d * z["heads"] * dh + 2 * d * z["kv_heads"] * dh
    held_per_row = z["per_token"] * z["held"] / z["experts"]
    return attention + d * z["experts"] + held_per_row * 3 * d * z["width"]


def train_flops_per_token(z):
    """Forward and backward, per token: six operations a multiplying
    parameter in every layer and the head, plus attention (12 x head_dim a
    needed score: QK^T and PV forward, four products backward), a token's
    mean share of a batch*head's needed scores by kind of layer; nothing
    recomputed."""
    products = 6 * (z["layers"] * multiplying_params_per_row(z)
                    + z["hidden"] * z["vocab"])
    scores_a_token = (
        z["band_layers"] * band_needed_scores(z["seq"], z["window"])
        + z["full_layers"] * causal_needed_scores(z["seq"])) / z["seq"]
    return products + 12 * z["heads"] * z["head_dim"] * scores_a_token
