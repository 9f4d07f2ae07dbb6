"""The Kanana 2 cut's own operations in a device trace, and the operations
a token of it needs in training.

The latent attention's flash kernels carry names of their own
(`mla_flash_fwd`, `mla_flash_bwd`: the HLO instruction is named after the
`pallas_call`, e.g. `%jvp_mla_flash_fwd_.1`), so a call is known by its
instruction's name, its [batch*heads, rows, value width] by its first
result (the forward's output, the backward's dq: then the key width), and
the other width from the configuration. `lib/flops.py` takes one head
width; these counts take two: keys of nope + rope (192) against values of
v_head_dim (128).

The latent attention's projections and the routed layers are known by
shapes (`_model_ops.py` says why shapes, and re-reads the trace), all from
the cell's configuration:

  projections  a tensor whose last two dimensions are [heads, w] or whose
               last three are [heads, rows, w], w one of the head's widths
               (nope + rope, nope + v, nope, v, rope: the query, the
               latent's up-projection, their parts, the rope turn and the
               change of layout before and after the kernels); the one
               rope key [.., 1, rope]; the latent [.., rank + rope] and
               [.., rank] with its norm; the up-projection's weight [rank,
               heads, nope + v]; the output projection's input [.., heads
               x v] and weight [heads x v, hidden]. The kernels' own calls
               are not counted here.
  routing      [rows, routed experts] and the one-dimensional arrays over
               the assignments (`_sdar_ops.routing_shape`)
  grouped      the expert weights [held, hidden, 2 width] and [held, width,
               hidden] (`_sdar_ops.grouped_shape`)
  shared       [rows, 2 x shared width], [rows, shared width] and the
               shared experts' two matrices

What reads `opt_state` is left out. The needed work is the mathematics',
whatever implements it: the causal half of the scores, S (S + 1) / 2 a
batch*head (not the run tiles' S (S + tile) / 2: the masked half of a
crossed tile is lost work, as the accepted rooflines count it), nothing
recomputed. A configuration of another model, a program without such
operations or a run without a trace gives None.
"""

import json
import re

from lib import cell, flops, peaks, trace

FWD, BWD = "mla_flash_fwd", "mla_flash_bwd"
KERNELS = (FWD, BWD)
_RESULT = re.compile(r"(bf16|f16|f32)\[(\d+),(\d+),(\d+)\]")
_ITEMSIZE = {"bf16": 2, "f16": 2, "f32": 4}


def sizes(run):
    """The cut's sizes, or None for a configuration of another model."""
    m, t = run.config["model"], run.traffic
    try:
        batch, seq = int(t["minibatch"]), int(run.config["record_tokens"])
        held = (m.get("experts_held") or [0, m["n_routed_experts"]])[1]
        layers = int(m["num_hidden_layers"])
        dense = int(m["first_k_dense_replace"])
        width = int(m["moe_intermediate_size"])
        return {
            "batch": batch, "seq": seq, "rows": batch * seq,
            "layers": layers, "dense_layers": dense,
            "routed_layers": layers - dense,
            "hidden": int(m["hidden_size"]),
            "heads": int(m["num_attention_heads"]),
            "nope": int(m["qk_nope_head_dim"]),
            "rope": int(m["qk_rope_head_dim"]),
            "dk": int(m["qk_nope_head_dim"]) + int(m["qk_rope_head_dim"]),
            "dv": int(m["v_head_dim"]), "rank": int(m["kv_lora_rank"]),
            "dense_width": int(m["intermediate_size"]), "width": width,
            "shared": int(m["n_shared_experts"]) * width,
            "experts": int(m["n_routed_experts"]),
            "per_token": int(m["num_experts_per_tok"]),
            "assignments": batch * seq * int(m["num_experts_per_tok"]),
            "block": int(m.get("expert_block_rows", 0)),
            "held": int(held), "vocab": int(m["vocab_size"]),
        }
    except KeyError:
        return None


# ---------- the latent attention's kernels ----------


def classify(name):
    """(kernel, batch_heads, rows, itemsize) of a compact event name, or
    None for another operation."""
    p = trace.parse(name)
    if p is None or p[2] != "custom-call":
        return None
    kernel = next((k for k in KERNELS if k in p[0]), None)
    found = _RESULT.search(p[1])
    if kernel is None or found is None:
        return None
    dtype, bh, rows, _ = found.groups()
    return kernel, int(bh), int(rows), _ITEMSIZE[dtype]


def kernel_events(run):
    """[(kernel, batch_heads, rows, itemsize, duration_ns)] of the latent
    attention's calls over all devices, or []."""
    if not run.trace or sizes(run) is None:
        return []
    return [(*classify(name), dur) for name, dur in trace.matching(
        run.trace, lambda n: classify(n) is not None)]


def causal_needed_scores(rows):
    return rows * (rows + 1) // 2


def kernel_flops(kernel, batch_heads, rows, dk, dv):
    """Needed operations of one call: forward QK^T over the key width and
    PV over the value width; backward dP and dV over the value width, dQ
    and dK over the key width (the second QK^T it builds is recompute)."""
    passes = 1 if kernel == FWD else 2
    return (passes * 2.0 * batch_heads * causal_needed_scores(rows)
            * (dk + dv))


def kernel_bytes(kernel, batch_heads, rows, dk, dv, itemsize):
    """Bytes one call of the chosen form moves at the least: q and k as
    one [rows, dk] operand each (the rope key copied to every head), v and
    the output at dv, one float32 log-sum-exp a row; the backward reads q,
    k, v, dO, the log-sum-exp and delta and writes dq, dk, dv."""
    row = batch_heads * rows
    if kernel == FWD:
        return row * ((2 * dk + 2 * dv) * itemsize + 4)
    return row * ((4 * dk + 3 * dv) * itemsize + 8)


def roofline_pct(run, reader):
    """The least time for the needed work of the calls (a forward and its
    backward a layer) over the device time they took, all of them. A step
    needs each layer's forward once: forward calls beyond the backward's
    count are rematerialised twins, which add their time and no needed
    work. Prints which roof binds."""
    events = kernel_events(run)
    if not events:
        return None
    z = sizes(run)
    p = peaks.peaks(run.device["kind"])
    took = sum(e[-1] for e in events) / 1e9
    fwd = [e for e in events if e[0] == FWD]
    bwd = [e for e in events if e[0] == BWD]
    needed = bwd + (fwd[:len(bwd)] if bwd else fwd)
    least, roofs = 0.0, {}
    for kernel, bh, rows, itemsize, _ in needed:
        seconds, roof = flops.roofline_seconds(
            kernel_flops(kernel, bh, rows, z["dk"], z["dv"]),
            kernel_bytes(kernel, bh, rows, z["dk"], z["dv"], itemsize),
            p["flops_bf16"], p["hbm_bytes_per_s"])
        least += seconds
        roofs[roof] = roofs.get(roof, 0) + 1
    print(json.dumps({
        "reader": reader, "calls": len(events),
        "calls_needed": len(needed), "binding_roof_by_call": roofs,
        "mean_ms_by_kernel": {
            k: sum(e[-1] for e in v) / len(v) / 1e6
            for k, v in ((FWD, fwd), (BWD, bwd)) if v},
        "least_s": least, "took_s": took}), flush=True)
    return 100.0 * least / took if took > 0 else None


def time_share_pct(run):
    """The calls' share of the device's busy time in the trace (a
    rematerialised forward counts: it is time the step spends)."""
    events = kernel_events(run)
    if not events or not run.trace["busy_s"]:
        return None
    per_device = sum(e[-1] for e in events) / len(run.trace["devices"])
    return 100.0 * per_device / 1e9 / run.trace["busy_s"]


# ---------- by shapes: the projections, the routed layers ----------


def projection_shape(dims, z):
    heads, widths = z["heads"], {
        z["dk"], z["nope"] + z["dv"], z["nope"], z["dv"], z["rope"]}
    if len(dims) < 2:
        return False
    if dims[-1] in widths and (
            dims[-2] == heads
            or (len(dims) >= 3 and dims[-3:-1] == (heads, z["seq"]))):
        return True
    if dims[-2:] == (1, z["rope"]):
        return True
    if dims[-1] in (z["rank"] + z["rope"], z["rank"], heads * z["dv"]):
        return True
    return dims in ((z["rank"], heads, z["nope"] + z["dv"]),
                    (heads * z["dv"], z["hidden"]))


_sdar_ops = cell.load_module("metrics", "_sdar_ops")
routing_shape, grouped_shape = (
    _sdar_ops.routing_shape, _sdar_ops.grouped_shape)


def shared_shape(dims, z):
    return dims in ((z["rows"], 2 * z["shared"]), (z["rows"], z["shared"]),
                    (z["hidden"], 2 * z["shared"]),
                    (z["shared"], z["hidden"]))


def is_flash_call(name):
    """An HLO line of a flash kernel's call, whatever its mask and widths:
    its operands have the projections' shapes and are not their work."""
    return "flash_" in name.split(" = ")[0]


def share_of_busy_pct(run, tests, but_kernels=False):
    """Device time of the operations that `tests` take (union of their
    intervals, mean over the devices) as a share of the device's busy
    time in the traced window; `but_kernels` leaves the latent attention's
    own calls out; None when nothing matches."""
    ops = cell.load_module("metrics", "_model_ops")
    z = sizes(run)
    events = ops.raw_events(run) if z else None
    if not events or not run.trace["busy_s"]:
        return None

    def taken(name):
        if but_kernels and is_flash_call(name):
            return False
        return ops.matches(name, tests, z)

    total = sum(
        trace.union_ns([(start, end) for name, start, end in spans
                        if taken(name)])
        for spans in events.values())
    if not total:
        return None
    seconds = total / len(run.trace["devices"]) / 1e9
    return 100.0 * seconds / run.trace["busy_s"]


# ---------- the whole step ----------


def attention_params(z):
    """Parameters a row is multiplied with in one latent attention: the
    query, the latent's down- and up-projection, the output projection."""
    d, heads = z["hidden"], z["heads"]
    return (d * heads * z["dk"] + d * (z["rank"] + z["rope"])
            + z["rank"] * heads * (z["nope"] + z["dv"])
            + heads * z["dv"] * d)


def multiplying_params_per_row(z):
    """Parameters one row is multiplied with over all the layers, in the
    cut as run: attention in every layer; the dense layers' MLP; in a
    routed layer the router, the shared experts and the held experts'
    share of the row's assignments (held / experts x experts a token: 0.75
    at 16 of 128 and 6)."""
    d = z["hidden"]
    held_per_row = z["per_token"] * z["held"] / z["experts"]
    routed = (d * z["experts"] + 3 * d * z["shared"]
              + held_per_row * 3 * d * z["width"])
    return (z["layers"] * attention_params(z)
            + z["dense_layers"] * 3 * d * z["dense_width"]
            + z["routed_layers"] * routed)


def train_flops_per_token(z):
    """Forward and backward, per token: six operations a multiplying
    parameter in every layer and the head, plus attention (6 x (dk + dv) a
    needed score and head: QK^T and PV forward, four products backward),
    a token's mean share of a batch*head's causal half; nothing
    recomputed."""
    products = 6 * (multiplying_params_per_row(z)
                    + z["hidden"] * z["vocab"])
    scores_a_token = (
        z["layers"] * causal_needed_scores(z["seq"]) / z["seq"])
    return products + 6 * z["heads"] * (z["dk"] + z["dv"]) * scores_a_token
