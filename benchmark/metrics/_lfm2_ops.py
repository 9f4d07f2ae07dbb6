"""The LFM2 cut's own operations in a device trace, told by shapes that
only they have, and the operations a token of it needs.

As `_model_ops.py` (which says why shapes and not scope names: an `XLA
Ops` event carries its HLO line and three timing statistics, nothing
else), with this model's sizes, taken from the cell's configuration:

  short conv  the in-projection's width, 3 x hidden: [batch, seq, 3 d] or
              [tokens, 3 d] (B, C, x side by side: the product that makes
              it, the gates and the convolution that read it, the backward
              that writes its gradient) and the weight [d, 3 d] and its
              gradient. The out-projection's own products ([tokens, d] x
              [d, d], as attention's out-projection has them) carry no
              shape of their own and are counted only where the compiler
              fused them with the gates: the share reads low by them.
  routing     [tokens, routed experts] (scores, top-k) and the
              one-dimensional arrays over the assignments (tokens x experts
              a token, with or without one block of padding: the sort and
              its inverse)
  grouped     the expert weights [held, d, 2 width] (w1 and w3 side by
              side) and [held, width, d]: the `while` loops carry them,
              and a loop's event covers its body's

What reads `opt_state` (the optimizer's update of the same weights) is
left out, as there. A share is of the union of the matching intervals. A
configuration of another model, a program without such operations or a run
without a trace gives None.
"""

from lib import cell, flops, trace


def sizes(run):
    """The cut's sizes, or None for a configuration of another model."""
    m, t = run.config["model"], run.traffic
    try:
        batch, seq = int(t["minibatch"]), int(run.config["record_tokens"])
        held = (m.get("experts_held") or [0, m["num_experts"]])[1]
        return {
            "batch": batch, "seq": seq, "tokens": batch * seq,
            "hidden": int(m["hidden_size"]),
            "heads": int(m["num_attention_heads"]),
            "kv_heads": int(m["num_key_value_heads"]),
            "dense_width": int(m["intermediate_size"]),
            "width": int(m["moe_intermediate_size"]),
            "experts": int(m["num_experts"]),
            "per_token": int(m["num_experts_per_tok"]),
            "assignments": batch * seq * int(m["num_experts_per_tok"]),
            "block": int(m.get("expert_block_rows", 0)),
            "held": int(held), "vocab": int(m["vocab_size"]),
            "layer_types": list(m["layer_types"]),
            "dense_layers": int(m["num_dense_layers"]),
        }
    except KeyError:
        return None


def shortconv_shape(dims, z):
    wide = 3 * z["hidden"]
    return dims in ((z["batch"], z["seq"], wide), (z["tokens"], wide),
                    (z["hidden"], wide), (wide, z["hidden"]))


def routing_shape(dims, z):
    return dims == (z["tokens"], z["experts"]) or dims in (
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


def multiplying_params_per_token(z):
    """Parameters a token is multiplied with, in the cut as run: the
    operators' projections, the dense feed-forward, in each routed layer the
    router and the held experts' share of the token's assignments (held /
    experts x experts a token: 0.5 at 8 of 64 and 4), and the tied head.
    Embedding look-ups, norms, gates and the convolution's taps multiply no
    matrix."""
    d = z["hidden"]
    head_dim = d // z["heads"]
    operators = {
        "conv": 3 * d * d + d * d,
        "full_attention": 2 * d * d + 2 * d * z["kv_heads"] * head_dim,
    }
    held_per_token = z["per_token"] * z["held"] / z["experts"]
    routed = d * z["experts"] + held_per_token * 3 * d * z["width"]
    total = d * z["vocab"]
    for i, kind in enumerate(z["layer_types"]):
        total += operators[kind] + (
            3 * d * z["dense_width"] if i < z["dense_layers"] else routed)
    return total


def train_flops_per_token(z):
    """Forward and backward, per trained token: six operations a
    multiplying parameter, plus causal attention in the attention layers;
    nothing recomputed."""
    attention_layers = z["layer_types"].count("full_attention")
    return 6 * multiplying_params_per_token(z) + \
        flops.causal_attention_flops_per_token(
            z["hidden"], attention_layers, z["seq"])


def window_block_rows(run):
    """(rows that were assignments, rows the grouped loops multiplied) over
    the window's `model_stats` events, or None without the counters."""
    events = run.events_of("model_stats", "worker", since=run.t0,
                           until=run.t1)
    ran = sum(float(e.get("moe_block_rows_run", 0.0)) for e in events)
    if not ran:
        return None
    return sum(float(e.get("moe_block_rows_real", 0.0))
               for e in events), ran
