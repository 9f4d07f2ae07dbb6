"""The hybrid model's own operations in a device trace, told by shapes
that only they have.

The program puts the chunked scan, the routing, the grouped expert product
and the shared expert under `jax.named_scope`s, but a scope's name is no
part of an operation's HLO line, and the profiler's reader
(`jax.profiler.ProfileData`) hands out no HLO metadata: an `XLA Ops` event
carries its HLO line as its name and three timing statistics, nothing
else (read from a chip trace, PR 27). So an operation is known by the
shapes in its line, results and operands alike, and the shapes come from
the cell's configuration, not from constants here:

  scan     a tensor still in the chunked layout: five or more dimensions
           that start [batch, chunks, ...], the states [batch * chunks *
           groups, heads a group, head_dim, state], or four or more that
           start [batch, groups, heads a group, ...]
  routing  [tokens, routed experts] (scores, top-k) and the one-dimensional
           arrays over the assignments (tokens * experts a token, with or
           without one block of padding: the sort and its inverse)
  grouped  the expert weights [held, hidden, width] and [held, width,
           hidden]: the `while` loops carry them (forward: two products a
           block; backward: five), and a loop's event covers its body's
  shared   [tokens, shared width] and the shared expert's two matrices

The optimizer's update of those same weights is not the layer's work: an
operation that reads `opt_state` is left out. A fusion that crosses a
boundary counts where one of its shapes puts it. This file re-reads the
trace file (found through the worker's `profile_written` event, as
`lib/hostspans.py` finds it), because `lib/trace.py` keeps results and
operand counts only. A share is of the union of the matching intervals, so
a loop and its body count once. A program without such operations, or
without the event, gives None.
"""

import re

from lib import hostspans, trace

_SHAPE = re.compile(r"\b(?:bf16|f16|f32|s32|u32|pred)\[([0-9,]+)\]")


def _sizes(run):
    m, t = run.config["model"], run.traffic
    batch, seq = int(t["minibatch"]), int(run.config["record_tokens"])
    heads, groups = int(m["mamba_num_heads"]), int(m["n_groups"])
    held = (m.get("experts_held") or [0, m["n_routed_experts"]])[1]
    return {
        "batch": batch, "chunks": seq // int(m["chunk_size"]),
        "groups": groups, "per": heads // groups,
        "head_dim": int(m["mamba_head_dim"]),
        "state": int(m["ssm_state_size"]),
        "tokens": batch * seq, "experts": int(m["n_routed_experts"]),
        "assignments": batch * seq * int(m["num_experts_per_tok"]),
        "block": int(m.get("expert_block_rows", 0)),
        "held": int(held), "hidden": int(m["hidden_size"]),
        "width": int(m["moe_intermediate_size"]),
        "shared": int(m["moe_shared_expert_intermediate_size"]),
    }


def scan_shape(dims, z):
    if len(dims) >= 5 and dims[:2] == (z["batch"], z["chunks"]):
        return True
    if dims == (z["batch"] * z["chunks"] * z["groups"], z["per"],
                z["head_dim"], z["state"]):
        return True
    return len(dims) >= 4 and dims[:3] == (
        z["batch"], z["groups"], z["per"])


def routing_shape(dims, z):
    return dims == (z["tokens"], z["experts"]) or dims in (
        (z["assignments"],), (z["assignments"] + z["block"],))


def grouped_shape(dims, z):
    return dims in ((z["held"], z["hidden"], z["width"]),
                    (z["held"], z["width"], z["hidden"]))


def shared_shape(dims, z):
    return dims in ((z["tokens"], z["shared"]),
                    (z["hidden"], z["shared"]), (z["shared"], z["hidden"]))


def matches(name, tests, z):
    """Does the HLO line `name` hold a shape that one of `tests` takes?"""
    if "opt_state" in name:
        return False
    for found in _SHAPE.findall(name):
        dims = tuple(int(d) for d in found.split(","))
        if any(test(dims, z) for test in tests):
            return True
    return False


def raw_events(run):
    """{device plane: [(HLO line, start_ns, end_ns)]} of the run's trace
    file, read once a run; None without a trace or its file."""
    if not hasattr(run, "_raw_device_events"):
        run._raw_device_events = _raw_events(run)
    return run._raw_device_events


def _raw_events(run):
    if not run.trace:
        return None
    path = hostspans.profile_file(run)
    if path is None:
        return None
    from jax.profiler import ProfileData

    found = {}
    for plane in ProfileData.from_file(path).planes:
        if not plane.name.startswith(trace.DEVICE_PLANE_PREFIX):
            continue
        for line in plane.lines:
            if line.name == trace.OPS_LINE:
                found[plane.name] = [
                    (e.name, float(e.start_ns),
                     float(e.start_ns + e.duration_ns))
                    for e in line.events if e.duration_ns > 0]
    return found or None


def share_of_busy_pct(run, tests):
    """Device time of the operations that `tests` take (union of their
    intervals, mean over the devices) as a share of the device's busy
    time in the traced window; None when nothing matches."""
    events = raw_events(run)
    if not events or not run.trace["busy_s"]:
        return None
    try:
        z = _sizes(run)
    except KeyError:
        return None  # not a configuration of this model
    total = 0.0
    for spans in events.values():
        total += trace.union_ns([
            (start, end) for name, start, end in spans
            if matches(name, tests, z)])
    if not total:
        return None
    seconds = total / len(run.trace["devices"]) / 1e9
    return 100.0 * seconds / run.trace["busy_s"]
