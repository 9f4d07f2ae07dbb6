"""From the profiler's trace (`*.xplane.pb`) to numbers.

`load` turns the trace into plain lists (what the committed recorded trace
under testdata/ holds); `reduce` turns those into per-device busy time,
per-operation self time and idle gaps. Reading needs jax but no backend.

Run `python benchmark/lib/trace.py <file.xplane.pb>` to look at a trace by
hand: planes, lines and the heaviest operations.
"""

import json
import re
import sys

DEVICE_PLANE_PREFIX = "/device:TPU:"
OPS_LINE = "XLA Ops"

_HLO = re.compile(r"^%?(\S+) = (.*?) ([a-z][a-z0-9\-]*)\((.*)$", re.S)
_LAYOUT = re.compile(r"\{[^{}]*\}")
_TARGET = re.compile(r'custom_call_target="([^"]+)"|kind=(k\w+)')


def compact(name):
    """The profiler names a device operation by its whole HLO line. Keep
    what tells operations apart and drop the rest:
    `<op> = <output shapes, no layouts> <opcode>(<operand count>) <custom
    call target or fusion kind>`. Other names pass through."""
    m = _HLO.match(name)
    if not m:
        return name
    op, shapes, opcode, rest = m.groups()
    depth, cut = 1, len(rest)
    for i, ch in enumerate(rest):
        depth += (ch == "(") - (ch == ")")
        if depth == 0:
            cut = i
            break
    operands = rest[:cut].count("%")
    target = _TARGET.search(rest[cut:])
    tail = f" {target.group(1) or target.group(2)}" if target else ""
    shapes = _LAYOUT.sub("", shapes)
    return f"{op} = {shapes} {opcode}({operands}){tail}"


def parse(compact_name):
    """(op, output shapes, opcode, operand count, target) of a compact
    name, or None for a name that is no HLO line."""
    m = re.match(r"^(\S+) = (.*) ([a-z][a-z0-9\-]*)\((\d+)\)(?: (\S+))?$",
                 compact_name)
    if not m:
        return None
    op, shapes, opcode, operands, target = m.groups()
    return op, shapes, opcode, int(operands), target


def family(compact_name):
    """What the same operation of every layer shares: opcode, target and
    output shapes, without the operation's own number."""
    p = parse(compact_name)
    if p is None:
        return compact_name
    _, shapes, opcode, _, target = p
    return f"{opcode}{':' + target if target else ''} -> {shapes}"


def load(path, plane_prefix=DEVICE_PLANE_PREFIX, lines=(OPS_LINE,)):
    """{plane name: {line name: [[name, start_ns, duration_ns]]}} for the
    device planes of one trace file, names compacted."""
    from jax.profiler import ProfileData

    data = ProfileData.from_file(path)
    out = {}
    for plane in data.planes:
        if not plane.name.startswith(plane_prefix):
            continue
        kept = {}
        for line in plane.lines:
            if lines and line.name not in lines:
                continue
            events = [
                [compact(e.name), float(e.start_ns), float(e.duration_ns)]
                for e in line.events
            ]
            events.sort(key=lambda ev: (ev[1], -ev[2]))
            kept[line.name] = events
        if kept:
            out[plane.name] = kept
    return out


def union_ns(intervals):
    """Total length covered by [(start, end)]."""
    total = 0.0
    end = None
    for s, e in sorted(intervals):
        if end is None or s > end:
            total += e - s
            end = e
        elif e > end:
            total += e - end
            end = e
    return total


def self_times(events):
    """[(name, start, self_ns)] for events of ONE line sorted by start:
    an event's own time is its duration minus what events nested in it
    cover (a `while` encloses its body's operations)."""
    out = []
    stack = []  # [name, start, end, child_ns]

    def close(upto):
        while stack and stack[-1][2] <= upto:
            name, start, end, child = stack.pop()
            out.append((name, start, max(0.0, (end - start) - child)))
            if stack:
                stack[-1][3] += end - start

    for name, start, dur in events:
        close(start)
        stack.append([name, start, start + dur, 0.0])
    close(float("inf"))
    return out


def reduce_device(events):
    """One device's operations line -> busy, window, self time by name,
    idle gaps [(start, length)]."""
    if not events:
        return None
    spans = [(s, s + d) for _, s, d in events if d > 0]
    start = min(s for s, _ in spans)
    end = max(e for _, e in spans)
    by_name = {}
    for name, _, own in self_times(events):
        by_name[name] = by_name.get(name, 0.0) + own
    gaps = []
    cursor = None
    for s, e in sorted(spans):
        if cursor is not None and s > cursor:
            gaps.append((cursor, s - cursor))
        cursor = e if cursor is None else max(cursor, e)
    return {
        "busy_ns": union_ns(spans), "start_ns": start, "end_ns": end,
        "by_name_ns": by_name, "gaps_ns": gaps, "events": events,
    }


def reduce(raw, ops_line=OPS_LINE):
    """The whole trace: {devices: {plane: reduce_device(...)}, busy_s and
    window_s averaged over devices, device_ops (self time by operation
    family, top 10) and idle_gaps (top 10): the breakdown. Gaps carry the
    label `unattributed`: the program writes no host spans into the
    profiler's trace yet."""
    devices = {}
    for plane, lines in sorted(raw.items()):
        dev = reduce_device(lines.get(ops_line) or [])
        if dev:
            devices[plane] = dev
    if not devices:
        return None
    n = len(devices)
    totals = {}
    for dev in devices.values():
        for name, ns in dev["by_name_ns"].items():
            key = family(name)
            totals[key] = totals.get(key, 0.0) + ns / n
    gaps = sorted(
        (length for dev in devices.values() for _, length in dev["gaps_ns"]),
        reverse=True,
    )
    return {
        "devices": devices,
        "busy_s": sum(d["busy_ns"] for d in devices.values()) / n / 1e9,
        "window_s": sum(
            d["end_ns"] - d["start_ns"] for d in devices.values()
        ) / n / 1e9,
        "device_ops": [
            [name, ns / 1e9] for name, ns in sorted(
                totals.items(), key=lambda kv: -kv[1])[:10]
        ],
        "idle_gaps": [["unattributed", g / 1e9] for g in gaps[:10]],
    }


def matching(reduced, predicate):
    """Operations whose compact name satisfies predicate, over all
    devices: [(name, duration_ns)] an event. Divide sums by the number of
    devices for a per-device mean."""
    return [
        (name, dur)
        for dev in reduced["devices"].values()
        for name, _, dur in dev["events"] if dur > 0 and predicate(name)
    ]


def exposed_seconds(reduced, predicate):
    """Mean over devices of the time in which a matching operation runs
    and no other operation runs on that device."""
    total = 0.0
    for dev in reduced["devices"].values():
        match = [(s, s + d) for name, s, d in dev["events"]
                 if d > 0 and predicate(name)]
        other = [(s, s + d) for name, s, d in dev["events"]
                 if d > 0 and not predicate(name)]
        both = union_ns(match + other)
        total += both - union_ns(other)
    return total / len(reduced["devices"]) / 1e9


def main(argv):
    from jax.profiler import ProfileData

    data = ProfileData.from_file(argv[1])
    for plane in data.planes:
        print("PLANE", plane.name)
        for line in plane.lines:
            events = list(line.events)
            print(f"  LINE {line.name!r}: {len(events)} events")
    raw = load(argv[1])
    reduced = reduce(raw)
    if reduced is None:
        print("no device operations in this trace")
        return 1
    print(json.dumps(
        {k: reduced[k] for k in
         ("busy_s", "window_s", "device_ops", "idle_gaps")}, indent=1))
    dev = next(iter(reduced["devices"].values()))
    heavy = sorted(dev["by_name_ns"].items(), key=lambda kv: -kv[1])[:40]
    for name, ns in heavy:
        print(f"{ns / 1e6:10.3f} ms  {name}")
    return 0


if __name__ == "__main__":
    sys.exit(main(sys.argv))
