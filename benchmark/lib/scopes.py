"""The device's busy time by the scope of the program that spent it.

The worker writes, beside each profile, which scope every instruction of
the step it ran belongs to (`step_scopes.json`: one row an instruction with
its `phase`, `kind`, `layer` and `scope`, decided in the program,
`elasticdl_tpu/observability/step_scopes.py`) and says where with a
`step_scopes_written` event. The profile names a device event by that same
instruction (`%fusion.123 = ...`). This file joins the two by the
instruction's name and books every instant of the device's busy time once:
to the innermost instruction running then whose row carries an `op_name`
(a `while` less its body), and to `none` only where nothing named runs,
because the compiler's unnamed prefetches (`slice-start`, `copy-start`)
run beside the compute and a sum of durations would count that time twice.
It holds no scope name of any model: it only sums what the rows say.

`booked(run)` gives seconds by (phase, kind, layer), and prints the table
as the other readers print theirs. It gives None, never a partial number,
without the event or its file, and when under 99% of the busy time is
covered by events that found their row (a map of another executable).
"""

import json
import os

from lib import hostspans, trace

FOUND_SHARE = 0.99


def scopes_file(run):
    """The map the worker says it wrote beside the profile (the last
    `step_scopes_written`), or None."""
    written = run.events_of("step_scopes_written", "worker")
    if not written or not os.path.exists(written[-1]["path"]):
        return None
    return written[-1]["path"]


def instruction(hlo_line):
    """(name, opcode) of the HLO line a device event is named by."""
    p = trace.parse(trace.compact(hlo_line))
    if p is None:
        return hlo_line.split(" = ")[0].lstrip("%"), None
    return p[0].lstrip("%"), p[2]


def read_trace(path):
    """({device plane: [(HLO line, start_ns, end_ns)]} of the `XLA Ops`
    line, {device plane: [(module name, start_ns, end_ns)]} of the `XLA
    Modules` line) of a trace file."""
    from jax.profiler import ProfileData

    ops, modules = {}, {}
    for plane in ProfileData.from_file(path).planes:
        if not plane.name.startswith(trace.DEVICE_PLANE_PREFIX):
            continue
        for line in plane.lines:
            if line.name not in (trace.OPS_LINE, hostspans.MODULES_LINE):
                continue
            found = [
                (e.name, float(e.start_ns),
                 float(e.start_ns + e.duration_ns))
                for e in line.events if e.duration_ns > 0]
            (ops if line.name == trace.OPS_LINE else modules)[
                plane.name] = found
    return ops, modules


def book_device(events, rows, runs=None):
    """One device's `XLA Ops` events [(HLO line, start_ns, end_ns)]
    against the map's rows {name: row}: {"booked": {(phase, kind, layer,
    scope): ns}, "busy": ns, "covered": ns of busy time under an event
    that found its row, "crossing": ns booked to a fusion that crosses a
    boundary, "unfound": {instruction that found no row: ns}, "unscoped":
    {opcode of an instruction whose row has no `op_name`: ns}}. `runs`: the
    [(start, end)] in which the map's own module ran; an event outside
    them finds no row (another program's `fusion.3` is not the step's)."""
    marks = []  # (time, 0 opens / -1 closes, index)
    found = []  # (instruction, row or None, start, opcode)
    for i, (line, start, end) in enumerate(events):
        name, opcode = instruction(line)
        row = rows.get(name)
        if row is not None and (
                (opcode and row["opcode"] != opcode)
                or (runs is not None and not any(
                    a <= start < b for a, b in runs))):
            row = None
        found.append((name, row, start, opcode))
        marks.append((start, 0, i))
        marks.append((end, -1, i))
    marks.sort()
    out = {"booked": {}, "busy": 0.0, "covered": 0.0, "crossing": 0.0,
           "unfound": {}, "unscoped": {}}
    running, last = set(), None
    for when, closes, i in marks:
        if running and when > last:
            span = when - last
            out["busy"] += span
            rowed = [j for j in running if found[j][1] is not None]
            named = [j for j in rowed if found[j][1]["phase"] != "none"]
            key = ("none", "other", None, "")
            if rowed:
                out["covered"] += span
            else:
                name = found[max(running)][0]
                out["unfound"][name] = out["unfound"].get(name, 0.0) + span
            if rowed and not named:
                opcode = found[max(rowed)][3]
                out["unscoped"][opcode] = out["unscoped"].get(
                    opcode, 0.0) + span
            if named:
                # The innermost: of those running, the last to start.
                row = found[max(named, key=lambda j: (found[j][2], j))][1]
                key = (row["phase"], row["kind"], row["layer"],
                       row["scope"])
                if row.get("crosses"):
                    out["crossing"] += span
            out["booked"][key] = out["booked"].get(key, 0.0) + span
        last = when
        if closes:
            running.discard(i)
        else:
            running.add(i)
    return out


def book(ops, modules, scopes):
    """Every device's events against the map `scopes` (the file's
    content): {"seconds": {(phase, kind, layer): s}, "by_scope": {(scope,
    phase): s}, "busy_s", "found_share", "crossing_s" (booked to fusions
    that cross a boundary), "unfound": {instruction: s}, "unscoped":
    {opcode: s} (what ran where nothing named did)}, seconds the mean
    over the devices; None where under `FOUND_SHARE` of the busy time
    found its row."""
    rows = {r["name"]: r for r in scopes["rows"]}
    module = scopes.get("hlo_module") or ""
    seconds, by_scope, unfound, unscoped = {}, {}, {}, {}
    busy = covered = crossing = 0.0
    share = 1.0 / max(1, len(ops)) / 1e9
    for plane, events in ops.items():
        # A trace without the modules' line cannot say when the step ran.
        runs = [(a, b) for name, a, b in modules[plane]
                if name.startswith(module)] if plane in modules else None
        dev = book_device(events, rows, runs)
        busy += dev["busy"] * share
        covered += dev["covered"] * share
        crossing += dev["crossing"] * share
        for (phase, kind, layer, scope), ns in dev["booked"].items():
            key = (phase, kind, layer)
            seconds[key] = seconds.get(key, 0.0) + ns * share
            by_scope[(scope, phase)] = by_scope.get(
                (scope, phase), 0.0) + ns * share
        for name, ns in dev["unfound"].items():
            unfound[name] = unfound.get(name, 0.0) + ns * share
        for opcode, ns in dev["unscoped"].items():
            unscoped[opcode] = unscoped.get(opcode, 0.0) + ns * share
    if not busy or covered < FOUND_SHARE * busy:
        return None
    return {
        "seconds": seconds, "by_scope": by_scope, "busy_s": busy,
        "found_share": covered / busy, "crossing_s": crossing,
        "unfound": unfound, "unscoped": unscoped,
    }


def booked(run):
    """`book` of the run's trace and map, read once a run; None without a
    trace, the event, its file, or enough events that find their row."""
    if not hasattr(run, "_scope_seconds"):
        run._scope_seconds = _booked(run)
    return run._scope_seconds


def _booked(run):
    if not run.trace:
        return None
    path, trace_path = scopes_file(run), hostspans.profile_file(run)
    if path is None or trace_path is None:
        return None
    with open(path) as f:
        scopes = json.load(f)
    result = book(*read_trace(trace_path), scopes)
    say(scopes, result)
    return result


def say(scopes, result):
    """The table: phase by kind, the ten heaviest (scope, phase) pairs,
    what ran where nothing named did (by opcode), and the heaviest
    instructions that found no row."""
    if result is None:
        print(json.dumps({"reader": "scopes", "fn": scopes.get("fn"),
                          "found": "under 99% of the busy time"}),
              flush=True)
        return
    busy = result["busy_s"]

    def pct(s):
        return round(100.0 * s / busy, 3)

    table = {}
    for (phase, kind, _), s in result["seconds"].items():
        table.setdefault(phase, {})
        table[phase][kind] = table[phase].get(kind, 0.0) + s
    print(json.dumps({
        "reader": "scopes", "fn": scopes.get("fn"), "busy_s": busy,
        "found_pct": round(100.0 * result["found_share"], 3),
        "crossing_pct": pct(result["crossing_s"]),
        "phase_by_kind_pct": {
            phase: {k: pct(s) for k, s in sorted(kinds.items())}
            for phase, kinds in sorted(table.items())},
        "heaviest_scopes_pct": [
            [scope, phase, pct(s)] for (scope, phase), s in sorted(
                result["by_scope"].items(), key=lambda kv: -kv[1])[:10]],
        "unscoped_by_opcode_pct": [
            [opcode, pct(s)] for opcode, s in sorted(
                result["unscoped"].items(), key=lambda kv: -kv[1])[:6]],
        "heaviest_unfound_pct": [
            [name, pct(s)] for name, s in sorted(
                result["unfound"].items(), key=lambda kv: -kv[1])[:10]],
    }), flush=True)


def share_pct(run, phases=None, kinds=None):
    """The busy time booked to `phases` (all when None) and `kinds` (all
    when None) as a share of the device's busy time; None without a map
    that fits the trace."""
    result = booked(run)
    if result is None:
        return None
    total = sum(
        s for (phase, kind, _), s in result["seconds"].items()
        if (phases is None or phase in phases)
        and (kinds is None or kind in kinds))
    return 100.0 * total / result["busy_s"]
