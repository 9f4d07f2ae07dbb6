"""The program's host spans, read from the profiler's own trace, against
the device's idle gaps.

While a jax.profiler session is open the program's `tracing.span()` also
enters a `TraceAnnotation`, so every span lies on its thread's line of the
host plane of the same `.xplane.pb` as the device's operations, on one
clock. This module finds that file from the worker's `profile_written`
event, loads the host lines (`load`), flattens one thread's nested spans
into a timeline of the deepest span at each instant (`flatten`), and
splits every idle gap of `lib.trace.reduce(...)["devices"]` over that
timeline (`attribute`). Host and device stamps of one file are brought
together by the profiler, not by one counter: the device line runs ahead
of the host lines by a millisecond or two, which `clock_skew` bounds from
below (no program starts before the host enqueued it; the two are paired
by `run_id`) and `attribute` takes out.

Which names are the program's spans, which span marks a dispatch and
which a step are the caller's to say: a table kept beside the readers
under metrics/. A program that writes no such event or span (an older
one) gives `None`, never an error.

Run `python benchmark/lib/hostspans.py <file.xplane.pb> <prefix,...>` to
print the spans of a trace by hand.
"""

import bisect
import json
import sys

from lib import measure

HOST_PLANE_PREFIX = "/host:"
MODULES_LINE = "XLA Modules"
UNATTRIBUTED = "unattributed"
# The device line of a v5e trace runs 1.7 ms ahead of the host lines
# (PERF.md section 5); the gaps worth a name are 3 ms and longer.
MAX_SKEW_NS = 5e6


def profile_file(run):
    """The trace file the worker says it wrote (`profile_written`, the
    last one), or None. The run's work directory still stands while the
    readers run."""
    written = run.events_of("profile_written", "worker")
    if not written:
        return None
    files = measure.trace_files(written[-1]["dir"])
    return files[0] if files else None


def load(path, is_span):
    """(host lines, device program runs, host enqueues) of one trace file.

    host lines: [{"plane", "line", "spans": [[name, start_ns, end_ns,
    step_num or None]]}], only events whose name satisfies is_span, only
    lines that hold one, each sorted by start.
    program runs: {device plane: [[run_id, start_ns, end_ns]]} from the
    `XLA Modules` line (one event a program execution), by start.
    host enqueues: {"<device ordinal>:<run_id>": start_ns} of the
    earliest host event that carries that `run_id` and starts a flow (the
    runtime stamps the enqueue of a program with the id of its execution;
    every device counts its own run ids)."""
    from jax.profiler import ProfileData

    from lib import trace

    data = ProfileData.from_file(path)
    lines, runs, enqueued = [], {}, {}
    for plane in data.planes:
        if plane.name.startswith(trace.DEVICE_PLANE_PREFIX):
            for line in plane.lines:
                if line.name != MODULES_LINE:
                    continue
                found = []
                for e in line.events:
                    run_id = dict(e.stats).get("run_id")
                    if run_id is not None:
                        found.append([
                            int(run_id), float(e.start_ns),
                            float(e.start_ns + e.duration_ns)])
                runs[plane.name] = sorted(found, key=lambda r: r[1])
        if not plane.name.startswith(HOST_PLANE_PREFIX):
            continue
        for line in plane.lines:
            spans = []
            for e in line.events:
                if is_span(e.name):
                    step = dict(e.stats).get("step_num")
                    spans.append([
                        e.name, float(e.start_ns),
                        float(e.start_ns + e.duration_ns),
                        None if step is None else int(step)])
                elif not e.name.startswith("$"):  # not a python frame
                    stats = dict(e.stats)
                    run_id = stats.get("run_id")
                    # `_p`: the event that starts the flow to the device
                    # (the enqueue), not one that follows its completion.
                    if run_id is not None and "_p" in stats:
                        key = f"{stats.get('device_ordinal', 0)}:{run_id}"
                        start = float(e.start_ns)
                        if start < enqueued.get(key, float("inf")):
                            enqueued[key] = start
            if spans:
                spans.sort(key=lambda s: (s[1], -s[2]))
                lines.append({"plane": plane.name, "line": line.name,
                              "spans": spans})
    return lines, runs, enqueued


def thread_of(lines, span_name):
    """The line that carries the most `span_name` spans, or None."""
    best, most = None, 0
    for line in lines:
        n = sum(1 for s in line["spans"] if s[0] == span_name)
        if n > most:
            best, most = line, n
    return best


def flatten(spans):
    """Nested spans of ONE thread, sorted by (start, -end) -> segments
    [[start, end, name, step_num]] that do not overlap, each carrying the
    deepest span open at that time and the step number of the nearest
    enclosing span that has one."""
    out = []
    stack = []  # [name, end, step]

    def emit(start, end):
        if stack and end > start:
            name, _, step = stack[-1]
            out.append([start, end, name, step])

    cursor = None
    for name, start, end, step in spans:
        while stack and stack[-1][1] <= start:
            emit(cursor, stack[-1][1])
            cursor = stack.pop()[1]
        if stack:
            emit(cursor, start)
            # A child never outlives its parent on one thread; clock
            # rounding can say so by a nanosecond.
            end = min(end, stack[-1][1])
        if step is None and stack:
            step = stack[-1][2]
        cursor = start
        stack.append([name, end, step])
    while stack:
        emit(cursor, stack[-1][1])
        cursor = stack.pop()[1]
    return out


def split_gap(segments, starts, start, length):
    """One gap over the flattened timeline: ({name: ns}, step of the
    largest part). Time under no segment is UNATTRIBUTED."""
    end = start + length
    parts = {}
    best = (0.0, UNATTRIBUTED, None)
    covered = 0.0
    i = max(0, bisect.bisect_right(starts, start) - 1)
    while i < len(segments) and segments[i][0] < end:
        s, e, name, step = segments[i]
        over = min(e, end) - max(s, start)
        if over > 0:
            covered += over
            parts[name] = parts.get(name, 0.0) + over
            if parts[name] > best[0]:
                best = (parts[name], name, step)
        i += 1
    rest = length - covered
    if rest > 0:
        parts[UNATTRIBUTED] = rest
        if rest > best[0]:
            best = (rest, UNATTRIBUTED, None)
    return parts, best[1], best[2]


def enqueue_of(plane, run_id, enqueued):
    """When the host enqueued execution `run_id` of device `plane`
    (`/device:TPU:<ordinal>`), or None when that was outside the trace."""
    return enqueued.get(f"{plane.rsplit(':', 1)[-1]}:{run_id}")


def clock_skew(runs, enqueued):
    """{device plane: ns} by which the device's timestamps must move
    later so that no program starts on the device before the host
    enqueued it (paired by run_id): a lower bound of how far the device
    line runs ahead of the host lines. 0 where nothing needs moving or
    nothing pairs."""
    out = {}
    for plane, found in runs.items():
        out[plane] = 0.0
        for run_id, start, _ in found:
            enqueue = enqueue_of(plane, run_id, enqueued)
            if enqueue is not None:
                out[plane] = max(out[plane], enqueue - start)
    return out


def clock_fault(main, runs, enqueued, skew, dispatch_span):
    """A sentence saying why host spans and device operations of this
    trace cannot be laid over each other, or None when they can.

    The step's program is the one that runs longest. Its executions
    whose enqueue lies inside the trace pair off, in order, with the
    dispatch spans (one enqueue a dispatch); with the device moved later
    by `skew`, none may start before the span that enqueued it starts.
    A skew beyond MAX_SKEW_NS means the two clocks are not one."""
    dispatches = [s for s in main["spans"] if s[0] == dispatch_span]
    for plane, found in sorted(runs.items()):
        if skew[plane] > MAX_SKEW_NS:
            return (f"{plane}: a program starts {skew[plane] / 1e6:.3f} ms "
                    "before the host enqueued it: not one clock")
        if not found:
            continue
        longest = max(e - s for _, s, e in found)
        steps = [r for r in found if r[2] - r[1] > 0.5 * longest
                 and enqueue_of(plane, r[0], enqueued) is not None]
        # A dispatch at the very end of the trace may not have reached
        # the device before the trace stopped; never the other way round.
        if not 0 <= len(dispatches) - len(steps) <= 1:
            return (f"{plane}: {len(steps)} executions of the step's "
                    f"program were enqueued in the trace but it holds "
                    f"{len(dispatches)} {dispatch_span} spans")
        for (_, start, _), span in zip(steps, dispatches):
            if start + skew[plane] < span[1]:
                return (f"{plane}: a step starts on the device "
                        f"{(span[1] - start - skew[plane]) / 1e6:.3f} ms "
                        f"before its {dispatch_span} span starts")
    return None


def attribute_loaded(devices, lines, runs, enqueued, dispatch_span):
    """`attribute` on what `load` gave: (result, None) or (None, why)."""
    main = thread_of(lines, dispatch_span)
    if main is None:
        return None, f"no {dispatch_span} span in the host plane"
    skew = clock_skew(runs, enqueued)
    for plane in devices:
        skew.setdefault(plane, 0.0)
    fault = clock_fault(main, runs, enqueued, skew, dispatch_span)
    if fault:
        return None, fault
    segments = flatten(main["spans"])
    starts = [s[0] for s in segments]
    gaps = []
    for plane, dev in sorted(devices.items()):
        for start, length in dev["gaps_ns"]:
            parts, label, step = split_gap(
                segments, starts, start + skew[plane], length)
            gaps.append((plane, start, length, label, step, parts))
    others = []
    for line in lines:
        if line is main:
            continue
        counts = {}
        for s in line["spans"]:
            counts[s[0]] = counts.get(s[0], 0) + 1
        others.append({"line": line["line"], "spans": counts})
    return {"gaps": gaps, "other_threads": others,
            "clock_skew_ms": {p: ns / 1e6 for p, ns in skew.items()}}, None


def attribute(run, is_span, dispatch_span):
    """Every idle gap of the run's device trace, split over the spans of
    the thread that dispatches.

    Returns None (and prints why) when the run has no trace, the program
    wrote no `profile_written` event or no dispatch span, or the clock
    check fails. Else {"gaps": [(plane, start_ns, length_ns, label,
    step_num, {name: ns})] in the order of
    run.trace["devices"][plane]["gaps_ns"] (start as the device gave it;
    the split is taken `clock_skew_ms` later), "other_threads": [{"line",
    "spans": {name: count}}] (listed; they label nothing),
    "clock_skew_ms": {plane: ms}}."""
    if not run.trace:
        return None
    path = profile_file(run)
    if path is None:
        found, why = None, "the program wrote no profile_written event"
    else:
        found, why = attribute_loaded(
            run.trace["devices"], *load(path, is_span), dispatch_span)
    if found is None:
        print(json.dumps({"reader": "hostspans", "nothing": why}),
              flush=True)
    return found


def main(argv):
    prefixes = tuple(argv[2].split(",")) if len(argv) > 2 else ("",)
    lines, runs, enqueued = load(
        argv[1], lambda n: n.startswith(prefixes) and not n.startswith("$"))
    for plane, ms in sorted(clock_skew(runs, enqueued).items()):
        print(f"{plane}: {len(runs[plane])} program executions, the "
              f"device line runs at least {ms / 1e6:.3f} ms ahead")
    for line in lines:
        print(f"{line['plane']} / {line['line']!r}: "
              f"{len(line['spans'])} spans")
        for start, end, name, step in flatten(line["spans"])[:200]:
            print(f"  {start / 1e6:12.3f} ms +{(end - start) / 1e6:9.3f} "
                  f"{name} {'' if step is None else step}")
    return 0


if __name__ == "__main__":
    sys.exit(main(sys.argv))
