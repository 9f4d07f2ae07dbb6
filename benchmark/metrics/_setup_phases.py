"""The set-up path of a run as the program's own `setup_phase` events
tell it: one event a closed `setup.*` span (`name`, `start`, `seconds`,
`role`; `ts` is when it closed), on the host clock every process of a job
and the harness share. Readers take the master's phases and those of the
first worker's first life, before the window opened. A child phase lies
inside its parent in time, so sums are taken over the union. A program
that writes no such event (every reader here then returns None) is one
from before these spans."""

import json


def phases(run):
    """[(name, start, end, role)] of the master and the first worker,
    begun before the window opened, in order of their start."""
    events = run.events_of("setup_phase", until=run.t0)
    workers = [e for e in events if str(e.get("role", "")).startswith(
        "worker")]
    first = workers[0]["role"] if workers else None
    out = []
    for e in events:
        role = e.get("role", "")
        if role != "master" and role != first:
            continue
        start = float(e["start"])
        if start >= run.t0:
            continue
        out.append((e["name"], start, start + float(e["seconds"]), role))
    return sorted(out, key=lambda p: p[1])


def first(run, name, role_prefix=""):
    """(start, end) of the first phase `name` of a role, or None."""
    for got, start, end, role in phases(run):
        if got == name and role.startswith(role_prefix):
            return start, end
    return None


def seconds(run, names):
    """Summed seconds of the first worker's first phase of each name; None
    unless every one is there."""
    spans = [first(run, name, "worker") for name in names]
    if None in spans:
        return None
    return sum(end - start for start, end in spans)


def warmup(run):
    """(start, end): from the end of the first worker's last
    `setup.first_dispatch` before the window to the window's start."""
    ends = [end for name, _, end, role in phases(run)
            if name == "setup.first_dispatch" and role.startswith("worker")
            and end <= run.t0]
    return (max(ends), run.t0) if ends else None


def union(spans):
    """Merged, sorted [(start, end)]."""
    out = []
    for start, end in sorted(spans):
        if out and start <= out[-1][1]:
            out[-1] = (out[-1][0], max(out[-1][1], end))
        else:
            out.append((start, end))
    return out


def unnamed(run, say=False):
    """Seconds between `edl train`'s start and the window under no
    phase of the master or the first worker and not warm-up; None for a
    program without the spans. With `say`, prints the longest gaps and
    the phases on either side."""
    named = phases(run)
    if not named:
        return None
    a, b = run.t_launch, run.t0
    spans = [(max(s, a), min(e, b)) for _, s, e, _ in named if e > a]
    warm = warmup(run)
    if warm:
        spans.append(warm)
    covered = union(spans)
    gaps, at = [], a
    for start, end in covered + [(b, b)]:
        if start > at:
            before = [n for n, _, e, _ in named if e <= at + 1e-6]
            after = [n for n, s, _, _ in named if s >= start - 1e-6]
            gaps.append({
                "seconds": start - at, "from": at - a,
                "after": before[-1] if before else "launch",
                "before": after[0] if after else "window",
            })
        at = max(at, end)
    if say:
        gaps.sort(key=lambda g: -g["seconds"])
        print(json.dumps({
            "reader": "setup_phases",
            "phases": [
                {"name": n, "role": r, "from": round(s - a, 3),
                 "seconds": round(e - s, 3)} for n, s, e, r in named],
            "harness_before_launch_s": round(a - run.t_start, 3),
            "longest_unnamed": gaps[:5],
        }), flush=True)
    return sum(g["seconds"] for g in gaps)
