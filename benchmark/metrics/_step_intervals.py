"""Milliseconds between consecutive steps leaving the device, from the
worker's `steps_done` events (its step-done clock stamps every step
without a fence), inside the window; in a traced run after the trace was
written, as RunView.fenced_steps does."""

import json
import statistics

MIN_INTERVALS = 20


def intervals(run):
    """[(ms, step)] for consecutive steps s-1, s both stamped inside the
    window by the first worker, in step order."""
    events = run.events_of("steps_done", "worker")
    if not events:
        return []
    role = events[0].get("role")
    since = max(run.t0, run.t_traced or run.t0)
    done = {}
    for e in events:
        if e.get("role") != role:
            continue
        for i, ts in enumerate(e["stamps"]):
            if since <= ts <= run.t1:
                done[int(e["first_step"]) + i] = float(ts)
    return [(1e3 * (done[s] - done[s - 1]), s)
            for s in sorted(done) if s - 1 in done]


def percentile(run, q, say=False):
    """The q-th percentile (q in 1..99) of the intervals; None with fewer
    than MIN_INTERVALS."""
    got = intervals(run)
    if len(got) < MIN_INTERVALS:
        return None
    if say:
        longest = max(got)
        print(json.dumps({"reader": "step_intervals", "count": len(got),
                          "longest_ms": longest[0],
                          "longest_at_step": longest[1]}), flush=True)
    cuts = statistics.quantiles(
        [ms for ms, _ in got], n=100, method="inclusive")
    return cuts[q - 1]
