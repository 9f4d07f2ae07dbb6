"""Seconds the first worker's device had nothing queued (`dry_s` of its
`step_stall` events, those marked `profile` left out) as a share of the
window; in a traced run of what follows the written trace. None for a
program whose step-done clock counts no stalls
(`edl_worker_step_stalls_total` on the worker's /metrics). Prints each
stall it counted, without the stacks."""

import json


def read(run):
    if not any(k.startswith("edl_worker_step_stalls_total")
               for k in run.worker_series):
        return None
    since = max(run.t0, run.t_traced or run.t0)
    if run.t1 <= since:
        return None
    stalls = [e for e in run.events_of(
        "step_stall", "worker", since=since, until=run.t1)
        if e.get("cause") != "profile"]
    for e in stalls:
        print(json.dumps({"reader": "host_stall", **{
            k: e.get(k) for k in (
                "step", "cause", "dry_s", "interval_s", "median_s",
                "wake_late_s", "open_compiles", "gc_collections")}}),
              flush=True)
    dry = sum(float(e.get("dry_s") or 0.0) for e in stalls)
    return 100.0 * dry / (run.t1 - since)
