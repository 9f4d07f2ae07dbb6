"""Which host span of the program belongs to which layer, and the device's
idle time split over them.

The names are the program's (docs/OBSERVABILITY.md, "Spans on the step
path"): every boundary of the worker's step path is one `tracing.span`,
and in a profiler session each lies in the trace's host plane.
`lib/hostspans.py` does the reading; this table says what the names
mean. A program without them (before PR 25) gives None everywhere.
"""

import json

from lib import hostspans

DISPATCH = "trainer.dispatch"
_PREFIXES = ("worker.", "datapath.", "trainer.")
_NAMES = ("task_process", "batch_process")

# Layer buckets, by BENCHMARK.json's `layer` names.
TASK_PLANE = "task plane"
WORKER_LOOP = "worker loop"
TRAINER = "trainer"
DEVICE = "device"  # idle under no span of the program
_TASK_SPANS = ("datapath.task", "worker.report_task",
               "worker.report_version")


def is_span(name):
    return name.startswith(_PREFIXES) or name in _NAMES


def layer_of(name):
    """A gap under `worker.step` alone (python between the stages), or
    under any other span of the worker's loop, is the worker loop's."""
    if name == hostspans.UNATTRIBUTED:
        return DEVICE
    if name in _TASK_SPANS:
        return TASK_PLANE
    if name.startswith("trainer."):
        return TRAINER
    return WORKER_LOOP


def idle_by_span(run):
    """{"gaps": hostspans' gaps, "by_span_s": {span: seconds, mean over
    the chips}, "by_layer_pct": {layer: share of the traced window}},
    worked out once a run; None when there is nothing to read."""
    if not hasattr(run, "_idle_by_span"):
        run._idle_by_span = _idle_by_span(run)
    return run._idle_by_span


def _idle_by_span(run):
    t = run.trace
    if not t or t["window_s"] <= 0:
        return None
    found = hostspans.attribute(run, is_span, DISPATCH)
    if found is None:
        return None
    n = len(t["devices"])
    by_span = {}
    for _, _, _, _, _, parts in found["gaps"]:
        for name, ns in parts.items():
            by_span[name] = by_span.get(name, 0.0) + ns / n / 1e9
    by_layer = {layer: 0.0 for layer in
                (TASK_PLANE, WORKER_LOOP, TRAINER, DEVICE)}
    for name, seconds in by_span.items():
        by_layer[layer_of(name)] += 100.0 * seconds / t["window_s"]
    found["by_span_s"] = by_span
    found["by_layer_pct"] = by_layer
    return found


def idle_pct(run, layer):
    found = idle_by_span(run)
    return None if found is None else found["by_layer_pct"][layer]


def relabel_and_print(run):
    """One line for the record, and the names into the breakdown: the
    entries of run.trace["idle_gaps"] (the longest gaps over all chips,
    longest first) keep their lengths and order and get their labels."""
    found = idle_by_span(run)
    if found is None:
        return
    longest = sorted(found["gaps"], key=lambda g: -g[2])
    listed = run.trace["idle_gaps"]
    top = longest[:len(listed)]
    if all(abs(g[2] / 1e9 - entry[1]) < 1e-12
           for g, entry in zip(top, listed)):
        for g, entry in zip(top, listed):
            entry[0] = g[3]
    print(json.dumps({
        "reader": "idle_by_span",
        "by_span_s": dict(sorted(
            found["by_span_s"].items(), key=lambda kv: -kv[1])),
        "gaps": [[g[3], g[2] / 1e9, g[4]] for g in top],
        "other_threads": found["other_threads"],
    }), flush=True)
