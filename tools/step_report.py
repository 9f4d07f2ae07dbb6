"""Offline step-time attribution from a job's observability directory.

`python tools/step_report.py <obs_dir>` (or `edl profile --obs_dir ...`)
merges what the deep-profiling plane already wrote to disk —

    trace_<role>.jsonl   phase spans (batch_process, ps_push_serialize,
                         ps_push_wait, rpc_client/* pulls, compile:*)
    events.jsonl         compile events (cause attribution) and memory
                         high-watermark events

— into one "where did this step go" table per worker role: the fraction
of step time (batch_process wall) spent in compute / serialize / PS
wire / recompile / other, plus a compile-cause summary and the memory
watermark timeline. Derived from spans, not from trainer Timing, so
any job that wrote its traces reads on the one scale.

Offline span sums cannot see nesting, so compute is derived as the
batch remainder after the known non-compute spans — a conservative
upper bound, clamped at zero like every other bucket.
"""

import glob
import json
import os
import sys

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path.insert(0, REPO)

from elasticdl_tpu.observability.events import read_events  # noqa: E402

# span name (exact or prefix) -> bucket, in seconds of span duration
_SPAN_BUCKETS = (
    ("ps_push_serialize", "serialize"),
    ("ps_push_wait", "ps_wire"),
    ("rpc_client/elasticdl_tpu.Pserver/pull_dense_parameters", "ps_wire"),
    ("rpc_client/elasticdl_tpu.Pserver/pull_embedding_vectors",
     "input_wait"),
    ("compile:", "recompile"),
)


def read_role_spans(path):
    """{span name: total seconds} + batch/task wall for one trace file.
    Torn final lines (SIGKILLed writer) are skipped like read_events."""
    sums = {}
    with open(path) as f:
        for line in f:
            line = line.strip()
            if not line:
                continue
            try:
                event = json.loads(line)
            except json.JSONDecodeError:
                continue
            if event.get("ph") != "X":
                continue
            name = event.get("name", "")
            dur_s = float(event.get("dur", 0.0)) / 1e6
            sums[name] = sums.get(name, 0.0) + dur_s
    return sums


def role_attribution(span_sums):
    """One role's bucket fractions from its span duration sums; None
    when the trace carries no batch_process steps."""
    batch_s = span_sums.get("batch_process", 0.0)
    if batch_s <= 0:
        return None
    buckets = {}
    for needle, bucket in _SPAN_BUCKETS:
        for name, total in span_sums.items():
            if (
                name.startswith(needle)
                if needle.endswith((":", "/"))
                else name == needle
            ):
                buckets[bucket] = buckets.get(bucket, 0.0) + total
    fractions = {
        bucket: min(1.0, total / batch_s)
        for bucket, total in buckets.items()
    }
    attributed = sum(fractions.values())
    if attributed > 1.0:
        fractions = {
            k: v / attributed for k, v in fractions.items()
        }
        attributed = 1.0
    fractions["compute"] = max(0.0, 1.0 - attributed)
    fractions["batch_seconds"] = batch_s
    return {
        k: round(v, 4) for k, v in fractions.items()
    }


def collect(obs_dir):
    """The report's raw material: per-role attributions, compile events,
    memory watermarks."""
    roles = {}
    for path in sorted(glob.glob(os.path.join(obs_dir, "trace_*.jsonl"))):
        role = os.path.basename(path)[len("trace_"):-len(".jsonl")]
        attribution = role_attribution(read_role_spans(path))
        if attribution:
            roles[role] = attribution
    compiles = []
    watermarks = []
    events_path = os.path.join(obs_dir, "events.jsonl")
    if os.path.exists(events_path):
        for event in read_events(events_path):
            if event.get("kind") == "compile":
                compiles.append(event)
            elif event.get("kind") == "mem_high_watermark":
                watermarks.append(event)
    return {
        "roles": roles,
        "compiles": compiles,
        "mem_watermarks": watermarks,
    }


COLUMNS = ("compute", "serialize", "ps_wire", "input_wait", "recompile")


def render_report(obs_dir):
    data = collect(obs_dir)
    lines = [f"step-time attribution for {obs_dir}"]
    if not data["roles"]:
        lines.append("  (no batch_process spans found in any trace)")
    else:
        width = max(len(r) for r in data["roles"])
        head = "  ".join(f"{c:>10}" for c in COLUMNS)
        lines.append(f"  {'role':<{width}}  {head}  step_wall_s")
        for role in sorted(data["roles"]):
            row = data["roles"][role]
            cells = "  ".join(
                f"{row.get(c, 0.0):>10.3f}" for c in COLUMNS
            )
            lines.append(
                f"  {role:<{width}}  {cells}  "
                f"{row['batch_seconds']:.2f}"
            )
    by_cause = {}
    seconds = 0.0
    for event in data["compiles"]:
        cause = event.get("cause", "?")
        by_cause[cause] = by_cause.get(cause, 0) + 1
        seconds += float(event.get("seconds", 0.0))
    lines.append(
        f"compiles: {sum(by_cause.values())} "
        f"({', '.join(f'{c}={n}' for c, n in sorted(by_cause.items()))})"
        f" totalling {seconds:.2f}s"
        if by_cause
        else "compiles: none recorded"
    )
    for event in data["mem_watermarks"]:
        lines.append(
            f"mem high-watermark: {event.get('role', '?')} reached "
            f"{event.get('bytes', 0)} bytes "
            f"(x{event.get('ratio')} over previous peak)"
        )
    return "\n".join(lines)


def main(argv):
    if len(argv) != 1:
        print("usage: python tools/step_report.py <obs_dir>")
        return 2
    print(render_report(argv[0]))
    return 0


if __name__ == "__main__":
    sys.exit(main(sys.argv[1:]))
