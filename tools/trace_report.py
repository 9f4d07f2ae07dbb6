"""Merge per-process trace JSONL files and summarize per-phase timing.

The observability plane writes one `trace_<role>.jsonl` per process (master,
each PS, each worker) into the job's obs/metrics directory. This tool:

  1. merges them into a single Chrome-trace JSON (`--out merged.json`)
     loadable in Perfetto (https://ui.perfetto.dev) or chrome://tracing;
  2. prints the per-phase summary: per
     process and span name, total/count/mean plus p50/p99 over complete
     ("X") events;
  3. with --task, filters to one task's cross-process chain and prints it
     in time order — the dispatch -> pull -> train -> push -> report view.

Usage:
  python tools/trace_report.py <obs_dir_or_trace_files...> \
      [--out merged.json] [--task TASK_ID] [--json]
"""

import argparse
import glob
import json
import os
import sys


def load_events(paths):
    """Parse trace_*.jsonl files (directories expand to their trace files).
    Returns (events, process_names: pid -> name)."""
    files = []
    for path in paths:
        if os.path.isdir(path):
            files.extend(
                sorted(glob.glob(os.path.join(path, "trace_*.jsonl")))
            )
        else:
            files.append(path)
    events, names = [], {}
    for file in files:
        with open(file) as f:
            for line in f:
                line = line.strip()
                if not line:
                    continue
                try:
                    event = json.loads(line)
                except json.JSONDecodeError:
                    continue  # torn final line of a killed process
                if (
                    event.get("ph") == "M"
                    and event.get("name") == "process_name"
                ):
                    names[event["pid"]] = event["args"]["name"]
                events.append(event)
    return events, names


def quantile(ordered, q):
    if not ordered:
        return 0.0
    return ordered[min(len(ordered) - 1, int(q * len(ordered)))]


def summarize(events, names):
    """{(process, name): {total_ms, count, mean_ms, p50_ms, p99_ms}}"""
    groups = {}
    for event in events:
        if event.get("ph") != "X":
            continue
        process = names.get(event["pid"], str(event["pid"]))
        groups.setdefault((process, event["name"]), []).append(
            event.get("dur", 0.0) / 1e3
        )
    out = {}
    for key, durs in groups.items():
        ordered = sorted(durs)
        out[key] = {
            "total_ms": round(sum(durs), 3),
            "count": len(durs),
            "mean_ms": round(sum(durs) / len(durs), 3),
            "p50_ms": round(quantile(ordered, 0.50), 3),
            "p99_ms": round(quantile(ordered, 0.99), 3),
        }
    return out


def task_chain(events, names, task_id):
    """One task's events across every process, in time order."""
    chain = [
        e
        for e in events
        if e.get("ph") in ("X", "i")
        and e.get("args", {}).get("task_id") == task_id
    ]
    chain.sort(key=lambda e: e.get("ts", 0))
    return [
        {
            "process": names.get(e["pid"], str(e["pid"])),
            "name": e["name"],
            "ts_us": e.get("ts"),
            "dur_ms": round(e.get("dur", 0.0) / 1e3, 3),
        }
        for e in chain
    ]


def main(argv=None):
    parser = argparse.ArgumentParser(
        "trace_report", description=__doc__.split("\n")[0]
    )
    parser.add_argument(
        "paths", nargs="+", help="obs dirs and/or trace_*.jsonl files"
    )
    parser.add_argument(
        "--out", default="", help="write merged Chrome-trace JSON here"
    )
    parser.add_argument(
        "--task", type=int, default=None, help="print one task's chain"
    )
    parser.add_argument(
        "--json", action="store_true", help="machine-readable output"
    )
    args = parser.parse_args(argv)

    events, names = load_events(args.paths)
    if not events:
        print("no trace events found", file=sys.stderr)
        return 1

    if args.out:
        with open(args.out, "w") as f:
            json.dump({"traceEvents": events}, f)
        print(
            f"wrote {len(events)} events from {len(names)} processes "
            f"to {args.out} (load in https://ui.perfetto.dev)",
            file=sys.stderr,
        )

    summary = summarize(events, names)
    if args.json:
        payload = {
            "processes": sorted(names.values()),
            "phases": [
                {"process": p, "name": n, **stats}
                for (p, n), stats in sorted(summary.items())
            ],
        }
        if args.task is not None:
            payload["task_chain"] = task_chain(events, names, args.task)
        print(json.dumps(payload, indent=2))
        return 0

    width = max(
        (len(f"{p} {n}") for p, n in summary), default=20
    )
    header = (
        f"{'process / span':<{width}}  {'count':>7} {'total_ms':>10} "
        f"{'mean_ms':>9} {'p50_ms':>9} {'p99_ms':>9}"
    )
    print(header)
    print("-" * len(header))
    for (process, name), s in sorted(summary.items()):
        print(
            f"{process + ' ' + name:<{width}}  {s['count']:>7} "
            f"{s['total_ms']:>10.3f} {s['mean_ms']:>9.3f} "
            f"{s['p50_ms']:>9.3f} {s['p99_ms']:>9.3f}"
        )
    if args.task is not None:
        print(f"\ntask {args.task} chain:")
        for hop in task_chain(events, names, args.task):
            print(
                f"  {hop['ts_us']:>18.1f}us {hop['process']:<24} "
                f"{hop['name']} ({hop['dur_ms']}ms)"
            )
    return 0


if __name__ == "__main__":
    sys.exit(main())
