"""What a metric reader is given: one run's samples, events, log, trace.

A reader is `benchmark/metrics/<metric>.py` with `read(run)`, where `run`
is a RunView; it returns a number, or None when it finds nothing to read
(the metric is then left out of the result line).
"""

import datetime
import re

from lib import measure

# "[2026-09-27 08:00:45,105] [INFO] [...] Step 8 (version 8) loss 10.88"
STEP_LINE = re.compile(
    r"^\[(\d{4}-\d\d-\d\d \d\d:\d\d:\d\d),(\d{3})\].*"
    r"Step (\d+) \((?:version|lease) \d+\) loss ([-+0-9.eE]+|nan|inf)"
)


def _log_epoch(date, millis):
    dt = datetime.datetime.strptime(date, "%Y-%m-%d %H:%M:%S")
    return dt.timestamp() + int(millis) / 1000.0


def step_losses(log_text):
    """[(epoch seconds, step, loss)] from the workers' step lines, in log
    order."""
    out = []
    for line in log_text.splitlines():
        m = STEP_LINE.match(line)
        if m:
            out.append((_log_epoch(m.group(1), m.group(2)),
                        int(m.group(3)), float(m.group(4))))
    return out


class RunView:
    def __init__(self, cell, seed, seconds, t_start, t_launch, measured,
                 events, log, trace=None, device=None):
        self.cell = cell
        self.config = cell.config
        self.traffic = cell.traffic
        self.seed = seed
        self.seconds = seconds
        self.t_start = t_start      # run.py began
        self.t_launch = t_launch    # `edl train` was started
        self.t0 = measured["t0"]    # the window opened
        self.t1 = measured["t1"]    # its nominal end
        self.samples = measured["samples"]
        self.status = measured["last"]
        self.worker_series = measured["worker_series"]
        self.events = events
        self.log = log
        self.trace = trace          # lib.trace.reduce(...) or None
        # When the worker had written its trace (a traced run only):
        # starting, stopping and writing it stalls the worker's loop.
        self.t_traced = measured.get("t_traced")
        self.device = device or {}

    # ---------- the window ----------

    def window_ends(self):
        return measure.window_ends(self.samples, self.t0, self.t1)

    def fenced_steps(self):
        """[(time, step)] of the step lines the first worker logged
        inside the window (in a traced run: after the trace was written).
        A step line is written after `float(loss)`, so at its time every
        step up to it has left the device."""
        since = max(self.t0, self.t_traced or self.t0)
        return [(ts, step) for ts, step, _ in step_losses(self.log)
                if since <= ts <= self.t1]

    def record_rate(self):
        """Records a second between the first and the last fenced step
        line inside the window; None without two.

        Not from `records_done`: the worker reports a task when its steps
        are dispatched, up to one logging period ahead of the device, so
        a rate between two task reports read 3.2 % high and jumped by a
        task with the phase of the fence (PR 24, PERF.md)."""
        fenced = self.fenced_steps()
        if len(fenced) < 2:
            return None
        (ta, sa), (tb, sb) = fenced[0], fenced[-1]
        if tb <= ta:
            return None
        return (sb - sa) * int(self.traffic["minibatch"]) / (tb - ta)

    def window_span(self):
        ends = self.window_ends()
        return (ends[0][0], ends[1][0]) if ends else (self.t0, self.t1)

    # ---------- events ----------

    def events_of(self, kinds, role_prefix=None, since=None, until=None):
        if isinstance(kinds, str):
            kinds = (kinds,)
        out = []
        for e in self.events:
            if e.get("kind") not in kinds:
                continue
            if role_prefix and not str(e.get("role", "")).startswith(
                    role_prefix):
                continue
            ts = e.get("ts", 0.0)
            if since is not None and ts < since:
                continue
            if until is not None and ts > until:
                continue
            out.append(e)
        return out

    def stage_share_pct(self, stages):
        """Seconds the workers' `datapath` events book under the named
        stages, over the tasks that ended inside the window, as a share of
        the time between the first and last such task."""
        a, b = self.window_span()
        tasks = self.events_of("datapath", "worker", since=a, until=b)
        if len(tasks) < 2 or b <= a:
            return None
        # An event closes its task: the first one's seconds were spent
        # before the span opened.
        spent = sum(
            float(e.get(f"{stage}_s", 0.0))
            for e in tasks[1:] for stage in stages
        )
        span = tasks[-1]["ts"] - tasks[0]["ts"]
        return 100.0 * spent / span if span > 0 else None

    def step_load_seconds(self, since=None, until=None):
        """Seconds the workers spent compiling or loading `*_step`
        programs (compile and compile_cache_hit events)."""
        events = [
            e for e in self.events_of(
                ("compile", "compile_cache_hit"), "worker", since, until)
            if str(e.get("fn", "")).endswith("_step")
        ]
        if not events:
            return None
        return sum(float(e["seconds"]) for e in events)
