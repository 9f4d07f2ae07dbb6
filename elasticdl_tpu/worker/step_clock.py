"""A step-done clock that needs no loss fence, and says when the device
ran dry.

The worker dispatches steps ahead of the device and only learns that a
step has finished when it reads a loss back (`float(loss)`, every
`--log_loss_steps`-th step), which stalls the dispatch loop. This clock
learns it on a thread of its own: the worker hands it each step's scalar
loss array as the step is dispatched, the thread waits for the array to
be ready (`block_until_ready` releases the GIL and reads nothing back)
and stamps `time.time()`.

It publishes the counter `edl_worker_steps_done_total` (beside
`edl_worker_steps_total`, which counts dispatches: the difference is how
far the host runs ahead) and, at most once a second, a `steps_done` event
`{"first_step": N, "stamps": [...]}` with one stamp a step (`ts` is the
event log's own key). It holds only the scalar loss, never a donated
buffer. A step that fails stamps nothing.

**Stalls.** The same thread is the only watcher of the step path, and
the dispatching thread pays nothing for it. Two fixed rules, no knob:

- *dry*: a step is stamped, nothing is queued behind it, and nothing
  arrives for `DRY_RULE_SECONDS` (0.1 s). An ordinary fence or task
  boundary hands the next step over within a millisecond (the device
  idles 9 to 33 us there) and never comes near the rule; a drought of
  0.3 s is three times over it. While the drought lasts the thread
  samples `sys._current_frames()` (every thread at first, then the
  dispatching thread alone, at 0.1, 0.3, 0.7 and 1.5 s), and measures
  how late its own timed wait returned (`wake_late_s`: a frozen process
  or a held GIL reads there, a blocked dispatcher does not).
- *slow*: the device had work queued, and the step came more than
  `SLOW_FACTOR` (1.5) times the rolling median after the one before it,
  and at least `SLOW_FLOOR_SECONDS` (0.05 s) later than the median (a
  collective, a reload: the device itself was slow). No stacks. The
  record waits for the step after it: where the two together took what
  two steps take (`interval_s + next_interval_s` under twice the median
  plus the floor), the device was on time and the *stamp* was late (this
  thread was kept from running, or the runtime told it late: on a TPU
  v5e some 0.1 s about every 10 s, PERF.md section 7), and the cause
  reads `late_stamp`.

Either writes one `step_stall` event once the late step is done: `step`,
`cause` (`dry`, `slow`, `late_stamp`, or `profile` where starting,
stopping or writing a profile overlapped the interval: that stalls the
loop by design), `dry_s` (nothing queued; 0 under the rule), `interval_s`
(done to done) beside `median_s`, `recent_spans` (the flight recorder's
last closed spans; in a worker every span but an `rpc` one closes on the
dispatching thread), `open_compiles` (any tracked function's compile or
`speculative_compile` still running) and `gc_collections` (collections
a generation during the interval; pauses are not timed: a `gc` callback
would run on the dispatching thread); a drought also `wake_late_s` and
`samples` (the stacks), a slow step `next_interval_s`. At most
`MAX_STALL_EVENTS` (20) events a process life; after that stalls are
counted only: `edl_worker_step_stalls_total{cause}`,
`edl_worker_step_stall_seconds_total{cause}` (`dry_s`, or for a slow
step or a late stamp the seconds over the median).

**A frozen process.** While this thread waits for a step it cannot
sample anything, and where another thread holds the GIL no Python thread
can. So, once a median is known, it arms `faulthandler`'s own watchdog
round each wait (`dump_traceback_later`: a C thread that needs no GIL):
a step that has not come `max(FROZEN_RULE_SECONDS, 3 x median)` after
this thread began to wait for it has every thread's Python stack written
to an unnamed temporary file, and the `step_stall` event of that step
carries it as `frozen_stacks` (lines as `faulthandler` writes them, the
threads named, the dispatching one marked). At most `MAX_FROZEN_DUMPS`
(3) a process life; the watchdog is armed by this thread and nothing is
added to the dispatching one.

`dry_s` is the clock's view: from the stamp to the next hand-over. At a
logging step the hand-over follows the enqueue by one loss read, so a
stall that falls exactly there reads up to one step longer than the
device idled.
"""

import collections
import contextlib
import faulthandler
import gc
import os
import queue
import statistics
import sys
import tempfile
import threading
import time

from elasticdl_tpu.observability import emit_event, flightrec, profiling
from elasticdl_tpu.observability.metrics import default_registry

_STEPS_DONE = default_registry().counter(
    "edl_worker_steps_done_total",
    "Minibatch steps whose result has left the device",
)
_STALLS = default_registry().counter(
    "edl_worker_step_stalls_total",
    "Steps that came late: the device ran dry (dry), was slow itself "
    "(slow), or a profile was being started or written (profile)",
    labelnames=("cause",),
)
_STALL_SECONDS = default_registry().counter(
    "edl_worker_step_stall_seconds_total",
    "Seconds lost to late steps: nothing queued (dry), or over the "
    "median interval (slow)",
    labelnames=("cause",),
)
for _cause in ("dry", "slow", "late_stamp", "profile"):
    # On /metrics at 0 from the start: a reader tells a clean run from
    # a program that does not watch.
    _STALLS.labels(cause=_cause)
    _STALL_SECONDS.labels(cause=_cause)

EMIT_INTERVAL_SECONDS = 1.0
DRY_RULE_SECONDS = 0.1
SLOW_FACTOR = 1.5
SLOW_FLOOR_SECONDS = 0.05
MEDIAN_WINDOW = 64
MIN_INTERVALS = 8
MAX_STALL_EVENTS = 20
MAX_SAMPLES = 4
RECENT_SPANS = 12
FROZEN_RULE_SECONDS = 1.0
MAX_FROZEN_DUMPS = 3
FROZEN_LINES = 150
_DISPATCHER_FRAMES = 12
_OTHER_FRAMES = 4


def _frames(frame, depth):
    out = []
    while frame is not None and len(out) < depth:
        code = frame.f_code
        path = code.co_filename.split(os.sep)
        out.append(
            f"{os.sep.join(path[-2:])}:{frame.f_lineno} {code.co_name}"
        )
        frame = frame.f_back
    return out


def sample_stacks(dispatcher, every_thread=True):
    """[{"thread", "dispatcher", "frames"}] of this process's threads but
    the caller's, innermost frame first as `dir/file:line function`; with
    `every_thread` false the dispatching thread alone."""
    names = {t.ident: t.name for t in threading.enumerate()}
    own = threading.get_ident()
    out = []
    for ident, frame in sys._current_frames().items():
        dispatching = ident == dispatcher
        if ident == own or not (every_thread or dispatching):
            continue
        out.append({
            "thread": names.get(ident, str(ident)),
            "dispatcher": dispatching,
            "frames": _frames(
                frame, _DISPATCHER_FRAMES if dispatching else _OTHER_FRAMES
            ),
        })
    return out


def _recent_spans():
    rec = flightrec.get()
    if rec is None:
        return []
    return rec.recent(RECENT_SPANS)


def _gc_collections():
    return [g["collections"] for g in gc.get_stats()]


class StepDoneClock:
    def __init__(self, emit_interval=EMIT_INTERVAL_SECONDS):
        self._emit_interval = emit_interval
        self._queue = queue.SimpleQueue()
        self._thread = None
        self._dispatcher = None
        # Written by the dispatching thread round a profile call.
        self._profile_open = False
        self._profile_end = 0.0
        # Touched by the clock's thread only.
        self._first_step = None
        self._stamps = []
        self._last_emit = 0.0
        self._last_done = None  # (step, stamp, gc counts) of the newest
        self._intervals = collections.deque(maxlen=MEDIAN_WINDOW)
        self._held = None  # a slow step waiting for the step after it
        self._stall_events = 0
        self._stacks_file = None  # where faulthandler's watchdog writes
        self._stacks_read = 0
        self._frozen_dumps = 0
        self._frozen_stacks = None  # the newest dump, until an event has it

    def dispatched(self, step, loss):
        """Step number `step` was just dispatched; `loss` is its (lazy)
        scalar loss. Starts the thread with the first step: whoever
        calls is the dispatching thread."""
        if self._thread is None:
            self._dispatcher = threading.get_ident()
            self._thread = threading.Thread(
                target=self._run, name="edl-step-done", daemon=True
            )
            self._thread.start()
        self._queue.put((step, loss))

    @contextlib.contextmanager
    def profile_call(self):
        """Round `start_trace` / `stop_trace`: a step that comes late
        across it is marked `cause: "profile"`."""
        self._profile_open = True
        try:
            yield
        finally:
            self._profile_end = time.time()
            self._profile_open = False

    def close(self, timeout=10.0):
        """Stamp what is queued, write the last event, end the thread."""
        thread, self._thread = self._thread, None
        if thread is None:
            return
        self._queue.put(None)
        thread.join(timeout)

    def _run(self):
        self._last_done = None
        while True:
            item, drought = self._next()
            if item is None:
                self._settle()
                self._flush()
                return
            step, loss = item
            try:
                wait = getattr(loss, "block_until_ready", None)
                if wait is not None:
                    armed = self._watch_for_a_freeze()
                    try:
                        wait()
                    finally:
                        if armed:
                            faulthandler.cancel_dump_traceback_later()
                            self._read_frozen_stacks()
            except Exception:
                # The step failed on the device (the dispatch loop sees
                # the same error at its next fence): no stamp, and the
                # run of consecutive steps ends here, and no interval is
                # reckoned across the break.
                self._settle()
                self._flush()
                self._last_done = None
                continue
            now = time.time()
            _STEPS_DONE.inc()
            if self._first_step is None:
                self._first_step = step
            elif step != self._first_step + len(self._stamps):
                # A retried or skipped step number: start a new run so
                # that first_step + index stays each stamp's step.
                self._flush()
                self._first_step = step
            self._stamps.append(round(now, 6))
            self._note_interval(step, now, drought)
            if now - self._last_emit >= self._emit_interval:
                self._flush()

    def _watch_for_a_freeze(self):
        """Arm faulthandler's watchdog for the wait that follows; False
        while there is no median yet or the dumps are used up."""
        if (
            len(self._intervals) < MIN_INTERVALS
            or self._frozen_dumps >= MAX_FROZEN_DUMPS
        ):
            return False
        if self._stacks_file is None:
            self._stacks_file = tempfile.TemporaryFile()
        faulthandler.dump_traceback_later(
            max(FROZEN_RULE_SECONDS,
                3 * statistics.median(self._intervals)),
            file=self._stacks_file,
        )
        return True

    def _read_frozen_stacks(self):
        size = os.fstat(self._stacks_file.fileno()).st_size
        if size <= self._stacks_read:
            return
        self._stacks_file.seek(self._stacks_read)
        text = self._stacks_file.read().decode(errors="replace")
        self._stacks_read = size
        self._frozen_dumps += 1
        names = {
            f"0x{t.ident:016x}": t.name + (
                " (dispatcher)" if t.ident == self._dispatcher else ""
            )
            for t in threading.enumerate()
        }
        lines = []
        for line in text.splitlines()[:FROZEN_LINES]:
            words = line.split()
            if len(words) > 1 and words[0] in ("Thread", "Current"):
                ident = words[2] if words[0] == "Current" else words[1]
                line = f"{line} {names.get(ident, '')}".rstrip()
            lines.append(line)
        self._frozen_stacks = lines

    def _next(self):
        """(the next queued item, what was seen while nothing was queued
        behind the newest stamped step for longer than the rule, or
        None)."""
        if self._last_done is None:
            return self._queue.get(), None
        try:
            return self._queue.get_nowait(), None
        except queue.Empty:
            pass
        drought = None
        wait = DRY_RULE_SECONDS
        while True:
            asked = time.monotonic()
            try:
                item = self._queue.get(timeout=wait)
                break
            except queue.Empty:
                late = time.monotonic() - asked - wait
            if drought is None:
                drought = {
                    "wake_late_s": 0.0, "samples": [],
                    "recent_spans": _recent_spans(),
                    "open_compiles": profiling.open_compiles(),
                }
            drought["wake_late_s"] = max(drought["wake_late_s"], late)
            samples = drought["samples"]
            if len(samples) < MAX_SAMPLES:
                samples.append({
                    "at_s": round(time.time() - self._last_done[1], 3),
                    "threads": sample_stacks(
                        self._dispatcher, every_thread=not samples
                    ),
                })
                wait *= 2
        if drought is not None:
            drought["dry_s"] = time.time() - self._last_done[1]
        return item, drought

    def _note_interval(self, step, now, drought):
        last, counts = self._last_done, _gc_collections()
        self._last_done = (step, now, counts)
        consecutive = last is not None and step == last[0] + 1
        interval = now - last[1] if consecutive else None
        self._settle(interval)
        if not consecutive:
            return
        median = (
            statistics.median(self._intervals)
            if len(self._intervals) >= MIN_INTERVALS else None
        )
        self._intervals.append(interval)
        profile = self._profile_open or self._profile_end >= last[1]
        fields = {
            "step": step, "interval_s": round(interval, 6),
            "median_s": None if median is None else round(median, 6),
            "gc_collections": [b - a for a, b in zip(last[2], counts)],
        }
        if self._frozen_stacks is not None:
            fields["frozen_stacks"] = self._frozen_stacks
            self._frozen_stacks = None
        if drought is not None:
            lost = drought.pop("dry_s")
            self._stall(
                "profile" if profile else "dry", lost,
                dict(fields, dry_s=round(lost, 6), **drought),
            )
        elif (
            median is not None
            and interval > SLOW_FACTOR * median
            and interval - median >= SLOW_FLOOR_SECONDS
        ):
            # Held until the next step is stamped: the interval after it
            # tells a slow step from a late stamp.
            self._held = (profile, interval, median, dict(
                fields, dry_s=0.0, recent_spans=_recent_spans(),
                open_compiles=profiling.open_compiles(),
            ))

    def _settle(self, next_interval=None):
        """Write the held slow step, now that the interval after it is
        known (None: the run of steps ended with it)."""
        held, self._held = self._held, None
        if held is None:
            return
        profile, interval, median, fields = held
        late = (
            next_interval is not None
            and interval + next_interval < 2 * median + SLOW_FLOOR_SECONDS
        )
        self._stall(
            "profile" if profile else "late_stamp" if late else "slow",
            interval - median,
            dict(fields, next_interval_s=(
                None if next_interval is None else round(next_interval, 6)
            )),
        )

    def _stall(self, cause, lost, fields):
        _STALLS.labels(cause=cause).inc()
        _STALL_SECONDS.labels(cause=cause).inc(lost)
        self._stall_events += 1
        if self._stall_events <= MAX_STALL_EVENTS:
            emit_event("step_stall", cause=cause, **fields)

    def _flush(self):
        if self._stamps:
            emit_event(
                "steps_done", first_step=self._first_step, stamps=self._stamps
            )
        self._first_step, self._stamps = None, []
        self._last_emit = time.time()
