"""Stage-level data-plane instrumentation for the input pipeline.

The trainers' Timing summaries give `input_wait` as one large share
of every PS-mode step, but it is a single opaque bucket: nothing says
whether the time went to waiting on the master for a task lease, to the
record reader, to decode, or to the h2d copy. This module decomposes the
feed path into named stages and lands every stage three ways at once:

- a `Timing` phase (``input_<stage>``) on the Timing object the call
  site passes (the PS and local trainers' h2d), so
  a reader of `Timing.summary()` can split `input_wait` into
  sub-fractions from the same phase summaries it already reads;
- a tracing span (``datapath.<stage>``) so Perfetto shows the feed
  path interleaved with train_step/push/pull spans, and, while a
  jax.profiler session is open, on the device trace's own clock;
- Prometheus series: `edl_datapath_seconds_total{stage}` and
  `edl_datapath_records_total` for fleet rollups (per-worker
  starvation share, decode throughput).

Stage model (docs/OBSERVABILITY.md "Data plane"):

    task     waiting on the master for a task lease (get_task RPC wait)
    read     pulling raw records out of the reader/storage
    decode   parsing records into arrays (InputSpec.feed)
    collate  assembling rows/batches from already-read records
    h2d      host-to-device transfer of the built batch
    starve   trainer blocked on an EMPTY prefetch queue (the step could
             not start because no batch was ready)

`read` vs `starve`: with the prefetch pipeline on (the default), the
producer thread owns `read` and the consumer's wait on the hand-off
queue is `starve` — the signal a perf PR acts on. Without prefetch the
consumer's pull IS the read, and starve stays zero.

Hand-off queues additionally report occupancy through `QueueTelemetry`:
an `edl_datapath_queue_depth{queue}` gauge plus edge-triggered
high-watermark events (`datapath_backpressure`) and an
`edl_datapath_backpressure_total{queue}` counter when a bounded queue
crosses ELASTICDL_DATAPATH_QUEUE_WATERMARK of its capacity.

Overhead is bounded by design: one wall-clock timestamp pair (the
span's) and a counter bump per stage; ELASTICDL_DATAPATH=0 turns every stage() into a
no-op yield.
"""

import contextlib
import threading

from elasticdl_tpu.common import knobs
from elasticdl_tpu.observability import emit_event, tracing
from elasticdl_tpu.observability.metrics import default_registry

DATAPATH_ENV = "ELASTICDL_DATAPATH"
QUEUE_CAPACITY_ENV = "ELASTICDL_DATAPATH_QUEUE_CAPACITY"
QUEUE_WATERMARK_ENV = "ELASTICDL_DATAPATH_QUEUE_WATERMARK"

# Canonical stage names; the Timing phase is "input_<stage>" so the
# summary's readers can bucket them under input_wait.
STAGES = ("task", "read", "decode", "collate", "h2d", "starve")

_registry = default_registry()
_SECONDS = _registry.counter(
    "edl_datapath_seconds_total",
    "Wall seconds spent per input-pipeline stage",
    labelnames=("stage",),
)
_RECORDS = _registry.counter(
    "edl_datapath_records_total",
    "Records delivered by the input pipeline",
)
_QUEUE_DEPTH = _registry.gauge(
    "edl_datapath_queue_depth",
    "Current occupancy of an input-pipeline hand-off queue",
    labelnames=("queue",),
)
_BACKPRESSURE = _registry.counter(
    "edl_datapath_backpressure_total",
    "High-watermark crossings of an input-pipeline hand-off queue",
    labelnames=("queue",),
)


class _Stage:
    """Mutable holder yielded by stage(); the body sets .records to the
    number of records the stage delivered (counted ONCE per record, at
    the delivery boundary — producers and transforms leave it 0)."""

    __slots__ = ("records",)

    def __init__(self, records=0):
        self.records = records


class Datapath:
    """Per-process data-plane instrumentation hub.

    One instance per process (module singleton via get()); Timing
    mirroring is per-call-site — pass `timing=` so the phase lands on
    the Timing object whose summary the caller reports (the PS and
    local trainers' own Timing for h2d)."""

    def __init__(self, enabled=None):
        if enabled is None:
            enabled = knobs.get_int(DATAPATH_ENV) != 0
        self._enabled = bool(enabled)
        self._lock = threading.Lock()
        # Per-flush accumulation for the `datapath` event trail:
        # {stage: seconds} plus a record count, swapped out whole by
        # flush_event() at task boundaries.
        self._acc = {}
        self._acc_records = 0

    @property
    def enabled(self):
        return self._enabled

    @contextlib.contextmanager
    def stage(self, name, records=0, timing=None):
        """Time one stage execution. Yields a holder whose .records the
        body may set once the delivered record count is known."""
        holder = _Stage(records)
        if not self._enabled:
            yield holder
            return
        # One clock pair: the span's. An annotation in the profiler's
        # trace cannot be entered after the fact, so the span wraps the
        # body and the counters are fed from its duration.
        sp = tracing.span("datapath." + name, cat="datapath")
        try:
            with sp:
                yield holder
        finally:
            self.add(name, sp.dur, records=holder.records, timing=timing)

    def add(self, name, seconds, records=0, timing=None):
        """Account an already-measured stage interval (for producer
        threads that time with their own clock pair)."""
        if not self._enabled or seconds < 0:
            return
        _SECONDS.labels(stage=name).inc(seconds)
        if records:
            _RECORDS.inc(records)
        if timing is not None:
            timing.add("input_" + name, seconds)
        with self._lock:
            self._acc[name] = self._acc.get(name, 0.0) + seconds
            self._acc_records += records

    def flush_event(self, **extra):
        """Emit one `datapath` event carrying the per-stage seconds
        accumulated since the last flush (called at task boundaries so
        the event trail stays one line per task, not per batch)."""
        if not self._enabled:
            return
        with self._lock:
            acc, self._acc = self._acc, {}
            records, self._acc_records = self._acc_records, 0
        if not acc and not records:
            return
        fields = {f"{k}_s": round(v, 6) for k, v in sorted(acc.items())}
        emit_event("datapath", records=records, **fields, **extra)


class QueueTelemetry:
    """Occupancy/backpressure telemetry for one bounded hand-off queue.

    depth() sets the `edl_datapath_queue_depth{queue}` gauge and fires
    an edge-triggered `datapath_backpressure` event (plus counter) when
    occupancy first crosses the high watermark; it re-arms once depth
    falls back below the mark, so a saturated queue costs one event per
    excursion, not one per put."""

    def __init__(self, name, capacity=None, datapath=None):
        self.name = name
        if capacity is None:
            capacity = knobs.get_int(QUEUE_CAPACITY_ENV)
        self.capacity = int(capacity) if capacity else 0
        ratio = knobs.get_float(QUEUE_WATERMARK_ENV)
        self._mark = (
            self.capacity * ratio if self.capacity and ratio > 0 else 0
        )
        self._armed = True
        self._dp = datapath
        self._gauge = _QUEUE_DEPTH.labels(queue=name)
        self._counter = _BACKPRESSURE.labels(queue=name)

    def depth(self, d):
        dp = self._dp if self._dp is not None else get()
        if not dp.enabled:
            return
        self._gauge.set(d)
        if not self._mark:
            return
        if d >= self._mark:
            if self._armed:
                self._armed = False
                self._counter.inc()
                emit_event(
                    "datapath_backpressure",
                    queue=self.name,
                    depth=int(d),
                    capacity=self.capacity,
                )
        else:
            self._armed = True


_singleton = None
_singleton_lock = threading.Lock()


def get():
    """The process-global Datapath instance (created on first use, so
    the ELASTICDL_DATAPATH gate is read after the process environment is
    fully set up)."""
    global _singleton
    if _singleton is None:
        with _singleton_lock:
            if _singleton is None:
                _singleton = Datapath()
    return _singleton
