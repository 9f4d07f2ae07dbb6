"""Cross-process tracing: Chrome-trace JSONL spans + gRPC context propagation.

Each process appends complete ("ph": "X") events to its own
`trace_<role>.jsonl`; `tools/trace_report.py` merges the per-process files
into one Chrome-trace JSON loadable in Perfetto / chrome://tracing. The
trace "pid" is a stable hash of the process's role string — NOT the OS pid —
so the master / each PS / each worker get distinct, deterministic process
rows even when a test hosts several roles inside one interpreter.

Trace context is a contextvar carrying (trace_id, span_id, job, task_id,
lease_epoch). The client interceptor injects it into gRPC metadata
(`edl-trace-*` keys); the server interceptor extracts it and runs the
handler under it, so one task's dispatch -> pull -> train -> push -> report
chain shares a trace id across every process it touches. Propagation is
always on (a few string pairs per RPC); recording costs nothing until
observability.setup() installs a recorder.
"""

import contextvars
import json
import os
import threading
import time
import uuid
import zlib

import grpc

from elasticdl_tpu.observability import events as _events
from elasticdl_tpu.observability.metrics import default_registry

# Metadata keys must be lowercase in gRPC.
_MD_TRACE = "edl-trace-id"
_MD_PARENT = "edl-parent-span"
_MD_TASK = "edl-task-id"
_MD_EPOCH = "edl-lease-epoch"
_MD_JOB = "edl-job"

_context = contextvars.ContextVar("edl_trace_context", default=None)

_recorder = None

# Secondary span consumers (the flight recorder). Sinks receive every
# span the plane observes — (name, start_s, dur_s, cat, args) — even
# when no file recorder is installed, and must be cheap + non-raising.
_sinks = []


def add_sink(sink):
    if sink not in _sinks:
        _sinks.append(sink)


def remove_sink(sink):
    if sink in _sinks:
        _sinks.remove(sink)


def _feed_sinks(name, start_s, dur_s, cat, args):
    for sink in list(_sinks):
        try:
            sink(name, start_s, dur_s, cat, args)
        except Exception:
            pass


# The category of a set-up phase: a span opened with `cat=SETUP` (named
# `setup.<phase>`, docs/OBSERVABILITY.md) is also written to the event
# log when it closes, as one `setup_phase` event, because the event log
# is what the benchmark, `edl top` and a kill drill read. A span of any
# other category pays one comparison for this. Some thirty a process
# life, none on the step path.
SETUP = "setup"

_SETUP_SECONDS = default_registry().gauge(
    "edl_setup_phase_seconds",
    "Seconds the newest run of each set-up phase of this process took",
    labelnames=("phase",),
)


def _setup_phase(name, start_s, dur_s, args):
    _SETUP_SECONDS.labels(phase=name).set(dur_s)
    fields = {
        k: v for k, v in (args or {}).items()
        if isinstance(v, (str, int, float, bool))
    }
    _events.emit(
        "setup_phase", name=name, start=round(start_s, 6),
        seconds=round(dur_s, 6), **fields,
    )


class TraceContext:
    __slots__ = ("trace_id", "span_id", "job", "task_id", "lease_epoch")

    def __init__(
        self, trace_id=None, span_id="", job="", task_id=-1, lease_epoch=-1
    ):
        self.trace_id = trace_id or uuid.uuid4().hex[:16]
        self.span_id = span_id
        self.job = job
        self.task_id = task_id
        self.lease_epoch = lease_epoch

    def args(self):
        out = {"trace_id": self.trace_id}
        if self.job:
            out["job"] = self.job
        if self.task_id >= 0:
            out["task_id"] = self.task_id
        if self.lease_epoch >= 0:
            out["lease_epoch"] = self.lease_epoch
        return out


def set_context(task_id=None, lease_epoch=None, job=None, trace_id=None):
    """Create/refresh this thread's trace context; returns it. Starting a
    new task (task_id given, different from the current one) mints a new
    trace id so each task forms its own trace tree."""
    ctx = _context.get()
    if ctx is None or (
        trace_id is not None and trace_id != ctx.trace_id
    ) or (
        task_id is not None and task_id != ctx.task_id
    ):
        ctx = TraceContext(
            trace_id=trace_id,
            job=job if job is not None else (ctx.job if ctx else ""),
            task_id=task_id if task_id is not None else -1,
            lease_epoch=(
                lease_epoch
                if lease_epoch is not None
                else (ctx.lease_epoch if ctx else -1)
            ),
        )
        _context.set(ctx)
        return ctx
    if job is not None:
        ctx.job = job
    if lease_epoch is not None:
        ctx.lease_epoch = lease_epoch
    return ctx


def clear_context():
    _context.set(None)


def role_pid(role):
    """Deterministic per-role trace pid (distinct process rows in the
    merged trace even when several roles share one OS process)."""
    return zlib.crc32(role.encode()) & 0x7FFFFFF


class SpanRecorder:
    """Appends Chrome-trace events to a JSONL file; thread-safe.
    Size-capped (observability/rotation.py): a rotated generation keeps
    the previous cap's worth of spans as <path>.1 and re-stamps the
    process-name metadata plus a `rotated` marker so the fresh file is
    independently loadable in Perfetto."""

    def __init__(self, path, process_name, max_bytes=None):
        from elasticdl_tpu.observability.rotation import SizeCappedFile

        self.path = path
        self.process_name = process_name
        self.pid = role_pid(process_name)
        self._lock = threading.Lock()
        os.makedirs(os.path.dirname(path) or ".", exist_ok=True)
        self._file = SizeCappedFile(
            path, max_bytes=max_bytes, on_rotate=self._on_rotate
        )
        # Perfetto reads process names from this metadata event.
        self._write(self._process_meta())

    def _process_meta(self):
        return {
            "ph": "M",
            "name": "process_name",
            "pid": self.pid,
            "tid": 0,
            "args": {"name": self.process_name},
        }

    def _on_rotate(self, generation):
        # Runs under self._lock mid-write (rotation.py callback): these
        # are the new generation's first lines.
        for event in (
            self._process_meta(),
            {
                "ph": "i",
                "s": "p",
                "name": "rotated",
                "cat": "edl",
                "ts": round(time.time() * 1e6, 1),
                "pid": self.pid,
                "tid": 0,
                "args": {"generation": generation},
            },
        ):
            self._file.append_line(
                json.dumps(event, separators=(",", ":"))
            )

    def _write(self, event):
        line = json.dumps(event, separators=(",", ":"))
        with self._lock:
            if self._file.closed:
                return
            self._file.write_line(line)

    def record(self, name, start_s, dur_s, cat="edl", args=None):
        """One complete span; times in seconds (perf-epoch: time.time)."""
        ctx = _context.get()
        merged = ctx.args() if ctx is not None else {}
        if args:
            merged.update(args)
        self._write(
            {
                "ph": "X",
                "name": name,
                "cat": cat,
                "ts": round(start_s * 1e6, 1),
                "dur": round(dur_s * 1e6, 1),
                "pid": self.pid,
                "tid": threading.get_ident() & 0xFFFF,
                "args": merged,
            }
        )

    def instant(self, name, cat="edl", args=None):
        ctx = _context.get()
        merged = ctx.args() if ctx is not None else {}
        if args:
            merged.update(args)
        self._write(
            {
                "ph": "i",
                "s": "p",
                "name": name,
                "cat": cat,
                "ts": round(time.time() * 1e6, 1),
                "pid": self.pid,
                "tid": threading.get_ident() & 0xFFFF,
                "args": merged,
            }
        )

    def close(self):
        with self._lock:
            if not self._file.closed:
                self._file.close()


def set_recorder(recorder):
    global _recorder
    _recorder = recorder


def get_recorder():
    return _recorder


# (TraceAnnotation, StepTraceAnnotation) while a jax.profiler session is
# open in this process, else None. Set by whoever calls start_trace /
# stop_trace; never imported here (the master imports this module and
# stays jax-free).
_annotations = None


def set_profiler_session(is_open):
    """Tell span() whether a jax.profiler session is open in this
    process. Called by the owner of the session right after start_trace
    and right before stop_trace."""
    global _annotations
    if is_open:
        from jax.profiler import StepTraceAnnotation, TraceAnnotation

        _annotations = (TraceAnnotation, StepTraceAnnotation)
    else:
        _annotations = None


class span:
    """Mark one boundary: `with tracing.span(name, cat, **args) as s:`.

    One clock pair (`time.time`), whoever listens: after the body
    `s.start` and `s.dur` (seconds) are set, so the caller feeds its own
    counters from the same pair. The span goes to the JSONL recorder and
    the sinks when they are installed, and, while a jax.profiler session
    is open in this process, also onto the calling thread's line of the
    profiler's host plane — the device trace's own clock — as a
    TraceAnnotation (a StepTraceAnnotation when `step_num` is among the
    args). The body's exceptions propagate and the span still closes."""

    __slots__ = ("name", "cat", "args", "start", "dur", "_annotation")

    def __init__(self, name, cat="edl", **args):
        self.name = name
        self.cat = cat
        self.args = args
        self.start = self.dur = 0.0
        self._annotation = None

    def __enter__(self):
        annotations = _annotations
        if annotations is not None:
            if "step_num" in self.args:
                self._annotation = annotations[1](
                    self.name, step_num=self.args["step_num"]
                )
            else:
                self._annotation = annotations[0](self.name)
            self._annotation.__enter__()
        self.start = time.time()
        return self

    def __exit__(self, exc_type, exc, tb):
        self.dur = time.time() - self.start
        if self._annotation is not None:
            self._annotation.__exit__(exc_type, exc, tb)
        rec = _recorder
        if rec is not None:
            rec.record(
                self.name, self.start, self.dur, cat=self.cat,
                args=self.args,
            )
        if _sinks:
            _feed_sinks(self.name, self.start, self.dur, self.cat, self.args)
        if self.cat == SETUP:
            _setup_phase(self.name, self.start, self.dur, self.args)
        return False


def record_span(name, start_s, dur_s, cat="edl", args=None):
    """Record an already-measured span (recorder + sinks). For callers
    that time the interval themselves — e.g. the compile tracker, which
    only knows a call was a compile once it returns, or a set-up phase
    that began before this module was imported."""
    rec = _recorder
    if rec is not None:
        rec.record(name, start_s, dur_s, cat=cat, args=args)
    _feed_sinks(name, start_s, dur_s, cat, args)
    if cat == SETUP:
        _setup_phase(name, start_s, dur_s, args)


def instant(name, cat="edl", **args):
    rec = _recorder
    if rec is not None:
        rec.instant(name, cat=cat, args=args)


# ---------- gRPC propagation ----------


def _inject(metadata):
    ctx = _context.get()
    if ctx is None:
        return metadata
    extra = [(_MD_TRACE, ctx.trace_id)]
    if ctx.span_id:
        extra.append((_MD_PARENT, ctx.span_id))
    if ctx.job:
        extra.append((_MD_JOB, ctx.job))
    if ctx.task_id >= 0:
        extra.append((_MD_TASK, str(ctx.task_id)))
    if ctx.lease_epoch >= 0:
        extra.append((_MD_EPOCH, str(ctx.lease_epoch)))
    return list(metadata or ()) + extra


def context_from_metadata(metadata):
    """TraceContext extracted from invocation metadata, or None."""
    md = {k: v for k, v in (metadata or ())}
    trace_id = md.get(_MD_TRACE)
    if trace_id is None:
        return None
    return TraceContext(
        trace_id=trace_id,
        span_id=md.get(_MD_PARENT, ""),
        job=md.get(_MD_JOB, ""),
        task_id=int(md.get(_MD_TASK, -1)),
        lease_epoch=int(md.get(_MD_EPOCH, -1)),
    )


class _ClientCallDetails(grpc.ClientCallDetails):
    def __init__(self, base, metadata):
        self.method = base.method
        self.timeout = base.timeout
        self.metadata = metadata
        self.credentials = base.credentials
        self.wait_for_ready = getattr(base, "wait_for_ready", None)
        self.compression = getattr(base, "compression", None)


class TracingClientInterceptor(grpc.UnaryUnaryClientInterceptor):
    """Injects the caller's trace context and records a client span."""

    def intercept_unary_unary(self, continuation, details, request):
        new_details = _ClientCallDetails(
            details, _inject(details.metadata)
        )
        rec = _recorder
        if rec is None and not _sinks:
            return continuation(new_details, request)
        start = time.time()
        call = continuation(new_details, request)

        # Record at response time so the span covers the full RPC. Futures
        # returned by stub.method.future() are recorded when they resolve.
        def done(c, s=start):
            dur = time.time() - s
            args = {"code": str(c.code())}
            if rec is not None:
                rec.record(
                    f"rpc_client{details.method}", s, dur, cat="rpc",
                    args=args,
                )
            _feed_sinks(
                f"rpc_client{details.method}", s, dur, "rpc", args
            )

        call.add_done_callback(done)
        return call


class TracingServerInterceptor(grpc.ServerInterceptor):
    """Runs each handler under the caller's propagated trace context and
    records a server span."""

    def intercept_service(self, continuation, handler_call_details):
        handler = continuation(handler_call_details)
        if handler is None or handler.unary_unary is None:
            return handler
        inner = handler.unary_unary
        method = handler_call_details.method

        def traced(request, context):
            ctx = context_from_metadata(
                context.invocation_metadata()
            )
            token = None
            if ctx is not None:
                token = _context.set(ctx)
            try:
                rec = _recorder
                if rec is None and not _sinks:
                    return inner(request, context)
                start = time.time()
                try:
                    return inner(request, context)
                finally:
                    dur = time.time() - start
                    if rec is not None:
                        rec.record(
                            f"rpc_server{method}", start, dur, cat="rpc"
                        )
                    _feed_sinks(
                        f"rpc_server{method}", start, dur, "rpc", None
                    )
            finally:
                if token is not None:
                    _context.reset(token)

        return grpc.unary_unary_rpc_method_handler(
            traced,
            request_deserializer=handler.request_deserializer,
            response_serializer=handler.response_serializer,
        )
