"""Deep profiling plane, part 1: compile accounting + on-demand device
profiles.

The observability arc so far sees the job from the outside (RPC spans,
scraped metrics, push phase splits) but is blind below the JAX boundary.
This module opens that boundary in two ways:

Compile tracker
    `tracked_jit(fn, name=...)` replaces every direct `jax.jit`/`pjit`
    in the worker/parallel/ps trainer paths (the `compile-tracker` lint
    rule enforces the replacement). The wrapper keys each call on the
    (shape, dtype) signature of its arguments plus the current *mesh
    fingerprint* (`note_mesh()`, stamped by the elastic trainer on every
    world change) and attributes each lowering to a cause:

        cold           first compile of this step function ever
        mesh_change    the mesh/world fingerprint moved since the last
                       compile (elastic regroup re-lowering the step)
        shape_change   same mesh, new argument shapes (ragged batch,
                       new eval shape)
        rebuild        a rebuilt jit object re-lowering a signature this
                       process already compiled (checkpoint restore,
                       forward rebuild)
        donation_miss  XLA's own cache grew on an already-seen signature
                       (donation/weak-type/tree retrace) — the silent
                       recompile class the wrapper exists to surface

    Each compile lands in three places: `edl_compile_total{fn,cause}` /
    `edl_compile_seconds_total{fn,cause}` counters, a `compile` event in
    events.jsonl, and a `compile:<fn>` span (cat "compile") in the trace
    — so a regroup's recompile stall is visible in the merged timeline,
    not just as a mysteriously slow step. Compile seconds come from
    jax.monitoring's real compile-phase durations when the runtime emits
    them (this jax does), with the first-call wall time as the fallback
    and always recorded alongside in the event.

On-demand device profiles
    `capture_device_profile(seconds, out_dir)` wraps
    `jax.profiler.start_trace`/`stop_trace` behind a process-wide lock;
    the exporter serves it as `GET /debug/profile?seconds=N` on every
    role, and the master's `StartProfile` RPC fans the HTTP call out to
    every advertised endpoint — so any running role can be profiled
    without a restart, writing into the job's obs dir.

Everything is cheap until it fires: a warm-cache tracked call costs one
shape-key hash and one C++ cache-size read. ELASTICDL_COMPILE_TRACKER=0
degrades tracked_jit to a plain jax.jit.
"""

import json
import os
import threading
import time

from elasticdl_tpu.common import knobs
from elasticdl_tpu.common.log_utils import get_logger
from elasticdl_tpu.observability import events as _events
from elasticdl_tpu.observability import tracing
from elasticdl_tpu.observability.metrics import default_registry

logger = get_logger("observability.profiling")

TRACKER_ENV = "ELASTICDL_COMPILE_TRACKER"
PROFILE_MAX_SECONDS_ENV = "ELASTICDL_PROFILE_MAX_SECONDS"

CAUSE_COLD = "cold"
CAUSE_MESH = "mesh_change"
CAUSE_SHAPE = "shape_change"
CAUSE_REBUILD = "rebuild"
CAUSE_DONATION = "donation_miss"

_REG = default_registry()
_C_COMPILES = _REG.counter(
    "edl_compile_total",
    "Tracked step-function lowerings, by function and attributed cause",
    labelnames=("fn", "cause"),
)
_C_COMPILE_SECONDS = _REG.counter(
    "edl_compile_seconds_total",
    "Seconds spent compiling tracked step functions, by function and "
    "cause (jax.monitoring compile phases when available, else the "
    "first-call wall time)",
    labelnames=("fn", "cause"),
)
_G_LAST_COMPILE = _REG.gauge(
    "edl_compile_last_seconds",
    "Duration of the most recent tracked compile",
)
_C_CACHE_HITS = _REG.counter(
    "edl_compile_cache_hits_total",
    "Tracked lowerings fully served by the persistent compilation "
    "cache (rehydrated executables, by function and the cause the "
    "compile would have had)",
    labelnames=("fn", "cause"),
)

# jax.monitoring event keys that cover a lowering's host-side cost on
# this runtime (trace -> MLIR -> backend compile).
_COMPILE_EVENT_PREFIXES = (
    "/jax/core/compile/",
    "/jax/pjit/",
)

# Persistent-compilation-cache outcome events (common/compile_cache.py
# wires the cache): a lowering whose every backend compile was served
# from disk is a REHYDRATION, not a compile — it lands as a
# `compile_cache_hit` event + edl_compile_cache_hits_total, and does NOT
# count toward edl_compile_total (so "mesh_change stays flat during a
# warm-cache worker-kill drill" is assertable directly on the counter).
_CACHE_HIT_EVENT = "/jax/compilation_cache/cache_hits"
_CACHE_MISS_EVENT = "/jax/compilation_cache/cache_misses"


def tracker_enabled():
    return knobs.get_str(TRACKER_ENV).lower() not in ("0", "false", "off")


# ---------------------------------------------------------------------------
# mesh fingerprint
# ---------------------------------------------------------------------------

_mesh_lock = threading.Lock()
_mesh_token = ""
_mesh_world = 0


def note_mesh(token, world_size=0):
    """Stamp the current mesh/world fingerprint. The elastic trainer
    calls this on every world change (token = mesh axes + membership
    epoch), so the next lowering of any tracked function is attributed
    to the regroup instead of reading as a random shape change."""
    global _mesh_token, _mesh_world
    with _mesh_lock:
        _mesh_token = str(token)
        _mesh_world = int(world_size)


def current_mesh():
    with _mesh_lock:
        return _mesh_token, _mesh_world


# ---------------------------------------------------------------------------
# jax.monitoring capture
# ---------------------------------------------------------------------------

_capture = threading.local()  # .sink: list to append (key, secs) into
_listener_installed = False
_listener_lock = threading.Lock()


def _on_event_duration(name, secs, **kw):
    sink = getattr(_capture, "sink", None)
    if sink is None:
        return
    if name.startswith(_COMPILE_EVENT_PREFIXES):
        sink.append((name, float(secs)))


def _on_event(name, **kw):
    sink = getattr(_capture, "events", None)
    if sink is None:
        return
    if name in (_CACHE_HIT_EVENT, _CACHE_MISS_EVENT):
        sink.append(name)


def _install_listener():
    """Register the process-wide jax.monitoring listeners once (lazily,
    so importing this module never imports jax)."""
    global _listener_installed
    with _listener_lock:
        if _listener_installed:
            return
        try:
            import jax.monitoring

            jax.monitoring.register_event_duration_secs_listener(
                _on_event_duration
            )
            jax.monitoring.register_event_listener(_on_event)
            _listener_installed = True
        except Exception:  # unexpected runtime without monitoring
            _listener_installed = True  # don't retry every call


class _MonitoringCapture:
    """Collects this thread's compile-phase durations (and persistent-
    cache outcome events) around one call."""

    def __enter__(self):
        self._prev = getattr(_capture, "sink", None)
        self._prev_events = getattr(_capture, "events", None)
        self.samples = []
        self.cache_events = []
        _capture.sink = self.samples
        _capture.events = self.cache_events
        return self

    def __exit__(self, *exc):
        _capture.sink = self._prev
        _capture.events = self._prev_events
        return False

    def compile_seconds(self):
        return sum(secs for _, secs in self.samples)

    def persistent_cache_hit(self):
        """True when the persistent compilation cache served EVERY
        backend compile of this call (one jit call can compile several
        subprograms; a single miss means real compile work happened)."""
        hits = self.cache_events.count(_CACHE_HIT_EVENT)
        misses = self.cache_events.count(_CACHE_MISS_EVENT)
        return hits > 0 and misses == 0


# ---------------------------------------------------------------------------
# compile tracker
# ---------------------------------------------------------------------------


class _FnHistory:
    """Process-global per-logical-name compile history (survives wrapper
    rebuilds, which happen on every elastic regroup / restore)."""

    __slots__ = ("compiled_once", "last_mesh_token", "sigs")

    def __init__(self):
        self.compiled_once = False
        self.last_mesh_token = None
        self.sigs = set()  # (mesh_token, shape_sig) ever compiled


class CompileTracker:
    """Counts and times lowerings of tracked functions; process-global."""

    def __init__(self):
        self._lock = threading.Lock()
        self._history = {}  # name -> _FnHistory
        self._events = []  # bounded recent-compile list for reports
        self._events_cap = 256
        self.total_compiles = 0
        self.total_seconds = 0.0
        self.peak_seconds = 0.0  # longest single compile observed
        self.by_cause = {}

    def classify_locked(self, name, sig, mesh_token):
        hist = self._history.get(name)
        if hist is None:
            hist = self._history[name] = _FnHistory()
        if not hist.compiled_once:
            return hist, CAUSE_COLD
        if (mesh_token, sig) in hist.sigs:
            return hist, CAUSE_REBUILD
        if hist.last_mesh_token != mesh_token:
            return hist, CAUSE_MESH
        return hist, CAUSE_SHAPE

    def record(self, name, cause, seconds, wall_seconds, sig=None,
               mesh_token="", cache_hit=False, event_fields=None):
        """One observed compile: metrics + event + recent-report entry.
        The trace span is recorded by the caller (it owns the start
        timestamp). `event_fields` are what the builder of this jit said
        about its form (`tracked_jit(event_fields=...)`, e.g. the sharded
        step's `dp_overlap` and `update_apart`); they ride on the event as
        they are. `cache_hit=True` means the persistent compilation
        cache rehydrated the executable: the lowering updates the
        classification history (later re-lowerings of the same signature
        still read as rebuilds) but lands as a `compile_cache_hit` event
        and counter instead of a compile — it neither moves
        edl_compile_total nor widens the peak-compile floor timeouts
        derive from."""
        with self._lock:
            hist = self._history.get(name)
            if hist is None:
                hist = self._history[name] = _FnHistory()
            hist.compiled_once = True
            hist.last_mesh_token = mesh_token
            if sig is not None:
                hist.sigs.add((mesh_token, sig))
            entry = {
                "ts": time.time(),
                "fn": name,
                "cause": cause,
                "seconds": round(seconds, 4),
            }
            if cache_hit:
                entry["cache_hit"] = True
            else:
                self.total_compiles += 1
                self.total_seconds += seconds
                self.peak_seconds = max(self.peak_seconds, seconds)
                self.by_cause[cause] = self.by_cause.get(cause, 0) + 1
            self._events.append(entry)
            del self._events[: -self._events_cap]
        world = current_mesh()[1]
        event_fields = event_fields or {}
        if cache_hit:
            _C_CACHE_HITS.labels(fn=name, cause=cause).inc()
            _events.emit(
                "compile_cache_hit",
                fn=name,
                cause=cause,
                seconds=round(seconds, 4),
                world_size=world,
                **event_fields,
            )
            return
        _C_COMPILES.labels(fn=name, cause=cause).inc()
        _C_COMPILE_SECONDS.labels(fn=name, cause=cause).inc(seconds)
        _G_LAST_COMPILE.set(seconds)
        _events.emit(
            "compile",
            fn=name,
            cause=cause,
            seconds=round(seconds, 4),
            first_call_seconds=round(wall_seconds, 4),
            world_size=world,
            **event_fields,
        )

    def snapshot(self):
        """(total_compiles, total_seconds, by_cause) — runner/report
        consumers diff two snapshots to attribute recompile time to one
        window."""
        with self._lock:
            return (
                self.total_compiles,
                self.total_seconds,
                dict(self.by_cause),
            )

    def recent(self, n=32):
        with self._lock:
            return list(self._events[-n:])


_tracker = CompileTracker()


def tracker():
    return _tracker


def peak_compile_seconds():
    """The longest single compile this process has observed (0.0 before
    any). Timeouts that must outlast a peer's recompile — the elastic
    join gate above all — derive their floor from this instead of
    guessing a constant."""
    with _tracker._lock:
        return _tracker.peak_seconds


class TrackedFunction:
    """A jitted callable that reports its own lowerings.

    Forwards the AOT surface (`lower`, `_cache_size`, ...) to the
    underlying jitted function so MFU cost analysis and the benches keep
    working against the wrapped object.
    """

    def __init__(self, jitted, name, key_argnums=None, event_fields=None):
        self._jitted = jitted
        self._name = name
        self._key_argnums = key_argnums
        self._event_fields = event_fields
        self._seen = set()
        self._expected_cache = 0

    # -- forwarding --

    @property
    def __wrapped__(self):
        return self._jitted

    def __getattr__(self, item):
        return getattr(self.__dict__["_jitted"], item)

    def lower(self, *args, **kw):
        return self._jitted.lower(*args, **kw)

    # -- signature --

    def _sig(self, args, kwargs):
        import jax

        if self._key_argnums is not None:
            args = tuple(args[i] for i in self._key_argnums)
        leaves = jax.tree_util.tree_leaves(args)
        if kwargs:
            # Keyword args (legal on any jitted callable) always join
            # the signature — key_argnums only narrows the positionals.
            leaves += jax.tree_util.tree_leaves(
                tuple(kwargs[k] for k in sorted(kwargs))
            )
        return tuple(
            (
                tuple(getattr(l, "shape", ())),
                str(getattr(l, "dtype", type(l).__name__)),
            )
            for l in leaves
        )

    def _observed_cache_size(self):
        try:
            return int(self._jitted._cache_size())
        except Exception:
            return None

    def __call__(self, *args, **kwargs):
        try:
            sig = self._sig(args, kwargs)
        except Exception:
            return self._jitted(*args, **kwargs)
        mesh_token = current_mesh()[0]
        key = (mesh_token, sig)
        predicted = key not in self._seen
        if not predicted:
            # Warm path: one dict probe + one C++ cache-size read; the
            # cache-size check is what surfaces silent retraces.
            out = self._jitted(*args, **kwargs)
            size = self._observed_cache_size()
            if size is not None and size > self._expected_cache:
                extra = size - self._expected_cache
                self._expected_cache = size
                for _ in range(extra):
                    _tracker.record(
                        self._name, CAUSE_DONATION, 0.0, 0.0,
                        mesh_token=mesh_token,
                        event_fields=self._event_fields,
                    )
            return out
        _install_listener()
        start = time.time()
        t0 = time.perf_counter()
        with _MonitoringCapture() as cap, open_compile(self._name):
            out = self._jitted(*args, **kwargs)
        wall = time.perf_counter() - t0
        self._seen.add(key)
        first_call = getattr(self, "_first_call", None)
        if first_call:
            # The set-up phase round a newly built program's first call:
            # tracing, the compile or the cache load (the `compile` /
            # `compile_cache_hit` event below is its child), the enqueue.
            tracing.record_span(
                first_call, start, wall, cat=tracing.SETUP,
                args={"fn": self._name},
            )
        size = self._observed_cache_size()
        if size is not None:
            if size == self._expected_cache:
                # The underlying cache did not grow: jax already had the
                # executable (cannot happen for a fresh jit object, but a
                # shared one stays honest here) — no compile to record.
                return out
            self._expected_cache = size
        compile_s = cap.compile_seconds() or wall
        cache_hit = cap.persistent_cache_hit()
        with _tracker._lock:
            _, cause = _tracker.classify_locked(
                self._name, sig, mesh_token
            )
        _tracker.record(
            self._name, cause, compile_s, wall, sig=sig,
            mesh_token=mesh_token, cache_hit=cache_hit,
            event_fields=self._event_fields,
        )
        tracing.record_span(
            f"compile:{self._name}", start, wall, cat="compile",
            args={
                "cause": cause,
                "compile_s": round(compile_s, 4),
                **({"persistent_cache": "hit"} if cache_hit else {}),
            },
        )
        if compile_s > 0.5:
            logger.info(
                "Compiled %s in %.2fs (cause=%s, wall %.2fs)",
                self._name, compile_s, cause, wall,
            )
        return out


class open_compile:
    """Context manager: lists `name` (a tracked function's, or
    `speculative_compile`) among the compiles in flight in this process
    meanwhile. A compile is known as a span only once it returns; the
    step-done clock reads `open_compiles()` when the device runs dry, to
    say which one the host was inside. Defined down here, and entered on
    the line that was there: `TrackedFunction`'s line numbers are in
    every traced step's Mosaic payload (the kernels' debug locations
    hold the whole call stack), and so in the compile cache's key."""

    _open = {}  # {the manager: (name, start)}

    def __init__(self, name):
        self._name = name

    def __enter__(self):
        open_compile._open[self] = (self._name, time.time())
        return self

    def __exit__(self, *exc):
        open_compile._open.pop(self, None)
        return False


def open_compiles():
    """[{"name", "age_s"}] of the compiles still running, oldest first."""
    now = time.time()
    return [
        {"name": name, "age_s": round(now - start, 3)}
        for name, start in sorted(
            list(open_compile._open.values()), key=lambda e: e[1]
        )
    ]


def tracked_jit(fn, *, name, key_argnums=None, event_fields=None,
                first_call=None, **jit_kwargs):
    """`jax.jit` with compile accounting. `name` is the logical step
    name the metrics/events carry (stable across rebuilds); `key_argnums`
    restricts the per-call shape signature to the argument positions
    that actually vary (trainers pass the batch so the hot path never
    flattens the parameter tree); `event_fields` is a dict the `compile`
    / `compile_cache_hit` events of this function carry beside their own
    fields (which form the builder took), at no cost to a warm call;
    `first_call` names the set-up phase (`setup.*`) recorded round the
    first call of each new signature or mesh."""
    import jax

    jitted = jax.jit(fn, **jit_kwargs)
    if not tracker_enabled():
        return jitted
    tracked = TrackedFunction(
        jitted, name, key_argnums=key_argnums, event_fields=event_fields
    )
    # Set from here, not through the constructor: see `open_compile`.
    tracked._first_call = first_call
    return tracked


# ---------------------------------------------------------------------------
# on-demand device profiles
# ---------------------------------------------------------------------------

_profile_lock = threading.Lock()


def capture_device_profile(seconds, out_dir):
    """Capture a jax.profiler trace of this process for `seconds` into a
    timestamped subdirectory of `out_dir`. Returns a JSON-able summary
    {dir, files, bytes, seconds}; raises RuntimeError when a capture is
    already running (the profiler is process-global)."""
    import jax.profiler

    import math

    seconds = float(seconds)
    if not math.isfinite(seconds):
        # ?seconds=inf parses as a float; sleeping on it would wedge
        # the process-wide capture lock until restart.
        raise ValueError(f"seconds must be finite, got {seconds!r}")
    cap = knobs.get_float(PROFILE_MAX_SECONDS_ENV)
    seconds = max(0.1, min(seconds, cap) if cap else seconds)
    if not _profile_lock.acquire(blocking=False):
        raise RuntimeError("a device profile capture is already running")
    try:
        stamp = time.strftime("%Y%m%d-%H%M%S")
        target = os.path.join(out_dir, f"profile-{stamp}-{os.getpid()}")
        os.makedirs(target, exist_ok=True)
        _events.emit("profile_start", dir=target, seconds=seconds)
        jax.profiler.start_trace(target)
        # The program's spans land in this trace too while it is open.
        tracing.set_profiler_session(True)
        try:
            time.sleep(seconds)
        finally:
            tracing.set_profiler_session(False)
            jax.profiler.stop_trace()
        # Which scope every instruction of the training step belongs to,
        # beside the trace it names them in (a worker; else nothing).
        from elasticdl_tpu.observability import step_scopes

        step_scopes.write_for_running_step(target)
        files, total = [], 0
        for root, _, names in os.walk(target):
            for n in names:
                p = os.path.join(root, n)
                files.append(os.path.relpath(p, target))
                total += os.path.getsize(p)
        summary = {
            "dir": target,
            "files": sorted(files),
            "bytes": total,
            "seconds": seconds,
        }
        _events.emit(
            "profile_done", dir=target, bytes=total, files=len(files)
        )
        return summary
    finally:
        _profile_lock.release()


def profile_provider(obs_dir, role):
    """The callable observability.setup() hands the exporter for
    /debug/profile: captures into <obs_dir>/profiles/<role>/."""
    base = os.path.join(obs_dir or ".", "profiles", role or "process")

    def provider(seconds):
        return capture_device_profile(seconds, base)

    return provider


def fanout_profiles(endpoints, seconds, timeout_margin=20.0):
    """Hit every advertised endpoint's /debug/profile concurrently
    (the master's StartProfile RPC body). Returns {role: result-dict};
    failures land as {"error": ...} per role, never an exception."""
    import urllib.request

    results = {}
    lock = threading.Lock()

    def one(info):
        role = info.get("role", "?")
        host = info.get("host") or "127.0.0.1"
        url = (
            f"http://{host}:{info['port']}/debug/profile"
            f"?seconds={seconds:g}"
        )
        try:
            body = urllib.request.urlopen(
                url, timeout=seconds + timeout_margin
            ).read()
            out = json.loads(body.decode())
        except Exception as e:
            out = {"error": str(e)[:200]}
        with lock:
            results[role] = out

    threads = [
        threading.Thread(target=one, args=(info,), daemon=True)
        for info in endpoints
        if info.get("port")
    ]
    for t in threads:
        t.start()
    for t in threads:
        t.join(timeout=seconds + timeout_margin + 5)
    return results
