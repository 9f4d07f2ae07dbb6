"""Speculative ahead-of-time compilation of nearby elastic worlds.

The compile tracker proved that compile IS the elastic rejoin (a
~6.5 s step re-lowering on every `mesh_change`). The unified world spec
(parallel/mesh.py) makes the fix possible: the mesh of a world this
process is NOT in yet is a pure function of (config, topology), so a
background thread can lower + compile that world's step — through
`tracked_jit`'s AOT surface (`.lower(...).compile()`) — while training
continues, and `init_world_if_needed` consumes the prebuilt executable
instead of cold-compiling when the guess lands.

Semantics the trainer relies on:

- **Non-blocking**: submit/cancel/take are lock-brief; compilation runs
  in one daemon thread. A world change mid-compile never stalls the
  step loop — it bumps the generation, and the in-flight result is
  discarded on completion (`abandoned`), since XLA compiles cannot be
  interrupted.
- **Wrong guesses are abandoned cleanly**: `cancel(keep=...)` drops
  every prebuilt executable whose spec fingerprint is not the world
  that actually formed; consuming is an exact (fingerprint, shape-key)
  match, so a stale executable can never run a wrong world's program.
- **Donation is preserved**: the executable comes from the SAME jit
  object the live path would build (`donate_argnums` captured at
  lower time), so consuming it keeps the in-place update aliasing.
- **Everything lands in the persistent cache too**: when
  the persistent compile cache is on (it is by default — see
  common/compile_cache.py), a speculative compile writes its
  disk entry even if the executable object later dies with a backend
  re-init (multi-host regroups) — the re-lowering on the other side
  rehydrates it (`compile_cache_hit`), which is how speculation helps
  worlds whose devices it cannot hold.

Outcome accounting: `edl_speculative_compiles_total{outcome}` with
outcome in {built, consumed, abandoned, failed} plus a
`speculative_compile` event per attempt.
"""

import collections
import threading
import time

from elasticdl_tpu.common import knobs
from elasticdl_tpu.common.log_utils import get_logger
from elasticdl_tpu.observability import emit_event, profiling
from elasticdl_tpu.observability.metrics import default_registry

logger = get_logger("worker.world_speculator")

SPECULATE_ENV = "ELASTICDL_AOT_SPECULATE"
AOT_WORLDS_ENV = "ELASTICDL_AOT_WORLDS"

_C_SPECULATIVE = default_registry().counter(
    "edl_speculative_compiles_total",
    "Speculative world-step compiles by outcome "
    "(built / consumed / abandoned / failed)",
    labelnames=("outcome",),
)


def speculation_enabled():
    return knobs.get_str(SPECULATE_ENV).lower() not in (
        "0", "false", "off",
    )


def world_deltas():
    """How many neighboring world sizes to guess in each direction."""
    return max(0, knobs.get_int(AOT_WORLDS_ENV))


class _Job:
    __slots__ = ("generation", "spec", "real_n")

    def __init__(self, generation, spec, real_n):
        self.generation = generation
        self.spec = spec
        self.real_n = real_n


class SpeculativeWorldCompiler:
    """Owns the background compile thread and the prebuilt-executable
    store. `plan_fn(spec, real_n)` — supplied by the trainer — returns
    `(shape_key, jitted_step, abstract_args)` for a candidate world, or
    None when that world's step cannot be planned (hook-bound paths)."""

    def __init__(self, plan_fn, max_prebuilt=8):
        self._plan_fn = plan_fn
        self._max_prebuilt = max_prebuilt
        self._lock = threading.Lock()
        self._idle = threading.Condition(self._lock)
        self._queue = collections.deque()
        self._prebuilt = collections.OrderedDict()
        # (fingerprint, shape_key) sets so one (world, shape) is only
        # ever attempted once per generation.
        self._attempted = set()
        self._generation = 0
        # Fingerprint the last cancel() kept: an in-flight compile for
        # exactly that world is stored on completion instead of being
        # discarded by the generation bump (it is the executable the
        # next step wants).
        self._keep_fp = None
        self._in_flight = False
        self._stopped = False
        self._thread = None
        self.stats = collections.Counter()

    # ---------- trainer-facing API (all lock-brief) ----------

    def submit(self, specs, real_n):
        """Queue candidate worlds for background compilation. Dedups by
        (fingerprint, real_n) within the current generation."""
        if not specs:
            return
        with self._lock:
            if self._stopped:
                return
            queued = False
            for spec in specs:
                tag = (spec.fingerprint(), real_n)
                if tag in self._attempted:
                    continue
                self._attempted.add(tag)
                self._queue.append(
                    _Job(self._generation, spec, real_n)
                )
                queued = True
            if queued:
                self._ensure_thread_locked()
                self._idle.notify_all()

    def cancel(self, keep_fingerprint=None):
        """The world changed: drop queued guesses and prebuilt
        executables that are not `keep_fingerprint`, and invalidate any
        in-flight compile (its result is discarded on completion —
        unless it is for `keep_fingerprint`, the world that actually
        formed, in which case it is stored as usual). Returns
        immediately — never waits on the compile thread."""
        with self._lock:
            self._generation += 1
            self._keep_fp = keep_fingerprint
            kept_jobs = [
                j for j in self._queue
                if keep_fingerprint is not None
                and j.spec.fingerprint() == keep_fingerprint
            ]
            abandoned = len(self._queue) - len(kept_jobs)
            self._queue.clear()
            self._attempted = set()
            for job in kept_jobs:
                job.generation = self._generation
                self._queue.append(job)
                self._attempted.add(
                    (job.spec.fingerprint(), job.real_n)
                )
            for key in list(self._prebuilt):
                if key[0] != keep_fingerprint:
                    del self._prebuilt[key]
                    abandoned += 1
            self.stats["abandoned"] += abandoned
        if abandoned:
            _C_SPECULATIVE.labels(outcome="abandoned").inc(abandoned)

    def take(self, fingerprint, shape_key):
        """Pop the prebuilt executable for (world fingerprint, shape
        key), or None. Exact match only — a wrong-world guess can never
        be consumed."""
        with self._lock:
            exe = self._prebuilt.pop((fingerprint, shape_key), None)
            if exe is not None:
                self.stats["consumed"] += 1
        if exe is not None:
            _C_SPECULATIVE.labels(outcome="consumed").inc()
        return exe

    def prebuilt_keys(self):
        with self._lock:
            return list(self._prebuilt)

    def drain(self, timeout=30.0):
        """Block until no work is queued or in flight (tests/bench —
        the trainer never calls this). True when idle was reached."""
        deadline = time.time() + timeout
        with self._lock:
            while self._queue or self._in_flight:
                remaining = deadline - time.time()
                if remaining <= 0:
                    return False
                self._idle.wait(remaining)
        return True

    def stop(self):
        with self._lock:
            self._stopped = True
            self._queue.clear()
            self._prebuilt.clear()
            self._idle.notify_all()

    # ---------- the compile thread ----------

    def _ensure_thread_locked(self):
        if self._thread is None or not self._thread.is_alive():
            self._thread = threading.Thread(
                target=self._run, name="world-speculator", daemon=True
            )
            self._thread.start()

    def _run(self):
        while True:
            with self._lock:
                while not self._queue and not self._stopped:
                    self._idle.notify_all()
                    self._idle.wait()
                if self._stopped:
                    self._idle.notify_all()
                    return
                job = self._queue.popleft()
                self._in_flight = True
            try:
                self._compile_one(job)
            finally:
                with self._lock:
                    self._in_flight = False
                    self._idle.notify_all()

    def _compile_one(self, job):
        fingerprint = job.spec.fingerprint()
        start = time.perf_counter()
        outcome = "failed"
        shape_key = None
        try:
            plan = self._plan_fn(job.spec, job.real_n)
            if plan is None:
                outcome = "skipped"
                return
            shape_key, step, abstract_args = plan
            with profiling.open_compile("speculative_compile"):
                executable = step.lower(*abstract_args).compile()
            with self._lock:
                stale = job.generation != self._generation
                if self._stopped or (
                    stale and fingerprint != self._keep_fp
                ):
                    outcome = "abandoned"
                    return
                self._prebuilt[(fingerprint, shape_key)] = executable
                while len(self._prebuilt) > self._max_prebuilt:
                    self._prebuilt.popitem(last=False)
            outcome = "built"
        except Exception as e:
            logger.warning(
                "Speculative compile for world %s failed: %s",
                fingerprint, e,
            )
        finally:
            seconds = time.perf_counter() - start
            with self._lock:
                self.stats[outcome] += 1
            if outcome != "skipped":
                _C_SPECULATIVE.labels(outcome=outcome).inc()
                emit_event(
                    "speculative_compile",
                    spec=fingerprint,
                    outcome=outcome,
                    seconds=round(seconds, 4),
                    shape_key=list(shape_key) if shape_key else None,
                )
                if outcome == "built":
                    logger.info(
                        "Speculatively compiled world %s %s in %.2fs",
                        fingerprint, shape_key, seconds,
                    )
