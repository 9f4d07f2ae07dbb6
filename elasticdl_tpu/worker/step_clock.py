"""A step-done clock that needs no loss fence.

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
"""

import queue
import threading
import time

from elasticdl_tpu.observability import emit_event
from elasticdl_tpu.observability.metrics import default_registry

_STEPS_DONE = default_registry().counter(
    "edl_worker_steps_done_total",
    "Minibatch steps whose result has left the device",
)

EMIT_INTERVAL_SECONDS = 1.0


class StepDoneClock:
    def __init__(self, emit_interval=EMIT_INTERVAL_SECONDS):
        self._emit_interval = emit_interval
        self._queue = queue.SimpleQueue()
        self._thread = None
        # Touched by the clock's thread only.
        self._first_step = None
        self._stamps = []
        self._last_emit = 0.0

    def dispatched(self, step, loss):
        """Step number `step` was just dispatched; `loss` is its (lazy)
        scalar loss. Starts the thread with the first step."""
        if self._thread is None:
            self._thread = threading.Thread(
                target=self._run, name="edl-step-done", daemon=True
            )
            self._thread.start()
        self._queue.put((step, loss))

    def close(self, timeout=10.0):
        """Stamp what is queued, write the last event, end the thread."""
        thread, self._thread = self._thread, None
        if thread is None:
            return
        self._queue.put(None)
        thread.join(timeout)

    def _run(self):
        while True:
            item = self._queue.get()
            if item is None:
                self._flush()
                return
            step, loss = item
            try:
                wait = getattr(loss, "block_until_ready", None)
                if wait is not None:
                    wait()
            except Exception:
                # The step failed on the device (the dispatch loop sees
                # the same error at its next fence): no stamp, and the
                # run of consecutive steps ends here.
                self._flush()
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
            if now - self._last_emit >= self._emit_interval:
                self._flush()

    def _flush(self):
        if self._stamps:
            emit_event(
                "steps_done", first_step=self._first_step, stamps=self._stamps
            )
        self._first_step, self._stamps = None, []
        self._last_emit = time.time()
