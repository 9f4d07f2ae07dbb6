"""Per-step MFU (model FLOPs utilization) estimation for workers.

The trainer hands its jitted step function plus the step's example
arguments to a StepCostModel once per minibatch. The model:

- computes the step's FLOPs once per argument-shape signature via
  `jitted.lower(*args).compile().cost_analysis()` (an XLA estimate; the
  AOT lowering is a one-time cost per shape, cached forever after),
- measures the steady-state step period as the wall time BETWEEN
  successive observe() calls (which includes pulls/pushes/feed — MFU is
  utilization of the whole loop, not of the kernel in isolation), and
- exports `edl_worker_step_flops` and, when a peak-FLOPs figure is
  known, `edl_worker_mfu` gauges that the master's aggregator re-exports
  as `edl_job_mfu{worker=...}`.

A backend without cost_analysis or an un-lowerable step leaves the
gauges absent — never a training failure. ELASTICDL_MFU=0 disables the
lowering entirely. The peak comes from ONE table keyed by the
`device_kind` jax reports (the one table the package has); a device
that is not in it has no MFU — `peak_flops` raises, so nothing prints a
utilization against a guessed denominator.
"""

import threading
import time

from elasticdl_tpu.common import knobs
from elasticdl_tpu.common.log_utils import get_logger
from elasticdl_tpu.observability.metrics import default_registry

logger = get_logger("observability.mfu")

MFU_ENV = "ELASTICDL_MFU"

# Peak dense bf16 FLOP/s of one chip, keyed by `device.device_kind`
# exactly as jax reports it. Source: Google Cloud TPU documentation,
# "System architecture" page of each generation (v4: 275 TFLOP/s;
# v5e, reported as "TPU v5 lite": 197; v5p, reported as "TPU v5": 459;
# v6e, reported as "TPU v6 lite": 918). No override and no default: an
# unknown device is an error wherever an MFU would be printed.
PEAK_BF16_FLOPS_BY_KIND = {
    "TPU v4": 275e12,
    "TPU v5 lite": 197e12,
    "TPU v5": 459e12,
    "TPU v6 lite": 918e12,
}


class UnknownDeviceError(LookupError):
    """The device kind has no entry in PEAK_BF16_FLOPS_BY_KIND."""


def peak_flops(device_kind):
    """Peak bf16 FLOP/s of one `device_kind` chip; raises
    UnknownDeviceError for a kind the table does not list."""
    try:
        return PEAK_BF16_FLOPS_BY_KIND[device_kind]
    except KeyError:
        raise UnknownDeviceError(
            f"no peak FLOP/s known for device kind {device_kind!r}; "
            f"known: {sorted(PEAK_BF16_FLOPS_BY_KIND)}"
        ) from None


_REG = default_registry()
_STEP_FLOPS = _REG.gauge(
    "edl_worker_step_flops",
    "XLA-estimated FLOPs of one training step (current shape)",
)
_MFU = _REG.gauge(
    "edl_worker_mfu",
    "Estimated model FLOPs utilization (step flops / period / peak)",
)
_STEP_PERIOD = _REG.gauge(
    "edl_worker_step_period_seconds",
    "EWMA wall time between successive training steps",
)

_EWMA_ALPHA = 0.2


def enabled():
    """ELASTICDL_MFU: 1/true forces on, 0/false forces off; the default
    ("auto") activates only in processes that configured the
    observability plane (worker/PS/master entrypoints call setup()).
    Bare trainer construction — unit tests, library embedding — then
    skips the per-shape AOT lowering entirely."""
    raw = knobs.get_str(MFU_ENV).lower()
    if raw in ("0", "false", "no"):
        return False
    if raw in ("1", "true", "yes"):
        return True
    from elasticdl_tpu import observability

    return observability.current_handle() is not None


def shape_key(args):
    """Hashable (shape, dtype) signature of a step's argument pytree."""
    import jax

    leaves = jax.tree_util.tree_leaves(args)
    return tuple(
        (tuple(getattr(l, "shape", ())), str(getattr(l, "dtype", "")))
        for l in leaves
    )


def _analyzed_flops(jitted, spec):
    """FLOPs from XLA's compiled-cost analysis; None when unavailable.
    `spec` is a ShapeDtypeStruct pytree (AOT lowering needs shapes only —
    never live buffers, which the real step may have donated by the time
    the analysis thread runs)."""
    analysis = jitted.lower(*spec).compile().cost_analysis()
    if not analysis:
        return None
    flops = analysis.get("flops")
    if flops is None or flops <= 0:
        return None
    return float(flops)


_PENDING = object()  # analysis in flight on the background thread


class StepCostModel:
    """Caches per-shape step FLOPs and tracks the step period EWMA."""

    def __init__(self):
        self._enabled = enabled()
        self._peak = None
        if self._enabled:
            import jax

            kind = jax.devices()[0].device_kind
            try:
                self._peak = peak_flops(kind)
            except UnknownDeviceError:
                # Not printed, so not an error: the FLOPs and period
                # gauges still export, the MFU gauge stays absent.
                logger.info("No MFU gauge on device kind %r", kind)
        # shape key -> float (analyzed) | None (failed) | _PENDING
        self._flops = {}
        self._last_ts = None
        self._last_key = None
        self._period_ewma = None

    def observe(self, jitted, args, key_args=None):
        """Record one about-to-run (or just-dispatched) training step.

        Call once per train_minibatch with the jitted step callable and
        the exact argument tuple it runs with. `key_args` (default: all
        of args) is the subtree whose shapes key the cache — trainers
        pass the (features, labels) batch so the hot path never flattens
        the full parameter tree; FLOPs for secondary shape variation
        (e.g. per-batch embedding row counts) reuse the first sighting's
        estimate. The AOT lowering itself runs on a daemon thread against
        a ShapeDtypeStruct spec, so the training loop never blocks on the
        analysis compile."""
        if not self._enabled or jitted is None:
            return
        now = time.perf_counter()
        try:
            key = shape_key(args if key_args is None else key_args)
        except Exception:
            return
        if key not in self._flops:
            self._flops[key] = _PENDING
            try:
                import jax

                spec = jax.tree_util.tree_map(
                    lambda l: jax.ShapeDtypeStruct(l.shape, l.dtype),
                    args,
                )
            except Exception:
                # Missing cost analysis degrades to absent gauges.
                self._flops[key] = None
            else:
                threading.Thread(
                    target=self._analyze,
                    args=(jitted, spec, key),
                    name="edl-mfu-analysis",
                    daemon=True,
                ).start()
        flops = self._flops[key]
        if not isinstance(flops, float):
            flops = None
        if (
            self._last_ts is not None
            and self._last_key == key
            and now > self._last_ts
        ):
            period = now - self._last_ts
            self._period_ewma = (
                period
                if self._period_ewma is None
                else _EWMA_ALPHA * period
                + (1 - _EWMA_ALPHA) * self._period_ewma
            )
            _STEP_PERIOD.set(self._period_ewma)
            if flops is not None:
                _STEP_FLOPS.set(flops)
                if self._peak:
                    _MFU.set(
                        flops / (self._period_ewma * self._peak)
                    )
        self._last_ts = now
        self._last_key = key

    def _analyze(self, jitted, spec, key):
        try:
            self._flops[key] = _analyzed_flops(jitted, spec)
        except Exception:
            logger.info(
                "Step cost analysis unavailable; MFU gauges disabled "
                "for this shape",
                exc_info=True,
            )
            self._flops[key] = None
