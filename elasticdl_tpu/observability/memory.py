"""Deep profiling plane, part 2: live/peak memory accounting.

A MemoryAccountant samples, on a background thread (or on demand from
tests and the report tools):

- device memory: the sum of `jax.live_arrays()` byte sizes (works on
  every backend, CPU included) and, where the runtime reports them,
  `device.memory_stats()` bytes_in_use / peak_bytes_in_use per device;
- host memory: VmRSS from /proc/self/status (live) and
  `resource.getrusage` ru_maxrss (peak) — the PS-side number, since PS
  shards are pure-host processes whose embedding slabs dominate RSS;
- registered components: any subsystem can `add_provider(fn)` returning
  {component: bytes} — the PS registers per-embedding-table and dense-
  param byte counts so a hot shard's footprint is attributable to the
  table that causes it.

Exported as `edl_mem_*` gauges; a `mem_high_watermark` event fires when
a sample's live device total jumps past the previous peak by the
ELASTICDL_MEM_WATERMARK_RATIO factor — that is the "which step blew up
HBM" breadcrumb, timestamped into the same events.jsonl the elastic
timeline lives in. Sampling period: ELASTICDL_MEM_SAMPLE_SECONDS (0
disables the thread; direct `sample()` calls always work).

Everything degrades to absent gauges, never to a training failure: no
jax, no /proc, no providers — each leg is independently guarded.
"""

import os
import threading

from elasticdl_tpu.common import knobs
from elasticdl_tpu.common.log_utils import get_logger
from elasticdl_tpu.observability import events as _events
from elasticdl_tpu.observability.metrics import default_registry

logger = get_logger("observability.memory")

SAMPLE_SECONDS_ENV = "ELASTICDL_MEM_SAMPLE_SECONDS"
WATERMARK_RATIO_ENV = "ELASTICDL_MEM_WATERMARK_RATIO"

_REG = default_registry()
_G_DEVICE_LIVE = _REG.gauge(
    "edl_mem_device_live_bytes",
    "Bytes held by live jax arrays at the last sample",
)
_G_DEVICE_PEAK = _REG.gauge(
    "edl_mem_device_peak_bytes",
    "Peak live-array bytes observed by any sample this process",
)
_G_DEVICE_STATS = _REG.gauge(
    "edl_mem_device_stats_bytes",
    "Runtime-reported device memory (platforms with memory_stats)",
    labelnames=("device", "stat"),
)
_G_HOST_RSS = _REG.gauge(
    "edl_mem_host_rss_bytes",
    "Resident set size of this process at the last sample",
)
_G_HOST_PEAK = _REG.gauge(
    "edl_mem_host_peak_rss_bytes",
    "Peak resident set size (getrusage high watermark)",
)
_G_COMPONENT = _REG.gauge(
    "edl_mem_component_bytes",
    "Registered component byte counts (PS embedding tables, dense "
    "params, ...)",
    labelnames=("component",),
)


def host_rss_bytes():
    """Current VmRSS from /proc (Linux); None elsewhere."""
    try:
        with open("/proc/self/status") as f:
            for line in f:
                if line.startswith("VmRSS:"):
                    return int(line.split()[1]) * 1024
    except OSError:
        pass
    return None


def host_peak_rss_bytes():
    try:
        import resource

        peak = resource.getrusage(resource.RUSAGE_SELF).ru_maxrss
        return int(peak) * 1024  # Linux reports KiB
    except Exception:
        return None


def _jax_backend_up():
    """True only when THIS process has already initialised a jax
    backend. The sampler reads memory from a backend that is up and
    never brings one up: a chip belongs to one process, and the master
    and every PS import jax (through the user's model module) without
    ever computing on it — a sample there must not claim the device the
    worker needs."""
    import sys

    if "jax" not in sys.modules:
        return False
    from jax._src import xla_bridge

    return xla_bridge.backends_are_initialized()


def device_live_bytes():
    """Sum of live jax array bytes; None when this process runs no jax
    backend (see _jax_backend_up)."""
    if not _jax_backend_up():
        return None
    import jax

    return sum(int(getattr(a, "nbytes", 0)) for a in jax.live_arrays())


def device_memory_stats():
    """{device_label: {stat: bytes}} from backends that report them
    (TPU/GPU); {} on CPU and in processes that run no jax backend."""
    if not _jax_backend_up():
        return {}
    import jax

    out = {}
    for d in jax.local_devices():
        stats = d.memory_stats()
        if not stats:
            continue
        picked = {
            k: v
            for k, v in stats.items()
            if k in ("bytes_in_use", "peak_bytes_in_use", "bytes_limit")
        }
        if picked:
            out[f"{d.platform}:{d.id}"] = picked
    return out


class MemoryAccountant:
    """Samples process memory into gauges + high-watermark events."""

    def __init__(self, watermark_ratio=None):
        if watermark_ratio is None:
            watermark_ratio = knobs.get_float(WATERMARK_RATIO_ENV)
        self.watermark_ratio = max(1.0, watermark_ratio)
        self._lock = threading.Lock()
        self._providers = []
        self._device_peak = 0
        self._stop = threading.Event()
        self._thread = None

    def add_provider(self, fn):
        """Register a callable() -> {component: bytes}; exceptions and
        non-dict returns are swallowed per sample."""
        with self._lock:
            if fn not in self._providers:
                self._providers.append(fn)

    def remove_provider(self, fn):
        with self._lock:
            if fn in self._providers:
                self._providers.remove(fn)

    # ---------- sampling ----------

    def sample(self):
        """One pass; returns the sample dict (tests and /api consumers).
        Also the thread body's unit of work."""
        out = {}
        live = device_live_bytes()
        if live is not None:
            out["device_live_bytes"] = live
            _G_DEVICE_LIVE.set(live)
            # Peak decision, gauge, and event all under the lock:
            # sample() is documented as callable concurrently with the
            # sampler thread, and an unlocked late writer could pin the
            # peak gauge below the true peak (or double-fire the event).
            with self._lock:
                prev_peak = self._device_peak
                if live > prev_peak:
                    self._device_peak = live
                    _G_DEVICE_PEAK.set(live)
                    if (
                        prev_peak > 0
                        and live > prev_peak * self.watermark_ratio
                    ):
                        _events.emit(
                            "mem_high_watermark",
                            bytes=live,
                            previous_peak=prev_peak,
                            ratio=round(live / prev_peak, 3),
                        )
        stats = device_memory_stats()
        if stats:
            out["device_stats"] = stats
            for device, picked in stats.items():
                for stat, value in picked.items():
                    _G_DEVICE_STATS.labels(
                        device=device, stat=stat
                    ).set(value)
        rss = host_rss_bytes()
        if rss is not None:
            out["host_rss_bytes"] = rss
            _G_HOST_RSS.set(rss)
        peak = host_peak_rss_bytes()
        if peak is not None:
            out["host_peak_rss_bytes"] = peak
            _G_HOST_PEAK.set(peak)
        with self._lock:
            providers = list(self._providers)
        components = {}
        for fn in providers:
            try:
                result = fn()
            except Exception:
                continue
            if not isinstance(result, dict):
                continue
            for component, value in result.items():
                components[str(component)] = int(value)
        for component, value in components.items():
            _G_COMPONENT.labels(component=component).set(value)
        if components:
            out["components"] = components
        return out

    @property
    def device_peak_bytes(self):
        with self._lock:
            return self._device_peak

    # ---------- lifecycle ----------

    def start(self, interval=None):
        if interval is None:
            interval = knobs.get_float(SAMPLE_SECONDS_ENV)
        if interval <= 0 or self._thread is not None:
            return self
        # A close()d accountant must be restartable: setup()/close()
        # cycles reuse the process-global instance, and a stale stop
        # flag would kill the relaunched thread after zero samples.
        self._stop.clear()
        self._interval = interval

        def run():
            while not self._stop.is_set():
                try:
                    self.sample()
                except Exception:
                    logger.warning("memory sample failed", exc_info=True)
                self._stop.wait(self._interval)

        self._thread = threading.Thread(
            target=run, name="edl-mem-accountant", daemon=True
        )
        self._thread.start()
        return self

    def close(self):
        self._stop.set()
        if self._thread is not None:
            self._thread.join(timeout=5)
            self._thread = None


_accountant = None
_accountant_lock = threading.Lock()


def accountant():
    """The process-global accountant (created on first use; providers
    can register before the sampler thread ever starts)."""
    global _accountant
    with _accountant_lock:
        if _accountant is None:
            _accountant = MemoryAccountant()
        return _accountant


def embedding_bytes_provider(parameters):
    """Provider for a PS shard's ps.Parameters: per-table used-row bytes
    plus the dense-parameter total — `os.environ`-free, lock-free reads
    of sizes that only grow."""

    def provider():
        out = {}
        dense = 0
        for arr in parameters.dense.values():
            dense += int(getattr(arr, "nbytes", 0))
        if dense:
            out["ps_dense_params"] = dense
        for name, table in parameters.embedding_tables.items():
            rows = len(table)
            itemsize = getattr(table, "dtype", None)
            itemsize = getattr(itemsize, "itemsize", 4) or 4
            out[f"ps_embedding:{name}"] = rows * table.dim * itemsize
        return out

    return provider
