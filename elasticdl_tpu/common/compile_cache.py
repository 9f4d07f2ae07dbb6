"""Persistent XLA compilation cache wiring (recompile-free elasticity).

Compile IS the elastic rejoin: a relaunched worker, a regrouped world
and a speculated world all lower programs this host has already
compiled. jax ships a content-addressed persistent compilation cache;
this module is the one place the framework decides WHERE it lives:

- `JAX_COMPILATION_CACHE_DIR` set: jax reads that variable itself and
  this module sets no directory in code — the machine (or the chip
  tool) places the cache.
- unset: one fixed path inside the checkout, `<repo>/.jax_cache`. Never
  a temporary directory, a pid or a timestamp: the path is part of what
  makes a second run hit.

Every process of a job resolves the same directory: children inherit
the environment, and the fixed path depends only on where the package
is. To run cold on purpose use jax's own switch
(`JAX_ENABLE_COMPILATION_CACHE=false`), not a second directory.

Thresholds are zeroed (`min_compile_time_secs`, `min_entry_size`):
elasticity cares about the many small programs around the step (eval
forwards, broadcast zero-templates), not only the headline compile.
"""

import os
import threading

from elasticdl_tpu.common.log_utils import get_logger

logger = get_logger("common.compile_cache")

JAX_CACHE_DIR_ENV = "JAX_COMPILATION_CACHE_DIR"

_REPO_ROOT = os.path.dirname(
    os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
)
FIXED_CACHE_DIR = os.path.join(_REPO_ROOT, ".jax_cache")

_lock = threading.Lock()
_configured = None  # the resolved directory once wired


def resolve_cache_dir():
    """The directory every process of a job caches in."""
    return os.environ.get(JAX_CACHE_DIR_ENV) or FIXED_CACHE_DIR


def ensure_compile_cache():
    """Idempotently wire jax's persistent compilation cache and return
    its directory. Safe to call from every trainer/bench/role entrypoint
    — the first caller wins, later calls are a lock + compare. A
    directory that cannot be created raises: a job that silently
    compiles everything cold is the failure this module exists to
    prevent."""
    global _configured
    with _lock:
        if _configured is not None:
            return _configured
        import jax

        cache_dir = resolve_cache_dir()
        os.makedirs(cache_dir, exist_ok=True)
        if not os.environ.get(JAX_CACHE_DIR_ENV):
            jax.config.update("jax_compilation_cache_dir", cache_dir)
        # Cache EVERYTHING: the defaults skip sub-second compiles and
        # small executables, which is exactly the long tail a relaunched
        # worker re-pays (eval forward, state templates).
        jax.config.update("jax_persistent_cache_min_compile_time_secs", 0.0)
        jax.config.update("jax_persistent_cache_min_entry_size_bytes", -1)
        _configured = cache_dir
        logger.info("Persistent compilation cache at %s", cache_dir)
        return cache_dir

