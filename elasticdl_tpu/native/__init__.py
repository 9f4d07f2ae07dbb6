"""ctypes bindings for the native optimizer/table kernels.

The reference reaches its C++ kernels through Go's cgo
(/root/reference/elasticdl/go/pkg/kernel/kernel.go:16-18); here the Python
parameter server calls the shared library directly via ctypes — no binding
codegen, no copy: numpy arrays pass as raw pointers.

`lib()` builds the library with the package Makefile on first use, from
the committed .cc files, on the machine that runs it. The file name
carries a hash of the sources and the Makefile, so a clean checkout and a
used one load the same thing: a stale or foreign binary has another name
and is never picked up. A library that cannot be built or loaded RAISES —
a PS quietly several times slower on numpy is not a fallback anyone asked
for. EDL_NO_NATIVE=1 is the explicit way to run the pure-numpy paths
(elasticdl_tpu/ps/optimizer.py), which the tests keep honest.
"""

import ctypes
import hashlib
import os
import subprocess
import threading

from elasticdl_tpu.common.log_utils import get_logger

logger = get_logger("native")

_HERE = os.path.dirname(os.path.abspath(__file__))
_SOURCES = ("kernels.cc", "recordio.cc", "idmap.cc")
_lock = threading.Lock()
_lib = None


def _f32p(a):
    return a.ctypes.data_as(ctypes.POINTER(ctypes.c_float))


def _i64p(a):
    return a.ctypes.data_as(ctypes.POINTER(ctypes.c_int64))


def _declare(lib):
    f32p = ctypes.POINTER(ctypes.c_float)
    i64p = ctypes.POINTER(ctypes.c_int64)
    i64 = ctypes.c_int64
    f32 = ctypes.c_float
    sigs = {
        "edl_sgd": [f32p, f32p, f32, i64],
        "edl_momentum": [f32p, f32p, f32p, f32, f32, ctypes.c_int, i64],
        "edl_adam": [f32p, f32p, f32p, f32p, f32p, f32, i64, f32, f32, f32,
                     i64],
        "edl_adagrad": [f32p, f32p, f32p, f32, f32, i64],
        "edl_sgd_indexed": [f32p, i64p, i64, i64, f32p, f32],
        "edl_momentum_indexed": [f32p, i64p, i64, i64, f32p, f32p, f32, f32,
                                 ctypes.c_int],
        "edl_adam_indexed": [f32p, i64p, i64, i64, f32p, f32p, f32p, f32p,
                             f32, i64, f32, f32, f32],
        "edl_adagrad_indexed": [f32p, i64p, i64, i64, f32p, f32p, f32, f32],
        "edl_gather_rows": [f32p, i64p, i64, i64, f32p],
        "edl_scatter_rows": [f32p, i64p, i64, i64, f32p],
        "edl_uniform_init": [f32p, i64, f32, f32, ctypes.c_uint64],
        "edl_uniform_init_rows": [f32p, i64, i64, i64, f32, f32,
                                  ctypes.c_uint64],
        "edl_normal_init_rows": [f32p, i64, i64, i64, f32, f32,
                                 ctypes.c_uint64, ctypes.c_int],
        "edl_idmap_free": [ctypes.c_void_p],
        "edl_idmap_export_ids": [ctypes.c_void_p, i64, i64, i64p],
    }
    for name, argtypes in sigs.items():
        fn = getattr(lib, name)
        fn.argtypes = argtypes
        fn.restype = None
    # Record-file reader (recordio.cc) returns byte counts / error codes.
    u8p = ctypes.POINTER(ctypes.c_uint8)
    lib.edl_records_read.argtypes = [
        ctypes.c_char_p, ctypes.c_longlong, ctypes.c_longlong, u8p,
        ctypes.c_longlong, i64p,
    ]
    lib.edl_records_read.restype = ctypes.c_longlong
    # id->row map handle functions (non-void returns).
    lib.edl_idmap_new.argtypes = [i64]
    lib.edl_idmap_new.restype = ctypes.c_void_p
    lib.edl_idmap_size.argtypes = [ctypes.c_void_p]
    lib.edl_idmap_size.restype = i64
    lib.edl_idmap_rows_for_ids.argtypes = [
        ctypes.c_void_p, i64p, i64, ctypes.c_int, i64p,
    ]
    lib.edl_idmap_rows_for_ids.restype = i64
    lib.edl_dedup_sum.argtypes = [i64p, f32p, i64, i64, i64p, f32p]
    lib.edl_dedup_sum.restype = i64
    return lib


def _so_path():
    digest = hashlib.sha1()
    for name in _SOURCES + ("Makefile",):
        with open(os.path.join(_HERE, name), "rb") as f:
            digest.update(f.read())
    return os.path.join(
        _HERE, f"libedl_kernels-{digest.hexdigest()[:12]}.so"
    )


def build():
    """Build the library for the current sources; returns its path.
    Concurrent builders (two PS shards, xdist workers) each compile to a
    private name and publish with an atomic rename."""
    so = _so_path()
    tmp = f"libedl_kernels-build-{os.getpid()}.so"
    try:
        subprocess.run(
            ["make", "-s", "-C", _HERE, f"OUT={tmp}"],
            check=True, capture_output=True, text=True,
        )
    except subprocess.CalledProcessError as e:
        raise RuntimeError(
            f"building the native kernels failed: {e.stderr[-2000:]}"
        ) from e
    os.replace(os.path.join(_HERE, tmp), so)
    return so


def lib():
    """The loaded shared library, building it on first call. None only
    when EDL_NO_NATIVE is set; a failed build or load raises."""
    global _lib
    if _lib is not None:
        return _lib or None
    with _lock:
        if _lib is not None:
            return _lib or None
        if os.environ.get("EDL_NO_NATIVE"):
            _lib = False
            return None
        so = _so_path()
        if not os.path.exists(so):
            # A set-up phase of whichever role gets here first, in a
            # job that may never call a PS kernel (the record reader
            # lives in the same library).
            from elasticdl_tpu.observability import tracing

            with tracing.span("setup.native_build", cat=tracing.SETUP):
                build()
        _lib = _declare(ctypes.CDLL(so))
        logger.info("Loaded native kernels from %s", so)
    return _lib


def available():
    return lib() is not None
