"""Native (C++) components, loaded via ctypes with pure-Python fallbacks.

``codec.cpp`` holds the element-wise wire-codec hot path (f32<->bf16
conversion, int8 quantization, crc32); ``wire.cpp`` (wrapped by
:mod:`.wire`) is the whole-frame wire engine layered on the same
primitives.  Each shared library is compiled with g++ on first use and
cached beside its source under a name keyed on that source, the build
flags and the host's CPU (``_wire.<key>.so``), so a library copied in
from another box or left over from an older source is rebuilt, never
loaded; environments without a toolchain fall back to
numpy/ml_dtypes/zlib implementations with identical semantics (the tests
assert bit-equality).

Build hardening (ISSUE 9): every library exports ``dlt_abi_version()``
(``dlt_abi.h``), checked right after ``dlopen`` against this module's
``_ABI_VERSION``.  A failed g++ build (or an ABI disagreement) logs ONE
warning on the ``dlt.native`` logger and bumps the
``native.build_failed`` obs counter, then the pure-Python fallback
serves.
"""

from __future__ import annotations

import contextlib
import ctypes
import glob
import hashlib
import logging
import os
import platform
import subprocess
import threading
import zlib
from typing import Callable, Optional

import numpy as np

__all__ = [
    "native_available",
    "f32_to_bf16",
    "bf16_to_f32",
    "f32_to_i8",
    "i8_to_f32",
    "crc32",
]

_HERE = os.path.dirname(os.path.abspath(__file__))
_SRC = os.path.join(_HERE, "codec.cpp")
_LIB = os.path.join(_HERE, "_codec.so")
_lock = threading.Lock()
_lib: Optional[ctypes.CDLL] = None
_tried = False

#: Expected ``dlt_abi_version()`` of every native library; must match
#: DLT_ABI_VERSION in ``dlt_abi.h`` (bumped when the symbol set changes).
_ABI_VERSION = 3

_logger = logging.getLogger("dlt.native")


def _report_build_failure(src: str, detail: str) -> None:
    """One warning + one counter per failed build — a box quietly running
    the slow path is an observability bug, not a convenience."""
    _logger.warning(
        "native build of %s failed (%s); falling back to the pure-Python "
        "codec — wire throughput will be the fallback's",
        os.path.basename(src), detail,
    )
    try:  # lazy: obs must stay importable without the comm/native stack
        from distributed_learning_tpu.obs import get_registry

        get_registry().inc("native.build_failed")
    except Exception:
        pass


def _cache_override(lib_path: str) -> str:
    """Instrumented-build hook (graftlint --native, ISSUE 10): when
    ``DLT_NATIVE_CACHE_DIR`` is set, the built ``.so`` lives under that
    directory instead of beside its source — a sanitizer run rebuilds
    with its own flags WITHOUT ever clobbering the production cache."""
    cache_dir = os.environ.get("DLT_NATIVE_CACHE_DIR")
    if not cache_dir:
        return lib_path
    os.makedirs(cache_dir, exist_ok=True)
    return os.path.join(cache_dir, os.path.basename(lib_path))


def _host_cpu_flags() -> str:
    """What ``-march=native`` resolves against on this host: the first
    ``flags``/``Features`` line of /proc/cpuinfo (empty where the file
    is absent — the machine name below still keys the build)."""
    try:
        with open("/proc/cpuinfo", encoding="utf-8") as fh:
            for line in fh:
                if line.startswith(("flags", "Features")):
                    return line
    except OSError:
        pass
    return ""


def _keyed_lib_path(src: str, lib_path: str, cflags: list) -> str:
    """``_wire.so`` -> ``_wire.<key>.so``, the key a hash of everything
    the binary depends on: the source and ``dlt_abi.h`` as checked in,
    the build flags, and this host's CPU.  A library found under that
    name was built from this checkout's sources for this host; one that
    came from another box, or predates a source edit, has another name
    and is never opened."""
    h = hashlib.sha256()
    for path in (src, os.path.join(os.path.dirname(src), "dlt_abi.h")):
        if os.path.exists(path):
            with open(path, "rb") as fh:
                h.update(fh.read())
    h.update(" ".join(cflags).encode())
    h.update(platform.machine().encode())
    h.update(_host_cpu_flags().encode())
    stem, ext = os.path.splitext(lib_path)
    return f"{stem}.{h.hexdigest()[:16]}{ext}"


def _build_lib(src: str, lib_path: str) -> Optional[str]:
    """Compile ``src`` unless this host already built this source; the
    library lands beside ``lib_path`` under its keyed name
    (:func:`_keyed_lib_path`), which is returned.

    ``DLT_NATIVE_EXTRA_CFLAGS`` (space-separated) appends build flags —
    the sanitizer stage's ``-fsanitize=...`` hook; combined with
    ``DLT_NATIVE_CACHE_DIR`` the instrumented build is fully separate.
    """
    extra_cflags = os.environ.get("DLT_NATIVE_EXTRA_CFLAGS", "").split()
    cflags = ["-O3", "-shared", "-fPIC", "-std=c++17", *extra_cflags]
    keyed = _keyed_lib_path(src, lib_path, cflags)
    if os.path.exists(keyed):
        return keyed
    # Per-process temp name: concurrent first-use builds (multi-process
    # deployments) must not interleave g++ output on a shared path; the
    # final os.replace is atomic either way.  -march=native is safe
    # because the host's CPU is part of the library's name, and lets
    # the wire engine's bulk loops vectorize; boxes whose toolchain
    # rejects it retry with the portable baseline.
    tmp = f"{keyed}.{os.getpid()}.tmp"
    last_exc: Optional[BaseException] = None
    for march in (["-march=native"], []):
        try:
            subprocess.run(
                ["g++", *march, *cflags, src, "-o", tmp],
                check=True,
                capture_output=True,
                timeout=120,
            )
            os.replace(tmp, keyed)
            # Builds this one supersedes (an edited source, another
            # host's copy) would otherwise pile up beside it.
            stem, ext = os.path.splitext(lib_path)
            for stale in glob.glob(f"{glob.escape(stem)}.*{ext}"):
                if stale != keyed:
                    with contextlib.suppress(OSError):
                        os.unlink(stale)
            return keyed
        except (OSError, subprocess.SubprocessError) as exc:
            last_exc = exc
            with contextlib.suppress(OSError):
                os.unlink(tmp)
    detail = type(last_exc).__name__
    stderr = getattr(last_exc, "stderr", None)
    if stderr:
        detail += ": " + stderr.decode("utf-8", "replace").strip()[:200]
    _report_build_failure(src, detail)
    return None


def _abi_ok(lib: ctypes.CDLL) -> bool:
    try:
        fn = lib.dlt_abi_version
    except AttributeError:
        return False
    fn.argtypes = []
    fn.restype = ctypes.c_uint32
    return int(fn()) == _ABI_VERSION


def _load_lib(
    src: str,
    lib_path: str,
    configure: Callable[[ctypes.CDLL], None],
) -> Optional[ctypes.CDLL]:
    """Build (if needed), dlopen, ABI-check, and configure one library.

    The library is always one this host built from ``src`` as it stands
    (see :func:`_keyed_lib_path`), so an ABI mismatch means the source
    and this module's ``_ABI_VERSION`` disagree: the Python fallback
    serves and the failure is counted.
    """
    path = _build_lib(src, lib_path)
    if path is None:
        return None
    try:
        lib = ctypes.CDLL(path)
    except OSError:
        return None
    if not _abi_ok(lib):
        _report_build_failure(
            src, f"library reports another ABI than v{_ABI_VERSION}"
        )
        return None
    configure(lib)
    return lib


def _configure_codec(lib: ctypes.CDLL) -> None:
    lib.dlt_f32_to_bf16.argtypes = [
        ctypes.c_void_p, ctypes.c_void_p, ctypes.c_size_t,
    ]
    lib.dlt_f32_to_bf16.restype = None
    lib.dlt_bf16_to_f32.argtypes = [
        ctypes.c_void_p, ctypes.c_void_p, ctypes.c_size_t,
    ]
    lib.dlt_bf16_to_f32.restype = None
    lib.dlt_f32_to_i8.argtypes = [
        ctypes.c_void_p, ctypes.c_void_p, ctypes.c_size_t,
        ctypes.c_float,
    ]
    lib.dlt_f32_to_i8.restype = None
    lib.dlt_i8_to_f32.argtypes = [
        ctypes.c_void_p, ctypes.c_void_p, ctypes.c_size_t,
        ctypes.c_float,
    ]
    lib.dlt_i8_to_f32.restype = None
    lib.dlt_crc32.argtypes = [
        ctypes.c_void_p, ctypes.c_size_t, ctypes.c_uint32,
    ]
    lib.dlt_crc32.restype = ctypes.c_uint32


def _load() -> Optional[ctypes.CDLL]:
    global _lib, _tried
    if _lib is not None or _tried:
        return _lib
    with _lock:
        if _lib is not None or _tried:
            return _lib
        _tried = True
        if os.environ.get("DLT_NO_NATIVE") == "1":
            return None
        _lib = _load_lib(_SRC, _cache_override(_LIB), _configure_codec)
        return _lib


def native_available() -> bool:
    return _load() is not None


def f32_to_bf16(x: np.ndarray) -> np.ndarray:
    """float32 array -> uint16 array of bfloat16 bit patterns (RNE)."""
    x = np.ascontiguousarray(x, dtype=np.float32)
    out = np.empty(x.shape, dtype=np.uint16)
    lib = _load()
    if lib is not None and x.size:
        lib.dlt_f32_to_bf16(
            x.ctypes.data, out.ctypes.data, ctypes.c_size_t(x.size)
        )
        return out
    import ml_dtypes  # bundled with jax

    return x.astype(ml_dtypes.bfloat16).view(np.uint16)


def bf16_to_f32(bits: np.ndarray) -> np.ndarray:
    """uint16 bfloat16 bit patterns -> float32 array."""
    bits = np.ascontiguousarray(bits, dtype=np.uint16)
    out = np.empty(bits.shape, dtype=np.float32)
    lib = _load()
    if lib is not None and bits.size:
        lib.dlt_bf16_to_f32(
            bits.ctypes.data, out.ctypes.data, ctypes.c_size_t(bits.size)
        )
        return out
    import ml_dtypes

    return bits.view(ml_dtypes.bfloat16).astype(np.float32)


def f32_to_i8(x: np.ndarray, scale: float) -> np.ndarray:
    """Symmetric int8 quantization: round(x/scale) clamped to [-127, 127]
    (ties to even, matching np.rint).  ``scale`` is the caller's
    per-tensor max|x|/127."""
    x = np.ascontiguousarray(x, dtype=np.float32)
    out = np.empty(x.shape, dtype=np.int8)
    inv = 0.0 if scale == 0.0 else 1.0 / float(scale)
    lib = _load()
    if lib is not None and x.size:
        lib.dlt_f32_to_i8(
            x.ctypes.data, out.ctypes.data, ctypes.c_size_t(x.size),
            ctypes.c_float(inv),
        )
        return out
    return np.clip(np.rint(x * inv), -127, 127).astype(np.int8)


def i8_to_f32(q: np.ndarray, scale: float) -> np.ndarray:
    """Dequantize int8 back to f32: q * scale."""
    q = np.ascontiguousarray(q, dtype=np.int8)
    out = np.empty(q.shape, dtype=np.float32)
    lib = _load()
    if lib is not None and q.size:
        lib.dlt_i8_to_f32(
            q.ctypes.data, out.ctypes.data, ctypes.c_size_t(q.size),
            ctypes.c_float(scale),
        )
        return out
    return q.astype(np.float32) * np.float32(scale)


def crc32(data, seed: int = 0) -> int:
    """crc32 (zlib-compatible) of a bytes-like or contiguous array."""
    lib = _load()
    if lib is not None:
        buf = np.frombuffer(memoryview(data).cast("B"), dtype=np.uint8)
        if buf.size == 0:
            return zlib.crc32(b"", seed) & 0xFFFFFFFF
        return int(
            lib.dlt_crc32(
                buf.ctypes.data, ctypes.c_size_t(buf.size), ctypes.c_uint32(seed)
            )
        )
    return zlib.crc32(memoryview(data).cast("B"), seed) & 0xFFFFFFFF
