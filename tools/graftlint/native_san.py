"""Sanitizer stage (graftlint stage c', ISSUE 10): ``graftlint --native``.

The native wire engine's AVX-512 scatter/compress paths are exactly
where a memory-safety bug would be silent on the happy path and
catastrophic on a corrupt frame.  The existing fuzz corpus
(``tests/test_wire.py``) proves *semantic* rejection; this stage proves
*memory* safety: both native libraries are rebuilt with
``-fsanitize=address,undefined -fno-sanitize-recover`` into a SEPARATE
cache directory (``.san_cache/`` at the repo root — the production
``.so`` files are never touched, enforced by mtime in the rot-guard
test), and the ~200-case corruption-fuzz corpus plus the byte-identity
oracle matrix are replayed under the instrumented libraries.  Any
sanitizer report is a lint failure.

LD_PRELOAD-free load: the replay runs in a fresh subprocess
(``python -m tools.graftlint.native_san``) that dlopens ``libasan.so``/
``libubsan.so`` with ``RTLD_GLOBAL`` *before* the instrumented ``.so``
is loaded, so the sanitizer runtime resolves at dlopen time without
touching the parent interpreter or its environment
(``ASAN_OPTIONS=verify_asan_link_order=0`` silences the
runtime-not-first warning this pattern triggers by design).  Because
python's own allocations predate the runtime, leak checking is off and
redzone coverage on caller buffers comes from the harness itself: the
direct-ctypes replay allocates every frame/ravel buffer through the
sanitizer's ``malloc``, so an out-of-bounds scatter or frame read in
``wire.cpp`` lands in a redzone and aborts the child — which the parent
reports as the lint failure.

Environment requirements (g++ with the libasan/libubsan runtimes);
absent toolchains SKIP with a notice — memory-safety lint never fakes a
pass, and never blocks a box that cannot run it.
"""

from __future__ import annotations

import os
import subprocess
import sys
from typing import List, Tuple

from tools.graftlint.core import REPO_ROOT

#: Separate build cache for instrumented libraries (gitignored).
SAN_CACHE = os.path.join(REPO_ROOT, ".san_cache")

SAN_CFLAGS = (
    "-fsanitize=address,undefined -fno-sanitize-recover=all "
    "-fno-omit-frame-pointer -g"
)

#: Child-process sanitizer knobs: abort (non-zero exit) on the first
#: report; leaks are off because the interpreter's own startup
#: allocations predate the runtime (see module docstring).
ASAN_OPTIONS = (
    "detect_leaks=0:abort_on_error=1:halt_on_error=1:"
    "verify_asan_link_order=0"
)
UBSAN_OPTIONS = "print_stacktrace=1:halt_on_error=1"

_REPORT_MARKERS = (
    "AddressSanitizer",
    "UndefinedBehaviorSanitizer",
    "runtime error:",
    "LeakSanitizer",
)


def _runtime_path(name: str) -> str:
    """Resolve a sanitizer runtime through the toolchain ('' if absent)."""
    try:
        out = subprocess.run(
            ["g++", f"-print-file-name={name}"],
            capture_output=True, text=True, timeout=30,
        ).stdout.strip()
    except (OSError, subprocess.SubprocessError):
        return ""
    return out if out and os.path.exists(out) and os.path.isabs(out) else ""


def toolchain_status() -> Tuple[bool, str]:
    """(usable, reason-when-not) for the sanitizer toolchain."""
    try:
        subprocess.run(
            ["g++", "--version"], capture_output=True, timeout=30,
        )
    except (OSError, subprocess.SubprocessError):
        return False, "g++ not available"
    if not _runtime_path("libasan.so"):
        return False, "libasan.so runtime not found by g++"
    if not _runtime_path("libubsan.so"):
        return False, "libubsan.so runtime not found by g++"
    return True, ""


def run_native_stage(timeout_s: float = 600.0) -> Tuple[str, List[str]]:
    """Parent side: spawn the replay child; returns (status, detail)
    with status in {"ok", "skip", "fail"}."""
    usable, reason = toolchain_status()
    if not usable:
        return "skip", [f"sanitizer toolchain absent: {reason}"]
    env = dict(os.environ)
    env.update(
        {
            # The child interpreter must resolve THIS repo first and
            # stay off any accelerator.
            "PYTHONPATH": REPO_ROOT + (
                os.pathsep + env["PYTHONPATH"] if env.get("PYTHONPATH")
                else ""
            ),
            "JAX_PLATFORMS": "cpu",
            "DLT_NATIVE_CACHE_DIR": SAN_CACHE,
            "DLT_NATIVE_EXTRA_CFLAGS": SAN_CFLAGS,
            "ASAN_OPTIONS": ASAN_OPTIONS,
            "UBSAN_OPTIONS": UBSAN_OPTIONS,
        }
    )
    env.pop("DLT_NO_NATIVE", None)  # the whole point is the native path
    try:
        proc = subprocess.run(
            [sys.executable, "-m", "tools.graftlint.native_san"],
            cwd=REPO_ROOT, env=env, capture_output=True, text=True,
            timeout=timeout_s,
        )
    except subprocess.TimeoutExpired:
        return "fail", [f"sanitized replay timed out after {timeout_s}s"]
    output = (proc.stdout or "") + (proc.stderr or "")
    reported = [m for m in _REPORT_MARKERS if m in output]
    if proc.returncode != 0 or reported:
        tail = output.strip().splitlines()[-25:]
        detail = [
            f"sanitized replay FAILED (rc={proc.returncode}"
            + (f", markers: {', '.join(reported)}" if reported else "")
            + ")"
        ] + tail
        return "fail", detail
    summary = [
        ln for ln in (proc.stdout or "").splitlines()
        if ln.startswith("native-san-replay:")
    ]
    return "ok", summary or ["sanitized replay passed"]


# --------------------------------------------------------------------- #
# Child side: the replay harness (run as python -m ...native_san)       #
# --------------------------------------------------------------------- #
def _load_sanitizer_runtimes():
    """dlopen the runtimes RTLD_GLOBAL (the LD_PRELOAD-free load) and
    return the libasan handle — its malloc/free are the redzoned heap
    the raw replay allocates from.  Resolving them from the handle, not
    the global scope, matters: global dlsym walks load order and would
    find libc's malloc first."""
    import ctypes

    handles = {}
    for name in ("libasan.so", "libubsan.so"):
        path = _runtime_path(name)
        if not path:
            raise RuntimeError(f"{name} not resolvable in the child")
        handles[name] = ctypes.CDLL(path, mode=ctypes.RTLD_GLOBAL)
    return handles["libasan.so"]


class _AsanAlloc:
    """Buffers allocated through the sanitizer's malloc, so redzones
    bracket every byte the native engine touches."""

    def __init__(self, asan):
        import ctypes

        self._libc = asan  # the interceptor malloc/free: redzoned heap
        self._libc.malloc.restype = ctypes.c_void_p
        self._libc.malloc.argtypes = [ctypes.c_size_t]
        self._libc.free.argtypes = [ctypes.c_void_p]
        self._ctypes = ctypes

    def buf(self, data: bytes = b"", size: int = 0):
        """(ptr, nbytes): a malloc'd copy of ``data`` (or ``size`` zero
        bytes).  Caller frees via :meth:`free`."""
        ct = self._ctypes
        n = max(len(data), size, 1)
        ptr = self._libc.malloc(n)
        assert ptr, "sanitizer malloc failed"
        ct.memset(ptr, 0, n)
        if data:
            ct.memmove(ptr, data, len(data))
        return ptr, n

    def free(self, ptr) -> None:
        self._libc.free(self._ctypes.c_void_p(ptr))

    def read(self, ptr, n: int) -> bytes:
        return self._ctypes.string_at(ptr, n)


def _import_wire_corpus():
    """The fuzz corpus + oracle matrix live in tests/test_wire.py; load
    it by path (tests/ is not a package) so the corpus stays single-
    sourced between pytest and this stage."""
    import importlib.util

    path = os.path.join(REPO_ROOT, "tests", "test_wire.py")
    spec = importlib.util.spec_from_file_location("_wire_corpus", path)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


def _replay() -> int:
    """Child main: build instrumented libs, replay matrix + fuzz corpus
    through the PUBLIC codec paths, then re-drive the raw C entry points
    on sanitizer-malloc'd buffers.  Exit 0 = silence.

    Import order is load-bearing: every C extension (numpy, pytest's
    deps, the obs layer) must bind its allocator symbols BEFORE libasan
    enters the global scope — a C++ extension loaded after the runtime
    would route ``operator delete`` through ASan and abort on any
    object allocated pre-load (observed as 'bad-free ... wild pointer').
    So: heavy imports first with the native path disabled, THEN the
    sanitizer runtimes, THEN the instrumented ``.so`` — the only
    library that ever resolves against ASan."""
    import struct

    import numpy as np

    # Phase 1: heavy imports, native path held off so nothing dlopens
    # the (sanitized) lib before the runtime is in scope.
    os.environ["DLT_NO_NATIVE"] = "1"
    from distributed_learning_tpu import native
    from distributed_learning_tpu.comm import tensor_codec as tc
    from distributed_learning_tpu.native import wire
    from distributed_learning_tpu import obs as _obs  # noqa: F401

    corpus = _import_wire_corpus()
    del os.environ["DLT_NO_NATIVE"]

    # Phase 2: runtimes, then the instrumented libraries.
    asan = _load_sanitizer_runtimes()
    if not wire.available() or not native.native_available():
        print(
            "native-san-replay: instrumented build failed to load",
            file=sys.stderr,
        )
        return 3

    oracle_cases = 0
    # --- Byte-identity oracle matrix under the instrumented engine ---- #
    for name, flat, buckets in corpus._scenarios():
        for mode in corpus._MODES:
            frame = tc.encode_fused_sparse(flat, buckets, **mode)
            modes = tc._bucket_modes(
                tuple(buckets), mode.get("bf16_wire", False),
                mode.get("int8_wire", False),
            )
            oracle = tc._encode_fused_sparse_py(flat, tuple(buckets), modes)
            assert frame == oracle, (name, mode, "encode bytes diverged")
            out = tc.decode_fused_sparse(frame)
            ref = tc._decode_fused_sparse_py(frame, len(buckets), flat.size)
            np.testing.assert_array_equal(out, ref)
            oracle_cases += 1
    rng = np.random.default_rng(7)
    for shape in [(), (0,), (7,), (64, 33), (2, 3, 4)]:
        for mode in corpus._MODES:
            x = rng.normal(size=shape).astype(np.float32)
            frame = tc.encode_tensor(x, **mode)
            os.environ["DLT_NO_NATIVE"] = "1"
            oracle = tc.encode_tensor(x, **mode)
            decoded_py = tc.decode_tensor(frame)
            del os.environ["DLT_NO_NATIVE"]
            assert frame == oracle, (shape, mode, "dense bytes diverged")
            np.testing.assert_array_equal(tc.decode_tensor(frame), decoded_py)
            oracle_cases += 1

    # --- The ~200-case corruption-fuzz corpus (public decode path) ---- #
    fuzz_rng = np.random.default_rng(99)
    frames = corpus._base_frames()
    fuzz_cases = rejected = 0
    mutants = []
    while fuzz_cases < 200:
        frame, flat = frames[int(fuzz_rng.integers(len(frames)))]
        roll = int(fuzz_rng.integers(3))
        if roll == 0:
            mutant = frame[: int(fuzz_rng.integers(0, len(frame)))]
        elif roll == 1:
            b = bytearray(frame)
            pos = int(fuzz_rng.integers(len(b)))
            b[pos] ^= 1 << int(fuzz_rng.integers(8))
            mutant = bytes(b)
        else:
            b = bytearray(frame)
            if len(b) <= 16:
                continue
            pos = int(fuzz_rng.integers(8, len(b) - 8))
            val = int(fuzz_rng.choice([
                0xFFFFFFFF, 0x7FFFFFFF, len(b) * 2, int(flat.size), 1 << 28,
            ]))
            b[pos : pos + 4] = struct.pack("<I", val)
            mutant = corpus._recrc(bytes(b))
        fuzz_cases += 1
        mutants.append((mutant, flat.size))
        try:
            out = tc.decode_fused_sparse(mutant)
        except (tc.CodecError, ValueError):
            rejected += 1
            continue
        assert out.shape == (flat.size,)

    # --- FaultPlan harness mutants (ISSUE 13): the fault injector's
    # deterministic corrupt/truncate mutations must reject with
    # CodecError under the instrumented engine too, then ride the raw
    # redzoned replay below with the rest of the corpus. ------------- #
    fault_mutants = corpus._faultplan_mutants()
    fault_cases = 0
    for mutant, _total in fault_mutants:
        try:
            tc.decode_fused_sparse(mutant)
        except (tc.CodecError, ValueError):
            fault_cases += 1
            continue
        print(
            "native-san-replay: faultplan mutant decoded instead of "
            "rejecting", file=sys.stderr,
        )
        return 4
    mutants.extend(fault_mutants)

    # --- Raw C entry points on sanitizer-malloc'd (redzoned) buffers -- #
    import ctypes

    alloc = _AsanAlloc(asan)
    lib = wire._load()
    raw_cases = 0
    for mutant, total in mutants + [(f, fl.size) for f, fl in frames]:
        in_ptr, _ = alloc.buf(mutant)
        out_ptr, _ = alloc.buf(size=max(total * 4, 1))
        # (argtypes declare c_char_p for the frame pointer; cast keeps
        # the sanitizer-malloc'd address instead of a python copy.)
        in_cp = ctypes.cast(ctypes.c_void_p(in_ptr), ctypes.c_char_p)
        lib.dlt_wire_fused_decode(
            in_cp, ctypes.c_uint64(len(mutant)),
            ctypes.c_void_p(out_ptr), ctypes.c_uint64(total),
        )
        lib.dlt_wire_crc32(
            in_cp, ctypes.c_size_t(len(mutant)),
            ctypes.c_uint32(0),
        )
        # ABI v3 entries (zero-copy wire path): the no-output validation
        # walk, then the fused scatter-add into an exact-size redzoned
        # target.  validate-before-first-write is part of the contract —
        # a rejected apply must leave the redzoned target byte-identical
        # (the target is live CHOCO hat state in production).
        lib.dlt_wire_fused_validate(
            in_cp, ctypes.c_uint64(len(mutant)), ctypes.c_uint64(total),
        )
        tgt_ptr, tgt_n = alloc.buf(size=max(total * 4, 1))
        before = alloc.read(tgt_ptr, tgt_n)
        rc = int(lib.dlt_wire_fused_apply(
            in_cp, ctypes.c_uint64(len(mutant)),
            ctypes.c_void_p(tgt_ptr), ctypes.c_uint64(total),
            ctypes.c_float(0.5),
        ))
        if rc < 0 and alloc.read(tgt_ptr, tgt_n) != before:
            print(
                "native-san-replay: rejected fused_apply wrote into its "
                "target", file=sys.stderr,
            )
            alloc.free(tgt_ptr)
            alloc.free(in_ptr)
            alloc.free(out_ptr)
            return 5
        alloc.free(tgt_ptr)
        alloc.free(in_ptr)
        alloc.free(out_ptr)
        raw_cases += 1
    # Encode into an exact-size redzoned output: any write past the
    # measured frame size is an immediate ASan abort.
    for name, flat, buckets in corpus._scenarios():
        for mode in corpus._MODES:
            modes = tc._bucket_modes(
                tuple(buckets), mode.get("bf16_wire", False),
                mode.get("int8_wire", False),
            )
            flat32 = np.ascontiguousarray(flat, np.float32).ravel()
            span_off, span_size, ptr_arr, mode_arr = wire._span_arrays(
                tuple((m, spans) for m, (_n, spans) in zip(modes, buckets))
            )
            ks = np.zeros(len(buckets), dtype=np.uint64)
            maxabs = np.zeros(len(buckets), dtype=np.float32)
            flat_ptr, _ = alloc.buf(flat32.tobytes(), size=flat32.nbytes)
            size = int(lib.dlt_wire_fused_size(
                ctypes.c_void_p(flat_ptr), ctypes.c_uint64(flat32.size),
                span_off.ctypes.data, span_size.ctypes.data,
                ptr_arr.ctypes.data, mode_arr.ctypes.data,
                ctypes.c_uint32(len(buckets)),
                ks.ctypes.data, maxabs.ctypes.data,
            ))
            if size > 0:
                out_ptr, _ = alloc.buf(size=size)
                n = int(lib.dlt_wire_fused_encode(
                    ctypes.c_void_p(flat_ptr), ctypes.c_uint64(flat32.size),
                    span_off.ctypes.data, span_size.ctypes.data,
                    ptr_arr.ctypes.data, mode_arr.ctypes.data,
                    ctypes.c_uint32(len(buckets)),
                    ks.ctypes.data, maxabs.ctypes.data,
                    ctypes.c_void_p(out_ptr), ctypes.c_uint64(size),
                ))
                assert n == size, (name, mode, n, size)
                alloc.free(out_ptr)
            alloc.free(flat_ptr)
            raw_cases += 1

    # --- decode_apply ↔ Python scatter oracle under the instrumented
    # engine (ISSUE 18): the fused in-place consume must stay
    # ulp-identical to the numpy np.add.at reference. ----------------- #
    apply_cases = 0
    arng = np.random.default_rng(11)
    for frame, flat in frames:
        base = arng.normal(size=flat.size).astype(np.float32)
        got = base.copy()
        tc.decode_fused_apply(frame, got, scale=0.25)
        ref = base.copy()
        os.environ["DLT_NO_NATIVE"] = "1"
        tc.decode_fused_apply(frame, ref, scale=0.25)
        del os.environ["DLT_NO_NATIVE"]
        np.testing.assert_array_equal(got, ref)
        apply_cases += 1

    print(
        "native-san-replay: ok "
        f"(oracle={oracle_cases} fuzz={fuzz_cases} rejected={rejected} "
        f"fault={fault_cases} raw={raw_cases} apply={apply_cases})"
    )
    return 0


if __name__ == "__main__":
    sys.exit(_replay())
