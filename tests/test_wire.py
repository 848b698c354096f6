"""Native wire engine (ISSUE 9): oracle matrix, corruption fuzz, build
hardening.

Three layers, all tier-1 (no mesh, no jitted programs):

* **Wire-oracle matrix** — the public codec (native engine when it
  builds) must be byte-identical to the pure-Python oracle
  (``_encode_fused_sparse_py`` / forced ``DLT_NO_NATIVE=1``) across
  dtype-bucket mixes, NaN payloads, empty buckets, zero-length trees,
  and both frame kinds.  Every matrix test runs twice via the
  ``wire_path`` fixture — once on the native engine, once with the
  fallback forced — so correctness never needs a toolchain.
* **Corruption/fuzz property test** — ~200 seeded mutations of valid
  frames (truncation, bit flips, adversarial section lengths/offsets
  with a re-stamped crc) must raise ``CodecError`` and never segfault
  or scatter out of bounds, on both paths.
* **Build hardening** — ``dlt_abi_version()`` is checked at load: a
  stale cached ``.so`` missing the symbol (or reporting the wrong
  version) triggers a rebuild; failed g++ builds warn once on
  ``dlt.native`` and bump the ``native.build_failed`` counter.
"""

import ctypes
import os
import struct
import subprocess
import zlib

import numpy as np
import pytest

from distributed_learning_tpu import native
from distributed_learning_tpu.comm import tensor_codec as tc
from distributed_learning_tpu.comm.tensor_codec import (
    CodecError,
    decode_fused_sparse,
    decode_tensor,
    encode_fused_sparse,
    encode_tensor,
)
from distributed_learning_tpu.native import wire
from distributed_learning_tpu.obs import MetricsRegistry, use_registry

_HAVE_NATIVE = wire.available()


@pytest.fixture(params=["native", "python"])
def wire_path(request, monkeypatch):
    """Run the test on the native engine AND with the fallback forced.

    ``DLT_NO_NATIVE`` is honored per call by the codec's dispatcher, so
    setting it mid-process flips the served path without reloads."""
    if request.param == "native":
        if not _HAVE_NATIVE:
            pytest.skip("native wire engine unavailable in this env")
        monkeypatch.delenv("DLT_NO_NATIVE", raising=False)
    else:
        monkeypatch.setenv("DLT_NO_NATIVE", "1")
    return request.param


def _sparsify(rng, dense, keep=0.1):
    return np.where(
        rng.random(dense.size) < keep, dense, 0.0
    ).astype(np.float32)


def _scenarios():
    """(name, flat, buckets) — the fused-frame shapes the fleet ships."""
    rng = np.random.default_rng(42)
    out = []
    # Mixed bf16+f32 buckets, multi-span, ~10% density (a model tree's
    # dtype_buckets() shape).
    flat = _sparsify(rng, rng.normal(size=4096).astype(np.float32))
    out.append((
        "mixed",
        flat,
        (
            ("bfloat16", ((0, 1024), (3072, 512))),
            ("float32", ((1024, 2048), (3584, 512))),
        ),
    ))
    # float16-origin bucket (also a _BF16_ORIGIN narrow-always dtype).
    out.append((
        "f16_origin",
        _sparsify(rng, rng.normal(size=256).astype(np.float32)),
        (("float16", ((0, 128),)), ("float32", ((128, 128),))),
    ))
    # Empty value sets: an all-zero bucket and a bucket with no spans.
    z = np.zeros(64, np.float32)
    z[50] = 1.5
    out.append((
        "empty_bucket",
        z,
        (("bfloat16", ()), ("float32", ((0, 32), (32, 32)))),
    ))
    # Zero-length tree: no buckets, no elements.
    out.append(("zero_tree", np.zeros(0, np.float32), ()))
    # Fully dense ravel (k == total; worst-case frame).
    out.append((
        "all_dense",
        rng.normal(size=512).astype(np.float32) + 0.25,
        (("float32", ((0, 512),)),),
    ))
    return out


_MODES = [
    {},
    {"bf16_wire": True},
    {"int8_wire": True},
]


@pytest.mark.parametrize(
    "name,flat,buckets", _scenarios(), ids=[s[0] for s in _scenarios()]
)
@pytest.mark.parametrize(
    "mode", _MODES, ids=["plain", "bf16", "int8"]
)
def test_fused_matrix_byte_identical_to_python_oracle(
    wire_path, name, flat, buckets, mode
):
    """The full fused-frame matrix: public path == Python oracle bytes,
    decode agreement, and semantic round-trip per wire mode."""
    frame = encode_fused_sparse(flat, buckets, **mode)
    modes = tc._bucket_modes(
        tuple(buckets), mode.get("bf16_wire", False),
        mode.get("int8_wire", False),
    )
    oracle = tc._encode_fused_sparse_py(flat, tuple(buckets), modes)
    assert frame == oracle, (wire_path, name, mode)
    out = decode_fused_sparse(frame)
    np.testing.assert_array_equal(
        out, tc._decode_fused_sparse_py(frame, len(buckets), flat.size)
    )
    # Semantics: f32 sections exact under plain; bf16 sections are the
    # RNE narrowing; int8 bounded by scale/2 per bucket.
    if not mode:
        for bname, spans in buckets:
            for off, size in spans:
                seg, got = flat[off : off + size], out[off : off + size]
                if bname in tc._BF16_ORIGIN:
                    exp = native.bf16_to_f32(native.f32_to_bf16(seg))
                    exp = np.where(seg == 0, 0.0, exp).astype(np.float32)
                    np.testing.assert_array_equal(got, exp)
                else:
                    np.testing.assert_array_equal(got, seg)
    elif mode.get("int8_wire"):
        # The int8 scale is per BUCKET (max|v| over the nonzeros of all
        # its spans), so the quantization error bound is bucket-wide.
        for _bname, spans in buckets:
            segs = [flat[off : off + size] for off, size in spans]
            cat = np.concatenate(segs) if segs else np.zeros(0, np.float32)
            nz = cat[cat != 0]
            scale = float(np.abs(nz).max() / 127.0) if nz.size else 0.0
            for off, size in spans:
                assert float(
                    np.abs(out[off : off + size] - flat[off : off + size])
                    .max(initial=0.0)
                ) <= 0.5 * scale + 1e-9


def test_fused_nan_payload_survives_bf16_and_refuses_int8(wire_path):
    """A NaN-poisoned correction must stay LOUD: carried through the
    bf16/f32 frames, refused (CodecError) by the int8 quantizer."""
    flat = np.zeros(128, np.float32)
    flat[3] = np.nan
    flat[77] = 2.0
    buckets = (("bfloat16", ((0, 64),)), ("float32", ((64, 64),)))
    for kw in ({}, {"bf16_wire": True}):
        out = decode_fused_sparse(encode_fused_sparse(flat, buckets, **kw))
        assert np.isnan(out[3]) and out[77] == 2.0
    with pytest.raises(CodecError, match="finite"):
        encode_fused_sparse(flat, buckets, int8_wire=True)
    # Inf poisons the int8 scale the same way.
    flat[3] = np.inf
    with pytest.raises(CodecError, match="finite"):
        encode_fused_sparse(flat, buckets, int8_wire=True)


@pytest.mark.parametrize(
    "shape", [(), (0,), (7,), (64, 33), (2, 3, 4)],
    ids=["0d", "empty", "vec", "mat", "3d"],
)
@pytest.mark.parametrize("mode", _MODES, ids=["plain", "bf16", "int8"])
def test_dense_matrix_byte_identical_across_paths(
    wire_path, monkeypatch, shape, mode
):
    """Dense frames: the served path's bytes equal the forced-fallback
    bytes, and decode agrees — the dense half of the wire matrix."""
    rng = np.random.default_rng(7)
    x = rng.normal(size=shape).astype(np.float32)
    frame = encode_tensor(x, **mode)
    monkeypatch.setenv("DLT_NO_NATIVE", "1")
    oracle = encode_tensor(x, **mode)
    decoded_py = decode_tensor(frame)
    monkeypatch.delenv("DLT_NO_NATIVE")
    assert frame == oracle
    np.testing.assert_array_equal(decode_tensor(frame), decoded_py)


def test_dense_non_f32_dtypes_keep_python_path(wire_path):
    """int32/bool/f64 payloads (control-plane tensors) round-trip
    unchanged — the native fast path only claims f32-sourced frames."""
    for arr in (
        np.arange(12, dtype=np.int32).reshape(3, 4),
        np.asarray([True, False, True]),
        np.linspace(0, 1, 9, dtype=np.float64),
    ):
        np.testing.assert_array_equal(decode_tensor(encode_tensor(arr)), arr)


def test_wire_gauge_records_serving_path(monkeypatch):
    """`comm.wire.native` says which engine ran — run reports and bench
    records read it instead of guessing from the environment."""
    flat = np.asarray([1.0, 0.0, 2.0], np.float32)
    buckets = (("float32", ((0, 3),)),)
    reg = MetricsRegistry()
    with use_registry(reg):
        encode_fused_sparse(flat, buckets)
    expected = 1.0 if _HAVE_NATIVE else 0.0
    assert reg.snapshot()["gauges"]["comm.wire.native"] == expected
    reg2 = MetricsRegistry()
    monkeypatch.setenv("DLT_NO_NATIVE", "1")
    with use_registry(reg2):
        encode_fused_sparse(flat, buckets)
    assert reg2.snapshot()["gauges"]["comm.wire.native"] == 0.0


# --------------------------------------------------------------------- #
# Zero-copy receive path (ISSUE 18): out= scratch, fused apply, lazy    #
# frames                                                                #
# --------------------------------------------------------------------- #
def test_fused_decode_out_matrix_matches_alloc_path(wire_path):
    """``decode_fused_sparse(out=)`` into a NaN-dirty scratch must equal
    the allocating decode bit-for-bit across the full scenario/mode
    matrix AND hand back the caller's scratch — the zero-copy contract:
    no allocation, no dirty-scratch leak into untouched positions."""
    for name, flat, buckets in _scenarios():
        for mode in _MODES:
            frame = encode_fused_sparse(flat, buckets, **mode)
            ref = decode_fused_sparse(frame)
            scratch = np.full(flat.size, np.nan, np.float32)
            got = decode_fused_sparse(frame, out=scratch)
            assert np.shares_memory(got, scratch) or flat.size == 0
            np.testing.assert_array_equal(got, ref, err_msg=(name, mode))
            # Untouched positions are exactly zero-filled, never NaN.
            assert not np.isnan(got).any() or np.isnan(flat).any()


def test_dense_decode_out_matrix_matches_alloc_path(wire_path):
    """``decode_tensor(out=)``: same bytes as the allocating decode, into
    caller scratch, for every shape and wire mode."""
    rng = np.random.default_rng(18)
    for shape in [(), (0,), (7,), (64, 33), (2, 3, 4)]:
        x = rng.normal(size=shape).astype(np.float32)
        for mode in _MODES:
            frame = encode_tensor(x, **mode)
            ref = decode_tensor(frame)
            scratch = np.full(max(x.size, 1) if shape == () else x.size,
                              np.nan, np.float32)
            got = decode_tensor(frame, out=scratch)
            assert got.shape == ref.shape
            np.testing.assert_array_equal(got, ref, err_msg=(shape, mode))
            assert np.shares_memory(got, scratch) or x.size == 0


def test_decode_out_contract_rejects_bad_scratch(wire_path):
    """A bad ``out=`` is a CALLER bug (ValueError before any parse work),
    never a wire error: wrong size, dtype, layout, writability."""
    flat = np.asarray([0.0, 1.0, 0.0, -2.0], np.float32)
    frame = encode_fused_sparse(flat, (("float32", ((0, 4),)),))
    with pytest.raises(ValueError, match="elements"):
        decode_fused_sparse(frame, out=np.zeros(3, np.float32))
    with pytest.raises(ValueError, match="float32"):
        decode_fused_sparse(frame, out=np.zeros(4, np.float64))
    with pytest.raises(ValueError, match="contiguous"):
        decode_fused_sparse(frame, out=np.zeros(8, np.float32)[::2])
    frozen = np.zeros(4, np.float32)
    frozen.setflags(write=False)
    with pytest.raises(ValueError, match="writ"):
        decode_fused_sparse(frame, out=frozen)
    with pytest.raises(ValueError, match="ndarray"):
        decode_fused_sparse(frame, out=[0.0] * 4)


def test_fused_apply_matches_dense_oracle_and_preserves_bytes(wire_path):
    """``decode_fused_apply``: ulp-identical to the densify-then-add form
    on touched positions, BYTE-identical on untouched ones (the dense
    form perturbs ``-0.0``; the fused scatter never visits it)."""
    for name, flat, buckets in _scenarios():
        for mode in _MODES:
            frame = encode_fused_sparse(flat, buckets, **mode)
            dense = decode_fused_sparse(frame)
            rng = np.random.default_rng(5)
            base = rng.normal(size=flat.size).astype(np.float32)
            sentinel = None
            untouched = np.flatnonzero(dense == 0)
            # Plant a -0.0 in an untouched slot: its sign bit must
            # survive the apply (and would not survive `+= 0.5*dense`).
            for j in untouched:
                if flat[j] == 0:
                    base[j] = np.float32(-0.0)
                    sentinel = int(j)
                    break
            target = base.copy()
            got = tc.decode_fused_apply(frame, target, scale=0.5)
            assert got is target
            ref = base + np.float32(0.5) * dense
            np.testing.assert_array_equal(got, ref, err_msg=(name, mode))
            if sentinel is not None:
                assert np.signbit(got[sentinel]), (name, mode)


def test_fused_apply_corruption_leaves_live_target_untouched(wire_path):
    """CodecError from ``decode_fused_apply`` guarantees the target kept
    its exact bytes — it is live CHOCO hat state, not scratch.  Replays
    the fault-harness mutants, the adversarial crc-clean headers, and a
    seeded corruption corpus through the apply path."""
    rng = np.random.default_rng(77)
    base_frames = _base_frames()
    corpus = list(_faultplan_mutants())
    # Seeded extra mutants: bit flips and crc-clean u32 stomps.
    for _ in range(60):
        frame, flat = base_frames[int(rng.integers(len(base_frames)))]
        b = bytearray(frame)
        if rng.integers(2):
            pos = int(rng.integers(len(b)))
            b[pos] ^= 1 << int(rng.integers(8))
            corpus.append((bytes(b), flat.size))
        else:
            pos = int(rng.integers(8, max(9, len(b) - 8)))
            b[pos : pos + 4] = struct.pack(
                "<I", int(rng.choice([0xFFFFFFFF, len(b) * 2, 1 << 28]))
            )
            corpus.append((_recrc(bytes(b)), flat.size))
    applied = rejected = 0
    for mutant, total in corpus:
        target = rng.normal(size=total).astype(np.float32)
        before = target.tobytes()
        try:
            tc.decode_fused_apply(mutant, target, scale=0.5)
            applied += 1  # survivor: landed in a value payload
        except (CodecError, ValueError):
            rejected += 1
            assert target.tobytes() == before, "rejected apply wrote"
    assert rejected >= len(_faultplan_mutants())  # all harness mutants


def test_lazy_frames_validate_at_construction_and_defer_densify(
    wire_path,
):
    """The lazy receive payloads: construction validates (corrupt frames
    raise CodecError at unpack time, preserving the mux drop
    discipline); densify/apply defer to caller scratch and agree with
    the eager decodes."""
    rng = np.random.default_rng(21)
    flat = _sparsify(rng, rng.normal(size=512).astype(np.float32))
    buckets = (("bfloat16", ((0, 256),)), ("float32", ((256, 256),)))
    frame = encode_fused_sparse(flat, buckets, bf16_wire=True)
    lazy = tc.FusedFrame(frame)
    assert lazy.size == 512 and lazy.shape == (512,)
    ref = decode_fused_sparse(frame)
    scratch = np.full(512, np.nan, np.float32)
    np.testing.assert_array_equal(lazy.densify(out=scratch), ref)
    base = rng.normal(size=512).astype(np.float32)
    target = base.copy()
    lazy.apply_into(target, scale=0.25)
    np.testing.assert_array_equal(
        target, tc.decode_fused_apply(frame, base.copy(), scale=0.25)
    )
    np.testing.assert_array_equal(np.asarray(lazy), ref)
    # Corruption is caught at CONSTRUCTION, not first densify.
    b = bytearray(frame)
    b[12:16] = struct.pack("<I", 0xFFFFFFFF)
    with pytest.raises(CodecError):
        tc.FusedFrame(_recrc(bytes(b)))
    with pytest.raises(CodecError):
        tc.FusedFrame(frame[: len(frame) // 2])
    # Dense twin: same contract.
    x = rng.normal(size=(16, 8)).astype(np.float32)
    dlazy = tc.DenseFrame(encode_tensor(x, bf16_wire=True))
    assert dlazy.shape == (16, 8) and dlazy.size == 128
    dref = decode_tensor(encode_tensor(x, bf16_wire=True))
    dscratch = np.full(128, np.nan, np.float32)
    np.testing.assert_array_equal(dlazy.densify(out=dscratch), dref)


# --------------------------------------------------------------------- #
# Corruption / fuzz property test                                       #
# --------------------------------------------------------------------- #
def _recrc(frame: bytes) -> bytes:
    body = frame[:-4]
    return body + struct.pack("<I", zlib.crc32(body) & 0xFFFFFFFF)


def _base_frames():
    rng = np.random.default_rng(1234)
    frames = []
    for total, buckets in [
        (256, (("bfloat16", ((0, 128),)), ("float32", ((128, 128),)))),
        (64, (("float32", ((0, 64),)),)),
    ]:
        flat = _sparsify(rng, rng.normal(size=total).astype(np.float32),
                         keep=0.3)
        for kw in ({}, {"bf16_wire": True}, {"int8_wire": True}):
            frames.append(
                (encode_fused_sparse(flat, buckets, **kw), flat)
            )
    return frames


def test_fused_fuzz_corruption_never_scatters(wire_path):
    """~200 seeded mutations per path — truncations, bit flips, and
    adversarial section lengths/offsets/counts re-stamped with a valid
    crc — must ALL raise CodecError (crc or bounds), never segfault,
    never return a silently-wrong ravel of a different shape."""
    rng = np.random.default_rng(99)
    frames = _base_frames()
    cases = rejected = 0
    while cases < 200:
        frame, flat = frames[int(rng.integers(len(frames)))]
        roll = int(rng.integers(3))
        if roll == 0:  # truncation at a random point
            cut = int(rng.integers(0, len(frame)))
            mutant = frame[:cut]
        elif roll == 1:  # single bit flip anywhere
            b = bytearray(frame)
            pos = int(rng.integers(len(b)))
            b[pos] ^= 1 << int(rng.integers(8))
            mutant = bytes(b)
        else:  # adversarial section field + valid crc
            b = bytearray(frame)
            # Overwrite a u32 inside the section area (k, an index, a
            # vlen, a dims field...) with an extreme value.
            if len(b) <= 16:
                continue
            pos = int(rng.integers(8, len(b) - 8))
            val = int(rng.choice([0xFFFFFFFF, 0x7FFFFFFF, len(b) * 2,
                                  int(flat.size), 1 << 28]))
            b[pos : pos + 4] = struct.pack("<I", val)
            mutant = _recrc(bytes(b))
        cases += 1
        try:
            out = decode_fused_sparse(mutant)
        except (CodecError, ValueError):
            rejected += 1
            continue
        # The rare mutant that still decodes must be a coherent frame:
        # right size, and (bit flips aside) values where the crc says.
        assert out.shape == (flat.size,)
    # Truncations and bit flips must ALL be rejected (the crc covers
    # every byte); only the adversarial-u32-then-recrc class may
    # legitimately survive — when the overwrite lands inside a value
    # payload it IS a valid frame.  Seeded generator: deterministic.
    assert rejected >= 150, (rejected, cases)


def _faultplan_mutants(seed=4242):
    """The fault harness's two deterministic wire mutations
    (``comm/faults.py``) applied to every fuzz-corpus base frame:
    post-crc byte flip (``corrupt_bytes``) and pre-crc truncation
    re-stamped checksum-clean (``truncate_bytes`` + ``_recrc``).
    Shared with the ``--native`` sanitizer replay
    (``tools/graftlint/native_san.py``), so the same mutants that prove
    semantic rejection here prove memory-safe rejection there."""
    from distributed_learning_tpu.comm.faults import FaultPlan

    plan = FaultPlan(seed=seed)
    out = []
    for i, (frame, flat) in enumerate(_base_frames()):
        out.append((plan.corrupt_bytes(i, frame), flat.size))
        out.append((_recrc(plan.truncate_bytes(i, frame[:-4])), flat.size))
    return out


def test_faultplan_corruptions_rejected_before_scatter(wire_path):
    """ISSUE 13: every corruption the fault-injection harness can put on
    the wire — the crc-dirty flip AND the crc-clean structural
    truncation — must raise CodecError before any scatter, on both
    engines, and the seeded mutant set must replay bit-identically
    (the FaultPlan determinism contract at the codec boundary)."""
    mutants = _faultplan_mutants()
    assert len(mutants) == 2 * len(_base_frames())
    assert mutants == _faultplan_mutants()  # seeded: replay-identical
    for mutant, _total in mutants:
        with pytest.raises((CodecError, ValueError)):
            decode_fused_sparse(mutant)


def test_fused_adversarial_sections_raise_bounds_not_write(wire_path):
    """Targeted adversarial section headers with VALID checksums: the
    bounds check (not the crc) must reject every one before scatter."""
    flat = np.zeros(32, np.float32)
    flat[[1, 9, 30]] = [1.0, -2.0, 3.0]
    frame = encode_fused_sparse(flat, (("float32", ((0, 32),)),))
    # k inflated past the ravel.
    b = bytearray(frame)
    b[8:12] = struct.pack("<I", 1000)
    with pytest.raises(CodecError):
        decode_fused_sparse(_recrc(bytes(b)))
    # Scatter index == total (one past the end).
    b = bytearray(frame)
    b[12:16] = struct.pack("<I", 32)
    with pytest.raises(CodecError, match="range"):
        decode_fused_sparse(_recrc(bytes(b)))
    # Value-section length lying about its payload.
    b = bytearray(frame)
    vlen_off = 8 + 4 + 4 * 3  # header | k | idx[3]
    b[vlen_off : vlen_off + 4] = struct.pack("<I", 5)
    with pytest.raises(CodecError):
        decode_fused_sparse(_recrc(bytes(b)))
    # Trailing slack between the last section and the crc.
    with pytest.raises(CodecError):
        decode_fused_sparse(_recrc(frame[:-4] + b"\x00\x00" + frame[-4:]))


def test_fused_unsupported_value_dtype_falls_back_to_python_oracle():
    """A crc-valid frame whose value section rides a dtype the native
    engine does not speak (here f64) must decode through the Python
    oracle — identically on both paths, never an error."""
    idx = np.asarray([2, 5], np.uint32)
    vals = np.asarray([1.5, -2.5], np.float64)
    vframe = encode_tensor(vals)
    body = (
        struct.pack("<BBBBI", 0xFE, 1, 1, 0, 8)
        + struct.pack("<I", 2) + idx.tobytes()
        + struct.pack("<I", len(vframe)) + vframe
    )
    frame = body + struct.pack("<I", zlib.crc32(body) & 0xFFFFFFFF)
    out = decode_fused_sparse(frame)
    np.testing.assert_array_equal(
        out, np.asarray([0, 0, 1.5, 0, 0, -2.5, 0, 0], np.float32)
    )


# --------------------------------------------------------------------- #
# Build hardening: ABI versioning, stale caches, failure visibility     #
# --------------------------------------------------------------------- #
def _have_gxx() -> bool:
    try:
        subprocess.run(["g++", "--version"], capture_output=True, timeout=30)
        return True
    except (OSError, subprocess.SubprocessError):
        return False


def test_abi_version_matches_loaded_libraries():
    if not _HAVE_NATIVE:
        pytest.skip("native wire engine unavailable in this env")
    for lib in (native._load(), wire._load()):
        assert lib is not None
        fn = lib.dlt_abi_version
        fn.restype = ctypes.c_uint32
        assert int(fn()) == native._ABI_VERSION


def _mini_src(marker: int) -> str:
    return (
        "#include <cstdint>\n"
        'extern "C" { uint32_t dlt_abi_version() { return %du; }\n'
        "int dlt_mini_marker() { return %d; } }\n"
        % (native._ABI_VERSION, marker)
    )


def _marker(lib) -> int:
    lib.dlt_mini_marker.restype = ctypes.c_int
    return lib.dlt_mini_marker()


def test_copied_in_so_is_rebuilt_not_loaded(tmp_path):
    """A ``.so`` this host did not build from the checked-in source (one
    copied in with the tree, or left by an older source, whatever its
    mtime) sits under another name than the keyed one and is never
    opened: _load_lib builds from source instead."""
    if not _have_gxx():
        pytest.skip("no g++ in this environment")
    src = tmp_path / "mini.cpp"
    lib_path = tmp_path / "_mini.so"
    src.write_text(_mini_src(7))
    # A foreign library at the un-keyed name, postdated so an mtime
    # check would keep serving it; it lacks every symbol.
    stale_src = tmp_path / "stale.cpp"
    stale_src.write_text('extern "C" { int old_symbol() { return 1; } }\n')
    subprocess.run(
        ["g++", "-O0", "-shared", "-fPIC", str(stale_src), "-o",
         str(lib_path)],
        check=True, capture_output=True, timeout=120,
    )
    os.utime(lib_path, (2**31 - 10, 2**31 - 10))
    lib = native._load_lib(str(src), str(lib_path), lambda l: None)
    assert lib is not None, "the foreign library must not be served"
    assert _marker(lib) == 7
    built = sorted(p.name for p in tmp_path.glob("_mini.*.so"))
    assert len(built) == 1 and built[0] != "_mini.so"


def test_so_name_is_keyed_on_source_and_flags(tmp_path, monkeypatch):
    """Editing the source (or changing the flags) changes the library's
    name, so the next load rebuilds; the superseded build is removed."""
    if not _have_gxx():
        pytest.skip("no g++ in this environment")
    src = tmp_path / "mini.cpp"
    lib_path = str(tmp_path / "_mini.so")
    src.write_text(_mini_src(7))
    first = native._build_lib(str(src), lib_path)
    assert native._build_lib(str(src), lib_path) == first  # cached
    src.write_text(_mini_src(8))
    second = native._build_lib(str(src), lib_path)
    assert second != first
    assert not os.path.exists(first) and os.path.exists(second)
    assert _marker(ctypes.CDLL(second)) == 8
    monkeypatch.setenv("DLT_NATIVE_EXTRA_CFLAGS", "-DDLT_OTHER_FLAGS")
    assert native._build_lib(str(src), lib_path) != second


def test_wrong_abi_after_rebuild_falls_back_with_counter(tmp_path):
    """A source that genuinely reports the wrong ABI (toolchain/source
    skew) must end in the Python fallback with the failure counted."""
    if not _have_gxx():
        pytest.skip("no g++ in this environment")
    src = tmp_path / "wrong.cpp"
    src.write_text(
        "#include <cstdint>\n"
        'extern "C" { uint32_t dlt_abi_version() { return 424242u; } }\n'
    )
    reg = MetricsRegistry()
    with use_registry(reg):
        lib = native._load_lib(
            str(src), str(tmp_path / "_wrong.so"), lambda l: None
        )
    assert lib is None
    assert reg.snapshot()["counters"]["native.build_failed"] == 1


def test_failed_build_warns_and_bumps_counter(tmp_path, caplog):
    """g++ failing must be VISIBLE: one dlt.native warning and a
    native.build_failed counter bump (it used to return None silently)."""
    if not _have_gxx():
        pytest.skip("no g++ in this environment")
    src = tmp_path / "broken.cpp"
    src.write_text("this is not C++\n")
    reg = MetricsRegistry()
    with caplog.at_level("WARNING", logger="dlt.native"):
        with use_registry(reg):
            out = native._build_lib(str(src), str(tmp_path / "_broken.so"))
    assert out is None
    assert reg.snapshot()["counters"]["native.build_failed"] == 1
    assert any("native build" in r.message for r in caplog.records)


def test_so_artifacts_are_gitignored():
    """The built libraries are per-box artifacts: they must never be
    trackable (a committed .so from one box is a stale cache on every
    other)."""
    repo = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
    out = subprocess.run(
        ["git", "check-ignore",
         "distributed_learning_tpu/native/_codec.0123456789abcdef.so",
         "distributed_learning_tpu/native/_wire.0123456789abcdef.so"],
        cwd=repo, capture_output=True, text=True,
    )
    assert out.returncode == 0, "native *.so must be gitignored"
    tracked = subprocess.run(
        ["git", "ls-files", "distributed_learning_tpu/native/"],
        cwd=repo, capture_output=True, text=True,
    ).stdout
    assert ".so" not in tracked
