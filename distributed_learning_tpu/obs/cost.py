"""Device-cost observatory: what a compiled dispatch actually costs.

PR 6's run-wide plane says *who* is slow; this module says *why*: it
reads the costs XLA already knows about every compiled program —
FLOPs and bytes accessed (``compiled.cost_analysis()``), peak HBM and
argument/output/temp/donated bytes (``compiled.memory_analysis()``) —
plus the collective inventory (the same HLO scan the graftlint audit
pins), and pairs that *static* profile with *measured* step time so
throughput claims decompose into compute vs. communication vs. idle
(the decomposition adaptive-synchronization schedules are built on:
arxiv.org/pdf/2002.01119, arxiv.org/pdf/1910.13598).

Two pieces, both host-side, neither touching a compiled program:

* :class:`CostProfile` — extracted from any jitted entry point via the
  AOT ``.lower(...).compile()`` surface (``InstrumentedStep`` delegates
  both, so instrumented tp/pp steps profile without unwrapping) and
  registered process-wide by program name
  (:func:`profile_fn` / :func:`get_profile` / :func:`all_profiles`).
  Registration also lands ``cost.*`` gauges in the metrics registry, so
  profiles ride ``run_report()`` / obs deltas / ``obs-report`` with no
  new plumbing.
* :class:`SampledDispatchTimer` — the measurement side: an explicit
  ``jax.block_until_ready`` on 1-in-N dispatches at chunk boundaries
  only, **off by default** (``every_n=0``).  A sampled chunk records
  ``cost.step_time_s`` and, when the program's profile and the chip's
  peak FLOP/s are known, the ``cost.mfu`` / ``cost.bytes_per_sec``
  gauges.  Unsampled dispatches pay two integer ops on the host —
  nothing on the device, no program change (the obs on/off bit-identity
  oracle covers the timer).

MFU definition: ``achieved FLOP/s / peak FLOP/s`` where achieved is the
compiled program's XLA-counted FLOPs per dispatch times dispatches over
wall seconds, and peak comes from :func:`device_peak_flops` — a dense
bf16/fp16 per-chip table keyed on ``jax.Device.device_kind``,
overridable with ``DLT_PEAK_FLOPS`` (unknown chips and CPU return None:
no peak, no MFU, never a made-up number).

Importable without jax; jax is imported lazily inside the extraction
paths only.
"""

from __future__ import annotations

import dataclasses
import os
import threading
import time
from typing import Any, Callable, Dict, Optional

__all__ = [
    "CostProfile",
    "SampledDispatchTimer",
    "profile_fn",
    "register_profile",
    "get_profile",
    "all_profiles",
    "clear_profiles",
    "device_peak_flops",
    "mfu",
    "PEAK_FLOPS_ENV",
]

#: env override for the chip's peak dense FLOP/s (a float, e.g. 197e12).
PEAK_FLOPS_ENV = "DLT_PEAK_FLOPS"

#: Peak dense bf16 FLOP/s per chip, keyed on a lowercase substring of
#: ``jax.Device.device_kind``.  Longest match wins (``"v5 lite"`` before
#: ``"v5"``).  Sources: published TPU per-chip peaks (v2 45T, v3 123T,
#: v4 275T, v5e 197T, v5p 459T, v6e/Trillium 918T).  CPU has no entry
#: on purpose: MFU against an unknown peak is noise.
PEAK_FLOPS_TABLE: Dict[str, float] = {
    "v6e": 918e12,
    "trillium": 918e12,
    "v5p": 459e12,
    "v5 lite": 197e12,
    "v5e": 197e12,
    "v5litepod": 197e12,
    "v4": 275e12,
    "v3": 123e12,
    "v2": 45e12,
}


def device_peak_flops(device: Any = None) -> Optional[float]:
    """Peak dense FLOP/s of ``device`` (default: ``jax.devices()[0]``),
    or None when the chip is unknown (CPU, new hardware) — callers must
    treat None as "no MFU", never substitute a guess.  ``DLT_PEAK_FLOPS``
    overrides the table (it wins even over known chips, so a sliced or
    down-clocked part can be pinned to its real ceiling)."""
    env = os.environ.get(PEAK_FLOPS_ENV)
    if env:
        try:
            return float(env)
        except ValueError:
            pass
    if device is None:
        import jax

        devices = jax.devices()
        if not devices:
            return None
        device = devices[0]
    kind = str(getattr(device, "device_kind", "")).lower()
    best: Optional[float] = None
    best_len = -1
    for key, peak in PEAK_FLOPS_TABLE.items():
        if key in kind and len(key) > best_len:
            best, best_len = peak, len(key)
    return best


def mfu(flops: Optional[float], seconds: Optional[float],
        peak_flops: Optional[float]) -> Optional[float]:
    """Model-FLOPs-utilization: ``(flops / seconds) / peak_flops``.
    Any missing/non-positive input yields None — an MFU is either
    grounded in all three measurements or absent."""
    if not flops or not seconds or not peak_flops:
        return None
    if flops <= 0 or seconds <= 0 or peak_flops <= 0:
        return None
    return (flops / seconds) / peak_flops


# ---------------------------------------------------------------------- #
# CostProfile                                                            #
# ---------------------------------------------------------------------- #
def _first_cost_dict(cost_analysis: Any) -> Dict[str, float]:
    """``Compiled.cost_analysis()`` returns a per-program list on some
    backends and a flat dict on others; normalize to one dict."""
    if cost_analysis is None:
        return {}
    if isinstance(cost_analysis, dict):
        return dict(cost_analysis)
    if isinstance(cost_analysis, (list, tuple)) and cost_analysis:
        first = cost_analysis[0]
        return dict(first) if isinstance(first, dict) else {}
    return {}


def _collectives_of(hlo_text: str) -> Dict[str, int]:
    """Collective-instruction inventory of compiled HLO text, reusing
    the graftlint audit's scanner so the two surfaces cannot drift.
    ``tools`` is a repo-root package; when this library runs installed
    elsewhere the inventory is simply absent (empty dict)."""
    try:
        from tools.graftlint.jaxpr_audit import collect_hlo_collectives
    except Exception:
        return {}
    return {
        op: int(n) for (op, _axes), n in
        sorted(collect_hlo_collectives(hlo_text).items())
    }


@dataclasses.dataclass
class CostProfile:
    """Static cost of ONE compiled program (one XLA dispatch).

    ``peak_bytes`` is the backend's reported peak when available, else
    the standard estimate ``argument + output + temp - alias`` (donated
    buffers alias their outputs, so donation headroom is visible as
    ``alias_bytes``).  Fields the backend does not report are None —
    absent, not zero.

    Loop caveat (load-bearing for MFU): XLA's cost analysis counts a
    ``while``/``scan`` BODY once — trip counts are not folded in — so
    ``flops`` for a scanned program is per loop body, not per dispatch.
    Callers that know the trip count (the trainer knows ``epoch_len``
    and its superstep) pass it as ``loop_steps`` to
    :meth:`mfu` / :meth:`bytes_per_sec`; without it the derived rates
    are lower bounds.  (Pinned by
    ``tests/test_obs_cost.py::test_cost_profile_counts_loop_body_once``.)
    """

    name: str
    platform: str = ""
    flops: Optional[float] = None
    bytes_accessed: Optional[float] = None
    argument_bytes: Optional[int] = None
    output_bytes: Optional[int] = None
    temp_bytes: Optional[int] = None
    alias_bytes: Optional[int] = None
    generated_code_bytes: Optional[int] = None
    peak_bytes: Optional[int] = None
    collectives: Dict[str, int] = dataclasses.field(default_factory=dict)

    # -- construction --------------------------------------------------- #
    @classmethod
    def from_compiled(cls, name: str, compiled: Any,
                      *, platform: str = "") -> "CostProfile":
        """Extract a profile from a ``jax.stages.Compiled`` (the object
        ``fn.lower(*args).compile()`` returns).  Every field degrades to
        None independently: a backend that reports cost but not memory
        still yields a useful profile."""
        prof = cls(name=name, platform=platform)
        try:
            cost = _first_cost_dict(compiled.cost_analysis())
        except Exception:
            cost = {}
        if "flops" in cost:
            prof.flops = float(cost["flops"])
        if "bytes accessed" in cost:
            prof.bytes_accessed = float(cost["bytes accessed"])
        try:
            ma = compiled.memory_analysis()
        except Exception:
            ma = None
        if ma is not None:
            prof.argument_bytes = int(ma.argument_size_in_bytes)
            prof.output_bytes = int(ma.output_size_in_bytes)
            prof.temp_bytes = int(ma.temp_size_in_bytes)
            prof.alias_bytes = int(ma.alias_size_in_bytes)
            prof.generated_code_bytes = int(
                ma.generated_code_size_in_bytes
            )
            peak = getattr(ma, "peak_memory_in_bytes", None)
            prof.peak_bytes = (
                int(peak) if peak else
                prof.argument_bytes + prof.output_bytes
                + prof.temp_bytes - prof.alias_bytes
            )
        try:
            prof.collectives = _collectives_of(compiled.as_text())
        except Exception:
            prof.collectives = {}
        return prof

    # -- derived measurements ------------------------------------------- #
    def mfu(self, seconds: Optional[float],
            peak_flops: Optional[float] = None,
            *, dispatches: int = 1,
            loop_steps: int = 1) -> Optional[float]:
        """MFU of ``dispatches`` runs of this program over ``seconds``
        wall time; ``peak_flops`` defaults to :func:`device_peak_flops`
        (None on unknown chips — then MFU is None too).  ``loop_steps``
        is the caller-known scan/while trip product (see the class
        docstring: XLA counts loop bodies once); leaving it 1 makes the
        result a lower bound for looped programs."""
        if peak_flops is None:
            peak_flops = device_peak_flops()
        f = (
            None if self.flops is None
            else self.flops * dispatches * max(int(loop_steps), 1)
        )
        return mfu(f, seconds, peak_flops)

    def bytes_per_sec(self, seconds: Optional[float],
                      *, dispatches: int = 1,
                      loop_steps: int = 1) -> Optional[float]:
        """Achieved HBM traffic (XLA bytes-accessed per counted body,
        times dispatches and the caller-known loop trip product, over
        wall seconds)."""
        if not seconds or seconds <= 0 or self.bytes_accessed is None:
            return None
        return (
            self.bytes_accessed * dispatches * max(int(loop_steps), 1)
            / seconds
        )

    # -- (de)serialization ---------------------------------------------- #
    def to_dict(self) -> dict:
        return dataclasses.asdict(self)

    @classmethod
    def from_dict(cls, d: dict) -> "CostProfile":
        fields = {f.name for f in dataclasses.fields(cls)}
        return cls(**{k: v for k, v in d.items() if k in fields})


# ---------------------------------------------------------------------- #
# Process-wide profile registry                                          #
# ---------------------------------------------------------------------- #
_PROFILES: Dict[str, CostProfile] = {}
_PROFILES_LOCK = threading.Lock()


def register_profile(profile: CostProfile, *, registry: Any = None) -> CostProfile:
    """Register ``profile`` process-wide under its program name and
    mirror its headline numbers as ``cost.*`` gauges so they ride
    ``run_report()``, obs deltas, and ``obs-report`` (``registry``
    defaults to the process-wide metrics registry; pass False to skip
    the gauges)."""
    with _PROFILES_LOCK:
        _PROFILES[profile.name] = profile
    if registry is False:
        return profile
    if registry is None:
        from distributed_learning_tpu.obs.registry import get_registry

        registry = get_registry()
    for key, value in (
        ("flops", profile.flops),
        ("bytes_accessed", profile.bytes_accessed),
        ("peak_bytes", profile.peak_bytes),
        ("alias_bytes", profile.alias_bytes),
    ):
        if value is not None:
            registry.gauge(f"cost.{key}/{profile.name}", float(value))
    if profile.collectives:
        registry.gauge(
            f"cost.collectives/{profile.name}",
            float(sum(profile.collectives.values())),
        )
    return profile


def get_profile(name: str) -> Optional[CostProfile]:
    """The registered profile for program ``name`` (None when absent)."""
    with _PROFILES_LOCK:
        return _PROFILES.get(name)


def all_profiles() -> Dict[str, CostProfile]:
    """Snapshot of every registered profile, by program name."""
    with _PROFILES_LOCK:
        return dict(_PROFILES)


def clear_profiles() -> None:
    """Drop all registered profiles (test isolation)."""
    with _PROFILES_LOCK:
        _PROFILES.clear()


def profile_fn(fn: Callable, *args: Any, name: Optional[str] = None,
               register: bool = True, registry: Any = None,
               **kwargs: Any) -> CostProfile:
    """Extract (and by default register) the :class:`CostProfile` of
    ``fn`` at these argument shapes.

    ``fn`` may be a jitted callable, an :class:`InstrumentedStep`
    (which delegates the AOT surface), a ``jax.stages.Lowered``, or a
    plain traceable callable (jitted here).  Profiling uses the AOT
    ``lower → compile`` path only — it never executes the program and
    never changes what a later call compiles (the obs on/off
    bit-identity oracle covers this).  ``name`` defaults to the
    instrumented step's span name or the function's ``__name__``."""
    import jax

    if name is None:
        name = getattr(fn, "_name", None) or getattr(
            fn, "__name__", fn.__class__.__name__
        )
    if hasattr(fn, "compile") and not hasattr(fn, "lower"):
        lowered = fn  # already a Lowered
    else:
        if not hasattr(fn, "lower"):
            fn = jax.jit(fn)
        lowered = fn.lower(*args, **kwargs)
    compiled = lowered.compile()
    platform = jax.devices()[0].platform if jax.devices() else ""
    profile = CostProfile.from_compiled(name, compiled, platform=platform)
    if register:
        register_profile(profile, registry=registry)
    return profile


# ---------------------------------------------------------------------- #
# Sampled dispatch timer                                                 #
# ---------------------------------------------------------------------- #
class SampledDispatchTimer:
    """Measured step time on 1-in-N chunk-boundary dispatches.

    OFF by default (``every_n=0``): the constructor is free, ``tick()``
    always answers False, nothing syncs.  With ``every_n=N >= 1`` the
    caller asks ``tick()`` before each chunk dispatch; on every N-th it
    answers True and the caller closes the chunk with
    ``measure(outputs, t0)`` — ONE explicit ``jax.block_until_ready``
    at the chunk boundary (the same host boundary the metrics-carry
    flush already syncs at; never inside a compiled program, never per
    step).  Each sample records the ``cost.step_time_s[/name]`` series
    and — when ``profile`` (or a registered profile under ``name``) and
    the chip peak are known — the ``cost.mfu[/name]`` and
    ``cost.bytes_per_sec[/name]`` gauges.

    Sync accounting is explicit: ``samples`` / ``skipped`` count every
    decision, mirrored as ``cost.timer.samples`` / ``cost.timer.skipped``
    counters so a report shows exactly how many extra syncs the timer
    added (the declared 1-in-N, and nothing else)."""

    def __init__(self, every_n: int = 0, *, name: str = "",
                 registry: Any = None,
                 peak_flops: Optional[float] = None):
        self.every_n = max(int(every_n), 0)
        self.name = name
        self._registry = registry
        self._peak_flops = peak_flops
        self._count = 0
        self.samples = 0
        self.skipped = 0
        self.last_step_time_s: Optional[float] = None
        self.last_mfu: Optional[float] = None
        self.last_bytes_per_sec: Optional[float] = None

    @property
    def enabled(self) -> bool:
        return self.every_n > 0

    def _suffix(self, name: Optional[str]) -> str:
        n = name or self.name
        return f"/{n}" if n else ""

    def _reg(self):
        if self._registry is not None:
            return self._registry
        from distributed_learning_tpu.obs.registry import get_registry

        return get_registry()

    def tick(self) -> bool:
        """Should THIS dispatch be sampled?  Two host integer ops when
        disabled or off-sample; increments the sync accounting either
        way."""
        if not self.enabled:
            return False
        sample = self._count % self.every_n == 0
        self._count += 1
        if sample:
            self.samples += 1
            self._reg().inc("cost.timer.samples")
        else:
            self.skipped += 1
            self._reg().inc("cost.timer.skipped")
        return sample

    def measure(self, outputs: Any, t0: float, *,
                name: Optional[str] = None,
                profile: Optional[CostProfile] = None,
                loop_steps: int = 1,
                step: Optional[int] = None) -> float:
        """Close a sampled chunk: drain ``outputs`` with ONE
        ``jax.block_until_ready``, record the elapsed wall time since
        ``t0`` (a ``time.perf_counter()`` stamp taken just before the
        dispatch), derive MFU / bytes-per-sec when the program's profile
        is known (``loop_steps`` = the caller-known scan trip product;
        see :class:`CostProfile`'s loop caveat), and return the chunk
        wall time in seconds."""
        import jax

        # The declared 1-in-N chunk-boundary sync — the ONLY sync this
        # timer ever adds, at a boundary the carry flush already pays.
        jax.block_until_ready(outputs)
        dt = time.perf_counter() - t0
        reg = self._reg()
        suffix = self._suffix(name)
        reg.observe(f"cost.step_time_s{suffix}", dt, step=step)
        self.last_step_time_s = dt
        prof = profile or get_profile(name or self.name)
        peak = self._peak_flops
        if peak is None:
            peak = device_peak_flops()
        self.last_mfu = (
            None if prof is None
            else prof.mfu(dt, peak, loop_steps=loop_steps)
        )
        self.last_bytes_per_sec = (
            None if prof is None
            else prof.bytes_per_sec(dt, loop_steps=loop_steps)
        )
        if self.last_mfu is not None:
            reg.gauge(f"cost.mfu{suffix}", self.last_mfu)
        if self.last_bytes_per_sec is not None:
            reg.gauge(
                f"cost.bytes_per_sec{suffix}", self.last_bytes_per_sec
            )
        return dt
