"""Unified observability layer: metrics registry, device-side metrics
carry, span tracing, and the comm-layer gossip counters.

One import surface for the four pieces:

* :class:`MetricsRegistry` (+ JSONL event-log / run-report exporters)
  — `registry.py`;
* :func:`flush_chunk` / :func:`global_norm` — the device-side metrics
  carry that keeps instrumentation out of the hot path — `carry.py`;
* :class:`SpanTracer` (nested wall-clock spans, Chrome trace export,
  ``jax.profiler`` integration) — `spans.py`;
* :func:`instrument_step` — transparent call wrapping for compiled step
  functions — `instrument.py`;
* run-report rendering + the ``obs-report`` / ``obs-monitor`` CLIs —
  `report.py`;
* the **run-wide plane** — `aggregate.py` (:class:`ObsDeltaSource`
  agent-side registry deltas, :class:`RunAggregator` master-side merge
  with per-agent labels, straggler profiles, merged Perfetto traces)
  and `flight.py` (:class:`FlightRecorder` — per-agent event rings
  dumped to a JSONL black box on abort/death/deadline/shutdown);
* the **trace plane + health sentinel** — wire-propagated frame flow
  events (`spans.py` :func:`emit_flow` over the
  ``protocol.TraceContext`` carried on the gossip wire, arrow-linked in
  the merged trace), per-edge wire profiles
  (:func:`edge_profile_from_registry`), and `health.py`
  (:class:`HealthSentinel` — declarative live-run rules over the
  merged registry, reason-tagged flight dumps on breach);
* the **device-cost observatory** — `cost.py` (:class:`CostProfile`
  extracted from any compiled entry point: FLOPs, bytes, peak HBM,
  donation, collective inventory; :class:`SampledDispatchTimer`
  1-in-N chunk-boundary step timing with MFU/bytes-per-sec gauges).

Library code counts into the process-wide default registry/tracer
(`get_registry()` / `get_tracer()`); tests and multi-run drivers scope
them with `use_registry` / `set_tracer`.
"""

from distributed_learning_tpu.obs.carry import flush_chunk, global_norm
from distributed_learning_tpu.obs.cost import (
    CostProfile,
    SampledDispatchTimer,
    all_profiles,
    clear_profiles,
    device_peak_flops,
    get_profile,
    profile_fn,
    register_profile,
)
from distributed_learning_tpu.obs.instrument import InstrumentedStep, instrument_step
from distributed_learning_tpu.obs.registry import (
    JsonlSink,
    JsonlTelemetry,
    MetricsRegistry,
    get_registry,
    read_jsonl,
    run_report,
    set_registry,
    use_registry,
)
from distributed_learning_tpu.obs.aggregate import (
    OBS_PAYLOAD_KIND,
    OBS_PAYLOAD_SECTIONS,
    OBS_PAYLOAD_VERSION,
    SKETCH_SERIES,
    ObsDeltaSource,
    RunAggregator,
    SubAggregator,
    edge_profile_from_registry,
    is_obs_payload,
    straggler_profile_from_registry,
)
from distributed_learning_tpu.obs.sketch import (
    DEFAULT_ALPHA,
    LabelRollup,
    QuantileSketch,
)
from distributed_learning_tpu.obs.flight import FlightRecorder
from distributed_learning_tpu.obs.health import (
    HealthBreach,
    HealthRule,
    HealthSentinel,
    default_rules,
)
from distributed_learning_tpu.obs.report import format_run_report, obs_report_main
from distributed_learning_tpu.obs.spans import (
    FLOW_EVENT,
    FLOW_PHASES,
    Span,
    SpanTracer,
    emit_flow,
    flow_key,
    get_tracer,
    set_tracer,
    span,
    trace_keep,
)

__all__ = [
    "MetricsRegistry",
    "JsonlSink",
    "JsonlTelemetry",
    "get_registry",
    "set_registry",
    "use_registry",
    "read_jsonl",
    "run_report",
    "flush_chunk",
    "global_norm",
    "Span",
    "SpanTracer",
    "get_tracer",
    "set_tracer",
    "span",
    "InstrumentedStep",
    "instrument_step",
    "CostProfile",
    "SampledDispatchTimer",
    "profile_fn",
    "register_profile",
    "get_profile",
    "all_profiles",
    "clear_profiles",
    "device_peak_flops",
    "format_run_report",
    "obs_report_main",
    "OBS_PAYLOAD_KIND",
    "OBS_PAYLOAD_SECTIONS",
    "OBS_PAYLOAD_VERSION",
    "SKETCH_SERIES",
    "DEFAULT_ALPHA",
    "QuantileSketch",
    "LabelRollup",
    "ObsDeltaSource",
    "RunAggregator",
    "SubAggregator",
    "FlightRecorder",
    "is_obs_payload",
    "straggler_profile_from_registry",
    "edge_profile_from_registry",
    "FLOW_EVENT",
    "FLOW_PHASES",
    "emit_flow",
    "flow_key",
    "trace_keep",
    "HealthBreach",
    "HealthRule",
    "HealthSentinel",
    "default_rules",
]
