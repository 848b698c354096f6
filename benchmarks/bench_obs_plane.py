"""Fleet-scale obs-plane load harness (ISSUE 17 tentpole gate).

Drives 500+ synthetic per-agent ``obs.delta`` streams through a
two-tier aggregator tree (agents -> :class:`SubAggregator` pods ->
root) and gates the plane's fleet contract:

* **merge throughput** — payloads/sec through a root
  :class:`RunAggregator` (the sharded-master control plane budgets
  telemetry merging out of the master's round loop);
* **bounded memory** — the root's merged sketch state is O(metrics),
  not O(agents x samples): doubling the per-agent sample count must
  not grow the bucket footprint, and fleet-mode deltas
  (``raw_series=False``) must keep sketched series out of the raw
  point rings entirely;
* **bounded delta bytes** — a pack's encoded size stays flat as the
  per-agent sample count grows 10x, and a pod's upstream export stays
  flat as its agent count grows (label rollups fold the per-agent
  counter dimension);
* **aggregate-of-aggregates oracle** — the two-tier merge produces
  exactly the same rendered straggler quantiles as the flat
  single-aggregator merge of the same streams, and every sketch
  quantile matches the exact nearest-rank oracle within the sketch's
  documented relative-error bound.

Jax-free by construction (the obs plane never touches a jitted
program); ``benchmarks/common.py`` is used only for sizing and the
JSON metric-line contract.  ``out_dir=`` additionally dumps each pod's
merged registry as ``<token>.jsonl``, so the whole run is inspectable
with ``obs-report --merge <out_dir>``.
"""

from __future__ import annotations

import json
import os
from typing import Dict, List, Optional

import numpy as np

from benchmarks.common import emit, full_scale, smoke, stopwatch
from distributed_learning_tpu.obs.aggregate import (
    RunAggregator,
    SubAggregator,
    ObsDeltaSource,
)
from distributed_learning_tpu.obs.registry import MetricsRegistry
from distributed_learning_tpu.obs.sketch import DEFAULT_ALPHA, QuantileSketch

#: Tier-1 gate: a root aggregator must merge at least this many delta
#: payloads per second (the headline run on the measurement box shows
#: orders of magnitude more; the gate is loose so shared-CI timing
#: noise cannot flake).
MERGE_GATE_PAYLOADS_PER_SEC = 50.0


def _pct_exact(sorted_vals: List[float], q: float) -> float:
    """The exact nearest-rank oracle (same rank convention as the
    sketch and ``aggregate._pct``)."""
    import math

    rank = max(1, math.ceil(q * len(sorted_vals)))
    return sorted_vals[rank - 1]


def _synth_streams(n_agents: int, packs: int, points_per_pack: int):
    """Deterministic synthetic fleet: per-agent delta payload lists plus
    the exact per-agent sample record (the oracle).  Agent 0 is the
    injected straggler (10x latencies); the rest draw a heavy-tail
    lognormal — the adversarial shape for a quantile sketch."""
    payloads: List[List[dict]] = [[] for _ in range(packs)]
    exact: Dict[str, List[float]] = {}
    regs: Dict[str, MetricsRegistry] = {}
    for i in range(n_agents):
        token = f"a{i:04d}"
        rng = np.random.default_rng(1000 + i)
        reg = MetricsRegistry(clock=lambda: 0.0)
        # Fleet mode: sketched series travel as sketches only.
        src = ObsDeltaSource(reg, raw_series=False)
        vals: List[float] = []
        for p in range(packs):
            scale = 10.0 if i == 0 else 1.0
            draws = scale * rng.lognormal(mean=-3.0, sigma=1.0,
                                          size=points_per_pack)
            for v in draws:
                reg.observe("comm.agent.round_s", float(v))
                vals.append(float(v))
            reg.inc("comm.agent.rounds_run", points_per_pack)
            reg.observe("comm.agent.staleness", float(p % 3))
            payloads[p].append((token, src.pack()))
        exact[token] = sorted(vals)
        regs[token] = reg
        src.close()
    return payloads, exact, regs


def _sketch_footprint(agg: RunAggregator) -> int:
    """Total bucket entries across the aggregator's merged sketches —
    the O(metrics) quantity the memory gate tracks."""
    with agg._lock:
        return sum(
            len(sk.buckets) + len(sk.neg)
            for sk in agg.sketches.values()
        )


def run(n_agents: Optional[int] = None, packs: Optional[int] = None,
        points_per_pack: Optional[int] = None, n_subs: int = 10,
        out_dir: Optional[str] = None) -> dict:
    if n_agents is None:
        n_agents = 500 if full_scale() else 64
    if packs is None:
        packs = 2 if smoke() else 4
    if points_per_pack is None:
        points_per_pack = 20 if smoke() else 50
    n_subs = max(1, min(int(n_subs), n_agents))

    payloads, exact, regs = _synth_streams(n_agents, packs,
                                           points_per_pack)
    flat_payloads = [tp for pack in payloads for tp in pack]

    # ---- flat single-aggregator merge (the oracle topology) --------- #
    flat = RunAggregator(registry=MetricsRegistry(clock=lambda: 0.0))
    half_mark = None
    for k, (token, payload) in enumerate(flat_payloads):
        flat.process(token, payload)
        if k + 1 == len(flat_payloads) // 2:
            half_mark = _sketch_footprint(flat)
    full_mark = _sketch_footprint(flat)

    # ---- two-tier: agents -> pods -> root --------------------------- #
    subs = [
        SubAggregator(
            registry=MetricsRegistry(clock=lambda: 0.0),
            forward_raw_series=False,
        )
        for _ in range(n_subs)
    ]
    root = RunAggregator(registry=MetricsRegistry(clock=lambda: 0.0))
    for pack in payloads:
        for j, (token, payload) in enumerate(pack):
            subs[j % n_subs].process(token, payload)
        # One bounded upstream export per pod per pack round.
        for s, sub in enumerate(subs):
            root.process(f"pod{s}", sub.export_delta())

    # ---- oracle: two-tier == flat on every rendered quantile -------- #
    flat_prof = flat.straggler_profile()
    root_prof = root.straggler_profile()
    assert flat_prof["quantiles"] == root_prof["quantiles"] == "sketch"
    mismatches = 0
    rel_err_max = 0.0
    for token, entry in flat_prof["per_agent"].items():
        other = root_prof["per_agent"][token]
        for key in ("count", "p50_s", "p95_s", "max_s"):
            if entry[key] != other[key]:
                mismatches += 1
        # Sketch-vs-exact relative error on the quantiles the report
        # renders (the documented DDSketch-style alpha bound).
        vals = exact[token]
        for q, key in ((0.50, "p50_s"), (0.95, "p95_s")):
            truth = _pct_exact(vals, q)
            err = abs(entry[key] - truth) / truth
            rel_err_max = max(rel_err_max, err)
    two_tier_exact = mismatches == 0
    alpha_ok = rel_err_max <= DEFAULT_ALPHA + 1e-12

    # Counter totals agree up to float-summation order.
    flat_total = flat.registry.counters["comm.agent.rounds_run"]
    root_total = root.registry.counters["comm.agent.rounds_run"]
    counters_ok = (
        abs(flat_total - root_total) <= 1e-9 * max(1.0, flat_total)
    )

    # ---- bounded memory --------------------------------------------- #
    # Bucket saturation: 10x the samples from a stationary
    # distribution must not meaningfully grow a sketch's bucket
    # footprint (the occupied log-buckets saturate; only the counts in
    # them keep rising).  This is the O(metrics)-not-O(samples)
    # memory contract measured directly.
    sat_rng = np.random.default_rng(42)
    sat_sk = QuantileSketch()
    for v in sat_rng.lognormal(mean=-3.0, sigma=1.0, size=1000):
        sat_sk.add(float(v))
    sat_1k = len(sat_sk.buckets) + len(sat_sk.neg)
    for v in sat_rng.lognormal(mean=-3.0, sigma=1.0, size=9000):
        sat_sk.add(float(v))
    sat_10k = len(sat_sk.buckets) + len(sat_sk.neg)
    memory_flat = sat_10k <= sat_1k * 1.75
    # Fleet mode kept sketched series out of the raw rings entirely.
    no_raw_series = (
        len(flat.registry.series.get("comm.agent.round_s/a0000", ()))
        == 0
    )

    # ---- bounded delta bytes ---------------------------------------- #
    # Per-agent pack: 10x the samples must not 10x the payload.
    def _pack_bytes(points: int) -> int:
        reg = MetricsRegistry(clock=lambda: 0.0)
        src = ObsDeltaSource(reg, raw_series=False)
        rng = np.random.default_rng(7)
        for v in rng.lognormal(mean=-3.0, sigma=1.0, size=points):
            reg.observe("comm.agent.round_s", float(v))
        payload = src.pack()
        src.close()
        return len(json.dumps(payload).encode())

    bytes_1x = _pack_bytes(200)
    bytes_10x = _pack_bytes(2000)
    # Sub-linear, bucket-saturation growth: 10x the samples stays well
    # under 3x the bytes (a raw-series payload would be ~10x).
    delta_bytes_flat = bytes_10x <= bytes_1x * 3.0

    # Pod export: 4x the agents must not 4x the upstream delta (label
    # rollups fold the per-agent counter dimension).
    def _export_bytes(agents: int) -> int:
        sub = SubAggregator(
            registry=MetricsRegistry(clock=lambda: 0.0),
            forward_raw_series=False, rollup_labels=16,
        )
        for p in range(2):
            for i in range(agents):
                token = f"b{i:04d}"
                reg = MetricsRegistry(clock=lambda: 0.0)
                src = ObsDeltaSource(reg, raw_series=False)
                rng = np.random.default_rng(i)
                for v in rng.lognormal(size=20):
                    reg.observe("comm.agent.round_s", float(v))
                reg.inc("comm.agent.rounds_run", 20)
                sub.process(token, src.pack())
                src.close()
        return len(json.dumps(sub.export_delta()).encode())

    export_small = _export_bytes(16)
    export_large = _export_bytes(64)
    # The sketch section still carries per-agent labeled sketches (the
    # straggler profile needs per-agent attribution), so the export is
    # O(agents x metrics) there by design — but NOT O(samples): the
    # gate is that 4x agents with the same per-agent volume stays
    # comfortably under 4x bytes (rollups folded the counter rows).
    export_bounded = export_large <= export_small * 4

    # ---- merge throughput gate -------------------------------------- #
    sink = RunAggregator(registry=MetricsRegistry(clock=lambda: 0.0))
    with stopwatch() as t:
        for token, payload in flat_payloads:
            sink.process(token, payload)
    payloads_per_sec = len(flat_payloads) / max(t["s"], 1e-9)
    gate_passed = payloads_per_sec >= MERGE_GATE_PAYLOADS_PER_SEC

    # ---- optional artifact dir for obs-report --merge --------------- #
    # Per-agent registry dumps (the local rings retain the raw series
    # even in fleet mode, so the offline merge re-derives sketches and
    # renders the same per-agent picture): the whole fleet run is
    # inspectable with one ``obs-report --merge <out_dir>``.
    if out_dir:
        os.makedirs(out_dir, exist_ok=True)
        for token, reg in regs.items():
            reg.dump_jsonl(os.path.join(out_dir, f"{token}.jsonl"))

    out = {
        "n_agents": n_agents,
        "packs": packs,
        "points_per_pack": points_per_pack,
        "n_subs": n_subs,
        "payloads_merged": len(flat_payloads),
        "payloads_per_sec": payloads_per_sec,
        "gate": MERGE_GATE_PAYLOADS_PER_SEC,
        "gate_passed": bool(gate_passed),
        "two_tier_exact": bool(two_tier_exact),
        "counters_ok": bool(counters_ok),
        "sketch_rel_err_max": rel_err_max,
        "alpha": DEFAULT_ALPHA,
        "alpha_ok": bool(alpha_ok),
        "sketch_footprint_half": half_mark,
        "sketch_footprint_full": full_mark,
        "sat_buckets_1k": sat_1k,
        "sat_buckets_10k": sat_10k,
        "memory_flat": bool(memory_flat),
        "no_raw_series": bool(no_raw_series),
        "pack_bytes_1x": bytes_1x,
        "pack_bytes_10x": bytes_10x,
        "delta_bytes_flat": bool(delta_bytes_flat),
        "export_bytes_16": export_small,
        "export_bytes_64": export_large,
        "export_bounded": bool(export_bounded),
        "slowest_agent": flat_prof["slowest_agent"],
    }
    emit({
        "metric": "obs_plane_merge_payloads_per_sec",
        "value": payloads_per_sec,
        "unit": "payloads/sec",
        "vs_baseline": None,
        "bench": "obs_plane",
        "n_agents": n_agents,
        "gate": MERGE_GATE_PAYLOADS_PER_SEC,
        "gate_passed": bool(gate_passed),
        "two_tier_exact": bool(two_tier_exact),
        "sketch_rel_err_max": rel_err_max,
        "alpha_ok": bool(alpha_ok),
        "memory_flat": bool(memory_flat),
        "delta_bytes_flat": bool(delta_bytes_flat),
        "export_bounded": bool(export_bounded),
    })
    emit({
        "metric": "obs_plane_export_bytes",
        "value": float(export_large),
        "unit": "bytes",
        "vs_baseline": None,
        "bench": "obs_plane",
        "export_bytes_16_agents": export_small,
        "export_bytes_64_agents": export_large,
        "pack_bytes_1x": bytes_1x,
        "pack_bytes_10x": bytes_10x,
    })
    return out


if __name__ == "__main__":
    run()
