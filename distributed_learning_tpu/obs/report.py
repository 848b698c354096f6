"""Run-report rendering + the ``obs-report`` / ``obs-monitor`` CLIs.

``python -m distributed_learning_tpu.cli obs-report <run.jsonl>``
replays a JSONL event log (written by ``MetricsRegistry.dump_jsonl`` or
streamed by a ``JsonlSink`` / ``JsonlTelemetry``) and prints the
aggregated run summary: counter totals, last gauges, time-series stats,
and span timings — "where did this run's time and bandwidth go" without
TensorBoard.

The run-wide plane adds two modes (both jax-free):

* ``obs-report --merge a.jsonl b.jsonl ...`` — merge per-agent event
  logs into ONE run report with per-agent labels plus the straggler
  profile (each file's stem names its agent; a DIRECTORY argument
  expands to its sorted ``*.jsonl`` members, so a fleet harness's
  output dir is one argument; ``--trace out.json`` additionally writes
  the merged Perfetto trace);
* ``obs-monitor <aggregate.jsonl>`` — live text dashboard over the
  aggregate stream a master-side ``RunAggregator`` + ``JsonlSink``
  writes (round rate, per-agent latency bars, consensus residual, wire
  bytes); ``--once`` renders a single frame.
"""

from __future__ import annotations

import argparse
import json
import os
import sys
import time
from typing import Any, Dict, Iterator, List, Optional, Sequence

from distributed_learning_tpu.obs.aggregate import (
    RunAggregator,
    straggler_profile_from_registry,
)
from distributed_learning_tpu.obs.registry import MetricsRegistry

__all__ = [
    "format_run_report",
    "format_straggler_profile",
    "format_edge_profile",
    "obs_report_main",
    "obs_monitor_main",
]


def _fmt(v: float) -> str:
    if v == int(v) and abs(v) < 1e15:
        return str(int(v))
    return f"{v:.6g}"


def format_run_report(report: dict) -> str:
    """Human-readable rendering of ``MetricsRegistry.run_report()``."""
    lines: List[str] = []
    wall = report.get("wall_s")
    head = f"run report — {report.get('events', 0)} events"
    if wall is not None:
        head += f" over {wall:.3f}s"
    lines.append(head)
    if report.get("counters"):
        lines.append("\ncounters:")
        for name in sorted(report["counters"]):
            lines.append(f"  {name:44s} {_fmt(report['counters'][name]):>14}")
    if report.get("gauges"):
        lines.append("\ngauges (last value):")
        for name in sorted(report["gauges"]):
            lines.append(f"  {name:44s} {_fmt(report['gauges'][name]):>14}")
    if report.get("series"):
        lines.append(
            f"\nseries:\n  {'name':44s} {'n':>6} {'mean':>12} "
            f"{'min':>12} {'max':>12} {'last':>12}"
        )
        for name in sorted(report["series"]):
            s = report["series"][name]
            lines.append(
                f"  {name:44s} {s['count']:6d} {s['mean']:12.5g} "
                f"{s['min']:12.5g} {s['max']:12.5g} {s['last']:12.5g}"
            )
    if report.get("spans"):
        lines.append(
            f"\nspans (wall clock):\n  {'name':44s} {'n':>6} "
            f"{'total s':>12} {'mean s':>12} {'max s':>12}"
        )
        for name in sorted(
            report["spans"],
            key=lambda n: -report["spans"][n]["total_s"],
        ):
            s = report["spans"][name]
            lines.append(
                f"  {name:44s} {s['count']:6d} {s['total_s']:12.4f} "
                f"{s['mean_s']:12.4f} {s['max_s']:12.4f}"
            )
    return "\n".join(lines)


# ---------------------------------------------------------------------- #
# Straggler profile                                                      #
# ---------------------------------------------------------------------- #
def _bar(value: float, top: float, width: int = 24) -> str:
    if top <= 0:
        return ""
    return "#" * max(0, min(width, round(width * value / top)))


def format_straggler_profile(profile: dict) -> str:
    """Render :func:`straggler_profile_from_registry` output."""
    head = (
        f"straggler profile — {profile['rounds']} rounds, "
        f"source: {profile['source']}"
    )
    if profile.get("quantiles") == "sketch":
        # Which statistics path produced the percentiles (the sketch's
        # relative-error guarantee vs the exact small-run oracle).
        alpha = profile.get("alpha", 0.01)
        head += f", quantiles: sketch(α={alpha * 100:g}%)"
    lines = [head]
    skew = profile.get("skew") or {}
    if profile["rounds"]:
        lines.append(
            f"  round skew  p50 {skew.get('p50_s', 0.0):.4f}s  "
            f"p95 {skew.get('p95_s', 0.0):.4f}s  "
            f"max {skew.get('max_s', 0.0):.4f}s"
        )
    per_agent = profile.get("per_agent") or {}
    if per_agent:
        top = max(a["p95_s"] for a in per_agent.values())
        lines.append(
            f"  {'agent':10s} {'n':>5} {'p50 s':>9} {'p95 s':>9} "
            f"{'max s':>9} {'slowest':>8} {'stale':>6} {'defer':>6}  p95"
        )
        for token in sorted(per_agent):
            a = per_agent[token]
            lines.append(
                f"  {token:10s} {a['count']:5d} {a['p50_s']:9.4f} "
                f"{a['p95_s']:9.4f} {a['max_s']:9.4f} "
                f"{a['slowest_rounds']:8d} {_fmt(a['stale_dropped']):>6} "
                f"{_fmt(a['deferred']):>6}  {_bar(a['p95_s'], top)}"
            )
        evicted = sum(
            int(a.get("evicted", 0)) for a in per_agent.values()
        )
        if evicted and profile.get("quantiles") != "sketch":
            # Exact-path percentiles cover the retained ring only; the
            # dropped tail is disclosed, never silently absorbed (the
            # sketch path is eviction-immune and needs no caveat).
            lines.append(
                f"  ! {evicted} series points evicted — exact "
                f"percentiles cover the retained window only"
            )
    # Staleness vs convergence (docs/async_runtime.md): what the async
    # runtime mixed stale/dropped, next to where each agent's consensus
    # residual went — the τ trade-off in one table.
    sv = {
        t: a for t, a in per_agent.items()
        if a.get("staleness") or "residual_last" in a
    }
    if sv:
        lines.append("  staleness vs convergence")
        lines.append(
            f"  {'agent':10s} {'mixes':>6} {'stale mean':>11} "
            f"{'stale max':>10} {'dropped':>8} {'resid first':>12} "
            f"{'resid last':>12}"
        )
        for token in sorted(sv):
            a = sv[token]
            st = a.get("staleness") or {}
            rf, rl = a.get("residual_first"), a.get("residual_last")
            lines.append(
                f"  {token:10s} {st.get('n', 0):6d} "
                f"{st.get('mean', 0.0):11.2f} "
                f"{_fmt(st.get('max', 0)):>10} "
                f"{_fmt(a.get('stale_dropped_mix', 0)):>8} "
                f"{(f'{rf:12.3g}' if rf is not None else ' ' * 12)} "
                f"{(f'{rl:12.3g}' if rl is not None else ' ' * 12)}"
            )
    if profile.get("slowest_agent") is not None:
        lines.append(f"  slowest agent: {profile['slowest_agent']}")
    return "\n".join(lines)


# ---------------------------------------------------------------------- #
# Per-edge wire profile                                                  #
# ---------------------------------------------------------------------- #
def _ms(v: Optional[float]) -> str:
    return "—" if v is None else f"{v * 1e3:.1f}"


def format_edge_profile(profile: dict) -> str:
    """Render :func:`~distributed_learning_tpu.obs.aggregate.
    edge_profile_from_registry` output: one row per directed edge —
    volume, throughput, retries, trace-derived latency percentiles,
    mix staleness, and injected-fault attribution.  When any edge
    carries decode scratch-pool attribution (the async runner's
    zero-copy receive path, docs/wire.md), a second subtable breaks
    hits/misses/bytes down per inbound edge; scratch-less profiles
    render byte-identically to the pre-scratch table."""
    edges = profile.get("edges") or {}
    window = profile.get("window_s") or 0.0
    head = f"edge profile — {len(edges)} directed edges"
    if window:
        head += f" over {window:.1f}s"
    if profile.get("quantiles") == "sketch":
        alpha = profile.get("alpha", 0.01)
        head += f", quantiles: sketch(α={alpha * 100:g}%)"
    lines = [head]
    if not edges:
        return "\n".join(lines)
    lines.append(
        f"  {'edge':12s} {'frames':>7} {'KiB out':>9} {'KiB/s':>8} "
        f"{'retry':>6} {'lat p50 ms':>11} {'p95 ms':>8} {'max ms':>8} "
        f"{'stale mean':>11} {'faults':>7}"
    )
    for edge in sorted(edges):
        e = edges[edge]
        lat = e.get("latency") or {}
        st = e.get("staleness") or {}
        faults = int(sum((e.get("faults") or {}).values()))
        stale_mean = f"{st['mean']:.2f}" if st else "—"
        lines.append(
            f"  {edge:12s} {int(e.get('frames_out', 0)):7d} "
            f"{float(e.get('bytes_out', 0.0)) / 1024.0:9.2f} "
            f"{float(e.get('bytes_out_per_s', 0.0)) / 1024.0:8.2f} "
            f"{int(e.get('retries', 0)):6d} "
            f"{_ms(lat.get('p50_s')):>11} {_ms(lat.get('p95_s')):>8} "
            f"{_ms(lat.get('max_s')):>8} "
            f"{stale_mean:>11} {faults:7d}"
        )
    scratch = {
        edge: e["scratch"] for edge, e in edges.items()
        if e.get("scratch")
    }
    if scratch:
        lines.append("  decode scratch pool (zero-copy receive path)")
        lines.append(
            f"  {'edge':12s} {'hits':>7} {'misses':>7} {'hit %':>7} "
            f"{'MiB decoded':>12}"
        )
        for edge in sorted(scratch):
            s = scratch[edge]
            hits = int(s.get("hits", 0))
            misses = int(s.get("misses", 0))
            total = hits + misses
            pct = f"{100.0 * hits / total:.1f}" if total else "—"
            lines.append(
                f"  {edge:12s} {hits:7d} {misses:7d} {pct:>7} "
                f"{float(s.get('bytes', 0.0)) / 2**20:12.2f}"
            )
    return "\n".join(lines)


# ---------------------------------------------------------------------- #
# Offline merge (obs-report --merge)                                     #
# ---------------------------------------------------------------------- #
def _token_from_path(path: str) -> str:
    stem = path.replace("\\", "/").rsplit("/", 1)[-1]
    if stem.endswith(".jsonl"):
        stem = stem[: -len(".jsonl")]
    return stem


def _expand_log_paths(paths: Sequence[str]) -> List[str]:
    """Expand directory arguments into their sorted ``*.jsonl`` files
    (one fleet-harness output directory is one ``--merge`` argument);
    plain file paths pass through unchanged."""
    out: List[str] = []
    for path in paths:
        if os.path.isdir(path):
            names = sorted(
                n for n in os.listdir(path) if n.endswith(".jsonl")
            )
            if not names:
                raise FileNotFoundError(
                    f"--merge directory {path!r} holds no .jsonl logs"
                )
            out.extend(os.path.join(path, n) for n in names)
        else:
            out.append(path)
    return out


def merge_agent_logs(paths: Sequence[str]) -> RunAggregator:
    """Merge per-agent JSONL event logs (file stem == agent token) into
    one :class:`RunAggregator`.  Directory arguments expand to their
    sorted ``*.jsonl`` members.  The merged registry re-stamps nothing:
    its clock is pinned to 0 because offline-merge timestamps are the
    agents' own (carried inside the replayed events), and a
    deterministic clock keeps merged reports reproducible."""
    agg = RunAggregator(
        registry=MetricsRegistry(clock=lambda: 0.0)
    )
    for path in _expand_log_paths(paths):
        agg.merge_registry(
            _token_from_path(path), MetricsRegistry.from_jsonl(path)
        )
    return agg


# ---------------------------------------------------------------------- #
# obs-report CLI                                                         #
# ---------------------------------------------------------------------- #
def obs_report_main(argv: Optional[Sequence[str]] = None) -> int:
    """Entry point for ``cli.py obs-report``."""
    ap = argparse.ArgumentParser(
        prog="python -m distributed_learning_tpu.cli obs-report",
        description="summarize JSONL observability event logs",
    )
    ap.add_argument("paths", nargs="+",
                    help="JSONL event log(s) (dump_jsonl/JsonlSink)")
    ap.add_argument("--json", action="store_true",
                    help="emit the raw report dict as JSON")
    ap.add_argument("--merge", action="store_true",
                    help="merge per-agent logs (file stem == agent "
                         "token; a directory expands to its *.jsonl "
                         "files) into one run report + straggler "
                         "profile")
    ap.add_argument("--trace", default=None, metavar="OUT.json",
                    help="with --merge: also write the merged "
                         "Chrome/Perfetto trace here")
    args = ap.parse_args(argv)
    try:
        if args.merge:
            agg = merge_agent_logs(args.paths)
            if args.trace:
                agg.export_chrome_trace(args.trace)
            report = agg.registry.run_report()
            profile = agg.straggler_profile()
            edge_profile = agg.edge_profile()
            payload = {"report": report, "straggler": profile}
            text_parts = [
                format_run_report(report),
                format_straggler_profile(profile),
            ]
            if edge_profile["edges"]:
                # Rendered only when edge-labeled streams ran: plain
                # (pre-observatory) logs keep their exact report shape.
                payload["edges"] = edge_profile
                text_parts.append(format_edge_profile(edge_profile))
            text = (
                json.dumps(payload, indent=2, sort_keys=True)
                if args.json else "\n\n".join(text_parts)
            )
        else:
            if len(args.paths) != 1:
                # graftlint: disable=no-print-in-library -- CLI error reporting to stderr (argparse convention)
                print("obs-report: pass one log, or --merge for several",
                      file=sys.stderr)
                return 2
            report = MetricsRegistry.from_jsonl(args.paths[0]).run_report()
            text = (
                json.dumps(report, indent=2, sort_keys=True)
                if args.json else format_run_report(report)
            )
    except FileNotFoundError as exc:
        # graftlint: disable=no-print-in-library -- CLI error reporting to stderr (argparse convention)
        print(f"obs-report: no such file: {exc.filename}", file=sys.stderr)
        return 2
    except (json.JSONDecodeError, ValueError) as exc:
        # graftlint: disable=no-print-in-library -- CLI error reporting to stderr (argparse convention)
        print(f"obs-report: input is not a JSONL event log: {exc}",
              file=sys.stderr)
        return 2
    # graftlint: disable=no-print-in-library -- obs-report's stdout IS its interface (the CLI subcommand's one output)
    print(text)
    return 0


# ---------------------------------------------------------------------- #
# obs-monitor: live dashboard over the aggregate stream                  #
# ---------------------------------------------------------------------- #
def _iter_jsonl_tolerant(path: str) -> Iterator[dict]:
    """Yield parseable lines, silently skipping a torn tail — the
    monitor reads a file the master is still appending to."""
    with open(path, "r", encoding="utf-8") as fh:
        for line in fh:
            line = line.strip()
            if not line:
                continue
            try:
                yield json.loads(line)
            except json.JSONDecodeError:
                continue


def _sum_labeled(counters: Dict[str, float], name: str) -> float:
    """Run-wide total for ``name``: the bare counter when present, else
    the sum over its ``name/label`` dimensions."""
    if name in counters:
        return counters[name]
    return sum(
        v for k, v in counters.items() if k.startswith(name + "/")
    )


def _stream_counters(registry: MetricsRegistry,
                     events: List[dict]) -> Dict[str, float]:
    """Counters over a replayed aggregate STREAM: counter totals don't
    stream as events, but every merged delta leaves an ``obs.delta``
    marker carrying its agent's absolute totals — the last marker per
    agent reconstructs them.  Replayed snapshot lines (a dumped file)
    land in ``registry.counters`` and win."""
    latest: Dict[str, Dict[str, float]] = {}
    for ev in events:
        if (ev.get("kind") == "event" and ev.get("name") == "obs.delta"
                and isinstance(ev.get("counters"), dict)):
            latest[str(ev.get("token"))] = ev["counters"]
    counters: Dict[str, float] = {}
    sums: Dict[str, float] = {}
    for token, per_agent in latest.items():
        for name, total in per_agent.items():
            counters[f"{name}/{token}"] = float(total)
            sums[name] = sums.get(name, 0.0) + float(total)
    counters.update(sums)
    counters.update(registry.counters)
    return counters


def render_dashboard(registry: MetricsRegistry, *,
                     window_s: float = 30.0,
                     now: Optional[float] = None,
                     title: str = "") -> str:
    """One text-dashboard frame over a (replayed) aggregate registry."""
    events = registry.recent_events()
    counters = _stream_counters(registry, events)
    ts = [e["ts"] for e in events if "ts" in e]
    # Cross-process ages compare wall-clock timestamps, the one clock
    # every process shares; this is reporting, not a measured duration.
    # graftlint: disable=wallclock-duration -- cross-process staleness: event ts are wall-clock stamps from other processes, monotonic clocks cannot compare across them
    age = (time.time() if now is None else now) - max(ts) if ts else None
    lines = [
        "obs-monitor"
        + (f" — {title}" if title else "")
        + f" · {len(events)} events"
        + (f" · last update {age:.1f}s ago" if age is not None else "")
    ]
    # Round rate over the trailing window.  The done count falls back
    # to the master's per-round series (one point per completed round)
    # when no counter reached the stream.
    done = int(
        _sum_labeled(counters, "comm.master.rounds_done")
        or len(registry.series.get("comm.master.round_s", ()))
    )
    cutoff = (max(ts) if ts else 0.0) - window_s
    recent = [
        e for e in events
        if e.get("kind") == "series"
        and e.get("name") == "comm.master.round_s"
        and e.get("ts", 0.0) >= cutoff
    ]
    rate = len(recent) / window_s if recent else 0.0
    lines.append(
        f"rounds: {done} done · rate {rate:.2f}/s "
        f"(last {window_s:.0f}s)"
    )
    profile = straggler_profile_from_registry(registry, counters=counters)
    if profile["per_agent"]:
        lines.append(format_straggler_profile(profile))
    residuals = {
        name: pts for name, pts in registry.series.items()
        if "consensus.residual" in name
    }
    if residuals:
        last = {
            name: list(pts)[-1][1] for name, pts in residuals.items()
        }
        worst = max(last.values())
        lines.append(f"consensus residual (worst last): {worst:.3g}")
    # Async-runtime staleness line (docs/async_runtime.md): how stale
    # the values being mixed are, and how much was dropped outright.
    stale_pts = [
        v for name, pts in registry.series.items()
        if "comm.agent.staleness" in name
        for _, v in pts
    ]
    if stale_pts:
        dropped = int(
            _sum_labeled(counters, "comm.agent.async_stale_dropped")
        )
        lines.append(
            f"staleness: mean {sum(stale_pts) / len(stale_pts):.2f} · "
            f"max {max(stale_pts):.0f} over {len(stale_pts)} mixes · "
            f"{dropped} dropped"
        )
    # Device-cost gauges (obs/cost.py): the sampled dispatch timer's
    # MFU / bytes-per-sec, per program name.
    mfus = {
        name.split("/", 1)[1] if "/" in name else "step": value
        for name, value in sorted(registry.gauges.items())
        if name.startswith("cost.mfu")
    }
    if mfus:
        bps = {
            name.split("/", 1)[1] if "/" in name else "step": value
            for name, value in registry.gauges.items()
            if name.startswith("cost.bytes_per_sec")
        }
        parts = []
        for prog, value in mfus.items():
            part = f"{prog} {value * 100:.1f}%"
            if prog in bps:
                part += f" ({bps[prog] / 2**30:.2f} GiB/s)"
            parts.append(part)
        lines.append("mfu: " + " · ".join(parts))
    out_b = _sum_labeled(counters, "comm.bytes_framed_out")
    in_b = _sum_labeled(counters, "comm.bytes_framed_in")
    if out_b or in_b:
        lines.append(
            f"wire: {out_b / 1024.0:.1f} KiB out · "
            f"{in_b / 1024.0:.1f} KiB in · "
            f"{int(_sum_labeled(counters, 'comm.frames_out'))} frames out"
        )
    lost = counters.get("obs.deltas_lost", 0)
    if lost:
        lines.append(f"obs: {int(lost)} telemetry deltas lost")
    lines.extend(_health_lines(registry, counters, events))
    return "\n".join(lines)


def _health_lines(registry: MetricsRegistry,
                  counters: Dict[str, float],
                  events: List[dict]) -> List[str]:
    """The dashboard's live health section: rules breached by the run's
    own sentinel (``health.breach`` events riding the stream) unioned
    with a fresh evaluation over the replayed registry (catches
    breaches a sentinel-less master never evaluated).  Empty when the
    stream carries no health signal at all, so pre-sentinel streams
    render unchanged."""
    from distributed_learning_tpu.obs.health import HealthSentinel

    # Signal detection BEFORE the fresh evaluation: evaluate() writes
    # health.* gauges of its own, which must not count as "this stream
    # already carried health data".
    had_signal = any(k.startswith("health.") for k in counters) or any(
        k.startswith("health.") for k in registry.gauges
    )
    live = sorted({
        str(ev.get("rule")) for ev in events
        if ev.get("kind") == "event" and ev.get("name") == "health.breach"
        and ev.get("rule")
    })
    sentinel = HealthSentinel(registry)
    try:
        fresh = {
            b.rule: b for b in sentinel.evaluate(counters=counters)
        }
    except Exception:  # pragma: no cover - render must never die
        fresh = {}
    names = sorted(set(live) | set(fresh))
    if not (names or had_signal):
        return []
    if not names:
        return [f"health: OK ({len(sentinel.rules)} rules)"]
    lines = [f"health: BREACH — {', '.join(names)}"]
    for name in names:
        br = fresh.get(name)
        if br is not None:
            lines.append(f"  {name}: {br.detail}")
    return lines


def obs_monitor_main(argv: Optional[Sequence[str]] = None) -> int:
    """Entry point for ``cli.py obs-monitor``: tail an aggregate JSONL
    stream (a master-side ``RunAggregator`` registry with a
    ``JsonlSink``) and re-render the dashboard every ``--interval``
    seconds; ``--once`` prints a single frame (scripts, tests)."""
    ap = argparse.ArgumentParser(
        prog="python -m distributed_learning_tpu.cli obs-monitor",
        description="live text dashboard over an aggregate obs stream",
    )
    ap.add_argument("path", help="aggregate JSONL stream (JsonlSink on "
                                 "the RunAggregator registry)")
    ap.add_argument("--interval", type=float, default=2.0)
    ap.add_argument("--window", type=float, default=30.0,
                    help="trailing seconds for the round-rate estimate")
    ap.add_argument("--once", action="store_true",
                    help="render one frame and exit")
    args = ap.parse_args(argv)
    while True:
        try:
            reg = MetricsRegistry.from_events(
                _iter_jsonl_tolerant(args.path)
            )
        except FileNotFoundError:
            # graftlint: disable=no-print-in-library -- CLI error reporting to stderr (argparse convention)
            print(f"obs-monitor: no such file: {args.path}",
                  file=sys.stderr)
            return 2
        frame = render_dashboard(
            reg, window_s=args.window, title=args.path
        )
        # graftlint: disable=no-print-in-library -- obs-monitor's stdout IS its interface (the live dashboard)
        print(frame, flush=True)
        if args.once:
            return 0
        try:
            time.sleep(args.interval)
        except KeyboardInterrupt:
            return 0
        # graftlint: disable=no-print-in-library -- obs-monitor's stdout IS its interface (frame separator)
        print("", flush=True)
