"""Run-wide observability plane (obs/aggregate.py, obs/flight.py, the
comm wiring, and the obs-report/obs-monitor CLIs).

The acceptance oracle: a loopback N-agent run produces ONE merged run
registry with per-agent labels, a straggler profile that attributes an
injected slow agent, ONE merged Perfetto trace with one track per agent
on a shared timeline, and a flight-recorder JSONL dump on an injected
round abort — each asserted below.  Satellites: registry ring buffers
with visible eviction, the tracer wall-clock anchor, the
``obs-report --merge`` golden file, and the BENCH trajectory table.
"""

import asyncio
import json
import os

import numpy as np
import pytest

from distributed_learning_tpu.obs import (
    FlightRecorder,
    MetricsRegistry,
    ObsDeltaSource,
    RunAggregator,
    SpanTracer,
    get_registry,
    is_obs_payload,
)
from distributed_learning_tpu.obs.aggregate import OBS_PAYLOAD_VERSION

GOLDEN = os.path.join(os.path.dirname(__file__), "data",
                      "obs_merge_golden.txt")


# ---------------------------------------------------------------------- #
# Registry rings (satellite: bounded series/events + visible eviction)   #
# ---------------------------------------------------------------------- #
def test_series_ring_bounds_points_and_counts_evictions():
    reg = MetricsRegistry(max_points=4)
    for i in range(10):
        reg.observe("loss", float(i), step=i)
    pts = list(reg.series["loss"])
    assert len(pts) == 4 and [v for _, v in pts] == [6.0, 7.0, 8.0, 9.0]
    assert reg.points_dropped["loss"] == 6
    assert reg.snapshot()["dropped"]["series_points"] == 6
    rep = reg.run_report()
    assert rep["series"]["loss"]["dropped"] == 6
    assert rep["series"]["loss"]["count"] == 4  # stats over the window


def test_event_ring_keeps_the_tail():
    reg = MetricsRegistry(max_events=3, max_points=100)
    for i in range(7):
        reg.event("e", i=i)
    kept = [e["i"] for e in reg.recent_events()]
    assert kept == [4, 5, 6]  # LAST N: the black-box semantics
    assert reg.snapshot()["dropped"]["events"] == 4
    assert reg.run_report()["events"] == 7  # total stays honest


def test_unbounded_registry_keeps_list_semantics():
    reg = MetricsRegistry()
    reg.observe("x", 1.0)
    assert isinstance(reg.series["x"], list)
    assert "dropped" not in reg.run_report().get("series", {}).get("x", {})


def test_default_registry_is_bounded():
    reg = get_registry()
    assert reg._max_points is not None and reg._max_points > 0
    assert reg._max_events is not None and reg._max_events > 0


# ---------------------------------------------------------------------- #
# Tracer wall anchor (satellite: cross-process trace alignment)          #
# ---------------------------------------------------------------------- #
def test_tracer_wall_anchor_and_chrome_export():
    import time

    reg = MetricsRegistry()
    tr = SpanTracer(registry=reg)
    before = time.time()
    with tr.span("s"):
        pass
    after = time.time()
    # The registry span event carries an ABSOLUTE wall-clock start.
    ev = [e for e in reg.recent_events() if e["kind"] == "span"][0]
    assert before - 1e-3 <= ev["t0"] <= after + 1e-3
    # Chrome export: wall-anchored ts by default, relative on request.
    wall = tr.to_chrome_trace()["traceEvents"][0]["ts"]
    rel = tr.to_chrome_trace(wall_clock=False)["traceEvents"][0]["ts"]
    assert abs(wall - (rel + tr.wall0 * 1e6)) < 1e3  # within 1 ms
    assert rel < 1e12 < wall  # relative stays small, wall is epoch-scale


def test_two_tracers_share_one_timeline():
    import time

    regs = [MetricsRegistry(), MetricsRegistry()]
    tr1 = SpanTracer(registry=regs[0])
    with tr1.span("first"):
        pass
    time.sleep(0.02)
    tr2 = SpanTracer(registry=regs[1])  # a "second process", born later
    with tr2.span("second"):
        pass
    t0_first = regs[0].recent_events()[0]["t0"]
    t0_second = regs[1].recent_events()[0]["t0"]
    # Process-local monotonic origins would make these incomparable;
    # the wall anchor orders them correctly across tracers.
    assert t0_second > t0_first


# ---------------------------------------------------------------------- #
# Delta source + aggregator units                                        #
# ---------------------------------------------------------------------- #
def test_obs_delta_source_is_incremental_and_backfills():
    reg = MetricsRegistry(max_points=64)
    reg.observe("early", 1.0)  # recorded BEFORE the source attaches
    src = ObsDeltaSource(reg)
    reg.inc("c", 3)
    reg.observe("late", 2.0)
    p1 = src.pack()
    assert is_obs_payload(p1) and p1["v"] == OBS_PAYLOAD_VERSION
    assert p1["seq"] == 1 and p1["counters"] == {"c": 3.0}
    names = [e["name"] for e in p1["events"]]
    assert "early" in names and "late" in names  # backfill
    reg.inc("c", 2)
    p2 = src.pack()
    assert p2["seq"] == 2
    assert p2["counters"] == {"c": 5.0}  # absolute totals (idempotent)
    assert [e["name"] for e in p2["events"]] == []  # buffer drained
    # Payloads must survive the JSON wire (Telemetry packs JSON).
    json.dumps(p1), json.dumps(p2)
    src.close()
    reg.observe("after_close", 1.0)
    assert [e["name"] for e in src.pack()["events"]] == []


def test_aggregator_merges_per_agent_labels_and_runwide_sums():
    agg = RunAggregator()
    for token, rounds in (("a", 3), ("b", 5)):
        reg = MetricsRegistry()
        src = ObsDeltaSource(reg)
        reg.inc("comm.agent.rounds_run", rounds)
        reg.gauge("depth", rounds)
        reg.observe("comm.agent.round_s", 0.1 * rounds, step=1)
        agg.process(token, src.pack())
    c = agg.registry.counters
    assert c["comm.agent.rounds_run/a"] == 3
    assert c["comm.agent.rounds_run/b"] == 5
    assert c["comm.agent.rounds_run"] == 8  # run-wide sum
    assert agg.registry.gauges["depth/a"] == 3
    assert sorted(agg.agents()) == ["a", "b"]
    assert len(agg.registry.series["comm.agent.round_s/a"]) == 1


def test_aggregator_seq_gap_reset_and_version_guards():
    agg = RunAggregator()
    mk = lambda seq, total, v=OBS_PAYLOAD_VERSION: {
        "kind": "obs.delta", "v": v, "seq": seq,
        "counters": {"n": total}, "gauges": {}, "events": [],
    }
    agg.process("a", mk(1, 5))
    agg.process("a", mk(1, 5))  # duplicate: ignored
    assert agg.registry.counters["obs.stale_deltas"] == 1
    agg.process("a", mk(4, 9))  # seq 2, 3 lost on the wire
    assert agg.registry.counters["obs.deltas_lost"] == 2
    assert agg.registry.counters["n"] == 9  # totals stay exact
    agg.process("a", mk(5, 2))  # counter went BACKWARD: agent restarted
    assert agg.registry.counters["obs.counter_resets"] == 1
    assert agg.registry.counters["n"] == 11
    agg.process("a", mk(6, 2, v=OBS_PAYLOAD_VERSION + 1))
    assert agg.registry.counters["obs.unknown_version"] == 1
    # Opaque (non-delta) telemetry still lands as an event.
    agg.process("a", {"acc": 0.9})
    assert any(
        e.get("name") == "telemetry"
        for e in agg.registry.recent_events()
    )


def test_flight_recorder_ring_and_dump(tmp_path):
    fr = FlightRecorder(str(tmp_path / "flight"), capacity=3)
    for i in range(5):
        fr.note("a", "tick", i=i)
    fr.note("b", "boom")
    assert [e["i"] for e in fr.ring("a")] == [2, 3, 4]  # last N
    path = fr.trigger("round_aborted", round_id=7, token="a")
    header, events = FlightRecorder.read_dump(path)
    assert header["reason"] == "round_aborted" and header["round_id"] == 7
    assert header["agents"] == ["a", "b"]
    assert header["ring_evictions"] == {"a": 2}
    by_agent = {}
    for e in events:
        by_agent.setdefault(e["agent"], []).append(e)
    assert len(by_agent["a"]) == 3 and len(by_agent["b"]) == 1
    # Rings survive the dump: a second fault still has its window.
    assert fr.ring("b")


def test_merged_chrome_trace_one_track_per_agent_shared_timeline():
    agg = RunAggregator()
    for token, offset in (("a", 0.0), ("b", 0.5)):
        reg = MetricsRegistry()
        src = ObsDeltaSource(reg)
        for r in range(3):
            reg.record_span("round", 0.1, t0=1000.0 + offset + r)
        agg.process(token, src.pack())
    trace = agg.to_chrome_trace()
    meta = [e for e in trace["traceEvents"] if e["ph"] == "M"]
    spans = [e for e in trace["traceEvents"] if e["ph"] == "X"]
    assert sorted(m["args"]["name"] for m in meta) == [
        "agent a", "agent b",
    ]
    assert len(spans) == 6
    pids = {m["args"]["name"]: m["pid"] for m in meta}
    assert pids["agent a"] != pids["agent b"]  # one track per agent
    # Shared timeline: b's spans interleave 0.5s after a's, in wall
    # order, normalized to the earliest span.
    a_ts = sorted(e["ts"] for e in spans if e["pid"] == pids["agent a"])
    b_ts = sorted(e["ts"] for e in spans if e["pid"] == pids["agent b"])
    assert a_ts[0] == 0.0
    assert b_ts[0] == pytest.approx(5e5, rel=1e-3)  # 0.5 s in µs
    assert a_ts[1] < b_ts[1] < a_ts[2]


# ---------------------------------------------------------------------- #
# Acceptance: the loopback N-agent run                                   #
# ---------------------------------------------------------------------- #
TRIANGLE = [("a", "b"), ("b", "c"), ("c", "a")]


def test_loopback_plane_straggler_attribution_and_merged_outputs(tmp_path):
    """Master + 3 agents; agent "b" is artificially delayed before each
    round.  The plane must attribute it, merge the three registries
    with per-agent labels, and produce one multi-track wall-aligned
    trace."""
    from distributed_learning_tpu.comm import ConsensusAgent, ConsensusMaster

    flight = FlightRecorder(str(tmp_path / "flight"), capacity=64)
    agg = RunAggregator(flight=flight)

    async def main():
        master = ConsensusMaster(
            TRIANGLE, convergence_eps=1e-6,
            aggregator=agg, flight=flight,
        )
        host, port = await master.start()
        agents = {
            t: ConsensusAgent(t, host, port, obs=MetricsRegistry())
            for t in "abc"
        }
        await asyncio.gather(*(a.start() for a in agents.values()))

        async def one_round(t, a, v):
            if t == "b":
                await asyncio.sleep(0.12)  # the injected straggler
            return await a.run_round(v, 1.0)

        for r in range(3):
            vals = {
                t: np.full(4, float(i), np.float32)
                for i, t in enumerate("abc")
            }
            await asyncio.gather(
                *(one_round(t, a, vals[t]) for t, a in agents.items())
            )
        await asyncio.gather(
            *(a.send_obs_delta() for a in agents.values())
        )
        await asyncio.sleep(0.2)  # let the master drain telemetry
        await master.shutdown()
        for a in agents.values():
            await a.close()
        return master

    master = asyncio.run(asyncio.wait_for(main(), 60))

    # One merged run registry with per-agent label dimensions.
    c = agg.registry.counters
    for t in "abc":
        assert c[f"comm.agent.rounds_run/{t}"] == 3
    assert c["comm.agent.rounds_run"] == 9
    for t in "abc":
        assert len(agg.registry.series[f"comm.agent.round_s/{t}"]) == 3

    # Straggler profile: the delayed agent is attributed, per round.
    prof = agg.straggler_profile()
    assert prof["source"] == "master-arrival-lag"
    assert prof["slowest_agent"] == "b"
    assert prof["per_agent"]["b"]["slowest_rounds"] == 3
    assert prof["per_agent"]["b"]["p50_s"] >= 0.1
    assert prof["per_agent"]["a"]["p50_s"] < 0.1
    assert prof["skew"]["max_s"] >= 0.1
    assert prof["rounds"] == 3

    # One merged trace: a track per agent (+ master), shared timeline.
    trace = agg.to_chrome_trace()
    tracks = sorted(
        e["args"]["name"] for e in trace["traceEvents"]
        if e["ph"] == "M"
    )
    assert tracks == [
        "agent <master>", "agent a", "agent b", "agent c",
    ]
    spans = [e for e in trace["traceEvents"] if e["ph"] == "X"]
    assert len(spans) == 12  # 3 rounds x (3 agents + master)
    assert all(s["ts"] >= 0 for s in spans)
    # Wall alignment: round r spans across agents sit within ~1 round
    # of each other, not offset by process-local clock origins.
    by_pid = {}
    for s in spans:
        by_pid.setdefault(s["pid"], []).append(s["ts"])
    firsts = [min(v) for v in by_pid.values()]
    assert max(firsts) - min(firsts) < 5e6  # all within 5 s of each other

    # Round-trip: the aggregate registry dumps/replays (the obs-report
    # path over a master-side dump).
    dump = str(tmp_path / "aggregate.jsonl")
    agg.registry.dump_jsonl(dump)
    back = MetricsRegistry.from_jsonl(dump)
    assert back.counters["comm.agent.rounds_run/b"] == 3
    assert master.counters["rounds_done"] == 3


def test_loopback_flight_recorder_dumps_on_injected_abort(tmp_path):
    """An agent crashes mid-round under an elastic master: the round
    aborts and the flight recorder ships the black box."""
    from distributed_learning_tpu.comm import ConsensusAgent, ConsensusMaster

    flight = FlightRecorder(str(tmp_path / "flight"), capacity=32)
    agg = RunAggregator(flight=flight)

    async def main():
        master = ConsensusMaster(
            TRIANGLE, convergence_eps=1e-9, elastic=True,
            aggregator=agg, flight=flight,
        )
        host, port = await master.start()
        agents = {
            t: ConsensusAgent(t, host, port, obs=MetricsRegistry())
            for t in "abc"
        }
        await asyncio.gather(*(a.start() for a in agents.values()))
        vals = {
            t: np.full(4, float(i), np.float32)
            for i, t in enumerate("abc")
        }
        # Round 1 completes; its events populate the rings.
        await asyncio.gather(
            *(a.run_round(vals[t], 1.0) for t, a in agents.items())
        )
        await asyncio.gather(
            *(a.send_obs_delta() for a in agents.values())
        )
        # Round 2: "b" crashes the moment the round starts — sockets
        # vanish mid-exchange, deterministically mid-round.
        b = agents["b"]

        async def crash_exchange(y, active=None):
            b._mux.close()
            for s in b._neighbors.values():
                s.close()
            b._master.close()
            raise ConnectionError("simulated crash")

        b._exchange_values = crash_exchange

        async def run(t):
            try:
                return await agents[t].run_round(vals[t], 1.0)
            except ConnectionError:
                return None

        await asyncio.gather(*(run(t) for t in "abc"))
        await asyncio.sleep(0.2)  # master observes the death
        await master.shutdown()
        for t in ("a", "c"):
            await agents[t].close()
        return master

    master = asyncio.run(asyncio.wait_for(main(), 60))

    assert master.counters["rounds_aborted"] == 1
    assert master.counters["flight_dumps"] >= 1
    dumps = [p for p in flight.dumped if "round_aborted" in p]
    assert len(dumps) == 1
    header, events = FlightRecorder.read_dump(dumps[0])
    assert header["reason"] == "round_aborted"
    assert header["token"] == "b" and header["round_id"] == 2
    # The ring contains the abort event and per-agent history from
    # before the fault (round-1 deltas fed the rings).
    assert any(
        e["agent"] == "<master>" and e.get("name") == "agent_down"
        for e in events
    )
    agent_events = {e["agent"] for e in events}
    assert {"a", "b", "c", "<master>"} <= agent_events


def test_loopback_round_deadline_expiry_dumps(tmp_path):
    """A round that overstays round_deadline_s is counted and dumped
    (observe-only: the lock-step round still completes)."""
    from distributed_learning_tpu.comm import ConsensusAgent, ConsensusMaster

    flight = FlightRecorder(str(tmp_path / "flight"), capacity=16)

    async def main():
        master = ConsensusMaster(
            [("a", "b")], convergence_eps=1e-6,
            flight=flight, round_deadline_s=0.05,
        )
        host, port = await master.start()
        agents = {
            t: ConsensusAgent(t, host, port) for t in "ab"
        }
        await asyncio.gather(*(a.start() for a in agents.values()))
        b = agents["b"]
        orig = b._gossip_iteration

        async def slow(y):
            await asyncio.sleep(0.15)  # straggle past the deadline
            return await orig(y)

        b._gossip_iteration = slow
        vals = {"a": np.zeros(2, np.float32), "b": np.ones(2, np.float32)}
        outs = await asyncio.gather(
            *(a.run_round(vals[t], 1.0) for t, a in agents.items())
        )
        await master.shutdown()
        for a in agents.values():
            await a.close()
        return master, outs

    master, outs = asyncio.run(asyncio.wait_for(main(), 60))
    for out in outs:
        np.testing.assert_allclose(out, 0.5, atol=1e-3)  # round completed
    assert master.counters["round_deadlines_expired"] >= 1
    deadline_dumps = [p for p in flight.dumped if "round_deadline" in p]
    assert deadline_dumps
    header, _ = FlightRecorder.read_dump(deadline_dumps[0])
    assert header["waiting_on"]  # names who the master was waiting on


def test_shutdown_with_reason_ships_its_black_box(tmp_path):
    """The fourth trigger: a master torn down WITH a reason dumps; a
    clean (reasonless) shutdown does not."""
    from distributed_learning_tpu.comm import ConsensusMaster

    flight = FlightRecorder(str(tmp_path / "flight"), capacity=8)

    async def main():
        master = ConsensusMaster([("a", "b")], flight=flight)
        await master.start()
        await master.shutdown("operator abort")
        return master

    master = asyncio.run(asyncio.wait_for(main(), 30))
    assert master.counters["flight_dumps"] == 1
    header, _ = FlightRecorder.read_dump(flight.dumped[0])
    assert header["reason"] == "shutdown"
    assert header["detail"] == "operator abort"

    flight2 = FlightRecorder(str(tmp_path / "flight2"))

    async def clean():
        master = ConsensusMaster([("a", "b")], flight=flight2)
        await master.start()
        await master.shutdown()

    asyncio.run(asyncio.wait_for(clean(), 30))
    assert flight2.dumped == []


def test_agent_periodic_obs_stream(tmp_path):
    """start_obs_stream ships deltas without explicit sends; close
    stops the task."""
    from distributed_learning_tpu.comm import ConsensusAgent, ConsensusMaster

    agg = RunAggregator()

    async def main():
        master = ConsensusMaster(
            [("a", "b")], convergence_eps=1e-6, aggregator=agg,
        )
        host, port = await master.start()
        agents = {
            t: ConsensusAgent(t, host, port, obs=MetricsRegistry())
            for t in "ab"
        }
        await asyncio.gather(*(a.start() for a in agents.values()))
        for a in agents.values():
            a.start_obs_stream(period_s=0.05)
        vals = {"a": np.zeros(2, np.float32), "b": np.ones(2, np.float32)}
        await asyncio.gather(
            *(a.run_round(vals[t], 1.0) for t, a in agents.items())
        )
        await asyncio.sleep(0.3)  # a few periods tick
        await master.shutdown()
        for a in agents.values():
            await a.close()

    asyncio.run(asyncio.wait_for(main(), 60))
    assert agg.registry.counters["obs.deltas_merged"] >= 2
    assert agg.registry.counters["comm.agent.rounds_run/a"] == 1
    assert agg.registry.counters["comm.agent.obs_deltas_sent"] >= 2


# ---------------------------------------------------------------------- #
# CLI: obs-report --merge golden, --bench, obs-monitor                   #
# ---------------------------------------------------------------------- #
def _write_agent_logs(tmp_path):
    """Two deterministic per-agent JSONL logs (fixed clocks)."""
    import itertools

    paths = []
    for token, slow in (("a", 0.01), ("b", 0.2)):
        clock = itertools.count(1000)
        reg = MetricsRegistry(clock=lambda c=clock: float(next(c)))
        reg.inc("comm.agent.rounds_run", 5)
        if token == "b":
            reg.inc("comm.agent.stale_requests_dropped", 2)
        for r in range(5):
            reg.observe("comm.agent.round_s", slow + r * 0.001,
                        step=r + 1)
            reg.record_span("comm.agent.round", slow,
                            t0=1000.0 + r + (0.2 if token == "b" else 0.0))
        reg.observe("consensus.residual", 1e-4, step=5)
        path = str(tmp_path / f"{token}.jsonl")
        reg.dump_jsonl(path)
        paths.append(path)
    return paths


def test_obs_report_merge_matches_golden(tmp_path, capsys):
    from distributed_learning_tpu.cli import main

    paths = _write_agent_logs(tmp_path)
    trace_path = str(tmp_path / "trace.json")
    assert main(["obs-report", "--merge", *paths,
                 "--trace", trace_path]) == 0
    out = capsys.readouterr().out
    with open(GOLDEN, "r", encoding="utf-8") as fh:
        golden = fh.read()
    assert out == golden, (
        "obs-report --merge output drifted from the golden file; if the "
        "change is intentional, regenerate tests/data/obs_merge_golden.txt"
    )
    # The merged trace rode along: one track per agent.
    trace = json.load(open(trace_path))
    names = sorted(
        e["args"]["name"] for e in trace["traceEvents"]
        if e["ph"] == "M"
    )
    assert names == ["agent a", "agent b"]
    # --json mode carries both report and straggler profile.
    assert main(["obs-report", "--merge", "--json", *paths]) == 0
    rep = json.loads(capsys.readouterr().out)
    assert rep["straggler"]["slowest_agent"] == "b"
    assert rep["report"]["counters"]["comm.agent.rounds_run"] == 10


def test_obs_report_merge_takes_a_directory_of_logs(tmp_path, capsys):
    """A fleet harness's output directory is ONE argument: it expands to
    its sorted ``*.jsonl`` members (other files are left alone) and
    renders what naming the files renders."""
    from distributed_learning_tpu.cli import main

    paths = _write_agent_logs(tmp_path)
    (tmp_path / "notes.txt").write_text("not a log\n")
    assert main(["obs-report", "--merge", *paths]) == 0
    by_file = capsys.readouterr().out
    assert main(["obs-report", "--merge", str(tmp_path)]) == 0
    assert capsys.readouterr().out == by_file


def test_obs_monitor_once_renders_dashboard(tmp_path, capsys):
    from distributed_learning_tpu.cli import main
    from distributed_learning_tpu.obs import JsonlSink

    # Build an aggregate stream the way a master would: aggregator
    # registry + JsonlSink.
    agg = RunAggregator()
    stream = str(tmp_path / "aggregate.jsonl")
    sink = JsonlSink(stream)
    agg.registry.add_sink(sink)
    for token, slow in (("a", 0.01), ("b", 0.2)):
        reg = MetricsRegistry()
        src = ObsDeltaSource(reg)
        reg.inc("comm.agent.rounds_run", 3)
        reg.inc("comm.bytes_framed_out", 2048)
        if token == "b":
            reg.inc("comm.agent.stale_requests_dropped", 4)
        for r in range(3):
            reg.observe("comm.agent.round_s", slow, step=r + 1)
            reg.observe("consensus.residual", 10.0 ** -(r + 2),
                        step=r + 1)
        agg.process(token, src.pack())
    for r in range(3):
        agg.note_round_arrivals(r + 1, {"a": 100.0 + r, "b": 100.2 + r})
        agg.note_round_done(r + 1, 0.05, wall_t0=100.2 + r)
    sink.close()
    # A torn tail (mid-write) must not break the monitor.
    with open(stream, "a") as fh:
        fh.write('{"kind": "series", "name": "torn')

    assert main(["obs-monitor", stream, "--once"]) == 0
    out = capsys.readouterr().out
    assert "rounds: 3 done" in out
    assert "slowest agent: b" in out
    assert "consensus residual" in out
    assert "KiB out" in out
    # Staleness counters reach the profile through the stream's delta
    # markers (counter totals never travel as events): the b row is
    # token, n, p50, p95, max, slowest, stale, defer, bar.
    b_row = [l for l in out.splitlines() if l.split()[:2] == ["b", "3"]][0]
    assert b_row.split()[6] == "4", b_row
    assert main(["obs-monitor", str(tmp_path / "missing.jsonl"),
                 "--once"]) == 2


# ---------------------------------------------------------------------- #
# Fleet-scale plane (ISSUE 17): sketches, hierarchy, fleet mode          #
# ---------------------------------------------------------------------- #
def _agent_payloads(token, vals, *, packs=1, sketch=True,
                    raw_series=True):
    """``packs`` delta payloads from one synthetic agent registry."""
    from distributed_learning_tpu.obs.aggregate import ObsDeltaSource

    reg = MetricsRegistry(clock=lambda: 0.0)
    src = ObsDeltaSource(reg, sketch=sketch, raw_series=raw_series)
    out = []
    chunk = max(1, len(vals) // packs)
    for p in range(packs):
        for v in vals[p * chunk:(p + 1) * chunk]:
            reg.observe("comm.agent.round_s", float(v))
        reg.inc("comm.agent.rounds_run", chunk)
        out.append(src.pack())
    src.close()
    return out


def test_sketch_quantiles_are_eviction_immune():
    """The PR 6 regression the sketches fix: ring eviction at the
    merged registry used to silently bias percentiles toward the
    retained window.  The sketch path covers every point exactly once
    regardless of the ring, and the eviction is disclosed either way."""
    from distributed_learning_tpu.obs.report import (
        format_straggler_profile,
    )

    vals = [0.01] * 90 + [1.0] * 10  # true p50 = 0.01

    # Registry-direct exact path (obs-monitor's live view) with a tiny
    # ring: the window only sees the last 8 points (all 1.0) — p50
    # collapses to the slow tail.
    from distributed_learning_tpu.obs.aggregate import (
        straggler_profile_from_registry,
    )

    reg = MetricsRegistry(max_points=8, clock=lambda: 0.0)
    for v in vals:
        reg.observe("comm.agent.round_s/a", v)
    prof = straggler_profile_from_registry(reg)
    entry = prof["per_agent"]["a"]
    assert prof["quantiles"] == "exact"
    assert entry["count"] == 8 and entry["p50_s"] == 1.0
    assert entry["evicted"] == 92  # the bias is disclosed ...
    text = format_straggler_profile(prof)
    assert "92 series points evicted" in text  # ... and rendered

    # The delta path, same tiny merged ring: sketch quantiles cover
    # all 100 points no matter what the ring evicted.
    agg2 = RunAggregator(registry=MetricsRegistry(max_points=8,
                                                  clock=lambda: 0.0))
    for payload in _agent_payloads("a", vals, sketch=True):
        agg2.process("a", payload)
    prof2 = agg2.straggler_profile()
    entry2 = prof2["per_agent"]["a"]
    assert prof2["quantiles"] == "sketch"
    assert entry2["count"] == 100
    assert entry2["p50_s"] == pytest.approx(0.01, rel=0.01)
    assert entry2["max_s"] == 1.0  # extremes stay exact
    text2 = format_straggler_profile(prof2)
    assert "quantiles: sketch" in text2
    assert "evicted" not in text2  # sketch path has nothing to warn


def test_v1_payload_without_sketch_section_still_sketches():
    """Version compatibility: a v1 producer (no ``sketches`` section)
    merges fine — the aggregator derives the sketch state from the raw
    series points, so mixed-version fleets keep one coherent profile."""
    agg = RunAggregator(registry=MetricsRegistry(clock=lambda: 0.0))
    payload = {
        "kind": "obs.delta", "v": 1, "seq": 1,
        "counters": {"comm.agent.rounds_run": 3.0},
        "gauges": {},
        "events": [
            {"kind": "series", "name": "comm.agent.round_s",
             "value": v, "ts": 0.0}
            for v in (0.1, 0.2, 0.3)
        ],
    }
    agg.process("old", payload)
    sk = agg.sketch("comm.agent.round_s/old")
    assert sk is not None and sk.n == 3
    assert agg.straggler_profile()["per_agent"]["old"]["count"] == 3
    # A payload that DOES carry the section is authoritative: the
    # aggregator must not re-sketch its raw points (double count).
    agg2 = RunAggregator(registry=MetricsRegistry(clock=lambda: 0.0))
    for p in _agent_payloads("new", [0.1, 0.2, 0.3]):
        agg2.process("new", p)
    sk2 = agg2.sketch("comm.agent.round_s/new")
    assert sk2 is not None and sk2.n == 3  # not 6


def test_two_tier_aggregation_matches_flat_merge():
    """Aggregate-of-aggregates oracle at unit scale: pods forward
    merged sketch deltas upstream and the root renders exactly the
    flat merge's per-agent quantiles."""
    from distributed_learning_tpu.obs import SubAggregator

    streams = {
        f"t{i}": _agent_payloads(f"t{i}", [0.01 * (i + 1)] * 20, packs=2)
        for i in range(6)
    }
    flat = RunAggregator(registry=MetricsRegistry(clock=lambda: 0.0))
    subs = [
        SubAggregator(registry=MetricsRegistry(clock=lambda: 0.0))
        for _ in range(2)
    ]
    root = RunAggregator(registry=MetricsRegistry(clock=lambda: 0.0))
    for p in range(2):
        for i, (token, payloads) in enumerate(sorted(streams.items())):
            flat.process(token, payloads[p])
            subs[i % 2].process(token, payloads[p])
        for s, sub in enumerate(subs):
            root.process(f"pod{s}", sub.export_delta())
    fp = flat.straggler_profile()["per_agent"]
    rp = root.straggler_profile()["per_agent"]
    assert set(fp) == set(rp)
    for token in fp:
        for key in ("count", "p50_s", "p95_s", "max_s"):
            assert fp[token][key] == rp[token][key], (token, key)
    assert (flat.registry.counters["comm.agent.rounds_run"]
            == pytest.approx(
                root.registry.counters["comm.agent.rounds_run"]))


def test_subaggregator_export_filters_tier_bookkeeping():
    """A pod's upstream delta must carry the fleet's signal, not the
    pod's own merge accounting: ``obs.*`` counters and the per-payload
    ``obs.delta`` stream markers stay local to the tier."""
    from distributed_learning_tpu.obs import SubAggregator

    sub = SubAggregator(registry=MetricsRegistry(clock=lambda: 0.0),
                        forward_raw_series=False)
    for token in ("a", "b"):
        for p in _agent_payloads(token, [0.1, 0.2], packs=1):
            sub.process(token, p)
    export = sub.export_delta()
    assert export["agg"] is True
    assert is_obs_payload(export)
    assert not any(n.startswith("obs.") for n in export["counters"])
    assert not any(e.get("name") == "obs.delta"
                   for e in export["events"])
    # The pod's merged per-agent sketches ride upstream.
    assert "comm.agent.round_s/a" in export["sketches"]
    assert "comm.agent.round_s" in export["sketches"]
    # Fleet mode at the pod tier: no raw sketched-series events.
    assert not any(
        e.get("kind") == "series"
        and e.get("name", "").startswith("comm.agent.round_s")
        for e in export["events"]
    )


def test_fleet_mode_suppression_is_disclosed_not_silent():
    """``raw_series=False``: sketched series stop travelling as raw
    points (O(metrics) deltas), the substitution count rides in the
    payload, and the aggregator surfaces it as ``obs.series_sketched``."""
    agg = RunAggregator(registry=MetricsRegistry(clock=lambda: 0.0))
    payloads = _agent_payloads("a", [0.1] * 30, raw_series=False)
    for p in payloads:
        assert not any(e.get("kind") == "series"
                       and e.get("name") == "comm.agent.round_s"
                       for e in p["events"])
        assert p["series_sketched"] == 30
        agg.process("a", p)
    assert agg.registry.counters["obs.series_sketched"] == 30
    # The profile still has the full picture — from the sketch.
    assert agg.straggler_profile()["per_agent"]["a"]["count"] == 30


def test_fleet_mode_delta_bytes_grow_with_buckets_not_samples():
    """Bounded delta bytes: with sketched series kept off the raw
    stream, ten times the samples from one distribution stay under three
    times the payload (raw points would be ten times)."""
    from distributed_learning_tpu.obs.aggregate import ObsDeltaSource

    def pack_bytes(points):
        reg = MetricsRegistry(clock=lambda: 0.0)
        src = ObsDeltaSource(reg, raw_series=False)
        rng = np.random.default_rng(7)
        for v in rng.lognormal(mean=-3.0, sigma=1.0, size=points):
            reg.observe("comm.agent.round_s", float(v))
        payload = src.pack()
        src.close()
        return len(json.dumps(payload).encode())

    assert pack_bytes(2000) <= 3 * pack_bytes(200)


def test_pod_export_rolls_up_the_per_agent_counter_rows():
    """Bounded upstream bytes: a pod that rolls labels up
    (``rollup_labels=``) folds the per-agent counter dimension, so four
    times the agents at the same volume each stay under four times the
    exported delta (the per-agent sketches, kept for the straggler
    profile, are the part that still grows with agents)."""
    from distributed_learning_tpu.obs import SubAggregator
    from distributed_learning_tpu.obs.aggregate import ObsDeltaSource

    def export_bytes(agents):
        sub = SubAggregator(
            registry=MetricsRegistry(clock=lambda: 0.0),
            forward_raw_series=False, rollup_labels=16,
        )
        for _pack in range(2):
            for i in range(agents):
                reg = MetricsRegistry(clock=lambda: 0.0)
                src = ObsDeltaSource(reg, raw_series=False)
                for v in np.random.default_rng(i).lognormal(size=20):
                    reg.observe("comm.agent.round_s", float(v))
                reg.inc("comm.agent.rounds_run", 20)
                sub.process(f"b{i:04d}", src.pack())
                src.close()
        return len(json.dumps(sub.export_delta()).encode())

    assert export_bytes(64) <= 4 * export_bytes(16)


def test_flight_recorder_global_cap_sheds_proportionally():
    """ISSUE 17 satellite: a 500-agent fleet must not grow the flight
    recorder 500x — the global cap shrinks the per-agent window as
    agents appear, oldest-first, and ``snapshot()`` discloses it."""
    import tempfile

    with tempfile.TemporaryDirectory() as d:
        fr = FlightRecorder(d, capacity=64, global_capacity=64)
        for i in range(8):
            for j in range(20):
                fr.note(f"a{i}", "ev", j=j)
        snap = fr.snapshot()
        assert snap["agents"] == 8
        assert snap["per_agent_capacity"] == 8  # 64 // 8
        assert snap["global_capacity"] == 64
        assert snap["occupancy"] <= 64
        assert sum(snap["evictions"].values()) > 0
        # The window keeps the TAIL (newest events), like the rings.
        assert [e["j"] for e in fr.ring("a0")] == list(range(12, 20))
