"""Observability layer tests (obs/): registry units, JSONL round-trip,
span nesting, device-side carry, comm counters, CLI report — plus the
oracle that matters most: obs-enabled training is BIT-IDENTICAL to
obs-disabled training (params and loss trace), per the repo's
exact-equality convention.  The carry is part of the compiled chunk
either way, so the toggle only changes host-side bookkeeping — this
test pins that invariant.
"""

import asyncio
import json

import numpy as np
import pytest

from distributed_learning_tpu import obs
from distributed_learning_tpu.obs import (
    JsonlSink,
    JsonlTelemetry,
    MetricsRegistry,
    SpanTracer,
    flush_chunk,
    instrument_step,
    use_registry,
)


# ---------------------------------------------------------------------- #
# Registry                                                               #
# ---------------------------------------------------------------------- #
def test_registry_counters_gauges_series():
    reg = MetricsRegistry()
    assert reg.inc("rounds", 2) == 2.0
    assert reg.inc("rounds") == 3.0
    reg.gauge("depth", 4)
    reg.gauge("depth", 1)  # last value wins
    reg.observe("loss", 0.5, step=10)
    reg.observe("loss", 0.3, step=20)
    reg.observe("loss", 0.7, step=30)
    snap = reg.snapshot()
    assert snap["counters"]["rounds"] == 3.0
    assert snap["gauges"]["depth"] == 1.0
    assert snap["series"]["loss"] == 3
    rep = reg.run_report()
    s = rep["series"]["loss"]
    assert s["count"] == 3 and s["min"] == 0.3 and s["max"] == 0.7
    assert s["last"] == 0.7 and s["last_step"] == 30
    assert s["mean"] == pytest.approx(0.5)


def test_registry_thread_safety():
    import threading

    reg = MetricsRegistry()

    def work():
        for _ in range(1000):
            reg.inc("n")

    threads = [threading.Thread(target=work) for _ in range(8)]
    for t in threads:
        t.start()
    for t in threads:
        t.join()
    assert reg.counters["n"] == 8000


def test_registry_jsonl_roundtrip(tmp_path):
    reg = MetricsRegistry()
    reg.inc("comm.bytes", 1024)
    reg.gauge("depth", 2)
    reg.observe("residual", 1e-3, step=5)
    reg.record_span("epoch", 0.25, depth=0)
    reg.event("abort", token="b", reason="died")
    path = str(tmp_path / "run.jsonl")
    n = reg.dump_jsonl(path)
    # Every line parses as JSON (the event-log contract).
    lines = [json.loads(l) for l in open(path) if l.strip()]
    assert len(lines) == n
    back = MetricsRegistry.from_jsonl(path)
    assert back.counters == reg.counters
    assert back.gauges == reg.gauges
    assert back.series == {"residual": [(5, 1e-3)]}
    assert back.run_report()["spans"]["epoch"]["count"] == 1
    # Replayed events include the free-form one.
    assert any(
        e.get("kind") == "event" and e.get("name") == "abort"
        for e in back.events
    )


def test_jsonl_sink_streams_each_event(tmp_path):
    path = str(tmp_path / "stream.jsonl")
    reg = MetricsRegistry()
    sink = JsonlSink(path)
    reg.add_sink(sink)
    reg.observe("loss", 1.0, step=1)
    reg.observe("loss", 0.5, step=2)
    # On disk already, before any dump/close — the streaming guarantee.
    lines = [json.loads(l) for l in open(path) if l.strip()]
    assert [e["value"] for e in lines] == [1.0, 0.5]
    sink.close()


def test_jsonl_telemetry_streams_payloads(tmp_path):
    path = str(tmp_path / "telemetry.jsonl")
    tel = JsonlTelemetry(path)
    tel.process("a", {"loss": 0.5})
    tel.process("b", {"loss": 0.25})
    lines = [json.loads(l) for l in open(path) if l.strip()]
    assert [e["token"] for e in lines] == ["a", "b"]
    assert lines[1]["payload"]["loss"] == 0.25
    tel.close()


def test_use_registry_scopes_default():
    inner = MetricsRegistry()
    with use_registry(inner):
        assert obs.get_registry() is inner
        obs.get_registry().inc("x")
    assert obs.get_registry() is not inner
    assert inner.counters["x"] == 1.0


# ---------------------------------------------------------------------- #
# Spans                                                                  #
# ---------------------------------------------------------------------- #
def test_span_nesting_depth_and_parent():
    tr = SpanTracer()
    with tr.span("outer"):
        with tr.span("mid"):
            with tr.span("inner"):
                pass
        with tr.span("mid2"):
            pass
    by_name = {s.name: s for s in tr.spans}
    assert by_name["outer"].depth == 0 and by_name["outer"].parent is None
    assert by_name["mid"].depth == 1 and by_name["mid"].parent == "outer"
    assert by_name["inner"].depth == 2 and by_name["inner"].parent == "mid"
    assert by_name["mid2"].parent == "outer"
    # Children complete before parents; parent duration covers child.
    assert by_name["outer"].dur >= by_name["mid"].dur >= by_name["inner"].dur


def test_span_exception_still_recorded():
    tr = SpanTracer()
    with pytest.raises(RuntimeError):
        with tr.span("boom"):
            raise RuntimeError("x")
    assert [s.name for s in tr.spans] == ["boom"]


def test_span_chrome_trace_export(tmp_path):
    tr = SpanTracer()
    with tr.span("a"):
        with tr.span("b"):
            pass
    path = str(tmp_path / "trace.json")
    n = tr.export_chrome_trace(path)
    trace = json.load(open(path))
    assert n == 2 and len(trace["traceEvents"]) == 2
    for ev in trace["traceEvents"]:
        assert ev["ph"] == "X" and ev["dur"] >= 0 and "ts" in ev
    # b nests inside a on the timeline.
    by = {e["name"]: e for e in trace["traceEvents"]}
    assert by["a"]["ts"] <= by["b"]["ts"]
    assert by["a"]["ts"] + by["a"]["dur"] >= by["b"]["ts"] + by["b"]["dur"]


def test_span_aggregates_into_registry():
    reg = MetricsRegistry()
    tr = SpanTracer(registry=reg)
    for _ in range(3):
        with tr.span("step"):
            pass
    rep = reg.run_report()
    assert rep["spans"]["step"]["count"] == 3
    assert rep["spans"]["step"]["total_s"] >= rep["spans"]["step"]["max_s"]


def test_span_cap_keeps_aggregates_exact():
    reg = MetricsRegistry()
    tr = SpanTracer(registry=reg, max_spans=2)
    for _ in range(5):
        with tr.span("s"):
            pass
    assert len(tr.spans) == 2 and tr.dropped == 3
    assert reg.run_report()["spans"]["s"]["count"] == 5  # exact past cap


# ---------------------------------------------------------------------- #
# Carry                                                                  #
# ---------------------------------------------------------------------- #
def test_flush_chunk_records_per_node_and_mean():
    reg = MetricsRegistry()
    arr = np.array([[1.0, 3.0], [3.0, 5.0]])  # (steps=2, nodes=2)
    out = flush_chunk(
        reg, {"loss": arr, "rounds": np.float32(4.0)},
        step0=10, node_names=["a", "b"],
    )
    assert isinstance(out["loss"], np.ndarray)
    rep = reg.run_report()
    assert rep["series"]["train.loss/a"]["last"] == 2.0
    assert rep["series"]["train.loss/b"]["last"] == 4.0
    assert rep["series"]["train.loss"]["last"] == 3.0
    assert rep["series"]["train.loss"]["last_step"] == 12
    assert rep["series"]["train.rounds"]["last_step"] == 10
    # registry=None still materializes (the trainer's obs-off path).
    out2 = flush_chunk(None, {"x": arr})
    assert np.array_equal(out2["x"], arr)


def test_global_norm_matches_numpy():
    import jax.numpy as jnp

    tree = {"a": jnp.asarray([3.0, 4.0]), "b": jnp.asarray([[12.0]])}
    got = float(obs.global_norm(tree))
    assert got == pytest.approx(13.0)


# ---------------------------------------------------------------------- #
# instrument_step                                                        #
# ---------------------------------------------------------------------- #
def test_instrument_step_counts_and_delegates():
    import jax
    import jax.numpy as jnp

    base = jax.jit(lambda x: x * 2)
    step = instrument_step(base, "test.step")
    reg = MetricsRegistry()
    with use_registry(reg):
        out = step(jnp.float32(3.0))
    assert float(out) == 6.0
    assert reg.counters["test.step.calls"] == 1.0
    # .lower() still reaches the jit object (the audit's contract).
    lowered = step.lower(jnp.float32(1.0))
    assert hasattr(lowered, "compile")


# ---------------------------------------------------------------------- #
# Comm counters (agent + master + bytes framed)                          #
# ---------------------------------------------------------------------- #
def test_agent_and_master_gossip_counters():
    from distributed_learning_tpu.comm import ConsensusAgent, ConsensusMaster

    reg = MetricsRegistry()

    async def main():
        master = ConsensusMaster([("a", "b")], convergence_eps=1e-6)
        host, port = await master.start()
        agents = [ConsensusAgent(t, host, port) for t in ("a", "b")]
        await asyncio.gather(*(ag.start() for ag in agents))
        await asyncio.gather(
            *(ag.run_once(np.ones(4, np.float32)) for ag in agents)
        )
        await asyncio.gather(
            *(ag.run_round(np.ones(4, np.float32)) for ag in agents)
        )
        stats = [ag.wire_stats() for ag in agents]
        await master.shutdown()
        for ag in agents:
            await ag.close()
        return master, agents, stats

    with use_registry(reg):
        master, agents, stats = asyncio.run(asyncio.wait_for(main(), 60))

    for ag in agents:
        assert ag.counters["run_once"] == 1
        assert ag.counters["rounds_run"] == 1
        assert ag.counters["gossip_iterations"] >= 2
        assert ag.counters.get("rounds_aborted", 0) == 0
    assert master.counters["registrations"] == 2
    assert master.counters["rounds_started"] == 1
    assert master.counters["rounds_done"] == 1
    # Bytes framed: every agent both sent and received whole frames.
    for st in stats:
        assert st["bytes_sent"] > 0 and st["bytes_received"] > 0
        assert st["frames_sent"] > 0 and st["frames_received"] > 0
    # ...and the registry aggregated the wire volume + per-role counters.
    assert reg.counters["comm.bytes_framed_out"] > 0
    assert reg.counters["comm.bytes_framed_in"] > 0
    assert reg.counters["comm.agent.rounds_run"] == 2
    assert reg.counters["comm.master.rounds_done"] == 1
    assert "comm.master.telemetry_payloads" not in reg.counters


def test_agent_debug_routes_through_logging(caplog):
    """The _debug path is the standard logging module now: named logger,
    lazy formatting, no prints."""
    import logging

    from distributed_learning_tpu.comm import ConsensusAgent, ConsensusMaster

    async def main():
        master = ConsensusMaster([("a", "b")])
        host, port = await master.start()
        agents = [ConsensusAgent(t, host, port) for t in ("a", "b")]
        await asyncio.gather(*(ag.start() for ag in agents))
        await master.shutdown()
        for ag in agents:
            await ag.close()

    with caplog.at_level(logging.DEBUG, logger="dlt"):
        asyncio.run(asyncio.wait_for(main(), 60))
    names = {r.name for r in caplog.records}
    assert "dlt.comm.master" in names
    assert any(n.startswith("dlt.comm.agent.") for n in names)
    assert any("registered" in r.message for r in caplog.records)


# ---------------------------------------------------------------------- #
# Prefetch counters                                                      #
# ---------------------------------------------------------------------- #
def test_prefetch_counts_batches_and_wait():
    from distributed_learning_tpu.data.prefetch import prefetch_to_device

    reg = MetricsRegistry()
    batches = [np.ones((2, 2), np.float32) * i for i in range(5)]
    with use_registry(reg):
        out = list(prefetch_to_device(iter(batches), size=2))
    assert len(out) == 5
    assert reg.counters["data.prefetch.batches"] == 5
    assert reg.counters["data.prefetch.consumer_wait_s"] >= 0
    assert "data.prefetch.depth" in reg.gauges


# ---------------------------------------------------------------------- #
# CLI: obs-report                                                        #
# ---------------------------------------------------------------------- #
def test_cli_obs_report(tmp_path, capsys):
    from distributed_learning_tpu.cli import main

    reg = MetricsRegistry()
    reg.inc("comm.agent.rounds_run", 7)
    reg.observe("consensus.residual", 1e-4, step=100)
    reg.record_span("trainer.epoch", 1.5)
    path = str(tmp_path / "run.jsonl")
    reg.dump_jsonl(path)

    assert main(["obs-report", path]) == 0
    out = capsys.readouterr().out
    assert "comm.agent.rounds_run" in out and "7" in out
    assert "consensus.residual" in out
    assert "trainer.epoch" in out

    assert main(["obs-report", "--json", path]) == 0
    rep = json.loads(capsys.readouterr().out)
    assert rep["counters"]["comm.agent.rounds_run"] == 7
    assert rep["spans"]["trainer.epoch"]["count"] == 1

    assert main(["obs-report", str(tmp_path / "missing.jsonl")]) == 2


# ---------------------------------------------------------------------- #
# Oracle: obs on == obs off, bit for bit                                 #
# ---------------------------------------------------------------------- #
def _tiny_trainer(obs_arg, seed_data=0):
    from distributed_learning_tpu.training.trainer import GossipTrainer

    rng = np.random.default_rng(seed_data)
    train = {
        i: (
            rng.standard_normal((96, 8)).astype(np.float32),
            (rng.integers(0, 2, 96) * 2 - 1).astype(np.float32),
        )
        for i in range(3)
    }
    return GossipTrainer(
        node_names=[0, 1, 2],
        model="ann",
        model_args=[1],
        model_kwargs={"hidden_dim": 8},
        error="binary_logistic",
        weights=np.full((3, 3), 1.0 / 3.0),
        train_data=train,
        stat_step=2,
        epoch=2,
        batch_size=16,
        mix_eps=1e-5,
        obs=obs_arg,
        seed=1,
        dropout=False,
    )


def test_trainer_obs_enabled_is_bit_identical_to_disabled(tmp_path):
    import jax

    reg = MetricsRegistry()
    t_on = _tiny_trainer(reg)
    t_off = _tiny_trainer(None)
    t_prof = _tiny_trainer(None)
    outs_on = t_on.start_consensus()
    outs_off = t_off.start_consensus()
    # Under an open profiler session every span is a live annotation and
    # the programs' named scopes are being recorded: still the same bits.
    with jax.profiler.trace(str(tmp_path)):
        outs_prof = t_prof.start_consensus()

    # Exact equality: final params, every epoch's loss/acc trace.
    for other, outs in ((t_off, outs_off), (t_prof, outs_prof)):
        for a, b in zip(
            jax.tree.leaves(t_on.state[0]), jax.tree.leaves(other.state[0])
        ):
            np.testing.assert_array_equal(np.asarray(a), np.asarray(b))
        for oa, ob in zip(outs_on, outs):
            np.testing.assert_array_equal(oa["train_loss"], ob["train_loss"])
            np.testing.assert_array_equal(oa["train_acc"], ob["train_acc"])
            np.testing.assert_array_equal(oa["grad_norm"], ob["grad_norm"])
            assert oa["mix_rounds"] == ob["mix_rounds"] > 0
            assert oa["deviation"] == ob["deviation"]

    # And the enabled run actually observed things.
    rep = reg.run_report()
    assert rep["counters"]["consensus.rounds_run"] >= 2
    assert rep["series"]["train.loss"]["count"] == 2
    assert rep["series"]["train.grad_norm/0"]["count"] == 2
    assert rep["series"]["consensus.residual"]["count"] == 2
    for name in ("trainer.epoch", "trainer.indices", "trainer.dispatch",
                 "trainer.mix", "trainer.flush", "trainer.stats",
                 "trainer.deviation"):
        assert rep["spans"][name]["count"] == 2, name


def test_trainer_telemetry_streams_per_chunk():
    """Telemetry flushes once per jitted chunk (epoch), carrying the
    device-side metrics — grad_norm and mix_rounds ride the existing
    TelemetryProcessor interface unchanged."""
    from distributed_learning_tpu.utils import RecordingTelemetry

    tel = RecordingTelemetry()
    trainer = _tiny_trainer(None)
    trainer.telemetry = tel
    trainer.train_epoch()
    # One payload per node after ONE chunk — streaming, not end-of-run.
    assert len(tel.records) == 3
    for _tok, payload in tel.records:
        assert payload["grad_norm"] > 0
        assert payload["mix_rounds"] >= 1
        assert "deviation" in payload
    trainer.train_epoch()
    assert len(tel.records) == 6
