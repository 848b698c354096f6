"""Device-cost observatory (obs/cost.py): CostProfile extraction on a
known-FLOPs program, MFU arithmetic and its peak source, the sampled
dispatch timer's sync accounting,
the trainer/engine integration (bit-identity preserved), and the
graftlint audit's cost columns."""

import json

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from distributed_learning_tpu.obs import (
    CostProfile,
    MetricsRegistry,
    SampledDispatchTimer,
    instrument_step,
    use_registry,
)
from distributed_learning_tpu.obs import cost as cost_mod

@pytest.fixture(autouse=True)
def _fresh_profiles():
    cost_mod.clear_profiles()
    yield
    cost_mod.clear_profiles()


# ---------------------------------------------------------------------- #
# CostProfile extraction                                                 #
# ---------------------------------------------------------------------- #
def test_cost_profile_known_flops_matmul():
    """XLA counts 2*M*K*N FLOPs for a dense matmul — the profile must
    report exactly that, plus coherent memory accounting."""
    m, k, n = 64, 128, 32
    f = jax.jit(lambda a, b: a @ b)
    reg = MetricsRegistry()
    with use_registry(reg):
        prof = cost_mod.profile_fn(
            f, jnp.ones((m, k)), jnp.ones((k, n)), name="matmul"
        )
    assert prof.flops == 2 * m * k * n
    assert prof.argument_bytes == 4 * (m * k + k * n)
    assert prof.output_bytes == 4 * m * n
    assert prof.peak_bytes == (
        prof.argument_bytes + prof.output_bytes
        + prof.temp_bytes - prof.alias_bytes
    )
    assert prof.collectives == {}  # single-program matmul: no comms
    # Registered process-wide + mirrored as cost.* gauges.
    assert cost_mod.get_profile("matmul") is prof
    assert reg.gauges["cost.flops/matmul"] == prof.flops
    assert reg.gauges["cost.peak_bytes/matmul"] == prof.peak_bytes
    # Serialization round-trips (the ledger stores profiles as dicts).
    again = CostProfile.from_dict(prof.to_dict())
    assert again == prof


def test_cost_profile_counts_loop_body_once():
    """XLA's cost analysis does NOT fold scan trip counts in — the
    body is counted once regardless of length.  Every ``loop_steps``
    multiplier in the trainer/bench MFU math assumes exactly this;
    if XLA ever starts folding trip counts, this pin fails first."""

    def run(c, xs):
        return jax.lax.scan(lambda c, x: (c @ x, ()), c, xs)[0]

    c = jnp.ones((32, 32))
    f2 = cost_mod.profile_fn(
        jax.jit(run), c, jnp.ones((2, 32, 32)), register=False, name="s2"
    )
    f8 = cost_mod.profile_fn(
        jax.jit(run), c, jnp.ones((8, 32, 32)), register=False, name="s8"
    )
    assert f2.flops == f8.flops  # body once, not per trip
    # ...which is why mfu() takes the caller-known trip product:
    assert f8.mfu(1.0, 1e9, loop_steps=8) == pytest.approx(
        8 * f8.flops / 1e9
    )


def test_cost_profile_sees_donation():
    """Donated inputs alias their outputs: ``alias_bytes`` exposes the
    in-place-update headroom the trainer's donated state relies on."""
    f = jax.jit(lambda s: s * 2.0, donate_argnums=(0,))
    prof = cost_mod.profile_fn(
        f, jnp.ones((256,)), name="donated", register=False
    )
    assert prof.alias_bytes == 256 * 4
    assert cost_mod.get_profile("donated") is None  # register=False


def test_instrument_step_delegates_aot_surface():
    """The instrumented wrapper must expose ``lower`` AND ``compile`` so
    the cost/audit paths never unwrap (ISSUE 7 satellite)."""
    f = jax.jit(lambda a: a @ a)
    step = instrument_step(f, "test.step")
    x = jnp.ones((16, 16))
    compiled = step.compile(x)
    assert compiled.cost_analysis() is not None
    assert step.lower(x).compile().memory_analysis() is not None
    # profile_fn picks the span name off the wrapper.
    prof = cost_mod.profile_fn(step, x)
    assert prof.name == "test.step"
    assert prof.flops == 2 * 16 * 16 * 16
    # ...and the instrumented call path still counts (unchanged).
    reg = MetricsRegistry()
    with use_registry(reg):
        pass  # (call counting is covered in test_obs.py)


# ---------------------------------------------------------------------- #
# MFU arithmetic + peak source                                           #
# ---------------------------------------------------------------------- #
def test_mfu_arithmetic():
    assert cost_mod.mfu(1e12, 0.5, 4e12) == pytest.approx(0.5)
    assert cost_mod.mfu(None, 0.5, 4e12) is None
    assert cost_mod.mfu(1e12, 0.0, 4e12) is None
    assert cost_mod.mfu(1e12, 0.5, None) is None
    prof = CostProfile(name="p", flops=1e9, bytes_accessed=4e9)
    # 10 dispatches of 1 GFLOP in 2s against a 10 GFLOP/s peak = 50%.
    assert prof.mfu(2.0, 10e9, dispatches=10) == pytest.approx(0.5)
    assert prof.bytes_per_sec(2.0, dispatches=10) == pytest.approx(2e10)


def test_device_peak_flops_source(monkeypatch):
    """Peak FLOP/s: env override wins; CPU (unknown chip) is None so an
    MFU can never be fabricated against a guessed ceiling."""
    monkeypatch.delenv(cost_mod.PEAK_FLOPS_ENV, raising=False)
    assert cost_mod.device_peak_flops() is None  # test mesh is CPU
    monkeypatch.setenv(cost_mod.PEAK_FLOPS_ENV, "1.97e14")
    assert cost_mod.device_peak_flops() == pytest.approx(1.97e14)

    class FakeDevice:
        device_kind = "TPU v5 lite"

    monkeypatch.delenv(cost_mod.PEAK_FLOPS_ENV, raising=False)
    assert cost_mod.device_peak_flops(FakeDevice()) == pytest.approx(
        197e12
    )


# ---------------------------------------------------------------------- #
# Sampled dispatch timer                                                 #
# ---------------------------------------------------------------------- #
def test_sampled_timer_off_by_default():
    timer = SampledDispatchTimer()
    reg = MetricsRegistry()
    with use_registry(reg):
        assert not timer.enabled
        assert not any(timer.tick() for _ in range(8))
    assert timer.samples == timer.skipped == 0
    assert reg.counters == {}


def test_sampled_timer_sync_accounting():
    """1-in-N means exactly ceil(calls/N) syncs, each visible in the
    counters — the graftlint-honest accounting of the declared sample."""
    import time

    reg = MetricsRegistry()
    prof = CostProfile(name="prog", flops=1e9, bytes_accessed=2e9)
    timer = SampledDispatchTimer(
        2, name="prog", registry=reg, peak_flops=1e13
    )
    x = jnp.ones((8,))
    decisions = []
    for step in range(5):
        sampled = timer.tick()
        decisions.append(sampled)
        if sampled:
            timer.measure(x, time.perf_counter(), profile=prof, step=step)
    assert decisions == [True, False, True, False, True]
    assert timer.samples == 3 and timer.skipped == 2
    assert reg.counters["cost.timer.samples"] == 3
    assert reg.counters["cost.timer.skipped"] == 2
    series = reg.series["cost.step_time_s/prog"]
    assert len(series) == 3
    assert all(v > 0 for _, v in series)
    assert 0 < reg.gauges["cost.mfu/prog"] < 1e6
    assert reg.gauges["cost.bytes_per_sec/prog"] > 0
    assert timer.last_step_time_s > 0


# ---------------------------------------------------------------------- #
# Trainer integration: profiling + sampled timer, bit-identity intact    #
# ---------------------------------------------------------------------- #
def _tiny_trainer(**kwargs):
    from distributed_learning_tpu.training.trainer import GossipTrainer

    rng = np.random.default_rng(7)
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
        mix_times=2,
        seed=1,
        dropout=False,
        **kwargs,
    )


def test_trainer_cost_observatory_is_bit_identical(monkeypatch):
    """Enabling cost profiling AND the sampled timer changes nothing the
    program computes: same params, same traces — the obs on/off oracle
    extended to the observatory knobs — while the registry gains the
    cost gauges, the sampled step-time series, and the telemetry
    payloads gain (None-able) step_time_s/mfu keys."""
    from distributed_learning_tpu.utils import RecordingTelemetry

    monkeypatch.setenv(cost_mod.PEAK_FLOPS_ENV, "1e12")
    reg = MetricsRegistry()
    tel = RecordingTelemetry()
    t_on = _tiny_trainer(
        obs=reg, telemetry=tel, profile_costs=True, timer_every_n=2
    )
    t_off = _tiny_trainer()
    outs_on = t_on.start_consensus()
    outs_off = t_off.start_consensus()
    for a, b in zip(
        jax.tree.leaves(t_on.state[0]), jax.tree.leaves(t_off.state[0])
    ):
        np.testing.assert_array_equal(np.asarray(a), np.asarray(b))
    for oa, ob in zip(outs_on, outs_off):
        np.testing.assert_array_equal(oa["train_loss"], ob["train_loss"])
        np.testing.assert_array_equal(oa["train_acc"], ob["train_acc"])

    prof = cost_mod.get_profile("trainer.epoch")
    assert prof is not None and prof.flops > 0
    assert reg.gauges["cost.flops/trainer.epoch"] == prof.flops
    # 2 epochs at 1-in-2 sampling: exactly one sync taken, one skipped.
    assert reg.counters["cost.timer.samples"] == 1
    assert reg.counters["cost.timer.skipped"] == 1
    assert len(reg.series["cost.step_time_s/trainer.epoch"]) == 1
    assert reg.gauges["cost.mfu/trainer.epoch"] > 0
    # Telemetry payloads carry the sampled measurement (None when the
    # chunk was not sampled) — 3 nodes x 2 epochs.
    assert len(tel.records) == 6
    sampled = [p["step_time_s"] for _, p in tel.records]
    assert sampled[:3] != [None] * 3 and sampled[3:] == [None] * 3
    assert all("mfu" in p for _, p in tel.records)


def test_trainer_superstep_cost_profile():
    """The K-epoch superstep registers its own profile.  Per the loop
    caveat XLA counts the nested scan bodies ONCE: the superstep
    profile is the epoch body plus the in-program gossip/residual tail
    — more than one epoch, nowhere near K of them (the loop_steps
    multipliers in the timer math assume exactly this shape)."""
    t = _tiny_trainer(obs=MetricsRegistry(), profile_costs=True,
                      timer_every_n=1)
    t.initialize_nodes()
    e = t.cost_profile()
    t.train_epochs(2)
    s = cost_mod.get_profile("trainer.superstep2")
    assert s is not None and e is not None
    assert e.flops < s.flops < 1.5 * e.flops
    timer = t._cost_timer
    assert timer.samples == 1 and timer.last_step_time_s > 0


def test_consensus_engine_cost_profile():
    from distributed_learning_tpu.parallel.consensus import ConsensusEngine
    from distributed_learning_tpu.parallel.topology import Topology

    eng = ConsensusEngine(Topology.ring(4).metropolis_weights())
    x = {"w": jnp.ones((4, 16)), "b": jnp.zeros((4, 2))}
    prof = eng.cost_profile(x, times=2)
    assert prof.name == "consensus.mix"
    assert prof.flops > 0
    assert cost_mod.get_profile("consensus.mix") is prof


# ---------------------------------------------------------------------- #
# tp/pp entry points                                                     #
# ---------------------------------------------------------------------- #
def test_tp_step_profile_via_instrumented_factory():
    """The tp factory returns an InstrumentedStep; its profile extracts
    through the delegated AOT surface and the collective inventory
    matches the audit's pinned compiled-HLO counts."""
    from tools.graftlint.jaxpr_audit import EXPECTED_PATH, load_expected

    from tools.graftlint.jaxpr_audit import _tp_step_compiled

    compiled = _tp_step_compiled()
    prof = CostProfile.from_compiled("tp.train_step", compiled)
    assert prof.flops > 0
    pinned = load_expected(EXPECTED_PATH)["tp_train_step"]
    inv = pinned["inventory"]
    assert prof.collectives.get("all-reduce") == inv["all-reduce|"]
    assert prof.collectives.get("all-gather") == inv["all-gather|"]
    cost_pin = pinned["cost"]
    assert prof.flops == pytest.approx(
        cost_pin["flops"], rel=cost_pin["rtol"]
    )


@pytest.mark.skipif(
    not hasattr(jax, "shard_map"),
    reason="pp 1F1B needs the jax.shard_map surface (jax >= 0.7 era)",
)
def test_pp_1f1b_step_profile():
    from distributed_learning_tpu.training.pp import make_1f1b_train_step
    from jax.sharding import Mesh

    S, D, M, MB = 4, 8, 4, 2
    mesh = Mesh(np.array(jax.devices()[:S]), ("stage",))
    key = jax.random.key(0)
    stage_params = {"w": jax.random.normal(key, (S, D, D)) * 0.1}
    head_params = {"w": jax.random.normal(key, (D, 1)) * 0.1}

    def stage_fn(p, a):
        return jnp.tanh(a @ p["w"])

    def head_fn(hp, o, y):
        return jnp.mean((o @ hp["w"] - y) ** 2)

    step = make_1f1b_train_step(
        mesh, stage_fn, head_fn=head_fn, collect_input_grads=True
    )
    mbs = jax.random.normal(key, (M, MB, D))
    labels = jnp.zeros((M, MB, 1))
    prof = cost_mod.profile_fn(step, stage_params, head_params, mbs, labels)
    assert prof.name == "pp.1f1b_step"
    assert prof.flops > 0


# ---------------------------------------------------------------------- #
# Audit cost columns                                                     #
# ---------------------------------------------------------------------- #
def test_audit_cost_columns_pin_and_drift(tmp_path):
    """--audit-write pins {flops, peak_bytes, rtol}; a silent 2x FLOPs
    drift fails the audit naming the cost column, exactly like a
    collective drift; an in-tolerance wiggle passes."""
    from tools.graftlint.jaxpr_audit import audit

    exp = str(tmp_path / "expected.json")
    res = audit(names=["tp_train_step"], write=True, expected_path=exp)
    assert res["tp_train_step"]["status"] == "ok"
    pinned = json.load(open(exp))
    cost_pin = pinned["tp_train_step"]["cost"]
    assert cost_pin["flops"] > 0 and cost_pin["peak_bytes"] > 0
    assert cost_pin["rtol"] == pytest.approx(0.05)

    # Clean re-audit against the pin: ok, cost columns reported.
    res = audit(names=["tp_train_step"], expected_path=exp)
    assert res["tp_train_step"]["status"] == "ok"
    assert res["tp_train_step"]["cost"]["flops"] == cost_pin["flops"]

    # In-tolerance wiggle passes; a 2x drift fails with the column named.
    pinned["tp_train_step"]["cost"]["flops"] *= 1.01
    json.dump(pinned, open(exp, "w"))
    res = audit(names=["tp_train_step"], expected_path=exp)
    assert res["tp_train_step"]["status"] == "ok"

    pinned["tp_train_step"]["cost"]["flops"] *= 2.0
    json.dump(pinned, open(exp, "w"))
    res = audit(names=["tp_train_step"], expected_path=exp)
    assert res["tp_train_step"]["status"] == "mismatch"
    assert "cost drift" in res["tp_train_step"]["detail"]
    assert "flops" in res["tp_train_step"]["detail"]


# ---------------------------------------------------------------------- #
# obs-monitor cost line                                                  #
# ---------------------------------------------------------------------- #
def test_monitor_renders_mfu_line():
    from distributed_learning_tpu.obs.report import render_dashboard

    reg = MetricsRegistry()
    reg.gauge("cost.mfu/trainer.epoch", 0.42)
    reg.gauge("cost.bytes_per_sec/trainer.epoch", 3 * 2**30)
    frame = render_dashboard(reg, now=0.0)
    assert "mfu: trainer.epoch 42.0% (3.00 GiB/s)" in frame
