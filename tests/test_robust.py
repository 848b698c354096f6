"""Byzantine-robust mixing programs (parallel/robust.py).

Two acceptance oracles from ISSUE 13:

* **Benign-knob bitwise identity** — every robust program (dense,
  fused and per-leaf, sync and async) at neutral knobs (radius=inf,
  trim=0) is bit-identical to plain ``mix`` / ``mix_async`` on mixed
  bf16+f32 trees, carry threading included.  The robust path must cost
  nothing in trust when the defense is turned off.
* **Breakdown** — with f < n/2 agents re-injecting a poisoned value
  every round, clipped and trimmed mixing keep the honest agents near
  their honest-only fixed point while plain mixing is dragged away;
  the redirected-mass statistic (the detection signal) is positive
  exactly when an attack is underway.

The wire half of the breakdown story (lying async FIELDS -> quarantine
counters + flight dump) lives in ``tests/test_faults.py``; this file is
the device side (poisoned VALUES -> robust estimators).
"""

import math

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from distributed_learning_tpu.parallel import (
    RobustConfig,
    Topology,
    as_robust_config,
)
from distributed_learning_tpu.parallel.consensus import ConsensusEngine

NEUTRAL_SPECS = [
    "clip",                                       # radius defaults to inf
    {"kind": "clip", "radius": math.inf, "adaptive": True},
    {"kind": "trim", "trim": 0},
]


def _mixed_dtype_state(n, seed=3):
    rng = np.random.default_rng(seed)
    return {
        "w": jnp.asarray(rng.normal(size=(n, 3, 2)).astype(np.float32)),
        "b": jnp.zeros((n, 5), jnp.float32),
        "h": jnp.asarray(
            rng.normal(size=(n, 4)).astype(np.float32)
        ).astype(jnp.bfloat16),
    }


def _assert_bit_identical(ref, got, tag):
    for k in ref:
        assert ref[k].dtype == got[k].dtype, (tag, k)
        assert np.array_equal(
            np.asarray(ref[k]), np.asarray(got[k])
        ), (tag, k)


# --------------------------------------------------------------------- #
# Config plumbing                                                       #
# --------------------------------------------------------------------- #
def test_as_robust_config_accepts_and_rejects():
    assert as_robust_config("clip") == RobustConfig(kind="clip")
    assert as_robust_config("median").kind == "median"
    cfg = as_robust_config(
        {"kind": "clip", "radius": 2.0, "adaptive": True}
    )
    assert cfg.radius == 2.0 and cfg.adaptive
    assert as_robust_config(cfg) is cfg
    assert as_robust_config("clip").neutral
    assert as_robust_config({"kind": "trim", "trim": 0}).neutral
    assert not as_robust_config({"kind": "trim", "trim": 1}).neutral
    assert not as_robust_config("median").neutral
    with pytest.raises(ValueError, match="kind"):
        as_robust_config("nope")
    with pytest.raises(ValueError, match="unknown"):
        as_robust_config({"kind": "clip", "bogus": 1})
    with pytest.raises(ValueError, match="trim"):
        as_robust_config({"kind": "trim", "trim": -1})
    with pytest.raises(TypeError):
        as_robust_config(3.5)


# --------------------------------------------------------------------- #
# Benign-knob oracle: bitwise identity at neutral knobs                 #
# --------------------------------------------------------------------- #
@pytest.mark.parametrize("fused", [True, False])
@pytest.mark.parametrize("spec", NEUTRAL_SPECS)
def test_neutral_robust_mix_bit_identical_to_mix(fused, spec):
    n = 4
    eng = ConsensusEngine(
        Topology.ring(n).metropolis_weights(), fused=fused
    )
    x = _mixed_dtype_state(n)
    ref = eng.mix(x, times=3)
    got, mass = eng.mix_robust(x, spec, times=3)
    _assert_bit_identical(ref, got, spec)
    assert float(mass) == 0.0  # nothing redirected at neutral knobs


@pytest.mark.parametrize("fused", [True, False])
@pytest.mark.parametrize("spec", NEUTRAL_SPECS)
def test_neutral_robust_async_bit_identical_to_mix_async(fused, spec):
    """Async counterpart incl. carry threading: tau>0 and uneven publish
    periods exercise the stale-weighted path, robust wrapper at neutral
    knobs must reproduce it bit for bit."""
    n = 4
    eng = ConsensusEngine(
        Topology.ring(n).metropolis_weights(), fused=fused
    )
    x = _mixed_dtype_state(n)
    periods = (1, 2, 1, 3)
    ref, st_ref = eng.mix_async(x, tau=2, periods=periods, times=3)
    got, st_got, mass = eng.mix_async_robust(
        x, spec=spec, tau=2, periods=periods, times=3
    )
    _assert_bit_identical(ref, got, spec)
    assert float(mass) == 0.0
    # Carries agree and thread identically through a second call.
    assert int(st_ref.rnd) == int(st_got.rnd)
    np.testing.assert_array_equal(
        np.asarray(st_ref.age), np.asarray(st_got.age)
    )
    ref2, _ = eng.mix_async(ref, st_ref, tau=2, periods=periods, times=2)
    got2, _, mass2 = eng.mix_async_robust(
        got, st_got, spec=spec, tau=2, periods=periods, times=2
    )
    _assert_bit_identical(ref2, got2, spec)
    assert float(mass2) == 0.0


def test_robust_program_embeds_under_outer_jit():
    """`robust_mix_program` returns a traceable body: composing it
    inside an outer jitted function must not re-enter the engine's
    python dispatch (same result, no tracer leaks)."""
    n = 4
    eng = ConsensusEngine(Topology.ring(n).metropolis_weights())
    x = _mixed_dtype_state(n)
    prog = eng.robust_mix_program(
        {"kind": "clip", "radius": 2.0}, times=2
    )

    @jax.jit
    def step(x):
        mixed, mass = prog(x)
        return mixed, mass

    got, mass = step(x)
    ref, ref_mass = eng.mix_robust(
        x, {"kind": "clip", "radius": 2.0}, times=2
    )
    _assert_bit_identical(ref, got, "jit-embed")
    assert float(mass) == float(ref_mass)


# --------------------------------------------------------------------- #
# Breakdown: poisoned values, honest agents survive                     #
# --------------------------------------------------------------------- #
N = 8
LIARS = (2, 5)  # f = 2 < n/2 byzantine agents
POISON = 1e3


def _poisoned_round(eng, x, mix_fn):
    """One attack round: the liars re-inject the poison (a persistent
    byzantine agent, not a one-shot glitch), everyone mixes."""
    arr = np.array(x["w"])  # copy: jax buffers are read-only
    arr[list(LIARS)] = POISON
    return mix_fn({"w": jnp.asarray(arr)})


def _honest_spread(x, ref):
    honest = np.array([i for i in range(N) if i not in LIARS])
    vals = np.asarray(x["w"], np.float64)[honest]
    return float(np.abs(vals - ref).max())


@pytest.mark.parametrize(
    "spec",
    [
        {"kind": "clip", "radius": 2.0},
        {"kind": "trim", "trim": 2},
        "median",
    ],
)
def test_robust_mixing_survives_persistent_liars(spec):
    """On a complete graph with 2/8 persistent liars: plain mixing is
    dragged to the poison scale, every robust estimator keeps the
    honest agents near their honest-only average, and the redirected
    mass flags the attack."""
    eng = ConsensusEngine(Topology.complete(N).metropolis_weights())
    rng = np.random.default_rng(0)
    x0 = {"w": jnp.asarray(rng.normal(size=(N, 6)).astype(np.float32))}
    honest = np.array([i for i in range(N) if i not in LIARS])
    honest_mean = np.asarray(x0["w"], np.float64)[honest].mean(axis=0)

    x_plain, x_rob = x0, x0
    total_mass = 0.0
    for _ in range(6):
        x_plain = _poisoned_round(
            eng, x_plain, lambda v: eng.mix(v, times=1)
        )

        def robust(v):
            out, mass = eng.mix_robust(v, spec, times=1)
            return out

        x_rob2 = _poisoned_round(eng, x_rob, robust)
        _, mass = eng.mix_robust(
            {"w": jnp.asarray(np.array(x_rob["w"]))}, spec, times=1
        )
        x_rob = x_rob2
        total_mass += float(mass)

    plain_err = _honest_spread(x_plain, honest_mean)
    robust_err = _honest_spread(x_rob, honest_mean)
    # Plain mixing absorbed the poison at its scale; robust stayed at
    # the data scale, orders of magnitude closer to the honest mean.
    assert plain_err > 50.0, plain_err
    assert robust_err < 5.0, robust_err
    assert plain_err / max(robust_err, 1e-9) > 20.0


@pytest.mark.parametrize(
    "spec", [{"kind": "clip", "radius": 2.0}, {"kind": "trim", "trim": 2}],
    ids=["clip", "trim"],
)
def test_async_robust_survives_liar_and_flags_mass(spec):
    """Async breakdown: the same persistent-liar attack through the
    stale-weighted async program — the robust estimator keeps honest
    agents bounded (at most 1/50 of the undefended error), plain
    mix_async diverges, and the mass statistic is positive under
    attack."""
    eng = ConsensusEngine(Topology.complete(N).metropolis_weights())
    rng = np.random.default_rng(1)
    x0 = {"w": jnp.asarray(rng.normal(size=(N, 6)).astype(np.float32))}
    honest = np.array([i for i in range(N) if i not in LIARS])
    honest_mean = np.asarray(x0["w"], np.float64)[honest].mean(axis=0)

    x_plain, st_plain = x0, None
    x_rob, st_rob = x0, None
    masses = []
    for _ in range(6):
        arr = np.array(x_plain["w"]); arr[list(LIARS)] = POISON
        x_plain, st_plain = eng.mix_async(
            {"w": jnp.asarray(arr)}, st_plain, tau=1, periods=1, times=1
        )
        arr = np.array(x_rob["w"]); arr[list(LIARS)] = POISON
        x_rob, st_rob, mass = eng.mix_async_robust(
            {"w": jnp.asarray(arr)}, st_rob, spec=spec,
            tau=1, periods=1, times=1,
        )
        masses.append(float(mass))

    plain_err = _honest_spread(x_plain, honest_mean)
    robust_err = _honest_spread(x_rob, honest_mean)
    assert plain_err > 50.0
    assert robust_err < 5.0
    assert robust_err <= plain_err / 50
    assert all(m > 0.0 for m in masses)  # attack visible every round


def test_median_on_ring_trim_depth_is_zero():
    """Documented estimator geometry: on a degree-2 ring the
    coordinate median over {self, 2 neighbors} has trim depth
    (deg-1)//2 = 0 for the off-diagonal correction — i.e. it reduces
    to the mean, redirected mass exactly 0.  Guards the trim_counts
    contract rather than a defense claim (rings cannot tolerate
    f >= 1 anyway: a liar CUTS every ring)."""
    eng = ConsensusEngine(Topology.ring(4).metropolis_weights())
    x = _mixed_dtype_state(4)
    ref = eng.mix(x, times=2)
    got, mass = eng.mix_robust(x, "median", times=2)
    _assert_bit_identical(ref, got, "ring-median")
    assert float(mass) == 0.0


def test_adaptive_radius_needs_honest_majority_support():
    """Adaptive clipping anchors the radius to the median neighbor
    delta; with a dense graph and a 0.5 multiplier the liar's edges are
    clipped (mass > 0) while honest edges survive at neutral scale."""
    eng = ConsensusEngine(Topology.complete(N).metropolis_weights())
    rng = np.random.default_rng(2)
    arr = rng.normal(size=(N, 6)).astype(np.float32)
    arr[list(LIARS)] = POISON
    x = {"w": jnp.asarray(arr)}
    _, mass = eng.mix_robust(
        x, {"kind": "clip", "adaptive": True, "radius": 0.5}, times=1
    )
    assert float(mass) > 0.0


@pytest.mark.skipif(
    not hasattr(jax, "shard_map"),
    reason="sharded robust programs need the jax.shard_map API "
    "(jax >= 0.7)",
)
@pytest.mark.parametrize("storage, times", [("f32", 2), ("mixed", 1)])
def test_sharded_robust_matches_dense(storage, times):
    """The sharded round keeps a leaf's running sum in the leaf's own
    dtype (``ConsensusEngine._local_mix_once``: the self term and each
    partner term are cast to storage before they are added), the dense
    round sums in f32 and casts once.  On f32 leaves the two agree to an
    ulp, round after round.  A bf16 leaf takes five roundings a ring
    round where the dense one takes one, each at most half a bf16 step
    of its operand: it is held to one bf16 step of the leaf's largest
    entry, and for one round, because the clip norms are taken over the
    whole row and carry that step into every leaf's clip scale from the
    second round on."""
    from distributed_learning_tpu.parallel.consensus import (
        make_agent_mesh,
    )

    mesh = make_agent_mesh(8)
    W = Topology.ring(8).metropolis_weights()
    dense, sharded = ConsensusEngine(W), ConsensusEngine(W, mesh=mesh)
    x = _mixed_dtype_state(8)
    if storage == "f32":
        x = jax.tree.map(lambda v: v.astype(jnp.float32), x)
    spec = {"kind": "clip", "radius": 2.0}
    ref, ref_mass = dense.mix_robust(x, spec, times=times)
    got, got_mass = sharded.mix_robust(sharded.shard(x), spec, times=times)
    for k in ref:
        assert ref[k].dtype == got[k].dtype == x[k].dtype
        a = np.asarray(ref[k], np.float64)
        b = np.asarray(got[k], np.float64)
        if x[k].dtype == jnp.bfloat16:
            top = float(np.abs(np.asarray(x[k], np.float64)).max())
            assert np.abs(a - b).max() <= 2.0 ** (np.floor(np.log2(top)) - 7)
            continue
        np.testing.assert_allclose(a, b, rtol=2e-6, atol=2e-6)
    assert float(ref_mass) > 0.0
    np.testing.assert_allclose(
        float(ref_mass), float(got_mass), rtol=1e-5
    )
