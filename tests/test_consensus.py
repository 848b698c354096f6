"""Consensus-engine tests on the 8-virtual-device CPU harness.

Mirrors the reference's tier-2 integration pattern (the asyncio fake network,
``Titanic Consensus GD test.ipynb`` cell 10: "average five numbers") plus the
mathematical invariants from ``wiki/consensus_basics.ipynb``: mean
preservation, contraction at rate gamma, weighted-mean fixed point.
"""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from distributed_learning_tpu.ops import mixing as ops
from distributed_learning_tpu.parallel import Topology, solve_fastest_mixing
from distributed_learning_tpu.parallel.consensus import (
    ConsensusEngine,
    Mixer,
    make_agent_mesh,
)
from distributed_learning_tpu.parallel.topology import gamma as exact_gamma


def _tree_state(n, seed=0):
    """A small model-shaped pytree stacked over n agents."""
    rng = np.random.default_rng(seed)
    return {
        "w": jnp.asarray(rng.normal(size=(n, 4, 3)), dtype=jnp.float32),
        "b": jnp.asarray(rng.normal(size=(n, 3)), dtype=jnp.float32),
    }


def _tree_mean(x):
    return jax.tree.map(lambda v: v.mean(axis=0), x)


def _make_engine(topo, sharded, W=None):
    if W is None:
        W = topo.metropolis_weights()
    mesh = make_agent_mesh(topo.n_agents) if sharded else None
    return ConsensusEngine(W, mesh=mesh)


@pytest.mark.parametrize("sharded", [False, True])
def test_average_five_numbers(sharded):
    # The reference's smoke test: 5 agents reach the average of 5 numbers.
    topo = Topology.from_edges([(0, 1), (1, 2), (2, 3), (3, 4), (4, 0)])
    eng = _make_engine(topo, sharded)
    x = {"v": jnp.asarray([[1.0], [2.0], [3.0], [4.0], [5.0]])}
    x = eng.shard(x)
    out, t, res = eng.mix_until(x, eps=1e-6, max_rounds=500)
    np.testing.assert_allclose(np.asarray(out["v"]), 3.0, atol=1e-5)
    assert int(t) < 500
    assert float(res) < 1e-6


@pytest.mark.parametrize("sharded", [False, True])
def test_mean_preservation(sharded):
    topo = Topology.grid2d(2, 4)
    eng = _make_engine(topo, sharded)
    x = eng.shard(_tree_state(8))
    before = _tree_mean(x)
    out = eng.mix(x, times=7)
    after = _tree_mean(out)
    for b, a in zip(jax.tree.leaves(before), jax.tree.leaves(after)):
        np.testing.assert_allclose(np.asarray(a), np.asarray(b), atol=1e-5)


@pytest.mark.parametrize("sharded", [False, True])
def test_contraction_at_gamma_rate(sharded):
    topo = Topology.ring(8)
    W = topo.metropolis_weights()
    g = exact_gamma(W)
    eng = _make_engine(topo, sharded, W)
    x = eng.shard(_tree_state(8, seed=3))
    r0 = float(eng.max_deviation(x))
    k = 10
    out = eng.mix(x, times=k)
    rk = float(eng.max_deviation(out))
    # Worst-case bound with sqrt(n) slack between max-norm and 2-norm.
    assert rk <= g**k * r0 * np.sqrt(8) + 1e-6


@pytest.mark.parametrize("sharded", [False, True])
def test_sharded_matches_dense(sharded):
    """ppermute matching schedule computes exactly W @ x."""
    topo = Topology.watts_strogatz(8, 4, 0.4, seed=11)
    W = topo.metropolis_weights()
    eng = _make_engine(topo, sharded, W)
    x = _tree_state(8, seed=4)
    out = eng.mix(eng.shard(x), times=3)
    # Direct numpy reference: W^3 applied leaf-wise.
    W3 = np.linalg.matrix_power(W, 3)
    for key in x:
        flat = np.asarray(x[key]).reshape(8, -1)
        expect = (W3 @ flat).reshape(x[key].shape)
        np.testing.assert_allclose(np.asarray(out[key]), expect, atol=1e-5)


@pytest.mark.parametrize("sharded", [False, True])
def test_weighted_consensus_fixed_point(sharded):
    # Weighted average: gossip converges to sum(w_i x_i)/sum(w_i)
    # (the reference's sample-count weighting, consensus_asyncio.py:288-293).
    topo = Topology.ring(8)
    eng = _make_engine(topo, sharded)
    vals = np.arange(8, dtype=np.float32).reshape(8, 1) + 1.0
    weights = np.asarray([1, 2, 3, 4, 4, 3, 2, 1], dtype=np.float32)
    expect = float((vals[:, 0] * weights).sum() / weights.sum())
    x = eng.shard({"v": jnp.asarray(vals)})
    out = eng.run_round(x, weights, convergence_eps=1e-6, max_rounds=2000)
    np.testing.assert_allclose(np.asarray(out["v"]), expect, atol=1e-4)


@pytest.mark.parametrize("sharded", [False, True])
def test_chebyshev_beats_plain_on_device(sharded):
    topo = Topology.ring(8)
    W = topo.metropolis_weights()
    eng = _make_engine(topo, sharded, W)
    x = eng.shard(_tree_state(8, seed=5))
    k = 10
    plain = eng.mix(x, times=k)
    cheb = eng.mix_chebyshev(x, times=k)
    assert float(eng.max_deviation(cheb)) < float(eng.max_deviation(plain)) / 5
    # Chebyshev preserves the mean too.
    for b, a in zip(
        jax.tree.leaves(_tree_mean(x)), jax.tree.leaves(_tree_mean(cheb))
    ):
        np.testing.assert_allclose(np.asarray(a), np.asarray(b), atol=1e-5)


@pytest.mark.parametrize("sharded", [False, True])
def test_optimal_weights_mix_faster(sharded):
    topo = Topology.grid2d(2, 4)
    W_opt, g_opt = solve_fastest_mixing(topo)
    W_met = topo.metropolis_weights()
    e_opt = _make_engine(topo, sharded, W_opt)
    e_met = _make_engine(topo, sharded, W_met)
    x = _tree_state(8, seed=6)
    k = 15
    r_opt = float(e_opt.max_deviation(e_opt.mix(e_opt.shard(x), times=k)))
    r_met = float(e_met.max_deviation(e_met.mix(e_met.shard(x), times=k)))
    assert r_opt < r_met


def test_mix_until_respects_min_times():
    topo = Topology.complete(4)
    eng = ConsensusEngine(topo.metropolis_weights())
    x = _tree_state(4)
    # Already-converged state (all equal) must still run min_times rounds.
    x_eq = jax.tree.map(lambda v: jnp.broadcast_to(v[:1], v.shape), x)
    _, t, res = eng.mix_until(x_eq, eps=1e-3, min_times=3, max_rounds=100)
    assert int(t) == 3
    assert float(res) < 1e-3


def test_mix_until_bounded_by_max_rounds():
    # Disconnected graph never converges; loop must stop at max_rounds.
    W = np.eye(4)  # identity mixing = no progress
    eng = ConsensusEngine(W)
    x = _tree_state(4, seed=7)
    _, t, res = eng.mix_until(x, eps=1e-9, max_rounds=17)
    assert int(t) == 17
    assert float(res) > 0


class _ListLogger:
    def __init__(self):
        self.lines = []

    def debug(self, msg):
        self.lines.append(str(msg))


def test_mixer_reference_api():
    # The consensus_simple.Mixer surface: dict params + dict topology.
    topology = {
        "Alice": {"Alice": 0.9, "Bob": 0.05, "Charlie": 0.05},
        "Bob": {"Alice": 0.05, "Bob": 0.9, "Charlie": 0.05},
        "Charlie": {"Alice": 0.05, "Bob": 0.05, "Charlie": 0.9},
    }
    params = {
        name: {"w": jnp.full((2, 2), float(i)), "b": jnp.full((2,), float(i))}
        for i, name in enumerate(["Alice", "Bob", "Charlie"])
    }
    log = _ListLogger()
    mixer = Mixer(params, topology, logger=log)
    devs = mixer.get_parameters_deviation()
    assert set(devs) == {"Alice", "Bob", "Charlie"}
    assert mixer.get_max_parameters_std() > 0
    done = mixer.mix(times=2)
    assert done == 2
    done = mixer.mix(times=1, eps=1e-5)
    assert done >= 1
    assert max(mixer.get_parameters_deviation().values()) < 1e-4
    # All agents converged to the initial mean (1.0 everywhere).
    final = mixer.parameters()
    np.testing.assert_allclose(np.asarray(final["Bob"]["w"]), 1.0, atol=1e-5)
    assert any("Mixer start" in l for l in log.lines)


def test_mixer_single_agent_noop():
    mixer = Mixer({"a": {"w": jnp.ones((2,))}}, {"a": {"a": 1.0}})
    assert mixer.mix(times=5) == 0


def test_dense_mix_preserves_non_f32_leaf_dtypes():
    # int32 leaves (e.g. step counters) must mix in f32 and cast back,
    # matching the sharded path — not be annihilated by W.astype(int).
    topo = Topology.ring(4)
    W = topo.metropolis_weights()
    eng_d = ConsensusEngine(W)
    x = {
        "w": jnp.asarray(np.arange(4.0)[:, None], jnp.float32),
        "step": jnp.asarray([10, 20, 30, 40], jnp.int32)[:, None],
    }
    out_d = eng_d.mix(x, times=1)
    assert out_d["step"].dtype == jnp.int32
    expect = (W @ np.array([10.0, 20, 30, 40])).astype(np.int32)
    np.testing.assert_array_equal(np.asarray(out_d["step"][:, 0]), expect)


def test_chebyshev_times_zero_is_noop():
    eng = ConsensusEngine(Topology.ring(4).metropolis_weights())
    x = {"v": jnp.arange(4.0)[:, None]}
    out = eng.mix_chebyshev(x, times=0)
    np.testing.assert_array_equal(np.asarray(out["v"]), np.asarray(x["v"]))


def test_mixer_token_count_must_match_matrix():
    W = Topology.ring(4).metropolis_weights()
    params = {t: {"w": jnp.ones(2)} for t in "abc"}
    with pytest.raises(ValueError, match="tokens"):
        Mixer(params, W, tokens=("a", "b", "c"))


def test_run_round_rejects_degenerate_weights():
    eng = ConsensusEngine(Topology.ring(4).metropolis_weights())
    x = {"v": jnp.arange(4.0)[:, None]}
    with pytest.raises(ValueError):
        eng.run_round(x, np.zeros(4))
    with pytest.raises(ValueError):
        eng.run_round(x, np.ones(3))


def test_weighted_readout_push_sum():
    # Push-sum style: gossip (w*x, w) jointly, then divide. After full
    # convergence both channels hit their means, ratio = weighted average.
    topo = Topology.ring(6)
    eng = ConsensusEngine(topo.metropolis_weights())
    rng = np.random.default_rng(1)
    vals = rng.normal(size=(6, 3)).astype(np.float32)
    w = np.asarray([1, 2, 3, 1, 2, 3], np.float32)
    num = {"v": jnp.asarray(vals * w[:, None])}
    den = jnp.asarray(w)
    num_mixed, _, _ = eng.mix_until(num, eps=1e-6, max_rounds=2000)
    den_mixed = eng.mix({"d": den[:, None]}, times=2000)["d"][:, 0]
    out = ops.weighted_readout(num_mixed, den_mixed)
    expect = (vals * w[:, None]).sum(0) / w.sum()
    np.testing.assert_allclose(np.asarray(out["v"]), np.tile(expect, (6, 1)), atol=1e-4)


@pytest.mark.parametrize("sharded", [False, True])
def test_mix_with_traced_matrix_matches_numpy(sharded):
    """Traced-W path (time-varying graphs) computes exactly W^t @ x for an
    arbitrary runtime W, in both dense and masked-all-to-all sharded modes."""
    topo = Topology.ring(8)
    eng = _make_engine(topo, sharded)
    x = _tree_state(8, seed=7)
    xs = eng.shard(x)
    # A *different* graph than the engine was built with, supplied at runtime.
    W2 = Topology.erdos_renyi(8, 0.5, seed=3).metropolis_weights()
    out = eng.mix_with(xs, W2, times=2)
    ref = np.linalg.matrix_power(W2, 2)
    for key in x:
        flat = np.asarray(x[key]).reshape(8, -1)
        expect = (ref @ flat).reshape(x[key].shape)
        np.testing.assert_allclose(np.asarray(out[key]), expect, atol=1e-5)


@pytest.mark.parametrize("sharded", [False, True])
def test_mix_with_no_recompile_across_graphs(sharded):
    """Resampling the topology must reuse the compiled program."""
    topo = Topology.ring(8)
    eng = _make_engine(topo, sharded)
    xs = eng.shard(_tree_state(8, seed=9))
    for seed in range(3):
        W = Topology.erdos_renyi(8, 0.5, seed=seed).metropolis_weights()
        xs = eng.mix_with(xs, W, times=1, route="allgather")
    fn = eng._jit_cache["mix_with"]
    # One trace serves all three graphs (W is a traced argument).  In the
    # sharded mode the cached callable is the jitted shard_map itself; in
    # dense mode it is jax.jit(lambda ...).
    if hasattr(fn, "_cache_size"):
        assert fn._cache_size() == 1
    before = _tree_mean(eng.shard(_tree_state(8, seed=9)))
    after = _tree_mean(xs)
    for b, a in zip(jax.tree.leaves(before), jax.tree.leaves(after)):
        np.testing.assert_allclose(np.asarray(a), np.asarray(b), atol=1e-5)


def _sparse_ring_plus_chords(n=8):
    """Ring + two span-2 chords: max ring span 2, so the routed path needs
    2 relay hops/round vs the all_gather fallback's n-1 messages."""
    edges = [(i, (i + 1) % n) for i in range(n)] + [(0, 2), (4, 6)]
    return Topology.from_edges(edges).metropolis_weights()


def test_ring_offset_decomposition_reconstructs_w():
    eng = ConsensusEngine(Topology.ring(8).metropolis_weights())
    for W in [
        Topology.ring(8).metropolis_weights(),
        _sparse_ring_plus_chords(),
        Topology.complete(8).metropolis_weights(),
        Topology.erdos_renyi(8, 0.4, seed=2).metropolis_weights(),
    ]:
        self_w, w_fwd, w_bwd, k = eng._ring_offset_weights(W)
        n = 8
        R = np.diag(self_w)
        i = np.arange(n)
        for kk in range(1, n // 2 + 1):
            R[i, (i - kk) % n] += w_fwd[:, kk - 1]
            R[i, (i + kk) % n] += w_bwd[:, kk - 1]
        np.testing.assert_allclose(R, W, atol=1e-7)
        # k is exactly the maximal ring span of any present edge.
        spans = [
            min((u - v) % n, (v - u) % n)
            for u in range(n)
            for v in range(n)
            if u != v and W[u, v] != 0.0
        ]
        assert k == (max(spans) if spans else 0)


def test_auto_route_scales_with_span_not_n():
    """Sparse resampled graphs take the k-hop ring path (bandwidth 2k
    messages/round); dense graphs fall back to all_gather (n-1)."""
    eng = ConsensusEngine(Topology.ring(8).metropolis_weights())
    route, (_, _, _, k) = eng._route_for(_sparse_ring_plus_chords(), "auto")
    assert route == "ring" and k == 2  # 2*2 < 7 messages
    route, (_, _, _, k) = eng._route_for(
        Topology.complete(8).metropolis_weights(), "auto"
    )
    assert route == "allgather" and k == 4  # 2*4 >= 7


@pytest.mark.parametrize("route", ["ring", "allgather"])
def test_mix_with_routed_matches_numpy(route):
    """Both sharded strategies compute exactly W^t @ x for a sparse W."""
    eng = _make_engine(Topology.ring(8), sharded=True)
    x = _tree_state(8, seed=7)
    xs = eng.shard(x)
    W2 = _sparse_ring_plus_chords()
    out = eng.mix_with(xs, W2, times=2, route=route)
    ref = np.linalg.matrix_power(W2, 2)
    for key in x:
        flat = np.asarray(x[key]).reshape(8, -1)
        expect = (ref @ flat).reshape(x[key].shape)
        np.testing.assert_allclose(np.asarray(out[key]), expect, atol=1e-5)


def test_ring_route_no_recompile_across_spans():
    """Graphs with different spans and weights reuse one compiled ring
    program (weights AND hop count are traced)."""
    eng = _make_engine(Topology.ring(8), sharded=True)
    xs = eng.shard(_tree_state(8, seed=9))
    for W in [
        Topology.ring(8).metropolis_weights(),
        _sparse_ring_plus_chords(),
        Topology.from_edges(
            [(i, (i + 1) % 8) for i in range(8)] + [(0, 3)]
        ).metropolis_weights(),
    ]:
        xs = eng.mix_with(xs, W, times=1, route="ring")
    fn = eng._jit_cache[("mix_with_ring", True, True)]
    if hasattr(fn, "_cache_size"):
        assert fn._cache_size() == 1
    before = _tree_mean(eng.shard(_tree_state(8, seed=9)))
    after = _tree_mean(xs)
    for b, a in zip(jax.tree.leaves(before), jax.tree.leaves(after)):
        np.testing.assert_allclose(np.asarray(a), np.asarray(b), atol=1e-5)


@pytest.mark.parametrize("route", ["ring", "allgather"])
def test_chebyshev_routed_matches_dense(route):
    from distributed_learning_tpu.parallel.schedule import chebyshev_omegas

    W = _sparse_ring_plus_chords()
    dense = ConsensusEngine(W)
    sharded = ConsensusEngine(W, mesh=make_agent_mesh(8))
    x = _tree_state(8, seed=13)
    omegas = chebyshev_omegas(exact_gamma(W), 5)
    expect = dense.mix_chebyshev_with(x, W, omegas)
    got = sharded.mix_chebyshev_with(sharded.shard(x), W, omegas, route=route)
    for key in x:
        np.testing.assert_allclose(
            np.asarray(got[key]), np.asarray(expect[key]), atol=1e-5
        )


@pytest.mark.parametrize("sharded", [False, True])
def test_chebyshev_traced_matches_static(sharded):
    """mix_chebyshev_with(W_engine, omegas) == mix_chebyshev for the same
    graph and round count."""
    from distributed_learning_tpu.parallel.schedule import chebyshev_omegas

    topo = Topology.ring(8)
    W = topo.metropolis_weights()
    eng = _make_engine(topo, sharded, W)
    x = _tree_state(8, seed=5)
    xs = eng.shard(x)
    k = 6
    expect = eng.mix_chebyshev(xs, times=k)
    omegas = chebyshev_omegas(eng.gamma, k)
    got = eng.mix_chebyshev_with(xs, W, omegas)
    for key in x:
        np.testing.assert_allclose(
            np.asarray(got[key]), np.asarray(expect[key]), atol=1e-5
        )


def test_time_varying_chebyshev_converges_faster_than_plain():
    """Config-5 semantics: per-round resampled graphs with per-round
    Chebyshev schedules still contract, and faster than plain mixing."""
    from distributed_learning_tpu.parallel.schedule import chebyshev_omegas

    n, rounds_per_epoch, epochs = 8, 4, 5
    eng = ConsensusEngine(Topology.ring(n).metropolis_weights())
    x0 = _tree_state(n, seed=11)
    x_plain = x_cheby = x0
    for e in range(epochs):
        W = Topology.erdos_renyi(n, 0.4, seed=100 + e).metropolis_weights()
        x_plain = eng.mix_with(x_plain, W, times=rounds_per_epoch)
        omegas = chebyshev_omegas(exact_gamma(W), rounds_per_epoch)
        x_cheby = eng.mix_chebyshev_with(x_cheby, W, omegas)
    r_plain = float(eng.max_deviation(x_plain))
    r_cheby = float(eng.max_deviation(x_cheby))
    assert r_cheby < r_plain
    # Mean is preserved through both paths.
    for b, a in zip(
        jax.tree.leaves(_tree_mean(x0)), jax.tree.leaves(_tree_mean(x_cheby))
    ):
        np.testing.assert_allclose(np.asarray(a), np.asarray(b), atol=1e-4)


@pytest.mark.parametrize("n, edge_p", [(8, 0.4), (4, 0.6)])
def test_time_varying_chebyshev_needs_no_more_rounds_to_eps(n, edge_p):
    """The same claim counted in rounds: over one sequence of resampled
    graphs, three rounds a graph, Chebyshev mixing reaches the 1e-4
    residual in no more rounds than plain mixing does."""
    from distributed_learning_tpu.parallel.schedule import chebyshev_omegas

    eng = ConsensusEngine(Topology.ring(n).metropolis_weights())
    rng = np.random.default_rng(0)
    x0 = jnp.asarray(rng.normal(size=(n, 1 << 10)).astype(np.float32))
    k, eps, graphs = 3, 1e-4, 60

    def rounds_to_eps(cheby):
        x = x0
        for e in range(graphs):
            W = Topology.erdos_renyi(
                n, edge_p, seed=1000 + e
            ).metropolis_weights()
            if cheby:
                x = eng.mix_chebyshev_with(
                    x, W, chebyshev_omegas(exact_gamma(W), k)
                )
            else:
                x = eng.mix_with(x, W, times=k)
            if float(eng.max_deviation(x)) < eps:
                return (e + 1) * k
        raise AssertionError(f"residual above {eps} after {graphs * k} rounds")

    assert rounds_to_eps(True) <= rounds_to_eps(False)


@pytest.mark.parametrize("sharded", [False, True])
def test_global_average_is_exact_consensus(sharded):
    """global_average == the gamma=0 all-reduce: every agent gets the exact
    mean, residual drops to ~0 in one call."""
    topo = Topology.ring(8)
    eng = _make_engine(topo, sharded)
    x = _tree_state(8, seed=13)
    out = eng.global_average(eng.shard(x))
    for key in x:
        mean = np.asarray(x[key]).mean(axis=0)
        np.testing.assert_allclose(
            np.asarray(out[key]), np.broadcast_to(mean, x[key].shape),
            atol=1e-6,
        )
    assert float(eng.max_deviation(out)) < 1e-5


# --------------------------------------------------------------------- #
# Fused flat-buffer layout (ops.flatten_stacked / fused=True engines)   #
# --------------------------------------------------------------------- #
def _mixed_dtype_state(n, seed=0):
    """Stacked tree spanning the fused layout's edge cases: f32 + bf16
    dtype buckets, a scalar-per-agent (n,) leaf, and an int32 leaf."""
    rng = np.random.default_rng(seed)
    return {
        "w": jnp.asarray(rng.normal(size=(n, 4, 3)), jnp.float32),
        "b": jnp.asarray(rng.normal(size=(n, 3)), jnp.float32),
        "h": jnp.asarray(rng.normal(size=(n, 5)), jnp.bfloat16),
        "scalar": jnp.asarray(rng.normal(size=(n,)), jnp.float32),
        "step": jnp.asarray(rng.integers(0, 100, (n, 2)), jnp.int32),
    }


def _assert_trees_close(a, b, tag, tol=2e-6, storage_steps=0):
    """Fused-vs-per-leaf tolerance: identical math, the only divergence
    is GEMM accumulation order (~1 ulp for f32).  ``storage_steps`` lets
    a leaf stored below f32 differ by that many steps of its own dtype
    (the f32 ulp can fall on a rounding boundary of the storage cast):
    for a caller that holds the same values to ``tol`` in f32 itself."""
    for ka, kb in zip(sorted(a), sorted(b)):
        assert ka == kb
        av = np.asarray(a[ka], np.float64)
        bv = np.asarray(b[kb], np.float64)
        assert a[ka].dtype == b[kb].dtype
        if storage_steps and a[ka].dtype != jnp.float32:
            # one bf16 step at v is 2**(floor(log2 |v|) - 7), one int step 1
            mag = np.maximum(np.maximum(np.abs(av), np.abs(bv)), 1e-30)
            step = (
                2.0 ** (np.floor(np.log2(mag)) - 7)
                if a[ka].dtype == jnp.bfloat16 else 1.0
            )
            assert np.all(np.abs(av - bv) <= storage_steps * step), (
                f"{tag}:{ka}"
            )
            continue
        np.testing.assert_allclose(av, bv, rtol=tol, atol=tol,
                                   err_msg=f"{tag}:{ka}")


def _fused_pair(W):
    return ConsensusEngine(W), ConsensusEngine(W, fused=False)


def test_flatten_unflatten_roundtrip_with_dtype_buckets():
    x = _mixed_dtype_state(8)
    bufs, layout = ops.flatten_stacked(x)
    # One contiguous (N, P) buffer per storage dtype.
    assert set(bufs) == {"float32", "bfloat16", "int32"}
    assert bufs["float32"].shape == (8, 4 * 3 + 3 + 1)
    assert layout.leaf_count == 5 and layout.bucket_count == 3
    assert layout.bytes_per_round(8) == 8 * (16 * 4 + 5 * 2 + 2 * 4)
    y = ops.unflatten_stacked(bufs, layout)
    for k in x:
        assert y[k].dtype == x[k].dtype and y[k].shape == x[k].shape
        np.testing.assert_array_equal(np.asarray(y[k]), np.asarray(x[k]))


def test_fused_layout_rejects_leaf_without_agent_axis():
    with pytest.raises(ValueError, match="leading agent axis"):
        ops.fused_layout({"a": jnp.ones((8, 2)), "bad": jnp.float32(1.0)})
    with pytest.raises(ValueError, match="inconsistent"):
        ops.fused_layout({"a": jnp.ones((8, 2)), "b": jnp.ones((4, 2))})


def test_unstack_tree_rejects_scalar_leaf():
    # The old hasattr-__getitem__ guard silently SHARED a scalar leaf
    # across agents; now it errors, consistent with the stack_trees
    # invariant (stack_trees turns per-agent scalars into an (n,) leaf,
    # which unstacks fine).
    with pytest.raises(ValueError, match="leading agent axis"):
        ops.unstack_tree({"w": jnp.ones((4, 2)), "s": 3.0}, 4)
    with pytest.raises(ValueError, match="leading agent axis"):
        ops.unstack_tree({"w": jnp.ones((3, 2))}, 4)
    stacked = ops.stack_trees([{"v": float(i)} for i in range(4)])
    out = ops.unstack_tree(stacked, 4)
    assert [float(t["v"]) for t in out] == [0.0, 1.0, 2.0, 3.0]


def test_fused_oracle_mix_and_until():
    W = Topology.ring(8).metropolis_weights()
    ef, ep = _fused_pair(W)
    x = _mixed_dtype_state(8, seed=1)
    _assert_trees_close(ef.mix(x, times=3), ep.mix(x, times=3), "mix")
    of, tf, rf = ef.mix_until(x, eps=1e-3, max_rounds=200)
    op_, tp_, rp_ = ep.mix_until(x, eps=1e-3, max_rounds=200)
    _assert_trees_close(of, op_, "mix_until")
    assert int(tf) == int(tp_)
    np.testing.assert_allclose(float(rf), float(rp_), rtol=1e-5)


def test_fused_oracle_traced_w_routes():
    W = Topology.ring(8).metropolis_weights()
    ef, ep = _fused_pair(W)
    x = _mixed_dtype_state(8, seed=2)
    W2 = Topology.erdos_renyi(8, 0.5, seed=3).metropolis_weights()
    _assert_trees_close(
        ef.mix_with(x, W2, times=2), ep.mix_with(x, W2, times=2), "mix_with"
    )
    of, tf, _ = ef.mix_until_with(x, W2, eps=1e-3)
    op_, tp_, _ = ep.mix_until_with(x, W2, eps=1e-3)
    # The leafwise round and the per-leaf matmul share no arithmetic, and
    # over this many rounds their f32 ulp lands on a bf16 rounding
    # boundary of "h": that leaf is held to one bf16 step here, and the
    # same values to 2e-6 in f32, before any storage cast, below.
    _assert_trees_close(of, op_, "mix_until_with", storage_steps=1)
    assert int(tf) == int(tp_)
    x32 = jax.tree.map(lambda v: v.astype(jnp.float32), x)
    _assert_trees_close(
        ef.mix_until_with(x32, W2, eps=1e-3)[0],
        ep.mix_until_with(x32, W2, eps=1e-3)[0],
        "mix_until_with in f32",
    )


def test_fused_oracle_chebyshev_and_pairwise():
    from distributed_learning_tpu.parallel.schedule import chebyshev_omegas

    W = Topology.ring(8).metropolis_weights()
    ef, ep = _fused_pair(W)
    x = _mixed_dtype_state(8, seed=3)
    _assert_trees_close(
        ef.mix_chebyshev(x, times=5), ep.mix_chebyshev(x, times=5), "cheby"
    )
    W2 = _sparse_ring_plus_chords()
    omegas = chebyshev_omegas(exact_gamma(W2), 4)
    _assert_trees_close(
        ef.mix_chebyshev_with(x, W2, omegas),
        ep.mix_chebyshev_with(x, W2, omegas),
        "cheby_with",
    )
    key = jax.random.key(0)
    # Same key -> same edge draws -> identical pairwise averaging.
    _assert_trees_close(
        ef.mix_pairwise(x, key, 7), ep.mix_pairwise(x, key, 7), "pairwise"
    )


def test_fused_oracle_reductions_and_global_average():
    W = Topology.grid2d(2, 4).metropolis_weights()
    ef, ep = _fused_pair(W)
    x = _mixed_dtype_state(8, seed=4)
    np.testing.assert_allclose(
        np.asarray(ef.deviations(x)), np.asarray(ep.deviations(x)), rtol=1e-6
    )
    np.testing.assert_allclose(
        float(ef.max_std(x)), float(ep.max_std(x)), rtol=1e-6
    )
    _assert_trees_close(
        ef.global_average(x), ep.global_average(x), "global_average"
    )
    w = np.asarray([1, 2, 3, 4, 4, 3, 2, 1], np.float32)
    _assert_trees_close(
        ef.run_round(x, w, convergence_eps=1e-3),
        ep.run_round(x, w, convergence_eps=1e-3),
        "run_round",
        tol=5e-6,
    )


def _numpy_round(W, x):
    """One gossip round in float64, leaf by leaf, BEFORE the cast back
    to each leaf's storage dtype."""
    n = W.shape[0]
    return {
        k: (W @ np.asarray(v, np.float64).reshape(n, -1)).reshape(v.shape)
        for k, v in x.items()
    }


def _assert_is_cast_of(got, exact, tag, tol=2e-6):
    """``got`` (storage dtype) is what a value within ``tol`` of the
    float64 ``exact`` casts to: the comparison is made in f32, before
    the storage cast, so a bf16 or int32 leaf gets no tolerance of its
    own (casts are monotone: between the casts of both ends)."""
    for k, v in got.items():
        slack = tol * (1.0 + np.abs(exact[k]))
        lo = (exact[k] - slack).astype(np.float32).astype(v.dtype)
        hi = (exact[k] + slack).astype(np.float32).astype(v.dtype)
        v = np.asarray(v)
        assert np.all((lo <= v) & (v <= hi)), f"{tag}:{k}"


def _follow_rounds(eng, x, W64, rounds):
    """``rounds`` engine rounds, one call each, every one held to the
    float64 round of the state it started from."""
    for r in range(rounds):
        nxt = eng.mix(x, times=1)
        _assert_is_cast_of(
            nxt, _numpy_round(W64, {k: np.asarray(v) for k, v in x.items()}),
            f"round {r}",
        )
        x = nxt
    return x


def _numpy_deviations(x):
    """Per-agent L2 distance from the agents' mean over the whole tree,
    in float64.  A leaf stored below f32 takes its mean and difference
    in its own dtype, as the engine's statistic does."""
    total = 0.0
    for v in x.values():
        v = np.asarray(v)
        if v.dtype == jnp.bfloat16:
            mean = v.astype(np.float32).mean(axis=0).astype(v.dtype)
            d = (v - mean).astype(np.float64)
        else:
            v = v.astype(np.float64)
            d = v - v.mean(axis=0)
        total = total + (d * d).reshape(d.shape[0], -1).sum(axis=1)
    return np.sqrt(total)


@pytest.mark.parametrize(
    "call", ["mix", "mix_until", "deviations", "max_deviation", "many_agents"]
)
def test_dense_leafwise_matches_numpy_reference(call):
    """The dense engine's programs against plain numpy in float64, leaf
    by leaf (``W @ X.reshape(N, -1)``): the mixed-dtype tree plus a leaf
    whose trailing shape (257, 7) is a multiple of no tile size.  A
    round is compared before its storage cast, at the f32 tolerance of
    the ``test_fused_oracle_*`` tests on every leaf; several rounds
    follow the engine's own trajectory, one such comparison a round.
    ``many_agents`` is the round above the agent count to which it is
    written out as sums (``ops.mixing._weighted_rows``)."""
    n = 8 if call != "many_agents" else ops._ROWS_BY_SUM_MAX_AGENTS + 4
    W = Topology.ring(n).metropolis_weights()
    eng = ConsensusEngine(W)
    x = _mixed_dtype_state(n, seed=5)
    x["odd"] = jnp.asarray(
        np.random.default_rng(6).normal(size=(n, 257, 7)), jnp.float32
    )
    W64 = np.asarray(W, np.float64)
    if call in ("mix", "many_agents"):
        out = _follow_rounds(eng, x, W64, 3)
        # rounds compose: one call of three is three calls of one
        for k, v in eng.mix(x, times=3).items():
            np.testing.assert_array_equal(np.asarray(v), np.asarray(out[k]))
    elif call == "mix_until":
        eps, rounds, ref = 0.5, 0, x
        while _numpy_deviations(ref).max() >= eps:
            ref, rounds = eng.mix(ref, times=1), rounds + 1
        out, t, res = eng.mix_until(x, eps=eps, max_rounds=200)
        assert 0 < rounds == int(t)
        ref = _follow_rounds(eng, x, W64, rounds)
        for k, v in out.items():
            np.testing.assert_array_equal(np.asarray(v), np.asarray(ref[k]))
        np.testing.assert_allclose(
            float(res), _numpy_deviations(ref).max(), rtol=1e-5
        )
    elif call == "deviations":
        np.testing.assert_allclose(
            np.asarray(eng.deviations(x)), _numpy_deviations(x), rtol=1e-5
        )
    else:
        np.testing.assert_allclose(
            float(eng.max_deviation(x)), _numpy_deviations(x).max(),
            rtol=1e-5,
        )


def test_leaf_mix_holds_one_form_chosen_by_the_agent_count():
    """Up to ``_ROWS_BY_SUM_MAX_AGENTS`` agents the round is written out
    as sums (no contraction in the program), above it one ``dot_general``
    a leaf on the leaf's own shape (no N*N terms): the agent count is
    static, so a traced program carries one of the two, and no branch."""
    cap = ops._ROWS_BY_SUM_MAX_AGENTS

    def prims(n):
        x = {"w": jnp.ones((n, 5, 3)), "b": jnp.ones((n, 3))}
        W = jnp.asarray(Topology.ring(n).metropolis_weights(), jnp.float32)
        jaxpr = jax.make_jaxpr(lambda s: ops.leaf_mix(s, W))(x).jaxpr
        return [e.primitive.name for e in jaxpr.eqns], jaxpr

    at_cap, _ = prims(cap)
    assert "dot_general" not in at_cap and "cond" not in at_cap
    assert at_cap.count("mul") == 2 * cap * cap
    above, jaxpr = prims(cap + 1)
    assert above.count("dot_general") == 2 and "cond" not in above
    assert above.count("mul") == 0
    shapes = [e.invars[1].aval.shape for e in jaxpr.eqns
              if e.primitive.name == "dot_general"]
    assert sorted(shapes) == [(cap + 1, 3), (cap + 1, 5, 3)]


def test_fused_mix_records_layout_counters():
    from distributed_learning_tpu.obs import MetricsRegistry, use_registry

    W = Topology.ring(4).metropolis_weights()
    x = {
        "w": jnp.ones((4, 6), jnp.float32),
        "b": jnp.ones((4, 3), jnp.float32),
        "h": jnp.ones((4, 2), jnp.bfloat16),
    }
    assert ops.fused_layout(x).bucket_count == 2
    reg = MetricsRegistry()
    with use_registry(reg):
        ConsensusEngine(W).mix(x, times=3)
    # What the launched program works on: the dense engine mixes the 3
    # leaves where they lie; only a sharded program packs them into the
    # layout's 2 dtype buckets.
    assert reg.gauges["consensus.fused_buckets"] == 3
    assert reg.gauges["consensus.leaf_count"] == 3
    # bytes/round = 4 * (6*4 + 3*4 + 2*2) = 160; 3 rounds.
    assert reg.counters["consensus.bytes_mixed"] == 3 * 4 * (
        6 * 4 + 3 * 4 + 2 * 2
    )
    if hasattr(jax, "shard_map"):
        reg = MetricsRegistry()
        with use_registry(reg):
            ConsensusEngine(W, mesh=make_agent_mesh(4)).mix(x, times=1)
        assert reg.gauges["consensus.fused_buckets"] == 2
        assert reg.gauges["consensus.leaf_count"] == 3


@pytest.mark.skipif(
    not hasattr(jax, "shard_map"),
    reason="sharded fused engine needs the jax.shard_map API (jax >= 0.7)",
)
def test_fused_oracle_sharded_mix_until():
    W = Topology.ring(8).metropolis_weights()
    mesh = make_agent_mesh(8)
    ef = ConsensusEngine(W, mesh=mesh)
    ep = ConsensusEngine(W, mesh=mesh, fused=False)
    x = _mixed_dtype_state(8, seed=5)
    of, tf, _ = ef.mix_until(ef.shard(x), eps=1e-3, max_rounds=200)
    op_, tp_, _ = ep.mix_until(ep.shard(x), eps=1e-3, max_rounds=200)
    _assert_trees_close(of, op_, "sharded_mix_until")
    assert int(tf) == int(tp_)
