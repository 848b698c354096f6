"""CHOCO-GOSSIP: compressed consensus with error feedback.

Key properties, straight from the Koloskova-Stich-Jaggi analysis:
contractive compressors, linear convergence to EXACT consensus despite
compression (naive compressed gossip stalls at a floor), and mean
preservation under symmetric W.
"""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from distributed_learning_tpu.ops import mixing as mixing_ops
from distributed_learning_tpu.parallel import Topology
from distributed_learning_tpu.parallel.compression import (
    ChocoGossipEngine,
    FusedCompressor,
    approx_top_k,
    compressor_delta,
    compressor_from_spec,
    identity,
    int8_quant,
    random_k,
    scaled_sign,
    top_k,
)
from distributed_learning_tpu.parallel.consensus import make_agent_mesh

N, DIM = 8, 64


def _x0(seed=0):
    return jnp.asarray(
        np.random.default_rng(seed).normal(size=(N, DIM)).astype(np.float32)
    )


@pytest.mark.parametrize(
    "comp", [top_k(0.1), approx_top_k(0.1), random_k(0.25), scaled_sign(),
             identity()]
)
def test_compressors_are_contractive(comp):
    delta = compressor_delta(comp, dim=128, trials=30)
    assert 0.0 < delta <= 1.0 + 1e-6


def test_top_k_keeps_largest_entries():
    v = jnp.asarray([0.1, -5.0, 0.2, 3.0, -0.05, 0.0, 1.0, -0.3])
    out = top_k(0.25)(v, jax.random.key(0))
    np.testing.assert_allclose(
        np.asarray(out), [0, -5.0, 0, 3.0, 0, 0, 0, 0], atol=1e-7
    )


def test_choco_reaches_exact_consensus_where_naive_stalls():
    W = Topology.ring(N).metropolis_weights()
    x0 = _x0()
    mean = np.asarray(x0).mean(axis=0)

    eng = ChocoGossipEngine(W, top_k(0.1), gamma=0.3)
    state, res = eng.run(eng.init(x0), 400)
    # Exact consensus at the exact initial mean (error feedback works).
    np.testing.assert_allclose(
        np.asarray(state.x), np.tile(mean, (N, 1)), atol=1e-3
    )
    assert float(res[-1]) < 1e-3

    # Naive compressed gossip: gossip the compressed VALUES directly.
    comp = top_k(0.1)
    Wj = jnp.asarray(W, jnp.float32)

    def naive_body(x, _):
        cx = jax.vmap(comp, in_axes=(0, None))(x, jax.random.key(0))
        return x + 0.3 * (Wj @ cx - cx), None

    x_naive, _ = jax.lax.scan(naive_body, x0, None, length=400)
    naive_dev = float(jnp.abs(x_naive - jnp.asarray(mean)[None]).max())
    choco_dev = float(jnp.abs(jnp.asarray(state.x) - jnp.asarray(mean)[None]).max())
    assert choco_dev < naive_dev / 10, (choco_dev, naive_dev)


def test_choco_preserves_mean_every_round():
    W = Topology.erdos_renyi(N, 0.5, seed=1).metropolis_weights()
    x0 = _x0(3)
    mean0 = np.asarray(x0).mean(axis=0)
    eng = ChocoGossipEngine(W, scaled_sign(), gamma=0.2)
    state = eng.init(x0)
    for _ in range(4):
        state, _ = eng.run(state, 10)
        np.testing.assert_allclose(
            np.asarray(state.x).mean(axis=0), mean0, rtol=1e-4, atol=1e-5
        )


@pytest.mark.parametrize("fraction", [0.05, 0.5])
def test_dense_and_sharded_agree_on_path_graph(fraction):
    # Path graph: non-uniform weights (shard_map in_specs regression guard).
    W = Topology.from_edges(
        [(i, i + 1) for i in range(N - 1)]
    ).metropolis_weights()
    x0 = _x0(5)
    dense = ChocoGossipEngine(W, top_k(fraction), gamma=0.25)
    sd, rd = dense.run(dense.init(x0, seed=7), 60)
    shard = ChocoGossipEngine(
        W, top_k(fraction), gamma=0.25, mesh=make_agent_mesh(N)
    )
    ss, rs = shard.run(shard.init(x0, seed=7), 60)
    # Same compressor, same W; top-k is deterministic, so the trajectories
    # agree to float32 round-off.
    np.testing.assert_allclose(
        np.asarray(sd.x), np.asarray(ss.x), rtol=2e-4, atol=2e-5
    )


def test_identity_compressor_matches_plain_gossip_on_estimates():
    W = Topology.complete(N).metropolis_weights()
    x0 = _x0(9)
    eng = ChocoGossipEngine(W, identity(), gamma=1.0)
    state, res = eng.run(eng.init(x0), 80)
    # gamma=1, delta=1: xhat == x after the first round; K_n Metropolis
    # mixes to the mean fast.
    assert float(res[-1]) < 1e-5


def test_approx_top_k_matches_exact_at_high_recall():
    """The TPU-native bucketed selection keeps (at least) nearly the same
    mass as exact top-k; on CPU the op is exact, so outputs coincide."""
    v = jnp.asarray(
        np.random.default_rng(3).normal(size=(512,)).astype(np.float32)
    )
    exact = top_k(0.1)(v, jax.random.key(0))
    approx = approx_top_k(0.1, recall_target=0.95)(v, jax.random.key(0))
    kept_exact = float(jnp.sum(exact != 0))
    kept_approx = float(jnp.sum(approx != 0))
    assert kept_approx >= 0.9 * kept_exact
    # Kept entries are a subset of v's entries (no value distortion).
    mask = approx != 0
    np.testing.assert_allclose(
        np.asarray(approx[mask]), np.asarray(v[mask]), atol=0
    )


def test_choco_converges_with_approx_top_k():
    W = Topology.ring(N).metropolis_weights()
    eng = ChocoGossipEngine(W, approx_top_k(0.2), gamma=0.25)
    st = eng.init(_x0())
    st, res = eng.run(st, 400)
    assert float(res[-1]) < 1e-3


@pytest.mark.parametrize(
    "spec, gamma", [("topk:0.1", 0.2), ("atopk:0.1", 0.2), ("randk:0.1", 0.05)]
)
def test_choco_ten_percent_budget_reaches_the_target_residual(spec, gamma):
    """The CHOCO trade at a 10% budget, per k-sparse compressor: the
    residual goes under the dense north-star target of 1e-4 (where dense
    gossip on the same ring needs tens of rounds, CHOCO needs hundreds),
    and a round ships more than 3x fewer bytes than the dense round by
    the engine's own wire accounting."""
    n, dim, target = 8, 256, 1e-4
    W = Topology.ring(n).metropolis_weights()
    x0 = np.random.default_rng(0).normal(size=(n, dim)).astype(np.float32)
    x0 = jnp.asarray(x0 / np.abs(x0).max())  # residual starts O(1)
    comp = compressor_from_spec(spec)
    eng = ChocoGossipEngine(W, comp, gamma=gamma)
    _, res = eng.run(eng.init(x0), 1500)
    res = np.asarray(res)
    assert res[0] > 100 * target and res[-1] < target, (res[0], res[-1])
    layout = mixing_ops.fused_layout(x0)
    wire = FusedCompressor(comp).wire_bytes_per_round(layout, n)
    assert layout.bytes_per_round(n) > 3 * wire > 0


def test_compressor_from_spec_atopk():
    comp = compressor_from_spec("atopk:0.25")
    v = jnp.asarray(
        np.random.default_rng(4).normal(size=(64,)).astype(np.float32)
    )
    out = comp(v, jax.random.key(0))
    assert 0 < int(jnp.sum(out != 0)) <= 20


def test_int8_compressor_contracts_and_choco_converges():
    """int8 delta quantization: bounded per-entry error and CHOCO reaches
    consensus through it (the on-device twin of the int8 wire)."""
    comp = compressor_from_spec("int8")
    v = jnp.asarray(np.random.default_rng(0).normal(size=512), jnp.float32)
    q = comp(v, jax.random.key(0))
    scale = float(jnp.max(jnp.abs(v)) / 127.0)
    assert float(jnp.max(jnp.abs(q - v))) <= 0.5 * scale + 1e-9
    # Contraction: quantization error well below the signal.
    assert float(jnp.sum((q - v) ** 2)) < 0.01 * float(jnp.sum(v ** 2))

    topo = Topology.ring(4)
    eng = ChocoGossipEngine(topo.metropolis_weights(), comp, gamma=0.8)
    x0 = jnp.asarray(
        np.random.default_rng(1).normal(size=(4, 64)), jnp.float32
    )
    state, res = eng.run(eng.init(x0), 150)
    mean = x0.mean(axis=0)
    np.testing.assert_allclose(
        np.asarray(state.x), np.tile(mean, (4, 1)), atol=1e-3
    )
    assert float(res[-1]) < 1e-3


# --------------------------------------------------------------------- #
# Fused whole-buffer compression (ISSUE 5 tentpole)                     #
# --------------------------------------------------------------------- #
def _mixed_tree(seed=0):
    """Mixed bf16+f32, multi-shape, scalar-leaf stacked tree."""
    rng = np.random.default_rng(seed)
    return {
        "w": jnp.asarray(rng.normal(size=(N, 16)), jnp.float32),
        "b": jnp.asarray(rng.normal(size=(N, 3)), jnp.float32),
        "h": jnp.asarray(rng.normal(size=(N, 5)), jnp.bfloat16),
        "g": jnp.asarray(rng.normal(size=(N, 7)), jnp.bfloat16),
        "s": jnp.asarray(rng.normal(size=(N,)), jnp.float32),
        "m": jnp.asarray(rng.normal(size=(N, 2, 4)), jnp.float32),
    }


def _per_leaf_reference(comp, tree, key, n):
    """The exact per-leaf compression the engine's ``fused=False`` path
    performs (``ChocoGossipEngine._compress_tree``, dense mode)."""
    leaves, treedef = jax.tree.flatten(tree)
    keys = jax.random.split(key, len(leaves))
    return jax.tree.unflatten(
        treedef,
        [
            jax.vmap(comp)(leaf, jax.random.split(k, n))
            for leaf, k in zip(leaves, keys)
        ],
    )


@pytest.mark.parametrize(
    "comp",
    [top_k(0.3), approx_top_k(0.3), random_k(0.25), scaled_sign(),
     int8_quant(), identity()],
    ids=["top_k", "approx_top_k", "random_k", "scaled_sign", "int8",
         "identity"],
)
def test_fused_per_leaf_budget_bit_identical(comp):
    """The acceptance oracle: budget='per-leaf' fused compression is
    BIT-identical to the per-leaf path — values AND selected index sets
    (array_equal covers both: a different index set would put a nonzero
    where the oracle has a zero) — on a mixed bf16+f32 tree, for every
    shipped compressor kind.  For random_k this pins the per-(leaf,
    agent) RNG stream; for the top-k family the segment-aware selection
    (ties to the lowest index)."""
    x = _mixed_tree()
    layout = mixing_ops.fused_layout(x)
    buffers, _ = mixing_ops.flatten_stacked(x, layout)
    key = jax.random.key(7)
    fused = mixing_ops.unflatten_stacked(
        FusedCompressor(comp, budget="per-leaf").compress(
            buffers, layout, key, n=N
        ),
        layout,
    )
    want = _per_leaf_reference(comp, x, key, N)
    for (ka, a), (kb, b) in zip(
        sorted(fused.items()), sorted(want.items())
    ):
        assert np.array_equal(np.asarray(a), np.asarray(b)), ka


def test_fused_segment_top_k_keeps_nan_and_ties_like_lax_top_k():
    """NaN counts as above every finite magnitude and boundary ties go
    to the lowest index — the lax.top_k total order, preserved by the
    fused segment selection."""
    x = {"a": jnp.asarray(
        [[1.0, np.nan, 3.0, 0.5, 2.0, 0.1, -2.0, 0.0]], jnp.float32
    )}
    layout = mixing_ops.fused_layout(x)
    buffers, _ = mixing_ops.flatten_stacked(x, layout)
    got = mixing_ops.unflatten_stacked(
        FusedCompressor(top_k(0.5)).compress(
            buffers, layout, jax.random.key(0), n=1
        ),
        layout,
    )["a"]
    want = _per_leaf_reference(top_k(0.5), x, jax.random.key(0), 1)["a"]
    np.testing.assert_array_equal(np.asarray(got), np.asarray(want))
    assert np.isnan(np.asarray(got)[0, 1])  # the NaN was kept, loudly


def test_fused_compressor_rejects_bad_configs():
    with pytest.raises(ValueError, match="budget"):
        FusedCompressor(top_k(0.1), budget="per-tensor")
    with pytest.raises(ValueError, match="named compressor"):
        FusedCompressor(lambda v, k: v, budget="global")
    with pytest.raises(ValueError, match="fused=True"):
        ChocoGossipEngine(
            Topology.ring(N).metropolis_weights(), top_k(0.1),
            fused=False, budget="global",
        )


def test_fused_custom_callable_falls_back_to_per_leaf_views():
    """An arbitrary (value, key) callable still works through the fused
    interface — compressed per leaf view, exact per-leaf semantics."""
    x = _mixed_tree(3)
    layout = mixing_ops.fused_layout(x)
    buffers, _ = mixing_ops.flatten_stacked(x, layout)
    key = jax.random.key(5)
    halve = lambda v, k: 0.5 * v  # noqa: E731 - deliberately a bare lambda
    fc = FusedCompressor(halve)
    assert fc.kind == "custom"
    assert fc.wire_bytes_per_round(layout, N) is None
    got = mixing_ops.unflatten_stacked(
        fc.compress(buffers, layout, key, n=N), layout
    )
    want = _per_leaf_reference(halve, x, key, N)
    for k in got:
        np.testing.assert_array_equal(
            np.asarray(got[k], np.float32), np.asarray(want[k], np.float32)
        )


def test_global_budget_keeps_more_mass_at_fewer_bytes():
    """budget='global' spends one k across the bucket: at the same
    fraction it ships no more bytes (rounding aside) and keeps at least
    the per-leaf-budget L2 mass on heterogeneous-magnitude states (big
    leaves donate budget to the coordinates that matter)."""
    rng = np.random.default_rng(2)
    # One loud leaf, many quiet ones: per-leaf budget wastes k on noise.
    x = {"loud": jnp.asarray(10.0 * rng.normal(size=(1, 64)), jnp.float32)}
    x.update({
        f"quiet{i}": jnp.asarray(
            0.01 * rng.normal(size=(1, 8)), jnp.float32
        )
        for i in range(8)
    })
    layout = mixing_ops.fused_layout(x)
    buffers, _ = mixing_ops.flatten_stacked(x, layout)
    key = jax.random.key(0)
    comp = top_k(0.25)
    kept = {}
    for budget in ("per-leaf", "global"):
        fc = FusedCompressor(comp, budget=budget)
        out = fc.compress(buffers, layout, key, n=1)
        kept[budget] = sum(
            float(jnp.sum(jnp.square(b.astype(jnp.float32))))
            for b in out.values()
        )
        assert fc.wire_bytes_per_round(layout, 1) > 0
    assert kept["global"] >= kept["per-leaf"]
    assert (
        FusedCompressor(comp, budget="global").wire_bytes_per_round(layout, 1)
        <= FusedCompressor(comp, budget="per-leaf").wire_bytes_per_round(
            layout, 1
        )
    )


def test_choco_global_budget_converges():
    """The whole-buffer budget is still a delta-contractive compressor:
    CHOCO reaches exact consensus through it."""
    W = Topology.ring(N).metropolis_weights()
    eng = ChocoGossipEngine(W, top_k(0.1), gamma=0.3, budget="global")
    x0 = _x0()
    state, res = eng.run(eng.init(x0), 400)
    mean = np.asarray(x0).mean(axis=0)
    np.testing.assert_allclose(
        np.asarray(state.x), np.tile(mean, (N, 1)), atol=1e-3
    )
    assert float(res[-1]) < 1e-3


def test_compressed_bytes_counter_and_ratio_gauge():
    """Obs satellite: a concrete fused run books the nominal sparse-wire
    bytes of its rounds and a compression-ratio gauge — host-side only."""
    from distributed_learning_tpu.obs import MetricsRegistry, use_registry

    reg = MetricsRegistry()
    W = Topology.ring(N).metropolis_weights()
    x = _mixed_tree(4)
    layout = mixing_ops.fused_layout(x)
    eng = ChocoGossipEngine(W, top_k(0.25), gamma=0.2)
    wire = FusedCompressor(top_k(0.25)).wire_bytes_per_round(layout, N)
    with use_registry(reg):
        eng.run(eng.init(x), 5)
    snap = reg.snapshot()
    assert snap["counters"]["consensus.compressed_bytes"] == wire * 5
    ratio = snap["gauges"]["consensus.compression_ratio"]
    assert 0 < ratio < 1
    assert ratio == pytest.approx(wire / layout.bytes_per_round(N))


def test_compressor_delta_single_sync_matches_loop_reference():
    """The vectorized compressor_delta (one jitted batch, one sync) is
    deterministic and agrees with a hand-rolled per-trial loop over the
    same split(key, trials) streams."""
    comp = top_k(0.25)
    got = compressor_delta(comp, dim=64, trials=16, seed=3)
    assert got == compressor_delta(comp, dim=64, trials=16, seed=3)
    worst = 1.0
    for k in jax.random.split(jax.random.key(3), 16):
        k1, k2 = jax.random.split(k)
        v = jax.random.normal(k1, (64,))
        err = v - comp(v, k2)
        worst = min(
            worst,
            1.0 - float(jnp.sum(err * err) / jnp.sum(v * v)),
        )
    assert got == pytest.approx(worst, rel=1e-6)
    assert 0.0 < got <= 1.0


def test_host_and_device_top_k_selection_agree():
    """Cross-path consistency (ISSUE 5 satellite): the host-side wire
    selection (``tensor_codec.top_k_sparse``) and the device compressor
    (``compression.top_k``) pick the SAME entries — ties to the lowest
    index, NaN kept — so the TCP sparse wire and the on-device CHOCO
    engine cannot silently diverge."""
    from distributed_learning_tpu.comm.tensor_codec import top_k_sparse

    rng = np.random.default_rng(9)
    cases = [
        rng.normal(size=100).astype(np.float32),
        np.repeat([2.0, -2.0, 1.0, 2.0], 5).astype(np.float32),  # ties
    ]
    nan_case = rng.normal(size=50).astype(np.float32)
    nan_case[7] = np.nan
    for v in cases:
        k = 10
        dev = np.asarray(
            top_k(k / v.size)(jnp.asarray(v), jax.random.key(0))
        )
        idx_host, vals_host = top_k_sparse(v, k)
        dev_idx = np.flatnonzero(dev)
        np.testing.assert_array_equal(dev_idx, idx_host)
        np.testing.assert_array_equal(dev[dev_idx], vals_host)
    # NaN: both selection paths keep the poisoned coordinate, loudly.
    k = 5
    dev = np.asarray(
        top_k(k / nan_case.size)(jnp.asarray(nan_case), jax.random.key(0))
    )
    idx_host, _ = top_k_sparse(nan_case, k)
    assert 7 in idx_host and np.isnan(dev[7])
    dev_sel = set(np.flatnonzero(dev != 0)) | {
        i for i in range(dev.size) if np.isnan(dev[i])
    }
    assert dev_sel == set(int(i) for i in idx_host)


def test_choco_fused_carry_matches_perleaf_oracle():
    """The fused flat-buffer carry (x/xhat raveled once per run, mixing
    on the fused estimate buffers, compression per ORIGINAL leaf) is the
    same recurrence as the per-leaf scan — allclose at GEMM-accumulation
    tolerance on a mixed bf16+f32, multi-leaf, scalar-leaf tree."""
    rng = np.random.default_rng(0)
    x = {
        "w": jnp.asarray(rng.normal(size=(N, 16)), jnp.float32),
        "b": jnp.asarray(rng.normal(size=(N, 3)), jnp.float32),
        "h": jnp.asarray(rng.normal(size=(N, 5)), jnp.bfloat16),
        "s": jnp.asarray(rng.normal(size=(N,)), jnp.float32),
    }
    W = Topology.ring(N).metropolis_weights()
    ef = ChocoGossipEngine(W, top_k(0.3), gamma=0.2)
    ep = ChocoGossipEngine(W, top_k(0.3), gamma=0.2, fused=False)
    assert ef.fused and not ep.fused
    sf, trf = ef.run(ef.init(x, seed=1), 10)
    sp, trp = ep.run(ep.init(x, seed=1), 10)
    for k in x:
        np.testing.assert_allclose(
            np.asarray(sf.x[k], np.float64), np.asarray(sp.x[k], np.float64),
            rtol=2e-6, atol=2e-6, err_msg=f"x:{k}",
        )
        np.testing.assert_allclose(
            np.asarray(sf.xhat[k], np.float64),
            np.asarray(sp.xhat[k], np.float64),
            rtol=2e-6, atol=2e-6, err_msg=f"xhat:{k}",
        )
    np.testing.assert_allclose(
        np.asarray(trf), np.asarray(trp), rtol=2e-5, atol=2e-6
    )
