"""GossipTrainer with state no gradient moves in an LM (the sigmoid
router's balancing bias, ``models/moe.py::HeldExpertsMLP``) and with
Pallas kernels in the step under a mesh (one agent a device).

The bias lives in ``batch_stats``, the collection the trainer carries for
BatchNorm: it rides the epoch scan and the superstep scan, is sharded and
saved with its agent, and is never mixed.  Under a mesh the vmapped step
runs inside ``shard_map`` over the agent axis, so that a kernel (which
has no partitioning rule) sees its own agent's operands alone, and a
state too large to pack is mixed leaf by leaf.
"""

from __future__ import annotations

import jax
import jax.numpy as jnp
import numpy as np
import optax
import pytest

from distributed_learning_tpu.models import TransformerLM
from distributed_learning_tpu.parallel import Topology, consensus
from distributed_learning_tpu.parallel.consensus import (
    ConsensusEngine, make_agent_mesh,
)
from distributed_learning_tpu.training.trainer import GossipTrainer

N, T, VOCAB, STEPS = 4, 32, 48, 2
GAMMA = 0.001
MLA = dict(
    vocab_size=VOCAB, num_layers=2, hidden_size=32, num_heads=2, head_dim=16,
    max_len=64, pos_emb="rope", rope_base=1e6, attn_impl="full",
    norm="rmsnorm", norm_eps=1e-6, head_bias=False, kv_lora_rank=16,
    qk_nope_head_dim=8, qk_rope_head_dim=4, v_head_dim=8, num_dense_layers=1,
    dense_width=48, mlp="held_experts", num_experts=16, moe_top_k=2,
    experts_held=4, expert_width=16, shared_expert_width=16,
    router_score="sigmoid", route_scale=2.448, route_bias_rate=GAMMA,
    shared_expert_gate=False,
)


def _data(seed=0, per_agent=STEPS):
    ids = np.random.default_rng(seed).integers(
        0, VOCAB, size=(N, per_agent, T + 1)).astype(np.int32)
    return {a: (ids[a, :, :-1], ids[a, :, 1:]) for a in range(N)}


def _trainer(model, init=True, **kw):
    args = dict(
        node_names=list(range(N)), model=model, optimizer="adam",
        learning_rate=1e-2, error="cross_entropy", weights=Topology.ring(N),
        train_data=_data(), test_data=None, batch_size=1, epoch_len=STEPS,
        epoch=1 << 30, mix_times=1, dropout=False, seed=0,
    )
    args.update(kw)
    trainer = GossipTrainer(**args)
    if init:
        trainer.initialize_nodes()
    return trainer


@pytest.fixture(scope="module")
def trained():
    """One epoch (two local steps, then one ring round) of the toy LM,
    with the state it started from."""
    trainer = _trainer(TransformerLM(**MLA))
    start = jax.device_get(trainer.state[:3])
    payload = trainer.train_epochs(1)[0]
    return trainer, start, payload


def test_the_bias_rides_the_epoch_scan_and_is_not_mixed(trained):
    """Agent 0's bias after the epoch is what its own two Adam steps,
    replayed here with the model alone, leave: the scan carried it from
    step to step and the mix after them did not touch it."""
    trainer, (params, bias, _), payload = trained
    assert payload["mixed"]
    model, tx = trainer.model, optax.adam(1e-2)
    p = jax.tree.map(lambda a: a[0], params)
    b = jax.tree.map(lambda a: a[0], bias)
    order = trainer._epoch_perm(0)[:, 0, 0]
    opt = tx.init(p)

    @jax.jit
    def step(p, b, opt, x, y):
        def lossf(p):
            logits, mut = model.apply(
                {"params": p, "batch_stats": b}, x[None], train=True,
                mutable=["batch_stats", "counters"])
            return optax.softmax_cross_entropy_with_integer_labels(
                logits, y[None]).mean(), mut["batch_stats"]
        (_, b), g = jax.value_and_grad(lossf, has_aux=True)(p)
        updates, opt = tx.update(g, opt, p)
        return optax.apply_updates(p, updates), b, opt

    X, Y = trainer._Xs[0], trainer._ys[0]
    for t in range(STEPS):
        p, b, opt = step(p, b, opt, X[order[t]], Y[order[t]])
    got = jax.tree.map(lambda a: a[0], trainer.state[1])
    for g, w in zip(jax.tree.leaves(got), jax.tree.leaves(b), strict=True):
        np.testing.assert_allclose(g, w, atol=1e-7)
        assert float(jnp.abs(g).max()) > 0.5 * GAMMA  # it moved
    # the parameters WERE mixed: the replay's are not the trainer's
    moved = [float(jnp.abs(a[0] - r).max()) for a, r in zip(
        jax.tree.leaves(trainer.state[0]), jax.tree.leaves(p))]
    assert max(moved) > 1e-4


def test_each_agents_bias_stays_on_its_own_lattice(trained):
    """A bias moves by gamma, up, down or not, a step: every entry is a
    whole number of gammas, where a ring round over the bias would have
    left thirds of one.  And the agents, fed different batches, hold
    different biases."""
    trainer, _, _ = trained
    for leaf in jax.tree.leaves(trainer.state[1]):
        steps = np.asarray(leaf, np.float64) / GAMMA
        assert np.abs(steps - np.round(steps)).max() < 1e-3
        assert np.abs(np.round(steps)).max() <= STEPS
        assert np.abs(steps[0] - steps[1]).max() > 0.5


def test_the_counter_of_all_experts_reaches_the_payload(trained):
    _, _, payload = trained
    counters = payload["counters"]
    assert set(counters) == {"moe.rows_held", "moe.load_max",
                             "moe.load_max_all"}
    assert counters["moe.load_max_all"].shape == (STEPS, N)
    assert (counters["moe.load_max_all"] >= counters["moe.load_max"]).all()


def test_the_bias_rides_the_superstep_scan():
    """Two epochs in one superstep program against two epoch programs."""
    model = TransformerLM(**MLA)
    one = _trainer(model)
    for _ in range(2):
        one.train_epochs(1)
    both = _trainer(model, superstep=2)
    both.train_epochs(2)
    for a, b in zip(jax.tree.leaves(one.state[1]),
                    jax.tree.leaves(both.state[1]), strict=True):
        np.testing.assert_array_equal(a, b)
    for a, b in zip(jax.tree.leaves(one.state[0]),
                    jax.tree.leaves(both.state[0]), strict=True):
        np.testing.assert_allclose(a, b, atol=1e-6)


def test_the_bias_survives_a_checkpoint_round_trip(trained, tmp_path):
    trainer, _, _ = trained
    path = str(tmp_path / "ckpt")
    trainer.save_checkpoint(path)
    fresh = _trainer(TransformerLM(**MLA))
    assert all(float(jnp.abs(a).max()) == 0.0
               for a in jax.tree.leaves(fresh.state[1]))
    fresh.restore_checkpoint(path)
    for a, b in zip(jax.tree.leaves(trainer.state[1]),
                    jax.tree.leaves(fresh.state[1]), strict=True):
        np.testing.assert_array_equal(a, b)


@pytest.mark.parametrize("kwargs", [
    dict(vocab_size=VOCAB, num_layers=1, num_heads=2, head_dim=8, max_len=T),
    {**MLA, "router_score": "softmax", "route_bias_rate": None,
     "route_scale": 1.0, "shared_expert_gate": True},
], ids=["gpt2-like", "softmax-held-experts"])
def test_a_model_without_the_bias_has_no_such_state(kwargs):
    """No leaf is added where no layer asks for one: the state tree of
    the accepted configurations is the one they had."""
    trainer = _trainer(TransformerLM(**kwargs))
    assert trainer.state[1] is None
    trainer.train_epochs(1)
    assert trainer.state[1] is None


# ---------------------------------------------------------------------- #
# kernels in the step, one agent a device                                #
# ---------------------------------------------------------------------- #
@pytest.fixture
def interpreted_flash(monkeypatch):
    """``attn_impl="flash"`` runs the Pallas kernels interpreted (the
    public wrapper falls back to dense attention on a CPU)."""
    import distributed_learning_tpu.ops.flash_attention as fa

    kernels = fa.flash_attention
    monkeypatch.setattr(
        fa, "flash_attention",
        lambda *a, **kw: kernels(*a, **kw, interpret=True))


FLASH = dict(vocab_size=VOCAB, num_layers=2, num_heads=2, head_dim=16,
             max_len=T, attn_impl="flash", pos_emb="rope")


@pytest.mark.parametrize("kwargs", [FLASH, {**MLA, "attn_impl": "flash"}],
                         ids=["flash", "latent-two-widths"])
def test_the_sharded_layout_is_the_dense_layout(interpreted_flash, kwargs):
    """One agent a device against four stacked on one, after an epoch
    (plain SGD: Adam's first steps turn a rounding of a gradient near
    zero into a whole step), to the tolerance of tests/test_multidevice.py;
    and the sharded epoch program moves nothing between devices."""
    model = TransformerLM(**kwargs)
    sgd = dict(optimizer="sgd", learning_rate=0.1)
    dense = _trainer(model, **sgd)
    sharded = _trainer(model, mesh=make_agent_mesh(N), **sgd)
    text = jax.jit(sharded._epoch_fn).lower(
        sharded.state, sharded._Xs, sharded._ys, sharded._epoch_indices(0)
    ).compile().as_text()
    for collective in ("all-gather", "all-to-all", "all-reduce",
                       "collective-permute"):
        assert collective not in text
    told_d = dense.train_epochs(1)[0]
    told_s = sharded.train_epochs(1)[0]
    np.testing.assert_allclose(
        told_s["train_loss"], told_d["train_loss"], atol=1e-5)
    for s, d in zip(jax.tree.leaves(sharded.state[:3]),
                    jax.tree.leaves(dense.state[:3]), strict=True):
        # every leaf has one shard, one agent, on each device
        assert len(s.addressable_shards) == N
        assert s.addressable_shards[0].data.shape[0] == 1
        np.testing.assert_allclose(s, d, atol=1e-5)


def test_under_a_mesh_the_shared_init_runs_on_one_device():
    """The model's forward inside ``initialize_nodes`` must not be
    partitioned over the mesh (on a TPU a Pallas kernel in it cannot be):
    the sample it is traced with is a host copy, and the replicas are made
    in place, one shard of every leaf a device."""
    trainer = _trainer(TransformerLM(**MLA), init=False,
                       mesh=make_agent_mesh(N))
    init, seen = trainer._jit_init, {}

    def spy(rng, x0):
        seen["x0"] = x0
        return init(rng, x0)

    trainer._jit_init = spy
    trainer.initialize_nodes()
    assert isinstance(seen["x0"], np.ndarray)
    for leaf in jax.tree.leaves(trainer.state[:3]):
        assert len(leaf.addressable_shards) == N
        assert leaf.addressable_shards[0].data.shape[0] == 1
    # the step key sits where the epoch program leaves it: a second epoch
    # is the first epoch's program, not another compile
    assert len(trainer.state[3].addressable_shards) == N
    trainer.train_epochs(1)
    size = trainer._jit_epoch._cache_size()
    trainer.train_epochs(1)
    assert trainer._jit_epoch._cache_size() == size


@pytest.mark.parametrize("limit, packed", [(1 << 30, True), (1 << 10, False)])
def test_a_shard_too_large_to_pack_is_mixed_leaf_by_leaf(monkeypatch, limit,
                                                         packed):
    """Above ``FUSE_MAX_BYTES`` an agent's state stays a tree through the
    sharded round (no concatenate of every leaf into one buffer), and the
    round is the same round."""
    monkeypatch.setattr(consensus, "FUSE_MAX_BYTES", limit)
    W = Topology.ring(N).metropolis_weights()
    engine = ConsensusEngine(W, mesh=make_agent_mesh(N))
    rng = np.random.default_rng(0)
    x = {"a": rng.normal(size=(N, 64, 8)).astype(np.float32),
         "b": rng.normal(size=(N, 128)).astype(np.float32)}
    state = engine.shard(x)
    text = jax.jit(lambda s: engine.mix(s, times=1)).lower(state).as_text()
    assert ("concatenate" in text) == packed
    got = engine.mix(state, times=1)
    for name in x:
        want = np.einsum("ab,b...->a...", W, x[name])
        np.testing.assert_allclose(got[name], want, atol=1e-6)
    assert float(engine.max_deviation(got)) < float(engine.max_deviation(state))
