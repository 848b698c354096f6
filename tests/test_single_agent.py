"""One agent is a graph: ``Topology.complete(1)`` is the single vertex with
``W = [[1]]``, and ``GossipTrainer`` trains one agent as bare Adam steps on
the same batches (the layout of the Phi-4-mini-flash cell, one agent a
chip, on one chip)."""

from __future__ import annotations

import jax
import jax.numpy as jnp
import numpy as np
import optax
import pytest

from distributed_learning_tpu.models import TransformerLM
from distributed_learning_tpu.parallel.topology import Topology
from distributed_learning_tpu.training.trainer import GossipTrainer

STEPS, T = 3, 16


def test_complete_one_is_the_single_vertex():
    topo = Topology.complete(1)
    assert topo.n_agents == 1 and topo.edges == () and topo.tokens == (0,)
    np.testing.assert_array_equal(topo.metropolis_weights(), [[1.0]])
    assert topo.connected() and topo.uniform_epsilon() == 0.0
    # more agents are the graph they always were
    assert Topology.complete(3).edges == ((0, 1), (0, 2), (1, 2))


@pytest.fixture(scope="module")
def trained():
    ids = np.random.default_rng(0).integers(0, 32, (STEPS, T + 1), np.int32)
    trainer = GossipTrainer(
        node_names=[0], model=TransformerLM(vocab_size=32, num_layers=1,
                                            num_heads=2, head_dim=8, max_len=T),
        optimizer="adam", learning_rate=1e-2, error="cross_entropy",
        weights=Topology.complete(1),
        train_data={0: (ids[:, :-1], ids[:, 1:])}, test_data=None,
        batch_size=1, epoch_len=STEPS, epoch=1 << 30, dropout=False, seed=3)
    trainer.initialize_nodes()
    start = jax.device_get(trainer.state[0])
    payload = trainer.train_epochs(1)[0]
    return trainer, start, payload


def test_one_agent_trains_as_bare_adam_steps(trained):
    trainer, start, payload = trained
    model, tx = trainer.model, optax.adam(1e-2)
    p = jax.tree.map(lambda a: a[0], start)
    opt = tx.init(p)

    @jax.jit
    def step(p, opt, x, y):
        def lossf(p):
            logits = model.apply({"params": p}, x[None])
            return optax.softmax_cross_entropy_with_integer_labels(
                logits, y[None]).mean()
        loss, g = jax.value_and_grad(lossf)(p)
        updates, opt = tx.update(g, opt, p)
        return optax.apply_updates(p, updates), opt, loss

    order = trainer._epoch_perm(0)[:, 0, 0]
    X, Y = trainer._Xs[0], trainer._ys[0]
    losses = []
    for t in range(STEPS):
        p, opt, loss = step(p, opt, X[order[t]], Y[order[t]])
        losses.append(float(loss))
    np.testing.assert_allclose(np.asarray(payload["train_loss"]).ravel(),
                               [np.mean(losses)], rtol=1e-5)
    for got, want in zip(jax.tree.leaves(trainer.state[0]),
                         jax.tree.leaves(p), strict=True):
        np.testing.assert_allclose(got[0], want, rtol=1e-5, atol=1e-6)


def test_one_agent_has_no_deviation(trained):
    _, _, payload = trained
    assert float(payload["deviation"]) == 0.0
