"""Gossip-SGD trainer tests: the MasterNode workflow end to end.

Scenario parity: ``Man_Colab.ipynb`` cells 14-24 — named nodes, topology
dict with weights, string model name, torch-style optimizer kwargs,
stat_step curves, per-node test accuracy, ``show_graphs``.
"""

import numpy as np
import pytest

import jax
import jax.numpy as jnp

from distributed_learning_tpu.data import (
    load_cifar,
    normalize,
    shard_dataset,
    synthetic_cifar,
)
from distributed_learning_tpu.training import (
    GossipTrainer,
    MasterNode,
    get_loss,
    make_optimizer,
)
from distributed_learning_tpu.utils import RecordingTelemetry

TOPOLOGY = {
    "Alice": {"Alice": 0.4, "Bob": 0.3, "Charlie": 0.3},
    "Bob": {"Alice": 0.3, "Bob": 0.4, "Charlie": 0.3},
    "Charlie": {"Alice": 0.3, "Bob": 0.3, "Charlie": 0.4},
}


def _small_setup(n_train=768, batch=64):
    (X, y), (Xt, yt) = synthetic_cifar(n_train=n_train, n_test=128, seed=0)
    Xn = np.asarray(normalize(jnp.asarray(X)))
    Xtn = np.asarray(normalize(jnp.asarray(Xt)))
    shards = shard_dataset(Xn, y, list(TOPOLOGY), batch_size=batch, seed=1)
    return shards, (Xtn, yt)


def test_masternode_full_workflow():
    shards, test = _small_setup()
    telemetry = RecordingTelemetry()
    master = MasterNode(
        node_names=TOPOLOGY.keys(),
        model="lenet",
        model_args=[10],
        optimizer="sgd",
        optimizer_kwargs={"momentum": 0.9, "weight_decay": 5e-4},
        error="cross_entropy",
        weights=TOPOLOGY,
        train_loaders=shards,
        test_loader=test,
        stat_step=2,
        epoch=3,
        epoch_len=4,
        epoch_cons_num=1,
        batch_size=64,
        learning_rate=0.05,
        telemetry=telemetry,
        seed=0,
    )
    master.initialize_nodes()

    # Shared init: all nodes identical before training.
    assert master.parameter_deviation() == pytest.approx(0.0, abs=1e-5)

    results = master.start_consensus()
    assert len(results) == 3

    # Learning happened: final epoch train acc above chance for every node.
    assert np.all(results[-1]["train_acc"] > 0.2)
    # Mixing happened every epoch (epoch_cons_num=1).
    assert all(r["mixed"] for r in results)

    # Per-node curves recorded every stat_step batches: 4 steps / 2 = 2 per
    # epoch, 3 epochs -> 6 stat points.
    node = master.network["Bob"]
    assert len(node.stats.train_loss) == 6
    assert len(node.stats.test_acc) == 3

    # Telemetry: one payload per node per epoch.
    by_tok = telemetry.by_token()
    assert set(by_tok) == set(TOPOLOGY)
    assert len(by_tok["Alice"]) == 3
    assert "deviation" in telemetry.records[0][1]
    assert by_tok["Alice"][0]["train_loss"] > 0

    # show_graphs returns a figure (Agg backend).
    fig = node.show_graphs()
    assert fig is not None


def test_epoch_cons_num_delays_mixing():
    shards, test = _small_setup()
    master = GossipTrainer(
        node_names=list(TOPOLOGY),
        model="lenet",
        model_args=[10],
        weights=TOPOLOGY,
        train_data=shards,
        test_data=None,
        epoch=3,
        epoch_len=2,
        epoch_cons_num=3,  # consensus only from the 3rd epoch
        batch_size=64,
        learning_rate=0.05,
        seed=1,
    )
    r = master.start_consensus()
    assert [ri["mixed"] for ri in r] == [False, False, True]
    # After first mixing round, deviation strictly dropped.
    assert r[2]["deviation"] < r[1]["deviation"]


def test_no_weights_means_isolated_nodes():
    shards, _ = _small_setup()
    t = GossipTrainer(
        node_names=list(TOPOLOGY),
        model="lenet",
        model_args=[10],
        weights=None,  # identity mixing
        train_data=shards,
        epoch=1,
        epoch_len=2,
        batch_size=64,
        seed=2,
    )
    r = t.start_consensus()
    assert r[0]["deviation"] > 0  # nodes drift apart, nothing pulls them back


def test_mlp_model_without_batchnorm_or_dropout():
    rng = np.random.default_rng(0)
    X = rng.normal(size=(600, 784)).astype(np.float32)
    w = rng.normal(size=(784, 10)).astype(np.float32)
    y = (X @ w).argmax(-1).astype(np.int32)
    shards = {
        i: (X[i * 200 : (i + 1) * 200], y[i * 200 : (i + 1) * 200])
        for i in range(3)
    }
    t = GossipTrainer(
        node_names=[0, 1, 2],
        model="ann",
        model_kwargs={"hidden_dim": 64, "output_dim": 10},
        weights=np.full((3, 3), 1 / 3),
        train_data=shards,
        test_data=(X[:100], y[:100]),
        epoch=5,
        batch_size=50,
        learning_rate=0.05,
        optimizer="adam",
        seed=3,
    )
    r = t.start_consensus()
    # Complete-graph averaging every epoch: nodes agree afterwards.
    assert r[-1]["deviation"] < 1e-4
    assert r[-1]["test_acc"].mean() > 0.5


def test_checkpoint_roundtrip(tmp_path):
    shards, test = _small_setup()
    kwargs = dict(
        node_names=list(TOPOLOGY),
        model="lenet",
        model_args=[10],
        weights=TOPOLOGY,
        train_data=shards,
        test_data=test,
        epoch=2,
        epoch_len=2,
        batch_size=64,
        learning_rate=0.05,
        seed=4,
    )
    t1 = GossipTrainer(**kwargs)
    t1.train_epoch()
    ckpt = str(tmp_path / "ckpt")
    t1.save_checkpoint(ckpt)
    t1_result = t1.train_epoch()

    t2 = GossipTrainer(**kwargs)
    t2.initialize_nodes()
    t2.restore_checkpoint(ckpt)
    assert t2._epochs_done == 1
    t2_result = t2.train_epoch()

    # Resumed run reproduces the original bit-for-bit.
    np.testing.assert_allclose(
        t1_result["train_loss"], t2_result["train_loss"], rtol=1e-6
    )
    p1 = t1.node_parameters()["Alice"]
    p2 = t2.node_parameters()["Alice"]
    for a, b in zip(jax.tree.leaves(p1), jax.tree.leaves(p2)):
        np.testing.assert_allclose(np.asarray(a), np.asarray(b), atol=1e-6)


def test_loss_and_optimizer_registries():
    import optax

    assert callable(get_loss("cross_entropy"))
    assert callable(get_loss("binary_logistic"))
    with pytest.raises(ValueError):
        get_loss("hinge")
    tx = make_optimizer("sgd", {"momentum": 0.9, "weight_decay": 5e-4}, 0.1)
    assert isinstance(tx, optax.GradientTransformation)
    tx2 = make_optimizer(optax.adam(1e-3))
    assert isinstance(tx2, optax.GradientTransformation)
    with pytest.raises(ValueError):
        make_optimizer("lbfgs")


def test_trainer_validations():
    shards, _ = _small_setup()
    with pytest.raises(ValueError, match="missing"):
        GossipTrainer(
            node_names=["Alice", "Dave"],
            model="lenet",
            model_args=[10],
            train_data=shards,
            epoch=1,
        )
    with pytest.raises(ValueError, match="shape"):
        GossipTrainer(
            node_names=list(TOPOLOGY),
            model="lenet",
            model_args=[10],
            weights=np.eye(2),
            train_data=shards,
            epoch=1,
        )


def test_binary_logistic_metric_reports_sign_accuracy():
    from distributed_learning_tpu.training import get_metric

    margin = jnp.asarray([[2.0], [-1.0], [0.5], [-3.0]])
    y = jnp.asarray([1.0, -1.0, -1.0, -1.0])
    acc = get_metric("binary_logistic")(margin, y)
    assert float(acc) == pytest.approx(0.75)
    # multiclass default still argmax
    logits = jnp.asarray([[0.1, 0.9], [0.8, 0.2]])
    assert float(get_metric("cross_entropy")(logits, jnp.asarray([1, 0]))) == 1.0


def test_time_varying_topology_schedule_with_chebyshev():
    """BASELINE config-5 shape: the trainer resamples a random graph every
    epoch and mixes with a per-epoch Chebyshev schedule."""
    from distributed_learning_tpu.parallel.topology import Topology

    rng = np.random.default_rng(0)
    names = list(range(4))
    train = {
        i: (
            rng.normal(size=(64, 8)).astype(np.float32),
            rng.integers(0, 3, size=(64,)).astype(np.int32),
        )
        for i in names
    }
    seen = []

    def schedule(epoch):
        topo = Topology.erdos_renyi(4, 0.6, seed=500 + epoch)
        seen.append(epoch)
        return topo

    tr = GossipTrainer(
        node_names=names,
        model="mlp",
        model_kwargs={"hidden_dim": 16, "output_dim": 3},
        error="cross_entropy",
        train_data=train,
        topology_schedule=schedule,
        chebyshev=True,
        mix_times=3,
        batch_size=16,
        epoch=2,
        stat_step=2,
        dropout=False,
    )
    tr.initialize_nodes()
    out0 = tr.train_epoch()
    out1 = tr.train_epoch()
    assert out0["mixed"] and out1["mixed"]
    # schedule(0) seeds the engine, then each epoch resolves its own graph.
    assert seen == [0, 0, 1]
    assert np.isfinite(out1["deviation"])


def test_chebyshev_config_validation():
    """Conflicting or unusable chebyshev configs fail at construction, not
    mid-training."""
    rng = np.random.default_rng(0)
    train = {
        i: (
            rng.normal(size=(32, 4)).astype(np.float32),
            rng.integers(0, 2, size=(32,)).astype(np.int32),
        )
        for i in range(3)
    }
    kw = dict(
        node_names=[0, 1, 2],
        model="mlp",
        model_kwargs={"hidden_dim": 8, "output_dim": 2},
        train_data=train,
        batch_size=8,
        dropout=False,
    )
    # weights=None -> isolated nodes -> gamma=1: chebyshev is meaningless.
    with pytest.raises(ValueError, match="gamma"):
        GossipTrainer(chebyshev=True, **kw)
    # eps-stopping and the fixed chebyshev schedule are mutually exclusive.
    with pytest.raises(ValueError, match="mutually exclusive"):
        GossipTrainer(chebyshev=True, mix_eps=1e-4, **kw)


def test_eps_stopping_composes_with_topology_schedule():
    """mix_eps + topology_schedule: each epoch's resampled graph gossips
    until the residual drops below eps (engine.mix_until_with), so the
    post-mix deviation must sit at/below eps even though the graph
    changes every epoch."""
    from distributed_learning_tpu.parallel.topology import Topology

    rng = np.random.default_rng(3)
    train = {
        i: (
            rng.normal(size=(32, 6)).astype(np.float32),
            rng.integers(0, 2, size=(32,)).astype(np.int32),
        )
        for i in range(3)
    }
    schedules = []

    def schedule(e):
        schedules.append(e)
        return Topology.ring(3) if e % 2 == 0 else Topology.complete(3)

    tr = GossipTrainer(
        node_names=[0, 1, 2],
        model="mlp",
        model_kwargs={"hidden_dim": 8, "output_dim": 2},
        train_data=train,
        batch_size=8,
        dropout=False,
        epoch=2,
        topology_schedule=schedule,
        mix_eps=1e-4,
        mix_times=1,
        seed=5,
    )
    for _ in range(2):
        payload = tr.train_epoch()
        assert payload["mixed"]
        assert payload["deviation"] <= 1e-4 + 1e-6
    assert set(schedules) >= {0, 1}


def test_gossip_pga_and_adaptive_mix_times():
    """Gossip-PGA: every H-th consensus epoch is exact averaging (residual
    ~0); the adaptive mix_times schedule is consulted per epoch."""
    rng = np.random.default_rng(0)
    names = list(range(4))
    train = {
        i: (
            rng.normal(size=(64, 8)).astype(np.float32),
            rng.integers(0, 3, size=(64,)).astype(np.int32),
        )
        for i in names
    }
    from distributed_learning_tpu.parallel.topology import Topology

    asked = []

    def times_schedule(epoch):
        asked.append(epoch)
        return 1

    tr = GossipTrainer(
        node_names=names,
        model="mlp",
        model_kwargs={"hidden_dim": 16, "output_dim": 3},
        train_data=train,
        weights=Topology.ring(4),
        batch_size=16,
        epoch=3,
        stat_step=2,
        dropout=False,
        global_avg_every=2,
        mix_times_schedule=times_schedule,
    )
    tr.initialize_nodes()
    out0 = tr.train_epoch()  # consensus epoch 0: gossip
    out1 = tr.train_epoch()  # consensus epoch 1: global average (H=2)
    assert out0["mixed"] and out1["mixed"]
    # After exact averaging the residual is (numerically) zero.
    assert out1["deviation"] < 1e-5
    assert out0["deviation"] > out1["deviation"]
    assert asked == [0, 1]

    with pytest.raises(ValueError, match="global_avg_every"):
        GossipTrainer(
            node_names=names, model="mlp",
            model_kwargs={"hidden_dim": 8, "output_dim": 3},
            train_data=train, batch_size=16, global_avg_every=0,
        )


def test_augmentation_changes_training_but_stays_finite():
    """augment=True applies the jitted crop+flip inside the step; training
    remains finite and the option round-trips through ExperimentConfig."""
    (X, y), _ = synthetic_cifar(n_train=256, n_test=32, seed=0)
    Xn = np.asarray(normalize(jnp.asarray(X)))
    names = [0, 1]
    shards = shard_dataset(Xn, y, names, batch_size=16, seed=0)
    kw = dict(
        node_names=names, model="lenet", model_args=[10],
        train_data=shards, batch_size=16, stat_step=2, epoch=1,
        dropout=False,
    )
    plain = GossipTrainer(**kw)
    plain.initialize_nodes()
    out_plain = plain.train_epoch()
    aug = GossipTrainer(augment=True, **kw)
    aug.initialize_nodes()
    out_aug = aug.train_epoch()
    assert np.isfinite(out_aug["train_loss"]).all()
    # Same data+seed, different pixels seen -> different loss trajectory.
    assert not np.allclose(out_plain["train_loss"], out_aug["train_loss"])


def test_augment_validation_and_pad_value():
    """Non-image data rejects augment up front; config computes the
    normalized-black pad value; augment_batch borders carry it."""
    import jax
    from distributed_learning_tpu.data.cifar import (
        augment_batch,
        normalized_pad_value,
    )
    from distributed_learning_tpu.training import ExperimentConfig

    rng = np.random.default_rng(0)
    tabular = {
        i: (
            rng.normal(size=(32, 8)).astype(np.float32),
            rng.integers(0, 2, size=(32,)).astype(np.int32),
        )
        for i in range(2)
    }
    with pytest.raises(ValueError, match="image inputs"):
        GossipTrainer(
            node_names=[0, 1], model="mlp",
            model_kwargs={"hidden_dim": 8, "output_dim": 2},
            train_data=tabular, batch_size=8, augment=True,
        )
    with pytest.raises(ValueError, match="image datasets"):
        ExperimentConfig(
            node_names=[0, 1], dataset="titanic", augment=True,
            model="ann", model_args=[2],
        ).build()

    pv = normalized_pad_value("cifar10")
    x = jnp.ones((2, 32, 32, 3), jnp.float32) * 5.0
    out = augment_batch(jax.random.key(0), x, pad_value=pv)
    vals = np.asarray(out).reshape(-1, 3)
    # Any border pixel that survived the crop equals pv, not 0.
    border = vals[~np.isclose(vals[:, 0], 5.0)]
    if len(border):
        np.testing.assert_allclose(border, np.broadcast_to(pv, border.shape),
                                   rtol=1e-5)


def test_remat_matches_plain_training():
    """remat=True recomputes activations in backward but must produce the
    same numerics as plain training."""
    rng = np.random.default_rng(0)
    names = [0, 1]
    train = {
        i: (
            rng.normal(size=(32, 8)).astype(np.float32),
            rng.integers(0, 3, size=(32,)).astype(np.int32),
        )
        for i in names
    }
    kw = dict(
        node_names=names, model="mlp",
        model_kwargs={"hidden_dim": 16, "output_dim": 3},
        train_data=train, batch_size=8, stat_step=2, epoch=1, dropout=False,
    )
    a = GossipTrainer(**kw)
    a.initialize_nodes()
    out_a = a.train_epoch()
    b = GossipTrainer(remat=True, **kw)
    b.initialize_nodes()
    out_b = b.train_epoch()
    np.testing.assert_allclose(
        np.asarray(out_a["train_loss"]), np.asarray(out_b["train_loss"]),
        rtol=1e-5,
    )
    # Identical losses alone don't establish identical updates — the final
    # parameters (and BN stats, when present) must agree too.
    pa, ba = a.state[0], a.state[1]
    pb, bb = b.state[0], b.state[1]
    for la, lb in zip(jax.tree.leaves(pa), jax.tree.leaves(pb)):
        np.testing.assert_allclose(np.asarray(la), np.asarray(lb), rtol=1e-5)
    if ba is not None:
        for la, lb in zip(jax.tree.leaves(ba), jax.tree.leaves(bb)):
            np.testing.assert_allclose(np.asarray(la), np.asarray(lb), rtol=1e-5)


def test_choco_state_survives_checkpoint_resume(tmp_path):
    """Compressed-run resume reproduces the uninterrupted trajectory:
    the CHOCO error-feedback state (public estimates xhat + PRNG key) is
    checkpointed, so save/restore mid-run must yield the same parameters
    as never stopping (previously estimates reset to zero on restore and
    the resumed run silently diverged)."""
    from distributed_learning_tpu.models import ANNModel
    from distributed_learning_tpu.parallel.topology import Topology

    rng = np.random.default_rng(1)
    n, d = 4, 8
    train = {
        i: (
            rng.normal(size=(64, d)).astype(np.float32),
            rng.integers(0, 3, size=(64,)).astype(np.int32),
        )
        for i in range(n)
    }
    kw = dict(
        node_names=list(range(n)),
        model=ANNModel(hidden_dim=8, output_dim=3),
        optimizer="sgd",
        learning_rate=0.05,
        weights=Topology.ring(n),
        train_data=train,
        batch_size=16,
        epoch=4,
        dropout=False,
        seed=7,
        mix_times=4,
        compression="topk:0.3",
        compression_gamma=0.3,
    )
    straight = GossipTrainer(**kw)
    straight.initialize_nodes()
    for _ in range(4):
        straight.train_epoch()

    t1 = GossipTrainer(**kw)
    t1.initialize_nodes()
    t1.train_epoch()
    t1.train_epoch()
    assert t1._choco_xhat is not None  # estimates exist mid-run
    ckpt = str(tmp_path / "choco-ckpt")
    t1.save_checkpoint(ckpt)

    t2 = GossipTrainer(**kw)
    t2.restore_checkpoint(ckpt)
    assert t2._epochs_done == 2
    assert t2._choco_xhat is not None  # estimates restored, not reset
    t2.train_epoch()
    t2.train_epoch()

    for a, b in zip(
        jax.tree.leaves(straight.state[0]), jax.tree.leaves(t2.state[0])
    ):
        np.testing.assert_allclose(
            np.asarray(a), np.asarray(b), rtol=1e-6, atol=1e-7
        )


def test_choco_restore_falls_back_on_pre_choco_checkpoint(tmp_path):
    """A checkpoint written without CHOCO state (older version / dense
    trainer) still restores into a compressed trainer: estimates reset
    with a warning instead of an unrecoverable structure mismatch."""
    import warnings as _warnings

    from distributed_learning_tpu.models import ANNModel
    from distributed_learning_tpu.parallel.topology import Topology

    rng = np.random.default_rng(2)
    n, d = 3, 6
    train = {
        i: (
            rng.normal(size=(32, d)).astype(np.float32),
            rng.integers(0, 2, size=(32,)).astype(np.int32),
        )
        for i in range(n)
    }
    kw = dict(
        node_names=list(range(n)),
        model=ANNModel(hidden_dim=6, output_dim=2),
        weights=Topology.ring(n),
        train_data=train,
        batch_size=16,
        epoch=2,
        dropout=False,
        seed=3,
    )
    old = GossipTrainer(**kw)  # no compression: saves no choco subtree
    old.initialize_nodes()
    old.train_epoch()
    ckpt = str(tmp_path / "old-ckpt")
    old.save_checkpoint(ckpt)

    new = GossipTrainer(compression="topk:0.5", **kw)
    with _warnings.catch_warnings(record=True) as caught:
        _warnings.simplefilter("always")
        new.restore_checkpoint(ckpt)
    assert any("no CHOCO state" in str(w.message) for w in caught)
    assert new._epochs_done == 1 and new._choco_xhat is None
    new.train_epoch()  # and the resumed run still trains + mixes


def test_dense_trainer_restores_compressed_checkpoint(tmp_path):
    """The reverse compatibility direction: a compressed run's checkpoint
    (which carries a 'choco' subtree) restores into a dense trainer —
    training state loads, the estimates are ignored with a warning."""
    import warnings as _warnings

    from distributed_learning_tpu.models import ANNModel
    from distributed_learning_tpu.parallel.topology import Topology

    rng = np.random.default_rng(4)
    n, d = 3, 6
    train = {
        i: (
            rng.normal(size=(32, d)).astype(np.float32),
            rng.integers(0, 2, size=(32,)).astype(np.int32),
        )
        for i in range(n)
    }
    kw = dict(
        node_names=list(range(n)),
        model=ANNModel(hidden_dim=6, output_dim=2),
        weights=Topology.ring(n),
        train_data=train,
        batch_size=16,
        epoch=2,
        dropout=False,
        seed=3,
    )
    comp = GossipTrainer(compression="topk:0.5", **kw)
    comp.initialize_nodes()
    comp.train_epoch()
    ckpt = str(tmp_path / "comp-ckpt")
    comp.save_checkpoint(ckpt)

    dense = GossipTrainer(**kw)
    with _warnings.catch_warnings(record=True) as caught:
        _warnings.simplefilter("always")
        dense.restore_checkpoint(ckpt)
    assert any("estimates are ignored" in str(w.message) for w in caught)
    assert dense._epochs_done == 1
    for a, b in zip(
        jax.tree.leaves(comp.state[0]), jax.tree.leaves(dense.state[0])
    ):
        np.testing.assert_array_equal(np.asarray(a), np.asarray(b))
    dense.train_epoch()


def test_shard_truncation_warnings_distinguish_imbalance():
    """Balanced-but-unaligned shards warn about batch-grid truncation
    (samples ARE dropped), imbalanced shards warn about imbalance; the
    old message called equal shards 'imbalanced'."""
    import warnings as _warnings

    def build(lens):
        rng = np.random.default_rng(0)
        train = {
            i: (
                rng.normal(size=(ln, 4)).astype(np.float32),
                rng.integers(0, 2, size=(ln,)).astype(np.int32),
            )
            for i, ln in enumerate(lens)
        }
        with _warnings.catch_warnings(record=True) as caught:
            _warnings.simplefilter("always")
            GossipTrainer(
                node_names=list(range(len(lens))),
                model="mlp",
                model_kwargs={"hidden_dim": 4, "output_dim": 2},
                train_data=train,
                batch_size=16,
                dropout=False,
            )
        return [str(w.message) for w in caught]

    balanced = build([100, 100, 100])  # truncated to 96, equal shards
    assert any("not a multiple" in m for m in balanced), balanced
    assert not any("imbalanced" in m for m in balanced), balanced

    imbalanced = build([100, 120, 100])
    assert any("imbalanced" in m for m in imbalanced), imbalanced

    aligned = build([96, 96, 96])  # nothing dropped: silent
    assert not any(
        "truncat" in m or "imbalanced" in m for m in aligned
    ), aligned


def test_choco_compressed_mixing_trains_and_converges():
    """CHOCO-SGD through the trainer: compression='topk:0.3' gossips only
    compressed corrections between epochs; deviation still shrinks and
    training matches dense gossip closely."""
    from distributed_learning_tpu.models import ANNModel
    from distributed_learning_tpu.parallel.topology import Topology

    rng = np.random.default_rng(0)
    n, d = 4, 8
    train = {
        i: (
            rng.normal(size=(64, d)).astype(np.float32),
            rng.integers(0, 3, size=(64,)).astype(np.int32),
        )
        for i in range(n)
    }
    kw = dict(
        node_names=list(range(n)),
        model=ANNModel(hidden_dim=8, output_dim=3),
        optimizer="sgd",
        learning_rate=0.05,
        error="cross_entropy",
        weights=Topology.ring(n),
        train_data=train,
        batch_size=16,
        stat_step=2,
        epoch=4,
        dropout=False,
        seed=0,
    )
    dense = GossipTrainer(mix_times=4, **kw)
    dense.initialize_nodes()
    dense_out = [dense.train_epoch() for _ in range(4)]

    choco = GossipTrainer(
        mix_times=4, compression="topk:0.3", compression_gamma=0.3, **kw
    )
    choco.initialize_nodes()
    choco_out = [choco.train_epoch() for _ in range(4)]

    assert all(o["mixed"] for o in choco_out)
    # Deviation must shrink epoch-over-epoch despite compressed gossip,
    # and training loss must track the dense run to first-decimal level.
    assert choco_out[-1]["deviation"] < choco_out[0]["deviation"]
    dl = float(np.mean(np.asarray(dense_out[-1]["train_loss"])))
    cl = float(np.mean(np.asarray(choco_out[-1]["train_loss"])))
    assert abs(dl - cl) < 0.15, (dl, cl)
    # Estimates persist across epochs (set after the first mixing epoch).
    assert choco._choco_xhat is not None


def test_choco_fused_matches_perleaf_through_trainer_donate_on_off():
    """ISSUE 5 acceptance: CHOCO training with the fused whole-buffer
    compressor (fused_consensus=True, budget='per-leaf') tracks the
    per-leaf oracle (fused_consensus=False) at GEMM-accumulation
    tolerance — compressed values are bit-identical, only the mixing
    product's accumulation order differs — under donate_state on AND off
    (donation is inert on CPU but the config path must not perturb the
    carry)."""
    from distributed_learning_tpu.models import ANNModel
    from distributed_learning_tpu.parallel.topology import Topology

    rng = np.random.default_rng(1)
    n, d = 4, 6
    train = {
        i: (
            rng.normal(size=(32, d)).astype(np.float32),
            rng.integers(0, 3, size=(32,)).astype(np.int32),
        )
        for i in range(n)
    }
    kw = dict(
        node_names=list(range(n)),
        model=ANNModel(hidden_dim=8, output_dim=3),
        optimizer="sgd",
        learning_rate=0.05,
        error="cross_entropy",
        weights=Topology.ring(n),
        train_data=train,
        batch_size=16,
        epoch=2,
        dropout=False,
        seed=0,
        mix_times=3,
        compression="topk:0.3",
        compression_gamma=0.3,
    )
    for donate in (True, False):
        runs = {}
        for fused in (True, False):
            tr = GossipTrainer(
                fused_consensus=fused, donate_state=donate, **kw
            )
            tr.initialize_nodes()
            for _ in range(3):
                tr.train_epoch()
            runs[fused] = (tr.state[0], tr._choco_xhat)
        for a, b in zip(
            jax.tree.leaves(runs[True][0]), jax.tree.leaves(runs[False][0])
        ):
            np.testing.assert_allclose(
                np.asarray(a, np.float64), np.asarray(b, np.float64),
                rtol=2e-5, atol=2e-6, err_msg=f"donate={donate}",
            )
        for a, b in zip(
            jax.tree.leaves(runs[True][1]), jax.tree.leaves(runs[False][1])
        ):
            np.testing.assert_allclose(
                np.asarray(a, np.float64), np.asarray(b, np.float64),
                rtol=2e-5, atol=2e-6, err_msg=f"donate={donate} xhat",
            )


def test_choco_exclusive_with_other_mixing_modes():
    from distributed_learning_tpu.models import ANNModel
    from distributed_learning_tpu.parallel.topology import Topology

    rng = np.random.default_rng(0)
    train = {
        i: (
            rng.normal(size=(16, 4)).astype(np.float32),
            rng.integers(0, 2, size=(16,)).astype(np.int32),
        )
        for i in range(2)
    }
    kw = dict(
        node_names=[0, 1],
        model=ANNModel(hidden_dim=4, output_dim=2),
        weights=Topology.ring(2),
        train_data=train,
        batch_size=8,
        dropout=False,
    )
    with pytest.raises(ValueError, match="exclusive"):
        GossipTrainer(compression="sign", chebyshev=True, **kw)
    with pytest.raises(ValueError, match="exclusive"):
        GossipTrainer(compression="sign", mix_eps=1e-4, **kw)
    with pytest.raises(ValueError, match="unknown compressor"):
        GossipTrainer(compression="nonsense:9", **kw)


def test_compression_none_means_dense_gossip():
    """Trainer-level 'none' disables CHOCO entirely (a CLI override for a
    saved config) — it must NOT run gamma-damped identity-CHOCO."""
    from distributed_learning_tpu.models import ANNModel
    from distributed_learning_tpu.parallel.topology import Topology

    rng = np.random.default_rng(0)
    train = {
        i: (
            rng.normal(size=(16, 4)).astype(np.float32),
            rng.integers(0, 2, size=(16,)).astype(np.int32),
        )
        for i in range(2)
    }
    t = GossipTrainer(
        node_names=[0, 1],
        model=ANNModel(hidden_dim=4, output_dim=2),
        weights=Topology.ring(2),
        train_data=train,
        batch_size=8,
        dropout=False,
        compression="none",
        chebyshev=True,  # would raise if compression were considered active
    )
    assert t._choco is None


def test_compression_none_with_arg_still_disables():
    from distributed_learning_tpu.models import ANNModel
    from distributed_learning_tpu.parallel.topology import Topology

    rng = np.random.default_rng(0)
    train = {0: (rng.normal(size=(16, 4)).astype(np.float32),
                 rng.integers(0, 2, size=(16,)).astype(np.int32)),
             1: (rng.normal(size=(16, 4)).astype(np.float32),
                 rng.integers(0, 2, size=(16,)).astype(np.int32))}
    t = GossipTrainer(
        node_names=[0, 1], model=ANNModel(hidden_dim=4, output_dim=2),
        weights=Topology.ring(2), train_data=train, batch_size=8,
        dropout=False, compression="none:0",
    )
    assert t._choco is None
    # Compression + a round schedule used to be rejected (the CHOCO hat
    # update assumed a static round count); the superstep lift made the
    # round count traced data, so the combination now constructs — the
    # bit-identity oracle for it lives in the superstep config matrix.
    t2 = GossipTrainer(
        node_names=[0, 1], model=ANNModel(hidden_dim=4, output_dim=2),
        weights=Topology.ring(2), train_data=train, batch_size=8,
        dropout=False, compression="sign",
        mix_times_schedule=lambda e: 1 + e,
    )
    assert t2._choco is not None


def test_fused_consensus_matches_perleaf_oracle():
    """fused_consensus=True (default) trains identically to the per-leaf
    gossip programs — same losses, same deviations, same final accuracy —
    with donate_state=True (the default) and an eps-stopping mix so the
    fused while_loop's residual drives the round count too."""
    rng = np.random.default_rng(0)
    X = rng.normal(size=(450, 784)).astype(np.float32)
    w = rng.normal(size=(784, 10)).astype(np.float32)
    y = (X @ w).argmax(-1).astype(np.int32)
    shards = {
        i: (X[i * 150 : (i + 1) * 150], y[i * 150 : (i + 1) * 150])
        for i in range(3)
    }
    kwargs = dict(
        node_names=[0, 1, 2],
        model="ann",
        model_kwargs={"hidden_dim": 32, "output_dim": 10},
        weights=np.full((3, 3), 1 / 3),
        train_data=shards,
        epoch=2,
        epoch_len=2,
        batch_size=50,
        learning_rate=0.05,
        mix_eps=1e-5,
        donate_state=True,
        seed=4,
    )
    runs = {}
    for fused in (True, False):
        t = GossipTrainer(fused_consensus=fused, **kwargs)
        assert t.engine.fused is fused
        runs[fused] = t.start_consensus()
    for rf, rp in zip(runs[True], runs[False]):
        np.testing.assert_allclose(
            rf["train_loss"], rp["train_loss"], rtol=1e-5, atol=1e-6
        )
        np.testing.assert_allclose(
            rf["deviation"], rp["deviation"], rtol=1e-4, atol=1e-6
        )
        assert rf["mix_rounds"] == rp["mix_rounds"]


# --------------------------------------------------------------------- #
# Epoch superstep (train_epochs): K epochs in one donated dispatch      #
# --------------------------------------------------------------------- #
def _superstep_data(n=3, d=8, seed=0):
    rng = np.random.default_rng(seed)
    return {
        i: (
            rng.normal(size=(48, d)).astype(np.float32),
            rng.integers(0, 3, size=(48,)).astype(np.int32),
        )
        for i in range(n)
    }


def _superstep_kwargs(train, **overrides):
    kw = dict(
        node_names=sorted(train),
        model="mlp",
        model_kwargs={"hidden_dim": 8, "output_dim": 3},
        weights=np.full((len(train),) * 2, 1.0 / len(train)),
        train_data=train,
        batch_size=8,
        epoch_len=2,
        stat_step=2,
        dropout=False,
        learning_rate=0.05,
        optimizer="sgd",
        optimizer_kwargs={"momentum": 0.9},
        seed=7,
    )
    kw.update(overrides)
    return kw


def _assert_states_equal(a, b, label=""):
    ka = (a[0], a[1], a[2], jax.random.key_data(a[3]))
    kb = (b[0], b[1], b[2], jax.random.key_data(b[3]))
    for la, lb in zip(jax.tree.leaves(ka), jax.tree.leaves(kb)):
        np.testing.assert_array_equal(
            np.asarray(la), np.asarray(lb), err_msg=label
        )


def test_superstep_bit_identical_to_per_epoch_loop():
    """The superstep oracle at maximal strength: ``train_epochs(K)`` is
    BIT-identical (params, opt state, losses/accs/grad-norms, per-epoch
    round counts) to K calls of ``train_epoch`` on every compiled gossip
    path — plain mix, eps-stopping, Chebyshev, Gossip-PGA — under both
    fused layouts; ``donate_state`` toggles across the configs (inert on
    the CPU harness, where donation is disabled, but the flag plumbs
    through the same jit construction)."""
    from distributed_learning_tpu.parallel.topology import Topology

    train = _superstep_data()
    configs = [
        ("plain", dict(mix_times=2), True),
        ("eps", dict(mix_eps=1e-4, mix_times=1), False),
        ("cheby", dict(chebyshev=True, mix_times=3,
                       weights=Topology.ring(3)), True),
        ("gavg", dict(mix_times=1, global_avg_every=2,
                      epoch_cons_num=2), False),
    ]
    k = 3
    for name, cfg, donate in configs:
        for fused in (True, False):
            kw = _superstep_kwargs(
                train, fused_consensus=fused, donate_state=donate, **cfg
            )
            ref = GossipTrainer(**kw)
            ref.initialize_nodes()
            ref_out = [ref.train_epoch() for _ in range(k)]
            sup = GossipTrainer(**kw)
            sup.initialize_nodes()
            sup_out = sup.train_epochs(k)
            label = f"{name} fused={fused}"
            _assert_states_equal(ref.state, sup.state, label)
            assert len(sup_out) == k
            for ro, so in zip(ref_out, sup_out):
                for key in ("train_loss", "train_acc", "grad_norm"):
                    np.testing.assert_array_equal(
                        np.asarray(ro[key]), np.asarray(so[key]),
                        err_msg=f"{label} {key}",
                    )
                assert ro["mix_rounds"] == so["mix_rounds"], label
                assert ro["mixed"] == so["mixed"], label
                assert so["epoch"] == ro["epoch"]
            # Per-epoch residual reporting: the superstep's scan ys
            # carry every epoch's deviation (it is also the adaptive
            # controller's feedback signal) and each reading matches
            # the per-epoch loop's bitwise in float32.
            for ro, so in zip(ref_out, sup_out):
                assert so["deviation"] is not None, label
                assert np.float32(so["deviation"]) == np.float32(
                    ro["deviation"]
                ), label
            # And the per-node stat curves are the same points.
            for nm in kw["node_names"]:
                assert (
                    ref.network[nm].stats.train_loss
                    == sup.network[nm].stats.train_loss
                ), label


def test_superstep_respects_epoch_cons_num_boundary():
    """A superstep spanning the epoch_cons_num boundary gates gossip per
    epoch inside the compiled program, exactly like the host-side loop."""
    train = _superstep_data(seed=3)
    kw = _superstep_kwargs(train, mix_times=2, epoch_cons_num=3)
    ref = GossipTrainer(**kw)
    ref.initialize_nodes()
    ref_out = [ref.train_epoch() for _ in range(4)]
    sup = GossipTrainer(**kw)
    sup.initialize_nodes()
    sup_out = sup.train_epochs(4)
    assert [o["mixed"] for o in sup_out] == [False, False, True, True]
    assert [o["mix_rounds"] for o in sup_out] == [0, 0, 2, 2]
    _assert_states_equal(ref.state, sup.state, "cons_num boundary")
    for ro, so in zip(ref_out, sup_out):
        np.testing.assert_array_equal(
            np.asarray(ro["train_loss"]), np.asarray(so["train_loss"])
        )


def test_superstep_checkpoint_boundary_resumes_bit_identically():
    """save_checkpoint at a superstep boundary + restore into a fresh
    trainer resumes the superstep trajectory bit-identically to the
    per-epoch loop (the state layout is superstep-agnostic)."""
    train = _superstep_data(seed=5)
    kw = _superstep_kwargs(train, mix_times=2, superstep=2)
    import tempfile

    ref = GossipTrainer(**kw)
    ref.initialize_nodes()
    ref_out = [ref.train_epoch() for _ in range(4)]

    t1 = GossipTrainer(**kw)
    t1.initialize_nodes()
    t1.train_epochs(2)
    with tempfile.TemporaryDirectory() as tmp:
        ckpt = tmp + "/superstep-ckpt"
        t1.save_checkpoint(ckpt)
        t2 = GossipTrainer(**kw)
        t2.initialize_nodes()
        t2.restore_checkpoint(ckpt)
        assert t2._epochs_done == 2
        out = t2.train_epochs(2)
    assert [o["epoch"] for o in out] == [2, 3]
    _assert_states_equal(ref.state, t2.state, "checkpoint resume")
    np.testing.assert_array_equal(
        np.asarray(ref_out[-1]["train_loss"]),
        np.asarray(out[-1]["train_loss"]),
    )


def _assert_trees_equal(a, b, label=""):
    """Bitwise equality over pytrees that may carry PRNG-key leaves."""
    la, lb = jax.tree.leaves(a), jax.tree.leaves(b)
    assert len(la) == len(lb), label
    for va, vb in zip(la, lb):
        if hasattr(va, "dtype") and jax.dtypes.issubdtype(
            va.dtype, jax.dtypes.prng_key
        ):
            va, vb = jax.random.key_data(va), jax.random.key_data(vb)
        np.testing.assert_array_equal(
            np.asarray(va), np.asarray(vb), err_msg=label
        )


def test_superstep_compiles_schedule_choco_async_robust_configs():
    """The ISSUE 20 lift, at oracle strength: the previously
    chunk-hostile configs — per-epoch round/topology schedules, CHOCO
    compression (fused and per-leaf), async gossip (including a
    per-epoch staleness-bound schedule), robust mixing, and their
    compositions — now compile INTO the superstep.  ``train_epochs(K)``
    is bit-identical to K calls of ``train_epoch`` (params, opt state,
    losses/accs/grad-norms, per-epoch round counts and residuals, the
    CHOCO hat/key carry and the async double-buffer carry), and NO
    fallback warning is emitted — there is no fallback left."""
    import warnings as _warnings

    from distributed_learning_tpu.parallel.topology import Topology

    train = _superstep_data(seed=6)
    ring = Topology.ring(3)
    configs = [
        ("sched", dict(
            weights=ring, mix_times_schedule=lambda e: 1 + (e % 2),
        ), True),
        ("topo", dict(
            weights=ring,
            topology_schedule=lambda e: (
                ring if e % 2 == 0 else Topology.star(3)
            ),
        ), False),
        ("choco", dict(
            weights=ring, compression="top_k:0.5", compression_gamma=0.3,
        ), True),
        ("async", dict(
            weights=ring,
            async_gossip={"staleness_bound": lambda e: e % 3,
                          "publish_period": [1, 2, 1]},
        ), False),
        ("robust", dict(
            weights=ring, robust_mixing={"kind": "clip", "radius": 0.05},
        ), True),
        ("async+robust+sched", dict(
            weights=ring,
            async_gossip={"staleness_bound": 2,
                          "publish_period": [1, 2, 1]},
            robust_mixing={"kind": "trim", "trim": 1},
            mix_times_schedule=lambda e: 1 + (e % 2),
        ), False),
    ]
    k = 3
    # fused=False re-runs only where per-leaf gossip is a genuinely
    # different program (CHOCO's per-leaf selection, the composition's
    # per-leaf async/robust route) — the other configs' fused/per-leaf
    # split is the plain oracle's, covered above.
    perleaf_too = {"choco", "async+robust+sched"}
    for name, cfg, donate in configs:
        for fused in ((True, False) if name in perleaf_too else (True,)):
            kw = _superstep_kwargs(
                train, mix_times=2, fused_consensus=fused,
                donate_state=donate, **cfg,
            )
            ref = GossipTrainer(**kw)
            ref.initialize_nodes()
            ref_out = [ref.train_epoch() for _ in range(k)]
            sup = GossipTrainer(**kw)
            sup.initialize_nodes()
            with _warnings.catch_warnings(record=True) as caught:
                _warnings.simplefilter("always")
                sup_out = sup.train_epochs(k)
            msgs = [str(w.message) for w in caught
                    if "superstep" in str(w.message)]
            assert msgs == [], msgs
            label = f"{name} fused={fused}"
            _assert_states_equal(ref.state, sup.state, label)
            for ro, so in zip(ref_out, sup_out):
                for key in ("train_loss", "train_acc", "grad_norm"):
                    np.testing.assert_array_equal(
                        np.asarray(ro[key]), np.asarray(so[key]),
                        err_msg=f"{label} {key}",
                    )
                assert ro["mix_rounds"] == so["mix_rounds"], label
                assert ro["mixed"] == so["mixed"], label
                assert so["deviation"] is not None, label
                assert np.float32(so["deviation"]) == np.float32(
                    ro["deviation"]
                ), label
            # Cross-superstep gossip carries land back in the host
            # mirrors bit-identically (next superstep resumes exactly).
            if "compression" in cfg:
                assert sup._choco_xhat is not None, label
                _assert_trees_equal(
                    ref._choco_xhat, sup._choco_xhat, f"{label} xhat"
                )
                _assert_trees_equal(
                    ref._choco_key, sup._choco_key, f"{label} key"
                )
            if "async_gossip" in cfg:
                assert sup._async_state is not None, label
                _assert_trees_equal(
                    ref._async_state, sup._async_state, f"{label} async"
                )


def test_superstep_robust_mass_and_rounds_metrics_match_per_epoch():
    """The robust redirected-mass scalar and the rounds-run counter
    materialize from the superstep's scan ys into the SAME obs-registry
    series/counters the per-epoch loop records — cumulative values
    equal to float32."""
    from distributed_learning_tpu.obs import MetricsRegistry
    from distributed_learning_tpu.parallel.topology import Topology

    train = _superstep_data(seed=12)
    cfg = dict(
        weights=Topology.ring(3),
        robust_mixing={"kind": "clip", "radius": 0.05},
        mix_times_schedule=lambda e: 1 + (e % 2),
    )
    regs = {}
    for mode in ("per-epoch", "superstep"):
        regs[mode] = MetricsRegistry()
        tr = GossipTrainer(
            **_superstep_kwargs(train, mix_times=2, obs=regs[mode], **cfg)
        )
        tr.initialize_nodes()
        if mode == "per-epoch":
            for _ in range(3):
                tr.train_epoch()
        else:
            tr.train_epochs(3)
    snaps = {m: r.snapshot() for m, r in regs.items()}
    for key in ("consensus.rounds_run", "consensus.robust.clipped_mass"):
        a = snaps["per-epoch"]["counters"][key]
        b = snaps["superstep"]["counters"][key]
        assert np.float32(a) == np.float32(b), (key, a, b)
    assert snaps["superstep"]["counters"][
        "consensus.robust.clipped_mass"
    ] >= 0.0


def test_superstep_and_epoch_donation_alias_every_state_buffer():
    """Buffer-donation guard: donating the carried state into the
    superstep (and the per-epoch program) aliases EVERY state leaf to an
    output — no 'donated buffer not used' warnings, no un-donated copies
    — proven via .lower()/.compile() input-output aliasing (the CPU
    harness never executes donation, so the lowering is the testable
    surface)."""
    import warnings as _warnings

    train = _superstep_data(seed=8)
    kw = _superstep_kwargs(train, mix_times=2)
    tr = GossipTrainer(**kw)
    tr.initialize_nodes()
    k = 2
    idx = tr._superstep_indices(0, k)
    modes = jnp.asarray(
        [tr._epoch_mode(j) for j in range(k)], dtype=jnp.int32
    )
    gcarry = tr._superstep_carry()
    sched = tr._superstep_sched(0, k)
    n_leaves = len(jax.tree.leaves((tr.state, gcarry)))

    with _warnings.catch_warnings(record=True) as caught:
        _warnings.simplefilter("always")
        lowered = jax.jit(
            tr._make_superstep_fn(k), donate_argnums=(0, 1)
        ).lower(tr.state, gcarry, tr._Xs, tr._ys, idx, modes, sched)
        compiled = lowered.compile()
        ep_lowered = jax.jit(tr._epoch_fn, donate_argnums=(0,)).lower(
            tr.state, tr._Xs, tr._ys, tr._epoch_indices(0)
        )
        ep_compiled = ep_lowered.compile()
    donation_warnings = [
        str(w.message) for w in caught if "donat" in str(w.message).lower()
    ]
    assert donation_warnings == [], donation_warnings
    # Every donated state AND gossip-carry leaf is aliased to an output
    # buffer (the carry rides the scan across supersteps).
    assert lowered.as_text().count("tf.aliasing_output") == n_leaves
    assert ep_lowered.as_text().count("tf.aliasing_output") == len(
        jax.tree.leaves(tr.state)
    )
    # And the aliasing survives compilation (the buffers are reused in
    # place — the donated inputs are dead after the call).
    assert "alias" in compiled.as_text()
    assert "alias" in ep_compiled.as_text()


def test_superstep_adaptive_comm_neutral_identity_and_modulation():
    """The residual-adaptive controller: at neutral knobs (gain=0) the
    adaptive trainer is BIT-identical to the static config — the
    controller compiles to an exact identity.  With gain>0 the
    superstep matches the per-epoch host mirror bitwise AND the
    per-epoch round counts actually move away from the static budget
    (the residual feedback engages, arXiv:1910.13598)."""
    from distributed_learning_tpu.parallel.topology import Topology

    train = _superstep_data(seed=11)
    base_kw = _superstep_kwargs(train, weights=Topology.ring(3),
                                mix_times=2)
    k = 3
    static = GossipTrainer(**base_kw)
    static.initialize_nodes()
    static_out = static.train_epochs(k)
    neutral = GossipTrainer(
        **base_kw, adaptive_comm={"target": 0.05, "gain": 0.0}
    )
    neutral.initialize_nodes()
    neutral_out = neutral.train_epochs(k)
    _assert_states_equal(static.state, neutral.state, "adaptive neutral")
    assert [o["mix_rounds"] for o in static_out] == [
        o["mix_rounds"] for o in neutral_out
    ]

    adaptive = {"target": 1e-3, "gain": 1.0, "max_times": 6}
    kw = dict(base_kw, adaptive_comm=adaptive)
    ref = GossipTrainer(**kw)
    ref.initialize_nodes()
    ref_out = [ref.train_epoch() for _ in range(k)]
    sup = GossipTrainer(**kw)
    sup.initialize_nodes()
    sup_out = sup.train_epochs(k)
    _assert_states_equal(ref.state, sup.state, "adaptive gain=1")
    rounds = [o["mix_rounds"] for o in sup_out]
    assert rounds == [o["mix_rounds"] for o in ref_out]
    # target far below the early-training residual -> the controller
    # raises the budget above the static 2 (capped at max_times).
    assert any(r != 2 for r in rounds), rounds
    assert all(1 <= r <= 6 for r in rounds), rounds


def test_superstep_choco_error_feedback_oracle_and_banking():
    """CHOCO error feedback (arXiv:1901.09847) under the global fused
    budget: superstep vs per-epoch oracle holds bitwise, the EF bank is
    non-zero after training (the compressor drops mass and the bank
    keeps it), and the knob refuses the per-leaf/non-fused layouts it
    cannot serve."""
    from distributed_learning_tpu.parallel.topology import Topology

    train = _superstep_data(seed=13)
    cfg = dict(
        weights=Topology.ring(3),
        compression="top_k:0.5",
        compression_gamma=0.3,
        compression_budget="global",
        compression_error_feedback=True,
    )
    kw = _superstep_kwargs(train, mix_times=2, **cfg)
    ref = GossipTrainer(**kw)
    ref.initialize_nodes()
    for _ in range(3):
        ref.train_epoch()
    sup = GossipTrainer(**kw)
    sup.initialize_nodes()
    sup.train_epochs(3)
    _assert_states_equal(ref.state, sup.state, "choco ef")
    _assert_trees_equal(ref._choco_ef, sup._choco_ef, "ef bank")
    assert sup._choco_ef is not None
    assert any(
        float(np.abs(np.asarray(v)).max()) > 0.0
        for v in jax.tree.leaves(sup._choco_ef)
    ), "EF bank never accumulated anything"
    with pytest.raises(ValueError, match="error_feedback"):
        GossipTrainer(**{**kw, "fused_consensus": False,
                         "compression_budget": "per-leaf"})


def test_superstep_single_node_and_start_consensus_chunking():
    """superstep=K through start_consensus: the schedule runs in chunks
    of K with a short final chunk, epochs/indices line up with the
    per-epoch loop, and a single-node trainer (never mixes) supersteps
    too."""
    train = _superstep_data(seed=9)
    kw = _superstep_kwargs(train, mix_times=1, epoch=5, superstep=2)
    ref = GossipTrainer(**{**kw, "superstep": 1})
    ref_out = ref.start_consensus()
    sup = GossipTrainer(**kw)
    sup_out = sup.start_consensus()
    assert [o["epoch"] for o in sup_out] == [0, 1, 2, 3, 4]
    _assert_states_equal(ref.state, sup.state, "start_consensus chunks")
    for ro, so in zip(ref_out, sup_out):
        np.testing.assert_array_equal(
            np.asarray(ro["train_loss"]), np.asarray(so["train_loss"])
        )

    solo = {0: train[0]}
    t = GossipTrainer(**_superstep_kwargs(
        solo, weights=None, mix_times=1, superstep=2, epoch=2
    ))
    out = t.start_consensus()
    assert [o["mixed"] for o in out] == [False, False]
    assert all(o["mix_rounds"] == 0 for o in out)


# The four configs the superstep lift compiled in (schedules as traced
# data, CHOCO / async / robust state as scan carries), on a 4-node ring.
_LIFTED = {
    "plain": {},
    "choco": {"compression": "top_k:0.5", "compression_gamma": 0.3},
    "sched": {"mix_times_schedule": lambda e: 1 + (e % 2)},
    "async": {"async_gossip": {"staleness_bound": 2,
                               "publish_period": [1, 2, 1, 3]}},
    "robust": {"robust_mixing": {"kind": "clip", "radius": 0.1}},
}


@pytest.mark.parametrize(
    "k, config",
    [(2, "plain"), (4, "plain"), (16, "plain"),
     (4, "choco"), (4, "sched"), (4, "async"), (4, "robust")],
)
def test_train_epochs_is_one_dispatch_a_call(k, config):
    """The superstep's headline claim, off the counter that counts it:
    ``train_epochs(k)`` launches ONE train-path program whatever k and
    whatever the gossip config (1/k dispatches an epoch), where the
    per-epoch path launches three an epoch (epoch program, gossip,
    deviation read-out: ``tests/test_profile_names.py``)."""
    from distributed_learning_tpu.obs import MetricsRegistry
    from distributed_learning_tpu.parallel.topology import Topology

    reg = MetricsRegistry()
    tr = GossipTrainer(**_superstep_kwargs(
        _superstep_data(n=4, seed=10), weights=Topology.ring(4),
        mix_times=1, test_data=None, obs=reg, **_LIFTED[config],
    ))
    tr.initialize_nodes()
    calls = 2
    for _ in range(calls):
        out = tr.train_epochs(k)
        assert len(out) == k and all(o["mixed"] for o in out)
    assert reg.counters["trainer.dispatches"] == calls
    assert tr._epochs_done == calls * k


def test_gossip_recovers_centralized_accuracy_on_label_sorted_titanic():
    """The decentralized claim on real data under the hard sharding,
    through the trainer: Titanic rows sorted by label before they are
    dealt (two nodes see only casualties, one only survivors), every arm
    at the same budget and seed.  Nodes that never mix score far below
    the ring that mixes every step, and the ring lands in the
    centralized run's ballpark."""
    from distributed_learning_tpu.data import load_titanic, split_data
    from distributed_learning_tpu.parallel.topology import Topology

    X_tr, y_tr, X_te, y_te = load_titanic()
    order = np.argsort(y_tr, kind="stable")
    n, steps = 4, 100
    skewed = split_data(X_tr[order], y_tr[order], n)
    assert sum(len(np.unique(y)) == 1 for _, y in skewed.values()) >= 3

    def final_test_acc(train, weights, **kw):
        import warnings

        with warnings.catch_warnings():
            # shards of 200/201 (and the union's 802) rows are cut to
            # whole batches of 32
            warnings.simplefilter("ignore", UserWarning)
            tr = GossipTrainer(
                node_names=sorted(train), model="ann",
                model_kwargs={"hidden_dim": 16, "output_dim": 1},
                error="binary_logistic", optimizer="sgd",
                learning_rate=0.05, weights=weights, train_data=train,
                test_data=(X_te, y_te), epoch=steps, epoch_len=1,
                batch_size=32, mix_times=1, stat_step=1000, dropout=False,
                seed=0, **kw,
            )
        tr.initialize_nodes()
        return float(np.mean(tr.train_epochs(steps)[-1]["test_acc"]))

    ring = Topology.ring(n)
    isolated = final_test_acc(skewed, ring, epoch_cons_num=10**6)
    gossip = final_test_acc(skewed, ring)
    centralized = final_test_acc({0: (X_tr, y_tr)}, None)
    assert isolated < gossip - 0.05, (isolated, gossip)
    assert abs(gossip - centralized) < 0.1, (gossip, centralized)
