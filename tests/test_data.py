"""Data-pipeline tests: Titanic prep/split parity, CIFAR shapes/augmentation."""

import os

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from distributed_learning_tpu.data import (
    FEATURES,
    augment_batch,
    load_cifar,
    load_titanic,
    normalize,
    shard_dataset,
    split_data,
    synthetic_cifar,
    synthetic_titanic,
)

_REFERENCE_TITANIC = os.path.isdir("/root/reference/data/titanic")


def test_titanic_features_schema():
    X_tr, y_tr, X_te, y_te = load_titanic()
    assert X_tr.shape[1] == len(FEATURES) == 7
    assert set(np.unique(y_tr)) <= {-1, 1}
    # bias column is last and all ones
    np.testing.assert_array_equal(X_tr[:, -1], 1.0)
    # Sex is +-1, Age/Fare scaled to <~1
    assert set(np.unique(X_tr[:, 1])) <= {-1.0, 1.0}
    assert np.abs(X_tr[:, 2]).max() <= 1.0


@pytest.mark.skipif(not _REFERENCE_TITANIC, reason="reference CSVs not present")
def test_titanic_real_csv_layout():
    # 891 rows total, first 10% (89) held out as common test (notebook cell 4).
    X_tr, y_tr, X_te, y_te = load_titanic("/root/reference/data/titanic")
    assert len(X_tr) + len(X_te) == 891
    assert len(X_te) == 89


def test_titanic_source_reports_real_or_synthetic(tmp_path):
    from distributed_learning_tpu.data import titanic_source

    # Explicit missing dir -> synthetic fallback is disclosed.
    assert titanic_source(str(tmp_path / "nope")) == "synthetic"
    # A dir with train.csv -> real, naming the dir.
    d = tmp_path / "titanic"
    d.mkdir()
    (d / "train.csv").write_text("PassengerId,Survived\n")
    assert titanic_source(str(d)) == f"real:{d}"


def test_split_data_contiguous_near_equal():
    # Parity: notebook cell 12 — remainder rows land on the later shards.
    X = np.arange(802 * 2, dtype=np.float32).reshape(802, 2)
    y = np.ones(802, np.int32)
    shards = split_data(X, y, 5)
    sizes = [len(shards[i][0]) for i in range(5)]
    assert sizes == [160, 160, 160, 161, 161]
    # Contiguity + disjointness: concatenation reproduces X exactly.
    np.testing.assert_array_equal(
        np.concatenate([shards[i][0] for i in range(5)]), X
    )


def test_split_data_token_names():
    X, y = synthetic_titanic(n=30)
    shards = split_data(X, y, ["Alice", "Bob", "Charlie"])
    assert set(shards) == {"Alice", "Bob", "Charlie"}
    assert sum(len(v[0]) for v in shards.values()) == 30


def test_synthetic_titanic_learnable():
    X, y = synthetic_titanic(n=600, seed=1)
    # Majority class under 70%: the signal is in the features, not the prior.
    assert 0.3 < np.mean(y == 1) < 0.7


def test_cifar_synthetic_shapes_and_determinism():
    (X1, y1), (Xt1, yt1) = synthetic_cifar(n_train=128, n_test=32, seed=7)
    (X2, y2), _ = synthetic_cifar(n_train=128, n_test=32, seed=7)
    assert X1.shape == (128, 32, 32, 3) and X1.dtype == np.uint8
    assert Xt1.shape == (32, 32, 32, 3)
    np.testing.assert_array_equal(X1, X2)
    assert set(np.unique(y1)) <= set(range(10))


def test_cifar100_label_range():
    (X, y), _ = synthetic_cifar("cifar100", n_train=256, n_test=16)
    assert y.max() >= 50  # plausibly spans 100 classes


def test_load_cifar_falls_back_to_synthetic():
    (X, y), (Xt, yt) = load_cifar("cifar10", data_dir="/nonexistent")
    assert X.shape[1:] == (32, 32, 3)


def test_normalize_range():
    x = jnp.full((2, 32, 32, 3), 128, jnp.uint8)
    out = normalize(x, "cifar10")
    assert out.dtype == jnp.float32
    assert float(jnp.abs(out).max()) < 1.0  # mid-gray is near the mean


def test_augment_batch_jittable_and_valid():
    rng = jax.random.key(0)
    x = jnp.asarray(
        np.random.default_rng(0).random((8, 32, 32, 3)), jnp.float32
    )
    aug = jax.jit(augment_batch)(rng, x)
    assert aug.shape == x.shape
    # Different keys give different crops; same key identical.
    aug2 = jax.jit(augment_batch)(rng, x)
    np.testing.assert_array_equal(np.asarray(aug), np.asarray(aug2))
    aug3 = jax.jit(augment_batch)(jax.random.key(1), x)
    assert not np.allclose(np.asarray(aug), np.asarray(aug3))


def test_shard_dataset_disjoint_and_batch_aligned():
    (X, y), _ = synthetic_cifar(n_train=1000, n_test=8)
    shards = shard_dataset(X, y, 4, batch_size=64, seed=3)
    total = 0
    for tok, (xs, ys) in shards.items():
        assert len(xs) % 64 == 0
        assert len(xs) == len(ys)
        total += len(xs)
    assert total <= 1000
    assert total >= 4 * 192  # near-equal shards of 250 -> 192 after trunc


def test_epoch_batches_covers_and_shuffles():
    from distributed_learning_tpu.data import epoch_batches

    X = np.arange(20, dtype=np.float32)[:, None]
    y = np.arange(20, dtype=np.int32)
    got = list(epoch_batches(X, y, 8, seed=0))
    # drop_remainder: 2 full batches of 8, 4 rows dropped.
    assert len(got) == 2 and all(b[0].shape == (8, 1) for b in got)
    seen = np.concatenate([b[1] for b in got])
    assert len(set(seen.tolist())) == 16          # no duplicates
    assert not np.array_equal(seen, np.arange(16))  # shuffled
    # x/y stay aligned through the permutation.
    for xb, yb in got:
        np.testing.assert_array_equal(xb[:, 0].astype(np.int32), yb)
    # Same seed -> same order; different seed -> different order.
    again = np.concatenate([b[1] for b in epoch_batches(X, y, 8, seed=0)])
    np.testing.assert_array_equal(seen, again)
    other = np.concatenate([b[1] for b in epoch_batches(X, y, 8, seed=1)])
    assert not np.array_equal(seen, other)


def test_prefetch_to_device_preserves_stream_and_shards():
    import jax
    from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

    from distributed_learning_tpu.data import (
        epoch_batches,
        prefetch_to_device,
    )

    X = np.random.default_rng(0).normal(size=(32, 4)).astype(np.float32)
    y = np.arange(32, dtype=np.int32)
    plain = list(epoch_batches(X, y, 8, seed=3))
    mesh = Mesh(np.array(jax.devices()[:8]), ("data",))
    sharding = NamedSharding(mesh, P("data"))
    fetched = list(prefetch_to_device(
        epoch_batches(X, y, 8, seed=3), size=2, sharding=sharding
    ))
    assert len(fetched) == len(plain)
    for (xa, ya), (xb, yb) in zip(plain, fetched):
        np.testing.assert_array_equal(xa, np.asarray(xb))
        np.testing.assert_array_equal(ya, np.asarray(yb))
        assert xb.sharding.spec == P("data")


def test_prefetch_propagates_source_errors():
    import pytest

    from distributed_learning_tpu.data import prefetch_to_device

    def bad():
        yield np.zeros(4)
        raise RuntimeError("source broke")

    it = prefetch_to_device(bad(), size=1)
    next(it)
    with pytest.raises(RuntimeError, match="source broke"):
        next(it)


def test_prefetch_releases_producer_on_early_break():
    import threading
    import time

    from distributed_learning_tpu.data import prefetch_to_device

    before = threading.active_count()

    def src():
        for i in range(100):
            yield np.full(4, i, np.float32)

    it = prefetch_to_device(src(), size=1)
    got = next(it)
    np.testing.assert_array_equal(np.asarray(got), np.zeros(4))
    it.close()  # the consumer walks away (generator finalized)
    deadline = time.time() + 5
    while threading.active_count() > before and time.time() < deadline:
        time.sleep(0.05)
    assert threading.active_count() <= before, "producer thread leaked"


# ---------------------------------------------------------------------------
# Non-IID partitioners (data/partition.py): seeded label-skew / size-skew.


def _partition_fixture(n=240, classes=4, seed=3):
    rng = np.random.default_rng(seed)
    X = rng.normal(size=(n, 5)).astype(np.float32)
    y = rng.integers(0, classes, size=n).astype(np.int32)
    return X, y


def _assert_disjoint_cover(shards, X, y):
    from collections import Counter

    rows = [tuple(np.round(xs_row, 6)) for _, (xs, _) in sorted(
        shards.items(), key=lambda kv: str(kv[0])
    ) for xs_row in xs]
    assert len(rows) == len(X)
    assert Counter(rows) == Counter(tuple(np.round(r, 6)) for r in X)
    for xs, ys in shards.values():
        assert len(xs) == len(ys)


def test_label_skew_shards_deterministic_and_covering():
    from distributed_learning_tpu.data import label_skew_shards

    X, y = _partition_fixture()
    a = label_skew_shards(X, y, ["A", "B", "C"], alpha=0.3, seed=7)
    b = label_skew_shards(X, y, ["A", "B", "C"], alpha=0.3, seed=7)
    assert set(a) == {"A", "B", "C"}
    for tok in a:
        np.testing.assert_array_equal(a[tok][0], b[tok][0])
        np.testing.assert_array_equal(a[tok][1], b[tok][1])
    _assert_disjoint_cover(a, X, y)
    # A different seed deals a different partition.
    c = label_skew_shards(X, y, ["A", "B", "C"], alpha=0.3, seed=8)
    assert any(
        a[t][0].shape != c[t][0].shape or not np.array_equal(a[t][0], c[t][0])
        for t in a
    )


def test_label_skew_small_alpha_concentrates_classes():
    from distributed_learning_tpu.data import label_skew_shards

    X, y = _partition_fixture(n=2000, classes=4, seed=0)
    skewed = label_skew_shards(X, y, 4, alpha=0.05, seed=1)
    iid = label_skew_shards(X, y, 4, alpha=1e4, seed=1)

    def max_class_frac(shards):
        fracs = []
        for _, ys in shards.values():
            counts = np.bincount(ys, minlength=4)
            fracs.append(counts.max() / max(1, counts.sum()))
        return float(np.mean(fracs))

    # Small alpha -> shards dominated by one class; huge alpha -> ~uniform.
    assert max_class_frac(skewed) > 0.6
    assert max_class_frac(iid) < 0.4


def test_label_skew_rejects_empty_agent():
    from distributed_learning_tpu.data import label_skew_shards

    X, y = _partition_fixture(n=12, classes=2)
    with pytest.raises(ValueError, match="min_per_agent|examples"):
        # 40 examples demanded per agent from 12 rows: must raise, not
        # silently hand back an undersized shard.
        label_skew_shards(X, y, 3, alpha=0.5, seed=0, min_per_agent=40)


def test_size_skew_shards_geometric_sizes_and_determinism():
    from distributed_learning_tpu.data import size_skew_shards

    X, y = _partition_fixture(n=210)
    a = size_skew_shards(X, y, 3, ratio=2.0, seed=5)
    b = size_skew_shards(X, y, 3, ratio=2.0, seed=5)
    for tok in a:
        np.testing.assert_array_equal(a[tok][0], b[tok][0])
        np.testing.assert_array_equal(a[tok][1], b[tok][1])
    _assert_disjoint_cover(a, X, y)
    sizes = [len(a[t][0]) for t in range(3)]
    assert sizes == sorted(sizes)  # geometric: later agents data-rich
    assert sizes[2] >= 3 * sizes[0]  # ratio 2 over 3 agents: 1:2:4
    # ratio=1 recovers the near-equal deal.
    eq = size_skew_shards(X, y, 3, ratio=1.0, seed=5)
    eq_sizes = sorted(len(eq[t][0]) for t in range(3))
    assert eq_sizes[-1] - eq_sizes[0] <= 1


def test_partitioners_batch_size_truncation():
    from distributed_learning_tpu.data import (
        label_skew_shards,
        size_skew_shards,
    )

    X, y = _partition_fixture(n=300)
    for shards in (
        label_skew_shards(X, y, 3, alpha=0.5, seed=2, batch_size=16),
        size_skew_shards(X, y, 3, ratio=1.5, seed=2, batch_size=16),
    ):
        for xs, ys in shards.values():
            assert len(xs) % 16 == 0
            assert len(xs) == len(ys)
