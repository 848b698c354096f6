"""Sketch algebra oracles (ISSUE 17): the fleet obs plane is only
sound if its summaries are *exactly* mergeable and their error bound
is real.

* merge is exact: associative, commutative, identity — byte-identical
  bucket state in any grouping/order (what makes
  aggregate-of-aggregates safe);
* every quantile reconstructs within the documented relative-error
  bound α against the exact nearest-rank oracle, on adversarial
  shapes (bimodal, heavy-tail, constant, signed);
* encoding is deterministic and round-trips byte-identically;
* the key clamp bounds the footprint under hostile inputs;
* :class:`LabelRollup` preserves total mass exactly while bounding
  cardinality, and discloses the fold.

Everything here is jax-free by design — the sketches run on the comm
control-plane host path.
"""

import json
import math

import numpy as np
import pytest

from distributed_learning_tpu.obs.sketch import (
    DEFAULT_ALPHA,
    LabelRollup,
    QuantileSketch,
)


def _pct_exact(vals, q):
    """Exact nearest-rank quantile (same rank convention as the
    sketch and ``aggregate._pct``)."""
    s = sorted(vals)
    rank = max(1, math.ceil(q * len(s)))
    return s[rank - 1]


def _sk(vals, alpha=DEFAULT_ALPHA):
    sk = QuantileSketch(alpha)
    sk.extend(float(v) for v in vals)
    return sk


_DISTRIBUTIONS = {
    "bimodal": lambda rng: np.concatenate([
        rng.normal(0.01, 0.001, 500), rng.normal(10.0, 1.0, 500),
    ]),
    "heavy_tail": lambda rng: rng.lognormal(mean=-3.0, sigma=1.5,
                                            size=1000),
    "constant": lambda rng: np.full(1000, 0.125),
    "signed": lambda rng: np.concatenate([
        -rng.lognormal(size=400), np.zeros(200), rng.lognormal(size=400),
    ]),
}


@pytest.mark.parametrize("dist", sorted(_DISTRIBUTIONS))
def test_quantile_within_alpha_of_exact_oracle(dist):
    rng = np.random.default_rng(17)
    vals = [float(v) for v in _DISTRIBUTIONS[dist](rng)]
    sk = _sk(vals)
    assert sk.n == len(vals)
    assert sk.min == min(vals) and sk.max == max(vals)
    assert sk.mean == pytest.approx(np.mean(vals), rel=1e-9)
    for q in (0.0, 0.01, 0.1, 0.25, 0.5, 0.75, 0.9, 0.95, 0.99, 1.0):
        truth = _pct_exact(vals, q) if 0.0 < q < 1.0 else (
            min(vals) if q == 0.0 else max(vals)
        )
        est = sk.quantile(q)
        if truth == 0.0:
            assert est == 0.0
        else:
            assert abs(est - truth) <= DEFAULT_ALPHA * abs(truth) + 1e-15, (
                dist, q, est, truth,
            )


def test_merge_exact_associative_commutative_identity():
    rng = np.random.default_rng(3)
    a = _sk(rng.lognormal(size=300))
    b = _sk(-rng.lognormal(size=200))
    c = _sk(np.concatenate([np.zeros(50), rng.normal(5.0, 1.0, 250)]))

    # Commutative: float sum a+b == b+a exactly (IEEE), buckets are
    # integer counts — full byte-identical state.
    ab = a.copy().merge(b)
    ba = b.copy().merge(a)
    assert ab == ba
    assert (json.dumps(ab.to_dict(), sort_keys=True)
            == json.dumps(ba.to_dict(), sort_keys=True))

    # Associative: bucket counts / n / min / max / zeros are exactly
    # grouping-independent; only the float `sum` may differ in the
    # last ulp across parenthesizations.
    left = a.copy().merge(b).merge(c)
    right = a.copy().merge(b.copy().merge(c))
    dl, dr = left.to_dict(), right.to_dict()
    assert dl.pop("sum") == pytest.approx(dr.pop("sum"), rel=1e-12)
    assert dl == dr
    for q in (0.05, 0.5, 0.95):
        assert left.quantile(q) == right.quantile(q)

    # Identity: merging an empty sketch changes nothing.
    before = json.dumps(a.to_dict(), sort_keys=True)
    a.merge(QuantileSketch())
    assert json.dumps(a.to_dict(), sort_keys=True) == before


def test_merge_order_determinism_across_ten_shards():
    rng = np.random.default_rng(11)
    shards = [_sk(rng.lognormal(size=100)) for _ in range(10)]
    fwd = QuantileSketch()
    for s in shards:
        fwd.merge(s)
    rev = QuantileSketch()
    for s in reversed(shards):
        rev.merge(s)
    df, dr = fwd.to_dict(), rev.to_dict()
    assert df.pop("sum") == pytest.approx(dr.pop("sum"), rel=1e-12)
    assert df == dr
    for q in (0.01, 0.5, 0.99):
        assert fwd.quantile(q) == rev.quantile(q)


def test_encode_roundtrip_is_byte_identical():
    rng = np.random.default_rng(5)
    sk = _sk(np.concatenate([
        rng.lognormal(size=200), -rng.lognormal(size=100), np.zeros(30),
    ]))
    wire = json.dumps(sk.to_dict(), sort_keys=True)
    back = QuantileSketch.from_dict(json.loads(wire))
    assert back == sk
    assert json.dumps(back.to_dict(), sort_keys=True) == wire
    # A second generation (merge of round-tripped halves) still
    # encodes identically to the direct merge.
    other = _sk(rng.lognormal(size=50))
    direct = sk.copy().merge(other)
    via_wire = QuantileSketch.from_dict(json.loads(wire)).merge(
        QuantileSketch.from_dict(other.to_dict())
    )
    assert direct == via_wire


def test_key_clamp_bounds_footprint_under_hostile_stream():
    sk = QuantileSketch()
    hostile = [1e300, 1e-300, 5e-324, 1.7e308, -1e300, -5e-324]
    for v in hostile:
        sk.add(v)
    assert all(abs(k) <= sk.key_bound for k in sk.buckets)
    assert all(abs(k) <= sk.key_bound for k in sk.neg)
    # Extremes stay exact even when buckets clamp.
    assert sk.min == -1e300 and sk.max == 1.7e308
    assert math.isfinite(sk.quantile(0.5))
    # The footprint is the number of touched (clamped) buckets, not
    # the value range.
    assert len(sk) <= len(hostile)


def test_bucket_footprint_saturates_on_a_stationary_stream():
    """The O(metrics)-not-O(samples) memory contract: ten times the
    samples of one distribution fill nearly the same log-buckets (only
    the counts in them rise)."""
    rng = np.random.default_rng(42)
    sk = QuantileSketch()
    for v in rng.lognormal(mean=-3.0, sigma=1.0, size=1000):
        sk.add(float(v))
    at_1k = len(sk)
    for v in rng.lognormal(mean=-3.0, sigma=1.0, size=9000):
        sk.add(float(v))
    assert sk.n == 10_000 and len(sk) <= 1.75 * at_1k


def test_degenerate_inputs_are_ignored():
    sk = QuantileSketch()
    sk.add(float("nan"))
    sk.add(1.0, count=0)
    sk.add(1.0, count=-3)
    assert sk.n == 0 and sk.quantile(0.5) == 0.0


def test_geometry_mismatch_refuses_merge():
    a = QuantileSketch(0.01)
    b = QuantileSketch(0.02)
    with pytest.raises(ValueError, match="geometry mismatch"):
        a.merge(b)
    c = QuantileSketch(0.01, key_bound=128)
    with pytest.raises(ValueError, match="geometry mismatch"):
        a.merge(c)


def test_signed_stream_orders_quantiles_correctly():
    sk = _sk([-2.0, -1.0, 0.0, 1.0, 2.0])
    assert sk.quantile(0.0) == -2.0
    assert sk.quantile(1.0) == 2.0
    med = sk.quantile(0.5)
    assert med == 0.0
    assert sk.quantile(0.2) < 0.0 < sk.quantile(0.9)


def test_histogram_partitions_all_mass():
    rng = np.random.default_rng(9)
    vals = rng.lognormal(mean=-2.0, sigma=1.0, size=500)
    sk = _sk(vals)
    bounds = (0.05, 0.2, 1.0, math.inf)
    rows = sk.histogram(bounds)
    assert sum(c for _, c in rows) == sk.n
    assert [ub for ub, _ in rows] == sorted(ub for ub, _ in rows)
    # Cumulative counts agree with count_le at every finite bound.
    cum = 0
    by_ub = dict((ub, c) for ub, c in rows)
    for ub in bounds[:-1]:
        cum += by_ub.get(ub, 0)
        assert cum == sk.count_le(ub)


# ---------------------------------------------------------------------- #
# LabelRollup                                                            #
# ---------------------------------------------------------------------- #
def test_rollup_bounds_cardinality_and_conserves_mass():
    ru = LabelRollup(max_labels=8)
    total = 0.0
    for i in range(100):
        ru.add(f"agent{i:03d}", float(i + 1))
        total += float(i + 1)
    assert len(ru.counts) == 8
    assert ru.total() == pytest.approx(total, rel=1e-12)
    assert ru.other_labels == 92
    # The survivors are the heaviest labels (fold is smallest-first).
    assert set(ru.counts) == {f"agent{i:03d}" for i in range(92, 100)}
    # Deterministic: the same sequence folds identically.
    ru2 = LabelRollup(max_labels=8)
    for i in range(100):
        ru2.add(f"agent{i:03d}", float(i + 1))
    assert ru == ru2


def test_rollup_merge_tightens_bound_and_roundtrips():
    a = LabelRollup(max_labels=8)
    b = LabelRollup(max_labels=4)
    for i in range(6):
        a.add(f"x{i}", 10.0 * (i + 1))
        b.add(f"y{i}", 1.0 * (i + 1))
    mass = a.total() + b.total()
    merged = a.copy().merge(b)
    assert merged.max_labels == 4
    assert len(merged.counts) <= 4
    assert merged.total() == pytest.approx(mass, rel=1e-12)
    # Encoding round-trip preserves state byte-identically.
    wire = json.dumps(merged.to_dict(), sort_keys=True)
    back = LabelRollup.from_dict(json.loads(wire))
    assert back == merged
    assert json.dumps(back.to_dict(), sort_keys=True) == wire


def test_rollup_merge_commutes_on_totals():
    a = LabelRollup(max_labels=4)
    b = LabelRollup(max_labels=4)
    for i in range(10):
        a.add(f"l{i}", float(i))
        b.add(f"l{9 - i}", float(i))
    ab = a.copy().merge(b)
    ba = b.copy().merge(a)
    assert ab.total() == pytest.approx(ba.total(), rel=1e-12)
    assert ab.max_labels == ba.max_labels == 4
