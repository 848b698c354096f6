"""Compressed gossip with error feedback (CHOCO-GOSSIP).

Beyond-parity extension.  Every byte the reference moves between agents is
a full-precision parameter vector (flat numpy over queues,
``consensus_asyncio.py:279-281``, or pickled tensors over TCP,
``pickled_socket.py``).  Bandwidth-constrained links want *compressed*
messages — but naively gossiping compressed values destroys convergence:
the compression error accumulates and the network stalls at a noise floor
set by the compressor.

CHOCO-GOSSIP (Koloskova-Stich-Jaggi) fixes this with error feedback.  Each
agent keeps a *public* estimate ``xhat_i`` that its neighbors also track;
only the compressed correction ``q_i = C(x_i - xhat_i)`` crosses the wire:

    q_i     = C(x_i - xhat_i)                (the ONLY transmitted bytes)
    xhat_j <- xhat_j + q_j                   (every holder of the estimate)
    x_i    <- x_i + gamma * sum_j W_ij (xhat_j - xhat_i)

With any delta-contractive compressor (``||C(v) - v||^2 <= (1-delta)
||v||^2``: top-k, random-k, scaled sign) the iterates converge **linearly
to exact consensus** — the estimates chase the iterates, so the
compression error is driven to zero instead of accumulating.

TPU mapping: the recurrence is two stacked elementwise updates plus one
mixing product on the estimate stack, so it rides the same fabric as every
other engine here (dense batched MXU matmuls, or the ppermute matching
schedule under ``shard_map``).  With ``fused=True`` (default) the whole
round — compression included — runs on the fused ``{dtype: (N, P)}``
flat buffers (:class:`FusedCompressor`): O(dtype-buckets) selection and
scatter ops per round instead of O(leaves).  On-chip the full estimates
move through the mixing product — the compression *math* is exact, and
the wire saving is realized where the wire is real: the TCP backend runs
the same recurrence over sockets (``comm.agent.ConsensusAgent.
run_choco_once`` with ``sparse_wire=True``, or ``run_choco_tree`` for a
whole model pytree as ONE fused sparse frame per round), shipping each
top-k correction as ``k`` values + indices
(``comm.tensor_codec.encode_sparse`` / ``encode_fused_sparse``) instead
of the dense vector; a sparse collective-permute would be the ICI/DCN
analogue.
"""

from __future__ import annotations

from typing import Any, Callable, Dict, NamedTuple, Optional, Tuple

import jax
import jax.numpy as jnp
import numpy as np
from jax.sharding import Mesh, PartitionSpec as P

from distributed_learning_tpu.obs import get_registry
from distributed_learning_tpu.ops import mixing as ops
from ._spmd import cached_scan, mix_once, residual
from .consensus import ConsensusEngine

Pytree = Any

__all__ = [
    "Compressor",
    "FusedCompressor",
    "top_k",
    "approx_top_k",
    "random_k",
    "scaled_sign",
    "identity",
    "compressor_delta",
    "int8_quant",
    "compressor_from_spec",
    "ChocoState",
    "ChocoGossipEngine",
]


def _k_of(fraction: float, size: int) -> int:
    """The per-vector keep count of a top-k/random-k fraction — max(1,
    round(fraction * size)), the single source for per-leaf, per-bucket,
    and wire-byte accounting."""
    return max(1, int(round(fraction * size)))


def _sel_mag(v: jax.Array) -> jax.Array:
    """|v| as a selection key, sub-f32 floats widened to f32: bf16 -> f32
    is exact and order-preserving, so the selected index set is
    bit-identical, while CPU ``lax.top_k``/``lax.sort`` on f32 keys run
    ~13x faster than the emulated bf16 comparators (measured at bench
    geometry).  Values are never touched — only the comparison keys."""
    mag = jnp.abs(v)
    if mag.dtype in (jnp.bfloat16, jnp.float16):
        mag = mag.astype(jnp.float32)
    return mag


class Compressor:
    """A delta-contractive compressor: callable ``(value, key) ->
    compressed value`` of the SAME shape (the wire format is the codec's
    concern; the engine works with densified values).

    Instances carry their algebraic identity — ``kind`` plus parameters —
    so the fused engine (:class:`FusedCompressor`) can execute the same
    math directly on the fused ``(N, P)`` dtype-bucket buffers instead of
    mapping the callable over leaves.  Any plain ``(value, key)`` callable
    still satisfies the engine contract (``kind="custom"``: correct, but
    compressed per leaf view — only the named kinds fuse)."""

    def __init__(
        self,
        fn: Callable[[jax.Array, jax.Array], jax.Array],
        kind: str = "custom",
        *,
        fraction: Optional[float] = None,
        recall_target: Optional[float] = None,
    ):
        self._fn = fn
        self.kind = str(kind)
        self.fraction = fraction
        self.recall_target = recall_target

    def __call__(self, v: jax.Array, key: jax.Array) -> jax.Array:
        return self._fn(v, key)

    def __repr__(self) -> str:  # pragma: no cover - debugging aid
        arg = "" if self.fraction is None else f":{self.fraction}"
        return f"Compressor({self.kind}{arg})"


def compressor_from_spec(spec: str) -> "Compressor":
    """Parse a config/CLI compressor spec: ``"topk:0.1"``, ``"atopk:0.1"``,
    ``"randk:0.25"``, ``"sign"``, ``"int8"``, or ``"none"`` (identity)."""
    name, _, arg = str(spec).partition(":")
    name = name.strip().lower()
    if name in ("none", "identity"):
        return identity()
    if name in ("sign", "scaled_sign"):
        return scaled_sign()
    if name in ("int8", "q8"):
        return int8_quant()
    if name in ("topk", "top_k", "randk", "random_k", "atopk", "approx_top_k"):
        try:
            fraction = float(arg) if arg else 0.1
        except ValueError:
            raise ValueError(
                f"bad fraction in compressor spec {spec!r} (want e.g. "
                f"'{name}:0.1')"
            ) from None
        if name in ("topk", "top_k"):
            return top_k(fraction)
        if name in ("atopk", "approx_top_k"):
            return approx_top_k(fraction)
        return random_k(fraction)
    raise ValueError(
        f"unknown compressor spec {spec!r} (want topk:F, atopk:F, randk:F, "
        f"sign, int8, none)"
    )


# --------------------------------------------------------------------- #
# delta-contractive compressors                                         #
# --------------------------------------------------------------------- #
def top_k(fraction: float) -> Compressor:
    """Keep the top ``fraction`` of entries by magnitude (delta =
    fraction for the worst case; much better on real spectra)."""
    if not 0.0 < fraction <= 1.0:
        raise ValueError(f"fraction must be in (0, 1], got {fraction}")

    def compress(v: jax.Array, key: jax.Array) -> jax.Array:
        flat = v.ravel()
        k = _k_of(fraction, flat.size)
        _, idx = jax.lax.top_k(_sel_mag(flat), k)
        out = jnp.zeros_like(flat).at[idx].set(flat[idx])
        return out.reshape(v.shape)

    return Compressor(compress, "top_k", fraction=fraction)


def approx_top_k(fraction: float, recall_target: float = 0.95) -> Compressor:
    """Hardware-aware top-k: ``jax.lax.approx_max_k``, the TPU's native
    bucketed selection, instead of the exact sort-based ``lax.top_k``.

    Exact top-k at large dim is the wall-clock pathology of compressed
    gossip on TPU (a 65k-entry sort per agent per round dwarfs the mixing
    matmul).  The approximate op trades a bounded recall miss — it keeps
    >= ``recall_target`` of the true top-k in expectation — for an
    order-of-magnitude cheaper selection.  For CHOCO that is still a
    delta-contractive compressor (the kept mass is a superset-biased
    sample of the exact one), so convergence theory is unchanged with a
    marginally smaller delta; measure with :func:`compressor_delta`.
    """
    if not 0.0 < fraction <= 1.0:
        raise ValueError(f"fraction must be in (0, 1], got {fraction}")
    if not 0.0 < recall_target <= 1.0:
        raise ValueError(
            f"recall_target must be in (0, 1], got {recall_target}"
        )

    def compress(v: jax.Array, key: jax.Array) -> jax.Array:
        flat = v.ravel()
        k = _k_of(fraction, flat.size)
        _, idx = jax.lax.approx_max_k(
            _sel_mag(flat), k, recall_target=recall_target
        )
        out = jnp.zeros_like(flat).at[idx].set(flat[idx])
        return out.reshape(v.shape)

    return Compressor(
        compress, "approx_top_k", fraction=fraction,
        recall_target=recall_target,
    )


def random_k(fraction: float) -> Compressor:
    """Keep a uniformly random ``fraction`` of entries (delta = fraction
    in expectation; unbiased up to the 1/fraction scale, used plain here —
    CHOCO only needs contraction, not unbiasedness)."""
    if not 0.0 < fraction <= 1.0:
        raise ValueError(f"fraction must be in (0, 1], got {fraction}")

    def compress(v: jax.Array, key: jax.Array) -> jax.Array:
        flat = v.ravel()
        k = _k_of(fraction, flat.size)
        idx = jax.random.choice(key, flat.size, (k,), replace=False)
        out = jnp.zeros_like(flat).at[idx].set(flat[idx])
        return out.reshape(v.shape)

    return Compressor(compress, "random_k", fraction=fraction)


def scaled_sign() -> Compressor:
    """``(||v||_1 / d) * sign(v)`` — 1 bit/entry + one scale; contractive
    with delta = ||v||_1^2 / (d ||v||_2^2) >= 1/d."""

    def compress(v: jax.Array, key: jax.Array) -> jax.Array:
        flat = v.ravel()
        scale = jnp.sum(jnp.abs(flat)) / flat.size
        return (scale * jnp.sign(flat)).reshape(v.shape)

    return Compressor(compress, "scaled_sign")


def int8_quant() -> Compressor:
    """Symmetric int8 quantization: round(v/s)*s with s = max|v|/127 —
    1 byte/entry + one scale, the on-device counterpart of the comm
    backend's ``int8_wire`` (``comm/tensor_codec.py``).

    Contractivity caveat: the worst-case bound (per-entry error <= s/2,
    so ||Q(v)-v||^2 <= d s^2/4 <= (d/64516) ||v||^2, i.e.
    delta >= 1 - d/64516) is only non-vacuous for d < 64516 — for
    model-sized flattened deltas it guarantees nothing (adversarial
    vectors with many entries near s/2 defeat it), so CHOCO's
    delta-contraction assumption rests on the empirical concentration
    of ||v||^2 well above max|v|^2 for dense gradient-like deltas.
    Measure with :func:`compressor_delta` on representative deltas, or
    compose with top-k for very large d if the measured delta is poor.

    Simulates the wire exactly: the value AFTER compression is what
    both sender and receivers apply to their estimates, matching the
    hat-consistency rule."""

    def compress(v: jax.Array, key: jax.Array) -> jax.Array:
        flat = v.ravel()
        scale = jnp.max(jnp.abs(flat)) / 127.0
        safe = jnp.where(scale > 0, scale, 1.0)
        q = jnp.clip(jnp.round(flat / safe), -127, 127)
        return jnp.where(scale > 0, q * safe, 0.0).reshape(v.shape)

    return Compressor(compress, "int8_quant")


def identity() -> Compressor:
    """No compression (delta = 1): CHOCO then reduces to plain gossip on
    the estimates — useful as a correctness reference."""
    return Compressor(lambda v, key: v, "identity")


def compressor_delta(
    compress: Compressor, dim: int = 256, trials: int = 50, seed: int = 0
) -> float:
    """Empirical contraction factor ``min_v 1 - ||C(v)-v||^2 / ||v||^2``
    over random gaussian vectors — a measurement aid for picking gamma.

    All ``trials`` run as ONE jitted, vmapped batch with a single host
    sync at the end (a per-trial ``float(...)`` loop would pay one
    device round-trip per trial).  Same statistic, same
    one-independent-key-per-trial structure."""

    def one(k: jax.Array) -> jax.Array:
        k1, k2 = jax.random.split(k)
        v = jax.random.normal(k1, (dim,))
        err = v - compress(v, k2)
        return jnp.sum(err * err) / jnp.sum(v * v)

    ratios = jax.jit(
        lambda key: jax.vmap(one)(jax.random.split(key, trials))
    )(jax.random.key(seed))
    return float(1.0 - jnp.max(ratios))


# --------------------------------------------------------------------- #
# Fused whole-buffer compression                                        #
# --------------------------------------------------------------------- #
def _keep_columns(buf: jax.Array, idx: jax.Array) -> jax.Array:
    """Densify per-row selected column indices into a keep-masked copy of
    ``buf`` — the fused analogue of ``zeros.at[idx].set(flat[idx])``
    (selected values are exact copies, everything else exact zero)."""
    rows = buf.shape[0]
    mask = (
        jnp.zeros(buf.shape, jnp.bool_)
        .at[jnp.arange(rows)[:, None], idx]
        .set(True)
    )
    return jnp.where(mask, buf, jnp.zeros_like(buf))


class FusedCompressor:
    """Compression executed directly on the fused ``{dtype: (rows, P)}``
    flat buffers (:func:`~distributed_learning_tpu.ops.mixing.flatten_stacked`).

    The per-leaf contract maps a :class:`Compressor` over every leaf of
    the correction — O(leaves) selection sorts, scatters, and RNG splits
    per agent per round, which dwarf the single fused mixing GEMM they
    feed on model-shaped states (~100 leaves).  This class runs the SAME
    math as O(dtype-buckets) whole-buffer programs:

    ``budget="per-leaf"`` preserves today's selection semantics exactly.
    The top-k family becomes ONE segment-aware selection per bucket
    (:meth:`_segment_top_k`: a stable three-operand ``lax.sort`` over
    ``(leaf-segment, -|v|, column)`` plus one scatter — bit-identical
    values AND index sets to per-leaf ``lax.top_k``, which ties to the
    lowest index exactly like a stable sort); ``scaled_sign`` /
    ``int8_quant`` reduce their per-leaf scale over the layout's leaf
    spans (pure slices of the contiguous buffer — the identical reduce
    the vmapped per-leaf op performs) and apply ONE elementwise pass per
    bucket.  ``random_k`` and custom callables keep per-leaf ops through
    the layout views: their per-(leaf, agent) RNG stream / opaque body
    IS the contract (``fused=False`` on the engine remains the oracle).

    ``budget="global"`` spends one k-budget across the whole bucket —
    a single ``lax.top_k``/``approx_max_k`` over the ``(rows, P)``
    buffer, one scale per bucket, and one RNG key per round for
    ``random_k`` instead of one per leaf.  Better kept mass at equal
    bytes than per-leaf budgeting (large leaves donate budget to the
    coordinates that matter; measure with :func:`compressor_delta` /
    ``tests/test_compression.py``); requires a named compressor kind.

    ``rows`` is ``N`` in dense mode and 1 inside ``shard_map`` (the
    per-device shard); pass ``axis_name`` there so RNG-dependent kinds
    fold the device's agent index into the key — the same key
    discipline as the per-leaf engine path.
    """

    _KINDS = (
        "top_k", "approx_top_k", "random_k", "scaled_sign", "int8_quant",
        "identity",
    )

    def __init__(self, base: Compressor, budget: str = "per-leaf"):
        if budget not in ("per-leaf", "global"):
            raise ValueError(
                f"unknown compression budget {budget!r} (want 'per-leaf' "
                "or 'global')"
            )
        self.base = base
        self.budget = budget
        self.kind = getattr(base, "kind", "custom")
        if self.kind not in self._KINDS:
            self.kind = "custom"
        if budget == "global" and self.kind == "custom":
            raise ValueError(
                "budget='global' needs a named compressor kind "
                f"({'/'.join(self._KINDS)}); got a custom callable whose "
                "whole-buffer form is unknowable"
            )

    # ------------------------------------------------------------------ #
    def compress(
        self,
        buffers: Dict[str, jax.Array],
        layout: "ops.FusedLayout",
        key: jax.Array,
        *,
        n: int,
        axis_name: Optional[str] = None,
    ) -> Dict[str, jax.Array]:
        """Compress the fused correction buffers (same tree of
        ``{dtype: (rows, P)}`` arrays back)."""
        if self.kind == "identity":
            return dict(buffers)
        if self.kind == "custom" or (
            self.kind == "random_k" and self.budget == "per-leaf"
        ):
            return self._per_leaf_views(
                buffers, layout, key, n=n, axis_name=axis_name
            )
        return {
            name: self._bucket(
                buffers[name], layout, name, key, axis_name=axis_name
            )
            for name, _w in layout.buckets
        }

    def _per_leaf_views(
        self, buffers, layout, key, *, n: int, axis_name: Optional[str]
    ) -> Dict[str, jax.Array]:
        """Exact per-leaf compression through the layout views — the
        fallback for kinds whose per-leaf semantics cannot fuse (the
        random-k RNG stream, custom callables).  Key derivation matches
        the per-leaf engine path bit for bit: one split per leaf in tree
        order, then one per agent."""
        tree = ops.unflatten_stacked(buffers, layout)
        leaves, treedef = jax.tree.flatten(tree)
        keys = jax.random.split(key, len(leaves))
        if axis_name is None:
            comp = [
                jax.vmap(self.base)(leaf, jax.random.split(k, n))
                for leaf, k in zip(leaves, keys)
            ]
        else:
            i = jax.lax.axis_index(axis_name)
            comp = [
                self.base(leaf[0], jax.random.fold_in(k, i))[None]
                for leaf, k in zip(leaves, keys)
            ]
        out, _ = ops.flatten_stacked(
            jax.tree.unflatten(treedef, comp), layout
        )
        return out

    # ------------------------------------------------------------------ #
    def _bucket(
        self, buf, layout, name: str, key, *, axis_name: Optional[str]
    ) -> jax.Array:
        P_ = buf.shape[1]
        if self.kind in ("top_k", "approx_top_k"):
            if self.budget == "per-leaf":
                return self._segment_top_k(buf, layout.bucket_spans(name))
            k = _k_of(self.base.fraction, P_)
            if self.kind == "top_k":
                _, idx = jax.lax.top_k(_sel_mag(buf), k)
            else:
                _, idx = jax.lax.approx_max_k(
                    _sel_mag(buf), k, recall_target=self.base.recall_target
                )
            return _keep_columns(buf, idx)
        if self.kind == "random_k":  # global budget (per-leaf is views)
            k = _k_of(self.base.fraction, P_)
            if axis_name is None:
                idx = jax.vmap(
                    lambda kk: jax.random.choice(kk, P_, (k,), replace=False)
                )(jax.random.split(key, buf.shape[0]))
            else:
                folded = jax.random.fold_in(
                    key, jax.lax.axis_index(axis_name)
                )
                idx = jax.random.choice(folded, P_, (k,), replace=False)[None]
            return _keep_columns(buf, idx)
        if self.kind == "scaled_sign":
            scale = self._scale_cols(
                buf, layout, name,
                lambda sl: jnp.sum(jnp.abs(sl), axis=1, keepdims=True)
                / sl.shape[1],
            )
            return scale * jnp.sign(buf)
        if self.kind == "int8_quant":
            scale = self._scale_cols(
                buf, layout, name,
                lambda sl: jnp.max(jnp.abs(sl), axis=1, keepdims=True)
                / 127.0,
            )
            safe = jnp.where(scale > 0, scale, 1.0)
            q = jnp.clip(jnp.round(buf / safe), -127, 127)
            return jnp.where(scale > 0, q * safe, 0.0)
        raise AssertionError(self.kind)  # pragma: no cover

    def _scale_cols(self, buf, layout, name: str, red) -> jax.Array:
        """Per-column scale array: the bucket-wide scale (global budget)
        or each leaf span's scale broadcast over its columns (per-leaf
        budget; the slice-wise reduce is the identical XLA reduce the
        vmapped per-leaf op performs, so scales are bit-identical)."""
        if self.budget == "global":
            return red(buf)
        parts = []
        for off, size in layout.bucket_spans(name):
            sl = jax.lax.slice_in_dim(buf, off, off + size, axis=1)
            parts.append(jnp.broadcast_to(red(sl), sl.shape))
        return parts[0] if len(parts) == 1 else jnp.concatenate(parts, axis=1)

    def _segment_top_k(self, buf, spans) -> jax.Array:
        """Segment-aware top-k selection over a whole bucket: every leaf
        span keeps its top ``max(1, round(fraction * size))`` columns by
        |value| — exactly per-leaf ``lax.top_k`` (magnitude ties at the
        boundary go to the LOWEST column; NaN counts as above every
        finite magnitude — ``lax.top_k``'s total order) — in a
        leaf-count-INDEPENDENT number of device ops.

        Strategy: spans are grouped into power-of-two size classes (so
        within-class padding wastes < 2x); each class is gathered into an
        ``(rows, L_class, max_span)`` padded layout through a static
        index map (padding reads a -inf magnitude sentinel column), runs
        ONE batched ``lax.top_k`` (``approx_max_k`` for the approx kind —
        exact on CPU) at the class's max per-leaf k, masks each leaf's
        surplus ranks with a static boolean, and scatters the surviving
        global columns into a shared keep mask.  Ops per bucket per
        round = O(size classes) ≈ 1-4 regardless of leaf count (a
        uniform-width bucket is exactly one top_k + one scatter);
        measured ~2.7x faster than the per-leaf top_k chain at bench
        geometry.  Selected index sets and values are bit-identical to
        the per-leaf oracle (``tests/test_compression.py``)."""
        rows, P_ = buf.shape
        classes: Dict[int, list] = {}
        for j, (_off, size) in enumerate(spans):
            classes.setdefault(max(int(size).bit_length(), 1), []).append(j)
        mag = _sel_mag(buf)
        mag_ext = jnp.concatenate(
            [mag, jnp.full((rows, 1), -jnp.inf, mag.dtype)], axis=1
        )
        all_cols = []
        for _cls, members in sorted(classes.items()):
            sizes = [spans[j][1] for j in members]
            ks = [_k_of(self.base.fraction, s) for s in sizes]
            L, maxd, kmax = len(members), max(sizes), max(ks)
            # Static padded-position -> bucket-column map; P_ is the
            # sentinel (the extra -inf magnitude column, never selected:
            # k_i <= size_i).
            gidx = np.full((L, maxd), P_, np.int32)
            for i, j in enumerate(members):
                off, size = spans[j]
                gidx[i, :size] = np.arange(off, off + size, dtype=np.int32)
            keep = np.arange(kmax)[None, :] < np.asarray(ks)[:, None]
            padded = mag_ext[:, jnp.asarray(gidx.ravel())].reshape(
                rows, L, maxd
            )
            if self.kind == "approx_top_k":
                _, idx = jax.lax.approx_max_k(
                    padded, kmax, recall_target=self.base.recall_target
                )
            else:
                _, idx = jax.lax.top_k(padded, kmax)
            cols = jnp.take(
                jnp.asarray(gidx),
                idx
                + (jnp.arange(L, dtype=jnp.int32) * maxd)[None, :, None],
            )
            # Surplus ranks (a leaf whose k is below the class max) are
            # redirected to the sentinel column, sliced away below.
            cols = jnp.where(jnp.asarray(keep)[None], cols, P_)
            all_cols.append(cols.reshape(rows, L * kmax))
        cols = (
            all_cols[0]
            if len(all_cols) == 1
            else jnp.concatenate(all_cols, axis=1)
        )
        # ONE boolean scatter (all classes' selections) + one select
        # builds the densified output: selected values are exact copies
        # of ``buf``, everything else exact zero.  (A value-scatter
        # variant — gather the kept values, scatter them into zeros —
        # measured ~1.5x slower on the CPU harness.)
        mask = (
            jnp.zeros((rows, P_ + 1), jnp.bool_)
            .at[jnp.arange(rows)[:, None], cols]
            .set(True)
        )
        return jnp.where(mask[:, :P_], buf, jnp.zeros_like(buf))

    # ------------------------------------------------------------------ #
    def wire_bytes_per_round(
        self, layout: "ops.FusedLayout", n: int
    ) -> Optional[int]:
        """Nominal sparse-wire bytes one compressed round ships for ``n``
        agents — what the TCP fused sparse frame moves (u32 index + one
        stored-dtype value per kept entry for the k-sparse kinds; 1
        bit/entry + one scale for scaled_sign; 1 byte/entry + one scale
        for int8; the dense buffer for identity).  ``None`` for custom
        callables (their k is unknowable statically).  Feeds the
        ``consensus.compressed_bytes`` obs counter and the benchmark
        bytes/round column."""
        if self.kind == "custom":
            return None
        total = 0
        for name, width in layout.buckets:
            item = np.dtype(name).itemsize
            if self.kind in ("top_k", "approx_top_k", "random_k"):
                if self.budget == "global":
                    k = _k_of(self.base.fraction, width)
                else:
                    k = sum(
                        _k_of(self.base.fraction, size)
                        for _off, size in layout.bucket_spans(name)
                    )
                total += k * (4 + item)
            elif self.kind == "scaled_sign":
                total += (width + 7) // 8 + item
            elif self.kind == "int8_quant":
                total += width + 4
            else:  # identity
                total += width * item
        return total * n


# --------------------------------------------------------------------- #
class ChocoState(NamedTuple):
    """Stacked CHOCO state: iterates, public estimates, PRNG key, and —
    only when the engine runs with ``error_feedback=True`` — the EF
    residual accumulator (``ef=None`` otherwise: None is an empty
    pytree, so the 3-field layout, checkpoints, and scan carries of the
    default configuration are unchanged)."""

    x: Pytree
    xhat: Pytree
    key: jax.Array
    ef: Any = None


class ChocoGossipEngine:
    """CHOCO-GOSSIP over a mixing matrix, dense or mesh-sharded.

    Parameters
    ----------
    W:
        (n, n) symmetric row-stochastic mixing matrix.
    compressor:
        A delta-contractive compressor (:func:`top_k`, :func:`random_k`,
        :func:`scaled_sign`, :func:`identity`).
    gamma:
        Consensus step size.  Stability degrades as the compressor gets
        more aggressive; ``gamma ~ delta`` is a reliable heuristic
        (measured: top-k 10% on d=4096 converges to 2e-7 at gamma <= 0.2
        but oscillates at 0.4; top-k 25% on small d tolerates 0.4).  See
        :func:`compressor_delta` to measure delta.
    fused:
        Carry the scan state on the fused flat-buffer layout
        (``ops.flatten_stacked``): iterates and estimates are raveled
        ONCE per :meth:`run` call — not per round — the mixing product
        on the estimates moves O(dtype-buckets) messages per round
        instead of O(leaves), and the correction is compressed by a
        :class:`FusedCompressor` directly on the buffers — O(buckets)
        selection/scatter ops and one RNG split per round.
        ``fused=False`` is the per-leaf oracle.
    budget:
        Compression budget of the fused path: ``"per-leaf"`` (default)
        keeps each leaf's k/scale/RNG contract exactly (bit-identical
        compressed values to the oracle); ``"global"`` spends one budget
        across each whole dtype bucket (better kept mass at equal
        bytes).  See :class:`FusedCompressor`.
    """

    def __init__(
        self,
        W: np.ndarray,
        compressor: Compressor,
        *,
        gamma: float = 0.3,
        mesh: Optional[Mesh] = None,
        axis_name: str = "agents",
        fused: bool = True,
        budget: str = "per-leaf",
        error_feedback: bool = False,
    ):
        self.engine = ConsensusEngine(
            W, mesh=mesh, axis_name=axis_name, fused=fused
        )
        self.n = self.engine.n
        self.mesh = mesh
        self.axis_name = axis_name
        self.compressor = compressor
        self.gamma = float(gamma)
        self.fused = bool(fused)
        if not fused and budget != "per-leaf":
            raise ValueError(
                "budget='global' requires fused=True (the per-leaf "
                "oracle is, by definition, per-leaf budgeted)"
            )
        self.budget = budget
        # Error feedback on the CORRECTION channel (EF-SGD style,
        # arXiv:1901.09847 composed with the CHOCO recurrence): the mass
        # a lossy compressor drops from ``x - xhat`` is banked and
        # re-offered next round, so an aggressive global budget (which
        # can starve whole buckets for rounds at a time) stays
        # convergent instead of stalling at the compressor's floor.
        # ``False`` (default) keeps the plain recurrence bit-identical.
        self.error_feedback = bool(error_feedback)
        if self.error_feedback and not fused:
            raise ValueError(
                "error_feedback=True is the fused global-budget rescue; "
                "it requires fused=True (the per-leaf oracle keeps each "
                "leaf's exact compressor contract instead)"
            )
        self._fused_comp = FusedCompressor(compressor, budget=budget)
        self._jit_run: dict = {}

    # ------------------------------------------------------------------ #
    def _compress_tree(self, delta_tree: Pytree, key: jax.Array) -> Pytree:
        """Per-agent, per-leaf compression of the correction."""
        leaves, treedef = jax.tree.flatten(delta_tree)
        keys = jax.random.split(key, len(leaves))
        if self.mesh is None:
            comp = [
                # Independent key per (leaf, agent): random-k masks must
                # differ across agents.
                jax.vmap(self.compressor)(leaf, jax.random.split(k, self.n))
                for leaf, k in zip(leaves, keys)
            ]
        else:
            # Inside shard_map the leading axis is this device's single
            # agent; fold its mesh position into the key so agents draw
            # independent random-k masks.
            i = jax.lax.axis_index(self.axis_name)
            comp = [
                self.compressor(leaf[0], jax.random.fold_in(k, i))[None]
                for leaf, k in zip(leaves, keys)
            ]
        return jax.tree.unflatten(treedef, comp)

    def _mix(self, t: Pytree, self_w, match_w) -> Pytree:
        return mix_once(self.engine, t, self_w, match_w)

    def _step(self, s: ChocoState, self_w, match_w) -> ChocoState:
        key, sub = jax.random.split(s.key)
        q = self._compress_tree(
            jax.tree.map(lambda a, b: a - b, s.x, s.xhat), sub
        )
        xhat = jax.tree.map(lambda h, qv: h + qv, s.xhat, q)
        mixed_hat = self._mix(xhat, self_w, match_w)
        x = jax.tree.map(
            lambda xv, mh, h: xv + self.gamma * (mh - h),
            s.x, mixed_hat, xhat,
        )
        return ChocoState(x=x, xhat=xhat, key=key)

    # ------------------------------------------------------------------ #
    def init(self, x0: Pytree, *, seed: int = 0) -> ChocoState:
        """Estimates start at zero — the standard CHOCO initialization
        (so does the EF residual bank, when enabled)."""
        x = self.engine.shard(x0)
        xhat = jax.tree.map(jnp.zeros_like, x)
        ef = (
            jax.tree.map(jnp.zeros_like, x)
            if self.error_feedback else None
        )
        return ChocoState(x=x, xhat=xhat, key=jax.random.key(seed), ef=ef)

    def _step_fused(
        self, s: ChocoState, layout, self_w, match_w
    ) -> ChocoState:
        """One CHOCO round on the fused carry: ``s.x``/``s.xhat`` are the
        ``{dtype: (N, P)}`` buffer pytrees.  The correction is compressed
        by the :class:`FusedCompressor` directly on the buffers —
        O(dtype-buckets) selection/scatter ops per round — and the mixing
        product, the only cross-agent traffic, runs on the fused estimate
        buffers."""
        key, sub = jax.random.split(s.key)
        delta = jax.tree.map(lambda a, b: a - b, s.x, s.xhat)
        if s.ef is not None:
            # EF bank: re-offer the previously dropped correction mass.
            delta = jax.tree.map(lambda d, e: d + e, delta, s.ef)
        q = self._fused_comp.compress(
            delta, layout, sub, n=self.n,
            axis_name=None if self.mesh is None else self.axis_name,
        )
        ef = (
            jax.tree.map(lambda d, qv: d - qv, delta, q)
            if s.ef is not None else None
        )
        xhat = jax.tree.map(lambda h, qv: h + qv, s.xhat, q)
        mixed_hat = self._mix(xhat, self_w, match_w)
        x = jax.tree.map(
            lambda xv, mh, h: xv + self.gamma * (mh - h),
            s.x, mixed_hat, xhat,
        )
        return ChocoState(x=x, xhat=xhat, key=key, ef=ef)

    def _fused_program(self, layout, rounds: int):
        """Traceable fused-carry program ``state -> (state, trace)``:
        flatten x/xhat once at program entry, scan ``rounds`` fused
        steps, unflatten once at exit — the flatten cost is per call (the
        trainer calls once per epoch), never per round.  Exposed unjitted
        so the graftlint ``choco_run_fused`` audit entry can pin its
        collective inventory (``tools/graftlint/jaxpr_audit.py``)."""
        engine = self.engine

        def scan_fused(s, self_w, match_w):
            st0 = self._flatten_state(s, layout)

            def body(st, _):
                st = self._step_fused(st, layout, self_w, match_w)
                return st, residual(engine, st.x)

            fs, trace = jax.lax.scan(body, st0, None, length=rounds)
            return self._unflatten_state(fs, layout), trace

        if engine.mesh is None:
            return lambda s: scan_fused(s, None, None)
        st_spec = self._state_spec()
        inner = jax.shard_map(
            scan_fused,
            mesh=engine.mesh,
            in_specs=(st_spec, P(self.axis_name), P(None, self.axis_name)),
            out_specs=(st_spec, P()),
            check_vma=True,
        )
        return lambda s: inner(s, engine._self_w, engine._match_w)

    def _flatten_state(self, s: ChocoState, layout) -> ChocoState:
        """Ravel every tree-valued field of the carry onto the fused
        buffer layout (once per program entry, never per round)."""
        bx, _ = ops.flatten_stacked(s.x, layout)
        bh, _ = ops.flatten_stacked(s.xhat, layout)
        bef = None
        if s.ef is not None:
            bef, _ = ops.flatten_stacked(s.ef, layout)
        return ChocoState(x=bx, xhat=bh, key=s.key, ef=bef)

    def _unflatten_state(self, s: ChocoState, layout) -> ChocoState:
        return ChocoState(
            x=ops.unflatten_stacked(s.x, layout),
            xhat=ops.unflatten_stacked(s.xhat, layout),
            key=s.key,
            ef=(
                None if s.ef is None
                else ops.unflatten_stacked(s.ef, layout)
            ),
        )

    def _state_spec(self) -> ChocoState:
        spec = P(self.axis_name)
        return ChocoState(
            x=spec, xhat=spec, key=P(),
            ef=spec if self.error_feedback else None,
        )

    def superstep_program(self, layout):
        """Traceable ``(ChocoState, times) -> ChocoState`` with a TRACED
        round count: a ``fori_loop`` of the same per-round step the
        jitted :meth:`run` scans (``_step_fused`` on the fused carry,
        the per-leaf ``_step`` otherwise), so the carried state is
        bitwise :meth:`run`'s at equal counts — only the per-round
        residual trace (a pure readout) is dropped.  This is the body
        the trainer's superstep embeds: the CHOCO hat-carry threads
        through the epoch scan and each epoch's round budget arrives as
        schedule data.  ``layout`` must be the concrete
        :func:`ops.fused_layout` of the state (ignored when
        ``fused=False``)."""
        engine = self.engine

        if self.fused:
            def run_st(s, t, self_w, match_w):
                st0 = self._flatten_state(s, layout)
                st = jax.lax.fori_loop(
                    0, t,
                    lambda i, st: self._step_fused(
                        st, layout, self_w, match_w
                    ),
                    st0,
                )
                return self._unflatten_state(st, layout)
        else:
            def run_st(s, t, self_w, match_w):
                return jax.lax.fori_loop(
                    0, t,
                    lambda i, st: self._step(st, self_w, match_w),
                    s,
                )

        if engine.mesh is None:
            return lambda s, t: run_st(s, t, None, None)
        st_spec = self._state_spec()
        inner = jax.shard_map(
            run_st,
            mesh=engine.mesh,
            in_specs=(
                st_spec, P(), P(self.axis_name),
                P(None, self.axis_name),
            ),
            out_specs=st_spec,
            check_vma=True,
        )
        return lambda s, t: inner(s, t, engine._self_w, engine._match_w)

    def _run_fused(
        self, state: ChocoState, rounds: int
    ) -> Tuple[ChocoState, jax.Array]:
        rounds = int(rounds)
        layout = ops.fused_layout(state.x)
        ckey = ("fused", rounds, layout)
        if ckey not in self._jit_run:
            self._jit_run[ckey] = jax.jit(
                self._fused_program(layout, rounds)
            )
        return self._jit_run[ckey](state)

    def _note_compression(self, state: ChocoState, rounds: int) -> None:
        """Compressed-gossip accounting (obs), host-side only: on
        concrete calls record the nominal sparse-wire bytes the rounds'
        corrections occupy (``consensus.compressed_bytes``) and the
        ratio to the dense state volume (``consensus.compression_ratio``
        gauge).  Tracer inputs and custom compressors (unknowable k) are
        skipped — never a device sync here, same discipline as
        ``ConsensusEngine._note_layout``."""
        leaves = jax.tree.leaves(state.x)
        if not leaves or any(
            isinstance(l, jax.core.Tracer) for l in leaves
        ):
            return
        try:
            layout = ops.fused_layout(state.x)
        except (ValueError, TypeError):
            return
        wire = self._fused_comp.wire_bytes_per_round(layout, self.n)
        if wire is None:
            return
        reg = get_registry()
        reg.inc("consensus.compressed_bytes", wire * int(rounds))
        dense = layout.bytes_per_round(self.n)
        if dense:
            reg.gauge("consensus.compression_ratio", wire / dense)

    def run(self, state: ChocoState, rounds: int) -> Tuple[ChocoState, jax.Array]:
        """``rounds`` CHOCO iterations in one jitted ``lax.scan``; returns
        the final state and the per-round consensus-residual trace."""
        self._note_compression(state, int(rounds))
        if self.fused:
            return self._run_fused(state, rounds)
        spec = P(self.axis_name)
        st_spec = ChocoState(x=spec, xhat=spec, key=P())
        fn = cached_scan(self, self._jit_run, rounds, st_spec, self._step)
        return fn(state)

    def max_deviation(self, state: ChocoState) -> float:
        return float(self.engine.max_deviation(state.x))
