"""Jitted mixing and disagreement primitives on stacked parameter pytrees.

State convention: per-agent values live in one pytree whose every leaf has a
leading *agent* axis of size N ("stacked" layout).  On a single device this
axis is a batch dimension and one gossip round is a single MXU matmul; over a
device mesh the axis is sharded (one agent per device) and the same functions
are applied under ``shard_map`` with ``ppermute`` doing the neighbor exchange
(see ``parallel/consensus.py``).

These primitives replace the reference's host-side numpy path
(``utils/consensus_simple/mixer.py``): its flatten -> O(N^2 P) dense mixing ->
unflatten round-trip (``mixer.py:43-49, 68-76``) becomes a device-resident
``W @ x`` per leaf with no reshape churn, and its deviation metrics
(``mixer.py:51-66, 78-84``) become jitted tree reductions.
"""

from __future__ import annotations

from typing import Any, Dict, Hashable, List, NamedTuple, Sequence, Tuple

import jax
import jax.numpy as jnp
import numpy as np

Pytree = Any

__all__ = [
    "stack_trees",
    "unstack_tree",
    "dense_mix",
    "leaf_mix",
    "agent_deviations",
    "max_deviation",
    "max_std",
    "weighted_lift",
    "weighted_readout",
    "FusedLayout",
    "fused_layout",
    "flatten_stacked",
    "unflatten_stacked",
    "stale_weight_matrix",
    "presence_weight_matrix",
    "stale_weighted_mix",
    "pairwise_sq_dists",
    "clip_weight_matrix",
    "adaptive_clip_radius",
    "clipped_mix",
    "trim_counts",
    "trimmed_mix",
]


def stack_trees(trees: Sequence[Pytree]) -> Pytree:
    """Stack N per-agent pytrees into one tree with a leading agent axis."""
    return jax.tree.map(lambda *xs: jnp.stack(xs, axis=0), *trees)


def unstack_tree(stacked: Pytree, n: int) -> List[Pytree]:
    """Split the leading agent axis back into N per-agent pytrees.

    Every leaf must carry the leading agent axis of size ``n`` (the
    :func:`stack_trees` invariant).  A leaf without it — a python scalar,
    a 0-d array, or an array whose leading dimension is not ``n`` — is
    rejected: silently handing the SAME value to all agents (the old
    ``hasattr(x, "__getitem__")`` fallback) turns a shape bug into n-way
    state aliasing.
    """
    flat, treedef = jax.tree_util.tree_flatten_with_path(stacked)
    for path, leaf in flat:
        shape = getattr(leaf, "shape", None)
        if shape is None or len(shape) == 0 or shape[0] != n:
            raise ValueError(
                f"unstack_tree: leaf {jax.tree_util.keystr(path)} has "
                f"shape {shape} — every leaf of a stacked tree must have "
                f"a leading agent axis of size {n} (stack scalars with "
                "stack_trees first)"
            )
    return [
        jax.tree_util.tree_unflatten(
            treedef, [leaf[i] for _, leaf in flat]
        )
        for i in range(n)
    ]


# --------------------------------------------------------------------- #
# Fused flat-buffer layout                                              #
# --------------------------------------------------------------------- #
class _LeafSlot(NamedTuple):
    """Where one stacked leaf lives inside its dtype bucket."""

    bucket: str            # canonical dtype name, e.g. "float32"
    offset: int            # column offset inside the (N, P_bucket) buffer
    shape: Tuple[int, ...]  # trailing (per-agent) shape; () for (N,) leaves
    size: int              # prod(shape)


class FusedLayout(NamedTuple):
    """Static (host-side, hashable) metadata of a fused flat-buffer state.

    A stacked pytree is raveled into ONE contiguous ``(N, P)`` buffer per
    storage dtype ("bucket"), so a sharded gossip round is O(buckets)
    collectives instead of O(leaves).  The layout is leading-axis
    agnostic: the same object serves the global ``(N, ...)`` tree and the
    per-device ``(1, ...)`` shards inside ``shard_map``.  Hashable on
    purpose — jit caches may key on it.
    """

    treedef: Any
    slots: Tuple[_LeafSlot, ...]          # one per leaf, in tree order
    buckets: Tuple[Tuple[str, int], ...]  # (dtype name, width P), sorted

    @property
    def leaf_count(self) -> int:
        return len(self.slots)

    @property
    def bucket_count(self) -> int:
        return len(self.buckets)

    def bytes_per_round(self, n: int) -> int:
        """Bytes of state one gossip round touches for ``n`` agents."""
        return sum(
            n * width * np.dtype(name).itemsize for name, width in self.buckets
        )

    def bucket_width(self, bucket: str) -> int:
        """Column count P of one dtype bucket."""
        for name, width in self.buckets:
            if name == bucket:
                return width
        raise KeyError(bucket)

    def bucket_spans(self, bucket: str) -> Tuple[Tuple[int, int], ...]:
        """``(offset, size)`` leaf spans of one dtype bucket, ascending.

        Offsets are column positions inside the bucket's ``(N, P)``
        buffer; spans tile ``[0, P)`` exactly (leaves of a bucket are
        laid out consecutively in tree order).  This is the static
        segment map fused *compression* selects against
        (``parallel/compression.py::FusedCompressor``): a per-leaf k
        budget is a per-span budget over these columns.
        """
        spans = tuple(
            (s.offset, s.size) for s in self.slots if s.bucket == bucket
        )
        if not spans:
            raise KeyError(bucket)
        return spans


def fused_layout(stacked: Pytree) -> FusedLayout:
    """Compute the fused flat-buffer layout of a stacked pytree.

    Works on concrete arrays and on tracers (shapes are static under
    jit).  Leaves are grouped by *storage* dtype — bf16/f32 leaves keep
    their dtype at the buffer boundary; the mixing math stays f32 either
    way (see :func:`dense_mix`).
    """
    flat, treedef = jax.tree_util.tree_flatten_with_path(stacked)
    if not flat:
        return FusedLayout(treedef, (), ())
    lead = None
    widths: Dict[str, int] = {}
    slots: List[_LeafSlot] = []
    for path, leaf in flat:
        shape = getattr(leaf, "shape", None)
        if shape is None or len(shape) == 0:
            raise ValueError(
                f"fused_layout: leaf {jax.tree_util.keystr(path)} has "
                f"shape {shape} — every leaf of a stacked tree must have "
                "a leading agent axis (stack scalars with stack_trees "
                "first)"
            )
        if lead is None:
            lead = shape[0]
        elif shape[0] != lead:
            raise ValueError(
                f"fused_layout: leaf {jax.tree_util.keystr(path)} has "
                f"leading axis {shape[0]}, expected {lead} (inconsistent "
                "agent axis across leaves)"
            )
        bucket = str(np.dtype(leaf.dtype))
        size = int(np.prod(shape[1:], dtype=np.int64))
        slots.append(
            _LeafSlot(bucket, widths.get(bucket, 0), tuple(shape[1:]), size)
        )
        widths[bucket] = widths.get(bucket, 0) + size
    return FusedLayout(
        treedef, tuple(slots), tuple(sorted(widths.items()))
    )


def flatten_stacked(
    stacked: Pytree, layout: FusedLayout | None = None
) -> Tuple[Dict[str, jax.Array], FusedLayout]:
    """Ravel a stacked pytree into its fused ``{dtype: (N, P)}`` buffers.

    Inside jit this is a reshape+concatenate at program entry, and it is
    not free: on a TPU a leaf ``(N, a, b)`` is tiled over its two minor
    dimensions, so ``(N, a, b) -> (N, a*b)`` moves every byte (13 ms for
    one 617 MB leaf on a v5e, PERF.md).  It pays where a round costs a
    collective per array — the sharded engine's ``ppermute``s, the CHOCO
    codec — because the loop body then runs on O(buckets) buffers instead
    of O(leaves) arrays; the dense engine, which has no collective to
    save, mixes the leaves where they lie (:func:`leaf_mix`).  Returns
    ``(buffers, layout)``; pass a precomputed ``layout`` to skip
    revalidation (the CHOCO scan does, per cached program).
    """
    if layout is None:
        layout = fused_layout(stacked)
    leaves = jax.tree.leaves(stacked)
    by_bucket: Dict[str, List[jax.Array]] = {}
    # consensus.pack / .unpack / .round / .residual: the names a profile
    # shows for the mix's parts (docs/observability.md); metadata only.
    with jax.named_scope("consensus.pack"):
        for slot, leaf in zip(layout.slots, leaves):
            by_bucket.setdefault(slot.bucket, []).append(
                leaf.reshape(leaf.shape[0], slot.size)
            )
        buffers = {
            name: (
                parts[0] if len(parts) == 1
                else jnp.concatenate(parts, axis=1)
            )
            for name, parts in by_bucket.items()
        }
    return buffers, layout


def unflatten_stacked(
    buffers: Dict[str, jax.Array], layout: FusedLayout
) -> Pytree:
    """Inverse of :func:`flatten_stacked`: slice each leaf back out of its
    dtype bucket and restore the tree structure (one-time exit cost)."""
    leaves = []
    with jax.named_scope("consensus.unpack"):
        for slot in layout.slots:
            buf = buffers[slot.bucket]
            piece = jax.lax.slice_in_dim(
                buf, slot.offset, slot.offset + slot.size, axis=1
            )
            leaves.append(piece.reshape((buf.shape[0],) + slot.shape))
    return jax.tree_util.tree_unflatten(layout.treedef, leaves)


def dense_mix(
    stacked: Pytree,
    W: jax.Array,
    *,
    precision: jax.lax.Precision = jax.lax.Precision.HIGHEST,
) -> Pytree:
    """One gossip round on the whole stacked state: ``x_a <- sum_b W[a,b] x_b``.

    The mixing math of ``mixer.py:43-49`` / ``consensus_asyncio.py:295`` as a
    single batched matmul per leaf — on TPU this rides the MXU.  ``precision``
    defaults to HIGHEST because consensus residuals are driven to ~1e-4 and
    below, which bf16 matmul accumulation would floor.
    """

    def mix_leaf(x: jax.Array) -> jax.Array:
        # Mix in float32 regardless of storage dtype (matches the sharded
        # path); cast back so bf16/int leaves keep their layout.
        xf = x.reshape(x.shape[0], -1).astype(jnp.float32)
        out = jnp.matmul(W.astype(jnp.float32), xf, precision=precision)
        return out.reshape(x.shape).astype(x.dtype)

    with jax.named_scope("consensus.round"):
        return jax.tree.map(mix_leaf, stacked)


# The largest agent count whose round is written out term by term
# (:func:`_rows_by_sum`: N*N multiply-adds a leaf in the traced program,
# zeros of W included since W may be traced, and N vector operations per
# byte moved).  Timed on a v5e against the one contraction a leaf at
# N = 4, 8, 16 and 32 (PERF.md, PR 27; ms a round per GB of state): 5.5
# and 6.8 against 12.8 and 9.7 at 4 and 8, then bound by the vector unit
# and slower, 10.1 against 9.5 at 16 and 19.7 against 8.2 at 32.
_ROWS_BY_SUM_MAX_AGENTS = 8


def _rows_by_sum(W: jax.Array, xf: jax.Array) -> jax.Array:
    """``out[i] = sum_j W[i, j] * xf[j]`` as the explicit weighted sum of
    the N leading slices of an f32 array of any shape: elementwise
    arithmetic that XLA fuses into passes over the array where it lies
    (contracting over a handful of agents with a matmul makes the TPU
    compiler re-tile the array first)."""
    n = xf.shape[0]
    rows = []
    for i in range(n):
        acc = W[i, 0] * xf[0]
        for j in range(1, n):
            acc = acc + W[i, j] * xf[j]
        rows.append(acc)
    return jnp.stack(rows)


def _rows_by_dot(W: jax.Array, xf: jax.Array) -> jax.Array:
    """The same contraction as one ``dot_general`` over the leading axis,
    on the array's own shape."""
    return jax.lax.dot_general(
        W, xf, (((1,), (0,)), ((), ())),
        precision=jax.lax.Precision.HIGHEST,
    )


def _weighted_rows(W: jax.Array, xf: jax.Array) -> jax.Array:
    """``out[i] = sum_j W[i, j] * xf[j]`` on an f32 array of any shape:
    the dense engine's round on one leaf (:func:`leaf_mix`).

    Up to ``_ROWS_BY_SUM_MAX_AGENTS`` agents this is :func:`_rows_by_sum`,
    the form a v5e runs fastest there; beyond, :func:`_rows_by_dot`.
    The agent count is static, so a program holds one of the two.
    """
    Wf = W.astype(jnp.float32)
    if xf.shape[0] <= _ROWS_BY_SUM_MAX_AGENTS:
        return _rows_by_sum(Wf, xf)
    return _rows_by_dot(Wf, xf)


def leaf_mix(stacked: Pytree, W: jax.Array) -> Pytree:
    """One gossip round, leaf by leaf on each leaf's own shape: the same
    ``x_a <- sum_b W[a,b] x_b`` in f32 as :func:`dense_mix` (storage dtype
    kept per leaf), contracted over the agent axis on the leaf's own
    shape (:func:`_weighted_rows`) instead of by a matmul on its 2-D
    reshape.  The two differ by accumulation order only; this is the
    dense engine's round, and :func:`dense_mix` its oracle.
    """

    def mix_leaf(x: jax.Array) -> jax.Array:
        return _weighted_rows(W, x.astype(jnp.float32)).astype(x.dtype)

    with jax.named_scope("consensus.round"):
        return jax.tree.map(mix_leaf, stacked)


# --------------------------------------------------------------------- #
# Stale-weighted mixing (the async gossip runtime's device program)      #
# --------------------------------------------------------------------- #
def stale_weight_matrix(
    W: jax.Array, age: jax.Array, *, tau
) -> jax.Array:
    """Effective mixing matrix under per-agent publication staleness.

    ``age[j]`` counts rounds since agent ``j`` last published its
    parameters (the async runtime's double-buffer model: local compute
    runs on buffer A while neighbors mix against the last *published*
    buffer B).  Stale contributions are down-weighted by ``1/(1+age)``
    (the stale-tolerant mixing of arXiv:2002.01119 §3) and DROPPED
    beyond the hard staleness bound ``tau``; the dropped/decayed mass
    of each row moves onto the self edge, so every row still sums to
    exactly what it did before — row-stochasticity is restored on
    device, no host round-trip.

    Self edges never decay (an agent is never stale to itself).  With
    ``age == 0`` everywhere the scale is exactly 1.0 and the result is
    bitwise ``W`` — the async-with-neutral-knobs oracle rides on this.
    """
    W = jnp.asarray(W, jnp.float32)
    n = W.shape[0]
    agef = jnp.asarray(age).astype(jnp.float32)
    scale = jnp.where(agef <= jnp.float32(tau), 1.0 / (1.0 + agef), 0.0)
    eye = jnp.eye(n, dtype=bool)
    off = jnp.where(eye, 0.0, W)
    off_eff = jnp.where(eye, 0.0, W * scale[None, :])
    dropped = jnp.sum(off - off_eff, axis=1)
    # where-placement (not addition) keeps surviving off-diagonal
    # entries bitwise untouched.
    return jnp.where(
        eye, (jnp.diagonal(W) + dropped)[:, None], off_eff
    )


def presence_weight_matrix(W: jax.Array, present: jax.Array) -> jax.Array:
    """Effective mixing matrix when some agents sit a round out.

    ``present[j]`` is 1.0/True for agents participating in this round
    (deadline-enforced rounds drop rather than wait: a straggler that
    missed the round deadline contributes nothing).  Edges to absent
    agents get zero weight with the mass moved to the self edge (row
    sums preserved on device); an absent agent's own row becomes the
    identity — it keeps its value and re-joins next round.  With
    everyone present the result is bitwise ``W``.
    """
    W = jnp.asarray(W, jnp.float32)
    n = W.shape[0]
    p = jnp.asarray(present).astype(jnp.float32)
    eye = jnp.eye(n, dtype=bool)
    off = jnp.where(eye, 0.0, W)
    off_eff = jnp.where(eye, 0.0, W * p[None, :])
    dropped = jnp.sum(off - off_eff, axis=1)
    W_eff = jnp.where(eye, (jnp.diagonal(W) + dropped)[:, None], off_eff)
    return jnp.where(
        p[:, None] > 0.0, W_eff, jnp.eye(n, dtype=jnp.float32)
    )


def stale_weighted_mix(
    stacked: Pytree,
    published: Pytree,
    W_eff: jax.Array,
    *,
    precision: jax.lax.Precision = jax.lax.Precision.HIGHEST,
    like_leaf_mix: bool = False,
) -> Pytree:
    """One stale-weighted gossip round on double-buffered state:
    ``x_i <- W_eff[i, i] * x_i + sum_{j != i} W_eff[i, j] * pub_j``.

    Neighbor contributions come from the *published* buffer (the last
    state each agent shipped), the self term from the live buffer (an
    agent always has its own fresh value).  Computed as one GEMM per
    leaf/bucket plus a rank-local diagonal correction,
    ``W_eff @ pub + diag(W_eff) * (x - pub)`` — when ``pub`` carries
    the same bits as ``x`` (every agent just published) the correction
    is exactly zero and the round is bitwise :func:`dense_mix` under
    ``W_eff``.  ``like_leaf_mix`` takes the product ``W_eff @ pub`` in
    :func:`leaf_mix`'s arithmetic instead (the dense engine's round: the
    identity then holds bitwise against that).
    """
    d = jnp.diagonal(jnp.asarray(W_eff, jnp.float32))

    def leaf(xv: jax.Array, pv: jax.Array) -> jax.Array:
        if like_leaf_mix:
            xf, pf = xv.astype(jnp.float32), pv.astype(jnp.float32)
            dd = d.reshape((-1,) + (1,) * (xf.ndim - 1))
            out = _weighted_rows(W_eff, pf) + dd * (xf - pf)
            return out.astype(xv.dtype)
        xf = xv.reshape(xv.shape[0], -1).astype(jnp.float32)
        pf = pv.reshape(pv.shape[0], -1).astype(jnp.float32)
        out = jnp.matmul(
            jnp.asarray(W_eff, jnp.float32), pf, precision=precision
        )
        out = out + d[:, None] * (xf - pf)
        return out.reshape(xv.shape).astype(xv.dtype)

    return jax.tree.map(leaf, stacked, published)


# --------------------------------------------------------------------- #
# Byzantine-robust aggregation kernels (clipped / trimmed / median)     #
# --------------------------------------------------------------------- #
# The robust family follows the effective-matrix discipline of
# :func:`stale_weight_matrix`: each defense is expressed as either an
# effective mixing matrix (clipping) or a zero-at-neutral additive
# correction on top of the plain GEMM (trimming), so that at the neutral
# knobs — ``radius=inf`` / ``trim=0`` — the computation runs the exact
# same ops as :func:`dense_mix` / :func:`stale_weighted_mix` and the
# result is bitwise identical.  All kernels are layout-agnostic: they
# serve the stacked tree and the fused ``{dtype: (N, P)}`` buffer dict
# alike, and the clipping radius is measured over the agent's WHOLE
# flattened parameter vector (summed across leaves/buckets).


def pairwise_sq_dists(
    stacked: Pytree,
    neighbors: Pytree | None = None,
    *,
    precision: jax.lax.Precision = jax.lax.Precision.HIGHEST,
) -> jax.Array:
    """(N, N) squared L2 distances between agents' full parameter vectors.

    ``sq[i, j] = || row_i(stacked) - row_j(neighbors or stacked) ||^2``
    summed over every leaf — computed per leaf/bucket as one Gram GEMM
    (``X Y^T``) plus rank-1 corrections, so the fused layout pays
    O(dtype-buckets) GEMMs, never materializing an (N, N, P) tensor.
    ``neighbors`` defaults to ``stacked`` (synchronous gossip); the async
    double-buffer path passes the *published* buffers so ``sq[i, j]`` is
    the distance from agent i's live value to agent j's publication.
    """
    xs = jax.tree.leaves(stacked)
    ys = xs if neighbors is None else jax.tree.leaves(neighbors)
    total = None
    for xv, yv in zip(xs, ys):
        xf = xv.reshape(xv.shape[0], -1).astype(jnp.float32)
        yf = yv.reshape(yv.shape[0], -1).astype(jnp.float32)
        g = jnp.matmul(xf, yf.T, precision=precision)
        sx = jnp.sum(xf * xf, axis=1)
        sy = jnp.sum(yf * yf, axis=1)
        sq = sx[:, None] + sy[None, :] - 2.0 * g
        total = sq if total is None else total + sq
    return jnp.maximum(total, 0.0)


def clip_weight_matrix(
    W: jax.Array, sq_dists: jax.Array, radius
) -> Tuple[jax.Array, jax.Array]:
    """Effective mixing matrix with neighbor deltas clipped at ``radius``.

    Clipped gossip rewrites ``x_i + sum_j W_ij * clip_r(x_j - x_i)`` as a
    row-stochastic GEMM: scaling a neighbor delta by
    ``s_ij = min(1, r_i / ||x_j - x_i||)`` is exactly the edge reweighting
    ``W_ij <- W_ij * s_ij`` with the lost mass moved onto the self edge —
    so one clipped round is :func:`dense_mix` under this matrix, and a
    lying agent's arbitrarily large pull is bounded by ``r_i * W_ij``
    (the Gorbunov/Karimireddy clipped-gossip estimator family).

    ``radius`` is a scalar or per-receiver ``(N,)`` vector (see
    :func:`adaptive_clip_radius`).  NaN distances (a poisoned payload)
    clip to zero weight.  With ``radius=inf`` the scale is exactly 1.0
    and the result is bitwise ``W`` — the robust-with-neutral-knobs
    oracle rides on this, same discipline as :func:`stale_weight_matrix`.
    Returns ``(W_eff, clipped_mass)`` where ``clipped_mass`` is the total
    absolute edge weight moved onto self edges (0.0 when nothing
    clipped) — the obs plane's detection signal.
    """
    W = jnp.asarray(W, jnp.float32)
    n = W.shape[0]
    r = jnp.broadcast_to(jnp.asarray(radius, jnp.float32), (n,))
    norm = jnp.sqrt(sq_dists)
    norm = jnp.where(jnp.isnan(norm), jnp.inf, norm)
    s = jnp.where(
        norm <= r[:, None],
        jnp.float32(1.0),
        r[:, None] / jnp.maximum(norm, jnp.float32(1e-30)),
    )
    # A non-finite or negative radius row clips everything to self-hold.
    s = jnp.where(jnp.isnan(s) | (s < 0.0), jnp.float32(0.0), s)
    eye = jnp.eye(n, dtype=bool)
    off = jnp.where(eye, 0.0, W)
    off_eff = jnp.where(eye, 0.0, W * s)
    dropped = jnp.sum(off - off_eff, axis=1)
    # where-placement (not addition) keeps surviving off-diagonal
    # entries bitwise untouched (stale_weight_matrix discipline).
    W_eff = jnp.where(eye, (jnp.diagonal(W) + dropped)[:, None], off_eff)
    clipped_mass = jnp.sum(jnp.abs(off) - jnp.abs(off_eff))
    return W_eff, clipped_mass


def adaptive_clip_radius(
    W: jax.Array, sq_dists: jax.Array, multiplier
) -> jax.Array:
    """Per-receiver adaptive clipping radius: ``multiplier`` times the
    median neighbor-delta norm.

    A fixed radius must be tuned to the (drifting) scale of honest
    disagreement; anchoring it to each receiver's *median* incident delta
    norm keeps honest edges unclipped (s=1 for at least half the
    neighborhood) while an outlier sits far above the median and gets
    clipped to median-scale pull — robust as long as the honest
    neighbors are the majority, the same f < n/2 breakdown point as
    trimming.  ``multiplier=inf`` returns ``inf`` rows exactly (the
    neutral knob survives the composition), and an isolated agent's
    radius is 0.
    """
    W = jnp.asarray(W, jnp.float32)
    n = W.shape[0]
    eye = jnp.eye(n, dtype=bool)
    support = jnp.logical_and(W != 0.0, ~eye)
    norm = jnp.sqrt(jnp.maximum(sq_dists, 0.0))
    norm = jnp.where(jnp.isnan(norm), jnp.inf, norm)
    med = jnp.nanmedian(jnp.where(support, norm, jnp.nan), axis=1)
    med = jnp.where(jnp.isnan(med), jnp.float32(0.0), med)
    mult = jnp.asarray(multiplier, jnp.float32)
    return jnp.where(
        jnp.isinf(mult), jnp.float32(jnp.inf), mult * med
    ) * jnp.ones((n,), jnp.float32)


def clipped_mix(
    stacked: Pytree,
    W: jax.Array,
    radius,
    *,
    adaptive: bool = False,
    published: Pytree | None = None,
    precision: jax.lax.Precision = jax.lax.Precision.HIGHEST,
    like_leaf_mix: bool = False,
) -> Tuple[Pytree, jax.Array]:
    """One clipped-gossip round; returns ``(mixed, clipped_mass)``.

    ``published=None`` is the synchronous round (:func:`dense_mix` under
    the clipped matrix); passing the async double buffer composes with
    staleness — hand the *stale-decayed* ``W_eff`` in as ``W`` and the
    clip applies on top of the decay, measuring each delta from the
    receiver's live value to the neighbor's publication.  ``adaptive``
    reinterprets ``radius`` as the :func:`adaptive_clip_radius`
    multiplier.  With ``radius=inf`` (adaptive or not) the effective
    matrix is bitwise ``W`` and the round is bitwise the plain one
    (``like_leaf_mix``: the plain one is :func:`leaf_mix`).
    """
    sq = pairwise_sq_dists(
        stacked, published, precision=precision
    )
    r = adaptive_clip_radius(W, sq, radius) if adaptive else radius
    W_eff, mass = clip_weight_matrix(W, sq, r)
    if published is not None:
        mixed = stale_weighted_mix(
            stacked, published, W_eff, precision=precision,
            like_leaf_mix=like_leaf_mix,
        )
    elif like_leaf_mix:
        mixed = leaf_mix(stacked, W_eff)
    else:
        mixed = dense_mix(stacked, W_eff, precision=precision)
    return mixed, mass


def trim_counts(W, trim) -> jax.Array:
    """Per-receiver trim depth ``t_i`` for :func:`trimmed_mix`.

    An integer ``trim`` applies uniformly; ``trim="median"`` picks the
    maximal depth ``(deg_i - 1) // 2`` that still keeps the central one
    (odd degree) or two (even degree) neighbor contributions — the
    coordinate-wise median aggregator as the extreme of the trimmed-mean
    family (degree 2 keeps both neighbors: the median of two values IS
    their mean, so a ring is already at its breakdown point).
    """
    W = jnp.asarray(W, jnp.float32)
    n = W.shape[0]
    eye = jnp.eye(n, dtype=bool)
    deg = jnp.sum(
        jnp.logical_and(W != 0.0, ~eye).astype(jnp.int32), axis=1
    )
    if isinstance(trim, str):
        if trim != "median":
            raise ValueError(
                f"trim must be an int or 'median', got {trim!r}"
            )
        return jnp.maximum((deg - 1) // 2, 0)
    return jnp.full((n,), int(trim), jnp.int32)


def trimmed_mix(
    stacked: Pytree,
    W: jax.Array,
    trim: jax.Array,
    *,
    published: Pytree | None = None,
    precision: jax.lax.Precision = jax.lax.Precision.HIGHEST,
    like_leaf_mix: bool = False,
) -> Tuple[Pytree, jax.Array]:
    """One coordinate-wise trimmed-mean gossip round; returns
    ``(mixed, trimmed_mass)``.

    For each receiver i and coordinate p, the ``t_i`` highest and ``t_i``
    lowest neighbor contributions (ranked per coordinate among i's
    in-neighbors, index tie-break) are redirected onto the self edge —
    rows stay stochastic, and with ``f <= t_i`` liars per neighborhood
    every adversarial coordinate is discarded (the Yin et al. 2018
    coordinate-trimmed-mean estimator on gossip weights).  Computed as
    the plain GEMM plus a correction
    ``sum_j W_ij m_ijp (x_i[p] - nb_j[p])`` that is exactly 0.0 at
    ``trim=0`` — the round is then bitwise :func:`dense_mix` (sync) /
    :func:`stale_weighted_mix` (async, via ``published``).  ``trim`` is
    the per-receiver ``(N,)`` depth from :func:`trim_counts` (pass
    ``trim_counts(W, "median")`` for the median aggregator).  Cost is
    O(N^2 P) comparisons per bucket — the price of per-coordinate ranks;
    N is the agent count, so the constant is small.  ``like_leaf_mix``
    takes the plain product in :func:`leaf_mix`'s arithmetic (on the
    same ``(N, size)`` view the ranks use), so that the ``trim=0``
    identity holds against the dense engine's round.

    ``trimmed_mass`` is the average per-coordinate edge weight redirected
    (summed over leaves; 0.0 when nothing trimmed).
    """
    W = jnp.asarray(W, jnp.float32)
    n = W.shape[0]
    eye = jnp.eye(n, dtype=bool)
    support = jnp.logical_and(W != 0.0, ~eye)
    supf = support.astype(jnp.float32)
    deg = jnp.sum(supf, axis=1)
    tf = jnp.asarray(trim, jnp.int32).astype(jnp.float32)
    W_off = jnp.where(support, W, 0.0)
    d = jnp.diagonal(W)
    idx = jnp.arange(n)
    tie_lo = (idx[:, None] < idx[None, :])[:, :, None]

    xs, treedef = jax.tree_util.tree_flatten(stacked)
    ps = xs if published is None else jax.tree.leaves(published)
    outs = []
    mass = jnp.float32(0.0)
    for xv, pv in zip(xs, ps):
        xf = xv.reshape(n, -1).astype(jnp.float32)
        pf = pv.reshape(n, -1).astype(jnp.float32)
        if like_leaf_mix:
            base = _weighted_rows(W, pf)
        else:
            base = jnp.matmul(W, pf, precision=precision)
        if published is not None:
            base = base + d[:, None] * (xf - pf)
        # rank[i, j, p]: how many of receiver i's neighbors sort strictly
        # below contribution j at coordinate p (index tie-break keeps the
        # ranking a permutation under duplicates).
        lt = pf[:, None, :] < pf[None, :, :]
        tie = jnp.logical_and(pf[:, None, :] == pf[None, :, :], tie_lo)
        cmp = jnp.logical_or(lt, tie).astype(jnp.float32)
        rank = jnp.einsum("ik,kjp->ijp", supf, cmp)
        m = support[:, :, None] & (
            (rank < tf[:, None, None])
            | (rank >= (deg - tf)[:, None, None])
        )
        delta = xf[:, None, :] - pf[None, :, :]
        corr = jnp.einsum("ij,ijp->ip", W_off, jnp.where(m, delta, 0.0))
        mass = mass + jnp.einsum(
            "ij,ijp->", W_off, m.astype(jnp.float32)
        ) / jnp.float32(pf.shape[1])
        outs.append((base + corr).reshape(xv.shape).astype(xv.dtype))
    return jax.tree_util.tree_unflatten(treedef, outs), mass


def _sq_dev_from_mean(stacked: Pytree) -> jax.Array:
    """Per-agent squared L2 distance from the across-agent mean, summed over
    every leaf (i.e. over the agent's whole flattened parameter vector)."""
    leaves = jax.tree.leaves(stacked)
    total = None
    for x in leaves:
        mean = x.mean(axis=0, keepdims=True)
        d = (x - mean).astype(jnp.float32)
        sq = jnp.sum(d * d, axis=tuple(range(1, d.ndim)))
        total = sq if total is None else total + sq
    return total


def agent_deviations(stacked: Pytree) -> jax.Array:
    """(N,) array: each agent's L2 distance from the mean parameter vector.

    Parity: ``basic_deviation_metric`` + ``_get_deviation_dict``
    (``mixer.py:5-6, 57-66``) — the norm is over the agent's *entire*
    flattened parameter vector.
    """
    with jax.named_scope("consensus.residual"):
        return jnp.sqrt(_sq_dev_from_mean(stacked))


def max_deviation(stacked: Pytree) -> jax.Array:
    """Scalar: max over agents of :func:`agent_deviations` — the residual the
    eps-stopping rule compares against (``mixer.py:40-41, 51-55``)."""
    dev = agent_deviations(stacked)
    with jax.named_scope("consensus.residual"):
        return jnp.max(dev)


def max_std(stacked: Pytree) -> jax.Array:
    """Max over parameters of the across-agent standard deviation.

    Parity: ``Mixer.get_max_parameters_std`` (``mixer.py:82-84``).
    """
    leaves = jax.tree.leaves(stacked)
    return jnp.max(
        jnp.stack([jnp.max(jnp.std(x.astype(jnp.float32), axis=0)) for x in leaves])
    )


def weighted_lift(stacked: Pytree, weights: jax.Array) -> Pytree:
    """Rescale each agent's value by ``w_i / mean(w)`` so that plain average
    consensus computes the *weighted* average.

    This is the reference's weighting trick: ``y_i = x_i w_i / mean_w``
    implies ``(1/n) sum y_i = (sum w_i x_i) / (sum w_i)``
    (``consensus_asyncio.py:231`` and the derivation at :288-293).
    """
    w = weights / jnp.mean(weights)

    def lift(x: jax.Array) -> jax.Array:
        return x * w.reshape((-1,) + (1,) * (x.ndim - 1)).astype(x.dtype)

    return jax.tree.map(lift, stacked)


def weighted_readout(stacked_num: Pytree, stacked_den: jax.Array) -> Pytree:
    """Finish a push-sum style weighted consensus: divide the mixed numerator
    by the mixed scalar weight channel.

    Used when per-agent weights are themselves gossiped alongside the values
    (the generalization of the reference's master-computed ``mean_weight``,
    which a masterless SPMD program cannot get for free).
    """

    def div(x: jax.Array) -> jax.Array:
        return x / stacked_den.reshape((-1,) + (1,) * (x.ndim - 1)).astype(x.dtype)

    return jax.tree.map(div, stacked_num)
