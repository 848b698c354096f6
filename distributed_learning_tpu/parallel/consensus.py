"""Consensus (gossip) engines: the TPU-native runtime replacing the
reference's three backends.

The reference implements one conceptual protocol three times — shared-memory
(``utils/consensus_simple/mixer.py``), asyncio queues
(``utils/consensus_asyncio.py``), TCP + pickle (``utils/consensus_tcp/``) —
all interpreting "each agent averages with its neighbors until converged" as
runtime message passing coordinated by a master.

Here the protocol is *compiled*: a :class:`ConsensusEngine` owns a mixing
matrix and executes whole gossip rounds as jitted XLA programs.

Two execution modes, one API:

* **dense** (``mesh=None``): all N agents' replicas live on the current
  device as a leading axis; one round is one batched matmul (MXU).  This is
  the analogue of the asyncio simulator — N logical nodes, no cluster — and
  is also the fastest layout when N models fit on one chip.
* **sharded** (``mesh=`` a ``jax.sharding.Mesh`` with an ``agents`` axis):
  one agent per device; one round is ``chromatic_index`` many
  ``jax.lax.ppermute`` steps over ICI (compiled from
  :class:`~distributed_learning_tpu.parallel.schedule.MatchingSchedule`),
  residuals via ``pmean``/``pmax``.  The master's round lifecycle
  (NEW_ROUND -> CONVERGED -> DONE, ``consensus_asyncio.py:120-174``)
  collapses into a ``lax.while_loop`` on the device.

The eps-or-times stopping rule, deviation metrics, and the weighted
(sample-count) averaging trick all keep the reference's semantics — see the
per-method parity notes.
"""

from __future__ import annotations

import functools
from typing import (
    Any,
    Dict,
    Hashable,
    Mapping,
    NamedTuple,
    Optional,
    Sequence,
    Tuple,
)

import jax
import jax.numpy as jnp
import numpy as np
from jax import lax
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

from distributed_learning_tpu.obs import get_registry, get_tracer
from distributed_learning_tpu.ops import mixing as ops
from .schedule import MatchingSchedule, chebyshev_omegas, validate_mixing_matrix
from .topology import Topology, gamma as exact_gamma

Pytree = Any

__all__ = [
    "ConsensusEngine",
    "Mixer",
    "AsyncGossipState",
    "make_agent_mesh",
    "ring_offset_weights",
    "local_ring_mix",
]


def _public_call(name: str):
    """Open the host span ``name`` at the top of a public engine call,
    with ``call=<n>`` (the engine's running call count) so that the
    spans :meth:`ConsensusEngine._launch` opens inside it share its
    identifier."""

    def deco(method):
        @functools.wraps(method)
        def wrapper(self, *args, **kwargs):
            self._calls += 1
            with get_tracer().span(name, call=self._calls):
                return method(self, *args, **kwargs)

        return wrapper

    return deco


class AsyncGossipState(NamedTuple):
    """Device-side carry of the simulated asynchronous gossip runtime
    (docs/async_runtime.md): the double-buffer model on one chip.

    ``pub`` is buffer B — the last state each agent *published* (what
    neighbors mix against); the live params are buffer A.  ``age[j]``
    counts gossip rounds since agent ``j`` last published; ``rnd`` is
    the global async round counter (drives the per-agent publish
    periods).  A pytree, so the whole carry threads through jit.
    """

    pub: Pytree
    age: jax.Array  # (n,) int32
    rnd: jax.Array  # () int32


def make_agent_mesh(n: int, *, axis_name: str = "agents") -> Mesh:
    """Mesh over the first ``n`` available devices with a single agent axis."""
    devices = jax.devices()
    if len(devices) < n:
        raise ValueError(f"need {n} devices for {n} agents, have {len(devices)}")
    return Mesh(np.array(devices[:n]), (axis_name,))


def ring_offset_weights(
    W: np.ndarray,
) -> Tuple[np.ndarray, np.ndarray, np.ndarray, int]:
    """Decompose a mixing matrix's off-diagonal onto signed ring offsets.

    Returns ``(self_w, w_fwd, w_bwd, k_hops)``: ``w_fwd[i, k-1]`` weights
    agent ``(i-k) % n`` (reached by ``k`` forward relay hops on the device
    ring) and ``w_bwd[i, k-1]`` weights ``(i+k) % n``; ``k_hops`` is the
    largest offset carrying any weight — the number of relay rounds a
    routed gossip round needs.  For ``n`` even the antipodal offset
    ``n/2`` is reachable both ways and is counted once (forward).  Works
    for any square matrix — symmetry is not assumed, so directed
    (push-sum) matrices decompose too.
    """
    W = np.asarray(W)
    n = W.shape[0]
    k_cap = n // 2
    w_fwd = np.zeros((n, max(k_cap, 1)), np.float32)
    w_bwd = np.zeros((n, max(k_cap, 1)), np.float32)
    i = np.arange(n)
    for k in range(1, k_cap + 1):
        w_fwd[:, k - 1] = W[i, (i - k) % n]
        if not (n % 2 == 0 and k == n // 2):
            w_bwd[:, k - 1] = W[i, (i + k) % n]
    k_hops = 0
    for k in range(k_cap, 0, -1):
        if w_fwd[:, k - 1].any() or w_bwd[:, k - 1].any():
            k_hops = k
            break
    return np.diag(W).astype(np.float32), w_fwd, w_bwd, k_hops


def local_ring_mix(
    x: Pytree,
    self_w: jax.Array,
    w_fwd: jax.Array,
    w_bwd: jax.Array,
    k_hops: jax.Array,
    *,
    axis_name: str,
    n: int,
    use_fwd: bool = True,
    use_bwd: bool = True,
) -> Pytree:
    """One gossip round under traced per-offset weights, routed over the
    device ring with <=k-hop relays (SURVEY §7 hard part 1: multi-hop
    routing for graphs whose edges are not physical ring neighbors).

    Runs inside ``shard_map``; per-device inputs are ``self_w`` (1,) and
    ``w_fwd``/``w_bwd`` (1, k_cap) rows of :func:`ring_offset_weights`.
    Each relay hop rotates the value one step in both ring directions (two
    ``ppermute``s) and accumulates that offset's weighted contribution, so
    one round moves ``2*k_hops`` shard-sized messages per device — scaling
    with the graph's maximal ring span instead of the agent count like an
    all_gather.  Both the weights and ``k_hops`` are traced: resampling
    the topology each epoch reuses the compiled program.  Accumulation is
    float32 regardless of the state dtype (~1e-4 consensus residuals would
    be floored by bf16), cast back once at the end.

    ``use_fwd``/``use_bwd`` are compile-time flags: a direction whose
    weights the (concrete) decomposition shows identically zero is skipped
    statically — a unidirectional push-sum ring then moves ``k_hops``
    messages per round, not ``2*k_hops``.
    """
    fwd_pairs = [(j, (j + 1) % n) for j in range(n)]
    bwd_pairs = [(j, (j - 1) % n) for j in range(n)]

    def scale(v: jax.Array, s: jax.Array) -> jax.Array:
        return v.astype(jnp.float32) * s

    def body(k, carry):
        fwd, bwd, acc = carry
        terms = []
        if use_fwd:
            fwd = jax.tree.map(
                lambda v: lax.ppermute(v, axis_name, fwd_pairs), fwd
            )
            wf = lax.dynamic_index_in_dim(w_fwd[0], k, keepdims=False)
            terms.append((fwd, wf))
        if use_bwd:
            bwd = jax.tree.map(
                lambda v: lax.ppermute(v, axis_name, bwd_pairs), bwd
            )
            wb = lax.dynamic_index_in_dim(w_bwd[0], k, keepdims=False)
            terms.append((bwd, wb))
        for nb, w in terms:
            acc = jax.tree.map(lambda a, v: a + scale(v, w), acc, nb)
        return fwd, bwd, acc

    with jax.named_scope("consensus.round"):
        acc0 = jax.tree.map(lambda v: scale(v, self_w[0]), x)
        _, _, acc = lax.fori_loop(0, k_hops, body, (x, x, acc0))
        return jax.tree.map(lambda a, v: a.astype(v.dtype), acc, x)


def local_sq_deviation(x: Pytree, axis_name: str) -> jax.Array:
    """This shard's squared L2 distance from the global mean vector (runs
    inside ``shard_map``; the sharded analogue of
    ``ops.agent_deviations``**2)."""
    with jax.named_scope("consensus.residual"):
        total = jnp.float32(0.0)
        for leaf in jax.tree.leaves(x):
            # graftlint: disable=raw-collective-in-shard-map -- consensus residual: the pmean over agents IS the statistic (distance from the global mean), not a TP exit
            mean = lax.pmean(leaf.astype(jnp.float32), axis_name)
            d = leaf.astype(jnp.float32) - mean
            total = total + jnp.sum(d * d)
        return total


class ConsensusEngine:
    """Executes gossip rounds on stacked per-agent pytrees.

    Parameters
    ----------
    W:
        (n, n) symmetric row-stochastic mixing matrix.
    mesh:
        Optional mesh with ``axis_name`` of size n; if given, rounds run as
        SPMD ppermute schedules, else as dense batched matmuls.
    precision:
        Matmul precision for the dense path (HIGHEST: consensus residuals
        of ~1e-4 would be floored by bf16 accumulation).
    fused:
        Run every mixing program on the fused flat-buffer layout
        (:func:`~distributed_learning_tpu.ops.mixing.flatten_stacked`):
        the state is raveled once at program entry into one contiguous
        ``(N, P)`` buffer per storage dtype, the whole gossip loop runs
        on those O(buckets) buffers — O(1) ppermutes/GEMMs per round and
        direction instead of O(leaves) — and unraveled once at exit.
        ``fused=False`` keeps the per-leaf programs (the exact-equality
        oracle; results differ only by GEMM accumulation order, ~1 ulp).
    """

    def __init__(
        self,
        W: np.ndarray,
        *,
        mesh: Optional[Mesh] = None,
        axis_name: str = "agents",
        precision: jax.lax.Precision = jax.lax.Precision.HIGHEST,
        fused: bool = True,
    ):
        self.W = validate_mixing_matrix(W)
        self.n = self.W.shape[0]
        self.axis_name = axis_name
        self.mesh = mesh
        self.precision = precision
        self.fused = bool(fused)
        self.gamma = exact_gamma(self.W)
        self.schedule = MatchingSchedule.from_matrix(self.W)
        if mesh is not None:
            if axis_name not in mesh.axis_names:
                raise ValueError(f"mesh has no axis {axis_name!r}")
            if mesh.shape[axis_name] != self.n:
                raise ValueError(
                    f"mesh axis {axis_name!r} has size {mesh.shape[axis_name]}, "
                    f"need {self.n} (one device per agent)"
                )
        self._W_dev = jnp.asarray(self.W, dtype=jnp.float32)
        self._self_w = jnp.asarray(self.schedule.self_weights, dtype=jnp.float32)
        self._match_w = jnp.asarray(self.schedule.weights, dtype=jnp.float32)
        self._jit_cache: Dict[str, Any] = {}
        self._calls = 0  # public calls so far: the spans' ``call=`` id

    # ------------------------------------------------------------------ #
    # Local (per-shard) building blocks                                  #
    # ------------------------------------------------------------------ #
    def _local_mix_once(self, x: Pytree, self_w: jax.Array, match_w: jax.Array) -> Pytree:
        """One gossip round on the local shard: self term + one ppermute per
        matching (color class) of the mixing graph."""
        ax = self.axis_name

        def scale(v: jax.Array, s: jax.Array) -> jax.Array:
            return (v.astype(jnp.float32) * s).astype(v.dtype)

        with jax.named_scope("consensus.round"):
            acc = jax.tree.map(lambda v: scale(v, self_w[0]), x)
            for r in range(self.schedule.num_rounds):
                pairs = self.schedule.ppermute_pairs(r)
                nb = jax.tree.map(lambda v: lax.ppermute(v, ax, pairs), x)
                acc = jax.tree.map(
                    lambda a, b: a + scale(b, match_w[r, 0]), acc, nb
                )
            return acc

    def _ring_offset_weights(
        self, W: np.ndarray
    ) -> Tuple[np.ndarray, np.ndarray, np.ndarray, int]:
        return ring_offset_weights(W)

    def _local_ring_mix(
        self,
        x: Pytree,
        self_w: jax.Array,
        w_fwd: jax.Array,
        w_bwd: jax.Array,
        k_hops: jax.Array,
    ) -> Pytree:
        return local_ring_mix(
            x, self_w, w_fwd, w_bwd, k_hops,
            axis_name=self.axis_name, n=self.n,
        )

    def _local_allgather_mix(self, x: Pytree, W_row: jax.Array) -> Pytree:
        """One gossip round against a *traced* mixing row: all_gather the
        agent axis and contract with this device's row of W (masked
        all-to-all — the dynamic-topology fallback when no static ppermute
        schedule exists)."""

        def leaf(v: jax.Array) -> jax.Array:
            ag = lax.all_gather(v, self.axis_name, axis=0, tiled=True)
            vf = ag.astype(jnp.float32).reshape(self.n, -1)
            out = jnp.matmul(
                W_row.astype(jnp.float32), vf, precision=self.precision
            )
            return out.reshape(v.shape).astype(v.dtype)

        with jax.named_scope("consensus.round"):
            return jax.tree.map(leaf, x)

    def _local_sq_deviation(self, x: Pytree) -> jax.Array:
        return local_sq_deviation(x, self.axis_name)

    # ------------------------------------------------------------------ #
    # Global (dense) building blocks                                     #
    # ------------------------------------------------------------------ #
    def _dense_mix_once(self, x: Pytree) -> Pytree:
        return ops.dense_mix(x, self._W_dev, precision=self.precision)

    @staticmethod
    def _dense_residual(x: Pytree) -> jax.Array:
        """Max agent deviation of a (possibly fused) stacked state — the
        eps-stopping residual of the dense programs."""
        return ops.max_deviation(x)

    def _local_residual(self, x: Pytree) -> jax.Array:
        """The sharded residual: this shard's deviation, pmax'd over the
        agent axis (runs inside ``shard_map``)."""
        sq = self._local_sq_deviation(x)
        with jax.named_scope("consensus.residual"):
            return lax.pmax(jnp.sqrt(sq), self.axis_name)

    @staticmethod
    def _dense_global_avg(x: Pytree) -> Pytree:
        return jax.tree.map(
            lambda v: jnp.broadcast_to(
                v.astype(jnp.float32).mean(axis=0, keepdims=True),
                v.shape,
            ).astype(v.dtype),
            x,
        )

    def _local_global_avg(self, x: Pytree) -> Pytree:
        ax = self.axis_name
        return jax.tree.map(
            # graftlint: disable=raw-collective-in-shard-map -- exact consensus: the global average is the mixing fixed point, pmean over agents by definition
            lambda v: lax.pmean(v.astype(jnp.float32), ax).astype(v.dtype),
            x,
        )

    # ------------------------------------------------------------------ #
    # Fused flat-buffer plumbing                                         #
    # ------------------------------------------------------------------ #
    def _fuse_state_fn(self, run):
        """Wrap a state-first program onto the fused flat-buffer layout.

        ``run(state, *args)`` must take the stacked state as its first
        argument and return either the new state or a tuple whose first
        element is the state.  With ``fused=True`` the state is raveled
        into its dtype-bucket buffers ONCE at entry (a reshape+concat the
        compiler folds into the program prologue), ``run`` executes on the
        buffer pytree — every ``jax.tree.map``-built primitive in this
        module is layout-agnostic, so the same loop bodies serve both
        layouts — and the result is unraveled once at exit.  Applied to
        the *local* body when the program runs under ``shard_map`` (the
        per-device shard flattens; ppermutes then move one fused message
        per bucket instead of one per leaf).
        """
        if not self.fused:
            return run

        def wrapped(x, *args):
            buffers, layout = ops.flatten_stacked(x)
            out = run(buffers, *args)
            if isinstance(out, tuple):
                return (ops.unflatten_stacked(out[0], layout),) + tuple(
                    out[1:]
                )
            return ops.unflatten_stacked(out, layout)

        return wrapped

    def _fuse_in(self, x: Pytree) -> Pytree:
        """Fused view of the state for pure reductions (deviations,
        max_std): the statistic is leaf-order invariant, so computing it
        on the buckets turns O(leaves) reductions into O(buckets)."""
        if not self.fused:
            return x
        return ops.flatten_stacked(x)[0]

    def _note_layout(self, stacked: Pytree, rounds=None) -> None:
        """Fused-layout accounting (obs), host-side only: concrete calls
        record the bucket/leaf geometry and — when the round count is
        static — the bytes the gossip rounds touched.  Traced calls (the
        caller is inside jit) and traced round counts are skipped, same
        discipline as :meth:`_count_rounds`: never a device sync here."""
        leaves = jax.tree.leaves(stacked)
        if not leaves or any(
            isinstance(l, jax.core.Tracer) for l in leaves
        ):
            return
        try:
            layout = ops.fused_layout(stacked)
        except (ValueError, TypeError):
            return
        reg = get_registry()
        reg.gauge("consensus.leaf_count", layout.leaf_count)
        reg.gauge(
            "consensus.fused_buckets",
            layout.bucket_count if self.fused else layout.leaf_count,
        )
        if rounds is not None and not isinstance(rounds, jax.core.Tracer):
            reg.inc(
                "consensus.bytes_mixed",
                layout.bytes_per_round(self.n) * int(rounds),
            )

    @staticmethod
    def _ring_operands(decomp) -> tuple:
        """The k-hop ring programs' leading operands, on the device."""
        self_w, w_fwd, w_bwd, k_hops = decomp
        return (
            jnp.asarray(self_w),
            jnp.asarray(w_fwd),
            jnp.asarray(w_bwd),
            jnp.int32(k_hops),
        )

    def _launch(self, fn, stacked: Pytree, operands=None, *, rounds=None):
        """The host's part of one public call, each step under its own
        span inside the call's (:func:`_public_call`):
        ``consensus.layout`` (:meth:`_note_layout`),
        ``consensus.operands`` (``operands()`` builds the program's other
        arguments, the python scalars going to the device there) and
        ``consensus.dispatch`` (``fn`` enqueued; nothing waits for it)."""
        span, call = get_tracer().span, self._calls
        with span("consensus.layout", call=call):
            self._note_layout(stacked, rounds=rounds)
        with span("consensus.operands", call=call):
            args = operands() if operands is not None else ()
        with span("consensus.dispatch", call=call):
            return fn(stacked, *args)

    # ------------------------------------------------------------------ #
    # Public API                                                         #
    # ------------------------------------------------------------------ #
    def shard(self, stacked: Pytree) -> Pytree:
        """Place a stacked pytree on the mesh, agent axis sharded."""
        if self.mesh is None:
            return jax.tree.map(jnp.asarray, stacked)
        sharding = NamedSharding(self.mesh, P(self.axis_name))
        return jax.tree.map(lambda v: jax.device_put(v, sharding), stacked)

    @staticmethod
    def _count_rounds(times) -> None:
        """Gossip-round counter (obs): static round counts only — a
        traced ``times`` (caller inside jit) is counted by the caller at
        its own chunk boundary, never synced here."""
        if not isinstance(times, jax.core.Tracer):
            get_registry().inc("consensus.rounds_run", int(times))

    @_public_call("consensus.mix")
    def mix(self, stacked: Pytree, times: int = 1) -> Pytree:
        """Run exactly ``times`` gossip rounds (``Mixer.mix(times, eps=None)``
        semantics, ``mixer.py:18-41``)."""
        fn = self._get_jitted("mix")
        self._count_rounds(times)
        return self._launch(
            fn, stacked, lambda: (jnp.int32(times),), rounds=times
        )

    @_public_call("consensus.mix_until")
    def mix_until(
        self,
        stacked: Pytree,
        *,
        eps: float,
        min_times: int = 0,
        max_rounds: int = 10_000,
    ) -> Tuple[Pytree, jax.Array, jax.Array]:
        """Gossip until ``max_deviation < eps`` (and at least ``min_times``
        rounds), returning ``(state, rounds_done, final_residual)``.

        This is the reference's eps-stopping rule (``mixer.py:40-41``:
        ``(eps is None or max_dev < eps) and times_done >= times``) compiled
        into a ``lax.while_loop`` — no host round-trip per gossip iteration,
        unlike the asyncio/TCP masters which exchange CONVERGED /
        NOT_CONVERGED messages every round (``consensus_asyncio.py:297-310``).
        ``max_rounds`` bounds the loop (the reference's is unbounded).
        """
        fn = self._get_jitted("mix_until")
        get_registry().inc("consensus.mix_until.calls")
        return self._launch(
            fn,
            stacked,
            lambda: (
                jnp.float32(eps),
                jnp.int32(min_times),
                jnp.int32(max_rounds),
            ),
        )

    @_public_call("consensus.mix_until_with")
    def mix_until_with(
        self,
        stacked: Pytree,
        W,
        *,
        eps: float,
        min_times: int = 0,
        max_rounds: int = 10_000,
        route: str = "auto",
    ) -> Tuple[Pytree, jax.Array, jax.Array]:
        """Eps-stopping under a *traced* mixing matrix: the composition of
        :meth:`mix_until` (the reference's eps-or-times rule,
        ``mixer.py:40-41``, as a ``lax.while_loop``) with :meth:`mix_with`
        (time-varying graphs as runtime arguments).  Resampling the
        topology every epoch keeps both the compiled program AND the
        adaptive stopping rule; returns ``(state, rounds_done,
        final_residual)`` like ``mix_until``.

        Sharded routing matches :meth:`mix_with`: sparse graphs relay over
        the device ring with <=k hops, dense graphs use the masked
        all-to-all; ``route="auto"`` picks whichever moves less data.
        """
        W_traced, decomp = self._traced_w_dispatch(W, route)

        def stop():
            return (
                jnp.float32(eps),
                jnp.int32(min_times),
                jnp.int32(max_rounds),
            )

        get_registry().inc("consensus.mix_until.calls")
        if W_traced is not None:
            return self._launch(
                self._get_jitted("mix_until_with"),
                stacked,
                lambda: (W_traced,) + stop(),
            )
        _, w_fwd, w_bwd, _ = decomp
        fn = self._get_ring_jitted(
            "mix_until_with_ring", bool(w_fwd.any()), bool(w_bwd.any())
        )
        return self._launch(
            fn, stacked, lambda: self._ring_operands(decomp) + stop()
        )

    @_public_call("consensus.mix_pairwise")
    def mix_pairwise(
        self,
        stacked: Pytree,
        key: jax.Array,
        rounds: int,
    ) -> Pytree:
        """``rounds`` of randomized pairwise gossip (Boyd-Ghosh-Prabhakar-
        Shah 2006 — the asynchronous-gossip model the reference's whole
        literature builds on): each round one edge of the mixing graph is
        drawn uniformly and its two endpoints average,
        ``x_i, x_j <- (x_i + x_j) / 2``.

        Dense mode is the literal model: per round one edge index is
        sampled on device and the two rows are updated by gather/scatter
        inside one ``lax.scan`` — "asynchrony" costs no host round-trips.

        Sharded mode runs the natural mesh variant: each round draws a
        uniformly random **maximal matching** of the mixing graph (from a
        host-precomputed pool that covers every edge) and all matched
        pairs average simultaneously — each device talks to at most ONE
        partner per round (a single ``ppermute``), no device idles behind
        a lone active edge, and the per-round update is still an
        (I + P_M)/2 pairwise-averaging matrix, so the Boyd-style analysis
        applies with E[W] averaged over the matching pool.

        Both modes preserve the mean exactly every round and contract
        E[spread^2] at the rate lambda_2(E[W]).
        """
        # Same edge convention as MatchingSchedule.from_matrix: magnitude
        # above tolerance (SDP weights can legitimately be negative, and
        # roundoff noise must not become a full-strength averaging edge).
        upper = np.triu(self.W, 1)
        edges = np.argwhere(np.abs(upper) > 1e-12)
        if len(edges) == 0:
            return stacked
        self._count_rounds(rounds)
        if self.mesh is not None:
            return self._mix_pairwise_sharded(stacked, key, rounds, edges)
        ckey = ("pairwise", len(edges))
        if ckey not in self._jit_cache:
            edges_dev = jnp.asarray(edges, jnp.int32)

            def body(r, carry):
                x, key = carry
                e = jax.random.randint(
                    jax.random.fold_in(key, r), (), 0, edges_dev.shape[0]
                )
                i, j = edges_dev[e, 0], edges_dev[e, 1]

                def leaf(v):
                    vi = v[i].astype(jnp.float32)
                    vj = v[j].astype(jnp.float32)
                    avg = ((vi + vj) * 0.5).astype(v.dtype)
                    return v.at[i].set(avg).at[j].set(avg)

                return jax.tree.map(leaf, x), key

            def f(x, key, rounds):
                # rounds is traced: one compile per edge set, any count.
                out, _ = jax.lax.fori_loop(0, rounds, body, (x, key))
                return out

            self._jit_cache[ckey] = jax.jit(self._fuse_state_fn(f))
        return self._launch(
            self._jit_cache[ckey],
            stacked,
            lambda: (key, jnp.int32(rounds)),
            rounds=rounds,
        )

    def _random_maximal_matchings(
        self, edges: np.ndarray
    ) -> Tuple[Tuple[Tuple[int, int], ...], ...]:
        """Host-side pool of random maximal matchings of the edge set.

        Greedy completion of random edge orders; seeding one order per
        edge guarantees every edge appears in at least one matching (so
        E[W] over the pool is supported on the whole graph and consensus
        reaches every component the graph connects).  Deduplicated; a few
        extra fully-random orders add diversity on dense graphs.
        """
        cached = getattr(self, "_pairwise_matchings", None)
        if cached is not None:  # W is fixed after __init__, so is the pool
            return cached
        rng = np.random.default_rng(0x5EED)
        E = [(int(i), int(j)) for i, j in edges]

        def greedy(order):
            used, M = set(), []
            for (i, j) in order:
                if i not in used and j not in used:
                    M.append((i, j))
                    used.update((i, j))
            return tuple(sorted(M))

        pool = dict()
        for k, e in enumerate(E):
            rest = E[:k] + E[k + 1:]
            rng.shuffle(rest)
            pool.setdefault(greedy([e] + rest), None)
        for _ in range(8):
            order = list(E)
            rng.shuffle(order)
            pool.setdefault(greedy(order), None)
        # Memoized for reuse (and exposed for tests/diagnostics).
        self._pairwise_matchings = tuple(pool.keys())
        return self._pairwise_matchings

    def _mix_pairwise_sharded(
        self, stacked: Pytree, key: jax.Array, rounds: int, edges: np.ndarray
    ) -> Pytree:
        """Sharded pairwise gossip: ``lax.switch`` over one statically
        compiled ppermute per matching in the pool; the per-round matching
        index is sampled on device from the (replicated) key, so all
        devices agree on the draw without any coordination traffic."""
        matchings = self._random_maximal_matchings(edges)
        ckey = ("pairwise_sharded", matchings)
        if ckey not in self._jit_cache:
            mesh, ax, n = self.mesh, self.axis_name, self.n

            def matching_branch(M):
                pairs = [(i, j) for (i, j) in M] + [(j, i) for (i, j) in M]
                matched = np.zeros((n,), np.float32)
                for (i, j) in M:
                    matched[i] = matched[j] = 1.0
                half = jnp.asarray(0.5 * matched)  # (n,) constant

                def f(x):
                    i = lax.axis_index(ax)
                    c = half[i]  # 0.5 if this device is matched else 0.0
                    nb = jax.tree.map(
                        lambda v: lax.ppermute(v, ax, pairs), x
                    )
                    # Unmatched devices receive zeros from ppermute and
                    # keep (1 - 0) = full self weight.
                    return jax.tree.map(
                        lambda v, b: (
                            (1.0 - c) * v.astype(jnp.float32)
                            + c * b.astype(jnp.float32)
                        ).astype(v.dtype),
                        x, nb,
                    )

                return f

            branches = [matching_branch(M) for M in matchings]

            def local(x, key, rounds):
                def body(r, xx):
                    m = jax.random.randint(
                        jax.random.fold_in(key, r), (), 0, len(branches)
                    )
                    return lax.switch(m, branches, xx)

                return lax.fori_loop(0, rounds, body, x)

            self._jit_cache[ckey] = jax.jit(
                jax.shard_map(
                    self._fuse_state_fn(local),
                    mesh=mesh,
                    in_specs=(P(ax), P(), P()),
                    out_specs=P(ax),
                )
            )
        return self._launch(
            self._jit_cache[ckey],
            stacked,
            lambda: (key, jnp.int32(rounds)),
            rounds=rounds,
        )

    @_public_call("consensus.mix_chebyshev")
    def mix_chebyshev(self, stacked: Pytree, times: int) -> Pytree:
        """``times`` rounds of Chebyshev-accelerated gossip (BASELINE
        config 5: "Chebyshev-accelerated averaging").

        Uses this engine's exact ``gamma``; residual after k rounds decays
        like the scaled Chebyshev polynomial — quadratically faster in the
        spectral gap than plain mixing.  ``times`` is static (it fixes the
        scalar schedule).
        """
        key = ("cheby", int(times))
        if key not in self._jit_cache:
            omegas = chebyshev_omegas(self.gamma, int(times))
            self._jit_cache[key] = jax.jit(
                lambda x: self._run_chebyshev(x, omegas)
            )
        self._count_rounds(times)
        return self._launch(self._jit_cache[key], stacked, rounds=times)

    def _traced_w_dispatch(self, W, route: str):
        """Shared guard for the traced-W entry points.

        Returns ``(W_traced, decomposition)``: exactly one is non-None.
        ``W_traced`` (a jnp array) means "feed the traced all-to-all /
        dense program"; ``decomposition`` means "use the k-hop ring
        program with these host-decomposed weights".
        """
        if route not in ("auto", "ring", "allgather"):
            raise ValueError(f"unknown route {route!r}")
        if jnp.shape(W) != (self.n, self.n):
            raise ValueError(
                f"W must have shape ({self.n}, {self.n}), got {jnp.shape(W)}"
            )
        if self.mesh is None or isinstance(W, jax.core.Tracer):
            # Dense mode contracts with W directly; a traced W (caller is
            # inside jit) cannot be decomposed on the host, so the sharded
            # path keeps the all-to-all for it.
            if route == "ring" and self.mesh is not None:
                raise ValueError(
                    "route='ring' needs a concrete W (the k-hop "
                    "decomposition runs on the host); call outside jit or "
                    "use 'allgather'"
                )
            return jnp.asarray(W, dtype=jnp.float32), None
        route, decomp = self._route_for(np.asarray(W, dtype=np.float32), route)
        if route == "allgather":
            return jnp.asarray(W, dtype=jnp.float32), None
        return None, decomp

    def _route_for(self, W: np.ndarray, route: str) -> Tuple[str, tuple]:
        """Pick the sharded execution strategy for a traced mixing matrix.

        ``"ring"`` routes neighbor values over the device ring with k-hop
        relays (bandwidth ``2k`` shard-messages/round, ``k`` = max ring span
        of present edges); ``"allgather"`` is the masked all-to-all
        (``n-1`` shard-messages/round with a ring all-gather, plus an
        ``(n, P)`` buffer).  ``"auto"`` picks ring exactly when it moves
        less data.  Returns the choice plus the ring decomposition.
        """
        if route not in ("auto", "ring", "allgather"):
            raise ValueError(f"unknown route {route!r}")
        self_w, w_fwd, w_bwd, k_hops = self._ring_offset_weights(W)
        if route == "auto":
            route = "ring" if 2 * k_hops < self.n - 1 else "allgather"
        return route, (self_w, w_fwd, w_bwd, k_hops)

    @_public_call("consensus.mix_with")
    def mix_with(
        self, stacked: Pytree, W, times: int = 1, *, route: str = "auto"
    ) -> Pytree:
        """Run ``times`` gossip rounds under a *traced* mixing matrix ``W``.

        This is the time-varying-graph path (BASELINE config 5: "time-varying
        random graph"): the compiled program takes the mixing weights as
        runtime arguments, so resampling the topology every epoch costs a
        host->device transfer of an (n, n) matrix instead of a recompilation.

        Dense mode contracts with ``W`` directly.  Sharded mode has two
        strategies (SURVEY §7 hard part 1 — arbitrary graphs on a physical
        ring): sparse graphs route neighbor values over the device ring with
        <=k-hop relays (:meth:`_local_ring_mix` — bandwidth scales with the
        graph's maximal ring span, not the agent count), dense graphs
        emulate the general graph with a masked all-to-all (``all_gather``
        the agent axis, contract with this device's row of ``W``).
        ``route="auto"`` picks whichever moves less data per round.
        """
        W_traced, decomp = self._traced_w_dispatch(W, route)
        self._count_rounds(times)
        if W_traced is not None:
            return self._launch(
                self._get_jitted("mix_with"),
                stacked,
                lambda: (W_traced, jnp.int32(times)),
                rounds=times,
            )
        _, w_fwd, w_bwd, _ = decomp
        fn = self._get_ring_jitted(
            "mix_with_ring", bool(w_fwd.any()), bool(w_bwd.any())
        )
        return self._launch(
            fn,
            stacked,
            lambda: self._ring_operands(decomp) + (jnp.int32(times),),
            rounds=times,
        )

    @_public_call("consensus.mix_chebyshev_with")
    def mix_chebyshev_with(
        self, stacked: Pytree, W, omegas, *, route: str = "auto"
    ) -> Pytree:
        """Chebyshev-accelerated gossip under a traced ``W`` and traced
        ``omegas`` schedule (host-computed from that round's graph via
        :func:`~distributed_learning_tpu.parallel.schedule.chebyshev_omegas`).

        Only the *number* of rounds is static; changing the graph or its
        gamma between epochs reuses the compiled program.  Sharded mode
        routes each round like :meth:`mix_with` (ring relays for sparse
        graphs, masked all-to-all for dense ones).
        """
        omegas = jnp.asarray(omegas, dtype=jnp.float32)
        W_traced, decomp = self._traced_w_dispatch(W, route)
        times = int(omegas.shape[0])
        self._count_rounds(times)
        if W_traced is not None:
            return self._launch(
                self._get_jitted("mix_chebyshev_with"),
                stacked,
                lambda: (W_traced, omegas),
                rounds=times,
            )
        _, w_fwd, w_bwd, _ = decomp
        fn = self._get_ring_jitted(
            "mix_chebyshev_with_ring", bool(w_fwd.any()), bool(w_bwd.any())
        )
        return self._launch(
            fn,
            stacked,
            lambda: self._ring_operands(decomp) + (omegas,),
            rounds=times,
        )

    @_public_call("consensus.global_average")
    def global_average(self, stacked: Pytree) -> Pytree:
        """Exact averaging — the gamma=0 degenerate case (centralized DP
        all-reduce).  Dense mode is a mean over the agent axis; sharded
        mode one ``pmean`` over ICI.

        Used standalone as the exact-consensus reference for convergence
        metrics, and by the trainer's Gossip-PGA schedule (periodic global
        averaging accelerates gossip SGD: arXiv:2105.09080 — every H-th
        round replaces neighbor gossip with one exact all-reduce, removing
        the accumulated consensus error at bounded extra bandwidth).
        """
        get_registry().inc("consensus.global_averages")
        return self._launch(
            self._get_jitted("global_average"), stacked, rounds=1
        )

    def run_round(
        self,
        stacked: Pytree,
        weights: jax.Array | np.ndarray,
        *,
        convergence_eps: float = 1e-4,
        max_rounds: int = 10_000,
    ) -> Pytree:
        """Weighted average consensus round: every agent contributes its
        value with weight ``w_i`` (e.g. local sample count) and receives the
        weighted average.

        Parity with ``ConsensusAgent.run_round(value, weight)``
        (``consensus_asyncio.py:209-312``): values are lifted to
        ``y_i = x_i * w_i / mean(w)`` — the reference's master computes
        ``mean(w)`` centrally (:165); here it is a closed-form rescale —
        then gossiped until the residual drops below ``convergence_eps``.
        The reference's convergence check is one-sided and per-agent
        (``(y - v) <= eps``, :297 — a recorded defect); ours is the global
        symmetric residual.
        """
        w = jnp.asarray(weights, dtype=jnp.float32)
        if w.shape != (self.n,):
            raise ValueError(f"weights must have shape ({self.n},), got {w.shape}")
        total = float(jnp.sum(w))
        if not np.isfinite(total) or total <= 0.0:
            raise ValueError(
                f"agent weights must sum to a positive finite value, got {total}"
            )
        lifted = ops.weighted_lift(stacked, w)
        mixed, _, _ = self.mix_until(
            lifted, eps=convergence_eps, min_times=1, max_rounds=max_rounds
        )
        return mixed

    def deviations(self, stacked: Pytree) -> jax.Array:
        """(N,) per-agent L2 deviations from the mean parameter vector
        (parity: ``Mixer.get_parameters_deviation``, ``mixer.py:78-80``)."""
        return self._get_jitted("deviations")(stacked)

    def max_deviation(self, stacked: Pytree) -> jax.Array:
        return jnp.max(self.deviations(stacked))

    def max_std(self, stacked: Pytree) -> jax.Array:
        """Max across-agent parameter std (parity: ``mixer.py:82-84``)."""
        return self._get_jitted("max_std")(stacked)

    # ------------------------------------------------------------------ #
    # Program bodies: traceable under a CALLER's jit                     #
    # ------------------------------------------------------------------ #
    # The entry points above are top-level jitted programs — one XLA
    # dispatch per call.  The ``*_program`` methods expose the SAME
    # computations (same building blocks, same fused layout, same op
    # order) as plain traceable callables, so a caller can embed a whole
    # gossip phase inside its own compiled program: the trainer's epoch
    # superstep scans K epochs of train+gossip in ONE donated dispatch
    # (``training/trainer.py::GossipTrainer.train_epochs``) instead of
    # paying a dispatch boundary per epoch.  Static knobs (round counts,
    # stopping thresholds) are baked at program-build time; they are
    # compile-time constants of the caller's program anyway.

    def mix_program(self, times: int):
        """Traceable ``state -> state`` body of :meth:`mix` for a static
        round count: ``times`` unrolled rounds of this engine's gossip
        update — numerically identical to the ``fori_loop`` entry point
        (same per-round ops, same order)."""
        times = int(times)
        if self.mesh is None:
            def run(x):
                for _ in range(times):
                    x = self._dense_mix_once(x)
                return x

            return self._fuse_state_fn(run)
        mesh, ax = self.mesh, self.axis_name
        sw, mw = self._self_w, self._match_w

        def local(x, sw, mw):
            for _ in range(times):
                x = self._local_mix_once(x, sw, mw)
            return x

        inner = jax.shard_map(
            self._fuse_state_fn(local),
            mesh=mesh,
            in_specs=(P(ax), P(ax), P(None, ax)),
            out_specs=P(ax),
        )
        return lambda x: inner(x, sw, mw)

    def mix_until_program(
        self, *, eps: float, min_times: int = 0, max_rounds: int = 10_000
    ):
        """Traceable ``state -> (state, rounds_done, residual)`` body of
        :meth:`mix_until` with the stopping rule baked static — the
        eps-stopping ``lax.while_loop`` itself is unchanged, so the
        caller's program still decides the round count on device."""
        eps_f = jnp.float32(eps)
        mn = jnp.int32(min_times)
        mx = jnp.int32(max_rounds)
        if self.mesh is None:
            def run(x):
                return self._run_until(
                    x, eps_f, mn, mx, self._dense_mix_once,
                    self._dense_residual,
                )

            return self._fuse_state_fn(run)
        mesh, ax = self.mesh, self.axis_name
        sw, mw = self._self_w, self._match_w

        def local(x, sw, mw):
            return self._run_until(
                x, eps_f, mn, mx,
                lambda s: self._local_mix_once(s, sw, mw),
                self._local_residual,
            )

        inner = jax.shard_map(
            self._fuse_state_fn(local),
            mesh=mesh,
            in_specs=(P(ax), P(ax), P(None, ax)),
            out_specs=(P(ax), P(), P()),
        )
        return lambda x: inner(x, sw, mw)

    def chebyshev_program(self, times: int):
        """Traceable ``state -> state`` body of :meth:`mix_chebyshev`:
        the fixed accelerated schedule from this engine's exact gamma
        (``times`` static, as in the entry point)."""
        omegas = chebyshev_omegas(self.gamma, int(times))
        return lambda x: self._run_chebyshev(x, omegas)

    def global_average_program(self):
        """Traceable ``state -> state`` body of :meth:`global_average`
        (the Gossip-PGA exact all-reduce epoch)."""
        if self.mesh is None:
            return self._fuse_state_fn(self._dense_global_avg)
        mesh, ax = self.mesh, self.axis_name
        return jax.shard_map(
            self._fuse_state_fn(self._local_global_avg),
            mesh=mesh,
            in_specs=(P(ax),),
            out_specs=P(ax),
        )

    def max_deviation_program(self):
        """Traceable ``state -> scalar`` max agent deviation — the
        :meth:`max_deviation` statistic embedded in a caller's program
        (the superstep reads the post-mix residual out of the same
        dispatch that produced it)."""
        if self.mesh is None:
            return lambda x: ops.fused_max_deviation(x, fused=self.fused)

        mesh, ax = self.mesh, self.axis_name

        def local(x):
            return self._local_residual(self._fuse_in(x))

        return jax.shard_map(
            local, mesh=mesh, in_specs=(P(ax),), out_specs=P()
        )

    # ------------------------------------------------------------------ #
    # Traced-knob program bodies: round counts / schedules as DATA       #
    # ------------------------------------------------------------------ #
    # The ``*_times_program`` / ``*_masked_program`` builders below are
    # the per-epoch-schedule counterparts of the static ``*_program``
    # bodies: the round count (and, for the traced-W variants, the
    # mixing matrix and the Chebyshev omega row) is a TRACED operand of
    # the returned callable, so a caller can scan K epochs with a
    # different round budget per epoch inside ONE compiled program (the
    # trainer's superstep, ``training/trainer.py::train_epochs``).
    # ``fori_loop`` over the same per-round body is bitwise the static
    # unroll (same ops, same order — the ``mix_program`` contract), so
    # every variant here stays bit-identical to its per-epoch oracle.

    def mix_times_program(self):
        """Traceable ``(state, times) -> state``: :meth:`mix_program`
        with the round count as a traced int32 operand (``fori_loop``
        over the same per-round update — bitwise the static unroll)."""
        if self.mesh is None:
            def run(x, t):
                return self._run_times(x, t, self._dense_mix_once)

            return self._fuse_state_fn(run)
        mesh, ax = self.mesh, self.axis_name
        sw, mw = self._self_w, self._match_w

        def local(x, t, sw, mw):
            return self._run_times(
                x, t, lambda s: self._local_mix_once(s, sw, mw)
            )

        inner = jax.shard_map(
            self._fuse_state_fn(local),
            mesh=mesh,
            in_specs=(P(ax), P(), P(ax), P(None, ax)),
            out_specs=P(ax),
        )
        return lambda x, t: inner(x, t, sw, mw)

    def mix_until_times_program(self, *, eps: float, max_rounds: int = 10_000):
        """Traceable ``(state, min_times) -> (state, rounds_done,
        residual)``: :meth:`mix_until_program` with the round floor as a
        traced operand (the eps-stopping ``while_loop`` already decides
        the count on device; only the floor becomes data)."""
        eps_f = jnp.float32(eps)
        mx = jnp.int32(max_rounds)
        if self.mesh is None:
            def run(x, mn):
                return self._run_until(
                    x, eps_f, mn, mx, self._dense_mix_once,
                    self._dense_residual,
                )

            return self._fuse_state_fn(run)
        mesh, ax = self.mesh, self.axis_name
        sw, mw = self._self_w, self._match_w

        def local(x, mn, sw, mw):
            return self._run_until(
                x, eps_f, mn, mx,
                lambda s: self._local_mix_once(s, sw, mw),
                self._local_residual,
            )

        inner = jax.shard_map(
            self._fuse_state_fn(local),
            mesh=mesh,
            in_specs=(P(ax), P(), P(ax), P(None, ax)),
            out_specs=(P(ax), P(), P()),
        )
        return lambda x, mn: inner(x, mn, sw, mw)

    def mix_with_times_program(self):
        """Traceable ``(state, W, times) -> state``: the traced-W gossip
        of :meth:`mix_with` with a traced round count.  Under a mesh the
        matrix is traced data, so the route is always the masked
        all-to-all (:meth:`_local_allgather_mix`); the k-hop ring
        decomposition needs a concrete host-side W."""
        if self.mesh is None:
            precision = self.precision

            def run(x, W, t):
                return self._run_times(
                    x, t,
                    lambda s: ops.dense_mix(s, W, precision=precision),
                )

            return self._fuse_state_fn(run)
        mesh, ax = self.mesh, self.axis_name

        def local(x, W, t):
            i = lax.axis_index(ax)
            W_row = lax.dynamic_index_in_dim(
                W.astype(jnp.float32), i, keepdims=False
            )
            return self._run_times(
                x, t, lambda s: self._local_allgather_mix(s, W_row)
            )

        return jax.shard_map(
            self._fuse_state_fn(local),
            mesh=mesh,
            in_specs=(P(ax), P(), P()),
            out_specs=P(ax),
        )

    def mix_until_with_times_program(
        self, *, eps: float, max_rounds: int = 10_000
    ):
        """Traceable ``(state, W, min_times) -> (state, rounds_done,
        residual)``: eps-stopped gossip against a traced matrix with a
        traced round floor (the superstep's ``topology_schedule`` +
        ``mix_eps`` composition)."""
        eps_f = jnp.float32(eps)
        mx = jnp.int32(max_rounds)
        if self.mesh is None:
            precision = self.precision

            def run(x, W, mn):
                return self._run_until(
                    x, eps_f, mn, mx,
                    lambda s: ops.dense_mix(s, W, precision=precision),
                    self._dense_residual,
                )

            return self._fuse_state_fn(run)
        mesh, ax = self.mesh, self.axis_name

        def local(x, W, mn):
            i = lax.axis_index(ax)
            W_row = lax.dynamic_index_in_dim(
                W.astype(jnp.float32), i, keepdims=False
            )
            return self._run_until(
                x, eps_f, mn, mx,
                lambda s: self._local_allgather_mix(s, W_row),
                self._local_residual,
            )

        return jax.shard_map(
            self._fuse_state_fn(local),
            mesh=mesh,
            in_specs=(P(ax), P(), P()),
            out_specs=(P(ax), P(), P()),
        )

    def chebyshev_masked_program(self):
        """Traceable ``(state, omegas, times) -> state``: the Chebyshev
        recurrence over a zero-PADDED traced omega row, frozen after the
        traced round count — collectives run every padded round (branch-
        uniform), the recurrence state just stops updating.  The omega
        prefix property (``chebyshev_omegas(g, t) ==
        chebyshev_omegas(g, T)[:t]``) makes the frozen result bitwise
        :meth:`mix_chebyshev` at ``times`` rounds."""
        if self.mesh is None:
            mix_once = self._dense_mix_once

            def run(x, omegas, t):
                return self._cheby_masked(x, omegas, t, mix_once)

            return self._fuse_state_fn(run)
        mesh, ax = self.mesh, self.axis_name
        sw, mw = self._self_w, self._match_w

        def local(x, omegas, t, sw, mw):
            return self._cheby_masked(
                x, omegas, t, lambda s: self._local_mix_once(s, sw, mw)
            )

        inner = jax.shard_map(
            self._fuse_state_fn(local),
            mesh=mesh,
            in_specs=(P(ax), P(), P(), P(ax), P(None, ax)),
            out_specs=P(ax),
        )
        return lambda x, omegas, t: inner(x, omegas, t, sw, mw)

    def chebyshev_masked_with_program(self):
        """Traceable ``(state, W, omegas, times) -> state``: the masked
        Chebyshev recurrence against a traced per-epoch matrix (the
        superstep's ``topology_schedule`` + ``chebyshev`` composition;
        all-gather route, as for every traced W)."""
        if self.mesh is None:
            precision = self.precision

            def run(x, W, omegas, t):
                return self._cheby_masked(
                    x, omegas, t,
                    lambda s: ops.dense_mix(s, W, precision=precision),
                )

            return self._fuse_state_fn(run)
        mesh, ax = self.mesh, self.axis_name

        def local(x, W, omegas, t):
            i = lax.axis_index(ax)
            W_row = lax.dynamic_index_in_dim(
                W.astype(jnp.float32), i, keepdims=False
            )
            return self._cheby_masked(
                x, omegas, t, lambda s: self._local_allgather_mix(s, W_row)
            )

        return jax.shard_map(
            self._fuse_state_fn(local),
            mesh=mesh,
            in_specs=(P(ax), P(), P(), P()),
            out_specs=P(ax),
        )

    def robust_mix_times_program(self, spec):
        """Traceable ``(state, times) -> (state, mass)``: the robust
        gossip of :meth:`robust_mix_program` with a traced round count;
        see :mod:`..parallel.robust`."""
        from distributed_learning_tpu.parallel import robust

        return robust.robust_mix_times_program(self, spec)

    def robust_async_times_program(self, spec, *, periods):
        """Traceable ``(stacked, state, times, tau) -> (stacked, state,
        mass)``: the robust async gossip with traced round count and
        staleness bound; see :mod:`..parallel.robust`."""
        from distributed_learning_tpu.parallel import robust

        return robust.robust_async_gossip_times_program(
            self, spec, periods=periods
        )

    # ------------------------------------------------------------------ #
    # Asynchronous (stale-weighted) gossip: the device-side simulation   #
    # of the comm-layer async runtime (docs/async_runtime.md)            #
    # ------------------------------------------------------------------ #
    def _normalize_periods(self, periods) -> Tuple[int, ...]:
        """Static per-agent publish periods: agent ``j`` publishes its
        params every ``periods[j]``-th async round (1 = every round; a
        ``k``-slow straggler is ``periods[j] = k``)."""
        if np.isscalar(periods):
            periods = (int(periods),) * self.n
        periods = tuple(int(p) for p in periods)
        if len(periods) != self.n:
            raise ValueError(
                f"periods must have length {self.n}, got {len(periods)}"
            )
        if any(p < 1 for p in periods):
            raise ValueError(f"publish periods must be >= 1, got {periods}")
        return periods

    def init_async_state(self, stacked: Pytree) -> AsyncGossipState:
        """Fresh double-buffer carry: every agent publishes on the first
        round (round 0 is a multiple of every period), so the initial
        ``pub`` contents never survive a mix."""
        return AsyncGossipState(
            pub=jax.tree.map(jnp.asarray, stacked),
            age=jnp.zeros((self.n,), jnp.int32),
            rnd=jnp.int32(0),
        )

    def _async_round_body(self, periods_dev: jax.Array):
        """One async gossip round on (x, pub, age, rnd) — layout-agnostic
        (serves the stacked tree and the fused buffer dict alike), with
        the staleness bound ``tau`` a per-call operand (a python int in
        the static programs, a traced int32 in the superstep's
        schedulable-tau variant — :func:`ops.mixing.stale_weight_matrix`
        is knob-polymorphic).

        publish -> age -> stale-weighted mix: agents whose period divides
        the round copy buffer A into buffer B (their age resets), every
        agent then mixes its live value with the *published* neighbor
        buffers under :func:`ops.mixing.stale_weight_matrix` — stale
        neighbors decay as 1/(1+age) and drop beyond ``tau``, with the
        lost mass renormalized onto the self edge on device.
        """
        W_dev, precision = self._W_dev, self.precision

        def round_once(x, pub, age, rnd, tau):
            publish = (rnd % periods_dev) == 0  # (n,) bool

            def select(xv, pv):
                m = publish.reshape((-1,) + (1,) * (xv.ndim - 1))
                return jnp.where(m, xv, pv)

            pub = jax.tree.map(select, x, pub)
            age = jnp.where(publish, jnp.int32(0), age + jnp.int32(1))
            W_eff = ops.stale_weight_matrix(W_dev, age, tau=tau)
            x = ops.stale_weighted_mix(x, pub, W_eff, precision=precision)
            return x, pub, age, rnd + jnp.int32(1)

        return round_once

    def _local_async_round(self, periods_dev: jax.Array):
        """Sharded counterpart of :meth:`_async_round_body`: one async
        round on this device's shard (one all_gather of the published
        buffer per leaf/bucket), ``tau`` again a per-call operand."""
        ax, n = self.axis_name, self.n
        W_dev, precision = self._W_dev, self.precision

        def local_round(x, pub, age, rnd, tau):
            publish = (rnd % periods_dev) == 0
            i = lax.axis_index(ax)
            mine = publish[i]
            pub = jax.tree.map(
                lambda xv, pv: jnp.where(mine, xv, pv), x, pub
            )
            age = jnp.where(publish, jnp.int32(0), age + jnp.int32(1))
            W_eff = ops.stale_weight_matrix(W_dev, age, tau=tau)
            W_row = lax.dynamic_index_in_dim(W_eff, i, keepdims=False)
            d = W_row[i]

            def leaf(xv, pv):
                ag = lax.all_gather(pv, ax, axis=0, tiled=True)
                pf = ag.astype(jnp.float32).reshape(n, -1)
                out = jnp.matmul(
                    W_row.astype(jnp.float32), pf, precision=precision
                )
                xf = xv.reshape(xv.shape[0], -1).astype(jnp.float32)
                lpf = pv.reshape(pv.shape[0], -1).astype(jnp.float32)
                out = out[None] + d * (xf - lpf)
                return out.reshape(xv.shape).astype(xv.dtype)

            x = jax.tree.map(leaf, x, pub)
            return x, pub, age, rnd + jnp.int32(1)

        return local_round

    def _fuse_async_fn(self, run):
        """Fused-layout wrapper for the double-buffered programs: both
        the live state and the published buffer ravel with the SAME
        layout (one flatten each at entry, one unflatten at exit), so
        every async round moves O(dtype-buckets) GEMMs."""
        if not self.fused:
            return run

        def wrapped(x, pub, *rest):
            bx, layout = ops.flatten_stacked(x)
            bp, _ = ops.flatten_stacked(pub, layout)
            out = run(bx, bp, *rest)
            return (
                ops.unflatten_stacked(out[0], layout),
                ops.unflatten_stacked(out[1], layout),
            ) + tuple(out[2:])

        return wrapped

    def async_gossip_program(self, *, tau: int, periods, times: int = 1):
        """Traceable ``(stacked, AsyncGossipState) -> (stacked, state)``
        body of :meth:`mix_async` for a static round count — the program
        the trainer's async knob embeds and the ``async_stale_mix``
        graftlint audit entry pins.

        With ``tau=0`` and ``periods`` all 1 every round publishes
        (``pub`` carries the live bits), every age is 0, and
        ``stale_weight_matrix`` returns ``W`` bitwise — the rounds are
        bit-identical to :meth:`mix_program`'s: the lock-step path IS
        the neutral point of this program, not a separate oracle.
        """
        periods = self._normalize_periods(periods)
        times = int(times)
        periods_dev = jnp.asarray(periods, jnp.int32)
        tau_i = int(tau)

        if self.mesh is None:
            round_once = self._async_round_body(periods_dev)

            def run(x, pub, age, rnd):
                def body(_, carry):
                    return round_once(*carry, tau_i)

                return lax.fori_loop(0, times, body, (x, pub, age, rnd))

            fused = self._fuse_async_fn(run)

            def program(x, st: AsyncGossipState):
                x, pub, age, rnd = fused(x, st.pub, st.age, st.rnd)
                return x, AsyncGossipState(pub, age, rnd)

            return program

        mesh, ax = self.mesh, self.axis_name
        local_round = self._local_async_round(periods_dev)

        def local(x, pub, age, rnd):
            def body(_, carry):
                return local_round(*carry, tau_i)

            return lax.fori_loop(0, times, body, (x, pub, age, rnd))

        inner = jax.shard_map(
            self._fuse_async_fn(local),
            mesh=mesh,
            in_specs=(P(ax), P(ax), P(), P()),
            out_specs=(P(ax), P(ax), P(), P()),
        )

        def program(x, st: AsyncGossipState):
            x, pub, age, rnd = inner(x, st.pub, st.age, st.rnd)
            return x, AsyncGossipState(pub, age, rnd)

        return program

    def async_gossip_times_program(self, *, periods):
        """Traceable ``(stacked, AsyncGossipState, times, tau) ->
        (stacked, state)``: :meth:`async_gossip_program` with the round
        count AND the staleness bound as traced int32 operands — the
        superstep feeds a per-epoch schedule for both, in one compiled
        program.  Same per-round body as the static variant (bitwise at
        equal knob values); only the publish periods stay static (they
        shape the per-agent cadence array)."""
        periods = self._normalize_periods(periods)
        periods_dev = jnp.asarray(periods, jnp.int32)

        if self.mesh is None:
            round_once = self._async_round_body(periods_dev)

            def run(x, pub, age, rnd, t, tau):
                def body(_, carry):
                    return round_once(*carry, tau)

                return lax.fori_loop(0, t, body, (x, pub, age, rnd))

            fused = self._fuse_async_fn(run)

            def program(x, st: AsyncGossipState, t, tau):
                x, pub, age, rnd = fused(x, st.pub, st.age, st.rnd, t, tau)
                return x, AsyncGossipState(pub, age, rnd)

            return program

        mesh, ax = self.mesh, self.axis_name
        local_round = self._local_async_round(periods_dev)

        def local(x, pub, age, rnd, t, tau):
            def body(_, carry):
                return local_round(*carry, tau)

            return lax.fori_loop(0, t, body, (x, pub, age, rnd))

        inner = jax.shard_map(
            self._fuse_async_fn(local),
            mesh=mesh,
            in_specs=(P(ax), P(ax), P(), P(), P(), P()),
            out_specs=(P(ax), P(ax), P(), P()),
        )

        def program(x, st: AsyncGossipState, t, tau):
            x, pub, age, rnd = inner(x, st.pub, st.age, st.rnd, t, tau)
            return x, AsyncGossipState(pub, age, rnd)

        return program

    @_public_call("consensus.mix_async")
    def mix_async(
        self,
        stacked: Pytree,
        state: Optional[AsyncGossipState] = None,
        *,
        tau: int,
        periods,
        times: int = 1,
    ) -> Tuple[Pytree, AsyncGossipState]:
        """Run ``times`` asynchronous (stale-weighted, double-buffered)
        gossip rounds; returns ``(mixed, carry)`` — thread the carry into
        the next call so publish ages and the round counter persist
        across epochs.  ``state=None`` starts a fresh carry.

        This is the device-side simulation of the comm runtime's
        straggler model (``comm/async_runtime.py``): ``periods[j] = k``
        models an agent whose updates reach the fabric every k-th round,
        ``tau`` bounds how stale a contribution may be before it is
        dropped (weight renormalized on device).  ``tau=0`` with all
        periods 1 is bit-identical to :meth:`mix`.
        """
        periods = self._normalize_periods(periods)
        key = ("mix_async", int(tau), periods, int(times))
        if key not in self._jit_cache:
            self._jit_cache[key] = jax.jit(
                self.async_gossip_program(
                    tau=tau, periods=periods, times=times
                )
            )
        self._count_rounds(times)
        return self._launch(
            self._jit_cache[key],
            stacked,
            lambda: (
                self.init_async_state(stacked) if state is None else state,
            ),
            rounds=times,
        )

    # ------------------------------------------------------------------ #
    # Byzantine-robust variants (parallel/robust.py)                     #
    # ------------------------------------------------------------------ #
    def robust_mix_program(self, spec, times: int = 1):
        """Traceable ``state -> (state, mass)`` robust-mixing body — the
        clipped / trimmed-mean / coordinate-median counterpart of
        :meth:`mix_program`; see :mod:`..parallel.robust`."""
        from distributed_learning_tpu.parallel import robust

        return robust.robust_mix_program(self, spec, times)

    @_public_call("consensus.mix_robust")
    def mix_robust(self, stacked: Pytree, spec, times: int = 1):
        """Run ``times`` robust gossip rounds; returns ``(mixed, mass)``
        where ``mass`` is the total edge weight the defense redirected to
        self edges (0.0 at the neutral knobs, where the result is
        bit-identical to :meth:`mix`)."""
        from distributed_learning_tpu.parallel import robust

        cfg = robust.as_robust_config(spec)
        key = ("mix_robust", cfg, int(times))
        if key not in self._jit_cache:
            self._jit_cache[key] = jax.jit(
                robust.robust_mix_program(self, cfg, times)
            )
        self._count_rounds(times)
        mixed, mass = self._launch(
            self._jit_cache[key], stacked, rounds=times
        )
        get_registry().inc("consensus.robust.rounds", int(times))
        return mixed, mass

    def robust_async_gossip_program(
        self, spec, *, tau: int, periods, times: int = 1
    ):
        """Traceable robust counterpart of :meth:`async_gossip_program`
        (``(stacked, state) -> (stacked, state, mass)``); see
        :mod:`..parallel.robust`."""
        from distributed_learning_tpu.parallel import robust

        return robust.robust_async_gossip_program(
            self, spec, tau=tau, periods=periods, times=times
        )

    @_public_call("consensus.mix_async_robust")
    def mix_async_robust(
        self,
        stacked: Pytree,
        state: Optional[AsyncGossipState] = None,
        *,
        spec,
        tau: int,
        periods,
        times: int = 1,
    ) -> Tuple[Pytree, AsyncGossipState, jax.Array]:
        """Robust :meth:`mix_async`: stale-weighted double-buffered
        rounds with the robust estimator applied on top of the
        stale-decayed matrix.  Returns ``(mixed, carry, mass)``; at the
        neutral knobs bit-identical to :meth:`mix_async`."""
        from distributed_learning_tpu.parallel import robust

        cfg = robust.as_robust_config(spec)
        periods = self._normalize_periods(periods)
        key = ("mix_async_robust", cfg, int(tau), periods, int(times))
        if key not in self._jit_cache:
            self._jit_cache[key] = jax.jit(
                robust.robust_async_gossip_program(
                    self, cfg, tau=tau, periods=periods, times=times
                )
            )
        self._count_rounds(times)
        return self._launch(
            self._jit_cache[key],
            stacked,
            lambda: (
                self.init_async_state(stacked) if state is None else state,
            ),
            rounds=times,
        )

    def cost_profile(self, stacked: Pytree, *, times: int = 1,
                     name: str = "consensus.mix"):
        """:class:`~distributed_learning_tpu.obs.cost.CostProfile` of
        this engine's compiled ``times``-round mix program at
        ``stacked``'s shapes, registered process-wide under ``name`` —
        the static FLOPs/bytes/collectives side of "is the bottleneck
        compute or gossip?".  AOT ``lower().compile()`` only: nothing
        executes, and the engine's own jitted entry-point caches are
        untouched."""
        from distributed_learning_tpu.obs.cost import profile_fn

        return profile_fn(
            jax.jit(self.mix_program(int(times))), stacked, name=name
        )

    # ------------------------------------------------------------------ #
    # Jit plumbing                                                       #
    # ------------------------------------------------------------------ #
    def _get_jitted(self, name: str):
        if name in self._jit_cache:
            return self._jit_cache[name]

        def wrap(f):
            return jax.jit(f)

        fuse = self._fuse_state_fn

        if self.mesh is None:
            if name == "mix":
                fn = wrap(
                    fuse(lambda x, t: self._run_times(x, t, self._dense_mix_once))
                )
            elif name == "mix_until":
                fn = wrap(
                    fuse(
                        lambda x, eps, mn, mx: self._run_until(
                            x,
                            eps,
                            mn,
                            mx,
                            self._dense_mix_once,
                            self._dense_residual,
                        )
                    )
                )
            elif name == "deviations":
                fn = wrap(lambda x: ops.agent_deviations(self._fuse_in(x)))
            elif name == "max_std":
                fn = wrap(lambda x: ops.max_std(self._fuse_in(x)))
            elif name == "mix_with":
                fn = wrap(
                    fuse(
                        lambda x, W, t: self._run_times(
                            x,
                            t,
                            lambda s: ops.dense_mix(
                                s, W, precision=self.precision
                            ),
                        )
                    )
                )
            elif name == "mix_until_with":
                fn = wrap(
                    fuse(
                        lambda x, W, eps, mn, mx: self._run_until(
                            x,
                            eps,
                            mn,
                            mx,
                            lambda s: ops.dense_mix(
                                s, W, precision=self.precision
                            ),
                            self._dense_residual,
                        )
                    )
                )
            elif name == "mix_chebyshev_with":
                fn = wrap(
                    fuse(
                        lambda x, W, om: self._cheby_traced(
                            x,
                            om,
                            lambda s: ops.dense_mix(
                                s, W, precision=self.precision
                            ),
                        )
                    )
                )
            elif name == "global_average":
                fn = wrap(fuse(self._dense_global_avg))
            else:
                raise KeyError(name)
        else:
            mesh, ax = self.mesh, self.axis_name

            def sharded(f, out_specs, extra_in=()):
                return jax.jit(
                    jax.shard_map(
                        f,
                        mesh=mesh,
                        in_specs=(P(ax),) + extra_in,
                        out_specs=out_specs,
                    )
                )

            fuse = self._fuse_state_fn

            if name == "mix":
                def local_mix(x, t, sw, mw):
                    return self._run_times(
                        x, t, lambda s: self._local_mix_once(s, sw, mw)
                    )

                inner = sharded(
                    fuse(local_mix), P(ax), extra_in=(P(), P(ax), P(None, ax))
                )
                fn = lambda x, t: inner(x, t, self._self_w, self._match_w)
            elif name == "mix_until":
                def local_until(x, eps, mn, mx, sw, mw):
                    return self._run_until(
                        x,
                        eps,
                        mn,
                        mx,
                        lambda s: self._local_mix_once(s, sw, mw),
                        self._local_residual,
                    )

                inner = sharded(
                    fuse(local_until),
                    (P(ax), P(), P()),
                    extra_in=(P(), P(), P(), P(ax), P(None, ax)),
                )
                fn = lambda x, eps, mn, mx: inner(
                    x, eps, mn, mx, self._self_w, self._match_w
                )
            elif name == "deviations":
                inner = sharded(
                    lambda x: jnp.sqrt(
                        self._local_sq_deviation(self._fuse_in(x))
                    )[None],
                    P(ax),
                )
                fn = inner
            elif name == "max_std":
                def local_max_std(x):
                    m = jnp.float32(0.0)
                    for leaf in jax.tree.leaves(self._fuse_in(x)):
                        lf = leaf.astype(jnp.float32)
                        # graftlint: disable=raw-collective-in-shard-map -- telemetry: per-coordinate mean over agents (reference mixer.py:78-84 stats)
                        mean = lax.pmean(lf, ax)
                        # graftlint: disable=raw-collective-in-shard-map -- telemetry: per-coordinate variance over agents (same stat family)
                        var = lax.pmean((lf - mean) ** 2, ax)
                        m = jnp.maximum(m, jnp.max(jnp.sqrt(var)))
                    return m

                fn = sharded(local_max_std, P())
            elif name == "mix_with":
                def local_mw(x, W_rows, t):
                    return self._run_times(
                        x, t, lambda s: self._local_allgather_mix(s, W_rows)
                    )

                fn = sharded(fuse(local_mw), P(ax), extra_in=(P(ax), P()))
            elif name == "mix_until_with":
                def local_uw(x, W_rows, eps, mn, mx):
                    return self._run_until(
                        x,
                        eps,
                        mn,
                        mx,
                        lambda s: self._local_allgather_mix(s, W_rows),
                        self._local_residual,
                    )

                fn = sharded(
                    fuse(local_uw),
                    (P(ax), P(), P()),
                    extra_in=(P(ax), P(), P(), P()),
                )
            elif name == "mix_chebyshev_with":
                def local_cw(x, W_rows, om):
                    return self._cheby_traced(
                        x, om, lambda s: self._local_allgather_mix(s, W_rows)
                    )

                fn = sharded(fuse(local_cw), P(ax), extra_in=(P(ax), P()))
            elif name == "global_average":
                fn = sharded(fuse(self._local_global_avg), P(ax))
            else:
                raise KeyError(name)

        self._jit_cache[name] = fn
        return fn

    def _get_ring_jitted(self, name: str, use_fwd: bool, use_bwd: bool):
        """Jitted k-hop ring programs, keyed by which ring directions are
        statically live (a direction with all-zero weights is skipped at
        compile time — see :func:`local_ring_mix`)."""
        key = (name, use_fwd, use_bwd)
        if key in self._jit_cache:
            return self._jit_cache[key]
        mesh, ax = self.mesh, self.axis_name

        def ring_once(s, sw, wf, wb, k):
            return local_ring_mix(
                s, sw, wf, wb, k, axis_name=ax, n=self.n,
                use_fwd=use_fwd, use_bwd=use_bwd,
            )

        in_specs = (P(ax), P(ax), P(ax), P(ax), P(), P())
        out_specs: Any = P(ax)
        if name == "mix_with_ring":
            def local_mr(x, sw, wf, wb, k, t):
                return self._run_times(
                    x, t, lambda s: ring_once(s, sw, wf, wb, k)
                )

            body = local_mr
        elif name == "mix_chebyshev_with_ring":
            def local_cr(x, sw, wf, wb, k, om):
                return self._cheby_traced(
                    x, om, lambda s: ring_once(s, sw, wf, wb, k)
                )

            body = local_cr
        elif name == "mix_until_with_ring":
            def local_ur(x, sw, wf, wb, k, eps, mn, mx):
                return self._run_until(
                    x,
                    eps,
                    mn,
                    mx,
                    lambda s: ring_once(s, sw, wf, wb, k),
                    self._local_residual,
                )

            body = local_ur
            in_specs = (P(ax), P(ax), P(ax), P(ax), P(), P(), P(), P())
            out_specs = (P(ax), P(), P())
        else:
            raise KeyError(name)
        fn = jax.jit(
            jax.shard_map(
                self._fuse_state_fn(body),
                mesh=mesh,
                in_specs=in_specs,
                out_specs=out_specs,
            )
        )
        self._jit_cache[key] = fn
        return fn

    # ------------------------------------------------------------------ #
    # Loop bodies (shared by dense and sharded paths)                    #
    # ------------------------------------------------------------------ #
    @staticmethod
    def _run_times(x: Pytree, times: jax.Array, mix_once) -> Pytree:
        return lax.fori_loop(0, times, lambda i, s: mix_once(s), x)

    @staticmethod
    def _run_until(x, eps, min_times, max_rounds, mix_once, residual):
        def cond(carry):
            t, s, res = carry
            return (t < min_times) | ((res >= eps) & (t < max_rounds))

        def body(carry):
            t, s, _ = carry
            s = mix_once(s)
            return (t + 1, s, residual(s))

        t0 = jnp.int32(0)
        t, s, res = lax.while_loop(cond, body, (t0, x, residual(x)))
        return s, t, res

    def _run_chebyshev(self, x: Pytree, omegas: np.ndarray) -> Pytree:
        """x_{k+1} = omega_{k+1} (W x_k - x_{k-1}) + x_{k-1}; mean-preserving
        at every step.  Runs dense or inside shard_map depending on mode."""
        if self.mesh is None:
            mix_once = self._dense_mix_once

            def run(xx):
                return self._cheby_loop(xx, omegas, mix_once)

            return self._fuse_state_fn(run)(x)
        mesh, ax = self.mesh, self.axis_name

        def local(xx, sw, mw):
            return self._cheby_loop(
                xx, omegas, lambda s: self._local_mix_once(s, sw, mw)
            )

        return jax.shard_map(
            self._fuse_state_fn(local),
            mesh=mesh,
            in_specs=(P(ax), P(ax), P(None, ax)),
            out_specs=P(ax),
        )(x, self._self_w, self._match_w)

    @staticmethod
    def _cheby_traced(x: Pytree, omegas: jax.Array, mix_once) -> Pytree:
        """Chebyshev recurrence with a *traced* omega schedule: a lax.scan
        over omegas[1:], so only the round count is compile-time static."""
        k = omegas.shape[0]
        if k == 0:
            return x
        x_prev, xk = x, mix_once(x)  # omega_1 = 1 step
        if k == 1:
            return xk

        def body(carry, om):
            prev, cur = carry
            wx = mix_once(cur)
            nxt = jax.tree.map(
                lambda wv, pv: (
                    om * (wv.astype(jnp.float32) - pv.astype(jnp.float32))
                    + pv.astype(jnp.float32)
                ).astype(wv.dtype),
                wx,
                prev,
            )
            return (cur, nxt), None

        (_, xk), _ = lax.scan(body, (x_prev, xk), omegas[1:])
        return xk

    @staticmethod
    def _cheby_masked(x: Pytree, omegas: jax.Array, t: jax.Array,
                      mix_once) -> Pytree:
        """Chebyshev recurrence over a zero-padded traced omega row,
        frozen once the traced round count ``t`` is spent: every padded
        round still runs ``mix_once`` (the collective footprint is
        round-count invariant — branch-uniform by construction), but the
        recurrence carry stops updating at ``r > t``.  Because the omega
        sequence depends only on gamma — ``chebyshev_omegas(g, t)`` is a
        prefix of ``chebyshev_omegas(g, T)`` — the frozen result is
        bitwise :meth:`_cheby_traced` on ``omegas[:t]``."""
        k = omegas.shape[0]
        if k == 0:
            return x
        x1 = mix_once(x)
        # times >= 1 everywhere in the trainer; the mask keeps the
        # program total for t == 0 anyway.
        xk = jax.tree.map(lambda a, b: jnp.where(t >= 1, b, a), x, x1)
        if k == 1:
            return xk

        def body(carry, inp):
            om, r = inp
            prev, cur = carry
            wx = mix_once(cur)
            nxt = jax.tree.map(
                lambda wv, pv: (
                    om * (wv.astype(jnp.float32) - pv.astype(jnp.float32))
                    + pv.astype(jnp.float32)
                ).astype(wv.dtype),
                wx,
                prev,
            )
            live = r <= t
            prev = jax.tree.map(
                lambda c, p: jnp.where(live, c, p), cur, prev
            )
            cur = jax.tree.map(
                lambda nv, c: jnp.where(live, nv, c), nxt, cur
            )
            return (prev, cur), None

        (_, xk), _ = lax.scan(
            body, (x, xk), (omegas[1:], jnp.arange(2, k + 1))
        )
        return xk

    @staticmethod
    def _cheby_loop(x: Pytree, omegas: np.ndarray, mix_once) -> Pytree:
        if len(omegas) == 0:
            return x
        x_prev, xk = x, mix_once(x)  # omega_1 = 1 step
        for omega in omegas[1:]:
            om = jnp.float32(omega)
            wx = mix_once(xk)
            x_next = jax.tree.map(
                lambda wv, pv: (om * (wv.astype(jnp.float32) - pv.astype(jnp.float32))
                                + pv.astype(jnp.float32)).astype(wv.dtype),
                wx,
                x_prev,
            )
            x_prev, xk = xk, x_next
        return xk


class Mixer:
    """Drop-in equivalent of the reference's synchronous in-process mixer
    (``utils/consensus_simple/mixer.py:9-84``), device-resident.

    Takes per-agent parameter pytrees plus the reference's
    ``{agent: {neighbor: weight}}`` topology dict (``Man_Colab.ipynb`` cell
    14 format), stacks them on device, and gossips with a
    :class:`ConsensusEngine` — eliminating the torch->numpy flatten /
    unflatten round-trip of ``mixer.py:68-76``.
    """

    def __init__(
        self,
        params: Mapping[Hashable, Pytree],
        topology: Mapping[Hashable, Mapping[Hashable, float]] | np.ndarray,
        *,
        tokens: Sequence[Hashable] | None = None,
        mesh: Optional[Mesh] = None,
        logger=None,
        max_rounds: int = 10_000,
    ):
        if isinstance(topology, Mapping):
            topo, W = Topology.from_neighbor_dict(topology)
            self.tokens = topo.tokens
        else:
            W = np.asarray(topology)
            self.tokens = tuple(tokens) if tokens is not None else tuple(range(W.shape[0]))
            if len(self.tokens) != W.shape[0]:
                raise ValueError(
                    f"expected {W.shape[0]} tokens for a {W.shape} mixing "
                    f"matrix, got {len(self.tokens)}"
                )
        self.engine = ConsensusEngine(W, mesh=mesh)
        self._logger = logger
        self._max_rounds = max_rounds
        self.set_parameters(params)

    def mix(self, times: int = 1, eps: float | None = None) -> int:
        """Gossip ``times`` rounds; with ``eps`` keep going until the max
        deviation drops below it (at least ``times`` rounds).  Returns the
        number of rounds executed (parity: ``mixer.py:18-41``)."""
        if len(self.tokens) <= 1:
            return 0
        if self._logger is not None:
            self._logger.debug(f"Mixer start with times= {times}, eps= {eps}")
        if eps is None:
            self._stacked = self.engine.mix(self._stacked, times)
            done = int(times)
        else:
            self._stacked, t, _res = self.engine.mix_until(
                self._stacked, eps=eps, min_times=times, max_rounds=self._max_rounds
            )
            done = int(t)
        if self._logger is not None:
            self._logger.debug(f"Mixer finished with {done} times")
        return done

    def parameters(self) -> Dict[Hashable, Pytree]:
        """Current per-agent parameter pytrees."""
        trees = ops.unstack_tree(self._stacked, len(self.tokens))
        return dict(zip(self.tokens, trees))

    def set_parameters(self, params: Mapping[Hashable, Pytree]) -> None:
        """Replace the device-resident state from per-agent pytrees (the
        single owner of the stack/shard invariant — external adapters like
        ``interop.TorchModelMixer`` resync through this, not ``_stacked``)."""
        missing = [t for t in self.tokens if t not in params]
        if missing:
            raise ValueError(f"params missing for agents: {missing}")
        self._stacked = self.engine.shard(
            ops.stack_trees([params[t] for t in self.tokens])
        )

    def stacked_parameters(self) -> Pytree:
        return self._stacked

    def get_parameters_deviation(self) -> Dict[Hashable, float]:
        devs = np.asarray(self.engine.deviations(self._stacked))
        return {t: float(d) for t, d in zip(self.tokens, devs)}

    def get_max_parameters_std(self) -> float:
        return float(self.engine.max_std(self._stacked))
