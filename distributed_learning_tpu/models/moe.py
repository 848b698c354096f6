"""Mixture-of-experts MLP with expert parallelism (GShard-style).

The fifth axis of the parallelism matrix: expert weights are a stacked
``(E, ...)`` tree whose leading axis shards over an ``expert`` mesh
axis, and the layer is written as dense einsums against a one-hot
dispatch tensor — the GShard formulation (arXiv:2006.16668) that keeps
shapes static so the XLA partitioner can place the token all-to-alls
itself.  No dynamic routing control flow anywhere: top-k gating
becomes k stacked ``(tokens, E, C)`` one-hots (k is a small static
constant — 1 = Switch routing, 2 = the GShard default), dispatch and
combine are einsum contractions against them.

Capacity: each expert processes at most ``C = ceil(tokens/E * factor)``
tokens; overflow tokens fall through the residual (their MoE
contribution is zero) — the standard GShard drop policy, exposed in the
returned aux so tests and training can watch it.

``shard_moe_params`` places the stacked expert kernels over the mesh;
everything else in the layer is replicated.

:class:`HeldExpertsMLP` is the other formulation, for models with far
more experts than a chip holds (arXiv:2101.03961's expert parallelism
seen from ONE of its chips): the layer is told which experts it holds,
routes over all of them, computes the part of the result its own experts
give, and drops no token.
"""

from __future__ import annotations

import math
from typing import Any

import flax.linen as nn
import jax
import jax.numpy as jnp
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

__all__ = [
    "MoEMLP",
    "HeldExpertsMLP",
    "shard_moe_params",
    "moe_param_spec",
    "collect_load_balance_loss",
    "apply_collecting_moe_aux",
]


def apply_collecting_moe_aux(model, params, x, **apply_kwargs):
    """``model.apply`` with the MoE stat collection open, returning
    ``(output, aux)`` where ``aux`` is the per-layer-mean load-balance
    loss or ``None`` for dense models.

    The shared forward for every step builder that regularizes routing:
    one place owns the ``mutable=["moe_stats"]`` plumbing so the
    builders cannot drift apart.
    """
    out, state = model.apply(
        {"params": params}, x, mutable=["moe_stats"], **apply_kwargs
    )
    return out, collect_load_balance_loss(state)


def collect_load_balance_loss(state: Any):
    """Mean over MoE layers of the sown ``moe_stats/load_balance_loss``.

    ``state`` is the mutable-collection dict returned by
    ``model.apply(..., mutable=["moe_stats"])``.  A model with several
    MoE blocks sows one scalar per block under its own module path; the
    step builders regularize with the MEAN across blocks (the Switch
    convention — arXiv:2101.03961 reports per-layer aux averaged into
    one coefficient) so the coefficient's meaning doesn't change with
    depth.

    Returns ``None`` when the model sowed nothing (a dense model run
    through an MoE-aware step builder) — a trace-time structural fact,
    so step builders can skip the aux term entirely under ``jit``.
    """
    from collections.abc import Mapping

    col = state.get("moe_stats") if isinstance(state, Mapping) else None
    if not col:
        return None
    leaves = [
        leaf
        for path, leaf in jax.tree_util.tree_flatten_with_path(col)[0]
        if any(getattr(k, "key", None) == "load_balance_loss" for k in path)
    ]
    if not leaves:
        return None
    total = leaves[0]
    for leaf in leaves[1:]:
        total = total + leaf
    return total / len(leaves)


class MoEMLP(nn.Module):
    """Top-k MoE feed-forward block: gate -> dispatch -> per-expert MLP
    -> combine.  Input/output (B, T, d).

    ``top_k=1`` is the Switch-style router; ``top_k=2`` the GShard
    default (second choice queues for capacity AFTER every first
    choice, the standard priority rule).  Selected gates renormalize to
    sum to one.  The router's load-balance auxiliary
    (``aux = E * sum_e f_e * P_e`` — arXiv:2101.03961 eq. 4, where
    ``f_e`` is the fraction of tokens first-routed to expert ``e`` and
    ``P_e`` the mean router probability) is sown as
    ``moe_stats/load_balance_loss`` for the training loss to pick up.
    """

    num_experts: int
    mlp_ratio: int = 4
    capacity_factor: float = 1.25
    top_k: int = 1
    drop_tokens: bool = True
    dtype: jnp.dtype = jnp.float32
    # MANUAL expert parallelism (for shard_map contexts — the pipeline's
    # stages, where GSPMD auto-sharding can't reach): when set, this
    # module's expert kernels hold only the LOCAL E/n shard (the caller
    # shards the stacked (E, ...) kernels over the axis), routing is
    # computed against the GLOBAL expert set from the replicated gate,
    # each shard runs its own experts on the (replicated) tokens, and
    # one ``lax.psum`` over the axis combines — no all-to-all at all,
    # because tokens are replicated across the expert axis here (the
    # pp x ep layout).  ``None`` keeps the GSPMD-auto formulation the
    # fsdp/tp/data-sharded paths use.
    expert_axis: str | None = None

    def _local_experts(self, E: int) -> tuple[int, int]:
        """(E_local, my first global expert index) under manual ep."""
        if self.expert_axis is None:
            return E, 0
        n = jax.lax.axis_size(self.expert_axis)
        if E % n:
            raise ValueError(
                f"num_experts {E} must be divisible by the "
                f"{self.expert_axis!r} axis size {n}"
            )
        return E // n, jax.lax.axis_index(self.expert_axis) * (E // n)

    @nn.compact
    def __call__(self, x):
        B, T, d = x.shape
        E = self.num_experts
        S = B * T
        if not 1 <= self.top_k <= E:
            raise ValueError(f"top_k {self.top_k} not in [1, {E}]")
        C = max(1, math.ceil(S / E * self.capacity_factor))
        tokens = x.reshape(S, d)

        gate_logits = nn.Dense(E, use_bias=False, dtype=self.dtype,
                               name="gate")(tokens)  # (S, E)
        probs = jax.nn.softmax(gate_logits.astype(jnp.float32), axis=-1)

        # k routing choices, each a one-hot over experts; choice j+1 is
        # the argmax with previous choices masked out (static shapes —
        # this is a Python loop over a small constant k).
        masked = probs
        onehots, gates = [], []
        for _ in range(self.top_k):
            expert_j = jnp.argmax(masked, axis=-1)             # (S,)
            oh = jax.nn.one_hot(expert_j, E, dtype=jnp.float32)
            onehots.append(oh)
            gates.append(jnp.sum(probs * oh, axis=-1))         # (S,)
            masked = masked * (1.0 - oh)
        if self.top_k > 1:
            # Renormalize the selected gates (GShard): combine weights
            # sum to 1 over the chosen experts.
            gsum = sum(gates)
            gates = [g / jnp.maximum(gsum, 1e-9) for g in gates]
        # top_k == 1 keeps the RAW router probability as the combine
        # weight (Switch-style) — renormalizing would make it constant
        # 1.0 and cut the router out of the gradient entirely.

        # Load-balance aux on FIRST choices (Switch eq. 4).  Sown before
        # the routing-branch split so both branches expose the identical
        # stat surface — the aux depends only on the router, not on how
        # tokens are dispatched.
        f_e = jnp.mean(onehots[0], axis=0)                     # (E,)
        p_e = jnp.mean(probs, axis=0)                          # (E,)
        self.sow(
            "moe_stats", "load_balance_loss",
            E * jnp.sum(f_e * p_e),
            reduce_fn=lambda a, b: b,
        )

        if not self.drop_tokens:
            return self._dense_dropfree(
                x, tokens, onehots, gates, B, T, d, E, S
            )

        # Capacity slots with choice priority: choice j's tokens queue
        # behind ALL tokens of choices < j for the same expert.
        occupancy = jnp.zeros((E,), jnp.float32)
        dispatches = []
        for oh in onehots:
            pos = (jnp.cumsum(oh, axis=0) - oh) * oh           # (S, E)
            pos_in_e = (
                jnp.sum(pos, axis=-1) + jnp.sum(oh * occupancy, axis=-1)
            ).astype(jnp.int32)                                # (S,)
            kept = pos_in_e < C
            dispatches.append(
                oh[:, :, None]
                * jax.nn.one_hot(pos_in_e, C, dtype=jnp.float32)[:, None, :]
                * kept[:, None, None]
            )
            occupancy = occupancy + jnp.sum(oh, axis=0)
        # (S, E, C) combined dispatch, gate-weighted combine tensor.
        dispatch = sum(dispatches)
        combine_w = sum(
            g[:, None, None] * dsp for g, dsp in zip(gates, dispatches)
        )

        # Manual ep: routing above used the GLOBAL expert set; this
        # shard computes only its E/n experts, so slice its columns of
        # the dispatch/combine tensors and declare the LOCAL kernels.
        E_loc, e0 = self._local_experts(E)
        disp_total = jnp.sum(dispatch)  # global (pre-slice) kept count
        if self.expert_axis is not None:
            dispatch = jax.lax.dynamic_slice_in_dim(dispatch, e0, E_loc, 1)
            combine_w = jax.lax.dynamic_slice_in_dim(combine_w, e0, E_loc, 1)

        # Expert buffers: (E, C, d) — the all-to-all XLA inserts when
        # tokens are data-sharded and experts expert-sharded (under
        # manual ep tokens are replicated across the axis, so this is
        # pure local compute instead).
        buffers = jnp.einsum("sec,sd->ecd", dispatch,
                             tokens.astype(jnp.float32))

        h = self.mlp_ratio * d
        w_up = self.param(
            "w_up", nn.initializers.lecun_normal(batch_axis=(0,)),
            (E_loc, d, h), self.dtype,
        )
        b_up = self.param("b_up", nn.initializers.zeros, (E_loc, h),
                          self.dtype)
        w_dn = self.param(
            "w_dn", nn.initializers.lecun_normal(batch_axis=(0,)),
            (E_loc, h, d), self.dtype,
        )
        b_dn = self.param("b_dn", nn.initializers.zeros, (E_loc, d),
                          self.dtype)

        act = jnp.einsum("ecd,edh->ech", buffers, w_up.astype(jnp.float32))
        act = nn.gelu(act + b_up.astype(jnp.float32)[:, None, :])
        out_e = jnp.einsum("ech,ehd->ecd", act, w_dn.astype(jnp.float32))
        out_e = out_e + b_dn.astype(jnp.float32)[:, None, :]

        # Combine with the gate-weighted tensor: out_s = sum over the
        # token's kept choices of gate_j * expert_out.  Under manual ep
        # each shard contributes its experts' share; the psum exit is
        # the whole combine (and, like the TP stages, transposes to the
        # correct cotangent broadcast automatically — training/tp.py's
        # NOTE).
        out = jnp.einsum("sec,ecd->sd", combine_w, out_e)
        if self.expert_axis is not None:
            # graftlint: disable=raw-collective-in-shard-map -- manual-EP combine exit: psum over expert_axis totals the shards' gate-weighted expert outputs; entry-cast transpose is the cotangent broadcast (training/tp.py NOTE)
            out = jax.lax.psum(out, self.expert_axis)
        self.sow(
            "moe_stats", "dropped_fraction",
            1.0 - disp_total / (S * self.top_k),
            reduce_fn=lambda a, b: b,
        )
        return out.reshape(B, T, d).astype(x.dtype)

    def _dense_dropfree(self, x, tokens, onehots, gates, B, T, d,
                        E, S):
        """Drop-free path (``drop_tokens=False`` — autoregressive
        decode): run EVERY expert on every token and combine with the
        top-k gate weights.  Capacity drops depend on the other tokens
        sharing the flattened batch (order-dependent), so decode must
        not drop or incremental and from-scratch computations of the
        same position diverge.  Dense all-experts costs E*S*d*h — less
        than the (S, E, S)-dispatch alternative whenever S > ratio*d —
        and keeps every shape static.
        """
        h = self.mlp_ratio * d
        # Declare the SAME params as the dropping branch (names, shapes,
        # initializers) so a drop-free module inits/shards identically
        # (LOCAL shard shapes under manual ep, exactly as there).
        E_loc, e0 = self._local_experts(E)
        w_up = self.param(
            "w_up", nn.initializers.lecun_normal(batch_axis=(0,)),
            (E_loc, d, h), self.dtype,
        )
        b_up = self.param("b_up", nn.initializers.zeros, (E_loc, h),
                          self.dtype)
        w_dn = self.param(
            "w_dn", nn.initializers.lecun_normal(batch_axis=(0,)),
            (E_loc, h, d), self.dtype,
        )
        b_dn = self.param("b_dn", nn.initializers.zeros, (E_loc, d),
                          self.dtype)
        act = jnp.einsum(
            "sd,edh->seh", tokens.astype(jnp.float32),
            w_up.astype(jnp.float32),
        ) + b_up.astype(jnp.float32)[None]
        act = nn.gelu(act)
        out_e = jnp.einsum(
            "seh,ehd->sed", act, w_dn.astype(jnp.float32)
        ) + b_dn.astype(jnp.float32)[None]
        weight = sum(
            g[:, None] * oh for g, oh in zip(gates, onehots)
        )  # (S, E) over the GLOBAL experts; slice this shard's columns.
        if self.expert_axis is not None:
            weight = jax.lax.dynamic_slice_in_dim(weight, e0, E_loc, 1)
        out = jnp.einsum("se,sed->sd", weight, out_e)
        if self.expert_axis is not None:
            # graftlint: disable=raw-collective-in-shard-map -- manual-EP combine exit (dense top-k path): same psum-over-expert_axis combine as above
            out = jax.lax.psum(out, self.expert_axis)
        self.sow(
            "moe_stats", "dropped_fraction", jnp.zeros(()),
            reduce_fn=lambda a, b: b,
        )
        return out.reshape(B, T, d).astype(x.dtype)


class HeldExpertsMLP(nn.Module):
    """One chip's share of a top-k expert layer with a shared expert
    (the sparse block of arXiv:2101.03961 as hybrid MoE LMs configure
    it: softmax router, renormalised top-k, SwiGLU experts of a stated
    width, one always-on shared expert behind a sigmoid gate).

    The router scores all ``num_experts`` in f32 (softmax over all of
    them, top ``top_k``, renormalised when ``norm_topk``); this layer
    holds experts ``[first_expert, first_expert + experts_held)`` and
    computes their part of the sum alone: a (token, choice) pair that
    falls on an expert held elsewhere adds nothing here, and a token
    whose choices all lie elsewhere gets the shared expert alone.  No
    exchange and no stand-in for the absent chips.

    Sized for the worst case the routing can produce, which is every
    token on every held expert: each held expert runs over ALL the
    tokens and a token's result is weighted by its gate for that expert,
    zero where the expert was not among its choices (dense dispatch over
    the held experts: ``experts_held * tokens`` rows whatever the router
    does, three large matrix products, no gather, no scatter, no buffer
    that can overflow).  A sorted buffer of fewer rows was built first
    and measured on the chip: a share's router LEARNS to prefer the
    experts held here (the absent ones add nothing, so only these lower
    the loss), the held pairs grew sevenfold within sixty steps and
    overran any buffer short of the worst case, at which size the
    buffer's gathers cost more than this (PERF.md, PR 28).

    Counters sown under ``counters`` (``obs/carry.py::collect_counters``):
    ``moe.rows_held`` (the (token, choice) pairs that fall on held
    experts) and ``moe.load_max`` (the fullest held expert's pairs): what
    a dispatch that gathers only the chosen rows would have to place.
    Nothing counts cut pairs, because dense dispatch has no way to cut
    one.  Input/output (B, T, d); parameters f32.

    What it accepts beyond that layer (the defaults are that layer, its
    parameter tree and its program as they were; anything else is a
    ``ValueError`` that names the argument):

    * ``score_func``: ``"softmax"`` over all experts, or ``"sigmoid"``,
      each expert scored alone (DeepSeek-V3, arXiv:2412.19437, eq. 15);
    * ``route_scale``: multiplies the chosen experts' (normalised)
      weights, the published ``routed_scaling_factor``;
    * ``bias_rate``: ``None``, or the step ``gamma`` of the balancing bias
      of the same paper's section 2.1.2 (``noaux_tc``): one f32 value an
      expert, zero at first, in the ``batch_stats`` collection — state no
      gradient moves, which a trainer carries, shards and saves as it
      does BatchNorm's and never mixes.  The top k are chosen on ``score
      + bias``; the weights are the scores alone.  A ``train=True`` call
      whose ``batch_stats`` are mutable leaves ``bias + gamma * sign(mean
      load - load)`` behind, the load being the (token, choice) pairs
      each of ALL the experts received from this call's tokens.
      ``moe.load_max_all`` counts the fullest of them;
    * ``shared_gate``: the shared expert's sigmoid gate, or (``False``)
      the shared expert added as it is.
    """

    num_experts: int
    experts_held: int
    first_expert: int = 0
    top_k: int = 1
    expert_width: int = 512
    shared_width: int = 512
    norm_topk: bool = True
    dtype: jnp.dtype = jnp.float32
    score_func: str = "softmax"      # | "sigmoid"
    route_scale: float = 1.0         # multiplies the chosen weights
    bias_rate: float | None = None   # the balancing bias's step; None: none
    shared_gate: bool = True         # the shared expert's sigmoid gate

    @nn.compact
    def __call__(self, x, train: bool = False):
        B, T, d = x.shape
        E, Eh, K = self.num_experts, self.experts_held, self.top_k
        if not (1 <= K <= E and 1 <= Eh and 0 <= self.first_expert
                and self.first_expert + Eh <= E):
            raise ValueError(
                f"top_k {K}, experts [{self.first_expert}, "
                f"{self.first_expert + Eh}) do not fit {E} experts"
            )
        if self.score_func not in ("softmax", "sigmoid"):
            raise ValueError(
                f"unknown score_func {self.score_func!r} "
                "(want softmax|sigmoid)")
        if self.bias_rate is not None and not self.bias_rate > 0:
            raise ValueError(
                f"bias_rate must be None or positive, got {self.bias_rate}")
        if not self.route_scale > 0:
            raise ValueError(
                f"route_scale must be positive, got {self.route_scale}")
        S, h = B * T, self.expert_width
        tokens = x.reshape(S, d)

        with jax.named_scope("moe_route"):
            router = self.param("router", nn.initializers.lecun_normal(),
                                (d, E), jnp.float32)
            # The router reads the activations rounded to their dtype, and
            # that value is sown as "router_input".  Written out because XLA
            # may hand a consumer the producer's unrounded f32 where a bf16
            # tensor is read back as f32 (xla_allow_excess_precision), fusion
            # by fusion: on the chip the block's norm read by this layer and
            # the same norm read back by a caller differed by a bf16 rounding
            # in half their elements, and one token in twenty has its k-th
            # and (k+1)-th expert closer than that.  Whoever checks the
            # routing (the chip benchmark's reference) needs the input the
            # router really had.  Sown values are read back only by a caller
            # that makes "intermediates" mutable.
            info = jnp.finfo(tokens.dtype)
            seen = jax.lax.reduce_precision(
                tokens.astype(jnp.float32), info.nexp, info.nmant)
            self.sow("intermediates", "router_input", seen)
            logits = jnp.dot(seen, router, precision="highest")
            self.sow("intermediates", "router_logits", logits)
            if self.score_func == "softmax":
                scores = jax.nn.softmax(logits, -1)
            else:
                scores = jax.nn.sigmoid(logits)
            if self.bias_rate is None:
                gates, chosen = jax.lax.top_k(scores, K)
            else:
                bias = self.variable("batch_stats", "route_bias",
                                     jnp.zeros, (E,), jnp.float32)
                # the bias picks the experts; the weights never see it
                _, chosen = jax.lax.top_k(scores + bias.value, K)
                gates = jnp.take_along_axis(scores, chosen, axis=-1)
            self.sow("intermediates", "chosen", chosen)
            if self.norm_topk:
                total = jnp.sum(gates, -1, keepdims=True)
                # sigmoid scores can all be zero: the published epsilon
                gates = gates / (total if self.score_func == "softmax"
                                 else total + 1e-20)
            if self.route_scale != 1.0:
                gates = gates * self.route_scale
            local = chosen - self.first_expert               # (S, K)
            on = local[..., None] == jnp.arange(Eh)          # (S, K, Eh)
            # weights[s, e]: token s's gate for held expert e, else 0
            weights = jnp.sum(jnp.where(on, gates[..., None], 0.0), axis=1)
            self.sow("intermediates", "held_weights", weights)
        counts = jnp.sum(on, axis=(0, 1))                    # (Eh,) pairs
        for name, value in (
            ("moe.rows_held", jnp.sum(counts)),
            ("moe.load_max", jnp.max(counts)),
        ):
            self.sow("counters", name, value.astype(jnp.int32),
                     reduce_fn=lambda a, b: b)
        if self.bias_rate is not None:
            with jax.named_scope("moe_bias"):
                # the pairs each of ALL the experts received
                load = jnp.sum(chosen[..., None] == jnp.arange(E),
                               axis=(0, 1)).astype(jnp.float32)
                self.sow("counters", "moe.load_max_all",
                         jnp.max(load).astype(jnp.int32),
                         reduce_fn=lambda a, b: b)
                if train and self.is_mutable_collection("batch_stats") \
                        and not self.is_initializing():
                    bias.value = bias.value + self.bias_rate * jnp.sign(
                        jnp.mean(load) - load)

        with jax.named_scope("moe_experts"):
            init = nn.initializers.lecun_normal(batch_axis=(0,))
            w_gate = self.param("w_gate", init, (Eh, d, h), jnp.float32)
            w_up = self.param("w_up", init, (Eh, d, h), jnp.float32)
            w_down = self.param("w_down", init, (Eh, h, d), jnp.float32)
            xc, w_gate, w_up, w_down = nn.dtypes.promote_dtype(
                tokens, w_gate, w_up, w_down, dtype=self.dtype)
            mid = nn.silu(jnp.einsum("sd,edh->seh", xc, w_gate)) * jnp.einsum(
                "sd,edh->seh", xc, w_up)
            # the gate goes in before the down-projection, in f32, so that
            # the sum over experts is one product over (expert, width)
            mid = (mid.astype(jnp.float32) * weights[..., None]).astype(
                self.dtype)
            out = jnp.einsum("seh,ehd->sd", mid, w_down,
                             preferred_element_type=jnp.float32)

        with jax.named_scope("moe_shared"):
            dense = lambda f, name: nn.Dense(
                f, use_bias=False, dtype=self.dtype, name=name)
            shared = dense(d, "shared_down")(
                nn.silu(dense(self.shared_width, "shared_gate_proj")(tokens))
                * dense(self.shared_width, "shared_up")(tokens)
            )
            if self.shared_gate:
                out = out + shared.astype(jnp.float32) * jax.nn.sigmoid(
                    dense(1, "shared_gate")(tokens).astype(jnp.float32)
                )
            else:
                out = out + shared.astype(jnp.float32)
        return out.reshape(B, T, d).astype(x.dtype)


def moe_param_spec(path: tuple, leaf, expert_axis: str) -> P:
    """Stacked expert kernels shard over the expert axis; the gate and
    everything else replicate."""
    names = [getattr(k, "key", str(k)) for k in path]
    if names and names[-1] in ("w_up", "b_up", "w_dn", "b_dn"):
        return P(expert_axis, *([None] * (leaf.ndim - 1)))
    return P()


def shard_moe_params(params: Any, mesh: Mesh,
                     expert_axis: str = "expert") -> Any:
    """Device-put an :class:`MoEMLP`-bearing param tree with the expert
    kernels split over ``expert_axis``."""
    def place(path, leaf):
        return jax.device_put(
            leaf, NamedSharding(mesh, moe_param_spec(path, leaf, expert_axis))
        )

    return jax.tree_util.tree_map_with_path(place, params)
