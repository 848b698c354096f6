"""Decoder-only transformer with pluggable sequence-parallel attention.

No counterpart exists in the reference (its models are tabular/image nets,
SURVEY.md §2 C11-C13); this model exists so the framework's long-context
machinery (``ops/ring_attention.py``) has a first-class consumer: the same
gossip-SGD trainer can train a language model whose attention runs
sequence-parallel over the device ring.

Knobs:

* ``attn_impl`` — ``"full"`` (reference), ``"flash"`` (Pallas kernels),
  ``"ring"`` / ``"ring_flash"`` / ``"ulysses"`` (inside ``shard_map``
  with ``seq_axis`` sharded);
* ``attn_window`` — causal sliding-window attention (full/flash);
* ``pos_emb`` — learned table or rotary (``"rope"``, global positions,
  sequence-parallel safe);
* ``num_kv_heads`` — grouped-query attention (KV cache shrinks H/Hkv);
* ``mlp`` / ``num_experts`` / ``moe_top_k`` — dense or expert-parallel
  MoE feed-forward;
* ``dropout_rate`` — residual-branch dropout under ``train=True`` (the
  trainer already threads dropout rngs);
* ``decode`` + :func:`generate` — KV-cache autoregressive generation;
* ``full_attention_interval`` / ``norm`` / ``attn_gate`` /
  ``mlp="held_experts"`` — hybrid blocks (training path only): Gated
  DeltaNet layers (``models/gated_delta.py``) with every n-th layer
  gated softmax attention, zero-centred RMSNorm, partial rotary, and one
  chip's share of a many-expert layer (``models/moe.py::HeldExpertsMLP``).
"""

from __future__ import annotations

import contextlib
import functools
from typing import Any

import flax.linen as nn
import jax
import jax.numpy as jnp

from distributed_learning_tpu.models.moe import MoEMLP
from distributed_learning_tpu.ops.ring_attention import (
    attention_reference,
    ring_attention,
    ring_flash_attention,
    ulysses_attention,
)

__all__ = ["TransformerLM", "generate", "sample_fn", "validate_sampling"]


def _rope(x, positions, *, base: float = 10000.0,
          rotary_dim: int | None = None):
    """Rotary position embedding (arXiv:2104.09864) over the head dim,
    in the half-split (GPT-NeoX) layout: dimension ``j`` pairs with
    ``j + Dh/2`` and the pair rotates by ``pos / base^(2j/Dh)``.  (The
    paper's interleaved consecutive-pair layout is a fixed permutation
    of this one — self-consistent here, but checkpoints ported from
    interleaved-layout models would need that permutation applied.)

    ``x`` is (B, T, H, Dh) with even Dh; ``positions`` is (T,) GLOBAL
    token positions — under sequence parallelism each shard passes its
    offset slice, and in decode mode the cache write index, so the same
    rotation is applied no matter how the sequence is split.  Applied to
    Q and K before attention; relative-position structure then lives in
    the dot products and no learned position table is needed.

    ``rotary_dim`` turns only the first ``rotary_dim`` dimensions of the
    head (partial rotary, the GPT-NeoX ``rotary_pct``); the rest pass
    through untouched.
    """
    if rotary_dim is not None and rotary_dim != x.shape[-1]:
        turned = _rope(x[..., :rotary_dim], positions, base=base)
        return jnp.concatenate([turned, x[..., rotary_dim:]], axis=-1)
    B, T, H, Dh = x.shape
    if Dh % 2:
        raise ValueError(f"rope needs an even head_dim, got {Dh}")
    half = Dh // 2
    freqs = positions[:, None].astype(jnp.float32) / (
        base ** (jnp.arange(half, dtype=jnp.float32) / half)
    )  # (T, half)
    cos = jnp.cos(freqs)[None, :, None, :]
    sin = jnp.sin(freqs)[None, :, None, :]
    x1, x2 = x[..., :half].astype(jnp.float32), x[..., half:].astype(jnp.float32)
    out = jnp.concatenate(
        [x1 * cos - x2 * sin, x1 * sin + x2 * cos], axis=-1
    )
    return out.astype(x.dtype)


class RMSNorm(nn.Module):
    """Zero-centred RMSNorm (arXiv:1910.07467 with the weight stored as
    its offset from one): ``x * rsqrt(mean x^2 + eps) * (1 + w)``, ``w``
    initialised to zero, statistics in f32."""

    eps: float = 1e-6
    dtype: jnp.dtype = jnp.float32

    @nn.compact
    def __call__(self, x):
        w = self.param("scale", nn.initializers.zeros, (x.shape[-1],),
                       jnp.float32)
        xf = x.astype(jnp.float32)
        y = xf * jax.lax.rsqrt(jnp.mean(xf * xf, -1, keepdims=True) + self.eps)
        return (y * (1.0 + w)).astype(self.dtype)


def _norm(kind: str, eps: float, dtype):
    if kind == "layernorm":
        return nn.LayerNorm(epsilon=eps, dtype=dtype)
    if kind == "rmsnorm":
        return RMSNorm(eps=eps, dtype=dtype)
    raise ValueError(f"unknown norm {kind!r} (want layernorm|rmsnorm)")


class _Attention(nn.Module):
    num_heads: int
    head_dim: int
    attn_impl: str = "full"
    seq_axis: str = "seq"
    dtype: jnp.dtype = jnp.float32
    window: int | None = None  # sliding window (full/flash paths only)
    decode: bool = False       # autoregressive KV-cache mode
    cache_len: int = 0         # static KV-cache length (decode mode)
    rope: bool = False         # rotary Q/K (positions arg required)
    num_kv_heads: int | None = None  # GQA: kv heads < query heads
    # MANUAL megatron tensor parallelism (shard_map contexts — the
    # pipeline's stages, where GSPMD annotation can't reach): when set,
    # this module declares only its LOCAL H/n heads' kernels (the
    # caller shards the stacked kernels over the axis), attention runs
    # head-local, and the out-projection's partial product exits
    # through one raw lax.psum — the shard_map transpose rules supply
    # the Megatron f/g pair (training/tp.py's NOTE).
    tp_axis: str | None = None
    # Gated attention (the softmax layers of Gated-DeltaNet hybrids):
    # q_proj gives the query AND a per-head output gate, q and k get a
    # per-head zero-centred RMSNorm, and the output is multiplied by
    # sigmoid(gate) before the out-projection.
    gated: bool = False
    rotary_dim: int | None = None  # partial rotary (None: the whole head)
    rope_base: float = 10000.0
    norm_eps: float = 1e-6

    def _tp_shard(self, n_global: int, what: str) -> int:
        if self.tp_axis is None:
            return n_global
        n = jax.lax.axis_size(self.tp_axis)
        if n_global % n:
            raise ValueError(
                f"{what} {n_global} must be divisible by the "
                f"{self.tp_axis!r} axis size {n}"
            )
        return n_global // n

    @nn.compact
    def __call__(self, x, positions=None):
        # QKV as ONE DenseGeneral with structured (3, H, Dh) output
        # features — the kernel is (d_model, 3, H, Dh), so tensor
        # parallelism shards it on the HEAD axis (training/tp.py) and
        # every downstream attention op is head-local: no activation
        # resharding inside the block.  A flat Dense(3*H*Dh) kernel can
        # only be split contiguously over the concatenated [Q|K|V]
        # columns, which straddles heads and forces XLA to re-gather.
        if self.tp_axis is not None and self.decode:
            raise ValueError(
                "manual tp_axis is a training-stage mode; decode uses "
                "the GSPMD path (training/tp.py::make_tp_generate)"
            )
        if self.gated and (self.tp_axis is not None or self.decode):
            raise ValueError(
                "gated attention is a training-path layer: no tp_axis, "
                "no decode"
            )
        H = self._tp_shard(self.num_heads, "num_heads")
        Hkv = (self._tp_shard(self.num_kv_heads, "num_kv_heads")
               if self.num_kv_heads is not None else H)
        gate = None
        if self.gated:
            qg = nn.DenseGeneral(
                features=(2, H, self.head_dim), use_bias=False,
                dtype=self.dtype, name="q_proj",
            )(x)  # (B, T, 2, H, Dh): query, gate
            kv = nn.DenseGeneral(
                features=(2, Hkv, self.head_dim), use_bias=False,
                dtype=self.dtype, name="kv_proj",
            )(x)
            with jax.named_scope("attn_gate"):
                q, gate = qg[:, :, 0], qg[:, :, 1]
                q = RMSNorm(self.norm_eps, self.dtype, name="q_norm")(q)
                k = RMSNorm(self.norm_eps, self.dtype, name="k_norm")(
                    kv[:, :, 0])
                v = kv[:, :, 1]
        elif Hkv == H:
            qkv = nn.DenseGeneral(
                features=(3, H, self.head_dim),
                use_bias=False, dtype=self.dtype,
            )(x)  # (B, T, 3, H, Dh)
            q, k, v = qkv[:, :, 0], qkv[:, :, 1], qkv[:, :, 2]
        else:
            # Grouped-query attention (arXiv:2305.13245): Hkv shared K/V
            # heads serve H/Hkv query heads each.  Projections, decode
            # cache, and (in decode) the cache WRITE all carry only Hkv
            # heads — the KV-cache shrinks by H/Hkv, which is the point;
            # compute paths broadcast K/V up to H just before attention.
            if H % Hkv:
                raise ValueError(
                    f"num_heads {H} must divide by num_kv_heads {Hkv}"
                )
            q = nn.DenseGeneral(
                features=(H, self.head_dim), use_bias=False,
                dtype=self.dtype, name="q_proj",
            )(x)  # (B, T, H, Dh)
            kv = nn.DenseGeneral(
                features=(2, Hkv, self.head_dim), use_bias=False,
                dtype=self.dtype, name="kv_proj",
            )(x)  # (B, T, 2, Hkv, Dh)
            k, v = kv[:, :, 0], kv[:, :, 1]
        if self.rope:
            # One rope application for BOTH modes: the caller always
            # passes global positions (decode mode derives them from the
            # top-level position counter), so no per-layer recompute.
            turn = functools.partial(
                _rope, positions=positions, base=self.rope_base,
                rotary_dim=self.rotary_dim,
            )
            with (jax.named_scope("attn_gate") if self.gated
                  else contextlib.nullcontext()):
                q, k = turn(q), turn(k)
        if self.window is not None and self.attn_impl not in ("full", "flash"):
            raise ValueError(
                f"window is only supported for full/flash attention, "
                f"not {self.attn_impl!r}"
            )
        if self.decode:
            return self._decode_step(q, k, v, x)
        k, v = self._expand_kv(k, v, H)
        if self.attn_impl == "full":
            out = attention_reference(q, k, v, causal=True,
                                      window=self.window)
        elif self.attn_impl == "flash":
            from distributed_learning_tpu.ops.flash_attention import flash_attention

            out = flash_attention(q, k, v, causal=True, window=self.window)
        elif self.attn_impl == "ring":
            out = ring_attention(q, k, v, axis_name=self.seq_axis, causal=True)
        elif self.attn_impl == "ring_flash":
            out = ring_flash_attention(
                q, k, v, axis_name=self.seq_axis, causal=True
            )
        elif self.attn_impl == "ulysses":
            out = ulysses_attention(q, k, v, axis_name=self.seq_axis, causal=True)
        else:
            raise ValueError(f"unknown attn_impl {self.attn_impl!r}")
        if gate is not None:
            with jax.named_scope("attn_gate"):
                out = out * jax.nn.sigmoid(gate.astype(jnp.float32)).astype(
                    out.dtype)
        # Out-projection contracts (H, Dh) directly — kernel (H, Dh, d),
        # head-sharded under TP with one psum placed by the partitioner.
        return self._out_proj(out, x.shape[-1])

    def _expand_kv(self, k, v, H: int | None = None):
        """Broadcast Hkv K/V heads up to the H query heads (no-op when
        equal): repeat each kv head for its group of queries.  ``H`` is
        the query-head count actually in play — the LOCAL shard under
        manual tp, where ``num_heads`` would be the global count."""
        if H is None:
            H = self.num_heads
        if k.shape[2] == H:
            return k, v
        g = H // k.shape[2]
        return (jnp.repeat(k, g, axis=2), jnp.repeat(v, g, axis=2))

    def _out_proj(self, out, d):
        y = nn.DenseGeneral(
            features=d, axis=(-2, -1),
            use_bias=False, dtype=self.dtype, name="DenseGeneral_1",
        )(out)
        if self.tp_axis is not None:
            # Local heads contracted a partial product; one psum totals
            # it (bias-free, so nothing to de-duplicate).
            # graftlint: disable=raw-collective-in-shard-map -- megatron g exit: attention out-projection psum over tp_axis (training/tp.py NOTE)
            y = jax.lax.psum(y, self.tp_axis)
        return y

    def _decode_step(self, q, k, v, x):
        """Autoregressive attention against a static KV cache.

        One method covers prefill (T = prompt length at write index 0)
        and stepping (T = 1): this call's K/V are written at positions
        ``[i, i+T)`` of a fixed ``(B, cache_len, H, Dh)`` cache pair,
        and each query row ``t`` attends to cached positions
        ``<= i + t`` (inside ``window`` if set) — masking by position
        instead of slicing keeps every shape static for jit.

        Stepping past ``cache_len`` poisons the output with NaN: the
        clamped ``dynamic_update_slice`` would otherwise land the write
        on the last slot while the position counter keeps advancing —
        silently wrong attention.  ``generate()`` never reaches this;
        the guard is for direct ``apply`` users driving the cache
        themselves (the index is a traced value, so a Python raise
        cannot see it under jit).
        """
        B, T, _, Dh = q.shape
        Hkv = k.shape[2]  # under GQA the cache holds only the kv heads
        L = self.cache_len
        if T > L:
            raise ValueError(
                f"prefill length {T} exceeds the cache ({L}); a longer "
                "prompt would silently clamp the cache write"
            )
        ck = self.variable(
            "cache", "key",
            lambda: jnp.zeros((B, L, Hkv, Dh), self.dtype),
        )
        cv = self.variable(
            "cache", "value",
            lambda: jnp.zeros((B, L, Hkv, Dh), self.dtype),
        )
        idx = self.variable(
            "cache", "index", lambda: jnp.zeros((), jnp.int32)
        )
        i = idx.value
        ck.value = jax.lax.dynamic_update_slice(
            ck.value, k.astype(self.dtype), (0, i, 0, 0)
        )
        cv.value = jax.lax.dynamic_update_slice(
            cv.value, v.astype(self.dtype), (0, i, 0, 0)
        )
        idx.value = i + T
        scale = 1.0 / (Dh ** 0.5)
        # Grouped attention against the Hkv-head cache: reshape queries
        # to (B, T, Hkv, group, Dh) and contract against the cache
        # directly — the expanded (B, L, H, Dh) copy jnp.repeat would
        # materialize per generated token is exactly the memory GQA
        # exists to avoid.
        g = q.shape[2] // Hkv
        qg = q.reshape(B, T, Hkv, g, Dh)
        s = jnp.einsum(
            "bqhgd,bkhd->bhgqk", qg, ck.value
        ).astype(jnp.float32) * scale
        qpos = i + jnp.arange(T)                      # (T,)
        kpos = jnp.arange(L)                          # (L,)
        live = kpos[None, :] <= qpos[:, None]         # (T, L)
        if self.window is not None:
            live &= kpos[None, :] > qpos[:, None] - self.window
        s = jnp.where(live[None, None, None], s, -jnp.inf)
        p = jax.nn.softmax(s, axis=-1)
        out = jnp.einsum(
            "bhgqk,bkhd->bqhgd", p.astype(cv.value.dtype), cv.value
        ).reshape(B, T, Hkv * g, Dh)
        # Overflow guard (see docstring): once i + T walks past the
        # cache the write has been clamped, so every subsequent output
        # is garbage — make it loud, and keep it loud (idx only grows).
        out = jnp.where(i + T > L, jnp.nan, out)
        return self._out_proj(out, x.shape[-1])


class _RowDense(nn.Module):
    """Row-parallel Dense for the manual-TP MLP exit: the kernel holds
    this shard's ROWS (the caller shards dim 0 over ``tp_axis``), the
    partial product exits through one psum, and the (replicated) bias
    is added AFTER it — added before, every shard would contribute a
    copy and the psum would scale it by the axis size.  Param names and
    initializers match ``nn.Dense`` exactly so the tree is
    checkpoint-compatible with the unsharded block."""

    features: int
    tp_axis: str
    dtype: jnp.dtype = jnp.float32

    @nn.compact
    def __call__(self, x):
        kernel = self.param(
            "kernel", nn.initializers.lecun_normal(),
            (x.shape[-1], self.features), self.dtype,
        )
        bias = self.param(
            "bias", nn.initializers.zeros, (self.features,), self.dtype
        )
        x, kernel, bias = nn.dtypes.promote_dtype(
            x, kernel, bias, dtype=self.dtype
        )
        # graftlint: disable=raw-collective-in-shard-map -- megatron g exit: row-sharded kernel's partial matmul psum'd over tp_axis before the (replicated) bias
        return jax.lax.psum(x @ kernel, self.tp_axis) + bias


class _LatentAttention(nn.Module):
    """Multi-head latent attention in its training form, nothing absorbed
    (DeepSeek-V2, arXiv:2405.04434, section 2.1.2, without the query's
    low-rank path: ``q_lora_rank`` null).  Keys and values come from ONE
    compressed latent a token (``kv_rank`` wide, RMS-normed), the
    positions from a rotary part beside it: ``rope_dim`` more columns of
    the query's heads, and one rotary key of ``rope_dim`` that every head
    shares.  A head's query/key is then ``nope_dim + rope_dim`` wide and
    its value ``v_dim``: two widths, which the flash kernels take as they
    are.  No biases; training path only.  Scopes ``mla_proj`` (the three
    projections, the latent's norm, rotary) and ``mla_attn`` (the
    kernels and the output projection)."""

    num_heads: int
    nope_dim: int
    rope_dim: int
    v_dim: int
    kv_rank: int
    attn_impl: str = "full"
    dtype: jnp.dtype = jnp.float32
    rope_base: float = 10000.0
    norm_eps: float = 1e-6

    @nn.compact
    def __call__(self, x, positions):
        if self.attn_impl not in ("full", "flash"):
            raise ValueError(
                f"latent attention runs attn_impl full|flash, not "
                f"{self.attn_impl!r}")
        B, T, d = x.shape
        H, Dn, Dr, Dv = self.num_heads, self.nope_dim, self.rope_dim, self.v_dim
        R = self.kv_rank
        proj = lambda features, name: nn.DenseGeneral(
            features=features, use_bias=False, dtype=self.dtype, name=name)
        turn = functools.partial(_rope, positions=positions,
                                 base=self.rope_base)
        with jax.named_scope("mla_proj"):
            q = proj((H, Dn + Dr), "q_proj")(x)           # [q_nope | q_pe]
            ckv = proj(R + Dr, "kv_a_proj")(x)            # [c | k_pe]
            c = RMSNorm(self.norm_eps, self.dtype, name="kv_a_norm")(
                ckv[..., :R])
            kv = proj((H, Dn + Dv), "kv_b_proj")(c)       # [k_nope | v]
            # the ONE rotary key of a token, which every head shares
            k_pe = turn(ckv[..., None, R:])               # (B, T, 1, Dr)
            k_pe = jnp.broadcast_to(k_pe, (B, T, H, Dr))
            q = jnp.concatenate([q[..., :Dn], turn(q[..., Dn:])], axis=-1)
            k = jnp.concatenate([kv[..., :Dn], k_pe], axis=-1)
            v = kv[..., Dn:]
            # read back only by a caller that makes "intermediates"
            # mutable (the chip benchmark's check of the three operands)
            for name, value in (("q", q), ("k", k), ("v", v)):
                self.sow("intermediates", name, value)
        with jax.named_scope("mla_attn"):
            scale = float((Dn + Dr) ** -0.5)
            if self.attn_impl == "full":
                out = attention_reference(q, k, v, causal=True,
                                          sm_scale=scale)
            else:
                from distributed_learning_tpu.ops.flash_attention import (
                    flash_attention,
                )

                out = flash_attention(q, k, v, causal=True, sm_scale=scale)
            return nn.DenseGeneral(
                features=d, axis=(-2, -1), use_bias=False, dtype=self.dtype,
                name="o_proj",
            )(out)


class _Block(nn.Module):
    num_heads: int
    head_dim: int
    mlp_ratio: int = 4
    attn_impl: str = "full"
    seq_axis: str = "seq"
    dtype: jnp.dtype = jnp.float32
    mlp: str = "dense"
    num_experts: int = 4
    moe_top_k: int = 1
    attn_window: int | None = None
    decode: bool = False
    cache_len: int = 0
    rope: bool = False
    num_kv_heads: int | None = None
    dropout_rate: float = 0.0
    moe_expert_axis: str | None = None  # manual ep (models/moe.py)
    tp_axis: str | None = None          # manual megatron tp (_Attention)
    moe_capacity_factor: float = 1.25   # GShard slots per expert
    # Hybrid blocks (TransformerLM builds these; training path only):
    norm: str = "layernorm"             # | "rmsnorm" (zero-centred)
    norm_eps: float = 1e-6
    attn_gate: bool = False             # gated attention (_Attention.gated)
    rotary_dim: int | None = None
    rope_base: float = 10000.0
    # GatedDeltaNet's arguments: this block's mixer is linear attention
    linear_attn: Any = None
    held_experts: Any = None            # HeldExpertsMLP's (mlp="held_experts")
    # _LatentAttention's arguments: this block's mixer is latent attention
    latent_attn: Any = None
    dense_width: int | None = None      # the SwiGLU's (mlp="swiglu")

    @nn.compact
    def __call__(self, x, positions=None, train: bool = False):
        def drop(h):
            # Residual-branch dropout (the GPT placement), gated like the
            # WRN blocks: deterministic unless training.
            if self.dropout_rate > 0:
                h = nn.Dropout(
                    self.dropout_rate, deterministic=not train
                )(h)
            return h

        if self.tp_axis is not None and self.mlp == "moe":
            raise ValueError(
                "manual tp_axis with mlp='moe' is not supported: shard "
                "experts over an expert axis instead (moe_expert_axis)"
            )
        hybrid = (self.linear_attn is not None or self.latent_attn is not None
                  or self.mlp in ("held_experts", "swiglu"))
        if hybrid and (self.decode or self.tp_axis is not None
                       or self.moe_expert_axis is not None):
            raise ValueError(
                "linear-attention, latent-attention, held-experts and "
                "SwiGLU blocks are training-path layers: no decode, "
                "tp_axis or expert axis"
            )
        h = _norm(self.norm, self.norm_eps, self.dtype)(x)
        if self.linear_attn is not None:
            from distributed_learning_tpu.models.gated_delta import (
                GatedDeltaNet,
            )

            x = x + drop(GatedDeltaNet(
                dtype=self.dtype, eps=self.norm_eps, **self.linear_attn
            )(h))
        elif self.latent_attn is not None:
            x = x + drop(_LatentAttention(
                self.num_heads, attn_impl=self.attn_impl, dtype=self.dtype,
                rope_base=self.rope_base, norm_eps=self.norm_eps,
                **self.latent_attn
            )(h, positions))
        else:
            x = x + drop(_Attention(
                self.num_heads, self.head_dim, self.attn_impl, self.seq_axis,
                self.dtype, self.attn_window, self.decode, self.cache_len,
                self.rope, self.num_kv_heads, tp_axis=self.tp_axis,
                gated=self.attn_gate, rotary_dim=self.rotary_dim,
                rope_base=self.rope_base, norm_eps=self.norm_eps,
            )(h, positions))
        h = _norm(self.norm, self.norm_eps, self.dtype)(x)
        if self.mlp == "held_experts":
            from distributed_learning_tpu.models.moe import HeldExpertsMLP

            return x + drop(HeldExpertsMLP(
                dtype=self.dtype, **self.held_experts
            )(h, train))
        if self.mlp == "swiglu":
            # the dense layers before a many-expert stack's first expert
            # layer (the published first_k_dense_replace): no biases
            if not self.dense_width:
                raise ValueError("mlp='swiglu' needs dense_width")
            dense = lambda f, name: nn.Dense(
                f, use_bias=False, dtype=self.dtype, name=name)
            with jax.named_scope("mlp_dense"):
                return x + drop(dense(x.shape[-1], "down_proj")(
                    nn.silu(dense(self.dense_width, "gate_proj")(h))
                    * dense(self.dense_width, "up_proj")(h)))
        if self.mlp == "moe":
            # Expert-parallel feed-forward (models/moe.py): params become
            # stacked (E, ...) kernels shardable over an expert mesh axis.
            return x + drop(MoEMLP(
                num_experts=self.num_experts, mlp_ratio=self.mlp_ratio,
                capacity_factor=self.moe_capacity_factor,
                top_k=self.moe_top_k, dtype=self.dtype,
                drop_tokens=not self.decode,
                expert_axis=self.moe_expert_axis,
            )(h))
        if self.mlp != "dense":
            raise ValueError(
                f"unknown mlp {self.mlp!r} "
                "(want dense|moe|held_experts|swiglu)"
            )
        d = x.shape[-1]
        if self.tp_axis is not None:
            # Megatron column-then-row MLP: the up-projection declares
            # only this shard's COLUMNS (nn.Dense with local features —
            # kernel (d, h/n), bias (h/n): the same tree paths as the
            # unsharded block, locally shaped), gelu stays elementwise
            # local, and the row-parallel exit psums before its bias.
            n = jax.lax.axis_size(self.tp_axis)
            h_f = self.mlp_ratio * d
            if h_f % n:
                raise ValueError(
                    f"mlp width {h_f} must be divisible by the "
                    f"{self.tp_axis!r} axis size {n}"
                )
            h = nn.Dense(h_f // n, dtype=self.dtype, name="Dense_0")(h)
            h = nn.gelu(h)
            return x + drop(_RowDense(
                d, self.tp_axis, self.dtype, name="Dense_1"
            )(h))
        h = nn.Dense(self.mlp_ratio * d, dtype=self.dtype)(h)
        h = nn.gelu(h)
        h = nn.Dense(d, dtype=self.dtype)(h)
        return x + drop(h)


class TransformerLM(nn.Module):
    """Small causal LM: token embedding + learned positions + N blocks.

    ``__call__(tokens, train=False) -> logits`` matches the framework's
    shared model interface (``models/__init__.py``), so it drops straight
    into :class:`~distributed_learning_tpu.training.trainer.GossipTrainer`.
    """

    vocab_size: int = 256
    num_layers: int = 2
    num_heads: int = 4
    head_dim: int = 16
    max_len: int = 1024
    mlp_ratio: int = 4
    attn_impl: str = "full"
    seq_axis: str = "seq"
    dtype: jnp.dtype = jnp.float32
    mlp: str = "dense"       # "dense" | "moe" (expert-parallel blocks)
    num_experts: int = 4
    moe_top_k: int = 1       # router choices per token (1=Switch, 2=GShard)
    # GShard capacity: slots per expert = ceil(tokens/E * factor).
    # NOTE training (drop_tokens=True) DROPS overflow while decode
    # (drop-free) runs every expert, so a capacity-constrained model is
    # a slightly different function at decode time; raise the factor
    # (e.g. 8.0 at toy sizes) when train/generate agreement matters
    # more than the capacity behavior.
    moe_capacity_factor: float = 1.25
    attn_window: int | None = None  # sliding-window attention (full/flash)
    dropout_rate: float = 0.0  # residual-branch dropout (train=True only)
    pos_emb: str = "learned"  # "learned" table | "rope" rotary Q/K
    num_kv_heads: int | None = None  # GQA: shared K/V heads (cache /Hkv)
    decode: bool = False     # KV-cache autoregressive mode (see generate).
                             # Direct decode users must keep prompt+steps
                             # <= max_len; past it the dynamic cache write
                             # clamps (generate() enforces the bound).
    # --- hybrid linear-attention / many-expert models (training path
    # only; decode, tp and the pipeline builders refuse them).  The
    # defaults leave the model above exactly as it was.
    hidden_size: int | None = None  # d_model, when not heads x head_dim
    norm: str = "layernorm"         # | "rmsnorm": zero-centred RMSNorm
    norm_eps: float = 1e-6
    head_bias: bool = True          # the output head's bias
    rope_base: float = 10000.0
    rope_fraction: float = 1.0      # share of the head that rotary turns
    attn_gate: bool = False         # gated attention with q/k RMSNorm
    # Every n-th layer is full attention, the others Gated DeltaNet
    # (models/gated_delta.py); None: attention everywhere.
    full_attention_interval: int | None = None
    linear_num_key_heads: int = 16
    linear_num_value_heads: int = 32
    linear_key_head_dim: int = 128
    linear_value_head_dim: int = 128
    linear_conv_kernel: int = 4
    linear_chunk: int = 64
    # mlp="held_experts" (models/moe.py::HeldExpertsMLP): num_experts is
    # the router's width, moe_top_k its choices per token, and this chip
    # holds experts [first_expert, first_expert + experts_held).
    experts_held: int | None = None
    first_expert: int = 0
    expert_width: int = 512
    shared_expert_width: int = 512
    remat_blocks: bool = False      # rematerialise each block in backward
    # --- latent attention and the sigmoid-routed expert stack (training
    # path only; the defaults build the models above as they were).
    # kv_lora_rank set: every attention layer is _LatentAttention, a
    # head's query/key qk_nope_head_dim + qk_rope_head_dim wide (rotary on
    # the second part, pos_emb="rope") and its value v_head_dim; head_dim
    # is then not read.
    kv_lora_rank: int | None = None
    qk_nope_head_dim: int = 128
    qk_rope_head_dim: int = 64
    v_head_dim: int = 128
    # The first num_dense_layers blocks take a SwiGLU of dense_width in
    # place of ``mlp`` (the published first_k_dense_replace).
    num_dense_layers: int = 0
    dense_width: int | None = None
    # HeldExpertsMLP's router (its docstring): score_func, route_scale,
    # bias_rate, shared_gate.
    router_score: str = "softmax"
    route_scale: float = 1.0
    route_bias_rate: float | None = None
    shared_expert_gate: bool = True

    @property
    def layer_types(self) -> tuple:
        """Each layer's token mixer, ``"full_attention"`` or
        ``"linear_attention"``."""
        n = self.full_attention_interval
        return tuple(
            "linear_attention" if n and (i + 1) % n else "full_attention"
            for i in range(self.num_layers)
        )

    @property
    def uniform(self) -> bool:
        """Every block is the plain attention + dense/MoE block that
        decode, tensor parallelism and the pipeline builders know."""
        return (
            self.full_attention_interval is None
            and self.mlp != "held_experts" and not self.attn_gate
            and self.norm == "layernorm" and self.hidden_size is None
            and self.rope_fraction == 1.0
            and self.kv_lora_rank is None and not self.num_dense_layers
        )

    def require_uniform(self, what: str) -> None:
        if not self.uniform:
            raise ValueError(
                f"{what} supports only uniform attention blocks; hybrid "
                "layer kinds (linear or latent attention, gated attention, "
                "held experts, rmsnorm) run on the training path alone"
            )

    @nn.compact
    def __call__(self, tokens, train: bool = False):
        if self.attn_window is not None and \
                self.attn_impl not in ("full", "flash"):
            # Checked here (not only in _Attention) so the error fires
            # before the sequence-parallel paths touch their mesh axis.
            raise ValueError(
                f"attn_window is only supported for full/flash attention, "
                f"not {self.attn_impl!r}"
            )
        if self.decode:
            self.require_uniform("decode")
        d_model = self.hidden_size or self.num_heads * self.head_dim
        T = tokens.shape[1]
        x = nn.Embed(self.vocab_size, d_model, dtype=self.dtype)(tokens)
        # Positions must be GLOBAL: under shard_map (ring/ulysses) each
        # shard sees only its local T, so offset by the shard index.
        # "full" and "flash" are single-device paths (no mesh axis bound).
        if self.decode:
            if self.attn_impl not in ("full", "flash"):
                raise ValueError("decode mode requires full/flash attention")
            pos_v = self.variable(
                "cache", "pos", lambda: jnp.zeros((), jnp.int32)
            )
            positions = pos_v.value + jnp.arange(T)
            pos_v.value = pos_v.value + T
        elif self.attn_impl in ("full", "flash"):
            if T > self.max_len:
                raise ValueError(
                    f"sequence length {T} exceeds max_len {self.max_len}; "
                    "out-of-range positions would silently clamp"
                )
            positions = jnp.arange(T)
        else:
            # Local T * axis size must fit max_len; checked per-shard
            # statically (axis size is known at trace time).
            n_shards = jax.lax.axis_size(self.seq_axis)
            if T * n_shards > self.max_len:
                raise ValueError(
                    f"global sequence length {T * n_shards} (local {T} x "
                    f"{n_shards} shards) exceeds max_len {self.max_len}"
                )
            positions = jax.lax.axis_index(self.seq_axis) * T + jnp.arange(T)
        if self.pos_emb == "rope":
            use_rope = True
        elif self.pos_emb == "learned":
            use_rope = False
            pos = nn.Embed(self.max_len, d_model, dtype=self.dtype)(positions)
            x = x + pos[None]
        else:
            raise ValueError(
                f"unknown pos_emb {self.pos_emb!r} (want learned|rope)"
            )
        # Every block takes the norm, rotary and gate choices: their
        # defaults build the plain block, parameter names and program as
        # they always were.  Only the naming by depth is the hybrids'.
        hybrid = dict(
            norm=self.norm, norm_eps=self.norm_eps,
            attn_gate=self.attn_gate, rope_base=self.rope_base,
            rotary_dim=int(self.head_dim * self.rope_fraction),
        )
        if self.mlp == "held_experts":
            hybrid["held_experts"] = dict(
                num_experts=self.num_experts, top_k=self.moe_top_k,
                experts_held=self.experts_held or self.num_experts,
                first_expert=self.first_expert,
                expert_width=self.expert_width,
                shared_width=self.shared_expert_width,
                score_func=self.router_score, route_scale=self.route_scale,
                bias_rate=self.route_bias_rate,
                shared_gate=self.shared_expert_gate,
            )
        if self.kv_lora_rank is not None:
            if not use_rope or self.full_attention_interval is not None \
                    or self.attn_gate or self.num_kv_heads is not None:
                raise ValueError(
                    "kv_lora_rank (latent attention) needs pos_emb='rope' "
                    "and takes no full_attention_interval, attn_gate or "
                    "num_kv_heads")
            hybrid["latent_attn"] = dict(
                nope_dim=self.qk_nope_head_dim, rope_dim=self.qk_rope_head_dim,
                v_dim=self.v_head_dim, kv_rank=self.kv_lora_rank,
            )
        if self.num_dense_layers:
            hybrid["dense_width"] = self.dense_width
        linear_attn = dict(
            num_key_heads=self.linear_num_key_heads,
            num_value_heads=self.linear_num_value_heads,
            key_head_dim=self.linear_key_head_dim,
            value_head_dim=self.linear_value_head_dim,
            conv_kernel=self.linear_conv_kernel, chunk=self.linear_chunk,
        )
        # (self, x, positions, train): train is a Python bool
        block_cls = (nn.remat(_Block, static_argnums=(3,))
                     if self.remat_blocks else _Block)
        for i, kind in enumerate(self.layer_types):
            x = block_cls(
                self.num_heads, self.head_dim, self.mlp_ratio,
                self.attn_impl, self.seq_axis, self.dtype,
                "swiglu" if i < self.num_dense_layers else self.mlp,
                self.num_experts, self.moe_top_k,
                self.attn_window, self.decode, self.max_len,
                use_rope, self.num_kv_heads, self.dropout_rate,
                moe_capacity_factor=self.moe_capacity_factor, **hybrid,
                linear_attn=(linear_attn if kind == "linear_attention"
                             else None),
                # hybrid layers are named by depth, remat or not
                name=None if self.uniform else f"layer_{i}",
            )(x, positions if use_rope else None, train)
        x = _norm(self.norm, self.norm_eps, self.dtype)(x)
        logits = nn.Dense(self.vocab_size, use_bias=self.head_bias,
                          dtype=self.dtype)(x)
        return logits.astype(jnp.float32)


def generate(
    model: TransformerLM,
    params,
    prompt: jax.Array,
    steps: int,
    *,
    key: jax.Array | None = None,
    temperature: float = 0.0,
    top_k: int | None = None,
    top_p: float | None = None,
) -> jax.Array:
    """Autoregressive generation with a KV cache: prefill the prompt in
    one pass, then one jitted single-token step per new token under
    ``lax.scan``.

    ``prompt`` is (B, Tp) int32; returns (B, steps) generated tokens.
    ``temperature=0`` is greedy argmax; otherwise tokens are sampled
    from ``softmax(logits / temperature)`` (``key`` required), with the
    candidate set optionally truncated FIRST by ``top_k`` (keep the k
    highest-logit tokens) and/or ``top_p`` (nucleus sampling,
    arXiv:1904.09751: the smallest set whose cumulative probability
    reaches p — the top token always survives).  The decode-mode model
    reuses the TRAINING parameters unchanged — the cache is a flax
    ``cache`` collection threaded through the scan, so the whole loop
    compiles to one program with static shapes.
    """
    validate_sampling(model, prompt.shape[1], steps, key, temperature,
                      top_k, top_p)
    run = _generate_runner(model.clone(decode=True), steps,
                           float(temperature),
                           None if top_k is None else int(top_k),
                           None if top_p is None else float(top_p))
    return run(params, prompt, key)


def validate_sampling(model: "TransformerLM", prompt_len: int, steps: int,
                      key, temperature: float, top_k: int | None,
                      top_p: float | None) -> None:
    """The :func:`generate` argument contract, shared with the
    tensor-parallel decode path."""
    if prompt_len + steps > model.max_len:
        raise ValueError(
            f"prompt ({prompt_len}) + steps ({steps}) exceeds max_len "
            f"{model.max_len}"
        )
    if temperature > 0.0 and key is None:
        raise ValueError("sampling (temperature > 0) requires a PRNG key")
    if (top_k is not None or top_p is not None) and temperature <= 0.0:
        raise ValueError(
            "top_k/top_p shape the SAMPLING distribution; greedy decoding "
            "(temperature=0) ignores them — pass temperature > 0"
        )
    if top_k is not None and not 1 <= top_k <= model.vocab_size:
        raise ValueError(
            f"top_k must be in [1, vocab_size={model.vocab_size}], "
            f"got {top_k}"
        )
    if top_p is not None and not 0.0 < top_p <= 1.0:
        raise ValueError(f"top_p must be in (0, 1], got {top_p}")


def sample_fn(temperature: float, top_k: int | None = None,
              top_p: float | None = None):
    """Build ``pick(logits, key, dtype) -> token`` for one sampling
    configuration — greedy argmax at temperature 0, else temperature/
    top-k/nucleus sampling.  Shared by :func:`generate` and the
    tensor-parallel decode path (``training/tp.py::make_tp_generate``)
    so the two cannot drift."""

    def pick(logits, k, dtype):
        if temperature <= 0.0:
            return jnp.argmax(logits, axis=-1).astype(dtype)
        scaled = logits / temperature
        if top_k is not None:
            kth = jax.lax.top_k(scaled, top_k)[0][..., -1:]
            scaled = jnp.where(scaled < kth, -jnp.inf, scaled)
        if top_p is not None:
            # Nucleus cutoff on the (possibly top_k-truncated) logits:
            # rank tokens by probability, keep every token whose
            # cumulative mass BEFORE it is < p (so the top token always
            # survives), and mask the rest via the kept-set's smallest
            # logit — all static shapes.
            srt = jnp.sort(scaled, axis=-1)[..., ::-1]
            probs = jax.nn.softmax(srt, axis=-1)
            cum = jnp.cumsum(probs, axis=-1)
            # (cum - probs) is the EXCLUSIVE prefix sum: < p keeps every
            # token whose predecessors haven't reached the nucleus yet,
            # so n_keep >= 1 always.
            n_keep = jnp.sum((cum - probs) < top_p, axis=-1, keepdims=True)
            thresh = jnp.take_along_axis(srt, n_keep - 1, axis=-1)
            scaled = jnp.where(scaled < thresh, -jnp.inf, scaled)
        return jax.random.categorical(k, scaled, axis=-1).astype(dtype)

    return pick


@functools.lru_cache(maxsize=64)
def _generate_runner(dec: TransformerLM, steps: int, temperature: float,
                     top_k: int | None = None, top_p: float | None = None):
    """The jitted prefill+scan program for one (model, steps,
    temperature, top_k, top_p) configuration.  Cached by the module's
    (frozen, hashable) dataclass identity so repeated :func:`generate`
    calls with the same settings reuse the compile instead of
    re-tracing — jit caches by function object, and a closure built
    inside ``generate`` would be fresh every call."""

    pick = sample_fn(temperature, top_k, top_p)

    @jax.jit
    def _run(params, prompt, key):
        logits, state = dec.apply(
            {"params": params}, prompt, mutable=["cache"]
        )
        key0 = key if key is not None else jax.random.key(0)
        k_first, k_scan = jax.random.split(key0)
        tok = pick(logits[:, -1], k_first, prompt.dtype)

        def step(carry, k_t):
            cache, tok = carry
            logits, st = dec.apply(
                {"params": params, "cache": cache["cache"]},
                tok[:, None], mutable=["cache"],
            )
            nxt = pick(logits[:, -1], k_t, tok.dtype)
            return (st, nxt), tok

        keys = jax.random.split(k_scan, steps)
        # Each iteration collects the token ENTERING it, so toks is
        # exactly [t_1 .. t_steps]; the final carry (t_steps+1) is
        # unneeded lookahead.
        _, toks = jax.lax.scan(step, (state, tok), keys)
        return toks.T

    return _run
