"""The token mixers of SambaY (arXiv:2507.06607), the decoder-hybrid-decoder
of Phi-4-mini-flash-reasoning, for ``TransformerLM``'s per-layer
description (``layers``).

No counterpart exists in the reference (SURVEY.md §2 C11-C13 are
tabular/image nets).  :class:`SambaYBlock` is one block of the stack,
built by ``models/transformer.py::TransformerLM`` from a layer's
description; it places one of four mixers, each a flax module:

* :class:`Mamba` — Mamba-1 (arXiv:2312.00752, §3): ``[x | z] = h W_in``,
  a causal depthwise convolution with bias and SiLU on ``x``, ``[delta |
  B | C] = x W_x``, ``dt = softplus(delta W_dt + b_dt)``, the selective
  scan (``ops/selective_scan.py``) with ``A = -exp(A_log)``, ``y = scan +
  D x``, and ``(y * SiLU(z)) W_out``.  The self-decoder's last Mamba layer
  also hands on ``y`` (before the gate): the memory the GMUs read.
* :class:`GatedMemoryUnit` — SambaY's GMU: ``(SiLU(h W_in) * m) W_out``
  over another layer's memory ``m``.
* :class:`DiffAttention` — differential attention (arXiv:2410.05258,
  eq. 1-3): a head pair's two softmax maps over one value head, the
  second weighted by a learned ``lambda``, each pair's output RMS-normed
  and scaled by ``1 - lambda_init``.  Query heads pair up as ``(j, j +
  H/2)`` and K/V heads as ``(g, g + Hkv/2)`` (the DIFF Transformer pairs
  neighbours: with seeded weights a permutation of the columns), pair
  ``j`` reads K/V group ``j // 2`` at the published widths; no positional
  encoding.  Both maps of all pairs go through ONE flash call of
  ``2 * pairs`` query heads, query/key 64 against value 128.  The
  cross-decoder's layers (``cross=True``) project queries alone and
  attend over the K/V another layer made and handed on.

The scopes (``mamba_proj``, ``mamba_conv``, ``mamba_scan``,
``mamba_out``, ``gmu``, ``dattn_proj``, ``dattn_norm``) name the parts in
a profile (docs/observability.md); the Mamba layers also count
``ssm.state_dead`` (the counters collection, ``obs/carry.py``).
"""

from __future__ import annotations

import math
from typing import Any

import flax.linen as nn
import jax
import jax.numpy as jnp

from distributed_learning_tpu.models.gated_delta import causal_depthwise_conv
from distributed_learning_tpu.ops.selective_scan import selective_scan

__all__ = ["Mamba", "GatedMemoryUnit", "DiffAttention", "SambaYBlock",
           "lambda_init", "DEAD_DECAY"]

#: a (channel, state) pair whose decay over the mean step is below this
#: carries nothing from one token to the next (``ssm.state_dead``)
DEAD_DECAY = 1e-3


def lambda_init(depth: int) -> float:
    """The DIFF Transformer's ``lambda_init`` at 0-based layer ``depth``
    (arXiv:2410.05258, §2.1)."""
    return 0.8 - 0.6 * math.exp(-0.3 * depth)


def _a_log_init(key, shape, dtype=jnp.float32):
    # Mamba's S4D-real init: A[c, n] = n + 1 for every channel
    del key
    return jnp.log(jnp.broadcast_to(
        jnp.arange(1, shape[1] + 1, dtype=dtype), shape))


def _dt_proj_init(key, shape, dtype=jnp.float32):
    # U(-r^-1/2, r^-1/2) over the rank r: Mamba's dt_init "random" at
    # dt_scale 1
    bound = shape[0] ** -0.5
    return jax.random.uniform(key, shape, dtype, -bound, bound)


def _dt_bias_init(key, shape, dtype=jnp.float32):
    # softplus(b_dt) log-uniform on (0.001, 0.1), floored at 1e-4, stored
    # through the inverse softplus (Mamba's dt_init)
    dt = jnp.exp(jax.random.uniform(
        key, shape, dtype, math.log(1e-3), math.log(1e-1)))
    dt = jnp.maximum(dt, 1e-4)
    return dt + jnp.log(-jnp.expm1(-dt))


class Mamba(nn.Module):
    """One Mamba-1 mixer: (B, T, d) -> ((B, T, d), memory (B, T, d_inner)).
    Parameters f32; ``dtype`` is the compute dtype of the products, the
    convolution and the scan's operands (its state is f32).  The sizes
    default to Mamba's own, which SambaY keeps: ``d_inner = expand * d``,
    ``dt_rank = ceil(d / 16)``."""

    d_state: int = 16
    d_conv: int = 4
    expand: int = 2
    dt_rank: int | None = None
    dtype: jnp.dtype = jnp.float32

    @nn.compact
    def __call__(self, h):
        d = h.shape[-1]
        Di, N = self.expand * d, self.d_state
        R = self.dt_rank or -(-d // 16)
        dense = lambda f, name, **kw: nn.Dense(
            f, use_bias=False, dtype=self.dtype, name=name, **kw)
        with jax.named_scope("mamba_proj"):
            xz = dense(2 * Di, "in_proj")(h)
        with jax.named_scope("mamba_conv"):
            w = self.param(
                "conv", nn.initializers.lecun_normal(in_axis=0, out_axis=1),
                (self.d_conv, Di), jnp.float32)
            b = self.param("conv_bias", nn.initializers.zeros, (Di,),
                           jnp.float32)
            x = nn.silu(causal_depthwise_conv(xz[..., :Di], w.astype(self.dtype))
                        + b.astype(self.dtype))
        with jax.named_scope("mamba_proj"):
            dbc = dense(R + 2 * N, "x_proj")(x)
            delta = dense(Di, "dt_proj", kernel_init=_dt_proj_init)(
                dbc[..., :R])
        A_log = self.param("A_log", _a_log_init, (Di, N), jnp.float32)
        D = self.param("D", nn.initializers.ones, (Di,), jnp.float32)
        dt_bias = self.param("dt_bias", _dt_bias_init, (Di,), jnp.float32)
        with jax.named_scope("mamba_scan"):
            A = -jnp.exp(A_log)
            B, C = dbc[..., R:R + N], dbc[..., R + N:]
            y = selective_scan(x, delta, A, B, C, bias=dt_bias)
            # read back only by a caller that makes "intermediates" mutable
            # (the chip benchmark's check of the scan on its own operands)
            self.sow("intermediates", "scan", (x, delta, B, C, y))
            y = (y + x * D).astype(self.dtype)
        with jax.named_scope("mamba_count"):
            dt_mean = jnp.mean(jax.nn.softplus(
                jax.lax.stop_gradient(delta).astype(jnp.float32)
                + dt_bias), axis=1)                                 # (B, Di)
            dead = jnp.exp(dt_mean[..., None] * jax.lax.stop_gradient(A))
            self.sow("counters", "ssm.state_dead",
                     jnp.sum(dead < DEAD_DECAY).astype(jnp.int32),
                     reduce_fn=lambda a, b: b)
        memory = y
        with jax.named_scope("mamba_out"):
            out = dense(h.shape[-1], "out_proj")(y * nn.silu(xz[..., Di:]))
        return out, memory


class GatedMemoryUnit(nn.Module):
    """SambaY's GMU: ``(SiLU(h W_in) * m) W_out`` over the memory ``m``
    another layer handed on, (B, T, d_inner); the gate is as wide."""

    dtype: jnp.dtype = jnp.float32

    @nn.compact
    def __call__(self, h, memory):
        dense = lambda f, name: nn.Dense(
            f, use_bias=False, dtype=self.dtype, name=name)
        with jax.named_scope("gmu"):
            gate = nn.silu(dense(memory.shape[-1], "in_proj")(h))
            return dense(h.shape[-1], "out_proj")(
                gate * memory.astype(self.dtype))


def _rms(o, w, eps):
    of = o.astype(jnp.float32)
    return of * jax.lax.rsqrt(jnp.mean(of * of, -1, keepdims=True) + eps) * w


class DiffAttention(nn.Module):
    """Differential attention over ``num_heads`` query heads of
    ``head_dim`` (``num_heads / 2`` pairs) and ``num_kv_heads`` key heads
    of ``head_dim`` (their pairs' values ``num_kv_heads / 2`` heads of
    ``2 * head_dim``).  ``__call__(h, kv=None) -> (out, (k, v))``: with
    ``cross`` the layer has no K/V projection and attends over ``kv``;
    otherwise it makes its own and returns them for whoever reads them."""

    num_heads: int
    num_kv_heads: int
    head_dim: int
    depth: int                  # the published 0-based layer index
    window: int | None = None
    cross: bool = False
    attn_impl: str = "flash"
    eps: float = 1e-5
    dtype: jnp.dtype = jnp.float32

    @nn.compact
    def __call__(self, h, kv=None):
        B, T, d = h.shape
        P, G, Dh = self.num_heads // 2, self.num_kv_heads // 2, self.head_dim
        proj = lambda features, name: nn.DenseGeneral(
            features=features, use_bias=False, dtype=self.dtype, name=name)
        with jax.named_scope("dattn_proj"):
            q = proj((2, P, Dh), "q_proj")(h).reshape(B, T, 2 * P, Dh)
            if self.cross:
                k, v = kv
            else:
                k = proj((2, G, Dh), "k_proj")(h)        # (B, T, 2, G, Dh)
                v = proj((G, 2 * Dh), "v_proj")(h)       # (B, T, G, 2 Dh)
            # query head (side, j) reads key head (side, j // 2) and value
            # head j // 2: the key heads repeat, the values twice over
            keys = jnp.repeat(k.reshape(B, T, 2 * G, Dh), P // G, axis=2)
            vals = jnp.repeat(v, P // G, axis=2)
            vals = jnp.concatenate([vals, vals], axis=2)
        scale = float(Dh ** -0.5)
        if self.attn_impl == "flash":
            from distributed_learning_tpu.ops.flash_attention import (
                flash_attention,
            )

            o = flash_attention(q, keys, vals, causal=True, sm_scale=scale,
                                window=self.window)
        elif self.attn_impl == "full":
            from distributed_learning_tpu.ops.ring_attention import (
                attention_reference,
            )

            o = attention_reference(q, keys, vals, causal=True,
                                    sm_scale=scale, window=self.window)
        else:
            raise ValueError(f"differential attention runs attn_impl "
                             f"full|flash, not {self.attn_impl!r}")
        lam0 = lambda_init(self.depth)
        lam = lambda name: self.param(
            name, nn.initializers.normal(0.1), (Dh,), jnp.float32)
        with jax.named_scope("dattn_norm"):
            lq1, lk1, lq2, lk2 = (lam(n) for n in (
                "lambda_q1", "lambda_k1", "lambda_q2", "lambda_k2"))
            lmb = (jnp.exp(jnp.sum(lq1 * lk1)) - jnp.exp(jnp.sum(lq2 * lk2))
                   + lam0)
            o = o[:, :, :P].astype(jnp.float32) - lmb * o[:, :, P:].astype(
                jnp.float32)
            w = self.param("subln", nn.initializers.ones, (2 * Dh,),
                           jnp.float32)
            o = (_rms(o, w, self.eps) * (1.0 - lam0)).astype(self.dtype)
        with jax.named_scope("dattn_proj"):
            out = nn.DenseGeneral(features=d, axis=(-2, -1), use_bias=False,
                                  dtype=self.dtype, name="o_proj")(o)
        return out, (k, v)


class SambaYBlock(nn.Module):
    """One block of a described stack (``TransformerLM.layers``): ``x +=
    mixer(LN(x))``, ``x += W_down(SiLU(h W_gate) * h W_up)`` with ``h =
    LN(x)``, ``dense_width`` wide, no biases.  ``layer`` is the block's
    entry of the description.  ``__call__(x, shared) -> (x, shared)``: ``shared`` carries what one layer hands on to later ones,
    ``"memory"`` (a Mamba layer's ``y``) and ``"kv"`` (a self-attention
    layer's K and V), as a block input and output, so that under
    ``remat_blocks`` it crosses the boundary as a value.  No dropout: the
    published config has none."""

    layer: Any
    num_heads: int
    num_kv_heads: int
    head_dim: int
    dense_width: int
    attn_impl: str = "flash"
    norm_eps: float = 1e-5
    dtype: jnp.dtype = jnp.float32

    @nn.compact
    def __call__(self, x, shared=None):
        spec, shared = self.layer, dict(shared or {})
        kind = spec["mixer"]
        h = nn.LayerNorm(epsilon=self.norm_eps, dtype=self.dtype)(x)
        if kind == "mamba":
            out, memory = Mamba(dtype=self.dtype, name="mamba")(h)
            if spec.get("memory") == "write":
                shared["memory"] = memory
        elif kind == "gmu":
            out = GatedMemoryUnit(self.dtype, name="gmu")(h, shared["memory"])
        elif kind in ("attention", "cross_attention"):
            cross = kind == "cross_attention"
            out, kv = DiffAttention(
                self.num_heads, self.num_kv_heads, self.head_dim,
                spec["depth"], spec.get("window"), cross, self.attn_impl,
                self.norm_eps, self.dtype, name=kind,
            )(h, shared["kv"] if cross else None)
            if spec.get("kv") == "write":
                shared["kv"] = kv
        else:
            raise ValueError(f"unknown mixer {kind!r} (want mamba|gmu|"
                             "attention|cross_attention)")
        x = x + out
        h = nn.LayerNorm(epsilon=self.norm_eps, dtype=self.dtype)(x)
        dense = lambda f, name: nn.Dense(
            f, use_bias=False, dtype=self.dtype, name=name)
        with jax.named_scope("mlp_dense"):
            out = dense(x.shape[-1], "down_proj")(
                nn.silu(dense(self.dense_width, "gate_proj")(h))
                * dense(self.dense_width, "up_proj")(h))
        return x + out, shared
