"""Gated DeltaNet token mixer (arXiv:2412.06464), the linear-attention
layer of hybrid models that interleave it with softmax attention.

No counterpart exists in the reference (SURVEY.md §2 C11-C13 are
tabular/image nets); ``models/transformer.py::_Block`` places it where
``layer_types`` says ``"linear_attention"``.  Per layer: one projection
to q, k (``num_key_heads`` x ``key_head_dim``), v and the output gate z
(``num_value_heads`` x ``value_head_dim``), one to the per-head write
strength ``b`` and decay input ``a``; a causal depthwise convolution with
SiLU over the concatenated q, k, v; q, k L2-normalised, q scaled by
``key_head_dim ** -0.5``; the gated delta rule (``ops/gated_delta.py``;
value head ``h`` reads key head ``h // (num_value_heads /
num_key_heads)``) with ``g = -exp(A_log) softplus(a + dt_bias)`` and
``beta = sigmoid(b)``; then RMSNorm of each head's output times a learned
weight times ``silu(z)``, and the output projection.

The ``jax.named_scope`` blocks (``gdn_proj``, ``gdn_conv``, ``gdn_rule``,
``gdn_out``) name the layer's parts in a profile
(docs/observability.md); they are metadata, not computation.
"""

from __future__ import annotations

import flax.linen as nn
import jax
import jax.numpy as jnp

from distributed_learning_tpu.ops.gated_delta import gated_delta_rule

__all__ = ["GatedDeltaNet", "causal_depthwise_conv"]


def _a_log_init(key, shape, dtype=jnp.float32):
    # A ~ U(0, 16), stored as its log (the published module's init; the
    # lower end is kept off 0 so that the log stays finite).
    return jnp.log(jax.random.uniform(key, shape, dtype, 1e-3, 16.0))


def _dt_bias_init(key, shape, dtype=jnp.float32):
    # softplus(dt_bias) ~ log U(1e-3, 1e-1), stored through the inverse
    # softplus: the Gated DeltaNet reference implementation's init (after
    # Mamba2's).  With A ~ U(0, 16) a token's decay exp(-A dt) then
    # remembers tens to thousands of tokens, head by head; a bias of one
    # (what the published module builds before its checkpoint is loaded)
    # forgets within a token or two and leaves the state nothing to do.
    dt = jnp.exp(jax.random.uniform(
        key, shape, dtype, jnp.log(1e-3), jnp.log(1e-1)))
    return dt + jnp.log(-jnp.expm1(-dt))


def causal_depthwise_conv(x, w):
    """``y[t, c] = sum_j w[j, c] x[t - (K-1) + j, c]`` with zeros before
    the sequence's start (arXiv:2412.06464 §3.4's short convolution).
    ``x``: (B, T, C); ``w``: (K, C).  K shifted multiply-adds: K is 4."""
    K = w.shape[0]
    T = x.shape[1]
    xp = jnp.pad(x, ((0, 0), (K - 1, 0), (0, 0)))
    return sum(xp[:, j:j + T] * w[j] for j in range(K))


class GatedDeltaNet(nn.Module):
    """One Gated DeltaNet mixer (arXiv:2412.06464 §3.4): (B, T, d) ->
    (B, T, d).  Parameters are f32; ``dtype`` is the compute dtype of the
    projections and the convolution, the rule's state stays f32."""

    num_key_heads: int
    num_value_heads: int
    key_head_dim: int
    value_head_dim: int
    conv_kernel: int = 4
    chunk: int = 64
    eps: float = 1e-6
    dtype: jnp.dtype = jnp.float32

    @nn.compact
    def __call__(self, x):
        B, T, d = x.shape
        Hk, Hv = self.num_key_heads, self.num_value_heads
        Dk, Dv = self.key_head_dim, self.value_head_dim
        if Hv % Hk:
            raise ValueError(f"value heads {Hv} must divide by key heads {Hk}")
        key_dim, value_dim = Hk * Dk, Hv * Dv
        with jax.named_scope("gdn_proj"):
            # columns: [q | k | v | z], each head-major
            qkvz = nn.Dense(2 * key_dim + 2 * value_dim, use_bias=False,
                            dtype=self.dtype, name="in_proj_qkvz")(x)
            ba = nn.Dense(2 * Hv, use_bias=False, dtype=self.dtype,
                          name="in_proj_ba")(x)
        with jax.named_scope("gdn_conv"):
            conv_w = self.param(
                "conv", nn.initializers.lecun_normal(in_axis=0, out_axis=1),
                (self.conv_kernel, 2 * key_dim + value_dim), jnp.float32,
            )
            qkv = nn.silu(causal_depthwise_conv(
                qkvz[..., : 2 * key_dim + value_dim],
                conv_w.astype(self.dtype),
            ))
        z = qkvz[..., 2 * key_dim + value_dim:].reshape(B, T, Hv, Dv)
        A_log = self.param("A_log", _a_log_init, (Hv,), jnp.float32)
        dt_bias = self.param("dt_bias", _dt_bias_init, (Hv,), jnp.float32)
        with jax.named_scope("gdn_rule"):
            f32 = jnp.float32
            q = qkv[..., :key_dim].reshape(B, T, Hk, Dk).astype(f32)
            k = qkv[..., key_dim:2 * key_dim].reshape(B, T, Hk, Dk).astype(f32)
            v = qkv[..., 2 * key_dim:].reshape(B, T, Hv, Dv)
            q = q * jax.lax.rsqrt(jnp.sum(q * q, -1, keepdims=True) + self.eps)
            k = k * jax.lax.rsqrt(jnp.sum(k * k, -1, keepdims=True) + self.eps)
            q = q * Dk ** -0.5
            beta = jax.nn.sigmoid(ba[..., :Hv].astype(f32))
            g = -jnp.exp(A_log) * jax.nn.softplus(
                ba[..., Hv:].astype(f32) + dt_bias
            )
            o = gated_delta_rule(q, k, v, g, beta, chunk=self.chunk)
        with jax.named_scope("gdn_out"):
            norm_w = self.param("norm", nn.initializers.ones, (Dv,),
                                jnp.float32)
            o = o * jax.lax.rsqrt(
                jnp.mean(o * o, -1, keepdims=True) + self.eps
            )
            o = (o * norm_w) * nn.silu(z.astype(jnp.float32))
            return nn.Dense(d, use_bias=False, dtype=self.dtype,
                            name="out_proj")(
                o.astype(self.dtype).reshape(B, T, value_dim)
            )
