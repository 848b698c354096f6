"""The gated delta rule in its chunked (WY) form, differentiable.

No counterpart exists in the reference (its models are tabular/image
nets, SURVEY.md §2 C11-C13).  The rule is the token mixer of Gated
DeltaNet (arXiv:2412.06464): per head a matrix state ``S`` (Dk x Dv,
kept in f32) that every token decays and then corrects by a rank-one
delta update,

    S <- exp(g_t) S
    S <- S + k_t (x) beta_t (v_t - S^T k_t)
    o_t = S^T q_t

Token by token that is ``T`` dependent steps of tiny matrices
(:func:`gated_delta_recurrence`, the plain form the tests and the
reference use).  The chunked form (arXiv:2412.06464 §3.3, the WY
representation of arXiv:2406.06484 with the decay folded in) does the
work of ``chunk`` tokens as matrix products: inside a chunk the updates
``u_i = beta_i (v_i - sum_{j<i} d_ij (k_i . k_j) u_j)`` are one unit
lower-triangular solve, and only ``T / chunk`` steps remain sequential,
each a handful of (chunk x D) x (D x D) products against the carried
state.  Everything is plain XLA (a ``lax.scan`` over chunks under
autodiff): no kernel, so a later kernel has a baseline to beat.
"""

from __future__ import annotations

import jax
import jax.numpy as jnp

__all__ = ["gated_delta_rule", "gated_delta_recurrence"]


def gated_delta_rule(q, k, v, g, beta, chunk: int = 64, precision=None):
    """Chunked gated delta rule (arXiv:2412.06464 §3.3).

    ``q``, ``k``: (B, T, H, Dk), already normalised and scaled by the
    caller; ``v``: (B, T, H, Dv); ``g`` (log decay, <= 0) and ``beta``
    (write strength): (B, T, H).  Returns ``o``: (B, T, H, Dv) in f32.
    The state starts at zero and lives in f32 whatever the inputs' dtype;
    ``precision`` is that of the rule's matrix products (``None``: the
    backend's default, on a TPU one bf16 pass with f32 accumulation).
    ``T`` need not divide by ``chunk``: the tail is padded with tokens
    that neither decay nor write (``g = 0``, ``beta = 0``, ``k = 0``).
    """
    B, T, H, Dk = q.shape
    Dv = v.shape[-1]
    C = int(chunk)
    pad = -T % C
    f32 = jnp.float32

    def chunks(x):  # (B, T, H, ...) -> (N, B, H, C, ...), f32
        x = x.astype(f32)
        if pad:
            x = jnp.pad(x, [(0, 0), (0, pad)] + [(0, 0)] * (x.ndim - 2))
        x = x.reshape((B, (T + pad) // C, C) + x.shape[2:])
        return jnp.moveaxis(x, 3, 2).swapaxes(0, 1)

    q, k, v, g, beta = (chunks(x) for x in (q, k, v, g, beta))
    gc = jnp.cumsum(g, axis=-1)  # decay from the chunk's start, log
    lower = jnp.tril(jnp.ones((C, C), bool))
    # d_ij = exp(gc_i - gc_j) for i >= j; the masked exponent keeps the
    # upper triangle (a positive, possibly huge exponent) out of exp and
    # so out of the gradient.
    diff = gc[..., :, None] - gc[..., None, :]
    decay = jnp.where(lower, jnp.exp(jnp.where(lower, diff, 0.0)), 0.0)
    k_beta = k * beta[..., None]
    kk = jnp.einsum("...id,...jd->...ij", k_beta, k, precision=precision)
    strict = jnp.tril(kk * decay, -1)
    # (I + A) [U | W] = [beta v | beta k exp(gc)]: every token's update
    # against the chunk's earlier ones, and what it reads of the state.
    rhs = jnp.concatenate(
        [v * beta[..., None], k_beta * jnp.exp(gc)[..., None]], axis=-1
    )
    sol = jax.scipy.linalg.solve_triangular(
        strict, rhs, lower=True, unit_diagonal=True
    )
    u, w = sol[..., :Dv], sol[..., Dv:]
    qk = jnp.einsum("...id,...jd->...ij", q, k, precision=precision) * decay
    q_in = q * jnp.exp(gc)[..., None]  # the query as the state sees it
    g_end = gc[..., -1]
    k_out = k * jnp.exp(g_end[..., None] - gc)[..., None]

    def step(S, xs):
        u_i, w_i, qk_i, q_i, k_i, end_i = xs
        new = u_i - jnp.einsum("...ck,...kv->...cv", w_i, S,
                               precision=precision)
        o = jnp.einsum("...ck,...kv->...cv", q_i, S, precision=precision)
        o = o + jnp.einsum("...ij,...jv->...iv", qk_i, new,
                           precision=precision)
        S = S * jnp.exp(end_i)[..., None, None] + jnp.einsum(
            "...ck,...cv->...kv", k_i, new, precision=precision
        )
        return S, o

    S0 = jnp.zeros((B, H, Dk, Dv), f32)
    _, o = jax.lax.scan(step, S0, (u, w, qk, q_in, k_out, g_end))
    o = jnp.moveaxis(o.swapaxes(0, 1), 2, 3).reshape(B, T + pad, H, Dv)
    return o[:, :T]


def gated_delta_recurrence(q, k, v, g, beta):
    """The rule as written (arXiv:2412.06464 eq. 10), one token a step:
    the oracle for :func:`gated_delta_rule`.  Same arguments and result;
    f32 throughout at the highest matrix precision."""
    f32 = jnp.float32
    q, k, v, g, beta = (
        jnp.moveaxis(x.astype(f32), 1, 0) for x in (q, k, v, g, beta)
    )
    B, H, Dk = q.shape[1:]

    def step(S, xs):
        q_t, k_t, v_t, g_t, b_t = xs
        S = S * jnp.exp(g_t)[..., None, None]
        read = jnp.einsum("bhkv,bhk->bhv", S, k_t, precision="highest")
        delta = (v_t - read) * b_t[..., None]
        S = S + k_t[..., :, None] * delta[..., None, :]
        return S, jnp.einsum("bhkv,bhk->bhv", S, q_t, precision="highest")

    S0 = jnp.zeros((B, H, Dk, v.shape[-1]), f32)
    _, o = jax.lax.scan(step, S0, (q, k, v, g, beta))
    return jnp.moveaxis(o, 0, 1)
