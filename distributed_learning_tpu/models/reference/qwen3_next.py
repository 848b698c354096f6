"""Plain reference of the Qwen3-Next block stack: forward and loss
(gradients: ``jax.grad`` of it).

The oracle for ``TransformerLM`` configured as a Gated-DeltaNet hybrid
(``full_attention_interval``, ``attn_gate``, ``norm="rmsnorm"``,
``mlp="held_experts"``).  No counterpart exists in the reference repo
(SURVEY.md §2 C11-C13 are tabular/image nets); the equations follow
``modeling_qwen3_next.py`` of the published model
(https://huggingface.co/Qwen/Qwen3-Next-80B-A3B-Instruct, config.json)
and the Gated DeltaNet paper (arXiv:2412.06464).  Everything here is
``jax.numpy`` in f32 at ``jax.default_matmul_precision("highest")``: no
kernel, no chunking (the delta rule is the recurrence, one token a
``lax.scan`` step), no vmap over agents (one sequence, ``(T,)`` token
ids), dense attention under an explicit mask, and the expert layer as a
loop over the held experts with a mask.

It reads the program's parameter tree (the names are the data's format)
and the model's keyword arguments as a plain dict.  ``experts_held`` /
``first_expert`` and the vocabulary slice are taken exactly as the
program takes them: what the absent experts would have added is left
out, in the program and in the reference alike.  ``experts_held=None``
holds all of them (the uncut layer).

Departures from the published module, each listed in the benchmark
configuration's ``assumed``:

* no multi-token-prediction module (the catalog's config has no key for
  one);
* the columns of ``in_proj_qkvz`` are ordered [q | k | v | z], each
  head-major, and those of ``q_proj`` [query | gate]; the published
  module interleaves them per key head: with seeded weights a
  permutation of columns;
* the router's matrix product and softmax are f32 (the published module
  runs the product in the model's dtype);
* initialisers are flax's (``A_log`` as published, log U(0, 16)).

``blocks`` changes memory, not mathematics: the recurrence's backward
then keeps one state per ``blocks`` tokens and recomputes the rest, and
attention runs one head at a time.
"""

from __future__ import annotations

import jax
import jax.numpy as jnp

__all__ = ["forward", "loss", "token_loss", "expert_layer",
           "route", "delta_recurrence"]


def rms_norm(x, w, eps):
    """Zero-centred RMSNorm: ``x rsqrt(mean x^2 + eps) (1 + w)``."""
    return x * jax.lax.rsqrt(jnp.mean(x * x, -1, keepdims=True) + eps) * (1.0 + w)


def partial_rope(x, base, rotary_dim):
    """Rotary embedding on the first ``rotary_dim`` dimensions of each
    head, half-split pairs (j, j + rotary_dim / 2); ``x``: (T, H, D)."""
    T = x.shape[0]
    half = rotary_dim // 2
    inv = base ** (-jnp.arange(half, dtype=jnp.float32) / half)
    ang = jnp.arange(T, dtype=jnp.float32)[:, None] * inv[None, :]
    cos, sin = jnp.cos(ang)[:, None, :], jnp.sin(ang)[:, None, :]
    x1, x2, rest = x[..., :half], x[..., half:rotary_dim], x[..., rotary_dim:]
    return jnp.concatenate(
        [x1 * cos - x2 * sin, x1 * sin + x2 * cos, rest], axis=-1
    )


def gated_attention(p, x, cfg, blocks=None):
    """Softmax attention with a query-side sigmoid gate (T, d) -> (T, d)."""
    H, Dh = cfg["num_heads"], cfg["head_dim"]
    Hkv = cfg.get("num_kv_heads") or H
    eps = cfg.get("norm_eps", 1e-6)
    T = x.shape[0]
    wq, wkv = p["q_proj"]["kernel"], p["kv_proj"]["kernel"]
    q = jnp.einsum("td,dhe->the", x, wq[:, 0])
    gate = jnp.einsum("td,dhe->the", x, wq[:, 1])
    k = jnp.einsum("td,dhe->the", x, wkv[:, 0])
    v = jnp.einsum("td,dhe->the", x, wkv[:, 1])
    q = rms_norm(q, p["q_norm"]["scale"], eps)
    k = rms_norm(k, p["k_norm"]["scale"], eps)
    rd = int(Dh * cfg.get("rope_fraction", 1.0))
    base = cfg.get("rope_base", 10000.0)
    q, k = partial_rope(q, base, rd), partial_rope(k, base, rd)
    k, v = jnp.repeat(k, H // Hkv, axis=1), jnp.repeat(v, H // Hkv, axis=1)
    mask = jnp.arange(T)[:, None] >= jnp.arange(T)[None, :]

    def one_head(qkv):
        qh, kh, vh = qkv  # (T, Dh)
        s = jnp.where(mask, (qh @ kh.T) * Dh ** -0.5, -jnp.inf)
        return jax.nn.softmax(s, axis=-1) @ vh

    heads = tuple(jnp.moveaxis(a, 1, 0) for a in (q, k, v))
    if blocks:
        o = jax.lax.map(jax.checkpoint(one_head), heads)
    else:
        o = jax.vmap(one_head)(heads)
    o = jnp.moveaxis(o, 0, 1) * jax.nn.sigmoid(gate)
    return jnp.einsum("the,hed->td", o, p["DenseGeneral_1"]["kernel"])


def delta_recurrence(q, k, v, g, beta, blocks=None):
    """``S <- exp(g_t) S; S <- S + k_t (x) beta_t (v_t - S^T k_t);
    o_t = S^T q_t`` over (T, H, D) inputs, ``S`` (H, Dk, Dv) from zero."""

    def step(S, xs):
        q_t, k_t, v_t, g_t, b_t = xs
        S = S * jnp.exp(g_t)[:, None, None]
        delta = (v_t - jnp.einsum("hkv,hk->hv", S, k_t)) * b_t[:, None]
        S = S + k_t[:, :, None] * delta[:, None, :]
        return S, jnp.einsum("hkv,hk->hv", S, q_t)

    T, H, Dk = q.shape
    S0 = jnp.zeros((H, Dk, v.shape[-1]), jnp.float32)
    xs = (q, k, v, g, beta)
    if not blocks or T % blocks:
        return jax.lax.scan(step, S0, xs)[1]
    inner = jax.checkpoint(lambda S, b: jax.lax.scan(step, S, b))
    xs = tuple(a.reshape((T // blocks, blocks) + a.shape[1:]) for a in xs)
    o = jax.lax.scan(inner, S0, xs)[1]
    return o.reshape((T,) + o.shape[2:])


def gated_delta_net(p, x, cfg, blocks=None):
    """The Gated DeltaNet mixer (T, d) -> (T, d)."""
    Hk, Hv = cfg["linear_num_key_heads"], cfg["linear_num_value_heads"]
    Dk, Dv = cfg["linear_key_head_dim"], cfg["linear_value_head_dim"]
    eps = cfg.get("norm_eps", 1e-6)
    T = x.shape[0]
    kd, vd = Hk * Dk, Hv * Dv
    qkvz = x @ p["in_proj_qkvz"]["kernel"]
    ba = x @ p["in_proj_ba"]["kernel"]
    mixed, z = qkvz[:, : 2 * kd + vd], qkvz[:, 2 * kd + vd:]
    w = p["conv"]  # (K, channels); tap K-1 is the current token
    K = w.shape[0]
    padded = jnp.concatenate(
        [jnp.zeros((K - 1, mixed.shape[1]), mixed.dtype), mixed]
    )
    conv = sum(padded[j:j + T] * w[j] for j in range(K))
    conv = conv * jax.nn.sigmoid(conv)  # SiLU
    q = conv[:, :kd].reshape(T, Hk, Dk)
    k = conv[:, kd:2 * kd].reshape(T, Hk, Dk)
    v = conv[:, 2 * kd:].reshape(T, Hv, Dv)
    q = q * jax.lax.rsqrt(jnp.sum(q * q, -1, keepdims=True) + eps)
    k = k * jax.lax.rsqrt(jnp.sum(k * k, -1, keepdims=True) + eps)
    q = jnp.repeat(q, Hv // Hk, axis=1) * Dk ** -0.5
    k = jnp.repeat(k, Hv // Hk, axis=1)
    beta = jax.nn.sigmoid(ba[:, :Hv])
    g = -jnp.exp(p["A_log"]) * jax.nn.softplus(ba[:, Hv:] + p["dt_bias"])
    o = delta_recurrence(q, k, v, g, beta, blocks)
    o = o * jax.lax.rsqrt(jnp.mean(o * o, -1, keepdims=True) + eps) * p["norm"]
    z = z.reshape(T, Hv, Dv)
    o = o * (z * jax.nn.sigmoid(z))
    return o.reshape(T, vd) @ p["out_proj"]["kernel"]


def route(p, x, cfg):
    """The router's probabilities (T, E) over all experts and each
    token's ``moe_top_k`` choices (T, K), best first."""
    probs = jax.nn.softmax(x @ p["router"], axis=-1)
    return probs, jnp.argsort(-probs, axis=-1)[:, :cfg["moe_top_k"]]


def expert_layer(p, x, cfg, shared: bool = True, chosen=None):
    """The held experts' part of the top-k sum, plus (``shared``) the
    shared expert behind its sigmoid gate; (T, d) -> (T, d).  ``chosen``
    (T, K) takes the choices as given in place of the router's own
    top-k (the gates are still this router's probabilities of them):
    for comparing two computations whose inputs differ by rounding, so
    that a choice at the edge of the top k does not flip between them."""
    E = cfg["num_experts"]
    held = cfg.get("experts_held") or E
    first = cfg.get("first_expert", 0)
    probs, own = route(p, x, cfg)
    chosen = own if chosen is None else chosen
    gates = jnp.take_along_axis(probs, chosen, axis=-1)
    gates = gates / jnp.sum(gates, -1, keepdims=True)  # norm_topk_prob
    out = jnp.zeros_like(x)
    for e in range(held):
        weight = jnp.sum(jnp.where(chosen == first + e, gates, 0.0), -1)
        a = x @ p["w_gate"][e]
        y = ((a * jax.nn.sigmoid(a)) * (x @ p["w_up"][e])) @ p["w_down"][e]
        out = out + weight[:, None] * y
    if shared:
        a = x @ p["shared_gate_proj"]["kernel"]
        y = ((a * jax.nn.sigmoid(a)) * (x @ p["shared_up"]["kernel"])
             ) @ p["shared_down"]["kernel"]
        out = out + jax.nn.sigmoid(x @ p["shared_gate"]["kernel"]) * y
    return out


def forward(params, tokens, cfg, blocks=None, routing=None):
    """Logits (T, vocab) of one sequence of token ids (T,).  ``routing``:
    one ``chosen`` (T, K) a layer for :func:`expert_layer`, or None."""
    with jax.default_matmul_precision("highest"):
        params = jax.tree.map(lambda a: a.astype(jnp.float32), params)
        eps = cfg.get("norm_eps", 1e-6)
        x = params["Embed_0"]["embedding"][tokens]
        n = cfg["full_attention_interval"]
        for i in range(cfg["num_layers"]):
            p = params[f"layer_{i}"]
            h = rms_norm(x, p["RMSNorm_0"]["scale"], eps)
            if (i + 1) % n:
                x = x + gated_delta_net(p["GatedDeltaNet_0"], h, cfg, blocks)
            else:
                x = x + gated_attention(p["_Attention_0"], h, cfg, blocks)
            h = rms_norm(x, p["RMSNorm_1"]["scale"], eps)
            x = x + expert_layer(
                p["HeldExpertsMLP_0"], h, cfg,
                chosen=None if routing is None else routing[i])
        x = rms_norm(x, params["RMSNorm_0"]["scale"], eps)
        return x @ params["Dense_0"]["kernel"]


def token_loss(logits, targets):
    """Mean next-token cross entropy over the (sliced) vocabulary."""
    logp = logits - jax.scipy.special.logsumexp(logits, -1, keepdims=True)
    return -jnp.mean(jnp.take_along_axis(logp, targets[:, None], -1))


def loss(params, tokens, targets, cfg, blocks=None, routing=None):
    """:func:`token_loss` of :func:`forward`'s logits."""
    return token_loss(forward(params, tokens, cfg, blocks, routing), targets)
