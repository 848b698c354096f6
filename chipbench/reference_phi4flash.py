"""The benchmark's own plain reference of the Phi-4-mini-flash-reasoning
block stack (SambaY, ``model_type: phi4flash``): forward, loss (gradients:
``jax.grad`` of it), each mixer alone, and Adam written out for a replay.

A copy of ``distributed_learning_tpu/models/reference/phi4flash.py`` kept
under the benchmark's paths so that the comparison which decides
``correct`` imports none of the program's model code
(``tests/chipbench_tests/test_train_ref_phi4flash.py`` holds the two
copies to the same text below this paragraph).  What follows is that
file's own account.

The oracle for ``TransformerLM`` configured with a per-layer description
(``layers``) of SambaY's mixers (``models/sambay.py``).  No counterpart
exists in the reference repo (SURVEY.md §2 C11-C13 are tabular/image
nets); the equations follow the published config
(https://huggingface.co/microsoft/Phi-4-mini-flash-reasoning,
config.json) and the papers that define its layers: SambaY's
decoder-hybrid-decoder with the gated memory unit (arXiv:2507.06607,
§2), Mamba-1's selective scan (arXiv:2312.00752, §3, Algorithm 2) and
differential attention (arXiv:2410.05258, eq. 1-3).  Everything here is
``jax.numpy`` in f32 at ``jax.default_matmul_precision("highest")``: no
kernel, no flash, no vmap over agents (one sequence, ``(T,)`` token ids);
the recurrence is a ``lax.scan`` over time, one token a step, and each
softmax map is written out under an explicit mask.

It reads the program's parameter tree (the names are the data's format)
and the model's keyword arguments as a plain dict.  The vocabulary slice
is taken as the program takes it: ids, logits and loss over the slice.

Departures from the published module, each listed in the benchmark
configuration's ``assumed``:

* a differential head pair is query heads ``(j, j + H/2)`` and key heads
  ``(g, g + Hkv/2)``; the published module pairs neighbours ``(2j, 2j +
  1)``: with seeded weights a permutation of the projections' columns,
  and so is the SwiGLU's split of its first matrix into gate and up;
* no positional encoding (the config has no rotary key: order comes from
  the Mamba layers and the causal mask);
* the Mamba sizes are Mamba's defaults (d_inner 2d, 16 states, a
  4-tap convolution, dt rank ceil(d / 16)): the config has no key for
  them;
* no loss beyond cross entropy.

``blocks`` changes memory and compile time, not mathematics: each layer
is then rematerialised in the backward pass, attention runs one head pair
at a time under ``jax.checkpoint``, and the scan over time is a scan over
chunks of ``blocks`` steps, each under ``jax.checkpoint`` (a test holds
the two forms to the same numbers).
"""

from __future__ import annotations

import math

import jax
import jax.numpy as jnp


def silu(x):
    return x * jax.nn.sigmoid(x)


def layer_norm(p, x, eps):
    mu = jnp.mean(x, -1, keepdims=True)
    var = jnp.mean((x - mu) ** 2, -1, keepdims=True)
    return (x - mu) / jnp.sqrt(var + eps) * p["scale"] + p["bias"]


def lambda_init(depth: int) -> float:
    """arXiv:2410.05258, §2.1, at the published 0-based layer index."""
    return 0.8 - 0.6 * math.exp(-0.3 * depth)


def mamba_sizes(cfg):
    """Mamba's defaults, which SambaY keeps: d_inner 2d, 16 states, the
    rank of dt ceil(d / 16) (the convolution's 4 taps are its shape)."""
    d = cfg["hidden_size"]
    return dict(d_inner=2 * d, d_state=16, dt_rank=math.ceil(d / 16))


def scan(x, dt, A, B, C, blocks=None):
    """``s_t = exp(dt_t A) s_{t-1} + (dt_t x_t) B_t``, ``y_t = s_t C_t``
    over (T, channels) ``x`` and ``dt`` and (T, states) ``B``, ``C``; the
    state (channels, states) from zero."""

    def step(s, xs):
        x_t, dt_t, b_t, c_t = xs
        s = jnp.exp(dt_t[:, None] * A) * s + (dt_t * x_t)[:, None] * b_t
        return s, s @ c_t

    T = x.shape[0]
    s0 = jnp.zeros(A.shape, jnp.float32)
    xs = (x, dt, B, C)
    if not blocks or T % blocks:
        return jax.lax.scan(step, s0, xs)[1]
    inner = jax.checkpoint(lambda s, b: jax.lax.scan(step, s, b))
    xs = tuple(a.reshape((T // blocks, blocks) + a.shape[1:]) for a in xs)
    return jax.lax.scan(inner, s0, xs)[1].reshape(x.shape)


def mamba(p, h, cfg, blocks=None):
    """A Mamba-1 mixer (T, d) -> ((T, d), its memory y (T, d_inner))."""
    m = mamba_sizes(cfg)
    Di, N, R = m["d_inner"], m["d_state"], m["dt_rank"]
    T = h.shape[0]
    xz = h @ p["in_proj"]["kernel"]
    x, z = xz[:, :Di], xz[:, Di:]
    w = p["conv"]  # (K, channels); tap K-1 is the current token
    K = w.shape[0]
    padded = jnp.concatenate([jnp.zeros((K - 1, Di), x.dtype), x])
    x = silu(sum(padded[j:j + T] * w[j] for j in range(K)) + p["conv_bias"])
    dbc = x @ p["x_proj"]["kernel"]
    dt = jax.nn.softplus(dbc[:, :R] @ p["dt_proj"]["kernel"] + p["dt_bias"])
    A = -jnp.exp(p["A_log"])
    y = scan(x, dt, A, dbc[:, R:R + N], dbc[:, R + N:], blocks)
    y = y + p["D"] * x
    return (y * silu(z)) @ p["out_proj"]["kernel"], y


def gmu(p, h, memory):
    """SambaY's gated memory unit (T, d) -> (T, d) over a memory (T, Di)."""
    return (silu(h @ p["in_proj"]["kernel"]) * memory) @ p["out_proj"][
        "kernel"]


def diff_attention(p, h, cfg, depth, window=None, kv=None, blocks=None):
    """Differential attention (T, d) -> ((T, d), (K, V)): pair ``j`` of
    query heads against key group ``j // (pairs / groups)``, two softmax
    maps under the causal (and window) mask, their difference weighted by
    ``lambda`` on the group's value, RMS-normed and scaled by ``1 -
    lambda_init``.  ``kv``: the K (T, 2, G, Dh) and V (T, G, 2 Dh) another
    layer made (a cross-decoder layer has no K/V projection)."""
    H, Hkv, Dh = cfg["num_heads"], cfg["num_kv_heads"], cfg["head_dim"]
    P, G = H // 2, Hkv // 2
    eps = cfg.get("norm_eps", 1e-5)
    T = h.shape[0]
    q = jnp.einsum("td,dspe->tspe", h, p["q_proj"]["kernel"])
    if kv is None:
        k = jnp.einsum("td,dsge->tsge", h, p["k_proj"]["kernel"])
        v = jnp.einsum("td,dge->tge", h, p["v_proj"]["kernel"])
    else:
        k, v = kv
    rows, cols = jnp.arange(T)[:, None], jnp.arange(T)[None, :]
    mask = cols <= rows
    if window is not None:
        mask &= cols > rows - window
    lam0 = lambda_init(depth)
    lam = (jnp.exp(jnp.sum(p["lambda_q1"] * p["lambda_k1"]))
           - jnp.exp(jnp.sum(p["lambda_q2"] * p["lambda_k2"])) + lam0)

    def one_pair(j):
        g = j // (P // G)
        maps = []
        for side in (0, 1):
            s = q[:, side, j] @ k[:, side, g].T * Dh ** -0.5
            maps.append(jax.nn.softmax(jnp.where(mask, s, -jnp.inf), -1))
        o = (maps[0] - lam * maps[1]) @ v[:, g]
        o = o * jax.lax.rsqrt(jnp.mean(o * o, -1, keepdims=True) + eps)
        return o * p["subln"] * (1.0 - lam0)

    if blocks:
        o = jax.lax.map(jax.checkpoint(one_pair), jnp.arange(P))
    else:
        o = jnp.stack([one_pair(j) for j in range(P)])
    out = jnp.einsum("pte,ped->td", o, p["o_proj"]["kernel"])
    return out, (k, v)


def swiglu(p, h):
    a = h @ p["gate_proj"]["kernel"]
    return (silu(a) * (h @ p["up_proj"]["kernel"])) @ p["down_proj"]["kernel"]


def mixer(p, spec, h, cfg, shared, blocks=None):
    """One layer's mixer on its input ``h``: (output, what it hands on)."""
    kind = spec["mixer"]
    if kind == "mamba":
        out, memory = mamba(p["mamba"], h, cfg, blocks)
        return out, ({"memory": memory} if spec.get("memory") == "write"
                     else {})
    if kind == "gmu":
        return gmu(p["gmu"], h, shared["memory"]), {}
    cross = kind == "cross_attention"
    out, kv = diff_attention(p[kind], h, cfg, spec["depth"],
                             spec.get("window"),
                             shared["kv"] if cross else None, blocks)
    return out, ({"kv": kv} if spec.get("kv") == "write" else {})


def forward(params, tokens, cfg, blocks=None):
    """Logits (T, vocab) of one sequence of token ids (T,)."""
    with jax.default_matmul_precision("highest"):
        params = jax.tree.map(lambda a: a.astype(jnp.float32), params)
        eps = cfg.get("norm_eps", 1e-5)
        embedding = params["Embed_0"]["embedding"]
        x = embedding[tokens]
        shared = {}
        for i, spec in enumerate(cfg["layers"]):
            def block(p, x, shared, spec=spec):
                out, made = mixer(p, spec, layer_norm(p["LayerNorm_0"], x, eps),
                                  cfg, shared, blocks)
                x = x + out
                return x + swiglu(p, layer_norm(p["LayerNorm_1"], x, eps)), made

            if blocks:
                block = jax.checkpoint(block)
            x, made = block(params[f"layer_{i}"], x, shared)
            shared = {**shared, **made}
        x = layer_norm(params["LayerNorm_0"], x, eps)
        return x @ embedding.T  # the tied head


def token_loss(logits, targets):
    """Mean next-token cross entropy over the (sliced) vocabulary."""
    logp = logits - jax.scipy.special.logsumexp(logits, -1, keepdims=True)
    return -jnp.mean(jnp.take_along_axis(logp, targets[:, None], -1))


def loss(params, tokens, targets, cfg, blocks=None):
    """:func:`token_loss` of :func:`forward`'s logits."""
    return token_loss(forward(params, tokens, cfg, blocks), targets)


def adam(p, mu, nu, g, count, lr, b1=0.9, b2=0.999, eps=1e-8):
    """One Adam step as published (arXiv:1412.6980, Algorithm 1) from
    ``count`` steps taken: the new parameters and moments."""
    t = (count + 1).astype(jnp.float32)
    mu = jax.tree.map(lambda m, g: b1 * m + (1 - b1) * g, mu, g)
    nu = jax.tree.map(lambda v, g: b2 * v + (1 - b2) * g * g, nu, g)
    p = jax.tree.map(
        lambda p, m, v: p - lr * (m / (1 - b1 ** t)) / (
            jnp.sqrt(v / (1 - b2 ** t)) + eps),
        p, mu, nu)
    return p, mu, nu
