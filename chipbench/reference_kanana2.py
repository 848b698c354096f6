"""The benchmark's own plain reference of the kanana-2-30b-a3b block stack
(``model_type: deepseek_v3``): forward, loss (gradients: ``jax.grad`` of
it), each layer alone, and the balancing bias's update.

A copy of ``distributed_learning_tpu/models/reference/kanana2.py`` kept
under the benchmark's paths so that the comparison which decides
``correct`` imports none of the program's model code
(``tests/chipbench_tests/test_train_ref_mesh.py`` holds the two copies to
the same text below this paragraph).  What follows is that file's own
account.

The oracle for ``TransformerLM`` configured with latent attention
(``kv_lora_rank``), leading dense SwiGLU layers (``num_dense_layers``)
and the sigmoid-routed held experts (``router_score="sigmoid"``,
``route_scale``, ``route_bias_rate``, ``shared_expert_gate=False``).  No
counterpart exists in the reference repo (SURVEY.md §2 C11-C13 are
tabular/image nets); the equations follow the published config
(https://huggingface.co/kakaocorp/kanana-2-30b-a3b-instruct-2601,
config.json) and the papers that define its layers: multi-head latent
attention (DeepSeek-V2, arXiv:2405.04434, section 2.1.2, eqs. 9-19) and
the sigmoid router with its auxiliary-loss-free balancing bias
(DeepSeek-V3, arXiv:2412.19437, section 2.1.2, eqs. 12-16).  Everything
here is ``jax.numpy`` in f32 at ``jax.default_matmul_precision
("highest")``: no kernel, no vmap over agents (one sequence, ``(T,)``
token ids), dense attention under an explicit mask, one head at a time,
and the expert layer as a loop over the held experts with a mask.

It reads the program's parameter tree (the names are the data's format)
and the model's keyword arguments as a plain dict.  ``experts_held`` /
``first_expert`` and the vocabulary slice are taken exactly as the
program takes them: what the absent experts would have added is left
out, in the program and in the reference alike.  ``experts_held=None``
holds all of them (the uncut layer).

Departures from the published module, each listed in the benchmark
configuration's ``assumed``:

* ``rope_interleave: true`` pairs a rotary head's columns (2j, 2j + 1);
  here the pairs are (j, j + 32), a permutation of the columns of
  ``q_proj`` and ``kv_a_proj``: with seeded weights the same model;
* RMSNorm stores its weight as the offset from one (``1 + w``, ``w``
  zero at first) where the published module stores the weight (one at
  first): the same function and, under Adam without weight decay, the
  same trajectory;
* the router's product and sigmoid are f32 at the highest precision;
* the bias's step ``gamma`` is 0.001 (arXiv:2412.19437, section 2.1.2
  defines the update and section 4.2 trains with that value; the config
  has no key for it), and the bias is per replica;
* no multi-token-prediction module and no auxiliary loss (the config has
  a key for neither).

``blocks`` changes memory and compile time, not mathematics: attention
then runs one head at a time under ``jax.checkpoint``, each layer is
rematerialised in the backward pass, and the loops over the held experts
and over the expert layers (which are alike) are ``lax.scan``s, so that
their bodies are compiled once (a test holds the two forms to the same
numbers).
"""

from __future__ import annotations

import jax
import jax.numpy as jnp

__all__ = ["forward", "loss", "token_loss", "block", "latent_attention",
           "latent_operands", "dense_mlp", "route", "expert_layer", "bias_update", "choices"]


def rms_norm(x, w, eps):
    """RMSNorm with the weight stored as its offset from one."""
    return x * jax.lax.rsqrt(jnp.mean(x * x, -1, keepdims=True) + eps) * (1.0 + w)


def rope(x, base):
    """Rotary embedding on the whole last dimension, half-split pairs
    (j, j + D / 2); ``x``: (T, H, D), positions 0..T-1."""
    T, half = x.shape[0], x.shape[-1] // 2
    inv = base ** (-jnp.arange(half, dtype=jnp.float32) / half)
    ang = jnp.arange(T, dtype=jnp.float32)[:, None] * inv[None, :]
    cos, sin = jnp.cos(ang)[:, None, :], jnp.sin(ang)[:, None, :]
    x1, x2 = x[..., :half], x[..., half:]
    return jnp.concatenate([x1 * cos - x2 * sin, x1 * sin + x2 * cos], -1)


def silu(a):
    return a * jax.nn.sigmoid(a)


def latent_operands(p, x, cfg):
    """What attention is handed, from the layer's input (T, d): the
    queries and keys (T, H, nope + rope), rotary on their second part,
    the ONE rotary key of a token shared by every head, and the values
    (T, H, v)."""
    H, R = cfg["num_heads"], cfg["kv_lora_rank"]
    Dn, Dr = cfg["qk_nope_head_dim"], cfg["qk_rope_head_dim"]
    eps, base = cfg.get("norm_eps", 1e-6), cfg.get("rope_base", 10000.0)
    T = x.shape[0]
    q = jnp.einsum("td,dhe->the", x, p["q_proj"]["kernel"])  # (T, H, Dn+Dr)
    ckv = x @ p["kv_a_proj"]["kernel"]                       # (T, R+Dr)
    c = rms_norm(ckv[:, :R], p["kv_a_norm"]["scale"], eps)
    kv = jnp.einsum("tr,rhe->the", c, p["kv_b_proj"]["kernel"])
    k_pe = rope(ckv[:, None, R:], base)                      # (T, 1, Dr)
    q = jnp.concatenate([q[..., :Dn], rope(q[..., Dn:], base)], -1)
    k = jnp.concatenate(
        [kv[..., :Dn], jnp.broadcast_to(k_pe, (T, H, Dr))], -1)
    return q, k, kv[..., Dn:]


def latent_attention(p, x, cfg, blocks=None):
    """Multi-head latent attention, nothing absorbed; (T, d) -> (T, d)."""
    q, k, v = latent_operands(p, x, cfg)
    T, Dqk = x.shape[0], q.shape[-1]
    mask = jnp.arange(T)[:, None] >= jnp.arange(T)[None, :]

    def one_head(qkv):
        qh, kh, vh = qkv
        s = jnp.where(mask, (qh @ kh.T) * Dqk ** -0.5, -jnp.inf)
        return jax.nn.softmax(s, axis=-1) @ vh

    heads = tuple(jnp.moveaxis(a, 1, 0) for a in (q, k, v))
    if blocks:
        o = jax.lax.map(jax.checkpoint(one_head), heads)
    else:
        o = jax.vmap(one_head)(heads)
    return jnp.einsum("hte,hed->td", o, p["o_proj"]["kernel"])


def dense_mlp(p, x):
    """The leading dense layer's SwiGLU; ``p`` is the block's own tree."""
    return (silu(x @ p["gate_proj"]["kernel"]) * (x @ p["up_proj"]["kernel"])
            ) @ p["down_proj"]["kernel"]


def route(p, x, cfg, bias=None):
    """The router's sigmoid scores (T, E) over all experts and each
    token's ``moe_top_k`` choices (T, K) on ``score + bias``, best
    first."""
    scores = jax.nn.sigmoid(x @ p["router"])
    picked = scores if bias is None else scores + bias
    return scores, jnp.argsort(-picked, axis=-1)[:, :cfg["moe_top_k"]]


def expert_layer(p, x, cfg, bias=None, shared: bool = True, chosen=None,
                 blocks=None):
    """The held experts' part of the top-k sum, plus (``shared``) the
    ungated shared expert; (T, d) -> (T, d).  ``chosen`` (T, K) takes
    the choices as given in place of the router's own (the weights are
    still this router's scores of them): for comparing two computations
    whose inputs differ by rounding, so that a choice at the edge of the
    top k does not flip between them."""
    E = cfg["num_experts"]
    held = cfg.get("experts_held") or E
    first = cfg.get("first_expert", 0)
    scores, own = route(p, x, cfg, bias)
    chosen = own if chosen is None else chosen
    gates = jnp.take_along_axis(scores, chosen, axis=-1)
    gates = gates / (jnp.sum(gates, -1, keepdims=True) + 1e-20)
    gates = gates * cfg.get("route_scale", 1.0)

    def one(e, w_gate, w_up, w_down):  # held expert e's weighted output
        weight = jnp.sum(jnp.where(chosen == first + e, gates, 0.0), -1)
        return weight[:, None] * ((silu(x @ w_gate) * (x @ w_up)) @ w_down)

    out = jnp.zeros_like(x)
    if blocks:
        out = jax.lax.scan(
            lambda acc, xs: (acc + one(*xs), None), out,
            (jnp.arange(held), p["w_gate"][:held], p["w_up"][:held],
             p["w_down"][:held]))[0]
    else:
        for e in range(held):
            out = out + one(e, p["w_gate"][e], p["w_up"][e], p["w_down"][e])
    if shared:
        out = out + (
            silu(x @ p["shared_gate_proj"]["kernel"])
            * (x @ p["shared_up"]["kernel"])) @ p["shared_down"]["kernel"]
    return out


def bias_update(bias, chosen, cfg):
    """``b_e + gamma * sign(mean load - load_e)``, the load the (token,
    choice) pairs each of all the experts received."""
    E = cfg["num_experts"]
    load = jnp.sum(chosen[..., None] == jnp.arange(E), axis=(0, 1)).astype(
        jnp.float32)
    return bias + cfg["route_bias_rate"] * jnp.sign(jnp.mean(load) - load)


def _bias(stats, i):
    if stats is None or f"layer_{i}" not in stats:
        return None
    return stats[f"layer_{i}"]["HeldExpertsMLP_0"]["route_bias"]


def block(p, x, cfg, i, blocks=None, bias=None, chosen=None):
    """Layer ``i``: ``x + Attn(norm(x))`` then ``x + MLP(norm(x))``.
    Returns the new ``x`` and the experts chosen (``chosen`` if given,
    the router's own otherwise; None in a dense layer)."""
    eps = cfg.get("norm_eps", 1e-6)
    h = rms_norm(x, p["RMSNorm_0"]["scale"], eps)
    x = x + latent_attention(p["_LatentAttention_0"], h, cfg, blocks)
    h = rms_norm(x, p["RMSNorm_1"]["scale"], eps)
    if i < cfg.get("num_dense_layers", 0):
        return x + dense_mlp(p, h), None
    moe = p["HeldExpertsMLP_0"]
    if chosen is None:
        chosen = route(moe, h, cfg, bias)[1]
    return x + expert_layer(moe, h, cfg, bias, chosen=chosen,
                            blocks=blocks), chosen


def forward(params, tokens, cfg, blocks=None, routing=None, stats=None,
            with_choices: bool = False):
    """Logits (T, vocab) of one sequence of token ids (T,).  ``stats``:
    the program's ``batch_stats`` tree (the balancing biases), or None
    for zeros.  ``routing``: ``{layer index: chosen (T, K)}`` for
    :func:`expert_layer`, or None.  ``with_choices`` also returns
    ``{layer index: chosen}`` as routed here.  ``blocks``: the module
    docstring."""
    with jax.default_matmul_precision("highest"):
        params = jax.tree.map(lambda a: a.astype(jnp.float32), params)
        x = params["Embed_0"]["embedding"][tokens]
        L, dense = cfg["num_layers"], cfg.get("num_dense_layers", 0)
        given = lambda i: None if routing is None else routing.get(i)
        made = {}
        if not blocks:
            for i in range(L):
                x, chosen = block(params[f"layer_{i}"], x, cfg, i, None,
                                  _bias(stats, i), given(i))
                if chosen is not None:
                    made[i] = chosen
        else:
            for i in range(dense):
                x = jax.checkpoint(lambda p, x, i=i: block(
                    p, x, cfg, i, blocks)[0])(params[f"layer_{i}"], x)
            # the expert layers are alike: one body, scanned over them
            experts = range(dense, L)
            zeros = jnp.zeros((cfg["num_experts"],), jnp.float32)
            biases = [_bias(stats, i) for i in experts]
            xs = [jax.tree.map(lambda *a: jnp.stack(a),
                               *[params[f"layer_{i}"] for i in experts]),
                  jnp.stack([zeros if b is None else b for b in biases])]
            if routing is not None:
                xs.append(jnp.stack([routing[i] for i in experts]))

            def body(x, xs):
                return block(xs[0], x, cfg, dense, blocks, xs[1],
                             xs[2] if routing is not None else None)

            x, chosen = jax.lax.scan(jax.checkpoint(body), x, tuple(xs))
            made = {i: chosen[j] for j, i in enumerate(experts)}
        x = rms_norm(x, params["RMSNorm_0"]["scale"],
                     cfg.get("norm_eps", 1e-6))
        logits = x @ params["Dense_0"]["kernel"]
        return (logits, made) if with_choices else logits


def choices(params, tokens, cfg, blocks=None, stats=None):
    """``{layer index: chosen (T, K)}`` of :func:`forward`'s own routing."""
    return forward(params, tokens, cfg, blocks, None, stats, True)[1]


def token_loss(logits, targets):
    """Mean next-token cross entropy over the (sliced) vocabulary."""
    logp = logits - jax.scipy.special.logsumexp(logits, -1, keepdims=True)
    return -jnp.mean(jnp.take_along_axis(logp, targets[:, None], -1))


def loss(params, tokens, targets, cfg, blocks=None, routing=None, stats=None):
    """:func:`token_loss` of :func:`forward`'s logits."""
    return token_loss(
        forward(params, tokens, cfg, blocks, routing, stats), targets)
