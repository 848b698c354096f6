"""Phi-4-mini-flash-reasoning (SambaY) through ``TransformerLM(layers=...)``:
the Mamba, GMU, differential and cross-attention mixers at a small size on
the CPU with seeded weights, against the plain reference
``distributed_learning_tpu/models/reference/phi4flash.py``; the selective
scan's kernels (interpreted) against its ``lax.scan``; the published role
rule against the benchmark configuration's list; and the parameter count
of that configuration."""

from __future__ import annotations

import json
import math
import os

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from distributed_learning_tpu.models import TransformerLM
from distributed_learning_tpu.models.reference import phi4flash as ref
from distributed_learning_tpu.ops import selective_scan as ss

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
CONFIG = os.path.join(REPO, "chipbench", "configs", "phi4-mini-flash-solo1.json")
LAYERS = [
    {"mixer": "mamba", "depth": 14},
    {"mixer": "attention", "depth": 15, "window": 8},
    {"mixer": "mamba", "depth": 16, "memory": "write"},
    {"mixer": "attention", "depth": 17, "kv": "write"},
    {"mixer": "gmu", "depth": 18},
    {"mixer": "cross_attention", "depth": 19},
]
KW = dict(vocab_size=50, hidden_size=32, num_heads=8, num_kv_heads=4,
          head_dim=4, dense_width=48, norm_eps=1e-5, layers=LAYERS,
          pos_emb="none", attn_impl="flash", max_len=64)
T = 24


def _perturbed(params, scale=0.1):
    leaves, tree = jax.tree.flatten(params)
    return tree.unflatten([
        a + scale * jax.random.normal(jax.random.key(10 + i), a.shape)
        for i, a in enumerate(leaves)])


@pytest.fixture(scope="module")
def toy():
    model = TransformerLM(**KW, remat_blocks=True)
    x = jax.random.randint(jax.random.key(1), (1, T), 0, 50)
    y = jax.random.randint(jax.random.key(2), (T,), 0, 50)
    p = _perturbed(model.init(jax.random.key(0), x)["params"])
    return model, p, x, y


def _rel(a, b):
    return float(jnp.linalg.norm(a - b) / jnp.linalg.norm(b))


def _program_loss(model, p, x, y):
    return ref.token_loss(model.apply({"params": p}, x)[0], y)


def test_the_tree_has_the_published_layers(toy):
    _, p, _, _ = toy
    assert set(p) == {"Embed_0", "LayerNorm_0"} | {f"layer_{i}" for i in range(6)}
    mixers = [next(k for k in p[f"layer_{i}"] if k in (
        "mamba", "attention", "gmu", "cross_attention")) for i in range(6)]
    assert mixers == [spec["mixer"] for spec in LAYERS]
    shapes = jax.tree.map(lambda a: a.shape, p["layer_0"]["mamba"])
    assert shapes["A_log"] == (64, 16) and shapes["x_proj"]["kernel"] == (64, 34)
    assert shapes["dt_proj"]["kernel"] == (2, 64) and shapes["conv"] == (4, 64)
    cross = p["layer_5"]["cross_attention"]
    assert "k_proj" not in cross and "v_proj" not in cross  # no K/V of its own
    attn = jax.tree.map(lambda a: a.shape, p["layer_3"]["attention"])
    assert attn["q_proj"]["kernel"] == (32, 2, 4, 4)
    assert attn["k_proj"]["kernel"] == (32, 2, 2, 4)
    assert attn["v_proj"]["kernel"] == (32, 2, 8)
    assert attn["o_proj"]["kernel"] == (4, 8, 32) and attn["subln"] == (8,)


def test_logits_loss_and_gradients_match_the_reference(toy):
    model, p, x, y = toy
    with jax.default_matmul_precision("highest"):
        logits = model.apply({"params": p}, x)[0]
        want = ref.forward(p, x[0], KW)
        assert _rel(logits, want) < 1e-5
        g = jax.grad(lambda p: _program_loss(model, p, x, y))(p)
        w = jax.grad(ref.loss)(p, x[0], y, KW)
    for (path, a), b in zip(jax.tree_util.tree_leaves_with_path(g),
                            jax.tree.leaves(w)):
        assert _rel(a, b) < 1e-4, path


def test_blocks_change_memory_not_mathematics(toy):
    _, p, x, y = toy
    with jax.default_matmul_precision("highest"):
        a = jax.value_and_grad(ref.loss)(p, x[0], y, KW)
        b = jax.value_and_grad(ref.loss)(p, x[0], y, KW, 8)
    assert float(a[0]) == pytest.approx(float(b[0]), rel=1e-6)
    for u, v in zip(jax.tree.leaves(a[1]), jax.tree.leaves(b[1])):
        np.testing.assert_allclose(u, v, rtol=1e-4, atol=1e-6)


def test_the_cross_layer_grads_reach_the_kv_layer(toy):
    """Layer 19 attends over layer 17's K and V: what it adds to the loss
    reaches layer 17's K/V projections, in the program as in the reference
    (the cross layer's output projection at zero takes it away)."""
    model, p, x, y = toy
    cut = jax.tree.map(lambda a: a, p)
    o = cut["layer_5"]["cross_attention"]["o_proj"]["kernel"]
    cut["layer_5"]["cross_attention"]["o_proj"]["kernel"] = jnp.zeros_like(o)
    kv = lambda g: {k: g["layer_3"]["attention"][k]["kernel"]
                    for k in ("k_proj", "v_proj")}
    with jax.default_matmul_precision("highest"):
        prog = lambda q: kv(jax.grad(
            lambda q: _program_loss(model, q, x, y))(q))
        want = lambda q: kv(jax.grad(ref.loss)(q, x[0], y, KW))
        got_through = jax.tree.map(jnp.subtract, prog(p), prog(cut))
        want_through = jax.tree.map(jnp.subtract, want(p), want(cut))
    for k in ("k_proj", "v_proj"):
        assert float(jnp.linalg.norm(want_through[k])) > 1e-3
        assert _rel(got_through[k], want_through[k]) < 1e-4


def test_window_and_full_attention_in_one_stack(toy):
    """Layer 15 sees the last 8 keys, layer 17 the whole past: the
    program's window layer is the reference's with the window, not
    without it."""
    model, p, x, _ = toy
    with jax.default_matmul_precision("highest"):
        _, seen = model.apply({"params": p}, x, capture_intermediates=lambda m, _: (
            m.name in ("attention", "LayerNorm_0")), mutable=["intermediates"])
        inter = seen["intermediates"]
        for i, window in ((1, 8), (3, None)):
            h = inter[f"layer_{i}"]["LayerNorm_0"]["__call__"][0][0]
            got = inter[f"layer_{i}"]["attention"]["__call__"][0][0][0]
            lp = p[f"layer_{i}"]["attention"]
            depth = LAYERS[i]["depth"]
            want = ref.diff_attention(lp, h, KW, depth, window)[0]
            other = ref.diff_attention(lp, h, KW, depth,
                                       None if window else 8)[0]
            assert _rel(got, want) < 1e-5
            assert _rel(got, other) > 1e-2


@pytest.mark.parametrize("T_, chunk", [(40, 16), (200, 128)])
def test_the_scan_kernels_match_the_scan(T_, chunk):
    """The Pallas pair (interpreted) and the ``lax.scan`` form against the
    step-by-step recurrence, forward and every gradient, at a length that
    is not a multiple of the chunk."""
    k = jax.random.split(jax.random.key(0), 7)
    Bsz, D, N = 2, 16, 16
    x = jax.random.normal(k[0], (Bsz, T_, D))
    dt = jax.random.normal(k[1], (Bsz, T_, D)) - 2
    A = -jnp.exp(jax.random.normal(k[2], (D, N)))
    B = jax.random.normal(k[3], (Bsz, T_, N))
    C = jax.random.normal(k[4], (Bsz, T_, N))
    bias = 0.5 * jax.random.normal(k[5], (D,))
    dy = jax.random.normal(k[6], (Bsz, T_, D))
    args = (x, dt, A, B, C, bias)

    def loss(fn):
        return lambda *a: jnp.sum(fn(*a[:5], bias=a[5]) * dy)

    with jax.default_matmul_precision("highest"):
        want = ss.selective_scan_reference(*args[:5], bias=bias)
        gw = jax.grad(loss(ss.selective_scan_reference), range(6))(*args)
        for interpret in (False, True):
            fn = lambda *a, bias: ss.selective_scan(
                *a, bias=bias, chunk=chunk, interpret=interpret)
            y = fn(*args[:5], bias=bias)
            assert y.dtype == jnp.float32 and y.shape == x.shape
            assert _rel(y, want) < 1e-5
            for a, b in zip(jax.grad(loss(fn), range(6))(*args), gw):
                assert _rel(a, b) < 1e-5


def test_the_scan_writes_no_state_of_every_step():
    """The kernels' residual is the state each chunk enters with: (T /
    chunk, states, channels), never (T, channels, states)."""
    x = jnp.zeros((1, 256, 128))
    B = jnp.zeros((1, 256, 16))
    jaxpr = jax.make_jaxpr(lambda x: jax.vjp(lambda x: ss._scan_kernels(
        x, x, jnp.zeros(128), -jnp.ones((128, 16)), B, B, 128, True), x))(x)
    shapes = {tuple(v.aval.shape) for eqn in jaxpr.eqns for v in eqn.outvars
              if hasattr(v.aval, "shape")}
    assert (1, 2, 16, 128) in shapes
    assert not any(math.prod(s) >= 256 * 128 * 16 for s in shapes)


def published_roles(L: int, window: int) -> list:
    """SambaY's rule (modeling_phi4flash.py, arXiv:2507.06607 §2): even
    layers Mamba, odd attention; the second half GMUs and cross-attention,
    but for its first two layers, the memory's Mamba and the K/V's full
    attention; attention in the first half windowed."""
    roles = []
    for i in range(L):
        spec = {"mixer": "mamba" if i % 2 == 0 else "attention", "depth": i}
        if i >= L // 2:
            if i == L // 2:
                spec["memory"] = "write"
            elif i == L // 2 + 1:
                spec["kv"] = "write"
            else:
                spec["mixer"] = "gmu" if i % 2 == 0 else "cross_attention"
        elif i % 2:
            spec["window"] = window
        roles.append(spec)
    return roles


def test_the_configuration_lists_the_published_roles_of_layers_14_to_19():
    with open(CONFIG, encoding="utf-8") as fh:
        cfg = json.load(fh)
    roles = published_roles(cfg["published"]["num_hidden_layers"],
                            cfg["sliding_window"])
    assert cfg["model"]["kwargs"]["layers"] == roles[14:20]
    mixers = [r["mixer"] for r in roles]
    assert [mixers.count(k) for k in (
        "mamba", "attention", "gmu", "cross_attention")] == [9, 9, 7, 7]
    assert sum(r.get("window") is not None for r in roles) == 8


def test_the_configuration_holds_697_million_parameters():
    with open(CONFIG, encoding="utf-8") as fh:
        kw = dict(json.load(fh)["model"]["kwargs"])
    kw["dtype"] = jnp.dtype(kw["dtype"])
    var = jax.eval_shape(lambda: TransformerLM(**kw).init(
        jax.random.key(0), jnp.zeros((1, 8), jnp.int32)))
    assert sum(a.size for a in jax.tree.leaves(var["params"])) == 697_073_792


def test_the_mamba_layers_count_dead_states(toy):
    model, p, x, _ = toy
    _, mut = model.apply({"params": p}, x, mutable=["counters"])
    from distributed_learning_tpu.obs.carry import collect_counters

    dead = int(collect_counters(mut)["ssm.state_dead"])
    # by hand: exp(mean_t softplus(delta + b) * A) < 1e-3, both layers
    want = 0
    with jax.default_matmul_precision("highest"):
        _, seen = model.apply({"params": p}, x, mutable=["intermediates"])
    for i in (0, 2):
        _, delta, _, _, _ = seen["intermediates"][f"layer_{i}"]["mamba"]["scan"][0]
        mp = p[f"layer_{i}"]["mamba"]
        dt = jnp.mean(jax.nn.softplus(delta[0] + mp["dt_bias"]), axis=0)
        want += int(jnp.sum(jnp.exp(dt[:, None] * -jnp.exp(mp["A_log"])) < 1e-3))
    assert dead == want
