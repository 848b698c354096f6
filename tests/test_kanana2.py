"""kanana-2-30b-a3b through ``TransformerLM``: latent attention, the
leading dense layer, the sigmoid router with its balancing bias and the
held experts, at a small size on the CPU with seeded weights, against the
plain reference ``distributed_learning_tpu/models/reference/kanana2.py``;
the flash kernels at two widths (interpreted); the bias through the
trainer's ``batch_stats``; and the parameter count of the benchmark's
configuration."""

from __future__ import annotations

import json
import os

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from distributed_learning_tpu.models import TransformerLM
from distributed_learning_tpu.models.moe import HeldExpertsMLP
from distributed_learning_tpu.models.reference import kanana2 as ref
from distributed_learning_tpu.ops import flash_attention as fa
from distributed_learning_tpu.ops.ring_attention import attention_reference

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
KW = dict(
    vocab_size=64, num_layers=3, hidden_size=32, num_heads=2, head_dim=16,
    max_len=64, pos_emb="rope", rope_base=1e6, norm="rmsnorm", norm_eps=1e-6,
    head_bias=False, kv_lora_rank=16, qk_nope_head_dim=8, qk_rope_head_dim=4,
    v_head_dim=8, num_dense_layers=1, dense_width=48, mlp="held_experts",
    num_experts=16, moe_top_k=3, experts_held=4, first_expert=4,
    expert_width=8, shared_expert_width=16, router_score="sigmoid",
    route_scale=2.448, route_bias_rate=0.001, shared_expert_gate=False,
)
T = 32


@pytest.fixture(scope="module")
def toy():
    """Model, perturbed parameters (norm offsets and all), biases that
    matter, one sequence and its targets."""
    model = TransformerLM(**KW)
    x = jax.random.randint(jax.random.key(1), (1, T), 0, 64)
    y = jax.random.randint(jax.random.key(2), (T,), 0, 64)
    var = model.init(jax.random.key(0), x)
    leaves, tree = jax.tree.flatten(var["params"])
    p = tree.unflatten([
        a + 0.1 * jax.random.normal(jax.random.key(10 + i), a.shape)
        for i, a in enumerate(leaves)])
    bs = jax.tree.map(
        lambda a: 0.05 * jax.random.normal(jax.random.key(7), a.shape),
        var["batch_stats"])
    return model, p, bs, x, y


def test_the_tree_has_the_published_layers(toy):
    _, p, bs, _, _ = toy
    assert set(p["layer_0"]) == {"RMSNorm_0", "RMSNorm_1", "_LatentAttention_0",
                                 "gate_proj", "up_proj", "down_proj"}
    attn = jax.tree.map(lambda a: a.shape, p["layer_1"]["_LatentAttention_0"])
    assert attn == {
        "q_proj": {"kernel": (32, 2, 12)}, "kv_a_proj": {"kernel": (32, 20)},
        "kv_a_norm": {"scale": (16,)}, "kv_b_proj": {"kernel": (16, 2, 16)},
        "o_proj": {"kernel": (2, 8, 32)}}  # no biases
    moe = p["layer_1"]["HeldExpertsMLP_0"]
    assert "shared_gate" not in moe and moe["router"].shape == (32, 16)
    assert moe["w_gate"].shape == (4, 32, 8)
    # the bias: one value an expert, every expert layer, no gradient's
    assert jax.tree.map(lambda a: a.shape, bs) == {
        f"layer_{i}": {"HeldExpertsMLP_0": {"route_bias": (16,)}}
        for i in (1, 2)}


def test_forward_loss_and_gradients_match_the_reference(toy):
    model, p, bs, x, y = toy

    def program(p):
        logits = model.apply({"params": p, "batch_stats": bs}, x)[0]
        return ref.token_loss(logits, y), logits

    def reference(p):
        logits = ref.forward(p, x[0], KW, stats=bs)
        return ref.token_loss(logits, y), logits

    (l1, z1), g1 = jax.value_and_grad(program, has_aux=True)(p)
    (l2, z2), g2 = jax.value_and_grad(reference, has_aux=True)(p)
    np.testing.assert_allclose(z1, z2, atol=2e-5)
    assert abs(float(l1 - l2)) < 1e-6
    for a, b in zip(jax.tree.leaves(g1), jax.tree.leaves(g2), strict=True):
        np.testing.assert_allclose(a, b, atol=1e-5 * float(jnp.abs(b).max()) + 1e-8)
    # the blocked form (one head at a time, layers rematerialised) is the
    # same mathematics
    g3 = jax.grad(lambda p: ref.loss(p, x[0], y, KW, 1, None, bs))(p)
    for a, b in zip(jax.tree.leaves(g3), jax.tree.leaves(g2), strict=True):
        np.testing.assert_allclose(a, b, atol=1e-5 * float(jnp.abs(b).max()) + 1e-8)


def test_the_bias_picks_the_experts_and_never_enters_the_weights(toy):
    model, p, bs, x, _ = toy
    _, seen = model.apply({"params": p, "batch_stats": bs}, x,
                          mutable=["intermediates"])
    seen = seen["intermediates"]["layer_1"]["HeldExpertsMLP_0"]
    h, chosen = seen["router_input"][0], seen["chosen"][0]
    moe = p["layer_1"]["HeldExpertsMLP_0"]
    bias = bs["layer_1"]["HeldExpertsMLP_0"]["route_bias"]
    scores, own = ref.route(moe, h, KW, bias)
    assert (np.sort(own, -1) == np.sort(chosen, -1)).all()
    # without the bias other experts would have been picked somewhere
    assert (np.sort(ref.route(moe, h, KW)[1], -1) != np.sort(chosen, -1)).any()
    gates = jnp.take_along_axis(scores, chosen, -1)
    gates = 2.448 * gates / gates.sum(-1, keepdims=True)
    want = jnp.stack([jnp.where(chosen == 4 + e, gates, 0).sum(-1)
                      for e in range(4)], -1)
    np.testing.assert_allclose(seen["held_weights"][0], want, atol=1e-6)


def test_the_bias_update_by_hand():
    cfg = {"num_experts": 4, "route_bias_rate": 0.001}
    chosen = jnp.array([[0, 1], [0, 2], [0, 1], [0, 3]])  # loads 4, 2, 1, 1
    bias = jnp.array([0.5, 0.0, -0.25, 0.0])
    got = ref.bias_update(bias, chosen, cfg)
    # mean load 2: expert 0 is over it, 1 sits on it, 2 and 3 are under
    np.testing.assert_allclose(got, [0.499, 0.0, -0.249, 0.001], atol=1e-7)


def test_a_training_call_moves_the_bias_as_the_reference_does(toy):
    model, p, bs, x, _ = toy
    _, mut = model.apply({"params": p, "batch_stats": bs}, x, train=True,
                         mutable=["batch_stats", "counters"])
    chosen = ref.choices(p, x[0], KW, stats=bs)
    for i in (1, 2):
        path = (f"layer_{i}", "HeldExpertsMLP_0")
        before = bs[path[0]][path[1]]["route_bias"]
        got = mut["batch_stats"][path[0]][path[1]]["route_bias"]
        np.testing.assert_array_equal(got, ref.bias_update(before, chosen[i], KW))
        assert float(jnp.abs(got - before).max()) == pytest.approx(0.001, rel=1e-3)
        counters = mut["counters"][path[0]][path[1]]
        load = np.bincount(np.asarray(chosen[i]).ravel(), minlength=16)
        assert int(counters["moe.load_max_all"]) == load.max()
        assert int(counters["moe.load_max"]) == load[4:8].max()
        assert int(counters["moe.rows_held"]) == load[4:8].sum()
    # not a training call, or the collection not mutable: the bias stays
    _, mut = model.apply({"params": p, "batch_stats": bs}, x, train=False,
                         mutable=["batch_stats"])
    assert jax.tree.all(jax.tree.map(lambda a, b: (a == b).all(),
                                     mut["batch_stats"], bs))
    model.apply({"params": p, "batch_stats": bs}, x, train=True)  # no raise


def test_the_sixteen_shares_add_up_to_the_uncut_layer():
    """Each share's routed part (its held experts alone), and the shared
    expert counted once, add up to the reference's whole layer."""
    d, E, K, held = 32, 16, 3, 1
    layer = lambda first: HeldExpertsMLP(
        num_experts=E, experts_held=held, first_expert=first, top_k=K,
        expert_width=8, shared_width=16, score_func="sigmoid",
        route_scale=2.448, bias_rate=0.001, shared_gate=False)
    x = jax.random.normal(jax.random.key(3), (1, T, d))
    full = HeldExpertsMLP(
        num_experts=E, experts_held=E, top_k=K, expert_width=8,
        shared_width=16, score_func="sigmoid", route_scale=2.448,
        bias_rate=0.001, shared_gate=False)
    var = full.init(jax.random.key(4), x)
    p = var["params"]
    bs = {"route_bias": 0.05 * jax.random.normal(jax.random.key(5), (E,))}
    cfg = dict(num_experts=E, moe_top_k=K, route_scale=2.448)
    whole = ref.expert_layer(p, x[0], cfg, bs["route_bias"])
    shared = (ref.silu(x[0] @ p["shared_gate_proj"]["kernel"])
              * (x[0] @ p["shared_up"]["kernel"])) @ p["shared_down"]["kernel"]
    total = shared
    for first in range(0, E, held):  # the 16 shares of a 16-chips-a-layer cut
        share = {**p, **{k: p[k][first:first + held]
                         for k in ("w_gate", "w_up", "w_down")}}
        out = layer(first).apply({"params": share, "batch_stats": bs}, x)[0]
        total = total + (out - shared)  # this share's routed part
    np.testing.assert_allclose(total, whole, atol=2e-5)
    # and the uncut program layer is the uncut reference layer
    np.testing.assert_allclose(
        full.apply({"params": p, "batch_stats": bs}, x)[0], whole, atol=2e-5)


@pytest.mark.parametrize("kwargs, named", [
    (dict(score_func="tanh"), "score_func"),
    (dict(bias_rate=0.0), "bias_rate"),
    (dict(route_scale=-1.0), "route_scale"),
])
def test_the_expert_layer_refuses_what_it_does_not_know(kwargs, named):
    layer = HeldExpertsMLP(num_experts=4, experts_held=2, **kwargs)
    with pytest.raises(ValueError, match=named):
        layer.init(jax.random.key(0), jnp.zeros((1, 4, 8)))


def test_the_softmax_layer_is_the_layer_it_was():
    """The defaults build the accepted layer: no bias, no new parameter,
    a gated shared expert."""
    layer = HeldExpertsMLP(num_experts=8, experts_held=2, top_k=2)
    var = layer.init(jax.random.key(0), jnp.zeros((1, 4, 8)))
    assert "batch_stats" not in var
    assert "shared_gate" in var["params"]


def test_latent_attention_needs_rotary_positions():
    model = TransformerLM(**{**KW, "pos_emb": "learned"})
    with pytest.raises(ValueError, match="kv_lora_rank"):
        model.init(jax.random.key(0), jnp.zeros((1, 8), jnp.int32))
    with pytest.raises(ValueError, match="uniform"):
        TransformerLM(**KW).require_uniform("decode")


# ---------------------------------------------------------------------- #
# the flash kernels at two widths                                        #
# ---------------------------------------------------------------------- #
def _qkv(dqk, dv, T=256, H=2, dtype=jnp.float32):
    k0 = jax.random.key(0)
    mk = lambda i, d: jax.random.normal(
        jax.random.fold_in(k0, i), (1, T, H, d)).astype(dtype)
    return mk(1, dqk), mk(2, dqk), mk(3, dv), mk(4, dv)


@pytest.mark.parametrize("dqk, dv", [(192, 128), (24, 16)])
def test_flash_at_two_widths_matches_plain_attention(dqk, dv):
    q, k, v, w = _qkv(dqk, dv)
    scale = dqk ** -0.5
    flash = lambda q, k, v: (fa.flash_attention(
        q, k, v, interpret=True, block_q=64, block_k=128) * w).sum()
    plain = lambda q, k, v: (attention_reference(
        q, k, v, sm_scale=scale) * w).sum()
    out = fa.flash_attention(q, k, v, interpret=True, block_q=64, block_k=128)
    assert out.shape == v.shape
    np.testing.assert_allclose(out, attention_reference(q, k, v), atol=2e-5)
    for got, want in zip(jax.grad(flash, (0, 1, 2))(q, k, v),
                         jax.grad(plain, (0, 1, 2))(q, k, v)):
        assert got.shape == want.shape
        np.testing.assert_allclose(got, want, atol=5e-5)


def test_two_widths_stream_on_the_operands_as_they_lie():
    """Nothing padded to the other's width: the kernels' operands keep 192
    and 128, and the names a profile is read by are the streaming ones."""
    q, k, v, _ = _qkv(192, 128, dtype=jnp.bfloat16)
    f = lambda q, k, v: fa._attend(q, k, v, 192 ** -0.5, True, 64, 128,
                                   True, None).astype(jnp.float32).sum()
    text = str(jax.make_jaxpr(jax.grad(f, (0, 1, 2)))(q, k, v))
    for name in ("flash_fwd", "flash_bwd_dq", "flash_bwd_dkv"):
        assert f"name={name}" in text or name in text
    assert "resident" not in text
    assert "256,256]" not in text.replace(" ", "")  # no operand padded to 256
    assert "bf16[2,256,192]" in text.replace(" ", "")
    assert "bf16[2,256,128]" in text.replace(" ", "")


@pytest.mark.parametrize("bad, named", [
    (lambda q, k, v: (q[0], k, v), "q must be"),
    (lambda q, k, v: (q, k[..., :8], v), "k must have"),
    (lambda q, k, v: (q, k, v[:, :8]), "v must be"),
])
def test_flash_attention_names_the_argument_it_refuses(bad, named):
    q, k, v, _ = _qkv(24, 16, T=16)
    with pytest.raises(ValueError, match=named):
        fa.flash_attention(*bad(q, k, v), interpret=True)


# ---------------------------------------------------------------------- #
# the benchmark's configuration                                          #
# ---------------------------------------------------------------------- #
@pytest.fixture(scope="module")
def cell_model():
    with open(os.path.join(REPO, "chipbench", "configs",
                           "kanana-2-30b-a3b-ring4.json")) as fh:
        config = json.load(fh)
    kwargs = dict(config["model"]["kwargs"])
    kwargs["dtype"] = jnp.dtype(kwargs["dtype"])
    model = TransformerLM(**kwargs)
    return jax.eval_shape(lambda: model.init(
        jax.random.key(0), jnp.zeros((1, 8192), jnp.int32)))


def test_the_cell_counts_424960512_parameters(cell_model):
    count = lambda tree: sum(a.size for a in jax.tree.leaves(tree))
    p = cell_model["params"]
    assert count(p["layer_1"]["_LatentAttention_0"]) == 26_345_984
    assert count(p["layer_0"]) == 64_098_816
    assert count(p["layer_4"]) == 73_798_144
    moe = p["layer_1"]["HeldExpertsMLP_0"]
    assert moe["router"].size == 262_144
    assert count({k: moe[k] for k in moe if k.startswith("shared")}) == 9_437_184
    assert moe["w_gate"].shape == (8, 2048, 768)
    assert count(p) == 424_960_512
    assert all(a.dtype == jnp.float32 for a in jax.tree.leaves(p))
    # the balancing bias of all 128 experts, in every expert layer
    assert jax.tree.map(lambda a: (a.shape, a.dtype), cell_model["batch_stats"]) == {
        f"layer_{i}": {"HeldExpertsMLP_0": {"route_bias": ((128,), jnp.float32)}}
        for i in range(1, 5)}
