"""The Gated-DeltaNet hybrid LM (the Qwen3-Next block stack) at toy size on
the CPU: every new mechanism against the plain reference
(``models/reference/qwen3_next.py``), seeded weights.

Toy shape: hidden 64, 2 query heads of 32 over 1 KV head, 4 layers in the
3:1 pattern, 16 experts top 4 with 4 held, vocabulary 256, T 128, chunk 16.
"""

from __future__ import annotations

import jax
import jax.numpy as jnp
import numpy as np
import optax
import pytest

from distributed_learning_tpu.models import TransformerLM
from distributed_learning_tpu.models.moe import HeldExpertsMLP
from distributed_learning_tpu.models.reference import qwen3_next as ref
from distributed_learning_tpu.models.transformer import _rope
from distributed_learning_tpu.ops.gated_delta import (
    gated_delta_recurrence,
    gated_delta_rule,
)

TOY = dict(
    vocab_size=256, num_layers=4, num_heads=2, head_dim=32, num_kv_heads=1,
    hidden_size=64, max_len=128, attn_impl="full", pos_emb="rope",
    rope_base=1e7, rope_fraction=0.25, attn_gate=True, norm="rmsnorm",
    head_bias=False, full_attention_interval=4, linear_num_key_heads=2,
    linear_num_value_heads=4, linear_key_head_dim=16,
    linear_value_head_dim=16, linear_chunk=16, mlp="held_experts",
    num_experts=16, moe_top_k=4, experts_held=4, first_expert=4,
    expert_width=32, shared_expert_width=32,
)
T = 128


def _perturbed(params, seed=2):
    """Seeded weights with the zero- and one-initialised vectors (norm
    scales, ``dt_bias``) moved off their init, so that each one matters."""
    leaves, treedef = jax.tree.flatten(params)
    keys = jax.random.split(jax.random.key(seed), len(leaves))
    return jax.tree.unflatten(treedef, [
        leaf + 0.1 * jax.random.normal(k, leaf.shape) if leaf.ndim == 1
        else leaf for leaf, k in zip(leaves, keys)
    ])


@pytest.fixture(scope="module")
def toy():
    model = TransformerLM(**TOY)
    tokens = jax.random.randint(jax.random.key(0), (2, T), 0, 256)
    targets = jax.random.randint(jax.random.key(5), (2, T), 0, 256)
    params = _perturbed(model.init(jax.random.key(1), tokens)["params"])
    return model, params, tokens, targets


def _rel(a, b):
    return float(jnp.abs(a - b).max() / (jnp.abs(b).max() + 1e-30))


# ---------------------------------------------------------------------- #
# the whole model against the reference                                  #
# ---------------------------------------------------------------------- #
def test_layer_pattern_and_parameter_count():
    model = TransformerLM(**TOY)
    assert model.layer_types == ("linear_attention",) * 3 + ("full_attention",)
    assert not model.uniform
    # the published widths at the benchmark's cut: the issue's arithmetic
    full = TransformerLM(
        vocab_size=18992, num_layers=4, num_heads=16, head_dim=256,
        num_kv_heads=2, hidden_size=2048, max_len=4096, pos_emb="rope",
        rope_fraction=0.25, attn_gate=True, norm="rmsnorm", head_bias=False,
        full_attention_interval=4, mlp="held_experts", num_experts=512,
        moe_top_k=10, experts_held=8,
    )
    shapes = jax.eval_shape(
        full.init, jax.random.key(0), jnp.zeros((1, 4096), jnp.int32)
    )["params"]
    count = lambda t: sum(int(np.prod(l.shape)) for l in jax.tree.leaves(t))
    assert count(shapes["layer_0"]["GatedDeltaNet_0"]) == 33_718_464
    assert count(shapes["layer_3"]["_Attention_0"]) == 27_263_488
    assert count(shapes["layer_0"]["HeldExpertsMLP_0"]) == (
        4_196_352 + 8 * 3_145_728)
    assert count(shapes) == 323_677_248


def test_logits_match_the_reference(toy):
    model, params, tokens, _ = toy
    got = model.apply({"params": params}, tokens)
    want = jnp.stack([ref.forward(params, t, TOY) for t in tokens])
    assert _rel(got, want) < 1e-4


def test_remat_blocks_is_the_same_function(toy):
    model, params, tokens, _ = toy
    got = model.clone(remat_blocks=True).apply({"params": params}, tokens)
    np.testing.assert_allclose(
        got, model.apply({"params": params}, tokens), atol=1e-5)


@pytest.fixture(scope="module")
def both_grads(toy):
    model, params, tokens, targets = toy

    def program(p):
        logits = model.apply({"params": p}, tokens)
        return optax.softmax_cross_entropy_with_integer_labels(
            logits, targets).mean()

    def reference(p):
        return sum(ref.loss(p, t, y, TOY) for t, y in zip(tokens, targets)) / 2

    return jax.value_and_grad(program)(params), jax.value_and_grad(
        reference)(params)


def test_loss_matches_the_reference(both_grads):
    (got, _), (want, _) = both_grads
    assert abs(float(got) - float(want)) < 1e-5


_LEAVES = [
    jax.tree_util.keystr(path) for path, _ in
    jax.tree_util.tree_flatten_with_path(jax.eval_shape(
        TransformerLM(**TOY).init, jax.random.key(0),
        jnp.zeros((1, T), jnp.int32))["params"])[0]
]


@pytest.mark.parametrize("leaf", _LEAVES)
def test_every_leafs_gradient_matches_the_reference(both_grads, leaf):
    (_, got), (_, want) = both_grads
    flat = lambda t: {jax.tree_util.keystr(p): v for p, v in
                      jax.tree_util.tree_flatten_with_path(t)[0]}
    g, w = flat(got)[leaf], flat(want)[leaf]
    assert float(jnp.abs(w).max()) > 0, "the leaf does not reach the loss"
    assert _rel(g, w) < 3e-3


# ---------------------------------------------------------------------- #
# the chunked rule against the recurrence                                #
# ---------------------------------------------------------------------- #
def _rule_inputs(t, seed=0, decay=2.0):
    ks = jax.random.split(jax.random.key(seed), 5)
    B, H, Dk, Dv = 2, 3, 16, 32
    unit = lambda x: x / jnp.linalg.norm(x, axis=-1, keepdims=True)
    return (
        unit(jax.random.normal(ks[0], (B, t, H, Dk))) * Dk ** -0.5,
        unit(jax.random.normal(ks[1], (B, t, H, Dk))),
        jax.random.normal(ks[2], (B, t, H, Dv)),
        -decay * jax.nn.softplus(jax.random.normal(ks[3], (B, t, H))),
        jax.nn.sigmoid(jax.random.normal(ks[4], (B, t, H))),
    )


@pytest.mark.parametrize("t", [128, 100])  # a multiple of the chunk, and not
@pytest.mark.parametrize("what", ["forward", "backward"])
# a state that forgets within a chunk, and one that carries over all of them
@pytest.mark.parametrize("decay", [2.0, 0.01])
def test_chunked_rule_matches_the_recurrence(t, what, decay):
    args = _rule_inputs(t, decay=decay)
    chunked = lambda *a: gated_delta_rule(*a, chunk=16)
    if what == "forward":
        np.testing.assert_allclose(
            chunked(*args), gated_delta_recurrence(*args), atol=2e-6)
        return
    grad = lambda fn: jax.grad(
        lambda *a: jnp.sum(jnp.sin(fn(*a))), argnums=(0, 1, 2, 3, 4))(*args)
    for got, want in zip(grad(chunked), grad(gated_delta_recurrence)):
        assert _rel(got, want) < 1e-5


def test_reference_blocks_change_memory_not_mathematics(toy):
    _, params, tokens, _ = toy
    np.testing.assert_allclose(
        ref.forward(params, tokens[0], TOY, blocks=16),
        ref.forward(params, tokens[0], TOY), atol=1e-5)


# ---------------------------------------------------------------------- #
# the held-experts layer                                                 #
# ---------------------------------------------------------------------- #
_MOE = dict(num_experts=16, top_k=4, expert_width=32, shared_width=32)
_CFG = dict(num_experts=16, moe_top_k=4)


def _moe_inputs(seed=3):
    x = jax.random.normal(jax.random.key(seed), (2, 64, 48))
    whole = HeldExpertsMLP(experts_held=16, **_MOE)
    return x, whole, whole.init(jax.random.key(seed + 1), x)["params"]


def test_the_shares_add_up_to_the_uncut_layer():
    """The routed parts of all four shares of 4 experts, plus the shared
    expert once, are the uncut reference's layer with all 16."""
    x, _, params = _moe_inputs()
    flat = x.reshape(-1, x.shape[-1])
    with jax.default_matmul_precision("highest"):
        want = ref.expert_layer(params, flat, dict(_CFG, experts_held=16))
        shared = want - ref.expert_layer(
            params, flat, dict(_CFG, experts_held=16), shared=False)
    total, held = shared, 0
    for first in range(0, 16, 4):
        share = {
            k: v[first:first + 4] if k in ("w_gate", "w_up", "w_down") else v
            for k, v in params.items()
        }
        layer = HeldExpertsMLP(experts_held=4, first_expert=first, **_MOE)
        out, mut = layer.apply({"params": share}, x, mutable=["counters"])
        held = held + int(mut["counters"]["moe.rows_held"])
        total = total + (out.reshape(flat.shape) - shared)
        # and each share is the reference's share
        np.testing.assert_allclose(
            out.reshape(flat.shape),
            ref.expert_layer(share, flat, dict(
                _CFG, experts_held=4, first_expert=first)),
            atol=1e-5)
    np.testing.assert_allclose(total, want, atol=2e-5)
    # every (token, choice) pair is counted by exactly one share
    assert held == flat.shape[0] * _CFG["moe_top_k"]


def test_no_pair_is_cut_when_every_token_goes_to_one_held_expert():
    x, _, params = _moe_inputs()
    share = {k: v[4:8] if k in ("w_gate", "w_up", "w_down") else v
             for k, v in params.items()}
    # a router that sends every token to expert 5 first (held: 4..7)
    share["router"] = share["router"].at[:, 5].set(0.0) * 1e-3
    x = jnp.abs(x)
    share["router"] = share["router"].at[:, 5].set(1.0)
    layer = HeldExpertsMLP(experts_held=4, first_expert=4, **_MOE)
    out, mut = layer.apply({"params": share}, x, mutable=["counters"])
    counters = mut["counters"]
    assert int(counters["moe.load_max"]) == x.shape[0] * x.shape[1]
    assert int(counters["moe.rows_held"]) >= int(counters["moe.load_max"])
    flat = x.reshape(-1, x.shape[-1])
    np.testing.assert_allclose(
        out.reshape(flat.shape),
        ref.expert_layer(share, flat, dict(_CFG, experts_held=4,
                                           first_expert=4)),
        atol=1e-5)


def test_a_token_with_no_held_choice_gets_the_shared_expert_alone():
    x, _, params = _moe_inputs()
    share = {k: v[:4] if k in ("w_gate", "w_up", "w_down") else v
             for k, v in params.items()}
    # every token prefers experts 8..15: none of its 4 choices is held
    share["router"] = jnp.zeros_like(share["router"]).at[:, 8:].set(1.0)
    x = jnp.abs(x)
    layer = HeldExpertsMLP(experts_held=4, **_MOE)
    out, mut = layer.apply({"params": share}, x, mutable=["counters"])
    assert int(mut["counters"]["moe.rows_held"]) == 0
    flat = x.reshape(-1, x.shape[-1])
    with jax.default_matmul_precision("highest"):
        cfg = dict(_CFG, experts_held=4)
        want = ref.expert_layer(share, flat, cfg)
        assert float(jnp.abs(
            ref.expert_layer(share, flat, cfg, shared=False)).max()) == 0.0
    np.testing.assert_allclose(out.reshape(flat.shape), want, atol=1e-5)


def test_the_reference_takes_the_routing_it_is_given():
    """``chosen=`` replaces the reference's own top-k (the chip comparison
    hands it the program's, so that bf16 noise upstream flips no choice):
    its own choices back give the same layer, the sown choices of the
    program are those, and other choices give another layer."""
    x, _, params = _moe_inputs()
    share = {k: v[4:8] if k in ("w_gate", "w_up", "w_down") else v
             for k, v in params.items()}
    cfg = dict(_CFG, experts_held=4, first_expert=4)
    flat = x.reshape(-1, x.shape[-1])
    layer = HeldExpertsMLP(experts_held=4, first_expert=4, **_MOE)
    _, mut = layer.apply({"params": share}, x, mutable=["intermediates"])
    sown = mut["intermediates"]["chosen"][0]
    with jax.default_matmul_precision("highest"):
        _, own = ref.route(share, flat, cfg)
        np.testing.assert_array_equal(sown, own)
        want = ref.expert_layer(share, flat, cfg)
        np.testing.assert_array_equal(
            ref.expert_layer(share, flat, cfg, chosen=own), want)
        other = ref.expert_layer(share, flat, cfg, chosen=(own + 1) % 16)
    assert float(jnp.abs(other - want).max()) > 1e-3


# ---------------------------------------------------------------------- #
# gated attention's parts                                                #
# ---------------------------------------------------------------------- #
def test_partial_rotary_leaves_the_rest_of_the_head_untouched():
    x = jax.random.normal(jax.random.key(0), (2, 16, 3, 32))
    pos = jnp.arange(16)
    out = _rope(x, pos, base=1e7, rotary_dim=8)
    np.testing.assert_array_equal(out[..., 8:], x[..., 8:])
    np.testing.assert_allclose(
        out[..., :8], _rope(x[..., :8], pos, base=1e7), atol=0)
    assert float(jnp.abs(out[:, 1:, :, :8] - x[:, 1:, :, :8]).max()) > 1e-3
    # the whole head by default, as before
    np.testing.assert_array_equal(_rope(x, pos), _rope(x, pos, rotary_dim=32))


def test_flash_at_head_size_256_matches_dense_attention():
    from distributed_learning_tpu.ops.flash_attention import flash_attention
    from distributed_learning_tpu.ops.ring_attention import attention_reference

    ks = jax.random.split(jax.random.key(0), 3)
    q, k, v = (jax.random.normal(kk, (1, 256, 2, 256)) for kk in ks)
    got = flash_attention(q, k, v, causal=True, interpret=True,
                          block_q=128, block_k=128)
    np.testing.assert_allclose(
        got, attention_reference(q, k, v, causal=True), atol=2e-5)
    grad = lambda fn: jax.grad(lambda *a: jnp.sum(jnp.sin(fn(*a))),
                               argnums=(0, 1, 2))(q, k, v)
    flash = lambda *a: flash_attention(*a, causal=True, interpret=True,
                                       block_q=128, block_k=128)
    dense = lambda *a: attention_reference(*a, causal=True)
    for g, w in zip(grad(flash), grad(dense)):
        assert _rel(g, w) < 1e-4


# ---------------------------------------------------------------------- #
# what was there stays as it was                                         #
# ---------------------------------------------------------------------- #
def test_defaults_leave_the_gpt2_model_bit_for_bit():
    """The GPT-2 cell's kwargs (at a small depth and width): the same
    parameter tree (recorded paths) and the output of the pre-hybrid
    block, written out by hand from the same parameters."""
    kwargs = dict(vocab_size=50257, num_layers=2, num_heads=2, head_dim=64,
                  max_len=64, mlp_ratio=4, pos_emb="learned",
                  attn_impl="full")
    model = TransformerLM(**kwargs)
    assert model.uniform
    tokens = jax.random.randint(jax.random.key(0), (2, 64), 0, 50257)
    params = model.init(jax.random.key(1), tokens)["params"]
    paths = [jax.tree_util.keystr(p) for p, _ in
             jax.tree_util.tree_flatten_with_path(params)[0]]
    assert paths == _GPT2_PATHS
    out = model.apply({"params": params}, tokens)
    # built by hand from the same parameters: the pre-hybrid block
    import flax.linen as nn

    def block(p, x):
        h = nn.LayerNorm().apply({"params": p["LayerNorm_0"]}, x)
        a = p["_Attention_0"]
        qkv = jnp.einsum("btd,dchf->btchf", h, a["DenseGeneral_0"]["kernel"])
        q, k, v = qkv[:, :, 0], qkv[:, :, 1], qkv[:, :, 2]
        s = jnp.einsum("bqhd,bkhd->bhqk", q, k) / 8.0
        s = jnp.where(jnp.tril(jnp.ones((64, 64), bool)), s, -jnp.inf)
        o = jnp.einsum("bhqk,bkhd->bqhd", jax.nn.softmax(s, -1), v)
        x = x + jnp.einsum("bqhd,hdm->bqm", o, a["DenseGeneral_1"]["kernel"])
        h = nn.LayerNorm().apply({"params": p["LayerNorm_1"]}, x)
        h = nn.gelu(h @ p["Dense_0"]["kernel"] + p["Dense_0"]["bias"])
        return x + h @ p["Dense_1"]["kernel"] + p["Dense_1"]["bias"]

    x = params["Embed_0"]["embedding"][tokens] + params["Embed_1"]["embedding"][
        jnp.arange(64)][None]
    for i in range(2):
        x = block(params[f"_Block_{i}"], x)
    x = nn.LayerNorm().apply({"params": params["LayerNorm_0"]}, x)
    want = x @ params["Dense_0"]["kernel"] + params["Dense_0"]["bias"]
    np.testing.assert_allclose(out, want, atol=2e-5)


_GPT2_PATHS = [
    "['Dense_0']['bias']", "['Dense_0']['kernel']",
    "['Embed_0']['embedding']", "['Embed_1']['embedding']",
    "['LayerNorm_0']['bias']", "['LayerNorm_0']['scale']",
] + [
    f"['_Block_{i}']{rest}" for i in range(2) for rest in (
        "['Dense_0']['bias']", "['Dense_0']['kernel']",
        "['Dense_1']['bias']", "['Dense_1']['kernel']",
        "['LayerNorm_0']['bias']", "['LayerNorm_0']['scale']",
        "['LayerNorm_1']['bias']", "['LayerNorm_1']['scale']",
        "['_Attention_0']['DenseGeneral_0']['kernel']",
        "['_Attention_0']['DenseGeneral_1']['kernel']",
    )
]


@pytest.mark.parametrize("arg, value", [
    ("rope_base", 1e6), ("norm_eps", 1e-2),
])
def test_a_plain_model_takes_the_new_arguments_alone(arg, value):
    """``rope_base`` and ``norm_eps`` reach a uniform model's blocks without
    any other hybrid flag: same parameter tree, another function (and one
    that decode still runs)."""
    kwargs = dict(vocab_size=64, num_layers=2, num_heads=2, head_dim=16,
                  max_len=32, pos_emb="rope")
    plain, other = TransformerLM(**kwargs), TransformerLM(
        **kwargs, **{arg: value})
    assert other.uniform
    tokens = jax.random.randint(jax.random.key(0), (1, 32), 0, 64)
    params = plain.init(jax.random.key(1), tokens)["params"]
    assert jax.tree.structure(params) == jax.tree.structure(
        other.init(jax.random.key(1), tokens)["params"])
    base = plain.apply({"params": params}, tokens)
    moved = other.apply({"params": params}, tokens)
    assert float(jnp.max(jnp.abs(moved - base))) > 1e-4
    # the explicit default is the model as it was
    same = TransformerLM(**kwargs, **{arg: getattr(plain, arg)})
    np.testing.assert_array_equal(same.apply({"params": params}, tokens), base)


@pytest.mark.parametrize("what", ["decode", "pipeline", "tensor_parallel"])
def test_the_other_paths_refuse_the_hybrid_layers(what):
    from jax.sharding import Mesh

    model = TransformerLM(**TOY)
    mesh = Mesh(np.array(jax.devices()[:2]).reshape(1, 2), ("data", "model"))
    with pytest.raises(ValueError, match="training path"):
        if what == "decode":
            model.clone(decode=True).init(
                jax.random.key(0), jnp.zeros((1, 8), jnp.int32))
        elif what == "pipeline":
            from distributed_learning_tpu.training.pp_lm import _LMParts

            _LMParts(mesh, model, "model")
        else:
            from distributed_learning_tpu.training.tp import make_tp_train_step

            make_tp_train_step(mesh, model, optax.sgd(0.1))


# ---------------------------------------------------------------------- #
# through the trainer                                                    #
# ---------------------------------------------------------------------- #
def test_one_gossip_epoch_of_two_agents_lowers_the_loss_and_counts():
    from distributed_learning_tpu.parallel.topology import Topology
    from distributed_learning_tpu.training.trainer import GossipTrainer

    rng = np.random.default_rng(0)
    p = 1.0 / np.arange(1, 257) ** 1.1
    ids = rng.choice(256, size=(2, 8, T + 1), p=p / p.sum()).astype(np.int32)
    trainer = GossipTrainer(
        node_names=[0, 1], model=TransformerLM(**TOY), optimizer="adam",
        learning_rate=3e-3, error="cross_entropy",
        weights=Topology.complete(2),
        train_data={a: (ids[a, :, :-1], ids[a, :, 1:]) for a in range(2)},
        test_data=None, batch_size=1, epoch_len=8, epoch=1 << 30,
        dropout=False, seed=0,
    )
    trainer.initialize_nodes()
    first = trainer.train_epochs(1)[0]
    last = trainer.train_epochs(1)[0]
    assert float(np.mean(last["train_loss"])) < float(
        np.mean(first["train_loss"]))
    assert last["mixed"] and last["deviation"] < 1e-5  # W = 1/2: the mean
    counters = last["counters"]
    assert set(counters) == {"moe.rows_held", "moe.load_max"}
    assert counters["moe.rows_held"].shape == (8, 2)
    assert np.all(counters["moe.load_max"] <= counters["moe.rows_held"])
    # 4 layers x 128 tokens x 4 choices x 4 / 16 held, about
    assert 200 < float(counters["moe.rows_held"].mean()) < 1000
