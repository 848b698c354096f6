"""Compile the main path's kernels for a described v5e, without a chip.

The TPU's compiler is installed here and compiles for a chip that is
described and not attached (``jax.experimental.topologies``), so what it
would refuse on the machine — a slice off the tiling, too much VMEM, a
kernel that cannot be partitioned, a program that does not fit HBM — is
refused in tier-1, at no chip time.  Nothing runs: a compile that passes
is not a chip run.

The topology is described inside a module-scoped fixture (never at import:
only one process at a time may load the TPU's library, and every xdist
worker imports every test file), and the persistent compilation cache is
off around the compiles (such an entry cannot be read back without a chip).
The kernels themselves are compiled: the public wrappers ask
``jax.devices()`` and would take their CPU branch here.
"""

import os

import jax
import jax.numpy as jnp
import numpy as np
import pytest
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P
from jax.sharding import SingleDeviceSharding

from distributed_learning_tpu.ops import flash_attention as fa
from distributed_learning_tpu.ops import gated_delta as gd

GiB = 2.0**30
#: (batch*heads, T, head_dim) of the streaming flash cases, default blocks
#: 256/512.  The last is the Qwen3-Next cell's gated-attention layer: 2
#: agents x 16 heads of 256 at T 4,096.  The GPT-2 cell's head size 64
#: takes the resident schedule: ``test_flash_compiles_at_the_gpt2_cell``.
FLASH_SHAPES = [(8, 8192, 128), (16, 2048, 128), (32, 4096, 256)]
BLOCK_Q, BLOCK_K = 256, 512


@pytest.fixture(scope="module")
def topo():
    os.environ.setdefault("TPU_LOG_DIR", "disabled")
    from jax.experimental import topologies

    try:
        return topologies.get_topology_desc(
            platform="tpu", topology_name="v5e:2x2"
        )
    except Exception as e:
        pytest.skip(f"no v5e:2x2 topology can be described here: {e}")


@pytest.fixture(scope="module")
def one_chip(topo):
    return SingleDeviceSharding(topo.devices[0])


@pytest.fixture(scope="module")
def agent_mesh(topo):
    return Mesh(np.array(topo.devices[:4]), ("agents",))


@pytest.fixture(scope="module")
def cache_off():
    from jax.experimental.compilation_cache import compilation_cache as cc

    before = jax.config.jax_enable_compilation_cache
    jax.config.update("jax_enable_compilation_cache", False)
    cc.reset_cache()
    yield
    jax.config.update("jax_enable_compilation_cache", before)
    cc.reset_cache()


def _compile(fn, *shapes):
    return jax.jit(fn).lower(*shapes).compile()


def _flash_fn(variant: str, scale: float):
    """The custom-vjp kernels under the public wrappers, by variant."""
    f32sum = lambda x: x.astype(jnp.float32).sum()

    def flash(window):
        return lambda q, k, v: fa._flash(
            q, k, v, scale, True, BLOCK_Q, BLOCK_K, False, window
        )

    def flash_lse(q, k, v):
        out, lse = fa._flash_lse(
            q, k, v, scale, True, BLOCK_Q, BLOCK_K, False
        )
        return f32sum(out) + lse.sum()

    if variant == "fwd":
        return flash(None)
    if variant == "grad":
        return jax.grad(lambda *a: f32sum(flash(None)(*a)), argnums=(0, 1, 2))
    if variant == "window":
        return jax.value_and_grad(
            lambda *a: f32sum(flash(1024)(*a)), argnums=(0, 1, 2)
        )
    if variant == "lse":
        return jax.value_and_grad(flash_lse, argnums=(0, 1, 2))
    raise ValueError(variant)


@pytest.mark.parametrize("variant", ["fwd", "grad", "window", "lse"])
@pytest.mark.parametrize("shape", FLASH_SHAPES, ids=lambda s: "x".join(map(str, s)))
def test_flash_kernels_compile_for_v5e(one_chip, cache_off, shape, variant):
    x = jax.ShapeDtypeStruct(shape, jnp.bfloat16, sharding=one_chip)
    fn = _flash_fn(variant, float(1.0 / np.sqrt(shape[-1])))
    compiled = _compile(fn, x, x, x)
    assert "tpu_custom_call" in compiled.as_text()


@pytest.mark.parametrize("variant", ["fwd", "grad"])
def test_flash_compiles_at_two_widths_for_the_latent_cell(one_chip, cache_off,
                                                          variant):
    """The kanana cell's attention through the wrapper's own preparation:
    one agent a chip, (1, 8192, 32 heads) with a query/key of 192 against
    a value of 128.  The streaming kernels take both widths as they lie (a
    block's last dimension is the array's own, 192, which is off the
    128-lane grid): nothing is padded in HBM, and what Mosaic refuses of
    such a block is refused here."""
    qk = jax.ShapeDtypeStruct((1, 8192, 32, 192), jnp.bfloat16,
                              sharding=one_chip)
    v = jax.ShapeDtypeStruct((1, 8192, 32, 128), jnp.bfloat16,
                             sharding=one_chip)
    attend = lambda q, k, v: fa._attend(
        q, k, v, 192 ** -0.5, True, BLOCK_Q, BLOCK_K, False, None)
    fn = attend if variant == "fwd" else jax.grad(
        lambda *a: attend(*a).astype(jnp.float32).sum(), argnums=(0, 1, 2))
    text = _compile(fn, qk, qk, v).as_text()
    assert "resident" not in text and "flash_fwd" in text
    assert ("flash_bwd_dq" in text and "flash_bwd_dkv" in text) == (
        variant != "fwd")
    assert text.count("tpu_custom_call") == (1 if variant == "fwd" else 3)
    assert "bf16[32,8192,256]" not in text  # no operand padded to 256


@pytest.mark.parametrize("variant", ["fwd", "grad", "window"])
def test_flash_compiles_at_the_gpt2_cell(one_chip, cache_off, variant):
    """The GPT-2 cell's attention through the wrapper's own preparation
    (``fa._attend``; the public wrapper asks ``jax.devices()``): 4 agents
    vmapped x (2, 1024, 12, 64) bf16.  The shape takes the resident
    schedule, two heads of 64 a 128-lane block: a block, an in-kernel
    transpose or a loop bound Mosaic refuses is refused here."""
    x = jax.ShapeDtypeStruct((4, 2, 1024, 12, 64), jnp.bfloat16,
                             sharding=one_chip)
    window = 256 if variant == "window" else None
    attend = jax.vmap(lambda q, k, v: fa._attend(
        q, k, v, 0.125, True, BLOCK_Q, BLOCK_K, False, window))
    fn = attend if variant == "fwd" else jax.grad(
        lambda *a: attend(*a).astype(jnp.float32).sum(), argnums=(0, 1, 2))
    text = _compile(fn, x, x, x).as_text()
    assert "flash_fwd_resident" in text
    assert ("flash_bwd_dq_dkv_resident" in text) == (variant != "fwd")
    assert text.count("tpu_custom_call") == (1 if variant == "fwd" else 2)


@pytest.mark.parametrize("variant", ["fwd", "grad"])
@pytest.mark.parametrize("precision, D", [
    (None, 128), ("highest", 128), (None, 256)],
    ids=["default", "highest", "head256"])
def test_gdn_scan_kernels_compile_for_v5e(one_chip, cache_off, precision, D,
                                          variant):
    """The delta rule's chunk-scan kernel pair at the Qwen3-Next cell's
    shape: 2 agents vmapped over 64 chunks of 64 tokens, 32 heads of 128
    (the rule itself asks ``jax.devices()`` and would take its scan); and
    at a head size of 256, where a grid step takes fewer heads."""
    agents, N, B, H, C = 2, 64, 1, 32, 64
    sds = lambda *s: jax.ShapeDtypeStruct(
        (agents, N, B, H) + s, jnp.float32, sharding=one_chip)
    shapes = (sds(C, D), sds(C, D), sds(C, C), sds(C, D), sds(C, D), sds())
    scan = jax.vmap(lambda *a: gd._chunk_scan(*a, precision, False))
    fn = scan if variant == "fwd" else jax.grad(
        lambda *a: jnp.sin(scan(*a)).sum(), argnums=(0, 1, 2, 3, 4, 5))
    text = _compile(fn, *shapes).as_text()
    assert text.count("tpu_custom_call") >= (1 if variant == "fwd" else 2)
    assert "gdn_scan_fwd" in text and ("gdn_scan_bwd" in text) == (
        variant == "grad")


def _gpt2_small():
    from distributed_learning_tpu.models import TransformerLM

    model = TransformerLM(
        vocab_size=50257, num_layers=12, num_heads=12, head_dim=64,
        max_len=1024, mlp_ratio=4, pos_emb="learned", attn_impl="flash",
        dtype=jnp.bfloat16,
    )
    return model, jnp.zeros((1, 1024), jnp.int32), 163_050_577


def _wrn_28_10():
    from distributed_learning_tpu.models import WideResNet

    model = WideResNet(depth=28, widen_factor=10, dropout_rate=0.3,
                       num_classes=10)
    return model, jnp.zeros((1, 32, 32, 3)), 36_489_290


@pytest.mark.parametrize("build", [_gpt2_small, _wrn_28_10],
                         ids=["gpt2-small", "wrn-28-10"])
def test_dense_mix_until_compiles_leafwise_at_cell_widths(
    one_chip, cache_off, build
):
    """The engine's dense ``mix_until`` on 4 stacked f32 replicas of the
    chip benchmark's two trees (GPT-2 small, 4 x 163,050,577 parameters,
    the consensus-only cell's; WRN-28-10, 4 x 36,489,290): the leaves
    are mixed where they lie, so the program joins nothing and its
    temporaries stay under two states."""
    from distributed_learning_tpu.parallel.consensus import ConsensusEngine
    from distributed_learning_tpu.parallel.topology import Topology

    n = 4
    model, sample, n_params = build()
    shapes = jax.eval_shape(
        lambda: model.init(jax.random.key(0), sample, train=False)["params"]
    )
    params = jax.tree.map(
        lambda v: jax.ShapeDtypeStruct(
            (n,) + v.shape, jnp.float32, sharding=one_chip
        ),
        shapes,
    )
    state = sum(4 * int(np.prod(v.shape)) for v in jax.tree.leaves(params))
    assert state == 4 * n * n_params

    def scalar(dtype):
        return jax.ShapeDtypeStruct((), dtype, sharding=one_chip)

    engine = ConsensusEngine(Topology.ring(n).metropolis_weights())
    compiled = engine._get_jitted("mix_until").lower(
        params, scalar(jnp.float32), scalar(jnp.int32), scalar(jnp.int32)
    ).compile()
    hlo = compiled.as_text()
    assert hlo.startswith("HloModule jit_wrapped")
    assert " concatenate(" not in hlo
    # the round is the weighted sum of a leaf's rows: no contraction
    assert " convolution(" not in hlo and " dot(" not in hlo
    assert compiled.memory_analysis().temp_size_in_bytes < 2 * state


def test_sharded_ring_mix_compiles_to_collective_permute(agent_mesh, cache_off):
    """One agent per device on the 2x2: the gossip round is ppermutes."""
    from distributed_learning_tpu.parallel.consensus import ConsensusEngine
    from distributed_learning_tpu.parallel.topology import Topology

    engine = ConsensusEngine(
        Topology.ring(4).metropolis_weights(), mesh=agent_mesh
    )
    x = jax.ShapeDtypeStruct(
        (4, 4_194_304), jnp.float32,
        sharding=NamedSharding(agent_mesh, P("agents")),
    )
    compiled = _compile(lambda s: engine.mix(s, times=1), x)
    text = compiled.as_text()
    assert "collective-permute" in text
    assert "all-gather" not in text  # the state never leaves its device whole
    # Per device: one agent's 16 MiB in, 16 MiB out.
    assert compiled.memory_analysis().argument_size_in_bytes < 0.02 * GiB


def test_the_sharded_step_with_kernels_compiles_one_agent_a_chip(
        agent_mesh, cache_off, monkeypatch):
    """The latent-attention LM's epoch program for the 2x2, one agent a
    chip, at the kanana cell's widths (two layers, T 1,024, a 2,048-row
    vocabulary): the vmapped step runs under ``shard_map`` over the agent
    axis, so every chip gets its own Mosaic calls and nothing is gathered;
    left to the partitioner a kernel has no rule."""
    from distributed_learning_tpu.models import TransformerLM
    from distributed_learning_tpu.parallel.topology import Topology
    from distributed_learning_tpu.training.trainer import GossipTrainer

    # the public wrapper asks jax.devices() and would take its CPU branch
    monkeypatch.setattr(
        fa, "flash_attention",
        lambda q, k, v, causal=True, sm_scale=None, window=None: fa._attend(
            q, k, v, sm_scale, causal, BLOCK_Q, BLOCK_K, False, window))
    n, T, steps, vocab = 4, 1024, 2, 2048
    model = TransformerLM(
        vocab_size=vocab, num_layers=2, hidden_size=2048, num_heads=32,
        head_dim=64, max_len=T, pos_emb="rope", rope_base=1e6,
        attn_impl="flash", norm="rmsnorm", head_bias=False, kv_lora_rank=512,
        qk_nope_head_dim=128, qk_rope_head_dim=64, v_head_dim=128,
        num_dense_layers=1, dense_width=6144, mlp="held_experts",
        num_experts=128, moe_top_k=6, experts_held=8, expert_width=768,
        shared_expert_width=1536, router_score="sigmoid", route_scale=2.448,
        route_bias_rate=0.001, shared_expert_gate=False, remat_blocks=True,
        dtype=jnp.bfloat16)
    ids = np.zeros((steps, T), np.int32)
    trainer = GossipTrainer(
        node_names=list(range(n)), model=model, optimizer="adam",
        learning_rate=3e-4, error="cross_entropy", weights=Topology.ring(n),
        train_data={a: (ids, ids) for a in range(n)}, test_data=None,
        batch_size=1, epoch_len=steps, epoch=1 << 30, mesh=None,
        dropout=False, seed=0)
    trainer.engine.mesh = agent_mesh  # read when the program is traced
    per_agent = NamedSharding(agent_mesh, P("agents"))
    everywhere = NamedSharding(agent_mesh, P())
    var = jax.eval_shape(lambda: model.init(
        jax.random.key(0), jnp.zeros((1, T), jnp.int32)))
    stacked = lambda tree: jax.tree.map(
        lambda a: jax.ShapeDtypeStruct((n,) + a.shape, a.dtype,
                                       sharding=per_agent), tree)
    opt = stacked(jax.eval_shape(trainer.tx.init, var["params"]))
    key = jax.eval_shape(lambda: jax.random.key(0))
    state = (stacked(var["params"]), stacked(var["batch_stats"]), opt,
             jax.ShapeDtypeStruct(key.shape, key.dtype, sharding=everywhere))
    data = jax.ShapeDtypeStruct((n, steps, T), jnp.int32, sharding=per_agent)
    idx = jax.ShapeDtypeStruct((steps, n, 1), jnp.int32, sharding=everywhere)
    compiled = jax.jit(trainer._epoch_fn, donate_argnums=(0,)).lower(
        state, data, data, idx).compile()
    text = compiled.as_text()
    assert "tpu_custom_call" in text and "flash_bwd_dkv" in text
    for collective in ("all-gather", "all-to-all", "all-reduce",
                       "collective-permute"):
        assert collective + "(" not in text and collective + "-start(" not in text
    # per chip: one agent's state, not four
    one_agent = sum(a.size * a.dtype.itemsize for a in jax.tree.leaves(
        (var["params"], jax.eval_shape(trainer.tx.init, var["params"]))))
    assert compiled.memory_analysis().argument_size_in_bytes < 1.05 * one_agent
