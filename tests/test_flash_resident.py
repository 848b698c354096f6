"""The flash kernels' resident schedule (``ops/flash_attention.py``).

Where a head's K and V fit VMEM a grid step takes a block of queries
against all of its keys, on the free (B, T, H*D) view with the heads of a
128-lane block side by side, and the backward is one kernel.  Here, on
the CPU with ``interpret=True``: values and gradients against
:func:`attention_reference` and against the streaming kernels; which
schedule a shape takes, read from the kernel names in the text lowered
for a TPU; and that no operand of the GPT-2 shape's kernels is padded.
"""

import re

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from distributed_learning_tpu.ops import flash_attention as fa
from distributed_learning_tpu.ops.ring_attention import attention_reference


def _rel(a, b):
    a, b = (np.asarray(x, np.float32) for x in (a, b))
    return float(np.linalg.norm(a - b) / np.linalg.norm(b))


def _operands(T, H, D, dtype, seed=0):
    ks = jax.random.split(jax.random.key(seed), 4)
    return tuple(
        jax.random.normal(k, (1, T, H, D), jnp.float32).astype(dtype)
        for k in ks
    )


def _streaming(q, k, v, causal, window):
    """Today's kernels whatever the plan says: pad, transpose, stream."""
    scale = float(1.0 / np.sqrt(q.shape[-1]))
    qb, kb, vb, bq, bk, unpack = fa._prep_blocks(q, k, v, 128, 128)
    return unpack(fa._flash(qb, kb, vb, scale, causal, bq, bk, True, window))


MODES = {"causal": (True, None), "full": (False, None),
         "window": (True, 200), "narrow-window": (True, 48)}


def _resident(q, k, v, causal, window, block):
    """The resident kernels at a stated sub-block (the plan's own is one
    block at these lengths)."""
    B, T, H, D = q.shape
    view = lambda x: x.reshape(B, T, H * D)
    out = fa._flash_resident(view(q), view(k), view(v), D, 128, block,
                             float(1.0 / np.sqrt(D)), causal, window, True)
    return out.reshape(B, T, H, D)


@pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16],
                         ids=["f32", "bf16"])
@pytest.mark.parametrize("mode", sorted(MODES))
@pytest.mark.parametrize("T, block", [(512, 256), (384, 128)],
                         ids=["T512", "T384"])
@pytest.mark.parametrize("D", [64, 128], ids=["D64", "D128"])
def test_resident_matches_reference_and_streaming(D, T, block, mode, dtype):
    """T 512 walks two sub-blocks of 256, T 384 (no multiple of 256)
    three of 128; two heads of 64 share a lane block, a head of 128 has
    its own."""
    causal, window = MODES[mode]
    q, k, v, co = _operands(T, 2, D, dtype)

    def grads(fn):
        def loss(q, k, v):
            return jnp.sum(fn(q, k, v).astype(jnp.float32)
                           * co.astype(jnp.float32))
        return jax.grad(loss, argnums=(0, 1, 2))(q, k, v)

    resident = lambda q, k, v: _resident(q, k, v, causal, window, block)
    dense = lambda q, k, v: attention_reference(
        q, k, v, causal=causal, window=window)
    streaming = lambda q, k, v: _streaming(q, k, v, causal, window)

    # bf16: one rounding of the output / of each gradient
    tol = 1e-5 if dtype == jnp.float32 else 1e-2
    assert _rel(resident(q, k, v), dense(q, k, v)) < tol
    want = grads(dense)
    for got, ref in zip(grads(resident), want):
        assert got.dtype == dtype
        assert _rel(got, ref) < tol
    # the same arithmetic as the streaming kernels, the f32 sums over key
    # blocks in another order
    assert _rel(resident(q, k, v), streaming(q, k, v)) < tol / 2
    for got, ref in zip(grads(resident), grads(streaming)):
        assert _rel(got, ref) < tol / 2


@pytest.mark.parametrize("T", [512, 384, 128])
def test_the_public_wrapper_takes_the_plan(T):
    """``flash_attention`` at a resident shape: the plan's sub-block (the
    largest multiple of 128 up to ``_RESIDENT_BLOCK`` that divides T)."""
    W, block = fa._resident_plan(T, 2, 64, jnp.float32)
    assert W == 128 and T % block == 0 and block % 128 == 0
    assert block == max(b for b in range(128, fa._RESIDENT_BLOCK + 1, 128)
                        if T % b == 0)
    q, k, v, _ = _operands(T, 2, 64, jnp.float32, seed=1)
    got = fa.flash_attention(q, k, v, causal=True, interpret=True)
    assert _rel(got, _resident(q, k, v, True, None, block)) == 0.0
    assert _rel(got, attention_reference(q, k, v, causal=True)) < 1e-5


def test_four_heads_of_32_share_a_lane_block():
    q, k, v, _ = _operands(256, 4, 32, jnp.float32, seed=3)
    assert fa._resident_plan(256, 4, 32, jnp.float32) == (128, 256)
    got = fa.flash_attention(q, k, v, causal=True, interpret=True)
    assert _rel(got, attention_reference(q, k, v, causal=True)) < 1e-5


@pytest.mark.parametrize("T, H, D, why", [
    (1024, 12, 64, None),            # GPT-2: 4 MiB held
    (2048, 16, 128, None),           # 7 MiB
    (4096, 16, 256, "budget"),       # the Qwen3-Next layer: 26 MiB
    (8192, 8, 128, "budget"),
    (1024, 3, 64, "lanes"),          # 192 columns: no whole lane block
    (1024, 4, 96, "lanes"),
    (320, 2, 64, "rows"),            # no multiple of 128 divides T
])
def test_the_plan_is_read_off_the_shape(T, H, D, why):
    plan = fa._resident_plan(T, H, D, jnp.bfloat16)
    assert (plan is None) == (why is not None), (plan, why)


def _lowered_for_tpu(fn, *shapes):
    return jax.jit(fn).trace(*shapes).lower(
        lowering_platforms=("tpu",)).as_text()


def _kernels(text):
    """(kernel name, operand types, result types) of each Mosaic call."""
    calls = re.findall(
        r"stablehlo\.custom_call @tpu_custom_call\(.*?\) \{(.*?)\} : "
        r"\((.*?)\) -> (.*)", text)
    tensors = lambda s: re.findall(r"tensor<([^>]+)>", s)
    return [
        (re.search(r'kernel_name = "([^"]+)"', cfg).group(1),
         tensors(args), tensors(res))
        for cfg, args, res in calls
    ]


def _loss(window=None):
    def loss(q, k, v):
        scale = float(1.0 / np.sqrt(q.shape[-1]))
        out = fa._attend(q, k, v, scale, True, 256, 512, False, window)
        return out.astype(jnp.float32).sum()
    return jax.grad(loss, argnums=(0, 1, 2))


@pytest.mark.parametrize("shape, names", [
    ((2, 1024, 12, 64),
     ["flash_fwd_resident", "flash_bwd_dq_dkv_resident"]),
    ((1, 4096, 16, 256), ["flash_fwd", "flash_bwd_dq", "flash_bwd_dkv"]),
], ids=["gpt2", "qwen3-next"])
def test_which_schedule_a_shape_takes(shape, names):
    """GPT-2's attention is resident, forward and backward, in two
    kernels; the Qwen3-Next layer streams through today's three.  The
    names are what a profile shows and what ``flash_ms.*`` reads."""
    x = jax.ShapeDtypeStruct(shape, jnp.bfloat16)
    got = [name for name, _, _ in _kernels(_lowered_for_tpu(_loss(), x, x, x))]
    assert got == names
    scope = re.compile("flash_(fwd|bwd_dq|bwd_dkv)")  # flash_ms.tok / .hyb
    assert all(scope.search(n) for n in got)


def test_no_operand_is_padded_at_the_gpt2_shape():
    """The lowered gradient at (2, 1024, 12, 64): q, k, v, o, dO and the
    three gradients pass the kernels at their own width (the free view
    (B, T, H*D)), and the logsumexp kept for the backward is one f32 a
    row, under the 8 the issue allows."""
    B, T, H, D = 2, 1024, 12, 64
    x = jax.ShapeDtypeStruct((B, T, H, D), jnp.bfloat16)
    text = _lowered_for_tpu(_loss(), x, x, x)
    assert "stablehlo.pad" not in text and "stablehlo.transpose" not in text
    (_, fwd_in, fwd_out), (_, bwd_in, bwd_out) = _kernels(text)
    wide = f"{B}x{T}x{H * D}xbf16"
    assert fwd_in == [wide] * 3 and bwd_out == [wide] * 3
    assert fwd_out[0] == wide and bwd_in[:5] == [wide] * 5
    lse = fwd_out[1]
    assert bwd_in[5] == lse and lse.endswith("xf32")
    assert np.prod([int(n) for n in lse.split("x")[:-1]]) <= B * H * T * 8


def test_the_window_takes_the_schedule_of_its_shape():
    x = jax.ShapeDtypeStruct((2, 1024, 12, 64), jnp.bfloat16)
    names = [n for n, _, _ in _kernels(
        _lowered_for_tpu(_loss(window=256), x, x, x))]
    assert names == ["flash_fwd_resident", "flash_bwd_dq_dkv_resident"]


def _count(jaxpr, name, acc=0):
    for eqn in jaxpr.eqns:
        acc += eqn.primitive.name == name
        for val in eqn.params.values():
            for v in val if isinstance(val, (tuple, list)) else [val]:
                inner = getattr(v, "jaxpr", v)
                if hasattr(inner, "eqns"):
                    acc = _count(inner, name, acc)
    return acc


def test_one_pass_backward_makes_five_products_a_block_pair():
    """S, dP, dV, dK, dQ once a head and stretch of key blocks: with one
    head a lane block and a causal walk of two stretches (below the
    diagonal, on it) the backward kernel holds 10 ``dot_general``s and
    2 ``exp``s where the streaming pair holds 7 and 2 a block pair."""
    x = jnp.zeros((1, 512, 128), jnp.float32)
    res = fa._flash_resident_fwd(x, x, x, 128, 128, 256, 0.1, True, None,
                                 True)[1]
    jaxpr = jax.make_jaxpr(
        lambda do: fa._flash_resident_bwd(128, 128, 256, 0.1, True, None,
                                          True, res, do))(x).jaxpr
    assert _count(jaxpr, "pallas_call") == 1
    assert _count(jaxpr, "dot_general") == 10
    assert _count(jaxpr, "exp") == 2
