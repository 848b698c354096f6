"""Native-dtype MXU contract for the flash kernels.

The kernels take native-dtype operands with f32 accumulation
(``ops/flash_attention.py::_masked_scores``) instead of f32-upcast
matmuls, which run at a fraction of the MXU's bf16 rate (the chip
number: not measured).  The PROGRAM
property is checkable anywhere: trace the kernels in interpret mode
(the pallas bodies inline into the jaxpr) and assert every
``dot_general`` in forward AND both backward kernels takes bf16
operands with ``preferred_element_type=float32``.  An accidental
upcast (``.astype(f32)`` before a dot) fails this immediately."""

import jax
import jax.numpy as jnp
import pytest

from distributed_learning_tpu.ops.flash_attention import flash_attention

B, T, H, D = 1, 256, 2, 64


def _walk_dots(jaxpr, acc):
    """Collect (operand dtypes, preferred_element_type) for every
    dot_general, descending into call/scan/cond/pjit sub-jaxprs."""
    for eqn in jaxpr.eqns:
        if eqn.primitive.name == "dot_general":
            acc.append((
                tuple(str(x.aval.dtype) for x in eqn.invars),
                str(eqn.params.get("preferred_element_type")),
            ))
        for val in eqn.params.values():
            vals = val if isinstance(val, (tuple, list)) else [val]
            for v2 in vals:
                inner = getattr(v2, "jaxpr", None)
                if inner is not None and hasattr(inner, "eqns"):
                    _walk_dots(inner, acc)
                elif hasattr(v2, "eqns"):
                    _walk_dots(v2, acc)
    return acc


@pytest.fixture(scope="module")
def qkv():
    x = jnp.zeros((B, T, H, D), jnp.bfloat16)
    return x, x, x


def test_forward_dots_native_bf16(qkv):
    q, k, v = qkv
    jx = jax.make_jaxpr(
        lambda q, k, v: flash_attention(q, k, v, interpret=True)
    )(q, k, v)
    dots = _walk_dots(jx.jaxpr, [])
    # Q@K^T and P@V per grid step.
    assert len(dots) >= 2, dots
    for operands, pref in dots:
        assert operands == ("bfloat16", "bfloat16"), dots
        assert pref == "float32", dots


def test_backward_dots_native_bf16(qkv):
    q, k, v = qkv
    jg = jax.make_jaxpr(jax.grad(
        lambda q, k, v: flash_attention(
            q, k, v, interpret=True
        ).astype(jnp.float32).sum(),
        argnums=(0, 1, 2),
    ))(q, k, v)
    dots = _walk_dots(jg.jaxpr, [])
    # dQ kernel: S, dP, dQ accumulation; dK/dV kernel: S^T, dV, dK (plus
    # the recomputes) — 9 dots at HEAD; >= 6 guards against refactors
    # that fuse some.
    assert len(dots) >= 6, dots
    for operands, pref in dots:
        assert operands == ("bfloat16", "bfloat16"), dots
        assert pref == "float32", dots


def test_f32_inputs_stay_f32(qkv):
    """The identity-cast path: f32 inputs must not be demoted."""
    q = jnp.zeros((B, T, H, D), jnp.float32)
    jx = jax.make_jaxpr(
        lambda q, k, v: flash_attention(q, k, v, interpret=True)
    )(q, q, q)
    dots = _walk_dots(jx.jaxpr, [])
    assert len(dots) >= 2
    for operands, _ in dots:
        assert operands == ("float32", "float32"), dots
