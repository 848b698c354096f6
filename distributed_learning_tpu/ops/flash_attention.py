"""Fused flash attention as Pallas TPU kernels — forward AND backward.

The single-device hot op behind the transformer path: O(T^2) attention
computed blockwise with the online-softmax recurrence, so neither the
(T, T) score matrix nor the full K/V ever sits in VMEM.  Grid =
(batch*heads, q-blocks, k-blocks): the innermost k dimension iterates
sequentially on a TPU core, so the (block_q, D) accumulator and the
running max/denominator live in VMEM scratch across k steps — initialized
at k==0, finalized into the output block at the last k.  K/V blocks
stream HBM->VMEM via the grid's implicit double-buffered DMA, matmuls hit
the MXU with f32 accumulation, and the causal path skips the compute for
fully-masked blocks.

Training works through the kernel: a ``jax.custom_vjp`` supplies the
standard recompute-based flash backward.  The forward additionally saves
the per-row logsumexp of the scaled scores — lane-replicated to shape
``(BH, T, 128)``, the layout the TPU Pallas lowering requires (the last
two block dims must tile to (8, 128); a ``(1, block_q)`` block does not
lower, as the real compiler taught this module the hard way).  The
backward recomputes each score block from (Q, K) on the MXU instead of
materializing the (T, T) probability matrix, and splits into two kernels
so every accumulator is a sequential reduction over its innermost grid
axis:

* dQ kernel  — grid (BH, q-blocks, k-blocks): for one Q block, walk K/V
  blocks accumulating dQ += scale * dS @ K with dS = P * (dP - delta),
  P = exp(S - lse), dP = dO @ V^T, delta = rowsum(dO * O)  (computed
  in-kernel from the O block — cheaper than materializing a (BH, T, 128)
  delta tensor in HBM).
* dK/dV kernel — grid (BH, k-blocks, q-blocks): for one K/V block, walk
  Q blocks accumulating dV += P^T @ dO and dK += scale * dS^T @ Q.

Head dims that do not fill a 128-lane tile are zero-padded to 128 before
the kernels and sliced after — scores and softmax are unchanged by zero
columns, and the pad/slice pair is differentiable, so the padding
composes with the custom VJP.

Context length is bounded by HBM, not VMEM.  On the chip the kernels'
time is the chip benchmark's ``flash_ms.tok`` / ``flash_ms.hyb``
(PERF.md section 5).  On CPU the
same kernels run under ``interpret=True`` for the
tests; correctness bar: values and gradients match
:func:`~distributed_learning_tpu.ops.ring_attention.attention_reference`.
"""

from __future__ import annotations

import functools
from typing import Optional

import jax
import jax.numpy as jnp
import numpy as np
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from distributed_learning_tpu.ops.ring_attention import attention_reference

__all__ = ["flash_attention", "flash_attention_with_lse"]

_NEG_INF = -1e30  # large-but-finite: exp(-1e30 - m) underflows to 0 cleanly
_LANES = 128  # native tile width: scratch vectors and lse are lane-replicated


def _sds(shape, dtype, like):
    """ShapeDtypeStruct matching ``like``'s varying-manual-axes: under
    ``shard_map`` (ring flash attention) pallas outputs must declare
    their vma or the shard_map vma check rejects the call; under plain
    jit the vma set is empty and this is an ordinary SDS."""
    vma = getattr(jax.typeof(like), "vma", None)
    if vma:
        return jax.ShapeDtypeStruct(shape, dtype, vma=vma)
    return jax.ShapeDtypeStruct(shape, dtype)


def _causal_live(qi, kj, block_q, block_k, window=None):
    """Whether block (qi, kj) holds any unmasked (row >= col) pair —
    and, with a sliding ``window``, any pair inside the band
    ``col >= row - window + 1``.  Blocks entirely below the band are as
    dead as blocks above the diagonal: skipping both is what turns the
    windowed kernel's cost from O(T^2) into O(T * window)."""
    live = kj * block_k <= (qi + 1) * block_q - 1
    if window is not None:
        # program ids are traced: combine with &, not `and`.
        live = live & ((kj + 1) * block_k - 1 >= qi * block_q - (window - 1))
    return live


def _masked_scores(q, k_blk, qi, kj, block_q, block_k, sm_scale, causal,
                   window=None):
    """Scaled (block_q, block_k) scores with causal masking applied.

    The Q@K^T matmul runs in the refs' native dtype (bf16 in the training
    path) with f32 accumulation — upcasting the inputs first would force
    an f32 MXU pass at a fraction of bf16 throughput (measured on v5e:
    the all-f32 variant of this kernel sustained 10.9 TFLOP/s vs 197
    peak).  ``sm_scale`` is applied to the f32 scores after the matmul,
    which also preserves more precision than scaling bf16 queries."""
    s = jax.lax.dot_general(
        q, k_blk,
        dimension_numbers=(((1,), (1,)), ((), ())),
        preferred_element_type=jnp.float32,
    ) * sm_scale
    if causal:
        rows = qi * block_q + jax.lax.broadcasted_iota(
            jnp.int32, (block_q, block_k), 0
        )
        cols = kj * block_k + jax.lax.broadcasted_iota(
            jnp.int32, (block_q, block_k), 1
        )
        keep = cols <= rows
        if window is not None:
            keep &= cols >= rows - (window - 1)
        s = jnp.where(keep, s, _NEG_INF)
    return s


def _flash_kernel(
    q_ref, k_ref, v_ref, o_ref, lse_ref, acc_ref, m_ref, l_ref,
    *, sm_scale, causal, window=None,
):
    """One (bh, qi, kj) grid step of the online-softmax recurrence."""
    qi, kj = pl.program_id(1), pl.program_id(2)
    block_q = q_ref.shape[1]
    block_k = k_ref.shape[1]
    nk = pl.num_programs(2)

    @pl.when(kj == 0)
    def _init():
        acc_ref[...] = jnp.zeros_like(acc_ref)
        m_ref[...] = jnp.full_like(m_ref, _NEG_INF)
        l_ref[...] = jnp.zeros_like(l_ref)

    # Causal: blocks whose first key is beyond this q block's last query
    # are fully masked — skip their FLOPs entirely.
    live = (_causal_live(qi, kj, block_q, block_k, window)
            if causal else True)

    @pl.when(live)
    def _step():
        s = _masked_scores(
            q_ref[0], k_ref[0], qi, kj, block_q, block_k, sm_scale, causal,
            window,
        )
        m_prev = m_ref[:, :1]  # lane-replicated; any lane is the value
        l_prev = l_ref[:, :1]
        m_cur = jnp.max(s, axis=-1, keepdims=True)
        m_next = jnp.maximum(m_prev, m_cur)
        corr = jnp.exp(m_prev - m_next)
        p = jnp.exp(s - m_next)
        l_next = l_prev * corr + jnp.sum(p, axis=-1, keepdims=True)
        # l is summed from the f32 probabilities above; only the matmul
        # operand drops to V's dtype, so the normalizer stays exact while
        # P@V hits the MXU at native-dtype rate (identity cast for f32 V).
        pv = jax.lax.dot_general(
            p.astype(v_ref.dtype), v_ref[0],
            dimension_numbers=(((1,), (0,)), ((), ())),
            preferred_element_type=jnp.float32,
        )
        acc_ref[...] = acc_ref[...] * corr + pv
        m_ref[...] = jnp.broadcast_to(m_next, m_ref.shape)
        l_ref[...] = jnp.broadcast_to(l_next, l_ref.shape)

    @pl.when(kj == nk - 1)
    def _finalize():
        l = jnp.maximum(l_ref[...], 1e-30)
        o_ref[0] = (acc_ref[...] / l[:, :1]).astype(o_ref.dtype)
        if lse_ref is not None:
            # Per-row logsumexp of the SCALED scores — the backward's
            # softmax normalizer, so P is recomputed without a second
            # online pass.  Lane-replicated (block_q, 128): pure
            # elementwise on the already-replicated m/l scratch, which the
            # Mosaic lowering takes.  The primal (inference) path omits
            # this output entirely rather than write-and-discard it.
            lse_ref[0] = m_ref[...] + jnp.log(l)


def _flash_dq_kernel(
    q_ref, k_ref, v_ref, o_ref, do_ref, lse_ref, dadj_ref, dq_ref, dq_acc,
    *, sm_scale, causal, window=None,
):  # dadj_ref is None on the plain path (no lse consumer): zero term.
    """dQ for one Q block: sequential accumulation over K/V blocks.

    ``dadj`` is a per-row additive adjustment to the softmax backward:
    ``dS = P * (dP - delta + dadj)``.  Zero for plain attention; the lse
    cotangent when the caller consumes the logsumexp output too (ring
    flash attention combines blocks through their lse, so d loss/d lse
    is generally nonzero — the math folds it into exactly this term).
    """
    qi, kj = pl.program_id(1), pl.program_id(2)
    block_q = q_ref.shape[1]
    block_k = k_ref.shape[1]
    nk = pl.num_programs(2)

    @pl.when(kj == 0)
    def _init():
        dq_acc[...] = jnp.zeros_like(dq_acc)

    live = (_causal_live(qi, kj, block_q, block_k, window)
            if causal else True)

    @pl.when(live)
    def _step():
        s = _masked_scores(
            q_ref[0], k_ref[0], qi, kj, block_q, block_k, sm_scale, causal,
            window,
        )
        p = jnp.exp(s - lse_ref[0][:, :1])  # (bq, bk); masked entries -> 0
        # Matmuls run on native-dtype operands with f32 accumulation (see
        # _masked_scores); delta's (bq, D) multiply-reduce stays f32 on
        # the VPU — noise next to the two MXU matmuls.
        delta = jnp.sum(
            do_ref[0].astype(jnp.float32) * o_ref[0].astype(jnp.float32),
            axis=-1, keepdims=True,
        )
        dp = jax.lax.dot_general(  # dO @ V^T -> (bq, bk)
            do_ref[0], v_ref[0],
            dimension_numbers=(((1,), (1,)), ((), ())),
            preferred_element_type=jnp.float32,
        )
        adj = 0.0 if dadj_ref is None else dadj_ref[0][:, :1]
        ds = p * (dp - delta + adj)
        dq_acc[...] += sm_scale * jax.lax.dot_general(  # dS @ K -> (bq, D)
            ds.astype(k_ref.dtype), k_ref[0],
            dimension_numbers=(((1,), (0,)), ((), ())),
            preferred_element_type=jnp.float32,
        )

    @pl.when(kj == nk - 1)
    def _finalize():
        dq_ref[0] = dq_acc[...].astype(dq_ref.dtype)


def _flash_dkv_kernel(
    q_ref, k_ref, v_ref, o_ref, do_ref, lse_ref, dadj_ref, dk_ref, dv_ref,
    dk_acc, dv_acc, *, sm_scale, causal, window=None,
):
    """dK and dV for one K/V block: sequential accumulation over Q blocks.
    ``dadj`` as in :func:`_flash_dq_kernel`."""
    kj, qi = pl.program_id(1), pl.program_id(2)
    block_q = q_ref.shape[1]
    block_k = k_ref.shape[1]
    nq = pl.num_programs(2)

    @pl.when(qi == 0)
    def _init():
        dk_acc[...] = jnp.zeros_like(dk_acc)
        dv_acc[...] = jnp.zeros_like(dv_acc)

    live = (_causal_live(qi, kj, block_q, block_k, window)
            if causal else True)

    @pl.when(live)
    def _step():
        q_blk = q_ref[0]
        s = _masked_scores(
            q_blk, k_ref[0], qi, kj, block_q, block_k, sm_scale, causal,
            window,
        )
        p = jnp.exp(s - lse_ref[0][:, :1])  # (bq, bk)
        delta = jnp.sum(
            do_ref[0].astype(jnp.float32) * o_ref[0].astype(jnp.float32),
            axis=-1, keepdims=True,
        )
        dv_acc[...] += jax.lax.dot_general(  # P^T @ dO -> (bk, D)
            p.astype(do_ref.dtype), do_ref[0],
            dimension_numbers=(((0,), (0,)), ((), ())),
            preferred_element_type=jnp.float32,
        )
        dp = jax.lax.dot_general(  # dO @ V^T -> (bq, bk)
            do_ref[0], v_ref[0],
            dimension_numbers=(((1,), (1,)), ((), ())),
            preferred_element_type=jnp.float32,
        )
        adj = 0.0 if dadj_ref is None else dadj_ref[0][:, :1]
        ds = p * (dp - delta + adj)
        dk_acc[...] += sm_scale * jax.lax.dot_general(  # dS^T @ Q -> (bk, D)
            ds.astype(q_blk.dtype), q_blk,
            dimension_numbers=(((0,), (0,)), ((), ())),
            preferred_element_type=jnp.float32,
        )

    @pl.when(qi == nq - 1)
    def _finalize():
        dk_ref[0] = dk_acc[...].astype(dk_ref.dtype)
        dv_ref[0] = dv_acc[...].astype(dv_ref.dtype)


def _fwd_call(qb, kb, vb, sm_scale, causal, block_q, block_k, interpret,
              *, with_lse, window=None):
    """Forward pallas_call; ``with_lse=False`` (the inference/primal path)
    omits the lse output entirely so forward-only callers don't pay a
    (BH, T, 128) f32 HBM write they would immediately discard."""
    BH, T, D = qb.shape
    if with_lse:
        kernel = functools.partial(
            _flash_kernel, sm_scale=sm_scale, causal=causal, window=window
        )
    else:
        def kernel(q_ref, k_ref, v_ref, o_ref, acc_ref, m_ref, l_ref):
            _flash_kernel(q_ref, k_ref, v_ref, o_ref, None, acc_ref, m_ref,
                          l_ref, sm_scale=sm_scale, causal=causal,
                          window=window)
    o_spec = pl.BlockSpec((1, block_q, D), lambda bh, qi, kj: (bh, qi, 0))
    lse_spec = pl.BlockSpec(
        (1, block_q, _LANES), lambda bh, qi, kj: (bh, qi, 0)
    )
    o_shape = _sds((BH, T, D), qb.dtype, qb)
    lse_shape = _sds((BH, T, _LANES), jnp.float32, qb)
    return pl.pallas_call(
        kernel,
        grid=(BH, T // block_q, T // block_k),
        in_specs=[
            pl.BlockSpec((1, block_q, D), lambda bh, qi, kj: (bh, qi, 0)),
            pl.BlockSpec((1, block_k, D), lambda bh, qi, kj: (bh, kj, 0)),
            pl.BlockSpec((1, block_k, D), lambda bh, qi, kj: (bh, kj, 0)),
        ],
        out_specs=[o_spec, lse_spec] if with_lse else o_spec,
        out_shape=[o_shape, lse_shape] if with_lse else o_shape,
        scratch_shapes=[
            pltpu.VMEM((block_q, D), jnp.float32),
            pltpu.VMEM((block_q, _LANES), jnp.float32),
            pltpu.VMEM((block_q, _LANES), jnp.float32),
        ],
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "parallel", "arbitrary"),
        ),
        interpret=interpret,
        name="flash_fwd",
    )(qb, kb, vb)


def _bwd_call(qb, kb, vb, out, do, lse, dadj, sm_scale, causal, block_q,
              block_k, interpret, window=None):
    """The two backward pallas_calls, shared by both custom VJPs.

    ``dadj=None`` (the plain path — no lse consumer) omits the extra
    kernel input entirely instead of streaming a known-zero tensor
    through both kernels' grids."""
    BH, T, D = qb.shape
    lse_spec_q = pl.BlockSpec(
        (1, block_q, _LANES), lambda bh, qi, kj: (bh, qi, 0)
    )
    lse_spec_kv = pl.BlockSpec(
        (1, block_q, _LANES), lambda bh, kj, qi: (bh, qi, 0)
    )
    extra = [] if dadj is None else [dadj]

    dq_kernel = functools.partial(
        _flash_dq_kernel, sm_scale=sm_scale, causal=causal, window=window
    )
    if dadj is None:
        def dq_kernel(q, k, v, o, do_, lse_, dq_, acc):
            _flash_dq_kernel(q, k, v, o, do_, lse_, None, dq_, acc,
                             sm_scale=sm_scale, causal=causal,
                             window=window)
    dq = pl.pallas_call(
        dq_kernel,
        grid=(BH, T // block_q, T // block_k),
        in_specs=[
            pl.BlockSpec((1, block_q, D), lambda bh, qi, kj: (bh, qi, 0)),
            pl.BlockSpec((1, block_k, D), lambda bh, qi, kj: (bh, kj, 0)),
            pl.BlockSpec((1, block_k, D), lambda bh, qi, kj: (bh, kj, 0)),
            pl.BlockSpec((1, block_q, D), lambda bh, qi, kj: (bh, qi, 0)),
            pl.BlockSpec((1, block_q, D), lambda bh, qi, kj: (bh, qi, 0)),
            lse_spec_q,
        ] + ([] if dadj is None else [lse_spec_q]),
        out_specs=pl.BlockSpec((1, block_q, D), lambda bh, qi, kj: (bh, qi, 0)),
        out_shape=_sds((BH, T, D), qb.dtype, qb),
        scratch_shapes=[pltpu.VMEM((block_q, D), jnp.float32)],
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "parallel", "arbitrary"),
        ),
        interpret=interpret,
        name="flash_bwd_dq",
    )(qb, kb, vb, out, do, lse, *extra)

    dkv_kernel = functools.partial(
        _flash_dkv_kernel, sm_scale=sm_scale, causal=causal, window=window
    )
    if dadj is None:
        def dkv_kernel(q, k, v, o, do_, lse_, dk_, dv_, ka, va):
            _flash_dkv_kernel(q, k, v, o, do_, lse_, None, dk_, dv_, ka, va,
                              sm_scale=sm_scale, causal=causal,
                              window=window)
    dk, dv = pl.pallas_call(
        dkv_kernel,
        grid=(BH, T // block_k, T // block_q),
        in_specs=[
            pl.BlockSpec((1, block_q, D), lambda bh, kj, qi: (bh, qi, 0)),
            pl.BlockSpec((1, block_k, D), lambda bh, kj, qi: (bh, kj, 0)),
            pl.BlockSpec((1, block_k, D), lambda bh, kj, qi: (bh, kj, 0)),
            pl.BlockSpec((1, block_q, D), lambda bh, kj, qi: (bh, qi, 0)),
            pl.BlockSpec((1, block_q, D), lambda bh, kj, qi: (bh, qi, 0)),
            lse_spec_kv,
        ] + ([] if dadj is None else [lse_spec_kv]),
        out_specs=[
            pl.BlockSpec((1, block_k, D), lambda bh, kj, qi: (bh, kj, 0)),
            pl.BlockSpec((1, block_k, D), lambda bh, kj, qi: (bh, kj, 0)),
        ],
        out_shape=[
            _sds((BH, T, D), kb.dtype, qb),
            _sds((BH, T, D), vb.dtype, qb),
        ],
        scratch_shapes=[
            pltpu.VMEM((block_k, D), jnp.float32),
            pltpu.VMEM((block_k, D), jnp.float32),
        ],
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "parallel", "arbitrary"),
        ),
        interpret=interpret,
        name="flash_bwd_dkv",
    )(qb, kb, vb, out, do, lse, *extra)

    return dq, dk, dv


@functools.partial(jax.custom_vjp, nondiff_argnums=(3, 4, 5, 6, 7, 8))
def _flash(qb, kb, vb, sm_scale, causal, block_q, block_k, interpret,
           window):
    return _fwd_call(qb, kb, vb, sm_scale, causal, block_q, block_k,
                     interpret, with_lse=False, window=window)


def _flash_fwd(qb, kb, vb, sm_scale, causal, block_q, block_k, interpret,
               window):
    out, lse = _fwd_call(qb, kb, vb, sm_scale, causal, block_q, block_k,
                         interpret, with_lse=True, window=window)
    return out, (qb, kb, vb, out, lse)


def _flash_bwd(sm_scale, causal, block_q, block_k, interpret, window, res,
               do):
    qb, kb, vb, out, lse = res
    # dadj=None: no lse consumer, so the kernels omit the input entirely
    # instead of streaming a known-zero tensor through both grids.
    return _bwd_call(qb, kb, vb, out, do, lse, None, sm_scale, causal,
                     block_q, block_k, interpret, window=window)


_flash.defvjp(_flash_fwd, _flash_bwd)


@functools.partial(jax.custom_vjp, nondiff_argnums=(3, 4, 5, 6, 7))
def _flash_lse(qb, kb, vb, sm_scale, causal, block_q, block_k, interpret):
    """Like :func:`_flash` but also returns the per-row logsumexp
    (lane-replicated (BH, T, 128) f32) — the building block for ring
    flash attention, whose cross-block combine differentiates through
    lse.  d lse/d s_rc = p_rc, which folds into the shared backward as
    the ``dadj`` row term."""
    return _fwd_call(qb, kb, vb, sm_scale, causal, block_q, block_k,
                     interpret, with_lse=True)


def _flash_lse_fwd(qb, kb, vb, sm_scale, causal, block_q, block_k, interpret):
    out, lse = _fwd_call(qb, kb, vb, sm_scale, causal, block_q, block_k,
                         interpret, with_lse=True)
    return (out, lse), (qb, kb, vb, out, lse)


def _flash_lse_bwd(sm_scale, causal, block_q, block_k, interpret, res, cts):
    qb, kb, vb, out, lse = res
    do, dlse = cts
    # The primal lse is lane-replicated: the true per-row cotangent is the
    # SUM over lanes of the replicated output's cotangents (a consumer
    # that only read lane 0 leaves the rest zero — summing is exact
    # either way).  Re-broadcast so the kernel can read any lane.
    dadj = jnp.broadcast_to(
        jnp.sum(dlse, axis=-1, keepdims=True), dlse.shape
    )
    return _bwd_call(qb, kb, vb, out, do, lse, dadj, sm_scale, causal,
                     block_q, block_k, interpret)


_flash_lse.defvjp(_flash_lse_fwd, _flash_lse_bwd)



def _prep_blocks(q, k, v, block_q, block_k):
    """Shared wrapper preprocessing: clamp block sizes to T (callers
    must forward the returned sizes to the kernel), validate
    divisibility, pad the head dim to the 128-lane grid, and flatten
    (B, T, H, D) -> (B*H, T, Dp).  Returns (qb, kb, vb, block_q,
    block_k, unpack) where ``unpack`` restores a (B*H, T, Dp) result to
    (B, T, H, D) and slices off the head-dim padding."""
    B, T, H, D = q.shape

    def _fit(request: int) -> int:
        # Largest block <= request that divides T, preferring 8-aligned
        # (the TPU sublane tile) — so the measured-best large defaults
        # degrade gracefully for any T instead of raising (same policy
        # as ring_flash_attention's fit_block).
        b = min(request, T)
        aligned = next(
            (c for c in range(b, 7, -1) if T % c == 0 and c % 8 == 0),
            None,
        )
        if aligned is not None:
            return aligned
        while T % b:
            b -= 1
        return b

    block_q = _fit(block_q)
    block_k = _fit(block_k)
    if jax.devices()[0].platform == "tpu" and T % 8:
        # Unaligned T cannot produce 8-aligned blocks; fail with a clear
        # message instead of a Mosaic lowering error.
        raise ValueError(
            f"flash_attention on TPU needs T divisible by 8, got {T}; "
            "pad the sequence or use attention_reference"
        )
    # The TPU lowering tiles the last two block dims to (8, 128): pad the
    # head dim up to a lane multiple.  Zero K/Q columns leave every score
    # unchanged; zero V columns produce zero output columns, sliced off.
    Dp = max(_LANES, -(-D // _LANES) * _LANES)
    if Dp != D:
        pad = [(0, 0), (0, 0), (0, 0), (0, Dp - D)]
        q, k, v = (jnp.pad(x, pad) for x in (q, k, v))
    to_bh = lambda x: x.transpose(0, 2, 1, 3).reshape(B * H, T, Dp)

    def unpack(out):
        out = out.reshape(B, H, T, Dp).transpose(0, 2, 1, 3)
        return out[..., :D] if Dp != D else out

    return to_bh(q), to_bh(k), to_bh(v), block_q, block_k, unpack


@functools.partial(
    jax.jit,
    static_argnames=(
        "causal", "sm_scale", "block_q", "block_k", "interpret", "window"
    ),
)
def flash_attention(
    q: jax.Array,
    k: jax.Array,
    v: jax.Array,
    *,
    causal: bool = True,
    sm_scale: Optional[float] = None,
    block_q: int = 256,
    block_k: int = 512,
    interpret: bool = False,
    window: Optional[int] = None,
) -> jax.Array:
    """Fused attention on (B, T, H, D); T must divide by the block sizes.

    Differentiable: gradients run through the Pallas backward kernels
    (``jax.custom_vjp``), so the transformer's ``attention="flash"`` mode
    trains on TPU.  Head dims off the 128-lane grid are zero-padded
    through the kernels and sliced back.  Off-TPU without ``interpret``
    this falls back to the reference einsum/softmax path (XLA fuses it
    well enough on CPU; the kernel is the TPU fast path).

    Default blocks (256, 512) fit the VMEM budget of
    ``docs/flash_roofline.md``; their rate against other block shapes on
    the chip is not measured.  For any T they degrade to
    the largest 8-aligned blocks that divide T, so every previously
    valid sequence length keeps working.

    ``window`` (requires ``causal``) is sliding-window attention: row
    ``r`` attends to keys ``[r - window + 1, r]``.  Blocks entirely
    outside the band are skipped in the forward AND both backward
    kernels, so cost scales O(T * window) instead of O(T^2) — the
    standard long-context local-attention trade (Mistral-style).
    """
    D = q.shape[-1]
    scale = sm_scale if sm_scale is not None else float(1.0 / np.sqrt(D))
    if window is not None:
        if not causal:
            raise ValueError("window requires causal=True")
        if window < 1:
            raise ValueError(f"window must be >= 1, got {window}")
    on_tpu = jax.devices()[0].platform == "tpu"
    if not on_tpu and not interpret:
        return attention_reference(q, k, v, causal=causal, sm_scale=scale,
                                   window=window)
    qb, kb, vb, block_q, block_k, unpack = _prep_blocks(
        q, k, v, block_q, block_k
    )
    return unpack(
        _flash(qb, kb, vb, scale, causal, block_q, block_k, interpret,
               window)
    )


@functools.partial(
    jax.jit, static_argnames=("causal", "sm_scale", "block_q", "block_k", "interpret")
)
def flash_attention_with_lse(
    q: jax.Array,
    k: jax.Array,
    v: jax.Array,
    *,
    causal: bool = True,
    sm_scale: Optional[float] = None,
    block_q: int = 256,
    block_k: int = 512,
    interpret: bool = False,
) -> tuple[jax.Array, jax.Array]:
    """Like :func:`flash_attention` but also returns the per-row
    logsumexp of the scaled scores, shape (B, H, T) f32 — the quantity
    that lets independent attention pieces be combined exactly
    (``ops.ring_attention.ring_flash_attention`` merges per-device block
    results through it).  Fully differentiable: the lse cotangent folds
    into the backward kernels' ``dadj`` row term.

    Off-TPU without ``interpret`` this computes the reference path plus a
    JAX logsumexp — same semantics, XLA-fused, differentiable.
    """
    B, T, H, D = q.shape
    scale = sm_scale if sm_scale is not None else float(1.0 / np.sqrt(D))
    on_tpu = jax.devices()[0].platform == "tpu"
    if not on_tpu and not interpret:
        # One O(T^2) score tensor feeds both outputs (attention_reference
        # would compute the same scores a second time).
        s = jnp.einsum("bqhd,bkhd->bhqk", q, k).astype(jnp.float32) * scale
        if causal:
            mask = jnp.tril(jnp.ones((T, T), bool))
            s = jnp.where(mask[None, None], s, -jnp.inf)
        probs = jax.nn.softmax(s, axis=-1)
        out = jnp.einsum("bhqk,bkhd->bqhd", probs.astype(v.dtype), v)
        lse = jax.scipy.special.logsumexp(s, axis=-1)  # (B, H, T)
        return out, lse
    qb, kb, vb, block_q, block_k, unpack = _prep_blocks(
        q, k, v, block_q, block_k
    )
    out, lse = _flash_lse(
        qb, kb, vb, scale, causal, block_q, block_k, interpret
    )
    return unpack(out), lse[:, :, 0].reshape(B, H, T)
