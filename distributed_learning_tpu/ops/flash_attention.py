"""Fused flash attention as Pallas TPU kernels — forward AND backward.

The single-device hot op behind the transformer path: O(T^2) attention
computed blockwise with the online-softmax recurrence, so the (T, T)
score matrix never exists.  One algorithm, two block schedules, chosen
at trace time from the shape alone (:func:`_resident_plan`):

* **resident** — a head's whole K and V (and, backward, their f32
  gradient accumulators) fit a VMEM budget, as at GPT-2's T 1,024 x
  head size 64.  Grid (batch, lane blocks, q-blocks): K/V are fetched
  once a head, the walk over key sub-blocks is a loop inside the kernel
  with the causal (or window) trip count, the mask runs only on the
  sub-blocks the diagonal or the window's edge crosses, and the
  backward is ONE kernel.  Operands are the free view (B, T, H*D); a
  128-lane block holds 128 // D heads side by side, each head's
  products masked by lane, so nothing is padded or transposed in HBM.
  Kernel names ``flash_fwd_resident``, ``flash_bwd_dq_dkv_resident``
  (the section further down).
* **streaming** — every other shape (long sequences, the Qwen3-Next
  layer's T 4,096 x head size 256, heads that do not tile 128 lanes,
  and a query/key width that differs from the value's: latent
  attention's 192 against 128, which go through unpadded), and
  ``flash_attention_with_lse`` (ring flash attention) always.  Grid
  = (batch*heads, q-blocks, k-blocks): the innermost k dimension
  iterates sequentially on a TPU core, so the (block_q, D) accumulator
  and the running max/denominator live in VMEM scratch across k steps —
  initialized at k==0, finalized into the output block at the last k.
  K/V blocks stream HBM->VMEM via the grid's implicit double-buffered
  DMA and the causal path skips the compute for fully-masked blocks.
  Kernel names ``flash_fwd``, ``flash_bwd_dq``, ``flash_bwd_dkv``.

Both run their matmuls on the MXU in the operands' own dtype with f32
accumulation and keep f32 softmax statistics; the kernel bodies share
:func:`_masked_scores`, :func:`_online_softmax` and
:func:`_softmax_grad`.

Training works through the kernels: a ``jax.custom_vjp`` supplies the
standard recompute-based flash backward.  The forward additionally saves
the per-row logsumexp of the scaled scores — in the streaming schedule
lane-replicated to shape ``(BH, T, 128)`` (the last two block dims must
tile to (8, 128); a ``(1, block_q)`` block does not lower), in the
resident one as rows along lanes, one f32 a row.  The backward
recomputes each score block from (Q, K) on the MXU instead of
materializing the (T, T) probability matrix.  Streaming, it splits into
two kernels so every accumulator is a sequential reduction over its
innermost grid axis:

* dQ kernel  — grid (BH, q-blocks, k-blocks): for one Q block, walk K/V
  blocks accumulating dQ += scale * dS @ K with dS = P * (dP - delta),
  P = exp(S - lse), dP = dO @ V^T, delta = rowsum(dO * O)  (computed
  in-kernel from the O block — cheaper than materializing a (BH, T, 128)
  delta tensor in HBM).
* dK/dV kernel — grid (BH, k-blocks, q-blocks): for one K/V block, walk
  Q blocks accumulating dV += P^T @ dO and dK += scale * dS^T @ Q.

Streaming, head dims that do not fill a 128-lane tile (q, k and v of one
width) are zero-padded to
128 before the kernels and sliced after — scores and softmax are
unchanged by zero columns, and the pad/slice pair is differentiable, so
the padding composes with the custom VJP.

Context length is bounded by HBM, not VMEM.  On the chip the kernels'
time is the chip benchmark's ``flash_ms.tok`` / ``flash_ms.hyb``
(PERF.md section 5; the byte counts and what was measured:
``docs/flash_roofline.md``).  On CPU the same kernels run under
``interpret=True`` for the tests; correctness bar: values and gradients
match
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


def _online_softmax(s, m_prev, l_prev, acc_prev, v_blk):
    """One key block of the online-softmax recurrence: the running max
    ``m``, denominator ``l`` (both (block_q, 1) f32) and the (block_q, D)
    f32 accumulator after the scores ``s`` of this block."""
    m_cur = jnp.max(s, axis=-1, keepdims=True)
    m_next = jnp.maximum(m_prev, m_cur)
    corr = jnp.exp(m_prev - m_next)
    p = jnp.exp(s - m_next)
    l_next = l_prev * corr + jnp.sum(p, axis=-1, keepdims=True)
    # l is summed from the f32 probabilities above; only the matmul
    # operand drops to V's dtype, so the normalizer stays exact while
    # P@V hits the MXU at native-dtype rate (identity cast for f32 V).
    pv = jax.lax.dot_general(
        p.astype(v_blk.dtype), v_blk,
        dimension_numbers=(((1,), (0,)), ((), ())),
        preferred_element_type=jnp.float32,
    )
    return m_next, l_next, acc_prev * corr + pv


def _softmax_grad(s, lse, do, v_blk, delta, adj=0.0):
    """``(P, dS)`` of one score block from the saved logsumexp:
    ``P = exp(S - lse)``, ``dP = dO @ V^T``, ``dS = P * (dP - delta +
    adj)``; ``lse``, ``delta`` and ``adj`` are (block_q, 1) columns.
    Matmuls run on native-dtype operands with f32 accumulation (see
    :func:`_masked_scores`)."""
    p = jnp.exp(s - lse)  # (bq, bk); masked entries -> 0
    dp = jax.lax.dot_general(  # dO @ V^T -> (bq, bk)
        do, v_blk,
        dimension_numbers=(((1,), (1,)), ((), ())),
        preferred_element_type=jnp.float32,
    )
    return p, p * (dp - delta + adj)


def _row_delta(do, o):
    """``rowsum(dO * O)`` in f32 on the VPU, noise next to the MXU
    matmuls: (block_q, D) -> (block_q, 1)."""
    return jnp.sum(do.astype(jnp.float32) * o.astype(jnp.float32),
                   axis=-1, keepdims=True)


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
        # m and l are lane-replicated; any lane is the value
        m_next, l_next, acc_ref[...] = _online_softmax(
            s, m_ref[:, :1], l_ref[:, :1], acc_ref[...], v_ref[0]
        )
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
        adj = 0.0 if dadj_ref is None else dadj_ref[0][:, :1]
        _, ds = _softmax_grad(
            s, lse_ref[0][:, :1], do_ref[0], v_ref[0],
            _row_delta(do_ref[0], o_ref[0]), adj,
        )
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
        adj = 0.0 if dadj_ref is None else dadj_ref[0][:, :1]
        p, ds = _softmax_grad(
            s, lse_ref[0][:, :1], do_ref[0], v_ref[0],
            _row_delta(do_ref[0], o_ref[0]), adj,
        )
        dv_acc[...] += jax.lax.dot_general(  # P^T @ dO -> (bk, D)
            p.astype(do_ref.dtype), do_ref[0],
            dimension_numbers=(((0,), (0,)), ((), ())),
            preferred_element_type=jnp.float32,
        )
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
    Dv = vb.shape[-1]  # the value's (and the output's) own width
    if with_lse:
        kernel = functools.partial(
            _flash_kernel, sm_scale=sm_scale, causal=causal, window=window
        )
    else:
        def kernel(q_ref, k_ref, v_ref, o_ref, acc_ref, m_ref, l_ref):
            _flash_kernel(q_ref, k_ref, v_ref, o_ref, None, acc_ref, m_ref,
                          l_ref, sm_scale=sm_scale, causal=causal,
                          window=window)
    o_spec = pl.BlockSpec((1, block_q, Dv), lambda bh, qi, kj: (bh, qi, 0))
    lse_spec = pl.BlockSpec(
        (1, block_q, _LANES), lambda bh, qi, kj: (bh, qi, 0)
    )
    o_shape = _sds((BH, T, Dv), qb.dtype, qb)
    lse_shape = _sds((BH, T, _LANES), jnp.float32, qb)
    return pl.pallas_call(
        kernel,
        grid=(BH, T // block_q, T // block_k),
        in_specs=[
            pl.BlockSpec((1, block_q, D), lambda bh, qi, kj: (bh, qi, 0)),
            pl.BlockSpec((1, block_k, D), lambda bh, qi, kj: (bh, kj, 0)),
            pl.BlockSpec((1, block_k, Dv), lambda bh, qi, kj: (bh, kj, 0)),
        ],
        out_specs=[o_spec, lse_spec] if with_lse else o_spec,
        out_shape=[o_shape, lse_shape] if with_lse else o_shape,
        scratch_shapes=[
            pltpu.VMEM((block_q, Dv), jnp.float32),
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
    Dv = vb.shape[-1]  # v, o, dO and dV; q, k, dQ and dK are D wide
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
            pl.BlockSpec((1, block_k, Dv), lambda bh, qi, kj: (bh, kj, 0)),
            pl.BlockSpec((1, block_q, Dv), lambda bh, qi, kj: (bh, qi, 0)),
            pl.BlockSpec((1, block_q, Dv), lambda bh, qi, kj: (bh, qi, 0)),
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
            pl.BlockSpec((1, block_k, Dv), lambda bh, kj, qi: (bh, kj, 0)),
            pl.BlockSpec((1, block_q, Dv), lambda bh, kj, qi: (bh, qi, 0)),
            pl.BlockSpec((1, block_q, Dv), lambda bh, kj, qi: (bh, qi, 0)),
            lse_spec_kv,
        ] + ([] if dadj is None else [lse_spec_kv]),
        out_specs=[
            pl.BlockSpec((1, block_k, D), lambda bh, kj, qi: (bh, kj, 0)),
            pl.BlockSpec((1, block_k, Dv), lambda bh, kj, qi: (bh, kj, 0)),
        ],
        out_shape=[
            _sds((BH, T, D), kb.dtype, qb),
            _sds((BH, T, Dv), vb.dtype, qb),
        ],
        scratch_shapes=[
            pltpu.VMEM((block_k, D), jnp.float32),
            pltpu.VMEM((block_k, Dv), jnp.float32),
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



# ---------------------------------------------------------------------- #
# the resident schedule: a short sequence's K/V stay in VMEM             #
# ---------------------------------------------------------------------- #
# The module docstring has what this schedule is; the kernels keep the
# logsumexp as (B, H*D // W, heads a lane block, T) f32, rows along lanes.

#: Rows of queries a grid step takes and keys an inner step takes (the
#: largest multiple of 128 up to this that divides T).  Measured on a v5e
#: at GPT-2's shape (T 1,024, D 64, 4 agents x 24 heads, forward +
#: backward kernels, ms a layer): 128: 3.52, 256: 1.40, 512: 1.21.  An
#: inner step costs 0.2-0.4 us whatever its size beside 5.6 (forward) +
#: 8.4 (backward) ps a score, so the fewer, larger steps of 512 win
#: though 75% of the causal square is then live where 256 has 62.5%
#: (docs/flash_roofline.md); 1,024 would put 16 MiB of f32 score tiles
#: in VMEM.
_RESIDENT_BLOCK = 512
#: What a resident grid step may hold in VMEM, of the 16 MiB Mosaic
#: scopes a kernel to by default on a v5e (128 MiB physical): half, the
#: other half left to the f32 score tiles (S, P, dP, dS) and whatever
#: the compiler spills.  Counted for the backward, the larger kernel:
#: K, V, dK, dV whole and the q, o, dO, dq blocks, each double-buffered
#: by the pipeline, plus the two f32 accumulators.  GPT-2's head pair
#: (T 1,024 x 128 lanes, bf16) needs 4 MiB; the Qwen3-Next layer
#: (T 4,096 x 256) 26 MiB and streams.
_RESIDENT_VMEM_BUDGET = 8 * 2**20


def _resident_plan(T, H, D, dtype):
    """``(lane width W, sub-block)`` if attention over (B, T, H, D) takes
    the resident schedule, else ``None`` (it streams).  Decided from the
    shape alone: the heads must tile 128 lanes (D a multiple of 128, or a
    divisor of it with H*D on the lane grid), the sub-block must be a
    multiple of 128 that divides T (the logsumexp's rows lie along
    lanes), and the backward's working set must fit the budget."""
    if D % _LANES == 0:
        W = D
    elif _LANES % D == 0 and (H * D) % _LANES == 0:
        W = _LANES
    else:
        return None
    block = next(
        (b for b in range(_RESIDENT_BLOCK, 0, -_LANES) if T % b == 0), None
    )
    if block is None:
        return None
    item = jnp.dtype(dtype).itemsize
    held = 2 * (4 * T + 4 * block) * W * item + 2 * T * W * 4
    return (W, block) if held <= _RESIDENT_VMEM_BUDGET else None


def _key_ranges(qi, block, T, causal, window):
    """Key sub-blocks a query block walks, as ``(lo, a, b, hi)``: live
    are ``[lo, hi)``; ``[a, b)`` lie wholly inside the mask's support and
    need no mask, ``[lo, a)`` (the window's edge) and ``[b, hi)`` (the
    diagonal) are crossed by it."""
    nk = T // block
    if not causal:
        return 0, 0, nk, nk
    # query and key sub-blocks are the same size: block qi's diagonal
    # block is key block qi, everything before it is wholly visible.
    if window is None:
        return 0, 0, qi, qi + 1
    r0 = qi * block
    lo = jnp.maximum(r0 - (window - 1), 0) // block
    # wholly inside the band: first key >= last query - (window - 1)
    a = jnp.maximum(r0 + block - window + block - 1, 0) // block
    a = jnp.clip(a, lo, qi)
    return lo, a, qi, qi + 1


def _lane_heads(W, head_dim):
    """Which head of a packed block each lane belongs to, or ``None``
    where the block is one head (nothing to mask)."""
    if W == head_dim:
        return None
    return jax.lax.broadcasted_iota(jnp.int32, (1, W), 1) // head_dim


def _col_to_row(col):
    """(n, 1) column -> (1, n) row, through a lane-replicated transpose
    (the aligned 2-D transposes Mosaic has)."""
    n = col.shape[0]
    return jnp.transpose(jnp.broadcast_to(col, (n, _LANES)))[:1]


def _row_to_col(row):
    n = row.shape[1]
    return jnp.transpose(jnp.broadcast_to(row, (_LANES, n)))[:, :1]


def _walk(ranges, step, carry):
    """Run ``step(kj, carry, masked)`` over the three stretches of
    :func:`_key_ranges`; a stretch that is statically empty is not
    traced."""
    lo, a, b, hi = ranges
    for start, stop, masked in ((lo, a, True), (a, b, False), (b, hi, True)):
        if isinstance(start, int) and isinstance(stop, int) and start == stop:
            continue
        carry = jax.lax.fori_loop(
            start, stop, functools.partial(step, masked=masked), carry
        )
    return carry


def _head_slices(x, lane_head, heads):
    """``x`` once a head of a packed block with the other heads' lanes
    zeroed: the 128-lane contraction is then that head's alone (what a
    zero-padded copy would compute).  A block of one head passes."""
    if lane_head is None:
        return [x]
    return [jnp.where(lane_head == h, x, jnp.zeros_like(x))
            for h in range(heads)]


def _merge_heads(per_head, lane_head):
    """Each head's own lanes out of its (rows, W) result."""
    out = per_head[0]
    for h, x in enumerate(per_head[1:], 1):
        out = jnp.where(lane_head == h, x, out)
    return out


def _resident_fwd_kernel(q_ref, k_ref, v_ref, o_ref, lse_ref, *, sm_scale,
                         causal, window, head_dim):
    """One block of queries of one lane block (one head, or several side
    by side) against all of its live keys.  The heads of a block walk
    the keys together: their recurrences are independent, so one head's
    products overlap the other's softmax."""
    qi = pl.program_id(2)
    block, W = q_ref.shape[1], q_ref.shape[2]
    heads = W // head_dim
    lane_head = _lane_heads(W, head_dim)
    q_heads = _head_slices(q_ref[0], lane_head, heads)

    def step(kj, carry, masked):
        rows = pl.ds(pl.multiple_of(kj * block, block), block)
        k_blk, v_blk = k_ref[0, rows, :], v_ref[0, rows, :]
        return tuple(
            _online_softmax(
                _masked_scores(q_h, k_blk, qi, kj, block, block, sm_scale,
                               masked, window),
                *state, v_blk)
            for q_h, state in zip(q_heads, carry)
        )

    carry = _walk(
        _key_ranges(qi, block, k_ref.shape[1], causal, window), step,
        tuple((jnp.full((block, 1), _NEG_INF, jnp.float32),
               jnp.zeros((block, 1), jnp.float32),
               jnp.zeros((block, W), jnp.float32)) for _ in range(heads)),
    )
    outs = []
    for h, (m, l, acc) in enumerate(carry):
        l = jnp.maximum(l, 1e-30)
        outs.append(acc / l)  # P @ V filled every lane; a head's are its own
        if lse_ref is not None:
            lse_ref[0, 0, h:h + 1, :] = _col_to_row(m + jnp.log(l))
    o_ref[0] = _merge_heads(outs, lane_head).astype(o_ref.dtype)


def _resident_bwd_kernel(q_ref, k_ref, v_ref, o_ref, do_ref, lse_ref, dq_ref,
                         dk_ref, dv_ref, dk_acc, dv_acc, *, sm_scale, causal,
                         window, head_dim):
    """dQ of one block of queries and its share of dK, dV: S, P and dP
    are computed once a block pair and feed all three."""
    qi = pl.program_id(2)
    block, W = q_ref.shape[1], q_ref.shape[2]
    heads = W // head_dim
    lane_head = _lane_heads(W, head_dim)

    @pl.when(qi == 0)
    def _init():
        dk_acc[...] = jnp.zeros_like(dk_acc)
        dv_acc[...] = jnp.zeros_like(dv_acc)

    do = do_ref[0]
    q_heads = _head_slices(q_ref[0], lane_head, heads)
    do_heads = _head_slices(do, lane_head, heads)
    deltas = [
        jnp.sum(x, axis=-1, keepdims=True) for x in _head_slices(
            do.astype(jnp.float32) * o_ref[0].astype(jnp.float32),
            lane_head, heads)
    ]
    lses = [_row_to_col(lse_ref[0, 0, h:h + 1, :]) for h in range(heads)]
    contract_rows = (((0,), (0,)), ((), ()))

    def step(kj, dq_accs, masked):
        rows = pl.ds(pl.multiple_of(kj * block, block), block)
        k_blk, v_blk = k_ref[0, rows, :], v_ref[0, rows, :]
        dqs, dks, dvs = [], [], []
        for q_h, do_h, delta, lse, dq_acc in zip(
                q_heads, do_heads, deltas, lses, dq_accs):
            s = _masked_scores(q_h, k_blk, qi, kj, block, block, sm_scale,
                               masked, window)
            p, ds = _softmax_grad(s, lse, do_h, v_blk, delta)
            ds = ds.astype(q_h.dtype)
            # q_h and do_h are zero off their head's lanes, so are these
            # products: the heads of a block share the accumulators.
            dvs.append(jax.lax.dot_general(  # P^T @ dO -> (block, W)
                p.astype(do_h.dtype), do_h, dimension_numbers=contract_rows,
                preferred_element_type=jnp.float32,
            ))
            dks.append(jax.lax.dot_general(  # dS^T @ Q -> (block, W)
                ds, q_h, dimension_numbers=contract_rows,
                preferred_element_type=jnp.float32,
            ))
            dqs.append(dq_acc + jax.lax.dot_general(  # dS @ K -> (block, W)
                ds, k_blk, dimension_numbers=(((1,), (0,)), ((), ())),
                preferred_element_type=jnp.float32,
            ))
        dv_acc[rows, :] += sum(dvs[1:], dvs[0])
        dk_acc[rows, :] += sum(dks[1:], dks[0])
        return tuple(dqs)

    dqs = _walk(
        _key_ranges(qi, block, k_ref.shape[1], causal, window), step,
        tuple(jnp.zeros((block, W), jnp.float32) for _ in range(heads)),
    )
    # dS @ K filled every lane; a head's are its own
    dq_ref[0] = (sm_scale * _merge_heads(dqs, lane_head)).astype(dq_ref.dtype)

    @pl.when(qi == pl.num_programs(2) - 1)
    def _finalize():
        dk_ref[0] = (sm_scale * dk_acc[...]).astype(dk_ref.dtype)
        dv_ref[0] = dv_acc[...].astype(dv_ref.dtype)


def _resident_layout(q, W, head_dim, block):
    """``(grid, rows, whole, lse_spec, lse_shape)`` on the (B, T, H*D)
    view, grid (B, lane blocks, query blocks): ``rows`` a block of
    queries; ``whole`` all of K or V, whose index does not move with the
    query block, so the pipeline fetches it once a head."""
    B, T, HD = q.shape
    heads = W // head_dim
    rows = pl.BlockSpec((1, block, W), lambda b, j, qi: (b, qi, j))
    whole = pl.BlockSpec((1, T, W), lambda b, j, qi: (b, 0, j))
    lse_spec = pl.BlockSpec((1, 1, heads, block),
                            lambda b, j, qi: (b, j, 0, qi))
    lse_shape = _sds((B, HD // W, heads, T), jnp.float32, q)
    return (B, HD // W, T // block), rows, whole, lse_spec, lse_shape


_RESIDENT_COMPILER_PARAMS = pltpu.CompilerParams(
    dimension_semantics=("parallel", "parallel", "arbitrary"),
)


def _resident_fwd_call(q, k, v, head_dim, W, block, sm_scale, causal, window,
                       interpret, *, with_lse):
    grid, rows, whole, lse_spec, lse_shape = _resident_layout(
        q, W, head_dim, block)
    kwargs = dict(sm_scale=sm_scale, causal=causal, window=window,
                  head_dim=head_dim)
    if with_lse:
        kernel = functools.partial(_resident_fwd_kernel, **kwargs)
    else:
        def kernel(q_ref, k_ref, v_ref, o_ref):
            _resident_fwd_kernel(q_ref, k_ref, v_ref, o_ref, None, **kwargs)
    o_shape = _sds(q.shape, q.dtype, q)
    return pl.pallas_call(
        kernel,
        grid=grid,
        in_specs=[rows, whole, whole],
        out_specs=[rows, lse_spec] if with_lse else rows,
        out_shape=[o_shape, lse_shape] if with_lse else o_shape,
        compiler_params=_RESIDENT_COMPILER_PARAMS,
        interpret=interpret,
        name="flash_fwd_resident",
    )(q, k, v)


@functools.partial(jax.custom_vjp, nondiff_argnums=(3, 4, 5, 6, 7, 8, 9))
def _flash_resident(q, k, v, head_dim, W, block, sm_scale, causal, window,
                    interpret):
    """Attention on the (B, T, H*D) view in the resident schedule."""
    return _resident_fwd_call(q, k, v, head_dim, W, block, sm_scale, causal,
                              window, interpret, with_lse=False)


def _flash_resident_fwd(q, k, v, head_dim, W, block, sm_scale, causal, window,
                        interpret):
    out, lse = _resident_fwd_call(q, k, v, head_dim, W, block, sm_scale,
                                  causal, window, interpret, with_lse=True)
    return out, (q, k, v, out, lse)


def _flash_resident_bwd(head_dim, W, block, sm_scale, causal, window,
                        interpret, res, do):
    q, k, v, out, lse = res
    grid, rows, whole, lse_spec, _ = _resident_layout(q, W, head_dim, block)
    T = q.shape[1]
    return tuple(pl.pallas_call(
        functools.partial(_resident_bwd_kernel, sm_scale=sm_scale,
                          causal=causal, window=window, head_dim=head_dim),
        grid=grid,
        in_specs=[rows, whole, whole, rows, rows, lse_spec],
        out_specs=[rows, whole, whole],
        out_shape=[_sds(q.shape, x.dtype, q) for x in (q, k, v)],
        scratch_shapes=[
            pltpu.VMEM((T, W), jnp.float32),
            pltpu.VMEM((T, W), jnp.float32),
        ],
        compiler_params=_RESIDENT_COMPILER_PARAMS,
        interpret=interpret,
        name="flash_bwd_dq_dkv_resident",
    )(q, k, v, out, do, lse))


_flash_resident.defvjp(_flash_resident_fwd, _flash_resident_bwd)


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
    Dv = v.shape[-1]
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
    # Two widths (q, k of D against v of Dv, latent attention's 192 / 128)
    # go through as they lie: a block's last dimension is then the whole
    # array's, which Mosaic tiles itself, and padding one to the other's
    # width in HBM would move and multiply the dead columns.
    Dp = D if Dv != D else max(_LANES, -(-D // _LANES) * _LANES)
    if Dp != D:
        pad = [(0, 0), (0, 0), (0, 0), (0, Dp - D)]
        q, k, v = (jnp.pad(x, pad) for x in (q, k, v))
    to_bh = lambda x: x.transpose(0, 2, 1, 3).reshape(
        B * H, T, x.shape[-1])

    def unpack(out):
        out = out.reshape(B, H, T, out.shape[-1]).transpose(0, 2, 1, 3)
        return out[..., :D] if Dp != D else out

    return to_bh(q), to_bh(k), to_bh(v), block_q, block_k, unpack


def _attend(q, k, v, scale, causal, block_q, block_k, interpret, window):
    """The kernels under :func:`flash_attention` on (B, T, H, D), in the
    schedule the shape takes (:func:`_resident_plan`)."""
    B, T, H, D = q.shape
    # the resident kernels walk lane blocks of one width: two widths stream
    plan = (_resident_plan(T, H, D, q.dtype) if v.shape[-1] == D else None)
    if plan is not None:
        view = lambda x: x.reshape(B, T, H * D)  # free: no transpose, no pad
        out = _flash_resident(view(q), view(k), view(v), D, *plan, scale,
                              causal, window, interpret)
        return out.reshape(B, T, H, D)
    qb, kb, vb, block_q, block_k, unpack = _prep_blocks(
        q, k, v, block_q, block_k
    )
    return unpack(
        _flash(qb, kb, vb, scale, causal, block_q, block_k, interpret,
               window)
    )


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
    """Fused attention on q, k (B, T, H, D) and v (B, T, H, Dv) ->
    (B, T, H, Dv); T must divide by the block sizes.

    ``Dv`` may differ from ``D`` (latent attention: a query/key of 192
    against a value of 128): such a call always streams, on the operands'
    own widths, nothing padded in HBM, forward and both backward kernels;
    ``sm_scale`` defaults to ``1 / sqrt(D)``, the query/key width.  q and
    k must agree in every dimension and v with them in all but the last;
    anything else is a ``ValueError`` that names the argument.

    Differentiable: gradients run through the Pallas backward kernels
    (``jax.custom_vjp``), so the transformer's ``attention="flash"`` mode
    trains on TPU.  Off-TPU without ``interpret`` this falls back to the
    reference einsum/softmax path (XLA fuses it well enough on CPU; the
    kernel is the TPU fast path).

    The shape picks the schedule (module docstring): where K/V fit the
    VMEM budget the resident kernels run on the operands as they lie,
    with their own sub-block; everything else streams, head dims off the
    128-lane grid zero-padded through the kernels and sliced back.
    ``block_q`` / ``block_k`` are the streaming schedule's blocks: the
    defaults (256, 512) fit the VMEM budget of
    ``docs/flash_roofline.md``, which has what was measured on the chip;
    for any T they degrade to the largest 8-aligned blocks that divide
    T, so every previously valid sequence length keeps working.

    ``window`` (requires ``causal``) is sliding-window attention: row
    ``r`` attends to keys ``[r - window + 1, r]``.  Blocks entirely
    outside the band are skipped in the forward AND the backward
    kernels of either schedule, so cost scales O(T * window) instead of O(T^2) — the
    standard long-context local-attention trade (Mistral-style).
    """
    if q.ndim != 4:
        raise ValueError(f"q must be (B, T, H, D), got shape {q.shape}")
    if k.shape != q.shape:
        raise ValueError(f"k must have q's shape {q.shape}, got {k.shape}")
    if v.ndim != 4 or v.shape[:3] != q.shape[:3]:
        raise ValueError(
            f"v must be (B, T, H, Dv) with q's {q.shape[:3]}, got {v.shape}")
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
    return _attend(q, k, v, scale, causal, block_q, block_k, interpret,
                   window)


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
    into the backward kernels' ``dadj`` row term.  Always the streaming
    schedule: its consumers read the lane-replicated ``lse`` and hand
    back a cotangent for it, which the resident kernels do not take.

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
