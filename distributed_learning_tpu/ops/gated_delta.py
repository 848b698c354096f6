"""The gated delta rule in its chunked (WY) form, differentiable.

No counterpart exists in the reference (its models are tabular/image
nets, SURVEY.md §2 C11-C13).  The rule is the token mixer of Gated
DeltaNet (arXiv:2412.06464): per head a matrix state ``S`` (Dk x Dv,
kept in f32) that every token decays and then corrects by a rank-one
delta update,

    S <- exp(g_t) S
    S <- S + k_t (x) beta_t (v_t - S^T k_t)
    o_t = S^T q_t

Token by token that is ``T`` dependent steps of tiny matrices
(:func:`gated_delta_recurrence`, the plain form the tests and the
reference use).  The chunked form (arXiv:2412.06464 §3.3, the WY
representation of arXiv:2406.06484 with the decay folded in) does the
work of ``chunk`` tokens as matrix products: inside a chunk the updates
``u_i = beta_i (v_i - sum_{j<i} d_ij (k_i . k_j) u_j)`` are one unit
lower-triangular solve, and only ``T / chunk`` steps remain sequential,
each a handful of (chunk x D) x (D x D) products against the carried
state.

Neither sequential part runs on a slow path.  The solve is block forward
substitution written as batched matrix products
(:func:`_unit_lower_solve`: no triangular-solve custom call, its
transpose two products more).  The steps over chunks are a ``lax.scan``
under autodiff wherever the program runs, and on a TPU, at head sizes
that fill the 128 lanes, a Pallas kernel pair under ``jax.custom_vjp``
(``gdn_scan_fwd`` / ``gdn_scan_bwd``: grid (blocks of heads, chunks), the
states in VMEM along the chunk axis, one chunk a grid step) that the scan
is the oracle of.  What tracing and lowering cost does not grow with
``T``: the kernel bodies are one chunk's arithmetic, reached once a
direction (``tests/test_gated_delta_kernel.py`` holds both to that).
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
import numpy as np
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

__all__ = ["gated_delta_rule", "gated_delta_recurrence"]


_hi = functools.partial(jnp.matmul, precision="highest")


def _unit_lower_inverse(strict):
    """``(I + strict)^-1`` for a strictly lower triangular ``strict``
    (..., C, C), built from the diagonal out by block forward substitution
    written as batched matrix products.

    Where ``T`` inverts the diagonal blocks of size ``s`` (``T = I`` at
    size 1), the inverse on the blocks of size ``2 s`` differs from it in
    the lower left block of each pair, ``-L22^-1 L21 L11^-1`` for ``[[L11,
    0], [L21, L22]]``: those blocks of ``-T strict T``, since ``T`` is block
    diagonal.  So a level is ``T - pairs(T strict T)``, two products and a
    mask on their result; the first level needs none (``T = I``).  That is
    substitution block by block, as stable as row by row, in ``2 log2(C /
    2)`` products whatever the number of chunks, heads or agents.
    """
    C = strict.shape[-1]
    # which block of size s an index lies in: masks made with numpy are
    # constants of the traced program, not equations of it
    block = lambda s: np.arange(C) // s
    same = lambda s: block(s)[:, None] == block(s)[None, :]
    pairs = lambda s: same(2 * s) & ~same(s) & np.tri(C, k=-1, dtype=bool)
    T = jnp.eye(C, dtype=strict.dtype) - jnp.where(pairs(1), strict, 0.0)
    s = 2
    while s < C:  # log2(C / 2) levels: 5 at the chunk of 64
        T = T - jnp.where(pairs(s), _hi(_hi(T, strict), T), 0.0)
        s *= 2
    return T


@jax.custom_vjp
def _unit_lower_solve(strict, rhs):
    """``X`` with ``(I + strict) X = rhs`` for a strictly lower triangular
    ``strict`` (..., C, C), as products alone: no triangular-solve call
    (on a TPU a custom call of its own, run once a pass and layer).  Every
    product runs at the highest precision, as the triangular solve's did:
    in one bf16 pass the solution loses what the rule's f32 state keeps.

    The transpose is the solve's own, ``d rhs = T^T dX`` and ``d strict =
    -d rhs X^T``, two products on the inverse the forward built.  Left to
    autodiff, the ten products of the inverse keep eleven (C, C) f32
    intermediates a chunk and head alive for twenty products backward:
    1.3 GB more at the Qwen3-Next cell's shape, where none is spare.
    """
    return _hi(_unit_lower_inverse(strict), rhs)


def _unit_lower_solve_fwd(strict, rhs):
    T = _unit_lower_inverse(strict)
    X = _hi(T, rhs)
    return X, (T, X)


def _unit_lower_solve_bwd(res, dX):
    T, X = res
    d_rhs = _hi(T.mT, dX)
    lower = np.tri(T.shape[-1], k=-1, dtype=bool)
    return jnp.where(lower, -_hi(d_rhs, X.mT), 0.0), d_rhs


_unit_lower_solve.defvjp(_unit_lower_solve_fwd, _unit_lower_solve_bwd)


def _on_tpu() -> bool:
    return jax.devices()[0].platform == "tpu"


def _scan_kernel_runs(C: int, Dk: int, Dv: int) -> bool:
    """Whether the chunk scan runs as the Pallas kernel pair: on a TPU,
    at head sizes that fill the 128 lanes and a chunk that fills the 8
    sublanes (what ``flash_attention`` asks of its blocks).  Everything
    else takes the ``lax.scan`` inside :func:`gated_delta_rule`."""
    return _on_tpu() and Dk % 128 == 0 and Dv % 128 == 0 and C % 8 == 0


#: heads a grid step of the scan kernels takes at head size 128 (fewer
#: where ``B * H`` has no such divisor, or the heads are larger): their
#: products are independent, so the four matrix units overlap them, and a
#: step's fixed cost is paid once for all.  Eight (128, 128) f32 states and
#: their blocks, double-buffered, fit the 16 MB of VMEM a kernel may use.
_HEADS_PER_STEP = 8


def _kernel_dot(precision):
    """The kernels' matrix product over a block of heads, ``dot(a, b, ca,
    cb)`` contracting axis ``ca`` of ``a`` with ``cb`` of ``b`` (axis 0 is
    the heads), f32 out.  At the default precision the operands are
    rounded to bf16 first: what XLA's default does to the scan's f32
    products on a TPU, said out loud because Mosaic would otherwise run
    them in several passes."""
    def dot(a, b, ca=2, cb=1):
        if precision is None:
            a, b = a.astype(jnp.bfloat16), b.astype(jnp.bfloat16)
        return jax.lax.dot_general(
            a, b, (((ca,), (cb,)), ((0,), (0,))), precision=precision,
            preferred_element_type=jnp.float32,
        )
    return dot


def _scan_fwd_kernel(u_ref, w_ref, qk_ref, q_ref, k_ref, a_ref, o_ref,
                     states_ref, S, *, precision):
    """One chunk of a block of heads: ``step`` of :func:`gated_delta_rule`,
    the states in VMEM from the heads' first chunk to their last."""
    dot = _kernel_dot(precision)

    @pl.when(pl.program_id(1) == 0)
    def _init():
        S[...] = jnp.zeros_like(S)

    s = S[...]
    if states_ref is not None:  # the state the chunk entered with
        states_ref[...] = s
    new = u_ref[...] - dot(w_ref[...], s)
    o_ref[...] = dot(q_ref[...], s) + dot(qk_ref[...], new)
    S[...] = s * a_ref[...] + dot(k_ref[...], new, 1, 1)


def _scan_bwd_kernel(u_ref, w_ref, qk_ref, q_ref, k_ref, a_ref, states_ref,
                     do_ref, du_ref, dw_ref, dqk_ref, dq_ref, dk_ref, da_ref,
                     dS, *, precision):
    """The transpose of one chunk, the grid walking the chunks from the
    last to the first with the states' cotangent in VMEM.  ``new`` is
    computed again from the entry state the forward wrote out."""
    dot = _kernel_dot(precision)

    @pl.when(pl.program_id(1) == 0)
    def _init():
        dS[...] = jnp.zeros_like(dS)

    s, ds, do = states_ref[...], dS[...], do_ref[...]
    w, q = w_ref[...], q_ref[...]
    new = u_ref[...] - dot(w, s)
    dnew = dot(qk_ref[...], do, 1, 1) + dot(k_ref[...], ds)
    du_ref[...] = dnew
    dw_ref[...] = -dot(dnew, s, 2, 2)
    dqk_ref[...] = dot(do, new, 2, 2)
    dq_ref[...] = dot(do, s, 2, 2)
    dk_ref[...] = dot(new, ds, 2, 2)
    # d exp(g_end) = sum(s * ds): the rows here, the lanes by the caller
    da_ref[...] = jnp.sum(s * ds, axis=1, keepdims=True)
    dS[...] = ds * a_ref[...] + dot(q, do, 1, 1) - dot(w, dnew, 1, 1)


#: the kernels' common operands, by the name of the block spec each goes by
_OPERANDS = ("u", "w", "qk", "q", "k", "a")


def _scan_call(kernel, name, ins, outs, reverse, precision, interpret):
    """``pallas_call`` over the grid (blocks of heads, chunks) of arrays
    laid out (N, B*H, ...): the blocks in parallel, a block's chunks in
    order (from the last backwards if ``reverse``) with its (Dk, Dv) f32
    states in VMEM across them.  ``ins``: (spec name, array), the first
    two ``u`` and ``w``; ``outs``: spec names, all f32.  A grid step takes
    as many heads as divide ``B * H`` and keep the states within
    ``_HEADS_PER_STEP`` of (128, 128)."""
    (_, u), (_, w) = ins[:2]
    N, BH, C, Dv = u.shape
    Dk = w.shape[-1]
    most = max(1, _HEADS_PER_STEP * 128 * 128 // (Dk * Dv))
    heads = max(h for h in range(1, most + 1) if BH % h == 0)
    at = (lambda bh, n: (N - 1 - n, bh, 0, 0)) if reverse else (
        lambda bh, n: (n, bh, 0, 0))
    blocks = dict(u=(C, Dv), w=(C, Dk), qk=(C, C), q=(C, Dk), k=(C, Dk),
                  a=(1, 1), states=(Dk, Dv), colsum=(1, Dv))
    spec = lambda s: pl.BlockSpec((None, heads) + blocks[s], at)
    return pl.pallas_call(
        functools.partial(kernel, precision=precision),
        grid=(BH // heads, N),
        in_specs=[spec(s) for s, _ in ins],
        out_specs=[spec(s) for s in outs],
        out_shape=[jax.ShapeDtypeStruct((N, BH) + blocks[s], jnp.float32)
                   for s in outs],
        scratch_shapes=[pltpu.VMEM((heads, Dk, Dv), jnp.float32)],
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "arbitrary"),
        ),
        interpret=interpret,
        name=name,
    )(*(x for _, x in ins))


def _scan_operands(u, w, qk, q_in, k_out, g_end, precision):
    """The kernels' common operands, in ``_OPERANDS``' order: (N, B, H,
    ...) -> (N, B*H, ...), the chunk's decay as a (1, 1) block.  What a
    kernel only ever multiplies at the default precision goes in as the
    bf16 it would round to: half the bytes to write, keep for the backward
    pass and read."""
    N, B, H = g_end.shape
    flat = lambda x: x.reshape((N, B * H) + x.shape[3:])
    factor = lambda x: flat(x.astype(jnp.bfloat16) if precision is None else x)
    return [flat(u), factor(w), factor(qk), factor(q_in), factor(k_out),
            jnp.exp(g_end).reshape(N, B * H, 1, 1)]


def _scan_fwd_call(operands, precision, interpret, with_states):
    kernel = _scan_fwd_kernel
    if not with_states:  # the primal alone writes no residual
        def kernel(*refs, precision):
            _scan_fwd_kernel(*refs[:7], None, *refs[7:], precision=precision)
    return _scan_call(
        kernel, "gdn_scan_fwd", list(zip(_OPERANDS, operands)),
        ["u", "states"] if with_states else ["u"], False, precision,
        interpret)


@functools.partial(jax.custom_vjp, nondiff_argnums=(6, 7))
def _chunk_scan(u, w, qk, q_in, k_out, g_end, precision, interpret):
    """The scan over chunks of :func:`gated_delta_rule` as a kernel pair:
    same operands (N, B, H, C, ...) and ``g_end`` (N, B, H), same ``o``
    (N, B, H, C, Dv).  The residuals are the kernels' own operands and
    each chunk's entry state, what the scan's transpose keeps."""
    operands = _scan_operands(u, w, qk, q_in, k_out, g_end, precision)
    (o,) = _scan_fwd_call(operands, precision, interpret, with_states=False)
    return o.reshape(u.shape)


def _chunk_scan_fwd(u, w, qk, q_in, k_out, g_end, precision, interpret):
    operands = _scan_operands(u, w, qk, q_in, k_out, g_end, precision)
    o, states = _scan_fwd_call(operands, precision, interpret,
                               with_states=True)
    return o.reshape(u.shape), (operands, states, g_end)


def _chunk_scan_bwd(precision, interpret, res, do):
    operands, states, g_end = res
    N, B, H = g_end.shape
    do = do.astype(jnp.bfloat16) if precision is None else do
    ins = list(zip(_OPERANDS, operands)) + [
        ("states", states), ("u", do.reshape(operands[0].shape))]
    *grads, da = _scan_call(
        _scan_bwd_kernel, "gdn_scan_bwd", ins,
        ["u", "w", "qk", "q", "k", "colsum"], True, precision, interpret)
    grads = [dx.reshape((N, B, H) + dx.shape[2:]) for dx in grads]
    return (*grads, jnp.exp(g_end) * da.sum((-2, -1)).reshape(N, B, H))


_chunk_scan.defvjp(_chunk_scan_fwd, _chunk_scan_bwd)


def gated_delta_rule(q, k, v, g, beta, chunk: int = 64, precision=None):
    """Chunked gated delta rule (arXiv:2412.06464 §3.3).

    ``q``, ``k``: (B, T, H, Dk), already normalised and scaled by the
    caller; ``v``: (B, T, H, Dv); ``g`` (log decay, <= 0) and ``beta``
    (write strength): (B, T, H).  Returns ``o``: (B, T, H, Dv) in f32.
    The state starts at zero and lives in f32 whatever the inputs' dtype;
    ``precision`` is that of the rule's matrix products (``None``: the
    backend's default, on a TPU one bf16 pass with f32 accumulation).
    ``T`` need not divide by ``chunk``: the tail is padded with tokens
    that neither decay nor write (``g = 0``, ``beta = 0``, ``k = 0``).
    """
    B, T, H, Dk = q.shape
    Dv = v.shape[-1]
    C = int(chunk)
    pad = -T % C
    f32 = jnp.float32

    def chunks(x):  # (B, T, H, ...) -> (N, B, H, C, ...), f32
        x = x.astype(f32)
        if pad:
            x = jnp.pad(x, [(0, 0), (0, pad)] + [(0, 0)] * (x.ndim - 2))
        x = x.reshape((B, (T + pad) // C, C) + x.shape[2:])
        return jnp.moveaxis(x, 3, 2).swapaxes(0, 1)

    q, k, v, g, beta = (chunks(x) for x in (q, k, v, g, beta))
    gc = jnp.cumsum(g, axis=-1)  # decay from the chunk's start, log
    lower = jnp.tril(jnp.ones((C, C), bool))
    # d_ij = exp(gc_i - gc_j) for i >= j; the masked exponent keeps the
    # upper triangle (a positive, possibly huge exponent) out of exp and
    # so out of the gradient.
    diff = gc[..., :, None] - gc[..., None, :]
    decay = jnp.where(lower, jnp.exp(jnp.where(lower, diff, 0.0)), 0.0)
    k_beta = k * beta[..., None]
    kk = jnp.einsum("...id,...jd->...ij", k_beta, k, precision=precision)
    strict = jnp.tril(kk * decay, -1)
    # (I + A) [U | W] = [beta v | beta k exp(gc)]: every token's update
    # against the chunk's earlier ones, and what it reads of the state.
    rhs = jnp.concatenate(
        [v * beta[..., None], k_beta * jnp.exp(gc)[..., None]], axis=-1
    )
    sol = _unit_lower_solve(strict, rhs)
    u, w = sol[..., :Dv], sol[..., Dv:]
    qk = jnp.einsum("...id,...jd->...ij", q, k, precision=precision) * decay
    q_in = q * jnp.exp(gc)[..., None]  # the query as the state sees it
    g_end = gc[..., -1]
    k_out = k * jnp.exp(g_end[..., None] - gc)[..., None]

    def step(S, xs):
        u_i, w_i, qk_i, q_i, k_i, end_i = xs
        new = u_i - jnp.einsum("...ck,...kv->...cv", w_i, S,
                               precision=precision)
        o = jnp.einsum("...ck,...kv->...cv", q_i, S, precision=precision)
        o = o + jnp.einsum("...ij,...jv->...iv", qk_i, new,
                           precision=precision)
        S = S * jnp.exp(end_i)[..., None, None] + jnp.einsum(
            "...ck,...cv->...kv", k_i, new, precision=precision
        )
        return S, o

    if _scan_kernel_runs(C, Dk, Dv):
        o = _chunk_scan(u, w, qk, q_in, k_out, g_end, precision, False)
    else:
        S0 = jnp.zeros((B, H, Dk, Dv), f32)
        _, o = jax.lax.scan(step, S0, (u, w, qk, q_in, k_out, g_end))
    o = jnp.moveaxis(o.swapaxes(0, 1), 2, 3).reshape(B, T + pad, H, Dv)
    return o[:, :T]


def gated_delta_recurrence(q, k, v, g, beta):
    """The rule as written (arXiv:2412.06464 eq. 10), one token a step:
    the oracle for :func:`gated_delta_rule`.  Same arguments and result;
    f32 throughout at the highest matrix precision."""
    f32 = jnp.float32
    q, k, v, g, beta = (
        jnp.moveaxis(x.astype(f32), 1, 0) for x in (q, k, v, g, beta)
    )
    B, H, Dk = q.shape[1:]

    def step(S, xs):
        q_t, k_t, v_t, g_t, b_t = xs
        S = S * jnp.exp(g_t)[..., None, None]
        read = jnp.einsum("bhkv,bhk->bhv", S, k_t, precision="highest")
        delta = (v_t - read) * b_t[..., None]
        S = S + k_t[..., :, None] * delta[..., None, :]
        return S, jnp.einsum("bhkv,bhk->bhv", S, q_t, precision="highest")

    S0 = jnp.zeros((B, H, Dk, v.shape[-1]), f32)
    _, o = jax.lax.scan(step, S0, (q, k, v, g, beta))
    return jnp.moveaxis(o, 0, 1)
