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
transpose two products more), and the steps over chunks are a
``lax.scan`` under autodiff: the rule in XLA, wherever the program runs.
On a TPU, at head sizes that fill the 128 lanes, the whole rule is
instead a Pallas kernel pair under ``jax.custom_vjp`` (``gdn_scan_fwd`` /
``gdn_scan_bwd``: grid (blocks of heads, chunks), the states in VMEM along
the chunk axis, one chunk a grid step) that the XLA form is the oracle
of: a grid step reads its chunk's q, k (at the key heads), v, g's
cumulative sum and beta once, and builds the decay matrix, the masked
products, the inverse and the solve's result in VMEM before the step
against the state; backward it builds them again from the same inputs
and the chunk's entry state, and carries the cotangents back through
all of it.  No (chunk, ...) f32 array of the preparation reaches HBM.
What tracing and lowering cost does not grow with ``T``: the kernel
bodies are one chunk's arithmetic, reached once a direction
(``tests/test_gated_delta_kernel.py`` holds both to that).
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


def _unit_lower_inverse(strict, index=None):
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
    # row and column index: numpy's are constants of the traced program,
    # not equations of it; a kernel hands in iotas (``index``).  Two
    # indices lie in one block of size s (a power of two) where they agree
    # above its bits.
    row, col = np.indices((C, C)) if index is None else index
    same = lambda s: (row & -s) == (col & -s)
    pairs = lambda s: same(2 * s) & ~same(s) & (row > col)
    T = (row == col).astype(strict.dtype) - jnp.where(pairs(1), strict, 0.0)
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
    """Whether the rule runs as the Pallas kernel pair: on a TPU, at head
    sizes that fill the 128 lanes and a chunk that fills the 8 sublanes
    (what ``flash_attention`` asks of its blocks).  Everything else takes
    the ``lax.scan`` inside :func:`gated_delta_rule`."""
    return _on_tpu() and Dk % 128 == 0 and Dv % 128 == 0 and C % 8 == 0


#: VMEM a grid step of the kernels may hold (of the 128 MiB a v5e core has)
_VMEM_LIMIT = 32 * 2**20
#: what one head of a grid step holds there, in (C, 128) f32 tiles, beside
#: five (Dk, Dv) f32 states (the scratch and the double-buffered blocks of
#: the entry states): the backward body's live (C, C) and (C, D) arrays
#: and blocks (a 64-wide row pads to the 128 lanes), as the compiler for
#: a v5e placed them at the Qwen3-Next cell's shape (72 MiB for 32 heads
#: of 128 at the highest precision, 44 MiB for 8 of 256)
_TILES_PER_HEAD = 62


def _heads_per_step(BH: int, r: int, C: int, Dk: int, Dv: int) -> int:
    """Heads a grid step takes: the most that divide ``BH``, come in whole
    groups of the ``r`` value heads that share a key head, and fit
    ``_VMEM_LIMIT`` (their products are independent, so the matrix units
    overlap them, and a step's fixed cost is paid once for all)."""
    per_head = 4 * (_TILES_PER_HEAD * C * max(Dk, Dv, 128) + 5 * Dk * Dv)
    most = max(r, _VMEM_LIMIT // per_head)
    return max(h for h in range(r, most + 1, r) if BH % h == 0)


def _kernel_dot(precision):
    """The kernels' matrix product over a block of heads, ``dot(a, b, ca,
    cb)`` contracting axis ``ca`` of ``a`` with ``cb`` of ``b`` (axis 0 is
    the heads), f32 out.  At the default precision the operands are
    rounded to bf16 first: what XLA's default does to the rule's f32
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


class _Chunk:
    """One chunk's preparation for a block of heads, in VMEM: what
    :func:`gated_delta_rule` computes in XLA before its ``step``, from the
    blocks of the chunk's q, k (f32, at the key heads), v, and the rows of
    g's cumulative sum and of beta (``(heads, 1, C)``).  Every product is
    rounded where the XLA path rounds it: ``k k^T`` and ``q k^T`` at the
    rule's ``precision``, the solve at the highest."""

    def __init__(self, q_ref, k_ref, v_ref, gc_ref, beta_ref, dot):
        gc_row = gc_ref[...]
        C = gc_row.shape[-1]
        self.r = v_ref.shape[0] // q_ref.shape[0]  # value heads a key head
        q, k = (jnp.repeat(x[...], self.r, axis=0) for x in (q_ref, k_ref))
        v = v_ref[...].astype(jnp.float32)
        # index iotas: a numpy constant would be an array the kernel captures
        self.row, self.col = (jax.lax.broadcasted_iota(jnp.int32, (C, C), d)
                              for d in (0, 1))
        self.eye = self.row == self.col
        self.lower = self.row >= self.col
        self.q, self.k, self.v = q, k, v
        gc, self.beta = self.column(gc_row), self.column(beta_ref[...])
        # exp(gc_i - gc_j) for i >= j, the upper triangle kept out of exp
        self.decay = jnp.where(self.lower, jnp.exp(
            jnp.where(self.lower, gc - gc_row, 0.0)), 0.0)
        self.e = jnp.exp(gc)
        self.k_beta = k * self.beta
        self.kk = dot(self.k_beta, k, 2, 2)
        self.strict = jnp.where(self.row > self.col, self.kk * self.decay, 0.0)
        self.T = _unit_lower_inverse(self.strict, (self.row, self.col))
        self.u = _hi(self.T, v * self.beta)
        self.w = _hi(self.T, self.k_beta * self.e)
        self.qk0 = dot(q, k, 2, 2)
        self.qk = self.qk0 * self.decay
        self.q_in = q * self.e
        self.g_end = jnp.sum(jnp.where(self.col[:1] == C - 1, gc_row, 0.0),
                             axis=2, keepdims=True)  # (heads, 1, 1)
        self.a = jnp.exp(self.g_end)
        self.f = jnp.exp(self.g_end - gc)
        self.k_out = k * self.f

    def column(self, row):
        """(heads, 1, C) -> (heads, C, 1), exactly: a sum of one entry."""
        return jnp.sum(jnp.where(self.eye, row, 0.0), axis=2, keepdims=True)

    def row_of(self, column):
        """(heads, C, 1) -> (heads, 1, C), the same way."""
        return jnp.sum(jnp.where(self.eye, column, 0.0), axis=1, keepdims=True)


def _rule_fwd_kernel(q_ref, k_ref, v_ref, gc_ref, beta_ref, o_ref,
                     states_ref, S, *, precision):
    """One chunk of a block of heads: the preparation and ``step`` of
    :func:`gated_delta_rule`, the states in VMEM from the heads' first
    chunk to their last."""
    dot = _kernel_dot(precision)

    @pl.when(pl.program_id(1) == 0)
    def _init():
        S[...] = jnp.zeros_like(S)

    c = _Chunk(q_ref, k_ref, v_ref, gc_ref, beta_ref, dot)
    s = S[...]
    if states_ref is not None:  # the state the chunk entered with
        states_ref[...] = s
    new = c.u - dot(c.w, s)
    o_ref[...] = dot(c.q_in, s) + dot(c.qk, new)
    S[...] = s * c.a + dot(c.k_out, new, 1, 1)


def _rule_bwd_kernel(q_ref, k_ref, v_ref, gc_ref, beta_ref, states_ref,
                     do_ref, dq_ref, dk_ref, dv_ref, dgc_ref, dbeta_ref, dS,
                     *, precision):
    """The transpose of one chunk, the grid walking the chunks from the
    last to the first with the states' cotangent in VMEM: the preparation
    built again from the inputs, ``step``'s transpose on the entry state
    the forward wrote out, then the preparation's, ending in each token's
    dq, dk, dv, d beta and d gc (the caller's cumulative sum takes the
    last to dg)."""
    dot = _kernel_dot(precision)
    sum_lanes = lambda x: jnp.sum(x, axis=2, keepdims=True)

    @pl.when(pl.program_id(1) == 0)
    def _init():
        dS[...] = jnp.zeros_like(dS)

    c = _Chunk(q_ref, k_ref, v_ref, gc_ref, beta_ref, dot)
    s, ds, do = states_ref[...], dS[...], do_ref[...]
    # step's transpose
    new = c.u - dot(c.w, s)
    du = dot(c.qk, do, 1, 1) + dot(c.k_out, ds)
    dw = -dot(du, s, 2, 2)
    dqk = dot(do, new, 2, 2)
    dq_in = dot(do, s, 2, 2)
    dk_out = dot(new, ds, 2, 2)
    da = jnp.sum(sum_lanes(s * ds), axis=1, keepdims=True)
    dS[...] = ds * c.a + dot(c.q_in, do, 1, 1) - dot(c.w, du, 1, 1)
    # the solve's: d rhs = T^T dX, d strict = -d rhs X^T below the diagonal
    hi = _kernel_dot("highest")
    d_rv, d_rw = hi(c.T, du, 1, 1), hi(c.T, dw, 1, 1)
    d_strict = jnp.where(c.row > c.col, -(hi(d_rv, c.u, 2, 2) +
                                           hi(d_rw, c.w, 2, 2)), 0.0)
    # the decay-masked products
    dkk = d_strict * c.decay
    dqk0 = dqk * c.decay
    d_decay = d_strict * c.kk + dqk * c.qk0
    dk_beta = d_rw * c.e + dot(dkk, c.k)
    dq = dot(dqk0, c.k) + dq_in * c.e
    dk = (dot(dkk, c.k_beta, 1, 1) + dot(dqk0, c.q, 1, 1) + dk_out * c.f
          + dk_beta * c.beta)
    dv = d_rv * c.beta
    dbeta = sum_lanes(d_rv * c.v) + sum_lanes(dk_beta * c.k)
    # the exponents: exp(gc) (q_in, the right-hand side), exp(g_end - gc)
    # (k_out), exp(g_end) (the state's decay), exp(gc_i - gc_j) (decay)
    de = sum_lanes(dq_in * c.q) + sum_lanes(d_rw * c.k_beta)
    df = sum_lanes(dk_out * c.k) * c.f
    d_end = jnp.sum(df, axis=1, keepdims=True) + da * c.a
    d_diff = jnp.where(c.lower, d_decay * c.decay, 0.0)
    dgc = (c.row_of(de * c.e - df + sum_lanes(d_diff))
           - jnp.sum(d_diff, axis=1, keepdims=True)
           + jnp.where(c.col[:1] == c.col.shape[1] - 1, d_end, 0.0))
    key_heads = lambda x: x.reshape((-1, c.r) + x.shape[1:]).sum(1)
    dq_ref[...] = key_heads(dq)
    dk_ref[...] = key_heads(dk)
    dv_ref[...] = dv.astype(dv_ref.dtype)
    dgc_ref[...] = dgc
    dbeta_ref[...] = c.row_of(dbeta)


def _rule_call(kernel, name, ins, outs, reverse, precision, interpret):
    """``pallas_call`` over the grid (blocks of heads, chunks): the blocks
    in parallel, a block's chunks in order (from the last backwards if
    ``reverse``) with its (Dk, Dv) f32 states in VMEM across them.
    ``ins`` and ``outs`` (shapes) are the rule's arrays laid out heads
    first: (B*H, T, D) a chunk's (C, D) block, (B*H, N, ...) a chunk's
    entry; q and k (and their cotangents) have the key heads, ``ins[2]``
    (v) and the rest the value heads."""
    q, v, gc = ins[0], ins[2], ins[3]
    (BH, _, Dv), Dk, (N, _, C) = v.shape, q.shape[-1], gc.shape[1:]
    heads = _heads_per_step(BH, BH // q.shape[0], C, Dk, Dv)
    at = (lambda n: N - 1 - n) if reverse else (lambda n: n)

    def spec(x):
        hb = heads * x.shape[0] // BH  # the key heads of a block: fewer
        if len(x.shape) == 3:
            return pl.BlockSpec((hb, C, x.shape[2]),
                                lambda b, n: (b, at(n), 0))
        return pl.BlockSpec((hb, None) + tuple(x.shape[2:]),
                            lambda b, n: (b, at(n), 0, 0))

    return pl.pallas_call(
        functools.partial(kernel, precision=precision),
        grid=(BH // heads, N),
        in_specs=[spec(x) for x in ins],
        out_specs=[spec(x) for x in outs],
        out_shape=outs,
        scratch_shapes=[pltpu.VMEM((heads, Dk, Dv), jnp.float32)],
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "arbitrary"),
            vmem_limit_bytes=_VMEM_LIMIT,
        ),
        interpret=interpret,
        name=name,
    )(*ins)


def _rule_fwd_call(ins, precision, interpret, with_states):
    q, v, gc = ins[0], ins[2], ins[3]
    (BH, T, Dv), Dk, N = v.shape, q.shape[-1], gc.shape[1]
    f32 = jnp.float32
    outs = [jax.ShapeDtypeStruct((BH, T, Dv), f32)]
    kernel = _rule_fwd_kernel
    if with_states:
        outs.append(jax.ShapeDtypeStruct((BH, N, Dk, Dv), f32))
    else:  # the primal alone writes no residual
        def kernel(*refs, precision):
            _rule_fwd_kernel(*refs[:6], None, *refs[6:], precision=precision)
    return _rule_call(kernel, "gdn_scan_fwd", ins, outs, False, precision,
                      interpret)


@functools.partial(jax.custom_vjp, nondiff_argnums=(5, 6))
def _rule_kernels(q, k, v, gc, beta, precision, interpret):
    """The rule as a kernel pair on arrays laid out heads first: q, k
    (B*Hk, T, Dk) f32, v (B*H, T, Dv), g's cumulative sum within each
    chunk and beta (B*H, N, 1, C) f32; ``o`` (B*H, T, Dv) f32.  The
    residuals are the inputs and each chunk's entry state."""
    (o,) = _rule_fwd_call((q, k, v, gc, beta), precision, interpret, False)
    return o


def _rule_kernels_fwd(q, k, v, gc, beta, precision, interpret):
    ins = (q, k, v, gc, beta)
    o, states = _rule_fwd_call(ins, precision, interpret, True)
    return o, (ins, states)


def _rule_kernels_bwd(precision, interpret, res, do):
    ins, states = res
    do = do.astype(jnp.bfloat16) if precision is None else do
    outs = [jax.ShapeDtypeStruct(x.shape, x.dtype) for x in ins]
    return tuple(_rule_call(
        _rule_bwd_kernel, "gdn_scan_bwd", (*ins, states, do), outs, True,
        precision, interpret))


_rule_kernels.defvjp(_rule_kernels_fwd, _rule_kernels_bwd)


def _rule_on_tpu(q, k, v, g, beta, C, precision, interpret=False):
    """:func:`gated_delta_rule` where the kernels run: one XLA pass lays
    q, k, v, beta and g's cumulative sum within each chunk out heads
    first, in their own dtypes and at their own heads, and one lays ``o``
    back."""
    B, T, H, Dv = v.shape
    pad = -T % C

    def heads_first(x):  # (B, T, H, ...) -> (B*H, T + pad, ...)
        if pad:
            x = jnp.pad(x, [(0, 0), (0, pad)] + [(0, 0)] * (x.ndim - 2))
        x = jnp.moveaxis(x, 2, 1)
        return x.reshape((-1,) + x.shape[2:])

    def rows(x):  # (B, T, H) -> (B*H, N, 1, C)
        return heads_first(x).reshape(B * H, -1, 1, C)

    gc = jnp.cumsum(rows(g.astype(jnp.float32)), axis=-1)
    o = _rule_kernels(heads_first(q), heads_first(k), heads_first(v), gc,
                      rows(beta.astype(jnp.float32)), precision, interpret)
    return jnp.moveaxis(o.reshape(B, H, T + pad, Dv), 1, 2)[:, :T]


def gated_delta_rule(q, k, v, g, beta, chunk: int = 64, precision=None):
    """Chunked gated delta rule (arXiv:2412.06464 §3.3).

    ``q``, ``k``: (B, T, Hk, Dk), already normalised and scaled by the
    caller; ``v``: (B, T, H, Dv), where ``Hk`` divides ``H`` (value head
    ``h`` reads key head ``h // (H / Hk)``); ``g`` (log decay, <= 0) and
    ``beta`` (write strength): (B, T, H).  Returns ``o``: (B, T, H, Dv)
    in f32.  The state starts at zero and lives in f32 whatever the
    inputs' dtype; ``precision`` is that of the rule's matrix products
    (``None``: the backend's default, on a TPU one bf16 pass with f32
    accumulation).  ``T`` need not divide by ``chunk``: the tail is
    padded with tokens that neither decay nor write (``g = 0``, ``beta =
    0``, ``k = 0``).
    """
    B, T, Hk, Dk = q.shape
    H, Dv = v.shape[2:]
    C = int(chunk)
    if _scan_kernel_runs(C, Dk, Dv):
        return _rule_on_tpu(q, k, v, g, beta, C, precision)
    q, k = (jnp.repeat(x, H // Hk, axis=2) for x in (q, k))
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
