"""Mamba's selective scan (arXiv:2312.00752, Algorithm 2), chunked and
differentiable, with its state never in HBM.

No counterpart exists in the reference (its models are tabular/image
nets, SURVEY.md §2 C11-C13).  Per channel ``c`` and state ``n`` the scan
carries ``s[n, c]`` through time,

    dt_t = softplus(delta_t + bias)
    s_t  = exp(dt_t A) * s_{t-1} + (dt_t x_t) B_t
    y_t  = sum_n C_t[n] s_t[n]

(``D x_t`` and the output gate are the caller's).  A's columns differ by
channel and state, so nothing turns the recurrence into matrix products
(Mamba-2's scalar decay does): it is ``T`` dependent elementwise steps.
The full (T, channels, states) tensor of decays or states is 2.7 GB a
layer at Phi-4-mini-flash's widths and T 8,192, so it exists nowhere.

* On a TPU (channels a multiple of 128): a Pallas kernel pair under
  ``jax.custom_vjp``, ``mamba_scan_fwd`` / ``mamba_scan_bwd``.  Grid
  (batch, blocks of channels, chunks of time): a block's (states x
  channels) f32 state sits in VMEM, states on the sublanes and channels
  on the lanes, and the kernel walks the chunk's time steps eight rows
  at a time.  The forward writes the state each chunk ENTERS with, (T /
  chunk, states, channels) f32 and nothing more; the backward walks the
  chunks from the last, recomputes a chunk's states into VMEM from that
  entry state, then walks it in reverse with the state's cotangent.
  ``B`` and ``C`` go in transposed, (states, T): a step's column is then
  picked out of one (states, chunk) tile by a lane mask and reduction.
* Everywhere else: a ``lax.scan`` over chunks whose body is a
  ``jax.checkpoint``-ed ``lax.scan`` over the chunk's steps: the same
  residuals (each chunk's entry state), the oracle of the kernels.

The state and ``y`` are f32 whatever the operands' dtype; ``softplus``
and its derivative run in f32 inside the kernels.  ``T`` need not divide by the
chunk: the tail is padded with steps that write nothing (``x = 0``),
after every real one, so no real output sees them.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

__all__ = ["selective_scan", "selective_scan_reference"]

_SUBLANES = 8
#: time steps a grid step of the kernels takes (lanes of the B, C tiles)
CHUNK = 128
#: channels a grid step takes, at most: the backward's (chunk, states,
#: channels) f32 states are 4 MiB of VMEM at 16 states
_CHANNELS = 512


def _softplus(v):
    return jnp.maximum(v, 0.0) + jnp.log(1.0 + jnp.exp(-jnp.abs(v)))


def _sigmoid(v):
    return 1.0 / (1.0 + jnp.exp(-v))


def _on_tpu() -> bool:
    return jax.devices()[0].platform == "tpu"


# --------------------------------------------------------------------- #
# the lax.scan form (every backend; the kernels' oracle)                 #
# --------------------------------------------------------------------- #
def _scan_xla(x, delta, bias, A, B, C, chunk):
    """(batch, T, channels) in, y in f32; T a multiple of chunk."""
    Bsz, T, Dch = x.shape
    N = A.shape[1]
    f32 = jnp.float32
    At = A.T.astype(f32)  # (N, Dch)

    def step(s, xs):
        xt, dt, bt, ct = xs  # (Bsz, Dch) x2, (Bsz, N) x2
        d = _softplus(dt.astype(f32) + bias)
        s = jnp.exp(d[:, None] * At) * s + (d * xt.astype(f32))[:, None] * (
            bt.astype(f32)[..., None])
        return s, jnp.sum(s * ct.astype(f32)[..., None], axis=1)

    @jax.checkpoint
    def one_chunk(s, xs):
        return jax.lax.scan(step, s, xs)

    def chunks(a):  # (Bsz, T, ...) -> (T/chunk, chunk, Bsz, ...)
        a = jnp.moveaxis(a, 1, 0)
        return a.reshape((T // chunk, chunk) + a.shape[1:])

    s0 = jnp.zeros((Bsz, N, Dch), f32)
    xs = tuple(map(chunks, (x, delta, B, C)))
    _, y = jax.lax.scan(one_chunk, s0, xs)
    return jnp.moveaxis(y.reshape((T, Bsz, Dch)), 0, 1)


# --------------------------------------------------------------------- #
# the kernels                                                            #
# --------------------------------------------------------------------- #
def _column(tile, lane, t):
    """Column ``t`` of a (states, chunk) tile as (states, 1)."""
    return jnp.sum(jnp.where(lane == t, tile, 0.0), axis=1, keepdims=True)


def _rows(ref, t0):
    return ref[pl.ds(t0, _SUBLANES), :].astype(jnp.float32)


def _fwd_kernel(x_ref, dt_ref, bias_ref, a_ref, b_ref, c_ref, y_ref, h_ref,
                s_ref):
    """One chunk of a block of channels: its y, and the entry state."""
    @pl.when(pl.program_id(2) == 0)
    def _init():
        s_ref[...] = jnp.zeros_like(s_ref)

    h_ref[...] = s_ref[...]
    A, bias = a_ref[...], bias_ref[...]
    Bt, Ct = b_ref[...].astype(jnp.float32), c_ref[...].astype(jnp.float32)
    lane = jax.lax.broadcasted_iota(jnp.int32, Bt.shape, 1)
    row = jax.lax.broadcasted_iota(jnp.int32, (_SUBLANES, A.shape[1]), 0)

    def group(g, s):
        t0 = pl.multiple_of(g * _SUBLANES, _SUBLANES)
        x8 = _rows(x_ref, t0)
        d8 = _softplus(_rows(dt_ref, t0) + bias)
        u8 = d8 * x8
        y8 = jnp.zeros_like(x8)
        for r in range(_SUBLANES):
            s = (jnp.exp(d8[r:r + 1] * A) * s
                 + u8[r:r + 1] * _column(Bt, lane, t0 + r))
            y = jnp.sum(s * _column(Ct, lane, t0 + r), axis=0, keepdims=True)
            y8 = jnp.where(row == r, y, y8)
        y_ref[pl.ds(t0, _SUBLANES), :] = y8
        return s

    s_ref[...] = jax.lax.fori_loop(
        0, x_ref.shape[0] // _SUBLANES, group, s_ref[...])


def _bwd_kernel(x_ref, dt_ref, bias_ref, a_ref, b_ref, c_ref, h_ref, dy_ref,
                dx_ref, ddt_ref, db_ref, dc_ref, da_ref, dbias_ref,
                g_ref, hs_ref, da_acc, dbias_acc):
    """One chunk of a block of channels, the grid walking the chunks from
    the last: the chunk's states again into VMEM (``hs_ref[t]`` the state
    step ``t`` reads), then its steps in reverse with ``g_ref``, the
    cotangent of the state, carried from chunk to chunk."""
    ti, nt = pl.program_id(2), pl.num_programs(2)

    @pl.when(ti == 0)
    def _init():
        g_ref[...] = jnp.zeros_like(g_ref)
        da_acc[...] = jnp.zeros_like(da_acc)
        dbias_acc[...] = jnp.zeros_like(dbias_acc)

    A, bias = a_ref[...], bias_ref[...]
    Bt, Ct = b_ref[...].astype(jnp.float32), c_ref[...].astype(jnp.float32)
    lane = jax.lax.broadcasted_iota(jnp.int32, Bt.shape, 1)
    row = jax.lax.broadcasted_iota(jnp.int32, (_SUBLANES, A.shape[1]), 0)
    groups = x_ref.shape[0] // _SUBLANES

    def forward(g, s):
        t0 = pl.multiple_of(g * _SUBLANES, _SUBLANES)
        d8 = _softplus(_rows(dt_ref, t0) + bias)
        u8 = d8 * _rows(x_ref, t0)
        for r in range(_SUBLANES):
            hs_ref[t0 + r] = s
            s = (jnp.exp(d8[r:r + 1] * A) * s
                 + u8[r:r + 1] * _column(Bt, lane, t0 + r))
        return s

    jax.lax.fori_loop(0, groups, forward, h_ref[...])

    def backward(i, carry):
        G, dB, dC, dA, dbias = carry
        t0 = pl.multiple_of((groups - 1 - i) * _SUBLANES, _SUBLANES)
        x8, raw8, dy8 = _rows(x_ref, t0), _rows(dt_ref, t0), _rows(dy_ref, t0)
        d8 = _softplus(raw8 + bias)
        sig8 = _sigmoid(raw8 + bias)
        u8 = d8 * x8
        dx8 = jnp.zeros_like(x8)
        ddt8 = jnp.zeros_like(x8)
        for r in reversed(range(_SUBLANES)):
            t = t0 + r
            bcol, ccol = _column(Bt, lane, t), _column(Ct, lane, t)
            s_prev = hs_ref[t]
            a = jnp.exp(d8[r:r + 1] * A)
            s = a * s_prev + u8[r:r + 1] * bcol
            dyr = dy8[r:r + 1]
            G = G + dyr * ccol                      # d loss / d s_t
            dC = jnp.where(lane == t,
                           jnp.sum(s * dyr, axis=1, keepdims=True), dC)
            dB = jnp.where(lane == t,
                           jnp.sum(G * u8[r:r + 1], axis=1, keepdims=True), dB)
            du = jnp.sum(G * bcol, axis=0, keepdims=True)
            ga = G * s_prev * a                     # d loss / d (dt A)
            dA = dA + ga * d8[r:r + 1]
            dd = jnp.sum(ga * A, axis=0, keepdims=True) + du * x8[r:r + 1]
            ddt = dd * sig8[r:r + 1]
            dbias = dbias + ddt
            dx8 = jnp.where(row == r, du * d8[r:r + 1], dx8)
            ddt8 = jnp.where(row == r, ddt, ddt8)
            G = G * a                               # on to s_{t-1}
        dx_ref[pl.ds(t0, _SUBLANES), :] = dx8.astype(dx_ref.dtype)
        ddt_ref[pl.ds(t0, _SUBLANES), :] = ddt8.astype(ddt_ref.dtype)
        return G, dB, dC, dA, dbias

    zeros = jnp.zeros_like(Bt)
    G, dB, dC, dA, dbias = jax.lax.fori_loop(
        0, groups, backward,
        (g_ref[...], zeros, zeros, jnp.zeros_like(A),
         jnp.zeros_like(bias)))
    g_ref[...] = G
    db_ref[...] = dB
    dc_ref[...] = dC
    da_acc[...] += dA
    dbias_acc[...] += dbias

    @pl.when(ti == nt - 1)
    def _finalize():
        da_ref[...] = da_acc[...]
        dbias_ref[...] = dbias_acc[...]


def _blocks(Dch: int) -> int:
    return next(c for c in (_CHANNELS, 256, 128, Dch) if Dch % c == 0)


def _specs(Bsz, Dch, N, nt, chunk, cb, reverse):
    """Block specs by operand kind over the grid (batch, channel blocks,
    chunks); ``reverse`` walks the chunks from the last."""
    at = (lambda t: nt - 1 - t) if reverse else (lambda t: t)
    return dict(
        seq=pl.BlockSpec((None, chunk, cb), lambda b, c, t: (b, at(t), c)),
        chan=pl.BlockSpec((1, cb), lambda b, c, t: (0, c)),
        state=pl.BlockSpec((N, cb), lambda b, c, t: (0, c)),
        bc=pl.BlockSpec((None, N, chunk), lambda b, c, t: (b, 0, at(t))),
        entry=pl.BlockSpec((None, None, N, cb),
                           lambda b, c, t: (b, at(t), 0, c)),
        part=pl.BlockSpec((None, None, N, chunk),
                          lambda b, c, t: (b, c, 0, at(t))),
        dstate=pl.BlockSpec((None, N, cb), lambda b, c, t: (b, 0, c)),
        dchan=pl.BlockSpec((None, 1, cb), lambda b, c, t: (b, 0, c)),
    )


_PARAMS = pltpu.CompilerParams(
    dimension_semantics=("parallel", "parallel", "arbitrary"),
    vmem_limit_bytes=32 * 2**20,
)


def _kernel_fwd(x, delta, bias, At, Bt, Ct, chunk, interpret):
    Bsz, T, Dch = x.shape
    N, nt, cb = At.shape[0], T // chunk, _blocks(Dch)
    sp = _specs(Bsz, Dch, N, nt, chunk, cb, False)
    return pl.pallas_call(
        _fwd_kernel,
        grid=(Bsz, Dch // cb, nt),
        in_specs=[sp["seq"], sp["seq"], sp["chan"], sp["state"], sp["bc"],
                  sp["bc"]],
        out_specs=[sp["seq"], sp["entry"]],
        out_shape=[jax.ShapeDtypeStruct(x.shape, jnp.float32),
                   jax.ShapeDtypeStruct((Bsz, nt, N, Dch), jnp.float32)],
        scratch_shapes=[pltpu.VMEM((N, cb), jnp.float32)],
        compiler_params=_PARAMS,
        interpret=interpret,
        name="mamba_scan_fwd",
    )(x, delta, bias, At, Bt, Ct)


def _kernel_bwd(x, delta, bias, At, Bt, Ct, entry, dy, chunk, interpret):
    Bsz, T, Dch = x.shape
    N, nt, cb = At.shape[0], T // chunk, _blocks(Dch)
    nc = Dch // cb
    sp = _specs(Bsz, Dch, N, nt, chunk, cb, True)
    f32 = jnp.float32
    return pl.pallas_call(
        _bwd_kernel,
        grid=(Bsz, nc, nt),
        in_specs=[sp["seq"], sp["seq"], sp["chan"], sp["state"], sp["bc"],
                  sp["bc"], sp["entry"], sp["seq"]],
        out_specs=[sp["seq"], sp["seq"], sp["part"], sp["part"],
                   sp["dstate"], sp["dchan"]],
        out_shape=[jax.ShapeDtypeStruct(x.shape, x.dtype),
                   jax.ShapeDtypeStruct(delta.shape, delta.dtype),
                   jax.ShapeDtypeStruct((Bsz, nc, N, T), f32),
                   jax.ShapeDtypeStruct((Bsz, nc, N, T), f32),
                   jax.ShapeDtypeStruct((Bsz, N, Dch), f32),
                   jax.ShapeDtypeStruct((Bsz, 1, Dch), f32)],
        scratch_shapes=[pltpu.VMEM((N, cb), f32),
                        pltpu.VMEM((chunk, N, cb), f32),
                        pltpu.VMEM((N, cb), f32),
                        pltpu.VMEM((1, cb), f32)],
        compiler_params=_PARAMS,
        interpret=interpret,
        name="mamba_scan_bwd",
    )(x, delta, bias, At, Bt, Ct, entry, dy)


def _operands(bias, A, B, C):
    """The kernels' layout: bias (1, channels), A (states, channels), B and
    C (batch, states, T), all f32."""
    f32 = jnp.float32
    return (bias.astype(f32)[None], A.T.astype(f32),
            jnp.swapaxes(B, 1, 2).astype(f32), jnp.swapaxes(C, 1, 2).astype(f32))


@functools.partial(jax.custom_vjp, nondiff_argnums=(6, 7))
def _scan_kernels(x, delta, bias, A, B, C, chunk, interpret):
    y, _ = _kernel_fwd(x, delta, *_operands(bias, A, B, C), chunk, interpret)
    return y


def _scan_kernels_fwd(x, delta, bias, A, B, C, chunk, interpret):
    ops = _operands(bias, A, B, C)
    y, entry = _kernel_fwd(x, delta, *ops, chunk, interpret)
    # B's and C's dtypes ride along as empty arrays (a residual is arrays)
    return y, (x, delta, ops, entry, B[:0, :0, :0], C[:0, :0, :0])


def _scan_kernels_bwd(chunk, interpret, res, dy):
    x, delta, ops, entry, b_like, c_like = res
    dx, ddelta, dBt, dCt, dAt, dbias = _kernel_bwd(
        x, delta, *ops, entry, dy.astype(jnp.float32), chunk, interpret)
    back = lambda parts, dtype: jnp.swapaxes(parts.sum(1), 1, 2).astype(dtype)
    return (dx, ddelta, dbias.sum((0, 1)), dAt.sum(0).T,
            back(dBt, b_like.dtype), back(dCt, c_like.dtype))


_scan_kernels.defvjp(_scan_kernels_fwd, _scan_kernels_bwd)


def kernels_run(channels: int) -> bool:
    """Whether :func:`selective_scan` runs the Pallas pair here: on a TPU,
    channels a multiple of the 128 lanes."""
    return _on_tpu() and channels % 128 == 0


def selective_scan(x, delta, A, B, C, *, bias, chunk: int = CHUNK,
                   interpret: bool = False):
    """The selective scan's ``y`` (module docstring), without ``D x``.

    ``x``: (batch, T, channels); ``delta``: the same shape, BEFORE the
    softplus and its ``bias`` (channels,); ``A``: (channels, states), the
    (negative) decay rates; ``B``, ``C``: (batch, T, states).  Returns y
    (batch, T, channels) in f32, as the f32 state gives it.  The Pallas
    kernels run on a TPU (:func:`kernels_run`) or where ``interpret``;
    elsewhere the ``lax.scan`` form.  Differentiable in every operand."""
    Bsz, T, Dch = x.shape
    if chunk % _SUBLANES:
        raise ValueError(f"chunk {chunk} must be a multiple of {_SUBLANES}")
    pad = -T % chunk
    if pad:  # steps after the last that write nothing (x = 0)
        widen = lambda a: jnp.pad(a, ((0, 0), (0, pad), (0, 0)))
        x, delta, B, C = map(widen, (x, delta, B, C))
    if interpret or kernels_run(Dch):
        y = _scan_kernels(x, delta, bias, A, B, C, chunk, interpret)
    else:
        y = _scan_xla(x, delta, bias.astype(jnp.float32), A, B, C, chunk)
    return y[:, :T] if pad else y


def selective_scan_reference(x, delta, A, B, C, *, bias):
    """The recurrence step by step, one ``lax.scan`` over T, in f32: what
    the tests hold both forms to."""
    f32 = jnp.float32
    d = jax.nn.softplus(delta.astype(f32) + bias.astype(f32))
    u = d * x.astype(f32)

    def step(s, xs):
        dt, ut, bt, ct = xs
        s = jnp.exp(dt[:, :, None] * A) * s + ut[:, :, None] * bt[:, None, :]
        return s, jnp.einsum("bcn,bn->bc", s, ct)

    s0 = jnp.zeros(x.shape[:1] + A.shape, f32)
    xs = tuple(jnp.moveaxis(a, 1, 0) for a in (d, u, B.astype(f32),
                                               C.astype(f32)))
    _, y = jax.lax.scan(step, s0, xs)
    return jnp.moveaxis(y, 0, 1)
