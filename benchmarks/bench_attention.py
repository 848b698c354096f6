"""Flash-attention kernel throughput: TFLOP/s at 8k/32k/131k tokens.

Substantiates the Pallas kernel's performance on the real chip
(``ops/flash_attention.py``): for each context length, sweeps
(block_q, block_k) and reports the best configuration's sustained TFLOP/s.
Causal FLOPs are counted as 4*B*H*T^2*D/2 (two matmuls, two FLOPs per MAC,
half the score matrix live).

The reference has no attention anywhere (SURVEY.md §5: "long-context /
sequence parallelism entirely absent"), so ``vs_baseline`` is null; the
yardstick is fraction of the chip's bf16 peak (~197 TFLOP/s on v5e).
"""

from __future__ import annotations

import time

import jax
import jax.numpy as jnp
import numpy as np

from benchmarks.common import emit, full_scale, platform, smoke, sync

V5E_BF16_PEAK_TFLOPS = 197.0


def _time(fn, iters: int) -> float:
    """Shared compile/warm/measure protocol: one compile call, one warm
    call, then ``iters`` timed calls ended by :func:`sync`.  Both our
    kernel and the upstream rival go through THIS function so the
    ours/upstream ratio can never be skewed by protocol drift."""
    out = fn()
    sync(out)  # compile
    out = fn()
    sync(out)  # warm
    t0 = time.perf_counter()
    for _ in range(iters):
        out = fn()
    sync(out)
    return (time.perf_counter() - t0) / iters


def _qkv(T: int, B: int, H: int, D: int, *, heads_second: bool):
    """bf16 inputs from the shared seed — drawn once in our (B, T, H, D)
    layout and TRANSPOSED for upstream's (B, H, T, D), so both kernels
    see the same values and an output cross-check stays meaningful."""
    rng = np.random.default_rng(0)
    mk = lambda: jnp.asarray(
        rng.normal(size=(B, T, H, D)).astype(np.float32),
        dtype=jnp.bfloat16,
    )
    q, k, v = mk(), mk(), mk()
    if heads_second:
        q, k, v = (a.transpose(0, 2, 1, 3) for a in (q, k, v))
    return q, k, v


def _measure(
    T: int, block_q: int, block_k: int, *, B=1, H=8, D=128, iters=8,
    interpret=False, backward=False, window=None,
):
    from distributed_learning_tpu.ops.flash_attention import flash_attention

    q, k, v = _qkv(T, B, H, D, heads_second=False)
    if backward:
        # Forward (with lse) + all three backward kernels via custom_vjp.
        grad_fn = jax.jit(jax.grad(
            lambda q, k, v: flash_attention(
                q, k, v, causal=True, block_q=block_q, block_k=block_k,
                interpret=interpret, window=window,
            ).astype(jnp.float32).sum(),
            argnums=(0, 1, 2),
        ))
        fn = lambda: grad_fn(q, k, v)[0]
    else:
        fn = lambda: flash_attention(
            q, k, v, causal=True, block_q=block_q, block_k=block_k,
            window=window,
            interpret=interpret,
        )
    dt = _time(fn, iters)
    if window is None:
        live_pairs = T * T / 2  # causal triangle
    else:
        W = min(window, T)
        live_pairs = W * (W + 1) / 2 + (T - W) * W  # causal band
    fwd_flops = 4 * B * H * D * live_pairs
    # USEFUL-FLOPs convention (the standard flash accounting): backward =
    # 2.5x forward (5 gradient matmuls vs 2), plus the lse-producing
    # forward, = 3.5x.  The kernels EXECUTE more than that — the split
    # into dQ and dK/dV kernels recomputes scores and dP in both, ~9
    # matmuls per block pair — so true MXU utilization is ~20-25% above
    # the reported fraction; the reported number is comparable across
    # implementations precisely because it counts algorithmic work.
    flops = fwd_flops * (1 + 2.5) if backward else fwd_flops
    return flops / dt / 1e12, dt


def _measure_upstream(T: int, *, B=1, H=8, D=128, iters=8, backward=False,
                      blocks=None):
    """Same-shape rival: ``jax.experimental.pallas.ops.tpu.flash_attention``
    (the upstream TPU kernel shipped in site-packages), measured with the
    identical FLOPs accounting.  Its layout is (B, H, T, D) and its
    default sm_scale is 1.0, so the shared inputs are transposed and the
    1/sqrt(D) scale passed explicitly — same values, same function."""
    from jax.experimental.pallas.ops.tpu import flash_attention as upstream

    q, k, v = _qkv(T, B, H, D, heads_second=True)
    bs = None
    if blocks is not None:
        bq, bk = blocks
        bs = upstream.BlockSizes(
            block_q=bq, block_k_major=bk, block_k=bk, block_b=1,
            block_q_major_dkv=bq, block_k_major_dkv=bk, block_k_dkv=bk,
            block_q_dkv=bq, block_k_major_dq=bk, block_k_dq=bk,
            block_q_dq=bq,
        )
    sm = 1.0 / (D ** 0.5)
    if backward:
        grad_fn = jax.jit(jax.grad(
            lambda q, k, v: upstream.flash_attention(
                q, k, v, causal=True, sm_scale=sm, block_sizes=bs
            ).astype(jnp.float32).sum(),
            argnums=(0, 1, 2),
        ))
        fn = lambda: grad_fn(q, k, v)[0]
    else:
        fn = jax.jit(lambda: upstream.flash_attention(
            q, k, v, causal=True, sm_scale=sm, block_sizes=bs
        ))
    dt = _time(fn, iters)
    fwd_flops = 4 * B * H * D * (T * T / 2)
    flops = fwd_flops * 3.5 if backward else fwd_flops
    return flops / dt / 1e12, dt


def _rival_pass(T: int, iters: int, ours_best, ours_grad) -> None:
    """Measure the upstream kernel at the same shapes and emit the
    side-by-side records VERDICT asks for (ours >= upstream is the bar)."""
    for tag, backward, ours in (("fwd", False, ours_best),
                                ("grad", True, ours_grad)):
        best = None
        for blocks in (None, (256, 512), (512, 512)):
            if blocks is not None and (T % blocks[0] or T % blocks[1]):
                continue
            try:
                tflops, dt = _measure_upstream(
                    T, iters=iters, backward=backward, blocks=blocks
                )
            except Exception as e:
                emit({
                    "metric": f"upstream_flash_{tag}_T{T}_"
                              f"{'default' if blocks is None else 'x'.join(map(str, blocks))}",
                    "value": None,
                    "unit": "TFLOP/s",
                    "vs_baseline": None,
                    "error": f"{type(e).__name__}: {str(e)[:120]}",
                })
                continue
            if best is None or tflops > best[0]:
                best = (tflops, blocks, dt)
        if best is None:
            continue
        rec = {
            "metric": f"upstream_flash_{tag}_T{T}_best",
            "value": round(best[0], 2),
            "unit": "TFLOP/s",
            "vs_baseline": None,
            "config": "jax.experimental.pallas.ops.tpu.flash_attention, "
                      f"blocks={best[1] or 'default(128)'}",
            "seconds_per_call": round(best[2], 4),
        }
        if ours is not None:
            rec["ours_over_upstream"] = round(ours / best[0], 3)
        emit(rec)


def run() -> None:
    on_tpu = platform() == "tpu"
    if not on_tpu and not smoke():
        raise RuntimeError(
            "bench_attention measures the Pallas kernels and needs a TPU "
            f"(found {platform()!r}); off-chip only the BENCH_SMOKE=1 "
            "interpret-mode rot guard runs"
        )
    # Off-TPU smoke runs the real kernel under interpret=True (tiny sizes;
    # without it flash_attention would silently time the einsum fallback).
    interpret = not on_tpu
    if on_tpu and full_scale():
        lengths = [8192, 32768, 131072]
        blocks = [(128, 128), (128, 256), (256, 256), (256, 512), (512, 512)]
        iters = 8
    else:
        lengths = [256]
        blocks = [(128, 128), (128, 256)]
        iters = 1
    for T in lengths:
        best = None
        for bq, bk in blocks:
            if T % bq or T % bk:
                continue
            if T >= 131072 and min(bq, bk) < 256:
                # O(T^2) at 131k: the small-block points are minutes of
                # chip time each and have never won any sweep (block
                # 512/512 won at every measured T) — spend the window on
                # configurations that can.
                continue
            try:
                tflops, dt = _measure(T, bq, bk, iters=iters,
                                      interpret=interpret)
            except Exception as e:  # OOM/VMEM overflow at big blocks
                emit({
                    "metric": f"flash_attention_{T}_bq{bq}_bk{bk}",
                    "value": None,
                    "unit": "TFLOP/s",
                    "vs_baseline": None,
                    "error": f"{type(e).__name__}: {str(e)[:120]}",
                })
                continue
            emit({
                "metric": f"flash_attention_{T}_bq{bq}_bk{bk}",
                "value": round(tflops, 2),
                "unit": "TFLOP/s",
                "vs_baseline": None,
                "seconds_per_call": round(dt, 4),
            })
            if best is None or tflops > best[0]:
                best = (tflops, bq, bk)
        if best is not None:
            emit({
                "metric": f"flash_attention_causal_T{T}_best",
                "value": round(best[0], 2),
                "unit": "TFLOP/s",
                "vs_baseline": None,
                "config": f"B1 H8 D128 bf16, block_q={best[1]} block_k={best[2]}",
                "fraction_of_v5e_peak": round(best[0] / V5E_BF16_PEAK_TFLOPS, 3),
            })
            # Training step (fwd-with-lse + dQ + dK/dV kernels) at the
            # best forward block configuration.
            grad_tflops = None
            try:
                tflops, dt = _measure(T, best[1], best[2], iters=iters,
                                      interpret=interpret, backward=True)
            except Exception as e:
                emit({
                    "metric": f"flash_attention_grad_T{T}",
                    "value": None,
                    "unit": "TFLOP/s",
                    "vs_baseline": None,
                    "error": f"{type(e).__name__}: {str(e)[:120]}",
                })
            else:
                grad_tflops = tflops
                emit({
                    "metric": f"flash_attention_grad_T{T}",
                    "value": round(tflops, 2),
                    "unit": "TFLOP/s",
                    "vs_baseline": None,
                    "config": f"B1 H8 D128 bf16 fwd+bwd, block_q={best[1]} "
                              f"block_k={best[2]}",
                    "seconds_per_call": round(dt, 4),
                    "fraction_of_v5e_peak": round(
                        tflops / V5E_BF16_PEAK_TFLOPS, 3
                    ),
                })
            if on_tpu and full_scale() and T <= 32768:
                # Upstream rival at the same shapes (131k skipped: the
                # upstream kernel's all-T backward at 131k is many
                # minutes of chip time; the VERDICT bar names 8k/32k).
                _rival_pass(T, iters, best[0], grad_tflops)

    # Sliding-window long context: the O(T * W) path that makes 131k+
    # affordable.  One record (tiny interpreted sizes off-TPU, so the
    # path stays rot-guarded by the smoke test).
    if on_tpu and full_scale():
        Tw, W, bq, bk = 131072, 4096, 256, 512
    else:
        Tw, W, bq, bk = 256, 64, 128, 128
    try:
        tflops, dt = _measure(Tw, bq, bk, iters=iters, window=W,
                              interpret=interpret)
    except Exception as e:
        emit({
            "metric": f"flash_attention_window{W}_T{Tw}",
            "value": None,
            "unit": "TFLOP/s",
            "vs_baseline": None,
            "error": f"{type(e).__name__}: {str(e)[:120]}",
        })
    else:
        emit({
            "metric": f"flash_attention_window{W}_T{Tw}",
            "value": round(tflops, 2),
            "unit": "TFLOP/s",
            "vs_baseline": None,
            "config": (
                f"B1 H8 D128 bf16, sliding window {W}, "
                f"block_q={bq} block_k={bk}"
            ),
            "seconds_per_call": round(dt, 4),
            "fraction_of_v5e_peak": round(
                tflops / V5E_BF16_PEAK_TFLOPS, 3
            ),
        })


if __name__ == "__main__":
    run()
