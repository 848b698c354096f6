"""Language-model training throughput: tokens/sec, full vs flash attention.

Beyond-parity evidence for the long-context path (the reference has no
sequence models anywhere — SURVEY.md §5): steady-state causal-LM training
throughput of :class:`TransformerLM` on one chip, with the O(T^2)
materialized reference attention versus the Pallas flash kernels
(``ops/flash_attention.py``, fwd + custom-vjp backward).  Same model, same
data, same optimizer — the only variable is ``attn_impl``, so the delta is
the kernel.

Model at full scale: 8 layers, 8 heads x 128 head-dim (d_model=1024),
vocab 8192, bf16 compute — ~117M params, the MXU-friendly shape class.
Sequence lengths 4096 and 8192 (flash only at 8192; full attention's
(B, H, T, T) f32 score tensor is already multi-GB there).

Prints one JSON line per (impl, T); ``vs_baseline`` is null (no reference
anchor exists for any sequence workload).
"""

from __future__ import annotations

import functools
import time

import jax
import jax.numpy as jnp
import numpy as np
import optax

from benchmarks.common import emit, full_scale, platform, sync


def _measure(
    attn_impl: str,
    T: int,
    *,
    B: int,
    vocab: int,
    num_layers: int,
    num_heads: int,
    head_dim: int,
    steps: int,
    warm: int = 2,
) -> tuple[float, float]:
    """Returns (tokens_per_sec, seconds_per_step) at steady state."""
    from distributed_learning_tpu.models import TransformerLM

    model = TransformerLM(
        vocab_size=vocab,
        num_layers=num_layers,
        num_heads=num_heads,
        head_dim=head_dim,
        max_len=T,
        attn_impl=attn_impl,
        dtype=jnp.bfloat16,
    )
    rng = np.random.default_rng(0)
    x = jnp.asarray(rng.integers(0, vocab, size=(B, T)), jnp.int32)
    y = jnp.asarray(rng.integers(0, vocab, size=(B, T)), jnp.int32)

    params = jax.jit(model.init)(jax.random.key(0), x)["params"]
    tx = optax.adamw(3e-4, weight_decay=0.01)
    opt_state = jax.jit(tx.init)(params)

    @functools.partial(jax.jit, donate_argnums=(0, 1))
    def step(params, opt_state, x, y):
        def loss_fn(p):
            logits = model.apply({"params": p}, x)
            return optax.softmax_cross_entropy_with_integer_labels(
                logits, y
            ).mean()

        loss, grads = jax.value_and_grad(loss_fn)(params)
        updates, opt_state = tx.update(grads, opt_state, params)
        return optax.apply_updates(params, updates), opt_state, loss

    for _ in range(warm):
        params, opt_state, loss = step(params, opt_state, x, y)
    sync(loss)
    t0 = time.perf_counter()
    for _ in range(steps):
        params, opt_state, loss = step(params, opt_state, x, y)
    sync(loss)
    dt = (time.perf_counter() - t0) / steps
    return B * T / dt, dt


def _measure_decode(
    T_prompt: int, steps: int, *, B: int, vocab: int, num_layers: int,
    num_heads: int, head_dim: int, num_kv_heads=None,
) -> tuple[float, float]:
    """Steady-state autoregressive generation rate (tokens/sec summed
    over the batch) through the KV-cache decode path."""
    from distributed_learning_tpu.models import TransformerLM
    from distributed_learning_tpu.models.transformer import generate

    model = TransformerLM(
        vocab_size=vocab, num_layers=num_layers, num_heads=num_heads,
        head_dim=head_dim, max_len=T_prompt + steps, attn_impl="full",
        num_kv_heads=num_kv_heads, dtype=jnp.bfloat16,
    )
    rng = np.random.default_rng(0)
    prompt = jnp.asarray(
        rng.integers(0, vocab, size=(B, T_prompt)), jnp.int32
    )
    params = jax.jit(model.init)(jax.random.key(0), prompt)["params"]
    return _time_decode(
        lambda p, n: generate(model, params, p, n), prompt, steps
    )


def _time_decode(gen_fn, prompt, steps: int) -> tuple[float, float]:
    """Prefill-subtracted decode timing, shared by the single-device and
    tensor-parallel paths so the MHA/GQA-vs-TP comparison uses ONE
    protocol.  Subtract the prefill (one O(T^2) forward, identical
    across configurations) from the timed window so the reported rate
    is the steady-state single-token decode loop; steps=1 ≈ prefill +
    one step."""
    B = prompt.shape[0]
    for n in (1, steps):
        sync(gen_fn(prompt, n))  # compile both programs
    t0 = time.perf_counter()
    sync(gen_fn(prompt, 1))
    dt_prefill = time.perf_counter() - t0
    t0 = time.perf_counter()
    sync(gen_fn(prompt, steps))
    dt = time.perf_counter() - t0
    decode_dt = dt - dt_prefill
    if decode_dt <= 0.1 * dt_prefill:
        # Noise-dominated difference (possible in single-shot smoke
        # timing): a clamped divisor would emit an astronomically
        # inflated rate indistinguishable from a real one.
        raise RuntimeError(
            f"decode window not resolvable: total {dt:.4f}s vs prefill "
            f"{dt_prefill:.4f}s"
        )
    return B * (steps - 1) / decode_dt, dt


def _measure_decode_tp(
    T_prompt: int, steps: int, *, B: int, vocab: int, num_layers: int,
    num_heads: int, head_dim: int, num_kv_heads=None,
) -> tuple[float, float]:
    """Like :func:`_measure_decode` but through the tensor-parallel
    path on a (data=1, model=2) mesh — prefill-subtracted steady-state
    rate with the KV cache head-sharded."""
    from jax.sharding import Mesh

    from distributed_learning_tpu.models import TransformerLM
    from distributed_learning_tpu.training.tp import (
        make_tp_generate,
        shard_transformer_params,
    )

    model = TransformerLM(
        vocab_size=vocab, num_layers=num_layers, num_heads=num_heads,
        head_dim=head_dim, max_len=T_prompt + steps, attn_impl="full",
        num_kv_heads=num_kv_heads, dtype=jnp.bfloat16,
    )
    mesh = Mesh(
        np.array(jax.devices()[:2]).reshape(1, 2), ("data", "model")
    )
    rng = np.random.default_rng(0)
    prompt = jnp.asarray(
        rng.integers(0, vocab, size=(B, T_prompt)), jnp.int32
    )
    params = shard_transformer_params(
        jax.jit(model.init)(jax.random.key(0), prompt)["params"], mesh
    )
    gen = make_tp_generate(mesh, model)
    return _time_decode(
        lambda p, n: gen(params, p, n), prompt, steps
    )


def run() -> None:
    full = full_scale()
    if full:
        cases = [
            ("full", 4096), ("flash", 4096), ("flash", 8192),
        ]
        kw = dict(B=2, vocab=8192, num_layers=8, num_heads=8,
                  head_dim=128, steps=8)
    else:
        cases = [("full", 128), ("flash", 128)]
        kw = dict(B=2, vocab=64, num_layers=2, num_heads=2, head_dim=16,
                  steps=1)
    results = {}
    for impl, T in cases:
        try:
            toks, dt = _measure(impl, T, **kw)
        except Exception as e:  # OOM at the quadratic sizes
            emit({
                "metric": f"lm_train_tokens_per_sec_{impl}_T{T}",
                "value": None,
                "unit": "tokens/sec",
                "vs_baseline": None,
                "error": f"{type(e).__name__}: {str(e)[:120]}",
            })
            continue
        results[(impl, T)] = toks
        emit({
            "metric": f"lm_train_tokens_per_sec_{impl}_T{T}",
            "value": round(toks, 1),
            "unit": "tokens/sec",
            "vs_baseline": None,
            "config": (
                f"TransformerLM L{kw['num_layers']} H{kw['num_heads']}x"
                f"{kw['head_dim']} vocab{kw['vocab']} B{kw['B']} bf16, "
                f"attn={impl}, single chip"
            ),
            "seconds_per_step": round(dt, 4),
            "platform": platform(),
        })
    # Autoregressive decode throughput (the KV-cache path), MHA vs GQA.
    if full:
        dec_cases = [(None, 2048, 256), (2, 2048, 256)]
    else:
        dec_cases = [(None, 32, 8), (1, 32, 8)]
    for hkv, tp, steps in dec_cases:
        # Tag by the measured grouping, not a fixed label: smoke and
        # full-scale configs have different head counts.
        tag = "mha" if hkv is None else f"gqa{kw['num_heads'] // hkv}"
        try:
            toks, dt = _measure_decode(
                tp, steps, B=kw["B"], vocab=kw["vocab"],
                num_layers=kw["num_layers"], num_heads=kw["num_heads"],
                head_dim=kw["head_dim"], num_kv_heads=hkv,
            )
        except Exception as e:
            emit({
                "metric": f"lm_decode_tokens_per_sec_{tag}",
                "value": None,
                "unit": "tokens/sec",
                "vs_baseline": None,
                "error": f"{type(e).__name__}: {str(e)[:120]}",
            })
            continue
        emit({
            "metric": f"lm_decode_tokens_per_sec_{tag}",
            "value": round(toks, 1),
            "unit": "tokens/sec",
            "vs_baseline": None,
            "config": (
                f"prefill {tp} + {steps} greedy steps, B{kw['B']} "
                f"L{kw['num_layers']} H{kw['num_heads']}x"
                f"{kw['head_dim']} kv_heads={hkv or kw['num_heads']}, "
                "KV-cache decode"
            ),
            "seconds_total": round(dt, 3),
            "platform": platform(),
        })

    # Tensor-parallel decode (training/tp.py::make_tp_generate): the
    # head-sharded KV-cache serving path on a (data, model) mesh.  Needs
    # >= 2 devices — on one chip this emits a skip record; the
    # 8-virtual-device CPU smoke run rot-guards the path, and a
    # four-chip host measures it for real.
    n_dev = len(jax.devices())
    if n_dev >= 2:
        try:
            toks, dt = _measure_decode_tp(
                *(dec_cases[0][1:]), B=kw["B"], vocab=kw["vocab"],
                num_layers=kw["num_layers"], num_heads=kw["num_heads"],
                head_dim=kw["head_dim"],
                num_kv_heads=kw["num_heads"] // 2 or None,
            )
            emit({
                "metric": "lm_decode_tp_tokens_per_sec",
                "value": round(toks, 1),
                "unit": "tokens/sec",
                "vs_baseline": None,
                "config": (
                    f"(data=1, model=2) mesh, head-sharded KV cache, "
                    f"B{kw['B']} L{kw['num_layers']} "
                    f"H{kw['num_heads']}x{kw['head_dim']}"
                ),
                "seconds_total": round(dt, 3),
                "platform": platform(),
            })
        except Exception as e:
            emit({
                "metric": "lm_decode_tp_tokens_per_sec",
                "value": None,
                "unit": "tokens/sec",
                "vs_baseline": None,
                "error": f"{type(e).__name__}: {str(e)[:120]}",
            })
    else:
        emit({
            "metric": "lm_decode_tp_tokens_per_sec",
            "value": None,
            "unit": "tokens/sec",
            "vs_baseline": None,
            "config": "skipped: single device (TP decode needs >= 2)",
            "platform": platform(),
        })

    # Headline ratio: the kernel's end-to-end training win at matched T.
    for T in sorted({t for _, t in cases}):
        fu, fl = results.get(("full", T)), results.get(("flash", T))
        if fu and fl:
            emit({
                "metric": f"lm_train_flash_speedup_T{T}",
                "value": round(fl / fu, 3),
                "unit": "x vs full attention",
                "vs_baseline": None,
                "platform": platform(),
            })


if __name__ == "__main__":
    run()
