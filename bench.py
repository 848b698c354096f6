"""Headline benchmark: gossip-SGD throughput on WRN-28-10 / CIFAR-10 shapes.

Measures steady-state training throughput (samples/sec summed over agents)
of the framework's core loop, structured exactly like the trainer's epoch
program (``training/trainer.py``): N agent replicas stacked on the leading
axis, a ``lax.scan`` of vmapped fwd/bwd/update steps (batched onto the MXU
in bf16, batches gathered device-side from resident shards), then one full
gossip mixing round per epoch — the reference's ``MasterNode`` cadence
(``Man_Colab.ipynb`` cell 21: train an epoch, then mix).  The epoch state
is donated, so XLA updates the stacked params/optimizer buffers in place.

Baseline: the reference's only recorded wall-clock for this model is the
single-node torch run in ``CIFAR_10_Baseline.ipynb`` cell 9 — WRN-28-10,
CIFAR-10, 100 epochs in 8h 18m 07s on a Tesla T4, i.e.
100 * 50_000 / 29_887 s = 167.3 samples/sec.  ``vs_baseline`` is the
speedup over that number.  (The reference's own gossip driver is absent
from its snapshot and its TCP round loop is a stub, so the centralized
baseline is the only wall-clock anchor; our measurement additionally pays
for gossip mixing, which only handicaps us.)

Measures the configuration it was asked for (the ``BENCH_*`` sizes; the
defaults are the WRN-28-10 headline) on the device JAX finds, and prints
exactly one JSON line naming that device:
    {"metric": ..., "value": ..., "unit": "samples/sec", "vs_baseline": ...,
     "platform": ..., "device_kind": ..., "device_count": ...,
     "cost": {flops, peak_hbm_bytes, mfu, bytes_per_round, ...},
     "wire": {native, bytes_per_sec, ...}}
Anything that goes wrong — a compile error, out of memory, a size that
does not divide — fails the run non-zero with no record: there is no
other platform, smaller model or smaller batch to fall back to.

The ``cost`` payload is the device-cost observatory (obs/cost.py): the
measured program's compiled cost profile plus measured MFU; ``wire``
says which frame-codec path (native wire engine vs Python fallback)
served and its measured fused-frame throughput at this model's width
(benchmarks/bench_wire.py is the full measurement).  The emitted record
is also appended to the program's perf ledger
(``benchmarks/results/perf_ledger.jsonl``, ``obs-report --ledger``).
"""

from __future__ import annotations

import json
import os
import time

import jax

# Hardware PRNG for dropout: threefry is a software hash that costs ~8% of
# the WRN step on v5e (measured 3,123 -> 3,381 samples/s at 2x512); rbg
# uses the TPU's native RNG instruction.  Gossip math is PRNG-agnostic.
# Any value jax accepts may be passed (threefry2x32, rbg, unsafe_rbg);
# unknown names fail loudly in jax.config.update.
jax.config.update(
    "jax_default_prng_impl", os.environ.get("BENCH_PRNG", "rbg")
)

import jax.numpy as jnp
import numpy as np
import optax

from distributed_learning_tpu.models import WideResNet
from distributed_learning_tpu.obs import CostProfile, SpanTracer
from distributed_learning_tpu.obs import cost as cost_mod
from distributed_learning_tpu.utils.profiling import maybe_trace
from distributed_learning_tpu.ops import mixing as mixing_ops
from distributed_learning_tpu.parallel.compression import (
    FusedCompressor,
    top_k as choco_top_k,
)
from distributed_learning_tpu.parallel.consensus import ConsensusEngine
from distributed_learning_tpu.parallel.topology import Topology

BASELINE_SAMPLES_PER_SEC = 100 * 50_000 / 29_887.0  # T4, BASELINE.md

# Per-phase wall-clock spans (compile / warmup / measure):
# aggregated into the one JSON record's "phases" payload so the driver
# log shows where a run's time went.  Registry-free tracer — nothing
# here may print; stdout stays the single json.dumps line.
_TRACER = SpanTracer()


def _phase_payload() -> dict:
    """{phase: {"s": total_seconds, "n": count}} over the spans so far."""
    return {
        name: {"s": round(agg["total_s"], 3), "n": agg["count"]}
        for name, agg in sorted(_TRACER.aggregate().items())
    }


def _obs_payload() -> dict:
    """Telemetry-plane summary INSIDE the one JSON record (stdout
    contract: fields ride the record, never extra lines): the obs.delta
    schema version this build speaks, the default registry's nonzero
    counter totals, and the ring-eviction picture — so the driver log
    shows what a run observed, not just what it measured."""
    from distributed_learning_tpu.obs import OBS_PAYLOAD_VERSION, get_registry

    snap = get_registry().snapshot()
    return {
        "schema": OBS_PAYLOAD_VERSION,
        "counters": {
            name: round(total, 3)
            for name, total in sorted(snap["counters"].items())
            if total
        },
        "events": sum(snap["series"].values()),
        "dropped": snap["dropped"],
    }


def build_epoch(model, tx, engine, n_agents, *, unroll=None, remat=None,
                mix=True, pregather=False, superstep=1):
    """One jitted, donated epoch: scan of vmapped train steps + one gossip
    round (the trainer's per-epoch mixing cadence).

    ``unroll``/``remat`` default to the ``BENCH_UNROLL``/``BENCH_REMAT``
    env knobs; ``benchmarks/profile_wrn.py`` passes them (and ``mix``)
    explicitly so its ablations measure this exact program.
    ``pregather`` is an ablation-only variant: materialize every batch
    with one big device-side gather before the scan instead of a
    ``take`` per step — attributing the in-scan gather's cost (the
    trainer uses in-scan gathers to avoid materializing the permuted
    epoch tensor; this measures what that choice pays).
    ``superstep=K`` (``BENCH_SUPERSTEP``) wraps the epoch in an outer
    epoch scan — the trainer's ``train_epochs`` cadence: the returned
    program takes ``(K, steps, n, B)`` indices and runs K epochs of
    scan+mix per dispatch.
    """
    if unroll is None:
        unroll = int(os.environ.get("BENCH_UNROLL", 2))
    if remat is None:
        remat = os.environ.get("BENCH_REMAT") == "1"

    def train_step(params, batch_stats, opt_state, x, y, rng):
        def lossf(p):
            out, mut = model.apply(
                {"params": p, "batch_stats": batch_stats},
                x,
                train=True,
                rngs={"dropout": rng},
                mutable=["batch_stats"],
            )
            loss = optax.softmax_cross_entropy_with_integer_labels(out, y).mean()
            return loss, mut["batch_stats"]

        if remat:
            # Recompute activations in backward (the trainer's remat knob,
            # training/trainer.py:535-538): trades ~1/3 extra fwd FLOPs for
            # the activation HBM that makes larger agent x batch products
            # fit on a 16 GB chip.
            lossf = jax.checkpoint(lossf)
        (loss, new_bs), grads = jax.value_and_grad(lossf, has_aux=True)(params)
        updates, opt_state = tx.update(grads, opt_state, params)
        params = optax.apply_updates(params, updates)
        return params, new_bs, opt_state, loss

    vstep = jax.vmap(train_step)
    take = jax.vmap(lambda X, i: jnp.take(X, i, axis=0))

    def epoch(state, Xs, ys, idx):
        def step(carry, x, y):
            params, bs, opt, rng = carry
            rng, *subs = jax.random.split(rng, n_agents + 1)
            params, bs, opt, loss = vstep(params, bs, opt, x, y, jnp.stack(subs))
            return (params, bs, opt, rng), loss

        if pregather:
            Xb = jax.vmap(lambda it: take(Xs, it))(idx)  # (steps, n, B, ...)
            yb = jax.vmap(lambda it: take(ys, it))(idx)
            (params, bs, opt, rng), losses = jax.lax.scan(
                lambda c, xy: step(c, *xy), state, (Xb, yb), unroll=unroll
            )
        else:
            (params, bs, opt, rng), losses = jax.lax.scan(
                lambda c, it: step(c, take(Xs, it), take(ys, it)),
                state, idx, unroll=unroll,
            )
        if mix:
            # Fused flat-buffer gossip: one GEMM per dtype bucket instead
            # of one per leaf (ops/mixing.py); inside this jitted epoch
            # the flatten/unflatten pair is a one-time prologue/epilogue.
            params = mixing_ops.fused_dense_mix(
                params, engine._W_dev, precision=engine.precision
            )
        return (params, bs, opt, rng), losses

    donate = (0,) if jax.default_backend() != "cpu" else ()
    if superstep <= 1:
        return jax.jit(epoch, donate_argnums=donate)

    def epoch_superstep(state, Xs, ys, idx):
        # idx: (K, steps, n, B).  One dispatch covers K epochs of
        # scan+mix; the carried state crosses epochs on device.
        return jax.lax.scan(
            lambda carry, idx_e: epoch(carry, Xs, ys, idx_e), state, idx
        )

    return jax.jit(epoch_superstep, donate_argnums=donate)


def measure_throughput(model, tx, engine, *, n_agents, batch, steps, epochs,
                       pool=None, unroll=None, remat=None, mix=True,
                       pregather=False, superstep=1, trace_dir=None):
    """Steady-state samples/sec of :func:`build_epoch` on random resident
    data — the shared harness behind ``bench.py`` and
    ``benchmarks/profile_wrn.py``.

    Every timed region ends in ``jax.block_until_ready``; ``trace_dir``
    wraps the timed epochs in a ``jax.profiler`` trace
    (``utils/profiling.maybe_trace``).

    The epoch program is AOT-compiled (``lower().compile()``) and the
    SAME executable is dispatched for compile/warmup/measure — so its
    :class:`CostProfile` (XLA-counted FLOPs, bytes, peak HBM, donation,
    collective inventory) describes exactly the measured program, with
    no second compile; the profile plus measured MFU / bytes-per-sec
    land in the module-level ``_COST_INFO`` for the JSON record's
    ``cost`` payload.
    """
    if pool is None:
        pool = steps * batch
    superstep = max(int(superstep), 1)
    if epochs % superstep:
        raise ValueError(
            f"epochs ({epochs}) must be a multiple of superstep "
            f"({superstep}) so every dispatch runs the same program"
        )
    run_epoch = build_epoch(model, tx, engine, n_agents, unroll=unroll,
                            remat=remat, mix=mix, pregather=pregather,
                            superstep=superstep)

    rng = jax.random.key(0)
    x0 = jnp.ones((batch, 32, 32, 3), jnp.float32)
    variables = jax.jit(lambda r: model.init(r, x0, train=False))(rng)
    stack = lambda t: jax.tree.map(
        lambda v: jnp.broadcast_to(v[None], (n_agents,) + v.shape), t
    )
    params = stack(variables["params"])
    layout = mixing_ops.fused_layout(params)
    _LAYOUT_INFO.update(
        leaf_count=layout.leaf_count,
        fused_buckets=layout.bucket_count,
        mix_bytes_per_round=layout.bytes_per_round(n_agents),
        # What one CHOCO round's corrections would ship over the sparse
        # wire at the nominal 10% top-k budget (the fused frame's
        # u32-index + stored-dtype-value accounting) — the compressed
        # counterpart of mix_bytes_per_round; host-side arithmetic only.
        choco_bytes_per_round=FusedCompressor(
            choco_top_k(0.1)
        ).wire_bytes_per_round(layout, n_agents),
    )
    _measure_wire(sum(width for _name, width in layout.buckets))
    bs = stack(variables["batch_stats"])
    opt = jax.vmap(tx.init)(params)
    state = (params, bs, opt, jax.random.key(1))

    data_rng = np.random.default_rng(0)
    Xs = jnp.asarray(
        data_rng.normal(size=(n_agents, pool, 32, 32, 3)).astype(np.float32)
    )
    ys = jnp.asarray(
        data_rng.integers(0, 10, size=(n_agents, pool)).astype(np.int32)
    )

    def _epoch_idx_np(e):
        r = np.random.default_rng(e)
        idx = np.stack(
            [r.permutation(pool)[: steps * batch] for _ in range(n_agents)]
        ).astype(np.int32)
        return idx.reshape(n_agents, steps, batch).swapaxes(0, 1)

    def epoch_idx(e):
        if superstep == 1:
            return jnp.asarray(_epoch_idx_np(e))
        # K epochs of indices, transferred once per superstep dispatch.
        return jnp.asarray(
            np.stack([_epoch_idx_np(e * superstep + j)
                      for j in range(superstep)])
        )

    program = "bench.superstep" if superstep > 1 else "bench.epoch"
    _COST_INFO.clear()
    with _TRACER.span("compile"):
        # AOT: one lower+compile, the executable reused for every
        # dispatch below — the cost profile IS the measured program.
        compiled = run_epoch.lower(state, Xs, ys, epoch_idx(0)).compile()
        profile = CostProfile.from_compiled(
            program, compiled, platform=jax.default_backend()
        )
        cost_mod.register_profile(profile)
        state, losses = compiled(state, Xs, ys, epoch_idx(0))
        jax.block_until_ready((state, losses))
    _COST_INFO.update({
        k: v for k, v in {
            "program": program,
            "flops": profile.flops,
            "bytes_accessed": profile.bytes_accessed,
            "peak_hbm_bytes": profile.peak_bytes,
            "alias_bytes": profile.alias_bytes,
            "collectives": profile.collectives or None,
            "bytes_per_round": _LAYOUT_INFO.get("mix_bytes_per_round"),
        }.items() if v is not None
    })
    with _TRACER.span("warmup"):
        state, losses = compiled(state, Xs, ys, epoch_idx(1))  # warm
        jax.block_until_ready((state, losses))

    with maybe_trace(trace_dir):
        with _TRACER.span("measure"):
            t0 = time.perf_counter()
            for e in range(epochs // superstep):
                state, losses = compiled(state, Xs, ys, epoch_idx(2 + e))
            jax.block_until_ready((state, losses))
            elapsed = time.perf_counter() - t0
    dispatches = max(epochs // superstep, 1)
    peak_flops = cost_mod.device_peak_flops()
    # XLA counts scan bodies once (CostProfile's loop caveat): one
    # dispatch executes the counted train-step body steps x superstep
    # times.  The epoch's once-per-epoch mix tail is scaled with it —
    # an overcount that is noise next to the WRN step, accepted for one
    # multiplier instead of a second compile.
    loop_steps = steps * superstep
    measured_mfu = profile.mfu(
        elapsed, peak_flops, dispatches=dispatches, loop_steps=loop_steps
    )
    measured_bps = profile.bytes_per_sec(
        elapsed, dispatches=dispatches, loop_steps=loop_steps
    )
    _COST_INFO.update({
        "loop_steps": loop_steps,
        "step_time_s": round(elapsed / dispatches, 4),
        "mfu": None if measured_mfu is None else round(measured_mfu, 4),
        "hbm_bytes_per_sec": (
            None if measured_bps is None else round(measured_bps, 1)
        ),
        "peak_flops": peak_flops,
    })
    return n_agents * batch * steps * epochs / elapsed


# Fused-consensus geometry of the measured model (leaf count / dtype
# buckets / bytes one gossip round moves), recorded by measure_throughput
# for the JSON record — measurement metadata, not a phase span.
_LAYOUT_INFO: dict = {}

# Device-cost observatory payload (obs/cost.py): the measured program's
# compiled cost profile (FLOPs / bytes / peak HBM / donation /
# collectives) plus the measured MFU and HBM bytes/sec — rides the one
# JSON record as its "cost" field and the perf ledger as "cost".
_COST_INFO: dict = {}

# Native wire engine summary (ISSUE 9): which frame-codec path this box
# runs (comm.wire.native) and its measured fused-frame throughput at the
# measured model's width — host-side microbenchmark, never stdout.
_WIRE_INFO: dict = {}


def _measure_wire(total_params: int) -> None:
    """Fill _WIRE_INFO with {native, bytes_per_sec}: one fused-sparse
    frame (10% density, bf16 wire — the per-round gossip frame) encoded
    and decoded at the measured model's width, capped so the probe stays
    ~100 ms.  The TCP data plane ships exactly these frames, so the
    record says what the wire can sustain next to what the device did."""
    from distributed_learning_tpu.comm.tensor_codec import (
        decode_fused_sparse,
        encode_fused_sparse,
    )
    from distributed_learning_tpu.native import wire as native_wire

    total = max(1024, min(int(total_params), 1 << 23))
    rng = np.random.default_rng(0)
    flat = rng.normal(size=total).astype(np.float32)
    flat[rng.random(total) >= 0.1] = 0.0
    buckets = (("float32", ((0, total),)),)
    frame = encode_fused_sparse(flat, buckets, bf16_wire=True)
    t0 = time.perf_counter()
    frame = encode_fused_sparse(flat, buckets, bf16_wire=True)
    decode_fused_sparse(frame)
    dt = max(time.perf_counter() - t0, 1e-9)
    _WIRE_INFO.update(
        native=native_wire.available(),
        bytes_per_sec=round(2 * len(frame) / dt, 1),
        frame_bytes=len(frame),
        probe_elems=total,
    )


def _emit_record(rec: dict) -> None:
    """Print ``rec`` as THE one JSON stdout line and mirror it into the
    program's perf ledger (a file, never stdout; best-effort)."""
    print(json.dumps(rec), flush=True)
    cost_mod.ledger_append({
        "source": "bench.py",
        "env": {"platform": rec["platform"],
                "device_kind": rec["device_kind"]},
        **{k: rec.get(k) for k in (
            "metric", "value", "unit", "vs_baseline", "superstep", "cost",
            "wire", "phases",
        )},
    })


def main():
    from distributed_learning_tpu.utils.compile_cache import (
        enable_compile_cache,
    )

    enable_compile_cache()
    device = jax.devices()[0]
    platform = device.platform

    # 4x256 on WRN-28-10 is the headline configuration (the reference's
    # worker count of 4, BASELINE.json config 4); every size is taken as
    # asked, on whatever device JAX found.
    n_agents = int(os.environ.get("BENCH_AGENTS", 4))
    batch = int(os.environ.get("BENCH_BATCH", 256))
    depth = int(os.environ.get("BENCH_DEPTH", 28))
    widen = int(os.environ.get("BENCH_WIDEN", 10))
    steps = int(os.environ.get("BENCH_STEPS", 16))
    epochs = int(os.environ.get("BENCH_EPOCHS", 3))
    # Epoch superstep (trainer.train_epochs cadence): K epochs of
    # scan+mix compiled into one donated dispatch.  1 = the headline
    # per-epoch program; BENCH_EPOCHS must be a multiple of K.
    superstep_k = max(int(os.environ.get("BENCH_SUPERSTEP", 1)), 1)
    if epochs % superstep_k:
        raise SystemExit(
            f"BENCH_EPOCHS={epochs} must be a multiple of "
            f"BENCH_SUPERSTEP={superstep_k}"
        )
    pool = int(os.environ.get("BENCH_POOL", steps * batch))
    if pool < steps * batch:
        raise SystemExit(
            f"BENCH_POOL={pool} must be >= BENCH_STEPS*BENCH_BATCH "
            f"({steps}*{batch}={steps * batch}): each epoch samples that "
            "many distinct indices per agent"
        )

    model = WideResNet(
        depth=depth, widen_factor=widen, dropout_rate=0.3,
        num_classes=10, dtype=jnp.bfloat16,
    )
    tx = optax.chain(
        optax.add_decayed_weights(5e-4), optax.sgd(0.1, momentum=0.9)
    )
    engine = ConsensusEngine(Topology.ring(n_agents).metropolis_weights())
    # BENCH_TRACE_DIR wires the jax.profiler programmatic trace around
    # the measure phase (utils/profiling.maybe_trace).
    sps = measure_throughput(
        model, tx, engine, n_agents=n_agents, batch=batch, steps=steps,
        epochs=epochs, pool=pool, superstep=superstep_k,
        trace_dir=os.environ.get("BENCH_TRACE_DIR") or None,
    )
    _emit_record({
        "metric": f"gossip_sgd_wrn{depth}x{widen}_cifar10_throughput_{platform}",
        "value": round(sps, 2),
        "unit": "samples/sec",
        "vs_baseline": round(sps / BASELINE_SAMPLES_PER_SEC, 3),
        "platform": platform,
        "device_kind": device.device_kind,
        "device_count": len(jax.devices()),
        "config": f"{n_agents} agents x batch {batch}, bf16, rbg dropout, "
                  "mix 1/epoch",
        "superstep": superstep_k,
        "consensus": dict(_LAYOUT_INFO),
        "cost": dict(_COST_INFO),
        "wire": dict(_WIRE_INFO),
        "phases": _phase_payload(),
        "obs": _obs_payload(),
    })


if __name__ == "__main__":
    main()
