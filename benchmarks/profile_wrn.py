"""Profile the WRN gossip-SGD epoch on hardware: trace + ablations.

Batch tuning alone says nothing about where a step's time goes — the
next lever must come from a measurement.  Two instruments, both driving ``bench.py``'s own harness
(:func:`bench.measure_throughput`), so what is profiled is exactly the
shipped epoch program:

1. ``jax.profiler`` trace (``--trace``): a TensorBoard/xprof-loadable
   device timeline under ``benchmarks/results/profile_<stamp>/``.
2. Timed ablations (default): re-measure throughput with one element
   removed or altered at a time.  The throughput delta attributes the
   cost of each element without needing trace parsing:

   - ``baseline``      the shipped configuration as-is
   - ``no_mix``        skip the per-epoch gossip round
   - ``no_dropout``    dropout_rate=0 (removes RNG + mask apply)
   - ``no_weight_decay`` drop the decoupled weight-decay chain link
   - ``unroll1/4``     scan unroll factor (shipped: 2)
   - ``remat``         rematerialized backward (HBM for FLOPs trade)
   - ``pregather``     one big batch gather before the scan (vs per-step)
   - ``f32_conv``      params/compute in f32 (quantifies the bf16 win)

Usage (one process per chip — never concurrently with other TPU
work):

    python -m benchmarks.profile_wrn                 # ablations
    python -m benchmarks.profile_wrn --trace         # profiler trace
    BENCH_AGENTS=2 BENCH_BATCH=512 ...               # same knobs as bench.py

Each ablation prints one JSON line; a summary table lands in
``benchmarks/results/profile_ablations_<stamp>.json``.
"""

from __future__ import annotations

import argparse
import json
import os
import time

import jax

jax.config.update(
    "jax_default_prng_impl", os.environ.get("BENCH_PRNG", "rbg")
)

import jax.numpy as jnp
import optax

import bench
from distributed_learning_tpu.models import WideResNet
from distributed_learning_tpu.parallel.consensus import ConsensusEngine
from distributed_learning_tpu.parallel.topology import Topology


def _measure_config(
    *,
    n_agents: int,
    batch: int,
    steps: int,
    epochs: int,
    depth: int = 28,
    widen: int = 10,
    dropout: float = 0.3,
    mix: bool = True,
    weight_decay: bool = True,
    unroll: int = 2,
    remat: bool = False,
    pregather: bool = False,
    dtype=jnp.bfloat16,
    trace_dir: str | None = None,
) -> float:
    model = WideResNet(
        depth=depth, widen_factor=widen, dropout_rate=dropout,
        num_classes=10, dtype=dtype,
    )
    links = [optax.sgd(0.1, momentum=0.9)]
    if weight_decay:
        links.insert(0, optax.add_decayed_weights(5e-4))
    tx = optax.chain(*links)
    engine = ConsensusEngine(Topology.ring(n_agents).metropolis_weights())
    return bench.measure_throughput(
        model, tx, engine, n_agents=n_agents, batch=batch, steps=steps,
        epochs=epochs, unroll=unroll, remat=remat, mix=mix,
        pregather=pregather, trace_dir=trace_dir,
    )


def main() -> None:
    ap = argparse.ArgumentParser()
    ap.add_argument("--trace", action="store_true",
                    help="capture a jax.profiler trace of the baseline config")
    ap.add_argument("--only", default=None,
                    help="comma-separated subset of ablation names")
    args = ap.parse_args()

    base = dict(
        n_agents=int(os.environ.get("BENCH_AGENTS", 4)),
        batch=int(os.environ.get("BENCH_BATCH", 256)),
        steps=int(os.environ.get("BENCH_STEPS", 16)),
        epochs=int(os.environ.get("BENCH_EPOCHS", 3)),
        depth=int(os.environ.get("BENCH_DEPTH", 28)),
        widen=int(os.environ.get("BENCH_WIDEN", 10)),
    )

    stamp = time.strftime("%Y%m%d_%H%M%S")
    outdir = os.path.join(os.path.dirname(__file__), "results")
    os.makedirs(outdir, exist_ok=True)

    if args.trace:
        trace_dir = os.path.join(outdir, f"profile_{stamp}")
        sps = _measure_config(**base, trace_dir=trace_dir)
        rec = {
            "metric": "profile_trace", "samples_per_sec": round(sps, 1),
            "trace_dir": trace_dir,
        }
        try:
            from distributed_learning_tpu.utils.profiling import (
                format_trace_summary, summarize_trace,
            )
            rows = summarize_trace(trace_dir, top=20)
            rec["top_ops"] = rows
            # Persist the computed table BEFORE the cosmetic print: a
            # formatting hiccup must not discard the summary artifact.
            with open(os.path.join(outdir,
                                   f"profile_summary_{stamp}.json"), "w") as f:
                json.dump(rec, f, indent=1)
            print(format_trace_summary(rows))
        except Exception as exc:  # missing xprof / empty trace: keep the dir
            rec["summary_error"] = f"{type(exc).__name__}: {exc}"
        print(json.dumps({k: v for k, v in rec.items() if k != "top_ops"}))
        return

    ablations: dict[str, dict] = {
        "baseline": {},
        "no_mix": {"mix": False},
        "no_dropout": {"dropout": 0.0},
        "no_weight_decay": {"weight_decay": False},
        "unroll1": {"unroll": 1},
        "unroll4": {"unroll": 4},
        "remat": {"remat": True},
        "pregather": {"pregather": True},
        "f32_conv": {"dtype": jnp.float32},
    }
    if args.only:
        keep = set(args.only.split(","))
        ablations = {k: v for k, v in ablations.items() if k in keep}

    results = {}
    for name, overrides in ablations.items():
        try:
            sps = _measure_config(**{**base, **overrides})
        except Exception as exc:
            results[name] = {"error": f"{type(exc).__name__}: {str(exc)[:160]}"}
            print(json.dumps({"ablation": name, **results[name]}), flush=True)
            continue
        results[name] = {"samples_per_sec": round(sps, 1)}
        rec = {"ablation": name, **results[name]}
        if "baseline" in results and name != "baseline" \
                and "samples_per_sec" in results["baseline"]:
            rec["delta_vs_baseline_pct"] = round(
                100.0 * (sps / results["baseline"]["samples_per_sec"] - 1), 2
            )
        print(json.dumps(rec), flush=True)

    out = os.path.join(outdir, f"profile_ablations_{stamp}.json")
    with open(out, "w") as f:
        json.dump({
            "config": {**base, "prng": os.environ.get("BENCH_PRNG", "rbg"),
                       "platform": jax.devices()[0].platform},
            "results": results,
        }, f, indent=1)
    print(json.dumps({"written": out}))


if __name__ == "__main__":
    main()
