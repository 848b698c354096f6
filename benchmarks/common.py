"""Shared utilities for the benchmark harness.

Each script in this package is one of the five target configurations from
the driver's ``BASELINE.json`` (``configs`` list).  Every script prints one
JSON line per recorded metric:

    {"metric": str, "value": float, "unit": str, "vs_baseline": float|null,
     "config": str, "platform": str, ...}

``vs_baseline`` is the ratio versus the corresponding recorded reference
number from ``BASELINE.md`` when one exists (>1.0 = better), else null.

Sizing: the full problem sizes run on whatever device JAX finds, and every
record names that device (``platform``); only ``BENCH_SMOKE=1`` (which the
tests set) selects the smallest sizes.  Timed regions end in
``jax.block_until_ready`` (:func:`sync`).
"""

from __future__ import annotations

import contextlib
import json
import os
import time
from typing import Any, Dict, Iterator

import jax

from distributed_learning_tpu.utils.compile_cache import enable_compile_cache

__all__ = [
    "platform",
    "full_scale",
    "smoke",
    "emit",
    "stopwatch",
    "sync",
]


def platform() -> str:
    return jax.devices()[0].platform


def smoke() -> bool:
    return os.environ.get("BENCH_SMOKE") == "1"


def full_scale() -> bool:
    """Full problem sizes, on any device: everything but the tests'
    ``BENCH_SMOKE=1`` run.  Every script asks this before it compiles
    anything, so a full-scale run also turns the persistent compilation
    cache on here (``utils/compile_cache.py``); the tests never do."""
    if smoke():
        return False
    enable_compile_cache()
    return True


def emit(record: Dict[str, Any]) -> Dict[str, Any]:
    """Print one JSON metric line (and append to $BENCH_OUT if set).

    Every emitted metric also lands in the program's perf ledger
    (``benchmarks/results/perf_ledger.jsonl`` / ``$DLT_PERF_LEDGER``,
    ``obs/cost.py``) so ``obs-report --ledger`` renders the cross-session
    trend; the append is best-effort and cannot fail the benchmark."""
    record = dict(record)
    record.setdefault("platform", platform())
    line = json.dumps(record)
    print(line, flush=True)
    out = os.environ.get("BENCH_OUT")
    if out:
        with open(out, "a") as f:
            f.write(line + "\n")
    from distributed_learning_tpu.obs.cost import ledger_append

    ledger_append({
        "source": "benchmarks",
        "env": {"platform": record.get("platform")},
        **record,
    })
    return record


def sync(x) -> None:
    """Wait until every array in ``x`` is computed — what ends each
    timed region."""
    jax.block_until_ready(x)


@contextlib.contextmanager
def stopwatch() -> Iterator[Dict[str, float]]:
    """``with stopwatch() as t: ...; t['s']`` — wall seconds of the block."""
    box: Dict[str, float] = {}
    t0 = time.perf_counter()
    try:
        yield box
    finally:
        box["s"] = time.perf_counter() - t0
