"""Run one cell of the chip benchmark once.

    python -m chipbench.run --workload <name> --seed <n> --seconds <s> --trace <0|1>

The cell's file (``workloads/<name>.json``) names its configuration
(``configs/``), its driver (``drivers/``) and the metrics it reports
(``metrics/``, each with a reducer from ``reducers/``).  The last line of
standard output is one JSON object: ``correct``, ``attempted``,
``failed``, ``metrics``, ``device`` and, traced, ``breakdown``.  Without a
TPU, or with fewer chips than the cell asks for, it exits non-zero and
prints no result; a compilation inside the measured window is an error.
"""

from __future__ import annotations

import time

_T0 = time.perf_counter()  # set-up is counted from here, imports included

import argparse  # noqa: E402
import importlib  # noqa: E402
import json  # noqa: E402
import os  # noqa: E402
import shutil  # noqa: E402
import sys  # noqa: E402
from types import SimpleNamespace  # noqa: E402

ROOT = os.path.dirname(os.path.abspath(__file__))
#: What a run leaves behind (traces), inside the checkout and git-ignored.
OUT_DIR = os.path.join(os.path.dirname(ROOT), ".chipbench_out")


def load(kind: str, name: str) -> dict:
    with open(os.path.join(ROOT, kind, name + ".json"), encoding="utf-8") as fh:
        return json.load(fh)


def say(msg: str) -> None:
    print(msg, flush=True)


def timed(unit) -> dict:
    t0 = time.perf_counter()
    out = unit()
    t1 = time.perf_counter()
    return {**out, "start": t0, "end": t1, "seconds": t1 - t0}


def device_report(devices: list) -> dict:
    """The device as JAX reports it.  The TPU runtime counts live buffers
    (``peak_bytes_in_use``) apart from the region it reserves for the
    loaded programs' temporaries (``peak_bytes_reserved``); both come out
    of the chip's memory, so the peak is their sum, on the fullest chip."""
    import jax

    stats = [d.memory_stats() or {} for d in devices]
    say("memory: " + json.dumps(stats[0]))
    peaks = [
        s.get("peak_bytes_in_use", 0) + s.get("peak_bytes_reserved", 0)
        for s in stats
    ]
    return {
        "platform": devices[0].platform,
        "kind": devices[0].device_kind,
        "count": len(jax.devices()),
        "memory_peak_bytes": max(peaks) or None,
    }


def reduce_trace(ctx, cell: dict, logdir: str):
    """The cell's per-layer metrics, the device's busy seconds and the
    breakdown, from the trace under ``logdir``."""
    from chipbench import trace as tr

    ctx.trace = tr.load(tr.newest_xplane(logdir))
    ops = ctx.trace.line(tr.OPS)
    busy = [tr.busy_intervals(events) for events in ops]
    ctx.busy_s = (
        sum(e - s for iv in busy for s, e in iv) * 1e-9 / len(busy)
        if busy else None
    )
    metrics = {}
    for name in cell["per_layer"]:
        m = load("metrics", name)
        reducer = importlib.import_module(f"chipbench.reducers.{m['reducer']}")
        value = reducer.reduce(ctx, **m.get("args", {}))
        if value is not None:
            metrics[name] = {"value": value, "unit": m["unit"]}
    breakdown = None
    if ops:
        self_s = tr.self_seconds(ops[0])
        gaps = sorted(
            (b[0] - a[1] for a, b in zip(busy[0], busy[0][1:])), reverse=True
        )
        breakdown = {
            "device_ops": sorted(
                ([k, v] for k, v in self_s.items()), key=lambda kv: -kv[1]
            )[:10],
            "idle_gaps": [["host: not attributed", g * 1e-9] for g in gaps[:5]],
        }
    return metrics, breakdown


def run_cell(workload: str, seed: int, seconds: float, trace: bool) -> dict:
    """Set up, warm up, measure (or trace) and check one cell; returns the
    result object.  ``main`` checks the device first; this does not."""
    import jax

    from chipbench.meter import CompileMeter
    from distributed_learning_tpu.utils.compile_cache import enable_compile_cache

    cell = load("workloads", workload)
    config = load("configs", cell["config"])
    devices = jax.devices()[: cell["chips"]]
    say(f"compile cache: {enable_compile_cache()}")
    meter = CompileMeter()
    driver = importlib.import_module(
        f"chipbench.drivers.{cell['driver']}"
    ).Driver(cell, config, seed, devices)
    driver.warm_up()
    setup_s = time.perf_counter() - _T0
    setup = SimpleNamespace(compile_s=meter.compile_s, events=meter.events,
                            hits=meter.hits, misses=meter.misses)
    say(f"set-up {setup_s:.2f} s: compile or cache load {setup.compile_s:.2f} s"
        f" in {setup.events} programs, cache hits {setup.hits} misses "
        f"{setup.misses}")

    units = []
    if trace:
        logdir = os.path.join(OUT_DIR, "trace", workload)
        shutil.rmtree(logdir, ignore_errors=True)
        options = jax.profiler.ProfileOptions()
        options.python_tracer_level = 0
        jax.profiler.start_trace(logdir, profiler_options=options)
        try:
            for _ in range(cell["trace_units"]):
                units.append(timed(driver.unit))
        finally:
            jax.profiler.stop_trace()
    else:
        end = time.perf_counter() + seconds
        while time.perf_counter() < end:
            units.append(timed(driver.unit))
    span_s = units[-1]["end"] - units[0]["start"]
    if meter.events != setup.events:
        raise SystemExit(
            f"{meter.events - setup.events} program(s) compiled or loaded "
            "inside the measured window: warm-up missed a shape"
        )

    device = device_report(devices)  # before the check puts its own buffers there
    checks = driver.check(units)
    say("check: " + json.dumps(checks))
    failed = sum(not u["ok"] for u in units)
    result = {
        "correct": failed == 0
        and all(v for v in checks.values() if isinstance(v, bool)),
        "attempted": len(units),
        "failed": failed,
    }
    if trace:
        window = {
            k: sum(u[k] for u in units)
            for k in units[0] if isinstance(units[0][k], int)
            and not isinstance(units[0][k], bool)
        }
        ctx = SimpleNamespace(
            window=window, window_s=span_s, work=driver.work(config),
            peaks=load(".", "peaks")["devices"], kind=device["kind"],
            chips=len(devices),
            setup_compile_s=setup.compile_s, trace=None, busy_s=None,
        )
        result["metrics"], breakdown = reduce_trace(ctx, cell, logdir)
        device.update(busy_s=ctx.busy_s, window_s=span_s)
        if breakdown:
            result["breakdown"] = breakdown
    else:
        values = {**driver.metrics(units, span_s), "setup_s": setup_s}
        result["metrics"] = {
            name: {"value": values[m["from"]], "unit": m["unit"]}
            for name in cell["end_to_end"]
            for m in [load("metrics", name)]
        }
    result["device"] = device
    return result


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--trace", type=int, choices=(0, 1), required=True)
    args = ap.parse_args(argv)

    import jax

    chips = load("workloads", args.workload)["chips"]
    found = jax.devices()
    if found[0].platform != "tpu" or len(found) < chips:
        raise SystemExit(
            f"chipbench needs {chips} TPU chip(s); JAX found "
            f"{len(found)} x {found[0].platform!r}"
        )
    result = run_cell(args.workload, args.seed, args.seconds, bool(args.trace))
    if args.trace and not result["device"]["busy_s"]:
        raise SystemExit("the trace shows no operation on the device")
    print(json.dumps(result), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
