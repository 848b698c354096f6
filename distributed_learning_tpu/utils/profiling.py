"""Tracing & debug instrumentation.

The reference's tracing is ad-hoc ``_debug(...)`` printers gated by a
``debug`` flag (``consensus_asyncio.py:52-57``, ``master.py:63-68``,
``agent.py:46-51``) plus notebook ``%time`` cells.  TPU-native
equivalents:

* :func:`trace` — a context manager around ``jax.profiler`` producing a
  TensorBoard-loadable trace of device execution;
* :func:`annotate` — named ``TraceAnnotation`` spans that show up inside
  the profile;
* :class:`DebugLogger` — the reference's debug-flag pattern as a small
  structured logger with per-round residual reporting
  (``log_residual(round, residual)``), usable anywhere the reference
  passed its ``logger``/``debug`` args.
"""

from __future__ import annotations

import contextlib
import logging
import time
from typing import Iterator, Optional

__all__ = [
    "trace",
    "annotate",
    "DebugLogger",
    "enable_debug_logging",
    "summarize_trace",
    "format_trace_summary",
]


def enable_debug_logging(name: str = "dlt") -> logging.Logger:
    """Make the framework's named loggers (``dlt.comm.agent.<token>``,
    ``dlt.comm.master``, ...) visible: set the ``dlt`` root to DEBUG and
    attach a stderr handler if none is configured.

    The comm layer's legacy ``debug=True`` flags call this, so the old
    print-style debugging experience survives the move to ``logging``;
    applications that configure logging themselves never need it.
    """
    logger = logging.getLogger(name)
    logger.setLevel(logging.DEBUG)
    if not logger.handlers and not logging.getLogger().handlers:
        handler = logging.StreamHandler()
        handler.setFormatter(
            logging.Formatter("%(name)s %(levelname).1s %(message)s")
        )
        logger.addHandler(handler)
    return logger


@contextlib.contextmanager
def trace(log_dir: str, *, host_profile: bool = True) -> Iterator[None]:
    """Capture a ``jax.profiler`` trace of the enclosed block.

    View with TensorBoard (``tensorboard --logdir <log_dir>``) or
    ``xprof``.  Host-side Python activity is included unless
    ``host_profile=False``.
    """
    import jax

    jax.profiler.start_trace(log_dir, create_perfetto_link=False)
    try:
        yield
    finally:
        jax.profiler.stop_trace()


def annotate(name: str, **ids):
    """``jax.profiler.TraceAnnotation(name, **ids)``: a named host span on
    the profiler's clock, with ``ids`` (``epoch=7``, ``call=3``) as the
    event's stats.  A flag test while no profiler session is open."""
    from jax.profiler import TraceAnnotation

    return TraceAnnotation(name, **ids)


class DebugLogger:
    """Structured replacement for the reference's injected logger +
    ``debug`` flag; quacks like ``logging.Logger`` for ``Mixer(logger=)``.
    """

    def __init__(self, name: str = "dlt", *, enabled: bool = True,
                 logger: Optional[logging.Logger] = None):
        self.enabled = enabled
        self._log = logger or logging.getLogger(name)
        self._t0 = time.perf_counter()
        self.residuals: list = []

    def debug(self, msg, *args):
        if self.enabled:
            self._log.debug("[%7.3fs] %s", time.perf_counter() - self._t0,
                            msg % args if args else msg)

    info = debug

    def log_residual(self, round_idx: int, residual: float) -> None:
        """Record + report a per-round consensus residual (the metric the
        reference's Mixer debug lines printed, ``mixer.py:37,54``)."""
        self.residuals.append((round_idx, float(residual)))
        self.debug(f"round {round_idx}: residual {residual:.3e}")


def _as_percent(row: dict):
    """Self-time share in percent regardless of source tool:
    ``framework_op_stats`` reports 0-100 percents, ``hlo_stats`` reports
    0-1 fractions.  Explicit None checks — a legitimate 0.0 must not
    fall through to the other column."""
    pct = row.get("device_total_self_time_percent")
    if pct is not None:
        return pct
    frac = row.get("total_self_time_as_fraction")
    if frac is not None:
        return frac * 100.0
    return None


def summarize_trace(
    log_dir: str, *, top: int = 15, tool: str = "framework_op_stats"
) -> list:
    """Digest a ``jax.profiler`` trace into the top-``top`` ops by
    self-time — the "where did the step go" table, without TensorBoard.

    Parses the ``.xplane.pb`` files under ``log_dir`` with xprof's
    converter (the TensorBoard profile plugin's own backend).  Returns a
    list of dicts sorted by total self-time, each with ``operation``,
    ``type``, ``occurrences``, ``total_self_us``, ``avg_self_us``, and
    (on device rows) ``device_self_pct``.  Raises ``FileNotFoundError``
    when the dir holds no xplanes and ``ImportError`` when xprof isn't
    installed — callers decide whether that is fatal.
    """
    import glob
    import json as _json

    paths = sorted(
        glob.glob(f"{log_dir}/**/*.xplane.pb", recursive=True)
    )
    if not paths:
        raise FileNotFoundError(f"no .xplane.pb under {log_dir}")
    from xprof.convert import raw_to_tool_data as _rtd  # tensorboard plugin

    # No output-format option: the converter returns gviz-DataTable JSON,
    # which is exactly what the parser below consumes.
    data, _ = _rtd.xspace_to_tool_data(paths, tool, {})
    if isinstance(data, bytes):
        data = data.decode()
    table = _json.loads(data)
    # DataTable-style payload: a list of {"cols": [...], "rows": [...]}
    # blocks (framework_op_stats emits device and host tables separately).
    blocks = table if isinstance(table, list) else [table]
    out = []
    for block in blocks:
        if not isinstance(block, dict) or "cols" not in block:
            continue
        cols = [c["id"] for c in block["cols"]]
        for r in block.get("rows") or block.get("data") or []:
            cells = r.get("c") if isinstance(r, dict) else r
            row = dict(zip(cols, [
                c.get("v") if isinstance(c, dict) else c for c in cells
            ]))
            # Column ids differ per tool (framework_op_stats vs
            # hlo_stats); coalesce the common concepts.  Numeric fields
            # use first-non-None (not `or`): a legitimate 0.0 must not
            # fall through to the other tool's absent column.
            first = lambda *keys: next(
                (row[k] for k in keys if row.get(k) is not None), None
            )
            out.append({
                "operation": first(
                    "operation", "hlo_op_name", "hlo_op_expression"
                ),
                "type": first("type", "category"),
                "host_or_device": row.get("host_or_device"),
                "occurrences": row.get("occurrences"),
                "total_self_us": first(
                    "total_self_time", "total_self_time_us"
                ),
                "avg_self_us": first("avg_self_time", "avg_self_time_us"),
                "device_self_pct": _as_percent(row),
            })
    out.sort(key=lambda d: -(d["total_self_us"] or 0.0))
    return out[:top]


def format_trace_summary(rows: list) -> str:
    """Readable table for :func:`summarize_trace` output."""
    lines = [
        f"{'self us':>12} {'avg us':>10} {'n':>6} {'where':>6}  operation"
    ]
    for r in rows:
        lines.append(
            f"{(r['total_self_us'] or 0):12.1f} {(r['avg_self_us'] or 0):10.2f} "
            f"{int(r['occurrences'] or 0):6d} {(r['host_or_device'] or '?'):>6}  "
            f"{(r['type'] or '')}: {str(r['operation'] or '')[:70]}"
        )
    return "\n".join(lines)
