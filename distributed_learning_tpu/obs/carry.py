"""Device-side metrics carry: per-step scalars accumulated *inside* the
jitted chunk, flushed to the registry once per chunk host-side.

The repo's hot-path contract (graftlint ``host-sync-in-hot-path``, the
pinned jaxpr/HLO audits) forbids instrumentation that syncs or
communicates per step.  The carry pattern satisfies it by construction:

* inside the jitted chunk, each tracked metric is an ordinary traced
  scalar (loss, grad norm, consensus residual, mixing-round count) that
  the ``lax.scan`` stacks into a ``(steps, ...)`` trace — pure device
  compute, no collectives, no callbacks;
* the chunk returns those traces alongside its existing outputs, and
  the host flushes them with ONE ``np.asarray`` materialization per
  array per chunk (:func:`flush_chunk`) — the same sync the trainer
  already pays to read its loss curve.

The carry is part of the compiled program whether or not a registry is
attached, so toggling observability cannot change the computation: an
obs-enabled run is bit-identical to an obs-disabled one (the oracle
test in ``tests/test_obs.py``).
"""

from __future__ import annotations

from typing import Any, Dict, Mapping, Optional, Sequence

from distributed_learning_tpu.obs.registry import MetricsRegistry

__all__ = ["global_norm", "collect_counters", "flush_chunk"]


def collect_counters(state: Any) -> Dict[str, Any]:
    """One traced scalar per counter name from the ``counters``
    collection a model sowed (``model.apply(..., mutable=["counters"])``):
    layers that sow the same name are summed, or their maximum taken
    where the name ends in ``_max`` or ``_max_all``.  ``{}`` for a model that sows none
    (every model but the held-experts LM): no leaf, so the step program
    is the one it was."""
    import jax
    import jax.numpy as jnp

    out: Dict[str, Any] = {}
    col = state.get("counters") if isinstance(state, Mapping) else None
    for path, leaf in jax.tree_util.tree_flatten_with_path(col or {})[0]:
        name = next(
            str(k.key) for k in reversed(path) if hasattr(k, "key")
        )
        if name not in out:
            out[name] = leaf
        elif name.endswith(("_max", "_max_all")):
            out[name] = jnp.maximum(out[name], leaf)
        else:
            out[name] = out[name] + leaf
    return out


def global_norm(tree: Any):
    """L2 norm of a pytree, accumulated in f32 — the device-side grad
    norm metric (jax-traced; call inside the jitted step).  Equivalent
    to ``optax.global_norm`` but f32-accumulated regardless of the
    state dtype, so bf16 training still reports a usable norm."""
    import jax
    import jax.numpy as jnp

    total = jnp.float32(0.0)
    for leaf in jax.tree.leaves(tree):
        lf = leaf.astype(jnp.float32)
        total = total + jnp.sum(lf * lf)
    return jnp.sqrt(total)


def flush_chunk(
    registry: Optional[MetricsRegistry],
    carry: Mapping[str, Any],
    *,
    step0: int = 0,
    node_names: Optional[Sequence] = None,
    prefix: str = "train",
) -> Dict[str, Any]:
    """Flush one jitted chunk's carried metric traces to ``registry``.

    ``carry`` maps metric name to a per-chunk array: scalars, ``(steps,)``
    traces, ``(steps, n_nodes)`` stacked traces, or — when the chunk is
    an epoch *superstep* — ``(k_epochs, steps, n_nodes)`` doubly-stacked
    traces (the outer epoch scan stacks the per-epoch traces; the two
    leading axes collapse to one ``k*steps`` step trace here, so the
    one-flush-per-chunk contract holds whether the chunk is one epoch or
    K).  Each array is materialized host-side exactly once
    (``np.asarray``) — the single per-chunk sync the carry pattern
    allows.  Per-node chunk means are recorded as
    ``{prefix}.{name}/{node}`` series points at the chunk's final step,
    plus the cross-node mean as ``{prefix}.{name}``; scalars record one
    point.  Returns the materialized numpy arrays (original shapes) so
    the caller reuses them (the trainer feeds the same arrays to its
    stats/telemetry paths — no second sync).
    """
    import numpy as np

    arrays = {k: np.asarray(v) for k, v in carry.items()}
    if registry is None:
        return arrays
    for name, arr in arrays.items():
        key = f"{prefix}.{name}" if prefix else str(name)
        if arr.ndim == 0:
            registry.observe(key, float(arr), step=step0)
            continue
        flat = arr
        if arr.ndim >= 3 and node_names is not None and \
                arr.shape[-1] == len(node_names):
            # (k_epochs, steps, n) superstep trace -> (k*steps, n).
            flat = arr.reshape(-1, arr.shape[-1])
        steps = flat.shape[0]
        end = step0 + steps
        if flat.ndim >= 2 and node_names is not None and \
                flat.shape[1] == len(node_names):
            for a, node in enumerate(node_names):
                registry.observe(
                    f"{key}/{node}", float(flat[:, a].mean()), step=end
                )
        registry.observe(key, float(flat.mean()), step=end)
    return arrays
