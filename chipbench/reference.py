"""The harness's own arithmetic for ``correct``: none of the program's.

A Metropolis matrix built from the graph, the consensus residual, and
``W^r X`` leaf by leaf in plain ``jax.numpy``.
"""

from __future__ import annotations

import jax
import jax.numpy as jnp
import numpy as np


def adjacency(kind: str, n: int) -> np.ndarray:
    A = np.zeros((n, n), bool)
    if kind == "ring":
        for i in range(n):
            A[i, (i + 1) % n] = A[(i + 1) % n, i] = True
    elif kind == "complete":
        A[:] = ~np.eye(n, dtype=bool)
    else:
        raise ValueError(f"no reference graph {kind!r}")
    return A


def metropolis(A: np.ndarray) -> np.ndarray:
    """``W[i, j] = 1 / (1 + max(d_i, d_j))`` on edges, rows summing to 1."""
    d = A.sum(axis=1)
    W = np.where(A, 1.0 / (1.0 + np.maximum.outer(d, d)), 0.0)
    np.fill_diagonal(W, 1.0 - W.sum(axis=1))
    return W


def placed(tree, devices: list, sharded: bool) -> bool:
    """Every leaf sits on exactly the cell's devices, and under the sharded
    layout has one shard on each."""
    want = set(devices)
    return all(
        leaf.sharding.device_set == want
        and (not sharded or len(leaf.addressable_shards) == len(want))
        for leaf in jax.tree.leaves(tree)
    )


@jax.jit
def max_deviation(stacked) -> jax.Array:
    """Max over agents of the L2 distance of the agent's whole parameter
    vector from the agents' mean (leading axis = agents)."""
    sq = 0.0
    for leaf in jax.tree.leaves(stacked):
        x = leaf.astype(jnp.float32)
        d = x - x.mean(axis=0, keepdims=True)
        sq = sq + jnp.sum(d * d, axis=tuple(range(1, x.ndim)))
    return jnp.sqrt(jnp.max(sq))


def mixed_error(W: np.ndarray, rounds: int, before, after) -> tuple:
    """(max |after - W^rounds before|, max |mean(after) - mean(before)|),
    leaf by leaf in plain ``jax.numpy`` at the highest precision; the power
    of ``W`` is taken in float64 on the host."""
    Wr = jnp.asarray(
        np.linalg.matrix_power(np.asarray(W, np.float64), int(rounds)),
        jnp.float32,
    )
    return tuple(float(v) for v in _mixed_error(Wr, before, after))


@jax.jit
def _mixed_error(Wr, before, after):
    err = drift = 0.0
    for x, y in zip(jax.tree.leaves(before), jax.tree.leaves(after),
                    strict=True):
        want = jnp.tensordot(Wr, x, 1, precision="highest")
        err = jnp.maximum(err, jnp.abs(want - y).max())
        drift = jnp.maximum(drift, jnp.abs(x.mean(0) - y.mean(0)).max())
    return err, drift
