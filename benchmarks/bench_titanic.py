"""BASELINE config 1: Titanic logreg consensus-GD, 4 workers, ring graph.

Reference scenario: ``notebooks/Titanic Consensus GD test.ipynb`` cells
14-15 — 4 agents with contiguous shards, manual-gradient logistic
regression with the ``alpha * (it+1)^-0.5`` schedule, full gossip
convergence after every SGD step; recorded test accuracy 0.7978 for both
the centralized and the K4 consensus runs (BASELINE.md).

Here the entire iterate-then-gossip loop is one jitted ``fori_loop``: a
vmapped subgradient step for the 4 replicas and a ``mix_until`` inner
``while_loop`` per iteration (the reference's asyncio message rounds).
Metrics: iterations/sec of the full consensus-GD loop, final per-agent test
accuracy (vs the recorded 0.7978), and the final parameter spread.
"""

from __future__ import annotations

import jax
import jax.numpy as jnp
import numpy as np

from benchmarks import common
from distributed_learning_tpu.data import load_titanic, split_data
from distributed_learning_tpu.models import logreg_loss
from distributed_learning_tpu.models.logreg import accuracy as logreg_accuracy
from distributed_learning_tpu.parallel import Topology
from distributed_learning_tpu.parallel.consensus import (
    ConsensusEngine,
    make_agent_mesh,
)

ALPHA, TAU = 0.1, 1e-4
REFERENCE_ACC = 0.7978  # Titanic nb cell 15 (K4 / 4-agent recorded value)


def run(n_agents: int = 4, iters: int | None = None, mix_eps: float = 1e-9):
    if iters is None:
        iters = 4000 if common.full_scale() else 100
    X_tr, y_tr, X_te, y_te = load_titanic()
    shards = split_data(X_tr, y_tr, n_agents)
    m = min(len(s[0]) for s in shards.values())
    Xs = jnp.stack([jnp.asarray(shards[i][0][:m]) for i in range(n_agents)])
    ys = jnp.stack(
        [jnp.asarray(shards[i][1][:m], jnp.float32) for i in range(n_agents)]
    )
    engine = ConsensusEngine(
        Topology.ring(n_agents).metropolis_weights(),
        mesh=make_agent_mesh(n_agents),
    )

    def local_step(w, X, y, lr):
        g = jax.grad(logreg_loss)(w, X, y, TAU)
        return w - lr * g

    vstep = jax.vmap(local_step, in_axes=(0, 0, 0, None))

    @jax.jit
    def run_loop(w0, iters):
        def body(it, w):
            lr = ALPHA * (it + 1.0) ** -0.5
            w = vstep(w, Xs, ys, lr)
            w, _, _ = engine.mix_until(w, eps=mix_eps, max_rounds=300)
            return w

        return jax.lax.fori_loop(0, iters, body, w0)

    w0 = engine.shard(jnp.zeros((n_agents, Xs.shape[-1])))
    w = run_loop(w0, 2)  # compile + warm
    common.sync(w)
    with common.stopwatch() as t:
        w = run_loop(w0, iters)
        common.sync(w)

    accs = [
        float(logreg_accuracy(w[a], jnp.asarray(X_te), jnp.asarray(y_te, jnp.float32)))
        for a in range(n_agents)
    ]
    spread = float(jnp.max(jnp.abs(w - w.mean(axis=0))))
    its_per_sec = iters / t["s"]
    common.emit(
        {
            "metric": "titanic_consensus_gd_iters_per_sec",
            "value": round(its_per_sec, 2),
            "unit": "iters/sec",
            # The reference records no wall clock for this run; accuracy is
            # the recorded anchor (next record).
            "vs_baseline": None,
            "config": "titanic-logreg-ring4",
            "iters": iters,
            "n_agents": n_agents,
        }
    )
    common.emit(
        {
            "metric": "titanic_consensus_gd_test_accuracy",
            "value": round(float(np.mean(accs)), 4),
            "unit": "accuracy",
            "vs_baseline": round(float(np.mean(accs)) / REFERENCE_ACC, 4),
            "config": "titanic-logreg-ring4",
            "per_agent": [round(a, 4) for a in accs],
            "param_spread": spread,
        }
    )
    return {"accs": accs, "spread": spread, "iters_per_sec": its_per_sec}


if __name__ == "__main__":
    run()
