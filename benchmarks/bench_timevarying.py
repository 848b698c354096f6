"""BASELINE config 5: CIFAR-100 WRN, time-varying random graph +
Chebyshev-accelerated averaging.

Every epoch resamples a connected G(n, p) graph; mixing runs through the
engine's traced-W path (no recompilation per graph) with the Chebyshev
semi-iteration schedule computed host-side from that epoch's gamma.

Reference anchor: CIFAR-100 WRN-28-10 single-node, 100 epochs, 4h11m35s on
a Tesla P100 = 331.7 samples/sec (``CIFAR_100_Baseline.ipynb`` cell 9).
The second record isolates the Chebyshev benefit: rounds-to-1e-4 residual
with and without acceleration over the same sequence of random graphs.
"""

from __future__ import annotations

import jax
import jax.numpy as jnp
import numpy as np

from benchmarks import common
from distributed_learning_tpu.data import load_cifar, normalize, shard_dataset
from distributed_learning_tpu.parallel import Topology
from distributed_learning_tpu.parallel.consensus import (
    ConsensusEngine,
    make_agent_mesh,
)
from distributed_learning_tpu.parallel.schedule import chebyshev_omegas
from distributed_learning_tpu.parallel.topology import gamma as exact_gamma
from distributed_learning_tpu.training import MasterNode

P100_SAMPLES_PER_SEC = 100 * 50_000 / 15_095.0  # BASELINE.md wall-clock


def run(
    n_agents: int | None = None,
    depth: int | None = None,
    widen: int | None = None,
    batch_size: int | None = None,
    epochs: int = 2,
    edge_p: float = 0.4,
):
    full = common.full_scale()
    n_agents = n_agents or (8 if full else 2)
    depth = depth or (28 if full else 10)
    widen = widen or (10 if full else 1)
    batch_size = batch_size or (128 if full else 8)
    n_train = 50_000 if full else 256

    (X, y), (Xt, yt) = load_cifar("cifar100")
    X, y = X[:n_train], y[:n_train]
    Xt, yt = Xt[:256], yt[:256]
    Xn = np.asarray(normalize(jnp.asarray(X), dataset="cifar100"))
    Xtn = np.asarray(normalize(jnp.asarray(Xt), dataset="cifar100"))
    names = list(range(n_agents))
    shards = shard_dataset(Xn, y, names, batch_size=batch_size, seed=0)

    def schedule(epoch: int) -> Topology:
        return Topology.erdos_renyi(n_agents, edge_p, seed=1000 + epoch)

    master = MasterNode(
        node_names=names,
        model="wide-resnet",
        model_args=[100],
        model_kwargs={
            "depth": depth,
            "widen_factor": widen,
            "dropout_rate": 0.3,
            "dtype": jnp.bfloat16,
        },
        optimizer="sgd",
        optimizer_kwargs={"momentum": 0.9, "weight_decay": 5e-4},
        learning_rate=0.1,
        error="cross_entropy",
        train_loaders=shards,
        test_loader=(Xtn, yt),
        stat_step=100,
        epoch=epochs + 1,
        epoch_cons_num=1,
        batch_size=batch_size,
        mix_times=4,
        topology_schedule=schedule,
        chebyshev=True,
        mesh=make_agent_mesh(n_agents),
    )
    master.initialize_nodes()
    master.train_epoch()  # compile + warm
    with common.stopwatch() as t:
        outs = [master.train_epoch() for _ in range(epochs)]
    samples = n_agents * master.epoch_len * batch_size * epochs
    sps = samples / t["s"]
    common.emit(
        {
            "metric": f"cifar100_wrn{depth}x{widen}_timevarying_cheby_throughput",
            "value": round(sps, 2),
            "unit": "samples/sec",
            "vs_baseline": round(sps / P100_SAMPLES_PER_SEC, 3)
            if (depth, widen) == (28, 10)
            else None,
            "config": "cifar100-wrn-timevarying-chebyshev",
            "n_agents": n_agents,
            "consensus_residual": float(outs[-1]["deviation"]),
        }
    )

    # Isolate the averaging acceleration: same random-graph sequence, plain
    # vs Chebyshev mixing on a synthetic divergent state.
    engine = ConsensusEngine(Topology.ring(n_agents).metropolis_weights())
    rng = np.random.default_rng(0)
    dim = 1 << 16 if full else 1 << 12
    x0 = jnp.asarray(rng.normal(size=(n_agents, dim)).astype(np.float32))
    k_per_graph = 3
    target = 1e-4

    def rounds_to_target(cheby: bool) -> int:
        x = x0
        for e in range(200):
            W = schedule(e).metropolis_weights()
            if cheby:
                om = chebyshev_omegas(exact_gamma(W), k_per_graph)
                x = engine.mix_chebyshev_with(x, W, om)
            else:
                x = engine.mix_with(x, W, times=k_per_graph)
            if float(engine.max_deviation(x)) < target:
                return (e + 1) * k_per_graph
        return 200 * k_per_graph

    plain = rounds_to_target(False)
    cheby = rounds_to_target(True)
    common.emit(
        {
            "metric": "timevarying_chebyshev_round_reduction",
            "value": round(plain / max(cheby, 1), 3),
            "unit": "x fewer rounds",
            "vs_baseline": None,
            "config": "cifar100-wrn-timevarying-chebyshev",
            "rounds_plain": plain,
            "rounds_chebyshev": cheby,
            "target_residual": target,
        }
    )
    return {
        "samples_per_sec": sps,
        "rounds_plain": plain,
        "rounds_chebyshev": cheby,
    }


if __name__ == "__main__":
    run()
