"""BASELINE config 3: CIFAR-10 ``ann_model`` gossip-SGD, 8 workers, 2D torus.

Reference scenario: the torch MLP (``networks/ann_model.py``) trained with
the (missing) ``MasterNode`` gossip driver — ``Man_Colab.ipynb`` cell 21
documents the surface; no wall-clock was ever recorded for it.  Here the
same workflow runs through :class:`MasterNode`: 8 nodes on a 2x4 torus,
local epoch then gossip, all under jit.

Metrics: steady-state training throughput (samples/sec over all agents) and
the post-epoch consensus residual.
"""

from __future__ import annotations

import jax
import numpy as np

from benchmarks import common
from distributed_learning_tpu.data import normalize, shard_dataset, load_cifar
from distributed_learning_tpu.data.cifar import real_cifar_present
from distributed_learning_tpu.parallel import Topology
from distributed_learning_tpu.parallel.consensus import make_agent_mesh
from distributed_learning_tpu.training import MasterNode

import jax.numpy as jnp


def run(
    n_agents: int = 8,
    batch_size: int | None = None,
    epochs: int = 2,
    n_train: int | None = None,
):
    full = common.full_scale()
    if batch_size is None:
        batch_size = 128 if full else 16
    if n_train is None:
        n_train = 50_000 if full else 512
    (X, y), (Xt, yt) = load_cifar("cifar10")
    X, y = X[:n_train], y[:n_train]
    Xt, yt = Xt[: max(n_train // 8, 128)], yt[: max(n_train // 8, 128)]
    Xn = np.asarray(normalize(jnp.asarray(X)))
    Xtn = np.asarray(normalize(jnp.asarray(Xt)))
    names = list(range(n_agents))
    shards = shard_dataset(Xn, y, names, batch_size=batch_size, seed=0)

    master = MasterNode(
        node_names=names,
        model="ann",
        model_args=[10],
        model_kwargs={"hidden_dim": 512},
        optimizer="sgd",
        optimizer_kwargs={"momentum": 0.9, "weight_decay": 5e-4},
        learning_rate=0.05,
        error="cross_entropy",
        weights=Topology.torus2d(2, n_agents // 2),
        train_loaders=shards,
        test_loader=(Xtn, yt),
        stat_step=50,
        epoch=epochs + 1,
        epoch_cons_num=1,
        batch_size=batch_size,
        mix_times=2,
        mesh=make_agent_mesh(n_agents),
        dropout=False,
    )
    master.initialize_nodes()
    first = master.train_epoch()  # compile + warm
    with common.stopwatch() as t:
        outs = [master.train_epoch() for _ in range(epochs)]
    samples = n_agents * master.epoch_len * batch_size * epochs
    sps = samples / t["s"]
    final = outs[-1]
    common.emit(
        {
            "metric": "cifar10_ann_gossip_sgd_throughput",
            "value": round(sps, 2),
            "unit": "samples/sec",
            # No reference wall-clock exists for this config (the driver is
            # absent from the reference snapshot).
            "vs_baseline": None,
            "config": "cifar10-ann-torus8",
            "n_agents": n_agents,
            "batch_size": batch_size,
            "consensus_residual": float(final["deviation"]),
            "mean_test_acc": None
            if final["test_acc"] is None
            else round(float(np.mean(final["test_acc"])), 4),
            # Accuracy is only meaningful as a CIFAR number on real data;
            # the zero-egress environment falls back to the learnable
            # synthetic stand-in, which this field discloses.
            "data_source": "real-cifar10" if real_cifar_present("cifar10")
            else "synthetic-stand-in",
        }
    )
    return {"samples_per_sec": sps, "final": final, "first": first}


if __name__ == "__main__":
    run()
