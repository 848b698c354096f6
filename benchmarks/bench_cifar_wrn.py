"""BASELINE config 4: CIFAR-10 WRN-28-10 gossip-SGD on a v5e-8 ring.

The reference's only recorded wall-clock for this model is the *single
node* torch run: WRN-28-10, 100 CIFAR-10 epochs, 8h18m07s on a Tesla T4 =
167.3 samples/sec (``CIFAR_10_Baseline.ipynb`` cell 9).  Its gossip driver
for this model is absent from the snapshot, so the centralized number is
the anchor; our run additionally pays for gossip every epoch, which only
handicaps the comparison.

Also records the north-star residual metric: after an epoch of divergent
local SGD, how many gossip rounds until the consensus residual < 1e-4
(BASELINE.json: "<= 1e-4 consensus residual ... in <= 200 rounds").

On non-TPU hosts the model shrinks (depth/widen/agents) so the script runs
anywhere; the recorded headline number is the TPU configuration.
"""

from __future__ import annotations

import jax
import jax.numpy as jnp
import numpy as np

from benchmarks import common
from distributed_learning_tpu.data import load_cifar, normalize, shard_dataset
from distributed_learning_tpu.parallel import Topology
from distributed_learning_tpu.parallel.consensus import make_agent_mesh
from distributed_learning_tpu.training import MasterNode

T4_SAMPLES_PER_SEC = 100 * 50_000 / 29_887.0  # BASELINE.md wall-clock


def run(
    n_agents: int | None = None,
    depth: int | None = None,
    widen: int | None = None,
    batch_size: int | None = None,
    epochs: int = 1,
):
    full = common.full_scale()
    n_agents = n_agents or (8 if full else 2)
    depth = depth or (28 if full else 10)
    widen = widen or (10 if full else 1)
    batch_size = batch_size or (128 if full else 8)
    n_train = 50_000 if full else 256

    (X, y), (Xt, yt) = load_cifar("cifar10")
    X, y = X[:n_train], y[:n_train]
    Xt, yt = Xt[:256], yt[:256]
    Xn = np.asarray(normalize(jnp.asarray(X)))
    Xtn = np.asarray(normalize(jnp.asarray(Xt)))
    names = list(range(n_agents))
    shards = shard_dataset(Xn, y, names, batch_size=batch_size, seed=0)

    master = MasterNode(
        node_names=names,
        model="wide-resnet",
        model_args=[10],
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
        weights=Topology.ring(n_agents),
        train_loaders=shards,
        test_loader=(Xtn, yt),
        stat_step=100,
        epoch=epochs + 1,
        epoch_cons_num=1,
        batch_size=batch_size,
        mix_times=1,
        mesh=make_agent_mesh(n_agents),
    )
    master.initialize_nodes()
    master.train_epoch()  # compile + warm
    with common.stopwatch() as t:
        outs = [master.train_epoch() for _ in range(epochs)]
    samples = n_agents * master.epoch_len * batch_size * epochs
    sps = samples / t["s"]
    n_chips = max(len(set(jax.devices())), 1) if common.platform() == "tpu" else 1
    common.emit(
        {
            "metric": f"cifar10_wrn{depth}x{widen}_gossip_sgd_throughput",
            "value": round(sps, 2),
            "unit": "samples/sec",
            "vs_baseline": round(sps / T4_SAMPLES_PER_SEC, 3)
            if (depth, widen) == (28, 10)
            else None,
            "config": "cifar10-wrn-ring",
            "n_agents": n_agents,
            "batch_size": batch_size,
            "samples_per_sec_per_chip": round(sps / n_chips, 2),
            "consensus_residual": float(outs[-1]["deviation"]),
        }
    )

    # North-star: rounds to 1e-4 residual from post-local-SGD divergence.
    # Re-run one epoch without mixing to get genuinely divergent replicas.
    master2 = MasterNode(
        node_names=names,
        model="wide-resnet",
        model_args=[10],
        model_kwargs={
            "depth": depth,
            "widen_factor": widen,
            "dropout_rate": 0.3,
            "dtype": jnp.bfloat16,
        },
        optimizer="sgd",
        optimizer_kwargs={"momentum": 0.9},
        learning_rate=0.1,
        error="cross_entropy",
        weights=Topology.ring(n_agents),
        train_loaders=shards,
        stat_step=100,
        epoch=2,
        epoch_cons_num=10**9,  # never mix during the epoch
        batch_size=batch_size,
        mesh=make_agent_mesh(n_agents),
    )
    master2.initialize_nodes()
    master2.train_epoch()
    params = master2.state[0]
    r0 = float(master2.engine.max_deviation(params))
    _, rounds, res = master2.engine.mix_until(params, eps=1e-4, max_rounds=500)
    common.emit(
        {
            "metric": "cifar10_wrn_rounds_to_1e-4_residual",
            "value": int(rounds),
            "unit": "rounds",
            "vs_baseline": round(200.0 / max(int(rounds), 1), 3),  # target <= 200
            "config": "cifar10-wrn-ring",
            "initial_residual": r0,
            "final_residual": float(res),
        }
    )
    return {"samples_per_sec": sps, "rounds_to_residual": int(rounds)}


if __name__ == "__main__":
    run()
