"""Consensus-only cells: no training, the mix does all the work.

The stacked parameter tree of the configuration's model (its own leaf
structure, every agent's leaves normal from the seed, made on the device
in one jitted call) goes through ``ConsensusEngine.mix_until`` again and
again from the same input; one call, ended in ``block_until_ready``, is
one timed unit.  ``layout: sharded`` puts one agent on each chip
(``make_agent_mesh``, ``engine.shard``, ``ppermute`` gossip).
"""

from __future__ import annotations

import statistics

import jax
import jax.numpy as jnp
import numpy as np
from jax.sharding import NamedSharding, PartitionSpec, SingleDeviceSharding

from chipbench import reference
from chipbench.drivers.train import build_model


class Driver:
    def __init__(self, cell: dict, config: dict, seed: int, devices: list):
        from distributed_learning_tpu.parallel.consensus import (
            ConsensusEngine,
            make_agent_mesh,
        )
        from distributed_learning_tpu.parallel.topology import Topology

        traffic = cell["traffic"]
        n = config["agents"]
        self.devices = devices
        self.eps = traffic["mix_eps"]
        self.max_rounds = traffic["max_rounds"]
        self.tol = traffic["tol"]
        self.sharded = traffic["layout"] == "sharded"
        topology = config["topology"]
        if topology["weights"] != "metropolis":
            raise ValueError(topology)
        self.W = reference.metropolis(reference.adjacency(topology["kind"], n))
        mesh = make_agent_mesh(n) if self.sharded else None
        self.engine = ConsensusEngine(
            getattr(Topology, topology["kind"])(n).metropolis_weights(),
            mesh=mesh,
        )
        model = build_model(config["model"])
        x0 = jnp.zeros([1] + config["model"]["input_shape"],
                       config["model"]["input_dtype"])
        shapes = jax.eval_shape(
            lambda: model.init(jax.random.key(0), x0, train=False)["params"]
        )
        std = traffic["init_std"]

        def make(key):
            leaves, treedef = jax.tree.flatten(shapes)
            return treedef.unflatten([
                std * jax.random.normal(
                    jax.random.fold_in(key, i), (n,) + s.shape, jnp.float32)
                for i, s in enumerate(leaves)
            ])

        where = (NamedSharding(mesh, PartitionSpec("agents")) if self.sharded
                 else SingleDeviceSharding(devices[0]))
        tree = jax.jit(make, out_shardings=where)(
            jax.random.key(seed % (1 << 31)))
        self.x = self.engine.shard(tree)
        self.out = None
        self.state_bytes = sum(l.nbytes for l in jax.tree.leaves(self.x))

    def warm_up(self) -> None:
        self.unit()

    def unit(self) -> dict:
        out, rounds, residual = jax.block_until_ready(self.engine.mix_until(
            self.x, eps=self.eps, max_rounds=self.max_rounds))
        self.out = out
        rounds, residual = int(rounds), float(residual)
        return {
            "ok": rounds < self.max_rounds and residual <= self.eps,
            "work": 1, "calls": 1, "rounds": rounds, "residual": residual,
        }

    def metrics(self, units: list, span_s: float) -> dict:
        ms = sorted(1e3 * u["seconds"] for u in units)
        return {
            "median_ms": statistics.median(ms),
            # the 90th percentile, nearest rank
            "p90_ms": ms[min(len(ms) - 1, int(np.ceil(0.9 * len(ms))) - 1)],
        }

    def work(self, config: dict) -> dict:
        # a round reads the (N, P) state and writes it: 2 * N * P * 4 bytes
        return {"bytes_per_round": 2 * self.state_bytes}

    def check(self, units: list) -> dict:
        rounds = units[-1]["rounds"]
        err, drift = reference.mixed_error(self.W, rounds, self.x, self.out)
        own = float(reference.max_deviation(self.out))
        return {
            "equals_W_power": bool(err <= self.tol),
            "mean_kept": bool(drift <= self.tol),
            "residual_below_eps": bool(own <= self.eps),
            "same_rounds": len({u["rounds"] for u in units}) == 1,
            "placed": reference.placed(self.out, self.devices, self.sharded),
            "rounds": rounds, "error": err, "mean_drift": drift,
            "own_residual": own, "residual": units[-1]["residual"],
        }
