"""Training cells: the library's public path, as a user drives it.

``GossipTrainer(...)``, ``initialize_nodes()``, then ``train_epochs(k)``
with ``k`` the cell's ``superstep`` — one call is one timed unit (a
chunk).  No test set is handed in, so no evaluation program is built, and
nothing is checkpointed.
"""

from __future__ import annotations

import importlib

import jax
import jax.numpy as jnp
import numpy as np

from chipbench import reference


def build_model(spec: dict):
    module, _, attr = spec["import"].partition(":")
    kwargs = dict(spec["kwargs"])
    if "dtype" in kwargs:
        kwargs["dtype"] = jnp.dtype(kwargs["dtype"])
    return getattr(importlib.import_module(module), attr)(**kwargs)


class Driver:
    def __init__(self, cell: dict, config: dict, seed: int, devices: list):
        from distributed_learning_tpu.parallel.consensus import make_agent_mesh
        from distributed_learning_tpu.parallel.topology import Topology
        from distributed_learning_tpu.training.trainer import GossipTrainer

        traffic = cell["traffic"]
        n, batch = config["agents"], config["batch"]
        self.devices = devices
        self.sharded = traffic["layout"] == "sharded"
        self.k = traffic["superstep"]
        self.epoch_len = traffic["epoch_len"]
        self.per_step = n * batch * config["unit_per_sample"]
        data = importlib.import_module(f"chipbench.data.{config['data']['name']}")
        topology = config["topology"]
        if topology["weights"] != "metropolis":
            raise ValueError(topology)
        self.trainer = GossipTrainer(
            node_names=list(range(n)),
            model=build_model(config["model"]),
            optimizer=config["optimizer"]["name"],
            optimizer_kwargs=config["optimizer"].get("kwargs"),
            learning_rate=config["optimizer"]["learning_rate"],
            error=config["loss"],
            weights=getattr(Topology, topology["kind"])(n),
            train_data=data.make(
                seed, agents=n, per_agent=batch * self.epoch_len,
                **config["data"]["kwargs"],
            ),
            test_data=None,
            batch_size=batch,
            epoch_len=self.epoch_len,
            epoch=1 << 30,
            superstep=self.k,
            mix_times=traffic["mix_times"],
            mix_eps=traffic["mix_eps"],
            compression=traffic["compression"],
            mesh=make_agent_mesh(n) if self.sharded else None,
            dropout=config["dropout"],
            seed=seed % (1 << 31),
        )
        self.first_loss = None

    def warm_up(self) -> None:
        self.trainer.initialize_nodes()
        self.first_loss = self.unit()["losses"][0]

    def unit(self) -> dict:
        payloads = self.trainer.train_epochs(self.k)
        jax.block_until_ready(self.trainer.state)
        losses = [float(np.mean(p["train_loss"])) for p in payloads]
        return {
            "ok": all(np.isfinite(losses)),
            "work": self.per_step * self.epoch_len * len(payloads),
            "losses": losses,
            "deviation": float(payloads[-1]["deviation"]),
            "epochs": len(payloads),
            "steps": self.epoch_len * len(payloads),
            "gossips": sum(bool(p["mixed"]) for p in payloads),
        }

    def metrics(self, units: list, span_s: float) -> dict:
        return {"throughput": sum(u["work"] for u in units) / span_s}

    def work(self, config: dict) -> dict:
        flops = importlib.import_module(f"chipbench.flops.{config['flops']}")
        return {"flops_per_step": flops.per_step(config)}

    def check(self, units: list) -> dict:
        params = self.trainer.state[0]
        own = float(reference.max_deviation(params))
        told = units[-1]["deviation"]
        last = units[-1]["losses"][-1]
        return {
            "loss_fell": bool(last < self.first_loss),
            "deviation_agrees": bool(abs(own - told) <= 1e-4 * max(own, 1e-12)),
            # the trainer donates its state wherever the backend can
            "donated": self.trainer._donate_active
            == (self.devices[0].platform != "cpu"),
            "placed": reference.placed(self.trainer.state, self.devices, self.sharded),
            "first_loss": self.first_loss, "last_loss": last,
            "deviation": told, "own_deviation": own,
        }
