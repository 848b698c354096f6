"""Training cells whose model has a plain reference with the benchmark.

``train.Driver`` with two additions.  A unit also returns the model's own
integer counters (``payload["counters"]``: ``moe.rows_held``,
``moe.load_max``).  ``check`` keeps every check of ``train.Driver`` and
then, outside the window, holds the program to the reference the
configuration names (``"reference"``: a module of ``chipbench/``), **on
the state the timed window left on the chip, per agent, at the timed
sizes**.  That state is copied to the host and the trainer runs ONE MORE
unit, the timed program itself (``train_epochs``: the vmapped, scanned
epoch program with its optimizer, then the mix):

* the timed program against a replay: from the copied state the
  reference alone (its own routing, its own gradients, Adam written out
  here, the harness's own Metropolis matrix) takes the same steps on the
  same batches.  Held to it: each agent's mean loss and mean gradient
  norm over the epoch as the trainer reports them (``epoch_loss_abs``,
  ``epoch_gnorm_rel``), and the change of the picked leaves from the
  copied state to what the unit left on the chip (``update_rel``: 0 is
  the same step, 1 is a state left unchanged);
* layer by layer, on the copied parameters and the unit's first batch,
  the reference's layer on the very input the program's layer had
  (captured from the program's forward: the trainer's model object, its
  dtype, its kernels, one agent, not vmapped): each mixer; the router
  twice — the reference's own top-k from the input the program's router
  reports it read must be the program's choices (``routing_flips`` 0
  outside f32 ties), and from the block's norm as a caller reads it back
  it may differ only by what bf16 rounding of that input flips
  (``own_routing_flips``); the experts with the program's choices, where
  one token short of a pair stands out by itself;
* end to end: that program's logits, loss and gradients against the
  reference's from the same token ids, the reference taking each token's
  experts as the program chose them (checked above).  Without that the
  two routers' inputs differ by bf16 rounding, one token in twenty has
  its tenth and eleventh expert closer than that, and a flipped choice
  is a different function, not an error of either.

``LIMITS`` gives each number's limit with the two readings it lies
between; the line before the result prints each number beside its limit.
"""

from __future__ import annotations

import importlib
import json
import time

import jax
import jax.numpy as jnp
import numpy as np

from chipbench import reference
from chipbench.drivers import train

#: name -> (limit, the two readings it lies between).  The program computes
#: in bf16 with f32 accumulation (relative rounding 2^-9 = 0.2% a product).
#: "sound" is the largest reading over the builder's chip runs of the final
#: program on the state a window leaves; the control is the smallest reading
#: of the deliberate faults meant to move that number
#: (tests/chipbench_tests/faults.py, run on the same state).  The limit is
#: the geometric mean of the two, a rule fixed before the readings (PERF.md,
#: PR 28, which has every reading).  A number no control moves has no limit:
#: ``moe_rel`` and ``ties`` are printed with the checks and decide nothing.
LIMITS = {
    # relative L2 over all logits of the sequence
    "logits_rel": (0.018, "sound 0.83%; one chunk of the delta rule reading "
                   "a zero state 3.96% (the attention gate skipped 5.75%)"),
    "loss_abs": (0.0046, "the mean of 4,096 token losses averages the "
                 "rounding: sound 0.00094; one lost chunk 0.0226"),
    # per layer, the same input on both sides: relative L2 over the layer's
    # output, and the worst token's error over the RMS token norm
    "gdn_rel": (0.015, "sound 1.05%, the same to a twentieth on every run; "
                "all that the rule holds rounded to bf16 2.2% (one lost "
                "chunk 5.95%, the decay dropped between chunks 63%).  NOT "
                "caught: the state alone rounded to bf16 between chunks "
                "reads the same to five digits, every product reads it in "
                "one bf16 pass already"),
    "gdn_token_rel": (0.22, "sound 4.9% at the worst of 4,096 tokens; a "
                      "chunk that reads a zero state 102% (the rule held in "
                      "bf16 reads 10.5% here and is caught by gdn_rel)"),
    "attn_rel": (0.073, "sound 0.38%; the one control, the gate skipped, "
                 "is gross: 139%"),
    "attn_token_rel": (0.27, "sound 1.9% at the worst of 4,096 tokens; the "
                       "gate skipped 393%"),
    "moe_token_rel": (0.094, "sound 2.7%; one token's held pairs not "
                      "computed leaves it short of whole experts' outputs: "
                      "32.7% (moe_rel moved from 0.69% to 0.85%)"),
    # the largest element's distance between the input the router reports
    # and the block's norm as read back, over the largest element
    "router_input_rel": (0.0215, "the same tensor up to XLA's choice of "
                         "rounding (excess precision), two bf16 steps of "
                         "the largest element at most: sound 1.3%; the "
                         "input off by 3% a channel 3.57%"),
    # tokens whose experts the program chose otherwise than the reference
    # does from the input the program's router had, outside f32 ties
    "routing_flips": (0.5, "an f32 router on both sides of one input: 0 on "
                      "every sound run; parameters rounded to bf16 64 of "
                      "4,096 tokens in a layer, the router in bf16 190"),
    # the same from the block's norm as a caller reads it back
    "own_routing_flips": (472, "what a bf16 step on half that input's "
                          "elements flips: sound 239 of 4,096 in a layer; "
                          "the router's input off by 3% a channel, and "
                          "reported so, 934"),
    # relative L2 of a leaf's gradient, program against reference
    "grad_rel": (0.16, "gradients pass bf16 products twice: sound 0.9-7.2% "
                 "by leaf; one lost chunk 35.4% (the gate skipped 344%)"),
    # the trainer's own unit against the replay: each agent's mean loss and
    # mean gradient norm over the unit, and each picked leaf's change
    "epoch_loss_abs": (0.0073, "sound 0.00029; every update halved in the "
                       "trainer's epoch program 0.188"),
    "epoch_gnorm_rel": (0.0142, "sound 0.16%; every update halved leaves "
                        "the trainer on another path: 13.0%"),
    "update_rel": (0.32, "1 is a state left unchanged.  Sound 1.1-4.2% by "
                   "leaf and 9.5% on the router, whose gradient sees each "
                   "of the 4% of tokens the replay routes otherwise; every "
                   "update halved 0.48-0.60.  More room above the reading "
                   "than below 1"),
}

#: the leaves whose gradients are compared, by path in the parameter tree
GRAD_LEAVES = {
    "gdn.A_log": ("layer_0", "GatedDeltaNet_0", "A_log"),
    "gdn.dt_bias": ("layer_0", "GatedDeltaNet_0", "dt_bias"),
    "gdn.conv": ("layer_0", "GatedDeltaNet_0", "conv"),
    "gdn.in_proj_qkvz": ("layer_0", "GatedDeltaNet_0", "in_proj_qkvz",
                         "kernel"),
    "attn.q_proj": ("layer_3", "_Attention_0", "q_proj", "kernel"),
    "moe.router": ("layer_1", "HeldExpertsMLP_0", "router"),
    "moe.expert0_down": ("layer_1", "HeldExpertsMLP_0", "w_down"),
    "moe.shared_gate": ("layer_1", "HeldExpertsMLP_0", "shared_gate",
                        "kernel"),
}


def _at(tree, path):
    for key in path:
        tree = tree[key]
    return tree


def _rel(got, want):
    got, want = got.astype(jnp.float32), want.astype(jnp.float32)
    return jnp.linalg.norm((got - want).ravel()) / jnp.linalg.norm(want.ravel())


#: two probabilities closer than this (relative) are a tie of f32 rounding
TIE = 1e-5


def _token_rel(got, want):
    """The worst token's L2 error over the RMS token norm of ``want``."""
    got, want = got.astype(jnp.float32), want.astype(jnp.float32)
    err = jnp.linalg.norm(got - want, axis=-1)
    return jnp.max(err) / jnp.sqrt(jnp.mean(jnp.sum(want * want, -1)))


def _flips(routed, chosen, K):
    """Tokens whose experts ``routed`` (the reference's probabilities and
    top-k) chose otherwise than ``chosen``, as sets; a token whose K-th
    and (K+1)-th probabilities tie is left out and counted apart."""
    probs, own = routed
    top = -jnp.sort(-probs, axis=-1)[:, K - 1:K + 1]
    tie = (top[:, 0] - top[:, 1]) <= TIE * top[:, 0]
    differ = jnp.any(jnp.sort(own, -1) != jnp.sort(chosen, -1), axis=-1)
    return jnp.sum(differ & ~tie), jnp.sum(tie)


def replay_step(cfg: dict, ref, optimizer: dict, donate: bool):
    """One training step as the reference takes it, jitted: its own
    routing, its own gradients, then Adam as published (arXiv:1412.6980,
    algorithm 1) on ``p`` with the moments ``mu``, ``nu`` after ``count``
    steps.  Returns the new three, the loss and the gradient's norm."""
    if optimizer["name"] != "adam" or optimizer.get("kwargs"):
        raise ValueError(f"the replay knows plain Adam, not {optimizer}")
    lr, b1, b2, eps = optimizer["learning_rate"], 0.9, 0.999, 1e-8

    def step(p, mu, nu, count, x, y):
        loss, g = jax.value_and_grad(ref.loss)(p, x, y, cfg, 64)
        t = (count + 1).astype(jnp.float32)
        mu = jax.tree.map(lambda m, g: b1 * m + (1 - b1) * g, mu, g)
        nu = jax.tree.map(lambda v, g: b2 * v + (1 - b2) * g * g, nu, g)
        p = jax.tree.map(
            lambda p, m, v: p - lr * (m / (1 - b1 ** t)) / (
                jnp.sqrt(v / (1 - b2 ** t)) + eps),
            p, mu, nu)
        gnorm = jnp.sqrt(sum(jnp.sum(a * a) for a in jax.tree.leaves(g)))
        return p, mu, nu, loss, gnorm

    return jax.jit(step, donate_argnums=(0, 1, 2) if donate else ())


def comparisons(model, cfg: dict, ref):
    """The comparison's three jitted programs, of one agent's parameters
    ``p`` and one sequence ``x`` with its targets ``y``: ``program`` (the
    trainer's model: loss, logits, the picked gradients, every layer's
    input and output and the router's choices), ``end_to_end`` and
    ``layer_by_layer`` (the reference against them)."""
    layers = {"RMSNorm_0", "RMSNorm_1", "GatedDeltaNet_0", "_Attention_0",
              "HeldExpertsMLP_0"}

    def captured(mdl, _method):
        return mdl.name in layers

    def program_loss(p, x, y):
        logits = model.apply({"params": p}, x[None])[0]
        logp = jax.nn.log_softmax(logits)
        return -jnp.mean(jnp.take_along_axis(logp, y[:, None], -1)), logits

    @jax.jit
    def program(p, x, y):
        (loss, logits), grads = jax.value_and_grad(
            program_loss, has_aux=True)(p, x, y)
        _, seen = model.apply(
            {"params": p}, x[None], capture_intermediates=captured,
            mutable=["intermediates"],
        )
        picked = {name: _at(grads, path)
                  for name, path in GRAD_LEAVES.items()}
        return loss, logits, picked, seen["intermediates"]

    chosen_of = lambda seen, i: seen[f"layer_{i}"]["HeldExpertsMLP_0"][
        "chosen"][0]

    @jax.jit
    def end_to_end(p, x, y, loss, logits, picked, seen):
        routing = [chosen_of(seen, i) for i in range(cfg["num_layers"])]

        def ref_loss(p):  # one forward pass for logits, loss and gradients
            logits = ref.forward(p, x, cfg, 64, routing)
            return ref.token_loss(logits, y), logits

        (want_loss, want_logits), want = jax.value_and_grad(
            ref_loss, has_aux=True)(p)
        out = {
            "logits_rel": _rel(logits, want_logits),
            "loss_abs": jnp.abs(loss - want_loss),
        }
        for name, path in GRAD_LEAVES.items():
            g, w = picked[name], _at(want, path)
            if name == "moe.expert0_down":
                g, w = g[0], w[0]
            out["grad_rel/" + name] = _rel(g, w)
        return out

    @jax.jit
    def layer_by_layer(p, seen):
        out = {}
        K = cfg["moe_top_k"]
        with jax.default_matmul_precision("highest"):
            p = jax.tree.map(lambda a: a.astype(jnp.float32), p)
            for i in range(cfg["num_layers"]):
                lp, ls = p[f"layer_{i}"], seen[f"layer_{i}"]
                io = lambda name: ls[name]["__call__"][0][0].astype(
                    jnp.float32)
                if "GatedDeltaNet_0" in lp:
                    kind, want = "gdn", ref.gated_delta_net(
                        lp["GatedDeltaNet_0"], io("RMSNorm_0"), cfg, 64)
                    got = io("GatedDeltaNet_0")
                else:
                    kind, want = "attn", ref.gated_attention(
                        lp["_Attention_0"], io("RMSNorm_0"), cfg, 64)
                    got = io("_Attention_0")
                out[f"{kind}_token_rel/layer_{i}"] = _token_rel(got, want)
                out[f"{kind}_rel/layer_{i}"] = _rel(got, want)
                # the router, on the input it really had (the layer sows
                # it: XLA may feed it another rounding of the norm than a
                # caller reads back): the reference's choices against the
                # program's, as sets; a token whose K-th and (K+1)-th
                # probabilities tie is left out
                h, moe = io("RMSNorm_1"), ls["HeldExpertsMLP_0"]
                had, chosen = moe["router_input"][0], moe["chosen"][0]
                out[f"router_input_rel/layer_{i}"] = (
                    jnp.max(jnp.abs(had - h)) / jnp.max(jnp.abs(h)))
                flips = lambda read: _flips(
                    ref.route(lp["HeldExpertsMLP_0"], read, cfg), chosen, K)
                out[f"routing_flips/layer_{i}"], out[f"ties/layer_{i}"] = (
                    flips(had))
                # and on the norm as a caller reads it back, which the
                # program did not prepare: flips here are what a bf16 step
                # on half the input's elements moves, and no more
                out[f"own_routing_flips/layer_{i}"] = flips(h)[0]
                # the experts, with those choices (checked above) on the
                # input a caller reads back
                got = io("HeldExpertsMLP_0")
                want = ref.expert_layer(
                    lp["HeldExpertsMLP_0"], h, cfg, chosen=chosen)
                out[f"moe_token_rel/layer_{i}"] = _token_rel(got, want)
                out[f"moe_rel/layer_{i}"] = _rel(got, want)
        return out

    return program, end_to_end, layer_by_layer


def beside_limits(readings: dict) -> dict:
    return {name: {"read": value, "limit": LIMITS[name.split("/")[0]][0]}
            for name, value in readings.items()
            if name.split("/")[0] in LIMITS}


def verdicts(readings: dict) -> dict:
    """One verdict a limit: every layer, leaf and agent under it."""
    return {
        kind + ".within": all(
            value <= limit for name, value in readings.items()
            if name.split("/")[0] == kind)
        for kind, (limit, _reason) in LIMITS.items()
    }


class Driver(train.Driver):
    def __init__(self, cell: dict, config: dict, seed: int, devices: list):
        super().__init__(cell, config, seed, devices)
        if self.k != 1 or config["batch"] != 1:
            raise ValueError("train_ref replays one epoch of one sequence "
                             "an agent and step")
        self.config = config
        self.ref = importlib.import_module(f"chipbench.{config['reference']}")
        #: agent 0's parameters (on the host) and first batch, as compared:
        #: for whoever compares again (tests/chipbench_tests/faults.py)
        self.kept = None
        self.mix_times = cell["traffic"]["mix_times"]
        self._compiled = None  # comparisons(), built at the first compare

    def unit(self) -> dict:
        payloads = self.trainer.train_epochs(self.k)
        jax.block_until_ready(self.trainer.state)
        losses = [float(np.mean(p["train_loss"])) for p in payloads]
        count = lambda name, fn: int(fn(
            [fn(p["counters"][name]) for p in payloads]))
        return {
            "ok": all(np.isfinite(losses)),
            "work": self.per_step * self.epoch_len * len(payloads),
            "losses": losses,
            "deviation": float(payloads[-1]["deviation"]),
            "epochs": len(payloads),
            "steps": self.epoch_len * len(payloads),
            "gossips": sum(bool(p["mixed"]) for p in payloads),
            "rows_held": count("moe.rows_held", np.sum),
            "load_max": float(count("moe.load_max", np.max)),
        }

    def work(self, config: dict) -> dict:
        flops = importlib.import_module(f"chipbench.flops.{config['flops']}")
        return {**super().work(config), **flops.extra_work(config)}

    def check(self, units: list) -> dict:
        checks = super().check(units)
        checks["load_max"] = max(u["load_max"] for u in units)
        checks["rows_held_per_step"] = (
            sum(u["rows_held"] for u in units) / sum(u["steps"] for u in units)
        )
        readings = self.against_reference()
        print("reference: " + json.dumps(beside_limits(readings)), flush=True)
        checks.update(readings)
        checks.update(verdicts(readings))
        return checks

    # ------------------------------------------------------------------ #
    def compare(self, p, x, y, program_p=None) -> dict:
        """The program's forward and gradients on one agent's parameters
        and one sequence, against the reference's: every reading of the
        second and third kind (module docstring).  ``program_p``: what the
        program reads in place of ``p`` (a fault's)."""
        if self._compiled is None:
            self._compiled = comparisons(
                self.trainer.model, self.config["model"]["kwargs"], self.ref)
        program, end_to_end, layer_by_layer = self._compiled
        loss, logits, picked, seen = program(
            p if program_p is None else program_p, x, y)
        readings = {**end_to_end(p, x, y, loss, logits, picked, seen),
                    **layer_by_layer(p, seen)}
        return {name: float(value) for name, value in readings.items()}

    def against_reference(self) -> dict:
        """The largest reading over the agents of every comparison."""
        trainer, config = self.trainer, self.config
        n, steps = config["agents"], self.epoch_len
        params, _, opt, _ = trainer.state
        f32 = all(leaf.dtype == jnp.float32 for leaf in jax.tree.leaves(params))
        has_moments = lambda s: hasattr(s, "mu") and hasattr(s, "nu")
        adam, = [s for s in jax.tree.leaves(opt, is_leaf=has_moments)
                 if has_moments(s)]
        # What the window left, to the host (the unit below donates it),
        # then the timed program once more.
        before = jax.device_get(
            {"p": params, "mu": adam.mu, "nu": adam.nu, "count": adam.count})
        del params, opt, adam
        epoch = trainer._epochs_done
        told = trainer.train_epochs(self.k)[0]
        after = jax.device_get({name: _at(trainer.state[0], path)
                                for name, path in GRAD_LEAVES.items()})
        order = trainer._epoch_perm(epoch)[:, :, 0]  # (steps, n)
        batch = lambda a, t: (trainer._Xs[a, order[t, a]],
                              trainer._ys[a, order[t, a]])
        # The check takes the chip: the trainer's state (Adam's moments
        # are two thirds of it) makes room for the reference.
        trainer._state = None

        on_chip = lambda tree, a: jax.device_put(
            jax.tree.map(lambda leaf: leaf[a], tree), self.devices[0])
        step = replay_step(config["model"]["kwargs"], self.ref,
                           config["optimizer"],
                           donate=self.devices[0].platform != "cpu")
        worst: dict = {}

        def read(name, value):
            # a NaN must not pass as "not above the limit"
            value = float(value) if np.isfinite(value) else float("inf")
            worst[name] = max(worst.get(name, 0.0), value)

        ends, spent = [], {"compare_s": 0.0, "replay_s": 0.0}
        for a in range(n):
            t0 = time.perf_counter()
            p = on_chip(before["p"], a)
            if a == 0:
                self.kept = (jax.tree.map(lambda leaf: leaf[0], before["p"]),
                             *batch(0, 0))
            for name, value in self.compare(p, *batch(a, 0)).items():
                read(name, value)
            t1 = time.perf_counter()
            mu, nu = on_chip(before["mu"], a), on_chip(before["nu"], a)
            losses, gnorms = [], []
            for t in range(steps):
                p, mu, nu, loss, gnorm = step(
                    p, mu, nu, before["count"][a] + t, *batch(a, t))
                losses.append(float(loss))
                gnorms.append(float(gnorm))
            del mu, nu
            ends.append(jax.device_get(
                {name: _at(p, path) for name, path in GRAD_LEAVES.items()}))
            del p
            spent["compare_s"] += t1 - t0
            spent["replay_s"] += time.perf_counter() - t1
            read("epoch_loss_abs",
                 abs(np.mean(losses) - float(told["train_loss"][a])))
            read("epoch_gnorm_rel",
                 abs(np.mean(gnorms) - float(told["grad_norm"][a]))
                 / np.mean(gnorms))
        # the mix, by the harness's own matrix; then each picked leaf's
        # change over the unit, the trainer's against the replay's
        topology = config["topology"]
        W = np.linalg.matrix_power(
            reference.metropolis(reference.adjacency(topology["kind"], n)),
            self.mix_times if told["mixed"] else 0)
        for name, path in GRAD_LEAVES.items():
            start = np.asarray(_at(before["p"], path), np.float64)
            mixed = np.einsum("ab,b...->a...", W,
                              np.stack([end[name] for end in ends]))
            want, got = mixed - start, after[name] - start
            for a in range(n):
                read("update_rel/" + name, np.linalg.norm(got[a] - want[a])
                     / np.linalg.norm(want[a]))
        worst["params_f32"] = f32
        # what the check cost on the host's clock, compiles included
        return {**worst, **spent}
