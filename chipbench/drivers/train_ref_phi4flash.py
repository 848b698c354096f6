"""The Phi-4-mini-flash-reasoning cell: ``train_ref``'s method for a stack
of SambaY mixers (``TransformerLM(layers=...)``), one agent.

The units are ``train.Driver``'s, with the model's own counter
``ssm.state_dead`` (the Mamba layers' (channel, state) pairs whose decay
over the mean step is under 1e-3) summed a step and printed with the
checks; no limit reads it.  ``check`` keeps every check of
``train.Driver`` and then, outside the window, holds the program to
``chipbench/reference_phi4flash.py`` **on the state the timed window left
on the chip, at the timed sizes**: the state is copied to the host and
the trainer runs ONE MORE unit, the timed program itself.

* the timed program against a replay: from the copied state the
  reference alone (its own gradients, Adam written out there) takes the
  same steps on the same batches; the mix of one agent is ``W = [[1]]``.
  Held to it: the mean loss over the unit as the trainer reports it
  (``epoch_loss_abs``) and the change of the picked leaves from the
  copied state to what the unit left (``update_rel``: 0 the same step, 1
  a state left unchanged), limited at the median leaf
  (``update_median_rel``);
* layer by layer, on the copied parameters and the unit's first batch,
  each mixer of the reference on the very input the program's had
  (captured from the program's forward: the trainer's model object, its
  dtype, its kernels, one agent, not vmapped).  A GMU and the
  cross-attention layer read the memory and the K/V the PROGRAM's
  earlier layer handed on; the memory itself is compared where it is
  made (``memory_rel``), and the scan alone on its own operands as the
  program's Mamba layer gave them to the kernel, forward (``scan_rel``)
  and backward (``scan_grad_rel``: the kernels' gradients of every
  operand under one seeded cotangent, f32 operands on both sides);
* end to end: that program's logits, loss and gradients against the
  reference's from the same token ids; and the same program in f32
  (the trainer's model object with an f32 compute dtype, its kernels,
  at the highest matrix precision), whose gradients are held to the
  reference's (``grad32_rel``): the bf16 program's own are printed.

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

from chipbench.drivers import train
from chipbench.drivers.train_ref import _at, _rel, _token_rel

#: name -> (limit, the two readings it lies between).  The program
#: computes in bf16 with f32 accumulation.  "sound" is the largest reading
#: over the chip runs on the state a window leaves (PR 37: eight of the
#: first session's tree and seven of the final one, on seeds unused when
#: these limits were written, but for epoch_loss_abs's, raised from 0.002
#: after a sound 0.00050);
#: the control is the smallest reading of the deliberate faults meant to
#: move that number (tests/chipbench_tests/faults_phi4flash.py, on one such
#: state).  The limit is at least 4x the sound reading and under the
#: control (PERF.md, PR 37, gives every reading and the seeds; the forward
#: readings' sound ranges and controls come from the first session's eight
#: runs and sweep, whose program computed the same numbers).  Printed with
#: the checks, no limit: ``grad_rel`` (the bf16 program's gradients: sound
#: up to 96% at the worst leaf on states where the whole gradient is a
#: small sum of 8,192 token gradients that bf16's rounding does not
#: cancel, while the same program in f32 reads ``grad32_rel``; PERF.md),
#: each leaf's ``update_rel`` (Adam's step over such a gradient: sound up
#: to 53%), ``loss_abs`` (the mean of 8,192 token losses: sound up to
#: 0.0019 against one lost chunk's 0.0045, which ``mamba_token_rel`` and
#: ``scan_rel`` fail), ``gmu_rel`` (the GMU reads the memory the program
#: handed on; a wrong memory is ``memory_rel``'s) and ``epoch_gnorm_rel``.
LIMITS = {
    # relative L2 over all logits of the sequence
    "logits_rel": (0.012, "sound 0.17-0.23%; one chunk of the scan reading "
                   "a zero state 2.67% (the memory gated 35.8%)"),
    # per layer, the same input on both sides: relative L2 over the
    # layer's output; the worst token's error over the RMS token norm
    "mamba_rel": (0.04, "sound 0.38-0.76%; one lost chunk 6.6%, the state "
                  "held in bf16 7.6%"),
    "mamba_token_rel": (0.3, "sound 2.7-7.2% at the worst of 8,192 tokens; "
                        "the state in bf16 44%, one lost chunk 235%"),
    "memory_rel": (0.03, "the y layer 16 hands on: sound 0.28-0.64%; one "
                   "lost chunk 8.0%, the gated output handed on 412%"),
    "scan_rel": (1e-4, "the scan alone on the kernel's own operands, f32 "
                 "state and output: sound 4.5e-7-2.7e-6; the parameters "
                 "read in bf16 (dt_bias, A_log) 1.7e-3, the state in bf16 "
                 "0.105"),
    "scan_grad_rel": (1e-3, "the scan's backward alone, the worst of its "
                      "six operands' gradients: sound 2.7e-5-1.2e-4; the "
                      "state's cotangent lost at one chunk 0.73"),
    "swa_rel": (0.04, "sound 0.38-0.58%; the window dropped 15.2%, lambda "
                "at zero 74.9%, no sub-layer norm 39.3%"),
    "attn_rel": (0.3, "sound 0.5-3.5% (T 8,192 of bf16 maps); lambda at "
                 "zero 112%, no sub-layer norm 85%"),
    "cross_rel": (0.3, "sound 0.47-2.1%; the window layer's K/V read 138%, "
                  "lambda at zero 133%, no sub-layer norm 72%"),
    # D's gradient (the skip past the scan), program against reference
    "skip_grad_rel": (0.85, "sound 1.8-18.1%; D x left out 1.0 (D's "
                      "gradient is zero; its forward moves by 0.5% of the "
                      "memory, under every forward reading)"),
    # the program in f32 at the highest precision: each picked leaf's
    # gradient against the reference's
    "grad32_rel": (0.05, "sound 5.1e-5-4.0e-4 at the worst leaf (where "
                   "the bf16 program's grad_rel read 6.8-23%); the backward "
                   "kernel's lost chunk 0.565, layer 17's K/V read as "
                   "constants by the cross layer 0.80"),
    # the trainer's own unit against the replay: the mean loss over it
    "epoch_loss_abs": (0.0025, "sound 0.000013-0.00050; every update "
                       "halved 0.0101, no update 0.0101"),
    # the median over the picked leaves of their update_rel
    "update_median_rel": (0.2, "sound 0.0015-0.030; every update halved "
                          "0.502, no update 1.0"),
}

#: the leaves whose gradients are compared, by path in the parameter tree;
#: D's reading of the bf16 program goes by its own name
#: (``skip_grad_rel``), the others by ``grad_rel``
GRAD_LEAVES = {
    "mamba.in_proj": ("layer_0", "mamba", "in_proj", "kernel"),
    "mamba.A_log": ("layer_0", "mamba", "A_log"),
    "mamba.dt_bias": ("layer_0", "mamba", "dt_bias"),
    "mamba.D": ("layer_2", "mamba", "D"),
    "mamba.conv": ("layer_2", "mamba", "conv"),
    "swa.q_proj": ("layer_1", "attention", "q_proj", "kernel"),
    "swa.lambda_q1": ("layer_1", "attention", "lambda_q1"),
    # layer 17's K/V projections: their gradients come through the
    # cross-attention layer too
    "attn.k_proj": ("layer_3", "attention", "k_proj", "kernel"),
    "attn.v_proj": ("layer_3", "attention", "v_proj", "kernel"),
    "attn.subln": ("layer_3", "attention", "subln"),
    "gmu.in_proj": ("layer_4", "gmu", "in_proj", "kernel"),
    "cross.q_proj": ("layer_5", "cross_attention", "q_proj", "kernel"),
    "mlp.gate_proj": ("layer_5", "gate_proj", "kernel"),
    "embedding": ("Embed_0", "embedding"),
}

#: the reference's chunk of the scan and its per-pair attention: memory
BLOCKS = 64


def layer_kinds(cfg: dict) -> list:
    """Each layer's reading name: ``mamba``, ``swa`` (window attention),
    ``attn``, ``gmu``, ``cross``."""
    names = {"mamba": "mamba", "gmu": "gmu", "cross_attention": "cross"}
    return [names.get(spec["mixer"]) or ("swa" if spec.get("window")
                                         else "attn")
            for spec in cfg["layers"]]


def _writer(cfg: dict, what: str) -> int:
    return next(i for i, spec in enumerate(cfg["layers"])
                if spec.get(what) == "write")


def _cotangent(y):
    """The one cotangent both sides of ``scan_grad_rel`` pull back."""
    return jax.random.normal(jax.random.key(0), y.shape, jnp.float32)


def program_of(model):
    """The program's side of the comparison, of one agent's parameters
    ``p`` and one sequence ``x`` with its targets ``y``: the trainer's
    model object's loss, logits, the picked gradients and every mixer's
    input and output; the scan's kernels' gradients on each Mamba layer's
    own operands; and the picked gradients of the same model in f32.
    Three jitted programs, one after the other (the f32 one is the
    largest)."""
    from distributed_learning_tpu.ops.selective_scan import (
        kernels_run,
        selective_scan,
    )

    captured = lambda mdl, _method: mdl.name in {
        "LayerNorm_0", "mamba", "gmu", "attention", "cross_attention"}
    model32 = model.clone(dtype=jnp.float32)

    def loss_of(model):
        def program_loss(p, x, y):
            logits = model.apply({"params": p}, x[None])[0]
            logp = jax.nn.log_softmax(logits)
            return (-jnp.mean(jnp.take_along_axis(logp, y[:, None], -1)),
                    logits)
        return program_loss

    def picked(grads):
        return {name: _at(grads, path) for name, path in GRAD_LEAVES.items()}

    @jax.jit
    def main(p, x, y):
        (loss, logits), grads = jax.value_and_grad(
            loss_of(model), has_aux=True)(p, x, y)
        _, seen = model.apply(
            {"params": p}, x[None], capture_intermediates=captured,
            mutable=["intermediates"],
        )
        return loss, logits, picked(grads), seen["intermediates"]

    @jax.jit
    def scan_grads(p, seen):
        out = {}
        for layer, ls in seen.items():
            if "mamba" not in ls:
                continue
            xs, delta, B, C, y = (a.astype(jnp.float32)
                                  for a in ls["mamba"]["scan"][0])
            mp = p[layer]["mamba"]
            scan = lambda x, d, A, B, C, b: selective_scan(
                x, d, A, B, C, bias=b,
                interpret=not kernels_run(x.shape[-1]))
            _, pull = jax.vjp(scan, xs, delta, -jnp.exp(mp["A_log"]), B, C,
                              mp["dt_bias"])
            out[layer] = pull(_cotangent(y))
        return out

    @jax.jit
    def in_f32(p, x, y):
        with jax.default_matmul_precision("highest"):
            grads = jax.grad(lambda p: loss_of(model32)(p, x, y)[0])(p)
        return picked(grads)

    def program(p, x, y):
        loss, logits, grads, seen = main(p, x, y)
        return loss, logits, grads, seen, scan_grads(p, seen), in_f32(p, x, y)

    return program


def reference_side(cfg: dict, ref):
    """The reference against the program's outputs, jitted:
    ``end_to_end`` (logits, loss, gradients from the token ids) and
    ``layer_by_layer`` (each mixer on the program's own input, the scan
    forward and backward on its own operands)."""
    kinds = layer_kinds(cfg)
    mem_at, kv_at = _writer(cfg, "memory"), _writer(cfg, "kv")

    @jax.jit
    def end_to_end(p, x, y, loss, logits, picked, picked32):
        def ref_loss(p):  # one forward pass for logits, loss and gradients
            logits = ref.forward(p, x, cfg, BLOCKS)
            return ref.token_loss(logits, y), logits

        (want_loss, want_logits), want = jax.value_and_grad(
            ref_loss, has_aux=True)(p)
        out = {"logits_rel": _rel(logits, want_logits),
               "loss_abs": jnp.abs(loss - want_loss)}
        for name, path in GRAD_LEAVES.items():
            kind = "skip_grad_rel" if name == "mamba.D" else "grad_rel"
            out[f"{kind}/{name}"] = _rel(picked[name], _at(want, path))
            out[f"grad32_rel/{name}"] = _rel(picked32[name], _at(want, path))
        return out

    @jax.jit
    def layer_by_layer(p, seen, scan_grads):
        out = {}
        with jax.default_matmul_precision("highest"):
            p = jax.tree.map(lambda a: a.astype(jnp.float32), p)
            f32 = lambda a: a.astype(jnp.float32)
            made = lambda i, mixer: seen[f"layer_{i}"][mixer]["__call__"][0]
            shared = {
                "memory": f32(made(mem_at, "mamba")[1][0]),
                "kv": jax.tree.map(lambda a: f32(a[0]),
                                   made(kv_at, "attention")[1]),
            }
            for i, (spec, kind) in enumerate(zip(cfg["layers"], kinds)):
                lp, ls = p[f"layer_{i}"], seen[f"layer_{i}"]
                h = f32(ls["LayerNorm_0"]["__call__"][0][0])
                got = made(i, spec["mixer"])  # a GMU hands nothing on
                out_of = (got[0] if isinstance(got, tuple) else got)[0]
                want, handed = ref.mixer(lp, spec, h, cfg, shared, BLOCKS)
                out[f"{kind}_rel/layer_{i}"] = _rel(out_of, want)
                if kind == "mamba":
                    out[f"mamba_token_rel/layer_{i}"] = _token_rel(out_of, want)
                    if "memory" in handed:
                        out[f"memory_rel/layer_{i}"] = _rel(
                            got[1][0], handed["memory"])
                    # the scan alone, on the operands the kernel had
                    xs, delta, B, C, y = (f32(a[0]) for a in
                                          ls["mamba"]["scan"][0])
                    mp = lp["mamba"]

                    def scan(x, d, A, B, C, b):
                        return ref.scan(x, jax.nn.softplus(d + b), A, B, C,
                                        BLOCKS)

                    A = -jnp.exp(mp["A_log"])
                    want_y, pull = jax.vjp(scan, xs, delta, A, B, C,
                                           mp["dt_bias"])
                    out[f"scan_rel/layer_{i}"] = _rel(y, want_y)
                    wants = pull(_cotangent(y))
                    gots = scan_grads[f"layer_{i}"]
                    # the kernels' gradients carry the batch axis
                    out[f"scan_grad_rel/layer_{i}"] = jnp.max(jnp.stack([
                        _rel(g.reshape(w.shape), w)
                        for g, w in zip(gots, wants)]))
        return out

    return end_to_end, layer_by_layer


def beside_limits(readings: dict) -> dict:
    return {name: {"read": value, "limit": LIMITS[name.split("/")[0]][0]}
            for name, value in readings.items()
            if name.split("/")[0] in LIMITS}


def verdicts(readings: dict) -> dict:
    """One verdict a limit: every layer and leaf under it."""
    return {
        kind + ".within": all(
            value <= limit for name, value in readings.items()
            if name.split("/")[0] == kind)
        for kind, (limit, _reason) in LIMITS.items()
    }


def _finite(value) -> float:
    # a NaN must not pass as "not above the limit"
    value = float(value)
    return value if np.isfinite(value) else float("inf")


class Driver(train.Driver):
    def __init__(self, cell: dict, config: dict, seed: int, devices: list):
        super().__init__(cell, config, seed, devices)
        if self.k != 1 or config["batch"] != 1 or config["agents"] != 1:
            raise ValueError("train_ref_phi4flash replays one epoch of one "
                             "sequence a step, one agent")
        self.config = config
        self.ref = importlib.import_module(f"chipbench.{config['reference']}")
        #: what the check compared, for whoever compares again
        #: (tests/chipbench_tests/faults_phi4flash.py): the window's state
        #: on the host, the epoch it had reached, the parameters and first
        #: batch as compared, and the replay
        self.kept = None
        self._program = None    # program_of(), built at the first compare
        self._reference = None  # reference_side(), likewise

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
            "state_dead": int(max(np.max(p["counters"]["ssm.state_dead"])
                                  for p in payloads)),
        }

    def work(self, config: dict) -> dict:
        flops = importlib.import_module(f"chipbench.flops.{config['flops']}")
        return {**super().work(config), **flops.extra_work(config)}

    def check(self, units: list) -> dict:
        checks = super().check(units)
        checks["ssm.state_dead"] = max(u["state_dead"] for u in units)
        readings = self.against_reference()
        print("reference: " + json.dumps(beside_limits(readings)), flush=True)
        checks.update(readings)
        checks.update(verdicts(readings))
        return checks

    # ------------------------------------------------------------------ #
    def compare(self, p, x, y, program_p=None) -> dict:
        """The program's forward and gradients on one agent's parameters
        and one sequence, against the reference's (module docstring).
        ``program_p``: what the program reads in place of ``p``."""
        cfg = self.config["model"]["kwargs"]
        if self._program is None:
            self._program = program_of(self.trainer.model)
        if self._reference is None:
            self._reference = reference_side(cfg, self.ref)
        end_to_end, layer_by_layer = self._reference
        loss, logits, picked, seen, scan_grads, picked32 = self._program(
            p if program_p is None else program_p, x, y)
        readings = {**end_to_end(p, x, y, loss, logits, picked, picked32),
                    **layer_by_layer(p, seen, scan_grads)}
        return {name: _finite(value) for name, value in readings.items()}

    def run_unit(self, state=None, epoch=None) -> dict:
        """The trainer's unit once more: from where it stands, or from
        ``state`` (a host copy of its parameters, batch statistics and
        optimizer state) at ``epoch``.  What it reported, and the picked
        leaves it left, on the host; the trainer's state is let go of."""
        trainer = self.trainer
        if state is not None:
            # the step key: no operation of this model draws from it
            trainer._state = (*trainer.engine.shard(state),
                              trainer._on_every_chip(jax.random.key(0)))
            trainer._epochs_done = epoch
        told = trainer.train_epochs(self.k)[0]
        after = jax.device_get({name: _at(trainer.state[0], path)[0]
                                for name, path in GRAD_LEAVES.items()})
        trainer._state = None
        return {"told": told, "after": after}

    def replay(self, before: dict, batch) -> dict:
        """The reference alone takes the unit's steps from ``before``: its
        mean loss and gradient norm, and the picked leaves it left."""
        cfg, config = self.config["model"]["kwargs"], self.config
        step = replay_step(cfg, self.ref, config["optimizer"],
                           donate=self.devices[0].platform != "cpu")
        put = lambda tree: jax.device_put(tree, self.devices[0])
        p, mu, nu = put(before["p"]), put(before["mu"]), put(before["nu"])
        count = np.asarray(before["count"]).reshape(-1)[0]
        losses, gnorms = [], []
        for t in range(self.epoch_len):
            p, mu, nu, loss, gnorm = step(p, mu, nu, count + t, *batch(t))
            losses.append(float(loss))
            gnorms.append(float(gnorm))
        end = jax.device_get({name: _at(p, path)
                              for name, path in GRAD_LEAVES.items()})
        return {"loss": float(np.mean(losses)),
                "gnorm": float(np.mean(gnorms)), "end": end}

    @staticmethod
    def unit_readings(before: dict, unit: dict, replay: dict) -> dict:
        """A unit of the trainer against the replay (module docstring)."""
        told = unit["told"]
        out = {
            "epoch_loss_abs": _finite(
                abs(replay["loss"] - float(told["train_loss"][0]))),
            "epoch_gnorm_rel": _finite(
                abs(replay["gnorm"] - float(told["grad_norm"][0]))
                / replay["gnorm"]),
        }
        for name, path in GRAD_LEAVES.items():
            start = np.asarray(_at(before["p"], path), np.float64)
            want = replay["end"][name] - start
            got = unit["after"][name] - start
            out["update_rel/" + name] = _finite(
                np.linalg.norm(got - want) / np.linalg.norm(want))
        out["update_median_rel"] = float(np.median(
            [v for k, v in out.items() if k.startswith("update_rel/")]))
        return out

    def against_reference(self) -> dict:
        trainer = self.trainer
        has_moments = lambda s: hasattr(s, "mu") and hasattr(s, "nu")
        # What the window left, to the host (the unit below donates it),
        # then the timed program once more.
        state = jax.device_get(trainer.state[:3])
        params, _, opt = state
        f32 = all(leaf.dtype == np.float32 for leaf in jax.tree.leaves(params))
        adam, = [s for s in jax.tree.leaves(opt, is_leaf=has_moments)
                 if has_moments(s)]
        one = lambda tree: jax.tree.map(lambda leaf: leaf[0], tree)
        before = {"p": one(params), "mu": one(adam.mu), "nu": one(adam.nu),
                  "count": adam.count}
        epoch = trainer._epochs_done
        # The check takes the chip: the unit lets the trainer's state go
        # (Adam's moments are two thirds of it) to make room for the
        # reference.
        unit = self.run_unit()
        order = trainer._epoch_perm(epoch)[:, 0, 0]  # (steps,)
        batch = lambda t: (trainer._Xs[0, order[t]], trainer._ys[0, order[t]])

        t0 = time.perf_counter()
        put = lambda tree: jax.device_put(tree, self.devices[0])
        readings = self.compare(put(before["p"]), *batch(0))
        t1 = time.perf_counter()
        replay = self.replay(before, batch)
        readings.update(self.unit_readings(before, unit, replay))
        self.kept = (state, epoch, (before["p"], *batch(0)), replay)
        return {**readings, "params_f32": f32, "compare_s": t1 - t0,
                "replay_s": time.perf_counter() - t1}


def replay_step(cfg: dict, ref, optimizer: dict, donate: bool):
    """One training step as the reference takes it, jitted: its own
    gradients, then its Adam on ``p`` with the moments ``mu``, ``nu``
    after ``count`` steps.  Returns the new three, the loss and the
    gradient's norm."""
    if optimizer["name"] != "adam" or optimizer.get("kwargs"):
        raise ValueError(f"the replay knows plain Adam, not {optimizer}")

    def step(p, mu, nu, count, x, y):
        loss, g = jax.value_and_grad(ref.loss)(p, x, y, cfg, BLOCKS)
        p, mu, nu = ref.adam(p, mu, nu, g, count, optimizer["learning_rate"])
        gnorm = jnp.sqrt(sum(jnp.sum(a * a) for a in jax.tree.leaves(g)))
        return p, mu, nu, loss, gnorm

    return jax.jit(step, donate_argnums=(0, 1, 2) if donate else ())
