"""Deliberate faults in the latent-attention, sigmoid-routed stack, to show
that the chip benchmark's comparison with its reference
(``chipbench/drivers/train_ref_mesh.py``) fails them, and to give each of
its limits the reading it has to stay under.  ``faults.py``'s method, for
the layers PR 34 added:

    python tests/chipbench_tests/faults_kanana2.py <name> --workload <cell> --seed <n> --seconds <s> --trace 0
    python tests/chipbench_tests/faults_kanana2.py sweep:<name>,<name>,... --workload ...

The first form runs the whole cell with the fault in place.  The second
runs the sound cell and then, on the state its check compared, compares
again with each fault in turn: a fault of the forward pass through the
comparison's programs (every agent on its own chip), a fault of the
trainer's unit (``UNIT``) by putting the copied state back, building the
trainer's epoch program anew with the fault in place and holding that
unit to the replay the sound check made; a fault of the gossip round
(``MIX``) the same way with the trainer's engine mixing otherwise for that
one unit (the mix is a launch of its own: nothing is built anew).  One
set-up and one trained state for all of them; every reading is printed
beside the sound one (``fault <name>: fails [...]: {...}``).  The program
has no such switch.

``<name>:derived`` of a ``MIX`` fault costs no unit: one round under a
matrix ``W`` is linear and ``W`` has an inverse, so what the trainer would
have left under another matrix ``V`` is ``V W^-1`` of what the sound unit
left, on the host (a test holds the two forms to the same readings at toy
size).  ``<name><<seconds>`` (``skip_mix<470``) leaves a fault out that
is not begun that many seconds after the process's start: a chip call
has a limit.
"""

import concurrent.futures
import contextlib
import importlib
import inspect
import json
import os
import sys
import time

_T0 = time.monotonic()
sys.path.insert(0, os.path.dirname(os.path.abspath(__file__)))
import numpy as np  # noqa: E402

import faults as accepted  # noqa: E402  (the accepted cell's controls)

_MLA = ("distributed_learning_tpu.models.transformer", "_LatentAttention")
_MOE = ("distributed_learning_tpu.models.moe", "HeldExpertsMLP")
_TRAINER = accepted.FAULTS["half_update"][:2]

#: name -> (module, attribute, [(old, new), ...]); each ``old`` stands
#: exactly once in the attribute's source
FAULTS = {
    # rotary left off the shared key
    "no_rope_k": (*_MLA, [(
        "            k_pe = turn(ckv[..., None, R:])               # (B, T, 1, Dr)\n",
        "            k_pe = ckv[..., None, R:]\n")]),
    # the latent's RMSNorm skipped
    "no_latent_norm": (*_MLA, [(
        "            c = RMSNorm(self.norm_eps, self.dtype, name=\"kv_a_norm\")(\n"
        "                ckv[..., :R])\n",
        "            c = ckv[..., :R]\n")]),
    # a rotary key per head (head h's columns rolled by h) where all
    # heads share one
    "k_pe_per_head": (*_MLA, [(
        "            k_pe = jnp.broadcast_to(k_pe, (B, T, H, Dr))\n",
        "            k_pe = jnp.stack([jnp.roll(k_pe[:, :, 0], h, axis=-1)\n"
        "                              for h in range(H)], axis=2)\n")]),
    # scores scaled by the no-rope width alone
    "scale_nope": (*_MLA, [(
        "            scale = float((Dn + Dr) ** -0.5)\n",
        "            scale = float(Dn ** -0.5)\n")]),
    # routed_scaling_factor dropped
    "no_route_scale": (*_MOE, [(
        "                gates = gates * self.route_scale\n",
        "                gates = gates\n")]),
    # the bias ignored where the experts are chosen
    "bias_ignored": (*_MOE, [(
        "                _, chosen = jax.lax.top_k(scores + bias.value, K)\n",
        "                _, chosen = jax.lax.top_k(scores, K)\n")]),
    # the bias added into the weights too
    "bias_in_weights": (*_MOE, [(
        "                gates = jnp.take_along_axis(scores, chosen, axis=-1)\n",
        "                gates = jnp.take_along_axis(scores + bias.value, chosen,\n"
        "                                            axis=-1)\n")]),
    # the first token with a held (token, choice) pair loses its held pairs
    # in every expert layer: the accepted cell's control, the same line
    "drop_pair": accepted.FAULTS["drop_pair"],
    # the bias's update skipped, in the trainer's unit
    "no_bias_update": (*_MOE, [(
        "                if train and self.is_mutable_collection(\"batch_stats\") \\\n",
        "                if False and self.is_mutable_collection(\"batch_stats\") \\\n")]),
    # every step's update halved, in the trainer's epoch program alone:
    # the accepted cell's control
    "half_update": accepted.FAULTS["half_update"],
}
#: the faults of the trainer's unit: the comparison's own programs are
#: sound under them, only what the timed program left gives them away
UNIT = ("no_bias_update", "half_update")
#: the faults of the gossip round: name -> the matrix the trainer's engine
#: mixes by in place of the cell's ``W`` (one round a unit)
MIX = {
    # the round left out
    "skip_mix": lambda W: np.eye(len(W)),
    # a wrong W: the lazy walk, half of every edge's weight kept at home
    "lazy_w": lambda W: (np.eye(len(W)) + W) / 2,
}
DERIVED = ":derived"
#: the program reads parameters rounded to bf16 (the comparison's program,
#: not the trainer's: its state stays f32)
ROUNDED = "bf16_params"
NAMES = sorted([*FAULTS, *MIX, *(m + DERIVED for m in MIX), ROUNDED])


def apply(name: str):
    """Put the fault in place; returns the call that takes it out again."""
    if name == ROUNDED:
        return _bf16_params()
    if name not in FAULTS:
        raise ValueError(f"{name!r} runs in a sweep only (want one of "
                         f"{sorted([*FAULTS, ROUNDED])})")
    module_name, attr, patches = FAULTS[name]
    module = importlib.import_module(module_name)
    sound = getattr(module, attr)
    src = inspect.getsource(sound)
    for old, new in patches:
        assert src.count(old) == 1, (name, old, src.count(old))
        src = src.replace(old, new)
    exec(compile(src, f"<fault {name}>", "exec"), module.__dict__)
    return lambda: setattr(module, attr, sound)


def _bf16_params():
    import jax
    import jax.numpy as jnp

    from chipbench.drivers import train_ref_mesh

    inner = train_ref_mesh.Driver.compare

    def compare(self, p, b, x, y, *, program_p=None):
        rounded = jax.tree.map(
            lambda a: a.astype(jnp.bfloat16).astype(a.dtype), p)
        return inner(self, p, b, x, y, program_p=rounded)

    train_ref_mesh.Driver.compare = compare
    return lambda: setattr(train_ref_mesh.Driver, "compare", inner)


@contextlib.contextmanager
def applied(name: str):
    undo = apply(name)
    try:
        yield
    finally:
        undo()


@contextlib.contextmanager
def _mixing_by(trainer, name: str):
    """The trainer's engine mixes by ``MIX[name]`` of its matrix."""
    engine = trainer.engine
    V = MIX[name](np.asarray(engine.W, np.float64)).astype(np.float32)
    engine.mix = lambda params, times=1: (
        params if name == "skip_mix"
        else engine.mix_with(params, V, times=times))
    try:
        yield
    finally:
        del engine.mix  # the instance's: the class's own is back


def _unit_with(driver, name: str, before: dict, epoch: int) -> dict:
    """The trainer's unit once more from ``before`` with the fault in its
    epoch program, which is built anew (and left so: nothing runs after
    the check), or in its gossip round."""
    import jax

    trainer = driver.trainer
    with (_mixing_by(trainer, name) if name in MIX else applied(name)):
        if name not in MIX:
            module = importlib.import_module(_TRAINER[0])
            getattr(module, _TRAINER[1])._build_jitted(trainer)
        trainer._state = (
            driver._put(before["p"]), driver._put(before["b"]),
            driver._put(before["opt"]),
            trainer._on_every_chip(jax.random.key(0)),
        )
        trainer._epochs_done = epoch
        unit = driver.run_unit(before)
        trainer._state = None
    return unit


def _unit_derived(driver, name: str, unit: dict) -> dict:
    """What ``unit`` would have left of the picked leaves had its round
    run under ``MIX[name]``: ``V W^-1`` of what it left, on the host."""
    W = driver.mix_matrix()
    M = MIX[name](W) @ np.linalg.inv(W)
    return {**unit, "p": {
        group: tuple(np.einsum("ab,b...->a...", M, np.asarray(x, np.float64))
                     for x in leaves)
        for group, leaves in unit["p"].items()}}


def sweep(names: list) -> None:
    """After the sound cell's own check, compare again with each fault."""
    from chipbench.drivers import train_ref_mesh as trm

    deadlines = {n.split("<")[0]: float(n.split("<")[1])
                 for n in names if "<" in n}
    names = [n.split("<")[0] for n in names]
    sound_check = trm.Driver.against_reference

    def against_reference(self):
        worst = sound_check(self)
        before, unit, batch, replay = self.kept
        # said at once (the check says it again after the controls): a
        # call cut at its limit must not take the sound readings along
        print("sound: " + json.dumps({
            "fails": sorted(k for k, ok in trm.verdicts(worst).items()
                            if not ok),
            "readings": trm.beside_limits(worst)}), flush=True)

        def say(name, readings):
            failed = sorted(k[:-len(".within")] for k, ok in
                            trm.verdicts(readings).items()
                            if not ok and any(
                                r.split("/")[0] == k[:-len(".within")]
                                for r in readings))
            print(f"fault {name}: fails {failed}: " + json.dumps(readings),
                  flush=True)

        def in_time(name):
            if time.monotonic() - _T0 < deadlines.get(name, float("inf")):
                return True
            print(f"fault {name}: left out, {deadlines[name]:.0f} s are over",
                  flush=True)
            return False

        # cheapest first: what the host derives (beside the chips' next
        # comparison), what is compared again as it is compiled, a unit, a
        # comparison traced anew, a unit whose epoch program is built anew
        derived = [n for n in names if n.endswith(DERIVED)]
        with concurrent.futures.ThreadPoolExecutor(1) as beside:
            jobs = [beside.submit(lambda n=n: self.unit_readings(
                before, _unit_derived(self, n[:-len(DERIVED)], unit), replay))
                for n in derived]
            if ROUNDED in names and in_time(ROUNDED):
                with applied(ROUNDED):
                    say(ROUNDED, self.compare_all(before, batch(0)))
            for name, job in zip(derived, jobs):
                say(name, job.result())
        for name in [n for n in names if n in MIX and in_time(n)]:
            faulty = _unit_with(self, name, before, unit["epoch"])
            say(name, self.unit_readings(before, faulty, replay))
        traced = [n for n in names if n in FAULTS and n not in UNIT]
        for name in ["sound", *traced] if traced else []:
            if not in_time(name):
                continue
            self._compiled = None  # the program is traced anew
            with (applied(name) if name != "sound"
                  else contextlib.nullcontext()):
                say(name, self.compare_all(before, batch(0)))
        self._compiled = None
        for name in [n for n in names if n in UNIT and in_time(n)]:
            faulty = _unit_with(self, name, before, unit["epoch"])
            say(name, self.unit_readings(before, faulty, replay))
        return worst

    trm.Driver.against_reference = against_reference


if __name__ == "__main__":
    sys.path.insert(0, ".")
    what = sys.argv[1]
    if what.startswith("sweep:"):
        sweep(what[len("sweep:"):].split(","))
    else:
        apply(what)
    from chipbench import run

    sys.exit(run.main(sys.argv[2:]))
