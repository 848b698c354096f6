"""Deliberate faults in the SambaY program, to show that the Phi-4-mini-
flash cell's comparison with its reference
(``chipbench/drivers/train_ref_phi4flash.py``) fails them, and to give
each of its limits the reading it has to stay under.

``apply(name)`` rewrites lines of the program's source in the running
process and returns what undoes it;
``tests/chipbench_tests/test_train_ref_phi4flash.py`` runs the toy cell on
the CPU with each of them, and on the chip they go through the real cell:

    python tests/chipbench_tests/faults_phi4flash.py <name> --workload <cell> --seed <n> --seconds <s> --trace 0
    python tests/chipbench_tests/faults_phi4flash.py sweep:<name>,<name>,... --workload ...

The first form runs the whole cell with the fault in place (training
included).  The second runs the sound cell and then, on the parameters
and the batch its check compared, compares again with each fault in turn
in the program's forward and backward: one set-up and one trained state
for all of them, every reading beside the sound one (the program is
traced again for each; the reference's side is not).  A fault of the
trainer's epoch program (``UNIT``) is swept as a unit instead: the
window's state put back, the epoch program built anew with the fault,
one unit, held to the replay the sound check made.  The program itself
has no such switch.
"""

import contextlib
import importlib
import inspect
import json
import sys

_SAMBAY = "distributed_learning_tpu.models.sambay"
_SCAN = "distributed_learning_tpu.ops.selective_scan"
_BLOCK = (_SAMBAY, "SambaYBlock")
_TRAINER = ("distributed_learning_tpu.training.trainer", "GossipTrainer")
_APPLY = "                params = optax.apply_updates(params, updates)\n"
_ROUND = ".astype(jnp.bfloat16).astype(jnp.float32)"

#: name -> [(module, attribute, [(old, new), ...]), ...]; each ``old``
#: stands exactly once in the attribute's source
FAULTS = {
    # the window attention attends over the whole past
    "no_window": [(*_BLOCK, [(
        'spec["depth"], spec.get("window"), cross,',
        'spec["depth"], None, cross,')])],
    # differential attention without its second map
    "lambda_zero": [(_SAMBAY, "DiffAttention", [(
        "                   + lam0)\n", "                   + lam0) * 0.0\n")])],
    # a head pair's output not RMS-normed
    "no_subln": [(_SAMBAY, "DiffAttention", [(
        "            o = (_rms(o, w, self.eps) * (1.0 - lam0))",
        "            o = (o * (1.0 - lam0))")])],
    # the memory the GMU reads is the gated output, y * SiLU(z)
    "gated_memory": [(_SAMBAY, "Mamba", [(
        "        memory = y\n",
        "        memory = y * nn.silu(xz[..., Di:])\n")])],
    # the cross-attention layer reads the window layer's K/V
    "kv_from_swa": [(*_BLOCK, [(
        '            if spec.get("kv") == "write":\n',
        '            if spec.get("window"):\n')])],
    # the skip connection D x left out
    "no_D": [(_SAMBAY, "Mamba", [(
        "            y = (y + x * D).astype(self.dtype)\n",
        "            y = y.astype(self.dtype)\n")])],
    # the state is not carried into the middle chunk: the kernel's (on a
    # TPU) and the lax.scan's (elsewhere)
    "lost_chunk": [
        (_SCAN, "_fwd_kernel", [(
            "    @pl.when(pl.program_id(2) == 0)\n",
            "    @pl.when((pl.program_id(2) == 0)\n"
            "             | (pl.program_id(2) == pl.num_programs(2) // 2))\n")]),
        (_SCAN, "_scan_xla", [(
            "    _, y = jax.lax.scan(one_chunk, s0, xs)\n",
            "    half = T // chunk // 2\n"
            "    _, y1 = jax.lax.scan(one_chunk, s0, jax.tree.map(\n"
            "        lambda a: a[:half], xs))\n"
            "    _, y2 = jax.lax.scan(one_chunk, s0, jax.tree.map(\n"
            "        lambda a: a[half:], xs))\n"
            "    y = jnp.concatenate([y1, y2])\n")]),
    ],
    # the scan's state rounded to bf16 after every step
    "bf16_scan_state": [
        (_SCAN, "_fwd_kernel", [(
            "            y = jnp.sum(s * _column(Ct, lane, t0 + r)",
            f"            s = s{_ROUND}\n"
            "            y = jnp.sum(s * _column(Ct, lane, t0 + r)")]),
        (_SCAN, "_scan_xla", [(
            "        return s, jnp.sum(s * ct",
            f"        s = s{_ROUND}\n"
            "        return s, jnp.sum(s * ct")]),
    ],
    # the scan's backward kernel does not carry the state's cotangent
    # into the middle chunk (its forward and the lax.scan are sound)
    "lost_chunk_bwd": [(_SCAN, "_bwd_kernel", [(
        "    @pl.when(ti == 0)\n",
        "    @pl.when((ti == 0) | (ti == nt // 2))\n")])],
    # the cross-attention layer reads layer 17's K/V as constants: their
    # gradients no longer reach W_k and W_v from there (forward sound)
    "kv_no_grad": [(*_BLOCK, [(
        ')(h, shared["kv"] if cross else None)\n',
        ')(h, jax.lax.stop_gradient(shared["kv"]) if cross else None)\n')])],
    # every step's update halved, in the trainer's epoch program alone
    "half_update": [(*_TRAINER, [(
        _APPLY, "                params = optax.apply_updates(params, "
        "jax.tree.map(\n                    lambda u: 0.5 * u, updates))\n")])],
    # no step moves the parameters (Adam's moments still move), likewise
    "frozen": [(*_TRAINER, [(_APPLY, "                del updates\n")])],
}

#: the faults of the trainer's epoch program: swept as a unit of the
#: trainer from the window's state against the sound check's replay
UNIT = ("half_update", "frozen")


def _bf16_params():
    """The program reads parameters rounded to bf16 (the comparison's
    program, not the trainer's: its state stays f32)."""
    import jax
    import jax.numpy as jnp

    from chipbench.drivers import train_ref_phi4flash as driver

    inner = driver.Driver.compare

    def compare(self, p, x, y, program_p=None):
        rounded = jax.tree.map(
            lambda a: a.astype(jnp.bfloat16).astype(a.dtype), p)
        return inner(self, p, x, y, program_p=rounded)

    driver.Driver.compare = compare
    return lambda: setattr(driver.Driver, "compare", inner)


INSTALLED = {"bf16_params": _bf16_params}
NAMES = sorted([*FAULTS, *INSTALLED])


def _patch(module_name, attr, patches, name):
    module = importlib.import_module(module_name)
    sound = getattr(module, attr)
    src = inspect.getsource(sound)
    for old, new in patches:
        assert src.count(old) == 1, (name, old, src.count(old))
        src = src.replace(old, new)
    exec(compile(src, f"<fault {name}>", "exec"), module.__dict__)
    return lambda: setattr(module, attr, sound)


def apply(name: str):
    """Put the fault in place; returns the call that takes it out again."""
    if name in INSTALLED:
        return INSTALLED[name]()
    undo = [_patch(*where, name) for where in FAULTS[name]]
    return lambda: [u() for u in reversed(undo)]


@contextlib.contextmanager
def applied(name: str):
    undo = apply(name)
    try:
        yield
    finally:
        undo()


def sweep(names: list) -> None:
    """After the sound cell's own check, compare again with each fault."""
    import jax

    from chipbench.drivers import train_ref_phi4flash as driver

    sound_check = driver.Driver.against_reference

    def say(name, readings):
        failed = sorted(k[:-len(".within")] for k, ok in
                        driver.verdicts(readings).items() if not ok)
        print(f"fault {name}: fails {failed}: " + json.dumps(readings),
              flush=True)

    def against_reference(self):
        worst = sound_check(self)
        state, epoch, (p_host, x, y), replay = self.kept
        for name in ["sound", *[n for n in names if n not in UNIT]]:
            p = jax.device_put(p_host, self.devices[0])
            self._program = None  # the program is traced anew
            with applied(name) if name != "sound" else contextlib.nullcontext():
                readings = self.compare(p, x, y)
            del p
            say(name, readings)
        self._program = None
        module = importlib.import_module(_TRAINER[0])
        for name in [n for n in names if n in UNIT]:
            with applied(name):
                getattr(module, _TRAINER[1])._build_jitted(self.trainer)
                unit = self.run_unit(state, epoch)
            say(name, self.unit_readings(
                {"p": p_host}, unit, replay))
        if any(n in UNIT for n in names):
            self.trainer._build_jitted()  # the sound epoch program again
        return worst

    driver.Driver.against_reference = against_reference


if __name__ == "__main__":
    sys.path.insert(0, ".")
    what = sys.argv[1]
    if what.startswith("sweep:"):
        sweep(what[len("sweep:"):].split(","))
    else:
        apply(what)
    from chipbench import run

    sys.exit(run.main(sys.argv[2:]))
