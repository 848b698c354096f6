"""Deliberate faults in the program, to show that the chip benchmark's
comparison with its reference (``chipbench/drivers/train_ref.py``) fails
them, and to give each of its limits the reading it has to stay under.

``apply(name)`` rewrites lines of the program's source in the running
process and returns what undoes it;
``tests/chipbench_tests/test_train_ref.py`` runs the toy cell on the CPU
with some of them, and on the chip they go through the real cell:

    python tests/chipbench_tests/faults.py <name> --workload <cell> --seed <n> --seconds <s> --trace 0
    python tests/chipbench_tests/faults.py sweep:<name>,<name>,... --workload ...

The first form runs the whole cell with the fault in place (training
included: the one way to put a fault into the timed program, as
``half_update`` needs).  The second runs the sound cell and then, on the
parameters and the batch its check compared (agent 0), compares again
with each fault in turn in the program's forward and backward: one
set-up and one trained state for all of them, every reading beside the
sound one.  The program itself has no such switch.
"""

import contextlib
import importlib
import inspect
import json
import sys

_ROUND = "(lambda a: a.astype(jnp.bfloat16).astype(jnp.float32))"
_RULE = ("distributed_learning_tpu.ops.gated_delta", "gated_delta_rule")
_MOE = ("distributed_learning_tpu.models.moe", "HeldExpertsMLP")

#: name -> (module, attribute, [(old, new), ...]); each ``old`` stands
#: exactly once in the attribute's source
FAULTS = {
    # the delta rule's state rounded to bf16 after every chunk
    "bf16_state": (*_RULE, [(
        "        return S, o\n",
        "        return S.astype(jnp.bfloat16).astype(f32), o\n")]),
    # everything the rule holds in bf16: its operands, the running decay,
    # the solve's result, the state and the output (the products round
    # their operands to bf16 as they always did)
    "rule_bf16": (*_RULE, [
        ("        x = x.astype(f32)\n",
         "        x = x.astype(jnp.bfloat16).astype(f32)\n"),
        ("    gc = jnp.cumsum(g, axis=-1)  # decay",
         f"    gc = {_ROUND}(jnp.cumsum(g, axis=-1))  # decay"),
        ("    u, w = sol[..., :Dv], sol[..., Dv:]\n",
         f"    sol = {_ROUND}(sol)\n"
         "    u, w = sol[..., :Dv], sol[..., Dv:]\n"),
        ("        return S, o\n", f"        return {_ROUND}(S), {_ROUND}(o)\n"),
    ]),
    # a wrong decay: the state crosses a chunk boundary undecayed
    "no_chunk_decay": (*_RULE, [(
        "        S = S * jnp.exp(end_i)[..., None, None] + jnp.einsum(\n",
        "        S = S + jnp.einsum(\n")]),
    # a dropped chunk boundary: the middle chunk reads a zero state
    "lost_chunk": (*_RULE, [
        ("    u, w = sol[..., :Dv], sol[..., Dv:]\n",
         "    u, w = sol[..., :Dv], sol[..., Dv:]\n"
         "    w = w.at[w.shape[0] // 2].set(0.0)\n"),
        ("    q_in = q * jnp.exp(gc)[..., None]  # the query",
         "    q_in = (q * jnp.exp(gc)[..., None]).at[q.shape[0] // 2].set(0.0)"
         "  # the query"),
    ]),
    # the first token with a held (token, choice) pair loses its held pairs
    # in every expert layer (and the counters with them: they cannot see it)
    "drop_pair": (*_MOE, [(
        "            on = local[..., None] == jnp.arange(Eh)          # (S, K, Eh)\n",
        "            on = local[..., None] == jnp.arange(Eh)\n"
        "            on = on & (jnp.arange(S)[:, None, None]\n"
        "                       != jnp.argmax(on.any((1, 2))))\n")]),
    # the router's product and softmax in bf16
    "bf16_router": (*_MOE, [(
        "            logits = jnp.dot(seen, router, precision=\"highest\")\n",
        "            logits = jnp.dot(seen.astype(jnp.bfloat16),\n"
        "                             router.astype(jnp.bfloat16))\n")]),
    # the router reads (and reports) its input off by up to 3% a channel
    "router_input_skew": (*_MOE, [(
        "                tokens.astype(jnp.float32), info.nexp, info.nmant)\n",
        "                tokens.astype(jnp.float32) * (1 + 0.03 * jnp.cos(\n"
        "                    jnp.arange(d, dtype=jnp.float32))),\n"
        "                info.nexp, info.nmant)\n")]),
    # gated attention's sigmoid output gate skipped
    "no_gate": (
        "distributed_learning_tpu.models.transformer", "_Attention",
        [("        if gate is not None:\n", "        if False:\n")]),
    # every step's update halved, in the trainer's epoch program alone
    "half_update": (
        "distributed_learning_tpu.training.trainer", "GossipTrainer",
        [("                params = optax.apply_updates(params, updates)\n",
          "                params = optax.apply_updates(params, jax.tree.map(\n"
          "                    lambda u: 0.5 * u, updates))\n")]),
}


def _bf16_params():
    """The program reads parameters rounded to bf16 (the comparison's
    program, not the trainer's: its state stays f32)."""
    import jax
    import jax.numpy as jnp

    from chipbench.drivers import train_ref

    inner = train_ref.Driver.compare

    def compare(self, p, x, y, program_p=None):
        rounded = jax.tree.map(
            lambda a: a.astype(jnp.bfloat16).astype(a.dtype), p)
        return inner(self, p, x, y, program_p=rounded)

    train_ref.Driver.compare = compare
    return lambda: setattr(train_ref.Driver, "compare", inner)


INSTALLED = {"bf16_params": _bf16_params}
NAMES = sorted([*FAULTS, *INSTALLED])


def apply(name: str):
    """Put the fault in place; returns the call that takes it out again."""
    if name in INSTALLED:
        return INSTALLED[name]()
    module_name, attr, patches = FAULTS[name]
    module = importlib.import_module(module_name)
    sound = getattr(module, attr)
    src = inspect.getsource(sound)
    for old, new in patches:
        assert src.count(old) == 1, (name, old, src.count(old))
        src = src.replace(old, new)
    exec(compile(src, f"<fault {name}>", "exec"), module.__dict__)
    aliases = []
    if attr == "gated_delta_rule":  # the mixer imported the rule by name
        aliases.append(importlib.import_module(
            "distributed_learning_tpu.models.gated_delta"))
    for alias in aliases:
        setattr(alias, attr, getattr(module, attr))

    def undo():
        for holder in (module, *aliases):
            setattr(holder, attr, sound)

    return undo


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

    from chipbench.drivers import train_ref

    sound_check = train_ref.Driver.against_reference

    def against_reference(self):
        worst = sound_check(self)
        p_host, x, y = self.kept
        for name in ["sound", *names]:
            p = jax.device_put(p_host, self.devices[0])
            self._compiled = None  # the program is traced anew
            with applied(name) if name != "sound" else contextlib.nullcontext():
                readings = self.compare(p, x, y)
            del p
            failed = sorted(k[:-len(".within")] for k, ok in
                            train_ref.verdicts(readings).items() if not ok)
            print(f"fault {name}: fails {failed}: " + json.dumps(readings),
                  flush=True)
        self._compiled = None
        return worst

    train_ref.Driver.against_reference = against_reference


if __name__ == "__main__":
    sys.path.insert(0, ".")
    what = sys.argv[1]
    if what.startswith("sweep:"):
        sweep(what[len("sweep:"):].split(","))
    else:
        apply(what)
    from chipbench import run

    sys.exit(run.main(sys.argv[2:]))
