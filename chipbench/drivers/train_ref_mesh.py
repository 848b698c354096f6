"""Training cells of the latent-attention, sigmoid-routed stack, held to
the benchmark's plain reference with every agent on its own chip.

``train_ref.Driver``'s idea for a model that carries state no gradient
moves (the router's balancing bias, in the trainer's ``batch_stats``) and
for agents that live on different chips.  ``check`` keeps every check of
``train.Driver`` (the loss fell, the deviation agrees with the harness's,
the state was donated, every leaf has one shard on each of the cell's
chips) and then, outside the window, holds the program to the reference
the configuration names **on the state the timed window left, per agent,
at the timed sizes**.  That state is copied to the host and the trainer
runs ONE MORE unit, the timed program itself:

* the unit against a replay: from the copied state the reference (its
  own forward, gradients, Adam written out here, the bias's update, then
  the harness's own Metropolis matrix over the agents; the bias is NOT
  mixed, as the trainer never mixes ``batch_stats``) takes the same steps
  on the same batches.  **Every step's choice of experts is the
  program's**: the trainer's model routes on the replay's own state of
  that step and the reference takes those choices, as the end-to-end
  comparison does.  Routed by itself on its f32 activations, the
  reference sent up to 3,115 of a layer's 8,192 tokens elsewhere than the
  trainer where the sigmoids had saturated (``own_routing_flips``), and
  the held experts' update then read 33% off on a sound run; the router
  itself is held by ``routing_flips`` and ``router_logit_rel``.  Held to
  the replay: each agent's mean loss and mean gradient norm over the unit
  (``epoch_loss_abs``, ``epoch_gnorm_rel``), the change of the picked
  leaves (``update_rel``: 0 is the same step, 1 a state left unchanged),
  the mix (``mix_rel``: what the agents disagree by after the unit, each
  picked group's distance from the agents' mean, against the replay's; a
  ring round at Metropolis weights shrinks every disagreeing mode to a
  third, so a round left out reads 2 to 4 whatever the agents' updates
  have in common) and the bias after the unit (``bias_abs``: the mean
  over a layer's experts of the distance to the replay's; the bias moves
  in steps of ``gamma``, so a missed update stands out);
* layer by layer, on the copied state and the unit's first batch, the
  reference's layer on the very input the program's layer had: latent
  attention's operands as the kernels got them (``mla_k_rel``: the keys,
  the shared rotary part with them) and its output (``mla_rel``,
  ``mla_token_rel``), the dense layer (``dense_rel``), the router on the
  input it reports it read and this layer's bias (its logits,
  ``router_logit_rel``: f32 at the highest precision on both sides;
  ``routing_flips`` 0 outside f32 ties, and ``tie_share``, the tokens so
  left out, which must stay a minority), the weights it gave the held experts
  (``gate_rel``), the experts with the program's choices (``moe_rel``,
  ``moe_token_rel``); and the expert layers once more under a bias that
  puts the held experts first (``PROBE``; ``probe_*``), because the
  state a window leaves may choose none of them;
* end to end: the program's logits, loss and picked gradients against
  the reference's from the same token ids, the reference taking each
  token's experts as the program chose them.

Every one of these runs as one program over the cell's mesh, each agent's
on its own chip (``shard_map`` over the agent axis: one compilation, all
chips at once); agents stacked on one chip (a toy, a rehearsal) take
turns through the same programs.

The rules of ``LIMITS`` were fixed before any reading (ISSUE 34): a
limit is at least 4x the largest sound reading over the builder's runs of
the final program and under the smallest control meant to move it; a
relative reading's denominator cannot vanish (leaves of the expert layers
are taken over all of them as one vector, and a reading whose reference
norm is under ``FLOORS`` is reported absolute, ``<kind>_abs``, against
the relative limit times the floor); a non-finite reading fails only
where the reference's own value is finite.  The line before the result
prints each reading beside its limit.
"""

from __future__ import annotations

import json
import time

import jax
import jax.numpy as jnp
import numpy as np
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

from chipbench import reference
from chipbench.drivers import train, train_ref
from chipbench.drivers.train_ref import TIE, _at

#: name -> (limit, the readings it lies between).  "sound" is the largest
#: reading over the builder's chip runs of the final program (PERF.md, PR
#: 34, has every one); a control is a deliberate fault of
#: ``tests/chipbench_tests/faults_kanana2.py`` on the same state.  The
#: program computes in bf16 with f32 accumulation.
LIMITS = {
    "logits_rel": (0.015, "relative L2 over all logits: sound 0.222-0.245%; "
                   "the latent's norm skipped 3.07%"),
    "loss_abs": (0.003, "the mean of 8,192 token losses: sound 0.00022-"
                 "0.00051; the latent's norm skipped 0.0078 (rotary off the "
                 "shared key 0.0013, a key per head 0.0014: NOT caught by it)"),
    # per layer, the same input on both sides: relative L2 over the layer's
    # output, and the worst token's error over the RMS token norm
    "mla_k_rel": (0.05, "the keys the kernels were handed: sound 0.32-0.46%, "
                  "growing with depth; the latent's norm skipped 39.9%, "
                  "rotary off the shared key 73.5%, a key per head 96.0%"),
    "mla_rel": (0.02, "latent attention's output: sound 0.18-0.21%; scores "
                "scaled by 1/sqrt(128) 5.5% in the first layer (0.45% and "
                "0.19% in the next two, whose attention hardly depends on "
                "the scores), a key per head 8.9%, rotary off 12.8%"),
    "mla_token_rel": (0.04, "its worst token: sound 0.19-0.32%; the scale "
                      "11.5%, a key per head 36.6%, rotary off 42.8%"),
    "dense_rel": (0.01, "the dense layer's output: sound 0.182-0.188% on "
                  "every run; no control of this cell is meant to move it: "
                  "the limit is 4x the reading and a little"),
    "moe_rel": (0.02, "the expert layer's output, the program's choices: "
                "sound 0.29-0.36%; in the states read few or no tokens chose "
                "a held expert, and the controls of the weights move it "
                "under the probe alone (probe_moe_rel)"),
    "moe_token_rel": (0.03, "its worst token: sound 0.35-0.43%; as moe_rel"),
    "gate_rel": (0.001, "the weights of the held experts, all expert "
                 "layers as one vector, f32 on both sides of one input: "
                 "sound 0 on every run (absolute, 0, where no token chose a "
                 "held expert); at toy size the bias added into the "
                 "weights, the scale dropped and a dropped pair read 1% and "
                 "more where a held expert is chosen (probe_gate_rel)"),
    "router_input_rel": (0.075, "the input the router reports against the "
                         "block's norm as read back, largest element: sound "
                         "0.89-1.78%; no control of this cell moves it: the "
                         "limit is 4x the reading and a little"),
    "routing_flips": (0.5, "tokens whose experts the reference picks "
                      "otherwise on score + bias from the input and the "
                      "bias the program's router had, outside f32 ties: 0 "
                      "on every run; parameters rounded to bf16 628 and "
                      "779 of 8,192 in two layers of the four-chip state "
                      "(276 in the rehearsal's), the bias ignored 8,054"),
    "tie_share": (0.5, "the share of a layer's tokens whose 6th and 7th "
                  "score + bias tie in f32, which routing_flips leaves out: "
                  "sound 0-2.6% (212 of 8,192) by layer over seven runs; above a "
                  "half, routing_flips would no longer say how the layer "
                  "routes"),
    "router_logit_rel": (1e-5, "the router's logits against the reference's "
                         "product of the input and weights the router "
                         "reports, f32 at the highest precision on both "
                         "sides, relative L2 over a layer's (tokens, 128): "
                         "sound exactly 0 in every layer and agent (four "
                         "chips, seed 3400000301; the toy too); parameters "
                         "rounded to bf16 0.129-0.171% there"),
    # the expert layers once more under the probe's bias (PROBE): every
    # token on held experts.  Their controls ran at toy size alone (the
    # chip budget ended before the sweep at the published widths)
    "probe_gate_rel": (0.001, "gate_rel under the probe: sound 0 on every "
                       "run; at toy size the bias added into the weights, "
                       "the scale dropped and a dropped pair fail it"),
    "probe_moe_rel": (0.02, "moe_rel under the probe: sound 0.30-0.37%"),
    "probe_moe_token_rel": (0.03, "moe_token_rel under the probe: sound "
                            "0.35-0.44%; at toy size one token's held pairs "
                            "dropped fails it"),
    "probe_routing_flips": (0.5, "routing_flips under the probe: 0 on every "
                            "run"),
    "grad_rel": (0.6, "relative L2 of a picked group of leaves' gradient: "
                 "sound 1.2-13.5% by group and run (the embedding's 13.5% "
                 "once, 2.8-4.5% otherwise); every control of latent "
                 "attention reads 80% and more on the group it moves most "
                 "(the embedding 100-271%, kv_b_proj 80-172%)"),
    # the trainer's own unit against the replay
    "epoch_loss_abs": (0.002, "an agent's mean loss over the unit: sound "
                       "0.00004-0.00023 over seven runs; every update halved "
                       "0.0040"),
    "epoch_gnorm_rel": (0.1, "its mean gradient norm: sound 0.56-2.14%; "
                        "every update halved 13.2%"),
    "update_rel": (0.4, "a picked group's change over the unit, the held "
                   "experts' among them; 1 is a state left unchanged.  "
                   "The replay on the program's choices (four chips, seed "
                   "3400000301): 0.54-3.1%, the held experts 2.0%; routed by "
                   "itself (six runs before) 0.4-5.7%, once 11.7% and 13.6% "
                   "(the latent's norm, kv_a_proj) and once 32.7% (the held "
                   "experts).  Every update halved 0.47-0.50 (one chip's "
                   "rehearsal); the mix left out 0.87-2.6 in the four-chip "
                   "state"),
    "mix_rel": (0.5, "a picked group's distance from the agents' mean after "
                "the unit, all agents as one vector, against the replay's "
                "under the harness's own W: sound 0.22-2.1% (four chips, "
                "seed 3400000301); the round left out 2.09-2.61 in that "
                "state (every disagreeing mode at three times its size), a "
                "wrong W (the lazy walk) half of that, by the same "
                "arithmetic and at toy size"),
    "bias_abs": (0.002, "the bias after the unit, mean distance over a "
                 "layer's 128 experts: sound 0.00003-0.00025 (an expert "
                 "whose load sits at the mean takes the other sign where "
                 "the replay's routing differs: replay_rows_off, 1.3% of a "
                 "step's pairs at the most); the update skipped 0.008, "
                 "eight steps of gamma on every expert"),
}

#: kind -> the reference norm under which a relative reading of that kind
#: is reported absolute (``<kind>_abs/<leaf>``), against ``LIMITS[<kind>_
#: rel] * floor``.  A gradient group's norm is 1e-3 to 1 in every state
#: seen, an update's (eight Adam steps of 3e-4 on thousands of elements)
#: above 1e-2, the held weights' above 1.
FLOORS = {"grad": 1e-6, "update": 1e-6, "gate": 1e-6, "mix": 1e-6}

#: name -> the leaves (by path) whose gradients and updates are compared,
#: as one vector.  The expert layers' leaves are taken over all the expert
#: layers: in a given state every token of one layer may choose experts
#: none of which is held here, and that layer's experts then have a
#: gradient of exactly zero (ISSUE 34 on PR 33).
_ALL = range(64)  # cut to the stack's depth by ``leaves_for``
GRAD_LEAVES = {
    "embed": [("Embed_0", "embedding")],
    "head": [("Dense_0", "kernel")],
    # every layer's, as one vector: in a trained state the deeper layers'
    # attention hardly depends on the query (one chip's rehearsal read the
    # last layer's q_proj gradient alone 79% off, a difference of large
    # terms under bf16 rounding, where every other leaf read 3-4%: PERF.md,
    # PR 34), so a single deep layer's is a vanishing denominator too
    "mla.q_proj": [(f"layer_{i}", "_LatentAttention_0", "q_proj", "kernel")
                   for i in _ALL],
    "mla.kv_a_proj": [(f"layer_{i}", "_LatentAttention_0", "kv_a_proj",
                       "kernel") for i in _ALL],
    "mla.kv_b_proj": [(f"layer_{i}", "_LatentAttention_0", "kv_b_proj",
                       "kernel") for i in _ALL],
    "mla.kv_a_norm": [(f"layer_{i}", "_LatentAttention_0", "kv_a_norm",
                       "scale") for i in _ALL],
    "dense.up_proj": [("layer_0", "up_proj", "kernel")],
    "moe.experts_down": [(f"layer_{i}", "HeldExpertsMLP_0", "w_down")
                         for i in _ALL],
    "moe.shared_up": [(f"layer_{i}", "HeldExpertsMLP_0", "shared_up",
                       "kernel") for i in _ALL],
    # read and printed, held to no limit (``UNLIMITED``)
    "moe.router": [(f"layer_{i}", "HeldExpertsMLP_0", "router")
                   for i in _ALL],
}
#: The routers' gradient, update and disagreement are printed as
#: ``router_grad_rel``, ``router_update_rel`` and ``router_mix_rel`` and
#: decide nothing: the gradient reaches a router only through the tokens
#: that chose a held expert, a saturated sigmoid passes next to none of it
#: (a denominator that vanishes), and Adam turns what is left into steps of
#: full size.  Sound runs read 0-300% (gradient), 0.6-89% (update) and
#: 65% (disagreement).  The held experts' leaves are held to every limit
#: like any other group's.
UNLIMITED = {"moe.router": "router"}

#: the reference's remat switch (``blocks``: memory, not mathematics)
BLOCKS = 1

#: What the probe adds to the bias of the experts held here.  The state a
#: window leaves may send every token to experts held elsewhere (the
#: router's sigmoids saturate within a few dozen Adam steps on tokens that
#: are all alike, and the bias moves by 0.001 a step): the held experts'
#: weights are then exactly zero and a fault in them shows nowhere.  The
#: bias is an input of the program, so the comparison's forward runs once
#: more with a bias that puts the held experts first (scores lie in [0, 1])
#: and the expert layer is held to the reference there too: ``probe_*``.
PROBE = 2.0
PROBED = ("gate_rel", "moe_rel", "moe_token_rel", "routing_flips")


def _has(tree, path) -> bool:
    for key in path:
        if not hasattr(tree, "keys") or key not in tree:
            return False
        tree = tree[key]
    return True


def leaves_for(params) -> dict:
    """``GRAD_LEAVES`` as far as this tree has them (a toy is shallower)."""
    out = {}
    for name, paths in GRAD_LEAVES.items():
        paths = [p for p in paths if _has(params, p)]
        if paths:
            out[name] = paths
    return out


def _pick(tree, paths) -> tuple:
    return tuple(_at(tree, path) for path in paths)


def _norm(arrays):
    return jnp.sqrt(sum(jnp.sum(jnp.square(a.astype(jnp.float32)))
                        for a in arrays))


def _err_and_norm(got, want):
    """``(|got - want|, |want|)`` of two arrays, or two tuples of arrays
    taken as one vector: the host makes the reading of them
    (:func:`reading_of`)."""
    if not isinstance(got, (tuple, list)):
        got, want = (got,), (want,)
    diff = [g.astype(jnp.float32) - w.astype(jnp.float32)
            for g, w in zip(got, want, strict=True)]
    return jnp.stack([_norm(diff), _norm(want)])


def _token_err_and_norm(got, want):
    """The worst token's L2 error, and the RMS token norm of ``want``."""
    got, want = got.astype(jnp.float32), want.astype(jnp.float32)
    err = jnp.linalg.norm(got - want, axis=-1)
    return jnp.stack(
        [jnp.max(err), jnp.sqrt(jnp.mean(jnp.sum(want * want, -1)))])


def _flips(picked, own, chosen, K):
    """Tokens whose experts the reference (``own``, from the values
    ``picked`` it chose on) chose otherwise than ``chosen``, as sets; a
    token whose K-th and (K+1)-th values tie is left out; the share of
    such tokens is returned beside the count."""
    top = -jnp.sort(-picked, axis=-1)[:, K - 1:K + 1]
    tie = (top[:, 0] - top[:, 1]) <= TIE * jnp.abs(top[:, 0])
    differ = jnp.any(jnp.sort(own, -1) != jnp.sort(chosen, -1), axis=-1)
    return jnp.sum(differ & ~tie), jnp.mean(tie.astype(jnp.float32))


def _named(kind: str, leaf: str) -> str:
    """``grad_rel/embed``, but ``router_grad_rel`` for an unlimited leaf."""
    if leaf in UNLIMITED:
        return f"{UNLIMITED[leaf]}_{kind}"
    return f"{kind}/{leaf}"


def reading_of(name: str, err: float, norm: float) -> tuple:
    """``(name, value)`` of one comparison from its error and the
    reference's norm, by the module's rules: relative where the norm is
    above the kind's floor (no floor: always), absolute and renamed
    ``<kind>_abs`` under it; ``None`` where the reference itself is not
    finite; ``inf`` where only the program's side is not."""
    if not np.isfinite(norm):
        return name, None
    if not np.isfinite(err):
        return name, float("inf")
    kind = name.split("/")[0]
    floor = FLOORS.get(kind[:-len("_rel")]) if kind.endswith("_rel") else None
    if floor is not None and norm < floor:
        return name.replace("_rel", "_abs", 1), float(err)
    return name, float(err / norm) if norm > 0 else float(err)


def limit_of(name: str):
    """The limit a reading is held to, or None (printed only)."""
    kind = name.split("/")[0]
    if kind in LIMITS:
        return LIMITS[kind][0]
    if kind.endswith("_abs") and kind[:-4] in FLOORS:
        return LIMITS[kind[:-4] + "_rel"][0] * FLOORS[kind[:-4]]
    return None


def beside_limits(readings: dict) -> dict:
    return {name: {"read": value, "limit": limit_of(name)}
            for name, value in readings.items()
            if limit_of(name) is not None}


def verdicts(readings: dict) -> dict:
    """One verdict a limit: every layer, leaf and agent under it.  A
    reading of None (the reference's own value was not finite) decides
    nothing."""
    out = {}
    for name, value in readings.items():
        limit = limit_of(name)
        if limit is None:
            continue
        key = name.split("/")[0] + ".within"
        out[key] = out.get(key, True) and (value is None or value <= limit)
    for kind in LIMITS:  # a limit nothing was read against fails
        twin = kind[:-4] + "_abs.within"  # read under its floor: absolute
        if not (kind.endswith("_rel") and twin in out):
            out.setdefault(kind + ".within", False)
    return out


def over_agents(fn, mesh, donate=()):
    """``fn`` of one agent's operands, as one jitted program of the
    stacked operands (agents leading).  Under a mesh each chip runs its
    own agent's, all at once; without one the program takes ONE agent
    (a leading axis of 1) and the agents take turns (``Driver.groups``)."""
    one = lambda *args: jax.tree.map(
        lambda a: a[None], fn(*jax.tree.map(lambda a: a[0], args)))
    if mesh is None:
        return jax.jit(one, donate_argnums=donate)
    spec = P(mesh.axis_names[0])
    return jax.jit(jax.shard_map(one, mesh=mesh, in_specs=spec,
                                 out_specs=spec, check_vma=False),
                   donate_argnums=donate)


def replay_step(cfg: dict, ref, optimizer: dict, model):
    """One training step as the reference takes it, of one agent: the
    experts of every token as the trainer's ``model`` chooses them on this
    very state (module docstring), the reference's own forward and
    gradients, Adam as published (arXiv:1412.6980, algorithm 1) on ``p``
    with the moments ``mu``, ``nu`` after ``count`` steps, then the bias's
    step from those choices.  Returns the new four, the loss, the
    gradient's norm and the (token, choice) pairs that fell on held
    experts, all layers (the trainer's ``moe.rows_held``)."""
    if optimizer["name"] != "adam" or optimizer.get("kwargs"):
        raise ValueError(f"the replay knows plain Adam, not {optimizer}")
    lr, b1, b2, eps = optimizer["learning_rate"], 0.9, 0.999, 1e-8

    dense = cfg.get("num_dense_layers", 0)

    def step(p, mu, nu, b, count, x, y):
        sown = model.apply({"params": p, "batch_stats": b}, x[None],
                           mutable=["intermediates"])[1]["intermediates"]
        chosen = {i: sown[f"layer_{i}"]["HeldExpertsMLP_0"]["chosen"][0]
                  for i in range(dense, cfg["num_layers"])}
        loss, g = jax.value_and_grad(lambda p: ref.token_loss(
            ref.forward(p, x, cfg, BLOCKS, chosen, b), y))(p)
        t = (count + 1).astype(jnp.float32)
        mu = jax.tree.map(lambda m, g: b1 * m + (1 - b1) * g, mu, g)
        nu = jax.tree.map(lambda v, g: b2 * v + (1 - b2) * g * g, nu, g)
        p = jax.tree.map(
            lambda p, m, v: p - lr * (m / (1 - b1 ** t)) / (
                jnp.sqrt(v / (1 - b2 ** t)) + eps),
            p, mu, nu)
        gnorm = jnp.sqrt(sum(jnp.sum(a * a) for a in jax.tree.leaves(g)))
        b = {layer: {"HeldExpertsMLP_0": {"route_bias": ref.bias_update(
            stats["HeldExpertsMLP_0"]["route_bias"],
            chosen[int(layer.split("_")[1])], cfg)}}
            for layer, stats in b.items()}
        first = cfg.get("first_expert", 0)
        held = cfg.get("experts_held") or cfg["num_experts"]
        rows = sum(jnp.sum((c >= first) & (c < first + held))
                   for c in chosen.values())
        return p, mu, nu, b, loss, gnorm, rows

    return step


def program_of(model, picked_leaves: dict):
    """The trainer's model on one agent's parameters ``p``, bias ``b`` and
    one sequence: loss, logits, the picked gradients, every layer's input
    and output, attention's operands and the router's input, choices and
    weights."""
    layers = {"RMSNorm_0", "RMSNorm_1", "_LatentAttention_0",
              "HeldExpertsMLP_0", "down_proj"}

    def program_loss(p, b, x, y):
        logits = model.apply({"params": p, "batch_stats": b}, x[None])[0]
        logp = jax.nn.log_softmax(logits)
        return -jnp.mean(jnp.take_along_axis(logp, y[:, None], -1)), logits

    def program(p, b, x, y):
        (loss, logits), grads = jax.value_and_grad(
            program_loss, has_aux=True)(p, b, x, y)
        _, seen = model.apply(
            {"params": p, "batch_stats": b}, x[None],
            capture_intermediates=lambda mdl, _method: mdl.name in layers,
            mutable=["intermediates"],
        )
        picked = {name: _pick(grads, paths)
                  for name, paths in picked_leaves.items()}
        return loss, logits, picked, seen["intermediates"]

    return program


def reference_side(cfg: dict, ref, picked_leaves: dict):
    """The reference against what :func:`program_of` returned, of one
    agent: ``end_to_end`` and ``layer_by_layer``; every value is an
    ``(error, reference norm)`` pair, or a count."""
    L, dense = cfg["num_layers"], cfg.get("num_dense_layers", 0)
    K = cfg["moe_top_k"]
    chosen_of = lambda seen, i: seen[f"layer_{i}"]["HeldExpertsMLP_0"][
        "chosen"][0]

    def end_to_end(p, b, x, y, loss, logits, picked, seen):
        routing = {i: chosen_of(seen, i) for i in range(dense, L)}

        def ref_loss(p):  # one forward pass for logits, loss and gradients
            logits = ref.forward(p, x, cfg, BLOCKS, routing, b)
            return ref.token_loss(logits, y), logits

        (want_loss, want_logits), want = jax.value_and_grad(
            ref_loss, has_aux=True)(p)
        out = {"logits_rel": _err_and_norm(logits, want_logits),
               "loss_abs": jnp.stack([jnp.abs(loss - want_loss),
                                      jnp.ones(())])}
        for name, paths in picked_leaves.items():
            out[_named("grad_rel", name)] = _err_and_norm(
                picked[name], _pick(want, paths))
        return out

    def layer_by_layer(p, b, seen):
        out, gates_got, gates_want = {}, [], []
        with jax.default_matmul_precision("highest"):
            p = jax.tree.map(lambda a: a.astype(jnp.float32), p)
            for i in range(L):
                lp, ls = p[f"layer_{i}"], seen[f"layer_{i}"]
                io = lambda name: ls[name]["__call__"][0][0].astype(
                    jnp.float32)
                # latent attention: its output, and the keys the kernels
                # were handed, from the input the layer had
                attn, h = lp["_LatentAttention_0"], io("RMSNorm_0")
                got, want = io("_LatentAttention_0"), ref.latent_attention(
                    attn, h, cfg, BLOCKS)
                out[f"mla_token_rel/layer_{i}"] = _token_err_and_norm(
                    got, want)
                out[f"mla_rel/layer_{i}"] = _err_and_norm(got, want)
                out[f"mla_k_rel/layer_{i}"] = _err_and_norm(
                    ls["_LatentAttention_0"]["k"][0][0],
                    ref.latent_operands(attn, h, cfg)[1])
                h = io("RMSNorm_1")
                if i < dense:
                    out[f"dense_rel/layer_{i}"] = _err_and_norm(
                        io("down_proj"), ref.dense_mlp(lp, h))
                    continue
                # the router, on the input it really had (the layer sows
                # it) and this layer's bias: the reference's choices on
                # score + bias against the program's, as sets
                moe, sown = lp["HeldExpertsMLP_0"], ls["HeldExpertsMLP_0"]
                had, chosen = sown["router_input"][0], sown["chosen"][0]
                bias = b[f"layer_{i}"]["HeldExpertsMLP_0"]["route_bias"]
                out[f"router_input_rel/layer_{i}"] = jnp.stack(
                    [jnp.max(jnp.abs(had - h)), jnp.max(jnp.abs(h))])

                out[f"router_logit_rel/layer_{i}"] = _err_and_norm(
                    sown["router_logits"][0], had @ moe["router"])

                def flips(read):
                    scores, own = ref.route(moe, read, cfg, bias)
                    return _flips(scores + bias, own, chosen, K)

                out[f"routing_flips/layer_{i}"], out[
                    f"tie_share/layer_{i}"] = flips(had)
                out[f"own_routing_flips/layer_{i}"] = flips(h)[0]
                # the weights of the held experts, from the input the
                # router had and the program's choices
                scores = ref.route(moe, had, cfg, bias)[0]
                gates = jnp.take_along_axis(scores, chosen, axis=-1)
                gates = gates / (jnp.sum(gates, -1, keepdims=True) + 1e-20)
                gates = gates * cfg.get("route_scale", 1.0)
                first = cfg.get("first_expert", 0)
                held = cfg.get("experts_held") or cfg["num_experts"]
                gates_want.append(jnp.stack(
                    [jnp.sum(jnp.where(chosen == first + e, gates, 0.0), -1)
                     for e in range(held)], axis=-1))
                gates_got.append(sown["held_weights"][0])
                # the experts, with those choices on the input a caller
                # reads back
                got, want = io("HeldExpertsMLP_0"), ref.expert_layer(
                    moe, h, cfg, bias, chosen=chosen)
                out[f"moe_token_rel/layer_{i}"] = _token_err_and_norm(
                    got, want)
                out[f"moe_rel/layer_{i}"] = _err_and_norm(got, want)
        if gates_want:
            out["gate_rel"] = _err_and_norm(tuple(gates_got),
                                            tuple(gates_want))
        return out

    return end_to_end, layer_by_layer


def _keep_worst(worst: dict, name: str, value) -> None:
    """The largest reading under ``name``; None (the reference's own value
    was not finite) only while nothing else was read."""
    if value is None:
        worst.setdefault(name, None)
    elif worst.get(name) is None or value > worst[name]:
        worst[name] = value


COUNTS = ("routing_flips", "tie_share", "own_routing_flips",
          "probe_routing_flips")


def readings_from(pairs: dict) -> dict:
    """The largest reading over the agents of what the comparison's
    programs returned (agents leading): ``{name: value or None}``."""
    out: dict = {}
    for name, value in pairs.items():
        value = np.asarray(value)
        for a in range(value.shape[0]):
            if name.split("/")[0] in COUNTS:
                named, read = name, float(value[a])
            else:
                named, read = reading_of(name, *value[a])
            _keep_worst(out, named, read)
    return out


class Driver(train_ref.Driver):
    """``train_ref.Driver``'s set-up (one epoch a unit, one sequence a
    step, the configuration's reference, the kernels' work for the
    roofline) with the comparison of this module."""

    def __init__(self, cell: dict, config: dict, seed: int, devices: list):
        super().__init__(cell, config, seed, devices)
        self.mesh = (Mesh(np.array(devices[:config["agents"]]), ("agents",))
                     if self.sharded else None)
        #: after a check: the state it compared, the unit, the batches and
        #: the replay, on the host, for whoever compares again
        #: (tests/chipbench_tests/faults_kanana2.py)
        self.kept = None

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
            # (token, choice) pairs on held experts, all layers and agents
            "rows_held": count("moe.rows_held", np.sum),
            "load_max": float(count("moe.load_max", np.max)),
            "load_max_all": float(count("moe.load_max_all", np.max)),
        }

    def check(self, units: list) -> dict:
        checks = train.Driver.check(self, units)
        for name in ("load_max", "load_max_all"):
            checks[name] = max(u[name] for u in units)
        checks["rows_held_per_step"] = (
            sum(u["rows_held"] for u in units) / sum(u["steps"] for u in units)
        )
        readings = self.against_reference()
        print("reference: " + json.dumps(beside_limits(readings)), flush=True)
        checks.update(readings)
        checks.update(verdicts(readings))
        return checks

    # ------------------------------------------------------------------ #
    def groups(self) -> list:
        """The agents one program takes at a time: all of them under a
        mesh (one a chip), one after the other on one chip."""
        n = self.config["agents"]
        return ([np.arange(n)] if self.mesh is not None
                else [np.array([a]) for a in range(n)])

    def _put(self, tree, group=None):
        """A host tree with the agents leading (``group``: those of them),
        onto the cell's chips."""
        if group is not None:
            tree = jax.tree.map(lambda a: np.asarray(a)[group], tree)
        if self.mesh is None:
            return jax.device_put(tree, self.devices[0])
        return jax.device_put(tree, NamedSharding(self.mesh, P("agents")))

    def compare(self, p, b, x, y, *, program_p=None) -> dict:
        """The program's forward and gradients on a group's parameters
        ``p``, bias ``b`` and one sequence each (on the chips), against the
        reference's: every reading of the second and third kind (module
        docstring), the largest over the group.  ``program_p``: what the
        program reads in place of ``p`` (a control's)."""
        cfg = self.config["model"]["kwargs"]
        if self._compiled is None:
            picked = leaves_for(p)
            self._compiled = tuple(over_agents(fn, self.mesh) for fn in (
                *reference_side(cfg, self.ref, picked),
                program_of(self.trainer.model, picked)))
        end_to_end, layer_by_layer, program = self._compiled
        loss, logits, picked, seen = program(
            p if program_p is None else program_p, b, x, y)
        pairs = {**end_to_end(p, b, x, y, loss, logits, picked, seen),
                 **layer_by_layer(p, b, seen)}
        del loss, logits, picked, seen
        # the expert layers once more, the held experts put first (PROBE)
        cfg_held = cfg.get("experts_held") or cfg["num_experts"]
        held = np.zeros((cfg["num_experts"],), np.float32)
        held[cfg.get("first_expert", 0):][:cfg_held] = PROBE
        probe_b = jax.tree.map(lambda a: a + held, b)
        seen = program(p if program_p is None else program_p, probe_b, x, y)[3]
        pairs.update({
            "probe_" + name: value
            for name, value in layer_by_layer(p, probe_b, seen).items()
            if name.split("/")[0] in PROBED})
        del seen
        return readings_from(jax.device_get(pairs))

    def compare_all(self, before: dict, xy: tuple) -> dict:
        """:meth:`compare` of every agent's copied state and sequence, the
        largest reading over the agents."""
        worst: dict = {}
        for group in self.groups():
            p, b = self._put(before["p"], group), self._put(before["b"], group)
            for name, value in self.compare(
                    p, b, *self._put(xy, group)).items():
                _keep_worst(worst, name, value)
            del p, b
        return worst

    def snapshot(self) -> dict:
        """What the window left, on the host."""
        params, bias, opt, _ = self.trainer.state
        return jax.device_get({"p": params, "b": bias, "opt": opt})

    def run_unit(self, before: dict) -> dict:
        """The timed program once more, from the state ``before`` is a
        copy of; what it reported and left, on the host."""
        trainer = self.trainer
        picked = leaves_for(before["p"])
        epoch = trainer._epochs_done
        told = trainer.train_epochs(self.k)[0]
        return {
            "told": told, "epoch": epoch,
            "p": jax.device_get({name: _pick(trainer.state[0], paths)
                                 for name, paths in picked.items()}),
            "b": jax.device_get(trainer.state[1]),
        }

    def batches(self, epoch: int):
        """``t -> (X, y)``, every agent's batch of step ``t`` of that
        epoch, agents leading."""
        n = self.config["agents"]
        order = self.trainer._epoch_perm(epoch)[:, :, 0]  # (steps, n)
        Xs, ys = np.asarray(self.trainer._Xs), np.asarray(self.trainer._ys)
        return lambda t: (
            np.stack([Xs[a, order[t, a]] for a in range(n)]),
            np.stack([ys[a, order[t, a]] for a in range(n)]))

    def replay(self, before: dict, batch) -> dict:
        """The unit as the reference takes it, from ``before``: what it
        left of the picked leaves and the bias, and each agent's mean loss
        and gradient norm."""
        cfg, config = self.config["model"]["kwargs"], self.config
        has_moments = lambda s: hasattr(s, "mu") and hasattr(s, "nu")
        adam, = [s for s in jax.tree.leaves(before["opt"], is_leaf=has_moments)
                 if has_moments(s)]
        picked = leaves_for(before["p"])
        step = over_agents(
            replay_step(cfg, self.ref, config["optimizer"],
                        self.trainer.model), self.mesh,
            donate=(0, 1, 2, 3) if self.devices[0].platform != "cpu" else ())
        done = []
        for group in self.groups():
            put = lambda tree: self._put(tree, group)
            p, b, mu, nu = map(put, (before["p"], before["b"], adam.mu,
                                     adam.nu))
            losses, gnorms, rows = [], [], []
            for t in range(self.epoch_len):
                p, mu, nu, b, loss, gnorm, held = step(
                    p, mu, nu, b, put(np.asarray(adam.count) + t),
                    *put(batch(t)))
                losses.append(loss)
                gnorms.append(gnorm)
                rows.append(held)
            del mu, nu
            done.append(jax.device_get({
                "p": {name: _pick(p, paths)
                      for name, paths in picked.items()},
                "b": b, "loss": jnp.stack(losses, 1),   # (agents, steps)
                "gnorm": jnp.stack(gnorms, 1),
                "rows": jnp.stack(rows, 1)}))
            del p, b
        out = jax.tree.map(lambda *parts: np.concatenate(parts), *done)
        out["loss"], out["gnorm"] = (out[k].mean(axis=1)
                                     for k in ("loss", "gnorm"))
        return out

    def unit_readings(self, before: dict, unit: dict, replay: dict) -> dict:
        """The trainer's unit against the replay: readings of the first
        kind (module docstring), the largest over the agents."""
        n, told = self.config["agents"], unit["told"]
        pairs = {
            "epoch_loss_abs": [
                (abs(replay["loss"][a] - float(told["train_loss"][a])), 1.0)
                for a in range(n)],
            "epoch_gnorm_rel": [
                (abs(replay["gnorm"][a] - float(told["grad_norm"][a])),
                 replay["gnorm"][a]) for a in range(n)],
        }
        # how far the routing of the replay's steps (the trainer's model
        # on the replay's state) lies from that of the trainer's own: the
        # pairs on held experts, step by step, as a share of a step's
        # pairs; printed, held to no limit
        cfg = self.config["model"]["kwargs"]
        pairs_a_step = self.config["unit_per_sample"] * cfg["moe_top_k"] * (
            cfg["num_layers"] - cfg.get("num_dense_layers", 0))
        told_rows = np.asarray(told["counters"]["moe.rows_held"])  # (steps, n)
        pairs["replay_rows_off"] = [
            (np.max(np.abs(replay["rows"][a] - told_rows[:, a])),
             pairs_a_step) for a in range(n)]
        # the bias is not mixed: each agent's against its own replay
        pairs["bias_abs"] = [
            (max(np.mean(np.abs(got[a] - want[a])) for got, want in zip(
                jax.tree.leaves(unit["b"]), jax.tree.leaves(replay["b"]),
                strict=True)), 1.0) for a in range(n)]
        # the mix, by the harness's own matrix; then each picked group's
        # change over the unit, the trainer's against the replay's, and
        # what the agents disagree by afterwards
        W = self.mix_matrix(told["mixed"])
        # squared norms agent by agent (n,), in f64, of a group's leaves
        sq = lambda arrays: sum(
            np.einsum("ak,ak->a", x, x)
            for x in (np.reshape(x, (n, -1)) for x in arrays))
        apart = lambda x: x - x.mean(axis=0, keepdims=True)
        for name, paths in leaves_for(before["p"]).items():
            starts = _pick(before["p"], paths)
            want = [np.einsum("ab,b...->a...", W, np.asarray(end, np.float64))
                    for end in replay["p"][name]]
            off = [np.asarray(got, np.float64) - w
                   for got, w in zip(unit["p"][name], want)]
            moved = sq([w - start for w, start in zip(want, starts)])
            pairs[_named("update_rel", name)] = list(
                zip(np.sqrt(sq(off)), np.sqrt(moved)))
            # what the agents disagree by after the unit (each started it
            # from a state of its own: the ends, not the changes)
            pairs[_named("mix_rel", name)] = [
                (np.sqrt(sq(map(apart, off)).sum()),
                 np.sqrt(sq(map(apart, want)).sum()))]
        return readings_from(pairs)

    def mix_matrix(self, mixed: bool = True) -> np.ndarray:
        """What one unit's gossip does to the agents, by the harness's own
        arithmetic: the Metropolis matrix of the cell's graph to the power
        of its rounds (the identity where the unit did not mix)."""
        topology, n = self.config["topology"], self.config["agents"]
        return np.linalg.matrix_power(
            reference.metropolis(reference.adjacency(topology["kind"], n)),
            self.mix_times if mixed else 0)

    def against_reference(self) -> dict:
        """Every reading, the largest over the agents, and what the check
        cost on the host's clock, compiles included."""
        trainer = self.trainer
        f32 = all(leaf.dtype == jnp.float32
                  for leaf in jax.tree.leaves(trainer.state[0]))
        t0 = time.perf_counter()
        before = self.snapshot()
        unit = self.run_unit(before)
        batch = self.batches(unit["epoch"])
        # The check takes the chips: the trainer's state (Adam's moments
        # are two thirds of it) makes room for the reference.
        trainer._state = None
        self.kept = (before, unit, batch)
        t1 = time.perf_counter()
        readings = self.compare_all(before, batch(0))
        # said at once: a later step that fails must not take these along
        print("compared: " + json.dumps(beside_limits(readings)), flush=True)
        t2 = time.perf_counter()
        replay = self.replay(before, batch)
        self.kept += (replay,)
        readings.update(self.unit_readings(before, unit, replay))
        readings["params_f32"] = f32
        return {**readings, "unit_s": t1 - t0, "compare_s": t2 - t1,
                "replay_s": time.perf_counter() - t2}
