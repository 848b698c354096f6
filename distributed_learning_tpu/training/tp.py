"""Tensor parallelism for the transformer via GSPMD sharding annotations.

The idiomatic-JAX half of the parallelism matrix: where ``spmd_lm.py``
writes the collectives by hand (shard_map + ppermute/psum), this module
only *annotates* — megatron-style shardings on the transformer's weight
matrices over a ``model`` mesh axis — and lets XLA's SPMD partitioner
insert the all-gathers/reduce-scatters.  The recipe the scaling
playbook prescribes: pick a mesh, place shardings, compile, profile.

Rules (the Megatron-LM split, arXiv:1909.08053):

* QKV projection kernel (d_model, 3, H, Dh) -> shard the HEAD axis.
  The model emits QKV through one DenseGeneral with structured
  (3, H, Dh) features precisely so the kernel HAS a head axis: this is
  the true head-local Megatron split, and Q/K/V activations plus the
  whole attention computation stay on the head's device — no activation
  resharding inside the block (asserted by the HLO collective-count
  test in tests/test_tp.py),
* attention out-projection (H, Dh, d_model) -> shard the head rows (its
  matmul contracts the sharded axis; XLA places one psum),
* MLP up kernel (d, 4d) -> columns; MLP down kernel (4d, d) -> rows
  (same column-then-row pairing, one psum per block),
* embeddings and LayerNorms replicated.

``shard_transformer_params`` maps a TransformerLM param tree to these
shardings; ``make_tp_train_step`` builds a jitted data x tensor
parallel LM step over a ``(data, model)`` mesh: batch sharded over
``data``, weights over ``model``, XLA inserting every collective.
"""

from __future__ import annotations

import functools
from typing import Any, Callable, Tuple

import jax
import jax.numpy as jnp
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

__all__ = ["transformer_tp_rules", "shard_transformer_params",
           "make_tp_train_step", "make_tp_generate",
           "constrain_decode_cache"]

# NOTE on hand-written (shard_map) megatron regions: no explicit
# Megatron f/g conjugate operators (arXiv:1909.08053 §3) are needed
# here.  Under shard_map's varying-manual-axes tracking, a raw
# ``lax.psum(partial, model_axis)`` at a region's exit transposes to the
# identity broadcast, and the implicit invariant->varying cast at the
# region's entry transposes to the cotangent ``psum`` — exactly the
# f/g pair, inserted automatically.  Hand-rolling them double-counts:
# an extra entry-psum scales every upstream gradient by the TP width
# per pipeline stage (caught by tests/test_pp_tp.py's oracle check
# during development).  Write the region with plain ``lax.psum`` and
# let the transpose rules do the rest.


def transformer_tp_rules(path: tuple, leaf, model_axis: str) -> P:
    """PartitionSpec for one TransformerLM parameter.

    Path keys follow flax's module naming: ``_Attention`` holds two
    DenseGeneral kernels — QKV ``(d_model, 3, H, Dh)`` and
    out-projection ``(H, Dh, d_model)``, both with an explicit head
    axis; ``_Block`` additionally holds the MLP Dense pair
    (``Dense_0`` up, ``Dense_1`` down) at its own level.
    """
    names = [getattr(k, "key", str(k)) for k in path]
    if len(names) < 2:
        return P()
    if any(n.startswith("_Attention") for n in names):
        # GQA projections carry their own names; the head axis is dim 1
        # of q_proj (d, H, Dh) and dim 2 of kv_proj (d, 2, Hkv, Dh).
        if names[-2] == "q_proj":
            return P(None, model_axis, None)
        if names[-2] == "kv_proj":
            return P(None, None, model_axis, None)
        # Head-axis sharding on both attention kernels: QKV outputs and
        # out-projection inputs split per head, so Q/K/V activations,
        # the attention math, and the contraction stay head-local — the
        # partitioner places exactly one psum (out-projection) and never
        # reshards activations inside the block.
        if leaf.ndim == 4:  # QKV (d_model, 3, H, Dh)
            return P(None, None, model_axis, None)
        if leaf.ndim == 3:  # out-projection (H, Dh, d_model)
            return P(model_axis, None, None)
        return P()
    if leaf.ndim != 2:
        return P()  # biases, LayerNorm scales: replicated
    dense = names[-2]  # the Dense module owning this kernel
    if any(n.startswith("_Block") for n in names):
        # The block's own Dense pair is the MLP: up = columns, down = rows.
        if dense == "Dense_0":
            return P(None, model_axis)
        if dense == "Dense_1":
            return P(model_axis, None)
    # Embeddings, the final vocab head, anything unrecognized: replicated
    # (always correct; sharding them is a later perf choice).
    return P()


def _divisible_or_replicated(spec: P, leaf, mesh: Mesh, model_axis: str) -> P:
    """Fall back to replicated when the sharded dim does not divide by
    the axis size (e.g. MQA's kv_proj with Hkv=1 on a 4-way model axis):
    replication is always correct, and a crash would make an otherwise
    valid model configuration unusable under TP."""
    n = mesh.shape[model_axis]
    for d, name in enumerate(spec):
        if name == model_axis and leaf.shape[d] % n:
            return P()
    return spec


def shard_transformer_params(params: Any, mesh: Mesh,
                             model_axis: str = "model") -> Any:
    """Device-put a TransformerLM param tree with megatron-style specs."""
    def place(path, leaf):
        spec = transformer_tp_rules(path, leaf, model_axis)
        spec = _divisible_or_replicated(spec, leaf, mesh, model_axis)
        return jax.device_put(leaf, NamedSharding(mesh, spec))

    return jax.tree_util.tree_map_with_path(place, params)


def make_tp_train_step(
    mesh: Mesh,
    model: Any,
    tx: Any,
    *,
    data_axis: str = "data",
    model_axis: str = "model",
    moe_aux_coef: float = 0.01,
) -> Callable[..., Tuple[Any, Any, jax.Array]]:
    """Jitted DP x TP step: batch over ``data_axis``, weights over
    ``model_axis``, all collectives inserted by the XLA partitioner.

    ``step(params, opt_state, x_tok, y_tok) -> (params, opt_state,
    loss)`` with ``x_tok``/``y_tok`` of shape (B, T) int32 (B divisible
    by the data-axis size).  Params may come from
    :func:`shard_transformer_params`; the step re-constrains them every
    call so the layout survives the optimizer update.

    An MoE model's sown ``moe_stats/load_balance_loss`` joins the
    objective scaled by ``moe_aux_coef`` (Switch default 0.01); dense
    models are unaffected.
    """

    if hasattr(model, "require_uniform"):
        # the sharding rules know the plain attention block's kernels only
        model.require_uniform("tensor parallelism")
    from distributed_learning_tpu.models.moe import (
        apply_collecting_moe_aux,
    )
    from distributed_learning_tpu.training.fsdp import (
        reject_dropout_model,
    )

    reject_dropout_model(model)
    import optax

    def constrain_params(params):
        def place(path, leaf):
            spec = _divisible_or_replicated(
                transformer_tp_rules(path, leaf, model_axis),
                leaf, mesh, model_axis,
            )
            return jax.lax.with_sharding_constraint(
                leaf, NamedSharding(mesh, spec)
            )

        return jax.tree_util.tree_map_with_path(place, params)

    def constrain_opt(opt_state, params):
        # Optimizer moments are param-shaped but live under optax's own
        # tree structure, so the path rules don't apply directly.  Match
        # by shape against the params' sharded kernels: Adam's mu/nu for
        # a column-split QKV kernel must be column-split too, or each
        # device replicates moments for weights it doesn't own — the
        # memory TP exists to save.  A shape carried by params with
        # DIFFERENT specs (e.g. a replicated (32, 32) embedding next to
        # a (32, 32) out-projection) is ambiguous: fall back to
        # replicated for it rather than mis-shard some moments.
        shape_spec: dict = {}
        def record(path, leaf):
            spec = _divisible_or_replicated(
                transformer_tp_rules(path, leaf, model_axis),
                leaf, mesh, model_axis,
            )
            prev = shape_spec.get(leaf.shape)
            if prev is not None and prev != spec:
                shape_spec[leaf.shape] = P()  # collision: stay safe
            else:
                shape_spec[leaf.shape] = spec
            return leaf
        jax.tree_util.tree_map_with_path(record, params)

        def place(leaf):
            spec = shape_spec.get(getattr(leaf, "shape", None), P())
            return jax.lax.with_sharding_constraint(
                leaf, NamedSharding(mesh, spec)
            )

        return jax.tree.map(place, opt_state)

    data_sharding = NamedSharding(mesh, P(data_axis, None))

    @jax.jit
    def step(params, opt_state, x_tok, y_tok):
        params = constrain_params(params)
        opt_state = constrain_opt(opt_state, params)
        x = jax.lax.with_sharding_constraint(x_tok, data_sharding)
        y = jax.lax.with_sharding_constraint(y_tok, data_sharding)

        def loss_fn(p):
            logits, aux = apply_collecting_moe_aux(model, p, x)
            loss = optax.softmax_cross_entropy_with_integer_labels(
                logits, y
            ).mean()
            if aux is not None:
                loss = loss + moe_aux_coef * aux
            return loss

        loss, grads = jax.value_and_grad(loss_fn)(params)
        updates, opt_state = tx.update(grads, opt_state, params)
        params = optax.apply_updates(params, updates)
        return constrain_params(params), constrain_opt(opt_state, params), loss

    # Call-level span + counter only: the wrapper delegates .lower() to
    # the jit object, so the compiled program (and its pinned HLO
    # collective inventory — tools/graftlint --audit) is untouched.
    from distributed_learning_tpu.obs import instrument_step

    return instrument_step(step, "tp.train_step")


def constrain_decode_cache(state: Any, mesh: Mesh, *,
                           data_axis: str = "data",
                           model_axis: str = "model") -> Any:
    """Pin the KV cache to the head split: ``key``/``value`` are
    (B, L, Hkv, Dh) — batch over data, heads over model (replicated
    when Hkv doesn't divide, mirroring ``_divisible_or_replicated``);
    the index/pos counters replicate.  Without the constraint the
    decode scan carry is at the partitioner's mercy and a single
    all-gather choice would replicate the cache — the memory TP decode
    exists to shard.  Module-level so tests can pin the cache leaves'
    sharding directly (tests/test_tp_decode.py) instead of grepping
    compiled HLO."""
    n_model = mesh.shape[model_axis]
    n_data = mesh.shape[data_axis]

    def place(path, leaf):
        name = getattr(path[-1], "key", None)
        if name in ("key", "value") and leaf.ndim == 4:
            heads_ok = leaf.shape[2] % n_model == 0
            batch_ok = leaf.shape[0] % n_data == 0
            spec = P(
                data_axis if batch_ok else None,
                None,
                model_axis if heads_ok else None,
                None,
            )
        else:
            spec = P()
        return jax.lax.with_sharding_constraint(
            leaf, NamedSharding(mesh, spec)
        )

    return jax.tree_util.tree_map_with_path(place, state)


@functools.lru_cache(maxsize=32)
def _tp_generate_runner(dec, steps: int, temperature: float,
                        top_k, top_p, mesh: Mesh,
                        data_axis: str, model_axis: str):
    """Jitted tensor-parallel prefill+scan decode program, cached like
    ``models/transformer.py::_generate_runner`` (flax modules and Mesh
    are both hashable)."""
    from distributed_learning_tpu.models.transformer import sample_fn

    pick = sample_fn(temperature, top_k, top_p)
    n_data = mesh.shape[data_axis]

    def constrain_cache(state):
        # The per-step cache pin (see constrain_decode_cache's
        # docstring for why the carry must be constrained every step).
        return constrain_decode_cache(
            state, mesh, data_axis=data_axis, model_axis=model_axis
        )

    def constrain_params(params):
        def place(path, leaf):
            spec = _divisible_or_replicated(
                transformer_tp_rules(path, leaf, model_axis),
                leaf, mesh, model_axis,
            )
            return jax.lax.with_sharding_constraint(
                leaf, NamedSharding(mesh, spec)
            )

        return jax.tree_util.tree_map_with_path(place, params)

    @jax.jit
    def _run(params, prompt, key):
        params = constrain_params(params)
        if prompt.shape[0] % n_data == 0:
            prompt = jax.lax.with_sharding_constraint(
                prompt, NamedSharding(mesh, P(data_axis, None))
            )
        logits, state = dec.apply(
            {"params": params}, prompt, mutable=["cache"]
        )
        state = constrain_cache(state)
        key0 = key if key is not None else jax.random.key(0)
        k_first, k_scan = jax.random.split(key0)
        tok = pick(logits[:, -1], k_first, prompt.dtype)

        def step(carry, k_t):
            cache, tok = carry
            logits, st = dec.apply(
                {"params": params, "cache": cache["cache"]},
                tok[:, None], mutable=["cache"],
            )
            st = constrain_cache(st)
            nxt = pick(logits[:, -1], k_t, tok.dtype)
            return (st, nxt), tok

        keys = jax.random.split(k_scan, steps)
        _, toks = jax.lax.scan(step, (state, tok), keys)
        return toks.T

    return _run


def make_tp_generate(
    mesh: Mesh,
    model: Any,
    *,
    data_axis: str = "data",
    model_axis: str = "model",
) -> Callable[..., jax.Array]:
    """Tensor-parallel autoregressive generation on a (data, model)
    mesh: the KV cache and Q/KV projections shard over HEADS on the
    model axis (the same megatron split training uses, so a trained
    sharded checkpoint serves without resharding), the cache's batch
    dim over data.  GQA's Hkv-head cache shards whenever Hkv divides
    the axis; otherwise it falls back to replicated-KV with sharded
    query heads — still the memory win over MHA, never a crash
    (``_divisible_or_replicated``'s contract).

    Returns ``gen(params, prompt, steps, *, key=None, temperature=0.0,
    top_k=None, top_p=None) -> (B, steps) tokens``, exact-match to the
    single-device :func:`~distributed_learning_tpu.models.transformer.
    generate` (pinned by tests/test_tp_decode.py).  The reference has
    no serving path at all (SURVEY.md §2 — its models stop at training
    notebooks); this is the framework's decode story scaled past one
    chip.
    """
    from distributed_learning_tpu.models.transformer import (
        validate_sampling,
    )

    dec = model.clone(decode=True)

    def gen(params, prompt, steps, *, key=None, temperature=0.0,
            top_k=None, top_p=None):
        validate_sampling(model, prompt.shape[1], int(steps), key,
                          float(temperature), top_k, top_p)
        run = _tp_generate_runner(
            dec, int(steps), float(temperature),
            None if top_k is None else int(top_k),
            None if top_p is None else float(top_p),
            mesh, data_axis, model_axis,
        )
        with mesh:
            return run(params, prompt, key)

    from distributed_learning_tpu.obs import instrument_step

    return instrument_step(gen, "tp.generate")
