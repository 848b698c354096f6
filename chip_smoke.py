"""Chip smoke: the quickest proof that the system still starts on a TPU.

One process drives the main path once through the entry points a user
calls, at the full width of the flagship model, and checks what comes
out by the repo's own means:

* ``train``     — WRN-28-10 (depth 28, widen 10, dropout 0.3), 4 agents,
  ring + Metropolis weights, dense layout on one chip, built as
  ``cli.main`` builds it (``config_from_args`` → ``ExperimentConfig.build``
  → ``initialize_nodes`` → ``train_epoch`` / ``train_epochs``) on the
  synthetic CIFAR stand-in; then a checkpoint round trip into a freshly
  built trainer.
* ``consensus`` — the README's "pure consensus" use: ``mix_until`` on
  8 agents × 4,194,304 f32 to eps 1e-4.
* ``flash``     — the Pallas flash-attention kernels, forward and grad,
  compiled (``tpu_custom_call`` in the text of what ran) and checked
  against ``attention_reference``; then two training steps of
  ``TransformerLM(attn_impl="flash")`` through ``GossipTrainer``.

``--multichip`` (four chips) runs instead the sharded SPMD layout — one
agent per device, ``ppermute`` gossip — and the dense layout it is
compared with, and no other phase.

There is no CPU fallback: the script refuses to go on unless
``jax.devices()[0].platform == "tpu"`` and exits non-zero the moment a
phase fails.  Its last stdout line is one JSON object,
``{"ok": true, "device": {"platform": ..., "kind": ..., "count": ...}}``;
everything else goes on earlier lines.  Run it through the chip tool:
``python chip_smoke.py`` on one chip, ``python chip_smoke.py --multichip``
on four; one process per chip.
"""

from __future__ import annotations

import argparse
import gc
import glob
import importlib.metadata
import json
import os
import shutil
import sys
import time

import jax
import jax.numpy as jnp
import numpy as np

from distributed_learning_tpu.cli import build_parser, config_from_args
from distributed_learning_tpu.utils.compile_cache import enable_compile_cache

HERE = os.path.dirname(os.path.abspath(__file__))
#: The script's output directory (git-ignored): the checkpoint of the
#: ``train`` phase lives under it for the length of the round trip.
OUT_DIR = os.path.join(HERE, ".chip_smoke_out")
#: What the chip tool brings back: the per-phase report, as JSON.
REPORT_PATH = os.path.join(HERE, "chiprun_out", "chip_smoke_report.json")

NODES = 4
#: bf16 tolerance: max |a - b| relative to max(1, max |b|).
BF16_TOL = 2e-2


def say(msg: str) -> None:
    print(msg, flush=True)


def require_tpu(min_devices: int = 1):
    """The device check: no accelerator, no run (exit code 2, nothing
    on stdout)."""
    dev = jax.devices()[0]
    if dev.platform != "tpu" or len(jax.devices()) < min_devices:
        raise SystemExit(
            f"chip_smoke.py needs {min_devices} TPU device(s); JAX found "
            f"{len(jax.devices())} x {dev.platform!r}"
        )
    return dev


class CompileMeter:
    """Seconds XLA spent compiling (or loading from the persistent
    cache) and the cache's hit/miss counts, from JAX's own monitoring
    events — the programs under test are not touched."""

    def __init__(self):
        self.compile_s = 0.0
        self.hits = 0
        self.misses = 0
        jax.monitoring.register_event_duration_secs_listener(self._duration)
        jax.monitoring.register_event_listener(self._event)

    def _duration(self, event: str, secs: float, **_kw) -> None:
        if event == "/jax/core/compile/backend_compile_duration":
            self.compile_s += secs

    def _event(self, event: str, **_kw) -> None:
        if event == "/jax/compilation_cache/cache_hits":
            self.hits += 1
        elif event == "/jax/compilation_cache/cache_misses":
            self.misses += 1

    def snapshot(self):
        return self.compile_s, self.hits, self.misses


def run_phase(meter: CompileMeter, device, name: str, fn, *args, **kwargs):
    """Run one phase, print its seconds split into compile and the rest,
    and return its report.  A failing phase raises: nothing carries on."""
    c0, h0, m0 = meter.snapshot()
    t0 = time.perf_counter()
    report = fn(device, *args, **kwargs)
    wall = time.perf_counter() - t0
    c1, h1, m1 = meter.snapshot()
    stats = device.memory_stats() or {}
    report.update(
        wall_s=round(wall, 2),
        compile_s=round(c1 - c0, 2),
        run_s=round(wall - (c1 - c0), 2),
        cache_hits=h1 - h0,
        cache_misses=m1 - m0,
        peak_bytes_in_use=stats.get("peak_bytes_in_use"),
    )
    say(f"phase {name}: " + json.dumps(report))
    return report


def check(ok, msg) -> None:
    """A failed check ends the run (``assert`` would vanish under -O)."""
    if not ok:
        raise AssertionError(msg)


def _close(a, b, tol: float = BF16_TOL, *, relative: bool = True) -> float:
    """Max |a - b|, over max(1, max |b|) when ``relative``; raises when
    above ``tol``."""
    a = np.asarray(a, np.float32)
    b = np.asarray(b, np.float32)
    err = float(np.max(np.abs(a - b)))
    if relative:
        err /= max(1.0, float(np.max(np.abs(b))))
    check(err <= tol, f"mismatch {err:.3e} > {tol:.1e}")
    return err


# ---------------------------------------------------------------------- #
# train: the gossip trainer through its normal entry points              #
# ---------------------------------------------------------------------- #
def trainer_argv(*, depth: int, widen: int, batch: int, steps: int,
                 seed: int) -> list:
    """The command line of the main path (README "CLI")."""
    return [
        "--net_type", "wide-resnet", "--depth", str(depth),
        "--widen_factor", str(widen), "--dropout", "0.3",
        "--dataset", "cifar10", "--nodes", str(NODES),
        "--topology", "ring", "--weight-mode", "metropolis",
        "--batch-size", str(batch), "--n-train", str(NODES * batch * steps),
        "--epochs", "3", "--superstep", "2", "--seed", str(seed),
    ]


def build_trainer(argv: list, mesh=None):
    """What ``cli.main`` does before its epoch loop."""
    cfg = config_from_args(build_parser().parse_args(argv))
    master = cfg.build(mesh=mesh)
    master.initialize_nodes()
    return master


def _check_payloads(payloads: list) -> list:
    losses = [float(np.mean(p["train_loss"])) for p in payloads]
    devs = [p["deviation"] for p in payloads]
    check(all(np.isfinite(l) for l in losses), f"loss not finite: {losses}")
    check(
        all(d is not None and np.isfinite(d) and d >= 0 for d in devs),
        f"consensus residual not finite and >= 0: {devs}",
    )
    return losses


def train(device, out_dir: str, *, depth: int = 28, widen: int = 10,
          batch: int = 256, steps: int = 4, seed: int = 0) -> dict:
    """One ``train_epoch``, one ``train_epochs(2)`` chunk, a checkpoint
    round trip.  ``steps`` per epoch is the cut: the synthetic stand-in
    is trimmed to ``NODES * batch * steps`` samples (``--n-train``)."""
    os.makedirs(out_dir, exist_ok=True)
    ckpt = os.path.join(out_dir, "ckpt")
    argv = trainer_argv(depth=depth, widen=widen, batch=batch, steps=steps,
                        seed=seed) + ["--checkpoint-dir", ckpt]
    say("train: python -m distributed_learning_tpu " + " ".join(argv))
    say(f"train: cut — n_train {NODES * batch * steps} (an epoch is "
        f"{steps} steps of {NODES} agents x batch {batch}); depth and "
        "width are not cut")
    master = build_trainer(argv)
    check(master.epoch_len == steps, (master.epoch_len, steps))
    payloads = [master.train_epoch()] + master.train_epochs(2)
    losses = _check_payloads(payloads)
    check(losses[-1] < losses[0], f"loss did not fall: {losses}")
    check(master._donate_active == (device.platform != "cpu"),
          "the epoch state is not donated")
    for leaf in jax.tree.leaves(master.state):
        check(leaf.sharding.device_set == {device}, leaf.sharding)

    t0 = time.perf_counter()
    try:
        master.save_checkpoint(ckpt)
        fresh = build_trainer(argv)
        fresh.restore_checkpoint(ckpt)
    finally:
        shutil.rmtree(ckpt, ignore_errors=True)
    ckpt_s = time.perf_counter() - t0
    check(fresh._epochs_done == master._epochs_done == 3, "epoch counter")
    saved, back = master.state[0], fresh.state[0]
    n_params = 0
    for a, b in zip(jax.tree.leaves(saved), jax.tree.leaves(back), strict=True):
        check(np.array_equal(np.asarray(a), np.asarray(b)),
              "restored parameters differ from the saved ones")
        n_params += a.size
    return {
        "model": f"wrn-{depth}-{widen}", "agents": NODES, "batch": batch,
        "steps_per_epoch": steps, "params_per_agent": n_params // NODES,
        "losses": [round(l, 4) for l in losses],
        "residuals": [float(p["deviation"]) for p in payloads],
        "mix_rounds": [int(p["mix_rounds"]) for p in payloads],
        "donated": bool(master._donate_active),
        "checkpoint_roundtrip_s": round(ckpt_s, 2),
    }


# ---------------------------------------------------------------------- #
# consensus: mix_until on a large f32 state                              #
# ---------------------------------------------------------------------- #
def consensus(device, *, n_agents: int = 8, dim: int = 4_194_304,
              eps: float = 1e-4, max_rounds: int = 10_000,
              seed: int = 0) -> dict:
    from distributed_learning_tpu.parallel.consensus import ConsensusEngine
    from distributed_learning_tpu.parallel.topology import Topology

    engine = ConsensusEngine(Topology.ring(n_agents).metropolis_weights())
    x = jax.random.normal(jax.random.key(seed), (n_agents, dim), jnp.float32)
    mean0 = np.asarray(jnp.mean(x, axis=0))
    out, rounds, residual = engine.mix_until(
        x, eps=eps, max_rounds=max_rounds
    )
    rounds, residual = int(rounds), float(residual)
    check(rounds < max_rounds, f"while_loop ran to its cap ({rounds})")
    check(np.isfinite(residual) and residual <= eps, (residual, eps))
    drift = float(np.max(np.abs(np.asarray(jnp.mean(out, axis=0)) - mean0)))
    check(drift <= 1e-5, f"mean moved by {drift:.3e}")
    check(out.sharding.device_set == {device}, out.sharding)
    return {"agents": n_agents, "dim": dim, "eps": eps, "rounds": rounds,
            "residual": residual, "mean_drift": drift}


# ---------------------------------------------------------------------- #
# flash: the Pallas kernels, then an LM trained through them             #
# ---------------------------------------------------------------------- #
def _qkv(seed: int, B: int, T: int, H: int, D: int):
    keys = jax.random.split(jax.random.key(seed), 4)
    shape = (B, T, H, D)
    q, k, v, w = (jax.random.normal(kk, shape, jnp.float32) for kk in keys)
    return tuple(x.astype(jnp.bfloat16) for x in (q, k, v)) + (w,)


def _run_compiled(fn, *args):
    """AOT-compile ``fn``, require the Pallas kernel in the text of the
    executable, and run that same executable."""
    compiled = jax.jit(fn).lower(*args).compile()
    check("tpu_custom_call" in compiled.as_text(),
          "no tpu_custom_call: the Pallas kernel is not what ran")
    return jax.block_until_ready(compiled(*args))


def flash(device, *, seed: int = 0, lm_T: int = 4096) -> dict:
    from distributed_learning_tpu.models import TransformerLM
    from distributed_learning_tpu.ops.flash_attention import flash_attention
    from distributed_learning_tpu.ops.ring_attention import (
        attention_reference,
    )
    from distributed_learning_tpu.parallel.topology import Topology
    from distributed_learning_tpu.training.trainer import GossipTrainer

    def loss(attn):
        return lambda q, k, v, w: jnp.sum(attn(q, k, v).astype(jnp.float32) * w)

    flash_grad = jax.grad(loss(flash_attention), argnums=(0, 1, 2))

    # Full size: compiled kernels, finite values of the expected shape.
    q, k, v, w = _qkv(seed, 1, 8192, 8, 64)
    out = _run_compiled(flash_attention, q, k, v)
    grads = _run_compiled(flash_grad, q, k, v, w)
    check(out.dtype == jnp.bfloat16, out.dtype)
    for x in (out, *grads):
        check(x.shape == q.shape, x.shape)
        check(bool(jnp.all(jnp.isfinite(x.astype(jnp.float32)))),
              "flash attention gave a non-finite value")

    # Small input: the same kernels against the plain f32 reference.
    q, k, v, w = _qkv(seed + 1, 1, 1024, 8, 64)
    f32 = lambda x: x.astype(jnp.float32)
    ref_out = attention_reference(f32(q), f32(k), f32(v))
    ref_grads = jax.grad(loss(attention_reference), argnums=(0, 1, 2))(
        f32(q), f32(k), f32(v), w
    )
    fwd_err = _close(_run_compiled(flash_attention, q, k, v), ref_out)
    bwd_err = max(
        _close(g, r)
        for g, r in zip(_run_compiled(flash_grad, q, k, v, w), ref_grads)
    )

    # A small LM (vocab 8192, 8 heads x 128, bf16, 2 layers); two steps
    # of one epoch.
    lm = dict(vocab_size=8192, num_layers=2, num_heads=8, head_dim=128)
    say(f"flash: LM through GossipTrainer — T {lm_T}; "
        f"widths: {lm}")
    rng = np.random.default_rng(seed)
    batch, steps = 2, 2
    tokens = rng.integers(
        0, lm["vocab_size"], size=(NODES, batch * steps, lm_T + 1)
    ).astype(np.int32)
    trainer = GossipTrainer(
        node_names=list(range(NODES)),
        model=TransformerLM(max_len=lm_T, attn_impl="flash",
                            dtype=jnp.bfloat16, **lm),
        optimizer="adam",
        learning_rate=3e-4,
        error="cross_entropy",
        weights=Topology.ring(NODES),
        train_data={a: (tokens[a, :, :-1], tokens[a, :, 1:])
                    for a in range(NODES)},
        batch_size=batch,
        dropout=False,
        seed=seed,
    )
    trainer.initialize_nodes()
    check(trainer.epoch_len == steps, trainer.epoch_len)
    lowered = trainer._jit_epoch.lower(
        trainer.state, trainer._Xs, trainer._ys, trainer._epoch_indices(0)
    )
    check("tpu_custom_call" in lowered.as_text(),
          "the LM's epoch program holds no Pallas kernel")
    losses = _check_payloads([trainer.train_epoch()])
    return {"kernel_shape": [1, 8192, 8, 64], "fwd_err_T1024": fwd_err,
            "bwd_err_T1024": bwd_err, "tolerance": BF16_TOL,
            "lm": {**lm, "T": lm_T, "agents": NODES, "batch": batch,
                   "steps": steps},
            "lm_loss": round(losses[0], 4)}


# ---------------------------------------------------------------------- #
# --multichip: one agent per device, ppermute gossip, against dense      #
# ---------------------------------------------------------------------- #
def _four_shards(tree, devices: list) -> None:
    for leaf in jax.tree.leaves(tree):
        held = {s.device for s in leaf.addressable_shards}
        check(
            len(leaf.addressable_shards) == NODES and held == set(devices),
            f"leaf {leaf.shape} sits on {held}",
        )


def _epoch_losses(argv: list, mesh):
    """One epoch of a freshly built trainer; the trainer (and what it
    holds on the devices) is dropped on return."""
    master = build_trainer(argv, mesh=mesh)
    payload = master.train_epoch()
    _check_payloads([payload])
    if mesh is not None:
        params, batch_stats, opt_state, _rng = master.state
        _four_shards(
            (params, batch_stats, opt_state, master._Xs, master._ys),
            list(mesh.devices.flat),
        )
    return np.asarray(payload["train_loss"]), float(payload["deviation"])


def multichip(device, *, depth: int = 28, widen: int = 10,
              batch: int = 256, steps: int = 4, dim: int = 4_194_304,
              eps: float = 1e-4, seed: int = 0) -> dict:
    from distributed_learning_tpu.parallel.consensus import (
        ConsensusEngine,
        make_agent_mesh,
    )
    from distributed_learning_tpu.parallel.topology import Topology

    mesh = make_agent_mesh(NODES)
    devices = list(mesh.devices.flat)
    check(devices[0] == device and len(set(devices)) == NODES, devices)

    # (a) the consensus engine, sharded against dense, on one f32 input.
    W = Topology.ring(NODES).metropolis_weights()
    dense, sharded = ConsensusEngine(W), ConsensusEngine(W, mesh=mesh)
    x = jax.random.normal(jax.random.key(seed), (NODES, dim), jnp.float32)
    xs = sharded.shard(x)
    _four_shards(xs, devices)
    hlo = jax.jit(lambda s: sharded.mix(s, times=1)).lower(xs).compile()
    check("collective-permute" in hlo.as_text(), "mix has no ppermute")
    mixed = sharded.mix(xs, times=3)
    _four_shards(mixed, devices)
    mix_err = _close(mixed, dense.mix(x, times=3), 1e-5, relative=False)
    out_s, rounds_s, res_s = sharded.mix_until(xs, eps=eps)
    out_d, rounds_d, res_d = dense.mix_until(x, eps=eps)
    _four_shards(out_s, devices)
    check(float(res_s) <= eps and float(res_d) <= eps, (res_s, res_d))
    until_err = _close(out_s, out_d, 1e-5, relative=False)
    del x, xs, mixed, out_s, out_d

    # (b) the trainer of the `train` phase, one epoch, sharded and dense.
    argv = trainer_argv(depth=depth, widen=widen, batch=batch, steps=steps,
                        seed=seed)
    say("multichip: python -m distributed_learning_tpu " + " ".join(argv))
    loss_s, dev_s = _epoch_losses(argv, mesh)
    gc.collect()
    loss_d, dev_d = _epoch_losses(argv, None)
    loss_err = _close(loss_s, loss_d)
    return {
        "mix_err": mix_err, "mix_until_err": until_err,
        "rounds_sharded": int(rounds_s), "rounds_dense": int(rounds_d),
        "loss_sharded": [round(float(l), 4) for l in loss_s],
        "loss_dense": [round(float(l), 4) for l in loss_d],
        "loss_err": loss_err, "loss_tolerance": BF16_TOL,
        "residual_sharded": dev_s, "residual_dense": dev_d,
        "devices": [str(d) for d in devices],
    }


# ---------------------------------------------------------------------- #
def codec_report() -> dict:
    """Which wire-codec path serves, and whether its libraries were built
    by this process (a library found under its keyed name was built on
    this host from this checkout's sources; see ``native/__init__.py``)."""
    from distributed_learning_tpu import native
    from distributed_learning_tpu.native import wire

    names = lambda: {
        os.path.basename(p)
        for p in glob.glob(os.path.join(os.path.dirname(wire.__file__), "_*.so"))
    }
    found = names()
    serving = {"codec": native.native_available(), "wire": wire.available()}
    return {"native": serving, "built_here": sorted(names() - found),
            "found": sorted(found)}


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--multichip", action="store_true",
                    help="four chips: the sharded SPMD layout against "
                         "the dense one, and no other phase")
    ap.add_argument("--seed", type=int, default=0,
                    help="weights and data are made from this seed")
    args = ap.parse_args(argv)

    device = require_tpu(NODES if args.multichip else 1)
    cache_dir = enable_compile_cache()
    versions = ", ".join(
        f"{pkg} {importlib.metadata.version(pkg)}"
        for pkg in ("jax", "jaxlib", "libtpu")
    )
    say(f"device: {len(jax.devices())} x {device.device_kind} "
        f"({device.platform}); {versions}")
    say(f"compile cache: {cache_dir}")
    say("wire codec: " + json.dumps(codec_report()))
    meter = CompileMeter()
    report = {}
    if args.multichip:
        report["multichip"] = run_phase(
            meter, device, "multichip", multichip, seed=args.seed
        )
    else:
        report["train"] = run_phase(
            meter, device, "train", train, OUT_DIR, seed=args.seed
        )
        report["consensus"] = run_phase(
            meter, device, "consensus", consensus, seed=args.seed
        )
        report["flash"] = run_phase(
            meter, device, "flash", flash, seed=args.seed
        )
    result = {
        "ok": True,
        "device": {
            "platform": device.platform,
            "kind": device.device_kind,
            "count": len(jax.devices()),
        },
    }
    os.makedirs(os.path.dirname(REPORT_PATH), exist_ok=True)
    with open(REPORT_PATH, "w", encoding="utf-8") as fh:
        json.dump({**result, "phases": report}, fh, indent=1)
    print(json.dumps(result), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
