"""North-star head-to-head: reference asyncio backend vs ConsensusEngine.

BASELINE.md's north star asks >= 5x wall-clock over the reference on the
same decentralized task.  The reference's TCP path has never run (its
master crashes on the first round request — ``master.py:140``), but its
*asyncio* backend works and runs right here on CPU, so this benchmark
turns the argument into a number: the Titanic consensus-GD recipe
(``notebooks/Titanic Consensus GD test.ipynb`` cell 14 — local
subgradient step with the ``alpha*(it+1)^-0.5`` schedule, then gossip to
convergence after every step) on the SAME topology, shards, step
schedule, and convergence eps, driven through

* the reference: ``/root/reference/utils/consensus_asyncio.py`` —
  ConsensusNetwork/ConsensusAgent over asyncio queues, one coroutine per
  agent (imported and RUN as the baseline, not copied); the driver loop
  below is a fresh implementation of the notebook's ``learning_instance``
  (cell 14) against that API;
* this framework: one jitted program — vmapped local steps +
  ``ConsensusEngine.mix_until`` (eps-stopped Perron gossip) inside a
  ``lax.fori_loop``, on the 8-virtual-device CPU mesh settings the tests
  use (no TPU needed: the point is same-hardware wall-clock).

Both sides use the uniform-eps Perron mixing the reference's master
distributes (eps = 0.95/max_deg, ``consensus_asyncio.py:78-86``) and the
notebook's convergence_eps=1e-4 default.  Prints one JSON line and (with
--publish) records absolute times for both sides in BASELINE.json.
"""

from __future__ import annotations

import argparse
import asyncio
import json
import sys
import time

import numpy as np

ALPHA, TAU = 0.1, 1e-4
CONVERGENCE_EPS = 1e-4  # reference ConsensusAgent default
TOPOLOGY = [(0, 1), (1, 2), (2, 3), (3, 4)]  # 5-node path ("grid") graph
N_AGENTS = 5


def _shards():
    from distributed_learning_tpu.data import load_titanic, split_data

    X_tr, y_tr, X_te, y_te = load_titanic()
    shards = split_data(X_tr, y_tr, N_AGENTS)
    m = min(len(s[0]) for s in shards.values())
    Xs = np.stack([np.asarray(shards[i][0][:m]) for i in range(N_AGENTS)])
    ys = np.stack(
        [np.asarray(shards[i][1][:m], np.float32) for i in range(N_AGENTS)]
    )
    return Xs, ys, np.asarray(X_te), np.asarray(y_te, np.float32)


def _np_grad(w, X, y):
    """Numpy gradient of the ridge logistic loss (labels {-1,+1}) — the
    notebook's inline manual gradient, matching models/logreg.loss_fn."""
    margins = y * (X @ w)
    s = 1.0 / (1.0 + np.exp(margins))  # sigmoid(-margins)
    return TAU * w - (X.T @ (y * s)) / len(y)


def run_reference(Xs, ys, iters):
    """Drive the reference asyncio backend through the notebook recipe,
    in a SUBPROCESS: the reference tree is untrusted public content, so
    its module-level code never runs in the measuring process — and its
    asyncio event loop cannot leak state into ours.  Wall-clock is
    timed inside the child around the run itself (not the interpreter
    spawn), keeping the comparison fair."""
    import os
    import subprocess
    import tempfile

    with tempfile.TemporaryDirectory() as td:
        inp, out = os.path.join(td, "in.npz"), os.path.join(td, "out.npz")
        np.savez(inp, Xs=Xs, ys=ys, iters=iters)
        env = dict(os.environ)
        env["PYTHONPATH"] = os.path.dirname(os.path.dirname(
            os.path.abspath(__file__)
        ))
        try:
            proc = subprocess.run(
                [sys.executable, "-c",
                 "from benchmarks.bench_northstar import _reference_child; "
                 f"_reference_child({inp!r}, {out!r})"],
                env=env, capture_output=True, text=True,
                timeout=900,  # the reference's asyncio loop can stall;
                              # a hang must surface as an error record
            )
        except subprocess.TimeoutExpired as e:
            raise RuntimeError(
                f"reference subprocess hung past 900s: "
                f"{(e.stderr or '')[-500:]}"
            ) from e
        if proc.returncode:
            raise RuntimeError(
                f"reference subprocess failed: {proc.stderr[-2000:]}"
            )
        rec = np.load(out)
        return rec["ws"], float(rec["elapsed"])


def _reference_child(in_path: str, out_path: str) -> None:
    """Subprocess body for :func:`run_reference` (child-only import of
    the reference package)."""
    rec = np.load(in_path)
    Xs, ys, iters = rec["Xs"], rec["ys"], int(rec["iters"])
    ws, elapsed = _run_reference_inproc(Xs, ys, iters)
    np.savez(out_path, ws=ws, elapsed=elapsed)


def _run_reference_inproc(Xs, ys, iters):
    sys.path.insert(0, "/root/reference")
    from utils.consensus_asyncio import ConsensusAgent, ConsensusNetwork

    dim = Xs.shape[-1]

    async def learning_instance(agent, X, y):
        w = np.zeros(dim)
        for it in range(iters):
            w = w - ALPHA * (it + 1.0) ** -0.5 * _np_grad(w, X, y)
            w = await agent.run_round(w, len(y))
        return w

    async def main():
        shutdown_q = asyncio.Queue()
        net = ConsensusNetwork(TOPOLOGY, shutdown_q)
        agents = [
            ConsensusAgent(t, convergence_eps=CONVERGENCE_EPS)
            for t in range(N_AGENTS)
        ]
        for a in agents:
            net.register_agent(a)
        serve = asyncio.create_task(net.serve())
        ws = await asyncio.gather(
            *[
                learning_instance(a, Xs[i], ys[i])
                for i, a in enumerate(agents)
            ]
        )
        await shutdown_q.put(True)
        await serve
        return np.stack(ws)

    t0 = time.perf_counter()
    ws = asyncio.run(main())
    return ws, time.perf_counter() - t0


def run_engine(Xs, ys, iters):
    """The same recipe as one jitted SPMD program."""
    import jax
    import jax.numpy as jnp

    from distributed_learning_tpu.models.logreg import loss_fn
    from distributed_learning_tpu.parallel import Topology
    from distributed_learning_tpu.parallel.consensus import ConsensusEngine

    engine = ConsensusEngine(Topology.from_edges(TOPOLOGY).perron())
    Xs_d, ys_d = jnp.asarray(Xs), jnp.asarray(ys)

    def local_step(w, X, y, lr):
        return w - lr * jax.grad(loss_fn)(w, X, y, TAU)

    vstep = jax.vmap(local_step, in_axes=(0, 0, 0, None))

    @jax.jit
    def run(w0):
        def body(it, w):
            lr = ALPHA * (it + 1.0) ** -0.5
            w = vstep(w, Xs_d, ys_d, lr)
            w, _, _ = engine.mix_until(
                w, eps=CONVERGENCE_EPS, max_rounds=300
            )
            return w

        return jax.lax.fori_loop(0, iters, body, w0)

    w0 = jnp.zeros(Xs.shape[:1] + Xs.shape[2:])
    t0 = time.perf_counter()
    w_warm = run(w0).block_until_ready()  # includes compile
    compile_and_run = time.perf_counter() - t0
    t0 = time.perf_counter()
    w = run(w0).block_until_ready()
    steady = time.perf_counter() - t0
    return np.asarray(w), steady, compile_and_run


def _accuracy(w, X, y):
    pred = np.where(1.0 / (1.0 + np.exp(-(X @ w))) >= 0.5, 1.0, -1.0)
    return float((pred == y).mean())


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--iters", type=int, default=500)
    ap.add_argument("--publish", action="store_true",
                    help="record the result in BASELINE.json")
    args = ap.parse_args()

    Xs, ys, X_te, y_te = _shards()

    w_eng, t_eng, t_eng_cold = run_engine(Xs, ys, args.iters)
    w_ref, t_ref = run_reference(Xs, ys, args.iters)

    acc_ref = _accuracy(w_ref.mean(0), X_te, y_te)
    acc_eng = _accuracy(w_eng.mean(0), X_te, y_te)
    spread_ref = float(np.abs(w_ref - w_ref.mean(0)).max())
    spread_eng = float(np.abs(w_eng - w_eng.mean(0)).max())

    rec = {
        "metric": "northstar_titanic_gd_wallclock_ratio",
        "value": round(t_ref / t_eng, 2),
        "unit": "x (reference asyncio / engine steady-state)",
        "vs_baseline": round(t_ref / t_eng, 2),
        "iters": args.iters,
        "topology": "path-5",
        "convergence_eps": CONVERGENCE_EPS,
        "reference_s": round(t_ref, 3),
        "engine_steady_s": round(t_eng, 3),
        "engine_with_compile_s": round(t_eng_cold, 3),
        "test_acc_reference": round(acc_ref, 4),
        "test_acc_engine": round(acc_eng, 4),
        "agent_spread_reference": spread_ref,
        "agent_spread_engine": spread_eng,
        "platform": "cpu-8dev",
    }
    print(json.dumps(rec))

    if args.publish:
        import collections

        with open("BASELINE.json") as f:
            d = json.load(f, object_pairs_hook=collections.OrderedDict)
        d.setdefault("published", {})[
            "northstar_titanic_asyncio_headtohead"
        ] = rec
        with open("BASELINE.json", "w") as f:
            json.dump(d, f, indent=1)
            f.write("\n")


if __name__ == "__main__":
    main()
