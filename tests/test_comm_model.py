"""The TCP backend carries a real model: 3 OS processes gossip actual MLP
parameter pytrees to consensus via ``run_round`` with the bf16 wire on.

This is the reference's ``tcp-consensus-test`` scenario
(``notebooks/tcp-consensus-test/``: master + agents as separate kernels on
localhost) upgraded from basis vectors to whole models — the protocol the
reference documents but stubs out (``agent.py:155-156``).
"""

import os
import socket
import subprocess
import sys
import tempfile
import time

import numpy as np
import pytest

from distributed_learning_tpu.comm.pytree_codec import (
    TreeSpec,
    flat_to_tree,
    tree_to_flat,
)

# ---------------------------------------------------------------------- #
# Codec unit tests                                                       #
# ---------------------------------------------------------------------- #
def test_pytree_codec_roundtrip_mixed_float_dtypes():
    import jax.numpy as jnp

    tree = {
        "dense": {"kernel": jnp.ones((3, 4), jnp.bfloat16),
                  "bias": jnp.arange(4, dtype=jnp.float32)},
        "scale": jnp.float32(2.5),
    }
    flat, spec = tree_to_flat(tree)
    assert flat.dtype == np.float32 and flat.size == spec.total == 17
    back = flat_to_tree(flat, spec)
    import jax

    for a, b in zip(jax.tree.leaves(tree), jax.tree.leaves(back)):
        assert np.asarray(a).dtype == np.asarray(b).dtype
        np.testing.assert_allclose(
            np.asarray(a, dtype=np.float32), np.asarray(b, dtype=np.float32)
        )


def test_pytree_codec_rejects_integer_leaves():
    with pytest.raises(TypeError):
        tree_to_flat({"step": np.int32(3), "w": np.ones(2, np.float32)})


def test_pytree_codec_spec_equality_across_processifiable_builds():
    import jax
    import jax.numpy as jnp

    from distributed_learning_tpu.models import ANNModel

    def build(seed):
        model = ANNModel(hidden_dim=8, output_dim=3)
        return model.init(jax.random.key(seed), jnp.zeros((1, 4)))["params"]

    _, s0 = tree_to_flat(build(0))
    _, s1 = tree_to_flat(build(1))
    assert s0 == s1  # same architecture => same spec on every agent


# ---------------------------------------------------------------------- #
# 3-OS-process model gossip                                              #
# ---------------------------------------------------------------------- #
_MASTER = r"""
import asyncio, sys
import jax
jax.config.update("jax_platforms", "cpu")
from distributed_learning_tpu.comm.master import ConsensusMaster

async def main():
    port = int(sys.argv[1])
    master = ConsensusMaster(
        [("A", "B"), ("B", "C"), ("C", "A")],
        port=port, convergence_eps=1e-3,
    )
    await master.start()
    print("MASTER-UP", flush=True)
    await master._stopped.wait()

asyncio.run(main())
"""

_AGENT = r"""
import asyncio, socket, sys, time
import numpy as np
import jax
jax.config.update("jax_platforms", "cpu")
import jax.numpy as jnp

from distributed_learning_tpu.comm.agent import ConsensusAgent
from distributed_learning_tpu.comm.pytree_codec import flat_to_tree, tree_to_flat
from distributed_learning_tpu.models import ANNModel

token, port, weight, outdir = (
    sys.argv[1], int(sys.argv[2]), float(sys.argv[3]), sys.argv[4]
)

model = ANNModel(hidden_dim=8, output_dim=3)
params = model.init(jax.random.key(ord(token)), jnp.zeros((1, 4)))["params"]
flat, spec = tree_to_flat(params)

deadline = time.monotonic() + 30
while True:  # wait for the master to listen
    try:
        socket.create_connection(("127.0.0.1", port), timeout=1).close()
        break
    except OSError:
        if time.monotonic() > deadline:
            raise
        time.sleep(0.1)

async def main():
    agent = ConsensusAgent(token, "127.0.0.1", port, bf16_wire=True)
    await agent.start()
    out = await agent.run_round(flat, weight=weight)
    mixed = flat_to_tree(out, spec)  # restores the model pytree
    assert jax.tree.structure(mixed) == jax.tree.structure(params)
    np.save(f"{outdir}/{token}.npy", out)
    await agent.close()

asyncio.run(asyncio.wait_for(main(), 120))
print(f"AGENT-DONE {token}", flush=True)
"""


def test_three_processes_gossip_mlp_params_to_weighted_mean():
    import jax
    import jax.numpy as jnp

    from distributed_learning_tpu.models import ANNModel

    with socket.socket() as s:
        s.bind(("127.0.0.1", 0))
        port = s.getsockname()[1]

    repo = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
    env = dict(os.environ)
    env["JAX_PLATFORMS"] = "cpu"
    env.pop("XLA_FLAGS", None)
    # Hermetic children: they import this checkout and nothing else
    # from the parent's PYTHONPATH.
    env["PYTHONPATH"] = repo
    weights = {"A": 1.0, "B": 2.0, "C": 3.0}

    with tempfile.TemporaryDirectory() as outdir:
        master = subprocess.Popen(
            [sys.executable, "-c", _MASTER, str(port)],
            env=env, cwd=repo,
            stdout=subprocess.PIPE, stderr=subprocess.STDOUT, text=True,
        )
        agents = {
            t: subprocess.Popen(
                [sys.executable, "-c", _AGENT, t, str(port), str(w), outdir],
                env=env, cwd=repo,
                stdout=subprocess.PIPE, stderr=subprocess.STDOUT, text=True,
            )
            for t, w in weights.items()
        }
        try:
            outs = {}
            for t, p in agents.items():
                out, _ = p.communicate(timeout=300)
                outs[t] = out
            for t, p in agents.items():
                assert p.returncode == 0, f"agent {t} failed:\n{outs[t]}"
                assert f"AGENT-DONE {t}" in outs[t]
        finally:
            master.kill()
            master.communicate()
            for p in agents.values():
                if p.poll() is None:
                    p.kill()

        # Expected consensus: the weighted mean of the three initial
        # parameter vectors (same seeds as the agent processes).
        model = ANNModel(hidden_dim=8, output_dim=3)
        flats = {}
        spec: TreeSpec | None = None
        for t in weights:
            params = model.init(jax.random.key(ord(t)), jnp.zeros((1, 4)))[
                "params"
            ]
            flats[t], spec = tree_to_flat(params)
        expect = sum(weights[t] * flats[t] for t in weights) / sum(
            weights.values()
        )

        results = {t: np.load(f"{outdir}/{t}.npy") for t in weights}
        for t, got in results.items():
            # bf16 wire quantizes each hop: agree to bf16-scale tolerance.
            np.testing.assert_allclose(got, expect, atol=2e-2)
            tree = flat_to_tree(got, spec)
            assert jax.tree.structure(tree) is not None
        # All agents agree with each other (consensus reached).
        vals = list(results.values())
        for v in vals[1:]:
            np.testing.assert_allclose(v, vals[0], atol=5e-3)
