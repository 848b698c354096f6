"""Hermetic 2-process ``jax.distributed`` smoke test for the multihost
helpers (``parallel/multihost.py``).

The reference's multi-process story is its asyncio-TCP backend
(``utils/consensus_tcp/``, exercised only by 4 manually-run notebooks);
the TPU framework's is one SPMD program joined via
``jax.distributed.initialize``.  This test spawns two CPU processes with 2
virtual devices each, joins them into one 4-device runtime, and checks
``initialize`` (idempotence included), ``hybrid_agent_mesh`` ordering, and
``process_local_agents`` partitioning — the full control-plane path that
cannot run under the single-process fixture.
"""

import os
import socket
import subprocess
import sys

_WORKER = r"""
import sys
import numpy as np
import jax
jax.config.update("jax_platforms", "cpu")

from distributed_learning_tpu.parallel import multihost

coordinator, pid = sys.argv[1], int(sys.argv[2])
multihost.initialize(coordinator, num_processes=2, process_id=pid)
multihost.initialize(coordinator, num_processes=2, process_id=pid)  # no-op

assert jax.process_count() == 2, jax.process_count()
devices = jax.devices()
assert len(devices) == 4, devices

mesh = multihost.hybrid_agent_mesh()
flat = list(np.asarray(mesh.devices).ravel())
# Sorted by process first: agents 0-1 on process 0, agents 2-3 on process 1.
assert [d.process_index for d in flat] == [0, 0, 1, 1], flat

local = multihost.process_local_agents(mesh)
assert local == ((0, 1) if pid == 0 else (2, 3)), (pid, local)

# The consensus engines run ONE SPMD program across both processes over
# this mesh — gossip, compressed gossip, and gradient tracking all cross
# the process boundary through the same collectives.
import jax.numpy as jnp
from distributed_learning_tpu.parallel import (
    ChocoGossipEngine,
    GradientTrackingEngine,
    Topology,
    top_k,
)
from distributed_learning_tpu.parallel.consensus import ConsensusEngine

W = Topology.ring(4).metropolis_weights()
x0 = jnp.asarray(np.random.default_rng(0).normal(size=(4, 8)).astype(np.float32))
mean = np.asarray(x0).mean(axis=0)

eng = ConsensusEngine(W, mesh=mesh)
out, rounds, res = eng.mix_until(eng.shard(x0), eps=1e-5, max_rounds=500)
assert float(res) < 1e-5, float(res)
# Residual alone could pass on a wrong fixed point; pin the mean too.
assert float(jnp.max(jnp.abs(out - mean[None]))) < 1e-3

choco = ChocoGossipEngine(W, top_k(0.5), gamma=0.4, mesh=mesh)
cstate, _ = choco.run(choco.init(x0), 120)
cerr = float(jnp.max(jnp.abs(cstate.x - mean[None])))
assert cerr < 1e-3, cerr

A = jnp.asarray(np.stack([np.eye(8) * (1 + i) for i in range(4)]), jnp.float32)
b = jnp.asarray(np.random.default_rng(1).normal(size=(4, 8)), jnp.float32)
x_star = np.linalg.solve(np.asarray(A).sum(0), np.asarray(b).sum(0))
gt = GradientTrackingEngine(
    W, lambda x, i, s: A[i] @ x - b[i], learning_rate=0.05, mesh=mesh
)
gstate, _ = gt.run(gt.init(jnp.zeros((4, 8), jnp.float32)), 1500)
gerr = float(jnp.max(jnp.abs(jnp.asarray(gstate.x) - x_star[None])))
assert gerr < 1e-3, gerr

# The 2D dp x sp LM step across the SAME process boundary: agents split
# across processes (the gossip ppermute is a cross-host transfer), each
# agent's sequence shards within one process (K/V rotation stays local).
import optax
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P
from distributed_learning_tpu.models.transformer import TransformerLM
from distributed_learning_tpu.training.spmd_lm import (
    make_gossip_lm_step,
    stack_agent_states,
)

mesh2d = Mesh(np.asarray(mesh.devices).reshape(2, 2), ("agents", "seq"))
kw = dict(vocab_size=8, num_layers=1, num_heads=2, head_dim=4, max_len=8)
lm = TransformerLM(**kw, attn_impl="ring", seq_axis="seq")
twin = TransformerLM(**kw, attn_impl="full")
tx2 = optax.adam(3e-3)
seqs = (
    np.random.default_rng(2).integers(0, 8, size=(2, 2, 1)) + np.arange(9)
) % 8
xt = jnp.asarray(seqs[..., :-1], jnp.int32)
yt = jnp.asarray(seqs[..., 1:], jnp.int32)
p2, o2 = stack_agent_states(twin, tx2, jax.random.key(4), xt[0], 2)
# Same host values on both processes -> device_put with global shardings
# produces the global arrays the jitted step consumes.
put = lambda t, spec: jax.tree.map(
    lambda a: jax.device_put(a, NamedSharding(mesh2d, spec)), t
)
p2 = put(p2, P("agents"))
o2 = put(o2, P("agents"))
xt = jax.device_put(xt, NamedSharding(mesh2d, P("agents", None, "seq")))
yt = jax.device_put(yt, NamedSharding(mesh2d, P("agents", None, "seq")))
step2 = make_gossip_lm_step(mesh2d, lm, tx2)
losses = []
with mesh2d:
    for _ in range(3):
        p2, o2, l2 = step2(p2, o2, xt, yt)
        losses.append(float(l2))
assert np.isfinite(losses[-1]), losses
assert losses[-1] < losses[0], losses

# PIPELINE parallelism across the process boundary: a 4-stage 1F1B
# step whose stage ring spans both processes (activations and
# cotangents hop hosts via ppermute) — grads must equal autodiff
# through the unsharded stack, same oracle as tests/test_pp.py.
from distributed_learning_tpu.training.pp import make_1f1b_train_step

mesh_pp = Mesh(np.asarray(mesh.devices), ("stage",))
rng_pp = np.random.default_rng(5)
Dp = 8
ppar = {"W": jnp.asarray(
    rng_pp.normal(size=(4, Dp, Dp)).astype(np.float32) / np.sqrt(Dp)
)}
mbs = jnp.asarray(rng_pp.normal(size=(3, 2, Dp)).astype(np.float32))
yss = jnp.asarray(rng_pp.normal(size=(3, 2, Dp)).astype(np.float32))
stage_fn = lambda p, a: jnp.tanh(a @ p["W"])
loss_pp = lambda o, yy: jnp.mean((o - yy) ** 2)
step_pp = make_1f1b_train_step(mesh_pp, stage_fn, loss_pp)
with mesh_pp:
    g_pp, l_pp = step_pp(
        jax.device_put(ppar, NamedSharding(mesh_pp, P("stage"))),
        mbs, yss,
    )

def _ref_pp(p):
    a = mbs
    for s_ in range(4):
        a = jnp.tanh(a @ p["W"][s_])
    return jnp.mean(jax.vmap(loss_pp)(a, yss))

rg_pp = jax.grad(_ref_pp)(ppar)
assert np.isfinite(float(l_pp))  # loss is replicated: addressable
# The grads are sharded ACROSS PROCESSES (not fully addressable):
# each host checks its own stages' shards against the oracle slice.
ref_W = np.asarray(rg_pp["W"])
for sh in g_pp["W"].addressable_shards:
    err = np.abs(np.asarray(sh.data) - ref_W[sh.index]).max()
    assert err < 1e-4, (sh.index, err)

print(f"OK-MH {pid}", flush=True)
"""


def test_two_process_initialize_and_local_agents():
    with socket.socket() as s:
        s.bind(("127.0.0.1", 0))
        port = s.getsockname()[1]
    coordinator = f"127.0.0.1:{port}"

    env = dict(os.environ)
    env["JAX_PLATFORMS"] = "cpu"
    env["XLA_FLAGS"] = "--xla_force_host_platform_device_count=2"
    # Hermetic children: they import this checkout and nothing else
    # from the parent's PYTHONPATH.
    repo = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
    env["PYTHONPATH"] = repo
    procs = [
        subprocess.Popen(
            [sys.executable, "-c", _WORKER, coordinator, str(pid)],
            env=env,
            cwd=repo,
            stdout=subprocess.PIPE,
            stderr=subprocess.STDOUT,
            text=True,
        )
        for pid in range(2)
    ]
    outs = []
    try:
        for p in procs:
            out, _ = p.communicate(timeout=600)
            outs.append(out)
    finally:
        for p in procs:
            if p.poll() is None:
                p.kill()
    for pid, (p, out) in enumerate(zip(procs, outs)):
        assert p.returncode == 0, f"process {pid} failed:\n{out}"
        assert f"OK-MH {pid}" in out


# --------------------------------------------------------------------- #
# Multi-slice mesh ordering: pod layouts are out of reach here, but the
# ordering logic that keeps ring traffic on ICI is pure — drive it with
# stand-in device objects carrying (process_index, slice_index, id).
# --------------------------------------------------------------------- #

import jax
import numpy as np


class _FakeDev:
    def __init__(self, process_index, slice_index, id):
        self.process_index = process_index
        self.slice_index = slice_index
        self.id = id

    def __repr__(self):
        return f"p{self.process_index}s{self.slice_index}d{self.id}"


def _cross_slice_ring_edges(order):
    """Count closed-ring edges whose endpoints live on different slices
    (the DCN hops a gossip ring pays per round)."""
    n = len(order)
    key = lambda d: (d.process_index, getattr(d, "slice_index", 0) or 0)
    return sum(1 for i in range(n) if key(order[i]) != key(order[(i + 1) % n]))


def _assert_slices_contiguous(order):
    key = lambda d: (d.process_index, getattr(d, "slice_index", 0) or 0)
    seen, prev = set(), None
    for d in order:
        k = key(d)
        if k != prev:
            assert k not in seen, f"slice {k} split apart in {order}"
            seen.add(k)
            prev = k


def test_ring_order_2x4_slices_stay_contiguous():
    """2 slices x 4 devices, presented shuffled: each slice's devices
    must end up contiguous, so the closed agent ring pays exactly
    n_slices DCN hops (the minimum) instead of up to n_devices."""
    from distributed_learning_tpu.parallel.multihost import (
        order_devices_for_ring,
    )

    devs = [_FakeDev(p, p, p * 4 + i) for p in range(2) for i in range(4)]
    rng = np.random.default_rng(0)
    shuffled = [devs[i] for i in rng.permutation(len(devs))]
    order = order_devices_for_ring(shuffled)
    _assert_slices_contiguous(order)
    assert _cross_slice_ring_edges(order) == 2
    # Within a slice, device-id order (the ICI-adjacent order).
    assert [d.id for d in order] == list(range(8))


def test_ring_order_4x2_slices_stay_contiguous():
    from distributed_learning_tpu.parallel.multihost import (
        order_devices_for_ring,
    )

    devs = [_FakeDev(p, p, p * 2 + i) for p in range(4) for i in range(2)]
    rng = np.random.default_rng(1)
    shuffled = [devs[i] for i in rng.permutation(len(devs))]
    order = order_devices_for_ring(shuffled)
    _assert_slices_contiguous(order)
    assert _cross_slice_ring_edges(order) == 4


def test_ring_order_multiprocess_single_slice_groups_by_process():
    """megascale-less multi-host (e.g. CPU two-process tests): slice_index
    is None everywhere; grouping must fall back to process boundaries."""
    from distributed_learning_tpu.parallel.multihost import (
        order_devices_for_ring,
    )

    devs = [_FakeDev(p, None, p * 4 + i) for p in range(2) for i in range(4)]
    rng = np.random.default_rng(2)
    shuffled = [devs[i] for i in rng.permutation(len(devs))]
    order = order_devices_for_ring(shuffled)
    _assert_slices_contiguous(order)
    assert _cross_slice_ring_edges(order) == 2


_WORKER4 = r"""
import sys
import numpy as np
import jax
jax.config.update("jax_platforms", "cpu")

from distributed_learning_tpu.parallel import multihost

coordinator, pid = sys.argv[1], int(sys.argv[2])
multihost.initialize(coordinator, num_processes=4, process_id=pid)

assert jax.process_count() == 4, jax.process_count()
devices = jax.devices()
assert len(devices) == 8, devices

mesh = multihost.hybrid_agent_mesh()
flat = list(np.asarray(mesh.devices).ravel())
assert [d.process_index for d in flat] == [0, 0, 1, 1, 2, 2, 3, 3], flat
local = multihost.process_local_agents(mesh)
assert local == (2 * pid, 2 * pid + 1), (pid, local)

# One SPMD gossip program spanning all four processes: the ring ppermute
# crosses three process boundaries; eps-stopped mixing must still reach
# the exact global mean.
import jax.numpy as jnp
from distributed_learning_tpu.parallel import Topology
from distributed_learning_tpu.parallel.consensus import ConsensusEngine

W = Topology.ring(8).metropolis_weights()
x0 = jnp.asarray(
    np.random.default_rng(0).normal(size=(8, 8)).astype(np.float32)
)
mean = np.asarray(x0).mean(axis=0)
eng = ConsensusEngine(W, mesh=mesh)
out, rounds, res = eng.mix_until(eng.shard(x0), eps=1e-5, max_rounds=800)
assert float(res) < 1e-5, float(res)
assert float(jnp.max(jnp.abs(out - mean[None]))) < 1e-3

# Traced-W mixing over a denser runtime graph on the same mesh.
W2 = Topology.erdos_renyi(8, 0.6, seed=3).metropolis_weights()
m2 = eng.mix_with(out, W2, times=2, route="allgather")
jax.block_until_ready(m2)

print(f"OK-MH4 {pid}", flush=True)
"""


def test_four_process_gossip():
    """Four CPU processes x two devices each — the >2-process control
    plane VERDICT r4 next-#6 asks for: initialize, hybrid mesh ordering
    across four process boundaries, and eps-stopped gossip reaching the
    global mean through three DCN-analog hops."""
    with socket.socket() as s:
        s.bind(("127.0.0.1", 0))
        port = s.getsockname()[1]
    coordinator = f"127.0.0.1:{port}"

    env = dict(os.environ)
    env["JAX_PLATFORMS"] = "cpu"
    env["XLA_FLAGS"] = "--xla_force_host_platform_device_count=2"
    repo = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
    env["PYTHONPATH"] = repo
    procs = [
        subprocess.Popen(
            [sys.executable, "-c", _WORKER4, coordinator, str(pid)],
            env=env,
            cwd=repo,
            stdout=subprocess.PIPE,
            stderr=subprocess.STDOUT,
            text=True,
        )
        for pid in range(4)
    ]
    outs = []
    try:
        for p in procs:
            out, _ = p.communicate(timeout=600)
            outs.append(out)
    finally:
        for p in procs:
            if p.poll() is None:
                p.kill()
    for pid, (p, out) in enumerate(zip(procs, outs)):
        assert p.returncode == 0, f"process {pid} failed:\n{out}"
        assert f"OK-MH4 {pid}" in out


def test_hybrid_agent_mesh_two_slice_schedule_dcn_hops(monkeypatch):
    """End-to-end on a MOCKED 2-slice topology (VERDICT r4 next-#6):
    ``hybrid_agent_mesh`` built from a shuffled fake device set must
    order the mesh so the ring topology's edge-colored ppermute
    schedule (``parallel/schedule.py``) pays exactly n_slices = 2 DCN
    hops per full round — the minimum a closed ring can pay — with
    every other matched pair staying intra-slice (ICI)."""
    from distributed_learning_tpu.parallel.multihost import (
        hybrid_agent_mesh,
    )
    from distributed_learning_tpu.parallel.schedule import (
        MatchingSchedule,
    )
    from distributed_learning_tpu.parallel.topology import Topology

    devs = [_FakeDev(p, p, p * 4 + i) for p in range(2) for i in range(4)]
    rng = np.random.default_rng(7)
    shuffled = [devs[i] for i in rng.permutation(len(devs))]
    monkeypatch.setattr(jax, "devices", lambda *a, **k: shuffled)

    mesh = hybrid_agent_mesh()
    order = list(np.asarray(mesh.devices).ravel())
    _assert_slices_contiguous(order)

    sched = MatchingSchedule.from_topology(Topology.ring(8))
    slice_of = lambda d: (d.process_index, d.slice_index or 0)
    dcn = intra = 0
    for matching in sched.matchings:
        for i, j in matching:
            if slice_of(order[i]) != slice_of(order[j]):
                dcn += 1
            else:
                intra += 1
    # A ring's matchings cover each of the 8 undirected edges exactly
    # once per full round; on the ordered mesh exactly the two
    # slice-boundary edges cross DCN.
    assert dcn + intra == 8, (dcn, intra)
    assert dcn == 2, (dcn, [slice_of(d) for d in order])


def test_hybrid_agent_mesh_uses_ring_order():
    """On the virtual 8-CPU backend the mesh must be the ordered device
    list (one process, one slice -> plain id order)."""
    from distributed_learning_tpu.parallel.multihost import (
        hybrid_agent_mesh,
        order_devices_for_ring,
    )

    mesh = hybrid_agent_mesh()
    expect = order_devices_for_ring(jax.devices())
    assert list(np.asarray(mesh.devices).ravel()) == expect
    assert mesh.axis_names == ("agents",)
