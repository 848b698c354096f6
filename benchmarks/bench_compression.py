"""Compressed vs dense gossip: rounds-to-residual and wire bytes.

Beyond the five BASELINE configs: quantifies the CHOCO-GOSSIP trade
(``parallel/compression.py``) on WRN-sized parameter vectors — how many
extra rounds compressed consensus needs to hit the 1e-4 north-star
residual, and how many fewer bytes per round cross the links.  Wire bytes
are computed with the real codec sizes (``comm/tensor_codec``): dense
bf16 = 2 B/entry; sparse = 6 B/non-zero (u32 index + bf16 value).

Hardware-independent math metrics (like the fast-averaging config): the
recorded numbers come from the 8-virtual-device CPU mesh / dense engine
and are identical on TPU.
"""

from __future__ import annotations

import jax
import jax.numpy as jnp
import numpy as np

from benchmarks.common import emit, full_scale, smoke
from distributed_learning_tpu.parallel import Topology
from distributed_learning_tpu.parallel.compression import (
    ChocoGossipEngine,
    approx_top_k,
    top_k,
)

TARGET = 1e-4  # BASELINE.json north-star consensus residual


def run() -> None:
    n = 8
    # Full-scale dim sized for TPU wall-clock: exact top-k is a sort, and
    # a 65k sort per agent per round made the original full-scale choice
    # take the better part of an hour on the chip for zero extra insight.
    # 16k keeps the vectors WRN-block-sized; the atopk case below shows
    # the hardware-aware escape hatch at the same dim.
    dim = 16_384 if full_scale() else 256
    W = Topology.ring(n).metropolis_weights()
    rng = np.random.default_rng(0)
    x0 = jnp.asarray(rng.normal(size=(n, dim)).astype(np.float32))
    x0 = x0 / float(jnp.abs(x0).max())  # residual starts O(1)

    # Dense gossip reference: rounds to target via the consensus engine.
    from distributed_learning_tpu.parallel.consensus import ConsensusEngine

    eng = ConsensusEngine(W)
    _, rounds_dense, res = eng.mix_until(x0, eps=TARGET, max_rounds=10_000)
    rounds_dense = int(rounds_dense)
    if float(res) >= TARGET:
        raise SystemExit(
            f"dense baseline failed to reach {TARGET} in {rounds_dense} "
            "rounds; byte-ratio comparisons would be fictitious"
        )
    dense_bytes_per_round = 2 * dim  # bf16 per directed edge message

    # (label, compressor factory, fraction, gamma); the atopk case is the
    # TPU-native approximate selection (lax.approx_max_k) at the identical
    # fraction — same bytes, cheaper selection, marginally smaller delta.
    cases = [("topk", top_k, 0.1, 0.2)]
    if not smoke():
        cases += [
            ("topk", top_k, 0.01, 0.02),
            ("atopk", approx_top_k, 0.1, 0.2),
        ]
    for label, factory, fraction, gamma in cases:
        choco = ChocoGossipEngine(W, factory(fraction), gamma=gamma)
        state = choco.init(x0)
        rounds, chunk = 0, 200
        reached = False
        last_res = float("inf")
        while rounds < 60_000:
            state, r = choco.run(state, chunk)
            trace = np.asarray(r)
            below = np.flatnonzero(trace < TARGET)
            if below.size:
                # Exact crossing round inside this chunk.
                rounds += int(below[0]) + 1
                last_res = float(trace[below[0]])
                reached = True
                break
            rounds += chunk
            last_res = float(trace[-1])
        k = max(1, int(round(fraction * dim)))
        sparse_bytes_per_round = 6 * k
        emit({
            "metric": f"choco_{label}{fraction}_rounds_to_{TARGET}",
            "value": rounds if reached else None,
            "unit": "rounds",
            "vs_baseline": None,
            "config": f"ring-{n}, dim {dim}, gamma {gamma}; dense gossip "
                      f"needs {rounds_dense} rounds",
            "publish_key": f"choco_{label}{fraction}_ring8",
            "rounds_dense": rounds_dense,
            "bytes_per_round_sparse": sparse_bytes_per_round,
            "bytes_per_round_dense": dense_bytes_per_round,
            "byte_reduction": round(dense_bytes_per_round / sparse_bytes_per_round, 1),
            "total_bytes_ratio_vs_dense": (
                round(
                    (rounds * sparse_bytes_per_round)
                    / (rounds_dense * dense_bytes_per_round),
                    3,
                )
                if reached
                else None
            ),
            "final_residual": last_res,
        })


if __name__ == "__main__":
    run()
