"""Test configuration: run JAX on 8 virtual CPU devices.

This is the TPU-framework analogue of the reference's asyncio fake-network
fixture (``utils/consensus_asyncio.py``): N logical agents, the real SPMD
protocol, one process, no hardware.

The tests always run on the virtual CPU mesh, whatever accelerator the
machine has: the platform is set in the environment before JAX is imported
and pinned in its config after.
"""

import os
import tempfile

os.environ["JAX_PLATFORMS"] = "cpu"

# The program's perf ledger (obs/cost.py) defaults to a file under
# benchmarks/results/ so real runs accumulate history; tests must not
# grow it — point it at a throwaway dir unless the environment already
# pinned it.
os.environ.setdefault(
    "DLT_PERF_LEDGER",
    os.path.join(
        tempfile.mkdtemp(prefix="dlt_test_ledgers_"), "perf_ledger.jsonl"
    ),
)
flags = os.environ.get("XLA_FLAGS", "")
if "xla_force_host_platform_device_count" not in flags:
    os.environ["XLA_FLAGS"] = (
        flags + " --xla_force_host_platform_device_count=8"
    ).strip()

import jax  # noqa: E402

jax.config.update("jax_platforms", "cpu")
assert len(jax.devices()) == 8, (
    f"expected 8 virtual CPU devices, got {jax.devices()}"
)
