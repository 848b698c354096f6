"""Test configuration: run JAX on 8 virtual CPU devices.

This is the TPU-framework analogue of the reference's asyncio fake-network
fixture (``utils/consensus_asyncio.py``): N logical agents, the real SPMD
protocol, one process, no hardware.

The tests always run on the virtual CPU mesh, whatever accelerator the
machine has: the platform is set in the environment before JAX is imported
and pinned in its config after.
"""

import os

os.environ["JAX_PLATFORMS"] = "cpu"

flags = os.environ.get("XLA_FLAGS", "")
if "xla_force_host_platform_device_count" not in flags:
    os.environ["XLA_FLAGS"] = (
        flags + " --xla_force_host_platform_device_count=8"
    ).strip()
# The equivalence oracles (superstep == per-epoch loop, async / robust
# rounds at neutral knobs == the plain round) compare DIFFERENT compiled
# programs bit for bit.  XLA:CPU contracts ``a * b + c`` into one fused
# multiply-add, with one rounding in place of two, wherever the multiply
# and the add land in one fusion, so elementwise arithmetic (the dense
# gossip round, ``ops.mixing.leaf_mix``) would take its last bit from
# the program around it.  AVX has no FMA: capped there, the CPU
# evaluates what was written, as the TPU's vector unit does, and equal
# arithmetic gives equal bits whatever it is fused with.
if "xla_cpu_max_isa" not in os.environ["XLA_FLAGS"]:
    os.environ["XLA_FLAGS"] += " --xla_cpu_max_isa=AVX"

import jax  # noqa: E402

jax.config.update("jax_platforms", "cpu")
assert len(jax.devices()) == 8, (
    f"expected 8 virtual CPU devices, got {jax.devices()}"
)
