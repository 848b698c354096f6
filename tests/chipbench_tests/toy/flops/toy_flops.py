"""Tests only: a FLOP function added as a file."""

from chipbench.flops.wrn import per_step  # noqa: F401
