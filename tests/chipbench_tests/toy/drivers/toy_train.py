"""Tests only: a driver added as a file."""

from chipbench.drivers.train import Driver  # noqa: F401
