"""Tests only: a data generator added as a file."""

from chipbench.data.cifar_synth import make  # noqa: F401
