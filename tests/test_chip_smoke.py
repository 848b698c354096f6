"""CPU rehearsal of ``chip_smoke.py`` and of the compile-cache helper.

The script's phase functions run here at toy size (sizes passed as
arguments — the program has no option for them), so wrong paths,
arguments and control flow are found before a chip call; its ``flash``
phase needs the compiled kernels, which ``test_chip_compile.py`` covers.
The script itself must refuse to run without a TPU.
"""

import os
import subprocess
import sys

import jax
import numpy as np
import pytest

import chip_smoke
from distributed_learning_tpu.utils.compile_cache import enable_compile_cache

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
TOY = dict(depth=10, widen=1, batch=8, steps=4)


def test_train_phase_at_toy_size(tmp_path):
    report = chip_smoke.train(jax.devices()[0], str(tmp_path), **TOY)
    assert report["model"] == "wrn-10-1" and report["agents"] == 4
    assert len(report["losses"]) == 3  # train_epoch + train_epochs(2)
    assert report["losses"][-1] < report["losses"][0]
    assert all(np.isfinite(r) and r >= 0 for r in report["residuals"])
    assert report["donated"] is False  # the CPU backend ignores donation
    assert not os.path.exists(tmp_path / "ckpt")  # round trip cleaned up


def test_consensus_phase_at_toy_size():
    report = chip_smoke.consensus(jax.devices()[0], n_agents=8, dim=4096)
    assert 0 < report["rounds"] < 10_000
    assert report["residual"] <= report["eps"] == 1e-4
    assert report["mean_drift"] <= 1e-5


def test_multichip_phase_at_toy_size():
    """Four of the virtual CPU devices: sharded equals dense."""
    report = chip_smoke.multichip(jax.devices()[0], dim=4096, **TOY)
    assert report["mix_err"] <= 1e-5 and report["mix_until_err"] <= 1e-5
    assert report["loss_err"] <= 1e-4  # f32 on the CPU: far inside bf16
    assert len(set(report["devices"])) == 4


def test_device_check_exits_nonzero_without_a_tpu(tmp_path):
    """``python chip_smoke.py`` on a CPU-only JAX: another code than 0,
    no result line, before any phase."""
    env = dict(os.environ, JAX_PLATFORMS="cpu", PYTHONPATH=REPO)
    out = subprocess.run(
        [sys.executable, os.path.join(REPO, "chip_smoke.py")],
        env=env, cwd=str(tmp_path), capture_output=True, text=True,
        timeout=300,
    )
    assert out.returncode != 0
    assert out.stdout.strip() == ""
    assert "needs 1 TPU device" in out.stderr
    with pytest.raises(SystemExit):
        chip_smoke.require_tpu()


def test_cache_helper_leaves_a_placed_cache_alone(monkeypatch, tmp_path):
    placed = str(tmp_path / "elsewhere")
    monkeypatch.setenv("JAX_COMPILATION_CACHE_DIR", placed)
    before = jax.config.jax_compilation_cache_dir
    try:
        assert enable_compile_cache() == placed
        # entries are keyed on the ops' names too (the profiles' scopes)
        assert jax.config.jax_compilation_cache_include_metadata_in_key
    finally:
        jax.config.update(
            "jax_compilation_cache_include_metadata_in_key", False)
    assert jax.config.jax_compilation_cache_dir == before


def test_cache_helper_default_is_one_fixed_path_in_the_checkout(monkeypatch):
    monkeypatch.delenv("JAX_COMPILATION_CACHE_DIR", raising=False)
    before = jax.config.jax_compilation_cache_dir
    try:
        first = enable_compile_cache()
        assert jax.config.jax_compilation_cache_dir == first
        assert enable_compile_cache() == first
    finally:
        jax.config.update("jax_compilation_cache_dir", before)
        jax.config.update(
            "jax_compilation_cache_include_metadata_in_key", False)
    assert first == os.path.join(REPO, ".jax_cache")
