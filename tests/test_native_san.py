"""graftlint sanitizer stage (ISSUE 10) rot-guard.

The acceptance property: ``graftlint --native`` replays the corruption-
fuzz corpus + byte-identity oracle matrix under ASan/UBSan with ZERO
reports, builds into its own cache (the production ``.so`` files are
untouched), and skips cleanly on boxes without g++ or the sanitizer
runtimes.  One full-stage test (the expensive one — a sanitized rebuild
plus ~450 replay cases) plus cheap wiring checks.
"""

import os
import subprocess
import sys

import pytest

from tools.graftlint import native_san
from tools.graftlint.core import REPO_ROOT

_USABLE, _REASON = native_san.toolchain_status()

def _prod_sos():
    """The production libraries as built here (keyed names)."""
    import glob

    return glob.glob(os.path.join(
        REPO_ROOT, "distributed_learning_tpu", "native", "_*.so"
    ))


def test_toolchain_status_shape():
    usable, reason = native_san.toolchain_status()
    assert isinstance(usable, bool)
    if not usable:
        assert reason  # the skip notice must say what is missing


@pytest.mark.skipif(
    not _USABLE, reason=f"sanitizer toolchain absent: {_REASON}"
)
def test_native_stage_runs_clean_without_touching_production_sos():
    before = {p: os.path.getmtime(p) for p in _prod_sos()}
    status, detail = native_san.run_native_stage()
    assert status == "ok", (status, detail)
    # The replay summary proves the corpus actually ran.
    summary = " ".join(detail)
    assert "fuzz=200" in summary and "oracle=" in summary, detail
    after = {p: os.path.getmtime(p) for p in _prod_sos()}
    assert after == before, (
        "sanitized build must live in .san_cache/, never the production "
        "native cache"
    )
    assert os.path.isdir(native_san.SAN_CACHE)
    assert any(
        name.startswith("_wire.") and name.endswith(".so")
        for name in os.listdir(native_san.SAN_CACHE)
    )


@pytest.mark.skipif(
    not _USABLE, reason=f"sanitizer toolchain absent: {_REASON}"
)
def test_cli_native_flag_wires_the_stage():
    out = subprocess.run(
        [sys.executable, "-m", "tools.graftlint", "--native", "--rules",
         "no-pickle"],
        cwd=REPO_ROOT, capture_output=True, text=True, timeout=600,
    )
    assert out.returncode == 0, (out.stdout[-2000:], out.stderr[-2000:])
    assert "native-san: ok" in out.stderr


def test_stage_skips_cleanly_when_toolchain_absent(monkeypatch):
    """The no-toolchain path: a skip with the missing piece named, never
    a fake pass/fail — simulated by blinding the runtime resolver."""
    monkeypatch.setattr(
        native_san, "toolchain_status",
        lambda: (False, "libasan.so runtime not found by g++"),
    )
    status, detail = native_san.run_native_stage()
    assert status == "skip"
    assert "libasan" in detail[0]
