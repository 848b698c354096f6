"""The Phi-4-mini-flash cell's driver (``chipbench/drivers/train_ref_phi4flash.py``),
its reference and FLOP counts on the CPU at toy size: the SambaY cell
added as toy files runs end to end, is held to the benchmark's own
reference, and every control of ``faults_phi4flash.py`` fails it."""

from __future__ import annotations

import json
import os
import subprocess
import sys

import pytest

from test_chipbench_harness import (  # noqa: F401
    BENCH, REPO, RESULT_KEYS, _run_cell, copy_with_toys,
)

HERE = os.path.dirname(os.path.abspath(__file__))
CELL = "phi4-mini-flash-solo1.local-sgd-h8"
TOY = "toy-sy.train"
#: each control and a limit it must fail (others may fail too)
CONTROLS = {
    "no_window": "swa_rel.within",
    "lambda_zero": "attn_rel.within",
    "no_subln": "cross_rel.within",
    "gated_memory": "memory_rel.within",
    "kv_from_swa": "cross_rel.within",
    "no_D": "skip_grad_rel.within",
    "lost_chunk": "mamba_token_rel.within",
    "bf16_scan_state": "scan_rel.within",
    "bf16_params": "scan_rel.within",
    "lost_chunk_bwd": "scan_grad_rel.within",
    "kv_no_grad": "grad32_rel.within",
    # in the trainer's epoch program, swept as a unit
    "half_update": "update_median_rel.within",
    "frozen": "update_median_rel.within",
}


def _python(root, code, tmp_path):
    env = dict(os.environ)
    env.update(
        PYTHONPATH=os.pathsep.join([str(root), REPO, HERE]),
        JAX_COMPILATION_CACHE_DIR=str(tmp_path / "jax_cache"),
        JAX_PLATFORMS="cpu",
    )
    proc = subprocess.run([sys.executable, "-c", code], cwd=root, env=env,
                          timeout=600, capture_output=True, text=True)
    assert proc.returncode == 0, proc.stderr[-3000:]
    return proc.stdout.strip().splitlines()


def _checks(lines):
    return json.loads(next(
        l for l in lines if l.startswith("check: "))[len("check: "):])


@pytest.fixture(scope="module")
def swept(copy_with_toys, tmp_path_factory):
    """The sound toy cell, then every control compared on its state."""
    code = (
        "import json, faults_phi4flash as f; "
        f"f.sweep({sorted(CONTROLS)!r}); "
        "from chipbench.run import run_cell; "
        f"print(json.dumps(run_cell({TOY!r}, 2**31 + 11, 0.5, False)))"
    )
    lines = _python(copy_with_toys, code, tmp_path_factory.mktemp("sweep"))
    faults = {}
    for line in lines:
        if line.startswith("fault "):
            name, rest = line[len("fault "):].split(": fails ", 1)
            faults[name] = json.loads(rest[rest.index("{"):])
    return json.loads(lines[-1]), _checks(lines), faults


def test_the_toy_cell_is_held_to_the_reference(swept):
    result, checks, faults = swept
    assert set(result) == RESULT_KEYS
    assert result["correct"] is True and result["failed"] == 0
    assert set(result["metrics"]) == {"tokens_per_s", "setup_s"}
    assert checks["params_f32"] is True and checks["ssm.state_dead"] >= 0
    # one agent: the round is the identity
    assert checks["deviation"] == 0.0
    assert checks["update_median_rel"] < 1e-3
    assert set(faults) == {"sound", *CONTROLS}


@pytest.mark.parametrize("fault", sorted(CONTROLS))
def test_every_control_fails_the_comparison(swept, fault):
    from chipbench.drivers import train_ref_phi4flash as driver

    _, _, faults = swept
    verdicts = driver.verdicts(faults[fault])
    assert verdicts[CONTROLS[fault]] is False
    assert all(driver.verdicts(faults["sound"]).values())


def test_half_update_in_the_timed_program_fails(copy_with_toys, tmp_path):
    """Every update halved in the trainer's epoch program alone: the
    comparison's own programs are sound, what the unit left is not."""
    code = (
        "import json, faults_phi4flash as f; f.apply('half_update'); "
        "from chipbench.run import run_cell; "
        f"print(json.dumps(run_cell({TOY!r}, 2**31 + 11, 0.5, False)))"
    )
    lines = _python(copy_with_toys, code, tmp_path)
    result, checks = json.loads(lines[-1]), _checks(lines)
    assert result["correct"] is False
    assert checks["epoch_loss_abs.within"] is False
    # printed, no limit: the halved step is half the replay's
    assert checks["update_rel/mamba.in_proj"] == pytest.approx(0.5, abs=0.05)
    assert checks["update_median_rel"] == pytest.approx(0.5, abs=0.05)
    assert all(ok for name, ok in checks.items() if name.endswith(
        ".within") and not name.startswith(("epoch_", "update_")))


def test_a_traced_toy_run_leaves_out_what_it_cannot_read(copy_with_toys,
                                                         tmp_path):
    result = _run_cell(copy_with_toys, TOY, True, tmp_path)
    assert result["correct"] is True
    assert set(result["metrics"]) == {"compile_s.sy"}


def test_a_fault_is_taken_out_again():
    import faults_phi4flash as f
    from distributed_learning_tpu.models import sambay, transformer
    from distributed_learning_tpu.ops import selective_scan

    from distributed_learning_tpu.training import trainer

    now = lambda: (sambay.Mamba, sambay.SambaYBlock, transformer._Block,
                   selective_scan._fwd_kernel, selective_scan._bwd_kernel,
                   selective_scan._scan_xla, trainer.GossipTrainer)
    sound = now()
    for name in f.FAULTS:  # every fault still finds its lines
        f.apply(name)()
    with f.applied("lost_chunk_bwd"):
        assert selective_scan._bwd_kernel is not sound[4]
    assert now() == sound


def test_the_two_references_are_one_text():
    with open(os.path.join(BENCH, "reference_phi4flash.py"),
              encoding="utf-8") as fh:
        bench = fh.read()
    with open(os.path.join(REPO, "distributed_learning_tpu", "models",
                           "reference", "phi4flash.py"),
              encoding="utf-8") as fh:
        program = fh.read()
    below = lambda text: text[text.index("The oracle for"):]
    assert below(bench) == below(program)


def test_the_flop_counts_at_the_cell():
    from chipbench.flops import phi4flash as flops

    with open(os.path.join(BENCH, "configs", "phi4-mini-flash-solo1.json"),
              encoding="utf-8") as fh:
        config = json.load(fh)
    m = config["model"]["kwargs"]
    # every parameter but the norms, the conv, A, D, the biases and the
    # lambdas sits in a product: 697,073,792 less those
    assert 696e6 < flops.matmul_params(**m) < 697_073_792
    assert flops.per_step(config) == pytest.approx(37.5e12, rel=0.01)
    work = flops.extra_work(config)
    assert work["flash_flops_per_step"] == pytest.approx(3.64e12, rel=0.01)
    assert flops.live_pairs(8192, 512) == 512 * 513 // 2 + 512 * 7680
    # the scan's bytes bound it: ~1.6 ms a step at 819 GB/s
    assert work["scan_bytes_per_step"] / 819e9 == pytest.approx(1.65e-3,
                                                               rel=0.05)
    assert work["scan_flops_per_step"] / 197e12 < 0.2e-3


def test_the_cell_and_its_metrics_are_declared():
    """Found by name, never by place: a later PR appends after this one."""
    with open(os.path.join(BENCH, "workloads", CELL + ".json"),
              encoding="utf-8") as fh:
        cell = json.load(fh)
    assert cell["chips"] == 1 and cell["driver"] == "train_ref_phi4flash"
    assert cell["traffic"] == {
        "epoch_len": 8, "superstep": 1, "mix_times": 1, "mix_eps": None,
        "compression": None, "layout": "dense"}
    with open(os.path.join(REPO, "BENCHMARK.json"), encoding="utf-8") as fh:
        bench = json.load(fh)
    declared, = [w for w in bench["workloads"] if w["name"] == CELL]
    assert {k: declared[k] for k in ("config", "chips", "why")} == {
        k: cell[k] for k in ("config", "chips", "why")}
    assert [c["name"] for c in bench["configs"]].count(cell["config"]) == 1
    metrics = {m["name"]: m for m in bench["per_layer"]}
    assert len(cell["per_layer"]) == 12
    for name in cell["per_layer"]:
        assert name.endswith(".sy") and metrics[name]["workloads"] == [CELL]
        with open(os.path.join(BENCH, "metrics", name + ".json"),
                  encoding="utf-8") as fh:
            m = json.load(fh)
        keys = ("unit", "better", "source", "layer", "moves")
        assert {k: m[k] for k in keys} == {k: metrics[name][k] for k in keys}
    tokens, = [m for m in bench["end_to_end"] if m["name"] == "tokens_per_s"]
    assert CELL in tokens["workloads"]
