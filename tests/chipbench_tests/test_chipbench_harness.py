"""The chip benchmark's harness, on the CPU at toy size.

Nothing here is a measurement: these tests hold the harness to its
contract (files found by name, the last line's keys, no fallback without
a TPU) and its arithmetic (FLOP counts, the trace reducers) to known
numbers.  The toy configurations, cells, metric and code files under
``toy/`` are not part of the benchmark: each run copies ``chipbench/`` to a
temporary directory, adds them there as new files, and runs from the copy
in a child process (so the compile cache the harness turns on never
touches the test process).
"""

from __future__ import annotations

import glob
import hashlib
import json
import os
import re
import shutil
import subprocess
import sys
from types import SimpleNamespace

import numpy as np
import pytest

HERE = os.path.dirname(os.path.abspath(__file__))
REPO = os.path.dirname(os.path.dirname(HERE))
BENCH = os.path.join(REPO, "chipbench")
NAME = re.compile(r"^[A-Za-z0-9_][A-Za-z0-9_.\-]{0,63}$")
UNIT = re.compile(r"^[A-Za-z0-9_/%.\-]{1,16}$")
RESULT_KEYS = {"correct", "attempted", "failed", "metrics", "device"}


def _load(kind, name, root=BENCH):
    with open(os.path.join(root, kind, name + ".json"), encoding="utf-8") as fh:
        return json.load(fh)


def _names(kind, root=BENCH):
    return sorted(
        os.path.basename(p)[:-5]
        for p in glob.glob(os.path.join(root, kind, "*.json"))
    )


def _benchmark():
    with open(os.path.join(REPO, "BENCHMARK.json"), encoding="utf-8") as fh:
        return json.load(fh)


# ---------------------------------------------------------------------- #
# the data files                                                         #
# ---------------------------------------------------------------------- #
@pytest.mark.parametrize("kind", ["configs", "workloads", "metrics"])
def test_every_data_file_loads_and_is_named_within_the_contract(kind):
    names = _names(kind)
    assert names, kind
    for name in names:
        entry = _load(kind, name)
        assert entry["name"] == name and NAME.match(name), name
        if kind == "metrics":
            assert UNIT.match(entry["unit"]), entry
            assert entry["better"] in ("lower", "higher")
            assert entry["source"] in (
                "device_trace", "program_span", "program_counter", "host_clock"
            )
            if entry["kind"] == "per_layer":
                assert os.path.isfile(
                    os.path.join(BENCH, "reducers", entry["reducer"] + ".py")
                ), entry
                assert entry["moves"] in _names("metrics")
        if kind == "workloads":
            assert entry["chips"] in (1, 4) and len(entry["why"]) <= 200
            assert entry["config"] in _names("configs")
            for metric in entry["end_to_end"] + entry["per_layer"]:
                assert metric in _names("metrics"), (name, metric)
            assert os.path.isfile(
                os.path.join(BENCH, "drivers", entry["driver"] + ".py")
            )
        if kind == "configs":
            for part in ("data", "flops"):
                mod = entry[part]["name"] if part == "data" else entry[part]
                assert os.path.isfile(os.path.join(BENCH, part, mod + ".py"))
            for key in entry["reduced"]:
                assert NAME.match(key)


def test_benchmark_json_resolves_to_the_files():
    bench = _benchmark()
    assert set(bench) == {
        "command", "paths", "run_seconds", "configs", "workloads",
        "end_to_end", "per_layer",
    }
    for path in bench["paths"]:
        assert os.path.isdir(os.path.join(REPO, path)), path
    for cfg in bench["configs"]:
        entry = _load("configs", cfg["name"])
        assert os.path.join(REPO, cfg["file"]) == os.path.join(
            BENCH, "configs", cfg["name"] + ".json"
        )
        assert cfg["source"] == entry["source"]
        assert cfg["reduced"] == entry["reduced"]
    assert sum(w["chips"] == 4 for w in bench["workloads"]) <= 1
    reported = {}
    for cell in bench["workloads"]:
        entry = _load("workloads", cell["name"])
        assert cell["name"] == f"{cell['config']}.{cell['traffic']}"
        assert (cell["config"], cell["chips"], cell["why"]) == (
            entry["config"], entry["chips"], entry["why"]
        )
        assert "setup_s" in entry["end_to_end"]
        for metric in entry["end_to_end"] + entry["per_layer"]:
            reported.setdefault(metric, set()).add(cell["name"])
    cells = {w["name"] for w in bench["workloads"]}
    for group in ("end_to_end", "per_layer"):
        for m in bench[group]:
            entry = _load("metrics", m["name"])
            assert entry["kind"] == group
            for key in ("unit", "better", "source"):
                assert m[key] == entry[key], (m["name"], key)
            if group == "per_layer":
                assert (m["layer"], m["moves"]) == (entry["layer"], entry["moves"])
                # the metric it should move is reported wherever it is
                assert reported[m["name"]] <= reported[m["moves"]], m["name"]
            else:
                assert 0 < m["bound"] <= 0.1
            assert set(m.get("workloads", cells)) == reported[m["name"]], m["name"]
    assert set(reported) == {
        m["name"] for g in ("end_to_end", "per_layer") for m in bench[g]
    }


# ---------------------------------------------------------------------- #
# toy cells, added as files to a temporary copy                          #
# ---------------------------------------------------------------------- #
def _tree_digest(root):
    out = {}
    for path in sorted(glob.glob(os.path.join(root, "**", "*"), recursive=True)):
        if os.path.isfile(path) and "__pycache__" not in path:
            with open(path, "rb") as fh:
                out[os.path.relpath(path, root)] = hashlib.sha256(
                    fh.read()).hexdigest()
    return out


@pytest.fixture(scope="module")
def copy_with_toys(tmp_path_factory):
    """``chipbench/`` copied, the toy files added beside what was there."""
    root = tmp_path_factory.mktemp("checkout")
    shutil.copytree(BENCH, root / "chipbench",
                    ignore=shutil.ignore_patterns("__pycache__"))
    before = _tree_digest(root / "chipbench")
    toys = _tree_digest(os.path.join(HERE, "toy"))
    assert toys and not set(toys) & set(before), "a toy file shadows a real one"
    shutil.copytree(os.path.join(HERE, "toy"), root / "chipbench",
                    dirs_exist_ok=True,
                    ignore=shutil.ignore_patterns("__pycache__"))
    after = _tree_digest(root / "chipbench")
    assert {k: after[k] for k in before} == before  # nothing there was touched
    assert len(after) == len(before) + len(toys)
    return root


def _run_cell(root, workload, trace, tmp_path):
    code = (
        "import json; from chipbench.run import run_cell; "
        f"print(json.dumps(run_cell({workload!r}, 2**31 + 11, 0.5, {trace!r})))"
    )
    env = dict(os.environ)
    env.update(
        PYTHONPATH=os.pathsep.join([str(root), REPO]),
        JAX_COMPILATION_CACHE_DIR=str(tmp_path / "jax_cache"),
        JAX_PLATFORMS="cpu",
    )
    proc = subprocess.run(
        [sys.executable, "-c", code], cwd=root, env=env, timeout=600,
        capture_output=True, text=True,
    )
    assert proc.returncode == 0, proc.stderr[-3000:]
    return json.loads(proc.stdout.strip().splitlines()[-1])


@pytest.mark.parametrize(
    "workload, metrics",
    [
        # an added driver, data generator and FLOP function (toy/*/*.py)
        ("toy-wrn.train", {"samples_per_s", "setup_s"}),
        ("toy-lm.train", {"tokens_per_s", "setup_s"}),
        ("toy-wrn.consensus", {"consensus_ms", "consensus_ms_p90", "setup_s"}),
        # four virtual CPU devices stand for the four chips
        ("toy-wrn.consensus-sharded",
         {"consensus_ms", "consensus_ms_p90", "setup_s"}),
    ],
)
def test_a_cell_added_as_files_runs_end_to_end(copy_with_toys, tmp_path,
                                               workload, metrics):
    result = _run_cell(copy_with_toys, workload, False, tmp_path)
    assert set(result) == RESULT_KEYS
    assert result["correct"] is True and result["failed"] == 0
    assert result["attempted"] >= 1
    assert set(result["metrics"]) == metrics
    for m in result["metrics"].values():
        assert set(m) == {"value", "unit"} and m["value"] > 0
    assert result["device"]["platform"] == "cpu"  # named, never disguised


def test_a_traced_run_reports_an_added_metric_through_an_added_reducer(
        copy_with_toys, tmp_path):
    result = _run_cell(copy_with_toys, "toy-wrn.consensus", True, tmp_path)
    # no device plane in a CPU trace: the trace readers return nothing and
    # their metrics (mix_ms.cons) are left out of the line
    assert set(result) == RESULT_KEYS
    assert set(result["metrics"]) == {"compile_s", "toy_units"}
    assert result["metrics"]["toy_units"] == {"value": 3, "unit": "count"}
    assert result["device"]["busy_s"] is None
    assert result["device"]["window_s"] > 0


def test_the_command_exits_non_zero_without_a_tpu():
    env = dict(os.environ, JAX_PLATFORMS="cpu", PYTHONPATH=REPO)
    proc = subprocess.run(
        [sys.executable, "-m", "chipbench.run", "--workload",
         _names("workloads")[0], "--seed", "1", "--seconds", "1",
         "--trace", "0"],
        cwd=REPO, env=env, timeout=300, capture_output=True, text=True,
    )
    assert proc.returncode != 0
    assert "{" not in proc.stdout  # no result of any kind
    assert "TPU" in proc.stderr


# ---------------------------------------------------------------------- #
# arithmetic                                                             #
# ---------------------------------------------------------------------- #
def test_wrn_flops_against_a_hand_count():
    from chipbench.flops import wrn

    k, px = 10, 32 * 32
    w1, w2, w3 = 16 * k, 32 * k, 64 * k
    stem = px * 9 * 3 * 16
    # stage 1 at 32x32: the first block widens 16 -> 160 with a 1x1 shortcut
    s1 = px * (9 * 16 * w1 + 9 * w1 * w1 + 16 * w1) + 3 * px * 2 * 9 * w1 * w1
    # stages 2, 3: the first 3x3 of the first block still runs at the
    # larger resolution (the stride is on the second 3x3)
    s2 = (px * 9 * w1 * w2 + (px // 4) * (9 * w2 * w2 + w1 * w2)
          + 3 * (px // 4) * 2 * 9 * w2 * w2)
    s3 = ((px // 4) * 9 * w2 * w3 + (px // 16) * (9 * w3 * w3 + w2 * w3)
          + 3 * (px // 16) * 2 * 9 * w3 * w3)
    hand = stem + s1 + s2 + s3 + w3 * 10
    assert wrn.forward_macs(depth=28, widen_factor=10) == hand
    assert abs(hand / 1e9 - 5.951) < 0.001  # GMAC forward per image
    config = _load("configs", "wrn28x10-ring4")
    assert wrn.per_step(config) == 6.0 * hand * 4 * 256


def test_gpt2_small_flops_are_6n_plus_causal_attention():
    from chipbench.flops import transformer_lm as lm

    config = _load("configs", "gpt2-small-ring4")
    model = config["model"]["kwargs"]
    d, L, T, V = 768, 12, 1024, 50257
    assert model["num_heads"] * model["head_dim"] == d
    n = L * 12 * d * d + d * V  # parameters that sit in a matmul
    assert lm.matmul_params(**model) == n == 123_532_032
    assert lm.per_token(seq_len=T, **model) == 6.0 * n + 6.0 * L * T * d
    assert lm.per_step(config) == lm.per_token(seq_len=T, **model) * (
        4 * config["batch"] * T)


def test_the_harness_builds_the_same_metropolis_matrix_by_its_own_means():
    from chipbench import reference
    from distributed_learning_tpu.parallel.topology import Topology

    W = reference.metropolis(reference.adjacency("ring", 4))
    np.testing.assert_allclose(W, Topology.ring(4).metropolis_weights(),
                               atol=1e-15)
    np.testing.assert_allclose(W.sum(0), 1) and np.testing.assert_allclose(
        W[0], [1 / 3, 1 / 3, 0, 1 / 3])
    x = np.arange(8.0, dtype=np.float32).reshape(4, 2)
    err, drift = reference.mixed_error(
        W, 3, {"a": x}, {"a": (W @ W @ W @ x).astype(np.float32)})
    assert err < 1e-6 and drift < 1e-6
    err, _ = reference.mixed_error(W, 2, {"a": x}, {"a": (W @ x).astype(np.float32)})
    assert err > 0.1  # one round is not two
    assert float(reference.max_deviation({"a": x})) == pytest.approx(
        np.sqrt(2 * 3.0 ** 2))


def test_data_is_made_from_the_seed_and_is_one_epoch_large():
    from chipbench.data import cifar_synth, zipf_tokens

    a = cifar_synth.make(2**31 + 5, agents=2, per_agent=6)
    b = cifar_synth.make(2**31 + 5, agents=2, per_agent=6)
    c = cifar_synth.make(2**31 + 6, agents=2, per_agent=6)
    assert a[1][0].shape == (6, 32, 32, 3) and a[1][0].dtype == np.float32
    assert np.array_equal(a[1][0], b[1][0]) and not np.array_equal(
        a[1][0], c[1][0])
    t = zipf_tokens.make(3, agents=2, per_agent=4, vocab_size=50, seq_len=16)
    x, y = t[0]
    assert x.shape == y.shape == (4, 16) and x.dtype == np.int32
    assert np.array_equal(x[:, 1:], y[:, :-1]) and x.max() < 50
    # Zipf: the commonest id is 0, so the unigram entropy is under ln(V)
    assert np.bincount(np.concatenate([x.ravel(), y.ravel()])).argmax() == 0


def test_interval_arithmetic_of_the_trace_module():
    from chipbench.trace import Event, busy_intervals, self_seconds

    events = [
        Event("while", 0, 100), Event("fusion.1", 10, 20),
        Event("fusion.2", 40, 50), Event("copy", 120, 30),
        Event("fusion.1", 160, 10),
    ]
    assert busy_intervals(events) == [(0, 100), (120, 150), (160, 170)]
    got = self_seconds(events)
    assert got == pytest.approx(
        {"while": 30e-9, "fusion.1": 30e-9, "fusion.2": 50e-9, "copy": 30e-9})


# ---------------------------------------------------------------------- #
# the reducers on a recorded trace                                       #
# ---------------------------------------------------------------------- #
RECORDED = os.path.join(HERE, "trace", "toy-wrn.consensus.xplane.pb")


def test_the_reducers_give_known_numbers_on_a_recorded_trace():
    """Three ``mix_until`` calls of the toy consensus cell (WRN-10-1, 4
    agents, 11 rounds each) recorded on one v5e chip by this harness."""
    from chipbench import trace as tr
    from chipbench.reducers import idle, launches, module_time, op_time, peak_share

    trace = tr.load(RECORDED)
    assert list(trace.devices) == ["/device:TPU:0"]
    ops = trace.line(tr.OPS)[0]
    busy_s = sum(e - s for s, e in tr.busy_intervals(ops)) * 1e-9
    with open(os.path.join(BENCH, "peaks.json"), encoding="utf-8") as fh:
        peaks = json.load(fh)["devices"]
    ctx = SimpleNamespace(
        trace=trace, window={"calls": 3, "rounds": 33}, window_s=EXPECT["window_s"],
        busy_s=busy_s, work={"bytes_per_round": 2 * EXPECT["state_bytes"]},
        peaks=peaks, kind="TPU v5 lite", chips=1,
    )
    mix = _load("metrics", "mix_ms.cons")["args"]
    assert launches.reduce(ctx, per="calls") == EXPECT["launches_per_call"]
    assert module_time.reduce(ctx, **mix) == pytest.approx(EXPECT["mix_ms"])
    assert module_time.reduce(ctx, module="^no_such_program", per="rounds") is None
    assert op_time.reduce(ctx, op=EXPECT["op"], per="rounds") == pytest.approx(
        EXPECT["op_ms"])
    assert busy_s == pytest.approx(EXPECT["busy_s"])
    assert idle.reduce(ctx) == pytest.approx(
        100 * (1 - EXPECT["busy_s"] / EXPECT["window_s"]))
    share = peak_share.reduce(
        ctx, **_load("metrics", "mix_hbm_share.cons")["args"])
    assert share == pytest.approx(
        100 * 2 * EXPECT["state_bytes"] / (EXPECT["mix_ms"] * 1e-3) / 819e9)
    ctx.kind = "TPU v9"
    with pytest.raises(KeyError):  # an unknown device is an error, not a default
        peak_share.reduce(ctx, **_load("metrics", "mix_hbm_share.cons")["args"])


#: Read off the recorded trace by hand (``jax.profiler.ProfileData``).
EXPECT = {
    "window_s": 0.020009198,          # the host's clock around the 3 calls
    "state_bytes": 4 * 78186 * 4,     # WRN-10-1: 78,186 parameters, f32
    "launches_per_call": 4.0,         # 3 x jit_wrapped, 9 x convert_element_type
    "mix_ms": 1e3 * 0.000522213 / 33,  # the 3 jit_wrapped events, 33 rounds
    "op": r"^%fusion\.12 ",           # the round's fused 4x4 GEMM, 33 events
    "op_ms": 1e3 * 0.000272459 / 33,
    "busy_s": 0.000518595,            # union of the 591 op intervals
}
