"""Example-driver rot guard: every script in ``examples/`` runs as a real
subprocess (fresh interpreter, public surface only) and its COMPUTED
output is parsed and range-checked — substring-matching static labels
would be vacuous (a lesson learned: the round-1 Titanic guard passed on
the printed anchor text alone).

The reference's notebooks were its examples AND its integration tests
(SURVEY §4); these scripts are ours, so each one gets a guard here, sized
via CLI flags / env knobs to stay test-suite fast.
"""

import json
import os
import re
import signal
import subprocess
import sys
import time

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


def _env(extra=None):
    env = dict(os.environ)
    env["JAX_PLATFORMS"] = "cpu"
    env.pop("XLA_FLAGS", None)
    env["PYTHONPATH"] = REPO  # hermetic: no site hooks
    if extra:
        env.update(extra)
    return env


def _run(module: str, *args: str, timeout: float = 300.0, env_extra=None) -> str:
    out = subprocess.run(
        [sys.executable, "-m", f"examples.{module}", *args],
        cwd=REPO, env=_env(env_extra), capture_output=True, text=True,
        timeout=timeout,
    )
    assert out.returncode == 0, f"{module} failed:\n{out.stdout}\n{out.stderr}"
    return out.stdout


def _float_after(pattern: str, text: str) -> float:
    m = re.search(pattern, text)
    assert m, f"pattern {pattern!r} not found in:\n{text}"
    return float(m.group(1))


def test_pushsum_directed_example():
    out = _run("pushsum_directed")
    assert "push-sum" in out.lower() or "estimate" in out.lower()


def test_titanic_consensus_gd_example():
    out = _run("titanic_consensus_gd")
    acc = _float_after(r"test acc (\d+\.\d+)", out)
    assert 0.70 <= acc <= 0.90, out


def test_choco_compressed_example():
    out = _run("choco_compressed")
    naive = _float_after(r"naive compressed gossip error after \d+ rounds: ([\d.e+-]+)", out)
    choco = _float_after(r"CHOCO error feedback\s+error after \d+ rounds: ([\d.e+-]+)", out)
    # The demo's whole claim: error feedback converges, naive top-k stalls.
    assert choco < 1e-4, out
    assert naive > 100 * choco, out


def test_superstep_local_sgd_example():
    # Bit identity across two differently fused programs needs a CPU
    # that evaluates what was written (tests/conftest.py: no FMA
    # contraction); this child gets no XLA_FLAGS from _env() otherwise.
    out = _run("superstep_local_sgd", env_extra={
        "SLS_EPOCHS": "8", "SLS_K": "4",
        "XLA_FLAGS": "--xla_cpu_max_isa=AVX",
    })
    # The demo's whole claim: fusing K epochs into one dispatch changes
    # NOTHING about the trajectory (the diff is computed, not printed
    # statically) while the wall-clock improves.
    diff = _float_after(r"max \|param diff\| ([\d.e+-]+)", out)
    assert diff == 0.0, out
    speed = _float_after(r"speedup \((\d+\.\d+)x\)", out)
    assert speed > 0.5, out  # timing under CI load: identity is the claim
    acc = _float_after(r"final mean train acc (\d+\.\d+)", out)
    assert 0.3 <= acc <= 1.0, out
    # Lifted config (ISSUE 20): CHOCO + round schedule fuse into the
    # same superstep, still bit-identical.
    choco_diff = _float_after(
        r"choco\+schedule max \|param diff\| ([\d.e+-]+)", out)
    assert choco_diff == 0.0, out
    # Residual-adaptive communication: the controller must shed a
    # nonzero number of gossip rounds AND end inside its residual bar
    # (both counts and residuals are deterministic on the CPU harness).
    m = re.search(r"adaptive rounds saved (\d+) of (\d+)", out)
    assert m, out
    saved, total = int(m.group(1)), int(m.group(2))
    assert 0 < saved < total, out
    res = _float_after(r"adaptive residual ([\d.e+-]+) vs target", out)
    tgt = _float_after(r"vs target ([\d.e+-]+)", out)
    assert res <= tgt, out
    assert "(matched)" in out, out


def test_gradient_tracking_example():
    out = _run("gradient_tracking")
    gossip = _float_after(r"gossip SGD optimality gap after \d+ steps: ([\d.e+-]+)", out)
    dsgt = _float_after(r"DSGT\s+optimality gap after \d+ steps: ([\d.e+-]+)", out)
    extra = _float_after(r"EXTRA\s+optimality gap after \d+ steps: ([\d.e+-]+)", out)
    assert gossip > 1e-2, out          # constant-step gossip is biased
    assert dsgt < gossip / 50, out     # tracking removes the bias
    assert extra < gossip / 50, out    # so does EXTRA


def test_dsgt_titanic_example():
    out = _run("dsgt_titanic")
    cent = _float_after(r"centralized test acc: (\d+\.\d+)", out)
    gossip_gap = _float_after(r"gossip GD : \|w - w_cent\| = ([\d.e+-]+)", out)
    gt_gap = _float_after(r"DSGT      : \|w - w_cent\| = ([\d.e+-]+)", out)
    assert 0.7 <= cent <= 0.9, out
    assert gossip_gap > 1e-2, out
    assert gt_gap < 1e-3, out


def test_fast_averaging_gallery_example():
    out = _run("fast_averaging_gallery")
    g = _float_after(r"gamma=(\d+\.\d+)", out)
    assert abs(g - 2 / 3) < 2e-3, out  # recorded 5-edge optimum
    # Every gallery row must show the SDP beating (or tying) Metropolis.
    rows = re.findall(r"metropolis (\d+\.\d+) -> optimal (\d+\.\d+)", out)
    assert len(rows) >= 5, out
    for met, opt in rows:
        assert float(opt) <= float(met) + 1e-6, out


def test_long_context_lm_example():
    out = _run(
        "long_context_lm", "--seq-len", "512",
        env_extra={"XLA_FLAGS": "--xla_force_host_platform_device_count=8"},
    )
    assert "finite=True" in out, out
    err = _float_after(r"ring vs full attention max err: ([\d.e+-]+)", out)
    assert err < 3e-5, out


def test_cifar_gossip_masternode_example():
    out = _run(
        "cifar_gossip_masternode",
        "--epochs", "1", "--n-train", "768", "--batch-size", "64",
    )
    assert "mixed=True" in out, out
    loss = _float_after(r"mean train loss (\d+\.\d+)", out)
    assert 0.0 < loss < 10.0, out
    acc = _float_after(r"final test acc (\d+\.\d+)", out)
    assert 0.05 <= acc <= 1.0, out


def test_wrn_accuracy_cifar100_proxy_smoke(tmp_path):
    """The cifar100 shape of the accuracy run (the reference's second
    anchor, CIFAR_100_Baseline.ipynb cell 9): 100-class model wiring,
    synthetic-label path, and record naming.  Called in process, at a
    size its command line does not offer, so that regressions surface
    here and not in a paid TPU session."""
    from examples import wrn_accuracy

    out = str(tmp_path / "wrn100.json")
    rec = wrn_accuracy.run(
        proxy=True, epochs=1, n_agents=2, dataset="cifar100",
        n_train=128, n_test=64, out_path=out,
    )
    assert "cifar100" in rec["metric"]
    assert rec["data_source"] == "synthetic-stand-in"
    assert rec["platform"] == "cpu"
    assert 0.0 <= rec["value"] <= 1.0
    with open(out) as f:
        saved = json.load(f)
    assert saved["summary"]["metric"] == rec["metric"]
    assert len(saved["curve"]) == 1


def test_tcp_consensus_example_pair(tmp_path):
    """The master/agent scripts agree on the weighted mean: agents 1..3
    feed 10*e_{i-1} with weights 1, 2, 3 over the path 1-2, 2-3, so every
    agent must print [10/6, 20/6, 30/6] after its rounds.  The run hosts
    the run-wide observability plane (--obs-dir / --obs-period): the
    aggregate stream, merged trace, and straggler profile must come out
    the other end."""
    env = _env()
    obs_dir = str(tmp_path / "obs")
    master = subprocess.Popen(
        [sys.executable, "examples/tcp_consensus/master.py", "--port", "0",
         "--obs-dir", obs_dir],
        cwd=REPO, env=env, stdout=subprocess.PIPE, stderr=subprocess.STDOUT,
        text=True,
    )
    agents = []
    try:
        # Reader thread: a bare readline() would block forever if the
        # master wedges before announcing, hanging the whole suite.
        import queue
        import threading

        lines: "queue.Queue[str]" = queue.Queue()
        threading.Thread(
            target=lambda: [lines.put(l) for l in master.stdout],
            daemon=True,
        ).start()
        deadline = time.time() + 60
        port = None
        while port is None:
            assert master.poll() is None, "master exited early"
            try:
                line = lines.get(timeout=max(0.1, deadline - time.time()))
            except queue.Empty:
                raise AssertionError("master never announced its port")
            m = re.search(r"listening on [\d.]+:(\d+)", line)
            port = m.group(1) if m else None
            assert time.time() < deadline, "master never announced its port"
        for tok in ("1", "2", "3"):
            agents.append(
                subprocess.Popen(
                    [sys.executable, "examples/tcp_consensus/agent.py", tok,
                     "--master-port", port, "--rounds", "2",
                     "--obs-period", "0.2"],
                    cwd=REPO, env=env, stdout=subprocess.PIPE,
                    stderr=subprocess.STDOUT, text=True,
                )
            )
        outs = [a.communicate(timeout=120)[0] for a in agents]
        for tok, out in zip(("1", "2", "3"), outs):
            assert agents[int(tok) - 1].returncode == 0, out
            vals = re.findall(r"round 1: \[([\d.,\s-]+)\]", out)
            assert vals, out
            got = [float(v) for v in vals[-1].split(",")]
            expect = [10 / 6, 20 / 6, 30 / 6]
            assert all(abs(a - b) < 1e-2 for a, b in zip(got, expect)), out
    finally:
        for a in agents:
            if a.poll() is None:
                a.kill()
        master.send_signal(signal.SIGINT)
        try:
            master.wait(timeout=30)
        except subprocess.TimeoutExpired:
            master.kill()
    # The run-wide plane came out the other end: the aggregate stream
    # holds per-agent labeled counters, the merged trace has one track
    # per agent, and the master printed a straggler profile.
    rest = []
    while not lines.empty():
        rest.append(lines.get_nowait())
    master_out = "".join(rest)
    assert "straggler profile" in master_out, master_out
    assert "merged trace" in master_out, master_out
    with open(os.path.join(obs_dir, "aggregate.jsonl")) as fh:
        stream = [json.loads(l) for l in fh if l.strip()]
    merged = [
        e for e in stream
        if e.get("kind") == "event" and e.get("name") == "obs.delta"
    ]
    assert {e["token"] for e in merged} == {"1", "2", "3"}, master_out
    with open(os.path.join(obs_dir, "trace.json")) as fh:
        trace = json.load(fh)
    tracks = {
        e["args"]["name"] for e in trace["traceEvents"] if e["ph"] == "M"
    }
    assert {"agent 1", "agent 2", "agent 3"} <= tracks, tracks


def test_lm_gossip_example():
    out = _run(
        "lm_gossip",
        env_extra={"LMG_EPOCHS": "6", "LMG_SEQS": "32"},
    )
    # Computed-output assert: the per-node accuracies must parse and the
    # short run must beat chance (1/16) decisively; the full-budget run
    # (tests/test_trainer_lm.py) pins the >0.95 knowledge-transfer claim.
    m = re.search(r"acc per node=\[([0-9., ]+)\]", out)
    assert m, out
    accs = [float(v) for v in m.group(1).split(",")]
    assert len(accs) == 4 and min(accs) > 0.12, out


def test_lm_2d_mesh_example():
    out = _run(
        "lm_2d_mesh",
        env_extra={
            "XLA_FLAGS": "--xla_force_host_platform_device_count=8",
            "LM2D_STEPS": "5",
        },
    )
    m = re.search(r"loss (\d+\.\d+) -> (\d+\.\d+)", out)
    assert m, out
    assert float(m.group(2)) < float(m.group(1)), out


def test_lm_generate_example():
    """The generation demo: computed correct-token count must be perfect
    at the full default training budget's smaller test size."""
    out = _run("lm_generate", "--steps", "220", "--gen", "6")
    m = re.search(r"correct_tokens: (\d+)/(\d+)", out)
    assert m, out
    assert int(m.group(1)) == int(m.group(2)) == 6, out
    loss = float(re.search(r"final loss ([\d.]+)", out).group(1))
    assert loss < 0.1, out


def test_parallelism_matrix_example():
    """tp/pp-1F1B/fsdp demos: computed oracle errors must be tiny and
    both training demos must reduce their loss."""
    out = _run("parallelism_matrix", timeout=580.0,
               env_extra={"PM_STEPS": "4"})
    tp_err = float(re.search(r"tp: sharded==unsharded err ([\d.e+-]+)",
                             out).group(1))
    pp_err = float(re.search(r"pp\(1F1B\): grads==autodiff err ([\d.e+-]+)",
                             out).group(1))
    assert tp_err < 1e-4 and pp_err < 1e-4, out
    fracs = [float(m.group(1)) for m in
             re.finditer(r"per-device residency ([\d.]+)", out)]
    assert len(fracs) == 2 and abs(fracs[0] - 1 / 8) < 1e-6 \
        and abs(fracs[1] - 1 / 8) < 1e-6, out
    for m in re.finditer(r"loss ([\d.]+) -> ([\d.]+)", out):
        assert float(m.group(2)) < float(m.group(1)), out
    assert "parallelism matrix ok" in out


def test_lm_pipeline_example():
    """The pipelined-LM demo: trains through a REAL multi-stage mesh
    (the script self-forces 8 virtual devices; the assertion pins it)
    and the merged params generate the progression correctly."""
    out = _run("lm_pipeline", "--steps", "220", "--gen", "6")
    assert "over 4 pipeline stages" in out, out
    m = re.search(r"correct_tokens: (\d+)/(\d+)", out)
    assert m, out
    assert int(m.group(1)) == int(m.group(2)) == 6, out
    loss = float(re.search(r"final loss ([\d.]+)", out).group(1))
    assert loss < 0.1, out


def test_lm_pipeline_interleaved_example():
    """The interleaved-schedule variant of the pipelined-LM demo learns
    the progression too (2 virtual chunks per stage)."""
    out = _run("lm_pipeline", "--schedule", "interleaved",
               "--steps", "220", "--gen", "6")
    m = re.search(r"correct_tokens: (\d+)/(\d+)", out)
    assert m, out
    assert int(m.group(1)) == int(m.group(2)) == 6, out


def test_lm_pipeline_ring_example():
    """pp x sp mode: ring attention inside the pipeline stages on a
    (stage, seq) mesh still learns the progression."""
    out = _run("lm_pipeline", "--attn", "ring",
               "--steps", "220", "--gen", "6", timeout=580.0)
    assert "2 seq shards" in out, out
    m = re.search(r"correct_tokens: (\d+)/(\d+)", out)
    assert m, out
    assert int(m.group(1)) == int(m.group(2)) == 6, out


def test_lm_pipeline_ep_example():
    """pp x ep mode: the MoE LM with expert kernels sharded inside the
    stages learns the progression."""
    out = _run("lm_pipeline", "--ep", "--schedule", "1f1b",
               "--steps", "220", "--gen", "6", timeout=580.0)
    assert "2 expert shards" in out, out
    m = re.search(r"correct_tokens: (\d+)/(\d+)", out)
    assert m, out
    assert int(m.group(1)) == int(m.group(2)) == 6, out


def test_lm_generate_tp_example():
    """--tp decode: the tensor-parallel path must reproduce the
    single-device tokens exactly."""
    out = _run("lm_generate", "--tp", "--steps", "220", "--gen", "6",
               env_extra={"XLA_FLAGS":
                          "--xla_force_host_platform_device_count=8"})
    assert "tp_matches_single_device: True" in out, out


def test_async_gossip_example():
    """ISSUE 8 demo guard: the straggler demo's COMPUTED speedup (async
    fast-agent rounds/sec over lock-step rounds/sec, both timed in the
    script) clears 2x, and the staleness picture comes from the obs
    registry counters, not static labels."""
    out = _run("async_gossip", "--rounds", "10", timeout=240.0)
    speedup = _float_after(r"async speedup: (\d+\.\d+)x", out)
    assert speedup >= 2.0, out
    stale_mixed = _float_after(r"stale-mixed (\d+)", out)
    assert stale_mixed > 0, out
    lock = _float_after(r"lock-step: *(\d+\.\d+) rounds/s", out)
    fast = _float_after(r"async: *(\d+\.\d+) rounds/s", out)
    assert fast > lock, out


def test_byzantine_gossip_example():
    """ISSUE 13 demo guard: the COMPUTED breakdown picture — undefended
    averaging is dragged to the poison scale while the clipped/trimmed
    runs keep honest accuracy, with the redirected-mass detection signal
    (read back from the obs registry) strictly positive."""
    out = _run("byzantine_gossip", "--iters", "120", timeout=300.0)
    rows = {
        m.group(1): (float(m.group(2)), float(m.group(3)), float(m.group(4)))
        for m in re.finditer(
            r"(\w+) +honest test acc ([\d.]+) +param scale ([\d.e+-]+) +"
            r"robust rounds +\d+ +redirected mass +([\d.]+)",
            out,
        )
    }
    assert set(rows) == {"undefended", "clipped", "trimmed"}, out
    un_acc, un_scale, un_mass = rows["undefended"]
    assert un_scale > 100.0, out        # dragged to the poison scale
    assert un_mass == 0.0, out          # plain mix has no detection signal
    for mode in ("clipped", "trimmed"):
        acc, scale, mass = rows[mode]
        assert acc >= 0.70, (mode, out)             # honest accuracy kept
        assert scale < un_scale / 100.0, (mode, out)
        assert mass > 0.0, (mode, out)              # attack was detected


def test_tcp_consensus_async_flags(tmp_path):
    """The --async/--staleness-bound/--deadline-s flags on the
    tcp_consensus example run push-based async rounds end to end: each
    agent's printed vector must conserve mass (row-stochastic mixing:
    every agent's value sums to 10 after any number of rounds) and mix
    toward the mean, and the async round stats are printed."""
    env = _env()
    master = subprocess.Popen(
        [sys.executable, "examples/tcp_consensus/master.py", "--port", "0",
         "--weights", "metropolis"],
        cwd=REPO, env=env, stdout=subprocess.PIPE, stderr=subprocess.STDOUT,
        text=True,
    )
    agents = []
    try:
        import queue
        import threading

        lines: "queue.Queue[str]" = queue.Queue()
        threading.Thread(
            target=lambda: [lines.put(l) for l in master.stdout],
            daemon=True,
        ).start()
        deadline = time.time() + 60
        port = None
        while port is None:
            assert master.poll() is None, "master exited early"
            try:
                line = lines.get(timeout=max(0.1, deadline - time.time()))
            except queue.Empty:
                raise AssertionError("master never announced its port")
            m = re.search(r"listening on [\d.]+:(\d+)", line)
            port = m.group(1) if m else None
            assert time.time() < deadline, "master never announced its port"
        for tok in ("1", "2", "3"):
            agents.append(
                subprocess.Popen(
                    [sys.executable, "examples/tcp_consensus/agent.py", tok,
                     "--master-port", port, "--rounds", "6", "--async",
                     "--staleness-bound", "1", "--deadline-s", "2.0"],
                    cwd=REPO, env=env, stdout=subprocess.PIPE,
                    stderr=subprocess.STDOUT, text=True,
                )
            )
        outs = [a.communicate(timeout=120)[0] for a in agents]
        import numpy as np

        finals = {}
        for tok, out in zip(("1", "2", "3"), outs):
            assert agents[int(tok) - 1].returncode == 0, out
            assert "(stale" in out, out  # async stats printed
            vals = re.findall(r"round 5: \[([\d.,\s-]+)\]", out)
            assert vals, out
            finals[tok] = np.array([float(v) for v in vals[-1].split(",")])
        for tok, v in finals.items():
            # Row-stochastic mixing conserves each agent's mass exactly.
            assert abs(v.sum() - 10.0) < 1e-2, (tok, v)
            # After 6 rounds on the path 1-2-3 every agent has mixed
            # mass from every coordinate (the graph is connected).
            assert (v > 0.05).all(), (tok, v)
    finally:
        for a in agents:
            if a.poll() is None:
                a.kill()
        master.send_signal(signal.SIGINT)
        try:
            master.wait(timeout=30)
        except subprocess.TimeoutExpired:
            master.kill()
