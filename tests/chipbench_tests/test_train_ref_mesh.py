"""The mesh-aware reference-carrying driver
(``chipbench/drivers/train_ref_mesh.py``), its reference, FLOP counts and
the reducer it brought, on the CPU at toy size: the latent-attention cell
added as toy files runs end to end on four virtual devices, one agent
each, is held to the benchmark's own reference, and fails every control
of ``faults_kanana2.py``."""

from __future__ import annotations

import json
import os
import subprocess
import sys
from types import SimpleNamespace

import numpy as np
import pytest

from chipbench import trace as tr
from chipbench.drivers import train_ref_mesh as trm
from chipbench.flops import kanana2 as flops
from chipbench.reducers import op_union_time
from test_chipbench_harness import (  # noqa: F401
    BENCH, REPO, RESULT_KEYS, _run_cell, copy_with_toys,
)

HERE = os.path.dirname(os.path.abspath(__file__))
CELL = "kanana-2-30b-a3b-ring4.local-sgd-h8"
MLA_METRICS = [
    "compile_s.mla", "launches_per_epoch.mla", "epoch_program_ms.mla",
    "mfu.mla", "mix_ms.mla", "collective_ms.mla", "device_idle.mla",
    "mla_ms.mla", "flash_ms.mla", "flash_roofline_share.mla",
    "moe_route_ms.mla", "moe_experts_ms.mla", "moe_bias_ms.mla",
]


def _load(kind, name):
    with open(os.path.join(BENCH, kind, name + ".json"), encoding="utf-8") as fh:
        return json.load(fh)


@pytest.mark.parametrize("workload", ["toy-mla.train-mesh", "toy-mla.train"])
def test_the_toy_latent_cell_is_held_to_the_reference(copy_with_toys, tmp_path,
                                                      workload):
    """Sharded: four virtual devices stand for the four chips, the step,
    the mix and every comparison one agent a device.  Dense: the same
    driver with the agents stacked (they take turns)."""
    result = _run_cell(copy_with_toys, workload, False, tmp_path)
    assert set(result) == RESULT_KEYS
    assert result["correct"] is True and result["failed"] == 0
    assert set(result["metrics"]) == {"tokens_per_s", "setup_s"}


def test_a_traced_toy_run_leaves_out_what_it_cannot_read(copy_with_toys,
                                                         tmp_path):
    result = _run_cell(copy_with_toys, "toy-mla.train-mesh", True, tmp_path)
    assert result["correct"] is True
    # no device plane in a CPU trace: the scope, roofline and collective
    # readers find nothing and their metrics are left out, without raising
    assert set(result["metrics"]) == {"compile_s.mla"}


#: control -> a number it has to fail at toy size (f32 on both sides)
CONTROLS = {
    "no_rope_k": "mla_k_rel", "no_latent_norm": "mla_k_rel",
    "k_pe_per_head": "mla_k_rel", "scale_nope": "mla_rel",
    "no_route_scale": "probe_gate_rel", "bias_ignored": "routing_flips",
    "bias_in_weights": "probe_gate_rel", "drop_pair": "probe_moe_token_rel",
    "bf16_params": "router_logit_rel", "half_update": "update_rel",
    "no_bias_update": "bias_abs",
    # the gossip round: left out, and under a wrong W; each as a unit of
    # the trainer and as the host derives it from the sound unit
    "skip_mix": "mix_rel", "skip_mix:derived": "mix_rel",
    "lazy_w": "mix_rel", "lazy_w:derived": "mix_rel",
}


@pytest.fixture(scope="module")
def swept(copy_with_toys, tmp_path_factory):
    """One run of the toy cell, then every control on the state it left:
    ``{name: (failed kinds, readings)}`` from the ``fault`` lines."""
    code = (
        "import json, faults_kanana2 as f; f.sweep(f.NAMES); "
        "from chipbench.run import run_cell; "
        "print(json.dumps(run_cell('toy-mla.train-mesh', 2**31 + 11, 0.5, False)))"
    )
    env = dict(os.environ)
    env.update(
        PYTHONPATH=os.pathsep.join([str(copy_with_toys), REPO, HERE]),
        JAX_COMPILATION_CACHE_DIR=str(
            tmp_path_factory.mktemp("sweep") / "jax_cache"),
        JAX_PLATFORMS="cpu",
    )
    proc = subprocess.run([sys.executable, "-c", code], cwd=copy_with_toys,
                          env=env, timeout=1200, capture_output=True, text=True)
    assert proc.returncode == 0, proc.stderr[-3000:]
    lines = proc.stdout.strip().splitlines()
    assert json.loads(lines[-1])["correct"] is True  # the sound cell's own
    out = {}
    for line in lines:
        if line.startswith("fault "):
            name, rest = line[len("fault "):].split(": fails ", 1)
            failed, readings = rest.split(": ", 1)
            out[name] = (json.loads(failed.replace("'", '"')),
                         json.loads(readings))
    return out


def test_the_sound_program_passes_again_in_the_sweep(swept):
    assert swept["sound"][0] == []


@pytest.mark.parametrize("control", sorted(CONTROLS))
def test_a_control_fails_the_comparison(swept, control):
    failed, _readings = swept[control]
    assert CONTROLS[control] in failed, failed


def test_only_the_timed_program_gives_a_unit_fault_away(swept):
    _failed, readings = swept["half_update"]
    # only readings of the unit are taken again, and every picked group's
    # change is half the replay's
    assert not any(name.startswith(("mla_", "moe_", "grad_")) for name in readings)
    # (Adam at the toy's learning rate leaves the halved run on another
    # path after the first step: a half, give or take)
    assert 0.3 < readings["update_rel/mla.q_proj"] < 0.8
    _failed, readings = swept["no_bias_update"]
    # four steps of gamma not taken, on nearly every expert
    assert 0.002 < readings["bias_abs"] <= 0.0041


def test_every_control_still_finds_its_lines():
    import faults_kanana2 as faults
    from distributed_learning_tpu.models import transformer

    sound = transformer._LatentAttention
    with faults.applied("scale_nope"):
        assert transformer._LatentAttention is not sound
    assert transformer._LatentAttention is sound
    for name in [*faults.FAULTS, faults.ROUNDED]:
        faults.apply(name)()
    assert set(CONTROLS) == set(faults.NAMES)


@pytest.mark.parametrize("name", ["skip_mix", "lazy_w"])
def test_a_derived_mix_fault_reads_what_the_trainers_unit_reads(swept, name):
    """``V W^-1`` of what the sound unit left is what a unit under ``V``
    leaves: the two forms of a control of the gossip round agree, so the
    derived one (no unit, no chip time) can stand for the other."""
    real, derived = swept[name][1], swept[name + ":derived"][1]
    assert set(real) == set(derived)
    for key in real:
        if key.startswith(("update_rel", "mix_rel")):
            assert derived[key] == pytest.approx(real[key], rel=1e-3, abs=1e-4), key
    # a round left out leaves every disagreeing mode at three times (ring-4
    # Metropolis: eigenvalues 1/3, 1/3, -1/3) what the replay has
    if name == "skip_mix":
        assert all(1.9 < v < 4.1 for k, v in real.items()
                   if k.startswith("mix_rel"))


def test_the_two_copies_of_the_reference_are_one_text():
    text = lambda path: open(os.path.join(REPO, path), encoding="utf-8").read()
    ours = text("chipbench/reference_kanana2.py")
    theirs = text("distributed_learning_tpu/models/reference/kanana2.py")
    cut = "The oracle for"
    assert ours[ours.index(cut):] == theirs[theirs.index(cut):]


# ---------------------------------------------------------------------- #
# the rules of the limits                                                #
# ---------------------------------------------------------------------- #
def test_a_reading_under_its_floor_is_absolute_against_a_stated_limit():
    floor = trm.FLOORS["grad"]
    assert trm.reading_of("grad_rel/embed", 1e-3, 1e-2) == (
        "grad_rel/embed", pytest.approx(0.1))
    # a reference norm that vanishes: no division, another name, and the
    # limit the relative one times the floor
    name, value = trm.reading_of("grad_rel/embed", 1e-9, floor / 10)
    assert (name, value) == ("grad_abs/embed", 1e-9)
    assert trm.limit_of(name) == pytest.approx(
        trm.LIMITS["grad_rel"][0] * floor)
    assert trm.reading_of("grad_rel/embed", 0.0, 0.0) == ("grad_abs/embed", 0.0)
    # kinds without a floor stay relative; a count or an absolute passes
    assert trm.reading_of("mla_rel/layer_0", 1.0, 4.0)[1] == 0.25
    assert trm.reading_of("loss_abs", 0.5, 1.0) == ("loss_abs", 0.5)


def test_a_non_finite_reading_fails_only_where_the_reference_is_finite():
    assert trm.reading_of("mla_rel/layer_0", float("nan"), 1.0)[1] == float("inf")
    assert trm.reading_of("mla_rel/layer_0", float("nan"), float("nan"))[1] is None
    readings = {name: 0.0 for name in trm.LIMITS}
    assert all(trm.verdicts(readings).values())
    assert trm.verdicts({**readings, "mla_rel": None})["mla_rel.within"]
    assert not trm.verdicts({**readings, "mla_rel": float("inf")})["mla_rel.within"]
    # a limit nothing was read against is a failure, not a pass
    del readings["bias_abs"]
    assert not trm.verdicts(readings)["bias_abs.within"]
    # unless it was read under its floor, as an absolute (no token chose a
    # held expert: two of the builder's six chip runs)
    del readings["gate_rel"]
    assert "gate_rel.within" not in trm.verdicts(
        {**readings, "bias_abs": 0.0, "gate_abs": 0.0})
    assert trm.verdicts({**readings, "gate_abs": 0.0})["gate_abs.within"]
    assert not trm.verdicts({**readings, "gate_abs": 1e-3})["gate_abs.within"]


def test_only_the_routers_own_leaves_decide_nothing():
    """The routers' gradient, update and disagreement are printed and held
    to no limit; the held experts' leaves are held to every one (the
    replay takes the program's choices step by step)."""
    for kind in ("grad_rel", "update_rel", "mix_rel"):
        assert trm._named(kind, "moe.experts_down") == f"{kind}/moe.experts_down"
        assert trm.limit_of(f"{kind}/moe.experts_down") == trm.LIMITS[kind][0]
        assert trm._named(kind, "moe.router") == f"router_{kind}"
        assert trm.limit_of(f"router_{kind}") is None
    # under what every update halved reads (0.47-0.50 on the chip)
    assert trm.LIMITS["update_rel"][0] < 0.47
    # under what a wrong W reads at the least (1) and a round left out (2)
    assert trm.LIMITS["mix_rel"][0] < 1


def test_readings_take_the_worst_agent_and_print_beside_their_limits():
    pairs = {"mla_rel/layer_0": np.array([[1.0, 100.0], [3.0, 100.0]]),
             "routing_flips/layer_1": np.array([0, 2]),
             "router_grad_rel": np.array([[1.0, 2.0], [1.0, 4.0]])}
    got = trm.readings_from(pairs)
    assert got == {"mla_rel/layer_0": 0.03, "routing_flips/layer_1": 2.0,
                   "router_grad_rel": 0.5}
    beside = trm.beside_limits(got)
    assert "router_grad_rel" not in beside  # printed with the checks only
    assert beside["mla_rel/layer_0"] == {
        "read": 0.03, "limit": trm.LIMITS["mla_rel"][0]}


def test_every_limit_states_its_readings():
    for name, (limit, reason) in trm.LIMITS.items():
        assert limit > 0 and len(reason) > 20, name


# ---------------------------------------------------------------------- #
# the FLOP counts, by hand                                               #
# ---------------------------------------------------------------------- #
def test_flash_work_at_two_widths_by_hand():
    m = dict(_load("configs", "kanana-2-30b-a3b-ring4")["model"]["kwargs"])
    T = 8192
    pairs = T * (T + 1) // 2
    assert flops.live_pairs(T) == pairs == 33_558_528
    # a live pair and head: forward 2*192 + 2*128, backward 3*2*192 + 2*2*128
    assert 2 * 192 + 2 * 128 + 6 * 192 + 4 * 128 == 2304
    assert flops.flash_flops(T, **m) == 2304.0 * 32 * 5 * pairs
    # what the step requires leaves the recomputed scores out
    assert flops.attention_flops(T, **m) == 1920.0 * 32 * 5 * pairs
    # q, k, dq, dk at 192 and v, o, dO, dv at 128, once each, in bf16
    assert flops.flash_bytes(T, **m) == 5 * T * 32 * (4 * 192 + 4 * 128) * 2


def test_step_flops_by_hand():
    config = _load("configs", "kanana-2-30b-a3b-ring4")
    m = config["model"]["kwargs"]
    attn = 2048 * 32 * 192 + 2048 * 576 + 512 * 32 * 256 + 32 * 128 * 2048
    assert attn == 26_345_984 - 512  # the tree's, less the latent's norm
    moe = 2048 * 128 + 3 * 2048 * 1536 + 6 * 8 / 128 * 3 * 2048 * 768
    want = 5 * attn + 3 * 2048 * 6144 + 4 * moe + 2048 * 16032
    assert flops.matmul_params(**m) == want
    per_seq = 6.0 * want * 8192 + flops.attention_flops(8192, **m)
    assert flops.per_step(config) == 4 * per_seq
    work = flops.extra_work(config)
    assert work["flash_flops_per_step"] == 4 * flops.flash_flops(8192, **m)
    assert work["flash_bytes_per_step"] == 4 * flops.flash_bytes(8192, **m)


# ---------------------------------------------------------------------- #
# the benchmark's files                                                  #
# ---------------------------------------------------------------------- #
def test_the_cell_and_its_metrics_are_declared():
    cell = _load("workloads", CELL)
    assert cell["chips"] == 4 and cell["driver"] == "train_ref_mesh"
    assert cell["traffic"] == {
        "epoch_len": 8, "superstep": 1, "mix_times": 1, "mix_eps": None,
        "compression": None, "layout": "sharded"}
    assert cell["per_layer"] == MLA_METRICS
    with open(os.path.join(REPO, "BENCHMARK.json"), encoding="utf-8") as fh:
        bench = json.load(fh)
    assert bench["workloads"][-1]["name"] == CELL
    assert sum(w["chips"] == 4 for w in bench["workloads"]) == 1
    declared = {m["name"]: m for m in bench["per_layer"]}
    for name in MLA_METRICS:
        m = _load("metrics", name)
        assert declared[name]["workloads"] == [CELL]
        assert {k: m[k] for k in ("unit", "better", "source", "layer", "moves")} == {
            k: declared[name][k] for k in ("unit", "better", "source", "layer", "moves")}
    tokens, = [m for m in bench["end_to_end"] if m["name"] == "tokens_per_s"]
    assert tokens["workloads"][-1] == CELL


def test_the_configuration_keeps_every_published_number():
    config = _load("configs", "kanana-2-30b-a3b-ring4")
    published = {
        "attention_bias": False, "first_k_dense_replace": 1, "head_dim": 64,
        "hidden_act": "silu", "hidden_size": 2048, "intermediate_size": 6144,
        "kv_lora_rank": 512, "max_position_embeddings": 32768,
        "model_type": "deepseek_v3", "moe_intermediate_size": 768,
        "moe_layer_freq": 1, "n_group": 1, "n_routed_experts": 128,
        "n_shared_experts": 2, "norm_topk_prob": True,
        "num_attention_heads": 32, "num_experts_per_tok": 6,
        "num_hidden_layers": 48, "num_key_value_heads": 32,
        "q_lora_rank": None, "qk_head_dim": 192, "qk_nope_head_dim": 128,
        "qk_rope_head_dim": 64, "rms_norm_eps": 1e-06, "rope_interleave": True,
        "rope_scaling": None, "rope_theta": 1000000,
        "routed_scaling_factor": 2.448, "scoring_func": "sigmoid",
        "tie_word_embeddings": False, "topk_group": 1,
        "topk_method": "noaux_tc", "v_head_dim": 128, "vocab_size": 128256,
    }
    assert config["reduced"] == ["num_hidden_layers", "n_routed_experts",
                                 "vocab_size"]
    for key, value in published.items():
        if key in config["reduced"]:
            assert config["published"][key] == value
        else:
            assert config[key] == value, key
    m = config["model"]["kwargs"]
    assert (m["num_layers"], m["experts_held"], m["vocab_size"]) == (
        config["num_hidden_layers"], config["n_routed_experts"],
        config["vocab_size"]) == (5, 8, 16032)
    # every width as published, the router's too
    assert (m["hidden_size"], m["num_heads"], m["kv_lora_rank"],
            m["qk_nope_head_dim"], m["qk_rope_head_dim"], m["v_head_dim"],
            m["dense_width"], m["expert_width"], m["shared_expert_width"],
            m["num_experts"], m["moe_top_k"], m["route_scale"]) == (
        2048, 32, 512, 128, 64, 128, 6144, 768, 2 * 768, 128, 6, 2.448)
    assert "424,960,512" in config["deployment"]


# ---------------------------------------------------------------------- #
# the union of spans                                                     #
# ---------------------------------------------------------------------- #
def test_messages_in_flight_side_by_side_count_their_time_once():
    E = tr.Event
    events = [E("%collective-permute-start.1", 0.0, 4e6),
              E("%collective-permute-start.2", 1e6, 4e6),   # overlaps the first
              E("%copy-start.7", 2e6, 20e6),                # another op
              E("%collective-permute-start.3", 10e6, 1e6)]
    plane = {tr.ASYNC_OPS: events}
    ctx = SimpleNamespace(trace=tr.Trace({"/device:TPU:0": plane,
                                          "/device:TPU:1": plane}),
                          window={"gossips": 2})
    got = op_union_time.reduce(ctx, op="^%?collective-permute", per="gossips")
    assert got == pytest.approx((5.0 + 1.0) / 2)  # ms a gossip, not 9 / 2
    assert op_union_time.reduce(ctx, op="^%?all-reduce", per="gossips") is None
    assert op_union_time.reduce(ctx, op="^%?collective", per="rounds") is None
