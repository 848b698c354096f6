"""The reference-carrying training driver (``chipbench/drivers/train_ref.py``),
its reference, FLOP counts and the two reducers it brought, on the CPU at
toy size: the Gated-DeltaNet hybrid cell added as toy files runs end to
end and is held to the benchmark's own reference."""

from __future__ import annotations

import json
import os
import subprocess
import sys
from types import SimpleNamespace

import numpy as np
import pytest

from chipbench import trace as tr
from chipbench.reducers import scope_peak_share, scope_time
from test_chipbench_harness import (  # noqa: F401
    BENCH, REPO, RESULT_KEYS, _load, _run_cell, copy_with_toys,
)

HERE = os.path.dirname(os.path.abspath(__file__))
CELL = "qwen3-next-80b-a3b-pair2.local-sgd-h8"
OLD = os.path.join(HERE, "trace", "toy-wrn.consensus.xplane.pb")


def test_the_toy_hybrid_cell_is_held_to_the_reference(copy_with_toys, tmp_path):
    result = _run_cell(copy_with_toys, "toy-hyb.train", False, tmp_path)
    assert set(result) == RESULT_KEYS
    assert result["correct"] is True and result["failed"] == 0
    assert set(result["metrics"]) == {"tokens_per_s", "setup_s"}


def test_a_traced_toy_run_leaves_out_what_it_cannot_read(copy_with_toys,
                                                         tmp_path):
    result = _run_cell(copy_with_toys, "toy-hyb.train", True, tmp_path)
    assert result["correct"] is True
    # no device plane in a CPU trace: the scope readers find nothing and
    # their metrics are left out, without raising
    assert set(result["metrics"]) == {"compile_s.hyb"}


@pytest.mark.parametrize("fault, limit", [
    ("drop_pair", "moe_token_rel.within"),
    ("no_gate", "attn_token_rel.within"),
    ("lost_chunk", "gdn_token_rel.within"),
    ("half_update", "update_rel.within"),
])
def test_a_fault_in_the_program_fails_the_comparison(copy_with_toys, tmp_path,
                                                     fault, limit):
    """One (token, choice) pair not computed, the attention gate skipped,
    one chunk of the delta rule reading a zero state, or every update
    halved in the trainer's epoch program alone (``faults.py``): the unit
    still runs, the loss still falls; the reference says not correct."""
    code = (
        f"import json, faults; faults.apply({fault!r}); "
        "from chipbench.run import run_cell; "
        "print(json.dumps(run_cell('toy-hyb.train', 2**31 + 11, 0.5, False)))"
    )
    env = dict(os.environ)
    env.update(
        PYTHONPATH=os.pathsep.join([str(copy_with_toys), REPO, HERE]),
        JAX_COMPILATION_CACHE_DIR=str(tmp_path / "jax_cache"),
        JAX_PLATFORMS="cpu",
    )
    proc = subprocess.run([sys.executable, "-c", code], cwd=copy_with_toys,
                          env=env, timeout=600, capture_output=True, text=True)
    assert proc.returncode == 0, proc.stderr[-3000:]
    lines = proc.stdout.strip().splitlines()
    result = json.loads(lines[-1])
    checks = json.loads(next(
        l for l in lines if l.startswith("check: "))[len("check: "):])
    assert result["correct"] is False and result["failed"] == 0
    assert checks[limit] is False
    assert checks["loss_fell"] and checks["routing_flips.within"]
    if fault == "half_update":
        # the comparison's own programs are sound: only what the timed
        # program left behind gives it away
        assert checks["update_rel/attn.q_proj"] == pytest.approx(0.5, abs=0.05)
        assert all(ok for name, ok in checks.items() if name.endswith(
            ".within") and not name.startswith(("update_", "epoch_")))


def test_a_fault_is_taken_out_again():
    import faults
    from distributed_learning_tpu.models import gated_delta
    from distributed_learning_tpu.ops import gated_delta as op

    sound = op.gated_delta_rule
    with faults.applied("lost_chunk"):
        assert op.gated_delta_rule is not sound
        assert gated_delta.gated_delta_rule is op.gated_delta_rule
    assert op.gated_delta_rule is sound
    assert gated_delta.gated_delta_rule is sound
    for name in faults.FAULTS:  # every fault still finds its lines
        faults.apply(name)()


def test_scope_peak_share_is_the_roofline_time_over_the_scope_time():
    with open(os.path.join(BENCH, "peaks.json"), encoding="utf-8") as fh:
        peaks = json.load(fh)["devices"]
    ctx = SimpleNamespace(
        trace=tr.load(OLD), window={"rounds": 33}, xplane=OLD, peaks=peaks,
        kind="TPU v5 lite", chips=1,
        work={"flops": 197e12 * 1e-6, "bytes": 819e9 * 3e-6},
    )
    args = dict(module="^jit_wrapped", scope="while/body", per="rounds")
    ms = scope_time.reduce(ctx, **args)
    # the larger of 1 us of FLOPs and 3 us of bytes, over the scope's time
    got = scope_peak_share.reduce(ctx, flops="flops", bytes="bytes", **args)
    assert got == pytest.approx(100 * 3e-6 / (ms * 1e-3))
    ctx.work["flops"] *= 5
    assert scope_peak_share.reduce(
        ctx, flops="flops", bytes="bytes", **args
    ) == pytest.approx(100 * 5e-6 / (ms * 1e-3))
    # a program without the scope (the parent), or work not given: nothing
    assert scope_peak_share.reduce(
        ctx, flops="flops", bytes="bytes", module="^jit_wrapped",
        scope="gdn_rule", per="rounds") is None
    assert scope_peak_share.reduce(
        ctx, flops="absent", bytes="bytes", **args) is None
    ctx.kind = "TPU v9"
    with pytest.raises(KeyError):
        scope_peak_share.reduce(ctx, flops="flops", bytes="bytes", **args)


def test_qwen3_next_flops_against_a_hand_count():
    from chipbench.flops import qwen3_next as fl

    config = _load("configs", "qwen3-next-80b-a3b-pair2")
    model = config["model"]["kwargs"]
    gdn = 2048 * 12288 + 2048 * 64 + 4096 * 2048
    attn = 2048 * 8192 + 2048 * 1024 + 4096 * 2048
    moe = 2048 * 512 + 3 * 2048 * 512 + 2048 + 10 * 8 / 512 * 3 * 2048 * 512
    head = 2048 * 18992
    assert fl.matmul_params(**model) == 3 * gdn + attn + 4 * moe + head
    rule = 3 * 32 * 3 * 7 * 128 * 128
    assert fl.rule_flops_per_token(**model) == rule
    per_token = 6 * (3 * gdn + attn + 4 * moe + head) + 6 * 4096 * 4096 + rule
    assert fl.per_token(seq_len=4096, **model) == per_token
    assert 1.2e9 < per_token < 1.3e9  # the issue's "about 1.25 GFLOP a token"
    assert fl.per_step(config) == per_token * 2 * 4096
    extra = fl.extra_work(config)
    assert extra["gdn_rule_flops_per_step"] == rule * 8192
    # bf16: q, k of 16 heads, v, o, do of 32, g and beta, forward + backward
    elements = 3 * ((2 * 2048 + 4096 + 64) * 3 + 2 * 4096)
    assert extra["gdn_rule_bytes_per_step"] == 2 * elements * 8192
    # by these counts the rule is bound by bytes: its roofline time a step
    assert extra["gdn_rule_bytes_per_step"] / 819e9 > (
        extra["gdn_rule_flops_per_step"] / 197e12)


def test_the_configuration_states_the_published_widths_and_the_cut():
    config = _load("configs", "qwen3-next-80b-a3b-pair2")
    kw = config["model"]["kwargs"]
    same = {
        "hidden_size": "hidden_size", "head_dim": "head_dim",
        "num_attention_heads": "num_heads", "num_key_value_heads": "num_kv_heads",
        "full_attention_interval": "full_attention_interval",
        "linear_conv_kernel_dim": "linear_conv_kernel",
        "linear_key_head_dim": "linear_key_head_dim",
        "linear_value_head_dim": "linear_value_head_dim",
        "linear_num_key_heads": "linear_num_key_heads",
        "linear_num_value_heads": "linear_num_value_heads",
        "moe_intermediate_size": "expert_width",
        "shared_expert_intermediate_size": "shared_expert_width",
        "num_experts_per_tok": "moe_top_k", "partial_rotary_factor": "rope_fraction",
        "rms_norm_eps": "norm_eps", "rope_theta": "rope_base",
        "num_hidden_layers": "num_layers", "num_experts": "experts_held",
        "vocab_size": "vocab_size", "max_position_embeddings": "max_len",
    }
    for published, ours in same.items():
        assert config[published] == kw[ours], published
    assert config["reduced"] == ["num_hidden_layers", "num_experts", "vocab_size"]
    assert config["published"] == {
        "num_hidden_layers": 48, "num_experts": 512, "vocab_size": 151936}
    assert kw["num_experts"] == 512  # the router keeps its width
    assert kw["vocab_size"] * 8 == 151936 and kw["num_layers"] * 12 == 48
    assert config["data"]["kwargs"]["vocab_size"] == kw["vocab_size"]
    assert not config["tie_word_embeddings"] and not kw["head_bias"]
    cell = _load("workloads", CELL)
    assert cell["driver"] == "train_ref" and cell["chips"] == 1
    assert all(name.endswith(".hyb") for name in cell["per_layer"])


def test_the_benchmarks_reference_is_the_packages_reference():
    """Two files, one mathematics: the copy under ``chipbench/`` and the
    package's give the same logits and the same loss on seeded weights."""
    import jax
    import jax.numpy as jnp

    from chipbench import reference_qwen3_next as ours
    from distributed_learning_tpu.models import TransformerLM
    from distributed_learning_tpu.models.reference import qwen3_next as theirs

    kw = dict(_load("configs", "toy-hyb", os.path.join(HERE, "toy"))[
        "model"]["kwargs"])
    kw.pop("dtype")
    tokens = jax.random.randint(jax.random.key(0), (64,), 0, 64)
    params = TransformerLM(**kw).init(jax.random.key(1), tokens[None])["params"]
    np.testing.assert_array_equal(
        ours.forward(params, tokens, kw, 16), theirs.forward(params, tokens, kw, 16))
    assert float(ours.loss(params, tokens, tokens, kw)) == float(
        theirs.loss(params, tokens, tokens, kw))
    assert jnp.isfinite(ours.loss(params, tokens, tokens, kw))


def test_every_reading_of_the_comparison_has_a_limit_and_a_reason():
    from chipbench.drivers import train_ref

    for name, (limit, reason) in train_ref.LIMITS.items():
        # shares lie under 1; the two counts of flipped tokens do not
        assert 0 < limit and (limit < 1 or name.endswith("_flips")), name
        assert len(reason) > 40, name
    config = _load("configs", "qwen3-next-80b-a3b-pair2")
    shapes = {"layer_0", "layer_1", "layer_3"}
    assert {path[0] for path in train_ref.GRAD_LEAVES.values()} == shapes
    assert config["reference"] == "reference_qwen3_next"
