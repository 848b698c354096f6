"""FLOPs one training step of the Qwen3-Next block stack requires, from
shapes (``TransformerLM`` with Gated DeltaNet layers, gated attention and
the held-experts layer), and the work of the gated delta rule alone.

Per token: 6 FLOPs for each parameter that sits in a matrix product a
token meets: every projection of the mixers, the router, the shared
expert and its gate, the head's columns, and for the routed experts the
EXPECTED ``top_k * experts_held / num_experts`` experts a token (what a
uniform router sends here; the counter ``moe.rows_held`` says what this
one did).  Causal attention is ``6 T (heads x head_dim)`` for each full
attention layer (scores and weighted sum, forward and backward, the half
under the diagonal).  The delta rule is counted as the recurrence
requires it, whatever implements it: per token and value head, forward,
``7 Dk Dv`` (the decay ``Dk Dv``, ``S^T k``, the rank-one update and
``S^T q`` ``2 Dk Dv`` each), three times that with the backward.  The
norms, the short convolution, SiLU, softmax, the loss and the optimizer
count nothing, and neither does anything recomputed, remat or not.
"""

_BYTES = {"bfloat16": 2, "float32": 4}


def _layers(num_layers: int, full_attention_interval: int, **_kw):
    full = sum((i + 1) % full_attention_interval == 0 for i in range(num_layers))
    return num_layers - full, full


def matmul_params(**m) -> float:
    d = m["hidden_size"]
    linear, full = _layers(**m)
    kd = m["linear_num_key_heads"] * m["linear_key_head_dim"]
    vd = m["linear_num_value_heads"] * m["linear_value_head_dim"]
    gdn = d * (2 * kd + 2 * vd) + d * 2 * m["linear_num_value_heads"] + vd * d
    heads = m["num_heads"] * m["head_dim"]
    kv = m["num_kv_heads"] * m["head_dim"]
    attn = d * 2 * heads + d * 2 * kv + heads * d
    expert = 3 * d * m["expert_width"]
    moe = (
        d * m["num_experts"]
        + 3 * d * m["shared_expert_width"] + d
        + m["moe_top_k"] * m["experts_held"] / m["num_experts"] * expert
    )
    return (linear * gdn + full * attn + m["num_layers"] * moe
            + d * m["vocab_size"])


def rule_flops_per_token(**m) -> float:
    """The recurrence's FLOPs a token, forward and backward, all linear
    layers: ``3 * 7 Dk Dv`` a value head."""
    linear, _ = _layers(**m)
    return (linear * m["linear_num_value_heads"] * 3 * 7.0
            * m["linear_key_head_dim"] * m["linear_value_head_dim"])


def rule_bytes_per_token(**m) -> float:
    """The bytes the rule must move a token, all linear layers, at the
    compute dtype: forward reads q, k (key heads), v, g, beta and writes
    o; backward reads them and ``do`` and writes their five gradients."""
    linear, _ = _layers(**m)
    qk = 2 * m["linear_num_key_heads"] * m["linear_key_head_dim"]
    v = m["linear_num_value_heads"] * m["linear_value_head_dim"]
    inputs = qk + v + 2 * m["linear_num_value_heads"]
    return linear * _BYTES[m.get("dtype", "float32")] * (
        (inputs + v) + (inputs + v) + inputs
    )


def per_token(*, seq_len: int, **m) -> float:
    _, full = _layers(**m)
    return (6.0 * matmul_params(**m)
            + 6.0 * full * seq_len * m["num_heads"] * m["head_dim"]
            + rule_flops_per_token(**m))


def _tokens(config: dict) -> int:
    return (config["agents"] * config["batch"]
            * config["data"]["kwargs"]["seq_len"])


def per_step(config: dict) -> float:
    T = config["data"]["kwargs"]["seq_len"]
    return per_token(seq_len=T, **config["model"]["kwargs"]) * _tokens(config)


def extra_work(config: dict) -> dict:
    """What the rule alone requires a step: read by ``scope_peak_share``."""
    m = config["model"]["kwargs"]
    return {
        "gdn_rule_flops_per_step": rule_flops_per_token(**m) * _tokens(config),
        "gdn_rule_bytes_per_step": rule_bytes_per_token(**m) * _tokens(config),
    }
