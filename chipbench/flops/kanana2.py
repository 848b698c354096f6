"""FLOPs one training step of the kanana-2-30b-a3b block stack requires,
from shapes (``TransformerLM`` with latent attention, a leading dense
layer and the sigmoid-routed held-experts layer), and the work of the
attention products alone at their two widths (what the flash kernels
implement).

Per token: 6 FLOPs for each parameter that sits in a matrix product a
token meets: latent attention's four projections, the dense layer's
SwiGLU, the router, the shared expert, the head's columns, and for the
routed experts the EXPECTED ``top_k * experts_held / num_experts``
experts a token (what a uniform router sends here; the counter
``moe.rows_held`` says what this one did).  Attention's products are
counted by their live (query, key) pairs, ``T (T + 1) / 2`` under the
causal mask, a query/key of ``Dqk = qk_nope + qk_rope`` against a value
of ``Dv``, per pair and head:

* the step (``per_step``, what ``mfu`` is a share of): forward ``2 Dqk +
  2 Dv`` (a score, a weighted value), backward twice that (dV and dP at
  ``Dv``, dQ and dK at ``Dqk``): ``6 Dqk + 6 Dv`` = 1,920 at 192 / 128.
  Nothing recomputed counts, remat or not;
* the kernels (``flash_flops``, what ``flash_roofline_share`` is a share
  of): the same and the scores once more, which the backward of any
  flash algorithm has to form again because the (T, T) matrix is never
  kept: forward ``2 Dqk + 2 Dv``, backward ``3 * 2 Dqk + 2 * 2 Dv``:
  2,304 at 192 / 128.

The kernels' bytes are q, k, v, o, dO, dq, dk, dv once, at the compute
dtype.  The norms, rotary, SiLU, the sigmoids, softmax, the loss, the
bias and the optimizer count nothing.
"""

_BYTES = {"bfloat16": 2, "float32": 4}


def live_pairs(seq_len: int) -> int:
    """(query, key) pairs the causal mask leaves alive."""
    return seq_len * (seq_len + 1) // 2


def _widths(**m):
    return m["qk_nope_head_dim"] + m["qk_rope_head_dim"], m["v_head_dim"]


def matmul_params(**m) -> float:
    d, L, H = m["hidden_size"], m["num_layers"], m["num_heads"]
    dense = m.get("num_dense_layers", 0)
    dqk, dv = _widths(**m)
    R, Dn, Dr = m["kv_lora_rank"], m["qk_nope_head_dim"], m["qk_rope_head_dim"]
    attn = d * H * dqk + d * (R + Dr) + R * H * (Dn + dv) + H * dv * d
    expert = 3 * d * m["expert_width"]
    moe = (
        d * m["num_experts"] + 3 * d * m["shared_expert_width"]
        + m["moe_top_k"] * m["experts_held"] / m["num_experts"] * expert
    )
    return (L * attn + dense * 3 * d * m["dense_width"] + (L - dense) * moe
            + d * m["vocab_size"])


def attention_flops(seq_len: int, **m) -> float:
    """The attention products of one sequence the step requires, forward
    and backward, all layers."""
    dqk, dv = _widths(**m)
    return (6.0 * dqk + 6.0 * dv) * m["num_heads"] * m["num_layers"] * (
        live_pairs(seq_len))


def flash_flops(seq_len: int, **m) -> float:
    """What the flash kernels have to execute for one sequence, all
    layers: :func:`attention_flops` and the scores formed again."""
    dqk, dv = _widths(**m)
    return ((2.0 * dqk + 2.0 * dv) + (6.0 * dqk + 4.0 * dv)) * (
        m["num_heads"] * m["num_layers"] * live_pairs(seq_len))


def flash_bytes(seq_len: int, **m) -> float:
    """The bytes the kernels must move for one sequence, all layers: q,
    k, dq, dk at the query/key width and v, o, dO, dv at the value's,
    once each."""
    dqk, dv = _widths(**m)
    return float(m["num_layers"] * seq_len * m["num_heads"]
                 * (4 * dqk + 4 * dv) * _BYTES[m.get("dtype", "float32")])


def per_sequence(*, seq_len: int, **m) -> float:
    return 6.0 * matmul_params(**m) * seq_len + attention_flops(seq_len, **m)


def _sequences(config: dict) -> int:
    return config["agents"] * config["batch"]


def per_step(config: dict) -> float:
    T = config["data"]["kwargs"]["seq_len"]
    return (per_sequence(seq_len=T, **config["model"]["kwargs"])
            * _sequences(config))


def extra_work(config: dict) -> dict:
    """What the flash kernels alone require a step, all agents: read by
    ``scope_peak_share`` over the kernels' names."""
    m, T = config["model"]["kwargs"], config["data"]["kwargs"]["seq_len"]
    return {
        "flash_flops_per_step": flash_flops(T, **m) * _sequences(config),
        "flash_bytes_per_step": flash_bytes(T, **m) * _sequences(config),
    }
