"""FLOPs one training step of the Phi-4-mini-flash-reasoning block stack
requires, from shapes (``TransformerLM`` with a per-layer description of
SambaY's mixers), and the work of its two kinds of kernels alone: the
selective scan and the differential attention's softmax maps.

Per token: 6 FLOPs for each parameter that sits in a matrix product a
token meets: every projection of the mixers (Mamba's ``in_proj``,
``x_proj``, ``dt_proj``, ``out_proj``; attention's query, key, value and
output; the GMU's two; the cross-attention layers' query and output),
each block's SwiGLU and the tied head's columns (the embedding's look-up
counts nothing).  Differential attention's products are counted by their
live (query, key) pairs, ``T (T + 1) / 2`` under the causal mask and
``sum_r min(r + 1, window)`` inside a window, for each of its two maps a
head pair (``num_heads`` maps a layer), a query/key of ``head_dim``
against a value of ``2 head_dim`` (each map weighs the pair's value):

* the step (``per_step``, what ``mfu`` is a share of): forward ``2 Dqk +
  2 Dv`` a pair and map, backward twice that; nothing recomputed counts,
  remat or not;
* the kernels (``flash_flops``, what ``flash_roofline_share`` is a share
  of): the same and the scores once more, which any flash backward forms
  again: forward ``2 Dqk + 2 Dv``, backward ``3 * 2 Dqk + 2 * 2 Dv``.

The kernels' bytes are q, k, v, o, dO, dq, dk, dv once a map head, at the
compute dtype.  The scan is counted as the recurrence requires it,
whatever implements it: per token, channel and state 7 FLOPs forward
(``dt A``, its exponential, the decay, ``(dt x) B``, the sum, ``s C``
and its sum) and three times that with the backward; its bytes are x,
the pre-softplus delta, B and C read and y written forward, the same read
with dy and dx, d delta, dB, dC written backward, in the compute dtype.
The norms, the convolution, SiLU, the softmax itself, the loss and the
optimizer count nothing.
"""

_BYTES = {"bfloat16": 2, "float32": 4}


def _mamba(m):
    """Mamba's sizes (the configuration's ``assumed``): d, d_inner 2d, 16
    states, the rank of dt ceil(d / 16)."""
    d = m["hidden_size"]
    return d, 2 * d, 16, -(-d // 16)


def _kinds(m):
    kinds = [spec["mixer"] for spec in m["layers"]]
    return {k: kinds.count(k) for k in
            ("mamba", "attention", "gmu", "cross_attention")}


def matmul_params(**m) -> float:
    d, Di, N, R = _mamba(m)
    H, Hkv, Dh = m["num_heads"], m["num_kv_heads"], m["head_dim"]
    q, kv = d * H * Dh, d * Hkv * Dh   # k and v are each Hkv * Dh wide
    mixer = {
        "mamba": d * 2 * Di + Di * (R + 2 * N) + R * Di + Di * d,
        "attention": q + 2 * kv + H * Dh * d,
        "gmu": 2 * d * Di,
        "cross_attention": q + H * Dh * d,
    }
    n = _kinds(m)
    return (sum(n[k] * mixer[k] for k in n)
            + len(m["layers"]) * 3 * d * m["dense_width"]
            + d * m["vocab_size"])


def live_pairs(seq_len: int, window=None) -> int:
    """(query, key) pairs the causal mask (and window) leaves alive."""
    if window is None or window >= seq_len:
        return seq_len * (seq_len + 1) // 2
    return window * (window + 1) // 2 + window * (seq_len - window)


def _maps(seq_len: int, **m) -> float:
    """Live (query, key) pairs over all maps of all attention layers."""
    return m["num_heads"] * sum(
        live_pairs(seq_len, spec.get("window")) for spec in m["layers"]
        if spec["mixer"] in ("attention", "cross_attention"))


def attention_flops(seq_len: int, **m) -> float:
    Dqk, Dv = m["head_dim"], 2 * m["head_dim"]
    return 3 * (2.0 * Dqk + 2.0 * Dv) * _maps(seq_len, **m)


def flash_flops(seq_len: int, **m) -> float:
    Dqk, Dv = m["head_dim"], 2 * m["head_dim"]
    return ((2.0 * Dqk + 2.0 * Dv) + (6.0 * Dqk + 4.0 * Dv)) * _maps(
        seq_len, **m)


def flash_bytes(seq_len: int, **m) -> float:
    Dqk, Dv = m["head_dim"], 2 * m["head_dim"]
    layers = sum(spec["mixer"] in ("attention", "cross_attention")
                 for spec in m["layers"])
    return float(layers * seq_len * m["num_heads"] * (4 * Dqk + 4 * Dv)
                 * _BYTES[m.get("dtype", "float32")])


def scan_flops_per_token(**m) -> float:
    _, Di, N, _ = _mamba(m)
    return _kinds(m)["mamba"] * 3 * 7.0 * Di * N


def scan_bytes_per_token(**m) -> float:
    _, Di, N, _ = _mamba(m)
    ins = 2 * Di + 2 * N          # x, delta, B, C
    return _kinds(m)["mamba"] * _BYTES[m.get("dtype", "float32")] * (
        (ins + Di) + (ins + Di) + ins)


def per_token(*, seq_len: int, **m) -> float:
    return (6.0 * matmul_params(**m) + attention_flops(seq_len, **m) / seq_len
            + scan_flops_per_token(**m))


def _tokens(config: dict) -> int:
    return (config["agents"] * config["batch"]
            * config["data"]["kwargs"]["seq_len"])


def per_step(config: dict) -> float:
    T = config["data"]["kwargs"]["seq_len"]
    return per_token(seq_len=T, **config["model"]["kwargs"]) * _tokens(config)


def extra_work(config: dict) -> dict:
    """What the kernels alone require a step: read by
    ``scope_peak_share``."""
    m, T = config["model"]["kwargs"], config["data"]["kwargs"]["seq_len"]
    seqs = config["agents"] * config["batch"]
    return {
        "flash_flops_per_step": flash_flops(T, **m) * seqs,
        "flash_bytes_per_step": flash_bytes(T, **m) * seqs,
        "scan_flops_per_step": scan_flops_per_token(**m) * _tokens(config),
        "scan_bytes_per_step": scan_bytes_per_token(**m) * _tokens(config),
    }
