"""FLOPs one training step of ``TransformerLM`` requires, from shapes.

Per token: 6 FLOPs for each parameter that sits in a matrix
multiplication (per layer ``4 d^2`` of attention projections and
``2 * mlp_ratio * d^2`` of the MLP; the untied ``d x vocab`` output head;
the embedding tables are look-ups and count nothing), plus causal
attention, ``6 L T d``: the scores and the weighted sum are ``4 T d`` per
layer forward and three times that with the backward, and a causal model
needs only the half under the diagonal.  LayerNorm, GELU, softmax, the
loss and the optimizer are not counted, and neither is anything
recomputed (flash attention's backward recomputes the scores).
"""


def matmul_params(*, num_layers: int, num_heads: int, head_dim: int,
                  vocab_size: int, mlp_ratio: int = 4, **_kw) -> int:
    d = num_heads * head_dim
    return num_layers * (4 + 2 * mlp_ratio) * d * d + d * vocab_size


def per_token(*, seq_len: int, **model) -> float:
    d = model["num_heads"] * model["head_dim"]
    return 6.0 * matmul_params(**model) + 6.0 * model["num_layers"] * seq_len * d


def per_step(config: dict) -> float:
    T = config["data"]["kwargs"]["seq_len"]
    tokens = config["agents"] * config["batch"] * T
    return per_token(seq_len=T, **config["model"]["kwargs"]) * tokens
