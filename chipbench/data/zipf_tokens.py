"""Token ids drawn from a Zipf law over the vocabulary (p_k ~ 1/k^s), so
that the loss can fall below ln(vocab_size): one epoch's sequences per
agent, ``T + 1`` ids each (inputs and next-token targets)."""

import numpy as np


def make(seed: int, *, agents: int, per_agent: int, vocab_size: int,
         seq_len: int, exponent: float = 1.1):
    """``{agent: (tokens int32 (m, T), targets int32 (m, T))}``."""
    rng = np.random.default_rng(seed)
    p = 1.0 / np.arange(1, vocab_size + 1) ** exponent
    ids = rng.choice(
        vocab_size, size=(agents, per_agent, seq_len + 1), p=p / p.sum()
    ).astype(np.int32)
    return {a: (ids[a, :, :-1], ids[a, :, 1:]) for a in range(agents)}
