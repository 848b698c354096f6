"""CIFAR-shaped synthetic images, the benchmark's copy of the program's
stand-in (``distributed_learning_tpu/data/cifar.py::synthetic_cifar``):
each class is a smooth colour prototype plus noise, so a model can learn
it.  Made from the seed, normalised with CIFAR-10's mean and std, and
only as many as one epoch uses: ``agents * batch * epoch_len``."""

import numpy as np

MEAN = np.array([0.4914, 0.4822, 0.4465], np.float32)
STD = np.array([0.2470, 0.2435, 0.2616], np.float32)


def make(seed: int, *, agents: int, per_agent: int, num_classes: int = 10):
    """``{agent: (X float32 (m, 32, 32, 3), y int32 (m,))}``."""
    rng = np.random.default_rng(seed)
    yy, xx = np.mgrid[0:32, 0:32].astype(np.float32) / 32.0
    protos = []
    for c in range(num_classes):
        phase = 2 * np.pi * c / num_classes
        protos.append(np.stack([
            0.5 + 0.4 * np.sin(2 * np.pi * (xx * (1 + c % 4)) + phase),
            0.5 + 0.4 * np.cos(2 * np.pi * (yy * (1 + c % 3)) + phase),
            0.5 + 0.4 * np.sin(2 * np.pi * (xx + yy) * (1 + c % 5) + phase),
        ], axis=-1))
    protos = np.stack(protos).astype(np.float32)
    n = agents * per_agent
    y = rng.integers(0, num_classes, size=n).astype(np.int32)
    x = protos[y] + rng.normal(0, 0.18, size=(n, 32, 32, 3)).astype(np.float32)
    x = np.clip(x, 0, 1)
    x = (np.round(x * 255) / 255 - MEAN) / STD  # through uint8, as CIFAR is
    x = x.astype(np.float32).reshape(agents, per_agent, 32, 32, 3)
    y = y.reshape(agents, per_agent)
    return {a: (x[a], y[a]) for a in range(agents)}
