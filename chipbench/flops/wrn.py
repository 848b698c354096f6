"""FLOPs one training step of a Wide ResNet requires, from shapes alone.

Multiply-accumulates of every convolution and of the classifier in the
forward pass (``models/vision.py::WideResNet``: a 3x3 stem, three stages
of ``(depth - 4) / 6`` pre-activation blocks at widths 16k, 32k, 64k, the
stride on each block's second 3x3, a 1x1 shortcut where shape changes);
the backward pass costs twice the forward, so a sample is 6 FLOPs per
forward MAC.  BatchNorm, ReLU, dropout and the optimizer are not counted,
and neither is anything recomputed.
"""


def forward_macs(*, depth: int, widen_factor: int, num_classes: int = 10,
                 image: int = 32, channels: int = 3, **_kw) -> int:
    n = (depth - 4) // 6
    macs = image * image * 9 * channels * 16
    c_in, size = 16, image
    for stage, c in enumerate((16 * widen_factor, 32 * widen_factor,
                               64 * widen_factor)):
        for b in range(n):
            stride = 2 if (stage > 0 and b == 0) else 1
            out = size // stride
            macs += size * size * 9 * c_in * c      # first 3x3, stride 1
            macs += out * out * 9 * c * c           # second 3x3, strided
            if c_in != c or stride != 1:
                macs += out * out * c_in * c        # 1x1 shortcut
            c_in, size = c, out
    return macs + c_in * num_classes


def per_step(config: dict) -> float:
    samples = config["agents"] * config["batch"]
    return 6.0 * forward_macs(**config["model"]["kwargs"]) * samples
