"""Tests only: FLOPs a step of the toy MLP requires (6 per weight per sample)."""


def per_step(config: dict) -> float:
    kw = config["model"]["kwargs"]
    h, out = kw["hidden_dim"], kw["output_dim"]
    inputs = 1
    for d in config["model"]["input_shape"]:
        inputs *= d
    weights = inputs * h + 2 * h * h + h * out
    return 6.0 * weights * config["agents"] * config["batch"]
