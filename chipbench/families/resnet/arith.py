"""Operations and bytes of the bottleneck-ResNet family, from shapes.

The convention is the usual one for model FLOP/s utilization: a
multiply-add is 2 operations, convolutions and the classifier count,
BatchNorm, ReLU, pooling and the optimizer do not, and a training step
costs three forward passes (forward, input-gradient, weight-gradient).
Recomputed operations never count.
"""

from __future__ import annotations

from .reference import conv_plan


def forward_flops_per_image(cfg: dict) -> int:
    flops = sum(2 * c["k"] ** 2 * c["cin"] * c["cout"] * c["hout"] ** 2
                for c in conv_plan(cfg))
    feat = cfg["stem_width"] * 8 * cfg["expansion"]
    return flops + 2 * feat * cfg["num_classes"]


def train_flops_per_image(cfg: dict) -> int:
    return 3 * forward_flops_per_image(cfg)


def conv_passes(cfg: dict, batch: int, act_bytes: int = 2,
                weight_bytes: int = 2) -> list[dict]:
    """Every convolution's three passes for ``batch`` rows on one chip:
    operations, and the bytes the algorithm has to move (each operand
    read once, the result written once) at the configuration's compute
    type."""
    out = []
    for c in conv_plan(cfg):
        x = batch * c["hin"] ** 2 * c["cin"] * act_bytes
        y = batch * c["hout"] ** 2 * c["cout"] * act_bytes
        w = c["k"] ** 2 * c["cin"] * c["cout"] * weight_bytes
        flops = 2 * c["k"] ** 2 * c["cin"] * c["cout"] \
            * c["hout"] ** 2 * batch
        for name, nbytes in (("forward", x + w + y),
                             ("input_grad", y + w + x),
                             ("weight_grad", x + y + w)):
            if name == "input_grad" and c["path"] == "conv1":
                continue  # no gradient flows to the image
            out.append({"conv": c["path"], "pass": name, "flops": flops,
                        "bytes": nbytes})
    return out


def conv_roofline_seconds(cfg: dict, batch: int, peak: dict) -> dict:
    """The least time one chip could take for all convolution passes of
    a step, and which bound holds it: per pass the larger of operations
    over peak FLOP/s and bytes over peak bytes/s."""
    total = by_flops = by_bytes = 0.0
    for p in conv_passes(cfg, batch):
        tf = p["flops"] / peak["bf16_flops_per_s"]
        tb = p["bytes"] / peak["hbm_bytes_per_s"]
        total += max(tf, tb)
        if tf >= tb:
            by_flops += tf
        else:
            by_bytes += tb
    return {"seconds": total, "compute_bound_s": by_flops,
            "bandwidth_bound_s": by_bytes}
