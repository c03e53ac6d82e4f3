"""What the harness has to know of the program for this family: how a
configuration is handed to it (its sizes and recipe as the flags
``python -m imagent_tpu`` takes), and how the first gradient is read
back out of its optimizer (SGD with momentum and coupled weight decay:
an optax chain whose ``trace`` is the momentum)."""

from __future__ import annotations


def engine_flags(cfg: dict) -> list[str]:
    if cfg["compute_dtype"] not in ("bfloat16", "float32"):
        raise ValueError(f"compute_dtype {cfg['compute_dtype']!r}")
    flags = [
        f"--arch={cfg['arch']}",
        f"--image-size={cfg['image_size']}",
        f"--num-classes={cfg['num_classes']}",
        f"--optimizer={cfg['optimizer']}",
        f"--momentum={cfg['momentum']}",
        f"--weight-decay={cfg['weight_decay']}",
        f"--lr={cfg['lr']}",
        f"--warmup-epochs={cfg['warmup_epochs']}",
    ]
    if cfg["compute_dtype"] == "float32":
        flags.append("--no-bf16")
    return flags


def optimizer_memory(opt_state):
    """What the clock copies after step 1: the params-shaped momentum
    trace inside the optax chain's state."""
    for part in opt_state:
        trace = getattr(part, "trace", None)
        if trace is not None:
            return trace
    raise RuntimeError("chipbench: optimizer state holds no momentum "
                       "trace")


def first_gradient(p0: dict, memory1: dict, cfg: dict) -> dict:
    """The first gradient as the optimizer got it: after one step from
    zero momentum m1 = g + wd * p0, so g = m1 - wd * p0."""
    wd = cfg["weight_decay"]
    return {k: memory1[k] - wd * p0[k] for k in p0}
