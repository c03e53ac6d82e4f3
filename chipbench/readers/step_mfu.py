"""The whole step's share of the chip's peak: analytic train operations
per image (the family's arithmetic, kept with the benchmark) times this
run's window images/s/chip, over the chip's bf16 peak."""

import importlib


def read(ctx: dict, args: dict):
    if ctx["peak"] is None:
        return None
    arith = importlib.import_module(
        f"chipbench.families.{ctx['config']['family']}.arith")
    flops = arith.train_flops_per_image(ctx["config"])
    rate = ctx["e2e"]["train_throughput"]
    return 100.0 * flops * rate / ctx["peak"]["bf16_flops_per_s"]
