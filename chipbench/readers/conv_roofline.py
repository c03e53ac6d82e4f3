"""Convolution kernels' share of their roofline: the least time the
chip could take for every convolution pass of a step (the family's
shapes, per pass the larger of operations/peak and bytes/peak), over
the time the trace's convolution-category ops took per traced step on
the fullest device."""

import importlib


def read(ctx: dict, args: dict):
    t = ctx["trace"]
    if t is None or ctx["peak"] is None:
        return None
    conv_ns = t["per_device"][t["fullest"]]["conv_ns"]
    if conv_ns <= 0:
        return None  # no op of that category in this trace
    arith = importlib.import_module(
        f"chipbench.families.{ctx['config']['family']}.arith")
    least = arith.conv_roofline_seconds(
        ctx["config"], ctx["mix"]["per_chip_batch"], ctx["peak"])
    return 100.0 * least["seconds"] / (conv_ns / 1e9 / t["n_steps"])
