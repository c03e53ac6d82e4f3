"""Fused bottleneck kernel vs XLA's unfused schedule, on the real chip.

Chains the block output into the next iteration (same shape), so timing
needs no CSE tricks and cancels the fixed per-dispatch latency by
differencing two chain lengths.

    python benchmarks/fused_block.py        # l3 + l4 geometries, bf16
"""

from __future__ import annotations

import functools
import json
import os
import sys
import time

import numpy as np

sys.path.insert(0, os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))


def bench_geometry(name, b, h, w, c, f, batch_tile):
    import jax
    import jax.numpy as jnp

    from imagent_tpu.ops.fused_block import (
        fused_bottleneck, reference_bottleneck,
    )

    rng = np.random.default_rng(0)
    x = jnp.asarray(rng.normal(size=(b, h, w, c)) * 0.1, jnp.bfloat16)
    w1 = jnp.asarray(rng.normal(size=(c, f)) * 0.05, jnp.bfloat16)
    b1 = jnp.zeros((f,), jnp.float32)
    w3 = jnp.asarray(rng.normal(size=(3, 3, f, f)) * 0.05, jnp.bfloat16)
    b3 = jnp.zeros((f,), jnp.float32)
    wc = jnp.asarray(rng.normal(size=(f, c)) * 0.05, jnp.bfloat16)
    bc = jnp.zeros((c,), jnp.float32)

    def chain(step_fn, k):
        def body(i, y):
            return step_fn(y, w1, b1, w3, b3, wc, bc)
        return jax.lax.fori_loop(0, k, body, x)

    fused = functools.partial(fused_bottleneck, batch_tile=batch_tile)
    out = {}
    for label, fn in (("xla", reference_bottleneck), ("fused", fused)):
        run = jax.jit(functools.partial(chain, fn), static_argnums=(0,))

        def timed(k):
            o = run(k)
            np.asarray(o.ravel()[:1])
            best = float("inf")
            for _ in range(6):
                t0 = time.perf_counter()
                o = run(k)
                np.asarray(o.ravel()[:1])
                best = min(best, time.perf_counter() - t0)
            return best

        t_lo, t_hi = timed(5), timed(105)
        out[label] = (t_hi - t_lo) / 100
    flops = 2 * b * h * w * (c * f + 9 * f * f + f * c)
    print(json.dumps({
        "geometry": name, "shape": [b, h, w, c], "bottleneck_width": f,
        "xla_us": round(out["xla"] * 1e6, 1),
        "fused_us": round(out["fused"] * 1e6, 1),
        "speedup": round(out["xla"] / out["fused"], 3),
        "fused_tflops": round(flops / out["fused"] / 1e12, 1),
        "xla_tflops": round(flops / out["xla"] / 1e12, 1),
    }))


def main() -> int:
    bench_geometry("resnet50_l3", 256, 14, 14, 1024, 256, batch_tile=4)
    bench_geometry("resnet50_l4", 256, 7, 7, 2048, 512, batch_tile=8)
    return 0


if __name__ == "__main__":
    sys.exit(main())
