"""Reproduce the numbers behind docs/ROOFLINE.md.

Three measurements, all robust to a fixed per-dispatch latency
(on-device dependent chains, two loop lengths differenced to cancel
fixed overheads):

  1. achieved HBM bandwidth (bf16 copy-scale chain),
  2. achieved MXU throughput (chained 4096^2 bf16 matmuls),
  3. train-step phase times (full step / fwd train / fwd eval) for the
     three headline configs (resnet50@224, resnet18@448, vit_b16@224),
     against their analytic MXU + HBM bounds.

    python benchmarks/roofline.py            # all sections, ~10 min
"""

from __future__ import annotations

import json
import os
import sys
import time
from functools import partial

import numpy as np

sys.path.insert(0, os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))


def _timed(f, *a):
    o = f(*a)
    np.asarray(o.ravel()[:1])
    best = float("inf")
    for _ in range(8):
        t0 = time.perf_counter()
        o = f(*a)
        np.asarray(o.ravel()[:1])
        best = min(best, time.perf_counter() - t0)
    return best


def measure_hbm_gbs() -> float:
    """Read+write bandwidth of a 512 MB bf16 copy-scale chain."""
    import jax
    import jax.numpy as jnp

    @partial(jax.jit, static_argnums=(2,))
    def copy_k(x, c, k):
        return jax.lax.fori_loop(0, k, lambda i, y: y * c, x)

    # The scale must be a traced value and representable in bf16 —
    # a constant that rounds to 1.0 lets XLA delete the whole loop.
    c = jnp.bfloat16(1.0078125)
    n = 512 * 1024 * 1024 // 2
    x = jnp.ones((n,), jnp.bfloat16)
    t_lo = _timed(copy_k, x, c, 10)
    t_hi = _timed(copy_k, x, c, 410)
    return 2 * n * 2 / 1e9 / ((t_hi - t_lo) / 400)


def measure_mxu_tflops() -> float:
    """Chained 4096^2 bf16 matmul throughput."""
    import jax
    import jax.numpy as jnp

    @partial(jax.jit, static_argnums=(2,))
    def mm_k(a, b, k):
        def body(i, c):
            return (a @ c).astype(jnp.bfloat16) * jnp.bfloat16(1e-3)
        return jax.lax.fori_loop(0, k, body, b)

    m = 4096
    a = jnp.ones((m, m), jnp.bfloat16) * jnp.bfloat16(0.01)
    b = jnp.ones((m, m), jnp.bfloat16)
    t_lo = _timed(mm_k, a, b, 10)
    t_hi = _timed(mm_k, a, b, 410)
    return 2 * m ** 3 / ((t_hi - t_lo) / 400) / 1e12


def measure_step_phases(arch: str, size: int, batch: int) -> dict:
    """Full-step / fwd(train-BN) / fwd(eval) times for one config."""
    import jax
    import jax.numpy as jnp

    from imagent_tpu.cluster import make_mesh
    from imagent_tpu.models import create_model
    from imagent_tpu.train import (
        create_train_state, make_optimizer, make_train_step,
        replicate_state, shard_batch,
    )

    mesh = make_mesh(model_parallel=1)
    model = create_model(arch, num_classes=1000, bf16=True)
    opt = make_optimizer()
    state0 = replicate_state(
        create_train_state(model, jax.random.key(0), size, opt,
                           batch_size=2), mesh)
    step = make_train_step(model, opt, mesh)
    rng = np.random.default_rng(0)
    images = rng.normal(size=(batch, size, size, 3)).astype(jnp.bfloat16)
    labels = rng.integers(0, 1000, size=(batch,)).astype(np.int32)
    gi, gl = shard_batch(mesh, images, labels)
    lr = np.float32(0.1)

    # Full step: state-chained iterations (the step donates its state).
    state = replicate_state(jax.device_get(state0), mesh)
    for _ in range(3):
        state, metrics = step(state, gi, gl, lr)
    np.asarray(metrics)
    best = float("inf")
    for _ in range(3):
        t0 = time.perf_counter()
        for _ in range(10):
            state, metrics = step(state, gi, gl, lr)
        np.asarray(metrics)
        best = min(best, (time.perf_counter() - t0) / 10)
    out = {"step_ms": best * 1e3}

    p, bs = state0.params, state0.batch_stats
    fwd_train = jax.jit(lambda p, bs, x: jnp.sum(model.apply(
        {"params": p, "batch_stats": bs}, x, train=True,
        mutable=["batch_stats"])[0].astype(jnp.float32)))
    fwd_eval = jax.jit(lambda p, bs, x: jnp.sum(model.apply(
        {"params": p, "batch_stats": bs}, x,
        train=False).astype(jnp.float32)))

    def timed_fwd(f):
        o = f(p, bs, gi)
        np.asarray(o)
        best = float("inf")
        for _ in range(3):
            t0 = time.perf_counter()
            for _ in range(10):
                o = f(p, bs, gi)
            np.asarray(o)
            best = min(best, (time.perf_counter() - t0) / 10)
        return best * 1e3

    out["fwd_train_ms"] = timed_fwd(fwd_train)
    out["fwd_eval_ms"] = timed_fwd(fwd_eval)
    return out


def main() -> int:
    hbm = measure_hbm_gbs()
    mxu = measure_mxu_tflops()
    print(json.dumps({"hbm_copy_gbs": round(hbm, 1),
                      "mxu_matmul_tflops": round(mxu, 1)}))
    for arch, size, batch in (("resnet50", 224, 256),
                              ("resnet18", 448, 128),
                              ("vit_b16", 224, 256)):
        r = measure_step_phases(arch, size, batch)
        r.update({"arch": arch, "image_size": size, "per_chip_batch": batch,
                  "img_s": round(batch / (r["step_ms"] / 1e3), 1)})
        print(json.dumps({k: round(v, 2) if isinstance(v, float) else v
                          for k, v in r.items()}))
    return 0


if __name__ == "__main__":
    sys.exit(main())
