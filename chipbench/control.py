"""The control and the planted faults: the reference put in the
program's place, altered the way a tempting later change would alter
the program, and held against the unaltered reference by the same
comparison that decides ``correct``.  Each fault comes out as not
correct.  The control moves ``grad1_best_diff`` alone (8x), and that
number is printed, not compared, since one sound run read almost as
high: for now the control fails nothing (PERF.md section 7, first).

* ``control``: every convolution's and the classifier's operands
  rounded to float8 (e4m3) -- the nearest precision below the
  configuration's bfloat16 compute.  ``bf16`` rounds them to bfloat16
  instead: not a control but a second witness of what the stated
  precision alone does to each number.
* ``half_batch``: half of each chip's rows left out, the mean taken
  over the rest.
* ``no_exchange`` (cells on several chips): the gradient exchange left
  out, chip 0's gradient applied unaveraged.
* ``state_unchanged``: a step that returns its state as it got it; by
  the worst-leaf measure this reads 1 and needs no computation.

``readings.py`` reads them on the chip at the cell's own size, beside
the program's own numbers; the tests under tests/chipbench run them
small on the CPU.
"""

from __future__ import annotations


def _rounded(x, dtype, grad_dtype):
    """``x`` rounded to ``dtype`` on the way forward and its cotangent
    to ``grad_dtype`` on the way back (scaled by its largest magnitude
    first, as a mixed-precision recipe does, so that small gradients do
    not underflow): what a step whose matrix units are fed operands of
    those types computes, accumulating in float32.  (A bare cast would
    cast unscaled cotangents, which underflow in float8 and zero every
    gradient: a control that fails for a reason no later change would
    be tempted by.)"""
    import jax
    import jax.numpy as jnp

    @jax.custom_vjp
    def q(v):
        return v.astype(dtype).astype(v.dtype)

    def fwd(v):
        return q(v), None

    def bwd(_, g):
        scale = jnp.maximum(jnp.max(jnp.abs(g)), 1e-30)
        return ((g / scale).astype(grad_dtype).astype(g.dtype) * scale,)

    q.defvjp(fwd, bwd)
    return q(x)


def quant_fp8(x):
    """Float8 as it is trained in: e4m3 operands forward, e5m2
    gradients backward."""
    import jax.numpy as jnp
    return _rounded(x, jnp.float8_e4m3fn, jnp.float8_e5m2)


def quant_bf16(x):
    import jax.numpy as jnp
    return _rounded(x, jnp.bfloat16, jnp.bfloat16)


def variant(reference, program, cfg: dict, seed: int, batches: list,
            chips: int, what: str) -> dict:
    """``checks``' program-side triple of one altered reference
    (``reference`` and ``program`` are the family's modules)."""
    from chipbench import checks
    if what == "state_unchanged":
        base = reference.follow(cfg, seed, batches[:1], replicas=chips)
        side = checks.reference_side(base)
        zero = {k: 0.0 * v for k, v in base["p0"].items()}
        # the optimizer's memory stays 0: what the harness would read
        return {"losses": [side["losses"][0]] * len(batches),
                "grad1": program.first_gradient(base["p0"], zero, cfg),
                "dparam": zero}
    kw = {"control": dict(quant=quant_fp8),
          "bf16": dict(quant=quant_bf16),
          "half_batch": dict(fault="half_batch"),
          "no_exchange": dict(fault="no_exchange")}[what]
    return checks.reference_side(
        reference.follow(cfg, seed, batches, replicas=chips, **kw))
