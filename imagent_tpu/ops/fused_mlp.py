"""Pallas TPU fused ConvNeXt MLP: LayerNorm -> Linear C->4C -> GELU ->
Linear 4C->C -> layer-scale -> residual add in ONE pass, with a custom
VJP that recomputes the LayerNorm output and the 4C activation in the
backward (FlashAttention-style remat-in-kernel).

Why (docs/ROOFLINE.md "ConvNeXt-T anatomy", round 5): the C->4C->C MLP
pair dominates every ConvNeXt block (43-71% of block time) and at
s0/s1 is HBM-bound INCLUDING a charged round-trip for the 4C
intermediate — 154 MB at stage 0, which cannot stay on-chip under
XLA's per-op schedule. This kernel tiles the flattened spatial rows so
that intermediate (and the LN statistics) live in VMEM and never touch
HBM: per block the ideal traffic drops from ~10 activation passes to 3
(read the dwconv output, read the residual input, write the block
output) plus one weight fetch. The discipline is Dao et al. 2022
(fuse the chain; rematerialize the fat intermediate in the backward)
applied to the inverted bottleneck of Liu et al. 2022.

Design notes:

* Grid is 1-D over row tiles of the flattened ``(B*H*W, C)`` batch;
  both GEMMs hit the MXU with ``preferred_element_type=float32``; LN
  statistics, GELU, and the residual accumulate in fp32 regardless of
  the compute dtype (the unfused bf16 path rounds MORE, so parity is
  within bf16 tolerance by construction — pinned in
  ``tests/test_fused_mlp.py``).
* The backward is one kernel over the same row grid: it recomputes
  ``xn`` (the normalized input) and the 4C activation from the saved
  block INPUTS only — the residuals are exactly the forward's operands,
  nothing intermediate is stored — and accumulates the weight/param
  gradients in revisited fp32 output blocks (constant index map: the
  block stays VMEM-resident across sequential grid steps, one HBM
  write at the end). Vector gradients carry a broadcast sublane-8
  leading axis so their blocks satisfy TPU tiling; row 0 is taken on
  the way out.
* VMEM sizing (``fused_vmem_bytes`` / ``pick_block_rows``): the
  backward working set is dominated by the resident W1+W2 (8C² x
  itemsize) plus their fp32 gradient accumulators (8C² x 4). On a 16 MB
  VMEM core with a ~12 MB usable budget that admits C <= 192 at the
  default 256-row tile and C = 384 at reduced tiles — exactly the
  HBM-bound stage-0/1 geometries the anatomy table targets; C = 768
  (MXU-bound anyway) falls back to the unfused path.
* Stochastic depth is NOT fused: the production train step applies
  ConvNeXt without droppath rngs (rate 0.0 only — models/convnext.py
  docstring), so an active per-sample drop mask falls back to the
  unfused path (``fused_block_rows`` returns None when ``dropping``).
* ``interpret=None`` compiles on the TPU and interprets on the CPU
  platform (``ops.resolve_interpret``), so the CPU CI mesh exercises
  the real kernel code; ``tests/test_chip_compile.py`` compiles it
  ``interpret=False`` for a described v5e at every fusable width.

``ops/fused_block.py`` (the rejected ResNet bottleneck fusion) is the
sibling negative result; this kernel attacks the one geometry the
round-5 measurement shows XLA does NOT already win (the accept bar and
verdict protocol live in docs/ROOFLINE.md "Fused ConvNeXt MLP").
"""

from __future__ import annotations

import functools
import math

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl

from imagent_tpu.ops import resolve_interpret

_SQRT2 = math.sqrt(2.0)
_INV_SQRT_2PI = 1.0 / math.sqrt(2.0 * math.pi)
_VEC_SUBLANES = 8  # broadcast rows so vector-grad blocks tile on TPU

# Usable VMEM budget for the auto-fuse decision: ~16 MB/core minus
# headroom for Mosaic's own double buffering of the streamed row tiles.
VMEM_BUDGET = 12 * 2 ** 20
_DEFAULT_BLOCK_ROWS = 256


# erf has no Pallas TPU lowering in the installed JAX (0.9.0:
# "Unimplemented primitive ... erf"), so the kernel evaluates it the
# way XLA itself lowers f32 erf: x * P(x^2) / Q(x^2) on the clamped
# argument (xla/client/lib/math.cc, ErfImpl32). Max abs error vs
# ``jax.lax.erf`` in fp32: 3e-7 (one ulp of the result), pinned in
# tests/test_fused_mlp.py; mul/add/div/clamp all lower to the VPU.
_ERF_CLAMP = 3.832506856900711
_ERF_ALPHA = (0.00022905065861350646, 0.0034082910107109506,
              0.050955695062380861, 0.18520832239976145,
              1.128379143519084)
_ERF_BETA = (-1.1791602954361697e-7, 0.000023547966471313185,
             0.0010179625278914885, 0.014070470171167667,
             0.11098505178285362, 0.49746925110067538, 1.0)


def _horner(x, coeffs):
    acc = jnp.full_like(x, coeffs[0])
    for c in coeffs[1:]:
        acc = acc * x + c
    return acc


def _erf(x):
    """fp32 erf from primitives the Pallas TPU lowering has."""
    x = jnp.clip(x, -_ERF_CLAMP, _ERF_CLAMP)
    x2 = x * x
    return x * _horner(x2, _ERF_ALPHA) / _horner(x2, _ERF_BETA)


def _gelu(a):
    """Exact (erf) GELU in fp32 — matches ``nn.gelu(approximate=False)``."""
    return 0.5 * a * (1.0 + _erf(a / _SQRT2))


def _gelu_grad(a):
    """d/da of exact GELU: Phi(a) + a * phi(a)."""
    phi = jnp.exp(-0.5 * a * a) * _INV_SQRT_2PI
    return 0.5 * (1.0 + _erf(a / _SQRT2)) + a * phi


def _ln_fwd(h32, eps):
    """fp32 LayerNorm core: returns (xn, rsig) for reuse by both passes."""
    mu = jnp.mean(h32, axis=-1, keepdims=True)
    var = jnp.mean(jnp.square(h32 - mu), axis=-1, keepdims=True)
    rsig = jax.lax.rsqrt(var + eps)
    return (h32 - mu) * rsig, rsig


def _mlp_chain(h_ref, ls_ref, lb_ref, w1_ref, b1_ref, w2_ref, b2_ref, eps):
    """The shared forward chain on one row tile (fp32 stats/epilogues,
    compute-dtype GEMM operands): returns every stage the backward needs."""
    cd = w1_ref.dtype
    xn, rsig = _ln_fwd(h_ref[...].astype(jnp.float32), eps)
    y1 = xn * ls_ref[...].astype(jnp.float32) + lb_ref[...].astype(
        jnp.float32)
    y1c = y1.astype(cd)
    a = jnp.dot(y1c, w1_ref[...],
                preferred_element_type=jnp.float32) + b1_ref[...].astype(
        jnp.float32)
    ga = _gelu(a)
    gac = ga.astype(cd)  # the 4C intermediate — VMEM-resident only
    o = jnp.dot(gac, w2_ref[...],
                preferred_element_type=jnp.float32) + b2_ref[...].astype(
        jnp.float32)
    return xn, rsig, y1c, a, gac, o


def _fwd_kernel(res_ref, h_ref, ls_ref, lb_ref, w1_ref, b1_ref, w2_ref,
                b2_ref, g_ref, o_ref, *, eps):
    _, _, _, _, _, o = _mlp_chain(h_ref, ls_ref, lb_ref, w1_ref, b1_ref,
                                  w2_ref, b2_ref, eps)
    out = res_ref[...].astype(jnp.float32) + g_ref[...].astype(
        jnp.float32) * o
    o_ref[...] = out.astype(o_ref.dtype)


def _bwd_kernel(h_ref, ls_ref, lb_ref, w1_ref, b1_ref,
                w2_ref, b2_ref, g_ref, do_ref, dh_ref, dw1_ref, db1_ref,
                dw2_ref, dg_ref, dls_ref, dlb_ref, *, eps):
    i = pl.program_id(0)
    cd = w1_ref.dtype
    xn, rsig, y1c, a, gac, o = _mlp_chain(
        h_ref, ls_ref, lb_ref, w1_ref, b1_ref, w2_ref, b2_ref, eps)
    g = do_ref[...].astype(jnp.float32)

    do = g * g_ref[...].astype(jnp.float32)            # d(branch output)
    dgamma = jnp.sum(g * o, axis=0)                    # (C,)
    doc = do.astype(cd)
    dw2 = jax.lax.dot_general(gac, doc, (((0,), (0,)), ((), ())),
                              preferred_element_type=jnp.float32)
    dga = jax.lax.dot_general(doc, w2_ref[...], (((1,), (1,)), ((), ())),
                              preferred_element_type=jnp.float32)
    da = dga * _gelu_grad(a)
    db1 = jnp.sum(da, axis=0)                          # (4C,)
    dac = da.astype(cd)
    dw1 = jax.lax.dot_general(y1c, dac, (((0,), (0,)), ((), ())),
                              preferred_element_type=jnp.float32)
    dy1 = jax.lax.dot_general(dac, w1_ref[...], (((1,), (1,)), ((), ())),
                              preferred_element_type=jnp.float32)
    dls = jnp.sum(dy1 * xn, axis=0)                    # (C,)
    dlb = jnp.sum(dy1, axis=0)                         # (C,)
    dxn = dy1 * ls_ref[...].astype(jnp.float32)
    m1 = jnp.mean(dxn, axis=-1, keepdims=True)
    m2 = jnp.mean(dxn * xn, axis=-1, keepdims=True)
    dh_ref[...] = (rsig * (dxn - m1 - xn * m2)).astype(dh_ref.dtype)

    @pl.when(i == 0)
    def _():
        dw1_ref[...] = jnp.zeros_like(dw1_ref)
        dw2_ref[...] = jnp.zeros_like(dw2_ref)
        db1_ref[...] = jnp.zeros_like(db1_ref)
        dg_ref[...] = jnp.zeros_like(dg_ref)
        dls_ref[...] = jnp.zeros_like(dls_ref)
        dlb_ref[...] = jnp.zeros_like(dlb_ref)

    # Constant-index output blocks: VMEM-resident across the sequential
    # row grid, one HBM write at the end — the Pallas reduction pattern.
    dw1_ref[...] += dw1
    dw2_ref[...] += dw2
    db1_ref[...] += jnp.broadcast_to(db1, db1_ref.shape)
    dg_ref[...] += jnp.broadcast_to(dgamma, dg_ref.shape)
    dls_ref[...] += jnp.broadcast_to(dls, dls_ref.shape)
    dlb_ref[...] += jnp.broadcast_to(dlb, dlb_ref.shape)


def _row_specs(block_rows, c):
    return pl.BlockSpec((block_rows, c), lambda i: (i, 0))


def _full_spec(shape):
    return pl.BlockSpec(shape, lambda i: (0,) * len(shape))


def _fused_fwd_impl(resid, h, ls, lb, w1, b1, w2, b2, gamma, eps,
                    block_rows, interpret):
    rp, c = h.shape
    grid = (rp // block_rows,)
    return pl.pallas_call(
        functools.partial(_fwd_kernel, eps=eps),
        grid=grid,
        in_specs=[
            _row_specs(block_rows, c), _row_specs(block_rows, c),
            _full_spec((c,)), _full_spec((c,)),
            _full_spec((c, 4 * c)), _full_spec((4 * c,)),
            _full_spec((4 * c, c)), _full_spec((c,)),
            _full_spec((c,)),
        ],
        out_specs=_row_specs(block_rows, c),
        out_shape=jax.ShapeDtypeStruct((rp, c), resid.dtype),
        interpret=interpret,
    )(resid, h, ls, lb, w1, b1, w2, b2, gamma)


@functools.partial(jax.custom_vjp, nondiff_argnums=(9, 10, 11))
def _fused_core(resid, h, ls, lb, w1, b1, w2, b2, gamma, eps, block_rows,
                interpret):
    return _fused_fwd_impl(resid, h, ls, lb, w1, b1, w2, b2, gamma, eps,
                           block_rows, interpret)


def _fused_core_fwd(resid, h, ls, lb, w1, b1, w2, b2, gamma, eps,
                    block_rows, interpret):
    out = _fused_fwd_impl(resid, h, ls, lb, w1, b1, w2, b2, gamma, eps,
                          block_rows, interpret)
    # FlashAttention discipline: the residuals ARE the inputs — the LN
    # output and the 4C activation are recomputed inside the backward.
    return out, (h, ls, lb, w1, b1, w2, b2, gamma)


def _fused_core_bwd(eps, block_rows, interpret, res, dout):
    h, ls, lb, w1, b1, w2, b2, gamma = res
    rp, c = h.shape
    grid = (rp // block_rows,)
    vec = _VEC_SUBLANES
    dh, dw1, db1, dw2, dgamma, dls, dlb = pl.pallas_call(
        functools.partial(_bwd_kernel, eps=eps),
        grid=grid,
        in_specs=[
            _row_specs(block_rows, c),  # h (the residual add needs no
            # input in the backward: d(out)/d(resid) is the identity)
            _full_spec((c,)), _full_spec((c,)),
            _full_spec((c, 4 * c)), _full_spec((4 * c,)),
            _full_spec((4 * c, c)), _full_spec((c,)),
            _full_spec((c,)),
            _row_specs(block_rows, c),
        ],
        out_specs=[
            _row_specs(block_rows, c),
            _full_spec((c, 4 * c)), _full_spec((vec, 4 * c)),
            _full_spec((4 * c, c)), _full_spec((vec, c)),
            _full_spec((vec, c)), _full_spec((vec, c)),
        ],
        out_shape=[
            jax.ShapeDtypeStruct((rp, c), h.dtype),
            jax.ShapeDtypeStruct((c, 4 * c), jnp.float32),
            jax.ShapeDtypeStruct((vec, 4 * c), jnp.float32),
            jax.ShapeDtypeStruct((4 * c, c), jnp.float32),
            jax.ShapeDtypeStruct((vec, c), jnp.float32),
            jax.ShapeDtypeStruct((vec, c), jnp.float32),
            jax.ShapeDtypeStruct((vec, c), jnp.float32),
        ],
        interpret=interpret,
    )(h, ls, lb, w1, b1, w2, b2, gamma, dout)
    # d(out)/d(b2) = gamma per channel — no recompute needed, one XLA
    # reduce over the cotangent that is already in HBM.
    db2 = jnp.sum(dout.astype(jnp.float32), axis=0) * gamma.astype(
        jnp.float32)
    return (dout, dh, dls[0].astype(ls.dtype), dlb[0].astype(lb.dtype),
            dw1.astype(w1.dtype), db1[0].astype(b1.dtype),
            dw2.astype(w2.dtype), db2.astype(b2.dtype),
            dgamma[0].astype(gamma.dtype))


_fused_core.defvjp(_fused_core_fwd, _fused_core_bwd)


def fused_vmem_bytes(c: int, block_rows: int = _DEFAULT_BLOCK_ROWS,
                     itemsize: int = 2, backward: bool = True) -> int:
    """Coarse VMEM working-set model for the auto-fuse decision. The
    dominant terms: the resident W1+W2 (8C² x itemsize), their fp32
    gradient accumulators in the backward (8C² x 4), and the fp32 4C
    activation tiles. Deliberately conservative (counts every live fp32
    temporary) — a false 'fits' wedges a real run at compile time, a
    false 'does not fit' just keeps today's measured path."""
    weights = 8 * c * c * itemsize
    tile_c, tile_4c = block_rows * c, block_rows * 4 * c
    fwd = (3 * tile_c * itemsize      # resid + h in, out
           + 4 * tile_c * 4           # fp32 h/xn/y1/out temporaries
           + 2 * tile_4c * 4)         # fp32 a + gelu(a)
    if not backward:
        return weights + fwd
    bwd = (8 * c * c * 4              # dW1 + dW2 fp32 accumulators
           + 4 * tile_c * 4           # g, dy1, dxn, dh temporaries
           + 2 * tile_4c * 4)         # dga, da
    return weights + fwd + bwd


def pick_block_rows(c: int, itemsize: int = 2, backward: bool = True,
                    budget: int = VMEM_BUDGET) -> int | None:
    """Largest row tile whose working set fits the VMEM budget, or None
    when even the smallest tile does not (C=768's backward: the 18.9 MB
    of fp32 dW accumulators alone exceed a 16 MB core)."""
    for br in (256, 128, 64, 32, 16):
        if fused_vmem_bytes(c, br, itemsize, backward) <= budget:
            return br
    return None


def fused_block_rows(mode: str, dim: int, *, dtype=jnp.bfloat16,
                     dropping: bool = False,
                     budget: int = VMEM_BUDGET) -> int | None:
    """The --fused-mlp decision for one block geometry: the row tile to
    fuse with, or None for the unfused path.

    * ``off``: never fuse (today's path, the measured baseline).
    * ``auto``: fuse only where the backward working set fits VMEM AND
      the backend is TPU (off-TPU the kernel would run interpreted —
      orders of magnitude slower than XLA's native schedule).
    * ``on``: force the fused lowering wherever it CAN run (interpret
      mode off-TPU — how CI exercises the real kernel); VMEM overflow
      still falls back, since compiling an overflowing kernel is a
      hard error, not a slow path.

    An active stochastic-depth mask (``dropping``) always falls back:
    the kernel fuses the production block, and the production train
    step applies ConvNeXt without droppath rngs (rate 0.0 only)."""
    if mode not in ("auto", "on", "off"):
        raise ValueError(
            f"--fused-mlp must be one of auto|on|off, got {mode!r}")
    if mode == "off" or dropping:
        return None
    br = pick_block_rows(dim, jnp.dtype(dtype).itemsize, backward=True,
                         budget=budget)
    if br is None:
        return None
    if mode == "auto" and jax.default_backend() != "tpu":
        return None
    return br


def fused_mlp_plan(mode: str, dims, *, dtype=jnp.bfloat16) -> dict:
    """Per-stage-width decision map (engine startup observability):
    ``{dim: block_rows | None}``."""
    return {int(d): fused_block_rows(mode, int(d), dtype=dtype)
            for d in dims}


def fused_mlp_block(resid, h, ln_scale, ln_bias, w1, b1, w2, b2, gamma,
                    *, eps: float = 1e-6, block_rows: int | None = None,
                    interpret: bool | None = None):
    """Fused [LN -> C->4C -> GELU -> 4C->C -> layer-scale -> residual].

    ``resid``: the block input (the residual stream); ``h``: the
    depthwise-conv output the LayerNorm reads. Both ``(..., C)``, any
    leading shape (flattened to rows internally). Parameters are cast
    to the activation dtype first — the same value rounding the unfused
    flax modules apply — and all statistics/epilogues run in fp32.
    ``interpret=None``: compiled on the TPU, interpreted on the CPU
    platform (``ops.resolve_interpret``)."""
    interpret = resolve_interpret(interpret)
    if resid.shape != h.shape:
        raise ValueError(f"resid/h shape mismatch: {resid.shape} vs "
                         f"{h.shape}")
    orig_shape = h.shape
    c = orig_shape[-1]
    r = math.prod(orig_shape[:-1])
    cd = resid.dtype
    if block_rows is None:
        block_rows = pick_block_rows(c, jnp.dtype(cd).itemsize)
        if block_rows is None:
            # The design rule (fused_vmem_bytes): a false "fits" is a
            # Mosaic compile-time wedge on a real run — refuse instead.
            raise ValueError(
                f"C={c} exceeds the VMEM budget at every row tile "
                "(backward-inclusive model); use the unfused path "
                "(--fused-mlp auto/off) or pass block_rows explicitly")
    # Keep the tile sublane-aligned and no larger than the padded rows.
    block_rows = max(16, min(block_rows, -(-r // 16) * 16))

    ls, lb, w1, b1, w2, b2, g = (a.astype(cd) for a in
                                 (ln_scale, ln_bias, w1, b1, w2, b2, gamma))
    rp = -(-r // block_rows) * block_rows
    pad = ((0, rp - r), (0, 0))
    out = _fused_core(jnp.pad(resid.reshape(r, c), pad),
                      jnp.pad(h.reshape(r, c), pad),
                      ls, lb, w1, b1, w2, b2, g,
                      float(eps), int(block_rows), bool(interpret))
    return out[:r].reshape(orig_shape)


def reference_mlp_block(resid, h, ln_scale, ln_bias, w1, b1, w2, b2,
                        gamma, *, eps: float = 1e-6):
    """The same computation as unfused XLA ops in the flax module's
    dtype discipline (params cast to the activation dtype, bf16 GEMMs,
    fp32 LN statistics) — the parity oracle and benchmark baseline."""
    cd = resid.dtype
    ls, lb, w1, b1, w2, b2, g = (a.astype(cd) for a in
                                 (ln_scale, ln_bias, w1, b1, w2, b2, gamma))
    xn, _ = _ln_fwd(h.astype(jnp.float32), eps)
    y = (xn * ls.astype(jnp.float32) + lb.astype(jnp.float32)).astype(cd)
    y = jnp.dot(y, w1, preferred_element_type=jnp.float32) + b1.astype(
        jnp.float32)
    y = jax.nn.gelu(y, approximate=False).astype(cd)
    y = jnp.dot(y, w2, preferred_element_type=jnp.float32) + b2.astype(
        jnp.float32)
    return (resid.astype(jnp.float32)
            + g.astype(jnp.float32) * y).astype(cd)
