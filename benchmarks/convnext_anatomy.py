"""ConvNeXt-T per-stage step anatomy (VERDICT r4 item 8).

The round-4 family table pins ConvNeXt-T at ~18.6% MFU and explains it
by the grouped-conv roofline (the depthwise 7x7 runs at cg=1, pure
HBM streaming). That explanation was by-analogy; this instrument makes
it measured: for each of the four stage geometries (224px input,
depths (3,3,9,3), dims (96,192,384,768) -> 56/28/14/7 px feature maps)
it times every block op in isolation —

  dw7x7   — the depthwise conv (feature_group_count=C)
  ln      — channels-last LayerNorm over the lane dim
  mlp     — the C->4C GEMM + GELU + 4C->C GEMM pair (timed as one
            shape-preserving composite; the chained-loop estimator
            requires fn(x).shape == x.shape)
  block   — the whole fused block (what XLA actually runs)

and, on TPU (round 6 — the round-5 VERDICT's "attack the dominant
memory-shaped cost" item), the Pallas fused-kernel columns
(ops/fused_mlp.py; parity vs the unfused composite asserted before
timing):

  mlp_fused   — LN -> C->4C -> GELU -> 4C->C -> layer-scale ->
                residual in ONE pallas_call, the 4C intermediate
                VMEM-resident (its HBM bound drops the charged
                round-trip: 3 activation passes + one weight fetch)
  block_fused — dw7x7 (XLA) + the fused kernel: the whole block as
                the --fused-mlp lowering runs it

The accept bar (docs/ROOFLINE.md "Fused ConvNeXt MLP"): >= 10%
block-vs-block_fused time reduction at s0/s1 within bf16 tolerance;
`speedup_vs_block` in each block_fused entry is the verdict number.
Off-TPU the fused columns are skipped (interpret-mode timing says
nothing about the chip); CNX_FUSED=force overrides for debugging.

— and prints each against its HBM bound (bytes / measured copy GB/s)
and MXU bound (flops / measured matmul TFLOP/s), plus which bound is
binding. The verdict this produces (see docs/ROOFLINE.md "ConvNeXt
anatomy"): the dw7x7 and LN are HBM-bound as predicted, the two
pointwise GEMMs are the FLOP carriers, and the block total is within
the sum of its memory-bound parts — i.e. the 18.6% MFU is structural
(cg=1 + elementwise traffic), with no >=10% kernel-level lever hiding
in the block.

Method matches benchmarks/grouped_conv.py: chained fori_loop
differencing, median of `pairs`, with ADAPTIVE chain lengths per op
(~120ms hi window sized from the op's roofline bound — fixed short
chains read negative on the sub-100us ops under host timing noise;
effective reps echoed per entry); bounds from the same roofline
microbenches. Run on the chip:

    python benchmarks/convnext_anatomy.py
"""

from __future__ import annotations

import json
import os
import sys

import numpy as np

sys.path.insert(0, os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))

from benchmarks.grouped_conv import _timed_chain  # noqa: E402
from benchmarks.roofline import (  # noqa: E402
    measure_hbm_gbs, measure_mxu_tflops,
)

# ConvNeXt-T stage geometries at 224px: (name, H=W, C, blocks_in_stage).
STAGES = [
    ("s0.56x56x96", 56, 96, 3),
    ("s1.28x28x192", 28, 192, 3),
    ("s2.14x14x384", 14, 384, 9),
    ("s3.7x7x768", 7, 768, 3),
]


def measure_stage(name: str, hw: int, c: int, n_blocks: int, batch: int,
                  hbm_gbs: float, mxu_tflops: float) -> dict:
    import jax
    import jax.numpy as jnp
    from jax import lax

    k_x, k_dw, k_1, k_2 = jax.random.split(jax.random.key(0), 4)
    x = jax.random.normal(k_x, (batch, hw, hw, c), jnp.bfloat16)
    wdw = jax.random.normal(k_dw, (7, 7, 1, c), jnp.bfloat16) * 0.05
    w1 = jax.random.normal(k_1, (c, 4 * c), jnp.bfloat16) * 0.05
    w2 = jax.random.normal(k_2, (4 * c, c), jnp.bfloat16) * 0.05
    scale = jnp.ones((c,), jnp.bfloat16)
    gamma = jnp.full((c,), 1e-2, jnp.bfloat16)
    dn = lax.conv_dimension_numbers(x.shape, wdw.shape,
                                    ("NHWC", "HWIO", "NHWC"))

    def dw(y):
        return lax.conv_general_dilated(
            y, wdw, (1, 1), "SAME", dimension_numbers=dn,
            feature_group_count=c,
            preferred_element_type=jnp.bfloat16).astype(jnp.bfloat16)

    def ln(y):
        yf = y.astype(jnp.float32)
        mu = yf.mean(-1, keepdims=True)
        var = ((yf - mu) ** 2).mean(-1, keepdims=True)
        return ((yf - mu) * lax.rsqrt(var + 1e-6)).astype(jnp.bfloat16)

    def dw_shift(y):
        # Alternative lowering: 49 statically-sliced shifted
        # multiply-adds over a SAME-padded input, weights broadcast
        # over C — elementwise VPU work XLA can fuse into one output
        # kernel, instead of feature_group_count=C on the conv path.
        yp = jnp.pad(y, ((0, 0), (3, 3), (3, 3), (0, 0)))
        acc = jnp.zeros_like(y, jnp.float32)
        for ky in range(7):
            for kx in range(7):
                acc = acc + (yp[:, ky:ky + hw, kx:kx + hw, :]
                             * wdw[ky, kx, 0, :]).astype(jnp.float32)
        return acc.astype(jnp.bfloat16)

    def mlp(y):
        h = jnp.einsum("nhwc,cd->nhwd", y, w1,
                       preferred_element_type=jnp.bfloat16)
        h = jax.nn.gelu(h, approximate=False).astype(jnp.bfloat16)
        return jnp.einsum("nhwd,dc->nhwc", h, w2,
                          preferred_element_type=jnp.bfloat16)

    def block(y):
        return y + gamma * mlp(ln(dw(y)) * scale)

    nhw = batch * hw * hw
    elems = nhw * c
    # Per-op (fn, analytic flops, minimal bf16 traffic). Traffic model:
    # elementwise ops read input + write output; the MLP's 4C
    # intermediate CANNOT stay on-chip (e.g. 154 MB at stage 0), so its
    # bound charges one HBM round-trip for it — read x(C), write h(4C),
    # read h(4C), write out(C) = 10*elems units. The block assumes
    # dw+ln+scale fuse into one pass (2), the first GEMM writes h
    # (1+4), and the second GEMM's epilogue fuses the residual
    # (4+1 read x+1 write) = 13*elems units total.
    ops = {
        "dw7x7": (dw, 2 * 49 * elems, 2 * 2 * elems),
        "dw_shift": (dw_shift, 2 * 49 * elems, 2 * 2 * elems),
        "ln": (ln, 8 * elems, 2 * 2 * elems),
        "mlp": (mlp, 2 * nhw * c * 8 * c, 2 * 10 * elems),
        "block": (block, 2 * nhw * c * (49 + 8 * c) + 12 * elems,
                  2 * 13 * elems),
    }

    # Fused-kernel columns (TPU only: interpret-mode timing on CPU says
    # nothing about the chip). The fused HBM bound charges 3 activation
    # passes (read h, read resid, write out — the 4C intermediate never
    # leaves VMEM) plus one resident-weight fetch of 8C² elements;
    # block_fused adds the dw conv's 2 passes. The tile is the
    # BACKWARD-inclusive one — the tile --fused-mlp training actually
    # runs the forward at — so the measured geometry is the deployed
    # geometry; C=768 (fits forward-only, never fuses in training) gets
    # no fused columns, matching the verdict table's "falls back" row.
    fused_br = None
    if jax.default_backend() == "tpu" or os.environ.get("CNX_FUSED"):
        from imagent_tpu.ops.fused_mlp import (
            fused_mlp_block, pick_block_rows,
        )
        fused_br = pick_block_rows(c, itemsize=2, backward=True)
    if fused_br is not None:
        zc = jnp.zeros((c,), jnp.float32)
        z4c = jnp.zeros((4 * c,), jnp.float32)

        def mlp_fused(y):
            return fused_mlp_block(y, y, scale, zc, w1, z4c, w2, zc,
                                   gamma, block_rows=fused_br)

        def block_fused(y):
            return fused_mlp_block(y, dw(y), scale, zc, w1, z4c, w2, zc,
                                   gamma, block_rows=fused_br)

        wbytes = 2 * 8 * c * c
        ops["mlp_fused"] = (mlp_fused, 2 * nhw * c * 8 * c + 10 * elems,
                            2 * 3 * elems + wbytes)
        ops["block_fused"] = (block_fused, ops["block"][1],
                              2 * 5 * elems + wbytes)

    out = {"stage": name, "hw": hw, "c": c, "blocks": n_blocks,
           "batch": batch, "fused_block_rows": fused_br}
    # Correctness cross-check before timing (bf16-loose): the shift
    # lowering must compute the same depthwise conv.
    ref = np.asarray(dw(x), np.float32)
    got = np.asarray(dw_shift(x), np.float32)
    err = np.max(np.abs(got - ref)) / (np.max(np.abs(ref)) + 1e-6)
    assert err < 0.05, err
    if fused_br is not None:
        # …and the fused kernel must compute the same LN->MLP->residual
        # chain as the unfused composite the `block` column times.
        ref = np.asarray(x + gamma * mlp(ln(x) * scale), np.float32)
        got = np.asarray(ops["mlp_fused"][0](x), np.float32)
        err = np.max(np.abs(got - ref)) / (np.max(np.abs(ref)) + 1e-6)
        assert err < 0.05, err

    for label, (f, flops, bts) in ops.items():
        hbm_ms = bts / (hbm_gbs * 1e9) * 1e3
        mxu_ms = flops / (mxu_tflops * 1e12) * 1e3
        # Adaptive chain lengths: sub-100us ops under a 288-iter chain
        # sit below host timing noise and the differencing goes
        # negative (the round-4 grouped-conv lesson) — size the hi
        # window to ~120ms from the op's roofline bound instead.
        est_ms = max(hbm_ms, mxu_ms, 1e-3)
        reps_hi = int(np.clip(120.0 / est_ms, 288, 8192))
        reps_lo = max(reps_hi // 9, 8)
        dt = _timed_chain(f, x, reps_lo=reps_lo, reps_hi=reps_hi)
        out[label] = {
            "ms": round(dt * 1e3, 4),
            "hbm_bound_ms": round(hbm_ms, 3),
            "mxu_bound_ms": round(mxu_ms, 3),
            "binding": "hbm" if hbm_ms > mxu_ms else "mxu",
            "pct_of_bound": round(
                100 * max(hbm_ms, mxu_ms) / (dt * 1e3), 1),
            "reps": [reps_lo, reps_hi],
        }
    if "block_fused" in out and out["block_fused"]["ms"] > 0:
        # The accept-bar number: >= 1.10 at s0/s1 accepts the kernel
        # (docs/ROOFLINE.md "Fused ConvNeXt MLP").
        out["block_fused"]["speedup_vs_block"] = round(
            out["block"]["ms"] / out["block_fused"]["ms"], 3)
    return out


def main() -> int:
    batch = int(os.environ.get("CNX_BATCH", "64"))
    hbm = measure_hbm_gbs()
    mxu = measure_mxu_tflops()
    print(json.dumps({"hbm_copy_gbs": round(hbm, 1),
                      "mxu_matmul_tflops": round(mxu, 1),
                      "batch": batch,
                      "reps": "adaptive per op (~120ms hi window, "
                              "echoed per entry)",
                      "stage_filter": os.environ.get("CNX_STAGE")}),
          flush=True)
    only = os.environ.get("CNX_STAGE")
    for name, hw, c, n_blocks in STAGES:
        if only and only not in name:
            continue
        print(json.dumps(measure_stage(name, hw, c, n_blocks, batch,
                                       hbm, mxu)), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
