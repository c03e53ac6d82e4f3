"""Pallas TPU flash attention: fused blockwise softmax attention with a
custom-VJP backward, O(N) memory in sequence length.

No reference analogue (the reference is an attention-free CNN,
``imagenet.py:312``); this is the framework's single-chip hot-op kernel
for the ViT family and pairs with ``parallel/ring_attention.py`` (which
distributes the same online-softmax fold across a mesh axis — here the
fold runs across grid steps inside one chip's VMEM).

Design (per the TPU Pallas playbook):

* grid ``(B*H, N/bq, N/bk)`` with the K dimension innermost, so the
  running ``(acc, m, l)`` statistics live in VMEM scratch across K steps
  and HBM traffic is one read of Q/K/V + one write of O;
* all matmuls hit the MXU via ``preferred_element_type=float32``; the
  softmax statistics are fp32 regardless of input dtype;
* the forward also emits the per-row logsumexp ``L = m + log(l)`` so the
  backward recomputes P exactly without materializing the (N, N) matrix;
* backward runs two kernels: dQ accumulates over K blocks (same grid
  order as forward), dK/dV accumulate over Q blocks (Q innermost);
* sequences that don't divide the block size are zero-padded by the
  wrapper and masked inside the kernel by global K position.

Interpret mode (``interpret=True`` on CPU) makes the exact same kernel
testable on the 8-device CPU mesh used by the test suite; what the
chip's compiler accepts is pinned by ``tests/test_chip_compile.py``
(``interpret=False`` against a described v5e).
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from imagent_tpu.ops import resolve_interpret

_NEG_BIG = -0.7 * float(jnp.finfo(jnp.float32).max)
_LANES = 128  # m/l scratch stores stats broadcast across one lane tile


def _kv_mask(ik, bk, n_real, bq):
    """(bq, bk) validity mask for global K positions beyond the true N."""
    k_pos = ik * bk + jax.lax.broadcasted_iota(jnp.int32, (bq, bk), 1)
    return k_pos < n_real


def _fwd_kernel(q_ref, k_ref, v_ref, o_ref, l_ref, acc, m, l, *,
                scale, n_real, bq, bk, nk):
    ik = pl.program_id(2)

    @pl.when(ik == 0)
    def _():
        acc[:] = jnp.zeros_like(acc)
        m[:] = jnp.full_like(m, _NEG_BIG)
        l[:] = jnp.zeros_like(l)

    q = q_ref[0]  # (bq, d)
    k = k_ref[0]  # (bk, d)
    s = jax.lax.dot_general(q, k, (((1,), (1,)), ((), ())),
                            preferred_element_type=jnp.float32) * scale
    s = jnp.where(_kv_mask(ik, bk, n_real, bq), s, _NEG_BIG)

    m_prev = m[:, :1]                                  # (bq, 1)
    m_blk = jnp.max(s, axis=1, keepdims=True)
    m_new = jnp.maximum(m_prev, m_blk)
    alpha = jnp.exp(m_prev - m_new)
    p = jnp.exp(s - m_new)                             # (bq, bk)
    l_new = l[:, :1] * alpha + jnp.sum(p, axis=1, keepdims=True)
    pv = jax.lax.dot_general(p, v_ref[0].astype(jnp.float32),
                             (((1,), (0,)), ((), ())),
                             preferred_element_type=jnp.float32)
    acc[:] = acc[:] * alpha + pv
    m[:] = jnp.broadcast_to(m_new, m.shape)
    l[:] = jnp.broadcast_to(l_new, l.shape)

    @pl.when(ik == nk - 1)
    def _():
        l_fin = l[:, :1]
        o_ref[0] = (acc[:] / jnp.maximum(l_fin, 1e-30)).astype(o_ref.dtype)
        # LSE broadcast across the lane tile: TPU tiling requires the last
        # two block dims be (8k, 128k), so per-row stats carry a 128-lane
        # axis (the same layout jax's reference TPU flash kernel uses).
        l_ref[0] = jnp.broadcast_to(
            m[:, :1] + jnp.log(jnp.maximum(l_fin, 1e-30)), l_ref.shape[1:])


def _dq_kernel(q_ref, k_ref, v_ref, do_ref, lse_ref, di_ref, dq_ref, dq_acc,
               *, scale, n_real, bq, bk, nk):
    ik = pl.program_id(2)

    @pl.when(ik == 0)
    def _():
        dq_acc[:] = jnp.zeros_like(dq_acc)

    q = q_ref[0]
    k = k_ref[0]
    s = jax.lax.dot_general(q, k, (((1,), (1,)), ((), ())),
                            preferred_element_type=jnp.float32) * scale
    s = jnp.where(_kv_mask(ik, bk, n_real, bq), s, _NEG_BIG)
    p = jnp.exp(s - lse_ref[0][:, :1])                 # (bq, bk)
    dp = jax.lax.dot_general(do_ref[0].astype(jnp.float32),
                             v_ref[0].astype(jnp.float32),
                             (((1,), (1,)), ((), ())),
                             preferred_element_type=jnp.float32)
    ds = p * (dp - di_ref[0][:, :1])                   # (bq, bk)
    dq_acc[:] += jax.lax.dot_general(
        ds, k.astype(jnp.float32), (((1,), (0,)), ((), ())),
        preferred_element_type=jnp.float32) * scale

    @pl.when(ik == nk - 1)
    def _():
        dq_ref[0] = dq_acc[:].astype(dq_ref.dtype)


def _dkv_kernel(q_ref, k_ref, v_ref, do_ref, lse_ref, di_ref, dk_ref, dv_ref,
                dk_acc, dv_acc, *, scale, n_real, bq, bk, nq):
    iq = pl.program_id(2)

    @pl.when(iq == 0)
    def _():
        dk_acc[:] = jnp.zeros_like(dk_acc)
        dv_acc[:] = jnp.zeros_like(dv_acc)

    ik = pl.program_id(1)
    q = q_ref[0]
    k = k_ref[0]
    s = jax.lax.dot_general(q, k, (((1,), (1,)), ((), ())),
                            preferred_element_type=jnp.float32) * scale
    s = jnp.where(_kv_mask(ik, bk, n_real, bq), s, _NEG_BIG)
    p = jnp.exp(s - lse_ref[0][:, :1])                 # (bq, bk)
    do = do_ref[0].astype(jnp.float32)
    dv_acc[:] += jax.lax.dot_general(p, do, (((0,), (0,)), ((), ())),
                                     preferred_element_type=jnp.float32)
    dp = jax.lax.dot_general(do, v_ref[0].astype(jnp.float32),
                             (((1,), (1,)), ((), ())),
                             preferred_element_type=jnp.float32)
    ds = p * (dp - di_ref[0][:, :1])
    dk_acc[:] += jax.lax.dot_general(
        ds, q.astype(jnp.float32), (((0,), (0,)), ((), ())),
        preferred_element_type=jnp.float32) * scale

    @pl.when(iq == nq - 1)
    def _():
        dk_ref[0] = dk_acc[:].astype(dk_ref.dtype)
        dv_ref[0] = dv_acc[:].astype(dv_ref.dtype)


def _pad_seq(x, block):
    n = x.shape[1]
    pad = (-n) % block
    if pad:
        x = jnp.pad(x, ((0, 0), (0, pad), (0, 0)))
    return x


def _flash_fwd_impl(q, k, v, *, block_q, block_k, interpret):
    bh, n, d = q.shape
    scale = d ** -0.5
    qp = _pad_seq(q, block_q)
    kp = _pad_seq(k, block_k)
    vp = _pad_seq(v, block_k)
    npad_q, npad_k = qp.shape[1], kp.shape[1]
    nq, nk = npad_q // block_q, npad_k // block_k

    kernel = functools.partial(_fwd_kernel, scale=scale, n_real=n,
                               bq=block_q, bk=block_k, nk=nk)
    o, lse = pl.pallas_call(
        kernel,
        grid=(bh, nq, nk),
        in_specs=[
            pl.BlockSpec((1, block_q, d), lambda b, i, j: (b, i, 0)),
            pl.BlockSpec((1, block_k, d), lambda b, i, j: (b, j, 0)),
            pl.BlockSpec((1, block_k, d), lambda b, i, j: (b, j, 0)),
        ],
        out_specs=[
            pl.BlockSpec((1, block_q, d), lambda b, i, j: (b, i, 0)),
            pl.BlockSpec((1, block_q, _LANES), lambda b, i, j: (b, i, 0)),
        ],
        out_shape=[
            jax.ShapeDtypeStruct((bh, npad_q, d), q.dtype),
            jax.ShapeDtypeStruct((bh, npad_q, _LANES), jnp.float32),
        ],
        scratch_shapes=[
            pltpu.VMEM((block_q, d), jnp.float32),
            pltpu.VMEM((block_q, _LANES), jnp.float32),
            pltpu.VMEM((block_q, _LANES), jnp.float32),
        ],
        interpret=interpret,
    )(qp, kp, vp)
    return o[:, :n], lse[:, :n, 0]


@functools.partial(jax.custom_vjp, nondiff_argnums=(3, 4, 5))
def _flash_bhd(q, k, v, block_q, block_k, interpret):
    o, _ = _flash_fwd_impl(q, k, v, block_q=block_q, block_k=block_k,
                           interpret=interpret)
    return o


def _flash_bhd_fwd(q, k, v, block_q, block_k, interpret):
    o, lse = _flash_fwd_impl(q, k, v, block_q=block_q, block_k=block_k,
                             interpret=interpret)
    return o, (q, k, v, o, lse)


def _flash_bhd_bwd(block_q, block_k, interpret, res, do):
    q, k, v, o, lse = res
    bh, n, d = q.shape
    scale = d ** -0.5
    # D_i = rowsum(dO ∘ O): tiny elementwise reduce, XLA fuses it.
    di = jnp.sum(do.astype(jnp.float32) * o.astype(jnp.float32), axis=-1)

    qp, kp, vp = (_pad_seq(x, b) for x, b in
                  ((q, block_q), (k, block_k), (v, block_k)))
    dop = _pad_seq(do, block_q)
    # Per-row stats re-enter the kernels in the 128-lane-broadcast layout
    # the tiling rules require (transient; the residual itself is compact).
    lsep = jnp.broadcast_to(_pad_seq(lse[..., None], block_q),
                            (bh, -(-n // block_q) * block_q, _LANES))
    dip = jnp.broadcast_to(_pad_seq(di[..., None], block_q), lsep.shape)
    npad_q, npad_k = qp.shape[1], kp.shape[1]
    nq, nk = npad_q // block_q, npad_k // block_k

    dq = pl.pallas_call(
        functools.partial(_dq_kernel, scale=scale, n_real=n,
                          bq=block_q, bk=block_k, nk=nk),
        grid=(bh, nq, nk),
        in_specs=[
            pl.BlockSpec((1, block_q, d), lambda b, i, j: (b, i, 0)),
            pl.BlockSpec((1, block_k, d), lambda b, i, j: (b, j, 0)),
            pl.BlockSpec((1, block_k, d), lambda b, i, j: (b, j, 0)),
            pl.BlockSpec((1, block_q, d), lambda b, i, j: (b, i, 0)),
            pl.BlockSpec((1, block_q, _LANES), lambda b, i, j: (b, i, 0)),
            pl.BlockSpec((1, block_q, _LANES), lambda b, i, j: (b, i, 0)),
        ],
        out_specs=pl.BlockSpec((1, block_q, d), lambda b, i, j: (b, i, 0)),
        out_shape=jax.ShapeDtypeStruct((bh, npad_q, d), q.dtype),
        scratch_shapes=[pltpu.VMEM((block_q, d), jnp.float32)],
        interpret=interpret,
    )(qp, kp, vp, dop, lsep, dip)

    dk, dv = pl.pallas_call(
        functools.partial(_dkv_kernel, scale=scale, n_real=n,
                          bq=block_q, bk=block_k, nq=nq),
        grid=(bh, nk, nq),
        in_specs=[
            pl.BlockSpec((1, block_q, d), lambda b, j, i: (b, i, 0)),
            pl.BlockSpec((1, block_k, d), lambda b, j, i: (b, j, 0)),
            pl.BlockSpec((1, block_k, d), lambda b, j, i: (b, j, 0)),
            pl.BlockSpec((1, block_q, d), lambda b, j, i: (b, i, 0)),
            pl.BlockSpec((1, block_q, _LANES), lambda b, j, i: (b, i, 0)),
            pl.BlockSpec((1, block_q, _LANES), lambda b, j, i: (b, i, 0)),
        ],
        out_specs=[
            pl.BlockSpec((1, block_k, d), lambda b, j, i: (b, j, 0)),
            pl.BlockSpec((1, block_k, d), lambda b, j, i: (b, j, 0)),
        ],
        out_shape=[
            jax.ShapeDtypeStruct((bh, npad_k, d), k.dtype),
            jax.ShapeDtypeStruct((bh, npad_k, d), v.dtype),
        ],
        scratch_shapes=[
            pltpu.VMEM((block_k, d), jnp.float32),
            pltpu.VMEM((block_k, d), jnp.float32),
        ],
        interpret=interpret,
    )(qp, kp, vp, dop, lsep, dip)
    return dq[:, :n], dk[:, :n], dv[:, :n]


_flash_bhd.defvjp(_flash_bhd_fwd, _flash_bhd_bwd)


def flash_attention(q: jnp.ndarray, k: jnp.ndarray, v: jnp.ndarray,
                    block_q: int = 128, block_k: int = 128,
                    interpret: bool | None = None) -> jnp.ndarray:
    """Fused attention, drop-in for ``dot_product_attention``.

    Shapes ``(B, N, H, D)`` → ``(B, N, H, D)``. ``interpret=None``:
    compiled on the TPU, interpreted on the CPU platform
    (``ops.resolve_interpret``).
    """
    interpret = resolve_interpret(interpret)
    b, n, h, d = q.shape
    # Clamp to the sequence but keep blocks 8-aligned (TPU sublane tiling);
    # _pad_seq rounds the sequence up to the block, so block==npad is legal.
    n8 = -(-max(n, 1) // 8) * 8
    block_q = min(block_q, n8)
    block_k = min(block_k, n8)

    def to_bhd(x):
        return x.transpose(0, 2, 1, 3).reshape(b * h, n, d)

    o = _flash_bhd(to_bhd(q), to_bhd(k), to_bhd(v),
                   block_q, block_k, interpret)
    return o.reshape(b, h, n, d).transpose(0, 2, 1, 3)
