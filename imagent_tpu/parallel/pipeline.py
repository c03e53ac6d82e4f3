"""GPipe-style pipeline parallelism over the mesh ``pipe`` axis.

No reference analogue (the reference is pure DP — SURVEY §2c lists PP as
"not required": its model is a single-stage ResNet, ``imagenet.py:312``);
this module makes depth a first-class sharding dimension so models larger
than one chip's HBM train by *streaming microbatches through stages*.

TPU-native design, not a port of torch pipeline APIs:

* **SPMD, not multi-controller.** Every device runs the SAME compiled
  program (``shard_map`` over the 3-D ``(data, pipe, model)`` mesh). A
  stage's identity is ``lax.axis_index("pipe")``; activations move between
  neighbouring stages with ``lax.ppermute`` — a single-hop ICI transfer,
  the cheapest collective on the torus.
* **One ``lax.scan`` of ticks.** The classic GPipe schedule — M
  microbatches through S stages in ``M + S - 1`` ticks (fill, steady
  state, drain) — is a scan whose carry is (current activation, output
  buffer). XLA compiles the whole schedule into one program; autodiff
  runs through it (``ppermute``'s transpose is the reverse permute), so
  the backward pipeline needs no hand-written schedule.
* **Layer-stacked params.** The repeated body is built with ``nn.scan``
  over layers, so its params carry a leading ``[num_layers]`` dim that
  shards over ``pipe`` (``PartitionSpec("pipe", ...)``): stage *i* holds
  layers ``[i*L/S, (i+1)*L/S)``. With ``pipe_axis=None`` the same module
  (identical param tree) just scans all layers on every device — that
  twin is used for the mesh-free init and as the numerical reference in
  tests.

Gradient semantics (see ``train.make_train_step``): the final activation
is returned via a masked ``psum`` off the last stage, so every pipe shard
computes an identical loss. Per-shard autodiff then yields ``S x`` the
true gradient for pipe-sharded (layer-stack) leaves and an
unequal-per-stage gradient for replicated leaves (embedding grads land on
stage 0 only, head grads on every stage); ``normalize_region_grads``
normalizes both: ``g / S`` for sharded leaves, ``pmean`` over the pipe
axis for replicated ones.
"""

from __future__ import annotations

from typing import Callable

import flax.linen as nn
import jax
import jax.numpy as jnp
from jax import lax
from jax.sharding import PartitionSpec as P

from imagent_tpu.cluster import PIPE_AXIS


def spec_has_axis(spec, axis: str) -> bool:
    """True if a PartitionSpec shards any dim over ``axis``."""
    if not isinstance(spec, P):
        return False
    for entry in spec:
        if entry == axis:
            return True
        if isinstance(entry, (tuple, list)) and axis in entry:
            return True
    return False


class _LayerStep(nn.Module):
    """One repeated layer, shaped ``(carry, None) -> (carry, None)`` for
    ``nn.scan`` over the stacked layer dim."""

    body: Callable[..., nn.Module]

    @nn.compact
    def __call__(self, x, _):
        return self.body()(x), None


class _PipeTick(nn.Module):
    """One tick of the GPipe schedule: receive from the previous stage
    (``ppermute``), run this stage's local layer stack, record finished
    microbatches on the last stage."""

    body: Callable[..., nn.Module]
    n_layers: int
    pipe_axis: str | None

    @nn.compact
    def __call__(self, carry, t):
        x_mb, buf, outs = carry
        layers = nn.scan(
            _LayerStep,
            variable_axes={"params": 0},
            split_rngs={"params": True},
            length=self.n_layers,
            metadata_params={nn.meta.PARTITION_NAME: "layers"},
        )(body=self.body, name="pipe_layers")
        n_mb = x_mb.shape[0]

        if self.pipe_axis is None:
            # Single-stage twin: plain microbatch loop, same param tree.
            out, _ = layers(
                lax.dynamic_index_in_dim(x_mb, t, 0, keepdims=False), None)
            outs = lax.dynamic_update_index_in_dim(outs, out, t, 0)
            return (x_mb, out, outs), None

        n_stages = lax.psum(1, self.pipe_axis)
        stage = lax.axis_index(self.pipe_axis)
        # Single-hop shift stage i -> i+1 (no wraparound: stage 0 feeds
        # from its microbatch queue, the last stage feeds the output buf).
        recv = lax.ppermute(buf, self.pipe_axis,
                            [(i, i + 1) for i in range(n_stages - 1)])
        my_mb = lax.dynamic_index_in_dim(
            x_mb, jnp.clip(t, 0, n_mb - 1), 0, keepdims=False)
        out, _ = layers(jnp.where(stage == 0, my_mb, recv), None)
        # Microbatch t emerges from the last stage at tick t + S - 1.
        idx = jnp.clip(t - (n_stages - 1), 0, n_mb - 1)
        valid = (t >= n_stages - 1) & (stage == n_stages - 1)
        cur = lax.dynamic_index_in_dim(outs, idx, 0, keepdims=False)
        outs = lax.dynamic_update_index_in_dim(
            outs, jnp.where(valid, out, cur), idx, 0)
        return (x_mb, out, outs), None


class Pipeline(nn.Module):
    """Pipeline-parallel repeat of ``body`` over ``num_layers`` layers.

    ``body`` is a zero-arg module factory (e.g. a ``functools.partial`` of
    the transformer block). With ``pipe_axis`` set — running inside
    ``shard_map`` on a mesh with that axis — the batch is cut into
    ``microbatches`` equal chunks and streamed through the stages; the
    output (all microbatches, re-concatenated) is broadcast to every
    stage via a masked ``psum`` so downstream (head/loss) code is
    oblivious to pipelining.
    """

    body: Callable[..., nn.Module]
    num_layers: int
    pipe_axis: str | None = None
    microbatches: int = 1

    @nn.compact
    def __call__(self, x):
        n_stages = 1 if self.pipe_axis is None else lax.psum(1, self.pipe_axis)
        if self.num_layers % n_stages:
            raise ValueError(
                f"num_layers {self.num_layers} not divisible by "
                f"pipeline stages {n_stages}")
        n_local = self.num_layers // n_stages
        n_mb = self.microbatches
        b = x.shape[0]
        if b % n_mb:
            raise ValueError(
                f"per-shard batch {b} not divisible by microbatches {n_mb}")
        x_mb = x.reshape(n_mb, b // n_mb, *x.shape[1:])
        n_ticks = n_mb + n_stages - 1

        ticks = nn.scan(
            _PipeTick,
            variable_broadcast="params",
            split_rngs={"params": False},
            length=n_ticks,
        )(body=self.body, n_layers=n_local, pipe_axis=self.pipe_axis,
          name="stage")
        buf0 = jnp.zeros(x_mb.shape[1:], x.dtype)
        (_, _, outs), _ = ticks((x_mb, buf0, jnp.zeros_like(x_mb)),
                                jnp.arange(n_ticks))
        if self.pipe_axis is not None:
            # Only the last stage holds real outputs (others kept zeros);
            # masked psum = broadcast-from-last-stage over the pipe axis.
            outs = lax.psum(outs, self.pipe_axis)
        return outs.reshape(b, *x.shape[1:])


def vit_pp_param_specs(params, pipe_axis: str = PIPE_AXIS,
                       tp_axis: str | None = None,
                       expert_axis: str | None = None):
    """PartitionSpec tree for a pipelined ViT param tree.

    Leaves under the ``pipe_layers`` scope are the layer-stacked encoder
    params: dim 0 (the layer dim) shards over ``pipe_axis``; with
    ``tp_axis`` also given, the head/MLP dims additionally shard
    Megatron-style (``vit_tp_param_specs`` rules shifted by the stack
    dim); with ``expert_axis``, MoE expert stacks (``wi``/``wo``,
    shapes ``[L, E, ...]``) additionally shard their expert dim — the
    pp x ep composition. Everything outside the stack (patchify,
    position embeddings, final LN, head) is replicated.
    """

    def spec(path, leaf):
        keys = [p.key for p in path if hasattr(p, "key")]
        if "pipe_layers" not in keys:
            return P()
        name_ = keys[-1] if keys else ""
        if expert_axis is not None and name_ in ("wi", "wo"):
            return P(pipe_axis, expert_axis)  # [L, E, ...]
        if tp_axis is None:
            return P(pipe_axis)
        parent = keys[-2] if len(keys) >= 2 else ""
        name = keys[-1] if keys else ""
        nd = jnp.ndim(leaf)
        if parent in ("query", "key", "value"):
            if name == "kernel":  # (L, d, H, hd)
                return P(pipe_axis, None, tp_axis, None)
            return P(pipe_axis, tp_axis, None)  # bias (L, H, hd)
        if parent == "out" and name == "kernel":  # (L, H, hd, d)
            return P(pipe_axis, tp_axis, *([None] * (nd - 2)))
        if parent == "mlp_0":
            if name == "kernel":  # (L, d, mlp)
                return P(pipe_axis, None, tp_axis)
            return P(pipe_axis, tp_axis)  # bias (L, mlp)
        if parent == "mlp_1" and name == "kernel":  # (L, mlp, d)
            return P(pipe_axis, tp_axis, None)
        return P(pipe_axis)

    return jax.tree_util.tree_map_with_path(spec, params)


def normalize_region_grads(grads, params_specs, axis: str):
    """Normalize per-shard gradients of a model whose output is
    *replicated* over ``axis`` while some params are *sharded* over it —
    the common situation for pipeline stages (this module) and
    expert-parallel MoE (``parallel/expert_parallel.py``).

    Per-shard SPMD autodiff then yields ``axis_size x`` the true gradient
    for axis-sharded leaves (the replicated loss seeds every shard; the
    broadcast collective's transpose sums the identical seeds) and
    unequal per-shard partial gradients for replicated leaves (e.g.
    embedding grads land only on pipeline stage 0, router grads only on
    the shard that sliced those tokens). Fix both: ``g / axis_size`` for
    sharded leaves; ``pmean`` over ``axis`` for replicated ones — which
    also restores the identical-across-shards property their replicated
    out_spec requires.
    """
    size = lax.psum(1, axis)
    g_leaves, tdef = jax.tree_util.tree_flatten(grads)
    s_leaves, _ = jax.tree_util.tree_flatten(params_specs)
    fixed = [
        g / size if spec_has_axis(s, axis) else lax.pmean(g, axis)
        for g, s in zip(g_leaves, s_leaves)
    ]
    return jax.tree_util.tree_unflatten(tdef, fixed)
