"""The SPMD training engine: jit-compiled train/eval steps over the mesh.

TPU-native re-design of the reference's hot loop (``train()``,
``imagenet.py:97-151``). One step of the reference costs: 1 H2D copy, a
DDP bucketed gradient allreduce overlapped with backward, 3 extra blocking
scalar allreduces for metrics (``imagenet.py:137-139``), and ≥4 device
syncs (``imagenet.py:141-148``). Here the whole step — forward, loss,
backward, gradient ``pmean``, SGD update, and metric ``psum`` — is ONE
jit-compiled program per device; XLA schedules the gradient collective to
overlap with the tail of the backward pass on ICI, and metrics come back
as a tiny replicated array fetched asynchronously (no per-step sync).

Numerical semantics match DDP exactly (SURVEY §7 "Exact DDP numerical
semantics"):

* gradients are *mean*-reduced over the data axis (DDP averages,
  ``imagenet.py:316``);
* the SGD update is computed identically on every replica (as in DDP,
  where each rank runs the same ``optimizer.step()``, ``imagenet.py:131``);
* torch-SGD update order: ``g += wd * p`` THEN momentum accumulation
  (``imagenet.py:325``: ``SGD(lr, momentum=0.9, weight_decay=1e-4)``);
* BatchNorm *normalizes with per-replica batch statistics* (DDP does not
  sync BN during forward). One deliberate deviation: running stats are
  ``pmean``-ed across replicas before being stored, instead of diverging
  per-rank with rank-0's copy checkpointed (``imagenet.py:392``) — the
  mean of the per-rank stats is strictly a better estimator and keeps the
  state replicated.
* loss/top-1/top-5 are reduced as global *sums* of per-sample terms with
  an explicit validity mask, so metrics stay exact for any batch
  remainder on any chip count — the reference silently relies on
  ``50000 % 16 == 0`` (``imagenet.py:347,355-359``).
"""

from __future__ import annotations

import functools
from typing import Any, Callable

import flax.struct
import jax
import jax.numpy as jnp
import numpy as np
import optax
from jax import lax
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

from imagent_tpu.cluster import DATA_AXIS, MODEL_AXIS, PIPE_AXIS
from imagent_tpu.ops import softmax_cross_entropy
from imagent_tpu.parallel import pmean_tree
from imagent_tpu.utils.metrics import topk_correct


class TrainState(flax.struct.PyTreeNode):
    """Replicated training state: the DDP-equivalent bundle of model
    replica + optimizer slots (``imagenet.py:312-325``).

    ``ema_params`` / ``ema_batch_stats`` (None when --ema-decay is off)
    are exponential moving averages of ``params`` and of the BatchNorm
    running stats, maintained inside the train step; evaluation runs on
    them when enabled (engine.py). The stats are averaged TOO (timm
    ModelEmaV2 semantics, which decays all buffers): the live running
    stats track the LIVE params' activation distribution, so evaluating
    EMA params against them diverges whenever the params move fast
    relative to the EMA horizon — observed catastrophically on a
    round-4 draft run (val loss 3817 mid-run at decay 0.999) before
    this field existed."""

    step: jnp.ndarray
    params: Any
    batch_stats: Any
    opt_state: Any
    ema_params: Any = None
    ema_batch_stats: Any = None


@functools.lru_cache(maxsize=None)
def make_optimizer(momentum: float = 0.9,
                   weight_decay: float = 1e-4,
                   name: str = "sgd") -> optax.GradientTransformation:
    """LR-free optimizer by name. The LR is applied by the caller each
    step (mirrors ``adjust_learning_rate`` writing ``param_groups``
    per-epoch, ``imagenet.py:154-162``), so every transformation here
    yields a *direction* the step scales by ``-lr``.

    Memoized: a transformation is immutable, and the same arguments
    give the same object, so ``create_train_state`` (static in its
    optimizer, which hashes by identity) reuses one compiled
    initializer across the runs and calls of one process.

    * ``sgd`` (parity): torch.optim.SGD order (``imagenet.py:325``) —
      grad += wd*param, then momentum trace.
    * ``nadam``: the optimizer the reference *intended* to try — its
      ``from custom_optimizers import FR, Nadam`` (``imagenet.py:36``)
      references a module missing from the repo; here Nesterov-Adam is a
      real option (L2-coupled wd, like torch.optim.NAdam's default).
    * ``adamw``: decoupled weight decay (applied after the Adam scaling,
      so it rides the caller's lr — Loshchilov & Hutter semantics).
    * ``lars``: layerwise trust-ratio scaling for large-batch SGD.
    """
    if name == "sgd":
        return optax.chain(
            optax.add_decayed_weights(weight_decay),
            optax.trace(decay=momentum, nesterov=False),
        )
    if name == "nadam":
        return optax.chain(
            optax.add_decayed_weights(weight_decay),
            optax.scale_by_adam(nesterov=True),
        )
    if name == "adamw":
        return optax.chain(
            optax.scale_by_adam(),
            optax.add_decayed_weights(weight_decay),
        )
    if name == "lars":
        # optax.lars is lr-parameterized and already NEGATES its update
        # (scale_by_learning_rate); flip the sign back so the caller's
        # uniform -lr factor applies — learning_rate=1.0 makes the
        # trust-ratio scaling compose multiplicatively with it.
        return optax.chain(
            optax.lars(learning_rate=1.0, weight_decay=weight_decay,
                       momentum=momentum),
            optax.scale(-1.0),
        )
    if name == "lamb":
        # Layerwise trust ratio over Adam (You et al. 2020) — the
        # large-batch companion to lars; same sign-flip wiring.
        return optax.chain(
            optax.lamb(learning_rate=1.0, weight_decay=weight_decay),
            optax.scale(-1.0),
        )
    raise ValueError(f"unknown optimizer {name!r}; "
                     "one of sgd|nadam|adamw|lars|lamb")


@functools.partial(jax.jit, static_argnames=(
    "model", "image_size", "optimizer", "batch_size"))
def create_train_state(model, rng: jax.Array, image_size: int,
                       optimizer: optax.GradientTransformation,
                       batch_size: int = 2) -> TrainState:
    """Initialize params/BN stats/optimizer slots (fp32) as ONE jitted
    program on the process's default device.

    Run op by op, each initializer, random draw and the throw-away
    forward pass of ``model.init`` would go to the device one at a time
    (~41 s for ResNet-50 on a v5e); compiled once, the program is the
    random draws and the constants (XLA drops the unused forward pass),
    and the persistent compile cache serves it on every later start.
    ``rng`` is a traced argument, never a constant, so one executable
    serves every seed; the model, ``image_size``, the optimizer and
    ``batch_size`` are static (the model hashes by value, an optax
    transformation by identity). Every caller gets the same values, and
    every process builds the identical state from the same seed (the
    DDP broadcast equivalence): placement onto a mesh is the caller's
    (``place_state``)."""
    variables = model.init(
        rng, jnp.zeros((batch_size, image_size, image_size, 3)), train=False)
    params = variables["params"]
    batch_stats = variables.get("batch_stats", {})
    return TrainState(
        step=jnp.zeros((), jnp.int32),
        params=params,
        batch_stats=batch_stats,
        opt_state=optimizer.init(params),
    )


def state_partition_specs(state: TrainState, params_specs) -> TrainState:
    """TrainState-shaped tree of PartitionSpecs from a params spec tree
    (tensor parallelism, ``parallel/tensor_parallel.py``; FSDP,
    ``parallel/fsdp.py``). Optimizer slots inherit their parameter's
    spec wherever the optimizer state embeds a params-shaped subtree —
    true for the SGD trace (one), Adam/NAdam (mu and nu), LARS —
    detected structurally, so any optax chain whose slots mirror the
    param tree shards correctly; scalars (Adam's count) and anything
    unrecognized stay replicated."""
    p_tdef = jax.tree_util.tree_structure(state.params)
    p_shapes = [jnp.shape(x)
                for x in jax.tree_util.tree_leaves(state.params)]

    def is_param_tree(sub) -> bool:
        try:
            if jax.tree_util.tree_structure(sub) != p_tdef:
                return False
            return [jnp.shape(x)
                    for x in jax.tree_util.tree_leaves(sub)] == p_shapes
        except (TypeError, ValueError):
            return False

    opt_specs = jax.tree_util.tree_map(
        lambda sub: params_specs if is_param_tree(sub) else P(),
        state.opt_state, is_leaf=is_param_tree)
    return TrainState(
        step=P(),
        params=params_specs,
        batch_stats=jax.tree.map(lambda _: P(), state.batch_stats),
        opt_state=opt_specs,
        # EMA leaves mirror their live twin's layout exactly.
        ema_params=None if state.ema_params is None else params_specs,
        ema_batch_stats=None if state.ema_batch_stats is None else
        jax.tree.map(lambda _: P(), state.ema_batch_stats),
    )


_INV255 = 1.0 / 255.0


def make_input_prep(mean=None, std=None, jitter_fn=None):
    """In-graph input stage for the step builders: dequantize the raw
    [0, 255]-scale wire batch (uint8 by default — see
    ``data/pipeline.py::Batch``; bf16/f32 carry the same values) to
    [0, 1] f32, apply photometric jitter on the raw RGB, then normalize
    with ``(mean, std)`` baked as compile-time literals so XLA folds
    the whole chain into the first conv's input read.

    Returns ``prep(images, key=None) -> f32 normalized batch``, or
    ``None`` when mean/std are absent — the legacy contract where
    images arrive preprocessed (bench/unit tests that build steps
    directly and feed normalized floats).

    Every wire dtype goes through the SAME f32 ops in the same order
    (uint8→f32 is exact, and uint8 values are exact in bf16), so the
    uint8 path is numerically identical to the float32 A/B path —
    pinned by tests/test_wire_format.py.
    """
    if mean is None and std is None:
        if jitter_fn is not None:
            raise ValueError("jitter_fn requires in-graph normalization: "
                             "pass mean/std (it operates on raw [0,1] RGB)")
        return None
    if mean is None or std is None:
        raise ValueError("pass both mean and std, or neither")
    m = jnp.asarray([float(v) for v in mean], jnp.float32)
    s = jnp.asarray([float(v) for v in std], jnp.float32)

    def prep(images, key=None):
        x = images.astype(jnp.float32) * jnp.float32(_INV255)
        if jitter_fn is not None and key is not None:
            x = jitter_fn(key, x)
        return (x - m) / s

    return prep


def _target_labels(labels) -> jnp.ndarray:
    """The primary (accuracy-bearing) labels: mixed batches carry a
    ``(y_a, y_b, lam)`` triple (ops/mixing.py) whose first entry is the
    original label; plain batches carry the int array itself."""
    return labels[0] if isinstance(labels, tuple) else labels


def make_loss_fn(model, label_smoothing: float = 0.0,
                 aux_loss_weight: float = 0.01) -> Callable:
    """The shared training objective: softmax CE (+ any sown aux losses,
    e.g. the MoE load-balancing term) — used by BOTH the explicit
    shard_map step and the FSDP auto step so the semantics can't drift.
    Returns ``loss, (logits, per_sample, new_batch_stats)``.

    ``labels`` is either a ``(B,)`` int array or a MixUp/CutMix
    ``(y_a, y_b, lam)`` triple (ops/mixing.py): the mixed objective is
    the convex combination of the two hard-label CEs — identical to CE
    against the mixed soft label, without materializing one-hots."""

    def loss_fn(params, batch_stats, images, labels):
        logits, mutated = model.apply(
            {"params": params, "batch_stats": batch_stats},
            images, train=True, mutable=["batch_stats", "intermediates"])
        if isinstance(labels, tuple):
            y_a, y_b, lam = labels
            per_sample = (
                lam * softmax_cross_entropy(logits, y_a, label_smoothing)
                + (1.0 - lam)
                * softmax_cross_entropy(logits, y_b, label_smoothing))
        else:
            per_sample = softmax_cross_entropy(logits, labels,
                                               label_smoothing)
        loss = per_sample.mean()
        aux = jax.tree_util.tree_leaves(mutated.get("intermediates", {}))
        if aux:  # static: sown aux losses (MoE load balancing)
            loss = loss + aux_loss_weight * (sum(aux) / len(aux))
        return loss, (logits, per_sample,
                      mutated.get("batch_stats", {}))

    return loss_fn


def masked_eval_metrics(logits, labels, mask) -> jnp.ndarray:
    """``[loss_sum, top1_cnt, top5_cnt, n]`` for one batch with a
    per-sample validity mask (padded eval remainders contribute nothing
    — SURVEY §7 "Eval sharding correctness"). Top-k membership via the
    rank of the target logit (strictly-greater count), the shared metric
    body of both eval paths. ``mask`` arrives as uint8 on the wire
    (data/pipeline.py) and is cast here, once, where floats are needed —
    a uint8 sum would wrap at 256 valid rows per shard."""
    mask = mask.astype(jnp.float32)
    per_sample = softmax_cross_entropy(logits, labels) * mask
    target_logit = jnp.take_along_axis(
        logits.astype(jnp.float32),
        labels[:, None].astype(jnp.int32), axis=1)
    rank = jnp.sum(logits.astype(jnp.float32) > target_logit, axis=1)
    c1 = jnp.sum((rank < 1) * mask)
    c5 = jnp.sum((rank < 5) * mask)
    return jnp.stack([per_sample.sum(), c1, c5, mask.sum()])


# Health scalars appended past the classic [loss_sum, top1, top5, n]
# metric head when the step builders get health_stats=True — order is
# the wire format the host-side monitor reads (telemetry/health.py).
HEALTH_FIELDS = ("grad_norm", "param_norm", "update_ratio")


def _sq_sum(tree) -> jnp.ndarray:
    """One reduced fp32 scalar: the sum of squares over every leaf.
    The primitive both the non-finite guard and the health stats are
    built from — non-finite values propagate into it, and its sqrt is
    the tree's global L2 norm."""
    leaves = jax.tree_util.tree_leaves(tree)
    return sum((jnp.sum(jnp.square(g.astype(jnp.float32)))
                for g in leaves), jnp.float32(0.0))


def _nonfinite_local(gnorm2, metrics) -> jnp.ndarray:
    """Scalar bool: this shard's step produced a non-finite loss or
    gradient. ``gnorm2`` is the gradient tree's ``_sq_sum`` (shared
    with the health stats, so the guard pays for it exactly once) —
    non-finite values propagate into the norm, so a single reduced
    scalar answers for the whole tree (an fp32 overflow of the norm
    itself flags the step too, which is the right call: such a step is
    garbage either way)."""
    return jnp.logical_not(jnp.isfinite(gnorm2)
                           & jnp.all(jnp.isfinite(metrics)))


def _sq_sum_normalized(tree, overcount) -> jnp.ndarray:
    """``_sq_sum`` with each leaf's square-sum divided by its
    replication factor over the health psum axes (``overcount``, a
    matching tree of static fp32 scalars from ``_health_overcounts``):
    the subsequent psum then yields the EXACT global square-sum for
    replicated and sharded leaves alike."""
    leaves = jax.tree_util.tree_leaves(tree)
    factors = jax.tree_util.tree_leaves(overcount)
    return sum((jnp.sum(jnp.square(g.astype(jnp.float32))) / f
                for g, f in zip(leaves, factors)), jnp.float32(0.0))


def _health_overcounts(param_specs, mesh, axes):
    """Per-leaf replication factor of the health square-sums over the
    psum ``axes``: the product of the sizes of every axis the leaf's
    PartitionSpec does NOT name (a replicated copy per shard). Sharded
    leaves get 1.0 — their windows already sum to the global value.
    Static fp32 constants, closed over by the step (no runtime cost
    beyond one scalar divide per leaf)."""
    sizes = {a: int(mesh.shape[a]) for a in axes}

    def factor(spec):
        named: set = set()
        if spec is not None:
            for entry in spec:
                if entry is None:
                    continue
                if isinstance(entry, (tuple, list)):
                    named.update(entry)
                else:
                    named.add(entry)
        f = 1.0
        for a, s in sizes.items():
            if a not in named:
                f *= s
        return jnp.float32(f)

    return jax.tree.map(factor, param_specs,
                        is_leaf=lambda x: x is None or isinstance(x, P))


def _health_stats(gnorm2, params, new_params, reduce_axes=None,
                  overcount=None) -> jnp.ndarray:
    """``[grad_norm, param_norm, update_ratio]`` (``HEALTH_FIELDS``)
    computed in-graph from square-sums the step already holds — the
    model-health tail of the replicated metric vector. No host sync:
    these three floats ride the same lagged D2H fetch as the loss.

    ``reduce_axes`` (the explicit shard_map path): per-shard square
    sums are ``psum``-ed over the model/pipe axes so sharded leaves
    contribute exactly once. On the pure data-parallel path both axes
    are size 1 and the psum is the identity (norms exact). In
    model-parallel configs a leaf REPLICATED over a reduce axis would
    be counted axis-size times; ``overcount`` (the per-leaf factor
    tree from ``_health_overcounts``, derived from the state's
    PartitionSpecs) divides that inflation out BEFORE the psum, so the
    series read identically across DP and TP runs — EWMAs, spike
    detection, status.json, and the OpenMetrics gauges see the same
    numbers either way. ``gnorm2`` must already be normalized by the
    caller when ``overcount`` is set (it is shared with the non-finite
    guard, which needs the raw un-normalized scalar).

    Non-finite inputs are passed through untouched: on a guarded-out
    step the norms carry the explosion's magnitude (or its NaN) to the
    flight recorder, while the host keys the skip on n == 0 as always.
    """
    if overcount is None:
        pnorm2 = _sq_sum(params)
        dnorm2 = _sq_sum(jax.tree.map(
            lambda new, old: new.astype(jnp.float32)
            - old.astype(jnp.float32), new_params, params))
    else:
        pnorm2 = _sq_sum_normalized(params, overcount)
        dnorm2 = _sq_sum_normalized(jax.tree.map(
            lambda new, old: new.astype(jnp.float32)
            - old.astype(jnp.float32), new_params, params), overcount)
    if reduce_axes is not None:
        gnorm2 = lax.psum(gnorm2, reduce_axes)
        pnorm2 = lax.psum(pnorm2, reduce_axes)
        dnorm2 = lax.psum(dnorm2, reduce_axes)
    pnorm = jnp.sqrt(pnorm2)
    return jnp.stack([jnp.sqrt(gnorm2), pnorm,
                      jnp.sqrt(dnorm2) / (pnorm + jnp.float32(1e-12))])


def _skip_if_bad(ok, new_tree, old_tree):
    """Per-leaf select: keep the freshly-computed leaf on a finite step,
    the pre-step leaf otherwise — the in-graph half of the non-finite
    step guard (no host sync; the engine reads the verdict from the
    zeroed metric vector, see ``make_train_step``)."""
    return jax.tree.map(lambda new, old: jnp.where(ok, new, old),
                        new_tree, old_tree)


def _grads_and_metrics(grad_fn, params, batch_stats, images, labels):
    """One batch: (grads, [loss_sum, top1, top5, n], new_batch_stats).
    On mixed batches the loss is the mixed objective; top-k counts
    against the primary label (the convention for mixup training)."""
    (_, (logits, per_sample, new_bs)), grads = grad_fn(
        params, batch_stats, images, labels)
    targets = _target_labels(labels)
    c1, c5 = topk_correct(logits, targets)
    metrics = jnp.stack([per_sample.sum(), c1, c5,
                         jnp.float32(targets.shape[0])])
    return grads, metrics, new_bs


def _scan_microbatches(grad_fn, params, batch_stats, images_k, labels_k,
                       grad_accum):
    """Shared accumulation scan over pre-sliced (K, B, ...) micro-batch
    arrays — ONE implementation for both the explicit shard_map step and
    the FSDP auto step, so the semantics can't drift. Gradients come
    back as the mean of per-micro means (== mean over the full batch at
    equal micro sizes, DDP's averaging); metrics as sums; BatchNorm
    statistics chain through the scan."""

    def micro(carry, xs):
        bs, grads_acc, metrics_acc = carry
        im, lb = xs
        grads, m, bs = _grads_and_metrics(grad_fn, params, bs, im, lb)
        grads_acc = jax.tree.map(jnp.add, grads_acc, grads)
        return (bs, grads_acc, metrics_acc + m), None

    # labels_k may be a (y_a, y_b, lam) triple (mixed batch) — scan
    # slices pytree xs leaf-wise, so micro() sees the per-micro triple.
    zeros = jax.tree.map(jnp.zeros_like, params)
    (new_bs, grads_sum, metrics), _ = lax.scan(
        micro, (batch_stats, zeros, jnp.zeros((4,), jnp.float32)),
        (images_k, labels_k))
    grads = jax.tree.map(lambda g: g / grad_accum, grads_sum)
    return grads, metrics, new_bs


def make_train_step(model, optimizer: optax.GradientTransformation,
                    mesh: Mesh, label_smoothing: float = 0.0,
                    seq_parallel: bool = False,
                    state_specs: TrainState | None = None,
                    grad_accum: int = 1,
                    pipe_axis: str | None = None,
                    expert_parallel: bool = False,
                    aux_loss_weight: float = 0.01,
                    zero1: bool = False, momentum: float = 0.9,
                    weight_decay: float = 1e-4,
                    mix_fn: Callable | None = None,
                    mix_seed: int = 0,
                    ema_decay: float = 0.0,
                    jitter_fn: Callable | None = None,
                    mean=None, std=None,
                    health_stats: bool = False) -> Callable:
    """Build the jitted SPMD train step.

    ``health_stats``: append ``HEALTH_FIELDS`` (global grad-norm,
    param-norm, update-ratio ‖Δp‖/‖p‖) to the replicated metric
    vector, computed inside the compiled step from the square-sums the
    non-finite guard already pays for — model-health observability
    with zero added host syncs (the engine consumes them on the same
    ``_GUARD_LAG`` lagged frontier; see ``telemetry/health.py``).

    ``mean``/``std`` (both or neither): enable the in-graph input stage
    (``make_input_prep``) — the batch arrives on the raw [0, 255] wire
    scale (uint8 by default) and dequantize → jitter-on-raw-RGB →
    normalize run inside the compiled step with the constants folded by
    XLA. Without them the legacy contract holds: images arrive
    preprocessed (direct-build unit tests, device-resident benches).

    ``shard_map`` over the ``data`` axis gives each device its batch shard
    and a replicated view of the state — the exact DDP execution model,
    expressed as one XLA program. Signature::

        new_state, metrics = step(state, images, labels, lr)

    ``metrics`` is a replicated ``[loss_sum, top1_cnt, top5_cnt, n]``
    vector; the host-side meters divide (``AverageMeter`` semantics,
    ``imagenet.py:143-145``) without forcing a device sync.

    Non-finite step guard (resilience subsystem): when the loss or any
    gradient is NaN/Inf, the update is skipped IN-GRAPH (params,
    optimizer slots, BN stats and EMA all keep their pre-step values;
    ``step`` still advances) and the metric vector comes back all-zero —
    ``n == 0`` is impossible for a real step, so it doubles as the
    bad-step flag without changing the vector's shape or adding any
    per-step host sync. Rollback policy on repeated bad steps lives in
    ``engine.train_one_epoch``.

    ``grad_accum`` splits each device's batch into that many sequential
    micro-batches inside the compiled step (``lax.scan``): one optimizer
    update and ONE gradient collective per step regardless of K, trading
    activation memory for wall-clock — the standard way to reach the
    reference's global-batch-2048 geometry (``imagenet.py:443``) on few
    chips. Gradients average over the full effective batch (exact DDP
    semantics); BatchNorm running stats chain through the micro-batches.

    ``pipe_axis``: set (with matching ``state_specs``) for a
    pipeline-parallel model (``parallel/pipeline.py``) — applies the
    per-shard gradient normalization (``normalize_region_grads``).

    ``expert_parallel``: set (with matching ``state_specs``) for a MoE
    model with experts sharded over the model axis
    (``parallel/expert_parallel.py``) — same normalization, model axis.

    Models that sow auxiliary losses into the ``intermediates``
    collection (the MoE router's load-balancing term) contribute
    ``aux_loss_weight x`` their mean to the objective; reported metrics
    remain pure cross-entropy.

    ``zero1``: optimizer state sharded over the data axis
    (``parallel/zero.py``); the ``optimizer`` argument is ignored and a
    torch-order SGD(momentum, weight_decay) runs on each shard's slice —
    numerically identical to the replicated path. ``state.opt_state``
    must be the flat buffer from ``zero.init_opt_state``.

    ``mix_fn`` (ops/mixing.make_mix_fn): MixUp/CutMix applied in-graph
    to each device's batch shard before the forward pass. The PRNG key
    is ``fold_in(key(mix_seed), state.step)`` — replicated across
    devices (every model/pipe shard of the same data rows mixes
    identically) and a pure function of the step, so preemption+resume
    replays the identical augmentation sequence.
    """
    if (pipe_axis is not None or expert_parallel) and state_specs is None:
        raise ValueError("pipe_axis / expert_parallel require state_specs "
                         "(the sharded param layout)")
    # Axes over which the model's output is replicated while some params
    # shard (pipeline stages / MoE experts) — each needs grad fixup.
    region_axes = ([pipe_axis] if pipe_axis is not None else []) + \
        ([MODEL_AXIS] if expert_parallel else [])

    loss_fn = make_loss_fn(model, label_smoothing, aux_loss_weight)
    grad_fn = jax.value_and_grad(loss_fn, has_aux=True)
    prep = make_input_prep(mean, std, jitter_fn)
    # Health-norm replication factors over the (pipe, model) psum axes,
    # from the params' PartitionSpecs: with a real model axis a
    # replicated leaf would otherwise be counted axis-size times in
    # grad/param norms, making a TP run's health series read ~sqrt(tp)x
    # a DP run's. None on the pure-DP path (both axes size 1 — exact
    # already) so its compiled graph is untouched.
    health_overcount = None
    if (health_stats and state_specs is not None
            and int(mesh.shape[PIPE_AXIS]) * int(mesh.shape[MODEL_AXIS])
            > 1):
        health_overcount = _health_overcounts(
            state_specs.params, mesh, (PIPE_AXIS, MODEL_AXIS))

    def accumulate(params, batch_stats, images, labels):
        """(grads_mean, metrics_sum, new_batch_stats) over K micro-batches."""
        if grad_accum <= 1:
            return _grads_and_metrics(grad_fn, params, batch_stats,
                                      images, labels)
        return _scan_microbatches(
            grad_fn, params, batch_stats,
            images.reshape(grad_accum, -1, *images.shape[1:]),
            jax.tree.map(lambda a: a.reshape(grad_accum, -1), labels),
            grad_accum)

    def per_device_step(state: TrainState, images, labels, lr):
        if jitter_fn is not None or mix_fn is not None:
            key = jax.random.fold_in(jax.random.key(mix_seed), state.step)
        if prep is not None:
            jkey = None
            if jitter_fn is not None:  # ops/jitter.py, on raw RGB before
                # normalize and before mixing — torchvision order:
                # photometric jitter on each source image, then the
                # batch-level mix. Jitter factors are PER-IMAGE, so
                # decorrelate across data shards (fold in the data
                # position; model/pipe shards of the same rows still
                # agree) — unlike the mix, whose lam is per-batch by
                # design and stays replicated.
                jkey = jax.random.fold_in(
                    jax.random.fold_in(key, 1),
                    lax.axis_index(DATA_AXIS))
            images = prep(images, jkey)
        if mix_fn is not None:
            # Key layout note: with jitter off this is the same key
            # round-2 runs used — their checkpoints resume with the
            # identical mixing replay. Mixing stays on the NORMALIZED
            # batch: normalization is affine and the convex mix
            # commutes with it, so the round-2 numerics are preserved.
            mkey = (key if jitter_fn is None
                    else jax.random.fold_in(key, 2))
            images, labels = mix_fn(mkey, images, labels)
        grads, local, new_bs = accumulate(
            state.params, state.batch_stats, images, labels)

        # DDP gradient averaging (imagenet.py:316) — one fused allreduce.
        grads = pmean_tree(grads, DATA_AXIS)
        new_bs = pmean_tree(new_bs, DATA_AXIS)
        if seq_parallel:
            # Sequence-parallel models: the loss output is REPLICATED over
            # the model axis (pmean readout), so SPMD autodiff seeds all P
            # identical losses — each shard's grad is P x its true share
            # of d(loss)/d(params). pmean both de-duplicates the P seeds
            # and sums the per-shard partial contributions:
            #   (1/P) * sum_i P * dL/dp_i = sum_i dL/dp_i = dL/dparams.
            grads = pmean_tree(grads, MODEL_AXIS)
        for axis in region_axes:
            from imagent_tpu.parallel.pipeline import normalize_region_grads
            grads = normalize_region_grads(grads, state_specs.params, axis)

        # Non-finite step guard (resilience subsystem): one NaN step must
        # not poison the weights for the rest of a 100-epoch run. The
        # verdict is agreed across ALL mesh axes (model/pipe shards hold
        # different param slices, so one shard can go non-finite alone;
        # a split-brain select would desynchronize the replicas), then
        # the update is skipped in-graph — no host sync; the engine
        # reads the verdict from the zeroed metric vector (n == 0, which
        # no real step can produce) and handles rollback policy.
        gnorm2 = _sq_sum(grads)
        bad = _nonfinite_local(gnorm2, local).astype(jnp.float32)
        ok = lax.psum(bad, (DATA_AXIS, PIPE_AXIS, MODEL_AXIS)) == 0.0

        if zero1:
            from imagent_tpu.parallel.zero import sgd_momentum_shard_update
            new_params, new_opt_state = sgd_momentum_shard_update(
                state.params, grads, state.opt_state, lr,
                momentum, weight_decay)
        else:
            updates, new_opt_state = optimizer.update(
                grads, state.opt_state, state.params)
            updates = jax.tree.map(lambda u: -lr * u, updates)
            new_params = optax.apply_updates(state.params, updates)

        metrics = lax.psum(jnp.where(ok, local, jnp.zeros_like(local)),
                           DATA_AXIS)
        if health_stats:
            # Before the skip-select below: the norms describe the
            # ATTEMPTED update (on a guarded-out step they carry the
            # explosion's magnitude to the flight recorder; the n == 0
            # head still tells the host the update never applied).
            # Post-pmean grads and replicated params are identical on
            # every data shard, so only model/pipe need reducing. With
            # a real model axis the grad square-sum is recomputed
            # per-leaf with the replication factors divided out (the
            # guard above needs the raw gnorm2, so it can't be shared
            # here) — DP/TP health parity is pinned by
            # tests/test_tp_pod.py.
            metrics = jnp.concatenate([metrics, _health_stats(
                (gnorm2 if health_overcount is None
                 else _sq_sum_normalized(grads, health_overcount)),
                state.params, new_params,
                reduce_axes=(PIPE_AXIS, MODEL_AXIS),
                overcount=health_overcount)])

        new_ema = state.ema_params
        new_ema_bs = state.ema_batch_stats
        if ema_decay > 0.0:  # timm ModelEma semantics: no bias correction
            if state.ema_params is None:
                raise ValueError(
                    "ema_decay > 0 but state.ema_params is None — "
                    "initialize it first, e.g. state.replace(ema_params="
                    "jax.tree.map(jnp.array, state.params)) "
                    "(engine.run does this for --ema-decay)")
            new_ema = jax.tree.map(
                lambda e, p: ema_decay * e + (1.0 - ema_decay) * p,
                state.ema_params, new_params)
            if state.ema_batch_stats is not None:  # None: legacy resume
                new_ema_bs = jax.tree.map(
                    lambda e, s: ema_decay * e + (1.0 - ema_decay) * s,
                    state.ema_batch_stats, new_bs)

        # Skipped step: every state component keeps its pre-step value
        # (``step`` still advances — the batch WAS consumed, so the
        # resume bookkeeping and the per-step augmentation stream stay
        # aligned with the loader's deterministic order).
        new_params = _skip_if_bad(ok, new_params, state.params)
        new_opt_state = _skip_if_bad(ok, new_opt_state, state.opt_state)
        new_bs = _skip_if_bad(ok, new_bs, state.batch_stats)
        if ema_decay > 0.0:
            new_ema = _skip_if_bad(ok, new_ema, state.ema_params)
            if new_ema_bs is not None:
                new_ema_bs = _skip_if_bad(ok, new_ema_bs,
                                          state.ema_batch_stats)

        new_state = state.replace(
            step=state.step + 1, params=new_params,
            batch_stats=new_bs, opt_state=new_opt_state,
            ema_params=new_ema, ema_batch_stats=new_ema_bs)
        return new_state, metrics

    st = state_specs if state_specs is not None else P()
    sharded = jax.shard_map(
        per_device_step, mesh=mesh,
        in_specs=(st, P(DATA_AXIS), P(DATA_AXIS), P()),
        out_specs=(st, P()),
        check_vma=False)
    return jax.jit(sharded, donate_argnums=(0,))


def make_train_step_auto(model, optimizer: optax.GradientTransformation,
                         mesh: Mesh, state_specs: TrainState,
                         label_smoothing: float = 0.0,
                         aux_loss_weight: float = 0.01,
                         grad_accum: int = 1,
                         mix_fn: Callable | None = None,
                         mix_seed: int = 0,
                         ema_decay: float = 0.0,
                         jitter_fn: Callable | None = None,
                         mean=None, std=None,
                         health_stats: bool = False) -> Callable:
    """FSDP train step via the XLA SPMD partitioner (``parallel/fsdp.py``).

    ``health_stats``: same ``HEALTH_FIELDS`` metric tail as
    ``make_train_step`` — here the partitioner sees logical arrays, so
    the square-sums are globally exact with no explicit psum.

    ``mean``/``std``: same in-graph input stage as ``make_train_step``
    (raw-scale wire batch dequantized, jittered, normalized in-graph).

    A PLAIN jitted function — no ``shard_map``, no axis names. Param and
    momentum shardings come from ``state_specs`` (each leaf split over
    the data axis); the batch is sharded over ``data``; XLA inserts the
    per-layer all-gathers, the gradient reduce-scatters, and the metric
    reductions, overlapping them with compute.

    ``grad_accum``: K sequential micro-batches inside the compiled step
    (``lax.scan``), one optimizer update — the north-star geometry
    (global-batch 2048 on few chips, ``imagenet.py:443``) under FSDP.
    The global batch arrives as each device's K micro-shards
    concatenated (the same loader layout the explicit path uses); the
    reshape below regroups it per-microbatch along sharding boundaries,
    so no resharding collective is inserted.

    Numerics note vs the explicit path: loss/grads are means over the
    GLOBAL (micro)batch (identical to DDP's mean-of-means at equal
    shard sizes), and BatchNorm statistics are computed over the global
    micro-batch (SyncBN semantics) rather than per-replica — the one
    deliberate difference, since the partitioner sees a single logical
    batch.
    """
    from imagent_tpu.parallel.fsdp import shardings_from_specs

    loss_fn = make_loss_fn(model, label_smoothing, aux_loss_weight)
    grad_fn = jax.value_and_grad(loss_fn, has_aux=True)
    prep = make_input_prep(mean, std, jitter_fn)
    n_data = mesh.shape[DATA_AXIS]

    def accumulate_auto(params, batch_stats, images, labels):
        if grad_accum <= 1:
            return _grads_and_metrics(grad_fn, params, batch_stats,
                                      images, labels)
        g = images.shape[0]
        b_loc = g // (n_data * grad_accum)
        # (n*K*b_loc, ...) -> (n, K, b_loc, ...) splits the sharded dim
        # on its shard boundary (device i holds rows [i*K*b_loc, ...));
        # the swap to (K, n, b_loc, ...) then merges back to per-micro
        # global batches (K, n*b_loc, ...) still sharded over `data`.
        im = images.reshape(n_data, grad_accum, b_loc, *images.shape[1:])
        lb = jax.tree.map(
            lambda a: jnp.swapaxes(
                a.reshape(n_data, grad_accum, b_loc), 0, 1
            ).reshape(grad_accum, n_data * b_loc), labels)
        im = jnp.swapaxes(im, 0, 1).reshape(
            grad_accum, n_data * b_loc, *images.shape[1:])
        return _scan_microbatches(grad_fn, params, batch_stats, im, lb,
                                  grad_accum)

    def step(state: TrainState, images, labels, lr):
        if jitter_fn is not None or mix_fn is not None:
            # Global-batch mixing (the partitioner sees one logical
            # batch): the reversed-batch pairing spans devices — XLA
            # inserts the permute — consistent with this path's
            # global-batch BN/loss semantics. Jitter draws per-image
            # factors over the global batch in one shot (no per-shard
            # decorrelation needed here).
            key = jax.random.fold_in(jax.random.key(mix_seed), state.step)
        if prep is not None:
            images = prep(images, jax.random.fold_in(key, 1)
                          if jitter_fn is not None else None)
        if mix_fn is not None:
            mkey = (key if jitter_fn is None
                    else jax.random.fold_in(key, 2))
            images, labels = mix_fn(mkey, images, labels)
        grads, metrics, new_bs = accumulate_auto(
            state.params, state.batch_stats, images, labels)
        # Non-finite step guard — same semantics as the explicit path;
        # the partitioner sees logical arrays, so no psum is needed for
        # the verdict to be globally agreed.
        gnorm2 = _sq_sum(grads)
        ok = jnp.logical_not(_nonfinite_local(gnorm2, metrics))
        metrics = jnp.where(ok, metrics, jnp.zeros_like(metrics))
        updates, new_opt_state = optimizer.update(
            grads, state.opt_state, state.params)
        new_params = optax.apply_updates(
            state.params, jax.tree.map(lambda u: -lr * u, updates))
        if health_stats:
            metrics = jnp.concatenate([
                metrics, _health_stats(gnorm2, state.params, new_params)])
        new_ema = state.ema_params
        new_ema_bs = state.ema_batch_stats
        if ema_decay > 0.0:
            if state.ema_params is None:
                raise ValueError(
                    "ema_decay > 0 but state.ema_params is None — "
                    "initialize it first, e.g. state.replace(ema_params="
                    "jax.tree.map(jnp.array, state.params)) "
                    "(engine.run does this for --ema-decay)")
            new_ema = jax.tree.map(
                lambda e, p: ema_decay * e + (1.0 - ema_decay) * p,
                state.ema_params, new_params)
            if state.ema_batch_stats is not None:  # None: legacy resume
                new_ema_bs = jax.tree.map(
                    lambda e, s: ema_decay * e + (1.0 - ema_decay) * s,
                    state.ema_batch_stats, new_bs)
        new_params = _skip_if_bad(ok, new_params, state.params)
        new_opt_state = _skip_if_bad(ok, new_opt_state, state.opt_state)
        new_bs = _skip_if_bad(ok, new_bs, state.batch_stats)
        if ema_decay > 0.0:
            new_ema = _skip_if_bad(ok, new_ema, state.ema_params)
            if new_ema_bs is not None:
                new_ema_bs = _skip_if_bad(ok, new_ema_bs,
                                          state.ema_batch_stats)
        return state.replace(step=state.step + 1, params=new_params,
                             batch_stats=new_bs,
                             opt_state=new_opt_state,
                             ema_params=new_ema,
                             ema_batch_stats=new_ema_bs), metrics

    state_sh = shardings_from_specs(mesh, state_specs)
    batch_sh = NamedSharding(mesh, P(DATA_AXIS))
    repl = NamedSharding(mesh, P())
    return jax.jit(step,
                   in_shardings=(state_sh, batch_sh, batch_sh, repl),
                   out_shardings=(state_sh, repl),
                   donate_argnums=(0,))


def make_eval_step_auto(model, mesh: Mesh,
                        state_specs: TrainState,
                        mean=None, std=None) -> Callable:
    """FSDP eval step (plain jit + shardings; masked, exact on any chip
    count like ``make_eval_step``). ``mean``/``std`` enable the same
    in-graph dequantize+normalize stage as the train steps."""
    from imagent_tpu.parallel.fsdp import shardings_from_specs

    prep = make_input_prep(mean, std)

    def eval_step(state: TrainState, images, labels, mask):
        if prep is not None:
            images = prep(images)
        logits = model.apply(
            {"params": state.params, "batch_stats": state.batch_stats},
            images, train=False)
        return masked_eval_metrics(logits, labels, mask)

    state_sh = shardings_from_specs(mesh, state_specs)
    batch_sh = NamedSharding(mesh, P(DATA_AXIS))
    repl = NamedSharding(mesh, P())
    return jax.jit(eval_step,
                   in_shardings=(state_sh, batch_sh, batch_sh, batch_sh),
                   out_shardings=repl)


def make_eval_step(model, mesh: Mesh,
                   state_specs: TrainState | None = None,
                   mean=None, std=None) -> Callable:
    """Jitted eval step (reference ``validate()``, ``imagenet.py:166-210``).

    Takes an explicit per-sample validity ``mask`` (uint8 on the wire,
    cast in-graph) so padded remainder batches contribute nothing —
    exact on any chip count (SURVEY §7 "Eval sharding correctness").
    Returns the same replicated ``[loss_sum, top1_cnt, top5_cnt, n]``
    vector as the train step. ``mean``/``std`` enable the in-graph
    dequantize+normalize stage (``make_input_prep``).
    """

    prep = make_input_prep(mean, std)

    def per_device_eval(state: TrainState, images, labels, mask):
        if prep is not None:
            images = prep(images)
        logits = model.apply(
            {"params": state.params, "batch_stats": state.batch_stats},
            images, train=False)
        return lax.psum(masked_eval_metrics(logits, labels, mask),
                        DATA_AXIS)

    st = state_specs if state_specs is not None else P()
    sharded = jax.shard_map(
        per_device_eval, mesh=mesh,
        in_specs=(st, P(DATA_AXIS), P(DATA_AXIS), P(DATA_AXIS)),
        out_specs=P(),
        check_vma=False)
    return jax.jit(sharded)


def snapshotable(state: TrainState) -> bool:
    """Whether every leaf's full value is reachable from THIS host
    without a collective — the precondition for the async checkpoint
    snapshot (``checkpoint.save_async``). True for single-host states
    and multi-host *replicated* states (every device holds the whole
    value, so one addressable shard is the array); False once a leaf is
    genuinely sharded across hosts (multi-host FSDP/TP), where only a
    collective gather could reassemble it."""
    for x in jax.tree_util.tree_leaves(state):
        if not isinstance(x, jax.Array):
            continue
        if x.is_fully_addressable:
            continue
        sharding = getattr(x, "sharding", None)
        if sharding is None or not sharding.is_fully_replicated:
            return False
    return True


def host_snapshot(state: TrainState) -> TrainState:
    """Copy the state to host numpy — the blocking slice of an async
    checkpoint. Runs on the MAIN thread before the next train step can
    donate these buffers; everything after (serialization, commit,
    manifest hashing) works on this copy from a background thread with
    zero device or collective traffic. Requires ``snapshotable``."""
    def fetch(x):
        if isinstance(x, jax.Array) and not x.is_fully_addressable:
            # Fully replicated across hosts: any one local shard IS the
            # whole array (np.asarray of the global view would demand
            # full addressability).
            return np.asarray(x.addressable_data(0))
        return np.asarray(x)

    return jax.tree.map(fetch, state)


def host_shard_snapshot(state: TrainState,
                        skip_replicated: bool = False) -> list[dict]:
    """Per-host shard dump for the SHARDED snapshot format
    (``imagent_tpu/shardfmt.py``) — the sharded generalization of
    ``host_snapshot``: every leaf of the tree appears once, carrying
    THIS host's addressable shards as ``(start, stop, numpy)`` index
    windows against the leaf's GLOBAL shape (exact-duplicate windows
    from local replicas deduplicated; a leaf this host holds no shard
    of contributes an empty window list, so every dump still
    enumerates the full keypath/shape table the coverage check needs).

    ``skip_replicated`` is the POD-level dedup for the normal commit
    paths: every rank but the lead passes it so fully-pod-replicated
    leaves (host scalars, and e.g. the ENTIRE parameter tree under
    ZeRO-1) ride only the lead's dump — an M-host pod must not write
    M full copies of a multi-GB replicated tree into every commit.
    ``save_emergency`` never skips: there the designated writer may be
    the corpse, so every survivor's dump must be able to cover.

    This is the blocking slice of a sharded async checkpoint: pure
    device→host copies of shards this host ALREADY holds — no
    collectives, no constraint on what the rest of the pod is doing,
    callable from a degraded pod with dead peers."""
    entries = []
    for keypath, leaf in jax.tree_util.tree_flatten_with_path(state)[0]:
        key = jax.tree_util.keystr(keypath)
        if not isinstance(leaf, jax.Array):
            arr = np.asarray(leaf)
            entries.append({
                "key": key, "dtype": np.dtype(arr.dtype).name,
                "shape": list(arr.shape),
                "windows": ([] if skip_replicated else
                            [((0,) * arr.ndim, tuple(arr.shape), arr)])})
            continue
        gshape = tuple(int(d) for d in leaf.shape)
        sharding = getattr(leaf, "sharding", None)
        if (skip_replicated and sharding is not None
                and sharding.is_fully_replicated):
            entries.append({"key": key,
                            "dtype": np.dtype(leaf.dtype).name,
                            "shape": list(gshape), "windows": []})
            continue
        seen: set = set()
        windows = []
        for shard in leaf.addressable_shards:
            idx = shard.index  # tuple of slices into the global array
            start = tuple(int(s.start or 0) for s in idx)
            stop = tuple(int(s.stop) if s.stop is not None
                         else gshape[d] for d, s in enumerate(idx))
            if (start, stop) in seen:
                continue  # local replica: identical window, once only
            seen.add((start, stop))
            windows.append((start, stop, np.asarray(shard.data)))
        entries.append({"key": key,
                        "dtype": np.dtype(leaf.dtype).name,
                        "shape": list(gshape), "windows": windows})
    return entries


def replicate_state(state: TrainState, mesh: Mesh) -> TrainState:
    """Place the state replicated over the mesh — the DDP initial
    parameter broadcast (``imagenet.py:316``) done by sharding layout.

    Multi-host placement goes through
    ``make_array_from_process_local_data``, NOT ``jax.device_put``:
    device_put of a host array onto a non-fully-addressable sharding
    runs a per-leaf ``assert_equal`` safety broadcast — the ENTIRE
    model crosses the wire at startup just to verify what same-seed
    init already guarantees (``engine._run``: every process builds the
    identical state from ``jax.random.key(cfg.seed)``). On a pod that
    is O(model-size) startup traffic; on the CPU/gloo test backend the
    hundreds-of-collectives storm is also the main reorder-abort
    hazard. The local-data path uploads each host's own copy to its
    own devices with zero cross-host ops."""
    sharding = NamedSharding(mesh, P())
    if jax.process_count() == 1:
        return jax.device_put(state, sharding)

    def put(x):
        x = np.asarray(x)
        return jax.make_array_from_process_local_data(sharding, x,
                                                      x.shape)

    return jax.tree.map(put, state)


def place_state(state: TrainState, mesh: Mesh,
                state_specs: TrainState | None = None) -> TrainState:
    """Lay a host-side (full) TrainState onto the mesh per spec tree —
    sharded leaves (tensor parallelism) are split, ``P()`` leaves
    replicated. With no specs this is ``replicate_state``."""
    if state_specs is None:
        return replicate_state(state, mesh)
    return jax.tree.map(
        lambda x, spec: jax.device_put(x, NamedSharding(mesh, spec)),
        state, state_specs)


def shard_batch(mesh: Mesh, *arrays):
    """Host-local numpy shards → one global device array each, split over
    the ``data`` axis. Replaces the reference's pinned-memory H2D copies
    (``imagenet.py:119-120``); under multi-host each process contributes
    its local shard (``DistributedSampler``-equivalent placement,
    ``imagenet.py:346-347``)."""
    out = []
    for a in arrays:
        sharding = NamedSharding(mesh, P(DATA_AXIS, *([None] * (a.ndim - 1))))
        out.append(jax.make_array_from_process_local_data(sharding, a))
    return tuple(out)
