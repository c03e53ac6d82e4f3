"""SPMD engine tests on 8 fake devices (SURVEY §4 "Multi-device without a
cluster"): the DDP-equivalence invariant — the 8-way sharded step's psum'd
gradients/update must equal a single-device step on the concatenated
batch (implied by reference ``imagenet.py:316`` + ``:85``)."""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from imagent_tpu.cluster import make_mesh
from imagent_tpu.models import create_model
from imagent_tpu.ops import softmax_cross_entropy
from imagent_tpu.train import (
    create_train_state, make_eval_step, make_optimizer, make_train_step,
    replicate_state, shard_batch,
)

BATCH, SIZE, CLASSES = 16, 32, 8


@pytest.fixture()
def setup():
    # Function-scoped: the train step donates its input state, so each
    # test needs a fresh one.
    mesh = make_mesh(model_parallel=1)
    model = create_model("resnet18", num_classes=CLASSES)
    opt = make_optimizer(momentum=0.9, weight_decay=1e-4)
    state = create_train_state(model, jax.random.key(0), SIZE, opt)
    state = replicate_state(state, mesh)
    rng = np.random.default_rng(42)
    images = rng.normal(size=(BATCH, SIZE, SIZE, 3)).astype(np.float32)
    labels = rng.integers(0, CLASSES, size=(BATCH,)).astype(np.int32)
    return mesh, model, opt, state, images, labels


def test_train_step_runs_and_metrics_shape(setup):
    mesh, model, opt, state, images, labels = setup
    step = make_train_step(model, opt, mesh)
    gi, gl = shard_batch(mesh, images, labels)
    new_state, metrics = step(state, gi, gl, np.float32(0.1))
    m = np.asarray(metrics)
    assert m.shape == (4,)
    assert m[3] == BATCH  # global count
    assert 0 <= m[1] <= BATCH and 0 <= m[2] <= BATCH
    assert int(new_state.step) == 1


import flax.linen as nn


class _PlainCNN(nn.Module):
    """BN-free conv net: numerically well-conditioned, so the sharded-vs-
    serial comparison is exact up to fp32 reassociation. (ResNet's BN over
    tiny per-shard batches is chaotic — covered by the smoke/e2e tests.)"""

    @nn.compact
    def __call__(self, x, train: bool = True):
        x = nn.Conv(16, (3, 3))(x)
        x = nn.relu(x)
        x = jnp.mean(x, axis=(1, 2))
        return nn.Dense(CLASSES)(x)


def test_sharded_grads_match_single_device(setup):
    """DDP-equivalence invariant (imagenet.py:316 + :85): pmean'd per-shard
    gradients + the shared SGD update == serial per-shard computation."""
    mesh, _, opt, _, images, labels = setup
    model = _PlainCNN()
    state = replicate_state(
        create_train_state(model, jax.random.key(0), SIZE, opt), mesh)
    host_state = jax.device_get(state)

    def shard_loss(params, x, y):
        logits = model.apply({"params": params}, x, train=True)
        return softmax_cross_entropy(logits, y).mean()

    n_shards, per = 8, BATCH // 8
    grads_acc = None
    for s in range(n_shards):
        g = jax.grad(shard_loss)(
            host_state.params,
            jnp.asarray(images[s * per:(s + 1) * per]),
            jnp.asarray(labels[s * per:(s + 1) * per]))
        grads_acc = g if grads_acc is None else jax.tree.map(
            jnp.add, grads_acc, g)
    grads_ref = jax.tree.map(lambda x: x / n_shards, grads_acc)

    # One SGD step by hand (torch order: g + wd*p, zero momentum trace):
    lr, wd = 0.1, 1e-4
    expect_params = jax.tree.map(
        lambda p, g: p - lr * (g + wd * p), host_state.params, grads_ref)

    step = make_train_step(model, opt, mesh)
    gi, gl = shard_batch(mesh, images, labels)
    new_state, _ = step(state, gi, gl, np.float32(lr))
    got = jax.device_get(new_state.params)

    flat_e, _ = jax.tree.flatten(expect_params)
    flat_g, _ = jax.tree.flatten(got)
    for e, g in zip(flat_e, flat_g):
        np.testing.assert_allclose(np.asarray(e), np.asarray(g),
                                   rtol=1e-4, atol=1e-6)


def test_eval_step_mask_exactness(setup):
    """Padded rows must not perturb metrics (SURVEY §7 eval sharding)."""
    mesh, model, opt, state, images, labels = setup
    eval_step = make_eval_step(model, mesh)
    mask_full = np.ones((BATCH,), np.float32)
    gi, gl, gm = shard_batch(mesh, images, labels, mask_full)
    full = np.asarray(eval_step(state, gi, gl, gm))

    # Same real samples + 8 garbage padded rows with mask 0.
    pad_img = np.concatenate(
        [images, np.random.default_rng(1).normal(
            size=(8, SIZE, SIZE, 3)).astype(np.float32) * 100])
    pad_lbl = np.concatenate([labels, np.zeros((8,), np.int32)])
    pad_msk = np.concatenate([mask_full, np.zeros((8,), np.float32)])
    gi, gl, gm = shard_batch(mesh, pad_img, pad_lbl, pad_msk)
    padded = np.asarray(eval_step(state, gi, gl, gm))
    np.testing.assert_allclose(full, padded, rtol=1e-5, atol=1e-5)
    assert padded[3] == BATCH


def test_determinism_fixed_seed(setup):
    """Fixed seed ⇒ identical first-step loss across runs (SURVEY §4)."""
    mesh, model, opt, _, images, labels = setup
    losses = []
    for _ in range(2):
        st = replicate_state(
            create_train_state(model, jax.random.key(7), SIZE, opt), mesh)
        step = make_train_step(model, opt, mesh)
        gi, gl = shard_batch(mesh, images, labels)
        _, metrics = step(st, gi, gl, np.float32(0.1))
        losses.append(float(np.asarray(metrics)[0]))
    assert losses[0] == losses[1]


class _BNCNN(nn.Module):
    """Minimal BatchNorm net for pinning cross-replica BN semantics."""

    @nn.compact
    def __call__(self, x, train: bool = True):
        x = nn.Conv(8, (3, 3))(x)
        x = nn.BatchNorm(use_running_average=not train, momentum=0.9,
                         epsilon=1e-5)(x)
        x = nn.relu(x)
        x = jnp.mean(x, axis=(1, 2))
        return nn.Dense(CLASSES)(x)


def test_bn_cross_replica_semantics(setup):
    """Pins the documented BN contract (train.py module docstring):
    (a) each replica NORMALIZES with its own shard's batch statistics
        (DDP semantics — gradients match a serial per-shard emulation),
    (b) the STORED running stats are the pmean across replicas of the
        per-shard EMA updates (the one deliberate DDP deviation),
    (c) and that is measurably different from SyncBN (global-batch
        stats), so the assertion actually discriminates."""
    mesh, _, opt, _, images, labels = setup
    # Give each shard a different input MEAN so per-shard statistics
    # measurably differ from global-batch statistics: SyncBN's variance
    # gains the across-shard variance of means (law of total variance),
    # while mean-of-per-shard-vars does not — otherwise (c) below
    # cannot discriminate.
    images = images.copy()
    per_shard = BATCH // 8
    for s in range(8):
        images[s * per_shard:(s + 1) * per_shard] += 0.75 * s
    model = _BNCNN()
    state = replicate_state(
        create_train_state(model, jax.random.key(0), SIZE, opt), mesh)
    host = jax.device_get(state)

    def shard_loss(params, bs, x, y):
        logits, mut = model.apply(
            {"params": params, "batch_stats": bs}, x, train=True,
            mutable=["batch_stats"])
        return (softmax_cross_entropy(logits, y).mean(),
                mut["batch_stats"])

    n_shards, per = 8, BATCH // 8
    grads_acc, stats_acc = None, None
    for s in range(n_shards):
        g, new_bs = jax.grad(shard_loss, has_aux=True)(
            host.params, host.batch_stats,
            jnp.asarray(images[s * per:(s + 1) * per]),
            jnp.asarray(labels[s * per:(s + 1) * per]))
        grads_acc = g if grads_acc is None else jax.tree.map(
            jnp.add, grads_acc, g)
        stats_acc = new_bs if stats_acc is None else jax.tree.map(
            jnp.add, stats_acc, new_bs)
    grads_ref = jax.tree.map(lambda x: x / n_shards, grads_acc)
    stats_ref = jax.tree.map(lambda x: x / n_shards, stats_acc)

    lr, wd = 0.1, 1e-4
    expect_params = jax.tree.map(
        lambda p, g: p - lr * (g + wd * p), host.params, grads_ref)

    step = make_train_step(model, opt, mesh)
    gi, gl = shard_batch(mesh, images, labels)
    new_state, _ = step(state, gi, gl, np.float32(lr))
    got = jax.device_get(new_state)

    # (b) stored stats == mean of per-shard EMA updates
    for ref, g in zip(jax.tree.leaves(stats_ref),
                      jax.tree.leaves(got.batch_stats)):
        np.testing.assert_allclose(np.asarray(ref), np.asarray(g),
                                   rtol=1e-5, atol=1e-6)
    # (a) per-shard-normalized gradients flowed into the update
    for ref, g in zip(jax.tree.leaves(expect_params),
                      jax.tree.leaves(got.params)):
        np.testing.assert_allclose(np.asarray(ref), np.asarray(g),
                                   rtol=1e-4, atol=1e-6)

    # (c) SyncBN (stats over the global batch) is a DIFFERENT answer:
    _, syncbn = jax.grad(shard_loss, has_aux=True)(
        host.params, host.batch_stats, jnp.asarray(images),
        jnp.asarray(labels))
    var_ref = stats_ref["BatchNorm_0"]["var"]
    var_sync = syncbn["BatchNorm_0"]["var"]
    assert not np.allclose(np.asarray(var_ref), np.asarray(var_sync),
                           rtol=1e-3)


def _leaves(state):
    return [np.asarray(x) for x in jax.tree.leaves(jax.device_get(state))]


@pytest.mark.parametrize("arch", ["resnet18", "vit_debug"])
def test_jitted_init_matches_eager(arch):
    """The one jitted initializer gives the values of its body run op by
    op (each initializer and random draw its own small program, as the
    init ran before it was jitted), leaf by leaf within 1e-7: XLA's
    fusion of the random draws moves a value by an ulp at most."""
    model = create_model(arch, num_classes=CLASSES)
    opt = make_optimizer(momentum=0.9, weight_decay=1e-4)
    jitted = create_train_state(model, jax.random.key(3), SIZE, opt)
    eager = create_train_state.__wrapped__(model, jax.random.key(3), SIZE,
                                           opt)
    assert jax.tree.structure(jitted) == jax.tree.structure(eager)
    for j, e in zip(_leaves(jitted), _leaves(eager)):
        assert j.shape == e.shape and j.dtype == e.dtype
        np.testing.assert_allclose(j, e, rtol=0, atol=1e-7)


def test_init_seed_is_an_argument(caplog):
    """The same seed gives a bit-identical state; another seed gives
    another state from the SAME executable — the key is a traced
    argument, not a constant baked into the program."""
    model = create_model("resnet18", num_classes=CLASSES)
    opt = make_optimizer(momentum=0.9, weight_decay=1e-4)
    first = create_train_state(model, jax.random.key(11), SIZE, opt)
    cached = create_train_state._cache_size()
    with jax.log_compiles(), caplog.at_level("DEBUG"):
        again = create_train_state(model, jax.random.key(11), SIZE, opt)
        other = create_train_state(model, jax.random.key(12), SIZE, opt)
    assert create_train_state._cache_size() == cached
    compiles = [r for r in caplog.records
                if "create_train_state" in r.getMessage()]
    assert not compiles, [r.getMessage() for r in compiles]
    for a, b in zip(_leaves(first), _leaves(again)):
        assert np.array_equal(a, b)
    assert not np.array_equal(
        first.params["conv1"]["kernel"], other.params["conv1"]["kernel"])
