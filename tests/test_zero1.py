"""ZeRO-1 exactness: sharding the momentum buffer over the data axis
(``parallel/zero.py``) must produce bit-comparable updates to the
replicated optax path — same torch-SGD order — while actually
partitioning the buffer across devices."""

import pytest

import jax
import numpy as np

from imagent_tpu.cluster import DATA_AXIS, make_mesh
from imagent_tpu.models import create_model
from imagent_tpu.parallel import zero as zero_lib
from imagent_tpu.train import (
    create_train_state, make_optimizer, make_train_step, place_state,
    replicate_state, shard_batch,
)

SIZE = 16
BATCH = 16


def _data():
    rng = np.random.default_rng(5)
    images = rng.normal(size=(BATCH, SIZE, SIZE, 3)).astype(np.float32)
    labels = rng.integers(0, 4, size=(BATCH,)).astype(np.int32)
    return images, labels


def test_zero1_update_bitwise_matches_optax():
    """Pure optimizer parity: the sharded-slice update must match the
    replicated optax chain to a few ulp on a pytree of awkward shapes
    (dims not divisible by the axis, scalars) — two steps so momentum
    engages. (Exact bitwise is unattainable: XLA may emit fma for
    ``g + wd*p`` in one program and mul+add in the other. Conv models
    can't test even this tightly: XLA/oneDNN may pick different
    conv-backward algorithms for differently-structured programs, which
    perturbs the *gradients*, not the optimizer.)"""
    import optax
    from jax.sharding import PartitionSpec as P

    mesh = make_mesh(model_parallel=1)
    rng = np.random.default_rng(0)
    params = {
        "conv": {"kernel": rng.normal(size=(3, 3, 3, 7)).astype(np.float32)},
        "bn": {"scale": rng.normal(size=(13,)).astype(np.float32)},
        "w": rng.normal(size=(5, 11)).astype(np.float32),
    }
    grads = jax.tree.map(
        lambda x: rng.normal(size=x.shape).astype(np.float32), params)
    lr, mu, wd = np.float32(0.1), 0.9, 1e-4

    opt = make_optimizer(momentum=mu, weight_decay=wd)
    ms = opt.init(params)
    p_ref = params
    for _ in range(2):
        u, ms = opt.update(grads, ms, p_ref)
        p_ref = optax.apply_updates(
            p_ref, jax.tree.map(lambda x: -lr * x, u))

    flat0 = zero_lib.init_opt_state(params, n_data=8)

    def one_step(p, g, o):
        return zero_lib.sgd_momentum_shard_update(p, g, o, lr, mu, wd)

    stepped = jax.jit(jax.shard_map(
        one_step, mesh=mesh,
        in_specs=(P(), P(), P(DATA_AXIS)), out_specs=(P(), P(DATA_AXIS)),
        check_vma=False))
    p_z, flat = params, flat0
    for _ in range(2):
        p_z, flat = stepped(p_z, g := grads, flat)

    for (path, a), (_, b) in zip(
            jax.tree_util.tree_flatten_with_path(p_ref)[0],
            jax.tree_util.tree_flatten_with_path(jax.device_get(p_z))[0]):
        np.testing.assert_array_almost_equal_nulp(
            np.asarray(b), np.asarray(a), nulp=8)


@pytest.mark.slow  # engine-heavy: keeps tier-1 inside its 870s budget
def test_zero1_resnet_integration_close():
    """Full-model integration, ONE step: step-1 metrics are computed from
    identical initial params so they match exactly; updated params match
    to conv-backward-algorithm noise (XLA/oneDNN may pick different conv
    algorithms for differently-structured programs — measured: the
    *replicated* path deviates ~2e-4 from a manually-computed ground
    truth while the zero1 path is exact). Optimizer exactness itself is
    covered bitwise by the pytree test above; multi-step training by the
    e2e smoke below."""
    images, labels = _data()
    mesh = make_mesh(model_parallel=1)
    model = create_model("resnet18", num_classes=4)
    opt = make_optimizer(momentum=0.9, weight_decay=1e-4)
    host = jax.device_get(
        create_train_state(model, jax.random.key(0), SIZE, opt))
    gi, gl = shard_batch(mesh, images, labels)
    lr = np.float32(0.005)

    state = replicate_state(host, mesh)
    step = make_train_step(model, opt, mesh)
    state, ref_metrics = step(state, gi, gl, lr)
    ref = jax.device_get(state)

    z_state = host.replace(
        opt_state=zero_lib.init_opt_state(host.params, n_data=8))
    specs = zero_lib.zero1_state_specs(z_state)
    z_state = place_state(z_state, mesh, specs)
    z_step = make_train_step(model, opt, mesh, state_specs=specs,
                             zero1=True, momentum=0.9, weight_decay=1e-4)
    z_state, z_metrics = z_step(z_state, gi, gl, lr)

    np.testing.assert_allclose(np.asarray(z_metrics),
                               np.asarray(ref_metrics), rtol=1e-6)
    flat_ref = jax.tree_util.tree_flatten_with_path(ref.params)[0]
    flat_got = jax.tree_util.tree_flatten_with_path(
        jax.device_get(z_state).params)[0]
    for (path, a), (_, b) in zip(flat_ref, flat_got):
        np.testing.assert_allclose(
            np.asarray(b), np.asarray(a), rtol=5e-2, atol=1e-3,
            err_msg=jax.tree_util.keystr(path))


def test_zero1_buffer_actually_sharded():
    mesh = make_mesh(model_parallel=1)
    model = create_model("resnet18", num_classes=4)
    opt = make_optimizer()
    host = jax.device_get(
        create_train_state(model, jax.random.key(0), SIZE, opt))
    n_params = sum(x.size for x in jax.tree_util.tree_leaves(host.params))
    z_state = host.replace(
        opt_state=zero_lib.init_opt_state(host.params, n_data=8))
    specs = zero_lib.zero1_state_specs(z_state)
    z_state = place_state(z_state, mesh, specs)
    assert z_state.opt_state.shape[0] % 8 == 0
    assert z_state.opt_state.shape[0] >= n_params
    # Each device holds exactly 1/8 of the padded buffer.
    shard_shapes = {s.data.shape for s in z_state.opt_state.addressable_shards}
    assert shard_shapes == {(z_state.opt_state.shape[0] // 8,)}


@pytest.mark.slow  # engine-heavy: keeps tier-1 inside its 870s budget
def test_zero1_e2e_smoke(tmp_path):
    """Engine-level: --zero1 trains, checkpoints, and resumes."""
    from imagent_tpu.config import Config
    from imagent_tpu.engine import run

    cfg = Config(backend="cpu", arch="resnet18", image_size=16, num_classes=4, batch_size=4,
                 epochs=1, lr=0.05, dataset="synthetic", synthetic_size=64,
                 workers=0, bf16=False, log_every=0, zero1=True,
                 save_model=True, log_dir=str(tmp_path / "tb"),
                 ckpt_dir=str(tmp_path / "ckpt"))
    result = run(cfg)
    assert result["best_epoch"] >= 0
    cfg2 = cfg.replace(epochs=2, resume=True)
    result2 = run(cfg2)
    assert result2["best_epoch"] >= 0


def test_zero1_grad_accum_matches_single_step():
    """--zero1 + --grad-accum K (the north-star geometry on few chips):
    K accumulated micro-batches through the sharded-momentum update must
    equal one ZeRO-1 step over the same effective batch (BN-free model,
    order-invariant gradient means)."""
    import flax.linen as nn
    import jax.numpy as jnp

    class _Plain(nn.Module):
        @nn.compact
        def __call__(self, x, train=True):
            x = nn.Conv(8, (3, 3))(x)
            x = nn.relu(x)
            x = jnp.mean(x, axis=(1, 2))
            return nn.Dense(4)(x)

    K = 2
    rng = np.random.default_rng(11)
    images = rng.normal(size=(BATCH * K, SIZE, SIZE, 3)).astype(np.float32)
    labels = rng.integers(0, 4, size=(BATCH * K,)).astype(np.int32)
    mesh = make_mesh(model_parallel=1)
    model = _Plain()
    opt = make_optimizer()
    host = jax.device_get(
        create_train_state(model, jax.random.key(0), SIZE, opt))
    lr = np.float32(0.05)
    gi, gl = shard_batch(mesh, images, labels)

    def make(grad_accum):
        z = host.replace(
            opt_state=zero_lib.init_opt_state(host.params, n_data=8))
        specs = zero_lib.zero1_state_specs(z)
        step = make_train_step(model, opt, mesh, state_specs=specs,
                               zero1=True, grad_accum=grad_accum)
        return place_state(z, mesh, specs), step

    ref_state, ref_step = make(1)
    ref_state, ref_metrics = ref_step(ref_state, gi, gl, lr)
    acc_state, acc_step = make(K)
    acc_state, acc_metrics = acc_step(acc_state, gi, gl, lr)

    np.testing.assert_allclose(np.asarray(acc_metrics),
                               np.asarray(ref_metrics), rtol=1e-4)
    for (path, a), (_, b) in zip(
            jax.tree_util.tree_flatten_with_path(
                jax.device_get(ref_state).params)[0],
            jax.tree_util.tree_flatten_with_path(
                jax.device_get(acc_state).params)[0]):
        np.testing.assert_allclose(
            np.asarray(b), np.asarray(a), rtol=1e-4, atol=1e-6,
            err_msg=jax.tree_util.keystr(path))
