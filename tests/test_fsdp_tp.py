"""Hybrid 2-D sharding: FSDP (data axis) x tensor parallelism (model
axis) on the SAME param tree, via the XLA SPMD partitioner alone.

This is the GSPMD composition the explicit shard_map paths don't cover:
the PLAIN ViT (no axis names in the model code) with each attention/MLP
leaf annotated TP-style on `model` AND FSDP-style on `data`; the
partitioner derives both collective families. Exactness is pinned
against a single-device run of the same model.
"""

import jax
import numpy as np
from jax.sharding import PartitionSpec as P

from imagent_tpu.cluster import DATA_AXIS, MODEL_AXIS, make_mesh
from imagent_tpu.models.vit import VisionTransformer
from imagent_tpu.parallel.fsdp import (
    fsdp_tp_param_specs, fsdp_tp_state_specs, sharded_fraction,
)
from imagent_tpu.train import (
    create_train_state, make_eval_step, make_eval_step_auto, make_optimizer,
    make_train_step, make_train_step_auto, place_state, replicate_state,
    shard_batch,
)

SIZE, BATCH, C = 32, 16, 4


def _model():
    return VisionTransformer(patch_size=8, hidden_dim=32, num_layers=2,
                             num_heads=4, mlp_dim=64, num_classes=C)


def _data():
    rng = np.random.default_rng(11)
    images = rng.normal(size=(BATCH, SIZE, SIZE, 3)).astype(np.float32)
    labels = rng.integers(0, C, size=(BATCH,)).astype(np.int32)
    return images, labels


def test_specs_are_two_dimensional():
    """QKV/MLP kernels carry BOTH axes; TP-replicated leaves get FSDP."""
    model = _model()
    opt = make_optimizer(name="adamw")
    state = create_train_state(model, jax.random.key(0), SIZE, opt)
    specs = fsdp_tp_param_specs(state.params, n_data=4)

    flat = {jax.tree_util.keystr(k): v for k, v in
            jax.tree_util.tree_flatten_with_path(
                specs, is_leaf=lambda x: isinstance(x, P))[0]}
    qkv = [v for k, v in flat.items() if "query" in k and "kernel" in k]
    assert qkv and all(
        MODEL_AXIS in tuple(s) and DATA_AXIS in tuple(s) for s in qkv)
    mlp = [v for k, v in flat.items() if "mlp_0" in k and "kernel" in k]
    assert mlp and all(tuple(s) == (DATA_AXIS, MODEL_AXIS) for s in mlp)
    # LayerNorm scales: TP-replicated, FSDP-sharded when divisible.
    ln = [v for k, v in flat.items() if "LayerNorm" in k or "ln" in k]
    assert ln and all(MODEL_AXIS not in tuple(s) for s in ln)


def test_hybrid_step_matches_single_device():
    """(data=4, model=2) hybrid step == single-device step, tightly
    (LayerNorm model: no BN chaos)."""
    images, labels = _data()
    model = _model()
    opt = make_optimizer(name="adamw")
    host = jax.device_get(
        create_train_state(model, jax.random.key(0), SIZE, opt))
    lr = np.float32(0.01)

    mesh1 = make_mesh(model_parallel=1, devices=jax.devices()[:1])
    ref_state = replicate_state(host, mesh1)
    ref_step = make_train_step(model, opt, mesh1)
    g1, l1 = shard_batch(mesh1, images, labels)
    ref_state, ref_metrics = ref_step(ref_state, g1, l1, lr)

    mesh = make_mesh(model_parallel=2)
    assert mesh.shape[DATA_AXIS] == 4 and mesh.shape[MODEL_AXIS] == 2
    specs = fsdp_tp_state_specs(host, n_data=mesh.shape[DATA_AXIS])
    h_state = place_state(host, mesh, specs)
    assert sharded_fraction(h_state) > 0.5
    h_step = make_train_step_auto(model, opt, mesh, specs)
    gi, gl = shard_batch(mesh, images, labels)
    h_state, h_metrics = h_step(h_state, gi, gl, lr)

    np.testing.assert_allclose(np.asarray(h_metrics),
                               np.asarray(ref_metrics), rtol=1e-5)
    flat_ref = jax.tree_util.tree_flatten_with_path(
        jax.device_get(ref_state).params)[0]
    flat_got = jax.tree_util.tree_flatten_with_path(
        jax.device_get(h_state).params)[0]
    # adamw divides by sqrt(nu): ulp-level reduction-order differences
    # between the two compilations amplify to ~4e-4 relative on a few
    # kernel entries — far tighter than the BN-model fsdp test (5e-2).
    # The KEY projection bias is excluded: softmax is invariant to the
    # per-query constant shift a key bias induces (logits_ij = q_i·k_j
    # + q_i·b), so its true gradient is exactly zero and adamw's
    # noise/sqrt(noise^2) turns roundoff into ±lr-scale garbage in BOTH
    # programs — equally meaningless, not comparable.
    for (path, a), (_, b) in zip(flat_ref, flat_got):
        name = jax.tree_util.keystr(path)
        if "['key']['bias']" in name:
            continue
        np.testing.assert_allclose(
            np.asarray(b), np.asarray(a), rtol=2e-3, atol=1e-5,
            err_msg=name)


def test_hybrid_eval_matches_replicated():
    images, labels = _data()
    model = _model()
    opt = make_optimizer(name="adamw")
    host = jax.device_get(
        create_train_state(model, jax.random.key(0), SIZE, opt))
    mask = np.ones((BATCH,), np.float32)

    mesh1 = make_mesh(model_parallel=1, devices=jax.devices()[:1])
    g1, l1, m1 = shard_batch(mesh1, images, labels, mask)
    want = np.asarray(make_eval_step(model, mesh1)(
        replicate_state(host, mesh1), g1, l1, m1))

    mesh = make_mesh(model_parallel=2)
    specs = fsdp_tp_state_specs(host, n_data=mesh.shape[DATA_AXIS])
    gi, gl, gm = shard_batch(mesh, images, labels, mask)
    got = np.asarray(make_eval_step_auto(model, mesh, specs)(
        place_state(host, mesh, specs), gi, gl, gm))
    np.testing.assert_allclose(got, want, rtol=1e-4, atol=1e-4)


def test_engine_fsdp_tp_smoke(tmp_path):
    """CLI surface: --fsdp --tensor-parallel --model-parallel 2."""
    from imagent_tpu.config import Config
    from imagent_tpu.engine import run

    cfg = Config(backend="cpu", arch="vit_debug", image_size=32, num_classes=4,
                 batch_size=4, epochs=1, lr=0.01, optimizer="adamw",
                 dataset="synthetic", synthetic_size=32, workers=0,
                 bf16=False, log_every=0, fsdp=True, tensor_parallel=True,
                 model_parallel=2, log_dir=str(tmp_path / "tb"),
                 ckpt_dir=str(tmp_path / "ckpt"))
    result = run(cfg)
    assert result["final_train"]["n"] == 32
    assert np.isfinite(result["final_train"]["loss"])


def test_engine_fsdp_sp_still_rejected(tmp_path):
    from imagent_tpu.config import Config
    from imagent_tpu.engine import run

    import pytest

    cfg = Config(backend="cpu", arch="vit_debug", image_size=32, num_classes=4,
                 batch_size=4, epochs=1, dataset="synthetic",
                 synthetic_size=16, workers=0, log_every=0, fsdp=True,
                 seq_parallel="ring", model_parallel=2,
                 log_dir=str(tmp_path / "tb"),
                 ckpt_dir=str(tmp_path / "ckpt"))
    with pytest.raises(ValueError, match="fsdp"):
        run(cfg)
