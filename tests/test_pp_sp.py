"""Pipeline x sequence parallelism (pp x sp): GPipe stages over the
`pipe` axis with ring/Ulysses attention sharding tokens over `model`
inside each stage — the composition for models both too deep for one
chip AND with sequences too long for one chip.

Exactness is pinned against the stacked pipe-free full-attention twin
on a single device (same param tree), like the other pp compositions.
"""

import jax
import numpy as np
import pytest

from imagent_tpu.cluster import MODEL_AXIS, PIPE_AXIS, make_mesh
from imagent_tpu.models.vit import VisionTransformer
from imagent_tpu.parallel.pipeline import vit_pp_param_specs
from imagent_tpu.train import (
    create_train_state, make_eval_step, make_optimizer, make_train_step,
    place_state, replicate_state, shard_batch, state_partition_specs,
)

KW = dict(patch_size=8, hidden_dim=32, num_layers=2, num_heads=4,
          mlp_dim=64, num_classes=4, gap_readout=True)
SIZE, BATCH = 32, 8


def _host_and_batch():
    twin = VisionTransformer(**KW, stacked=True)
    opt = make_optimizer()
    host = jax.device_get(
        create_train_state(twin, jax.random.key(0), SIZE, opt))
    rng = np.random.default_rng(0)
    images = rng.normal(size=(BATCH, SIZE, SIZE, 3)).astype(np.float32)
    labels = rng.integers(0, 4, size=(BATCH,)).astype(np.int32)
    return twin, opt, host, images, labels


@pytest.mark.parametrize("attn", ["ring", "ulysses"])
def test_pp_sp_train_step_matches_twin(attn):
    twin, opt, host, images, labels = _host_and_batch()
    lr = np.float32(0.05)

    mesh1 = make_mesh(model_parallel=1, devices=jax.devices()[:1])
    ref_state = replicate_state(host, mesh1)
    ref_step = make_train_step(twin, opt, mesh1)
    g1, l1 = shard_batch(mesh1, images, labels)
    ref_state, ref_m = ref_step(ref_state, g1, l1, lr)

    mesh = make_mesh(model_parallel=2, pipeline_parallel=2)
    model = VisionTransformer(**KW, attn_impl=attn, seq_axis=MODEL_AXIS,
                              pipe_axis=PIPE_AXIS, microbatches=2)
    specs = state_partition_specs(host, vit_pp_param_specs(host.params))
    state = place_state(host, mesh, specs)
    step = make_train_step(model, opt, mesh, seq_parallel=True,
                           state_specs=specs, pipe_axis=PIPE_AXIS)
    gi, gl = shard_batch(mesh, images, labels)
    state, m = step(state, gi, gl, lr)

    np.testing.assert_allclose(np.asarray(m), np.asarray(ref_m),
                               rtol=1e-5)
    flat_ref = jax.tree_util.tree_flatten_with_path(
        jax.device_get(ref_state).params)[0]
    flat_got = jax.tree_util.tree_flatten_with_path(
        jax.device_get(state).params)[0]
    for (path, a), (_, b) in zip(flat_ref, flat_got):
        np.testing.assert_allclose(
            np.asarray(b), np.asarray(a), rtol=1e-4, atol=1e-6,
            err_msg=jax.tree_util.keystr(path))


def test_pp_sp_eval_matches_twin():
    twin, opt, host, images, labels = _host_and_batch()
    mask = np.ones((BATCH,), np.float32)

    mesh1 = make_mesh(model_parallel=1, devices=jax.devices()[:1])
    g1, l1, m1 = shard_batch(mesh1, images, labels, mask)
    want = np.asarray(make_eval_step(twin, mesh1)(
        replicate_state(host, mesh1), g1, l1, m1))

    mesh = make_mesh(model_parallel=2, pipeline_parallel=2)
    model = VisionTransformer(**KW, attn_impl="ring", seq_axis=MODEL_AXIS,
                              pipe_axis=PIPE_AXIS, microbatches=2)
    specs = state_partition_specs(host, vit_pp_param_specs(host.params))
    got = np.asarray(make_eval_step(model, mesh, specs)(
        place_state(host, mesh, specs),
        *shard_batch(mesh, images, labels, mask)))
    np.testing.assert_allclose(got, want, rtol=1e-4, atol=1e-4)


def test_engine_pp_sp_smoke(tmp_path):
    """CLI: --pipeline-parallel 2 --seq-parallel ring --model-parallel 2."""
    from imagent_tpu.config import Config
    from imagent_tpu.engine import run

    cfg = Config(backend="cpu", arch="vit_debug", image_size=32, num_classes=4,
                 batch_size=4, epochs=1, lr=0.01, dataset="synthetic",
                 synthetic_size=16, workers=0, bf16=False, log_every=0,
                 seq_parallel="ring", model_parallel=2,
                 pipeline_parallel=2, microbatches=2,
                 log_dir=str(tmp_path / "tb"),
                 ckpt_dir=str(tmp_path / "ckpt"))
    result = run(cfg)
    assert result["final_train"]["n"] == 16
    assert np.isfinite(result["final_train"]["loss"])
