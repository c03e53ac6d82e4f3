"""In-graph MixUp/CutMix (ops/mixing.py) and its train-step integration.

The reference has no augmentation at all (SURVEY §0); these tests pin
the mixing math (label weights always match the pixels), the mixed-loss
identity against plain CE, determinism under the step-derived key, and
the SPMD/grad-accum compositions.
"""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from imagent_tpu.cluster import make_mesh
from imagent_tpu.models import create_model
from imagent_tpu.ops import make_mix_fn
from imagent_tpu.ops.mixing import cutmix, mixup
from imagent_tpu.train import (
    create_train_state, make_loss_fn, make_optimizer, make_train_step,
    replicate_state, shard_batch,
)

B, H, W, C = 8, 16, 16, 5


def _batch(seed=0):
    rng = np.random.default_rng(seed)
    images = rng.normal(size=(B, H, W, 3)).astype(np.float32)
    labels = rng.integers(0, C, size=(B,)).astype(np.int32)
    return jnp.asarray(images), jnp.asarray(labels)


def test_mixup_math():
    images, labels = _batch()
    mixed, (y_a, y_b, lam) = mixup(jax.random.key(1), images, labels, 0.4)
    lam0 = float(lam[0])
    assert 0.0 <= lam0 <= 1.0  # raw Beta sample (paper/timm semantics)
    np.testing.assert_array_equal(np.asarray(lam), lam0)  # one lam/batch
    np.testing.assert_array_equal(np.asarray(y_a), np.asarray(labels))
    np.testing.assert_array_equal(np.asarray(y_b),
                                  np.asarray(labels)[::-1])
    want = lam0 * np.asarray(images) + (1 - lam0) * np.asarray(images)[::-1]
    np.testing.assert_allclose(np.asarray(mixed), want, rtol=1e-5,
                               atol=1e-6)


def test_cutmix_label_weight_matches_pixels():
    images, labels = _batch(3)
    # Hunt a key whose box is non-degenerate (interior, nonzero area).
    for k in range(20):
        mixed, (y_a, y_b, lam) = cutmix(jax.random.key(k), images,
                                        labels, 1.0)
        mixed, lam0 = np.asarray(mixed), float(lam[0])
        if 0.01 < lam0 < 0.999:
            break
    else:
        pytest.fail("no non-degenerate cutmix box in 20 keys")
    src, pair = np.asarray(images), np.asarray(images)[::-1]
    # Every pixel comes verbatim from one of the two sources...
    from_src = np.isclose(mixed, src).all(axis=-1)
    from_pair = np.isclose(mixed, pair).all(axis=-1)
    assert np.all(from_src | from_pair)
    # ...and lam is the EXACT unreplaced-pixel fraction (the paper's
    # adjustment) — measured on sample 0 (same box for the whole batch).
    frac = from_src[0].sum() / (H * W)
    assert lam0 == pytest.approx(frac, abs=1e-6)
    np.testing.assert_array_equal(np.asarray(y_b),
                                  np.asarray(labels)[::-1])


def test_mixed_loss_identity():
    """The (y_a, y_b, lam) objective is the convex combination of the
    two hard-label CEs; degenerate cases collapse to plain CE."""
    images, labels = _batch(5)
    model = create_model("resnet18", num_classes=C)
    variables = model.init(jax.random.key(0), images, train=False)
    loss_fn = make_loss_fn(model)

    def loss_of(lbls):
        l, _ = loss_fn(variables["params"], variables["batch_stats"],
                       images, lbls)
        return float(l)

    plain = loss_of(labels)
    ones = jnp.ones((B,), jnp.float32)
    # lam=1 keeps only y_a regardless of y_b
    assert loss_of((labels, labels[::-1], ones)) == pytest.approx(
        plain, rel=1e-6)
    # identical labels at any lam == plain
    assert loss_of((labels, labels, 0.3 * ones)) == pytest.approx(
        plain, rel=1e-6)
    # general case: exact convex combination
    rev = loss_of(labels[::-1])
    got = loss_of((labels, labels[::-1], 0.25 * ones))
    assert got == pytest.approx(0.25 * plain + 0.75 * rev, rel=1e-5)


def test_make_mix_fn_gating():
    assert make_mix_fn(0.0, 0.0) is None
    assert make_mix_fn(0.2, 0.0) is not None
    # both enabled: the coin flip branch compiles and returns the triple
    mix = make_mix_fn(0.2, 1.0)
    images, labels = _batch(7)
    mixed, (y_a, y_b, lam) = jax.jit(mix)(jax.random.key(0), images,
                                          labels)
    assert mixed.shape == images.shape and lam.shape == labels.shape


@pytest.mark.parametrize("grad_accum", [1, 2])
def test_train_step_with_mixup_deterministic(grad_accum):
    """The step-keyed mixing is reproducible (same state.step ⇒ same
    augmentation — the preemption/resume replay guarantee) and the
    metrics count against the primary labels."""
    mesh = make_mesh(model_parallel=1)
    model = create_model("resnet18", num_classes=C)
    opt = make_optimizer()
    # 8 devices x grad_accum micro-batches need 16 rows minimum.
    rng = np.random.default_rng(9)
    images = rng.normal(size=(16, H, W, 3)).astype(np.float32)
    labels = rng.integers(0, C, size=(16,)).astype(np.int32)
    mix = make_mix_fn(mixup_alpha=0.2)

    def run_once():
        state = replicate_state(
            create_train_state(model, jax.random.key(0), H, opt), mesh)
        step = make_train_step(model, opt, mesh, mix_fn=mix, mix_seed=3,
                               grad_accum=grad_accum)
        gi, gl = shard_batch(mesh, images, labels)
        _, metrics = step(state, gi, gl, np.float32(0.1))
        return np.asarray(metrics)

    m1, m2 = run_once(), run_once()
    np.testing.assert_array_equal(m1, m2)
    assert m1[3] == 16 and np.isfinite(m1[0])


def test_engine_accepts_mixing_flags(tmp_path):
    """CLI surface end-to-end: --mixup/--cutmix through engine.run."""
    from imagent_tpu.config import Config
    from imagent_tpu.engine import run

    cfg = Config(backend="cpu", arch="resnet18", image_size=16, num_classes=4,
                 batch_size=4, epochs=1, lr=0.05, dataset="synthetic",
                 synthetic_size=32, workers=0, bf16=False, log_every=0,
                 mixup=0.2, cutmix=1.0,
                 log_dir=str(tmp_path / "tb"),
                 ckpt_dir=str(tmp_path / "ckpt"))
    result = run(cfg)
    assert result["final_train"]["n"] == 32
    assert np.isfinite(result["final_train"]["loss"])
