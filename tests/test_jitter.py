"""In-graph color jitter (ops/jitter.py): torchvision factor semantics
on RAW [0, 1] RGB batches — the jitter runs after the in-graph
dequantize and before normalization (train.make_input_prep), so the
old un-normalize → jitter → re-normalize round-trip is gone (its
equivalence to this formulation is pinned in tests/test_wire_format.py).
"""

import pytest

import jax
import numpy as np

from imagent_tpu.ops.jitter import color_jitter, make_jitter_fn

B, H, W = 4, 8, 8


def _batch(lo=0.2, hi=0.6, seed=0):
    rng = np.random.default_rng(seed)
    return rng.uniform(lo, hi, size=(B, H, W, 3)).astype(np.float32)


def test_zero_strength_is_identity():
    x = _batch()
    y = color_jitter(jax.random.key(0), x, 0.0, 0.0, 0.0)
    np.testing.assert_allclose(np.asarray(y), x, atol=1e-6)
    assert make_jitter_fn(0.0, 0.0, 0.0) is None


def test_brightness_factor_semantics():
    """Brightness multiplies each image by one factor in [1-b, 1+b]."""
    x = _batch()  # values <= 0.6, b=0.3 -> max 0.78, no clipping
    y = np.asarray(color_jitter(jax.random.key(1), x, 0.3, 0.0, 0.0))
    ratios = y / x
    for i in range(B):
        f = ratios[i].mean()
        assert 0.7 - 1e-4 <= f <= 1.3 + 1e-4
        np.testing.assert_allclose(ratios[i], f, rtol=1e-4)
    # and the per-image factors differ (per-image draws)
    assert np.std([ratios[i].mean() for i in range(B)]) > 1e-3


def test_contrast_preserves_constant_images():
    """A constant image IS its own gray-mean anchor: contrast no-op."""
    x = np.full((B, H, W, 3), 0.4, np.float32)
    y = np.asarray(color_jitter(jax.random.key(2), x, 0.0, 0.9, 0.0))
    np.testing.assert_allclose(y, x, atol=1e-5)


def test_saturation_preserves_gray_images():
    """R=G=B images equal their grayscale: saturation no-op."""
    g = _batch()[..., :1]
    x = np.repeat(g, 3, axis=-1)
    y = np.asarray(color_jitter(jax.random.key(3), x, 0.0, 0.0, 0.9))
    np.testing.assert_allclose(y, x, atol=1e-5)


def test_output_clamped_to_image_range():
    x = _batch(0.7, 1.0)  # bright inputs, strong brightness -> clips
    y = np.asarray(color_jitter(jax.random.key(4), x, 0.9, 0.0, 0.0))
    assert y.max() <= 1.0 + 1e-6 and y.min() >= -1e-6


def test_jitter_deterministic_and_dtype_preserving():
    import jax.numpy as jnp
    x = jnp.asarray(_batch()).astype(jnp.bfloat16)
    f = make_jitter_fn(0.4, 0.4, 0.4)
    y1 = f(jax.random.key(5), x)
    y2 = f(jax.random.key(5), x)
    assert y1.dtype == jnp.bfloat16
    np.testing.assert_array_equal(np.asarray(y1, np.float32),
                                  np.asarray(y2, np.float32))


def test_engine_jitter_smoke(tmp_path):
    """--color-jitter through engine.run, composed with mixup."""
    from imagent_tpu.config import Config
    from imagent_tpu.engine import run

    cfg = Config(backend="cpu", arch="resnet18", image_size=16, num_classes=4,
                 batch_size=4, epochs=1, lr=0.05, dataset="synthetic",
                 synthetic_size=32, workers=0, bf16=False, log_every=0,
                 color_jitter=(0.4, 0.4, 0.2), mixup=0.2,
                 log_dir=str(tmp_path / "tb"),
                 ckpt_dir=str(tmp_path / "ckpt"))
    result = run(cfg)
    assert result["final_train"]["n"] == 32
    assert np.isfinite(result["final_train"]["loss"])


@pytest.mark.slow  # engine-heavy: keeps tier-1 inside its 870s budget
def test_full_extended_recipe_composes(tmp_path):
    """Every round-3 lever in ONE run: jitter + mixup/cutmix + EMA +
    label smoothing + cosine/warmup + grad accumulation — the whole
    extended recipe through engine.run."""
    from imagent_tpu.config import Config
    from imagent_tpu.engine import run

    # global batch = 2 x 8 devices x 2 accum = 32 = the dataset
    cfg = Config(backend="cpu", arch="resnet18", image_size=16, num_classes=4,
                 batch_size=2, epochs=2, lr=0.05, dataset="synthetic",
                 synthetic_size=32, workers=0, bf16=False, log_every=0,
                 color_jitter=(0.4, 0.4, 0.4), mixup=0.2, cutmix=1.0,
                 ema_decay=0.9, label_smoothing=0.1, schedule="cosine",
                 warmup_epochs=1, grad_accum=2, save_model=True,
                 log_dir=str(tmp_path / "tb"),
                 ckpt_dir=str(tmp_path / "ckpt"))
    result = run(cfg)
    assert result["final_train"]["n"] == 32
    assert np.isfinite(result["final_val"]["loss"])
    # and it resumes (EMA + augmentation state all round-trip)
    resumed = run(cfg.replace(epochs=3, resume=True))
    assert np.isfinite(resumed["final_val"]["loss"])
