"""Model family tests (SURVEY §4 "Unit"): output shapes and parameter
counts vs torchvision's published counts (11,689,512 for resnet18 at 1000
classes — the reference's model, ``imagenet.py:312``)."""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from imagent_tpu.models import PARAM_COUNTS, create_model


def n_params(tree):
    return sum(x.size for x in jax.tree.leaves(tree))


@pytest.mark.parametrize("arch", ["resnet18", "resnet34", "resnet50"])
def test_param_counts_match_torchvision(arch):
    model = create_model(arch, num_classes=1000)
    variables = model.init(jax.random.key(0),
                           jnp.zeros((1, 64, 64, 3)), train=False)
    assert n_params(variables["params"]) == PARAM_COUNTS[arch]


@pytest.mark.parametrize("arch,count", [("resnet101", PARAM_COUNTS["resnet101"]),
                                        ("resnet152", PARAM_COUNTS["resnet152"])])
def test_param_counts_deep(arch, count):
    model = create_model(arch, num_classes=10)
    variables = model.init(jax.random.key(0),
                           jnp.zeros((1, 32, 32, 3)), train=False)
    # At 10 classes the head shrinks by 990*(512|2048)+990 params.
    head_in = 512 if arch in ("resnet18", "resnet34") else 2048
    assert n_params(variables["params"]) == count - 990 * head_in - 990


@pytest.mark.parametrize("arch", ["resnext50_32x4d", "resnext101_32x8d",
                                  "wide_resnet50_2", "wide_resnet101_2"])
def test_param_counts_resnext_wide(arch):
    """The groups/base_width generalization pinned to torchvision's
    published counts (grouped 3x3 kernels are in/groups wide)."""
    model = create_model(arch, num_classes=10)
    variables = model.init(jax.random.key(0),
                           jnp.zeros((1, 32, 32, 3)), train=False)
    assert n_params(variables["params"]) == (
        PARAM_COUNTS[arch] - 990 * 2048 - 990)


def test_resnext_forward_runs():
    model = create_model("resnext50_32x4d", num_classes=10, bf16=True)
    x = jax.random.normal(jax.random.key(1), (2, 64, 64, 3))
    variables = model.init(jax.random.key(0), x, train=False)
    logits = model.apply(variables, x, train=False)
    assert logits.shape == (2, 10)
    assert logits.dtype == jnp.float32
    # grouped 3x3: kernel input dim is width/groups = 128/32
    k = variables["params"]["layer1_block0"]["Conv_1"]["kernel"]
    assert k.shape == (3, 3, 4, 128)


def test_forward_shapes_and_dtype():
    model = create_model("resnet18", num_classes=1000, bf16=True)
    x = jnp.zeros((2, 64, 64, 3))
    variables = model.init(jax.random.key(0), x, train=False)
    logits = model.apply(variables, x, train=False)
    assert logits.shape == (2, 1000)
    assert logits.dtype == jnp.float32  # head is fp32 even under bf16


def test_batchnorm_state_updates_in_train_mode():
    model = create_model("resnet18", num_classes=10)
    x = jax.random.normal(jax.random.key(1), (2, 32, 32, 3))
    variables = model.init(jax.random.key(0), x, train=True)
    _, mutated = model.apply(variables, x, train=True,
                             mutable=["batch_stats"])
    before = variables["batch_stats"]["bn1"]["mean"]
    after = mutated["batch_stats"]["bn1"]["mean"]
    assert not jnp.allclose(before, after)


def test_vit_param_counts_match_torchvision():
    from imagent_tpu.models.vit import VIT_PARAM_COUNTS
    model = create_model("vit_b16", num_classes=1000)
    variables = model.init(jax.random.key(0),
                           jnp.zeros((1, 224, 224, 3)), train=False)
    assert n_params(variables["params"]) == VIT_PARAM_COUNTS["vit_b16"]


def test_vit_forward_shape():
    model = create_model("vit_b16", num_classes=10, bf16=True)
    x = jnp.zeros((2, 32, 32, 3))
    variables = model.init(jax.random.key(0), x, train=False)
    logits = model.apply(variables, x, train=False)
    assert logits.shape == (2, 10)
    assert logits.dtype == jnp.float32


def test_s2d_stem_equivalent_family():
    """The space-to-depth stem (docs/ROOFLINE.md "levers") is the
    MLPerf-style exact rewrite of the 7x7/s2 stem: same output shape,
    4x4x12x64 conv1 kernel, and the train step still learns."""
    model = create_model("resnet18", num_classes=10, stem="s2d")
    x = jax.random.normal(jax.random.key(1), (2, 64, 64, 3))
    variables = model.init(jax.random.key(0), x, train=False)
    assert variables["params"]["conv1"]["kernel"].shape == (4, 4, 12, 64)
    logits = model.apply(variables, x, train=False)
    assert logits.shape == (2, 10)
    # same spatial plan as v1: conv1 output is H/2 = 32
    _, inter = model.apply(variables, x, train=False,
                           capture_intermediates=True)
    conv1_out = inter["intermediates"]["conv1"]["__call__"][0]
    assert conv1_out.shape == (2, 32, 32, 64)
    # the even-H/W requirement is an explicit error, not a reshape crash
    with pytest.raises(ValueError, match="even H/W"):
        model.init(jax.random.key(0),
                   jax.numpy.zeros((1, 63, 63, 3)), train=False)
    with pytest.raises(ValueError, match="unknown stem"):
        create_model("resnet18", num_classes=10, stem="S2D").init(
            jax.random.key(0), x, train=False)


def test_vit_fused_qkv_same_tree_same_logits():
    """--fused-qkv computes q/k/v as one GEMM from the SAME param
    tensors: identical tree (checkpoints/TP specs/torch-compat
    unaffected) and identical logits on shared params."""
    import jax

    from imagent_tpu.models.vit import VisionTransformer

    kw = dict(patch_size=8, hidden_dim=64, num_layers=2, num_heads=4,
              mlp_dim=128, num_classes=10)
    m0 = VisionTransformer(**kw)
    m1 = VisionTransformer(**kw, fused_qkv=True)
    x = jax.random.normal(jax.random.key(1), (2, 32, 32, 3))
    v = m0.init(jax.random.key(0), x, train=False)
    v1 = m1.init(jax.random.key(0), x, train=False)
    assert (jax.tree_util.tree_structure(v)
            == jax.tree_util.tree_structure(v1))
    # Same key ⇒ IDENTICAL init values: flax folds the param rng by
    # path, and _ProjParams draws on DenseGeneral's flattened fan-in
    # shape — this is what catches an initializer-distribution drift
    # between the two paths (found by review in round 4).
    jax.tree.map(
        lambda a, b: np.testing.assert_array_equal(
            np.asarray(a), np.asarray(b)), v, v1)
    y0 = np.asarray(m0.apply(v, x, train=False))
    y1 = np.asarray(m1.apply(v, x, train=False))
    np.testing.assert_allclose(y1, y0, rtol=1e-5, atol=1e-5)


def test_vit_register_tokens():
    """Registers append learned tokens (R x D params) that ride the
    encoder but are excluded from both cls and GAP readout."""
    import jax
    import jax.numpy as jnp

    from imagent_tpu.models.vit import VisionTransformer

    kw = dict(patch_size=8, hidden_dim=64, num_layers=2, num_heads=4,
              mlp_dim=128, num_classes=10)
    x = jax.random.normal(jax.random.key(1), (2, 32, 32, 3))
    base = VisionTransformer(**kw)
    reg = VisionTransformer(**kw, register_tokens=5)
    v0 = base.init(jax.random.key(0), x, train=False)
    v5 = reg.init(jax.random.key(0), x, train=False)
    n0 = sum(a.size for a in jax.tree_util.tree_leaves(v0))
    n5 = sum(a.size for a in jax.tree_util.tree_leaves(v5))
    assert n5 - n0 == 5 * 64
    assert reg.apply(v5, x, train=False).shape == (2, 10)

    # GAP readout pools only the real tokens: zeroing the register
    # params must not be equivalent to removing them from the mean
    # (they still attend), but the output must stay finite and the
    # readout shape unchanged.
    gap = VisionTransformer(**kw, register_tokens=5, gap_readout=True)
    vg = gap.init(jax.random.key(0), x, train=False)
    out = gap.apply(vg, x, train=False)
    assert out.shape == (2, 10) and bool(jnp.isfinite(out).all())

    # seq-parallel + registers is rejected loudly.
    import pytest

    sp = VisionTransformer(**kw, register_tokens=4, gap_readout=True,
                           attn_impl="ring", seq_axis="model")
    with pytest.raises(ValueError, match="register_tokens"):
        sp.init(jax.random.key(0), x, train=False)


# --- ConvNeXt family (models/convnext.py) ---


@pytest.mark.parametrize("arch,nc", [("convnext_tiny", 1000),
                                     ("convnext_small", 1000),
                                     ("convnext_base", 10),
                                     ("convnext_large", 10)])
def test_convnext_param_counts(arch, nc):
    """Pinned to torchvision's published counts (28,589,128 for tiny at
    1000 classes); the 10-class heads shrink by 990*dim + 990."""
    from imagent_tpu.models.convnext import (
        CONVNEXT_DEFS, CONVNEXT_PARAM_COUNTS,
    )
    model = create_model(arch, num_classes=nc)
    variables = model.init(jax.random.key(0),
                           jnp.zeros((1, 32, 32, 3)), train=False)
    want = CONVNEXT_PARAM_COUNTS[arch]
    if nc != 1000:
        want -= 990 * CONVNEXT_DEFS[arch][1][-1] + 990
    assert n_params(variables["params"]) == want
    assert "batch_stats" not in variables  # LayerNorm-only network


def test_convnext_forward_and_grad_step():
    """A small custom-geometry ConvNeXt trains through the production
    loss (no batch_stats collection — the ViT/stat-less path)."""
    from imagent_tpu.models.convnext import ConvNeXt
    from imagent_tpu.ops import softmax_cross_entropy

    model = ConvNeXt(depths=(1, 1, 2, 1), dims=(16, 24, 32, 48),
                     num_classes=7)
    x = jax.random.normal(jax.random.key(1), (4, 32, 32, 3))
    y = jnp.array([0, 1, 2, 3])
    v = model.init(jax.random.key(0), x, train=False)

    def loss(p):
        logits = model.apply({"params": p}, x, train=True)
        return softmax_cross_entropy(logits, y).mean()

    l0, grads = jax.value_and_grad(loss)(v["params"])
    assert jnp.isfinite(l0)
    gnorm = sum(jnp.sum(g * g) for g in jax.tree.leaves(grads))
    assert gnorm > 0
    out = model.apply(v, x, train=False)
    assert out.shape == (4, 7)


def test_convnext_drop_path():
    """Stochastic depth: library-level (rngs required), per-sample,
    linearly scaled, off in eval and at rate 0."""
    from imagent_tpu.models.convnext import ConvNeXt

    kw = dict(depths=(1, 1, 2, 1), dims=(8, 12, 16, 24), num_classes=5)
    x = jax.random.normal(jax.random.key(1), (8, 32, 32, 3))
    base = ConvNeXt(**kw)
    drop = ConvNeXt(**kw, drop_path_rate=0.9)
    v = base.init(jax.random.key(0), x, train=False)

    # Same tree (drop-path adds no params); eval path identical.
    np.testing.assert_array_equal(
        np.asarray(base.apply(v, x, train=False)),
        np.asarray(drop.apply(v, x, train=False)))
    # Train with rngs: stochastic (two keys differ). Bit-inequality,
    # not allclose: at init the layer-scale gamma (1e-6) shrinks every
    # residual branch below allclose's tolerance, so differing masks
    # still compare "close" — identical masks would be bit-identical.
    o1 = drop.apply(v, x, train=True,
                    rngs={"droppath": jax.random.key(1)})
    o2 = drop.apply(v, x, train=True,
                    rngs={"droppath": jax.random.key(2)})
    assert not np.array_equal(np.asarray(o1), np.asarray(o2))
    # And determinism: the same key reproduces bit-exactly.
    o1b = drop.apply(v, x, train=True,
                     rngs={"droppath": jax.random.key(1)})
    np.testing.assert_array_equal(np.asarray(o1), np.asarray(o1b))
    # Train without rngs raises (the production step runs rate 0 only).
    with pytest.raises(Exception, match="droppath"):
        drop.apply(v, x, train=True)


def test_convnext_engine_smoke(tmp_path):
    """convnext_tiny through the full engine (sharded step, metrics,
    checkpointing) on the fake-device mesh — 1 epoch of synthetic data.
    Exercises the stat-less model path end-to-end."""
    from imagent_tpu.config import Config
    from imagent_tpu.engine import run

    cfg = Config(backend="cpu", arch="convnext_tiny", image_size=32, num_classes=8,
                 batch_size=8, epochs=1, lr=0.05, dataset="synthetic",
                 synthetic_size=32, workers=0, bf16=False, log_every=0,
                 seed=0, log_dir=str(tmp_path / "tb"),
                 ckpt_dir=str(tmp_path / "ckpt"))
    out = run(cfg)
    assert np.isfinite(out["final_train"]["loss"])


def test_convnext_remat_matches():
    """--remat wraps each block in jax.checkpoint: forward values are
    identical; only the backward schedule changes."""
    from imagent_tpu.models.convnext import ConvNeXt

    kw = dict(depths=(1, 1, 1, 1), dims=(8, 12, 16, 24), num_classes=5)
    x = jax.random.normal(jax.random.key(2), (2, 32, 32, 3))
    base = ConvNeXt(**kw)
    rem = ConvNeXt(**kw, remat=True)
    v = base.init(jax.random.key(0), x, train=False)
    np.testing.assert_allclose(
        np.asarray(base.apply(v, x, train=True)),
        np.asarray(rem.apply(v, x, train=True)), rtol=1e-6, atol=1e-6)
