"""The plain reference against the program at a small size on the CPU
(both configurations), and the benchmark's copy of the sample function
and epoch order against the program's, byte for byte."""

import json
import os

import numpy as np
import pytest

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))
SIZE, CLASSES, BATCH = 64, 10, 16


def config(name):
    with open(os.path.join(ROOT, "chipbench", "configs",
                           f"{name}.json")) as f:
        cfg = json.load(f)
    cfg.update(image_size=SIZE, num_classes=CLASSES)
    return cfg


def small_mix():
    from chipbench import traffic
    mix = traffic.load("synth_u8_b256_w12")
    mix.update(per_chip_batch=BATCH, epoch_images=4096, workers=0)
    return mix


@pytest.mark.parametrize("name,seed", [("resnet50", 7),
                                       ("wide_resnet50_2", 2**31 + 9)])
def test_reference_agrees_with_program(name, seed):
    """Same seed, same rows, float32 on both sides: the program's
    initial parameters are the reference's to rounding, its loss agrees
    to 1e-4 and every leaf's first gradient to a few percent (at 16
    rows of 64 px the network amplifies float32 round-off through
    BatchNorm over 64 values; a wrong stride, padding, epsilon or loss
    reads tens of percent to 100%)."""
    import flax
    import jax
    import jax.numpy as jnp
    from chipbench import traffic
    from chipbench.families.resnet import reference as ref
    from imagent_tpu.models import create_model
    from imagent_tpu.train import (
        create_train_state, make_input_prep, make_loss_fn, make_optimizer,
    )
    cfg = config(name)
    model = create_model(name, CLASSES, False)
    state = create_train_state(model, jax.random.key(seed), SIZE,
                               make_optimizer())
    flat = {"/".join(k): v for k, v in
            flax.traverse_util.flatten_dict(state.params).items()}
    p = ref.init_params(cfg, seed)
    assert set(p) == set(flat)
    assert sum(v.size for v in p.values()) > 2e7
    for k in p:
        np.testing.assert_allclose(np.asarray(flat[k]), np.asarray(p[k]),
                                   rtol=0, atol=1e-7, err_msg=k)
    (images, labels), = traffic.batches(small_mix(), seed, SIZE, CLASSES,
                                        1, 1)
    prep = make_input_prep(cfg["mean"], cfg["std"])
    loss_fn = make_loss_fn(model)
    lp, gp = jax.value_and_grad(lambda q: loss_fn(
        q, state.batch_stats, prep(jnp.asarray(images)),
        jnp.asarray(labels))[0])(state.params)
    lr, gr = jax.value_and_grad(lambda q: ref.loss_fn(
        q, jnp.asarray(images), jnp.asarray(labels), cfg))(p)
    assert abs(float(lp) - float(lr)) / float(lr) < 1e-4
    gp = {"/".join(k): np.asarray(v, np.float64) for k, v in
          flax.traverse_util.flatten_dict(gp).items()}
    norms = {k: np.linalg.norm(np.asarray(v, np.float64))
             for k, v in gr.items()}
    med = np.median(list(norms.values()))
    for k, v in gr.items():
        diff = np.linalg.norm(gp[k] - np.asarray(v, np.float64))
        assert diff / max(norms[k], med) < 0.15, (k, diff, norms[k])


def test_sample_function_and_order_are_the_programs():
    from chipbench import traffic
    from chipbench.generators.synthetic_sine_u8 import sample_u8
    from imagent_tpu.config import Config
    from imagent_tpu.data.synthetic import SyntheticLoader, _gen_one
    for args in [(1.5, 3.25, 32, 12345), (2.0, 1.0, 64, 5 * 1000003 + 9)]:
        assert sample_u8(*args).tobytes() == \
            _gen_one(*args).tobytes()
    seed = 2**31 + 3
    cfg = Config(seed=seed, image_size=32, num_classes=CLASSES,
                 batch_size=8, synthetic_size=4096, workers=0,
                 dataset="synthetic", transfer_dtype="uint8")
    loader = SyntheticLoader(cfg, 0, 1, global_batch=16, train=True)
    mix = small_mix()
    mix.update(per_chip_batch=8)
    mine = traffic.batches(mix, seed, 32, CLASSES, chips=2, steps=3)
    theirs = loader.epoch(0)
    for (images, labels), batch in zip(mine, theirs):
        assert images.dtype == np.uint8
        assert np.asarray(batch.images).tobytes() == images.tobytes()
        assert np.array_equal(np.asarray(batch.labels), labels)


def test_three_steps_follow_torch_order_sgd():
    """``follow`` applies weight decay to the gradient, then momentum,
    then the warm-up learning rate of epoch 0; two replicas average
    their gradients and each normalises with its own rows."""
    from chipbench import traffic
    from chipbench.families.resnet import reference as ref
    cfg = config("resnet50")
    cfg.update(image_size=32)
    mix = small_mix()
    mix.update(per_chip_batch=4)
    batches = traffic.batches(mix, 3, 32, CLASSES, chips=2, steps=2)
    out = ref.follow(cfg, 3, batches, replicas=2)
    assert ref.lr_at_epoch0(cfg) == pytest.approx(0.02)
    k = "fc/kernel"
    one = cfg["lr"] / cfg["warmup_epochs"] * (
        out["grad1"][k] + cfg["weight_decay"] * out["p0"][k])
    np.testing.assert_allclose(out["p0"][k] - one, ref.follow(
        cfg, 3, batches[:1], replicas=2)["p_end"][k], rtol=1e-5,
        atol=1e-7)
    alone = ref.follow(cfg, 3, batches, replicas=2, fault="no_exchange")
    assert np.linalg.norm(alone["grad1"][k] - out["grad1"][k]) > \
        0.1 * np.linalg.norm(out["grad1"][k])
    assert len(out["losses"]) == 2
