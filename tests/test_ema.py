"""Parameter EMA (train.TrainState.ema_params, --ema-decay).

The reference has no weight averaging; this is the standard recipe
lever, maintained inside the jitted step so it costs one fused
multiply-add pass and no extra host traffic.
"""

import jax
import numpy as np
import pytest

from imagent_tpu.cluster import make_mesh
from imagent_tpu.models import create_model
from imagent_tpu.train import (
    create_train_state, make_optimizer, make_train_step, replicate_state,
    shard_batch,
)

B, SIZE, C = 8, 16, 4


def _setup(ema_decay):
    mesh = make_mesh(model_parallel=1)
    model = create_model("resnet18", num_classes=C)
    opt = make_optimizer()
    state = create_train_state(model, jax.random.key(0), SIZE, opt)
    if ema_decay > 0.0:
        import jax.numpy as jnp
        state = state.replace(
            ema_params=jax.tree.map(jnp.array, state.params))
    state = replicate_state(state, mesh)
    step = make_train_step(model, opt, mesh, ema_decay=ema_decay)
    rng = np.random.default_rng(1)
    images = rng.normal(size=(B, SIZE, SIZE, 3)).astype(np.float32)
    labels = rng.integers(0, C, size=(B,)).astype(np.int32)
    return mesh, state, step, images, labels


def test_ema_update_math():
    """After one step: ema == d * init + (1-d) * new_params, and the
    params trajectory is IDENTICAL to a no-EMA run (the average is an
    observer, never fed back into training)."""
    d = 0.5
    mesh, state, step, images, labels = _setup(d)
    init = jax.device_get(state.params)
    gi, gl = shard_batch(mesh, images, labels)
    new_state, _ = step(state, gi, gl, np.float32(0.1))

    mesh2, state2, step2, _, _ = _setup(0.0)
    assert state2.ema_params is None
    new_plain, _ = step2(state2, *shard_batch(mesh2, images, labels),
                         np.float32(0.1))

    got_p = jax.device_get(new_state.params)
    want_p = jax.device_get(new_plain.params)
    jax.tree.map(lambda a, b: np.testing.assert_allclose(a, b, rtol=1e-6),
                 got_p, want_p)
    got_ema = jax.device_get(new_state.ema_params)
    jax.tree.map(
        lambda e, i, p: np.testing.assert_allclose(
            e, d * i + (1 - d) * p, rtol=1e-5, atol=1e-7),
        got_ema, init, got_p)
    assert jax.device_get(new_plain.ema_params) is None


def test_engine_ema_trains_and_resumes(tmp_path):
    """--ema-decay end-to-end: eval runs on the averaged weights, the
    EMA rides the checkpoint, and --resume continues it."""
    from imagent_tpu.config import Config
    from imagent_tpu.engine import run

    cfg = Config(backend="cpu", arch="resnet18", image_size=16, num_classes=4,
                 batch_size=4, epochs=2, lr=0.05, dataset="synthetic",
                 synthetic_size=32, workers=0, bf16=False, log_every=0,
                 ema_decay=0.9, save_model=True,
                 log_dir=str(tmp_path / "tb"),
                 ckpt_dir=str(tmp_path / "ckpt"))
    result = run(cfg)
    assert np.isfinite(result["final_val"]["loss"])

    resumed = run(cfg.replace(epochs=3, resume=True))
    assert np.isfinite(resumed["final_val"]["loss"])


def test_eval_uses_ema_weights(tmp_path):
    """The evaluated model is the averaged one: with decay ~1.0 the EMA
    stays at initialization, so val metrics must differ from a no-EMA
    twin whose eval tracks the trained weights."""
    from imagent_tpu.config import Config
    from imagent_tpu.engine import run

    base = dict(backend="cpu", arch="resnet18", image_size=16, num_classes=4,
                batch_size=8, epochs=2, lr=0.2, dataset="synthetic",
                synthetic_size=64, workers=0, bf16=False, log_every=0,
                log_dir=str(tmp_path / "tb1"),
                ckpt_dir=str(tmp_path / "c1"))
    frozen = run(Config(**base, ema_decay=0.999999))
    live = run(Config(**{**base, "log_dir": str(tmp_path / "tb2"),
                         "ckpt_dir": str(tmp_path / "c2")}))
    assert frozen["final_val"]["loss"] != pytest.approx(
        live["final_val"]["loss"], rel=1e-6)


def test_ema_toggle_across_restore(tmp_path):
    """ADVICE r3 (medium): --ema-decay toggled between the writing run
    and the resuming one changes the TrainState tree structure; restore
    must reconcile instead of failing every probe with a misleading
    arch-mismatch error. Off->on initializes the average from the
    restored params; on->off drops the buffers."""
    import jax.numpy as jnp

    from imagent_tpu import checkpoint as ckpt_lib
    from imagent_tpu.cluster import make_mesh
    from imagent_tpu.models import create_model
    from imagent_tpu.train import (
        create_train_state, make_optimizer, replicate_state,
    )

    mesh = make_mesh(model_parallel=1)
    state = replicate_state(
        create_train_state(create_model("resnet18", num_classes=4),
                           jax.random.key(0), 16, make_optimizer()), mesh)
    with_ema = state.replace(
        ema_params=jax.tree.map(lambda p: jnp.array(p) * 0.5, state.params))

    # Written WITHOUT ema, resumed WITH --ema-decay: the average starts
    # from the restored params.
    ckpt_lib.save(str(tmp_path / "a"), "last", state, {"epoch": 1})
    got, meta = ckpt_lib.restore(str(tmp_path / "a"), "last", with_ema)
    assert meta["epoch"] == 1
    assert got.ema_params is not None
    jax.tree.map(
        lambda e, p: np.testing.assert_array_equal(
            jax.device_get(e), jax.device_get(p)),
        got.ema_params, got.params)

    # Written WITH ema, resumed with --ema-decay off: buffers dropped.
    ckpt_lib.save(str(tmp_path / "b"), "last", with_ema, {"epoch": 2})
    got2, meta2 = ckpt_lib.restore(str(tmp_path / "b"), "last", state)
    assert meta2["epoch"] == 2
    assert got2.ema_params is None
    jax.tree.map(
        lambda a, b: np.testing.assert_array_equal(
            jax.device_get(a), jax.device_get(b)),
        got2.params, with_ema.params)


@pytest.mark.slow  # engine-heavy: keeps tier-1 inside its 870s budget
def test_engine_enables_ema_mid_run(tmp_path):
    """End-to-end: a run checkpointed without EMA resumes with
    --ema-decay on (and back off) through engine.run."""
    from imagent_tpu.config import Config
    from imagent_tpu.engine import run

    base = dict(backend="cpu", arch="resnet18", image_size=16, num_classes=4,
                batch_size=4, epochs=1, lr=0.05, dataset="synthetic",
                synthetic_size=32, workers=0, bf16=False, log_every=0,
                save_model=True, log_dir=str(tmp_path / "tb"),
                ckpt_dir=str(tmp_path / "ckpt"))
    run(Config(**base))
    on = run(Config(**{**base, "epochs": 2}, resume=True, ema_decay=0.9))
    assert np.isfinite(on["final_val"]["loss"])
    off = run(Config(**{**base, "epochs": 3}, resume=True))
    assert np.isfinite(off["final_val"]["loss"])


def test_ema_tracks_batch_stats(mesh8):
    """Round-4 fix: the EMA averages BatchNorm running stats too (timm
    ModelEmaV2 buffer semantics). Evaluating EMA params against the
    LIVE stats diverged on the run of record (val loss 3817 at decay
    0.999 — the stats tracked params ~10 epochs ahead of the average).
    One step must give ema_bs' = d*ema_bs + (1-d)*bs'."""
    import jax.numpy as jnp

    from imagent_tpu.models import create_model
    from imagent_tpu.train import (
        create_train_state, make_optimizer, make_train_step,
        replicate_state,
    )

    d = 0.9
    model = create_model("resnet18", num_classes=4)
    opt = make_optimizer()
    state = create_train_state(model, jax.random.key(0), 16, opt)
    state = state.replace(
        ema_params=jax.tree.map(jnp.array, state.params),
        ema_batch_stats=jax.tree.map(jnp.array, state.batch_stats))
    init_bs = jax.device_get(state.batch_stats)
    state = replicate_state(state, mesh8)
    step = make_train_step(model, opt, mesh8, ema_decay=d)

    rng = np.random.default_rng(0)
    images = rng.normal(size=(16, 16, 16, 3)).astype(np.float32)
    labels = rng.integers(0, 4, size=(16,)).astype(np.int32)
    from imagent_tpu.train import shard_batch
    gi, gl = shard_batch(mesh8, images, labels)
    new, _ = step(state, gi, gl, np.float32(0.1))

    got = jax.device_get(new.ema_batch_stats)
    live = jax.device_get(new.batch_stats)
    jax.tree.map(
        lambda e, i, s: np.testing.assert_allclose(
            e, d * i + (1 - d) * s, rtol=1e-5, atol=1e-7),
        got, init_bs, live)


def test_legacy_ema_checkpoint_gains_stat_buffers(tmp_path):
    """A pre-round-4 EMA checkpoint (ema_params but NO ema_batch_stats)
    must restore into the new layout with the stat average initialized
    from the restored running stats — not fail the probe."""
    import jax.numpy as jnp

    from imagent_tpu import checkpoint as ckpt_lib
    from imagent_tpu.cluster import make_mesh
    from imagent_tpu.models import create_model
    from imagent_tpu.train import (
        create_train_state, make_optimizer, replicate_state,
    )

    mesh = make_mesh(model_parallel=1)
    base = create_train_state(create_model("resnet18", num_classes=4),
                              jax.random.key(0), 16, make_optimizer())
    legacy = replicate_state(base.replace(
        ema_params=jax.tree.map(lambda p: jnp.array(p) * 0.5,
                                base.params)), mesh)
    assert legacy.ema_batch_stats is None
    ckpt_lib.save(str(tmp_path), "last", legacy, {"epoch": 3})

    target = replicate_state(base.replace(
        ema_params=jax.tree.map(jnp.array, base.params),
        ema_batch_stats=jax.tree.map(jnp.array, base.batch_stats)), mesh)
    got, meta = ckpt_lib.restore(str(tmp_path), "last", target)
    assert meta["epoch"] == 3
    assert got.ema_batch_stats is not None
    jax.tree.map(
        lambda e, s: np.testing.assert_array_equal(
            jax.device_get(e), jax.device_get(s)),
        got.ema_batch_stats, got.batch_stats)
    # And the params average is the LEGACY one (0.5x), not re-initialized.
    jax.tree.map(
        lambda e, p: np.testing.assert_allclose(
            jax.device_get(e), jax.device_get(p) * 0.5, rtol=1e-6),
        got.ema_params, got.params)
