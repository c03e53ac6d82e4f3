"""End-to-end CPU smoke (SURVEY §4 "Integration"): the full
init→shard→step→psum→metrics→log→checkpoint path on 8 fake devices with
synthetic data — the BASELINE.json "CPU smoke" config, hardware-free."""

import os

from imagent_tpu.config import Config
from imagent_tpu.engine import run


def _tiny_cfg(tmp_path, **kw):
    base = dict(
        backend="cpu", arch="resnet18", image_size=16, num_classes=4,
        batch_size=4, epochs=2, lr=0.05, dataset="synthetic",
        synthetic_size=128, workers=0, bf16=False, log_every=0, seed=0,
        log_dir=str(tmp_path / "tb"), ckpt_dir=str(tmp_path / "ckpt"))
    base.update(kw)
    return Config(**base)


def test_e2e_loss_decreases_and_best_tracked(tmp_path):
    cfg = _tiny_cfg(tmp_path, epochs=3, save_model=True)
    result = run(cfg)
    assert result["best_epoch"] >= 0
    assert result["best_top1"] > 0.0  # learned something above chance start


def test_e2e_resume_roundtrip(tmp_path):
    cfg = _tiny_cfg(tmp_path, epochs=1, save_model=True)
    run(cfg)
    # Resume and continue to epoch 2; must pick up from saved state.
    cfg2 = _tiny_cfg(tmp_path, epochs=2, save_model=True, resume=True)
    result = run(cfg2)
    assert result["best_epoch"] >= 0


def test_e2e_learns_synthetic(tmp_path):
    """The synthetic task is learnable: train top-1 beats chance clearly
    after a few epochs (loss-decrease assertion per SURVEY §4 Integration).
    Train metrics, not val: eval-mode BN running stats need far more steps
    to burn in at these tiny batch sizes."""
    cfg = _tiny_cfg(tmp_path, epochs=4, lr=0.1)
    result = run(cfg)
    assert result["final_train"]["top1"] > 40.0  # chance = 25%


def test_e2e_preemption_checkpoint_and_resume(tmp_path):
    """Preemption aux subsystem: a stop signal mid-epoch checkpoints LAST
    and exits cleanly; --resume redoes the interrupted epoch and
    finishes the run."""
    calls = {"n": 0}

    def stop_after_two_steps():
        calls["n"] += 1
        return calls["n"] > 2

    cfg = _tiny_cfg(tmp_path, epochs=2, save_model=True)
    result = run(cfg, stop_check=stop_after_two_steps)
    assert result["preempted"] is True
    assert (tmp_path / "ckpt" / "last").is_dir()
    # Mid-epoch checkpoint records the applied-step count so resume
    # skips exactly those batches (no gradient applied twice).
    import json
    meta = json.loads((tmp_path / "ckpt" / "last_meta.json").read_text())
    assert meta["epoch"] == -1 and meta["resume_step"] == 2

    cfg2 = _tiny_cfg(tmp_path, epochs=2, save_model=True, resume=True)
    result2 = run(cfg2)
    assert result2["preempted"] is False
    assert result2["best_epoch"] >= 0


def test_e2e_eval_only(tmp_path):
    """--eval-only: restores the checkpoint and validates, no training."""
    cfg = _tiny_cfg(tmp_path, epochs=1, save_model=True)
    run(cfg)
    cfg2 = _tiny_cfg(tmp_path, resume=True, eval_only=True)
    result = run(cfg2)
    assert result["final_val"]["n"] > 0
    assert result["final_train"]["top1"] == 0.0  # nothing trained


def test_e2e_async_ckpt_durability(tmp_path):
    """The async snapshot-then-commit LAST path (the default): commits
    land durably off the critical path — meta + manifest written, the
    in-progress marker cleared — and --resume restores them."""
    cfg = _tiny_cfg(tmp_path, epochs=2, save_model=True)
    assert cfg.async_ckpt  # the default; the sync baseline is the flag
    run(cfg)
    import json
    meta = (tmp_path / "ckpt" / "last_meta.json")
    assert meta.exists()
    assert json.loads(meta.read_text())["epoch"] == 1
    # Commit fully landed: snapshot format on disk, marker cleared,
    # integrity manifest present (hashed on the committer thread).
    assert (tmp_path / "ckpt" / "last" / "snapshot.json").is_file()
    assert not (tmp_path / "ckpt" / "last.pending.json").exists()
    assert (tmp_path / "ckpt" / "last.manifest.json").is_file()

    cfg2 = _tiny_cfg(tmp_path, epochs=3, save_model=True, resume=True)
    result = run(cfg2)
    assert result["best_epoch"] >= 0


def test_e2e_compile_cache(tmp_path, compile_cache_dir):
    """With the cache placed from outside (JAX_COMPILATION_CACHE_DIR —
    the ``compile_cache_dir`` fixture) the engine populates the
    persistent XLA cache AND the serialized AOT executable store there
    and nowhere else, in the process that holds the device (no probe
    children), and a resumed run in the same process steps the
    restored state through the LOADED donated executables."""
    from imagent_tpu import compilecache

    cache = compile_cache_dir
    before = (os.path.getmtime(compilecache.DEFAULT_CACHE_DIR)
              if os.path.isdir(compilecache.DEFAULT_CACHE_DIR) else None)
    cfg = _tiny_cfg(tmp_path, epochs=2, save_model=True)
    run(cfg)
    assert cache.is_dir() and any(cache.iterdir())  # cache written
    # No probe verdict/scratch any more; AOT store populated (one
    # entry dir with the fingerprint preimage + train/eval
    # executables).
    assert not (cache / "probe.json").exists()
    assert not (cache / ".probe_scratch").exists()
    aot_entries = [d for d in (cache / "aot").iterdir() if d.is_dir()]
    assert len(aot_entries) == 1
    assert (aot_entries[0] / "fingerprint.json").is_file()
    assert any(f.suffix == ".exe" for f in aot_entries[0].iterdir())
    # ...and the in-checkout default was not touched.
    after = (os.path.getmtime(compilecache.DEFAULT_CACHE_DIR)
             if os.path.isdir(compilecache.DEFAULT_CACHE_DIR) else None)
    assert before == after
    cfg2 = _tiny_cfg(tmp_path, epochs=3, save_model=True, resume=True)
    result = run(cfg2)
    assert result["best_epoch"] >= 0


def test_e2e_pooled_synthetic_counts_ring_batches(tmp_path):
    """With a generator pool, every epoch's telemetry counts the batches
    the pool had made before the step loop's producer asked for them
    (``synth_ahead_batches``), at most the epoch's batches; the warmed
    next epoch and the val loader run through their rings too. The run's
    ``run_start`` carries the seconds the initial state took
    (``init_s``, the benchmark's ``startup.init_s``)."""
    from imagent_tpu.telemetry.events import read_events
    cfg = _tiny_cfg(tmp_path, workers=2, synthetic_size=192)
    run(cfg)
    events = read_events(str(tmp_path / "tb" / "telemetry.jsonl"))
    starts = [e for e in events if e["event"] == "run_start"]
    assert len(starts) == 1 and starts[0]["init_s"] > 0, starts
    epochs = [e for e in events if e["event"] == "epoch"]
    assert len(epochs) == 2
    # 6 steps of 32 rows (4 a device, 8 devices): the steps outlast the
    # pool's 32 tiny samples, so it gets ahead.
    ahead = [rec["counters"]["synth_ahead_batches"] for rec in epochs]
    assert all(0 <= a <= 6 for a in ahead) and sum(ahead) > 0, ahead
