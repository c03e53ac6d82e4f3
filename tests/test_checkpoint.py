"""Checkpoint robustness (ADVICE r1): legacy-layout restore and the
mid-epoch resume topology guard."""

import numpy as np
import pytest

import jax

from imagent_tpu import checkpoint as ckpt_lib
from imagent_tpu.cluster import make_mesh
from imagent_tpu.config import Config
from imagent_tpu.engine import run
from imagent_tpu.models import create_model
from imagent_tpu.train import (
    create_train_state, make_optimizer, replicate_state,
)


def _tiny_state():
    model = create_model("resnet18", num_classes=4)
    opt = make_optimizer()
    return create_train_state(model, jax.random.key(0), 16, opt)


def test_legacy_flat_layout_restores_with_sidecar_meta(tmp_path):
    """A round-1 checkpoint (flat TrainState, meta only in the JSON
    sidecar) must restore — not die inside Orbax with a tree mismatch."""
    import json
    import os

    import orbax.checkpoint as ocp

    state = replicate_state(_tiny_state(), make_mesh(model_parallel=1))
    path = os.path.abspath(str(tmp_path / "last"))
    ckptr = ocp.StandardCheckpointer()
    ckptr.save(path, state)  # the OLD layout: no {state, meta} nesting
    ckptr.wait_until_finished()
    with open(str(tmp_path / "last_meta.json"), "w") as f:
        json.dump({"epoch": 3, "best_top1": 41.5, "best_epoch": 2}, f)

    restored = ckpt_lib.restore(str(tmp_path), "last", state)
    assert restored is not None
    got_state, meta = restored
    assert meta["epoch"] == 3 and meta["best_top1"] == 41.5
    np.testing.assert_array_equal(
        np.asarray(jax.device_get(got_state.params["conv1"]["kernel"])),
        np.asarray(jax.device_get(state.params["conv1"]["kernel"])))


def test_wrong_arch_still_fails_loudly(tmp_path):
    """The legacy fallback must NOT mask genuine shape mismatches."""
    state = replicate_state(_tiny_state(), make_mesh(model_parallel=1))
    ckpt_lib.save(str(tmp_path), "last", state, {"epoch": 0})
    other = replicate_state(
        create_train_state(create_model("resnet34", num_classes=4),
                           jax.random.key(0), 16, make_optimizer()),
        make_mesh(model_parallel=1))
    with pytest.raises(Exception, match="arch|shape|match|structure"):
        ckpt_lib.restore(str(tmp_path), "last", other)


def _cfg(tmp_path, **kw):
    base = dict(backend="cpu", arch="resnet18", image_size=16, num_classes=4, batch_size=4,
                epochs=2, lr=0.05, dataset="synthetic", synthetic_size=128,
                workers=0, bf16=False, log_every=0, seed=0, save_model=True,
                log_dir=str(tmp_path / "tb"), ckpt_dir=str(tmp_path / "ck"))
    base.update(kw)
    return Config(**base)


def test_mid_epoch_resume_topology_mismatch_rejected(tmp_path):
    """A mid-epoch (resume_step > 0) checkpoint records its loader-order
    fingerprint (global_batch, process_count, seed); resuming under a
    different one must fail loudly, not silently skip wrong batches."""
    calls = {"n": 0}

    def stop_after_two(n=2):
        calls["n"] += 1
        return calls["n"] > n

    result = run(_cfg(tmp_path), stop_check=stop_after_two)
    assert result["preempted"] is True

    with pytest.raises(ValueError, match="topology mismatch"):
        run(_cfg(tmp_path, resume=True, seed=1))  # different seed
    with pytest.raises(ValueError, match="topology mismatch"):
        run(_cfg(tmp_path, resume=True, batch_size=8))  # different batch
    # Matching topology resumes fine.
    result = run(_cfg(tmp_path, resume=True))
    assert result["preempted"] is False


def test_prior_five_field_meta_layout_restores(tmp_path):
    """A checkpoint from the previous framework version ({state, meta}
    layout but without the topology fields) must restore with the new
    fields defaulting — not die with a tree mismatch."""
    import os

    import orbax.checkpoint as ocp

    state = replicate_state(_tiny_state(), make_mesh(model_parallel=1))
    path = os.path.abspath(str(tmp_path / "last"))
    # 0-d ndarrays, not bare numpy scalars: older Orbax versions reject
    # np.int64 leaves in save() (the framework's own save() always
    # wraps with np.asarray).
    old_meta = {"epoch": np.asarray(4, np.int64),
                "best_top1": np.asarray(39.0, np.float64),
                "best_top5": np.asarray(70.0, np.float64),
                "best_epoch": np.asarray(4, np.int64),
                "resume_step": np.asarray(0, np.int64)}
    ckptr = ocp.StandardCheckpointer()
    ckptr.save(path, {"state": state, "meta": old_meta})
    ckptr.wait_until_finished()

    restored = ckpt_lib.restore(str(tmp_path), "last", state)
    assert restored is not None
    _, meta = restored
    assert meta["epoch"] == 4 and meta["best_top1"] == 39.0
    assert meta["global_batch"] == 0  # new field defaults
    assert meta["seed"] == -1


def test_prior_meta_layout_restores_without_metadata_api(
        tmp_path, monkeypatch):
    """ADVICE r2: when the Orbax metadata API is unavailable, the probe
    fallback must still restore a {state, meta} checkpoint with the
    older 5-field meta set — not raise the misleading arch-mismatch
    error after only trying the full 8-field probe."""
    import os

    import orbax.checkpoint as ocp

    state = replicate_state(_tiny_state(), make_mesh(model_parallel=1))
    path = os.path.abspath(str(tmp_path / "last"))
    old_meta = {"epoch": np.asarray(7, np.int64),
                "best_top1": np.asarray(55.0, np.float64),
                "best_top5": np.asarray(80.0, np.float64),
                "best_epoch": np.asarray(6, np.int64),
                "resume_step": np.asarray(0, np.int64)}
    ckptr = ocp.StandardCheckpointer()
    ckptr.save(path, {"state": state, "meta": old_meta})
    ckptr.wait_until_finished()

    def _no_metadata(self, *a, **k):
        raise NotImplementedError("metadata API unavailable")

    monkeypatch.setattr(ocp.StandardCheckpointer, "metadata",
                        _no_metadata)
    restored = ckpt_lib.restore(str(tmp_path), "last", state)
    assert restored is not None
    _, meta = restored
    assert meta["epoch"] == 7 and meta["best_top1"] == 55.0
    assert meta["global_batch"] == 0 and meta["seed"] == -1


def test_kill_during_async_save_preserves_previous(tmp_path):
    """Durability under preemption-during-save (found by the round-2
    run-of-record exercise): a process killed while an ASYNC save is in
    flight must not destroy the previous durable checkpoint. The live
    name is never the write target (staging + commit swap)."""
    import os
    import subprocess
    import sys

    worker = r"""
import sys, os
os.environ["JAX_PLATFORMS"] = "cpu"
import jax; jax.config.update("jax_platforms", "cpu")
from imagent_tpu import checkpoint as ckpt_lib
from imagent_tpu.cluster import make_mesh
from imagent_tpu.models import create_model
from imagent_tpu.train import (create_train_state, make_optimizer,
                               replicate_state)
d, mode = sys.argv[1], sys.argv[2]
state = replicate_state(
    create_train_state(create_model("resnet18", num_classes=4),
                       jax.random.key(0), 16, make_optimizer()),
    make_mesh(model_parallel=1))
if mode == "first":
    ckpt_lib.save(d, "last", state, {"epoch": 1}, block=True)
elif mode == "kill_async":
    ckpt_lib.save(d, "last", state, {"epoch": 2}, block=False)
    os._exit(9)  # die mid-async-save, like a hard preemption
elif mode == "check":
    r = ckpt_lib.restore(d, "last", state)
    print("RESTORED", "none" if r is None else r[1]["epoch"])
"""
    env = dict(os.environ)
    env["PYTHONPATH"] = (os.path.dirname(os.path.dirname(
        os.path.abspath(__file__))) + os.pathsep + env.get("PYTHONPATH", ""))
    env.pop("XLA_FLAGS", None)

    def run_mode(mode, check_rc=True):
        p = subprocess.run([sys.executable, "-c", worker, str(tmp_path),
                            mode], env=env, capture_output=True, text=True,
                           timeout=240,
                           cwd=os.path.dirname(os.path.dirname(
                               os.path.abspath(__file__))))
        if check_rc:
            assert p.returncode == 0, p.stdout + p.stderr
        return p.stdout

    run_mode("first")
    run_mode("kill_async", check_rc=False)  # exits 9 by design
    out = run_mode("check")
    assert "RESTORED 1" in out, out
