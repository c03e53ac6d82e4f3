"""Decode offload (``data/offload.py`` + ``python -m
imagent_tpu.data.serve``): wire roundtrip byte-identical to local
decode, handshake/label safety, degrade-to-local on service death,
and the ISSUE 11 acceptance drills — a training process fed over
localhost beats the local-decode baseline under an injected
slow-decode fault, and a mid-epoch service death completes the run on
local decode. The input-wait alert (``--input-wait-alert``) and the
train/eval blocked-series split are asserted on the same runs."""

import json
import os
import subprocess
import sys
import time

import numpy as np
import pytest
from PIL import Image

from imagent_tpu.config import Config
from imagent_tpu.data.imagefolder import ImageFolderLoader
from imagent_tpu.data.offload import (
    DecodeServer, OffloadClient, parse_endpoints,
)
from imagent_tpu.resilience import faultinject
from marginal import is_slow_host, marginal_attempts, retry_marginal

_DIR = os.path.dirname(os.path.abspath(__file__))
_REPO = os.path.dirname(_DIR)

N_TRAIN = 256  # global batch 16 on the 8-device CPU mesh -> 16 steps


def _build_imagefolder(root: str, n_train=N_TRAIN, n_val=8) -> None:
    rng = np.random.default_rng(0)
    for split, total in (("train", n_train), ("val", n_val)):
        for c in ("clsa", "clsb"):
            d = os.path.join(root, split, c)
            os.makedirs(d)
            for i in range(total // 2):
                arr = rng.integers(0, 255, size=(20, 20, 3),
                                   dtype=np.uint8)
                Image.fromarray(arr).save(os.path.join(d, f"{i}.jpg"),
                                          quality=90)


@pytest.fixture(scope="module")
def data_root(tmp_path_factory):
    root = str(tmp_path_factory.mktemp("offload_data"))
    _build_imagefolder(root)
    return root


@pytest.fixture(autouse=True)
def _reset_faults():
    yield
    faultinject.reset()


def _cfg(root, **kw):
    base = dict(backend="cpu", data_root=root, dataset="imagefolder", image_size=16,
                num_classes=2, workers=0, seed=0)
    base.update(kw)
    return Config(**base)


def test_parse_endpoints():
    assert parse_endpoints("a:1,b:22") == [("a", 1), ("b", 22)]
    for bad in ("", "a", "a:", ":7", "a:x"):
        with pytest.raises(ValueError):
            parse_endpoints(bad)


def test_offload_roundtrip_byte_identical(data_root):
    """The service's batches ARE the local batches: same stream key,
    same aug seeds, same decode — pixels and labels equal byte for
    byte, quarantine count carried."""
    srv = DecodeServer(_cfg(data_root, augment=True),
                       host="127.0.0.1", port=0)
    srv.serve_background()
    try:
        off = ImageFolderLoader(
            _cfg(data_root, augment=True,
                 decode_offload=f"127.0.0.1:{srv.port}"),
            0, 1, global_batch=8, split="train")
        loc = ImageFolderLoader(_cfg(data_root, augment=True), 0, 1,
                                global_batch=8, split="train")
        ob, lb = list(off.epoch(1)), list(loc.epoch(1))
        assert off.offload_fallbacks == 0
        assert len(ob) == len(lb) > 0
        for a, b in zip(ob, lb):
            np.testing.assert_array_equal(a.images, b.images)
            np.testing.assert_array_equal(a.labels, b.labels)
        off.close()
        loc.close()
    finally:
        srv.close()


def test_offload_fingerprint_mismatch_falls_back(data_root, capsys):
    """A decode host configured differently (here: another seed ⇒ a
    different augmentation stream) must be REFUSED at handshake — the
    run degrades to local decode instead of training on wrong pixels."""
    srv = DecodeServer(_cfg(data_root, augment=True, seed=9),
                       host="127.0.0.1", port=0)
    srv.serve_background()
    try:
        ld = ImageFolderLoader(
            _cfg(data_root, augment=True,
                 decode_offload=f"127.0.0.1:{srv.port}"),
            0, 1, global_batch=8, split="train")
        batches = list(ld.epoch(0))
        assert ld.offload_fallbacks == len(batches) > 0
        # Config-class refusal: the endpoint is DISABLED for the run
        # (re-probing a wrong dataset/seed can never heal and would
        # burn a decode + round-trip per backoff window forever).
        assert ld._offload._eps[0].down_until == float("inf")
        ld.close()
    finally:
        srv.close()
    out = capsys.readouterr().out
    assert "fingerprint mismatch" in out
    assert "DISABLED for this run" in out
    assert "falling back to local decode" in out


def test_offload_dead_endpoint_falls_back(data_root):
    """Nothing listening at all: every batch decodes locally, the
    epoch completes, and the fallback counter says how many."""
    ld = ImageFolderLoader(
        _cfg(data_root, decode_offload="127.0.0.1:1"),  # reserved port
        0, 1, global_batch=8, split="train")
    batches = list(ld.epoch(0))
    assert len(batches) == N_TRAIN // 8
    assert ld.offload_fallbacks >= 1  # backoff may skip later batches
    ld.close()


def test_offload_client_rejects_wrong_labels(data_root):
    """The per-batch label cross-check: a decode host whose dataset
    scan disagrees with the trainer's is dropped, not trusted."""
    srv = DecodeServer(_cfg(data_root), host="127.0.0.1", port=0)
    srv.serve_background()
    try:
        ld = ImageFolderLoader(_cfg(data_root), 0, 1, global_batch=8,
                               split="train")
        client = OffloadClient(f"127.0.0.1:{srv.port}",
                               fingerprint=ld.fingerprint())
        rows = np.arange(8, dtype=np.int64)
        good, q = client.decode(
            rows, 0, expect_labels=ld.labels[rows].astype(np.int32))
        assert good is not None
        wrong = 1 - ld.labels[rows].astype(np.int32)
        bad, _ = client.decode(rows, 0, expect_labels=wrong)
        assert bad is None  # endpoint dropped, caller goes local
        client.close()
        ld.close()
    finally:
        srv.close()


# ---------------------------------------------------------------------------
# Acceptance drills: a real engine run fed over localhost
# ---------------------------------------------------------------------------


def _spawn_server(data_root: str, die_after: int = 0,
                  timeout: float = 60.0) -> subprocess.Popen:
    env = dict(os.environ)
    for k in ("IMAGENT_FAULTS", "IMAGENT_SAMPLE_TRACE"):
        env.pop(k, None)  # the trainer's faults must NOT arm here
    env["PYTHONPATH"] = _REPO + os.pathsep + env.get("PYTHONPATH", "")
    cmd = [sys.executable, "-m", "imagent_tpu.data.serve",
           "--data-root", data_root, "--dataset", "imagefolder",
           "--image-size", "16", "--seed", "0", "--workers", "0",
           "--host", "127.0.0.1", "--port", "0"]
    if die_after:
        cmd += ["--die-after-requests", str(die_after)]
    p = subprocess.Popen(cmd, cwd=_REPO, env=env,
                         stdout=subprocess.PIPE,
                         stderr=subprocess.STDOUT, text=True, bufsize=1)
    deadline = time.time() + timeout
    while time.time() < deadline:
        line = p.stdout.readline()
        if "SERVE READY" in line:
            p.ready_port = int(line.split("port=")[1].split()[0])
            return p
        if p.poll() is not None:
            break
    p.kill()
    raise AssertionError("decode server never became ready")


def _engine_run(data_root, tmp_path, tag, **kw):
    from imagent_tpu.engine import run
    # lr deliberately tame: the images are synthesized noise, and a
    # diverging step would trip the non-finite guard's early epoch
    # abandon — this drill measures the INPUT pipeline, not numerics.
    cfg = Config(arch="resnet18", image_size=16, num_classes=2,
                 batch_size=2, epochs=1, lr=0.005, bf16=False,
                 dataset="imagefolder", data_root=data_root,
                 workers=0, log_every=0, seed=0, backend="cpu",
                 log_dir=str(tmp_path / f"tb_{tag}"),
                 ckpt_dir=str(tmp_path / f"ck_{tag}"), **kw)
    try:
        return run(cfg)
    finally:
        faultinject.reset()


def _epoch_counters(log_dir) -> dict:
    from imagent_tpu.telemetry.events import read_events
    recs = read_events(os.path.join(log_dir, "telemetry.jsonl"))
    epochs = [r for r in recs if r.get("event") == "epoch"]
    assert epochs, recs
    return epochs[-1]


# Slow enough that decode cannot hide under the CPU steps of this
# mesh even on a heavily loaded sandbox (steps run ~0.3-0.5s, worst
# observed ~1.4s; the fault models a genuinely CPU-starved decode
# host, so the margin matters more than the baseline run's wall).
SLOW = "decode.slow:times=999;secs=2.0"


def test_offload_beats_slow_local_decode(data_root, tmp_path):
    """THE acceptance drill: under an injected slow-decode fault on
    the TRAINING host, an epoch fed by a healthy localhost decode
    service finishes with input_wait well under the local-decode
    baseline's — the offload service genuinely rescues an input-bound
    host. The baseline's starvation must also trip the
    --input-wait-alert surface (WARN + event + status.json); the
    threshold is set WELL below the default so the e2e alert check
    does not depend on this sandbox's compile-time-dominated epoch
    wall (default-threshold semantics are pinned in
    test_telemetry.py).

    Environment-marginal on the 1-core sandbox: when compile time
    balloons the epoch wall, the starved fraction can graze the
    threshold. Margin widened (0.05 -> 0.02), and on a MEASURED-slow
    host (tests/marginal.py host probe) the drill deterministically
    pins the threshold down to 0.01 — the compile-dominated wall that
    dilutes the starved fraction is exactly the slow-host condition
    the probe detects, so the margin is granted by measurement rather
    than by losing the race first.  Still guarded by one loud
    fresh-scratch retry."""
    # Pinned per measured host speed, not per lost race: the starved
    # seconds are real either way; only the denominator (epoch wall)
    # balloons on a slow box.
    alert_thr = 0.01 if is_slow_host() else 0.02

    def attempt(i):
        base_tag, off_tag = f"base{i}", f"off{i}"
        tb = str(tmp_path / f"tb_{base_tag}")
        base = _engine_run(data_root, tmp_path, base_tag, faults=SLOW,
                           input_wait_alert=alert_thr)
        base_wait = base["final_train"]["host_blocked_s"]
        assert base_wait > 1.0, base  # the fault genuinely starves it

        # The baseline starved -> the alert surface must have fired.
        rec = _epoch_counters(tb)
        alert = rec.get("input_wait_alert")
        assert alert and alert["fraction"] > alert_thr, rec
        with open(os.path.join(tb, "status.json")) as f:
            status = json.load(f)
        assert status.get("input_wait_alert"), status
        from imagent_tpu.status import render
        assert "INPUT-BOUND" in render(tb)

        srv = _spawn_server(data_root)
        try:
            off = _engine_run(
                data_root, tmp_path, off_tag, faults=SLOW,
                decode_offload=f"127.0.0.1:{srv.ready_port}")
        finally:
            srv.kill()
        off_wait = off["final_train"]["host_blocked_s"]
        assert off_wait < base_wait * 0.5, (off_wait, base_wait)
        # Healthy service: no fallback ever decoded locally (the
        # fault would have fired there), and no alert on the
        # offloaded run.
        rec_off = _epoch_counters(str(tmp_path / f"tb_{off_tag}"))
        assert rec_off["counters"].get("offload_fallbacks", 0) == 0, \
            rec_off

        # Train/eval blocked-series split (the satellite regression):
        # the train series carries ONLY the step loop's wait; eval's
        # wait rides its own series + counter and never pollutes the
        # alert input.
        from benchmarks.render_curves import read_scalar
        train_pts = read_scalar(tb, "", "data/host_blocked_s")
        eval_pts = read_scalar(tb, "", "data/eval_blocked_s")
        assert len(train_pts) == len(eval_pts) == 1
        assert abs(train_pts[0][1] - base_wait) < 1e-3
        assert rec["counters"].get("eval_input_wait_s", 0.0) > 0.0
        assert abs(rec["phases"]["input_wait"] - base_wait) < 1e-3, (
            "eval wait leaked into the train input_wait phase")

    retry_marginal("offload input-wait-alert drill", attempt,
                   attempts=marginal_attempts())


def test_offload_service_death_degrades_to_local(data_root, tmp_path):
    """Service dies MID-EPOCH (after 3 decode requests): the client
    reconnect fails, the loader degrades to local decode, the run
    completes cleanly, and the fallbacks are counted in telemetry."""
    srv = _spawn_server(data_root, die_after=3)
    try:
        result = _engine_run(
            data_root, tmp_path, "death",
            decode_offload=f"127.0.0.1:{srv.ready_port}")
    finally:
        srv.kill()
    assert result["final_train"]["n"] == N_TRAIN
    rec = _epoch_counters(str(tmp_path / "tb_death"))
    assert rec["counters"].get("offload_fallbacks", 0) >= 1, rec
