"""End-to-end fault drills on the CPU backend: every recovery path the
resilience subsystem ships is driven by an injected fault
(resilience/faultinject.py) and must recover WITHOUT human
intervention — torn-checkpoint fallback restore, NaN-gradient skip +
rollback, watchdog checkpoint-and-exit, the in-process SIGTERM
preemption path, and the async-checkpoint commit drills (a slow commit
must not stall dispatch; a failed commit must fall back to the
previous generation, not hang). The synthetic dataset geometry
(128 imgs / global batch 32 on the 8 fake devices) gives exactly 4
steps/epoch, which the fault windows below count on."""

import signal
import time

import pytest

import jax

from imagent_tpu import checkpoint as ckpt_lib
from imagent_tpu.config import Config
from imagent_tpu.engine import run
from imagent_tpu.resilience import faultinject


@pytest.fixture(autouse=True)
def _disarm_faults():
    yield
    faultinject.reset()


def _cfg(tmp_path, **kw):
    base = dict(backend="cpu", arch="resnet18", image_size=16, num_classes=4, batch_size=4,
                epochs=2, lr=0.05, dataset="synthetic", synthetic_size=128,
                workers=0, bf16=False, log_every=0, seed=0, save_model=True,
                log_dir=str(tmp_path / "tb"), ckpt_dir=str(tmp_path / "ck"))
    base.update(kw)
    return Config(**base)


def test_nan_grad_rollback_drill(tmp_path, capsys):
    """Epoch 0 trains clean and checkpoints; every step of epoch 1 is
    NaN-poisoned (calls 5-8 of the nan-grads point). The in-graph guard
    skips each bad update; after --max-bad-steps consecutive skips the
    engine rolls back to the epoch-0 checkpoint and replays epoch 1 —
    by then the fault window has passed, so the run completes clean."""
    result = run(_cfg(tmp_path, faults="nan-grads:after=4;times=4",
                      max_bad_steps=2))
    assert result["rollbacks"] == 1
    assert result["preempted"] is False
    assert result["best_epoch"] >= 0
    out = capsys.readouterr().out
    assert "non-finite step skipped" in out
    assert "ROLLBACK 1/" in out


def test_nan_grads_without_checkpoint_warns_and_continues(tmp_path,
                                                          capsys):
    """No checkpoint to roll back to: the in-graph skip means the live
    state is unpoisoned, so the run must warn and press on (bounded by
    the rollback budget) rather than kill an intact run because
    --save-model is off."""
    result = run(_cfg(tmp_path, save_model=False, epochs=2,
                      faults="nan-grads:times=5", max_bad_steps=2))
    assert result["rollbacks"] == 1
    assert result["preempted"] is False
    out = capsys.readouterr().out
    assert "no checkpoint to roll back to" in out
    assert "abandoning the rest of this epoch" in out


def test_persistent_nan_without_checkpoint_gives_up(tmp_path):
    """...but a fault that trips the guard epoch after epoch still ends
    the run with diagnosis instead of spinning forever."""
    with pytest.raises(RuntimeError, match="persisted through"):
        run(_cfg(tmp_path, save_model=False, epochs=50,
                 faults="nan-grads:times=1000", max_bad_steps=2))


def test_torn_checkpoint_fault_falls_back_to_previous(tmp_path):
    """Checkpoint-level drill: the torn-checkpoint fault point truncates
    the SECOND commit mid-write; the fallback chain must land on the
    previous good LAST (keep-last-k rotation), not fail the restore."""
    from imagent_tpu.cluster import make_mesh
    from imagent_tpu.models import create_model
    from imagent_tpu.train import (
        create_train_state, make_optimizer, replicate_state,
    )

    mesh = make_mesh(model_parallel=1)
    state = replicate_state(
        create_train_state(create_model("resnet18", num_classes=4),
                           jax.random.key(0), 16, make_optimizer()), mesh)
    d = str(tmp_path)
    ckpt_lib.save(d, "last", state, {"epoch": 0}, keep_last_k=2)
    faultinject.configure("torn-checkpoint")
    ckpt_lib.save(d, "last", state, {"epoch": 1}, keep_last_k=2)
    faultinject.reset()

    restored = ckpt_lib.restore_resilient(d, state)
    assert restored is not None
    _, meta, src = restored
    assert src == "last.1" and meta["epoch"] == 0


def test_corrupt_resume_falls_back_through_engine(tmp_path, capsys):
    """Engine-level drill: bit-rot on the live LAST after a clean run;
    --resume must verify, warn, fall back to the rotated previous LAST,
    and finish the remaining epochs without intervention."""
    run(_cfg(tmp_path, epochs=2, keep_last_k=2))
    # Corrupt the live LAST's largest file (same shape a torn write or
    # bit-rot leaves; the manifest catches it on restore).
    root = tmp_path / "ck" / "last"
    victim = max((p for p in root.rglob("*") if p.is_file()),
                 key=lambda p: p.stat().st_size)
    victim.write_bytes(victim.read_bytes()[:victim.stat().st_size // 2])

    result = run(_cfg(tmp_path, epochs=3, resume=True, keep_last_k=2))
    out = capsys.readouterr().out
    assert "failed integrity verification" in out
    assert "fallback checkpoint last.1" in out
    assert result["preempted"] is False and result["best_epoch"] >= 0


def test_watchdog_drill_checkpoint_and_exit(tmp_path, capsys):
    """A stalled step (hung-collective stand-in) past the watchdog
    deadline dumps all-thread stacks and rides the preemption path:
    checkpoint LAST, exit cleanly, resumable."""
    result = run(_cfg(tmp_path, watchdog_secs=2.0,
                      faults="stall-step:after=2;secs=6"))
    assert result["preempted"] is True
    assert (tmp_path / "ck" / "last").is_dir()
    captured = capsys.readouterr()
    assert "WATCHDOG" in captured.err
    assert "all-thread stack dump" in captured.err
    assert "preemption signal" in captured.out

    faultinject.reset()  # drop the drill for the requeue
    resumed = run(_cfg(tmp_path, resume=True))
    assert resumed["preempted"] is False and resumed["best_epoch"] >= 0


def test_sigterm_fault_preempts_cleanly(tmp_path):
    """The sigterm fault point delivers a real SIGTERM mid-epoch; the
    chained PreemptionGuard checkpoints and exits cleanly — the Slurm
    pre-kill path without an external killer."""
    prior = signal.getsignal(signal.SIGTERM)
    result = run(_cfg(tmp_path, faults="sigterm:after=2"))
    assert result["preempted"] is True
    import json
    meta = json.loads((tmp_path / "ck" / "last_meta.json").read_text())
    assert meta["resume_step"] > 0
    # Guard uninstalled: the pre-run handler is back.
    assert signal.getsignal(signal.SIGTERM) is prior

    # Disarm before resuming: configure() exports the spec to the env
    # (for spawned decode workers), so without this the resumed run
    # re-arms the drill — as a real requeue re-running the same
    # --faults flags would.
    faultinject.reset()
    resumed = run(_cfg(tmp_path, resume=True))
    assert resumed["preempted"] is False


def test_slow_commit_keeps_dispatching(tmp_path):
    """Async-checkpoint overlap drill: epoch 0's LAST commit sleeps
    2.5s on the committer thread; the step loop must keep dispatching
    — epoch 1's steps land INSIDE the commit's wall-clock window — and
    the run completes with the commit landed durably (marker gone,
    resume restores the final epoch)."""
    dispatch_times = []

    def record_dispatches():
        dispatch_times.append(time.time())
        return False

    t_run = time.time()
    result = run(_cfg(tmp_path, epochs=2,
                      faults="ckpt.slow_commit:secs=2.5"),
                 stop_check=record_dispatches)
    assert result["preempted"] is False and result["rollbacks"] == 0
    # Epoch 0's commit is the slowed one (times=1); epoch 1's final
    # commit lands at run end — pick the injected window out of the
    # history by its length. The window log is module-global, so scope
    # the search to THIS run: other tests in the same process may have
    # left their own slow windows behind.
    slow = [w for w in ckpt_lib.commit_windows()
            if w["ok"] and w["start"] >= t_run
            and w["end"] - w["start"] >= 2.5]
    assert slow, ckpt_lib.commit_windows()
    win = slow[0]
    overlapped = [t for t in dispatch_times
                  if win["start"] < t < win["end"]]
    assert overlapped, (win, dispatch_times)
    # Landed durably: marker cleared, final generation's meta on disk
    # (resume-after-async is exercised by test_e2e_async_ckpt_durability).
    assert not (tmp_path / "ck" / "last.pending.json").exists()
    import json
    meta = json.loads((tmp_path / "ck" / "last_meta.json").read_text())
    assert meta["epoch"] == 1


def test_commit_fail_falls_back_to_previous_generation(tmp_path,
                                                       capsys):
    """A failed async commit (injected at the committer thread, before
    any rename) must be pod-agreed at the next landing point and leave
    the PREVIOUS generation as the last good checkpoint — the run
    keeps training (no hang, no crash) and the next epoch's save
    succeeds, so --resume lands on a consistent generation."""
    result = run(_cfg(tmp_path, epochs=2, keep_last_k=1,
                      faults="ckpt.commit_fail"))
    assert result["preempted"] is False and result["rollbacks"] == 0
    assert result["ckpt_commit_failures"] == 1  # epoch 0's, pod-agreed
    out = capsys.readouterr().out
    assert "async checkpoint commit FAILED" in out
    # Epoch 0's commit failed before any rename; epoch 1's succeeded —
    # the durable generation is epoch 1, cleanly committed (no marker,
    # no staging debris).
    import json
    meta = json.loads((tmp_path / "ck" / "last_meta.json").read_text())
    assert meta["epoch"] == 1
    assert not (tmp_path / "ck" / "last.pending.json").exists()
    assert not (tmp_path / "ck" / "last.staging").exists()


def test_divergence_drill_health_rollback_beats_guard(tmp_path,
                                                      capsys):
    """THE divergence drill (make drill-divergence): epoch 0 trains
    clean and checkpoints; epoch 1's second step gets its lr scaled
    x64 (step.grad_spike) — every step stays FINITE, so the non-finite
    guard is blind, but the update-ratio spikes ~64x its EWMA baseline
    and the early-warning detector must catch it on the lagged
    frontier, emit a health_anomaly telemetry event, and (with
    --health-rollback) restore the last good checkpoint BEFORE the
    guard could ever fire. The replay (fault expired) completes
    clean."""
    import json

    result = run(_cfg(tmp_path, faults="step.grad_spike:after=5",
                      health_rollback=True, health_warmup_steps=3,
                      max_bad_steps=2))
    assert result["rollbacks"] == 1
    assert result["preempted"] is False
    assert result["best_epoch"] >= 0
    out = capsys.readouterr().out
    assert "FAULT step.grad_spike" in out
    assert "HEALTH: update_spike anomaly" in out
    assert "rolling back to the last good checkpoint" in out
    assert "ROLLBACK 1/" in out
    # The whole point: the divergence was caught while every step was
    # still finite — the guard never saw anything.
    assert "non-finite step skipped" not in out
    # The verdict is durable in the event log, before the rollback.
    from imagent_tpu.telemetry.events import read_events
    events = read_events(str(tmp_path / "tb" / "telemetry.jsonl"))
    anomalies = [e for e in events if e["event"] == "health_anomaly"]
    assert anomalies and anomalies[0]["kind"] == "update_spike"
    assert anomalies[0]["baseline"] > 0
    assert anomalies[0]["value"] > 10 * anomalies[0]["baseline"]
    # The post-rollback checkpoint meta carries the re-warmed EWMAs a
    # --resume would re-seed the detector from.
    meta = json.loads((tmp_path / "ck" / "last_meta.json").read_text())
    assert meta["health_ewma_n"] > 0
    assert meta["health_grad_ewma"] > 0


def test_divergence_warn_only_without_health_rollback(tmp_path,
                                                      capsys):
    """Default policy: the same spike only warns (anomaly event +
    stdout) — no rollback, the run completes."""
    result = run(_cfg(tmp_path, faults="step.grad_spike:after=5",
                      health_warmup_steps=3, max_bad_steps=2))
    assert result["rollbacks"] == 0
    out = capsys.readouterr().out
    assert "HEALTH: update_spike anomaly" in out
    assert "warn only; --health-rollback to act" in out


def test_divergence_without_checkpoint_warns_honestly(tmp_path,
                                                      capsys):
    """Health trip with nothing to roll back to: unlike guard-skipped
    steps the diverging updates WERE applied, so the fallback must say
    so (not claim 'state unpoisoned') and continue bounded by the
    rollback budget."""
    result = run(_cfg(tmp_path, save_model=False,
                      faults="step.grad_spike:after=5",
                      health_rollback=True, health_warmup_steps=3,
                      max_bad_steps=2))
    assert result["rollbacks"] >= 1
    out = capsys.readouterr().out
    assert "health anomaly tripped rollback" in out
    assert "diverging updates WERE applied" in out
    assert "State is unpoisoned" not in out


def test_rollback_give_up_flushes_flight_recorder(tmp_path):
    """Every drilled fatal exit path must land a parseable flight
    recorder whose ring shows the death's approach — here the
    rollback-give-up (79) path: the last records are the NaN-poisoned
    (bad) steps the guard kept skipping."""
    from imagent_tpu.resilience import exitcodes
    from imagent_tpu.telemetry.flightrec import read_flightrec

    with pytest.raises(RuntimeError, match="persisted through"):
        run(_cfg(tmp_path, save_model=False, epochs=50,
                 faults="nan-grads:times=1000", max_bad_steps=2))
    rec = read_flightrec(str(tmp_path / "tb" / "flightrec.0.json"))
    assert rec is not None
    assert rec["reason"] == "rollback-give-up"
    assert rec["exit_code"] == exitcodes.ROLLBACK_GIVE_UP
    assert rec["records"], "the ring must hold the final steps"
    # Strict-JSON contract: the poisoned steps' NaN norms are nulled
    # (json.dumps would otherwise emit bare NaN tokens).
    bad = [r for r in rec["records"] if r["bad"]]
    assert bad and all(r["grad_norm"] is None for r in bad)
    assert "NaN" not in (tmp_path / "tb"
                         / "flightrec.0.json").read_text()
    assert rec["context"]["arch"] == "resnet18"


def test_guard_counts_bad_steps_in_epoch_metrics(tmp_path):
    """A single transient NaN step (below --max-bad-steps) is skipped
    and surfaced in the epoch metrics, with no rollback."""
    result = run(_cfg(tmp_path, epochs=1, faults="nan-grads:after=1",
                      max_bad_steps=3))
    assert result["rollbacks"] == 0
    assert result["final_train"]["bad_steps"] == 1
    # 4 steps/epoch, one skipped: the other 3 still count samples.
    assert result["final_train"]["n"] == 3 * 32
