"""Partial-pod failure: heartbeat mesh, deadman, exit-code taxonomy,
tombstone semantics, storage-outage drills, and the 2-process
acceptance drill (``mp_worker_deadman.py``).

The contract under test (docs/OPERATIONS.md "Partial-pod failure and
requeue"): one dead host must degrade the pod OUT-OF-BAND — detected
from heartbeat staleness or a tombstone, never by timing out inside a
collective — and every survivor must land what it can land without
collectives (process 0's flat emergency snapshot), classify itself
(tombstone + telemetry ``pod_degraded``), and exit with a retryable
code the launcher's requeue wrapper restarts onto ``--resume``.
"""

import json
import os
import re
import subprocess
import sys
import time

import pytest

from imagent_tpu.resilience import exitcodes, faultinject, heartbeat
from imagent_tpu.resilience.deadman import DeadmanMonitor, PodHeartbeat

_DIR = os.path.dirname(os.path.abspath(__file__))
_REPO = os.path.dirname(_DIR)


@pytest.fixture(autouse=True)
def _disarm_faults():
    yield
    faultinject.reset()


# ---------------------------------------------------------------------------
# Exit-code taxonomy
# ---------------------------------------------------------------------------


def test_exitcode_registry_is_consistent():
    codes = [e.code for e in exitcodes.REGISTRY]
    names = [e.name for e in exitcodes.REGISTRY]
    assert len(set(codes)) == len(codes), "duplicate exit codes"
    assert len(set(names)) == len(names), "duplicate exit names"
    # The historic watchdog code stays stable and retryable.
    assert exitcodes.WATCHDOG_HARD_EXIT == 86
    assert exitcodes.is_retryable(86)
    for code in (exitcodes.PREEMPTED, exitcodes.PEER_DEAD,
                 exitcodes.STORAGE_OUTAGE):
        assert exitcodes.is_retryable(code), code
    for code in (exitcodes.OK, exitcodes.FATAL_CONFIG,
                 exitcodes.ROLLBACK_GIVE_UP, exitcodes.FATAL_EXCEPTION):
        assert not exitcodes.is_retryable(code), code
    # Unregistered codes (OOM 137, shell 127) never auto-requeue.
    assert not exitcodes.is_retryable(137)
    assert exitcodes.describe(87).name == "peer-dead"
    assert exitcodes.by_name("storage-outage").code == 88


def test_fatal_errors_carry_their_codes():
    for exc, code, reason in (
            (exitcodes.PeerDeathError("x"), exitcodes.PEER_DEAD,
             "peer-dead"),
            (exitcodes.StorageOutageError("x"),
             exitcodes.STORAGE_OUTAGE, "storage-outage"),
            (exitcodes.RollbackGiveUpError("x"),
             exitcodes.ROLLBACK_GIVE_UP, "rollback-give-up")):
        assert isinstance(exc, exitcodes.FatalRunError)
        assert isinstance(exc, RuntimeError)  # legacy except-clauses
        assert exc.exit_code == code and exc.reason == reason


# ---------------------------------------------------------------------------
# Heartbeat writer
# ---------------------------------------------------------------------------


def test_heartbeat_writer_roundtrip(tmp_path):
    w = heartbeat.HeartbeatWriter(str(tmp_path), rank=0,
                                  interval_secs=0.05)
    w.start()
    try:
        w.note(epoch=2, step=17, phase="train")
        deadline = time.time() + 5.0
        rec = None
        while time.time() < deadline:
            rec = heartbeat.read_record(
                heartbeat.heartbeat_path(str(tmp_path), 0))
            if rec and rec["step"] == 17 and rec["seq"] >= 2:
                break
            time.sleep(0.02)
        assert rec is not None
        assert rec["rank"] == 0 and rec["pid"] == os.getpid()
        assert rec["epoch"] == 2 and rec["step"] == 17
        assert rec["phase"] == "train" and rec["seq"] >= 2
        seq_then = rec["seq"]
        time.sleep(0.2)
        rec2 = heartbeat.read_record(
            heartbeat.heartbeat_path(str(tmp_path), 0))
        assert rec2["seq"] > seq_then, "seq must keep advancing"
    finally:
        w.stop()
    final = heartbeat.read_record(
        heartbeat.heartbeat_path(str(tmp_path), 0))
    assert final["phase"] == heartbeat.PHASE_DONE


def test_heartbeat_writer_clears_own_stale_files(tmp_path):
    """A requeued attempt must not trip peers on last attempt's
    leftovers: rank 0's writer deletes rank 0's old heartbeat AND
    tombstone before the first fresh beat."""
    hb_dir = str(tmp_path)
    os.makedirs(hb_dir, exist_ok=True)
    stale_ts = heartbeat.tombstone_path(hb_dir, 0)
    with open(stale_ts, "w") as f:
        json.dump({"rank": 0, "reason": "peer-dead", "t": 1.0}, f)
    w = heartbeat.HeartbeatWriter(hb_dir, rank=0, interval_secs=5.0)
    w.start()
    try:
        assert not os.path.exists(stale_ts)
        assert heartbeat.read_record(
            heartbeat.heartbeat_path(hb_dir, 0)) is not None
    finally:
        w.stop()


def test_tombstone_written_once_first_cause_wins(tmp_path):
    w = heartbeat.HeartbeatWriter(str(tmp_path), rank=0)
    os.makedirs(str(tmp_path), exist_ok=True)
    assert w.tombstone("storage-outage", exitcodes.STORAGE_OUTAGE,
                       retryable=True, detail="first")
    assert not w.tombstone("exception", exitcodes.FATAL_EXCEPTION,
                           retryable=False, detail="echo")
    rec = heartbeat.read_record(
        heartbeat.tombstone_path(str(tmp_path), 0))
    assert rec["reason"] == "storage-outage" and rec["retryable"]
    assert rec["exit_code"] == exitcodes.STORAGE_OUTAGE


def test_hb_stale_fault_freezes_writer_but_not_process(tmp_path):
    """``hb.stale``: the heartbeat writer freezes while the thread (and
    process) live on — the unobservable-host false-positive drill."""
    faultinject.configure("hb.stale:after=2")
    w = heartbeat.HeartbeatWriter(str(tmp_path), rank=0,
                                  interval_secs=0.05)
    w.start()
    try:
        time.sleep(0.8)
        rec = heartbeat.read_record(
            heartbeat.heartbeat_path(str(tmp_path), 0))
        assert rec is not None and rec["seq"] <= 2, rec
        seq_frozen = rec["seq"]
        time.sleep(0.3)
        rec2 = heartbeat.read_record(
            heartbeat.heartbeat_path(str(tmp_path), 0))
        assert rec2["seq"] == seq_frozen, "writer must stay frozen"
        assert w._thread.is_alive(), "the process-side thread lives on"
    finally:
        w.stop()


# ---------------------------------------------------------------------------
# Deadman monitor
# ---------------------------------------------------------------------------


def _beat(hb_dir, rank, seq, phase="train", t=None):
    heartbeat._write_atomic(
        heartbeat.heartbeat_path(hb_dir, rank),
        {"rank": rank, "pid": 4242, "seq": seq,
         "t": time.time() if t is None else t,
         "epoch": 0, "step": seq, "phase": phase})


def test_deadman_trips_on_stale_heartbeat(tmp_path):
    hb_dir = str(tmp_path)
    os.makedirs(hb_dir, exist_ok=True)
    exits = []
    m = DeadmanMonitor(hb_dir, rank=0, world=2, deadline_secs=0.4,
                       escalate_secs=60.0, _exit=exits.append)
    m.start()
    try:
        # Fresh beats: no trip while the peer keeps changing.
        for seq in range(4):
            _beat(hb_dir, 1, seq)
            time.sleep(0.15)
        assert not m.degraded
        m.raise_if_degraded()  # no-op while healthy
        # Freeze the peer: staleness crosses the deadline.
        deadline = time.time() + 5.0
        while not m.degraded and time.time() < deadline:
            time.sleep(0.05)
        assert m.degraded
        v = m.verdict
        assert v["peer"] == 1 and v["reason"] == "stale"
        assert v["stale_for_s"] >= 0.4 and v["deadline_s"] == 0.4
        with pytest.raises(exitcodes.PeerDeathError) as ei:
            m.raise_if_degraded(state="STATE", epoch=3, resume_step=7)
        assert ei.value.salvage == {"state": "STATE", "epoch": 3,
                                    "resume_step": 7}
        assert ei.value.verdict["peer"] == 1
        assert not exits, "ack via raise must defer escalation"
    finally:
        m.stop()


def test_deadman_classifies_fresh_tombstone(tmp_path):
    """A peer that died deliberately is classified from its tombstone
    instantly — no staleness wait — with the reason passed through."""
    hb_dir = str(tmp_path)
    os.makedirs(hb_dir, exist_ok=True)
    m = DeadmanMonitor(hb_dir, rank=0, world=2, deadline_secs=5.0,
                       escalate_secs=60.0, _exit=lambda c: None)
    _beat(hb_dir, 1, 0)
    heartbeat._write_atomic(
        heartbeat.tombstone_path(hb_dir, 1),
        {"rank": 1, "reason": "rollback-give-up",
         "exit_code": exitcodes.ROLLBACK_GIVE_UP, "retryable": False,
         "detail": "", "t": time.time()})
    m.start()
    try:
        deadline = time.time() + 5.0
        while not m.degraded and time.time() < deadline:
            time.sleep(0.05)
        v = m.verdict
        assert v is not None and v["reason"] == "tombstone"
        assert v["tombstone"]["reason"] == "rollback-give-up"
        assert v["tombstone"]["retryable"] is False
    finally:
        m.stop()


def test_deadman_ignores_stale_tombstone_and_done_peers(tmp_path):
    """Requeue hygiene: last attempt's tombstone (old timestamp) and a
    cleanly-departed peer (phase=done, then silence) never trip."""
    hb_dir = str(tmp_path)
    os.makedirs(hb_dir, exist_ok=True)
    heartbeat._write_atomic(
        heartbeat.tombstone_path(hb_dir, 1),
        {"rank": 1, "reason": "peer-dead", "exit_code": 87,
         "retryable": True, "detail": "", "t": time.time() - 3600})
    _beat(hb_dir, 1, 0, phase=heartbeat.PHASE_DONE)
    m = DeadmanMonitor(hb_dir, rank=0, world=2, deadline_secs=0.2,
                       escalate_secs=60.0, _exit=lambda c: None)
    m.start()
    try:
        time.sleep(1.0)  # several deadlines of silence
        assert not m.degraded, m.verdict
    finally:
        m.stop()


def test_deadman_escalates_when_main_thread_never_acks(tmp_path):
    """The hard-exit backstop: a verdict nobody acknowledges (main
    thread wedged inside a dead collective) hard-exits retryable with
    this host's own peer-dead tombstone — shared machinery with the
    watchdog's escalation."""
    hb_dir = str(tmp_path)
    os.makedirs(hb_dir, exist_ok=True)
    exits = []
    stones = []
    m = DeadmanMonitor(hb_dir, rank=0, world=2, deadline_secs=0.2,
                       escalate_secs=0.3,
                       tombstone_cb=stones.append,
                       _exit=exits.append)
    _beat(hb_dir, 1, 0)
    m.start()
    try:
        deadline = time.time() + 5.0
        while not exits and time.time() < deadline:
            time.sleep(0.05)
        assert exits == [exitcodes.PEER_DEAD]
        assert stones == [exitcodes.PEER_DEAD], \
            "escalation must leave a classified tombstone"
    finally:
        m.stop()


def test_deadman_adopts_non_retryable_peer_verdict(tmp_path):
    """A tombstone classifying a NON-retryable death (the peer's fault
    reproduces on every requeue) is adopted pod-wide: the survivor's
    PeerDeathError carries the peer's code, so its own exit — and its
    own tombstone — stop the requeue wrapper instead of burning the
    restart budget on a rendezvous the dead peer can never rejoin."""
    hb_dir = str(tmp_path)
    os.makedirs(hb_dir, exist_ok=True)
    m = DeadmanMonitor(hb_dir, rank=0, world=2, deadline_secs=5.0,
                       escalate_secs=60.0, _exit=lambda c: None)
    heartbeat._write_atomic(
        heartbeat.tombstone_path(hb_dir, 1),
        {"rank": 1, "reason": "rollback-give-up",
         "exit_code": exitcodes.ROLLBACK_GIVE_UP, "retryable": False,
         "detail": "", "t": time.time()})
    m.start()
    try:
        deadline = time.time() + 5.0
        while not m.degraded and time.time() < deadline:
            time.sleep(0.05)
        assert m.degraded
        assert m.exit_code_for_verdict() == exitcodes.ROLLBACK_GIVE_UP
        with pytest.raises(exitcodes.PeerDeathError) as ei:
            m.raise_if_degraded()
        assert ei.value.exit_code == exitcodes.ROLLBACK_GIVE_UP
        assert not exitcodes.is_retryable(ei.value.exit_code)
        assert "adopting its verdict" in str(ei.value)
    finally:
        m.stop()


def test_deadman_warns_when_no_peer_ever_observed(tmp_path):
    """Non-shared heartbeat storage (per-VM local --log-dir on a real
    pod) makes every peer unobservable — the deadman must say so
    instead of being silently inert."""
    import io
    out = io.StringIO()
    m = DeadmanMonitor(str(tmp_path), rank=0, world=2,
                       deadline_secs=0.2, escalate_secs=60.0,
                       out=out, _exit=lambda c: None)
    m._t0_mono -= 120.0  # pretend the grace window already elapsed
    m.start()
    try:
        deadline = time.time() + 5.0
        while ("observed NO peer heartbeat" not in out.getvalue()
               and time.time() < deadline):
            time.sleep(0.05)
        assert "observed NO peer heartbeat" in out.getvalue()
        assert not m.degraded  # a warning, never a false verdict
    finally:
        m.stop()


def test_pod_heartbeat_facade_staleness_gauge(tmp_path):
    pod = PodHeartbeat(str(tmp_path), rank=0, world=2,
                       deadline_secs=2.0, interval_secs=0.1,
                       _exit=lambda c: None)
    pod.start()
    try:
        _beat(heartbeat.heartbeat_dir(str(tmp_path)), 1, 0)
        time.sleep(1.0)  # > the monitor's 0.25s poll, < the deadline
        assert pod.max_peer_staleness() >= 0.4
        assert not pod.degraded
    finally:
        pod.stop()


# ---------------------------------------------------------------------------
# Engine-level tombstone semantics (every fatal exit path classifies)
# ---------------------------------------------------------------------------


def _cfg(tmp_path, **kw):
    from imagent_tpu.config import Config
    base = dict(backend="cpu", arch="resnet18", image_size=16, num_classes=4,
                batch_size=4, epochs=2, lr=0.05, dataset="synthetic",
                synthetic_size=128, workers=0, bf16=False, log_every=0,
                seed=0, save_model=True, peer_deadline_secs=1.0,
                heartbeat_secs=0.25,
                log_dir=str(tmp_path / "tb"),
                ckpt_dir=str(tmp_path / "ck"))
    base.update(kw)
    return Config(**base)


def _read_tombstone(tmp_path, rank=0):
    return heartbeat.read_record(heartbeat.tombstone_path(
        heartbeat.heartbeat_dir(str(tmp_path / "tb")), rank))


def test_tombstone_on_rollback_give_up(tmp_path):
    from imagent_tpu.engine import run
    with pytest.raises(exitcodes.RollbackGiveUpError,
                       match="persisted through"):
        run(_cfg(tmp_path, save_model=False, epochs=50,
                 faults="nan-grads:times=1000", max_bad_steps=2))
    rec = _read_tombstone(tmp_path)
    assert rec is not None and rec["reason"] == "rollback-give-up"
    assert rec["exit_code"] == exitcodes.ROLLBACK_GIVE_UP
    assert rec["retryable"] is False
    # The flight recorder landed next to the tombstone that names it.
    from imagent_tpu.telemetry.flightrec import read_flightrec
    fr = read_flightrec(str(tmp_path / "tb" / "flightrec.0.json"))
    assert fr is not None and fr["reason"] == "rollback-give-up"
    assert fr["records"]
    assert "flightrec=flightrec.0.json" in rec["detail"]
    # ...and a peer's monitor classifies it verbatim.
    m = DeadmanMonitor(heartbeat.heartbeat_dir(str(tmp_path / "tb")),
                       rank=1, world=2, deadline_secs=60.0,
                       escalate_secs=600.0, _exit=lambda c: None)
    m._peers[0]["alive"] = True  # the peer was seen alive this run
    m._scan()
    assert m.degraded and m.verdict["reason"] == "tombstone"
    assert m.verdict["tombstone"]["reason"] == "rollback-give-up"


def test_tombstone_on_watchdog_clean_exit(tmp_path):
    from imagent_tpu.engine import run
    result = run(_cfg(tmp_path, watchdog_secs=2.0,
                      faults="stall-step:after=2;secs=6"))
    assert result["preempted"] is True
    rec = _read_tombstone(tmp_path)
    assert rec is not None and rec["reason"] == "watchdog-stall"
    assert rec["retryable"] is True
    assert rec["exit_code"] == exitcodes.PREEMPTED


def test_tombstone_on_sigterm_preemption(tmp_path):
    from imagent_tpu.engine import run
    result = run(_cfg(tmp_path, faults="sigterm:after=2"))
    assert result["preempted"] is True
    rec = _read_tombstone(tmp_path)
    assert rec is not None and rec["reason"] == "preempted"
    assert rec["retryable"] is True


def test_tombstone_on_unhandled_exception(tmp_path):
    from imagent_tpu.engine import run

    def boom():
        raise RuntimeError("synthetic operator error")

    with pytest.raises(RuntimeError, match="synthetic operator error"):
        run(_cfg(tmp_path), stop_check=boom)
    rec = _read_tombstone(tmp_path)
    assert rec is not None and rec["reason"] == "exception"
    assert rec["retryable"] is False
    assert "synthetic operator error" in rec["detail"]
    from imagent_tpu.telemetry.flightrec import read_flightrec
    fr = read_flightrec(str(tmp_path / "tb" / "flightrec.0.json"))
    assert fr is not None and fr["reason"] == "exception"
    assert fr["exit_code"] == exitcodes.FATAL_EXCEPTION
    assert "flightrec=flightrec.0.json" in rec["detail"]


def test_clean_finish_leaves_done_beat_and_no_tombstone(tmp_path):
    from imagent_tpu.engine import run
    result = run(_cfg(tmp_path, epochs=1))
    assert result["preempted"] is False
    assert _read_tombstone(tmp_path) is None
    hb = heartbeat.read_record(heartbeat.heartbeat_path(
        heartbeat.heartbeat_dir(str(tmp_path / "tb")), 0))
    assert hb["phase"] == heartbeat.PHASE_DONE


def test_peer_deadline_validation(tmp_path):
    from imagent_tpu.engine import run
    with pytest.raises(ValueError, match="peer-deadline-secs"):
        run(_cfg(tmp_path, peer_deadline_secs=0.3, heartbeat_secs=0.25))
    with pytest.raises(ValueError, match="heartbeat-secs"):
        run(_cfg(tmp_path, peer_deadline_secs=1.0, heartbeat_secs=0.0))


# ---------------------------------------------------------------------------
# Storage-outage drills
# ---------------------------------------------------------------------------


def test_storage_outage_commit_fail_streak_exits_retryable(tmp_path):
    """Epoch 0's LAST commit lands; every later commit fails at the
    committer (pre-rename, so the landed generation is untouched).
    After _MAX_CKPT_FAIL_STREAK consecutive failures the run exits
    retryable with the storage-outage code — instead of silently
    training past the last resumable point forever."""
    from imagent_tpu.engine import run
    with pytest.raises(exitcodes.StorageOutageError,
                       match="consecutive async checkpoint commits"):
        run(_cfg(tmp_path, epochs=8, keep_last_k=1,
                 faults="ckpt.commit_fail:after=1;times=50"))
    # The previous (epoch 0) generation is intact and restorable.
    meta = json.loads((tmp_path / "ck" / "last_meta.json").read_text())
    assert meta["epoch"] == 0
    assert (tmp_path / "ck" / "last" / "snapshot.json").is_file()
    assert not (tmp_path / "ck" / "last.pending.json").exists()
    assert not (tmp_path / "ck" / "last.staging").exists()
    rec = _read_tombstone(tmp_path)
    assert rec is not None and rec["reason"] == "storage-outage"
    assert rec["retryable"] is True
    assert exitcodes.is_retryable(rec["exit_code"])
    # Storage for the LOG dir is distinct from the (dead) checkpoint
    # dir in this drill, so the forensic record still lands.
    from imagent_tpu.telemetry.flightrec import read_flightrec
    fr = read_flightrec(str(tmp_path / "tb" / "flightrec.0.json"))
    assert fr is not None and fr["reason"] == "storage-outage"
    assert fr["exit_code"] == exitcodes.STORAGE_OUTAGE


def test_storage_outage_unwritable_staging_retries_then_exits(
        tmp_path, capsys):
    """The real-filesystem variant: after epoch 0 commits, the staging
    path is clobbered with a plain FILE, so every snapshot write fails
    with a real OSError (works even when tests run as root, where a
    chmod-based "unwritable" is a no-op). Each commit attempt must run
    its bounded backoff retries, fail the VERDICT without crashing the
    run or touching the live generation, and the streak must end in
    the clean retryable storage-outage exit — never a crash loop or a
    torn candidate."""
    from imagent_tpu.engine import run
    ck = tmp_path / "ck"
    sabotaged = []

    def sabotage():
        if (not sabotaged and (ck / "last_meta.json").exists()
                and not (ck / "last.pending.json").exists()):
            # The committer's rmtree(ignore_errors) cannot remove a
            # plain file, so os.makedirs keeps failing — a persistent
            # storage fault at exactly the write the retries wrap.
            (ck / "last.staging").write_text("not a directory")
            sabotaged.append(True)
        return False

    with pytest.raises(exitcodes.StorageOutageError,
                       match="consecutive async checkpoint commits"):
        run(_cfg(tmp_path, epochs=10, keep_last_k=1),
            stop_check=sabotage)
    assert sabotaged, "the drill never armed"
    out = capsys.readouterr().out
    assert "retry" in out, "bounded backoff retries must be visible"
    assert "async checkpoint commit FAILED" in out
    # The epoch-0 generation survived every failed attempt untouched.
    meta = json.loads((tmp_path / "ck" / "last_meta.json").read_text())
    assert meta["epoch"] == 0
    assert (tmp_path / "ck" / "last" / "snapshot.json").is_file()
    # The streak verdict can land while the final doomed commit is
    # still retrying on its daemon thread; it cleans its own marker
    # when the retries exhaust (and a dangling marker whose generation
    # mismatches the live meta is restore-benign regardless).
    deadline = time.time() + 15.0
    while ((tmp_path / "ck" / "last.pending.json").exists()
           and time.time() < deadline):
        time.sleep(0.2)
    assert not (tmp_path / "ck" / "last.pending.json").exists()
    rec = _read_tombstone(tmp_path)
    assert rec is not None and rec["reason"] == "storage-outage"


# ---------------------------------------------------------------------------
# The 2-process acceptance drill
# ---------------------------------------------------------------------------


def _launch_deadman(phase: str, scratch: str, timeout: float = 300):
    """Spawn the 2-rank drill; returns (outputs, returncodes). Unlike
    mp_launch.launch_group, nonzero exits are EXPECTED here (the whole
    point is the exit-code contract)."""
    from mp_launch import clean_env, free_port
    port = free_port()
    env = clean_env()
    env["IMAGENT_MP_SCRATCH"] = scratch
    env["IMAGENT_DEADMAN_PHASE"] = phase
    env.pop("IMAGENT_FAULTS", None)  # per-rank arming happens inside
    procs = [subprocess.Popen(
        [sys.executable, os.path.join(_DIR, "mp_worker_deadman.py"),
         str(rank), str(port), "2"],
        cwd=_REPO, env=env, stdout=subprocess.PIPE,
        stderr=subprocess.STDOUT, text=True)
        for rank in range(2)]
    try:
        outs = [p.communicate(timeout=timeout)[0] for p in procs]
    finally:
        for p in procs:
            if p.poll() is None:
                p.kill()
    return outs, [p.returncode for p in procs]


def test_deadman_pod_drill_kill_and_requeue(tmp_path):
    """THE acceptance drill: a real 2-process CPU pod; rank 1 is
    fault-killed mid-epoch (host.die — abrupt, no tombstone); the
    survivor must detect via heartbeat staleness (not the 60s watchdog
    armed alongside), refuse further collectives, land process 0's
    collective-free flat emergency snapshot, classify itself, and exit
    with the retryable peer-death code inside the ~2s peer deadline —
    then a requeued --resume pod restores mid-epoch and completes."""
    scratch = str(tmp_path)
    outs, rcs = _launch_deadman("kill", scratch)
    out0, out1 = outs
    # Rank 1 died abruptly with the fault's (unregistered) code.
    assert rcs[1] == 1, out1
    assert "FAULT host.die" in out1, out1
    # The survivor exited with the taxonomy's peer-death code...
    assert rcs[0] == exitcodes.PEER_DEAD, out0
    assert "DEADMAN_OK" in out0, out0
    assert "peer=1" in out0 and "reason=stale" in out0, out0
    # ...via the deadman, not the watchdog...
    assert "WATCHDOG" not in out0, out0
    assert "pod DEGRADED" in out0, out0
    # ...with detection latency on the order of the 2s deadline (the
    # whole point vs the watchdog's multi-minute hard-exit window).
    detect = float(re.search(r"detect_s=([0-9.]+)", out0).group(1))
    assert 2.0 <= detect <= 4.5, out0
    assert "emergency snapshot committed as LAST" in out0, out0
    # The survivor's peer-death exit (87) landed its flight recorder
    # with the last lagged health records before the pod degraded.
    from imagent_tpu.telemetry.flightrec import read_flightrec
    fr = read_flightrec(os.path.join(scratch, "tb",
                                     "flightrec.0.json"))
    assert fr is not None and fr["reason"] == "peer-dead", fr
    assert fr["exit_code"] == exitcodes.PEER_DEAD
    assert fr["records"], fr
    # ...and its span rings on the same ramp (run with --trace phases):
    # the trace of the death ends AT the death — deadman verdict
    # instant, emergency-snapshot span, and the dispatch windows that
    # preceded them — not at the last epoch boundary (there was none:
    # the pod died mid-epoch 0).
    from imagent_tpu.telemetry import trace as trace_lib
    hdr, spans = trace_lib.read_trace(os.path.join(
        scratch, "tb", "trace", "trace.0.jsonl"))
    assert hdr is not None and hdr["rank"] == 0, hdr
    names = {sp["n"] for sp in spans}
    assert "pod/degraded" in names, names
    assert "ckpt/emergency" in names, names
    assert "dispatch" in names or "compile" in names, names
    # Rank 1 died abruptly (host.die, no flush) — no trace file, by
    # design: an un-flushable death loses its ring, never the run.
    assert not os.path.exists(os.path.join(
        scratch, "tb", "trace", "trace.1.jsonl"))

    # Requeue: a fresh pod resumes from the emergency snapshot.
    outs2, rcs2 = _launch_deadman("resume", scratch)
    assert rcs2 == [0, 0], outs2
    assert "resumed from epoch 0 step 3" in outs2[0], outs2[0]
    assert all("RESUME_OK" in o for o in outs2), outs2
