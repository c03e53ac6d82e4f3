"""Model-health observability suite: the EWMA divergence detector
(telemetry/health.py), the crash flight recorder
(telemetry/flightrec.py), the live status surface (status.py) and the
offline `python -m imagent_tpu.telemetry summarize` CLI — plus the
no-sync contract: the hot modules are jax-free and the health-stat
wiring adds zero entries to jaxlint's host-sync rules.

The end-to-end divergence drill (step.grad_spike + --health-rollback)
lives in tests/test_fault_drills.py; the flight-recorder-on-fatal-exit
assertions ride the drills in tests/test_pod_failure.py; the 2-process
status acceptance rides tests/test_telemetry.py's pod drill."""

import json
import math
import os
import subprocess
import sys
import time

import pytest

from imagent_tpu import status as status_lib
from imagent_tpu.telemetry import flightrec as flightrec_lib
from imagent_tpu.telemetry import health as health_lib
from imagent_tpu.telemetry.flightrec import FlightRecorder, read_flightrec
from imagent_tpu.telemetry.health import Ewma, HealthMonitor

REPO_ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


# ----------------------------------------------- the no-sync contract


def test_per_step_health_cost_is_bounded(tmp_path):
    """20k observe+record rounds in well under 2s — a regression that
    sneaks I/O or allocation storms into the hot path fails loudly."""
    rec = FlightRecorder(str(tmp_path), 0, capacity=256)
    mon = HealthMonitor(warmup_steps=5, recorder=rec)
    t0 = time.perf_counter()
    for i in range(20_000):
        mon.observe(epoch=0, step=i, loss=2.0, grad_norm=10.0,
                    param_norm=100.0, update_ratio=0.01)
    elapsed = time.perf_counter() - t0
    assert elapsed < 2.0, (
        f"20k health observations took {elapsed:.2f}s — the hot path "
        "grew real work")
    assert mon.anomalies == 0


def test_health_wiring_adds_no_jaxlint_host_sync_findings():
    """The zero-added-host-syncs acceptance gate, statically: with the
    health stats wired through train.py and the engine's step loop
    (status writes, flight-recorder feeds), the repo still has ZERO
    blocking-call-in-step-loop / host-sync-in-jit findings."""
    from imagent_tpu.analysis.runner import run_paths
    result = run_paths(
        [os.path.join(REPO_ROOT, "imagent_tpu")],
        baseline_path=None,
        select={"blocking-call-in-step-loop", "host-sync-in-jit"},
        root=REPO_ROOT)
    assert result.findings == [], [
        f"{f.path}:{f.line} {f.rule}" for f in result.findings]


# ----------------------------------------------------------- detector

def test_ewma_math_and_seed():
    e = Ewma(beta=0.5)
    assert e.value is None and e.n == 0
    e.update(4.0)
    assert e.value == 4.0 and e.n == 1
    e.update(8.0)
    assert e.value == pytest.approx(6.0)
    e.update(float("nan"))  # never absorbed
    assert e.value == pytest.approx(6.0) and e.n == 2
    e2 = Ewma()
    e2.seed(3.5, 7)
    assert e2.value == 3.5 and e2.n == 7
    e2.seed(float("inf"), 9)  # garbage meta is ignored
    assert e2.value == 3.5
    with pytest.raises(ValueError):
        Ewma(beta=1.0)


def test_monitor_warmup_gates_verdicts():
    mon = HealthMonitor(grad_spike_factor=10.0, warmup_steps=3)
    # Two clean steps: a wild third value is NOT judged (baseline cold).
    for i in range(2):
        assert mon.observe(epoch=0, step=i, loss=2.0, grad_norm=1.0,
                           param_norm=10.0, update_ratio=0.01) is None
    assert not mon.ready
    assert mon.observe(epoch=0, step=2, loss=2.0, grad_norm=500.0,
                       param_norm=10.0, update_ratio=0.01) is None
    assert mon.ready  # 3 absorbed observations now


def _warm(mon, n=5, loss=2.0, grad=1.0, ratio=0.01):
    for i in range(n):
        mon.observe(epoch=0, step=i, loss=loss, grad_norm=grad,
                    param_norm=100.0, update_ratio=ratio)


def test_monitor_detects_each_spike_kind():
    mon = HealthMonitor(grad_spike_factor=10.0, loss_spike_factor=3.0,
                        warmup_steps=3)
    _warm(mon)

    def clean(step):  # end the streak so the next incident emits
        assert mon.observe(epoch=1, step=step, loss=2.0, grad_norm=1.0,
                           param_norm=100.0, update_ratio=0.01) is None

    a = mon.observe(epoch=1, step=0, loss=2.0, grad_norm=50.0,
                    param_norm=100.0, update_ratio=0.01)
    assert a["kind"] == "grad_spike" and a["baseline"] == pytest.approx(
        1.0)
    clean(1)
    a = mon.observe(epoch=1, step=2, loss=2.0, grad_norm=1.0,
                    param_norm=100.0, update_ratio=0.5)
    assert a["kind"] == "update_spike"
    clean(3)
    a = mon.observe(epoch=1, step=4, loss=30.0, grad_norm=1.0,
                    param_norm=100.0, update_ratio=0.01)
    assert a["kind"] == "loss_spike"
    clean(5)
    a = mon.observe(epoch=1, step=6, loss=float("nan"), grad_norm=1.0,
                    param_norm=100.0, update_ratio=0.01)
    assert a["kind"] == "non_finite" and a["value"] is None
    assert mon.anomalies == 4


def test_nonfinite_param_norm_fires_despite_zero_ratio():
    """A params fp32 overflow (pnorm2 = inf) makes update_ratio =
    dnorm/inf = 0.0 — finite, and actively suppressing the
    update_spike check. The non-finite classification must cover
    param_norm so the blown-up-weights regime still flags; the
    reported value is the offending scalar (nulled), never a
    normal-looking unrelated number."""
    mon = HealthMonitor(warmup_steps=3)
    _warm(mon)
    a = mon.observe(epoch=1, step=0, loss=2.0, grad_norm=1.0,
                    param_norm=float("inf"), update_ratio=0.0)
    assert a is not None and a["kind"] == "non_finite"
    assert a["value"] is None
    # Only the ratio non-finite: value must not echo the finite loss.
    mon2 = HealthMonitor(warmup_steps=3)
    _warm(mon2)
    a = mon2.observe(epoch=1, step=0, loss=2.0, grad_norm=1.0,
                     param_norm=100.0, update_ratio=float("inf"))
    assert a is not None and a["kind"] == "non_finite"
    assert a["value"] is None


def test_anomalies_are_not_absorbed_into_baseline():
    """A ramping divergence must not normalize itself into
    invisibility: the spiked values never move the EWMA. Counted every
    step; the VERDICT is emitted only at the streak's start (see the
    rate-limit test below)."""
    mon = HealthMonitor(grad_spike_factor=10.0, warmup_steps=3)
    _warm(mon)
    base = mon.grad.value
    verdicts = [mon.observe(epoch=1, step=i, loss=2.0, grad_norm=100.0,
                            param_norm=100.0, update_ratio=0.01)
                for i in range(10)]
    # EVERY anomalous step returns its verdict — the engine's rollback
    # trip keys on the step, not on the rate-limited emission.
    assert all(v is not None and v["kind"] == "grad_spike"
               for v in verdicts)
    assert mon.anomalies == 10
    assert mon.grad.value == base


def test_standing_anomaly_verdicts_are_rate_limited():
    """Warn-only mode must not flood telemetry.jsonl/stdout with one
    verdict per step for the rest of a run that settles anomalous:
    first step of a streak emits, then once per EMIT_EVERY; a clean
    step resets the streak so the NEXT incident emits immediately."""
    emitted = []
    mon = HealthMonitor(grad_spike_factor=10.0, warmup_steps=2,
                        on_anomaly=emitted.append)
    _warm(mon, n=3)
    n = 2 * HealthMonitor.EMIT_EVERY
    for i in range(n):
        a = mon.observe(epoch=1, step=i, loss=2.0, grad_norm=100.0,
                        param_norm=100.0, update_ratio=0.01)
        assert a is not None  # every step returns (the rollback trip)
    assert mon.anomalies == n  # every step counted...
    # ...but only streak starts + every-EMIT_EVERY repeats emitted.
    assert [a["streak"] for a in emitted] == [
        1, HealthMonitor.EMIT_EVERY, 2 * HealthMonitor.EMIT_EVERY]
    # A clean step ends the streak; a fresh incident emits at once.
    mon.observe(epoch=1, step=n, loss=2.0, grad_norm=1.0,
                param_norm=100.0, update_ratio=0.01)
    mon.observe(epoch=1, step=n + 1, loss=2.0, grad_norm=100.0,
                param_norm=100.0, update_ratio=0.01)
    assert emitted[-1]["streak"] == 1


def test_bad_steps_skip_baseline_and_detection():
    """The guard's skipped steps (metrics zeroed, n == 0) carry loss 0
    and NaN norms — neither may poison the baseline, and the guard
    owns their rollback policy."""
    mon = HealthMonitor(warmup_steps=3)
    _warm(mon)
    base = (mon.loss.value, mon.grad.value)
    a = mon.observe(epoch=1, step=0, loss=0.0,
                    grad_norm=float("nan"), param_norm=float("nan"),
                    update_ratio=float("nan"), bad=True)
    assert a is None
    assert mon.bad_steps == 1 and mon.anomalies == 0
    assert (mon.loss.value, mon.grad.value) == base


def test_monitor_zero_factor_disables_check():
    mon = HealthMonitor(grad_spike_factor=0.0, loss_spike_factor=0.0,
                        warmup_steps=2)
    _warm(mon)
    assert mon.observe(epoch=1, step=0, loss=1e6, grad_norm=1e6,
                       param_norm=100.0, update_ratio=1e6) is None


def test_monitor_meta_snapshot_seed_roundtrip():
    mon = HealthMonitor(warmup_steps=3)
    _warm(mon, n=8, loss=2.5, grad=7.0, ratio=0.03)
    meta = mon.meta_snapshot()
    assert meta["health_ewma_n"] == 8
    fresh = HealthMonitor(warmup_steps=3)
    assert not fresh.ready
    assert fresh.seed(meta) is True
    assert fresh.ready  # resume judges immediately, no cold start
    assert fresh.grad.value == pytest.approx(mon.grad.value)
    assert fresh.seed({"health_ewma_n": 0}) is False  # old checkpoint


def test_monitor_callbacks_and_recorder(tmp_path):
    rec = FlightRecorder(str(tmp_path), 0, capacity=8)
    seen = []
    mon = HealthMonitor(warmup_steps=2, recorder=rec,
                        on_anomaly=seen.append)
    _warm(mon, n=3)
    mon.observe(epoch=1, step=0, loss=2.0, grad_norm=99.0,
                param_norm=100.0, update_ratio=0.01)
    assert len(seen) == 1 and seen[0]["kind"] == "grad_spike"
    recs = rec.records()
    assert len(recs) == 4
    assert recs[-1]["anomaly"] == "grad_spike"
    assert recs[0]["grad_norm"] == 1.0


# ----------------------------------------------------- flight recorder

def test_flightrec_ring_wraps_oldest_first(tmp_path):
    rec = FlightRecorder(str(tmp_path), 0, capacity=4)
    for i in range(10):
        rec.record({"step": i})
    out = rec.records()
    assert [r["step"] for r in out] == [6, 7, 8, 9]


def test_flightrec_concurrent_flushes_land_one_valid_record(tmp_path):
    """The exit ramps race by design (watchdog/deadman threads vs the
    main handler): exactly one cause must win, and the published file
    must be complete."""
    import threading
    rec = FlightRecorder(str(tmp_path), 0, capacity=64)
    for i in range(64):
        rec.record({"step": i})
    barrier = threading.Barrier(4)
    paths = []

    def ramp(reason, code):
        barrier.wait()
        paths.append(rec.flush(reason, code))

    threads = [threading.Thread(target=ramp, args=(r, c))
               for r, c in (("watchdog-hard-exit", 86), ("peer-dead", 87),
                            ("exception", 70), ("storage-outage", 88))]
    for t in threads:
        t.start()
    for t in threads:
        t.join()
    assert len(set(paths)) == 1 and paths[0] is not None
    data = read_flightrec(paths[0])
    assert data is not None and len(data["records"]) == 64
    assert (data["reason"], data["exit_code"]) in {
        ("watchdog-hard-exit", 86), ("peer-dead", 87),
        ("exception", 70), ("storage-outage", 88)}


def test_flightrec_flush_first_cause_wins(tmp_path):
    import numpy as np
    rec = FlightRecorder(str(tmp_path), 3, capacity=4)
    # numpy values must never raise on the exit ramp (events.jsonsafe).
    rec.note(arch="resnet18", shard_shape=np.array([2, 3]),
             seed=np.int64(7))
    rec.record({"step": 0, "loss": float("inf")})
    path = rec.flush("rollback-give-up", 79, detail="gave up")
    assert path and path.endswith("flightrec.3.json")
    # A later handler on the same unwind is an echo: no overwrite.
    assert rec.flush("exception", 70) == path
    data = json.loads(open(path).read())
    assert data["reason"] == "rollback-give-up"
    assert data["exit_code"] == 79
    assert data["context"]["arch"] == "resnet18"
    assert data["context"]["shard_shape"] == [2, 3]
    assert data["context"]["seed"] == 7
    assert data["records"][0]["loss"] is None  # strict-JSON: inf nulled
    assert "Infinity" not in open(path).read()
    assert read_flightrec(path) == data
    assert read_flightrec(str(tmp_path / "missing.json")) is None


def test_flush_active_without_recorder_is_noop():
    flightrec_lib.deactivate()
    assert flightrec_lib.flush_active("exception", 70) is None


def test_pod_tombstone_references_active_flightrec(tmp_path):
    """The mechanism the watchdog-86 and deadman-87 hard-exit threads
    share: every PodHeartbeat.tombstone first flushes the active
    recorder (engine wires on_fatal) and names the landed file in the
    tombstone detail."""
    from imagent_tpu.resilience import heartbeat
    from imagent_tpu.resilience.deadman import PodHeartbeat

    rec = FlightRecorder(str(tmp_path), 0, capacity=4)
    rec.record({"step": 1, "loss": 2.0})
    flightrec_lib.activate(rec)
    try:
        pod = PodHeartbeat(str(tmp_path), 0, 1, deadline_secs=60.0)
        pod.on_fatal = flightrec_lib.flush_active
        assert pod.tombstone("watchdog-hard-exit", 86,
                             detail="no step progress") is True
    finally:
        flightrec_lib.deactivate()
    ts = heartbeat.read_record(heartbeat.tombstone_path(
        heartbeat.heartbeat_dir(str(tmp_path)), 0))
    assert ts["reason"] == "watchdog-hard-exit"
    assert "flightrec=flightrec.0.json" in ts["detail"]
    fr = read_flightrec(str(tmp_path / "flightrec.0.json"))
    assert fr["reason"] == "watchdog-hard-exit" and fr["exit_code"] == 86


# ------------------------------------------------------ status surface

def _write_status_fixture(run_dir, degraded=False):
    w = status_lib.StatusWriter(str(run_dir))
    w.write({"phase": "train", "epoch": 2, "epochs": 10, "step": 7,
             "steps_per_epoch": 40, "loss": 1.875, "lr": 0.05,
             "best_top1": 61.3, "bad_steps": 0, "degraded": degraded,
             "health": {"loss_ewma": 1.9, "grad_norm_ewma": 12.5,
                        "update_ratio_ewma": 0.004, "ewma_n": 87,
                        "anomalies": 1, "bad_steps": 0}})
    return w


def test_status_writer_roundtrip_and_torn_read(tmp_path):
    _write_status_fixture(tmp_path)
    st = status_lib.read_status(str(tmp_path))
    assert st["epoch"] == 2 and st["loss"] == 1.875
    assert st["t"] > 0
    # Torn/absent reads never raise.
    assert status_lib.read_status(str(tmp_path / "nope")) is None
    (tmp_path / "status.json").write_text('{"torn')
    assert status_lib.read_status(str(tmp_path)) is None


def test_status_render_one_screen(tmp_path):
    from imagent_tpu.resilience import heartbeat
    _write_status_fixture(tmp_path)
    hb_dir = heartbeat.heartbeat_dir(str(tmp_path))
    os.makedirs(hb_dir)
    heartbeat._write_atomic(heartbeat.heartbeat_path(hb_dir, 0),
                            {"rank": 0, "pid": 1, "seq": 9,
                             "t": time.time(), "epoch": 2, "step": 7,
                             "phase": "train"})
    heartbeat._write_atomic(heartbeat.tombstone_path(hb_dir, 1),
                            {"rank": 1, "reason": "storage-outage",
                             "exit_code": 88, "retryable": True,
                             "detail": "", "t": time.time()})
    with open(tmp_path / "telemetry.jsonl", "w") as f:
        f.write(json.dumps({"event": "run_start", "schema": 1, "t": 1,
                            "arch": "resnet50", "global_batch": 2048,
                            "process_count": 2,
                            "device_count": 8}) + "\n")
        f.write(json.dumps({"event": "epoch", "schema": 1, "t": 2,
                            "epoch": 2, "goodput": 0.91, "wall_s": 100,
                            "phases": {"input_wait": 2.5},
                            "step_ms": {"p95_ms": 123.4},
                            "stragglers": [],
                            "hbm": {"bytes_in_use": 9.8e9,
                                    "peak_bytes_in_use": 11.2e9,
                                    "bytes_limit": 16e9}}) + "\n")
        f.write(json.dumps({"event": "health_anomaly", "schema": 1,
                            "t": 3, "kind": "grad_spike", "epoch": 2,
                            "step": 5, "value": 150.0,
                            "baseline": 12.0}) + "\n")
    out = status_lib.render(str(tmp_path))
    assert "resnet50" in out and "2048" in out
    assert "epoch 3/10 step 7/40" in out
    assert "grad_norm ewma 12.5" in out
    assert "goodput 91.00%" in out
    assert "11.20 GB peak / 16.00 GB" in out
    assert "host 0: train epoch 3 step 7" in out
    assert "host 1: no heartbeat | TOMBSTONE storage-outage" in out
    assert "ANOMALY: grad_spike at epoch 3 step 5" in out
    # Degraded flag is unmissable.
    _write_status_fixture(tmp_path, degraded=True)
    assert "** POD DEGRADED **" in status_lib.render(str(tmp_path))


def test_status_cli(tmp_path):
    _write_status_fixture(tmp_path)
    proc = subprocess.run(
        [sys.executable, "-m", "imagent_tpu.status", str(tmp_path)],
        capture_output=True, text=True, timeout=60, cwd=REPO_ROOT)
    assert proc.returncode == 0, proc.stderr
    assert "frontier: epoch 3/10" in proc.stdout
    missing = subprocess.run(
        [sys.executable, "-m", "imagent_tpu.status",
         str(tmp_path / "absent")],
        capture_output=True, text=True, timeout=60, cwd=REPO_ROOT)
    assert missing.returncode == 2


# --------------------------------------------- telemetry summarize CLI

_GOLDEN_EVENTS = [
    {"event": "run_start", "schema": 1, "t": 1.0, "arch": "resnet18",
     "global_batch": 32, "process_count": 2, "steps_per_epoch": 4},
    {"event": "epoch", "schema": 1, "t": 2.0, "epoch": 0,
     "wall_s": 10.5, "goodput": 0.8123,
     "phases": {"input_wait": 1.25},
     "step_ms": {"p95_ms": 120.5},
     "counters": {"bad_steps": 1, "health_anomalies": 0},
     "health": {"grad_norm_ewma": 55.2, "update_ratio_ewma": 0.0123},
     "hbm": {"peak_bytes_in_use": 2_500_000_000}},
    {"event": "health_anomaly", "schema": 1, "t": 2.5,
     "kind": "update_spike", "epoch": 1, "step": 2},
    {"event": "epoch", "schema": 1, "t": 3.0, "epoch": 1,
     "wall_s": 8.0, "goodput": 0.9001,
     "phases": {"input_wait": 0.5},
     "step_ms": {"p95_ms": 98.7},
     "counters": {"health_anomalies": 1},
     "health": {"grad_norm_ewma": 60.0, "update_ratio_ewma": 0.011},
     "stragglers": [{"host": 1}], "interrupted": True},
    {"event": "run_end", "schema": 1, "t": 4.0, "best_top1": 61.25,
     "best_epoch": 0, "total_minutes": 0.35, "rollbacks": 1},
]

_GOLDEN_TABLE = """\
run: resnet18 global_batch 32 x2 host(s), 4 steps/epoch
epoch    wall_s  goodput   input_s    p95_ms   bad  anomal  gnorm_ewma  ratio_ewma   hbm_gb
    1      10.5    0.812       1.2     120.5     1       0        55.2      0.0123     2.50
    2       8.0    0.900       0.5      98.7     0       1          60       0.011        -  [interrupted]  [stragglers: 1]
  health_anomaly: update_spike at epoch 2 step 2
run_end: best_top1 61.25 (epoch 1), 0.35 min, rollbacks 1"""


def test_telemetry_summarize_golden_output(tmp_path):
    """The table format is a parse contract for downstream scripts —
    pinned byte-for-byte."""
    with open(tmp_path / "telemetry.jsonl", "w") as f:
        for rec in _GOLDEN_EVENTS:
            f.write(json.dumps(rec) + "\n")
        f.write('{"torn tail\n')  # killed-run tail must be tolerated
    proc = subprocess.run(
        [sys.executable, "-m", "imagent_tpu.telemetry", "summarize",
         str(tmp_path)],
        capture_output=True, text=True, timeout=60, cwd=REPO_ROOT)
    assert proc.returncode == 0, proc.stderr
    assert proc.stdout.rstrip("\n") == _GOLDEN_TABLE, proc.stdout
    empty = subprocess.run(
        [sys.executable, "-m", "imagent_tpu.telemetry", "summarize",
         str(tmp_path / "absent")],
        capture_output=True, text=True, timeout=60, cwd=REPO_ROOT)
    assert "no telemetry.jsonl" in empty.stdout


def test_telemetry_summarize_chipacct_columns(tmp_path):
    """The chip-accountant columns (ISSUE 19) appear ONLY when a
    record carries the ``chipacct`` sub-record — the golden test
    above pins that a pre-accountant log still renders byte-identical
    (the addition is conditional, not a table-format bump)."""
    events = [dict(rec) for rec in _GOLDEN_EVENTS]
    events[1] = dict(events[1])
    events[1]["chipacct"] = {
        "verdict": "ok", "modeled_peak_bytes": 3.2e9,
        "state_bytes": {"params": 1e9, "total": 1e9},
        "peak_tflops": 275.0, "tflops_per_chip": 115.61,
        "mfu": 0.4204}
    events[3] = dict(events[3])
    events[3]["chipacct"] = {
        "verdict": "ok", "modeled_peak_bytes": 3.2e9,
        "state_bytes": {"params": 1e9, "total": 1e9},
        "peak_tflops": None, "tflops_per_chip": 118.0,
        "mfu": None}  # honest-unknown peak: no ratio, cell dashes
    with open(tmp_path / "telemetry.jsonl", "w") as f:
        for rec in events:
            f.write(json.dumps(rec) + "\n")
    proc = subprocess.run(
        [sys.executable, "-m", "imagent_tpu.telemetry", "summarize",
         str(tmp_path)],
        capture_output=True, text=True, timeout=60, cwd=REPO_ROOT)
    assert proc.returncode == 0, proc.stderr
    lines = proc.stdout.splitlines()
    header = [ln for ln in lines if ln.startswith("epoch")][0]
    assert "mfu" in header.split() and "model_gb" in header.split()
    row1 = [ln for ln in lines if ln.strip().startswith("1 ")][0]
    assert "0.420" in row1 and "3.20" in row1, row1
    # --json carries the raw sub-record for scripts.
    js = subprocess.run(
        [sys.executable, "-m", "imagent_tpu.telemetry", "summarize",
         str(tmp_path), "--json"],
        capture_output=True, text=True, timeout=60, cwd=REPO_ROOT)
    doc = json.loads(js.stdout)
    ep0 = [e for e in doc["epochs"] if e["epoch"] == 0][0]
    assert ep0["chipacct"]["mfu"] == 0.4204


# -------------------------------------------------- engine round-trips

def _cfg(tmp_path, **kw):
    from imagent_tpu.config import Config
    base = dict(backend="cpu", arch="resnet18", image_size=16, num_classes=4,
                batch_size=4, epochs=1, lr=0.05, dataset="synthetic",
                synthetic_size=128, workers=0, bf16=False, log_every=2,
                seed=0, save_model=True,
                log_dir=str(tmp_path / "tb"),
                ckpt_dir=str(tmp_path / "ck"))
    base.update(kw)
    return Config(**base)


def test_resume_reseeds_detector_from_checkpoint_meta(tmp_path,
                                                      capsys):
    """The cold-start fix: a --resume must judge its first steps
    against the pre-crash EWMA baseline recorded in the checkpoint
    meta, not warm up blind while a spike slides past."""
    from imagent_tpu.engine import run
    run(_cfg(tmp_path))
    meta = json.loads((tmp_path / "ck" / "last_meta.json").read_text())
    assert meta["health_ewma_n"] > 0
    assert meta["health_grad_ewma"] > 0
    capsys.readouterr()
    run(_cfg(tmp_path, epochs=2, resume=True))
    out = capsys.readouterr().out
    assert (f"health detector re-seeded from checkpoint EWMAs "
            f"(n={meta['health_ewma_n']})") in out


def test_engine_live_status_tail(tmp_path):
    """The acceptance check for the live surface: a stop_check callback
    — called from inside the running step loop — tails status.json and
    renders the CLI view mid-run."""
    from imagent_tpu.engine import run
    snapshots = []

    def tail():
        st = status_lib.read_status(str(tmp_path / "tb"))
        if st is not None and not snapshots:
            snapshots.append((st, status_lib.render(
                str(tmp_path / "tb"))))
        return False

    run(_cfg(tmp_path, log_every=1), stop_check=tail)
    assert snapshots, "status.json never appeared during the live run"
    st, rendered = snapshots[0]
    assert st["phase"] == "train" and st["epochs"] == 1
    assert (st.get("health") or {}) != {}
    assert "frontier: epoch 1/1" in rendered


def test_no_health_stats_kills_the_whole_surface(tmp_path):
    """--no-health-stats: 4-vector metrics, no detector, no health in
    the telemetry record, no flight recorder — and the run is green."""
    from imagent_tpu.engine import run
    from imagent_tpu.telemetry.events import read_events
    result = run(_cfg(tmp_path, health_stats=False))
    assert result["best_epoch"] >= 0
    recs = read_events(str(tmp_path / "tb" / "telemetry.jsonl"))
    ep = [r for r in recs if r["event"] == "epoch"][-1]
    assert "health" not in ep
    assert not (tmp_path / "tb" / "flightrec.0.json").exists()
    meta = json.loads((tmp_path / "ck" / "last_meta.json").read_text())
    assert meta.get("health_ewma_n", 0) == 0


def test_health_flag_validation(tmp_path):
    from imagent_tpu.engine import run
    with pytest.raises(ValueError, match="health-warmup-steps"):
        run(_cfg(tmp_path, health_warmup_steps=0))
    with pytest.raises(ValueError, match="health-grad-spike"):
        run(_cfg(tmp_path, health_grad_spike=-1.0))
    with pytest.raises(ValueError, match="health-rollback"):
        run(_cfg(tmp_path, health_rollback=True, health_stats=False))
    with pytest.raises(ValueError, match="flightrec-steps"):
        run(_cfg(tmp_path, flightrec_steps=-1))


def test_cli_flags_parse():
    from imagent_tpu.config import parse_args
    cfg = parse_args(["--health-rollback", "--health-grad-spike", "6",
                      "--health-loss-spike", "4",
                      "--health-warmup-steps", "10",
                      "--flightrec-steps", "64"])
    assert cfg.health_rollback and cfg.health_grad_spike == 6.0
    assert cfg.health_loss_spike == 4.0
    assert cfg.health_warmup_steps == 10
    assert cfg.flightrec_steps == 64
    assert parse_args(["--no-health-stats"]).health_stats is False
    assert parse_args([]).health_stats is True


def test_train_step_metric_tail_matches_health_fields():
    """The wire contract between train.py's in-graph stack and the
    host-side monitor: 4 classic fields + HEALTH_FIELDS, in order,
    replicated; norms finite and the ratio consistent with them."""
    import jax
    import numpy as np
    from imagent_tpu.cluster import make_mesh
    from imagent_tpu.models import create_model
    from imagent_tpu.train import (
        HEALTH_FIELDS, create_train_state, make_optimizer,
        make_train_step, replicate_state, shard_batch,
    )
    # The two modules declare the tail independently (health.py must
    # stay jax-free) — the order IS the wire format, so they must
    # agree exactly.
    assert HEALTH_FIELDS == health_lib.HEALTH_FIELDS
    mesh = make_mesh(model_parallel=1)
    model = create_model("resnet18", num_classes=4)
    opt = make_optimizer()
    state = replicate_state(
        create_train_state(model, jax.random.key(0), 16, opt), mesh)
    step = make_train_step(model, opt, mesh, health_stats=True)
    imgs = np.random.default_rng(0).random((32, 16, 16, 3)).astype(
        np.float32)
    lbls = np.arange(32, dtype=np.int64) % 4
    di, dl = shard_batch(mesh, imgs, lbls)
    # The step donates its input state: keep a host copy for the
    # reference norms below.
    params0 = jax.tree.map(lambda x: np.asarray(x, np.float64),
                           state.params)
    import jax.numpy as jnp
    state2, m = step(state, di, dl, jnp.float32(0.1))
    m = np.asarray(m)
    assert m.shape == (4 + len(HEALTH_FIELDS),)
    grad_norm, param_norm, ratio = m[4:]
    assert np.isfinite([grad_norm, param_norm, ratio]).all()
    assert grad_norm > 0 and param_norm > 0 and ratio > 0
    # The ratio really is ||Δp|| / ||p|| for the applied update.
    dp = jax.tree.map(lambda a, b: np.asarray(a, np.float64) - b,
                      state2.params, params0)
    dnorm = math.sqrt(sum(float(np.sum(x * x))
                          for x in jax.tree.leaves(dp)))
    pnorm = math.sqrt(sum(float(np.sum(x * x))
                          for x in jax.tree.leaves(params0)))
    assert param_norm == pytest.approx(pnorm, rel=1e-3)
    assert ratio == pytest.approx(dnorm / pnorm, rel=1e-2)
