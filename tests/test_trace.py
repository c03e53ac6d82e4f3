"""Pod tracer suite (telemetry/trace.py): the span recorder contract
(jax-free, bounded rings, coalescing, overhead bound, off = zero
cost), the torn-tail reader, the skew-corrected merge + Chrome-trace
validation + CLI, the engine drills (phases/steps modes, flag
validation, fatal-exit flushes), and the summarize trace columns.

The 2-process pod acceptance (>= 2 ranks, >= 3 subsystems, skew
corrected via the real allgather clock record) rides
tests/test_telemetry.py's pod drill; the 87-ramp flush rides
tests/test_pod_failure.py's deadman kill drill; the bench-smoke gate
(spans-vs-goodput within 5% of wall) is stage 3 of
benchmarks/bench_smoke.py."""

import json
import os
import subprocess
import sys
import threading
import time

import pytest

from imagent_tpu.telemetry import trace as trace_lib
from imagent_tpu.telemetry.trace import (
    TraceRecorder, merge, phase_span_seconds, read_trace,
    validate_chrome_trace,
)

REPO_ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


@pytest.fixture(autouse=True)
def _no_leaked_recorder():
    """Every test leaves the module-global recorder uninstalled (the
    engine's finally does the same for real runs)."""
    yield
    trace_lib.deactivate()


# ------------------------------------------------- the no-sync contract


def test_per_span_overhead_is_bounded(tmp_path):
    """20k span emissions (the ctx manager AND the pre-timed complete
    path, merged and unmerged) in well under 2s — the sampler-pattern
    bound that catches I/O or allocation storms sneaking into the hot
    path."""
    rec = TraceRecorder(str(tmp_path), 0, mode="phases", buffer=4096)
    trace_lib.activate(rec)
    t0 = time.perf_counter()
    for i in range(10_000):
        with trace_lib.span("dispatch", cat=trace_lib.PHASE_CAT):
            pass
        trace_lib.complete("dispatch", 0.0, 0.001,
                           cat=trace_lib.PHASE_CAT, merge=True)
    elapsed = time.perf_counter() - t0
    assert elapsed < 2.0, (
        f"20k span emissions took {elapsed:.2f}s — the hot path grew "
        "real work")


def test_trace_off_is_the_shared_noop():
    """With no recorder active, span() returns the one shared null
    context manager (zero allocation) and complete()/instant() are
    no-ops — the '--trace off => zero ring cost' half of the
    contract (the zero-files half is drilled in the engine test)."""
    trace_lib.deactivate()
    s1 = trace_lib.span("x", attr=1)
    s2 = trace_lib.span("y")
    assert s1 is s2 is trace_lib._NULL
    with s1 as s:
        s.set(more=2)  # attribute surface exists and does nothing
    trace_lib.complete("x", 0.0, 1.0)
    trace_lib.instant("x")
    assert trace_lib.flush_active() is None


# ------------------------------------------------------------- recorder

def test_ring_bounds_drop_oldest_and_count(tmp_path):
    rec = TraceRecorder(str(tmp_path), 0, buffer=4)
    for i in range(10):
        rec.complete(f"s{i}", float(i), float(i) + 0.5)
    summary = rec.flush()
    assert summary["spans"] == 4 and summary["dropped"] == 6
    _hdr, spans = read_trace(trace_lib.trace_path(str(tmp_path), 0))
    # Oldest dropped: the newest 4 survive.
    assert [sp["n"] for sp in spans] == ["s6", "s7", "s8", "s9"]


def test_flush_appends_and_reader_roundtrips(tmp_path):
    rec = TraceRecorder(str(tmp_path), 3, mode="steps", buffer=16)
    rec.complete("dispatch", 1.0, 1.5, cat="phase", step=7)
    rec.flush()
    rec.instant("pod/degraded", cat="pod", peer=1)
    rec.flush()
    rec.flush()  # empty flush writes nothing
    hdr, spans = read_trace(trace_lib.trace_path(str(tmp_path), 3))
    assert hdr["rank"] == 3 and hdr["mode"] == "steps"
    assert {"mono", "wall"} <= set(hdr["clock"])
    assert len(spans) == 2
    assert spans[0]["a"] == {"step": 7}
    assert spans[1]["ph"] == "i" and spans[1]["a"] == {"peer": 1}
    assert spans[0]["tn"] == threading.current_thread().name


def test_reader_tolerates_torn_tail(tmp_path):
    rec = TraceRecorder(str(tmp_path), 0, buffer=16)
    rec.complete("a", 0.0, 1.0)
    rec.complete("b", 1.0, 2.0)
    rec.flush()
    path = trace_lib.trace_path(str(tmp_path), 0)
    with open(path, "a") as f:
        f.write('{"n": "torn", "t0": 2.0, "t1')  # kill mid-append
    hdr, spans = read_trace(path)
    assert hdr is not None
    assert [sp["n"] for sp in spans] == ["a", "b"]


def test_phases_mode_coalesces_windows_steps_mode_does_not(tmp_path):
    rec = TraceRecorder(str(tmp_path), 0, mode="phases", buffer=64)
    for i in range(4):
        rec.complete("dispatch", i * 1.0, i * 1.0 + 0.25,
                     cat="phase", merge=True)
    rec.complete("input_wait", 4.0, 4.2, cat="phase")  # breaks the run
    rec.complete("dispatch", 4.2, 4.5, cat="phase", merge=True)
    rec.flush()
    _h, spans = read_trace(trace_lib.trace_path(str(tmp_path), 0))
    assert [sp["n"] for sp in spans] == ["dispatch", "input_wait",
                                        "dispatch"]
    window = spans[0]
    assert window["k"] == 4 and window["b"] == pytest.approx(1.0)
    assert window["t1"] - window["t0"] == pytest.approx(3.25)
    # The consistency sum reads busy time, never the window extent.
    sums = phase_span_seconds(spans)
    assert sums["dispatch"] == pytest.approx(1.3)
    assert sums["input_wait"] == pytest.approx(0.2)

    rec2 = TraceRecorder(str(tmp_path), 1, mode="steps", buffer=64)
    for i in range(4):
        rec2.complete("dispatch", i * 1.0, i * 1.0 + 0.25,
                      cat="phase", merge=True)
    rec2.flush()
    _h, spans2 = read_trace(trace_lib.trace_path(str(tmp_path), 1))
    assert len(spans2) == 4  # steps mode never merges


def test_span_ctx_records_attrs_and_errors(tmp_path):
    rec = TraceRecorder(str(tmp_path), 0, buffer=16)
    trace_lib.activate(rec)
    with trace_lib.span("ckpt/candidate", cat="ckpt",
                        candidate="last") as sp:
        sp.set(outcome="restored")
    with pytest.raises(RuntimeError):
        with trace_lib.span("ckpt/commit", cat="ckpt"):
            raise RuntimeError("boom")
    rec.flush()
    _h, spans = read_trace(trace_lib.trace_path(str(tmp_path), 0))
    assert spans[0]["a"] == {"candidate": "last",
                             "outcome": "restored"}
    assert spans[1]["a"] == {"error": "RuntimeError"}
    assert spans[1]["t1"] >= spans[1]["t0"]


def test_threaded_emission_lands_per_thread_rows(tmp_path):
    """Spans from worker threads carry their own tid/thread-name — the
    committer-thread / prefetch-producer rows of the merged timeline —
    and a flush racing the emitters stays consistent."""
    rec = TraceRecorder(str(tmp_path), 0, buffer=256)
    trace_lib.activate(rec)

    def work():
        for i in range(50):
            trace_lib.complete("ckpt/commit", i * 1.0, i * 1.0 + 0.5,
                               cat="ckpt")

    threads = [threading.Thread(target=work, name=f"worker-{k}")
               for k in range(3)]
    for t in threads:
        t.start()
    rec.flush()  # mid-emission flush must not corrupt anything
    for t in threads:
        t.join()
    rec.flush()
    _h, spans = read_trace(trace_lib.trace_path(str(tmp_path), 0))
    by_thread = {sp["tn"] for sp in spans}
    assert by_thread == {"worker-0", "worker-1", "worker-2"}
    assert len(spans) == 150


# ------------------------------------------------------- merge + skew

def _write_rank_file(run_dir, rank, spans, clock=None):
    os.makedirs(trace_lib.trace_dir(run_dir), exist_ok=True)
    lines = [json.dumps({"event": "header", "schema": 1, "rank": rank,
                         "pid": 1000 + rank, "mode": "phases",
                         "clock": clock or {"mono": 0.0,
                                            "wall": 1e9}})]
    lines += [json.dumps(sp) for sp in spans]
    with open(trace_lib.trace_path(run_dir, rank), "w") as f:
        f.write("\n".join(lines) + "\n")


def _write_clock_epoch(run_dir, wall, mono):
    with open(os.path.join(run_dir, "telemetry.jsonl"), "w") as f:
        f.write(json.dumps({"event": "epoch", "schema": 1, "epoch": 0,
                            "clock": {"wall": wall, "mono": mono,
                                      "max_skew_s": max(wall)
                                      - min(wall)}}) + "\n")


def test_merge_corrects_wall_clock_skew(tmp_path):
    """Rank 1's wall clock is 1000s ahead (broken NTP), but both ranks
    hit the epoch-boundary allgather at the same true instant — the
    merge must land their simultaneous spans at the SAME corrected
    timestamp, and report the measured skew."""
    run = str(tmp_path)
    # At the shared event: rank 0 (mono 100, wall 5000), rank 1
    # (mono 700, wall 6000) => rank 1's clock is +1000s skewed.
    _write_clock_epoch(run, wall=[5000.0, 6000.0], mono=[100.0, 700.0])
    # Both spans start 10s after the shared event on their own
    # monotonic clocks => the same true instant. Each file's header
    # pair is captured by the same host clocks, so its wall-mono
    # offset agrees with that rank's allgather pair.
    _write_rank_file(run, 0, [{"n": "dispatch", "ph": "X", "c": "phase",
                               "t0": 110.0, "t1": 111.0, "tid": 1,
                               "tn": "MainThread"}],
                     clock={"mono": 50.0, "wall": 4950.0})
    _write_rank_file(run, 1, [{"n": "dispatch", "ph": "X", "c": "phase",
                               "t0": 710.0, "t1": 711.0, "tid": 1,
                               "tn": "MainThread"}],
                     clock={"mono": 600.0, "wall": 5900.0})
    obj = merge(run)
    assert validate_chrome_trace(obj) == []
    xs = [ev for ev in obj["traceEvents"] if ev["ph"] == "X"]
    assert len(xs) == 2
    ts = {ev["pid"]: ev["ts"] for ev in xs}
    assert ts[0] == pytest.approx(ts[1], abs=1.0)  # µs scale
    other = obj["otherData"]
    assert other["skews_s"] == {"0": 0.0, "1": 1000.0}
    assert other["max_skew_s"] == pytest.approx(1000.0)
    assert other["skew_corrected"] == {"0": True, "1": True}


def test_merge_falls_back_to_header_clock_without_telemetry(tmp_path):
    """A run killed before its first epoch boundary has no clock
    record: per-rank placement from the file header, NO cross-rank
    correction — flagged, not silently wrong."""
    run = str(tmp_path)
    _write_rank_file(run, 0, [{"n": "a", "ph": "X", "t0": 1.0,
                               "t1": 2.0, "tid": 1, "tn": "t"}],
                     clock={"mono": 0.0, "wall": 100.0})
    obj = merge(run)
    assert validate_chrome_trace(obj) == []
    assert obj["otherData"]["skew_corrected"] == {"0": False}
    assert obj["otherData"]["skews_s"] == {}


def test_merge_is_deterministic_across_file_write_order(tmp_path):
    """Byte-identical trace.json however the per-rank files were
    written or listed (merge output feeds diff-based tooling)."""
    spans0 = [{"n": "dispatch", "ph": "X", "c": "phase", "t0": 110.0,
               "t1": 111.0, "tid": 5, "tn": "MainThread"},
              {"n": "data/stage", "ph": "X", "c": "data", "t0": 110.2,
               "t1": 110.4, "tid": 9, "tn": "device-prefetch"}]
    spans1 = [{"n": "ckpt/commit", "ph": "X", "c": "ckpt", "t0": 710.0,
               "t1": 712.0, "tid": 3, "tn": "ckpt-commit-last"}]
    out = []
    for order in ((0, 1), (1, 0)):
        run = str(tmp_path / f"run{order[0]}{order[1]}")
        os.makedirs(run)
        _write_clock_epoch(run, wall=[5000.0, 6000.0],
                           mono=[100.0, 700.0])
        for rank in order:
            _write_rank_file(run, rank, spans0 if rank == 0 else spans1)
        path = trace_lib.write_merged(run)
        with open(path, "rb") as f:
            out.append(f.read())
    assert out[0] == out[1]


def test_merge_places_each_requeue_attempt_on_its_own_clock(tmp_path):
    """A requeued run APPENDS to the same per-rank file: each attempt
    writes its own header, and its monotonic origin differs per boot.
    The merge must place every segment via ITS OWN header pair — a
    span from attempt 1 must not ride attempt 2's clock (it would land
    hours off after a reboot)."""
    run = str(tmp_path)
    path = trace_lib.trace_path(run, 0)
    os.makedirs(trace_lib.trace_dir(run), exist_ok=True)
    lines = [
        # Attempt 1: mono origin ~100, wall 1000 at mono 100.
        json.dumps({"event": "header", "schema": 1, "rank": 0,
                    "pid": 10, "mode": "phases",
                    "clock": {"mono": 100.0, "wall": 1000.0}}),
        json.dumps({"n": "dispatch", "ph": "X", "c": "phase",
                    "t0": 110.0, "t1": 111.0, "tid": 1,
                    "tn": "MainThread"}),
        # Attempt 2 (post-reboot): mono origin RESET to ~5, wall 2000.
        json.dumps({"event": "header", "schema": 1, "rank": 0,
                    "pid": 11, "mode": "phases",
                    "clock": {"mono": 5.0, "wall": 2000.0}}),
        json.dumps({"n": "dispatch", "ph": "X", "c": "phase",
                    "t0": 10.0, "t1": 11.0, "tid": 1,
                    "tn": "MainThread"}),
    ]
    with open(path, "w") as f:
        f.write("\n".join(lines) + "\n")
    segments = trace_lib.read_trace_segments(path)
    assert [len(s) for _h, s in segments] == [1, 1]
    obj = merge(run)
    assert validate_chrome_trace(obj) == []
    assert obj["otherData"]["attempts"] == {"0": 2}
    xs = sorted((ev["ts"] for ev in obj["traceEvents"]
                 if ev["ph"] == "X"))
    # Attempt 1's span at wall 1010, attempt 2's at wall 2005 —
    # 995s apart on the merged timeline, in order (attempt 2's span
    # would land at wall ~1905 BEFORE attempt 1's epoch-1 spans if it
    # were mapped through attempt 1's pair, or attempt 1's at ~115s
    # through attempt 2's).
    assert xs[0] == pytest.approx(0.0, abs=1.0)
    assert xs[1] == pytest.approx(995.0 * 1e6, rel=1e-9)


def test_merge_raises_without_trace_files(tmp_path):
    with pytest.raises(FileNotFoundError, match="--trace"):
        merge(str(tmp_path))


def test_chrome_trace_validator_rejects_malformed():
    good = {"traceEvents": [
        {"ph": "M", "name": "process_name", "pid": 0, "tid": 0,
         "args": {"name": "rank 0"}},
        {"ph": "X", "name": "a", "pid": 0, "tid": 0, "ts": 1.0,
         "dur": 2.0},
        {"ph": "i", "name": "b", "pid": 0, "tid": 0, "ts": 1.0,
         "s": "t"}]}
    assert validate_chrome_trace(good) == []
    assert validate_chrome_trace([]) != []
    assert validate_chrome_trace({"traceEvents": "nope"}) != []
    bad_events = [
        {"ph": "Z", "name": "a", "pid": 0, "tid": 0, "ts": 1.0},
        {"ph": "X", "name": "a", "pid": 0, "tid": 0, "ts": 1.0},
        {"ph": "X", "name": "a", "pid": 0, "tid": 0, "ts": -5.0,
         "dur": 1.0},
        {"ph": "X", "name": 7, "pid": 0, "tid": 0, "ts": 1.0,
         "dur": 1.0},
        {"ph": "i", "name": "a", "pid": 0, "tid": 0, "ts": 1.0,
         "s": "q"},
        {"ph": "X", "name": "a", "pid": "zero", "tid": 0, "ts": 1.0,
         "dur": 1.0},
    ]
    for ev in bad_events:
        assert validate_chrome_trace({"traceEvents": [ev]}) != [], ev


def test_merge_keeps_recycled_thread_idents_apart(tmp_path):
    """The OS recycles raw thread idents across short-lived committer
    threads: two spans sharing a raw tid under DIFFERENT thread names
    must land on two Perfetto rows, each with its own thread_name."""
    run = str(tmp_path)
    _write_clock_epoch(run, wall=[5000.0], mono=[100.0])
    _write_rank_file(run, 0, [
        {"n": "ckpt/commit", "ph": "X", "c": "ckpt", "t0": 110.0,
         "t1": 111.0, "tid": 777, "tn": "ckpt-commit-last"},
        {"n": "ckpt/commit", "ph": "X", "c": "ckpt", "t0": 120.0,
         "t1": 121.0, "tid": 777, "tn": "ckpt-commit-best"}],
        clock={"mono": 50.0, "wall": 4950.0})
    obj = merge(run)
    assert validate_chrome_trace(obj) == []
    names = {(ev["tid"]): (ev.get("args") or {}).get("name")
             for ev in obj["traceEvents"]
             if ev["ph"] == "M" and ev["name"] == "thread_name"}
    assert sorted(names.values()) == ["ckpt-commit-best",
                                      "ckpt-commit-last"]
    tids = {ev["tid"] for ev in obj["traceEvents"] if ev["ph"] == "X"}
    assert len(tids) == 2


def test_top_spans_text_names_the_longest(tmp_path):
    run = str(tmp_path)
    _write_clock_epoch(run, wall=[5000.0], mono=[100.0])
    _write_rank_file(run, 0, [
        {"n": "quick", "ph": "X", "t0": 110.0, "t1": 110.1, "tid": 1,
         "tn": "MainThread"},
        {"n": "the-stall", "ph": "X", "t0": 111.0, "t1": 119.0,
         "tid": 1, "tn": "MainThread"}])
    txt = trace_lib.top_spans_text(merge(run), 1)
    assert "the-stall" in txt and "quick" not in txt


def test_trace_cli_merges_and_reports(tmp_path):
    run = str(tmp_path)
    _write_clock_epoch(run, wall=[5000.0, 6000.0], mono=[100.0, 700.0])
    _write_rank_file(run, 0, [{"n": "dispatch", "ph": "X",
                               "c": "phase", "t0": 110.0, "t1": 111.0,
                               "tid": 1, "tn": "MainThread"}])
    _write_rank_file(run, 1, [{"n": "eval", "ph": "X", "c": "phase",
                               "t0": 710.0, "t1": 713.0, "tid": 1,
                               "tn": "MainThread"}])
    proc = subprocess.run(
        [sys.executable, "-m", "imagent_tpu.telemetry", "trace", run,
         "--top", "2"],
        capture_output=True, text=True, timeout=120, cwd=REPO_ROOT)
    assert proc.returncode == 0, proc.stderr + proc.stdout
    assert "clock skew: max 1000.0s" in proc.stdout, proc.stdout
    assert "eval" in proc.stdout  # the --top table
    merged = os.path.join(run, "trace", "trace.json")
    assert os.path.isfile(merged)
    with open(merged) as f:
        assert validate_chrome_trace(json.load(f)) == []
    # No trace files: loud exit 2, not an empty trace.json.
    proc = subprocess.run(
        [sys.executable, "-m", "imagent_tpu.telemetry", "trace",
         str(tmp_path / "empty")],
        capture_output=True, text=True, timeout=120, cwd=REPO_ROOT)
    assert proc.returncode == 2, proc.stdout


def test_summarize_gains_trace_columns(tmp_path):
    """An epoch record carrying a trace summary grows the spans/drop
    columns and the top-span names; an untraced log keeps the exact
    pre-trace table (its golden test lives in test_health.py)."""
    from imagent_tpu.telemetry.__main__ import summarize
    rec = {"event": "epoch", "schema": 1, "epoch": 0, "wall_s": 10.0,
           "goodput": 0.9, "phases": {"input_wait": 1.0},
           "step_ms": {"p95_ms": 12.0}, "counters": {},
           "trace": {"spans": 42, "dropped": 1,
                     "top": [["dispatch", 8.1], ["eval", 0.7]]}}
    run = str(tmp_path)
    with open(os.path.join(run, "telemetry.jsonl"), "w") as f:
        f.write(json.dumps(rec) + "\n")
    out = summarize(run)
    assert "spans" in out and "drop" in out
    assert "     42" in out and "top[dispatch 8.1s, eval 0.7s]" in out
    # Untraced: no trace columns.
    del rec["trace"]
    with open(os.path.join(run, "telemetry.jsonl"), "w") as f:
        f.write(json.dumps(rec) + "\n")
    out = summarize(run)
    assert "spans" not in out and "top[" not in out


# ------------------------------------------------------- engine drills

def _cfg(tmp_path, **kw):
    from imagent_tpu.config import Config
    base = dict(backend="cpu", arch="resnet18", image_size=16, num_classes=4,
                batch_size=4, epochs=2, lr=0.05, dataset="synthetic",
                synthetic_size=128, workers=0, bf16=False, log_every=0,
                seed=0, save_model=True,
                log_dir=str(tmp_path / "tb"),
                ckpt_dir=str(tmp_path / "ck"))
    base.update(kw)
    return Config(**base)


def test_engine_validates_trace_flags_upfront(tmp_path):
    from imagent_tpu.engine import run
    with pytest.raises(ValueError, match="--trace must be one of"):
        run(_cfg(tmp_path, trace="bogus"))
    with pytest.raises(ValueError, match="--trace-buffer"):
        run(_cfg(tmp_path, trace="phases", trace_buffer=0))
    with pytest.raises(ValueError, match="--no-telemetry"):
        run(_cfg(tmp_path, trace="phases", telemetry=False))


def test_cli_flags_parse():
    from imagent_tpu.config import parse_args
    cfg = parse_args(["--trace", "steps", "--trace-buffer", "512"])
    assert cfg.trace == "steps" and cfg.trace_buffer == 512
    assert parse_args([]).trace == "off"


def test_engine_trace_off_means_zero_files(tmp_path):
    from imagent_tpu.engine import run
    result = run(_cfg(tmp_path, epochs=1, save_model=False))
    assert result["rollbacks"] == 0
    assert not os.path.exists(trace_lib.trace_dir(str(tmp_path
                                                      / "tb")))
    assert trace_lib.active() is None


def test_engine_trace_steps_e2e_consistency_and_merge(tmp_path):
    """The single-host acceptance drill, in steps mode: per-step
    dispatch spans (step attrs), phase spans summing to within 5% of
    wall of the accountant, ckpt + data subsystems present, per-epoch
    trace summaries in the records, and a schema-valid merge."""
    from imagent_tpu.engine import run
    from imagent_tpu.telemetry import read_events
    result = run(_cfg(tmp_path, trace="steps", eval_every=1,
                      keep_last_k=1))
    assert result["rollbacks"] == 0
    assert trace_lib.active() is None  # engine deactivated on exit
    hdr, spans = read_trace(trace_lib.trace_path(str(tmp_path / "tb"),
                                                 0))
    assert hdr["mode"] == "steps"
    # 128 imgs / global batch 32 (8 fake devices) = 4 steps/epoch x 2:
    # every dispatch is its own span with its step attr.
    dispatches = [sp for sp in spans
                  if sp["n"] in ("dispatch", "compile")
                  and sp.get("c") == trace_lib.PHASE_CAT]
    assert len(dispatches) == 8, len(dispatches)
    steps = sorted((sp.get("a") or {}).get("step", -1)
                   for sp in dispatches)
    assert steps == [0, 0, 1, 1, 2, 2, 3, 3], steps
    names = {sp["n"] for sp in spans}
    assert {"step_drain", "eval", "checkpoint"} <= names, names
    assert "ckpt/snapshot" in names and "ckpt/commit" in names, names
    assert "data/stage" in names, names
    # Consistency against the accountant (the bench-smoke gate's
    # assertion, here in steps mode).
    epochs = [e for e in read_events(str(tmp_path / "tb"
                                         / "telemetry.jsonl"))
              if e["event"] == "epoch"]
    acct = sum(v for rec in epochs
               for k, v in rec["phases"].items() if k != "host_other")
    wall = sum(rec["wall_s"] for rec in epochs)
    traced = sum(phase_span_seconds(spans).values())
    assert abs(traced - acct) <= 0.05 * wall, (traced, acct, wall)
    for rec in epochs:
        assert rec["trace"]["spans"] > 0 and \
            rec["trace"]["dropped"] == 0, rec["trace"]
        assert rec["clock"]["max_skew_s"] == 0.0  # single host
    obj = merge(str(tmp_path / "tb"))
    assert validate_chrome_trace(obj) == []
    assert obj["otherData"]["skew_corrected"] == {"0": True}


def test_fatal_exit_79_flushes_trace(tmp_path):
    """The rollback-give-up (79) ramp — the same drill that pins the
    flight-recorder flush — must land the span file too, ending at
    the death: recovery spans from the replays included."""
    from imagent_tpu.engine import run
    from imagent_tpu.resilience import faultinject
    try:
        with pytest.raises(RuntimeError, match="persisted through"):
            run(_cfg(tmp_path, save_model=False, epochs=50,
                     faults="nan-grads:times=1000", max_bad_steps=2,
                     trace="phases"))
    finally:
        faultinject.reset()
    hdr, spans = read_trace(trace_lib.trace_path(str(tmp_path / "tb"),
                                                 0))
    assert hdr is not None and spans
    names = {sp["n"] for sp in spans}
    assert "recovery" in names, names  # the rollback attempts
    assert "dispatch" in names or "compile" in names, names


def test_fatal_86_ramp_flushes_trace_via_on_fatal(tmp_path):
    """Mechanism drill for the watchdog-86 / deadman-87 hard-exit
    threads: the engine wires PodHeartbeat.on_fatal to flush the span
    rings before the tombstone lands, so a tombstone() call from ANY
    fatal ramp durably flushes the trace and still flushes the flight
    recorder it referenced."""
    from imagent_tpu.resilience import exitcodes
    from imagent_tpu.resilience.deadman import PodHeartbeat
    from imagent_tpu.telemetry import flightrec as flightrec_lib
    from imagent_tpu.telemetry.flightrec import FlightRecorder

    rec = TraceRecorder(str(tmp_path), 0, buffer=16)
    trace_lib.activate(rec)
    rec.complete("dispatch", 0.0, 1.0, cat="phase")
    fr = FlightRecorder(str(tmp_path), 0)
    fr.record({"step": 1, "bad": False})
    flightrec_lib.activate(fr)
    pod = PodHeartbeat(str(tmp_path), 0, 2, deadline_secs=5.0)

    # The engine's wiring (engine.run), reproduced verbatim.
    def _pod_fatal(reason, exit_code, detail=""):
        trace_lib.flush_active(fsync=True)
        return flightrec_lib.flush_active(reason, exit_code,
                                          detail=detail)

    pod.on_fatal = _pod_fatal
    try:
        assert pod.tombstone("watchdog-hard-exit",
                             exitcodes.WATCHDOG_HARD_EXIT,
                             detail="drill")
    finally:
        flightrec_lib.deactivate()
    hdr, spans = read_trace(trace_lib.trace_path(str(tmp_path), 0))
    assert hdr is not None and [sp["n"] for sp in spans] == ["dispatch"]
    assert os.path.isfile(os.path.join(str(tmp_path),
                                       "flightrec.0.json"))
