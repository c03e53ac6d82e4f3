"""Telemetry subsystem: goodput accounting, step-time percentiles,
pod-wide straggler detection, profiler windows.

The reference trainer's observability was four per-epoch TensorBoard
scalars; this package answers the operator questions those cannot:
*where did the wall-clock go* (``goodput``), *what does the step-time
distribution look like* (``sampler``), *which host is dragging the pod*
(``aggregate``), and *what exactly happened at step N*
(``profiler``) — with every answer queryable after the run from the
schema-versioned ``telemetry.jsonl`` event log (``events``).

``TelemetrySession`` is the engine-facing facade.  Contract with the
engine's host-sync discipline (``engine._GUARD_LAG``): the per-step
surface — ``record_dispatch`` and ``profile_step`` — is pure host
arithmetic (the hot modules ``goodput``/``sampler`` never import jax);
the one collective (per-host counter allgather) and all I/O happen in
``epoch_end``, once per epoch, on pod-agreed paths.
"""

from __future__ import annotations

import time

from imagent_tpu.telemetry.aggregate import (
    CLOCK_SKEW_WARN_S, HOST_FIELDS, allgather_host_stats, clock_record,
    flag_stragglers, summarize_hosts,
)
from imagent_tpu.telemetry.events import (
    SCHEMA_VERSION, TelemetryWriter, read_events,
)
from imagent_tpu.telemetry.flightrec import FlightRecorder
from imagent_tpu.telemetry.goodput import (
    OVERLAP_PHASES, PHASES, GoodputAccountant,
)
from imagent_tpu.telemetry.health import HEALTH_FIELDS, HealthMonitor
from imagent_tpu.telemetry.profiler import (
    ProfilerSession, hbm_stats, parse_profile_at_step,
)
from imagent_tpu.telemetry.sampler import StepTimeSampler
from imagent_tpu.telemetry import trace as trace_mod

__all__ = [
    "PHASES", "OVERLAP_PHASES", "HOST_FIELDS", "HEALTH_FIELDS",
    "SCHEMA_VERSION", "CLOCK_SKEW_WARN_S", "GoodputAccountant",
    "HealthMonitor", "FlightRecorder",
    "StepTimeSampler", "TelemetryWriter", "TelemetrySession",
    "ProfilerSession", "allgather_host_stats", "clock_record",
    "flag_stragglers",
    "summarize_hosts", "hbm_stats", "parse_profile_at_step",
    "read_events",
]


class TelemetrySession:
    """One training run's telemetry state, driven by the engine.

    Per-epoch lifecycle: ``epoch_begin`` → (steps: ``record_dispatch``
    / ``profile_step``) → ``absorb_input`` → run-loop ``phase``/
    ``count`` attributions → ``epoch_end`` (the only collective).
    ``epoch_end`` must be reached by every process on every epoch-exit
    path — normal, rollback, preemption — all of which the engine
    decides pod-globally, so the allgather never splits.

    ``enabled=False`` (``--no-telemetry``) turns every method into a
    no-op INCLUDING the allgather — consistent across the pod because
    the flag comes from the shared config.
    """

    def __init__(self, cfg, is_master: bool, logger=None):
        self.enabled = bool(getattr(cfg, "telemetry", True))
        self.is_master = bool(is_master)
        self.logger = logger
        self.straggler_factor = float(
            getattr(cfg, "straggler_factor", 2.0))
        # --input-wait-alert: an epoch whose input-wait fraction of
        # wall exceeds this gets a WARN + event + status surface
        # (0 = off). Streak counts consecutive offending epochs.
        self.input_wait_alert = float(
            getattr(cfg, "input_wait_alert", 0.0))
        self._alert_streak = 0
        self.acct = GoodputAccountant()
        self.sampler = StepTimeSampler()
        self.writer = (TelemetryWriter(cfg.log_dir)
                       if self.enabled and self.is_master else None)
        # Profiler windows ride the session but answer to their own
        # flag: --profile-at-step works under --no-telemetry too (the
        # trace is its own artifact; only the jsonl note is lost).
        self.profiler = ProfilerSession(
            parse_profile_at_step(getattr(cfg, "profile_at_step", "")),
            cfg.log_dir, is_master)
        self.counters: dict[str, float] = {}
        self._h2d_bytes = 0.0
        self._max_wait_s = 0.0
        self._in_epoch = False
        # Model-health monitor (telemetry/health.py), installed by the
        # engine when --health-stats is on; its EWMA snapshot rides the
        # per-epoch record and the health_anomaly events land here.
        self.health = None
        # Static chip account (telemetry/chipacct.py), installed by
        # the engine after step-build capture; epoch_end derives the
        # per-epoch MFU sub-record from it + the goodput partition.
        self.chipacct = None
        # Warm-start stats (compilecache.py), installed by the engine
        # after the one-compile AOT startup: cache key, hit/miss/load
        # counters plus the LIVE fallback_steps counter — epoch_end
        # snapshots the dict so each record reflects its boundary.
        self.compilecache = None

    # ---- run lifecycle --------------------------------------------------

    def run_start(self, info: dict) -> None:
        if self.writer is not None:
            self.writer.write("run_start", info)

    def run_end(self, summary: dict) -> None:
        ev = self.profiler.close()
        if self.writer is not None:
            if ev is not None:
                self.writer.write("profile", {"action": ev,
                                              "reason": "run_end"})
            self.writer.write("run_end", summary)
            self.writer.close()

    # ---- epoch lifecycle ------------------------------------------------

    def epoch_begin(self) -> None:
        if not self.enabled:
            return
        self.acct.begin_epoch()
        self.sampler.epoch_reset()
        self.counters = {}
        self._h2d_bytes = 0.0
        self._max_wait_s = 0.0
        self._in_epoch = True

    def phase(self, name: str, seconds: float) -> None:
        """Attribute ``seconds`` of the current epoch to a phase.

        The same call doubles as the phase-boundary SPAN emission
        (``telemetry/trace.py``, cat ``phase``, endpoints ``now -
        seconds .. now``) — the accountant and the tracer read the one
        measurement, so the spans-vs-goodput consistency gate cannot
        drift."""
        if self.enabled and self._in_epoch:
            self.acct.add(name, seconds)
            if seconds > 0 and trace_mod.active() is not None:
                t1 = time.perf_counter()
                trace_mod.complete(name, t1 - seconds, t1,
                                   cat=trace_mod.PHASE_CAT)

    def overlap(self, name: str, seconds: float) -> None:
        """Attribute background work that overlapped the epoch (async
        checkpoint commits) — reported under ``overlap``, outside the
        sum-to-wall phase partition."""
        if self.enabled and self._in_epoch:
            self.acct.add_overlapped(name, seconds)

    def count(self, name: str, inc: float = 1) -> None:
        if self.enabled and self._in_epoch:
            self.counters[name] = self.counters.get(name, 0) + inc

    def gauge(self, name: str, value: float) -> None:
        """A per-epoch high-water gauge (kept as max, not summed) —
        e.g. the peak peer-heartbeat staleness the deadman observed,
        which creeping toward --peer-deadline-secs IS the early
        warning for a host about to be declared dead."""
        if self.enabled and self._in_epoch:
            self.counters[name] = max(
                float(self.counters.get(name, 0.0)), float(value))

    def health_anomaly(self, info: dict) -> None:
        """A divergence early-warning verdict (telemetry/health.py):
        written as a ``health_anomaly`` event. Reached only on the
        monitor's rate-limited emission schedule — the per-epoch
        ``health_anomalies`` counter is fed separately by the engine
        from the monitor's every-step totals, so epochs inside a
        standing anomaly streak still count correctly. Detection rides
        the REPLICATED metric vector, so every host reaches the same
        verdict on the same step — pure local bookkeeping here, no
        collective."""
        if self.writer is not None:
            self.writer.write("health_anomaly", info)

    def slo_breach(self, info: dict) -> None:
        """One SLO objective breached this epoch (telemetry/slo.py —
        the engine evaluates on the master, against the already
        pod-aggregated epoch record): written as an ``slo_breach``
        event plus a TB marker series. Detail (value, threshold,
        streak) rides the event; the status.json ``slo`` field carries
        the session's standing verdict."""
        if self.writer is not None:
            self.writer.write("slo_breach", info)
        if self.logger is not None:
            self.logger.slo_breach(int(info.get("epoch", 0)),
                                   str(info.get("objective", "?")))

    def compile_event(self, info: dict) -> None:
        """A post-warmup XLA recompile (telemetry/recompile.py): the
        ``compile_event`` record names the jitted function and the
        compile seconds — the forensic answer to a goodput dip the
        phase taxonomy could only file under compile/step_drain."""
        if self.writer is not None:
            self.writer.write("compile_event", info)

    def pod_resized(self, info: dict) -> None:
        """An elastic resize took effect (or a grow stop is about to
        re-form the pod): written as a ``pod_resized`` event carrying
        the world-size transition and the lr/grad-accum adjustment the
        fixed --global-batch contract implies, plus a TB marker. Local
        bookkeeping only — the resize itself was already pod-agreed
        (the committed roster / the any-reduced grow stop)."""
        if self.writer is not None:
            self.writer.write("pod_resized", info)
        if self.logger is not None:
            self.logger.pod_resized(int(info.get("epoch", 0)),
                                    int(info.get("to_processes", 0)))

    def pod_degraded(self, info: dict) -> None:
        """The deadman's detection verdict: a peer died and this run is
        exiting retryable. Written as a ``pod_degraded`` event (the
        post-mortem record: who died, how it was detected, how stale
        the heartbeat was vs the deadline) plus a TB marker scalar.
        Out-of-band by construction — called from the degraded exit
        ramp, where no collective may run; pure local file writes."""
        if self.writer is not None:
            self.writer.write("pod_degraded", info)
        if self.logger is not None:
            self.logger.pod_degraded(int(info.get("epoch", 0)))

    # ---- per-step surface (host arithmetic only — no jax) ---------------

    def record_dispatch(self, seconds: float,
                        step: int | None = None) -> None:
        """One train-step dispatch returned after ``seconds``. With a
        tracer active, the same measurement becomes a ``dispatch`` /
        ``compile`` phase span: one span per step in ``steps`` mode
        (tagged with ``step``), coalesced into dispatch WINDOWS in
        ``phases`` mode (a window breaks at any interleaved span on
        this thread — a recorded input wait, a compile, a boundary
        phase)."""
        if self.enabled and self._in_epoch:
            phase = self.acct.add_dispatch(seconds)
            self.sampler.mark()
            rec = trace_mod.active()
            if rec is not None:
                t1 = time.perf_counter()
                if rec.mode == "steps" and step is not None:
                    rec.complete(phase, t1 - seconds, t1,
                                 cat=trace_mod.PHASE_CAT, step=step)
                else:
                    rec.complete(phase, t1 - seconds, t1,
                                 cat=trace_mod.PHASE_CAT, merge=True)

    def profile_step(self, global_step: int) -> None:
        """Drive the profiler window; called before each dispatch."""
        ev = self.profiler.on_step(global_step)
        if ev is not None and self.writer is not None:
            self.writer.write("profile", {
                "action": ev, "global_step": int(global_step),
                "window": {"start": self.profiler.window.start,
                           "steps": self.profiler.window.steps}})

    def absorb_input(self, stats) -> None:
        """Fold a ``PrefetchStats`` into the epoch (train loop only)."""
        if self.enabled and self._in_epoch:
            self.acct.add("input_wait", stats.wait_s)
            self._h2d_bytes += float(stats.bytes_staged)
            self._max_wait_s = max(self._max_wait_s,
                                   getattr(stats, "max_wait_s", 0.0))

    def absorb_step_wait(self, seconds: float) -> None:
        """Fold the epoch's summed waits on in-flight step results
        (``engine._LaggedMetrics.wait_s``) into ``step_drain``; their
        spans were emitted per wait, like the input waits'."""
        if self.enabled and self._in_epoch:
            self.acct.add("step_drain", seconds)

    def absorb_eval_input(self, stats) -> None:
        """Fold an EVAL epoch's ``PrefetchStats`` — strictly partitioned
        from the train-side ``absorb_input``: eval wait rides the
        ``eval_input_wait_s``/``eval_h2d_mb`` counters (inside the
        ``eval`` goodput phase), NEVER the ``input_wait`` phase or the
        ``data/host_blocked_s`` TB series, whose alerting threshold
        (`--input-wait-alert`) must judge the train step loop alone.
        The partition is regression-tested (tests/test_telemetry.py::
        test_eval_input_partitioned_from_train + the offload drill in
        tests/test_offload.py)."""
        if self.enabled and self._in_epoch:
            self.count("eval_input_wait_s", stats.wait_s)
            self.count("eval_h2d_mb", float(stats.bytes_staged) / 1e6)

    # ---- epoch close (the one collective) -------------------------------

    def epoch_end(self, epoch: int, train_m: dict | None = None,
                  interrupted: bool = False) -> dict | None:
        if not (self.enabled and self._in_epoch):
            return None
        self._in_epoch = False
        if train_m and train_m.get("bad_steps"):
            self.counters["bad_steps"] = \
                self.counters.get("bad_steps", 0) \
                + int(train_m["bad_steps"])
        overlap = self.acct.overlapped()
        wall, phases, goodput = self.acct.finish()
        pcts = self.sampler.percentiles()
        local = {
            "input_wait_s": phases["input_wait"],
            "max_wait_s": self._max_wait_s,
            "dispatch_s": phases["dispatch"],
            "compile_s": phases["compile"],
            "step_p50_ms": pcts["p50_ms"],
            "step_p95_ms": pcts["p95_ms"],
            "step_p99_ms": pcts["p99_ms"],
            "h2d_mb": self._h2d_bytes / 1e6,
            "quarantined": self.counters.get("quarantined", 0),
            # The clock-offset pair, captured immediately before the
            # shared allgather (aggregate.HOST_FIELDS for semantics).
            "clock_wall_s": time.time(),
            "clock_mono_s": time.perf_counter(),
        }
        matrix = allgather_host_stats(local)  # collective (per epoch)
        clock = clock_record(matrix)
        record = {
            "epoch": int(epoch),
            "wall_s": round(wall, 3),
            "goodput": round(goodput, 4),
            "phases": {k: round(v, 3) for k, v in phases.items()},
            "overlap": {k: round(v, 4) for k, v in overlap.items()},
            "step_ms": {k: round(v, 3) if isinstance(v, float) else v
                        for k, v in pcts.items()},
            "hosts": {"count": int(matrix.shape[0]),
                      "stats": summarize_hosts(matrix)},
            "stragglers": flag_stragglers(matrix,
                                          self.straggler_factor),
            "counters": {k: round(float(v), 3)
                         for k, v in sorted(self.counters.items())},
            "hbm": hbm_stats(),
            "clock": clock,
            "interrupted": bool(interrupted),
        }
        if self.health is not None:
            record["health"] = self.health.snapshot()
        if self.chipacct is not None:
            # Zero-step-cost MFU: achieved flops over the step loop's
            # seconds (dispatch + step_drain + input_wait) the
            # partition above already measured, against the static
            # account's peak.
            # Host floats only — the step loop never pays for this.
            from imagent_tpu.telemetry import chipacct as chipacct_mod
            perf = chipacct_mod.epoch_perf(
                self.chipacct, record["phases"],
                int(pcts.get("n", 0) or 0))
            if perf is not None:
                record["chipacct"] = perf
        if self.compilecache is not None:
            # Warm-start sub-record (an ADDITION, not a schema bump):
            # the startup counters are static for the attempt; the
            # fallback_steps counter is live, so snapshot per boundary.
            record["compilecache"] = dict(self.compilecache)
        tracer = trace_mod.active()
        if tracer is not None:
            # Epoch-boundary trace flush: drains every thread's ring
            # into trace.<rank>.jsonl and summarizes the chunk (span
            # count, drops, top names by busy time) into the epoch
            # record for `telemetry summarize`.
            record["trace"] = tracer.flush()
        if (self.is_master and clock["max_skew_s"] > CLOCK_SKEW_WARN_S
                and matrix.shape[0] > 1):
            wall_col = matrix[:, HOST_FIELDS.index("clock_wall_s")]
            print(f"WARNING: pod wall-clock skew "
                  f"{clock['max_skew_s']:.1f}s (host "
                  f"{int(wall_col.argmax())} fastest clock, host "
                  f"{int(wall_col.argmin())} slowest, measured at the "
                  "epoch-boundary sync point) — cross-rank log "
                  "timestamps are unreliable; fix NTP on the pod. The "
                  "trace merge corrects for this "
                  "(docs/OPERATIONS.md 'Reading a pod trace')",
                  flush=True)
        # Input-wait alerting (ROADMAP item 5's alerting clause): the
        # fraction is an epoch-long average, so one offending epoch IS
        # sustained starvation, not a burst; the streak counts how long
        # it has persisted. The pod straggler flags name the slow host
        # when ONE host is dragging (vs a pod-wide storage/offload
        # shortfall, where the flags stay empty and every host waits).
        alert = None
        if self.input_wait_alert > 0 and wall > 0:
            frac = phases["input_wait"] / wall
            if frac > self.input_wait_alert:
                self._alert_streak += 1
                col = matrix[:, HOST_FIELDS.index("input_wait_s")]
                worst = int(col.argmax())
                alert = {
                    "epoch": int(epoch),
                    "fraction": round(frac, 4),
                    "threshold": self.input_wait_alert,
                    "streak": self._alert_streak,
                    "wall_s": round(wall, 3),
                    "worst_host": worst,
                    "worst_host_wait_s": round(float(col[worst]), 3),
                    "stragglers": [
                        s for s in record["stragglers"]
                        if s["metric"] == "input_wait_s"],
                }
                record["input_wait_alert"] = alert
            else:
                self._alert_streak = 0
        if alert is not None and self.writer is not None:
            self.writer.write("input_wait_alert", alert)
        if self.is_master:
            if alert is not None:
                who = (f"host {alert['worst_host']} slowest "
                       f"({alert['worst_host_wait_s']}s)"
                       if record["hosts"]["count"] > 1 else
                       f"{alert['worst_host_wait_s']}s blocked")
                print(f"WARNING: INPUT-BOUND epoch {epoch + 1}: "
                      f"input_wait {alert['fraction']:.0%} of epoch "
                      f"wall (alert at "
                      f"{self.input_wait_alert:.0%}, streak "
                      f"{alert['streak']}) — {who}. Raise --workers, "
                      "add decode-offload hosts, or check storage "
                      "(docs/OPERATIONS.md 'Host CPU budget and "
                      "decode offload')", flush=True)
            if record["stragglers"]:
                names = ", ".join(
                    f"host {s['host']} {s['metric']} {s['value']} "
                    f"(pod median {s['median']})"
                    for s in record["stragglers"])
                print(f"STRAGGLER: {names} — exceeds "
                      f"{self.straggler_factor}x the pod median",
                      flush=True)
            if self.writer is not None:
                self.writer.write("epoch", record)
            if self.logger is not None:
                self.logger.telemetry(epoch, record,
                                      self.sampler.intervals_ms())
        return record
