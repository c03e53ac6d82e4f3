"""Goodput accounting: partition epoch wall time into named phases.

Of every wall-clock second an epoch spends, how many bought training
progress?  The accountant answers that without a profiler trace: the
engine attributes measured host durations to a fixed phase taxonomy
and the residual (Python overhead the engine does not bracket — stop
polls, logging, loop bookkeeping) lands in ``host_other``, so the
phases always sum to the measured wall time exactly.

Phase taxonomy (``PHASES``):

* ``compile``    — step dispatches that blocked on trace+compile (the
  first step of a geometry, and any retrace).  Classified by the
  dispatch-duration threshold: an async dispatch returns in
  microseconds, a compiling one blocks for seconds — there is nothing
  in between on a steady pipeline.
* ``dispatch``   — non-compiling step dispatches (host side of useful
  training work; the device computes under them).
* ``step_drain`` — the host waiting for the device to retire
  dispatched steps (``engine._LaggedMetrics.wait_s``): every read of
  the lagged metric frontier plus the epoch-end tail drain. Dispatch
  is asynchronous, so on a device-bound run THIS is where the epoch
  goes — the device side of useful training work; where the host is
  the bottleneck the reads find their vectors ready and it is ~0.
* ``input_wait`` — step loop blocked on the staging queue
  (``data/prefetch.py::PrefetchStats.wait_s``).
* ``eval``       — validation epochs.
* ``checkpoint`` — blocking portion of checkpoint saves (the host
  snapshot; the async commit overlaps training and is deliberately not
  charged here).
* ``recovery``   — resilience events: rollback restores, fallback
  walks.
* ``host_other`` — the residual (never negative).

Overlapped phases (``OVERLAP_PHASES``) account for work that runs
CONCURRENTLY with the wall partition above — today the async
checkpoint committer thread (``ckpt_commit_async``). They are tracked
separately and are NOT part of the wall sum: adding hidden-behind-
compute seconds into a partition that must sum to wall would double
count the very overlap the async path buys. The epoch record carries
them under ``overlap``.

``goodput`` = (compile-free step work) / wall =
``(dispatch + step_drain) / wall`` — the fraction of the epoch that
bought optimizer progress. A host-side LOWER bound: while the step
loop sits in ``input_wait`` the chip may still be computing the step
dispatched before, and the host cannot see it — on an input-bound run
(the v5e chip smoke with host-generated synthetic data) goodput reads
near 0 while the chip is busy for most of each input wait. Device busy
and idle shares come from a profiler trace, not from this partition.

This module is imported per training step (via ``TelemetrySession``)
and therefore must stay jax-free: pure host arithmetic on floats, no
device syncs (tested by ``tests/test_telemetry.py``).
"""

from __future__ import annotations

import time

PHASES = ("compile", "dispatch", "step_drain", "input_wait", "eval",
          "checkpoint", "recovery", "host_other")

# Work that overlaps the wall partition (background threads) — reported
# alongside the phases but excluded from the sum-to-wall invariant.
OVERLAP_PHASES = ("ckpt_commit_async",)

# A step dispatch is asynchronous (microseconds); one that blocks this
# long was compiling/retracing.  Conservative: a genuinely slow host
# misattributing one dispatch to `compile` costs nothing downstream.
# Known caveat: the CPU backend executes programs synchronously
# inside dispatch, so a CPU run of a large model attributes steady
# steps to `compile` — on the TPU (the platform this accounts for,
# checked by chip_smoke.py) dispatch returns in well under the
# threshold, and either way the phases still sum to the measured wall.
COMPILE_THRESHOLD_S = 0.5


class GoodputAccountant:
    """Per-epoch phase accumulator with an injectable clock (tests)."""

    def __init__(self, compile_threshold_s: float = COMPILE_THRESHOLD_S):
        self.compile_threshold_s = float(compile_threshold_s)
        self._acc: dict[str, float] = {}
        self._overlap: dict[str, float] = {p: 0.0 for p in OVERLAP_PHASES}
        self._t0: float | None = None

    def begin_epoch(self, now: float | None = None) -> None:
        self._acc = {p: 0.0 for p in PHASES}
        self._overlap = {p: 0.0 for p in OVERLAP_PHASES}
        self._t0 = time.perf_counter() if now is None else now

    def add(self, phase: str, seconds: float) -> None:
        if phase not in self._acc:
            raise ValueError(f"unknown phase {phase!r} (taxonomy: "
                             f"{', '.join(PHASES)})")
        self._acc[phase] += float(seconds)

    def add_overlapped(self, phase: str, seconds: float) -> None:
        """Attribute background-thread work that ran concurrently with
        the wall partition (not summed into it — see module docstring)."""
        if phase not in self._overlap:
            raise ValueError(f"unknown overlapped phase {phase!r} "
                             f"(taxonomy: {', '.join(OVERLAP_PHASES)})")
        self._overlap[phase] += float(seconds)

    def overlapped(self) -> dict[str, float]:
        return dict(self._overlap)

    def add_dispatch(self, seconds: float) -> str:
        """Attribute one step dispatch; returns the phase it landed in."""
        phase = ("compile" if seconds >= self.compile_threshold_s
                 else "dispatch")
        self._acc[phase] += float(seconds)
        return phase

    def finish(self, now: float | None = None
               ) -> tuple[float, dict[str, float], float]:
        """Close the epoch: ``(wall_s, phases, goodput)``.

        ``phases['host_other']`` is the unbracketed residual, clamped
        at zero (a double-counted bracket can push the named sum past
        the wall; the epoch record keeps the raw sum so the telemetry
        test catches that as sum > wall)."""
        if self._t0 is None:
            raise RuntimeError("finish() before begin_epoch()")
        now = time.perf_counter() if now is None else now
        wall = max(now - self._t0, 0.0)
        phases = dict(self._acc)
        named = sum(v for k, v in phases.items() if k != "host_other")
        phases["host_other"] = max(wall - named, 0.0)
        useful = phases["dispatch"] + phases["step_drain"]
        goodput = min(useful / wall, 1.0) if wall > 0 else 0.0
        self._t0 = None
        return wall, phases, goodput
