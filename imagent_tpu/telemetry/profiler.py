"""Programmatic profiler windows and HBM telemetry.

``--profile-at-step N[:M]`` captures a ``jax.profiler`` trace for the
M global steps starting at step N — mid-run, exactly around the steps
you care about (steady state after warmup, the step where throughput
dips), instead of the old start-to-end ``--profile`` whose trace of a
90-epoch run is unloadably large and 99% steady-state repetition.

Resume-aware: the window is addressed in GLOBAL steps (epoch ×
steps/epoch + step), so a preempted-and-resumed run still profiles the
same steps; a resume that lands past the window skips it rather than
profiling the wrong steps.

HBM telemetry: ``hbm_stats()`` reads ``device.memory_stats()`` where
the PJRT runtime implements it (TPU does; CPU typically returns
nothing) — per-epoch high-water marks without a profiler trace.
"""

from __future__ import annotations

import dataclasses


@dataclasses.dataclass(frozen=True)
class ProfileWindow:
    start: int  # first global step inside the window
    steps: int  # window length in steps

    @property
    def stop(self) -> int:  # first global step past the window
        return self.start + self.steps


DEFAULT_WINDOW_STEPS = 10


def parse_profile_at_step(spec: str) -> ProfileWindow | None:
    """``"N[:M]"`` → ProfileWindow (M defaults to 10); ``""`` → None.

    Raises ValueError on anything else — the engine validates the flag
    before burning pod time."""
    spec = (spec or "").strip()
    if not spec:
        return None
    start_s, sep, steps_s = spec.partition(":")
    try:
        start = int(start_s)
        steps = int(steps_s) if sep else DEFAULT_WINDOW_STEPS
    except ValueError:
        raise ValueError(
            f"--profile-at-step must be N or N:M (integers), got "
            f"{spec!r}") from None
    if start < 0:
        raise ValueError(f"--profile-at-step start must be >= 0, got "
                         f"{start}")
    if steps < 1:
        raise ValueError(f"--profile-at-step window must be >= 1 step, "
                         f"got {steps}")
    return ProfileWindow(start, steps)


class ProfilerSession:
    """Drives jax.profiler start/stop from the step counter.

    ``on_step(global_step)`` is called once per step BEFORE its
    dispatch; it returns ``"start"`` / ``"stop"`` on the steps where
    the trace opened/closed (for the event log), else None.  The
    comparison is two ints — nothing on the per-step path touches the
    device."""

    def __init__(self, window: ProfileWindow | None, log_dir: str,
                 enabled: bool = True):
        self.window = window
        self.log_dir = log_dir
        self.enabled = enabled and window is not None
        self.active = False
        self.done = False

    def on_step(self, global_step: int) -> str | None:
        if not self.enabled or self.done:
            return None
        w = self.window
        if not self.active:
            if global_step >= w.stop:
                # Resumed past the window: never profile the wrong
                # steps; record it as skipped.
                self.done = True
                return None
            if global_step >= w.start:
                import jax
                jax.profiler.start_trace(self.log_dir)
                self.active = True
                return "start"
            return None
        if global_step >= w.stop:
            return self._stop()
        return None

    def _stop(self) -> str:
        import jax
        jax.profiler.stop_trace()
        self.active = False
        self.done = True
        return "stop"

    def close(self) -> str | None:
        """End-of-run cleanup: land a window still open (short final
        epoch) so the trace file is complete."""
        if self.active:
            return self._stop()
        return None


def hbm_stats() -> dict | None:
    """Per-device memory stats from the PJRT runtime, or None where
    unimplemented (CPU).  The headline keys report the first local
    device (the engine's process-local view; HBM is symmetric across a
    pod's chips); ``devices`` lists bytes in use / peak for EVERY local
    device, so a mesh whose work is not really spread shows up as a
    chip holding nothing."""
    import jax
    keep = ("bytes_in_use", "peak_bytes_in_use", "bytes_limit")
    per_device = []
    for d in jax.local_devices():
        try:
            stats = d.memory_stats()
        except Exception:
            return None
        if not stats:
            return None
        per_device.append({"id": int(d.id),
                           **{k: int(stats[k]) for k in keep
                              if k in stats}})
    out = {k: v for k, v in per_device[0].items() if k != "id"}
    if not out:
        return None
    out["devices"] = [{k: v for k, v in d.items() if k != "bytes_limit"}
                      for d in per_device]
    if out.get("bytes_limit"):
        # Peak-fraction gauge: the headroom number an operator tunes
        # batch size / remat / fused kernels against, without opening
        # a profiler trace.
        out["utilization"] = round(
            out.get("peak_bytes_in_use", out.get("bytes_in_use", 0))
            / out["bytes_limit"], 4)
    return out
