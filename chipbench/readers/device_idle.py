"""Idle share of the fullest device in percent: one less the device's
busy time per traced step (from the trace) over the median untraced
poll-to-poll interval of the same run (the window's steps before the
profiler started).  The raw share inside the traced steps is not used:
the profiler slows the host, which stretches a host-paced cell's
cadence by half (PERF.md section 3); ``device.busy_s`` and
``device.window_s`` of the result line are the raw ones."""

import statistics


def read(ctx: dict, args: dict):
    t = ctx["trace"]
    if t is None or not ctx["clean_intervals"]:
        return None
    busy = t["per_device"][t["fullest"]]["busy_ns"] / 1e9 / t["n_steps"]
    cadence = statistics.median(ctx["clean_intervals"])
    return 100.0 * (1.0 - busy / cadence)
