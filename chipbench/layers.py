"""Per-layer metrics of a traced run: the device trace reduced once,
then one small reader per metric, found by the name in the metric's
file (``metrics/<metric>.json`` -> ``readers/<reader>.py``).

A reader that finds nothing to read returns None and the metric is
left out of the result line; it never returns 0 for a share.
"""

from __future__ import annotations

import importlib
import json
import os
import re
import statistics

from chipbench import trace_reader as tr

HERE = os.path.dirname(os.path.abspath(__file__))
PROGRAM_PHASES = ("input_wait", "dispatch", "compile", "step_drain")


def _poll_of(ev: dict) -> int | None:
    poll = ev["stats"].get("poll")
    if poll is None:
        m = re.search(r"poll=(\d+)", ev["name"])
        poll = m.group(1) if m else None
    return None if poll is None else int(poll)


def _name_gap(a_s: float, b_s: float, spans: list[dict]) -> str:
    """What the host was doing in ``[a_s, b_s)`` (perf_counter
    seconds): the program's phase span that covers most of it."""
    best, name = 0.0, "host_other"
    for sp in spans:
        if sp.get("n") not in PROGRAM_PHASES:
            continue
        ov = min(b_s, sp["t1"]) - max(a_s, sp["t0"])
        if ov > best:
            best, name = ov, sp["n"]
    return name


def load_device_trace(log_dir: str, clock, spans: list[dict]) -> dict | None:
    """The traced steps, reduced.  A traced step is one execution of
    the train-step program on the device (``XLA Modules``); the traced
    window runs from the first one's start to the last one's end.  The
    profiler slows the host while it runs (PERF.md section 3), so the
    spacing of the traced steps is not the untraced cadence: what is
    taken from here is the device's own time per step."""
    path = tr.find_xplane(log_dir)
    if path is None:
        return None
    planes = tr.load(path)
    ops = tr.device_ops(planes)
    mods = tr.step_modules(planes)
    if not ops or not mods:
        return None
    n_steps = min(len(m) for m in mods.values())
    t0 = min(m[0]["start"] for m in mods.values())
    t1 = max(m[-1]["end"] for m in mods.values())
    per = tr.reduce_window(ops, t0, t1)
    fullest = max(per, key=lambda d: per[d]["busy_ns"])
    # trace clock -> this process's perf_counter, through the polls
    anns = tr.step_annotations(planes)
    offs = [clock.all_stamps[p] * 1e9 - a["start"] for a in anns
            if (p := _poll_of(a)) is not None
            and p < len(clock.all_stamps)]
    off = statistics.median(offs) if offs else None
    gap_rows = []
    for a, b in sorted(tr.gaps(per[fullest]["busy"], t0, t1),
                       key=lambda g: g[0] - g[1])[:10]:
        name = ("unattributed" if off is None else _name_gap(
            (a + off) / 1e9, (b + off) / 1e9, spans))
        gap_rows.append([name, (b - a) / 1e9])
    return {
        "path": path, "n_steps": n_steps, "window_s": (t1 - t0) / 1e9,
        "busy_s": sum(p["busy_ns"] for p in per.values()) / len(per) / 1e9,
        "per_device": per, "fullest": fullest,
        "breakdown": {
            "device_ops": [[n, d / 1e9] for n, d in per[fullest]["ops"]],
            "idle_gaps": gap_rows},
    }


def read_all(wanted: list[dict], ctx: dict) -> dict:
    out = {}
    for m in wanted:
        with open(os.path.join(HERE, "metrics", f"{m['name']}.json")) as f:
            spec = json.load(f)
        reader = importlib.import_module(
            f"chipbench.readers.{spec['reader']}")
        value = reader.read(ctx, spec.get("args", {}))
        if value is not None:
            out[m["name"]] = {"value": float(value), "unit": m["unit"]}
    return out
