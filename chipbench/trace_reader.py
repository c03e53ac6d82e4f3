"""Reduction of a ``jax.profiler`` trace (``*.xplane.pb``) to numbers.

Read with nothing but JAX (``jax.profiler.ProfileData``).  What this
runtime's trace looks like (looked at by hand, PERF.md section 3): one
plane ``/device:TPU:<n>`` per chip with the lines ``XLA Modules`` (one
event per executed program, e.g. ``jit_per_device_step(...)``), ``XLA
Ops`` (one event per executed HLO op, named by the instruction's whole
text: ``%fusion.7 = bf16[...] fusion(...), kind=kOutput, calls=...``;
no category stat) and ``Async XLA Ops`` (background copies, not
counted as busy); and one plane ``/host:CPU`` whose thread lines hold
millions of runtime events and the ``TraceAnnotation`` spans -- the
benchmark's own ``chipbench_step``, poll to poll.

All times are nanoseconds on the trace's own clock; intervals are
``(start, end)`` pairs.
"""

from __future__ import annotations

import glob
import os
import re

DEVICE_PLANE = re.compile(r"^/device:TPU:(\d+)$")
OPS_LINE = "XLA Ops"
MODULES_LINE = "XLA Modules"
STEP_ANNOTATION = "chipbench_step"
COLLECTIVE = re.compile(
    r"all-reduce|all-gather|reduce-scatter|all-to-all|"
    r"collective-permute", re.I)


def find_xplane(log_dir: str) -> str | None:
    paths = sorted(glob.glob(os.path.join(
        log_dir, "plugins", "profile", "*", "*.xplane.pb")))
    return paths[-1] if paths else None


def load(path: str) -> list[dict]:
    """``[{"name", "lines": [{"name", "events": [ev]}]}]`` with
    ``ev = {"name", "start", "end", "stats"}``: of a device plane the
    op and module lines, of any other plane only the benchmark's
    annotations (a host plane holds millions of runtime events)."""
    from jax.profiler import ProfileData
    return _planes(ProfileData.from_file(path))


def load_text(text_proto: str) -> list[dict]:
    """The same from an XSpace in protobuf text format (tests)."""
    from jax.profiler import ProfileData
    return _planes(ProfileData.from_text_proto(text_proto))


def _planes(data) -> list[dict]:
    planes = []
    for plane in data.planes:
        is_device = bool(DEVICE_PLANE.match(plane.name))
        lines = []
        for line in plane.lines:
            if is_device and line.name not in (OPS_LINE, MODULES_LINE):
                continue
            events = []
            for ev in line.events:
                name = ev.name
                if not is_device and not name.startswith(STEP_ANNOTATION):
                    continue
                start = int(ev.start_ns)
                events.append({
                    "name": name, "start": start,
                    "end": start + int(ev.duration_ns),
                    "stats": ({} if is_device
                              else {k: v for k, v in ev.stats})})
            if events:
                lines.append({"name": line.name, "events": events})
        planes.append({"name": plane.name, "lines": lines})
    return planes


def device_line(planes: list[dict], which: str) -> dict[int, list[dict]]:
    """``{device ordinal: events of that line sorted by start}``."""
    out = {}
    for plane in planes:
        m = DEVICE_PLANE.match(plane["name"])
        if not m:
            continue
        for line in plane["lines"]:
            if line["name"] == which:
                out[int(m.group(1))] = sorted(
                    line["events"], key=lambda e: e["start"])
    return out


def device_ops(planes: list[dict]) -> dict[int, list[dict]]:
    return device_line(planes, OPS_LINE)


def step_modules(planes: list[dict]) -> dict[int, list[dict]]:
    """Per device the executions of the program that ran most often in
    the trace (the train step), in time order."""
    out = {}
    for dev, mods in device_line(planes, MODULES_LINE).items():
        if not mods:
            continue
        base = lambda e: e["name"].split("(")[0]  # noqa: E731
        names = [base(e) for e in mods]
        top = max(set(names), key=names.count)
        out[dev] = [e for e in mods if base(e) == top]
    return out


def step_annotations(planes: list[dict]) -> list[dict]:
    """The benchmark's poll-to-poll spans, in time order."""
    found = []
    for plane in planes:
        if DEVICE_PLANE.match(plane["name"]):
            continue
        for line in plane["lines"]:
            found += [e for e in line["events"]
                      if e["name"].startswith(STEP_ANNOTATION)]
    return sorted(found, key=lambda e: e["start"])


def short_name(name: str) -> str:
    """``%fusion.7 fusion kOutput`` from the instruction's whole text."""
    lhs, _, rhs = name.partition(" = ")
    if not rhs:
        return name[:80]
    op = re.search(r"\s([a-z][a-z0-9\-]*)\(", " " + rhs)
    kind = re.search(r"kind=(k\w+)", rhs)
    return " ".join(x for x in (lhs.strip()[:60],
                                op.group(1) if op else "",
                                kind.group(1) if kind else "") if x)


def opcode(ev: dict) -> str:
    """The instruction's opcode (``fusion``, ``convolution``,
    ``all-reduce``, ``copy`` ...), from its text or its name."""
    lhs, _, rhs = ev["name"].partition(" = ")
    m = re.search(r"\s([a-z][a-z0-9\-]*)\(", " " + rhs) if rhs else None
    if m:
        return m.group(1)
    return re.split(r"[.\d]", lhs.lstrip("%"), maxsplit=1)[0]


def is_collective(ev: dict) -> bool:
    return bool(COLLECTIVE.search(opcode(ev))
                or COLLECTIVE.search(ev["name"].partition(" = ")[0]))


def is_convolution(ev: dict) -> bool:
    """A convolution, bare or as the root of an output fusion (on this
    runtime XLA fuses a convolution's epilogue and the BatchNorm
    reductions into ``kind=kOutput`` fusions)."""
    return opcode(ev) == "convolution" or "kind=kOutput" in ev["name"]


def union(intervals: list[tuple[int, int]]) -> list[tuple[int, int]]:
    """Merged, sorted, non-overlapping intervals."""
    out: list[list[int]] = []
    for a, b in sorted(intervals):
        if b <= a:
            continue
        if out and a <= out[-1][1]:
            out[-1][1] = max(out[-1][1], b)
        else:
            out.append([a, b])
    return [(a, b) for a, b in out]


def clip(intervals, t0: int, t1: int) -> list[tuple[int, int]]:
    return [(max(a, t0), min(b, t1)) for a, b in intervals
            if min(b, t1) > max(a, t0)]


def total(intervals) -> int:
    return sum(b - a for a, b in intervals)


def subtract(a: list[tuple[int, int]],
             b: list[tuple[int, int]]) -> list[tuple[int, int]]:
    """The parts of ``a`` (merged) that no interval of ``b`` (merged)
    covers."""
    out = []
    j = 0
    for s, e in a:
        cur = s
        while j < len(b) and b[j][1] <= cur:
            j += 1
        k = j
        while k < len(b) and b[k][0] < e:
            if b[k][0] > cur:
                out.append((cur, b[k][0]))
            cur = max(cur, b[k][1])
            k += 1
        if cur < e:
            out.append((cur, e))
    return out


def gaps(busy: list[tuple[int, int]], t0: int,
         t1: int) -> list[tuple[int, int]]:
    return subtract([(t0, t1)], busy)


def reduce_window(ops_by_device: dict[int, list[dict]], t0: int, t1: int,
                  top: int = 10) -> dict:
    """Busy time, op table, convolution and collective time of the
    window ``[t0, t1)`` on every device."""
    per_device = {}
    for dev, ops in ops_by_device.items():
        inside = [e for e in ops if e["end"] > t0 and e["start"] < t1]
        iv = lambda evs: union(clip(  # noqa: E731
            [(e["start"], e["end"]) for e in evs], t0, t1))
        busy = iv(inside)
        coll = iv([e for e in inside if is_collective(e)])
        compute = iv([e for e in inside if not is_collective(e)])
        table: dict[str, int] = {}
        for e in inside:
            d = min(e["end"], t1) - max(e["start"], t0)
            key = short_name(e["name"])
            table[key] = table.get(key, 0) + d
        per_device[dev] = {
            "busy_ns": total(busy),
            "busy": busy,
            "conv_ns": total(iv([e for e in inside
                                 if is_convolution(e)])),
            "collective_ns": total(coll),
            "exposed_collective_ns": total(subtract(coll, compute)),
            "ops": sorted(table.items(), key=lambda kv: -kv[1])[:top],
            "n_ops": len(inside),
        }
    return per_device
