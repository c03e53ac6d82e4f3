"""The trace reader: interval arithmetic, and the reduction of a trace
to busy/idle, op table, convolution and collective time and idle gaps,
on a hand-made trace with known numbers and on a small one recorded on
the chip (fixtures/)."""

import os

import pytest

from chipbench import layers
from chipbench import trace_reader as tr

HERE = os.path.dirname(os.path.abspath(__file__))
US = 1_000_000  # picoseconds in a microsecond


def _events(rows):
    return "\n".join(
        f"events {{ metadata_id: {mid} offset_ps: {a * US} "
        f"duration_ps: {(b - a) * US} {stats} }}"
        for mid, a, b, stats in rows)


def hand_made():
    """Two devices, three executions of the step program (0-100,
    100-200, 200-300 us) and three annotated polls 8, 9, 10 at the same
    times.  Device 0: conv 10-40, loop fusion 40-50, all-reduce 45-70
    (5 us under the fusion, 20 us exposed), conv 110-150, copy 260-290.
    Device 1: one conv 0-30."""
    dev0 = _events([(1, 10, 40, ""), (2, 40, 50, ""), (3, 45, 70, ""),
                    (1, 110, 150, ""), (4, 260, 290, "")])
    dev1 = _events([(1, 0, 30, "")])
    mods = _events([(5, 100 * i, 100 * (i + 1), "") for i in range(3)]
                   + [(6, 95, 96, "")])
    steps = _events([
        (1, 100 * i, 100 * (i + 1),
         f"stats {{ metadata_id: 1 int64_value: {8 + i} }}")
        for i in range(3)] + [(2, 0, 300, "")])
    names = {
        1: "%fusion.7 = bf16[8]{0:T(8)} fusion(bf16[8]{0} %p), "
           "kind=kOutput, calls=%fused_computation.7",
        2: "%fusion.9 = bf16[8]{0:T(8)} fusion(bf16[8]{0} %p), "
           "kind=kLoop, calls=%fused_computation.9",
        3: "%all-reduce.1 = f32[8]{0} all-reduce(f32[8]{0} %g), "
           "replica_groups={}",
        4: "%copy.3 = f32[8]{0} copy(f32[8]{0} %q)",
        5: "jit_per_device_step(123)", 6: "jit_multiply(9)"}
    meta = "\n".join(
        f"event_metadata {{ key: {k} value {{ id: {k} name: '{v}' }} }}"
        for k, v in names.items())
    return f"""
planes {{ id: 1 name: "/device:TPU:0"
  lines {{ id: 1 name: "XLA Modules" timestamp_ns: 5 {mods} }}
  lines {{ id: 2 name: "XLA Ops" timestamp_ns: 5 {dev0} }}
  lines {{ id: 3 name: "Async XLA Ops" timestamp_ns: 5 {dev0} }}
  {meta} }}
planes {{ id: 2 name: "/device:TPU:1"
  lines {{ id: 1 name: "XLA Modules" timestamp_ns: 5 {mods} }}
  lines {{ id: 2 name: "XLA Ops" timestamp_ns: 5 {dev1} }}
  {meta} }}
planes {{ id: 3 name: "/host:CPU"
  lines {{ id: 7 name: "python3" timestamp_ns: 5 {steps} }}
  event_metadata {{ key: 1 value {{ id: 1 name: "chipbench_step" }} }}
  event_metadata {{ key: 2 value {{ id: 2 name: "$pool.py:764 wait" }} }}
  stat_metadata {{ key: 1 value {{ id: 1 name: "poll" }} }} }}
"""


def test_interval_arithmetic():
    assert tr.union([(5, 9), (1, 3), (2, 4), (9, 9)]) == [(1, 4), (5, 9)]
    assert tr.total(tr.union([(0, 10), (5, 20)])) == 20
    assert tr.clip([(0, 10), (20, 30)], 5, 25) == [(5, 10), (20, 25)]
    assert tr.subtract([(0, 10), (20, 30)], [(5, 22), (25, 26)]) == \
        [(0, 5), (22, 25), (26, 30)]
    assert tr.subtract([(0, 10)], []) == [(0, 10)]
    assert tr.gaps([(2, 3), (5, 8)], 0, 10) == [(0, 2), (3, 5), (8, 10)]


def test_hand_made_trace_gives_known_numbers():
    planes = tr.load_text(hand_made())
    ops = tr.device_ops(planes)
    assert sorted(ops) == [0, 1] and len(ops[0]) == 5
    anns = tr.step_annotations(planes)
    assert [a["stats"]["poll"] for a in anns] == [8, 9, 10]
    t0, t1 = anns[0]["start"], anns[-1]["end"]
    assert t1 - t0 == 300_000  # ns
    per = tr.reduce_window(ops, t0, t1)
    d0 = per[0]
    assert d0["busy_ns"] == (30 + 30 + 40 + 30) * 1000
    assert d0["conv_ns"] == 70_000
    assert d0["collective_ns"] == 25_000
    assert d0["exposed_collective_ns"] == 20_000
    assert d0["ops"][0] == ("%fusion.7 fusion kOutput", 70_000)
    mods = tr.step_modules(planes)
    assert [len(m) for m in mods.values()] == [3, 3]
    assert all(e["name"].startswith("jit_per_device_step")
               for e in mods[0])
    # a host plane keeps only the benchmark's annotations
    assert [len(ln["events"]) for ln in planes[2]["lines"]] == [3]
    assert per[1]["busy_ns"] == 30_000
    assert [b - a for a, b in tr.gaps(d0["busy"], t0, t1)] == \
        [10_000, 40_000, 110_000, 10_000]
    # a window that cuts an op counts only what lies inside it
    cut = tr.reduce_window(ops, t0 + 20_000, t0 + 60_000)[0]
    assert cut["busy_ns"] == 40_000 and cut["conv_ns"] == 20_000


class _Clock:
    def __init__(self, stamps):
        self.all_stamps = stamps


def test_reduced_trace_feeds_the_readers(tmp_path, monkeypatch):
    """``load_device_trace`` on the hand-made trace: busy averaged over
    the chips, idle share and step time on the fullest, gaps named by
    the program's span that covers them."""
    from chipbench.readers import (
        allreduce_exposed, device_idle, device_step_ms,
    )
    planes = tr.load_text(hand_made())
    monkeypatch.setattr(tr, "find_xplane", lambda d: "x")
    monkeypatch.setattr(tr, "load", lambda p: planes)
    t_first = tr.step_annotations(planes)[0]["start"] / 1e9
    # polls 8..10 happened at perf_counter 50.0 + k * 100 us
    stamps = [0.0] * 8 + [50.0 + k * 1e-4 for k in range(3)]
    off = 50.0 - t_first
    spans = [{"n": "input_wait", "t0": off + t_first + 150e-6,
              "t1": off + t_first + 255e-6},
             {"n": "step_drain", "t0": off + t_first + 70e-6,
              "t1": off + t_first + 105e-6}]
    t = layers.load_device_trace("unused", _Clock(stamps), spans)
    assert t["n_steps"] == 3 and t["fullest"] == 0
    assert t["window_s"] == pytest.approx(300e-6)
    assert t["busy_s"] == pytest.approx((130e-6 + 30e-6) / 2)
    assert t["breakdown"]["device_ops"][0] == [
        "%fusion.7 fusion kOutput", pytest.approx(70e-6)]
    gaps = t["breakdown"]["idle_gaps"]
    assert gaps[0] == ["input_wait", pytest.approx(110e-6)]
    assert gaps[1] == ["step_drain", pytest.approx(40e-6)]
    assert gaps[2][0] == "host_other"
    ctx = {"trace": t, "clean_intervals": [50e-6, 65e-6, 90e-6]}
    assert device_step_ms.read(ctx, {}) == pytest.approx(0.130 / 3)
    assert device_idle.read(ctx, {}) == pytest.approx(
        100 * (1 - (130 / 3) / 65))
    assert allreduce_exposed.read(ctx, {}) == pytest.approx(0.020 / 3)
    # nothing to read gives nothing, never 0
    assert device_idle.read(dict(ctx, trace=None), {}) is None
    t["per_device"][0]["collective_ns"] = 0
    assert allreduce_exposed.read(ctx, {}) is None


def test_span_readers_clip_to_the_window():
    from chipbench.readers import span_ms_per_step, span_share

    spans = [{"n": "input_wait", "t0": 9.5, "t1": 10.5},
             {"n": "input_wait", "t0": 11.0, "t1": 11.25},
             {"n": "dispatch", "t0": 11.9, "t1": 12.4},
             {"n": "input_wait", "t0": 12.5, "t1": 13.0}]
    ctx = {"spans": spans, "t_open": 10.0, "t_close": 12.0, "wall": 2.0,
           "steps": 10}
    assert span_share.read(ctx, {"span": "input_wait"}) == \
        pytest.approx(37.5)
    assert span_ms_per_step.read(ctx, {"span": "dispatch"}) == \
        pytest.approx(10.0)
    assert span_share.read(ctx, {"span": "step_drain"}) == 0.0
    assert span_share.read(dict(ctx, spans=[]),
                           {"span": "input_wait"}) is None


FIXTURE = os.path.join(HERE, "fixtures",
                       "r50_b256_traced_steps.xplane.pb")


def test_recorded_trace_gives_known_numbers(monkeypatch):
    """Four traced steps of ResNet-50 / b256 on one v5e (my chip run,
    PR 23), cut down by tools/cut_xplane.py to the device's op and
    module lines and the benchmark's annotations."""
    from chipbench.readers import conv_roofline, device_step_ms
    from chipbench.peaks import peaks
    import json
    planes = tr.load(FIXTURE)
    mods = tr.step_modules(planes)[0]
    assert len(mods) == 4
    assert all(97_600_000 < m["end"] - m["start"] < 97_800_000
               for m in mods)
    anns = tr.step_annotations(planes)
    assert [a["stats"]["poll"] for a in anns] == [69, 70, 71]
    monkeypatch.setattr(tr, "find_xplane", lambda d: FIXTURE)
    # polls every 0.17 s on a clock 1000 s ahead of the trace's
    stamps = [1000.0 + anns[0]["start"] / 1e9 + 0.17 * (k - 69)
              for k in range(72)]
    spans = [{"n": "input_wait", "t0": 1000.7, "t1": 1002.6}]
    t = layers.load_device_trace("unused", _Clock(stamps), spans)
    assert t["n_steps"] == 4 and t["fullest"] == 0
    assert t["window_s"] == pytest.approx(2.928208507)
    assert t["busy_s"] == pytest.approx(0.389891779)
    d = t["per_device"][0]
    assert d["n_ops"] == 12552 and d["collective_ns"] == 0
    assert d["conv_ns"] == 317801525
    assert t["breakdown"]["device_ops"][0][0] == \
        "%convert_reduce_fusion fusion kOutput"
    assert len(t["breakdown"]["device_ops"]) == 10
    # the profiler's own stall between the first and the second step
    assert t["breakdown"]["idle_gaps"][0] == [
        "input_wait", pytest.approx(2.511107406)]
    with open(os.path.join(os.path.dirname(os.path.dirname(HERE)),
                           "chipbench", "configs", "resnet50.json")) as f:
        cfg = json.load(f)
    ctx = {"trace": t, "config": cfg, "peak": peaks("TPU v5 lite"),
           "mix": {"per_chip_batch": 256}}
    assert device_step_ms.read(ctx, {}) == pytest.approx(97.4729, abs=1e-3)
    share = conv_roofline.read(ctx, {})
    assert share == pytest.approx(62.775, abs=0.01) and share < 100
    ctx["peak"] = None
    assert conv_roofline.read(ctx, {}) is None
