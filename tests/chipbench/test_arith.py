"""Metric arithmetic of the benchmark: window rate and p95 over all
intervals, operations and convolution bytes of both configurations,
the peak table."""

import json
import os

import pytest

from chipbench import peaks as peaks_mod
from chipbench import run as run_mod
from chipbench.clock import Clock
from chipbench.families.resnet import arith, reference

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))


def config(name):
    with open(os.path.join(ROOT, "chipbench", "configs",
                           f"{name}.json")) as f:
        return json.load(f)


@pytest.mark.parametrize("name,gmacs,params", [
    ("resnet50", 4.089, 25_557_032),
    ("wide_resnet50_2", 11.398, 68_883_240),
])
def test_flops_and_parameter_count(name, gmacs, params):
    cfg = config(name)
    assert arith.forward_flops_per_image(cfg) / 2e9 == pytest.approx(
        gmacs, abs=2e-3)
    assert arith.train_flops_per_image(cfg) == \
        3 * arith.forward_flops_per_image(cfg)
    assert cfg["param_count"] == params
    # the plan's shapes give the published parameter count
    n = sum(c["k"] ** 2 * c["cin"] * c["cout"] + 2 * c["cout"]
            for c in reference.conv_plan(cfg))
    feat = cfg["stem_width"] * 8 * cfg["expansion"]
    assert n + feat * cfg["num_classes"] + cfg["num_classes"] == params


@pytest.mark.parametrize("name", ["resnet50", "wide_resnet50_2"])
def test_conv_passes_bytes_and_roofline(name):
    cfg = config(name)
    passes = arith.conv_passes(cfg, batch=256)
    plan = reference.conv_plan(cfg)
    assert len(passes) == 3 * len(plan) - 1  # no input-gradient to the image
    stem = next(p for p in passes if p["conv"] == "conv1"
                and p["pass"] == "forward")
    assert stem["flops"] == 2 * 49 * 3 * 64 * 112 * 112 * 256
    assert stem["bytes"] == (256 * 224 * 224 * 3 * 2 + 49 * 3 * 64 * 2
                             + 256 * 112 * 112 * 64 * 2)
    peak = peaks_mod.peaks("TPU v5 lite")
    least = arith.conv_roofline_seconds(cfg, 256, peak)
    assert least["seconds"] == pytest.approx(
        least["compute_bound_s"] + least["bandwidth_bound_s"])
    flops = sum(p["flops"] for p in passes)
    assert least["seconds"] >= flops / peak["bf16_flops_per_s"]


def test_peak_lookup_fails_on_unknown_device():
    assert peaks_mod.peaks("TPU v5 lite")["bf16_flops_per_s"] == 197e12
    with pytest.raises(KeyError):
        peaks_mod.peaks("TPU v9 imaginary")
    with pytest.raises(KeyError):
        peaks_mod.peaks("_source")


def test_p95_is_over_all_intervals():
    assert run_mod.p95([1.0]) == 1.0
    assert run_mod.p95(list(range(1, 101))) == 95
    assert run_mod.p95(list(range(1, 21))) == 19
    # one stall among 40 steps is beyond the 95th percentile, two are not
    assert run_mod.p95([0.1] * 39 + [5.0]) == 0.1
    assert run_mod.p95([0.1] * 37 + [5.0] * 3) == 5.0


class TrainState:
    """What the clock looks for in the polling frame, by type name."""

    def __init__(self, params, opt_state):
        self.params, self.opt_state = params, opt_state


def test_clock_window_rate(monkeypatch):
    """Warm-up polls pass (the first four copy the polling frame's
    state and metric vector), the window opens at a poll and closes at
    the first poll ``seconds`` later; the rate is all steps over all
    the wall between the first and the last poll."""
    import time

    import numpy as np
    now = [100.0]
    monkeypatch.setattr(time, "perf_counter", lambda: now[0])
    clock = Clock(seconds=1.0, warmup_steps=5,
                  optimizer_memory=lambda opt: opt["trace"])
    stops = []
    for i in range(40):
        state = TrainState({"w": np.full(2, float(i))},  # noqa: F841
                           {"trace": {"w": np.full(2, 10.0 * i)}})
        metrics = np.array([6.0 * i, 0, 0, 2.0])  # noqa: F841
        stops.append(clock())
        if stops[-1]:
            break
        now[0] += 0.5 if i == 8 else 0.1  # one stall inside the window
    assert stops.index(True) == 5 + 7  # 6 x 0.1 + the 0.5 stall >= 1.0
    assert not any(stops[:-1])
    iv = clock.intervals_s()
    assert len(iv) == 7
    assert sum(iv) == pytest.approx(clock.t_close - clock.t_open)
    assert max(iv) == pytest.approx(0.5)
    assert len(iv) * 256 / (clock.t_close - clock.t_open) == \
        pytest.approx(7 * 256 / 1.1)
    got = clock.captured
    assert got["losses"] == [3.0, 6.0, 9.0]
    assert got["p0"]["w"][0] == 0.0 and got["p_end"]["w"][0] == 3.0
    assert got["opt1"]["w"][0] == 10.0


def test_clock_needs_warmup_past_the_followed_steps():
    with pytest.raises(ValueError):
        Clock(seconds=1.0, warmup_steps=3, optimizer_memory=None)
