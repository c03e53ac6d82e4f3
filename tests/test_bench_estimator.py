"""bench.py estimator honesty: the order-statistic median confidence
interval and the spread-bounded sample rejection/retry loop that the
r18@448 run-to-run drift motivated. Pure-host helpers — no jax, no
device."""

import os
import sys

import numpy as np
import pytest

sys.path.insert(0, os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))

from bench import _median_ci, _robust_samples, _spread_pct  # noqa: E402


def test_median_ci_small_n_reports_honest_coverage():
    """n=5 cannot reach 95%: the full range is returned with its ACTUAL
    binomial coverage, 1 - 2/32 = 93.75% — the JSON self-explains
    instead of overclaiming."""
    lo, hi, cov = _median_ci([3.0, 1.0, 2.0, 5.0, 4.0])
    assert (lo, hi) == (1.0, 5.0)
    assert cov == pytest.approx(93.75)


def test_median_ci_large_n_narrows_at_95():
    xs = [float(i) for i in range(1, 26)]  # n=25
    lo, hi, cov = _median_ci(xs)
    assert cov >= 95.0
    assert xs[0] < lo <= np.median(xs) <= hi < xs[-1]
    # Symmetric order statistics around the median.
    assert lo - xs[0] == xs[-1] - hi


def test_median_ci_degenerate_n1():
    assert _median_ci([2.0]) == (2.0, 2.0, 0.0)


def test_spread_pct():
    assert _spread_pct([1.0, 1.1, 0.9]) == pytest.approx(20.0)
    # Differencing noise swallowing the signal (median <= 0) is an
    # infinite spread, not a divide-by-zero.
    assert _spread_pct([-1.0, 0.0, 1.0]) == float("inf")


def test_robust_samples_rejects_outlier_and_retries():
    """One wild window out of five: the outlier is rejected, ONE fresh
    window replaces it, and the loop exits with in-band spread."""
    script = iter([1.0, 1.01, 0.99, 1.02, 5.0,  # round 1
                   1.0])                         # the one replacement
    samples, rejected, rounds = _robust_samples(
        lambda: next(script), pairs=5, max_spread_pct=8.0, max_rounds=3)
    assert (rejected, rounds) == (1, 2)
    assert len(samples) == 5
    assert _spread_pct(samples) <= 8.0
    assert 5.0 not in samples


def test_robust_samples_clean_run_single_round():
    samples, rejected, rounds = _robust_samples(
        iter([1.0, 1.01, 0.99, 1.02, 1.0]).__next__,
        pairs=5, max_spread_pct=8.0, max_rounds=3)
    assert (rejected, rounds) == (0, 1)


def test_robust_samples_persistent_noise_reported_not_hidden():
    """A genuine noise floor cannot be retried away: the loop stops at
    max_rounds and the caller publishes the honest residual spread (+
    the CI) instead of looping forever or silently truncating."""
    vals = iter([1.0, 2.0] * 50)
    samples, rejected, rounds = _robust_samples(
        lambda: next(vals), pairs=4, max_spread_pct=8.0, max_rounds=3)
    assert rounds == 3
    assert len(samples) == 4
    assert _spread_pct(samples) > 8.0
    assert rejected == 8  # every sample of rounds 1-2 was out of band


def test_input_pipeline_knee_stops_at_first_dip():
    """benchmarks/input_pipeline.find_knee: a later worker count that
    pops back above the bar (noise) must not certify linearity across
    a region that measurably broke it."""
    from benchmarks.input_pipeline import find_knee

    def cell(w, per_core):
        return {"workers": w, "img_s": per_core * w,
                "img_s_per_core": per_core}

    curve = [cell(1, 100.0), cell(2, 80.0), cell(4, 74.0),
             cell(8, 76.0)]
    knee = find_knee(curve, knee_frac=0.75)
    assert knee["knee_workers"] == 2  # 4 dipped below; 8 is noise
    assert not knee["linear_through_max_tested"]
    # Monotone-above-bar curve: knee = max tested.
    flat = [cell(1, 100.0), cell(2, 90.0), cell(4, 85.0)]
    knee = find_knee(flat, knee_frac=0.75)
    assert knee["knee_workers"] == 4
    assert knee["linear_through_max_tested"]
