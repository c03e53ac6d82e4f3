"""The control and the planted faults, at a size a test run can hold:
the reference in the program's place with half of the batch left out,
with the exchange between chips left out, or with its state left
unchanged has to come out as not correct under each cell's own limits
-- and the unaltered reference as correct.  The control (float8
operands, the precision below the configuration's bfloat16) shows on
``grad1_best_diff`` alone, which is printed and not compared since a
sound bfloat16 run read 0.0052 on it on the chip (PERF.md section 7,
first): here it has to read over the 0.003 that sound runs keep under
on all seeds but that one, and to fail nothing else."""

import json
import os

import pytest

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))


@pytest.fixture(scope="module")
def small():
    from chipbench import checks, traffic
    from chipbench.families.resnet import program, reference
    with open(os.path.join(ROOT, "chipbench", "configs",
                           "resnet50.json")) as f:
        cfg = json.load(f)
    cfg.update(image_size=64, num_classes=10)
    mix = traffic.load("synth_u8_b256x4_w28")
    mix.update(per_chip_batch=8, epoch_images=4096)
    seed, chips = 2**31 + 5, 2
    batches = traffic.batches(mix, seed, 64, 10, chips, 3)
    ref = checks.reference_side(
        reference.follow(cfg, seed, batches, replicas=chips))
    return (reference, program), cfg, seed, batches, chips, ref


def cells():
    """Every cell that has limits, in the manifest or kept as files."""
    return sorted(f[:-len(".json")] for f in os.listdir(
        os.path.join(ROOT, "chipbench", "limits")))


@pytest.mark.parametrize("what", ["control", "half_batch", "no_exchange",
                                  "state_unchanged"])
def test_control_and_faults_are_not_correct(small, what):
    from chipbench import checks, control
    family, cfg, seed, batches, chips, ref = small
    numbers = checks.compare(
        control.variant(*family, cfg, seed, batches, chips, what), ref)
    numbers.pop("_where")
    for cell in cells():
        ok, table = checks.judge(numbers, checks.limits_for(cell))
        assert ok == (what == "control"), (cell, what, table)
    if what == "control":
        assert numbers["grad1_best_diff"] > 0.003
    if what == "state_unchanged":
        assert numbers["dparam_norm_gap"] == pytest.approx(1.0)
        assert numbers["grad1_norm_gap"] > 0.9


def test_the_reference_itself_is_correct(small):
    from chipbench import checks
    _, cfg, seed, batches, chips, ref = small
    numbers = checks.compare(ref, ref)
    numbers.pop("_where")
    assert all(v == 0.0 for v in numbers.values())
    for cell in cells():
        assert checks.judge(numbers, checks.limits_for(cell))[0]
    limits = checks.limits_for(cells()[0])
    bad = dict(numbers, grad1_median_gap=float("nan"))
    assert not checks.judge(bad, limits)[0]
    # a number without a limit is carried along and decides nothing
    assert "loss_step2" not in limits
    ok, table = checks.judge(dict(numbers, loss_step2=9.0), limits)
    assert ok and table["loss_step2"] == {"value": 9.0, "limit": None}
