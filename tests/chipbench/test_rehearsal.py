"""The one command, end to end at a tiny size on the CPU backend
(``--rehearsal``): ``engine.run`` is driven to a cut window and the
last line of standard output holds exactly the contract's keys and
names its device as the CPU's."""

import json
import os

from subproc import ROOT, last_line, run

KEYS = {"correct", "attempted", "failed", "metrics", "device"}


def test_rehearsal_prints_the_contracts_last_line():
    proc = run(["chipbench/run.py", "--workload", "wrn50_2_b256_synth",
                "--seed", str(2**31 + 21), "--seconds", "1",
                "--trace", "1", "--rehearsal"])
    res = last_line(proc)
    assert KEYS <= set(res) and list(res)[-1] == "compared"
    assert set(res) - KEYS <= {"breakdown", "compared"}
    assert res["device"]["platform"] == "cpu"
    assert res["device"]["count"] == 1
    assert res["device"]["memory_peak_bytes"] > 0
    assert res["correct"] is True and res["failed"] == 0
    assert res["attempted"] >= 1
    with open(os.path.join(ROOT, "BENCHMARK.json")) as f:
        manifest = json.load(f)
    per_layer = {m["name"]: m for m in manifest["per_layer"]}
    assert res["metrics"], "a traced run reports per-layer metrics"
    for name, m in res["metrics"].items():
        assert name in per_layer and m["unit"] == per_layer[name]["unit"]
        assert isinstance(m["value"], float)
    # what only a chip can give is left out, not reported as 0
    assert "device.idle_pct" not in res["metrics"]
    assert "step.mfu_pct" not in res["metrics"]
    assert "input.wait_pct" in res["metrics"]
    limited = [r for r in res["compared"].values()
               if r["limit"] is not None]
    assert len(limited) >= 3 and len(res["compared"]) > len(limited)
    for row in limited:
        assert row["value"] <= row["limit"]
    tail = proc.stderr.strip().splitlines()[-len(res["compared"]):]
    assert all("compared" in line and "limit" in line for line in tail)
    assert "window:" in proc.stderr and "poll-to-poll" in proc.stderr


def test_no_chip_no_result():
    proc = run(["chipbench/run.py", "--workload", "r50_b256_synth",
                "--seed", "1", "--seconds", "1", "--trace", "0"])
    assert proc.returncode != 0
    assert proc.stdout.strip() == ""


def test_sound_four_device_run_is_correct():
    """The (data=4) path on four virtual devices against the reference
    with four replicas: every shard normalises with its own rows and
    the gradients are averaged."""
    proc = run(["tests/chipbench/broken_run.py", "none", "--",
                "--workload", "r50_b256x4_data4", "--seed", "32",
                "--seconds", "0.5", "--trace", "0"])
    res = last_line(proc)
    assert res["correct"] is True and res["device"]["count"] == 4
