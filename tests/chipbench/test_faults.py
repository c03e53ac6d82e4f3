"""A rehearsal run with the program's timed path broken underneath
(tests/chipbench/broken_run.py), once for each fault a cell can have:
the harness drives the rest of the run and ``correct`` has to come out
false."""

import pytest
from subproc import last_line, run


@pytest.mark.parametrize("fault,workload", [
    ("state_unchanged", "r50_b256_synth"),
    ("half_batch", "r50_b256_synth"),
    ("no_exchange", "r50_b256x4_data4"),
])
def test_broken_timed_path_is_not_correct(fault, workload):
    proc = run(["tests/chipbench/broken_run.py", fault, "--",
                "--workload", workload, "--seed", "31",
                "--seconds", "0.5", "--trace", "0"])
    res = last_line(proc)
    assert res["correct"] is False
    failed = [k for k, row in res["compared"].items()
              if row["limit"] is not None
              and not row["value"] <= row["limit"]]
    assert "grad1_norm_gap" in failed
    end_to_end = {"train_throughput", "step_time_p95_ms", "setup_s"}
    assert set(res["metrics"]) == end_to_end
