"""Chip accountant (telemetry/chipacct.py, ISSUE 19): XLA cost/memory
attribution units, the MFU derivation, the OOM preflight refusal drill
(fatal-config exit 78 with the per-component byte table), and the
end-to-end surfaces — telemetry.jsonl, status.json, the status CLI,
and `telemetry summarize`.
"""

import json
import os
import subprocess
import sys
import types

import numpy as np
import pytest

sys.path.insert(0, os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))

from imagent_tpu.resilience import exitcodes  # noqa: E402
from imagent_tpu.telemetry import chipacct  # noqa: E402

REPO_ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


# ------------------------------------------------------------- units

def test_fmt_bytes():
    assert chipacct.fmt_bytes(None) == "?"
    assert chipacct.fmt_bytes(512) == "512B"
    assert chipacct.fmt_bytes(2 * 2 ** 20) == "2.00MiB"
    assert chipacct.fmt_bytes(3.5 * 2 ** 30) == "3.50GiB"


class _FakeCompiled:
    """cost_analysis/memory_analysis double covering both jax shapes
    (per-partition list vs bare dict) and the backend-absent case."""

    def __init__(self, cost=None, mem=None, raise_cost=False):
        self._cost, self._mem = cost, mem
        self._raise = raise_cost

    def cost_analysis(self):
        if self._raise:
            raise NotImplementedError("backend has no cost model")
        return self._cost

    def memory_analysis(self):
        return self._mem


def test_extract_cost_list_and_dict_forms():
    cost = {"flops": 1e9, "bytes accessed": 2e8}
    for form in (cost, [cost], (cost,)):
        out = chipacct.extract_cost(_FakeCompiled(cost=form))
        assert out == {"flops": 1e9, "bytes_accessed": 2e8}
    assert chipacct.extract_cost(_FakeCompiled(cost=[])) is None
    assert chipacct.extract_cost(_FakeCompiled(raise_cost=True)) is None
    # Absent keys degrade to None, never KeyError.
    partial = chipacct.extract_cost(_FakeCompiled(cost={"flops": 5.0}))
    assert partial == {"flops": 5.0, "bytes_accessed": None}


def test_extract_memory_models_peak_with_aliasing():
    mem = types.SimpleNamespace(
        argument_size_in_bytes=100.0, output_size_in_bytes=40.0,
        temp_size_in_bytes=60.0, generated_code_size_in_bytes=10.0,
        alias_size_in_bytes=30.0)
    out = chipacct.extract_memory(_FakeCompiled(mem=mem))
    # args + out + temp + code - alias: donated buffers are reused.
    assert out["modeled_peak_bytes"] == 180.0
    assert chipacct.extract_memory(_FakeCompiled(mem=None)) is None


def test_resolve_peak_override_registry_and_honest_unknown():
    assert chipacct.resolve_peak_tflops("cpu", 7.5) == (7.5, "override")
    assert chipacct.resolve_peak_tflops("TPU v4") == (275.0, "registry")
    peak, src = chipacct.resolve_peak_tflops("cpu")
    assert peak is None and src is None  # honest: no invented peak


def test_state_component_bytes_unsharded_numpy():
    state = types.SimpleNamespace(
        params={"w": np.zeros((4, 4), np.float32)},       # 64 B
        opt_state=[np.zeros((4, 4), np.float32)] * 2,     # 128 B
        ema_params={"w": np.zeros((4,), np.float32)},     # 16 B
        ema_batch_stats=None,
        batch_stats={"m": np.zeros((2,), np.float32)})    # 8 B
    out = chipacct.state_component_bytes(state)
    assert out == {"params": 64.0, "opt_state": 128.0, "ema": 16.0,
                   "batch_stats": 8.0, "total": 216.0}


def _acct(**kw):
    base = dict(device_kind="TPU v4", n_devices=4, global_batch=32,
                peak_tflops=275.0, peak_source="registry",
                model_flops_per_step=1e12,
                train={"flops": 9e11, "bytes_accessed": 1e9,
                       "memory": {"args_bytes": 3e9, "output_bytes": 1e9,
                                  "temp_bytes": 2e9, "code_bytes": 1e7,
                                  "alias_bytes": 1e9,
                                  "modeled_peak_bytes": 5.01e9}},
                eval=None, capture_s=1.0,
                state_bytes={"params": 1e9, "opt_state": 2e9,
                             "ema": 1e9, "batch_stats": 1e6,
                             "total": 4.001e9},
                modeled_peak_bytes=5.01e9, hbm_limit_bytes=32e9,
                limit_source="device", verdict="ok",
                headroom_bytes=32e9 - 5.01e9)
    base.update(kw)
    return base


def test_epoch_perf_mfu_math():
    # 100 steps of 1 TFLOP over 10 step-loop seconds on 4 chips:
    # 10 TFLOP/s achieved -> 2.5 TFLOP/s/chip -> mfu 2.5/275. The
    # loop's seconds are dispatch + step_drain + input_wait (an
    # input-bound loop's device work hides under the input wait);
    # eval/checkpoint/compile seconds are not the step loop's.
    perf = chipacct.epoch_perf(
        _acct(), {"dispatch": 1.0, "step_drain": 2.0, "input_wait": 7.0,
                  "eval": 5.0, "checkpoint": 3.0, "compile": 9.0}, 100)
    assert perf["tflops_per_chip"] == pytest.approx(2.5)
    assert perf["mfu"] == pytest.approx(2.5 / 275.0, abs=1e-4)
    assert perf["verdict"] == "ok"
    assert perf["state_bytes"]["total"] == 4.001e9


def test_epoch_perf_honest_without_peak_or_steps():
    # Unknown peak: achieved TFLOP/s still reported, NO mfu ratio.
    perf = chipacct.epoch_perf(
        _acct(peak_tflops=None, peak_source=None),
        {"dispatch": 10.0}, 100)
    assert perf["tflops_per_chip"] == pytest.approx(2.5)
    assert perf["mfu"] is None
    # Compile-dominated epoch (no useful seconds): both honestly null.
    perf0 = chipacct.epoch_perf(_acct(), {"dispatch": 0.0}, 0)
    assert perf0["tflops_per_chip"] is None and perf0["mfu"] is None
    assert chipacct.epoch_perf(None, {"dispatch": 1.0}, 1) is None


def test_byte_table_and_refusal_fit_flightrec_budget():
    acct = _acct(verdict="over", hbm_limit_bytes=4e9,
                 limit_source="budget")
    table = chipacct.byte_table(acct)
    for frag in ("modeled_peak=", "args=", "temp=", "alias=-",
                 "state[params=", "limit=", "(budget)"):
        assert frag in table, table
    # The flightrec detail field truncates at 500 chars — the whole
    # refusal (table included) must survive intact.
    err = chipacct.preflight_error(acct)
    assert len(err) < 500, len(err)
    assert "--hbm-budget-gb" in err and "--no-chipacct" in err
    with pytest.raises(ValueError, match="chip accountant preflight"):
        chipacct.check_preflight(acct)
    chipacct.check_preflight(_acct())  # ok: no raise


def test_classify_oom_and_detail():
    assert chipacct.classify_oom(
        RuntimeError("RESOURCE_EXHAUSTED: Out of memory allocating"))
    assert chipacct.classify_oom(MemoryError("out of memory"))
    assert not chipacct.classify_oom(ValueError("shape mismatch"))
    assert chipacct.oom_detail(None).startswith("OOM (no chip account")
    assert "modeled_peak=" in chipacct.oom_detail(_acct())


def test_plan_line_carries_preflight_verdict():
    line = chipacct.plan_line(_acct())
    assert line.startswith("chip accountant: TPU v4 x4")
    assert "preflight ok:" in line and "peak 275 TFLOP/s" in line
    honest = chipacct.plan_line(_acct(peak_tflops=None))
    assert "peak unknown" in honest and "--peak-tflops" in honest


# ------------------------------------------------ engine round-trips

def _cfg(root, **kw):
    from imagent_tpu.config import Config
    base = dict(backend="cpu", arch="resnet18", image_size=16, num_classes=4,
                batch_size=4, epochs=2, lr=0.05, dataset="synthetic",
                synthetic_size=64, workers=0, bf16=False, log_every=0,
                seed=0, save_model=False, eval_every=2,
                log_dir=os.path.join(root, "tb"),
                ckpt_dir=os.path.join(root, "ck"))
    base.update(kw)
    return Config(**base)


@pytest.fixture(scope="module")
def acct_run(tmp_path_factory):
    """One real 2-epoch CPU run with a declared peak — every surface
    assertion below reads this single run."""
    from imagent_tpu.engine import run
    root = str(tmp_path_factory.mktemp("acct_run"))
    run(_cfg(root, peak_tflops=1.0))
    return root


def test_telemetry_records_carry_chipacct(acct_run):
    from imagent_tpu.telemetry import read_events
    epochs = [e for e in read_events(
        os.path.join(acct_run, "tb", "telemetry.jsonl"))
        if e["event"] == "epoch"]
    assert len(epochs) == 2
    for rec in epochs:
        sub = rec.get("chipacct")
        assert sub, rec
        assert sub["state_bytes"]["params"] > 0
        assert sub["modeled_peak_bytes"] > 0
        assert sub["verdict"] in ("ok", "unknown-limit")
    # Epoch 0 is compile-dominated (honest null allowed); epoch 1 must
    # produce a real ratio against the declared 1-TFLOP/s peak.
    assert epochs[-1]["chipacct"]["mfu"] is not None
    assert 0.0 < epochs[-1]["chipacct"]["mfu"] < 1.0
    assert epochs[-1]["chipacct"]["tflops_per_chip"] > 0.0


def test_status_surfaces_chipacct(acct_run):
    with open(os.path.join(acct_run, "tb", "status.json")) as f:
        st = json.load(f)
    assert st.get("chipacct"), st  # the terminal write carries it too
    from imagent_tpu.status import render
    out = render(os.path.join(acct_run, "tb"))
    assert "mfu:" in out, out
    assert "memory/device: modeled peak" in out, out
    assert "preflight" in out, out


def test_summarize_grows_mfu_column(acct_run):
    proc = subprocess.run(
        [sys.executable, "-m", "imagent_tpu.telemetry", "summarize",
         os.path.join(acct_run, "tb")],
        capture_output=True, text=True, timeout=120, cwd=REPO_ROOT)
    assert proc.returncode == 0, proc.stderr
    header = [ln for ln in proc.stdout.splitlines()
              if ln.startswith("epoch")][0]
    assert "mfu" in header.split() and "model_gb" in header.split()


def test_preflight_refusal_is_fatal_config_with_byte_table(tmp_path):
    """THE acceptance drill: a config whose modeled peak exceeds the
    (budget-declared) HBM limit is REFUSED before step 0 — ValueError
    through the engine's fatal-config ramp (exit 78), tombstone/
    flightrec carrying the per-component byte table."""
    from imagent_tpu.engine import run
    root = str(tmp_path)
    # ~171 MiB modeled peak vs a 50 MiB budget: deterministically over.
    with pytest.raises(ValueError,
                       match="chip accountant preflight"):
        run(_cfg(root, hbm_budget_gb=0.05))
    with open(os.path.join(root, "tb", "flightrec.0.json")) as f:
        rec = json.load(f)
    assert rec["reason"] == "fatal-config"
    assert rec["exit_code"] == exitcodes.FATAL_CONFIG
    detail = rec["detail"]
    for frag in ("modeled_peak=", "state[", "limit=", "(budget)",
                 "--hbm-budget-gb"):
        assert frag in detail, detail


def test_no_chipacct_flag_disables_everything(tmp_path, capsys):
    from imagent_tpu.engine import run
    root = str(tmp_path)
    # The same over-budget config runs to completion when bypassed.
    run(_cfg(root, epochs=1, hbm_budget_gb=0.05, chipacct=False))
    out = capsys.readouterr().out
    assert "chip accountant:" not in out
    from imagent_tpu.telemetry import read_events
    epochs = [e for e in read_events(
        os.path.join(root, "tb", "telemetry.jsonl"))
        if e["event"] == "epoch"]
    assert epochs and all("chipacct" not in e for e in epochs)
