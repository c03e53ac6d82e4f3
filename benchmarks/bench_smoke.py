"""CPU-backend smoke bench (``make bench-smoke``): input path + the
async-checkpoint telemetry regression gate.

Stage 1 — input path: a tiny synthetic-data bench iteration through the
REAL input path — SyntheticLoader (uint8 wire, ``data/pipeline.py``
Batch contract) → ``device_prefetch`` staging (with the starvation
counters) → the jitted train step with in-graph dequantize+normalize →
one masked eval batch — on the CPU backend, no TPU required. CI runs
this so an input-path crash (wire-dtype regression, Batch contract
break, prefetch deadlock) surfaces here, in under a minute, instead of
burning a real bench run.

Stage 2 — checkpoint critical-path regression: two 2-epoch engine runs
with checkpointing on and a deterministic ``ckpt.slow_commit`` fault
armed on epoch 0's LAST commit — one with ``--no-async-ckpt``
(synchronous baseline: the injected commit latency lands in the
blocking ``checkpoint`` phase), one with the default async path (the
same latency runs on the committer thread, hidden under epoch 1's
compute). The gate asserts, from IN-RUN telemetry (no wall-clock
comparisons between machines): the async run's epoch-0 blocking
``checkpoint`` phase is < 10% of the synchronous run's; the moved work
shows up in the overlapped ``ckpt_commit_async`` phase; and every
epoch's phases still sum to its measured wall (the accounting
invariant the overlap must not break). The comparison is pinned to
epoch 0 — ``eval_every=2`` keeps it free of the eval and BEST-save
costs both runs pay identically (and synchronously) at the final
epoch.

Stage 3 — pod tracer gate: a 2-epoch engine run with ``--trace
phases`` must produce span files whose PHASE spans sum to within 5% of
epoch wall of the goodput accountant's phases (both ride the same
measurements — ``TelemetrySession.phase``/``record_dispatch`` — so
drift means an emission site was dropped or double-fired), and merge
into a ``trace.json`` that validates against the Chrome trace event
schema (``telemetry/trace.py``).

Stage 4 — cross-run regression gate: stage 2's two runs double as a
known-degraded/clean twin pair, so ``telemetry regress``
(``telemetry/regress.py``) is asserted END TO END: the sync run
(whose injected slow commit BLOCKED the step loop) must trip a
nonzero exit against its async twin with ``ckpt_block_s`` among the
named regressions, and the clean twin compared against itself must
exit 0 — the gate can both catch a real regression and stay quiet on
identical runs.

Stage 5 — chip-accountant gate (ISSUE 19): the compiled FORWARD
executable's ``cost_analysis()`` flops must land within 10% of the
hand-computed padding-aware analytic count
(``utils/flops.resnet_forward_flops_padded`` — XLA's valid-tap
convention; at 16x16 the naive roofline count overcounts ~3x because
the deep stages run at 1x1-4x4 where most 3x3 taps are padding), and
a real engine run's startup plan must carry the accountant's
preflight verdict line.

Stage 6 — warm-start gate (ISSUE 20): two engine runs in FRESH
subprocesses sharing one compile-cache dir (a cold temporary root by
design, placed through ``JAX_COMPILATION_CACHE_DIR`` in the children's
environment — the one resolver, ``compilecache.resolve_cache_dir``).
The cold run must compile and serialize both step executables (0 hits
/ 2 compiled / 2 saved); the warm resumed run must load them (2 hits /
0 compiled), dispatch every step — from the restored, host-committed
state on — on the loaded executables (0 fallbacks), and land its
startup (load+compile) phase under 30% of the cold startup —
the sub-deadline-resize number ``make drill-warmstart`` measures at
larger scale.

Prints one JSON line per stage and exits non-zero on any crash, a
non-finite loss, or a telemetry-regression violation.
"""

from __future__ import annotations

import json
import os
import sys
import time

import numpy as np

sys.path.insert(0, os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))

# The injected final-commit latency: big enough that a regression (the
# sleep landing on the critical path) dwarfs scheduler noise in the
# blocking-phase comparison, small enough to keep the bench fast.
_SLOW_COMMIT_SECS = 1.0


def _input_path_stage() -> int:
    import jax

    from imagent_tpu.cluster import make_mesh
    from imagent_tpu.config import Config
    from imagent_tpu.data import make_loaders
    from imagent_tpu.data.prefetch import PrefetchStats, device_prefetch
    from imagent_tpu.train import (
        create_train_state, make_eval_step, make_optimizer,
        make_train_step, replicate_state,
    )

    n_chips = len(jax.devices())
    cfg = Config(backend="cpu", arch="resnet18", image_size=16, num_classes=4,
                 batch_size=4, dataset="synthetic", synthetic_size=32,
                 workers=0, bf16=False, seed=0)
    global_batch = cfg.batch_size * n_chips
    mesh = make_mesh(model_parallel=1)
    from imagent_tpu.models import create_model
    model = create_model(cfg.arch, cfg.num_classes, bf16=False)
    opt = make_optimizer()
    state = replicate_state(
        create_train_state(model, jax.random.key(0), cfg.image_size, opt,
                           batch_size=2), mesh)
    step = make_train_step(model, opt, mesh, mean=cfg.mean, std=cfg.std)
    eval_step = make_eval_step(model, mesh, mean=cfg.mean, std=cfg.std)
    train_loader, val_loader = make_loaders(
        cfg, jax.process_index(), jax.process_count(), global_batch)

    stats = PrefetchStats()
    t0 = time.time()
    n_steps = 0
    wire_dtype = None
    for batch in train_loader.epoch(0):
        wire_dtype = str(batch.images.dtype)
        break
    for gi, gl in device_prefetch(mesh, train_loader.epoch(0),
                                  depth=cfg.prefetch_depth, stats=stats):
        state, metrics = step(state, gi, gl, np.float32(0.1))
        n_steps += 1
    m = np.asarray(metrics)
    train_s = time.time() - t0
    if not np.isfinite(m).all() or m[3] != global_batch:
        print(f"FAIL: bad train metrics {m}", file=sys.stderr)
        return 1

    # Dispatch-then-fetch: the metric read happens OUTSIDE the
    # prefetched loop (blocking-call-in-step-loop lint invariant).
    eval_metrics = None
    for gi, gl, gm in device_prefetch(mesh, val_loader.epoch(0),
                                      with_mask=True):
        eval_metrics = eval_step(state, gi, gl, gm)
        break
    em = np.asarray(eval_metrics)
    if not np.isfinite(em).all():
        print(f"FAIL: bad eval metrics {em}", file=sys.stderr)
        return 1

    print(json.dumps({
        "metric": "bench_smoke_input_path",
        "status": "PASS",
        "wire_dtype": wire_dtype,
        "steps": n_steps,
        "img_s": round(n_steps * global_batch / train_s, 1),
        "host_blocked_s": round(stats.wait_s, 3),
        "h2d_bytes": int(stats.bytes_staged),
        "backend": jax.devices()[0].platform,
    }))
    return 0


def _ckpt_run(root: str, tag: str, async_on: bool) -> list[dict]:
    """A 2-epoch CPU engine run with checkpointing on and the final
    LAST commit slowed deterministically; returns its telemetry epoch
    records."""
    from imagent_tpu.config import Config
    from imagent_tpu.engine import run
    from imagent_tpu.resilience import faultinject
    from imagent_tpu.telemetry import read_events

    log_dir = os.path.join(root, f"tb_{tag}")
    cfg = Config(backend="cpu", arch="resnet18", image_size=16, num_classes=4,
                 batch_size=4, epochs=2, lr=0.05, dataset="synthetic",
                 synthetic_size=128, workers=0, bf16=False, log_every=0,
                 seed=0, save_model=True, keep_last_k=1,
                 # eval_every=2: epoch 0 has no eval and no BEST save —
                 # its checkpoint phase is EXACTLY the LAST-save cost
                 # the async path moves off the critical path.
                 eval_every=2, async_ckpt=async_on,
                 # Epoch 0's LAST commit sleeps; the async committer
                 # hides it under epoch 1's compute and lands it at the
                 # next boundary.
                 faults=f"ckpt.slow_commit:secs={_SLOW_COMMIT_SECS}",
                 log_dir=log_dir, ckpt_dir=os.path.join(root, f"ck_{tag}"))
    try:
        result = run(cfg)
    finally:
        faultinject.reset()
    if result["preempted"] or result["rollbacks"]:
        raise RuntimeError(f"{tag} run degraded: {result}")
    events = read_events(os.path.join(log_dir, "telemetry.jsonl"))
    return [e for e in events if e["event"] == "epoch"]


def _ckpt_regression_stage() -> tuple[int, str]:
    import tempfile

    root = tempfile.mkdtemp(prefix="bench_ckpt_")
    sync_eps = _ckpt_run(root, "sync", async_on=False)
    async_eps = _ckpt_run(root, "async", async_on=True)

    failures = []
    for tag, eps in (("sync", sync_eps), ("async", async_eps)):
        for rec in eps:
            phase_sum = sum(rec["phases"].values())
            # host_other absorbs the residual, so the partition must
            # cover (almost all of) the wall — the overlap phase must
            # NOT be needed to close the books.
            if phase_sum < 0.95 * rec["wall_s"]:
                failures.append(
                    f"{tag} epoch {rec['epoch']}: phases sum "
                    f"{phase_sum:.3f}s < 95% of wall {rec['wall_s']}s")
    # Epoch 0 only: pure LAST-save cost (no eval/BEST, eval_every=2).
    sync_ckpt = sync_eps[0]["phases"]["checkpoint"]
    async_ckpt = async_eps[0]["phases"]["checkpoint"]
    async_overlap = sum(r["overlap"]["ckpt_commit_async"]
                        for r in async_eps)
    sync_overlap = sum(r["overlap"]["ckpt_commit_async"]
                       for r in sync_eps)
    if sync_ckpt < _SLOW_COMMIT_SECS:
        failures.append(
            f"sync blocking checkpoint phase {sync_ckpt:.3f}s missed "
            f"the injected {_SLOW_COMMIT_SECS}s commit latency — the "
            "baseline itself is not attributing")
    if async_ckpt >= 0.1 * sync_ckpt:
        failures.append(
            f"async blocking checkpoint phase {async_ckpt:.3f}s is not "
            f"< 10% of the synchronous baseline {sync_ckpt:.3f}s — the "
            "commit is back on the critical path")
    if async_overlap <= 0.0:
        failures.append("async run recorded no ckpt_commit_async "
                        "overlap — the moved work is unaccounted")
    if sync_overlap != 0.0:
        failures.append(f"sync run recorded {sync_overlap}s of async "
                        "overlap — attribution leak")
    print(json.dumps({
        "metric": "bench_ckpt_async",
        "status": "FAIL" if failures else "PASS",
        "sync_checkpoint_s": round(sync_ckpt, 3),
        "async_checkpoint_s": round(async_ckpt, 3),
        "async_overlap_s": round(async_overlap, 3),
        "injected_commit_s": _SLOW_COMMIT_SECS,
    }))
    for f in failures:
        print(f"FAIL: {f}", file=sys.stderr)
    return (1 if failures else 0), root


def _regress_gate_stage(root: str) -> int:
    """Stage 4 — the cross-run regression gate, drilled on stage 2's
    twins: the sync run paid the injected slow commit ON the critical
    path (its blocking `checkpoint` phase carries it), the async run
    hid the same injected latency — a real degradation with a known
    cause, which `telemetry regress` must catch (exit 1, ckpt_block_s
    named) while the clean twin vs itself stays quiet (exit 0).
    --warmup 0: the degradation was deliberately injected on epoch 0's
    LAST commit, which the default compile-warmup exemption would
    exclude."""
    from imagent_tpu.telemetry import regress as regress_lib

    sync_dir = os.path.join(root, "tb_sync")
    async_dir = os.path.join(root, "tb_async")
    failures = []
    rc_degraded = regress_lib.main(
        [sync_dir, "--baseline", async_dir, "--warmup", "0"])
    if rc_degraded != 1:
        failures.append(
            f"regress exited {rc_degraded} for the slow-commit run vs "
            "its clean twin — the gate missed a seeded degradation")
    verdict = regress_lib.compare(
        regress_lib.load_run(sync_dir, warmup=0),
        regress_lib.load_run(async_dir, warmup=0))
    named = [f["metric"] for f in verdict["regressions"]]
    if "ckpt_block_s" not in named:
        failures.append(
            f"regress named {named} but not ckpt_block_s — the "
            "blocking-commit degradation was misattributed")
    rc_clean = regress_lib.main(
        [async_dir, "--baseline", async_dir, "--warmup", "0"])
    if rc_clean != 0:
        failures.append(
            f"regress exited {rc_clean} comparing the clean run "
            "against itself — the gate fails identical runs")
    print(json.dumps({
        "metric": "bench_regress_gate",
        "status": "FAIL" if failures else "PASS",
        "degraded_exit": rc_degraded,
        "clean_exit": rc_clean,
        "regressions_named": named,
    }))
    for f in failures:
        print(f"FAIL: {f}", file=sys.stderr)
    return 1 if failures else 0


def _trace_stage() -> int:
    """Stage 3 — pod tracer gate: a 2-epoch engine run with ``--trace
    phases`` must (a) produce a per-rank span file whose PHASE spans
    sum to within 5% of epoch wall of the goodput accountant's phases
    (the two ride the same measurements — drift means a span emission
    site was dropped or double-fired), (b) merge into a Chrome-trace-
    format ``trace.json`` that passes the schema validator, with the
    clock-offset record present, and (c) drop no spans at the default
    buffer on this tiny run."""
    import tempfile

    from imagent_tpu.config import Config
    from imagent_tpu.engine import run
    from imagent_tpu.telemetry import read_events
    from imagent_tpu.telemetry import trace as trace_lib

    root = tempfile.mkdtemp(prefix="bench_trace_")
    log_dir = os.path.join(root, "tb")
    cfg = Config(backend="cpu", arch="resnet18", image_size=16, num_classes=4,
                 batch_size=4, epochs=2, lr=0.05, dataset="synthetic",
                 synthetic_size=128, workers=0, bf16=False, log_every=0,
                 seed=0, save_model=True, keep_last_k=1, eval_every=1,
                 trace="phases", log_dir=log_dir,
                 ckpt_dir=os.path.join(root, "ck"))
    result = run(cfg)
    if result["preempted"] or result["rollbacks"]:
        print(f"FAIL: trace run degraded: {result}", file=sys.stderr)
        return 1

    failures = []
    epochs = [e for e in read_events(
        os.path.join(log_dir, "telemetry.jsonl"))
        if e["event"] == "epoch"]
    wall = sum(rec["wall_s"] for rec in epochs)
    acct = sum(v for rec in epochs
               for k, v in rec["phases"].items() if k != "host_other")
    dropped = sum((rec.get("trace") or {}).get("dropped", 0)
                  for rec in epochs)
    traces = trace_lib.load_run_traces(log_dir)
    if not traces:
        print("FAIL: --trace phases produced no trace files",
              file=sys.stderr)
        return 1
    spans = [sp for _rank, _hdr, sps in traces for sp in sps]
    traced = sum(trace_lib.phase_span_seconds(spans).values())
    # The consistency gate: the tracer and the accountant must tell
    # the same story about where the wall went.
    if abs(traced - acct) > 0.05 * wall:
        failures.append(
            f"traced phase spans sum {traced:.3f}s vs goodput phases "
            f"{acct:.3f}s — differ by more than 5% of epoch wall "
            f"{wall:.3f}s")
    if dropped:
        failures.append(f"{dropped} spans dropped at the default "
                        "buffer on a 2-epoch smoke run")
    if not any(rec.get("clock") for rec in epochs):
        failures.append("epoch records carry no clock-offset record")
    obj = trace_lib.merge(log_dir)
    errs = trace_lib.validate_chrome_trace(obj)
    out = None
    if errs:
        # Same refusal as the CLI: never ship a trace.json that
        # Perfetto will choke on.
        failures.append("merged trace.json fails Chrome-trace "
                        f"validation: {errs[:3]}")
    else:
        out = trace_lib.write_merged(log_dir, obj=obj)
    print(json.dumps({
        "metric": "bench_trace",
        "status": "FAIL" if failures else "PASS",
        "traced_phase_s": round(traced, 3),
        "goodput_phase_s": round(acct, 3),
        "wall_s": round(wall, 3),
        "spans": sum((rec.get("trace") or {}).get("spans", 0)
                     for rec in epochs),
        "merged": out,
    }))
    for f in failures:
        print(f"FAIL: {f}", file=sys.stderr)
    return 1 if failures else 0


def _chipacct_stage() -> int:
    """Stage 5 — chip-accountant gate: (a) the forward executable's
    ``cost_analysis()`` flops vs the padding-aware hand count, within
    10% (the analytic side of every MFU this repo will ever report —
    if the two diverge, one of the counters is lying); (b) a real
    engine run's startup plan carries the preflight verdict."""
    import contextlib
    import io
    import tempfile

    import jax

    from imagent_tpu.cluster import make_mesh
    from imagent_tpu.config import Config
    from imagent_tpu.models import create_model
    from imagent_tpu.telemetry import chipacct
    from imagent_tpu.train import (
        create_train_state, make_eval_step, make_optimizer,
        make_train_step, replicate_state,
    )
    from imagent_tpu.utils import flops as flops_lib

    cfg = Config(backend="cpu", arch="resnet18", image_size=16, num_classes=4,
                 batch_size=4, dataset="synthetic", synthetic_size=32,
                 workers=0, bf16=False, seed=0)
    global_batch = cfg.batch_size * len(jax.devices())
    mesh = make_mesh(model_parallel=1)
    model = create_model(cfg.arch, cfg.num_classes, bf16=False)
    opt = make_optimizer()
    state = replicate_state(
        create_train_state(model, jax.random.key(0), cfg.image_size,
                           opt, batch_size=2), mesh)
    step = make_train_step(model, opt, mesh, mean=cfg.mean, std=cfg.std)
    eval_step = make_eval_step(model, mesh, mean=cfg.mean, std=cfg.std)
    acct = chipacct.build_account(
        train_step=step, eval_step=eval_step, state=state, mesh=mesh,
        cfg=cfg, global_batch=global_batch)

    failures = []
    # (a) The forward (eval) executable vs the padding-aware analytic
    # count. The eval step adds only elementwise/metric flops on top
    # of conv+fc (~1% at this size), well inside the 10% gate.
    xla_fwd = ((acct.get("eval") or {}).get("flops"))
    analytic_fwd = flops_lib.resnet_forward_flops_padded(
        cfg.arch, cfg.image_size, cfg.num_classes) * global_batch
    if not xla_fwd:
        failures.append("eval executable produced no cost_analysis "
                        "flops — the accountant captured nothing")
        rel = None
    else:
        rel = abs(xla_fwd - analytic_fwd) / analytic_fwd
        if rel > 0.10:
            failures.append(
                f"cost-analysis forward flops {xla_fwd:.3e} vs "
                f"analytic {analytic_fwd:.3e} differ by "
                f"{rel:.1%} (> 10%) — a flop counter is lying")

    # (b) A real run's startup plan carries the preflight verdict.
    root = tempfile.mkdtemp(prefix="bench_chipacct_")
    from imagent_tpu.engine import run
    run_cfg = Config(backend="cpu", arch="resnet18", image_size=16, num_classes=4,
                     batch_size=4, epochs=1, lr=0.05,
                     dataset="synthetic", synthetic_size=64,
                     workers=0, bf16=False, log_every=0, seed=0,
                     save_model=False, eval_every=2,
                     log_dir=os.path.join(root, "tb"),
                     ckpt_dir=os.path.join(root, "ck"))
    out = io.StringIO()
    with contextlib.redirect_stdout(out):
        run(run_cfg)
    plan = [ln for ln in out.getvalue().splitlines()
            if ln.startswith("chip accountant:")]
    if not plan or "preflight" not in plan[0]:
        failures.append(
            "engine startup plan carries no chip-accountant "
            f"preflight verdict (got: {plan!r})")

    print(json.dumps({
        "metric": "bench_chipacct",
        "status": "FAIL" if failures else "PASS",
        "xla_forward_flops": xla_fwd,
        "analytic_forward_flops": analytic_fwd,
        "rel_err": None if rel is None else round(rel, 4),
        "train_step_flops": (acct.get("train") or {}).get("flops"),
        "preflight": acct.get("verdict"),
    }))
    for f in failures:
        print(f"FAIL: {f}", file=sys.stderr)
    return 1 if failures else 0


_WARM_CHILD = r"""
import os, sys
from imagent_tpu.config import Config
from imagent_tpu.engine import run

root, phase = sys.argv[1], sys.argv[2]
cfg = Config(arch="resnet18", image_size=16, num_classes=4,
             batch_size=4, epochs=(1 if phase == "cold" else 2),
             lr=0.05, dataset="synthetic", synthetic_size=128,
             workers=0, bf16=False, log_every=0, seed=0,
             save_model=True, resume=(phase == "warm"),
             backend="cpu",
             log_dir=os.path.join(root, "tb"),
             ckpt_dir=os.path.join(root, "ck"))
result = run(cfg)
sys.exit(0 if result["best_epoch"] >= 0 else 1)
"""


def _warm_start_stage() -> int:
    """Stage 6 — warm-start gate: fresh processes so the serialized
    store (not jax's in-memory caches) is what makes the second run
    fast; resume so a restored (device_put) state is what the loaded
    donated executables step."""
    import subprocess
    import tempfile

    from imagent_tpu.telemetry import read_events

    root = tempfile.mkdtemp(prefix="bench_warm_")
    env = dict(os.environ,
               JAX_COMPILATION_CACHE_DIR=os.path.join(root, "cc"),
               JAX_ENABLE_COMPILATION_CACHE="true")
    for phase in ("cold", "warm"):
        proc = subprocess.run(
            [sys.executable, "-c", _WARM_CHILD, root, phase],
            capture_output=True, text=True, timeout=900, env=env)
        if proc.returncode != 0:
            print(f"FAIL: {phase} engine run rc={proc.returncode}: "
                  f"{(proc.stdout + proc.stderr)[-800:]}",
                  file=sys.stderr)
            return 1

    stamps = [r["compile_cache"] for r in read_events(
        os.path.join(root, "tb", "telemetry.jsonl"))
        if r.get("event") == "run_start"
        and isinstance(r.get("compile_cache"), dict)]
    failures = []
    if len(stamps) != 2:
        failures.append(f"expected 2 run_start compile_cache stamps, "
                        f"got {len(stamps)}")
        cold = warm = {}
    else:
        cold, warm = stamps
        if (cold["hits"], cold["misses"], cold["saved"]) != (0, 2, 2):
            failures.append(f"cold run counters off: {cold}")
        if (warm["hits"], warm["misses"]) != (2, 0):
            failures.append(
                f"warm run did not load both executables: {warm}")
        if warm.get("fallback_steps"):
            failures.append(
                f"{warm['fallback_steps']} warm dispatches fell back "
                "to the jitted twin — the loaded executables were "
                "not reused")
        if warm["startup_s"] >= 0.30 * cold["startup_s"]:
            failures.append(
                f"warm startup {warm['startup_s']}s is not < 30% of "
                f"cold {cold['startup_s']}s — the store bought "
                "nothing")
    print(json.dumps({
        "metric": "bench_warm_start",
        "status": "FAIL" if failures else "PASS",
        "cold_startup_s": cold.get("startup_s"),
        "warm_startup_s": warm.get("startup_s"),
        "warm_hits": warm.get("hits"),
        "warm_fallback_steps": warm.get("fallback_steps"),
    }))
    for f in failures:
        print(f"FAIL: {f}", file=sys.stderr)
    return 1 if failures else 0


def main() -> int:
    # The CPU-backend smoke bench by definition: pin the platform here
    # (and for the engine children) rather than trusting the caller's
    # environment — none of its numbers is a device metric.
    os.environ["JAX_PLATFORMS"] = "cpu"
    rc = _input_path_stage()
    if rc:
        return rc
    rc, ckpt_root = _ckpt_regression_stage()
    if rc:
        return rc
    rc = _regress_gate_stage(ckpt_root)
    if rc:
        return rc
    rc = _trace_stage()
    if rc:
        return rc
    rc = _chipacct_stage()
    if rc:
        return rc
    return _warm_start_stage()


if __name__ == "__main__":
    sys.exit(main())
