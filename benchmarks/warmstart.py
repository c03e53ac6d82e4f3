"""Warm-start resize drill (``make drill-warmstart``): measures the
wall time from process launch to training-ready — cold (empty cache)
versus warm (persistent AOT executable store populated by a previous
attempt with the same compile fingerprint) — the number that decides
whether an elastic exec-restart lands inside the preemption deadline
(docs/OPERATIONS.md "Warm starts and the compile cache").

Three fresh engine processes share one compile-cache dir — a COLD
temporary root by design (the drill measures the cold attempt), handed
to the children the way any deployment places the cache: through
``JAX_COMPILATION_CACHE_DIR`` in their environment
(``compilecache.resolve_cache_dir``), not a second code path:

1. ``cold``    — first attempt ever: compiles both step executables,
                 serializes them into the store (0 hits / 2 saved).
2. ``requeue`` — the requeue/restart path: same fingerprint, fresh
                 process, ``--resume``; must load both executables
                 (2 hits / 0 compiled) and step the restored
                 (host-committed) state through them.
3. ``replay``  — a second warm attempt, confirming the verdict is
                 stable (the store, not an OS page cache accident).

Each phase reports the engine's own startup stamp (load+compile
seconds from the ``run_start`` telemetry record) AND the end-to-end
process wall — jax import, mesh init, model build and data pipeline
included — because the resize deadline is paid in process wall, not
compile seconds. Prints one JSON line per phase plus a summary line
with the warm/cold ratios; exits non-zero if the warm attempts fail
to load from the store. CPU-hosted (8 virtual devices, --backend=cpu
children) like every other drill — its seconds are CPU seconds, never
a device metric; the chip's cold/warm start-up is what
``chip_smoke.py`` prints."""

from __future__ import annotations

import json
import os
import subprocess
import sys
import tempfile
import time

sys.path.insert(0, os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))

_CHILD = r"""
import os, sys
from imagent_tpu.config import Config
from imagent_tpu.engine import run

root, phase, epochs = sys.argv[1], sys.argv[2], int(sys.argv[3])
cfg = Config(arch="resnet18", image_size=16, num_classes=4,
             batch_size=4, epochs=epochs, lr=0.05,
             dataset="synthetic", synthetic_size=128, workers=0,
             bf16=False, log_every=0, seed=0, save_model=True,
             resume=(phase != "cold"),
             backend="cpu",
             log_dir=os.path.join(root, "tb"),
             ckpt_dir=os.path.join(root, "ck"))
result = run(cfg)
sys.exit(0 if result["best_epoch"] >= 0 else 1)
"""


def _run_phase(root: str, phase: str, epochs: int) -> dict:
    env = dict(os.environ)
    env["JAX_PLATFORMS"] = "cpu"
    env["JAX_COMPILATION_CACHE_DIR"] = os.path.join(root, "cc")
    env["JAX_ENABLE_COMPILATION_CACHE"] = "true"
    if "xla_force_host_platform_device_count" not in \
            env.get("XLA_FLAGS", ""):
        env["XLA_FLAGS"] = (env.get("XLA_FLAGS", "")
                            + " --xla_force_host_platform_device_count=8")
    t0 = time.perf_counter()
    proc = subprocess.run(
        [sys.executable, "-c", _CHILD, root, phase, str(epochs)],
        capture_output=True, text=True, timeout=1800, env=env)
    wall = time.perf_counter() - t0
    if proc.returncode != 0:
        print((proc.stdout + proc.stderr)[-1500:], file=sys.stderr)
        raise RuntimeError(f"{phase} attempt rc={proc.returncode}")
    return {"phase": phase, "process_wall_s": round(wall, 2)}


def main() -> int:
    from imagent_tpu.telemetry import read_events

    root = tempfile.mkdtemp(prefix="drill_warmstart_")
    results = [_run_phase(root, "cold", 1),
               _run_phase(root, "requeue", 2),
               _run_phase(root, "replay", 3)]

    stamps = [r["compile_cache"] for r in read_events(
        os.path.join(root, "tb", "telemetry.jsonl"))
        if r.get("event") == "run_start"
        and isinstance(r.get("compile_cache"), dict)]
    failures = []
    if len(stamps) != 3:
        failures.append(f"expected 3 startup stamps, got {len(stamps)}")
    for res, stamp in zip(results, stamps):
        res["startup_s"] = stamp.get("startup_s")
        res["hits"] = stamp.get("hits")
        res["misses"] = stamp.get("misses")
        res["fallback_steps"] = stamp.get("fallback_steps")
        print(json.dumps(dict(res, metric="drill_warmstart")))
    if len(stamps) == 3:
        cold, requeue, replay = results
        if (cold["hits"], cold["misses"]) != (0, 2):
            failures.append(f"cold attempt counters off: {cold}")
        for warm in (requeue, replay):
            if (warm["hits"], warm["misses"]) != (2, 0):
                failures.append(f"{warm['phase']} attempt did not "
                                f"load from the store: {warm}")
            if warm["fallback_steps"]:
                failures.append(f"{warm['phase']} fell back "
                                f"{warm['fallback_steps']} step(s)")
        summary = {
            "metric": "drill_warmstart_summary",
            "status": "FAIL" if failures else "PASS",
            "cold_startup_s": cold["startup_s"],
            "warm_startup_s": requeue["startup_s"],
            "startup_ratio": round(
                requeue["startup_s"] / cold["startup_s"], 3)
            if cold["startup_s"] else None,
            "cold_process_wall_s": cold["process_wall_s"],
            "warm_process_wall_s": requeue["process_wall_s"],
            "wall_ratio": round(requeue["process_wall_s"]
                                / cold["process_wall_s"], 3),
        }
        print(json.dumps(summary))
    for f in failures:
        print(f"FAIL: {f}", file=sys.stderr)
    return 1 if failures else 0


if __name__ == "__main__":
    sys.exit(main())
