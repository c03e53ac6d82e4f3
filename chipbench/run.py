"""One run of one cell of the benchmark:

    python3 chipbench/run.py --workload <name> --seed <n> --seconds <s> --trace <0|1>

drives ``imagent_tpu.engine.run(cfg, stop_check=clock)`` in this
process (the function ``python -m imagent_tpu`` calls), measures a
window of train steps cut by the benchmark's own ``stop_check``, holds
the first three optimizer steps of that same run against the plain
reference, and prints one JSON object as the last line of standard
output.  Everything that belongs to one configuration, traffic mix,
cell or per-layer metric is a data file found by the name in
``BENCHMARK.json`` (see README.md in this directory).

``--rehearsal`` runs the same control flow at a tiny size on the CPU
backend, prints its device as what it is, and is never a measurement.
"""

from __future__ import annotations

import time

T0 = time.perf_counter()  # process start, to the interpreter's ~20 ms

import argparse  # noqa: E402
import contextlib  # noqa: E402
import gc  # noqa: E402
import glob  # noqa: E402
import importlib  # noqa: E402
import json  # noqa: E402
import os  # noqa: E402
import shutil  # noqa: E402
import statistics  # noqa: E402
import sys  # noqa: E402

HERE = os.path.dirname(os.path.abspath(__file__))
ROOT = os.path.dirname(HERE)
if ROOT not in sys.path:
    sys.path.insert(0, ROOT)

# One run directory per process (two runs may share a checkout), under
# a fixed parent; the compile cache is not here and does not move.
RUNS = os.path.join(ROOT, ".chipbench_run")
RUN_DIR = os.path.join(RUNS, str(os.getpid()))

# The tiny sizes of a --rehearsal (CPU, never a measurement).  float32
# compute, so that the program has to agree with the reference to
# rounding: at these sizes bfloat16 noise swamps BatchNorm over 8 rows.
REHEARSAL = {
    "config": {"image_size": 64, "num_classes": 10,
               "compute_dtype": "float32"},
    "mix": {"per_chip_batch": 16, "workers": 0, "epoch_images": 65536},
    # Sound float32 runs at this size read 2e-5, 3e-3 and 1.5e-2 on
    # these (CPU); the later steps and the parameters' change amplify
    # round-off at 16 rows and are not compared here.
    "limits": {"loss_step1": 1e-3, "grad1_median_gap": 0.05,
               "grad1_norm_gap": 0.1},
}


def log(msg: str) -> None:
    print(f"[chipbench] {msg}", file=sys.stderr, flush=True)


def load_json(path: str) -> dict:
    with open(path) as f:
        return json.load(f)


def find(entries: list, name: str, what: str) -> dict:
    for e in entries:
        if e["name"] == name:
            return e
    raise SystemExit(f"chipbench: no {what} named {name!r} in "
                     "BENCHMARK.json")


def p95(values: list[float]) -> float:
    """95th percentile of all values (nearest rank, the value with at
    most 5% of the samples above it)."""
    s = sorted(values)
    return s[min(len(s) - 1, max(0, -(-95 * len(s) // 100) - 1))]


def read_telemetry(log_dir: str) -> dict:
    out = {"run_start": None, "epoch": None}
    path = os.path.join(log_dir, "telemetry.jsonl")
    with open(path) as f:
        for line in f:
            line = line.strip()
            if not line:
                continue
            rec = json.loads(line)
            kind = rec.get("event")
            if kind in out:
                out[kind] = rec
    return out


def read_program_spans(log_dir: str) -> list[dict]:
    """The program's own host spans (``--trace steps``), on the
    ``time.perf_counter`` clock this process shares with the window."""
    spans = []
    for path in glob.glob(os.path.join(log_dir, "trace", "trace.*.jsonl")):
        with open(path) as f:
            for line in f:
                try:
                    row = json.loads(line)
                except ValueError:
                    continue
                if row.get("ph") == "X":
                    spans.append(row)
    return spans


def memory_peak_bytes(jax, epoch_rec: dict | None) -> tuple[int, dict]:
    """Peak on the fullest chip: the larger of what the runtime counted
    (``peak_bytes_in_use``: live buffers only on this runtime) and what
    XLA's memory analysis gives for the very train-step executable the
    engine compiled and ran (arguments + outputs + temporaries + code -
    aliased), as the program's chip accountant recorded it."""
    runtime = 0
    for d in jax.local_devices():
        try:
            stats = d.memory_stats() or {}
        except Exception:  # noqa: BLE001 - backend-optional API
            stats = {}
        runtime = max(runtime, int(stats.get("peak_bytes_in_use", 0)))
    modeled = 0
    acct = (epoch_rec or {}).get("chipacct") or {}
    if acct.get("modeled_peak_bytes"):
        modeled = int(acct["modeled_peak_bytes"])
    return max(runtime, modeled), {"runtime_peak_bytes_in_use": runtime,
                                   "xla_step_program_bytes": modeled}


def load_cell(workload: str, rehearsal: bool = False) -> dict:
    """A cell with everything its name leads to: manifest entry, cell
    file, configuration, traffic mix, the family's modules."""
    manifest = load_json(os.path.join(ROOT, "BENCHMARK.json"))
    cell = find(manifest["workloads"], workload, "workload")
    config_entry = find(manifest["configs"], cell["config"],
                        "configuration")
    cfg_model = load_json(os.path.join(ROOT, config_entry["file"]))
    chips = int(cell["chips"])
    from chipbench import traffic
    mix = traffic.load(cell["traffic"])
    if rehearsal:
        cfg_model.update(REHEARSAL["config"])
        mix.update(REHEARSAL["mix"])
        os.environ["JAX_PLATFORMS"] = "cpu"
        os.environ["XLA_FLAGS"] = (
            f"--xla_force_host_platform_device_count={chips}")
    family = f"chipbench.families.{cfg_model['family']}"
    return {
        "manifest": manifest, "cell": cell, "chips": chips,
        "cell_file": load_json(os.path.join(
            HERE, "workloads", f"{cell['name']}.json")),
        "config": cfg_model, "mix": mix,
        "program": importlib.import_module(family + ".program"),
        "reference": importlib.import_module(family + ".reference"),
    }


def program_flags(c: dict, seed: int, backend: str,
                  trace: bool) -> list[str]:
    """The program's flags, verbatim as a user would pass them."""
    from chipbench import traffic
    cell_file = c["cell_file"]
    flags = (c["program"].engine_flags(c["config"])
             + traffic.engine_flags(c["mix"])
             + list(cell_file.get("flags", []))
             + [f"--backend={backend}", f"--seed={seed}", "--epochs=1",
                f"--log-dir={os.path.join(RUN_DIR, 'log')}",
                f"--ckpt-dir={os.path.join(RUN_DIR, 'ckpt')}"])
    if trace:
        tw = cell_file["trace_window"]
        start = int(cell_file["warmup_steps"]) + int(tw["after_open"])
        flags += [f"--profile-at-step={start}:{tw['steps']}",
                  "--trace=steps", "--trace-buffer=65536"]
    return flags


def sweep_run_dirs() -> None:
    """Empty this process's run directory and remove those of
    processes that are gone (a killed run leaves its own behind)."""
    shutil.rmtree(RUN_DIR, ignore_errors=True)
    for name in os.listdir(RUNS) if os.path.isdir(RUNS) else []:
        try:
            os.kill(int(name), 0)
        except (ValueError, ProcessLookupError):
            shutil.rmtree(os.path.join(RUNS, name), ignore_errors=True)
        except PermissionError:
            pass


def drive(flags: list[str], clock) -> dict | None:
    """``engine.run`` with the benchmark's clock as its ``stop_check``,
    in an emptied run directory; None where the engine did not stop at
    the clock (the epoch ended first)."""
    from imagent_tpu import engine
    from imagent_tpu.config import parse_args
    sweep_run_dirs()
    log("engine flags: " + " ".join(flags))
    with contextlib.redirect_stdout(sys.stderr):
        summary = engine.run(parse_args(flags), stop_check=clock)
    if not (clock.closed and summary.get("preempted")):
        log("the epoch ended before the window closed, or the engine "
            "did not stop at the clock: no result")
        return None
    return summary


def main(argv=None) -> int:
    ap = argparse.ArgumentParser()
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    ap.add_argument("--rehearsal", action="store_true")
    ap.add_argument("--keep-trace", default="",
                    help="copy the profiler's .xplane.pb here (to look "
                         "at one by hand: tools/dump_xplane.py)")
    args = ap.parse_args(argv)

    c = load_cell(args.workload, args.rehearsal)
    manifest, cell, cell_file = c["manifest"], c["cell"], c["cell_file"]
    cfg_model, mix, chips = c["config"], c["mix"], c["chips"]
    from chipbench import checks, traffic
    from chipbench.clock import FOLLOWED_STEPS, Clock

    import jax
    devices = jax.devices()
    platform = devices[0].platform
    want = "cpu" if args.rehearsal else "tpu"
    if platform != want or len(devices) != chips:
        log(f"cell {cell['name']} needs {chips} {want} device(s); JAX "
            f"found {len(devices)} x {platform} "
            f"({devices[0].device_kind}). No result.")
        return 3
    device = {"platform": platform, "kind": devices[0].device_kind,
              "count": len(devices)}
    if not args.rehearsal:
        from chipbench.peaks import peaks
        peak = peaks(device["kind"])  # an unknown chip is an error
    else:
        peak = None

    warmup = int(cell_file["warmup_steps"])
    tw = cell_file["trace_window"]
    flags = program_flags(c, args.seed, want, bool(args.trace))
    log_dir = os.path.join(RUN_DIR, "log")
    clock = Clock(args.seconds, warmup, c["program"].optimizer_memory,
                  annotate=bool(args.trace))
    summary = drive(flags, clock)
    t_returned = time.perf_counter()
    if summary is None:
        return 4

    # -- the window -------------------------------------------------------
    intervals = clock.intervals_s()
    steps = len(intervals)
    wall = clock.t_close - clock.t_open
    gb = traffic.global_batch(mix, chips)
    e2e = {
        "train_throughput": steps * gb / wall / chips,
        "step_time_p95_ms": 1e3 * p95(intervals),
        "setup_s": clock.t_open - T0,
    }
    bad = int((summary.get("final_train") or {}).get("bad_steps", 0))
    log(f"window: {steps} steps of {gb} images in {wall:.4f} s "
        f"(p95 over {steps} poll-to-poll intervals, median "
        f"{1e3 * statistics.median(intervals):.3f} ms); warm-up "
        f"{warmup} steps; non-finite steps in the epoch: {bad}; engine "
        f"returned {t_returned - clock.t_close:.2f} s after the close")

    telem = read_telemetry(log_dir)
    mem, mem_parts = memory_peak_bytes(jax, telem["epoch"])
    device["memory_peak_bytes"] = mem
    log(f"memory: {json.dumps(mem_parts)}")

    # -- per-layer metrics (traced run) ------------------------------------
    metrics_out = {}
    breakdown = None
    if args.trace:
        from chipbench import layers
        # The profiler stalls the host when it starts and stops, so a
        # traced run reads its host-side layers over the window's steps
        # before the profiler started.
        n_clean = min(int(tw["after_open"]), steps)
        clean = intervals[:n_clean]
        clean_wall = sum(clean)
        spans = read_program_spans(log_dir)
        ctx = {
            "clock": clock, "steps": n_clean, "wall": clean_wall,
            "t_open": clock.t_open, "t_close": clock.t_open + clean_wall,
            "clean_intervals": clean,
            "e2e": dict(e2e, train_throughput=(
                n_clean * gb / clean_wall / chips)),
            "t0": T0, "chips": chips, "global_batch": gb,
            "config": cfg_model, "mix": mix, "peak": peak,
            "telemetry": telem, "spans": spans,
            "trace": layers.load_device_trace(log_dir, clock, spans),
        }
        log(f"traced run: host-side layers over the first {n_clean} "
            f"window steps ({clean_wall:.3f} s), before the profiler")
        wanted = [m for m in manifest["per_layer"]
                  if cell["name"] in m.get("workloads", [cell["name"]])]
        metrics_out = layers.read_all(wanted, ctx)
        if ctx["trace"] is not None:
            device["busy_s"] = ctx["trace"]["busy_s"]
            device["window_s"] = ctx["trace"]["window_s"]
            breakdown = ctx["trace"]["breakdown"]
            if args.keep_trace:
                os.makedirs(os.path.dirname(args.keep_trace) or ".",
                            exist_ok=True)
                shutil.copy(ctx["trace"]["path"], args.keep_trace)
    else:
        units = {m["name"]: m["unit"] for m in manifest["end_to_end"]}
        metrics_out = {k: {"value": v, "unit": units[k]}
                       for k, v in e2e.items() if k in units}

    # -- the output check: after the window, memory read, state freed ----
    shutil.rmtree(os.path.join(RUN_DIR, "ckpt"), ignore_errors=True)
    del summary
    gc.collect()
    t_ref = time.perf_counter()
    batches = traffic.batches(
        mix, args.seed, cfg_model["image_size"],
        cfg_model["num_classes"], chips, FOLLOWED_STEPS,
        workers=min(int(mix["workers"]), 16))
    followed = c["reference"].follow(cfg_model, args.seed, batches,
                                     replicas=chips)
    numbers = checks.compare(
        checks.program_side(clock.captured, c["program"], cfg_model),
        checks.reference_side(followed))
    where = numbers.pop("_where")
    if args.rehearsal:
        limits = REHEARSAL["limits"]  # not a measurement
    else:
        limits = checks.limits_for(cell["name"])
    correct, compared = checks.judge(numbers, limits)
    log(f"reference: {FOLLOWED_STEPS} steps in "
        f"{time.perf_counter() - t_ref:.1f} s; worst leaves {where}")

    shutil.rmtree(RUN_DIR, ignore_errors=True)
    with contextlib.suppress(OSError):
        os.rmdir(RUNS)  # unless another run of this checkout is using it
    result = {"correct": bool(correct), "attempted": steps,
              "failed": bad, "metrics": metrics_out, "device": device}
    if breakdown is not None:
        result["breakdown"] = breakdown
    result["compared"] = compared
    for k, v in compared.items():
        log(f"compared {k}: {v['value']!r} (limit {v['limit']!r})")
    sys.stdout.flush()
    print(json.dumps(result), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
