#!/usr/bin/env python3
"""chip_smoke.py — the standing proof that the trainer starts on the chip.

Drives the system's main path the way a user does — ``python -m
imagent_tpu`` (``imagent_tpu/__main__.py`` -> ``engine.run``) — at the
repo's north-star configuration, published ResNet-50 widths, nothing
shrunk but the number of steps:

  1. ``--backend=tpu --arch=resnet50 --image-size=224 --num-classes=1000
     --batch-size=256``, bf16, SGD, synthetic data made from ``--seed``:
     one epoch of train steps, the validation pass, ``--save-model`` so
     one checkpoint commits;
  2. the same command with ``--resume --epochs=2``: restores the
     checkpoint, loads the step executables the first invocation left in
     the compile cache (hits > 0, nothing compiled), trains and evaluates
     one more epoch.

Then it checks what came out, by the repo's own means (the engine's
telemetry.jsonl and start-up lines): finite losses, epoch 2's loss is not
epoch 1's (the state really updated and really restored), the
checkpoint committed and restored, the warm start hit — and the
telemetry against itself: the goodput phases sum to the epoch wall, no
steady step is classified ``compile`` (dispatch is asynchronous on a
real chip), the chip accountant's MFU is over the 197 TFLOP/s v5e entry,
and the device kind is a key of the peak table (an unknown chip is an
error, never a silent "peak unknown").

Processes: this parent NEVER imports JAX — a parent that has touched JAX
holds the chip, and a child that needs it then fails or hangs. The two
invocations run as children, one after the other, each the only process
on the chip while it lives (its ``--workers`` data pool stays off JAX).
The default phase sees exactly one chip even on a four-chip host: the
children's environment limits the visible devices before JAX starts.

Options:
  --four-chips     ONLY the path across chips, on a four-chip host: the
                   same ResNet-50 step through the engine on the (data=4)
                   mesh at per-chip batch 256, and the one-chip run it is
                   compared with at the same global batch through the
                   engine's --global-batch / grad-accum contract. The
                   ``count`` of the last line is then 4.
  --cpu-rehearsal  tiny shapes on the CPU backend (``--backend=cpu``):
                   finds wrong paths, arguments and control flow without
                   the chip. Never prints ``"platform": "tpu"``, and its
                   times are not device metrics.

The compile cache is wherever ``JAX_COMPILATION_CACHE_DIR`` says, else
``<checkout>/.jax_cache`` (``imagent_tpu.compilecache.resolve_cache_dir``
— the children resolve it themselves); run artifacts go under
``<checkout>/runs/chip_smoke``. Both are git-ignored; nothing else is
left behind.

Exit code 0 and a last stdout line
``{"ok": true, "device": {"platform": "tpu", "kind": "...", "count": 1}}``
only if every phase and every check passed; otherwise non-zero with
``"ok": false`` — nothing is caught and passed over.
"""

from __future__ import annotations

import argparse
import json
import math
import os
import re
import shutil
import subprocess
import sys
import time

sys.dont_write_bytecode = True  # leave no __pycache__ in the checkout

HERE = os.path.dirname(os.path.abspath(__file__))
RUN_ROOT = os.path.join(HERE, "runs", "chip_smoke")

# One chip for the child, whatever the host holds: libtpu reads these
# before JAX initializes. On a one-chip machine they describe what is
# there anyway.
ONE_CHIP_ENV = {
    "TPU_VISIBLE_CHIPS": "0",
    "TPU_CHIPS_PER_PROCESS_BOUNDS": "1,1,1",
    "TPU_PROCESS_BOUNDS": "1,1,1",
}
V5E_KINDS = ("TPU v5 lite", "TPU v5e")
V5E_PEAK_TFLOPS = 197.0  # published bf16 peak of one v5e chip
CHILD_TIMEOUT_S = 900
EPOCH_IMAGES = 4096  # default phase: 16 train steps at batch 256
# Synthetic-data generator processes — more than 0 on purpose: the
# loader's spawn pool must stay off JAX (a worker that touched the chip
# would fail or hang the run).
WORKERS = max(2, min(12, (os.cpu_count() or 4) - 1))


class SmokeFailure(Exception):
    pass


# The device of the last line, as the first training process reported
# it — remembered as soon as it is known, so a later failing check
# still names where it ran.
SEEN_DEVICE: dict = {}


def check(cond: bool, what: str) -> None:
    if not cond:
        raise SmokeFailure(what)


def say(msg: str) -> None:
    print(msg, flush=True)


# ----------------------------------------------------------------- children


def run_engine(name: str, flags: list[str], *, one_chip: bool,
               rehearsal: bool, virtual_devices: int = 0) -> dict:
    """One ``python -m imagent_tpu`` invocation as a child process.
    Returns its parsed outputs; raises SmokeFailure on a non-zero exit."""
    root = os.path.join(RUN_ROOT, name.split(".")[0])
    log_path = os.path.join(RUN_ROOT, f"{name}.log")
    env = dict(os.environ, PYTHONDONTWRITEBYTECODE="1",
               PYTHONUNBUFFERED="1")
    if rehearsal:
        env["JAX_PLATFORMS"] = "cpu"
        xla = " ".join(
            f for f in env.get("XLA_FLAGS", "").split()
            if "xla_force_host_platform_device_count" not in f)
        env["XLA_FLAGS"] = (
            f"{xla} --xla_force_host_platform_device_count="
            f"{max(virtual_devices, 1)}").strip()
    elif one_chip:
        env.update(ONE_CHIP_ENV)
    cmd = [sys.executable, "-m", "imagent_tpu", *flags,
           f"--log-dir={os.path.join(root, 'tb')}",
           f"--ckpt-dir={os.path.join(root, 'ckpt')}"]
    say(f"[{name}] $ " + " ".join(cmd[1:]))
    t0 = time.perf_counter()
    with open(log_path, "w") as log:
        proc = subprocess.Popen(cmd, cwd=HERE, env=env, stdout=log,
                                stderr=subprocess.STDOUT)
        try:
            rc = proc.wait(timeout=CHILD_TIMEOUT_S)
        except subprocess.TimeoutExpired:
            proc.kill()
            proc.wait()
            rc = -9
    wall = time.perf_counter() - t0
    with open(log_path, errors="replace") as f:
        out = f.read()
    if rc != 0:
        say(f"[{name}] FAILED rc={rc} after {wall:.1f}s; tail of "
            f"{log_path}:")
        say("\n".join(out.splitlines()[-40:]))
        raise SmokeFailure(f"{name}: `python -m imagent_tpu` exited {rc}"
                           + (" (timed out)" if rc == -9 else ""))
    with open(os.path.join(root, "tb", "telemetry.jsonl")) as f:
        events = [json.loads(line) for line in f if line.strip()]
    # This invocation is the LAST attempt in the log (a resumed run
    # appends to the first's file).
    starts = [i for i, e in enumerate(events)
              if e.get("event") == "run_start"]
    check(bool(starts), f"{name}: no run_start in telemetry.jsonl")
    tail = events[starts[-1]:]
    check(any(e.get("event") == "run_end" for e in tail),
          f"{name}: no run_end record — the run did not finish")
    return {"name": name, "root": root, "out": out, "wall_s": wall,
            "start": tail[0],
            "epochs": [e for e in tail if e.get("event") == "epoch"]}


def grep_line(run: dict, prefix: str) -> str:
    for line in run["out"].splitlines():
        if line.startswith(prefix):
            return line
    raise SmokeFailure(f"{run['name']}: no `{prefix}...` line in output")


def step_losses(run: dict) -> list[float]:
    """Per-step train losses from the --log-every lines, in step order
    (the lagged frontier prints step k's loss a couple of steps later;
    the ORDER is the step order, which is all the comparison needs)."""
    return [float(m.group(1)) for m in re.finditer(
        r"^  epoch \d+ step \d+/\d+ loss (\S+) ", run["out"], re.M)]


# ------------------------------------------------------------------- checks


def check_device(run: dict, *, rehearsal: bool, count: int) -> dict:
    start = run["start"]
    platform = start.get("platform")
    kind = start.get("device_kind")
    n = start.get("device_count")
    say(f"[{run['name']}] device as the training process saw it: "
        f"platform={platform} kind={kind!r} count={n}")
    want = "cpu" if rehearsal else "tpu"
    check(platform == want,
          f"{run['name']}: trained on platform {platform!r}, not {want!r}")
    check(n == count,
          f"{run['name']}: {n} device(s) visible, expected {count}")
    device = {"platform": platform, "kind": kind, "count": n}
    if not SEEN_DEVICE:
        SEEN_DEVICE.update(device)
    return device


def check_telemetry(run: dict, *, rehearsal: bool) -> None:
    """The telemetry against itself, for every epoch of the attempt."""
    start = run["start"]
    check(bool(run["epochs"]), f"{run['name']}: no epoch record")
    for rec in run["epochs"]:
        ep = rec["epoch"] + 1
        phases = rec["phases"]
        wall = rec["wall_s"]
        total = sum(phases.values())
        say(f"[{run['name']}] epoch {ep} goodput partition: wall "
            f"{wall:.3f}s = " + " + ".join(
                f"{k} {v:.3f}" for k, v in phases.items() if v)
            + f"; goodput {rec['goodput']:.4f}")
        check(abs(total - wall) <= 0.01 * wall + 0.01,
              f"{run['name']}: epoch {ep} phases sum to {total:.3f}s, "
              f"wall is {wall:.3f}s")
        # Every step executable was compiled (or loaded) AOT before the
        # epoch began: inside the partition a `compile` second is a
        # steady dispatch that blocked past the threshold.
        check(phases["compile"] == 0.0,
              f"{run['name']}: epoch {ep} classified "
              f"{phases['compile']:.3f}s of steady dispatch as `compile`")
        check(int(rec["counters"].get("recompiles", 0)) == 0,
              f"{run['name']}: epoch {ep} recompiled mid-run")
        step = rec["step_ms"]
        n_steps = int(step.get("n", 0))
        check(n_steps > 0, f"{run['name']}: epoch {ep} sampled no step")
        say(f"[{run['name']}] epoch {ep} steady step (dispatch-to-"
            f"dispatch, gated by a real block on step k-2's result): "
            f"p50 {step['p50_ms']:.2f} ms, p95 {step['p95_ms']:.2f} ms "
            f"over {n_steps} step(s)")
        acct = rec.get("chipacct")
        check(acct is not None, f"{run['name']}: no chipacct sub-record")
        hbm = rec.get("hbm") or {}
        say(f"[{run['name']}] epoch {ep} chip accountant: peak "
            f"{acct.get('peak_tflops')} TFLOP/s, achieved "
            f"{acct.get('tflops_per_chip')} TFLOP/s/chip, MFU "
            f"{acct.get('mfu')}; HBM peak modeled "
            f"{(acct.get('modeled_peak_bytes') or 0) / 2**30:.2f} GiB vs "
            f"reported {hbm.get('peak_bytes_in_use', 0) / 2**30:.2f} GiB "
            f"of {hbm.get('bytes_limit', 0) / 2**30:.2f} GiB")
        if rehearsal:
            continue
        kind = start.get("device_kind")
        check(acct.get("peak_tflops") is not None,
              f"device kind {kind!r} is not in the peak table "
              "(imagent_tpu/utils/flops.py) — an unknown chip is an "
              "error, not a run without MFU")
        if kind in V5E_KINDS:
            check(acct["peak_tflops"] == V5E_PEAK_TFLOPS,
                  f"MFU is over {acct['peak_tflops']} TFLOP/s, not the "
                  f"{V5E_PEAK_TFLOPS} v5e entry")
        mfu, tf = acct.get("mfu"), acct.get("tflops_per_chip")
        check(mfu is not None and tf is not None,
              f"{run['name']}: epoch {ep} has no MFU")
        check(0.0 < mfu <= 1.0,
              f"{run['name']}: epoch {ep} MFU {mfu} is not in (0, 1] — "
              "the accountant's seconds do not cover the device's work")
        check(abs(mfu - tf / acct["peak_tflops"]) < 1e-3,
              f"{run['name']}: MFU {mfu} != {tf}/{acct['peak_tflops']}")
        # The same flops over the whole epoch wall can only be lower.
        flops = acct["model_flops_per_step"] * n_steps
        floor = flops / wall / 1e12 / start["device_count"]
        check(tf >= floor * 0.999,
              f"{run['name']}: achieved {tf} TFLOP/s/chip is below the "
              f"whole-epoch-wall floor {floor:.3f}")
        check(hbm.get("peak_bytes_in_use", 0) > 0
              and hbm["peak_bytes_in_use"] <= hbm.get("bytes_limit", 0),
              f"{run['name']}: reported HBM peak {hbm} is not within "
              "the device limit")
        check(acct.get("modeled_peak_bytes")
              and acct["modeled_peak_bytes"] <= hbm["bytes_limit"],
              f"{run['name']}: modeled HBM peak does not fit the chip")
        check(hbm.get("bytes_in_use", 0)
              >= (acct.get("state_bytes") or {}).get("total", 0),
              f"{run['name']}: the device reports less memory in use "
              "than the accountant's resident train state")


def cache_stamp(run: dict) -> dict:
    stamp = run["start"].get("compile_cache")
    check(isinstance(stamp, dict),
          f"{run['name']}: no compile_cache stamp in run_start")
    say(f"[{run['name']}] " + grep_line(run, "compile cache: "))
    return stamp


# ------------------------------------------------------------------- phases


def base_flags(args, *, rehearsal: bool) -> list[str]:
    if rehearsal:
        return ["--backend=cpu", "--arch=resnet18", "--image-size=32",
                "--num-classes=8", "--batch-size=8", "--lr=0.1",
                "--warmup-epochs=5", "--workers=2",
                f"--seed={args.seed}", "--dataset=synthetic"]
    # lr 0.1 with the 5-epoch linear warm-up of the large-batch
    # ResNet-50 recipe (Goyal et al. 2017): the first epochs run at
    # 0.02 and 0.04. Without it the first 16 steps at 0.1 send the
    # loss from 7.5 to 11 (first v5e run, PR 21).
    return ["--backend=tpu", "--arch=resnet50", "--image-size=224",
            "--num-classes=1000", "--batch-size=256", "--lr=0.1",
            "--warmup-epochs=5", f"--workers={WORKERS}",
            f"--seed={args.seed}", "--dataset=synthetic"]


def default_phase(args) -> None:
    rehearsal = args.cpu_rehearsal
    size = 128 if rehearsal else EPOCH_IMAGES
    flags = base_flags(args, rehearsal=rehearsal) + [
        f"--synthetic-size={size}", "--log-every=4", "--save-model"]

    first = run_engine("train", flags + ["--epochs=1"], one_chip=True,
                       rehearsal=rehearsal)
    device = check_device(first, rehearsal=rehearsal, count=1)
    say(f"[train] start-to-finish {first['wall_s']:.1f}s")
    say("[train] " + grep_line(first, "mesh "))
    cold = cache_stamp(first)
    say("[train] start-up: "
        + (f"COLD — compiled {cold['misses']} step executable(s) in "
           f"{cold['compile_s']:.2f}s" if cold["misses"] else
           f"warm — the cache at $JAX_COMPILATION_CACHE_DIR came "
           f"populated; loaded {cold['hits']} in {cold['load_s']:.2f}s"))
    say("[train] " + grep_line(first, "chip accountant: "))
    check_telemetry(first, rehearsal=rehearsal)
    loss1 = epoch_loss(first, 0)
    check(cold["misses"] >= 1 or cold["hits"] >= 1,
          f"train: start-up neither compiled nor loaded a step: {cold}")

    meta_path = os.path.join(first["root"], "ckpt", "last_meta.json")
    check(os.path.isfile(meta_path),
          "train: no committed checkpoint (ckpt/last_meta.json)")
    with open(meta_path) as f:
        meta = json.load(f)
    check(int(meta.get("epoch", -1)) == 0,
          f"train: checkpoint meta names epoch {meta.get('epoch')}, not 0")
    say(f"[train] checkpoint committed: last (epoch {meta['epoch'] + 1}, "
        f"format {meta.get('ckpt_format')})")

    second = run_engine("train.resume",
                        flags + ["--epochs=2", "--resume"], one_chip=True,
                        rehearsal=rehearsal)
    device2 = check_device(second, rehearsal=rehearsal, count=1)
    check(device2 == device, "the resumed run saw another device: "
          f"{device2} vs {device}")
    say(f"[train.resume] start-to-finish {second['wall_s']:.1f}s")
    start2, ep2 = second["start"], second["epochs"]
    say("[train.resume] " + grep_line(second, "resumed from epoch 1"))
    check(start2.get("start_epoch") == 1
          and start2.get("restored") is not None,
          f"train.resume: did not restore the checkpoint: {start2}")
    warm = cache_stamp(second)
    check(warm["key"] == cold["key"],
          "the two invocations computed different compile fingerprints")
    check(warm["hits"] > 0 and warm["misses"] == 0,
          f"train.resume: expected compile-cache hits and 0 new step "
          f"compiles, got {warm['hits']} hit(s) / {warm['misses']} "
          "compiled")
    check(int(warm.get("fallback_steps", 0)) == 0
          and all(int((r.get("compilecache") or {})
                      .get("fallback_steps", 0)) == 0 for r in ep2),
          "train.resume: steps fell back off the loaded executables")
    say(f"[train.resume] start-up: warm — loaded {warm['hits']} step "
        f"executable(s) in {warm['load_s']:.2f}s, 0 compiled (first "
        f"invocation: {cold['startup_s']:.2f}s)")
    check_telemetry(second, rehearsal=rehearsal)
    check([r["epoch"] for r in ep2] == [1],
          f"train.resume: trained epochs {[r['epoch'] for r in ep2]}, "
          "expected exactly the second")
    loss2 = epoch_loss(second, 1)
    say(f"[smoke] train loss epoch 1 {loss1:.6f} -> epoch 2 (resumed) "
        f"{loss2:.6f}")
    check(loss2 != loss1,
          "epoch 2's loss equals epoch 1's — the state did not update")
    # A restored state that reached the loaded donated executables
    # damaged (the old-runtime defect the removed wash fenced) reads as
    # NaN or a blown-up loss; a healthy early run sits near ln(classes).
    sane = 10.0 * math.log(8 if rehearsal else 1000)
    check(loss2 < sane,
          f"epoch 2's loss {loss2} after the resume is not that of a "
          f"sanely restored model (bound {sane:.1f})")


def epoch_loss(run: dict, epoch: int) -> float:
    """The epoch's train/val metrics from the run summary lines; all
    must be finite."""
    m = re.search(
        rf"^Epoch {epoch + 1}: lr .*? train loss (\S+) .*?\| val loss "
        r"(\S+) ", run["out"], re.M)
    check(m is not None, f"{run['name']}: no epoch-{epoch + 1} summary "
          "line with train and val loss in the output")
    train, val = float(m.group(1)), float(m.group(2))
    say(f"[{run['name']}] epoch {epoch + 1}: train loss {train:.6f}, "
        f"val loss {val:.6f}")
    check(math.isfinite(train) and math.isfinite(val),
          f"{run['name']}: non-finite loss (train {train}, val {val})")
    check(all(r["epoch"] != epoch or not r["counters"].get("bad_steps")
              for r in run["epochs"]),
          f"{run['name']}: epoch {epoch + 1} skipped non-finite steps")
    return train


def four_chip_phase(args) -> None:
    """ONLY the path across chips and what it is compared with."""
    rehearsal = args.cpu_rehearsal
    per_chip = 8 if rehearsal else 256
    gb = 4 * per_chip
    steps = 6
    flags = base_flags(args, rehearsal=rehearsal) + [
        f"--global-batch={gb}", f"--synthetic-size={steps * gb}",
        "--log-every=1", "--epochs=1"]
    if rehearsal:
        # The tiny rehearsal net (8 rows per BatchNorm, 1x1 deep
        # stages) amplifies rounding differences chaotically within
        # three steps; in fp32 at a small lr the two programs stay
        # together and the rehearsal can hold the comparison's control
        # flow to the real tolerances.
        flags += ["--no-bf16", "--lr=0.005"]

    mesh4 = run_engine("mesh4", flags, one_chip=False,
                       rehearsal=rehearsal, virtual_devices=4)
    check_device(mesh4, rehearsal=rehearsal, count=4)
    mesh_line = grep_line(mesh4, "mesh ")
    say("[mesh4] " + mesh_line)
    check("{'data': 4, 'pipe': 1, 'model': 1}" in mesh_line
          and f"global_batch {gb}" in mesh_line,
          f"mesh4: not the (data=4) mesh at global batch {gb}")
    cache_stamp(mesh4)
    acct_line = grep_line(mesh4, "chip accountant: ")
    say("[mesh4] " + acct_line)
    m = re.search(r"all-reduce x(\d+)", acct_line)
    check(m is not None and int(m.group(1)) > 0,
          "mesh4: the compiled train step contains no all-reduce")
    check_telemetry(mesh4, rehearsal=rehearsal)
    ep4 = mesh4["epochs"]
    counters = ep4[0]["counters"]
    check(int(counters.get("batch_shard_devices", 0)) == 4
          and int(counters.get("batch_shard_rows", 0)) == per_chip,
          f"mesh4: the staged batch is not {per_chip} rows on each of 4 "
          f"devices: {counters}")
    say(f"[mesh4] batch shards: {per_chip} rows on each of "
        f"{int(counters['batch_shard_devices'])} devices")
    if not rehearsal:
        devs = (ep4[0].get("hbm") or {}).get("devices") or []
        say("[mesh4] HBM in use per device: " + ", ".join(
            f"#{d['id']} {d.get('bytes_in_use', 0) / 2**30:.2f} GiB "
            f"(peak {d.get('peak_bytes_in_use', 0) / 2**30:.2f})"
            for d in devs))
        # Each chip holds its own replica of the train state (and its
        # batch shards); the runtime's counters do not include the
        # step program's temporaries (see PERF.md).
        state = ep4[0]["chipacct"]["state_bytes"]["total"]
        check(len(devs) == 4
              and all(d.get("bytes_in_use", 0) >= state for d in devs),
              f"mesh4: not every device holds the {state / 2**20:.0f} MiB "
              f"train state: {devs}")

    one = run_engine("accum4", flags, one_chip=True, rehearsal=rehearsal,
                     virtual_devices=1)
    check_device(one, rehearsal=rehearsal, count=1)
    one_mesh = grep_line(one, "mesh ")
    say("[accum4] " + one_mesh)
    check("{'data': 1, 'pipe': 1, 'model': 1}" in one_mesh
          and f"global_batch {gb} (grad_accum 4)" in one_mesh,
          f"accum4: not one chip at global batch {gb} / grad_accum 4")
    cache_stamp(one)
    one_acct = grep_line(one, "chip accountant: ")
    say("[accum4] " + one_acct)
    # (The CPU backend keeps a size-1 psum as an all-reduce; the TPU
    # compiler drops it.)
    check(rehearsal or re.search(r"all-reduce x[1-9]", one_acct) is None,
          "accum4: a one-chip step should not all-reduce: " + one_acct)
    check_telemetry(one, rehearsal=rehearsal)

    a, b = step_losses(mesh4), step_losses(one)
    check(len(a) >= 3 and len(a) == len(b),
          f"expected the same >= 3 logged steps, got {len(a)} and {len(b)}")
    # bf16 tolerance: at step 1 (same weights, same rows) the two
    # programs differ only in how XLA fused and ordered the bf16 work;
    # later steps carry that rounding through the update.
    rels = []
    for k, (x, y) in enumerate(zip(a, b), 1):
        rels.append(abs(x - y) / max(abs(x), abs(y), 1e-9))
        say(f"[four-chips] step {k}: loss (data=4) {x:.4f} vs "
            f"(1 chip, accum 4) {y:.4f}  rel diff {rels[-1]:.2e}")
    for k, (x, y, rel) in enumerate(zip(a, b, rels), 1):
        tol = 2e-3 if k == 1 else 2e-2
        check(math.isfinite(x) and math.isfinite(y) and rel <= tol,
              f"step {k} loss differs beyond bf16 tolerance {tol}: "
              f"{x} vs {y}")
    ea, eb = epoch_loss(mesh4, 0), epoch_loss(one, 0)
    check(abs(ea - eb) / max(abs(ea), abs(eb)) <= 2e-2,
          f"epoch train loss differs: {ea} vs {eb}")


# --------------------------------------------------------------------- main


def main() -> int:
    p = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    p.add_argument("--four-chips", action="store_true",
                   help="run ONLY the (data=4) mesh path and its "
                        "one-chip comparison (needs a four-chip host)")
    p.add_argument("--cpu-rehearsal", action="store_true",
                   help="tiny shapes on the CPU backend; never reports "
                        "the tpu platform")
    p.add_argument("--seed", type=int, default=0,
                   help="seeds the weights and the synthetic data")
    args = p.parse_args()

    if not os.path.isfile(os.path.join(HERE, "imagent_tpu",
                                       "__main__.py")):
        print("chip_smoke.py drives `python -m imagent_tpu` from the "
              f"checkout it sits in; {HERE} holds no imagent_tpu "
              "package", file=sys.stderr)
        return 2
    shutil.rmtree(RUN_ROOT, ignore_errors=True)
    os.makedirs(RUN_ROOT)
    t0 = time.perf_counter()
    try:
        four_chip_phase(args) if args.four_chips else default_phase(args)
        ok = True
    except SmokeFailure as e:
        say(f"SMOKE FAILED: {e}")
        ok = False
    say(f"[smoke] total {time.perf_counter() - t0:.1f}s"
        + (" [CPU REHEARSAL — not a chip run; no time above is a "
           "device metric]" if args.cpu_rehearsal else ""))
    if SEEN_DEVICE:
        print(json.dumps({"ok": ok, "device": SEEN_DEVICE}), flush=True)
    else:
        # No device was ever seen (no accelerator, or the first child
        # died before reporting one): no result line on stdout at all.
        print(json.dumps({"ok": False}), file=sys.stderr, flush=True)
    return 0 if ok else 1


if __name__ == "__main__":
    sys.exit(main())
