"""Chip accountant: XLA cost/memory attribution, MFU, and the OOM
preflight sentinel (ISSUE 19).

At step-build time the engine hands this module the jitted train/eval
steps plus the placed TrainState; ``build_account`` lowers and
compiles them once (AOT — the products are the point, not the
executable) and extracts XLA's own ``cost_analysis()`` (flops, bytes
accessed) and ``memory_analysis()`` (argument / output / temp /
generated-code bytes) per device. Combined with:

* the per-device-kind bf16 peak registry (``utils/flops.py``) — or an
  operator ``--peak-tflops`` override for kinds the registry does not
  know; when neither is available the account is HONEST about it:
  achieved TFLOP/s is still reported, the MFU ratio is skipped;
* analytic model FLOPs per optimizer step (3x forward — the
  ``utils/flops.py`` convention, so remat overhead counts against MFU
  rather than inflating it);
* a sharding-aware per-leaf byte attribution of the TrainState
  (params / opt-state / EMA / batch-stats): each placed leaf's
  PER-DEVICE resident bytes come from its ``sharding.shard_shape`` —
  pure metadata, correct across dp / fsdp / zero1 / tp / pp without
  re-deriving the mesh math, and free of device syncs;

the account yields zero-step-cost MFU: the goodput wall partition
already measures the step loop's seconds (``dispatch + step_drain +
input_wait``) and the step count, so ``TelemetrySession.epoch_end``
derives achieved-flops/s → TFLOP/s-per-chip → MFU from numbers the
step loop was recording anyway. Nothing here runs inside the step loop, and the
jaxlint ``blocking-call-in-step-loop`` rule now rejects
``cost_analysis()`` / ``memory_analysis()`` / ``memory_stats()``
calls that ever migrate into one.

The OOM preflight sentinel: after compile but before step 0 the
modeled peak (args + output + temps + code − aliased) is compared
against the device HBM limit (``device.memory_stats()``; the
``--hbm-budget-gb`` override stands in where the backend reports none
— CPU has no limit, which is also what makes the refusal drill
CPU-testable). Over budget → the engine refuses with fatal-config
exit 78 and the per-component byte table in the tombstone/flightrec
detail; a runtime RESOURCE_EXHAUSTED gets classified with the same
breakdown (``classify_oom`` + ``oom_detail``).

Module import is jax-free (the status/summarize/regress renderers
read the account's JSON); every jax touch is lazy inside the capture
functions, which run exactly once at startup.
"""

from __future__ import annotations

import re
import time
from typing import Any

# Account schema note (events.py): the epoch record's ``chipacct``
# sub-record is an ADDITION to telemetry schema 1, not a bump — old
# readers ignore it, new readers treat its absence as "accountant off
# or log predates it".

_EXE_FIELDS = ("flops", "bytes_accessed")
_MEM_FIELDS = ("args_bytes", "output_bytes", "temp_bytes",
               "code_bytes", "alias_bytes", "modeled_peak_bytes")
_COMPONENTS = ("params", "opt_state", "ema", "batch_stats")


def fmt_bytes(n: float | None) -> str:
    """Compact human bytes (the flightrec detail budget is 500 chars —
    every component entry must stay short)."""
    if n is None:
        return "?"
    n = float(n)
    for unit, div in (("GiB", 2 ** 30), ("MiB", 2 ** 20),
                      ("KiB", 2 ** 10)):
        if abs(n) >= div:
            return f"{n / div:.2f}{unit}"
    return f"{int(n)}B"


# ------------------------------------------------- XLA product extraction

def extract_cost(compiled) -> dict | None:
    """``cost_analysis()`` → {"flops", "bytes_accessed"} floats.

    jax returns a per-partition list of dicts on some versions and a
    bare dict on others; absent keys (backends that do not model a
    quantity) are None. Never raises — an accountant failure must not
    take the run down."""
    try:
        ca = compiled.cost_analysis()
    except Exception:  # noqa: BLE001 - backend-optional API
        return None
    if isinstance(ca, (list, tuple)):
        ca = ca[0] if ca else None
    if not isinstance(ca, dict):
        return None
    out = {}
    for field, key in (("flops", "flops"),
                       ("bytes_accessed", "bytes accessed")):
        v = ca.get(key)
        out[field] = float(v) if v is not None else None
    return out


def extract_memory(compiled) -> dict | None:
    """``memory_analysis()`` → per-device byte attribution, plus the
    modeled peak: args + output + temps + generated code − aliased
    (donated inputs reuse their argument buffers)."""
    try:
        mem = compiled.memory_analysis()
    except Exception:  # noqa: BLE001 - backend-optional API
        return None
    if mem is None:
        return None
    fields = {
        "args_bytes": getattr(mem, "argument_size_in_bytes", None),
        "output_bytes": getattr(mem, "output_size_in_bytes", None),
        "temp_bytes": getattr(mem, "temp_size_in_bytes", None),
        "code_bytes": getattr(mem, "generated_code_size_in_bytes",
                              None),
        "alias_bytes": getattr(mem, "alias_size_in_bytes", None),
    }
    if all(v is None for v in fields.values()):
        return None
    out = {k: (float(v) if v is not None else None)
           for k, v in fields.items()}
    peak = sum(out[k] or 0.0 for k in ("args_bytes", "output_bytes",
                                       "temp_bytes", "code_bytes"))
    out["modeled_peak_bytes"] = peak - (out["alias_bytes"] or 0.0)
    return out


_COLLECTIVE_OPS = ("all-reduce", "all-gather", "reduce-scatter",
                   "all-to-all", "collective-permute")


def extract_collectives(compiled) -> dict | None:
    """How many of each collective the compiler put into the
    executable (sync and ``-start`` async forms alike), from its
    optimized HLO text — the startup evidence that a multi-chip step
    really reduces across chips (and that a one-chip step does not).
    None where the runtime cannot render the text."""
    try:
        text = compiled.as_text()
    except Exception:  # noqa: BLE001 - backend-optional API
        return None
    if not text:
        return None
    return {op: len(re.findall(rf"= [^=\n]*\b{op}(?:-start)?\(", text))
            for op in _COLLECTIVE_OPS}


def capture_executable(jitted, *args) -> tuple[dict | None, float]:
    """Lower + compile ``jitted`` on ``args`` (concrete arrays and/or
    ShapeDtypeStructs) and extract both analyses. Returns
    ``(facts, seconds)``; facts is None when the capture failed.

    The AOT compile does NOT land in the jit cache, so a legacy
    caller pays one extra startup compile per captured executable —
    the seconds are returned so the engine can attribute them to the
    ``compile`` goodput phase (and ``--no-chipacct`` skips the whole
    thing). The engine's default path no longer comes here: it hands
    ``build_account`` its own AOT-compiled executables
    (``compiled_train=``/``compiled_eval=``, compilecache.py) and the
    account extracts the analyses for free."""
    t0 = time.perf_counter()
    try:
        compiled = jitted.lower(*args).compile()
    except Exception:  # noqa: BLE001 - accountant is best-effort
        return None, time.perf_counter() - t0
    return extract_facts(compiled), time.perf_counter() - t0


# ------------------------------------------- state byte attribution

def state_component_bytes(state) -> dict:
    """Per-device resident bytes of the TrainState, by component.

    Sharding-aware via each placed leaf's ``sharding.shard_shape`` —
    a replicated leaf charges its full size, an fsdp/zero1/tp/pp
    shard only its per-device slice. Metadata only: no transfer, no
    sync (the no-sync contract the jaxlint select-run pins)."""
    import jax

    def leaf_bytes(x) -> float:
        shape = getattr(x, "shape", None)
        dtype = getattr(x, "dtype", None)
        if shape is None or dtype is None:
            return 0.0
        sharding = getattr(x, "sharding", None)
        if sharding is not None:
            try:
                shape = sharding.shard_shape(tuple(shape))
            except Exception:  # noqa: BLE001 - odd sharding kinds
                pass
        n = 1
        for d in shape:
            n *= int(d)
        return float(n * dtype.itemsize)

    def tree_bytes(tree) -> float:
        if tree is None:
            return 0.0
        return float(sum(leaf_bytes(x) for x in jax.tree.leaves(tree)))

    ema = (tree_bytes(getattr(state, "ema_params", None))
           + tree_bytes(getattr(state, "ema_batch_stats", None)))
    out = {
        "params": tree_bytes(getattr(state, "params", None)),
        "opt_state": tree_bytes(getattr(state, "opt_state", None)),
        "ema": ema,
        "batch_stats": tree_bytes(getattr(state, "batch_stats", None)),
    }
    out["total"] = float(sum(out.values()))
    return out


# ------------------------------------------------------ peak registry

def resolve_peak_tflops(device_kind: str,
                        override: float = 0.0
                        ) -> tuple[float | None, str | None]:
    """(peak bf16 TFLOP/s, source) for a device kind. The operator
    ``--peak-tflops`` override wins (unlisted kinds, CPU test runs);
    otherwise the ``utils/flops.py`` registry; otherwise honest
    ``(None, None)`` — achieved TFLOP/s only, no MFU ratio."""
    if override and override > 0.0:
        return float(override), "override"
    from ..utils.flops import chip_peak_bf16_tflops
    peak = chip_peak_bf16_tflops(device_kind)
    if peak is not None:
        return float(peak), "registry"
    return None, None


def analytic_step_flops(arch: str, image_size: int, num_classes: int,
                        global_batch: int) -> float:
    """Analytic model FLOPs for one optimizer step at the GLOBAL batch
    (the 3x-forward convention, ``utils/flops.py``)."""
    from ..utils.flops import forward_flops, train_step_flops_per_image
    return float(train_step_flops_per_image(
        forward_flops(arch, image_size, num_classes)) * global_batch)


# ------------------------------------------------------- the account

def abstract_batch(mesh, global_batch: int, image_size: int,
                   transfer_dtype: str, with_mask: bool = False):
    """ShapeDtypeStructs matching what ``shard_batch`` stages: images
    on the wire dtype, int32 labels, uint8 mask — all split over the
    data axis, exactly the shardings the real step sees."""
    import numpy as np

    import jax
    from jax.sharding import NamedSharding, PartitionSpec as P

    from ..cluster import DATA_AXIS

    if transfer_dtype == "bf16":
        import ml_dtypes
        img_dtype = np.dtype(ml_dtypes.bfloat16)
    elif transfer_dtype == "float32":
        img_dtype = np.dtype(np.float32)
    else:
        img_dtype = np.dtype(np.uint8)

    def sds(shape, dtype):
        spec = P(DATA_AXIS, *([None] * (len(shape) - 1)))
        return jax.ShapeDtypeStruct(shape, dtype,
                                    sharding=NamedSharding(mesh, spec))

    images = sds((global_batch, image_size, image_size, 3), img_dtype)
    labels = sds((global_batch,), np.int32)
    if with_mask:
        return images, labels, sds((global_batch,), np.uint8)
    return images, labels


def extract_facts(compiled) -> dict:
    """Both analyses off an ALREADY-compiled executable — the
    zero-cost half of ``capture_executable`` for the engine's AOT
    handoff (serialized-then-loaded executables keep both APIs)."""
    facts: dict[str, Any] = dict(extract_cost(compiled) or
                                 {f: None for f in _EXE_FIELDS})
    facts["memory"] = extract_memory(compiled)
    facts["collectives"] = extract_collectives(compiled)
    return facts


def build_account(*, train_step, eval_step, state, mesh, cfg,
                  global_batch: int, compiled_train=None,
                  compiled_eval=None) -> dict:
    """Capture everything knowable before step 0 into one JSON-safe
    account dict. Defensive throughout: a missing analysis on some
    backend degrades the account (None fields), never the run.

    ``compiled_train``/``compiled_eval``: pre-compiled executables
    from the engine's one-compile AOT startup (compilecache.py) —
    when provided, their analyses are read directly and the account
    pays NO compile of its own (``capture_s`` ~0). Without them
    (legacy callers, tests, ``--no-aot-steps``) the account compiles
    each jitted step itself, the duplicate this handoff exists to
    kill."""
    import numpy as np

    import jax
    from jax.sharding import NamedSharding, PartitionSpec as P

    device = jax.local_devices()[0]
    acct: dict[str, Any] = {
        "device_kind": str(device.device_kind),
        "n_devices": int(jax.device_count()),
        "global_batch": int(global_batch),
    }
    peak, src = resolve_peak_tflops(acct["device_kind"],
                                    cfg.peak_tflops)
    acct["peak_tflops"] = peak
    acct["peak_source"] = src
    try:
        acct["model_flops_per_step"] = analytic_step_flops(
            cfg.arch, cfg.image_size, cfg.num_classes, global_batch)
    except Exception:  # noqa: BLE001 - archs without a counter
        acct["model_flops_per_step"] = None

    if compiled_train is not None:
        t0 = time.perf_counter()
        train_facts = extract_facts(compiled_train)
        t_train = time.perf_counter() - t0
    else:
        lr_sds = jax.ShapeDtypeStruct(
            (), np.float32, sharding=NamedSharding(mesh, P()))
        images, labels = abstract_batch(
            mesh, global_batch, cfg.image_size, cfg.transfer_dtype)
        train_facts, t_train = capture_executable(
            train_step, state, images, labels, lr_sds)
    acct["train"] = train_facts
    acct["capture_s"] = round(t_train, 3)
    if compiled_eval is not None:
        t0 = time.perf_counter()
        acct["eval"] = extract_facts(compiled_eval)
        acct["capture_s"] = round(
            t_train + time.perf_counter() - t0, 3)
    elif eval_step is not None:
        ev = abstract_batch(mesh, global_batch, cfg.image_size,
                            cfg.transfer_dtype, with_mask=True)
        eval_facts, t_eval = capture_executable(eval_step, state, *ev)
        acct["eval"] = eval_facts
        acct["capture_s"] = round(t_train + t_eval, 3)
    else:
        acct["eval"] = None
    acct["reused_aot"] = compiled_train is not None
    acct["state_bytes"] = state_component_bytes(state)

    mem = (train_facts or {}).get("memory") or {}
    acct["modeled_peak_bytes"] = mem.get("modeled_peak_bytes")
    limit, limit_src = None, None
    if cfg.hbm_budget_gb and cfg.hbm_budget_gb > 0.0:
        limit, limit_src = float(cfg.hbm_budget_gb) * 2 ** 30, "budget"
    else:
        try:
            stats = device.memory_stats()
        except Exception:  # noqa: BLE001 - backend-optional API
            stats = None
        if stats and stats.get("bytes_limit"):
            limit, limit_src = float(stats["bytes_limit"]), "device"
    acct["hbm_limit_bytes"] = limit
    acct["limit_source"] = limit_src
    modeled = acct["modeled_peak_bytes"]
    if limit is None or modeled is None:
        acct["verdict"] = "unknown-limit" if modeled is not None \
            else "unmodeled"
        acct["headroom_bytes"] = None
    else:
        acct["headroom_bytes"] = limit - modeled
        acct["verdict"] = "ok" if modeled <= limit else "over"
    return acct


# --------------------------------------------------------- preflight

def byte_table(acct: dict) -> str:
    """One-line per-component byte table — the refusal/tombstone
    payload. Compact by construction: the flightrec detail field
    truncates at 500 chars."""
    mem = ((acct.get("train") or {}).get("memory")) or {}
    sb = acct.get("state_bytes") or {}
    parts = [f"modeled_peak={fmt_bytes(acct.get('modeled_peak_bytes'))}",
             f"args={fmt_bytes(mem.get('args_bytes'))}",
             f"out={fmt_bytes(mem.get('output_bytes'))}",
             f"temp={fmt_bytes(mem.get('temp_bytes'))}",
             f"code={fmt_bytes(mem.get('code_bytes'))}"]
    if mem.get("alias_bytes"):
        parts.append(f"alias=-{fmt_bytes(mem.get('alias_bytes'))}")
    parts.append(
        "state[" + " ".join(
            f"{k}={fmt_bytes(sb.get(k))}" for k in _COMPONENTS
            if sb.get(k)) + "]")
    if acct.get("hbm_limit_bytes") is not None:
        parts.append(f"limit={fmt_bytes(acct['hbm_limit_bytes'])}"
                     f"({acct.get('limit_source')})")
    return " ".join(parts)


def plan_line(acct: dict) -> str:
    """The startup plan print (master only) — the bench-smoke stage
    asserts the preflight verdict is present here."""
    mfu_part = (f"peak {acct['peak_tflops']:.0f} TFLOP/s "
                f"({acct['peak_source']})"
                if acct.get("peak_tflops")
                else "peak unknown (achieved TFLOP/s only; "
                     "--peak-tflops to set)")
    flops = acct.get("model_flops_per_step")
    flops_part = (f"{flops / 1e9:.2f} GFLOP/step" if flops
                  else "analytic flops unavailable")
    coll = ((acct.get("train") or {}).get("collectives")) or {}
    coll_part = ("train-step collectives: " + (", ".join(
        f"{op} x{n}" for op, n in coll.items() if n) or "none")
        if coll else "train-step collectives: unreadable")
    return (f"chip accountant: {acct.get('device_kind')} x"
            f"{acct.get('n_devices')}, {flops_part}, {mfu_part}; "
            f"{coll_part}; "
            f"preflight {acct.get('verdict')}: {byte_table(acct)}")


def preflight_error(acct: dict) -> str:
    """The fatal-config refusal text (engine maps ValueError → exit
    78); carries the per-component table so the tombstone/flightrec
    detail is actionable on its own."""
    return ("chip accountant preflight: modeled peak "
            f"{fmt_bytes(acct.get('modeled_peak_bytes'))}/device "
            "exceeds the HBM limit "
            f"{fmt_bytes(acct.get('hbm_limit_bytes'))} "
            f"({acct.get('limit_source')}); {byte_table(acct)} — "
            "shrink --batch-size, shard further (--fsdp/--zero1/--tp),"
            " raise --hbm-budget-gb, or --no-chipacct to bypass")


def check_preflight(acct: dict) -> None:
    """Raise ValueError (the engine's fatal-config ramp, exit 78) when
    the modeled peak exceeds the known limit."""
    if acct.get("verdict") == "over":
        raise ValueError(preflight_error(acct))


# ------------------------------------------------- runtime OOM triage

def classify_oom(exc: BaseException) -> bool:
    """Whether a runtime failure is a device out-of-memory — XLA
    surfaces RESOURCE_EXHAUSTED (jaxlib XlaRuntimeError) with an
    'Out of memory' / allocator message."""
    text = f"{type(exc).__name__}: {exc}"
    return ("RESOURCE_EXHAUSTED" in text
            or "Out of memory" in text
            or "out of memory" in text)


def oom_detail(acct: dict | None) -> str:
    """The flightrec/tombstone enrichment for a classified OOM."""
    if not acct:
        return "OOM (no chip account captured)"
    return f"OOM; {byte_table(acct)}"


# ----------------------------------------------------- MFU derivation

def epoch_perf(acct: dict | None, phases: dict, n_steps: int
               ) -> dict | None:
    """The per-epoch ``chipacct`` sub-record: zero-step-cost MFU from
    numbers the goodput partition already measured. Pure host floats —
    safe at the epoch boundary, nothing for the step loop.

    seconds = the compile-free step loop: dispatch + step_drain +
    input_wait. Dispatch is asynchronous, so the host cannot tell how
    much of an input wait the chip spent computing the step before —
    on an input-bound run (first seen on the v5e, PR 21: dispatch +
    step_drain were 0.25 s of a 4.0 s loop and read as 188% MFU) the
    two waits only mean something together. What comes out is the
    end-to-end utilization of the step loop — model flops x steps per
    second of loop, over chips x peak; input starvation counts against
    it, eval/checkpoint/compile do not. achieved = model_flops_per_step
    x steps / seconds; MFU only when the peak is known."""
    if not acct:
        return None
    flops = acct.get("model_flops_per_step")
    phases = phases or {}
    useful = float(phases.get("dispatch", 0.0)
                   + phases.get("step_drain", 0.0)
                   + phases.get("input_wait", 0.0))
    out: dict[str, Any] = {
        "verdict": acct.get("verdict"),
        "modeled_peak_bytes": acct.get("modeled_peak_bytes"),
        "state_bytes": acct.get("state_bytes"),
        "peak_tflops": acct.get("peak_tflops"),
        "model_flops_per_step": flops,
    }
    if flops and useful > 0.0 and n_steps > 0:
        achieved = flops * n_steps / useful
        per_chip = achieved / max(1, int(acct.get("n_devices") or 1))
        out["tflops_per_chip"] = round(per_chip / 1e12, 4)
        peak = acct.get("peak_tflops")
        if peak:
            out["mfu"] = round(per_chip / 1e12 / peak, 4)
        else:
            out["mfu"] = None
    else:
        out["tflops_per_chip"] = None
        out["mfu"] = None
    return out
