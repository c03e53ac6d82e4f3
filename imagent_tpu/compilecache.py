"""Warm starts: the persistent AOT executable cache (ISSUE 20).

Every production restart story — elastic shrink/grow (exec-restart +
full re-init), requeue-after-death, plain ``--resume`` — used to pay a
from-scratch XLA compile at the worst possible moment, plus one EXTRA
AOT compile per executable for the chip accountant's cost/memory
capture.  This module closes both gaps:

* **One-compile startup**: ``compile_steps`` lowers and compiles the
  train/eval steps ONCE via the AOT path (``jitted.lower(*args)
  .compile()`` — the same abstract batch the chip accountant already
  modeled) and hands the engine dispatch wrappers around the compiled
  executables.  The chip accountant reuses the SAME compiled objects
  for ``cost_analysis()``/``memory_analysis()`` (``build_account``'s
  ``compiled_train=``/``compiled_eval=`` handoff), so its
  ``capture_s`` collapses to ~0.
* **Persistent executable store**: the compiled products are
  serialized (``jax.experimental.serialize_executable``) under
  ``<cache dir>/aot/<key>/`` keyed by a COMPLETE compile fingerprint —
  device kind + count, mesh topology, world size, jax/jaxlib versions,
  global batch/accum, and every config field that reaches the step
  builders (``COMPILE_FIELDS``, pinned by the completeness guard in
  ``tests/test_compilecache.py``).  A restarted / requeued /
  resized-to-a-seen-topology run deserializes instead of recompiling;
  the XLA persistent cache in the same directory remains the second
  line of defense for everything else that compiles.
* **One place for the cache** (``resolve_cache_dir`` / ``arm``): where
  ``JAX_COMPILATION_CACHE_DIR`` is set, THAT directory is the cache
  and this code sets no other; where it is not, the cache is the fixed
  git-ignored ``<checkout>/.jax_cache`` — the path is part of XLA's
  cache key, so a directory that moves (mkdtemp, pid, time) never
  hits.  The engine, ``compilecache warm``, ``bench.py``,
  ``chip_smoke.py`` and the benchmarks all come through here; JAX's
  own switch (``JAX_ENABLE_COMPILATION_CACHE=false``) turns both
  halves off (the test suite does, for hermetic compiles).
* **Dispatch safety**: AOT executables are shape/dtype-specialized,
  but the fault drills deliberately change batch geometry mid-run
  (``step.shape_change`` crops, ``nan-grads`` promotes uint8→f32).
  ``CompiledStep`` checks the batch signature per call (host tuple
  compares, ~µs) and falls back to the never-yet-traced jitted twin on
  mismatch — one counted retrace, exactly the semantics the recompile
  sentinel drills pin.

``python -m imagent_tpu.compilecache ls|prune|warm`` is the operator
CLI (the cache directory argument defaults to the resolved one);
``make drill-warmstart`` measures the warm-vs-cold restart wall time
this module buys.

Module import is **jax-free** (manifest: ``analysis/jaxfree.json``) —
the CLI's ls/prune, the resolver and the fingerprint math must run on
any login node; every jax touch is lazy inside ``arm`` /
``compile_steps`` and the ``warm`` subcommand.
"""

from __future__ import annotations

import hashlib
import json
import os
import pickle
import sys
import time

# ---------------------------------------------------------------------------
# Where the cache lives
# ---------------------------------------------------------------------------

CACHE_DIR_ENV = "JAX_COMPILATION_CACHE_DIR"

# The fixed fallback: inside the checkout (git-ignored), never a
# temporary, pid- or time-derived path — XLA keys its cache on the
# directory, so a cache that moves is a cache that never hits.
DEFAULT_CACHE_DIR = os.path.join(
    os.path.dirname(os.path.dirname(os.path.abspath(__file__))),
    ".jax_cache")


def resolve_cache_dir() -> str:
    """THE compile-cache directory (XLA disk cache, ``aot/`` store
    beneath it): ``$JAX_COMPILATION_CACHE_DIR`` where set, else the
    fixed in-checkout path.  Pure path arithmetic — jax-free."""
    d = os.environ.get(CACHE_DIR_ENV, "").strip()
    return os.path.abspath(d) if d else DEFAULT_CACHE_DIR


def arm() -> str | None:
    """Point JAX's persistent compilation cache at the resolved
    directory and return it — or None where JAX's own switch
    (``jax_enable_compilation_cache`` /
    ``JAX_ENABLE_COMPILATION_CACHE=false``) turned persistent caching
    off, in which case the ``aot/`` store stays off with it.

    The only ``jax_compilation_cache_dir`` update in the tree: with
    the environment variable set, JAX already holds that value and
    nothing is written; unset, the fixed default is.  A process whose
    cache was already initialized elsewhere (a test session moving
    between directories) is re-pointed via ``reset_cache``."""
    import jax
    from jax.experimental.compilation_cache import compilation_cache

    if not jax.config.jax_enable_compilation_cache:
        return None
    d = resolve_cache_dir()
    if jax.config.jax_compilation_cache_dir != d:
        jax.config.update("jax_compilation_cache_dir", d)
        compilation_cache.reset_cache()
    os.makedirs(d, exist_ok=True)
    return d


# ---------------------------------------------------------------------------
# The compile fingerprint
# ---------------------------------------------------------------------------

# Config fields that reach the step builders / model construction and
# therefore change the compiled executable.  The completeness guard
# (tests/test_compilecache.py::test_compile_fields_cover_step_builders)
# diffs this list against the cfg.<field> reads in
# engine._build_model_and_steps, so a new compile-affecting flag cannot
# silently alias two different executables to one cache key.
COMPILE_FIELDS = (
    "arch", "num_classes", "image_size", "bf16", "transfer_dtype",
    "mean", "std", "seed",
    "optimizer", "momentum", "weight_decay",
    "label_smoothing", "mixup", "cutmix", "color_jitter", "ema_decay",
    "remat", "stem", "attn", "fused_mlp", "fused_qkv",
    "register_tokens",
    "seq_parallel", "tensor_parallel", "pipeline_parallel",
    "microbatches", "expert_parallel", "model_parallel",
    "moe_every", "num_experts", "capacity_factor", "moe_groups",
    "moe_top_k", "moe_aux_weight",
    "fsdp", "zero1", "health_stats", "check_nans",
)

# cfg fields _build_model_and_steps may read WITHOUT entering the key,
# each with its justification (the guard asserts the set matches):
EXEMPT_FIELDS = {
    # Weight VALUES only — the converted tree has identical
    # shapes/dtypes (shape agreement is enforced by the converter), so
    # the executable is byte-identical either way.
    "init_from_torch",
}

FINGERPRINT_VERSION = 1


def runtime_facts() -> dict:
    """The live-runtime half of the fingerprint (lazy jax — callers
    hold an initialized backend)."""
    import jax
    import jaxlib

    dev = jax.devices()[0]
    return {
        "jax": str(jax.__version__),
        "jaxlib": str(getattr(jaxlib, "__version__", "?")),
        "platform": str(dev.platform),
        "device_kind": str(dev.device_kind),
        "device_count": int(jax.device_count()),
        "local_device_count": int(jax.local_device_count()),
        "process_count": int(jax.process_count()),
    }


def fingerprint(cfg, *, mesh_shape: dict, global_batch: int,
                accum: int, runtime: dict) -> dict:
    """The complete compile fingerprint: pure data, jax-free (the
    runtime facts are an input).  Everything that changes the lowered
    step — topology, shapes, dtypes, versions, COMPILE_FIELDS — is in
    here; two runs with equal fingerprints compile byte-equivalent
    executables."""
    fields = {}
    for name in COMPILE_FIELDS:
        v = getattr(cfg, name)
        fields[name] = list(v) if isinstance(v, tuple) else v
    return {
        "v": FINGERPRINT_VERSION,
        "runtime": dict(runtime),
        "mesh": {str(k): int(v) for k, v in dict(mesh_shape).items()},
        "global_batch": int(global_batch),
        "accum": int(accum),
        "cfg": fields,
    }


def cache_key(fp: dict) -> str:
    """Deterministic 16-hex key over the canonical fingerprint JSON."""
    blob = json.dumps(fp, sort_keys=True, separators=(",", ":"))
    return hashlib.sha256(blob.encode("utf-8")).hexdigest()[:16]


# ---------------------------------------------------------------------------
# The on-disk executable store
# ---------------------------------------------------------------------------


class ExecutableStore:
    """``<root>/<key>/`` holds one fingerprint's executables:
    ``fingerprint.json`` (the human-auditable key preimage) plus one
    ``<name>.r<rank>of<world>.exe`` pickle of the
    ``serialize_executable`` triple per (step, rank) — serialized
    payloads carry device assignments, so a multi-host pod stores one
    file per rank and a resized world never loads another world's
    blob (the world size is in both the key and the file name).

    Best-effort by contract: every load returns None instead of
    raising (corrupt pickle, torn write, permission), every save is
    atomic (tmp + rename) and reports False on failure — the cache
    can only ever downgrade to a cold compile, never take the run
    down."""

    def __init__(self, root: str):
        self.root = str(root)

    # -- paths --------------------------------------------------------

    def entry_dir(self, key: str) -> str:
        return os.path.join(self.root, key)

    def exe_path(self, key: str, name: str, rank: int,
                 world: int) -> str:
        return os.path.join(self.entry_dir(key),
                            f"{name}.r{int(rank)}of{int(world)}.exe")

    # -- IO -----------------------------------------------------------

    def load(self, key: str, name: str, rank: int, world: int):
        """The pickled triple, or None (absent / torn / unpicklable —
        all of which mean 'miss', never 'crash')."""
        path = self.exe_path(key, name, rank, world)
        try:
            with open(path, "rb") as f:
                blob = pickle.load(f)
        except Exception:  # noqa: BLE001 - any rot is a miss
            return None
        return blob if isinstance(blob, tuple) and len(blob) == 3 \
            else None

    def save(self, key: str, fp: dict, name: str, rank: int,
             world: int, triple: tuple) -> bool:
        """Atomically land one serialized executable + (once per key)
        the fingerprint preimage. False on any failure."""
        try:
            d = self.entry_dir(key)
            os.makedirs(d, exist_ok=True)
            fp_path = os.path.join(d, "fingerprint.json")
            if not os.path.exists(fp_path):
                from imagent_tpu.telemetry.events import (
                    write_json_atomic,
                )
                write_json_atomic(fp_path,
                                  dict(fp, created=time.time()))
            path = self.exe_path(key, name, rank, world)
            tmp = f"{path}.{os.getpid()}.tmp"
            with open(tmp, "wb") as f:
                pickle.dump(triple, f, protocol=pickle.HIGHEST_PROTOCOL)
            os.replace(tmp, path)
            return True
        except Exception:  # noqa: BLE001 - cache write is best-effort
            return False

    # -- maintenance (the CLI) ---------------------------------------

    def entries(self) -> list[dict]:
        """One dict per cached fingerprint: key, creation time, the
        config headline (arch@size, mesh, world), file count, bytes."""
        out = []
        try:
            keys = sorted(os.listdir(self.root))
        except OSError:
            return out
        for key in keys:
            d = self.entry_dir(key)
            if not os.path.isdir(d):
                continue
            from imagent_tpu.telemetry.events import read_json
            fp = read_json(os.path.join(d, "fingerprint.json")) or {}
            exes = [e for e in sorted(os.listdir(d))
                    if e.endswith(".exe")]
            nbytes = 0
            newest = 0.0
            for e in exes:
                try:
                    st = os.stat(os.path.join(d, e))
                    nbytes += st.st_size
                    newest = max(newest, st.st_mtime)
                except OSError:
                    pass
            cfg = fp.get("cfg") or {}
            rt = fp.get("runtime") or {}
            out.append({
                "key": key,
                "created": fp.get("created"),
                "newest_mtime": newest or None,
                "arch": cfg.get("arch"),
                "image_size": cfg.get("image_size"),
                "mesh": fp.get("mesh"),
                "global_batch": fp.get("global_batch"),
                "accum": fp.get("accum"),
                "world": rt.get("process_count"),
                "jax": rt.get("jax"),
                "files": exes,
                "bytes": nbytes,
            })
        return out

    def prune(self, older_than_days: float | None = None,
              key: str | None = None) -> list[str]:
        """Drop entries (whole key dirs): a specific ``key``, entries
        whose newest executable is older than ``older_than_days``, or
        — with neither — everything. Returns the dropped keys."""
        import shutil

        dropped = []
        cutoff = (time.time() - older_than_days * 86400.0
                  if older_than_days is not None else None)
        for ent in self.entries():
            if key is not None and ent["key"] != key:
                continue
            if cutoff is not None and key is None:
                newest = ent["newest_mtime"] or ent["created"] or 0.0
                if newest >= cutoff:
                    continue
            shutil.rmtree(self.entry_dir(ent["key"]),
                          ignore_errors=True)
            dropped.append(ent["key"])
        return dropped


# ---------------------------------------------------------------------------
# The dispatch wrapper
# ---------------------------------------------------------------------------


def batch_signature(args: tuple) -> tuple:
    """((shape, dtype), ...) over the batch args — the per-call
    compatibility check's expected value."""
    return tuple((tuple(a.shape), str(a.dtype)) for a in args)


class CompiledStep:
    """An AOT-compiled step plus its never-yet-traced jitted twin.

    The compiled executable is shape/dtype-specialized; the fault
    drills (``step.shape_change``, ``nan-grads``) change the batch
    geometry mid-run on purpose.  Each call compares the batch args'
    (shape, dtype) tuples — pure host arithmetic, no device sync, no
    jax import — and dispatches the executable on match; a mismatch
    counts ``fallback_steps`` and runs the jitted twin, which traces
    exactly once per new geometry (the recompile sentinel still sees
    and classifies that compile, preserving the drill semantics).
    The state arg is not checked: its tree/shapes are pinned by the
    same config the cache key fingerprints."""

    def __init__(self, compiled, jitted, sig: tuple, stats: dict,
                 name: str):
        self.compiled = compiled
        self.jitted = jitted
        self.sig = sig
        self.stats = stats
        self.name = name

    def __call__(self, state, *batch):
        if batch_signature(batch) == self.sig:
            return self.compiled(state, *batch)
        self.stats["fallback_steps"] += 1
        return self.jitted(state, *batch)


class AotSteps:
    """``compile_steps``'s result: the dispatch wrappers, the raw
    compiled executables (the chip accountant's reuse handoff), and
    the mutable stats dict the telemetry surfaces snapshot."""

    def __init__(self, train, eval_step, compiled: dict, stats: dict):
        self.train = train
        self.eval = eval_step
        self.compiled = compiled
        self.stats = stats


def compile_steps(*, train_step, eval_step, state, mesh, cfg,
                  global_batch: int, fp: dict,
                  store: ExecutableStore | None,
                  rank: int, world: int) -> AotSteps:
    """One-compile startup: load-or-compile each step executable via
    the AOT path and wrap it for dispatch.

    The abstract args are exactly the chip accountant's
    (``chipacct.abstract_batch`` + the placed state + the replicated
    lr scalar) — the ONE geometry the steady step loop dispatches, so
    the wrapper's signature check passes on every non-drill step.
    Serialization failures downgrade (counted, WARNed by the caller's
    plan line) — a cold compile is the floor, never an error."""
    import numpy as np

    import jax
    from jax.experimental import serialize_executable as serexe
    from jax.sharding import NamedSharding, PartitionSpec as P

    from imagent_tpu.telemetry import chipacct as chipacct_lib

    key = cache_key(fp)
    stats = {
        "key": key,
        "store": store.root if store is not None else None,
        "hits": 0, "misses": 0, "saved": 0,
        "compile_s": 0.0, "load_s": 0.0,
        "fallback_steps": 0,
    }
    lr_sds = jax.ShapeDtypeStruct(
        (), np.float32, sharding=NamedSharding(mesh, P()))
    images, labels = chipacct_lib.abstract_batch(
        mesh, global_batch, cfg.image_size, cfg.transfer_dtype)
    ev = chipacct_lib.abstract_batch(
        mesh, global_batch, cfg.image_size, cfg.transfer_dtype,
        with_mask=True)
    plans = [("train", train_step, (state, images, labels, lr_sds))]
    if eval_step is not None:
        plans.append(("eval", eval_step, (state, *ev)))

    wrappers: dict = {"train": None, "eval": None}
    compiled_objs: dict = {"train": None, "eval": None}
    for name, jitted, args in plans:
        compiled = None
        if store is not None:
            triple = store.load(key, name, rank, world)
            if triple is not None:
                t0 = time.perf_counter()
                try:
                    compiled = serexe.deserialize_and_load(*triple)
                except Exception:  # noqa: BLE001 - stale blob = miss
                    compiled = None
                if compiled is not None:
                    stats["hits"] += 1
                    stats["load_s"] += time.perf_counter() - t0
        if compiled is None:
            stats["misses"] += 1
            t0 = time.perf_counter()
            compiled = jitted.lower(*args).compile()
            stats["compile_s"] += time.perf_counter() - t0
            if store is not None:
                try:
                    triple = serexe.serialize(compiled)
                    if store.save(key, fp, name, rank, world, triple):
                        stats["saved"] += 1
                except Exception:  # noqa: BLE001 - save is best-effort
                    pass
        wrappers[name] = CompiledStep(
            compiled, jitted, batch_signature(args[1:]), stats, name)
        compiled_objs[name] = compiled
    stats["startup_s"] = round(stats["compile_s"] + stats["load_s"], 3)
    stats["compile_s"] = round(stats["compile_s"], 3)
    stats["load_s"] = round(stats["load_s"], 3)
    return AotSteps(wrappers["train"], wrappers["eval"],
                    compiled_objs, stats)


def plan_line(stats: dict) -> str:
    """The startup plan print (master only) — the warm drill and
    bench-smoke stage 6 assert the hit/miss counters appear here."""
    src = (f"serialized store + XLA disk cache under "
           f"{os.path.dirname(stats['store'])}" if stats.get("store")
           else "persistent cache OFF (jax_enable_compilation_cache)")
    return (f"compile cache: key {stats.get('key')} — "
            f"{stats.get('hits', 0)} hit(s), "
            f"{stats.get('misses', 0)} compiled, "
            f"{stats.get('saved', 0)} saved; startup "
            f"{stats.get('startup_s', 0.0):.2f}s "
            f"(load {stats.get('load_s', 0.0):.2f}s + compile "
            f"{stats.get('compile_s', 0.0):.2f}s) [{src}]")


# ---------------------------------------------------------------------------
# CLI: python -m imagent_tpu.compilecache ls|prune|warm
# ---------------------------------------------------------------------------


def _fmt_mb(n: float) -> str:
    return f"{n / 2 ** 20:.1f}MiB"


def _cli_ls(cache_dir: str) -> int:
    store = ExecutableStore(os.path.join(cache_dir, "aot"))
    ents = store.entries()
    print(f"compile cache {cache_dir}:")
    if not ents:
        print("  aot store: empty")
    for e in ents:
        mesh = e.get("mesh") or {}
        layout = "x".join(f"{k}{v}" for k, v in sorted(mesh.items()))
        age = ""
        ts = e.get("newest_mtime") or e.get("created")
        if ts:
            age = f", {max(time.time() - float(ts), 0) / 3600.0:.1f}h old"
        print(f"  {e['key']}: {e.get('arch')}@{e.get('image_size')} "
              f"mesh {layout or '?'} gb {e.get('global_batch')} "
              f"accum {e.get('accum')} world {e.get('world')} "
              f"jax {e.get('jax')} — {len(e['files'])} exe(s), "
              f"{_fmt_mb(e['bytes'])}{age}")
    # The XLA persistent-cache half (everything else that compiled).
    n, nbytes = 0, 0
    try:
        for ent in os.listdir(cache_dir):
            p = os.path.join(cache_dir, ent)
            if ent == "aot" or not os.path.isfile(p):
                continue
            n += 1
            nbytes += os.stat(p).st_size
    except OSError:
        pass
    print(f"  xla disk cache: {n} file(s), {_fmt_mb(nbytes)}")
    return 0


def _cli_prune(cache_dir: str, older_days: float | None,
               key: str | None) -> int:
    store = ExecutableStore(os.path.join(cache_dir, "aot"))
    dropped = store.prune(older_than_days=older_days, key=key)
    for k in dropped:
        print(f"pruned {k}")
    print(f"pruned {len(dropped)} entr{'y' if len(dropped) == 1 else 'ies'}")
    return 0


def _cli_warm(engine_argv: list[str]) -> int:
    """Pre-populate the cache for a config WITHOUT training: build the
    mesh/model/steps exactly as the engine would (the shared
    ``_build_model_and_steps``) and run ``compile_steps`` against the
    store — a scheduler can warm a topology before the pod lands. The
    cache is the resolved one (``JAX_COMPILATION_CACHE_DIR`` or the
    in-checkout default), same as the run it warms."""
    from imagent_tpu.config import parse_args

    cfg = parse_args(engine_argv)
    import jax

    from imagent_tpu import cluster
    from imagent_tpu import engine as engine_lib

    cluster.initialize(cfg.backend or None)
    cluster.require_backend(cfg.backend or None)
    cache_dir = arm()
    if cache_dir is None:
        print("warm: REFUSED — the persistent cache is switched off "
              "(jax_enable_compilation_cache)", flush=True)
        return 1
    mesh = cluster.make_mesh(cfg.model_parallel,
                             pipeline_parallel=cfg.pipeline_parallel)
    n_data = mesh.shape[cluster.DATA_AXIS]
    if cfg.global_batch:
        accum = cfg.global_batch // (cfg.batch_size * n_data)
        global_batch = cfg.global_batch
    else:
        accum = cfg.grad_accum
        global_batch = cfg.batch_size * n_data * accum
    train_step, eval_step, state, _specs, _init_s = \
        engine_lib._build_model_and_steps(cfg, mesh, n_data, accum,
                                          is_master=True)
    store = ExecutableStore(os.path.join(cache_dir, "aot"))
    fp = fingerprint(cfg, mesh_shape=dict(mesh.shape),
                     global_batch=global_batch, accum=accum,
                     runtime=runtime_facts())
    aot = compile_steps(
        train_step=train_step, eval_step=eval_step, state=state,
        mesh=mesh, cfg=cfg, global_batch=global_batch, fp=fp,
        store=store, rank=jax.process_index(),
        world=jax.process_count())
    print(plan_line(aot.stats), flush=True)
    return 0


def main(argv=None) -> int:
    import argparse

    p = argparse.ArgumentParser(
        prog="python -m imagent_tpu.compilecache",
        description="Persistent AOT executable cache: list, prune, or "
                    "pre-warm the compile cache "
                    f"(${CACHE_DIR_ENV}, else {DEFAULT_CACHE_DIR})")
    sub = p.add_subparsers(dest="cmd", required=True)
    ls = sub.add_parser("ls", help="list cached executables + the XLA "
                                   "disk-cache footprint")
    pr = sub.add_parser("prune", help="drop cached executables")
    for cmd in (ls, pr):
        cmd.add_argument("cache_dir", nargs="?", default=None,
                         help="cache to inspect (default: the "
                              "resolved one)")
    pr.add_argument("--older-than-days", type=float, default=None,
                    metavar="D",
                    help="drop entries whose newest executable is "
                         "older than D days (default: drop all)")
    pr.add_argument("--key", default=None,
                    help="drop exactly this fingerprint key")
    warm = sub.add_parser(
        "warm", help="compile + serialize a config's step executables "
                     "into the resolved cache without training "
                     "(engine flags after --)")
    warm.add_argument("engine_args", nargs="*",
                      help="engine flags, e.g. --arch resnet50 "
                           "--image-size 224")
    ns = p.parse_args(argv)
    if ns.cmd == "warm":
        return _cli_warm(list(ns.engine_args))
    cache_dir = ns.cache_dir or resolve_cache_dir()
    if ns.cmd == "ls":
        return _cli_ls(cache_dir)
    return _cli_prune(cache_dir, ns.older_than_days, ns.key)


if __name__ == "__main__":
    sys.exit(main())
