"""Warm starts (PR 20): the persistent AOT executable store, the
one-compile startup, where the cache lives (one resolver), and the
loaded-donated-executable exactness case.

Layers under test, cheapest first: the jax-free fingerprint/store
pieces (pure pickle + JSON), the completeness guard that diffs the
cache key against what the step builders actually read, the dispatch
wrapper, the chipacct compiled-object handoff (no duplicate capture
compile), the regress gate's startup series, and finally the tier-1
warm-start drill — two fresh engine processes sharing one cache dir,
the second of which must load (not compile) both step executables and
start in a fraction of the cold wall."""

import dataclasses
import inspect
import json
import os
import pickle
import re
import subprocess
import sys

import numpy as np
import pytest

from imagent_tpu import compilecache
from imagent_tpu.config import Config


def _fp(cfg, **over):
    base = dict(
        mesh_shape={"data": 8, "pipe": 1, "model": 1},
        global_batch=32, accum=1,
        runtime={"jax": "0.4.37", "jaxlib": "0.4.36",
                 "platform": "cpu", "device_kind": "cpu",
                 "device_count": 8, "local_device_count": 8,
                 "process_count": 1})
    base.update(over)
    return compilecache.fingerprint(cfg, **base)


# ---------------------------------------------------------------------------
# Fingerprint + key (jax-free)
# ---------------------------------------------------------------------------


def test_fingerprint_deterministic_and_sensitive():
    cfg = Config(backend="cpu", arch="resnet18", image_size=16, num_classes=4)
    k0 = compilecache.cache_key(_fp(cfg))
    assert re.fullmatch(r"[0-9a-f]{16}", k0)
    assert compilecache.cache_key(_fp(cfg)) == k0  # deterministic
    # Every axis of the fingerprint moves the key: a config field the
    # step builders consume, the topology, the batch geometry, the
    # gradient-accumulation factor, and the runtime versions.
    assert compilecache.cache_key(
        _fp(Config(backend="cpu", arch="resnet18", image_size=16, num_classes=4,
                   label_smoothing=0.123))) != k0
    assert compilecache.cache_key(
        _fp(cfg, mesh_shape={"data": 4, "pipe": 1, "model": 2})) != k0
    assert compilecache.cache_key(_fp(cfg, global_batch=64)) != k0
    assert compilecache.cache_key(_fp(cfg, accum=2)) != k0
    rt = dict(_fp(cfg)["runtime"], jax="0.5.0")
    assert compilecache.cache_key(_fp(cfg, runtime=rt)) != k0


def test_fingerprint_is_pure_data():
    """The fingerprint must round-trip canonical JSON — no tuples, no
    numpy scalars, nothing the store's preimage file would mangle."""
    fp = _fp(Config(backend="cpu", arch="vit_s16", image_size=32, num_classes=10))
    blob = json.dumps(fp, sort_keys=True)
    assert json.loads(blob) == fp


def test_cache_key_completeness_guard():
    """The guard the ISSUE names: every ``cfg.<field>`` the model/step
    builder reads must be IN the fingerprint (or explicitly exempted
    with a written justification), and every fingerprinted field must
    exist on Config.  A new flag that reaches the builders without
    entering the key silently serves stale executables — this test
    makes that a CI failure, not a debugging session."""
    from imagent_tpu import engine

    src = inspect.getsource(engine._build_model_and_steps)
    read = set(re.findall(r"cfg\.([A-Za-z_][A-Za-z0-9_]*)", src))
    fingerprinted = set(compilecache.COMPILE_FIELDS)
    exempt = set(compilecache.EXEMPT_FIELDS)
    missing = read - fingerprinted - exempt
    assert not missing, (
        f"_build_model_and_steps reads config fields absent from "
        f"compilecache.COMPILE_FIELDS/EXEMPT_FIELDS: {sorted(missing)}"
        " — add them to the fingerprint (or EXEMPT_FIELDS with a "
        "justification) or warm starts will reuse stale executables")
    cfg_fields = {f.name for f in dataclasses.fields(Config)}
    phantom = (fingerprinted | exempt) - cfg_fields
    assert not phantom, f"fingerprint names unknown fields: {phantom}"
    assert not fingerprinted & exempt


# ---------------------------------------------------------------------------
# Store (jax-free: plain pickled triples)
# ---------------------------------------------------------------------------


def test_store_roundtrip_and_corruption(tmp_path):
    store = compilecache.ExecutableStore(str(tmp_path / "aot"))
    fp = _fp(Config(backend="cpu", arch="resnet18", image_size=16, num_classes=4))
    key = compilecache.cache_key(fp)
    triple = (b"payload-bytes", {"in": 1}, {"out": 2})
    assert store.load(key, "train", 0, 1) is None  # empty = miss
    assert store.save(key, fp, "train", 0, 1, triple)
    assert store.load(key, "train", 0, 1) == triple
    # Preimage landed once, with a created stamp.
    pre = json.loads(
        (tmp_path / "aot" / key / "fingerprint.json").read_text())
    assert pre["cfg"]["arch"] == "resnet18" and "created" in pre
    # Rank/world and step-name isolation.
    assert store.load(key, "eval", 0, 1) is None
    assert store.load(key, "train", 1, 2) is None
    # Torn/corrupt blobs and non-triple pickles are misses, not raises.
    path = store.exe_path(key, "train", 0, 1)
    with open(path, "wb") as f:
        f.write(b"\x80\x04 not a pickle")
    assert store.load(key, "train", 0, 1) is None
    with open(path, "wb") as f:
        pickle.dump(["wrong", "shape"], f)
    assert store.load(key, "train", 0, 1) is None


def test_store_entries_and_prune(tmp_path):
    store = compilecache.ExecutableStore(str(tmp_path / "aot"))
    cfg = Config(backend="cpu", arch="resnet18", image_size=16, num_classes=4)
    fps = [_fp(cfg), _fp(cfg, global_batch=64)]
    keys = [compilecache.cache_key(f) for f in fps]
    for f, k in zip(fps, keys):
        assert store.save(k, f, "train", 0, 1, (b"x", None, None))
    ents = store.entries()
    assert sorted(e["key"] for e in ents) == sorted(keys)
    dropped = store.prune(key=keys[0])
    assert dropped == [keys[0]]
    assert [e["key"] for e in store.entries()] == [keys[1]]
    assert store.prune(older_than_days=0.0) == [keys[1]]
    assert store.entries() == []


# ---------------------------------------------------------------------------
# Where the cache lives: one resolver (jax-free half)
# ---------------------------------------------------------------------------

_REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


def test_resolver_env_wins_else_fixed_in_checkout_path(tmp_path,
                                                       monkeypatch):
    """JAX_COMPILATION_CACHE_DIR set -> that directory; unset (or
    blank) -> the fixed <checkout>/.jax_cache, which .gitignore lists.
    Never a temporary, pid- or time-derived path."""
    monkeypatch.setenv(compilecache.CACHE_DIR_ENV, str(tmp_path / "x"))
    assert compilecache.resolve_cache_dir() == str(tmp_path / "x")
    monkeypatch.setenv(compilecache.CACHE_DIR_ENV, "  ")
    assert compilecache.resolve_cache_dir() == \
        compilecache.DEFAULT_CACHE_DIR
    monkeypatch.delenv(compilecache.CACHE_DIR_ENV)
    assert compilecache.resolve_cache_dir() == \
        compilecache.DEFAULT_CACHE_DIR
    assert compilecache.DEFAULT_CACHE_DIR == os.path.join(
        _REPO, ".jax_cache")
    with open(os.path.join(_REPO, ".gitignore")) as f:
        assert ".jax_cache/" in f.read().split()


def test_one_cache_dir_update_in_the_tree():
    """No code but the resolver points JAX's cache anywhere: grep over
    every python file git would commit (this one, which spells the
    pattern, aside)."""
    files = subprocess.run(
        ["git", "ls-files", "-co", "--exclude-standard", "*.py"],
        cwd=_REPO, capture_output=True, text=True,
        check=True).stdout.split()
    setter = re.compile(
        r"""update\(\s*["']jax_compilation_cache_dir"""
        r"""|set_cache_dir\(|initialize_cache\(""")
    hits = []
    for rel in files:
        if rel == "tests/test_compilecache.py":
            continue
        with open(os.path.join(_REPO, rel)) as f:
            hits += [f"{rel}:{n}" for n, line in enumerate(f, 1)
                     if setter.search(line)]
    assert [h.split(":")[0] for h in hits] == \
        ["imagent_tpu/compilecache.py"], hits


def test_arm_unset_env_uses_the_fixed_default(tmp_path, monkeypatch,
                                              compile_cache_dir):
    """Unset, the engine's ``arm`` points JAX at the fixed default
    (patched to a tmp dir here so the test leaves the checkout
    alone); with JAX's own switch off it arms nothing."""
    import jax

    monkeypatch.delenv(compilecache.CACHE_DIR_ENV)
    fixed = str(tmp_path / "fixed_default")
    monkeypatch.setattr(compilecache, "DEFAULT_CACHE_DIR", fixed)
    assert compilecache.arm() == fixed
    assert jax.config.jax_compilation_cache_dir == fixed
    assert os.path.isdir(fixed)
    jax.config.update("jax_enable_compilation_cache", False)
    assert compilecache.arm() is None


def test_warm_cli_writes_under_env_dir_only(tmp_path):
    """``compilecache warm`` takes no directory of its own: it fills
    the cache the environment names — and the engine run it warmed
    then starts with 2 hits from there."""
    cache = tmp_path / "placed"
    env = dict(os.environ, JAX_PLATFORMS="cpu",
               JAX_COMPILATION_CACHE_DIR=str(cache),
               JAX_ENABLE_COMPILATION_CACHE="true",
               XLA_FLAGS="--xla_force_host_platform_device_count=8")
    flags = ["--backend", "cpu", "--arch", "resnet18", "--image-size",
             "16", "--num-classes", "4", "--batch-size", "4",
             "--no-bf16", "--dataset", "synthetic",
             "--synthetic-size", "64", "--workers", "0",
             "--log-every", "0", "--epochs", "1",
             "--log-dir", str(tmp_path / "tb"),
             "--ckpt-dir", str(tmp_path / "ck")]
    default = compilecache.DEFAULT_CACHE_DIR
    before = os.path.getmtime(default) if os.path.isdir(default) \
        else None
    warm = subprocess.run(
        [sys.executable, "-m", "imagent_tpu.compilecache", "warm",
         "--", *flags], capture_output=True, text=True, timeout=600,
        env=env, cwd=_REPO)
    assert warm.returncode == 0, (warm.stdout + warm.stderr)[-2000:]
    assert re.search(r"0 hit\(s\), 2 compiled, 2 saved", warm.stdout)
    assert str(cache) in warm.stdout  # the plan line names the place
    entries = [d for d in (cache / "aot").iterdir() if d.is_dir()]
    assert len(entries) == 1
    run = subprocess.run(
        [sys.executable, "-m", "imagent_tpu", *flags],
        capture_output=True, text=True, timeout=600, env=env,
        cwd=_REPO)
    assert run.returncode == 0, (run.stdout + run.stderr)[-2000:]
    assert re.search(r"2 hit\(s\), 0 compiled, 0 saved", run.stdout)
    after = os.path.getmtime(default) if os.path.isdir(default) \
        else None
    assert before == after  # the in-checkout default was not touched


# ---------------------------------------------------------------------------
# Dispatch wrapper + the loaded-donated-executable case (in-process)
# ---------------------------------------------------------------------------


def test_compiled_step_fallback_on_geometry_change(mesh8):
    import jax
    import jax.numpy as jnp

    def step(state, x):
        return state + x.sum(), (x * state).sum()

    s0 = jnp.float32(1.0)
    x0 = jnp.arange(8.0, dtype=jnp.float32)
    jitted = jax.jit(step)
    compiled = jitted.lower(s0, x0).compile()
    stats = {"fallback_steps": 0}
    wrap = compilecache.CompiledStep(
        compiled, jitted, compilecache.batch_signature((x0,)), stats,
        "train")
    s1, m1 = wrap(s0, x0)
    assert stats["fallback_steps"] == 0
    assert float(s1) == 29.0
    # A drill-style geometry change must route to the jitted twin and
    # count, not crash the shape-specialized executable.
    x_small = jnp.arange(4.0, dtype=jnp.float32)
    s2, _m2 = wrap(s0, x_small)
    assert stats["fallback_steps"] == 1
    assert float(s2) == 7.0
    wrap(s0, x_small.astype(jnp.bfloat16))  # dtype change counts too
    assert stats["fallback_steps"] == 2


def test_loaded_donated_executable_exact_on_host_buffers():
    """The repo's isolating case for the old-runtime defect that the
    removed ``wash_state`` fenced: a DESERIALIZED executable with a
    donated argument fed host-committed ``device_put`` buffers
    (exactly what checkpoint restore and torch import produce)
    miscomputed 12/12 on jax < 0.5. On the installed jax it computes
    exactly, first call and chained, at small and large sizes — which
    is why restored states now go straight to the loaded executables
    (also run on the TPU, CHANGES.md PR 21)."""
    import jax
    import jax.numpy as jnp
    from jax.experimental import serialize_executable as serexe

    def step(s, x):
        return s + x, (s * x).sum()

    ref = jax.jit(step)
    for n in (8, 1 << 12, 1 << 18):
        x = jnp.ones(n, jnp.float32)
        compiled = jax.jit(step, donate_argnums=0).lower(x, x).compile()
        loaded = serexe.deserialize_and_load(
            *serexe.serialize(compiled),
            execution_devices=jax.devices()[:1])
        del compiled
        for _ in range(4):
            host = np.arange(n, dtype=np.float32) % 97
            want_s, want_v = ref(jnp.asarray(host), x)
            want_s2, want_v2 = ref(want_s, x)
            got_s, got_v = loaded(jax.device_put(host), x)  # donated
            got_s2, got_v2 = loaded(got_s, x)               # chained
            assert float(got_v) == float(want_v)
            assert float(got_v2) == float(want_v2)
            assert np.array_equal(np.asarray(got_s2),
                                  np.asarray(want_s2))


# ---------------------------------------------------------------------------
# Regress gate: the startup_compile_s series
# ---------------------------------------------------------------------------


def _write_telemetry(run_dir, startups):
    from imagent_tpu.telemetry.events import FILENAME

    os.makedirs(run_dir, exist_ok=True)
    env = {"device_kind": "cpu", "device_count": 8,
           "process_count": 1, "arch": "resnet18", "image_size": 16,
           "global_batch": 32, "transfer_dtype": "uint8"}
    with open(os.path.join(run_dir, FILENAME), "w") as f:
        for s in startups:
            rec = dict(env, event="run_start", schema=1,
                       compile_cache={"hits": 2, "misses": 0,
                                      "startup_s": s})
            f.write(json.dumps(rec) + "\n")


def test_regress_gates_startup_compile_seconds(tmp_path):
    """A warm start that silently degrades to cold-compile wall time
    must trip the regress verdict; jitter inside the absolute floor
    must not."""
    from imagent_tpu.telemetry import regress

    base, cand = str(tmp_path / "base"), str(tmp_path / "cand")
    _write_telemetry(base, [0.6])
    _write_telemetry(cand, [14.2])  # lost the warm start entirely
    b = regress.load_run(base)
    c = regress.load_run(cand)
    assert b["series"]["startup_compile_s"] == [0.6]
    verdict = regress.compare(c, b)
    hits = [r for r in verdict["regressions"]
            if r["metric"] == "startup_compile_s"]
    assert len(hits) == 1 and hits[0]["aggregate"] == "max"
    # Every attempt counts: a resumed log (two run_starts) gates on
    # the WORST attempt, not the folded last one.
    multi = str(tmp_path / "multi")
    _write_telemetry(multi, [0.5, 9.9])
    assert max(regress.load_run(multi)
               ["series"]["startup_compile_s"]) == 9.9
    # Inside the absolute floor (2 s) is jitter, not a regression.
    near = str(tmp_path / "near")
    _write_telemetry(near, [1.9])
    verdict2 = regress.compare(regress.load_run(near), b)
    assert not [r for r in verdict2["regressions"]
                if r["metric"] == "startup_compile_s"]


# ---------------------------------------------------------------------------
# Chipacct handoff: no duplicate capture compile
# ---------------------------------------------------------------------------


def test_chipacct_reuses_aot_executables(tmp_path, monkeypatch):
    """With the AOT handoff the accountant must NEVER pay its own
    capture compile: poison capture_executable and run the engine —
    the account still builds off the handed-over executables, with
    ``reused_aot`` stamped and ``capture_s`` ~0 (exactly one compile
    per step executable at cold startup)."""
    from imagent_tpu.engine import run
    from imagent_tpu.telemetry import chipacct

    def poisoned(*a, **k):
        raise AssertionError(
            "duplicate capture compile: build_account must reuse the "
            "AOT executables, not re-lower the steps")

    monkeypatch.setattr(chipacct, "capture_executable", poisoned)
    seen = {}
    orig_build = chipacct.build_account

    def capture_build(**kw):
        acct = orig_build(**kw)
        seen.update(acct)
        return acct

    monkeypatch.setattr(chipacct, "build_account", capture_build)
    result = run(Config(backend="cpu",
        arch="resnet18", image_size=16, num_classes=4, batch_size=4,
        epochs=1, lr=0.05, dataset="synthetic", synthetic_size=64,
        workers=0, bf16=False, log_every=0, seed=0,
        log_dir=str(tmp_path / "tb"), ckpt_dir=str(tmp_path / "ckpt")))
    assert result["final_val"]["n"] > 0
    assert seen.get("reused_aot") is True
    assert float(seen.get("capture_s", 1.0)) < 0.5


# ---------------------------------------------------------------------------
# The tier-1 warm-start drill (fresh processes, shared cache dir)
# ---------------------------------------------------------------------------

_DRILL_CHILD = r"""
import json, os, sys
from imagent_tpu.config import Config
from imagent_tpu.engine import run

tmp, phase = sys.argv[1], sys.argv[2]
cfg = Config(backend="cpu",
    arch="resnet18", image_size=16, num_classes=4, batch_size=4,
    epochs=(1 if phase == "cold" else 2), lr=0.05,
    dataset="synthetic", synthetic_size=128, workers=0, bf16=False,
    log_every=0, seed=0, save_model=True, resume=(phase == "warm"),
    log_dir=os.path.join(tmp, "tb"), ckpt_dir=os.path.join(tmp, "ckpt"))
result = run(cfg)
assert result["best_epoch"] >= 0
print("FINAL_TRAIN_LOSS", repr(float(result["final_train"]["loss"])))
"""


def _spawn_engine(tmp, phase, cache=True):
    # The cache is placed from outside, the one way there is: the
    # environment variable, under the run's own tmp root.
    env = dict(os.environ,
               JAX_PLATFORMS="cpu",
               JAX_COMPILATION_CACHE_DIR=os.path.join(str(tmp), "cc"),
               JAX_ENABLE_COMPILATION_CACHE="true" if cache else "false",
               XLA_FLAGS="--xla_force_host_platform_device_count=8")
    proc = subprocess.run(
        [sys.executable, "-c", _DRILL_CHILD, str(tmp), phase],
        capture_output=True, text=True, timeout=900, env=env,
        cwd=os.path.dirname(os.path.dirname(os.path.abspath(__file__))))
    assert proc.returncode == 0, (proc.stdout + proc.stderr)[-2000:]
    return proc.stdout


def _startup_stats(tmp):
    import glob

    recs = []
    for p in glob.glob(os.path.join(tmp, "tb", "**", "telemetry.jsonl"),
                       recursive=True):
        with open(p) as f:
            recs += [json.loads(ln) for ln in f if ln.strip()]
    return [r["compile_cache"] for r in recs
            if r.get("event") == "run_start"
            and isinstance(r.get("compile_cache"), dict)]


def test_warm_start_drill(tmp_path):
    """The acceptance drill: a second engine run in a FRESH process
    with the same fingerprint loads both serialized executables
    (2 hits, 0 compiles), its compile/startup phase lands well under
    30% of the cold wall, the hit counters surface in telemetry.jsonl
    and status.json, and no dispatch falls back to the jitted twin."""
    import shutil

    cold_out = _spawn_engine(tmp_path, "cold")
    assert re.search(r"compile cache: key [0-9a-f]{16} — 0 hit\(s\), "
                     r"2 compiled, 2 saved", cold_out)
    # The reference for the exactness check below: the same resume
    # from a copy of the same checkpoint, persistent cache OFF (cold
    # compiled executables, nothing deserialized).
    ref_root = tmp_path / "ref"
    shutil.copytree(tmp_path / "ckpt", ref_root / "ckpt")
    warm_out = _spawn_engine(tmp_path, "warm")
    assert re.search(r"2 hit\(s\), 0 compiled, 0 saved", warm_out)
    ref_out = _spawn_engine(ref_root, "warm", cache=False)
    assert "persistent cache OFF" in ref_out

    def final_loss(out):
        return float(re.search(r"FINAL_TRAIN_LOSS (\S+)", out).group(1))

    # No wash any more: the restored (host-committed, device_put)
    # state goes straight into the LOADED donated executables — and
    # must train exactly like the cold-compiled reference.
    assert final_loss(warm_out) == pytest.approx(final_loss(ref_out),
                                                 rel=1e-6)

    stamps = _startup_stats(tmp_path)
    assert len(stamps) == 2
    cold, warm = stamps
    assert (cold["hits"], cold["misses"]) == (0, 2)
    assert (warm["hits"], warm["misses"]) == (2, 0)
    assert warm["fallback_steps"] == 0
    assert warm["startup_s"] < 0.30 * cold["startup_s"], (
        f"warm startup {warm['startup_s']}s not under 30% of cold "
        f"{cold['startup_s']}s")
    assert "washes" not in warm  # the wash and its counter are gone
    # status.json carries the same stamp for jax-free dashboards.
    import glob

    sj = glob.glob(str(tmp_path / "tb" / "**" / "status.json"),
                   recursive=True)
    assert sj
    st = json.loads(open(sj[0]).read())
    assert (st.get("compile_cache") or {}).get("hits") == 2
    # Store on disk: one fingerprint entry, per-step executables.
    aot = tmp_path / "cc" / "aot"
    entries = [d for d in aot.iterdir() if d.is_dir()]
    assert len(entries) == 1
    assert (entries[0] / "fingerprint.json").is_file()
    assert sorted(p.suffix for p in entries[0].iterdir()
                  if p.suffix == ".exe") == [".exe", ".exe"]
