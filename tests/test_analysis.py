"""jaxlint (imagent_tpu/analysis) — fixture-backed rule tests.

Every rule gets at least one true-positive fixture (must fire) and one
clean fixture (must stay silent), plus suppression/baseline workflow
tests and a self-check that the repo itself lints clean — the same
gate ``make lint`` enforces in CI.
"""

from __future__ import annotations

import json
import os
import subprocess
import sys

import pytest

from imagent_tpu.analysis import RULES, lint_file, run_paths
from imagent_tpu.analysis.runner import load_baseline

REPO_ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


def lint_src(tmp_path, src: str, rel: str = "pkg/mod.py",
             rule: str | None = None):
    """Findings for an inline fixture, laid out under ``rel`` (rules
    that scope by path — data/, benchmarks/ — see the intended
    location)."""
    path = tmp_path / rel
    path.parent.mkdir(parents=True, exist_ok=True)
    path.write_text(src)
    select = {rule} if rule else None
    findings, _, _ = lint_file(str(path), rel, select)
    return findings


def rules_fired(findings):
    return {f.rule for f in findings}


def test_registry_has_all_nine_rules():
    assert set(RULES) == {
        "host-sync-in-jit", "prng-key-reuse", "recompile-hazard",
        "nondeterministic-pytree-order", "missing-donation",
        "dtype-contract", "untimed-block", "telemetry-tag-format",
        "blocking-call-in-step-loop"}
    for r in RULES.values():
        assert r.doc  # every rule documents why it bites


# -------------------------------------------------------------- rule 1

HOST_SYNC_BAD = """
import jax
import numpy as np

def make_step():
    def step(state, x):
        host = np.asarray(x)
        scale = x.item()
        return state, host, scale
    return jax.jit(step, donate_argnums=(0,))
"""

HOST_SYNC_SHARD_MAP_BAD = """
import jax

def make(mesh):
    def body(state, x):
        return float(x)
    return jax.jit(jax.shard_map(body, mesh=mesh, in_specs=(),
                                 out_specs=()))
"""

HOST_SYNC_GOOD = """
import jax
import jax.numpy as jnp
import numpy as np

def make_step():
    def step(state, x):
        b = float(x.shape[0])        # shape access: static, legal
        return state, jnp.asarray(x) * b
    out = jax.jit(step, donate_argnums=(0,))
    host = np.asarray(out)           # outside the jit body: fine
    return out, host
"""


def test_host_sync_fires_on_fetch_in_jit_body(tmp_path):
    findings = lint_src(tmp_path, HOST_SYNC_BAD, rule="host-sync-in-jit")
    assert len(findings) == 2  # np.asarray and .item()
    assert all(f.rule == "host-sync-in-jit" for f in findings)


def test_host_sync_sees_through_shard_map(tmp_path):
    findings = lint_src(tmp_path, HOST_SYNC_SHARD_MAP_BAD,
                        rule="host-sync-in-jit")
    assert len(findings) == 1  # float(tracer param)


def test_host_sync_silent_on_clean_step(tmp_path):
    assert lint_src(tmp_path, HOST_SYNC_GOOD,
                    rule="host-sync-in-jit") == []


# -------------------------------------------------------------- rule 2

KEY_REUSE_BAD = """
import jax

def init(key):
    a = jax.random.normal(key, (2,))
    b = jax.random.uniform(key, (2,))
    return a + b
"""

KEY_REUSE_GOOD = """
import jax

def init(key):
    k_a, k_b = jax.random.split(key)
    a = jax.random.normal(k_a, (2,))
    b = jax.random.uniform(k_b, (2,))
    return a + b

def derived(key):
    # fold_in with distinct data derives independent keys (train.py's
    # step-key idiom) — not reuse.
    k_1 = jax.random.fold_in(key, 1)
    k_2 = jax.random.fold_in(key, 2)
    return jax.random.normal(k_1, (2,)) + jax.random.uniform(k_2, (2,))

def rebound(key):
    a = jax.random.normal(key, (2,))
    key = jax.random.fold_in(key, 7)   # fresh binding: resets
    b = jax.random.normal(key, (2,))
    return a + b
"""


def test_key_reuse_fires_on_double_draw(tmp_path):
    findings = lint_src(tmp_path, KEY_REUSE_BAD, rule="prng-key-reuse")
    assert len(findings) == 1
    assert "split/fold_in" in findings[0].message


def test_key_reuse_silent_on_split_fold_and_rebind(tmp_path):
    assert lint_src(tmp_path, KEY_REUSE_GOOD,
                    rule="prng-key-reuse") == []


KEY_REUSE_BRANCHES_GOOD = """
import jax

def draw(key, uniform):
    if uniform:
        return jax.random.uniform(key, (2,))
    else:
        return jax.random.normal(key, (2,))

def draw_ternary(key, uniform):
    return (jax.random.uniform(key, (2,)) if uniform
            else jax.random.normal(key, (2,)))
"""

KEY_REUSE_BRANCH_BAD = """
import jax

def draw(key, flag):
    a = jax.random.normal(key, (2,))   # before the branch...
    if flag:
        b = jax.random.uniform(key, (2,))   # ...reused on this path
    else:
        b = a
    return a + b
"""


def test_key_reuse_branch_aware(tmp_path):
    """Mutually exclusive if/else (or ternary) arms are separate
    execution paths — one draw per arm is not reuse (review finding);
    a draw before the branch plus one inside still is."""
    assert lint_src(tmp_path, KEY_REUSE_BRANCHES_GOOD,
                    rule="prng-key-reuse") == []
    findings = lint_src(tmp_path, KEY_REUSE_BRANCH_BAD,
                        rule="prng-key-reuse")
    assert len(findings) == 1


KEY_REUSE_TRY_GOOD = """
import jax

def draw(key, shape):
    try:
        return jax.random.normal(key, shape)
    except ValueError:
        return jax.random.uniform(key, shape)  # fallback: same run, one draw
"""

KEY_REUSE_LOOP_BAD = """
import jax

def init_layers(key, n):
    ws = []
    for _i in range(n):
        ws.append(jax.random.normal(key, (4, 4)))  # same key every layer
    return ws
"""

KEY_REUSE_LOOP_GOOD = """
import jax

def init_layers(key, n):
    ws = []
    for i in range(n):
        k = jax.random.fold_in(key, i)
        ws.append(jax.random.normal(k, (4, 4)))
    return ws

def init_layers_chained(key, n):
    ws = []
    for _i in range(n):
        key, k = jax.random.split(key)
        ws.append(jax.random.normal(k, (4, 4)))
    return ws
"""


def test_key_reuse_try_except_arms_are_alternatives(tmp_path):
    """A try-draw with an except-fallback-draw is one draw per run
    (review finding)."""
    assert lint_src(tmp_path, KEY_REUSE_TRY_GOOD,
                    rule="prng-key-reuse") == []


def test_key_reuse_fires_on_loop_invariant_key(tmp_path):
    """A loop-invariant key drawn every iteration yields identical
    values per layer — the correlated-inits classic (review finding:
    single-pass body scans missed it). Per-iteration fold_in/split
    rebinding stays clean, and the finding is reported once."""
    findings = lint_src(tmp_path, KEY_REUSE_LOOP_BAD,
                        rule="prng-key-reuse")
    assert len(findings) == 1
    assert lint_src(tmp_path, KEY_REUSE_LOOP_GOOD,
                    rule="prng-key-reuse") == []


# -------------------------------------------------------------- rule 3

RECOMPILE_BAD = """
import jax

@jax.jit
def step(x):
    if x > 0:
        x = x * 2
    while x < 10:
        x = x + 1
    return x
"""

RECOMPILE_FSTRING_BAD = """
import jax

@jax.jit
def step(x):
    print(f"x is now {x}")
    return x
"""

RECOMPILE_GOOD = """
import jax
from functools import partial

@partial(jax.jit, static_argnames=("flag",))
def step(x, state, flag=True):
    if flag:                        # static arg: sound branch
        x = x * 2
    if state.ema is None:           # None-structure check: static
        x = x + 1
    return x
"""


def test_recompile_fires_on_traced_branch(tmp_path):
    findings = lint_src(tmp_path, RECOMPILE_BAD, rule="recompile-hazard")
    assert len(findings) == 2  # the if and the while


def test_recompile_fires_on_tracer_fstring(tmp_path):
    findings = lint_src(tmp_path, RECOMPILE_FSTRING_BAD,
                        rule="recompile-hazard")
    assert len(findings) == 1
    assert "f-string" in findings[0].message


def test_recompile_silent_on_static_and_is_none(tmp_path):
    assert lint_src(tmp_path, RECOMPILE_GOOD,
                    rule="recompile-hazard") == []


# -------------------------------------------------------------- rule 4

SET_ORDER_BAD = """
def build_params(names):
    return {k: 0.0 for k in set(names)}
"""

SET_ORDER_GOOD = """
def build_params(names):
    return {k: 0.0 for k in sorted(set(names))}

def membership(names, k):
    allowed = set(names)           # set as a membership probe: fine
    return k in allowed
"""


def test_set_iteration_fires_on_param_dict(tmp_path):
    findings = lint_src(tmp_path, SET_ORDER_BAD,
                        rule="nondeterministic-pytree-order")
    assert len(findings) == 1
    assert "sorted()" in findings[0].message


def test_set_iteration_silent_when_sorted(tmp_path):
    assert lint_src(tmp_path, SET_ORDER_GOOD,
                    rule="nondeterministic-pytree-order") == []


SET_ORDER_REBIND_GOOD = """
def build(names):
    s = set(names)
    s = sorted(s)            # rebinding de-sets `s`...
    return {k: 0.0 for k in s}

def late(names):
    out = [n for n in names]  # iterated BEFORE names is ever a set
    names = set(out)
    return names
"""

SET_ORDER_REBIND_BAD = """
def build(names):
    s = sorted(names)
    s = set(s)               # ...and re-setting re-arms the rule
    return {k: 0.0 for k in s}
"""


def test_set_iteration_tracks_rebinding_in_order(tmp_path):
    """Set-ness follows the source order of rebindings (review
    finding): sorted() rebinding clears it, a later set() restores
    it."""
    assert lint_src(tmp_path, SET_ORDER_REBIND_GOOD,
                    rule="nondeterministic-pytree-order") == []
    findings = lint_src(tmp_path, SET_ORDER_REBIND_BAD,
                        rule="nondeterministic-pytree-order")
    assert len(findings) == 1


# -------------------------------------------------------------- rule 5

DONATION_BAD = """
import jax

def make_train_step(step):
    return jax.jit(step)
"""

DONATION_GOOD = """
import jax

def make_train_step(step):
    return jax.jit(step, donate_argnums=(0,))

def make_eval_step(step):
    return jax.jit(step)           # eval: nothing worth donating
"""


def test_donation_fires_on_undonated_train_step(tmp_path):
    findings = lint_src(tmp_path, DONATION_BAD, rule="missing-donation")
    assert len(findings) == 1
    assert "donate_argnums" in findings[0].message


def test_donation_silent_when_donated_or_eval(tmp_path):
    assert lint_src(tmp_path, DONATION_GOOD,
                    rule="missing-donation") == []


# -------------------------------------------------------------- rule 6

DTYPE_BAD = """
import numpy as np

def pad(n):
    return np.zeros((n,))          # float64 default on the wire
"""

DTYPE_CAST_BAD = """
import numpy as np

def stage(x):
    return x.astype(np.float64)
"""

DTYPE_GOOD = """
import numpy as np

def pad(n):
    return np.zeros((n,), np.uint8)
"""

DTYPE_PREP_BAD = """
import jax.numpy as jnp

def make_input_prep(mean, std):
    m = jnp.asarray(mean)          # dtype must be pinned in the prep
    return m
"""


def test_dtype_fires_in_data_modules(tmp_path):
    findings = lint_src(tmp_path, DTYPE_BAD, rel="data/pipe_fix.py",
                        rule="dtype-contract")
    assert len(findings) == 1
    findings = lint_src(tmp_path, DTYPE_CAST_BAD,
                        rel="data/cast_fix.py", rule="dtype-contract")
    assert len(findings) == 1 and "float64" in findings[0].message


def test_dtype_fires_inside_make_input_prep_anywhere(tmp_path):
    findings = lint_src(tmp_path, DTYPE_PREP_BAD, rel="train_fix.py",
                        rule="dtype-contract")
    assert len(findings) == 1


def test_dtype_silent_with_explicit_dtype_and_outside_scope(tmp_path):
    assert lint_src(tmp_path, DTYPE_GOOD, rel="data/pipe_fix.py",
                    rule="dtype-contract") == []
    # Same implicit-dtype code OUTSIDE the wire path: not this rule's
    # business.
    assert lint_src(tmp_path, DTYPE_BAD, rel="utils/misc_fix.py",
                    rule="dtype-contract") == []


# -------------------------------------------------------------- rule 7

UNTIMED_BAD = """
import time
import jax
import jax.numpy as jnp

def measure(f, x):
    t0 = time.perf_counter()
    y = f(x)
    return time.perf_counter() - t0
"""

UNTIMED_GOOD = """
import time
import jax
import numpy as np

def measure(f, x):
    t0 = time.perf_counter()
    y = jax.block_until_ready(f(x))
    return time.perf_counter() - t0

def measure_hard_fetch(f, x):
    # The repo's other sync idiom: a hard D2H fetch as the barrier.
    t0 = time.perf_counter()
    np.asarray(f(x).ravel()[:1])
    return time.perf_counter() - t0
"""


def test_untimed_fires_in_benchmark_without_sync(tmp_path):
    findings = lint_src(tmp_path, UNTIMED_BAD,
                        rel="benchmarks/bench_fix.py",
                        rule="untimed-block")
    assert len(findings) == 1
    assert "async" in findings[0].message


UNTIMED_WARMUP_ONLY_BAD = """
import time
import numpy as np
import jax

def measure(f, x):
    np.asarray(f(x))            # warmup sync, BEFORE the timed region
    t0 = time.perf_counter()
    y = f(x)
    return time.perf_counter() - t0
"""


def test_untimed_fires_when_only_warmup_is_synced(tmp_path):
    """A sync before the first timer doesn't close the timed region —
    the measurement still brackets async dispatch (review finding:
    sync detection must be position-aware)."""
    findings = lint_src(tmp_path, UNTIMED_WARMUP_ONLY_BAD,
                        rel="benchmarks/bench_fix.py",
                        rule="untimed-block")
    assert len(findings) == 1


def test_untimed_silent_with_sync_or_outside_benchmarks(tmp_path):
    assert lint_src(tmp_path, UNTIMED_GOOD,
                    rel="benchmarks/bench_fix.py",
                    rule="untimed-block") == []
    # Timing without sync in non-benchmark code is out of scope.
    assert lint_src(tmp_path, UNTIMED_BAD, rel="pkg/loop_fix.py",
                    rule="untimed-block") == []


# -------------------------------------------------------------- rule 8

TAG_FSTRING_BAD = """
def log_steps(writer, losses):
    for i, loss in enumerate(losses):
        writer.add_scalar(f"loss/step_{i}", loss, i)
"""

TAG_CASE_BAD = """
def log_epoch(writer, m, epoch):
    writer.add_scalar("Top1 accuracy", m, epoch)
    writer.add_histogram("stepTime/dist", [m], epoch)
"""

TAG_GOOD = """
def log_epoch(writer, m, epoch, group):
    writer.add_scalar("goodput/fraction", m, epoch)
    writer.add_scalar("steptime/p95_ms", m, epoch)
    writer.add_histogram("steptime/dist_ms", [m], epoch)
    # Variable tags are out of scope (bounded families document
    # themselves at the call site).
    writer.add_scalars(group, {"train": m}, epoch)
    # Non-writer methods with stringy first args stay silent.
    writer.add_text("Whatever Case", "x", epoch)
"""


def test_telemetry_tag_fstring_fires(tmp_path):
    findings = lint_src(tmp_path, TAG_FSTRING_BAD,
                        rule="telemetry-tag-format")
    assert len(findings) == 1
    assert "NEW" in findings[0].message  # unbounded-series warning


def test_telemetry_tag_case_fires(tmp_path):
    findings = lint_src(tmp_path, TAG_CASE_BAD,
                        rule="telemetry-tag-format")
    assert len(findings) == 2  # space+case, camelCase namespace


def test_telemetry_tag_good_silent(tmp_path):
    assert lint_src(tmp_path, TAG_GOOD,
                    rule="telemetry-tag-format") == []


OM_FAMILY_BAD = """
def render(exp, items):
    for name, v in items:
        exp.family(f"imagent_{name}", "gauge", "per-item").sample(v)
    exp.family("Imagent-Goodput", "counter", "bad grammar").sample(1)
    exp.family("goodput/fraction", "gauge", "tb-style slash").sample(1)
"""

OM_FAMILY_GOOD = """
def render(exp, phases):
    fam = exp.family("imagent_goodput_phase_seconds", "gauge", "x")
    for name, secs in phases.items():
        fam.sample(secs, phase=name)  # variables belong in LABELS
    exp.family("imagent_up", "gauge", "liveness").sample(1)
    # Unrelated .family() methods (no literal metric type in arg 2)
    # are out of scope for this rule.
    taxonomy.family("Whatever Case", object(), "not an exporter")
"""


def test_exporter_family_fstring_and_grammar_fire(tmp_path):
    """The exporter half of the rule (ISSUE 15 satellite): family
    names handed to Exposition.family must be literal snake_case —
    an f-string mints one metric family per interpolated value, and
    slashes/capitals break the Prometheus naming grammar."""
    findings = lint_src(tmp_path, OM_FAMILY_BAD,
                        rule="telemetry-tag-format")
    assert len(findings) == 3
    assert any("f-string" in f.message for f in findings)
    assert sum("snake_case" in f.message for f in findings) == 2


def test_exporter_family_good_silent(tmp_path):
    assert lint_src(tmp_path, OM_FAMILY_GOOD,
                    rule="telemetry-tag-format") == []


# -------------------------------------------------------------- rule 9

STEP_LOOP_BAD = """
import numpy as np
from imagent_tpu.data.prefetch import device_prefetch

def train_epoch(mesh, step, state, batches, log):
    for images, labels in device_prefetch(mesh, batches):
        state, metrics = step(state, images, labels)
        log(np.asarray(metrics))
        log(metrics.item())
    return state
"""

STEP_LOOP_VARIABLE_BAD = """
import jax
from imagent_tpu.data.prefetch import Prefetcher

def train_epoch(mesh, step, state, batches):
    it = Prefetcher(mesh, batches)
    out = []
    for arrays in it:
        state, metrics = step(state, *arrays)
        out.append(jax.block_until_ready(metrics))
    return state, out
"""

STEP_LOOP_LAGGED_GOOD = """
import numpy as np
from imagent_tpu.data.prefetch import device_prefetch

_GUARD_LAG = 2

def train_epoch(mesh, step, state, batches, log):
    buf = []
    for images, labels in device_prefetch(mesh, batches):
        state, metrics = step(state, images, labels)
        buf.append(metrics)
        if len(buf) > _GUARD_LAG:
            log(np.asarray(buf[len(buf) - 1 - _GUARD_LAG]))
    # The boundary drain happens OUTSIDE the loop.
    total = np.asarray(buf[-1])
    return state, total
"""

STEP_LOOP_PLAIN_GOOD = """
import numpy as np

def host_epoch(batches, log):
    # A plain host loop (no prefetched source) may fetch freely.
    for batch in batches:
        log(np.asarray(batch))
    it = iter(batches)
    for x in it:
        log(np.asarray(x))
"""


def test_step_loop_blocking_fetch_fires(tmp_path):
    findings = lint_src(tmp_path, STEP_LOOP_BAD,
                        rule="blocking-call-in-step-loop")
    assert len(findings) == 2  # np.asarray + .item()
    assert all("step loop" in f.message for f in findings)


def test_step_loop_tracks_prefetcher_variable(tmp_path):
    """The engine's idiom: the loop iterates a NAME assigned from a
    Prefetcher(...) constructor, not the call itself."""
    findings = lint_src(tmp_path, STEP_LOOP_VARIABLE_BAD,
                        rule="blocking-call-in-step-loop")
    assert len(findings) == 1
    assert "block_until_ready" in findings[0].message


def test_step_loop_lagged_read_and_plain_loops_silent(tmp_path):
    # A statement referencing _GUARD_LAG reads the lagged frontier —
    # the step already retired, the fetch is free.
    assert lint_src(tmp_path, STEP_LOOP_LAGGED_GOOD,
                    rule="blocking-call-in-step-loop") == []
    # Loops over non-prefetched sources are out of scope.
    assert lint_src(tmp_path, STEP_LOOP_PLAIN_GOOD,
                    rule="blocking-call-in-step-loop") == []


STEP_LOOP_ACCOUNTANT_BAD = """
from imagent_tpu.data.prefetch import device_prefetch

def train_epoch(mesh, step, state, batches, dev, compiled, log):
    for images, labels in device_prefetch(mesh, batches):
        state, metrics = step(state, images, labels)
        log(dev.memory_stats())
        log(compiled.cost_analysis())
        log(compiled.memory_analysis())
    return state
"""


def test_step_loop_accountant_introspection_fires(tmp_path):
    """The ISSUE 19 no-sync contract: the chip accountant's
    introspection calls — ``memory_stats()`` (a per-device runtime
    sync) and ``cost_analysis()``/``memory_analysis()`` (executable
    walks) — are blocking fetches when issued inside a prefetched
    step loop.  Rule 9 names all three."""
    findings = lint_src(tmp_path, STEP_LOOP_ACCOUNTANT_BAD,
                        rule="blocking-call-in-step-loop")
    assert len(findings) == 3, findings
    msgs = " ".join(f.message for f in findings)
    for name in ("memory_stats", "cost_analysis", "memory_analysis"):
        assert name in msgs, msgs


def test_chipacct_module_is_step_loop_clean():
    """The accountant itself honours the contract it linted into
    existence: a select-run of rule 9 over the real
    ``telemetry/chipacct.py`` finds nothing — every introspection
    call happens at build/boundary time, never in a step loop."""
    rel = os.path.join("imagent_tpu", "telemetry", "chipacct.py")
    findings, _, _ = lint_file(os.path.join(REPO_ROOT, rel), rel,
                               {"blocking-call-in-step-loop"})
    assert findings == [], [f.message for f in findings]


# ------------------------------------------------- suppressions/baseline

SUPPRESSED = """
import jax

def init(key):
    a = jax.random.normal(key, (2,))
    b = jax.random.uniform(key, (2,))  # jaxlint: disable=prng-key-reuse -- fixture: intentional reuse
    return a + b
"""

BARE_SUPPRESSION = """
import jax

def init(key):
    a = jax.random.normal(key, (2,))
    b = jax.random.uniform(key, (2,))  # jaxlint: disable=prng-key-reuse
    return a + b
"""


def test_suppression_with_justification_silences(tmp_path):
    path = tmp_path / "mod.py"
    path.write_text(SUPPRESSED)
    findings, suppressed, unused = lint_file(str(path), "mod.py", None)
    assert findings == []
    assert suppressed == 1
    assert unused == []


def test_bare_suppression_is_itself_reported(tmp_path):
    path = tmp_path / "mod.py"
    path.write_text(BARE_SUPPRESSION)
    findings, suppressed, _ = lint_file(str(path), "mod.py", None)
    assert suppressed == 1  # the hit is silenced...
    assert rules_fired(findings) == {"bare-suppression"}  # ...loudly


SUPPRESSED_MULTILINE = """
import jax

def init(key):
    a = jax.random.normal(key, (2,))
    b = jax.random.uniform(
        key,
        (2,))  # jaxlint: disable=prng-key-reuse -- fixture: comment on the closing line
    return a + b
"""

UNUSED_SUPPRESSION = """
import jax

def init(key):
    k1, k2 = jax.random.split(key)
    a = jax.random.normal(k1, (2,))  # jaxlint: disable=prng-key-reuse -- stale: the split above already fixed this
    return a + jax.random.uniform(k2, (2,))
"""


def test_suppression_on_closing_line_of_multiline_statement(tmp_path):
    """A suppression placed at the END of a multiline call covers the
    finding anchored at its first line (review finding)."""
    path = tmp_path / "mod.py"
    path.write_text(SUPPRESSED_MULTILINE)
    findings, suppressed, unused = lint_file(str(path), "mod.py", None)
    assert findings == []
    assert suppressed == 1
    assert unused == []


def test_suppression_in_docstring_is_inert():
    """Suppression parsing is token-based: an example quoted in a
    docstring is not a live suppression (and so is never reported
    unused)."""
    from imagent_tpu.analysis.runner import parse_suppressions

    by_line, unjustified = parse_suppressions(
        '"""docs: use  # jaxlint: disable=all -- why  on the line"""\n'
        "x = 1  # jaxlint: disable=dtype-contract -- real comment\n")
    assert list(by_line) == [2]
    assert unjustified == []


def test_unused_suppression_is_audited(tmp_path):
    """A suppression no finding consumes is reported (review finding:
    audit parity with stale baseline entries), without failing the
    gate."""
    src_dir = tmp_path / "src"
    src_dir.mkdir()
    (src_dir / "mod.py").write_text(UNUSED_SUPPRESSION)
    result = run_paths([str(src_dir)], root=str(tmp_path))
    assert result.ok  # advisory, not a gate failure
    assert result.unused_suppressions == [("src/mod.py", 6)]


def test_baseline_grandfathers_by_code_fingerprint(tmp_path):
    src_dir = tmp_path / "src"
    src_dir.mkdir()
    (src_dir / "mod.py").write_text(KEY_REUSE_BAD)
    entry = {"path": "src/mod.py", "rule": "prng-key-reuse",
             "code": "b = jax.random.uniform(key, (2,))",
             "reason": "fixture: grandfathered for the test"}
    bl = tmp_path / "baseline.json"
    bl.write_text(json.dumps([entry]))
    result = run_paths([str(src_dir)], baseline_path=str(bl),
                       root=str(tmp_path))
    assert result.ok and result.baselined == 1
    # Stale entries (nothing matches) are reported, not fatal.
    (src_dir / "mod.py").write_text(KEY_REUSE_GOOD)
    result = run_paths([str(src_dir)], baseline_path=str(bl),
                       root=str(tmp_path))
    assert result.ok and result.stale_baseline == [entry]


def test_missing_lint_path_fails_loudly(tmp_path):
    """A typo'd path must not let the CI gate pass while checking
    nothing (review finding: os.walk on a nonexistent dir yields
    nothing silently)."""
    with pytest.raises(FileNotFoundError, match="does not exist"):
        run_paths([str(tmp_path / "no_such_dir")], root=str(tmp_path))
    proc = subprocess.run(
        [sys.executable, "-m", "imagent_tpu.analysis", "imagent_tpu",
         "benchmarcks_typo"],
        cwd=REPO_ROOT, capture_output=True, text=True, timeout=120)
    assert proc.returncode == 2
    assert "does not exist" in proc.stderr


def test_write_baseline_skips_meta_and_keeps_reasons(tmp_path):
    """--write-baseline must (a) not emit bare-suppression/syntax-error
    entries load_baseline would reject, and (b) carry hand-written
    reasons forward for unchanged fingerprints (review findings)."""
    from imagent_tpu.analysis.runner import write_baseline

    src_dir = tmp_path / "src"
    src_dir.mkdir()
    (src_dir / "mod.py").write_text(
        KEY_REUSE_BAD + BARE_SUPPRESSION + DONATION_BAD)
    result = run_paths([str(src_dir)], root=str(tmp_path))
    assert "bare-suppression" in rules_fired(result.findings)
    bl = tmp_path / "baseline.json"
    prior = [{"path": "src/mod.py", "rule": "prng-key-reuse",
              "code": "b = jax.random.uniform(key, (2,))",
              "reason": "curated: kept across rewrites"}]
    skipped = write_baseline(result, str(bl), prior)
    assert skipped == 1  # the bare-suppression meta-finding
    entries = load_baseline(str(bl))  # loads cleanly: no meta rules
    reasons = {e["reason"] for e in entries}
    assert "curated: kept across rewrites" in reasons  # carried forward
    # The fresh (non-prior) finding got the TODO stamp.
    assert any(r.startswith("TODO") for r in reasons)


def test_write_baseline_rejects_select(tmp_path):
    """A partial-rule snapshot would silently delete other rules'
    grandfathered entries (review finding) — refuse the combination."""
    proc = subprocess.run(
        [sys.executable, "-m", "imagent_tpu.analysis", "imagent_tpu",
         "--select", "prng-key-reuse", "--write-baseline",
         "--baseline", str(tmp_path / "bl.json")],
        cwd=REPO_ROOT, capture_output=True, text=True, timeout=120)
    assert proc.returncode == 2
    assert "cannot be combined" in proc.stderr
    assert not (tmp_path / "bl.json").exists()


def test_baseline_requires_justification(tmp_path):
    bl = tmp_path / "baseline.json"
    bl.write_text(json.dumps([{"path": "a.py", "rule": "prng-key-reuse",
                               "code": "x", "reason": "  "}]))
    with pytest.raises(ValueError, match="reason"):
        load_baseline(str(bl))


# ------------------------------------------------------------ CI gate


def test_repo_lints_clean_via_cli():
    """The tier-1 lint gate: the shipped tree must pass with all rules
    armed and the checked-in (empty-or-justified) baseline."""
    proc = subprocess.run(
        [sys.executable, "-m", "imagent_tpu.analysis",
         "imagent_tpu", "benchmarks", "bench.py"],
        cwd=REPO_ROOT, capture_output=True, text=True, timeout=120)
    assert proc.returncode == 0, \
        f"jaxlint found regressions:\n{proc.stdout}{proc.stderr}"
    assert "0 finding(s)" in proc.stdout


def test_cli_list_rules_names_all_seven():
    proc = subprocess.run(
        [sys.executable, "-m", "imagent_tpu.analysis", "--list-rules"],
        cwd=REPO_ROOT, capture_output=True, text=True, timeout=60)
    assert proc.returncode == 0
    for name in RULES:
        assert name in proc.stdout


def test_checked_in_baseline_is_valid():
    """Every grandfathered entry (if any) carries its justification."""
    bl = os.path.join(REPO_ROOT, "imagent_tpu", "analysis",
                      "baseline.json")
    entries = load_baseline(bl)
    assert entries == [], \
        "repo should lint clean without grandfathered findings; " \
        "if one was added, it must carry a real reason"
