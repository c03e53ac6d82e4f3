# Developer entry points. Everything runs hardware-free on the CPU
# backend (8 fake devices via conftest.py).

PY ?= python
PYTEST = env JAX_PLATFORMS=cpu $(PY) -m pytest -q -p no:cacheprovider

.PHONY: smoke test lint bench-smoke bench-anatomy bench-input \
	drill-pod drill-divergence drill-elastic drill-sharded drill-tp \
	drill-warmstart trace-smoke slo-check slo-smoke

# Static-analysis gate (docs/STATIC_ANALYSIS.md): ONE command runs
# both layers — jaxlint (per-module JAX/TPU rules) and podlint (the
# interprocedural collective-symmetry / deadman-gate / thread-
# discipline / jax-free-manifest pass over the project call graph) —
# across the package, the benchmarks, and the bench driver; exit != 0
# on any unsuppressed finding. ~3s, no jax import. ruff (correctness
# classes only, [tool.ruff] in pyproject.toml) rides along when the
# binary exists; the CI image doesn't ship it, so its absence is a
# skip, not a failure.
lint:
	$(PY) -m imagent_tpu.analysis imagent_tpu benchmarks bench.py
	@if command -v ruff >/dev/null 2>&1; then \
	    ruff check imagent_tpu benchmarks tests bench.py; \
	else \
	    echo "ruff not installed; skipping (jaxlint gate enforced above)"; \
	fi

# Fast confidence tier (<5 min on CPU): the lint gate, the resilience
# unit tests, the end-to-end fault-injection drills (torn checkpoint,
# NaN rollback, watchdog, SIGTERM, slow/failed async commits), the
# async-checkpoint drills (incl. the 2-process mid-commit-kill
# acceptance drill), and the core e2e train/resume smoke.
smoke: lint
	$(PY) benchmarks/input_pipeline.py --smoke \
	    --out /tmp/BENCH_input_smoke.json
	$(PYTEST) -m "not slow" tests/test_resilience.py \
	    tests/test_fault_drills.py tests/test_ckpt_async.py \
	    tests/test_e2e.py

# The full tier-1 gate (what CI runs).
test:
	$(PYTEST) -m "not slow" --continue-on-collection-errors tests/

# Partial-pod failure drills (docs/OPERATIONS.md "Partial-pod failure
# and requeue"): the 2-process deadman kill + requeue-resume drill,
# the storage-outage drills, the tombstone-classification suite, and
# the requeue-wrapper contract. All tier-1 (registered with the
# existing marker scheme); this target is the focused loop for working
# on the resilience layer.
drill-pod:
	$(PYTEST) -m "not slow" tests/test_pod_failure.py \
	    tests/test_launch.py

# Divergence drill (docs/OPERATIONS.md "Reading model health"): the
# step.grad_spike fault blows the update scale while every step stays
# FINITE; the health early-warning detector must catch it and
# --health-rollback must restore the last good checkpoint BEFORE the
# non-finite guard ever fires — plus the health unit/engine suite
# (EWMA detector, flight recorder, status surface). All tier-1.
drill-divergence:
	$(PYTEST) -m "not slow" tests/test_health.py
	$(PYTEST) -m "not slow" tests/test_fault_drills.py -k divergence

# Elastic-pod suite (docs/OPERATIONS.md "Elastic pod: shrink, grow,
# and the batch contract"): the tier-1 acceptance drill — a REAL
# 4-process CPU pod loses a rank mid-epoch (host.die), the survivors
# re-form a 3-host mesh and keep training (pod_resized event, no
# sample replayed or skipped), a fresh 4-process --resume re-expands,
# and the final loss matches the uninterrupted run within tolerance —
# plus the hb.flap no-split-brain drill, the rendezvous/roster unit
# tests, the stream re-sharding invariance matrix, and the
# elastic-flag validation. All tier-1.
drill-elastic:
	$(PYTEST) -m "not slow" tests/test_elastic.py

# Model-parallel pod suite (docs/OPERATIONS.md "Model-parallel pods:
# groups, death, and resize" — ISSUE 16's done bar): the group-math
# units (rank->group, group-aligned roster commits, accum
# re-derivation), the deadman group-condemnation verdicts, the
# TP-vs-DP health-series parity pin, and THE acceptance drill — a REAL
# 4-process --tp 2 pod loses a whole model group mid-epoch
# (group.die), the survivors condemn the group, salvage from the
# surviving whole group, re-form a one-group world (accum re-derived
# under --global-batch), a fresh 4-process resume re-expands to two
# groups, and the final loss matches the uninterrupted run within 1%
# with no sample replayed or skipped. All tier-1.
drill-tp:
	$(PYTEST) -m "not slow" tests/test_groups.py tests/test_tp_pod.py

# Warm-start resize drill (docs/OPERATIONS.md "Warm starts and the
# compile cache" — ISSUE 20's done bar): three fresh engine
# processes sharing one cold compile-cache dir (handed to them through
# JAX_COMPILATION_CACHE_DIR, the one resolver) — cold populate, then a
# requeue/--resume restart and a replay, both of which must load
# every step executable from the persistent AOT store (2 hits, 0
# compiled, 0 fallback dispatches) and land startup at a fraction of
# the cold compile. Prints cold-vs-warm startup and process-wall JSON
# lines. CPU-hosted: its seconds are not device metrics (the chip's
# cold/warm start-up is what chip_smoke.py prints).
drill-warmstart:
	env JAX_PLATFORMS=cpu $(PY) benchmarks/warmstart.py

# Sharded-state resilience suite (docs/OPERATIONS.md "Sharded
# checkpoints and salvage coverage" — ROADMAP item 2's done bar): the
# collective-free sharded snapshot format units (coverage rule,
# jax-free + zero-collectives subprocess asserts, shard-fault fallback
# chain, Orbax deadman-gate audit) and the REAL-process drills — a
# 2-process ZeRO-1 pod preempted mid-epoch resuming onto world 2 AND
# world 1 with loss parity, a 2-process FSDP pod losing a rank to the
# honest incomplete-coverage verdict, and a TP pod overlapping a
# slowed sharded commit with cross-process psums then salvaging at
# full coverage. All tier-1.
drill-sharded:
	$(PYTEST) -m "not slow" tests/test_ckpt_sharded.py \
	    tests/test_zz_sharded_drills.py

# Evaluate a finished run directory against the default SLO spec
# (docs/OPERATIONS.md "Monitoring, SLOs, and regression gating"):
# exit 1 on any breached epoch. Override the run dir with
# `make slo-check RUN=<log_dir>` and the spec with SLO_SPEC=<path>.
RUN ?= runs/imagent_tpu
SLO_SPEC ?= default
slo-check:
	$(PY) -m imagent_tpu.telemetry slo $(RUN) --spec $(SLO_SPEC)

# SLO engine / exporter / regression-gate suite (docs/OPERATIONS.md
# "Monitoring, SLOs, and regression gating"): spec validation + the
# evaluator edge cases, the golden OpenMetrics exposition + live
# scrape, the regress verdict/exit-code matrix, and the mid-run
# recompile sentinel drills. All tier-1; the focused loop for the
# observability-gating layer.
slo-smoke:
	$(PYTEST) -m "not slow" tests/test_slo.py

# Pod tracer suite (docs/OPERATIONS.md "Reading a pod trace"): the
# span recorder / torn-tail reader / skew-corrected merge unit tests,
# the engine trace drills (phases + steps modes, fatal-exit flushes,
# --trace off = zero files), and the Chrome-trace-schema validation.
# All tier-1; this target is the focused loop for the tracing layer.
trace-smoke:
	$(PYTEST) -m "not slow" tests/test_trace.py

# Tiny synthetic-data bench iteration through the real input path
# (uint8 wire -> device_prefetch -> in-graph normalize -> step) on the
# CPU backend, plus the async-checkpoint telemetry regression gate
# (blocking `checkpoint` phase < 10% of the synchronous baseline, the
# moved work accounted in `ckpt_commit_async`, phases still summing to
# wall): catches input-path crashes AND critical-path regressions
# before a real bench run.
bench-smoke:
	env JAX_PLATFORMS=cpu $(PY) benchmarks/bench_smoke.py

# Input-pipeline thread-scaling sweep (VERDICT item 7 / ROADMAP item
# 5): decoder workers x batch x resolution through the real uint8-wire
# path (decode -> worker IPC -> staging queue -> PrefetchStats), into
# BENCH_input.json — the img/s/core curve + linearity knee recorded in
# docs/ROOFLINE.md, and the sizing input for decode-offload hosts
# (docs/OPERATIONS.md "Host CPU budget and decode offload"). Host-side
# only (never imports jax); `--smoke` (a ~30s variant) gates `make
# smoke` above.
bench-input:
	$(PY) benchmarks/input_pipeline.py

# ConvNeXt-T per-stage block anatomy on the REAL chip, including the
# fused-kernel columns (mlp_fused / block_fused) whose block-vs-fused
# ratio at s0/s1 is the --fused-mlp accept-or-reject verdict
# (docs/ROOFLINE.md "Fused ConvNeXt MLP"). Run on TPU; CNX_BATCH and
# CNX_STAGE narrow the sweep.
bench-anatomy:
	$(PY) benchmarks/convnext_anatomy.py
