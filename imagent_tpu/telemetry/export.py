"""Live OpenMetrics/Prometheus exporter: ``--metrics-port``.

``status.json`` answers one operator's "is THIS run alive" from a
shell; a fleet scraper needs the same answers as a pull endpoint in a
format its monitoring stack already speaks.  This module serves
exactly that: process 0 binds ``--metrics-port`` and a daemon serving
thread renders the SAME epoch-boundary state the status.json writer
reads — goodput phases, step percentiles, input wait, health EWMAs,
HBM, pod world size, per-peer heartbeat staleness, checkpoint commit
geometry, SLO breach counters, and compile-event counts — as
OpenMetrics text (``GET /metrics``).

Design constraints:

* **stdlib-only and jax-free** (asserted by ``tests/test_slo.py``):
  the serving thread must never be able to touch a device, and the
  renderer must be reusable by tooling on any box.
* **Zero step-loop cost**: the engine calls ``update`` once per epoch
  boundary with an already-computed state dict; scrapes read that
  snapshot under a lock.  Between boundaries the snapshot ages —
  ``imagent_snapshot_age_seconds`` says by how much, so the scraper
  can judge freshness instead of being lied to.
* **Bounded, literal metric families**: every family is declared
  through ``Exposition.family`` with a literal snake_case name — the
  jaxlint ``telemetry-tag-format`` rule lints those call sites, so an
  interpolated family name (one series per step number...) fails the
  lint gate before it ever reaches a scraper.

``validate_exposition`` is the in-repo OpenMetrics text-format checker
(the ``trace.json`` validator pattern): the golden test renders a full
state and the drill scrapes a live run, and both must parse clean.
"""

from __future__ import annotations

import http.server
import re
import threading
import time

CONTENT_TYPE = ("application/openmetrics-text; version=1.0.0; "
                "charset=utf-8")

# Family names: strict snake_case (no colons — those are for recording
# rules). Label names likewise.
_NAME_RE = re.compile(r"^[a-z][a-z0-9_]*$")
_TYPES = ("gauge", "counter", "info")


def _escape_label(value: str) -> str:
    return (str(value).replace("\\", r"\\").replace("\n", r"\n")
            .replace('"', r'\"'))


def _fmt_value(value) -> str:
    f = float(value)
    if f == int(f) and abs(f) < 1e15:
        return str(int(f))
    return repr(f)


class _Family:
    """One metric family being rendered; ``sample`` appends one
    ``name{labels} value`` line.  Counter families sample under
    ``<name>_total`` (the OpenMetrics counter contract)."""

    def __init__(self, exp: "Exposition", name: str, mtype: str):
        self._exp = exp
        self.name = name
        self.mtype = mtype
        self._seen: set[tuple] = set()

    def sample(self, value, **labels) -> "_Family":
        if value is None:
            return self  # absent observable: no sample, family stays
        name = self.name + ("_total" if self.mtype == "counter" else "")
        key = tuple(sorted(labels.items()))
        if key in self._seen:
            raise ValueError(f"duplicate sample {name}{labels}")
        self._seen.add(key)
        for ln in labels:
            if not _NAME_RE.match(ln):
                raise ValueError(f"bad label name {ln!r} on {name}")
        label_str = ""
        if labels:
            inner = ",".join(f'{k}="{_escape_label(v)}"'
                             for k, v in sorted(labels.items()))
            label_str = "{" + inner + "}"
        self._exp._lines.append(f"{name}{label_str} "
                                f"{_fmt_value(value)}")
        return self


class Exposition:
    """OpenMetrics text builder.  Families are declared exactly once,
    with literal names (``telemetry-tag-format`` lints the call
    sites); ``render`` closes the document with the mandatory
    ``# EOF``."""

    def __init__(self):
        self._lines: list[str] = []
        self._names: set[str] = set()

    def family(self, name: str, mtype: str, help_text: str) -> _Family:
        if not _NAME_RE.match(name):
            raise ValueError(f"metric family name {name!r} is not "
                             "snake_case")
        if mtype not in _TYPES:
            raise ValueError(f"metric type {mtype!r} not in {_TYPES}")
        if name in self._names:
            raise ValueError(f"family {name!r} declared twice")
        self._names.add(name)
        self._lines.append(f"# HELP {name} {help_text}")
        self._lines.append(f"# TYPE {name} {mtype}")
        return _Family(self, name, mtype)

    def render(self) -> str:
        return "\n".join(self._lines + ["# EOF", ""])


# ---------------------------------------------------------------------------
# State -> exposition
# ---------------------------------------------------------------------------


def build_state(run_info: dict | None = None, record: dict | None = None,
                health: dict | None = None, slo: dict | None = None,
                compile_counts: dict | None = None,
                peer_staleness: dict | None = None,
                totals: dict | None = None) -> dict:
    """Assemble the exporter snapshot from the artifacts the epoch
    boundary already has in hand: the telemetry epoch ``record``, the
    health monitor snapshot, the SLO session status, the recompile
    sentinel counts, the deadman's per-peer staleness map, and the
    engine's run totals (rollbacks, commit failures).  Plain dicts in,
    plain dict out — the engine computes nothing new for this."""
    return {
        "t": time.time(),
        "run": dict(run_info or {}),
        "record": record,
        "health": health,
        "slo": slo,
        "compile": dict(compile_counts or {}),
        "peer_staleness": dict(peer_staleness or {}),
        "totals": dict(totals or {}),
    }


def render_state(state: dict | None, now: float | None = None) -> str:
    """The full exposition for one snapshot (``None`` = run started,
    no epoch boundary yet: identity + liveness series only)."""
    now = time.time() if now is None else now
    state = state or {}
    run = state.get("run") or {}
    exp = Exposition()
    info = exp.family("imagent_run_info", "gauge",
                      "run identity (labels; value is always 1)")
    if run:
        labels = dict(arch=str(run.get("arch", "?")),
                      chip=str(run.get("chip", "?")),
                      transfer_dtype=str(run.get("transfer_dtype", "?")))
        if run.get("mesh"):
            # Model-axis runs carry the mesh layout as an identity
            # label (dpAxtpBxppC) — scrapers slice fleet dashboards by
            # parallelism shape without a schema bump.
            labels["mesh"] = str(run.get("mesh"))
        info.sample(1, **labels)
    exp.family("imagent_up", "gauge",
               "1 while the training process serves this endpoint"
               ).sample(1)
    if state.get("t"):
        exp.family(
            "imagent_snapshot_age_seconds", "gauge",
            "seconds since the serving snapshot was refreshed (it "
            "refreshes at epoch boundaries; judge freshness with this)"
        ).sample(max(now - float(state["t"]), 0.0))
    record = state.get("record")
    if record is not None:
        phases = record.get("phases") or {}
        counters = record.get("counters") or {}
        step = record.get("step_ms") or {}
        exp.family("imagent_epoch", "gauge",
                   "last completed epoch (0-based)"
                   ).sample(record.get("epoch"))
        exp.family("imagent_epoch_wall_seconds", "gauge",
                   "wall time of the last completed epoch"
                   ).sample(record.get("wall_s"))
        exp.family("imagent_goodput_ratio", "gauge",
                   "fraction of the last epoch that bought optimizer "
                   "progress ((dispatch+drain)/wall)"
                   ).sample(record.get("goodput"))
        fam = exp.family("imagent_goodput_phase_seconds", "gauge",
                         "last epoch's wall partition by phase "
                         "(phases sum to wall)")
        for name in sorted(phases):
            fam.sample(phases[name], phase=name)
        overlap = record.get("overlap") or {}
        fam = exp.family("imagent_goodput_overlap_seconds", "gauge",
                         "background work overlapped with the last "
                         "epoch (not part of the wall partition)")
        for name in sorted(overlap):
            fam.sample(overlap[name], phase=name)
        fam = exp.family("imagent_step_time_seconds", "gauge",
                         "dispatch-to-dispatch step cadence "
                         "percentiles over the last epoch")
        for q, key in (("0.5", "p50_ms"), ("0.95", "p95_ms"),
                       ("0.99", "p99_ms")):
            if step.get(key) is not None:
                fam.sample(float(step[key]) / 1e3, quantile=q)
        exp.family("imagent_step_samples", "gauge",
                   "step-cadence samples behind the percentiles"
                   ).sample(step.get("n"))
        exp.family("imagent_input_wait_seconds", "gauge",
                   "step loop blocked on the staging queue last epoch"
                   ).sample(phases.get("input_wait"))
        exp.family("imagent_h2d_bytes", "gauge",
                   "host-to-device wire bytes staged last epoch"
                   ).sample(float(counters.get("h2d_mb", 0.0)) * 1e6
                            if "h2d_mb" in counters else None)
        hosts = record.get("hosts") or {}
        exp.family("imagent_pod_world_size", "gauge",
                   "processes in the pod (the epoch allgather row "
                   "count)").sample(hosts.get("count"))
        exp.family("imagent_pod_launched_world_size", "gauge",
                   "processes the scheduler launched (a gap vs "
                   "world_size = elastic resize)"
                   ).sample(run.get("launched"))
        if run.get("groups") is not None:
            # Model-axis twin of world_size: a TP/pipeline pod loses
            # capacity in whole model groups, so fleet alerts key on
            # this pair, not the flat rank count.
            exp.family("imagent_pod_groups", "gauge",
                       "model groups in the pod (sets of ranks "
                       "jointly holding one model replica)"
                       ).sample(run.get("groups"))
            exp.family("imagent_pod_launched_groups", "gauge",
                       "model groups the scheduler launched (a gap "
                       "vs groups = whole-group loss)"
                       ).sample(run.get("launched_groups"))
        exp.family("imagent_pod_stragglers", "gauge",
                   "hosts flagged as stragglers last epoch"
                   ).sample(len(record.get("stragglers") or []))
        hbm = record.get("hbm") or {}
        fam = exp.family("imagent_hbm_bytes", "gauge",
                         "device HBM usage where the runtime reports "
                         "it")
        for kind, key in (("in_use", "bytes_in_use"),
                          ("peak", "peak_bytes_in_use"),
                          ("limit", "bytes_limit")):
            if hbm.get(key) is not None:
                fam.sample(hbm[key], kind=kind)
        exp.family("imagent_hbm_utilization_ratio", "gauge",
                   "peak HBM in use / limit"
                   ).sample(hbm.get("utilization"))
        acct = record.get("chipacct") or {}
        # Chip-accountant families (telemetry/chipacct.py): absent
        # sub-record / unknown peak -> None samples -> skipped, so a
        # --no-chipacct run still renders a valid exposition.
        exp.family("imagent_mfu", "gauge",
                   "model FLOPs utilization last epoch (analytic "
                   "flops over step-loop seconds, vs chip peak)"
                   ).sample(acct.get("mfu"))
        exp.family("imagent_tflops_per_chip", "gauge",
                   "achieved model TFLOP/s per chip last epoch"
                   ).sample(acct.get("tflops_per_chip"))
        exp.family("imagent_hbm_modeled_peak_bytes", "gauge",
                   "XLA memory_analysis modeled peak per device "
                   "(args+output+temps+code-aliased)"
                   ).sample(acct.get("modeled_peak_bytes"))
        fam = exp.family("imagent_hbm_state_bytes", "gauge",
                         "per-device TrainState resident bytes by "
                         "component (sharding-aware)")
        for comp, nbytes in sorted(
                (acct.get("state_bytes") or {}).items()):
            if comp != "total" and nbytes:
                fam.sample(nbytes, component=comp)
        cc = record.get("compilecache") or {}
        # Warm-start families (compilecache.py): absent sub-record
        # (--no-aot-steps, legacy logs) -> None samples -> skipped.
        fam = exp.family("imagent_compile_cache_executables", "gauge",
                         "step executables at startup by source "
                         "(hit = deserialized from the store, "
                         "miss = compiled cold)")
        for source, key in (("hit", "hits"), ("miss", "misses")):
            if cc.get(key) is not None:
                fam.sample(cc[key], source=source)
        exp.family("imagent_compile_cache_startup_seconds", "gauge",
                   "wall seconds this attempt spent loading + "
                   "compiling step executables at startup"
                   ).sample(cc.get("startup_s"))
        exp.family("imagent_compile_cache_fallback_steps", "counter",
                   "steps dispatched through the jitted twin because "
                   "the batch geometry left the AOT signature "
                   "(fault drills)").sample(cc.get("fallback_steps"))
        exp.family("imagent_ckpt_commit_bytes", "gauge",
                   "bytes of the newest committed checkpoint "
                   "generation").sample(counters.get("ckpt_commit_bytes"))
        exp.family("imagent_bad_steps", "counter",
                   "non-finite steps skipped in-graph this epoch's "
                   "run so far").sample(
                       (state.get("health") or {}).get("bad_steps"))
    health = state.get("health") or {}
    fam = exp.family("imagent_health_ewma", "gauge",
                     "model-health trailing EWMAs "
                     "(telemetry/health.py)")
    for metric, key in (("grad_norm", "grad_norm_ewma"),
                        ("update_ratio", "update_ratio_ewma"),
                        ("loss", "loss_ewma")):
        if health.get(key) is not None:
            fam.sample(health[key], metric=metric)
    exp.family("imagent_health_anomalies", "counter",
               "health anomalies this run (every anomalous step)"
               ).sample(health.get("anomalies"))
    staleness = state.get("peer_staleness") or {}
    fam = exp.family("imagent_peer_heartbeat_staleness_seconds",
                     "gauge",
                     "age of each peer's out-of-band heartbeat at the "
                     "last boundary (creeping toward the deadline = a "
                     "host about to be declared dead)")
    for rank in sorted(staleness):
        fam.sample(staleness[rank], rank=str(rank))
    totals = state.get("totals") or {}
    exp.family("imagent_rollbacks", "counter",
               "rollback-and-replay incidents this run"
               ).sample(totals.get("rollbacks"))
    exp.family("imagent_ckpt_commit_failures", "counter",
               "pod-agreed failed async checkpoint commits this run"
               ).sample(totals.get("ckpt_commit_failures"))
    compile_counts = state.get("compile") or {}
    fam = exp.family("imagent_compile_events", "counter",
                     "XLA backend compiles observed by the recompile "
                     "sentinel, by phase (midrun = the silent "
                     "throughput killer)")
    for phase in ("warmup", "expected", "midrun"):
        if phase in compile_counts:
            fam.sample(compile_counts[phase], phase=phase)
    slo = state.get("slo")
    if slo is not None:
        exp.family("imagent_slo_epochs_judged", "gauge",
                   "epochs the live SLO evaluator has judged "
                   "(0 = still in warmup)"
                   ).sample(slo.get("epochs_judged"))
        breached = set(slo.get("breached") or [])
        slo_totals = slo.get("totals") or {}
        from imagent_tpu.telemetry.slo import OBJECTIVES
        fam = exp.family("imagent_slo_breached", "gauge",
                         "1 when the newest judged epoch breached "
                         "this objective")
        for name, _d, _k in OBJECTIVES:
            fam.sample(1 if name in breached else 0, objective=name)
        tot = exp.family("imagent_slo_breaches", "counter",
                         "epochs that breached this objective, run "
                         "total")
        for name, _d, _k in OBJECTIVES:
            tot.sample(slo_totals.get(name, 0), objective=name)
    return exp.render()


# ---------------------------------------------------------------------------
# OpenMetrics text-format validator (the trace.json pattern)
# ---------------------------------------------------------------------------

_META_RE = re.compile(r"^# (HELP|TYPE|UNIT) (\S+)(?: (.*))?$")
_SAMPLE_RE = re.compile(
    r"^([a-zA-Z_:][a-zA-Z0-9_:]*)"          # sample name
    r"(?:\{((?:[^\"\\}]|\"(?:[^\"\\]|\\.)*\")*)\})?"  # labels
    r" (-?(?:[0-9.eE+-]+|NaN|[+-]?Inf))"    # value
    r"(?: -?[0-9.eE+]+)?$")                 # optional timestamp
_LABEL_RE = re.compile(
    r'([a-zA-Z_][a-zA-Z0-9_]*)="((?:[^"\\]|\\.)*)"(?:,|$)')


def validate_exposition(text: str) -> list[str]:
    """Errors in an OpenMetrics text exposition (empty list = valid).
    Checks the rules a real scraper enforces: terminal ``# EOF``,
    TYPE-before-samples, counter ``_total`` suffixes, label syntax,
    parseable values, no duplicate (name, labelset) samples, and no
    family interleaving."""
    errors: list[str] = []
    if not text.endswith("# EOF\n"):
        errors.append("exposition must end with '# EOF\\n'")
    types: dict[str, str] = {}
    seen_samples: set = set()
    closed_families: set[str] = set()
    current: str | None = None
    for i, line in enumerate(text.splitlines(), start=1):
        if line == "# EOF":
            continue
        if not line:
            errors.append(f"line {i}: blank line inside exposition")
            continue
        if line.startswith("#"):
            m = _META_RE.match(line)
            if not m:
                errors.append(f"line {i}: malformed metadata {line!r}")
                continue
            kind, name = m.group(1), m.group(2)
            if kind == "TYPE":
                if name in types:
                    errors.append(f"line {i}: duplicate TYPE for "
                                  f"{name}")
                if m.group(3) not in ("gauge", "counter", "info",
                                      "histogram", "summary",
                                      "unknown", "stateset"):
                    errors.append(f"line {i}: unknown metric type "
                                  f"{m.group(3)!r}")
                types[name] = m.group(3) or ""
            if current is not None and name != current:
                closed_families.add(current)
            if name in closed_families:
                errors.append(f"line {i}: family {name} interleaved "
                              "(its samples/metadata must be "
                              "contiguous)")
            current = name
            continue
        m = _SAMPLE_RE.match(line)
        if not m:
            errors.append(f"line {i}: malformed sample {line!r}")
            continue
        sample_name, label_blob, value = m.group(1), m.group(2), \
            m.group(3)
        family = None
        for fam, mtype in types.items():
            expected = (fam + "_total" if mtype == "counter"
                        else fam)
            if sample_name == expected:
                family = fam
                break
            if mtype == "counter" and sample_name == fam:
                errors.append(
                    f"line {i}: counter {fam} must sample as "
                    f"{fam}_total")
                family = fam
                break
        if family is None:
            errors.append(f"line {i}: sample {sample_name} has no "
                          "preceding # TYPE declaration")
            continue
        if family != current:
            errors.append(f"line {i}: sample of {family} outside its "
                          "family block")
        labels = tuple(sorted(_LABEL_RE.findall(label_blob or "")))
        key = (sample_name, labels)
        if key in seen_samples:
            errors.append(f"line {i}: duplicate sample "
                          f"{sample_name}{dict(labels)}")
        seen_samples.add(key)
        try:
            float(value.replace("Inf", "inf").replace("NaN", "nan"))
        except ValueError:
            errors.append(f"line {i}: unparseable value {value!r}")
    return errors


def parse_samples(text: str) -> dict[str, dict[tuple, float]]:
    """``{sample_name: {sorted-label-tuple: value}}`` — the test /
    tooling accessor over a validated exposition."""
    out: dict[str, dict[tuple, float]] = {}
    for line in text.splitlines():
        if not line or line.startswith("#"):
            continue
        m = _SAMPLE_RE.match(line)
        if m is None:
            continue
        labels = tuple(sorted(_LABEL_RE.findall(m.group(2) or "")))
        out.setdefault(m.group(1), {})[labels] = float(
            m.group(3).replace("Inf", "inf").replace("NaN", "nan"))
    return out


# ---------------------------------------------------------------------------
# The HTTP exporter
# ---------------------------------------------------------------------------


class MetricsExporter:
    """Process-0 OpenMetrics endpoint: a daemon ``ThreadingHTTPServer``
    serving ``GET /metrics`` from the newest ``update()`` snapshot.
    ``port=0`` binds an ephemeral port (tests); ``self.port`` is the
    bound port either way."""

    def __init__(self, port: int, host: str = ""):
        if port < 0:
            raise ValueError("metrics port must be >= 0")
        self._requested = (host, int(port))
        self._state: dict | None = None
        self._lock = threading.Lock()
        self._server: http.server.ThreadingHTTPServer | None = None
        self._thread: threading.Thread | None = None
        self.port = int(port)
        self.scrapes = 0

    def start(self) -> "MetricsExporter":
        exporter = self

        class Handler(http.server.BaseHTTPRequestHandler):
            def do_GET(self):  # noqa: N802 (http.server contract)
                if self.path.split("?")[0] not in ("/", "/metrics"):
                    self.send_error(404)
                    return
                body = exporter.render_current().encode("utf-8")
                exporter.scrapes += 1
                self.send_response(200)
                self.send_header("Content-Type", CONTENT_TYPE)
                self.send_header("Content-Length", str(len(body)))
                self.end_headers()
                self.wfile.write(body)

            def log_message(self, *a):  # scrapes are not run events
                pass

        self._server = http.server.ThreadingHTTPServer(
            self._requested, Handler)
        self._server.daemon_threads = True
        self.port = self._server.server_address[1]
        self._thread = threading.Thread(
            target=self._server.serve_forever,
            name=f"metrics-exporter-{self.port}", daemon=True)
        self._thread.start()
        return self

    def update(self, state: dict) -> None:
        with self._lock:
            self._state = state

    def render_current(self) -> str:
        with self._lock:
            state = self._state
        return render_state(state)

    def close(self) -> None:
        if self._server is not None:
            self._server.shutdown()
            self._server.server_close()
            self._server = None
        if self._thread is not None:
            self._thread.join(timeout=5.0)
            self._thread = None


# Module-global active exporter (the flightrec/trace pattern): the
# engine activates it in _run and run()'s finally closes it even on
# the fatal ramps, without threading the handle through every layer.
_ACTIVE: MetricsExporter | None = None


def activate(exporter: MetricsExporter) -> None:
    global _ACTIVE
    _ACTIVE = exporter


def close_active() -> None:
    global _ACTIVE
    if _ACTIVE is not None:
        _ACTIVE.close()
        _ACTIVE = None
