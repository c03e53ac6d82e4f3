"""Runtime recompile sentinel: the dynamic half of jaxlint's static
``recompile-hazard`` rule.

A mid-run XLA recompile is the silent TPU throughput killer: the step
loop stalls for seconds while nothing is "wrong", and the goodput
accountant can only misattribute the stall (``compile`` if the
dispatch blocked, ``step_drain`` if the drain did).  jaxlint catches
the HAZARDS it can see in the source (shape branching, traced-value
``if``); this sentinel catches the EVENTS at runtime: it listens on
``jax.monitoring``'s backend-compile duration event and classifies
every compile as

* ``warmup``   — before the first epoch boundary (first-step compiles
  of the train/eval geometry are the price of jit, not a bug);
* ``expected`` — inside an ``expect(label)`` window the engine opens
  around compiles it KNOWS are first-time geometries (the first eval
  epoch under ``--eval-every > 1``);
* ``midrun``   — everything else: a post-warmup recompile.  Each one
  fires the engine callback, which emits a ``compile_event``
  telemetry record, a trace instant, a master WARN naming the jitted
  function, and an SLO breach (``recompiles_max``).

Function attribution: the monitoring event carries no name, but JAX
logs ``"Compiling <fun> ..."`` on the compiling thread immediately
before the backend compile — a DEBUG-level logging handler captures
that name per-thread and the duration listener pairs it with the
event that follows on the same thread.  Cost discipline: both hooks
fire only when a compile actually happens (seconds-scale by
definition); the step loop's steady path never enters this module —
zero added host syncs.

The jax.monitoring listener registry has no per-listener removal, so
installation is process-global and once-only; ``activate``/
``deactivate`` swap which sentinel (if any) receives events — the
flightrec/trace module-global pattern, safe across repeated in-process
``engine.run`` calls (tests).
"""

from __future__ import annotations

import contextlib
import logging
import threading
import time
from collections import deque

# jax._src.dispatch.BACKEND_COMPILE_EVENT — matched by prefix so a
# jaxlib that renames the suffix (duration vs duration_sec) still
# feeds the sentinel.
BACKEND_COMPILE_PREFIX = "/jax/core/compile/backend_compile"

# Loggers that announce "Compiling <fun> ..." right before the
# backend compile on the compiling thread.
_COMPILE_LOGGERS = ("jax._src.interpreters.pxla", "jax._src.dispatch")

PHASES = ("warmup", "expected", "midrun")


class RecompileSentinel:
    """Per-attempt compile-event state (the process-global hooks feed
    whichever sentinel is active)."""

    def __init__(self, on_midrun=None, keep: int = 256):
        self.on_midrun = on_midrun  # callable(event_dict) or None
        self._lock = threading.Lock()
        self._events: deque = deque(maxlen=keep)
        self.counts = {p: 0 for p in PHASES}
        self._warmup = True
        self._names: dict[int, tuple[str, float]] = {}  # per thread
        self._expected: dict[int, list[str]] = {}       # per thread

    # ---- engine surface --------------------------------------------------

    def end_warmup(self) -> None:
        """First epoch boundary reached: compiles from here on are
        either expected (bracketed) or midrun (the bug). Idempotent."""
        self._warmup = False

    @contextlib.contextmanager
    def expect(self, label: str):
        """Bracket a KNOWN first-time geometry (the first eval epoch):
        compiles on this thread inside the window classify as
        ``expected``, not ``midrun``."""
        ident = threading.get_ident()
        self._expected.setdefault(ident, []).append(str(label))
        try:
            yield
        finally:
            stack = self._expected.get(ident)
            if stack:
                stack.pop()

    def events(self) -> list[dict]:
        with self._lock:
            return list(self._events)

    # ---- hook surface (called by the process-global listeners) -----------

    def note_fun_name(self, name: str) -> None:
        self._names[threading.get_ident()] = (str(name),
                                              time.monotonic())

    def on_compile_event(self, duration: float) -> None:
        ident = threading.get_ident()
        name, t = self._names.pop(ident, ("<unknown>", 0.0))
        if name != "<unknown>" and time.monotonic() - t > 600.0:
            name = "<unknown>"  # stale capture from a long-dead pair
        expected = self._expected.get(ident) or []
        if self._warmup:
            phase = "warmup"
        elif expected:
            phase = "expected"
        else:
            phase = "midrun"
        event = {"fun": name, "secs": round(float(duration), 3),
                 "phase": phase, "t": round(time.time(), 3)}
        if phase == "expected":
            event["label"] = expected[-1]
        with self._lock:
            self.counts[phase] += 1
            self._events.append(event)
        if phase == "midrun" and self.on_midrun is not None:
            self.on_midrun(dict(event))


# ---------------------------------------------------------------------------
# Process-global hook installation (once) + active-sentinel switch
# ---------------------------------------------------------------------------

_ACTIVE: RecompileSentinel | None = None
_INSTALLED = False
_install_lock = threading.Lock()


def active() -> RecompileSentinel | None:
    return _ACTIVE


def activate(sentinel: RecompileSentinel) -> None:
    """Make ``sentinel`` the event receiver (installing the
    process-global jax.monitoring listener + compile-log handler on
    first use — they stay installed and no-op while nothing is
    active)."""
    global _ACTIVE
    _install()
    _ACTIVE = sentinel


def deactivate() -> None:
    global _ACTIVE
    _ACTIVE = None


class _CompileNameHandler(logging.Handler):
    """Captures the function name from JAX's "Compiling <fun> ..."
    log record on the compiling thread (emitted immediately before
    the backend compile whose duration event follows)."""

    def emit(self, record: logging.LogRecord) -> None:
        sentinel = _ACTIVE
        if sentinel is None:
            return
        msg = record.msg
        if isinstance(msg, str) and msg.startswith("Compiling") \
                and record.args:
            try:
                # The installed jax logs the wrapped name,
                # "jit(train_step)": report the function's own.
                name = str(record.args[0])
                if name.startswith("jit(") and name.endswith(")"):
                    name = name[4:-1]
                sentinel.note_fun_name(name)
            except Exception:  # noqa: BLE001 — a log hook must not
                pass           # take down the compile it observes


class _ForwardHandler(logging.Handler):
    """Re-emits records at/above the logger's ORIGINAL effective level
    into the parent chain.  Needed because capturing the DEBUG-level
    "Compiling" line requires lowering the jax child loggers to DEBUG
    with ``propagate=False`` — the ``jax`` parent logger ships a
    NOTSET stderr handler that would otherwise spray every DEBUG
    record onto the console.  Records the user would have seen without
    the sentinel (WARNINGs, ``jax_log_compiles`` output) still reach
    them through this forwarder; DEBUG chatter stays captured-only."""

    def __init__(self, parent: logging.Logger, threshold: int):
        super().__init__(level=logging.DEBUG)
        self._parent = parent
        self._threshold = threshold

    def emit(self, record: logging.LogRecord) -> None:
        if record.levelno >= self._threshold:
            self._parent.handle(record)


def _duration_listener(event: str, duration: float, **kw) -> None:
    sentinel = _ACTIVE
    if sentinel is not None and event.startswith(
            BACKEND_COMPILE_PREFIX):
        sentinel.on_compile_event(duration)


def _install() -> None:
    global _INSTALLED
    with _install_lock:
        if _INSTALLED:
            return
        import jax.monitoring as monitoring  # the one jax touchpoint

        monitoring.register_event_duration_secs_listener(
            _duration_listener)
        handler = _CompileNameHandler(level=logging.DEBUG)
        for name in _COMPILE_LOGGERS:
            lg = logging.getLogger(name)
            original = lg.getEffectiveLevel()
            if original > logging.DEBUG:
                lg.setLevel(logging.DEBUG)
                lg.propagate = False
                lg.addHandler(_ForwardHandler(
                    lg.parent or logging.getLogger("jax"), original))
            lg.addHandler(handler)
        _INSTALLED = True
