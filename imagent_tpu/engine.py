"""The orchestrator: init → data → model → epoch loop → summary.

Re-designs the reference's ``run()`` (``imagenet.py:213-429``) as a
TPU-native driver:

* cluster init via ``cluster.initialize`` (replacing ``imagenet.py:237-273``);
* global mesh; global-batch = per-replica batch × data-parallel size
  (the reference's 128 × 16 = 2048 geometry);
* epoch loop with epoch-seeded reshuffle (``set_epoch``,
  ``imagenet.py:375``), per-epoch LR (``imagenet.py:378``), train +
  validate (``imagenet.py:381-384``), best-top1 tracking + master-only
  best checkpoint (``imagenet.py:388-396``), epoch prints + TensorBoard
  scalars (``imagenet.py:397-421``), final summary (``imagenet.py:422-429``).

Host-sync discipline (SURVEY §7): steps are dispatched asynchronously;
per-step metric vectors are tiny replicated arrays accumulated on host
at epoch end — the device never waits on Python between steps, unlike
the reference's ``torch.cuda.synchronize()`` every step
(``imagenet.py:147``).
"""

from __future__ import annotations

import collections
import contextlib
import os
import signal
import time

import jax
import jax.numpy as jnp
import numpy as np

from imagent_tpu import checkpoint as ckpt_lib
from imagent_tpu import cluster
from imagent_tpu import compilecache as compilecache_lib
from imagent_tpu import elastic as elastic_lib
from imagent_tpu import groups as groups_lib
from imagent_tpu.config import Config
from imagent_tpu.data import make_loaders
from imagent_tpu.data.pipeline import WIRE_DTYPES
from imagent_tpu.data.prefetch import (
    Prefetcher, PrefetchStats, device_prefetch,
)
from imagent_tpu.models import create_model
from imagent_tpu.resilience import deadman as deadman_lib
from imagent_tpu.resilience import exitcodes, faultinject
from imagent_tpu.resilience.deadman import PodHeartbeat
from imagent_tpu.resilience.watchdog import StepWatchdog
from imagent_tpu.schedule import lr_for_epoch
from imagent_tpu.status import StatusWriter
from imagent_tpu.telemetry import TelemetrySession, parse_profile_at_step
from imagent_tpu.telemetry import chipacct as chipacct_lib
from imagent_tpu.telemetry import export as export_lib
from imagent_tpu.telemetry import flightrec as flightrec_lib
from imagent_tpu.telemetry import recompile as recompile_lib
from imagent_tpu.telemetry import slo as slo_lib
from imagent_tpu.telemetry import trace as trace_lib
from imagent_tpu.telemetry.health import HealthMonitor
from imagent_tpu.train import (
    TrainState, create_train_state, make_eval_step, make_optimizer,
    make_train_step, place_state, shard_batch, snapshotable,
    state_partition_specs,
)
from imagent_tpu.utils.logging import TrainLogger
from imagent_tpu.utils.metrics import AverageMeter

# The chip account of the ACTIVE run (telemetry/chipacct.py): a
# module-global handle so the fatal ramps in run() can enrich a
# runtime RESOURCE_EXHAUSTED with the per-component byte table without
# threading the account through every call — the same pattern the
# recompile sentinel and the metrics exporter use.
_chipacct_active: dict | None = None


class PreemptionGuard:
    """Graceful-shutdown aux subsystem (absent in the reference: a rank
    failure or walltime kill loses everything since epoch 0 — SURVEY §5
    "Failure detection").

    Catches SIGTERM and SIGUSR1 (Slurm's ``--signal`` pre-kill warning;
    Cloud TPU preemption notice) and raises a flag; the epoch loop
    checkpoints LAST and exits cleanly so ``--resume`` continues from the
    interrupted epoch. Multi-host note: Slurm delivers the signal to
    every task in the step, so all processes reach the collective
    checkpoint save together.

    Handler hygiene: any previously-installed Python handler is CHAINED
    (called after the flag is raised) and restored by ``uninstall()`` —
    so embedding ``engine.run`` in a larger process (or running it
    repeatedly in one test session) neither swallows the host's own
    signal handling nor leaks this guard's past its run.
    """

    def __init__(self):
        self.requested = False
        self._prev: dict = {}
        for sig in (signal.SIGTERM, getattr(signal, "SIGUSR1", None)):
            if sig is None:
                continue
            try:
                self._prev[sig] = signal.signal(sig, self._on_signal)
            except ValueError:  # not on the main thread (e.g. tests)
                pass

    def _on_signal(self, signum, frame):
        self.requested = True
        prev = self._prev.get(signum)
        if callable(prev):  # chain; SIG_IGN/SIG_DFL/None have no code
            prev(signum, frame)

    def request(self) -> None:
        """Raise the stop flag programmatically (watchdog, drills)."""
        self.requested = True

    def uninstall(self) -> None:
        """Put back whatever handlers were installed before this guard
        (None — a non-Python handler — restores SIG_DFL, the closest
        Python can get)."""
        for sig, prev in self._prev.items():
            try:
                signal.signal(sig, signal.SIG_DFL if prev is None else prev)
            except ValueError:
                pass
        self._prev.clear()

    def __call__(self) -> bool:
        return self.requested


_GUARD_LAG = 2  # steps behind the dispatch the lagged frontier reads


class _LaggedMetrics:
    """The metric frontier: per-step [loss_sum, top1, top5, n] vectors
    consumed ``lag`` steps BEHIND the dispatch.

    This is what makes the epoch boundary drain-free: every fetch
    (``np.asarray``) targets a vector ``lag`` steps old — a D2H of 16
    bytes, never a pipeline drain — and by the time the epoch ends only
    the ≤ ``lag``-step tail remains unconsumed, so ``drain()`` waits on
    the in-flight frontier tail, not on transferring a whole epoch of
    buffered vectors. Dispatch is asynchronous, so on a device-bound
    run these fetches are where the host waits for the chip — the
    waits are summed in ``wait_s`` (and, with ``wait_span`` named,
    traced as phase spans) for the goodput ``step_drain`` phase. The
    non-finite step guard (``bad``/``tripped``) and the ``--log-every``
    readout (``last``) ride the same consumed stream, so the step loop
    body itself contains NO blocking call on an in-flight result (the
    invariant the ``blocking-call-in-step-loop`` jaxlint rule pins).
    """

    def __init__(self, lag: int = _GUARD_LAG, max_bad: int = 0,
                 is_master: bool = False,
                 health: HealthMonitor | None = None,
                 health_rollback: bool = False, epoch: int = 0,
                 start_step: int = 0, wait_span: str | None = None):
        self._pending: collections.deque = collections.deque()
        self.lag = lag
        # Host seconds blocked on not-yet-retired vectors (lagged reads
        # + the tail drain); wait_span names their trace phase spans.
        self.wait_s = 0.0
        self._wait_span = wait_span
        self.max_bad = max_bad
        self.is_master = is_master
        # Model-health tail: vectors longer than the classic 4-field
        # head carry train.HEALTH_FIELDS; each consumed vector is
        # handed to the monitor (host arithmetic + a ring store — the
        # same cost class as the guard check itself).
        self.health = health
        self.health_rollback = health_rollback
        self.health_tripped = False
        self._epoch = epoch
        self._step0 = start_step
        self._sums = np.zeros(4, np.float64)
        self.steps = 0
        self.bad_steps = 0
        self.consec_bad = 0
        self.tripped = False
        self.last: np.ndarray | None = None  # newest consumed vector

    def _consume(self, m) -> None:
        t0 = time.perf_counter()
        v = np.asarray(m)
        waited = time.perf_counter() - t0
        self.wait_s += waited
        if (self._wait_span is not None
                and waited > trace_lib.MIN_WAIT_SPAN_S):
            # (a no-op without an active tracer)
            trace_lib.complete(self._wait_span, t0, t0 + waited,
                               cat=trace_lib.PHASE_CAT)
        self._sums += v[:4]
        self.steps += 1
        self.last = v
        bad = v[3] == 0  # n == 0: the in-graph guard skipped this step
        if bad:
            self.bad_steps += 1
            self.consec_bad += 1
            if self.is_master and self.max_bad:
                # With --max-bad-steps off there is no rollback to
                # warn about per step; bad_steps still reach the epoch
                # summary.
                print(f"WARNING: non-finite step skipped "
                      f"({self.consec_bad} consecutive; rollback at "
                      f"{self.max_bad})", flush=True)
            if self.max_bad and self.consec_bad >= self.max_bad:
                self.tripped = True
        else:
            self.consec_bad = 0
        if self.health is not None and v.shape[0] > 4:
            anomaly = self.health.observe(
                epoch=self._epoch,
                step=self._step0 + self.steps - 1,
                loss=float(v[0]) / max(float(v[3]), 1.0),
                grad_norm=float(v[4]), param_norm=float(v[5]),
                update_ratio=float(v[6]), bad=bool(bad),
                t=time.time())
            if anomaly is not None and self.health_rollback:
                # Divergence early-warning: same pod-agreed trip
                # semantics as the guard (the verdict rides the
                # REPLICATED vector every host consumes in order), so
                # the existing rollback machinery applies unchanged.
                self.health_tripped = True

    def push(self, m) -> None:
        """Record a just-dispatched step's metric vector; consumes the
        one now ``lag`` steps old."""
        self._pending.append(m)
        if len(self._pending) > self.lag:
            self._consume(self._pending.popleft())

    def drain(self) -> bool:
        """Consume the ≤ ``lag``-step tail (the only boundary wait);
        True if the consecutive-bad budget tripped."""
        while self._pending:
            self._consume(self._pending.popleft())
        return self.tripped

    def summary(self) -> dict:
        """Epoch averages over everything consumed so far."""
        loss_sum, c1, c5, n = [float(x) for x in self._sums]
        n = max(n, 1.0)
        return {"loss": loss_sum / n, "top1": c1 * 100.0 / n,
                "top5": c5 * 100.0 / n,
                "n": int(n) if self.steps else 0,
                "bad_steps": self.bad_steps}


def _stop_agreed(stop_check, step_i: int) -> bool:
    """Preemption decision all processes agree on.

    Single-host: poll every step. Multi-host: polling per-process could
    desynchronize the pod (one process enters the collective checkpoint
    save while another dispatches one more train_step — mismatched
    collectives hang). Instead, every 8 steps the per-process flags are
    ANY-reduced (allgather + max), so every host breaks at the SAME
    step boundary. Any-reduce, not a rank-0 broadcast: Slurm delivers
    the signal to every task, but Cloud TPU per-VM preemption notices
    can land on a single non-zero host — its flag must still stop the
    whole pod, or that host dies without the mid-epoch checkpoint.
    """
    if stop_check is None:
        return False
    if jax.process_count() == 1:
        return stop_check()
    if step_i % 8:
        return False
    from jax.experimental import multihost_utils
    flag = np.array([1 if stop_check() else 0], np.int32)
    return bool(multihost_utils.process_allgather(flag).max())


def train_one_epoch(cfg: Config, mesh, train_step, state: TrainState,
                    loader, epoch: int, lr: float, is_master: bool,
                    stop_check=None, start_step: int = 0,
                    watchdog: StepWatchdog | None = None,
                    telem: TelemetrySession | None = None,
                    prefetch: Prefetcher | None = None,
                    pod: PodHeartbeat | None = None,
                    health: HealthMonitor | None = None,
                    status: StatusWriter | None = None,
                    ) -> tuple[TrainState, dict, float, int, bool,
                               Prefetcher | None]:
    """One training epoch (reference ``train()``, ``imagenet.py:97-151``).

    ``start_step``: skip the first N batches — resuming an epoch that a
    preemption interrupted after N optimizer steps (the loader's order
    is deterministic per (seed, epoch), so the skipped batches are
    exactly the ones already applied).
    Returns ``(state, metrics, seconds, interrupted_at, rollback,
    warm)`` where ``interrupted_at`` is -1 for a completed epoch, else
    the number of optimizer steps applied when the stop fired;
    ``rollback`` is True when ``cfg.max_bad_steps`` consecutive
    non-finite steps were observed and the caller should restore the
    last good checkpoint (``run``'s rollback loop); ``warm`` is the
    next epoch's already-running ``Prefetcher`` (see below), or None.

    Drain-free boundary discipline: metric vectors are consumed by a
    ``_LaggedMetrics`` frontier ``_GUARD_LAG`` steps behind the
    dispatch — each read is a cheap D2H of 16 ready bytes, never a
    pipeline drain — so the epoch-end ``drain()`` waits only on the
    ≤ 2-step in-flight tail, and BEFORE that wait the next epoch's
    producer is started (``warm``): decode + H2D staging for epoch N+1
    overlap epoch N's tail drain, eval, and checkpoint. The bad-step
    verdicts ride the same replicated vectors, so every host counts the
    same sequence and agrees on the rollback decision without any
    extra collective. ``prefetch``: a warm handle from the PREVIOUS
    boundary (mutually exclusive with ``start_step`` skipping).

    ``telem`` (telemetry.TelemetrySession): per-step instrumentation is
    two host timestamps around the dispatch (goodput attribution +
    step-cadence sampling) plus an int comparison for the profiler
    window — the same zero-device-sync discipline as the guard above.

    ``pod`` (resilience/deadman.PodHeartbeat): per step, the heartbeat
    frontier is noted (lock + two int stores — host-only, same cost
    class as the telemetry sampler) and the DEGRADED flag is read
    twice: once at the loop top and once immediately before the
    dispatch (a fault/stall may have slept past a peer's death in
    between). A degraded pod raises ``exitcodes.PeerDeathError``
    BEFORE this host files into another collective the dead peer will
    never complete — carrying the current (clean, fully-retired under
    the raise conditions) state as salvage for the emergency snapshot.
    """
    t0 = time.time()
    data_time = AverageMeter("data")
    # Place the epoch's LR on the mesh ONCE, not per step: an
    # uncommitted numpy scalar handed to the jitted step is device_put
    # onto the replicated sharding at EVERY dispatch, and on multi-host
    # that placement runs an assert_equal broadcast collective — a
    # per-step host round-trip racing the in-flight step psums (gloo
    # aborts on the reorder; TPU just serializes). The local-data path
    # (every host computes the same lr_for_epoch) makes the placement
    # itself collective-free too, same as replicate_state.
    lr_arr = jax.make_array_from_process_local_data(
        jax.sharding.NamedSharding(mesh, jax.sharding.PartitionSpec()),
        np.asarray(lr, np.float32))
    interrupted_at = -1
    steps_done = start_step
    # ``health`` (telemetry/health.py): every consumed lagged vector's
    # HEALTH_FIELDS tail feeds the divergence detector; an anomaly with
    # --health-rollback armed trips the SAME rollback flag as the
    # non-finite guard — caught while the steps are still finite.
    # ``status``: the master's live status.json surface, rewritten at
    # each --log-every boundary (one atomic local write, no syncs).
    acc = _LaggedMetrics(max_bad=max(cfg.max_bad_steps, 0),
                         is_master=is_master, health=health,
                         health_rollback=cfg.health_rollback,
                         epoch=epoch, start_step=start_step,
                         wait_span="step_drain")
    rollback = False

    if prefetch is not None:
        assert start_step == 0, "warm prefetch cannot skip batches"
        prefetch_iter = prefetch
    else:
        # The loader opens its deterministic sample stream AT
        # (epoch, start_step) (data/stream.py): a mid-epoch resume
        # never decodes the already-trained prefix — the old
        # skip-and-discard path paid start_step full batch decodes
        # just to throw them away.
        it = loader.epoch(epoch, start_step=start_step)
        prefetch_iter = Prefetcher(mesh, it, depth=cfg.prefetch_depth)
    stats = prefetch_iter.stats
    if watchdog is not None:
        watchdog.arm()
    try:
        t_fetch = time.time()
        # Batches arrive as device arrays staged ahead (H2D overlapped
        # with the running step, data/prefetch.py; --prefetch-depth).
        for i, arrays in enumerate(prefetch_iter):
            step_i = start_step + i
            if pod is not None:
                pod.note(epoch=epoch, step=step_i, phase="train")
                pod.raise_if_degraded(state=state, epoch=epoch - 1,
                                      resume_step=steps_done)
            if _stop_agreed(stop_check, step_i):
                interrupted_at = steps_done
                break
            data_time.update(time.time() - t_fetch)
            images, labels = arrays
            if i == 0 and telem is not None:
                # Where the staged batch really lives (sharding
                # metadata, no sync): every data-axis device should
                # hold its own rows.
                shards = images.addressable_shards
                telem.gauge("batch_shard_devices",
                            len({s.device.id for s in shards}))
                telem.gauge("batch_shard_rows", shards[0].data.shape[0])
            lr_step = lr_arr
            if faultinject.active():  # drills only; falsy no-op otherwise
                f = faultinject.fire("step.grad_spike")
                if f is not None:
                    # Divergence drill: scale THIS dispatch's lr — the
                    # update ratio spikes on the spiked step itself and
                    # the blown-up params spike the following steps'
                    # loss/grad norms, all still FINITE: exactly the
                    # ramp the early-warning detector must catch before
                    # the non-finite guard sees anything. The eager
                    # multiply preserves the replicated sharding and
                    # dispatches async (no host sync).
                    factor = float(f.get("factor", 64.0))
                    print(f"FAULT step.grad_spike: lr x{factor:g} for "
                          "this step", flush=True)
                    lr_step = lr_arr * jnp.float32(factor)
                f = faultinject.fire("step.shape_change")
                if f is not None:
                    # Recompile drill: crop THIS batch spatially so the
                    # compiled step sees a new input shape mid-run —
                    # exactly the silent retrace the recompile sentinel
                    # (telemetry/recompile.py) must catch and name. The
                    # crop is done on the HOST (a deliberate sync: a
                    # device-side slice would itself jit-compile and
                    # the drill must produce exactly ONE new compile)
                    # and re-placed via the normal shard_batch path
                    # (pure placement, no compile).
                    crop = int(f.get("crop", 2))
                    print(f"FAULT step.shape_change: cropping this "
                          f"batch by {crop}px (forces a retrace)",
                          flush=True)
                    images, labels = shard_batch(mesh, np.asarray(images)[:, crop:, crop:, :], np.asarray(labels))  # jaxlint: disable=blocking-call-in-step-loop -- drill-only fault path; the hard host sync is the drill's point (stage ONE new shape with no extra eager-op compile)
                f = faultinject.fire("stall-step")
                if f is not None:  # hung collective / wedged input stand-in
                    time.sleep(float(f.get("secs", 5.0)))
                if faultinject.fire("nan-grads") is not None:
                    # Poison the batch: loss and every gradient go NaN,
                    # driving the in-graph skip + rollback path. The
                    # multiply promotes a uint8 wire batch to f32 (NaN
                    # has no uint8 encoding); the step retraces once
                    # for the f32 input and dequantizes it identically.
                    images = images * jnp.float32(np.nan)
                if faultinject.fire("sigterm") is not None:
                    os.kill(os.getpid(), signal.SIGTERM)
                f = faultinject.fire("host.die")
                if f is not None:
                    # Abrupt host loss (VM reclaim / kernel panic
                    # stand-in): no tombstone, no cleanup, no flushes —
                    # peers must detect THIS via heartbeat staleness
                    # alone (resilience/deadman.py).
                    print("FAULT host.die: hard-exiting this host now",
                          flush=True)
                    os._exit(int(f.get("code", 1)))
                f = faultinject.fire("group.die")
                if f is not None:
                    # Model-group loss: every armed rank in the TARGET
                    # rank's model group hard-exits — tombstone-free
                    # like host.die, standing in for a shared failure
                    # domain (one VM holding a whole TP pair, a rack
                    # power event). Arm on every rank; only the target's
                    # group dies. Params: rank=R (default: this rank),
                    # code=C.
                    me = (pod.rank if pod is not None
                          else jax.process_index())
                    target = int(f.get("rank", me))
                    mine = (pod.group_for(me) if pod is not None
                            else [me])
                    if target in mine:
                        print(f"FAULT group.die: rank {me} is in dead "
                              f"group {sorted(mine)} — hard-exiting "
                              "this host now", flush=True)
                        os._exit(int(f.get("code", 1)))
            if pod is not None:
                # Re-check right before the dispatch: the stall/fault
                # window above (or a long input wait) may have slept
                # across a peer's death — never enter the collective.
                pod.raise_if_degraded(state=state, epoch=epoch - 1,
                                      resume_step=steps_done)
            if telem is not None:
                telem.profile_step(
                    epoch * loader.steps_per_epoch + step_i)
                t_dispatch = time.perf_counter()
            state, metrics = train_step(state, images, labels, lr_step)
            if telem is not None:
                # Dispatch is async: this duration is µs on a steady
                # step and seconds on a compiling one — the accountant
                # splits compile from dispatch on that gap (and, when
                # tracing, the same measurement becomes the
                # dispatch/compile span — per step or coalesced into
                # windows by --trace mode).
                telem.record_dispatch(time.perf_counter() - t_dispatch,
                                      step=step_i)
            # The lagged frontier consumes the vector from _GUARD_LAG
            # steps ago (already retired — a free D2H, not a drain) and
            # carries the guard + log readout; NOTHING in this loop
            # body blocks on an in-flight result
            # (blocking-call-in-step-loop lint invariant).
            acc.push(metrics)
            steps_done += 1
            if acc.tripped or acc.health_tripped:
                rollback = True
                break
            if watchdog is not None:
                watchdog.beat()
            if is_master and cfg.log_every \
                    and (step_i + 1) % cfg.log_every == 0 \
                    and acc.last is not None:
                # The printed loss lags the step counter by
                # <= _GUARD_LAG steps (harmless for monitoring).
                m = acc.last
                print(f"  epoch {epoch + 1} step {step_i + 1}/"
                      f"{loader.steps_per_epoch} loss "
                      f"{m[0] / max(m[3], 1):.4f} "
                      f"data_time {data_time.avg:.3f}s",
                      flush=True)
                if status is not None:
                    # The live frontier for `python -m
                    # imagent_tpu.status`: one small atomic local
                    # write per log interval — same cost class as the
                    # print above, nothing device-side.
                    status.write({
                        "phase": "train", "epoch": epoch,
                        "epochs": cfg.epochs, "step": step_i + 1,
                        "steps_per_epoch": loader.steps_per_epoch,
                        "loss": float(m[0]) / max(float(m[3]), 1.0),
                        "lr": lr, "bad_steps": acc.bad_steps,
                        "degraded": bool(pod is not None
                                         and pod.degraded),
                        "health": (health.snapshot()
                                   if health is not None else None),
                    })
            t_fetch = time.time()
    finally:
        if watchdog is not None:
            watchdog.disarm()
        prefetch_iter.close()  # eager iterator: no GeneratorExit unwind
    # Warm the NEXT epoch's staging queue before draining this epoch's
    # metric tail: decode + H2D for epoch N+1 overlap the tail drain
    # and the eval/checkpoint phases at the boundary (drain-free epoch
    # boundary). Skipped on preemption (the run is exiting); discarded
    # below if the tail drain trips a rollback.
    warm: Prefetcher | None = None
    if (interrupted_at < 0 and not rollback
            and epoch + 1 < cfg.epochs):
        warm = Prefetcher(mesh, loader.epoch(epoch + 1),
                          depth=cfg.prefetch_depth)
    # Drain the ≤ _GUARD_LAG-step in-flight tail (not a sync). A trip
    # discovered here — the guard's or the health detector's — counts
    # only for a completed epoch; a preemption exit keeps the
    # interrupted-checkpoint path.
    if (acc.drain() or acc.health_tripped) and interrupted_at < 0:
        rollback = True
        if warm is not None:
            warm.close()
            warm = None
    epoch_metrics = acc.summary()
    # Which tripwire asked for the rollback: the caller's no-checkpoint
    # fallback must NOT claim "state unpoisoned" for a health trip —
    # the diverging updates, unlike guard-skipped ones, WERE applied.
    epoch_metrics["health_rollback"] = bool(acc.health_tripped)
    if telem is not None:
        # Every wait on the frontier — the lagged reads and the tail
        # drain — is the host waiting for the device to retire
        # dispatched steps: the device side of useful training work.
        telem.absorb_step_wait(acc.wait_s)
        telem.absorb_input(stats)
        telem.count("quarantined",
                    int(getattr(loader, "quarantined", 0) or 0))
        # Synthetic batches the generator pool had finished before the
        # producer asked for them (data/synthetic.py's ring): near the
        # epoch's batch count, the pool is not what the loop waits on.
        telem.count("synth_ahead_batches",
                    int(getattr(loader, "ahead_batches", 0) or 0))
        # Batches the decode-offload service missed (down/unreachable)
        # and local decode carried instead — a dying offload host is a
        # counter + warning, never a silent throughput cliff.
        telem.count("offload_fallbacks",
                    int(getattr(loader, "offload_fallbacks", 0) or 0))
    # Data-starvation counters (data/prefetch.py::PrefetchStats): how
    # long the step loop sat blocked on the staging queue, and the wire
    # bytes that crossed host→device — input-boundness diagnosable from
    # the epoch summary alone, no profiler trace needed.
    epoch_metrics["host_blocked_s"] = round(stats.wait_s, 3)
    epoch_metrics["h2d_bytes"] = int(stats.bytes_staged)
    return (state, epoch_metrics, time.time() - t0, interrupted_at,
            rollback, warm)


def evaluate(cfg: Config, mesh, eval_step, state: TrainState, loader,
             epoch: int, telem: TelemetrySession | None = None,
             ) -> tuple[dict, float]:
    """Validation epoch (reference ``validate()``, ``imagenet.py:166-210``),
    exact under padding via the mask. With --ema-decay the evaluated
    weights are the EMA (``model.eval()`` on the averaged model) AND so
    are the BatchNorm stats — the live running stats track the LIVE
    params' activation distribution, so pairing them with EMA params
    diverges when the params drift fast (train.TrainState docstring);
    the tree structure is unchanged, so the compiled step and its
    shardings are reused as-is."""
    if cfg.ema_decay > 0.0 and state.ema_params is not None:
        state = state.replace(params=state.ema_params)
        if state.ema_batch_stats is not None:
            state = state.replace(batch_stats=state.ema_batch_stats)
    t0 = time.time()
    stats = PrefetchStats()
    # Pipelined eval: every shard is dispatched before any metric
    # vector is waited on — the lagged frontier (mirroring the train
    # guard's _GUARD_LAG) fetches only already-retired vectors while
    # later shards are still dispatching, so the fetch cost hides
    # under the eval compute instead of serializing after it.
    acc = _LaggedMetrics()
    # trace_name: eval-side queue waits become `eval_input` DATA spans,
    # never `input_wait` PHASE spans — the spans-vs-goodput consistency
    # gate judges the train step loop alone, mirroring the
    # absorb_eval_input partition below.
    for images, labels, mask in device_prefetch(
            mesh, loader.epoch(epoch), with_mask=True,
            depth=cfg.prefetch_depth, stats=stats,
            trace_name="eval_input"):
        acc.push(eval_step(state, images, labels, mask))
    acc.drain()
    metrics = acc.summary()
    metrics["host_blocked_s"] = round(stats.wait_s, 3)
    metrics["h2d_bytes"] = int(stats.bytes_staged)
    if telem is not None:
        # The eval epoch is one `eval` phase to the goodput accountant
        # (attributed by the caller); its internal input-wait rides the
        # eval-side counters — strictly partitioned from the train
        # `input_wait` phase and its alert threshold. The val loader
        # runs the same offload client (split="val"): its fallbacks
        # must surface too, not just the train loader's.
        telem.absorb_eval_input(stats)
        telem.count("eval_offload_fallbacks",
                    int(getattr(loader, "offload_fallbacks", 0) or 0))
    return metrics, time.time() - t0


def _load_torch_weights(cfg: Config, state: TrainState) -> TrainState:
    """Convert a torch ``state_dict`` checkpoint (the reference's save
    format, ``imagenet.py:392``) into this state's params/batch_stats.
    Shape agreement with the freshly-initialized tree is enforced, so
    arch/num-classes mismatches fail loudly."""
    import torch

    from imagent_tpu.compat import resnet_from_torch, vit_from_torch

    sd = torch.load(cfg.init_from_torch, map_location="cpu")
    if isinstance(sd, dict) and "state_dict" in sd:
        sd = sd["state_dict"]
    sd = {k: v.numpy() for k, v in sd.items()}
    if cfg.arch.startswith("vit"):
        from imagent_tpu.models.vit import VIT_REGISTRY
        params = vit_from_torch(sd, VIT_REGISTRY[cfg.arch]["num_heads"])
        stats = state.batch_stats
    elif cfg.arch.startswith("convnext"):
        from imagent_tpu.compat import convnext_from_torch
        params = convnext_from_torch(sd)
        stats = state.batch_stats  # {} — ConvNeXt has no BN buffers
    else:
        from imagent_tpu.models.resnet import STAGE_SIZES
        params, stats = resnet_from_torch(sd, STAGE_SIZES[cfg.arch])

    def check(path, old, new):
        new = np.asarray(new, dtype=np.asarray(old).dtype)
        if np.shape(new) != np.shape(old):
            raise ValueError(
                f"torch checkpoint shape mismatch at "
                f"{jax.tree_util.keystr(path)}: {np.shape(new)} vs "
                f"{np.shape(old)} (wrong --arch/--num-classes?)")
        return new

    params = jax.tree_util.tree_map_with_path(check, state.params, params)
    stats = jax.tree_util.tree_map_with_path(check, state.batch_stats,
                                             stats)
    return state.replace(params=params, batch_stats=stats)


def _export_torch(cfg: Config, state, is_master: bool,
                  prefer_best: bool = False) -> None:
    """--export-torch: write the final params (+ batch_stats) as a
    torchvision-named torch ``state_dict`` — the inverse of
    ``--init-from-torch`` (the reference's checkpoint format,
    ``imagenet.py:392``, without the DDP prefix so torchvision loads it
    directly). Under ``--ema-decay`` the EMA weights are exported —
    the same weights every reported val metric was evaluated on
    (``evaluate()``), so the exported model reproduces the logged
    accuracy. Runs after training or the ``--eval-only`` pass.

    ``prefer_best`` (the end-of-training call site): the run summary
    headlines ``best_top1``, and the reference's ``.pt`` is saved at
    the best epoch (``imagenet.py:388-392``) — so when ``--save-model``
    kept a BEST checkpoint, export THOSE weights, not the final-epoch
    state. Falls back to the final state with a logged warning when no
    BEST is restorable (--save-model off, or no eval improved), in
    which case the export matches ``final_val``, not ``best_top1``.
    The restore goes through ``restore_resilient`` so every verdict is
    pod-agreed: one host with a missing/torn BEST replica must divert
    ALL hosts to the same fallback (or to the final state), never
    allgather an export whose shards mix two generations."""
    if not cfg.export_torch:
        return
    if prefer_best:
        restored = (ckpt_lib.restore_resilient(cfg.ckpt_dir, state,
                                               name=ckpt_lib.BEST)
                    if cfg.save_model else None)
        if restored is not None:
            state, best_meta, _cand = restored
            if is_master:
                print("exporting the BEST checkpoint (epoch "
                      f"{int(best_meta.get('epoch', -1)) + 1}, top1 "
                      f"{float(best_meta.get('best_top1', 0.0)):.3f}) — "
                      "the weights behind the summary's best_top1",
                      flush=True)
        elif is_master:
            print("WARNING: --export-torch exporting the FINAL-epoch "
                  "state (no BEST checkpoint to restore"
                  + ("" if cfg.save_model else "; --save-model is off")
                  + ") — the export matches final_val, not best_top1",
                  flush=True)
    # Eval parity: export what evaluate() scores.
    if cfg.ema_decay > 0.0 and state.ema_params is not None:
        state = state.replace(params=state.ema_params)
        if state.ema_batch_stats is not None:
            state = state.replace(batch_stats=state.ema_batch_stats)
    if jax.process_count() > 1:
        # Sharded leaves are not fully addressable on any one host —
        # gather them (same multihost path as the stop-flag reduce).
        from jax.experimental import multihost_utils
        params = multihost_utils.process_allgather(state.params)
        stats = multihost_utils.process_allgather(state.batch_stats)
    else:
        params = jax.device_get(state.params)
        stats = jax.device_get(state.batch_stats)
    if not is_master:
        return
    import torch

    from imagent_tpu.compat import to_torch_state_dict

    sd = to_torch_state_dict(cfg.arch, params, stats)

    def as_tensor(v):
        t = np.asarray(v)
        if t.dtype.kind in "iu":
            t = t.copy()  # from_numpy needs an owned, writable buffer
        else:  # bf16 params upcast losslessly; astype always copies
            t = t.astype(np.float32)
        return torch.from_numpy(t)

    torch.save({k: as_tensor(v) for k, v in sd.items()}, cfg.export_torch)
    print(f"exported torch state_dict ({len(sd)} tensors) to "
          f"{cfg.export_torch}", flush=True)


def run(cfg: Config, stop_check=None) -> dict:
    """Full training run. Returns the final summary dict.

    ``stop_check``: optional zero-arg callable polled each step; when it
    returns True the run checkpoints and exits cleanly (defaults to a
    ``PreemptionGuard`` on SIGTERM/SIGUSR1). With ``--watchdog-secs``
    a step-progress watchdog rides the same stop path: a wedged run
    (hung collective, stuck input pipeline) dumps all-thread stacks,
    checkpoints LAST, and exits cleanly for the scheduler to requeue.
    Fault drills: ``--faults`` / ``IMAGENT_FAULTS`` arm named fault
    points (resilience/faultinject.py).

    Model health (``--health-stats``, on by default): the train step's
    metric vector carries grad/param-norm and update-ratio scalars
    consumed on the lagged frontier; an EWMA divergence detector warns
    (and with ``--health-rollback`` rolls back) BEFORE the non-finite
    guard can fire, a flight recorder of the last N step records is
    flushed on every fatal exit path, and process 0 keeps
    ``status.json`` live for ``python -m imagent_tpu.status``
    (docs/OPERATIONS.md "Reading model health").

    With ``--peer-deadline-secs`` the out-of-band heartbeat mesh runs
    for the whole call (resilience/heartbeat + deadman): this host
    beats into ``<log_dir>/heartbeats/`` and watches its peers with no
    collectives; a dead peer degrades the pod — the loops stop
    entering collectives at the next check, process 0 lands a
    collective-free emergency snapshot, and the run raises
    ``exitcodes.PeerDeathError`` (exit code 87, retryable) for the
    launcher's requeue wrapper. Every fatal exit path leaves a
    tombstone record peers classify instantly.

    ``--elastic`` (with the fixed ``--global-batch`` contract) turns
    the death verdict into CONTINUE: the lowest survivor lands the
    salvage, every survivor departs on a done-beat and exec-restarts
    into the filesystem rendezvous (``imagent_tpu/elastic.py``), and
    the re-formed smaller pod restores the salvage at the exact
    (epoch, step) frontier with gradient accumulation absorbing the
    lost rank — the loss trajectory follows the batch, not the world
    size. Grow rides join requests + the pod-agreed stop
    (docs/OPERATIONS.md "Elastic pod")."""
    # Mesh-axis shorthand (--tp/--pp/--dp, the production spelling for
    # model-axis pods) resolves into the legacy fields BEFORE any
    # validation so every downstream check sees one spelling.
    if cfg.tp < 0 or cfg.pp < 0 or cfg.dp < 0:
        raise ValueError("--tp/--pp/--dp must be >= 0 (0 = unset)")
    if cfg.tp:
        if cfg.tensor_parallel or cfg.model_parallel > 1:
            raise ValueError(
                "--tp N is the shorthand for --tensor-parallel "
                "--model-parallel N; pass one spelling, not both")
        if cfg.tp < 2:
            raise ValueError("--tp must be >= 2 (a 1-wide tensor axis "
                             "is plain DP; drop --tp)")
        cfg = cfg.replace(tensor_parallel=True, model_parallel=cfg.tp)
    if cfg.pp:
        if cfg.pipeline_parallel > 1:
            raise ValueError(
                "--pp N is the shorthand for --pipeline-parallel N; "
                "pass one spelling, not both")
        if cfg.pp < 2:
            raise ValueError("--pp must be >= 2 (a 1-stage pipeline is "
                             "no pipeline; drop --pp)")
        cfg = cfg.replace(pipeline_parallel=cfg.pp)
    # Elastic-pod flag contract, validated BEFORE any distributed init
    # (a bad combination must fail on the launch host, not at pod
    # rendezvous time).
    if cfg.global_batch < 0:
        raise ValueError("--global-batch must be >= 0 (0 = legacy "
                         "batch_size x dp x grad_accum)")
    if cfg.global_batch and cfg.grad_accum > 1:
        raise ValueError(
            "--grad-accum is DERIVED under the --global-batch "
            "contract (global_batch / (batch_size x dp)); drop "
            "--grad-accum, or drop --global-batch to size the global "
            "batch from it")
    if cfg.elastic:
        if cfg.global_batch <= 0:
            raise ValueError(
                "--elastic requires --global-batch: a resize with the "
                "global batch tied to world size would silently "
                "change the optimization trajectory (lr/batch "
                "contract). Set --global-batch to the fixed "
                "optimization batch; grad accumulation absorbs the "
                "lost/regained hosts.")
        if cfg.seq_parallel != "none" or cfg.expert_parallel:
            raise ValueError(
                "--elastic supports plain DP, --fsdp, --zero1, and "
                "the tensor/pipeline meshes (--tp/--pp: one dead rank "
                "condemns its whole model group, survivors shrink by "
                "whole groups, and sharded snapshots reshard onto the "
                "resized mesh); seq-parallel and expert-parallel stay "
                "refused — their token/expert routing re-partitions "
                "activation state across the model axis and no "
                "group-aligned salvage covers it yet")
        if cfg.elastic_settle_secs <= 0:
            raise ValueError("--elastic-settle-secs must be > 0")
    if cfg.ckpt_format not in ("snapshot", "orbax"):
        raise ValueError("--ckpt-format must be one of snapshot|orbax, "
                         f"got {cfg.ckpt_format!r}")
    if cfg.elastic and cfg.ckpt_format == "orbax":
        raise ValueError(
            "--elastic requires --ckpt-format snapshot: the legacy "
            "Orbax path cannot land a collective-free emergency "
            "salvage or reshard a sharded checkpoint onto the "
            "resized mesh")
    if (cfg.ckpt_format == "orbax"
            and (cfg.model_parallel > 1 or cfg.pipeline_parallel > 1)):
        raise ValueError(
            "--ckpt-format orbax does not cover model-axis meshes "
            "(tp/pp leaves shard across the mesh and the legacy Orbax "
            "path has no sharded save/restore or salvage coverage "
            "rule); use --ckpt-format snapshot")
    # SLO / exporter flag contract (telemetry/slo.py + export.py): a
    # bad spec or port must fail on the launch host, before any
    # distributed init.
    if cfg.metrics_port < 0:
        raise ValueError("--metrics-port must be >= 0 (0 = off)")
    if cfg.metrics_port and not cfg.telemetry:
        raise ValueError("--metrics-port serves the telemetry "
                         "session's epoch-boundary state; drop "
                         "--no-telemetry")
    slo_lib.parse_spec_arg(cfg.slo)  # raises ValueError on a bad spec
    if cfg.slo not in ("", "off") and not cfg.telemetry:
        raise ValueError("--slo evaluates the telemetry epoch record; "
                         "drop --no-telemetry")
    # cfg.backend selects the PJRT platform: "cpu"/"gpu" are forced,
    # overriding any environment preset; "tpu" is what JAX selects
    # wherever a chip is attached — and require_backend below refuses
    # (fatal-config, exit 78) anything else the runtime fell back to.
    # --elastic: membership comes from the filesystem rendezvous (the
    # roster of processes that actually showed up), not the scheduler
    # env — a requeued pod missing a host re-forms at N-1 instead of
    # timing out, and the full relaunch re-expands.
    elastic_kw = {}
    # Processes per model group (the set of ranks jointly holding one
    # model replica). The rendezvous runs BEFORE the JAX backend exists,
    # so the pre-init value uses the IMAGENT_LOCAL_DEVICES hint; the
    # real local device count re-verifies it right after init.
    group_size_hint = groups_lib.process_group_size(
        cfg.model_parallel, cfg.pipeline_parallel,
        groups_lib.env_local_devices())
    if cfg.elastic:
        elastic_kw = dict(
            elastic_dir=elastic_lib.elastic_dir(cfg.log_dir),
            elastic_settle=cfg.elastic_settle_secs,
            group_size=group_size_hint)
    senv = cluster.initialize(cfg.backend or None, **elastic_kw)
    cluster.require_backend(cfg.backend or None)
    # Real (post-init) group size. A wrong IMAGENT_LOCAL_DEVICES hint
    # under --elastic means the roster was committed against the wrong
    # group map — refuse loudly rather than shrink by the wrong stride.
    proc_group_size = groups_lib.process_group_size(
        cfg.model_parallel, cfg.pipeline_parallel,
        jax.local_device_count())
    if (cfg.elastic and senv is not None and getattr(senv, "members", ())
            and proc_group_size != group_size_hint):
        raise ValueError(
            f"model-group size mismatch: the elastic rendezvous "
            f"committed the roster assuming "
            f"{groups_lib.LOCAL_DEVICES_ENV}="
            f"{groups_lib.env_local_devices()} (group size "
            f"{group_size_hint}) but this process has "
            f"{jax.local_device_count()} local devices (group size "
            f"{proc_group_size}); export "
            f"{groups_lib.LOCAL_DEVICES_ENV} to the real per-process "
            "device count in the launch wrapper")
    faultinject.configure(cfg.faults or None)
    if faultinject.active() and jax.process_index() == 0:
        print(f"FAULT DRILL: fault points armed ({cfg.faults or 'env'})",
              flush=True)
    if cfg.peer_deadline_secs < 0:
        raise ValueError("--peer-deadline-secs must be >= 0 (0 = off)")
    if cfg.flightrec_steps < 0:
        raise ValueError("--flightrec-steps must be >= 0 (0 = off)")
    pod = None
    if cfg.peer_deadline_secs > 0:
        if cfg.heartbeat_secs <= 0:
            raise ValueError("--heartbeat-secs must be > 0 when the "
                             "peer deadman is armed")
        if cfg.peer_deadline_secs < 2.0 * cfg.heartbeat_secs:
            raise ValueError(
                f"--peer-deadline-secs ({cfg.peer_deadline_secs:g}) "
                f"must be >= 2x --heartbeat-secs "
                f"({cfg.heartbeat_secs:g}): a single missed write "
                "would read as a host death")
        # Heartbeat/tombstone identity is the LAUNCHED rank (the stable
        # scheduler slot): it survives elastic re-numbering, so a
        # re-formed pod keeps reading the same per-host files. The
        # monitor watches only the current roster's members — a slot
        # the pod already resized away must not be judged again.
        launched_rank = jax.process_index()
        launched_world = jax.process_count()
        members = None
        if senv is not None and getattr(senv, "members", ()):
            launched_rank = senv.launched_rank
            launched_world = senv.launched_world
            members = list(senv.members)
        pod = PodHeartbeat(cfg.log_dir, launched_rank, launched_world,
                           deadline_secs=cfg.peer_deadline_secs,
                           interval_secs=cfg.heartbeat_secs,
                           members=members,
                           group_size=proc_group_size,
                           continue_on_death=cfg.elastic,
                           elastic_dir=(elastic_lib.elastic_dir(
                               cfg.log_dir) if cfg.elastic else None),
                           elastic_attempt=(getattr(
                               senv, "elastic_attempt", 0)
                               if senv is not None else 0))
        pod.start()
        deadman_lib.activate(pod)
    if cfg.trace not in trace_lib.MODES:
        raise ValueError(f"--trace must be one of "
                         f"{'|'.join(trace_lib.MODES)}, got "
                         f"{cfg.trace!r}")
    if cfg.trace_buffer < 1:
        raise ValueError("--trace-buffer must be >= 1 (spans kept "
                         "per thread between flushes)")
    if cfg.trace != "off" and not cfg.telemetry:
        raise ValueError("--trace rides the telemetry session (phase "
                         "boundaries, the epoch-boundary flush, the "
                         "clock allgather); drop --no-telemetry")
    tracer = None
    if cfg.trace != "off":
        # Pod tracer (telemetry/trace.py): every subsystem emits spans
        # through the module-global recorder; rings are flushed to
        # trace/trace.<rank>.jsonl at each epoch boundary
        # (TelemetrySession.epoch_end) and on every fatal ramp below —
        # the same exits that flush the flight recorder.
        tracer = trace_lib.TraceRecorder(
            cfg.log_dir, jax.process_index(), mode=cfg.trace,
            buffer=cfg.trace_buffer)
        trace_lib.activate(tracer)
    recorder = None
    if cfg.flightrec_steps > 0 and cfg.health_stats:
        # Crash flight recorder (telemetry/flightrec.py): the last N
        # lagged health records, landed as flightrec.<rank>.json by
        # every fatal exit ramp below — including the watchdog's and
        # deadman's hard-exit threads, which reach it through the
        # module-global active handle / the pod's tombstone hook.
        recorder = flightrec_lib.FlightRecorder(
            cfg.log_dir, jax.process_index(),
            capacity=cfg.flightrec_steps)
        flightrec_lib.activate(recorder)
    if pod is not None:
        # Every tombstone write (all deliberate fatal ramps funnel
        # there, including the monitor threads' os._exit paths) first
        # flushes the flight recorder and references it in the detail.
        # The span rings ride the same hook: a fatal exit's trace tail
        # (the spans of the seconds before death) lands durably before
        # the tombstone classifies the exit.
        def _pod_fatal(reason, exit_code, detail=""):
            trace_lib.flush_active(fsync=True)
            return flightrec_lib.flush_active(reason, exit_code,
                                              detail=detail)

        pod.on_fatal = _pod_fatal
    guard = None
    if stop_check is None:
        stop_check = guard = PreemptionGuard()
    watchdog = None
    if cfg.watchdog_secs > 0:
        watchdog = StepWatchdog(cfg.watchdog_secs)
        base_stop = stop_check
        stop_check = lambda: watchdog.fired or base_stop()  # noqa: E731

        def _on_watchdog_escalate():
            # Hard-exit ramp: land the forensic record, then (with the
            # mesh armed) the classified tombstone so peers fail over
            # instantly instead of waiting out the staleness deadline.
            # (With a pod, tombstone() reaches the trace flush through
            # on_fatal; without one, flush here — the timeline of a
            # hung run is exactly what the 86 post-mortem needs.)
            detail = "no step progress; main thread never polled"
            if pod is not None:
                pod.tombstone("watchdog-hard-exit",
                              exitcodes.WATCHDOG_HARD_EXIT,
                              detail=detail)  # flushes via on_fatal
            else:
                trace_lib.flush_active(fsync=True)
                flightrec_lib.flush_active(
                    "watchdog-hard-exit",
                    exitcodes.WATCHDOG_HARD_EXIT, detail=detail)

        watchdog.on_escalate = _on_watchdog_escalate
    try:
        return _run(cfg, stop_check, senv, watchdog, pod, recorder)
    except exitcodes.FatalRunError as e:
        # Classified fatal exits (peer death, storage outage, rollback
        # give-up): span rings and flight recorder first (write-once —
        # an exit ramp may have flushed already), then the tombstone;
        # its writer's write-once guard keeps the first cause. A
        # RESIZE is not a death: the survivors depart on a done-beat
        # and re-form — a tombstone here would read as a fresh fatal
        # to the very peers about to rendezvous with us.
        trace_lib.flush_active(fsync=True)
        flightrec_lib.flush_active(e.reason, e.exit_code,
                                   detail=str(e))
        if pod is not None and not isinstance(
                e, exitcodes.PodResizeError):
            pod.tombstone(e.reason, e.exit_code, detail=str(e))
        raise
    except ValueError as e:
        trace_lib.flush_active(fsync=True)
        flightrec_lib.flush_active("fatal-config",
                                   exitcodes.FATAL_CONFIG,
                                   detail=str(e))
        if pod is not None:
            pod.tombstone("fatal-config", exitcodes.FATAL_CONFIG,
                          detail=str(e))
        raise
    except Exception as e:
        trace_lib.flush_active(fsync=True)
        detail = f"{type(e).__name__}: {e}"
        if chipacct_lib.classify_oom(e):
            # A runtime RESOURCE_EXHAUSTED that slipped past (or ran
            # without) the preflight: lead with the accountant's
            # per-component byte table so it survives the flightrec
            # detail truncation — the post-mortem starts from WHERE
            # the bytes went, not just that they ran out.
            detail = (chipacct_lib.oom_detail(_chipacct_active)
                      + "; " + detail)
        flightrec_lib.flush_active(
            "exception", exitcodes.FATAL_EXCEPTION, detail=detail)
        if pod is not None:
            pod.tombstone("exception", exitcodes.FATAL_EXCEPTION,
                          detail=detail)
        raise
    finally:
        # Final flush (a clean exit's post-boundary spans: the last
        # commit land, the torch export) + deactivate.
        trace_lib.close_active()
        flightrec_lib.deactivate()
        # The recompile sentinel and the OpenMetrics endpoint live
        # exactly as long as the run: compiles after this are not this
        # run's problem, and a closed port (connection refused) is the
        # scraper's down signal — module-global handles so the fatal
        # ramps above need no extra plumbing.
        recompile_lib.deactivate()
        export_lib.close_active()
        if pod is not None:
            deadman_lib.deactivate()
            pod.stop()
        if watchdog is not None:
            watchdog.stop()
        if guard is not None:
            guard.uninstall()


# Rollback-to-checkpoint attempts before declaring the run unrecoverable
# (persistent non-finite gradients re-poison every replay — a config
# problem, not a transient).
_MAX_ROLLBACKS = 3

# Consecutive failed async checkpoint commits before the run classifies
# the storage as dead and exits retryable. Each failed commit already
# survived the committer's own bounded backoff retries and left the
# previous generation intact — a streak means the outage outlives the
# epoch cadence, and a run that can no longer land checkpoints is
# silently un-resumable (every epoch trained past the last good
# generation is lost on the next failure).
_MAX_CKPT_FAIL_STREAK = 3


def _storage_guard(fn, *args, **kwargs):
    """Run a blocking checkpoint save, classifying storage-level
    failures (OSError: dir vanished, mount dead, disk full) as the
    retryable storage-outage exit instead of an anonymous crash. The
    commit dance guarantees the previous generation survives any
    failed attempt (checkpoint._commit_files: live is never the write
    target)."""
    try:
        return fn(*args, **kwargs)
    except OSError as e:
        raise exitcodes.StorageOutageError(
            f"checkpoint save failed ({type(e).__name__}: {e}) — "
            "checkpoint storage looks dead; the previous committed "
            "generation is intact. Exiting retryable for the launcher "
            "to requeue onto --resume.") from e


def _pod_death_exit(cfg: Config, err, pod, telem, epoch: int,
                    topo_meta: dict, best_meta: dict,
                    is_master: bool) -> None:
    """The degraded-pod exit ramp: everything here is out-of-band —
    NO collectives, NO barriers (the dead peer would never arrive).

    Process 0 lands the salvage state (if the raise site could vouch
    for one) as a collective-free flat emergency snapshot committed as
    LAST — the requeued pod's ``--resume`` restores it through the
    normal fallback walk. The detection verdict goes to the telemetry
    event log (``pod_degraded``) and this host's tombstone, so the
    remaining survivors classify our exit instantly instead of waiting
    out their own staleness deadlines (detection cascades outward in
    O(deadline), not O(world x deadline))."""
    v = dict(err.verdict or {})
    v["epoch"] = int(epoch)
    is_resize = isinstance(err, exitcodes.PodResizeError)
    v["continue"] = bool(is_resize)
    print(f"DEADMAN: {err} — landing what can be landed without "
          "collectives and "
          + ("re-forming the pod on the survivors (elastic continue, "
             f"code {err.exit_code})" if is_resize else
             f"exiting retryable (code {err.exit_code})"), flush=True)
    telem.pod_degraded(v)
    salvage = err.salvage
    # The salvage lander is the LOWEST SURVIVING member, not process 0:
    # the dead host may BE process 0, and losing the salvage with it
    # would turn every rank-0 death into a lost mid-epoch frontier.
    # The flat emergency format is pure local file I/O, so any single
    # host can commit it (checkpoint.save_emergency(any_rank=True)).
    # SHARDED states (multi-host FSDP/TP/ZeRO-1): every survivor dumps
    # its own addressable windows — still pure local file I/O — and
    # the lander assembles them under the coverage rule (commit iff
    # the survivors' union tiles every leaf; honest incomplete-coverage
    # fallback otherwise). Shard files are keyed by the ACTIVE mesh
    # process id (the member's position in the sorted roster), not the
    # launched rank, because that is what decides which windows a host
    # holds.
    # Death condemns the dead peer's whole MODEL GROUP (the verdict's
    # "group", deadman._trip): the group's other ranks hold unusable
    # partial replicas, so they are dead for salvage and roster
    # purposes even while their processes still breathe. Survivors are
    # therefore whole groups only — min(survivors) is automatically in
    # a covering group (its ranks tile every leaf window), and the
    # shardfmt coverage rule stays the final arbiter: no whole group
    # surviving means the windows cannot tile, and the lander reports
    # the honest incomplete-coverage verdict instead of committing.
    members = (list(pod.members) if pod is not None
               else list(range(jax.process_count())))
    my_rank = pod.rank if pod is not None else jax.process_index()
    dead = set(int(r) for r in
               (v.get("group")
                or ([v["peer"]] if v.get("peer") is not None else [])))
    survivors = [r for r in members if r not in dead]
    i_land = bool(survivors) and my_rank == min(survivors)
    i_condemned = my_rank in dead
    sharded = salvage is not None and not snapshotable(salvage["state"])
    if i_condemned:
        # Our own group lost a rank: our windows are exactly the ones
        # the survivors' groups duplicate (or, with no whole group
        # left, the ones nobody can vouch a consistent frontier for) —
        # stay out of the salvage and let the roster exclude us.
        salvage = None
        print(f"DEADMAN: rank {my_rank} is in the dead peer's model "
              f"group {sorted(dead)} — condemned with it (partial "
              "replica); standing down from salvage", flush=True)
    if salvage is not None and (i_land or sharded):
        health_meta = (telem.health.meta_snapshot()
                       if telem.health is not None else {})
        meta = {**best_meta, **topo_meta, **health_meta,
                "epoch": int(salvage["epoch"]),
                "resume_step": int(salvage["resume_step"]),
                "emergency": 1}
        sorted_members = sorted(int(r) for r in members)
        try:
            landed = ckpt_lib.save_emergency(
                cfg.ckpt_dir, ckpt_lib.LAST, salvage["state"], meta,
                keep_last_k=cfg.keep_last_k, any_rank=True,
                lander=i_land,
                rank=sorted_members.index(int(my_rank)),
                survivors=[sorted_members.index(int(r))
                           for r in survivors])
            if landed:
                print("DEADMAN: emergency snapshot committed as LAST "
                      f"(epoch {meta['epoch'] + 1}, "
                      f"resume_step {meta['resume_step']}, landed by "
                      f"host {my_rank}"
                      + (", sharded format" if sharded else "")
                      + "); --resume restores it", flush=True)
        except Exception as se:
            print(f"WARNING: emergency snapshot failed "
                  f"({type(se).__name__}: {se}); the last committed "
                  "generation stands", flush=True)
    if pod is not None and not is_resize:
        pod.tombstone(err.reason, err.exit_code, detail=str(err))


def _build_model_and_steps(cfg, mesh, n_data: int, accum: int,
                           is_master: bool):
    """Model + init + placement + step builders, extracted from the
    body of ``_run`` so the ``compilecache warm`` CLI can construct
    the EXACT executables a training run would — and so the cache-key
    completeness guard (tests/test_compilecache.py) can diff this
    function's ``cfg.<field>`` reads against
    ``compilecache.COMPILE_FIELDS``: every config field read here
    shapes the compiled step and must be in the fingerprint (or in
    the justified ``EXEMPT_FIELDS``).

    Returns ``(train_step, eval_step, state, state_specs, init_s)``
    with the state already placed on ``mesh``; ``init_s`` is the
    seconds ``create_train_state`` took until its state was ready.
    Pure construction: config validation (including the sp/tp/pp/ep
    composition rules) happened in ``_run`` before the loaders were
    built."""
    use_sp = cfg.seq_parallel != "none"
    use_tp = cfg.tensor_parallel
    use_pp = cfg.pipeline_parallel > 1
    use_ep = cfg.expert_parallel
    if ((cfg.fused_qkv or cfg.register_tokens)
            and not cfg.arch.startswith("vit")):
        raise ValueError("--fused-qkv / --register-tokens apply to the "
                         "ViT family only")
    # ViT perf levers ride every ViT construction site (model and init
    # twin alike — register tokens add params, so the trees must agree;
    # fused_qkv keeps the tree unchanged either way).
    vit_kw = ({"fused_qkv": cfg.fused_qkv,
               "register_tokens": cfg.register_tokens}
              if cfg.arch.startswith("vit") else {})

    if use_sp:
        # Optionally pipelined: layers shard over `pipe`, tokens over
        # `model` — the ring/Ulysses collectives run inside each stage.
        pp_kw = (dict(pipe_axis=cluster.PIPE_AXIS,
                      microbatches=cfg.microbatches) if use_pp else {})
        model = create_model(
            cfg.arch, cfg.num_classes, cfg.bf16, gap_readout=True,
            attn_impl=cfg.seq_parallel, seq_axis=cluster.MODEL_AXIS,
            remat=cfg.remat, **pp_kw, **vit_kw)
        # Same param tree, no mesh-axis ops — usable for the mesh-free init.
        init_model = create_model(cfg.arch, cfg.num_classes, cfg.bf16,
                                  gap_readout=True, remat=cfg.remat,
                                  **({"stacked": True} if use_pp else {}),
                                  **vit_kw)
    elif cfg.moe_every:
        moe_kw = dict(moe_every=cfg.moe_every, num_experts=cfg.num_experts,
                      capacity_factor=cfg.capacity_factor,
                      moe_groups=cfg.moe_groups, moe_top_k=cfg.moe_top_k)
        pp_kw = (dict(pipe_axis=cluster.PIPE_AXIS,
                      microbatches=cfg.microbatches) if use_pp else {})
        model = create_model(
            cfg.arch, cfg.num_classes, cfg.bf16, attn_impl=cfg.attn,
            expert_axis=cluster.MODEL_AXIS if use_ep else None,
            **moe_kw, **pp_kw, remat=cfg.remat, **vit_kw)
        # Mesh-free init twin: same param tree; EP consumes slices of it.
        # groups=1 — params don't depend on the capacity grouping, and
        # the init batch (2 images) need not divide the run's groups.
        # Under pp the twin is the layer-stacked pipe-free model.
        init_model = create_model(cfg.arch, cfg.num_classes, cfg.bf16,
                                  attn_impl=cfg.attn,
                                  **({"stacked": True} if use_pp else {}),
                                  **{**moe_kw, "moe_groups": 1},
                                  remat=cfg.remat, **vit_kw)
    elif use_pp and not cfg.arch.startswith("vit"):
        # ResNet family: 2-stage GPipe over heterogeneous conv stages,
        # params replicated over pipe (parallel/resnet_pipeline.py).
        from imagent_tpu.parallel.resnet_pipeline import PipelinedResNet
        init_model = create_model(cfg.arch, cfg.num_classes, cfg.bf16,
                                  remat=cfg.remat, stem=cfg.stem)
        model = PipelinedResNet(init_model, cfg.microbatches)
    elif use_pp:
        model = create_model(
            cfg.arch, cfg.num_classes, cfg.bf16, attn_impl=cfg.attn,
            pipe_axis=cluster.PIPE_AXIS, microbatches=cfg.microbatches,
            tp_axis=cluster.MODEL_AXIS if use_tp else None,
            remat=cfg.remat, **vit_kw)
        # The mesh-free init uses the layer-stacked pipe-free twin (same
        # param tree, parallel/pipeline.py).
        init_model = create_model(cfg.arch, cfg.num_classes, cfg.bf16,
                                  attn_impl=cfg.attn, stacked=True,
                                  remat=cfg.remat, **vit_kw)
    elif use_tp and not cfg.fsdp:
        model = create_model(cfg.arch, cfg.num_classes, cfg.bf16,
                             attn_impl=cfg.attn,
                             tp_axis=cluster.MODEL_AXIS,
                             remat=cfg.remat, **vit_kw)
        # The mesh-free init uses the unsharded twin; TP consumes slices of
        # the same param tree (parallel/tensor_parallel.py).
        init_model = create_model(cfg.arch, cfg.num_classes, cfg.bf16,
                                  attn_impl=cfg.attn, remat=cfg.remat,
                                  **vit_kw)
    elif cfg.arch.startswith("vit") and cfg.attn != "full":
        model = create_model(cfg.arch, cfg.num_classes, cfg.bf16,
                             attn_impl=cfg.attn, remat=cfg.remat,
                             **vit_kw)
        init_model = model
    else:
        if cfg.arch.startswith("vit"):
            kw = vit_kw
        elif cfg.arch.startswith("convnext"):
            # stem/vit levers don't apply; drop-path is library-level
            # (models/convnext.py docstring). --fused-mlp selects the
            # Pallas block lowering (same param tree in every mode).
            kw = {"fused_mlp": cfg.fused_mlp}
            if cfg.fused_mlp != "off" and is_master:
                from imagent_tpu.models.convnext import CONVNEXT_DEFS
                from imagent_tpu.ops.fused_mlp import fused_mlp_plan
                # Unknown arch: stay silent and let create_model below
                # raise its friendly unknown-arch ValueError.
                if cfg.arch in CONVNEXT_DEFS:
                    cd = jnp.bfloat16 if cfg.bf16 else jnp.float32
                    dims = CONVNEXT_DEFS[cfg.arch][1]
                    plan = fused_mlp_plan(cfg.fused_mlp, dims, dtype=cd)
                    # "on"-mode plan = pure VMEM fit: attributes each
                    # unfused entry to VMEM vs the non-TPU backend.
                    fit = fused_mlp_plan("on", dims, dtype=cd)

                    def why(d):
                        return "VMEM" if fit[d] is None else "backend"

                    print("fused-mlp " + cfg.fused_mlp + ": "
                          + ", ".join(
                              f"C={d} " + (f"fused (rows={br})" if br
                                           else f"unfused ({why(d)})")
                              for d, br in plan.items()), flush=True)
        else:
            kw = {"stem": cfg.stem}
        model = create_model(cfg.arch, cfg.num_classes, cfg.bf16,
                             remat=cfg.remat, **kw)
        init_model = model
    if cfg.zero1 and cfg.optimizer != "sgd":
        raise ValueError("--zero1 implements the sharded SGD update; use "
                         "--fsdp for other optimizers")
    optimizer = make_optimizer(cfg.momentum, cfg.weight_decay,
                               cfg.optimizer)
    # Same seed on every process ⇒ identical init, the DDP broadcast
    # equivalence (imagenet.py:215,316). One jitted program with the key
    # as its argument; blocked on once, since the first step needs the
    # state anyway, so `init_s` is the init's whole cost.
    t_init = time.perf_counter()
    state = jax.block_until_ready(create_train_state(
        init_model, jax.random.key(cfg.seed), cfg.image_size, optimizer))
    init_s = time.perf_counter() - t_init
    if cfg.init_from_torch:
        state = _load_torch_weights(cfg, state)
        if is_master:
            print(f"initialized params from torch checkpoint "
                  f"{cfg.init_from_torch}", flush=True)
    if cfg.ema_decay > 0.0:
        # Fresh buffers (not aliases) — the train step donates the state,
        # and a leaf may not be donated through two tree slots at once.
        # BN stats are averaged too (timm ModelEmaV2 buffer semantics;
        # see TrainState docstring for the failure mode otherwise).
        state = state.replace(
            ema_params=jax.tree.map(jnp.array, state.params),
            ema_batch_stats=jax.tree.map(jnp.array, state.batch_stats))
    if cfg.zero1:
        from imagent_tpu.parallel import zero as zero_lib
        state = state.replace(
            opt_state=zero_lib.init_opt_state(state.params, n_data))
    state_specs = None
    if cfg.fsdp and use_tp:
        # Hybrid 2-D sharding: TP dims on `model`, FSDP on `data`, both
        # as pure annotations on the PLAIN model — GSPMD derives the
        # collectives (parallel/fsdp.py::fsdp_tp_param_specs).
        from imagent_tpu.parallel.fsdp import fsdp_tp_state_specs
        state_specs = fsdp_tp_state_specs(state, n_data)
    elif cfg.fsdp:
        from imagent_tpu.parallel.fsdp import fsdp_state_specs
        state_specs = fsdp_state_specs(state, n_data)
    elif cfg.zero1:
        from imagent_tpu.parallel.zero import zero1_state_specs
        state_specs = zero1_state_specs(state)
    elif use_pp and not cfg.arch.startswith("vit"):
        from imagent_tpu.parallel.resnet_pipeline import (
            resnet_pp_param_specs,
        )
        state_specs = state_partition_specs(
            state, resnet_pp_param_specs(state.params))
    elif use_pp:
        # pp (optionally composed with tp OR ep on the model axis).
        from imagent_tpu.parallel.pipeline import vit_pp_param_specs
        state_specs = state_partition_specs(
            state, vit_pp_param_specs(
                state.params,
                tp_axis=cluster.MODEL_AXIS if use_tp else None,
                expert_axis=cluster.MODEL_AXIS if use_ep else None))
    elif use_ep:
        from imagent_tpu.parallel.expert_parallel import vit_moe_param_specs
        state_specs = state_partition_specs(
            state, vit_moe_param_specs(state.params))
    elif use_tp:
        from imagent_tpu.parallel.tensor_parallel import vit_tp_param_specs
        state_specs = state_partition_specs(
            state, vit_tp_param_specs(state.params))
    state = place_state(state, mesh, state_specs)
    from imagent_tpu.ops import make_mix_fn
    from imagent_tpu.ops.jitter import make_jitter_fn
    mix_fn = make_mix_fn(cfg.mixup, cfg.cutmix)
    jitter_fn = make_jitter_fn(*cfg.color_jitter)
    if cfg.fsdp:
        from imagent_tpu.train import (
            make_eval_step_auto, make_train_step_auto,
        )
        train_step = make_train_step_auto(
            model, optimizer, mesh, state_specs,
            label_smoothing=cfg.label_smoothing,
            aux_loss_weight=cfg.moe_aux_weight,
            grad_accum=accum,
            mix_fn=mix_fn, mix_seed=cfg.seed, ema_decay=cfg.ema_decay,
            jitter_fn=jitter_fn, mean=cfg.mean, std=cfg.std,
            health_stats=cfg.health_stats)
        eval_step = make_eval_step_auto(model, mesh, state_specs,
                                        mean=cfg.mean, std=cfg.std)
    else:
        train_step = make_train_step(
            model, optimizer, mesh, seq_parallel=use_sp,
            label_smoothing=cfg.label_smoothing,
            state_specs=state_specs, grad_accum=accum,
            pipe_axis=cluster.PIPE_AXIS if use_pp else None,
            expert_parallel=use_ep, aux_loss_weight=cfg.moe_aux_weight,
            zero1=cfg.zero1, momentum=cfg.momentum,
            weight_decay=cfg.weight_decay,
            mix_fn=mix_fn, mix_seed=cfg.seed, ema_decay=cfg.ema_decay,
            jitter_fn=jitter_fn, mean=cfg.mean, std=cfg.std,
            health_stats=cfg.health_stats)
        eval_step = make_eval_step(model, mesh, state_specs,
                                   mean=cfg.mean, std=cfg.std)
    return train_step, eval_step, state, state_specs, init_s


def _run(cfg: Config, stop_check, senv, watchdog, pod=None,
         recorder=None) -> dict:
    # The persistent compile cache (XLA disk cache + the aot/ store
    # beneath it) lives where JAX_COMPILATION_CACHE_DIR says, else at
    # the fixed in-checkout path — one resolver for every entry point
    # (compilecache.arm). None = JAX's own switch
    # (jax_enable_compilation_cache) turned it off.
    cache_dir = compilecache_lib.arm()
    print(cluster.rank_banner(senv), flush=True)
    is_master = jax.process_index() == 0

    # Processes per model group, from the LIVE backend (run() already
    # verified the pre-init IMAGENT_LOCAL_DEVICES hint agrees).
    proc_group_size = groups_lib.process_group_size(
        cfg.model_parallel, cfg.pipeline_parallel,
        jax.local_device_count())

    # When a model replica spans processes (proc_group_size > 1), force
    # the naive C-order device grid: group math (death condemnation,
    # group-aligned rosters, salvage coverage) and the group-keyed data
    # feed below all rely on replica d being exactly the consecutive
    # processes [d*gsize, (d+1)*gsize). mesh_utils' topology-aware
    # permutation is only taken when replicas are process-local, where
    # device order never crosses a failure domain.
    mesh = cluster.make_mesh(
        cfg.model_parallel, pipeline_parallel=cfg.pipeline_parallel,
        devices=(jax.devices() if proc_group_size > 1 else None))
    n_data = mesh.shape[cluster.DATA_AXIS]
    if cfg.dp and cfg.dp != n_data:
        raise ValueError(
            f"--dp {cfg.dp} does not match the mesh: "
            f"{jax.device_count()} device(s) / (model_parallel "
            f"{cfg.model_parallel} x pipeline_parallel "
            f"{cfg.pipeline_parallel}) = data degree {n_data}. Fix the "
            "world size or the mesh flags — silent resharding is "
            "refused.")
    # Model groups: processes jointly holding one replica. The world
    # must be group-aligned (whole groups only) — under --elastic the
    # rendezvous guarantees it, but a mis-launched static pod must be
    # refused here before any collective.
    if jax.process_count() % proc_group_size:
        raise ValueError(
            f"world size {jax.process_count()} does not divide into "
            f"whole model groups of {proc_group_size} process(es) "
            "(one replica spans that many ranks); launch a multiple "
            "of the group size")
    n_groups = jax.process_count() // proc_group_size
    if cfg.grad_accum < 1:
        raise ValueError("--grad-accum must be >= 1")
    if cfg.global_batch:
        # The fixed-global-batch contract (--global-batch, required by
        # --elastic): the optimization batch is pinned and gradient
        # accumulation absorbs the world size — a resize recomputes
        # accum here, holding lr/batch (and so the loss trajectory)
        # fixed across shrink and grow.
        denom = cfg.batch_size * n_data
        if cfg.global_batch % denom:
            raise ValueError(
                f"--global-batch {cfg.global_batch} is not divisible "
                f"by batch_size x data_parallel = {cfg.batch_size} x "
                f"{n_data} = {denom} at this world size. Pick a "
                "global batch divisible at every world size the pod "
                "may resize to (or adjust --batch-size).")
        accum = cfg.global_batch // denom
        global_batch = cfg.global_batch
    else:
        accum = cfg.grad_accum
        global_batch = cfg.batch_size * n_data * accum
    if is_master:
        print(f"mesh {dict(mesh.shape)} global_batch {global_batch}"
              + (f" (grad_accum {accum})" if accum > 1 else "")
              + (" [fixed --global-batch contract]"
                 if cfg.global_batch else "")
              + (f" model_groups {n_groups}x{proc_group_size}"
                 if proc_group_size > 1 else ""),
              flush=True)

    if len(cfg.color_jitter) != 3 or min(cfg.color_jitter) < 0.0:
        raise ValueError(
            "--color-jitter takes three non-negative strengths "
            f"(brightness contrast saturation), got {cfg.color_jitter}")
    if cfg.transfer_dtype not in WIRE_DTYPES:
        raise ValueError(
            f"--transfer-dtype must be one of {'|'.join(WIRE_DTYPES)}, "
            f"got {cfg.transfer_dtype!r}")
    if cfg.prefetch_depth < 1:
        raise ValueError("--prefetch-depth must be >= 1")
    if cfg.workers < 0:
        raise ValueError(
            f"--workers must be >= 0 (0 = in-process serial decode; "
            f"got {cfg.workers}) — the contract every loader honors "
            "(data/pipeline.py)")
    if not 0.0 <= cfg.input_wait_alert <= 1.0:
        raise ValueError("--input-wait-alert is a fraction of epoch "
                         f"wall in [0, 1] (0 disables), got "
                         f"{cfg.input_wait_alert}")
    if cfg.decode_offload:
        if cfg.dataset == "synthetic":
            raise ValueError("--decode-offload applies to the "
                             "imagefolder/tar datasets (synthetic "
                             "generates in-process; nothing to "
                             "offload)")
        from imagent_tpu.data.offload import parse_endpoints
        parse_endpoints(cfg.decode_offload)  # loud on typos, pre-pod
    if cfg.profile and cfg.profile_at_step:
        raise ValueError("--profile and --profile-at-step are mutually "
                         "exclusive: both drive jax.profiler traces "
                         "(prefer the windowed --profile-at-step)")
    parse_profile_at_step(cfg.profile_at_step)  # fail before pod time
    if cfg.straggler_factor < 0:
        raise ValueError("--straggler-factor must be >= 0 "
                         "(0 disables flagging)")
    if cfg.health_warmup_steps < 1:
        raise ValueError("--health-warmup-steps must be >= 1")
    if cfg.health_grad_spike < 0 or cfg.health_loss_spike < 0:
        raise ValueError("--health-grad-spike / --health-loss-spike "
                         "must be >= 0 (0 disables that check)")
    if cfg.health_rollback and not cfg.health_stats:
        raise ValueError("--health-rollback needs the in-graph health "
                         "stats (drop --no-health-stats)")
    # Divergence early-warning (telemetry/health.py): consumes the
    # HEALTH_FIELDS tail of every lagged metric vector. Created before
    # any restore so --resume can re-seed its EWMA baselines from the
    # checkpoint meta instead of cold-starting them.
    monitor = None
    if cfg.health_stats:
        monitor = HealthMonitor(
            grad_spike_factor=cfg.health_grad_spike,
            loss_spike_factor=cfg.health_loss_spike,
            warmup_steps=cfg.health_warmup_steps,
            recorder=recorder)

    def _health_meta() -> dict:
        """The EWMA snapshot every checkpoint meta carries (see
        checkpoint._META_FIELDS): --resume re-seeds the detector."""
        return monitor.meta_snapshot() if monitor is not None else {}
    use_sp = cfg.seq_parallel != "none"
    if use_sp and (not cfg.arch.startswith("vit") or cfg.model_parallel < 2):
        raise ValueError(
            "--seq-parallel requires a ViT arch and --model-parallel >= 2")
    if cfg.attn != "full" and not cfg.arch.startswith("vit"):
        raise ValueError(f"--attn={cfg.attn} requires a ViT arch "
                         f"(got --arch={cfg.arch})")
    if cfg.attn != "full" and use_sp:
        raise ValueError("--attn and --seq-parallel are mutually exclusive: "
                         "the seq-parallel kernels replace attention")
    if cfg.fused_mlp not in ("auto", "on", "off"):
        raise ValueError("--fused-mlp must be one of auto|on|off, got "
                         f"{cfg.fused_mlp!r}")
    if cfg.fused_mlp == "on" and not cfg.arch.startswith("convnext"):
        raise ValueError("--fused-mlp on requires a ConvNeXt arch (the "
                         "fused block is the ConvNeXt inverted "
                         f"bottleneck; got --arch={cfg.arch}). auto/off "
                         "are no-ops elsewhere.")
    use_tp = cfg.tensor_parallel
    if use_tp and (not cfg.arch.startswith("vit") or cfg.model_parallel < 2):
        raise ValueError(
            "--tensor-parallel requires a ViT arch and --model-parallel >= 2")
    if use_tp and use_sp:
        raise ValueError(
            "--tensor-parallel and --seq-parallel both consume the model "
            "axis; pick one")
    use_pp = cfg.pipeline_parallel > 1
    if use_pp and cfg.arch.startswith("convnext"):
        raise ValueError("--pipeline-parallel covers the ViT (stage-"
                         "sharded) and ResNet (2-stage conv) families; "
                         "ConvNeXt runs dp/grad-accum/zero1/fsdp")
    if (use_pp and not cfg.arch.startswith("vit")
            and cfg.pipeline_parallel != 2):
        raise ValueError("ResNet pipeline parallelism is 2-stage "
                         "(--pipeline-parallel 2); deeper conv-stage "
                         "pipelines need a ViT arch")
    if cfg.export_torch and use_pp and cfg.arch.startswith("vit"):
        # Fail BEFORE pod time: the pipelined ViT's params are layer-
        # stacked (nn.scan — no encoder_layer_i keys) and
        # compat.vit_to_torch refuses them, so the export at the END of
        # the run would crash after the whole training budget is spent.
        raise ValueError(
            "--export-torch does not support the pipelined ViT "
            "(layer-stacked params have no encoder_layer_i keys for "
            "the torchvision state_dict); export from a non-pipelined "
            "run, or drop --export-torch")
    # pp x sp composes: stages shard layers over `pipe` while ring /
    # Ulysses attention shards tokens over `model` inside each stage
    # (exactness-tested in tests/test_pp_sp.py).
    use_ep = cfg.expert_parallel
    if cfg.moe_every and not cfg.arch.startswith("vit"):
        raise ValueError("--moe-every requires a ViT arch")
    if cfg.moe_every and (use_sp or use_tp):
        raise ValueError("MoE composes with data parallelism, "
                         "--expert-parallel, and (at --moe-every 1) "
                         "pipeline stages; not with sp/tp")
    if cfg.moe_every and use_pp and not (cfg.moe_every == 1 and use_ep):
        raise ValueError(
            "MoE inside pipeline stages requires --moe-every 1 (the "
            "nn.scan stage stack must be homogeneous) and "
            "--expert-parallel (experts ride the model axis)")
    if use_ep and (not cfg.moe_every or cfg.model_parallel < 2):
        raise ValueError("--expert-parallel requires --moe-every > 0 and "
                         "--model-parallel >= 2")
    if cfg.zero1 and (use_sp or use_tp or use_pp or use_ep):
        raise ValueError("--zero1 currently supports the data-parallel "
                         "path only (parallel/zero.py)")
    if cfg.fsdp and (use_sp or use_pp or use_ep or cfg.zero1):
        raise ValueError("--fsdp is its own execution path (XLA SPMD "
                         "partitioner); it combines with "
                         "--tensor-parallel (2-D FSDP x TP sharding) "
                         "but not with sp/pp/ep or --zero1")
    if cfg.stem != "v1":
        if cfg.arch.startswith(("vit", "convnext")):
            raise ValueError("--stem applies to the ResNet family only")
        if cfg.init_from_torch:
            raise ValueError("--init-from-torch requires --stem v1 (the "
                             "s2d stem has a different conv1 shape)")
        if cfg.image_size % 2:
            raise ValueError("--stem s2d needs an even --image-size "
                             "(space-to-depth rearrange)")

    # Data is sharded over the data axis and REPLICATED over the model
    # axis, so the feed is keyed by model group, not by process: every
    # process in group g loads group g's row slice (its addressable
    # shards of the global batch — shard_batch maps local rows onto
    # them). With process-local replicas (group size 1) this is the
    # classic per-process slicing, unchanged.
    train_loader, val_loader = make_loaders(
        cfg, jax.process_index() // proc_group_size, n_groups,
        global_batch, skip_train=cfg.eval_only)

    train_step, eval_step, state, state_specs, init_s = \
        _build_model_and_steps(cfg, mesh, n_data, accum, is_master)

    # One-compile startup (compilecache.py): lower+compile each step
    # executable ONCE via the AOT path, dispatch through wrappers that
    # fall back to the jitted twin only when a fault drill changes the
    # batch geometry, and load/save serialized executables in the
    # compile cache so restarts, requeues and already-seen elastic
    # topologies start warm. The compiled objects are handed to the
    # chip accountant below, killing its duplicate capture compile. A
    # step that does not compile is the run's failure, raised here —
    # not a WARN and a second attempt under jit (--no-aot-steps is the
    # explicit legacy jit-on-first-step path; eval_only one-shots skip
    # AOT).
    cc_stats = None
    compiled_train = compiled_eval = None
    if cfg.aot_steps and not cfg.eval_only:
        cc_store = (compilecache_lib.ExecutableStore(
            os.path.join(cache_dir, "aot")) if cache_dir else None)
        cc_fp = compilecache_lib.fingerprint(
            cfg, mesh_shape=dict(mesh.shape),
            global_batch=global_batch, accum=accum,
            runtime=compilecache_lib.runtime_facts())
        aot = compilecache_lib.compile_steps(
            train_step=train_step, eval_step=eval_step,
            state=state, mesh=mesh, cfg=cfg,
            global_batch=global_batch, fp=cc_fp, store=cc_store,
            rank=jax.process_index(), world=jax.process_count())
        compiled_train = aot.compiled.get("train")
        compiled_eval = aot.compiled.get("eval")
        train_step, eval_step = aot.train, aot.eval
        cc_stats = aot.stats
        if is_master:
            print(compilecache_lib.plan_line(cc_stats), flush=True)

    # Chip accountant (telemetry/chipacct.py): XLA cost/memory
    # analyses and the sharding-aware state byte attribution BEFORE
    # step 0 — then the OOM preflight refuses a modeled peak over the
    # HBM limit while it is still a config error (fatal-config, exit
    # 78) instead of a mid-epoch RESOURCE_EXHAUSTED. On the default
    # path the analyses come off the AOT executables compiled above
    # (capture_s ~0); only with --no-aot-steps does the account pay
    # its own capture compile (--no-chipacct skips it all).
    global _chipacct_active
    chip_acct = None
    _chipacct_active = None
    if cfg.chipacct:
        chip_acct = chipacct_lib.build_account(
            train_step=train_step, eval_step=eval_step, state=state,
            mesh=mesh, cfg=cfg, global_batch=global_batch,
            compiled_train=compiled_train, compiled_eval=compiled_eval)
        _chipacct_active = chip_acct
        if is_master:
            print(chipacct_lib.plan_line(chip_acct), flush=True)
        chipacct_lib.check_preflight(chip_acct)

    def _resume_point(meta: dict) -> tuple[int, int, float, float, int]:
        """(start_epoch, resume_step, best_top1, best_top5, best_epoch)
        from checkpoint meta, validating a mid-epoch checkpoint's
        loader-order fingerprint. Shared by --resume and the bad-step
        rollback path.

        Topology-change-proof under the --global-batch contract: the
        sample order is a pure function of (seed, epoch) and the
        trained prefix a pure function of (global_batch, step) — the
        per-step global row set ``order[s*G:(s+1)*G]`` does not depend
        on how many hosts partitioned it (data/stream.py; pinned by
        the re-sharding invariance tests) — so a mid-epoch frontier
        restores onto ANY world size as long as seed and global batch
        match. Without --global-batch the legacy strict check stands:
        the global batch follows the world size, so a different
        process count means a different loader order."""
        start_epoch = int(meta.get("epoch", -1)) + 1
        # Preemption checkpoints record how many optimizer steps of
        # the interrupted epoch are already applied; resume skips
        # exactly those batches (deterministic loader order).
        resume_step = int(meta.get("resume_step", 0))
        if resume_step > 0:
            recorded = {"global_batch": int(meta.get("global_batch", 0)),
                        "process_count": int(
                            meta.get("process_count", 0)),
                        "seed": int(meta.get("seed", -1))}
            current = {"global_batch": global_batch,
                       "process_count": jax.process_count(),
                       "seed": cfg.seed}
            if recorded["global_batch"] == 0:
                if is_master:
                    print("WARNING: mid-epoch checkpoint predates "
                          "topology recording; cannot verify the "
                          "resumed loader order matches", flush=True)
            elif cfg.global_batch:
                # Fixed-G contract: the stream frontier is world-size
                # independent; only (seed, global_batch) pin the order.
                fixed = {k: recorded[k] for k in ("global_batch",
                                                  "seed")}
                want = {k: current[k] for k in ("global_batch", "seed")}
                if fixed != want:
                    raise ValueError(
                        f"mid-epoch resume contract mismatch: "
                        f"checkpoint was written under {fixed} but "
                        f"this run is {want} — under --global-batch "
                        "these must match exactly (the trained "
                        "prefix is keyed on them); the process count "
                        "alone may differ (elastic resize).")
                if (is_master and recorded["process_count"]
                        and recorded["process_count"]
                        != current["process_count"]):
                    print("ELASTIC: mid-epoch frontier written by a "
                          f"{recorded['process_count']}-host pod "
                          "resumes on "
                          f"{current['process_count']} host(s) — "
                          "sample streams re-open at the exact "
                          "(epoch, step) with shards rebalanced; no "
                          "sample replayed or skipped", flush=True)
            elif recorded != current:
                raise ValueError(
                    f"mid-epoch resume topology mismatch: checkpoint "
                    f"was written under {recorded} but this run is "
                    f"{current} — resuming would skip the wrong "
                    f"batches (some gradients twice, others never). "
                    f"Restart the epoch (delete the 'last' "
                    f"checkpoint's resume_step), match the original "
                    "topology, or adopt the fixed --global-batch "
                    "contract (and --elastic) to make resumes "
                    "topology-change-proof.")
            if (train_loader is not None
                    and resume_step >= train_loader.steps_per_epoch):
                raise ValueError(
                    f"recorded resume_step {resume_step} >= "
                    f"{train_loader.steps_per_epoch} steps/epoch — "
                    "the dataset or batch geometry changed since "
                    "the interrupted run")
        return (start_epoch, resume_step,
                float(meta.get("best_top1", 0.0)),
                float(meta.get("best_top5", 0.0)),
                int(meta.get("best_epoch", -1)))

    start_epoch, best_top1, best_top5, best_epoch = 0, 0.0, 0.0, -1
    resume_step = 0
    resized_info: dict | None = None
    restored_info: dict | None = None
    if cfg.resume or cfg.elastic:
        # Fallback-chain restore: a torn/corrupt LAST (kill mid-commit,
        # bit-rot) falls back to the previous LAST, then BEST, instead
        # of stranding the requeued run (resilience/integrity.py).
        # --elastic implies resume-if-checkpoint-exists: every
        # rendezvoused attempt must reach the same restore verdict —
        # a newly-admitted replacement host launched WITHOUT --resume
        # training from scratch while the survivors restore would be
        # a split brain (restore_resilient pod-agrees the rest).
        restored = ckpt_lib.restore_resilient(cfg.ckpt_dir, state)
        if restored is not None:
            state, meta, src = restored
            state = place_state(state, mesh, state_specs)
            # What was restored, for the status/telemetry surfaces: an
            # emergency salvage or a sharded-format generation must be
            # visibly not a clean Orbax LAST (satellite of the
            # sharded-resilience work; describe_checkpoint renders the
            # same facts jax-free from the meta sidecar).
            restored_info = {
                "candidate": src,
                "format": str(meta.get("ckpt_format", "orbax")),
                "emergency": int(meta.get("emergency", 0)),
                "shard_ranks": int(meta.get("shard_ranks", 0) or 0),
                "coverage": meta.get("shard_coverage"),
            }
            if (cfg.global_batch
                    and int(meta.get("global_batch", 0))
                    and int(meta.get("global_batch", 0))
                    != global_batch):
                raise ValueError(
                    f"--global-batch {global_batch} does not match "
                    f"the checkpoint's recorded global batch "
                    f"{int(meta['global_batch'])} — the fixed-batch "
                    "contract pins the optimization trajectory; "
                    "resuming with a different value would silently "
                    "change it")
            (start_epoch, resume_step, best_top1, best_top5,
             best_epoch) = _resume_point(meta)
            old_p = int(meta.get("process_count", 0))
            if old_p and old_p != jax.process_count():
                # Topology changed across the restore: the pod resized
                # (shrink-to-survive or grow-on-requeue). Record the
                # lr/accum adjustment for the pod_resized telemetry
                # event emitted once the session is up.
                old_d = int(meta.get("device_count", 0))
                # Pre-resize DATA degree: on a model-axis mesh it is
                # device_count / replica size, not the device count —
                # newer checkpoints record it; for older DP-era metas
                # the device count IS the data degree.
                old_dp = int(meta.get("data_parallel", 0)) or old_d
                accum_prev = (int(meta["global_batch"])
                              // (cfg.batch_size * old_dp)
                              if old_dp and cfg.global_batch
                              and int(meta.get("global_batch", 0))
                              and int(meta["global_batch"])
                              % (cfg.batch_size * old_dp) == 0
                              else None)
                resized_info = {
                    "from_processes": old_p,
                    "to_processes": jax.process_count(),
                    "from_devices": old_d or None,
                    "to_devices": jax.device_count(),
                    "global_batch": global_batch,
                    "grad_accum": accum,
                    "grad_accum_prev": accum_prev,
                    "lr": lr_for_epoch(cfg, start_epoch),
                    "epoch": start_epoch, "resume_step": resume_step,
                    "emergency": int(meta.get("emergency", 0)),
                }
            if monitor is not None and monitor.seed(meta) and is_master:
                # A resume directly into a spike must be judged against
                # the pre-crash baseline, not an empty one.
                print("health detector re-seeded from checkpoint "
                      f"EWMAs (n={int(meta.get('health_ewma_n', 0))})",
                      flush=True)
            if is_master:
                print(f"resumed from epoch {start_epoch}"
                      + (f" step {resume_step}" if resume_step else "")
                      + (f" (fallback checkpoint {src})"
                         if src != ckpt_lib.LAST else "")
                      + (" [EMERGENCY salvage snapshot]"
                         if int(meta.get("emergency", 0)) else ""),
                      flush=True)
                from imagent_tpu.status import describe_restored
                print(describe_restored(restored_info), flush=True)
                if resized_info is not None:
                    adj = (f"grad_accum {resized_info['grad_accum_prev']}"
                           f" -> {resized_info['grad_accum']}"
                           if resized_info["grad_accum_prev"]
                           else f"grad_accum {resized_info['grad_accum']}")
                    print(f"POD RESIZED: {resized_info['from_processes']}"
                          f" -> {resized_info['to_processes']} host(s) "
                          f"at fixed global_batch {global_batch} — "
                          f"{adj}, lr {resized_info['lr']:g} "
                          "(unchanged: the trajectory follows the "
                          "batch, not the world size)", flush=True)

    logger = TrainLogger(cfg.log_dir, is_master)
    if cfg.check_nans:
        jax.config.update("jax_debug_nans", True)
    if cfg.profile and is_master:
        jax.profiler.start_trace(cfg.log_dir)

    run_t0 = time.time()
    # Written into every checkpoint meta: the loader-order fingerprint a
    # mid-epoch resume must match (see the resume guard above), plus
    # the data-parallel size so a resized resume can report the
    # grad-accum adjustment the fixed --global-batch contract implies.
    topo_meta = {"global_batch": global_batch,
                 "process_count": jax.process_count(), "seed": cfg.seed,
                 "device_count": jax.device_count(),
                 # Data degree at save time: a model-axis resize needs
                 # it to report the accum adjustment (devices alone
                 # over-count by the replica size).
                 "data_parallel": int(n_data)}
    train_m = {"loss": 0.0, "top1": 0.0, "top5": 0.0}
    val_m = {"loss": 0.0, "top1": 0.0, "top5": 0.0}
    preempted = False
    interrupted_at = -1  # persists past the loop (terminal status)

    if cfg.eval_only:
        # Validation pass on the current params (--resume /
        # --init-from-torch supply them); no training, no checkpoint.
        val_m, val_t = evaluate(cfg, mesh, eval_step, state,
                                val_loader, max(start_epoch - 1, 0))
        if is_master:
            print(f"eval-only: val loss {val_m['loss']:.4f} "
                  f"top1 {val_m['top1']:.3f} top5 {val_m['top5']:.3f} "
                  f"({val_m['n']} samples, {val_t:.1f}s)", flush=True)
        if cfg.profile and is_master:
            jax.profiler.stop_trace()
        _export_torch(cfg, state, is_master)
        logger.close()
        return {"best_top1": val_m["top1"], "best_top5": val_m["top5"],
                "best_epoch": start_epoch - 1,
                "total_minutes": (time.time() - run_t0) / 60.0,
                "final_train": train_m, "final_val": val_m,
                "preempted": False, "rollbacks": 0,
                "ckpt_commit_failures": 0}

    # Telemetry (imagent_tpu/telemetry): goodput phases, step-time
    # percentiles, pod aggregation + straggler flags — TB scalars and
    # the telemetry.jsonl event log. Its one collective (the per-host
    # counter allgather) runs inside epoch_end, which every epoch-exit
    # path below reaches on every process (the exits are pod-agreed
    # decisions: rollback verdicts ride replicated metric vectors, the
    # preemption stop is any-reduced).
    telem = TelemetrySession(cfg, is_master, logger)
    telem.health = monitor
    # The static chip account: epoch_end derives the per-epoch MFU /
    # TFLOP-per-chip sub-record from it plus the goodput partition it
    # already measured — zero added step-loop cost.
    telem.chipacct = chip_acct
    # Warm-start stats ride every epoch record as the `compilecache`
    # sub-record (the fallback_steps counter is live — a fault drill's
    # geometry change shows up at the next boundary).
    telem.compilecache = cc_stats
    if monitor is not None:

        def _on_anomaly(a: dict) -> None:
            # Detection rides the replicated metric vector, so every
            # host fires identically — local bookkeeping only.
            telem.health_anomaly(a)
            if is_master:
                val = a.get("value")
                base = a.get("baseline")
                print(f"HEALTH: {a['kind']} anomaly at epoch "
                      f"{a['epoch'] + 1} step {a['step'] + 1} — value "
                      + ("non-finite" if val is None else f"{val:.4g}")
                      + (f" vs EWMA baseline {base:.4g}"
                         if base else "")
                      + (" — rolling back to the last good checkpoint"
                         if cfg.health_rollback else
                         " (warn only; --health-rollback to act)"),
                      flush=True)

        monitor.on_anomaly = _on_anomaly
    # Runtime recompile sentinel (telemetry/recompile.py): classifies
    # every XLA backend compile as warmup / expected / midrun. A
    # midrun compile — the silent TPU throughput killer the goodput
    # heuristic can only misattribute to step_drain — becomes a
    # compile_event record, a trace instant, a loud master WARN naming
    # the jitted function, and the `recompiles` counter the SLO
    # objective `recompiles_max` judges. The hooks fire only when a
    # compile actually happens: zero cost on the steady step path.
    sentinel = None
    if cfg.telemetry:

        def _on_midrun_compile(ev: dict) -> None:
            telem.count("recompiles")
            telem.compile_event(ev)
            trace_lib.instant("compile_event", cat="compile",
                              fun=ev.get("fun", "?"),
                              secs=ev.get("secs", 0.0))
            if is_master:
                print(f"WARNING: RECOMPILE mid-run: `{ev.get('fun')}` "
                      f"recompiled ({ev.get('secs', 0.0):.2f}s) after "
                      "warmup — a changing input shape/dtype or a "
                      "traced-value branch is silently stalling the "
                      "step loop (docs/OPERATIONS.md 'Monitoring, "
                      "SLOs, and regression gating'; jaxlint "
                      "recompile-hazard finds the static cases)",
                      flush=True)

        sentinel = recompile_lib.RecompileSentinel(
            on_midrun=_on_midrun_compile)
        recompile_lib.activate(sentinel)
    # Live SLO evaluation (telemetry/slo.py, --slo): the spec is
    # judged against each epoch's telemetry record on the master —
    # the record is already pod-aggregated, so the verdict needs no
    # collective. Breaches become slo_breach events, TB markers,
    # status.json fields and loud prints.
    slo_spec = slo_lib.parse_spec_arg(cfg.slo)
    slo_session = (slo_lib.SloSession(slo_spec)
                   if slo_spec is not None and is_master else None)
    if recorder is not None:
        recorder.note(arch=cfg.arch, global_batch=global_batch,
                      process_count=jax.process_count(),
                      steps_per_epoch=train_loader.steps_per_epoch,
                      seed=cfg.seed)
    # Live status surface (status.py): process 0 atomically rewrites
    # runs/<run>/status.json at every --log-every boundary and epoch
    # exit; `python -m imagent_tpu.status <log_dir>` renders it.
    status = StatusWriter(cfg.log_dir) if is_master else None
    # Launched vs active world: the scheduler slots this pod was
    # started with vs the roster that actually formed — the status
    # surface renders the difference so a silently-shrunk pod is
    # visible on one screen.
    launched_world = (getattr(senv, "launched_world", 0)
                      if senv is not None else 0) or jax.process_count()
    # Mesh layout, surfaced everywhere world_size is (status.json, the
    # status CLI, telemetry summarize, OpenMetrics): a model-axis pod
    # degrades in whole groups, so flat rank counts alone under-read a
    # TP/pipeline pod's health.
    mesh_info = {
        "dp": int(n_data),
        "tp": int(mesh.shape[cluster.MODEL_AXIS]),
        "pp": int(mesh.shape[cluster.PIPE_AXIS]),
        "layout": (f"dp{int(n_data)}"
                   f"xtp{int(mesh.shape[cluster.MODEL_AXIS])}"
                   f"xpp{int(mesh.shape[cluster.PIPE_AXIS])}"),
        "group_size": int(proc_group_size),
        "groups": int(n_groups),
        "launched_groups": max(int(launched_world) // int(proc_group_size),
                               int(n_groups)),
    }
    # OpenMetrics exporter (--metrics-port, telemetry/export.py):
    # process 0 serves the epoch-boundary telemetry state as a pull
    # endpoint for fleet scrapers. Module-global handle so run()'s
    # finally closes the port on every exit ramp.
    exporter = None
    exporter_info = {
        "arch": cfg.arch,
        "chip": jax.devices()[0].device_kind,
        "transfer_dtype": cfg.transfer_dtype,
        "launched": launched_world,
        "mesh": mesh_info["layout"],
        "groups": mesh_info["groups"],
        "launched_groups": mesh_info["launched_groups"],
    }
    if cfg.metrics_port and is_master:
        exporter = export_lib.MetricsExporter(cfg.metrics_port).start()
        export_lib.activate(exporter)
        # Identity + liveness are scrapable before the first epoch
        # boundary lands real series.
        exporter.update(export_lib.build_state(run_info=exporter_info))
        print(f"metrics: serving OpenMetrics on "
              f":{exporter.port}/metrics (refreshed at epoch "
              "boundaries)", flush=True)
    telem.run_start({
        "arch": cfg.arch, "global_batch": global_batch,
        "process_count": jax.process_count(),
        "launched_process_count": launched_world,
        "mesh": mesh_info,
        "elastic_attempt": (getattr(senv, "elastic_attempt", 0)
                            if senv is not None else 0),
        "device_count": jax.device_count(),
        "steps_per_epoch": train_loader.steps_per_epoch,
        "start_epoch": start_epoch, "resume_step": resume_step,
        "seed": cfg.seed,
        "ckpt_format": cfg.ckpt_format,
        # Environment fingerprint (telemetry/regress.py ENV_KEYS): the
        # regression gate refuses cross-hardware/config comparisons on
        # these instead of producing a nonsense verdict. Additions,
        # not a schema bump (consumers ignore unknown keys).
        "device_kind": jax.devices()[0].device_kind,
        "platform": jax.devices()[0].platform,
        "jax_version": jax.__version__,
        "image_size": cfg.image_size,
        "batch_size": cfg.batch_size,
        "transfer_dtype": cfg.transfer_dtype,
        # Format/coverage of the restored generation (None on a fresh
        # start): `telemetry summarize` and post-mortems must see
        # whether this attempt resumed a clean LAST, a fallback rung,
        # or an emergency salvage — and in which on-disk format.
        "restored": restored_info,
        # This attempt's warm-start verdict (compilecache.py): cache
        # key, hit/miss counters and the startup load/compile seconds.
        # Per-ATTEMPT by construction — every run_start carries its
        # own — so the regress gate's startup_compile_s series reads
        # ALL run_start records, not the folded last one.
        "compile_cache": cc_stats,
        # Seconds the initial TrainState took to build (one jitted
        # program, served by the compile cache after a checkout's
        # first run), read by the benchmark's startup.init_s.
        "init_s": init_s,
    })
    if resized_info is not None:
        # The resize verdict of THIS attempt (restore found a
        # different world size than the checkpoint's): the lr/accum
        # adjustment is on the record before the first step runs.
        telem.pod_resized(dict(resized_info, phase="resize"))

    anomaly_hwm = [0]  # monitor.anomalies already attributed to epochs
    last_input_alert = [None]  # newest epoch's input-wait alert (if any)
    last_clock_skew = [None]   # newest epoch's max pod wall-clock skew
    last_slo = [None]          # newest SLO session status (if armed)
    last_acct = [None]         # newest epoch's chipacct sub-record

    def _end_telemetry_epoch(ep: int, tm: dict,
                             interrupted: bool = False,
                             step: int | None = None) -> None:
        if monitor is not None:
            # Per-epoch anomaly count from the monitor's EVERY-step
            # totals (the emission schedule is rate-limited; counting
            # there would report 0 for epochs inside a standing
            # streak).
            delta = monitor.anomalies - anomaly_hwm[0]
            if delta:
                telem.count("health_anomalies", delta)
            anomaly_hwm[0] = monitor.anomalies
        if pod is not None:
            # telemetry.epoch_end runs the per-host counter allgather —
            # the same class of dead-peer hang as the checkpoint
            # collectives. Bare gate (no salvage): some call sites sit
            # mid-rollback, where the live state must not be vouched
            # for; the last committed generation stands.
            pod.raise_if_degraded()
        if watchdog is not None and watchdog.fired:
            telem.count("watchdog_fired")
        if pod is not None:
            # High-water peer-heartbeat age this epoch: a value creeping
            # toward --peer-deadline-secs is a host about to be declared
            # dead (or a deadline tuned too tight for the fs).
            telem.gauge("hb_peer_staleness_s",
                        round(pod.max_peer_staleness(), 3))
        # Continuous pod/world_size series (elastic visibility): one
        # float per epoch, a step down marks a shrink-to-survive. The
        # groups series is the model-axis twin — a TP pod that lost a
        # replica steps down here even when stragglers keep the rank
        # count noisy in between.
        telem.gauge("world_size", float(jax.process_count()))
        telem.gauge("groups", float(n_groups))
        record = telem.epoch_end(ep, tm, interrupted=interrupted)
        if (record or {}).get("chipacct") is not None:
            last_acct[0] = record["chipacct"]
        last_input_alert[0] = (record or {}).get("input_wait_alert")
        last_clock_skew[0] = ((record or {}).get("clock")
                              or {}).get("max_skew_s")
        if slo_session is not None and record is not None:
            # The SLO verdict for this epoch: pure local arithmetic on
            # the already-pod-aggregated record (no collective).
            # Breaches are events + TB markers + a loud line; the
            # session status rides status.json and the exporter.
            for b in slo_session.evaluate(record):
                telem.slo_breach(b)
                print(slo_lib.describe_breach(b)
                      + " — docs/OPERATIONS.md 'Monitoring, SLOs, "
                        "and regression gating'", flush=True)
            last_slo[0] = slo_session.status()
        if status is not None:
            # Epoch-boundary status write: covers --log-every 0 runs
            # and adds the goodput the in-epoch writes can't know yet.
            status.write({
                "phase": "boundary", "epoch": ep, "epochs": cfg.epochs,
                # An interrupted epoch's true frontier, not a full
                # epoch that never ran (progress/ETA tooling reads
                # this; the mid-epoch checkpoint's resume_step agrees).
                "step": (step if step is not None
                         else train_loader.steps_per_epoch),
                "steps_per_epoch": train_loader.steps_per_epoch,
                "loss": tm.get("loss"), "lr": lr_for_epoch(cfg, ep),
                "best_top1": best_top1,
                "bad_steps": tm.get("bad_steps", 0),
                "goodput": (record or {}).get("goodput"),
                # The input-bound alert (when tripped): the status CLI
                # renders it so a starving pod is visible at a glance.
                "input_wait_alert": last_input_alert[0],
                # Max pod wall-clock skew from the epoch's clock
                # allgather: skewed clocks break cross-rank log
                # reading, and this is the one place that measures it.
                "clock_skew_s": last_clock_skew[0],
                "degraded": bool(pod is not None and pod.degraded),
                "interrupted": bool(interrupted),
                # Elastic visibility: current vs launched world — a
                # silently-shrunk pod must be one glance away.
                "world_size": jax.process_count(),
                "launched_world_size": launched_world,
                "mesh": mesh_info,
                # What this attempt restored (format/coverage/salvage):
                # an incomplete-pod salvage resume stays one glance
                # away for the whole run, not just its first print.
                "restored": restored_info,
                "health": (monitor.snapshot()
                           if monitor is not None else None),
                # The live SLO verdict (breached objectives + run
                # totals): the status CLI renders a loud line from it.
                "slo": last_slo[0],
                # The chip accountant's epoch verdict (MFU, modeled
                # peak, per-component state bytes): the status CLI
                # renders the memory table from it.
                "chipacct": last_acct[0],
                # This attempt's warm-start verdict (hits/misses/
                # startup seconds + live fallback counter).
                "compile_cache": cc_stats,
            })
        if exporter is not None and record is not None:
            # Refresh the serving snapshot: the exporter's thread
            # renders scrapes from exactly this epoch-boundary state
            # (the same numbers status.json just recorded).
            exporter.update(export_lib.build_state(
                run_info=exporter_info, record=record,
                health=(monitor.snapshot()
                        if monitor is not None else None),
                slo=last_slo[0],
                compile_counts=(dict(sentinel.counts)
                                if sentinel is not None else None),
                peer_staleness=(pod.peer_staleness()
                                if pod is not None else None),
                totals={"rollbacks": rollbacks,
                        "ckpt_commit_failures": ckpt_commit_failures}))
        if sentinel is not None:
            # First boundary reached: compiles from here on are either
            # bracketed first-time geometries or genuine mid-run
            # recompiles. Idempotent.
            sentinel.end_warmup()

    ckpt_commit_failures = 0  # pod-agreed failed async commits
    ckpt_fail_streak = 0      # consecutive — the storage-outage verdict

    def _absorb_commit(landed: dict | None) -> None:
        """Attribute a landed async-commit verdict: its duration moves
        to the overlapped ``ckpt_commit_async`` phase (work hidden
        behind compute, NOT part of the wall partition); a pod-agreed
        failure is counted — the previous generation silently remains
        the last good checkpoint and the next epoch's save retries.
        A STREAK of failures (each already past the committer's own
        bounded backoff) means the storage outage is not transient:
        exit retryable while the last good generation is still worth
        resuming from, instead of training on un-checkpointable."""
        nonlocal ckpt_commit_failures, ckpt_fail_streak
        if landed is None:
            return
        if landed["ok"]:
            ckpt_fail_streak = 0
            telem.overlap("ckpt_commit_async", landed["secs"])
            if landed.get("bytes"):
                # Per-commit shard geometry (process 0 carries it; the
                # broadcast verdict on other ranks doesn't): the
                # telemetry series that shows a sharded commit's
                # per-rank contribution shrinking/growing across
                # elastic resizes.
                telem.gauge("ckpt_commit_bytes",
                            float(landed["bytes"]))
                telem.gauge("ckpt_commit_shards",
                            float(landed.get("shards", 1)))
            if is_master:
                shard_note = ""
                if landed.get("shards", 0) > 1:
                    shard_note = (f", {landed['shards']} shards / "
                                  f"{landed.get('bytes', 0)} bytes")
                print(f"async checkpoint '{landed['name']}' committed "
                      f"in {landed['secs']:.2f}s (overlapped with "
                      f"training{shard_note})", flush=True)
        else:
            ckpt_commit_failures += 1
            ckpt_fail_streak += 1
            telem.count("ckpt_commit_failed")
            if ckpt_fail_streak >= _MAX_CKPT_FAIL_STREAK:
                raise exitcodes.StorageOutageError(
                    f"{ckpt_fail_streak} consecutive async checkpoint "
                    f"commits failed (last: {landed['error']}), each "
                    "past its own backoff retries — checkpoint storage "
                    "looks dead. The previous good generation is "
                    "intact; exiting retryable for the launcher to "
                    "requeue onto --resume.")

    if watchdog is not None and cfg.async_ckpt and cfg.save_model:
        # A wedged committer thread (dead storage mount) gets the same
        # stack-dump + checkpoint-and-exit + hard-exit escalation as a
        # hung step (resilience/watchdog.py::add_monitor).
        watchdog.add_monitor(ckpt_lib.commit_monitor(
            max(4.0 * cfg.watchdog_secs, 60.0)))

    rollbacks = 0        # total, reported in the summary
    rollback_streak = 0  # consecutive incidents — the give-up budget
    epoch = start_epoch
    warm = None  # next epoch's pre-started input pipeline
    first_eval_done = False  # the first eval epoch's compile is
    #                          EXPECTED by the recompile sentinel

    def _pod_gate(phase: str) -> None:
        """Degraded-pod check before each pod-agreed phase: a dead peer
        must divert us to the out-of-band exit ramp BEFORE this host
        files into the phase's collectives. The salvage meta names the
        last pod-consistent point: mid-epoch when the train loop was
        interrupted, else the epoch boundary just reached. An epoch
        that tripped the non-finite rollback verdict vouches for
        NOTHING — its state is partial and its meta would claim a
        complete epoch; no salvage, the last committed generation
        stands (it is what the rollback would have restored anyway)."""
        if pod is None:
            return
        pod.note(phase=phase)
        if want_rollback:
            pod.raise_if_degraded()
        elif interrupted_at >= 0:
            pod.raise_if_degraded(state=state, epoch=epoch - 1,
                                  resume_step=interrupted_at)
        else:
            pod.raise_if_degraded(state=state, epoch=epoch,
                                  resume_step=0)

    # Grow-on-requeue: the master polls the elastic dir (throttled —
    # one listdir every few seconds, jax-free) for join files NEWER
    # than the committed roster: a standing request from an excluded /
    # replacement host waiting in its own rendezvous. The verdict
    # rides the EXISTING pod-agreed stop machinery (_stop_agreed's
    # any-reduce), so every member stops at the same step, lands the
    # mid-epoch checkpoint, and re-forms the larger pod together.
    grow_state = {"fired": False, "t": 0.0, "joiners": []}
    grow_stop = False  # the agreed stop was a grow, not a preemption
    if cfg.elastic and senv is not None and getattr(senv, "members", ()):
        grow_edir = elastic_lib.elastic_dir(cfg.log_dir)
        grow_roster = {"attempt": senv.elastic_attempt,
                       "members": list(senv.members)}

        def _grow_pending() -> bool:
            if not is_master:
                return False
            now = time.monotonic()
            if now - grow_state["t"] < 2.0:
                return grow_state["fired"]
            grow_state["t"] = now
            pend = elastic_lib.pending_joiners(grow_edir, grow_roster)
            if pend and not grow_state["fired"]:
                grow_state["fired"] = True
                grow_state["joiners"] = pend
                print(f"ELASTIC: host(s) {pend} filed a join request "
                      "— stopping at the next pod-agreed step to "
                      "re-form the pod (grow)", flush=True)
            return grow_state["fired"]

        base_stop_check = stop_check
        grow_state["base"] = base_stop_check
        stop_check = (lambda: (base_stop_check() if base_stop_check
                               is not None else False)
                      or _grow_pending())

    def _grow_stop_agreed() -> bool:
        """Pod-agreed CLASSIFICATION of an agreed stop: only the
        master polls the elastic dir, so its verdict (grow vs
        preemption) is broadcast — otherwise every other member would
        classify the same stop as a preemption, tombstone 'preempted',
        exit 75, and take the normal interpreter exit into a shutdown
        barrier the exec-restarted master can never complete. A REAL
        preemption (or the watchdog) that latched alongside the grow
        request outranks it: exec-restarting into a rendezvous while
        the scheduler's grace clock runs would turn a routine
        preemption into a SIGKILL mid-rendezvous."""
        if "base" not in grow_state:
            # Grow polling not armed (non-elastic, or no roster): the
            # stop is a plain preemption on every rank — no collective.
            # The key is set identically pod-wide (cfg + roster), so
            # entry into the broadcast below stays symmetric.
            return False
        base = grow_state.get("base")
        local = 1 if (grow_state["fired"]
                      and not (base is not None and base())) else 0
        if jax.process_count() == 1:
            return bool(local)
        from jax.experimental import multihost_utils
        out = multihost_utils.broadcast_one_to_all(
            np.asarray([local], np.int32))
        return bool(out[0])

    try:
        while epoch < cfg.epochs:
            lr = lr_for_epoch(cfg, epoch)
            telem.epoch_begin()
            interrupted_at = -1   # for _pod_gate if the epoch raises
            want_rollback = False
            (state, train_m, train_t, interrupted_at, want_rollback,
             warm) = train_one_epoch(
                cfg, mesh, train_step, state, train_loader, epoch, lr,
                is_master, stop_check, resume_step, watchdog, telem,
                prefetch=warm, pod=pod, health=monitor, status=status)
            resume_step = 0  # only the first resumed epoch skips batches
            # Land the previous epoch's async checkpoint commit if it
            # has completed (non-blocking; the verdict is pod-agreed
            # HERE, at commit completion — checkpoint.poll_async).
            _pod_gate("boundary")
            _absorb_commit(ckpt_lib.poll_async())
            if not want_rollback:
                # An epoch got through without tripping the guard: any
                # earlier incident was genuinely transient. The give-up
                # budget is per incident-STREAK, not per run — three
                # isolated recovered transients across 100 epochs must
                # not kill a healthy job on the fourth.
                rollback_streak = 0
            if want_rollback:
                # --max-bad-steps consecutive non-finite steps: the
                # updates were all skipped in-graph, so the live state
                # is not poisoned — but something is persistently wrong
                # (data shard, numerics). Roll back to the last
                # restorable checkpoint and replay rather than abort: a
                # transient (one corrupt shard served once, a flaky
                # host) costs one checkpoint interval instead of the
                # run.
                rollbacks += 1
                rollback_streak += 1
                telem.count("rollbacks")
                if rollback_streak > _MAX_ROLLBACKS:
                    raise exitcodes.RollbackGiveUpError(
                        f"non-finite or diverging steps persisted "
                        f"through {_MAX_ROLLBACKS} consecutive "
                        "rollbacks — giving up (check data / lr / bf16 "
                        "ranges; the fault reproduces on every replay)")
                t_rec = time.perf_counter()
                _pod_gate("recovery")
                restored = ckpt_lib.restore_resilient(cfg.ckpt_dir,
                                                      state)
                if restored is None:
                    # Nothing to roll back to. For a GUARD trip the
                    # in-graph skip means the live state is NOT
                    # poisoned, so killing an intact run because
                    # --save-model is off would be strictly worse than
                    # pressing on. A HEALTH trip is different — the
                    # diverging (finite) updates WERE applied — but
                    # with no checkpoint there is nothing to restore
                    # either way: say so honestly and continue, still
                    # bounded by the rollback budget above (a state
                    # that stays diverged keeps tripping and gives up;
                    # a survivable spike recovers).
                    if is_master and train_m.get("health_rollback"):
                        print("WARNING: health anomaly tripped "
                              f"rollback in epoch {epoch + 1} but "
                              "there is no checkpoint to roll back to "
                              "(--save-model off?). The diverging "
                              "updates WERE applied (unlike guard-"
                              "skipped steps) — continuing on the "
                              "possibly-diverged state; "
                              f"({rollback_streak}/{_MAX_ROLLBACKS} "
                              "consecutive strikes before giving up)",
                              flush=True)
                    elif is_master:
                        print(f"WARNING: {cfg.max_bad_steps} "
                              "consecutive non-finite steps in epoch "
                              f"{epoch + 1} and no checkpoint to roll "
                              "back to (--save-model off?). State is "
                              "unpoisoned (updates were skipped "
                              "in-graph); abandoning the rest of this "
                              f"epoch ({rollback_streak}/"
                              f"{_MAX_ROLLBACKS} consecutive strikes "
                              "before giving up)", flush=True)
                    telem.phase("recovery", time.perf_counter() - t_rec)
                    _end_telemetry_epoch(epoch, train_m)
                    epoch += 1
                    continue
                state, meta, src = restored
                state = place_state(state, mesh, state_specs)
                telem.phase("recovery", time.perf_counter() - t_rec)
                # The record names the epoch that FAILED (the one whose
                # wall time this was), not the replay target below.
                _end_telemetry_epoch(epoch, train_m)
                (epoch, resume_step, best_top1, best_top5,
                 best_epoch) = _resume_point(meta)
                if monitor is not None:
                    # Replay against the restored generation's health
                    # baseline — the anomalous observations were never
                    # absorbed, and the checkpoint's EWMAs describe
                    # exactly the weights now live again.
                    monitor.seed(meta)
                if is_master:
                    print(f"ROLLBACK {rollback_streak}/{_MAX_ROLLBACKS}"
                          f": restored checkpoint '{src}', replaying "
                          f"from epoch {epoch + 1}"
                          + (f" step {resume_step}" if resume_step
                             else ""),
                          flush=True)
                continue
            if interrupted_at >= 0:
                # Preemption: persist the mid-epoch state, recording
                # how many of this epoch's steps it contains —
                # --resume skips exactly those batches, so no gradient
                # is applied twice.
                t_ck = time.perf_counter()
                _pod_gate("checkpoint")
                _storage_guard(
                    ckpt_lib.save, cfg.ckpt_dir, ckpt_lib.LAST, state, {
                        "epoch": epoch - 1,
                        "resume_step": interrupted_at,
                        "best_top1": best_top1, "best_top5": best_top5,
                        "best_epoch": best_epoch, **topo_meta,
                        **_health_meta()},
                    keep_last_k=cfg.keep_last_k, fmt=cfg.ckpt_format)
                telem.phase("checkpoint", time.perf_counter() - t_ck)
                # Classify the agreed stop POD-WIDE (the master's
                # verdict, broadcast — it alone polls the join files):
                # a real preemption or the watchdog outranks a grow
                # stop. Every rank then takes the same ramp — skip the
                # tombstone, report resize_grow, exec-restart — or
                # none does.
                grow_stop = _grow_stop_agreed()
                if grow_stop:
                    telem.count("pod_resize_grow")
                    telem.pod_resized({
                        "phase": "grow-stop", "epoch": epoch,
                        "resume_step": interrupted_at,
                        "from_processes": jax.process_count(),
                        # The world the re-formed pod is headed for
                        # (also the TB pod/resized marker value).
                        "to_processes": (jax.process_count()
                                         + len(grow_state["joiners"])),
                        "joiners": grow_state["joiners"],
                        "global_batch": global_batch,
                    })
                else:
                    telem.count("preempted")
                _end_telemetry_epoch(epoch, train_m, interrupted=True,
                                     step=interrupted_at)
                if is_master and grow_stop:
                    print("ELASTIC grow stop: checkpointed epoch "
                          f"{epoch + 1} at step {interrupted_at}; "
                          "re-forming the pod with the waiting "
                          f"host(s) {grow_state['joiners']} (exit "
                          f"{exitcodes.POD_RESIZE}, then rendezvous "
                          "onto --resume)", flush=True)
                elif is_master:
                    print("preemption signal: checkpointed epoch "
                          f"{epoch + 1} at step {interrupted_at}; "
                          "exiting cleanly (--resume continues from "
                          "there)", flush=True)
                preempted = True
                break
            did_eval = ((epoch + 1) % cfg.eval_every == 0
                        or epoch == cfg.epochs - 1)
            if did_eval:
                _pod_gate("eval")
                # The FIRST eval epoch compiles the eval geometry —
                # with --eval-every > 1 that lands after warmup ended,
                # so the sentinel is told to expect it (a later,
                # unexpected eval recompile still classifies midrun).
                with (sentinel.expect("first-eval")
                      if sentinel is not None and not first_eval_done
                      else contextlib.nullcontext()):
                    val_m, val_t = evaluate(cfg, mesh, eval_step,
                                            state, val_loader, epoch,
                                            telem)
                first_eval_done = True
                telem.phase("eval", val_t)
            else:
                val_t = 0.0
            t_ck = time.perf_counter()
            _pod_gate("checkpoint")
            if did_eval and val_m["top1"] > best_top1:
                best_top1, best_top5, best_epoch = (
                    val_m["top1"], val_m["top5"], epoch)
                if cfg.save_model:
                    _storage_guard(
                        ckpt_lib.save, cfg.ckpt_dir, ckpt_lib.BEST,
                        state, {
                            "epoch": epoch, "best_top1": best_top1,
                            "best_top5": best_top5,
                            "best_epoch": best_epoch, **topo_meta,
                            **_health_meta()}, fmt=cfg.ckpt_format)
            if cfg.save_model:
                last_meta = {"epoch": epoch, "best_top1": best_top1,
                             "best_top5": best_top5,
                             "best_epoch": best_epoch, **topo_meta,
                             **_health_meta()}
                if cfg.async_ckpt:
                    # Snapshot-then-commit: the only blocking slice is
                    # the device→host copy; serialization + rotation +
                    # manifest hashing run on the committer thread
                    # while the next epoch trains
                    # (checkpoint.save_async). If the PREVIOUS commit
                    # was somehow still in flight, landing it blocks
                    # here and its verdict is returned.
                    _absorb_commit(_storage_guard(
                        ckpt_lib.save_async,
                        cfg.ckpt_dir, ckpt_lib.LAST, state, last_meta,
                        keep_last_k=cfg.keep_last_k,
                        fmt=cfg.ckpt_format))
                else:
                    # --no-async-ckpt: the fully synchronous baseline
                    # (bench-smoke's reference point) — the loop stalls
                    # for the whole serialize + commit + manifest.
                    _storage_guard(
                        ckpt_lib.save, cfg.ckpt_dir, ckpt_lib.LAST,
                        state, last_meta, block=True,
                        keep_last_k=cfg.keep_last_k,
                        fmt=cfg.ckpt_format)
            # The blocking slice only: the host snapshot for the async
            # LAST (its commit overlaps the next epoch by design) plus
            # any BEST save — the wall time checkpointing actually
            # cost this epoch.
            telem.phase("checkpoint", time.perf_counter() - t_ck)
            if is_master and train_m.get("bad_steps"):
                print(f"  epoch {epoch + 1}: {train_m['bad_steps']} "
                      "non-finite step(s) skipped", flush=True)
            logger.epoch_summary(epoch, lr, train_m,
                                 val_m if did_eval else None, train_t,
                                 val_t)
            logger.scalars(epoch, lr, train_m,
                           val_m if did_eval else None)
            _end_telemetry_epoch(epoch, train_m)
            epoch += 1

        # Land any in-flight async save — the final epoch's LAST commit
        # lands HERE, so its verdict (a failure has no next-epoch
        # retry) must be absorbed, not dropped.
        _absorb_commit(ckpt_lib.wait_until_finished())
    except exitcodes.PeerDeathError as e:
        _pod_death_exit(cfg, e, pod, telem, epoch, topo_meta,
                        {"best_top1": best_top1, "best_top5": best_top5,
                         "best_epoch": best_epoch}, is_master)
        raise
    except exitcodes.FatalRunError:
        raise
    except Exception as exc:
        # A one-sided collective blow-up (gloo abort, ICI timeout,
        # XlaRuntimeError) is very often the SYMPTOM of a peer death
        # whose heartbeat has not yet crossed the deadline: hold the
        # exception for one deadline and let the out-of-band verdict
        # classify it. No salvage — a state whose producing step blew
        # up cannot be vouched for; the last committed generation
        # stands.
        if pod is not None and not pod.degraded:
            # Under --elastic the verdict may be an EXCLUSION: the
            # survivors' re-formed roster only commits after their
            # exec + rendezvous settle, so hold the exception long
            # enough to cover that window — classifying the resulting
            # gloo blow-up as an anonymous exception would cost the
            # flapper its clear elastic-excluded tombstone.
            pod.wait_verdict(cfg.peer_deadline_secs
                             + 2.0 * cfg.heartbeat_secs
                             + (3.0 * cfg.elastic_settle_secs
                                if cfg.elastic else 0.0))
        if pod is not None and pod.degraded:
            # Kind-aware classification: the same verdict semantics as
            # an in-loop detection — elastic continue raises the
            # RESIZE error (survivors re-form), an exclusion raises
            # the tombstoned stop, a plain death the retryable 87.
            err = pod.error_for_verdict(
                prefix=(f"run exception attributed to pod "
                        f"degradation ({type(exc).__name__}: {exc}) "
                        "— "))
            _pod_death_exit(cfg, err, pod, telem, epoch, topo_meta,
                            {"best_top1": best_top1,
                             "best_top5": best_top5,
                             "best_epoch": best_epoch}, is_master)
            raise err from exc
        raise
    if preempted and pod is not None and not grow_stop:
        # Clean checkpoint-and-exit still classifies itself for the
        # peers' monitors (and the requeue wrapper reads the matching
        # exit code from __main__): preemption and the watchdog's
        # clean path are both retryable. A GROW stop writes no
        # tombstone — every member departs on a done-beat and
        # immediately re-forms; a tombstone would race the re-formed
        # monitors as a fresh fatal.
        if watchdog is not None and watchdog.fired:
            pod.tombstone("watchdog-stall", exitcodes.PREEMPTED,
                          detail="stalled steps; clean "
                                 "checkpoint-and-exit")
        else:
            pod.tombstone("preempted", exitcodes.PREEMPTED,
                          detail="preemption checkpoint-and-exit")
    if cfg.profile and is_master:
        jax.profiler.stop_trace()
    if not preempted:
        # Skip under preemption: the grace window is for the mid-epoch
        # checkpoint, not a full-model serialize — the resumed run
        # exports the true final state.
        _export_torch(cfg, state, is_master, prefer_best=True)
    total_min = (time.time() - run_t0) / 60.0
    logger.final_summary(best_epoch, best_top1, best_top5, total_min)
    if status is not None:
        # Terminal status: a finished run must not render as a hung
        # one ("updated Xs ago" growing forever at the last boundary).
        status.write({
            "phase": "preempted" if preempted else "done",
            # Preempted: the interrupted epoch's true frontier (agrees
            # with the mid-epoch checkpoint's resume_step); finished:
            # the last trained epoch, complete.
            "epoch": epoch if preempted else max(epoch - 1, 0),
            "epochs": cfg.epochs,
            "step": (interrupted_at
                     if preempted and interrupted_at >= 0
                     else train_loader.steps_per_epoch),
            "steps_per_epoch": train_loader.steps_per_epoch,
            "loss": train_m.get("loss"), "best_top1": best_top1,
            # Carried into the terminal record: a run that FINISHED
            # input-bound should say so on its last status surface,
            # not only in the per-epoch telemetry log.
            "input_wait_alert": last_input_alert[0],
            "clock_skew_s": last_clock_skew[0],
            "degraded": bool(pod is not None and pod.degraded),
            "world_size": jax.process_count(),
            "launched_world_size": launched_world,
            "mesh": mesh_info,
            "restored": restored_info,
            "health": (monitor.snapshot()
                       if monitor is not None else None),
            # A run that FINISHED in breach must say so on its last
            # status surface, not only in the event log.
            "slo": last_slo[0],
            # The last epoch's chip account (MFU + memory table):
            # the terminal surface keeps the efficiency verdict too.
            "chipacct": last_acct[0],
            # The warm-start verdict survives to the terminal surface.
            "compile_cache": cc_stats,
        })
    summary = {"best_top1": best_top1, "best_top5": best_top5,
               "best_epoch": best_epoch, "total_minutes": total_min,
               "final_train": train_m, "final_val": val_m,
               "preempted": preempted, "rollbacks": rollbacks,
               # The agreed stop was a GROW: __main__ maps this to the
               # POD_RESIZE exit (or exec-restarts straight into the
               # rendezvous) instead of the preemption code.
               "resize_grow": grow_stop,
               "ckpt_commit_failures": ckpt_commit_failures}
    telem.run_end({"best_top1": best_top1, "best_epoch": best_epoch,
                   "total_minutes": round(total_min, 3),
                   "preempted": preempted, "rollbacks": rollbacks,
                   "ckpt_commit_failures": ckpt_commit_failures})
    logger.close()
    return summary
