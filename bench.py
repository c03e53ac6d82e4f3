"""Headline benchmarks with MFU accounting.

Three configs, every round:
  1. (primary, parsed) ResNet-18 448x448 b128/chip — mirrors the
     reference's run-of-record (`imagent_sgd.out:14,278`; BASELINE.md:
     152.8 img/s/GPU on its 16-GPU cluster).
  2. ResNet-50 224x224 b256/chip — the north-star config
     (BASELINE.json: >= 1200 img/s/chip).
  3. ViT-B/16 224x224 b256/chip AdamW — the attention-family headline
     (no reference analogue; MFU is the scoreboard).

All measure the jitted SPMD train step on the local device(s) with
synthetic device-resident data (input pipeline excluded; the honest
end-to-end epoch number lives in benchmarks/e2e_epoch.py). Each metric
carries `tflops_per_chip` (analytic model FLOPs: 3x forward,
multiply-add = 2) and `mfu_pct` against the detected chip's bf16 peak —
so the number is judged against the hardware, not just a 2019 GPU log.

Prints ONE JSON line; the primary metric is the top-level object, the
other configs ride in the "extra" list. A device benchmark needs the
device: off the TPU it exits non-zero without a record, and a
configuration (or the calibration) that fails to measure fails the
run — nothing is skipped and passed over.
"""

import json
import os
import sys
import time

import numpy as np

sys.path.insert(0, os.path.dirname(os.path.abspath(__file__)))

# The order-statistic median-CI lives in imagent_tpu/utils/stats.py so
# the cross-run regression gate (telemetry/regress.py) judges deltas
# with the SAME noise model this driver publishes. The underscore
# names are kept as aliases (tests + external callers).
from imagent_tpu.utils.stats import (  # noqa: E402
    median_ci as _median_ci, spread_pct as _spread_pct,
)

BASELINE_IMG_S_PER_CHIP = 152.8  # reference img/s/GPU (BASELINE.md)
NORTH_STAR_IMG_S_PER_CHIP = 1200.0  # BASELINE.json resnet50@224 target


def environment() -> dict:
    """Environment fingerprint stamped into every bench record (the
    ``env`` key): ``telemetry regress`` refuses to compare numbers
    measured on different hardware/topology/software instead of
    producing a nonsense verdict (regress.ENV_KEYS)."""
    import platform

    import jax
    import jaxlib

    return {
        "device_kind": jax.devices()[0].device_kind,
        "device_count": jax.device_count(),
        "process_count": jax.process_count(),
        "jax_version": jax.__version__,
        "jaxlib_version": jaxlib.__version__,
        "python": platform.python_version(),
        # The wire/contract dtype the measured step consumes
        # (uint8-wire PR 2): a float32-wire rerun is not comparable.
        "transfer_dtype": "uint8",
    }


def require_chip() -> None:
    """A device benchmark needs the device: off the TPU, or on a chip
    the peak table does not know, exit non-zero without a record. A
    CPU timing is never written under the name of a device metric, and
    an unknown chip is an error, not a default."""
    import jax

    from imagent_tpu.utils.flops import chip_peak_bf16_tflops

    dev = jax.devices()[0]
    if dev.platform != "tpu":
        sys.exit(f"bench: this measures the TPU; JAX initialized the "
                 f"{dev.platform!r} platform ({dev.device_kind}). A CPU "
                 "timing is not a device metric — no record written.")
    if chip_peak_bf16_tflops(dev.device_kind) is None:
        sys.exit(f"bench: device kind {dev.device_kind!r} is not in the "
                 "peak table (imagent_tpu/utils/flops.py) — add it with "
                 "its source")


def _robust_samples(sample_fn, pairs: int, max_spread_pct: float,
                    max_rounds: int) -> tuple[list, int, int]:
    """Collect paired-window samples with outlier rejection + retry
    (the host-noise guard: one descheduled window must not move the
    median). Round 1 collects ``pairs``
    samples; while their spread exceeds ``max_spread_pct``, samples
    outside a half-band around the median are REJECTED and replaced
    with fresh windows, up to ``max_rounds`` total rounds. A persistent
    noise floor is reported, not hidden: the loop exits with whatever
    spread remains and the caller publishes it plus the median CI.
    Returns ``(samples, n_rejected, rounds)``."""
    samples = [sample_fn() for _ in range(pairs)]
    rejected = 0
    rounds = 1
    while _spread_pct(samples) > max_spread_pct and rounds < max_rounds:
        med = float(np.median(samples))
        band = med * max_spread_pct / 200.0  # half-band: total <= bound
        keep = [s for s in samples if abs(s - med) <= band]
        rejected += len(samples) - len(keep)
        keep += [sample_fn() for _ in range(pairs - len(keep))]
        samples = keep
        rounds += 1
    return samples, rejected, rounds


def chip_calibration() -> dict:
    """Per-run chip-state snapshot: the roofline copy-bandwidth and
    matmul microbenches ride alongside every bench record, so a
    cross-session drift in a bandwidth-sensitive config (r18@448) can
    be attributed to chip state vs the estimator — compare the drift
    against these two numbers' drift."""
    from benchmarks.roofline import measure_hbm_gbs, measure_mxu_tflops

    return {"hbm_copy_gbs": round(measure_hbm_gbs(), 1),
            "mxu_matmul_tflops": round(measure_mxu_tflops(), 1)}


def measure(arch: str, size: int, per_chip_batch: int,
            optimizer: str = "sgd", bf16: bool = True,
            pairs: int = 5, lo_iters: int = 3, hi_iters: int = 15,
            max_spread_pct: float = 8.0, max_rounds: int = 3,
            model_kw: dict | None = None) -> dict:
    """Shared measurement harness (also used by benchmarks/throughput.py):
    jitted train step, synthetic device-resident batches, analytic-FLOPs
    MFU.

    Estimator: paired-window differencing — each sample is
    ``(T(hi_iters) - T(lo_iters)) / (hi_iters - lo_iters)`` over
    state-chained step windows, which cancels every fixed per-window
    cost (dispatch ramp, the final device->host metric fetch) a plain
    best-of-N window folds into the rate. The MEDIAN of ``pairs``
    samples resists one-sided host-contention outliers."""
    if hi_iters <= lo_iters:
        raise ValueError(
            f"hi_iters ({hi_iters}) must exceed lo_iters ({lo_iters}) — "
            "the estimator divides by their difference")
    import jax

    from imagent_tpu import compilecache
    from imagent_tpu.cluster import make_mesh
    from imagent_tpu.models import create_model
    from imagent_tpu.train import (
        create_train_state, make_optimizer, make_train_step,
        replicate_state, shard_batch,
    )
    from imagent_tpu.utils.flops import (
        chip_peak_bf16_tflops, forward_flops, train_step_flops_per_image,
    )

    require_chip()
    compilecache.arm()
    n_chips = len(jax.devices())
    batch = per_chip_batch * n_chips

    mesh = make_mesh(model_parallel=1)
    model = create_model(arch, num_classes=1000, bf16=bf16,
                         **(model_kw or {}))
    opt = make_optimizer(name=optimizer)
    state = replicate_state(
        create_train_state(model, jax.random.key(0), size, opt,
                           batch_size=2), mesh)
    # The production input contract: uint8 wire batches with
    # dequantize+normalize in-graph (train.make_input_prep). 1 byte/pixel
    # input HBM read — a quarter of the old f32 path, half of bf16 —
    # and the measured step includes the in-graph input stage, so the
    # bench number reflects what engine.run actually compiles.
    step = make_train_step(model, opt, mesh,
                           mean=(0.5, 0.5, 0.5), std=(0.5, 0.5, 0.5))

    rng = np.random.default_rng(0)
    images = rng.integers(0, 256, size=(batch, size, size, 3),
                          dtype=np.uint8)
    labels = rng.integers(0, 1000, size=(batch,)).astype(np.int32)
    gi, gl = shard_batch(mesh, images, labels)
    lr = np.float32(0.1)

    # Warmup / compile.
    for _ in range(3):
        state, metrics = step(state, gi, gl, lr)
    jax.block_until_ready((state, metrics))

    def window(iters):
        """Wall time of `iters` state-chained steps, ending in a real
        block_until_ready (dispatch alone returns before the device
        finishes)."""
        nonlocal state
        t0 = time.perf_counter()
        for _ in range(iters):
            state, metrics = step(state, gi, gl, lr)
        # The last step depends on the whole chain through the state.
        jax.block_until_ready((state, metrics))
        return time.perf_counter() - t0

    def sample():
        t_lo = window(lo_iters)
        t_hi = window(hi_iters)
        return (t_hi - t_lo) / (hi_iters - lo_iters)

    # Outlier rejection + retry on the high-variance configs, and an
    # order-statistic CI on the median so the JSON carries what the
    # estimator actually resolves.
    samples, n_rejected, rounds = _robust_samples(
        sample, pairs, max_spread_pct, max_rounds)
    per_step = float(np.median(samples))
    ci_lo, ci_hi, ci_cov = _median_ci(samples)

    img_s_chip = batch / per_step / n_chips
    step_flops = train_step_flops_per_image(forward_flops(arch, size))
    tflops_chip = img_s_chip * step_flops / 1e12
    kind = jax.devices()[0].device_kind
    peak = chip_peak_bf16_tflops(kind)
    out = {
        "metric": f"{arch}_{size}_train_throughput_per_chip",
        "value": round(img_s_chip, 2),
        "unit": "img/s/chip",
        "tflops_per_chip": round(tflops_chip, 2),
        # The raw analytic model-FLOP count behind tflops_per_chip /
        # mfu_pct (utils/flops.py, the 3x-forward convention) — stamped
        # so BENCH_*.json carries honest, recomputable MFU instead of
        # an opaque ratio, and so the chip accountant's XLA
        # cost-analysis figure has an analytic anchor to be checked
        # against (benchmarks/bench_smoke.py does exactly that).
        "model_flops_per_image": int(step_flops),
        "chip": kind,
        "compute_dtype": "bf16" if bf16 else "fp32",
        "optimizer": optimizer,
        "method": (f"paired-window differencing, median of {pairs} "
                   f"({lo_iters}/{hi_iters} chained iters), "
                   f"spread>{max_spread_pct:g}% rejected+retried "
                   f"(max {max_rounds} rounds)"),
        "spread_pct": round(_spread_pct(samples), 2),
        "samples_rejected": n_rejected,
        "sample_rounds": rounds,
    }
    if ci_lo > 0 and ci_cov > 0:
        # Median CI in img/s/chip (per-step maps inversely), published
        # only together with its coverage. A non-positive low bound
        # means the differencing noise swamped the signal — spread_pct
        # already says so, no fake interval (and no orphan coverage
        # claim); n<2's degenerate zero-coverage interval likewise
        # stays out of the JSON.
        out["ci_img_s"] = [round(batch / ci_hi / n_chips, 2),
                           round(batch / ci_lo / n_chips, 2)]
        out["ci_coverage_pct"] = round(ci_cov, 2)
    # MFU only against a peak that matches the compute dtype — there is
    # no per-chip fp32 peak table here, and fp32 achieved FLOPs over the
    # bf16 peak is not a meaningful utilization figure.
    if peak is not None and bf16:
        out["mfu_pct"] = round(100.0 * tflops_chip / peak, 2)
        out["chip_peak_bf16_tflops"] = peak
    return out


def main() -> int:
    require_chip()  # before the first minute of compiling

    primary = measure("resnet18", 448, 128)
    primary["vs_baseline"] = round(
        primary["value"] / BASELINE_IMG_S_PER_CHIP, 3)
    # Environment fingerprint (regress.ENV_KEYS): cross-hardware /
    # cross-topology BENCH comparisons are refused by `telemetry
    # regress` on these keys instead of yielding a nonsense verdict.
    primary["env"] = environment()
    primary["chip_calibration"] = chip_calibration()

    # The full README family table rides here so every published
    # number is driver-measured. A configuration that fails to measure
    # raises: the record is all-or-nothing.
    north = measure("resnet50", 224, 256)
    north["vs_baseline"] = round(
        north["value"] / NORTH_STAR_IMG_S_PER_CHIP, 3)
    primary["extra"] = [
        north,
        measure("vit_b16", 224, 256, optimizer="adamw"),
        measure("wide_resnet50_2", 224, 256),
        measure("resnext50_32x4d", 224, 256),
        measure("convnext_tiny", 224, 256, optimizer="adamw"),
    ]

    print(json.dumps(primary))
    return 0


if __name__ == "__main__":
    sys.exit(main())
