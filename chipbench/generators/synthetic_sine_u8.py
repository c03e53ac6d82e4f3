"""The generator ``synthetic_sine_u8``: seeded synthetic images made on
the host, as ``--dataset synthetic`` makes them.

The per-sample function and the epoch order are the benchmark's own
copy of what ``imagent_tpu/data/synthetic.py`` and ``data/stream.py``
do (pinned byte for byte by tests/chipbench): a later change that made
the program's input cheaper by generating something else would fail
the comparison instead of moving the yardstick.

A mix of this generator gives ``wire_dtype``, ``per_chip_batch``,
``workers``, ``prefetch_depth`` and ``epoch_images``.
"""

from __future__ import annotations

import numpy as np


def engine_flags(mix: dict) -> list[str]:
    """The data flags of one mix, verbatim as a user would pass them."""
    return [
        "--dataset=synthetic",
        f"--transfer-dtype={mix['wire_dtype']}",
        f"--batch-size={mix['per_chip_batch']}",
        f"--workers={mix['workers']}",
        f"--prefetch-depth={mix['prefetch_depth']}",
        f"--synthetic-size={mix['epoch_images']}",
    ]


# ---- the benchmark's copy of the sample function --------------------------


def sample_u8(fy: float, fx: float, size: int, rng_seed: int) -> np.ndarray:
    """One image: a class-dependent sine pattern plus seeded noise,
    quantised to uint8 (a pure function of its arguments)."""
    fy = np.float32(fy)
    fx = np.float32(fx)
    rng = np.random.default_rng(rng_seed)
    yy, xx = np.mgrid[0:size, 0:size].astype(np.float32) / size
    pattern = np.sin(2 * np.pi * (fy * yy + fx * xx)).astype(np.float32)
    img = pattern[:, :, None] * 0.5 + rng.normal(
        0, 0.3, size=(size, size, 3)).astype(np.float32)
    return np.clip(np.rint((img * 0.5 + 0.5) * 255.0), 0, 255
                   ).astype(np.uint8)


def epoch_rows(mix: dict, seed: int, epoch: int, batch: int) -> np.ndarray:
    """Dataset rows of one epoch in training order, whole batches only:
    a permutation seeded by ``seed + epoch``."""
    n = mix["epoch_images"]
    order = np.random.default_rng(seed + epoch).permutation(n)
    return order[:(n // batch) * batch].astype(np.int64)


def _one(args) -> np.ndarray:
    return sample_u8(*args)


def batches(mix: dict, seed: int, image_size: int, num_classes: int,
            chips: int, steps: int, epoch: int = 0,
            workers: int = 0) -> list:
    """The first ``steps`` global batches of ``epoch`` as
    ``(uint8 NHWC images, int32 labels)``; ``workers`` > 0 makes the
    samples in that many spawned processes (the order is kept)."""
    gb = mix["per_chip_batch"] * chips
    rows = epoch_rows(mix, seed, epoch, gb)[:steps * gb]
    freqs = np.random.default_rng(seed).uniform(
        1.0, 4.0, size=(num_classes, 2)).astype(np.float32)
    labels = (rows % num_classes).astype(np.int32)
    args = [(float(freqs[lb][0]), float(freqs[lb][1]), image_size,
             seed * 1000003 + int(row)) for lb, row in zip(labels, rows)]
    if workers > 0:
        import multiprocessing as mp
        with mp.get_context("spawn").Pool(workers) as pool:
            images = pool.map(_one, args, chunksize=16)
    else:
        images = [_one(a) for a in args]
    images = np.stack(images)
    return [(images[s * gb:(s + 1) * gb], labels[s * gb:(s + 1) * gb])
            for s in range(steps)]
