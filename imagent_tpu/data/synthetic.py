"""Synthetic dataset: deterministic, learnable, no disk.

The reference has nothing here (its only data path is the real ImageNet
tree, ``imagenet.py:287-296``); SURVEY §7 step 3 adds a synthetic mode as
the hardware-free CI path. Images carry a label-dependent low-frequency
pattern plus noise, so a classifier genuinely learns — loss-decrease
tests are meaningful, not vacuous.

Sample order follows the shared deterministic stream contract
(``data/stream.py``): ``epoch(e, start_step=s)`` opens the stream at
``(e, s)``, so a mid-epoch resume generates nothing for the
already-trained prefix. ``--workers`` carries the same semantics as
the decode loaders — ``0`` = in-process serial, ``N`` = a spawn-context
pool of N generator processes. The pool writes into a ring of
``--prefetch-depth`` + 1 batch slots in shared memory, allocated once
with the pool: each batch goes out as 8-row tasks, up to one batch per
slot is in flight, every worker writes its rows straight into the
batch's slot (no sample crosses a pipe), and the producer copies a
finished slot out once, then hands the slot to the batch one ring
length later. The per-sample output is a pure function of
``(seed, row)``, so the pooled and serial paths are bit-identical
(pinned by tests/test_stream.py).
"""

from __future__ import annotations

import collections
import contextlib
import threading
from typing import Iterator

import numpy as np

from imagent_tpu.config import Config
from imagent_tpu.data import stream
from imagent_tpu.data.pipeline import (
    PAD_ROW, Batch, pad_batch, to_wire,
)


def _quantize_u8(img: np.ndarray) -> np.ndarray:
    """Float pattern (≈[-1.3, 1.3], zero-centered) → raw uint8 pixels on
    the wire contract's [0, 255] scale. The affine map targets [0, 1]
    so the in-graph (x/255 - 0.5)/0.5 normalization lands the model
    input back near the pattern's native zero-centered range; the clip
    costs only the noise tails, so the class signal survives."""
    return np.clip(np.rint((img * 0.5 + 0.5) * 255.0), 0, 255
                   ).astype(np.uint8)


def _gen_one(fy: float, fx: float, size: int, rng_seed: int) -> np.ndarray:
    """One sample, a pure function of (class frequencies, size, seed) —
    module-level so a spawn-context pool worker can run it. The fp32
    arithmetic mirrors the historical in-class body operation-for-
    operation, so pooled, serial, and pre-refactor outputs are
    bit-identical."""
    fy = np.float32(fy)
    fx = np.float32(fx)
    rng = np.random.default_rng(rng_seed)
    yy, xx = np.mgrid[0:size, 0:size].astype(np.float32) / size
    pattern = np.sin(2 * np.pi * (fy * yy + fx * xx)).astype(np.float32)
    img = pattern[:, :, None] * 0.5 + rng.normal(
        0, 0.3, size=(size, size, 3)).astype(np.float32)
    return _quantize_u8(img)


_CHUNK_ROWS = 8  # rows one pool task generates

# The ring as a pool worker sees it: set once per worker process by the
# pool's initializer (the parent process never sets it).
_worker_ring: np.ndarray | None = None


def _attach_ring(buf, shape: tuple) -> None:
    """Pool initializer: view the shared ring buffer as
    ``(slots, rows, size, size, 3)`` uint8."""
    global _worker_ring
    _worker_ring = np.frombuffer(buf, np.uint8).reshape(shape)


def _fill_rows(slot: int, row0: int, args: list) -> None:
    """Pool task: write ``_gen_one(*a)`` for each of ``args`` into rows
    ``row0, row0 + 1, ...`` of ring slot ``slot``."""
    for i, a in enumerate(args):
        _worker_ring[slot, row0 + i] = _gen_one(*a)


class SyntheticLoader:
    def __init__(self, cfg: Config, process_index: int, process_count: int,
                 global_batch: int, train: bool):
        self.cfg = cfg
        self.process_index = process_index
        self.process_count = process_count
        self.global_batch = global_batch
        self.train = train
        self.split = "train" if train else "val"
        self.num_examples = cfg.synthetic_size if train else max(
            cfg.synthetic_size // 4, global_batch)
        if train:
            self.steps_per_epoch = self.num_examples // global_batch
        else:
            self.steps_per_epoch = -(-self.num_examples // global_batch)
        self.local_rows = global_batch // process_count
        # Per-class pattern bank: identical on every host AND between
        # train/val (same classification task); only sample noise differs.
        rng = np.random.default_rng(cfg.seed)
        n_classes = cfg.num_classes
        freqs = rng.uniform(1.0, 4.0, size=(n_classes, 2)).astype(np.float32)
        self._freqs = freqs
        self._pool = None
        self._ring = None  # (slots, local_rows, S, S, 3) view, with the pool
        # The in-flight batches of the open epoch that owns the ring (its
        # ``_pooled`` deque), or None; guarded by ``_ring_lock``.
        self._owner = None
        self._ring_lock = threading.Lock()
        # Batches of the most recently closed pooled epoch whose every
        # task had finished when the producer came for them (0 serial);
        # published at the epoch's close, so a next epoch warmed before
        # the engine reads it leaves it alone.
        self.ahead_batches = 0

    def _stream_key(self) -> stream.StreamKey:
        return stream.StreamKey(
            num_examples=self.num_examples,
            global_batch=self.global_batch, seed=self.cfg.seed,
            process_index=self.process_index,
            process_count=self.process_count, shuffle=self.train,
            drop_remainder=self.train)

    def _ensure_pool(self):
        if self._pool is None and self.cfg.workers > 0:
            import multiprocessing as mp
            # spawn, not fork — same reasoning as the decode loaders
            # (data/imagefolder.py::_ensure_pool): the PJRT runtime is
            # multithreaded by loader time. Workers import numpy only.
            ctx = mp.get_context("spawn")
            # A RawArray reaches spawned workers only as a start-up
            # argument, so the ring is made here, once, with the pool.
            size = self.cfg.image_size
            shape = (self.cfg.prefetch_depth + 1, self.local_rows, size,
                     size, 3)
            buf = ctx.RawArray("B", int(np.prod(shape)))
            self._ring = np.frombuffer(buf, np.uint8).reshape(shape)
            self._pool = ctx.Pool(self.cfg.workers, initializer=_attach_ring,
                                  initargs=(buf, shape))

    def _submit(self, slot: int, args: list):
        """Queue one batch's rows as 8-row tasks writing into ``slot``;
        the result is ready once every task has finished."""
        return self._pool.starmap_async(
            _fill_rows, [(slot, r, args[r:r + _CHUNK_ROWS])
                         for r in range(0, len(args), _CHUNK_ROWS)],
            chunksize=1)

    def _claim(self, pending: collections.deque) -> None:
        """Make ``pending`` (an open epoch's in-flight batches) the
        ring's owner. Another open epoch's tasks are waited for first,
        and ``pending``'s are queued again: they may have been
        overwritten while the other epoch owned the slots."""
        owner = self._owner
        if owner is pending:
            return
        if owner is not None:
            for *_, res in owner:
                res.wait()
        for entry in pending:
            entry[3] = self._submit(entry[0], entry[1])
        self._owner = pending

    def _pooled(self, jobs: Iterator[tuple]) -> Iterator[tuple]:
        """Yield ``(meta, images)`` for each ``(args, meta)`` job, in
        order, with the images of ``args`` made in the ring: up to one
        batch per slot is queued ahead of the one being yielded, so the
        pool works while the caller copies, stages and waits."""
        pending: collections.deque = collections.deque()
        submitted = ahead = 0
        try:
            while True:
                with self._ring_lock:
                    self._claim(pending)
                    slots = len(self._ring)
                    while len(pending) < slots:
                        job = next(jobs, None)
                        if job is None:
                            break
                        args, meta = job
                        slot = submitted % slots
                        pending.append([slot, args, meta,
                                        self._submit(slot, args)])
                        submitted += 1
                    if not pending:
                        return
                    slot, args, meta, res = pending.popleft()
                    ahead += res.ready()
                    res.get()  # a worker's exception is raised here
                    images = self._ring[slot, :len(args)].copy()
                # Yielded batches are copies: the slot is refilled for
                # the batch one ring length later only after this yield.
                yield meta, images
        finally:
            with self._ring_lock:
                if self._owner is pending:
                    for *_, res in pending:
                        res.wait()
                    self._owner = None
            self.ahead_batches = ahead

    def epoch(self, epoch: int, start_step: int = 0,
              stats=None) -> Iterator[Batch]:
        """``stats`` is accepted for loader-API uniformity and unused:
        with ``--workers`` 0 the batch is made in the caller's thread
        when asked for; with a pool, up to ``--prefetch-depth`` + 1
        batches are being made in the ring ahead of the caller, whose
        waits the consumer's own stage (``Prefetcher``) counts."""
        cfg = self.cfg
        self._ensure_pool()
        labels_all = (np.arange(self.num_examples, dtype=np.int64)
                      % cfg.num_classes)
        # Distinct noise draws for train vs val rows (same class
        # patterns, different samples → a real generalization split).
        off = 0 if self.train else 10_000_019

        def jobs():
            for step, rows in stream.open_stream(self._stream_key(), epoch,
                                                 start_step):
                valid = rows[rows != PAD_ROW]
                labels = labels_all[valid].astype(np.int32)
                args = [(float(self._freqs[int(lb)][0]),
                         float(self._freqs[int(lb)][1]), cfg.image_size,
                         cfg.seed * 1000003 + int(r) + off)
                        for lb, r in zip(labels, valid)]
                yield args, (step, valid, labels)

        def serial(args):
            if not args:
                return np.zeros(
                    (0, cfg.image_size, cfg.image_size, 3), np.uint8)
            return np.stack([_gen_one(*a) for a in args])

        made = (self._pooled(jobs()) if self._pool is not None
                else ((meta, serial(args)) for args, meta in jobs()))
        with contextlib.closing(made):
            for (step, valid, labels), images in made:
                stream.trace_rows(self.process_index, self.split, epoch,
                                  step, valid, world=self.process_count)
                yield pad_batch(to_wire(images, cfg.transfer_dtype),
                                labels, self.local_rows)

    def close(self):
        if self._pool is not None:
            # Terminated workers write nothing more, so no open epoch
            # has tasks to wait for.
            with self._ring_lock:
                self._pool.terminate()
                self._pool = None
                self._ring = None
                self._owner = None
