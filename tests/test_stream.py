"""The deterministic sample-stream contract (``data/stream.py``):
seed-and-position-keyed order shared by all four loader paths
(imagefolder-PIL, imagefolder-native, tarshards, synthetic), opening a
stream at ``(epoch, step)`` with no decode of the skipped prefix, the
``--workers`` contract (0 = in-process serial, pooled == serial
bit-identically), the sample-trace hook the resume drill reads, and
the jax-free import chain the decode workers / offload hosts rely on."""

import io
import os
import tarfile

import numpy as np
import pytest
from PIL import Image

from imagent_tpu.config import Config
from imagent_tpu.data import stream
from imagent_tpu.data.stream import PAD_ROW, StreamKey, open_stream

SIZE = 12


def _key(**kw):
    base = dict(num_examples=103, global_batch=16, seed=5,
                process_index=1, process_count=2, shuffle=True,
                drop_remainder=True)
    base.update(kw)
    return StreamKey(**base)


def test_open_stream_positional():
    """open at step s == suffix of the full stream — the property the
    mid-epoch resume's no-replay/no-skip guarantee reduces to."""
    key = _key()
    full = list(open_stream(key, epoch=3))
    assert full[0][0] == 0 and full[-1][0] == len(full) - 1
    for s in (0, 1, 3, len(full)):
        tail = list(open_stream(key, epoch=3, start_step=s))
        assert [st for st, _ in tail] == [st for st, _ in full[s:]]
        for (_, a), (_, b) in zip(tail, full[s:]):
            np.testing.assert_array_equal(a, b)
    with pytest.raises(ValueError, match="start_step"):
        list(open_stream(key, 0, start_step=-1))


def test_stream_matches_legacy_shard_indices():
    """One implementation: the legacy array API and the stream yield
    the same slots, train (drop) and eval (pad) modes alike."""
    from imagent_tpu.data.pipeline import iter_batch_rows, shard_indices
    for drop in (True, False):
        key = _key(drop_remainder=drop, shuffle=drop)
        idx = shard_indices(103, 2, 5, 1, 2, shuffle=drop,
                            drop_remainder=drop, global_batch=16)
        legacy = list(iter_batch_rows(idx, key.local_rows))
        modern = [rows for _, rows in open_stream(key, 2)]
        assert len(legacy) == len(modern) == key.steps_per_epoch
        for a, b in zip(legacy, modern):
            np.testing.assert_array_equal(a, b)


def test_epoch_order_same_slot_count_per_process():
    keys = [_key(process_index=p, process_count=4, shuffle=False,
                 drop_remainder=False) for p in range(4)]
    orders = [stream.epoch_order(k, 0) for k in keys]
    assert len({len(o) for o in orders}) == 1  # SPMD invariant
    real = np.concatenate(orders)
    real = real[real != PAD_ROW]
    assert sorted(real) == list(range(103))  # every sample exactly once


# ---------------------------------------------------------------------------
# All four loader paths honor the contract
# ---------------------------------------------------------------------------


def _build_datasets(root: str):
    """One image set as a loose ImageFolder AND {split}/*.tar shards."""
    rng = np.random.default_rng(0)
    for split, n_per_class in (("train", 9), ("val", 3)):
        shard_members = {0: [], 1: []}
        for c in ("clsa", "clsb"):
            d = os.path.join(root, "folder", split, c)
            os.makedirs(d)
            for i in range(n_per_class):
                arr = rng.integers(0, 255, size=(24, 20, 3),
                                   dtype=np.uint8)
                buf = io.BytesIO()
                Image.fromarray(arr).save(buf, "JPEG", quality=95)
                with open(os.path.join(d, f"{i}.jpg"), "wb") as f:
                    f.write(buf.getvalue())
                shard_members[i % 2].append((f"{c}/{i}.jpg",
                                             buf.getvalue()))
        tar_dir = os.path.join(root, "tars", split)
        os.makedirs(tar_dir)
        for si, members in shard_members.items():
            with tarfile.open(os.path.join(tar_dir, f"s{si}.tar"),
                              "w") as tf:
                for name, data in members:
                    ti = tarfile.TarInfo(name)
                    ti.size = len(data)
                    tf.addfile(ti, io.BytesIO(data))


def _native_available() -> bool:
    from imagent_tpu import native
    return native.available()


LOADERS = ["imagefolder-pil", "imagefolder-native", "tar", "synthetic"]


def _make_loader(kind: str, root: str, workers: int,
                 global_batch: int = 4, split: str = "train"):
    if kind == "synthetic":
        from imagent_tpu.data.synthetic import SyntheticLoader
        cfg = Config(image_size=SIZE, num_classes=2, synthetic_size=36,
                     workers=workers, seed=1)
        return SyntheticLoader(cfg, 0, 1, global_batch,
                               train=(split == "train"))
    if kind == "tar":
        from imagent_tpu.data.tarshards import TarShardLoader
        cfg = Config(data_root=os.path.join(root, "tars"),
                     image_size=SIZE, dataset="tar", workers=workers,
                     augment=True, seed=1)
        return TarShardLoader(cfg, 0, 1, global_batch, split=split)
    from imagent_tpu.data.imagefolder import ImageFolderLoader
    if kind == "imagefolder-native" and not _native_available():
        pytest.skip("native decoder unavailable")
    cfg = Config(data_root=os.path.join(root, "folder"),
                 image_size=SIZE, workers=workers, augment=True,
                 native_io=(kind == "imagefolder-native"), seed=1)
    return ImageFolderLoader(cfg, 0, 1, global_batch, split=split)


@pytest.fixture(scope="module")
def data_root(tmp_path_factory):
    root = str(tmp_path_factory.mktemp("stream_data"))
    _build_datasets(root)
    return root


def _collect(loader, epoch, start_step=0):
    return [(b.images.copy(), b.labels.copy(), b.mask.copy())
            for b in loader.epoch(epoch, start_step=start_step)]


@pytest.mark.parametrize("kind", LOADERS)
def test_loader_opens_stream_at_step(kind, data_root):
    """epoch(e, start_step=s) is byte-identical to the suffix of
    epoch(e) — for every loader path, train and val splits."""
    ld = _make_loader(kind, data_root, workers=0)
    try:
        full = _collect(ld, epoch=1)
        assert len(full) >= 3
        for s in (1, 2, len(full)):
            tail = _collect(ld, epoch=1, start_step=s)
            assert len(tail) == len(full) - s
            for (ai, al, am), (bi, bl, bm) in zip(tail, full[s:]):
                np.testing.assert_array_equal(ai, bi)
                np.testing.assert_array_equal(al, bl)
                np.testing.assert_array_equal(am, bm)
    finally:
        ld.close()
    # Eval split: padded tail batches follow the same contract.
    lv = _make_loader(kind, data_root, workers=0, split="val")
    try:
        full = _collect(lv, epoch=0)
        tail = _collect(lv, epoch=0, start_step=1)
        for (ai, al, am), (bi, bl, bm) in zip(tail, full[1:]):
            np.testing.assert_array_equal(ai, bi)
            np.testing.assert_array_equal(am, bm)
    finally:
        lv.close()


@pytest.mark.parametrize("kind", LOADERS)
def test_workers_contract(kind, data_root):
    """``workers=0 ⇒ in-process serial`` for every loader — and the
    pooled output is bit-identical to serial (worker count must never
    change the training data)."""
    serial = _make_loader(kind, data_root, workers=0)
    pooled = _make_loader(kind, data_root, workers=2)
    try:
        sb = _collect(serial, epoch=0)
        assert serial._pool is None  # 0 = no child processes
        pb = _collect(pooled, epoch=0)
        if not getattr(pooled, "_use_native", False):
            # Native-decode loaders run workers as in-process threads
            # (no pool either way); every pool path must spawn one for
            # workers=2.
            assert pooled._pool is not None
        assert len(sb) == len(pb)
        for (ai, al, _), (bi, bl, _) in zip(sb, pb):
            np.testing.assert_array_equal(ai, bi)
            np.testing.assert_array_equal(al, bl)
    finally:
        serial.close()
        pooled.close()


def test_trace_rows_records_the_stream(data_root, monkeypatch,
                                       tmp_path):
    """The sample-trace hook (the resume drill's observability):
    produced batches land in the per-process trace file and match the
    pure stream contract exactly."""
    prefix = str(tmp_path / "trace")
    monkeypatch.setenv(stream.TRACE_ENV, prefix)
    ld = _make_loader("imagefolder-pil", data_root, workers=0)
    try:
        list(ld.epoch(0))
        list(ld.epoch(1, start_step=2))
    finally:
        ld.close()
    recs = stream.read_trace(prefix, 0, split="train")
    key = ld._stream_key()
    want = ([(0, st, r) for st, r in open_stream(key, 0)]
            + [(1, st, r) for st, r in open_stream(key, 1,
                                                   start_step=2)])
    assert [(r["epoch"], r["step"]) for r in recs] \
        == [(e, s) for e, s, _ in want]
    for rec, (_, _, rows) in zip(recs, want):
        assert rec["rows"] == [int(x) for x in rows[rows != PAD_ROW]]


# ---------------------------------------------------------------------------
# The synthetic generator pool's shared-memory ring
# ---------------------------------------------------------------------------


def _synth(workers: int, split: str = "train", seed: int = 7):
    """13 train batches of 4 rows, or 4 val batches (the last one padded),
    through a ring of 2 slots (prefetch_depth 1)."""
    from imagent_tpu.data.synthetic import SyntheticLoader
    cfg = Config(image_size=SIZE, num_classes=3, synthetic_size=52,
                 workers=workers, prefetch_depth=1, seed=seed)
    return SyntheticLoader(cfg, 0, 1, 4, train=(split == "train"))


def _assert_same(got, want):
    assert len(got) == len(want)
    for (ai, al, am), (bi, bl, bm) in zip(got, want):
        np.testing.assert_array_equal(ai, bi)
        np.testing.assert_array_equal(al, bl)
        np.testing.assert_array_equal(am, bm)


@pytest.mark.parametrize("split,start_step", [
    ("train", 0), ("train", 3), ("val", 0), ("val", 1)])
def test_synthetic_ring_matches_serial(split, start_step):
    """Pooled batches are the serial ones bit for bit over an epoch
    longer than the ring, padded val tail and mid-epoch start included.
    The pooled arrays are kept as yielded (no copy) and compared only
    after the epoch has ended: a yielded batch is never written again."""
    serial, pooled = _synth(0, split), _synth(2, split)
    try:
        want = _collect(serial, 0, start_step)
        got = [(b.images, b.labels, b.mask)
               for b in pooled.epoch(0, start_step=start_step)]
        assert len(got) > len(pooled._ring)
        assert not any(np.shares_memory(g[0], pooled._ring) for g in got)
        _assert_same(got, want)
    finally:
        serial.close()
        pooled.close()


def test_synthetic_ring_unwinds_and_is_reused():
    """An epoch closed after one batch leaves nothing in flight; the
    next epoch on the same loader yields the serial batches, and
    ``close()`` leaves no worker process alive."""
    serial, pooled = _synth(0), _synth(2)
    try:
        it = pooled.epoch(0)
        first = next(it)
        it.close()
        assert pooled._owner is None
        np.testing.assert_array_equal(first.images,
                                      _collect(serial, 0)[0][0])
        _assert_same(_collect(pooled, 1), _collect(serial, 1))
        workers = list(pooled._pool._pool)
        assert len(workers) == 2
    finally:
        serial.close()
        pooled.close()
    for w in workers:
        w.join(timeout=10)
        assert not w.is_alive()


def test_synthetic_ring_interleaved_epochs():
    """Two epochs of one loader open at once take the ring in turn: each
    still yields its serial batches."""
    serial, pooled = _synth(0), _synth(2)
    try:
        got0, got1 = [], []
        for b0, b1 in zip(pooled.epoch(0), pooled.epoch(1, start_step=2)):
            got0.append((b0.images, b0.labels, b0.mask))
            got1.append((b1.images, b1.labels, b1.mask))
        _assert_same(got0, _collect(serial, 0)[:len(got0)])
        _assert_same(got1, _collect(serial, 1, start_step=2))
    finally:
        serial.close()
        pooled.close()


def test_synthetic_ring_counts_batches_made_ahead():
    """Under a slow consumer the pool finishes batches before they are
    asked for; the count never exceeds the batches yielded, and the
    serial path counts none."""
    import time
    serial, pooled = _synth(0), _synth(2)
    try:
        n = 0
        for _ in pooled.epoch(0):
            time.sleep(0.02)
            n += 1
        assert 0 < pooled.ahead_batches <= n
        assert sum(1 for _ in serial.epoch(0)) == n
        assert serial.ahead_batches == 0
    finally:
        serial.close()
        pooled.close()


def test_synthetic_ring_worker_error_reaches_consumer():
    """A sample a worker cannot make (here a negative noise seed, which
    the serial path refuses the same way) raises in the consumer, and
    the loader works again afterwards."""
    import dataclasses
    serial, pooled = _synth(0, "val"), _synth(2, "val")
    try:
        want = _collect(pooled, 0)
        for ld in (serial, pooled):
            good = ld.cfg
            ld.cfg = dataclasses.replace(good, seed=-10**9)
            with pytest.raises(ValueError, match="negative"):
                list(ld.epoch(0))
            ld.cfg = good
        assert pooled._owner is None
        _assert_same(_collect(pooled, 0), want)
    finally:
        serial.close()
        pooled.close()
