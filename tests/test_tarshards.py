"""Tar-shard dataset (``data/tarshards.py``): indexing with sidecar
cache, class vocabulary from member directories, ranged-read staging,
and batch parity with the equivalent ImageFolder tree."""

import io
import os
import tarfile

import numpy as np
import pytest
from PIL import Image

from imagent_tpu.config import Config
from imagent_tpu.data.tarshards import TarShardLoader, index_shard

SIZE = 16


def _img_bytes(rng, fmt="JPEG"):
    arr = rng.integers(0, 255, size=(24, 20, 3), dtype=np.uint8)
    buf = io.BytesIO()
    Image.fromarray(arr).save(buf, fmt, quality=95)
    return arr, buf.getvalue()


def _build_tree(root, rng, n_per_class=6, classes=("clsa", "clsb")):
    """Same images as {split}/*.tar shards AND a loose ImageFolder."""
    for split in ("train", "val"):
        tar_dir = os.path.join(root, "tars", split)
        folder_dir = os.path.join(root, "folder", split)
        os.makedirs(tar_dir)
        shard_members = {0: [], 1: []}
        for c in classes:
            os.makedirs(os.path.join(folder_dir, c))
            for i in range(n_per_class):
                _, data = _img_bytes(rng)
                with open(os.path.join(folder_dir, c, f"{i}.jpg"),
                          "wb") as f:
                    f.write(data)
                shard_members[i % 2].append((f"{c}/{i}.jpg", data))
        for si, members in shard_members.items():
            with tarfile.open(os.path.join(tar_dir, f"shard{si}.tar"),
                              "w") as tf:
                for name, data in members:
                    ti = tarfile.TarInfo(name)
                    ti.size = len(data)
                    tf.addfile(ti, io.BytesIO(data))


@pytest.fixture()
def tree(tmp_path):
    _build_tree(str(tmp_path), np.random.default_rng(0))
    return str(tmp_path)


def _cfg(root, sub):
    return Config(data_root=os.path.join(root, sub), image_size=SIZE,
                  workers=2, dataset="tar" if sub == "tars"
                  else "imagefolder")


def test_index_sidecar_cache(tree):
    shard = os.path.join(tree, "tars", "train", "shard0.tar")
    idx1 = index_shard(shard)
    assert os.path.exists(shard + ".index.json")
    idx2 = index_shard(shard)  # served from the sidecar
    assert idx1 == idx2
    assert all(size > 0 and off > 0 for _, off, size in idx1)


def test_tar_matches_imagefolder_batches(tree):
    """Same images, same sharding semantics: tar batches must be
    pixel-identical to the ImageFolder loader's (both decode through the
    same native path; names sort identically)."""
    from imagent_tpu.data.imagefolder import ImageFolderLoader

    tl = TarShardLoader(_cfg(tree, "tars"), 0, 1, global_batch=4,
                        split="val")
    fl = ImageFolderLoader(_cfg(tree, "folder"), 0, 1, global_batch=4,
                           split="val")
    assert tl.num_examples == fl.num_examples == 12
    assert tl.classes == fl.classes
    tb = list(tl.epoch(0))
    fb = list(fl.epoch(0))
    assert len(tb) == len(fb) == 3
    for a, b in zip(tb, fb):
        np.testing.assert_array_equal(a.labels, b.labels)
        np.testing.assert_allclose(a.images, b.images, atol=1e-6)
        np.testing.assert_array_equal(a.mask, b.mask)
    tl.close()
    fl.close()
    assert not os.path.isdir(tl._staging)  # staging cleaned up


def test_tar_training_e2e(tree, tmp_path):
    from imagent_tpu.engine import run

    cfg = Config(backend="cpu", arch="resnet18", image_size=SIZE, num_classes=2,
                 batch_size=1, epochs=1, lr=0.01, dataset="tar",
                 data_root=os.path.join(tree, "tars"), workers=2,
                 bf16=False, log_every=0,
                 log_dir=str(tmp_path / "tb2"),
                 ckpt_dir=str(tmp_path / "ckpt2"))
    result = run(cfg)
    assert result["final_train"]["n"] == 8  # 12 train imgs, batch 8 global
