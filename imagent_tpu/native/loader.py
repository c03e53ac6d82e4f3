"""ctypes binding for the native C++ batch image loader.

The C++ side (``io_loader.cc``) is the TPU-native replacement for the
reference's native DataLoader workers (``imagenet.py:350-359``): threaded
libjpeg/libpng decode + triangle resize + normalize with the GIL released.
This module builds the shared library on demand with ``g++`` (toolchain is
baked into the image; no pip/pybind11 needed), binds it via ctypes, and
degrades gracefully — ``available()`` is False if the toolchain or headers
are missing, and callers fall back to the pure-Python (PIL) path.
"""

from __future__ import annotations

import ctypes
import glob
import hashlib
import os
import subprocess
import threading

import numpy as np

_DIR = os.path.dirname(os.path.abspath(__file__))
_SRC = os.path.join(_DIR, "io_loader.cc")
# -ffp-contract=off: exp_shared/sample_crop must round exactly like
# the Python port (two roundings per p*f+c, never fused) — GCC's
# default contraction would emit fma on targets that have it and
# silently break cross-path augmentation parity. No -march=native —
# the .so may be shared by heterogeneous hosts.
_CXXFLAGS = ("-O3", "-fPIC", "-std=c++17", "-ffp-contract=off")


def _lib_path() -> str:
    """The binary is named by the hash of what it was built FROM
    (source text + flags): a changed source is a different file name,
    so a stale build — a stray binary copied along with the tree, an
    older checkout on a shared filesystem — can never be picked up,
    whatever its mtime says (the ABI check below only catches a
    changed calling convention, not a changed body)."""
    h = hashlib.sha256(" ".join(_CXXFLAGS).encode())
    with open(_SRC, "rb") as f:
        h.update(f.read())
    return os.path.join(_DIR, f"libimagent_io.{h.hexdigest()[:12]}.so")


_lock = threading.Lock()
_lib: ctypes.CDLL | None = None
_load_failed = False

# Must match io_loader.cc::il_version(). Bump BOTH on any C-ABI change.
_ABI_VERSION = 4


def _abi_version(lib: ctypes.CDLL) -> int:
    try:
        fn = lib.il_version
    except AttributeError:
        return -1
    fn.restype = ctypes.c_int
    fn.argtypes = []
    return int(fn())


def _build(lib_path: str) -> bool:
    # Compile to a pid-unique temp path, then os.rename (atomic on POSIX):
    # under multi-process launches on a shared filesystem, concurrent
    # builders must never let a rank CDLL a half-written .so.
    tmp = f"{lib_path}.{os.getpid()}.tmp"
    base = ["g++", *_CXXFLAGS, "-shared", "-o", tmp, _SRC,
            "-ljpeg", "-lpng"]
    # libwebp is optional: hosts without its headers (common on lean
    # CPU decode boxes) still get the native jpeg/png fast path — webp
    # members fall to the per-file PIL rescue in that build.
    for cmd in (base + ["-lwebp"], base + ["-DIL_NO_WEBP"]):
        try:
            subprocess.run(cmd, check=True, capture_output=True,
                           timeout=120)
            os.replace(tmp, lib_path)
        except (subprocess.SubprocessError, FileNotFoundError, OSError):
            continue
        # Builds of other source versions are dead weight from here on.
        for old in glob.glob(os.path.join(_DIR, "libimagent_io.*.so")):
            if old != lib_path:
                try:
                    os.unlink(old)
                except OSError:
                    pass
        return True
    try:
        os.unlink(tmp)
    except OSError:
        pass
    return False


def _load() -> ctypes.CDLL | None:
    global _lib, _load_failed
    with _lock:
        if _lib is not None or _load_failed:
            return _lib
        lib = None
        try:
            lib_path = _lib_path()
            if os.path.exists(lib_path) or _build(lib_path):
                lib = ctypes.CDLL(lib_path)
        except OSError:  # unreadable source or unloadable binary
            lib = None
        if lib is None or _abi_version(lib) != _ABI_VERSION:
            # No toolchain/headers, or a source whose il_version()
            # disagrees with this binding: fail over to the PIL path
            # rather than corrupting memory.
            _load_failed = True
            return None
        lib.il_decode_resize_batch.restype = ctypes.c_int64
        lib.il_decode_resize_batch.argtypes = [
            ctypes.POINTER(ctypes.c_char_p), ctypes.c_int64, ctypes.c_int,
            ctypes.POINTER(ctypes.c_float), ctypes.POINTER(ctypes.c_float),
            ctypes.POINTER(ctypes.c_float), ctypes.POINTER(ctypes.c_uint64),
            ctypes.POINTER(ctypes.c_float), ctypes.POINTER(ctypes.c_uint8),
            ctypes.c_int,
        ]
        _lib = lib
        return _lib


def available() -> bool:
    """True once the native library is built and loadable."""
    return _load() is not None


def has_webp() -> bool:
    """Whether this build decodes webp natively (libwebp present at
    build time). Without it, webp members fall to the per-file PIL
    rescue — correct, just slower for webp-heavy datasets."""
    lib = _load()
    if lib is None:
        return False
    try:
        fn = lib.il_has_webp
    except AttributeError:
        return True  # pre-probe builds always linked libwebp
    fn.restype = ctypes.c_int
    fn.argtypes = []
    return bool(fn())


DEFAULT_AUG = (0.08, 1.0, 3.0 / 4.0, 4.0 / 3.0, 0.5)
"""torchvision RandomResizedCrop defaults + hflip p: (scale_min, scale_max,
ratio_min, ratio_max, hflip_prob)."""


def aug_params7(aug_params: tuple = DEFAULT_AUG) -> np.ndarray:
    """The 7-float C-side parameter block: the 5 public params plus
    fp32 log(ratio_min/max) precomputed HERE so no libm call enters the
    sampled stream — the C sampler and the PIL fallback's Python port
    (data/imagefolder.py::_sample_crop) then round identically."""
    p = np.asarray(aug_params, np.float32)
    if p.shape != (5,):
        raise ValueError(f"aug_params must be 5 floats, got {aug_params!r}")
    logs = np.log(p[2:4].astype(np.float64)).astype(np.float32)
    return np.ascontiguousarray(np.concatenate([p, logs]))


def decode_resize_batch(paths: list[str], size: int, mean, std,
                        n_threads: int = 0,
                        out: np.ndarray | None = None,
                        aug_seeds: np.ndarray | None = None,
                        aug_params: tuple = DEFAULT_AUG,
                        ) -> tuple[np.ndarray, np.ndarray]:
    """Decode+resize+normalize a batch of image files natively.

    Returns ``(images, ok)``: float32 (N, size, size, 3) and a bool mask of
    successfully decoded rows (failed rows are zero; the caller re-decodes
    those with PIL). ``out`` reuses a preallocated buffer across batches.

    ``aug_seeds`` (uint64, one per image) switches on RandomResizedCrop +
    horizontal flip with ``aug_params`` bounds; each image's crop is a pure
    function of its seed, so epochs are reproducible.
    """
    lib = _load()
    if lib is None:
        raise RuntimeError("native loader unavailable")
    n = len(paths)
    if out is None or out.shape != (n, size, size, 3):
        # np.empty, not zeros: every successfully decoded row is fully
        # written by the C side; failed rows are zeroed below. NOTE: when
        # batches are queued/prefetched, do NOT reuse one `out` across
        # calls — in-flight batches would alias it.
        out = np.empty((n, size, size, 3), np.float32)
    ok = np.zeros((n,), np.uint8)
    if n == 0:
        return out, ok.astype(bool)
    c_paths = (ctypes.c_char_p * n)(
        *[os.fsencode(p) for p in paths])
    mean_a = np.ascontiguousarray(mean, np.float32)
    std_a = np.ascontiguousarray(std, np.float32)
    if aug_seeds is not None:
        if len(aug_seeds) != n:
            raise ValueError(f"{len(aug_seeds)} seeds for {n} images")
        seeds_a = np.ascontiguousarray(aug_seeds, np.uint64)
        params_a = aug_params7(aug_params)
        c_seeds = seeds_a.ctypes.data_as(ctypes.POINTER(ctypes.c_uint64))
        c_params = params_a.ctypes.data_as(ctypes.POINTER(ctypes.c_float))
    else:
        c_seeds = None
        c_params = None
    lib.il_decode_resize_batch(
        c_paths, n, size,
        mean_a.ctypes.data_as(ctypes.POINTER(ctypes.c_float)),
        std_a.ctypes.data_as(ctypes.POINTER(ctypes.c_float)),
        c_params, c_seeds,
        out.ctypes.data_as(ctypes.POINTER(ctypes.c_float)),
        ok.ctypes.data_as(ctypes.POINTER(ctypes.c_uint8)),
        int(n_threads))
    okb = ok.astype(bool)
    if not okb.all():
        out[~okb] = 0.0
    return out, okb


RAW_MEAN = (0.0, 0.0, 0.0)
RAW_STD = (1.0 / 255.0,) * 3
"""Identity normalization constants. The C kernel folds the scaling
into ONE constant before touching pixels (``io_loader.cc`` —
``scale_c = inv255 / std``, then ``out = acc * scale_c + bias``): with
std exactly f32(1/255), ``scale_c == 1.0`` bit-exactly (x/x in IEEE)
and mean 0 makes the bias -0.0 — so the output is the raw resampled
value in [0, 255], untouched. It is still FRACTIONAL (triangle-filter
output); ``decode_batch_uint8``'s rint is the actual quantization, not
error cleanup."""


def decode_batch_uint8(paths: list[str], size: int, n_threads: int = 0,
                       aug_seeds: np.ndarray | None = None,
                       aug_params: tuple = DEFAULT_AUG,
                       ) -> tuple[np.ndarray, np.ndarray]:
    """uint8 wire-format decode: the same native kernel driven with the
    identity constants above, rounded to uint8 — the canonical host-side
    batch format (``data/pipeline.py::Batch``). Normalization moved
    in-graph (``train.make_input_prep``), so nothing downstream of the
    decoder ever needs float pixels on the host."""
    out, ok = decode_resize_batch(paths, size, RAW_MEAN, RAW_STD,
                                  n_threads=n_threads, aug_seeds=aug_seeds,
                                  aug_params=aug_params)
    # Round-to-nearest like PIL's own uint8 resample output; the clip
    # guards fp dust at the range edges (taps are convex weights).
    np.rint(out, out)
    np.clip(out, 0.0, 255.0, out=out)
    return out.astype(np.uint8), ok


def sample_crop(w: int, h: int, seed: int,
                aug_params: tuple = DEFAULT_AUG) -> tuple:
    """The C sampler's (x, y, cw, ch, flip) for one (size, seed) — the
    ground truth the PIL fallback's Python port is parity-tested
    against (tests/test_native_io.py)."""
    lib = _load()
    if lib is None:
        raise RuntimeError("native loader unavailable")
    params = aug_params7(aug_params)
    out = np.zeros((5,), np.float32)
    lib.il_sample_crop(
        ctypes.c_int(w), ctypes.c_int(h),
        params.ctypes.data_as(ctypes.POINTER(ctypes.c_float)),
        ctypes.c_uint64(seed),
        out.ctypes.data_as(ctypes.POINTER(ctypes.c_float)))
    return (int(out[0]), int(out[1]), int(out[2]), int(out[3]),
            bool(out[4] > 0.5))
