"""ctypes bindings for the C++ host data runtime (`src/dpt_native.cpp`).

The native library is the TPU-side stand-in for the C++ machinery the
reference gets from its dependency stack — DataLoader worker prefetch and
image-op decode (/root/reference/train_ddp.py:131-148; SURVEY.md §2b). It is
built lazily with g++ on first use and cached next to the sources under a
file name that carries the source's hash (so a stale or foreign ``.so`` left
in ``lib/`` by a copy of the tree is never loaded); every entry point has a
NumPy fallback so the framework keeps working where no toolchain exists.
`is_available()` reports which path is live, and the first load logs it.

Set ``DPT_TPU_NATIVE=0`` to force the NumPy fallbacks (used by parity tests).
"""

from __future__ import annotations

import ctypes
import hashlib
import logging
import os
import subprocess
import tempfile
import threading
from pathlib import Path
from typing import Optional, Tuple

import numpy as np

_SRC = Path(__file__).parent / "src" / "dpt_native.cpp"
_LIB_DIR = Path(__file__).parent / "lib"

logger = logging.getLogger(__name__)

_lock = threading.Lock()
_lib: Optional[ctypes.CDLL] = None
_tried = False


def lib_path() -> Path:
    """``lib/libdpt_native-<sha256(source)[:16]>.so``: the name IS the
    staleness check — mtimes mean nothing after a tree copy."""
    digest = hashlib.sha256(_SRC.read_bytes()).hexdigest()[:16]
    return _LIB_DIR / f"libdpt_native-{digest}.so"


def _build(lib: Path) -> Optional[str]:
    """Compile the shared library unless the file for this source already
    exists. Returns None on success, else why it could not be built."""
    if lib.exists():
        return None
    try:
        _LIB_DIR.mkdir(parents=True, exist_ok=True)
        # Build to a temp name, then atomic-rename: concurrent processes
        # (multi-host launch) race benignly.
        fd, tmp = tempfile.mkstemp(suffix=".so", dir=_LIB_DIR)
        os.close(fd)
        try:
            cmd = [
                "g++", "-O3", "-std=c++17", "-shared", "-fPIC", "-pthread",
                str(_SRC), "-o", tmp,
            ]
            res = subprocess.run(cmd, capture_output=True, text=True,
                                 timeout=120)
            if res.returncode != 0:
                return f"g++ rc={res.returncode}: {res.stderr.strip()[-200:]}"
            os.replace(tmp, lib)
            return None
        finally:
            Path(tmp).unlink(missing_ok=True)
    except (OSError, subprocess.SubprocessError) as e:
        return f"{type(e).__name__}: {e}"


def _load() -> Optional[ctypes.CDLL]:
    global _lib, _tried
    if _lib is not None:
        return _lib
    if _tried:
        return None
    with _lock:
        if _lib is not None or _tried:
            return _lib
        _tried = True
        if os.environ.get("DPT_TPU_NATIVE", "1") == "0":
            logger.info("native data library: off (DPT_TPU_NATIVE=0), "
                        "NumPy path")
            return None
        path = lib_path()
        problem = _build(path)
        if problem is None:
            try:
                lib = ctypes.CDLL(str(path))
            except OSError as e:
                problem = f"dlopen failed: {e}"
        if problem is not None:
            logger.warning("native data library unavailable (%s) — NumPy "
                           "path", problem)
            return None
        logger.info("native data library: %s", path.name)

        u8p = ctypes.POINTER(ctypes.c_uint8)
        i32p = ctypes.POINTER(ctypes.c_int32)
        i64p = ctypes.POINTER(ctypes.c_int64)
        f32p = ctypes.POINTER(ctypes.c_float)
        i32, i64, u64 = ctypes.c_int32, ctypes.c_int64, ctypes.c_uint64

        lib.dpt_version.restype = i32
        lib.dpt_chw_to_hwc_u8.argtypes = [u8p, u8p, i64, i64, i64, i32]
        lib.dpt_gather_rows_u8.argtypes = [u8p, i64p, u8p, i64, i64, i32]
        lib.dpt_permutation.argtypes = [u64, i64, i64p]
        lib.dpt_prefetch_create.argtypes = [u8p, i32p, i64, i64p, f32p,
                                            i64, i64, i32, i32]
        lib.dpt_prefetch_create.restype = ctypes.c_void_p
        lib.dpt_prefetch_next.argtypes = [ctypes.c_void_p, u8p, i32p, f32p]
        lib.dpt_prefetch_next.restype = i64
        lib.dpt_prefetch_destroy.argtypes = [ctypes.c_void_p]
        _lib = lib
        return _lib


def is_available() -> bool:
    return _load() is not None


def describe() -> str:
    """Which host data path is live, for banners: the built library's file
    name, or ``numpy``."""
    return lib_path().name if is_available() else "numpy"


def _ptr(a: np.ndarray, ctype):
    return a.ctypes.data_as(ctypes.POINTER(ctype))


_THREADS = max(1, min(8, (os.cpu_count() or 1)))


def chw_to_hwc_u8(records: np.ndarray, c: int, h: int, w: int) -> np.ndarray:
    """(N, c*h*w) planar uint8 records -> (N, h, w, c) interleaved images.

    The per-record decode torchvision's C++ ops do for the reference's
    CIFAR pickle batches (ref :103-108)."""
    records = np.ascontiguousarray(records, np.uint8)
    n = records.shape[0]
    lib = _load()
    if lib is None:
        return (records.reshape(n, c, h, w).transpose(0, 2, 3, 1)
                .copy())
    out = np.empty((n, h, w, c), np.uint8)
    lib.dpt_chw_to_hwc_u8(_ptr(records, ctypes.c_uint8),
                          _ptr(out, ctypes.c_uint8),
                          n, c, h * w, _THREADS)
    return out


def gather_rows(src: np.ndarray, idx: np.ndarray) -> np.ndarray:
    """Batch assembly: rows of `src` at `idx` (NumPy fancy-index equivalent,
    parallel memcpy off the GIL). Any contiguous dtype — the copy is
    byte-wise, so int32 token rows work the same as uint8 image rows."""
    src = np.ascontiguousarray(src)
    lib = _load()
    # Only trivially-copyable numeric rows take the native memcpy path
    # (object arrays hold PyObject pointers — memcpy would skip refcounting).
    if lib is None or src.dtype.kind not in "biufc":
        return src[idx]
    idx = np.ascontiguousarray(idx, np.int64)
    if len(idx) and (idx.min() < 0 or idx.max() >= len(src)):
        # The C side is a raw memcpy with no bounds check; keep NumPy's
        # loud failure instead of reading out-of-bounds host memory.
        raise IndexError(
            f"gather_rows indices out of range [0, {len(src)}): "
            f"min={idx.min()}, max={idx.max()}")
    row_bytes = int(np.prod(src.shape[1:], dtype=np.int64)) * src.itemsize
    out = np.empty((len(idx), *src.shape[1:]), src.dtype)
    # byte-pointer cast is dtype-agnostic: row_bytes covers the full row
    lib.dpt_gather_rows_u8(_ptr(src, ctypes.c_uint8),
                           _ptr(idx, ctypes.c_int64),
                           _ptr(out, ctypes.c_uint8),
                           len(idx), row_bytes, _THREADS)
    return out


_M64 = 2 ** 64 - 1


def _permutation_py(seed: int, n: int) -> np.ndarray:
    """Pure-Python mirror of dpt_permutation — SAME splitmix64 Fisher-Yates
    stream, so toolchain-less hosts shuffle identically to native hosts
    (cross-host shard consistency depends on this)."""
    s = (seed ^ 0xDA3E39CB94B95BDB) & _M64

    def splitmix64():
        nonlocal s
        s = (s + 0x9E3779B97F4A7C15) & _M64
        z = s
        z = ((z ^ (z >> 30)) * 0xBF58476D1CE4E5B9) & _M64
        z = ((z ^ (z >> 27)) * 0x94D049BB133111EB) & _M64
        return z ^ (z >> 31)

    out = np.arange(n, dtype=np.int64)
    for i in range(n - 1, 0, -1):
        j = (splitmix64() * (i + 1)) >> 64  # Lemire bounded, as in C++
        out[i], out[j] = out[j], out[i]
    return out


def permutation(seed: int, n: int) -> np.ndarray:
    """Deterministic Fisher-Yates permutation (splitmix64 stream). Native and
    Python paths produce the identical permutation for a given seed."""
    lib = _load()
    if lib is None:
        return _permutation_py(seed, n)
    out = np.empty(n, np.int64)
    lib.dpt_permutation(seed & _M64, n, _ptr(out, ctypes.c_int64))
    return out


class NativePrefetcher:
    """Bounded-ring background batch assembly over a fixed epoch plan.

    Wraps the C++ Prefetcher: producer thread + thread-pool gather fill
    `depth` reusable buffers; `__iter__` yields fresh (image, label, weight)
    arrays in step order. The DataLoader(num_workers) role, ref :136."""

    def __init__(self, images: np.ndarray, labels: np.ndarray,
                 indices: np.ndarray, weights: np.ndarray,
                 depth: int = 3, threads: int = 0):
        lib = _load()
        if lib is None:
            raise RuntimeError("native library unavailable")
        if images.dtype != np.uint8 or images.ndim < 2:
            raise TypeError(
                f"NativePrefetcher serves uint8 image batches, got "
                f"dtype={images.dtype} ndim={images.ndim}")
        steps, batch = indices.shape
        if indices.size and (indices.min() < 0
                             or indices.max() >= len(images)):
            raise IndexError(
                f"prefetch indices out of range [0, {len(images)}): "
                f"min={indices.min()}, max={indices.max()}")
        self._lib = lib
        # keep references so the buffers outlive the C++ pointers
        self._images = np.ascontiguousarray(images)
        self._labels = np.ascontiguousarray(labels, np.int32)
        self._indices = np.ascontiguousarray(indices, np.int64)
        self._weights = np.ascontiguousarray(weights, np.float32)
        self.steps = int(steps)
        self.batch = int(batch)
        self.item_shape = images.shape[1:]
        self._row_bytes = (int(np.prod(self.item_shape, dtype=np.int64))
                           * self._images.itemsize)
        self._handle = lib.dpt_prefetch_create(
            _ptr(self._images, ctypes.c_uint8),
            _ptr(self._labels, ctypes.c_int32),
            self._row_bytes,
            _ptr(self._indices, ctypes.c_int64),
            _ptr(self._weights, ctypes.c_float),
            self.steps, self.batch, depth, threads or _THREADS)
        if not self._handle:
            raise RuntimeError("dpt_prefetch_create failed")

    def next(self) -> Optional[Tuple[np.ndarray, np.ndarray, np.ndarray]]:
        if self._handle is None:
            return None
        img = np.empty((self.batch, *self.item_shape), np.uint8)
        lab = np.empty(self.batch, np.int32)
        w = np.empty(self.batch, np.float32)
        t = self._lib.dpt_prefetch_next(
            self._handle, _ptr(img, ctypes.c_uint8),
            _ptr(lab, ctypes.c_int32), _ptr(w, ctypes.c_float))
        if t < 0:
            return None
        return img, lab, w

    def __iter__(self):
        try:
            while True:
                item = self.next()
                if item is None:
                    return
                yield item
        finally:
            self.close()

    def close(self):
        if self._handle is not None:
            self._lib.dpt_prefetch_destroy(self._handle)
            self._handle = None

    def __del__(self):
        try:
            self.close()
        except Exception:
            pass
