"""Native C++ data-runtime parity tests (native/src/dpt_native.cpp).

Every native entry point must agree byte-for-byte with its NumPy fallback —
the same role the reference delegates to DataLoader workers + torchvision C++
ops (/root/reference/train_ddp.py:131-148; SURVEY.md §2b).
"""

import numpy as np
import pytest

from distributed_pytorch_training_tpu import native
from distributed_pytorch_training_tpu.data import ShardedLoader
from distributed_pytorch_training_tpu.data.datasets import (
    synthetic_image_dataset,
)

pytestmark = pytest.mark.skipif(
    not native.is_available(), reason="native toolchain unavailable")


def test_chw_to_hwc_matches_numpy():
    rec = np.random.RandomState(0).randint(0, 256, (33, 3 * 32 * 32)).astype(np.uint8)
    got = native.chw_to_hwc_u8(rec, 3, 32, 32)
    want = rec.reshape(-1, 3, 32, 32).transpose(0, 2, 3, 1)
    assert np.array_equal(got, want)


def test_gather_rows_matches_fancy_index():
    src = np.random.RandomState(1).randint(0, 256, (200, 8, 8, 3)).astype(np.uint8)
    idx = np.random.RandomState(2).randint(0, 200, 77)
    assert np.array_equal(native.gather_rows(src, idx), src[idx])


def test_gather_rows_non_uint8_dtypes():
    """The gather is byte-wise: int32 token rows and float32 rows round-trip
    exactly (TokenLoader depends on this)."""
    for dtype in (np.int32, np.float32, np.uint16):
        src = (np.random.RandomState(3).rand(50, 12) * 100).astype(dtype)
        idx = np.random.RandomState(4).randint(0, 50, 31)
        assert np.array_equal(native.gather_rows(src, idx), src[idx]), dtype


def test_permutation_is_deterministic_permutation():
    p = native.permutation(42, 5000)
    assert np.array_equal(np.sort(p), np.arange(5000))
    assert np.array_equal(p, native.permutation(42, 5000))
    assert not np.array_equal(p, native.permutation(43, 5000))


def test_permutation_python_fallback_bit_identical():
    """Toolchain-less hosts must shuffle identically to native hosts (multi-
    host shard consistency): the Python mirror follows the same splitmix64
    Fisher-Yates stream."""
    for seed, n in ((42, 1), (42, 257), (7, 4096)):
        assert np.array_equal(native.permutation(seed, n),
                              native._permutation_py(seed, n))


def test_prefetcher_yields_exact_batches_in_order():
    images = np.random.RandomState(3).randint(0, 256, (100, 4, 4, 3)).astype(np.uint8)
    labels = np.random.RandomState(4).randint(0, 10, 100).astype(np.int32)
    steps, batch = 9, 16
    idx = np.random.RandomState(5).randint(0, 100, (steps, batch)).astype(np.int64)
    w = np.random.RandomState(6).rand(steps, batch).astype(np.float32)
    pf = native.NativePrefetcher(images, labels, idx, w, depth=2)
    for t, (img, lab, weight) in enumerate(pf):
        assert np.array_equal(img, images[idx[t]])
        assert np.array_equal(lab, labels[idx[t]])
        assert np.allclose(weight, w[t])
    assert t == steps - 1


def test_prefetcher_early_close_does_not_hang():
    images = np.zeros((50, 4, 4, 3), np.uint8)
    labels = np.zeros(50, np.int32)
    idx = np.zeros((20, 8), np.int64)
    w = np.ones((20, 8), np.float32)
    pf = native.NativePrefetcher(images, labels, idx, w, depth=2)
    assert pf.next() is not None
    pf.close()
    assert pf.next() is None


def test_loader_native_path_matches_python_path(mesh8):
    """ShardedLoader output is identical whether batches come from the C++
    prefetcher or the Python fallback (same sampler plan, same arrays)."""
    ds = synthetic_image_dataset(70, (8, 8), 4, seed=0)
    loader = ShardedLoader(ds, mesh8, per_device_batch=4, shuffle=True, seed=7)

    native_batches = [
        {k: np.asarray(v) for k, v in b.items()}
        for b in loader._native_epoch(epoch=1)
    ]
    python_batches = [
        {k: np.asarray(v) for k, v in b.items()}
        for b in loader._python_epoch(epoch=1)
    ]
    assert len(native_batches) == len(python_batches) == len(loader)
    for nb, pb in zip(native_batches, python_batches):
        for k in ("image", "label", "weight"):
            assert np.array_equal(nb[k], pb[k]), k


def test_library_file_name_carries_the_source_hash():
    """mtimes mean nothing after a tree copy: the built file is named by a
    hash of dpt_native.cpp, so a stale or foreign .so left in lib/ is never
    the one that loads."""
    import hashlib

    digest = hashlib.sha256(native._SRC.read_bytes()).hexdigest()[:16]
    assert native.lib_path().name == f"libdpt_native-{digest}.so"
    assert native.lib_path().exists()  # is_available() built exactly it
    assert native.describe() == native.lib_path().name
