"""Native batch assembler: correctness vs numpy, determinism, fallback."""

import numpy as np

from distkeras_tpu.data import native
from distkeras_tpu.data.dataset import synthetic_mnist


def test_native_available_with_toolchain():
    # this image ships g++; the native path must build and load
    assert native.available()


def test_library_is_named_by_the_hash_of_its_source(tmp_path, monkeypatch):
    """The binary that loads is the one built from THIS source: its name
    carries the source's SHA-256, so editing the source (or finding a
    stale/foreign libdkbatch.so beside it) builds anew instead of
    loading the wrong code."""
    import hashlib
    import os
    import shutil

    src = tmp_path / "batcher.cc"
    shutil.copy(native._SRC, src)
    (tmp_path / "libdkbatch.so").write_bytes(b"not a library")  # foreign
    monkeypatch.setattr(native, "_SRC", str(src))
    first = native._build()
    digest = hashlib.sha256(src.read_bytes()).hexdigest()[:16]
    assert os.path.basename(first) == f"libdkbatch-{digest}.so"
    src.write_text(src.read_text() + "\n// edited\n")
    second = native._build()
    assert second != first and os.path.exists(second)
    assert not list(tmp_path.glob("*.tmp"))


def test_gather_rows_matches_numpy():
    rng = np.random.default_rng(0)
    for shape, dtype in [((1000, 784), np.float32), ((257, 3, 5), np.int32),
                         ((64,), np.float64)]:
        src = (rng.standard_normal(shape) * 100).astype(dtype)
        idx = rng.integers(0, shape[0], 513).astype(np.int64)
        out = native.gather_rows(src, idx)
        np.testing.assert_array_equal(out, src[idx])
        assert out.dtype == src.dtype


def test_gather_rows_bounds_checked():
    import pytest

    src = np.zeros((10, 4), np.float32)
    with pytest.raises(IndexError):
        native.gather_rows(src, np.array([0, 10], np.int64))
    with pytest.raises(IndexError):
        native.gather_rows(src, np.array([-1], np.int64))


def test_native_permutation_valid_and_deterministic():
    p1 = native.permutation(10_001, seed=42)
    p2 = native.permutation(10_001, seed=42)
    p3 = native.permutation(10_001, seed=43)
    np.testing.assert_array_equal(p1, p2)
    assert not np.array_equal(p1, p3)
    np.testing.assert_array_equal(np.sort(p1), np.arange(10_001))


def test_dataset_shuffle_uses_same_indices_as_numpy_path():
    """Dataset.shuffle numerics must not depend on the native path: indices
    come from utils.rng either way."""
    ds = synthetic_mnist(n=512)
    a = ds.shuffle(7)
    from distkeras_tpu.utils import rng as rng_lib

    perm = rng_lib.permutation(7, 512)
    np.testing.assert_array_equal(a["features"], ds["features"][perm])
