"""Native (C++/ctypes) data-loader kernels: parity with NumPy, bounds
safety, determinism, and integration through ArrayDataset."""

import numpy as np
import pytest

from distributed_training_tpu import native
from distributed_training_tpu.data import ArrayDataset, SyntheticLMDataset


def test_native_builds():
    """The toolchain is part of the environment contract — the native
    path must actually compile here, not silently fall back."""
    assert native.available()


@pytest.mark.parametrize("dtype,shape", [
    (np.float32, (64, 20)),
    (np.int32, (64, 128)),
    (np.float64, (33, 7, 3)),
    (np.uint8, (50, 11)),
    (np.float32, (16,)),  # 1-D rows (scalar per row)
])
def test_gather_matches_numpy(dtype, shape):
    rng = np.random.default_rng(0)
    src = (rng.random(shape) * 100).astype(dtype)
    idx = rng.integers(0, shape[0], size=37)
    np.testing.assert_array_equal(native.gather_rows(src, idx), src[idx])


def test_gather_large_multithreaded():
    """Cross the 1 MiB single-thread cutoff so the threaded path runs."""
    rng = np.random.default_rng(1)
    src = rng.random((4096, 512), dtype=np.float32)
    idx = rng.integers(0, 4096, size=2048)
    np.testing.assert_array_equal(
        native.gather_rows(src, idx, n_threads=7), src[idx])


def test_gather_negative_indices_wrap_like_numpy():
    src = np.arange(32, dtype=np.float32).reshape(8, 4)
    idx = np.array([-1, 0, -8, 3])
    np.testing.assert_array_equal(native.gather_rows(src, idx), src[idx])


def test_gather_out_of_range_raises_both_paths(monkeypatch):
    src = np.zeros((8, 4), np.float32)
    for oor in ([0, 8], [-9]):
        with pytest.raises(IndexError):
            native.gather_rows(src, np.array(oor))
    monkeypatch.setattr(native, "_load", lambda: None)
    for oor in ([0, 8], [-9]):
        with pytest.raises(IndexError):
            native.gather_rows(src, np.array(oor))


def test_gather_fallback_path_identical(monkeypatch):
    """With the library forced off, results must be byte-identical —
    ArrayDataset semantics cannot depend on whether g++ exists."""
    src = np.random.default_rng(2).random((64, 8), dtype=np.float32)
    idx = np.array([5, -1, 0, 63, -64])
    want = native.gather_rows(src, idx)
    monkeypatch.setattr(native, "_load", lambda: None)
    np.testing.assert_array_equal(native.gather_rows(src, idx), want)


def test_gather_multidim_index_falls_back_to_numpy():
    src = np.arange(40, dtype=np.int32).reshape(10, 4)
    idx = np.array([[1, 2], [3, 4]])
    got = native.gather_rows(src, idx)
    assert got.shape == (2, 2, 4)
    np.testing.assert_array_equal(got, src[idx])


def test_gather_noncontiguous_source():
    big = np.random.default_rng(3).random((32, 20), dtype=np.float32)
    view = big[:, ::2]  # non-contiguous column view
    idx = np.array([0, 7, 7, 31])
    np.testing.assert_array_equal(native.gather_rows(view, idx),
                                  view[idx])


def test_fill_tokens_thread_count_independent():
    if not native.available():
        pytest.skip("no native library")
    a = native.fill_tokens(seed=7, vocab=50257, n=100_000, n_threads=1)
    b = native.fill_tokens(seed=7, vocab=50257, n=100_000, n_threads=8)
    np.testing.assert_array_equal(a, b)
    assert a.min() >= 0 and a.max() < 50257
    # Same seed → same stream; different seed → different stream.
    np.testing.assert_array_equal(
        a, native.fill_tokens(seed=7, vocab=50257, n=100_000))
    assert not np.array_equal(
        a, native.fill_tokens(seed=8, vocab=50257, n=100_000))


def test_synthetic_lm_dataset_deterministic():
    a = SyntheticLMDataset(size=8, seq_len=16, vocab_size=1000, seed=5)
    b = SyntheticLMDataset(size=8, seq_len=16, vocab_size=1000, seed=5)
    idx = np.arange(8)
    np.testing.assert_array_equal(a.batch(idx)["tokens"],
                                  b.batch(idx)["tokens"])
    tok = a.batch(idx)["tokens"]
    assert tok.shape == (8, 17) and tok.min() >= 0 and tok.max() < 1000


def test_array_dataset_uses_gather():
    x = np.arange(40, dtype=np.float32).reshape(10, 4)
    y = np.arange(10, dtype=np.float32).reshape(10, 1)
    ds = ArrayDataset(x=x, y=y)
    got = ds.batch(np.array([3, 1, 3]))
    np.testing.assert_array_equal(got["x"], x[[3, 1, 3]])
    np.testing.assert_array_equal(got["y"], y[[3, 1, 3]])


@pytest.mark.parametrize("n", [1, 100, 4096, 4097, 100_000])
def test_fill_tokens_numpy_fallback_bit_identical(n):
    """The NumPy fallback must replay the native SplitMix64 stream
    exactly — mixed native-availability across pod hosts must never
    produce divergent per-host corpora (ADVICE.md round-1 medium)."""
    assert native.available()
    a = native.fill_tokens(seed=7, vocab=50257, n=n)
    b = native._fill_tokens_numpy(seed=7, vocab=50257, n=n)
    np.testing.assert_array_equal(a, b)
    # Negative / huge seeds hit the uint64 wrap paths.
    for seed in (-3, 2**63 + 11):
        np.testing.assert_array_equal(
            native.fill_tokens(seed=seed, vocab=997, n=5000),
            native._fill_tokens_numpy(seed=seed, vocab=997, n=5000))


def test_fill_tokens_fallback_used_when_disabled(monkeypatch):
    """DTT_NATIVE_DISABLE forces the fallback through the public API."""
    import importlib

    import distributed_training_tpu.native as nat
    monkeypatch.setenv("DTT_NATIVE_DISABLE", "1")
    fresh = importlib.reload(nat)
    try:
        assert not fresh.available()
        got = fresh.fill_tokens(seed=11, vocab=1000, n=9000)
    finally:
        monkeypatch.delenv("DTT_NATIVE_DISABLE")
        importlib.reload(nat)
    expect = nat.fill_tokens(seed=11, vocab=1000, n=9000)
    assert nat.available()
    np.testing.assert_array_equal(got, expect)


def test_library_is_keyed_on_flags_as_well_as_source(monkeypatch):
    """A binary left in the tree by another build recipe (the old
    -march=native one, say) must not be the one that loads: the cached
    file's name covers the compiler flags, which are portable ones."""
    assert "-march=native" not in native._CXX_FLAGS
    before = native._lib_path()
    monkeypatch.setattr(native, "_CXX_FLAGS",
                        [*native._CXX_FLAGS, "-march=native"])
    assert native._lib_path() != before
