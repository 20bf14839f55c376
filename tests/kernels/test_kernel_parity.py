"""Exhaustive interpret-mode parity sweeps: the standalone Pallas kernels
(`seg_boundary_pallas`, `radix_histogram_pallas`) against their pure-jnp
oracles in `ref.py`, over shapes, block sizes, key widths and adversarial
inputs (all-equal, all-distinct, single-block, boundary digits)."""
import jax.numpy as jnp
import numpy as np
import pytest

from repro.kernels import ref
from repro.kernels.radix_hist import radix_histogram_pallas
from repro.kernels.seg_boundary import seg_boundary_pallas


def _sorted_rows(rng, n, W, lo=0, hi=5):
    rows = rng.integers(lo, hi, (n, W)).astype(np.int32)
    order = np.lexsort(tuple(rows[:, c] for c in range(W - 1, -1, -1)))
    return rows[order]


def _assert_seg_parity(rows, block, num_keys=None):
    rows = jnp.asarray(rows)
    f, c, t = seg_boundary_pallas(rows, num_keys=num_keys, block=block,
                                  interpret=True)
    rf, rc, rt = ref.seg_boundary_ref(rows, num_keys=num_keys, block=block)
    np.testing.assert_array_equal(np.asarray(f), np.asarray(rf))
    np.testing.assert_array_equal(np.asarray(c), np.asarray(rc))
    np.testing.assert_array_equal(np.asarray(t), np.asarray(rt))


@pytest.mark.parametrize("n,W,block", [
    (256, 1, 64), (512, 3, 128), (1024, 4, 256), (2048, 2, 512),
    (512, 5, 512),            # single block: n == block
    (128, 8, 32),             # wide rows, small blocks
])
def test_seg_boundary_shape_sweep(n, W, block):
    rng = np.random.default_rng(n * W + block)
    _assert_seg_parity(_sorted_rows(rng, n, W), block)


@pytest.mark.parametrize("num_keys", [1, 2, 3])
def test_seg_boundary_num_keys_prefix(num_keys):
    # only the first num_keys columns participate in the boundary test;
    # trailing columns differ everywhere and must be ignored
    rng = np.random.default_rng(num_keys)
    rows = _sorted_rows(rng, 512, 4, hi=3)
    rows[:, 3] = np.arange(512, dtype=np.int32)
    _assert_seg_parity(rows, block=128, num_keys=num_keys)


def test_seg_boundary_all_equal_rows():
    rows = np.full((1024, 3), 7, np.int32)
    _assert_seg_parity(rows, block=256)
    f, _c, t = seg_boundary_pallas(jnp.asarray(rows), block=256,
                                   interpret=True)
    # one boundary per block (block-local convention), nothing else
    assert int(np.asarray(f).sum()) == 1024 // 256
    np.testing.assert_array_equal(np.asarray(t), np.ones(4, np.int32))


def test_seg_boundary_all_distinct_rows():
    rows = np.arange(512, dtype=np.int32)[:, None] * np.ones((1, 2), np.int32)
    _assert_seg_parity(rows, block=128)
    f, c, t = seg_boundary_pallas(jnp.asarray(rows), block=128,
                                  interpret=True)
    assert int(np.asarray(f).sum()) == 512          # every row a boundary
    np.testing.assert_array_equal(np.asarray(t), np.full(4, 128, np.int32))


def _assert_hist_parity(digits, n_bins, block):
    digits = jnp.asarray(digits, jnp.int32)
    got = np.asarray(radix_histogram_pallas(digits, n_bins, block=block,
                                            interpret=True))
    want = np.asarray(ref.radix_histogram_ref(digits, n_bins, block))
    np.testing.assert_array_equal(got, want)
    # blockwise sums must also agree with the global histogram
    np.testing.assert_array_equal(
        got.sum(axis=0), np.bincount(np.asarray(digits), minlength=n_bins))


@pytest.mark.parametrize("n,bins,block", [
    (1024, 256, 256), (2048, 8, 1024), (512, 2, 128), (4096, 128, 512),
    (256, 16, 256),           # single block: n == block
    (128, 1, 64),             # degenerate single-bin histogram
])
def test_radix_histogram_shape_sweep(n, bins, block):
    rng = np.random.default_rng(n + bins + block)
    _assert_hist_parity(rng.integers(0, bins, n), bins, block)


def test_radix_histogram_constant_digits():
    _assert_hist_parity(np.full(1024, 5, np.int32), 8, 256)


def test_radix_histogram_boundary_digits():
    # digits pinned to the first/last bin — one-hot edge columns
    d = np.where(np.arange(2048) % 2 == 0, 0, 255).astype(np.int32)
    _assert_hist_parity(d, 256, 512)


def test_radix_histogram_skewed_blocks():
    # each block holds a single distinct digit: per-block rows are one-hot
    d = np.repeat(np.arange(8, dtype=np.int32), 256)
    got = np.asarray(radix_histogram_pallas(jnp.asarray(d), 8, block=256,
                                            interpret=True))
    np.testing.assert_array_equal(got, np.eye(8, dtype=np.int32) * 256)
