"""Per-kernel interpret-mode validation: sweep shapes/dtypes, assert against
the pure-jnp ref.py oracles."""
import jax.numpy as jnp
import numpy as np
import pytest

from repro.kernels import ref
from repro.kernels.ops import (bitonic_sort, bitonic_stage, dense_rank_sorted,
                               radix_histogram)


@pytest.mark.parametrize("n,bins,block", [
    (2048, 256, 1024), (1024, 16, 256), (4096, 64, 512), (999, 8, 128),
    (128, 2, 128),
])
def test_radix_histogram(n, bins, block):
    rng = np.random.default_rng(n + bins)
    d = jnp.asarray(rng.integers(0, bins, n), jnp.int32)
    got = np.asarray(radix_histogram(d, bins, block=block, interpret=True))
    want = np.bincount(np.asarray(d), minlength=bins)
    assert np.array_equal(got, want)


def test_radix_histogram_matches_blockwise_ref():
    rng = np.random.default_rng(0)
    d = jnp.asarray(rng.integers(0, 32, 2048), jnp.int32)
    from repro.kernels.radix_hist import radix_histogram_pallas
    got = np.asarray(radix_histogram_pallas(d, 32, block=512, interpret=True))
    want = np.asarray(ref.radix_histogram_ref(d, 32, 512))
    assert np.array_equal(got, want)


@pytest.mark.parametrize("n,W,tile", [(256, 3, 64), (512, 5, 128),
                                      (1024, 2, 256), (128, 8, 32)])
def test_bitonic_stage_sweep(n, W, tile):
    rng = np.random.default_rng(n * W)
    rows = rng.integers(-4, 9, (n, W)).astype(np.int32)
    rows[:, -1] = rng.permutation(n)
    k = 2
    while k <= n:
        j = k // 2
        while j >= 1:
            got = bitonic_stage(jnp.asarray(rows), int(k), int(j), tile=tile,
                                interpret=True)
            want = ref.bitonic_stage_ref(jnp.asarray(rows), int(k), int(j))
            assert np.array_equal(np.asarray(got), np.asarray(want)), (k, j)
            j //= 4 if j >= 4 else 2          # sparse sweep for speed
        k *= 4
    # full sort end-to-end
    out = bitonic_sort(jnp.asarray(rows), tile=tile, interpret=True)
    want = ref.bitonic_sort_ref(jnp.asarray(rows))
    assert np.array_equal(np.asarray(out), np.asarray(want))


@pytest.mark.parametrize("n,W,block", [(1000, 3, 128), (512, 2, 512),
                                       (77, 4, 32), (4096, 1, 1024)])
def test_dense_rank_sweep(n, W, block):
    rng = np.random.default_rng(n + W)
    rows = rng.integers(0, 5, (n, W)).astype(np.int32)
    order = np.lexsort(tuple(rows[:, c] for c in range(W - 1, -1, -1)))
    rows = rows[order]
    got, ndist = dense_rank_sorted(jnp.asarray(rows), block=block,
                                    interpret=True)
    b = np.ones(n, bool)
    b[1:] = np.any(rows[1:] != rows[:-1], axis=1)
    want = np.cumsum(b) - 1
    assert np.array_equal(np.asarray(got), want)
    assert int(ndist) == want[-1] + 1


def test_seg_boundary_kernel_matches_ref():
    rng = np.random.default_rng(9)
    rows = np.sort(rng.integers(0, 4, (1024, 3)).astype(np.int32), axis=0)
    from repro.kernels.seg_boundary import seg_boundary_pallas
    f, c, t = seg_boundary_pallas(jnp.asarray(rows), block=256,
                                  interpret=True)
    rf, rc, rt = ref.seg_boundary_ref(jnp.asarray(rows), block=256)
    assert np.array_equal(np.asarray(f), np.asarray(rf))
    assert np.array_equal(np.asarray(c), np.asarray(rc))
    assert np.array_equal(np.asarray(t), np.asarray(rt))
