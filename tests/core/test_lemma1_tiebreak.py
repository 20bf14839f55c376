"""Host Lemma-1 tie-break: each tie group sorted at its own width.

`_lambda_tiebreak_bucketed` runs the lane-parallel bitonic network once
per power-of-two width class. Its permutation must be the single-width
network's (`_lambda_tiebreak_host` at the widest group's width) and the
brute-force Lemma-1 order of every group, with groups in slot order.
"""
import functools

import numpy as np
import pytest

from repro.core.bitonic import next_pow2
from repro.core.dcv_jax import (_lambda_tiebreak_bucketed,
                                _lambda_tiebreak_host)
from repro.core.difference_cover import cover_tables
from repro.core.oracle import suffix_array_doubling

#: group sizes of each residue layout; "mixed" mirrors a shard's level 0:
#: mostly pairs, some groups of 3-7, one of 9-16.
LAYOUTS = {
    "mixed": [2] * 60 + [3] * 9 + [4, 5, 6, 7, 4, 3] + [13],
    "one_width": [3, 4, 4, 3, 4, 3, 3],
    "one_group": [11],
}


def _residue(v, sizes, seed):
    """A tie residue of a random token text: groups of `sizes` suffixes
    that share their first v tokens (a pattern planted in disjoint blocks
    of 2v, at any class), in contiguous slots, and the text's sample ranks
    (the sample suffixes' order, -1 elsewhere)."""
    tabs = cover_tables(v)
    rng = np.random.default_rng(seed)
    blocks = 4 * sum(sizes)
    n_v = blocks * 2 * v
    x = rng.integers(0, 50, size=n_v + 2 * v)
    p = (rng.choice(blocks, size=sum(sizes), replace=False) * 2 * v
         + rng.integers(0, v, size=sum(sizes)))
    row_of = np.repeat(np.arange(len(sizes)), sizes)
    for g in range(len(sizes)):
        x[p[row_of == g, None] + np.arange(v)] = rng.integers(0, 50, size=v)
    inv = np.empty(len(x), dtype=np.int64)
    inv[suffix_array_doubling(x)] = np.arange(len(x))
    sample = np.flatnonzero(tabs.in_D[np.arange(n_v + v) % v])
    rank = np.full(n_v + v, -1, dtype=np.int64)
    rank[sample[np.argsort(inv[sample])]] = np.arange(len(sample))

    lane = np.concatenate([np.arange(s) for s in sizes])
    klass = p % v
    rvals = rank[p[:, None] + tabs.shifts[klass]]
    return tabs, rank, p, lane, row_of, rvals, klass


def _brute_force(tabs, rank, p, sizes):
    """Each group sorted by `sorted` with the paper's Lemma-1 comparison."""
    def lemma1_cmp(i, j):
        lam = int(tabs.lam[i % tabs.v, j % tabs.v])
        ri, rj = rank[i + lam], rank[j + lam]
        if ri != rj:
            return -1 if ri < rj else 1
        return (i > j) - (i < j)

    out, start = [], 0
    for s in sizes:
        out += sorted(p[start:start + s].tolist(),
                      key=functools.cmp_to_key(lemma1_cmp))
        start += s
    return np.asarray(out)


@pytest.mark.parametrize("layout", sorted(LAYOUTS))
@pytest.mark.parametrize("v", [3, 4, 7, 13])
def test_bucketed_tiebreak_matches_single_width_and_brute_force(v, layout):
    sizes = LAYOUTS[layout]
    tabs, rank, p, lane, row_of, rvals, klass = _residue(v, sizes, seed=v)
    args = (rvals, klass, tabs.lam_idx1, tabs.lam_idx2)

    got, slots = _lambda_tiebreak_bucketed(p, lane, row_of, len(sizes),
                                           *args)
    g2 = next_pow2(max(sizes))
    single = _lambda_tiebreak_host(p, lane, row_of, len(sizes), g2, *args)

    np.testing.assert_array_equal(got, single)
    np.testing.assert_array_equal(got, _brute_force(tabs, rank, p, sizes))
    assert slots == sum(max(2, next_pow2(s)) for s in sizes)
    if layout == "mixed":
        assert slots < len(sizes) * g2


def test_bucketed_tiebreak_index_breaks_equal_ranks():
    """Equal Lemma-1 ranks fall back to the position, as on the device."""
    tabs = cover_tables(3)
    p = np.array([9, 3, 6, 0, 12, 15], dtype=np.int64)
    klass = p % 3
    rvals = np.zeros((len(p), tabs.shifts.shape[1]), dtype=np.int64)
    row_of = np.array([0, 0, 0, 1, 1, 2])
    lane = np.array([0, 1, 2, 0, 1, 0])
    got, slots = _lambda_tiebreak_bucketed(
        p, lane, row_of, 3, rvals, klass, tabs.lam_idx1, tabs.lam_idx2)
    assert got.tolist() == [3, 6, 9, 0, 12, 15]
    assert slots == 4 + 2 + 2
