"""Property tests for the shard-local BSP primitives (pure, no mesh)."""
import jax.numpy as jnp
import numpy as np
from hypothesis import given, settings, strategies as st

from repro.bsp.exchange import hop_caps
from repro.bsp.primitives import (counts_per_bucket, group_running_counts,
                                  lower_bound, searchsorted_rows, take_cols,
                                  within_group_index)
from repro.bsp.psort import (pack_key_columns, packed_width, quantize_sigma,
                             resolve_bsp_sort_impl)
from repro.bsp.suffix_array import pack_window_columns


@given(st.lists(st.tuples(st.integers(0, 5), st.booleans()), min_size=1,
                max_size=120))
@settings(max_examples=60, deadline=None)
def test_within_group_index(items):
    group = np.array([g for g, _ in items], np.int32)
    valid = np.array([v for _, v in items], bool)
    out = np.asarray(within_group_index(jnp.asarray(group),
                                        jnp.asarray(valid), 6))
    seen: dict = {}
    for i, (g, v) in enumerate(items):
        if not v:
            assert out[i] == 0
            continue
        assert out[i] == seen.get(g, 0), (i, g)
        seen[g] = seen.get(g, 0) + 1


@given(st.integers(1, 200), st.integers(1, 16), st.integers(0, 100))
@settings(max_examples=40, deadline=None)
def test_hop_caps_bound_round_robin(m, p, seed):
    """The two-hop caps are sufficient for ANY destination pattern."""
    rng = np.random.default_rng(seed)
    dest = rng.integers(0, p, m)
    cap1, cap2 = hop_caps(m, p, cap_out=2 * m + 2 * p + 4)
    # hop 1: rows to intermediate q = (per-dest round robin)
    i_d = np.zeros(m, int)
    cnt: dict = {}
    for i, d in enumerate(dest):
        i_d[i] = cnt.get(d, 0)
        cnt[d] = cnt.get(d, 0) + 1
    inter = i_d % p
    assert np.bincount(inter, minlength=p).max() <= cap1


@given(st.integers(2, 40), st.integers(2, 9), st.integers(0, 50))
@settings(max_examples=40, deadline=None)
def test_pack_window_columns_preserves_order(n, v, seed):
    rng = np.random.default_rng(seed)
    sigma = int(rng.integers(2, 300))
    win = rng.integers(-1, sigma, (n, v)).astype(np.int32)
    packed = np.asarray(pack_window_columns(jnp.asarray(win.T), sigma)).T
    # lexicographic order identical before/after packing
    o1 = np.lexsort(tuple(win[:, c] for c in range(v - 1, -1, -1)))
    o2 = np.lexsort(tuple(packed[:, c]
                          for c in range(packed.shape[1] - 1, -1, -1)))
    k1 = [tuple(win[i]) for i in o1]
    k2 = [tuple(win[i]) for i in o2]
    assert k1 == k2           # same sorted key sequence (ties may permute)
    # equality is preserved exactly (injective packing)
    for i in range(min(n, 10)):
        for j in range(min(n, 10)):
            assert (tuple(win[i]) == tuple(win[j])) == \
                (tuple(packed[i]) == tuple(packed[j]))


@given(st.integers(2, 40), st.integers(1, 9), st.integers(0, 50),
       st.integers(-1, 4))
@settings(max_examples=40, deadline=None)
def test_pack_key_columns_generic_ranges(n, k, seed, lo):
    """The generic packer preserves lexicographic order and row equality
    for arbitrary [lo, hi] ranges, and its width matches `packed_width`."""
    rng = np.random.default_rng(seed)
    hi = lo + int(rng.integers(1, 500))
    cols = rng.integers(lo, hi + 1, (n, k)).astype(np.int32)
    packed = np.asarray(pack_key_columns(jnp.asarray(cols.T), lo, hi)).T
    assert packed.shape == (n, packed_width(k, lo, hi))
    assert packed.max(initial=0) < np.iinfo(np.int32).max
    o1 = np.lexsort(tuple(cols[:, c] for c in range(k - 1, -1, -1)))
    o2 = np.lexsort(tuple(packed[:, c]
                          for c in range(packed.shape[1] - 1, -1, -1)))
    assert [tuple(cols[i]) for i in o1] == [tuple(cols[i]) for i in o2]
    for i in range(min(n, 8)):
        for j in range(min(n, 8)):
            assert (tuple(cols[i]) == tuple(cols[j])) == \
                (tuple(packed[i]) == tuple(packed[j]))


@given(st.integers(0, 100_000), st.integers(1, 12))
@settings(max_examples=60, deadline=None)
def test_quantize_sigma_preserves_packed_width(sigma, k):
    """Quantisation keeps the packed layout identical (same bit width, so
    same lane count), never shrinks the value range, and is idempotent —
    the properties that make it a sound static-arg key."""
    q = quantize_sigma(sigma)
    assert q >= sigma
    assert quantize_sigma(q) == q
    assert (sigma + 1).bit_length() == (q + 1).bit_length()
    assert packed_width(k, -1, sigma) == packed_width(k, -1, q)


def test_resolve_bsp_sort_impl():
    assert resolve_bsp_sort_impl("auto") == "radix"
    assert resolve_bsp_sort_impl("auto", pack_keys=False) == "lax"
    assert resolve_bsp_sort_impl("bitonic") == "bitonic"
    assert resolve_bsp_sort_impl("lax", pack_keys=True) == "lax"
    for bad in ("pallas", "nope"):
        try:
            resolve_bsp_sort_impl(bad)
            raise AssertionError(f"{bad} accepted")
        except ValueError:
            pass


@given(st.integers(1, 50), st.integers(1, 12), st.integers(0, 99))
@settings(max_examples=30, deadline=None)
def test_searchsorted_rows_matches_linear(m, q, seed):
    rng = np.random.default_rng(seed)
    W = 3
    rows = rng.integers(0, 4, (m, W)).astype(np.int32)
    spl = np.sort(rng.integers(0, 4, (q, W)).astype(np.int32), axis=0)
    spl = spl[np.lexsort(tuple(spl[:, c] for c in range(W - 1, -1, -1)))]
    got = np.asarray(searchsorted_rows(jnp.asarray(spl.T),
                                       jnp.asarray(rows.T)))
    for i in range(m):
        want = sum(1 for s in spl if tuple(s) < tuple(rows[i]))
        assert got[i] == want


def test_counts_per_bucket():
    dest = jnp.asarray([0, 1, 1, 3, 3, 3], jnp.int32)
    valid = jnp.asarray([True, True, False, True, True, True])
    out = np.asarray(counts_per_bucket(dest, valid, 4))
    assert out.tolist() == [1, 1, 0, 3]


@given(st.lists(st.integers(0, 30), min_size=1, max_size=70),
       st.lists(st.integers(-2, 33), min_size=1, max_size=20))
@settings(max_examples=60, deadline=None)
def test_lower_bound_matches_searchsorted(a, x):
    a = np.sort(np.asarray(a, np.int32))
    x = np.asarray(x, np.int32)
    got = np.asarray(lower_bound(jnp.asarray(a), jnp.asarray(x)))
    assert got.tolist() == np.searchsorted(a, x, side="left").tolist()


@given(st.lists(st.tuples(st.integers(0, 3), st.booleans()), min_size=1,
                max_size=60))
@settings(max_examples=40, deadline=None)
def test_group_running_counts_and_take_cols(items):
    group = np.array([g for g, _ in items], np.int32)
    valid = np.array([v for _, v in items], bool)
    m = len(items)
    run = np.asarray(group_running_counts(jnp.asarray(group),
                                          jnp.asarray(valid), 4))
    for g in range(4):
        member = (group == g) & valid
        base = run[g * m - 1] if g else 0
        assert (run[g * m:(g + 1) * m] - base).tolist() == \
            np.cumsum(member).tolist()
    cols = np.stack([np.arange(m), 10 * np.arange(m)]).astype(np.int32)
    idx = group % m
    assert np.asarray(take_cols(jnp.asarray(cols), jnp.asarray(idx))
                      ).tolist() == cols[:, idx].tolist()
