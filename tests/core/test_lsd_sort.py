"""`lsd_argsort`: stable lexicographic argsort of uint32 key lanes from
single-operand radix passes, checked against numpy's lexsort."""
import numpy as np
import pytest
from hypothesis import given, settings, strategies as st

from repro.core.lsd_sort import lsd_argsort, radix_schedule


@given(st.integers(1, 300), st.lists(st.integers(1, 32), min_size=1,
                                     max_size=3), st.integers(0, 10_000))
@settings(max_examples=40, deadline=None)
def test_matches_lexsort(n, bits, seed):
    rng = np.random.default_rng(seed)
    # few distinct values per lane, so ties exercise stability
    lanes = np.stack([rng.integers(0, min(2 ** b, 7), n, dtype=np.uint64)
                      << np.uint64(max(b - 3, 0)) for b in bits]
                     ).astype(np.uint32)
    got = np.asarray(lsd_argsort(lanes, bits))
    np.testing.assert_array_equal(got, np.lexsort(tuple(lanes[::-1])))


@pytest.mark.parametrize("n,bits,passes", [
    (1 << 20, [27], 3),          # 20 index bits → 12-bit digits
    (41943042, [27], 5),         # 26 index bits → 6-bit digits
    (1000, [32, 1], 2 + 1),      # 10 index bits → 22-bit digits
])
def test_radix_schedule_pass_count(n, bits, passes):
    sched = radix_schedule(bits, n)
    assert len(sched) == passes
    # least significant lane first, digits cover every bit exactly once
    for lane, b in enumerate(bits):
        mine = sched[sched[:, 0] == lane]
        assert mine[:, 2].sum() == b
        assert list(mine[:, 1]) == sorted(mine[:, 1])
    assert sched[0, 0] == len(bits) - 1
