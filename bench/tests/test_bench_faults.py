"""`correct` must come out false when the timed path is broken: the
controls, and one planted fault of each kind a cell can have. Runs the
whole harness at a tiny size on the CPU, past its look for a chip."""
import numpy as np
import pytest

from bench import harness
from bench.reference import encoded_text, suffix_array_faults
from bench.tests.test_bench_harness import SEED, run_tiny
from bench.tools import control


@pytest.mark.parametrize("fault", ["stale", "swap", "half"])
def test_build_faults_are_not_correct(fault):
    res = run_tiny(harness.Spec(), "build.dedup-shard", seconds=0.3,
                   system=control.system_for("build_stream", fault))
    assert res["correct"] is False
    assert res["checks"]["sa_faults"]["value"] > 0
    assert res["failed"] >= 1


def test_control_is_not_correct():
    res = run_tiny(harness.Spec(), "build.dedup-shard", seconds=0.5,
                   system=control.system_for("build_stream", "control"))
    assert res["correct"] is False
    assert res["checks"]["sa_faults"]["value"] > 0


def test_depth_sorted_sa_is_exact_only_to_its_depth():
    from repro.core.oracle import suffix_array_doubling
    rng = np.random.default_rng(SEED)
    base = rng.integers(1, 5, 300)
    # a 60-symbol repeat whose later copy is the smaller suffix
    text = np.concatenate([base, base[:60], [0]])
    sa = suffix_array_doubling(text)
    assert np.array_equal(control.depth_sorted_sa(text, len(text)), sa)
    assert suffix_array_faults(text, control.depth_sorted_sa(text, 70)) == 0
    assert suffix_array_faults(text, control.depth_sorted_sa(text, 20)) > 0


def test_references_on_hand_worked_text():
    docs = [np.array([3, 1, 3, 1]), np.array([1, 3])]
    assert encoded_text(docs).tolist() == [5, 3, 5, 3, 0, 3, 5, 1]
    text = encoded_text(docs)
    from repro.core.oracle import suffix_array_doubling
    sa = suffix_array_doubling(text)
    assert suffix_array_faults(text, sa) == 0
    assert suffix_array_faults(text, sa[::-1]) > 0
    assert suffix_array_faults(text, sa[:-1]) > 0
    bad = sa.copy()
    bad[0] = bad[1]
    assert suffix_array_faults(text, bad) > 0
