"""The trace reduction: hand-worked intervals, and a trace recorded here."""
import jax
import jax.numpy as jnp
import pytest

from bench.trace import reduce as tr

MS = 1_000_000  # ns


def synthetic():
    """One device; window 0-100 ms; ops busy 10-30 and 50-60 (one op
    overlapping another); host spans name the gaps."""
    ops = [("fusion.1", 10 * MS, 20 * MS), ("fusion.2", 15 * MS, 30 * MS),
           ("sort.3", 50 * MS, 60 * MS), ("late", 95 * MS, 120 * MS)]
    modules = [("jit__lsd_argsort(12)", 10 * MS, 30 * MS),
               ("jit__lambda_tiebreak_jit", 50 * MS, 60 * MS),
               ("jit__lambda_tiebreak_jit", 95 * MS, 120 * MS)]
    spans = [("window", 0, 100 * MS), ("generate", 0, 10 * MS),
             ("build", 0, 80 * MS), ("check", 60 * MS, 100 * MS)]
    return {"devices": [{"name": "/device:TPU:0", "ops": ops,
                         "modules": modules}], "spans": spans}


def test_union_and_clip():
    assert tr.union([(5, 9), (1, 3), (2, 4), (9, 10), (7, 7)]) == [
        (1, 4), (5, 10)]
    assert tr.clip([(0, 5), (4, 12), (20, 30)], 2, 10) == [(2, 5), (4, 10)]


def test_reduce_hand_worked():
    s = tr.reduce(synthetic())
    assert s["window_s"] == pytest.approx(0.100)
    # busy: 10-30 (20 ms) + 50-60 (10 ms) + 95-100 (5 ms, clipped)
    assert s["busy_s"] == pytest.approx(0.035)
    assert tr.program_time(s, "_lsd_argsort") == (pytest.approx(0.020), 1)
    assert tr.program_time(s, "_lambda_tiebreak_jit") == (
        pytest.approx(0.015), 2)
    # gaps 0-10, 30-50, 60-95; each named by the innermost open span
    assert [(n, round(x, 6)) for n, x in s["gaps"]] == [
        ("check", 0.035), ("build", 0.020), ("generate", 0.010)]
    b = tr.breakdown(s, top=2)
    assert b["device_ops"][0] == ["jit__lsd_argsort(12)", pytest.approx(0.02)]
    assert len(b["idle_gaps"]) == 2


def test_reduce_has_nothing_to_read_without_device_or_window():
    x = synthetic()
    assert tr.reduce({"devices": [], "spans": x["spans"]}) is None
    assert tr.reduce({"devices": x["devices"], "spans": []}) is None


def test_recorded_cpu_trace(tmp_path):
    """A trace recorded here has the benchmark's host spans on the host
    plane and no device plane, so the reduction reads nothing."""
    f = jax.jit(lambda x: (x * 2).sum())
    f(jnp.ones(64))
    with tr.record(str(tmp_path)):
        with jax.profiler.TraceAnnotation("window"):
            with jax.profiler.TraceAnnotation("build"):
                f(jnp.ones(64)).block_until_ready()
            with jax.profiler.TraceAnnotation("not-a-bench-span"):
                pass
    x = tr.extract(tr.xplane_file(str(tmp_path)))
    names = sorted(n for n, _, _ in x["spans"])
    assert names == ["build", "window"]
    (_, ws, we), = [s for s in x["spans"] if s[0] == "window"]
    (_, bs, be), = [s for s in x["spans"] if s[0] == "build"]
    assert ws <= bs <= be <= we
    assert x["devices"] == []
    assert tr.reduce(x) is None
