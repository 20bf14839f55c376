"""`chip_smoke.py` rehearsed on the CPU: its phases at a tiny size, its
linear-time SA check, its refusal to run without a TPU or without the
repository, and the four-chip BSP phase on 4 virtual devices."""
import os
import shutil
import subprocess
import sys
import textwrap

import numpy as np
import pytest

ROOT = os.path.abspath(os.path.join(os.path.dirname(__file__), "..", ".."))
sys.path.insert(0, ROOT)

import chip_smoke  # noqa: E402


def test_default_phases_tiny():
    # on the CPU "auto" resolves to the host radix sort, and the pallas
    # phase runs the kernels in interpret mode
    chip_smoke.run_default_phases(
        n_chars=1 << 14, n_docs=8, oracle_n=1 << 12, pallas_n=1 << 10,
        n_queries=96, n_checked=chip_smoke.MIN_CHECKED, seed=0,
        clock=chip_smoke.CompileClock(), expect=("jax", "radix"))


def test_check_suffix_array_rejects_wrong_orders():
    from repro.core.oracle import suffix_array_doubling
    x = np.random.default_rng(0).integers(0, 4, 300)
    sa = suffix_array_doubling(x)
    chip_smoke.check_suffix_array(x, sa)
    swapped = sa.copy()
    swapped[[10, 11]] = swapped[[11, 10]]
    with pytest.raises(RuntimeError, match="order"):
        chip_smoke.check_suffix_array(x, swapped)
    dup = sa.copy()
    dup[5] = dup[6]
    with pytest.raises(RuntimeError, match="permutation"):
        chip_smoke.check_suffix_array(x, dup)


def test_scan_count_matches_naive():
    rng = np.random.default_rng(1)
    docs = list(rng.integers(0, 3, size=(4, 200), dtype=np.uint8))
    for m in (1, 2, 5):
        pat = docs[1][7:7 + m]
        want = sum(sum(np.array_equal(d[i:i + m], pat)
                       for i in range(len(d) - m + 1)) for d in docs)
        assert chip_smoke.scan_count(docs, pat) == want


def _run_script(cwd, env_extra=None):
    env = dict(os.environ)
    env.update(env_extra or {})
    env.pop("PYTHONPATH", None)
    return subprocess.run([sys.executable, "chip_smoke.py"], cwd=cwd, env=env,
                          capture_output=True, text=True, timeout=300)


def test_main_refuses_a_cpu():
    r = _run_script(ROOT, {"JAX_PLATFORMS": "cpu"})
    assert r.returncode != 0
    assert '"ok"' not in r.stdout
    assert "no phase was run" in r.stderr


def test_main_refuses_without_the_repo(tmp_path):
    shutil.copy(os.path.join(ROOT, "chip_smoke.py"), tmp_path)
    r = _run_script(tmp_path, {"JAX_PLATFORMS": "cpu"})
    assert r.returncode != 0
    assert '"ok"' not in r.stdout


def test_four_chip_phase_on_virtual_devices():
    code = textwrap.dedent("""
    import sys
    sys.path.insert(0, {root!r})
    import chip_smoke
    import repro.bsp.suffix_array as bsa
    placed = []
    sm1 = bsa._sm1
    def spy(xg, **kw):
        placed.append({{s.device.id for s in xg.addressable_shards}})
        return sm1(xg, **kw)
    bsa._sm1 = spy
    docs = chip_smoke.make_docs(1 << 14, 8, 0)
    used = chip_smoke.phase_four_chip(docs, 4, chip_smoke.CompileClock())
    assert used == 4
    assert placed and all(p == {{0, 1, 2, 3}} for p in placed), placed
    print("FOUR_CHIP_OK")
    """).format(root=ROOT)
    env = dict(os.environ)
    env["XLA_FLAGS"] = "--xla_force_host_platform_device_count=4"
    env["PYTHONPATH"] = os.path.join(ROOT, "src")
    r = subprocess.run([sys.executable, "-c", code], env=env,
                       capture_output=True, text=True, timeout=600)
    assert "FOUR_CHIP_OK" in r.stdout, (r.stdout[-2000:], r.stderr[-3000:])


@pytest.mark.parametrize("kind", ["all_equal", "distinct", "random"])
def test_first_distinct_round_matches_a_bsp_run(kind):
    """The host replay of the short-circuit round agrees with the rounds a
    BSP run on 4 virtual devices logs (worst case: no round is distinct)."""
    code = textwrap.dedent("""
    import sys
    sys.path.insert(0, {root!r})
    import numpy as np
    import chip_smoke
    from repro.launch.mesh import make_sa_mesh
    from repro.bsp.counters import BSPCounters
    from repro.bsp.suffix_array import estimate_costs, suffix_array_bsp
    n = 6000
    x = {{"all_equal": np.zeros(n, np.int32),
         "distinct": np.arange(n, dtype=np.int32),
         "random": np.random.default_rng(0).integers(0, 4, n).astype(np.int32),
         }}[{kind!r}]
    r = chip_smoke.first_distinct_round(x, 4)
    ct = BSPCounters()
    suffix_array_bsp(x, make_sa_mesh(4), counters=ct)
    est = estimate_costs(n, 4, distinct_round=r)
    assert [e["label"] for e in ct.log] == [e["label"] for e in est.log], r
    print("ROUND", r)
    """).format(root=ROOT, kind=kind)
    env = dict(os.environ)
    env["XLA_FLAGS"] = "--xla_force_host_platform_device_count=4"
    env["PYTHONPATH"] = os.path.join(ROOT, "src")
    r = subprocess.run([sys.executable, "-c", code], env=env,
                       capture_output=True, text=True, timeout=600)
    assert "ROUND" in r.stdout, (r.stdout[-2000:], r.stderr[-3000:])
    got = r.stdout.split("ROUND")[-1].strip()
    assert got == {"all_equal": "None", "distinct": "1"}.get(kind, got)
