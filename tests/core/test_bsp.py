"""BSP layer tests — run in a subprocess with 8 fake CPU devices (the main
pytest process must keep the default 1-device view)."""
import os
import subprocess
import sys
import textwrap

import pytest

SRC = os.path.abspath(os.path.join(os.path.dirname(__file__), "..", "..",
                                   "src"))


def _run(body: str, timeout: int = 420):
    env = dict(os.environ)
    env["XLA_FLAGS"] = "--xla_force_host_platform_device_count=8"
    env["PYTHONPATH"] = SRC
    r = subprocess.run([sys.executable, "-c", textwrap.dedent(body)],
                       capture_output=True, text=True, timeout=timeout,
                       env=env)
    assert r.returncode == 0, f"stdout:\n{r.stdout}\nstderr:\n{r.stderr}"
    return r.stdout


def test_psort_key_and_comparator_modes():
    _run("""
    import jax, jax.numpy as jnp, numpy as np
    from jax.sharding import Mesh
    from repro.bsp.psort import run_psort, make_local_sort_bitonic, lex_lt_full
    mesh = Mesh(np.array(jax.devices()).reshape(8), ("bsp",))
    rng = np.random.default_rng(0)
    def rows_of(vals):
        N = len(vals)
        return np.stack([np.zeros(N, np.int32), np.asarray(vals, np.int32),
                         np.arange(N, dtype=np.int32)], axis=1)
    for vals in [rng.integers(0, 50, 256), np.zeros(512, np.int64),
                 np.arange(512)[::-1].copy()]:
        out, over = run_psort(mesh, "bsp", jnp.asarray(rows_of(vals)))
        assert not bool(np.asarray(over)[0])
        got = np.asarray(out); got = got[got[:, 0] == 0]
        want = np.lexsort((np.arange(len(vals)), vals))
        assert np.array_equal(got[:, 2], want)
    vals = rng.integers(0, 9, 256)
    ls = make_local_sort_bitonic(lex_lt_full)
    out, over = run_psort(mesh, "bsp", jnp.asarray(rows_of(vals)),
                          lt_fn=lex_lt_full, local_sort=ls)
    got = np.asarray(out); got = got[got[:, 0] == 0]
    assert np.array_equal(got[:, 2], np.lexsort((np.arange(256), vals)))
    print("OK")
    """)


def test_exchange_adversarial_skew():
    _run("""
    import jax, jax.numpy as jnp, numpy as np, functools
    from jax.sharding import Mesh, PartitionSpec as P
    from repro.bsp.exchange import exchange
    from jax import shard_map
    mesh = Mesh(np.array(jax.devices()).reshape(8), ("bsp",))
    p, m = 8, 32
    rng = np.random.default_rng(1)
    # adversarial: every shard sends everything to shard 3
    dest = np.full((p * m,), 3, np.int32)
    rows = np.stack([np.arange(p * m, dtype=np.int32),
                     rng.integers(0, 99, p * m).astype(np.int32)], axis=1)
    def f(r, d):
        out, valid, over = exchange(r.T, d[:, 0], jnp.ones(m, bool), p=p,
                                    cap_out=p * m, axis="bsp")
        return out.T, valid[:, None], over[None]
    fn = jax.jit(shard_map(f, mesh=mesh,
        in_specs=(P("bsp"), P("bsp")), out_specs=(P("bsp"), P("bsp"), P("bsp"))))
    out, valid, over = fn(jnp.asarray(rows), jnp.asarray(dest[:, None]))
    assert not bool(np.asarray(over).any())
    out, valid = np.asarray(out), np.asarray(valid)[:, 0]
    # shard 3 (rows p*m*3/... layout: out is [p * p*m, 2] global) —
    # reshape per shard: each shard got cap_out=p*m rows
    per = out.reshape(p, p * m, 2)
    pv = valid.reshape(p, p * m)
    assert pv[3].sum() == p * m            # all rows arrived at shard 3
    assert sorted(per[3][pv[3]][:, 0].tolist()) == list(range(p * m))
    assert pv[[0,1,2,4,5,6,7]].sum() == 0

    # order-preserving delivery: each shard receives its rows as a prefix,
    # by source shard, each source's rows in their original order
    for trial in range(3):
        dest = rng.integers(0, p, p * m).astype(np.int32)
        keep = rng.random(p * m) < 0.7
        rows[:, 1] = np.where(keep, 0, -1)
        def g(r, d):
            out, valid, over = exchange(r.T, d[:, 0], r[:, 1] == 0, p=p,
                                        cap_out=2 * m + 2 * p + 4, axis="bsp")
            return out.T, valid[:, None], over[None]
        fn = jax.jit(shard_map(g, mesh=mesh, in_specs=(P("bsp"), P("bsp")),
                               out_specs=(P("bsp"), P("bsp"), P("bsp"))))
        out, valid, over = fn(jnp.asarray(rows), jnp.asarray(dest[:, None]))
        assert not bool(np.asarray(over).any())
        cap = 2 * m + 2 * p + 4
        per = np.asarray(out).reshape(p, cap, 2)
        pv = np.asarray(valid).reshape(p, cap)
        for d in range(p):
            n_d = int(pv[d].sum())
            assert pv[d][:n_d].all()
            want = rows[keep & (dest == d), 0]
            assert per[d][:n_d, 0].tolist() == want.tolist(), (trial, d)
    print("OK")
    """)


def test_bsp_suffix_array_matches_oracle():
    _run("""
    import jax, numpy as np
    from jax.sharding import Mesh
    from repro.bsp.suffix_array import suffix_array_bsp
    from repro.bsp.counters import BSPCounters
    from repro.core.oracle import suffix_array_doubling
    mesh = Mesh(np.array(jax.devices()).reshape(8), ("bsp",))
    rng = np.random.default_rng(2)
    for n, sig in [(900, 3), (2048, 2), (1500, 30)]:
        x = rng.integers(0, sig, size=n)
        ct = BSPCounters()
        got = suffix_array_bsp(x, mesh, base_threshold=64, counters=ct)
        assert np.array_equal(got, suffix_array_doubling(x)), (n, sig)
        assert ct.supersteps > 0 and ct.comm_words > 0
    print("OK")
    """)


def test_bsp_sort_impls_edge_texts():
    """Packed-key / unpacked-key / comparator local sorts all match the
    oracle, including on all-equal and adversarial-periodic texts."""
    _run("""
    import jax, numpy as np
    from jax.sharding import Mesh
    from repro.bsp.suffix_array import suffix_array_bsp
    from repro.core.oracle import suffix_array_doubling
    mesh = Mesh(np.array(jax.devices()).reshape(8), ("bsp",))
    rng = np.random.default_rng(0)
    texts = [rng.integers(0, 256, size=1000),      # realistic bytes
             np.zeros(600, np.int64),              # all-equal (max ties)
             np.tile([1, 0, 2, 1, 0], 120)]        # adversarial periodic
    for impl in ("radix", "lax", "bitonic"):
        for x in texts:
            got = suffix_array_bsp(x, mesh, base_threshold=128,
                                   sort_impl=impl)
            want = suffix_array_doubling(np.asarray(x, np.int64))
            assert np.array_equal(got, want), impl
    print("OK")
    """, timeout=900)


def test_bsp_nonpow2_meshes_match_oracle():
    """Algorithm 3 on non-power-of-two p (the splitter machinery and the
    two-hop exchange caps make no power-of-two assumption)."""
    _run("""
    import jax, numpy as np
    from jax.sharding import Mesh
    from repro.bsp.suffix_array import suffix_array_bsp
    from repro.core.oracle import suffix_array_doubling
    devs = np.array(jax.devices())
    rng = np.random.default_rng(1)
    for p in (3, 5, 6):
        mesh = Mesh(devs[:p].reshape(p), ("bsp",))
        for x in [rng.integers(0, 9, size=1000), np.tile([3, 3, 1], 220)]:
            got = suffix_array_bsp(x, mesh, base_threshold=128)
            want = suffix_array_doubling(np.asarray(x, np.int64))
            assert np.array_equal(got, want), p
    print("OK")
    """, timeout=900)


def test_bsp_counters_match_estimate_and_overflow_is_hard_error():
    """C4/C5 reconciliation: measured superstep log == the analytic replay
    (`estimate_costs`) on a worst-case text, with SM1=11 / SM2=9 per round;
    and exchange capacity overflow is a detected, hard error."""
    _run("""
    import jax, jax.numpy as jnp, numpy as np
    from jax.sharding import Mesh, PartitionSpec as P
    from repro.bsp.counters import BSPCounters
    from repro.bsp.exchange import exchange
    from repro.bsp.suffix_array import estimate_costs, suffix_array_bsp
    from jax import shard_map
    import repro.bsp.psort as psort

    mesh = Mesh(np.array(jax.devices()).reshape(8), ("bsp",))

    # --- measured == analytic replay (all-equal text never short-circuits)
    x = np.zeros(3000, np.int64)
    ct = BSPCounters()
    sa = suffix_array_bsp(x, mesh, base_threshold=64, counters=ct)
    assert np.array_equal(sa, np.arange(3000)[::-1])
    est = estimate_costs(3000, 8, base_threshold=64, sigma=1)
    assert ct.supersteps == est.supersteps
    assert [e["label"] for e in ct.log] == [e["label"] for e in est.log]
    labels = [e["label"] for e in ct.log]
    assert labels.count("base/gather") == 1
    sm1 = sum(1 for l in labels if l.startswith("SM1/"))
    sm2 = sum(1 for l in labels if l.startswith("SM2/"))
    assert sm1 == 11 * ct.rounds and sm2 == 9 * ct.rounds
    assert ct.supersteps == 20 * ct.rounds + 1 and ct.rounds >= 2

    # --- exchange overflow is detected (cap_out far below the h-relation)
    p, m = 8, 32
    rows = np.stack([np.arange(p * m, dtype=np.int32),
                     np.arange(p * m, dtype=np.int32)], axis=1)
    dest = np.zeros((p * m, 1), np.int32)          # everything to shard 0
    def f(r, d):
        out, valid, over = exchange(r.T, d[:, 0], jnp.ones(m, bool), p=p,
                                    cap_out=4, axis="bsp")
        return out.T, valid[:, None], over[None]
    fn = jax.jit(shard_map(f, mesh=mesh, in_specs=(P("bsp"), P("bsp")),
                           out_specs=(P("bsp"), P("bsp"), P("bsp"))))
    _, _, over = fn(jnp.asarray(rows), jnp.asarray(dest))
    assert bool(np.asarray(over).any())

    # --- run_psort surfaces a set flag as RuntimeError (flag forced on one
    #     shard; real flag-raising is covered by the cap_out=4 case above)
    orig = psort.exchange
    def forced(rows, dest, valid, *, p, cap_out, axis):
        out, val, over = orig(rows, dest, valid, p=p, cap_out=cap_out,
                              axis=axis)
        return out, val, over | (jax.lax.axis_index(axis) == 0)
    psort.exchange = forced
    try:
        N = 512
        rows_g = jnp.asarray(np.stack(
            [np.zeros(N, np.int32), np.arange(N, dtype=np.int32) % 7,
             np.arange(N, dtype=np.int32)], axis=1))
        try:
            psort.run_psort(mesh, "bsp", rows_g)
            raise SystemExit("expected RuntimeError")
        except RuntimeError as e:
            assert "overflow" in str(e)
    finally:
        psort.exchange = orig

    # --- the driver-side check itself is a hard error on any set flag
    from repro.bsp.suffix_array import _check_overflow
    _check_overflow(np.zeros(8, bool), "SM1")          # all clear: no-op
    try:
        _check_overflow(np.asarray([False, True] + [False] * 6), "SM1")
        raise SystemExit("expected RuntimeError")
    except RuntimeError as e:
        assert "SM1" in str(e)
    print("OK")
    """, timeout=900)


def test_bsp_p1_degenerate_and_estimate_model():
    """p=1 degenerates to the single-device path (one base superstep), and
    the analytic model shows the accelerated schedule's round advantage."""
    import numpy as np

    from repro.bsp.counters import BSPCounters
    from repro.bsp.suffix_array import estimate_costs, suffix_array_bsp
    from repro.core.oracle import suffix_array_doubling
    from repro.core.seq_ref import fixed_next_v
    from repro.launch.mesh import make_sa_mesh

    x = np.random.default_rng(3).integers(0, 5, 600)
    ct = BSPCounters()
    got = suffix_array_bsp(x, make_sa_mesh(1), counters=ct)
    assert np.array_equal(got, suffix_array_doubling(x))
    assert ct.supersteps == 1 and ct.rounds == 0
    assert estimate_costs(600, 1).supersteps == 1

    # accelerated schedule: never more rounds than fixed-v, and an
    # O(log log) round count at realistic sizes (paper C4)
    for n, p in ((1 << 20, 16), (1 << 22, 64)):
        acc = estimate_costs(n, p)
        fix = estimate_costs(n, p, schedule=fixed_next_v)
        assert acc.rounds <= fix.rounds
        assert acc.supersteps == 20 * acc.rounds + 1
        assert acc.rounds <= 6          # log log n envelope at these sizes


def test_bsp_rejects_pallas_sort_impl():
    import numpy as np
    import pytest

    from repro.bsp.suffix_array import suffix_array_bsp
    from repro.launch.mesh import make_sa_mesh

    with pytest.raises(ValueError, match="pallas"):
        suffix_array_bsp(np.arange(100) % 7, make_sa_mesh(1),
                         sort_impl="pallas")


def test_bsp_superstep_scaling_model():
    """C4: cost-model round counts — accelerated O(log log p) vs fixed."""
    from repro.core.seq_ref import accelerated_next_v, fixed_next_v
    from repro.core.difference_cover import difference_cover

    def rounds(n, p, schedule):
        v, cnt = 3, 0
        while n > max(4096, 1):
            if n <= max(4096, n and 0) or n <= p * v * 2:
                break
            D = difference_cover(min(v, max(n, 3)))
            n = len(D) * -(-n // v)
            v = schedule(v, len(D), n)
            cnt += 1
            if cnt > 200:
                break
        return cnt

    n = 1 << 40
    for p in [2 ** k for k in range(4, 16, 2)]:
        ra = rounds(n, p, accelerated_next_v)
        rf = rounds(n, p, fixed_next_v)
        assert ra <= rf
