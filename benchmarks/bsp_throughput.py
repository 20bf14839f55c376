"""BSP (Algorithm 3) construction throughput over an n × p grid, per
shard-local `sort_impl` — the distributed side of the perf trajectory.
Emits the usual CSV lines plus a machine-readable `BENCH_bsp_throughput.json`
artifact.

Runs in this process, over meshes of the first p devices it sees (a chip
belongs to one process, so nothing is handed to a child). On a CPU host
the devices are virtual: set the count before jax is imported, e.g.

    XLA_FLAGS=--xla_force_host_platform_device_count=8 \
        PYTHONPATH=src python -m benchmarks.bsp_throughput [--smoke] [--out PATH]

(`benchmarks/run.py` does that itself). Every (n, sort_impl) cell is timed
warm (jit compile excluded) and its `BSPCounters` are recorded, so the
O(log log p) superstep schedule is visible in the artifact next to the
wall-clock numbers. The comparator-bitonic local sort is kept as the
regression row — it is the *before* of the packed-key psort rework,
exactly like `jax[bitonic]` in `BENCH_sa_throughput.json`.
"""
import argparse
import json
import platform
import sys
import time

import jax
import numpy as np

from repro.bsp.counters import BSPCounters
from repro.bsp.suffix_array import suffix_array_bsp
from repro.core.oracle import suffix_array_doubling
from repro.launch.mesh import make_sa_mesh

from .bench_util import emit

SIZES = (20_000, 100_000)
PS = (4, 8)
IMPLS = ("radix", "lax", "bitonic")
#: the comparator network is O(m log² m) compare-exchanges by design; cap
#: it at the acceptance size so the regression row stays measurable.
BITONIC_MAX_N = 100_000
#: sizes up to this are verified against the prefix-doubling oracle in-run.
CHECK_MAX_N = 20_000


def run_grid(ps, sizes, impls, iters, bitonic_max):
    n_dev = len(jax.devices())
    if max(ps) > n_dev:
        raise RuntimeError(
            f"bsp_throughput needs {max(ps)} devices, {n_dev} visible (on a "
            f"CPU host set XLA_FLAGS=--xla_force_host_platform_device_count="
            f"{max(ps)} before jax is imported)")
    records = []
    rng = np.random.default_rng(0)
    texts = {n: rng.integers(0, 256, size=n) for n in sizes}
    for p in ps:
        mesh = make_sa_mesh(p)
        for n, x in texts.items():
            for impl in impls:
                if impl == "bitonic" and n > bitonic_max:
                    continue
                ct = BSPCounters()
                sa = suffix_array_bsp(x, mesh, sort_impl=impl,
                                      counters=ct)          # warmup
                if n <= CHECK_MAX_N and not np.array_equal(
                        sa, suffix_array_doubling(x)):
                    raise RuntimeError(f"bsp[{impl}] n={n} p={p}: suffix "
                                       f"array differs from the oracle")
                ts = []
                for _ in range(iters):
                    t0 = time.perf_counter()
                    suffix_array_bsp(x, mesh, sort_impl=impl)
                    ts.append(time.perf_counter() - t0)
                us = 1e6 * float(np.median(ts))
                rec = {"backend": f"bsp[{impl}]", "sort_impl": impl, "n": n,
                       "p": p, "us": round(us, 1),
                       "mchars_per_s": round(n / us, 3),
                       "supersteps": ct.supersteps, "rounds": ct.rounds,
                       "comm_words": ct.comm_words, "work": ct.work}
                if impl == "radix":
                    rec["superstep_log"] = ct.log
                records.append(rec)
                emit(f"bsp_throughput/{rec['backend']}/n={n}/p={p}",
                     rec["us"],
                     f"Mchars_s={rec['mchars_per_s']};S={rec['supersteps']};"
                     f"rounds={rec['rounds']}")
    return records


def main(argv=None):
    ap = argparse.ArgumentParser()
    ap.add_argument("--out", default="BENCH_bsp_throughput.json",
                    help="JSON artifact path ('' disables)")
    ap.add_argument("--smoke", action="store_true",
                    help="tiny n on 4 devices (CI gate: proves the "
                         "distributed path builds, runs, and matches the "
                         "oracle — radix + bitonic regression row)")
    args = ap.parse_args(argv)

    if args.smoke:
        ps, sizes, impls, iters = (4,), (4_000,), ("radix", "bitonic"), 1
    else:
        ps, sizes, impls, iters = PS, SIZES, IMPLS, 2

    print("# bsp_throughput: backend, n, p, us, Mchars/s + BSP counters")
    records = run_grid(ps, sizes, impls, iters, BITONIC_MAX_N)

    if args.out:
        dev = jax.devices()[0]
        artifact = {
            "bench": "bsp_throughput",
            "python": sys.version.split()[0],
            "machine": platform.machine(),
            "device": {"platform": dev.platform, "kind": dev.device_kind,
                       "count": len(jax.devices())},
            "smoke": bool(args.smoke),
            "records": records,
        }
        with open(args.out, "w") as f:
            json.dump(artifact, f, indent=1)
        print(f"# wrote {args.out} ({len(records)} records)")
    return records


if __name__ == "__main__":
    main()
