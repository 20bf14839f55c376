"""Headline claim (C4): BSP synchronisation cost S(p).

Measured supersteps on a real run over up to 8 devices + the analytic cost
model for p up to 2^20, against the paper's O(log log p) and Kärkkäinen et
al.'s O(log² p) baselines. The per-round superstep constant is the measured
one (SM1=11, SM2=9, base=1), which `tests/core/test_bsp.py` pins against
`repro.bsp.suffix_array.estimate_costs` — the exact-replay model for
realistic (n, p). The capped model below trades that exactness for
feasibility at astronomic sizes (difference covers clamped at v=2048).

The measured run uses the devices this process sees (on a CPU host set
``XLA_FLAGS=--xla_force_host_platform_device_count=8`` before jax is
imported; `benchmarks/run.py` does) and fails with fewer than two."""
import jax
import numpy as np

from repro.api import SAOptions, build_suffix_array
from repro.bsp.counters import BSPCounters
from repro.core.difference_cover import difference_cover
from repro.core.seq_ref import accelerated_next_v, fixed_next_v
from repro.launch.mesh import make_sa_mesh

from .bench_util import emit

PER_ROUND = 20          # SM1 (11) + SM2 (9), measured by BSPCounters
BASE = 1


def model_supersteps(n, p, schedule):
    """Rounds until |X'| ≤ n/p (the paper's sequential-base condition)."""
    n0, v, rounds = n, 3, 0
    while n > max(n0 // p, 2 * p * v, 1024) and rounds < 500:
        D = difference_cover(min(max(v, 3), 2048))
        n = len(D) * -(-n // v)
        v = schedule(v, len(D), n)
        rounds += 1
    return PER_ROUND * rounds + BASE


def measured():
    p = min(8, len(jax.devices()))
    if p < 2:
        raise RuntimeError(
            "supersteps needs at least 2 devices for its BSP run (on a CPU "
            "host set XLA_FLAGS=--xla_force_host_platform_device_count=8 "
            "before jax is imported)")
    x = np.random.default_rng(0).integers(0, 2, size=4096)
    ct = BSPCounters()
    build_suffix_array(x, SAOptions(mesh=make_sa_mesh(p), base_threshold=64,
                                    counters=ct))
    if ct.rounds < 1:
        raise RuntimeError(f"BSP run over p={p} logged no distributed round")
    emit(f"supersteps/measured_p{p}_n4096", 0.0,
         f"S={ct.supersteps};H={ct.comm_words};W={ct.work}")


def main():
    measured()
    print("# model: p, S_accelerated, S_fixed_v3, karkkainen_log2p_bound")
    n = 1 << 44
    for k in range(4, 22, 2):
        p = 1 << k
        sa = model_supersteps(n, p, accelerated_next_v)
        sf = model_supersteps(n, p, fixed_next_v)
        kk = PER_ROUND * (np.log2(p) ** 2) / 4
        emit(f"supersteps/p=2^{k}", 0.0,
             f"accel={sa};fixed={sf};log2p_sq~{kk:.0f}")


if __name__ == "__main__":
    main()
