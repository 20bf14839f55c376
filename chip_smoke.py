"""Smoke run of the main path on a TPU.

    python chip_smoke.py [--seed S]                # one chip
    python chip_smoke.py --four-chip [--seed S]    # four chips: BSP only

By default, on one chip and in one process:

* **build** — a seeded corpus of 2^25 byte characters in 64 documents (one
  pretraining dedup shard) is indexed through `SuffixArrayIndex.from_docs`
  with default `SAOptions` (backend ``jax``, sort_impl ``lax`` on a TPU).
  The whole suffix array passes a linear-time check that needs no oracle,
  and a build of its 2^20-character prefix equals `repro.core.oracle`;
* **serve** — a few hundred open-loop queries of 8-64 characters, half
  planted and half random, go through the serving tier
  (`repro.launch.serve.serve_open_loop`); at least 32 of the served counts
  must equal an independent numpy scan of the documents;
* **pallas** — a 2^18-character build with ``sort_impl="pallas"`` (the
  compiled Mosaic kernels) must equal the ``lax`` build.

With ``--four-chip`` it runs only the BSP phase: the same corpus built on
a 4-device mesh (Algorithm 3) must equal a single-device build, and its
`BSPCounters` must replay `repro.bsp.suffix_array.estimate_costs`.

Each phase raises on a wrong result, so any failure exits non-zero. The
last line of standard output is one JSON object naming the device, and is
printed only when every phase passed. Build and compile seconds are
printed as set-up information, not as metrics. On anything but a TPU the
script exits non-zero before any phase runs.
"""
from __future__ import annotations

import argparse
import concurrent.futures
import json
import os
import sys
import time

import numpy as np

ROOT = os.path.dirname(os.path.abspath(__file__))

N_CHARS = 1 << 25        # one pretraining dedup shard, byte alphabet
N_DOCS = 64
ORACLE_N = 1 << 20       # prefix checked against repro.core.oracle
PALLAS_N = 1 << 18       # the compiled-kernel build
N_QUERIES = 256
N_CHECKED = 64           # served counts compared with the numpy scan
MIN_CHECKED = 32
FOUR_CHIPS = 4


def require(cond: bool, what: str) -> None:
    if not cond:
        raise RuntimeError(f"chip_smoke: {what}")


class CompileClock:
    """Seconds jax spends compiling (persistent-cache reads included) and
    persistent-cache hits, read from jax's monitoring events."""

    def __init__(self):
        import jax
        self.secs = 0.0
        self.cache_hits = 0

        def on_duration(event, duration, **_):
            if event == "/jax/core/compile/backend_compile_duration":
                self.secs += duration

        def on_event(event, **_):
            if event == "/jax/compilation_cache/cache_hits":
                self.cache_hits += 1

        jax.monitoring.register_event_duration_secs_listener(on_duration)
        jax.monitoring.register_event_listener(on_event)


def make_docs(n_chars: int, n_docs: int, seed: int) -> list:
    """`n_docs` seeded documents of uniform random bytes, `n_chars` total."""
    rng = np.random.default_rng(seed)
    return list(rng.integers(0, 256, size=(n_docs, n_chars // n_docs),
                             dtype=np.uint8))


def check_suffix_array(text, sa) -> None:
    """Linear-time suffix-array check that needs no oracle: `sa` is a
    permutation, and each adjacent pair is ordered by first character and,
    on a tie, by the rank of the next suffix (the empty suffix ranks
    lowest)."""
    text = np.asarray(text)
    sa = np.asarray(sa, np.int64)
    n = len(text)
    require(sa.shape == (n,), f"SA shape {sa.shape} != ({n},)")
    require(n == 0 or (sa.min() >= 0 and sa.max() < n), "SA out of range")
    seen = np.zeros(n, bool)
    seen[sa] = True
    require(bool(seen.all()), "SA is not a permutation")
    rank = np.empty(n + 1, np.int64)
    rank[sa] = np.arange(n)
    rank[n] = -1
    a, b = sa[:-1], sa[1:]
    ordered = (text[a] < text[b]) | ((text[a] == text[b])
                                     & (rank[a + 1] < rank[b + 1]))
    bad = np.flatnonzero(~ordered)
    require(len(bad) == 0, f"SA out of order at rank {bad[:1].tolist()}")


def scan_count(docs, pattern) -> int:
    """Occurrences of `pattern` in the documents, by a plain numpy scan."""
    pattern = np.asarray(pattern)
    m = len(pattern)
    total = 0
    for d in docs:
        hits = np.flatnonzero(d[:len(d) - m + 1] == pattern[0])
        for k in range(1, m):
            hits = hits[d[hits + k] == pattern[k]]
        total += len(hits)
    return total


def phase_build(docs, oracle_n: int, clock: CompileClock, *,
                expect=("jax", "lax")):
    """Index `docs` with default options; check the SA; return the index."""
    from repro.api import SAOptions, SuffixArrayIndex
    from repro.core.dcv_jax import resolve_sort_impl
    from repro.core.oracle import suffix_array_doubling

    opts = SAOptions()
    resolved = (opts.resolve_backend(), resolve_sort_impl(opts.sort_impl))
    print(f"build: backend={resolved[0]} sort_impl={resolved[1]}")
    require(resolved == tuple(expect), f"resolved {resolved}, expected "
            f"{tuple(expect)}")
    c0, t0 = clock.secs, time.perf_counter()
    index = SuffixArrayIndex.from_docs(docs, opts, sigma=256)
    build_s = time.perf_counter() - t0
    print(f"build: n={index.n} ({len(docs)} docs) in {build_s:.3f}s, of "
          f"which compile {clock.secs - c0:.3f}s (set-up information)")
    check_suffix_array(index.text, index.sa)
    print("build: linear-time SA check passed (permutation; adjacent pairs "
          "ordered by first character, then by next-suffix rank)")

    prefix = index.text[:oracle_n]
    c0, t0 = clock.secs, time.perf_counter()
    got = SuffixArrayIndex.build(prefix, opts).sa
    print(f"build: {len(prefix)}-char prefix in "
          f"{time.perf_counter() - t0:.3f}s, of which compile "
          f"{clock.secs - c0:.3f}s (set-up information)")
    require(np.array_equal(got, suffix_array_doubling(prefix)),
            "prefix SA differs from repro.core.oracle")
    print(f"build: {len(prefix)}-char prefix SA equals repro.core.oracle")
    return index


def phase_serve(index, docs, n_queries: int, n_checked: int, seed: int):
    """Serve planted and random patterns through the serving tier; compare
    served counts with a numpy scan of the documents."""
    from repro.configs import get_config
    from repro.launch.serve import serve_open_loop

    rng = np.random.default_rng(seed + 1)
    patterns, planted = [], []
    for q, m in enumerate(rng.integers(8, 65, size=n_queries)):
        if q % 2 == 0:
            d = docs[rng.integers(len(docs))]
            at = rng.integers(0, len(d) - m + 1)
            patterns.append(d[at:at + m])
        else:
            patterns.append(rng.integers(0, 256, size=m, dtype=np.uint8))
        planted.append(q % 2 == 0)

    responses = serve_open_loop(index, patterns,
                                get_config("suffix-array"), seed=seed)
    served = {}
    for i, r in enumerate(responses):
        if r.ok:
            q = i % n_queries
            require(served.setdefault(q, r.count) == r.count,
                    f"pattern {q} served two different counts")
    # half planted, half random among the checked queries
    pick = ([q for q in sorted(served) if planted[q]][:n_checked // 2]
            + [q for q in sorted(served) if not planted[q]][:n_checked // 2])
    require(len(pick) >= MIN_CHECKED,
            f"only {len(pick)} served queries to check")
    for q in pick:
        want = scan_count(docs, patterns[q])
        require(served[q] == want, f"pattern {q} (len {len(patterns[q])}, "
                f"planted={planted[q]}): served {served[q]}, scan {want}")
        require(want >= 1 or not planted[q], f"planted pattern {q} missing")
    absent = sum(1 for q in pick if served[q] == 0)
    require(absent >= 1, "no absent pattern among the checked queries")
    print(f"serve: {len(pick)} served counts equal the numpy scan "
          f"({len(pick) - absent} present, {absent} absent)")


def phase_pallas(docs, n: int, clock: CompileClock) -> None:
    """Build an n-char prefix with the Pallas kernels and with lax.sort."""
    from repro.api import SAOptions, SuffixArrayIndex

    x = np.concatenate(docs)[:n]
    sas = {}
    for impl in ("lax", "pallas"):
        c0, t0 = clock.secs, time.perf_counter()
        sas[impl] = SuffixArrayIndex.build(x, SAOptions(sort_impl=impl)).sa
        print(f"pallas: {impl} build of {n} chars in "
              f"{time.perf_counter() - t0:.3f}s, of which compile "
              f"{clock.secs - c0:.3f}s (set-up information)")
    require(np.array_equal(sas["pallas"], sas["lax"]),
            "pallas build differs from the lax build")
    print(f"pallas: sort_impl=pallas SA equals sort_impl=lax SA (n={n})")


def run_default_phases(*, n_chars: int, n_docs: int, oracle_n: int,
                       pallas_n: int, n_queries: int, n_checked: int,
                       seed: int, clock: CompileClock,
                       expect=("jax", "lax")) -> None:
    docs = make_docs(n_chars, n_docs, seed)
    index = phase_build(docs, oracle_n, clock, expect=expect)
    phase_serve(index, docs, n_queries, n_checked, seed)
    phase_pallas(docs, pallas_n, clock)


def _dense_ranks(cols: np.ndarray):
    """Dense lexicographic ranks of the columns of int64 cols [k, n] (row 0
    most significant), and whether they are all distinct."""
    lo, hi = int(cols.min()), int(cols.max())
    bits = max(1, (hi - lo).bit_length())
    per = max(1, 62 // bits)
    shifted = cols - lo
    lanes = []
    for c in range(0, len(cols), per):      # order-preserving int64 packing
        lane = np.zeros(cols.shape[1], np.int64)
        for row in shifted[c:c + per]:
            lane = (lane << bits) | row
        lane <<= bits * (per - len(shifted[c:c + per]))
        lanes.append(lane)
    order = np.lexsort(lanes[::-1])
    new = np.ones(cols.shape[1], bool)
    new[1:] = np.any([lane[order][1:] != lane[order][:-1] for lane in lanes],
                     axis=0)
    ranks = np.empty(cols.shape[1], np.int64)
    ranks[order] = np.cumsum(new) - 1
    return ranks, bool(new.all())


def first_distinct_round(text, p: int, v: int = 3):
    """The first BSP round (1-based) whose sample windows are all distinct,
    worked out on the host with numpy from Algorithm 3's definitions (round
    geometry, difference cover, block-major X' layout, accelerated v
    schedule), independently of any device run. None when the recursion
    reaches its sequential base first."""
    from repro.bsp.suffix_array import round_geometry
    from repro.core.seq_ref import accelerated_next_v

    x = np.asarray(text, np.int64)
    base = max(1024, len(x) // p)
    if len(x) <= max(base, 2 * p * 3, 8):
        return None
    depth = 1
    while len(x) > max(base, 2 * p * v, 8):
        n = len(x)
        v = int(min(max(v, 3), n))
        n_pv, _, _, m_tot, tabs = round_geometry(n, p, v)
        xp = np.full(n_pv + v, -1, np.int64)
        xp[:n] = x
        g = np.flatnonzero(tabs.in_D[np.arange(n_pv) % v])
        ranks, distinct = _dense_ranks(
            np.stack([xp[g + k] for k in range(v)]))
        if distinct:
            return depth
        d_idx = np.cumsum(tabs.in_D) - 1
        xprime = np.empty(m_tot, np.int64)
        xprime[d_idx[g % v] * (n_pv // v) + g // v] = ranks
        x, v = xprime, accelerated_next_v(v, len(tabs.D), m_tot)
        depth += 1
    return None


def phase_four_chip(docs, p: int, clock: CompileClock) -> int:
    """BSP build on a p-device mesh == single-device build; counters replay
    `estimate_costs`. Returns the number of devices used."""
    import jax

    from repro.api import SAOptions, SuffixArrayIndex
    from repro.api.index import encode_docs
    from repro.bsp.counters import BSPCounters
    from repro.bsp.suffix_array import estimate_costs
    from repro.launch.mesh import make_sa_mesh

    devs = jax.devices()
    require(len(devs) >= p, f"{p} devices needed, {len(devs)} visible")
    # the host replay of the short-circuit round is plain numpy (it
    # releases the GIL): work it out while the device builds
    text = encode_docs(docs)[0]
    replay = concurrent.futures.ThreadPoolExecutor(1)
    short_circuit = replay.submit(first_distinct_round, text, p)
    replay.shutdown(wait=False)
    ct = BSPCounters()
    opts = SAOptions(mesh=make_sa_mesh(p), counters=ct)
    require(opts.resolve_backend() == "bsp",
            f"mesh plan resolved to {opts.resolve_backend()!r}")
    c0, t0 = clock.secs, time.perf_counter()
    bsp = SuffixArrayIndex.from_docs(docs, opts, sigma=256)
    print(f"four-chip: bsp build of n={bsp.n} on {p} devices in "
          f"{time.perf_counter() - t0:.3f}s, of which compile "
          f"{clock.secs - c0:.3f}s (set-up information)")
    c0, t0 = clock.secs, time.perf_counter()
    one = SuffixArrayIndex.from_docs(docs, SAOptions(backend="jax"),
                                     sigma=256)
    print(f"four-chip: single-device jax build in "
          f"{time.perf_counter() - t0:.3f}s, of which compile "
          f"{clock.secs - c0:.3f}s (set-up information)")
    require(np.array_equal(bsp.sa, one.sa), "bsp SA differs from jax SA")
    print("four-chip: bsp SA equals the single-device jax SA")

    labels = [e["label"] for e in ct.log]
    require(np.array_equal(bsp.text, text), "facade encoded another text")
    short_circuit = short_circuit.result()
    est = estimate_costs(bsp.n, p, distinct_round=short_circuit)
    print(f"four-chip: counters S={ct.supersteps} rounds={ct.rounds} "
          f"H={ct.comm_words}; estimate_costs(distinct_round="
          f"{short_circuit}) S={est.supersteps} rounds={est.rounds}; worst "
          f"case S={estimate_costs(bsp.n, p).supersteps}")
    require(ct.rounds >= 1, "no distributed round ran")
    require((ct.supersteps, ct.rounds) == (est.supersteps, est.rounds)
            and labels == [e["label"] for e in est.log],
            "BSP counters do not replay estimate_costs")
    for d in devs[:p]:
        stats = d.memory_stats()
        if stats is not None:
            peak = stats.get("peak_bytes_in_use", 0)
            print(f"four-chip: device {d.id} peak_bytes_in_use={peak}")
            require(peak > 0, f"device {d.id} held no shard")
    return p


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--seed", type=int, default=0)
    ap.add_argument("--four-chip", action="store_true",
                    help="run only the BSP phase, on a mesh of 4 chips")
    args = ap.parse_args(argv)
    sys.stdout.reconfigure(line_buffering=True)   # progress survives a kill

    src = os.path.join(ROOT, "src")
    if not os.path.isdir(os.path.join(src, "repro")):
        print("chip_smoke: the repro package (src/repro) is not next to "
              "this script; no phase was run", file=sys.stderr)
        return 1
    sys.path.insert(0, src)
    import jax

    devs = jax.devices()
    dev = devs[0]
    print(f"device: platform={dev.platform} kind={dev.device_kind} "
          f"count={len(devs)}")
    if dev.platform != "tpu":
        print(f"chip_smoke: needs a TPU, jax found {dev.platform!r}; no "
              f"phase was run", file=sys.stderr)
        return 1

    from repro.launch.compile_cache import enable_compile_cache
    print(f"compile cache: {enable_compile_cache()}")
    clock = CompileClock()
    t0 = time.perf_counter()
    if args.four_chip:
        count = phase_four_chip(make_docs(N_CHARS, N_DOCS, args.seed),
                                FOUR_CHIPS, clock)
    else:
        run_default_phases(n_chars=N_CHARS, n_docs=N_DOCS, oracle_n=ORACLE_N,
                           pallas_n=PALLAS_N, n_queries=N_QUERIES,
                           n_checked=N_CHECKED, seed=args.seed, clock=clock)
        count = len(devs)
    print(f"total: {time.perf_counter() - t0:.3f}s, compile "
          f"{clock.secs:.3f}s, persistent-cache hits {clock.cache_hits} "
          f"(set-up information)")
    print(json.dumps({"ok": True, "device": {
        "platform": dev.platform, "kind": dev.device_kind, "count": count}}))
    return 0


if __name__ == "__main__":
    sys.exit(main())
