"""Batched serving loop: prefill a batch of prompts, then decode with
ring-buffer KV caches / recurrent states.

    python -m repro.launch.serve --arch rwkv6-1.6b --smoke --prompt-len 16 \\
        --gen 32 --batch 4

The paper's own workload is served here too: `--arch suffix-array` obtains
a `repro.api.SuffixArrayIndex` over a synthetic corpus — restored from a
persistent `repro.api.IndexStore` when `--store` points at a warm one,
built through the facade otherwise (BSP backend on a mesh when more than
one device is visible, vectorised JAX otherwise) — and serves substring
count queries through the asynchronous tier (`repro.serve.SAServer`):
open-loop seeded arrivals (`--arrival poisson|onoff|uniform` at
`--offered-qps`), request coalescing into pow2 kernel buckets, admission
control (`--overload-policy`), and per-request queue/service/total
latency percentiles with JIT warmup excluded.

    XLA_FLAGS=--xla_force_host_platform_device_count=8 \\
        python -m repro.launch.serve --arch suffix-array --smoke --queries 64 \\
        --store /tmp/sa_store --query-batch 64 --offered-qps 2000
"""
from __future__ import annotations

import argparse
import time

import jax
import jax.numpy as jnp
import numpy as np

from ..configs import get_config
from ..models.layers import logits_from_embedding
from ..models.lm import (decode_step, encode, forward_hidden,
                         init_decode_states, lm_init)
from .compile_cache import enable_compile_cache


def prefill_then_decode(params, cfg, prompts, gen: int, *, enc_out=None,
                        temperature: float = 0.0, seed: int = 0):
    """prompts int32[B, P] → tokens int32[B, P+gen]. Prefill runs stepwise
    through the decode path (correct for every layer family incl. ring
    buffers); production TPU serving would batch the prompt pass."""
    B, P = prompts.shape
    states = init_decode_states(cfg, B, cache_len=P + gen)
    step = jax.jit(lambda p, t, st, pos: decode_step(
        p, cfg, t, st, pos, enc_out=enc_out))
    key = jax.random.PRNGKey(seed)
    out = [prompts[:, i:i + 1] for i in range(P)]
    logits = None
    for t in range(P):
        logits, states = step(params, out[t], states, jnp.int32(t))
    for g in range(gen):
        if temperature > 0:
            key, sub = jax.random.split(key)
            nxt = jax.random.categorical(sub, logits[:, 0] / temperature,
                                         axis=-1)[:, None]
        else:
            nxt = jnp.argmax(logits[:, 0], axis=-1)[:, None]
        out.append(nxt.astype(jnp.int32))
        logits, states = step(params, out[-1], states, jnp.int32(P + g))
    return jnp.concatenate(out, axis=1)


def serve_open_loop(index, patterns, cfg, *, seed: int = 0,
                    query_batch: int | None = None,
                    offered_qps: float | None = None,
                    arrival: str | None = None,
                    coalesce_max_wait_us: float | None = None,
                    queue_depth: int | None = None,
                    overload_policy: str | None = None) -> list:
    """Serve `patterns` against `index` through `repro.serve.SAServer`.

    One seeded open-loop arrival per pattern (process/rate from cfg or the
    keyword overrides), coalesced into pow2 kernel buckets under admission
    control. Every kernel shape the patterns' lengths can reach is compiled
    in an explicit warmup first, so the printed percentiles describe
    steady state, never JIT time. Returns the `repro.serve.Response` list,
    one per pattern, in arrival order."""
    from ..serve import SAServer, make_arrivals, run_open_loop, summarize

    batch = int(query_batch if query_batch is not None else cfg.query_batch)
    qps = float(offered_qps if offered_qps is not None else cfg.offered_qps)
    proc = arrival if arrival is not None else cfg.arrival
    wait_us = float(coalesce_max_wait_us if coalesce_max_wait_us is not None
                    else cfg.coalesce_max_wait_us)
    depth = int(queue_depth if queue_depth is not None else cfg.queue_depth)
    policy = (overload_policy if overload_policy is not None
              else cfg.overload_policy)

    server = SAServer(index, max_batch=batch,
                      coalesce_max_wait_us=wait_us, queue_depth=depth,
                      overload_policy=policy,
                      gc_hygiene=cfg.gc_hygiene).start()
    try:
        t0 = time.time()
        shapes = server.warmup(pattern_lens=sorted({len(p) for p in patterns}))
        print(f"warmup: {shapes} kernel shapes compiled in "
              f"{time.time() - t0:.2f}s (excluded from percentiles)")
        arrivals = make_arrivals(proc, qps, len(patterns) / qps, seed=seed)
        t0 = time.time()
        responses = run_open_loop(server, patterns, arrivals)
        dt = time.time() - t0
    finally:
        server.stop()
    slo = summarize(responses, dt)
    m = server.metrics.snapshot()
    lat = {k: (f"{v * 1e3:.0f}us" if v is not None else "absent")
           for k, v in [("p50", slo["p50_ms"]), ("p95", slo["p95_ms"]),
                        ("p99", slo["p99_ms"])]}
    print(f"served {slo['offered']} open-loop queries ({proc}@{qps:.0f} "
          f"offered qps) in {dt:.3f}s: ok={slo['ok']} "
          f"rejected={slo['rejected']} shed={slo['shed']} "
          f"goodput={slo['goodput_qps']:.0f} qps")
    print(f"latency p50={lat['p50']} p95={lat['p95']} p99={lat['p99']}; "
          f"coalesced batch mean={m['batch_size']['mean'] or 0:.1f} "
          f"occupancy={m['bucket_occupancy']['mean'] or 0:.2f} "
          f"(policy={policy}, queue_depth={depth}, "
          f"max_wait={wait_us:.0f}us)")
    return responses


def serve_sa_queries(cfg, *, n_chars: int, n_docs: int, n_queries: int,
                     pattern_len: int = 16, seed: int = 0,
                     store_dir: str | None = None,
                     query_batch: int | None = None,
                     offered_qps: float | None = None,
                     arrival: str | None = None,
                     coalesce_max_wait_us: float | None = None,
                     queue_depth: int | None = None,
                     overload_policy: str | None = None,
                     segments: int | None = None,
                     ingest: int | None = None):
    """Serve substring queries through the asynchronous serving tier.

    The index is a persistent artifact: with a `store_dir` (flag or
    `cfg.store_dir`) the corpus is looked up in an
    `repro.api.IndexStore` first — a warm restart *restores* the index
    (builder-cache stats stay at zero builds) instead of rebuilding it.
    On a miss/stale entry the build goes through the facade's auto rule
    (a 1-D mesh over all devices when p > 1, else the vectorised
    single-device DC-v) and is persisted for the next process.

    With ``--segments K`` (or ``cfg.segments``) the corpus is served as a
    `repro.api.SegmentedIndex` of K segments (persisted through a
    `SegmentedIndexStore`), and ``--ingest M`` streams M extra documents
    through `add_docs` AFTER the initial build — each ingest builds one
    small segment, and with a store each sync writes only the segments
    that changed (traffic is printed from the store's own accounting).

    Traffic is open-loop: `repro.serve.make_arrivals` schedules
    ~`n_queries` seeded arrivals (process/rate from cfg or flags) and a
    `repro.serve.SAServer` coalesces them into pow2 kernel buckets under
    admission control. Kernel-shape compiles are paid in an explicit
    warmup pass first, so the reported percentiles describe steady
    state, never JIT time."""
    from ..api import (IndexStore, SegmentedIndex, SegmentedIndexStore,
                       SuffixArrayIndex, builder_cache_stats,
                       corpus_fingerprint, encode_docs)
    from ..bsp.counters import BSPCounters
    from .mesh import make_sa_mesh

    n_segments = int(segments if segments is not None
                     else getattr(cfg, "segments", 0))
    n_ingest = int(ingest if ingest is not None
                   else getattr(cfg, "ingest", 0))
    if n_ingest and not n_segments:
        raise ValueError("--ingest requires --segments > 0: the monolithic "
                         "index has no incremental ingest path")

    mesh = make_sa_mesh() if len(jax.devices()) > 1 else None
    counters = BSPCounters() if mesh is not None else None
    opts = cfg.to_options(mesh=mesh, counters=counters)
    rng = np.random.default_rng(seed)
    doc_len = max(n_chars // max(n_docs, 1), pattern_len + 1)
    docs = [rng.integers(0, 256, size=doc_len) for _ in range(n_docs)]

    store_dir = store_dir if store_dir is not None else cfg.store_dir
    store = entry = None
    t0 = time.time()
    if n_segments > 0:
        per = max(-(-n_docs // n_segments), 1)      # ceil(docs / segments)
        if store_dir:
            store = SegmentedIndexStore(store_dir)
            entry = f"corpus-n{n_chars}-d{n_docs}-s{seed}-seg{n_segments}"
            index, status = store.get_or_build(
                entry,
                lambda: SegmentedIndex.from_docs(docs, opts, sigma=256,
                                                 segment_docs=per),
                options=opts)
            print(f"segment store: {status} (root={store.root}, "
                  f"entry={entry}, {store.stats()})")
        else:
            status = "off"
            index = SegmentedIndex.from_docs(docs, opts, sigma=256,
                                             segment_docs=per)
    elif store_dir:
        store = IndexStore(store_dir)
        text, _, _ = encode_docs(docs)
        # one entry per corpus configuration, so alternating --smoke/full
        # (or batch/seed changes) coexist instead of going mutually stale
        entry = f"corpus-n{n_chars}-d{n_docs}-s{seed}"
        index, status = store.get_or_build(
            entry,
            lambda: SuffixArrayIndex.from_docs(docs, opts, sigma=256),
            options=opts, corpus_sha=corpus_fingerprint(text))
        age = store.manifest_age(entry)
        print(f"index store: {status} (root={store.root}, entry={entry}, "
              f"manifest_age={age:.1f}s, {store.stats()})")
    else:
        status = "off"
        index = SuffixArrayIndex.from_docs(docs, opts, sigma=256)
    build_s = time.time() - t0
    verb = "restored" if status == "hit" else "indexed"
    seg_note = (f", segments={index.n_segments}"
                if n_segments > 0 else "")
    print(f"{verb} {index.n} chars / {index.n_docs} docs in {build_s:.2f}s "
          f"(backend={opts.resolve_backend()}{seg_note}, "
          f"builder_cache={builder_cache_stats()})")

    if n_ingest:
        s0 = builder_cache_stats()
        t0 = time.time()
        for _ in range(n_ingest):
            index.add_docs([rng.integers(0, 256, size=doc_len)])
        s1 = builder_cache_stats()
        built = (s1["hits"] + s1["misses"]) - (s0["hits"] + s0["misses"])
        line = (f"ingested {n_ingest} docs in {time.time() - t0:.2f}s: "
                f"{built} segment builds (incl. compaction merges), "
                f"segments={index.n_segments}")
        if store is not None:
            traffic = store.save(entry, index)
            line += (f", synced {traffic['segments_written']} segments "
                     f"(-{traffic['segments_deleted']} dropped)")
        print(line)
    if counters is not None and counters.supersteps:
        from ..bsp.psort import resolve_bsp_sort_impl
        impl = resolve_bsp_sort_impl(opts.sort_impl, opts.pack_keys)
        print(f"bsp costs: S={counters.supersteps} supersteps over "
              f"{counters.rounds} distributed rounds, "
              f"H={counters.comm_words} words, W={counters.work} ops "
              f"(sort_impl={impl})")

    # half the queries are planted substrings (must hit), half random
    patterns, planted = [], set()
    for q in range(n_queries):
        if q % 2 == 0:
            d = rng.integers(0, n_docs)
            at = rng.integers(0, doc_len - pattern_len)
            patterns.append(docs[d][at:at + pattern_len])
            planted.add(q)
        else:
            patterns.append(rng.integers(0, 256, size=pattern_len))

    responses = serve_open_loop(
        index, patterns, cfg, seed=seed, query_batch=query_batch,
        offered_qps=offered_qps, arrival=arrival,
        coalesce_max_wait_us=coalesce_max_wait_us, queue_depth=queue_depth,
        overload_policy=overload_policy)

    # planted patterns that were admitted must hit; spot-check counts
    # against the closed-loop batched engine (same index, same kernel)
    ok_hits = [r for i, r in enumerate(responses)
               if r.ok and (i % n_queries) in planted]
    assert all(r.count >= 1 for r in ok_hits), "planted patterns must hit"
    check = [(i, r) for i, r in enumerate(responses) if r.ok][:8]
    if check:
        want = index.count_batch([patterns[i % n_queries] for i, _ in check])
        assert [r.count for _, r in check] == list(want), "tier != engine"
    return index


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--arch", required=True)
    ap.add_argument("--smoke", action="store_true")
    ap.add_argument("--batch", type=int, default=4)
    ap.add_argument("--prompt-len", type=int, default=16)
    ap.add_argument("--gen", type=int, default=32)
    ap.add_argument("--temperature", type=float, default=0.0)
    ap.add_argument("--queries", type=int, default=64,
                    help="query count for --arch suffix-array")
    ap.add_argument("--store", default=None,
                    help="IndexStore root for --arch suffix-array (a warm "
                         "restart restores the index instead of rebuilding)")
    ap.add_argument("--query-batch", type=int, default=None,
                    help="max coalesced batch for --arch suffix-array "
                         "(default: cfg.query_batch)")
    ap.add_argument("--offered-qps", type=float, default=None,
                    help="open-loop offered load (default: cfg.offered_qps)")
    ap.add_argument("--arrival", default=None,
                    choices=["uniform", "poisson", "onoff"],
                    help="arrival process (default: cfg.arrival)")
    ap.add_argument("--coalesce-max-wait-us", type=float, default=None,
                    help="batch-window deadline in µs "
                         "(default: cfg.coalesce_max_wait_us)")
    ap.add_argument("--queue-depth", type=int, default=None,
                    help="admission bound on queued requests "
                         "(default: cfg.queue_depth)")
    ap.add_argument("--overload-policy", default=None,
                    choices=["none", "reject", "shed"],
                    help="behavior past queue_depth (default: "
                         "cfg.overload_policy)")
    ap.add_argument("--segments", type=int, default=None,
                    help="serve a SegmentedIndex with this many segments "
                         "for --arch suffix-array (default: cfg.segments; "
                         "0 = monolithic)")
    ap.add_argument("--ingest", type=int, default=None,
                    help="docs to stream through add_docs after the initial "
                         "build (requires --segments; default: cfg.ingest)")
    args = ap.parse_args()
    enable_compile_cache()

    cfg = get_config(args.arch)
    if getattr(cfg, "name", "") == "suffix-array":
        n_chars = 20_000 if args.smoke else cfg.n
        return serve_sa_queries(cfg, n_chars=n_chars, n_docs=args.batch,
                                n_queries=args.queries,
                                pattern_len=args.prompt_len,
                                store_dir=args.store,
                                query_batch=args.query_batch,
                                offered_qps=args.offered_qps,
                                arrival=args.arrival,
                                coalesce_max_wait_us=args.coalesce_max_wait_us,
                                queue_depth=args.queue_depth,
                                overload_policy=args.overload_policy,
                                segments=args.segments,
                                ingest=args.ingest)
    if args.smoke:
        cfg = cfg.smoke()
    params, _ = lm_init(jax.random.PRNGKey(0), cfg)
    rng = np.random.default_rng(0)
    prompts = jnp.asarray(
        rng.integers(0, cfg.vocab_size, (args.batch, args.prompt_len)),
        jnp.int32)
    enc_out = None
    if cfg.is_encdec:
        enc = 0.02 * rng.standard_normal(
            (args.batch, cfg.enc_seq, cfg.d_model)).astype(np.float32)
        enc_out = encode(params, cfg, jnp.asarray(enc))

    t0 = time.time()
    toks = prefill_then_decode(params, cfg, prompts, args.gen,
                               enc_out=enc_out,
                               temperature=args.temperature)
    dt = time.time() - t0
    n_new = args.batch * args.gen
    print(f"generated {n_new} tokens in {dt:.2f}s "
          f"({n_new / dt:.1f} tok/s batched)")
    print("sample:", np.asarray(toks[0])[:32].tolist())
    assert toks.shape == (args.batch, args.prompt_len + args.gen)
    return toks


if __name__ == "__main__":
    main()
