"""Traffic kind ``build_stream``: corpus shards indexed back to back.

The documents of each shard are drawn once from the mix's
``content_seed``, and the run's ``--seed`` draws their order: how long a
shard takes depends on its content (2^24-token shards of this law took
either ~53 s or 65-73 s on a v5e, each seed the same on every run), so
every seed gets the same work, in another order.

Set-up builds one warm-up shard that carries a planted repeat longer than
any the laws make, so the recursion reaches its deepest level and every
program shape the window can meet is compiled before it opens. The window
then indexes shard i through `SuffixArrayIndex.from_docs` with default
options, one after the other, and starts builds until the builds have
taken ``--seconds``; it ends when the last build ends. Generation is not
the system under test and is not timed. Every shard the window built is
then checked against the plain reference.
"""
from __future__ import annotations

import gc
import time

import numpy as np

from bench.data.tokens import make_corpus, plant_repeat, reorder
from bench.harness import Check, Outcome, trace_events
from bench.program import Builder
from bench.reference import encoded_text, suffix_array_faults

#: seed streams of this kind
WARMUP, WINDOW = 0, 1


def run(run) -> Outcome:
    cfg, traffic = run.config, run.traffic
    system = run.system or Builder()
    n = int(cfg["shard_tokens"])
    content = int(traffic["content_seed"])

    with run.span("generate"):
        warm = reorder(make_corpus(cfg["corpus"], n, content, WARMUP),
                       run.seed, WARMUP)
        planted = plant_repeat(warm, int(traffic["warmup_repeat_tokens"]))
    c_setup = run.clock.secs
    with run.span("warmup"):
        system.build(warm)
    run.log(f"warm-up shard: {len(warm)} documents, planted repeat of "
            f"{planted} tokens; compile {run.clock.secs - c_setup:.3f} s")

    shards, build_s = [], []
    events0, traces0 = run.clock.events(), trace_events()
    t_window = time.perf_counter()
    while not build_s or sum(build_s) < run.seconds:
        with run.span("generate"):
            docs = reorder(make_corpus(cfg["corpus"], n, content, WINDOW,
                                       len(shards)),
                           run.seed, WINDOW, len(shards))
        gc.collect()            # the harness's own garbage, off the clock
        traced = run.trace and not shards
        if traced:
            run.trace_start()
        t0 = time.perf_counter()
        with run.span("build"):
            sa = system.build(docs)
        build_s.append(time.perf_counter() - t0)
        if traced:
            run.trace_stop()
        lens = np.fromiter((len(d) for d in docs), np.int64, len(docs))
        shards.append((np.concatenate(docs), lens, np.asarray(sa)))
        del docs, sa
    compiles = run.clock.events() - events0
    traces = trace_events() - traces0
    run.read_memory_peak()

    with run.span("check"):
        faults = [suffix_array_faults(
            encoded_text(np.split(flat, np.cumsum(lens)[:-1])), sa)
            for flat, lens, sa in shards]
    total_s = sum(build_s)
    notes = [
        f"window: {len(shards)} shards of {n} tokens in {total_s:.6f} s of "
        f"builds (first builds: "
        f"{', '.join(f'{s:.3f}' for s in build_s[:12])} s)",
        f"window: compiles or cache loads {compiles}, program traces "
        f"{traces} (both should be 0)",
        f"check: {sum(faults)} suffix-array faults over {len(shards)} "
        f"shards",
    ]
    return Outcome(
        setup_s=t_window - run.t_start,
        end_to_end={"build_tokens_per_s": n * len(shards) / total_s},
        attempted=len(shards),
        failed=sum(1 for f in faults if f),
        checks=[Check("sa_faults", sum(faults), 0)],
        records={"traced_tokens": n},
        notes=notes)
