"""The controls of `correct`: runs of a cell with its exact guarantee
broken on purpose, which the comparison has to refuse.

    python bench/tools/control.py --workload <name> --seeds 11 12 13 \
        [--seconds 10] [--system control|<fault>]

Each seed runs the cell once in this one process, with the system under
test replaced, and prints the numbers compared beside their limits. The
benchmark's own runs never run this.

* ``control`` — the step a later change could be tempted to take: the
  suffix array exact only to `DEPTH` = 50 tokens (ties by position),
  computed by the reference: ExactSubstr's threshold, which is all that
  a dedup pass reads.
* the planted faults, each in the program's own path: ``stale`` (a build
  hands back the previous shard's suffix array), ``swap`` (two entries
  of each suffix array swapped), ``half`` (half of each suffix array
  left out).
"""
from __future__ import annotations

import argparse
import json
import os
import sys
import time

import numpy as np

#: tokens the control orders suffixes by
DEPTH = 50


def depth_sorted_sa(text, depth: int) -> np.ndarray:
    """Positions ordered by their first `depth` symbols only, ties by
    position (past the end reads below every symbol), by prefix doubling:
    ranks of depth h and h + step, step <= h, combine exactly."""
    text = np.asarray(text, np.int64)
    n = len(text)
    rank = np.unique(text, return_inverse=True)[1].astype(np.int64) + 1
    h = 1
    while h < depth:
        step = min(h, depth - h)
        nxt = np.zeros(n, np.int64)
        nxt[:n - step] = rank[step:]
        rank = np.unique(rank * (n + 2) + nxt,
                         return_inverse=True)[1].astype(np.int64) + 1
        h += step
    return np.argsort(rank, kind="stable").astype(np.int32)


class BuildControl:
    def __init__(self, depth: int):
        self.depth = depth

    def build(self, docs):
        from bench.reference import encoded_text
        return depth_sorted_sa(encoded_text(docs), self.depth)


class BuildFault:
    """The program's build with one planted fault."""

    def __init__(self, kind: str):
        from bench.program import Builder
        self.program, self.kind, self.last = Builder(), kind, None

    def build(self, docs):
        sa = np.array(self.program.build(docs))
        if self.kind == "stale":
            out = sa if self.last is None else self.last
            self.last = sa
            return out
        if self.kind == "swap":
            sa[[0, len(sa) // 2]] = sa[[len(sa) // 2, 0]]
            return sa
        if self.kind == "half":
            return sa[:len(sa) // 2]
        raise ValueError(f"no build fault {self.kind!r}")


def system_for(kind_of_traffic: str, name: str):
    if kind_of_traffic != "build_stream":
        raise ValueError(f"no control for traffic kind {kind_of_traffic!r}")
    return BuildControl(DEPTH) if name == "control" else BuildFault(name)


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seeds", type=int, nargs="+", required=True)
    ap.add_argument("--seconds", type=float, default=10.0)
    ap.add_argument("--system", default="control")
    args = ap.parse_args(argv)
    root = os.path.dirname(os.path.dirname(os.path.dirname(
        os.path.abspath(__file__))))
    sys.path.insert(0, root)
    from bench.harness import (Spec, enable_compile_cache, execute,
                               prepare_process)
    prepare_process(root)
    import jax
    enable_compile_cache()
    spec = Spec(root)
    wl = spec.workload(args.workload)
    kind = spec.traffic(wl["traffic"])["kind"]
    dev = jax.devices()[0]
    device = {"platform": dev.platform, "kind": dev.device_kind,
              "count": len(jax.devices())}
    for seed in args.seeds:
        res = execute(spec, args.workload, seed=seed, seconds=args.seconds,
                      trace=False, t_start=time.perf_counter(),
                      device=device, system=system_for(kind, args.system))
        print(json.dumps({"system": args.system, "seed": seed,
                          "correct": res["correct"],
                          "checks": res["checks"]}), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
