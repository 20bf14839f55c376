"""Run one cell of the benchmark once, on the chip it is started on.

    python bench/run.py --workload <name> --seed <n> --seconds <s> --trace <0|1>

One process: it loads, warms up every shape the cell uses (set-up),
measures for ``--seconds``, checks what the timed path produced against
the plain reference, and prints one JSON object as the last line of
standard output. With ``--trace 0`` the metrics are the cell's end-to-end
metrics; with ``--trace 1`` a part of the window runs under the profiler
and the metrics are the cell's per-layer metrics. The numbers compared
for ``correct`` are the last lines of standard error.

It runs on a TPU only. With no accelerator, fewer chips than the cell
asks for, or no program (``src/repro``) beside it, it exits non-zero and
prints no result.
"""
from __future__ import annotations

import time

T_START = time.perf_counter()

import argparse      # noqa: E402
import json          # noqa: E402
import os            # noqa: E402
import sys           # noqa: E402

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    args = ap.parse_args(argv)
    sys.stdout.reconfigure(line_buffering=True)

    src = os.path.join(ROOT, "src")
    if not os.path.isdir(os.path.join(src, "repro")):
        print("bench: the program (src/repro) is not in this checkout; "
              "nothing was run", file=sys.stderr)
        return 1
    sys.path.insert(0, ROOT)
    from bench.harness import (Spec, enable_compile_cache, execute,
                               prepare_process)
    prepare_process(ROOT)

    spec = Spec(ROOT)
    wl = spec.workload(args.workload)
    import jax
    devs = jax.devices()
    if devs[0].platform != "tpu" or len(devs) < wl["chips"]:
        print(f"bench: cell {wl['name']} needs {wl['chips']} TPU chip(s); "
              f"JAX found {len(devs)} {devs[0].platform!r} device(s); "
              f"nothing was run", file=sys.stderr)
        return 1
    print(f"compile cache: {enable_compile_cache()}", flush=True)
    device = {"platform": devs[0].platform, "kind": devs[0].device_kind,
              "count": len(devs)}
    result = execute(spec, args.workload, seed=args.seed,
                     seconds=args.seconds, trace=bool(args.trace),
                     t_start=T_START, device=device)
    print(json.dumps(result))
    return 0


if __name__ == "__main__":
    sys.exit(main())
