"""Where a traced shard build spends its time, by the program's spans.

    python bench/tools/phases.py --workload build.dedup-shard --seed <n> \
        --seconds 40 [--out results/phases.json]

Runs the cell once with ``--trace 1`` through the harness, as
`bench/run.py` does: set-up, window, the check against the reference and
the cell's per-layer metrics. The one difference is the reduction of the
traced build: `bench.trace.program_spans` reads the program's ``sa.*``
spans beside the benchmark's, so the result line's idle gaps are named by
span and DC-v level. The result line is printed last; the JSON written to
``--out`` adds time per span and per level, the host layers per million
tokens and the counters of every span. It runs on a TPU only; the
benchmark's own runs never run it.
"""
from __future__ import annotations

import time

T_START = time.perf_counter()

import argparse      # noqa: E402
import json          # noqa: E402
import os            # noqa: E402
import sys           # noqa: E402
from unittest import mock  # noqa: E402

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))

#: the host layers' keys in `program_spans.layer_seconds`, by the name
#: each would carry as a per-layer metric in ms per million tokens
LAYERS = {"build.facade_host_ms_per_mtok": "facade_host_s",
          "build.dcv_host_ms_per_mtok": "dcv_host_s",
          "build.sort_wait_ms_per_mtok": "sort_wait_s"}


def traced_execute(spec, name: str, *, config: dict, **kw) -> dict:
    """`harness.execute(spec, name, trace=True, config=config, **kw)`
    with the traced build reduced by the program's spans too. Returns
    {"result": the result object, "summary": the reduction, "metrics":
    the host layers per million tokens, "counters": [[label, counters]]}."""
    from bench import harness
    from bench.trace import program_spans as ps
    from bench.trace import reduce as tr
    traced = {"summary": None, "spans": []}

    class SpanRun(harness.Run):
        def trace_stop(self) -> None:
            self._window_span.__exit__(None, None, None)
            self._trace_cm.__exit__(None, None, None)
            try:
                path = tr.xplane_file(self._trace_dir.name)
                traced["spans"] = ps.extract(path)
                self.trace_summary = ps.reduce(tr.extract(path),
                                               traced["spans"])
                traced["summary"] = self.trace_summary
            finally:
                self._trace_dir.cleanup()

    with mock.patch.object(harness, "Run", SpanRun):
        result = harness.execute(spec, name, trace=True, config=config, **kw)
    tokens = int(config["shard_tokens"])
    return {"result": result, "summary": traced["summary"],
            "metrics": {m: ps.per_mtok(traced["summary"], key, tokens)
                        for m, key in LAYERS.items()},
            "counters": [[ps.label(s), s["stats"]]
                         for s in ps.nest(traced["spans"]) if s["stats"]]}


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--workload", default="build.dedup-shard")
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, default=40)
    ap.add_argument("--out", default=os.path.join("results", "phases.json"))
    args = ap.parse_args(argv)
    sys.stdout.reconfigure(line_buffering=True)
    sys.path.insert(0, ROOT)
    from bench import harness
    harness.prepare_process(ROOT)
    import jax
    dev = jax.devices()[0]
    if dev.platform != "tpu":
        print(f"phases: needs a TPU; JAX found {dev.platform!r}; nothing "
              f"was run", file=sys.stderr)
        return 1
    print(f"compile cache: {harness.enable_compile_cache()}", flush=True)
    spec = harness.Spec(ROOT)
    out = traced_execute(
        spec, args.workload,
        config=spec.config(spec.workload(args.workload)["config"]),
        seed=args.seed, seconds=args.seconds, t_start=T_START,
        device={"platform": dev.platform, "kind": dev.device_kind,
                "count": len(jax.devices())})
    os.makedirs(os.path.dirname(os.path.abspath(args.out)), exist_ok=True)
    with open(args.out, "w") as f:
        json.dump(dict(out, workload=args.workload, seed=args.seed), f,
                  indent=1)
    print(json.dumps({"layers_ms_per_mtok": out["metrics"],
                      "layers_s": (out["summary"] or {}).get("layers")}))
    print(json.dumps(out["result"]))
    return 0


if __name__ == "__main__":
    sys.exit(main())
