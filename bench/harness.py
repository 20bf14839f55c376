"""One run of one cell: find its files by name, drive it, print the result.

`BENCHMARK.json` names each cell's configuration and traffic mix, and each
metric. Everything that belongs to one of them is a file of its own, found
by that name under the benchmark's directory:

* ``configs/<config>.json`` (the path `BENCHMARK.json` gives) — the
  deployment: corpus laws, sizes, guarantee;
* ``traffic/<traffic>.json`` — the mix, whose ``kind`` names its driver;
* ``drivers/<kind>.py`` — one driver per kind of traffic, with
  ``run(ctx) -> Outcome``;
* ``metrics/<metric>.py`` — one reader per per-layer metric, with
  ``read(run) -> float | None``.

A cell or a metric is added by adding its files and its entry in
`BENCHMARK.json`; no file that is already there changes.
"""
from __future__ import annotations

import dataclasses
import importlib.util
import json
import os
import sys
import tempfile

BENCH_DIR = os.path.dirname(os.path.abspath(__file__))
ROOT = os.path.dirname(BENCH_DIR)


def prepare_process(root: str = ROOT) -> None:
    """Before JAX is imported: make the program and the benchmark
    importable, and give the program the compile cache's directory, a
    fixed path inside this checkout."""
    for p in (os.path.join(root, "src"), root):
        if p not in sys.path:
            sys.path.insert(0, p)
    cache = os.path.join(root, ".jax_cache")
    os.makedirs(cache, exist_ok=True)
    os.environ["JAX_COMPILATION_CACHE_DIR"] = cache


def enable_compile_cache() -> str:
    """Switch the compile cache on, for every program however quickly it
    compiles, so that every run after a cell's first loads them all."""
    import jax
    from repro.launch.compile_cache import enable_compile_cache as enable
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0.0)
    return enable()


class Spec:
    """`BENCHMARK.json` at `root`, and the files it names under `root`."""

    def __init__(self, root: str = ROOT):
        self.root = root
        self.dir = os.path.join(root, os.path.basename(BENCH_DIR))
        with open(os.path.join(root, "BENCHMARK.json")) as f:
            self.doc = json.load(f)

    def workload(self, name: str) -> dict:
        for w in self.doc["workloads"]:
            if w["name"] == name:
                return w
        raise KeyError(f"no workload {name!r} in BENCHMARK.json; known: "
                       f"{[w['name'] for w in self.doc['workloads']]}")

    def config(self, name: str) -> dict:
        for c in self.doc["configs"]:
            if c["name"] == name:
                with open(os.path.join(self.root, c["file"])) as f:
                    return json.load(f)
        raise KeyError(f"no config {name!r} in BENCHMARK.json")

    def traffic(self, name: str) -> dict:
        with open(os.path.join(self.dir, "traffic", name + ".json")) as f:
            return json.load(f)

    def _module(self, kind: str, name: str):
        path = os.path.join(self.dir, kind, name + ".py")
        if not os.path.isfile(path):
            raise FileNotFoundError(f"no {kind[:-1]} file {path}")
        mod_name = "bench_" + kind + "_" + "".join(
            c if c.isalnum() else "_" for c in name)
        spec = importlib.util.spec_from_file_location(mod_name, path)
        mod = importlib.util.module_from_spec(spec)
        spec.loader.exec_module(mod)
        return mod

    def driver(self, kind: str):
        return self._module("drivers", kind)

    def reader(self, metric: str):
        return self._module("metrics", metric)

    def metrics(self, section: str, workload: str) -> list:
        """The entries of `section` ("end_to_end" or "per_layer") that
        the workload reports."""
        return [m for m in self.doc[section]
                if workload in m.get("workloads", [workload])]


class CompileClock:
    """Compiles (persistent-cache reads included) and the seconds they
    took, read from JAX's monitoring events."""

    def __init__(self):
        import jax
        self.secs = 0.0
        self.compiles = 0
        self.cache_hits = 0

        def on_duration(event, duration, **_):
            if event == "/jax/core/compile/backend_compile_duration":
                self.secs += duration
                self.compiles += 1

        def on_event(event, **_):
            if event == "/jax/compilation_cache/cache_hits":
                self.cache_hits += 1

        jax.monitoring.register_event_duration_secs_listener(on_duration)
        jax.monitoring.register_event_listener(on_event)

    def events(self) -> int:
        return self.compiles + self.cache_hits


def trace_events() -> int:
    """Traces of the program's jitted pieces so far (its own counters)."""
    from repro.core import dcv_jax
    return dcv_jax.trace_events()


@dataclasses.dataclass
class Check:
    """One number the run compares, beside its limit (value <= limit)."""
    name: str
    value: float
    limit: float

    @property
    def ok(self) -> bool:
        return self.value <= self.limit


@dataclasses.dataclass
class Outcome:
    """What a driver hands back after its window and its check."""
    setup_s: float
    end_to_end: dict                   # metric name -> value
    attempted: int
    failed: int
    checks: list                       # [Check]
    records: dict = dataclasses.field(default_factory=dict)
    notes: list = dataclasses.field(default_factory=list)


class Run:
    """What a driver gets: the cell's files, its seed and length, the
    clocks, and the means to trace a part of the window."""

    def __init__(self, *, workload: dict, config: dict, traffic: dict,
                 seed: int, seconds: float, trace: bool, t_start: float,
                 system=None):
        self.workload, self.config, self.traffic = workload, config, traffic
        self.seed, self.seconds, self.trace = int(seed), seconds, trace
        self.t_start = t_start
        self.system = system
        self.clock = CompileClock()
        self.memory_peak_bytes = 0
        self.trace_summary = None
        self._trace_dir = None

    def log(self, msg: str) -> None:
        print(f"[{self.workload['name']}] {msg}", flush=True)

    def span(self, name: str):
        """A host span in the profiler's trace (free when not tracing)."""
        import jax
        return jax.profiler.TraceAnnotation(name)

    def trace_start(self) -> None:
        from .trace import reduce as tr
        import jax
        self._trace_dir = tempfile.TemporaryDirectory(prefix="bench-trace-")
        self._trace_cm = tr.record(self._trace_dir.name)
        self._trace_cm.__enter__()
        self._window_span = jax.profiler.TraceAnnotation("window")
        self._window_span.__enter__()

    def trace_stop(self) -> None:
        from .trace import reduce as tr
        self._window_span.__exit__(None, None, None)
        self._trace_cm.__exit__(None, None, None)
        try:
            path = tr.xplane_file(self._trace_dir.name)
            self.trace_summary = tr.reduce(tr.extract(path))
        finally:
            self._trace_dir.cleanup()

    def read_memory_peak(self) -> int:
        """Peak bytes in use on the fullest chip, once the window closed."""
        import jax
        peaks = [(d.memory_stats() or {}).get("peak_bytes_in_use", 0)
                 for d in jax.local_devices()]
        self.memory_peak_bytes = int(max(peaks, default=0))
        return self.memory_peak_bytes


class ReadContext:
    """What a per-layer reader sees: the reduced trace (None when nothing
    was traced), the driver's records and the chip's peaks."""

    def __init__(self, run: Run, outcome: Outcome, peaks: dict):
        self.trace = run.trace_summary
        self.records = outcome.records
        self.peaks = peaks


def execute(spec: Spec, name: str, *, seed: int, seconds: float,
            trace: bool, t_start: float, device, config: dict | None = None,
            traffic: dict | None = None, system=None) -> dict:
    """Drive one run of cell `name` and return its result object.
    `config` and `traffic` replace the cell's files (tests run tiny
    copies); `system` replaces the program under test (controls and
    planted faults)."""
    wl = spec.workload(name)
    config = config if config is not None else spec.config(wl["config"])
    traffic = traffic if traffic is not None else spec.traffic(wl["traffic"])
    driver = spec.driver(traffic["kind"])
    run = Run(workload=wl, config=config, traffic=traffic, seed=seed,
              seconds=seconds, trace=trace, t_start=t_start, system=system)
    out = driver.run(run)
    for note in out.notes:
        run.log(note)
    metrics = {}
    if not trace:
        for m in spec.metrics("end_to_end", name):
            value = (out.setup_s if m["name"] == "setup_s"
                     else out.end_to_end.get(m["name"]))
            if value is not None:
                metrics[m["name"]] = {"value": value, "unit": m["unit"]}
    else:
        from .peaks import peaks_for
        ctx = ReadContext(run, out, peaks_for(device["kind"])
                          if device["platform"] == "tpu" else {})
        for m in spec.metrics("per_layer", name):
            value = spec.reader(m["name"]).read(ctx)
            if value is not None:
                metrics[m["name"]] = {"value": value, "unit": m["unit"]}
    dev = dict(device, memory_peak_bytes=run.memory_peak_bytes)
    result = {"correct": all(c.ok for c in out.checks) and bool(out.checks),
              "attempted": out.attempted, "failed": out.failed,
              "metrics": metrics, "device": dev}
    if trace and run.trace_summary is not None:
        from .trace import reduce as tr
        s = run.trace_summary
        dev.update(busy_s=s["busy_s"], window_s=s["window_s"])
        result["breakdown"] = tr.breakdown(s)
    for c in out.checks:
        print(f"check {c.name} = {c.value} (limit {c.limit}): "
              f"{'ok' if c.ok else 'FAILED'}", file=sys.stderr)
    result["checks"] = {c.name: {"value": c.value, "limit": c.limit}
                        for c in out.checks}
    return result
