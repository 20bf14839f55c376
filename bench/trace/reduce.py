"""From the profiler's trace to device busy time, time per program and
named idle gaps.

`record` runs a block under the JAX profiler with the Python tracer off
and writes the `.xplane.pb` to a directory; `extract` reads the planes
into plain intervals; `reduce` does the arithmetic. The traced window is
the host span named ``window`` that the benchmark opens around the
traced part of its run; everything is clipped to it.

* busy: the union of the intervals of the device's operations (the
  ``XLA Ops`` line of each ``/device:...`` plane), averaged over devices;
* programs: the device time of each compiled program (the ``XLA Modules``
  line), by name, with the number of its runs;
* gaps: the intervals of the window in which no operation ran, each named
  by the innermost of the benchmark's host spans open at its middle.
"""
from __future__ import annotations

import contextlib
import glob
import os
import re

#: host spans the benchmark's own files open; gaps are named by them
HOST_SPANS = ("window", "warmup", "generate", "build", "check")

_DEVICE_PLANE = re.compile(r"^/device:(?!CPU)[A-Za-z]+:\d+$")
OPS_LINE = "XLA Ops"
MODULES_LINE = "XLA Modules"


@contextlib.contextmanager
def record(log_dir: str):
    """Profile the block into `log_dir` (host spans kept, Python calls not
    traced, so the trace stays small and the host keeps its pace)."""
    import jax
    opts = jax.profiler.ProfileOptions()
    opts.python_tracer_level = 0
    opts.host_tracer_level = 2
    jax.profiler.start_trace(log_dir, profiler_options=opts)
    try:
        yield
    finally:
        jax.profiler.stop_trace()


def xplane_file(log_dir: str) -> str:
    files = glob.glob(os.path.join(log_dir, "**", "*.xplane.pb"),
                      recursive=True)
    if len(files) != 1:
        raise FileNotFoundError(f"expected one .xplane.pb under {log_dir}, "
                                f"found {len(files)}")
    return files[0]


def extract(path: str, span_names=HOST_SPANS) -> dict:
    """Plain intervals (ns) from one trace file: per device its operations
    and its program runs, and the host spans named in `span_names`."""
    import jax
    data = jax.profiler.ProfileData.from_file(path)
    devices, spans = [], []
    for plane in data.planes:
        if _DEVICE_PLANE.match(plane.name):
            dev = {"name": plane.name, "ops": [], "modules": []}
            for line in plane.lines:
                key = {OPS_LINE: "ops", MODULES_LINE: "modules"}.get(
                    line.name)
                if key is not None:
                    dev[key] = [(e.name, e.start_ns, e.end_ns)
                                for e in line.events]
            devices.append(dev)
        elif plane.name.startswith("/host:"):
            for line in plane.lines:
                spans.extend((e.name, e.start_ns, e.end_ns)
                             for e in line.events if e.name in span_names)
    return {"devices": devices, "spans": spans}


def union(intervals):
    """Sorted disjoint intervals covering the given (start, end) pairs."""
    out = []
    for s, e in sorted((s, e) for s, e in intervals if e > s):
        if out and s <= out[-1][1]:
            out[-1][1] = max(out[-1][1], e)
        else:
            out.append([s, e])
    return [tuple(x) for x in out]


def clip(intervals, lo, hi):
    return [(max(s, lo), min(e, hi)) for s, e in intervals
            if min(e, hi) > max(s, lo)]


def reduce(extracted: dict, window: str = "window") -> dict | None:
    """Busy, per-program and gap figures of the traced window, in seconds.
    None when the trace holds no window span or no device plane: there is
    then nothing to read, and no metric is made up."""
    wins = [(s, e) for name, s, e in extracted["spans"] if name == window]
    devices = extracted["devices"]
    if not wins or not devices:
        return None
    lo, hi = min(s for s, _ in wins), max(e for _, e in wins)
    busy_ns, programs, gaps = [], {}, []
    for dev in devices:
        busy = union(clip([(s, e) for _, s, e in dev["ops"]], lo, hi))
        busy_ns.append(sum(e - s for s, e in busy))
        for name, s, e in dev["modules"]:
            c = clip([(s, e)], lo, hi)
            if c:
                tot, runs = programs.get(name, (0.0, 0))
                programs[name] = (tot + c[0][1] - c[0][0], runs + 1)
        edges = [lo] + [x for iv in busy for x in iv] + [hi]
        gaps.extend((edges[i], edges[i + 1])
                    for i in range(0, len(edges), 2)
                    if edges[i + 1] > edges[i])
    host = [(n, s, e) for n, s, e in extracted["spans"] if n != window]
    named = []
    for s, e in gaps:
        mid = (s + e) / 2
        open_ = [(hs, -he, n) for n, hs, he in host if hs <= mid < he]
        named.append((max(open_)[2] if open_ else "none", (e - s) * 1e-9))
    named.sort(key=lambda g: -g[1])
    return {
        "window_s": (hi - lo) * 1e-9,
        "busy_s": sum(busy_ns) / len(busy_ns) * 1e-9,
        "programs": {k: {"device_s": v[0] * 1e-9, "runs": v[1]}
                     for k, v in programs.items()},
        "gaps": named,
        "devices": len(devices),
    }


def program_time(summary: dict, fragment: str) -> tuple[float, int]:
    """(device seconds, runs) of the programs whose name holds `fragment`
    (a jitted function's name, e.g. ``_lsd_argsort``)."""
    hit = [v for k, v in summary["programs"].items() if fragment in k]
    return (sum(v["device_s"] for v in hit), sum(v["runs"] for v in hit))


def breakdown(summary: dict, top: int = 10) -> dict:
    """The device programs that took most time and the longest idle gaps,
    each as [name, seconds], for the result line."""
    progs = sorted(summary["programs"].items(),
                   key=lambda kv: -kv[1]["device_s"])[:top]
    return {"device_ops": [[k, v["device_s"]] for k, v in progs],
            "idle_gaps": [[n, s] for n, s in summary["gaps"][:top]]}
