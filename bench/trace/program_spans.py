"""The program's own host spans (``sa.*``) in a profiler trace.

The program opens a span at each boundary of the facade and the DC-v
driver (`repro.core.spans` lists them with their counters). They land in
the same ``.xplane.pb`` as the device planes, on the same clock, so this
module reads them beside `reduce`'s figures:

* self time per span name, and per span name and DC-v level: a span's
  duration less the union of the spans nested in it on its own line;
* idle gaps named by the innermost span open across them, program or
  benchmark, with ``@L<level>`` where the span is inside a DC-v level
  (``sa.dcv.runs@L0``);
* the host layers' seconds: the facade's host time (``sa.facade`` not
  covered by ``sa.dcv.*`` spans), the DC-v driver's host time (inside
  ``sa.dcv.level`` spans, not covered by the device-wait spans) and the
  time spent waiting on the window sorts (``sa.dcv.sort``).

Everything is clipped to the benchmark's ``window`` span. `reduce.reduce`
does the gap arithmetic on the benchmark's spans and the program's
together, so busy time, programs and the window read as they do there; a
trace without program spans reads exactly as it does there.
"""
from __future__ import annotations

from .reduce import clip, reduce as reduce_trace, union

PREFIX = "sa."
#: spans in which the host waits on the device (``sa.dcv.lemma1`` only
#: with ``path=device``)
DEVICE_WAIT = ("sa.dcv.sort", "sa.dcv.base")


def extract(path: str) -> list[dict]:
    """Every program span of the trace file: name, start and end (ns), the
    host line (thread) it ran on, and its counters."""
    import jax
    data = jax.profiler.ProfileData.from_file(path)
    out = []
    for plane in data.planes:
        if not plane.name.startswith("/host:"):
            continue
        for i, line in enumerate(plane.lines):
            out.extend({"name": e.name, "start": e.start_ns,
                        "end": e.end_ns, "line": f"{plane.name}#{i}",
                        "stats": dict(e.stats)}
                       for e in line.events if e.name.startswith(PREFIX))
    return out


def nest(spans: list[dict]) -> list[dict]:
    """Copies of `spans` with ``parent`` (index into the result, or None)
    and ``level`` (the DC-v level of the span or of the innermost span
    around it that has one, or None), nesting within each line."""
    out = sorted((dict(s) for s in spans),
                 key=lambda s: (s["line"], s["start"], -s["end"]))
    stack = []
    for i, s in enumerate(out):
        while stack and not (out[stack[-1]]["line"] == s["line"]
                             and s["end"] <= out[stack[-1]]["end"]):
            stack.pop()
        s["parent"] = stack[-1] if stack else None
        s["level"] = s["stats"].get(
            "level", None if s["parent"] is None
            else out[s["parent"]]["level"])
        stack.append(i)
    return out


def label(span: dict) -> str:
    """``name@L<level>`` inside a DC-v level, else the name."""
    lv = span["level"]
    return span["name"] if lv is None else f"{span['name']}@L{lv}"


def _length(intervals) -> float:
    return sum(e - s for s, e in union(intervals))


def _covered(outer, inner) -> float:
    """Length of the union of `inner` that lies inside the union of
    `outer`."""
    inner = union(inner)
    return sum(_length(clip(inner, s, e)) for s, e in union(outer))


def span_times(nested: list[dict], lo, hi) -> tuple[dict, dict]:
    """({name: {count, total_s, self_s}}, the same by `label`), clipped
    to [lo, hi]."""
    kids = [[] for _ in nested]
    for s in nested:
        if s["parent"] is not None:
            kids[s["parent"]].append((s["start"], s["end"]))
    by_name, by_label = {}, {}
    for s, ch in zip(nested, kids):
        own = clip([(s["start"], s["end"])], lo, hi)
        if not own:
            continue
        total = own[0][1] - own[0][0]
        self_ = total - _length(clip(ch, *own[0]))
        for table, key in ((by_name, s["name"]), (by_label, label(s))):
            row = table.setdefault(key, {"count": 0, "total_s": 0.0,
                                         "self_s": 0.0})
            row["count"] += 1
            row["total_s"] += total * 1e-9
            row["self_s"] += self_ * 1e-9
    return by_name, by_label


def layer_seconds(nested: list[dict], lo, hi) -> dict:
    """Seconds of each host layer and device wait in [lo, hi]."""
    def iv(pred):
        return clip([(s["start"], s["end"]) for s in nested if pred(s)],
                    lo, hi)

    facade = iv(lambda s: s["name"] == "sa.facade")
    dcv = iv(lambda s: s["name"].startswith("sa.dcv."))
    levels = iv(lambda s: s["name"] == "sa.dcv.level")
    lemma_dev = iv(lambda s: s["name"] == "sa.dcv.lemma1"
                   and s["stats"].get("path") == "device")
    wait = iv(lambda s: s["name"] in DEVICE_WAIT) + lemma_dev
    return {
        "facade_host_s": (_length(facade) - _covered(facade, dcv)) * 1e-9,
        "dcv_host_s": (_length(levels) - _covered(levels, wait)) * 1e-9,
        "sort_wait_s": _length(iv(lambda s: s["name"] == "sa.dcv.sort"))
        * 1e-9,
        "base_s": _length(iv(lambda s: s["name"] == "sa.dcv.base")) * 1e-9,
        "lemma1_device_s": _length(lemma_dev) * 1e-9,
    }


def reduce(extracted: dict, spans: list[dict],
           window: str = "window") -> dict | None:
    """`reduce.reduce`'s summary of the trace with the program spans among
    the host spans that name the gaps (each by its `label`) and, where the
    trace holds program spans, ``spans`` (per name), ``span_levels`` (per
    `label`) and ``layers`` (`layer_seconds`)."""
    nested = nest(spans)
    named = [(label(s), s["start"], s["end"]) for s in nested]
    summary = reduce_trace(dict(extracted, spans=extracted["spans"] + named),
                           window)
    if summary is None or not spans:
        return summary
    wins = [(s, e) for n, s, e in extracted["spans"] if n == window]
    lo, hi = min(s for s, _ in wins), max(e for _, e in wins)
    by_name, by_label = span_times(nested, lo, hi)
    return dict(summary, spans=by_name, span_levels=by_label,
                layers=layer_seconds(nested, lo, hi))


def per_mtok(summary: dict, key: str, tokens: int) -> float | None:
    """Milliseconds of layer `key` per million `tokens`; None when the
    trace held no program spans."""
    layers = (summary or {}).get("layers")
    if not layers:
        return None
    return layers[key] * 1e3 / (tokens / 1e6)
