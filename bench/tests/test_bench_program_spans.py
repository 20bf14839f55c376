"""The program's spans in the trace: hand-worked nested spans, and the span
tree of real builds recorded here."""
import numpy as np
import pytest

from bench.harness import Spec
from bench.trace import program_spans as ps
from bench.trace import reduce as tr

MS = 1_000_000  # ns
TOKENS = 2_000_000


def program(line="/host:CPU#0"):
    """A build with two DC-v levels (hand-worked in the tests below)."""
    def s(name, a, b, **stats):
        return {"name": name, "start": a * MS, "end": b * MS, "line": line,
                "stats": stats}
    return [
        s("sa.facade", 1, 89),
        s("sa.encode", 1, 6),
        s("sa.dcv.level", 6, 88, level=0, n_v=2_000_010, v=3),
        s("sa.dcv.pack", 6, 9),
        s("sa.dcv.sort", 9, 31),
        s("sa.dcv.runs", 31, 35),
        s("sa.dcv.rank", 35, 40, distinct=0),
        s("sa.dcv.level", 40, 70, level=1, n_v=1_333_344, v=8),
        s("sa.dcv.pack", 40, 45),
        s("sa.dcv.sort", 45, 61),
        s("sa.dcv.runs", 61, 62),
        s("sa.dcv.rank", 62, 64, distinct=0),
        s("sa.dcv.base", 64, 66),
        s("sa.dcv.ties", 66, 69),
        s("sa.dcv.lemma1", 67, 68, ties=12, width=4, path="host"),
        s("sa.dcv.ties", 72, 87),
        s("sa.dcv.lemma1", 80, 86, ties=5000, width=32, path="device"),
    ]


def bench_trace():
    """One device busy 10-30 and 50-60 ms of the 0-100 ms window; the
    benchmark's own spans around the build and the check."""
    ops = [("sort.1", 10 * MS, 30 * MS), ("sort.2", 50 * MS, 60 * MS)]
    modules = [("jit__lsd_argsort(1)", 10 * MS, 30 * MS),
               ("jit__lsd_argsort(2)", 50 * MS, 60 * MS)]
    spans = [("window", 0, 100 * MS), ("build", 0, 90 * MS),
             ("check", 90 * MS, 100 * MS)]
    return {"devices": [{"name": "/device:TPU:0", "ops": ops,
                         "modules": modules}], "spans": spans}


def test_nest_parents_and_levels():
    nested = ps.nest(program())
    by = {(s["name"], s["start"]): s for s in nested}
    top = by[("sa.dcv.level", 6 * MS)]
    assert nested[top["parent"]]["name"] == "sa.facade"
    inner = by[("sa.dcv.pack", 40 * MS)]
    assert nested[inner["parent"]]["stats"]["level"] == 1
    assert [ps.label(by[k]) for k in [("sa.facade", 1 * MS),
                                      ("sa.encode", 1 * MS),
                                      ("sa.dcv.runs", 31 * MS),
                                      ("sa.dcv.base", 64 * MS),
                                      ("sa.dcv.lemma1", 80 * MS)]] == [
        "sa.facade", "sa.encode", "sa.dcv.runs@L0", "sa.dcv.base@L1",
        "sa.dcv.lemma1@L0"]
    # another line nests on its own
    other = ps.nest(program() + [dict(program()[3], line="/host:CPU#1")])
    assert [s["parent"] for s in other if s["line"] == "/host:CPU#1"] == [
        None]


def test_self_time_hand_worked():
    s = ps.reduce(bench_trace(), program())
    spans, lv = s["spans"], s["span_levels"]
    # facade 88 ms less encode (5) and the top level (82)
    assert spans["sa.facade"]["self_s"] == pytest.approx(0.001)
    # levels: 82 - (3+22+4+5+30+15) = 3, and 30 - (5+16+1+2+2+3) = 1
    assert lv["sa.dcv.level@L0"]["self_s"] == pytest.approx(0.003)
    assert lv["sa.dcv.level@L1"]["self_s"] == pytest.approx(0.001)
    assert spans["sa.dcv.level"] == {"count": 2,
                                     "total_s": pytest.approx(0.112),
                                     "self_s": pytest.approx(0.004)}
    assert spans["sa.dcv.sort"]["count"] == 2
    assert spans["sa.dcv.sort"]["self_s"] == pytest.approx(0.038)
    assert lv["sa.dcv.ties@L0"]["self_s"] == pytest.approx(0.009)
    assert lv["sa.dcv.ties@L1"]["self_s"] == pytest.approx(0.002)
    assert lv["sa.dcv.base@L1"]["total_s"] == pytest.approx(0.002)


def test_gaps_named_by_program_span_and_level():
    s = ps.reduce(bench_trace(), program())
    # gaps 0-10 (mid 5: encode), 30-50 (mid 40: level 1's pack),
    # 60-100 (mid 80: level 0's lemma1)
    assert [(n, round(x, 6)) for n, x in s["gaps"]] == [
        ("sa.dcv.lemma1@L0", 0.040), ("sa.dcv.pack@L1", 0.020),
        ("sa.encode", 0.010)]
    assert tr.breakdown(s)["idle_gaps"][0] == ["sa.dcv.lemma1@L0",
                                               pytest.approx(0.04)]


def test_layer_seconds_account_for_the_facade():
    s = ps.reduce(bench_trace(), program())
    lay = s["layers"]
    assert lay["facade_host_s"] == pytest.approx(0.006)   # 1-6, 88-89
    # levels 6-88 (82) less sorts (22 + 16), base (2), device lemma1 (6)
    assert lay["dcv_host_s"] == pytest.approx(0.036)
    assert lay["sort_wait_s"] == pytest.approx(0.038)
    assert lay["base_s"] == pytest.approx(0.002)
    assert lay["lemma1_device_s"] == pytest.approx(0.006)
    assert sum(lay.values()) == pytest.approx(
        s["spans"]["sa.facade"]["total_s"])


@pytest.mark.parametrize("key, value", [
    ("facade_host_s", 3.0), ("dcv_host_s", 18.0), ("sort_wait_s", 19.0)])
def test_per_mtok(key, value):
    s = ps.reduce(bench_trace(), program())
    assert ps.per_mtok(s, key, TOKENS) == pytest.approx(value)
    assert ps.per_mtok(tr.reduce(bench_trace()), key, TOKENS) is None
    assert ps.per_mtok(None, key, TOKENS) is None


def test_spans_clipped_to_the_window():
    late = program() + [{"name": "sa.facade", "start": 95 * MS,
                         "end": 120 * MS, "line": "/host:CPU#0",
                         "stats": {}}]
    s = ps.reduce(bench_trace(), late)
    assert s["spans"]["sa.facade"]["count"] == 2
    assert s["spans"]["sa.facade"]["total_s"] == pytest.approx(0.093)
    assert s["layers"]["facade_host_s"] == pytest.approx(0.011)


def test_existing_figures_unchanged_by_program_spans():
    x = bench_trace()
    base, full = tr.reduce(x), ps.reduce(x, program())
    for key in ("window_s", "busy_s", "programs", "devices"):
        assert full[key] == base[key]
    assert ps.reduce(x, []) == base
    assert ps.reduce({"devices": [], "spans": x["spans"]}, program()) is None
    spec = Spec()
    ctx = {name: type("Ctx", (), {"trace": summary,
                                  "records": {"traced_tokens": TOKENS}})
           for name, summary in (("base", base), ("full", full))}
    for m in spec.doc["per_layer"]:
        reader = spec.reader(m["name"])
        assert reader.read(ctx["full"]) == reader.read(ctx["base"])
        assert reader.read(ctx["base"]) is not None


def _recorded_tree(tmp_path, monkeypatch, sort_impl):
    """A from_docs build of ~3,000 tokens over a 4-symbol alphabet (deep
    enough to recurse), recorded by the benchmark's profiler; returns the
    nested program spans and the number of `lsd_argsort` calls."""
    from repro.api import SAOptions, SuffixArrayIndex
    from repro.core import lsd_sort
    calls = []
    real = lsd_sort.lsd_argsort

    def counted(*a, **k):
        calls.append(1)
        return real(*a, **k)

    monkeypatch.setattr(lsd_sort, "lsd_argsort", counted)
    rng = np.random.default_rng(7)
    docs = [rng.integers(0, 4, size=k) for k in (1200, 900, 800)]
    opts = SAOptions(sort_impl=sort_impl)
    SuffixArrayIndex.from_docs(docs, opts)           # compile, untraced
    calls.clear()
    with tr.record(str(tmp_path)):
        idx = SuffixArrayIndex.from_docs(docs, opts)
    from repro.core.oracle import suffix_array_doubling
    np.testing.assert_array_equal(idx.sa, suffix_array_doubling(idx.text))
    spans = ps.extract(tr.xplane_file(str(tmp_path)))
    return ps.nest(spans), len(calls)


@pytest.mark.parametrize("sort_impl", ["lax", "radix"])
def test_recorded_build_span_tree(tmp_path, monkeypatch, sort_impl):
    nested, argsorts = _recorded_tree(tmp_path, monkeypatch, sort_impl)

    def children(i):
        return [s for s in nested if s["parent"] == i]

    (fi, facade), = [(i, s) for i, s in enumerate(nested)
                     if s["name"] == "sa.facade"]
    assert facade["parent"] is None and facade["stats"] == {}
    kids = children(fi)
    assert [s["name"] for s in kids] == ["sa.encode", "sa.dcv.level"]
    li = nested.index(kids[1])
    top = nested[li]
    assert top["stats"]["level"] == 0
    assert top["stats"]["n_v"] >= 2903 and top["stats"]["v"] >= 3
    # radix's word sort yields the run boundaries: no runs span
    runs = ["sa.dcv.runs"] if sort_impl == "lax" else []
    assert [s["name"] for s in children(li)] == [
        "sa.dcv.pack", "sa.dcv.pack", "sa.dcv.sort", *runs, "sa.dcv.rank",
        "sa.dcv.level", "sa.dcv.ties"]
    lv1 = children(li)[-2]
    assert lv1["stats"]["level"] == 1
    assert children(li)[-3]["stats"]["distinct"] == 0
    sorts = [s for s in nested if s["name"] == "sa.dcv.sort"]
    levels = [s for s in nested if s["name"] == "sa.dcv.level"]
    assert len(sorts) == len(levels)
    assert argsorts == (len(sorts) if sort_impl == "lax" else 0)
    assert all(ps.label(s).endswith(f"@L{s['level']}") for s in sorts)
    for i, s in enumerate(levels):
        j = nested.index(s)
        names = [c["name"] for c in children(j)]
        rank, = [c for c in children(j) if c["name"] == "sa.dcv.rank"]
        # a level recurses (deeper level or base case) iff its samples tie
        assert (rank["stats"]["distinct"] == 0) == (
            "sa.dcv.level" in names or "sa.dcv.base" in names)


def test_phases_tool_reads_a_recorded_build():
    """The phases tool drives the cell through the harness, at 2^12 tokens
    on the CPU: the run and its check are the cell's, the program's spans
    and their counters are read; a CPU trace has no device plane, so no
    figure is made up."""
    from bench import harness
    from bench.tools import phases
    spec = Spec()
    wl = spec.workload("build.dedup-shard")
    cfg = dict(spec.config(wl["config"]), shard_tokens=1 << 12)
    out = phases.traced_execute(
        spec, "build.dedup-shard", config=cfg, seed=2**31 + 5, seconds=0.3,
        t_start=0.0, device={"platform": "cpu", "kind": "cpu", "count": 1})
    assert out["result"]["correct"] is True
    assert out["result"]["metrics"] == {}
    assert out["summary"] is None
    assert out["metrics"] == dict.fromkeys(phases.LAYERS)
    levels = [c for name, c in out["counters"]
              if name.startswith("sa.dcv.level@")]
    assert levels and levels[0]["level"] == 0
    assert set(levels[0]) == {"level", "n_v", "v"}
    assert harness.Run.__name__ == "Run"        # the harness is restored
