"""The harness at tiny sizes on the CPU: drivers in-process against their
references, refusal off the chip, files found by name, and the shape of
BENCHMARK.json."""
import json
import os
import re
import shutil
import subprocess
import sys
import time

import numpy as np
import pytest

from bench import harness
from bench.data.tokens import make_corpus, reorder

ROOT = harness.ROOT
CPU = {"platform": "cpu", "kind": "cpu", "count": 1}
SEED = 2**31 + 12345          # larger than 32 signed bits hold
NAME = re.compile(r"^[A-Za-z0-9_][A-Za-z0-9_.\-]{0,63}$")
UNIT = re.compile(r"^[A-Za-z0-9_/%.\-]{1,16}$")


def tiny(spec, name):
    """The cell's files, cut to 2^12-token shards."""
    wl = spec.workload(name)
    cfg, traffic = spec.config(wl["config"]), spec.traffic(wl["traffic"])
    cfg["shard_tokens"] = 1 << 12
    return cfg, traffic


def run_tiny(spec, name, seconds=0.5, trace=False, system=None, **over):
    cfg, traffic = tiny(spec, name)
    traffic.update(over)
    return harness.execute(spec, name, seed=SEED, seconds=seconds,
                           trace=trace, t_start=time.perf_counter(),
                           device=CPU, config=cfg, traffic=traffic,
                           system=system)


@pytest.mark.parametrize("name", ["build.dedup-shard"])
def test_driver_passes_its_reference_check(name):
    spec = harness.Spec()
    res = run_tiny(spec, name)
    assert res["correct"] is True, res
    assert res["attempted"] > 0 and res["failed"] == 0
    want = {m["name"] for m in spec.metrics("end_to_end", name)}
    assert set(res["metrics"]) == want
    assert all(m["value"] > 0 for m in res["metrics"].values())
    assert list(res)[-1] == "checks"
    assert all(c["value"] <= c["limit"] for c in res["checks"].values())


def test_build_window_holds_whole_builds_and_no_compile(capsys):
    """Builds start until they have taken `seconds`; the warm-up shard
    compiled every shape, so the window compiles and traces nothing."""
    res = run_tiny(harness.Spec(), "build.dedup-shard", seconds=0.3)
    out = capsys.readouterr().out
    assert "compiles or cache loads 0, program traces 0" in out
    shards = int(re.search(r"window: (\d+) shards", out).group(1))
    assert shards == res["attempted"] >= 1


def test_traced_run_off_the_chip_reports_nothing_it_cannot_read():
    """On the CPU there is no device plane: every per-layer metric of the
    build reads the device, so none is made up, and `device` carries no
    busy time."""
    res = run_tiny(harness.Spec(), "build.dedup-shard", trace=True)
    assert res["correct"] is True
    assert res["metrics"] == {}
    assert "busy_s" not in res["device"] and "breakdown" not in res


def run_cli(cwd, *args, env_extra=None):
    env = dict(os.environ, JAX_PLATFORMS="cpu", **(env_extra or {}))
    return subprocess.run(
        [sys.executable, "bench/run.py", *args], cwd=cwd, env=env,
        capture_output=True, text=True, timeout=300)


def test_run_refuses_a_platform_that_is_not_a_tpu():
    p = run_cli(ROOT, "--workload", "build.dedup-shard", "--seed",
                str(SEED), "--seconds", "1", "--trace", "0")
    assert p.returncode != 0
    assert "needs 1 TPU chip" in p.stderr
    assert not any(line.startswith("{") for line in p.stdout.splitlines())


def test_run_refuses_a_checkout_without_the_program(tmp_path):
    shutil.copy(os.path.join(ROOT, "BENCHMARK.json"), tmp_path)
    shutil.copytree(os.path.join(ROOT, "bench"), tmp_path / "bench",
                    ignore=shutil.ignore_patterns("__pycache__"))
    p = run_cli(tmp_path, "--workload", "build.dedup-shard", "--seed", "1",
                "--seconds", "1", "--trace", "0")
    assert p.returncode != 0
    assert "src/repro" in p.stderr
    assert p.stdout.strip() == ""


def test_new_mix_and_metric_are_found_by_name(tmp_path):
    """A cell and a per-layer metric added as files, plus their entries,
    run without an edit to any file that was there."""
    shutil.copytree(os.path.join(ROOT, "bench"), tmp_path / "bench",
                    ignore=shutil.ignore_patterns("__pycache__"))
    with open(os.path.join(ROOT, "BENCHMARK.json")) as f:
        doc = json.load(f)
    before = {p: open(p, "rb").read() for p in
              (str(x) for x in (tmp_path / "bench").rglob("*") if x.is_file())}
    (tmp_path / "bench" / "traffic" / "shard_stream_short.json").write_text(
        json.dumps({"kind": "build_stream", "warmup_repeat_tokens": 60,
                    "content_seed": 7}))
    (tmp_path / "bench" / "metrics" / "build.traced_mtok.short.py"
     ).write_text("def read(run):\n"
                  "    return run.records['traced_tokens'] / 1e6\n")
    doc["workloads"].append({
        "name": "build.dedup-shard.short", "config": "dedup-shard",
        "traffic": "shard_stream_short", "chips": 1, "why": "test"})
    doc["per_layer"].append({
        "name": "build.traced_mtok.short", "unit": "Mtokens",
        "better": "higher", "source": "program_counter",
        "layer": "facade", "moves": "build_tokens_per_s",
        "workloads": ["build.dedup-shard.short"]})
    for m in doc["end_to_end"]:
        if m["name"] == "build_tokens_per_s":
            m["workloads"].append("build.dedup-shard.short")
    (tmp_path / "BENCHMARK.json").write_text(json.dumps(doc))

    spec = harness.Spec(str(tmp_path))
    assert spec.traffic("shard_stream_short")["warmup_repeat_tokens"] == 60
    cfg = spec.config("dedup-shard")
    cfg["shard_tokens"] = 1 << 12
    res = harness.execute(spec, "build.dedup-shard.short", seed=SEED,
                          seconds=0.3, trace=True,
                          t_start=time.perf_counter(), device=CPU,
                          config=cfg)
    assert res["correct"] is True
    assert res["metrics"] == {"build.traced_mtok.short": {
        "value": (1 << 12) / 1e6, "unit": "Mtokens"}}
    res = harness.execute(spec, "build.dedup-shard.short", seed=SEED,
                          seconds=0.3, trace=False,
                          t_start=time.perf_counter(), device=CPU,
                          config=cfg)
    assert set(res["metrics"]) == {"build_tokens_per_s", "setup_s"}
    for p, data in before.items():
        assert open(p, "rb").read() == data, p


def test_benchmark_json_follows_its_rules():
    with open(os.path.join(ROOT, "BENCHMARK.json")) as f:
        doc = json.load(f)
    assert set(doc) == {"command", "paths", "run_seconds", "configs",
                        "workloads", "end_to_end", "per_layer"}
    assert doc["paths"] == ["bench"]
    assert 1 <= doc["run_seconds"] <= 51
    spec = harness.Spec()
    names = [x["name"] for k in ("configs", "workloads", "end_to_end",
                                 "per_layer") for x in doc[k]]
    assert len(names) == len(set(names))
    assert all(NAME.match(n) for n in names)
    used = {w["config"] for w in doc["workloads"]}
    assert used == {c["name"] for c in doc["configs"]}
    for c in doc["configs"]:
        assert set(c) == {"name", "source", "file", "reduced", "why"}
        assert c["file"].startswith("bench/")
        cfg = spec.config(c["name"])
        assert set(c["reduced"]) == set(cfg["reduced"])
        assert all(NAME.match(k) for k in c["reduced"])
    pairs = set()
    for w in doc["workloads"]:
        assert set(w) == {"name", "config", "traffic", "chips", "why"}
        assert w["chips"] in (1, 4) and len(w["why"]) <= 200
        assert NAME.match(w["traffic"])
        pairs.add((w["config"], w["traffic"]))
        spec.driver(spec.traffic(w["traffic"])["kind"])
        reported = spec.metrics("end_to_end", w["name"])
        assert "setup_s" in {m["name"] for m in reported}
        assert len(reported) >= 2
        assert spec.metrics("per_layer", w["name"])
    assert len(pairs) == len(doc["workloads"])
    e2e = {m["name"] for m in doc["end_to_end"]}
    assert "setup_s" in e2e
    for m in doc["end_to_end"]:
        assert UNIT.match(m["unit"]) and m["better"] in ("lower", "higher")
        assert 0.01 <= m["bound"] <= 0.25
        assert m["source"] in ("host_clock", "device_trace")
    for m in doc["per_layer"]:
        assert UNIT.match(m["unit"]) and m["better"] in ("lower", "higher")
        assert m["moves"] in e2e and m["workloads"]
        spec.reader(m["name"])
        for w in m["workloads"]:
            assert m["moves"] in {x["name"] for x in
                                  spec.metrics("end_to_end", w)}
        if "roofline" in m["name"]:
            assert m["name"].endswith("_roofline") and m["unit"] == "%"


def test_corpus_is_exact_in_size_and_fixed_by_the_seed():
    spec = harness.Spec()
    law = spec.config("dedup-shard")["corpus"]
    a = make_corpus(law, 1 << 14, SEED, 1, 3)
    b = make_corpus(law, 1 << 14, SEED, 1, 3)
    c = make_corpus(law, 1 << 14, SEED + 1, 1, 3)
    assert sum(len(d) + 1 for d in a) == 1 << 14
    assert all(len(d) >= 1 for d in a)
    assert all(np.array_equal(x, y) for x, y in zip(a, b))
    assert not all(len(x) == len(y) and np.array_equal(x, y)
                   for x, y in zip(a, c))
    flat = np.concatenate(a)
    assert flat.min() >= 0 and flat.max() < law["vocab"]


def test_reorder_keeps_the_documents_and_draws_their_order():
    law = harness.Spec().config("dedup-shard")["corpus"]
    docs = make_corpus(law, 1 << 14, 7, 1, 0)
    a, b = reorder(docs, SEED, 1, 0), reorder(docs, SEED, 1, 0)
    c = reorder(docs, SEED + 1, 1, 0)
    assert all(x is y for x, y in zip(a, b))
    assert [id(x) for x in a] != [id(x) for x in c]
    assert sorted(map(id, a)) == sorted(map(id, docs))
