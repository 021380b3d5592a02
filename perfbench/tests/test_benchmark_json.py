"""``BENCHMARK.json`` against the contract's limits on names, units and
structure, and every name against the file it must lead to."""
import json
import os
import re

import pytest

from perfbench import harness

NAME = re.compile(r"^[A-Za-z0-9_][A-Za-z0-9_.\-]{0,63}$")
UNIT = re.compile(r"^[A-Za-z0-9_/%.\-]{1,16}$")
PATH = re.compile(r"^[A-Za-z0-9_.\-/]{1,200}$")
SOURCES = {"device_trace", "program_span", "program_counter", "host_clock"}

BENCH = harness.benchmark()


def _line(s):
    return 1 <= len(s) <= 200 and "\n" not in s and "\t" not in s


def test_top_level_keys_and_sizes():
    assert set(BENCH) == {"command", "paths", "run_seconds", "configs",
                          "workloads", "end_to_end", "per_layer"}
    assert os.path.getsize(os.path.join(harness.ROOT, "BENCHMARK.json")) \
        <= 64 * 1024
    assert 1 <= BENCH["run_seconds"] <= 51
    assert BENCH["paths"] == ["perfbench"]
    assert all(_line(w) for w in BENCH["command"])
    assert not any(w.startswith("/") or ".." in w for w in BENCH["command"])
    n = len(BENCH["workloads"])
    assert 1 <= n <= 24 and 1 <= len(BENCH["configs"]) <= 24
    # a full check at the full 24 cells must fit the driver's budget
    s = BENCH["run_seconds"]
    assert (2 + 14 * 24) * (s + 60) + 24 * 2 * 90 + 1200 <= 43200
    four = sum(w["chips"] == 4 for w in BENCH["workloads"])
    assert four <= max(1, n // 4)


@pytest.mark.parametrize("conf", BENCH["configs"], ids=lambda c: c["name"])
def test_config_entry(conf):
    assert set(conf) == {"name", "source", "file", "reduced", "why"}
    assert NAME.match(conf["name"]) and _line(conf["source"])
    assert _line(conf["why"]) and PATH.match(conf["file"])
    assert conf["file"].startswith("perfbench/") and len(conf["reduced"]) <= 16
    with open(os.path.join(harness.ROOT, conf["file"])) as f:
        body = json.load(f)
    assert body["source"] == conf["source"]
    assert body["reduced"] == conf["reduced"] == []
    for key in ("assumed", "departures", "reference", "costs", "model"):
        assert body[key]
    assert any("bias on the attention projections" in d
               for d in body["departures"])
    assert any(c["name"] == conf["name"] for c in BENCH["configs"])
    assert any(w["config"] == conf["name"] for w in BENCH["workloads"])


def test_published_sizes_are_unchanged():
    want = {"gpt2-medium": (24, 1024, 16, 4096), "gpt2-large": (36, 1280, 20,
                                                                5120)}
    for conf in BENCH["configs"]:
        with open(os.path.join(harness.ROOT, conf["file"])) as f:
            c = json.load(f)
        assert (c["n_layer"], c["n_embd"], c["n_head"], c["n_inner"]) \
            == want[conf["name"]]
        assert (c["vocab_size"], c["n_positions"]) == (50257, 1024)


@pytest.mark.parametrize("cell", BENCH["workloads"], ids=lambda w: w["name"])
def test_workload_entry(cell):
    assert set(cell) == {"name", "config", "traffic", "chips", "why"}
    for key in ("name", "config", "traffic"):
        assert NAME.match(cell[key]), cell[key]
    assert cell["chips"] in (1, 4) and _line(cell["why"])
    c = harness.Cell(cell["name"])          # every file the names lead to
    assert c.traffic["kind"] and c.limits
    names = {m["name"] for m in c.end_to_end}
    assert "setup_s" in names and len(names) >= 2 and c.per_layer
    for m in c.per_layer:
        assert m["moves"] in names, (m["name"], m["moves"])
        harness.load_module("layer_metrics", m["name"] + ".py").read


def test_pairs_and_names_are_unique():
    pairs = [(w["config"], w["traffic"]) for w in BENCH["workloads"]]
    assert len(set(pairs)) == len(pairs)
    for group in ("configs", "workloads"):
        names = [e["name"] for e in BENCH[group]]
        assert len(set(names)) == len(names)
    metrics = [m["name"] for m in BENCH["end_to_end"] + BENCH["per_layer"]]
    assert len(set(metrics)) == len(metrics)


@pytest.mark.parametrize("m", BENCH["end_to_end"] + BENCH["per_layer"],
                         ids=lambda m: m["name"])
def test_metric_entry(m):
    e2e = m in BENCH["end_to_end"]
    keys = {"name", "unit", "better", "source"} | (
        {"bound"} if e2e else {"layer", "moves"})
    assert keys <= set(m) <= keys | {"workloads"}
    assert NAME.match(m["name"]) and UNIT.match(m["unit"])
    assert m["better"] in ("lower", "higher") and m["source"] in SOURCES
    cells = {w["name"] for w in BENCH["workloads"]}
    assert set(m.get("workloads", cells)) <= cells
    if e2e:
        assert m["source"] in ("host_clock", "device_trace")
        assert 0.01 <= m["bound"] <= 0.1
    else:
        assert _line(m["layer"])
        assert m["moves"] in {e["name"] for e in BENCH["end_to_end"]}
    if m["name"].endswith("_roofline") or "roofline" in m["name"]:
        assert m["unit"] == "%"


def test_benchmark_imports_nothing_it_must_not():
    """No TensorFlow, nothing of bench.py, benchmarks/ or tools/ anywhere in
    perfbench/; the reference and the generator import nothing of the
    program; the generator never imports jax."""
    bad = re.compile(r"^\s*(import|from)\s+(tensorflow|bench\b|benchmarks|"
                     r"tools)\b", re.M)
    for dirpath, _d, files in os.walk(harness.HERE):
        for f in files:
            if f.endswith(".py"):
                with open(os.path.join(dirpath, f)) as fh:
                    src = fh.read()
                assert not bad.search(src), os.path.join(dirpath, f)
    for rel in ("reference/gpt2.py", "loadgen.py", "costs/gpt2.py",
                "trace.py"):
        with open(os.path.join(harness.HERE, rel)) as fh:
            src = fh.read()
        assert not re.search(r"^\s*(import|from)\s+deeplearning4j_tpu", src,
                             re.M), rel
    with open(os.path.join(harness.HERE, "loadgen.py")) as fh:
        assert not re.search(r"^\s*(import|from)\s+jax", fh.read(), re.M)
