"""A later PR adds a cell, a configuration, a traffic mix (of a new kind, with
its runner) and a per-layer metric by adding files and appending entries to
``BENCHMARK.json``, and edits no file that is there: shown on a copy."""
import json
import os
import shutil
import subprocess
import sys

from perfbench import harness

RUNNER = '''
def run(cell, seed, seconds, trace, device, t_start):
    from perfbench import harness
    values = {"dummy_rate": 1.0 + cell.traffic["offset"], "setup_s": 0.5}
    ctx = {"cell": cell, "values": values, "seed": seed}
    if trace:
        harness.read_layer_metrics(cell, ctx, values)
    return {"correct": True, "attempted": 1, "failed": 0, "values": values,
            "memory_peak_bytes": 0, "busy_s": 0.0, "trace_window_s": 0.0}
'''
READER = "def read(ctx):\n    return float(ctx['seed']) + ctx['cell'].config['n_layer']\n"


def test_a_cell_is_added_by_new_files_and_appended_entries_alone(tmp_path):
    root = tmp_path / "checkout"
    shutil.copytree(harness.HERE, root / "perfbench", ignore=shutil.
                    ignore_patterns("__pycache__", "_work", "*.xplane.pb"))
    before = {p: p.read_bytes() for p in (root / "perfbench").rglob("*")
              if p.is_file()}
    bench = harness.benchmark()
    # ---- what the later PR adds: four files ...
    pb = root / "perfbench"
    (pb / "configs" / "dummy-model.json").write_text(json.dumps({
        "source": "https://example.org/dummy", "model": "gpt2",
        "reference": "gpt2", "costs": "gpt2", "n_layer": 3, "reduced": [],
        "assumed": {}, "departures": []}))
    (pb / "traffic" / "dummy-mix.json").write_text(json.dumps(
        {"kind": "dummy-kind", "offset": 41.0, "why": "a new kind of mix"}))
    (pb / "runners" / "dummy-kind.py").write_text(RUNNER)
    (pb / "layer_metrics" / "dummy_layer.metric.py").write_text(READER)
    # ---- ... and four appended entries
    bench["configs"].append({
        "name": "dummy-model", "source": "https://example.org/dummy",
        "file": "perfbench/configs/dummy-model.json", "reduced": [],
        "why": "test"})
    bench["workloads"].append({"name": "dummy-cell", "config": "dummy-model",
                               "traffic": "dummy-mix", "chips": 1,
                               "why": "test"})
    bench["end_to_end"].append({"name": "dummy_rate", "unit": "1/s",
                                "better": "higher", "bound": 0.01,
                                "source": "host_clock",
                                "workloads": ["dummy-cell"]})
    bench["per_layer"].append({"name": "dummy_layer.metric", "unit": "count",
                               "better": "higher", "source":
                               "program_counter", "layer": "dummy",
                               "moves": "dummy_rate",
                               "workloads": ["dummy-cell"]})
    (root / "BENCHMARK.json").write_text(json.dumps(bench))
    env = dict(os.environ, PYTHONPATH=harness.ROOT, JAX_PLATFORMS="cpu")
    for trace, want in ((0, ["dummy_rate", "setup_s"]),
                        (1, ["dummy_layer.metric"])):
        out = subprocess.run(
            [sys.executable, str(pb / "run.py"), "--workload", "dummy-cell",
             "--seed", "5", "--seconds", "1", "--trace", str(trace),
             "--rehearsal"], cwd=root, env=env, capture_output=True,
            text=True, timeout=300)
        assert out.returncode == 0, out.stderr[-2000:]
        line = json.loads(out.stdout.strip().splitlines()[-1])
        assert line["correct"] and line["reported"] == want
    # no file that was there has changed
    for p, body in before.items():
        assert p.read_bytes() == body, p


def test_a_checkout_without_the_program_is_refused(tmp_path):
    """Only BENCHMARK.json and perfbench/: non-zero exit, no result line."""
    root = tmp_path / "bare"
    shutil.copytree(harness.HERE, root / "perfbench", ignore=shutil.
                    ignore_patterns("__pycache__", "_work"))
    shutil.copy(os.path.join(harness.ROOT, "BENCHMARK.json"), root)
    env = {k: v for k, v in os.environ.items() if k != "PYTHONPATH"}
    out = subprocess.run(
        [sys.executable, "perfbench/run.py", "--workload", "gpt2m-train",
         "--seed", "1", "--seconds", "1", "--trace", "0"], cwd=root, env=env,
        capture_output=True, text=True, timeout=300)
    assert out.returncode != 0 and "correct" not in out.stdout
