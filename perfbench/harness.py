"""What every run shares: finding a cell's files by the names in
``BENCHMARK.json``, the device check, the compile cache, and the result line.

Nothing here knows a model, a traffic mix or a metric. A cell is found by its
``name`` in ``BENCHMARK.json``; its configuration in ``configs/<config>.json``,
its mix in ``traffic/<traffic>.json``, its limits in ``limits/<cell>.json``;
the runner by the mix's ``kind`` in ``runners/<kind>.py``; the model adapter,
the plain reference and the cost functions by the configuration's ``model``,
``reference`` and ``costs`` keys; every per-layer metric by its name in
``layer_metrics/<name>.py``. A later PR adds files and entries and edits none.
"""
from __future__ import annotations

import importlib.util
import json
import os
import sys
import time

_T0 = time.time()
HERE = os.path.dirname(os.path.abspath(__file__))
ROOT = os.path.dirname(HERE)


class Refused(Exception):
    """The run cannot be made (no chip, unknown cell): exit non-zero, print
    no result line."""


def _rss_gib() -> float:
    try:
        with open("/proc/self/statm") as f:
            return int(f.read().split()[1]) * os.sysconf("SC_PAGE_SIZE") \
                / 2**30
    except (OSError, ValueError, IndexError):
        return 0.0


def say(msg: str):
    """A line of the run's log, with the seconds since the benchmark was
    imported and the process's resident host memory."""
    print(f"[perfbench {time.time() - _T0:6.1f}s {_rss_gib():5.1f}G] {msg}",
          flush=True)


def load_json(*parts):
    with open(os.path.join(HERE, *parts)) as f:
        return json.load(f)


def load_module(*parts):
    """A module of the benchmark by its path under ``perfbench/`` (names may
    hold ``.`` and ``-``, which ``import`` cannot spell)."""
    path = os.path.join(HERE, *parts)
    if not os.path.exists(path):
        raise Refused(f"no such benchmark file: {path}")
    name = "perfbench_" + "_".join(parts).replace(".", "_").replace("-", "_")
    if name in sys.modules:
        return sys.modules[name]
    spec = importlib.util.spec_from_file_location(name, path)
    mod = importlib.util.module_from_spec(spec)
    sys.modules[name] = mod
    spec.loader.exec_module(mod)
    return mod


def benchmark():
    with open(os.path.join(ROOT, "BENCHMARK.json")) as f:
        return json.load(f)


class Cell:
    """One entry of ``workloads`` with everything its name leads to."""

    def __init__(self, name: str, rehearsal: bool = False, bench=None):
        bench = bench or benchmark()
        rows = [w for w in bench["workloads"] if w["name"] == name]
        if not rows:
            raise Refused(f"BENCHMARK.json has no workload {name!r}")
        self.row = rows[0]
        self.name = name
        self.chips = int(self.row["chips"])
        self.rehearsal = rehearsal
        conf = [c for c in bench["configs"] if c["name"] == self.row["config"]]
        if not conf:
            raise Refused(f"no configuration {self.row['config']!r}")
        with open(os.path.join(ROOT, conf[0]["file"])) as f:
            self.config = json.load(f)
        self.traffic = load_json("traffic", self.row["traffic"] + ".json")
        lim = os.path.join(HERE, "limits", name + ".json")
        self.limits = load_json("limits", name + ".json") \
            if os.path.exists(lim) else {}
        if rehearsal:
            self.config.update(self.config.get("rehearsal", {}))
            self.traffic.update(self.traffic.get("rehearsal", {}))
            self.limits = self.limits.get("rehearsal", {})
        self.end_to_end = [m for m in bench["end_to_end"]
                           if name in m.get("workloads", [name])]
        self.per_layer = [m for m in bench["per_layer"]
                          if name in m.get("workloads", [name])]
        self.model = load_module("models", self.config["model"] + ".py")
        self.reference = load_module("reference",
                                     self.config["reference"] + ".py")
        self.costs = load_module("costs", self.config["costs"] + ".py")
        self.runner = load_module("runners", self.traffic["kind"] + ".py")

    def limit(self, key: str) -> float:
        if key not in self.limits:
            raise Refused(f"limits/{self.name}.json sets no limit {key!r}")
        return float(self.limits[key])


def device_info(chips: int, rehearsal: bool):
    """Refuse anything but ``chips`` TPU chips (rehearsal: any backend with
    that many devices). Returns the ``device`` object of the result line,
    without the memory peak."""
    import jax
    devs = jax.devices()
    info = {"platform": devs[0].platform, "kind": devs[0].device_kind,
            "count": len(devs)}
    if rehearsal:
        if len(devs) < chips:
            raise Refused(f"rehearsal needs {chips} devices, found {info}")
        info["count"] = chips
        return info
    if info["platform"] != "tpu":
        raise Refused(f"no TPU: jax found {info}; refusing to measure")
    if len(devs) < chips:
        raise Refused(f"the cell needs {chips} chips, jax found {info}")
    if info["kind"] not in load_json("peaks.json"):
        raise Refused(f"perfbench/peaks.json has no row for {info['kind']!r}")
    info["count"] = chips
    return info


def peaks(kind: str) -> dict:
    table = load_json("peaks.json")
    if kind not in table:
        raise Refused(f"perfbench/peaks.json has no row for {kind!r}")
    return table[kind]


def read_layer_metrics(cell, ctx, values):
    """Every per-layer metric of the cell through its own reader; a reader
    that finds nothing to read returns None and the metric is left out. On
    a rehearsal's CPU a reader that needs the chip's peaks reads nothing."""
    for m in cell.per_layer:
        reader = load_module("layer_metrics", m["name"] + ".py")
        try:
            values[m["name"]] = reader.read(ctx)
        except Refused:
            if not cell.rehearsal:
                raise
            values[m["name"]] = None


def memory_peak_bytes(chips: int):
    """Peak bytes in use on the fullest of the chips used."""
    import jax
    peak = 0
    for d in jax.devices()[:chips]:
        stats = d.memory_stats() or {}
        peak = max(peak, int(stats.get("peak_bytes_in_use", 0)))
    return peak


def configure_cache():
    """The program's one place for the persistent compile cache:
    ``JAX_COMPILATION_CACHE_DIR`` if set, else ``<checkout>/.jax_cache`` -
    a fixed path inside the checkout either way the driver runs it."""
    from deeplearning4j_tpu.async_runtime import configure_compile_cache
    return configure_compile_cache()


class CacheEvents:
    """Persistent-cache hits and misses, for the set-up log."""

    def __init__(self):
        import jax.monitoring as mon
        self.hits = self.misses = 0
        mon.register_event_listener(self._on)

    def _on(self, event, **_kw):
        if event.endswith("compilation_cache/cache_hits"):
            self.hits += 1
        elif event.endswith("compilation_cache/cache_misses"):
            self.misses += 1


def quantile(values, q: float):
    """The q-quantile by linear interpolation; +inf entries stay +inf."""
    vals = sorted(values)
    if not vals:
        return None
    pos = q * (len(vals) - 1)
    lo = int(pos)
    hi = min(lo + 1, len(vals) - 1)
    if vals[hi] == float("inf"):
        return float("inf") if pos > lo or vals[lo] == float("inf") \
            else vals[lo]
    return vals[lo] + (vals[hi] - vals[lo]) * (pos - lo)


class Compare:
    """The numbers compared with the reference, each beside its limit;
    ``correct`` is that every one is inside."""

    def __init__(self):
        self.rows = []

    def add(self, name: str, value, limit: float, exact: bool = False):
        ok = (value == limit) if exact else (value is not None
                                             and value <= limit)
        self.rows.append((name, value, limit, ok))
        say(f"compare {name}: {value!r} (limit {limit!r}) "
            f"{'ok' if ok else 'OUTSIDE'}")

    @property
    def correct(self) -> bool:
        return bool(self.rows) and all(r[3] for r in self.rows)


def result_line(correct, attempted, failed, metrics, device, breakdown=None,
                rehearsal=False):
    out = {"correct": bool(correct), "attempted": int(attempted),
           "failed": int(failed)}
    if rehearsal:
        # a CPU run has counts, never a number under a device metric's name
        out["rehearsal"] = True
        out["reported"] = sorted(metrics)
    else:
        out["metrics"] = metrics
    out["device"] = device
    if breakdown is not None and not rehearsal:
        out["breakdown"] = breakdown
    return json.dumps(out)


def work_dir(cell) -> str:
    """The benchmark's scratch inside the checkout (``.gitignore`` lists it):
    where a traced run's profile is written before it is read and removed."""
    d = os.path.join(HERE, "_work", f"trace-{cell.name}")
    os.makedirs(d, exist_ok=True)
    return d
