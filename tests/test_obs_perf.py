"""Performance observatory (ISSUE 6): XLA cost-model accounting, live
MFU/roofline, perf-regression SLO, on-demand profiler capture,
metric/knob lints."""
import importlib.util
import json
import os
import threading
import urllib.error
import urllib.request

import numpy as np
import pytest

from deeplearning4j_tpu.data.dataset import DataSet
from deeplearning4j_tpu.nn.conf.configuration import NeuralNetConfiguration
from deeplearning4j_tpu.nn.conf.inputs import InputType
from deeplearning4j_tpu.nn.conf.layers import DenseLayer, OutputLayer
from deeplearning4j_tpu.nn.multilayer import MultiLayerNetwork
from deeplearning4j_tpu.observability import (global_cost_model, metrics,
                                              reset_global_registry,
                                              reset_global_slo_engine)
from deeplearning4j_tpu.observability import cost_model as cost_model_mod
from deeplearning4j_tpu.observability import profile_capture as pc
from deeplearning4j_tpu.optim.updaters import Adam

_REPO_ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))

MLN_STEP = "MultiLayerNetwork._train_step"


def _load_tool(name):
    spec = importlib.util.spec_from_file_location(
        name, os.path.join(_REPO_ROOT, "tools", f"{name}.py"))
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


def _net():
    return MultiLayerNetwork(
        NeuralNetConfiguration.builder().seed(1).updater(Adam(1e-2))
        .weight_init("xavier").list()
        .layer(DenseLayer(n_in=4, n_out=8, activation="relu"))
        .layer(OutputLayer(n_out=3, activation="softmax",
                           loss_function="mcxent"))
        .set_input_type(InputType.feed_forward(4)).build()).init()


def _data(n=16, seed=0):
    rng = np.random.RandomState(seed)
    X = rng.rand(n, 4).astype("f4")
    return DataSet(X, np.eye(3)[rng.randint(0, 3, n)].astype("f4"))


# ---------------------------------------------------------------------------
# cost accounting: once per compile, no steady-state analysis
# ---------------------------------------------------------------------------

def test_mln_cost_accounted_exactly_once_per_compile():
    """Fixed-shape training runs cost_analysis ONCE — every further step
    is an int compare; a shape change (new compile) re-accounts."""
    reset_global_registry()
    net = _net()
    for _ in range(5):
        net.fit(_data())
    entry = global_cost_model().entry(MLN_STEP)
    assert entry is not None
    assert entry["analyze_calls"] == 1
    assert entry["source"] == "cost_analysis"
    assert entry["error"] is None
    assert entry["flops"] > 0 and entry["bytes_accessed"] > 0
    assert entry["samples"] == 5
    assert metrics().get("dl4j_cost_flops").labels(
        fn=MLN_STEP).value == entry["flops"]
    net.fit(_data(n=9))                       # new signature → one recompile
    entry = global_cost_model().entry(MLN_STEP)
    assert entry["analyze_calls"] == 2
    reset_global_registry()


def test_cg_cost_accounted():
    from deeplearning4j_tpu.nn.graph import ComputationGraph

    reset_global_registry()
    conf = (NeuralNetConfiguration.builder().seed(1).updater(Adam(1e-2))
            .graph_builder()
            .add_inputs("in")
            .set_input_types(InputType.feed_forward(4))
            .add_layer("dense", DenseLayer(n_out=8, activation="relu"),
                       "in")
            .add_layer("out", OutputLayer(n_out=3, activation="softmax",
                                          loss_function="mcxent"), "dense")
            .set_outputs("out").build())
    net = ComputationGraph(conf).init()
    for _ in range(3):
        net.fit(_data())
    entry = global_cost_model().entry("ComputationGraph._train_step")
    assert entry is not None and entry["analyze_calls"] == 1
    assert entry["flops"] > 0 and entry["samples"] == 3
    reset_global_registry()


def test_cost_model_kill_switch(monkeypatch):
    monkeypatch.setenv("DL4J_TPU_COST_MODEL", "0")
    reset_global_registry()
    net = _net()
    net.fit(_data())
    assert global_cost_model().snapshot()["fns"] == {}
    assert metrics().get("dl4j_cost_flops") is None
    assert metrics().get("dl4j_mfu") is None
    reset_global_registry()


def test_program_costs_prices_the_compiled_executable_when_lowered_is_unpriced():
    """The TPU backend answers None to ``Lowered.cost_analysis()`` (jax
    0.9.0, found on the v5e) and left every live cost entry empty behind a
    swallowed error. The fallback prices the compiled executable —
    per-device there — and scales it back to the global program."""
    import jax
    import jax.numpy as jnp
    from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

    mesh = Mesh(np.array(jax.devices()), ("data",))
    x = jax.device_put(jnp.ones((64, 256)), NamedSharding(mesh, P("data")))
    w = jax.device_put(jnp.ones((256, 256)), NamedSharding(mesh, P()))
    f = jax.jit(lambda x, w: jnp.tanh(x @ w).sum())
    f(x, w)
    lowered = f.lower(x, w)
    want = cost_model_mod.program_costs(lowered)

    class Unpriced:
        def cost_analysis(self):
            return None

        def compile(self):
            return lowered.compile()

    got = cost_model_mod.program_costs(Unpriced())
    assert want[0] > 0 and got[0] == pytest.approx(want[0], rel=0.05)


# ---------------------------------------------------------------------------
# MFU gauge + roofline verdict under the env-pinned peak table
# ---------------------------------------------------------------------------

def test_mfu_gauge_matches_hand_computed_value(monkeypatch):
    """dl4j_mfu = flops / (mean step seconds × pinned peak): exact on a
    synthetic entry with known durations, and self-consistent on a real
    fixed-shape MLN step."""
    monkeypatch.setenv("DL4J_TPU_PEAK_FLOPS", "2e9")
    monkeypatch.setenv("DL4J_TPU_HBM_GBPS", "1")
    reset_global_registry()
    cm = global_cost_model()
    cm.record_cost("unit.step", flops=4e6, bytes_accessed=1e6)
    for t in (0.002, 0.004):
        cm.observe_time("unit.step", t)
    expected = 4e6 / (0.003 * 2e9)            # mean(2ms, 4ms) = 3ms
    entry = cm.entry("unit.step")
    assert entry["mfu"] == pytest.approx(expected, rel=1e-9)
    assert metrics().get("dl4j_mfu").labels(
        fn="unit.step").value == pytest.approx(expected, rel=1e-9)

    # integration: the real train step's gauge equals the snapshot's own
    # flops / (recent mean × pinned peak) — the published number is the
    # hand-computable one, not an internal variant
    net = _net()
    for _ in range(4):
        net.fit(_data())
    entry = cm.entry(MLN_STEP)
    hand = entry["flops"] / (entry["recent_seconds_mean"] * 2e9)
    assert metrics().get("dl4j_mfu").labels(
        fn=MLN_STEP).value == pytest.approx(hand, rel=0.2)
    reset_global_registry()


def test_roofline_verdict_flips_with_bw_knob(monkeypatch):
    """The same program is compute-bound against a slow-HBM table and
    memory-bound against a fast one: verdict = intensity vs ridge."""
    monkeypatch.setenv("DL4J_TPU_PEAK_FLOPS", "1e9")
    reset_global_registry()
    cm = global_cost_model()
    cm.record_cost("unit.roofline", flops=1e6, bytes_accessed=1e6)  # AI=1.0
    monkeypatch.setenv("DL4J_TPU_HBM_GBPS", "10")   # ridge = 1e9/1e10 = 0.1
    assert cm.entry("unit.roofline")["roofline_verdict"] == "compute_bound"
    monkeypatch.setenv("DL4J_TPU_HBM_GBPS", "0.1")  # ridge = 1e9/1e8 = 10
    assert cm.entry("unit.roofline")["roofline_verdict"] == "memory_bound"
    assert cm.snapshot()["ridge_intensity"] == pytest.approx(10.0)
    reset_global_registry()


# ---------------------------------------------------------------------------
# perf-regression SLO rule
# ---------------------------------------------------------------------------

def test_perf_regression_rule_trips_alerts(monkeypatch):
    """An injected sustained slowdown (same program, 4× the step time)
    drags live MFU under the frozen rolling baseline → perf_regression
    active on /alerts, /health degraded (pages, never ejects)."""
    from deeplearning4j_tpu.ui import UIServer

    monkeypatch.setenv("DL4J_TPU_PEAK_FLOPS", "1e9")
    reset_global_registry()
    reset_global_slo_engine()
    cm = global_cost_model()
    cm.record_cost("unit.regress", flops=1e6)
    for _ in range(64):                       # healthy steady state
        cm.observe_time("unit.regress", 0.001)
    baseline = cm.entry("unit.regress")["baseline_mfu"]
    for _ in range(64):                       # injected slowdown: 4× step
        cm.observe_time("unit.regress", 0.004)
    entry = cm.entry("unit.regress")
    assert entry["mfu"] < 0.7 * baseline
    # the baseline froze instead of normalizing the regression away
    assert entry["baseline_mfu"] == pytest.approx(baseline, rel=0.05)

    server = UIServer(port=0).start()
    try:
        alerts = json.loads(urllib.request.urlopen(
            server.get_address() + "/alerts", timeout=5).read())
        active = {a["rule"]: a for a in alerts["active"]}
        assert "perf_regression" in active
        assert active["perf_regression"]["status"] == "degraded"
        health = json.loads(urllib.request.urlopen(
            server.get_address() + "/health", timeout=5).read())
        assert health["status"] == "degraded"       # never 503 on perf
        assert "perf_regression" in health["degraded_rules"]
    finally:
        server.stop()
        reset_global_registry()
        reset_global_slo_engine()


# ---------------------------------------------------------------------------
# /debug/perf: train + serving-bucket + sharded entries
# ---------------------------------------------------------------------------

def test_debug_perf_covers_train_serving_and_sharded_entries():
    """Acceptance: /debug/perf rows exist for the train step, each
    serving shape-bucket executable, and the ShardedTrainer step (peak
    scaled by mesh size, analytic collective traffic attached)."""
    from deeplearning4j_tpu.parallel import MeshSpec, ShardedTrainer
    from deeplearning4j_tpu.parallel.inference import (InferenceMode,
                                                       ParallelInference)
    from deeplearning4j_tpu.ui import UIServer

    reset_global_registry()
    net = _net()
    net.fit(_data())
    pi = (ParallelInference.Builder(net)
          .inference_mode(InferenceMode.BATCHED).batch_limit(8).build())
    try:
        for _ in range(4):
            pi.output(np.random.rand(3, 4).astype("f4"))
    finally:
        pi.shutdown()

    net2 = _net()
    x = np.random.rand(32, 4).astype("f4")
    y = np.eye(3, dtype="f4")[np.random.randint(0, 3, 32)]
    tr = ShardedTrainer(net2, MeshSpec.data_parallel(8))
    for _ in range(2):
        tr.fit(x, y)

    server = UIServer(port=0).start()
    try:
        perf = json.loads(urllib.request.urlopen(
            server.get_address() + "/debug/perf", timeout=5).read())
    finally:
        server.stop()
    fns = perf["fns"]
    assert perf["enabled"] is True and perf["peak_flops"] > 0
    train = fns[MLN_STEP]
    assert train["flops"] > 0 and train["mfu"] is not None
    assert train["roofline_verdict"] in ("compute_bound", "memory_bound")
    bucket = fns["MultiLayerNetwork._output_jit[b4]"]
    assert bucket["flops"] > 0 and bucket["samples"] >= 4
    sharded = fns["ShardedTrainer.step"]
    assert sharded["devices"] == 8
    assert sharded["flops"] > 0 and sharded["samples"] == 2
    expected = sharded["collective_bytes_per_step"]["allreduce"]
    assert expected > 0
    c = metrics().get("dl4j_collective_bytes_total")
    assert c.labels(collective="allreduce").value == pytest.approx(
        2 * expected)
    reset_global_registry()


# ---------------------------------------------------------------------------
# postmortem bundle carries perf.json
# ---------------------------------------------------------------------------

def test_bundle_carries_perf_json(tmp_path):
    from deeplearning4j_tpu.observability import FlightRecorder

    reset_global_registry()
    net = _net()
    net.fit(_data())
    rec = FlightRecorder(hang_seconds=60, out_dir=str(tmp_path))
    bundle = rec.dump("perf-test")
    rec.stop()
    assert "perf.json" in set(os.listdir(bundle))
    perf = json.loads(open(os.path.join(bundle, "perf.json")).read())
    assert MLN_STEP in perf["fns"]
    assert perf["fns"][MLN_STEP]["flops"] > 0
    reset_global_registry()


# ---------------------------------------------------------------------------
# /debug/profile: round-trip, retention, busy, kill switch
# ---------------------------------------------------------------------------

class _FakeProfiler:
    """Writes a (trace-less) capture dir without driving jax.profiler —
    exercises the capture lifecycle at unit speed."""

    def __init__(self, logdir):
        self.logdir = logdir

    def start(self):
        os.makedirs(self.logdir, exist_ok=True)

    def stop(self):
        with open(os.path.join(self.logdir, "marker.txt"), "w") as f:
            f.write("fake")


@pytest.mark.slow


def test_profile_capture_retention_cap(tmp_path, monkeypatch):
    """Trace dirs beyond DL4J_TPU_POSTMORTEM_KEEP are evicted
    oldest-first, while the parsed ring keeps every record."""
    from deeplearning4j_tpu.profiler import xprof

    monkeypatch.setattr(xprof, "DeviceProfiler", _FakeProfiler)
    monkeypatch.setenv("DL4J_TPU_POSTMORTEM_KEEP", "2")
    cap = pc.ProfileCapture(out_dir=str(tmp_path))
    for _ in range(4):
        cap.capture(steps=1, timeout_s=0.1)
    dirs = [e for e in os.listdir(tmp_path) if e.startswith("profile-")]
    assert len(dirs) == 2
    snap = cap.snapshot()
    assert len(snap["captures"]) == 4
    assert snap["captures"][-1]["trace_dir"].endswith(sorted(dirs)[-1])


def test_profile_capture_busy_and_kill_switch(tmp_path, monkeypatch):
    from deeplearning4j_tpu.profiler import xprof

    monkeypatch.setattr(xprof, "DeviceProfiler", _FakeProfiler)
    cap = pc.ProfileCapture(out_dir=str(tmp_path))
    assert cap._busy.acquire(blocking=False)
    try:
        with pytest.raises(pc.CaptureBusy):
            cap.capture(steps=1, timeout_s=0.1)
    finally:
        cap._busy.release()
    monkeypatch.setenv("DL4J_TPU_PROFILE", "0")
    with pytest.raises(pc.ProfileDisabled):
        cap.capture(steps=1, timeout_s=0.1)
    assert cap.snapshot()["enabled"] is False


def test_debug_profile_http_roundtrip(tmp_path, monkeypatch):
    """GET /debug/profile?steps=N captures while work flows and serves
    the parsed record; plain GET lists retained captures; the kill
    switch answers 403."""
    from deeplearning4j_tpu.profiler import xprof
    from deeplearning4j_tpu.ui import UIServer

    # pre-pay the xplane-proto (tensorflow) import OUTSIDE the HTTP
    # request: on this box it costs ~20s cold, and paying it inside the
    # capture handler blows the client's socket timeout
    pytest.importorskip("tensorflow.tsl.profiler.protobuf.xplane_pb2")
    monkeypatch.setattr(xprof, "DeviceProfiler", _FakeProfiler)
    monkeypatch.setenv("DL4J_TPU_POSTMORTEM_DIR", str(tmp_path))
    reset_global_registry()
    pc.reset_global_profile_capture()
    net = _net()
    ds = _data()
    net.fit(ds)

    stop = threading.Event()

    def work():
        while not stop.is_set():
            net.fit(ds)

    t = threading.Thread(target=work, daemon=True)
    t.start()
    server = UIServer(port=0).start()
    try:
        rec = json.loads(urllib.request.urlopen(
            server.get_address() + "/debug/profile?steps=2&timeout_s=10",
            timeout=30).read())
        assert rec["steps_seen"] >= 2
        assert rec["trace_dir"].startswith(str(tmp_path))
        assert "top_ops" in rec or "parse_error" in rec

        listing = json.loads(urllib.request.urlopen(
            server.get_address() + "/debug/profile", timeout=5).read())
        assert listing["enabled"] is True
        assert any(c["id"] == rec["id"] for c in listing["captures"])

        monkeypatch.setenv("DL4J_TPU_PROFILE", "0")
        with pytest.raises(urllib.error.HTTPError) as ei:
            urllib.request.urlopen(
                server.get_address() + "/debug/profile?steps=1", timeout=5)
        assert ei.value.code == 403
    finally:
        stop.set()
        t.join(30.0)
        server.stop()
        pc.reset_global_profile_capture()
        reset_global_registry()
    assert not t.is_alive()


def test_real_device_profiler_capture(tmp_path):
    """One REAL jax.profiler capture (no fakes): the trace lands on disk
    and the record parses or reports why not — proves the /debug/profile
    path against the actual profiler, not just the lifecycle."""
    reset_global_registry()
    net = _net()
    ds = _data()
    net.fit(ds)
    cap = pc.ProfileCapture(out_dir=str(tmp_path))

    stop = threading.Event()

    def work():
        while not stop.is_set():
            net.fit(ds)

    t = threading.Thread(target=work, daemon=True)
    t.start()
    try:
        rec = cap.capture(steps=1, timeout_s=15)
    finally:
        stop.set()
        t.join(30.0)
    assert not t.is_alive()
    assert rec["steps_seen"] >= 1
    assert os.path.isdir(rec["trace_dir"])
    if "parse_error" not in rec:
        assert isinstance(rec["top_ops"], list)
        assert rec["source"] in ("device", "host")
    reset_global_registry()


# ---------------------------------------------------------------------------
# lints: metric naming + env-knob table stay green with the new series
# ---------------------------------------------------------------------------

def test_metric_names_lint_green():
    mod = _load_tool("check_metric_names")
    violations = mod.check_package(
        os.path.join(_REPO_ROOT, "deeplearning4j_tpu"))
    assert violations == [], "\n".join(str(v) for v in violations)


def test_env_knob_lint_green():
    mod = _load_tool("check_env_knobs")
    violations = mod.check_repo(_REPO_ROOT)
    assert violations == [], "\n".join(str(v) for v in violations)


def test_cost_model_module_has_no_date_dependence():
    """The snapshot is a pure function of recorded state (drivable from
    tests and postmortems): serializable via json with default=str."""
    snap = global_cost_model().snapshot()
    json.dumps(snap, default=str)
    assert set(snap) >= {"enabled", "device_kind", "peak_flops",
                         "hbm_bytes_per_second", "ridge_intensity", "fns"}
