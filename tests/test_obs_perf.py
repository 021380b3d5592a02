"""Performance observatory (ISSUE 6): XLA cost-model accounting, live
MFU/roofline, perf-regression SLO, on-demand profiler capture, bench
trajectory diff, metric/knob lints."""
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
        t.join()
        server.stop()
        pc.reset_global_profile_capture()
        reset_global_registry()


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
        t.join()
    assert rec["steps_seen"] >= 1
    assert os.path.isdir(rec["trace_dir"])
    if "parse_error" not in rec:
        assert isinstance(rec["top_ops"], list)
        assert rec["source"] in ("device", "host")
    reset_global_registry()


# ---------------------------------------------------------------------------
# bench trajectory diff (tools/bench_diff.py)
# ---------------------------------------------------------------------------

def test_bench_diff_green_on_repo_history(capsys):
    """The archived BENCH_r*.json trajectory holds no sustained
    regression (the round-4 single-sample dip is weather, not climate)."""
    mod = _load_tool("bench_diff")
    assert mod.main([_REPO_ROOT]) == 0


def _sample(rnd, vs_baseline, platform="tpu", metric="m", mfu=None):
    mod = _load_tool("bench_diff")
    return mod.Sample(round=rnd, path=f"BENCH_r{rnd:02d}.json",
                      metric=metric, platform=platform,
                      vs_baseline=vs_baseline, mfu=mfu,
                      device_timed=mfu is not None, value=1.0)


def test_bench_diff_detects_sustained_regression():
    mod = _load_tool("bench_diff")
    history = [_sample(r, v) for r, v in
               enumerate([1.0, 1.02, 0.98, 0.6, 0.62], start=1)]
    regs = mod.check_trajectory(history)
    assert len(regs) == 1
    assert regs[0].series == "vs_baseline" and regs[0].rounds == (4, 5)


def test_bench_diff_single_dip_is_not_a_regression():
    """One bad round (this box's ±40% weather) never fails the gate —
    only a SUSTAINED drop does."""
    mod = _load_tool("bench_diff")
    history = [_sample(r, v) for r, v in
               enumerate([1.0, 1.02, 0.98, 0.6, 1.01], start=1)]
    assert mod.check_trajectory(history) == []


def test_bench_diff_ignores_platform_changes():
    """A CPU-fallback round is incomparable with the TPU trajectory: the
    gate only grades rounds on the newest round's platform."""
    mod = _load_tool("bench_diff")
    history = ([_sample(r, 1.0) for r in (1, 2, 3)]
               + [_sample(4, 0.4, platform="cpu"),
                  _sample(5, 0.4, platform="cpu")])
    # newest platform is cpu → only 2 comparable rounds → thin-data skip
    assert mod.check_trajectory(history) == []
    history = [_sample(r, 1.0) for r in (1, 2, 3)] \
        + [_sample(4, 0.4, platform="cpu"), _sample(5, 1.0)]
    assert mod.check_trajectory(history) == []


def test_bench_diff_grades_device_mfu_series():
    mod = _load_tool("bench_diff")
    history = [_sample(r, None, mfu=m) for r, m in
               enumerate([0.46, 0.45, 0.47, 0.30, 0.31], start=1)]
    regs = mod.check_trajectory(history)
    assert len(regs) == 1 and regs[0].series == "device_mfu"


def test_bench_diff_empty_or_missing_trajectory_is_clean(tmp_path):
    """A fresh checkout (no BENCH_r*/MULTICHIP_r* archives) or a bogus
    root grades clean — exit 0, no crash, an explicit message."""
    mod = _load_tool("bench_diff")
    assert mod.main([str(tmp_path)]) == 0
    assert mod.main([str(tmp_path / "never_created")]) == 0
    assert mod.check_trajectory([]) == []
    assert mod.check_multichip([]) == []


def test_bench_diff_learns_multichip_dryruns(tmp_path):
    """MULTICHIP_r*.json driver dryruns ({n_devices, rc, ok, skipped,
    tail} — no 'metric' key) load as a boolean trajectory: newest
    non-skipped round failing = a break; an OLD failure healed by a
    newer pass, and skipped rounds, stay green. Unreadable/alien JSON is
    ignored, never fatal."""
    import json as _json
    mod = _load_tool("bench_diff")

    def write(rnd, **doc):
        p = tmp_path / f"MULTICHIP_r{rnd:02d}.json"
        p.write_text(_json.dumps(doc))
        return p

    write(1, n_devices=8, rc=1, ok=False, skipped=False, tail="boom")
    write(2, n_devices=8, rc=0, ok=True, skipped=False, tail="OK")
    write(3, skipped=True)
    (tmp_path / "MULTICHIP_r04.json").write_text("not json {")
    samples = mod.load_multichip(str(tmp_path))
    assert [(s.round, s.ok, s.skipped) for s in samples] == [
        (1, False, False), (2, True, False), (3, False, True)]
    # newest non-skipped round (r02) passes → the r01 failure is history
    assert mod.check_multichip(samples) == []
    assert mod.main([str(tmp_path)]) == 0
    # a failing newest round IS a break (boolean — no noise to sustain)
    write(5, n_devices=8, rc=3, ok=False, skipped=False, tail="died")
    samples = mod.load_multichip(str(tmp_path))
    breaks = mod.check_multichip(samples)
    assert len(breaks) == 1 and "r05" in breaks[0]
    assert mod.main([str(tmp_path)]) == 1


def test_bench_diff_learns_decode_schema(tmp_path):
    """DECODE_r*.json decode-bench archives: the combined {kv, cb}
    document loads both records, the A/B ratios + slot-occupancy mean
    grade sustained-only like the bench ratios, raw tokens/s is never
    gated, and alien/unreadable JSON is ignored."""
    import json as _json
    mod = _load_tool("bench_diff")

    def write(rnd, kv_ratio, occ):
        p = tmp_path / f"DECODE_r{rnd:02d}.json"
        p.write_text(_json.dumps({
            "kv": {"metric": "decode_kv_cache", "platform": "cpu",
                   "vs_naive": kv_ratio, "value": 500.0},
            "cb": {"metric": "decode_continuous_batching",
                   "platform": "cpu", "vs_static": 1.4,
                   "slot_occupancy": occ, "value": 700.0}}))

    for rnd, ratio in enumerate([7.0, 6.6, 7.2], start=1):
        write(rnd, ratio, [0.85, 0.9])
    samples = mod.load_decode(str(tmp_path))
    assert len(samples) == 6               # 2 records per round
    assert {s.metric for s in samples} == {
        "decode_kv_cache", "decode_continuous_batching"}
    assert mod.check_decode(samples) == []
    assert mod.main([str(tmp_path)]) == 0
    # a single dip is weather; a sustained collapse is a regression
    write(4, 2.0, [0.86])
    assert mod.check_decode(mod.load_decode(str(tmp_path))) == []
    write(5, 2.1, [0.87])
    regs = mod.check_decode(mod.load_decode(str(tmp_path)))
    assert len(regs) == 1
    assert regs[0].metric == "decode_kv_cache"
    assert regs[0].series == "ab_ratio" and regs[0].rounds == (4, 5)
    assert mod.main([str(tmp_path)]) == 1
    # occupancy trajectory collapse is graded the same way
    write(4, 7.0, [0.3]), write(5, 7.0, [0.3])
    regs = mod.check_decode(mod.load_decode(str(tmp_path)))
    assert [r.series for r in regs] == ["slot_occupancy"]
    # alien / unreadable JSON is ignored, never fatal
    (tmp_path / "DECODE_r06.json").write_text("not json {")
    (tmp_path / "DECODE_r07.json").write_text('{"whatever": 1}')
    assert len(mod.load_decode(str(tmp_path))) == 10


def test_bench_diff_decode_raw_rate_is_not_gated(tmp_path):
    """Raw tokens/s may crater (box weather) without failing the gate —
    only the interleaved A/B ratios and occupancy grade."""
    import json as _json
    mod = _load_tool("bench_diff")
    for rnd, rate in enumerate([900.0, 880.0, 910.0, 100.0, 95.0],
                               start=1):
        (tmp_path / f"DECODE_r{rnd:02d}.json").write_text(_json.dumps(
            {"kv": {"metric": "decode_kv_cache", "platform": "cpu",
                    "vs_naive": 7.0, "value": rate}}))
    assert mod.check_decode(mod.load_decode(str(tmp_path))) == []
    assert mod.main([str(tmp_path)]) == 0


def test_bench_diff_learns_paged_quant_spec_fields(tmp_path):
    """The PR-13 decode arms: vs_dense_cache / vs_f32 / vs_no_spec are
    graded as each metric's A/B ratio (sustained-only), while the
    speculative accept ratio is loaded and REPORTED but never gated —
    an accept-rate collapse alone cannot fail the trajectory."""
    import json as _json
    mod = _load_tool("bench_diff")

    def write(rnd, paged=2.0, quant=0.8, spec=1.5, accept=0.8):
        (tmp_path / f"DECODE_r{rnd:02d}.json").write_text(_json.dumps({
            "paged": {"metric": "decode_paged_cache", "platform": "cpu",
                      "vs_dense_cache": paged, "value": 600.0},
            "quant": {"metric": "decode_kv_quant", "platform": "cpu",
                      "vs_f32": quant, "value": 450.0},
            "spec": {"metric": "decode_speculative", "platform": "cpu",
                     "vs_no_spec": spec, "spec_accept_ratio": accept,
                     "value": 900.0}}))

    for rnd in (1, 2, 3):
        write(rnd)
    samples = mod.load_decode(str(tmp_path))
    assert {s.metric for s in samples} == {
        "decode_paged_cache", "decode_kv_quant", "decode_speculative"}
    spec = [s for s in samples if s.metric == "decode_speculative"][0]
    assert spec.ratio == 1.5 and spec.accept_ratio == 0.8
    assert mod.check_decode(samples) == []
    # accept-rate collapse alone: reported, never a regression
    write(4, accept=0.05), write(5, accept=0.05)
    assert mod.check_decode(mod.load_decode(str(tmp_path))) == []
    # a sustained vs_no_spec collapse IS one, attributed to its metric
    write(4, spec=0.5, accept=0.8), write(5, spec=0.5, accept=0.8)
    regs = mod.check_decode(mod.load_decode(str(tmp_path)))
    assert [(r.metric, r.series) for r in regs] == [
        ("decode_speculative", "ab_ratio")]
    # same discipline for the paged and quant ratios
    write(4, paged=0.9, spec=1.5), write(5, paged=0.9, spec=1.5)
    regs = mod.check_decode(mod.load_decode(str(tmp_path)))
    assert [(r.metric, r.series) for r in regs] == [
        ("decode_paged_cache", "ab_ratio")]
    assert mod.main([str(tmp_path)]) == 1


def test_bench_diff_learns_serve_schema(tmp_path):
    """SERVE_r*.json HTTP-load archives (benchmarks/http_load.py): the
    interleaved vs_direct ratio + goodput grade sustained-only, raw
    p50/p99 latency is never gated, driver wrappers are unwrapped, and
    alien/unreadable JSON is ignored."""
    import json as _json
    mod = _load_tool("bench_diff")

    def write(rnd, ratio, goodput, p99=150.0, wrap=False):
        rec = {"metric": "http_serve", "platform": "cpu",
               "vs_direct": ratio, "goodput": goodput, "value": goodput,
               "p99_ms": p99, "failed": 0}
        doc = {"n": rnd, "parsed": rec} if wrap else rec
        (tmp_path / f"SERVE_r{rnd:02d}.json").write_text(_json.dumps(doc))

    for rnd, (ratio, gp) in enumerate(
            [(0.5, 100.0), (0.46, 104.0), (0.52, 98.0)], start=1):
        write(rnd, ratio, gp, wrap=(rnd == 2))   # wrapper unwrapped too
    samples = mod.load_serve(str(tmp_path))
    assert [s.round for s in samples] == [1, 2, 3]
    assert samples[1].vs_direct == pytest.approx(0.46)
    assert mod.check_serve(samples) == []
    assert mod.main([str(tmp_path)]) == 0
    # one bad round is weather...
    write(4, 0.2, 101.0)
    assert mod.check_serve(mod.load_serve(str(tmp_path))) == []
    # ...two in a row is a sustained ratio regression
    write(5, 0.21, 99.0)
    regs = mod.check_serve(mod.load_serve(str(tmp_path)))
    assert len(regs) == 1
    assert regs[0].metric == "http_serve"
    assert regs[0].series == "ab_ratio" and regs[0].rounds == (4, 5)
    assert mod.main([str(tmp_path)]) == 1
    # goodput collapse is graded the same way; p99 never is
    write(4, 0.5, 20.0, p99=9000.0)
    write(5, 0.5, 19.0, p99=9000.0)
    regs = mod.check_serve(mod.load_serve(str(tmp_path)))
    assert [r.series for r in regs] == ["goodput"]
    # platform filter: CPU-fallback history doesn't grade a TPU round
    write(4, 0.5, 100.0)
    (tmp_path / "SERVE_r05.json").write_text(_json.dumps(
        {"metric": "http_serve", "platform": "tpu", "vs_direct": 0.9,
         "goodput": 5000.0}))
    assert mod.check_serve(mod.load_serve(str(tmp_path))) == []
    # alien / unreadable JSON is ignored, never fatal
    (tmp_path / "SERVE_r06.json").write_text("not json {")
    (tmp_path / "SERVE_r07.json").write_text('{"whatever": 1}')
    assert len(mod.load_serve(str(tmp_path))) == 5
    assert mod.main([str(tmp_path)]) == 0


def test_bench_diff_learns_fleet_schema(tmp_path):
    """FLEET_r*.json chaos-drill archives (http_load.py --fleet-chaos):
    goodput-under-chaos + the duplicate-execution ratio grade
    sustained-only, the leader-term/stage booleans gate like MULTICHIP
    (newest round must pass), raw p99 is never gated, and alien/empty
    JSON is green."""
    import json as _json
    mod = _load_tool("bench_diff")

    def write(rnd, goodput, dups=0, terms=True, regressed=False,
              p99=300.0, wrap=False):
        rec = {"metric": "fleet_chaos", "platform": "cpu",
               "goodput_ratio": goodput, "value": goodput,
               "duplicate_executions": dups, "terms_monotonic": terms,
               "stage_regressed": regressed, "p99_ms": p99}
        doc = {"n": rnd, "parsed": rec} if wrap else rec
        (tmp_path / f"FLEET_r{rnd:02d}.json").write_text(_json.dumps(doc))

    for rnd, gp in enumerate([0.97, 0.95, 0.98], start=1):
        write(rnd, gp, wrap=(rnd == 2))           # wrapper unwrapped too
    samples = mod.load_fleet(str(tmp_path))
    assert [s.round for s in samples] == [1, 2, 3]
    assert samples[0].dup_free == pytest.approx(1.0)
    assert mod.check_fleet(samples) == []
    assert mod.check_fleet_bool(samples) == []
    assert mod.main([str(tmp_path)]) == 0
    # one bad goodput round is weather...
    write(4, 0.5)
    assert mod.check_fleet(mod.load_fleet(str(tmp_path))) == []
    # ...two in a row is a sustained regression
    write(5, 0.52)
    regs = mod.check_fleet(mod.load_fleet(str(tmp_path)))
    assert [r.series for r in regs] == ["goodput"]
    assert regs[0].rounds == (4, 5)
    assert mod.main([str(tmp_path)]) == 1
    # duplicate executions drive the dup_free ratio below the floor
    write(4, 0.97, dups=2)
    write(5, 0.96, dups=1)
    regs = mod.check_fleet(mod.load_fleet(str(tmp_path)))
    assert [r.series for r in regs] == ["dup_free"]
    # the boolean audit gates like MULTICHIP: newest round failing = break
    write(4, 0.97)
    write(5, 0.96, terms=False, regressed=True)
    assert mod.check_fleet(mod.load_fleet(str(tmp_path))) == []
    breaks = mod.check_fleet_bool(mod.load_fleet(str(tmp_path)))
    assert len(breaks) == 2 and "leader-term" in breaks[0]
    assert mod.main([str(tmp_path)]) == 2
    # p99 collapse alone never gates
    write(5, 0.97, p99=90000.0)
    assert mod.check_fleet(mod.load_fleet(str(tmp_path))) == []
    assert mod.check_fleet_bool(mod.load_fleet(str(tmp_path))) == []
    # alien / unreadable JSON is ignored, never fatal; empty dir green
    (tmp_path / "FLEET_r06.json").write_text("not json {")
    (tmp_path / "FLEET_r07.json").write_text('{"whatever": 1}')
    assert len(mod.load_fleet(str(tmp_path))) == 5
    assert mod.main([str(tmp_path)]) == 0
    empty = tmp_path / "empty"
    empty.mkdir()
    assert mod.load_fleet(str(empty)) == []
    assert mod.main([str(empty)]) == 0


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


# ---------------------------------------------------------------------------
# bench_diff: TRACEQ trace-intelligence trajectory grading
# ---------------------------------------------------------------------------

def test_bench_diff_learns_traceq_schema(tmp_path):
    """TRACEQ_r*.json (http_load.py --trace-intel): retention coverage
    and assembly completeness grade sustained-only, assembly p99 is
    reported but never gated, driver wrappers unwrap, alien JSON is
    ignored, empty dir is green."""
    mod = _load_tool("bench_diff")
    assert mod.load_traceq(str(tmp_path)) == []
    assert mod.main([str(tmp_path)]) == 0               # empty = green

    def write(rnd, cov, comp, p99=15.0, wrap=False):
        rec = {"metric": "traceq_drill", "platform": "cpu",
               "value": cov, "retention_coverage": cov,
               "assembly_completeness": comp, "assembly_p99_ms": p99}
        doc = {"n": rnd, "parsed": rec} if wrap else rec
        (tmp_path / f"TRACEQ_r{rnd:02d}.json").write_text(
            json.dumps(doc))

    write(1, 1.0, 1.0)
    write(2, 0.99, 1.0, wrap=True)                      # wrapper unwraps
    write(3, 1.0, 1.0, p99=800.0)                       # p99 never gated
    samples = mod.load_traceq(str(tmp_path))
    assert [s.round for s in samples] == [1, 2, 3]
    assert samples[1].retention_coverage == pytest.approx(0.99)
    assert samples[2].assembly_p99_ms == pytest.approx(800.0)
    assert mod.check_traceq(samples) == []
    assert mod.main([str(tmp_path)]) == 0
    # one bad round is weather...
    write(4, 0.5, 1.0)
    assert mod.check_traceq(mod.load_traceq(str(tmp_path))) == []
    # ...two in a row is a sustained retention regression
    write(5, 0.5, 1.0)
    regs = mod.check_traceq(mod.load_traceq(str(tmp_path)))
    assert [(r.metric, r.series) for r in regs] == [
        ("traceq_drill", "retention_coverage")]
    assert mod.main([str(tmp_path)]) == 1
    # an assembly collapse grades the same way
    write(4, 1.0, 0.4)
    write(5, 1.0, 0.4)
    regs = mod.check_traceq(mod.load_traceq(str(tmp_path)))
    assert [r.series for r in regs] == ["assembly_completeness"]
    # alien / unreadable JSON is ignored, never fatal
    (tmp_path / "TRACEQ_r06.json").write_text("not json {")
    (tmp_path / "TRACEQ_r07.json").write_text('{"whatever": 1}')
    assert len(mod.load_traceq(str(tmp_path))) == 5
