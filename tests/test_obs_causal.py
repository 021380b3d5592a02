"""Causal observability: trace context across threads, flight recorder,
SLO-driven health, exemplars, and the metric-naming lint (ISSUE 3)."""
import collections
import importlib.util
import json
import os
import threading
import time
import urllib.error
import urllib.request

import numpy as np
import pytest

from deeplearning4j_tpu.data.dataset import DataSet
from deeplearning4j_tpu.nn.conf.configuration import NeuralNetConfiguration
from deeplearning4j_tpu.nn.conf.inputs import InputType
from deeplearning4j_tpu.nn.conf.layers import DenseLayer, OutputLayer
from deeplearning4j_tpu.nn.multilayer import MultiLayerNetwork
from deeplearning4j_tpu.observability import (metrics,
                                              reset_global_registry,
                                              reset_global_trace_sink)
from deeplearning4j_tpu.optim.updaters import Adam

_REPO_ROOT = os.path.normpath(os.path.join(os.path.dirname(__file__),
                                           os.pardir))


def _net():
    return MultiLayerNetwork(
        NeuralNetConfiguration.builder().seed(1).updater(Adam(1e-2))
        .weight_init("xavier").list()
        .layer(DenseLayer(n_in=4, n_out=8, activation="relu"))
        .layer(OutputLayer(n_out=3, activation="softmax",
                           loss_function="mcxent"))
        .set_input_type(InputType.feed_forward(4)).build()).init()


def _data(n=32, seed=0):
    rng = np.random.RandomState(seed)
    X = rng.rand(n, 4).astype("f4")
    return DataSet(X, np.eye(3)[rng.randint(0, 3, n)].astype("f4"))


# ---------------------------------------------------------------------------
# trace context
# ---------------------------------------------------------------------------

def test_span_trace_context_ids_nest():
    from deeplearning4j_tpu.observability import TraceSink, span

    sink = TraceSink(capacity=16)
    with span("root", sink=sink) as root:
        with span("child", sink=sink) as child:
            assert child.trace_id == root.trace_id
            assert child.parent_id == root.span_id
    recs = {r.name: r for r in sink.spans()}
    assert recs["root"].parent_id is None
    assert recs["child"].trace_id == recs["root"].trace_id
    assert recs["child"].parent_id == recs["root"].span_id
    # ids surface in the chrome export args
    ev = {e["name"]: e for e in sink.to_chrome_trace()
          if e["ph"] == "X"}
    assert ev["child"]["args"]["trace_id"] == recs["root"].trace_id
    assert ev["child"]["args"]["parent_id"] == recs["root"].span_id


def test_trace_context_crosses_threads_with_flow_events():
    from deeplearning4j_tpu.observability import (TraceSink, current_context,
                                                  span, trace_context)

    sink = TraceSink(capacity=16)
    captured = {}
    with span("producer", sink=sink) as p:
        ctx = current_context()
        assert ctx.trace_id == p.trace_id and ctx.span_id == p.span_id

        def worker():
            with trace_context(ctx), span("consumer", sink=sink):
                captured["inner"] = current_context()

        t = threading.Thread(target=worker)
        t.start()
        t.join(30.0)
        assert not t.is_alive()
    recs = {r.name: r for r in sink.spans()}
    assert recs["consumer"].trace_id == recs["producer"].trace_id
    assert recs["consumer"].parent_id == recs["producer"].span_id
    assert recs["consumer"].tid != recs["producer"].tid
    assert captured["inner"].trace_id == ctx.trace_id
    # the cross-thread edge draws a flow-event pair (ph s on the producer
    # thread, ph f on the consumer thread, same id)
    flows = [e for e in sink.to_chrome_trace() if e["ph"] in ("s", "f")]
    assert {e["ph"] for e in flows} == {"s", "f"}
    s_ev = next(e for e in flows if e["ph"] == "s")
    f_ev = next(e for e in flows if e["ph"] == "f")
    assert s_ev["id"] == f_ev["id"] == recs["consumer"].span_id
    assert s_ev["tid"] == recs["producer"].tid
    assert f_ev["tid"] == recs["consumer"].tid
    assert s_ev["ts"] <= f_ev["ts"]


def test_record_span_external_timing_parents_into_trace():
    from deeplearning4j_tpu.observability import (TraceSink, now_us,
                                                  record_span, span)

    sink = TraceSink(capacity=8)
    with span("request", sink=sink) as root:
        from deeplearning4j_tpu.observability import current_context
        ctx = current_context()
    start = now_us() - 5_000
    rec = record_span("queue_wait", start, ctx=ctx, sink=sink, examples=3)
    assert rec.trace_id == root.trace_id
    assert rec.parent_id == root.span_id
    assert rec.dur_us >= 4_000
    assert rec.attrs["examples"] == 3


def test_span_exit_records_error_and_counter():
    from deeplearning4j_tpu.observability import TraceSink, span

    reset_global_registry()
    sink = TraceSink(capacity=8)
    with pytest.raises(ValueError):
        with span("exploding_section", sink=sink):
            raise ValueError("boom")
    rec = sink.spans()[-1]
    assert rec.error and rec.error_type == "ValueError"
    ev = rec.to_chrome_event()
    assert ev["args"]["error"] is True
    assert ev["args"]["error_type"] == "ValueError"
    text = metrics().render_prometheus()
    assert 'dl4j_span_errors_total{name="exploding_section"} 1' in text
    # clean spans don't touch the counter
    with span("fine_section", sink=sink):
        pass
    assert not sink.spans()[-1].error


def test_trace_ring_drop_and_fill_metrics():
    from deeplearning4j_tpu.observability import span, trace_sink

    reset_global_registry()
    sink = reset_global_trace_sink(capacity=64)
    # drop flushing is batched every 64 records (hot-path lock hygiene):
    # 192 records into a 64-slot ring = 128 overwrites, all flushed by
    # the ticks at totals 128 and 192
    for i in range(192):
        with span(f"s{i}"):
            pass
    reg = metrics()
    assert sink.dropped == 128                # exact property
    assert reg.get("dl4j_trace_spans_dropped_total").value == 128
    assert reg.get("dl4j_trace_ring_fill_ratio").value == 1.0
    # clear() flushes stragglers and zeroes the occupancy gauge
    with span("one-more"):
        pass
    trace_sink().clear()
    assert reg.get("dl4j_trace_spans_dropped_total").value == 129
    assert reg.get("dl4j_trace_ring_fill_ratio").value == 0.0
    reset_global_trace_sink()


# ---------------------------------------------------------------------------
# cross-thread propagation through the real pipelines
# ---------------------------------------------------------------------------

def test_inference_request_phases_share_one_trace():
    """Acceptance: every request's queue_wait/dispatch/device/complete
    spans share its trace_id, cross ≥2 threads, and the chrome export has
    flow events linking them."""
    from deeplearning4j_tpu.parallel.inference import (InferenceMode,
                                                       ParallelInference)

    reset_global_registry()
    sink = reset_global_trace_sink()
    net = _net()
    x = np.random.RandomState(0).rand(8, 4).astype("f4")
    pb = (ParallelInference.Builder(net)
          .inference_mode(InferenceMode.BATCHED).batch_limit(8).build())
    results = {}
    try:
        def call(i):
            results[i] = pb.output(x[i:i + 2])

        threads = [threading.Thread(target=call, args=(i,))
                   for i in range(0, 8, 2)]
        for t in threads:
            t.start()
        for t in threads:
            t.join(timeout=30)
        assert len(results) == 4
    finally:
        pb.shutdown()

    spans = sink.spans()
    by_trace = collections.defaultdict(set)
    tids = collections.defaultdict(set)
    for r in spans:
        by_trace[r.trace_id].add(r.name)
        tids[r.trace_id].add(r.tid)
    roots = [r for r in spans if r.name == "inference_request"]
    assert len(roots) == 4
    for root in roots:
        assert {"inference_request", "queue_wait", "bucket_pad",
                "dispatch", "device", "complete"} <= by_trace[root.trace_id]
        assert len(tids[root.trace_id]) >= 2     # crossed the pipeline
    flows = [e for e in sink.to_chrome_trace() if e["ph"] in ("s", "f")]
    assert flows
    # phase spans parent DIRECTLY under their request root
    phase = next(r for r in spans if r.name == "queue_wait")
    root = next(r for r in roots if r.trace_id == phase.trace_id)
    assert phase.parent_id == root.span_id


def test_inference_sync_loop_propagates_too(monkeypatch):
    monkeypatch.setenv("DL4J_TPU_ASYNC", "0")
    from deeplearning4j_tpu.parallel.inference import (InferenceMode,
                                                       ParallelInference)

    reset_global_registry()
    sink = reset_global_trace_sink()
    net = _net()
    x = np.random.RandomState(0).rand(2, 4).astype("f4")
    pb = (ParallelInference.Builder(net)
          .inference_mode(InferenceMode.BATCHED).batch_limit(4).build())
    try:
        pb.output(x)
    finally:
        pb.shutdown()
    root = next(r for r in sink.spans() if r.name == "inference_request")
    names = {r.name for r in sink.spans() if r.trace_id == root.trace_id}
    assert {"queue_wait", "bucket_pad", "device", "complete"} <= names


def test_prefetch_thread_joins_fit_trace():
    from deeplearning4j_tpu.data.iterators import ListDataSetIterator

    reset_global_registry()
    sink = reset_global_trace_sink()
    net = _net()
    net.fit(ListDataSetIterator([_data()] * 3), epochs=2)
    spans = sink.spans()
    fit = next(r for r in spans if r.name == "fit")
    prefetch = [r for r in spans if r.name == "prefetch_place"]
    assert prefetch, "prefetch thread recorded no spans"
    assert all(r.trace_id == fit.trace_id for r in prefetch)
    assert any(r.tid != fit.tid for r in prefetch)
    # per-step spans live in the same trace: one trace_id per fit call
    assert all(r.trace_id == fit.trace_id
               for r in spans if r.name == "train_step")


def test_inference_batched_failure_marks_request_span():
    """A batched request that fails must close its inference_request span
    with error=True (and count in dl4j_span_errors_total) — the trace and
    the error counters have to agree about the failure."""
    from deeplearning4j_tpu.parallel.inference import (InferenceMode,
                                                       ParallelInference)

    class _Exploding:
        def output(self, x):
            raise RuntimeError("device on fire")

    reset_global_registry()
    sink = reset_global_trace_sink()
    pb = (ParallelInference.Builder(_Exploding())
          .inference_mode(InferenceMode.BATCHED).batch_limit(4).build())
    try:
        with pytest.raises(RuntimeError, match="device on fire"):
            pb.output(np.zeros((1, 4), "f4"))
    finally:
        pb.shutdown()
    root = next(r for r in sink.spans() if r.name == "inference_request")
    assert root.error and root.error_type == "RuntimeError"
    text = metrics().render_prometheus()
    assert 'dl4j_span_errors_total{name="inference_request"} 1' in text
    assert metrics().get("dl4j_inference_errors_total").value == 1


def test_straggler_detector_watches_inference_dispatch():
    from deeplearning4j_tpu.parallel.inference import (InferenceMode,
                                                       ParallelInference)

    reset_global_registry()
    net = _net()
    x = np.random.RandomState(0).rand(2, 4).astype("f4")
    pb = (ParallelInference.Builder(net)
          .inference_mode(InferenceMode.BATCHED).batch_limit(4).build())
    try:
        for _ in range(6):
            pb.output(x)
    finally:
        pb.shutdown()
    checked = metrics().get("dl4j_straggler_checked_steps_total")
    assert checked is not None
    assert checked.labels(phase="inference_batch").value >= 1


# ---------------------------------------------------------------------------
# flight recorder
# ---------------------------------------------------------------------------

def test_flight_recorder_dump_bundle_contents(tmp_path):
    from deeplearning4j_tpu.observability import FlightRecorder, span

    reset_global_registry()
    reset_global_trace_sink()
    with span("doomed_section"):
        pass
    metrics().counter("dl4j_unit_events_total", "unit").inc(3)
    rec = FlightRecorder(hang_seconds=60, out_dir=str(tmp_path))
    bundle = rec.dump("unit-test")
    files = sorted(os.listdir(bundle))
    assert files == ["compiles.json", "config.json", "deploy.json",
                     "elastic.json", "fleet.json", "frontdoor.json",
                     "generation.json", "metrics.prom", "numerics.json",
                     "perf.json", "resilience.json", "sessions.json",
                     "tenants.json", "threads.txt", "timeseries.json",
                     "trace.json", "traces.json"]
    # the multi-tenant QoS section names the posture + tenant table
    tenants = json.loads(open(os.path.join(bundle, "tenants.json")).read())
    assert "enabled" in tenants and "tenants" in tenants
    # the fleet robustness section carries the idempotency journal view
    fleet = json.loads(open(os.path.join(bundle, "fleet.json")).read())
    assert "idempotency" in fleet
    trace = json.loads(open(os.path.join(bundle, "trace.json")).read())
    assert any(e.get("name") == "doomed_section" for e in trace)
    prom = open(os.path.join(bundle, "metrics.prom")).read()
    assert "dl4j_unit_events_total 3" in prom
    threads_txt = open(os.path.join(bundle, "threads.txt")).read()
    assert "MainThread" in threads_txt
    # the dumping test frame itself is on the main thread's stack
    assert "test_flight_recorder_dump_bundle_contents" in threads_txt
    cfg = json.loads(open(os.path.join(bundle, "config.json")).read())
    assert cfg["reason"] == "unit-test"
    assert "async_runtime" in cfg and "prefetch_depth" in cfg["async_runtime"]
    assert "health" in cfg and cfg["health"]["status"] in (
        "ok", "degraded", "failing")
    # PR 4 observatory sections: device memory in config, compile ring +
    # numerics snapshot as their own files
    assert "device_memory" in cfg
    compiles = json.loads(open(os.path.join(bundle, "compiles.json")).read())
    assert "by_fn" in compiles and "events" in compiles
    numerics = json.loads(open(os.path.join(bundle, "numerics.json")).read())
    assert "nonfinite_events" in numerics
    # the dump itself is a metric
    assert metrics().get("dl4j_postmortem_dumps_total").labels(
        trigger="unit-test").value == 1
    rec.stop()


def test_flight_recorder_watchdog_detects_hang(tmp_path):
    from deeplearning4j_tpu.observability import FlightRecorder

    reset_global_registry()
    rec = FlightRecorder(hang_seconds=0.2, check_interval=0.05,
                         out_dir=str(tmp_path))
    try:
        with rec.arm("fit:unit"):
            deadline = time.monotonic() + 5.0
            while not rec.dumps and time.monotonic() < deadline:
                # progress on an IRRELEVANT channel must not mask the
                # hang: an armed fit listens to train_step only
                rec.progress("inference_batch")
                time.sleep(0.05)
            assert rec.dumps, "watchdog never fired"
            first = len(rec.dumps)
            cfg = json.loads(open(os.path.join(rec.dumps[0],
                                               "config.json")).read())
            assert cfg["reason"].startswith("hang")
            assert "fit:unit" in cfg["reason"]
            assert "fit:unit" in cfg["armed"]
            # one dump per stall episode, not one per watchdog tick
            time.sleep(0.12)
            assert len(rec.dumps) == first
            # RELEVANT progress ends the episode; a fresh stall dumps again
            deadline = time.monotonic() + 5.0
            rec.progress("train_step")
            while len(rec.dumps) == first and time.monotonic() < deadline:
                time.sleep(0.05)
            assert len(rec.dumps) > first, "fresh stall after recovery " \
                                           "did not dump"
    finally:
        rec.stop()


def test_flight_recorder_idle_never_fires(tmp_path):
    from deeplearning4j_tpu.observability import FlightRecorder

    rec = FlightRecorder(hang_seconds=0.1, check_interval=0.03,
                         out_dir=str(tmp_path))
    try:
        with rec.arm("op"):
            rec.progress()
        time.sleep(0.3)                     # disarmed: no dump
        assert rec.dumps == []
    finally:
        rec.stop()


def test_flight_recorder_bundle_retention_cap(tmp_path, monkeypatch):
    from deeplearning4j_tpu.observability import FlightRecorder

    monkeypatch.setenv("DL4J_TPU_POSTMORTEM_KEEP", "3")
    rec = FlightRecorder(hang_seconds=60, out_dir=str(tmp_path))
    for i in range(6):
        rec.dump(f"poll-{i}")
    assert len(rec.dumps) == 3
    on_disk = sorted(os.listdir(tmp_path))
    assert len(on_disk) == 3                 # oldest three evicted
    assert all(p.endswith(("-004", "-005", "-006")) for p in on_disk)
    rec.stop()


def test_flight_recorder_thread_excepthook_dumps(tmp_path, monkeypatch):
    """The ONE process-wide hook set dispatches to the currently-installed
    recorder; installing a second recorder re-points the dispatch instead
    of wrapping hooks around hooks (no bundle-per-generation chains)."""
    from deeplearning4j_tpu.observability import FlightRecorder

    reset_global_registry()
    rec = FlightRecorder(hang_seconds=60, out_dir=str(tmp_path))
    try:
        rec.install()
        hook_after_first = threading.excepthook
        rec2 = FlightRecorder(hang_seconds=60, out_dir=str(tmp_path))
        rec2.install()
        # second install re-targets, it does NOT stack another wrapper
        assert threading.excepthook is hook_after_first
        rec2.stop()
        rec.install()

        def die():
            raise RuntimeError("worker crashed")

        t = threading.Thread(target=die, name="crasher")
        t.start()
        t.join(30.0)
        assert not t.is_alive()
        assert rec.dumps, "fatal thread exception did not dump"
        cfg = json.loads(open(os.path.join(rec.dumps[0],
                                           "config.json")).read())
        assert cfg["reason"] == "thread_exception:RuntimeError"
        assert "crasher" in (cfg["fatal"] or "")
    finally:
        rec.stop()          # re-points dispatch back to the global recorder


def test_debug_dump_endpoint(tmp_path, monkeypatch):
    monkeypatch.setenv("DL4J_TPU_POSTMORTEM_DIR", str(tmp_path))
    from deeplearning4j_tpu.ui import UIServer

    server = UIServer(port=0).start()
    try:
        out = json.loads(urllib.request.urlopen(
            server.get_address() + "/debug/dump", timeout=10).read())
        assert out["bundle"].startswith(str(tmp_path))
        assert {"config.json", "metrics.prom", "threads.txt",
                "trace.json"} <= set(out["files"])
    finally:
        server.stop()


# ---------------------------------------------------------------------------
# SLO engine / health
# ---------------------------------------------------------------------------

def test_slo_rules_grade_and_skip_thin_data():
    from deeplearning4j_tpu.observability import (ErrorRateRule,
                                                  GaugeThresholdRule,
                                                  LatencyQuantileRule,
                                                  MetricsRegistry)

    reg = MetricsRegistry(enabled=True)
    lat = LatencyQuantileRule("lat", "unit_lat_seconds", degraded=0.1,
                              failing=1.0, min_count=4)
    assert lat.evaluate(reg)["status"] == "ok"        # no metric yet
    h = reg.histogram("unit_lat_seconds", "l")
    h.observe(0.05)
    assert lat.evaluate(reg)["status"] == "ok"        # < min_count
    for _ in range(4):
        h.observe(0.5)
    assert lat.evaluate(reg)["status"] == "degraded"
    for _ in range(8):
        h.observe(5.0)
    res = lat.evaluate(reg)
    assert res["status"] == "failing" and res["value"] > 1.0

    err = ErrorRateRule("err", "unit_err_total", "unit_req_total",
                        degraded=0.01, failing=0.5, min_requests=10)
    reg.counter("unit_req_total", "r").inc(20)
    assert err.evaluate(reg)["status"] == "ok"
    reg.counter("unit_err_total", "e").inc(2)         # 10% -> degraded
    assert err.evaluate(reg)["status"] == "degraded"
    reg.counter("unit_err_total", "e").inc(18)        # 100% -> failing
    assert err.evaluate(reg)["status"] == "failing"

    below = GaugeThresholdRule("overlap", "unit_ratio", degraded=0.5,
                               failing=None, mode="below")
    reg.gauge("unit_ratio", "x").set(0.9)
    assert below.evaluate(reg)["status"] == "ok"
    reg.gauge("unit_ratio", "x").set(0.1)
    assert below.evaluate(reg)["status"] == "degraded"  # failing disabled


def test_health_transitions_to_503_and_alerts():
    """Acceptance: an induced SLO breach flips /health to 503 with the
    violated rule named; recovery flips it back."""
    from deeplearning4j_tpu.observability.slo import (global_slo_engine,
                                                      reset_global_slo_engine)
    from deeplearning4j_tpu.ui import UIServer

    reset_global_registry()
    reset_global_slo_engine()
    server = UIServer(port=0).start()
    base = server.get_address()
    try:
        h = json.loads(urllib.request.urlopen(
            base + "/health", timeout=5).read())
        assert h["status"] == "ok" and h["failing_rules"] == []

        # degraded: p99 between 1s and 5s (>= min_count=16 samples)
        lat = metrics().histogram("dl4j_inference_latency_seconds",
                                  "latency", ("mode",))
        for _ in range(16):
            lat.labels(mode="BATCHED").observe(2.0)
        h = json.loads(urllib.request.urlopen(
            base + "/health", timeout=5).read())
        assert h["status"] == "degraded"
        assert "inference_p99_latency_seconds" in h["degraded_rules"]

        # failing: p99 over 5s -> HTTP 503 naming the rule
        for _ in range(20):
            lat.labels(mode="BATCHED").observe(30.0)
        with pytest.raises(urllib.error.HTTPError) as ei:
            urllib.request.urlopen(base + "/health", timeout=5)
        assert ei.value.code == 503
        body = json.loads(ei.value.read())
        assert body["status"] == "failing"
        assert "inference_p99_latency_seconds" in body["failing_rules"]

        alerts = json.loads(urllib.request.urlopen(
            base + "/alerts", timeout=5).read())
        active = {a["rule"]: a for a in alerts["active"]}
        assert active["inference_p99_latency_seconds"]["status"] == "failing"
        assert active["inference_p99_latency_seconds"]["since"] > 0
        assert any(t["to"] == "failing" for t in alerts["history"])

        # recovery: fresh registry -> ok again (and 200)
        reset_global_registry()
        h = json.loads(urllib.request.urlopen(
            base + "/health", timeout=5).read())
        assert h["status"] == "ok"
    finally:
        server.stop()
        reset_global_registry()
        reset_global_slo_engine()


def test_latency_exemplar_links_metrics_to_trace():
    """The exemplar→trace jump: a /metrics tail bucket names a trace_id
    that exists in /train/trace with the request's phase spans."""
    from deeplearning4j_tpu.parallel.inference import (InferenceMode,
                                                       ParallelInference)
    from deeplearning4j_tpu.ui import UIServer

    reset_global_registry()
    sink = reset_global_trace_sink()
    net = _net()
    x = np.random.RandomState(0).rand(2, 4).astype("f4")
    pb = (ParallelInference.Builder(net)
          .inference_mode(InferenceMode.BATCHED).batch_limit(4).build())
    try:
        pb.output(x)
    finally:
        pb.shutdown()
    server = UIServer(port=0).start()
    try:
        # exemplars are OpenMetrics-only: a plain 0.0.4 scrape must stay
        # strictly parseable (no `# {` after values), the negotiated
        # flavor carries them
        plain = urllib.request.urlopen(
            server.get_address() + "/metrics", timeout=5).read().decode()
        assert "# {" not in plain
        req = urllib.request.Request(
            server.get_address() + "/metrics",
            headers={"Accept": "application/openmetrics-text"})
        resp = urllib.request.urlopen(req, timeout=5)
        assert resp.headers["Content-Type"].startswith(
            "application/openmetrics-text")
        text = resp.read().decode()
        assert text.rstrip().endswith("# EOF")
        ex_lines = [l for l in text.splitlines()
                    if l.startswith("dl4j_inference_latency_seconds_bucket")
                    and "# {" in l]
        assert ex_lines, "no exemplar on the latency histogram"
        trace_id = ex_lines[0].split('trace_id="')[1].split('"')[0]
        trace = json.loads(urllib.request.urlopen(
            server.get_address() + "/train/trace", timeout=5).read())
        names = {e["name"] for e in trace
                 if e["ph"] == "X"
                 and e.get("args", {}).get("trace_id") == trace_id}
        assert "inference_request" in names
        assert {"queue_wait", "device"} <= names
    finally:
        server.stop()


# ---------------------------------------------------------------------------
# kill switches + lint
# ---------------------------------------------------------------------------

def test_trace_kill_switch_keeps_metrics(monkeypatch):
    monkeypatch.setenv("DL4J_TPU_TRACE", "0")
    from deeplearning4j_tpu.observability import span

    reset_global_registry()
    sink = reset_global_trace_sink()
    net = _net()
    net.fit(_data())
    assert sink.total_recorded == 0           # spans off
    step = metrics().get("dl4j_training_step_seconds")
    assert step.labels(model="MultiLayerNetwork").count >= 1  # metrics on
    with span("dead"):
        pass
    assert sink.total_recorded == 0


def test_metric_naming_conventions_lint():
    spec = importlib.util.spec_from_file_location(
        "check_metric_names",
        os.path.join(_REPO_ROOT, "tools", "check_metric_names.py"))
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    violations = mod.check_package(
        os.path.join(_REPO_ROOT, "deeplearning4j_tpu"))
    assert violations == [], "\n".join(str(v) for v in violations)
    # the lint itself catches offenders
    bad = mod.check_source(
        "reg.counter('requests', 'd')\n"
        "reg.histogram('dl4j_x_total', 'd')\n"
        "reg.gauge('dl4j_ok_depth', '')\n")
    msgs = " | ".join(str(v) for v in bad)
    assert "namespace prefix" in msgs and "_total" in msgs
    assert len(bad) >= 3
