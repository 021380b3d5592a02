"""UI/stats (D16), profiler (J12), ParallelInference (P8), crash dumps (5.5)."""
import json
import os
import urllib.request

import numpy as np
import pytest

from deeplearning4j_tpu.nn.conf.configuration import NeuralNetConfiguration
from deeplearning4j_tpu.nn.conf.inputs import InputType
from deeplearning4j_tpu.nn.conf.layers import DenseLayer, OutputLayer
from deeplearning4j_tpu.nn.multilayer import MultiLayerNetwork
from deeplearning4j_tpu.optim.updaters import Adam
from deeplearning4j_tpu.data.dataset import DataSet


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


def test_stats_listener_memory_storage():
    from deeplearning4j_tpu.ui import InMemoryStatsStorage, StatsListener
    storage = InMemoryStatsStorage()
    net = _net()
    net.setListeners(StatsListener(storage, session_id="s1"))
    net.fit([_data()] * 3, epochs=2)
    ups = storage.get_all_updates("s1")
    assert len(ups) == 6
    assert all("score" in u and "parameters" in u for u in ups)
    p = ups[-1]["parameters"]
    assert "0_W" in p and "meanMagnitude" in p["0_W"]
    assert "updates" in ups[-1]          # param deltas from iteration 2 on
    assert storage.list_session_ids() == ["s1"]


def test_file_stats_storage_roundtrip(tmp_path):
    from deeplearning4j_tpu.ui import FileStatsStorage
    path = os.path.join(str(tmp_path), "stats.jsonl")
    st = FileStatsStorage(path)
    st.put_update("a", {"iteration": 1, "score": 0.5})
    st.put_update("a", {"iteration": 2, "score": 0.4})
    st2 = FileStatsStorage(path)       # reopen
    assert len(st2.get_all_updates("a")) == 2
    assert st2.get_latest_update("a")["score"] == 0.4


def test_ui_server_serves_overview_and_json():
    from deeplearning4j_tpu.ui import (InMemoryStatsStorage, StatsListener,
                                       UIServer)
    storage = InMemoryStatsStorage()
    net = _net()
    net.setListeners(StatsListener(storage, session_id="web"))
    net.fit(_data(), epochs=3)
    server = UIServer(port=0).start()
    try:
        server.attach(storage)
        html = urllib.request.urlopen(
            server.get_address() + "/?sid=web", timeout=5).read().decode()
        assert "Training UI" in html and "<svg" in html and "0_W" in html
        sessions = json.loads(urllib.request.urlopen(
            server.get_address() + "/train/sessions", timeout=5).read())
        assert sessions == ["web"]
        ups = json.loads(urllib.request.urlopen(
            server.get_address() + "/train/updates?sid=web", timeout=5).read())
        assert len(ups) == 3
    finally:
        server.stop()


def test_op_profiler_timing_and_panic():
    from deeplearning4j_tpu.ops.registry import exec_op as raw_exec
    from deeplearning4j_tpu.profiler import OpProfiler, ProfilerConfig
    import jax.numpy as jnp
    prof = OpProfiler.get_instance()
    prof.reset()
    prof.set_config(ProfilerConfig(op_timing=True))
    try:
        from deeplearning4j_tpu.ops import registry
        registry.exec_op("relu", jnp.asarray([-1.0, 2.0]))
        registry.exec_op("relu", jnp.asarray([3.0]))
        assert prof.stats["relu"].invocations == 2
        assert prof.stats["relu"].total_seconds > 0
        report = prof.print_results()
        assert "relu" in report
        # INF panic
        prof.set_config(ProfilerConfig(check_for_inf=True))
        with pytest.raises(FloatingPointError, match="INF_PANIC"):
            registry.exec_op("log", jnp.asarray([0.0]))
    finally:
        prof.set_config(ProfilerConfig())      # uninstall
    from deeplearning4j_tpu.ops import registry
    assert registry.exec_op is raw_exec


def test_performance_tracker():
    from deeplearning4j_tpu.profiler import PerformanceTracker
    t = PerformanceTracker()
    t.record_iteration(32)
    t.record_iteration(32)
    t.add_transfer_bytes(host_to_device=1024)
    assert t.examples == 64
    assert t.examples_per_second() > 0
    assert "64 examples" in t.summary()


def test_parallel_inference_batched_and_instant():
    from deeplearning4j_tpu.parallel.inference import (InferenceMode,
                                                       ParallelInference)
    net = _net()
    x = np.random.RandomState(0).rand(4, 4).astype("f4")
    direct = np.asarray(net.output(x))

    pi = (ParallelInference.Builder(net)
          .inference_mode(InferenceMode.INSTANT).build())
    assert np.allclose(pi.output(x), direct, atol=1e-6)

    pb = (ParallelInference.Builder(net)
          .inference_mode(InferenceMode.BATCHED).batch_limit(8).build())
    try:
        import threading
        results = {}

        def call(i, xs):
            results[i] = pb.output(xs)

        threads = [threading.Thread(target=call, args=(i, x[i:i + 2]))
                   for i in range(0, 4, 2)]
        for t in threads:
            t.start()
        for t in threads:
            t.join(30.0)
        assert not any(t.is_alive() for t in threads)
        got = np.concatenate([results[0], results[2]])
        assert np.allclose(got, direct, atol=1e-5)
    finally:
        pb.shutdown()


def test_crash_reporting(tmp_path):
    from deeplearning4j_tpu.utils.crash_reporting import CrashReportingUtil
    CrashReportingUtil.crash_dump_output_directory(str(tmp_path))
    net = _net()
    try:
        raise MemoryError("synthetic OOM")
    except MemoryError as e:
        path = CrashReportingUtil.write_memory_crash_dump(net, e)
    assert os.path.exists(path)
    content = open(path).read()
    assert "synthetic OOM" in content
    assert "numParams" in content


def test_parallel_inference_overflow_under_load_no_deadlock():
    """ADVICE r1: oversized requests must be held locally, never re-queued
    onto the bounded queue (deadlock); many concurrent clients with a tiny
    queue_limit exercise exactly that path."""
    import threading

    from deeplearning4j_tpu.parallel.inference import (InferenceMode,
                                                       ParallelInference)
    net = _net()
    x = np.random.RandomState(1).rand(32, 4).astype("f4")
    direct = np.asarray(net.output(x))

    pi = (ParallelInference.Builder(net)
          .inference_mode(InferenceMode.BATCHED)
          .batch_limit(4).queue_limit(2).build())
    results = {}
    errors = []

    def call(i, n):
        try:
            results[i] = pi.output(x[i:i + n])
        except Exception as e:  # pragma: no cover
            errors.append(e)

    # mix of sizes incl. 3-row requests that overflow a partly-filled batch
    sizes = [1, 3, 2, 3, 1, 3, 2, 1, 3, 2, 3, 1, 3, 2, 1, 1]
    offs, threads = 0, []
    for n in sizes:
        threads.append(threading.Thread(target=call, args=(offs, n)))
        offs += n
    try:
        for t in threads:
            t.start()
        for t in threads:
            t.join(timeout=30)
        assert not any(t.is_alive() for t in threads), "deadlocked"
        assert not errors, errors
        offs = 0
        for n in sizes:
            assert np.allclose(results[offs], direct[offs:offs + n],
                               atol=1e-5), offs
            offs += n
    finally:
        pi.shutdown()


def test_parallel_inference_shutdown_fails_pending_cleanly():
    from deeplearning4j_tpu.parallel.inference import (InferenceMode,
                                                       ParallelInference)
    net = _net()
    pi = (ParallelInference.Builder(net)
          .inference_mode(InferenceMode.BATCHED).build())
    pi.shutdown()
    import pytest as _pytest
    with _pytest.raises(RuntimeError):
        pi.output(np.zeros((1, 4), "f4"))


def test_stats_listener_activation_histograms():
    """Activation histograms (ref: StatsListener activation telemetry —
    VERDICT r1 weak #10): opt-in collection re-runs the forward pass on the
    last batch and records per-layer summaries, and the UI renders the
    histogram SVGs."""
    from deeplearning4j_tpu.ui import (InMemoryStatsStorage, StatsListener,
                                       UIServer)
    storage = InMemoryStatsStorage()
    net = _net()
    net.setListeners(StatsListener(storage, session_id="act",
                                   collect_activations=True))
    net.fit(_data(), epochs=2)
    ups = storage.get_all_updates("act")
    acts = ups[-1]["activations"]
    assert "input" in acts
    assert any(k.endswith("DenseLayer") for k in acts)
    layer_stats = next(v for k, v in acts.items() if k.endswith("DenseLayer"))
    assert "histogramCounts" in layer_stats and "stdev" in layer_stats

    server = UIServer(port=0).start()
    try:
        server.attach(storage)
        html = urllib.request.urlopen(
            server.get_address() + "/?sid=act", timeout=5).read().decode()
        assert "Layer activations" in html
        assert html.count("<svg") > 3     # score chart + histograms
    finally:
        server.stop()


def test_stats_listener_model_info_and_graph_svg():
    """Model-graph view (reference UI's architecture tab): the first stats
    record carries modelInfo and the server renders a layer-chain SVG."""
    from deeplearning4j_tpu.ui import (InMemoryStatsStorage, StatsListener,
                                       UIServer)
    storage = InMemoryStatsStorage()
    net = _net()
    net.setListeners(StatsListener(storage, session_id="mg"))
    net.fit(_data(), epochs=2)
    ups = storage.get_all_updates("mg")
    assert "modelInfo" in ups[0] and "modelInfo" not in ups[1]
    layers = ups[0]["modelInfo"]["layers"]
    assert layers[0]["type"] == "DenseLayer" and layers[0]["nParams"] > 0

    server = UIServer(port=0).start()
    try:
        server.attach(storage)
        html = urllib.request.urlopen(
            server.get_address() + "/?sid=mg", timeout=5).read().decode()
        assert "Model graph" in html and "DenseLayer" in html
    finally:
        server.stop()


def test_sanitize_checked_catches_nan_and_user_checks():
    """checkify sanitizer (SURVEY 5.2): float errors and data-dependent
    asserts inside jitted code surface as Python exceptions."""
    import jax
    import jax.numpy as jnp

    from deeplearning4j_tpu.utils import sanitize

    @jax.jit
    def bad(x):
        return jnp.log(x)          # NaN for negative input

    wrapped = sanitize.checked(bad)
    wrapped(jnp.asarray([1.0, 2.0]))      # fine
    import pytest
    with pytest.raises(Exception, match="nan"):
        wrapped(jnp.asarray([-1.0]))

    def guarded(x):
        sanitize.check(jnp.all(x > 0), "input must be positive")
        return jnp.sqrt(x)

    g = sanitize.checked(jax.jit(guarded), nan=False)
    g(jnp.asarray([4.0]))
    with pytest.raises(Exception, match="positive"):
        g(jnp.asarray([-4.0]))


def test_remote_ui_stats_router_round_trip():
    """Detached-UI flow (ref: RemoteUIStatsStorageRouter → remote Vert.x
    endpoint): a training process posts stats over HTTP; the standalone UI
    server receives, stores, and renders them."""
    from deeplearning4j_tpu.ui import RemoteUIStatsStorageRouter, UIServer

    server = UIServer(port=0).start()
    try:
        router = RemoteUIStatsStorageRouter(server.get_address())
        net = _net()
        net.setListeners(__import__(
            "deeplearning4j_tpu.ui", fromlist=["StatsListener"]
        ).StatsListener(router, session_id="remote-sess"))
        net.fit(_data(), epochs=2)
        assert router.failures == 0
        sessions = json.loads(urllib.request.urlopen(
            server.get_address() + "/train/sessions", timeout=5).read())
        assert "remote-sess" in sessions
        ups = json.loads(urllib.request.urlopen(
            server.get_address() + "/train/updates?sid=remote-sess",
            timeout=5).read())
        assert len(ups) == 2 and all("score" in u for u in ups)
        html = urllib.request.urlopen(
            server.get_address() + "/?sid=remote-sess",
            timeout=5).read().decode()
        assert "remote-sess" in html
    finally:
        server.stop()


def test_parallel_transform_executor_matches_local():
    """Partitioned ETL (ref: SparkTransformExecutor — SURVEY E3): forked
    partitions produce exactly the local executor's output."""
    from deeplearning4j_tpu.datavec import (IntWritable, LocalTransformExecutor,
                                            Schema, Text, TransformProcess)
    from deeplearning4j_tpu.datavec.distributed import ParallelTransformExecutor
    from deeplearning4j_tpu.datavec.schema import ColumnMetaData, ColumnType

    schema = Schema([ColumnMetaData("a", ColumnType.Integer),
                     ColumnMetaData("tag", ColumnType.String)])
    tp = (TransformProcess.Builder(schema)
          .remove_columns("tag")
          .build())
    rows = [[IntWritable(i), Text(f"t{i}")] for i in range(37)]
    local = LocalTransformExecutor.execute(rows, tp)
    dist = ParallelTransformExecutor.execute(rows, tp, num_partitions=4)
    assert dist == local and len(dist) == 37


def test_device_profiler_produces_trace(tmp_path):
    """jax-profiler bridge (SURVEY 5.1 'jax profiler → XProf'): tracing a
    jitted step under ``DeviceProfiler.start/stop`` writes an XPlane trace
    TensorBoard can open, and a ``span()`` around the step is the host-side
    label on it: the one way to put the program's names on the profile."""
    import glob

    import jax
    import jax.numpy as jnp
    from jax.profiler import ProfileData

    from deeplearning4j_tpu.observability import span
    from deeplearning4j_tpu.profiler import DeviceProfiler

    d = str(tmp_path)
    step = jax.jit(lambda x: jnp.tanh(x @ x.T).sum())
    x = jnp.ones((64, 64))
    jax.block_until_ready(step(x))          # compile outside the trace
    prof = DeviceProfiler(d).start()
    try:
        for _ in range(2):
            with span("profiled_section"):
                out = jax.block_until_ready(step(x))
    finally:
        assert prof.stop() == d             # never leave the profiler on
    assert float(out) != 0
    traces = glob.glob(os.path.join(d, "**", "*.xplane.pb"), recursive=True)
    assert traces, f"no xplane trace written under {d}"
    labels = [ev.name for plane in ProfileData.from_file(traces[0]).planes
              if plane.name.startswith("/host:")
              for line in plane.lines for ev in line.events
              if ev.name == "profiled_section"]
    assert len(labels) == 2

    # with no profile running a span is no annotation, and still a span
    with span("profiled_section"):
        jax.block_until_ready(step(x))


def test_ui_system_tab_and_ratio_chart():
    """Round-4 D16 depth: the System tab serves the host/device snapshot
    StatsListener records at session start, and the overview carries the
    reference's log10 update:parameter ratio chart + auto-refresh."""
    import urllib.request

    from deeplearning4j_tpu.ui import (InMemoryStatsStorage, StatsListener,
                                       UIServer)
    storage = InMemoryStatsStorage()
    net = _net()
    net.setListeners(StatsListener(storage, session_id="sys"))
    net.fit([_data()] * 3, epochs=2)

    ups = storage.get_all_updates("sys")
    info = next(u["systemInfo"] for u in ups if "systemInfo" in u)
    assert info["deviceCount"] >= 1 and "jax" in info

    server = UIServer(port=0).start()
    try:
        server.attach(storage)
        html = urllib.request.urlopen(
            server.get_address() + "/?sid=sys", timeout=5).read().decode()
        assert "update : parameter ratio" in html
        assert 'http-equiv="refresh"' in html
        sys_html = urllib.request.urlopen(
            server.get_address() + "/train/system",
            timeout=5).read().decode()
        assert "System" in sys_html and "deviceCount" in sys_html
    finally:
        server.stop()


def test_ui_incremental_updates_endpoint():
    """/train/updates?since=N returns only newer records (VERDICT r4 #8:
    incremental JSON so clients need not re-pull whole sessions)."""
    from deeplearning4j_tpu.ui import InMemoryStatsStorage, UIServer

    storage = InMemoryStatsStorage()
    for i in range(5):
        storage.put_update("incr", {"iteration": i, "score": 1.0 / (i + 1)})
    server = UIServer(port=0)
    server.attach(storage)
    server.start()
    try:
        base = server.get_address()
        full = json.loads(urllib.request.urlopen(
            base + "/train/updates?sid=incr", timeout=5).read())
        assert len(full) == 5
        newer = json.loads(urllib.request.urlopen(
            base + "/train/updates?sid=incr&since=2", timeout=5).read())
        assert [u["iteration"] for u in newer] == [3, 4]
    finally:
        server.stop()


def test_ui_sse_stream_pushes_live_records():
    """/train/stream replays the session, then pushes NEW records as the
    storage receives them — the live-telemetry behavior the reference's
    Vert.x UI is built around (VERDICT r4 #8)."""
    import socket

    from deeplearning4j_tpu.ui import InMemoryStatsStorage, UIServer

    storage = InMemoryStatsStorage()
    storage.put_update("live", {"iteration": 0, "score": 3.0})
    server = UIServer(port=0)
    server.attach(storage)
    server.start()
    try:
        s = socket.create_connection(("127.0.0.1", server.port), timeout=10)
        s.sendall(b"GET /train/stream?sid=live HTTP/1.1\r\n"
                  b"Host: localhost\r\nAccept: text/event-stream\r\n\r\n")
        f = s.makefile("rb")
        status = f.readline()
        assert b"200" in status
        while f.readline().strip():       # drain headers
            pass

        def next_event():
            while True:
                line = f.readline()
                if line.startswith(b"data: "):
                    return json.loads(line[6:])

        first = next_event()              # replay of the existing record
        assert first["iteration"] == 0
        # a record arriving AFTER the client connected is pushed live
        storage.put_update("live", {"iteration": 1, "score": 2.5})
        second = next_event()
        assert second["iteration"] == 1 and second["score"] == 2.5
        # records for other sessions are filtered out of this stream
        storage.put_update("other", {"iteration": 7, "score": 9.9})
        storage.put_update("live", {"iteration": 2, "score": 2.0})
        third = next_event()
        assert third["iteration"] == 2
        s.close()
    finally:
        server.stop()


def test_ui_two_session_compare_render():
    """/train/compare renders >=2 sessions from ONE storage side by side
    with an overlaid score chart (VERDICT r4 #8)."""
    from deeplearning4j_tpu.ui import InMemoryStatsStorage, UIServer

    storage = InMemoryStatsStorage()
    for i in range(4):
        storage.put_update("run-a", {"iteration": i, "score": 2.0 - 0.3 * i})
        storage.put_update("run-b", {"iteration": i, "score": 1.5 - 0.2 * i})
    server = UIServer(port=0)
    server.attach(storage)
    server.start()
    try:
        base = server.get_address()
        page = urllib.request.urlopen(
            base + "/train/compare?sids=run-a,run-b", timeout=5).read() \
            .decode()
        assert "run-a" in page and "run-b" in page
        assert page.count("<polyline") >= 2      # one curve per session
        # per-layer side-by-side columns (one pair per session)
        storage.put_update("run-a", {"iteration": 4, "score": 0.9,
            "parameters": {"0_W": {"meanMagnitude": 0.1}},
            "updates": {"0_W": {"meanMagnitude": 0.001}}})
        storage.put_update("run-b", {"iteration": 4, "score": 0.8,
            "parameters": {"0_W": {"meanMagnitude": 0.2}},
            "updates": {"0_W": {"meanMagnitude": 0.004}}})
        page2 = urllib.request.urlopen(
            base + "/train/compare?sids=run-a,run-b", timeout=5).read() \
            .decode()
        assert "Per-layer" in page2 and "0_W" in page2
        assert "1.000e-02" in page2 and "2.000e-02" in page2  # the ratios
        # overview links to the comparison when several sessions exist
        over = urllib.request.urlopen(base + "/", timeout=5).read().decode()
        assert "/train/compare?sids=" in over
        # and carries the live-stream EventSource hook (no-reload charts)
        assert "EventSource" in over and "/train/stream" in over
    finally:
        server.stop()


# ---------------------------------------------------------------------------
# Unified observability core: metrics registry + structured tracing
# ---------------------------------------------------------------------------

def test_metrics_registry_counter_gauge_histogram_labels():
    from deeplearning4j_tpu.observability import MetricsRegistry

    reg = MetricsRegistry(enabled=True)
    c = reg.counter("obs_req_total", "requests", label_names=("route", "code"))
    c.labels(route="/a", code="200").inc()
    c.labels("/a", "200").inc(2.5)          # positional labels, same child
    c.labels(route="/b", code="500").inc()
    assert c.labels(route="/a", code="200").value == 3.5
    assert c.labels(route="/b", code="500").value == 1.0
    with pytest.raises(ValueError):
        c.labels(route="/a", code="200").inc(-1)      # counters only go up
    with pytest.raises(ValueError):
        c.labels("/only-one")                          # label arity enforced

    g = reg.gauge("obs_depth", "depth")
    g.set(7); g.inc(); g.dec(3)
    assert g.value == 5.0

    h = reg.histogram("obs_lat_seconds", "latency", label_names=("mode",),
                      buckets=(0.01, 0.1, 1.0))
    child = h.labels(mode="fast")
    for v in (0.005, 0.05, 0.5, 5.0):
        child.observe(v)
    assert child.count == 4 and abs(child.sum - 5.555) < 1e-9
    assert child.bucket_counts() == [1, 1, 1, 1]      # last = +Inf overflow
    # quantiles come from the reservoir (exact over the window)
    assert child.quantile(0.0) == 0.005 and child.quantile(1.0) == 5.0
    p = child.percentiles((0.5, 0.95, 0.99))
    assert p[0.5] <= p[0.95] <= p[0.99]

    # get-or-create: same name -> same instrument; kind clash is an error
    assert reg.counter("obs_req_total") is c
    with pytest.raises(ValueError):
        reg.gauge("obs_req_total")


def test_metrics_registry_thread_safety():
    import threading

    from deeplearning4j_tpu.observability import MetricsRegistry

    reg = MetricsRegistry(enabled=True)
    c = reg.counter("obs_conc_total", "c", label_names=("t",))
    h = reg.histogram("obs_conc_seconds", "h")

    def work(i):
        for _ in range(1000):
            c.labels(t=str(i % 4)).inc()
            h.observe(0.001)

    threads = [threading.Thread(target=work, args=(i,)) for i in range(8)]
    for t in threads:
        t.start()
    for t in threads:
        t.join(30.0)
    assert not any(t.is_alive() for t in threads)
    total = sum(c.labels(t=str(i)).value for i in range(4))
    assert total == 8000 and h.count == 8000


def test_prometheus_exposition_format():
    from deeplearning4j_tpu.observability import MetricsRegistry

    reg = MetricsRegistry(enabled=True)
    reg.counter("obs_a_total", "a counter", ("op",)).labels(op="x").inc(3)
    reg.gauge("obs_g", "a gauge").set(1.5)
    reg.histogram("obs_h_seconds", "a histogram",
                  buckets=(0.1, 1.0)).observe(0.5)
    text = reg.render_prometheus()
    lines = text.strip().splitlines()
    # HELP/TYPE headers precede every family, families sorted by name
    assert "# HELP obs_a_total a counter" in lines
    assert "# TYPE obs_a_total counter" in lines
    assert 'obs_a_total{op="x"} 3' in lines
    assert "# TYPE obs_g gauge" in lines and "obs_g 1.5" in lines
    assert "# TYPE obs_h_seconds histogram" in lines
    assert 'obs_h_seconds_bucket{le="0.1"} 0' in lines
    assert 'obs_h_seconds_bucket{le="1"} 1' in lines
    assert 'obs_h_seconds_bucket{le="+Inf"} 1' in lines
    assert "obs_h_seconds_sum 0.5" in lines
    assert "obs_h_seconds_count 1" in lines
    # label values escape quotes/backslashes/newlines per the format spec
    reg.counter("obs_esc_total", "esc", ("p",)).labels(p='a"b\\c\nd').inc()
    assert r'obs_esc_total{p="a\"b\\c\nd"} 1' in reg.render_prometheus()


def test_span_nesting_and_chrome_trace_json():
    from deeplearning4j_tpu.observability import TraceSink, span

    sink = TraceSink(capacity=16)
    with span("outer", sink=sink, phase="fit"):
        with span("inner", sink=sink):
            pass
        with span("inner2", sink=sink):
            pass
    events = sink.to_chrome_trace()
    # children close before the parent -> parent is last; array-of-events
    # chrome format: every entry has ph/ts/dur
    names = [e["name"] for e in events]
    assert names == ["inner", "inner2", "outer"]
    for e in events:
        assert e["ph"] == "X" and "ts" in e and "dur" in e and "pid" in e
    outer = events[-1]
    assert outer["args"]["phase"] == "fit"
    # parent duration covers both children; timestamps nest
    inner = events[0]
    assert outer["ts"] <= inner["ts"]
    assert outer["ts"] + outer["dur"] >= inner["ts"] + inner["dur"]
    # depths reflect nesting
    spans = sink.spans()
    assert spans[-1].depth == 0 and spans[0].depth == 1
    # the export is valid JSON loadable as a list
    parsed = json.loads(sink.export_json())
    assert isinstance(parsed, list) and len(parsed) == 3


def test_trace_sink_ring_buffer_bounds_memory():
    from deeplearning4j_tpu.observability import TraceSink, span

    sink = TraceSink(capacity=8)
    for i in range(20):
        with span(f"s{i}", sink=sink):
            pass
    assert len(sink) == 8 and sink.total_recorded == 20
    assert sink.dropped == 12
    # oldest dropped first: only the last 8 remain, in order
    assert [r.name for r in sink.spans()] == [f"s{i}" for i in range(12, 20)]


def test_training_fit_populates_step_metrics_and_spans():
    from deeplearning4j_tpu.data.iterators import ListDataSetIterator
    from deeplearning4j_tpu.observability import (metrics,
                                                  reset_global_registry,
                                                  reset_global_trace_sink)

    reset_global_registry()
    sink = reset_global_trace_sink()
    net = _net()
    net.fit(ListDataSetIterator([_data()] * 3), epochs=2)
    reg = metrics()
    step = reg.get("dl4j_training_step_seconds").labels(
        model="MultiLayerNetwork")
    assert step.count == 6
    phases = reg.get("dl4j_training_phase_seconds")
    for phase in ("data_wait", "device_compute", "host_callback"):
        assert phases.labels(model="MultiLayerNetwork",
                             phase=phase).count >= 6, phase
    assert reg.get("dl4j_training_examples_total").labels(
        model="MultiLayerNetwork").value == 6 * 32
    assert reg.get("dl4j_training_epochs_total").labels(
        model="MultiLayerNetwork").value == 2
    # device compute dominates a CPU step; all phases sum close to total
    text = reg.render_prometheus()
    assert "dl4j_training_step_seconds_bucket" in text
    assert 'model="MultiLayerNetwork"' in text
    # spans: train_step spans nested under nothing, data_wait spans present
    names = {r.name for r in sink.spans()}
    assert {"train_step", "data_wait", "listeners"} <= names


def test_straggler_detector_counts_slow_steps():
    from deeplearning4j_tpu.observability import (StragglerDetector,
                                                  reset_global_registry)

    reset_global_registry()
    det = StragglerDetector(phase="unit", threshold=3.0, window=16, warmup=2)
    for _ in range(10):
        assert not det.observe(0.010)
    assert det.observe(0.050)            # 5x median -> flagged
    assert not det.observe(0.012)
    assert det.slow_count == 1
    from deeplearning4j_tpu.observability import metrics
    text = metrics().render_prometheus()
    assert 'dl4j_slow_steps_total{phase="unit"} 1' in text


def test_parallel_inference_latency_histogram_population():
    from deeplearning4j_tpu.observability import (metrics,
                                                  reset_global_registry)
    from deeplearning4j_tpu.parallel.inference import (InferenceMode,
                                                       ParallelInference)

    reset_global_registry()
    net = _net()
    x = np.random.RandomState(0).rand(4, 4).astype("f4")

    pi = (ParallelInference.Builder(net)
          .inference_mode(InferenceMode.INSTANT).build())
    pi.output(x)
    pb = (ParallelInference.Builder(net)
          .inference_mode(InferenceMode.BATCHED).batch_limit(8).build())
    try:
        for i in range(3):
            pb.output(x[i:i + 1])
    finally:
        pb.shutdown()
        pi.shutdown()
    reg = metrics()
    lat = reg.get("dl4j_inference_latency_seconds")
    assert lat.labels(mode="INSTANT").count == 1
    batched = lat.labels(mode="BATCHED")
    assert batched.count == 3
    assert batched.quantile(0.5) <= batched.quantile(0.99)
    assert reg.get("dl4j_inference_requests_total").labels(
        mode="BATCHED").value == 3
    occ = reg.get("dl4j_inference_batch_occupancy")
    assert occ.count >= 1                  # at least one device call
    assert reg.get("dl4j_inference_batches_total").value >= 1
    # the full serving picture renders for a scrape
    text = reg.render_prometheus()
    assert "dl4j_inference_latency_seconds_bucket" in text
    assert "dl4j_inference_queue_depth" in text


def test_metrics_endpoint_serves_live_series():
    """Acceptance: GET /metrics returns valid Prometheus text including
    training-step, inference-latency, and collective-bytes series from a
    live run."""
    from deeplearning4j_tpu.observability import (metrics,
                                                  reset_global_registry)
    from deeplearning4j_tpu.parallel.inference import (InferenceMode,
                                                       ParallelInference)
    from deeplearning4j_tpu.parallel.mesh import MeshSpec
    from deeplearning4j_tpu.parallel.trainer import ShardedTrainer
    from deeplearning4j_tpu.ui import UIServer

    reset_global_registry()
    net = _net()
    net.fit(_data(), epochs=2)                           # training series
    pi = (ParallelInference.Builder(net)
          .inference_mode(InferenceMode.INSTANT).build())
    pi.output(np.zeros((2, 4), "f4"))                    # inference series
    pi.shutdown()
    trainer = ShardedTrainer(net, MeshSpec.data_parallel(8))
    trainer.fit(_data())                                 # collective series

    server = UIServer(port=0).start()
    try:
        body = urllib.request.urlopen(
            server.get_address() + "/metrics", timeout=5)
        text = body.read().decode()
        assert body.headers["Content-Type"].startswith("text/plain")
        assert "dl4j_training_step_seconds_count" in text
        assert "dl4j_inference_latency_seconds_count" in text
        assert 'dl4j_collective_bytes_total{collective="allreduce"}' in text
        # every non-comment line is "name{labels} value"
        for line in text.strip().splitlines():
            if line.startswith("#"):
                continue
            name_part, _, value = line.rpartition(" ")
            assert name_part and float(value) is not None

        health = json.loads(urllib.request.urlopen(
            server.get_address() + "/health", timeout=5).read())
        assert health["status"] == "ok"
        assert health["uptime_seconds"] >= 0
        assert isinstance(health["metrics_enabled"], bool)

        trace = json.loads(urllib.request.urlopen(
            server.get_address() + "/train/trace", timeout=5).read())
        assert isinstance(trace, list) and trace
        # complete events carry ts/dur; cross-thread handoffs may add
        # flow-event pairs (ph s/f) — the Perfetto request arrows
        assert all(e["ph"] in ("X", "s", "f") and "ts" in e for e in trace)
        assert any(e["ph"] == "X" and "dur" in e for e in trace)
    finally:
        server.stop()


def test_metrics_kill_switch(monkeypatch):
    """DL4J_TPU_METRICS=0: instruments and spans become no-ops."""
    monkeypatch.setenv("DL4J_TPU_METRICS", "0")
    from deeplearning4j_tpu.observability import (metrics,
                                                  reset_global_registry,
                                                  reset_global_trace_sink,
                                                  span)

    reset_global_registry()
    sink = reset_global_trace_sink()
    net = _net()
    net.fit(_data())
    reg = metrics()
    step = reg.get("dl4j_training_step_seconds")
    assert step is None or step.labels(
        model="MultiLayerNetwork").count == 0
    with span("dead"):
        pass
    assert sink.total_recorded == 0
    monkeypatch.delenv("DL4J_TPU_METRICS")
    reset_global_registry()


def test_metrics_reporting_listener_bridges_bus():
    from deeplearning4j_tpu.observability import (MetricsReportingListener,
                                                  metrics,
                                                  reset_global_registry)

    from deeplearning4j_tpu.data.iterators import ListDataSetIterator

    reset_global_registry()
    net = _net()
    net.setListeners(MetricsReportingListener())
    net.fit(ListDataSetIterator([_data()] * 2), epochs=2)
    reg = metrics()
    assert reg.get("dl4j_listener_iterations_total").labels(
        model="MultiLayerNetwork").value == 4
    assert reg.get("dl4j_listener_epochs_total").labels(
        model="MultiLayerNetwork").value == 2
    score = reg.get("dl4j_listener_score").labels(
        model="MultiLayerNetwork").value
    assert score == score and score > 0


def test_checkpoint_listener_publishes_save_metrics(tmp_path):
    from deeplearning4j_tpu.observability import (metrics,
                                                  reset_global_registry)
    from deeplearning4j_tpu.optim.listeners import CheckpointListener

    reset_global_registry()
    net = _net()
    net.setListeners(CheckpointListener(str(tmp_path),
                                        save_every_n_iterations=2))
    net.fit([_data()] * 4, epochs=1)
    reg = metrics()
    assert reg.get("dl4j_checkpoints_total").value == 2
    assert reg.get("dl4j_checkpoint_save_seconds").count == 2
    assert reg.get("dl4j_checkpoint_bytes_total").value > 0


def test_op_profiler_publishes_into_registry():
    """Refactor check: OpProfiler timings land in the registry series and
    the legacy stats view re-bases on reset."""
    import jax.numpy as jnp

    from deeplearning4j_tpu.observability import (metrics,
                                                  reset_global_registry)
    from deeplearning4j_tpu.ops import registry as ops_registry
    from deeplearning4j_tpu.profiler import OpProfiler, ProfilerConfig

    reset_global_registry()
    prof = OpProfiler.get_instance()
    prof.set_config(ProfilerConfig(op_timing=True))
    try:
        ops_registry.exec_op("relu", jnp.asarray([-1.0, 2.0]))
        ops_registry.exec_op("relu", jnp.asarray([1.0]))
    finally:
        prof.set_config(ProfilerConfig())
    hist = metrics().get("dl4j_eager_op_seconds")
    assert hist.labels(op="relu").count == 2
    assert prof.stats["relu"].invocations == 2
    prof.reset()
    assert prof.stats["relu"].invocations == 0          # view re-based
    assert hist.labels(op="relu").count == 2            # series cumulative


def test_performance_tracker_publishes_into_registry():
    from deeplearning4j_tpu.observability import (metrics,
                                                  reset_global_registry)
    from deeplearning4j_tpu.profiler import PerformanceTracker

    reset_global_registry()
    t = PerformanceTracker()
    t.record_iteration(16)
    t.add_transfer_bytes(host_to_device=2048, device_to_host=512)
    reg = metrics()
    assert reg.get("dl4j_perf_examples_total").value == 16
    tb = reg.get("dl4j_transfer_bytes_total")
    assert tb.labels(direction="h2d").value == 2048
    assert tb.labels(direction="d2h").value == 512
    assert t.examples == 16
    t.reset()                                # view window re-bases
    assert t.examples == 0
    assert reg.get("dl4j_perf_examples_total").value == 16


def test_data_iterator_metrics():
    from deeplearning4j_tpu.data.iterators import (AsyncDataSetIterator,
                                                   ListDataSetIterator)
    from deeplearning4j_tpu.observability import (metrics,
                                                  reset_global_registry)

    reset_global_registry()
    base = ListDataSetIterator([_data()] * 3)
    it = AsyncDataSetIterator(base, queue_size=2)
    n = sum(1 for _ in it)
    assert n == 3
    reg = metrics()
    assert reg.get("dl4j_data_batches_total").labels(
        iterator="AsyncDataSetIterator").value == 3
    assert reg.get("dl4j_data_wait_seconds").labels(
        iterator="AsyncDataSetIterator").count >= 3


def test_tolerant_checkpoint_loading_orphaned_conv_bias(tmp_path, caplog):
    """Checkpoints saved before has_bias=False carry orphaned conv ``b``
    entries — restore must warn and skip them, never shape-mismatch."""
    import logging as _logging
    import zipfile

    net = _net()
    net.fit(_data())
    path = os.path.join(str(tmp_path), "old.zip")
    net.save(path)

    # rewrite the artifact with an injected orphan parameter (the old
    # architecture's conv bias) and one missing parameter
    path2 = os.path.join(str(tmp_path), "tampered.zip")
    import io as _io

    import numpy as _np
    with zipfile.ZipFile(path) as zin:
        names = zin.namelist()
        coeffs = dict(_np.load(_io.BytesIO(zin.read("coefficients.npz"))))
        coeffs["0/b_orphan"] = _np.zeros(8, "f4")     # orphan entry
        missing = coeffs.pop("1/b")                   # dropped entry
        buf = _io.BytesIO()
        _np.savez(buf, **coeffs)
        with zipfile.ZipFile(path2, "w") as zout:
            for n in names:
                if n == "coefficients.npz":
                    zout.writestr(n, buf.getvalue())
                elif n == "updaterState.npz":
                    continue            # stale updater tolerated separately
                else:
                    zout.writestr(n, zin.read(n))

    from deeplearning4j_tpu.nn.multilayer import MultiLayerNetwork
    with caplog.at_level(_logging.WARNING, logger="deeplearning4j_tpu"):
        restored = MultiLayerNetwork.load(path2)
    msgs = " ".join(r.message for r in caplog.records)
    assert "orphaned" in msgs and "0/b_orphan" in msgs
    assert "keeping fresh initialization" in msgs
    # restored net is fully usable: same weights where present
    assert np.allclose(np.asarray(restored._params["0"]["W"]),
                       np.asarray(net._params["0"]["W"]))
    assert restored._params["1"]["b"].shape == missing.shape
    restored.output(np.zeros((2, 4), "f4"))


def test_graph_opt_flag_in_emission_cache_key(monkeypatch):
    """ADVICE r5: toggling DL4J_TPU_GRAPH_OPT mid-session must re-emit
    rather than silently reuse programs built under the other setting."""
    from deeplearning4j_tpu.autodiff.samediff import SameDiff

    sd = SameDiff.create()
    x = sd.placeholder("x", (2, 3))
    w = sd.var("w", init=np.ones((3, 3), np.float32))
    (x @ w).rename("y")
    xin = np.random.RandomState(0).rand(2, 3).astype("f4")

    monkeypatch.setenv("DL4J_TPU_GRAPH_OPT", "1")
    out1 = sd.output({"x": xin}, ["y"])["y"]
    n1 = len(sd._compiled_cache)
    monkeypatch.setenv("DL4J_TPU_GRAPH_OPT", "0")
    out2 = sd.output({"x": xin}, ["y"])["y"]
    assert len(sd._compiled_cache) == n1 + 1     # new entry, not stale hit
    assert np.allclose(np.asarray(out1), np.asarray(out2))
    monkeypatch.setenv("DL4J_TPU_GRAPH_OPT", "1")
    sd.output({"x": xin}, ["y"])
    assert len(sd._compiled_cache) == n1 + 1     # flag=1 entry reused
