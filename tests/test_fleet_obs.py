"""Fleet observability plane suite (ARCHITECTURE.md §23): cross-process
trace propagation (inbound ``X-Dl4j-Trace-Id`` joins the worker's root
span; the id echoes on EVERY response path — the status table), metrics
federation (worker-label injection, top-N fold, dead-worker partial
scrape that never 500s), the fleet health rollup (worst-worker
attribution, leader-published verdict), coordinated incident capture
(one incident id, every live worker's bundle), the proxy's own metrics
+ admin surface, and the kill switch (``DL4J_TPU_FLEET_OBS=0`` =
byte-identical pre-plane behavior). The live 2-worker subprocess drill is ``slow``.
"""
import importlib.util
import json
import os
import subprocess
import sys
import time
import urllib.error
import urllib.request

import numpy as np
import pytest

from deeplearning4j_tpu.nn.conf.configuration import NeuralNetConfiguration
from deeplearning4j_tpu.nn.conf.layers import DenseLayer, OutputLayer
from deeplearning4j_tpu.nn.multilayer import MultiLayerNetwork
from deeplearning4j_tpu.observability import (global_registry,
                                              global_trace_sink,
                                              reset_global_registry,
                                              reset_global_trace_sink)
from deeplearning4j_tpu.observability import federation as fed
from deeplearning4j_tpu.observability.flight_recorder import FlightRecorder
from deeplearning4j_tpu.optim.updaters import Adam
from deeplearning4j_tpu.resilience import faults
from deeplearning4j_tpu.serving import (FrontDoor, ModelRegistry,
                                        ServingRouter, SharedServingState,
                                        SharedStore)
from deeplearning4j_tpu.serving import idempotency as idem

import jax  # noqa: F401  (forces the CPU platform before nets build)

_REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))

TID = "aaaabbbbccccdddd"
PARENT = "1234567890abcdef"


def _load_tool(name):
    spec = importlib.util.spec_from_file_location(
        name, os.path.join(_REPO, "tools", f"{name}.py"))
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


_NET = None


def _net():
    global _NET
    if _NET is None:
        conf = (NeuralNetConfiguration.builder()
                .seed(1).updater(Adam(1e-2)).list()
                .layer(DenseLayer(n_in=4, n_out=8, activation="relu"))
                .layer(OutputLayer(n_in=8, n_out=3, activation="softmax",
                                   loss_function="mcxent"))
                .build())
        _NET = MultiLayerNetwork(conf).init()
    return _NET


_SAMPLE = np.zeros((1, 4), dtype="f4")


@pytest.fixture(autouse=True)
def _clean():
    faults.reset()
    reset_global_registry()
    reset_global_trace_sink()
    idem.reset_global_journal()
    yield
    faults.clear()
    from deeplearning4j_tpu.observability import flight_recorder as _fr
    _fr.set_incident_publisher(None)


def _scoring_door(**kw):
    reg = ModelRegistry()
    reg.deploy("v1", _net(), sample_input=_SAMPLE, batch_limit=4,
               max_wait_ms=1.0)
    return FrontDoor(ServingRouter(reg, "v1"), **kw).start(), reg


def _request(addr, path, body=None, headers=(), timeout=30.0):
    """(status, payload-bytes, response-headers) for any method/status."""
    hdrs = dict(headers)
    data = None
    if body is not None:
        hdrs.setdefault("Content-Type", "application/json")
        data = json.dumps(body).encode()
    req = urllib.request.Request(addr + path, data=data, headers=hdrs)
    try:
        with urllib.request.urlopen(req, timeout=timeout) as r:
            return r.status, r.read(), dict(r.headers)
    except urllib.error.HTTPError as e:
        return e.code, e.read(), dict(e.headers)


def _spans(name=None):
    recs = global_trace_sink().spans()
    return [r for r in recs if name is None or r.name == name]


def _wait_span(name, pred, timeout=3.0):
    """Span records land on ``__exit__`` AFTER the response bytes are
    written — poll instead of racing the handler thread."""
    deadline = time.monotonic() + timeout
    while time.monotonic() < deadline:
        hits = [r for r in _spans(name) if pred(r)]
        if hits:
            return hits
        time.sleep(0.05)
    return []


# ---------------------------------------------------------------------------
# trace propagation: inbound join + the response-header status table
# ---------------------------------------------------------------------------

def test_trace_header_on_every_response_path(monkeypatch):
    """The status table: EVERY front-door response path — success, 404,
    kill-switch 503, inflight 429, 400, the debug/metrics/health GETs —
    carries the caller's X-Dl4j-Trace-Id back."""
    fd, reg = _scoring_door(port=0)
    hdr = {fed.TRACE_HEADER: TID}
    try:
        addr = fd.get_address()
        table = [
            ("POST", "/nope", {"x": 1}, 404),
            ("POST", "/v1/classify", {"nope": 1}, 400),
            ("POST", "/v1/classify", {"inputs": [[0.0] * 4]}, 200),
            ("GET", "/metrics", None, 200),
            ("GET", "/health", None, 200),
            ("GET", "/debug/frontdoor", None, 200),
            ("GET", "/nope", None, 404),
        ]
        for method, path, body, want in table:
            code, _, h = _request(addr, path, body, headers=hdr)
            assert code == want, (method, path)
            assert h.get(fed.TRACE_HEADER) == TID, (method, path, code)
        # the disabled-503 path (checked before dispatch) carries it too
        monkeypatch.setenv("DL4J_TPU_FRONTDOOR", "0")
        code, _, h = _request(addr, "/v1/classify",
                              {"inputs": [[0.0] * 4]}, headers=hdr)
        assert code == 503 and h.get(fed.TRACE_HEADER) == TID
        monkeypatch.delenv("DL4J_TPU_FRONTDOOR")
        # idempotent replay responses carry it as well
        _request(addr, "/v1/classify", {"inputs": [[0.0] * 4]},
                 headers={fed.TRACE_HEADER: TID,
                          idem.IDEMPOTENCY_HEADER: "T1"})
        code, _, h = _request(addr, "/v1/classify", {"inputs": [[0.0] * 4]},
                              headers={fed.TRACE_HEADER: TID,
                                       idem.IDEMPOTENCY_HEADER: "T1"})
        assert code == 200 and h.get(idem.REPLAY_HEADER) == "1"
        assert h.get(fed.TRACE_HEADER) == TID
    finally:
        fd.stop()
        reg.shutdown()
    # the inflight-429 shed (separate door so nothing else sheds)
    fd2, reg2 = _scoring_door(port=0, max_inflight=0)
    try:
        code, _, h = _request(fd2.get_address(), "/v1/classify",
                              {"inputs": [[0.0] * 4]}, headers=hdr)
        assert code == 429 and h.get(fed.TRACE_HEADER) == TID
    finally:
        fd2.stop()
        reg2.shutdown()


def test_inbound_context_joins_root_span():
    """A caller-supplied trace id + parent id becomes the worker's root
    span context: same trace id, parent_id = the caller's span."""
    fd, reg = _scoring_door(port=0)
    try:
        code, _, h = _request(
            fd.get_address(), "/v1/classify", {"inputs": [[0.0] * 4]},
            headers={fed.TRACE_HEADER: TID, fed.PARENT_HEADER: PARENT})
        assert code == 200 and h.get(fed.TRACE_HEADER) == TID
        roots = _wait_span("http_request", lambda r: r.trace_id == TID)
        assert roots and roots[0].parent_id == PARENT
    finally:
        fd.stop()
        reg.shutdown()


def test_garbage_inbound_id_gets_fresh_root_never_an_error():
    fd, reg = _scoring_door(port=0)
    try:
        code, _, h = _request(
            fd.get_address(), "/v1/classify", {"inputs": [[0.0] * 4]},
            headers={fed.TRACE_HEADER: "ZZ-not-hex!"})
        assert code == 200
        got = h.get(fed.TRACE_HEADER)
        assert got and got != "ZZ-not-hex!"
        assert fed.parse_trace_id(got) == got       # a valid fresh root
    finally:
        fd.stop()
        reg.shutdown()


def test_parse_trace_id_and_header_injection():
    assert fed.parse_trace_id(" AAAABBBBCCCCDDDD ") == TID
    assert fed.parse_trace_id("12ab") is None            # too short
    assert fed.parse_trace_id("g" * 16) is None          # not hex
    assert fed.parse_trace_id(None) is None
    raw = (b"POST /v1/classify HTTP/1.1\r\nHost: x\r\n"
           b"X-Dl4j-Trace-Id: spoofed\r\n\r\n{}")
    out = fed.inject_trace_headers(raw, TID, PARENT)
    head, _, body = out.partition(b"\r\n\r\n")
    assert body == b"{}"
    assert head.count(b"X-Dl4j-Trace-Id:") == 1          # spoof stripped
    assert f"X-Dl4j-Trace-Id: {TID}".encode() in head
    assert f"X-Dl4j-Parent-Id: {PARENT}".encode() in head
    # no header/body separator (split read): bytes pass through untouched
    assert fed.inject_trace_headers(b"partial", TID, PARENT) == b"partial"


# ---------------------------------------------------------------------------
# metrics federation
# ---------------------------------------------------------------------------

W0_TEXT = """# HELP dl4j_http_requests_total req
# TYPE dl4j_http_requests_total counter
dl4j_http_requests_total{code="200",route="classify"} 5
dl4j_http_requests_total{code="500",route="classify"} 1
"""

W1_TEXT = """# HELP dl4j_http_requests_total other help
# TYPE dl4j_http_requests_total counter
dl4j_http_requests_total{code="200",route="classify"} 7
# HELP dl4j_fleet_scrape_errors_total e
# TYPE dl4j_fleet_scrape_errors_total counter
dl4j_fleet_scrape_errors_total{worker="w9"} 2
"""


def test_merge_injects_worker_label_help_first_wins():
    text = fed.merge_prometheus([("w0", W0_TEXT), ("w1", W1_TEXT)])
    assert ('dl4j_http_requests_total{code="200",route="classify",'
            'worker="w0"} 5') in text
    assert ('dl4j_http_requests_total{code="200",route="classify",'
            'worker="w1"} 7') in text
    assert "# HELP dl4j_http_requests_total req" in text
    assert "other help" not in text                     # first HELP wins
    # an existing worker label keeps its attribution (never re-labeled)
    assert 'dl4j_fleet_scrape_errors_total{worker="w9"} 2' in text
    parsed = fed.parse_prometheus(text)
    assert parsed                                       # stays parseable


def test_fold_bounds_cardinality_and_collisions_sum(monkeypatch):
    monkeypatch.setenv("DL4J_TPU_FLEET_WORKER_TOP_N", "1")
    fold = fed.fold_workers(["w1", "w0", "w2"])
    assert fold == {"w0": "w0", "w1": "__other__", "w2": "__other__"}
    text = fed.merge_prometheus([
        (fold["w0"], W0_TEXT), (fold["w1"], W0_TEXT),
        (fold["w2"], W0_TEXT)])
    # the two folded workers' identical series SUM under __other__
    assert ('dl4j_http_requests_total{code="200",route="classify",'
            'worker="__other__"} 10') in text
    assert ('dl4j_http_requests_total{code="200",route="classify",'
            'worker="w0"} 5') in text


def test_render_fleet_partial_on_dead_worker_never_raises(tmp_path):
    """One live worker, one registered-but-dead: the federated render
    carries the live worker's series AND a scrape-error count for the
    dead one — partial data, not an exception."""
    store = SharedStore(str(tmp_path / "fleet"))
    reg = ModelRegistry()
    reg.deploy("v1", _net(), sample_input=_SAMPLE, batch_limit=4,
               max_wait_ms=1.0)
    w0 = SharedServingState(store, "w0")
    w0.ensure_lane("scoring", "v1")
    fd = FrontDoor(ServingRouter(reg, "v1"), shared=w0, port=0).start()
    try:
        w0.register(os.getpid(), fd.port)
        # a port that refuses, heartbeat fresh: live-but-unreachable
        store.update(lambda d: d["workers"].update(
            dead={"pid": 1, "port": 1, "heartbeat": time.time()}))
        text = fed.render_fleet(store, local_worker="probe")
        assert 'worker="w0"' in text
        assert 'dl4j_fleet_scrape_errors_total{worker="dead"}' in text
        assert 'worker="probe"' in text                 # local series too
        # a heartbeat-EXPIRED worker is skipped silently (not an error)
        store.update(lambda d: d["workers"].update(
            gone={"pid": 1, "port": 2, "heartbeat": time.time() - 60}))
        text = fed.render_fleet(store, local_worker="probe")
        assert 'dl4j_fleet_scrape_errors_total{worker="gone"}' not in text
    finally:
        fd.stop()
        reg.shutdown()


# ---------------------------------------------------------------------------
# fleet health rollup
# ---------------------------------------------------------------------------

def test_fleet_health_flips_naming_the_missing_worker(tmp_path):
    store = SharedStore(str(tmp_path / "fleet"))
    reg = ModelRegistry()
    reg.deploy("v1", _net(), sample_input=_SAMPLE, batch_limit=4,
               max_wait_ms=1.0)
    w0 = SharedServingState(store, "w0")
    w0.ensure_lane("scoring", "v1")
    fd = FrontDoor(ServingRouter(reg, "v1"), shared=w0, port=0).start()
    try:
        w0.register(os.getpid(), fd.port)
        w0.sync()                                       # leader lease
        health = fed.FleetHealth(store, worker_id="probe")
        report = health.evaluate()
        assert report["status"] == "ok"
        assert report["workers_scraped"] == ["w0"]
        # a registered worker dies (refusing port, fresh heartbeat):
        # the verdict flips and NAMES it
        store.update(lambda d: d["workers"].update(
            w1={"pid": 1, "port": 1, "heartbeat": time.time()}))
        report = health.evaluate()
        assert report["status"] in ("degraded", "failing")
        alive = next(r for r in report["rules"]
                     if r["rule"] == "fleet_workers_alive")
        assert alive["status"] == "degraded"
        assert alive["missing"] == ["w1"]
        assert "w1" in report["scrape_errors"]
        # alerts carry the attribution too
        alerts = health.alerts()
        assert any(a["rule"] == "fleet_workers_alive"
                   for a in alerts["active"])
        # every registered worker gone ⇒ FAILING
        store.update(lambda d: d["workers"].update(
            w0={"pid": 1, "port": 1, "heartbeat": time.time() - 60},
            w1={"pid": 1, "port": 1, "heartbeat": time.time() - 60}))
        report = health.evaluate()
        assert report["status"] == "failing"
        assert "fleet_workers_alive" in report["failing_rules"]
    finally:
        fd.stop()
        reg.shutdown()


def test_bucket_quantile_interpolates():
    q = fed._bucket_quantile({0.1: 50.0, 1.0: 90.0, float("inf"): 100.0},
                             0.5)
    assert q == pytest.approx(0.1)                      # exact boundary
    # a quantile landing in +Inf answers the highest finite bound
    assert fed._bucket_quantile(
        {0.1: 50.0, 1.0: 90.0, float("inf"): 100.0}, 0.99) == 1.0
    assert fed._bucket_quantile({}, 0.99) != fed._bucket_quantile({}, 0.99)


def test_leader_publishes_rollup_to_debug_fleet(tmp_path):
    store = SharedStore(str(tmp_path / "fleet"))
    reg = ModelRegistry()
    reg.deploy("v1", _net(), sample_input=_SAMPLE, batch_limit=4,
               max_wait_ms=1.0)
    w0 = SharedServingState(store, "w0")
    w0.ensure_lane("scoring", "v1")
    fd = FrontDoor(ServingRouter(reg, "v1"), shared=w0, port=0).start()
    try:
        w0.register(os.getpid(), fd.port)
        w0.sync()
        assert w0.is_leader
        fd._fleet_obs_beat()                  # the sync-loop beat, inline
        doc = store.read()
        assert doc["fleet_health"]["by"] == "w0"
        assert doc["fleet_health"]["status"] in ("ok", "degraded")
        assert doc["fleet_health"]["term"] == w0.leader_term
        # and /debug/fleet serves the one shared verdict
        with urllib.request.urlopen(
                fd.get_address() + "/debug/fleet", timeout=10) as r:
            fleet = json.loads(r.read())
        assert fleet["fleet_health"]["by"] == "w0"
    finally:
        fd.stop()
        reg.shutdown()


# ---------------------------------------------------------------------------
# coordinated incident capture
# ---------------------------------------------------------------------------

def test_incident_fanout_same_id_on_every_worker(tmp_path):
    store = SharedStore(str(tmp_path / "fleet"))
    r1 = FlightRecorder(out_dir=str(tmp_path / "pm1"))
    r2 = FlightRecorder(out_dir=str(tmp_path / "pm2"))
    # w1's recorder publishes incidents (the frontdoor wires this hook)
    fed.install_incident_publisher(store, "w1")
    try:
        r1.dump("watchdog: wedged")
        incidents = store.read()["incidents"]
        assert len(incidents) == 1
        inc = incidents[0]
        assert inc["worker"] == "w1" and not inc["fanned_out"]
        assert "w1" in inc["captured"]
        # the leader's beat fans it out (w1 already captured: no re-dump)
        assert fed.incident_beat(store, "w1", True, recorder=r1) == []
        assert store.read()["incidents"][0]["fanned_out"] is True
        # w2's beat dumps ONE bundle stamped with the SAME incident id
        dumped = fed.incident_beat(store, "w2", False, recorder=r2)
        assert len(dumped) == 1
        with open(os.path.join(dumped[0], "incident.json")) as f:
            stamp = json.load(f)
        assert stamp["incident_id"] == inc["id"]
        assert stamp["reason"] == f"incident:{inc['id']}"
        captured = store.read()["incidents"][0]["captured"]
        assert set(captured) == {"w1", "w2"}
        # idempotent: the next beat dumps nothing
        assert fed.incident_beat(store, "w2", False, recorder=r2) == []
        # and the peer capture did NOT re-post (no echo storm)
        assert len(store.read()["incidents"]) == 1
    finally:
        from deeplearning4j_tpu.observability import flight_recorder as fr
        fr.set_incident_publisher(None)


def test_incident_publisher_inert_when_switched_off(tmp_path, monkeypatch):
    store = SharedStore(str(tmp_path / "fleet"))
    r1 = FlightRecorder(out_dir=str(tmp_path / "pm"))
    fed.install_incident_publisher(store, "w1")
    try:
        monkeypatch.setenv("DL4J_TPU_FLEET_OBS", "0")
        r1.dump("watchdog: wedged")
        assert "incidents" not in store.read()
        assert fed.incident_beat(store, "w1", True, recorder=r1) == []
    finally:
        from deeplearning4j_tpu.observability import flight_recorder as fr
        fr.set_incident_publisher(None)


# ---------------------------------------------------------------------------
# proxy e2e: one trace id across proxy -> worker, including failover
# ---------------------------------------------------------------------------

def _two_worker_fleet(tmp_path):
    store = SharedStore(str(tmp_path / "fleet"))
    doors, regs = [], []
    for wid in ("w0", "w1"):
        reg = ModelRegistry()
        reg.deploy("v1", _net(), sample_input=_SAMPLE, batch_limit=4,
                   max_wait_ms=1.0)
        shared = SharedServingState(store, wid)
        shared.ensure_lane("scoring", "v1")
        fd = FrontDoor(ServingRouter(reg, "v1"), shared=shared,
                       port=0).start()
        shared.register(os.getpid(), fd.port)
        fd.sync_once()
        doors.append(fd)
        regs.append(reg)
    return store, doors, regs


def test_proxy_one_trace_id_end_to_end(tmp_path):
    serve = _load_tool("serve")
    store, doors, regs = _two_worker_fleet(tmp_path)
    proxy = serve._HttpProxy(store, "127.0.0.1", 0)
    try:
        addr = f"http://127.0.0.1:{proxy.port}"
        code, _, h = _request(
            addr, "/v1/classify", {"inputs": [[0.0] * 4]},
            headers={fed.TRACE_HEADER: TID})
        assert code == 200
        assert h.get(fed.TRACE_HEADER) == TID           # proxied echo
        prox = _wait_span("proxy_request", lambda r: r.trace_id == TID)
        assert prox, "proxy span must join the caller's trace"
        sp = prox[0]
        assert sp.attrs["outcome"] == "ok"
        assert sp.attrs["worker"] in ("w0", "w1")
        assert sp.attrs["failovers"] == 0
        # the worker's root span: SAME trace, parented on the proxy span
        root = _wait_span("http_request", lambda r: r.trace_id == TID)
        assert root and root[0].parent_id == sp.span_id
        # satellite: the proxy registers its own series
        assert global_registry().get("dl4j_proxy_inflight") is not None
    finally:
        proxy.stop()
        for fd in doors:
            fd.stop()
        for reg in regs:
            reg.shutdown()


def test_proxy_failover_replay_keeps_the_trace_id(tmp_path):
    serve = _load_tool("serve")
    store, doors, regs = _two_worker_fleet(tmp_path)
    proxy = serve._HttpProxy(store, "127.0.0.1", 0)
    try:
        addr = f"http://127.0.0.1:{proxy.port}"
        # kill w1's server but keep its registration fresh: the proxy
        # must connect-failover and the replayed bytes carry the SAME id
        doors[1].stop()
        store.update(lambda d: d["workers"]["w1"].update(
            heartbeat=time.time() + 30))
        fo_tids = []
        for i in range(4):                    # round robin: some hit w1
            tid = f"f{i:015x}"
            code, _, h = _request(
                addr, "/v1/classify", {"inputs": [[0.0] * 4]},
                headers={fed.TRACE_HEADER: tid,
                         idem.IDEMPOTENCY_HEADER: f"FK{i}"})
            assert code == 200
            assert h.get(fed.TRACE_HEADER) == tid, f"request {i}"
            fo_tids.append(tid)
        failed_over = _wait_span(
            "proxy_request",
            lambda r: (r.trace_id in fo_tids
                       and (r.attrs.get("failovers") or 0) >= 1))
        assert failed_over, "at least one request must have failed over"
        assert failed_over[0].attrs["outcome"] == "ok"
        assert failed_over[0].attrs["worker"] == "w0"   # the survivor
        fcount = global_registry().get("dl4j_fleet_failovers_total")
        assert fcount is not None and fcount.value >= 1
    finally:
        proxy.stop()
        for fd in doors:
            fd.stop()
        for reg in regs:
            reg.shutdown()


# ---------------------------------------------------------------------------
# proxy admin surface (FleetAdminServer)
# ---------------------------------------------------------------------------

def test_admin_server_routes(tmp_path):
    store = SharedStore(str(tmp_path / "fleet"))
    reg = ModelRegistry()
    reg.deploy("v1", _net(), sample_input=_SAMPLE, batch_limit=4,
               max_wait_ms=1.0)
    w0 = SharedServingState(store, "w0")
    w0.ensure_lane("scoring", "v1")
    fd = FrontDoor(ServingRouter(reg, "v1"), shared=w0, port=0).start()
    admin = fed.FleetAdminServer(
        store, host="127.0.0.1", port=0, local_worker="proxy",
        debug_extra=lambda: {"mode": "http"}).start()
    try:
        w0.register(os.getpid(), fd.port)
        w0.sync()
        base = admin.get_address()
        code, body, _ = _request(base, "/metrics")
        assert code == 200 and b"dl4j_" in body         # local registry
        code, body, _ = _request(base, "/metrics/fleet")
        assert code == 200
        assert b'worker="w0"' in body and b'worker="proxy"' in body
        code, body, _ = _request(base, "/health/fleet")
        assert code == 200
        assert json.loads(body)["status"] in ("ok", "degraded")
        code, body, _ = _request(base, "/alerts/fleet")
        assert code == 200 and "active" in json.loads(body)
        code, body, _ = _request(base, "/debug/proxy")
        dbg = json.loads(body)
        assert code == 200 and dbg["proxy"] == {"mode": "http"}
        assert isinstance(dbg["recent_proxy_spans"], list)
        code, _, _ = _request(base, "/nope")
        assert code == 404
    finally:
        admin.stop()
        fd.stop()
        reg.shutdown()


# ---------------------------------------------------------------------------
# kill switch: DL4J_TPU_FLEET_OBS=0 is the pre-plane front door
# ---------------------------------------------------------------------------

def test_kill_switch_restores_pre_plane_behavior(tmp_path, monkeypatch):
    monkeypatch.setenv("DL4J_TPU_FLEET_OBS", "0")
    store = SharedStore(str(tmp_path / "fleet"))
    reg = ModelRegistry()
    reg.deploy("v1", _net(), sample_input=_SAMPLE, batch_limit=4,
               max_wait_ms=1.0)
    w0 = SharedServingState(store, "w0")
    w0.ensure_lane("scoring", "v1")
    fd = FrontDoor(ServingRouter(reg, "v1"), shared=w0, port=0).start()
    try:
        w0.register(os.getpid(), fd.port)
        fd.sync_once()
        addr = fd.get_address()
        # no trace header on ANY response, inbound ids ignored
        for path, body in [("/v1/classify", {"inputs": [[0.0] * 4]}),
                           ("/nope", {"x": 1})]:
            _, _, h = _request(addr, path, body,
                               headers={fed.TRACE_HEADER: TID})
            assert fed.TRACE_HEADER not in h, path
        for path in ("/metrics", "/health", "/debug/frontdoor"):
            code, _, h = _request(addr, path,
                                  headers={fed.TRACE_HEADER: TID})
            assert fed.TRACE_HEADER not in h, path
        # the caller's id did NOT join any span (fresh roots only)
        time.sleep(0.3)
        assert not [r for r in _spans() if r.trace_id == TID]
        # the fleet routes don't exist on the off path
        for path in ("/metrics/fleet", "/health/fleet", "/alerts/fleet"):
            code, _, _ = _request(addr, path)
            assert code == 404, path
        # /metrics payload is the plain pre-federation exposition
        code, body, h = _request(addr, "/metrics")
        assert code == 200
        assert h["Content-Type"].startswith("text/plain; version=0.0.4")
        assert b"dl4j_http_requests_total" in body
        # no rollup/incident machinery ran
        assert "fleet_health" not in store.read()
    finally:
        fd.stop()
        reg.shutdown()


def test_fleet_obs_enabled_reads_live(monkeypatch):
    assert fed.fleet_obs_enabled()
    monkeypatch.setenv("DL4J_TPU_FLEET_OBS", "0")
    assert not fed.fleet_obs_enabled()
    monkeypatch.setenv("DL4J_TPU_FLEET_OBS", "1")
    assert fed.fleet_obs_enabled()


# ---------------------------------------------------------------------------
# the live 2-worker drill (subprocess; slow)
# ---------------------------------------------------------------------------

@pytest.mark.slow
def test_fleet_obs_drill_live(tmp_path):
    out = tmp_path / "obsfleet.json"
    proc = subprocess.run(
        [sys.executable, os.path.join(_REPO, "benchmarks", "http_load.py"),
         "--fleet-obs", "--obs-requests", "20", "--obs-scrapes", "8",
         "--state-dir", str(tmp_path / "fleet"), "--out", str(out)],
        env=dict(os.environ, JAX_PLATFORMS="cpu"),
        capture_output=True, text=True, timeout=240)
    assert proc.returncode == 0, proc.stdout + proc.stderr
    rec = json.loads(out.read_text())
    assert rec["ok_verdict"]
    assert rec["trace_coverage"] >= 0.95
    assert rec["federation_completeness"] == 1.0
    assert rec["partial_scrape_ok"] and rec["single_trace_ok"]
