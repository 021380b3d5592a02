"""HTTP front-door suite: wire round-trips, SSE per-token streaming
(byte-identical to non-streamed, including under a mid-stream slot
join), typed-error → HTTP status mapping, the shared-store CAS + fleet
rollout state machine, the ``http.request`` chaos point (exactly-once,
slots always freed, none hang), and the live kill switch. Multi-process
fleet spin-up and the load-generator drill are ``slow`` (tier-1 budget:
in-process single-worker coverage only).
"""
import json
import os
import socket
import subprocess
import sys
import threading
import time
import urllib.error
import urllib.request

import numpy as np
import pytest

import jax

from deeplearning4j_tpu.models.generation import DecodeEngine
from deeplearning4j_tpu.models.transformer import (TransformerConfig,
                                                   TransformerLM)
from deeplearning4j_tpu.nn.conf.configuration import NeuralNetConfiguration
from deeplearning4j_tpu.nn.conf.layers import DenseLayer, OutputLayer
from deeplearning4j_tpu.nn.multilayer import MultiLayerNetwork
from deeplearning4j_tpu.observability import (global_registry,
                                              reset_global_registry)
from deeplearning4j_tpu.optim.updaters import Adam
from deeplearning4j_tpu.parallel.generation import GenerationPipeline
from deeplearning4j_tpu.resilience import faults
from deeplearning4j_tpu.serving import (FrontDoor, ModelRegistry,
                                        ServingRouter, SharedServingState,
                                        SharedStore)
from deeplearning4j_tpu.serving.frontdoor import http_status
from deeplearning4j_tpu.serving.shared_state import CANARY, FULL, ROLLED_BACK

VOCAB = 61


def _make_net(seed=1):
    conf = (NeuralNetConfiguration.builder()
            .seed(seed).updater(Adam(1e-2)).list()
            .layer(DenseLayer(n_in=4, n_out=8, activation="relu"))
            .layer(OutputLayer(n_in=8, n_out=3, activation="softmax",
                               loss_function="mcxent"))
            .build())
    return MultiLayerNetwork(conf).init()


# module-level net/engine: jit caches persist across tests, the deploys
# warm from cache (the test_serving/test_generation pattern on this box)
_NET = None
_ENGINE = None


def _net():
    global _NET
    if _NET is None:
        _NET = _make_net(1)
    return _NET


def _engine():
    global _ENGINE
    if _ENGINE is None:
        cfg = TransformerConfig(vocab_size=VOCAB, n_layers=2, n_heads=2,
                                d_model=32, max_len=64)
        m = TransformerLM(cfg)
        _ENGINE = DecodeEngine(m, m.init_params(jax.random.key(0)),
                               max_len=48)
    return _ENGINE


_SAMPLE = np.zeros((1, 4), dtype="f4")


@pytest.fixture(autouse=True)
def _clean():
    faults.reset()
    reset_global_registry()
    yield
    faults.clear()
    GenerationPipeline.shutdown_all()


def _post(addr, path, doc, timeout=30.0):
    """(status, json_body, headers) — HTTPError unwrapped, not raised."""
    req = urllib.request.Request(
        addr + path, data=json.dumps(doc).encode(),
        headers={"Content-Type": "application/json"})
    try:
        with urllib.request.urlopen(req, timeout=timeout) as r:
            return r.status, json.loads(r.read()), dict(r.headers)
    except urllib.error.HTTPError as e:
        return e.code, json.loads(e.read() or b"{}"), dict(e.headers)


def _get(addr, path, timeout=10.0):
    try:
        with urllib.request.urlopen(addr + path, timeout=timeout) as r:
            return r.status, json.loads(r.read())
    except urllib.error.HTTPError as e:
        return e.code, json.loads(e.read() or b"{}")


def _sse(addr, doc, timeout=60.0):
    """Parse one SSE generate: (token list, done payload, error payload,
    per-event arrival times)."""
    req = urllib.request.Request(
        addr + "/v1/generate",
        data=json.dumps(dict(doc, stream=True)).encode(),
        headers={"Content-Type": "application/json"})
    toks, done, error, at = [], None, None, []
    with urllib.request.urlopen(req, timeout=timeout) as r:
        assert r.headers["Content-Type"].startswith("text/event-stream")
        ev = None
        for line in r:
            line = line.decode().rstrip("\n")
            if line.startswith("event: "):
                ev = line[7:]
            elif line.startswith("data: "):
                data = json.loads(line[6:])
                if ev == "token":
                    toks.append(data["token"])
                    at.append(time.perf_counter())
                elif ev == "done":
                    done = data
                elif ev == "error":
                    error = data
    return toks, done, error, at


def _scoring_door(**fd_kw):
    reg = ModelRegistry()
    reg.deploy("v1", _net(), sample_input=_SAMPLE, batch_limit=4,
               max_wait_ms=1.0)
    router = ServingRouter(reg, "v1")
    fd = FrontDoor(router, port=0, **fd_kw).start()
    return reg, router, fd


def _gen_door(slots=2, **fd_kw):
    reg = ModelRegistry()
    reg.deploy_generative("g1", _engine(), slots=slots, max_new_tokens=16)
    gen_router = ServingRouter(reg, "g1")
    fd = FrontDoor(gen_router=gen_router, port=0, **fd_kw).start()
    return reg, gen_router, fd


# --------------------------------------------------------------- classify
def test_classify_http_round_trip_matches_direct_and_carries_trace_id():
    reg, router, fd = _scoring_door()
    try:
        x = np.random.RandomState(0).rand(2, 4).astype("f4")
        code, body, headers = _post(fd.get_address(), "/v1/classify",
                                    {"inputs": x.tolist(),
                                     "request_key": 7})
        assert code == 200
        direct = router.output(x, request_key=7)
        assert np.allclose(np.asarray(body["outputs"]),
                           np.asarray(direct), rtol=1e-5, atol=1e-6)
        assert headers.get("X-Dl4j-Trace-Id")       # joinable to traces
        # dl4j_http_* series landed
        inst = global_registry().get("dl4j_http_requests_total")
        assert any(lv[0] == "classify" and lv[1] == "200"
                   for lv, _ in inst.series())
    finally:
        fd.stop()
        reg.shutdown()


def test_status_mapping_400_404_429_503_504():
    reg, router, fd = _scoring_door()
    try:
        addr = fd.get_address()
        # malformed body / missing field → 400
        req = urllib.request.Request(addr + "/v1/classify",
                                     data=b"not json")
        with pytest.raises(urllib.error.HTTPError) as ei:
            urllib.request.urlopen(req)
        assert ei.value.code == 400
        code, body, _ = _post(addr, "/v1/classify", {"nope": 1})
        assert code == 400 and body["error"] == "BadRequest"
        # unknown route → 404
        code, _, _ = _post(addr, "/v1/nope", {})
        assert code == 404
        # no generative deploy behind this door → 404
        code, body, _ = _post(addr, "/v1/generate", {"prompt": [1, 2]})
        assert code == 404
        # oversized Content-Length is refused BEFORE buffering → 413
        import http.client
        conn = http.client.HTTPConnection("127.0.0.1", fd.port, timeout=10)
        conn.putrequest("POST", "/v1/classify")
        conn.putheader("Content-Length", str(10 ** 10))
        conn.endheaders()
        resp = conn.getresponse()
        assert resp.status == 413
        conn.close()
        # expired deadline → 504 (typed DeadlineExceeded) — and even the
        # ERROR reply carries the trace id (join-to-traces contract)
        code, body, headers = _post(addr, "/v1/classify",
                                    {"inputs": [[0.0] * 4],
                                     "deadline_ms": 1e-6})
        assert code == 504 and body["error"] == "DeadlineExceeded"
        assert headers.get("X-Dl4j-Trace-Id")
        # admission gate → 429 (a zero-inflight door sheds everything)
        fd2 = FrontDoor(router, port=0, max_inflight=0).start()
        try:
            code, body, _ = _post(fd2.get_address(), "/v1/classify",
                                  {"inputs": [[0.0] * 4]})
            assert code == 429 and body["error"] == "ShedError"
        finally:
            fd2.stop()
        # drained version → 503 (typed ShutdownError)
        reg.retire("v1")
        code, body, _ = _post(addr, "/v1/classify",
                              {"inputs": [[0.0] * 4]})
        assert code == 503 and body["error"] == "ShutdownError"
    finally:
        fd.stop()
        reg.shutdown()


def test_kill_switch_is_live_and_spares_debug_surfaces(monkeypatch):
    reg, _, fd = _scoring_door()
    try:
        addr = fd.get_address()
        code, _, _ = _post(addr, "/v1/classify", {"inputs": [[0.0] * 4]})
        assert code == 200
        monkeypatch.setenv("DL4J_TPU_FRONTDOOR", "0")   # no restart
        code, body, _ = _post(addr, "/v1/classify",
                              {"inputs": [[0.0] * 4]})
        assert code == 503 and body["error"] == "FrontDoorDisabled"
        code, snap = _get(addr, "/debug/frontdoor")
        assert code == 200 and snap["enabled"] is False
        monkeypatch.delenv("DL4J_TPU_FRONTDOOR")
        code, _, _ = _post(addr, "/v1/classify", {"inputs": [[0.0] * 4]})
        assert code == 200
    finally:
        fd.stop()
        reg.shutdown()


def test_http_request_is_a_valid_fault_point_and_maps_to_500():
    spec = faults.FaultSpec("http.request", "error", rate=1.0)
    assert spec.point == "http.request"
    with pytest.raises(ValueError):
        faults.FaultSpec("http.request", "nan")     # owns no array
    reg, _, fd = _scoring_door()
    try:
        with faults.active(faults.FaultPlan([spec])):
            code, body, _ = _post(fd.get_address(), "/v1/classify",
                                  {"inputs": [[0.0] * 4]})
        assert code == 500 and body["error"] == "InjectedFault"
        code, _, _ = _post(fd.get_address(), "/v1/classify",
                           {"inputs": [[0.0] * 4]})
        assert code == 200                           # plan cleared
    finally:
        fd.stop()
        reg.shutdown()


# -------------------------------------------------------------- streaming
def test_sse_stream_is_byte_identical_incremental_and_survives_slot_join():
    """The streaming-correctness satellite: the SSE token sequence equals
    the non-streamed result for the same seed/version EXACTLY — also
    while a second request joins a slot mid-stream — and tokens arrive
    incrementally (first event well before the last)."""
    reg, _, fd = _gen_door(slots=2)
    try:
        addr = fd.get_address()
        prompt = [3, 1, 4, 1, 5, 9, 2]
        doc = {"prompt": prompt, "max_new_tokens": 32}
        code, plain, _ = _post(addr, "/v1/generate", doc)
        assert code == 200
        joined = {}

        def join_other():
            joined["result"] = _post(addr, "/v1/generate",
                                     {"prompt": [8, 6, 7],
                                      "max_new_tokens": 8})

        t0 = time.perf_counter()
        joiner = threading.Thread(target=join_other, daemon=True)
        joiner.start()                 # lands mid-stream on slot 2
        toks, done, error, at = _sse(addr, doc)
        joiner.join(timeout=30)
        assert error is None
        assert toks == plain["tokens"]             # byte-identical
        assert done["tokens"] == toks
        assert joined["result"][0] == 200          # the join succeeded
        # incremental emission: the first token landed well before the
        # stream finished, not in one terminal flush
        assert len(at) == len(toks) and len(toks) >= 16
        assert at[0] - t0 < (at[-1] - t0) * 0.5
    finally:
        fd.stop()
        reg.shutdown()


def test_client_disconnect_mid_stream_frees_slot_with_typed_shed():
    """Chaos satellite piece: a client that RSTs its SSE connection
    mid-stream cancels the request at a step boundary — the slot frees
    (typed ``client_gone`` shed), other traffic keeps flowing."""
    reg, _, fd = _gen_door(slots=2)
    try:
        gp = reg.get("g1").gp
        payload = json.dumps({"prompt": [3, 1, 4, 1, 5, 9, 2],
                              "max_new_tokens": 40,
                              "stream": True}).encode()
        import struct
        s = socket.create_connection(("127.0.0.1", fd.port), timeout=10)
        # linger-0 close sends RST: the server's next write fails NOW,
        # not after kernel buffers drain
        s.setsockopt(socket.SOL_SOCKET, socket.SO_LINGER,
                     struct.pack("ii", 1, 0))
        s.sendall(b"POST /v1/generate HTTP/1.1\r\n"
                  b"Host: x\r\nContent-Type: application/json\r\n"
                  + f"Content-Length: {len(payload)}\r\n\r\n".encode()
                  + payload)
        # read until the first token event, then vanish
        buf = b""
        while b"event: token" not in buf:
            chunk = s.recv(4096)
            assert chunk, f"stream ended early: {buf!r}"
            buf += chunk
        s.close()
        deadline = time.monotonic() + 20
        while time.monotonic() < deadline:
            if gp.snapshot()["active"] == 0:
                break
            time.sleep(0.05)
        assert gp.snapshot()["active"] == 0        # slot freed, no hang
        shed = global_registry().get("dl4j_decode_shed_total")
        got = {lv[0]: c.value for lv, c in shed.series()}
        assert got.get("client_gone", 0) >= 1
        # the door still serves (nothing wedged)
        code, body, _ = _post(fd.get_address(), "/v1/generate",
                              {"prompt": [1, 2, 3],
                               "max_new_tokens": 4})
        assert code == 200 and len(body["tokens"]) == 4
    finally:
        fd.stop()
        reg.shutdown()


def test_frontdoor_chaos_every_request_resolves_exactly_once():
    """Chaos satellite: http.request faults x deadlines x concurrent
    mixed traffic — every request resolves with exactly one valid
    outcome (2xx/typed 4xx-5xx), no hangs, all slots freed."""
    reg = ModelRegistry()
    reg.deploy("v1", _net(), sample_input=_SAMPLE, batch_limit=4,
               max_wait_ms=1.0)
    reg.deploy_generative("g1", _engine(), slots=2, max_new_tokens=8)
    fd = FrontDoor(ServingRouter(reg, "v1"),
                   gen_router=ServingRouter(reg, "g1"), port=0).start()
    try:
        addr = fd.get_address()
        plan = faults.FaultPlan([
            faults.FaultSpec("http.request", "error", rate=0.3),
            faults.FaultSpec("http.request", "latency", rate=0.2,
                             latency_seconds=0.02),
            faults.FaultSpec("inference.device_execute", "error", rate=0.1),
        ], seed=11)
        outcomes = []
        lock = threading.Lock()

        def one(i):
            if i % 3 == 0:
                code, body, _ = _post(addr, "/v1/generate",
                                      {"prompt": [1 + i % 40, 2, 3],
                                       "max_new_tokens": 4,
                                       "deadline_ms": 10_000,
                                       "request_key": i}, timeout=60)
            else:
                code, body, _ = _post(addr, "/v1/classify",
                                      {"inputs": [[0.1 * i % 1] * 4],
                                       "deadline_ms": 10_000,
                                       "request_key": i}, timeout=60)
            with lock:
                outcomes.append((i, code))

        with faults.active(plan):
            threads = [threading.Thread(target=one, args=(i,), daemon=True)
                       for i in range(24)]
            for t in threads:
                t.start()
            for t in threads:
                t.join(timeout=90)
            assert not any(t.is_alive() for t in threads)   # none hang
        assert len(outcomes) == 24                          # exactly once
        assert all(c in (200, 429, 500, 503, 504) for _, c in outcomes)
        assert any(c == 200 for _, c in outcomes)
        assert any(c != 200 for _, c in outcomes)
        # slots all freed afterwards
        deadline = time.monotonic() + 10
        gp = reg.get("g1").gp
        while gp.snapshot()["active"] and time.monotonic() < deadline:
            time.sleep(0.05)
        assert gp.snapshot()["active"] == 0
    finally:
        fd.stop()
        reg.shutdown()


# ------------------------------------------------------------ shared store
def test_shared_store_cas_is_atomic_under_concurrency(tmp_path):
    store = SharedStore(str(tmp_path / "fleet"))
    # CAS refuses a stale rev
    doc = store.read()
    assert store.try_replace({"x": 1}, doc.get("rev", 0))
    assert not store.try_replace({"x": 2}, 0)       # stale
    assert store.read()["x"] == 1

    def bump(_):
        def mutate(d):
            d["count"] = d.get("count", 0) + 1
        for _ in range(25):
            store.update(mutate)

    threads = [threading.Thread(target=bump, args=(i,))
               for i in range(8)]
    for t in threads:
        t.start()
    for t in threads:
        t.join(timeout=60)
    final = store.read()
    assert final["count"] == 200                    # no lost updates
    assert final["rev"] >= 201                      # rev monotonic


def test_shared_rollout_advances_on_aggregated_windows(tmp_path):
    """Two workers' windows aggregate through the store; the leader
    (w0) advances canary → ramp → full and flips the lane primary; the
    follower observes the transitions through sync()."""
    store = SharedStore(str(tmp_path / "fleet"))
    w0 = SharedServingState(store, "w0")
    w1 = SharedServingState(store, "w1")
    w0.register(111, 8001)
    w1.register(222, 8002)
    w0.ensure_lane("scoring", "v1")
    w1.ensure_lane("scoring", "v1")                 # no-op: lane exists
    w0.begin_rollout("scoring", "v2", {
        "window_seconds": 0.05, "window_min_requests": 4,
        "healthy_windows": 1, "canary_fraction": 0.5,
        "ramp_fractions": [0.75], "min_latency_n": 2})
    assert w1.routing("scoring")["stage"] == CANARY
    # consistent hash split: both workers route the same fraction the
    # same way
    assert w0.pick("scoring", 0.4) == w1.pick("scoring", 0.4) == ("v2", True)
    assert w0.pick("scoring", 0.9) == ("v1", False)
    seen = []
    deadline = time.monotonic() + 30
    while time.monotonic() < deadline:
        for w in (w0, w1):
            for _ in range(3):
                w.record("v2", ok=True, latency_s=0.01)
                w.record("v1", ok=True, latency_s=0.01)
        w0.sync()
        seen.extend(w1.sync())
        if w1.routing("scoring")["stage"] == FULL:
            break
        time.sleep(0.06)
    assert w1.routing("scoring")["stage"] == FULL
    assert store.read()["lanes"]["scoring"]["primary"] == "v2"
    assert any(e["to"] == "full" for e in seen)     # follower saw it
    assert w0.is_leader and not w1.is_leader


def test_shared_rollout_rolls_back_on_aggregated_errors(tmp_path):
    store = SharedStore(str(tmp_path / "fleet"))
    w0 = SharedServingState(store, "w0")
    w0.register(111, 8001)
    w0.ensure_lane("scoring", "v1")
    w0.begin_rollout("scoring", "v2", {
        "window_seconds": 0.05, "window_min_requests": 4,
        "healthy_windows": 5, "error_rate_failing": 0.3})
    deadline = time.monotonic() + 30
    while time.monotonic() < deadline:
        for _ in range(4):
            w0.record("v2", ok=False, latency_s=0.01)
            w0.record("v1", ok=True, latency_s=0.01)
        w0.sync()
        if w0.routing("scoring")["stage"] == ROLLED_BACK:
            break
        time.sleep(0.06)
    r = w0.routing("scoring")
    assert r["stage"] == ROLLED_BACK and r["share"] == 0.0
    assert store.read()["lanes"]["scoring"]["primary"] == "v1"


def test_http_status_mapping_table():
    from deeplearning4j_tpu.parallel.generation import StreamCancelled
    from deeplearning4j_tpu.resilience.policy import (CircuitOpenError,
                                                      DeadlineExceeded,
                                                      ShedError,
                                                      ShutdownError)
    assert http_status(ShedError("x")) == 429
    assert http_status(StreamCancelled("x")) == 429
    assert http_status(DeadlineExceeded("x")) == 504
    assert http_status(CircuitOpenError("x")) == 503
    assert http_status(ShutdownError("x")) == 503
    assert http_status(KeyError("v9")) == 404
    assert http_status(ValueError("x")) == 400
    assert http_status(RuntimeError("x")) == 500


def test_ui_server_bind_host_knob(monkeypatch):
    """Satellite: DL4J_TPU_UI_HOST picks the UI bind host (default
    unchanged: loopback)."""
    from deeplearning4j_tpu.ui.server import UIServer, default_bind_host
    assert default_bind_host() == "127.0.0.1"
    monkeypatch.setenv("DL4J_TPU_UI_HOST", "0.0.0.0")
    assert default_bind_host() == "0.0.0.0"
    ui = UIServer(port=0).start()
    try:
        assert ui.host == "0.0.0.0"
        # the printable address still points somewhere reachable
        assert ui.get_address().startswith("http://127.0.0.1:")
        code, _ = _get(ui.get_address(), "/debug/frontdoor")
        assert code == 200
    finally:
        ui.stop()


# ---------------------------------------------------------- multi-process
@pytest.mark.slow
def test_two_worker_fleet_kill_drill_over_real_http(tmp_path):
    """The acceptance drill end-to-end: 2 worker processes behind the
    proxy serve one canaried version set; SIGKILL of one worker loses
    zero requests on the survivors; the respawned worker rejoins the
    same rollout stage; streaming matches non-streamed output."""
    out = tmp_path / "serve.json"
    r = subprocess.run(
        [sys.executable,
         os.path.join(os.path.dirname(os.path.dirname(
             os.path.abspath(__file__))), "benchmarks", "http_load.py"),
         "--qps", "12", "--duration-s", "20", "--workers", "2",
         "--kill-drill", "--state-dir", str(tmp_path / "fleet"),
         "--out", str(out)],
        capture_output=True, text=True, timeout=240,
        env=dict(os.environ, JAX_PLATFORMS="cpu"))
    assert r.returncode == 0, r.stdout + r.stderr
    rec = json.loads(out.read_text())
    assert rec["failed"] == 0                       # zero failed requests
    assert rec["stream"]["matches"]                 # SSE == non-streamed
    assert rec["stream"]["first_token_speedup"] > 1.5
    drill = rec["kill_drill"]
    assert drill["respawned"] and drill["rejoined_same_stage"]
    assert rec["workers"] == 2
