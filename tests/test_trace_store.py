"""Trace intelligence suite (ARCHITECTURE.md §24): tail-based
retention in both directions (errors / latency outliers / incident
windows kept, boring head-unsampled traffic dropped), bytes-budget
eviction oldest-first with pinned traces exempt, partial fleet
assembly when a worker dies mid-scrape (never a 500), the
``DL4J_TPU_TRACE_STORE=0`` kill switch (byte-identical pre-store
behavior: inert hooks, unstamped spans, no debug endpoints), and the
``/debug/trace/<id>`` 404 contract on unknown ids.  The live 2-worker
subprocess drill is ``slow``.
"""
import json
import os
import socket
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
from deeplearning4j_tpu.observability import (global_trace_sink,
                                              reset_global_registry,
                                              reset_global_trace_sink)
from deeplearning4j_tpu.observability import federation as fed
from deeplearning4j_tpu.observability import trace_store as ts
from deeplearning4j_tpu.observability.tracing import SpanRecord
from deeplearning4j_tpu.optim.updaters import Adam
from deeplearning4j_tpu.resilience import faults
from deeplearning4j_tpu.serving import (FrontDoor, ModelRegistry,
                                        ServingRouter)
from deeplearning4j_tpu.serving import idempotency as idem

import jax  # noqa: F401  (forces the CPU platform before nets build)

_REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))

TID = "aaaabbbbccccdddd"


@pytest.fixture(autouse=True)
def _clean(monkeypatch):
    faults.reset()
    reset_global_registry()
    reset_global_trace_sink()
    idem.reset_global_journal()
    ts.reset_global_trace_store()
    # deterministic retention: no head-sampling coin unless a test
    # flips it back on
    monkeypatch.setenv("DL4J_TPU_TRACE_SAMPLE", "0")
    yield
    faults.clear()
    ts.reset_global_trace_store()


_NET = None
_SAMPLE = np.zeros((1, 4), dtype="f4")


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


def _scoring_door(**kw):
    reg = ModelRegistry()
    reg.deploy("v1", _net(), sample_input=_SAMPLE, batch_limit=4,
               max_wait_ms=1.0)
    return FrontDoor(ServingRouter(reg, "v1"), **kw).start(), reg


def _request(addr, path, body=None, headers=(), timeout=30.0):
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


def _rec(trace_id, name="http_request", span_id="s1", parent=None,
         ts_us=0.0, dur_us=1000.0, attrs=None, error=False,
         error_type=None):
    return SpanRecord(name, ts_us, dur_us, 1, 0, attrs,
                      trace_id=trace_id, span_id=span_id,
                      parent_id=parent, error=error,
                      error_type=error_type)


def _complete(store, trace_id, **kw):
    """One open+close round-trip through the synchronous public API."""
    store.note_open(trace_id)
    store.feed(_rec(trace_id, **kw))


def _wait_span(name, pred, timeout=3.0):
    deadline = time.monotonic() + timeout
    while time.monotonic() < deadline:
        hits = [r for r in global_trace_sink().spans()
                if r.name == name and pred(r)]
        if hits:
            return hits
        time.sleep(0.05)
    return []


# ---------------------------------------------------------------------------
# retention: both directions
# ---------------------------------------------------------------------------

def test_error_traces_always_retained():
    """Every root-error shape the front door / proxy stamps keeps the
    trace: raised exception, typed error_type attr, HTTP status >= 400,
    and the proxy's typed shed outcomes."""
    store = ts.TraceStore()
    _complete(store, "e" * 16, error=True, error_type="RuntimeError")
    _complete(store, "f" * 16, attrs={"error_type": "DeadlineExceeded"})
    _complete(store, "1" * 16, attrs={"status": 500})
    _complete(store, "2" * 16, name="proxy_request",
              attrs={"outcome": "no_backend"})
    for tid in ("e" * 16, "f" * 16, "1" * 16, "2" * 16):
        got = store.get(tid)
        assert got is not None and got["reason"] == "error", tid
        assert got["error"]
    assert store.retained_count == 4 and store.discarded_count == 0


def test_latency_tail_retained_boring_dropped():
    """Tail-based sampling in both directions: once the per-endpoint
    window has enough samples, a root far past the rolling quantile is
    kept (reason latency_tail) while at-the-median traffic keeps being
    dropped with the head coin at 0."""
    store = ts.TraceStore()
    for i in range(24):
        _complete(store, f"{i:016x}", dur_us=1000.0)
    # direction 1: boring traffic was NOT retained
    assert store.retained_count == 0 and store.discarded_count == 24
    assert store.get(f"{3:016x}") is None
    # direction 2: the outlier IS
    _complete(store, "a" * 16, dur_us=500000.0)
    got = store.get("a" * 16)
    assert got is not None and got["reason"] == "latency_tail"
    # a fresh at-the-median trace after the outlier still drops
    _complete(store, "b" * 16, dur_us=1000.0)
    assert store.get("b" * 16) is None
    # windows are per-endpoint: the same duration under a different
    # route has no warmed window, so the tail rule stays off for it
    _complete(store, "c" * 16, dur_us=500000.0,
              attrs={"route": "/v1/other"})
    assert store.get("c" * 16) is None


def test_head_sample_coin_both_directions(monkeypatch):
    store = ts.TraceStore()
    monkeypatch.setenv("DL4J_TPU_TRACE_SAMPLE", "1.0")
    _complete(store, "d" * 16)
    got = store.get("d" * 16)
    assert got is not None and got["reason"] == "head_sample"
    monkeypatch.setenv("DL4J_TPU_TRACE_SAMPLE", "0")
    _complete(store, "e" * 16)
    assert store.get("e" * 16) is None


def test_incident_pin_and_window_retain():
    store = ts.TraceStore()
    store.pin("ab" * 8)
    _complete(store, "ab" * 8)       # boring, but pinned before close
    got = store.get("ab" * 8)
    assert got is not None and got["reason"] == "incident"
    assert got["pinned"]
    assert not store.incident_active()
    store.open_incident_window(60.0)
    assert store.incident_active()
    _complete(store, "cd" * 8)       # boring, inside the window
    got = store.get("cd" * 8)
    assert got is not None and got["reason"] == "incident"
    store.clear()
    assert not store.incident_active()


def test_multi_span_trace_completes_on_last_close():
    """A trace with nested opens only finalizes when the LAST open
    block closes; spans ship sorted by start time."""
    store = ts.TraceStore()
    store.note_open(TID)
    store.note_open(TID)
    store.feed(_rec(TID, name="prefill", span_id="s2", parent="s1",
                    ts_us=10.0, dur_us=50.0))
    assert store.get(TID) is None            # root still open
    store.feed(_rec(TID, name="http_request", span_id="s1",
                    ts_us=0.0, dur_us=100.0, attrs={"status": 503}))
    got = store.get(TID)
    assert got is not None and got["reason"] == "error"
    assert [s["name"] for s in got["spans"]] == ["http_request",
                                                 "prefill"]
    assert got["root"] == "http_request"


# ---------------------------------------------------------------------------
# bytes budget: eviction order
# ---------------------------------------------------------------------------

def test_budget_evicts_oldest_first_pinned_exempt():
    per = ts._est_bytes(ts._span_dict(_rec("x" * 16,
                                           attrs={"status": 500})))
    store = ts.TraceStore(budget=int(per * 3.5))     # room for 3
    for tid in ("e1", "e2", "e3"):
        _complete(store, tid * 8, attrs={"status": 500})
    assert store.snapshot()["traces"] == 3 and store.evicted_count == 0
    _complete(store, "e4" * 8, attrs={"status": 500})
    # oldest-first: e1 went, the rest stayed
    assert store.get("e1" * 8) is None
    assert all(store.get(t * 8) for t in ("e2", "e3", "e4"))
    assert store.evicted_count == 1
    store.pin("e2" * 8)
    _complete(store, "e5" * 8, attrs={"status": 500})
    # e2 is pinned: eviction skips it and takes the next-oldest e3
    assert store.get("e2" * 8) is not None
    assert store.get("e3" * 8) is None
    assert all(store.get(t * 8) for t in ("e2", "e4", "e5"))
    assert store.evicted_count == 2
    snap = store.snapshot()
    assert snap["bytes"] <= snap["budget_bytes"]


# ---------------------------------------------------------------------------
# partial fleet assembly: a dead worker is an answer, not a 500
# ---------------------------------------------------------------------------

class _FakeFleetStore:
    def __init__(self, workers):
        self._workers = workers

    def read(self):
        return {"workers": self._workers}


def _dead_port():
    s = socket.socket()
    s.bind(("127.0.0.1", 0))
    port = s.getsockname()[1]
    s.close()
    return port


def test_partial_assembly_after_worker_kill(monkeypatch):
    """A worker that died between announce and scrape lands in
    scrape_errors with partial=True; the surviving spans still
    assemble and the route answers 200, never 500."""
    monkeypatch.setenv("DL4J_TPU_FLEET_SCRAPE_TIMEOUT_S", "0.5")
    fleet = _FakeFleetStore({
        "w0": {"port": _dead_port(), "heartbeat": time.time()}})
    st = ts.global_trace_store()
    _complete(st, TID, attrs={"status": 500, "route": "/v1/classify"})
    doc = fed.assemble_trace(fleet, TID,
                             local_payload=st.get(TID),
                             local_worker="proxy")
    assert doc is not None and doc["partial"]
    assert "w0" in doc["scrape_errors"]
    assert doc["workers"] == ["proxy"]
    assert doc["spans"] and doc["waterfall"]
    code, payload = fed.handle_trace_route(
        f"/debug/trace/{TID}", {}, store=fleet, local_worker="proxy",
        fleet=True)
    assert code == 200 and payload["partial"]
    assert "w0" in payload["scrape_errors"]
    # recent fan-out degrades the same way
    code, payload = fed.handle_trace_route(
        "/debug/trace/recent", {}, store=fleet, local_worker="proxy",
        fleet=True)
    assert code == 200 and payload["partial"]
    assert any(t["trace_id"] == TID for t in payload["traces"])
    # chrome export of the partial doc still renders
    events = fed.assembled_chrome_trace(doc)
    assert any(ev.get("ph") == "X" for ev in events)


def test_trace_route_404_on_unknown_or_invalid_id():
    for path in ("/debug/trace/deadbeefdeadbeef",   # unknown, valid hex
                 "/debug/trace/nothex!!",           # invalid id
                 "/debug/trace/deadbeefdeadbeef/"):
        code, payload = fed.handle_trace_route(path, {})
        assert code == 404, path
        assert payload["error"] == "NotFound"
    code, _ = fed.handle_trace_route(
        "/debug/trace/deadbeefdeadbeef", {"format": ["chrome"]})
    assert code == 404
    code, _ = fed.handle_trace_route(
        "/debug/trace/deadbeefdeadbeef", {"local": ["1"]})
    assert code == 404


# ---------------------------------------------------------------------------
# kill switch: byte-identical pre-store behavior
# ---------------------------------------------------------------------------

def test_kill_switch_hooks_inert(monkeypatch):
    monkeypatch.setenv("DL4J_TPU_TRACE_STORE", "0")
    assert not ts.trace_store_enabled()
    ts.store_span_open(TID)
    ts.store_span_close(_rec(TID, attrs={"status": 500}))
    snap = ts.global_trace_store().snapshot()
    assert snap["traces"] == 0 and snap["pending"] == 0
    monkeypatch.setenv("DL4J_TPU_TRACE_STORE", "1")
    assert ts.trace_store_enabled()     # live re-read, no restart


def test_kill_switch_byte_identity_on_the_front_door(monkeypatch):
    """With DL4J_TPU_TRACE_STORE=0 the serving path is byte-identical
    to the pre-store code: root spans carry NO stamped status/tenant
    attrs, the store stays empty, and /debug/trace* is not routed
    (404).  Flipping it on stamps + retains + serves the same traffic."""
    monkeypatch.setenv("DL4J_TPU_TRACE_STORE", "0")
    fd, _ = _scoring_door(port=0)
    addr = fd.get_address()
    try:
        code, body_off, _ = _request(
            addr, "/v1/classify", {"inputs": [[0.0] * 4]},
            headers={fed.TRACE_HEADER: TID})
        assert code == 200
        hits = _wait_span("http_request", lambda r: r.trace_id == TID)
        assert hits and all("status" not in (r.attrs or {})
                            for r in hits)
        code, _, _ = _request(addr, "/debug/trace/recent")
        assert code == 404
        code, _, _ = _request(addr, f"/debug/trace/{TID}")
        assert code == 404
        assert ts.global_trace_store().snapshot()["traces"] == 0
    finally:
        fd.stop()

    monkeypatch.setenv("DL4J_TPU_TRACE_STORE", "1")
    monkeypatch.setenv("DL4J_TPU_TRACE_SAMPLE", "1.0")
    reset_global_trace_sink()
    ts.reset_global_trace_store()
    fd, _ = _scoring_door(port=0)
    addr = fd.get_address()
    try:
        code, body_on, _ = _request(
            addr, "/v1/classify", {"inputs": [[0.0] * 4]},
            headers={fed.TRACE_HEADER: TID})
        assert code == 200
        assert body_on == body_off      # the response itself never moves
        hits = _wait_span("http_request",
                          lambda r: r.trace_id == TID
                          and (r.attrs or {}).get("status") == 200)
        assert hits
        deadline = time.monotonic() + 3.0
        got = None
        while got is None and time.monotonic() < deadline:
            got = ts.global_trace_store().get(TID)
            if got is None:
                time.sleep(0.05)
        assert got is not None and got["reason"] == "head_sample"
        code, raw, _ = _request(addr, f"/debug/trace/{TID}")
        assert code == 200
        doc = json.loads(raw)
        assert doc["trace_id"] == TID and doc["waterfall"]
        code, _, _ = _request(addr, "/debug/trace/recent")
        assert code == 200
    finally:
        fd.stop()


def test_store_knobs_read_live(monkeypatch):
    monkeypatch.setenv("DL4J_TPU_TRACE_SAMPLE", "0.25")
    assert ts.sample_rate() == 0.25
    monkeypatch.setenv("DL4J_TPU_TRACE_SAMPLE", "7")      # clamped
    assert ts.sample_rate() == 1.0
    monkeypatch.setenv("DL4J_TPU_TRACE_TAIL_Q", "0.99")
    assert ts.tail_quantile() == 0.99
    monkeypatch.setenv("DL4J_TPU_TRACE_TAIL_Q", "junk")
    assert ts.tail_quantile() == ts.DEFAULT_TAIL_QUANTILE
    monkeypatch.setenv("DL4J_TPU_TRACE_STORE_BYTES", "1")  # floor
    assert ts.budget_bytes() == 64 << 10
    monkeypatch.delenv("DL4J_TPU_TRACE_STORE_BYTES")
    assert ts.budget_bytes() == ts.DEFAULT_BUDGET_BYTES


# ---------------------------------------------------------------------------
# the live 2-worker drill (subprocess; slow)
# ---------------------------------------------------------------------------

@pytest.mark.slow
def test_trace_intel_drill_live(tmp_path):
    out = tmp_path / "traceq.json"
    proc = subprocess.run(
        [sys.executable, os.path.join(_REPO, "benchmarks", "http_load.py"),
         "--trace-intel", "--state-dir", str(tmp_path / "fleet"),
         "--out", str(out)],
        env=dict(os.environ, JAX_PLATFORMS="cpu"),
        capture_output=True, text=True, timeout=240)
    assert proc.returncode == 0, proc.stdout + proc.stderr
    rec = json.loads(out.read_text())
    assert rec["ok_verdict"]
    assert rec["retention_coverage"] == 1.0
    assert rec["assembly_completeness"] == 1.0
    assert rec["postkill_coverage"] == 1.0
    assert rec["partial_never_5xx"] and rec["chrome_export_ok"]
    assert rec["head_sample_fraction"] <= 0.5
