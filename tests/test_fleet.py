"""Fleet-grade serving robustness suite: lease-fenced leadership (a
stale leader's write LOSES instead of landing, demotion counted at
write time, terms strictly monotonic, no lowest-id flap-back), clock
hardening (backward wall-clock jumps read as fresh), shared-store
corruption recovery (schema/digest validation, quarantine-aside,
rebuild from worker re-registration + history replay), the bounded
store-lock wait, the store.read/store.write fault points, the
idempotent-retry result journal (replay returns the original outcome,
attaches to in-flight, charges nothing, executes nothing), the
``/debug/fleet`` surfaces, and the kill switches
(``DL4J_TPU_FLEET_FENCE=0`` / ``DL4J_TPU_IDEMPOTENCY=0`` = byte-
identical pre-PR behavior). The 3-worker chaos drill is ``slow``
(tier-1 budget: in-process twins only).
"""
import json
import os
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
from deeplearning4j_tpu.serving import idempotency as idem
from deeplearning4j_tpu.serving import shared_state as ss
from deeplearning4j_tpu.serving.errors import StoreLockTimeout

_REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


def _make_net(seed=1):
    conf = (NeuralNetConfiguration.builder()
            .seed(seed).updater(Adam(1e-2)).list()
            .layer(DenseLayer(n_in=4, n_out=8, activation="relu"))
            .layer(OutputLayer(n_in=8, n_out=3, activation="softmax",
                               loss_function="mcxent"))
            .build())
    return MultiLayerNetwork(conf).init()


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
        cfg = TransformerConfig(vocab_size=61, n_layers=2, n_heads=2,
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
    idem.reset_global_journal()
    yield
    faults.clear()
    GenerationPipeline.shutdown_all()


def _post(addr, path, doc, timeout=30.0, idem_key=None):
    headers = {"Content-Type": "application/json"}
    if idem_key is not None:
        headers[idem.IDEMPOTENCY_HEADER] = idem_key
    req = urllib.request.Request(
        addr + path, data=json.dumps(doc).encode(), headers=headers)
    try:
        with urllib.request.urlopen(req, timeout=timeout) as r:
            return r.status, json.loads(r.read()), dict(r.headers)
    except urllib.error.HTTPError as e:
        return e.code, json.loads(e.read() or b"{}"), dict(e.headers)


def _get(addr, path, timeout=10.0):
    with urllib.request.urlopen(addr + path, timeout=timeout) as r:
        return r.status, json.loads(r.read())


def _sse(addr, doc, idem_key=None, timeout=60.0):
    headers = {"Content-Type": "application/json"}
    if idem_key is not None:
        headers[idem.IDEMPOTENCY_HEADER] = idem_key
    req = urllib.request.Request(
        addr + "/v1/generate",
        data=json.dumps(dict(doc, stream=True)).encode(), headers=headers)
    toks, done, rheaders = [], None, None
    with urllib.request.urlopen(req, timeout=timeout) as r:
        rheaders = dict(r.headers)
        ev = None
        for line in r:
            line = line.decode().rstrip("\n")
            if line.startswith("event: "):
                ev = line[7:]
            elif line.startswith("data: "):
                data = json.loads(line[6:])
                if ev == "token":
                    toks.append(data["token"])
                elif ev == "done":
                    done = data
    return toks, done, rheaders


def _series(name):
    inst = global_registry().get(name)
    if inst is None:
        return None
    if hasattr(inst, "series"):
        return {lv: c.value for lv, c in inst.series()}
    return inst.value


# ---------------------------------------------------------------------------
# lease-fenced leadership
# ---------------------------------------------------------------------------

def test_lease_moves_on_expiry_and_never_flaps_back(tmp_path):
    store = SharedStore(str(tmp_path / "fleet"))
    w0 = SharedServingState(store, "w0")
    w1 = SharedServingState(store, "w1")
    w0.register(111, 8001)
    w1.register(222, 8002)
    w0.sync()
    w1.sync()
    doc = store.read()
    assert doc["leader"] == {"worker": "w0", "term": 1,
                             "since": pytest.approx(doc["leader"]["since"])}
    assert w0.is_leader and w0.leader_term == 1 and not w1.is_leader
    # w0 pauses past TTL (simulated: its heartbeat goes stale)
    store.update(lambda d: d["workers"]["w0"].update(
        heartbeat=time.time() - 10.0))
    w1.sync()
    assert store.read()["leader"] == {
        "worker": "w1", "term": 2,
        "since": store.read()["leader"]["since"]}
    # w0 wakes: the lease does NOT flap back to the lowest id — w1
    # holds a fresh lease; w0 demotes AT WRITE TIME, counted
    w0.sync()
    led = store.read()["leader"]
    assert led["worker"] == "w1" and led["term"] == 2
    assert not w0.is_leader and w0.leader_term is None
    assert w0.snapshot()["fence"]["demotions"] == 1
    assert global_registry().get("dl4j_fleet_demotions_total").value == 1
    assert global_registry().get("dl4j_fleet_leader_term").value == 2.0
    assert any(e["category"] == "leader_demoted"
               for e in faults.events())


def test_stale_leader_fenced_write_loses(tmp_path):
    """The heart of the fence: a demoted ex-leader syncing with a due,
    fully-sampled window must NOT close it or advance the stage — its
    leader-only write loses; the real leader's next beat advances under
    ITS term, and every history event's term is monotonic."""
    store = SharedStore(str(tmp_path / "fleet"))
    w0 = SharedServingState(store, "w0")
    w1 = SharedServingState(store, "w1")
    w0.register(111, 8001)
    w1.register(222, 8002)
    w0.ensure_lane("scoring", "v1")
    w0.sync()
    w1.sync()
    assert w0.is_leader
    w0.begin_rollout("scoring", "v2", {
        "window_seconds": 0.01, "window_min_requests": 4,
        "healthy_windows": 1, "canary_fraction": 0.5,
        "ramp_fractions": [], "min_latency_n": 99})
    # demote w0 while it still believes it leads
    store.update(lambda d: d["workers"]["w0"].update(
        heartbeat=time.time() - 10.0))
    w1.sync()                      # w1 acquires term 2 (no samples yet)
    assert w1.is_leader
    time.sleep(0.05)               # window due
    for _ in range(6):
        w0.record("v2", ok=True, latency_s=0.001)
        w0.record("v1", ok=True, latency_s=0.001)
    w0.sync()                      # flushes counters; fenced write LOSES
    doc = store.read()
    ro = doc["lanes"]["scoring"]["rollout"]
    assert ro["stage"] == ss.CANARY          # w0 did not advance it
    assert all(e.get("term") != 1 or e["to"] == "canary"
               for e in doc["history"])
    # the real leader advances under term 2
    time.sleep(0.05)
    w1.sync()
    doc = store.read()
    assert doc["lanes"]["scoring"]["primary"] == "v2"
    full = [e for e in doc["history"] if e["to"] == "full"]
    assert full and full[-1]["term"] == 2
    terms = [e["term"] for e in doc["history"] if e.get("term") is not None]
    assert terms == sorted(terms)


def test_stage_monotonicity_guard_blocks_backward_moves(tmp_path):
    store = SharedStore(str(tmp_path / "fleet"))
    w0 = SharedServingState(store, "w0")
    doc = {"lanes": {}}
    ro = {"stage": ss.FULL, "ramp_idx": 1}
    assert not w0._guard_stage(doc, "scoring", ro, ss.RAMP, 0)
    assert not w0._guard_stage(doc, "scoring", ro, ss.CANARY)
    assert w0._guard_stage(doc, "scoring", ro, ss.ROLLED_BACK)
    ro = {"stage": ss.RAMP, "ramp_idx": 1}
    assert not w0._guard_stage(doc, "scoring", ro, ss.RAMP, 0)
    assert w0._guard_stage(doc, "scoring", ro, ss.RAMP, 2)
    assert w0._guard_stage(doc, "scoring", ro, ss.FULL)
    blocked = [e for e in faults.events()
               if e["category"] == "stage_regression_blocked"]
    assert len(blocked) == 3


def test_clock_regression_reads_fresh_never_dead(tmp_path, monkeypatch):
    """Satellite: heartbeat/window ages clamp negative deltas to 0 — a
    backward wall-clock jump must read as 'fresh', never as instant
    leader death or an instantly-closed window."""
    store = SharedStore(str(tmp_path / "fleet"))
    w0 = SharedServingState(store, "w0")
    w1 = SharedServingState(store, "w1")
    w0.register(111, 8001)
    w1.register(222, 8002)
    w0.sync()
    assert w0.is_leader and w0.leader_term == 1
    real_now = time.time()
    # the wall clock jumps BACKWARD by 100 s on every worker
    monkeypatch.setattr(ss, "_now", lambda: real_now - 100.0)
    assert ss._age(real_now - 100.0, real_now) == 0.0
    # w0's lease reads fresh: w1 must not steal it, nobody reads dead
    w1.sync()
    led = store.read()["leader"]
    assert led["worker"] == "w0" and led["term"] == 1
    assert set(w1.alive_workers()) == {"w0", "w1"}
    # and a due-window computation reads age 0, not instantly closed:
    w0.ensure_lane("scoring", "v1")
    w0.begin_rollout("scoring", "v2", {
        "window_seconds": 5.0, "window_min_requests": 1,
        "healthy_windows": 1, "ramp_fractions": []})
    for _ in range(4):
        w0.record("v2", ok=True, latency_s=0.001)
        w0.record("v1", ok=True, latency_s=0.001)
    w0.sync()
    assert (store.read()["lanes"]["scoring"]["rollout"]["stage"]
            == ss.CANARY)


# ---------------------------------------------------------------------------
# store corruption + recovery
# ---------------------------------------------------------------------------

def test_corrupt_doc_quarantined_and_rebuilt(tmp_path):
    d = str(tmp_path / "fleet")
    store = SharedStore(d)
    w0 = SharedServingState(store, "w0")
    w0.register(111, 8001)
    w0.ensure_lane("scoring", "v1")
    w0.sync()
    w0.begin_rollout("scoring", "v2", {
        "window_seconds": 99.0, "window_min_requests": 1,
        "healthy_windows": 1})
    hseq_before = store.read()["hseq"]
    # disk fault: the document becomes garbage
    with open(os.path.join(d, "state.json"), "w") as f:
        f.write('{"rev": "garbage", "lanes": [')
    w0.sync()
    doc = store.read()
    # quarantined ASIDE (never deleted), counted, and rebuilt: the lane,
    # its active rollout, the history, and the worker's registration
    # (pid/port) all survive
    aside = [fn for fn in os.listdir(d)
             if fn.startswith("state.json.corrupt.")]
    assert len(aside) == 1
    assert global_registry().get(
        "dl4j_fleet_store_corruptions_total").value >= 1
    assert doc["lanes"]["scoring"]["primary"] == "v1"
    ro = doc["lanes"]["scoring"]["rollout"]
    assert ro["candidate"] == "v2" and ro["active"]
    assert ro["window_base"] == {}           # re-baselined at zero
    assert doc["hseq"] == hseq_before
    assert [e["to"] for e in doc["history"]][-1] == "canary"
    assert doc["workers"]["w0"]["port"] == 8001      # re-registration
    assert doc["rebuilt"]["by"] == "w0"
    assert w0.snapshot()["fence"]["rebuilds"] == 1
    cats = [e["category"] for e in faults.events()]
    assert "store_corruption" in cats and "store_rebuilt" in cats
    # schema violations quarantine too (parseable but wrong shapes)
    with open(os.path.join(d, "state.json"), "w") as f:
        json.dump({"rev": 1, "workers": ["not", "a", "dict"]}, f)
    assert store.read()["rev"] == 0
    # digest mismatch = bit rot: quarantined as well
    good = store.update(lambda doc_: None)
    raw = json.loads(open(os.path.join(d, "state.json")).read())
    raw["stamp"] = 12345.0                   # silent partial edit
    with open(os.path.join(d, "state.json"), "w") as f:
        json.dump(raw, f)
    assert store.read()["rev"] == 0
    assert good["digest"] != ""


def test_store_lock_wait_is_bounded_and_typed(tmp_path):
    import fcntl
    d = str(tmp_path / "fleet")
    store = SharedStore(d, lock_timeout_s=0.3)
    store.update(lambda doc: None)
    fd = os.open(os.path.join(d, ".state.lock"), os.O_CREAT | os.O_RDWR)
    fcntl.flock(fd, fcntl.LOCK_EX)           # a writer wedged mid-commit
    try:
        t0 = time.monotonic()
        with pytest.raises(StoreLockTimeout):
            store.update(lambda doc: None)
        assert time.monotonic() - t0 < 5.0   # bounded, not forever
    finally:
        fcntl.flock(fd, fcntl.LOCK_UN)
        os.close(fd)
    store.update(lambda doc: None)           # heals once released


def test_store_fault_points_routing_falls_back_sync_retries(tmp_path):
    store = SharedStore(str(tmp_path / "fleet"))
    w0 = SharedServingState(store, "w0", routing_ttl_s=0.0)
    w0.register(111, 8001)
    w0.ensure_lane("scoring", "v1")
    w0.sync()
    assert w0.routing("scoring")["primary"] == "v1"
    # store.read faults: live routing serves the cached view instead of
    # failing traffic
    with faults.active(faults.FaultPlan(
            [faults.FaultSpec("store.read", "error", rate=1.0)])):
        assert w0.routing("scoring")["primary"] == "v1"
    # store.write faults: sync raises typed-or-injected and merges its
    # popped window counters back — nothing is lost, the next beat
    # flushes them
    w0.record("v1", ok=True, latency_s=0.001)
    with faults.active(faults.FaultPlan(
            [faults.FaultSpec("store.write", "error", rate=1.0)])):
        with pytest.raises(faults.InjectedFault):
            w0.sync()
    w0.sync()
    agg = store.read()["windows"]["w0"]["v1"]
    assert agg["n"] == 1


# ---------------------------------------------------------------------------
# idempotency journal
# ---------------------------------------------------------------------------

def test_result_journal_ttl_cap_attach_and_abandon():
    j = idem.ResultJournal(ttl_s=0.2, max_entries=16)
    e, state = j.begin("a")
    assert state == idem.NEW
    j.mark_executing("a")
    j.resolve("a", 200, {"x": 1})
    e2, state = j.begin("a")
    assert state == idem.DONE and e2 is e
    assert j.await_outcome(e2) == (200, {"x": 1})
    # attach-while-inflight: a second caller blocks until resolution
    e3, state = j.begin("b")
    assert state == idem.NEW
    got = {}

    def attach():
        entry, st = j.begin("b")
        assert st == idem.INFLIGHT
        got["outcome"] = j.await_outcome(entry, timeout_s=10.0)

    t = threading.Thread(target=attach, daemon=True)
    t.start()
    time.sleep(0.05)
    j.resolve("b", 200, {"y": 2})
    t.join(timeout=10.0)
    assert got["outcome"] == (200, {"y": 2})
    # abandon: the key is forgotten — a retry re-begins as NEW
    e4, _ = j.begin("c")
    j.abandon("c")
    _, state = j.begin("c")
    assert state == idem.NEW
    # TTL: resolved entries expire
    time.sleep(0.25)
    _, state = j.begin("a")
    assert state == idem.NEW
    # cap: oldest RESOLVED evicted first, in-flight never
    j2 = idem.ResultJournal(ttl_s=60.0, max_entries=16)
    for i in range(16):
        j2.begin(f"k{i}")
        if i < 8:
            j2.resolve(f"k{i}", 200, {})
    j2.begin("overflow")                     # evicts a resolved entry
    snap = j2.snapshot()
    assert snap["size"] == 16
    inflight = [k for k, v in snap["entries"].items()
                if v["state"] == idem.INFLIGHT]
    assert len(inflight) == 9                # none of the 8 inflight died
    # saturated with inflight: served untracked, counted
    j3 = idem.ResultJournal(ttl_s=60.0, max_entries=16)
    for i in range(16):
        j3.begin(f"k{i}")
    e, state = j3.begin("past-cap")
    assert e is None and state == idem.NEW
    assert j3.snapshot()["untracked"] == 1


def test_frontdoor_idempotent_replay_executes_once(tmp_path):
    reg = ModelRegistry()
    reg.deploy("v1", _net(), sample_input=_SAMPLE, batch_limit=4,
               max_wait_ms=1.0)
    fd = FrontDoor(ServingRouter(reg, "v1"), port=0).start()
    try:
        addr = fd.get_address()
        body = {"inputs": [[0.1, 0.2, 0.3, 0.4]]}
        c1, p1, h1 = _post(addr, "/v1/classify", body, idem_key="K1")
        assert c1 == 200 and idem.REPLAY_HEADER not in h1
        before = _series("dl4j_serving_version_requests_total")
        c2, p2, h2 = _post(addr, "/v1/classify", body, idem_key="K1")
        assert c2 == 200 and p2["outputs"] == p1["outputs"]
        assert h2.get(idem.REPLAY_HEADER) == "1"
        # NOTHING re-executed: per-version requests unchanged
        assert _series("dl4j_serving_version_requests_total") == before
        assert global_registry().get(
            "dl4j_fleet_idempotent_replays_total").value == 1
        snap = idem.snapshot()
        assert snap["entries"]["K1"]["executions"] == 1
        assert snap["duplicate_executions"] == 0
        # an executed ERROR outcome replays too (no double work)
        with faults.active(faults.FaultPlan([faults.FaultSpec(
                "inference.device_execute", "error", rate=1.0,
                count=1)])):
            c3, p3, _ = _post(addr, "/v1/classify", body, idem_key="K2")
        assert c3 == 500
        c4, p4, h4 = _post(addr, "/v1/classify", body, idem_key="K2")
        assert (c4, p4["error"]) == (c3, p3["error"])
        assert h4.get(idem.REPLAY_HEADER) == "1"
        # a PRE-execution rejection abandons: the retry gets a real
        # attempt (inflight gate shed → 429, then a clean 200)
        fd2 = FrontDoor(ServingRouter(reg, "v1"), port=0,
                        max_inflight=0).start()
        try:
            c5, _, _ = _post(fd2.get_address(), "/v1/classify", body,
                             idem_key="K3")
            assert c5 == 429
        finally:
            fd2.stop()
        c6, _, _ = _post(addr, "/v1/classify", body, idem_key="K3")
        assert c6 == 200
        # keyless traffic is untouched
        c7, _, h7 = _post(addr, "/v1/classify", body)
        assert c7 == 200 and idem.REPLAY_HEADER not in h7
    finally:
        fd.stop()
        reg.shutdown()


def test_frontdoor_idempotent_replay_streams_same_tokens():
    reg = ModelRegistry()
    reg.deploy_generative("g1", _engine(), slots=2, max_new_tokens=16)
    fd = FrontDoor(gen_router=ServingRouter(reg, "g1"), port=0).start()
    try:
        addr = fd.get_address()
        doc = {"prompt": [3, 1, 4, 1, 5], "max_new_tokens": 8}
        toks, done, h = _sse(addr, doc, idem_key="S1")
        assert len(toks) == 8 and done["tokens"] == toks
        assert idem.REPLAY_HEADER not in h
        before = _series("dl4j_decode_requests_total")
        # stream replay: the SAME token events, from the journal
        toks2, done2, h2 = _sse(addr, doc, idem_key="S1")
        assert toks2 == toks and done2["tokens"] == toks
        assert h2.get(idem.REPLAY_HEADER) == "1"
        assert _series("dl4j_decode_requests_total") == before
        # and a non-stream retry of the same key replays the outcome too
        c3, p3, h3 = _post(addr, "/v1/generate", doc, idem_key="S1")
        assert c3 == 200 and p3["tokens"] == toks
        assert h3.get(idem.REPLAY_HEADER) == "1"
    finally:
        fd.stop()
        reg.shutdown()


# ---------------------------------------------------------------------------
# surfaces + kill switches
# ---------------------------------------------------------------------------

def test_debug_fleet_surfaces(tmp_path):
    reg = ModelRegistry()
    reg.deploy("v1", _net(), sample_input=_SAMPLE, batch_limit=4,
               max_wait_ms=1.0)
    store = SharedStore(str(tmp_path / "fleet"))
    shared = SharedServingState(store, "w0")
    shared.ensure_lane("scoring", "v1")
    fd = FrontDoor(ServingRouter(reg, "v1"), shared=shared,
                   port=0).start()
    try:
        shared.register(os.getpid(), fd.port)
        fd.sync_once()
        _post(fd.get_address(), "/v1/classify",
              {"inputs": [[0.0] * 4]}, idem_key="D1")
        code, fleet = _get(fd.get_address(), "/debug/fleet")
        assert code == 200
        assert fleet["fence_enabled"] is True
        assert fleet["idempotency"]["entries"]["D1"]["executions"] == 1
        shared_view = fleet["frontdoors"][0]["shared"]
        assert shared_view["fence"]["leader"]["worker"] == "w0"
        assert shared_view["fence"]["leader"]["term"] == 1
        # the UI server mirrors the surface
        from deeplearning4j_tpu.ui.server import UIServer
        ui = UIServer(port=0).start()
        try:
            code, payload = _get(ui.get_address(), "/debug/fleet")
            assert code == 200 and "idempotency" in payload
        finally:
            ui.stop()
    finally:
        fd.stop()
        reg.shutdown()


def test_kill_switches_restore_pre_pr_behavior(tmp_path, monkeypatch):
    """DL4J_TPU_FLEET_FENCE=0 = unfenced lowest-alive-id semantics (no
    leader record, no term stamps, no fleet leadership series);
    DL4J_TPU_IDEMPOTENCY=0 = the key header is inert (re-executes), no
    journal, no replay series."""
    monkeypatch.setenv("DL4J_TPU_FLEET_FENCE", "0")
    store = SharedStore(str(tmp_path / "fleet"))
    w0 = SharedServingState(store, "w0")
    w1 = SharedServingState(store, "w1")
    w0.register(111, 8001)
    w1.register(222, 8002)
    w0.ensure_lane("scoring", "v1")
    w0.sync()
    w1.sync()
    doc = store.read()
    assert "leader" not in doc
    assert w0.is_leader and w0.leader_term is None
    # pre-fence flapping semantics: lowest ALIVE id leads, instantly
    store.update(lambda d: d["workers"]["w0"].update(
        heartbeat=time.time() - 10.0))
    w1.sync()
    assert w1.is_leader
    w0.sync()
    assert w0.is_leader                      # flaps straight back
    # history events carry no term/manual stamps
    w0.begin_rollout("scoring", "v2", {"window_seconds": 99.0})
    assert all("term" not in e and "manual" not in e
               for e in store.read()["history"])
    assert _series("dl4j_fleet_leader_term") is None
    assert _series("dl4j_fleet_demotions_total") is None
    monkeypatch.delenv("DL4J_TPU_FLEET_FENCE")

    monkeypatch.setenv("DL4J_TPU_IDEMPOTENCY", "0")
    reg = ModelRegistry()
    reg.deploy("v1", _net(), sample_input=_SAMPLE, batch_limit=4,
               max_wait_ms=1.0)
    fd = FrontDoor(ServingRouter(reg, "v1"), port=0).start()
    try:
        addr = fd.get_address()
        body = {"inputs": [[0.0] * 4]}
        before = _series("dl4j_serving_version_requests_total") or {}
        _post(addr, "/v1/classify", body, idem_key="K1")
        c, _, h = _post(addr, "/v1/classify", body, idem_key="K1")
        assert c == 200 and idem.REPLAY_HEADER not in h
        after = _series("dl4j_serving_version_requests_total")
        assert (sum(after.values())
                == sum(before.values()) + 2)   # both executed
        assert _series("dl4j_fleet_idempotent_replays_total") is None
        assert idem.snapshot()["entries"] == {}
    finally:
        fd.stop()
        reg.shutdown()


# ---------------------------------------------------------------------------
# the 3-worker chaos drill (slow: multi-process, ~1 min of load)
# ---------------------------------------------------------------------------

@pytest.mark.slow
def test_fleet_chaos_drill_end_to_end(tmp_path):
    """The acceptance drill: 3 workers under seeded load while the
    drill SIGSTOPs the leader past TTL, SIGKILLs a worker mid-stream,
    corrupts the store doc once, and injects store faults throughout.
    Graded: goodput >= 90%, zero duplicate executions, strictly
    monotonic leader terms, rollout stage never regresses."""
    out = tmp_path / "fleet.json"
    r = subprocess.run(
        [sys.executable,
         os.path.join(_REPO, "benchmarks", "http_load.py"),
         "--fleet-chaos", "--qps", "10", "--duration-s", "24",
         "--state-dir", str(tmp_path / "fleet"), "--out", str(out)],
        capture_output=True, text=True, timeout=240,
        env=dict(os.environ, JAX_PLATFORMS="cpu"))
    assert r.returncode == 0, r.stdout + r.stderr
    rec = json.loads(out.read_text())
    assert rec["goodput_ratio"] >= 0.90
    assert rec["duplicate_executions"] == 0
    assert rec["terms_monotonic"] is True
    assert rec["stage_regressed"] is False
    assert rec["demotions"] >= 1             # the woken leader demoted
    assert rec["corruptions"] >= 1           # the doc was quarantined
    assert rec["respawned"] is True


# ---------------------------------------------------------------------------
# one process for each chip: the fleet parent never initializes a backend

_FLEET_PARENT = r"""
import json, os, sys, tempfile, urllib.error, urllib.request
sys.path.insert(0, os.path.join(sys.argv[1], "tools"))
import serve                                   # what run_fleet's parent runs
import deeplearning4j_tpu, deeplearning4j_tpu.serving
import deeplearning4j_tpu.models.generation, deeplearning4j_tpu.models.transformer
import deeplearning4j_tpu.parallel.generation
from deeplearning4j_tpu.observability.federation import FleetAdminServer
from deeplearning4j_tpu.observability.flight_recorder import FlightRecorder
from deeplearning4j_tpu.serving import SharedStore

state = tempfile.mkdtemp()
serve._ProxyMetrics.get()
admin = FleetAdminServer(SharedStore(state), host="127.0.0.1", port=0,
                         local_worker="proxy").start()
codes = {}
for path in ("/metrics", "/metrics/fleet", "/health/fleet", "/alerts/fleet",
             "/debug/proxy", "/debug/alerts", "/debug/timeseries",
             "/debug/trace"):
    try:
        with urllib.request.urlopen(admin.get_address() + path,
                                    timeout=20) as r:
            codes[path] = r.status
    except urllib.error.HTTPError as e:
        codes[path] = e.code
admin.stop()
# an incident fan-out makes every process that sees it dump a bundle
bundle = FlightRecorder(out_dir=state).dump("incident:drill")
from jax._src import xla_bridge
print(json.dumps({"backends_initialized":
                  xla_bridge.backends_are_initialized(),
                  "codes": codes, "bundle": sorted(os.listdir(bundle))}))
"""


def test_fleet_parent_never_initializes_a_jax_backend():
    """A chip belongs to one process. The ``tools/serve.py`` parent imports
    the package, serves the admin routes and may dump an incident bundle —
    none of which may touch ``jax.devices()``, or the proxy would take a
    chip from a worker."""
    r = subprocess.run([sys.executable, "-c", _FLEET_PARENT, _REPO],
                       capture_output=True, text=True, timeout=120,
                       env=dict(os.environ, PYTHONPATH=_REPO))
    assert r.returncode == 0, r.stdout + r.stderr
    rec = json.loads(r.stdout.strip().splitlines()[-1])
    assert rec["backends_initialized"] is False
    assert rec["codes"]["/metrics"] == 200
    assert "config.json" in rec["bundle"]       # the dump did run
