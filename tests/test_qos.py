"""Multi-tenant QoS suite: quota buckets + refill, env/JSON policy
config, bounded tenant labels, DWRR weighted-share convergence (unit +
through the real serving pipeline), tenant-aware queue-full shedding,
priority preemption at decode step boundaries (resolves typed), the
front-door quota admission + Retry-After surface, the flooding-tenant
chaos drill (flooder + victims x faults x deadlines — every request
resolves typed-or-correct, victims hold, flooder sheds counted per
tenant), the DL4J_TPU_QOS=0 byte-identical kill switch, the
default-tenant passthrough, and the tenant-label cardinality lint rule.
"""
import json
import os
import sys
import threading
import time
import urllib.error
import urllib.request

import numpy as np
import pytest

from deeplearning4j_tpu.observability import (global_registry,
                                              reset_global_registry)
from deeplearning4j_tpu.parallel.inference import ParallelInference
from deeplearning4j_tpu.resilience import faults, qos
from deeplearning4j_tpu.resilience.policy import (DeadlineExceeded,
                                                  ShedError)

TOOLS = os.path.join(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))), "tools")
if TOOLS not in sys.path:
    sys.path.insert(0, TOOLS)


@pytest.fixture(autouse=True)
def _clean():
    faults.reset()
    reset_global_registry()
    qos.reset_global_tenants()
    yield
    faults.clear()
    ParallelInference.shutdown_all()
    qos.reset_global_tenants()


class StubModel:
    """Deterministic no-jit model: lets the serving pipeline run with
    controllable per-batch latency (fair-share tests need a backlog)."""

    def __init__(self, delay_s: float = 0.0):
        self.delay_s = delay_s

    def output(self, x):
        if self.delay_s:
            time.sleep(self.delay_s)
        return np.asarray(x) * 2.0


def _registry_with(policies, default=None):
    reg = qos.global_tenants()
    reg.configure(policies, default=default)
    return reg


# ---------------------------------------------------------------------------
# quotas
# ---------------------------------------------------------------------------

def test_token_bucket_quota_refill():
    reg = _registry_with({"t": qos.TenantPolicy(
        "t", request_rate=50.0, request_burst=2.0)})
    assert reg.admit("t") == "t"
    assert reg.admit("t") == "t"
    with pytest.raises(qos.QuotaExceeded) as ei:
        reg.admit("t")
    # the typed outcome is a ShedError (HTTP 429 at the door) and
    # carries the bucket refill time
    assert isinstance(ei.value, ShedError)
    assert ei.value.tenant == "t"
    assert 0.0 < ei.value.retry_after_s <= 0.1
    # quota sheds are counted per tenant
    assert reg.snapshot()["tenants"]["t"]["shed"] == 1
    # refill: at 50/s one token is back within ~20 ms
    time.sleep(0.06)
    assert reg.admit("t") == "t"


def test_token_rate_debt_model():
    reg = _registry_with({"g": qos.TenantPolicy(
        "g", token_rate=100.0, token_burst=10.0)})
    reg.admit("g")                       # balance 10 — fine
    reg.account_tokens("g", 200.0)       # usage overshoots into debt
    with pytest.raises(qos.QuotaExceeded) as ei:
        reg.admit("g")
    assert ei.value.quota == "token"
    assert ei.value.retry_after_s > 0.5  # 190 tokens of debt at 100/s
    snap = reg.snapshot()["tenants"]["g"]
    assert snap["over_quota"] and snap["tokens"] == 200.0


def test_tenant_config_env(monkeypatch, tmp_path):
    doc = {"default": {"weight": 2.0},
           "tenants": {"gold": {"weight": 4.0, "priority": 1,
                                "request_rate": 10.0}}}
    monkeypatch.setenv("DL4J_TPU_TENANT_CONFIG", json.dumps(doc))
    reg = qos.TenantRegistry()
    assert reg.policy("gold").weight == 4.0
    assert reg.priority("gold") == 1
    # unconfigured tenants inherit the default policy's knobs
    assert reg.policy("anon").weight == 2.0
    # file-path spelling
    p = tmp_path / "tenants.json"
    p.write_text(json.dumps(doc))
    monkeypatch.setenv("DL4J_TPU_TENANT_CONFIG", str(p))
    assert qos.TenantRegistry().policy("gold").weight == 4.0
    # alien policy keys are a config error, not a silent default
    monkeypatch.setenv("DL4J_TPU_TENANT_CONFIG",
                       json.dumps({"tenants": {"x": {"wieght": 2}}}))
    with pytest.raises(ValueError):
        qos.TenantRegistry()


def test_tenant_label_bounded(monkeypatch):
    monkeypatch.setenv("DL4J_TPU_TENANT_TOP_N", "3")
    reg = _registry_with({"vip": qos.TenantPolicy("vip")})
    labels = {reg.tenant_label(f"anon{i}") for i in range(20)}
    own = labels - {qos.OVERFLOW_TENANT}
    assert len(own) == 3 and qos.OVERFLOW_TENANT in labels
    # configured tenants and the default always keep their own label,
    # even past the top-N
    assert reg.tenant_label("vip") == "vip"
    assert reg.tenant_label(None) == qos.DEFAULT_TENANT
    # the mapping is sticky: the same name always maps the same way
    assert reg.tenant_label("anon0") == reg.tenant_label("anon0")


# ---------------------------------------------------------------------------
# fair queue
# ---------------------------------------------------------------------------

class _Req:
    def __init__(self, tenant, n=1):
        self.tenant = tenant
        self.n = n


def test_fair_queue_weighted_share_and_priority():
    reg = _registry_with({"a": qos.TenantPolicy("a", weight=3.0),
                          "b": qos.TenantPolicy("b", weight=1.0)})
    fq = qos.FairQueue(1000, reg, cost_fn=lambda r: r.n)
    for _ in range(200):
        fq.put_nowait(_Req("a"))
        fq.put_nowait(_Req("b"))
    first = [fq.get_nowait().tenant for _ in range(100)]
    # DRR converges to the exact weight ratio while both are backlogged
    assert first.count("a") == 75 and first.count("b") == 25
    # a higher priority tier always pops first
    reg.configure({"hi": qos.TenantPolicy("hi", priority=2)})
    fq.put_nowait(_Req("hi"))
    assert fq.peek_priority() == 2
    assert fq.get_nowait().tenant == "hi"


def test_fair_queue_pick_victim_tenant_aware():
    reg = _registry_with({"a": qos.TenantPolicy("a"),
                          "b": qos.TenantPolicy("b")})
    fq = qos.FairQueue(10, reg, cost_fn=lambda r: r.n)
    for _ in range(9):
        fq.put_nowait(_Req("flood"))
    fq.put_nowait(_Req("b"))
    # an under-share arrival evicts from the over-share tenant
    v = fq.pick_victim(_Req("a"))
    assert v is not None and v.tenant == "flood"
    assert fq.qsize() == 9
    # the flooding tenant arriving at its own full queue sheds ITSELF
    assert fq.pick_victim(_Req("flood")) is None
    assert fq.qsize() == 9            # nothing evicted
    # the under-share tenant is never the victim
    sizes = fq.tenant_sizes()
    assert sizes.get("b") == 1


def test_weighted_share_convergence_through_serving():
    """The integration pin: two backlogged tenants at weight 3:1 see
    ~3:1 service through the REAL batcher pipeline."""
    _registry_with({"a": qos.TenantPolicy("a", weight=3.0),
                    "b": qos.TenantPolicy("b", weight=1.0)})
    pi = ParallelInference(StubModel(delay_s=0.005), batch_limit=4,
                           queue_limit=256, max_wait_ms=1.0)
    completions = []
    done_lock = threading.Lock()

    def one(tenant):
        pi.output(np.ones((1, 3), "f4"), tenant=tenant)
        with done_lock:
            completions.append(tenant)

    threads = [threading.Thread(target=one, args=(t,), daemon=True)
               for t in ["a"] * 48 + ["b"] * 48]
    for t in threads:
        t.start()
    for t in threads:
        t.join(timeout=60.0)
    assert len(completions) == 96
    first = completions[:40]
    a, b = first.count("a"), first.count("b")
    # while both are backlogged, service tracks the 3:1 weights (loose
    # bound: thread scheduling jitters the enqueue order, but the DWRR
    # pop dominates; FIFO would give ~1:1)
    assert a / max(b, 1) >= 1.8, (a, b)
    pi.shutdown()


def test_tenant_aware_queue_full_shed():
    """A flooding tenant's arrivals shed ITS OWN work; an under-share
    victim's requests always get through."""
    _registry_with({"victim": qos.TenantPolicy("victim"),
                    "flood": qos.TenantPolicy("flood")})
    pi = ParallelInference(StubModel(delay_s=0.02), batch_limit=2,
                           max_queue_depth=8, max_wait_ms=1.0)
    outcomes = {"victim": [], "flood": []}
    lock = threading.Lock()

    def one(tenant):
        try:
            pi.output(np.ones((1, 3), "f4"), tenant=tenant)
            out = "ok"
        except ShedError:
            out = "shed"
        with lock:
            outcomes[tenant].append(out)

    flood = [threading.Thread(target=one, args=("flood",), daemon=True)
             for _ in range(30)]
    for t in flood:
        t.start()
    time.sleep(0.05)                   # flood backlog fills the queue
    # the victims stay UNDER their fair share (3 concurrent in an
    # 8-deep queue at equal weights) — the property under test is that
    # under-share work is never the eviction victim
    victims = [threading.Thread(target=one, args=("victim",),
                                daemon=True) for _ in range(3)]
    for t in victims:
        t.start()
    for t in flood + victims:
        t.join(timeout=60.0)
    assert len(outcomes["victim"]) == 3 and len(outcomes["flood"]) == 30
    assert outcomes["victim"].count("shed") == 0, outcomes["victim"]
    assert outcomes["flood"].count("shed") > 0
    # per-tenant shed accounting followed the evictions
    snap = qos.global_tenants().snapshot()["tenants"]
    assert snap["flood"]["shed"] > 0
    assert snap.get("victim", {}).get("shed", 0) == 0
    pi.shutdown()


def test_pick_victim_quota_state_never_trumps_share():
    """A quota-limited but UNDER-share tenant must not be scored above
    the actual flooder (quota state is a tie-break among over-share
    tenants, never the primary key) — and the innocent arrival must
    not be shed in its place."""
    reg = _registry_with({"paid": qos.TenantPolicy(
        "paid", request_rate=1.0, request_burst=1.0)})
    reg.admit("paid")                    # drain the bucket: over quota
    assert reg.over_quota("paid")
    fq = qos.FairQueue(32, reg, cost_fn=lambda r: r.n)
    fq.put_nowait(_Req("paid"))          # 1 request: far under share
    for _ in range(30):
        fq.put_nowait(_Req("flood"))
    v = fq.pick_victim(_Req("victim"))
    assert v is not None and v.tenant == "flood"
    assert fq.tenant_sizes().get("paid") == 1


def test_reject_oldest_single_tenant_keeps_policy_meaning():
    """Under QoS, a single-tenant (default) full queue with
    reject_oldest must still evict the stale OLDEST and admit the
    fresh arrival — not silently degrade to reject-newest."""
    reg = qos.global_tenants()
    fq = qos.FairQueue(3, reg, cost_fn=lambda r: 1)
    reqs = [_Req(qos.DEFAULT_TENANT) for _ in range(3)]
    for r in reqs:
        fq.put_nowait(r)
    assert fq.pick_victim(_Req(qos.DEFAULT_TENANT)) is None
    evicted = fq.pop_oldest_of(qos.DEFAULT_TENANT)
    assert evicted is reqs[0]            # the oldest, not the newest
    fq.put_nowait(_Req(qos.DEFAULT_TENANT))  # arrival now fits
    assert fq.qsize() == 3


def test_fair_queue_internals_stay_bounded_and_fast():
    """Drained tenants leave every FairQueue dict (an id-spraying
    caller can't grow queue internals); a head whose cost is many
    quanta pops via the bulk grant, not one-quantum-per-wrap spins."""
    reg = _registry_with({"w": qos.TenantPolicy("w", weight=0.1)})
    fq = qos.FairQueue(2000, reg, cost_fn=lambda r: r.n)
    for i in range(500):
        fq.put_nowait(_Req(f"spray{i}"))
        assert fq.get_nowait() is not None
    assert len(fq._queues) == 0 and len(fq._deficit) == 0
    assert len(fq._tcost) == 0 and len(fq._pv_cache) == 0
    # 512-cost head at weight 0.1 = ~5120 quanta needed: the bulk
    # grant makes this a handful of loop iterations, not thousands
    fq.put_nowait(_Req("w", 512))
    t0 = time.perf_counter()
    assert fq.get_nowait().n == 512
    assert time.perf_counter() - t0 < 0.05


def test_reject_oldest_exact_share_admits_new_tenant():
    """Every tenant exactly at its fair share + a brand-new arrival
    under reject_oldest: the global-oldest fallback must displace the
    stalest head — the most underserved newcomer never bounces."""
    reg = qos.global_tenants()
    fq = qos.FairQueue(3, reg, cost_fn=lambda r: 1)
    olds = [_Req(f"t{i}") for i in range(3)]
    for i, r in enumerate(olds):
        r.t_enqueue_us = 1000.0 + i
        fq.put_nowait(r)
    assert fq.pick_victim(_Req("newcomer")) is None     # nobody over
    assert fq.pop_oldest_of("newcomer") is None          # no backlog
    evicted = fq.pop_global_oldest()
    assert evicted is olds[0]                # the stalest head goes
    fq.put_nowait(_Req("newcomer"))
    assert fq.qsize() == 3


def test_zero_rate_policy_refused():
    with pytest.raises(ValueError):
        qos.TenantPolicy("x", request_rate=0)
    with pytest.raises(ValueError):
        qos.TenantPolicy("x", token_rate=-1.0)


def test_admit_token_debt_does_not_drain_request_bucket():
    """A tenant waiting out token debt must not ALSO burn its
    request-rate tokens on each (paced) retry."""
    reg = _registry_with({"g": qos.TenantPolicy(
        "g", request_rate=10.0, request_burst=3.0,
        token_rate=10.0, token_burst=5.0)})
    reg.admit("g")
    reg.account_tokens("g", 1e6)             # deep token debt
    for _ in range(5):
        with pytest.raises(qos.QuotaExceeded) as ei:
            reg.admit("g")
        assert ei.value.quota == "token"
    # the request bucket kept its tokens through the debt rejections
    snap = reg.snapshot()["tenants"]["g"]
    assert snap["request_bucket_level"] >= 2.0


def test_tenant_state_growth_is_bounded(monkeypatch):
    """An id-spraying caller must not grow the registry's state/label
    tables (and with them /debug/tenants and tenants.json) without
    bound: past the tracking cap fresh names share ONE overflow row."""
    monkeypatch.setenv("DL4J_TPU_TENANT_TOP_N", "4")
    reg = qos.TenantRegistry(load_env=False)
    cap = reg._max_tracked()
    for i in range(cap + 200):
        name = f"spray{i}"
        reg.observe_request(name, 0.001)
        reg.tenant_label(name)
    snap = reg.snapshot()
    assert len(snap["tenants"]) <= cap + 2      # + default/overflow
    assert len(reg._labels) <= cap
    # the overflow row absorbed the tail and kept counting
    assert snap["tenants"][qos.OVERFLOW_TENANT]["requests"] >= 199


# ---------------------------------------------------------------------------
# kill switch / default tenant
# ---------------------------------------------------------------------------

def test_kill_switch_byte_identical(monkeypatch):
    import queue as _stdlib_queue
    monkeypatch.setenv("DL4J_TPU_QOS", "0")
    _registry_with({"flood": qos.TenantPolicy(
        "flood", request_rate=0.001, request_burst=1.0)})
    pi = ParallelInference(StubModel(), batch_limit=4)
    # the pre-QoS FIFO queue, not a FairQueue
    assert type(pi._queue) is _stdlib_queue.Queue
    assert pi._qos is False
    # the tenant kwarg is inert — no quota, no tenant series
    for _ in range(3):
        out = pi.output(np.ones((2, 3), "f4"), tenant="flood")
        assert out.shape == (2, 3)
    pi.shutdown()
    for name in ("dl4j_tenant_requests_total", "dl4j_tenant_shed_total",
                 "dl4j_tenant_tokens_total",
                 "dl4j_tenant_cost_flops_total"):
        assert global_registry().get(name) is None, name


def test_default_tenant_passthrough():
    """Unlabeled traffic under the QoS posture rides the default tenant:
    never shed, counted under 'default'."""
    pi = ParallelInference(StubModel(), batch_limit=4)
    assert pi._qos is True
    for _ in range(4):
        pi.output(np.ones((1, 3), "f4"))        # no tenant given
    pi.shutdown()
    snap = qos.global_tenants().snapshot()["tenants"]
    assert snap[qos.DEFAULT_TENANT]["requests"] == 4
    assert snap[qos.DEFAULT_TENANT]["shed"] == 0
    inst = global_registry().get("dl4j_tenant_requests_total")
    assert inst is not None
    series = {lv[0]: c.value for lv, c in inst.series()}
    assert series.get(qos.DEFAULT_TENANT) == 4


# ---------------------------------------------------------------------------
# generation: preemption
# ---------------------------------------------------------------------------

@pytest.fixture(scope="module")
def gen_engine():
    import jax

    from deeplearning4j_tpu.models.generation import DecodeEngine
    from deeplearning4j_tpu.models.transformer import (TransformerConfig,
                                                       TransformerLM)
    cfg = TransformerConfig(vocab_size=61, n_layers=2, n_heads=2,
                            d_model=32, max_len=64)
    m = TransformerLM(cfg)
    return DecodeEngine(m, m.init_params(jax.random.key(0)), max_len=48)


def test_preemption_resolves_typed(gen_engine):
    """slots=1: a long low-tier generation is preempted by a higher-
    tier tenant at a step boundary — the victim resolves with the typed
    PreemptedError (never hangs), the winner completes, and the shed is
    counted per tenant with reason=preempted."""
    from deeplearning4j_tpu.parallel.generation import GenerationPipeline
    _registry_with({"low": qos.TenantPolicy("low", priority=0),
                    "hi": qos.TenantPolicy("hi", priority=2)})
    gp = GenerationPipeline(gen_engine, slots=1, max_new_tokens=40)
    results = {}

    def low():
        try:
            results["low"] = gp.generate([3, 1, 4], max_new_tokens=40,
                                         tenant="low")
        except BaseException as e:
            results["low"] = e

    t = threading.Thread(target=low, daemon=True)
    t.start()
    # let the low-tier request own the slot for a few decode steps
    deadline = time.monotonic() + 20
    while gp._n_active() == 0 and time.monotonic() < deadline:
        time.sleep(0.01)
    assert gp._n_active() == 1
    out = gp.generate([5, 9, 2], max_new_tokens=4, tenant="hi")
    assert out.shape[0] >= 1             # the winner generated
    t.join(timeout=30.0)
    assert not t.is_alive()              # the victim never hangs
    assert isinstance(results["low"], qos.PreemptedError)
    snap = qos.global_tenants().snapshot()["tenants"]
    assert snap["low"]["shed"] >= 1
    shed = global_registry().get("dl4j_decode_shed_total")
    series = {lv: c.value for lv, c in shed.series()}
    assert series.get(("preempted",), 0) >= 1
    gp.shutdown()


def test_equal_tiers_never_preempt(gen_engine):
    """Default priority (0 everywhere) must never preempt: a queued
    request waits for the slot instead of stealing it."""
    from deeplearning4j_tpu.parallel.generation import GenerationPipeline
    gp = GenerationPipeline(gen_engine, slots=1, max_new_tokens=8)
    r1 = {}

    def first():
        r1["out"] = gp.generate([3, 1, 4], max_new_tokens=8,
                                tenant="t1")

    t = threading.Thread(target=first, daemon=True)
    t.start()
    out2 = gp.generate([5, 9, 2], max_new_tokens=4, tenant="t2")
    t.join(timeout=30.0)
    assert isinstance(r1["out"], np.ndarray) and len(r1["out"]) == 8
    assert len(out2) == 4
    gp.shutdown()


def test_charge_path_midstream_death_exact_once_replay_zero(gen_engine):
    """Charge-path satellite: a generation that dies typed mid-stream
    after partial decode charges token debt for the tokens ACTUALLY
    emitted, exactly once — and an idempotent retry of an executed key
    replays the outcome and charges ZERO (per-tenant counters pinned
    under retry)."""
    from deeplearning4j_tpu.parallel.generation import GenerationPipeline
    from deeplearning4j_tpu.serving import (FrontDoor, ModelRegistry,
                                            ServingRouter)
    from deeplearning4j_tpu.serving import idempotency as idem
    idem.reset_global_journal()
    # token_rate must be > 0; 1e-3/s makes refill negligible so the
    # bucket level pins the exact debt charged
    treg = _registry_with({"t1": qos.TenantPolicy(
        "t1", token_rate=1e-3, token_burst=1000.0)})
    gp = GenerationPipeline(gen_engine, slots=1, max_new_tokens=24)
    emitted = []

    def cancel_after_3(tok, idx):
        emitted.append(int(tok))
        return len(emitted) < 3

    with pytest.raises(ShedError):           # typed StreamCancelled
        gp.generate([3, 1, 4, 1, 5], max_new_tokens=24,
                    on_token=cancel_after_3, tenant="t1")
    time.sleep(0.1)
    n = len(emitted)
    assert n >= 3
    inst = global_registry().get("dl4j_tenant_tokens_total")
    series = {lv[0]: c.value for lv, c in inst.series()}
    assert series.get("t1") == float(n)      # exactly once, exactly n
    st = treg.snapshot()["tenants"]["t1"]
    assert st["tokens"] == float(n)
    assert st["token_bucket_level"] == pytest.approx(1000.0 - n,
                                                     abs=0.1)
    gp.shutdown()
    # --- and through the front door, pinned under RETRY ---
    reg = ModelRegistry()
    reg.deploy_generative("g1", gen_engine, slots=2, max_new_tokens=16)
    fd = FrontDoor(gen_router=ServingRouter(reg, "g1"), port=0).start()
    try:
        addr = fd.get_address()
        doc = {"prompt": [3, 1, 4], "max_new_tokens": 5}
        code, body, _ = _post(addr, "/v1/generate", doc, tenant="t1",
                              idem_key="C1")
        assert code == 200 and len(body["tokens"]) == 5
        series = {lv[0]: c.value for lv, c
                  in global_registry().get(
                      "dl4j_tenant_tokens_total").series()}
        assert series.get("t1") == float(n + 5)
        req_series = {lv[0]: c.value for lv, c
                      in global_registry().get(
                          "dl4j_tenant_requests_total").series()}
        # the retry replays: same tokens, ZERO further charge, and the
        # per-tenant request/token counters do not move
        code2, body2, headers2 = _post(addr, "/v1/generate", doc,
                                       tenant="t1", idem_key="C1")
        assert code2 == 200 and body2["tokens"] == body["tokens"]
        assert headers2.get("X-Dl4j-Idempotent-Replay") == "1"
        after_tok = {lv[0]: c.value for lv, c
                     in global_registry().get(
                         "dl4j_tenant_tokens_total").series()}
        after_req = {lv[0]: c.value for lv, c
                     in global_registry().get(
                         "dl4j_tenant_requests_total").series()}
        assert after_tok.get("t1") == float(n + 5)   # charged ZERO more
        assert after_req == req_series
        st = treg.snapshot()["tenants"]["t1"]
        assert st["token_bucket_level"] == pytest.approx(
            1000.0 - n - 5, abs=0.1)
    finally:
        fd.stop()
        reg.shutdown()
        idem.reset_global_journal()


# ---------------------------------------------------------------------------
# the flooding-tenant chaos drill
# ---------------------------------------------------------------------------

def test_flooding_tenant_chaos_drill():
    """Flooder at 10x its quota + 2 victims, error+latency faults on
    the device path, per-request deadlines: every request resolves
    exactly once typed-or-correct (no hangs), the victims' goodput
    holds (>= 90% ok; quota sheds: zero), and the flooder's sheds are
    counted per tenant."""
    _registry_with({"v1": qos.TenantPolicy("v1", weight=2.0),
                    "v2": qos.TenantPolicy("v2", weight=1.0),
                    "flood": qos.TenantPolicy("flood")})
    plan = faults.FaultPlan.parse(
        "inference.device_execute:error:0.02,"
        "inference.dispatch:latency:0.05", seed=7)
    faults.install(plan)
    pi = ParallelInference(StubModel(delay_s=0.003), batch_limit=4,
                           max_queue_depth=16, max_wait_ms=1.0)
    outcomes = {"v1": [], "v2": [], "flood": []}
    lock = threading.Lock()

    def one(tenant, dl_ms):
        try:
            pi.output(np.ones((1, 3), "f4"), deadline_ms=dl_ms,
                      tenant=tenant)
            out = "ok"
        except (ShedError, DeadlineExceeded) as e:
            out = type(e).__name__
        except faults.InjectedFault:
            out = "fault"
        with lock:
            outcomes[tenant].append(out)

    def victim_stream(tenant):
        # victims are steady, paced, within-quota callers (4 workers x
        # 10 sequential requests each) — the flood is 160 simultaneous
        # one-shot threads slamming the same queue
        for _ in range(10):
            one(tenant, 5000)
            time.sleep(0.002)

    threads = []
    for _ in range(4):
        threads.append(threading.Thread(
            target=victim_stream, args=("v1",), daemon=True))
        threads.append(threading.Thread(
            target=victim_stream, args=("v2",), daemon=True))
    # the flood arrives at once whatever the box: threads started one by
    # one on a loaded core trickle in slower than the batcher drains them,
    # the queue never fills and nobody is shed
    flood_gate = threading.Barrier(160)

    def flood():
        flood_gate.wait(timeout=60.0)
        one("flood", 2000)

    for _ in range(160):
        threads.append(threading.Thread(target=flood, daemon=True))
    for t in threads:
        t.start()
    for t in threads:
        t.join(timeout=120.0)
    alive = [t for t in threads if t.is_alive()]
    assert not alive                    # nobody hangs — all resolved
    assert len(outcomes["v1"]) == 40 and len(outcomes["v2"]) == 40
    assert len(outcomes["flood"]) == 160
    for v in ("v1", "v2"):
        ok = outcomes[v].count("ok")
        # victims hold: typed-or-correct only, goodput >= 90% (the low
        # fault rates eat the rest; queue_full sheds land on the flood)
        assert ok >= 36, (v, outcomes[v])
        assert all(o in ("ok", "fault", "ShedError", "DeadlineExceeded")
                   for o in outcomes[v])
    # the flooder was shed, and per tenant
    assert outcomes["flood"].count("ShedError") > 0
    snap = qos.global_tenants().snapshot()["tenants"]
    assert snap["flood"]["shed"] > 0
    for v in ("v1", "v2"):
        assert snap[v]["requests"] == 40     # exactly-once accounting
    assert snap["flood"]["requests"] == 160
    pi.shutdown()
    faults.clear()


# ---------------------------------------------------------------------------
# lint
# ---------------------------------------------------------------------------

def test_metric_lint_tenant_label_rule():
    from check_metric_names import check_source

    # a raw request string bound to the tenant label is a violation
    bad = 'c.labels(tenant=request_header_value).inc()'
    assert len(check_source(bad, path="somewhere.py")) == 1
    # literals and the bounded helper pass, in both spellings
    good = ('c.labels(tenant="fixed").inc()\n'
            'c.labels(tenant=tenant_label(t)).inc()\n'
            'c.labels(tenant=qos.tenant_label(t)).inc()\n')
    assert check_source(good, path="somewhere.py") == []
    # the helper's home module binds pre-bounded label variables
    assert check_source('c.labels(tenant=label)',
                        path="deeplearning4j_tpu/resilience/qos.py") == []
    # (the whole-package sweep under this rule runs once from
    # test_obs_causal's lint test — not duplicated here)


# ---------------------------------------------------------------------------
# front door: quota admission, Retry-After, /debug/tenants
# ---------------------------------------------------------------------------

def _post(addr, path, doc, tenant=None, timeout=30.0, idem_key=None):
    headers = {"Content-Type": "application/json"}
    if tenant is not None:
        headers["X-Dl4j-Tenant"] = tenant
    if idem_key is not None:
        headers["X-Dl4j-Idempotency-Key"] = idem_key
    req = urllib.request.Request(
        addr + path, data=json.dumps(doc).encode(), headers=headers)
    try:
        with urllib.request.urlopen(req, timeout=timeout) as r:
            return r.status, json.loads(r.read()), dict(r.headers)
    except urllib.error.HTTPError as e:
        return e.code, json.loads(e.read() or b"{}"), dict(e.headers)


@pytest.fixture()
def front_door():
    from deeplearning4j_tpu.serving import FrontDoor, ModelRegistry
    from deeplearning4j_tpu.serving import ServingRouter

    class Wrap(StubModel):
        pass

    reg = ModelRegistry()
    reg.deploy("v1", Wrap(), warmup=False, batch_limit=4,
               max_wait_ms=1.0)
    router = ServingRouter(reg, "v1")
    fd = FrontDoor(router, None, port=0).start()
    yield fd
    fd.stop()
    reg.shutdown()


def test_front_door_quota_and_retry_after(front_door, monkeypatch):
    _registry_with({"flood": qos.TenantPolicy(
        "flood", request_rate=2.0, request_burst=2.0)})
    addr = front_door.get_address()
    doc = {"inputs": [[0.1, 0.2, 0.3]]}
    # default tenant: no quota, passes
    st, _, _ = _post(addr, "/v1/classify", doc)
    assert st == 200
    # the flooder's burst admits, then 429 + Retry-After (refill time)
    codes = [_post(addr, "/v1/classify", doc, tenant="flood")[0]
             for _ in range(4)]
    assert codes[:2] == [200, 200] and 429 in codes
    st, body, headers = _post(addr, "/v1/classify", doc, tenant="flood")
    assert st == 429
    assert body["error"] == "QuotaExceeded"
    assert headers.get("Retry-After") is not None
    assert int(headers["Retry-After"]) >= 1
    assert 0.0 < body["retry_after_s"] <= 1.0    # 2/s bucket
    # /debug/tenants names the posture + the shed counts
    with urllib.request.urlopen(addr + "/debug/tenants",
                                timeout=10.0) as r:
        snap = json.loads(r.read())
    assert snap["enabled"] is True
    assert snap["tenants"]["flood"]["shed"] >= 1
    assert snap["tenants"]["flood"]["over_quota"] is True
    # kill switch, flipped LIVE: the same flooder admits freely
    monkeypatch.setenv("DL4J_TPU_QOS", "0")
    st, _, _ = _post(addr, "/v1/classify", doc, tenant="flood")
    assert st == 200


def test_front_door_inflight_shed_carries_retry_after(front_door):
    front_door.max_inflight = 0          # everything sheds at the gate
    addr = front_door.get_address()
    st, body, headers = _post(addr, "/v1/classify",
                              {"inputs": [[0.1, 0.2, 0.3]]})
    assert st == 429
    assert headers.get("Retry-After") == "1"
    assert body["retry_after_s"] == 1.0
    front_door.max_inflight = 64
