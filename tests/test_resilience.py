"""Deterministic chaos suite for the resilience layer (PR 5).

Seeded faults at every injection point; deadline expiry under load;
breaker open→half-open→close; shed accounting; ResilientTrainer restores
and converges to the same params as an unfaulted run; quarantine skips
exactly the poisoned batch; kill switch ``DL4J_TPU_RESILIENCE=0``
restores the pre-resilience behavior.
"""
import gc
import json
import os
import threading
import time
import urllib.request
import zipfile

import numpy as np
import pytest

from deeplearning4j_tpu.data.dataset import DataSet
from deeplearning4j_tpu.data.iterators import ArrayDataSetIterator
from deeplearning4j_tpu.nn.conf.configuration import NeuralNetConfiguration
from deeplearning4j_tpu.nn.conf.layers import DenseLayer, OutputLayer
from deeplearning4j_tpu.nn.multilayer import MultiLayerNetwork
from deeplearning4j_tpu.observability import (global_registry,
                                              reset_global_registry)
from deeplearning4j_tpu.optim.updaters import Adam
from deeplearning4j_tpu.parallel.inference import (InferenceMode,
                                                   ParallelInference)
from deeplearning4j_tpu.resilience import faults
from deeplearning4j_tpu.resilience.policy import (CircuitBreaker,
                                                  CircuitOpenError,
                                                  CircuitOpenRule, Deadline,
                                                  DeadlineExceeded,
                                                  RestartBudgetExhausted,
                                                  RetryBudget, RetryPolicy,
                                                  ShedError, ShutdownError,
                                                  TransientError)
from deeplearning4j_tpu.resilience.recovery import (ResilientTrainer,
                                                    SkippingIterator,
                                                    newest_checkpoint)

_TYPED = (ShedError, DeadlineExceeded, ShutdownError, CircuitOpenError,
          faults.InjectedFault)


def _mlp_conf(seed=7):
    return (NeuralNetConfiguration.builder()
            .seed(seed).updater(Adam(1e-2)).list()
            .layer(DenseLayer(n_in=4, n_out=16, activation="relu"))
            .layer(OutputLayer(n_in=16, n_out=3, activation="softmax",
                               loss_function="mcxent"))
            .build())


def _data(n=32, seed=0):
    rng = np.random.RandomState(seed)
    x = rng.rand(n, 4).astype("f4")
    y = np.eye(3, dtype="f4")[rng.randint(0, 3, n)]
    return x, y


@pytest.fixture(autouse=True)
def _clean():
    faults.reset()
    reset_global_registry()
    yield
    faults.clear()


# ------------------------------------------------------------------- faults
def test_fault_spec_parsing_and_determinism():
    plan = faults.FaultPlan.parse(
        "train.step:crash:1.0:2, data.next_batch:nan:0.5")
    assert [(s.point, s.kind, s.rate, s.count) for s in plan.specs] == [
        ("train.step", "crash", 1.0, 2), ("data.next_batch", "nan", 0.5,
                                          None)]
    with pytest.raises(ValueError):
        faults.FaultSpec("nope.point", "error")
    with pytest.raises(ValueError):
        faults.FaultSpec("train.step", "segfault")
    with pytest.raises(ValueError):
        # nan only fires at points that own an array — accepting it at
        # e.g. allreduce would validate a chaos spec that never injects
        faults.FaultSpec("allreduce", "nan")
    # same seed + same call sequence => same draws
    def draws(seed):
        reg = faults.FaultRegistry()
        reg.install(faults.FaultPlan(
            [faults.FaultSpec("train.step", "error", rate=0.3)], seed=seed))
        out = []
        for _ in range(40):
            try:
                reg.check("train.step")
                out.append(0)
            except faults.InjectedFault:
                out.append(1)
        return out
    a, b, c = draws(5), draws(5), draws(6)
    assert a == b
    assert a != c           # different seed, different stream
    assert 1 in a and 0 in a


def test_injection_counts_points_and_kill_switch(monkeypatch):
    x, y = _data(16)
    it = ArrayDataSetIterator(x, y, 8)
    plan = faults.FaultPlan(
        [faults.FaultSpec("data.next_batch", "error", rate=1.0, count=1)])
    with faults.active(plan):
        with pytest.raises(faults.InjectedFault) as ei:
            for _ in it:
                pass
        assert ei.value.transient        # "error" kind is retryable
    counter = global_registry().get("dl4j_faults_injected_total")
    assert counter.labels(point="data.next_batch", kind="error").value == 1
    assert any(e["category"] == "fault_injected" for e in faults.events())
    # kill switch: same plan installed, nothing fires
    monkeypatch.setenv("DL4J_TPU_RESILIENCE", "0")
    with faults.active(plan):
        assert not faults.armed()
        it.reset()
        assert sum(1 for _ in it) == 2   # both batches, no injection


def test_latency_fault_and_env_spec(monkeypatch):
    monkeypatch.setenv("DL4J_TPU_FAULTS", "train.step:latency:1.0:1")
    net = MultiLayerNetwork(_mlp_conf()).init()
    x, y = _data(8)
    assert faults.armed()
    net.fit(DataSet(x, y))               # latency injects, then trains fine
    counter = global_registry().get("dl4j_faults_injected_total")
    assert counter.labels(point="train.step", kind="latency").value == 1
    # malformed spec: warn + inject nothing, never crash the fit
    monkeypatch.setenv("DL4J_TPU_FAULTS", "not a spec !!")
    net.fit(DataSet(x, y))


def test_nan_corruption_composes_with_numerics_skip(monkeypatch):
    monkeypatch.setenv("DL4J_TPU_NUMERICS_SKIP", "1")
    net = MultiLayerNetwork(_mlp_conf()).init()
    x, y = _data(8)
    net.fit(DataSet(x, y))               # warm trace with skip policy armed
    before = np.asarray(net.params()).copy()
    plan = faults.FaultPlan(
        [faults.FaultSpec("train.step", "nan", rate=1.0, count=1)])
    with faults.active(plan):
        net.fit(DataSet(x, y))           # poisoned batch -> in-graph skip
    after = np.asarray(net.params())
    assert np.array_equal(before, after), \
        "numerics skip must leave params untouched on the poisoned step"
    assert np.all(np.isfinite(after))
    counter = global_registry().get("dl4j_faults_injected_total")
    assert counter.labels(point="train.step", kind="nan").value == 1
    net.fit(DataSet(x, y))               # and training recovers
    assert np.all(np.isfinite(np.asarray(net.params())))


# ------------------------------------------------------------------- policy
def test_retry_policy_backoff_budget_and_transient_gate():
    calls = []

    def flaky():
        calls.append(1)
        if len(calls) < 3:
            raise TransientError("transient")
        return "ok"

    pol = RetryPolicy(max_retries=3, base_delay_seconds=0.001)
    assert pol.call(flaky, op="unit") == "ok"
    assert len(calls) == 3
    retries = global_registry().get("dl4j_resilience_retries_total")
    assert retries.labels(op="unit").value == 2
    # non-transient errors never retry
    calls.clear()

    def hard():
        calls.append(1)
        raise ValueError("real bug")

    with pytest.raises(ValueError):
        pol.call(hard, op="unit")
    assert len(calls) == 1
    # an empty budget surfaces transient failures immediately
    starved = RetryPolicy(max_retries=5, base_delay_seconds=0.001,
                          budget=RetryBudget(max_tokens=0.0))
    calls.clear()
    with pytest.raises(TransientError):
        starved.call(flaky, op="unit")
    assert len(calls) == 1


def test_deadline_and_circuit_breaker_unit():
    dl = Deadline.after_ms(1)
    time.sleep(0.005)
    assert dl.expired() and dl.remaining() < 0
    assert not Deadline.after(60).expired()

    br = CircuitBreaker("unit.op", failure_threshold=3,
                        reset_timeout_seconds=0.05, half_open_probes=1)
    try:
        assert br.allow()
        br.record_failure()
        br.record_failure()
        assert br.state_name() == "closed"
        br.record_failure()              # threshold -> open
        assert br.state_name() == "open"
        assert not br.allow()
        gauge = global_registry().get("dl4j_circuit_state")
        assert gauge.labels(op="unit.op").value == 2
        rule = CircuitOpenRule()
        assert rule.evaluate(global_registry())["status"] == "failing"
        time.sleep(0.06)                 # reset timeout -> half-open probes
        assert br.allow()                # the single probe passes
        assert br.state_name() == "half_open"
        assert not br.allow()            # probe budget spent
        assert rule.evaluate(global_registry())["status"] == "degraded"
        br.record_success()              # probe succeeded -> closed
        assert br.state_name() == "closed"
        assert br.allow()
        assert rule.evaluate(global_registry())["status"] == "ok"
        # a half-open probe failing re-opens immediately
        for _ in range(3):
            br.record_failure()
        time.sleep(0.06)
        assert br.allow()
        br.record_failure()
        assert br.state_name() == "open"
        transitions = [e for e in faults.events()
                       if e["category"] == "circuit"]
        assert [t["to_state"] for t in transitions[:4]] == [
            "open", "half_open", "closed", "open"]
        # a probe that dies a typed death (no success/failure recorded)
        # must not wedge the breaker half-open forever: probes replenish
        # on the reset cadence
        time.sleep(0.06)
        assert br.allow()                # probe consumed, outcome lost
        assert not br.allow()
        time.sleep(0.06)
        assert br.allow()                # replenished — liveness holds
    finally:
        br.retire()


def test_circuit_gauge_worst_state_wins_across_instances():
    """Two breakers on one op share the {op} gauge series: a fresh or
    retiring CLOSED instance must never mask another instance's OPEN
    circuit on /health."""
    a = CircuitBreaker("shared.op", failure_threshold=1,
                       reset_timeout_seconds=60)
    try:
        a.record_failure()
        gauge = global_registry().get("dl4j_circuit_state")
        assert gauge.labels(op="shared.op").value == 2
        b = CircuitBreaker("shared.op", failure_threshold=1,
                           reset_timeout_seconds=60)   # publishes at init
        assert gauge.labels(op="shared.op").value == 2, \
            "fresh CLOSED breaker clobbered the open one"
        b.retire()
        assert gauge.labels(op="shared.op").value == 2
    finally:
        a.retire()
    assert global_registry().get(
        "dl4j_circuit_state").labels(op="shared.op").value == 0


def test_a_breaker_collected_inside_the_registrys_lock_deadlocks_nothing():
    """The collector runs a dead breaker's finalizer at any bytecode of any
    thread, so also on a thread that is creating an instrument and holds
    the registry's lock (tier-1 of PR 28 stopped there, in
    ``test_sessions.py``, for 24 minutes). The finalizer takes no lock; the
    op's gauge is re-published when it is next read."""
    abandoned = CircuitBreaker("orphan.op", failure_threshold=1,
                               reset_timeout_seconds=60)
    abandoned.record_failure()
    assert CircuitOpenRule().evaluate(global_registry())["status"] \
        == "failing"
    retired = CircuitBreaker("retired.op")
    retired.retire()                   # what a pipeline's shutdown does
    abandoned.cycle, retired.cycle = abandoned, retired
    del abandoned, retired             # only the collector frees them now
    # a new registry re-publishes the breakers it still knows, not the
    # retired one: to re-publish that op is to create its gauge again
    reset_global_registry()
    registry = global_registry()
    collected = threading.Event()

    def collect_under_the_lock():
        with registry._lock:           # what ``registry.gauge()`` holds
            gc.collect()
        collected.set()

    t = threading.Thread(target=collect_under_the_lock, daemon=True)
    t.start()
    assert collected.wait(60.0), \
        "the finalizer waited for a lock its own thread holds"
    # an OPEN breaker that nobody retired no longer pins /health
    verdict = CircuitOpenRule().evaluate(global_registry())
    assert verdict["status"] == "ok", verdict


# ------------------------------------------------------------------ serving
def test_serving_deadline_sheds_and_never_hangs():
    net = MultiLayerNetwork(_mlp_conf()).init()
    x, _ = _data(8)

    class Slow:
        def output(self, xx):
            time.sleep(0.15)
            return net.output(xx)

    pi = (ParallelInference.Builder(Slow())
          .inference_mode(InferenceMode.BATCHED).batch_limit(8)
          .deadline_ms(10).build())
    try:
        with pytest.raises(DeadlineExceeded):
            pi.output(x[:2])
        shed = global_registry().get("dl4j_inference_shed_total")
        assert shed.labels(reason="deadline").value >= 1
        # an explicit generous per-request deadline overrides the default
        r = pi.output(x[:2], deadline_ms=30_000)
        assert r.shape[0] == 2
    finally:
        pi.shutdown()


def test_instant_mode_deadline_sheds_late_result():
    """INSTANT mode honors deadlines like BATCHED: a forward that finishes
    after the deadline is shed (late answer = wrong answer), not returned."""
    net = MultiLayerNetwork(_mlp_conf()).init()
    x, _ = _data(8)

    class Slow:
        def output(self, xx):
            time.sleep(0.15)
            return net.output(xx)

    pi = (ParallelInference.Builder(Slow())
          .inference_mode(InferenceMode.INSTANT).deadline_ms(10).build())
    try:
        m = global_registry().get("dl4j_inference_shed_total")
        before = m.labels(reason="deadline").value if m is not None else 0
        with pytest.raises(DeadlineExceeded):
            pi.output(x[:2])
        assert global_registry().get("dl4j_inference_shed_total").labels(
            reason="deadline").value == before + 1
        r = pi.output(x[:2], deadline_ms=30_000)
        assert r.shape[0] == 2
    finally:
        pi.shutdown()


def test_queue_shed_policies():
    net = MultiLayerNetwork(_mlp_conf()).init()
    x, _ = _data(32)
    release = threading.Event()

    class Gated:
        def output(self, xx):
            release.wait(timeout=10)
            return net.output(xx)

    for policy in ("reject_newest", "reject_oldest"):
        release.clear()
        pi = (ParallelInference.Builder(Gated())
              .inference_mode(InferenceMode.BATCHED).batch_limit(1)
              .max_queue_depth(1).shed_policy(policy).build())
        outcomes = []

        def call(i):
            try:
                pi.output(x[i:i + 1])
                outcomes.append("ok")
            except ShedError:
                outcomes.append("shed")
            except ShutdownError:
                outcomes.append("shutdown")

        threads = [threading.Thread(target=call, args=(i,))
                   for i in range(6)]
        try:
            for t in threads:
                t.start()
                time.sleep(0.02)     # deterministic-ish arrival order
            time.sleep(0.1)
            release.set()
            for t in threads:
                t.join(timeout=20)
            assert not any(t.is_alive() for t in threads), \
                f"caller hung under {policy}"
            assert "shed" in outcomes, (policy, outcomes)
            assert "ok" in outcomes, (policy, outcomes)
        finally:
            release.set()
            pi.shutdown()
        shed = global_registry().get("dl4j_inference_shed_total")
        assert shed.labels(reason="queue_full").value >= 1


def test_circuit_breaker_fails_fast_in_serving():
    class Boom:
        def output(self, xx):
            raise RuntimeError("device on fire")

    pi = (ParallelInference.Builder(Boom())
          .inference_mode(InferenceMode.BATCHED).batch_limit(4).build())
    pi._breaker = CircuitBreaker("inference.device_execute",
                                 failure_threshold=2,
                                 reset_timeout_seconds=60)
    x, _ = _data(8)
    seen = []
    try:
        for _ in range(5):
            try:
                pi.output(x[:1])
            except Exception as e:
                seen.append(type(e).__name__)
        assert seen[:2] == ["RuntimeError", "RuntimeError"]
        # breaker open: subsequent callers fail fast at the door
        assert set(seen[2:]) == {"CircuitOpenError"}
        shed = global_registry().get("dl4j_inference_shed_total")
        assert shed.labels(reason="circuit_open").value >= 3
        # fail-fast rejections still count as traffic: a 100% outage must
        # not read as "no requests, ok" to ErrorRateRule's gate
        reqs = global_registry().get("dl4j_inference_requests_total")
        assert reqs.labels(mode=InferenceMode.BATCHED).value == 5
    finally:
        pi.shutdown()
    # retire on shutdown publishes closed — /health must not stay failing
    assert CircuitOpenRule().evaluate(global_registry())["status"] == "ok"


def test_shutdown_error_is_typed():
    net = MultiLayerNetwork(_mlp_conf()).init()
    pi = (ParallelInference.Builder(net)
          .inference_mode(InferenceMode.BATCHED).batch_limit(4).build())
    pi.shutdown()
    x, _ = _data(4)
    with pytest.raises(ShutdownError):
        pi.output(x[:1])
    assert issubclass(ShutdownError, RuntimeError)   # old callers keep working


def test_chaos_serving_loses_no_nonexpired_request():
    """Seeded faults at both serving points + concurrent callers: every
    request resolves — a result, or a typed error — and nobody hangs."""
    net = MultiLayerNetwork(_mlp_conf()).init()
    x, _ = _data(64, seed=3)
    direct = np.asarray(net.output(x))
    plan = faults.FaultPlan([
        faults.FaultSpec("inference.dispatch", "error", rate=0.3, count=4),
        faults.FaultSpec("inference.device_execute", "error", rate=0.2,
                         count=3),
        faults.FaultSpec("inference.device_execute", "latency", rate=0.2,
                         count=3, latency_seconds=0.01),
    ], seed=11)
    pi = (ParallelInference.Builder(net)
          .inference_mode(InferenceMode.BATCHED)
          .batch_limit(8).queue_limit(8).build())
    results, failures, hung = {}, {}, []

    def call(off, n):
        try:
            results[off] = pi.output(x[off:off + n])
        except _TYPED as e:
            failures[off] = e
        except Exception as e:           # pragma: no cover
            hung.append(("unexpected", off, e))

    with faults.active(plan):
        threads, off = [], 0
        for n in [2, 3, 1, 2, 3, 2, 1, 3, 2, 2, 3, 2, 1, 2, 3, 2]:
            threads.append(threading.Thread(target=call, args=(off, n)))
            off += n
        sizes = {t: s for t, s in zip(threads,
                                      [2, 3, 1, 2, 3, 2, 1, 3, 2, 2, 3, 2,
                                       1, 2, 3, 2])}
        try:
            for t in threads:
                t.start()
            for t in threads:
                t.join(timeout=30)
            assert not any(t.is_alive() for t in threads), \
                "request hung under injected faults"
        finally:
            pi.shutdown()
    assert not hung, hung
    assert results, "every request failed — retries should save some"
    for off, r in results.items():
        n = r.shape[0]
        np.testing.assert_allclose(np.asarray(r), direct[off:off + n],
                                   atol=1e-5)
    # the injected transient dispatch faults were retried under the policy
    counter = global_registry().get("dl4j_faults_injected_total")
    assert counter.labels(point="inference.dispatch", kind="error").value \
        + counter.labels(point="inference.device_execute",
                         kind="error").value >= 1


# ----------------------------------------------------------------- recovery
def test_resilient_trainer_restores_to_unfaulted_params(tmp_path):
    x, y = _data(32)
    ref = MultiLayerNetwork(_mlp_conf()).init()
    ref.fit(ArrayDataSetIterator(x, y, 8), epochs=1)
    ref_params = np.asarray(ref.params())

    net = MultiLayerNetwork(_mlp_conf()).init()
    rt = ResilientTrainer(net, str(tmp_path), max_restarts=3)
    plan = faults.FaultPlan(
        [faults.FaultSpec("train.step", "crash", rate=1.0, count=1)],
        seed=1)
    epochs_before = global_registry().get(
        "dl4j_training_epochs_total").labels(model="MultiLayerNetwork").value
    with faults.active(plan):
        ret = rt.fit(ArrayDataSetIterator(x, y, 8), epochs=1)
    assert ret is net          # same return as the wrapped fit
    assert rt.restarts == 1
    np.testing.assert_allclose(np.asarray(net.params()), ref_params,
                               atol=0)
    assert global_registry().get("dl4j_training_epochs_total").labels(
        model="MultiLayerNetwork").value == epochs_before + 1
    # the restart budget is per fit() call, not per trainer lifetime
    rt.fit(ArrayDataSetIterator(x, y, 8), epochs=1)
    assert rt.restarts == 0
    assert global_registry().get(
        "dl4j_checkpoint_restores_total").value >= 1
    assert global_registry().get(
        "dl4j_training_step_failures_total").labels(
            model="MultiLayerNetwork").value == 1
    assert any(e["category"] == "restore" for e in faults.events())


def test_resilient_trainer_retries_transient_in_place(tmp_path):
    x, y = _data(32)
    ref = MultiLayerNetwork(_mlp_conf()).init()
    ref.fit(ArrayDataSetIterator(x, y, 8), epochs=1)

    net = MultiLayerNetwork(_mlp_conf()).init()
    rt = ResilientTrainer(net, str(tmp_path), max_restarts=0)
    plan = faults.FaultPlan(
        [faults.FaultSpec("train.step", "error", rate=1.0, count=2)],
        seed=1)
    with faults.active(plan):              # transient: no restore needed
        rt.fit(ArrayDataSetIterator(x, y, 8), epochs=1)
    assert rt.restarts == 0
    np.testing.assert_allclose(np.asarray(net.params()),
                               np.asarray(ref.params()), atol=0)


def test_transient_checkpoint_save_fault_never_double_applies(tmp_path):
    """A transient fault in the post-update tail (checkpoint.save fires in
    iteration_done, AFTER the param update landed) must not trigger an
    in-place re-run of the batch — that would apply the gradient twice."""
    x, y = _data(32)
    ref = MultiLayerNetwork(_mlp_conf()).init()
    ref.fit(ArrayDataSetIterator(x, y, 8), epochs=1)

    net = MultiLayerNetwork(_mlp_conf()).init()
    rt = ResilientTrainer(net, str(tmp_path), max_restarts=3)
    plan = faults.FaultPlan(
        [faults.FaultSpec("checkpoint.save", "error", rate=1.0, count=2)],
        seed=1)
    with faults.active(plan):
        rt.fit(ArrayDataSetIterator(x, y, 8), epochs=1)
    np.testing.assert_allclose(np.asarray(net.params()),
                               np.asarray(ref.params()), atol=0)
    assert net._iteration == 4


def test_post_update_nontransient_failure_blames_no_batch(tmp_path):
    """A non-transient failure AFTER the update landed (a failing
    listener — e.g. checkpoint save hitting a full disk) must take the
    restore path WITHOUT blaming the in-flight batch: quarantining it
    would silently drop healthy data from the replay."""
    from deeplearning4j_tpu.optim.listeners import TrainingListener

    x, y = _data(32)
    ref = MultiLayerNetwork(_mlp_conf()).init()
    ref.fit(ArrayDataSetIterator(x, y, 8), epochs=1)

    class FailOnce(TrainingListener):
        fired = False

        def iteration_done(self, model, iteration, epoch, score):
            if not self.fired and iteration >= 2:
                self.fired = True
                raise OSError("disk full")

    net = MultiLayerNetwork(_mlp_conf()).init()
    net.addListeners(FailOnce())
    # quarantine_after=1: any blame would quarantine the batch instantly
    # and drop it from the replay — byte-equality proves innocence
    rt = ResilientTrainer(net, str(tmp_path), max_restarts=3,
                          quarantine_after=1)
    rt.fit(ArrayDataSetIterator(x, y, 8), epochs=1)
    assert rt.restarts == 1
    np.testing.assert_allclose(np.asarray(net.params()),
                               np.asarray(ref.params()), atol=0)


def test_quarantine_skips_exactly_the_poisoned_batch(tmp_path):
    x, y = _data(32)
    # reference run: batches 1..3 only (batch 0 skipped)
    ref = MultiLayerNetwork(_mlp_conf()).init()
    for i in range(1, 4):
        ref.fit(DataSet(x[i * 8:(i + 1) * 8], y[i * 8:(i + 1) * 8]))

    net = MultiLayerNetwork(_mlp_conf()).init()
    rt = ResilientTrainer(net, str(tmp_path), max_restarts=5,
                          quarantine_after=2)
    plan = faults.FaultPlan(
        [faults.FaultSpec("train.step", "crash", rate=1.0, count=2)],
        seed=1)
    with faults.active(plan):
        rt.fit(ArrayDataSetIterator(x, y, 8), epochs=1)
    np.testing.assert_allclose(np.asarray(net.params()),
                               np.asarray(ref.params()), atol=0)
    assert global_registry().get("dl4j_data_quarantined_total").value == 1
    assert net._iteration == 3             # exactly the 3 clean batches


def test_restart_budget_exhausted(tmp_path):
    x, y = _data(16)
    net = MultiLayerNetwork(_mlp_conf()).init()
    rt = ResilientTrainer(net, str(tmp_path), max_restarts=2,
                          quarantine_after=99)
    plan = faults.FaultPlan(
        [faults.FaultSpec("train.step", "crash", rate=1.0)], seed=1)
    with faults.active(plan):
        with pytest.raises(RestartBudgetExhausted):
            rt.fit(ArrayDataSetIterator(x, y, 8), epochs=1)
    assert rt.restarts == 3                # budget + the exhausting attempt
    # the metric counts restarts PERFORMED — the exhausting attempt
    # restored nothing
    assert global_registry().get("dl4j_resilience_restarts_total").labels(
        model="MultiLayerNetwork").value == 2


def test_coarse_cadence_cross_epoch_restore_matches(tmp_path):
    """cadence > 1 with a crash in epoch 2: the epoch-boundary checkpoint
    keeps the restore from rewinding into epoch 1 (whose tail this
    epoch's replay loop could never reach) — params still match the
    fault-free run exactly."""
    x, y = _data(24)
    ref = MultiLayerNetwork(_mlp_conf()).init()
    ref.fit(ArrayDataSetIterator(x, y, 8), epochs=2)

    net = MultiLayerNetwork(_mlp_conf()).init()
    rt = ResilientTrainer(net, str(tmp_path), max_restarts=3,
                          checkpoint_every_iterations=2)
    # 3 batches/epoch at cadence 2: the newest cadence checkpoint after
    # epoch 1 is iteration 2 — only the boundary checkpoint holds iter 3.
    # Crash exactly on the 4th step attempt (= epoch 2's batch 0) by
    # patching the fit loop's fault hook — no FaultSpec is positional.
    import unittest.mock as mock

    from deeplearning4j_tpu.nn import multilayer as _ml
    calls = {"n": 0}

    def crash_on_fourth(point):
        if point == "train.step":
            calls["n"] += 1
            if calls["n"] == 4:
                raise faults.InjectedFault(point, "crash")

    with mock.patch.object(_ml._faults, "armed", return_value=True), \
            mock.patch.object(_ml._faults, "check",
                              side_effect=crash_on_fourth), \
            mock.patch.object(_ml._faults, "corrupt",
                              side_effect=lambda p, v: v):
        rt.fit(ArrayDataSetIterator(x, y, 8), epochs=2)
    assert rt.restarts == 1
    np.testing.assert_allclose(np.asarray(net.params()),
                               np.asarray(ref.params()), atol=0)
    assert net._iteration == ref._iteration == 6


def test_shuffled_iterator_replay_preserves_order(tmp_path):
    """A restore mid-epoch must replay the SAME shuffled order the
    interrupted pass used (reset_replay undoes the shuffle-epoch bump) —
    otherwise fast-forward skips a different permutation and examples get
    duplicated/omitted. Compared trainer-vs-trainer: the faulted run must
    be bit-identical to the fault-free one."""
    x, y = _data(32)
    a = MultiLayerNetwork(_mlp_conf()).init()
    ResilientTrainer(a, str(tmp_path / "a")).fit(
        ArrayDataSetIterator(x, y, 8, shuffle=True, seed=5), epochs=2)

    b = MultiLayerNetwork(_mlp_conf()).init()
    rt = ResilientTrainer(b, str(tmp_path / "b"), max_restarts=3)
    plan = faults.FaultPlan(
        [faults.FaultSpec("train.step", "crash", rate=1.0, count=1)],
        seed=1)
    with faults.active(plan):
        rt.fit(ArrayDataSetIterator(x, y, 8, shuffle=True, seed=5),
               epochs=2)
    assert rt.restarts == 1
    np.testing.assert_allclose(np.asarray(b.params()),
                               np.asarray(a.params()), atol=0)


def test_fit_surface_mirrors_wrapped_net(tmp_path):
    """fit(x, y) — valid on the wrapped net — must not misbind labels to
    epochs; non-iterator forms delegate through unchanged."""
    x, y = _data(8)
    net = MultiLayerNetwork(_mlp_conf()).init()
    rt = ResilientTrainer(net, str(tmp_path))
    rt.fit(x, y)
    assert net._iteration == 1


def test_resilient_trainer_kill_switch_delegates(tmp_path, monkeypatch):
    monkeypatch.setenv("DL4J_TPU_RESILIENCE", "0")
    x, y = _data(16)
    net = MultiLayerNetwork(_mlp_conf()).init()
    rt = ResilientTrainer(net, str(tmp_path), max_restarts=3)
    rt.fit(ArrayDataSetIterator(x, y, 8), epochs=1)
    assert os.listdir(str(tmp_path)) == []   # no checkpoints, no wrapping
    assert net._iteration == 2


def test_serving_kill_switch_restores_parking_behavior(monkeypatch):
    """DL4J_TPU_RESILIENCE=0: deadlines/shedding/breaker are inert — a
    tight deadline_ms on a slow model still returns a result, exactly the
    pre-resilience behavior."""
    monkeypatch.setenv("DL4J_TPU_RESILIENCE", "0")
    net = MultiLayerNetwork(_mlp_conf()).init()
    x, _ = _data(8)

    class Slow:
        def output(self, xx):
            time.sleep(0.05)
            return net.output(xx)

    pi = (ParallelInference.Builder(Slow())
          .inference_mode(InferenceMode.BATCHED).batch_limit(8)
          .deadline_ms(1).max_queue_depth(4).build())
    try:
        assert pi._breaker is None and pi._shed_policy is None
        # the bounded queue must not apply either: pre-resilience behavior
        # is the default-depth queue with producer parking
        assert pi._queue.maxsize == 64
        r = pi.output(x[:2], deadline_ms=1)
        assert r.shape[0] == 2           # deadline ignored: result, no shed
        shed = global_registry().get("dl4j_inference_shed_total")
        assert shed is None or all(c.value == 0 for _, c in shed.series())
    finally:
        pi.shutdown()


def test_newest_checkpoint_skips_torn_zip(tmp_path):
    net = MultiLayerNetwork(_mlp_conf()).init()
    good = str(tmp_path / "checkpoint_1_MultiLayerNetwork.zip")
    net.save(good)
    torn = str(tmp_path / "checkpoint_2_MultiLayerNetwork.zip")
    with open(torn, "wb") as f:
        f.write(b"PK\x03\x04 this is not a finished zip")
    os.utime(good, (time.time() - 60, time.time() - 60))
    assert newest_checkpoint(str(tmp_path)) == good


def test_skipping_iterator_positions():
    x, y = _data(32)
    it = SkippingIterator(ArrayDataSetIterator(x, y, 8), quarantine_after=1)
    seen = [it.position() for _ in iter(it)]
    assert seen == [0, 1, 2, 3]            # position() = last pulled index
    it.reset()
    assert it.position() == -1             # nothing pulled yet this epoch
    batches = list(iter(it))
    assert len(batches) == 4
    it.note_failure(2)                     # quarantine_after=1 -> instant
    it.reset()
    assert len(list(iter(it))) == 3
    assert it.quarantined() == [2]
    # a shuffling backing re-permutes per epoch: position-keyed quarantine
    # would name a DIFFERENT (healthy) batch next epoch, so reset() drops it
    sh = SkippingIterator(ArrayDataSetIterator(x, y, 8, shuffle=True),
                          quarantine_after=1)
    list(iter(sh))
    sh.note_failure(2)
    assert sh.quarantined() == [2]
    sh.reset_replay()                      # same-epoch replay keeps state
    assert sh.quarantined() == [2]
    sh.reset()                             # fresh epoch reshuffles
    assert sh.quarantined() == []


# -------------------------------------------------- preemption satellites
def test_preemption_checkpoint_newest_and_atomic(tmp_path):
    from deeplearning4j_tpu.utils.preemption import (PreemptionHandler,
                                                     PreemptionSafeListener,
                                                     TrainingPreempted,
                                                     find_final_checkpoint,
                                                     resume_or_new)
    d = str(tmp_path)
    # newest by mtime, not alphabetically-first
    older = os.path.join(d, "preempt_final_AAA.zip")
    newer = os.path.join(d, "preempt_final_ZZZ.zip")
    net = MultiLayerNetwork(_mlp_conf()).init()
    net.save(older)
    net.save(newer)
    past = time.time() - 120
    os.utime(older, (past, past))
    assert find_final_checkpoint(d) == newer
    # resume_or_new skips an unreadable newest and restores the next one
    os.remove(older)
    real = os.path.join(d, "preempt_final_MultiLayerNetwork.zip")
    net.fit(DataSet(*_data(8)))
    net.save(real)
    with open(newer, "wb") as f:
        f.write(b"corrupt")
    os.utime(real, (time.time() - 60, time.time() - 60))
    restored, resumed = resume_or_new(d, _mlp_conf)
    assert resumed and restored._iteration == net._iteration
    # a fully-unreadable directory degrades to a fresh net, not a crash
    with open(real, "wb") as f:
        f.write(b"also corrupt")
    fresh, resumed = resume_or_new(d, _mlp_conf)
    assert not resumed and fresh._iteration == 0
    # the preemption listener's write is tmp+rename: no .tmp survivors
    handler = PreemptionHandler()           # not installed: no real signals
    lst = PreemptionSafeListener(handler, d)
    handler.request_preemption()
    with pytest.raises(TrainingPreempted):
        lst.iteration_done(net, 7, 0, 0.5)
    assert not [n for n in os.listdir(d) if n.endswith(".tmp")]
    assert zipfile.is_zipfile(lst.checkpoint_path)


# ------------------------------------------------- snapshot / UI / bundles
def test_snapshot_debug_endpoint_and_bundle(tmp_path, monkeypatch):
    from deeplearning4j_tpu import resilience
    from deeplearning4j_tpu.observability.flight_recorder import (
        FlightRecorder)
    from deeplearning4j_tpu.ui.server import UIServer

    plan = faults.FaultPlan(
        [faults.FaultSpec("train.step", "latency", rate=1.0, count=1)])
    net = MultiLayerNetwork(_mlp_conf()).init()
    with faults.active(plan):
        net.fit(DataSet(*_data(8)))
        snap = resilience.snapshot()
        assert snap["enabled"]
        assert snap["faults"]["injected"] == {"train.step:latency": 1}
    assert any(e["category"] == "fault_injected"
               for e in resilience.snapshot()["events"])
    # /debug/resilience serves the same snapshot
    ui = UIServer(port=0).start()
    try:
        with urllib.request.urlopen(
                ui.get_address() + "/debug/resilience", timeout=10) as r:
            body = json.loads(r.read())
        assert body["enabled"] is True
        assert {"faults", "circuits", "events",
                "default_deadline_ms"} <= set(body)
    finally:
        ui.stop()
    # every postmortem bundle carries resilience.json
    rec = FlightRecorder(hang_seconds=1000, out_dir=str(tmp_path))
    bundle = rec.dump("unit-test")
    rec.stop()
    res = json.loads(open(os.path.join(bundle, "resilience.json")).read())
    assert "circuits" in res and "events" in res
    # async_runtime snapshot reports the resilience posture
    from deeplearning4j_tpu import async_runtime
    monkeypatch.setenv("DL4J_TPU_FAULTS", "allreduce:latency:0.1")
    s = async_runtime.snapshot()
    assert s["resilience_enabled"] is True
    assert s["fault_spec"] == "allreduce:latency:0.1"


def test_sharded_trainer_resilient_fit(tmp_path):
    import jax
    if len(jax.devices()) < 2:
        pytest.skip("needs the multi-device CPU mesh")
    from deeplearning4j_tpu.parallel.mesh import MeshSpec
    from deeplearning4j_tpu.parallel.trainer import ShardedTrainer

    x, y = _data(32)
    net = MultiLayerNetwork(_mlp_conf()).init()
    trainer = ShardedTrainer(net, MeshSpec.data_parallel(2),
                             devices=jax.devices()[:2])
    rt = ResilientTrainer(trainer, str(tmp_path), max_restarts=3)
    plan = faults.FaultPlan([
        faults.FaultSpec("allreduce", "error", rate=1.0, count=1),
        faults.FaultSpec("train.step", "crash", rate=1.0, count=1),
    ], seed=2)
    with faults.active(plan):
        rt.fit(ArrayDataSetIterator(x, y, 8), epochs=1)
    assert net._iteration == 4
    assert np.all(np.isfinite(np.asarray(net.params())))
    counter = global_registry().get("dl4j_faults_injected_total")
    assert counter.labels(point="allreduce", kind="error").value == 1
