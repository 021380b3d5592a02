"""Zero-downtime serving suite: registry deploys with AOT warmup, canary
rollout with SLO-gated auto-rollback, graceful drain under chaos, the
persistent compile cache, and the ``DL4J_TPU_ROLLOUT=0`` kill switch.
"""
import itertools
import json
import os
import subprocess
import sys
import threading
import time
import types
import urllib.request

import numpy as np
import pytest

from deeplearning4j_tpu import serving
from deeplearning4j_tpu.nn.conf.configuration import NeuralNetConfiguration
from deeplearning4j_tpu.nn.conf.layers import DenseLayer, OutputLayer
from deeplearning4j_tpu.nn.multilayer import MultiLayerNetwork
from deeplearning4j_tpu.observability import (compile_watch,
                                              global_registry,
                                              reset_global_registry)
from deeplearning4j_tpu.observability.flight_recorder import FlightRecorder
from deeplearning4j_tpu.optim.updaters import Adam
from deeplearning4j_tpu.resilience import faults
from deeplearning4j_tpu.resilience.faults import InjectedFault
from deeplearning4j_tpu.resilience.policy import (DeadlineExceeded, ShedError,
                                                  ShutdownError)
from deeplearning4j_tpu.serving import router as router_mod
from deeplearning4j_tpu.serving import (ModelRegistry, RolloutPolicy,
                                        RolloutState, ServingRouter)


def _make_net(seed=1):
    conf = (NeuralNetConfiguration.builder()
            .seed(seed).updater(Adam(1e-2)).list()
            .layer(DenseLayer(n_in=4, n_out=8, activation="relu"))
            .layer(OutputLayer(n_in=8, n_out=3, activation="softmax",
                               loss_function="mcxent"))
            .build())
    return MultiLayerNetwork(conf).init()


# module-level nets: the jit caches persist across tests, so repeated
# deploys warm from cache instead of recompiling every bucket (the box
# is slow; the first deploy per net pays the compiles once)
_NET_A = None
_NET_B = None
_NET_C = None


def _nets():
    global _NET_A, _NET_B, _NET_C
    if _NET_A is None:
        _NET_A, _NET_B, _NET_C = (_make_net(1), _make_net(1), _make_net(2))
    return _NET_A, _NET_B, _NET_C


_SAMPLE = np.zeros((1, 4), dtype="f4")


def _x(n=2, seed=0):
    return np.random.RandomState(seed).rand(n, 4).astype("f4")


def _fast_policy(**kw):
    base = dict(start_stage=RolloutState.CANARY, canary_fraction=0.5,
                ramp_fractions=(0.75,), window_requests=8,
                healthy_windows=1, min_latency_count=4, min_requests=4,
                min_shadow=2, drain_timeout_s=5.0)
    base.update(kw)
    return RolloutPolicy(**base)


@pytest.fixture(autouse=True)
def _clean():
    faults.reset()
    reset_global_registry()
    yield
    faults.clear()


def _deploy_pair(net_a, net_b, **pi_kw):
    kw = dict(sample_input=_SAMPLE, batch_limit=4, max_wait_ms=1.0)
    kw.update(pi_kw)
    reg = ModelRegistry()
    reg.deploy("v1", net_a, **kw)
    reg.deploy("v2", net_b, **kw)
    return reg


# ----------------------------------------------------------------- registry
def test_deploy_warms_every_bucket_with_zero_first_request_compiles():
    net_a, _, _ = _nets()
    reg = ModelRegistry()
    try:
        dv = reg.deploy("v1", net_a, sample_input=_SAMPLE, batch_limit=4,
                        max_wait_ms=1.0)
        assert dv.state == "live" and dv.admitting
        assert dv.warmed_buckets == [1, 2, 4]
        assert dv.warmup_seconds is not None
        router = ServingRouter(reg, "v1")
        watch = compile_watch.global_compile_watch()
        before = watch.count_for("MultiLayerNetwork._output_jit")
        # first request on EVERY configured bucket shape: all cache hits
        for n in (1, 2, 4):
            out = router.output(_x(n), request_key=n)
            assert np.asarray(out).shape == (n, 3)
        assert watch.count_for("MultiLayerNetwork._output_jit") == before
        # warmup gauge published
        g = global_registry().get("dl4j_serving_version_warmup_seconds")
        assert g.labels(version="v1").value == pytest.approx(
            dv.warmup_seconds)
    finally:
        reg.shutdown()


def test_duplicate_deploy_refused_and_retire_forgets():
    net_a, net_b, _ = _nets()
    reg = _deploy_pair(net_a, net_b)
    try:
        with pytest.raises(ValueError):
            reg.deploy("v1", net_b, sample_input=_SAMPLE)
        assert reg.versions() == ["v1", "v2"]
        assert reg.retire("v2")
        assert reg.versions() == ["v1"]
        with pytest.raises(KeyError):
            reg.get("v2")
    finally:
        reg.shutdown()


def _serve_threads_alive():
    return [t for t in threading.enumerate()
            if t.is_alive() and t.name.startswith("dl4j-serve")]


def test_retire_drain_leaves_no_threads_or_inflight_claims():
    net_a, _, _ = _nets()
    baseline = len(_serve_threads_alive())
    reg = ModelRegistry()
    dv = reg.deploy("v1", net_a, sample_input=_SAMPLE, batch_limit=4,
                    max_wait_ms=1.0)
    router = ServingRouter(reg, "v1")
    for i in range(4):
        router.output(_x(2), request_key=i)
    assert len(_serve_threads_alive()) > baseline
    assert reg.retire("v1")
    deadline = time.monotonic() + 5.0
    while len(_serve_threads_alive()) > baseline:
        if time.monotonic() > deadline:
            raise AssertionError(
                f"leaked serve threads: {_serve_threads_alive()}")
        time.sleep(0.05)
    assert dv.inflight() == 0
    assert dv.pi is None and dv.net is None       # executables released
    # a retired version refuses new traffic with the typed outcome
    with pytest.raises(ShutdownError):
        router.output(_x(2), request_key=99)


# ------------------------------------------------------------------ rollout
def test_healthy_rollout_advances_to_full_and_promotes(monkeypatch):
    net_a, net_b, _ = _nets()
    reg = _deploy_pair(net_a, net_b)
    # the latency gate stays on, at the ratio a healthy canary has: the
    # router reads its clock twice a request, and here every reading is one
    # tick after the last, so both versions measure the same latency however
    # loaded the machine is (what a slow canary does to the gate is
    # test_latency_degraded_canary_rolls_back)
    ticks = itertools.count()
    monkeypatch.setattr(
        router_mod, "time",
        types.SimpleNamespace(perf_counter=lambda: next(ticks) * 1e-3))
    try:
        router = ServingRouter(reg, "v1")
        ro = router.begin_rollout("v2", _fast_policy())
        stages = set()
        for i in range(80):
            router.output(_x(2, seed=i), request_key=i)
            stages.add(ro.stage)
            if not ro.active:
                break
        assert ro.stage == RolloutState.FULL
        assert RolloutState.RAMP in stages
        assert router.primary.version == "v2"
        # the old incumbent drained gracefully
        assert reg.get("v1").state == "retired"
        share = global_registry().get(
            "dl4j_serving_version_traffic_ratio")
        assert share.labels(version="v2").value == 1.0
        assert share.labels(version="v1").value == 0.0
    finally:
        reg.shutdown()


def test_time_based_rollout_window_advances_on_low_traffic():
    """``window_seconds`` mode: a trickle of traffic far below
    ``window_requests`` still advances the rollout on the wall clock
    (the low-traffic generative-version fix), while a zero-sample window
    never closes (``window_min_requests`` gate)."""
    net_a, net_b, _ = _nets()
    reg = _deploy_pair(net_a, net_b)
    try:
        router = ServingRouter(reg, "v1")
        ro = router.begin_rollout("v2", _fast_policy(
            window_seconds=0.08, window_min_requests=1,
            window_requests=10 ** 6,     # count mode would never fire
            min_latency_count=10 ** 6, min_requests=10 ** 6,
            min_shadow=10 ** 6))
        assert ro.snapshot()["window_mode"] == "time"
        # a candidate with NO samples must not advance on elapsed time
        time.sleep(0.1)
        ro.maybe_timed_evaluate()
        assert ro.stage == RolloutState.CANARY
        deadline = time.monotonic() + 30
        i = 0
        while ro.active and time.monotonic() < deadline:
            router.output(_x(2, seed=i), request_key=i)
            i += 1
            time.sleep(0.02)             # ~4 requests per window
        assert ro.stage == RolloutState.FULL
        assert router.primary.version == "v2"
    finally:
        reg.shutdown()


def test_degraded_canary_rolls_back_with_no_dropped_requests(tmp_path):
    """The acceptance chaos test: a canary degraded by injected error
    faults is auto-rolled-back by the SLO gate; every request resolves
    exactly once (correct or typed/injected); the incumbent's share
    returns to 100% — asserted on /debug/deploy, /metrics, and the
    bundle's deploy.json."""
    net_a, net_b, _ = _nets()
    reg = _deploy_pair(net_a, net_b)
    from deeplearning4j_tpu.ui.server import UIServer
    ui = UIServer(port=0).start()
    try:
        router = ServingRouter(reg, "v1")
        ro = router.begin_rollout("v2", _fast_policy(
            error_rate_degraded=0.2, error_rate_failing=0.5))
        plan = faults.FaultPlan(
            [faults.FaultSpec("serving.canary", "error", rate=1.0)])
        outcomes = []
        lock = threading.Lock()

        def one(i):
            try:
                out = router.output(_x(2, seed=i), request_key=i)
                result = ("ok", np.asarray(out).shape)
            except (InjectedFault, ShedError, DeadlineExceeded,
                    ShutdownError) as e:
                result = ("typed", type(e).__name__)
            with lock:
                outcomes.append(result)

        with faults.active(plan):
            threads = [threading.Thread(target=one, args=(i,))
                       for i in range(48)]
            for t in threads:
                t.start()
            for t in threads:
                t.join(timeout=30)
            assert not any(t.is_alive() for t in threads)
        # exactly-once resolution: every request produced exactly one
        # outcome (the claim() machinery under the hood)
        assert len(outcomes) == 48
        assert ro.stage == RolloutState.ROLLED_BACK
        assert not ro.active and ro.rollback_reason.startswith("slo:")
        assert any(o == ("ok", (2, 3)) for o in outcomes)
        assert any(o[0] == "typed" for o in outcomes)
        # post-rollback traffic runs clean on the incumbent at 100%
        for i in range(8):
            out = router.output(_x(2, seed=1000 + i), request_key=1000 + i)
            assert np.asarray(out).shape == (2, 3)
        share = global_registry().get("dl4j_serving_version_traffic_ratio")
        assert share.labels(version="v1").value == 1.0
        assert share.labels(version="v2").value == 0.0
        assert reg.get("v2").state == "retired"
        # surfaces: /debug/deploy names the rolled-back rollout
        with urllib.request.urlopen(
                ui.get_address() + "/debug/deploy") as r:
            deploy = json.loads(r.read())
        routers = [s for s in deploy["routers"]
                   if s["rollout"] and s["rollout"]["candidate"] == "v2"
                   and s["rollout"]["stage"] == "rolled_back"]
        assert routers and routers[0]["primary"] == "v1"
        # /metrics carries the rollback counter + per-version series
        with urllib.request.urlopen(ui.get_address() + "/metrics") as r:
            prom = r.read().decode()
        assert "dl4j_serving_rollbacks_total 1" in prom
        assert 'dl4j_serving_version_requests_total{version="v2"}' in prom
        # the flight-recorder bundle's deploy.json tells the same story
        rec = FlightRecorder(out_dir=str(tmp_path))
        bundle = rec.dump("test")
        rec.stop()
        with open(os.path.join(bundle, "deploy.json")) as f:
            dj = json.load(f)
        assert any(s["rollout"] and s["rollout"]["stage"] == "rolled_back"
                   for s in dj["routers"])
    finally:
        ui.stop()
        reg.shutdown()


def test_latency_degraded_canary_rolls_back():
    """Injected canary latency (not errors) trips the latency-quantile
    ratio rule."""
    net_a, net_b, _ = _nets()
    reg = _deploy_pair(net_a, net_b)
    try:
        router = ServingRouter(reg, "v1")
        ro = router.begin_rollout("v2", _fast_policy(
            latency_ratio_degraded=3.0, latency_ratio_failing=10.0,
            min_latency_count=6, window_requests=16))
        # warm the incumbent's latency series so the ratio has a
        # denominator, then serve under canary-side latency faults
        for i in range(10000, 10012):
            router.output(_x(2, seed=i), request_key=i)
        plan = faults.FaultPlan([faults.FaultSpec(
            "serving.canary", "latency", rate=1.0)])
        with faults.active(plan):
            for i in range(64):
                router.output(_x(2, seed=i), request_key=i)
                if not ro.active:
                    break
        assert ro.stage == RolloutState.ROLLED_BACK
        assert "canary_latency_ratio" in ro.rollback_reason
    finally:
        reg.shutdown()


def test_shadow_divergence_rolls_back_before_user_traffic():
    """A wrong-answer candidate is caught in SHADOW: users only ever see
    incumbent outputs, and the rollout never reaches canary."""
    net_a, _, net_c = _nets()           # net_c: different seed => diverges
    reg = _deploy_pair(net_a, net_c)
    try:
        router = ServingRouter(reg, "v1")
        direct = np.asarray(reg.get("v1").pi.output(_x(2, seed=7)))
        ro = router.begin_rollout("v2", RolloutPolicy(
            start_stage=RolloutState.SHADOW, shadow_fraction=1.0,
            window_requests=8, healthy_windows=3, min_shadow=4,
            divergence_degraded=0.2, divergence_failing=0.5))
        for i in range(24):
            out = router.output(_x(2, seed=7), request_key=i)
            assert np.allclose(np.asarray(out), direct)   # incumbent answer
            if not ro.active:
                break
        assert ro.stage == RolloutState.ROLLED_BACK
        assert "canary_shadow_divergence" in ro.rollback_reason
        shadow = global_registry().get("dl4j_serving_shadow_total")
        assert shadow.labels(version="v2", outcome="diverged").value >= 4
    finally:
        reg.shutdown()


def test_drain_under_chaos_resolves_every_inflight_request():
    """Satellite: a rollback triggered mid-flight with serving.canary +
    inference.device_execute faults active resolves every request —
    typed or correct, none dropped, none double-resolved (each thread
    observes exactly one outcome through the claim() machinery)."""
    net_a, net_b, _ = _nets()
    reg = _deploy_pair(net_a, net_b)
    try:
        router = ServingRouter(reg, "v1")
        ro = router.begin_rollout("v2", _fast_policy(
            error_rate_degraded=0.2, error_rate_failing=0.4,
            window_requests=6, drain_timeout_s=3.0))
        plan = faults.FaultPlan([
            faults.FaultSpec("serving.canary", "latency", rate=1.0,
                             latency_seconds=0.05),
            faults.FaultSpec("serving.canary", "error", rate=0.7),
            faults.FaultSpec("inference.device_execute", "error", rate=0.1),
        ], seed=3)
        n = 40
        outcomes = []
        lock = threading.Lock()

        def one(i):
            try:
                out = router.output(_x(2, seed=i), request_key=i)
                result = ("ok", np.asarray(out).shape)
            except (InjectedFault, ShedError, DeadlineExceeded,
                    ShutdownError) as e:
                result = ("typed", type(e).__name__)
            except Exception as e:      # no other error type may escape
                result = ("unexpected", repr(e))
            with lock:
                outcomes.append(result)

        with faults.active(plan):
            threads = [threading.Thread(target=one, args=(i,))
                       for i in range(n)]
            for t in threads:
                t.start()
            for t in threads:
                t.join(timeout=30)
            assert not any(t.is_alive() for t in threads)
        assert len(outcomes) == n                       # none dropped
        assert not [o for o in outcomes if o[0] == "unexpected"]
        assert ro.stage == RolloutState.ROLLED_BACK     # gate fired
        assert reg.get("v2").state == "retired"         # drained clean
        assert reg.get("v2").inflight() == 0
        assert any(e["category"] == "serving_drain"
                   for e in faults.events())
        # ok outcomes all correct-shaped (claimed exactly once — a
        # double resolution would have surfaced as a corrupt/None result)
        assert all(o[1] == (2, 3) for o in outcomes if o[0] == "ok")
    finally:
        reg.shutdown()


def test_redeployed_version_is_graded_on_fresh_metrics_only():
    """The per-version counters are process-lifetime: a redeploy of a
    rolled-back version must be graded on THIS rollout's traffic, not
    inherit the failed attempt's errors (rules baseline at rollout
    start)."""
    net_a, net_b, _ = _nets()
    reg = _deploy_pair(net_a, net_b)
    try:
        router = ServingRouter(reg, "v1")
        ro = router.begin_rollout("v2", _fast_policy(
            error_rate_degraded=0.2, error_rate_failing=0.5))
        plan = faults.FaultPlan(
            [faults.FaultSpec("serving.canary", "error", rate=1.0)])
        with faults.active(plan):
            for i in range(40):
                try:
                    router.output(_x(2, seed=i), request_key=i)
                except InjectedFault:
                    pass
                if not ro.active:
                    break
        assert ro.stage == RolloutState.ROLLED_BACK
        # redeploy the (fixed) build under the same version name and
        # roll out again with clean traffic: it must ADVANCE
        reg.deploy("v2", net_b, sample_input=_SAMPLE, batch_limit=4,
                   max_wait_ms=1.0)
        ro2 = router.begin_rollout("v2", _fast_policy(
            error_rate_degraded=0.2, error_rate_failing=0.5))
        for i in range(80):
            router.output(_x(2, seed=1000 + i), request_key=1000 + i)
            if not ro2.active:
                break
        assert ro2.stage == RolloutState.FULL, ro2.snapshot()
    finally:
        reg.shutdown()


# -------------------------------------------------------------- kill switch
def test_rollout_kill_switch_is_byte_identical_passthrough(monkeypatch):
    net_a, net_b, _ = _nets()
    monkeypatch.setenv("DL4J_TPU_ROLLOUT", "0")
    reg = _deploy_pair(net_a, net_b)
    try:
        router = ServingRouter(reg, "v1")
        x = _x(3, seed=5)
        direct = np.asarray(reg.get("v1").pi.output(x))
        routed = np.asarray(router.output(x))
        assert routed.tobytes() == direct.tobytes()
        with pytest.raises(RuntimeError):
            router.begin_rollout("v2")
        # passthrough records no per-version routing series
        inst = global_registry().get("dl4j_serving_version_requests_total")
        assert inst is None or not list(inst.series())
    finally:
        reg.shutdown()


# ------------------------------------------------------------ compile cache
_CACHE_CHILD = r"""
import json, os, sys
import jax
events, dir_updates = [], []
import jax.monitoring as mon
mon.register_event_listener(
    lambda ev, **kw: events.append(ev) if "compilation_cache" in ev else None)
_update = jax.config.update
def _spy(name, value):
    if name == "jax_compilation_cache_dir":
        dir_updates.append(value)
    return _update(name, value)
jax.config.update = _spy
import numpy as np
from deeplearning4j_tpu.nn.conf.configuration import NeuralNetConfiguration
from deeplearning4j_tpu.nn.conf.layers import DenseLayer, OutputLayer
from deeplearning4j_tpu.nn.multilayer import MultiLayerNetwork
from deeplearning4j_tpu.optim.updaters import Adam
from deeplearning4j_tpu.serving import ModelRegistry

conf = (NeuralNetConfiguration.builder().seed(3).updater(Adam(1e-2)).list()
        .layer(DenseLayer(n_in=4, n_out=8, activation="relu"))
        .layer(OutputLayer(n_in=8, n_out=3, activation="softmax",
                           loss_function="mcxent")).build())
net = MultiLayerNetwork(conf).init()
reg = ModelRegistry()
reg.deploy("v1", net, sample_input=np.zeros((1, 4), "f4"), batch_limit=2,
           max_wait_ms=1.0)
reg.shutdown()
print(json.dumps({
    "hits": sum(1 for e in events if e.endswith("cache_hits")),
    "misses": sum(1 for e in events if e.endswith("cache_misses")),
    "dir": jax.config.jax_compilation_cache_dir,
    "dir_updates": dir_updates,
}))
"""

_REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


def _cache_env(**extra):
    env = dict(os.environ)
    env.pop("JAX_COMPILATION_CACHE_DIR", None)
    env["JAX_ENABLE_COMPILATION_CACHE"] = "true"    # conftest keeps it off
    env["PYTHONPATH"] = _REPO + os.pathsep + env.get("PYTHONPATH", "")
    env.update(extra)
    return env


def test_compile_cache_dir_from_environment_second_process_hits(tmp_path):
    """With JAX_COMPILATION_CACHE_DIR set the program sets NO directory in
    code (jax reads the variable itself), and a second process deploying
    the same model retrieves the warmed bucket executables from the
    persistent cache instead of recompiling them."""
    cache = str(tmp_path / "xla-cache")
    env = _cache_env(JAX_COMPILATION_CACHE_DIR=cache)

    def run():
        r = subprocess.run([sys.executable, "-c", _CACHE_CHILD],
                           capture_output=True, text=True, timeout=120,
                           env=env)
        assert r.returncode == 0, r.stdout + r.stderr
        return json.loads(r.stdout.strip().splitlines()[-1])

    first = run()
    assert first["dir"] == cache and first["dir_updates"] == []
    assert first["misses"] >= 1          # cold: executables compiled + saved
    assert os.path.isdir(cache)
    second = run()
    assert second["dir_updates"] == []
    assert second["hits"] >= 1           # warm: retrieved from disk
    assert second["misses"] == 0         # nothing recompiled


def test_compile_cache_default_dir_is_fixed_in_checkout(tmp_path):
    """Unset, the cache lives at <repo>/.jax_cache whatever the pid or the
    working directory (nothing else — no state dir, no temp name — can
    place it): the path is part of jax's cache key, so a directory that
    moves never hits."""
    code = ("import json, os; "
            "from deeplearning4j_tpu.async_runtime import "
            "configure_compile_cache; "
            "print(json.dumps([configure_compile_cache(), os.getpid()]))")
    cwds = [tmp_path / "a", tmp_path / "b"]
    for d in cwds:
        d.mkdir()
    procs = [subprocess.Popen(
        [sys.executable, "-c", code], cwd=str(d), text=True,
        stdout=subprocess.PIPE, stderr=subprocess.PIPE, env=_cache_env())
        for d in cwds]
    outs = []
    for p in procs:
        out, err = p.communicate(timeout=120)
        assert p.returncode == 0, out + err
        outs.append(json.loads(out.strip().splitlines()[-1]))
    (dir0, pid0), (dir1, pid1) = outs
    assert pid0 != pid1
    assert dir0 == dir1 == os.path.join(_REPO, ".jax_cache")


# ------------------------------------------------------------------- faults
def test_serving_canary_is_a_valid_fault_point():
    spec = faults.FaultSpec("serving.canary", "error", rate=1.0)
    assert spec.point == "serving.canary"
    with pytest.raises(ValueError):
        faults.FaultSpec("serving.canary", "nan")   # owns no array
