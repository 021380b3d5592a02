"""Instrumentation overhead guard (observability PR acceptance tool).

Measures the lenet train step in six modes, interleaved with a
min-estimator:

- ``off``      — ``DL4J_TPU_METRICS=0`` (everything no-ops)
- ``no_trace`` — metrics on, ``DL4J_TPU_TRACE=0`` (spans + trace-context
  propagation disabled; isolates the causal-tracing cost)
- ``no_obs``   — metrics + tracing on, ``DL4J_TPU_NUMERICS=0
  DL4J_TPU_COMPILE_WATCH=0`` (isolates the PR-4 observatory: in-graph
  numerics terms + compile probes)
- ``no_res``   — everything on, ``DL4J_TPU_RESILIENCE=0`` (isolates the
  PR-5 resilience layer: armed-but-idle fault checks and policies, no
  faults configured)
- ``no_cost``  — everything on, ``DL4J_TPU_COST_MODEL=0`` (isolates the
  PR-6 cost observatory: per-step duration feed + the once-per-compile
  AOT cost lowering)
- ``on``       — full default instrumentation + armed resilience

Acceptance bars: total overhead (on vs off) <5%; trace-id propagation
overhead (on vs no_trace) <2%; observatory overhead (on vs no_obs) <2%;
resilience overhead (on vs no_res, policies armed / no faults) <2%;
cost-observatory overhead (on vs no_cost) <2%.

Each mode runs in a fresh subprocess: the kill switches are applied at
instrument creation (and, for numerics, at trace time), so flipping them
in-process after modules warmed up would measure the wrong thing.

``--elastic-ab`` runs a different comparison: the elastic
async-checkpoint A/B — a sharded manifest saved every ``--save-every``
steps (default 8, the perf posture; the exact-resume drills save every
step and are measured separately as the documented worst case) — arms
``no_elastic`` / ``elastic_async`` / ``elastic_sync``, interleaved
min-of-N with rotating order, proving the background save path keeps
armed step-time overhead under the 2% bar at that cadence while
showing what the synchronous spelling would cost.

``--warmup-ab`` runs the serving AOT-warmup A/B: first-request latency
through ``ServingRouter`` for a cold deploy (no warmup — the request
pays the whole-program XLA compile) vs. an AOT-warmed deploy (the
request should sit within box noise of steady state), interleaved
min-of-N in fresh subprocesses so every cold arm is genuinely cold.

``--fleet-obs-ab`` runs the fleet-observability-plane A/B: per-request
latency through a live ``FrontDoor`` with a caller-supplied
``X-Dl4j-Trace-Id`` header, ``DL4J_TPU_FLEET_OBS=0`` (the pre-PR
request path: no inbound-context parse, no response trace header) vs
``=1`` (the full cross-process propagation path). Bar: <2% — trace
propagation must be free enough to leave on in production.

``--watchtower-ab`` runs the watchtower A/B: per-request front-door
latency with a background thread beating the watchtower (timeseries
scrape + burn/change-point detectors + alert lifecycle) at drill
cadence, ``DL4J_TPU_WATCHTOWER=0`` (beats no-op — the pre-watchtower
process) vs ``=1``. Bar: <2% — continuous detection must be free enough
to leave on in production.

``--session-ab`` runs the durable-session A/B: steady-state generate
latency on an in-process ``GenerationPipeline``,
``DL4J_TPU_SESSIONS=0`` (the pre-session decode path) vs ``=1``
(per-request session minting, per-token ring append, batched journal
flushes into a live ``SharedStore``). Bar: <2% — crash-safety must be
free enough to leave on in production.

Run: python benchmarks/obs_overhead.py [--steps N] [--batch B] [--json]
     python benchmarks/obs_overhead.py --elastic-ab [--json]
     python benchmarks/obs_overhead.py --warmup-ab [--json]
     python benchmarks/obs_overhead.py --fleet-obs-ab [--json]
     python benchmarks/obs_overhead.py --watchtower-ab [--json]
     python benchmarks/obs_overhead.py --session-ab [--json]
"""
from __future__ import annotations

import argparse
import json
import os
import subprocess
import sys

_WORKER = r"""
import json, os, sys, time
import numpy as np

from deeplearning4j_tpu.models import zoo
from deeplearning4j_tpu.data.dataset import DataSet

steps = int(sys.argv[1])
batch = int(sys.argv[2])

net = zoo.LeNet().init_model()
rng = np.random.RandomState(0)
x = rng.rand(batch, 28 * 28).astype("f4")
y = np.eye(10, dtype="f4")[rng.randint(0, 10, batch)]
ds = DataSet(x, y)

net.fit(ds)                       # compile + warm caches outside the window
net.fit(ds)

t0 = time.perf_counter()
for _ in range(steps):
    net.fit(ds)
wall = time.perf_counter() - t0
print(json.dumps({"seconds_per_step": wall / steps,
                  "metrics": os.environ.get("DL4J_TPU_METRICS", "1")}))
"""

#: elastic async-checkpoint A/B worker: same lenet step loop, but with an
#: ElasticCheckpointer saving the full training state every SAVE_EVERY
#: steps (the perf posture — the exact-resume drills save every step).
#: Arms: no_elastic (DL4J_TPU_ELASTIC=0 — saves no-op, the pre-elastic
#: step time), elastic_async (background saves, the production posture),
#: elastic_sync (inline saves — the cost the async path keeps off the
#: critical path). Bar: elastic_async vs no_elastic < 2%.
_ELASTIC_WORKER = r"""
import json, os, sys, tempfile, time
import numpy as np

from deeplearning4j_tpu.models import zoo
from deeplearning4j_tpu.data.dataset import DataSet
from deeplearning4j_tpu.resilience.elastic import ElasticCheckpointer

steps = int(sys.argv[1])
batch = int(sys.argv[2])
sync = sys.argv[3] == "sync"
save_every = int(sys.argv[4])

net = zoo.LeNet().init_model()
rng = np.random.RandomState(0)
x = rng.rand(batch, 28 * 28).astype("f4")
y = np.eye(10, dtype="f4")[rng.randint(0, 10, batch)]
ds = DataSet(x, y)

ck = ElasticCheckpointer(tempfile.mkdtemp(prefix="dl4j-elastic-ab-"),
                         max_to_keep=2)
net.fit(ds)                       # compile + warm caches outside the window
net.fit(ds)

t0 = time.perf_counter()
for _ in range(steps):
    net.fit(ds)
    if net._iteration % save_every == 0:
        ck.save(net._iteration, net, sync=sync)
wall = time.perf_counter() - t0   # async saves may still be in flight:
ck.wait()                         # exactly the off-critical-path claim
print(json.dumps({"seconds_per_step": wall / steps,
                  "elastic": os.environ.get("DL4J_TPU_ELASTIC", "1")}))
"""

#: elastic A/B arm -> (env overrides, sync flag)
ELASTIC_MODES = {
    "no_elastic": ({"DL4J_TPU_ELASTIC": "0"}, "async"),
    "elastic_async": ({"DL4J_TPU_ELASTIC": "1"}, "async"),
    "elastic_sync": ({"DL4J_TPU_ELASTIC": "1"}, "sync"),
}


def _run_worker(script: str, args, overrides) -> float:
    """One fresh-subprocess measurement — kill switches apply at
    instrument creation, so flipping them in-process would measure the
    wrong thing. Shared by both A/Bs."""
    env = dict(os.environ, **overrides)
    out = subprocess.run(
        [sys.executable, "-c", script] + [str(a) for a in args],
        capture_output=True, text=True, env=env, check=True)
    return json.loads(out.stdout.strip().splitlines()[-1])["seconds_per_step"]


def _interleaved_min(modes, repeats: int, run_one) -> dict:
    """THE noisy-box measurement protocol, one spelling for every A/B in
    this file: interleaved repeats with a per-repeat ROTATING mode order
    (on this cpu-shares-throttled box, host speed drifts monotonically
    across minutes and a fixed order hands the last mode a systematic —
    once observed: 30% — advantage), min-estimator per mode."""
    samples = {m: [] for m in modes}
    order = list(modes)
    for r in range(repeats):
        for m in order[r % len(order):] + order[:r % len(order)]:
            samples[m].append(run_one(m))
    return {m: min(v) for m, v in samples.items()}


def _run_elastic(steps: int, batch: int, mode: str,
                 save_every: int) -> float:
    overrides, sync = ELASTIC_MODES[mode]
    return _run_worker(_ELASTIC_WORKER, [steps, batch, sync, save_every],
                       overrides)


def elastic_ab(steps: int, batch: int, repeats: int,
               as_json: bool, save_every: int = 8) -> float:
    """Interleaved min-of-N A/B (mode order rotates per repeat — the
    noisy-box protocol of benchmarks/RESULTS.md): does saving a sharded
    manifest every ``save_every`` steps off the critical path keep the
    armed step-time overhead under the 2% bar at that cadence?"""
    best = _interleaved_min(
        list(ELASTIC_MODES), repeats,
        lambda m: _run_elastic(steps, batch, m, save_every))
    async_overhead = ((best["elastic_async"] - best["no_elastic"])
                      / best["no_elastic"] * 100.0)
    sync_overhead = ((best["elastic_sync"] - best["no_elastic"])
                     / best["no_elastic"] * 100.0)
    result = {"lenet_step_seconds_no_elastic": best["no_elastic"],
              "lenet_step_seconds_elastic_async": best["elastic_async"],
              "lenet_step_seconds_elastic_sync": best["elastic_sync"],
              "elastic_async_overhead_percent": async_overhead,
              "elastic_sync_overhead_percent": sync_overhead,
              "steps": steps, "batch": batch, "repeats": repeats,
              "save_every": save_every}
    if as_json:
        print(json.dumps(result, indent=2))
    else:
        print(f"elastic checkpoint A/B (save every {save_every} steps), "
              f"batch={batch}, {steps} steps/arm, min of {repeats} "
              f"interleaved repeats")
        print(f"  no_elastic    (DL4J_TPU_ELASTIC=0): "
              f"{best['no_elastic'] * 1e3:8.3f} ms")
        print(f"  elastic_async (background saves):   "
              f"{best['elastic_async'] * 1e3:8.3f} ms")
        print(f"  elastic_sync  (inline saves):       "
              f"{best['elastic_sync'] * 1e3:8.3f} ms")
        print(f"  async-save overhead: {async_overhead:+.2f}%  (bar: < 2%)")
        print(f"  sync-save overhead (what async avoids): "
              f"{sync_overhead:+.2f}%")
    return async_overhead


#: serving warmup A/B worker: deploy a version with vs. without AOT
#: bucket warmup in a FRESH process (compiles must be cold), then time
#: the first routed request against steady state. The warm arm's first
#: request should sit within box noise of steady state; the cold arm
#: pays the whole-program XLA compile on live traffic.
_WARMUP_WORKER = r"""
import json, os, sys, time
import numpy as np

from deeplearning4j_tpu.models import zoo
from deeplearning4j_tpu.serving import ModelRegistry, ServingRouter

warm = sys.argv[1] == "warm"
batch = int(sys.argv[2])

net = zoo.LeNet().init_model()
x = np.random.RandomState(0).rand(batch, 28 * 28).astype("f4")
reg = ModelRegistry()
reg.deploy("v1", net, sample_input=x[:1] if warm else None, warmup=warm,
           batch_limit=batch, max_wait_ms=1.0)
router = ServingRouter(reg, "v1")
t0 = time.perf_counter()
router.output(x)
first = time.perf_counter() - t0
steady = []
for _ in range(20):
    t0 = time.perf_counter()
    router.output(x)
    steady.append(time.perf_counter() - t0)
reg.shutdown()
print(json.dumps({"first_ms": first * 1e3,
                  "steady_ms": min(steady) * 1e3}))
"""


def _run_warmup(batch: int, mode: str) -> dict:
    env = dict(os.environ)
    out = subprocess.run(
        [sys.executable, "-c", _WARMUP_WORKER, mode, str(batch)],
        capture_output=True, text=True, env=env, check=True)
    return json.loads(out.stdout.strip().splitlines()[-1])


def warmup_ab(batch: int, repeats: int, as_json: bool) -> float:
    """Interleaved min-of-N A/B (rotating arm order — the noisy-box
    protocol): first-request latency through ``ServingRouter`` with AOT
    deploy warmup vs. without. The acceptance claim: with warmup, the
    first request is within noise of steady state; without it, it eats
    the whole-program compile."""
    samples = {"cold": [], "warm": []}
    order = ["cold", "warm"]
    for r in range(repeats):
        for m in order[r % 2:] + order[:r % 2]:
            samples[m].append(_run_warmup(batch, m))
    cold_first = min(s["first_ms"] for s in samples["cold"])
    warm_first = min(s["first_ms"] for s in samples["warm"])
    steady = min(s["steady_ms"] for s in samples["warm"])
    result = {"first_request_ms_cold": cold_first,
              "first_request_ms_warm": warm_first,
              "steady_state_ms": steady,
              "cold_over_warm": cold_first / warm_first,
              "warm_first_over_steady": warm_first / steady,
              "batch": batch, "repeats": repeats}
    if as_json:
        print(json.dumps(result, indent=2))
    else:
        print(f"serving warmup A/B (lenet, batch={batch}, min of "
              f"{repeats} interleaved repeats)")
        print(f"  first request, cold deploy (no warmup): "
              f"{cold_first:9.2f} ms")
        print(f"  first request, AOT-warmed deploy:       "
              f"{warm_first:9.2f} ms")
        print(f"  steady state:                           "
              f"{steady:9.2f} ms")
        print(f"  cold/warm first-request ratio: "
              f"{cold_first / warm_first:6.1f}x")
        print(f"  warm first-request vs steady:  "
              f"{warm_first / steady:6.2f}x  (bar: within box noise)")
    return warm_first / steady


#: fleet-observability A/B worker: a live in-process FrontDoor (the
#: same demo scoring net tools/serve.py deploys), timed urllib POSTs to
#: /v1/classify each carrying a caller-supplied X-Dl4j-Trace-Id. The
#: arms differ ONLY in DL4J_TPU_FLEET_OBS: 0 is the pre-PR request path
#: (inbound header ignored, no trace header on the response), 1 parses
#: the inbound context, joins the span, and echoes the id — the cost
#: this A/B exists to bound.
_FLEET_OBS_WORKER = r"""
import json, os, sys, time, urllib.request
import numpy as np

from deeplearning4j_tpu.nn.conf.configuration import NeuralNetConfiguration
from deeplearning4j_tpu.nn.conf.layers import DenseLayer, OutputLayer
from deeplearning4j_tpu.nn.multilayer import MultiLayerNetwork
from deeplearning4j_tpu.optim.updaters import Adam
from deeplearning4j_tpu.serving import ModelRegistry, ServingRouter
from deeplearning4j_tpu.serving.frontdoor import FrontDoor

steps = int(sys.argv[1])

conf = (NeuralNetConfiguration.builder()
        .seed(1).updater(Adam(1e-2)).list()
        .layer(DenseLayer(n_in=4, n_out=8, activation="relu"))
        .layer(OutputLayer(n_in=8, n_out=3, activation="softmax",
                           loss_function="mcxent"))
        .build())
net = MultiLayerNetwork(conf).init()
reg = ModelRegistry()
reg.deploy("v1", net, sample_input=np.zeros((1, 4), dtype="f4"),
           batch_limit=4, max_wait_ms=1.0)
door = FrontDoor(ServingRouter(reg, "v1"), None, port=0).start()
addr = f"http://127.0.0.1:{door.port}"
body = json.dumps({"inputs": [[0.1, 0.2, 0.3, 0.4]]}).encode()


def one(i):
    req = urllib.request.Request(
        addr + "/v1/classify", data=body,
        headers={"Content-Type": "application/json",
                 "X-Dl4j-Trace-Id": f"{0xB0000000 + i:016x}"})
    with urllib.request.urlopen(req, timeout=30.0) as r:
        r.read()


for i in range(10):               # compile + socket churn outside the window
    one(i)
t0 = time.perf_counter()
for i in range(steps):
    one(i)
wall = time.perf_counter() - t0
door.stop()
reg.shutdown()
print(json.dumps({"seconds_per_step": wall / steps,
                  "fleet_obs": os.environ.get("DL4J_TPU_FLEET_OBS", "1")}))
"""

#: fleet-obs A/B arm -> env overrides
FLEET_OBS_MODES = {
    "obs_off": {"DL4J_TPU_FLEET_OBS": "0"},
    "obs_on": {"DL4J_TPU_FLEET_OBS": "1"},
}


def fleet_obs_ab(steps: int, repeats: int, as_json: bool) -> float:
    """Interleaved min-of-N A/B (rotating arm order — the noisy-box
    protocol): does cross-process trace propagation (inbound header
    parse + joined span + response header) keep per-request front-door
    latency under the 2% bar?"""
    best = _interleaved_min(
        list(FLEET_OBS_MODES), repeats,
        lambda m: _run_worker(_FLEET_OBS_WORKER, [steps],
                              FLEET_OBS_MODES[m]))
    overhead = ((best["obs_on"] - best["obs_off"])
                / best["obs_off"] * 100.0)
    result = {"request_seconds_fleet_obs_off": best["obs_off"],
              "request_seconds_fleet_obs_on": best["obs_on"],
              "fleet_obs_overhead_percent": overhead,
              "steps": steps, "repeats": repeats}
    if as_json:
        print(json.dumps(result, indent=2))
    else:
        print(f"fleet observability A/B (traced /v1/classify, {steps} "
              f"requests/arm, min of {repeats} interleaved repeats)")
        print(f"  fleet obs off (DL4J_TPU_FLEET_OBS=0): "
              f"{best['obs_off'] * 1e3:8.3f} ms/request")
        print(f"  fleet obs on  (trace propagation):    "
              f"{best['obs_on'] * 1e3:8.3f} ms/request")
        print(f"  trace-propagation overhead: {overhead:+.2f}%  "
              f"(bar: < 2%)")
    return overhead


#: trace-store A/B arm -> env overrides. Arms differ ONLY in
#: DL4J_TPU_TRACE_STORE: 0 is the pre-store span path (spans close into
#: the ring sink and vanish), 1 adds the per-span open/close store hooks
#: plus the retention decision at root close — the cost this A/B bounds.
#: Sampling is pinned to the default head rate so the measured arm is
#: the shipped posture, and the same traced front-door worker serves
#: both fleet-obs and trace-store A/Bs (one request path, one protocol).
TRACE_STORE_MODES = {
    "store_off": {"DL4J_TPU_TRACE_STORE": "0"},
    "store_on": {"DL4J_TPU_TRACE_STORE": "1"},
}


def trace_store_ab(steps: int, repeats: int, as_json: bool) -> float:
    """Interleaved min-of-N A/B (rotating arm order — the noisy-box
    protocol): do the trace-store hooks (note_open per span, feed +
    retention decision at close) keep per-request front-door latency
    under the 2% bar?"""
    best = _interleaved_min(
        list(TRACE_STORE_MODES), repeats,
        lambda m: _run_worker(_FLEET_OBS_WORKER, [steps],
                              TRACE_STORE_MODES[m]))
    overhead = ((best["store_on"] - best["store_off"])
                / best["store_off"] * 100.0)
    result = {"request_seconds_trace_store_off": best["store_off"],
              "request_seconds_trace_store_on": best["store_on"],
              "trace_store_overhead_percent": overhead,
              "steps": steps, "repeats": repeats}
    if as_json:
        print(json.dumps(result, indent=2))
    else:
        print(f"trace-store A/B (traced /v1/classify, {steps} "
              f"requests/arm, min of {repeats} interleaved repeats)")
        print(f"  trace store off (DL4J_TPU_TRACE_STORE=0): "
              f"{best['store_off'] * 1e3:8.3f} ms/request")
        print(f"  trace store on  (retention hooks):        "
              f"{best['store_on'] * 1e3:8.3f} ms/request")
        print(f"  trace-store overhead: {overhead:+.2f}%  (bar: < 2%)")
    return overhead


#: session A/B worker: an in-process GenerationPipeline on the demo
#: TransformerLM (the same engine tools/serve.py deploys), timed
#: generate() calls in steady state. The arms differ ONLY in
#: DL4J_TPU_SESSIONS: 0 is the pre-session decode path (no record, no
#: journal), 1 mints a session per request, appends every emitted token
#: to its ring record, and journals batches into a live SharedStore at
#: the default cadence off the hot path — the cost this A/B bounds.
_SESSION_WORKER = r"""
import json, os, sys, tempfile, time
import jax
import numpy as np

from deeplearning4j_tpu.models.generation import DecodeEngine
from deeplearning4j_tpu.models.transformer import (TransformerConfig,
                                                   TransformerLM)
from deeplearning4j_tpu.parallel.generation import GenerationPipeline
from deeplearning4j_tpu.serving import session as _sess
from deeplearning4j_tpu.serving.shared_state import SharedStore

steps = int(sys.argv[1])
cfg = TransformerConfig(vocab_size=61, n_layers=2, n_heads=2,
                        d_model=32, max_len=64)
model = TransformerLM(cfg)
engine = DecodeEngine(model, model.init_params(jax.random.key(0)),
                      max_len=48)
gp = GenerationPipeline(engine, slots=4, max_new_tokens=16)
if _sess.sessions_enabled():
    # the shipped posture: a live journal draining to a real store
    store = SharedStore(tempfile.mkdtemp(prefix="dl4j-sess-ab-"))
    _sess.global_journal().attach(store, "ab")
prompt = np.asarray([3, 1, 4, 1, 5], np.int32)
for _ in range(5):              # compile + slot churn outside the window
    gp.generate(prompt, max_new_tokens=16)
t0 = time.perf_counter()
for _ in range(steps):
    gp.generate(prompt, max_new_tokens=16)
wall = time.perf_counter() - t0
gp.shutdown()
print(json.dumps({"seconds_per_step": wall / steps,
                  "sessions": os.environ.get("DL4J_TPU_SESSIONS", "1")}))
"""

#: session A/B arm -> env overrides
SESSION_MODES = {
    "sess_off": {"DL4J_TPU_SESSIONS": "0"},
    "sess_on": {"DL4J_TPU_SESSIONS": "1"},
}


def session_ab(steps: int, repeats: int, as_json: bool) -> float:
    """Interleaved min-of-N A/B (rotating arm order — the noisy-box
    protocol): does per-request session minting + per-token ring append
    + batched store journaling keep steady-state generation latency
    under the 2% bar?"""
    best = _interleaved_min(
        list(SESSION_MODES), repeats,
        lambda m: _run_worker(_SESSION_WORKER, [steps],
                              SESSION_MODES[m]))
    overhead = ((best["sess_on"] - best["sess_off"])
                / best["sess_off"] * 100.0)
    result = {"generate_seconds_sessions_off": best["sess_off"],
              "generate_seconds_sessions_on": best["sess_on"],
              "session_overhead_percent": overhead,
              "steps": steps, "repeats": repeats}
    if as_json:
        print(json.dumps(result, indent=2))
    else:
        print(f"durable-session A/B (16-token generate, {steps} "
              f"requests/arm, min of {repeats} interleaved repeats)")
        print(f"  sessions off (DL4J_TPU_SESSIONS=0): "
              f"{best['sess_off'] * 1e3:8.3f} ms/request")
        print(f"  sessions on  (journal attached):    "
              f"{best['sess_on'] * 1e3:8.3f} ms/request")
        print(f"  session overhead: {overhead:+.2f}%  (bar: < 2%)")
    return overhead


#: watchtower A/B worker: the same traced front-door request loop, but
#: with a background thread beating the watchtower (timeseries scrape +
#: detector evaluation + alert lifecycle) at drill cadence throughout
#: the measurement window. The arms differ ONLY in DL4J_TPU_WATCHTOWER:
#: 0 makes every beat a no-op (the pre-watchtower process), 1 runs the
#: full scrape + detector + lifecycle machinery concurrently with the
#: request path — the cost this A/B exists to bound.
_WATCHTOWER_WORKER = r"""
import json, os, sys, threading, time, urllib.request
import numpy as np

from deeplearning4j_tpu.nn.conf.configuration import NeuralNetConfiguration
from deeplearning4j_tpu.nn.conf.layers import DenseLayer, OutputLayer
from deeplearning4j_tpu.nn.multilayer import MultiLayerNetwork
from deeplearning4j_tpu.observability.watchtower import global_watchtower
from deeplearning4j_tpu.optim.updaters import Adam
from deeplearning4j_tpu.serving import ModelRegistry, ServingRouter
from deeplearning4j_tpu.serving.frontdoor import FrontDoor

steps = int(sys.argv[1])

conf = (NeuralNetConfiguration.builder()
        .seed(1).updater(Adam(1e-2)).list()
        .layer(DenseLayer(n_in=4, n_out=8, activation="relu"))
        .layer(OutputLayer(n_in=8, n_out=3, activation="softmax",
                           loss_function="mcxent"))
        .build())
net = MultiLayerNetwork(conf).init()
reg = ModelRegistry()
reg.deploy("v1", net, sample_input=np.zeros((1, 4), dtype="f4"),
           batch_limit=4, max_wait_ms=1.0)
door = FrontDoor(ServingRouter(reg, "v1"), None, port=0).start()
addr = f"http://127.0.0.1:{door.port}"
body = json.dumps({"inputs": [[0.1, 0.2, 0.3, 0.4]]}).encode()

stop = threading.Event()


def beat_loop():                  # the sync-beat cadence, drill-scaled
    while not stop.is_set():
        global_watchtower().beat()
        stop.wait(0.05)


beater = threading.Thread(target=beat_loop, daemon=True)
beater.start()


def one(i):
    req = urllib.request.Request(
        addr + "/v1/classify", data=body,
        headers={"Content-Type": "application/json"})
    with urllib.request.urlopen(req, timeout=30.0) as r:
        r.read()


for i in range(10):               # compile + socket churn outside the window
    one(i)
t0 = time.perf_counter()
for i in range(steps):
    one(i)
wall = time.perf_counter() - t0
stop.set()
beater.join(timeout=2.0)
door.stop()
reg.shutdown()
print(json.dumps({"seconds_per_step": wall / steps,
                  "watchtower": os.environ.get("DL4J_TPU_WATCHTOWER",
                                               "1")}))
"""

#: watchtower A/B arm -> env overrides. Both arms run the beat thread;
#: with =0 every beat is a no-op (the byte-identical pre-watchtower
#: posture), with =1 the scrape + detectors + lifecycle run at drill
#: cadence concurrently with the request path.
WATCHTOWER_MODES = {
    "wt_off": {"DL4J_TPU_WATCHTOWER": "0"},
    "wt_on": {"DL4J_TPU_WATCHTOWER": "1",
              "DL4J_TPU_WATCHTOWER_INTERVAL_S": "0.1",
              "DL4J_TPU_TIMESERIES_INTERVAL_S": "0.1"},
}


def watchtower_ab(steps: int, repeats: int, as_json: bool) -> float:
    """Interleaved min-of-N A/B (rotating arm order — the noisy-box
    protocol): does the watchtower machinery (periodic registry scrape
    into the timeseries rings + burn/change-point detectors + alert
    lifecycle, beating at drill cadence on a background thread) keep
    per-request front-door latency under the 2% bar?"""
    best = _interleaved_min(
        list(WATCHTOWER_MODES), repeats,
        lambda m: _run_worker(_WATCHTOWER_WORKER, [steps],
                              WATCHTOWER_MODES[m]))
    overhead = ((best["wt_on"] - best["wt_off"])
                / best["wt_off"] * 100.0)
    result = {"request_seconds_watchtower_off": best["wt_off"],
              "request_seconds_watchtower_on": best["wt_on"],
              "watchtower_overhead_percent": overhead,
              "steps": steps, "repeats": repeats}
    if as_json:
        print(json.dumps(result, indent=2))
    else:
        print(f"watchtower A/B (/v1/classify under a 10 Hz beat, {steps} "
              f"requests/arm, min of {repeats} interleaved repeats)")
        print(f"  watchtower off (DL4J_TPU_WATCHTOWER=0):  "
              f"{best['wt_off'] * 1e3:8.3f} ms/request")
        print(f"  watchtower on  (scrape + detectors):     "
              f"{best['wt_on'] * 1e3:8.3f} ms/request")
        print(f"  watchtower overhead: {overhead:+.2f}%  (bar: < 2%)")
    return overhead


#: mode name -> env overrides on top of the caller's environment
MODES = {
    "off": {"DL4J_TPU_METRICS": "0"},
    "no_trace": {"DL4J_TPU_METRICS": "1", "DL4J_TPU_TRACE": "0"},
    "no_obs": {"DL4J_TPU_METRICS": "1", "DL4J_TPU_TRACE": "1",
               "DL4J_TPU_NUMERICS": "0", "DL4J_TPU_COMPILE_WATCH": "0"},
    "no_res": {"DL4J_TPU_METRICS": "1", "DL4J_TPU_TRACE": "1",
               "DL4J_TPU_NUMERICS": "1", "DL4J_TPU_COMPILE_WATCH": "1",
               "DL4J_TPU_RESILIENCE": "0"},
    "no_cost": {"DL4J_TPU_METRICS": "1", "DL4J_TPU_TRACE": "1",
                "DL4J_TPU_NUMERICS": "1", "DL4J_TPU_COMPILE_WATCH": "1",
                "DL4J_TPU_RESILIENCE": "1", "DL4J_TPU_COST_MODEL": "0"},
    "on": {"DL4J_TPU_METRICS": "1", "DL4J_TPU_TRACE": "1",
           "DL4J_TPU_NUMERICS": "1", "DL4J_TPU_COMPILE_WATCH": "1",
           "DL4J_TPU_RESILIENCE": "1", "DL4J_TPU_COST_MODEL": "1"},
}


def _run(steps: int, batch: int, mode: str) -> float:
    return _run_worker(_WORKER, [steps, batch], MODES[mode])


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--steps", type=int, default=30)
    ap.add_argument("--batch", type=int, default=32)
    ap.add_argument("--repeats", type=int, default=3,
                    help="interleaved mode quadruples; min per mode wins")
    ap.add_argument("--json", action="store_true")
    ap.add_argument("--elastic-ab", action="store_true",
                    help="run the elastic async-checkpoint A/B instead "
                         "of the kill-switch ladder")
    ap.add_argument("--warmup-ab", action="store_true",
                    help="run the serving AOT-warmup A/B: first-request "
                         "latency with vs. without deploy warmup")
    ap.add_argument("--fleet-obs-ab", action="store_true",
                    help="run the fleet-observability A/B: front-door "
                         "request latency with DL4J_TPU_FLEET_OBS=0 vs 1")
    ap.add_argument("--trace-store-ab", action="store_true",
                    help="run the trace-store A/B: front-door request "
                         "latency with DL4J_TPU_TRACE_STORE=0 vs 1")
    ap.add_argument("--watchtower-ab", action="store_true",
                    help="run the watchtower A/B: front-door request "
                         "latency with DL4J_TPU_WATCHTOWER=0 vs 1 under "
                         "a drill-cadence beat thread")
    ap.add_argument("--session-ab", action="store_true",
                    help="run the durable-session A/B: steady-state "
                         "generate latency with DL4J_TPU_SESSIONS=0 "
                         "vs 1 (journal attached to a live store)")
    ap.add_argument("--save-every", type=int, default=8,
                    help="elastic A/B checkpoint cadence in steps (the "
                         "perf posture; the exact-resume drills save "
                         "every step)")
    args = ap.parse_args()

    if args.elastic_ab:
        return elastic_ab(args.steps, args.batch, args.repeats, args.json,
                          args.save_every)
    if args.warmup_ab:
        return warmup_ab(args.batch, args.repeats, args.json)
    if args.fleet_obs_ab:
        return fleet_obs_ab(max(args.steps, 60), args.repeats, args.json)
    if args.trace_store_ab:
        return trace_store_ab(max(args.steps, 60), args.repeats, args.json)
    if args.watchtower_ab:
        # a longer window than the other request A/Bs: the beat thread
        # fires every 100 ms, so a 60-request (~0.2 s) window would
        # sample 2 beats and grade scheduler noise instead
        return watchtower_ab(max(args.steps, 200), args.repeats,
                             args.json)
    if args.session_ab:
        # floors: the per-request deltas at stake are ~100us, below the
        # jitter of a fresh-subprocess min-of-3 — 60 requests x 5
        # interleaved repeats keeps the estimator noise under the bar
        return session_ab(max(args.steps, 60), max(args.repeats, 5),
                          args.json)

    # a lone run is dominated by host warmup noise (the first subprocess
    # routinely runs 1.5x slower than steady state regardless of mode) —
    # the shared rotating-order min-of-N protocol handles it
    best = _interleaved_min(
        list(MODES), args.repeats,
        lambda m: _run(args.steps, args.batch, m))
    overhead = (best["on"] - best["off"]) / best["off"] * 100.0
    trace_overhead = ((best["on"] - best["no_trace"])
                      / best["no_trace"] * 100.0)
    obs_overhead = (best["on"] - best["no_obs"]) / best["no_obs"] * 100.0
    res_overhead = (best["on"] - best["no_res"]) / best["no_res"] * 100.0
    cost_overhead = (best["on"] - best["no_cost"]) / best["no_cost"] * 100.0
    result = {"lenet_step_seconds_uninstrumented": best["off"],
              "lenet_step_seconds_metrics_only": best["no_trace"],
              "lenet_step_seconds_no_observatory": best["no_obs"],
              "lenet_step_seconds_no_resilience": best["no_res"],
              "lenet_step_seconds_no_cost_model": best["no_cost"],
              "lenet_step_seconds_instrumented": best["on"],
              "overhead_percent": overhead,
              "trace_overhead_percent": trace_overhead,
              "observatory_overhead_percent": obs_overhead,
              "resilience_overhead_percent": res_overhead,
              "cost_overhead_percent": cost_overhead,
              "steps": args.steps, "batch": args.batch}
    if args.json:
        print(json.dumps(result, indent=2))
    else:
        print(f"lenet step, batch={args.batch}, {args.steps} steps/mode")
        print(f"  uninstrumented (DL4J_TPU_METRICS=0): "
              f"{best['off'] * 1e3:8.3f} ms")
        print(f"  metrics only   (DL4J_TPU_TRACE=0):   "
              f"{best['no_trace'] * 1e3:8.3f} ms")
        print(f"  no observatory (NUMERICS=0, COMPILE_WATCH=0): "
              f"{best['no_obs'] * 1e3:8.3f} ms")
        print(f"  no resilience  (DL4J_TPU_RESILIENCE=0):       "
              f"{best['no_res'] * 1e3:8.3f} ms")
        print(f"  no cost model  (DL4J_TPU_COST_MODEL=0):       "
              f"{best['no_cost'] * 1e3:8.3f} ms")
        print(f"  instrumented   (default):            "
              f"{best['on'] * 1e3:8.3f} ms")
        print(f"  total overhead: {overhead:+.2f}%  (bar: < 5%)")
        print(f"  trace-context overhead: {trace_overhead:+.2f}%  "
              f"(bar: < 2%)")
        print(f"  observatory overhead (numerics + compile watch): "
              f"{obs_overhead:+.2f}%  (bar: < 2%)")
        print(f"  resilience overhead (policies armed, no faults): "
              f"{res_overhead:+.2f}%  (bar: < 2%)")
        print(f"  cost-observatory overhead (MFU feed + AOT cost "
              f"lowering): {cost_overhead:+.2f}%  (bar: < 2%)")
    return overhead


if __name__ == "__main__":
    main()
