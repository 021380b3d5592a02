"""Versioned serving with SLO-gated canary rollout and auto-rollback.

Deploys two versions of a small classifier into a ModelRegistry (each
AOT-warmed at deploy so first requests never pay an XLA compile), routes
traffic through a ServingRouter, then:

1. runs a healthy rollout — shadow scoring, canary share, ramp, full
   promotion with the old incumbent gracefully drained;
2. re-deploys the old model and rolls it out under injected canary
   faults (the ``serving.canary`` chaos point) — the SLO gate grades the
   canary degraded and auto-rolls-back with zero dropped requests.

Watch it live: the UIServer's ``/debug/deploy`` names the stage, share,
and SLO verdicts at every step; ``/metrics`` carries the per-version
series. Run: python examples/versioned_serving.py
"""
import json
import urllib.request

import numpy as np

from deeplearning4j_tpu.nn.conf.configuration import NeuralNetConfiguration
from deeplearning4j_tpu.nn.conf.layers import DenseLayer, OutputLayer
from deeplearning4j_tpu.nn.multilayer import MultiLayerNetwork
from deeplearning4j_tpu.optim.updaters import Adam
from deeplearning4j_tpu.resilience import faults
from deeplearning4j_tpu.serving import (ModelRegistry, RolloutPolicy,
                                        RolloutState, ServingRouter)
from deeplearning4j_tpu.ui.server import UIServer


def make_net(seed):
    conf = (NeuralNetConfiguration.builder()
            .seed(seed).updater(Adam(1e-2)).list()
            .layer(DenseLayer(n_in=16, n_out=32, activation="relu"))
            .layer(OutputLayer(n_in=32, n_out=4, activation="softmax",
                               loss_function="mcxent"))
            .build())
    return MultiLayerNetwork(conf).init()


def main():
    rng = np.random.RandomState(0)
    x = rng.rand(256, 16).astype("f4")
    y = np.eye(4, dtype="f4")[rng.randint(0, 4, 256)]

    net_v1, net_v2 = make_net(1), make_net(2)
    for net in (net_v1, net_v2):
        net.fit(x, y)

    ui = UIServer(port=0).start()
    registry = ModelRegistry()
    print("deploying v1 (AOT warmup)...")
    v1 = registry.deploy("v1", net_v1, sample_input=x[:1], batch_limit=16)
    print(f"  warmed buckets {v1.warmed_buckets} in "
          f"{v1.warmup_seconds:.2f}s — first requests are cache hits")
    router = ServingRouter(registry, primary="v1")

    # ---- healthy rollout: v2 advances shadow -> canary -> ramp -> full
    print("deploying v2 and starting a healthy rollout...")
    registry.deploy("v2", net_v2, sample_input=x[:1], batch_limit=16)
    rollout = router.begin_rollout("v2", RolloutPolicy(
        start_stage=RolloutState.CANARY, canary_fraction=0.3,
        ramp_fractions=(0.6,), window_requests=16, healthy_windows=1,
        min_latency_count=8, min_requests=8, min_shadow=4,
        # v1 and v2 are different models: shadow divergence is expected,
        # so this rollout starts at canary and grades latency/errors
        divergence_degraded=None, divergence_failing=None))
    i = 0
    while rollout.active and i < 400:
        router.output(x[i % 128:i % 128 + 2], request_key=i)
        i += 1
    print(f"  rollout finished at stage {rollout.stage!r} after {i} "
          f"requests; primary is now {router.primary.version!r}")

    # ---- degraded rollout: v1 again, under injected canary faults
    print("re-deploying v1 and canarying it under injected faults...")
    registry.deploy("v1b", make_net(1), sample_input=x[:1], batch_limit=16)
    rollout = router.begin_rollout("v1b", RolloutPolicy(
        start_stage=RolloutState.CANARY, canary_fraction=0.5,
        window_requests=12, min_requests=6,
        error_rate_degraded=0.2, error_rate_failing=0.5,
        divergence_degraded=None, divergence_failing=None))
    plan = faults.FaultPlan(
        [faults.FaultSpec("serving.canary", "error", rate=0.9)], seed=7)
    served = errors = 0
    with faults.active(plan):
        for i in range(200):
            if not rollout.active:
                break
            try:
                router.output(x[i % 128:i % 128 + 2], request_key=i)
                served += 1
            except faults.InjectedFault:
                errors += 1
    print(f"  {served} served, {errors} injected canary errors -> stage "
          f"{rollout.stage!r} ({rollout.rollback_reason})")

    with urllib.request.urlopen(ui.get_address() + "/debug/deploy") as r:
        deploy = json.loads(r.read())
    print("/debug/deploy versions:",
          [(v["version"], v["state"])
           for reg in deploy["registries"] for v in reg["versions"]])
    registry.shutdown()
    ui.stop()


if __name__ == "__main__":
    main()
