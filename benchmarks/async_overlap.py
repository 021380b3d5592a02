"""Async hot-path guard (acceptance tool for the async-execution PR).

A/B-measures the effect of the async runtime (device prefetch + deferred
loss fetch + multi-in-flight bucketed serving) against the fully
synchronous behavior (``DL4J_TPU_ASYNC=0``):

- **training** — lenet (and a small self-attention "transformer" net) fit
  loop over a DataSetIterator with host-side ETL cost: wall clock per step
  and the ``data_wait`` share of the step-time decomposition, both read
  from the PR-1 metrics registry. Acceptance: async reduces the data_wait
  share and improves wall clock ≥5% on the lenet loop (or documented
  parity with an explanation in benchmarks/RESULTS.md).
- **serving** — ParallelInference at ~0.3 batch occupancy: padded-compute
  waste (1 - mean examples/padded-size) under power-of-two shape buckets
  vs pad-to-``batch_limit``.

Each mode runs in a fresh subprocess: the serving pipeline threads and the
bucket-executable caches are chosen at instance construction, so flipping
the switch in-process would measure a hybrid.

Run: python benchmarks/async_overlap.py [--steps N] [--batch B]
     [--model lenet|transformer|all] [--json]
"""
from __future__ import annotations

import argparse
import json
import os
import subprocess
import sys

_TRAIN_WORKER = r"""
import json, os, sys, time
import numpy as np

model, steps, batch, etl_ms = (sys.argv[1], int(sys.argv[2]),
                               int(sys.argv[3]), float(sys.argv[4]))

from deeplearning4j_tpu.data.dataset import DataSet
from deeplearning4j_tpu.data.iterators import DataSetIterator
from deeplearning4j_tpu.observability import global_registry

rng = np.random.RandomState(0)
if model == "lenet":
    from deeplearning4j_tpu.models import zoo
    net = zoo.LeNet().init_model()
    x = rng.rand(steps * batch, 28 * 28).astype("f4")
    y = np.eye(10, dtype="f4")[rng.randint(0, 10, steps * batch)]
else:  # small self-attention net — the transformer-shaped fit loop
    from deeplearning4j_tpu.nn.conf.configuration import NeuralNetConfiguration
    from deeplearning4j_tpu.nn.conf.inputs import InputType
    from deeplearning4j_tpu.nn.conf import layers as L
    from deeplearning4j_tpu.nn.multilayer import MultiLayerNetwork
    from deeplearning4j_tpu.optim.updaters import Adam
    T, C = 32, 32
    conf = (NeuralNetConfiguration.builder()
            .seed(0).updater(Adam(1e-3)).list()
            .layer(L.SelfAttentionLayer(n_out=C, n_heads=4))
            .layer(L.DenseLayer(n_out=64, activation="relu"))
            .layer(L.GlobalPoolingLayer(pooling_type="avg"))
            .layer(L.OutputLayer(n_out=8, activation="softmax",
                                 loss_function="mcxent"))
            .set_input_type(InputType.recurrent(C, T)).build())
    net = MultiLayerNetwork(conf).init()
    x = rng.rand(steps * batch, T, C).astype("f4")
    y = np.eye(8, dtype="f4")[rng.randint(0, 8, steps * batch)]


class EtlIterator(DataSetIterator):
    '''Host-side ETL with a fixed per-batch cost (models the I/O + decode
    stage of a real input pipeline; a sleep so the cost does not compete
    with the device step for CPU on small CI boxes).'''

    def __init__(self, x, y, batch, etl_seconds):
        self.x, self.y, self.bs, self.etl = x, y, batch, etl_seconds
        self._pos = 0

    def has_next(self):
        return self._pos + self.bs <= self.x.shape[0]

    def next(self):
        i = self._pos
        self._pos += self.bs
        if self.etl:
            time.sleep(self.etl)
        xb = (self.x[i:i + self.bs] - 0.5) * 2.0   # the "decode" work
        return DataSet(xb, self.y[i:i + self.bs])

    def reset(self):
        self._pos = 0

    def batch(self):
        return self.bs


warm = EtlIterator(x[: 2 * batch], y[: 2 * batch], batch, 0.0)
net.fit(warm)                       # compile + warm caches outside window
net.score()

it = EtlIterator(x, y, batch, etl_ms / 1e3)
t0 = time.perf_counter()
net.fit(it)
net.score()                         # flush any deferred loss fetch
wall = time.perf_counter() - t0

reg = global_registry()
phase = reg.get("dl4j_training_phase_seconds")
step = reg.get("dl4j_training_step_seconds")
kind = type(net).__name__
dw = phase.labels(model=kind, phase="data_wait")
st = step.labels(model=kind)
print(json.dumps({
    "seconds_per_step": wall / steps,
    "data_wait_share": dw.sum / max(st.sum, 1e-12),
    "async": os.environ.get("DL4J_TPU_ASYNC", "1"),
}))
"""

_SERVE_WORKER = r"""
import json, os, sys
import numpy as np

batch_limit, req_size, n_req = (int(sys.argv[1]), int(sys.argv[2]),
                                int(sys.argv[3]))

from deeplearning4j_tpu.nn.conf.configuration import NeuralNetConfiguration
from deeplearning4j_tpu.nn.conf import layers as L
from deeplearning4j_tpu.nn.multilayer import MultiLayerNetwork
from deeplearning4j_tpu.observability import global_registry
from deeplearning4j_tpu.optim.updaters import Adam
from deeplearning4j_tpu.parallel.inference import (InferenceMode,
                                                   ParallelInference)

conf = (NeuralNetConfiguration.builder().seed(0).updater(Adam(1e-3)).list()
        .layer(L.DenseLayer(n_in=16, n_out=32, activation="relu"))
        .layer(L.OutputLayer(n_in=32, n_out=4, activation="softmax",
                             loss_function="mcxent")).build())
net = MultiLayerNetwork(conf).init()
rng = np.random.RandomState(0)

pi = (ParallelInference.Builder(net)
      .inference_mode(InferenceMode.BATCHED)
      .batch_limit(batch_limit).build())
try:
    # sequential requests: each forms its own window of ``req_size``
    # examples -> occupancy req_size / batch_limit
    for _ in range(n_req):
        out = pi.output(rng.rand(req_size, 16).astype("f4"))
        assert out.shape[0] == req_size
finally:
    pi.shutdown()

fill = global_registry().get("dl4j_inference_bucket_fill")
mean_fill = fill.sum / max(fill.count, 1)
print(json.dumps({
    "occupancy": req_size / batch_limit,
    "padded_waste": 1.0 - mean_fill,
    "distinct_padded_shapes": len(pi._seen_buckets),
    "async": os.environ.get("DL4J_TPU_ASYNC", "1"),
}))
"""


def _run(worker: str, args, async_mode: str) -> dict:
    env = dict(os.environ, DL4J_TPU_ASYNC=async_mode)
    out = subprocess.run(
        [sys.executable, "-c", worker] + [str(a) for a in args],
        capture_output=True, text=True, env=env, check=True)
    return json.loads(out.stdout.strip().splitlines()[-1])


def run_train(model: str, steps: int, batch: int, etl_ms: float,
              repeats: int) -> dict:
    # interleaved A/B pairs with a min-estimator (same protocol as
    # obs_overhead.py): host warmup noise cannot masquerade as a win
    offs, ons = [], []
    for _ in range(repeats):
        offs.append(_run(_TRAIN_WORKER, [model, steps, batch, etl_ms], "0"))
        ons.append(_run(_TRAIN_WORKER, [model, steps, batch, etl_ms], "1"))
    off = min(offs, key=lambda r: r["seconds_per_step"])
    on = min(ons, key=lambda r: r["seconds_per_step"])
    speedup = (off["seconds_per_step"] - on["seconds_per_step"]) \
        / off["seconds_per_step"] * 100.0
    return {"model": model,
            "sync_seconds_per_step": off["seconds_per_step"],
            "async_seconds_per_step": on["seconds_per_step"],
            "wall_clock_improvement_percent": speedup,
            "sync_data_wait_share": off["data_wait_share"],
            "async_data_wait_share": on["data_wait_share"]}


def run_serving(batch_limit: int, occupancy: float, n_req: int) -> dict:
    req = max(1, round(batch_limit * occupancy))
    off = _run(_SERVE_WORKER, [batch_limit, req, n_req], "0")
    on = _run(_SERVE_WORKER, [batch_limit, req, n_req], "1")
    return {"batch_limit": batch_limit, "request_size": req,
            "occupancy": on["occupancy"],
            "sync_padded_waste": off["padded_waste"],
            "async_padded_waste": on["padded_waste"],
            "async_distinct_padded_shapes": on["distinct_padded_shapes"]}


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--steps", type=int, default=30)
    ap.add_argument("--batch", type=int, default=32)
    ap.add_argument("--etl-ms", type=float, default=25.0,
                    help="host ETL cost per batch the prefetch can hide; "
                         "keep it a visible share of the step (on a CPU "
                         "box the 'device' step competes for the same "
                         "cores, so a tiny ETL leaves nothing to overlap)")
    ap.add_argument("--repeats", type=int, default=2)
    ap.add_argument("--model", choices=("lenet", "transformer", "all"),
                    default="lenet")
    ap.add_argument("--occupancy", type=float, default=0.3)
    ap.add_argument("--requests", type=int, default=12)
    ap.add_argument("--json", action="store_true")
    args = ap.parse_args()

    models = ("lenet", "transformer") if args.model == "all" \
        else (args.model,)
    result = {"train": [run_train(m, args.steps, args.batch, args.etl_ms,
                                  args.repeats) for m in models],
              "serving": run_serving(32, args.occupancy, args.requests)}
    if args.json:
        print(json.dumps(result, indent=2))
        return result
    for tr in result["train"]:
        print(f"{tr['model']} fit loop, {args.steps} steps, "
              f"batch={args.batch}, etl={args.etl_ms}ms:")
        print(f"  sync  (DL4J_TPU_ASYNC=0): "
              f"{tr['sync_seconds_per_step'] * 1e3:8.3f} ms/step, "
              f"data_wait share {tr['sync_data_wait_share']:.3f}")
        print(f"  async (default):          "
              f"{tr['async_seconds_per_step'] * 1e3:8.3f} ms/step, "
              f"data_wait share {tr['async_data_wait_share']:.3f}")
        print(f"  wall-clock improvement: "
              f"{tr['wall_clock_improvement_percent']:+.1f}%  "
              f"(acceptance bar: >= 5% on lenet)")
    sv = result["serving"]
    print(f"serving at occupancy {sv['occupancy']:.2f} "
          f"(requests of {sv['request_size']}, batch_limit "
          f"{sv['batch_limit']}):")
    print(f"  padded-compute waste  sync pad-to-limit: "
          f"{sv['sync_padded_waste']:.3f}   async buckets: "
          f"{sv['async_padded_waste']:.3f}   "
          f"({sv['async_distinct_padded_shapes']} compiled shape(s))")
    return result


if __name__ == "__main__":
    main()
