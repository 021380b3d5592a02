"""ResNet-50 training throughput: our zoo ComputationGraph vs flax.linen.

BASELINE.md north-star row 1: "DL4J-zoo ResNet-50 train throughput
(images/sec/chip) ≥70% of JAX/Flax reference". Both sides run the same
optimizer (SGD+momentum), same batch/dtype, and are measured INTERLEAVED
(A,B,A,B…) with a per-window loss VALUE fetch as the sync point (bench.py's
rule). Prints one JSON line.

Both sides sync per STEP (net.fit fetches its score scalar every batch, so
the flax denominator fetches its loss every step too).

On TPU the printed value/vs_baseline are overridden by DEVICE-side timing
(one traced window per side parsed from the XPlane, BASELINE round-3
protocol) whenever the trace parses; ``timing_source`` records which path
produced the numbers.

Run: python benchmarks/resnet_bench.py [--smoke]   (--smoke: tiny CPU config)
"""
from __future__ import annotations

import argparse
import json
import os
import statistics
import sys
import time

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))



def _flax_resnet50(num_classes, dtype):
    import flax.linen as fnn
    import jax.numpy as jnp

    class Bottleneck(fnn.Module):
        filters: int
        stride: int = 1
        project: bool = False

        @fnn.compact
        def __call__(self, x, train=True):
            f = self.filters
            r = x
            y = fnn.Conv(f, (1, 1), use_bias=False, dtype=dtype)(x)
            y = fnn.BatchNorm(use_running_average=not train, dtype=dtype)(y)
            y = fnn.relu(y)
            y = fnn.Conv(f, (3, 3), strides=(self.stride, self.stride),
                         padding="SAME", use_bias=False, dtype=dtype)(y)
            y = fnn.BatchNorm(use_running_average=not train, dtype=dtype)(y)
            y = fnn.relu(y)
            y = fnn.Conv(4 * f, (1, 1), use_bias=False, dtype=dtype)(y)
            y = fnn.BatchNorm(use_running_average=not train, dtype=dtype)(y)
            if self.project or self.stride != 1:
                r = fnn.Conv(4 * f, (1, 1),
                             strides=(self.stride, self.stride),
                             use_bias=False, dtype=dtype)(r)
                r = fnn.BatchNorm(use_running_average=not train,
                                  dtype=dtype)(r)
            return fnn.relu(y + r)

    class ResNet50(fnn.Module):
        @fnn.compact
        def __call__(self, x, train=True):
            x = fnn.Conv(64, (7, 7), strides=(2, 2), padding=[(3, 3), (3, 3)],
                         use_bias=False, dtype=dtype)(x)
            x = fnn.BatchNorm(use_running_average=not train, dtype=dtype)(x)
            x = fnn.relu(x)
            x = fnn.max_pool(x, (3, 3), strides=(2, 2), padding="SAME")
            for i, (f, n) in enumerate([(64, 3), (128, 4), (256, 6),
                                        (512, 3)]):
                for b in range(n):
                    x = Bottleneck(f, stride=(2 if b == 0 and i > 0 else 1),
                                   project=(b == 0))(x, train)
            x = x.mean(axis=(1, 2))
            return fnn.Dense(num_classes, dtype=jnp.float32)(x)

    return ResNet50()


def measure_flax(img_hw, num_classes, batch, iters, lr, dtype="float32"):
    import jax
    import jax.numpy as jnp
    import numpy as np
    import optax

    # same alias canonicalization as our side (nn/_precision)
    from deeplearning4j_tpu.nn._precision import _COMPUTE_DTYPES
    model = _flax_resnet50(
        num_classes, _COMPUTE_DTYPES.get(dtype, jnp.float32))
    rng = np.random.default_rng(0)
    x = jnp.asarray(rng.normal(size=(batch,) + img_hw + (3,)), jnp.float32)
    y = jax.nn.one_hot(
        jnp.asarray(rng.integers(0, num_classes, (batch,))), num_classes)
    variables = model.init(jax.random.key(0), x)
    params, batch_stats = variables["params"], variables["batch_stats"]
    opt = optax.sgd(lr, momentum=0.9, nesterov=True)  # = ours (Nesterovs)
    opt_state = jax.jit(opt.init)(params)

    def loss_fn(p, bs, x, y):
        logits, upd = model.apply({"params": p, "batch_stats": bs}, x,
                                  mutable=["batch_stats"])
        logp = jax.nn.log_softmax(logits)
        return -jnp.mean(jnp.sum(y * logp, axis=-1)), upd["batch_stats"]

    import functools

    @functools.partial(jax.jit, donate_argnums=(0, 1, 2))
    def step(p, bs, s, x, y):
        (loss, bs), g = jax.value_and_grad(loss_fn, has_aux=True)(p, bs, x, y)
        up, s = opt.update(g, s, p)
        return optax.apply_updates(p, up), bs, s, loss

    state = (params, batch_stats, opt_state)
    p, bs, s, loss = step(*state, x, y)
    float(loss)
    state = (p, bs, s)

    def window():
        nonlocal state
        p, bs, s = state
        t0 = time.perf_counter()
        for _ in range(iters):
            p, bs, s, loss = step(p, bs, s, x, y)
            float(loss)   # per-STEP fetch, matching net.fit's score sync
        state = (p, bs, s)
        return batch * iters / (time.perf_counter() - t0)

    return window


def measure_ours(img_hw, num_classes, batch, iters, lr, dtype="float32"):
    import numpy as np

    from deeplearning4j_tpu.models import zoo
    from deeplearning4j_tpu.optim.updaters import Nesterovs

    m = zoo.ResNet50(num_classes=num_classes,
                     input_shape=img_hw + (3,),
                     updater=Nesterovs(lr, momentum=0.9),
                     data_type=dtype)
    net = m.init_model()
    rng = np.random.default_rng(0)
    x = rng.normal(size=(batch,) + img_hw + (3,)).astype(np.float32)
    y = np.eye(num_classes, dtype=np.float32)[
        rng.integers(0, num_classes, batch)]
    net.fit(x, y)                         # warm/compile

    def window():
        t0 = time.perf_counter()
        for _ in range(iters):
            net.fit(x, y)                 # each fit syncs on float(loss)
        return batch * iters / (time.perf_counter() - t0)

    return window


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--smoke", action="store_true",
                    help="tiny CPU config (CI/dev)")
    args = ap.parse_args()

    import jax

    if args.smoke:
        jax.config.update("jax_platforms", "cpu")
    platform = jax.devices()[0].platform
    on_tpu = platform != "cpu"
    if not (on_tpu or args.smoke):
        sys.exit("[resnet-bench] no accelerator (platform=cpu); "
                 "--smoke runs the tiny CPU config")
    print(f"[resnet-bench] platform={platform}", file=sys.stderr)

    if args.smoke or not on_tpu:
        img_hw, classes, batch, iters, repeats = (32, 32), 10, 4, 3, 2
        dtype = "float32"
    else:
        # bf16 compute on TPU (MXU rate); both sides use the same policy
        img_hw, classes, batch, iters, repeats = (224, 224), 1000, 32, 10, 3
        dtype = "bfloat16"

    ours = measure_ours(img_hw, classes, batch, iters, 0.1, dtype=dtype)
    flax_w = measure_flax(img_hw, classes, batch, iters, 0.1, dtype=dtype)

    ours_runs, flax_runs = [], []
    for _ in range(repeats):
        ours_runs.append(ours())
        flax_runs.append(flax_w())
    ours_ips = statistics.median(ours_runs)
    flax_ips = statistics.median(flax_runs)

    # device-side timing (BASELINE round-3 protocol): XPlane module
    # durations are the chip's own clock; ours jits _train_step,
    # flax jits step — distinct module names
    ours_dev = flax_dev = None
    can_parse = True
    try:
        from tensorflow.tsl.profiler.protobuf import xplane_pb2  # noqa: F401
    except Exception:
        can_parse = False   # don't burn two traced TPU windows for nothing
    if on_tpu and can_parse:
        from device_timing import measure_device_step
        r = measure_device_step(lambda: ours(), "jit__train_step")
        if r:
            ours_dev = batch / r["median_s"]
        r = measure_device_step(lambda: flax_w(), "jit_step")
        if r:
            flax_dev = batch / r["median_s"]
        if ours_dev and flax_dev:
            ours_ips, flax_ips = ours_dev, flax_dev

    print(json.dumps({
        "metric": "resnet50_train_images_per_sec",
        "value": round(ours_ips, 2),
        "unit": "images/sec",
        "vs_baseline": round(ours_ips / flax_ips, 3),
        "flax_images_per_sec": round(flax_ips, 2),
        "timing_source": "device_trace" if (on_tpu and ours_dev and flax_dev)
                         else "host_value_fetch",
        "platform": platform,
        "config": {"img": list(img_hw), "classes": classes, "batch": batch,
                   "dtype": dtype},
    }))


if __name__ == "__main__":
    main()
