"""Full-size zoo training steps on the real chip (BASELINE row: "VGG16 /
Darknet19 (zoo ComputationGraph) train end-to-end, v5e"; r3 weak #6: zoo
training evidence was toy-shaped — 224² steps had never executed on
hardware).

For each architecture: build at its REAL input resolution, run one warmup
(compile) train step + ``--steps`` timed steps at batch ``--batch``, print
one JSON line with the per-step wall time and the (finite) losses. Run it
under the chip tool's timeout.

Run: python benchmarks/zoo_fullsize_step.py [--smoke]
"""
from __future__ import annotations

import argparse
import gc
import json
import os
import sys
import time

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--smoke", action="store_true", help="tiny CPU config")
    ap.add_argument("--batch", type=int, default=8)
    ap.add_argument("--steps", type=int, default=3)
    ap.add_argument("--models", nargs="*",
                    default=["ResNet50", "VGG16", "Darknet19"])
    args = ap.parse_args()

    import jax

    if args.smoke:
        jax.config.update("jax_platforms", "cpu")
    platform = jax.devices()[0].platform
    on_tpu = platform != "cpu"
    if not (on_tpu or args.smoke):
        sys.exit("[zoo-fullsize] no accelerator (platform=cpu); "
                 "--smoke runs the tiny CPU config")

    import numpy as np

    from deeplearning4j_tpu.models import zoo
    from deeplearning4j_tpu.optim.updaters import Nesterovs

    side = 32 if (args.smoke or not on_tpu) else 224
    batch = 2 if (args.smoke or not on_tpu) else args.batch
    classes = 10 if (args.smoke or not on_tpu) else 1000
    dtype = "float32" if (args.smoke or not on_tpu) else "bfloat16"
    rng = np.random.default_rng(0)
    x = rng.normal(size=(batch, side, side, 3)).astype(np.float32)
    y = np.eye(classes, dtype=np.float32)[rng.integers(0, classes, batch)]

    # BN-less VGG diverges from scratch at 1e-2 (He-init logits are large);
    # the reference trains it from pretrained weights — use a gentler lr
    lr_by_model = {"VGG16": 1e-3, "VGG19": 1e-3, "AlexNet": 1e-3}
    for name in args.models:
        t0 = time.perf_counter()
        m = net = None
        try:
            m = getattr(zoo, name)(num_classes=classes,
                                   input_shape=(side, side, 3),
                                   updater=Nesterovs(
                                       lr_by_model.get(name, 0.01),
                                       momentum=0.9),
                                   data_type=dtype)
            net = m.init_model()
            net.fit(x, y)                      # warmup = compile + step 1
            compile_s = time.perf_counter() - t0
            losses = [float(net.score())]
            t1 = time.perf_counter()
            for _ in range(args.steps):
                net.fit(x, y)
                losses.append(float(net.score()))
            step_s = (time.perf_counter() - t1) / args.steps
            print(json.dumps({
                "metric": "zoo_fullsize_train_step", "model": name,
                "platform": platform, "img": side, "batch": batch,
                "dtype": dtype, "compile_s": round(compile_s, 1),
                "step_s": round(step_s, 4),
                "images_per_sec": round(batch / step_s, 2),
                "losses": [round(l, 4) for l in losses],
                "finite": bool(np.all(np.isfinite(losses))),
            }), flush=True)
        except Exception as e:
            print(json.dumps({
                "metric": "zoo_fullsize_train_step", "model": name,
                "platform": platform, "error": str(e)[:300],
            }), flush=True)
        # free the model's buffers before the next architecture compiles
        m = net = None
        gc.collect()
        jax.clear_caches()


if __name__ == "__main__":
    main()
