"""GPipe bubble-fraction measurement (VERDICT r3 #10: PP efficiency must be
evidenced, not asserted).

Theory: with S stages and M micro-batches, the GPipe schedule idles each
device for (S-1) of (M+S-1) ticks — bubble = (S-1)/(M+S-1), so throughput
at fixed global batch should scale ∝ (M+S-1)⁻¹·M ticks of useful work.
This harness measures a pipelined train step at fixed GLOBAL batch while
sweeping M, reports per-step wall time, implied utilisation vs the best
rung, and the theoretical bubble — one JSON line per M.

Run (virtual mesh):  JAX_PLATFORMS=cpu python benchmarks/pipeline_bubble.py
     (on TPU set stages to the real chip count)
"""
from __future__ import annotations

import argparse
import json
import os
import statistics
import sys
import time

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--stages", type=int, default=4)
    ap.add_argument("--micro", type=int, nargs="*", default=[4, 8, 16, 32])
    ap.add_argument("--layers", type=int, default=8)
    ap.add_argument("--d-model", type=int, default=128)
    ap.add_argument("--seq", type=int, default=128)
    ap.add_argument("--global-batch", type=int, default=32)
    ap.add_argument("--repeats", type=int, default=3)
    ap.add_argument("--iters", type=int, default=4)
    args = ap.parse_args()

    import jax

    if os.environ.get("JAX_PLATFORMS") == "cpu":
        jax.config.update("jax_num_cpu_devices", max(8, args.stages))
    import jax.numpy as jnp
    import numpy as np
    import optax

    from deeplearning4j_tpu.models.transformer import (TransformerConfig,
                                                       TransformerLM)
    from deeplearning4j_tpu.parallel.mesh import MeshSpec, STAGE_AXIS

    S = args.stages
    mesh = MeshSpec({STAGE_AXIS: S}).build(jax.devices()[:S])
    print(f"# platform={jax.devices()[0].platform} stages={S}",
          file=sys.stderr)

    rng = np.random.default_rng(0)
    toks_np = rng.integers(0, 1024, (args.global_batch, args.seq))
    rows = []
    for M in args.micro:
        if args.global_batch % M:
            continue
        cfg = TransformerConfig(
            vocab_size=1024, n_layers=args.layers,
            n_heads=4, d_model=args.d_model, max_len=args.seq,
            pipeline_stages=S, microbatches=M)
        model = TransformerLM(cfg, mesh)
        params = model.init_params(jax.random.key(0))
        params = jax.device_put(params, model.param_shardings(mesh))
        opt = optax.adamw(1e-3)
        opt_state = jax.jit(opt.init)(params)
        step = model.make_train_step(opt)
        toks = jnp.asarray(toks_np, jnp.int32)
        tgts = jnp.roll(toks, -1, axis=1)
        # XLA's own memory accounting for the compiled step: temp bytes =
        # live activations/workspace. Validates the O(M/S)-microbatch queue
        # claim with compiler numbers rather than arithmetic.
        temp_mib = None
        try:
            ma = step.lower(params, opt_state, toks,
                            tgts).compile().memory_analysis()
            if ma is not None:
                temp_mib = round(ma.temp_size_in_bytes / 2**20, 1)
        except Exception as e:
            print(f"# memory_analysis unavailable: {e!r}", file=sys.stderr)
        p, s, loss = step(params, opt_state, toks, tgts)   # compile+warm
        float(loss)
        runs = []
        for _ in range(args.repeats):
            t0 = time.perf_counter()
            for _ in range(args.iters):
                p, s, loss = step(p, s, toks, tgts)
            float(loss)                                    # value-fetch sync
            runs.append((time.perf_counter() - t0) / args.iters)
        step_s = statistics.median(runs)
        rows.append((M, step_s))
        print(json.dumps({
            "metric": "gpipe_step_seconds", "microbatches": M,
            "stages": S, "global_batch": args.global_batch,
            "step_s": round(step_s, 4),
            "bubble_theory": round((S - 1) / (M + S - 1), 4),
            "tokens_per_sec": round(args.global_batch * args.seq / step_s,
                                    1),
            "xla_temp_mib": temp_mib,
        }), flush=True)
    if len(rows) >= 2:
        # utilisation vs the best rung: the measured analog of 1-bubble
        best = min(s for _, s in rows)
        print(json.dumps({
            "metric": "gpipe_bubble_summary",
            "per_microbatch_utilisation": {
                str(m): round(best / s, 3) for m, s in rows},
            "expected_utilisation_ratio": {
                str(m): round((1 - (S - 1) / (m + S - 1))
                              / max(1 - (S - 1) / (mm + S - 1)
                                    for mm, _ in rows), 3)
                for m, _ in rows for mm, _ in [max(rows, key=lambda r: r[0])]
            },
        }), flush=True)


if __name__ == "__main__":
    main()
