"""Gradient-sync allreduce bandwidth (BASELINE.md row 3: "measure XLA
collective over ICI; record GB/s vs theoretical").

The reference's gradient-sharing transport (Aeron UDP mesh + threshold
codec, SURVEY P3/J13) is replaced by GSPMD-emitted dense allreduce; this
microbench measures that path directly: a psum over the ``data`` axis of a
parameter-sized f32 buffer, device-timed (XPlane) when possible.

On a real multi-chip slice the number is ICI bandwidth; on the virtual CPU
mesh it validates the harness (numbers are host-memory-bound and labeled as
such). Algorithmic bytes for a ring allreduce: 2·(n-1)/n · size per chip.

Run: python benchmarks/allreduce_bench.py [--devices N] [--mb SIZE_MB]

``--compressed-ab`` adds the ISSUE 7 dense-vs-compressed exchange A/B:
the dense f32 psum against the error-feedback threshold exchange
(encode to an int8 sign mask + per-bucket scale, psum the signs, decode
— the exact in-graph pipeline of ShardedTrainer's compressed step).
Repeats are INTERLEAVED (dense, compressed, dense, ...) and scored
min-of-N: this box drifts ±40%, and back-to-back blocks hand whichever
mode runs second a systematic advantage. Results are archived under
``benchmarks/ab/allreduce_compress_ab.json``.
"""
from __future__ import annotations

import argparse
import json
import os
import statistics
import sys
import time

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))
sys.path.insert(0, os.path.dirname(os.path.abspath(__file__)))


HERE = os.path.dirname(os.path.abspath(__file__))


def _compressed_ab(mesh, n, elems, repeats=7):
    """Interleaved min-of-N dense-vs-compressed exchange timing on the
    built mesh. Returns the result dict (archived by the caller)."""
    import jax
    import jax.numpy as jnp
    import numpy as np
    from jax import shard_map
    from jax.sharding import NamedSharding, PartitionSpec as P

    from deeplearning4j_tpu.parallel import compression as comp

    rng = np.random.default_rng(0)
    x = jax.device_put(
        jnp.asarray(rng.standard_normal((n, elems)) * 1e-3, jnp.float32),
        NamedSharding(mesh, P("data")))
    thr = 1e-3
    wdt = comp.wire_dtype(n)

    @jax.jit
    def dense(x):
        f = shard_map(lambda s: jax.lax.psum(s, "data"), mesh=mesh,
                      in_specs=P("data", None), out_specs=P("data", None))
        return f(x.reshape(n, 1, elems)).reshape(n, elems)

    @jax.jit
    def compressed(x):
        def body(s):
            # the trainer's own exchange pipeline — shared fn, so this
            # A/B measures exactly what the compressed step runs
            dec, _, _, _ = comp.exchange_bucket(s.reshape(-1), thr,
                                                "data", n)
            return dec.reshape(s.shape)
        f = shard_map(body, mesh=mesh, in_specs=P("data", None),
                      out_specs=P("data", None))
        return f(x.reshape(n, 1, elems)).reshape(n, elems)

    for fn in (dense, compressed):               # warm/compile both first
        jax.block_until_ready(fn(x))

    iters = 5
    times = {"dense": [], "compressed": []}
    for _ in range(repeats):                     # interleaved, never blocked
        for name, fn in (("dense", dense), ("compressed", compressed)):
            t0 = time.perf_counter()
            o = x
            for _ in range(iters):
                o = fn(o)
            jax.block_until_ready(o)
            times[name].append((time.perf_counter() - t0) / iters)

    dense_s = min(times["dense"])
    comp_s = min(times["compressed"])
    size = elems * 4
    payload = elems * jnp.dtype(wdt).itemsize + 8
    return {
        "metric": "allreduce_compress_ab",
        "devices": n,
        "buffer_mb": round(size / (1 << 20), 2),
        "threshold": thr,
        "dense_wire_bytes": size,
        "compressed_wire_bytes": int(payload),
        "wire_ratio": round(size / payload, 2),
        "dense_min_s": round(dense_s, 6),
        "compressed_min_s": round(comp_s, 6),
        "speedup_vs_dense": round(dense_s / comp_s, 3),
        "repeats": repeats,
        "schedule": "interleaved min-of-N (this box drifts +-40%; "
                    "back-to-back blocks bias the second mode)",
    }


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--devices", type=int, default=0,
                    help="virtual device count when not on TPU (default 8)")
    ap.add_argument("--mb", type=float, default=64.0,
                    help="buffer size in MiB (default 64 ≈ a 16M-param f32 "
                         "gradient shard)")
    ap.add_argument("--compressed-ab", action="store_true",
                    help="also run the dense-vs-compressed exchange A/B "
                         "and archive it under benchmarks/ab/")
    args = ap.parse_args()

    import jax

    if os.environ.get("JAX_PLATFORMS") == "cpu":
        # the environment pinned the CPU: a virtual mesh stands in
        jax.config.update("jax_num_cpu_devices", args.devices or 8)

    import jax.numpy as jnp
    import numpy as np
    from jax import shard_map
    from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

    devs = jax.devices()
    n = len(devs)
    platform = devs[0].platform
    if n < 2:
        print(json.dumps({
            "metric": "allreduce_busbw_gbps", "value": None,
            "unit": "GB/s", "vs_baseline": None, "platform": platform,
            "note": f"single {platform} device — allreduce needs >=2; run "
                    f"on a slice or with virtual devices"}))
        return

    mesh = Mesh(np.array(devs), ("data",))
    elems = int(args.mb * (1 << 20) // 4)
    x = jax.device_put(
        jnp.arange(elems * n, dtype=jnp.float32).reshape(n, elems),
        NamedSharding(mesh, P("data")))

    @jax.jit
    def allreduce(x):
        f = shard_map(lambda s: jax.lax.psum(s, "data"), mesh=mesh,
                      in_specs=P("data", None), out_specs=P("data", None))
        return f(x.reshape(n, 1, elems)).reshape(n, elems)

    out = allreduce(x)
    jax.block_until_ready(out)           # warm/compile

    iters, runs = 5, []
    for _ in range(3):
        t0 = time.perf_counter()
        for _ in range(iters):
            out = allreduce(out)
        float(out[0, 0])                 # value fetch = sync
        runs.append((time.perf_counter() - t0) / iters)
    sec = statistics.median(runs)

    size = elems * 4
    # ring-allreduce bus bandwidth convention: 2(n-1)/n · size / time
    busbw = 2 * (n - 1) / n * size / sec / 1e9
    out_json = {
        "metric": "allreduce_busbw_gbps",
        "value": round(busbw, 2),
        "unit": "GB/s",
        "vs_baseline": None,             # v5e ICI theoretical filled on HW
        "platform": platform,
        "devices": n,
        "buffer_mb": args.mb,
        "sec_per_allreduce": round(sec, 6),
        "note": ("host-memory-bound virtual mesh (harness validation)"
                 if platform == "cpu" else
                 "ICI path; compare to v5e 1.6 TB/s ICI per chip"),
    }
    print(json.dumps(out_json))

    if args.compressed_ab:
        ab = _compressed_ab(mesh, n, elems)
        ab["platform"] = platform
        if platform == "cpu":
            ab["note"] = ("virtual CPU mesh: encode/decode compute and the "
                          "psum are host-memory-bound, so the time ratio "
                          "is NOT an interconnect signal — the wire-bytes "
                          "ratio is the durable number; device A/B lands "
                          "next TPU window")
        path = os.path.join(HERE, "ab", "allreduce_compress_ab.json")
        with open(path, "w") as f:
            json.dump(ab, f, indent=1)
        print(json.dumps(ab))


if __name__ == "__main__":
    main()
