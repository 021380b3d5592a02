"""Device-timed sweep of the attention kernels against XLA attention (run on
the chip; feeds ``default_blocks`` in kernels/flash_attention.py and
``FLASH_MIN_SEQ`` in models/transformer.py; the table is in PERF.md).

Causal, head size 64, bfloat16, (B, T, H, hd) operands as the model's
projections make them. Two parts, one profile each:

- ``tune``: forward alone and forward + backward (a vjp with a random
  cotangent) over block sizes, per T, at B x H = 8 x 16;
- ``cross``: the kernels at the best blocks found, XLA attention
  (``_plain_attention``) and, with ``--old <path to a flash_attention.py>``,
  an older kernel at its own defaults, over T and B x H.

Times are device seconds of the jitted module (the profile's "XLA Modules"
line), the median of five runs. A variant that does not fit the chip is
tried again with fewer rows and says so; ``us`` is microseconds a (row,
head), which carries between row counts.

    python benchmarks/flash_crossover.py [--old PATH] [--out FILE]
"""
import argparse
import glob
import importlib
import importlib.util
import json
import os
import re
import statistics
import sys
import tempfile

import jax
import jax.numpy as jnp

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))
from deeplearning4j_tpu.parallel.ring import _plain_attention  # noqa: E402

# the package re-exports the function under the module's name
fa = importlib.import_module("deeplearning4j_tpu.kernels.flash_attention")
HD = 64
SEQS = (128, 256, 512, 768, 1024, 2048, 4096, 8192)
SIZES = (128, 256, 512, 1024)
RUNS = 5


def operands(b, t, h):
    keys = jax.random.split(jax.random.key(t * 131 + h), 4)
    return tuple(jax.random.normal(k, (b, t, h, HD), jnp.bfloat16)
                 for k in keys)


def variant(tag, fn, grad):
    """A jitted module named ``tag``: the forward, or a vjp of it."""
    if grad:
        def run(q, k, v, do):
            return jax.vjp(fn, q, k, v)[1](do)
    else:
        def run(q, k, v, do):
            return fn(q, k, v)
    run.__name__ = tag
    return jax.jit(run)


def kernels(fwd, bwd):
    def fn(q, k, v):
        b, t, h, hd = q.shape
        o = fa._flash(*(x.reshape(b, t, h * hd) for x in (q, k, v)),
                      128 // hd, 128, hd ** -0.5, True, fwd, bwd)
        return o.reshape(q.shape)
    return fn


def xla(q, k, v):
    return _plain_attention(q, k, v, causal=True)


def older(module, transposes):
    """An older ``flash_attention`` over (B, H, T, hd): as the model called
    it (operands and result transposed), or handed that layout outright."""
    def fn(q, k, v):
        o = module.flash_attention(*(x.transpose(0, 2, 1, 3)
                                     for x in (q, k, v)), causal=True)
        return o.transpose(0, 2, 1, 3)

    def bare(q, k, v):      # the (B, T, H, hd) operands read as (B, H, T, hd)
        b, t, h, hd = q.shape
        return module.flash_attention(*(x.reshape(b, h, t, hd)
                                        for x in (q, k, v)),
                                      causal=True).reshape(q.shape)
    return fn if transposes else bare


def measure(jobs):
    """jobs: [(tag, fn, grad, rows, t, heads)] -> {tag: (median device
    seconds, rows it ran with)}; a job that fails at 8 rows runs at 2."""
    ready, out = [], {}
    for tag, fn, grad, rows, t, h in jobs:
        for b in (rows, 2, 1):
            if b > rows:
                continue
            try:
                args = operands(b, t, h)
                run = variant(tag, fn, grad)
                jax.block_until_ready(run(*args))
                ready.append((tag, run, b, t, h))
                break
            except Exception as e:      # does not fit, or does not compile
                print(f"{tag}: {b} rows: {type(e).__name__}: "
                      f"{str(e)[:160]}", flush=True)
    log_dir = tempfile.mkdtemp(prefix="flash_sweep_")
    jax.profiler.start_trace(log_dir)
    for tag, run, b, t, h in ready:
        args = operands(b, t, h)
        for _ in range(RUNS):
            r = run(*args)
        jax.block_until_ready(r)
    jax.profiler.stop_trace()
    from jax.profiler import ProfileData
    path = sorted(glob.glob(os.path.join(
        log_dir, "plugins", "profile", "*", "*.xplane.pb")))[-1]
    times = {}
    for plane in ProfileData.from_file(path).planes:
        if not plane.name.startswith("/device:TPU:0"):
            continue
        for line in plane.lines:
            if line.name != "XLA Modules":
                continue
            for ev in line.events:
                m = re.match(r"jit_(\w+)\(", ev.name)
                if m:
                    times.setdefault(m.group(1), []).append(
                        ev.duration_ns * 1e-9)
    for tag, _run, b, _t, _h in ready:
        if tag in times:
            out[tag] = (statistics.median(times[tag]), b)
    return out


def block_pairs(t):
    sizes = [s for s in SIZES if s <= t and t % s == 0
             and (t < 2048 or s >= 256)]
    return [(a, b) for a in sizes for b in sizes]


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--old")
    ap.add_argument("--out", default="chiprun_out/flash_sweep.json")
    ap.add_argument("--seqs", type=int, nargs="*", default=list(SEQS))
    opts = ap.parse_args()
    dev = jax.devices()[0]
    print(f"device: {dev.platform} {dev.device_kind}", flush=True)
    if dev.platform != "tpu":
        raise SystemExit("device times come from a chip only")
    result = {"device": dev.device_kind, "tune": {}, "cross": {}}

    # ---- tune: forward over its blocks; backward over its own, beside a
    # fixed forward whose time is taken off
    jobs = []
    for t in opts.seqs:
        base = (min(256, t), min(256, t))
        for p in block_pairs(t):
            jobs.append((f"f_{t}_{p[0]}_{p[1]}", kernels(p, p), False, 8, t,
                         16))
            jobs.append((f"g_{t}_{p[0]}_{p[1]}", kernels(base, p), True, 8,
                         t, 16))
    got = measure(jobs)
    best = {}
    for t in opts.seqs:
        base = f"f_{t}_{min(256, t)}_{min(256, t)}"
        rows = {}
        for p in block_pairs(t):
            f, g = got.get(f"f_{t}_{p[0]}_{p[1]}"), got.get(
                f"g_{t}_{p[0]}_{p[1]}")
            rows[f"{p[0]}x{p[1]}"] = {
                "fwd_ms": f and 1e3 * f[0],
                "bwd_ms": g and base in got and 1e3 * (g[0] - got[base][0])}
            print(f"tune T={t} blocks {p[0]}x{p[1]}: fwd "
                  f"{rows[f'{p[0]}x{p[1]}']['fwd_ms']} ms, bwd "
                  f"{rows[f'{p[0]}x{p[1]}']['bwd_ms']} ms", flush=True)
        result["tune"][t] = rows

        def argmin(key):
            ok = {k: v[key] for k, v in rows.items() if v[key]}
            name = min(ok, key=ok.get) if ok else f"{min(256, t)}x{min(256, t)}"
            return tuple(int(x) for x in name.split("x"))
        best[t] = (argmin("fwd_ms"), argmin("bwd_ms"))
        print(f"tune T={t}: best forward {best[t][0]}, backward "
              f"{best[t][1]}", flush=True)
    result["best"] = {t: list(map(list, b)) for t, b in best.items()}

    # ---- cross: kernels at their best blocks, XLA attention, the old kernel
    old = None
    if opts.old:
        spec = importlib.util.spec_from_file_location("old_flash", opts.old)
        old = importlib.util.module_from_spec(spec)
        spec.loader.exec_module(old)
    jobs = []
    for t in opts.seqs:
        for h in (16, 10):
            for grad in (False, True):
                m = "g" if grad else "f"
                jobs.append((f"new_{m}_{t}_{h}", kernels(*best[t]), grad, 8,
                             t, h))
                jobs.append((f"xla_{m}_{t}_{h}", xla, grad, 8, t, h))
                if old is not None and t >= 1024:
                    jobs.append((f"old_{m}_{t}_{h}", older(old, True), grad,
                                 8, t, h))
                    jobs.append((f"oldbare_{m}_{t}_{h}", older(old, False),
                                 grad, 8, t, h))
    got = measure(jobs)
    for t in opts.seqs:
        for h in (16, 10):
            for m in ("f", "g"):
                row = {}
                for impl in ("new", "xla", "old", "oldbare"):
                    r = got.get(f"{impl}_{m}_{t}_{h}")
                    if r:
                        row[impl] = {"ms": 1e3 * r[0], "rows": r[1],
                                     "us": 1e6 * r[0] / (r[1] * h)}
                result["cross"][f"{t}_{h}_{m}"] = row
                print(f"cross T={t} H={h} {'fwd+bwd' if m == 'g' else 'fwd'}"
                      ": " + ", ".join(
                          f"{k} {v['ms']:.3f} ms ({v['rows']} rows, "
                          f"{v['us']:.2f} us)" for k, v in row.items()),
                      flush=True)
    os.makedirs(os.path.dirname(opts.out) or ".", exist_ok=True)
    with open(opts.out, "w") as f:
        json.dump(result, f, indent=1)
    print(f"wrote {opts.out}", flush=True)


if __name__ == "__main__":
    main()
