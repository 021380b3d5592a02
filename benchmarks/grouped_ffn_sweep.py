"""Device-timed sweep of the grouped feed-forward kernel against the two
``lax.ragged_dot`` and the activation it replaces in a TPU decode step (run
on the chip; feeds ``default_tiles`` and the constants beside it in
kernels/grouped_ffn.py; the table is in PERF.md, section 6, PR 36).

Both forms at published widths: ``swiglu`` 2304 x 1024 x 128 held of 256, 8 a
token (Kimi-Linear) and ``relu2`` 1024 x 2688 x 128 held of 512, 22 a token
(Nemotron-3-Super), bfloat16. Rows are the pair rows of a decode step of 64
slots (512 / 1,408; ``--tokens`` for fewer live slots), group sizes drawn as a
router draws them: every token chooses k experts of the published number
without replacement, by scores with a per-expert offset (so about 96 and 115
of the 128 held get a row, as in the cells), and the pairs on absent experts
sort behind every group.

Two parts, one profile each: ``tune`` runs the kernel over its tiles (f tile,
window), ``cross`` the kernel at its defaults beside the two ``ragged_dot``,
after the two are checked against each other. Times are device seconds of the
jitted module (the profile's "XLA Modules" line), the median of five runs;
GB/s is over the touched experts' bytes, which any implementation must read.

    python benchmarks/grouped_ffn_sweep.py [--out FILE] [--tokens N ...] [--forms F ...] [--no-tune]
"""
import argparse
import glob
import json
import os
import re
import statistics
import sys
import tempfile

import jax
import jax.numpy as jnp
import numpy as np
from jax import lax

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))
from deeplearning4j_tpu.kernels import grouped_ffn as gf  # noqa: E402
from deeplearning4j_tpu.parallel.moe import _activate  # noqa: E402

HELD = 128
#: form -> (width, inner width, published experts, k a token)
FAMILIES = {"swiglu": (2304, 1024, 256, 8), "relu2": (1024, 2688, 512, 22)}
TOKENS = (64,)
RUNS = 5


def draw_sizes(tokens, experts, k, seed):
    """(rows a held expert (HELD,), pair rows in all)."""
    rng = np.random.RandomState(seed)
    score = rng.gumbel(size=(tokens, experts)) + 0.5 * rng.randn(experts)
    chosen = np.argsort(-score, axis=1)[:, :k]
    return np.bincount(chosen[chosen < HELD], minlength=HELD).astype(
        np.int32), tokens * k


def operands(form, rows, seed=0):
    w, f, _e, _k = FAMILIES[form]
    n = gf.N_FIRST[form]
    ks = jax.random.split(jax.random.key(seed), 3)
    return (jax.random.normal(ks[0], (rows, w), jnp.bfloat16),
            (0.03 * jax.random.normal(ks[1], (HELD, w, n * f))).astype(
                jnp.bfloat16),
            (0.03 * jax.random.normal(ks[2], (HELD, f, w))).astype(
                jnp.bfloat16))


def two_products(form):
    def run(rows, first, down, sizes):
        h = lax.ragged_dot(rows, first, sizes,
                           preferred_element_type=jnp.float32)
        return lax.ragged_dot(_activate(h, form, rows.dtype), down, sizes,
                              preferred_element_type=jnp.float32)
    return run


def kernel(form, tiles):
    def run(rows, first, down, sizes):
        return gf.grouped_ffn(rows, first, down, sizes, form, tiles=tiles)[0]
    return run


def named(tag, fn):
    """``fn`` jitted as a module named ``tag``."""
    def run(*args):
        return fn(*args)
    run.__name__ = tag
    return jax.jit(run)


_weights = {}


def arguments(form, rows, sizes):
    if form not in _weights:
        _weights.clear()                # one family's weights at a time
        _weights[form] = operands(form, 16)[1:]
    return (operands(form, rows)[0],) + _weights[form] + (
        jnp.asarray(sizes),)


def measure(jobs):
    """jobs: [(tag, fn, form, sizes, rows)] -> {tag: median device seconds};
    a job that does not compile or fit says so and is left out."""
    ready = []
    for tag, fn, form, sizes, rows in jobs:
        run = named(tag, fn)
        try:
            jax.block_until_ready(run(*arguments(form, rows, sizes)))
            ready.append((tag, run, form, sizes, rows))
        except Exception as e:
            print(f"{tag}: {type(e).__name__}: {str(e)[:300]}", flush=True)
    log_dir = tempfile.mkdtemp(prefix="gffn_sweep_")
    jax.profiler.start_trace(log_dir)
    for tag, run, form, sizes, rows in ready:
        args = arguments(form, rows, sizes)
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
    return {tag: statistics.median(times[tag]) for tag, *_ in ready
            if tag in times}


def tile_choices(form, rows):
    """(f tile, window): every divisor of f in whole lanes whose step stays
    under 16 MB, with windows of 16 to 128 rows."""
    w, f, _e, _k = FAMILIES[form]
    n = gf.N_FIRST[form]
    tfs = [d for d in range(128, f + 1, 128) if f % d == 0
           and (n + 1) * w * d * 2 <= (16 << 20)]
    return [(tf, ts) for tf in tfs for ts in (16, 32, 64, 128)
            if ts <= -(-rows // 16) * 16]


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--out", default="chiprun_out/grouped_ffn_sweep.json")
    ap.add_argument("--tokens", type=int, nargs="*", default=list(TOKENS))
    ap.add_argument("--forms", nargs="*", default=list(FAMILIES))
    ap.add_argument("--no-tune", action="store_true")
    opts = ap.parse_args()
    dev = jax.devices()[0]
    print(f"device: {dev.platform} {dev.device_kind}", flush=True)
    if dev.platform != "tpu":
        raise SystemExit("device times come from a chip only")
    result = {"device": dev.device_kind, "tune": {}, "cross": {}}
    cases = []
    for form in opts.forms:
        w, f, experts, k = FAMILIES[form]
        for t in opts.tokens:
            sizes, rows = draw_sizes(t, experts, k, seed=t + k)
            touched = int((sizes > 0).sum())
            n = gf.N_FIRST[form]
            cases.append((form, t, rows, sizes, touched,
                          touched * (n + 1) * w * f * 2))

    # ---- correctness on the chip, at the decode rows
    for form, t, rows, sizes, touched, _b in cases:
        args = arguments(form, rows, sizes)
        got = jax.jit(kernel(form, None))(*args)
        want = jax.jit(two_products(form))(*args)
        n_held = int(sizes.sum())
        err = float(jnp.max(jnp.abs(got[:n_held] - want[:n_held])))
        scale = float(jnp.max(jnp.abs(want[:n_held])))
        print(f"check {form} rows {rows} ({n_held} on {touched} held "
              f"experts): largest gap {err:.3e} of {scale:.3e}", flush=True)
        result.setdefault("check", {})[f"{form}_{rows}"] = [err, scale]

    def gbs(nbytes, s):
        return nbytes / s / 1e9

    if not opts.no_tune:
        jobs = []
        for form, t, rows, sizes, touched, nbytes in cases:
            for tiles in tile_choices(form, rows):
                jobs.append((f"k_{form}_{rows}_" + "_".join(map(str, tiles)),
                             kernel(form, tiles), form, sizes, rows))
        got = measure(jobs)
        for form, t, rows, sizes, touched, nbytes in cases:
            table = {}
            for tiles in tile_choices(form, rows):
                s = got.get(f"k_{form}_{rows}_" + "_".join(map(str, tiles)))
                if s:
                    table["x".join(map(str, tiles))] = {
                        "ms": 1e3 * s, "gb_s": gbs(nbytes, s)}
                    print(f"tune {form} rows {rows} tiles {tiles}: "
                          f"{1e3 * s:.3f} ms, {gbs(nbytes, s):.0f} GB/s",
                          flush=True)
            result["tune"][f"{form}_{rows}"] = table

    jobs = []
    for form, t, rows, sizes, touched, nbytes in cases:
        jobs.append((f"new_{form}_{rows}", kernel(form, None), form, sizes,
                     rows))
        jobs.append((f"old_{form}_{rows}", two_products(form), form, sizes,
                     rows))
    got = measure(jobs)
    for form, t, rows, sizes, touched, nbytes in cases:
        w, f, _e, _k = FAMILIES[form]
        tiles = gf.default_tiles(rows, w, f, gf.N_FIRST[form], HELD)
        row = {"tokens": t, "rows": rows, "rows_held": int(sizes.sum()),
               "touched": touched, "tiles": list(tiles),
               "touched_gb": nbytes / 1e9}
        for impl in ("new", "old"):
            s = got.get(f"{impl}_{form}_{rows}")
            if s:
                row[impl] = {"ms": 1e3 * s, "gb_s": gbs(nbytes, s)}
        result["cross"][f"{form}_{rows}"] = row
        print(f"cross {form} rows {rows} ({row['rows_held']} held, {touched} "
              f"touched, tiles {tiles}, "
              f"{nbytes / 1e9:.3f} GB): " + ", ".join(
                  f"{k} {row[k]['ms']:.3f} ms {row[k]['gb_s']:.0f} GB/s"
                  for k in ("new", "old") if k in row), flush=True)
    os.makedirs(os.path.dirname(opts.out) or ".", exist_ok=True)
    with open(opts.out, "w") as f:
        json.dump(result, f, indent=1)
    print(f"wrote {opts.out}", flush=True)


if __name__ == "__main__":
    main()
