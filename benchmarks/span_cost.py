"""What a ``span()`` costs, and what the decode loop's spans and counters
add to one iteration: host-side microbenchmarks, no device work.

    python3 benchmarks/span_cost.py [--package-root DIR] [--n N]

``--package-root`` imports ``deeplearning4j_tpu`` from another checkout (a
parent commit unpacked under ``.checkouts/``), so both sides of a change to
``observability/tracing.py`` are timed by the same script on the same host.
Prints one JSON line: microseconds per ``with span(): pass`` with no profile
running and with a ``jax.profiler`` trace running, and microseconds per
synthetic decode-loop iteration in the span/counter pattern of
``GenerationPipeline._iterate`` against the one-span pattern it replaced.
Each figure is the best of ``--repeats`` timings (a minimum: the host of a
one-chip machine shares its cores).
"""
from __future__ import annotations

import argparse
import json
import os
import sys
import tempfile
import time


def best_us(fn, n, repeats):
    out = []
    for _ in range(repeats):
        t0 = time.perf_counter()
        for _ in range(n):
            fn()
        out.append((time.perf_counter() - t0) / n * 1e6)
    return min(out)


def main(argv=None) -> int:
    ap = argparse.ArgumentParser()
    ap.add_argument("--package-root", default=os.path.dirname(
        os.path.dirname(os.path.abspath(__file__))))
    ap.add_argument("--n", type=int, default=20000)
    ap.add_argument("--repeats", type=int, default=7)
    args = ap.parse_args(argv)
    sys.path.insert(0, args.package_root)
    import jax

    from deeplearning4j_tpu.observability import global_registry
    from deeplearning4j_tpu.observability.tracing import (
        reset_global_trace_sink, span)

    reset_global_trace_sink(1 << 20)
    reg = global_registry()
    phases = ("admit", "reclaim", "dispatch", "fetch", "sweep", "publish")
    fam = reg.counter("dl4j_span_cost_seconds_total",
                      "benchmarks/span_cost.py: a labelled counter like "
                      "the decode loop's", label_names=("phase",))
    kids = [fam.labels(phase=p) for p in phases]

    def one():
        with span("span_cost_probe"):
            pass

    def iteration_old():
        with span("decode_step", active=8, slots=8):
            pass

    def iteration_new():
        sec = dict.fromkeys(phases, 0.0)
        t_prev = time.perf_counter()

        def close(phase):
            nonlocal t_prev
            now = time.perf_counter()
            sec[phase] += now - t_prev
            t_prev = now

        with span("decode_iter", step=1):
            with span("loop_admit") as sp:
                sp.set_attr("joined", 0)
            close("admit")
            with span("loop_reclaim"):
                pass
            close("reclaim")
            with span("decode_step", active=8, slots=8, live_tokens=999):
                with span("decode_dispatch"):
                    pass
                close("dispatch")
                with span("token_fetch"):
                    pass
            close("fetch")
            with span("loop_sweep") as sp:
                sp.set_attr("finished", 0)
                sp.set_attr("emitted", 8)
            close("sweep")
            with span("loop_publish"):
                pass
            close("publish")
        for kid, s in zip(kids, sec.values()):
            if s:
                kid.inc(s)

    out = {"package_root": args.package_root,
           "platform": jax.default_backend(),
           # what a span is made of, on this host's kernel
           "parts_us": {
               "os_urandom_8": best_us(lambda: os.urandom(8), args.n, 3),
               "perf_counter": best_us(time.perf_counter, args.n, 3),
               "environ_get": best_us(
                   lambda: os.environ.get("DL4J_TPU_TRACE", "1"), args.n, 3)},
           "span_us_profile_off": best_us(one, args.n, args.repeats),
           "iteration_old_us": best_us(iteration_old, args.n // 4,
                                       args.repeats),
           "iteration_new_us": best_us(iteration_new, args.n // 4,
                                       args.repeats)}
    with tempfile.TemporaryDirectory() as d:
        opts = jax.profiler.ProfileOptions()
        opts.python_tracer_level = 0
        opts.host_tracer_level = 2
        jax.profiler.start_trace(d, profiler_options=opts)
        try:
            out["span_us_profile_on"] = best_us(one, args.n // 4, 3)
            out["iteration_new_us_profile_on"] = best_us(
                iteration_new, args.n // 8, 3)
        finally:
            jax.profiler.stop_trace()
    out["iteration_added_us"] = out["iteration_new_us"] \
        - out["iteration_old_us"]
    print(json.dumps(out), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
