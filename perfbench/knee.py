"""Find the knee of an open-loop mix once: the highest offered rate at which
the backlog does not grow. Not part of a benchmark run.

``python3 perfbench.knee.py --workload <cell> --rates 4,8,12,16 --seconds 20``
deploys the cell once, then offers the mix at each rate in turn (the cell's
own ramp and generator) and prints, for each: requests offered and finished
per second, how many were still in flight when the window closed, the
medians and 95th percentiles of time to first token and of the gap between
tokens. The knee is read from these lines by hand (perfbench/README.md) and
``rate_rps`` in the traffic file is set to 0.8 of it.
"""
from __future__ import annotations

import argparse
import json
import os
import subprocess
import sys
import time

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
if ROOT not in sys.path:
    sys.path.insert(0, ROOT)

from perfbench import harness, serving  # noqa: E402


def main(argv=None) -> int:
    ap = argparse.ArgumentParser()
    ap.add_argument("--workload", required=True)
    ap.add_argument("--rates", required=True)
    ap.add_argument("--seconds", type=float, default=20.0)
    ap.add_argument("--seed", type=int, default=1)
    ap.add_argument("--rehearsal", action="store_true")
    args = ap.parse_args(argv)
    cell = harness.Cell(args.workload, rehearsal=args.rehearsal)
    harness.device_info(cell.chips, args.rehearsal)
    if not args.rehearsal:
        harness.configure_cache()
    dep = serving.Deployment(cell, args.seed)
    try:
        serving.warm_requests(dep, cell, args.seed)
        for k, rate in enumerate(float(r) for r in args.rates.split(",")):
            tr = dict(cell.traffic, rate_rps=rate,
                      vocab_size=cell.config["vocab_size"])
            t_ramp = time.time() + 1.0
            gen = subprocess.run(
                serving.generator_command(dep, tr, args.seed + k,
                                          args.seconds, t_ramp),
                stdout=subprocess.PIPE, check=True)
            reqs = json.loads(gen.stdout)["requests"]
            t0, t1 = t_ramp + tr["ramp_s"], t_ramp + tr["ramp_s"] + args.seconds
            values, n, failed, _ = serving._end_to_end(tr, reqs, (t0, t1))
            mine = [r for r in reqs if t0 <= r["due"] < t1]
            ttft = [1e3 * (r["t_tokens"][0] - r["due"]) for r in mine
                    if r["t_tokens"]] or [float("inf")]
            done = sum(1 for r in reqs if r["ok"] and t0 <= r["end"] < t1)
            open_at_end = sum(1 for r in reqs if r["due"] < t1
                              and r.get("end", 1e99) > t1)
            open_at_start = sum(1 for r in reqs if r["due"] < t0
                                and r.get("end", 1e99) > t0)
            late = [r["sent"] - r["due"] for r in mine]
            print(f"KNEE rate={rate} offered={n / args.seconds:.2f}/s "
                  f"finished={done / args.seconds:.2f}/s failed={failed} "
                  f"in_flight_start={open_at_start} in_flight_end="
                  f"{open_at_end} ttft_p50={harness.quantile(ttft, 0.5):.1f} "
                  f"ttft_p95={values['ttft_p95_ms']:.1f} "
                  f"itl_p95={values['itl_p95_ms']:.1f} "
                  f"late_p95={1e3 * harness.quantile(late, 0.95):.2f} ms",
                  flush=True)
            time.sleep(3.0)     # callers hung up: let the slots empty
    finally:
        dep.close()
    return 0


if __name__ == "__main__":
    sys.exit(main())
