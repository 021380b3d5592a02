"""One run of one cell: ``python3 perfbench/run.py --workload <name> --seed <n>
--seconds <s> --trace <0|1>``, from the root of a checkout.

Refuses anything but the TPU chips the cell asks for, builds the model on the
device from the seed, warms only this cell's shapes through the persistent
compile cache, measures for ``--seconds``, compares what the window produced
with the plain reference, prints one JSON object as its last line, and exits.
``--rehearsal`` is for the CPU only: the configuration's and the mix's tiny
``rehearsal`` sizes, the same code paths, and a last line that says
``"rehearsal": true`` and carries no metric.
"""
from __future__ import annotations

import time

T_START = time.time()       # set-up is counted from here

import argparse
import os
import sys

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
if ROOT not in sys.path:
    sys.path.insert(0, ROOT)

from perfbench import harness  # noqa: E402


def main(argv=None) -> int:
    ap = argparse.ArgumentParser()
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    ap.add_argument("--rehearsal", action="store_true")
    args = ap.parse_args(argv)
    try:
        cell = harness.Cell(args.workload, rehearsal=args.rehearsal)
        import deeplearning4j_tpu  # noqa: F401  the system under test
        device = harness.device_info(cell.chips, args.rehearsal)
        harness.say(f"cell {cell.name}: {device}, seed {args.seed}, "
                    f"{args.seconds} s, trace {args.trace}; imports and "
                    f"devices took {time.time() - T_START:.1f} s")
        if args.rehearsal:
            import jax
            jax.config.update("jax_enable_compilation_cache", False)
        else:
            harness.say(f"compile cache: {harness.configure_cache()}")
        out = cell.runner.run(cell, seed=args.seed, seconds=args.seconds,
                              trace=bool(args.trace), device=device,
                              t_start=T_START)
    except (harness.Refused, ImportError) as e:
        print(f"perfbench: refused: {e}", file=sys.stderr)
        return 2
    wanted = cell.per_layer if args.trace else cell.end_to_end
    metrics = {m["name"]: {"value": out["values"][m["name"]],
                           "unit": m["unit"]}
               for m in wanted if out["values"].get(m["name"]) is not None}
    for name, rec in metrics.items():
        if (("roofline" in name or "mfu" in name) and rec["unit"] == "%"
                and rec["value"] > 105.0):
            print(f"perfbench: {name} reads {rec['value']} %: the operations "
                  f"or bytes are counted too high, or the time leaves out "
                  f"work", file=sys.stderr)
            return 3
    device["memory_peak_bytes"] = out["memory_peak_bytes"]
    if args.trace:
        device["busy_s"] = out["busy_s"]
        device["window_s"] = out["trace_window_s"]
    harness.say(f"whole run {time.time() - T_START:.1f} s, of which "
                f"{args.seconds} s are the window")
    print(harness.result_line(out["correct"], out["attempted"], out["failed"],
                              metrics, device, out.get("breakdown"),
                              rehearsal=args.rehearsal), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
