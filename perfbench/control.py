"""The control: what ``correct`` must fail. Not part of a benchmark run.

``python3 perfbench/control.py --workload <cell> --seeds 1,2,3 [--seconds s]``
reads, for every seed and in one process, the numbers that the cell compares
with the plain reference - once from the program (a sound run) and once from
the control, the reference computed in float8 (e4m3) in the program's place,
the nearest precision below the bfloat16 the configurations state. Serving
cells also read the program's own lower-precision path, the int8 page pool
(``kv_quant=True``), replayed over the same prompts and served tokens. The
limits in ``perfbench/limits/`` are set from these two readings (PERF.md).
"""
from __future__ import annotations

import argparse
import os
import sys
import time

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
if ROOT not in sys.path:
    sys.path.insert(0, ROOT)

import numpy as np  # noqa: E402

from perfbench import harness  # noqa: E402
from perfbench.harness import say  # noqa: E402


def train_readings(cell, seeds, device):
    tr = harness.load_module("runners", "train.py")
    for seed in seeds:
        opt, step, feed, weights = tr.build(cell, seed, device)
        keep = tr.sampled_leaves(cell, seed)
        ref = tr.reference_steps(cell, weights, feed, keep)
        low = tr.reference_steps(cell, weights, feed, keep, lowp=True)
        _p, _s, prog = tr.first_steps(cell, step, opt, weights, feed, keep)
        del _p, _s
        for who, out in (("program", prog), ("control-float8", low)):
            cmp = tr.compare(cell, out, ref, keep)
            print(f"READING {cell.name} seed={seed} {who} "
                  + " ".join(f"{n}={v:.6g}" for n, v, _l, _ok in cmp.rows),
                  flush=True)


def _int8_pool_tokens(cell, params, sample):
    """The token the program puts first at every served position when its
    page pool is int8: prompt prefilled, served tokens fed back one by one,
    through the engine's own executables."""
    from deeplearning4j_tpu.models.generation import DecodeEngine
    slots, slot = 4, 2
    model = cell.model.build_model(cell.config)
    engine = DecodeEngine(model, params, max_len=cell.config["n_positions"],
                          prefill_buckets=cell.traffic.get("prefill_buckets"),
                          kv_quant=True)
    out = []
    for r in sample:
        state = engine.new_state(slots)
        if not (engine.kv_quant and engine.quant_gate
                and engine.quant_gate["passed"]):
            raise RuntimeError(f"int8 gate turned the pool off: "
                               f"{engine.quant_gate}")
        prompt = np.asarray(r["prompt"], np.int32)
        first, _lg, kv, t = engine.prefill(prompt[None])
        state = engine.insert_slot(state, kv, slot)
        got = [int(np.asarray(first)[0])]
        tokens = np.zeros((slots,), np.int32)
        positions = np.zeros((slots,), np.int32)
        for j, tok in enumerate(r["tokens"][:-1]):
            tokens[slot], positions[slot] = tok, t + j
            nxt, _lg, state = engine.decode(state, tokens, positions, j + 1)
            got.append(int(np.asarray(nxt)[slot]))
        out.append(got)
        del state
    return out


def serving_hook(cell, params, sample, gaps):
    """Called by ``perfbench.serving.run`` with the sample it compared."""
    import jax.numpy as jnp
    from perfbench import serving
    ref = cell.reference
    seqs, cands, mask = serving.pack(sample, cell.config["n_positions"])

    def stats(name, g):
        print(f"READING {cell.name} {name} served_logit_gap_max="
              f"{float(g.max()):.6g} served_logit_gap_mean="
              f"{float(g.mean()):.6g} flips={int((g > 0).sum())}/{g.size}",
              flush=True)

    stats("program", gaps)
    t0 = time.time()
    low = np.asarray(ref.next_token_argmax(params, jnp.asarray(seqs),
                                           cell.config, True))
    g = np.asarray(ref.next_token_gaps(params, jnp.asarray(seqs),
                                       jnp.asarray(low), cell.config))[mask]
    stats("control-float8", g)
    say(f"float8 control in {time.time() - t0:.1f} s")
    t0 = time.time()
    cand8 = cands.copy()
    try:
        replayed = _int8_pool_tokens(cell, params, sample)
    except RuntimeError as e:       # the program's own gate refused int8
        print(f"READING {cell.name} control-int8-pool refused: {e}",
              flush=True)
        return
    for row, (r, got) in enumerate(zip(sample, replayed)):
        t = len(r["prompt"])
        cand8[row, t - 1:t - 1 + len(got)] = got
    g = np.asarray(ref.next_token_gaps(params, jnp.asarray(seqs),
                                       jnp.asarray(cand8), cell.config))[mask]
    stats("control-int8-pool", g)
    say(f"int8 pool control in {time.time() - t0:.1f} s")


def main(argv=None) -> int:
    ap = argparse.ArgumentParser()
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seeds", required=True)
    ap.add_argument("--seconds", type=float, default=15.0)
    ap.add_argument("--rehearsal", action="store_true")
    args = ap.parse_args(argv)
    seeds = [int(s) for s in args.seeds.split(",")]
    cell = harness.Cell(args.workload, rehearsal=args.rehearsal)
    device = harness.device_info(cell.chips, args.rehearsal)
    if args.rehearsal:
        import jax
        jax.config.update("jax_enable_compilation_cache", False)
    else:
        harness.configure_cache()
    if cell.traffic["kind"] == "train":
        train_readings(cell, seeds, device)
    else:
        from perfbench import serving
        for seed in seeds:
            out = serving.run(cell, seed, args.seconds, False, device,
                              time.time(), hook=serving_hook)
            print(f"READING {cell.name} seed={seed} correct={out['correct']}"
                  f" values={out['values']}", flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
