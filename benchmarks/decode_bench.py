#!/usr/bin/env python
"""Decode-path A/Bs: KV cache vs. naive recompute, continuous vs.
static, paged vs. dense cache, int8 vs. f32 storage, speculative vs.
plain decode.

Five questions, each answered with the RESULTS.md noisy-box protocol
(interleaved repeats, per-repeat rotating arm order, min-estimator per
arm — raw single samples on this ±40%-drift box are weather):

1. ``--kv-ab`` — tokens/s of KV-cache incremental decode
   (``DecodeEngine.generate``: one prefill + one O(T) step per token)
   vs. the naive full-recompute loop (``naive_generate``: one full
   O(T²)-attention forward over the fixed-padded sequence per token).
   Both greedy, both one compiled executable per arm, same prompt, same
   emitted tokens (asserted). The acceptance bar is ≥5× at 256 decoded
   tokens on the flagship CPU-smoke config.

2. ``--cb-ab`` — goodput (completed tokens/s over the whole workload)
   of continuous batching (``GenerationPipeline``: requests join/leave
   the slot batch at step boundaries) vs. static windowed batching (the
   same engine, but a window of ``slots`` requests decodes until its
   LONGEST member finishes before any new request is admitted) under
   mixed-length requests arriving on a seeded Poisson process. Same
   arrival schedule, same prompts, same budgets in both arms.

3. ``--paged-ab`` — max sustained concurrent slots AND goodput at a
   FIXED HBM budget: dense worst-case reservation vs. a page pool of
   the same bytes backing ``slot_factor`` x the slots (admission by
   actual cached tokens). Bar: >= 2x the concurrency.

4. ``--quant-ab`` — int8 per-page KV storage vs. f32 pages: tokens/s
   interleaved, the deploy-time numerics-gate record, and the
   resident-bytes-per-page ratio (the durable number on any host).

5. ``--spec-ab`` — draft-accelerated speculative decode vs. plain:
   tokens/s interleaved + accept rate, greedy tokens byte-identical
   asserted. Bar: >= 1.3x tokens/s.

JSON archives to ``benchmarks/ab/decode_ab.json`` (never the repo
root).
"""
from __future__ import annotations

import argparse
import json
import os
import queue
import sys
import threading
import time
from typing import Dict, List

import numpy as np

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))

import jax  # noqa: E402

from deeplearning4j_tpu.models.generation import (DecodeEngine,  # noqa: E402
                                                  naive_generate)
from deeplearning4j_tpu.models.transformer import (TransformerConfig,  # noqa: E402
                                                   TransformerLM)
from deeplearning4j_tpu.parallel.generation import GenerationPipeline  # noqa: E402

AB_DIR = os.path.join(os.path.dirname(os.path.abspath(__file__)), "ab")


def flagship_cpu_config(max_len: int) -> TransformerConfig:
    """The bench.py CPU-smoke flagship shape (vocab 1024, 2L, d128,
    fused qkv), with the cache length this A/B needs."""
    import jax.numpy as jnp
    return TransformerConfig(vocab_size=1024, n_layers=2, n_heads=4,
                             d_model=128, max_len=max_len,
                             dtype=jnp.float32, fused_qkv=True)


def _interleaved_best(modes: List[str], repeats: int, run_one) -> Dict:
    """The rotating-order interleaved protocol (obs_overhead.py), with
    the estimator flipped for RATE metrics: obs_overhead's min-of-N is
    min SECONDS per step (the least-interfered window); for tokens/s
    the same estimator is the MAX sample. In-process because both arms
    share the compiled engine deliberately — compiles must not land in
    a measured window (arms are warmed before the first repeat)."""
    samples = {m: [] for m in modes}
    order = list(modes)
    for r in range(repeats):
        for m in order[r % len(order):] + order[:r % len(order)]:
            samples[m].append(run_one(m))
    return {m: max(v) for m, v in samples.items()}


# ------------------------------------------------------------------ kv A/B
def kv_ab(decode_tokens: int, prompt_len: int, repeats: int,
          naive_tokens: int, as_json: bool) -> dict:
    max_len = prompt_len + decode_tokens
    cfg = flagship_cpu_config(max_len)
    model = TransformerLM(cfg)
    params = model.init_params(jax.random.key(0))
    engine = DecodeEngine(model, params, max_len=max_len)
    rng = np.random.default_rng(0)
    prompt = rng.integers(0, cfg.vocab_size, (1, prompt_len)).astype(np.int32)

    # correctness first: both paths emit the same greedy continuation
    kv_out = engine.generate(prompt, min(32, decode_tokens))
    nv_out = naive_generate(model, params, prompt, min(32, decode_tokens),
                            pad_to=max_len)
    assert np.array_equal(kv_out, nv_out), \
        "KV-cache decode diverged from the full-forward continuation"

    def run_kv() -> float:
        t0 = time.perf_counter()
        engine.generate(prompt, decode_tokens)
        return decode_tokens / (time.perf_counter() - t0)

    def run_naive() -> float:
        # the naive arm's per-token cost is CONSTANT (every step re-runs
        # the same fixed-padded forward), so a shorter run measures the
        # same tokens/s rate — full 256-token naive runs would spend
        # minutes re-proving a constant on this box
        n = min(naive_tokens, decode_tokens)
        t0 = time.perf_counter()
        naive_generate(model, params, prompt, n, pad_to=max_len)
        return n / (time.perf_counter() - t0)

    best = _interleaved_best(["kv", "naive"], repeats,
                             lambda m: run_kv() if m == "kv" else run_naive())
    ratio = best["kv"] / best["naive"]
    result = {
        "metric": "decode_kv_cache",
        "platform": jax.default_backend(),
        "value": best["kv"],
        "kv_tokens_per_s": best["kv"],
        "naive_tokens_per_s": best["naive"],
        "vs_naive": ratio,
        "decode_tokens": decode_tokens,
        "prompt_len": prompt_len,
        "naive_tokens_measured": min(naive_tokens, decode_tokens),
        "repeats": repeats,
        "ratio_method": "interleaved_rotating_best",
        "config": {"n_layers": cfg.n_layers, "d_model": cfg.d_model,
                   "vocab": cfg.vocab_size, "max_len": max_len},
    }
    if as_json:
        print(json.dumps(result, indent=2))
    else:
        print(f"KV-cache decode A/B ({decode_tokens} tokens, prompt "
              f"{prompt_len}, best of {repeats} rotating repeats)")
        print(f"  kv cache : {best['kv']:9.1f} tokens/s")
        print(f"  naive    : {best['naive']:9.1f} tokens/s "
              f"(full recompute, {min(naive_tokens, decode_tokens)} "
              "tokens measured)")
        print(f"  speedup  : {ratio:.2f}x  (bar: >= 5x)")
    return result


# ------------------------------------------------------------------ cb A/B
def _workload(n_requests: int, slots: int, seed: int):
    """Seeded mixed-length Poisson workload shared by both arms:
    heavy-tailed output budgets (mostly short chats, a long tail of
    long generations — the production LLM length distribution), so a
    static window genuinely strands slots behind its longest member."""
    rng = np.random.default_rng(seed)
    prompts = [rng.integers(0, 1024, (int(n),)).astype(np.int32)
               for n in rng.integers(4, 24, n_requests)]
    budgets = [int(rng.integers(48, 80)) if rng.random() < 0.25
               else int(rng.integers(6, 16)) for _ in range(n_requests)]
    # Poisson arrivals tuned so the offered load keeps ~slots streams busy
    gaps = rng.exponential(scale=0.01, size=n_requests)
    arrivals = np.cumsum(gaps)
    return prompts, budgets, arrivals


def _static_windowed(engine: DecodeEngine, slots: int, prompts, budgets,
                     arrivals):
    """The pre-continuous-batching baseline: admit up to ``slots``
    arrived requests, decode the window until EVERY member finished,
    then admit the next window (the whole window waits on its longest
    member — exactly the slot waste continuous batching removes).
    Returns (goodput tokens/s, per-request latencies)."""
    t_start = time.perf_counter()
    done_tokens = 0
    latencies = []
    i = 0
    while i < len(prompts):
        # wait for at least one arrival, then take whatever has arrived
        now = time.perf_counter() - t_start
        if arrivals[i] > now:
            time.sleep(arrivals[i] - now)
        now = time.perf_counter() - t_start
        window = [j for j in range(i, min(i + slots, len(prompts)))
                  if arrivals[j] <= now] or [i]
        i = window[-1] + 1
        cache = engine.new_cache(slots)
        toks = np.zeros((slots,), np.int32)
        pos = np.zeros((slots,), np.int32)
        remaining = {}
        for s, j in enumerate(window):
            first, _l, kv, t = engine.prefill(prompts[j][None], step=0)
            cache = engine.insert_slot(cache, kv, s)
            toks[s] = int(np.asarray(first)[0])
            pos[s] = t
            remaining[s] = budgets[j] - 1
            done_tokens += 1
        step = 0
        while any(r > 0 for r in remaining.values()):
            nxt, _l, cache = engine.decode(cache, toks, pos, step)
            nxt = np.asarray(nxt)
            for s, j in enumerate(window):
                if remaining[s] > 0:
                    remaining[s] -= 1
                    done_tokens += 1
                    if remaining[s] == 0:
                        latencies.append(time.perf_counter() - t_start
                                         - arrivals[j])
            toks, pos, step = nxt, pos + 1, step + 1
    return done_tokens / (time.perf_counter() - t_start), latencies


def _continuous(engine: DecodeEngine, slots: int, prompts, budgets,
                arrivals):
    """The same workload through GenerationPipeline (requests join/leave
    at step boundaries). Returns (goodput, per-request latencies)."""
    gp = GenerationPipeline(engine, slots=slots,
                            queue_limit=max(64, len(prompts)))
    results: "queue.Queue" = queue.Queue()
    t_start = time.perf_counter()

    def one(j, t_arr):
        try:
            out = gp.generate(prompts[j], max_new_tokens=budgets[j])
            results.put((len(out), time.perf_counter() - t_arr))
        except Exception:
            results.put((0, 0.0))

    threads = []
    for j in range(len(prompts)):
        now = time.perf_counter() - t_start
        if arrivals[j] > now:
            time.sleep(arrivals[j] - now)
        th = threading.Thread(target=one, args=(j, time.perf_counter()),
                              daemon=True)
        th.start()
        threads.append(th)
    for th in threads:
        th.join(timeout=120)
    pairs = [results.get() for _ in range(results.qsize())]
    goodput = sum(n for n, _ in pairs) / (time.perf_counter() - t_start)
    gp.shutdown()
    return goodput, [lat for n, lat in pairs if n]


def cb_ab(n_requests: int, slots: int, repeats: int, as_json: bool) -> dict:
    cfg = flagship_cpu_config(128)
    model = TransformerLM(cfg)
    params = model.init_params(jax.random.key(0))
    engine = DecodeEngine(model, params, max_len=128)
    prompts, budgets, arrivals = _workload(n_requests, slots, seed=7)
    occupancy: List[float] = []
    lat_p50: Dict[str, float] = {}

    # AOT-warm every executable both arms will hit — the SAME recipe a
    # production deploy runs (DecodeEngine.warm), so the rotating
    # windows measure decode, never compilation
    engine.warm(slots)

    def run_one(mode: str) -> float:
        if mode == "static":
            goodput, lats = _static_windowed(engine, slots, prompts,
                                             budgets, arrivals)
            lat_p50["static"] = float(np.median(lats)) if lats else 0.0
            return goodput
        from deeplearning4j_tpu.observability import global_registry
        inst = global_registry().get("dl4j_decode_slot_occupancy_ratio")
        before = (inst.sum, inst.count) if inst is not None else (0.0, 0)
        goodput, lats = _continuous(engine, slots, prompts, budgets,
                                    arrivals)
        lat_p50["continuous"] = float(np.median(lats)) if lats else 0.0
        inst = global_registry().get("dl4j_decode_slot_occupancy_ratio")
        if inst is not None and inst.count > before[1]:
            occupancy.append((inst.sum - before[0])
                             / (inst.count - before[1]))
        return goodput

    best = _interleaved_best(["continuous", "static"], repeats, run_one)
    ratio = best["continuous"] / best["static"]
    result = {
        "metric": "decode_continuous_batching",
        "platform": jax.default_backend(),
        "value": best["continuous"],
        "continuous_tokens_per_s": best["continuous"],
        "static_tokens_per_s": best["static"],
        "vs_static": ratio,
        "slot_occupancy": [round(o, 4) for o in occupancy],
        "latency_p50_s": {k: round(v, 4) for k, v in lat_p50.items()},
        "n_requests": n_requests,
        "slots": slots,
        "repeats": repeats,
        "ratio_method": "interleaved_rotating_best",
    }
    if as_json:
        print(json.dumps(result, indent=2))
    else:
        print(f"continuous-batching A/B ({n_requests} mixed-length "
              f"requests, {slots} slots, best of {repeats} rotating "
              "repeats)")
        print(f"  continuous: {best['continuous']:9.1f} tokens/s goodput")
        print(f"  static    : {best['static']:9.1f} tokens/s goodput")
        print(f"  ratio     : {ratio:.2f}x  (bar: > 1x)")
        if lat_p50:
            print(f"  p50 request latency: continuous "
                  f"{lat_p50.get('continuous', 0) * 1e3:.0f} ms vs static "
                  f"{lat_p50.get('static', 0) * 1e3:.0f} ms")
        if occupancy:
            print(f"  mean slot occupancy (continuous): "
                  f"{occupancy[-1]:.3f}")
    return result


# ------------------------------------------------------- paged-cache A/B
def paged_ab(n_requests: int, dense_slots: int, slot_factor: int,
             repeats: int, as_json: bool) -> dict:
    """Max sustained concurrent slots AND goodput at a FIXED HBM budget,
    paged vs dense. The budget is what ``dense_slots`` worst-case dense
    slots cost (slots x max_len rows); the paged arm spends exactly the
    same bytes as a page pool but runs ``slot_factor`` x the slots —
    admission is bounded by ACTUAL cached tokens, and the workload's
    streams use ~1/4 of max_len each, so the pool sustains what the
    dense worst-case reservation never could."""
    max_len = 128
    cfg = flagship_cpu_config(max_len)
    model = TransformerLM(cfg)
    params = model.init_params(jax.random.key(0))
    page = 32
    dense_eng = DecodeEngine(model, params, max_len=max_len, page_tokens=0)
    paged_eng = DecodeEngine(model, params, max_len=max_len,
                             page_tokens=page)
    budget_pages = dense_slots * paged_eng.pages_per_slot
    budget_bytes = budget_pages * paged_eng.page_bytes()
    paged_slots = dense_slots * slot_factor
    # short streams: ~max_len/4 actual rows per request, the regime the
    # worst-case reservation wastes 4x on
    rng = np.random.default_rng(11)
    prompts = [rng.integers(0, 1024, (int(n),)).astype(np.int32)
               for n in rng.integers(6, 14, n_requests)]
    budgets = [int(b) for b in rng.integers(12, 22, n_requests)]
    arrivals = np.cumsum(rng.exponential(scale=0.004, size=n_requests))
    dense_eng.warm(dense_slots)
    paged_eng.warm(paged_slots)
    peak = {"dense": 0, "paged": 0}

    def run_one(mode: str) -> float:
        if mode == "dense":
            gp = GenerationPipeline(dense_eng, slots=dense_slots,
                                    queue_limit=max(64, n_requests))
        else:
            gp = GenerationPipeline(paged_eng, slots=paged_slots,
                                    queue_limit=max(64, n_requests),
                                    cache_pages=budget_pages)
        results: "queue.Queue" = queue.Queue()
        stop = threading.Event()

        def sample_peak():
            while not stop.is_set():
                peak[mode] = max(peak[mode], gp._n_active())
                time.sleep(0.002)

        sampler = threading.Thread(target=sample_peak, daemon=True)
        sampler.start()
        t_start = time.perf_counter()

        def one(j, t_arr):
            try:
                out = gp.generate(prompts[j], max_new_tokens=budgets[j])
                results.put(len(out))
            except Exception:
                results.put(0)

        threads = []
        for j in range(n_requests):
            now = time.perf_counter() - t_start
            if arrivals[j] > now:
                time.sleep(arrivals[j] - now)
            th = threading.Thread(target=one,
                                  args=(j, time.perf_counter()),
                                  daemon=True)
            th.start()
            threads.append(th)
        for th in threads:
            th.join(timeout=120)
        done = sum(results.get() for _ in range(results.qsize()))
        goodput = done / (time.perf_counter() - t_start)
        stop.set()
        sampler.join(timeout=1)
        gp.shutdown()
        return goodput

    best = _interleaved_best(["paged", "dense"], repeats, run_one)
    ratio = best["paged"] / best["dense"]
    result = {
        "metric": "decode_paged_cache",
        "platform": jax.default_backend(),
        "value": best["paged"],
        "paged_tokens_per_s": best["paged"],
        "dense_tokens_per_s": best["dense"],
        "vs_dense_cache": ratio,
        "hbm_budget_bytes": budget_bytes,
        "page_tokens": page,
        "max_slots_dense": peak["dense"],
        "max_slots_paged": peak["paged"],
        "slot_ratio": (peak["paged"] / peak["dense"]
                       if peak["dense"] else None),
        "dense_slot_cap": dense_slots,
        "paged_slot_cap": paged_slots,
        "n_requests": n_requests,
        "repeats": repeats,
        "ratio_method": "interleaved_rotating_best",
    }
    if as_json:
        print(json.dumps(result, indent=2))
    else:
        print(f"paged-vs-dense KV cache A/B at a fixed "
              f"{budget_bytes / 1e6:.1f} MB HBM budget "
              f"({n_requests} short streams, best of {repeats})")
        print(f"  dense : {best['dense']:9.1f} tokens/s, peak "
              f"{peak['dense']} concurrent slots (cap {dense_slots} — "
              "worst-case reservation)")
        print(f"  paged : {best['paged']:9.1f} tokens/s, peak "
              f"{peak['paged']} concurrent slots (cap {paged_slots}, "
              "same bytes)")
        print(f"  goodput ratio {ratio:.2f}x, concurrency ratio "
              f"{result['slot_ratio']:.1f}x (bar: >= 2x)")
    return result


# ------------------------------------------------------- int8-quant A/B
def quant_ab(decode_tokens: int, prompt_len: int, repeats: int,
             as_json: bool) -> dict:
    """int8-quantized vs f32 paged cache: tokens/s (interleaved) and the
    numerics-gate record. The durable number on ANY host is the
    resident-bytes ratio — int8 k/v + per-row scale vs f32 rows; the
    tokens/s ratio only moves where decode is HBM-bound (a real chip),
    so it is reported, never a bar."""
    max_len = prompt_len + decode_tokens
    cfg = flagship_cpu_config(max_len)
    model = TransformerLM(cfg)
    params = model.init_params(jax.random.key(0))
    page = 32
    f32_eng = DecodeEngine(model, params, max_len=max_len,
                           page_tokens=page)
    q_eng = DecodeEngine(model, params, max_len=max_len, page_tokens=page,
                         kv_quant=True)
    rng = np.random.default_rng(0)
    prompt = rng.integers(0, cfg.vocab_size, (1, prompt_len)).astype(np.int32)
    f32_eng.warm(1)
    q_eng.warm(1)
    gate = dict(q_eng.quant_gate or {})
    quant_live = bool(q_eng.kv_quant)

    def run(eng) -> float:
        t0 = time.perf_counter()
        eng.generate(prompt, decode_tokens)
        return decode_tokens / (time.perf_counter() - t0)

    best = _interleaved_best(
        ["int8", "f32"], repeats,
        lambda m: run(q_eng if m == "int8" else f32_eng))
    result = {
        "metric": "decode_kv_quant",
        "platform": jax.default_backend(),
        "value": best["int8"],
        "int8_tokens_per_s": best["int8"],
        "f32_tokens_per_s": best["f32"],
        "vs_f32": best["int8"] / best["f32"],
        "quant_live": quant_live,
        "gate": gate,
        "page_bytes_int8": q_eng.page_bytes() if quant_live else None,
        "page_bytes_f32": f32_eng.page_bytes(),
        "bytes_ratio": ((q_eng.page_bytes() / f32_eng.page_bytes())
                        if quant_live else None),
        "decode_tokens": decode_tokens,
        "repeats": repeats,
        "ratio_method": "interleaved_rotating_best",
    }
    if as_json:
        print(json.dumps(result, indent=2))
    else:
        print(f"int8-vs-f32 KV cache A/B ({decode_tokens} tokens, "
              f"best of {repeats})")
        print(f"  int8 : {best['int8']:9.1f} tokens/s "
              f"(gate max |logit diff| {gate.get('max_abs_logit_diff', 0):.2e}"
              f" <= tol {gate.get('tol')}, "
              f"{'LIVE' if quant_live else 'FELL BACK TO f32'})")
        print(f"  f32  : {best['f32']:9.1f} tokens/s")
        if quant_live:
            print(f"  resident bytes/page: {q_eng.page_bytes()} vs "
                  f"{f32_eng.page_bytes()} "
                  f"({f32_eng.page_bytes() / q_eng.page_bytes():.2f}x "
                  "more tokens per byte)")
    return result


# ------------------------------------------------------ spec-decode A/B
def spec_ab(decode_tokens: int, prompt_len: int, spec_k: int,
            draft_layers: int, repeats: int, as_json: bool) -> dict:
    """Speculative vs plain decode on the flagship shape: the draft is a
    ``draft_layers``-layer truncation of the target sharing its
    embeddings (at 0.02 init scale the blocks barely perturb the
    logits, so even the 0-layer embedding-only draft agrees with the
    target often — the synthetic stand-in for a distilled production
    draft; the measured accept rate IS reported, it is a property of
    this config, not a claim about real drafts). Greedy mode, so the
    emitted tokens are asserted BYTE-IDENTICAL to plain decode; accept
    rate and tokens/s are the measurements. On this dispatch-bound box
    the win comes from round shape — ONE fused k-step propose + ONE
    windowed verify replace up to k single-token dispatches — which is
    also the shape of the win on a real chip, where the verify's W-row
    matmuls batch where plain decode runs GEMVs."""
    max_len = prompt_len + decode_tokens
    cfg = flagship_cpu_config(max_len)
    model = TransformerLM(cfg)
    params = model.init_params(jax.random.key(0))
    import dataclasses as _dc
    dcfg = _dc.replace(cfg, n_layers=draft_layers)
    draft_model = TransformerLM(dcfg)
    draft_params = {"tok_emb": params["tok_emb"],
                    "pos_emb": params["pos_emb"], "ln_f": params["ln_f"],
                    "blocks": [params["blocks"][i]
                               for i in range(draft_layers)]}
    page = 32
    draft = DecodeEngine(draft_model, draft_params, max_len=max_len,
                         page_tokens=0)
    plain_eng = DecodeEngine(model, params, max_len=max_len,
                             page_tokens=page)
    spec_eng = DecodeEngine(model, params, max_len=max_len,
                            page_tokens=page, draft=draft, spec_k=spec_k)
    rng = np.random.default_rng(0)
    prompt = rng.integers(0, cfg.vocab_size, (1, prompt_len)).astype(np.int32)
    plain_eng.warm(1)
    spec_eng.warm(1)
    # correctness first: greedy speculative decode must emit EXACTLY the
    # plain continuation (the accept loop's contract)
    ref = plain_eng.generate(prompt, decode_tokens)
    out = spec_eng.generate(prompt, decode_tokens)
    assert np.array_equal(ref, out), \
        "speculative greedy decode diverged from plain decode"

    def run(eng) -> float:
        t0 = time.perf_counter()
        eng.generate(prompt, decode_tokens)
        return decode_tokens / (time.perf_counter() - t0)

    spec_eng.spec_stats = {"rounds": 0, "proposed": 0, "accepted": 0}
    best = _interleaved_best(
        ["spec", "plain"], repeats,
        lambda m: run(spec_eng if m == "spec" else plain_eng))
    accept = spec_eng.spec_accept_ratio()
    result = {
        "metric": "decode_speculative",
        "platform": jax.default_backend(),
        "value": best["spec"],
        "spec_tokens_per_s": best["spec"],
        "plain_tokens_per_s": best["plain"],
        "vs_no_spec": best["spec"] / best["plain"],
        "spec_accept_ratio": accept,
        "spec_k": spec_k,
        "draft_layers": draft_layers,
        "greedy_identical": True,
        "decode_tokens": decode_tokens,
        "repeats": repeats,
        "ratio_method": "interleaved_rotating_best",
    }
    if as_json:
        print(json.dumps(result, indent=2))
    else:
        print(f"speculative-vs-plain decode A/B ({decode_tokens} tokens, "
              f"k={spec_k}, best of {repeats}; greedy tokens identical "
              "asserted)")
        print(f"  spec  : {best['spec']:9.1f} tokens/s "
              f"(accept ratio {accept:.3f})")
        print(f"  plain : {best['plain']:9.1f} tokens/s")
        print(f"  speedup {best['spec'] / best['plain']:.2f}x "
              "(bar: >= 1.3x)")
    return result


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--kv-ab", action="store_true",
                    help="KV-cache decode vs naive full recompute")
    ap.add_argument("--cb-ab", action="store_true",
                    help="continuous vs static windowed batching")
    ap.add_argument("--paged-ab", action="store_true",
                    help="paged vs dense cache at a fixed HBM budget")
    ap.add_argument("--quant-ab", action="store_true",
                    help="int8 vs f32 KV storage")
    ap.add_argument("--spec-ab", action="store_true",
                    help="speculative vs plain decode")
    ap.add_argument("--decode-tokens", type=int, default=256)
    ap.add_argument("--prompt-len", type=int, default=32)
    ap.add_argument("--naive-tokens", type=int, default=64,
                    help="tokens the naive arm measures per window (its "
                         "per-token cost is constant; see docstring)")
    ap.add_argument("--requests", type=int, default=24)
    ap.add_argument("--slots", type=int, default=4)
    ap.add_argument("--dense-slots", type=int, default=2,
                    help="paged A/B: dense slots whose worst-case bytes "
                         "set the fixed HBM budget")
    ap.add_argument("--slot-factor", type=int, default=4,
                    help="paged A/B: paged slot cap as a multiple of the "
                         "dense cap (same bytes)")
    ap.add_argument("--spec-k", type=int, default=8)
    ap.add_argument("--draft-layers", type=int, default=0,
                    help="spec A/B: target layers the draft keeps (0 = "
                         "embedding-only draft)")
    ap.add_argument("--repeats", type=int, default=3)
    ap.add_argument("--json", action="store_true")
    args = ap.parse_args()
    chosen = any((args.kv_ab, args.cb_ab, args.paged_ab, args.quant_ab,
                  args.spec_ab))
    results = {}
    if args.kv_ab or not chosen:
        results["kv"] = kv_ab(args.decode_tokens, args.prompt_len,
                              args.repeats, args.naive_tokens, args.json)
    if args.cb_ab or not chosen:
        results["cb"] = cb_ab(args.requests, args.slots, args.repeats,
                              args.json)
    if args.paged_ab or not chosen:
        results["paged"] = paged_ab(args.requests, args.dense_slots,
                                    args.slot_factor, args.repeats,
                                    args.json)
    if args.quant_ab or not chosen:
        results["quant"] = quant_ab(min(args.decode_tokens, 96),
                                    args.prompt_len, args.repeats,
                                    args.json)
    if args.spec_ab or not chosen:
        results["spec"] = spec_ab(min(args.decode_tokens, 96),
                                  args.prompt_len, args.spec_k,
                                  args.draft_layers, args.repeats,
                                  args.json)
    os.makedirs(AB_DIR, exist_ok=True)
    out = os.path.join(AB_DIR, "decode_ab.json")
    with open(out, "w") as f:
        json.dump(results, f, indent=2)
    print(f"archived -> {out}", file=sys.stderr)
    return 0


if __name__ == "__main__":
    sys.exit(main())
