"""Round benchmark — prints ONE JSON line (stdout) for the driver.

Measures flagship TransformerLM training throughput on the TPU chip. Chip
or fail: a platform that is not a TPU is a non-zero exit (``BENCH_CPU=1``,
set by tests, runs the CPU plumbing toy instead and says so in the JSON).

1. **One process per chip.** The parent never touches jax; each size rung
   is measured by a ``--worker`` child with a hard timeout, one at a time,
   so a rung that hangs costs that rung and the chip is free for the next.

2. **Device-side timing.** The step is timed by the TPU itself: steps run
   under ``jax.profiler.trace`` and the XPlane's per-module device durations
   (``benchmarks/device_timing.py``) give the step time. Host-side
   value-fetch timing is reported alongside for comparison.

3. **A config big enough to mean something.** MFU on a ~20M-param model is
   HBM-bound, not MXU-bound. The TPU config is ~190M params
   (12L/d1024/seq1024, bf16), sized so the matmuls dominate.

Reported numbers (BASELINE.md measurement protocol):
- ``value``:       tokens/sec of the whole jitted train step (device-timed
                   when a trace is available, else host value-fetch median)
- ``mfu``:         model FLOPs utilisation vs the chip's peak
                   (``cost_model.DEVICE_PEAKS``, keyed by ``device_kind``),
                   causal FLOP count 6·N_params + 6·L·T·d per token
- ``vs_baseline``: ours / plain-Flax-on-the-same-chip, both sides timed the
                   same way — the BASELINE.md denominator (target ≥ 1.0)
"""
from __future__ import annotations

import json
import os
import statistics
import subprocess
import sys
import time

sys.path.insert(0, os.path.join(os.path.dirname(os.path.abspath(__file__)),
                                "benchmarks"))

#: analytic-vs-cost-model FLOPs disagreement above this flags the estimate
FLOPS_DISAGREE_WARN = 0.10


def cost_analysis_flops(step, *args):
    """XLA cost-model FLOPs per execution of the jitted ``step`` — an AOT
    ``lower()`` (no execution; MUST run before the warmup donates the
    param buffers), priced by the observatory (on the TPU that compiles
    here, and the warmup's call reuses the executable). Best-effort: None
    when the backend doesn't report flops."""
    try:
        from deeplearning4j_tpu.observability.cost_model import program_costs
        flops, _ = program_costs(step.lower(*args))
        return flops or None
    except Exception as e:
        print(f"[bench] cost_analysis failed: {e!r}", file=sys.stderr)
        return None


class StepTimer:
    """Warmup once, then expose one-window timing so the model under test
    and the flax denominator can be measured INTERLEAVED (A,B,A,B…) — a
    sequential A…A,B…B layout lets any machine-load drift between the two
    phases masquerade as a model difference."""

    def __init__(self, step, params, opt_state, toks, tgts, iters):
        self.step = step
        self.state = (params, opt_state)
        self.toks, self.tgts = toks, tgts
        self.iters = iters
        self.n_tokens = toks.shape[0] * toks.shape[1]
        self.loss = None
        self.runs = []
        self.device_step_s = None
        self._warm()

    def _warm(self):
        p, s = self.state
        p, s, loss = self.step(p, s, self.toks, self.tgts)
        self.loss = float(loss)          # value fetch = unfakeable sync
        self.state = (p, s)

    def _window(self):
        p, s = self.state
        loss = None
        for _ in range(self.iters):
            p, s, loss = self.step(p, s, self.toks, self.tgts)
        # sync by FETCHING the final loss value: the last loss depends on
        # the donated params chain of every step in the window
        self.loss = float(loss)
        self.state = (p, s)

    def run_window(self):
        t0 = time.perf_counter()
        self._window()
        self.runs.append(self.n_tokens * self.iters
                         / (time.perf_counter() - t0))

    def run_traced_window(self, match="jit_step"):
        """One window under a profiler trace → device-measured step time."""
        try:
            from device_timing import measure_device_step
            r = measure_device_step(self._window, match)
            if r is not None:
                self.device_step_s = r["median_s"]
        except Exception as e:
            print(f"[bench] device trace failed: {e!r}", file=sys.stderr)

    def host_tokens_per_sec(self):
        return statistics.median(self.runs) if self.runs else None

    def device_tokens_per_sec(self):
        if self.device_step_s:
            return self.n_tokens / self.device_step_s
        return None


def flax_baseline_timer(cfg, batch, iters):
    """Same-shape decoder LM in plain flax.linen + optax — the BASELINE.md
    'JAX/Flax reference' denominator, measured on the same chip in-process
    (returns a warm StepTimer for interleaved measurement)."""
    import jax
    import jax.numpy as jnp
    import numpy as np
    import optax
    import flax.linen as fnn

    class Block(fnn.Module):
        n_heads: int
        d_model: int
        d_ff: int
        dtype: object

        @fnn.compact
        def __call__(self, x):
            h = fnn.LayerNorm(dtype=jnp.float32)(x).astype(self.dtype)
            h = fnn.SelfAttention(num_heads=self.n_heads, dtype=self.dtype,
                                  deterministic=True)(
                h, mask=fnn.make_causal_mask(jnp.zeros(x.shape[:2])))
            x = x + h
            h = fnn.LayerNorm(dtype=jnp.float32)(x).astype(self.dtype)
            h = fnn.Dense(self.d_ff, dtype=self.dtype)(h)
            h = fnn.gelu(h)
            h = fnn.Dense(self.d_model, dtype=self.dtype)(h)
            return x + h

    class LM(fnn.Module):
        cfg: object

        @fnn.compact
        def __call__(self, tokens):
            c = self.cfg
            emb = fnn.Embed(c.vocab_size, c.d_model, dtype=c.dtype)
            pos = self.param("pos", fnn.initializers.normal(0.02),
                             (c.max_len, c.d_model))
            x = emb(tokens) + pos[:tokens.shape[1]].astype(c.dtype)
            for _ in range(c.n_layers):
                x = Block(c.n_heads, c.d_model, c.d_ff, c.dtype)(x)
            x = fnn.LayerNorm(dtype=jnp.float32)(x)
            return emb.attend(x.astype(c.dtype)).astype(jnp.float32)

    model = LM(cfg)
    rng = np.random.default_rng(0)
    toks = jnp.asarray(rng.integers(0, cfg.vocab_size, (batch, cfg.max_len)),
                       jnp.int32)
    tgts = jnp.roll(toks, -1, axis=1)
    params = model.init(jax.random.key(0), toks)
    opt = optax.adamw(3e-4)
    opt_state = jax.jit(opt.init)(params)

    def loss_fn(p, toks, tgts):
        logits = model.apply(p, toks)
        logp = jax.nn.log_softmax(logits, axis=-1)
        return -jnp.mean(jnp.take_along_axis(logp, tgts[..., None], -1))

    # donate params/opt_state exactly like TransformerLM.make_train_step so
    # the vs_baseline ratio compares like for like
    import functools

    @functools.partial(jax.jit, donate_argnums=(0, 1))
    def flax_step(p, s, toks, tgts):
        loss, g = jax.value_and_grad(loss_fn)(p, toks, tgts)
        up, s = opt.update(g, s, p)
        return optax.apply_updates(p, up), s, loss

    return StepTimer(flax_step, params, opt_state, toks, tgts, iters)


def measure(rung: str) -> dict:
    """One full measurement at a given size rung ("small" | "large" | "cpu").

    Runs in the CURRENT process, which then holds the chip: ``main`` runs
    the TPU rungs via ``--worker`` children, one at a time, and stays off
    jax itself. The "cpu" rung is the BENCH_CPU=1 plumbing toy."""
    t_start = time.perf_counter()

    def phase(msg):
        print(f"[bench:{rung}] t+{time.perf_counter() - t_start:5.1f}s {msg}",
              file=sys.stderr, flush=True)

    import jax

    if rung == "cpu":
        jax.config.update("jax_platforms", "cpu")

    import jax.numpy as jnp
    import numpy as np
    import optax
    from deeplearning4j_tpu.models import transformer as transformer_mod
    from deeplearning4j_tpu.async_runtime import configure_compile_cache
    from deeplearning4j_tpu.models.transformer import (
        TransformerConfig, TransformerLM)
    from deeplearning4j_tpu.observability.cost_model import device_peaks

    devices = jax.devices()
    platform = devices[0].platform
    device_kind = devices[0].device_kind
    on_tpu = platform == "tpu"
    phase(f"platform={platform} device_kind={device_kind} "
          f"devices={len(devices)} "
          f"compile_cache={configure_compile_cache()}")
    if rung != "cpu" and not on_tpu:
        # a CPU number must never be written under the TPU metric's name
        raise RuntimeError(f"worker rung {rung!r} came up on platform="
                           f"{platform}; refusing to measure")

    # The attention backend is the measured auto policy (XLA attention below
    # transformer.FLASH_MIN_SEQ). Override via BENCH_FLASH=0/1 for A/B runs.
    if os.environ.get("BENCH_FLASH"):
        transformer_mod.FLASH_ATTENTION = os.environ["BENCH_FLASH"] == "1"
    # Live-window A/B knobs (never set by the driver): pin the CE chunking,
    # the batch ladder, or skip the flax denominator to halve a probe's cost.
    ce_override = (int(os.environ["BENCH_CE_CHUNKS"])
                   if os.environ.get("BENCH_CE_CHUNKS") else None)
    if ce_override is not None and ce_override <= 1:
        ce_override = 0                      # 0 and 1 both mean "unchunked"
    batch_override = (int(os.environ["BENCH_BATCH"])
                      if os.environ.get("BENCH_BATCH") else None)
    skip_flax = os.environ.get("BENCH_SKIP_FLAX") == "1"

    def build_cfg(remat, ce_chunks):
        if ce_override is not None:
            ce_chunks = ce_override
        if not on_tpu:                       # BENCH_CPU=1 plumbing toy
            return TransformerConfig(
                vocab_size=1024, n_layers=2, n_heads=4, d_model=128,
                max_len=128, dtype=jnp.float32, remat=remat, fused_qkv=True,
                ce_chunks=0)
        if rung == "small":
            # a shape that compiles in tens of seconds — banks a
            # device-timed number before the large config is attempted
            return TransformerConfig(
                vocab_size=16384, n_layers=4, n_heads=8, d_model=512,
                max_len=512, dtype=jnp.bfloat16, remat=remat, fused_qkv=True,
                ce_chunks=ce_chunks)
        # "large": ~190M params so the MXU (not HBM) sets the ceiling
        return TransformerConfig(
            vocab_size=32768, n_layers=12, n_heads=16, d_model=1024,
            max_len=1024, dtype=jnp.bfloat16, remat=remat, fused_qkv=True,
            ce_chunks=ce_chunks)

    # CPU: longer windows + more of them — the 1-core container's load
    # jitter puts ±10% on any single window, and the round-4 "regression"
    # (driver 0.908x vs builder 1.0-1.13x at the SAME commit) was exactly
    # that noise. The ratio below is the median of PAIRED interleaved
    # windows, which cancels common-mode drift.
    iters = 10
    repeats = 3 if on_tpu else 7
    rng = np.random.default_rng(0)

    # OOM ladder: unchunked CE first (measured 2.7% faster on-device at the
    # large config, 2026-07-31 window), then chunked CE (streams the
    # (B,T,V) logits — the memory saver), then remat, then half batch.
    # HBM is 16 GB on v5e; the warmup step is where RESOURCE_EXHAUSTED
    # surfaces, so each rung is attempted through it
    if not on_tpu:
        ladder = [(4, False, 0)]
    elif rung == "small":
        ladder = [(32, False, 0), (32, False, 4), (16, False, 4)]
    else:
        ladder = [(8, False, 0), (8, False, 8), (8, True, 8), (4, True, 8)]
    if batch_override is not None:
        # batch-only probe: keep the rung's CE progression so the override
        # changes ONE variable and retains the chunked-CE OOM fallback
        ce_rungs = sorted({ce for _, _, ce in ladder})
        ladder = [(batch_override, False, ce) for ce in ce_rungs]
    if ce_override is not None:
        # the override collapses the ce dimension — drop rungs that become
        # duplicates so an OOM is never retried on an identical config
        seen, deduped = set(), []
        for b, r, _ in ladder:
            if (b, r) not in seen:
                seen.add((b, r))
                deduped.append((b, r, ce_override))
        ladder = deduped
    last_err = None
    for batch, remat, ce_chunks in ladder:
        cfg = build_cfg(remat, ce_chunks)
        model = TransformerLM(cfg, mesh=None)
        params = model.init_params(jax.random.key(0))
        opt = optax.adamw(3e-4)
        opt_state = jax.jit(opt.init)(params)
        step = model.make_train_step(opt)
        toks = jnp.asarray(
            rng.integers(0, cfg.vocab_size, (batch, cfg.max_len)), jnp.int32)
        tgts = jnp.roll(toks, -1, axis=1)
        # cost-model cross-check input: lowered BEFORE the warmup executes
        # (donation leaves the param buffers deleted afterwards); the trace
        # is cached, so the warmup's compile reuses it
        cost_flops = cost_analysis_flops(step, params, opt_state, toks, tgts)
        try:
            phase(f"warmup (compile) batch={batch} remat={remat}")
            ours = StepTimer(step, params, opt_state, toks, tgts, iters)
            phase("warmup done")
            break
        except Exception as e:
            if "RESOURCE_EXHAUSTED" not in str(e) and "Out of memory" \
                    not in str(e):
                raise
            # keep only the text: the exception's traceback frames would pin
            # the failed rung's param/opt-state device buffers and defeat
            # the retry
            last_err = str(e)[:500]
            print(f"[bench] batch={batch} remat={remat} OOM — stepping "
                  f"down the ladder", file=sys.stderr)
            del e, params, opt_state, step
    else:
        raise RuntimeError(f"all bench configs OOMed: {last_err}")

    # --- plain-Flax denominator on the same chip, measured INTERLEAVED ---
    flax_timer = None
    try:
        if skip_flax:
            raise RuntimeError("BENCH_SKIP_FLAX=1 (A/B probe)")
        phase("flax denominator warmup (compile)")
        flax_timer = flax_baseline_timer(cfg, batch, iters)
    except Exception as e:  # measured best-effort; failure is reported, not hidden
        print(f"[bench] flax baseline failed: {e!r}", file=sys.stderr)

    for i in range(repeats):
        phase(f"timed window {i + 1}/{repeats}")
        ours.run_window()
        if flax_timer is not None:
            flax_timer.run_window()
    # device-timed windows (the headline number on TPU)
    if on_tpu:
        phase("traced windows (device timing)")
        ours.run_traced_window("jit_step")
        if flax_timer is not None:
            flax_timer.run_traced_window("jit_flax_step")
    phase("measurement done")

    host_tps = ours.host_tokens_per_sec()
    dev_tps = ours.device_tokens_per_sec()
    tokens_per_sec = dev_tps or host_tps
    timing_source = "device_trace" if dev_tps else "host_value_fetch"
    flax_host = flax_timer.host_tokens_per_sec() if flax_timer else None
    flax_dev = flax_timer.device_tokens_per_sec() if flax_timer else None
    # ratio compares like timing with like: device/device, else host/host;
    # flax_reported tracks the same method so the JSON stays self-consistent.
    # Host ratio = median of PAIRED interleaved windows (ours_i / flax_i):
    # machine-load drift hits both sides of a pair equally and divides out,
    # where median(ours)/median(flax) would keep it as signal
    if dev_tps and flax_dev:
        vs_flax, flax_reported = dev_tps / flax_dev, flax_dev
        ratio_method = "device_trace_ratio"
    elif host_tps and flax_host:
        if len(ours.runs) == len(flax_timer.runs) and ours.runs:
            vs_flax = statistics.median(
                a / b for a, b in zip(ours.runs, flax_timer.runs))
            # NOTE: not recomputable from host_tokens_per_sec /
            # flax_tokens_per_sec (those are per-side medians) — the
            # ratio_method field in the JSON names which estimator ran
            ratio_method = "paired_window_median"
        else:
            vs_flax = host_tps / flax_host
            ratio_method = "median_of_medians"
        flax_reported = flax_host
    else:
        vs_flax, flax_reported, ratio_method = None, None, None

    # --- MFU: causal-attention FLOPs/token = 6·N_params + 6·L·T·d ---
    n_params = sum(int(np.prod(a.shape)) for a in jax.tree.leaves(params))
    flops_per_token = 6 * n_params + 6 * cfg.n_layers * cfg.max_len * cfg.d_model
    # an unlisted device kind raises: no MFU against a guessed peak
    peak = device_peaks(device_kind)[0]
    mfu = (tokens_per_sec * flops_per_token / peak) if on_tpu else None
    # an MFU above 1.0 is physically impossible on one chip — flag loudly
    # rather than report nonsense
    timing_suspect = bool(mfu is not None and mfu > 1.0)

    # --- analytic vs. XLA-cost-model FLOPs cross-check -------------------
    # The 6·N counting that prices the MFU is an ESTIMATE; the compiled
    # step's own cost analysis is the ground truth for what the program
    # computes (unoptimized HLO — remat re-computation shows up here, so
    # remat configs legitimately exceed 6·N). >10% disagreement on a
    # non-remat config means the estimate (and the MFU built on it) is off.
    analytic_step_flops = float(flops_per_token) * toks.shape[0] * cfg.max_len
    flops_disagreement = None
    flops_estimate_suspect = False
    if cost_flops:
        flops_disagreement = abs(cost_flops - analytic_step_flops) \
            / analytic_step_flops
        flops_estimate_suspect = bool(not cfg.remat
                                      and flops_disagreement
                                      > FLOPS_DISAGREE_WARN)
        if flops_estimate_suspect:
            print(f"[bench] WARNING: analytic 6·N FLOPs/step "
                  f"({analytic_step_flops:.3e}) disagrees with "
                  f"cost_analysis ({cost_flops:.3e}) by "
                  f"{flops_disagreement:.1%} (> {FLOPS_DISAGREE_WARN:.0%}) "
                  f"— the reported MFU inherits that error",
                  file=sys.stderr)
        # feed the live observatory the same numbers so a long-running
        # process started from this entry point serves them on /debug/perf
        try:
            from deeplearning4j_tpu.observability import cost_model as _cost
            _cost.global_cost_model().record_cost(
                "bench.TransformerLM.step", cost_flops)
        except Exception:
            pass

    out = {
        "metric": "transformer_lm_train_tokens_per_sec",
        "value": round(tokens_per_sec, 1),
        "unit": "tokens/sec",
        # null (not 1.0) when the denominator could not be measured — a
        # missing baseline must never read as parity
        "vs_baseline": round(vs_flax, 3) if vs_flax else None,
        "ratio_method": ratio_method,
        "platform": platform,
        "device_kind": device_kind,
        "device_count": len(devices),
        "timing_source": timing_source,
        "mfu": round(mfu, 4) if mfu is not None else None,
        "device_step_ms": round(ours.device_step_s * 1e3, 3)
            if ours.device_step_s else None,
        "host_tokens_per_sec": round(host_tps, 1) if host_tps else None,
        "flax_tokens_per_sec": round(flax_reported, 1) if flax_reported else None,
        "n_params": n_params,
        "analytic_flops_per_step": analytic_step_flops,
        "cost_model_flops_per_step": cost_flops,
        "flops_disagreement": (round(flops_disagreement, 4)
                               if flops_disagreement is not None else None),
        "config": {"layers": cfg.n_layers, "d_model": cfg.d_model,
                   "seq": cfg.max_len, "batch": batch, "remat": cfg.remat,
                   "dtype": str(getattr(cfg.dtype, "__name__", cfg.dtype))},
        "flash_attention": transformer_mod._use_flash_attention(cfg.max_len),
        "loss": float(ours.loss),
    }
    if flops_estimate_suspect:
        out["flops_estimate_suspect"] = True
    if timing_suspect:
        out["timing_suspect"] = True
        print("[bench] WARNING: computed MFU > 1.0 — step timing is not "
              "trustworthy; treat value/mfu as an upper "
              "bound and vs_baseline (same-method ratio) as the meaningful "
              "number", file=sys.stderr)
    return out


WORKER_MARK = "WORKER_JSON:"
WORKER_BUDGET_S = {"small": 420, "large": 900}


def run_worker_phase(rung: str):
    """Run ``measure(rung)`` in a subprocess with a hard timeout, so a rung
    that hangs costs one phase, not the whole bench, and the chip is
    released between rungs. Returns (result_dict | None, error | None)."""
    try:
        # stderr inherits the parent's so the worker's phase() progress
        # markers stream LIVE while a phase runs
        r = subprocess.run(
            [sys.executable, os.path.abspath(__file__), "--worker", rung],
            stdout=subprocess.PIPE, stderr=None, text=True,
            timeout=WORKER_BUDGET_S[rung])
    except subprocess.TimeoutExpired:
        print(f"[bench] {rung} phase timed out after "
              f"{WORKER_BUDGET_S[rung]}s", file=sys.stderr)
        return None, f"{rung} phase timed out after {WORKER_BUDGET_S[rung]}s"
    for line in (r.stdout or "").splitlines():
        if line.startswith(WORKER_MARK):
            return json.loads(line[len(WORKER_MARK):]), None
    return None, (f"{rung} phase rc={r.returncode}: "
                  f"{(r.stdout or '').strip()[-800:]}")


def main() -> int:
    if len(sys.argv) >= 3 and sys.argv[1] == "--worker":
        out = measure(sys.argv[2])
        print(WORKER_MARK + json.dumps(out), flush=True)
        return 0

    if os.environ.get("BENCH_CPU") == "1":
        print(json.dumps(measure("cpu")))
        return 0

    # small first (banks a device-timed number early), then the
    # ~190M-param headline config; a worker that does not come up on a TPU
    # refuses to measure, so no accelerator means no result and rc != 0
    phases, errors = {}, {}
    for rung in ("small", "large"):
        res, perr = run_worker_phase(rung)
        if res is not None:
            phases[rung] = res
        else:
            errors[rung] = perr
    best = phases.get("large") or phases.get("small")
    if best is None:
        print("[bench] no phase produced a result: "
              + "; ".join(f"{k}: {v}" for k, v in errors.items()),
              file=sys.stderr)
        return 1
    best["phases"] = {
        k: {kk: v[kk] for kk in ("value", "vs_baseline", "mfu",
                                 "device_step_ms", "timing_source",
                                 "n_params", "platform", "timing_suspect")
            if kk in v}
        for k, v in phases.items()}
    if errors:
        best["phase_errors"] = errors
    print(json.dumps(best))
    return 0


if __name__ == "__main__":
    sys.exit(main())
