"""MFU A/B ladder — unattended flagship-step optimization study
(VERDICT r4 #4: device-time fused-QKV and scan-layers, bf16 optimizer
state, an XLA-flag rung, and a T=4096 rung where the flash kernel engages).

Each rung is ONE subprocess (fresh backend, one chip owner at a time, a
hard timeout, env-delivered XLA flags) that device-times the flagship train
step via the XPlane trace (benchmarks/device_timing.py). Every run starts
fresh: one JSON line per rung is written to ``chiprun_out/mfu_ladder.jsonl``
as each rung finishes (the directory the chip tool brings back); the stdout
summary at the end carries vs-base ratios. ``ab/mfu_ladder_live.jsonl`` is
the dated 2026-08-02 record, never read back.

Run: ``python benchmarks/mfu_ladder.py`` (TPU; add ``--cpu-smoke`` for a
tiny-config correctness pass on CPU).
"""
from __future__ import annotations

import json
import os
import subprocess
import sys
import time

HERE = os.path.dirname(os.path.abspath(__file__))
OUT = os.path.join(os.path.dirname(HERE), "chiprun_out", "mfu_ladder.jsonl")
RUNG_TIMEOUT_S = 600

# (name, config-overrides, env-overrides) — base first so every later
# rung has its denominator banked even if the window dies early
RUNGS = [
    ("base_12L_d1024_T1024_b8", {}, {}),
    ("no_fused_qkv", {"fused_qkv": False}, {}),
    # plain scan_layers OOM'd the window (bf16 [12,8,1024,...] HLO temps:
    # the scan saves every layer's activations); remat bounds the live set
    # to one layer. The "dots" save policy keeps matmul outputs resident
    # so backward replays only the cheap ops instead of re-paying the MXU
    # — the two rungs A/B full-recompute vs save-dots under scan
    ("scan_layers", {"scan_layers": True, "remat": True}, {}),
    ("scan_layers_remat_dots",
     {"scan_layers": True, "remat": True, "remat_policy": "dots"}, {}),
    ("opt_state_bf16", {"opt_bf16": True}, {}),
    ("latency_hiding_scheduler", {},
     {"LIBTPU_INIT_ARGS": "--xla_tpu_enable_latency_hiding_scheduler=true"}),
    ("T2048_b4", {"max_len": 2048, "batch": 4}, {}),
    ("T4096_b2_flash_auto", {"max_len": 4096, "batch": 2}, {}),
    ("T4096_b2_flash_off", {"max_len": 4096, "batch": 2, "flash": "0"}, {}),
]


def measure_rung(overrides: dict, smoke: bool) -> dict:
    """Runs INSIDE the subprocess: build the flagship config with the
    rung's overrides, device-time the train step."""
    import jax
    import jax.numpy as jnp
    import numpy as np
    import optax

    sys.path.insert(0, HERE)
    sys.path.insert(0, os.path.dirname(HERE))
    from deeplearning4j_tpu.models import transformer as tmod
    from deeplearning4j_tpu.models.transformer import (TransformerConfig,
                                                       TransformerLM)

    if overrides.get("flash") is not None:
        tmod.FLASH_ATTENTION = overrides["flash"] == "1"

    if smoke:
        cfg = TransformerConfig(
            vocab_size=512, n_layers=2, n_heads=4, d_model=128,
            max_len=128,
            dtype=jnp.float32, fused_qkv=overrides.get("fused_qkv", True),
            scan_layers=overrides.get("scan_layers", False),
            remat=overrides.get("remat", False),
            remat_policy=overrides.get("remat_policy"))
        batch = 2
        iters, repeats = 2, 1
    else:
        cfg = TransformerConfig(
            vocab_size=32768, n_layers=12, n_heads=16, d_model=1024,
            max_len=int(overrides.get("max_len", 1024)),
            dtype=jnp.bfloat16,
            fused_qkv=overrides.get("fused_qkv", True),
            scan_layers=overrides.get("scan_layers", False),
            remat=overrides.get("remat", False),
            remat_policy=overrides.get("remat_policy"))
        batch = int(overrides.get("batch", 8))
        iters, repeats = 10, 2

    model = TransformerLM(cfg, mesh=None)
    params = model.init_params(jax.random.key(0))
    if overrides.get("opt_bf16"):
        opt = optax.adamw(3e-4, mu_dtype=jnp.bfloat16)
    else:
        opt = optax.adamw(3e-4)
    opt_state = jax.jit(opt.init)(params)
    step = model.make_train_step(opt)
    rng = np.random.default_rng(0)
    toks = jnp.asarray(rng.integers(0, cfg.vocab_size, (batch, cfg.max_len)),
                       jnp.int32)
    tgts = jnp.roll(toks, -1, axis=1)

    t0 = time.perf_counter()
    params, opt_state, loss = step(params, opt_state, toks, tgts)
    loss0 = float(loss)                       # value fetch = real sync
    compile_s = time.perf_counter() - t0

    def window():
        nonlocal params, opt_state
        lo = None
        for _ in range(iters):
            params, opt_state, lo = step(params, opt_state, toks, tgts)
        float(lo)

    n_tokens = batch * cfg.max_len
    host_tps = []
    for _ in range(repeats):
        t0 = time.perf_counter()
        window()
        host_tps.append(n_tokens * iters / (time.perf_counter() - t0))

    device_step_s = None
    platform = jax.devices()[0].platform
    if platform != "cpu":
        try:
            from device_timing import measure_device_step
            r = measure_device_step(window, "jit_step")
            if r is not None:
                device_step_s = r["median_s"]
        except Exception as e:  # report, keep the host number
            print(f"[mfu] device trace failed: {e!r}", file=sys.stderr)

    tps = (n_tokens / device_step_s) if device_step_s else max(host_tps)
    n_params = sum(int(np.prod(a.shape)) for a in jax.tree.leaves(params))
    flops_tok = 6 * n_params + 6 * cfg.n_layers * cfg.max_len * cfg.d_model
    from deeplearning4j_tpu.observability.cost_model import device_peaks
    peak = device_peaks(jax.devices()[0].device_kind)[0]
    mfu = tps * flops_tok / peak if platform != "cpu" else None
    return {
        "tokens_per_sec": round(tps, 1),
        "timing_source": "device_trace" if device_step_s else "host",
        "device_step_ms": round(device_step_s * 1e3, 3)
        if device_step_s else None,
        "mfu": round(mfu, 4) if mfu is not None else None,
        "platform": platform,
        "compile_s": round(compile_s, 1),
        "loss": loss0,
        "n_params": n_params,
        "batch": batch,
        "seq": cfg.max_len,
        "flash_engaged": tmod._use_flash_attention(cfg.max_len),
    }


def main():
    smoke = "--cpu-smoke" in sys.argv
    if "--rung" in sys.argv:                      # subprocess entry
        i = sys.argv.index("--rung")
        overrides = json.loads(sys.argv[i + 1])
        out = measure_rung(overrides, smoke)
        print("RUNG_JSON:" + json.dumps(out), flush=True)
        return

    results = {}
    os.makedirs(os.path.dirname(OUT), exist_ok=True)
    open(OUT, "w").close()                # no resume: stale rungs never count
    for name, overrides, env in RUNGS:
        if smoke and name == "latency_hiding_scheduler":
            continue                              # flag is TPU-only
        child_env = dict(os.environ)
        child_env.update(env)
        if smoke:
            child_env["JAX_PLATFORMS"] = "cpu"
            overrides = {k: v for k, v in overrides.items()
                         if k not in ("max_len", "batch")}
        cmd = [sys.executable, os.path.abspath(__file__),
               "--rung", json.dumps(overrides)]
        if smoke:
            cmd.append("--cpu-smoke")
        t0 = time.time()
        try:
            r = subprocess.run(cmd, capture_output=True, text=True,
                               timeout=RUNG_TIMEOUT_S, env=child_env)
        except subprocess.TimeoutExpired:
            rec = {"rung": name, "error":
                   f"timeout after {RUNG_TIMEOUT_S}s"}
            results[name] = rec
            with open(OUT, "a") as f:
                f.write(json.dumps(rec) + "\n")
            continue
        rec = {"rung": name, "env": env, "wall_s": round(time.time() - t0, 1)}
        for line in (r.stdout or "").splitlines():
            if line.startswith("RUNG_JSON:"):
                rec.update(json.loads(line[len("RUNG_JSON:"):]))
                break
        else:
            rec["error"] = (r.stderr or r.stdout or "no output")[-800:]
        results[name] = rec
        with open(OUT, "a") as f:
            f.write(json.dumps(rec) + "\n")
        print(f"[mfu] {name}: "
              f"{rec.get('tokens_per_sec', rec.get('error'))}",
              file=sys.stderr, flush=True)

    base = results.get("base_12L_d1024_T1024_b8", {})
    base_tps = base.get("tokens_per_sec")
    summary = []
    for name, rec in results.items():
        row = {"rung": name,
               "tokens_per_sec": rec.get("tokens_per_sec"),
               "mfu": rec.get("mfu"),
               "timing_source": rec.get("timing_source"),
               "error": rec.get("error")}
        if base_tps and rec.get("tokens_per_sec") \
                and rec.get("seq") == base.get("seq"):
            row["vs_base"] = round(rec["tokens_per_sec"] / base_tps, 3)
        summary.append(row)
    print(json.dumps({"metric": "mfu_ladder", "rungs": summary}))


if __name__ == "__main__":
    main()
