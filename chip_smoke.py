"""chip_smoke.py — the quickest proof that the system still starts on the chip.

One process, the entry points a user would call, at the full width of the
one model the code supports (the flagship: 12 layers, d_model 1024, 16
heads, vocab 32768, bf16, fused QKV), weights random from a seed:

1. refuses to run unless ``jax.devices()[0].platform == "tpu"``;
2. trains: ``TransformerLM.make_train_step(adamw)`` at batch 8, T=1024;
3. serves the same weights: ``DecodeEngine`` (paged cache) →
   ``ModelRegistry.deploy_generative`` → ``ServingRouter`` → ``FrontDoor``,
   real socket requests to ``POST /v1/generate``, and compares one served
   request's last-step logits with ``model.apply`` over the full sequence;
4. builds a ``kv_quant=True`` engine and fails if its numerics gate turned
   int8 storage off;
5. compiles the Pallas flash kernel on the path that uses it (T=4096 train
   steps) and compares the loss with the same steps under XLA attention;
6. with four or more devices, trains over dp x tp and dp x seq meshes (ring
   attention across chips) and takes one compressed-gradient
   ``ShardedTrainer`` step.

Every section asserts what it printed; the first failed check raises, so
the process exits non-zero and prints no result line. The last line of a
passing run is ``{"ok": true, "device": {...}}``. No size option and no
platform option: tests drive the section functions on the CPU at 2L/d128.
Timings and byte counts printed here are smoke output, not benchmark
metrics.
"""
from __future__ import annotations

import contextlib
import json
import os
import sys
import threading
import time
import urllib.request

#: the one piece of state sections share: persistent-cache hit/miss counts
_cache_events = {"hits": 0, "misses": 0}

#: max |Δlogit| allowed between a served decode step and ``model.apply``
#: over the same sequence, by compute dtype. bf16: activations round to 8
#: mantissa bits per layer, and prefill (one (T,T) pass) and one-token
#: decode reduce in different orders: 0.022 measured on the v5e at flagship
#: width, where max|logit| is 2.7 and a paged-cache indexing bug moves
#: logits by that order.
LOGIT_TOL = {"bfloat16": 0.1, "float32": 2e-3}
#: |Δloss| allowed between attention backends / meshes / chip counts
#: (3e-4 measured on the v5e between the flash kernel and XLA attention)
LOSS_TOL = {"bfloat16": 0.01, "float32": 1e-3}


class SmokeFailure(AssertionError):
    """A check on what a section produced did not hold."""


def check(cond, msg: str):
    if not cond:
        raise SmokeFailure(msg)


def say(section: str, msg: str):
    print(f"[smoke:{section}] {msg}", flush=True)


def _dtype_name(cfg) -> str:
    import jax.numpy as jnp
    return jnp.dtype(cfg.dtype).name


def flagship_config(max_len: int = 1024):
    """The flagship (bench.py's "large" rung); only ``max_len`` varies."""
    import jax.numpy as jnp

    from deeplearning4j_tpu.models.transformer import TransformerConfig
    return TransformerConfig(
        vocab_size=32768, n_layers=12, n_heads=16, d_model=1024,
        max_len=max_len, dtype=jnp.bfloat16, fused_qkv=True)


def watch_compile_cache():
    """Count persistent-compile-cache hits and misses (jax.monitoring)."""
    import jax.monitoring as mon

    def on_event(event, **_kw):
        if event.endswith("compilation_cache/cache_hits"):
            _cache_events["hits"] += 1
        elif event.endswith("compilation_cache/cache_misses"):
            _cache_events["misses"] += 1

    mon.register_event_listener(on_event)


@contextlib.contextmanager
def cache_window(section: str, what: str):
    """Report the persistent-cache hits/misses of the compiles inside."""
    before = dict(_cache_events)
    yield
    say(section, f"{what}: persistent cache hits="
        f"{_cache_events['hits'] - before['hits']} "
        f"misses={_cache_events['misses'] - before['misses']}")


def _batch(cfg, batch: int, seed: int = 0):
    import jax.numpy as jnp
    import numpy as np
    toks = jnp.asarray(np.random.default_rng(seed).integers(
        0, cfg.vocab_size, (batch, cfg.max_len)), jnp.int32)
    return toks, jnp.roll(toks, -1, axis=1)


def _peak_bytes():
    import jax
    stats = jax.devices()[0].memory_stats()
    return stats.get("peak_bytes_in_use") if stats else None


def _run_steps(step, params, opt_state, toks, tgts, n: int):
    """n train steps, each ended by fetching the loss VALUE. Returns
    (params, opt_state, losses, seconds-per-call)."""
    losses, secs = [], []
    for _ in range(n):
        t0 = time.perf_counter()
        params, opt_state, loss = step(params, opt_state, toks, tgts)
        losses.append(float(loss))
        secs.append(time.perf_counter() - t0)
    return params, opt_state, losses, secs


# ------------------------------------------------------------------ train
def train_section(cfg, batch: int = 8, steps: int = 5, seed: int = 0):
    """A handful of steps on one fixed seeded batch. Returns (model,
    params, losses)."""
    import jax
    import numpy as np
    import optax

    from deeplearning4j_tpu.models.transformer import TransformerLM

    device = jax.devices()[0]
    model = TransformerLM(cfg, mesh=None)
    params = model.init_params(jax.random.key(seed))
    opt = optax.adamw(3e-4)
    opt_state = jax.jit(opt.init)(params)
    step = model.make_train_step(opt)
    toks, tgts = _batch(cfg, batch, seed)
    with cache_window("train", "train step"):
        params, opt_state, losses, secs = _run_steps(
            step, params, opt_state, toks, tgts, steps)
    say("train", f"losses={[round(x, 4) for x in losses]}")
    check(all(np.isfinite(losses)), f"non-finite loss in {losses}")
    check(all(b < a for a, b in zip(losses, losses[1:])),
          f"loss is not strictly falling: {losses}")
    for name, tree in (("params", params), ("optimizer state", opt_state)):
        homes = set()
        for leaf in jax.tree.leaves(tree):
            homes |= leaf.devices()
        check(homes == {device}, f"{name} live on {homes}, not {device}")
    say("train", f"first call (compile + step) {secs[0]:.1f} s; steady step "
        f"{1e3 * min(secs[1:]):.1f} ms; peak_bytes_in_use={_peak_bytes()} "
        f"(smoke output, not benchmark metrics)")
    del opt_state
    return model, params, losses


# ------------------------------------------------------------------ serve
def _post_json(addr: str, doc: dict, timeout: float = 600.0):
    req = urllib.request.Request(
        addr + "/v1/generate", data=json.dumps(doc).encode(),
        headers={"Content-Type": "application/json"})
    with urllib.request.urlopen(req, timeout=timeout) as r:
        return r.status, json.loads(r.read())


def _post_sse(addr: str, doc: dict, timeout: float = 600.0):
    """One streamed generate → (status, tokens in arrival order, done)."""
    req = urllib.request.Request(
        addr + "/v1/generate",
        data=json.dumps(dict(doc, stream=True)).encode(),
        headers={"Content-Type": "application/json"})
    toks, done, event = [], None, None
    with urllib.request.urlopen(req, timeout=timeout) as r:
        status = r.status
        for raw in r:
            line = raw.decode().rstrip("\n")
            if line.startswith("event: "):
                event = line[7:]
            elif line.startswith("data: "):
                data = json.loads(line[6:])
                if event == "token":
                    toks.append(data["token"])
                elif event == "done":
                    done = data
                elif event == "error":
                    raise SmokeFailure(f"stream error event: {data}")
    return status, toks, done


def _metric(text: str, name: str):
    """Sum of an unlabelled-or-labelled Prometheus series; None if absent."""
    total = None
    for line in text.splitlines():
        if line.startswith(name) and line[len(name):len(name) + 1] in " {":
            total = (total or 0.0) + float(line.rsplit(" ", 1)[1])
    return total


def serve_section(model, params, prompt_lens, new_tokens: int = 24,
                  slots: int = 8, stream_idx=(1, 4), seed: int = 1):
    """Front door → router → registry → pipeline → engine over real
    sockets, then one served request replayed through the SAME engine
    executables to compare its last-step logits with ``model.apply``."""
    import jax
    import jax.numpy as jnp
    import numpy as np

    from deeplearning4j_tpu.models.generation import (DECODE_FN, PREFILL_FN,
                                                      DecodeEngine)
    from deeplearning4j_tpu.observability.compile_watch import (
        global_compile_watch)
    from deeplearning4j_tpu.observability.cost_model import global_cost_model
    from deeplearning4j_tpu.resilience import faults
    from deeplearning4j_tpu.resilience.policy import CircuitBreaker
    from deeplearning4j_tpu.serving import (FrontDoor, ModelRegistry,
                                            ServingRouter)

    cfg = model.config
    rng = np.random.default_rng(seed)
    prompts = [rng.integers(0, cfg.vocab_size, (n,)).astype(np.int32)
               for n in prompt_lens]
    engine = DecodeEngine(model, params, max_len=cfg.max_len)
    check(engine.paged, "the default engine is not paged")
    buckets = {engine.prefill_bucket(len(p)) for p in prompts}
    check(len(buckets) >= 4, f"prompts hit only buckets {sorted(buckets)}")
    breaker = CircuitBreaker("generation.step:smoke")
    reg = ModelRegistry()
    fd = None
    try:
        t0 = time.perf_counter()
        with cache_window("serve", "warm-up (prefill buckets, insert, "
                          "decode step)"):
            dv = reg.deploy_generative("smoke", engine, slots=slots,
                                       max_new_tokens=new_tokens,
                                       breaker=breaker)
        say("serve", f"warm-up {time.perf_counter() - t0:.1f} s, buckets "
            f"{dv.warmed_buckets}, {slots} slots, page pool "
            f"{dv.gp.snapshot()['pool_bytes'] / 2**20:.0f} MiB")
        fd = FrontDoor(None, ServingRouter(reg, "smoke"), port=0).start()
        addr = fd.get_address()
        watch = global_compile_watch()
        traced = watch.counts()

        def decode_tokens_total():
            with urllib.request.urlopen(addr + "/metrics", timeout=30) as r:
                text = r.read().decode()
            return _metric(text, "dl4j_decode_tokens_total") or 0.0, text

        counted0, _ = decode_tokens_total()

        def doc(i):
            return {"prompt": prompts[i].tolist(),
                    "max_new_tokens": new_tokens}

        code, first = _post_json(addr, doc(0))
        check(code == 200 and len(first["tokens"]) == new_tokens,
              f"first request: {code} {first}")
        check(watch.counts() == traced, f"the first routed request "
              f"traced new programs: {traced} -> {watch.counts()}")

        results, errors = {}, []

        def run(key, i, stream):
            try:
                results[key] = (_post_sse if stream else _post_json)(
                    addr, doc(i))
            except Exception as e:      # collected; re-raised below
                errors.append((key, e))

        jobs = [(("plain", i), i, False) for i in range(len(prompts))]
        jobs += [(("stream", i), i, True) for i in stream_idx]
        threads = [threading.Thread(target=run, args=j, daemon=True)
                   for j in jobs]
        for t in threads:
            t.start()
            time.sleep(0.02)            # later requests join mid-decode
        for t in threads:
            t.join(timeout=900)
        check(not errors, f"requests failed: {errors}")
        check(len(results) == len(jobs), "a request did not finish")
        returned = len(first["tokens"])
        for (kind, i), res in sorted(results.items()):
            tokens = res[1]["tokens"] if kind == "plain" else res[1]
            check(res[0] == 200 and len(tokens) == new_tokens,
                  f"{kind} request {i}: status {res[0]}, "
                  f"{len(tokens)} tokens")
            returned += len(tokens)
        for i in stream_idx:
            _, toks, done = results[("stream", i)]
            plain = results[("plain", i)][1]["tokens"]
            check(toks == plain and done["tokens"] == plain,
                  f"SSE tokens differ from non-streamed for prompt {i}: "
                  f"{toks} vs {plain}")
        check(results[("plain", 0)][1]["tokens"] == first["tokens"],
              "the same greedy prompt produced different tokens")
        check(watch.counts() == traced, f"traffic traced new programs: "
              f"{traced} -> {watch.counts()}")
        counted, metrics = decode_tokens_total()
        check(counted - counted0 == returned, f"dl4j_decode_tokens_total "
              f"grew by {counted - counted0}, tokens returned={returned}")
        check(not _metric(metrics, "dl4j_decode_errors_total"),
              "dl4j_decode_errors_total is non-zero")
        check(not _metric(metrics, "dl4j_decode_shed_total"),
              "requests were shed")
        snap = breaker.snapshot()
        check(snap["state"] == "closed"
              and snap["consecutive_failures"] == 0, f"breaker: {snap}")
        resumed = [e for e in faults.events()
                   if e["category"] == "session_resume_inplace"]
        check(not resumed, f"a decode step failed and was resumed in "
              f"place: {resumed}")
        priced = global_cost_model().snapshot()["fns"]
        for fn in (PREFILL_FN, DECODE_FN):  # accounting swallows its errors
            row = priced.get(fn) or {}
            check(row.get("flops") and row["error"] is None,
                  f"the cost model has no price for {fn}: {row}")
        say("serve", f"{1 + len(jobs)} requests 200, {returned} tokens, "
            f"{len(stream_idx)} streamed == non-streamed, 0 new traces, "
            f"breaker closed")

        # ---- logits: the longest prompt's request, replayed through the
        # engine executables the pipeline just used (slot off zero, so
        # the page table is not the identity), against model.apply
        i = max(range(len(prompts)), key=lambda j: len(prompts[j]))
        served = results[("plain", i)][1]["tokens"]
        prompt, slot = prompts[i], slots - 3
        _first, _logits, kv, t = engine.prefill(prompt[None])
        state = engine.insert_slot(engine.new_state(slots), kv, slot)
        tokens = np.zeros((slots,), np.int32)
        positions = np.zeros((slots,), np.int32)
        agree = 0
        for j in range(new_tokens - 1):
            tokens[slot], positions[slot] = served[j], t + j
            _nxt, logits, state = engine.decode(state, tokens, positions,
                                                j + 1)
            agree += int(np.argmax(np.asarray(logits)[slot])
                         == served[j + 1])
        last = np.asarray(logits)[slot]
        full = np.zeros((1, cfg.max_len), np.int32)
        full[0, :t] = prompt
        full[0, t:t + new_tokens - 1] = served[:-1]
        ref = np.asarray(jax.jit(model.apply)(
            params, jnp.asarray(full)))[0, t + new_tokens - 2]
        diff = float(np.max(np.abs(last - ref)))
        tol = LOGIT_TOL[_dtype_name(cfg)]
        say("serve", f"last decode step vs model.apply at position "
            f"{t + new_tokens - 2}: max|dlogit|={diff:.4g} (tol {tol}, "
            f"max|logit|={float(np.max(np.abs(ref))):.3g}); replay argmax "
            f"== served token on {agree}/{new_tokens - 1} steps")
        check(np.isfinite(last).all() and diff <= tol,
              f"decode logits disagree with model.apply: {diff} > {tol}")
        check(watch.counts() == traced, f"the replay traced new "
              f"programs: {traced} -> {watch.counts()}")
    finally:
        if fd is not None:
            fd.stop()
        reg.shutdown()


# ------------------------------------------------------------- int8 gate
def quant_gate_section(model, params):
    """One kv_quant engine; the gate must leave int8 storage ON."""
    import jax.numpy as jnp

    from deeplearning4j_tpu.models.generation import DecodeEngine

    engine = DecodeEngine(model, params, max_len=model.config.max_len,
                          kv_quant=True)
    state = engine.new_state(1)         # first state build runs the gate
    say("int8", f"quant_gate={engine.quant_gate}")
    check(engine.quant_gate is not None and engine.quant_gate["passed"]
          and engine.kv_quant,
          f"the numerics gate turned int8 KV storage off: "
          f"{engine.quant_gate}")
    check(state.arrays["k"][0].dtype == jnp.int8,
          f"page pool is {state.arrays['k'][0].dtype}, not int8")


# ------------------------------------------------------------------ flash
def flash_section(cfg, batch: int = 2, steps: int = 2,
                  require_mosaic: bool = True, seed: int = 0):
    """Train steps at a sequence length where the attention policy picks
    the Pallas kernel, against the same steps under XLA attention (which
    recomputes activations in backward — same values, and twelve layers of
    (T,T) scores do not have to fit beside everything else)."""
    import dataclasses

    import jax
    import numpy as np
    import optax

    from deeplearning4j_tpu.models import transformer
    from deeplearning4j_tpu.models.transformer import TransformerLM

    check(transformer._use_flash_attention(cfg.max_len),
          f"the attention policy does not pick the flash kernel at "
          f"T={cfg.max_len}")
    toks, tgts = _batch(cfg, batch, seed)
    opt = optax.adamw(3e-4)

    def arm(arm_cfg, name):
        model = TransformerLM(arm_cfg, mesh=None)
        params = model.init_params(jax.random.key(seed))
        opt_state = jax.jit(opt.init)(params)
        step = model.make_train_step(opt)
        text = step.lower(params, opt_state, toks, tgts).as_text()
        with cache_window("flash", f"{name} step"):
            _p, _s, losses, secs = _run_steps(step, params, opt_state,
                                              toks, tgts, steps)
        say("flash", f"{name}: losses={[round(x, 4) for x in losses]}, "
            f"first call {secs[0]:.1f} s, then {1e3 * min(secs[1:]):.0f} ms")
        return losses, "tpu_custom_call" in text

    flash_losses, mosaic = arm(cfg, "flash")
    if require_mosaic:
        check(mosaic, "the lowered step has no Mosaic custom call: the "
              "kernel ran in interpret mode or the XLA path was taken")
    os.environ["DL4J_TPU_ATTN_BACKEND"] = "xla"     # read at trace time
    try:
        xla_losses, xla_mosaic = arm(
            dataclasses.replace(cfg, remat=True), "xla")
    finally:
        del os.environ["DL4J_TPU_ATTN_BACKEND"]
    check(not xla_mosaic, "the XLA arm lowered a Mosaic custom call")
    tol = LOSS_TOL[_dtype_name(cfg)]
    diffs = [abs(a - b) for a, b in zip(flash_losses, xla_losses)]
    check(all(np.isfinite(flash_losses)) and max(diffs) <= tol,
          f"flash vs XLA attention losses differ by {diffs} (tol {tol})")
    say("flash", f"mosaic custom call in lowering: {mosaic}; "
        f"|dloss| vs XLA attention {[round(d, 5) for d in diffs]} "
        f"(tol {tol})")
    return flash_losses


# -------------------------------------------------------------- multichip
def multichip_section(cfg, one_chip_losses, batch: int = 8,
                      steps: int = 3, seed: int = 0):
    """Section F: the flagship over four devices — dp x tp, then dp x seq
    (ring attention crossing devices) — against the one-chip losses of the
    same seed and batch, and one compressed-gradient ShardedTrainer step.
    The first loss barely depends on the data at random init; the later
    ones are what say the sharded backward and update are right."""
    import jax
    import numpy as np
    from jax.sharding import NamedSharding, PartitionSpec as P

    from deeplearning4j_tpu.models.transformer import make_sharded_lm
    from deeplearning4j_tpu.parallel import (FixedThresholdAlgorithm,
                                             MeshSpec, ShardedTrainer)

    devices = jax.devices()[:4]
    tol = LOSS_TOL[_dtype_name(cfg)]
    toks, tgts = _batch(cfg, batch, seed)
    for name, spec in (
            ("dp2 x tp2", MeshSpec.dp_tp(data=2, model=2)),
            ("dp2 x seq2", MeshSpec.dp_tp_sp(data=2, model=1, seq=2))):
        mesh = spec.build(devices)
        model, params, opt_state, opt = make_sharded_lm(cfg, mesh,
                                                        seed=seed)
        step = model.make_train_step(opt)
        axes = [a if a in mesh.axis_names else None for a in ("data", "seq")]
        sharding = NamedSharding(mesh, P(*axes))
        with cache_window("multichip", f"{name} step"):
            params, opt_state, losses, secs = _run_steps(
                step, params, opt_state, jax.device_put(toks, sharding),
                jax.device_put(tgts, sharding), steps)
        holders = set()
        for leaf in jax.tree.leaves(params):
            holders |= {s.device for s in leaf.addressable_shards}
        in_use = {d.id: (d.memory_stats() or {}).get("bytes_in_use")
                  for d in devices}
        say("multichip", f"{name}: losses={[round(x, 4) for x in losses]}, "
            f"first call {secs[0]:.1f} s, then {1e3 * min(secs[1:]):.0f} "
            f"ms; bytes_in_use by device {in_use}")
        check(all(np.isfinite(losses)), f"{name}: losses {losses}")
        check(holders == set(devices),
              f"{name}: parameter shards live on {holders} only")
        if devices[0].platform == "tpu":    # the CPU reports no stats
            check(all(in_use.values()),
                  f"{name}: a device holds nothing: {in_use}")
        diffs = [abs(a - b) for a, b in zip(losses, one_chip_losses)]
        check(diffs[0] <= tol and max(diffs) <= 5 * tol,
              f"{name}: losses {losses} vs one chip {one_chip_losses}: "
              f"|d|={diffs} (tol {tol} on the first step, 5x after "
              f"updates)")
        del params, opt_state

    from deeplearning4j_tpu.nn.conf.configuration import (
        NeuralNetConfiguration)
    from deeplearning4j_tpu.nn.conf.inputs import InputType
    from deeplearning4j_tpu.nn.conf.layers import DenseLayer, OutputLayer
    from deeplearning4j_tpu.nn.multilayer import MultiLayerNetwork
    from deeplearning4j_tpu.optim.updaters import Sgd
    conf = (NeuralNetConfiguration.builder().seed(3).updater(Sgd(0.1))
            .list()
            .layer(DenseLayer(n_out=256, activation="relu"))
            .layer(OutputLayer(n_out=16, activation="softmax",
                               loss_function="negativeloglikelihood"))
            .set_input_type(InputType.feed_forward(128)).build())
    rng = np.random.default_rng(seed)
    x = rng.random((64, 128), dtype=np.float32)
    y = np.eye(16, dtype=np.float32)[rng.integers(0, 16, 64)]
    trainer = ShardedTrainer(MultiLayerNetwork(conf),
                             MeshSpec.data_parallel(4), devices=devices,
                             grad_compression=FixedThresholdAlgorithm(1e-4))
    trainer.fit(x, y)
    score = float(trainer.score())
    say("multichip", f"ShardedTrainer compressed-gradient step over "
        f"{len(devices)} devices: score={score:.4f}")
    check(np.isfinite(score), f"compressed step score {score}")


# ------------------------------------------------------------------- main
def main() -> int:
    import jax
    import jaxlib

    devices = jax.devices()
    device = {"platform": devices[0].platform,
              "kind": devices[0].device_kind, "count": len(devices)}
    if device["platform"] != "tpu":
        print(f"chip_smoke: no TPU: jax.devices() found {device}; "
              f"refusing to run", file=sys.stderr)
        return 2

    from importlib import metadata

    from deeplearning4j_tpu import native
    from deeplearning4j_tpu.async_runtime import configure_compile_cache
    from deeplearning4j_tpu.observability.cost_model import device_peaks

    watch_compile_cache()
    cache_dir = configure_compile_cache()
    say("env", f"platform={device['platform']} "
        f"device_kind={device['kind']} devices={device['count']} "
        f"jax={jax.__version__} jaxlib={jaxlib.__version__} "
        f"libtpu={metadata.version('libtpu')}")
    say("env", f"compile cache dir={cache_dir} "
        f"(JAX_COMPILATION_CACHE_DIR="
        f"{os.environ.get('JAX_COMPILATION_CACHE_DIR')})")
    say("env", f"peaks for this device kind: {device_peaks(device['kind'])}; "
        f"native host-ops library loaded: {native.is_native()} (off the "
        f"main path)")

    t0 = time.perf_counter()
    cfg = flagship_config()
    model, params, losses = train_section(cfg)
    serve_section(model, params,
                  prompt_lens=(12, 60, 200, 500, 900, 30, 120, 350))
    quant_gate_section(model, params)
    del model, params
    flash_section(flagship_config(max_len=4096))
    if device["count"] >= 4:
        multichip_section(cfg, one_chip_losses=losses)
    else:
        say("multichip", f"not run ({device['count']} devices)")
    say("done", f"all sections passed in {time.perf_counter() - t0:.0f} s; "
        f"persistent cache totals: {_cache_events}")
    print(json.dumps({"ok": True, "device": device}), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
