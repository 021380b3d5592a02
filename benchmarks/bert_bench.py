"""BERT-base fine-tune samples/sec: TF-imported SameDiff vs HF FlaxBert.

BASELINE.md north-star row 2: "SameDiff TF-import BERT-base fine-tune
(samples/sec) >=70% of JAX/Flax reference". The numerator is the literal
reference workflow (ref: SURVEY J8 ``TFGraphMapper.importGraph`` on bert.pb
+ ``SameDiff#fit``): freeze a TF BERT-base, import it, promote the encoder
weights to variables, attach a [CLS] classifier head, and fine-tune through
``sd.fit``. The denominator is ``transformers.FlaxBertModel`` — an actual
JAX/Flax BERT — with the same head, optimizer (Adam 2e-5), batch, dtype
(f32: the imported graph's dtype), trainable set (everything), and per-step
loss-value fetch.

Both sides are measured INTERLEAVED (A,B,A,B...). On TPU the printed
value/vs_baseline come from DEVICE-side XPlane timing whenever the trace
parses (BASELINE round-3 protocol); ``timing_source`` records which path won.

Run: python benchmarks/bert_bench.py [--smoke]   (--smoke: tiny CPU config)
"""
from __future__ import annotations

import argparse
import functools
import json
import os
import statistics
import sys
import time

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))



def build_frozen_bert(batch, seq, layers, hidden, heads, intermediate,
                      vocab):
    """Freeze a deterministic TF BERT at the bench shape; returns graph_def."""
    import tensorflow as tf
    from tensorflow.python.framework.convert_to_constants import (
        convert_variables_to_constants_v2)
    from transformers import BertConfig, TFBertModel

    cfg = BertConfig(num_hidden_layers=layers, hidden_size=hidden,
                     num_attention_heads=heads,
                     intermediate_size=intermediate, vocab_size=vocab,
                     hidden_dropout_prob=0.0,
                     attention_probs_dropout_prob=0.0)
    model = TFBertModel(cfg)

    @tf.function
    def f(input_ids, attention_mask):
        return model(input_ids=input_ids,
                     attention_mask=attention_mask).last_hidden_state

    frozen = convert_variables_to_constants_v2(f.get_concrete_function(
        tf.TensorSpec((batch, seq), tf.int32, name="input_ids"),
        tf.TensorSpec((batch, seq), tf.int32, name="attention_mask")))
    return frozen.graph.as_graph_def()


def measure_ours(gd, hidden, batch, seq, vocab, iters, lr):
    """TF-import + promote + head + sd.fit window closure (per-step sync)."""
    import numpy as np

    from deeplearning4j_tpu.data.dataset import MultiDataSet
    from deeplearning4j_tpu.modelimport.tfimport import TFGraphMapper

    sys.path.insert(0, os.path.dirname(os.path.dirname(
        os.path.abspath(__file__))))
    from tests.bert_helpers import (attach_classifier_head,
                                    promote_weight_constants)

    t0 = time.perf_counter()
    sd = TFGraphMapper.import_graph(gd)
    promoted = promote_weight_constants(sd, min_size=512)
    attach_classifier_head(sd, gd, hidden_size=hidden, lr=lr)
    print(f"[bert-bench] import+head: {time.perf_counter() - t0:.1f}s, "
          f"{promoted} tensors promoted", file=sys.stderr, flush=True)

    rng = np.random.default_rng(0)
    ids = rng.integers(0, vocab, (batch, seq)).astype(np.int32)
    mask = np.ones((batch, seq), np.int32)
    y = np.eye(2, dtype=np.float32)[rng.integers(0, 2, batch)]
    ds = MultiDataSet([ids, mask], [y])

    t0 = time.perf_counter()
    sd.fit([ds], epochs=1)                 # warm/compile
    print(f"[bert-bench] ours warmup (compile+run): "
          f"{time.perf_counter() - t0:.1f}s", file=sys.stderr, flush=True)

    def window():
        t0 = time.perf_counter()
        sd.fit([ds] * iters, epochs=1)     # fit float()s the loss per batch
        return batch * iters / (time.perf_counter() - t0)

    return window


def measure_flax(batch, seq, layers, hidden, heads, intermediate, vocab,
                 iters, lr):
    """HF FlaxBertModel + [CLS] head + Adam — the JAX/Flax denominator."""
    import jax
    import jax.numpy as jnp
    import numpy as np
    import optax
    from transformers import BertConfig, FlaxBertModel

    cfg = BertConfig(num_hidden_layers=layers, hidden_size=hidden,
                     num_attention_heads=heads,
                     intermediate_size=intermediate, vocab_size=vocab,
                     hidden_dropout_prob=0.0,
                     attention_probs_dropout_prob=0.0)
    t0 = time.perf_counter()
    model = FlaxBertModel(cfg, seed=0)
    print(f"[bert-bench] flax init: {time.perf_counter() - t0:.1f}s",
          file=sys.stderr, flush=True)

    rng = np.random.default_rng(0)
    ids = jnp.asarray(rng.integers(0, vocab, (batch, seq)), jnp.int32)
    mask = jnp.ones((batch, seq), jnp.int32)
    y = jnp.asarray(np.eye(2, dtype=np.float32)[rng.integers(0, 2, batch)])

    params = {"bert": model.params,
              "head_w": jnp.zeros((hidden, 2), jnp.float32),
              "head_b": jnp.zeros((2,), jnp.float32)}
    opt = optax.adam(lr)
    opt_state = jax.jit(opt.init)(params)

    def loss_fn(p, ids, mask, y):
        out = model(input_ids=ids, attention_mask=mask,
                    params=p["bert"]).last_hidden_state
        logits = out[:, 0] @ p["head_w"] + p["head_b"]
        logp = jax.nn.log_softmax(logits)
        return -jnp.mean(jnp.sum(y * logp, axis=-1))

    @functools.partial(jax.jit, donate_argnums=(0, 1))
    def flax_step(p, s, ids, mask, y):
        loss, g = jax.value_and_grad(loss_fn)(p, ids, mask, y)
        up, s = opt.update(g, s, p)
        return optax.apply_updates(p, up), s, loss

    state = (params, opt_state)
    t0 = time.perf_counter()
    p, s, loss = flax_step(*state, ids, mask, y)
    float(loss)
    state = (p, s)
    print(f"[bert-bench] flax warmup (compile+run): "
          f"{time.perf_counter() - t0:.1f}s", file=sys.stderr, flush=True)

    def window():
        nonlocal state
        p, s = state
        t0 = time.perf_counter()
        for _ in range(iters):
            p, s, loss = flax_step(p, s, ids, mask, y)
            float(loss)                    # per-step fetch, matching sd.fit
        state = (p, s)
        return batch * iters / (time.perf_counter() - t0)

    return window


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--smoke", action="store_true",
                    help="tiny CPU config (CI/dev)")
    args = ap.parse_args()

    import jax

    if args.smoke:
        jax.config.update("jax_platforms", "cpu")
    platform = jax.devices()[0].platform
    on_tpu = platform != "cpu"
    if not (on_tpu or args.smoke):
        sys.exit("[bert-bench] no accelerator (platform=cpu); "
                 "--smoke runs the tiny CPU config")
    print(f"[bert-bench] platform={platform}", file=sys.stderr, flush=True)

    if args.smoke or not on_tpu:
        # 2L/h64 mini-BERT: exercises the full freeze->import->fit path
        layers, hidden, heads, inter, vocab = 2, 64, 2, 128, 1000
        batches, seq, iters, repeats, lr = [2], 16, 2, 2, 5e-3
    else:
        # the real thing: BERT-base 12L/h768/12A/i3072/V30522, f32
        # (the imported graph's dtype), classic fine-tune shape s128
        layers, hidden, heads, inter, vocab = 12, 768, 12, 3072, 30522
        batches, seq, iters, repeats, lr = [32, 16], 128, 10, 3, 2e-5
    batch_env = os.environ.get("BENCH_BERT_BATCH")
    if batch_env:
        batches = [int(batch_env)]

    ours = flax_w = None
    last_err = None
    for batch in batches:                  # OOM ladder (TPU HBM is 16 GB)
        try:
            gd = build_frozen_bert(batch, seq, layers, hidden, heads, inter,
                                   vocab)
            ours = measure_ours(gd, hidden, batch, seq, vocab, iters, lr)
            flax_w = measure_flax(batch, seq, layers, hidden, heads, inter,
                                  vocab, iters, lr)
            break
        except Exception as e:
            if "RESOURCE_EXHAUSTED" not in str(e) and "Out of memory" \
                    not in str(e):
                raise
            last_err = str(e)[:300]
            print(f"[bert-bench] batch={batch} OOM — stepping down",
                  file=sys.stderr)
            ours = flax_w = None
    if ours is None:
        raise RuntimeError(f"all batch rungs OOMed: {last_err}")

    ours_runs, flax_runs = [], []
    for i in range(repeats):
        print(f"[bert-bench] timed window {i + 1}/{repeats}",
              file=sys.stderr, flush=True)
        ours_runs.append(ours())
        flax_runs.append(flax_w())
    ours_sps = statistics.median(ours_runs)
    flax_sps = statistics.median(flax_runs)

    # device-side timing (BASELINE round-3 protocol): ours jits samediff's
    # `step` -> "jit_step"; the denominator jits `flax_step` -> distinct name
    ours_dev = flax_dev = None
    can_parse = True
    try:
        from tensorflow.tsl.profiler.protobuf import xplane_pb2  # noqa: F401
    except Exception:
        can_parse = False
    if on_tpu and can_parse:
        from device_timing import measure_device_step
        r = measure_device_step(lambda: ours(), "jit_step")
        if r:
            ours_dev = batch / r["median_s"]
        r = measure_device_step(lambda: flax_w(), "jit_flax_step")
        if r:
            flax_dev = batch / r["median_s"]
        if ours_dev and flax_dev:
            ours_sps, flax_sps = ours_dev, flax_dev

    print(json.dumps({
        "metric": "bert_base_tfimport_finetune_samples_per_sec",
        "value": round(ours_sps, 2),
        "unit": "samples/sec",
        "vs_baseline": round(ours_sps / flax_sps, 3),
        "flax_samples_per_sec": round(flax_sps, 2),
        "timing_source": "device_trace" if (on_tpu and ours_dev and flax_dev)
                         else "host_value_fetch",
        "platform": platform,
        "config": {"layers": layers, "hidden": hidden, "seq": seq,
                   "batch": batch, "dtype": "float32"},
    }))


if __name__ == "__main__":
    main()
