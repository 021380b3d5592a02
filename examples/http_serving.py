"""HTTP serving front door: deploy → curl classify → streamed generate
→ canary a v2 → watch /debug/frontdoor.

The end-to-end walkthrough of the network serving tier:

1. deploy a scoring classifier (v1, v2) and a generative LM (g1) into a
   ModelRegistry (AOT-warmed: first requests never pay an XLA compile);
2. start a :class:`FrontDoor` and hit it like any HTTP client would —
   ``POST /v1/classify`` with JSON, ``POST /v1/generate`` twice: once
   plain, once with ``"stream": true`` parsing the per-token SSE events
   (and checking the streamed sequence equals the non-streamed one);
3. retry a generation under an ``X-Dl4j-Idempotency-Key`` — the retry
   replays the journaled outcome (same tokens, ``X-Dl4j-Idempotent-
   Replay: 1``) without re-executing or re-charging;
4. start a canary rollout of v2 over ``POST /admin/rollout``, drive
   traffic until the SLO-gated state machine promotes it;
5. watch ``GET /debug/frontdoor`` and ``GET /debug/fleet`` narrate the
   whole thing.

Every request here is a real socket round-trip — the same surface
``tools/serve.py --workers N`` scales across processes (see the README
"HTTP serving front door" section and ARCHITECTURE.md §18).

Run: python examples/http_serving.py
"""
import json
import os
import sys
import time
import urllib.request

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))

import numpy as np

import jax

from deeplearning4j_tpu.models.generation import DecodeEngine
from deeplearning4j_tpu.models.transformer import (TransformerConfig,
                                                   TransformerLM)
from deeplearning4j_tpu.nn.conf.configuration import NeuralNetConfiguration
from deeplearning4j_tpu.nn.conf.layers import DenseLayer, OutputLayer
from deeplearning4j_tpu.nn.multilayer import MultiLayerNetwork
from deeplearning4j_tpu.optim.updaters import Adam
from deeplearning4j_tpu.serving import (FrontDoor, ModelRegistry,
                                        ServingRouter)


def make_net(seed):
    conf = (NeuralNetConfiguration.builder()
            .seed(seed).updater(Adam(1e-2)).list()
            .layer(DenseLayer(n_in=8, n_out=16, activation="relu"))
            .layer(OutputLayer(n_in=16, n_out=3, activation="softmax",
                               loss_function="mcxent"))
            .build())
    return MultiLayerNetwork(conf).init()


def post(addr, path, doc, idem_key=None):
    headers = {"Content-Type": "application/json"}
    if idem_key is not None:
        headers["X-Dl4j-Idempotency-Key"] = idem_key
    req = urllib.request.Request(
        addr + path, data=json.dumps(doc).encode(), headers=headers)
    with urllib.request.urlopen(req, timeout=60) as r:
        return json.loads(r.read()), dict(r.headers)


def sse_generate(addr, doc):
    """Stream one generation; prints tokens as they arrive."""
    req = urllib.request.Request(
        addr + "/v1/generate",
        data=json.dumps(dict(doc, stream=True)).encode(),
        headers={"Content-Type": "application/json"})
    toks, t0, first = [], time.perf_counter(), None
    with urllib.request.urlopen(req, timeout=120) as r:
        ev = None
        for line in r:
            line = line.decode().rstrip("\n")
            if line.startswith("event: "):
                ev = line[7:]
            elif line.startswith("data: "):
                data = json.loads(line[6:])
                if ev == "token":
                    if first is None:
                        first = time.perf_counter() - t0
                    toks.append(data["token"])
                    print(f"    token[{data['index']:2d}] = "
                          f"{data['token']:3d}  "
                          f"(+{(time.perf_counter() - t0) * 1e3:6.1f} ms)")
                elif ev == "done":
                    print(f"    done: {data['n']} tokens")
    return toks, first, time.perf_counter() - t0


def main():
    rng = np.random.RandomState(0)
    x = rng.rand(128, 8).astype("f4")
    y = np.eye(3, dtype="f4")[rng.randint(0, 3, 128)]
    net_v1, net_v2 = make_net(1), make_net(1)
    for net in (net_v1, net_v2):
        net.fit(x, y)

    registry = ModelRegistry()
    print("deploying v1 + v2 (scoring, AOT warmup)...")
    registry.deploy("v1", net_v1, sample_input=x[:1], batch_limit=8)
    registry.deploy("v2", net_v2, sample_input=x[:1], batch_limit=8)
    print("deploying g1 (generative, prefill+decode warmup)...")
    cfg = TransformerConfig(vocab_size=61, n_layers=2, n_heads=2,
                            d_model=32, max_len=64)
    model = TransformerLM(cfg)
    engine = DecodeEngine(model, model.init_params(jax.random.key(0)),
                          max_len=48)
    registry.deploy_generative("g1", engine, slots=4, max_new_tokens=24)

    fd = FrontDoor(ServingRouter(registry, "v1"),
                   gen_router=ServingRouter(registry, "g1"),
                   port=0).start()
    addr = fd.get_address()
    print(f"front door listening at {addr}\n")

    # ---- 1. classify over the wire (curl-equivalent) ----------------
    print("POST /v1/classify")
    body, headers = post(addr, "/v1/classify",
                         {"inputs": x[:2].tolist()})
    print(f"  outputs[0] = {[round(v, 4) for v in body['outputs'][0]]}")
    print(f"  trace id   = {headers.get('X-Dl4j-Trace-Id')}\n")

    # ---- 2. generate: plain, then streamed --------------------------
    prompt = [3, 1, 4, 1, 5, 9, 2, 6]
    print("POST /v1/generate (plain)")
    body, _ = post(addr, "/v1/generate",
                   {"prompt": prompt, "max_new_tokens": 12})
    plain = body["tokens"]
    print(f"  tokens = {plain}\n")
    print("POST /v1/generate (stream: true — SSE per token)")
    toks, first_s, total_s = sse_generate(
        addr, {"prompt": prompt, "max_new_tokens": 12})
    print(f"  streamed == non-streamed: {toks == plain}")
    print(f"  first token {first_s * 1e3:.1f} ms vs full "
          f"{total_s * 1e3:.1f} ms\n")

    # ---- 3. idempotent retry: same key, journaled replay ------------
    print("POST /v1/generate with X-Dl4j-Idempotency-Key (then retry)")
    body, _ = post(addr, "/v1/generate",
                   {"prompt": prompt, "max_new_tokens": 8},
                   idem_key="demo-key-1")
    retry, headers = post(addr, "/v1/generate",
                          {"prompt": prompt, "max_new_tokens": 8},
                          idem_key="demo-key-1")
    print(f"  retry tokens == original: {retry['tokens'] == body['tokens']}")
    print(f"  replayed (not re-executed): "
          f"{headers.get('X-Dl4j-Idempotent-Replay') == '1'}\n")

    # ---- 4. canary v2 through the admin surface ---------------------
    print("POST /admin/rollout (canary v2, fast policy)")
    body, _ = post(addr, "/admin/rollout", {
        "candidate": "v2",
        "policy": {"start_stage": "canary", "canary_fraction": 0.5,
                   "ramp_fractions": [0.75], "window_requests": 8,
                   "healthy_windows": 1, "min_latency_count": 4,
                   "min_requests": 4, "min_shadow": 2}})
    print(f"  stage = {body['stage']}, share = {body['share']}")
    for i in range(120):
        post(addr, "/v1/classify",
             {"inputs": x[i % 64:i % 64 + 1].tolist(), "request_key": i})
        ro = fd.router.rollout
        if ro is not None and not ro.active:
            break
    ro = fd.router.rollout
    print(f"  final stage = {ro.stage}, primary = "
          f"{fd.router.primary.version}\n")

    # ---- 5. watch /debug/frontdoor + /debug/fleet -------------------
    print("GET /debug/frontdoor")
    with urllib.request.urlopen(addr + "/debug/frontdoor") as r:
        snap = json.loads(r.read())
    print(f"  mode={snap['mode']} inflight={snap['inflight']} "
          f"scoring primary={snap['scoring']['primary']} "
          f"rollout stage={snap['scoring']['rollout']['stage']}")
    print("GET /debug/fleet")
    with urllib.request.urlopen(addr + "/debug/fleet") as r:
        fleet = json.loads(r.read())
    idem = fleet["idempotency"]
    print(f"  fence={fleet['fence_enabled']} journal size={idem['size']} "
          f"replays={idem['replays']} "
          f"duplicate_executions={idem['duplicate_executions']}")
    print("\nfor N processes serving ONE version set over a shared "
          "store:\n  python tools/serve.py --workers 2 --port 8080 "
          "--state-dir /tmp/fleet\n  python benchmarks/http_load.py "
          "--workers 3 --fleet-chaos")

    fd.stop()
    registry.shutdown()


if __name__ == "__main__":
    main()
