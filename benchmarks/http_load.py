#!/usr/bin/env python
"""HTTP front-door load generator: heavy-tailed traffic, SLO grading,
and the kill/respawn drill.

Drives the REAL wire surface (``serving/frontdoor.py``) with a seeded
open-loop load: Poisson arrivals at ``--qps`` with a heavy-tailed
request mix — mostly cheap classifies, a Pareto-tailed minority of
multi-token generations, a slice of SSE streams — because production
traffic is never uniform and the tail is what kills SLOs. Grades the
run with the SLO machinery (p50/p99 per route, goodput, shed/error
ratios via the PR-3 ``_grade``) and emits ONE JSON line
(``metric: http_serve``) the driver archives as ``SERVE_r*.json``.

Two modes:

- **in-process** (default): one worker in this process; the classify
  goodput is also measured DIRECT (in-process ``router.output``)
  interleaved A/B-style, so ``vs_direct`` is the HTTP overhead ratio —
  host-load drift divides out, which is the only host-timed series
  worth gating on.
- ``--workers N``: spawns a real ``tools/serve.py`` fleet (separate
  processes + proxy + shared store) and drives it over the proxy.
  ``--kill-drill`` additionally SIGKILLs one worker mid-load and
  asserts the acceptance properties: **zero failed requests on the
  survivors** (proxy failover), and the **respawned worker rejoins the
  same rollout stage** from the shared store.
- ``--tenants "a:2,b:1"``: the multi-tenant QoS flooding drill
  (in-process): the named weighted victim tenants run the SAME seeded
  load in two phases — alone (baseline), then alongside one flooding
  tenant at ``--flood-factor`` x its request-rate quota. Emits ONE
  JSON line (``metric: qos_drill``) the driver archives as
  ``QOS_r*.json``: per-victim goodput/p99 ratios (same-run, so host
  drift divides out), flooder shed counts, and the acceptance verdicts
  (victim goodput >= 90% of baseline, p99 within 2x, flooder shed at
  the door with Retry-After).

- ``--session-failover``: the graded exactly-once streaming drill
  (archives ``SESS_r*.json``): a 2-worker fleet under
  ``generation.step`` crash + ``generation.adopt`` faults, one worker
  SIGKILLed with every SSE stream mid-flight — 100% of streams must
  complete via survivor session adoption with gapless/duplicate-free
  ``id:`` sequences and greedy tokens byte-identical to an undisturbed
  in-process run (resume latency reported, never gated).

Every run also pins streaming correctness: for one seeded prompt the
SSE token sequence must equal the non-streamed result exactly, and the
first-token latency must beat the full-sequence latency by a real
margin (the reason per-token streaming exists).
"""
from __future__ import annotations

import argparse
import json
import os
import re
import signal
import socket
import statistics
import subprocess
import sys
import threading
import time
import urllib.error
import urllib.request

_REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
if _REPO not in sys.path:
    sys.path.insert(0, _REPO)

TYPED_CODES = (429, 503, 504)


# ------------------------------------------------------------ HTTP client
def _post(addr: str, path: str, doc: dict, timeout: float = 30.0,
          tenant: str = None, idem_key: str = None):
    headers = {"Content-Type": "application/json"}
    if tenant is not None:
        headers["X-Dl4j-Tenant"] = tenant
    if idem_key is not None:
        headers["X-Dl4j-Idempotency-Key"] = idem_key
    req = urllib.request.Request(
        addr + path, data=json.dumps(doc).encode(), headers=headers)
    with urllib.request.urlopen(req, timeout=timeout) as r:
        return r.status, json.loads(r.read())


def _get(addr: str, path: str, timeout: float = 10.0):
    with urllib.request.urlopen(addr + path, timeout=timeout) as r:
        return r.status, json.loads(r.read())


def _sse_generate(addr: str, doc: dict, timeout: float = 60.0,
                  idem_key: str = None):
    """POST a streaming generate; returns (tokens, first_token_s,
    total_s, done_payload)."""
    headers = {"Content-Type": "application/json"}
    if idem_key is not None:
        headers["X-Dl4j-Idempotency-Key"] = idem_key
    req = urllib.request.Request(
        addr + "/v1/generate",
        data=json.dumps(dict(doc, stream=True)).encode(),
        headers=headers)
    t0 = time.perf_counter()
    toks, first_at, done = [], None, None
    with urllib.request.urlopen(req, timeout=timeout) as r:
        ev = None
        for line in r:
            line = line.decode().rstrip("\n")
            if line.startswith("event: "):
                ev = line[7:]
            elif line.startswith("data: "):
                data = json.loads(line[6:])
                if ev == "token":
                    if first_at is None:
                        first_at = time.perf_counter() - t0
                    toks.append(data["token"])
                elif ev == "done":
                    done = data
                elif ev == "error":
                    raise RuntimeError(f"stream error: {data}")
    return toks, first_at, time.perf_counter() - t0, done


# ------------------------------------------------------------- load model
class _Stats:
    def __init__(self):
        self.lock = threading.Lock()
        self.lat = {"classify": [], "generate": [], "stream": []}
        self.ok = 0
        self.typed = 0
        self.failed = 0
        self.conn_retries = 0
        self.failures = []

    def add(self, route: str, dt: float, outcome: str, detail=None):
        with self.lock:
            if outcome == "ok":
                self.ok += 1
                self.lat[route].append(dt)
            elif outcome == "typed":
                self.typed += 1
            else:
                self.failed += 1
                if len(self.failures) < 16:
                    self.failures.append(detail)


def _quantile(xs, q):
    if not xs:
        return None
    xs = sorted(xs)
    i = min(len(xs) - 1, int(q * len(xs)))
    return xs[i]


def run_load(addr: str, rng, qps: float, duration_s: float,
             max_new_cap: int = 24, prompt_len: int = 7,
             stats: "_Stats" = None) -> "_Stats":
    """Open-loop seeded load against ``addr`` for ``duration_s``:
    Poisson arrivals, 70/20/10 classify/generate/stream mix, generation
    lengths Pareto-tailed (clipped at ``max_new_cap``) — the heavy tail
    that makes continuous batching and shedding earn their keep."""
    stats = stats or _Stats()
    threads = []
    t_end = time.monotonic() + duration_s

    def one(kind: str, n_new: int, seed: int, x):
        t0 = time.perf_counter()
        attempts = 0
        while True:
            attempts += 1
            try:
                if kind == "classify":
                    _post(addr, "/v1/classify",
                          {"inputs": [x], "request_key": seed})
                elif kind == "generate":
                    _post(addr, "/v1/generate",
                          {"prompt": [1 + seed % 50] * prompt_len,
                           "max_new_tokens": n_new, "request_key": seed})
                else:
                    _sse_generate(addr, {
                        "prompt": [1 + seed % 50] * prompt_len,
                        "max_new_tokens": n_new, "request_key": seed})
                stats.add(kind, time.perf_counter() - t0, "ok")
                return
            except urllib.error.HTTPError as e:
                stats.add(kind, 0.0,
                          "typed" if e.code in TYPED_CODES else "failed",
                          detail=f"{kind}: HTTP {e.code}")
                return
            except Exception as e:
                # connection-level death (a SIGKILLed worker's in-flight
                # request, a reset mid-stream): standard client behavior
                # is ONE retry — it must land on a survivor through the
                # proxy's failover, which is exactly the property the
                # drill grades. Retries are counted, never hidden.
                if attempts <= 1:
                    with stats.lock:
                        stats.conn_retries += 1
                    continue
                stats.add(kind, 0.0, "failed", detail=f"{kind}: {e!r}")
                return

    i = 0
    while time.monotonic() < t_end:
        # Poisson arrivals; the request mix and tail are drawn from the
        # SAME seeded rng, so two runs issue identical traffic
        gap = rng.expovariate(qps) if qps > 0 else 0.0
        time.sleep(min(gap, 1.0))
        u = rng.random()
        kind = ("classify" if u < 0.7 else
                "generate" if u < 0.9 else "stream")
        # Pareto tail (alpha 1.5) clipped to the cache budget
        n_new = min(max_new_cap, max(2, int(2 * rng.paretovariate(1.5))))
        # all randomness drawn HERE (one thread, one seeded rng): two
        # runs with the same seed issue identical traffic
        x = [round(rng.uniform(0, 1), 6) for _ in range(4)]
        t = threading.Thread(target=one, args=(kind, n_new, i, x),
                             daemon=True)
        t.start()
        threads.append(t)
        i += 1
    for t in threads:
        t.join(timeout=60.0)
    return stats


def check_streaming(addr: str, prompt, n_new: int) -> dict:
    """The streaming acceptance pins: byte-identical tokens and a real
    first-token win."""
    doc = {"prompt": list(prompt), "max_new_tokens": n_new}
    _, plain = _post(addr, "/v1/generate", doc)
    t0 = time.perf_counter()
    _post(addr, "/v1/generate", doc)      # timed non-stream run
    full_s = time.perf_counter() - t0
    toks, first_s, total_s, done = _sse_generate(addr, doc)
    return {
        "matches": toks == plain["tokens"] and done["tokens"] == toks,
        "n_tokens": len(toks),
        "first_token_ms": round(first_s * 1e3, 3) if first_s else None,
        "full_ms": round(full_s * 1e3, 3),
        "stream_total_ms": round(total_s * 1e3, 3),
        "first_token_speedup": (round(full_s / first_s, 3)
                                if first_s and first_s > 0 else None),
    }


# ----------------------------------------------------------- in-process AB
def run_inproc(args, rng) -> dict:
    """One in-process worker; interleaved HTTP-vs-direct classify
    windows give the drift-immune ``vs_direct`` ratio."""
    import numpy as np

    sys.path.insert(0, os.path.join(_REPO, "tools"))
    import serve as _serve

    reg, router, gen_router = _serve._build_demo(args.slots, True)
    from deeplearning4j_tpu.serving import FrontDoor
    fd = FrontDoor(router, gen_router, port=0,
                   max_inflight=args.max_inflight).start()
    addr = fd.get_address()
    try:
        stream = check_streaming(addr, [3, 1, 4, 1, 5, 9, 2], 12)
        stats = run_load(addr, rng, args.qps, args.duration_s, stats=None)
        # interleaved A/B: paired HTTP / direct windows, median of
        # per-pair ratios (bench.py's paired_window_median discipline)
        ratios = []
        x = np.asarray([[0.1, 0.2, 0.3, 0.4]], "f4")
        for pair in range(5):
            t0 = time.perf_counter()
            for i in range(16):
                _post(addr, "/v1/classify",
                      {"inputs": x.tolist(), "request_key": (pair, i)})
            http_s = time.perf_counter() - t0
            t0 = time.perf_counter()
            for i in range(16):
                router.output(x, request_key=(pair, i))
            direct_s = time.perf_counter() - t0
            if http_s > 0:
                ratios.append(direct_s / http_s)
        vs_direct = statistics.median(ratios) if ratios else None
        return _record(args, stats, stream, vs_direct=vs_direct,
                       workers=1, kill_drill=None)
    finally:
        fd.stop()
        reg.shutdown()


# ----------------------------------------------------------- QoS drill mode
def _parse_tenants(spec: str):
    """``name:weight,name:weight`` → ordered (name, weight) list."""
    out = []
    for part in spec.split(","):
        part = part.strip()
        if not part:
            continue
        name, _, w = part.partition(":")
        out.append((name.strip(), float(w) if w else 1.0))
    if not out:
        raise ValueError(f"no tenants in spec {spec!r}")
    return out


def _tenant_load(addr: str, seed: int, tenant: str, qps: float,
                 duration_s: float, stats: "_Stats"):
    """One tenant's open-loop seeded classify stream (its own rng, so
    the SAME traffic is issued in the baseline and flood phases)."""
    import random
    rng = random.Random(seed)
    threads = []
    t_end = time.monotonic() + duration_s

    def one(x, key):
        t0 = time.perf_counter()
        try:
            _post(addr, "/v1/classify",
                  {"inputs": [x], "request_key": key}, tenant=tenant)
            stats.add("classify", time.perf_counter() - t0, "ok")
        except urllib.error.HTTPError as e:
            stats.add("classify", 0.0,
                      "typed" if e.code in TYPED_CODES else "failed",
                      detail=f"{tenant}: HTTP {e.code}")
        except Exception as e:
            stats.add("classify", 0.0, "failed",
                      detail=f"{tenant}: {e!r}")

    i = 0
    while time.monotonic() < t_end:
        time.sleep(min(rng.expovariate(qps) if qps > 0 else 0.0, 1.0))
        x = [round(rng.uniform(0, 1), 6) for _ in range(4)]
        t = threading.Thread(target=one, args=(x, (tenant, i)),
                             daemon=True)
        t.start()
        threads.append(t)
        i += 1
    for t in threads:
        t.join(timeout=60.0)


def run_qos_drill(args, rng) -> dict:
    """The multi-tenant flooding drill (in-process worker): N weighted
    victim tenants at a steady per-tenant QPS, one flooding tenant at
    ``--flood-factor`` x its request-rate quota. Two phases with the
    SAME seeded victim traffic — (A) victims alone (the no-flood
    baseline), (B) victims + flooder — so each victim's goodput/p99
    ratio is a same-run interleaved comparison and host drift divides
    out. Acceptance: every victim's goodput holds >= 90% of its
    baseline and its p99 stays within 2x, while the flooder is shed
    (429 + Retry-After) at the door."""
    sys.path.insert(0, os.path.join(_REPO, "tools"))
    import serve as _serve

    from deeplearning4j_tpu.resilience import qos
    from deeplearning4j_tpu.serving import FrontDoor

    victims = _parse_tenants(args.tenants)
    flooder = args.flooder
    treg = qos.global_tenants()
    policies = {name: qos.TenantPolicy(name, weight=w)
                for name, w in victims}
    policies[flooder] = qos.TenantPolicy(
        flooder, weight=1.0, request_rate=args.flooder_quota_qps,
        request_burst=max(2.0, args.flooder_quota_qps))
    treg.configure(policies)
    reg, router, gen_router = _serve._build_demo(args.slots, False)
    fd = FrontDoor(router, gen_router, port=0,
                   max_inflight=args.max_inflight).start()
    addr = fd.get_address()
    phase_s = args.duration_s / 2

    def run_phase(phase: str, with_flood: bool):
        stats = {name: _Stats() for name, _ in victims}
        threads = [threading.Thread(
            target=_tenant_load,
            args=(addr, args.seed + 1000 * k, name, args.victim_qps,
                  phase_s, stats[name]), daemon=True)
            for k, (name, _) in enumerate(victims)]
        flood_stats = _Stats()
        if with_flood:
            threads.append(threading.Thread(
                target=_tenant_load,
                args=(addr, args.seed + 777, flooder,
                      args.flood_factor * args.flooder_quota_qps,
                      phase_s, flood_stats), daemon=True))
        for t in threads:
            t.start()
        for t in threads:
            t.join(timeout=phase_s + 120)
        return stats, flood_stats

    try:
        baseline, _ = run_phase("baseline", with_flood=False)
        flood, flood_stats = run_phase("flood", with_flood=True)
    finally:
        fd.stop()
        reg.shutdown()

    per_tenant = {}
    goodput_ratios, p99_ratios = [], []
    for name, w in victims:
        b, f = baseline[name], flood[name]
        b_good = b.ok / phase_s
        f_good = f.ok / phase_s
        b_p99 = _quantile(b.lat["classify"], 0.99)
        f_p99 = _quantile(f.lat["classify"], 0.99)
        g_ratio = (f_good / b_good) if b_good else None
        p_ratio = (f_p99 / b_p99) if b_p99 and f_p99 else None
        if g_ratio is not None:
            goodput_ratios.append(g_ratio)
        if p_ratio is not None:
            p99_ratios.append(p_ratio)
        per_tenant[name] = {
            "weight": w,
            "baseline_goodput": round(b_good, 3),
            "flood_goodput": round(f_good, 3),
            "goodput_ratio": (round(g_ratio, 4)
                              if g_ratio is not None else None),
            "baseline_p99_ms": (round(b_p99 * 1e3, 3) if b_p99 else None),
            "flood_p99_ms": (round(f_p99 * 1e3, 3) if f_p99 else None),
            "p99_ratio": (round(p_ratio, 4)
                          if p_ratio is not None else None),
            "typed": f.typed, "failed": f.failed,
        }
    victim_goodput_ratio = min(goodput_ratios) if goodput_ratios else None
    victim_p99_ratio = max(p99_ratios) if p99_ratios else None
    try:
        import jax
        platform = jax.default_backend()
    except Exception:
        platform = "unknown"
    snap = treg.snapshot()["tenants"].get(flooder, {})
    return {
        "metric": "qos_drill",
        "platform": platform,
        "value": victim_goodput_ratio,
        "unit": "victim_goodput_ratio",
        "ratio_method": "same_run_baseline_vs_flood",
        "victim_goodput_ratio": victim_goodput_ratio,
        "victim_p99_ratio": victim_p99_ratio,
        "victims": per_tenant,
        "flooder": flooder,
        "flooder_quota_qps": args.flooder_quota_qps,
        "flood_factor": args.flood_factor,
        "flooder_sent": (flood_stats.ok + flood_stats.typed
                         + flood_stats.failed),
        "flooder_ok": flood_stats.ok,
        "flooder_shed": flood_stats.typed,
        "flooder_failed": flood_stats.failed,
        "flooder_shed_counter": snap.get("shed"),
        "goodput_holds": (victim_goodput_ratio is not None
                          and victim_goodput_ratio >= 0.9),
        "p99_holds": (victim_p99_ratio is not None
                      and victim_p99_ratio <= 2.0),
        "victim_qps": args.victim_qps,
        "duration_s": args.duration_s,
        "seed": args.seed,
    }


# --------------------------------------------------------------- fleet mode
def _fleet_store(state_dir):
    from deeplearning4j_tpu.serving.shared_state import SharedStore
    return SharedStore(state_dir)


def run_fleet(args, rng) -> dict:
    """Spawn a real tools/serve.py fleet, drive it over the proxy, and
    (``--kill-drill``) SIGKILL + respawn one worker mid-load."""
    state_dir = args.state_dir or f"/tmp/dl4j-http-load-{os.getpid()}"
    proc = subprocess.Popen(
        [sys.executable, os.path.join(_REPO, "tools", "serve.py"),
         "--workers", str(args.workers), "--port", "0",
         "--state-dir", state_dir, "--slots", str(args.slots)],
        stdout=subprocess.PIPE, text=True)
    store = _fleet_store(state_dir)
    try:
        # read until the FLEET line (workers' announce lines may share
        # the stream on older serve.py builds — never drive a worker
        # directly: the drill's "survivors lose nothing" property is
        # about the proxy)
        fleet = None
        deadline = time.monotonic() + 240
        while time.monotonic() < deadline:
            line = proc.stdout.readline()
            if not line:
                raise RuntimeError("tools/serve.py exited before "
                                   "announcing the fleet")
            try:
                doc = json.loads(line)
            except ValueError:
                continue
            if isinstance(doc, dict) and "fleet" in doc:
                fleet = doc
                break
        if fleet is None:
            raise RuntimeError("fleet announce line never arrived")
        addr = fleet["address"]
        # wait until the fleet answers
        deadline = time.monotonic() + 60
        while True:
            try:
                _get(addr, "/debug/frontdoor", timeout=5.0)
                break
            except Exception:
                if time.monotonic() > deadline:
                    raise RuntimeError("fleet never answered")
                time.sleep(0.5)
        stream = check_streaming(addr, [3, 1, 4, 1, 5, 9, 2], 12)
        # canary v2 with a fast shared policy: the fleet must advance it
        # to FULL on aggregated windows while under load
        _post(addr, "/admin/rollout", {
            "lane": "scoring", "candidate": "v2",
            "policy": {"window_seconds": max(0.5, args.duration_s / 10),
                       "window_min_requests": 4, "healthy_windows": 1,
                       "canary_fraction": 0.3, "ramp_fractions": [0.6]}})
        stats = _Stats()
        load = threading.Thread(
            target=run_load,
            args=(addr, rng, args.qps, args.duration_s),
            kwargs={"stats": stats}, daemon=True)
        load.start()
        kill_drill = None
        if args.kill_drill:
            kill_drill = _kill_drill(store, addr, args)
        load.join(timeout=args.duration_s + 120)
        doc = store.read()
        lane = (doc.get("lanes") or {}).get("scoring") or {}
        ro = lane.get("rollout") or {}
        rollout = {"final_stage": ro.get("stage"),
                   "primary": lane.get("primary"),
                   "history": [
                       {k: e.get(k) for k in ("lane", "from", "to")}
                       for e in doc.get("history", [])]}
        return _record(args, stats, stream, vs_direct=None,
                       workers=args.workers, kill_drill=kill_drill,
                       rollout=rollout)
    finally:
        proc.terminate()
        try:
            proc.wait(timeout=15)
        except subprocess.TimeoutExpired:
            proc.kill()


def _kill_drill(store, addr: str, args) -> dict:
    """SIGKILL one non-leader worker mid-load; wait for the parent to
    respawn it; report the rejoin evidence. The zero-failed-on-survivors
    assertion lands in the final record (stats.failed)."""
    time.sleep(max(1.0, args.duration_s * 0.3))
    doc = store.read()
    workers = doc.get("workers") or {}
    victims = sorted(workers)[1:] or sorted(workers)  # spare the leader
    victim = victims[-1]
    old_pid = int(workers[victim]["pid"])
    stage_before = (((doc.get("lanes") or {}).get("scoring") or {})
                    .get("rollout") or {}).get("stage")
    os.kill(old_pid, signal.SIGKILL)
    killed_at = time.time()
    respawned = None
    deadline = time.monotonic() + 120
    while time.monotonic() < deadline:
        rec = (store.read().get("workers") or {}).get(victim) or {}
        if (int(rec.get("pid", old_pid)) != old_pid
                and float(rec.get("heartbeat", 0)) > killed_at):
            respawned = rec
            break
        time.sleep(0.5)
    doc = store.read()
    stage_after = (((doc.get("lanes") or {}).get("scoring") or {})
                   .get("rollout") or {}).get("stage")
    rejoined_view = None
    if respawned and respawned.get("port"):
        try:
            _, snap = _get(f"http://127.0.0.1:{respawned['port']}",
                           "/debug/frontdoor")
            sh = snap.get("shared") or {}
            rollout = ((sh.get("lanes") or {}).get("scoring")
                       or {}).get("rollout") or {}
            rejoined_view = rollout.get("stage")
        except Exception as e:
            rejoined_view = f"unreachable: {e!r}"
    return {
        "victim": victim,
        "old_pid": old_pid,
        "respawned": bool(respawned),
        "respawned_pid": int(respawned["pid"]) if respawned else None,
        "stage_at_kill": stage_before,
        "stage_after_respawn": stage_after,
        "respawned_worker_sees_stage": rejoined_view,
        # the stage the respawned worker reports must be the fleet's —
        # "rejoins the same rollout stage"
        "rejoined_same_stage": (rejoined_view == stage_after
                                if respawned else False),
    }


# ----------------------------------------------------------- fleet chaos
_STAGE_RANK = {"canary": 1, "ramp": 2, "full": 3}


def _chaos_load(addr: str, rng, qps: float, duration_s: float,
                stats: "_Stats", prompt_len: int = 7,
                max_new_cap: int = 16):
    """The fleet-chaos load: like :func:`run_load` but EVERY request
    carries a unique idempotency key and a connection-level death gets
    ONE retry **with the same key** — through the proxy's failover the
    retry lands on a survivor and the worker-side journal guarantees it
    replays rather than re-executes. The drill audits exactly that."""
    threads = []
    t_end = time.monotonic() + duration_s

    def one(kind: str, n_new: int, seed: int, x):
        key = f"fc-{seed}"
        t0 = time.perf_counter()
        attempts = 0
        while True:
            attempts += 1
            try:
                if kind == "classify":
                    _post(addr, "/v1/classify",
                          {"inputs": [x], "request_key": seed},
                          timeout=30.0, idem_key=key)
                else:
                    _post(addr, "/v1/generate",
                          {"prompt": [1 + seed % 50] * prompt_len,
                           "max_new_tokens": n_new, "request_key": seed},
                          timeout=30.0, idem_key=key)
                stats.add(kind, time.perf_counter() - t0, "ok")
                return
            except urllib.error.HTTPError as e:
                stats.add(kind, 0.0,
                          "typed" if e.code in TYPED_CODES else "failed",
                          detail=f"{kind}: HTTP {e.code}")
                return
            except Exception as e:
                if attempts <= 1:
                    with stats.lock:
                        stats.conn_retries += 1
                    continue
                stats.add(kind, 0.0, "failed", detail=f"{kind}: {e!r}")
                return

    i = 0
    while time.monotonic() < t_end:
        gap = rng.expovariate(qps) if qps > 0 else 0.0
        time.sleep(min(gap, 1.0))
        u = rng.random()
        kind = "classify" if u < 0.7 else "generate"
        n_new = min(max_new_cap, max(2, int(2 * rng.paretovariate(1.5))))
        x = [round(rng.uniform(0, 1), 6) for _ in range(4)]
        t = threading.Thread(target=one, args=(kind, n_new, i, x),
                             daemon=True)
        t.start()
        threads.append(t)
        i += 1
    for t in threads:
        t.join(timeout=90.0)


class _StageSampler:
    """Polls the shared store: the rollout stage sequence (must never
    move backward) and the leader (worker, term) sequence (terms must be
    strictly monotonic, and every history event's term non-decreasing)."""

    def __init__(self, store):
        self._store = store
        self.stages = []
        self.leaders = []
        self._stop = threading.Event()
        self._thread = threading.Thread(target=self._run, daemon=True)
        self._thread.start()

    def _run(self):
        while not self._stop.is_set():
            try:
                doc = self._store.read()
            except Exception:
                self._stop.wait(0.2)
                continue
            lane = (doc.get("lanes") or {}).get("scoring") or {}
            stage = (lane.get("rollout") or {}).get("stage")
            if stage is not None and (not self.stages
                                      or self.stages[-1] != stage):
                self.stages.append(stage)
            led = doc.get("leader") or {}
            cur = (led.get("worker"), int(led.get("term", 0)))
            if led and (not self.leaders or self.leaders[-1] != cur):
                self.leaders.append(cur)
            self._stop.wait(0.2)

    def stop(self):
        self._stop.set()
        self._thread.join(timeout=5.0)

    def stage_regressed(self) -> bool:
        ranks = [_STAGE_RANK.get(s) for s in self.stages]
        if "rolled_back" in self.stages:
            return True          # nothing in this drill should roll back
        ranks = [r for r in ranks if r is not None]
        return any(b < a for a, b in zip(ranks, ranks[1:]))

    def terms_monotonic(self) -> bool:
        """STRICTLY increasing across leadership changes: two leaders
        sharing one term (the exact fence failure this drill exists to
        catch) must fail, so ``>=`` would be wrong here. A corruption
        rebuild's ``{"worker": None}`` carry-forward record is term
        CONTINUITY (no one leads), not a transition — filtered out."""
        seq = []
        for w, t in self.leaders:
            if w is None:
                continue
            if not seq or seq[-1] != (w, t):
                seq.append((w, t))
        terms = [t for _, t in seq]
        return all(b > a for a, b in zip(terms, terms[1:]))


def run_fleet_chaos(args, rng) -> dict:
    """The graded fleet chaos drill: a 3-worker fleet under seeded load
    while the drill (1) SIGSTOPs the LEADER past the worker TTL then
    SIGCONTs it — the lease must move with a term bump, the woken
    ex-leader must demote at write time, and no stale-term write may
    land; (2) SIGKILLs a non-leader worker mid-stream — the proxy fails
    over with the idempotency key, the parent respawns it; (3) corrupts
    the store document once — it must be quarantined and rebuilt from
    the workers' mirrors; (4) injects store.read/store.write faults in
    every worker for the whole run. Graded: goodput >= 90%, ZERO
    duplicate executions (audited via the per-worker idempotency
    journals), leader terms strictly monotonic, rollout stage never
    regresses."""
    state_dir = args.state_dir or f"/tmp/dl4j-fleet-chaos-{os.getpid()}"
    env = dict(os.environ)
    # the whole run breathes injected store faults (seeded per process)
    env["DL4J_TPU_FAULTS"] = args.fleet_faults
    proc = subprocess.Popen(
        [sys.executable, os.path.join(_REPO, "tools", "serve.py"),
         "--workers", "3", "--port", "0", "--state-dir", state_dir,
         "--slots", str(args.slots)],
        stdout=subprocess.PIPE, text=True, env=env)
    store = _fleet_store(state_dir)
    sampler = None
    try:
        fleet = None
        deadline = time.monotonic() + 300
        while time.monotonic() < deadline:
            line = proc.stdout.readline()
            if not line:
                raise RuntimeError("tools/serve.py exited before "
                                   "announcing the fleet")
            try:
                doc = json.loads(line)
            except ValueError:
                continue
            if isinstance(doc, dict) and "fleet" in doc:
                fleet = doc
                break
        if fleet is None:
            raise RuntimeError("fleet announce line never arrived")
        addr = fleet["address"]
        deadline = time.monotonic() + 60
        while True:
            try:
                _get(addr, "/debug/frontdoor", timeout=5.0)
                break
            except Exception:
                if time.monotonic() > deadline:
                    raise RuntimeError("fleet never answered")
                time.sleep(0.5)
        # shared canary under load: its stage trajectory is one of the
        # graded invariants (forward-only). Retried: the workers run
        # with store faults armed, so the admin write itself may eat an
        # injected fault (500) a beat or two
        for _ in range(8):
            try:
                code, _body = _post(addr, "/admin/rollout", {
                    "lane": "scoring", "candidate": "v2",
                    "policy": {
                        "window_seconds": max(0.5, args.duration_s / 12),
                        "window_min_requests": 4, "healthy_windows": 1,
                        "canary_fraction": 0.3,
                        "ramp_fractions": [0.6]}})
                if code == 200:
                    break
            except Exception:
                pass
            time.sleep(0.5)
        sampler = _StageSampler(store)
        stats = _Stats()
        load = threading.Thread(
            target=_chaos_load,
            args=(addr, rng, args.qps, args.duration_s, stats),
            daemon=True)
        load.start()

        chaos: dict = {"corruptions": 0}

        def run_chaos():
            d = args.duration_s
            # --- SIGSTOP the leader past TTL, then SIGCONT
            time.sleep(d * 0.2)
            doc = store.read()
            leader = ((doc.get("leader") or {}).get("worker")
                      or (min(doc.get("workers") or {"w0": 0})))
            pid = int(((doc.get("workers") or {}).get(leader) or {})
                      .get("pid", 0))
            chaos["paused_leader"] = leader
            if pid:
                os.kill(pid, signal.SIGSTOP)
                time.sleep(args.pause_s)
                os.kill(pid, signal.SIGCONT)
                chaos["pause_s"] = args.pause_s
            # --- SIGKILL a non-leader worker MID-STREAM: pin several
            # long SSE generations in flight first (round-robin puts
            # some on the victim); their connection-level deaths retry
            # with the SAME idempotency key through the proxy
            time.sleep(d * 0.15)

            def one_stream(k: int):
                key = f"fcs-{k}"
                t0 = time.perf_counter()
                for attempt in (1, 2):
                    try:
                        _, _, _, done = _sse_generate(
                            addr, {"prompt": [1 + k, 2, 3],
                                   "max_new_tokens": 40,
                                   "request_key": ("fcs", k)},
                            timeout=60.0, idem_key=key)
                        if done is None:
                            # killed mid-stream: connection-close SSE
                            # framing makes a dead worker look like a
                            # clean (truncated) end — no terminal event
                            # = a connection-level death, retry by key
                            raise OSError("stream truncated (no done "
                                          "event)")
                        stats.add("stream",
                                  time.perf_counter() - t0, "ok")
                        return
                    except urllib.error.HTTPError as e:
                        stats.add("stream", 0.0,
                                  "typed" if e.code in TYPED_CODES
                                  else "failed",
                                  detail=f"stream: HTTP {e.code}")
                        return
                    except Exception as e:
                        if attempt == 1:
                            with stats.lock:
                                stats.conn_retries += 1
                            continue
                        stats.add("stream", 0.0, "failed",
                                  detail=f"stream: {e!r}")
                        return

            streamers = [threading.Thread(target=one_stream, args=(k,),
                                          daemon=True)
                         for k in range(6)]
            for t in streamers:
                t.start()
            time.sleep(0.15)         # streams are mid-flight NOW
            doc = store.read()
            leader = (doc.get("leader") or {}).get("worker")
            victims = [w for w in sorted(doc.get("workers") or {})
                       if w != leader and w != chaos.get("paused_leader")]
            victim = (victims or [w for w in sorted(
                doc.get("workers") or {}) if w != leader])[-1]
            vpid = int(doc["workers"][victim]["pid"])
            chaos["killed_worker"] = victim
            chaos["killed_pid"] = vpid
            os.kill(vpid, signal.SIGKILL)
            for t in streamers:
                t.join(timeout=60.0)
            # --- corrupt the store document once (disk fault); retry
            # the scribble until a reader actually quarantined it (an
            # in-flight atomic writer may immediately replace garbage
            # that nobody ever read)
            time.sleep(d * 0.2)
            state_file = os.path.join(state_dir, "state.json")
            for _ in range(4):
                try:
                    with open(state_file, "w") as f:
                        f.write('{"rev": "garbage", "workers": [')
                except OSError:
                    break
                time.sleep(1.0)
                quarantined = [fn for fn in os.listdir(state_dir)
                               if fn.startswith("state.json.corrupt.")]
                if quarantined:
                    chaos["corruptions"] = len(quarantined)
                    break

        chaos_thread = threading.Thread(target=run_chaos, daemon=True)
        chaos_thread.start()
        load.join(timeout=args.duration_s + 180)
        chaos_thread.join(timeout=60)
        # settle: wait for the parent's respawn of the killed worker to
        # register (its demo deploys may still be warming when the load
        # window closes)
        deadline = time.monotonic() + 90
        while time.monotonic() < deadline:
            try:
                rec_w = ((store.read().get("workers") or {})
                         .get(chaos.get("killed_worker")) or {})
            except Exception:
                rec_w = {}
            if (rec_w.get("port")
                    and int(rec_w.get("pid", 0)) != chaos.get("killed_pid")
                    and time.time() - float(rec_w.get("heartbeat", 0))
                    <= 3.0):
                break
            time.sleep(0.5)
        sampler.stop()
        # ---------------------------------------------------- the audit
        doc = store.read()
        _killed_rec = ((doc.get("workers") or {})
                       .get(chaos.get("killed_worker")) or {})
        respawned = bool(
            _killed_rec.get("port")
            and int(_killed_rec.get("pid", 0)) != chaos.get("killed_pid"))
        duplicate_execs = 0
        demotions = 0
        replays = 0
        rebuilds = 0
        per_worker = {}
        audited_all = True
        executed_on: dict = {}       # key -> live workers that executed it
        for w, rec in sorted((doc.get("workers") or {}).items()):
            port = rec.get("port")
            if not port:
                continue
            # the workers run with store.read faults armed — a single
            # fetch can 500 on an injected blip; retry before giving
            # up, and an UNAUDITED worker fails the verdict (its
            # journal could hide the duplicate the drill exists to
            # catch — 'unreachable' must never grade green)
            fl = err = None
            for _ in range(6):
                try:
                    _, fl = _get(f"http://127.0.0.1:{port}",
                                 "/debug/fleet", timeout=10.0)
                    break
                except Exception as e:
                    err = e
                    time.sleep(0.5)
            if fl is None:
                per_worker[w] = f"unreachable: {err!r}"
                audited_all = False
                continue
            idem = fl.get("idempotency") or {}
            duplicate_execs += int(idem.get("duplicate_executions", 0))
            replays += int(idem.get("replays", 0))
            for key, e in (idem.get("entries") or {}).items():
                if int(e.get("executions", 0)) > 0:
                    executed_on.setdefault(key, set()).add(w)
            for d_ in fl.get("frontdoors") or ():
                fence = ((d_.get("shared") or {}).get("fence") or {})
                demotions += int(fence.get("demotions", 0))
                rebuilds += int(fence.get("rebuilds", 0))
            per_worker[w] = {
                "journal_size": idem.get("size"),
                "duplicate_executions": idem.get(
                    "duplicate_executions"),
                "replays": idem.get("replays"),
            }
        # cross-worker half of the audit: one key executed in TWO live
        # journals is a duplicate the per-worker counts cannot see (the
        # killed worker's pre-death execution died with its journal and
        # is correctly not counted — nothing it charged survives)
        cross_dups = sum(len(ws) - 1 for ws in executed_on.values()
                         if len(ws) > 1)
        duplicate_execs += cross_dups
        history = doc.get("history") or []
        hist_terms = [e.get("term") for e in history
                      if e.get("term") is not None]
        terms_monotonic = (
            sampler.terms_monotonic()
            and all(b >= a for a, b in zip(hist_terms, hist_terms[1:])))
        stage_regressed = sampler.stage_regressed()
        total = stats.ok + stats.typed + stats.failed
        goodput_ratio = (stats.ok / total) if total else None
        all_lat = [v for xs in stats.lat.values() for v in xs]
        try:
            import jax
            platform = jax.default_backend()
        except Exception:
            platform = "unknown"
        lane = (doc.get("lanes") or {}).get("scoring") or {}
        rec = {
            "metric": "fleet_chaos",
            "platform": platform,
            "value": goodput_ratio,
            "unit": "goodput_ratio",
            "goodput_ratio": (round(goodput_ratio, 4)
                              if goodput_ratio is not None else None),
            "requests": total,
            "ok": stats.ok,
            "typed": stats.typed,
            "failed": stats.failed,
            "conn_retries": stats.conn_retries,
            "failures": stats.failures,
            "p50_ms": (round(_quantile(all_lat, 0.5) * 1e3, 3)
                       if all_lat else None),
            "p99_ms": (round(_quantile(all_lat, 0.99) * 1e3, 3)
                       if all_lat else None),
            "duplicate_executions": duplicate_execs,
            "cross_worker_duplicates": cross_dups,
            "double_charges": duplicate_execs,
            "idempotent_replays": replays,
            "terms_monotonic": terms_monotonic,
            "leader_sequence": sampler.leaders,
            "history_terms": hist_terms,
            "demotions": demotions,
            "stage_regressed": stage_regressed,
            "stage_sequence": sampler.stages,
            "final_stage": (lane.get("rollout") or {}).get("stage"),
            "final_primary": lane.get("primary"),
            "corruptions": chaos.get("corruptions", 0),
            "rebuilds": rebuilds,
            "proxy": doc.get("proxy"),
            "paused_leader": chaos.get("paused_leader"),
            "pause_s": chaos.get("pause_s"),
            "killed_worker": chaos.get("killed_worker"),
            "respawned": respawned,
            "per_worker": per_worker,
            "fleet_faults": args.fleet_faults,
            "workers": 3,
            "qps": args.qps,
            "duration_s": args.duration_s,
            "seed": args.seed,
        }
        rec["audited_all_workers"] = audited_all
        rec["ok_verdict"] = bool(
            goodput_ratio is not None and goodput_ratio >= 0.90
            and duplicate_execs == 0 and audited_all
            and terms_monotonic and not stage_regressed
            and chaos.get("corruptions", 0) >= 1
            and demotions >= 1 and respawned)
        return rec
    finally:
        if sampler is not None:
            sampler.stop()
        proc.terminate()
        try:
            proc.wait(timeout=15)
        except subprocess.TimeoutExpired:
            proc.kill()


def run_fleet_obs(args, rng) -> dict:
    """The graded fleet observability drill (archives OBSFLEET_r*.json):
    a 2-worker fleet behind the splice proxy with the fleet admin plane
    up.  Phase 1 issues classify requests carrying caller-supplied
    ``X-Dl4j-Trace-Id`` headers and checks the SAME id comes back on
    every response, and that the proxy's recent ``proxy_request`` spans
    carry a sent id (one trace id across proxy and worker).  Phase 2
    times ``/metrics/fleet`` scrapes (scrape p99, reported never gated)
    and checks every live worker appears as a ``worker="..."`` label
    (federation completeness).  Phase 3 SIGKILLs one worker: traced
    idempotent requests must keep echoing their ids through the
    failover replay, and ``/metrics/fleet`` must keep answering 200
    with partial data — never a 500 because one worker died.  Graded:
    trace coverage >= 0.95, federation completeness == 1.0, the
    single-trace check, and the partial scrape staying 200."""
    state_dir = args.state_dir or f"/tmp/dl4j-fleet-obs-{os.getpid()}"
    env = dict(os.environ)
    env.pop("DL4J_TPU_FLEET_OBS", None)      # the drill grades the ON path
    proc = subprocess.Popen(
        [sys.executable, os.path.join(_REPO, "tools", "serve.py"),
         "--workers", "2", "--port", "0", "--state-dir", state_dir,
         "--slots", str(args.slots), "--no-respawn"],
        stdout=subprocess.PIPE, text=True, env=env)
    store = _fleet_store(state_dir)
    try:
        fleet = None
        deadline = time.monotonic() + 300
        while time.monotonic() < deadline:
            line = proc.stdout.readline()
            if not line:
                raise RuntimeError("tools/serve.py exited before "
                                   "announcing the fleet")
            try:
                doc = json.loads(line)
            except ValueError:
                continue
            if isinstance(doc, dict) and "fleet" in doc:
                fleet = doc
                break
        if fleet is None:
            raise RuntimeError("fleet announce line never arrived")
        addr = fleet["address"]
        admin = fleet.get("admin_address")
        if not admin:
            raise RuntimeError("fleet announce carried no admin_address "
                               "(is DL4J_TPU_FLEET_OBS off?)")
        deadline = time.monotonic() + 60
        while True:
            try:
                _get(addr, "/debug/frontdoor", timeout=5.0)
                break
            except Exception:
                if time.monotonic() > deadline:
                    raise RuntimeError("fleet never answered")
                time.sleep(0.5)

        sent_ids: set = set()
        echoed = [0]
        attempted = [0]

        def traced_post(i: int, idem_key: str = None) -> bool:
            """One classify through the proxy with a caller-supplied
            trace id; True iff the response (ANY status — typed errors
            must carry the header too) echoed the SAME id back."""
            tid = f"{0xA0000000 + i:016x}"
            sent_ids.add(tid)
            attempted[0] += 1
            headers = {"Content-Type": "application/json",
                       "X-Dl4j-Trace-Id": tid}
            if idem_key is not None:
                headers["X-Dl4j-Idempotency-Key"] = idem_key
            req = urllib.request.Request(
                addr + "/v1/classify",
                data=json.dumps({
                    "inputs": [[round(rng.uniform(0, 1), 6)
                                for _ in range(4)]],
                    "request_key": i}).encode(),
                headers=headers)
            for attempt in (1, 2):
                try:
                    with urllib.request.urlopen(req, timeout=30.0) as r:
                        r.read()
                        got = r.headers.get("X-Dl4j-Trace-Id")
                    break
                except urllib.error.HTTPError as e:
                    got = e.headers.get("X-Dl4j-Trace-Id")
                    e.read()
                    break
                except Exception:
                    # connection-level death (the SIGKILLed worker):
                    # one retry — the replay must ride the proxy's
                    # failover AND still echo the id
                    if attempt == 2:
                        return False
            ok = got == tid
            if ok:
                echoed[0] += 1
            return ok

        # ---- phase 1: traced steady load + timed federation scrapes
        for i in range(args.obs_requests):
            traced_post(i)
        live = sorted(w for w, r in (store.read().get("workers")
                                     or {}).items()
                      if r.get("port")
                      and time.time() - float(r.get("heartbeat", 0))
                      <= 3.0)
        scrape_s = []
        completeness = 0.0
        label_re = re.compile(r'worker="([^"]+)"')
        for _ in range(max(8, args.obs_scrapes)):
            t0 = time.perf_counter()
            with urllib.request.urlopen(admin + "/metrics/fleet",
                                        timeout=10.0) as r:
                text = r.read().decode()
            scrape_s.append(time.perf_counter() - t0)
            seen = set(label_re.findall(text))
            if live:
                completeness = max(
                    completeness,
                    len([w for w in live if w in seen]) / len(live))
            time.sleep(0.05)
        # spans land in the ring on exit, AFTER the response bytes —
        # give the proxy a beat before reading its recent spans
        time.sleep(0.3)
        single_trace_ok = False
        try:
            _, dbg = _get(admin, "/debug/proxy", timeout=10.0)
            for sp in dbg.get("recent_proxy_spans") or ():
                if (sp.get("trace_id") in sent_ids
                        and (sp.get("attrs") or {}).get("worker")):
                    single_trace_ok = True
                    break
        except Exception:
            pass

        # ---- phase 3: SIGKILL one worker; traced replays + partial scrape
        doc = store.read()
        leader = (doc.get("leader") or {}).get("worker")
        victims = [w for w in sorted(doc.get("workers") or {})
                   if w != leader] or sorted(doc.get("workers") or {})
        victim = victims[-1]
        vpid = int(doc["workers"][victim]["pid"])
        survivors = [w for w in live if w != victim]
        os.kill(vpid, signal.SIGKILL)
        partial_codes = []
        scrape_errors_seen = False
        survivor_always = True
        t_end = time.monotonic() + 3.0
        while time.monotonic() < t_end:
            try:
                with urllib.request.urlopen(admin + "/metrics/fleet",
                                            timeout=10.0) as r:
                    text = r.read().decode()
                    partial_codes.append(r.status)
            except urllib.error.HTTPError as e:
                partial_codes.append(e.code)
                e.read()
                text = ""
            if "dl4j_fleet_scrape_errors_total" in text:
                scrape_errors_seen = True
            seen = set(label_re.findall(text))
            if survivors and not all(w in seen for w in survivors):
                survivor_always = False
            time.sleep(0.2)
        for i in range(args.obs_requests, args.obs_requests + 10):
            traced_post(i, idem_key=f"obs-{i}")
        partial_scrape_ok = bool(
            partial_codes and all(c == 200 for c in partial_codes)
            and survivor_always)
        trace_coverage = (echoed[0] / attempted[0]) if attempted[0] else 0.0
        try:
            import jax
            platform = jax.default_backend()
        except Exception:
            platform = "unknown"
        rec = {
            "metric": "obsfleet_drill",
            "platform": platform,
            "value": round(trace_coverage, 4),
            "unit": "trace_coverage",
            "trace_coverage": round(trace_coverage, 4),
            "federation_completeness": round(completeness, 4),
            "scrape_p50_ms": (round(_quantile(scrape_s, 0.5) * 1e3, 3)
                              if scrape_s else None),
            "scrape_p99_ms": (round(_quantile(scrape_s, 0.99) * 1e3, 3)
                              if scrape_s else None),
            "single_trace_ok": single_trace_ok,
            "partial_scrape_ok": partial_scrape_ok,
            "partial_scrape_codes": partial_codes,
            "scrape_errors_seen": scrape_errors_seen,
            "traced_requests": attempted[0],
            "echoed": echoed[0],
            "live_workers": live,
            "killed_worker": victim,
            "workers": 2,
            "seed": args.seed,
        }
        rec["ok_verdict"] = bool(
            trace_coverage >= 0.95 and completeness == 1.0
            and partial_scrape_ok and single_trace_ok)
        return rec
    finally:
        proc.terminate()
        try:
            proc.wait(timeout=15)
        except subprocess.TimeoutExpired:
            proc.kill()


def run_trace_intel(args, rng) -> dict:
    """The graded trace-intelligence drill (archives TRACEQ_r*.json):
    a 2-worker fleet behind the splice proxy, trace store on with head
    sampling at 0.1 and the tail rule at p90.  Phase 1 sends boring
    classify traffic (the head-sample volume bound) and short generates
    (warming the per-endpoint tail windows).  Phase 2 sends requests
    that MUST be retained: bad-input 400s and tiny-deadline 504s (error
    rule) under caller-supplied trace ids, then long generates that
    overshoot the warmed p90 (latency-tail rule).  Each expected id is
    then assembled through the proxy admin's ``/debug/trace/<id>`` and
    must stitch proxy + worker spans into one waterfall (retention
    coverage and assembly completeness, both gated).  Phase 3 SIGKILLs
    one worker: fresh error requests ride the failover and must still
    retain + assemble from the survivor, old ids must answer 200
    (partial) or 404 — never a 5xx — and the boring head-sampled volume
    must stay bounded.  Assembly latency p99 is reported, never gated
    (host weather)."""
    state_dir = args.state_dir or f"/tmp/dl4j-trace-intel-{os.getpid()}"
    env = dict(os.environ,
               DL4J_TPU_TRACE_SAMPLE="0.1", DL4J_TPU_TRACE_TAIL_Q="0.9")
    env.pop("DL4J_TPU_FLEET_OBS", None)     # the drill grades the ON path
    env.pop("DL4J_TPU_TRACE_STORE", None)
    proc = subprocess.Popen(
        [sys.executable, os.path.join(_REPO, "tools", "serve.py"),
         "--workers", "2", "--port", "0", "--state-dir", state_dir,
         "--slots", str(args.slots), "--no-respawn"],
        stdout=subprocess.PIPE, text=True, env=env)
    store = _fleet_store(state_dir)
    try:
        fleet = None
        deadline = time.monotonic() + 300
        while time.monotonic() < deadline:
            line = proc.stdout.readline()
            if not line:
                raise RuntimeError("tools/serve.py exited before "
                                   "announcing the fleet")
            try:
                doc = json.loads(line)
            except ValueError:
                continue
            if isinstance(doc, dict) and "fleet" in doc:
                fleet = doc
                break
        if fleet is None:
            raise RuntimeError("fleet announce line never arrived")
        addr = fleet["address"]
        admin = fleet.get("admin_address")
        if not admin:
            raise RuntimeError("fleet announce carried no admin_address "
                               "(is DL4J_TPU_FLEET_OBS off?)")
        deadline = time.monotonic() + 60
        while True:
            try:
                _get(addr, "/debug/frontdoor", timeout=5.0)
                break
            except Exception:
                if time.monotonic() > deadline:
                    raise RuntimeError("fleet never answered")
                time.sleep(0.5)

        def traced(path: str, doc: dict, tid: str, idem_key=None):
            """POST with a caller-supplied trace id; returns the HTTP
            status (connection death retries once — the failover path
            must still produce a retained trace)."""
            headers = {"Content-Type": "application/json",
                       "X-Dl4j-Trace-Id": tid}
            if idem_key is not None:
                headers["X-Dl4j-Idempotency-Key"] = idem_key
            req = urllib.request.Request(
                addr + path, data=json.dumps(doc).encode(),
                headers=headers)
            for attempt in (1, 2):
                try:
                    with urllib.request.urlopen(req, timeout=60.0) as r:
                        r.read()
                        return r.status
                except urllib.error.HTTPError as e:
                    e.read()
                    return e.code
                except Exception:
                    if attempt == 2:
                        return None
            return None

        assemble_s = []

        def assemble(tid: str):
            """GET the assembled waterfall through the proxy admin;
            returns (status, doc-or-None), timing every call."""
            t0 = time.perf_counter()
            try:
                code, doc = _get(admin, f"/debug/trace/{tid}",
                                 timeout=10.0)
            except urllib.error.HTTPError as e:
                code, doc = e.code, None
                e.read()
            assemble_s.append(time.perf_counter() - t0)
            return code, doc

        def stitched(doc) -> bool:
            """Does the assembled doc carry the proxy hop AND a serving
            worker's spans under one trace?"""
            if not doc:
                return False
            names = {s.get("name") for s in doc.get("waterfall") or ()}
            workers = {s.get("worker") for s in doc.get("waterfall") or ()}
            return ("proxy_request" in names and "http_request" in names
                    and len(workers) >= 2)

        # ---- phase 1: boring traffic (head bound) + tail-window warmup
        boring_ids = [f"{0xC0000000 + i:016x}" for i in range(40)]
        for i, tid in enumerate(boring_ids):
            traced("/v1/classify", {
                "inputs": [[round(rng.uniform(0, 1), 6)
                            for _ in range(4)]],
                "request_key": i}, tid)
        for i in range(40):          # short generates warm BOTH workers'
            traced("/v1/generate",   # /v1/generate tail windows past the
                   {"prompt": [1 + i % 50, 2, 3],   # 16-sample minimum
                    "max_new_tokens": 2, "request_key": 1000 + i},
                   f"{0xD0000000 + i:016x}")

        # ---- phase 2: requests the retention rules MUST keep
        error_ids = []
        for i in range(6):           # in-span 400s: bad input
            tid = f"{0xA0000000 + i:016x}"
            error_ids.append(tid)
            traced("/v1/classify", {"oops": 1, "request_key": 2000 + i},
                   tid)
        for i in range(6, 12):       # in-span 504s: unmeetable deadline
            tid = f"{0xA0000000 + i:016x}"
            error_ids.append(tid)
            traced("/v1/classify", {
                "inputs": [[0.1, 0.2, 0.3, 0.4]],
                "deadline_ms": 0.001, "request_key": 2000 + i}, tid)
        tail_ids = []
        for i in range(4):           # long generates overshoot the p90
            tid = f"{0xB0000000 + i:016x}"
            tail_ids.append(tid)
            traced("/v1/generate",
                   {"prompt": [1 + i, 2, 3], "max_new_tokens": 16,
                    "request_key": 3000 + i}, tid)
        time.sleep(0.3)              # spans land after response bytes

        # ---- retention + assembly over every expected id
        expected = error_ids + tail_ids
        retained_ok = assembled_ok = 0
        for tid in expected:
            code, doc = assemble(tid)
            if code == 200 and doc:
                retained_ok += 1
                if stitched(doc):
                    assembled_ok += 1
        retention_coverage = retained_ok / len(expected)
        assembly_completeness = (assembled_ok / retained_ok
                                 if retained_ok else 0.0)
        chrome_ok = False
        try:
            code, cdoc = _get(
                admin, f"/debug/trace/{expected[0]}?format=chrome",
                timeout=10.0)
            events = (cdoc.get("traceEvents")
                      if isinstance(cdoc, dict) else cdoc)
            chrome_ok = code == 200 and bool(events)
        except Exception:
            pass
        reasons_seen = set()
        try:
            code, rec_doc = _get(admin, "/debug/trace/recent?limit=200",
                                 timeout=10.0)
            for t in rec_doc.get("traces") or ():
                reasons_seen.add(t.get("reason"))
        except Exception:
            pass

        # ---- phase 3: SIGKILL one worker; retention must survive
        doc = store.read()
        leader = (doc.get("leader") or {}).get("worker")
        live = sorted(w for w, r in (doc.get("workers") or {}).items()
                      if r.get("port")
                      and time.time() - float(r.get("heartbeat", 0))
                      <= 3.0)
        victims = [w for w in sorted(doc.get("workers") or {})
                   if w != leader] or sorted(doc.get("workers") or {})
        victim = victims[-1]
        vpid = int(doc["workers"][victim]["pid"])
        os.kill(vpid, signal.SIGKILL)
        postkill_ids = []
        for i in range(6):           # fresh errors must ride failover
            tid = f"{0xE0000000 + i:016x}"
            postkill_ids.append(tid)
            traced("/v1/classify", {"oops": 1, "request_key": 4000 + i},
                   tid, idem_key=f"traceq-{i}")
        time.sleep(0.3)
        postkill_ok = 0
        for tid in postkill_ids:
            code, adoc = assemble(tid)
            if code == 200 and adoc:
                postkill_ok += 1
        postkill_coverage = postkill_ok / len(postkill_ids)
        # old ids: partial (200) or gone with the dead store (404) —
        # a dead worker must NEVER turn assembly into a 5xx
        partial_never_5xx = True
        for tid in expected[:6]:
            code, _doc2 = assemble(tid)
            if code >= 500:
                partial_never_5xx = False

        # ---- head-sampled volume stays bounded
        boring_retained = 0
        for tid in boring_ids:
            code, _doc3 = assemble(tid)
            if code == 200:
                boring_retained += 1
        head_fraction = boring_retained / len(boring_ids)
        head_bounded = head_fraction <= 0.5

        try:
            import jax
            platform = jax.default_backend()
        except Exception:
            platform = "unknown"
        rec = {
            "metric": "traceq_drill",
            "platform": platform,
            "value": round(retention_coverage, 4),
            "unit": "retention_coverage",
            "retention_coverage": round(retention_coverage, 4),
            "assembly_completeness": round(assembly_completeness, 4),
            "assembly_p50_ms": (round(_quantile(assemble_s, 0.5) * 1e3, 3)
                                if assemble_s else None),
            "assembly_p99_ms": (round(_quantile(assemble_s, 0.99) * 1e3, 3)
                                if assemble_s else None),
            "postkill_coverage": round(postkill_coverage, 4),
            "partial_never_5xx": partial_never_5xx,
            "chrome_export_ok": chrome_ok,
            "reasons_seen": sorted(r for r in reasons_seen if r),
            "head_sample_fraction": round(head_fraction, 4),
            "head_bounded": head_bounded,
            "error_requests": len(error_ids),
            "tail_requests": len(tail_ids),
            "postkill_requests": len(postkill_ids),
            "live_workers": live,
            "killed_worker": victim,
            "workers": 2,
            "seed": args.seed,
        }
        rec["ok_verdict"] = bool(
            retention_coverage == 1.0 and assembly_completeness == 1.0
            and postkill_coverage == 1.0 and partial_never_5xx
            and head_bounded and chrome_ok
            and {"error", "latency_tail"} <= reasons_seen)
        return rec
    finally:
        proc.terminate()
        try:
            proc.wait(timeout=15)
        except subprocess.TimeoutExpired:
            proc.kill()


def run_watchtower(args, rng) -> dict:
    """The graded watchtower drill (archives WATCH_r*.json): a 2-worker
    fleet with the detector windows drill-scaled (fast 3 s / slow 10 s,
    hold 0.5 s, clear 3 s).  Phase 1 sends clean classify traffic long
    enough to warm every detector baseline and asserts ZERO firing
    alerts and zero alert-opened incidents (the false-positive gate).
    Phase 2 injects a mid-run regression — a sustained burst of
    unmeetable-deadline 504s — and polls ``/debug/alerts`` until the
    error-burn page fires (detection latency, gated against the
    ``--detect-budget-s`` window); the firing page must close the loop
    into EXACTLY ONE ``alert:``-reason incident (two detectors or two
    workers paging inside the cooldown coalesce) with the offending
    retained traces pinned as evidence.  Phase 3 stops the burst and
    polls until the alert walks firing → resolved (flap damping exits
    cleanly after recovery)."""
    state_dir = args.state_dir or f"/tmp/dl4j-watchtower-{os.getpid()}"
    pm_dir = os.path.join(state_dir, "postmortem")
    env = dict(os.environ,
               DL4J_TPU_POSTMORTEM_DIR=pm_dir,
               DL4J_TPU_WATCHTOWER_INTERVAL_S="0.2",
               DL4J_TPU_TIMESERIES_INTERVAL_S="0.2",
               DL4J_TPU_WATCHTOWER_FAST_S="3.0",
               DL4J_TPU_WATCHTOWER_SLOW_S="10.0",
               DL4J_TPU_WATCHTOWER_HOLD_S="0.5",
               DL4J_TPU_WATCHTOWER_CLEAR_S="3.0",
               DL4J_TPU_WATCHTOWER_COOLDOWN_S="120.0",
               DL4J_TPU_FLEET_HEALTH_INTERVAL_S="0.5")
    env.pop("DL4J_TPU_WATCHTOWER", None)    # the drill grades the ON path
    env.pop("DL4J_TPU_FLEET_OBS", None)
    env.pop("DL4J_TPU_TRACE_STORE", None)
    proc = subprocess.Popen(
        [sys.executable, os.path.join(_REPO, "tools", "serve.py"),
         "--workers", "2", "--port", "0", "--state-dir", state_dir,
         "--slots", str(args.slots), "--no-respawn"],
        stdout=subprocess.PIPE, text=True, env=env)
    try:
        fleet = None
        deadline = time.monotonic() + 300
        while time.monotonic() < deadline:
            line = proc.stdout.readline()
            if not line:
                raise RuntimeError("tools/serve.py exited before "
                                   "announcing the fleet")
            try:
                doc = json.loads(line)
            except ValueError:
                continue
            if isinstance(doc, dict) and "fleet" in doc:
                fleet = doc
                break
        if fleet is None:
            raise RuntimeError("fleet announce line never arrived")
        addr = fleet["address"]
        admin = fleet.get("admin_address")
        if not admin:
            raise RuntimeError("fleet announce carried no admin_address "
                               "(is DL4J_TPU_FLEET_OBS off?)")
        deadline = time.monotonic() + 60
        while True:
            try:
                _get(addr, "/debug/frontdoor", timeout=5.0)
                break
            except Exception:
                if time.monotonic() > deadline:
                    raise RuntimeError("fleet never answered")
                time.sleep(0.5)

        def classify(i: int, bad_deadline: bool = False):
            doc = {"inputs": [[round(rng.uniform(0, 1), 6)
                               for _ in range(4)]],
                   "request_key": i}
            if bad_deadline:
                doc["deadline_ms"] = 0.001      # unmeetable: in-span 504
            req = urllib.request.Request(
                addr + "/v1/classify", data=json.dumps(doc).encode(),
                headers={"Content-Type": "application/json"})
            try:
                with urllib.request.urlopen(req, timeout=30.0) as r:
                    r.read()
                    return r.status
            except urllib.error.HTTPError as e:
                e.read()
                return e.code
            except Exception:
                return None

        def alerts_view():
            """The fleet alert rollup through the proxy admin (never a
            500); polling a worker's own /debug/alerts through the
            splice drives its beat too."""
            try:
                _get(addr, "/debug/alerts", timeout=5.0)     # beat a worker
                code, doc = _get(admin, "/debug/alerts", timeout=5.0)
                return doc if code == 200 else {}
            except Exception:
                return {}

        def firing_rules(view: dict):
            rules = set()
            for a in (view.get("watchtower") or {}).get("firing") or ():
                rules.add(a.get("rule"))
            for _wid, rec in (view.get("workers") or {}).items():
                for a in rec.get("firing") or ():
                    rules.add(a.get("rule"))
            for a in (view.get("fleet") or {}).get("firing") or ():
                rules.add(a.get("rule"))
            return rules - {None}

        def alert_incidents(view: dict):
            return [i for i in view.get("incidents") or ()
                    if str(i.get("reason", "")).startswith("alert:")]

        # ---- phase 1: clean baseline — warm every detector, zero alerts
        baseline_s = 10.0
        base_false = set()
        t0 = time.monotonic()
        i = 0
        while time.monotonic() - t0 < baseline_s:
            classify(i)
            i += 1
            if i % 10 == 0:
                base_false |= firing_rules(alerts_view())
            time.sleep(0.05)
        view = alerts_view()
        base_false |= firing_rules(view)
        baseline_incidents = len(alert_incidents(view))
        fp_free = not base_false and baseline_incidents == 0

        # ---- phase 2: regression — sustained 504 burst; detect + page
        detect_budget_s = args.detect_budget_s
        burst_t0 = time.monotonic()
        detect_s = None
        fired = set()
        j = 0
        while time.monotonic() - burst_t0 < detect_budget_s:
            classify(10_000 + j, bad_deadline=True)
            j += 1
            if j % 5 == 0:
                fired = firing_rules(alerts_view())
                if "watch_http_error_burn" in fired:
                    detect_s = time.monotonic() - burst_t0
                    break
            time.sleep(0.02)
        detected = detect_s is not None

        # keep the burst alive briefly so the capture fan-out completes,
        # then grade the incident ledger: EXACTLY ONE alert incident,
        # with pinned trace evidence attached
        incidents = []
        fan_deadline = time.monotonic() + 10.0
        while time.monotonic() < fan_deadline:
            classify(20_000 + j, bad_deadline=True)
            j += 1
            incidents = alert_incidents(alerts_view())
            if incidents and len((incidents[0].get("captured") or {})) >= 2:
                break
            time.sleep(0.2)
        single_incident = len(incidents) == 1
        traces_attached = bool(incidents
                               and incidents[0].get("trace_ids"))
        captured_workers = sorted((incidents[0].get("captured") or {})
                                  if incidents else ())

        # ---- phase 3: recovery — the page must resolve, not flap
        resolved = False
        recover_t0 = time.monotonic()
        k = 0
        while time.monotonic() - recover_t0 < 30.0:
            classify(30_000 + k)
            k += 1
            if k % 5 == 0:
                view = alerts_view()
                still = firing_rules(view)
                if "watch_http_error_burn" not in still:
                    res = set()
                    for _wid, rec in (view.get("workers") or {}).items():
                        for a in rec.get("resolved") or ():
                            res.add(a.get("rule"))
                    for a in ((view.get("watchtower") or {})
                              .get("resolved") or ()):
                        res.add(a.get("rule"))
                    if "watch_http_error_burn" in res:
                        resolved = True
                        break
            time.sleep(0.05)
        final_incidents = alert_incidents(alerts_view())

        try:
            import jax
            platform = jax.default_backend()
        except Exception:
            platform = "unknown"
        rec = {
            "metric": "watch_drill",
            "platform": platform,
            "value": round(detect_s, 3) if detected else None,
            "unit": "detect_latency_s",
            "detected": detected,
            "detect_latency_s": (round(detect_s, 3) if detected
                                 else None),
            "detect_budget_s": detect_budget_s,
            "fp_free": fp_free,
            "baseline_false_rules": sorted(base_false),
            "fired_rules": sorted(fired),
            "single_incident": single_incident,
            "alert_incidents": len(final_incidents),
            "traces_attached": traces_attached,
            "trace_ids": ((final_incidents[0].get("trace_ids") or [])[:8]
                          if final_incidents else []),
            "captured_workers": captured_workers,
            "resolved": resolved,
            "baseline_requests": i,
            "burst_requests": j,
            "recovery_requests": k,
            "workers": 2,
            "seed": args.seed,
        }
        rec["ok_verdict"] = bool(detected and fp_free and single_incident
                                 and traces_attached and resolved)
        return rec
    finally:
        proc.terminate()
        try:
            proc.wait(timeout=15)
        except subprocess.TimeoutExpired:
            proc.kill()


# ----------------------------------------------------------------- record
def _record(args, stats: "_Stats", stream: dict, vs_direct, workers,
            kill_drill, rollout=None) -> dict:
    from deeplearning4j_tpu.observability.slo import _grade
    total = stats.ok + stats.typed + stats.failed
    all_lat = [v for xs in stats.lat.values() for v in xs]
    p50 = _quantile(all_lat, 0.50)
    p99 = _quantile(all_lat, 0.99)
    goodput = stats.ok / args.duration_s if args.duration_s > 0 else None
    shed_ratio = stats.typed / total if total else 0.0
    error_ratio = stats.failed / total if total else 0.0
    slo = {
        "p99": _grade(p99 or 0.0, args.p99_degraded_s, args.p99_failing_s),
        "error_ratio": _grade(error_ratio, 0.01, 0.05),
        "shed_ratio": _grade(shed_ratio, 0.2, 0.5),
    }
    try:
        import jax
        platform = jax.default_backend()
    except Exception:
        platform = "unknown"
    return {
        "metric": "http_serve",
        "platform": platform,
        "value": goodput,
        "unit": "ok_requests_per_s",
        "goodput": goodput,
        "vs_direct": vs_direct,
        "ratio_method": "paired_window_median" if vs_direct else None,
        "requests": total,
        "ok": stats.ok,
        "typed": stats.typed,
        "failed": stats.failed,
        "conn_retries": stats.conn_retries,
        "failures": stats.failures,
        "p50_ms": round(p50 * 1e3, 3) if p50 else None,
        "p99_ms": round(p99 * 1e3, 3) if p99 else None,
        "shed_ratio": round(shed_ratio, 4),
        "error_ratio": round(error_ratio, 4),
        "slo": slo,
        "stream": stream,
        "rollout": rollout,
        "kill_drill": kill_drill,
        "workers": workers,
        "qps": args.qps,
        "duration_s": args.duration_s,
        "seed": args.seed,
    }


# ------------------------------------------------- session failover drill
class _SseCollector(threading.Thread):
    """One raw-socket SSE stream against the proxy: records every
    ``id:`` line, token, and terminal event with receive timestamps —
    the audit trail for the zero-duplicate/zero-missing assertion."""

    def __init__(self, host: str, port: int, prompt, n_new: int):
        super().__init__(daemon=True)
        self.prompt, self.n_new = list(prompt), n_new
        self._addr = (host, port)
        self.ids, self.toks, self.at = [], [], []
        self.done = None
        self.error = None
        self.exc = None

    def run(self):
        try:
            body = json.dumps({"prompt": self.prompt,
                               "max_new_tokens": self.n_new,
                               "stream": True}).encode()
            s = socket.create_connection(self._addr, timeout=180)
            s.sendall(b"POST /v1/generate HTTP/1.1\r\nHost: x\r\n"
                      b"Content-Type: application/json\r\n"
                      b"Content-Length: " + str(len(body)).encode()
                      + b"\r\nConnection: close\r\n\r\n" + body)
            s.settimeout(180)
            buf, ev, cur_id = b"", None, None
            while True:
                try:
                    data = s.recv(65536)
                except OSError as e:
                    self.exc = e
                    break
                if not data:
                    break
                buf += data
                while b"\n" in buf:
                    ln, _, buf = buf.partition(b"\n")
                    ln = ln.strip()
                    if ln.startswith(b"id:"):
                        cur_id = int(ln[3:].strip())
                    elif ln.startswith(b"event:"):
                        ev = ln.split(b":", 1)[1].strip().decode()
                    elif ln.startswith(b"data:"):
                        d = json.loads(ln[5:].strip())
                        if ev == "token":
                            self.ids.append(cur_id)
                            self.toks.append(d["token"])
                            self.at.append(time.monotonic())
                        elif ev == "done":
                            self.done = d
                        elif ev == "error":
                            self.error = d
            s.close()
        except Exception as e:
            self.exc = e


def _session_baselines(prompts, n_new: int, slots: int):
    """The undisturbed greedy token sequences, computed IN-PROCESS on
    the same demo engine the fleet deploys (same config, same seed, no
    faults) — what every chaos-run stream must match byte-for-byte."""
    import jax
    import numpy as np

    from deeplearning4j_tpu.models.generation import DecodeEngine
    from deeplearning4j_tpu.models.transformer import (TransformerConfig,
                                                       TransformerLM)
    from deeplearning4j_tpu.parallel.generation import GenerationPipeline
    cfg = TransformerConfig(vocab_size=61, n_layers=2, n_heads=2,
                            d_model=32, max_len=64)
    model = TransformerLM(cfg)
    engine = DecodeEngine(model, model.init_params(jax.random.key(0)),
                          max_len=48)
    gp = GenerationPipeline(engine, slots=slots, max_new_tokens=n_new)
    try:
        return [[int(t) for t in
                 gp.generate(np.asarray(p, np.int32),
                             max_new_tokens=n_new)]
                for p in prompts]
    finally:
        gp.shutdown()


def run_session_failover(args, rng) -> dict:
    """The graded exactly-once streaming drill (archives SESS_r*.json):
    a 2-worker fleet under chaos — per-step decode latency, seeded
    ``generation.step`` crashes (in-place resume), armed
    ``generation.adopt`` faults (the adoption retry path) — then one
    worker SIGKILLed with every stream mid-flight.  Every SSE stream
    must still complete through the proxy's mid-stream failover with a
    gapless, duplicate-free ``id:`` sequence and greedy tokens
    byte-identical to the undisturbed in-process baseline.  Resume
    latency (kill → first survivor token) is reported, never gated."""
    n_streams = max(8, args.workers * 4)
    n_new = 16
    prompts = [[rng.randrange(1, 61) for _ in range(rng.randrange(4, 8))]
               for _ in range(n_streams)]
    baselines = _session_baselines(prompts, n_new, args.slots)

    state_dir = args.state_dir or f"/tmp/dl4j-sess-drill-{os.getpid()}"
    env = dict(os.environ)
    env.pop("DL4J_TPU_SESSIONS", None)       # the drill grades the ON path
    env["DL4J_TPU_SESSION_JOURNAL_STEPS"] = "1"
    env["DL4J_TPU_FAULTS"] = args.session_faults
    proc = subprocess.Popen(
        [sys.executable, os.path.join(_REPO, "tools", "serve.py"),
         "--workers", "2", "--port", "0", "--state-dir", state_dir,
         "--slots", str(max(args.slots, n_streams)), "--no-respawn"],
        stdout=subprocess.PIPE, text=True, env=env)
    store = _fleet_store(state_dir)
    try:
        fleet = None
        deadline = time.monotonic() + 300
        while time.monotonic() < deadline:
            line = proc.stdout.readline()
            if not line:
                raise RuntimeError("tools/serve.py exited before "
                                   "announcing the fleet")
            try:
                doc = json.loads(line)
            except ValueError:
                continue
            if isinstance(doc, dict) and "fleet" in doc:
                fleet = doc
                break
        if fleet is None:
            raise RuntimeError("fleet announce line never arrived")
        addr = fleet["address"]
        host, port = addr.split("//")[1].split(":")
        port = int(port)
        deadline = time.monotonic() + 60
        while True:
            try:
                _get(addr, "/debug/frontdoor", timeout=5.0)
                break
            except Exception:
                if time.monotonic() > deadline:
                    raise RuntimeError("fleet never answered")
                time.sleep(0.5)

        workers = store.read().get("workers") or {}
        victim = sorted(workers)[-1]            # spare the leader
        victim_pid = int(workers[victim]["pid"])

        streams = [_SseCollector(host, port, p, n_new) for p in prompts]
        for st in streams:
            st.start()
            time.sleep(0.05)
        deadline = time.monotonic() + 180
        while time.monotonic() < deadline:
            if all(len(st.ids) >= 2 for st in streams):
                break
            time.sleep(0.05)
        inflight_at_kill = [len(st.ids) for st in streams]
        os.kill(victim_pid, signal.SIGKILL)
        killed_at = time.monotonic()
        for st in streams:
            st.join(timeout=300)

        complete = seq_exact = match = 0
        resume_lat = []
        failures = []
        for i, (st, base) in enumerate(zip(streams, baselines)):
            gapless = st.ids == list(range(len(st.ids)))
            ok_done = st.done is not None
            ok_match = st.toks == base
            complete += ok_done
            seq_exact += gapless
            match += ok_match
            if not (gapless and ok_done and ok_match):
                failures.append({
                    "stream": i, "n": len(st.ids), "gapless": gapless,
                    "done": ok_done, "match": ok_match,
                    "error": st.error, "exc": repr(st.exc)})
            post = [t for t in st.at if t > killed_at]
            if inflight_at_kill[i] < n_new and post:
                resume_lat.append(post[0] - killed_at)
        sessions = {}
        try:
            sessions = _get(addr, "/debug/sessions", timeout=10.0)[1]
        except Exception:
            pass
        frac = complete / max(1, n_streams)
        rec = {
            "metric": "sess_failover",
            "platform": "cpu",
            "value": round(frac, 4),
            "unit": "completion_fraction",
            "sess_completion": round(frac, 4),
            "sess_seq_exact": seq_exact / max(1, n_streams),
            "sess_greedy_match": match / max(1, n_streams),
            "sess_streams": n_streams,
            "inflight_at_kill": inflight_at_kill,
            "resume_latency_ms": (round(max(resume_lat) * 1e3, 1)
                                  if resume_lat else None),
            "resume_latency_ms_all": [round(t * 1e3, 1)
                                      for t in sorted(resume_lat)],
            "resumed_streams": len(resume_lat),
            "survivor_sessions": len(sessions.get("sessions") or []),
            "survivor_worker": sessions.get("worker"),
            "killed_worker": victim,
            "failures": failures,
            "session_faults": args.session_faults,
            "workers": 2,
            "seed": args.seed,
            "audited_all_streams": len(streams) == n_streams,
            "ok_verdict": (frac == 1.0 and seq_exact == n_streams
                           and match == n_streams),
        }
        return rec
    finally:
        proc.terminate()
        try:
            proc.wait(timeout=15)
        except subprocess.TimeoutExpired:
            proc.kill()


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--qps", type=float, default=20.0)
    ap.add_argument("--duration-s", type=float, default=10.0)
    ap.add_argument("--seed", type=int, default=0)
    ap.add_argument("--workers", type=int, default=0,
                    help="0 = in-process single worker; N = real fleet "
                         "via tools/serve.py")
    ap.add_argument("--kill-drill", action="store_true",
                    help="SIGKILL one worker mid-load (needs "
                         "--workers >= 2)")
    ap.add_argument("--slots", type=int, default=4)
    ap.add_argument("--max-inflight", type=int, default=64)
    ap.add_argument("--state-dir", default=None)
    ap.add_argument("--p99-degraded-s", type=float, default=2.0)
    ap.add_argument("--p99-failing-s", type=float, default=10.0)
    ap.add_argument("--tenants", default=None,
                    help="QoS flooding drill: victim tenants as "
                         "'name:weight,name:weight' (in-process mode; "
                         "archives QOS_r*.json)")
    ap.add_argument("--flooder", default="flood",
                    help="flooding tenant name (QoS drill)")
    ap.add_argument("--flooder-quota-qps", type=float, default=4.0,
                    help="the flooder's request-rate quota; it floods "
                         "at --flood-factor x this")
    ap.add_argument("--flood-factor", type=float, default=10.0)
    ap.add_argument("--victim-qps", type=float, default=6.0,
                    help="per-victim steady request rate (QoS drill)")
    ap.add_argument("--fleet-chaos", action="store_true",
                    help="the graded 3-worker chaos drill: SIGSTOP the "
                         "leader past TTL, SIGKILL a worker mid-stream, "
                         "corrupt the store doc once, store faults "
                         "throughout; archives FLEET_r*.json")
    ap.add_argument("--pause-s", type=float, default=4.5,
                    help="fleet-chaos leader SIGSTOP duration (must "
                         "exceed the 3 s worker TTL)")
    ap.add_argument("--fleet-faults",
                    default="store.read:error:0.02,store.write:error:0.02",
                    help="DL4J_TPU_FAULTS spec injected into every "
                         "fleet-chaos worker")
    ap.add_argument("--fleet-obs", action="store_true",
                    help="the graded 2-worker observability drill: "
                         "caller-supplied trace ids end-to-end, timed "
                         "/metrics/fleet scrapes, SIGKILL one worker "
                         "and check partial federation + traced "
                         "failover replays; archives OBSFLEET_r*.json")
    ap.add_argument("--obs-requests", type=int, default=40,
                    help="traced requests in the fleet-obs drill's "
                         "steady phase")
    ap.add_argument("--obs-scrapes", type=int, default=20,
                    help="timed /metrics/fleet scrapes (fleet-obs)")
    ap.add_argument("--trace-intel", action="store_true",
                    help="the graded 2-worker trace-intelligence "
                         "drill: error/tail/head retention rules, "
                         "cross-worker waterfall assembly through the "
                         "proxy admin, SIGKILL one worker and check "
                         "survivor retention + partial assembly; "
                         "archives TRACEQ_r*.json")
    ap.add_argument("--watchtower", action="store_true",
                    help="the graded 2-worker watchtower drill: clean "
                         "baseline must stay alert-free, a mid-run 504 "
                         "burst must page the error-burn detector within "
                         "the detection budget and close the loop into "
                         "exactly one trace-attached incident, and the "
                         "alert must resolve after recovery; archives "
                         "WATCH_r*.json")
    ap.add_argument("--detect-budget-s", type=float, default=15.0,
                    help="--watchtower: seconds the burn-rate page may "
                         "take to fire after the regression starts")
    ap.add_argument("--session-failover", action="store_true",
                    help="the graded exactly-once streaming drill: a "
                         "2-worker fleet under generation.step crash + "
                         "generation.adopt faults, one worker SIGKILLed "
                         "with every SSE stream mid-flight — 100%% must "
                         "complete via survivor adoption with gapless "
                         "ids and greedy tokens byte-identical to an "
                         "undisturbed run; archives SESS_r*.json")
    ap.add_argument("--session-faults",
                    default="generation.step:latency:1.0,"
                            "generation.step:crash:0.02:2,"
                            "generation.adopt:error:0.5:2",
                    help="DL4J_TPU_FAULTS spec injected into every "
                         "--session-failover worker")
    ap.add_argument("--out", default=None)
    args = ap.parse_args(argv)
    if args.kill_drill and args.workers < 2:
        ap.error("--kill-drill needs --workers >= 2")
    import random
    rng = random.Random(args.seed)
    if args.session_failover:
        rec = run_session_failover(args, rng)
        line = json.dumps(rec)
        print(line)
        if args.out:
            with open(args.out, "w") as f:
                f.write(line + "\n")
        return 0 if rec.get("ok_verdict") else 1
    if args.watchtower:
        rec = run_watchtower(args, rng)
        line = json.dumps(rec)
        print(line)
        if args.out:
            with open(args.out, "w") as f:
                f.write(line + "\n")
        return 0 if rec.get("ok_verdict") else 1
    if args.trace_intel:
        rec = run_trace_intel(args, rng)
        line = json.dumps(rec)
        print(line)
        if args.out:
            with open(args.out, "w") as f:
                f.write(line + "\n")
        return 0 if rec.get("ok_verdict") else 1
    if args.fleet_obs:
        rec = run_fleet_obs(args, rng)
        line = json.dumps(rec)
        print(line)
        if args.out:
            with open(args.out, "w") as f:
                f.write(line + "\n")
        return 0 if rec.get("ok_verdict") else 1
    if args.fleet_chaos:
        rec = run_fleet_chaos(args, rng)
        line = json.dumps(rec)
        print(line)
        if args.out:
            with open(args.out, "w") as f:
                f.write(line + "\n")
        return 0 if rec.get("ok_verdict") else 1
    if args.tenants:
        rec = run_qos_drill(args, rng)
        line = json.dumps(rec)
        print(line)
        if args.out:
            with open(args.out, "w") as f:
                f.write(line + "\n")
        ok = (rec["goodput_holds"] and rec["p99_holds"]
              and rec["flooder_shed"] > 0
              and all(v["failed"] == 0 for v in rec["victims"].values()))
        return 0 if ok else 1
    rec = (run_fleet(args, rng) if args.workers
           else run_inproc(args, rng))
    line = json.dumps(rec)
    print(line)
    if args.out:
        with open(args.out, "w") as f:
            f.write(line + "\n")
    ok = (rec["failed"] == 0 and rec["stream"]["matches"]
          and (rec["kill_drill"] is None
               or (rec["kill_drill"]["respawned"]
                   and rec["kill_drill"]["rejoined_same_stage"])))
    return 0 if ok else 1


if __name__ == "__main__":
    sys.exit(main())
