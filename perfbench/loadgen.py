"""The load generator: a process of its own that never imports jax.

One general generator reads a traffic mix (a data file) and drives the front
door over real sockets with SSE. Everything is drawn from ``--seed`` in one
thread, before any request is sent; one asyncio loop sends and reads.

* ``serve-open``: an open loop. Arrivals and lengths are a FIXED set - the
  quantiles of the mix's distributions (exponential gaps for Poisson, gamma
  for bursts; log-normal or uniform lengths) - that the seed only shuffles,
  so every seed offers the same work in another order. A request is timed
  from the instant it was DUE, not from when it was sent. When the window
  closes the callers hang up, as in the closed loop.
* ``serve-closed``: a closed loop of ``clients`` callers, each sending its
  next request when the last completed, from one shared seeded list. When the
  window closes the callers hang up: what is in flight or queued then would
  take up to two request lifetimes to drain, in every run of every later
  check.

It prints one JSON object: the requests, each with its due and sent times,
the arrival time of every token, the tokens, and whether it failed.
"""
from __future__ import annotations

import argparse
import asyncio
import json
import sys
import time
from statistics import NormalDist

import numpy as np


# ------------------------------------------------------------ the draws
def _quantile_points(n):
    return (np.arange(n) + 0.5) / n


def lengths(spec: dict, n: int, rng) -> np.ndarray:
    """``n`` lengths: the distribution's quantiles, clipped, shuffled."""
    u = _quantile_points(n)
    if spec["dist"] == "lognormal":
        z = np.array([NormalDist().inv_cdf(float(p)) for p in u])
        x = spec["median"] * np.exp(spec["sigma"] * z)
    elif spec["dist"] == "uniform":
        x = spec["min"] + u * (spec["max"] + 1 - spec["min"])
    elif spec["dist"] == "fixed":
        x = np.full(n, spec["value"], float)
    else:
        raise ValueError(f"unknown length distribution {spec['dist']!r}")
    x = np.clip(np.floor(x), spec["min"], spec["max"]).astype(int)
    rng.shuffle(x)
    return x


def gaps(spec: dict, n: int, total_s: float, rng) -> np.ndarray:
    """``n`` gaps between arrivals that sum to ``total_s``: the quantiles of
    an exponential (``poisson``) or of a gamma with the given coefficient of
    variation, shuffled."""
    u = _quantile_points(n)
    if spec["process"] == "poisson":
        g = -np.log1p(-u)
    elif spec["process"] == "gamma":
        # gamma with shape 1/cv^2 has coefficient of variation cv; its
        # quantiles by sorting a fixed large sample (seed 0: not the run's)
        k = 1.0 / spec["cv"] ** 2
        sample = np.sort(np.random.default_rng(0).gamma(k, 1.0 / k,
                                                        size=64 * n))
        g = sample[(u * len(sample)).astype(int)]
    else:
        raise ValueError(f"unknown arrival process {spec['process']!r}")
    g = g * (total_s / g.sum())
    rng.shuffle(g)
    return g


def plan(traffic: dict, seed: int, seconds: float):
    """The requests of one run, from the seed: due time (seconds after the
    ramp starts; None in a closed loop), prompt and output budget."""
    rng = np.random.default_rng([int(seed), 23])
    total_s = traffic["ramp_s"] + seconds
    if traffic["kind"] == "serve-open":
        n = max(1, round(traffic["rate_rps"] * total_s))
        due = np.cumsum(gaps(traffic["arrivals"], n, total_s, rng))
        due = due - due[0] * 0.5
    else:
        n = int(traffic["plan_requests"])
        due = [None] * n
    p_len = lengths(traffic["prompt_len"], n, rng)
    o_len = lengths(traffic["output_len"], n, rng)
    o_len = np.minimum(o_len, traffic["max_total"] - p_len)
    vocab = int(traffic["vocab_size"])
    reqs = []
    for i in range(n):
        reqs.append({"id": i, "due": None if due[i] is None else float(due[i]),
                     "prompt": rng.integers(0, vocab, int(p_len[i])).tolist(),
                     "max_new_tokens": int(o_len[i])})
    return reqs


# ------------------------------------------------------------ the client
async def post_sse(host: str, port: int, req: dict, rec: dict):
    """One streamed ``POST /v1/generate``; fills ``rec`` as tokens arrive."""
    body = json.dumps({"prompt": req["prompt"], "stream": True,
                       "max_new_tokens": req["max_new_tokens"]}).encode()
    rec["sent"] = time.time()
    reader = writer = None
    try:
        reader, writer = await asyncio.open_connection(host, port)
        writer.write(b"POST /v1/generate HTTP/1.1\r\nHost: bench\r\n"
                     b"Content-Type: application/json\r\nConnection: close\r\n"
                     b"Content-Length: " + str(len(body)).encode()
                     + b"\r\n\r\n" + body)
        await writer.drain()
        status = await reader.readline()
        rec["status"] = int(status.split()[1])
        event = None
        while True:
            line = await reader.readline()
            if not line:
                break
            line = line.decode().rstrip("\r\n")
            if line.startswith("event: "):
                event = line[7:]
            elif line.startswith("data: ") and event == "token":
                rec["t_tokens"].append(time.time())
                rec["tokens"].append(json.loads(line[6:])["token"])
            elif line.startswith("data: ") and event == "done":
                rec["done"] = True
            elif line.startswith("data: ") and event == "error":
                rec["error"] = line[6:][:200]
    except (OSError, ValueError, IndexError, asyncio.IncompleteReadError) as e:
        rec["error"] = f"{type(e).__name__}: {e}"[:200]
    except asyncio.CancelledError:
        rec["cancelled"] = True     # the window closed: the caller hung up
        raise
    finally:
        if writer is not None:
            writer.close()
    rec["end"] = time.time()
    rec["ok"] = bool(rec.get("done") and rec.get("status") == 200
                     and len(rec["tokens"]) == req["max_new_tokens"])


def _record(req, due_abs):
    return {"id": req["id"], "due": due_abs, "sent": None, "status": None,
            "t_tokens": [], "tokens": [], "prompt_len": len(req["prompt"]),
            "max_new_tokens": req["max_new_tokens"], "ok": False,
            "cancelled": False}


async def _hang_up_at(tasks, recs, t_stop):
    """Wait for the callers until the window closes, then hang up on what
    is still in flight: draining it would take a request's lifetime, in
    every run of every later check."""
    done, pending = await asyncio.wait(
        tasks, timeout=max(0.0, t_stop - time.time()))
    for t in pending:
        t.cancel()
    await asyncio.gather(*pending, return_exceptions=True)
    for t in done:
        t.result()                  # a caller's own failure is the run's
    for rec in recs:
        rec.setdefault("end", time.time())
    return recs


async def open_loop(host, port, reqs, t0, t_stop):
    recs, tasks = [], []
    for req in reqs:
        due_abs = t0 + req["due"]
        if due_abs >= t_stop:
            break
        delay = due_abs - time.time()
        if delay > 0:
            await asyncio.sleep(delay)
        rec = _record(req, due_abs)
        recs.append(rec)
        tasks.append(asyncio.ensure_future(post_sse(host, port, req, rec)))
    return await _hang_up_at(tasks, recs, t_stop)


async def closed_loop(host, port, reqs, t0, t_stop, clients):
    recs, queue = [], iter(reqs)

    async def client():
        while time.time() < t_stop:
            req = next(queue, None)
            if req is None:
                raise RuntimeError("plan_requests is too small for this run")
            rec = _record(req, None)
            recs.append(rec)
            await post_sse(host, port, req, rec)

    delay = t0 - time.time()
    if delay > 0:
        await asyncio.sleep(delay)
    tasks = [asyncio.ensure_future(client()) for _ in range(clients)]
    return await _hang_up_at(tasks, recs, t_stop)


def main(argv=None) -> int:
    ap = argparse.ArgumentParser()
    ap.add_argument("--host", required=True)
    ap.add_argument("--port", type=int, required=True)
    ap.add_argument("--traffic", required=True, help="the mix, as JSON text")
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--t0", type=float, required=True,
                    help="epoch seconds at which the ramp starts")
    args = ap.parse_args(argv)
    traffic = json.loads(args.traffic)
    reqs = plan(traffic, args.seed, args.seconds)
    t_stop = args.t0 + traffic["ramp_s"] + args.seconds
    if traffic["kind"] == "serve-open":
        recs = asyncio.run(open_loop(args.host, args.port, reqs, args.t0,
                                     t_stop))
    else:
        recs = asyncio.run(closed_loop(args.host, args.port, reqs, args.t0,
                                       t_stop, int(traffic["clients"])))
    prompts = {r["id"]: r["prompt"] for r in reqs}
    for rec in recs:
        rec["prompt"] = prompts[rec["id"]]
    json.dump({"started_late_s": max(0.0, -min(
        (r["due"] - r["sent"] for r in recs if r["due"] and r["sent"]),
        default=0.0)), "requests": recs}, sys.stdout)
    return 0


if __name__ == "__main__":
    sys.exit(main())
