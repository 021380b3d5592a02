"""Traffic kinds ``serve-open`` and ``serve-closed``: the served path -
``DecodeEngine`` -> ``ModelRegistry.deploy_generative`` -> ``ServingRouter``
-> ``FrontDoor`` - over real sockets with SSE, driven by ``loadgen.py`` in a
process of its own.

``correct``: once the window has closed, a sample of the requests it
finished (drawn from the seed, the longest in it) goes once through the
plain reference, prompt with served tokens, and the widest and the mean gap
by which a served token's logit lies below the reference's best are held to
the cell's limits; beside it the counts that must be exact (tokens the
clients counted against ``dl4j_decode_tokens_total``, no compile in the
window, nothing shed, nothing resumed in place, breaker closed).
"""
from __future__ import annotations

import json
import os
import shutil
import subprocess
import sys
import time
import urllib.request

import numpy as np

from perfbench import harness, trace as ptrace
from perfbench.harness import say

VERSION = "bench"


# ------------------------------------------------------------ deployment
class Deployment:
    """Engine, registry, router and front door for one run; ``close`` stops
    them all and frees the page pool."""

    def __init__(self, cell, seed, kv_quant=None):
        from deeplearning4j_tpu.models.generation import DecodeEngine
        from deeplearning4j_tpu.resilience.policy import CircuitBreaker
        from deeplearning4j_tpu.serving import (FrontDoor, ModelRegistry,
                                                ServingRouter)
        tr, cfg = cell.traffic, cell.config
        self.model = cell.model.build_model(cfg)
        self.params = cell.model.make_weights(cfg, seed)
        self.engine = DecodeEngine(
            self.model, self.params, max_len=cfg["n_positions"],
            prefill_buckets=tr.get("prefill_buckets"), kv_quant=kv_quant)
        self.breaker = CircuitBreaker(f"generation.step:{VERSION}")
        self.registry = ModelRegistry()
        self.front = None
        self.dv = self.registry.deploy_generative(
            VERSION, self.engine, slots=tr["slots"],
            queue_limit=tr["queue_limit"], cache_pages=tr.get("cache_pages"),
            max_new_tokens=tr["output_len"]["max"], breaker=self.breaker,
            warmup=bool(tr["deploy_warmup"]))
        self.front = FrontDoor(None, ServingRouter(self.registry, VERSION),
                               port=0, max_inflight=tr["max_inflight"]).start()
        self.addr = self.front.get_address()

    def host_port(self):
        host, port = self.addr.split("//")[1].rsplit(":", 1)
        return host, int(port)

    def metrics_text(self) -> str:
        with urllib.request.urlopen(self.addr + "/metrics", timeout=30) as r:
            return r.read().decode()

    def close(self):
        if self.front is not None:
            self.front.stop()
            self.front = None
        self.registry.shutdown()
        self.dv = self.engine = None


def generator_command(dep, traffic, seed, seconds, t_ramp):
    """The load generator's process: the mix as data, the seed, and the
    instant its ramp starts."""
    host, port = dep.host_port()
    return [sys.executable, os.path.join(harness.HERE, "loadgen.py"),
            "--host", host, "--port", str(port), "--traffic",
            json.dumps(traffic), "--seed", str(seed), "--seconds",
            str(seconds), "--t0", repr(t_ramp)]


def metric(text: str, name: str) -> float:
    """Sum of a Prometheus series over its labels; 0 where absent."""
    total = 0.0
    for line in text.splitlines():
        if line.startswith(name) and line[len(name):len(name) + 1] in " {":
            total += float(line.rsplit(" ", 1)[1])
    return total


def warm_requests(dep, cell, seed):
    """One short streamed request through the front door for every prefill
    bucket the mix can reach: with ``deploy_warmup`` these find every
    program compiled, without it they compile them, and either way the
    sockets and handler threads have run once before the window."""
    rng = np.random.default_rng([int(seed), 7])
    lo = cell.traffic["prompt_len"]["min"]
    hi = cell.traffic["prompt_len"]["max"]
    prev = returned = 0
    for bucket in dep.engine.prefill_buckets:
        if bucket >= lo and prev < hi:
            n = min(bucket, hi)
            doc = {"prompt": rng.integers(
                0, cell.config["vocab_size"], n).tolist(),
                "max_new_tokens": 2}
            req = urllib.request.Request(
                dep.addr + "/v1/generate", data=json.dumps(doc).encode(),
                headers={"Content-Type": "application/json"})
            with urllib.request.urlopen(req, timeout=1200) as r:
                out = json.loads(r.read())
            if r.status != 200 or len(out["tokens"]) != 2:
                raise RuntimeError(f"warm request for bucket {bucket}: "
                                   f"{r.status} {out}")
            returned += len(out["tokens"])
        prev = bucket
    return returned


# ------------------------------------------------------------ correctness
def sample_finished(requests, k, seed):
    """k finished requests drawn from the seed, the longest among them."""
    done = [r for r in requests if r["ok"]]
    if not done:
        return []
    longest = max(done, key=lambda r: r["prompt_len"] + len(r["tokens"]))
    rest = [r for r in done if r is not longest]
    rng = np.random.default_rng([int(seed), 11])
    picks = rng.choice(len(rest), size=min(k - 1, len(rest)), replace=False)
    return [longest] + [rest[i] for i in sorted(picks)]


def pack(sample, seq_len):
    """(seqs, cands, mask): each row a prompt with its served tokens, padded;
    ``cands[i]`` the token served after position i; ``mask`` the positions
    whose next token was served."""
    n = len(sample)
    seqs = np.zeros((n, seq_len), np.int32)
    cands = np.zeros((n, seq_len), np.int32)
    mask = np.zeros((n, seq_len), bool)
    for row, r in enumerate(sample):
        full = list(r["prompt"]) + list(r["tokens"])
        t = len(r["prompt"])
        seqs[row, :len(full)] = full
        cands[row, :len(full) - 1] = full[1:]
        mask[row, t - 1:len(full) - 1] = True
    return seqs, cands, mask


def served_token_gaps(cell, params, sample):
    """The reference's best logit less its logit of the served token, at
    every served position of the sample: a flat array."""
    import jax.numpy as jnp
    seqs, cands, mask = pack(sample, cell.config["n_positions"])
    gaps = np.asarray(cell.reference.next_token_gaps(
        params, jnp.asarray(seqs), jnp.asarray(cands), cell.config))
    return gaps[mask]


# ------------------------------------------------------------------ run
def run(cell, seed, seconds, trace, device, t_start, hook=None):
    from deeplearning4j_tpu.observability.compile_watch import (
        global_compile_watch)
    from deeplearning4j_tpu.observability.tracing import (
        reset_global_trace_sink)
    from deeplearning4j_tpu.resilience import faults

    events = harness.CacheEvents()
    tr = dict(cell.traffic)
    tr["vocab_size"] = cell.config["vocab_size"]
    t0 = time.time()
    dep = Deployment(cell, seed)
    try:
        say(f"deployed in {time.time() - t0:.1f} s: {tr['slots']} slots, "
            f"buckets {dep.engine.prefill_buckets}, page pool "
            f"{dep.dv.gp.snapshot()['pool_bytes'] / 2**30:.2f} GiB, warmed "
            f"{dep.dv.warmed_buckets}")
        t0 = time.time()
        counted_cold = metric(dep.metrics_text(), "dl4j_decode_tokens_total")
        warm_tokens = warm_requests(dep, cell, seed)
        say(f"warm requests in {time.time() - t0:.1f} s (persistent cache "
            f"hits {events.hits}, misses {events.misses})")
        sink = reset_global_trace_sink(int(tr["span_ring"]))
        watch = global_compile_watch()
        text_quiet = dep.metrics_text()
        counted0 = metric(text_quiet, "dl4j_decode_tokens_total")
        shed0 = metric(text_quiet, "dl4j_decode_shed_total")
        errors0 = metric(text_quiet, "dl4j_decode_errors_total")
        warm_counted = counted0 - counted_cold

        t_ramp = time.time() + 1.0
        gen = subprocess.Popen(generator_command(dep, tr, seed, seconds,
                                                 t_ramp),
                               stdout=subprocess.PIPE)
        try:
            t_w0 = t_ramp + tr["ramp_s"]
            t_w1 = t_w0 + seconds
            _sleep_until(t_w0)
            traced0 = watch.total
            compiles0 = events.hits + events.misses
            text0 = dep.metrics_text()
            rec = None
            if trace:
                _sleep_until(t_w0 + 1.0)
                rec = ptrace.Recorder(harness.work_dir(cell))
                rec.start()
                _sleep_until(rec.t0 + tr["trace_s"])
                rec.stop()
            _sleep_until(t_w1)
            compiles = (watch.total - traced0) + (events.hits + events.misses
                                                  - compiles0)
            text1 = dep.metrics_text()
            spans = [s for s in sink.spans()
                     if t_w0 * 1e6 <= s.ts_us + s.dur_us <= t_w1 * 1e6]
            dropped = sink.dropped
            raw, _ = gen.communicate(timeout=300)
        finally:
            if gen.poll() is None:
                gen.kill()
                gen.wait()
        if gen.returncode != 0:
            raise RuntimeError(f"load generator exited {gen.returncode}")
        requests = json.loads(raw)["requests"]
        text2 = _quiet_metrics(dep)
        resumed = [e for e in faults.events()
                   if e["category"] == "session_resume_inplace"]
        breaker = dep.breaker.snapshot()
        peak = harness.memory_peak_bytes(cell.chips)
        params = dep.params
    finally:
        dep.close()
    del dep

    window = (t_w0, t_w1)
    _say_stalls(spans, requests, window)
    values, attempted, failed, in_window = _end_to_end(
        tr, requests, window)
    values["setup_s"] = t_w0 - t_start
    say(f"window: {attempted} requests, {failed} failed; " + ", ".join(
        f"{k} {v}" for k, v in values.items()))

    cmp = harness.Compare()
    client_tokens = sum(len(r["tokens"]) for r in requests)
    counted = metric(text2, "dl4j_decode_tokens_total") - counted0
    # exact where the system is quiet: the warm requests, before the ramp
    cmp.add("decode_tokens_total_minus_warm_request_tokens",
            warm_counted - warm_tokens, 0, exact=True)
    hung_up = sum(bool(r["cancelled"]) for r in requests)
    if hung_up:
        # callers that hung up when the window closed: the engine may have
        # made tokens that nobody read - a write to a closed socket fails
        # only the second time, so two tokens a stream and a quarter of a
        # second of steps - but never fewer than the clients read
        cmp.add("decode_tokens_total_minus_client_tokens_floor",
                max(0.0, client_tokens - counted), 0, exact=True)
        gaps_s = [b - a for r in requests
                  for a, b in zip(r["t_tokens"], r["t_tokens"][1:])]
        per_stream = 2 + 0.25 / max(harness.quantile(gaps_s, 0.5), 1e-3)
        cmp.add("decode_tokens_total_minus_client_tokens",
                counted - client_tokens, round(per_stream * hung_up))
    else:
        cmp.add("decode_tokens_total_minus_client_tokens",
                counted - client_tokens, 0, exact=True)
    cmp.add("compiles_in_window", compiles, 0, exact=True)
    cmp.add("requests_failed", sum(not r["ok"] and not r["cancelled"]
                                   for r in requests), 0, exact=True)
    # sheds are read when the window closes: a caller hanging up after it is
    # a typed shed (client_gone) by the program's design
    cmp.add("dl4j_decode_shed_total",
            metric(text1, "dl4j_decode_shed_total") - shed0, 0, exact=True)
    cmp.add("dl4j_decode_errors_total",
            metric(text2, "dl4j_decode_errors_total") - errors0, 0,
            exact=True)
    cmp.add("resumed_in_place", len(resumed), 0, exact=True)
    cmp.add("breaker_open", 0 if breaker["state"] == "closed" else 1, 0,
            exact=True)
    cmp.add("spans_dropped", dropped, 0, exact=True)
    t0 = time.time()
    sample = sample_finished(in_window, int(tr["check_requests"]), seed)
    if sample:
        gaps = served_token_gaps(cell, params, sample)
        say(f"reference: {len(sample)} requests, {gaps.size} served tokens, "
            f"longest {max(r['prompt_len'] + len(r['tokens']) for r in sample)}"
            f" positions, {time.time() - t0:.1f} s; served token is not the "
            f"reference's first at {int((gaps > 0).sum())} positions")
        cmp.add("served_logit_gap_max", float(gaps.max()),
                cell.limit("served_logit_gap_max"))
        cmp.add("served_logit_gap_mean", float(gaps.mean()),
                cell.limit("served_logit_gap_mean"))
        if hook is not None:    # perfbench/control.py reads its controls
            hook(cell, params, sample, gaps)
    else:
        cmp.add("finished_requests_to_compare", 0, 1, exact=True)
    del params

    out = {"correct": cmp.correct, "attempted": attempted, "failed": failed,
           "values": values, "memory_peak_bytes": peak}
    if trace:
        tr_data = rec.load()
        lo, hi = tr_data.span()
        live, active = _live_tokens(requests, rec.t0, rec.t1)
        ctx = {"cell": cell, "device": device, "trace": tr_data,
               "values": values, "compiles_in_window": compiles,
               "spans": spans, "requests": in_window, "window": window,
               "trace_span": (lo, hi), "live_tokens_mean": live,
               "active_mean": active,
               "tokens_counted_in_window": metric(
                   text1, "dl4j_decode_tokens_total") - metric(
                   text0, "dl4j_decode_tokens_total")}
        harness.read_layer_metrics(cell, ctx, values)
        out["busy_s"] = tr_data.busy_seconds(lo, hi)
        out["trace_window_s"] = hi - lo
        out["breakdown"] = {
            "device_ops": tr_data.top_ops(10),
            "idle_gaps": _label_gaps(tr_data, lo, hi, sink.spans())}
        shutil.rmtree(harness.work_dir(cell), ignore_errors=True)
    return out


#: a request is in the time-to-first-token population if it was due at least
#: this long before the window closed: one still without a token when the
#: callers hang up has then waited longer than this, and counts as +inf
TTFT_GUARD_S = 1.0


def _end_to_end(tr, requests, window):
    """The mix's end-to-end numbers over the window, with the latencies'
    medians and counts said on an earlier line. Callers hang up when the
    window closes, so only what arrived inside it is counted."""
    t_w0, t_w1 = window
    inf = float("inf")
    values = {}

    def broken(r):          # failed of itself, not hung up on by its caller
        return not r["ok"] and not r["cancelled"]

    if tr["kind"] == "serve-open":
        mine = [r for r in requests if t_w0 <= r["due"] < t_w1]
        ttft = [1e3 * (r["t_tokens"][0] - r["due"]) if r["t_tokens"]
                and not broken(r) else inf
                for r in mine if r["due"] < t_w1 - TTFT_GUARD_S]
        itl = []
        for r in mine:
            t = [x for x in r["t_tokens"] if x < t_w1]
            itl += [1e3 * (b - a) for a, b in zip(t, t[1:])]
            if broken(r):
                itl.append(inf)
        values["ttft_p95_ms"] = harness.quantile(ttft, 0.95)
        values["itl_p95_ms"] = harness.quantile(itl, 0.95)
        say(f"ttft: median {harness.quantile(ttft, 0.5)} ms over "
            f"{len(ttft)} requests; itl: median "
            f"{harness.quantile(itl, 0.5)} ms over {len(itl)} gaps")
    else:
        mine = [r for r in requests if r["sent"] is not None
                and r["sent"] < t_w1 and r["end"] > t_w0]
        delivered = sum(1 for r in requests for t in r["t_tokens"]
                        if t_w0 <= t < t_w1)
        values["serve_tok_s"] = delivered / (t_w1 - t_w0)
        say(f"delivered {delivered} tokens in the window")
    failed = sum(broken(r) for r in mine)
    finished = [r for r in mine if r["ok"] and r["end"] <= t_w1]
    say(f"{len(finished)} of the window's {len(mine)} requests finished "
        f"inside it")
    return values, len(mine), failed, finished


def _live_tokens(requests, lo, hi, every=0.05):
    """Mean, over the traced part of the window, of the tokens whose keys
    and values the active slots hold, and of the active slots: from the
    clients' own records (a request is in a slot from its first token to its
    last; it holds its prompt and the tokens it has been sent)."""
    ts = np.arange(lo, hi, every)
    live = np.zeros(len(ts))
    active = np.zeros(len(ts))
    for r in requests:
        t = np.asarray(r["t_tokens"])
        if t.size == 0:
            continue
        inside = (ts >= t[0]) & (ts <= t[-1])
        active += inside
        live += inside * (r["prompt_len"] + np.searchsorted(t, ts))
    return (float(live.mean()), float(active.mean())) if len(ts) else (None,
                                                                       None)


def _label_gaps(tr_data, lo, hi, spans, k=10):
    """The longest idle gaps of the chip, each labelled by the program span
    that covers its middle, if the two clocks could be set against each
    other from the benchmark's marks; ``unattributed`` otherwise."""
    out = []
    off = tr_data.clock_offset
    loop = [s for s in spans if s.name in ("decode_step", "prefill")]
    for s, e in tr_data.idle_gaps(lo, hi, k):
        label = "unattributed"
        if off is not None:
            mid_us = ((s + e) / 2 + off) * 1e6
            inside = [x for x in loop
                      if x.ts_us <= mid_us <= x.ts_us + x.dur_us]
            if inside:
                x = min(inside, key=lambda x: x.dur_us)
                label = x.name + (":insert" if (x.attrs or {}).get(
                    "phase") == "insert" else "")
            else:
                label = "between spans"
        out.append([label, e - s])
    return out


def _say_stalls(spans, requests, window):
    """Where a window's time went at the coarsest level, on every run: how
    many decode steps, their longest, the longest pause between two, and the
    longest silence at the clients - so that a run that reads far off says
    whether it was one stall or a slower step."""
    steps = sorted((s.ts_us, s.dur_us) for s in spans
                   if s.name == "decode_step")
    if len(steps) > 1:
        durs = [d for _t, d in steps]
        pauses = [(b[0] - (a[0] + a[1])) for a, b in zip(steps, steps[1:])]
        k = max(range(len(pauses)), key=pauses.__getitem__)
        say(f"decode steps in the window: {len(steps)}, median "
            f"{harness.quantile(durs, 0.5) / 1e3:.1f} ms, longest "
            f"{max(durs) / 1e3:.1f} ms; pauses between steps: total "
            f"{sum(pauses) / 1e6:.2f} s, longest {pauses[k] / 1e3:.1f} ms at "
            f"{steps[k][0] / 1e6 - window[0]:.1f} s into the window")
    arrivals = sorted(t for r in requests for t in r["t_tokens"]
                      if window[0] <= t < window[1])
    if len(arrivals) > 1:
        gap, at = max((b - a, a) for a, b in zip(arrivals, arrivals[1:]))
        say(f"longest silence at the clients: {1e3 * gap:.1f} ms at "
            f"{at - window[0]:.1f} s into the window")


def _quiet_metrics(dep, still=0.5, limit=20.0):
    """``/metrics`` once the decode loop has stopped emitting: callers that
    hung up are swept at the next step boundaries."""
    t_end = time.time() + limit
    text = dep.metrics_text()
    while time.time() < t_end:
        time.sleep(still)
        nxt = dep.metrics_text()
        if metric(nxt, "dl4j_decode_tokens_total") == metric(
                text, "dl4j_decode_tokens_total"):
            return nxt
        text = nxt
    return text


def _sleep_until(t):
    d = t - time.time()
    if d > 0:
        time.sleep(d)
