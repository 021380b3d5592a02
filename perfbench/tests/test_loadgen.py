"""The generator: the same seed gives the same plan, every seed the same set
of lengths and gaps in another order, lengths inside their clips, and a
request timed from the instant it was due."""
import asyncio
import json
import threading
import time
from http.server import BaseHTTPRequestHandler, ThreadingHTTPServer

import numpy as np
import pytest

from perfbench import harness, loadgen

MIXES = ["chat-open-0.8knee", "batchgen-closed-40"]


def _mix(name):
    t = harness.load_json("traffic", name + ".json")
    t["vocab_size"] = 50257
    return t


@pytest.mark.parametrize("name", MIXES)
def test_plan_is_the_seeds_alone(name):
    t = _mix(name)
    a, b = loadgen.plan(t, 2**31 + 11, 20), loadgen.plan(t, 2**31 + 11, 20)
    assert a == b
    c = loadgen.plan(t, 5, 20)
    assert [r["prompt"] for r in a] != [r["prompt"] for r in c]
    # another seed: the same work, in another order
    assert len(a) == len(c)
    for size in (lambda r: len(r["prompt"]), lambda r: r["max_new_tokens"]):
        if t["kind"] == "serve-open":   # clipped together, so compare sums
            assert sum(map(size, a)) == sum(map(size, c))
    assert all(0 <= tok < 50257 for r in a[:20] for tok in r["prompt"])


@pytest.mark.parametrize("name", MIXES)
def test_lengths_stay_inside_their_clips(name):
    t = _mix(name)
    reqs = loadgen.plan(t, 7, 40)
    p = np.array([len(r["prompt"]) for r in reqs])
    o = np.array([r["max_new_tokens"] for r in reqs])
    assert p.min() >= t["prompt_len"]["min"] and p.max() <= t["prompt_len"]["max"]
    assert o.min() >= t["output_len"]["min"] and o.max() <= t["output_len"]["max"]
    assert (p + o).max() <= t["max_total"]
    if t["prompt_len"]["dist"] == "lognormal":
        assert abs(np.median(p) - t["prompt_len"]["median"]) <= 4
        assert abs(np.median(o) - t["output_len"]["median"]) <= 4
        assert p.max() == t["prompt_len"]["max"]        # the tail is there


def test_open_loop_arrivals_keep_the_rate():
    t = _mix("chat-open-0.8knee")
    t["rate_rps"] = 12.5
    reqs = loadgen.plan(t, 3, 40)
    due = np.array([r["due"] for r in reqs])
    assert len(reqs) == round(12.5 * (t["ramp_s"] + 40))
    assert np.all(np.diff(due) > 0) and due[0] >= 0
    assert due[-1] <= t["ramp_s"] + 40
    gaps = np.diff(due)
    # exponential gaps: coefficient of variation 1
    assert 0.9 < gaps.std() / gaps.mean() < 1.1
    other = np.diff([r["due"] for r in loadgen.plan(t, 4, 40)])
    assert np.allclose(np.sort(gaps), np.sort(other), rtol=0.05, atol=1e-3)


def test_gamma_arrivals_are_burstier_at_the_same_rate():
    rng = np.random.default_rng(0)
    g = loadgen.gaps({"process": "gamma", "cv": 2.0}, 400, 40.0, rng)
    assert abs(g.sum() - 40.0) < 1e-6 and 1.6 < g.std() / g.mean() < 2.4


class _Slow(BaseHTTPRequestHandler):
    """Accepts at once, answers the first token 50 ms later."""

    def log_message(self, *a):
        pass

    def do_POST(self):
        n = json.loads(self.rfile.read(int(self.headers["Content-Length"])))[
            "max_new_tokens"]
        time.sleep(0.05)
        self.send_response(200)
        self.send_header("Content-Type", "text/event-stream")
        self.end_headers()
        for i in range(n):
            self.wfile.write(f"event: token\ndata: "
                             f"{json.dumps({'index': i, 'token': 7})}\n\n"
                             .encode())
            self.wfile.flush()
        self.wfile.write(b"event: done\ndata: {}\n\n")


def test_a_request_is_timed_from_when_it_was_due():
    srv = ThreadingHTTPServer(("127.0.0.1", 0), _Slow)
    threading.Thread(target=srv.serve_forever, daemon=True).start()
    try:
        t0 = time.time() - 0.2          # both requests are already late
        reqs = [{"id": i, "due": 0.0, "prompt": [1, 2], "max_new_tokens": 3}
                for i in range(2)]
        recs = asyncio.run(loadgen.open_loop("127.0.0.1", srv.server_port,
                                             reqs, t0, time.time() + 30))
    finally:
        srv.shutdown()
        srv.server_close()
    for r in recs:
        assert r["ok"] and r["tokens"] == [7, 7, 7] and r["due"] == t0
        assert r["sent"] - r["due"] >= 0.2          # how late it was sent
        assert r["t_tokens"][0] - r["due"] >= 0.25  # lateness is in the ttft
        assert r["t_tokens"][0] - r["sent"] < 0.2
