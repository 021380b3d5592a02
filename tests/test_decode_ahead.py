"""The decode loop keeps one step in flight (PR 39): while every slot is
occupied ``GenerationPipeline._iterate`` dispatches step k + 1 with step k's
tokens still on the device and only then fetches, sweeps and publishes step
k. What must not change for that: the tokens of every stream, when the loop
may run ahead, what it compiles, and the loop's guarantees (no shed for
running ahead, a fault loses nothing a stream has seen, a cancelled or
expired request frees its slot at the next sweep).

Schedules are made deterministic by holding the decode thread inside the
first request's first ``on_token`` (its prefill, in ``loop_admit``) until
every request of the test is queued: the same admit pass then joins them in
the order given, and every later join follows from the streams' lengths.
"""
import json
import os
import sys
import threading
import time

import jax
import numpy as np
import pytest

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
if ROOT not in sys.path:
    sys.path.insert(0, ROOT)

from perfbench import harness  # noqa: E402

from deeplearning4j_tpu.models.generation import (  # noqa: E402
    DecodeEngine, SamplerConfig)
from deeplearning4j_tpu.models.transformer import (  # noqa: E402
    TransformerConfig, TransformerLM)
from deeplearning4j_tpu.observability import (  # noqa: E402
    global_registry, reset_global_registry)
from deeplearning4j_tpu.observability.compile_watch import (  # noqa: E402
    global_compile_watch)
from deeplearning4j_tpu.observability.tracing import (  # noqa: E402
    reset_global_trace_sink)
from deeplearning4j_tpu.parallel.generation import (  # noqa: E402
    GenerationPipeline, StreamCancelled)
from deeplearning4j_tpu.resilience import faults  # noqa: E402
from deeplearning4j_tpu.resilience.faults import (  # noqa: E402
    FaultPlan, FaultSpec, InjectedFault)
from deeplearning4j_tpu.resilience.policy import (  # noqa: E402
    CachePagesExhausted, DeadlineExceeded)
from deeplearning4j_tpu.serving import session as _sess  # noqa: E402

VOCAB = 61
_ENGINES = {}


def _gpt(sampler=None, page_tokens=8):
    """A small paged ``TransformerLM`` engine, one a sampler and page size
    for the module (the jit caches live on it)."""
    key = ("gpt", sampler, page_tokens)
    if key not in _ENGINES:
        cfg = TransformerConfig(vocab_size=VOCAB, n_layers=2, n_heads=2,
                                d_model=32, max_len=64)
        m = TransformerLM(cfg)
        _ENGINES[key] = DecodeEngine(
            m, m.init_params(jax.random.key(0)), max_len=48,
            page_tokens=page_tokens, sampler=sampler, seed=5)
    return _ENGINES[key]


def _hybrid():
    """A small ``HybridLM`` whose decode step returns counts behind its
    tokens (``step_stats``): the Kimi configuration at rehearsal sizes."""
    if "hybrid" not in _ENGINES:
        with open(os.path.join(ROOT, "perfbench", "configs",
                               "kimi-linear-48b-a3b-ep2share.json")) as f:
            cfg = json.load(f)
        cfg.update(cfg["rehearsal"])
        cfg.update(compute_dtype="float32", param_dtype="float32")
        km = harness.load_module("models", "kimi_linear.py")
        model = km.build_model(cfg)
        assert model.step_stats
        _ENGINES["hybrid"] = DecodeEngine(
            model, km.make_weights(cfg, 3), max_len=cfg["n_positions"],
            prefill_buckets=[16, 32, 64], page_tokens=8)
    return _ENGINES["hybrid"]


def _prompt(n, seed, vocab=VOCAB):
    return np.random.default_rng(seed).integers(
        1, vocab, (n,)).astype(np.int32)


@pytest.fixture(autouse=True)
def _clean():
    faults.reset()
    reset_global_registry()
    _sess.reset_for_tests()
    yield
    faults.clear()
    GenerationPipeline.shutdown_all()
    _sess.reset_for_tests()


def _counter(name):
    inst = global_registry().get(name)
    return 0.0 if inst is None else inst.value


def _serve(gp, jobs, timeout=120.0):
    """Run ``jobs`` (dicts of ``generate``'s arguments) through ``gp``,
    joined in the order given: the first stream's first token holds the
    decode thread until the rest are queued. Returns one record a job:
    ``tokens`` (as streamed), ``out`` (as returned) or ``error``."""
    recs = [{"tokens": [], "out": None, "error": None} for _ in jobs]
    release, entered = threading.Event(), threading.Event()

    def run(i):
        job = dict(jobs[i])
        hook = job.pop("on_token", None)

        def on_token(tok, idx):
            recs[i]["tokens"].append(int(tok))
            if i == 0 and idx == 0:
                entered.set()
                assert release.wait(timeout)
            return True if hook is None else hook(tok, idx)

        try:
            recs[i]["out"] = gp.generate(on_token=on_token, **job).tolist()
        except Exception as e:          # the test reads it
            recs[i]["error"] = e

    threads = [threading.Thread(target=run, args=(i,), daemon=True)
               for i in range(len(jobs))]
    threads[0].start()
    assert entered.wait(timeout)
    for n, t in enumerate(threads[1:], 1):
        t.start()
        t_end = time.time() + timeout
        while gp._queue.qsize() < n:
            assert time.time() < t_end, "a request never reached the queue"
            time.sleep(0.001)
    release.set()
    for t in threads:
        t.join(timeout)
    assert not any(t.is_alive() for t in threads), "a request hung"
    return recs


def _alone(eng, prompt, n, eos_id=None):
    return eng.generate(prompt[None], n, eos_id=eos_id)[0].tolist()


def _steps(sink):
    return sorted((s for s in sink.spans() if s.name == "decode_step"),
                  key=lambda s: s.ts_us)


# ------------------------------------------------------------ the tokens
@pytest.mark.parametrize("family", ["gpt", "gpt-dense", "hybrid"])
def test_greedy_streams_are_what_each_prompt_gives_alone(family):
    """Every slot occupied, more callers than slots, joiners mid-flight,
    one stream ended by ``eos_id`` mid-stream and the rest by
    ``max_new_tokens`` of different lengths: each finished stream is token
    for token what ``DecodeEngine.generate`` gives its prompt alone, the
    overshoot row of an ended request reaches nobody, and the loop did run
    ahead. The dense (unpaged) cache takes the same path."""
    eng = {"gpt": _gpt, "gpt-dense": lambda: _gpt(page_tokens=0),
           "hybrid": _hybrid}[family]()
    vocab = eng.model.config.vocab_size
    slots, lens = (2, [6, 11, 19, 8, 5]) if family == "hybrid" \
        else (3, [5, 9, 12, 7, 4, 10, 6])
    jobs = [{"prompt": _prompt(n, 40 + i, vocab),
             "max_new_tokens": 5 + (3 * i) % 9}
            for i, n in enumerate(lens)]
    # the second stream ends at the first token that it had not shown yet
    base = _alone(eng, jobs[1]["prompt"], 12)
    cut = next(i for i in range(2, 12) if base[i] not in base[:i])
    jobs[1].update(max_new_tokens=12, eos_id=base[cut])
    with GenerationPipeline(eng, slots=slots) as gp:
        recs = _serve(gp, jobs)
        snap = gp.snapshot()
    for job, rec in zip(jobs, recs):
        assert rec["error"] is None, rec["error"]
        want = _alone(eng, job["prompt"], job["max_new_tokens"],
                      eos_id=job.get("eos_id"))
        assert rec["out"] == want and rec["tokens"] == want
    assert recs[1]["out"] == base[:cut + 1]
    assert snap["steps_ahead"] > 0
    assert _counter("dl4j_decode_tokens_total") == sum(
        len(r["out"]) for r in recs)


def test_sampled_streams_fold_the_same_step_into_the_same_key():
    """Under a seeded sampler a stream is a function of its slot and of the
    step index folded into the key of each of its steps. Every served
    stream, joiners included, is replayed by hand from where the loop says
    it joined (slot, step index of its prefill): the prefill at that index,
    then one ``DecodeEngine.decode`` a step at the following indices, in
    that row of an otherwise empty batch."""
    sampler = SamplerConfig(kind="topk", top_k=8, temperature=1.3)
    eng = _gpt(sampler)
    slots = 2
    jobs = [{"prompt": _prompt(n, 70 + i), "max_new_tokens": m}
            for i, (n, m) in enumerate([(5, 9), (11, 5), (3, 7), (8, 6),
                                        (13, 4)])]
    base = _replay(eng, slots, 0, 0, jobs[0]["prompt"], 9)
    cut = next(i for i in range(2, 9) if base[i] not in base[:i])
    jobs[0]["eos_id"] = base[cut]
    sink = reset_global_trace_sink(65536)
    with GenerationPipeline(eng, slots=slots) as gp:
        recs = _serve(gp, jobs)
        assert gp.snapshot()["steps_ahead"] > 0
    spans = sink.spans()
    iters = {s.trace_id: s.attrs["step"] for s in spans
             if s.name == "decode_iter"}
    joined = {s.attrs["prompt_tokens"]: (s.attrs["slot"], iters[s.trace_id])
              for s in spans if s.name == "prefill_dispatch"}
    assert len(joined) == len(jobs)         # prompt lengths are distinct
    assert len({at for _slot, at in joined.values()}) > 2   # mid-flight
    for job, rec in zip(jobs, recs):
        assert rec["error"] is None, rec["error"]
        slot, at = joined[job["prompt"].size]
        want = _replay(eng, slots, slot, at, job["prompt"],
                       job["max_new_tokens"], job.get("eos_id"))
        assert rec["out"] == want and rec["tokens"] == want
    assert recs[0]["out"] == base[:cut + 1]


def _replay(eng, slots, slot, at, prompt, n, eos_id=None):
    """The stream of ``prompt`` joined into ``slot`` by a prefill at step
    index ``at``, by the plain order: prefill, insert, then dispatch a step
    and fetch it, ``n - 1`` times."""
    state = eng.new_state(slots)
    first, _logits, kv, t = eng.prefill(prompt[None], step=at)
    state = eng.insert_slot(state, kv, slot)
    tokens = np.zeros((slots,), np.int32)
    positions = np.zeros((slots,), np.int32)
    tokens[slot], positions[slot] = int(np.asarray(first)[0]), t
    out = [int(tokens[slot])]
    for step in range(at, at + n - 1):
        if eos_id is not None and out[-1] == eos_id:
            break
        assert eng.ensure_slot_pages(state, slot, int(positions[slot]))
        nxt, _logits, state = eng.decode(state, tokens, positions, step)
        tokens[slot] = int(np.asarray(nxt)[slot])
        positions[slot] += 1
        out.append(int(tokens[slot]))
    return out


# ----------------------------------------------------------- engagement
def test_ahead_follows_the_occupancy():
    """Two slots, two requests of 14 and 6 tokens and nobody queued behind
    them. The first pass with both slots occupied dispatches and fetches
    nothing; every pass after it, while both are occupied, dispatches step
    k + 1 before it fetches step k (``ahead`` 1); once the shorter request
    has left, its slot stays free and every pass fetches the step it
    dispatched (``ahead`` 0). Counter, snapshot and spans agree."""
    eng = _hybrid()
    vocab = eng.model.config.vocab_size
    jobs = [{"prompt": _prompt(7, 1, vocab), "max_new_tokens": 14},
            {"prompt": _prompt(9, 2, vocab), "max_new_tokens": 6}]
    sink = reset_global_trace_sink(65536)
    with GenerationPipeline(eng, slots=2) as gp:
        recs = _serve(gp, jobs)
        snap = gp.snapshot()
    assert [len(r["out"]) for r in recs] == [14, 6]
    spans = sink.spans()
    kids = {}
    for s in spans:
        kids.setdefault(s.parent_id, []).append(s)
    passes = []         # (ahead, dispatched step, fetched step, active)
    for st in _steps(sink):
        inner = {s.name: s for s in kids.get(st.span_id, [])}
        assert set(inner) <= {"decode_dispatch", "token_fetch"}
        sent, got = inner.get("decode_dispatch"), inner.get("token_fetch")
        if sent is not None and got is not None:
            # the dispatch starts (and here ends) before the fetch ends
            assert sent.ts_us + sent.dur_us <= got.ts_us
        if got is not None:
            # the attributes are those of the step whose tokens it fetched
            assert st.attrs["step"] == got.attrs["step"]
            assert "experts_touched" in st.attrs and "live_tokens" in st.attrs
        passes.append((st.attrs["ahead"],
                       None if sent is None else sent.attrs["step"],
                       None if got is None else got.attrs["step"],
                       st.attrs.get("active")))
    # the pass that found both slots occupied and nothing in flight
    assert passes[0] == (0, 0, None, None)
    n_ahead = sum(a for a, *_ in passes)
    assert [a for a, *_ in passes] == \
        [0] + [1] * n_ahead + [0] * (len(passes) - 1 - n_ahead)
    # step 4 makes the shorter request's sixth token: steps 1 to 5 went
    # ahead (5 with its overshoot row: the sweep of step 4 was not in yet)
    assert n_ahead == 5
    for ahead, sent, got, active in passes[1:1 + n_ahead]:
        assert ahead == 1 and sent == got + 1 and active == 2
    # then the pass that only fetches (step 5, dispatched with both slots
    # occupied), and from there one slot is free: dispatch and fetch of one
    # step a pass
    assert passes[1 + n_ahead] == (0, None, 5, 2)
    for ahead, sent, got, active in passes[2 + n_ahead:]:
        assert ahead == 0 and sent == got and active == 1
    assert passes[-1][2] == 12          # 13 steps made 14 tokens, no more
    assert snap["steps_ahead"] == n_ahead \
        == _counter("dl4j_decode_steps_ahead_total")
    assert _counter("dl4j_decode_steps_total") == 13
    # every pass that fetched has a sweep and a publish, no other has
    for it in (s for s in spans if s.name == "decode_iter"):
        names = [s.name for s in sorted(kids.get(it.span_id, []),
                                        key=lambda s: s.ts_us)]
        fetched = any(k.name == "token_fetch" for s in kids[it.span_id]
                      if s.name == "decode_step"
                      for k in kids.get(s.span_id, []))
        want = ["loop_admit", "loop_reclaim", "decode_step"]
        if "decode_step" in names:
            assert names == want + (["loop_sweep", "loop_publish"]
                                    if fetched else [])
    sweeps = [s for s in spans if s.name == "loop_sweep"]
    assert sum(s.attrs["emitted"] for s in sweeps) == 14 + 6 - 2
    assert sum(s.attrs["finished"] for s in sweeps) == 2


def test_no_compile_after_set_up():
    """The few-byte program that carries a step's tokens to the next is
    compiled when the pipeline is built: with the engine warmed, nothing is
    traced or compiled between the first full batch and the last step."""
    eng = _hybrid()
    vocab = eng.model.config.vocab_size
    eng.warm(2)
    jobs = [{"prompt": _prompt(n, 20 + i, vocab), "max_new_tokens": 6 + i}
            for i, n in enumerate([6, 18, 11, 40])]
    with GenerationPipeline(eng, slots=2) as gp:
        gp.generate(_prompt(5, 9, vocab), max_new_tokens=3)  # cost model
        watch = global_compile_watch()
        hist = global_registry().get("dl4j_compile_seconds")
        traced0 = watch.total
        compiled0 = 0 if hist is None else hist.count
        recs = _serve(gp, jobs)
        assert gp.snapshot()["steps_ahead"] > 0
    assert all(r["error"] is None for r in recs)
    assert watch.total == traced0
    hist = global_registry().get("dl4j_compile_seconds")
    assert (0 if hist is None else hist.count) == compiled0


# ------------------------------------------------------- the guarantees
def _pool_run(slots):
    """Three requests whose growth exhausts a pool of 7 pages twice (each
    prompt's bucket takes 2, a request crosses into a third at position 16
    and a fourth at 24). Returns what each stream got before it ended."""
    eng = _gpt()
    jobs = [{"prompt": _prompt(n, 90 + i), "max_new_tokens": 30}
            for i, n in enumerate([6, 7, 5])]
    with GenerationPipeline(eng, slots=slots, cache_pages=7) as gp:
        recs = _serve(gp, jobs)
        ahead = gp.snapshot()["steps_ahead"]
    return jobs, [(type(r["error"]).__name__, r["tokens"]) for r in recs], \
        ahead


def test_running_ahead_never_sheds():
    """With every slot occupied the loop runs ahead until the step ahead
    would need a page the pool cannot give; there it drains, and the
    reclaim that follows sheds as the plain order does: the victims, their
    order and the tokens each had are those of a run in which a fourth
    slot is held open (which never runs ahead)."""
    jobs, full, ahead = _pool_run(slots=3)
    _jobs, plain, never = _pool_run(slots=4)
    assert ahead > 0 and never == 0
    assert full == plain
    kinds = [k for k, _toks in full]
    assert kinds == ["NoneType", CachePagesExhausted.__name__,
                     CachePagesExhausted.__name__]
    # the youngest went first: fewer tokens than the second victim
    assert len(full[2][1]) < len(full[1][1]) < 30
    assert full[0][1] == _alone(_gpt(), jobs[0]["prompt"], 30)


def _crash_in_flight(gp):
    """An ``on_token`` that arms ``generation.step`` to crash (the retry's
    three attempts burnt on one step) from the decode thread, at a token
    that only a pass with a step in flight delivers."""
    def hook(_tok, idx):
        if idx == 4 and gp._inflight and not faults.snapshot()["injected"]:
            faults.install(FaultPlan([FaultSpec(
                "generation.step", "crash", rate=1.0, count=3)]))
        return True
    return hook


def _two_jobs():
    return [{"prompt": _prompt(n, 30 + i), "max_new_tokens": 12}
            for i, n in enumerate([6, 9])]


def test_a_fault_with_a_step_in_flight_loses_no_token_a_stream_has_seen():
    """``generation.step`` crashes in a pass that has a step in flight.
    Journaled sessions re-prefill prompt + emitted and finish the streams
    they would have had: no token twice, none lost."""
    eng, jobs = _gpt(), _two_jobs()
    want = [_alone(eng, j["prompt"], 12) for j in jobs]
    with GenerationPipeline(eng, slots=2) as gp:
        recs = _serve(gp, [dict(jobs[0], on_token=_crash_in_flight(gp)),
                           jobs[1]])
        assert gp.snapshot()["steps_ahead"] > 0
    assert faults.snapshot()["injected"], "the fault never fired"
    assert [r["error"] for r in recs] == [None, None]
    assert [r["out"] for r in recs] == want
    assert [r["tokens"] for r in recs] == want


def test_a_fault_with_a_step_in_flight_fails_the_rest_with_the_fault(
        monkeypatch):
    """Without sessions the same fault fails every request in a slot with
    the injected fault, not with an error about a deleted buffer, and the
    loop serves on from a rebuilt cache."""
    monkeypatch.setenv("DL4J_TPU_SESSIONS", "0")
    eng, jobs = _gpt(), _two_jobs()
    with GenerationPipeline(eng, slots=2) as gp:
        recs = _serve(gp, [dict(jobs[0], on_token=_crash_in_flight(gp)),
                           jobs[1]])
        faults.clear()
        again = gp.generate(jobs[0]["prompt"], max_new_tokens=12).tolist()
    assert all(isinstance(r["error"], InjectedFault) for r in recs), recs
    assert again == _alone(eng, jobs[0]["prompt"], 12)


def test_cancel_and_deadline_free_the_slot_at_the_next_sweep():
    """Both slots occupied and the loop ahead: one consumer hangs up at its
    fourth token, the other request's deadline passes while its fourth is
    delivered. Each is resolved typed at the sweep that sees it, the step
    already in flight is an overshoot nobody reads, and the queued requests
    take the freed slots and get what they get alone."""
    eng = _gpt()

    def hang_up(_tok, idx):
        return idx < 3

    def outstay(_tok, idx):
        if idx == 3:
            time.sleep(1.05)        # the deadline passes inside this sweep
        return True

    jobs = [{"prompt": _prompt(6, 50), "max_new_tokens": 20,
             "on_token": hang_up},
            {"prompt": _prompt(8, 51), "max_new_tokens": 40,
             "deadline_ms": 1000.0, "on_token": outstay},
            {"prompt": _prompt(5, 52), "max_new_tokens": 9},
            {"prompt": _prompt(10, 53), "max_new_tokens": 7}]
    with GenerationPipeline(eng, slots=2) as gp:
        gp.generate(_prompt(7, 54), max_new_tokens=3)   # compiles
        recs = _serve(gp, jobs)
        snap = gp.snapshot()
    assert isinstance(recs[0]["error"], StreamCancelled)
    assert recs[0]["tokens"] == _alone(eng, jobs[0]["prompt"], 20)[:4]
    assert isinstance(recs[1]["error"], DeadlineExceeded)
    assert recs[1]["tokens"] == _alone(eng, jobs[1]["prompt"], 40)[:4]
    for job, rec in zip(jobs[2:], recs[2:]):
        assert rec["error"] is None
        assert rec["out"] == _alone(eng, job["prompt"],
                                    job["max_new_tokens"])
    assert snap["steps_ahead"] > 0 and snap["active"] == 0
    assert snap["pages"]["in_use"] == 0
