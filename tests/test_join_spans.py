"""The join, measured where it happens (PR 40): ``_start_request`` splits a
joiner's pause where the program hands over to the device - dispatch, insert,
first-token fetch - by consecutive clock reads, says on the request's
``prefill`` span what the join was (``bucket``) and what it queued behind
(``inflight``, ``step``), says on ``loop_admit`` how many slots were free and
how many callers still queued when a pass joined, and counts the same without
a trace (two counters, ``snapshot()["joins"]``, one line when a pipeline
stops).

One schedule a family, served once and read by every test below: two slots,
four requests queued before the first admit pass ends (``_serve`` of
``tests/test_decode_ahead.py``), so the first pass joins two into an idle
pipeline and each later join is made the pass after a sweep freed one slot,
while the other is busy and the loop runs ahead: a step is then in flight.
"""
import logging
import os
import sys
import time

import pytest

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
if ROOT not in sys.path:
    sys.path.insert(0, ROOT)

import jax  # noqa: E402
from test_decode_ahead import (VOCAB, _counter, _gpt, _hybrid,  # noqa: E402
                               _prompt, _serve)

from deeplearning4j_tpu.models.generation import DecodeEngine  # noqa: E402
from deeplearning4j_tpu.models.transformer import (  # noqa: E402
    TransformerConfig, TransformerLM)

from deeplearning4j_tpu.observability import (  # noqa: E402
    global_registry, reset_global_registry)
from deeplearning4j_tpu.observability.tracing import (  # noqa: E402
    reset_global_trace_sink)
from deeplearning4j_tpu.parallel import generation as _generation  # noqa: E402
from deeplearning4j_tpu.parallel.generation import (  # noqa: E402
    _JOIN_PARTS, GenerationPipeline)
from deeplearning4j_tpu.resilience import faults  # noqa: E402
from deeplearning4j_tpu.serving import session as _sess  # noqa: E402

FAMILIES = ["gpt", "hybrid"]
#: prompt lengths in three buckets (16, 32 and 48 or 64) and outputs of
#: different lengths, so that no two streams end at one sweep
LENGTHS, OUTPUTS = [5, 20, 9, 34], [7, 11, 6, 5]
_RUNS = {}


def _engine(family):
    return {"gpt": _gpt, "hybrid": _hybrid}[family]()


def _jobs(eng):
    vocab = eng.model.config.vocab_size
    return [{"prompt": _prompt(n, 60 + i, vocab), "max_new_tokens": m}
            for i, (n, m) in enumerate(zip(LENGTHS, OUTPUTS))]


def _labelled(name, **labels):
    inst = global_registry().get(name)
    return 0.0 if inst is None else inst.labels(**labels).value


def _totals(buckets):
    out = {("joins", b): _labelled("dl4j_decode_joins_total", bucket=str(b))
           for b in buckets}
    out.update({ph: _labelled("dl4j_decode_join_seconds_total", phase=ph)
                for ph in _JOIN_PARTS})
    hist = global_registry().get("dl4j_decode_prefill_seconds")
    out["prefill_seconds"] = 0.0 if hist is None else hist.sum
    return out


def _schedule(family):
    """The schedule above, served once a family: its spans, what the
    counters gained, the pipeline's snapshot and the streams."""
    if family not in _RUNS:
        eng = _engine(family)
        jobs = _jobs(eng)
        with GenerationPipeline(eng, slots=2) as gp:    # compiles
            _serve(gp, jobs)
        sink = reset_global_trace_sink(65536)
        before = _totals(eng.prefill_buckets)
        with GenerationPipeline(eng, slots=2) as gp:
            recs = _serve(gp, jobs)
            snap = gp.snapshot()
        after = _totals(eng.prefill_buckets)
        assert all(r["error"] is None for r in recs)
        spans = sorted(sink.spans(), key=lambda s: s.ts_us)
        kids = {}
        for s in spans:
            kids.setdefault(s.parent_id, []).append(s)
        _RUNS[family] = {
            "eng": eng, "jobs": jobs, "spans": spans, "kids": kids,
            "snap": snap, "tokens": [r["out"] for r in recs],
            "gained": {k: after[k] - before[k] for k in after},
            "joins": [s for s in spans if s.name == "prefill"]}
    return _RUNS[family]


@pytest.fixture(autouse=True)
def _clean():
    faults.reset()
    reset_global_registry()
    _sess.reset_for_tests()
    yield
    faults.clear()
    GenerationPipeline.shutdown_all()
    _sess.reset_for_tests()


def _named(run, name):
    return [s for s in run["spans"] if s.name == name]


# ---------------------------------------------------------------- the spans
@pytest.mark.parametrize("family", FAMILIES)
def test_first_token_fetch_nests_and_the_join_spans_keep_their_place(family):
    """``first_token_fetch`` is a child of ``prefill_insert``, which with
    ``prefill_dispatch`` stays a child of ``loop_admit``, a joiner's dispatch
    before its insert; the new span lies at its parent's end."""
    run = _schedule(family)
    kids = run["kids"]
    admits = [s for s in _named(run, "loop_admit") if s.attrs["joined"]]
    seen = 0
    for admit in admits:
        mine = kids.get(admit.span_id, [])
        assert [s.name for s in mine] == \
            ["prefill_dispatch", "prefill_insert"] * admit.attrs["joined"]
        end = admit.ts_us
        for sent, ins in zip(mine[::2], mine[1::2]):
            assert sent.attrs["slot"] == ins.attrs["slot"]
            assert end <= sent.ts_us
            assert sent.ts_us + sent.dur_us <= ins.ts_us
            end = ins.ts_us + ins.dur_us
            inner = kids.get(ins.span_id, [])
            assert [s.name for s in inner] == ["first_token_fetch"]
            fetch = inner[0]
            assert fetch.attrs == {"slot": ins.attrs["slot"]}
            assert ins.ts_us <= fetch.ts_us
            assert fetch.ts_us + fetch.dur_us <= end
            assert fetch.depth == ins.depth + 1 == admit.depth + 2
            seen += 1
        assert end <= admit.ts_us + admit.dur_us
    assert seen == len(LENGTHS) == len(_named(run, "first_token_fetch"))
    # nothing else of the loop gained a child or a parent
    assert not [s for s in _named(run, "prefill_dispatch")
                if kids.get(s.span_id)]


@pytest.mark.parametrize("family", FAMILIES)
def test_the_three_parts_sum_to_the_prefill_span(family):
    """Consecutive clock reads: ``dispatch_us + insert_us + fetch_us`` is
    the request's ``prefill`` span to the microsecond, and each part holds
    the loop's own span of that part."""
    run = _schedule(family)
    sent, fetched = (_named(run, n) for n in ("prefill_dispatch",
                                              "first_token_fetch"))
    assert len(run["joins"]) == len(LENGTHS)
    for join, disp, fetch in zip(run["joins"], sent, fetched):
        a = join.attrs
        parts = [a[ph + "_us"] for ph in _JOIN_PARTS]
        assert all(p >= 0 for p in parts)
        assert abs(sum(parts) - join.dur_us) < 1.0
        assert join.ts_us <= disp.ts_us
        assert a["dispatch_us"] >= disp.dur_us
        assert a["fetch_us"] >= fetch.dur_us
        assert join.ts_us + join.dur_us >= fetch.ts_us + fetch.dur_us


@pytest.mark.parametrize("family", FAMILIES)
def test_bucket_is_the_padded_length_the_prefill_ran_at(family):
    run = _schedule(family)
    eng = run["eng"]
    want = [eng.prefill_bucket(n) for n in LENGTHS]
    assert len(set(want)) == 3
    by_len = {s.attrs["prompt_tokens"]: s.attrs["bucket"]
              for s in run["joins"]}
    assert [by_len[n] for n in LENGTHS] == want
    assert {s.attrs["prompt_tokens"]: s.attrs["bucket"]
            for s in _named(run, "prefill_dispatch")} == by_len
    assert all(s.attrs["tokens"] == s.attrs["prompt_tokens"]
               for s in run["joins"])


@pytest.mark.parametrize("family", FAMILIES)
def test_inflight_and_step_say_what_the_join_queued_behind(family):
    """The first joins of an idle pipeline find nothing on the chip; a join
    made while the other slot is busy and the loop runs ahead finds one
    step. ``step`` is the ``step`` of the pass's ``decode_iter``."""
    run = _schedule(family)
    assert run["snap"]["steps_ahead"] > 0
    assert [s.attrs["inflight"] for s in run["joins"]] == [0, 0, 1, 1]
    assert [s.attrs["stalled_slots"] for s in run["joins"]] == [0, 1, 1, 1]
    assert [s.attrs["step"] for s in run["joins"][:2]] == [0, 0]
    iters = {s.trace_id: s.attrs["step"] for s in _named(run, "decode_iter")}
    sent = _named(run, "prefill_dispatch")
    for join, disp in zip(run["joins"], sent):
        assert disp.attrs["inflight"] == join.attrs["inflight"]
        assert join.attrs["step"] == iters[disp.trace_id]
        assert join.attrs["slot"] == disp.attrs["slot"]
    assert run["joins"][2].attrs["step"] < run["joins"][3].attrs["step"]


@pytest.mark.parametrize("family", FAMILIES)
def test_loop_admit_says_free_and_queued_only_where_it_joined(family):
    run = _schedule(family)
    admits = _named(run, "loop_admit")
    joining = [s.attrs for s in admits if s.attrs["joined"]]
    assert joining == [{"joined": 2, "free": 2, "queued": 2},
                       {"joined": 1, "free": 1, "queued": 1},
                       {"joined": 1, "free": 1, "queued": 0}]
    idle = [s.attrs for s in admits if not s.attrs["joined"]]
    assert len(idle) > 10 and all(a == {"joined": 0} for a in idle)


# ------------------------------------------------- counters, snapshot, line
@pytest.mark.parametrize("family", FAMILIES)
def test_the_counters_grow_by_the_spans_numbers(family):
    run = _schedule(family)
    gained, joins = run["gained"], run["joins"]
    for b in run["eng"].prefill_buckets:
        assert gained["joins", b] == sum(s.attrs["bucket"] == b
                                         for s in joins)
    for ph in _JOIN_PARTS:
        assert gained[ph] == pytest.approx(
            sum(s.attrs[ph + "_us"] for s in joins) / 1e6, rel=1e-9)
    assert sum(gained[ph] for ph in _JOIN_PARTS) == pytest.approx(
        gained["prefill_seconds"], rel=1e-9)


@pytest.mark.parametrize("family", FAMILIES)
def test_snapshot_joins_is_the_spans_numbers(family):
    run = _schedule(family)
    joins, got = run["joins"], run["snap"]["joins"]
    assert got["count"] == 4 and got["behind"] == 2
    for ph in _JOIN_PARTS:
        assert got[ph + "_s"] == pytest.approx(
            sum(s.attrs[ph + "_us"] for s in joins) / 1e6, rel=1e-9)
    top = max(joins, key=lambda s: s.dur_us)
    assert got["longest"]["ms"] == pytest.approx(top.dur_us / 1e3, abs=1e-3)
    for key in ("slot", "bucket", "tokens", "inflight", "step"):
        assert got["longest"][key] == top.attrs[key]
    for ph in _JOIN_PARTS:
        assert got["longest"][ph + "_ms"] == pytest.approx(
            top.attrs[ph + "_us"] / 1e3)


def test_longest_names_the_join_a_slow_insert_held(monkeypatch):
    """An insert that sleeps (the third join's: the stall PERF.md's item 16
    had to be hunted for by hand) is the longest join of the snapshot, by
    slot, bucket, the step in flight and the part it was spent in."""
    run = _schedule("gpt")          # every bucket's programs are compiled
    eng, jobs = run["eng"], run["jobs"]
    real, calls = eng.insert_slot, []

    def insert_slot(cache, kv, slot):
        calls.append(slot)
        if len(calls) == 3:
            time.sleep(0.4)
        return real(cache, kv, slot)

    monkeypatch.setattr(eng, "insert_slot", insert_slot)
    sink = reset_global_trace_sink(65536)
    with GenerationPipeline(eng, slots=2) as gp:
        recs = _serve(gp, jobs)
        top = gp.snapshot()["joins"]["longest"]
    assert all(r["error"] is None for r in recs) and len(calls) == 4
    third = [s for s in sink.spans() if s.name == "prefill"][2]
    assert top["slot"] == calls[2] == third.attrs["slot"]
    assert top["bucket"] == eng.prefill_bucket(LENGTHS[2])
    assert top["tokens"] == LENGTHS[2] and top["inflight"] == 1
    assert top["step"] == third.attrs["step"]
    assert 400 <= top["insert_ms"] <= top["ms"]
    assert top["insert_ms"] > 20 * (top["dispatch_ms"] + top["fetch_ms"])


def test_a_join_that_traced_a_program_is_not_the_longest():
    """A bucket's first join on an engine nobody warmed traces and compiles:
    it is counted with its seconds, and it does not stand as the longest
    join, where it would hide every stall after it."""
    cfg = TransformerConfig(vocab_size=VOCAB, n_layers=1, n_heads=2,
                            d_model=32, max_len=64)
    model = TransformerLM(cfg)
    eng = DecodeEngine(model, model.init_params(jax.random.key(1)),
                       max_len=48, page_tokens=8, seed=5)
    sink = reset_global_trace_sink(65536)
    with GenerationPipeline(eng, slots=2) as gp:
        gp.generate(_prompt(5, 1), max_new_tokens=3)
        first = gp.snapshot()["joins"]
        gp.generate(_prompt(6, 2), max_new_tokens=3)
        second = gp.snapshot()["joins"]
    assert first["count"] == 1 and first["longest"] is None
    assert second["count"] == 2 and second["longest"]["tokens"] == 6
    cold, warm = [s for s in sink.spans() if s.name == "prefill"]
    assert cold.dur_us > 10 * warm.dur_us
    assert first["dispatch_s"] + first["insert_s"] + first["fetch_s"] \
        == pytest.approx(cold.dur_us / 1e6, rel=1e-9)
    assert second["longest"]["ms"] == pytest.approx(warm.dur_us / 1e3,
                                                    abs=1e-3)


def test_the_stop_line_is_logged_once(caplog):
    run = _schedule("gpt")
    eng, jobs = run["eng"], run["jobs"]
    with caplog.at_level(logging.INFO, logger=_generation.__name__):
        with GenerationPipeline(eng, slots=2) as gp:
            _serve(gp, jobs)
            top = gp.snapshot()["joins"]["longest"]
        lines = [r.getMessage() for r in caplog.records
                 if r.name == _generation.__name__]
    said = [m for m in lines if m.startswith("joins: ")]
    assert len(said) == 1
    assert said[0].startswith("joins: 4 in ")
    assert "; 2 of 4 behind a step in flight; longest " in said[0]
    assert (f"bucket {top['bucket']} slot {top['slot']} inflight "
            f"{top['inflight']} dispatch/insert/fetch ") in said[0]
    # beside the loop's own line, after it
    ahead = [i for i, m in enumerate(lines) if m.startswith("decode loop: ")]
    assert len(ahead) == 1 and lines[ahead[0] + 1] == said[0]


def test_a_pipeline_that_joined_nobody_says_so(caplog):
    with caplog.at_level(logging.INFO, logger=_generation.__name__):
        with GenerationPipeline(_gpt(), slots=2) as gp:
            assert gp.snapshot()["joins"] == {
                "count": 0, "behind": 0, "dispatch_s": 0.0, "insert_s": 0.0,
                "fetch_s": 0.0, "longest": None}
    said = [r.getMessage() for r in caplog.records
            if r.getMessage().startswith("joins: ")]
    assert said == ["joins: 0 in 0.000 s (dispatch 0.000, insert 0.000, "
                    "fetch 0.000); 0 of 0 behind a step in flight"]


@pytest.mark.parametrize("family", FAMILIES)
def test_with_tracing_off_nothing_is_recorded_and_everything_counts(
        family, monkeypatch):
    """``DL4J_TPU_TRACE=0``: no span in the ring, the counters and the
    snapshot still count every join, and the streams are token for token
    those of the traced schedule."""
    run = _schedule(family)
    eng, jobs = run["eng"], run["jobs"]
    monkeypatch.setenv("DL4J_TPU_TRACE", "0")
    sink = reset_global_trace_sink(65536)
    before = _totals(eng.prefill_buckets)
    with GenerationPipeline(eng, slots=2) as gp:
        recs = _serve(gp, jobs)
        got = gp.snapshot()["joins"]
    after = _totals(eng.prefill_buckets)
    assert len(sink) == 0 and sink.total_recorded == 0
    assert [r["out"] for r in recs] == run["tokens"]
    assert [r["tokens"] for r in recs] == run["tokens"]
    assert got["count"] == 4 and got["behind"] == 2
    assert got["longest"]["bucket"] in eng.prefill_buckets
    for b in eng.prefill_buckets:
        assert after["joins", b] - before["joins", b] \
            == run["gained"]["joins", b]
    parts = sum(after[ph] - before[ph] for ph in _JOIN_PARTS)
    assert parts == pytest.approx(
        after["prefill_seconds"] - before["prefill_seconds"], rel=1e-9)
    assert parts == pytest.approx(sum(got[ph + "_s"] for ph in _JOIN_PARTS),
                                  rel=1e-9)
    assert _counter("dl4j_decode_tokens_total") == sum(OUTPUTS)
