"""The readers of the join (``layer_metrics/_join.py`` and the seven files
that name them): each of the five span-reading metrics on made-up spans by
numbers worked out by hand, None on the spans of a program without the new
attributes; the device's holds on a made-up list of module executions; the
pairing of holds and spans across the two clocks; and how long a traced
run's worth takes to read."""
import time
from types import SimpleNamespace

import pytest

from perfbench import harness
from perfbench.layer_metrics import _join


def _read(name):
    return harness.load_module("layer_metrics", name + ".py").read


def _span(name, ts_ms, dur_ms, **attrs):
    return SimpleNamespace(name=name, ts_us=ts_ms * 1e3, dur_us=dur_ms * 1e3,
                           attrs=attrs or None, trace_id="t")


def _join_span(ts_ms, dur_ms, bucket, fetch_ms, stalled=63, inflight=1,
               **more):
    rest = dur_ms - fetch_ms
    return _span("prefill", ts_ms, dur_ms, slot=3, prompt_tokens=bucket - 7,
                 tokens=bucket - 7, stalled_slots=stalled, bucket=bucket,
                 inflight=inflight, step=11, dispatch_us=rest * 600.0,
                 insert_us=rest * 400.0, fetch_us=fetch_ms * 1e3, **more)


#: a window of 10 s that holds four joins (1024, 1024, 2048, 4096: 8,192
#: tokens of buckets, 100 + 120 + 180 + 400 = 800 ms, 560 ms of it fetch,
#: the first into an idle pipeline) and five passes of the loop, two of which
#: joined nobody, one joined two
WINDOW = (100.0, 110.0)
SPANS = [
    _join_span(100_100, 100, 1024, 60, stalled=0, inflight=0),
    _join_span(100_300, 120, 1024, 80),
    _join_span(102_000, 180, 2048, 120),
    _join_span(105_000, 400, 4096, 300),
    _span("loop_admit", 100_100, 225, joined=2, free=2, queued=5),
    _span("loop_admit", 101_000, 0.05, joined=0),
    _span("loop_admit", 102_000, 181, joined=1, free=1, queued=9),
    _span("loop_admit", 103_000, 0.05, joined=0),
    _span("loop_admit", 105_000, 401, joined=1, free=1, queued=0),
    _span("decode_step", 101_000, 14, active=64),
]


def _ctx(spans, **more):
    return dict({"spans": spans, "window": WINDOW}, **more)


def _parent(spans):
    """The same spans as a program before PR 40 writes them."""
    old = {"prefill": ("slot", "prompt_tokens", "tokens", "stalled_slots"),
           "loop_admit": ("joined",)}
    return [SimpleNamespace(
        name=s.name, ts_us=s.ts_us, dur_us=s.dur_us, trace_id=s.trace_id,
        attrs={k: v for k, v in (s.attrs or {}).items()
               if k in old.get(s.name, s.attrs or {})} or None)
        for s in spans]


# ------------------------------------------------------- the span readers
def test_prefill_stall_pct_tput_is_the_accepted_reader():
    read = _read("prefill_stall_pct.tput")
    assert read is _read("prefill_stall_pct.lat")
    # 120 + 180 + 400 ms held live streams, of a 10 s window
    assert read(_ctx(SPANS)) == pytest.approx(7.0)
    assert read(_ctx(_parent(SPANS))) == pytest.approx(7.0)
    assert read(_ctx([SPANS[-1]])) is None


def test_join_ms_per_ktok():
    read = _read("join_ms_per_ktok.tput")
    assert read(_ctx(SPANS)) == pytest.approx(800.0 / 8192 * 1024)   # 100.0
    assert read(_ctx(SPANS[2:])) == pytest.approx(580.0 / 6144 * 1024)
    assert read(_ctx(_parent(SPANS))) is None
    assert read(_ctx(SPANS[4:])) is None


def test_join_fetch_share_pct():
    read = _read("join_fetch_share_pct.tput")
    assert read(_ctx(SPANS)) == pytest.approx(100.0 * 560 / 800)     # 70.0
    assert read(_ctx(SPANS[:1])) == pytest.approx(60.0)
    assert read(_ctx(_parent(SPANS))) is None
    assert read(_ctx(SPANS[4:])) is None


def test_join_max_ms_and_what_it_says(capsys):
    read = _read("join_max_ms.tput")
    assert read(_ctx(SPANS)) == pytest.approx(400.0)
    said = capsys.readouterr().out
    assert "longest join: 400.0 ms at 5.0 s into the window: bucket 4096 " \
        "slot 3 tokens 4089 inflight 1 step 11 dispatch/insert/fetch " \
        "60.0/40.0/300.0 ms; median of 4 joins 150.0 ms" in said
    # the parent's spans have a length and a slot, and that is read
    assert read(_ctx(_parent(SPANS))) == pytest.approx(400.0)
    assert "bucket - slot 3 tokens 4089 inflight - step - " \
        "dispatch/insert/fetch - ms" in capsys.readouterr().out
    assert read(_ctx(SPANS[4:])) is None


def test_joins_per_admit_mean_and_what_it_says(capsys):
    read = _read("joins_per_admit_mean.tput")
    assert read(_ctx(SPANS)) == pytest.approx(4 / 3)
    said = capsys.readouterr().out
    assert "3 of 5 passes joined; free mean 1.33, most 2; queued mean " \
        "4.67, most 9; passes by joined: 1: 2, 2: 1" in said
    assert read(_ctx(_parent(SPANS))) == pytest.approx(4 / 3)
    assert "3 of 5 passes joined; passes by joined" \
        in capsys.readouterr().out
    # the loop ran and nobody joined: a number, not a gap in the ledger
    assert read(_ctx([SPANS[5], SPANS[7]])) == 0.0
    assert read(_ctx(SPANS[:4])) is None


def test_every_span_reader_says_how_long_it_took(capsys):
    for name in ("join_ms_per_ktok", "join_fetch_share_pct", "join_max_ms",
                 "joins_per_admit_mean"):
        _read(name + ".tput")(_ctx(SPANS))
        assert f"reader {name}: 0.0" in capsys.readouterr().out


# ------------------------------------------------------- the device plane
def _mod(name, start_ms, dur_ms):
    return (f"jit__{name}(123)", start_ms / 1e3, (start_ms + dur_ms) / 1e3)


#: six decode executions of 14 ms; between the second and the third one
#: join (prefill 70 ms + insert 2 ms, 6 ms of hand-over: a hold of 80 ms),
#: between the fourth and the fifth two joins (a hold of 190 ms: 2 x (80 + 3)
#: ms of programs), between the fifth and the sixth 30 ms with the token
#: carrier alone, which is no join
MODULES = [
    _mod("decode_paged", 0, 14), _mod("decode_paged", 14, 14),
    _mod("prefill", 29, 70), _mod("insert_paged", 100, 2),
    _mod("decode_paged", 108, 14), _mod("carry_tokens", 122, 0.01),
    _mod("decode_paged", 122.1, 14),
    _mod("prefill", 137, 80), _mod("insert_paged", 218, 3),
    _mod("prefill", 222, 80), _mod("insert_paged", 303, 3),
    _mod("decode_paged", 326.1, 14), _mod("carry_tokens", 345, 0.01),
    _mod("decode_paged", 370.1, 14),
]
TRACE_SPAN = (0.0, 0.3841)


def _trace(modules, offset=1000.0):
    return SimpleNamespace(devices=[SimpleNamespace(modules=modules)],
                           clock_offset=offset)


def _epoch_ms(ms, offset=1000.0):
    return offset * 1e3 + ms


def test_holds_are_the_decode_gaps_that_contain_a_join_program():
    found = _join.holds(list(reversed(MODULES)))      # any order in
    assert [(round(1e3 * s, 3), round(1e3 * e, 3), n)
            for s, e, _own, n in found] == [(28.0, 108.0, 2),
                                            (136.1, 326.1, 4)]
    assert [round(1e3 * own, 3) for _s, _e, own, _n in found] == [72.0,
                                                                  166.0]
    # a join before the first decode execution or after the last bounds no
    # hold; a trace without the decode program reads nothing
    assert _join.holds([_mod("prefill", 0, 70)] + MODULES[:2]
                       + [_mod("prefill", 40, 70)]) == []
    assert _join.holds(MODULES[:2]) == []
    assert _join.holds([_mod("prefill", 0, 70), _mod("step", 80, 5)]) is None
    assert _join.holds([]) is None


def test_join_hold_dev_pct_and_its_table(capsys):
    lat, tput = _read("join_hold_dev_pct.lat"), _read("join_hold_dev_pct.tput")
    assert lat is tput
    spans = [_join_span(_epoch_ms(20), 85, 1024, 60),
             _join_span(_epoch_ms(130), 95, 2048, 70),
             _join_span(_epoch_ms(226), 94, 2048, 72, inflight=0)]
    ctx = _ctx(spans, trace=_trace(MODULES), trace_span=TRACE_SPAN)
    assert tput(ctx) == pytest.approx(100.0 * (80 + 190) / 384.1)
    said = capsys.readouterr().out
    assert "2 holds, 0.2700 s of 0.3841 s traced, 0.2380 s of it the join " \
        "programs' own; 3 prefill spans paired, 0 holds without a span, 0 " \
        "spans inside the traced part without a hold" in said
    assert "bucket 1024: 1 joins, host span median 85.0 ms, device hold " \
        "median 80.0 ms, join programs 72.0 ms a join, span starts 8.0 ms " \
        "before its hold and ends 3.0 ms before it, inflight 1.00" in said
    assert "bucket 2048: 2 joins, host span median 94.5 ms, device hold " \
        "median 190.0 ms, join programs 83.0 ms a join, span starts -41.9 " \
        "ms before its hold and ends 53.6 ms before it, inflight 0.50" in said
    assert "reader join_hold_dev_pct: 0.0" in said
    # the parent's spans pair too, under no bucket
    ctx["spans"] = _parent(spans)
    assert tput(ctx) == pytest.approx(100.0 * 270 / 384.1)
    assert "bucket -: 3 joins, host span median 94.0 ms" \
        in capsys.readouterr().out


def test_join_hold_dev_pct_without_a_join_and_without_a_decode_program():
    read = _read("join_hold_dev_pct.tput")
    quiet = [m for m in MODULES if "prefill" not in m[0]
             and "insert" not in m[0]]
    assert read(_ctx([], trace=_trace(quiet), trace_span=TRACE_SPAN)) == 0.0
    train = [("jit_step(7)", 0.0, 0.1), ("jit_step(7)", 0.1, 0.2)]
    assert read(_ctx([], trace=_trace(train), trace_span=(0.0, 0.2))) is None
    assert read(_ctx(SPANS, trace=None, trace_span=TRACE_SPAN)) is None
    assert read(_ctx(SPANS, trace=SimpleNamespace(devices=[]),
                     trace_span=TRACE_SPAN)) is None
    # no marks, so no common clock: the share is read, nothing is paired
    assert read(_ctx(SPANS, trace=_trace(MODULES, offset=None),
                     trace_span=TRACE_SPAN)) == pytest.approx(
        100.0 * 270 / 384.1)


def test_pairing_moves_spans_onto_the_profiles_clock(capsys):
    """A span belongs to the hold it overlaps longest once its epoch times
    are moved by the trace's offset; one that overlaps none is named if it
    lies inside the traced part, and passed over if it does not."""
    found = _join.holds(MODULES)
    spans = [
        _join_span(_epoch_ms(20), 85, 1024, 60),       # the first hold
        # starts in the first hold's last 2 ms, lies in the second
        _join_span(_epoch_ms(106), 100, 2048, 70),
        _join_span(_epoch_ms(226), 94, 2048, 72),
        _join_span(_epoch_ms(340), 20, 512, 10),       # inside, no hold
        _join_span(_epoch_ms(-500), 90, 4096, 70),     # before the trace
        _join_span(20, 85, 1024, 60),                  # not moved at all
    ]
    by_hold, lost = _join.pair(found, spans, 1000.0, *TRACE_SPAN)
    assert [[s.attrs["bucket"] for s in mine] for mine in by_hold] \
        == [[1024], [2048, 2048]]
    assert [s.attrs["bucket"] for s in lost] == [512]
    # with another offset the same spans fall elsewhere: the clock matters
    by_hold, lost = _join.pair(found, spans[:3], 1000.0 - 0.110, *TRACE_SPAN)
    assert [[s.attrs["bucket"] for s in mine] for mine in by_hold] \
        == [[], [1024, 2048]]
    assert lost == []           # the third now ends after the traced part
    ctx = _ctx(spans[3:4], trace=_trace(MODULES), trace_span=TRACE_SPAN)
    _read("join_hold_dev_pct.tput")(ctx)
    said = capsys.readouterr().out
    assert "0 prefill spans paired, 2 holds without a span, 1 spans inside " \
        "the traced part without a hold" in said
    assert "hold without a span: 80.0 ms at 0.0280 s, 2 join programs" in said
    assert "span without a hold: 20.0 ms, attributes {'slot': 3" in said


# ------------------------------------------------------- the reading time
def test_a_traced_runs_worth_reads_in_under_a_second(capsys):
    """2,000 decode executions, 200 join programs and 200 spans: ten times
    what a 4 s trace of the cell with most joins holds."""
    modules, spans, t = [], [], 0.0
    for k in range(2000):
        modules.append(_mod("decode_paged", t, 14))
        t += 14.05
        if k % 20 == 10:
            spans.append(_join_span(_epoch_ms(t - 10), 90, 1024 << (k % 3),
                                    70))
            modules.append(_mod("prefill", t, 70))
            modules.append(_mod("insert_paged", t + 70.5, 2))
            t += 80
    assert len(spans) == 100
    spans += [_join_span(_epoch_ms(40_000 + 10 * k), 90, 1024, 70)
              for k in range(100)]           # outside the traced part
    admits = [_span("loop_admit", _epoch_ms(14 * k), 0.05, joined=0)
              for k in range(2000)]
    ctx = _ctx(spans + admits, trace=_trace(modules),
               trace_span=(0.0, t / 1e3))
    t0 = time.perf_counter()
    share = _read("join_hold_dev_pct.tput")(ctx)
    for name in ("prefill_stall_pct", "join_ms_per_ktok",
                 "join_fetch_share_pct", "join_max_ms",
                 "joins_per_admit_mean"):
        assert _read(name + ".tput")(ctx) is not None
    took = time.perf_counter() - t0
    assert took < 1.0, took
    assert share == pytest.approx(100.0 * 100 * 80.05 / t, rel=1e-6)
    assert "100 holds" in capsys.readouterr().out
