"""The reduction from a profiler trace to numbers, on the small trace that
``record_trace.py`` recorded on the v5e (``small.xplane.pb``: four runs of a
jitted ``step``) and on made-up intervals."""
import os

import pytest

from perfbench import trace as pt

HERE = os.path.dirname(os.path.abspath(__file__))


@pytest.fixture(scope="module")
def small():
    return pt.Trace.from_file(os.path.join(HERE, "small.xplane.pb"))


def test_interval_arithmetic():
    assert pt.union([(3, 4), (0, 1), (0.5, 2)]) == [(0, 2), (3, 4)]
    assert pt.total([(0, 1), (0.5, 2), (3, 4)]) == 3.0
    assert pt.subtract([(0, 10)], [(1, 2), (4, 6), (9, 12)]) \
        == [(0, 1), (2, 4), (6, 9)]
    assert pt.subtract([(0, 1), (2, 3)], []) == [(0, 1), (2, 3)]
    assert pt.subtract([(1, 2)], [(0, 5)]) == []
    assert pt.gaps([(1, 2), (3, 4)], 0, 5) == [(0, 1), (2, 3), (4, 5)]


def test_recorded_trace_planes_modules_and_ops(small):
    assert [d.name for d in small.devices] == ["/device:TPU:0"]
    dev = small.devices[0]
    assert len(dev.modules) == 4 and len(dev.ops) == 16
    assert all(n.startswith("jit_step(") for n, _s, _e in dev.modules)
    # names are the operations' own, not the whole HLO line
    assert {n for n, _s, _e in dev.ops} == {"copy-start", "copy-done",
                                            "fusion", "fusion.1"}
    # every operation lies inside a run of the program
    for _n, s, e in dev.ops:
        assert any(ms - 1e-9 <= s and e <= me + 1e-9
                   for _m, ms, me in dev.modules)


def test_recorded_trace_busy_idle_and_durations(small):
    lo, hi = small.span()
    busy = small.busy_seconds(lo, hi)
    by_hand = sum(e - s for _n, s, e in small.devices[0].ops)
    assert busy == pytest.approx(by_hand, rel=1e-6)    # no two ops overlap
    assert 0 < busy < hi - lo
    idle_pct = 100.0 * (1 - busy / (hi - lo))
    assert 99.0 < idle_pct < 100.0      # four 2-us programs in 13 ms
    durs = small.module_durations(r"^jit_step")
    assert len(durs) == 4 and all(1e-6 < d < 1e-5 for d in durs)
    assert small.module_median(r"^jit_step") == pytest.approx(
        sorted(durs)[1] / 2 + sorted(durs)[2] / 2)
    assert small.module_median(r"^jit_nothing") is None
    top = small.top_ops(3)
    assert top[0][0].startswith("fusion") and top[0][1] >= top[1][1]
    gaps = small.idle_gaps(lo, hi, 3)
    assert len(gaps) == 3 and gaps[0][1] - gaps[0][0] > 1e-3


def test_recorded_trace_carries_the_benchmarks_marks(small):
    marks = [e for e in small.host_events if e[0] == "perfbench_mark"]
    assert len(marks) == 2 and marks[0][1] < small.span()[0] < marks[1][1]


def test_exposed_collective_time_on_made_up_events():
    """A chip's step of 10: an all-reduce of 2 hidden behind a fusion, an
    all-gather of 1 with nothing beside it, a collective-permute half
    covered. Exposed: 0 + 1 + 0.5 of 10."""
    ops = [("fusion.1", 0.0, 4.0), ("all-reduce.3", 1.0, 3.0),
           ("all-gather.1", 4.0, 5.0), ("fusion.2", 5.0, 8.5),
           ("collective-permute-start.2", 8.0, 9.0), ("copy.4", 9.0, 10.0)]
    quiet = pt.DeviceTrace("/device:TPU:0", [("jit_step(1)", 0.0, 10.0)],
                           [o for o in ops if "all-" not in o[0]
                            and "collective" not in o[0]])
    busy = pt.DeviceTrace("/device:TPU:1", [("jit_step(1)", 0.0, 10.0)], ops)
    tr = pt.Trace([quiet, busy], [])
    assert tr.exposed_collective_share(r"^jit_step") == pytest.approx(15.0)
    assert pt.Trace([quiet], []).exposed_collective_share(r"^jit_step") \
        is None
    assert tr.busy_seconds(0.0, 10.0) == pytest.approx((8.5 + 10.0) / 2)


def test_op_names_from_hlo_lines():
    line = ("%all-reduce.7 = f32[1280]{0:T(1024)} all-reduce(f32[1280]{0} "
            "%x), replica_groups={{0,1}}")
    assert pt.op_name(line) == "all-reduce.7"
    assert pt.COLLECTIVE.match(pt.op_name(line))
    assert pt.op_label(line) == "all-reduce.7:f32[1280]"
    assert pt.op_name("fusion.1") == "fusion.1"


def test_recorded_four_chip_trace_has_an_exposed_all_reduce():
    """``small4.xplane.pb``: the same program over four chips of a v5e 2x2
    (``record_trace.py`` on four devices), where the sum crosses chips."""
    tr = pt.Trace.from_file(os.path.join(HERE, "small4.xplane.pb"))
    assert [d.name for d in tr.devices] == [f"/device:TPU:{i}"
                                            for i in range(4)]
    for dev in tr.devices:
        assert len(dev.modules) == 4 and len(dev.ops) == 20
        assert sum(bool(pt.COLLECTIVE.match(n)) for n, _s, _e in dev.ops) == 4
    share = tr.exposed_collective_share(r"^jit_step")
    # nothing else runs beside the all-reduce in so small a program, so its
    # whole device time is exposed; by hand on the worst chip:
    by_hand = max(
        100.0 * sum(e - s for n, s, e in d.ops if n == "all-reduce")
        / sum(e - s for _n, s, e in d.modules) for d in tr.devices)
    assert share == pytest.approx(by_hand, rel=1e-6) and 5.0 < share < 100.0
    lo, hi = tr.span()
    per_chip = [pt.total(d.busy(lo, hi)) for d in tr.devices]
    assert tr.busy_seconds(lo, hi) == pytest.approx(sum(per_chip) / 4)
