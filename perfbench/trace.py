"""From a profiler trace to numbers: device busy and idle time, the duration
of a jitted program and of single operations, exposed collective time.

Reads the ``.xplane.pb`` that ``jax.profiler`` writes with
``jax.profiler.ProfileData`` and nothing else. A TPU chip is a plane named
``/device:TPU:<n>``; its line ``XLA Modules`` holds one event for every run
of a compiled program and ``XLA Ops`` one for every operation inside it,
each with a start and a duration in nanoseconds on the profiler's clock.
The interval arithmetic is plain functions over (start, end) pairs, checked
in ``perfbench/tests`` on a recorded trace and on made-up intervals.
"""
from __future__ import annotations

import glob
import os
import re
import statistics
import time

MODULE_LINE = "XLA Modules"
OPS_LINE = "XLA Ops"
COLLECTIVE = re.compile(
    r"^(all-reduce|all-gather|reduce-scatter|collective-permute|all-to-all)")


def op_name(text: str) -> str:
    """The operation's own name: the profiler gives an operation's whole HLO
    line (``%fusion.285 = bf16[50257,1024]{...} fusion(...)``)."""
    return text.split(" = ", 1)[0].lstrip("%")


def op_label(text: str) -> str:
    """Name and result type, short enough for a breakdown line."""
    name, _, rest = text.partition(" = ")
    shape = rest.split("{", 1)[0].split(" ", 1)[0]
    shape = shape.strip("(,")
    return (name.lstrip("%") + (":" + shape if shape else ""))[:64]


# ----------------------------------------------------- interval arithmetic
def union(intervals):
    """Sorted, merged copy of (start, end) intervals."""
    out = []
    for s, e in sorted(intervals):
        if out and s <= out[-1][1]:
            out[-1][1] = max(out[-1][1], e)
        else:
            out.append([s, e])
    return [(s, e) for s, e in out]


def total(intervals) -> float:
    return float(sum(e - s for s, e in union(intervals)))


def subtract(a, b):
    """The parts of the intervals ``a`` that no interval of ``b`` covers."""
    out = []
    b = union(b)
    for s, e in union(a):
        cur = s
        for bs, be in b:
            if be <= cur:
                continue
            if bs >= e:
                break
            if bs > cur:
                out.append((cur, bs))
            cur = max(cur, be)
            if cur >= e:
                break
        if cur < e:
            out.append((cur, e))
    return out


def gaps(intervals, lo, hi):
    """The idle intervals inside [lo, hi] that ``intervals`` leave."""
    return subtract([(lo, hi)], intervals)


# ------------------------------------------------------------ the trace
class DeviceTrace:
    """One chip's events: ``modules`` and ``ops`` as (name, start, end) in
    seconds on the profiler's clock."""

    def __init__(self, name, modules, ops, labels=None):
        self.name, self.modules, self.ops = name, modules, ops
        self.labels = labels or {}

    def busy(self, lo=None, hi=None):
        iv = [(s, e) for _n, s, e in self.ops]
        if lo is not None:
            iv = [(max(s, lo), min(e, hi)) for s, e in iv
                  if e > lo and s < hi]
        return union(iv)


class Trace:
    def __init__(self, devices, host_events):
        self.devices = devices          # [DeviceTrace]
        self.host_events = host_events  # [(name, start_s, end_s)]
        #: seconds to add to a profiler time to get epoch time, where the
        #: recorder's marks were found (``Recorder.load``)
        self.clock_offset = None

    @classmethod
    def from_file(cls, path):
        from jax.profiler import ProfileData
        data = ProfileData.from_file(path)
        devices, host = [], []
        for plane in data.planes:
            if plane.name.startswith("/device:TPU:"):
                mods, ops = [], []
                for line in plane.lines:
                    if line.name not in (MODULE_LINE, OPS_LINE):
                        continue
                    dst = mods if line.name == MODULE_LINE else ops
                    for ev in line.events:
                        s = ev.start_ns * 1e-9
                        dst.append((ev.name, s, s + ev.duration_ns * 1e-9))
                # operations by their own names; the whole line is kept
                # apart for the breakdown's labels
                labels = {op_name(n): op_label(n) for n, _s, _e in ops}
                ops = [(op_name(n), s, e) for n, s, e in ops]
                devices.append(DeviceTrace(plane.name, mods, ops, labels))
            elif plane.name.startswith("/host:"):
                for line in plane.lines:
                    for ev in line.events:
                        if ev.name.startswith("perfbench_"):
                            s = ev.start_ns * 1e-9
                            host.append((ev.name, s,
                                         s + ev.duration_ns * 1e-9))
        devices.sort(key=lambda d: d.name)
        return cls(devices, host)

    @classmethod
    def from_dir(cls, log_dir):
        files = sorted(glob.glob(os.path.join(
            log_dir, "plugins", "profile", "*", "*.xplane.pb")))
        if not files:
            raise FileNotFoundError(f"no .xplane.pb under {log_dir}")
        return cls.from_file(files[-1])

    # ------------------------------------------------------- reductions
    def span(self):
        """(first start, last end) over every device event."""
        starts = [s for d in self.devices for _n, s, _e in d.ops + d.modules]
        ends = [e for d in self.devices for _n, _s, e in d.ops + d.modules]
        return (min(starts), max(ends)) if starts else (0.0, 0.0)

    def busy_seconds(self, lo=None, hi=None) -> float:
        """Seconds in which an operation ran, averaged over the chips."""
        if not self.devices:
            return 0.0
        return sum(total(d.busy(lo, hi)) for d in self.devices) \
            / len(self.devices)

    def module_durations(self, pattern: str):
        """Device seconds of every run of the programs whose name matches,
        on the first chip (every chip runs the same program)."""
        rx = re.compile(pattern)
        if not self.devices:
            return []
        return [e - s for n, s, e in self.devices[0].modules if rx.search(n)]

    def module_median(self, pattern: str):
        d = self.module_durations(pattern)
        return statistics.median(d) if d else None

    def top_ops(self, k=10):
        """The operations that took most device time, summed by name over
        the first chip: [[name and result type, seconds], ...]."""
        if not self.devices:
            return []
        dev, acc = self.devices[0], {}
        for n, s, e in dev.ops:
            acc[n] = acc.get(n, 0.0) + (e - s)
        return [[dev.labels.get(n, n), t] for n, t in
                sorted(acc.items(), key=lambda kv: -kv[1])[:k]]

    def exposed_collective_share(self, module_pattern: str):
        """Worst chip's share of its step time spent in collectives while
        no other operation ran on that chip, in percent; None where the
        trace has no collective or no such module."""
        rx = re.compile(module_pattern)
        worst = None
        for d in self.devices:
            step = sum(e - s for n, s, e in d.modules if rx.search(n))
            coll = [(s, e) for n, s, e in d.ops if COLLECTIVE.match(n)]
            other = [(s, e) for n, s, e in d.ops if not COLLECTIVE.match(n)]
            if not coll or step <= 0:
                continue
            share = 100.0 * total(subtract(coll, other)) / step
            worst = share if worst is None else max(worst, share)
        return worst

    def idle_gaps(self, lo, hi, k=10):
        """The k longest idle intervals of the first chip inside [lo, hi]."""
        if not self.devices:
            return []
        g = gaps(self.devices[0].busy(lo, hi), lo, hi)
        return sorted(g, key=lambda iv: iv[0] - iv[1])[:k]


# ------------------------------------------------ recording, with marks
class Recorder:
    """``jax.profiler`` around a part of the window, with two marks written
    from the benchmark's side: ``perfbench_mark`` annotations whose host
    times (``time.time()``) are kept, so the profiler's clock can be set
    against the program's spans."""

    def __init__(self, log_dir):
        self.log_dir = log_dir
        self.marks = []         # [(epoch seconds at the mark)]
        self.t0 = self.t1 = None

    def _mark(self):
        import jax
        with jax.profiler.TraceAnnotation("perfbench_mark"):
            self.marks.append(time.time())
            time.sleep(0.001)

    def start(self):
        import jax
        opts = jax.profiler.ProfileOptions()
        opts.python_tracer_level = 0
        opts.host_tracer_level = 2
        jax.profiler.start_trace(self.log_dir, profiler_options=opts)
        self.t0 = time.time()
        self._mark()

    def stop(self):
        import jax
        self._mark()
        self.t1 = time.time()
        jax.profiler.stop_trace()

    @property
    def window_s(self):
        return self.t1 - self.t0

    def load(self):
        tr = Trace.from_dir(self.log_dir)
        marks = sorted(s for n, s, _e in tr.host_events
                       if n == "perfbench_mark")
        if marks:
            tr.clock_offset = self.marks[0] - marks[0]
        return tr
