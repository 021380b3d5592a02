"""What the program wrote into the profile under its own names: the scope
(``jax.named_scope``) of every device operation, and the program's spans
(``observability.span``, a ``TraceAnnotation`` while a profile runs) on the
host threads - both on the profiler's one clock.

``jax.profiler.ProfileData`` gives an event's name and times but not the
stats of its *metadata*, and an operation's ``op_name`` (where a named scope
appears) is such a stat: ``tf_op``. So this reads the ``.xplane.pb`` itself:
protobuf wire format, the few fields of ``XSpace`` it needs, no import beyond
the standard library. Field numbers are those of ``xplane.proto``
(tsl/profiler/protobuf): XSpace.planes=1; XPlane name=2 lines=3
event_metadata=4 stat_metadata=5; XLine id=1 name=2 timestamp_ns=3 events=4;
XEvent metadata_id=1 offset_ps=2 duration_ps=3; XEventMetadata name=2
stats=5; XStatMetadata name=2; XStat metadata_id=1 uint64=3 int64=4 str=5
ref=7 (a ref names a stat metadata whose name is the string).

Every reader here returns None where the program wrote no such name (a
program older than the spans and scopes): the metric is then left out.
"""
from __future__ import annotations

import bisect
import glob
import os
import re
import statistics

from perfbench import harness, trace as ptrace
from perfbench.layer_metrics._shared import DECODE_MODULE, TRAIN_MODULE

#: the program's scope vocabulary (``models/transformer.py``)
SCOPES = frozenset((
    "embed", "ln", "attn_qkv", "attn_core", "attn_out", "mlp", "head", "loss",
    "optimizer", "cast_params", "kv_write", "kv_gather"))
#: the decode loop's iteration and the spans under it, innermost first: an
#: instant covered by several belongs to the first of them
ITER = "decode_iter"
PHASES = ("token_fetch", "decode_dispatch", "prefill_dispatch",
          "prefill_insert", "decode_step", "loop_admit", "loop_reclaim",
          "loop_sweep", "loop_publish")
#: the runtime's own host event around handing a program to the chip
ENQUEUE = "DoEnqueueProgram"
_SPAN_NAME = re.compile(r"^[a-z][a-z0-9_]*(\.[a-z][a-z0-9_]*)*$")
_WRAPPED = re.compile(r"^(\w+)\((.*)\)$")


# ------------------------------------------------------------ wire format
def _varint(buf, i):
    out = shift = 0
    while True:
        c = buf[i]
        i += 1
        out |= (c & 0x7F) << shift
        if c < 0x80:
            return out, i
        shift += 7


def _fields(buf):
    """(field number, value) of one message: a varint's value, or the bytes
    of a length-delimited or fixed field."""
    i, n = 0, len(buf)
    while i < n:
        key, i = _varint(buf, i)
        wire = key & 7
        if wire == 0:
            val, i = _varint(buf, i)
        elif wire == 2:
            size, i = _varint(buf, i)
            val, i = buf[i:i + size], i + size
        elif wire in (1, 5):
            size = 8 if wire == 1 else 4
            val, i = buf[i:i + size], i + size
        else:
            raise ValueError(f"wire type {wire} at byte {i}")
        yield key >> 3, val


def _text(buf) -> str:
    return bytes(buf).decode("utf-8", "replace")


def _map_entry(buf):
    key = val = None
    for f, v in _fields(buf):
        if f == 1:
            key = v
        elif f == 2:
            val = v
    return key, val


def _stats(buf_list, stat_names):
    """{stat name: value} of a metadata's XStats."""
    out = {}
    for buf in buf_list:
        name = val = None
        for f, v in _fields(buf):
            if f == 1:
                name = stat_names.get(v)
            elif f == 5:
                val = _text(v)
            elif f == 7:
                val = stat_names.get(v)
            elif f in (3, 4):
                val = v
        if name is not None:
            out[name] = val
    return out


class Plane:
    """One XPlane: ``meta`` {metadata id: (name, {stat: value})} and
    ``lines`` [(line id, line name, [(metadata id, start s, end s)])]."""

    def __init__(self, buf):
        self.name, raw_lines, raw_meta, stat_names = "", [], [], {}
        for f, v in _fields(buf):
            if f == 2:
                self.name = _text(v)
            elif f == 3:
                raw_lines.append(v)
            elif f == 4:
                raw_meta.append(v)
            elif f == 5:
                key, val = _map_entry(v)
                for f2, v2 in _fields(val):
                    if f2 == 2:
                        stat_names[key] = _text(v2)
        self.meta = {}
        for entry in raw_meta:
            key, val = _map_entry(entry)
            name, stats = "", []
            for f, v in _fields(val):
                if f == 2:
                    name = _text(v)
                elif f == 5:
                    stats.append(v)
            self.meta[key] = (name, _stats(stats, stat_names))
        self.lines = []
        for buf in raw_lines:
            lid, lname, t0_ns, events = 0, "", 0, []
            for f, v in _fields(buf):
                if f == 1:
                    lid = v
                elif f == 2:
                    lname = _text(v)
                elif f == 3:
                    t0_ns = v
                elif f == 4:
                    events.append(v)
            evs = []
            for ev in events:
                mid = off_ps = dur_ps = 0
                for f, v in _fields(ev):
                    if f == 1:
                        mid = v
                    elif f == 2:
                        off_ps = v
                    elif f == 3:
                        dur_ps = v
                start = t0_ns * 1e-9 + off_ps * 1e-12
                evs.append((mid, start, start + dur_ps * 1e-12))
            self.lines.append((lid, lname, evs))


def read_planes(path, wanted=("/device:TPU:", "/host:CPU")):
    with open(path, "rb") as f:
        data = memoryview(f.read())
    planes = []
    for f, v in _fields(data):
        if f != 1:
            continue
        # the name comes early in a plane: look before parsing all of it
        name = next((_text(x) for g, x in _fields(v) if g == 2), "")
        if name.startswith(tuple(wanted)):
            planes.append(Plane(v))
    return planes


# ----------------------------------------------------- scopes and programs
def scope_of(tf_op):
    """The innermost scope of the vocabulary on an operation's ``op_name``
    path, transformation wrappers stripped (``transpose(jvp(mlp))`` is
    ``mlp``); a ``jit(...)`` segment names a program, never a scope."""
    if not tf_op:
        return None
    for seg in reversed(tf_op.rstrip(":").split("/")):
        while True:
            m = _WRAPPED.match(seg)
            if not m or m.group(1) in ("jit", "pjit"):
                break
            seg = m.group(2)
        if seg in SCOPES:
            return seg
    return None


class Named:
    """``ops``: per TPU plane, in plane-name order, [(program, scope or None,
    operation's name, start s, end s)] of its ``XLA Ops`` line. ``spans``:
    [(name, start s, end s, thread line id)] of every host event whose name
    has the form of a program span. ``enqueues``: start seconds of the
    runtime's ``DoEnqueueProgram`` events, sorted."""

    def __init__(self, path):
        self.ops, self.spans, self.enqueues = [], [], []
        planes = sorted(read_planes(path), key=lambda p: p.name)
        for plane in planes:
            if plane.name.startswith("/device:TPU:"):
                self.ops.append(self._device_ops(plane))
            else:
                for lid, _lname, evs in plane.lines:
                    for mid, s, e in evs:
                        name = plane.meta.get(mid, ("", {}))[0]
                        if _SPAN_NAME.match(name):
                            self.spans.append((name, s, e, lid))
                        elif name == ENQUEUE:
                            self.enqueues.append(s)
        self.spans.sort(key=lambda x: x[1])
        self.enqueues.sort()

    @staticmethod
    def _device_ops(plane):
        programs = {}          # program id -> module name without the id
        for name, _stats in plane.meta.values():
            m = re.match(r"^(.*)\((-?\d+)\)$", name)
            if m:       # the id is 64 bits, printed signed or not
                programs[int(m.group(2)) % 2**64] = m.group(1)
        out = []
        for _lid, lname, evs in plane.lines:
            if lname != ptrace.OPS_LINE:
                continue
            for mid, s, e in evs:
                text, stats = plane.meta.get(mid, ("", {}))
                pid = stats.get("program_id")
                out.append((programs.get(pid % 2**64 if isinstance(pid, int)
                                         else pid, "?"),
                            scope_of(stats.get("tf_op")),
                            ptrace.op_label(text), s, e))
        return out

    def by_scope(self, program_pattern, chip=0):
        """{scope or None: device seconds} over the operations of the
        programs whose name matches, on one chip; None where that chip ran
        no such program."""
        if chip >= len(self.ops):
            return None
        rx, acc = re.compile(program_pattern), {}
        for prog, scope, _op, s, e in self.ops[chip]:
            if rx.search(prog):
                acc[scope] = acc.get(scope, 0.0) + (e - s)
        return acc or None

    def intervals(self, name):
        return [(s, e) for n, s, e, _lid in self.spans if n == name]


def named(ctx):
    """The traced run's profile, read once a run; None where there is no
    trace file (an untraced run, or nothing was recorded)."""
    if "_named" not in ctx:
        files = sorted(glob.glob(os.path.join(
            harness.work_dir(ctx["cell"]), "plugins", "profile", "*",
            "*.xplane.pb")))
        ctx["_named"] = Named(files[-1]) if files else None
        if ctx["_named"] is not None:
            _say_scope_table(ctx["_named"])
    return ctx["_named"]


def _say_scope_table(nm, programs=4):
    """Device seconds by program and scope on the first chip, for the log
    of every traced run."""
    if not nm.ops:
        return
    table = {}
    for prog, scope, _op, s, e in nm.ops[0]:
        row = table.setdefault(prog, {})
        row[scope] = row.get(scope, 0.0) + (e - s)
    for prog, row in sorted(table.items(),
                            key=lambda kv: -sum(kv[1].values()))[:programs]:
        whole = sum(row.values())
        harness.say(f"device seconds by scope, {prog}: {whole:.4f} s; "
                    + ", ".join(f"{k or 'unscoped'} {v:.4f} "
                                f"({100 * v / whole:.1f}%)" for k, v in
                                sorted(row.items(), key=lambda kv: -kv[1])))


# ---------------------------------------------------------- scope metrics
def scope_share_pct(ctx, program_pattern, scopes):
    """Device seconds of the operations under ``scopes`` over those of the
    whole program, first chip, in percent; None where the program's
    operations carry no scope at all."""
    nm = named(ctx)
    acc = nm.by_scope(program_pattern) if nm is not None else None
    if not acc or set(acc) == {None}:
        return None
    return 100.0 * sum(acc.get(s, 0.0) for s in scopes) / sum(acc.values())


def unscoped_pct(ctx, program_pattern):
    share = scope_share_pct(ctx, program_pattern, (None,))
    if share is not None:
        # the compiler numbers its own operations (copy.388, copy.389):
        # one row for those that differ in the number alone
        rx, acc = re.compile(program_pattern), {}
        for prog, scope, op, s, e in named(ctx).ops[0]:
            if scope is None and rx.search(prog):
                row = acc.setdefault(re.sub(r"\.\d+(?=:|$)", "", op),
                                     [set(), 0.0])
                row[0].add(op)
                row[1] += e - s
        harness.say("largest operations without a scope: " + ", ".join(
            f"{len(ops)} x {kind} {t:.4f} s" for kind, (ops, t) in
            sorted(acc.items(), key=lambda kv: -kv[1][1])[:5]))
    return share


def unscoped_train_pct(ctx):
    return unscoped_pct(ctx, TRAIN_MODULE)


def unscoped_decode_pct(ctx):
    return unscoped_pct(ctx, DECODE_MODULE)


def kv_move_pct(ctx):
    return scope_share_pct(ctx, DECODE_MODULE, ("kv_write", "kv_gather"))


# ----------------------------------------------------------- span metrics
def loop_host_ms_p50(ctx):
    """Median over the window's ``decode_iter`` spans of their duration
    less the ``token_fetch`` spans of the same iteration (its trace): the
    host's own work in one pass of the decode loop."""
    fetch = {}
    for s in ctx["spans"]:
        if s.name == "token_fetch":
            fetch[s.trace_id] = fetch.get(s.trace_id, 0.0) + s.dur_us
    host = [(s.dur_us - fetch.get(s.trace_id, 0.0)) / 1e3
            for s in ctx["spans"] if s.name == ITER]
    return harness.quantile(host, 0.5) if host else None


def prefill_stall_pct(ctx):
    """Seconds of the window's request ``prefill`` spans that ran while
    other slots were active, over the window, in percent."""
    mine = [s for s in ctx["spans"] if s.name == "prefill"
            and "stalled_slots" in (s.attrs or {})]
    if not mine:
        return None
    t_w0, t_w1 = ctx["window"]
    stalled = sum(s.dur_us for s in mine if s.attrs["stalled_slots"] > 0)
    return 100.0 * stalled / 1e6 / (t_w1 - t_w0)


def live_tokens_mean(ctx):
    """Mean ``live_tokens`` of the ``decode_step`` spans inside the traced
    part of the window (epoch time = profiler time + the trace's clock
    offset; attributes stay in the ring), said beside the clients' count."""
    tr = ctx.get("trace")
    if tr is None or tr.clock_offset is None:
        return None
    lo, hi = (1e6 * (t + tr.clock_offset) for t in ctx["trace_span"])
    live = [s.attrs["live_tokens"] for s in ctx["spans"]
            if s.name == "decode_step" and "live_tokens" in (s.attrs or {})
            and lo <= s.ts_us and s.ts_us + s.dur_us <= hi]
    if not live:
        return None
    mean = sum(live) / len(live)
    harness.say(f"live tokens a decode step: {mean:.1f} by the engine's "
                f"positions over {len(live)} steps, "
                f"{ctx.get('live_tokens_mean')} by the clients' records")
    return mean


def _intersect(a, b):
    return ptrace.subtract(a, ptrace.subtract(a, b))


def device_clock_lag(starts, enqueues, most=3e-3):
    """Seconds by which the device plane's times run behind the host
    plane's, or 0.0 where the trace cannot say. The profiler sets the two
    planes against each other only roughly: on the v5e a program shows on the
    device plane 1.2-1.3 ms BEFORE the runtime's host event that enqueues it
    (PERF.md, PR 24). ``starts``: device times at which a program began
    after the chip had been idle, so that it began as soon as it was handed
    over; each is paired with the first enqueue event that follows it within
    ``most``; the median of those distances is the lag (a lower bound: the
    hand-over itself takes some tens of microseconds)."""
    lags = []
    for m in starts:
        k = bisect.bisect_right(enqueues, m)
        if k < len(enqueues) and enqueues[k] - m < most:
            lags.append(enqueues[k] - m)
    return statistics.median(lags) if lags else 0.0


def idle_by_phase(idle, nm):
    """[(phase, seconds)] of the idle intervals: each instant belongs to the
    innermost span of the decode loop covering it, then to the iteration's
    own time, then to no iteration."""
    out, rest = [], ptrace.union(idle)
    for name in PHASES + (ITER,):
        iv = nm.intervals(name)
        out.append((name if name != ITER else ITER + " (no phase)",
                    ptrace.total(_intersect(rest, iv))))
        rest = ptrace.subtract(rest, iv)
    out.append(("outside " + ITER, ptrace.total(rest)))
    return out


def idle_named_pct(ctx):
    """Share of the first chip's idle seconds inside the traced span that
    lie inside a phase of the decode loop, spans taken from the profile's
    host plane; the split by phase goes to the log."""
    tr, nm = ctx.get("trace"), named(ctx)
    if tr is None or not tr.devices or nm is None \
            or not nm.intervals(ITER):
        return None
    lo, hi = ctx["trace_span"]
    idle = ptrace.gaps(tr.devices[0].busy(lo, hi), lo, hi)
    whole = ptrace.total(idle)
    if whole <= 0:
        return None
    lag = device_clock_lag([e for s, e in idle if e - s > 5e-4 and e < hi],
                           nm.enqueues)
    split = idle_by_phase([(s + lag, e + lag) for s, e in idle], nm)
    harness.say(f"idle seconds of the chip by phase of the decode loop "
                f"(idle {whole:.4f} s of {hi - lo:.4f} s; device times moved "
                f"{1e3 * lag:.3f} ms later, to the runtime's enqueue "
                f"events): " + ", ".join(f"{k} {v:.4f}" for k, v in split))
    return 100.0 * sum(v for k, v in split
                       if k in PHASES) / whole
