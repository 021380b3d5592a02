"""The join, read where the program measures it: the request's ``prefill``
span (``GenerationPipeline._start_request``: slot, tokens, ``stalled_slots``
and, since PR 40, ``bucket``, ``inflight``, ``step`` and the three parts
``dispatch_us`` / ``insert_us`` / ``fetch_us``), span ``loop_admit``
(``joined`` and, on a pass that joined, ``free`` and ``queued``), and beside
them the device's own account: the module executions of the device plane.

Every reader returns a number whenever the names it reads exist and None only
on a program that does not write them (the metric is then left out), and says
how long it took to read.
"""
from __future__ import annotations

import bisect
import functools
import re
import statistics
import time

from perfbench import harness
from perfbench.layer_metrics._shared import DECODE_MODULE

#: the programs of a join on the device plane's ``XLA Modules`` line
JOIN_MODULES = (r"^jit__prefill", r"^jit__insert")
PARTS = ("dispatch_us", "insert_us", "fetch_us")


def _timed(fn):
    @functools.wraps(fn)
    def read(ctx):
        t0 = time.perf_counter()
        try:
            return fn(ctx)
        finally:
            harness.say(f"reader {fn.__name__}: "
                        f"{time.perf_counter() - t0:.3f} s")
    return read


def _joins(ctx, needs=()):
    """The window's ``prefill`` spans that carry every attribute of
    ``needs`` (none on a program older than the attribute)."""
    return [s for s in ctx["spans"] if s.name == "prefill"
            and all(k in (s.attrs or {}) for k in needs)]


def _parts_ms(attrs):
    return "/".join(f"{attrs[k] / 1e3:.1f}" for k in PARTS) \
        if all(k in attrs for k in PARTS) else "-"


# ------------------------------------------------------ the program's spans
@_timed
def join_ms_per_ktok(ctx):
    """Milliseconds of the window's joins for each 1,024 tokens of the
    buckets their prefill programs ran at: one number across the mix's
    buckets."""
    mine = _joins(ctx, ("bucket",))
    if not mine:
        return None
    return sum(s.dur_us for s in mine) / 1e3 \
        / sum(s.attrs["bucket"] for s in mine) * 1024.0


@_timed
def join_fetch_share_pct(ctx):
    """The part of the joins' seconds spent waiting for the first token (the
    chip's part: the prefill program and the decode step in flight before
    it); the rest is the host's dispatch and insert."""
    mine = _joins(ctx, PARTS)
    whole = sum(s.dur_us for s in mine)
    if not mine or whole <= 0:
        return None
    harness.say("joins by part: " + ", ".join(
        f"{k[:-3]} {sum(s.attrs[k] for s in mine) / 1e6:.3f} s"
        for k in PARTS) + f" of {whole / 1e6:.3f} s in {len(mine)} joins")
    return 100.0 * sum(s.attrs["fetch_us"] for s in mine) / whole


@_timed
def join_max_ms(ctx):
    """The window's longest join; what it was goes to the log."""
    mine = _joins(ctx)
    if not mine:
        return None
    top = max(mine, key=lambda s: s.dur_us)
    a = top.attrs or {}
    t_w0 = ctx["window"][0]
    harness.say(f"longest join: {top.dur_us / 1e3:.1f} ms at "
                f"{top.ts_us / 1e6 - t_w0:.1f} s into the window: bucket "
                f"{a.get('bucket', '-')} slot {a.get('slot', '-')} tokens "
                f"{a.get('tokens', '-')} inflight {a.get('inflight', '-')} "
                f"step {a.get('step', '-')} dispatch/insert/fetch "
                f"{_parts_ms(a)} ms; median of {len(mine)} joins "
                f"{statistics.median(s.dur_us for s in mine) / 1e3:.1f} ms")
    return top.dur_us / 1e3


@_timed
def joins_per_admit_mean(ctx):
    """Mean ``joined`` over the window's ``loop_admit`` spans that joined at
    least one request: whether joiners ever meet at one boundary. 0.0 where
    the loop ran and nobody joined."""
    admits = [s.attrs for s in ctx["spans"] if s.name == "loop_admit"
              and "joined" in (s.attrs or {})]
    if not admits:
        return None
    joining = [a for a in admits if a["joined"] >= 1]
    if not joining:
        return 0.0
    said = f"{len(joining)} of {len(admits)} passes joined"
    for key in ("free", "queued"):
        seen = [a[key] for a in joining if key in a]
        if seen:
            said += f"; {key} mean {sum(seen) / len(seen):.2f}, " \
                    f"most {max(seen)}"
    by_n = {}
    for a in joining:
        by_n[a["joined"]] = by_n.get(a["joined"], 0) + 1
    harness.say(f"joins at one boundary: {said}; passes by joined: "
                + ", ".join(f"{n}: {c}" for n, c in sorted(by_n.items())))
    return sum(a["joined"] for a in joining) / len(joining)


# --------------------------------------------------------- the device plane
def holds(modules):
    """[(start s, end s, join programs' own device seconds, how many)] from
    one chip's module executions ``[(name, start s, end s)]``: the intervals
    between the end of one execution of the decode program and the start of
    the next that contain at least one execution of a join program. None
    where the decode program never ran. One sweep in start order."""
    decode = re.compile(DECODE_MODULE)
    join = re.compile("|".join(JOIN_MODULES))
    out, prev_end, own, n = [], None, 0.0, 0
    for name, s, e in sorted(modules, key=lambda m: m[1]):
        if decode.search(name):
            if prev_end is not None and n:
                out.append((prev_end, s, own, n))
            prev_end, own, n = e, 0.0, 0
        elif prev_end is not None and join.search(name):
            own += e - s
            n += 1
    return out if prev_end is not None else None


def pair(found, spans, offset, lo, hi):
    """Each ``prefill`` span, moved onto the profile's clock (epoch seconds
    less ``offset``), with the hold it overlaps longest. Returns
    ``(by_hold, lost)``: for every hold the spans that fell to it, and the
    spans lying inside the traced part ``[lo, hi]`` that overlap no hold."""
    by_hold, lost = [[] for _ in found], []
    starts = [h[0] for h in found]
    for sp in sorted(spans, key=lambda s: s.ts_us):
        s = sp.ts_us / 1e6 - offset
        e = s + sp.dur_us / 1e6
        best, k = 0.0, None
        # holds are disjoint and sorted: the last that starts before the
        # span ends, and those before it while they still reach the span
        i = bisect.bisect_left(starts, e) - 1
        while i >= 0 and found[i][1] > s:
            over = min(e, found[i][1]) - max(s, found[i][0])
            if over > best:
                best, k = over, i
            i -= 1
        if k is not None:
            by_hold[k].append(sp)
        elif lo <= s and e <= hi:
            lost.append(sp)
    return by_hold, lost


def _say_table(found, by_hold, lost, offset, span_s):
    """One table a traced run, by bucket: joins, the host span's median, the
    device hold's median, the join programs' own device time, by how much
    the span starts before its hold (the step it drains) and ends before it
    (the insert's tail and the next step's dispatch), inflight."""
    rows = {}
    for hold, mine in zip(found, by_hold):
        for sp in mine:
            a = sp.attrs or {}
            start = sp.ts_us / 1e6 - offset
            rows.setdefault(a.get("bucket"), []).append((
                sp.dur_us / 1e3, 1e3 * (hold[1] - hold[0]),
                1e3 * hold[2] / len(mine), a.get("inflight"),
                1e3 * (hold[0] - start),
                1e3 * (hold[1] - start) - sp.dur_us / 1e3))
    empty = [h for h, mine in zip(found, by_hold) if not mine]
    harness.say(f"joins on the device plane: {len(found)} holds, "
                f"{sum(h[1] - h[0] for h in found):.4f} s of {span_s:.4f} s "
                f"traced, {sum(h[2] for h in found):.4f} s of it the join "
                f"programs' own; {sum(map(len, by_hold))} prefill spans "
                f"paired, {len(empty)} holds without a span, {len(lost)} "
                f"spans inside the traced part without a hold")
    for bucket, joins in sorted(rows.items(),
                                key=lambda kv: (kv[0] is None, kv[0])):
        host, dev, own, infl, lead, tail = zip(*joins)
        seen = [i for i in infl if i is not None]
        harness.say(
            f"  bucket {'-' if bucket is None else bucket}: {len(host)} "
            f"joins, host span median {statistics.median(host):.1f} ms, "
            f"device hold median {statistics.median(dev):.1f} ms, join "
            f"programs {statistics.median(own):.1f} ms a join, span starts "
            f"{statistics.median(lead):.1f} ms before its hold and ends "
            f"{statistics.median(tail):.1f} ms before it, inflight "
            + (f"{sum(seen) / len(seen):.2f}" if seen else "-"))
    for h in empty:
        harness.say(f"  hold without a span: {1e3 * (h[1] - h[0]):.1f} ms "
                    f"at {h[0]:.4f} s, {h[3]} join programs")
    for sp in lost:
        harness.say(f"  span without a hold: {sp.dur_us / 1e3:.1f} ms, "
                    f"attributes {sp.attrs}")


@_timed
def join_hold_dev_pct(ctx):
    """Share of the traced span in which the decode program stood still
    around a join on the first chip: from the end of one of its executions
    to the start of the next, with a prefill or insert program between. 0.0
    where the decode program ran and no join fell into the traced part."""
    tr = ctx.get("trace")
    if tr is None or not tr.devices:
        return None
    found = holds(tr.devices[0].modules)
    lo, hi = ctx["trace_span"]
    if found is None or hi <= lo:
        return None
    if tr.clock_offset is not None:
        by_hold, lost = pair(found, _joins(ctx), tr.clock_offset, lo, hi)
        _say_table(found, by_hold, lost, tr.clock_offset, hi - lo)
    return 100.0 * sum(h[1] - h[0] for h in found) / (hi - lo)
