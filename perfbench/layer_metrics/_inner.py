"""The scopes a model writes INSIDE the fixed vocabulary of ``_named.py``
(``models/hybrid.py``, ``parallel/moe.py``: ``kda_*``, ``mla_*``, ``moe_*``),
and the counts a decode step hands back as attributes of its span.

``_named.Named`` keeps an operation's scope of the fixed vocabulary but not
its ``op_name`` path, so this reads the first chip's plane of the same
``.xplane.pb`` once more (device planes only) and resolves, for every
operation, the innermost name of ``INNER`` on its path beside the scope of
the vocabulary (and ``moe_experts`` for the grouped-matmul kernel, which
carries no path). Every reader returns None where the program wrote no such
name or attribute (a program older than them): the metric is then left out.
"""
from __future__ import annotations

import glob
import os
import re

from perfbench import harness, trace as ptrace
from perfbench.layer_metrics import _named
from perfbench.layer_metrics._shared import DECODE_MODULE

INNER = frozenset((
    "kda_proj", "kda_conv", "kda_state", "kda_out", "mla_proj", "mla_attend",
    "moe_route", "moe_experts", "moe_shared", "moe_combine"))
MOE = ("moe_route", "moe_experts", "moe_shared", "moe_combine")
#: the chip's grouped-matmul kernel, which the compiler makes out of
#: ``lax.ragged_dot``: a custom call named ``ragged-dot...`` that carries no
#: ``op_name`` (my chip run, PR 27), so it is taken by its name
GROUPED_KERNEL = "ragged-dot"


def inner_of(tf_op):
    """The innermost name of ``INNER`` on an operation's ``op_name`` path,
    transformation wrappers stripped as ``_named.scope_of`` strips them."""
    if not tf_op:
        return None
    for seg in reversed(tf_op.rstrip(":").split("/")):
        while True:
            m = _named._WRAPPED.match(seg)
            if not m or m.group(1) in ("jit", "pjit"):
                break
            seg = m.group(2)
        if seg in INNER:
            return seg
    return None


def seconds_by_names(path):
    """{(program, inner name or None, scope or None): device seconds} over
    the first chip's operations; None where the file has no device plane."""
    planes = sorted(_named.read_planes(path, wanted=("/device:TPU:",)),
                    key=lambda p: p.name)
    if not planes:
        return None
    plane, programs, acc = planes[0], {}, {}
    for name, _stats in plane.meta.values():
        m = re.match(r"^(.*)\((-?\d+)\)$", name)
        if m:
            programs[int(m.group(2)) % 2**64] = m.group(1)
    for _lid, lname, evs in plane.lines:
        if lname != ptrace.OPS_LINE:
            continue
        for mid, s, e in evs:
            text, stats = plane.meta.get(mid, ("", {}))
            pid = stats.get("program_id")
            prog = programs.get(pid % 2**64 if isinstance(pid, int) else pid,
                                "?")
            tf_op = stats.get("tf_op")
            inner = inner_of(tf_op)
            if inner is None and ptrace.op_label(text).startswith(
                    GROUPED_KERNEL):
                inner = "moe_experts"
            key = (prog, inner, _named.scope_of(tf_op))
            acc[key] = acc.get(key, 0.0) + (e - s)
    return acc


def decode_seconds(ctx):
    """{(inner, scope): seconds} of the decode program's operations in the
    traced run; None without a trace, or where no operation of it carries a
    name of ``INNER``."""
    if "_inner" not in ctx:
        files = sorted(glob.glob(os.path.join(
            harness.work_dir(ctx["cell"]), "plugins", "profile", "*",
            "*.xplane.pb")))
        acc = seconds_by_names(files[-1]) if files else None
        rx, mine = re.compile(DECODE_MODULE), {}
        for (prog, inner, scope), t in (acc or {}).items():
            if rx.search(prog):
                mine[(inner, scope)] = mine.get((inner, scope), 0.0) + t
        if not any(inner for inner, _scope in mine):
            mine = None
        else:
            by_inner = {}
            for (inner, _scope), t in mine.items():
                by_inner[inner] = by_inner.get(inner, 0.0) + t
            whole = sum(by_inner.values())
            harness.say("device seconds of the decode program by inner "
                        "scope: " + ", ".join(
                            f"{k or 'none'} {v:.4f} ({100 * v / whole:.1f}%)"
                            for k, v in sorted(by_inner.items(),
                                               key=lambda kv: -kv[1])))
        ctx["_inner"] = mine
    return ctx["_inner"]


def _sum(acc, inner=(), scopes=()):
    return sum(t for (i, s), t in acc.items() if i in inner or s in scopes)


def share_pct(ctx, inner):
    acc = decode_seconds(ctx)
    if not acc:
        return None
    return 100.0 * _sum(acc, inner) / sum(acc.values())


def seconds_a_step(ctx, inner=(), scopes=()):
    """Device seconds one decode step spends under the names given: their
    operations' seconds in the trace over the decode program's runs there."""
    acc, tr = decode_seconds(ctx), ctx.get("trace")
    if not acc or tr is None:
        return None
    runs = len(tr.module_durations(DECODE_MODULE))
    t = _sum(acc, inner, scopes)
    return t / runs if runs and t > 0 else None


def step_attr_mean(ctx, attr):
    """Mean of an attribute of the ``decode_step`` spans inside the traced
    part of the window (as ``_named.live_tokens_mean`` reads its own)."""
    tr = ctx.get("trace")
    if tr is None or tr.clock_offset is None:
        return None
    lo, hi = (1e6 * (t + tr.clock_offset) for t in ctx["trace_span"])
    vals = [s.attrs[attr] for s in ctx["spans"]
            if s.name == "decode_step" and attr in (s.attrs or {})
            and lo <= s.ts_us and s.ts_us + s.dur_us <= hi]
    return sum(vals) / len(vals) if vals else None


def roofline_pct(ctx, name, step_bytes, seconds):
    """``step_bytes`` over the chip's peak bytes a second, over ``seconds``,
    in percent; None where either is missing."""
    if step_bytes is None or not seconds:
        return None
    least = step_bytes / harness.peaks(ctx["device"]["kind"])[
        "hbm_bytes_per_s"]
    harness.say(f"{name}: least {1e3 * least:.3f} ms ({step_bytes / 1e9:.3f}"
                f" GB at the peak) over device {1e3 * seconds:.3f} ms a step")
    return 100.0 * least / seconds
