"""The scopes ``models/hybrid.py`` writes for its two grouped-query kinds
(PR 43: the full kind's ``gqa_proj``, ``gqa_rope``, ``gqa_attend``; the
window kind's ``swa_proj``, ``swa_rope``, ``swa_attend`` and ``swa_write``,
the ring's row under ``kv_write``; ``attn_gate``, the output gate, inside
either kind's ``*_proj``), read as ``_nemotron.py`` reads its own names: the
first chip's plane of the traced run's ``.xplane.pb``, every operation of the
decode program under the KIND whose names lie on its ``op_name`` path
(``gqa`` | ``swa`` | None), the innermost of ``NAMES`` there and its scope of
the fixed vocabulary. In a decode program of this family the pages belong to
the full kind alone, so an operation under ``kv_write`` or ``kv_gather`` and
under no kind's name is the full kind's page write (or, off the kernel's
path, its gather). Also the two attributes a decode step's span carries for
the cache (``window_rows``, ``cache_bytes``). Every function returns None
where the program wrote no such name or attribute (a program older than
them): the metric is then left out.
"""
from __future__ import annotations

import glob
import os
import re

from perfbench import harness, trace as ptrace
from perfbench.layer_metrics import _named
from perfbench.layer_metrics._shared import DECODE_MODULE

FULL = ("gqa_proj", "gqa_rope", "gqa_attend")
WINDOW = ("swa_proj", "swa_rope", "swa_attend", "swa_write")
NAMES = frozenset(FULL + WINDOW + ("attn_gate",))
PAGES = ("kv_write", "kv_gather")


def names_of(tf_op):
    """(kind, innermost name of ``NAMES``) of an operation's ``op_name``
    path, transformation wrappers stripped as ``_named.scope_of`` strips
    them; (None, None) where no segment is one of ``NAMES``."""
    kind = inner = None
    for seg in reversed((tf_op or "").rstrip(":").split("/")):
        while True:
            m = _named._WRAPPED.match(seg)
            if not m or m.group(1) in ("jit", "pjit"):
                break
            seg = m.group(2)
        if seg in NAMES:
            inner = inner or seg
            if seg in FULL:
                kind = kind or "gqa"
            elif seg in WINDOW:
                kind = kind or "swa"
    return kind, inner


def decode_seconds_by_names(path):
    """{(kind, inner name, scope): device seconds} over the decode program's
    operations on the first chip; None where the file has no device
    plane."""
    planes = sorted(_named.read_planes(path, wanted=("/device:TPU:",)),
                    key=lambda p: p.name)
    if not planes:
        return None
    plane, programs, acc = planes[0], {}, {}
    rx = re.compile(DECODE_MODULE)
    for name, _stats in plane.meta.values():
        m = re.match(r"^(.*)\((-?\d+)\)$", name)
        if m:
            programs[int(m.group(2)) % 2**64] = m.group(1)
    for _lid, lname, evs in plane.lines:
        if lname != ptrace.OPS_LINE:
            continue
        for mid, s, e in evs:
            _text, stats = plane.meta.get(mid, ("", {}))
            pid = stats.get("program_id")
            prog = programs.get(pid % 2**64 if isinstance(pid, int) else pid,
                                "?")
            if rx.search(prog):
                tf_op = stats.get("tf_op")
                key = (*names_of(tf_op), _named.scope_of(tf_op))
                acc[key] = acc.get(key, 0.0) + (e - s)
    return acc


def decode_seconds(ctx):
    """The traced run's ``decode_seconds_by_names``; None without a trace,
    or where no operation carries a name of ``NAMES``."""
    if "_laguna" not in ctx:
        files = sorted(glob.glob(os.path.join(
            harness.work_dir(ctx["cell"]), "plugins", "profile", "*",
            "*.xplane.pb")))
        acc = decode_seconds_by_names(files[-1]) if files else None
        if not acc or not any(inner for _k, inner, _s in acc):
            acc = None
        else:
            whole = sum(acc.values())
            by = {}
            for (kind, inner, scope), t in acc.items():
                if inner or scope in PAGES:
                    name = inner or f"{scope} (pages)"
                    by[name] = by.get(name, 0.0) + t
            harness.say("device seconds of the decode program under the "
                        "grouped-query kinds' scopes: " + ", ".join(
                            f"{k} {v:.4f} ({100 * v / whole:.1f}%)"
                            for k, v in sorted(by.items(),
                                               key=lambda kv: -kv[1])))
        ctx["_laguna"] = acc
    return ctx["_laguna"]


def _mine(key, kind, inner):
    """Whether an operation's (kind, inner, scope) is of ``kind`` (``gqa``
    owns the pages' own operations too) and, where ``inner`` is given, under
    one of those names."""
    k, i, scope = key
    pages = kind == "gqa" and k is None and scope in PAGES
    if inner is None:
        return k == kind or pages
    return (k == kind and i in inner) or pages


def seconds(ctx, kind, inner=None):
    acc = decode_seconds(ctx)
    if not acc:
        return None, None
    return sum(t for key, t in acc.items() if _mine(key, kind, inner)), \
        sum(acc.values())


def share_pct(ctx, kind):
    """Device seconds of the decode program's operations of one kind over
    those of all its operations, in percent."""
    mine, whole = seconds(ctx, kind)
    return None if not whole else 100.0 * mine / whole


def seconds_a_step(ctx, kind, inner):
    """Device seconds one decode step spends under the kind's names given
    (and, for ``gqa``, on the pages): their operations' seconds in the trace
    over the decode program's runs there."""
    mine, _whole = seconds(ctx, kind, inner)
    tr = ctx.get("trace")
    if not mine or tr is None:
        return None
    runs = len(tr.module_durations(DECODE_MODULE))
    return mine / runs if runs else None


def cache_bytes_per_live_token(ctx):
    """Mean, over the window's ``decode_step`` spans, of attribute
    ``cache_bytes`` over attribute ``live_tokens``."""
    vals = [s.attrs["cache_bytes"] / s.attrs["live_tokens"]
            for s in ctx["spans"] if s.name == "decode_step"
            and "cache_bytes" in (s.attrs or {})
            and s.attrs.get("live_tokens")]
    return sum(vals) / len(vals) if vals else None
