"""The scopes ``models/hybrid.py`` writes for the layers that own no cache
and read another layer's (PR 46: ``xattn_proj`` and ``xattn_attend``, the
query-only attention over the ONE paged layer's rows; ``gmu``, the gated
memory unit on an earlier layer's scan output; ``attn_diff``, the
differential subtraction and its sub-norm, inside whichever kind's
``*_attend`` it belongs to and so counted with it), read as ``_nemotron.py``
reads its own names: the first chip's plane of the traced run's
``.xplane.pb``, every operation of the decode program under the innermost of
``NAMES`` on its ``op_name`` path. Every function returns None where the
program wrote no such name (a program older than them): the metric is then
left out.
"""
from __future__ import annotations

import glob
import os
import re

from perfbench import harness, trace as ptrace
from perfbench.layer_metrics import _named
from perfbench.layer_metrics._shared import DECODE_MODULE

CROSS = ("xattn_proj", "xattn_attend")
NAMES = frozenset(CROSS + ("gmu",))
#: written by the program inside another name and counted with it, here and
#: by ``_laguna.py``: no reader resolves it
NESTED = frozenset(("attn_diff",))


def inner_of(tf_op):
    """The innermost name of ``NAMES`` on an operation's ``op_name`` path,
    transformation wrappers stripped as ``_named.scope_of`` strips them."""
    if not tf_op:
        return None
    for seg in reversed(tf_op.rstrip(":").split("/")):
        while True:
            m = _named._WRAPPED.match(seg)
            if not m or m.group(1) in ("jit", "pjit"):
                break
            seg = m.group(2)
        if seg in NAMES:
            return seg
    return None


def decode_seconds_by_names(path):
    """{inner name or None: device seconds} over the decode program's
    operations on the first chip; None where the file has no device plane."""
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
                key = inner_of(stats.get("tf_op"))
                acc[key] = acc.get(key, 0.0) + (e - s)
    return acc


def decode_seconds(ctx):
    """The traced run's ``decode_seconds_by_names``; None without a trace,
    or where no operation carries a name of ``NAMES``."""
    if "_phi4flash" not in ctx:
        files = sorted(glob.glob(os.path.join(
            harness.work_dir(ctx["cell"]), "plugins", "profile", "*",
            "*.xplane.pb")))
        acc = decode_seconds_by_names(files[-1]) if files else None
        if not acc or not any(acc):
            acc = None
        else:
            whole = sum(acc.values())
            harness.say("device seconds of the decode program under the "
                        "query-only attention's and the memory unit's "
                        "scopes: " + ", ".join(
                            f"{k} {v:.4f} ({100 * v / whole:.1f}%)"
                            for k, v in sorted(acc.items(),
                                               key=lambda kv: -kv[1]) if k))
        ctx["_phi4flash"] = acc
    return ctx["_phi4flash"]


def share_pct(ctx, names):
    acc = decode_seconds(ctx)
    if not acc:
        return None
    return 100.0 * sum(acc.get(n, 0.0) for n in names) / sum(acc.values())


def seconds_a_step(ctx, names):
    """Device seconds one decode step spends under the names given: their
    operations' seconds in the trace over the decode program's runs there."""
    acc, tr = decode_seconds(ctx), ctx.get("trace")
    if not acc or tr is None:
        return None
    runs = len(tr.module_durations(DECODE_MODULE))
    t = sum(acc.get(n, 0.0) for n in names)
    return t / runs if runs and t > 0 else None
