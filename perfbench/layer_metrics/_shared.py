"""Readers that several per-layer metrics share. Every reader takes the
run's context - ``cell``, ``device``, ``values`` (this run's end-to-end
numbers), ``spans`` (the program's spans that ended inside the window),
``trace`` (``perfbench.trace.Trace`` or None), ``trace_span``, counts - and
returns a number, or None where it finds nothing to read."""
from __future__ import annotations

import statistics

from perfbench import harness

TRAIN_MODULE = r"^jit_step"
DECODE_MODULE = r"^jit__decode_paged"


def idle_pct(ctx):
    tr = ctx.get("trace")
    if tr is None or not tr.devices:
        return None
    lo, hi = ctx["trace_span"]
    if hi <= lo:
        return None
    return 100.0 * (1.0 - tr.busy_seconds(lo, hi) / (hi - lo))


def compiles_in_window(ctx):
    return float(ctx["compiles_in_window"])


def span_durations_ms(ctx, name, keep=lambda attrs: True):
    return [s.dur_us / 1e3 for s in ctx["spans"]
            if s.name == name and keep(s.attrs or {})]


def span_quantile_ms(ctx, name, q, keep=lambda attrs: True):
    d = span_durations_ms(ctx, name, keep)
    return harness.quantile(d, q) if d else None


def decode_step_p50_ms(ctx):
    return span_quantile_ms(ctx, "decode_step", 0.5)


def module_median_ms(ctx, pattern):
    tr = ctx.get("trace")
    if tr is None:
        return None
    m = tr.module_median(pattern)
    return None if m is None else 1e3 * m


def median(values):
    return statistics.median(values) if values else None
