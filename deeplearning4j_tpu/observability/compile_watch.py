"""Compile watch: XLA trace/retrace accounting for the jitted entry points.

Whole-program jit is this stack's performance model — and its silent
failure mode. A signature change (new shape, new dtype, weak-typed leaf,
new sharding) retraces and recompiles the ENTIRE train/output program,
and nothing in a step-time histogram says *why* a step took 40× median:
recompilation storms are the dominant hidden cost when whole programs
compile per shape (Fishman et al. arXiv:1810.09868 make the same
argument for whole-program emission; the PR 1–3 decomposition stops at
time, this module extends it to compile events).

Mechanism — two independent sources, correlated best-effort:

- **Trace probes**: the jitted bodies (``MultiLayerNetwork._train_step``
  / ``_output_jit``, the ``ComputationGraph`` twins — and through them
  the ``ShardedTrainer`` step and every ``ParallelInference`` bucket
  executable) call :func:`note_trace` as their first statement. The body
  only executes while jax TRACES it, so each call is exactly one
  (re)trace of that entry point, and the abstract args carry the
  shape/dtype signature that triggered it. Steady-state cost is zero:
  a cached executable never re-enters the Python body.
- **Compile timing**: a process-wide ``jax.monitoring`` listener
  observes ``backend_compile_duration`` events into
  ``dl4j_compile_seconds`` and attributes each duration to the most
  recent probe (bounded staleness window) — trace counts are exact,
  compile seconds are best-effort global. A compile with NO fresh trace
  (jax recompiles for sharding/layout-only changes without re-entering
  the Python body — the ``ShardedTrainer`` placement path) still lands
  in the ring as an ``(untraced)`` event when a declared cause is
  pending, so mesh re-homing stays visible.

Each event lands in a bounded ring (``compiles.json`` in postmortem
bundles, ``GET /debug/compiles`` live) stamped with the training
iteration count at trace time, which is what makes
:class:`RetraceStormRule` possible: *recompiles* (per-fn events beyond
the fn's first compile) inside the last ``window_steps`` training steps
AND ``window_seconds`` grade degraded/failing on ``/health`` +
``/alerts``. Serving correlates causes: a shape-bucket miss registers a
pending cause via :func:`note_cause`, and the compile it provokes
carries ``cause="bucket_miss"``.

Metrics: ``dl4j_compile_total{fn}``, ``dl4j_compile_seconds``.
Kill switches: ``DL4J_TPU_COMPILE_WATCH=0`` (probes and listener no-op)
under the ``DL4J_TPU_METRICS=0`` master.
"""
from __future__ import annotations

import contextlib
import os
import threading
import time
from collections import deque
from typing import Any, Dict, List, Optional

from deeplearning4j_tpu.observability.registry import (global_registry,
                                                       metrics_enabled,
                                                       on_registry_reset)
from deeplearning4j_tpu.observability.slo import (DEGRADED, FAILING, OK,
                                                  SLORule)

#: retained compile events (a storm of thousands keeps only the tail —
#: the counts survive in dl4j_compile_total either way)
_RING_CAPACITY = 256

#: how long a noted cause (bucket miss, sharded placement) stays eligible
#: to be claimed by the next trace — compiles follow their cause within
#: the same dispatch, so seconds suffice
_CAUSE_TTL_S = 5.0

#: a backend_compile_duration is attributed to the latest probe only if
#: the probe is fresher than this (tracing immediately precedes compile)
_ATTRIBUTION_TTL_S = 120.0


def compile_watch_enabled() -> bool:
    """Kill switch (read per call so tests can flip it; probes only fire
    at trace time, so the per-step cost of the check is zero)."""
    return (metrics_enabled()
            and os.environ.get("DL4J_TPU_COMPILE_WATCH", "1") != "0")


def _signature(trees) -> str:
    """shape/dtype signature of the abstract args that triggered a trace,
    e.g. ``f32[32,784], f32[32,10], None``. Works on tracers (shape and
    dtype are aval attributes) and on concrete arrays alike."""
    import jax

    parts: List[str] = []
    for tree in trees:
        leaves = jax.tree.leaves(tree)
        if not leaves:
            parts.append("None" if tree is None else "{}")
            continue
        for leaf in leaves:
            dt = getattr(leaf, "dtype", None)
            shape = getattr(leaf, "shape", None)
            if dt is None or shape is None:
                parts.append(type(leaf).__name__)
            else:
                name = getattr(dt, "name", str(dt))
                short = (name.replace("float", "f").replace("uint", "u")
                         .replace("int", "i").replace("complex", "c")
                         .replace("bool", "pred"))
                parts.append(f"{short}[{','.join(str(d) for d in shape)}]")
    return ", ".join(parts)


def _current_training_step() -> int:
    """The shared fit-iteration clock the retrace-storm window counts
    against (see train_metrics.total_iterations)."""
    from deeplearning4j_tpu.observability.train_metrics import (
        total_iterations)
    return total_iterations()


def _compile_counter(fn: str):
    """The one registration site for the per-fn compile counter (traced
    and untraced events must land in the SAME series)."""
    return global_registry().counter(
        "dl4j_compile_total",
        "XLA traces (each one compiles a fresh executable) of the "
        "jitted entry points, by function",
        label_names=("fn",)).labels(fn=fn)


class CompileWatch:
    """Bounded ring of trace/compile events + the correlation state.

    One process-wide instance via :func:`global_compile_watch`; tests
    construct their own and pass it to probes explicitly if needed.
    """

    def __init__(self, capacity: int = _RING_CAPACITY):
        self._lock = threading.Lock()
        self._ring: deque = deque(maxlen=capacity)
        self._seen_fns: set = set()      # fns that have compiled ≥once
        self._counts: Dict[str, int] = {}
        self._seq = 0
        self._pending_cause: Optional[Dict[str, Any]] = None
        self._last_trace_mono = 0.0

    # ------------------------------------------------------------ probes
    def note_trace(self, fn: str, *arg_trees, **attrs) -> None:
        """Record one (re)trace of ``fn``. Call from INSIDE the jitted
        body — it executes once per trace, never per cached step."""
        if not compile_watch_enabled():
            return
        sig = _signature(arg_trees)
        now = time.time()
        mono = time.monotonic()
        with self._lock:
            self._seq += 1
            cause = None
            pc = self._pending_cause
            if pc is not None and mono - pc["noted_mono"] <= _CAUSE_TTL_S:
                cause = {k: v for k, v in pc.items() if k != "noted_mono"}
                self._pending_cause = None
            first = fn not in self._seen_fns
            self._seen_fns.add(fn)
            self._counts[fn] = self._counts.get(fn, 0) + 1
            event = {
                "seq": self._seq,
                "fn": fn,
                "signature": sig,
                "unix_ts": now,
                "step": _current_training_step(),
                "first_compile_of_fn": first,
                "compile_seconds": None,   # filled by the duration listener
                "cause": cause,
            }
            if attrs:
                event["attrs"] = {k: (v if isinstance(
                    v, (int, float, bool, str)) or v is None else str(v))
                    for k, v in attrs.items()}
            self._ring.append(event)
            self._last_trace_mono = mono
        _compile_counter(fn).inc()

    def note_cause(self, cause: str, **attrs) -> None:
        """Declare WHY the next trace (within a few seconds) will happen —
        e.g. the serving batcher's shape-bucket miss, or a ShardedTrainer
        re-homing params onto a mesh. Best-effort: claimed by the next
        :meth:`note_trace`, expires unclaimed."""
        if not compile_watch_enabled():
            return
        with self._lock:
            self._pending_cause = {"cause": cause,
                                   "noted_mono": time.monotonic(), **attrs}

    def attribute_duration(self, seconds: float) -> bool:
        """Fold one ``backend_compile_duration`` into the freshest
        unattributed event (tracing immediately precedes its compile).
        Returns False when no recent trace is waiting for a duration."""
        with self._lock:
            if (time.monotonic() - self._last_trace_mono
                    > _ATTRIBUTION_TTL_S):
                return False
            for event in reversed(self._ring):
                if event["compile_seconds"] is None:
                    event["compile_seconds"] = seconds
                    return True
        return False

    def note_untraced_compile(self, seconds: float) -> None:
        """A backend compile fired with NO fresh trace to claim it — on
        this jax a sharding/layout-only change (e.g. ``ShardedTrainer``
        re-homing params onto a mesh) hits the jaxpr cache and recompiles
        the executable WITHOUT re-entering the Python body, so the probes
        stay silent. Recorded into the ring ONLY when a declared cause is
        pending (placement, bucket miss): unscoped process-wide compiles
        (eager ops, other libraries) would otherwise flood the ring and
        poison the storm rule."""
        now = time.time()
        mono = time.monotonic()
        with self._lock:
            pc = self._pending_cause
            if pc is None or mono - pc["noted_mono"] > _CAUSE_TTL_S:
                return
            cause = {k: v for k, v in pc.items() if k != "noted_mono"}
            self._pending_cause = None
            self._seq += 1
            fn = "(untraced)"
            first = fn not in self._seen_fns
            self._seen_fns.add(fn)
            self._counts[fn] = self._counts.get(fn, 0) + 1
            self._ring.append({
                "seq": self._seq,
                "fn": fn,
                "signature": "sharding/layout change (no retrace)",
                "unix_ts": now,
                "step": _current_training_step(),
                "first_compile_of_fn": first,
                "compile_seconds": seconds,
                "cause": cause,
            })
        _compile_counter(fn).inc()

    # ---------------------------------------------------------- queries
    def events(self, limit: Optional[int] = None) -> List[dict]:
        """Retained events, oldest first (``compiles.json`` payload)."""
        with self._lock:
            out = [dict(e) for e in self._ring]
        return out[-limit:] if limit else out

    def counts(self) -> Dict[str, int]:
        with self._lock:
            return dict(self._counts)

    @property
    def total(self) -> int:
        with self._lock:
            return self._seq

    def count_for(self, fn: str) -> int:
        with self._lock:
            return self._counts.get(fn, 0)

    def recompiles_in_window(self, window_steps: int,
                             window_seconds: float) -> List[dict]:
        """RE-compiles (events past each fn's first-ever compile) recent
        on BOTH clocks: within ``window_steps`` of the current training
        iteration count AND ``window_seconds`` of now. A serving-only
        process never advances the step clock (diff 0), so the time
        window alone decays its storms; a training process ages events
        out by steps long before wall time."""
        cur = _current_training_step()
        now = time.time()
        with self._lock:
            return [dict(e) for e in self._ring
                    if not e["first_compile_of_fn"]
                    and cur - e["step"] <= window_steps
                    and now - e["unix_ts"] <= window_seconds]

    def snapshot(self) -> dict:
        """The bundle/endpoint payload."""
        return {
            "enabled": compile_watch_enabled(),
            "total_traces": self.total,
            "by_fn": self.counts(),
            "events": self.events(),
        }

    def clear(self) -> None:
        with self._lock:
            self._ring.clear()
            self._seen_fns.clear()
            self._counts.clear()
            self._seq = 0
            self._pending_cause = None


class RetraceStormRule(SLORule):
    """Retrace storm: recompiles of already-compiled entry points keep
    landing inside the recent step window — shape/signature churn is
    burning accelerator time on the compiler instead of the model.
    First-ever compiles per fn are free (cold start is not a storm)."""

    def __init__(self, name: str = "retrace_storm",
                 window_steps: int = 50, window_seconds: float = 600.0,
                 degraded: Optional[int] = 3, failing: Optional[int] = 8,
                 description: str = ""):
        super().__init__(name, description or
                         f"recompiles in the last {window_steps} steps / "
                         f"{window_seconds:.0f}s")
        self.window_steps = window_steps
        self.window_seconds = window_seconds
        self.degraded = degraded
        self.failing = failing

    def _evaluate(self, registry) -> dict:
        watch = global_compile_watch()
        recent = watch.recompiles_in_window(self.window_steps,
                                            self.window_seconds)
        n = len(recent)
        status = OK
        if self.failing is not None and n >= self.failing:
            status = FAILING
        elif self.degraded is not None and n >= self.degraded:
            status = DEGRADED
        out = {"status": status, "value": n,
               "window_steps": self.window_steps,
               "degraded_at": self.degraded, "failing_at": self.failing}
        if recent:
            worst = max(recent, key=lambda e: e["seq"])
            out["detail"] = (f"last: {worst['fn']}({worst['signature']})"
                             + (f" cause={worst['cause']['cause']}"
                                if worst.get("cause") else ""))
        return out


# --------------------------------------------------------- process wiring
_global_watch: Optional[CompileWatch] = None
_watch_lock = threading.Lock()
_listener_registered = False


def global_compile_watch() -> CompileWatch:
    """THE process-wide watch every built-in probe records into."""
    global _global_watch
    if _global_watch is None:
        with _watch_lock:
            if _global_watch is None:
                _global_watch = CompileWatch()
    return _global_watch


def reset_global_compile_watch() -> CompileWatch:
    global _global_watch
    with _watch_lock:
        _global_watch = CompileWatch()
    return _global_watch


def _on_compile_duration(event: str, duration: float, **kw) -> None:
    if not event.endswith("backend_compile_duration"):
        return
    if not compile_watch_enabled():
        return
    global_registry().histogram(
        "dl4j_compile_seconds",
        "XLA backend compile durations (process-wide jax.monitoring "
        "events; attributed best-effort to the last traced entry point)"
    ).observe(duration)
    watch = global_compile_watch()
    if not watch.attribute_duration(duration):
        # sharding-only recompile (no retrace): ring-record it if a
        # declared cause is waiting to be claimed
        watch.note_untraced_compile(duration)


def _ensure_listener() -> None:
    """Register the jax.monitoring duration listener once per process.
    Registration is permanent in jax, so the callback re-checks the kill
    switch per event instead of deregistering."""
    global _listener_registered
    with _watch_lock:
        if _listener_registered:
            return
        _listener_registered = True
    import jax.monitoring as _mon
    _mon.register_event_duration_secs_listener(_on_compile_duration)


# cost-model AOT re-lowerings re-enter the jitted bodies on a jaxpr-cache
# miss; their traces compile nothing, so the probes must stay silent for
# the duration (thread-local: the lowering happens on the caller's thread)
_suppress_tls = threading.local()


@contextlib.contextmanager
def suppress_probes():
    """``with suppress_probes(): f.lower(...)`` — body re-entries inside
    the block are not counted as compiles (cost_model's AOT lowering)."""
    prev = getattr(_suppress_tls, "active", False)
    _suppress_tls.active = True
    try:
        yield
    finally:
        _suppress_tls.active = prev


def probes_suppressed() -> bool:
    return getattr(_suppress_tls, "active", False)


def note_trace(fn: str, *arg_trees, **attrs) -> None:
    """Module-level probe the jitted bodies call (see CompileWatch)."""
    if not compile_watch_enabled() or probes_suppressed():
        return
    _ensure_listener()
    global_compile_watch().note_trace(fn, *arg_trees, **attrs)


def note_cause(cause: str, **attrs) -> None:
    """Module-level cause hint (see CompileWatch.note_cause)."""
    global_compile_watch().note_cause(cause, **attrs)


@on_registry_reset
def _clear_watch():
    # a fresh registry restarts the step clock — events stamped against
    # the old clock would all read "recent" forever (test isolation)
    if _global_watch is not None:
        _global_watch.clear()
