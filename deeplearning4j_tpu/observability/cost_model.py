"""XLA cost-model accounting: per-entry-point FLOPs/bytes, live MFU,
roofline grading.

PRs 1–4 made the stack observable in *time* (spans, step histograms,
compile events) but not in *work*: nothing in-process knew how many FLOPs
or bytes a compiled program moves, so "is this step fast?" was only
answerable by hand-running bench.py. This module closes that loop — the
same per-program accounting a whole-program XLA lowering gets for free
(Fishman et al. arXiv:1810.09868) and that weight-update-sharding papers
reason with (Xu et al. arXiv:2004.13336):

- **Cost accounting**: at the probe points compile_watch already owns
  (MLN/CG ``_train_step``, the ShardedTrainer sharded step, every
  ParallelInference shape-bucket executable), the entry point is AOT
  re-``lower()``-ed right after a (re)compile and its
  ``Lowered.cost_analysis()`` FLOPs / bytes-accessed published as
  ``dl4j_cost_flops{fn}`` / ``dl4j_cost_bytes{fn}``. The lowering is a
  jaxpr-cache HIT on the signature the step just ran (no retrace, no
  compile) and happens only when compile_watch's per-fn trace count
  moved — steady-state cost is one dict lookup and an int compare.
- **Live MFU**: the fit loops and the serving completer feed the same
  step/batch wall durations they already measure into a rolling window;
  ``dl4j_mfu{fn}`` = FLOPs / (rolling-mean seconds × peak FLOP/s). The
  window (64 samples) spans at least two deferred-score sync periods, so
  the async runtime's dispatch-only step timings average out correctly.
- **Roofline verdict**: arithmetic intensity (FLOPs / bytes accessed)
  against the ridge point of a per-backend peak-FLOPs / HBM-bandwidth
  table — ``compute_bound`` when the program could saturate the MXU,
  ``memory_bound`` when HBM sets the ceiling. Overridable via
  ``DL4J_TPU_PEAK_FLOPS`` (FLOP/s) and ``DL4J_TPU_HBM_GBPS`` (GB/s) so
  CPU tests are deterministic and bench comparisons share one table.
- **Regression reference**: a slow EWMA of the live MFU is each fn's own
  rolling baseline; :class:`~.slo.PerfRegressionRule` grades sustained
  drops on ``/health`` + ``/alerts``. The baseline freezes while a
  violation is in progress so a real regression cannot normalize itself
  away.

Surfaces: ``GET /debug/perf`` (full per-fn cost/time/MFU/roofline JSON),
``perf.json`` in flight-recorder bundles.

Known approximations (documented, not bugs): ``cost_analysis()`` runs on
the unoptimized HLO (fusion changes real bytes moved); sharded entries
report GLOBAL program FLOPs, so their peak is scaled by the mesh size
(:meth:`CostModel.set_scale`); serving batch durations include pipeline
queueing under multi-in-flight dispatch, so serving MFU is a lower bound.

Kill switch: ``DL4J_TPU_COST_MODEL=0`` (accounting + MFU timing no-op)
under the ``DL4J_TPU_METRICS=0`` master.
"""
from __future__ import annotations

import os
import threading
from collections import deque
from typing import Callable, Dict, List, Optional, Tuple

from deeplearning4j_tpu.observability import compile_watch as _cw
from deeplearning4j_tpu.observability import device_memory as _device_memory
from deeplearning4j_tpu.observability.registry import (global_registry,
                                                       metrics_enabled,
                                                       on_registry_reset)

#: step-duration samples the live MFU averages over — MUST span at least
#: two deferred-score sync periods (DL4J_TPU_SCORE_EVERY, default 16):
#: under the async runtime most per-step timings are dispatch-only and
#: the sync step absorbs the whole window, so only a window-spanning mean
#: reads the true per-step time
_TIMES_MAX = 64

#: slow EWMA weight for the per-fn MFU baseline the regression rule
#: grades against (half-life ~70 samples — a sustained drop is caught
#: long before the reference erodes)
_BASELINE_ALPHA = 0.01

#: fractional MFU drop below its rolling baseline that counts as a
#: regression. ONE constant on purpose: slo.PerfRegressionRule derives
#: its default ``drop`` from it, and the baseline EWMA freezes at the
#: same margin — a drop the rule would flag can never erode its own
#: reference. A custom rule with a smaller drop loses that guarantee.
PERF_REGRESSION_DROP = 0.3

#: per-chip peak dense bf16 FLOP/s and HBM bandwidth (bytes/s), keyed by
#: ``jax.Device.device_kind``. TPU row: Google Cloud documentation,
#: "TPU v5e" (197 TFLOP/s bf16, 819 GB/s HBM). bench.py reads the same
#: table, so live MFU and the bench's MFU share a denominator. A kind
#: that is not listed is an error, never a default. The CPU row is an
#: order-of-magnitude placeholder — tests pin the table via the env knobs.
DEVICE_PEAKS = {
    "TPU v5 lite": (197e12, 819e9),
    "cpu": (1e11, 5e10),
}


def device_peaks(device_kind: str) -> Tuple[float, float]:
    """(peak FLOP/s, HBM bytes/s) of one chip of ``device_kind``."""
    try:
        return DEVICE_PEAKS[device_kind]
    except KeyError:
        raise KeyError(
            f"no peak FLOP/s or HBM bandwidth is listed for device kind "
            f"{device_kind!r}: add it to cost_model.DEVICE_PEAKS with its "
            "source") from None


def cost_model_enabled() -> bool:
    """Kill switch (read per call so tests can flip it)."""
    return (metrics_enabled()
            and os.environ.get("DL4J_TPU_COST_MODEL", "1") != "0")


_device_kind_cache: Optional[str] = None


def _device_kind() -> str:
    """``device_kind`` of the device this process computes on. Raises in
    a process that has initialized no backend: the cost model prices the
    steps a process ran, and must not take a chip to look one up."""
    global _device_kind_cache
    if _device_kind_cache is None:
        devs = _device_memory.initialized_devices()
        if not devs:
            raise RuntimeError(
                "no jax backend is initialized in this process — there "
                "is no device to price a step against")
        _device_kind_cache = devs[0].device_kind
    return _device_kind_cache


def peak_flops() -> float:
    """Per-chip peak FLOP/s: ``DL4J_TPU_PEAK_FLOPS`` else the table."""
    env = os.environ.get("DL4J_TPU_PEAK_FLOPS")
    if env:
        try:
            return float(env)
        except ValueError:
            pass
    return device_peaks(_device_kind())[0]


def hbm_bytes_per_second() -> float:
    """Per-chip HBM bandwidth: ``DL4J_TPU_HBM_GBPS`` (GB/s) else table."""
    env = os.environ.get("DL4J_TPU_HBM_GBPS")
    if env:
        try:
            return float(env) * 1e9
        except ValueError:
            pass
    return device_peaks(_device_kind())[1]


def ridge_intensity() -> float:
    """FLOPs/byte at which the roofline's compute and memory ceilings
    meet — programs above it can saturate the MXU, below it HBM rules."""
    return peak_flops() / max(hbm_bytes_per_second(), 1.0)


def parse_cost_analysis(costs) -> Tuple[float, float]:
    """Normalize ``Lowered/Compiled.cost_analysis()`` output across jax
    versions (some return a per-device list) → (flops, bytes_accessed).
    The ONE place that parsing lives (callers want :func:`program_costs`,
    which also knows which object a backend prices)."""
    if isinstance(costs, (list, tuple)):
        costs = costs[0] if costs else {}
    return (float(costs.get("flops", 0.0) or 0.0),
            float(costs.get("bytes accessed", 0.0) or 0.0))


def program_costs(lowered) -> Tuple[float, float]:
    """(flops, bytes accessed) of one execution of the WHOLE program
    behind a ``jit(...).lower(...)`` result.

    The CPU backend prices the lowered module (unoptimized HLO, global
    over a mesh). The TPU backend answers ``None`` there (jax 0.9.0, found
    on the v5e) and prices only compiled executables, per device:
    ``compile()`` right after the call that compiled the same signature is
    an in-memory cache hit (9 ms for the flagship train step on the chip),
    and per-device costs times the executable's device count are the
    global ones the rest of this module assumes."""
    costs = lowered.cost_analysis()
    if costs is not None:
        return parse_cost_analysis(costs)
    import jax
    compiled = lowered.compile()
    flops, byts = parse_cost_analysis(compiled.cost_analysis())
    devices = max((len(s.device_set)
                   for s in jax.tree.leaves(compiled.output_shardings)),
                  default=1)
    return flops * devices, byts * devices


def _publish_cost(fn: str, flops: float, byts: float):
    """The ONE registration site for the per-fn cost gauges (account and
    record_cost must agree on name + help text)."""
    reg = global_registry()
    reg.gauge("dl4j_cost_flops",
              "XLA cost-model FLOPs per execution of the jitted "
              "entry point (unoptimized-HLO cost analysis)",
              label_names=("fn",)).labels(fn=fn).set(float(flops))
    reg.gauge("dl4j_cost_bytes",
              "XLA cost-model bytes accessed per execution of the "
              "jitted entry point",
              label_names=("fn",)).labels(fn=fn).set(float(byts))


class _Entry:
    """Per-fn accounting state (no lock of its own — CostModel's lock)."""

    __slots__ = ("flops", "bytes", "signature", "source", "error",
                 "analyzed_count", "analyze_calls", "times", "count",
                 "mfu", "bw_util", "baseline_mfu", "g_mfu")

    def __init__(self):
        self.flops = 0.0
        self.bytes = 0.0
        self.signature = None
        self.source = None            # "cost_analysis" once accounted
        self.error = None
        self.analyzed_count = -1      # compile-watch count last analyzed at
        self.analyze_calls = 0        # how often cost analysis actually ran
        self.times = deque(maxlen=_TIMES_MAX)
        self.count = 0                # lifetime duration samples
        self.mfu = None               # rolling-window MFU
        self.bw_util = None           # rolling-window HBM-bandwidth util
        self.baseline_mfu = None      # slow EWMA (regression reference)
        self.g_mfu = None             # cached gauge child


class CostModel:
    """Per-fn cost/time/MFU store. One process-wide instance via
    :func:`global_cost_model`; tests may construct their own."""

    def __init__(self):
        self._lock = threading.Lock()
        self._entries: Dict[str, _Entry] = {}
        self._collectives: Dict[str, Dict[str, float]] = {}
        self._compression: Dict[str, Dict[str, object]] = {}
        self._scales: Dict[str, int] = {}     # fn -> devices executing it

    # -------------------------------------------------------- accounting
    def has_entry(self, fn: str) -> bool:
        with self._lock:
            return fn in self._entries

    def needs_account(self, fn: str, probe_fn: Optional[str] = None) -> bool:
        """True when ``fn`` has never been analyzed, or compile_watch has
        counted a new (re)trace of ``probe_fn`` since the last analysis —
        the 'fires exactly once per compile' contract."""
        count = _cw.global_compile_watch().count_for(probe_fn or fn)
        with self._lock:
            e = self._entries.get(fn)
            return e is None or e.analyzed_count != count

    def account(self, fn: str, lower_thunk: Callable[[], object],
                probe_fn: Optional[str] = None) -> Optional[dict]:
        """Run ``lower_thunk()`` (an AOT ``jit(...).lower`` call at the
        signature that just executed — a jaxpr-cache hit, no compile) and
        record its ``cost_analysis()``. Analysis failures are recorded on
        the entry, never raised into the fit loop."""
        count = _cw.global_compile_watch().count_for(probe_fn or fn)
        with self._lock:
            e = self._entries.setdefault(fn, _Entry())
            e.analyzed_count = count
            e.analyze_calls += 1
        try:
            with _cw.suppress_probes():
                lowered = lower_thunk()
                flops, byts = program_costs(lowered)
            sig = None
            try:
                sig = _cw._signature([lowered.in_avals]) \
                    if hasattr(lowered, "in_avals") else None
            except Exception:
                sig = None
            with self._lock:
                # re-fetch: a concurrent clear()/invalidate() may have
                # dropped the entry between the two locked sections
                e = self._entries.setdefault(fn, _Entry())
                e.flops, e.bytes = flops, byts
                e.signature = sig
                e.source = "cost_analysis"
                e.error = None
            _publish_cost(fn, flops, byts)
            return {"flops": flops, "bytes": byts}
        except Exception as err:          # analysis is best-effort telemetry
            with self._lock:
                e = self._entries.get(fn)
                if e is not None:     # don't resurrect a concurrent clear()
                    e.error = repr(err)
            return None

    def record_cost(self, fn: str, flops: float, bytes_accessed: float = 0.0,
                    signature: Optional[str] = None):
        """Record externally computed costs (bench.py feeds the flagship
        transformer step it lowered itself)."""
        if not cost_model_enabled():      # same contract as every hook:
            return                        # the kill switch keeps it empty
        with self._lock:
            e = self._entries.setdefault(fn, _Entry())
            e.flops = float(flops)
            e.bytes = float(bytes_accessed or 0.0)
            e.signature = signature
            e.source = "external"
            e.analyze_calls += 1
        _publish_cost(fn, flops, bytes_accessed or 0.0)

    def invalidate(self, fn: str):
        """Drop one entry so the next step re-accounts it (ShardedTrainer
        re-placement recompiles WITHOUT a retrace — the probe count can't
        signal it)."""
        with self._lock:
            self._entries.pop(fn, None)

    def note_collectives(self, fn: str, bytes_by_op: Dict[str, float]):
        """Attach the analytic per-step collective traffic expectation to
        an entry (ShardedTrainer's allreduce / reduce-scatter+all-gather
        payload) — served next to the measured cost on /debug/perf."""
        with self._lock:
            self._collectives[fn] = {k: float(v)
                                     for k, v in bytes_by_op.items()}

    def note_compression(self, fn: str, info: Dict[str, object]):
        """Attach (merge) gradient-compression facts to an entry — the
        ThresholdAlgorithm in force, the analytic wire payload vs dense
        bytes, and the last synced encoded fraction — served as
        ``grad_compression`` next to the collective bytes on /debug/perf
        and in perf.json bundles."""
        with self._lock:
            self._compression.setdefault(fn, {}).update(info)

    def set_scale(self, fn: str, devices: int):
        """Sharded entries report GLOBAL program FLOPs — their roofline
        peak is ``devices`` chips, not one."""
        with self._lock:
            self._scales[fn] = max(1, int(devices))

    # ------------------------------------------------------------ timing
    def observe_time(self, fn: str, seconds: float):
        """Feed one measured execution duration; recomputes the rolling
        MFU/BW utilization and updates the regression baseline."""
        if seconds <= 0:
            return
        peak = peak_flops()
        hbm = hbm_bytes_per_second()
        with self._lock:
            e = self._entries.setdefault(fn, _Entry())
            e.times.append(float(seconds))
            e.count += 1
            if not e.flops:
                return
            scale = self._scales.get(fn, 1)
            mean_s = sum(e.times) / len(e.times)
            e.mfu = e.flops / (mean_s * peak * scale)
            e.bw_util = e.bytes / (mean_s * hbm * scale) if e.bytes else None
            # regression reference: slow EWMA, FROZEN at the SAME margin
            # PerfRegressionRule grades at — a drop the rule would flag
            # must not drag its own baseline down and self-heal the alert
            if e.baseline_mfu is None:
                e.baseline_mfu = e.mfu
            elif e.mfu >= e.baseline_mfu * (1.0 - PERF_REGRESSION_DROP):
                e.baseline_mfu += _BASELINE_ALPHA * (e.mfu - e.baseline_mfu)
            mfu, gauge = e.mfu, e.g_mfu
        if gauge is None:
            gauge = global_registry().gauge(
                "dl4j_mfu",
                "live model-FLOPs utilisation of the jitted entry point: "
                "cost-model FLOPs / (rolling-mean step seconds x peak "
                "FLOP/s from the DL4J_TPU_PEAK_FLOPS-overridable table)",
                label_names=("fn",)).labels(fn=fn)
            with self._lock:
                ent = self._entries.get(fn)   # clear() may have raced us
                if ent is not None:
                    ent.g_mfu = gauge
        gauge.set(mfu)

    def flops_for(self, fn: str) -> float:
        """Accounted FLOPs of one entry (0.0 when never analyzed) — the
        cheap read the per-tenant cost attribution uses per batch/step
        (one lock + dict lookup, no snapshot)."""
        with self._lock:
            e = self._entries.get(fn)
            return e.flops if e is not None else 0.0

    # ----------------------------------------------------------- queries
    def regression_view(self) -> List[Tuple[str, float, float, int]]:
        """(fn, rolling_mfu, baseline_mfu, samples) for every entry with
        both — the PerfRegressionRule's read surface."""
        with self._lock:
            return [(fn, e.mfu, e.baseline_mfu, e.count)
                    for fn, e in self._entries.items()
                    if e.mfu is not None and e.baseline_mfu]

    def entry(self, fn: str) -> Optional[dict]:
        snap = self.snapshot()
        return snap["fns"].get(fn)

    def snapshot(self) -> dict:
        """The /debug/perf + perf.json payload."""
        peak = peak_flops()
        hbm = hbm_bytes_per_second()
        ridge = peak / max(hbm, 1.0)
        fns = {}
        with self._lock:
            # times MUST be copied under the lock: observe_time appends
            # concurrently and list() over a mutating deque raises
            items = [(fn, e, list(e.times))
                     for fn, e in self._entries.items()]
            collectives = {k: dict(v) for k, v in self._collectives.items()}
            compression = {k: dict(v) for k, v in self._compression.items()}
            scales = dict(self._scales)
        for fn, e, times in items:
            mean_s = (sum(times) / len(times)) if times else None
            intensity = (e.flops / e.bytes) if e.bytes else None
            rec = {
                "flops": e.flops or None,
                "bytes_accessed": e.bytes or None,
                "arithmetic_intensity": intensity,
                "signature": e.signature,
                "source": e.source,
                "analyze_calls": e.analyze_calls,
                "error": e.error,
                "samples": e.count,
                "recent_seconds_mean": mean_s,
                "mfu": e.mfu,
                "bw_utilization": e.bw_util,
                "baseline_mfu": e.baseline_mfu,
                "mfu_vs_baseline": (e.mfu / e.baseline_mfu
                                    if e.mfu is not None and e.baseline_mfu
                                    else None),
                "roofline_verdict": (
                    None if intensity is None
                    else "compute_bound" if intensity >= ridge
                    else "memory_bound"),
                "devices": scales.get(fn, 1),
            }
            if fn in collectives:
                rec["collective_bytes_per_step"] = collectives[fn]
            if fn in compression:
                rec["grad_compression"] = compression[fn]
            fns[fn] = rec
        return {
            "enabled": cost_model_enabled(),
            "device_kind": _device_kind(),
            "peak_flops": peak,
            "hbm_bytes_per_second": hbm,
            "ridge_intensity": ridge,
            "fns": fns,
        }

    def clear(self):
        with self._lock:
            self._entries.clear()
            self._collectives.clear()
            self._compression.clear()
            self._scales.clear()


# --------------------------------------------------------- process wiring
_global_model: Optional[CostModel] = None
_model_lock = threading.Lock()


def global_cost_model() -> CostModel:
    """THE process-wide cost model every built-in hook records into."""
    global _global_model
    if _global_model is None:
        with _model_lock:
            if _global_model is None:
                _global_model = CostModel()
    return _global_model


def reset_global_cost_model() -> CostModel:
    global _global_model
    with _model_lock:
        _global_model = CostModel()
    return _global_model


# ------------------------------------------------------------ hook helpers
def on_step(probe_fn: str, fn: str, seconds: float,
            lower_thunk: Callable[[], object]):
    """The one-line fit-loop hook: observe the step duration and (only
    when compile_watch counted a fresh trace of ``probe_fn``) re-account
    the entry point's cost. ``fn`` may differ from ``probe_fn`` when a
    wrapper renames the entry (ShardedTrainer.step)."""
    if not cost_model_enabled():
        return
    cm = global_cost_model()
    if cm.needs_account(fn, probe_fn):
        cm.account(fn, lower_thunk, probe_fn=probe_fn)
    cm.observe_time(fn, seconds)


def bucket_fn(model, target: int) -> str:
    """Per-serving-bucket entry name, e.g.
    ``MultiLayerNetwork._output_jit[b8]`` — bounded cardinality (the
    bucket set is log2(batch_limit)+1 per model kind)."""
    return f"{type(model).__name__}._output_jit[b{int(target)}]"


def maybe_account_bucket(model, target: int, x):
    """Account one serving shape-bucket executable (called AFTER the real
    dispatch compiled it, so the AOT lowering is a cache hit and the
    bucket-miss cause attribution is untouched). Keyed to the model's
    ``_output_jit`` compile count: a bucket retraced at a new dtype — or
    a different same-class model compiling its first bucket — refreshes
    every bucket's FLOPs on next use, one cache-hit lowering each. Two
    same-class models serving the SAME bucket shape still share one
    entry (the last to account wins); keeping the label cardinality
    bounded per model KIND is the documented tradeoff."""
    if not cost_model_enabled():
        return
    fn = bucket_fn(model, target)
    probe = f"{type(model).__name__}._output_jit"
    cm = global_cost_model()
    if not cm.needs_account(fn, probe_fn=probe):
        return
    lower = getattr(model, "_lower_output", None)
    if lower is None:
        return
    cm.account(fn, lambda: lower(x), probe_fn=probe)


def observe_bucket_time(model, target: int, seconds: float):
    """Feed one device-batch dispatch→complete duration into the bucket's
    MFU (under multi-in-flight dispatch this includes queueing, so
    serving MFU is a lower bound — see module doc)."""
    if not cost_model_enabled():
        return
    global_cost_model().observe_time(bucket_fn(model, target), seconds)


@on_registry_reset
def _clear_model():
    # gauge handles and compile-count anchors die with the registry
    if _global_model is not None:
        _global_model.clear()
