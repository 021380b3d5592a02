#!/usr/bin/env python
"""Bench trajectory diff: grade the ``BENCH_r*.json`` history for
SUSTAINED performance regressions, noise-aware for this box.

Every round the driver runs ``bench.py`` once and archives the JSON as
``BENCH_r<NN>.json`` (plus suffixed extras like ``BENCH_r05_bert.json``).
Naively diffing raw tokens/sec across rounds is exactly wrong here: the
box's load drifts by ±40% between minutes (the round-4 "regression" —
0.908x at the SAME commit that measured 1.0–1.13x interactively — was
pure drift). Three rules make the comparison meaningful:

1. **Compare interleaved ratios, not raw single samples.** Each bench
   round already measures the model under test against a plain-Flax
   denominator INTERLEAVED (A,B,A,B windows; ``ratio_method:
   paired_window_median`` = the median of paired-window ratios, i.e. a
   min-of-N-style robust estimator over N interleaved pairs) — drift
   hits both sides of a pair and divides out. The trajectory is graded
   on that ``vs_baseline`` series and on device-trace MFU (chip-measured
   picoseconds, immune to host load); raw host tokens/sec is reported
   but never gated on.
2. **Same platform only.** A CPU round is not comparable to a TPU
   round; each metric's trajectory is filtered to
   the platform of its newest round.
3. **Sustained only.** A regression must hold for the trailing
   ``sustain`` rounds (default 2) against the MEDIAN of the prior
   comparable rounds, with a tolerance sized to the residual noise of
   the ratio estimator (default 25%). One bad round is weather; two in a
   row under a 25% drop is climate.

Also graded, each under its own schema: ``MULTICHIP_r*.json`` driver
dryruns (a boolean trajectory — the newest non-skipped round must pass),
``DECODE_r*.json`` decode-bench archives (the interleaved KV-vs-naive
/ continuous-vs-static / paged-vs-dense / int8-vs-f32 / spec-vs-plain
A/B ratios plus the slot-occupancy trajectory, sustained-only like the
bench ratios; raw tokens/s AND the speculative accept ratio are
reported, never gated), and ``SERVE_r*.json`` HTTP-load archives
(``benchmarks/http_load.py``: the interleaved HTTP-vs-direct
``vs_direct`` ratio plus the goodput trajectory, sustained-only; raw
p50/p99 milliseconds are reported, never gated — they are host-load
weather), and ``QOS_r*.json`` multi-tenant flooding drills
(``benchmarks/http_load.py --tenants``: the victim-tenant goodput
ratio — flood phase / no-flood baseline, an interleaved same-run
ratio so host drift divides out — sustained-only; raw victim p99
ratios are reported, never gated). Alien/unreadable JSON is ignored,
never fatal.

Run standalone (``python tools/bench_diff.py [root]``, exit code =
sustained regressions found) or from tests (tests/test_obs_perf.py
imports ``check_trajectory`` with synthetic histories and ``main`` over
the real repo history, like check_metric_names).
"""
from __future__ import annotations

import glob
import json
import os
import re
import statistics
import sys
from typing import Dict, List, NamedTuple, Optional, Tuple

#: trailing rounds that must ALL violate before a regression is real
DEFAULT_SUSTAIN = 2

#: fractional drop below the prior-round median that counts as a
#: violation — sized to the residual noise of the interleaved ratio
#: estimator on this box, NOT to the ±40% raw-throughput drift
DEFAULT_TOLERANCE = 0.25

_ROUND_RE = re.compile(r"BENCH_r(\d+)[^/]*\.json$")
_MULTICHIP_RE = re.compile(r"MULTICHIP_r(\d+)[^/]*\.json$")
_DECODE_RE = re.compile(r"DECODE_r(\d+)[^/]*\.json$")
_SERVE_RE = re.compile(r"SERVE_r(\d+)[^/]*\.json$")
_QOS_RE = re.compile(r"QOS_r(\d+)[^/]*\.json$")
_FLEET_RE = re.compile(r"FLEET_r(\d+)[^/]*\.json$")
_OBSFLEET_RE = re.compile(r"OBSFLEET_r(\d+)[^/]*\.json$")
_TRACEQ_RE = re.compile(r"TRACEQ_r(\d+)[^/]*\.json$")
_WATCH_RE = re.compile(r"WATCH_r(\d+)[^/]*\.json$")
_SESS_RE = re.compile(r"SESS_r(\d+)[^/]*\.json$")


class Sample(NamedTuple):
    round: int
    path: str
    metric: str
    platform: Optional[str]
    vs_baseline: Optional[float]
    mfu: Optional[float]
    device_timed: bool
    value: Optional[float]


class Regression(NamedTuple):
    metric: str
    series: str            # "vs_baseline" | "device_mfu"
    reference: float
    trailing: Tuple[float, ...]
    rounds: Tuple[int, ...]
    tolerance: float

    def __str__(self):
        return (f"{self.metric} [{self.series}]: trailing rounds "
                f"{list(self.rounds)} = {[round(v, 3) for v in self.trailing]}"
                f" all > {self.tolerance:.0%} below prior-round median "
                f"{self.reference:.3f}")


def _parse_record(path: str) -> Optional[dict]:
    try:
        with open(path, encoding="utf-8") as f:
            doc = json.load(f)
    except (OSError, ValueError):
        return None
    # driver wrapper format {n, cmd, rc, tail, parsed: {...}} or raw bench
    rec = doc.get("parsed") if isinstance(doc.get("parsed"), dict) else doc
    return rec if isinstance(rec, dict) and rec.get("metric") else None


def _file_mtime(path: str) -> float:
    """mtime, 0.0 when the path doesn't exist (synthetic test Samples) —
    equal keys keep the later glob-sorted file, the pre-mtime behavior."""
    try:
        return os.path.getmtime(path)
    except OSError:
        return 0.0


def load_samples(root: str) -> List[Sample]:
    out: List[Sample] = []
    for path in sorted(glob.glob(os.path.join(root, "BENCH_r*.json"))):
        m = _ROUND_RE.search(path)
        rec = _parse_record(path)
        if m is None or rec is None:
            continue
        value = rec.get("value")
        out.append(Sample(
            round=int(m.group(1)),
            path=path,
            metric=str(rec["metric"]),
            platform=rec.get("platform"),
            vs_baseline=(float(rec["vs_baseline"])
                         if isinstance(rec.get("vs_baseline"), (int, float))
                         else None),
            mfu=(float(rec["mfu"])
                 if isinstance(rec.get("mfu"), (int, float)) else None),
            device_timed=rec.get("timing_source") == "device_trace",
            value=(float(value)
                   if isinstance(value, (int, float)) else None)))
    return out


class DryrunSample(NamedTuple):
    round: int
    path: str
    ok: bool
    skipped: bool
    n_devices: Optional[int]


def load_multichip(root: str) -> List[DryrunSample]:
    """The driver's ``MULTICHIP_r*.json`` dryrun records — a different
    schema from bench rounds ({n_devices, rc, ok, skipped, tail}: a
    pass/fail smoke of the sharded paths, no throughput numbers). They
    are graded as a boolean trajectory, never as a perf series."""
    out: List[DryrunSample] = []
    for path in sorted(glob.glob(os.path.join(root, "MULTICHIP_r*.json"))):
        m = _MULTICHIP_RE.search(path)
        if m is None:
            continue
        try:
            with open(path, encoding="utf-8") as f:
                doc = json.load(f)
        except (OSError, ValueError):
            continue
        if not isinstance(doc, dict) or not (
                "ok" in doc or "rc" in doc or "skipped" in doc):
            continue
        ok = bool(doc.get("ok", doc.get("rc", 1) == 0))
        nd = doc.get("n_devices")
        out.append(DryrunSample(
            round=int(m.group(1)), path=path, ok=ok,
            skipped=bool(doc.get("skipped")),
            n_devices=int(nd) if isinstance(nd, (int, float)) else None))
    return out


class DecodeSample(NamedTuple):
    round: int
    path: str
    metric: str                  # "decode_kv_cache" | "decode_continuous_batching"
                                 # | "decode_paged_cache" | "decode_kv_quant"
                                 # | "decode_speculative"
    platform: Optional[str]
    ratio: Optional[float]       # vs_naive / vs_static / vs_dense_cache /
                                 # vs_f32 / vs_no_spec — the interleaved
                                 # A/B ratio, the only host-timed series
                                 # worth gating on (drift divides out)
    occupancy: Optional[float]   # mean of the slot-occupancy trajectory
    tokens_per_s: Optional[float]  # reported, never gated (raw host rate)
    accept_ratio: Optional[float]  # speculative accept rate — reported,
                                   # NEVER gated (a property of the
                                   # draft/target pair, not a perf series)


def load_decode(root: str) -> List[DecodeSample]:
    """``DECODE_r*.json`` decode-bench archives. Accepts the bench's
    combined ``{"kv": {...}, "cb": {...}}`` document, a single record,
    or the driver wrapper (``{"parsed": ...}``); anything without a
    ``decode_*`` metric — alien JSON — is ignored, never fatal."""
    out: List[DecodeSample] = []
    for path in sorted(glob.glob(os.path.join(root, "DECODE_r*.json"))):
        m = _DECODE_RE.search(path)
        if m is None:
            continue
        try:
            with open(path, encoding="utf-8") as f:
                doc = json.load(f)
        except (OSError, ValueError):
            continue
        if not isinstance(doc, dict):
            continue
        if isinstance(doc.get("parsed"), dict):
            doc = doc["parsed"]
        records = [doc] if "metric" in doc else [
            v for v in doc.values() if isinstance(v, dict)]
        for rec in records:
            metric = str(rec.get("metric", ""))
            if not metric.startswith("decode_"):
                continue
            ratio = None
            for key in ("vs_naive", "vs_static", "vs_dense_cache",
                        "vs_f32", "vs_no_spec"):
                if isinstance(rec.get(key), (int, float)):
                    ratio = float(rec[key])
                    break
            occ = rec.get("slot_occupancy")
            occupancy = (float(statistics.mean(occ))
                         if isinstance(occ, list) and occ
                         and all(isinstance(o, (int, float)) for o in occ)
                         else None)
            value = rec.get("value")
            accept = rec.get("spec_accept_ratio")
            out.append(DecodeSample(
                round=int(m.group(1)), path=path, metric=metric,
                platform=rec.get("platform"),
                ratio=ratio,
                occupancy=occupancy,
                tokens_per_s=(float(value)
                              if isinstance(value, (int, float))
                              else None),
                accept_ratio=(float(accept)
                              if isinstance(accept, (int, float))
                              else None)))
    return out


def check_decode(samples: List[DecodeSample],
                 tolerance: float = DEFAULT_TOLERANCE,
                 sustain: int = DEFAULT_SUSTAIN) -> List[Regression]:
    """Grade the decode trajectories with the SAME noise-aware rules as
    the bench rounds: newest file per round by mtime, same-platform
    only, sustained-only, and only the interleaved A/B ratio (per
    metric: vs_naive / vs_static / vs_dense_cache / vs_f32 /
    vs_no_spec) + the slot-occupancy trajectory. Raw tokens/s is ±40%
    weather here, and the speculative accept ratio is a property of the
    draft/target pair — both reported, never gated."""
    return _grade_metric_groups(samples, [
        ("ab_ratio", lambda s: s.ratio),
        ("slot_occupancy", lambda s: s.occupancy),
    ], tolerance, sustain)


class ServeSample(NamedTuple):
    round: int
    path: str
    metric: str                    # "http_serve"
    platform: Optional[str]
    vs_direct: Optional[float]     # interleaved HTTP/direct goodput
                                   # ratio — drift divides out
    goodput: Optional[float]       # ok requests/s (gated, with the
                                   # sustained+tolerance noise shield)
    p99_ms: Optional[float]        # reported, never gated (host weather)
    failed: Optional[int]


def load_serve(root: str) -> List[ServeSample]:
    """``SERVE_r*.json`` HTTP-load archives (``benchmarks/http_load.py``
    records, bare or driver-wrapped). Anything without an ``http_*``
    metric — alien JSON — is ignored, never fatal."""
    out: List[ServeSample] = []
    for path in sorted(glob.glob(os.path.join(root, "SERVE_r*.json"))):
        m = _SERVE_RE.search(path)
        if m is None:
            continue
        try:
            with open(path, encoding="utf-8") as f:
                doc = json.load(f)
        except (OSError, ValueError):
            continue
        if not isinstance(doc, dict):
            continue
        if isinstance(doc.get("parsed"), dict):
            doc = doc["parsed"]
        metric = str(doc.get("metric", ""))
        if not metric.startswith("http_"):
            continue
        goodput = doc.get("goodput", doc.get("value"))
        out.append(ServeSample(
            round=int(m.group(1)), path=path, metric=metric,
            platform=doc.get("platform"),
            vs_direct=(float(doc["vs_direct"])
                       if isinstance(doc.get("vs_direct"), (int, float))
                       else None),
            goodput=(float(goodput)
                     if isinstance(goodput, (int, float)) else None),
            p99_ms=(float(doc["p99_ms"])
                    if isinstance(doc.get("p99_ms"), (int, float))
                    else None),
            failed=(int(doc["failed"])
                    if isinstance(doc.get("failed"), (int, float))
                    else None)))
    return out


def check_serve(samples: List[ServeSample],
                tolerance: float = DEFAULT_TOLERANCE,
                sustain: int = DEFAULT_SUSTAIN) -> List[Regression]:
    """Grade the HTTP-serve trajectories under the same noise-aware
    rules: newest file per round by mtime, same-platform only,
    sustained-only — on the interleaved ``vs_direct`` ratio and the
    goodput series (p50/p99 raw latencies are never gated)."""
    return _grade_metric_groups(samples, [
        ("ab_ratio", lambda s: s.vs_direct),
        ("goodput", lambda s: s.goodput),
    ], tolerance, sustain)


class QosSample(NamedTuple):
    round: int
    path: str
    metric: str                      # "qos_drill"
    platform: Optional[str]
    victim_goodput_ratio: Optional[float]  # min over victims of
                                           # flood/baseline goodput —
                                           # same-run ratio, drift-immune
    victim_p99_ratio: Optional[float]      # reported, never gated
    flooder_shed: Optional[int]


def load_qos(root: str) -> List[QosSample]:
    """``QOS_r*.json`` flooding-drill archives (``http_load.py
    --tenants`` records, bare or driver-wrapped). Anything without a
    ``qos_`` metric — alien JSON — is ignored, never fatal."""
    out: List[QosSample] = []
    for path in sorted(glob.glob(os.path.join(root, "QOS_r*.json"))):
        m = _QOS_RE.search(path)
        if m is None:
            continue
        try:
            with open(path, encoding="utf-8") as f:
                doc = json.load(f)
        except (OSError, ValueError):
            continue
        if not isinstance(doc, dict):
            continue
        if isinstance(doc.get("parsed"), dict):
            doc = doc["parsed"]
        metric = str(doc.get("metric", ""))
        if not metric.startswith("qos_"):
            continue
        ratio = doc.get("victim_goodput_ratio", doc.get("value"))
        out.append(QosSample(
            round=int(m.group(1)), path=path, metric=metric,
            platform=doc.get("platform"),
            victim_goodput_ratio=(float(ratio)
                                  if isinstance(ratio, (int, float))
                                  else None),
            victim_p99_ratio=(float(doc["victim_p99_ratio"])
                              if isinstance(doc.get("victim_p99_ratio"),
                                            (int, float)) else None),
            flooder_shed=(int(doc["flooder_shed"])
                          if isinstance(doc.get("flooder_shed"),
                                        (int, float)) else None)))
    return out


def check_qos(samples: List[QosSample],
              tolerance: float = DEFAULT_TOLERANCE,
              sustain: int = DEFAULT_SUSTAIN) -> List[Regression]:
    """Grade the flooding-drill trajectory under the same noise-aware
    rules: newest file per round by mtime, same-platform only,
    sustained-only — on the victim-goodput ratio ONLY (it is a same-run
    interleaved ratio; the raw p99 ratios are host weather and are
    reported, never gated)."""
    return _grade_metric_groups(samples, [
        ("victim_goodput", lambda s: s.victim_goodput_ratio),
    ], tolerance, sustain)


class FleetSample(NamedTuple):
    round: int
    path: str
    metric: str                      # "fleet_chaos"
    platform: Optional[str]
    goodput_ratio: Optional[float]   # ok / total under chaos — gated
                                     # sustained-only
    dup_free: Optional[float]        # 1 / (1 + duplicate executions):
                                     # 1.0 = perfect exactly-once; any
                                     # duplicate drops it below the
                                     # tolerance floor — gated
                                     # sustained-only like a ratio
    p99_ms: Optional[float]          # reported, never gated (weather)
    terms_monotonic: Optional[bool]  # boolean audit, gated like
    stage_regressed: Optional[bool]  # MULTICHIP (newest round must pass)


def load_fleet(root: str) -> List[FleetSample]:
    """``FLEET_r*.json`` chaos-drill archives (``benchmarks/http_load.py
    --fleet-chaos`` records, bare or driver-wrapped). Anything without a
    ``fleet_`` metric — alien JSON — is ignored, never fatal."""
    out: List[FleetSample] = []
    for path in sorted(glob.glob(os.path.join(root, "FLEET_r*.json"))):
        m = _FLEET_RE.search(path)
        if m is None:
            continue
        try:
            with open(path, encoding="utf-8") as f:
                doc = json.load(f)
        except (OSError, ValueError):
            continue
        if not isinstance(doc, dict):
            continue
        if isinstance(doc.get("parsed"), dict):
            doc = doc["parsed"]
        metric = str(doc.get("metric", ""))
        if not metric.startswith("fleet_"):
            continue
        good = doc.get("goodput_ratio", doc.get("value"))
        dups = doc.get("duplicate_executions")
        out.append(FleetSample(
            round=int(m.group(1)), path=path, metric=metric,
            platform=doc.get("platform"),
            goodput_ratio=(float(good)
                           if isinstance(good, (int, float)) else None),
            dup_free=(1.0 / (1.0 + float(dups))
                      if isinstance(dups, (int, float)) and dups >= 0
                      else None),
            p99_ms=(float(doc["p99_ms"])
                    if isinstance(doc.get("p99_ms"), (int, float))
                    else None),
            terms_monotonic=(bool(doc["terms_monotonic"])
                             if isinstance(doc.get("terms_monotonic"),
                                           bool) else None),
            stage_regressed=(bool(doc["stage_regressed"])
                             if isinstance(doc.get("stage_regressed"),
                                           bool) else None)))
    return out


def check_fleet(samples: List[FleetSample],
                tolerance: float = DEFAULT_TOLERANCE,
                sustain: int = DEFAULT_SUSTAIN) -> List[Regression]:
    """Grade the chaos-drill trajectory under the same noise-aware
    rules: goodput-under-chaos and the duplicate-execution ratio
    (1/(1+dups)) sustained-only; raw p99 is reported, never gated."""
    return _grade_metric_groups(samples, [
        ("goodput", lambda s: s.goodput_ratio),
        ("dup_free", lambda s: s.dup_free),
    ], tolerance, sustain)


def check_fleet_bool(samples: List[FleetSample]) -> List[str]:
    """The boolean invariants grade like MULTICHIP: the NEWEST round's
    leader-term audit must hold and its stage must never have regressed
    — one failure is real, there is no noise to sustain through."""
    newest: Dict[int, FleetSample] = {}
    for s in samples:
        prev = newest.get(s.round)
        if prev is None or _file_mtime(s.path) >= _file_mtime(prev.path):
            newest[s.round] = s
    if not newest:
        return []
    latest = newest[max(newest)]
    out = []
    if latest.terms_monotonic is False:
        out.append(f"FLEET leader-term audit FAILING at "
                   f"r{latest.round:02d} (non-monotonic terms — a "
                   f"stale-term write landed; {latest.path})")
    if latest.stage_regressed is True:
        out.append(f"FLEET rollout stage REGRESSED at "
                   f"r{latest.round:02d} ({latest.path})")
    return out


class ObsFleetSample(NamedTuple):
    round: int
    path: str
    metric: str                      # "obsfleet_drill"
    platform: Optional[str]
    trace_coverage: Optional[float]  # fraction of requests whose caller
                                     # trace id round-tripped — gated
                                     # sustained-only
    federation_completeness: Optional[float]  # live workers present in
                                              # /metrics/fleet / live
                                              # workers — gated
    scrape_p99_ms: Optional[float]   # reported, never gated (weather)


def load_obsfleet(root: str) -> List[ObsFleetSample]:
    """``OBSFLEET_r*.json`` observability-drill archives
    (``benchmarks/http_load.py --fleet-obs`` records, bare or
    driver-wrapped). Anything without an ``obsfleet_`` metric — alien
    JSON — is ignored, never fatal."""
    out: List[ObsFleetSample] = []
    for path in sorted(glob.glob(os.path.join(root, "OBSFLEET_r*.json"))):
        m = _OBSFLEET_RE.search(path)
        if m is None:
            continue
        try:
            with open(path, encoding="utf-8") as f:
                doc = json.load(f)
        except (OSError, ValueError):
            continue
        if not isinstance(doc, dict):
            continue
        if isinstance(doc.get("parsed"), dict):
            doc = doc["parsed"]
        metric = str(doc.get("metric", ""))
        if not metric.startswith("obsfleet_"):
            continue
        cov = doc.get("trace_coverage", doc.get("value"))
        comp = doc.get("federation_completeness")
        out.append(ObsFleetSample(
            round=int(m.group(1)), path=path, metric=metric,
            platform=doc.get("platform"),
            trace_coverage=(float(cov)
                            if isinstance(cov, (int, float)) else None),
            federation_completeness=(float(comp)
                                     if isinstance(comp, (int, float))
                                     else None),
            scrape_p99_ms=(float(doc["scrape_p99_ms"])
                           if isinstance(doc.get("scrape_p99_ms"),
                                         (int, float)) else None)))
    return out


def check_obsfleet(samples: List[ObsFleetSample],
                   tolerance: float = DEFAULT_TOLERANCE,
                   sustain: int = DEFAULT_SUSTAIN) -> List[Regression]:
    """Grade the observability-drill trajectory under the same
    noise-aware rules: trace coverage and federation completeness
    sustained-only (both same-run fractions, drift-immune); the raw
    scrape p99 is host weather — reported, never gated."""
    return _grade_metric_groups(samples, [
        ("trace_coverage", lambda s: s.trace_coverage),
        ("federation_completeness",
         lambda s: s.federation_completeness),
    ], tolerance, sustain)


class TraceqSample(NamedTuple):
    round: int
    path: str
    metric: str                      # "traceq_drill"
    platform: Optional[str]
    retention_coverage: Optional[float]  # error/tail requests retained /
                                         # expected — gated sustained-only
    assembly_completeness: Optional[float]  # retained ids that assembled
                                            # to a cross-worker waterfall
                                            # through the proxy — gated
    assembly_p99_ms: Optional[float]  # reported, never gated (weather)


def load_traceq(root: str) -> List[TraceqSample]:
    """``TRACEQ_r*.json`` trace-intelligence drill archives
    (``benchmarks/http_load.py --trace-intel`` records, bare or
    driver-wrapped). Anything without a ``traceq_`` metric — alien
    JSON — is ignored, never fatal."""
    out: List[TraceqSample] = []
    for path in sorted(glob.glob(os.path.join(root, "TRACEQ_r*.json"))):
        m = _TRACEQ_RE.search(path)
        if m is None:
            continue
        try:
            with open(path, encoding="utf-8") as f:
                doc = json.load(f)
        except (OSError, ValueError):
            continue
        if not isinstance(doc, dict):
            continue
        if isinstance(doc.get("parsed"), dict):
            doc = doc["parsed"]
        metric = str(doc.get("metric", ""))
        if not metric.startswith("traceq_"):
            continue
        cov = doc.get("retention_coverage", doc.get("value"))
        comp = doc.get("assembly_completeness")
        out.append(TraceqSample(
            round=int(m.group(1)), path=path, metric=metric,
            platform=doc.get("platform"),
            retention_coverage=(float(cov)
                                if isinstance(cov, (int, float))
                                else None),
            assembly_completeness=(float(comp)
                                   if isinstance(comp, (int, float))
                                   else None),
            assembly_p99_ms=(float(doc["assembly_p99_ms"])
                             if isinstance(doc.get("assembly_p99_ms"),
                                           (int, float)) else None)))
    return out


def check_traceq(samples: List[TraceqSample],
                 tolerance: float = DEFAULT_TOLERANCE,
                 sustain: int = DEFAULT_SUSTAIN) -> List[Regression]:
    """Grade the trace-intelligence trajectory under the same
    noise-aware rules: retention coverage and assembly completeness
    sustained-only (same-run fractions, drift-immune); the raw assembly
    p99 is host weather — reported, never gated."""
    return _grade_metric_groups(samples, [
        ("retention_coverage", lambda s: s.retention_coverage),
        ("assembly_completeness", lambda s: s.assembly_completeness),
    ], tolerance, sustain)


class WatchSample(NamedTuple):
    round: int
    path: str
    metric: str                      # "watch_drill"
    platform: Optional[str]
    detected: Optional[float]        # page fired inside the budget (0/1)
    fp_free: Optional[float]         # clean baseline stayed alert-free
    single_incident: Optional[float]  # paging detectors coalesced to one
    traces_attached: Optional[float]  # incident carries pinned trace ids
    resolved: Optional[float]        # alert walked firing -> resolved
    detect_latency_s: Optional[float]  # reported, never gated (weather)


def _bool_frac(doc: dict, key: str) -> Optional[float]:
    v = doc.get(key)
    if isinstance(v, bool):
        return 1.0 if v else 0.0
    if isinstance(v, (int, float)):
        return float(v)
    return None


def load_watch(root: str) -> List[WatchSample]:
    """``WATCH_r*.json`` watchtower drill archives
    (``benchmarks/http_load.py --watchtower`` records, bare or
    driver-wrapped). Anything without a ``watch_`` metric — alien
    JSON — is ignored, never fatal."""
    out: List[WatchSample] = []
    for path in sorted(glob.glob(os.path.join(root, "WATCH_r*.json"))):
        m = _WATCH_RE.search(path)
        if m is None:
            continue
        try:
            with open(path, encoding="utf-8") as f:
                doc = json.load(f)
        except (OSError, ValueError):
            continue
        if not isinstance(doc, dict):
            continue
        if isinstance(doc.get("parsed"), dict):
            doc = doc["parsed"]
        metric = str(doc.get("metric", ""))
        if not metric.startswith("watch_"):
            continue
        lat = doc.get("detect_latency_s")
        out.append(WatchSample(
            round=int(m.group(1)), path=path, metric=metric,
            platform=doc.get("platform"),
            detected=_bool_frac(doc, "detected"),
            fp_free=_bool_frac(doc, "fp_free"),
            single_incident=_bool_frac(doc, "single_incident"),
            traces_attached=_bool_frac(doc, "traces_attached"),
            resolved=_bool_frac(doc, "resolved"),
            detect_latency_s=(float(lat)
                              if isinstance(lat, (int, float))
                              else None)))
    return out


def check_watch(samples: List[WatchSample],
                tolerance: float = DEFAULT_TOLERANCE,
                sustain: int = DEFAULT_SUSTAIN) -> List[Regression]:
    """Grade the watchtower trajectory sustained-only: detection,
    false-positive freedom, incident coalescing, trace evidence, and
    resolution are same-run booleans graded as 1.0/0.0 fractions (a
    sustained fall to 0.0 is a real break, one flaky run is not); the
    raw detection latency is host weather — reported, never gated."""
    return _grade_metric_groups(samples, [
        ("detected", lambda s: s.detected),
        ("fp_free", lambda s: s.fp_free),
        ("single_incident", lambda s: s.single_incident),
        ("traces_attached", lambda s: s.traces_attached),
        ("resolved", lambda s: s.resolved),
    ], tolerance, sustain)


class SessSample(NamedTuple):
    round: int
    path: str
    metric: str                      # "sess_failover"
    platform: Optional[str]
    completion: Optional[float]      # streams completed / streams (gated)
    seq_exact: Optional[float]       # gapless, duplicate-free id runs
    greedy_match: Optional[float]    # byte-identical to undisturbed run
    resume_latency_ms: Optional[float]  # reported, never gated (weather)


def load_sess(root: str) -> List[SessSample]:
    """``SESS_r*.json`` session-failover drill archives
    (``benchmarks/http_load.py --session-failover`` records, bare or
    driver-wrapped). Anything without a ``sess_`` metric — alien
    JSON — is ignored, never fatal."""
    out: List[SessSample] = []
    for path in sorted(glob.glob(os.path.join(root, "SESS_r*.json"))):
        m = _SESS_RE.search(path)
        if m is None:
            continue
        try:
            with open(path, encoding="utf-8") as f:
                doc = json.load(f)
        except (OSError, ValueError):
            continue
        if not isinstance(doc, dict):
            continue
        if isinstance(doc.get("parsed"), dict):
            doc = doc["parsed"]
        metric = str(doc.get("metric", ""))
        if not metric.startswith("sess_"):
            continue
        lat = doc.get("resume_latency_ms")
        out.append(SessSample(
            round=int(m.group(1)), path=path, metric=metric,
            platform=doc.get("platform"),
            completion=_bool_frac(doc, "sess_completion"),
            seq_exact=_bool_frac(doc, "sess_seq_exact"),
            greedy_match=_bool_frac(doc, "sess_greedy_match"),
            resume_latency_ms=(float(lat)
                               if isinstance(lat, (int, float))
                               else None)))
    return out


def check_sess(samples: List[SessSample],
               tolerance: float = DEFAULT_TOLERANCE,
               sustain: int = DEFAULT_SUSTAIN) -> List[Regression]:
    """Grade the session-failover trajectory sustained-only: stream
    completion, exact (gapless/duplicate-free) sequence delivery, and
    greedy byte-identity are same-run fractions — drift-immune; the
    raw resume latency is host weather — reported, never gated."""
    return _grade_metric_groups(samples, [
        ("sess_completion", lambda s: s.completion),
        ("sess_seq_exact", lambda s: s.seq_exact),
        ("sess_greedy_match", lambda s: s.greedy_match),
    ], tolerance, sustain)


def check_multichip(samples: List[DryrunSample]) -> List[str]:
    """The NEWEST non-skipped dryrun per round must pass; a failing
    newest round is a break (boolean — one failure is real, there is no
    noise to sustain through)."""
    newest: Dict[int, DryrunSample] = {}
    for s in samples:
        if s.skipped:
            continue
        prev = newest.get(s.round)
        if prev is None or _file_mtime(s.path) >= _file_mtime(prev.path):
            newest[s.round] = s
    if not newest:
        return []
    latest = newest[max(newest)]
    if latest.ok:
        return []
    return [f"MULTICHIP dryrun FAILING at r{latest.round:02d} "
            f"({latest.path})"]


def _grade_metric_groups(samples, series_extractors, tolerance: float,
                         sustain: int) -> List[Regression]:
    """Shared per-metric grading scaffold for every sample schema:
    group by metric, keep the newest FILE per round by mtime (a round
    may archive several files for one metric; glob order would let a
    stale suffixed archive shadow a fresh plain one — '_' sorts after
    '.'), filter to the platform of the newest round's authoritative
    file (a stale archive can't flip the trajectory's platform either),
    then grade each (series, extractor) trajectory sustained-only."""
    by_metric: Dict[str, list] = {}
    for s in samples:
        by_metric.setdefault(s.metric, []).append(s)
    out: List[Regression] = []
    for metric, group in sorted(by_metric.items()):
        group.sort(key=lambda s: s.round)
        newest: Dict[int, object] = {}
        for s in group:
            prev = newest.get(s.round)
            if prev is None or _file_mtime(s.path) >= _file_mtime(prev.path):
                newest[s.round] = s
        platform = newest[max(newest)].platform
        ordered = [newest[r] for r in sorted(newest)
                   if newest[r].platform == platform]
        for series, extract in series_extractors:
            pts = [(s.round, extract(s)) for s in ordered
                   if extract(s) is not None]
            reg = _grade_series(metric, series, pts, tolerance, sustain)
            if reg is not None:
                out.append(reg)
    return out


def _grade_series(metric: str, series: str, points: List[Tuple[int, float]],
                  tolerance: float, sustain: int) -> Optional[Regression]:
    """One trajectory: trailing ``sustain`` points vs. the median of
    everything before them. Needs at least sustain+1 points."""
    if len(points) < sustain + 1:
        return None
    points = sorted(points)
    prior = [v for _, v in points[:-sustain]]
    trailing = points[-sustain:]
    reference = statistics.median(prior)
    if reference <= 0:
        return None
    floor = reference * (1.0 - tolerance)
    if all(v < floor for _, v in trailing):
        return Regression(metric, series, reference,
                          tuple(v for _, v in trailing),
                          tuple(r for r, _ in trailing), tolerance)
    return None


def check_trajectory(samples: List[Sample],
                     tolerance: float = DEFAULT_TOLERANCE,
                     sustain: int = DEFAULT_SUSTAIN) -> List[Regression]:
    """Grade every metric's history; returns the sustained regressions.
    device_mfu is chip-clocked, so it is the tighter signal when the
    rounds have it (host-load drift cannot touch picosecond sums)."""
    return _grade_metric_groups(samples, [
        ("vs_baseline", lambda s: s.vs_baseline),
        ("device_mfu", lambda s: s.mfu if s.device_timed else None),
    ], tolerance, sustain)


def main(argv=None) -> int:
    args = (argv if argv is not None else sys.argv[1:])
    root = args[0] if args else os.path.normpath(os.path.join(
        os.path.dirname(os.path.abspath(__file__)), os.pardir))
    samples = load_samples(root)
    dryruns = load_multichip(root)
    decodes = load_decode(root)
    serves = load_serve(root)
    qos = load_qos(root)
    fleet = load_fleet(root)
    obsfleet = load_obsfleet(root)
    traceq = load_traceq(root)
    watch = load_watch(root)
    sess = load_sess(root)
    if (not samples and not dryruns and not decodes and not serves
            and not qos and not fleet and not obsfleet and not traceq
            and not watch and not sess):
        # a fresh checkout / pre-first-bench tree has no trajectory at
        # all — that is a clean state, not an error
        print(f"no bench trajectory under {root} (0 samples) — "
              "nothing to grade")
        return 0
    regressions = (check_trajectory(samples) + check_decode(decodes)
                   + check_serve(serves) + check_qos(qos)
                   + check_fleet(fleet) + check_obsfleet(obsfleet)
                   + check_traceq(traceq) + check_watch(watch)
                   + check_sess(sess))
    breaks = check_multichip(dryruns) + check_fleet_bool(fleet)
    for s in samples:
        marks = []
        if s.vs_baseline is not None:
            marks.append(f"vs_baseline={s.vs_baseline:.3f}")
        if s.mfu is not None and s.device_timed:
            marks.append(f"device_mfu={s.mfu:.4f}")
        print(f"r{s.round:02d} {s.metric} [{s.platform}] "
              + (" ".join(marks) or f"value={s.value}"))
    for d in dryruns:
        state = ("skipped" if d.skipped else "ok" if d.ok else "FAIL")
        dev = f" devices={d.n_devices}" if d.n_devices else ""
        print(f"r{d.round:02d} multichip_dryrun {state}{dev}")
    for s in decodes:
        marks = []
        if s.ratio is not None:
            marks.append(f"ab_ratio={s.ratio:.3f}")
        if s.occupancy is not None:
            marks.append(f"occupancy={s.occupancy:.3f}")
        if s.accept_ratio is not None:
            marks.append(f"spec_accept={s.accept_ratio:.3f}")
        print(f"r{s.round:02d} {s.metric} [{s.platform}] "
              + (" ".join(marks) or f"tokens/s={s.tokens_per_s}"))
    for s in serves:
        marks = []
        if s.vs_direct is not None:
            marks.append(f"ab_ratio={s.vs_direct:.3f}")
        if s.goodput is not None:
            marks.append(f"goodput={s.goodput:.1f}/s")
        if s.p99_ms is not None:
            marks.append(f"p99={s.p99_ms:.1f}ms")
        print(f"r{s.round:02d} {s.metric} [{s.platform}] "
              + " ".join(marks))
    for s in qos:
        marks = []
        if s.victim_goodput_ratio is not None:
            marks.append(f"victim_goodput={s.victim_goodput_ratio:.3f}")
        if s.victim_p99_ratio is not None:
            marks.append(f"victim_p99_ratio={s.victim_p99_ratio:.2f}")
        if s.flooder_shed is not None:
            marks.append(f"flooder_shed={s.flooder_shed}")
        print(f"r{s.round:02d} {s.metric} [{s.platform}] "
              + " ".join(marks))
    for s in fleet:
        marks = []
        if s.goodput_ratio is not None:
            marks.append(f"goodput={s.goodput_ratio:.3f}")
        if s.dup_free is not None:
            marks.append(f"dup_free={s.dup_free:.3f}")
        if s.terms_monotonic is not None:
            marks.append(f"terms_monotonic={s.terms_monotonic}")
        if s.stage_regressed is not None:
            marks.append(f"stage_regressed={s.stage_regressed}")
        if s.p99_ms is not None:
            marks.append(f"p99={s.p99_ms:.1f}ms")
        print(f"r{s.round:02d} {s.metric} [{s.platform}] "
              + " ".join(marks))
    for s in obsfleet:
        marks = []
        if s.trace_coverage is not None:
            marks.append(f"trace_coverage={s.trace_coverage:.3f}")
        if s.federation_completeness is not None:
            marks.append(
                f"federation={s.federation_completeness:.3f}")
        if s.scrape_p99_ms is not None:
            marks.append(f"scrape_p99={s.scrape_p99_ms:.1f}ms")
        print(f"r{s.round:02d} {s.metric} [{s.platform}] "
              + " ".join(marks))
    for s in traceq:
        marks = []
        if s.retention_coverage is not None:
            marks.append(f"retention={s.retention_coverage:.3f}")
        if s.assembly_completeness is not None:
            marks.append(f"assembly={s.assembly_completeness:.3f}")
        if s.assembly_p99_ms is not None:
            marks.append(f"assembly_p99={s.assembly_p99_ms:.1f}ms")
        print(f"r{s.round:02d} {s.metric} [{s.platform}] "
              + " ".join(marks))
    for s in watch:
        marks = []
        if s.detect_latency_s is not None:
            marks.append(f"detect={s.detect_latency_s:.2f}s")
        for name, v in (("detected", s.detected), ("fp_free", s.fp_free),
                        ("single_incident", s.single_incident),
                        ("traces", s.traces_attached),
                        ("resolved", s.resolved)):
            if v is not None:
                marks.append(f"{name}={v:.0f}")
        print(f"r{s.round:02d} {s.metric} [{s.platform}] "
              + " ".join(marks))
    for s in sess:
        marks = []
        for name, v in (("completion", s.completion),
                        ("seq_exact", s.seq_exact),
                        ("greedy_match", s.greedy_match)):
            if v is not None:
                marks.append(f"{name}={v:.3f}")
        if s.resume_latency_ms is not None:
            marks.append(f"resume={s.resume_latency_ms:.1f}ms")
        print(f"r{s.round:02d} {s.metric} [{s.platform}] "
              + " ".join(marks))
    for reg in regressions:
        print(f"SUSTAINED REGRESSION: {reg}")
    for b in breaks:
        print(b)
    if not regressions and not breaks:
        print(f"bench trajectory OK ({len(samples)} bench + "
              f"{len(dryruns)} dryrun + {len(decodes)} decode + "
              f"{len(serves)} serve + {len(qos)} qos + "
              f"{len(fleet)} fleet + {len(obsfleet)} obsfleet + "
              f"{len(traceq)} traceq + {len(watch)} watch + "
              f"{len(sess)} sess samples under {root})")
    return len(regressions) + len(breaks)


if __name__ == "__main__":
    sys.exit(main())
