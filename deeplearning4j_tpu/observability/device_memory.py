"""Device (HBM) memory telemetry sampled at step/batch boundaries.

An OOM on an accelerator is the other silent killer next to retrace
storms and divergence: fragmentation and leak curves are invisible until
the allocator throws. ``jax.Device.memory_stats()`` exposes the PJRT
allocator's live view (``bytes_in_use`` / ``peak_bytes_in_use`` /
``bytes_limit`` on TPU/GPU backends); this module turns it into gauges

    ``dl4j_device_memory_bytes{device,kind}``   kind ∈ in_use|peak|limit

scraped at ``/metrics`` and snapshotted into flight-recorder bundles.
Sampling happens at the boundaries the fit loops and the serving
completer already cross (``train_metrics.record_step``, the
``ParallelInference`` completer) — never inside the jitted step — and is
throttled to at most one sweep per ``_MIN_INTERVAL_S`` so a fast step
loop pays one cached-time comparison, not eight PJRT calls.

Graceful no-op everywhere stats are unavailable: the CPU backend returns
``None`` from ``memory_stats()`` — the sampler remembers that and stops
asking (per process), so the CPU test mesh costs nothing.

Rides the master kill switch ``DL4J_TPU_METRICS=0``.
"""
from __future__ import annotations

import threading
import time
from typing import List, Optional

from deeplearning4j_tpu.observability.registry import (global_registry,
                                                       metrics_enabled)

_MIN_INTERVAL_S = 1.0

#: stat-dict keys → gauge ``kind`` label (PJRT's naming, stable across
#: TPU and GPU plugins)
_KINDS = (("bytes_in_use", "in_use"),
          ("peak_bytes_in_use", "peak"),
          ("bytes_limit", "limit"))

_lock = threading.Lock()
_last_sample_mono = 0.0
_unsupported = False


def initialized_devices() -> list:
    """This process's devices, WITHOUT initializing a backend: empty in a
    process that has run nothing on jax. A chip belongs to one process, so
    an observer (a postmortem bundle, a stats page, the ``tools/serve.py``
    proxy parent) must never be the call that takes it from a worker."""
    from jax._src import xla_bridge

    if not xla_bridge.backends_are_initialized():
        return []
    import jax

    return jax.devices()


def _stats_per_device(devices) -> List[tuple]:
    """[(device, stats-dict)] for the devices that report stats."""
    out = []
    for d in devices:
        try:
            stats = d.memory_stats()
        except Exception:
            stats = None
        if stats:
            out.append((d, stats))
    return out


def sample(min_interval_s: Optional[float] = None) -> bool:
    """Sweep every device's memory stats into the gauges (throttled).
    Returns True when a sweep actually published."""
    global _last_sample_mono, _unsupported
    if not metrics_enabled() or _unsupported:
        return False
    interval = _MIN_INTERVAL_S if min_interval_s is None else min_interval_s
    now = time.monotonic()
    with _lock:
        if now - _last_sample_mono < interval:
            return False
        _last_sample_mono = now
    devices = initialized_devices()
    per_dev = _stats_per_device(devices)
    if not per_dev:
        # nothing on this backend reports (CPU test mesh) — stop asking;
        # a process with no backend yet may still get one that does
        _unsupported = bool(devices)
        return False
    gauge = global_registry().gauge(
        "dl4j_device_memory_bytes",
        "PJRT allocator memory per device (sampled at step/batch "
        "boundaries): kind=in_use|peak|limit",
        label_names=("device", "kind"))
    for d, stats in per_dev:
        dev_id = str(getattr(d, "id", d))
        for stat_key, kind in _KINDS:
            v = stats.get(stat_key)
            if v is not None:
                gauge.labels(device=dev_id, kind=kind).set(float(v))
    return True


def snapshot() -> dict:
    """Unthrottled point-in-time view for postmortem bundles."""
    devices = []
    for d in initialized_devices():
        try:
            stats = d.memory_stats()
        except Exception:
            stats = None
        devices.append({
            "id": getattr(d, "id", None),
            "platform": getattr(d, "platform", None),
            "kind": getattr(d, "device_kind", None),
            "memory_stats": ({k: stats[k] for k in sorted(stats)}
                             if stats else None),
        })
    return {"devices": devices}


def reset_for_tests() -> None:
    """Forget the throttle and the unsupported latch (test isolation)."""
    global _last_sample_mono, _unsupported
    with _lock:
        _last_sample_mono = 0.0
        _unsupported = False
