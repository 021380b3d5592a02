"""Device-side step timing via the JAX profiler's XPlane trace.

Host-side wall-clock includes dispatch, transfers and whatever else the host
was doing. The trace is recorded **on the device**: each execution of a jitted
module appears on the ``/device:TPU:*`` plane's "XLA Modules" line with a
picosecond duration measured by the TPU itself.

Protocol (BASELINE.md):
    run K warm steps under ``jax.profiler.trace`` → parse the xplane proto →
    median duration of the module whose name matches the jitted function →
    tokens/sec and MFU computed from device time.

Reference analog: the per-op wall-time aggregation of ``OpProfiler``
(`org.nd4j.linalg.profiler.OpProfiler`, SURVEY §5.1) — but measured by the
hardware instead of the host clock.
"""
from __future__ import annotations

import glob
import os
import statistics
import tempfile
from typing import Callable, Dict, List, Optional


def _load_xplane(logdir: str):
    """Parse every *.xplane.pb under ``logdir`` into XSpace protos.

    The xplane proto ships inside tensorflow (tsl); the import is deferred so
    the module stays usable (host-timing paths) when TF is absent.
    """
    from tensorflow.tsl.profiler.protobuf import xplane_pb2  # deferred: heavy

    spaces = []
    for f in glob.glob(os.path.join(logdir, "**", "*.xplane.pb"),
                       recursive=True):
        sp = xplane_pb2.XSpace()
        with open(f, "rb") as fh:
            sp.ParseFromString(fh.read())
        spaces.append(sp)
    return spaces


def module_times(logdir: str, name_prefix: str = "jit_") -> Dict[str, List[float]]:
    """Durations (seconds) of every device-side XLA module execution,
    grouped by module name (fingerprint suffix stripped).

    Only device planes are read ("/device:TPU:*" etc.) — host planes carry
    dispatch time, which is exactly what we must NOT measure.
    """
    out: Dict[str, List[float]] = {}
    for space in _load_xplane(logdir):
        for plane in space.planes:
            if not plane.name.startswith("/device:"):
                continue
            if "CUSTOM" in plane.name:  # megascale/transport pseudo-planes
                continue
            meta = plane.event_metadata
            for line in plane.lines:
                if "module" not in line.name.lower():
                    continue
                for ev in line.events:
                    name = meta[ev.metadata_id].name
                    base = name.split("(")[0]  # strip (fingerprint)
                    if name_prefix and not base.startswith(name_prefix):
                        continue
                    out.setdefault(base, []).append(ev.duration_ps / 1e12)
    return out


def op_times(logdir: str, top: int = 25) -> List[tuple]:
    """Aggregate device-side per-op time: [(op_name, total_s, count)] sorted
    by total time. The "XLA Ops" line of the device plane — the kernel-level
    breakdown used to hunt regressions."""
    agg: Dict[str, List[float]] = {}
    for space in _load_xplane(logdir):
        for plane in space.planes:
            if not plane.name.startswith("/device:"):
                continue
            meta = plane.event_metadata
            for line in plane.lines:
                if line.name != "XLA Ops":
                    continue
                for ev in line.events:
                    name = meta[ev.metadata_id].name
                    a = agg.setdefault(name, [0.0, 0])
                    a[0] += ev.duration_ps / 1e12
                    a[1] += 1
    rows = sorted(((k, v[0], v[1]) for k, v in agg.items()),
                  key=lambda r: -r[1])
    return rows[:top]


def measure_device_step(run_window: Callable[[], None],
                        match: str,
                        logdir: Optional[str] = None) -> Optional[dict]:
    """Run ``run_window`` (which must execute >=2 steps of the jitted fn and
    sync) under a profiler trace; return device-timing stats for the module
    whose name starts with ``match`` (e.g. "jit_train_step").

    Returns None when no matching device events were captured (CPU backend,
    or a backend whose PJRT plugin does not export device traces).
    """
    import jax

    own_dir = logdir is None
    logdir = logdir or tempfile.mkdtemp(prefix="dl4j_tpu_trace_")
    try:
        with jax.profiler.trace(logdir):
            run_window()
        try:
            times = module_times(logdir)
        except Exception as e:  # TF absent or proto drift — report, don't crash
            import sys
            print(f"[device_timing] trace parse failed: {e!r}", file=sys.stderr)
            return None
    finally:
        if own_dir:
            # trace files are multi-MB; don't accumulate them across runs
            import shutil
            shutil.rmtree(logdir, ignore_errors=True)
    for base, durs in times.items():
        if base.startswith(match) or base.startswith("jit_" + match):
            # first execution in the window may still include autotuning
            # noise; median over the window is the protocol number
            return {
                "module": base,
                "n": len(durs),
                "median_s": statistics.median(durs),
                "mean_s": statistics.fmean(durs),
                "min_s": min(durs),
                "logdir": None if own_dir else logdir,
            }
    return None
