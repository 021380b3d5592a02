"""Device-level profiling bridge (SURVEY §5.1: "TPU equivalent: jax
profiler → XProf/TensorBoard").

The eager-path ``OpProfiler`` times per-op host dispatch; compiled programs
need the device timeline instead. This wraps ``jax.profiler`` behind the
same start/stop surface the reference exposes through
``Nd4j.getExecutioner().setProfilingConfig`` — traces land in a directory
TensorBoard/XProf can open. Host-side labels on that timeline are the
program's own ``observability.span()``s: while a profile runs, every span
is also written into it under its name (``observability/tracing.py``).
"""
from __future__ import annotations

import os


class DeviceProfiler:
    """ref-analog surface: start/stop around the XLA device timeline
    (``OpProfiler``'s scoped sections are ``span()``s here)."""

    def __init__(self, log_dir: str = "/tmp/dl4j_tpu_profile"):
        self.log_dir = log_dir
        self._active = False

    def start(self):
        import jax

        if self._active:
            return self
        os.makedirs(self.log_dir, exist_ok=True)
        jax.profiler.start_trace(self.log_dir)
        self._active = True
        return self

    def stop(self) -> str:
        import jax

        if self._active:
            jax.profiler.stop_trace()
            self._active = False
        return self.log_dir
