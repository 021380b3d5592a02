"""Op profiling + numerical-panic debugging (ref: SURVEY J12/5.1-5.2)."""
from deeplearning4j_tpu.profiler.op_profiler import (OpProfiler,
                                                     ProfilerConfig)
from deeplearning4j_tpu.profiler.performance import PerformanceTracker
from deeplearning4j_tpu.profiler.xprof import DeviceProfiler

__all__ = ["OpProfiler", "ProfilerConfig", "PerformanceTracker",
           "DeviceProfiler"]
