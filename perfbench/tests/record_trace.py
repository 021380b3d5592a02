"""Record the small trace that ``test_trace.py`` reduces: a few runs of a
small jitted program named ``step`` on the chip, through the benchmark's own
``Recorder``. Run on the chip: ``python3 perfbench/tests/record_trace.py
<out.xplane.pb>``; prints the planes and lines it finds."""
import glob
import os
import shutil
import sys
import time

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))
sys.path.insert(0, ROOT)

import jax  # noqa: E402
import jax.numpy as jnp  # noqa: E402

from perfbench import trace as ptrace  # noqa: E402


def main(out):
    devs = jax.devices()
    mesh = jax.sharding.Mesh(devs, ("data",))
    spec = jax.sharding.NamedSharding(mesh, jax.sharding.PartitionSpec("data"))

    @jax.jit
    def step(x, w):
        h = jnp.tanh(x @ w)
        return h @ w.T, jnp.sum(h)      # the sum crosses chips when sharded

    x = jax.device_put(jnp.ones((8 * len(devs), 512), jnp.bfloat16), spec)
    w = jnp.ones((512, 512), jnp.bfloat16)
    step(x, w)[0].block_until_ready()
    log = os.path.join(os.path.dirname(out) or ".", "_trace_tmp")
    rec = ptrace.Recorder(log)
    rec.start()
    for _ in range(4):
        y, s = step(x, w)
        float(s)
        time.sleep(0.002)
    rec.stop()
    src = sorted(glob.glob(os.path.join(log, "plugins", "profile", "*",
                                        "*.xplane.pb")))[-1]
    shutil.copy(src, out)
    shutil.rmtree(log, ignore_errors=True)
    from jax.profiler import ProfileData
    data = ProfileData.from_file(out)
    for plane in data.planes:
        print("PLANE", plane.name)
        for line in plane.lines:
            evs = list(line.events)
            print("  LINE", line.name, len(evs),
                  [e.name for e in evs[:4]])
    tr = ptrace.Trace.from_file(out)
    lo, hi = tr.span()
    print("devices", [d.name for d in tr.devices], "busy",
          tr.busy_seconds(lo, hi), "span", hi - lo, "step median",
          tr.module_median(r"^jit_step"), "marks", tr.host_events,
          "window", rec.window_s, os.path.getsize(out), "bytes")


if __name__ == "__main__":
    main(sys.argv[1])
