"""Record the trace that ``test_named.py`` reads: a few steps of a small
jitted program ``step`` whose two halves carry named scopes of the program's
vocabulary (``mlp``, ``head``), driven by a host loop that opens the decode
loop's own span names nested as the program nests them - on the chip, through
the benchmark's ``Recorder``. ``python3 perfbench/tests/record_scoped_trace.py
<out.xplane.pb>`` prints what the reader finds."""
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

from deeplearning4j_tpu.observability import span  # noqa: E402
from perfbench import trace as ptrace  # noqa: E402
from perfbench.layer_metrics import _named  # noqa: E402


def main(out):
    @jax.jit
    def step(x, w):
        with jax.named_scope("mlp"):
            h = jnp.tanh(x @ w)
        with jax.named_scope("head"):
            return h @ w.T, jnp.sum(h)

    x = jnp.ones((8, 512), jnp.bfloat16)
    w = jnp.ones((512, 512), jnp.bfloat16)
    step(x, w)[0].block_until_ready()
    log = os.path.join(os.path.dirname(out) or ".", "_trace_tmp")
    rec = ptrace.Recorder(log)
    rec.start()
    for i in range(4):
        with span("decode_iter", step=i):
            with span("decode_step"):
                with span("decode_dispatch"):
                    y, s = step(x, w)
                with span("token_fetch"):
                    float(s)
            with span("loop_sweep"):
                time.sleep(0.001)
        time.sleep(0.002)
    rec.stop()
    src = sorted(glob.glob(os.path.join(log, "plugins", "profile", "*",
                                        "*.xplane.pb")))[-1]
    shutil.copy(src, out)
    shutil.rmtree(log, ignore_errors=True)
    nm = _named.Named(out)
    for op in nm.ops[0][:8]:
        print("OP", op)
    for sp in nm.spans:
        print("SPAN", sp)
    tr = ptrace.Trace.from_file(out)
    lo, hi = tr.span()
    idle = ptrace.gaps(tr.devices[0].busy(lo, hi), lo, hi)
    lag = _named.device_clock_lag([e for s, e in idle if e - s > 5e-4],
                                  nm.enqueues)
    print("by scope", nm.by_scope(r"^jit_step"), "device clock lag", lag,
          "idle by phase", _named.idle_by_phase(
              [(s + lag, e + lag) for s, e in idle], nm),
          os.path.getsize(out), "bytes")


if __name__ == "__main__":
    main(sys.argv[1])
