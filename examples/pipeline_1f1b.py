"""Pipeline parallelism with the 1F1B schedule (net-new vs the reference —
SURVEY P5 lists pipelining as ABSENT upstream).

The flagship TransformerLM turns pipelining on with two config fields:
``pipeline_stages=S`` splits the block stack over the ``stage`` mesh axis,
and ``pipeline_schedule`` picks how the backward runs:

- ``"gpipe"``  — differentiate the whole schedule (autodiff through the
  ppermute ring); simple, but reverse-mode keeps every micro-batch's
  activations live.
- ``"1f1b"``   — a custom-vjp backward runs the classic one-forward-
  one-backward wavefront: micro-batch m's backward starts the tick its
  forward leaves the last stage, so per-stage live activations are
  bounded by the pipeline depth (XLA memory_analysis: constant in the
  micro-batch count; see benchmarks/RESULTS.md).

Both produce the same gradients (tests/test_parallel.py::Test1F1B).

Needs 8 devices; on a virtual mesh:
  JAX_PLATFORMS=cpu JAX_NUM_CPU_DEVICES=8 python examples/pipeline_1f1b.py
"""
import jax
import jax.numpy as jnp
import numpy as np
import optax

from deeplearning4j_tpu.models.transformer import (TransformerConfig,
                                                   TransformerLM)
from deeplearning4j_tpu.parallel.mesh import DATA_AXIS, STAGE_AXIS, MeshSpec

mesh = MeshSpec({STAGE_AXIS: 4, DATA_AXIS: 2}).build(jax.devices()[:8])
cfg = TransformerConfig(vocab_size=256, n_layers=4, n_heads=4, d_model=64,
                        max_len=32, pipeline_stages=4, microbatches=8,
                        pipeline_schedule="1f1b")
model = TransformerLM(cfg, mesh)
params = jax.device_put(model.init_params(jax.random.key(0)),
                        model.param_shardings(mesh))
opt = optax.adamw(1e-3)
opt_state = jax.jit(opt.init)(params)
step = model.make_train_step(opt)

rng = np.random.default_rng(0)
toks = jnp.asarray(rng.integers(0, 256, (16, 32)), jnp.int32)
tgts = jnp.roll(toks, -1, axis=1)

for i in range(5):
    params, opt_state, loss = step(params, opt_state, toks, tgts)
    print(f"step {i}: loss {float(loss):.4f}")
print("1F1B pipeline (4 stages x dp=2) trains — loss decreasing:",
      "OK" if float(loss) < 6.0 else "check config")
