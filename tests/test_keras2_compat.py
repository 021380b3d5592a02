"""Keras-2 artifact compatibility (ref: the reference's Keras import
targets Keras 1/2 H5 files — SURVEY D12 `KerasModelImport`).

The main keras suite runs under whatever Keras generation the process
loaded (Keras 3, or legacy tf_keras when HF transformers imported
first). This module pins BOTH generations explicitly: a subprocess with
``TF_USE_LEGACY_KERAS=1`` re-runs representative import tests so every
H5 under test is a genuine Keras-2 artifact (different inbound-node
encoding — call-kwarg tensors, ``:0`` weight suffixes, sublayer paths).
The full suite passes under the flag too (verified 2026-08-01); this
subset keeps CI time bounded."""
import os
import subprocess
import sys

import pytest

_REPRESENTATIVE = [
    "tests/test_keras_import.py::test_sequential_dense",
    "tests/test_keras_import.py::test_sequential_cnn_with_bn",
    "tests/test_keras_import.py::test_multihead_cross_attention",
    "tests/test_keras_import.py::test_conv2d_transpose_dilation",
    "tests/test_keras_import.py::test_convlstm2d_tanh_recurrent_activation",
]


@pytest.mark.slow
def test_import_suite_under_legacy_keras2():
    env = dict(os.environ)
    env["TF_USE_LEGACY_KERAS"] = "1"
    env["JAX_PLATFORMS"] = "cpu"
    r = subprocess.run(
        [sys.executable, "-m", "pytest", "-q", *_REPRESENTATIVE],
        cwd=os.path.dirname(os.path.dirname(os.path.abspath(__file__))),
        capture_output=True, text=True, timeout=240, env=env)
    assert r.returncode == 0, (
        f"Keras-2 compat subset failed:\n{r.stdout[-2000:]}\n"
        f"{r.stderr[-1000:]}")
