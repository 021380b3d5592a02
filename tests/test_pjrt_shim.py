"""PJRT C-API shim tests (SURVEY N5 — the nd4j-tpu native runtime layer).

What is verifiable without TPU hardware:
- the C++ shim builds and loads;
- it dlopens a real PJRT plugin (the bundled ``libtpu.so``) and reads its
  PJRT_Api version table (GetPjrtApi is hardware-free);
- error paths surface as clean Python exceptions, not crashes.

Client creation against libtpu LOG(FATAL)s on a host with no TPU, so the
full compile/transfer/execute cycle runs in a crash-tolerant SUBPROCESS: on
a TPU host it completes and its output is asserted; on a TPU-less host the
abort is tolerated and recorded. (The in-framework compute path does not
depend on this shim — it exists for non-Python frontend parity, SURVEY N5.)
"""
import os
import subprocess
import sys

import numpy as np
import pytest

from deeplearning4j_tpu.native.pjrt import (PjrtPlugin,
                                            compile_options_bytes,
                                            default_tpu_plugin_path)

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


def test_shim_builds_and_loads_libtpu_api():
    path = default_tpu_plugin_path()
    if path is None:
        pytest.skip("libtpu not installed")
    plug = PjrtPlugin(path)
    major, minor = plug.api_version()
    assert major >= 0 and minor > 0      # a real PJRT_Api version table


def test_bad_plugin_path_clean_error():
    with pytest.raises(RuntimeError, match="dlopen failed"):
        PjrtPlugin("/nonexistent/plugin.so")


def test_non_pjrt_library_clean_error():
    # a real .so without GetPjrtApi: the host-ops library itself
    from deeplearning4j_tpu.native import _build
    host_lib = _build("libdl4jtpu_host.so")
    with pytest.raises(RuntimeError, match="GetPjrtApi symbol not found"):
        PjrtPlugin(host_lib)


def test_compile_options_proto_bytes():
    b = compile_options_bytes()
    assert isinstance(b, bytes) and len(b) > 0


_FULL_CYCLE = r"""
import sys
sys.path.insert(0, "__REPO__")
import numpy as np
from deeplearning4j_tpu.native.pjrt import PjrtPlugin, default_tpu_plugin_path

plug = PjrtPlugin(default_tpu_plugin_path())
client = plug.create_client()             # LOG(FATAL)s without TPU hardware
print("PLATFORM=" + client.platform_name(), flush=True)

# StableHLO for f(x, y) = x @ y + 1 on (2,3)x(3,4)
mlir = '''
module @jit_f {
  func.func public @main(%arg0: tensor<2x3xf32>, %arg1: tensor<3x4xf32>) -> tensor<2x4xf32> {
    %0 = stablehlo.dot_general %arg0, %arg1, contracting_dims = [1] x [0] : (tensor<2x3xf32>, tensor<3x4xf32>) -> tensor<2x4xf32>
    %cst = stablehlo.constant dense<1.0> : tensor<2x4xf32>
    %1 = stablehlo.add %0, %cst : tensor<2x4xf32>
    return %1 : tensor<2x4xf32>
  }
}
'''
exe = client.compile_mlir(mlir)
rng = np.random.default_rng(0)
x = rng.normal(size=(2, 3)).astype(np.float32)
y = rng.normal(size=(3, 4)).astype(np.float32)
(out,) = exe.execute([x, y], [(2, 4)])
np.testing.assert_allclose(out, x @ y + 1.0, rtol=1e-5)
print("FULL_CYCLE_OK", flush=True)
"""


def test_full_cycle_subprocess_tolerant():
    if default_tpu_plugin_path() is None:
        pytest.skip("libtpu not installed")
    r = subprocess.run([sys.executable, "-c",
                        _FULL_CYCLE.replace("__REPO__", REPO)],
                       capture_output=True, text=True, timeout=300)
    if "FULL_CYCLE_OK" in r.stdout:
        assert "PLATFORM=" in r.stdout     # real end-to-end PJRT run
    else:
        # no TPU on this host: libtpu aborts during client create —
        # the shim must have gotten that far (plugin loaded in-process)
        assert r.returncode != 0
