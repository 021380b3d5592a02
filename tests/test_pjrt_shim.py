"""PJRT C-API shim tests (SURVEY N5 — the nd4j-tpu native runtime layer).

What is verifiable without TPU hardware:
- the C++ shim builds and loads;
- it dlopens a real PJRT plugin (the bundled ``libtpu.so``) and reads its
  PJRT_Api version table (GetPjrtApi is hardware-free);
- error paths surface as clean Python exceptions, not crashes.

Client creation takes the chip where there is one and LOG(FATAL)s where
there is none, so no test and no child of a test creates a client: the
compile/transfer/execute cycle is not covered here. (The in-framework
compute path does not depend on this shim — it exists for non-Python
frontend parity, SURVEY N5.)
"""
import os
import subprocess
import sys

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


_LOAD_ONLY = r"""
import sys
sys.path.insert(0, "__REPO__")
from deeplearning4j_tpu.native.pjrt import PjrtPlugin, default_tpu_plugin_path

major, minor = PjrtPlugin(default_tpu_plugin_path()).api_version()
print(f"API={major}.{minor}", flush=True)
"""


def test_plugin_loads_in_a_fresh_interpreter_without_a_client():
    """An interpreter that never imported jax dlopens the plugin and reads
    its version table. No client: tier-1 takes no chip."""
    if default_tpu_plugin_path() is None:
        pytest.skip("libtpu not installed")
    r = subprocess.run([sys.executable, "-c",
                        _LOAD_ONLY.replace("__REPO__", REPO)],
                       capture_output=True, text=True, timeout=120)
    assert r.returncode == 0, r.stdout + r.stderr
    assert "API=" in r.stdout
