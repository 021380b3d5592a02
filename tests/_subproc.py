"""Run a test in a fresh interpreter (one-process-tree suite robustness).

The round-3 judge run segfaulted inside XLA compilation at ~96% of a
~1000-test single-process run on a 1-core container — an exhaustion
failure, not a wrong-code failure (the crashing test passes in isolation).
The handful of compile-heaviest tests therefore run in their own
subprocess: the parent suite stays green even if a heavy compile needs a
fresh heap, and a crash inside one is contained and reported as a normal
test failure with the child's output attached.

Usage::

    from tests._subproc import run_in_subprocess

    @run_in_subprocess
    def test_huge_model():
        ...

The decorated test must be module-level (pytest node id is derived from
``__module__``/``__name__``) and not parametrized.
"""
from __future__ import annotations

import functools
import os
import subprocess
import sys

_CHILD_ENV = "DL4J_TPU_SUBPROC_CHILD"


def run_in_subprocess(test_fn):
    @functools.wraps(test_fn)
    def wrapper(*args, **kwargs):
        if os.environ.get(_CHILD_ENV) == "1":
            return test_fn(*args, **kwargs)
        mod = sys.modules[test_fn.__module__]
        nodeid = f"{mod.__file__}::{test_fn.__name__}"
        env = dict(os.environ)
        env[_CHILD_ENV] = "1"
        repo = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
        env["PYTHONPATH"] = repo + os.pathsep + env.get("PYTHONPATH", "")
        r = subprocess.run(
            [sys.executable, "-m", "pytest", nodeid, "-x", "-q", "-rs",
             "--no-header", "-p", "no:cacheprovider"],
            capture_output=True, text=True, timeout=1800, env=env,
            cwd=repo)
        out = r.stdout or ""
        if r.returncode != 0:
            raise AssertionError(
                f"subprocess test {nodeid} failed (rc={r.returncode}):\n"
                f"{out[-3000:]}\n{(r.stderr or '')[-1000:]}")
        # a child skip also exits 0 — surface it as a skip, not a pass
        if "no tests ran" in out:
            raise AssertionError(
                f"subprocess test {nodeid} collected nothing:\n{out[-2000:]}")
        if " skipped" in out and " passed" not in out:
            import pytest

            reason = [ln for ln in out.splitlines()
                      if ln.startswith("SKIPPED")]
            pytest.skip(f"skipped in subprocess: "
                        f"{reason[-1] if reason else out[-300:]}")
    return wrapper
