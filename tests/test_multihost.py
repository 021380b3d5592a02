"""Two-process DCN/multi-host convergence-parity test (VERDICT r1 item 6).

The multi-host analog of the reference's localhost-Aeron gradient-sharing
tests (``GradientSharingTrainingTest`` runs the full distributed stack over
loopback — SURVEY §4(d)): two REAL jax processes bootstrap through
``DistributedConfig`` (the VoidConfiguration analog), form one global
4-device mesh, and train via ``ShardedTrainer`` with GSPMD allreduce
crossing the process boundary. Parity gate: final params must match a
single-process 4-device run on the same global batches.
"""
import os
import socket
import subprocess
import sys
import time

import numpy as np
import pytest

from deeplearning4j_tpu.parallel import master as _master

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
WORKER = os.path.join(REPO, "tests", "multihost_worker.py")


@pytest.fixture(scope="module")
def _needs_multiprocess_collectives():
    """Gate for the real cross-process tests: this container's jax
    bootstraps ``jax.distributed`` fine but cannot RUN a multi-process
    CPU computation — the runtime capability probe (a 2-process loopback
    psum, cached per process) decides, so the tests skip with the actual
    backend error instead of failing tier-1."""
    supported, reason = _master.multiprocess_cpu_collectives_supported()
    if not supported:
        pytest.skip(f"multiprocess CPU collectives unavailable: {reason}")
    return reason


def test_capability_probe_is_exercised(monkeypatch):
    """The probe itself must run (not silently default): it returns a
    verdict + a human-readable reason, caches per process, and honors
    the DL4J_TPU_MULTIHOST_PROBE override in both directions. An
    operator's pre-set override is neutralized via monkeypatch (and
    restored after) so the REAL probe is exercised either way."""
    monkeypatch.delenv("DL4J_TPU_MULTIHOST_PROBE", raising=False)
    # bounded: a box where the loopback probe HANGS must cost this test
    # ~1 min, not the default 2 (the verdict is cached for the gated
    # tests either way, and a timeout grades as unsupported)
    supported, reason = _master.multiprocess_cpu_collectives_supported(
        timeout_s=60.0)
    assert isinstance(supported, bool)
    assert isinstance(reason, str) and reason
    if not supported:
        # the skip must name the failure, not just shrug
        assert "psum" in reason or "Error" in reason or "error" in reason \
            or "timeout" in reason
    # cached: the second call returns the same object, no new subprocesses
    assert _master.multiprocess_cpu_collectives_supported() \
        == (supported, reason)
    assert _master._MULTIPROC_PROBE == (supported, reason)
    # the override bypasses (and does not clobber) the cached probe
    monkeypatch.setenv("DL4J_TPU_MULTIHOST_PROBE", "0")
    forced, why = _master.multiprocess_cpu_collectives_supported()
    assert forced is False and "DL4J_TPU_MULTIHOST_PROBE" in why
    monkeypatch.setenv("DL4J_TPU_MULTIHOST_PROBE", "1")
    forced, why = _master.multiprocess_cpu_collectives_supported()
    assert forced is True and "DL4J_TPU_MULTIHOST_PROBE" in why
    monkeypatch.delenv("DL4J_TPU_MULTIHOST_PROBE")
    assert _master._MULTIPROC_PROBE == (supported, reason)


def _free_port():
    s = socket.socket()
    s.bind(("127.0.0.1", 0))
    port = s.getsockname()[1]
    s.close()
    return port


def _clean_env():
    env = dict(os.environ)
    # the workers pick their own platform/devices; scrub the conftest pins
    env.pop("JAX_PLATFORMS", None)
    env.pop("JAX_NUM_CPU_DEVICES", None)
    env["PYTHONPATH"] = REPO + os.pathsep + env.get("PYTHONPATH", "")
    return env


def _communicate_all(procs, seconds, what, wait_for=None):
    """The output of every rank in ``wait_for`` (default: all of them), all
    inside ONE limit; a rank still running at the limit fails the test, and
    no rank of ``procs`` outlives the call either way."""
    deadline = time.monotonic() + seconds
    try:
        outs = []
        for p in (procs if wait_for is None else wait_for):
            try:
                out, _ = p.communicate(
                    timeout=max(deadline - time.monotonic(), 0.1))
            except subprocess.TimeoutExpired:
                pytest.fail(f"{what}: a worker had not ended after "
                            f"{seconds:g} s")
            outs.append(out)
        return outs
    finally:
        # never leak a worker blocked in a cross-process collective
        for p in procs:
            if p.poll() is None:
                p.kill()
                p.communicate(timeout=30)


@pytest.mark.parametrize("nprocs", [2, 4])
def test_n_process_training_matches_single_process(
        tmp_path, nprocs, _needs_multiprocess_collectives):
    """nprocs x 2 virtual devices = one DCN mesh; parity vs a single process
    with the same global device count (VERDICT r2 #7: 2- AND 4-process)."""
    port = _free_port()
    out_n = str(tmp_path / f"params_{nprocs}proc.npy")
    env = _clean_env()

    procs = [subprocess.Popen(
        [sys.executable, WORKER, str(i), str(nprocs), str(port), out_n],
        stdout=subprocess.PIPE, stderr=subprocess.STDOUT, text=True, env=env)
        for i in range(nprocs)]
    outs = _communicate_all(procs, 120.0, f"{nprocs}-process multihost")
    for i, (p, out) in enumerate(zip(procs, outs)):
        assert p.returncode == 0, f"worker {i} failed:\n{out[-4000:]}"

    # single-process reference on the same global device count + batches
    ndev = 2 * nprocs
    ref_out = str(tmp_path / "params_1proc.npy")
    single = subprocess.run(
        [sys.executable, "-c", f"""
import os
os.environ["JAX_PLATFORMS"] = "cpu"
os.environ["JAX_NUM_CPU_DEVICES"] = "{ndev}"
import jax
import numpy as np
import sys
sys.path.insert(0, {REPO!r})
sys.argv = ["single"]
from tests.multihost_worker import build_net, global_data
from deeplearning4j_tpu.parallel import MeshSpec
from deeplearning4j_tpu.parallel.trainer import ShardedTrainer
net = build_net()
tr = ShardedTrainer(net, MeshSpec.data_parallel())
for step in range(5):
    x, y = global_data(step)
    tr.fit(x, y)
np.save({ref_out!r}, np.asarray(net.params().buf()))
"""],
        capture_output=True, text=True, env=env, timeout=120)
    assert single.returncode == 0, single.stderr[-4000:]

    np.testing.assert_allclose(np.load(out_n), np.load(ref_out),
                               rtol=1e-5, atol=1e-6)


ELASTIC = os.path.join(REPO, "tests", "elastic_worker.py")


def _run_elastic(nsteps, port, ckpt_dir, out, die_at=-1, expect_kill=False):
    timeout = 90.0          # three runs a test, inside the per-test limit
    env = _clean_env()
    procs = [subprocess.Popen(
        [sys.executable, ELASTIC, str(i), "2", str(port), ckpt_dir, out,
         str(nsteps), str(die_at)],
        stdout=subprocess.PIPE, stderr=subprocess.STDOUT, text=True, env=env)
        for i in range(2)]
    if not expect_kill:
        outs = _communicate_all(procs, timeout, "elastic")
        for i, (p, o) in enumerate(zip(procs, outs)):
            assert p.returncode == 0, f"elastic worker {i}:\n{o[-4000:]}"
        return outs
    # fault arm: worker 1 SIGKILLs itself; worker 0 then hangs in the next
    # collective and is reaped by the helper (the Spark-analog "job fails,
    # restart from checkpoint" path)
    (o1,) = _communicate_all(procs, timeout, "fault-arm worker 1",
                             wait_for=procs[1:])
    assert procs[1].returncode == -9, \
        f"worker1 expected SIGKILL, rc={procs[1].returncode}:\n{o1[-2000:]}"
    return None


def test_sigkill_mid_run_then_resume_matches_uninterrupted(
        tmp_path, _needs_multiprocess_collectives):
    """Fault injection: SIGKILL one worker mid-run, restart BOTH ranks from
    the newest checkpoint, finish — final params must equal an
    uninterrupted run's (deterministic step-keyed data schedule)."""
    nsteps = 6

    # uninterrupted reference run
    ref_dir = str(tmp_path / "ckpt_ref")
    os.makedirs(ref_dir)
    ref_out = str(tmp_path / "ref.npy")
    _run_elastic(nsteps, _free_port(), ref_dir, ref_out)

    # fault run: worker1 dies after step 2's checkpoint
    dir2 = str(tmp_path / "ckpt_fault")
    os.makedirs(dir2)
    out2 = str(tmp_path / "fault.npy")
    _run_elastic(nsteps, _free_port(), dir2, out2, die_at=2,
                 expect_kill=True)
    ckpts = [n for n in os.listdir(dir2) if n.endswith(".zip")]
    assert ckpts, "no checkpoint survived the kill"
    assert not os.path.exists(out2), "fault run must not have finished"

    # restart both ranks on a fresh coordinator; resume from checkpoint
    _run_elastic(nsteps, _free_port(), dir2, out2)

    np.testing.assert_allclose(np.load(out2), np.load(ref_out),
                               rtol=1e-5, atol=1e-6)
