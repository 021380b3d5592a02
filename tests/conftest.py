"""Test harness config: force an 8-device virtual CPU mesh so sharding /
multi-chip paths are exercised without TPU hardware (the analog of the
reference's localhost-Aeron / local[N]-Spark test trick, SURVEY.md §4).

These are environment variables jax reads at import, so they must be set
before ``import jax`` and are inherited by every child a test spawns. The
chip is reached only through ``chip_smoke.py`` under the chip tool, never
from this suite.
"""
import contextlib
import faulthandler
import hashlib
import os
import signal
import sys
import tempfile
import threading
import time
import traceback

os.environ["JAX_PLATFORMS"] = "cpu"
os.environ.setdefault("JAX_NUM_CPU_DEVICES", "8")
# every deploy places the persistent compile cache (<repo>/.jax_cache by
# default); the suite keeps it off so no run depends on what an earlier run
# left on disk. The cache tests turn it back on for their own children.
os.environ.setdefault("JAX_ENABLE_COMPILATION_CACHE", "false")
# XLA:CPU stands in for the chip here: the suite checks what the programs
# compute, not how fast LLVM makes them, and most of its CPU time is LLVM
# optimizing programs that then run once, on a handful of elements. Level 0
# leaves XLA's own passes (fusion, layout, sharding) as they are; it took
# 40% off the CPU-seconds of test_hybrid_lm + test_dl4j_zip (PR 29). It
# goes after whatever XLA_FLAGS the caller set (the last one given wins), so
# the suite compiles the same code, at the same cost, whatever the ambient
# variable holds.
_LEVEL_0 = "--xla_backend_optimization_level=0"
if not os.environ.get("XLA_FLAGS", "").endswith(_LEVEL_0):  # a worker's
    os.environ["XLA_FLAGS"] = " ".join(                     # has it already
        filter(None, (os.environ.get("XLA_FLAGS"), _LEVEL_0)))

import pytest  # noqa: E402

# One limit for every test, set-up and teardown included. A test that needs
# longer is `slow`. Every wait a test sets for itself stays under it, so the
# wait fails by its own assertion first.
TEST_LIMIT_S = 300.0
# A main thread blocked in native code never runs the alarm's handler: this
# much later the worker is killed, xdist reports the test as crashed, starts
# a new worker and the run goes on. (The interpreter has ONE such timer: a
# run given pytest's ``faulthandler_timeout`` keeps the alarm, not the kill.)
KILL_AFTER_S = 60.0

_limits = []        # the limits in force, innermost last: [alarm_at, kill_at]


def _all_stacks() -> str:
    names = {t.ident: t.name for t in threading.enumerate()}
    return "\n".join(
        f"--- thread {names.get(ident, '?')} ({ident})\n"
        + "".join(traceback.format_stack(frame))
        for ident, frame in sys._current_frames().items())


def _arm():
    """(Re)start both timers for the innermost limit, or stop them."""
    faulthandler.cancel_dump_traceback_later()
    if not _limits:
        signal.setitimer(signal.ITIMER_REAL, 0)
        return
    alarm_at, kill_at = _limits[-1]
    now = time.monotonic()
    # an alarm that has rung stays off: what is left of the test (its
    # ``finally`` blocks, its teardown) has until the kill
    signal.setitimer(signal.ITIMER_REAL,
                     0 if alarm_at is None else max(alarm_at - now, 1e-3))
    faulthandler.dump_traceback_later(max(kill_at - now, 1e-3), exit=True)


@contextlib.contextmanager
def time_limit(seconds: float):
    """Fail the test whose body is still running after ``seconds``, with
    every thread's stack in the failure; kill the process ``KILL_AFTER_S``
    seconds later if by then the body has still not ended (a main thread
    blocked in native code, a ``finally`` or a teardown that waits in its
    turn). Main thread only; nests (the inner limit hands the timers back
    to the outer one)."""
    alarm_at = time.monotonic() + seconds
    mine = [alarm_at, alarm_at + KILL_AFTER_S]

    def expired(signum, frame):
        mine[0] = None
        pytest.fail(f"still running after its limit of {seconds:g} s\n"
                    + _all_stacks(), pytrace=False)

    _limits.append(mine)
    previous = signal.signal(signal.SIGALRM, expired)
    _arm()
    try:
        yield
    finally:
        _limits.remove(mine)
        signal.signal(signal.SIGALRM, previous)
        _arm()


_worker_died = pytest.StashKey[bool]()


@pytest.hookimpl(wrapper=True)
def pytest_runtest_protocol(item, nextitem):
    # under ``--dist loadfile`` xdist hands a dead worker's file, the test it
    # died in included, to the worker that replaces it, and gives the whole
    # run up after a few deaths. A test leaves a marker while it runs, so the
    # one a worker died in is found and failed by the next, not run again.
    marker = None
    worker = getattr(item.config, "workerinput", None)  # an xdist worker's
    if worker:
        test = hashlib.sha1(item.nodeid.encode()).hexdigest()
        marker = os.path.join(tempfile.gettempdir(),
                              f"dl4j-tier1-{worker['testrunuid']}-{test}")
        item.stash[_worker_died] = os.path.exists(marker)
        open(marker, "w").close()
    try:
        with time_limit(TEST_LIMIT_S):
            return (yield)
    finally:
        if marker:
            with contextlib.suppress(FileNotFoundError):
                os.remove(marker)


@pytest.hookimpl(tryfirst=True)
def pytest_runtest_setup(item):
    if item.stash.get(_worker_died, False):
        pytest.fail("a worker died while it ran this test, killed at the "
                    "limit or crashed: not run a second time", pytrace=False)


@pytest.hookimpl(trylast=True)
def pytest_exception_interact():
    # pytest's own faulthandler plugin cancels every pending
    # ``dump_traceback_later`` when a test fails (it may be about to open a
    # debugger): start the kill timer again for the teardown that follows
    _arm()


@pytest.fixture(autouse=True, scope="session")
def _backend_is_up():
    """The CPU backend is up before a process's first test, whichever file
    that is. The observers (``device_memory.initialized_devices``, the cost
    model's ``_device_kind``) never start one, so a file of stub models that
    ran first priced its batches against no device: every batch failed its
    breaker, and how many requests the open breaker then refused was a
    matter of load (``test_qos.py``, first on a worker)."""
    import jax

    jax.devices()


@pytest.fixture(autouse=True)
def _fixed_seed():
    from deeplearning4j_tpu.ndarray import random as rng
    rng.set_seed(12345)
    yield


def _rss_mib() -> float:
    try:
        with open("/proc/self/statm") as f:
            return int(f.read().split()[1]) * os.sysconf("SC_PAGE_SIZE") / 2**20
    except Exception:
        return 0.0


# RSS (MiB) past which a module's teardown drops every compiled program.
# The allocator keeps what was freed, so RSS does not fall after a clear: the
# floor moves up to what the process holds then plus this much, and the next
# clear comes only when the room the last one made is used up.
_CLEAR_ABOVE_MIB = 2500.0
_CLEAR_AGAIN_AFTER_MIB = 256.0
_clear_floor = [_CLEAR_ABOVE_MIB]


_PROCESS_WIDE_STATE = (
    ("deeplearning4j_tpu.resilience.faults", "reset"),
    ("deeplearning4j_tpu.observability.cost_model", "reset_global_cost_model"),
    ("deeplearning4j_tpu.observability.registry", "reset_global_registry"),
)


@pytest.fixture(autouse=True, scope="module")
def _module_hygiene():
    """Per-module teardown: stop leaked serve threads, forget what the
    process counted, and bound memory.

    Six xdist workers each keep every compiled program of every module they
    ran (``test_op_conformance`` alone leaves 3 GiB of one-off programs).
    Dropping them costs the next module its eager primitives again, so it
    is done only past ``_CLEAR_ABOVE_MIB``, and then only as often as the
    memory grows back."""
    yield
    import gc

    try:
        from deeplearning4j_tpu.parallel.inference import ParallelInference
        ParallelInference.shutdown_all()
    except Exception:
        pass
    try:
        gen = sys.modules.get("deeplearning4j_tpu.parallel.generation")
        if gen is not None:          # never import it just to shut it down
            gen.GenerationPipeline.shutdown_all()
    except Exception:
        pass
    # what the process counts (metrics, injected faults and their events,
    # priced programs) starts from nothing in every module: under
    # ``--dist loadfile`` which files share a worker changes from run to
    # run, and a module that sheds or injects on purpose must not decide
    # the one after it, which reads the same counters
    for module, reset in _PROCESS_WIDE_STATE:
        loaded = sys.modules.get(module)
        if loaded is not None:       # never import it just to reset it
            getattr(loaded, reset)()
    if _rss_mib() > _clear_floor[0]:
        import jax

        jax.clear_caches()
        gc.collect()
        _clear_floor[0] = _rss_mib() + _CLEAR_AGAIN_AFTER_MIB
