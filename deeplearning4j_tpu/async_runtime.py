"""Async hot-path configuration — one home for the knobs and kill switch.

The training and serving hot paths overlap host work with device work
(classic input-pipeline / transfer-compute overlap, Abadi et al.
arXiv:1605.08695 §4.2, Awan et al. arXiv:1810.11112):

- ``DevicePrefetchIterator`` (data/iterators.py) moves batch *k+1* to the
  device while step *k* computes;
- the fit loops (nn/multilayer.py, nn/graph.py) defer the blocking
  ``float(loss)`` fetch so JAX's async dispatch keeps several steps
  enqueued instead of round-tripping per step;
- ``ParallelInference`` (parallel/inference.py) runs a batcher →
  dispatcher → completer pipeline with several device batches in flight
  and pads to power-of-two shape buckets instead of ``batch_limit``.

Kill switch: ``DL4J_TPU_ASYNC=0`` restores the fully synchronous
behavior everywhere (one batch in flight, per-step loss sync,
pad-to-``batch_limit`` serving). All values are read per call so tests
can flip them with ``monkeypatch.setenv``.

Knobs (env var → default):

============================  =======  ==========================================
``DL4J_TPU_ASYNC``            ``1``    master switch; ``0`` = fully synchronous
``DL4J_TPU_PREFETCH_DEPTH``   ``2``    device batches buffered ahead of the step
``DL4J_TPU_SCORE_EVERY``      ``16``   steps between loss materializations
``DL4J_TPU_INFLIGHT``         ``2``    serving batches dispatched but uncompleted
============================  =======  ==========================================

The persistent XLA compile cache has no knob of its own: jax's
``JAX_COMPILATION_CACHE_DIR`` places it, see :func:`configure_compile_cache`.

Because the async pipelines are exactly what a hung run was doing when it
hung, :func:`snapshot` returns every live knob value — the flight recorder
(observability/flight_recorder.py) folds it into each postmortem bundle.
Related observability knobs (read by that package, listed here for one
discoverable table; the full reference lives in README "Environment knob
reference" and is lint-enforced by ``tools/check_env_knobs.py``):
``DL4J_TPU_TRACE=0`` disables span recording while metrics stay live,
``DL4J_TPU_HANG_SECONDS`` sets the no-progress watchdog threshold
(default 300), ``DL4J_TPU_POSTMORTEM_DIR`` the bundle directory,
``DL4J_TPU_POSTMORTEM_KEEP`` the retained-bundle cap (default 8),
``DL4J_TPU_FLIGHT_RECORDER=0`` disables the watchdog + crash hooks,
``DL4J_TPU_POSTMORTEM_ON_EXIT=1`` dumps a bundle at interpreter exit,
``DL4J_TPU_COMPILE_WATCH=0`` disables the trace/compile accounting,
``DL4J_TPU_NUMERICS=0`` keeps the in-graph numerics health out of newly
traced train steps, and ``DL4J_TPU_NUMERICS_SKIP=1`` opts into skipping
the optimizer update on non-finite gradients. The numerics fetch cadence
deliberately has NO knob of its own: it rides ``DL4J_TPU_SCORE_EVERY``
(one sync schedule, one mental model).
"""
from __future__ import annotations

import os


def async_enabled() -> bool:
    """The documented kill switch (read per call so tests can flip it)."""
    return os.environ.get("DL4J_TPU_ASYNC", "1") != "0"


def _int_env(name: str, default: int, floor: int = 1) -> int:
    try:
        return max(floor, int(os.environ.get(name, default)))
    except (TypeError, ValueError):
        return default


def prefetch_depth() -> int:
    """Device batches the prefetch thread keeps ready ahead of the step."""
    return _int_env("DL4J_TPU_PREFETCH_DEPTH", 2)


def score_sync_every() -> int:
    """Steps between blocking loss materializations in a deferred fit loop.
    Bounds how far the host can run ahead of the device (and how stale
    ``score()`` can be mid-epoch); the fetch always happens at epoch end."""
    return _int_env("DL4J_TPU_SCORE_EVERY", 16)


def inflight_limit() -> int:
    """Serving pipeline depth: device batches dispatched but not yet
    completed (dispatch batch k+1 while k's results transfer back)."""
    return _int_env("DL4J_TPU_INFLIGHT", 2)


#: where compiles persist when the environment does not say. A FIXED path
#: inside the checkout: the directory is part of jax's cache key, so one
#: named after a state dir, a pid or a time never hits.
DEFAULT_COMPILE_CACHE_DIR = os.path.join(
    os.path.dirname(os.path.dirname(os.path.abspath(__file__))), ".jax_cache")

_cache_configured = False


def configure_compile_cache() -> str:
    """The ONE place the persistent compilation cache is placed; every
    entry point that compiles calls it once at start (idempotent). Returns
    the directory in force.

    ``JAX_COMPILATION_CACHE_DIR`` set: jax already reads it, and the
    program sets no directory in code. Unset: ``DEFAULT_COMPILE_CACHE_DIR``.
    The min-compile-time / min-entry-size gates are zeroed so every
    serving-bucket executable is eligible (the point is skipping the
    small-but-many bucket compiles). An operation's metadata (its
    ``op_name`` with the model's named scopes, its source line) is part of
    the key: by jax's default it is not, and a hit then hands back an
    executable with whatever names its first compile had - a profile of
    this program would be read under another program's scopes, or none.
    Failures propagate: an entry point
    that believes its compiles persist when they do not pays every cold
    start in full and never says so."""
    global _cache_configured
    import jax

    if not _cache_configured:
        if not os.environ.get("JAX_COMPILATION_CACHE_DIR"):
            jax.config.update("jax_compilation_cache_dir",
                              DEFAULT_COMPILE_CACHE_DIR)
        jax.config.update("jax_persistent_cache_min_compile_time_secs", 0.0)
        jax.config.update("jax_persistent_cache_min_entry_size_bytes", -1)
        jax.config.update("jax_compilation_cache_include_metadata_in_key",
                          True)
        # jax memoizes its cache decision at the FIRST backend compile; a
        # caller that compiled before configuring (model init, the normal
        # order) would otherwise never engage the dir. The reset drops only
        # that memo — jit dispatch caches survive.
        from jax._src import compilation_cache as _cc
        _cc.reset_cache()
        _cache_configured = True
    return jax.config.jax_compilation_cache_dir


def snapshot() -> dict:
    """Every live knob value — the async-runtime half of a postmortem
    bundle (a hang report without the pipeline depths that shaped the hang
    is not actionable)."""
    import jax

    out = {
        "async_enabled": async_enabled(),
        "prefetch_depth": prefetch_depth(),
        "score_sync_every": score_sync_every(),
        "inflight_limit": inflight_limit(),
        # the directory in force (None until an entry point placed it)
        "compile_cache_dir": jax.config.jax_compilation_cache_dir,
    }
    try:
        # the observatory switches shape what a wedged step was computing
        # (numerics terms in-graph?) and what the bundle can explain
        # (retraces counted?) — resolve their live values here too
        from deeplearning4j_tpu.observability.compile_watch import (
            compile_watch_enabled)
        from deeplearning4j_tpu.observability.numerics import (
            numerics_enabled, skip_on_nonfinite)
        out["compile_watch_enabled"] = compile_watch_enabled()
        out["numerics_enabled"] = numerics_enabled()
        out["numerics_skip_on_nonfinite"] = skip_on_nonfinite()
    except Exception:
        pass
    try:
        # resilience posture: whether policies were armed, what chaos was
        # configured, and the default serving deadline — a hang under
        # injected faults must say so in the bundle
        from deeplearning4j_tpu.resilience.elastic import elastic_enabled
        from deeplearning4j_tpu.resilience.faults import resilience_enabled
        from deeplearning4j_tpu.resilience.policy import default_deadline_ms
        out["resilience_enabled"] = resilience_enabled()
        out["fault_spec"] = os.environ.get("DL4J_TPU_FAULTS", "")
        out["default_deadline_ms"] = default_deadline_ms()
        # elastic posture: whether host loss is a restorable fault here
        out["elastic_enabled"] = elastic_enabled()
    except Exception:
        pass
    return out


def default_buckets(batch_limit: int) -> tuple:
    """Power-of-two padding buckets up to and including ``batch_limit``.

    Each bucket is one compiled executable; padding to the next bucket
    instead of to ``batch_limit`` trades a small bounded set of compiles
    (log2(limit) + 1) for far less padded compute at partial occupancy.
    """
    out, b = [], 1
    while b < batch_limit:
        out.append(b)
        b <<= 1
    out.append(batch_limit)
    return tuple(sorted(set(out)))
