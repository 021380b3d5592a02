"""GenerationPipeline: continuous batching for autoregressive decode.

The serving half of the generative decode path (the model half is
``models/generation.py``). ``ParallelInference``'s batcher coalesces
*one-shot* requests into padded windows; generation is different — a
request occupies device batch space for its whole multi-step lifetime,
and windowed batching makes every member wait on the window's LONGEST
member before any slot frees. Continuous batching fixes exactly that:

- the decode batch is a fixed set of ``slots`` (one compiled
  ``decode_step`` executable over all of them, occupied or not);
- a finished/shed request frees its slot **at the step boundary**, and a
  queued request joins in the freed slot immediately — its prefill runs
  and its k/v land in that slot's cache pages
  (``DecodeEngine.insert_slot``) while every other slot keeps decoding
  on the next step;
- steady-state decode triggers **zero** new XLA traces (fixed shapes
  throughout; pinned via ``compile_watch`` counters in tests).

The PR-5 policies apply unchanged: per-request deadlines (shed at
admission, at the step boundary, and by the caller's walk-away),
bounded-queue shedding (``reject_newest``/``reject_oldest``), a circuit
breaker on the decode device path, transient-fault retries under a
budget, and exactly-once resolution through the shared
``_Request.claim()``. Chaos point ``generation.step`` fires once per
step boundary. Trace phases per request: ``slot_wait`` (enqueue → slot
granted), ``prefill``, and a batch-level ``decode_step`` span per step.

Metrics (``dl4j_decode_*``): generated tokens, slot occupancy,
prefill/decode latency split, cache bytes, sheds, queue depth — on
``/metrics``, with decode/prefill MFU entries on ``/debug/perf`` via the
cost model, and in flight-recorder bundles (``generation.json``).

Multi-tenant QoS (kill switch ``DL4J_TPU_QOS=0``, see
``resilience/qos.py``): the slot-wait queue becomes a per-tenant DWRR
``FairQueue`` (cost = one slot per request), full-queue shedding evicts
the most over-share tenant's newest request, a higher-priority tenant
may PREEMPT a lower-tier slot at a step boundary (the victim resolves
with the typed ``PreemptedError``), and each request's tenant is charged
its emitted tokens plus prefill + per-slot decode-step FLOPs shares.

Paged admission (PR 13): with the paged engine (default), FREE PAGES —
not free slots — are the admission unit. ``cache_pages=`` bounds the
pool below the dense worst case; ``_admit`` parks a joiner the pool
cannot back yet and retries it at every step boundary, and
``_reclaim_pages`` sheds the youngest active generation with the typed
``CachePagesExhausted`` when mid-decode growth exhausts the pool
(pages return, admission resumes). Speculative engines emit 1..spec_k
tokens per step boundary; ``_sweep_finished`` consumes per-slot token
LISTS so eos/budget/deadline/stream-cancel semantics are per token,
exactly as the one-token path behaved.

Durable sessions (PR 20, kill switch ``DL4J_TPU_SESSIONS=0``, see
``serving/session.py``): every admitted generation carries a journaled
session record; the decode loop's only added cost is a list append per
token and an ``Event.set`` per step boundary. A device-level fault now
RESUMES journaled sessions in place (re-prefill of prompt + emitted —
deterministic because sampling is in-graph seeded) instead of failing
every slot; ``resume(record)`` re-enters an adopted session from
another worker's journal through the ordinary admission path; page
reclamation prefers shedding unjournaled (new) sessions over journaled
ones; and a fence-stolen session sheds typed (``session_lost``) at the
next boundary so a stalled worker can never double-decode.

One step in flight (PR 39): of a decode step's inputs only the tokens
come from the step before; positions, page tables and the step counter
are the host's own. So while every slot is occupied the loop dispatches
step k + 1 with step k's tokens still on the device
(``DecodeEngine.carry_tokens``) and only then fetches, sweeps and
publishes step k: the host's work of a pass runs beside the device's
step, not between two steps. With a free slot the loop keeps the order
dispatch, fetch, sweep, because then an arrival can join at the very
next boundary and a step already queued would stand between it and its
prefill (``_iterate``, ``_runs_ahead``).

The join, measured where it happens (PR 40): ``_start_request`` splits a
join where the program hands over to the device, by consecutive clock
reads: ``dispatch`` (pad, puts, the prefill program's call), ``insert``
(page allocation, the insert program's call) and ``fetch`` (span
``first_token_fetch`` inside ``prefill_insert``: the wait for the first
token, which since PR 39 also waits for the decode step that was in flight
when the join started). The request's ``prefill`` span carries the three
as ``dispatch_us`` / ``insert_us`` / ``fetch_us`` beside ``bucket``,
``inflight`` and ``step``; ``loop_admit`` carries ``free`` and ``queued``
on a pass that joins; ``dl4j_decode_joins_total{bucket}`` and
``dl4j_decode_join_seconds_total{phase}`` count the same without a trace,
and ``snapshot()["joins"]`` and one INFO line at shutdown name the
longest join and the part it was spent in.
"""
from __future__ import annotations

import logging
import queue
import threading
import time
import weakref
from typing import Dict, List, Optional

import numpy as np

from deeplearning4j_tpu.models.generation import (DECODE_FN, PREFILL_FN,
                                                  PROPOSE_FN, VERIFY_FN,
                                                  DecodeEngine)
from deeplearning4j_tpu.observability import compile_watch as _cw
from deeplearning4j_tpu.observability import cost_model as _cost
from deeplearning4j_tpu.observability import global_registry, on_registry_reset
from deeplearning4j_tpu.observability import span as _span
from deeplearning4j_tpu.observability.flight_recorder import (
    global_flight_recorder as _flight)
from deeplearning4j_tpu.observability.tracing import (current_context,
                                                      now_us, record_span)
from deeplearning4j_tpu.parallel.inference import _Request
from deeplearning4j_tpu.resilience import faults as _faults
from deeplearning4j_tpu.resilience import qos as _qos
from deeplearning4j_tpu.resilience.policy import (TYPED_OUTCOMES,
                                                  CachePagesExhausted,
                                                  CircuitBreaker,
                                                  CircuitOpenError, Deadline,
                                                  DeadlineExceeded,
                                                  RetryPolicy, ShedError,
                                                  ShutdownError,
                                                  default_deadline_ms)

_TYPED_OUTCOMES = TYPED_OUTCOMES

_log = logging.getLogger(__name__)

#: the disjoint phases of one decode-loop iteration, in order: admit (joins
#: and their prefills), reclaim (page backing for this step's writes),
#: dispatch (``engine.decode``: table upload, enqueue), fetch (the host
#: waits for the step's tokens), sweep (emit to every stream, resolve,
#: free), publish (step metrics, cost model, breaker, flight recorder,
#: journal, cache gauges). Their seconds sum to the loop's busy time.
_LOOP_PHASES = ("admit", "reclaim", "dispatch", "fetch", "sweep", "publish")

#: the parts of one join (``_start_request``), in order, split where the
#: program hands over to the device: dispatch (pad, puts, the prefill
#: program's call), insert (page allocation, the insert program's call, the
#: draft's insert), fetch (the wait for the first token). Consecutive clock
#: reads: they sum to the request's ``prefill`` span.
_JOIN_PARTS = ("dispatch", "insert", "fetch")


def _session_mod():
    """Lazy ``serving.session`` import: ``parallel`` must not import the
    ``serving`` package at module load (the registry there imports the
    parallel modules back) — by the time a pipeline is constructed both
    packages are fully loaded and the import is safe."""
    from deeplearning4j_tpu.serving import session
    return session


class StreamCancelled(ShedError):
    """The streaming consumer walked away (its ``on_token`` callback
    returned ``False`` or raised): the request stops decoding and its
    slot frees at the next step boundary. A typed lifecycle outcome
    (``ShedError`` subclass), never an error-rate event — a client
    closing its SSE connection is load behavior, not a model failure."""


class _GenMetrics:
    """Label-bound decode instruments (shared across instances, same
    rationale as ``_ServingMetrics``)."""

    _instance = None
    _lock = threading.Lock()

    def __init__(self):
        reg = global_registry()
        self.tokens = reg.counter(
            "dl4j_decode_tokens_total",
            "tokens emitted by the continuous-batching decode loop "
            "(rate = serving tokens/s)")
        self.steps = reg.counter(
            "dl4j_decode_steps_total",
            "decode step boundaries executed (each runs every occupied "
            "slot one token forward)")
        self.steps_ahead = reg.counter(
            "dl4j_decode_steps_ahead_total",
            "decode steps dispatched while the step before was still on "
            "the device, its tokens not yet fetched (every slot occupied): "
            "over dl4j_decode_steps_total, the share of steps whose host "
            "work ran beside the device and not between two steps")
        self.requests = reg.counter(
            "dl4j_decode_requests_total",
            "generation requests resolved (success, typed shed, or error)")
        self.errors = reg.counter(
            "dl4j_decode_errors_total",
            "generation requests that raised a non-typed error")
        shed = reg.counter(
            "dl4j_decode_shed_total",
            "generation requests shed by admission control or deadlines",
            label_names=("reason",))
        self.shed = {r: shed.labels(reason=r)
                     for r in ("queue_full", "deadline", "circuit_open",
                               "client_gone", "preempted",
                               "pages_exhausted", "session_lost")}
        self.occupancy = reg.histogram(
            "dl4j_decode_slot_occupancy_ratio",
            "occupied slots / total slots per decode step (1.0 = the "
            "device batch is full — continuous batching's win condition)",
            buckets=(0.125, 0.25, 0.375, 0.5, 0.625, 0.75, 0.875, 1.0))
        self.prefill_latency = reg.histogram(
            "dl4j_decode_prefill_seconds",
            "prompt prefill wall time (trunk forward + cache insert), "
            "per joining request")
        self.step_latency = reg.histogram(
            "dl4j_decode_step_seconds",
            "one decode step boundary's wall time (single-query "
            "attention over every occupied slot + sampling)")
        self.latency = reg.histogram(
            "dl4j_decode_latency_seconds",
            "end-to-end GenerationPipeline.generate latency (queue wait "
            "+ prefill + all decode steps)")
        self.cache_bytes = reg.gauge(
            "dl4j_decode_cache_bytes",
            "ACTUAL resident KV-cache bytes of live pipelines: paged = "
            "pages in use x page bytes (post-quantization), dense = the "
            "full preallocation")
        self.slot_state_bytes = reg.gauge(
            "dl4j_decode_slot_state_bytes",
            "device bytes of the fixed per-slot state (a recurrent "
            "layer's state and convolution tail) live pipelines hold "
            "for all their slots, beside the page pool")
        self.page_pool_bytes = reg.gauge(
            "dl4j_decode_page_pool_bytes",
            "device bytes of the whole page pool of live paged "
            "pipelines (capacity + the trash page, x page bytes)")
        pairs = reg.counter(
            "dl4j_moe_pairs_total",
            "token-expert pairs decode steps routed to an expert with "
            "weights, by whether it is held on this chip (held=\"0\": "
            "another chip's part of the result)", label_names=("held",))
        self.moe_pairs = {h: pairs.labels(held=h) for h in ("1", "0")}
        self.moe_zero = reg.counter(
            "dl4j_moe_zero_pairs_total",
            "token-expert pairs decode steps routed to an identity "
            "(zero-compute) expert: the token itself, weighted, computed "
            "where the token lives; 0 for a router without such experts")
        self.moe_touched = reg.counter(
            "dl4j_moe_experts_touched_total",
            "held experts that received at least one token, summed over "
            "expert layers and decode steps (the expert weights a step "
            "had to read)")
        self.moe_visits = reg.counter(
            "dl4j_moe_expert_visits_total",
            "times the grouped feed-forward kernel streamed an expert's "
            "weights, summed over expert layers and decode steps: equal to "
            "dl4j_moe_experts_touched_total where the kernel reads each "
            "touched expert once, 0 where lax.ragged_dot computes the "
            "experts")
        self.slots_in_use = reg.gauge(
            "dl4j_decode_slots_in_use",
            "slots occupied by in-flight generations (sampled per step "
            "boundary)")
        self.queue_depth = reg.gauge(
            "dl4j_decode_queue_depth",
            "generation requests waiting for a free slot")
        self.pages_in_use = reg.gauge(
            "dl4j_decode_pages_in_use",
            "KV-cache pages allocated to live generations across paged "
            "pipelines (the admission unit)")
        self.pages_total = reg.gauge(
            "dl4j_decode_pages_capacity",
            "KV-cache page pool capacity across live paged pipelines "
            "(gauge: _total is counter-reserved by the metric lint)")
        loop = reg.counter(
            "dl4j_decode_loop_seconds_total",
            "decode-thread seconds by phase of the loop iteration (admit, "
            "reclaim, dispatch, fetch, sweep, publish); the phases are "
            "disjoint and sum to the loop's busy time - everything but "
            "fetch is host work the device may be waiting for",
            label_names=("phase",))
        self.loop_seconds = {ph: loop.labels(phase=ph)
                             for ph in _LOOP_PHASES}
        self.prefill_stall = reg.counter(
            "dl4j_decode_prefill_stall_seconds_total",
            "seconds the decode thread spent starting a joining request "
            "(prefill, insert, first-token fetch) while at least one "
            "other slot was active: every live stream waits that long")
        self.joins = reg.counter(
            "dl4j_decode_joins_total",
            "joining requests started (prefill dispatched, cache entries "
            "inserted, first token fetched), by the padded length the "
            "prefill program ran at", label_names=("bucket",))
        join = reg.counter(
            "dl4j_decode_join_seconds_total",
            "decode-thread seconds of the joins by part: dispatch (pad, "
            "puts, the prefill program's call), insert (page allocation, "
            "the insert program's call), fetch (the wait for the first "
            "token: the device's prefill, and the decode step in flight "
            "before it); the three sum to dl4j_decode_prefill_seconds' sum",
            label_names=("phase",))
        self.join_seconds = {ph: join.labels(phase=ph)
                             for ph in _JOIN_PARTS}
        self.spec_accept = reg.gauge(
            "dl4j_spec_accept_ratio",
            "cumulative speculative-decode acceptance: accepted draft "
            "tokens / proposed (per live spec engines; 1.0 = every "
            "proposal verified)")

    @classmethod
    def get(cls) -> "_GenMetrics":
        if cls._instance is None:
            with cls._lock:
                if cls._instance is None:
                    cls._instance = cls()
        return cls._instance


@on_registry_reset
def _drop_gen_metrics():
    _GenMetrics._instance = None


class _Flight:
    """A decode step that is on the chip, its tokens not fetched yet: what
    the pass that fetches it needs from the pass that dispatched it.
    ``reqs`` is the slot table as the step saw it: a slot whose request
    has left or changed by the fetch was a row nobody reads."""

    __slots__ = ("step", "outputs", "active", "reqs", "live", "pages",
                 "window_rows", "cache_bytes")

    def __init__(self, step: int, outputs, active: List[int], reqs: list,
                 live: int, pages: int, window_rows: int, cache_bytes: int):
        self.step = step
        self.outputs = outputs      # (tokens [+ counts], logits), on device
        self.active = active
        self.reqs = reqs
        self.live = live
        self.pages = pages
        self.window_rows = window_rows
        self.cache_bytes = cache_bytes


class _GenRequest(_Request):
    """One generation request riding the shared exactly-once machinery
    (``claim()``): ``x`` is the 1-D int32 prompt, ``out`` accumulates
    emitted tokens while the request owns a slot. ``on_token`` (when
    set) streams each token out at the step boundary that produced it."""

    __slots__ = ("max_new_tokens", "eos_id", "out", "t_slot_us",
                 "on_token", "cost_flops", "session", "resumes")

    def __init__(self, x, max_new_tokens: int, eos_id: Optional[int],
                 on_token=None, session=None, out=None):
        super().__init__(x)
        self.max_new_tokens = max_new_tokens
        self.eos_id = eos_id
        # non-empty ``out`` = a RESUMED session: these tokens were
        # already emitted (by this worker before a fault, or by a dead
        # one — they came back from the journal) and prefill re-enters
        # at prompt + out
        self.out: List[int] = list(out) if out else []
        self.t_slot_us = 0.0
        self.on_token = on_token
        # accounted device work attributed to this request (prefill +
        # per-slot decode-step shares) — charged to its tenant at
        # resolution under the QoS posture
        self.cost_flops = 0.0
        # the durable session record riding this request (None with
        # DL4J_TPU_SESSIONS=0); ``resumes`` bounds the in-place
        # fault-resume budget so a poisoned cache can't loop forever
        self.session = session
        self.resumes = 0


class GenerationPipeline:
    """Slot-based continuous batching over one :class:`DecodeEngine`.

    Owns a decode-loop thread; call :meth:`shutdown` (or use as a
    context manager) when done. :meth:`shutdown_all` stops every live
    instance (test-harness teardown, like ``ParallelInference``)."""

    _live: "weakref.WeakSet[GenerationPipeline]" = weakref.WeakSet()

    def __init__(self, engine: DecodeEngine, slots: int = 4,
                 queue_limit: int = 64,
                 max_new_tokens: int = 32, eos_id: Optional[int] = None,
                 max_queue_depth: Optional[int] = None,
                 shed_policy: Optional[str] = None,
                 deadline_ms: Optional[float] = None,
                 breaker: Optional[CircuitBreaker] = None,
                 cache_pages: Optional[int] = None):
        self.engine = engine
        self.slots = int(slots)
        if self.slots < 1:
            # a zero-slot pipeline would warm, go live, and then park
            # every request forever — refuse at construction
            raise ValueError(f"slots must be >= 1, got {slots}")
        # paged admission pool: None = the dense worst case (every slot
        # can hold max_len tokens); pass FEWER pages to run more slots
        # against a fixed HBM budget and admit by ACTUAL cached tokens
        self._cache_pages = cache_pages
        if cache_pages is not None and engine.paged:
            if int(cache_pages) < engine.pages_per_slot:
                raise ValueError(
                    f"cache_pages {cache_pages} cannot back even one "
                    f"full-length slot ({engine.pages_per_slot} pages)")
        self.default_max_new_tokens = int(max_new_tokens)
        self.default_eos_id = eos_id
        self._resilience = _faults.resilience_enabled()
        # durable-session posture (kill switch DL4J_TPU_SESSIONS=0):
        # resolved once at construction, same discipline as _resilience
        self._sessions = _session_mod().sessions_enabled()
        if shed_policy is not None and shed_policy not in (
                "reject_newest", "reject_oldest"):
            raise ValueError("shed_policy must be 'reject_newest' or "
                             f"'reject_oldest', got {shed_policy!r}")
        if max_queue_depth is not None and self._resilience:
            queue_limit = max(1, int(max_queue_depth))
            shed_policy = shed_policy or "reject_newest"
        self._shed_policy = shed_policy if self._resilience else None
        self.default_deadline_ms = (deadline_ms if deadline_ms is not None
                                    else default_deadline_ms())
        self._breaker = None
        if self._resilience:
            self._breaker = breaker if breaker is not None else \
                CircuitBreaker("generation.step")
            self._retry = RetryPolicy(max_retries=2,
                                      base_delay_seconds=0.01)
        # QoS posture: per-tenant DWRR queue (cost = 1 slot per
        # request), same kill-switch discipline as ParallelInference
        self._qos = self._resilience and _qos.qos_enabled()
        if self._qos:
            self._queue = _qos.FairQueue(queue_limit,
                                         _qos.global_tenants())
        else:
            self._queue: "queue.Queue[_GenRequest]" = queue.Queue(
                maxsize=queue_limit)
        self._lock = threading.Lock()
        self._not_full = threading.Condition(self._lock)
        self._stop = threading.Event()
        # slot state, owned exclusively by the decode thread
        self._slot_req: List[Optional[_GenRequest]] = [None] * self.slots
        self._tokens = np.zeros((self.slots,), np.int32)
        self._positions = np.zeros((self.slots,), np.int32)
        self._cache = engine.new_state(self.slots, pages=cache_pages)
        # constants of this deployment, for the gauges every step publishes
        self._page_bytes = engine.page_bytes()
        self._slot_state_bytes = self.slots * engine.slot_state_bytes()
        # layers that read the paged rows a step (a layer may read another
        # layer's pages: rows read = live tokens x this, whatever is held)
        self._page_readers = engine.model.page_readers
        # a popped request the pool couldn't back yet — retried at every
        # step boundary (pages free there) before the queue is touched
        self._waiting: Optional[_GenRequest] = None
        # the index of the next decode step to dispatch (folded into the
        # sampler's key), and the steps dispatched whose tokens are not
        # fetched yet, oldest first: at most one between two passes of the
        # loop, two inside a pass that runs ahead
        self._step = 0
        self._inflight: List[_Flight] = []
        self._steps_dispatched = 0
        self._steps_ahead = 0
        # the joins' own books (``snapshot()["joins"]``, the stop line): the
        # count, how many were started with a step in flight, the seconds of
        # the three parts, and the longest join so far that traced no
        # program (a new dict a record, so a snapshot never reads one
        # half-written)
        self._joins = 0
        self._joins_behind = 0
        self._join_s = dict.fromkeys(_JOIN_PARTS, 0.0)
        self._join_longest: Optional[dict] = None
        if not engine.spec:
            # the few-byte program that hands a step's tokens to the next
            # one on the device is compiled here, not by the first full
            # batch inside somebody's window
            engine.warm_carry(self.slots)
        self._thread = threading.Thread(target=self._decode_loop,
                                        daemon=True, name="dl4j-gen-decode")
        self._thread.start()
        GenerationPipeline._live.add(self)
        self._publish_cache_bytes()

    @classmethod
    def _publish_cache_bytes(cls):
        """The gauge is documented as the ACTUAL resident footprint of
        LIVE pipelines — sum across them (a second deploy must not mask
        the first, and a retired pipeline's bytes must leave the
        gauge). Paged pipelines contribute pages-in-use x page-bytes
        (post-quantization), dense ones their full preallocation."""
        obs = _GenMetrics.get()
        total = in_use = pages = slot_state = pool = 0
        accepted = proposed = 0
        for gp in list(cls._live):
            if gp._stop.is_set():
                continue
            total += gp._safe_cache_bytes() or 0
            st = gp._cache
            if st is not None and st.alloc is not None:
                in_use += st.alloc.in_use
                pages += st.alloc.total
                slot_state += gp._slot_state_bytes
                pool += (st.alloc.total + 1) * gp._page_bytes
            if gp.engine.spec:
                accepted += gp.engine.spec_stats["accepted"]
                proposed += gp.engine.spec_stats["proposed"]
        obs.cache_bytes.set(total)
        obs.pages_in_use.set(in_use)
        obs.pages_total.set(pages)
        obs.slot_state_bytes.set(slot_state)
        obs.page_pool_bytes.set(pool)
        # 0 when no live spec engine has proposed anything — a retired
        # spec deploy's final ratio must not outlive it on dashboards
        obs.spec_accept.set(accepted / proposed if proposed else 0.0)

    def __enter__(self):
        return self

    def __exit__(self, *exc):
        self.shutdown()
        return False

    @classmethod
    def shutdown_all(cls):
        for gp in list(cls._live):
            gp.shutdown()

    # ------------------------------------------------------------- API
    def _resolve_deadline(self, deadline_ms) -> Optional[Deadline]:
        if not self._resilience:
            return None
        ms = (deadline_ms if deadline_ms is not None
              else self.default_deadline_ms)
        return Deadline.after_ms(ms) if ms and ms > 0 else None

    def _shed(self, reason: str, tenant=None):
        _GenMetrics.get().shed[reason].inc()
        if tenant is not None:
            _qos.global_tenants().count_shed(tenant, reason)
        _faults.record_event("shed", op="generation", reason=reason)

    def _check_admission(self, tenant=None):
        if self._breaker is not None and not self._breaker.allow():
            self._shed("circuit_open", tenant=tenant)
            raise CircuitOpenError(
                "generation circuit open (consecutive decode-step "
                "failures); retry after the reset timeout")

    def _begin_session(self, prompt: np.ndarray, n_new: int, eos_id,
                       tenant, session_version, session_id):
        """Mint the durable session record for an admitted generation
        (None under ``DL4J_TPU_SESSIONS=0``)."""
        if not self._sessions:
            return None
        smod = _session_mod()
        samp = self.engine.sampler
        return smod.global_sessions().begin(
            prompt.tolist(),
            {"kind": samp.kind, "top_k": samp.top_k,
             "temperature": samp.temperature},
            getattr(self.engine, "_seed", None), n_new, eos_id,
            tenant=tenant, version=session_version, sid=session_id)

    @staticmethod
    def _session_append(req: "_GenRequest", tok: int):
        if req.session is not None:
            req.session.append(tok)

    def _run_request(self, req: "_GenRequest", obs: "_GenMetrics",
                     t0: float, span_name: str, **span_kw) -> np.ndarray:
        """Submit → await → account, shared by :meth:`generate` and
        :meth:`resume` (identical lifecycle, different admission
        preludes)."""
        # span names stay literal (bounded trace-index cardinality);
        # the two lifecycles are the only callers
        span_cm = (_span("generation_resume", **span_kw)
                   if span_name == "generation_resume"
                   else _span("generation_request", **span_kw))
        with _flight().arm(span_name), span_cm:
            req.ctx = current_context()
            req.t_enqueue_us = now_us()

            def _account(err: Optional[BaseException]):
                obs.latency.observe(time.perf_counter() - t0)
                obs.requests.inc()
                if err is not None and not isinstance(err, _TYPED_OUTCOMES):
                    obs.errors.inc()
                if req.tenant is not None:
                    reg = _qos.global_tenants()
                    reg.observe_request(req.tenant,
                                        time.perf_counter() - t0, err)
                    if req.out:
                        reg.account_tokens(req.tenant, len(req.out))
                    if req.cost_flops:
                        reg.account_cost(req.tenant, req.cost_flops)

            try:
                self._check_admission(tenant=req.tenant)
                self._enqueue(req, obs)
            except Exception as e:
                if req.session is not None:
                    req.session.finish(
                        "cancelled" if isinstance(e, _TYPED_OUTCOMES)
                        else "failed")
                _account(e)
                raise
            self._await(req)
            if req.error is not None:
                # the resolver paths (_resolve/_fail/_shed) run on the
                # decode thread; the caller's walk-away resolves HERE —
                # the session terminal status is stamped once, centrally
                if req.session is not None:
                    req.session.finish(
                        "cancelled" if isinstance(req.error,
                                                  _TYPED_OUTCOMES)
                        else "failed")
                _account(req.error)
                raise req.error
            if req.session is not None:
                req.session.finish("done")
        _account(None)
        return req.result

    def generate(self, prompt, max_new_tokens: Optional[int] = None,
                 eos_id: Optional[int] = None,
                 deadline_ms: Optional[float] = None,
                 on_token=None, tenant=None,
                 session_id: Optional[str] = None,
                 session=None,
                 session_version: Optional[str] = None) -> np.ndarray:
        """Generate up to ``max_new_tokens`` continuation tokens for a
        1-D int32 ``prompt``. Blocks until the request resolves; raises
        the typed resilience outcomes (shed/deadline/circuit/shutdown)
        or the device error that killed it. Returns the emitted tokens
        (1-D int32, possibly shorter on ``eos_id``).

        ``on_token(token, index)`` (optional) streams each emitted token
        at the step boundary that produced it — the SSE per-token wire
        surface rides this. It is called from the decode-loop thread, so
        it must be fast and non-blocking (hand off to a queue, never
        write a socket inline). Returning ``False`` or raising cancels
        the request: it resolves with the typed :class:`StreamCancelled`
        and its slot frees at the boundary — the disconnect-mid-stream
        path can never leak a slot. The streamed sequence is exactly the
        returned array: same tokens, same order, nothing elided.

        Under the durable-session posture every admitted generation
        also gets a :mod:`~deeplearning4j_tpu.serving.session` record
        (``session_id`` pins its id, ``session`` supplies a pre-built
        record — the adoption path — and ``session_version`` stamps the
        serving deploy it ran under); ``DL4J_TPU_SESSIONS=0`` makes all
        three inert."""
        prompt = np.asarray(prompt, np.int32).reshape(-1)
        if prompt.size == 0:
            raise ValueError("prompt must contain at least one token")
        n_new = int(max_new_tokens if max_new_tokens is not None
                    else self.default_max_new_tokens)
        if n_new < 1:
            raise ValueError("max_new_tokens must be >= 1")
        # fail fast on prompts that can never decode (bucket overflow):
        # a programming error, not a load condition — never typed
        self.engine.prefill_bucket(prompt.size)
        if prompt.size + 1 > self.engine.max_len:
            raise ValueError(
                f"prompt ({prompt.size} tokens) leaves no room to "
                f"decode in a {self.engine.max_len}-token cache")
        if (self.engine.paged and self.engine.min_pages_for_prompt(
                prompt.size) > self._cache.alloc.total):
            # capacity misconfiguration, not load: this prompt could
            # never admit even into an EMPTY pool
            raise ValueError(
                f"prompt ({prompt.size} tokens) needs "
                f"{self.engine.min_pages_for_prompt(prompt.size)} pages "
                f"but the pool holds {self._cache.alloc.total}")
        obs = _GenMetrics.get()
        t0 = time.perf_counter()
        real_eos = eos_id if eos_id is not None else self.default_eos_id
        sess = session
        if self._sessions and sess is None:
            sess = self._begin_session(prompt, n_new, real_eos, tenant,
                                       session_version, session_id)
        req = _GenRequest(prompt, n_new, real_eos, on_token=on_token,
                          session=sess)
        req.deadline = self._resolve_deadline(deadline_ms)
        req.tenant = (_qos.global_tenants().resolve(tenant)
                      if self._qos else None)
        return self._run_request(req, obs, t0, "generation_request",
                                 prompt_tokens=int(prompt.size),
                                 max_new_tokens=n_new)

    def resume(self, record: dict, on_token=None,
               deadline_ms: Optional[float] = None,
               tenant=None, session=None) -> np.ndarray:
        """Re-enter a journaled session (tentpole 2/3): replay the
        journaled token log through ``on_token`` (indices ``0..k-1`` —
        the caller's ``Last-Event-ID`` window dedups what its client
        already received), then re-prefill ``prompt + emitted`` into a
        free slot and continue the stream. Sampling is in-graph seeded,
        so under greedy the continued stream is byte-identical to the
        one the dead worker would have produced. Live slots are never
        disturbed — a resume is an ordinary admission into a freed slot
        (page pressure parks it exactly like any joiner).

        ``record`` is the journal/store form (``prompt``, ``tokens``,
        ``max_new_tokens``, ``eos_id``, ...); ``session`` (optional) is
        the local :class:`~deeplearning4j_tpu.serving.session.Session`
        mirror the continued tokens journal into — pass the
        ``adopt_local`` result on the adoption path."""
        prompt = np.asarray(record.get("prompt") or [],
                            np.int32).reshape(-1)
        if prompt.size == 0:
            raise ValueError("session record has no prompt to resume")
        emitted = [int(t) for t in (record.get("tokens") or [])]
        n_new = int(record.get("max_new_tokens")
                    or self.default_max_new_tokens)
        eos = record.get("eos_id")
        eos = int(eos) if eos is not None else None

        def _replay() -> bool:
            """Push the already-emitted log through the stream; False =
            the consumer walked away."""
            if on_token is None:
                return True
            for i, t in enumerate(emitted):
                if on_token(int(t), i) is False:
                    return False
            return True

        complete = (len(emitted) >= n_new
                    or (eos is not None and emitted
                        and emitted[-1] == eos))
        if complete:
            # nothing left to decode — the record IS the result (the
            # done-status adoption / replay-only path)
            _replay()
            return np.asarray(emitted, np.int32)
        total = prompt.size + len(emitted)
        self.engine.prefill_bucket(total)
        if total + 1 > self.engine.max_len:
            raise ValueError(
                f"resumed session ({total} cached tokens) leaves no "
                f"room to decode in a {self.engine.max_len}-token cache")
        if (self.engine.paged and self.engine.min_pages_for_prompt(total)
                > self._cache.alloc.total):
            raise ValueError(
                f"resumed session ({total} tokens) needs "
                f"{self.engine.min_pages_for_prompt(total)} pages but "
                f"the pool holds {self._cache.alloc.total}")
        obs = _GenMetrics.get()
        t0 = time.perf_counter()
        if not _replay():
            # client gone before the resume even admitted — same typed
            # outcome the mid-stream walk-away gets
            raise StreamCancelled(
                "streaming consumer cancelled during session replay")
        req = _GenRequest(prompt, n_new, eos, on_token=on_token,
                          session=session, out=emitted)
        req.deadline = self._resolve_deadline(deadline_ms)
        req.tenant = (_qos.global_tenants().resolve(
            tenant if tenant is not None else record.get("tenant"))
            if self._qos else None)
        if self._sessions:
            _session_mod().session_metrics().resumes.inc()
            _faults.record_event("session_resume",
                                 sid=record.get("sid"),
                                 emitted=len(emitted))
        return self._run_request(req, obs, t0, "generation_resume",
                                 prompt_tokens=int(prompt.size),
                                 replayed_tokens=len(emitted),
                                 max_new_tokens=n_new)

    def _enqueue(self, req: _GenRequest, obs: "_GenMetrics"):
        """Bounded enqueue with the PI condition/shed semantics."""
        with self._not_full:
            while True:
                if self._stop.is_set():
                    raise ShutdownError(
                        "GenerationPipeline has been shut down")
                if req.deadline is not None and req.deadline.expired():
                    self._shed("deadline", tenant=req.tenant)
                    raise DeadlineExceeded(
                        "request expired while waiting to enqueue")
                try:
                    self._queue.put_nowait(req)
                    obs.queue_depth.set(self._queue.qsize())
                    return
                except queue.Full:
                    if self._qos and self._shed_policy is not None:
                        # tenant-aware: evict the most over-share
                        # tenant's newest request; None = the arriving
                        # tenant is itself the most over-share (under
                        # reject_oldest its OWN stale head gives way —
                        # the pre-QoS policy meaning, tenant-scoped)
                        victim = self._queue.pick_victim(req)
                        if (victim is None
                                and self._shed_policy == "reject_oldest"):
                            victim = (self._queue.pop_oldest_of(
                                req.tenant)
                                or self._queue.pop_global_oldest())
                        if victim is None:
                            self._shed("queue_full", tenant=req.tenant)
                            raise ShedError(
                                f"generation queue full "
                                f"({self._queue.maxsize} requests); "
                                "request rejected (tenant over its "
                                "fair share)")
                        self._shed_request(victim, "queue_full",
                                           ShedError(
                                               "shed from a full "
                                               "generation queue (most "
                                               "over-share tenant)"))
                        continue
                    if self._shed_policy == "reject_newest":
                        self._shed("queue_full", tenant=req.tenant)
                        raise ShedError(
                            f"generation queue full "
                            f"({self._queue.maxsize} requests); request "
                            "rejected (reject_newest)")
                    if self._shed_policy == "reject_oldest":
                        try:
                            old = self._queue.get_nowait()
                        except queue.Empty:
                            continue
                        self._shed_request(old, "queue_full", ShedError(
                            "shed from a full generation queue by a "
                            "newer request (reject_oldest)"))
                        continue
                    self._not_full.wait(timeout=0.1)

    def _await(self, req: _GenRequest):
        """Deadline-aware wait with the walk-away claim (a wedged decode
        step must not hang a deadline'd caller)."""
        if req.deadline is None:
            req.event.wait()
            return
        while not req.event.is_set():
            rem = req.deadline.remaining()
            if rem <= 0:
                break
            req.event.wait(timeout=rem)
        if not req.event.is_set():
            if req.claim():
                req.error = DeadlineExceeded(
                    "request expired while decoding")
                req.event.set()
                self._shed("deadline", tenant=req.tenant)
            else:
                req.event.wait(timeout=5.0)
                if req.error is None and req.result is None:
                    req.error = DeadlineExceeded(
                        "request expired while decoding "
                        "(resolution stalled)")

    # --------------------------------------------------- decode thread
    def _shed_request(self, req: _GenRequest, reason: str,
                      error: BaseException):
        if not req.claim():
            return
        self._shed(reason, tenant=req.tenant)
        if req.ctx is not None:
            record_span("shed", now_us(), ctx=req.ctx, reason=reason)
        req.error = error
        req.event.set()

    def _resolve(self, req: _GenRequest):
        """Successful completion (slot already freed by the caller)."""
        if not req.claim():
            return
        req.result = np.asarray(req.out, np.int32)
        req.event.set()

    @staticmethod
    def _emit_token(req: _GenRequest, tok: int) -> bool:
        """Deliver one just-appended token to the request's streaming
        callback (decode-thread context). Returns False when the
        consumer cancelled — returned False or raised — and the caller
        must shed the request (``client_gone``)."""
        cb = req.on_token
        if cb is None:
            return True
        try:
            return cb(tok, len(req.out) - 1) is not False
        # graftlint: disable=typed-errors — a broken consumer callback is
        # resolved as a client_gone shed by the caller, not swallowed
        except Exception:
            # a broken consumer must never kill the decode loop the
            # other slots are riding — treat exactly like a walk-away
            return False

    def _fail_request(self, req: _GenRequest, error: BaseException):
        if not req.claim():
            return
        req.error = error
        req.event.set()

    def _n_active(self) -> int:
        return sum(1 for r in self._slot_req if r is not None)

    def _take_request(self, timeout: float) -> Optional[_GenRequest]:
        """Pop one queued request (shedding already-expired ones), waking
        any producer parked on the full queue."""
        wait_until = time.monotonic() + timeout
        while True:
            try:
                req = self._queue.get(
                    timeout=max(0.0, wait_until - time.monotonic()))
            except queue.Empty:
                return None
            with self._not_full:
                self._not_full.notify()
            if (self._resilience and req.deadline is not None
                    and req.deadline.expired()):
                self._shed_request(req, "deadline", DeadlineExceeded(
                    "request expired waiting for a slot"))
                continue
            return req

    def _free_slot(self, slot: int):
        """Release ``slot``: request pointer, its cache pages (paged),
        and the position/token books — every slot-freeing path must go
        through here or pages leak.

        The pages go back to the pool at once, also while a step that
        still writes this slot's row is in flight (dispatched before the
        sweep that frees it): whatever writes them next, a joiner's insert
        or a later step of the slot they are handed to, is enqueued behind
        that step on the same device stream, so the stale row lands first
        and is overwritten or never read (rows past a slot's position are
        masked), as a retired slot's scribbles always were."""
        self._slot_req[slot] = None
        self.engine.free_slot(self._cache, slot)
        self._positions[slot] = 0
        self._tokens[slot] = 0

    def _rebuild_after_fault(self, error: BaseException):
        """A device-level fault poisoned the cache: fail every in-flight
        request EXCEPT the ones a durable session can deterministically
        resume (tentpole 2 — only genuinely unjournaled work is lost,
        bounded by the journal cadence), zero the slot books, and
        rebuild the page pool. Returns the resumable survivors for
        :meth:`_replace_survivors`. With sessions off every slot fails,
        byte-identical to the pre-session behavior."""
        if self._inflight:
            # the steps on the chip died with the cache and their tokens
            # reached no stream: the books go back to the last swept step
            self._step = self._inflight[0].step
            self._inflight.clear()
        survivors: List[_GenRequest] = []
        for slot, req in enumerate(self._slot_req):
            if req is not None:
                if (self._sessions and req.session is not None
                        and not req.session.stolen and not req._claimed
                        and req.resumes < 3):
                    req.resumes += 1
                    survivors.append(req)
                else:
                    self._fail_request(req, error)
            self._slot_req[slot] = None
        self._tokens[:] = 0
        self._positions[:] = 0
        self._cache = self.engine.new_state(self.slots,
                                            pages=self._cache_pages)
        return survivors

    def _replace_survivors(self, survivors: List[_GenRequest],
                           error: BaseException):
        """Re-prefill fault survivors into the rebuilt cache (all slots
        are free when this runs). A survivor that cannot re-place —
        pool too small for its grown context, or its re-prefill fails
        again — resolves with the original fault."""
        if not survivors:
            return
        _session_mod().session_metrics().resumes.inc(len(survivors))
        _faults.record_event("session_resume_inplace",
                             count=len(survivors))
        slot_i = 0
        for req in survivors:
            if slot_i >= self.slots:
                self._fail_request(req, error)
                continue
            if self._start_request(req, slot_i):
                slot_i += 1

    def _start_request(self, req: _GenRequest, slot: int) -> bool:
        """Prefill ``req`` into ``slot``'s cache pages. Returns True when
        the slot is now occupied (False: resolved without occupying)."""
        obs = _GenMetrics.get()
        if req._claimed:
            return False          # caller already walked away — no work
        req.t_slot_us = now_us()
        if req.ctx is not None:
            # the join-latency phase continuous batching exists to shrink
            record_span("slot_wait", req.t_enqueue_us, req.t_slot_us,
                        ctx=req.ctx, slot=slot)
        t_us = now_us()
        # the live streams that get no token until this joiner is in, and
        # the steps on the chip its prefill queues behind
        stalled = self._n_active()
        inflight = len(self._inflight)
        traced0 = _cw.global_compile_watch().total
        # a resumed request re-prefills prompt + already-emitted tokens:
        # the cache rebuilds to exactly the state the lost slot held, and
        # the in-graph seeded sampler continues the identical stream
        # (byte-identical under greedy)
        k_resumed = len(req.out)
        x_in = (np.concatenate([req.x, np.asarray(req.out, np.int32)])
                if k_resumed else req.x)
        n_in = int(x_in.size)
        try:
            bucket = self.engine.prefill_bucket(n_in)
            with _span("prefill_dispatch", slot=slot, prompt_tokens=n_in,
                       bucket=bucket, inflight=inflight):
                first, _logits, kv, t = self.engine.prefill(
                    x_in[None], step=self._step)
        except Exception as e:
            # prefill failed BEFORE the insert donated anything — the
            # live cache is intact, only the joiner dies
            if self._breaker is not None:
                self._breaker.record_failure()
            self._fail_request(req, e)
            return False
        # the join's three parts, split where the program hands over to the
        # device: consecutive clock reads, so they leave no gap and sum to
        # the ``prefill`` span
        t_disp_us = now_us()
        try:
            with _span("prefill_insert", slot=slot):
                self._cache = self.engine.insert_slot(self._cache, kv, slot)
                if self.engine.spec:
                    # the draft tracks the same prompt in its own dense
                    # cache — a failure here cannot touch the target
                    # pool (handled below)
                    self.engine.insert_draft_slot(self._cache, slot,
                                                  x_in[None],
                                                  step=self._step)
                t_ins_us = now_us()
                # everything above returned as soon as the device had its
                # work; this waits for it: the prefill program, and before
                # it the decode step in flight, if there is one
                with _span("first_token_fetch", slot=slot):
                    first_tok = int(np.asarray(first)[0])
            end_us = now_us()
            dt = (end_us - t_us) * 1e-6
            parts_us = (t_disp_us - t_us, t_ins_us - t_disp_us,
                        end_us - t_ins_us)
            if req.ctx is not None:
                record_span("prefill", t_us, end_us, ctx=req.ctx,
                            slot=slot, prompt_tokens=int(req.x.size),
                            tokens=n_in, stalled_slots=stalled,
                            bucket=bucket, inflight=inflight,
                            step=self._step, dispatch_us=parts_us[0],
                            insert_us=parts_us[1], fetch_us=parts_us[2],
                            tail_rows=self.engine.model.prefill_tail_rows(
                                bucket))
            self._book_join(
                obs, parts_us,
                compiled=_cw.global_compile_watch().total != traced0,
                slot=slot, bucket=bucket, tokens=n_in, inflight=inflight,
                step=self._step)
            obs.prefill_latency.observe(dt)
            if stalled:
                obs.prefill_stall.inc(dt)
            _cost.global_cost_model().observe_time(PREFILL_FN, dt)
            if req.tenant is not None:
                req.cost_flops += _cost.global_cost_model().flops_for(
                    PREFILL_FN)
            if self._breaker is not None:
                self._breaker.record_success()
        except CachePagesExhausted as e:
            # raised BEFORE any device write (the paged insert checks
            # the free list first): the live cache is intact, only the
            # joiner sheds typed — _admit normally parks it first, so
            # this is the belt-and-braces path
            self._shed_request(req, "pages_exhausted", e)
            return False
        except Exception as e:
            if self._breaker is not None:
                self._breaker.record_failure()
            self._fail_request(req, e)
            if isinstance(e, (ValueError, TypeError)):
                # a POISONED REQUEST (bad shapes/dtypes/values raised by
                # validation before any device write): the live cache is
                # intact — one bad joiner must never take down every
                # in-flight stream (blast-radius fix, pinned by a test)
                return False
            # device-level: insert DONATED live cache arrays before
            # dying — its pages are gone, so every active generation
            # lost its cache: rebuild the pages, resume the journaled
            # sessions in place, and fail the rest with the real insert
            # error (not the deleted-buffer error one step later)
            survivors = self._rebuild_after_fault(e)
            self._replace_survivors(survivors, e)
            return False
        req.out.append(first_tok)
        self._session_append(req, first_tok)
        # the generation budget may be clipped by the cache length —
        # never write a position past the preallocated pages. On resume
        # (len(out)-1 == k pre-existing tokens) the budget already spent
        # k of its allowance; the cache-room clip applies to the REST
        cap = min(req.max_new_tokens,
                  (len(req.out) - 1) + self.engine.max_len - t)
        req.max_new_tokens = cap
        done = (len(req.out) >= cap
                or (req.eos_id is not None and first_tok == req.eos_id))
        obs.tokens.inc()
        if not self._emit_token(req, first_tok):
            if done:
                self._resolve(req)       # complete anyway: result is whole
            else:
                self._shed_request(req, "client_gone", StreamCancelled(
                    "streaming consumer cancelled during prefill"))
            self.engine.free_slot(self._cache, slot)
            return False
        if done:
            self._resolve(req)
            self.engine.free_slot(self._cache, slot)
            return False
        self._slot_req[slot] = req
        self._tokens[slot] = first_tok
        self._positions[slot] = t
        return True

    def _book_join(self, obs: "_GenMetrics", parts_us, compiled: bool,
                   **what):
        """One finished join into the counters and the pipeline's own books
        (:meth:`snapshot`, the stop line): ``parts_us`` its dispatch, insert
        and fetch microseconds, ``what`` its slot, bucket, tokens, the steps
        in flight when it started and the loop's step counter. A join during
        which a program was traced (a bucket's first, unless the deployment
        warmed it) counts like any other but is not a candidate for the
        longest: its seconds are a compile's or a cache load's, which
        ``compile_watch`` reports, and it would hide every stall after it."""
        obs.joins.labels(bucket=what["bucket"]).inc()
        for phase, us in zip(_JOIN_PARTS, parts_us):
            obs.join_seconds[phase].inc(us * 1e-6)
            self._join_s[phase] += us * 1e-6
        self._joins += 1
        if what["inflight"]:
            self._joins_behind += 1
        ms = sum(parts_us) / 1e3
        if not compiled and (self._join_longest is None
                             or ms > self._join_longest["ms"]):
            self._join_longest = dict(
                what, ms=ms, **{ph + "_ms": us / 1e3
                                for ph, us in zip(_JOIN_PARTS, parts_us)})

    def _maybe_preempt(self, pri: Optional[float] = None) -> bool:
        """Priority preemption at a step boundary (QoS posture): when
        the contending tier — the highest QUEUED tier by default, or an
        explicit ``pri`` for a page-starved parked joiner — strictly
        exceeds some active slot's tier, that slot's request is shed
        typed
        (:class:`~deeplearning4j_tpu.resilience.qos.PreemptedError`) and
        the slot freed (its cache pages with it: under the paged engine
        the bottleneck is usually PAGES, not slots, and preemption must
        fire there too or the PR-12 priority guarantee silently dies in
        the default mode). The victim: among lower-tier active slots,
        the most over-share tenant's longest-running request (slot
        frees and joins already happen exactly here — the preempted
        caller resolves typed, never hangs). Default tiers (0
        everywhere) never preempt."""
        if pri is None:
            pri = self._queue.peek_priority()
        if pri is None:
            return False
        reg = _qos.global_tenants()
        active = [(slot, r) for slot, r in enumerate(self._slot_req)
                  if r is not None]
        cands = [(slot, r) for slot, r in active
                 if reg.priority(r.tenant) < pri]
        if not cands:
            return False
        counts: dict = {}
        for _, r in active:
            counts[r.tenant] = counts.get(r.tenant, 0) + 1
        wsum = sum(reg.weight(t) for t in counts) or 1.0

        def over_share(t):
            return counts[t] / max(1e-9,
                                   len(active) * reg.weight(t) / wsum)

        victim_slot, victim = max(
            cands, key=lambda sr: (over_share(sr[1].tenant),
                                   -sr[1].t_slot_us))
        self._shed_request(victim, "preempted", _qos.PreemptedError(
            f"generation slot {victim_slot} preempted by a higher-"
            f"priority tenant at a decode step boundary"))
        self._free_slot(victim_slot)
        return True

    def _admit(self):
        """Join queued requests into free slots at this step boundary.
        Paged mode admits on FREE PAGES, not free slots: a popped
        request whose prompt the pool cannot back yet is parked in
        ``_waiting`` and retried at every boundary (pages free exactly
        there) before the queue is touched — admission resumes the
        moment reclamation or completions return enough pages.
        Never blocks: an idle pipeline waits for its next request in
        ``_decode_loop``, outside any iteration. Returns how many
        requests it started, the slots that were free when the first of
        them was taken and the queue's depth after the last (both None
        where nobody joined): whether several joiners ever meet at one
        boundary, and whether more were waiting."""
        joined, free0, queued = 0, None, None
        while not self._stop.is_set():
            free = [i for i, r in enumerate(self._slot_req) if r is None]
            if not free:
                if self._qos and self._maybe_preempt():
                    continue       # a slot was freed — re-scan and join
                break
            req, self._waiting = self._waiting, None
            if req is not None:
                if req._claimed:
                    continue        # parked caller already walked away
                if (self._resilience and req.deadline is not None
                        and req.deadline.expired()):
                    self._shed_request(req, "deadline", DeadlineExceeded(
                        "request expired waiting for cache pages"))
                    continue
            else:
                req = self._take_request(timeout=0.0)
            if req is None:
                break
            if (self.engine.paged
                    and self.engine.min_pages_for_prompt(
                        req.x.size + len(req.out))
                    > self._cache.alloc.free_count):
                # can't back the prompt yet; active slots still hold
                # pages (generate() pre-checked the empty-pool fit, so
                # an idle pipeline always admits). A higher-tier
                # tenant's joiner may PREEMPT a lower-tier slot for its
                # pages — the paged twin of the slots-full preemption
                # above (page pressure is the common overload state
                # under a bounded pool)
                if self._qos and self._maybe_preempt(
                        pri=_qos.global_tenants().priority(req.tenant)):
                    self._waiting = req
                    continue       # pages came back — retry this joiner
                self._waiting = req
                break
            queued = self._queue.qsize()
            _GenMetrics.get().queue_depth.set(queued)
            if not joined:
                free0 = len(free)
            self._start_request(req, free[0])
            joined += 1
        return joined, free0, queued

    def _reclaim_victim_key(self, slot: int):
        """Reclamation victim ordering (max wins): shed sessions with
        NOTHING journaled before sessions the journal already made
        durable, youngest first within each class — under page pressure
        a worker sheds NEW sessions before evicting journaled ones
        (tentpole 4). With sessions off every slot is "unjournaled" and
        the key degenerates to the pre-session pure youngest-first."""
        req = self._slot_req[slot]
        unjournaled = True
        if self._sessions and req.session is not None:
            unjournaled = req.session.journaled == 0
        return (unjournaled, req.t_slot_us)

    def _reclaim_pages(self, active: List[int]) -> List[int]:
        """Step-boundary reclamation: grow every active slot's pages for
        this step's writes (spec windows reach ``spec_k`` further); on
        pool exhaustion the YOUNGEST active request is shed typed
        (:class:`CachePagesExhausted`) and its pages return to the
        pool, until the survivors fit. Returns the surviving active
        list — deterministic, oldest generations win."""
        if not self.engine.paged:
            return active
        reach = self.engine.spec_k if self.engine.spec else 0
        for slot in sorted(active,
                           key=lambda s: self._slot_req[s].t_slot_us):
            req = self._slot_req[slot]
            if req is None:
                continue            # already shed as a victim below
            last = min(int(self._positions[slot]) + reach,
                       self.engine.max_len - 1)
            while not self.engine.ensure_slot_pages(self._cache, slot,
                                                    last):
                # victim = the youngest ACTIVE request, whether or not
                # it is the one needing the page — oldest generations
                # win unconditionally (shedding an elder because a
                # newcomer grew would invert the policy)
                cands = [s for s in active
                         if self._slot_req[s] is not None]
                victim = max(cands, key=self._reclaim_victim_key)
                self._shed_request(
                    self._slot_req[victim], "pages_exhausted",
                    CachePagesExhausted(
                        "KV page pool exhausted at a decode step "
                        "boundary; request shed to reclaim pages"))
                self._free_slot(victim)
                if victim == slot:
                    break
        return [s for s in active if self._slot_req[s] is not None]

    def _sweep_finished(self, emitted: Dict[int, List[int]]):
        """Post-step bookkeeping for every stepped slot: append its
        emitted tokens IN ORDER (one for a plain decode step, up to
        ``spec_k`` for a speculative round), then resolve/free finished,
        cancelled, or expired requests. A request finishing mid-window
        simply ignores the window's tail — same semantics as plain
        decode stopping at its boundary. Returns how many requests left
        their slots."""
        obs = _GenMetrics.get()
        left = 0
        # each occupied slot owns 1/slots of the step boundary's
        # accounted FLOPs (the whole slot batch runs whether occupied or
        # not — charging per OCCUPIED slot would make a lonely tenant
        # look cheap while it monopolizes the executable). A spec round
        # ran propose + verify, never the one-token decode executable —
        # charge what actually executed.
        step_share = 0.0
        if self._qos:
            cm = _cost.global_cost_model()
            flops = ((cm.flops_for(VERIFY_FN) + cm.flops_for(PROPOSE_FN))
                     if self.engine.spec else cm.flops_for(DECODE_FN))
            step_share = flops / max(1, self.slots)
        for slot, toks_l in emitted.items():
            req = self._slot_req[slot]
            if req is None:
                continue
            left += 1               # taken back below if the slot stays
            if req._claimed:
                # another path already resolved it (the caller's
                # deadline walk-away) — stop spending device steps on a
                # request nobody will read (racy read is safe: worst
                # case is one extra step before the slot frees)
                self._free_slot(slot)
                continue
            if req.session is not None and req.session.stolen:
                # another worker fence-bumped this session away (it
                # adopted the stream mid-failover while we were merely
                # stalled): stop decoding NOW — continuing would
                # double-decode, and our journal writes are already
                # fenced off
                self._shed_request(req, "session_lost",
                                   _session_mod().SessionLost(
                                       "session adopted by another "
                                       "worker (lease fenced)"))
                self._free_slot(slot)
                continue
            if req.tenant is not None:
                req.cost_flops += step_share
            done = cancelled = False
            for tok in toks_l:
                req.out.append(int(tok))
                self._session_append(req, tok)
                obs.tokens.inc()
                done = (len(req.out) >= req.max_new_tokens
                        or (req.eos_id is not None
                            and int(tok) == req.eos_id))
                if not self._emit_token(req, int(tok)) and not done:
                    cancelled = True
                    break
                if done:
                    break
            expired = (self._resilience and req.deadline is not None
                       and req.deadline.expired())
            if cancelled:
                # consumer gone mid-stream: free the slot NOW — other
                # slots keep decoding, nothing leaks
                self._shed_request(req, "client_gone", StreamCancelled(
                    "streaming consumer cancelled mid-stream"))
                self._free_slot(slot)
            elif expired and not done:
                self._shed_request(req, "deadline", DeadlineExceeded(
                    "request expired at a decode step boundary"))
                self._free_slot(slot)
            elif done:
                self._resolve(req)
                self._free_slot(slot)
            else:
                left -= 1
        return left

    def _decode_loop(self):
        """The decode thread: one ``decode_iter`` span a pass that has
        work (:meth:`_iterate`), the phases' seconds into
        ``dl4j_decode_loop_seconds_total``. A step may stay on the chip
        from one pass to the next (``_inflight``): of a step's inputs only
        the tokens depend on the step before (positions, page tables and
        the step counter are the host's books), so while every slot is
        occupied a pass dispatches the next step from tokens still on the
        device and only then fetches and sweeps the one before. Pages a
        sweep frees may be handed out with a step in flight because
        whatever writes them next is enqueued behind it on the device's
        one stream. A free slot switches the order back to dispatch,
        fetch, sweep: an arrival can then join at the very next boundary,
        and a step already queued would stand before its prefill."""
        while not self._stop.is_set():
            # re-fetch per iteration: a registry reset mid-flight drops
            # and re-binds the singleton (on_registry_reset) — a cached
            # handle would keep writing to detached instruments
            obs = _GenMetrics.get()
            if (self._waiting is None and self._n_active() == 0
                    and not self._inflight):
                # idle: the wait for a request is no phase of any
                # iteration, and a poll that found none records nothing
                self._waiting = self._take_request(timeout=0.05)
                if self._waiting is None:
                    obs.slots_in_use.set(0)
                    continue
            sec = dict.fromkeys(_LOOP_PHASES, 0.0)
            with _span("decode_iter", step=self._step):
                self._iterate(obs, sec)
            for phase, s in sec.items():
                if s:
                    obs.loop_seconds[phase].inc(s)
        # shutdown: resolve whatever still occupies a slot (and the
        # parked joiner the pool never backed)
        self._inflight.clear()
        _log.info("decode loop: %d of %d steps dispatched ahead",
                  self._steps_ahead, self._steps_dispatched)
        _log.info("%s", self._joins_line())
        for slot, req in enumerate(self._slot_req):
            if req is not None:
                self._fail_request(req, ShutdownError(
                    "GenerationPipeline shut down"))
                self._slot_req[slot] = None
        if self._waiting is not None:
            self._fail_request(self._waiting, ShutdownError(
                "GenerationPipeline shut down"))
            self._waiting = None

    def _joins_line(self) -> str:
        """The joins of this pipeline's life in one line, for the log when
        it stops: what a stall inside a join has to be hunted for
        otherwise (which part, behind a step or not, which bucket)."""
        j = self._join_s
        line = (f"joins: {self._joins} in {sum(j.values()):.3f} s "
                f"(dispatch {j['dispatch']:.3f}, insert {j['insert']:.3f}, "
                f"fetch {j['fetch']:.3f}); {self._joins_behind} of "
                f"{self._joins} behind a step in flight")
        top = self._join_longest
        if top is not None:
            line += (f"; longest {top['ms']:.1f} ms: bucket {top['bucket']} "
                     f"slot {top['slot']} inflight {top['inflight']} "
                     f"dispatch/insert/fetch {top['dispatch_ms']:.1f}/"
                     f"{top['insert_ms']:.1f}/{top['fetch_ms']:.1f}")
        return line

    def _owed(self, slot: int) -> int:
        """Tokens ``slot``'s request still wants beyond the steps in
        flight, by count alone (``max_new_tokens`` less what was swept and
        what is on the chip): an end by ``eos_id``, a cancel or a deadline
        shows only at the sweep. Zero or less: the next step's row for
        this slot is a scribble nobody reads."""
        req = self._slot_req[slot]
        flying = sum(1 for f in self._inflight if f.reqs[slot] is req)
        return req.max_new_tokens - len(req.out) - flying

    def _runs_ahead(self, active: List[int]) -> bool:
        """Whether the step after the newest one in flight may be
        dispatched before that one's tokens are fetched. Decided from what
        the loop observes, in this order:

        - every slot is occupied. With a free slot an arrival can join at
          the very next boundary, and a step already queued would put a
          whole step between it and its prefill; with none it cannot join
          before a sweep frees one, so nothing is lost by queueing;
        - some slot has a token to gain from that step (a step in which
          every row is an overshoot is not worth dispatching);
        - the pool can back it: running ahead never sheds. If a write of
          that step needs a page the pool cannot give, the pass drains
          first (fetch and sweep the step in flight, which may free pages)
          and the next pass reclaims in the synchronous order, so the
          victim and the order of ``pages_exhausted`` sheds do not depend
          on how far the loop ran ahead."""
        if len(active) < self.slots:
            return False
        if not any(self._owed(s) > 0 for s in active):
            return False
        if not self.engine.paged:
            return True
        eng, st = self.engine, self._cache
        short = sum(max(0, eng.pages_for(int(self._positions[s]) + 1)
                        - len(st.slot_pages[s])) for s in active)
        return short <= st.alloc.free_count

    def _dispatch_step(self, active: List[int]):
        """Hand step ``self._step`` to the device and put it in flight.
        Its tokens are the host's (``self._tokens``) when nothing is in
        flight, else those of the newest step in flight, still on the
        device, with the host's token put in for every slot whose request
        joined since that step was dispatched (a joiner's first token is
        fetched by its prefill). The host's books then move to the step
        after: positions advance by one for every slot that wants a
        further token, which no fetch has to tell; a slot that by count
        ends with the steps in flight keeps its position, so the step
        ahead rewrites a row it already owns and needs no page."""
        flying = self._inflight
        if flying:
            last = flying[-1]
            own = np.full((self.slots,), -1, np.int32)
            for slot in active:
                if self._slot_req[slot] is not last.reqs[slot]:
                    own[slot] = self._tokens[slot]
            tokens = self.engine.carry_tokens(last.outputs[0], own)
        else:
            # copies: the device may read a host array after this call
            # returns, and the books below change before the fetch
            tokens = self._tokens.copy()
        # the keys and values this step reads: every active slot's rows
        # up to and including the one it writes
        at = self._positions[active]
        live = int(at.sum()) + len(active)
        # and the pages those rows lie in: what an attention that walks a
        # slot's live pages visits (0 for a cache that has no pages)
        pt = self.engine.page_tokens
        pages = int((at // pt + 1).sum()) if pt else 0
        # the rows a window layer reads, where the model has one: every
        # active slot's last ``cache_window`` rows and no more
        window = getattr(self.engine.model, "cache_window", None)
        window_rows = int(np.minimum(at + 1, window).sum()) if window else 0
        # what the step's cache holds: the pages handed out, in every layer
        # that keeps pages, and every occupied slot's fixed state
        cache_bytes = self.engine.resident_cache_bytes(self._cache)
        nxt, logits, self._cache = self.engine.decode(
            self._cache, tokens, self._positions.copy(), self._step)
        flying.append(_Flight(self._step, (nxt, logits), active,
                              list(self._slot_req), live, pages,
                              window_rows, cache_bytes))
        self._positions[[s for s in active if self._owed(s) > 0]] += 1
        self._step += 1
        self._steps_dispatched += 1

    def _iterate(self, obs: "_GenMetrics", sec: Dict[str, float]):
        """One pass of the decode loop with work in hand: join, back the
        next step's pages, dispatch it, fetch a step's tokens, sweep,
        publish. Each phase is a span under the caller's ``decode_iter``
        and adds its seconds to ``sec`` (``_LOOP_PHASES``; consecutive
        clock reads, so the phases leave no gap between them).

        Which step the fetch is of follows from the occupancy
        (:meth:`_runs_ahead`). Of the inputs of step k + 1 only the tokens
        depend on step k; positions, page tables and the step counter are
        the host's own books. So while every slot is occupied the pass
        dispatches step k + 1 from tokens still on the device and THEN
        fetches, sweeps and publishes step k: the device goes from one
        step to the next with nothing between them, and the host's work
        runs beside it. ``decode_step`` then spans the dispatch of step
        k + 1 and the fetch of step k (attribute ``ahead`` 1, the other
        attributes those of the step fetched): the time the loop waited
        on the device for one step. With a free slot the pass fetches the
        step it dispatched, the order there always was: an arrival can
        then join at the very next boundary, and a step already queued
        would put a whole step between it and its prefill. Between the two,
        one pass dispatches and fetches nothing (the first with every
        slot occupied) and one fetches and dispatches nothing (a slot has
        come free and nobody joined, or the pool cannot back the step
        ahead).

        What a step in flight changes for the rest of the loop: a request
        that ends at step k (``eos_id``, cancelled, expired, shed) has one
        more row computed by the time the sweep learns it, and that row's
        token is dropped at its fetch (``_Flight.reqs``); its pages are
        handed out at once, which is safe because whatever writes them
        next (an insert, a later step) is enqueued behind the step in
        flight on the same device stream (:meth:`_free_slot`); a
        fault loses the device results of the steps in flight and nothing
        a stream has seen (:meth:`_rebuild_after_fault`)."""
        t_prev = time.perf_counter()

        def close(phase: str):
            nonlocal t_prev
            now = time.perf_counter()
            sec[phase] += now - t_prev
            t_prev = now

        with _span("loop_admit") as sp:
            joined, free, queued = self._admit()
            sp.set_attr("joined", joined)
            if joined:
                sp.set_attr("free", free)
                sp.set_attr("queued", queued)
        active = [i for i, r in enumerate(self._slot_req)
                  if r is not None]
        obs.slots_in_use.set(len(active))
        close("admit")
        flying = self._inflight
        if not active and not flying:
            return
        try:
            with _span("loop_reclaim"):
                if self._resilience:
                    self._retry.call(
                        lambda: _faults.check("generation.step"),
                        op="generation.step")
                # a pass dispatches the next step unless one is in flight
                # and the next may not go before its fetch
                older = bool(flying)
                dispatch = not older or self._runs_ahead(active)
                if dispatch:
                    active = self._reclaim_pages(active)
            close("reclaim")
            if not active and not flying:
                self._step += 1
                self._publish_cache_bytes()
                close("publish")
                return
            if self.engine.spec:
                # a speculative round fetches twice inside itself: it
                # keeps the synchronous order
                live = int(self._positions[active].sum()) + len(active)
                fetch0 = self.engine.spec_fetch_s
                with _span("decode_step", active=len(active),
                           slots=self.slots, spec=True, live_tokens=live):
                    emitted = self.engine.spec_step(
                        self._cache, self._tokens, self._positions,
                        self._step, active)
                for slot, toks_l in emitted.items():
                    # the last emitted token is the next carry; the
                    # cache advanced one row per emitted token
                    self._tokens[slot] = toks_l[-1]
                    self._positions[slot] += len(toks_l)
                self._step += 1
                self._steps_dispatched += 1
                close("dispatch")
                # the round's waits for the device, as the engine timed
                # them, are fetch; the rest of it is dispatch
                waited = self.engine.spec_fetch_s - fetch0
                sec["dispatch"] -= waited
                sec["fetch"] += waited
            else:
                ahead = older and dispatch
                with _span("decode_step", slots=self.slots,
                           ahead=int(ahead)) as step_sp:
                    if dispatch:
                        with _span("decode_dispatch", step=self._step):
                            self._dispatch_step(active)
                        if ahead:
                            self._steps_ahead += 1
                            obs.steps_ahead.inc()
                    close("dispatch")
                    if not older and self._runs_ahead(active):
                        # every slot occupied: the step stays on the chip
                        # and the next pass dispatches the one after it
                        # before it fetches this one
                        return
                    step = flying[0]
                    with _span("token_fetch", step=step.step):
                        toks = np.asarray(step.outputs[0])  # device→host
                    flying.pop(0)
                    # a model's own counts of the step (experts touched,
                    # pairs on held experts) came in the same transfer
                    counts = self.engine.step_counts(toks, self.slots)
                    step_sp.set_attr("step", step.step)
                    step_sp.set_attr("active", len(step.active))
                    step_sp.set_attr("live_tokens", step.live)
                    step_sp.set_attr("attn_pages", step.pages)
                    step_sp.set_attr("window_rows", step.window_rows)
                    step_sp.set_attr("cache_bytes", step.cache_bytes)
                    step_sp.set_attr("page_readers", self._page_readers)
                    for name, n in counts.items():
                        step_sp.set_attr(name, n)
                    # the step's device outputs die here, inside the
                    # step's span and the fetch phase, not at this
                    # function's return: handing (B, V) float32 logits
                    # back to the runtime takes 1-2 ms on the chip's host
                    step.outputs = None
                emitted = {}
                for slot in step.active:
                    # a slot whose request left or changed while the
                    # step ran was a scribble: its token is dropped
                    if self._slot_req[slot] is step.reqs[slot]:
                        self._tokens[slot] = toks[slot]
                        emitted[slot] = [int(toks[slot])]
                active = step.active
                close("fetch")
            dt = sec["dispatch"] + sec["fetch"]
        # graftlint: disable=typed-errors — the catch must be broad
        # (any step fault poisons the donated cache); the taxonomy
        # is resolved per-request via _fail_request/_shed_request
        except Exception as e:
            if (self._breaker is not None
                    and not isinstance(e, _TYPED_OUTCOMES)):
                self._breaker.record_failure()
            # the step died mid-donation: the cache buffers are no
            # longer trustworthy — rebuild the pages (the steps in
            # flight are lost with them and the step counter goes back
            # to the oldest of them: no stream has seen their tokens),
            # resume the journaled sessions in place (tentpole 2; the
            # in-graph seed makes the continued stream deterministic),
            # and fail the rest (queued requests are untouched; the
            # fresh state resets the page allocator and, in spec
            # mode, the draft cache with it)
            survivors = self._rebuild_after_fault(e)
            self._step += 1
            self._replace_survivors(survivors, e)
            close("sweep")      # requests failed and resumed: a sweep
            self._notify_journal()
            self._publish_cache_bytes()
            close("publish")
            return
        with _span("loop_sweep") as sp:
            sp.set_attr("finished", self._sweep_finished(emitted))
            sp.set_attr("emitted", sum(map(len, emitted.values())))
        close("sweep")
        with _span("loop_publish"):
            obs.step_latency.observe(dt)
            obs.steps.inc()
            obs.occupancy.observe(len(active) / max(1, self.slots))
            if self.engine.spec:
                # the round's wall time covers the fused propose +
                # the windowed verify — book it against the verify
                # entry (the dominant executable), NEVER the
                # one-token decode step that did not run
                _cost.global_cost_model().observe_time(VERIFY_FN, dt)
                if self._fresh_spec_compile():
                    self.engine.account_spec(
                        self._cache, self._tokens, self._positions,
                        self._step)
            else:
                _cost.global_cost_model().observe_time(DECODE_FN, dt)
                if counts:
                    held = counts["pairs_held"]
                    zero = counts.get("pairs_zero", 0)
                    obs.moe_touched.inc(counts["experts_touched"])
                    obs.moe_visits.inc(counts["expert_visits"])
                    obs.moe_pairs["1"].inc(held)
                    obs.moe_pairs["0"].inc(counts["pairs_routed"] - held
                                           - zero)
                    obs.moe_zero.inc(zero)
                if self._fresh_decode_compile():
                    self.engine.account_decode(
                        self._cache, self._tokens, self._positions,
                        self._step)
            if self._breaker is not None:
                self._breaker.record_success()
            _flight().progress("generation_step")
            self._notify_journal()
            self._publish_cache_bytes()
        close("publish")

    def _notify_journal(self):
        """Step-boundary poke for the session journal writer — an
        ``Event.set``, the only hot-path cost journaling adds to the
        decode loop (the batched store write happens on the journal's
        own thread)."""
        if self._sessions:
            _session_mod().global_journal().notify()

    def _fresh_decode_compile(self) -> bool:
        """True when compile_watch counted a decode trace the cost model
        has not analyzed yet (kept cheap: one counter compare)."""
        try:
            return _cost.global_cost_model().needs_account(DECODE_FN,
                                                           DECODE_FN)
        except Exception:  # graftlint: disable=typed-errors — best-effort
            return False   # cost-telemetry probe; no request outcome here

    def _fresh_spec_compile(self) -> bool:
        """The spec twin: a fresh propose OR verify trace pending cost
        analysis."""
        try:
            cm = _cost.global_cost_model()
            return (cm.needs_account(VERIFY_FN, VERIFY_FN)
                    or cm.needs_account(PROPOSE_FN, PROPOSE_FN))
        except Exception:  # graftlint: disable=typed-errors — best-effort
            return False   # cost-telemetry probe; no request outcome here

    # -------------------------------------------------------- lifecycle
    def shutdown(self):
        self._stop.set()
        with self._not_full:
            self._not_full.notify_all()
        self._thread.join(timeout=5.0)
        if self._breaker is not None:
            self._breaker.retire()
        while True:
            try:
                req = self._queue.get_nowait()
            except queue.Empty:
                break
            self._fail_request(req, ShutdownError(
                "GenerationPipeline shut down"))
        _GenMetrics.get().queue_depth.set(self._queue.qsize())
        self._publish_cache_bytes()

    def snapshot(self) -> dict:
        """Live pipeline state (``/debug/generation`` + the
        flight-recorder ``generation.json`` payload)."""
        slots = []
        tenants: dict = {}
        # kind -> (choice, why) that the decode program's trace took, where
        # the model chooses at trace time (``HybridLM.attention_backend``)
        took = getattr(self.engine.model, "attention_backend", None)
        for i, req in enumerate(self._slot_req):
            if req is None:
                slots.append({"slot": i, "state": "free"})
            else:
                slots.append({
                    "slot": i, "state": "decoding",
                    "position": int(self._positions[i]),
                    "generated": len(req.out),
                    "max_new_tokens": req.max_new_tokens,
                    "tenant": req.tenant,
                    "session": (req.session.sid
                                if req.session is not None else None),
                    "resumes": req.resumes,
                    "trace_id": (req.ctx.trace_id
                                 if req.ctx is not None else None)})
                if req.tenant is not None:
                    t = tenants.setdefault(req.tenant,
                                           {"active_slots": 0,
                                            "queued": 0})
                    t["active_slots"] += 1
        if self._qos:
            for t, n in self._queue.tenant_sizes().items():
                tenants.setdefault(t, {"active_slots": 0,
                                       "queued": 0})["queued"] = n
        eng = self.engine
        pages = None
        st = self._cache
        if eng.paged and st is not None and st.alloc is not None:
            pages = {
                "page_tokens": eng.page_tokens,
                "pages_per_slot": eng.pages_per_slot,
                "in_use": st.alloc.in_use,
                "total": st.alloc.total,
                "page_bytes": eng.page_bytes(),
                "slot_state_bytes": eng.slot_state_bytes(),
                "quant": bool(eng.kv_quant),
                "quant_gate": eng.quant_gate,
                "waiting_for_pages": self._waiting is not None,
                "slot_pages": [len(p) for p in st.slot_pages],
            }
        spec = None
        if eng.draft is not None:
            ratio = eng.spec_accept_ratio()
            spec = {
                "enabled": eng.spec,
                "spec_k": eng.spec_k,
                "rounds": eng.spec_stats["rounds"],
                "proposed": eng.spec_stats["proposed"],
                "accepted": eng.spec_stats["accepted"],
                "accept_ratio": (round(ratio, 4)
                                 if ratio is not None else None),
            }
        return {
            "qos": self._qos,
            "sessions": self._sessions,
            "tenants": tenants,
            "slots": self.slots,
            "active": self._n_active(),
            "queue_depth": self._queue.qsize(),
            "step": self._step,
            "steps_ahead": self._steps_ahead,
            "joins": {"count": self._joins, "behind": self._joins_behind,
                      **{ph + "_s": v for ph, v in self._join_s.items()},
                      "longest": self._join_longest},
            "max_len": self.engine.max_len,
            "prefill_buckets": list(self.engine.prefill_buckets),
            "attention_backend": "; ".join(
                "%s: %s: %s" % (kind, *said)
                for kind, said in took.items()) if took else None,
            "sampler": {"kind": self.engine.sampler.kind,
                        "top_k": self.engine.sampler.top_k,
                        "temperature": self.engine.sampler.temperature},
            "cache_bytes": self._safe_cache_bytes(),
            "pool_bytes": self._safe_pool_bytes(),
            "pages": pages,
            "spec": spec,
            "slot_table": slots,
        }

    def _safe_cache_bytes(self):
        """The decode thread may be mid-step (old cache donated away)
        when a /debug or bundle snapshot races this read — answer None
        for that instant rather than raising into the dump. Reports
        ACTUAL resident bytes (paged: pages in use x page bytes)."""
        try:
            return self.engine.resident_cache_bytes(self._cache)
        except Exception:  # graftlint: disable=typed-errors — snapshot
            return None    # reader racing the decode thread; answers None

    def _safe_pool_bytes(self):
        """Worst-case device footprint (the whole pool + draft cache) —
        the snapshot reports it next to the resident number."""
        try:
            return DecodeEngine.cache_bytes(self._cache)
        except Exception:  # graftlint: disable=typed-errors — snapshot
            return None    # reader racing the decode thread; answers None

    @classmethod
    def live_snapshots(cls) -> list:
        return [gp.snapshot() for gp in list(cls._live)
                if not gp._stop.is_set()]
