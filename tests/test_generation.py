"""Generative decode suite: KV-cache prefill/decode equivalence (loop and
scan trunks), seeded sampling, zero steady-state recompiles, the
continuous-batching chaos drill (faults + deadlines + mixed lengths —
every request resolves exactly once, typed or correct), admission
control, and the generative serving deploy (AOT prefill+decode warmup,
time-windowed canary)."""
import threading
import time

import numpy as np
import pytest

import jax

from deeplearning4j_tpu.models import transformer as _tr
from deeplearning4j_tpu.models.generation import (DecodeEngine,
                                                  SamplerConfig,
                                                  naive_generate)
from deeplearning4j_tpu.models.transformer import (TransformerConfig,
                                                   TransformerLM)
from deeplearning4j_tpu.observability import (compile_watch,
                                              reset_global_registry)
from deeplearning4j_tpu.parallel.generation import GenerationPipeline
from deeplearning4j_tpu.resilience import faults
from deeplearning4j_tpu.resilience.faults import (FaultPlan, FaultSpec,
                                                  InjectedFault)
from deeplearning4j_tpu.resilience.policy import (CircuitOpenError,
                                                  DeadlineExceeded,
                                                  ShedError, ShutdownError)

VOCAB = 61


def _model(scan_layers=False, seed=0):
    cfg = TransformerConfig(vocab_size=VOCAB, n_layers=2, n_heads=2,
                            d_model=32, max_len=64,
                            scan_layers=scan_layers)
    m = TransformerLM(cfg)
    return m, m.init_params(jax.random.key(seed))


# module-level engine: the jit caches live on it, so the whole module
# pays the prefill/decode compiles once (same pattern as test_serving's
# module nets on this slow box)
_ENGINE = None


def _engine():
    global _ENGINE
    if _ENGINE is None:
        m, p = _model()
        _ENGINE = DecodeEngine(m, p, max_len=48)
    return _ENGINE


def _prompt(n, seed=0):
    return np.random.default_rng(seed).integers(
        0, VOCAB, (n,)).astype(np.int32)


@pytest.fixture(autouse=True)
def _clean():
    faults.reset()
    reset_global_registry()
    yield
    faults.clear()
    GenerationPipeline.shutdown_all()


# ------------------------------------------------------------ equivalence
@pytest.mark.parametrize("scan_layers", [False, True],
                         ids=["loop_trunk", "scan_trunk"])
def test_per_token_equivalence_with_full_forward(scan_layers):
    """Incremental KV-cache decode must match the full forward at EVERY
    position: same greedy argmax (exactly) and same logits (to float
    accumulation tolerance) — on both block-storage layouts."""
    m, p = _model(scan_layers=scan_layers)
    eng = DecodeEngine(m, p, max_len=48)
    prompt = _prompt(9, seed=3)[None]
    toks, logit_steps = eng.generate(prompt, 12, return_logits=True)
    # greedy continuation equals the naive full-recompute loop
    ref = naive_generate(m, p, prompt, 12, pad_to=48)
    assert np.array_equal(toks, ref)
    # per-position logits equal the one-shot full forward over the
    # realized sequence
    full = np.concatenate([prompt, toks], axis=1)
    logits_full = np.asarray(m.apply(p, full))
    for i, step_logits in enumerate(logit_steps):
        pos = prompt.shape[1] + i - 1
        err = np.max(np.abs(step_logits - logits_full[:, pos]))
        assert err < 2e-4, f"position {pos}: logits drifted {err}"
        assert np.array_equal(np.argmax(step_logits, -1),
                              np.argmax(logits_full[:, pos], -1))


@pytest.mark.slow
def test_prefill_bucket_padding_is_invisible():
    """A prompt padded up to its length bucket decodes the same tokens
    as one that exactly fills a bucket (pad k/v is never attended)."""
    eng = _engine()
    m, p = eng.model, eng.params
    for n in (5, 16, 17):        # inside bucket 16, exact, next bucket
        prompt = _prompt(n, seed=n)[None]
        assert np.array_equal(eng.generate(prompt, 8),
                              naive_generate(m, p, prompt, 8, pad_to=48))


def test_topk_sampling_seeded_and_bounded():
    """Seeded top-k/temperature sampling: reproducible from the seed,
    different across seeds, and every sampled token is inside the top-k
    of the step's logits."""
    m, p = _model()
    s = SamplerConfig(kind="topk", top_k=4, temperature=0.8)
    a = DecodeEngine(m, p, max_len=48, sampler=s, seed=7)
    c = DecodeEngine(m, p, max_len=48, sampler=s, seed=8)
    prompt = _prompt(6, seed=1)[None]
    ta, logits = a.generate(prompt, 10, return_logits=True)
    tb = a.generate(prompt, 10)           # rng is fold_in(seed, step):
    tc = c.generate(prompt, 10)           # stateless, so a re-run repeats
    assert np.array_equal(ta, tb)
    assert not np.array_equal(ta, tc)     # 10 draws over k=4: p≈4^-10
    for i, step_logits in enumerate(logits):
        topk = np.argsort(step_logits[0])[-4:]
        assert ta[0, i] in topk


def test_sampler_config_validation():
    with pytest.raises(ValueError):
        SamplerConfig(kind="beam")
    with pytest.raises(ValueError):
        SamplerConfig(kind="topk", temperature=0.0)
    with pytest.raises(ValueError):
        DecodeEngine(*_model(), max_len=4096)   # beyond pos_emb table


def test_eos_stops_early_and_budget_caps_to_cache():
    eng = _engine()
    prompt = _prompt(7, seed=2)
    ref = eng.generate(prompt[None], 10)[0]
    eos = int(ref[0])
    out = eng.generate(prompt[None], 10, eos_id=eos)[0]
    # stops at a step boundary at/after the first eos, emitting a prefix
    # of the unconstrained continuation
    assert eos in out and len(out) < 10
    assert np.array_equal(out, ref[:len(out)])
    # an eos that never fires leaves the continuation untouched
    never = next(t for t in range(VOCAB) if t not in set(ref.tolist()))
    assert np.array_equal(eng.generate(prompt[None], 10, eos_id=never)[0],
                          ref)
    # a 40-token prompt in a 48-token cache can only decode 8 tokens —
    # the pipeline must clip the budget, never write past the pages
    with GenerationPipeline(eng, slots=2, max_new_tokens=32) as gp:
        out = gp.generate(_prompt(40, seed=4), max_new_tokens=32)
        assert len(out) == 48 - 40


# ---------------------------------------------------- compile discipline
def test_zero_steady_state_decode_recompiles():
    """After one request has warmed a prefill bucket and the decode
    executable, further traffic (mixed sizes inside the same buckets)
    triggers ZERO new XLA traces — the executable-set contract."""
    eng = _engine()
    watch = compile_watch.global_compile_watch()
    with GenerationPipeline(eng, slots=3, max_new_tokens=6) as gp:
        gp.generate(_prompt(5), max_new_tokens=6)      # bucket 16
        gp.generate(_prompt(17), max_new_tokens=6)     # bucket 32
        before = {fn: watch.count_for(fn)
                  for fn in ("TransformerLM.prefill",
                             "TransformerLM.decode_step")}
        threads = [threading.Thread(
            target=gp.generate, args=(_prompt(3 + i),),
            kwargs={"max_new_tokens": 5}) for i in range(6)]
        for t in threads:
            t.start()
        for t in threads:
            t.join(timeout=60)
        after = {fn: watch.count_for(fn) for fn in before}
    assert before == after, f"steady-state retraced: {before} -> {after}"


def test_decode_path_never_reaches_flash_policy(monkeypatch):
    """The decode step is XLA single-query attention: tracing and running
    it never consults the attention-backend policy, so no Pallas kernel
    can land in a decode executable. Prefill consults it at trace time."""
    calls = {"n": 0}
    real = _tr._use_flash_attention

    def counting(seq_len=None):
        calls["n"] += 1
        return real(seq_len)

    monkeypatch.setattr(_tr, "_use_flash_attention", counting)
    m, p = _model()
    eng = DecodeEngine(m, p, max_len=48)    # fresh jits: every call traces
    first, _logits, kv, t = eng.prefill(_prompt(5)[None])
    assert calls["n"] >= 1
    calls["n"] = 0
    state = eng.insert_slot(eng.new_state(1), kv, 0)
    eng.decode(state, np.asarray(first), np.full((1,), t, np.int32), 1)
    assert calls["n"] == 0


def test_attn_backend_knob(monkeypatch):
    """``DL4J_TPU_ATTN_BACKEND`` forces the attention backend at trace
    time: ``xla`` disables the flash path everywhere, ``flash`` forces
    it, ``auto`` keeps the measured-crossover policy."""
    monkeypatch.setenv("DL4J_TPU_ATTN_BACKEND", "xla")
    assert _tr._use_flash_attention(8192) is False
    monkeypatch.setenv("DL4J_TPU_ATTN_BACKEND", "flash")
    assert _tr._use_flash_attention(64) is True
    monkeypatch.setenv("DL4J_TPU_ATTN_BACKEND", "auto")
    assert _tr._use_flash_attention(64) is False    # < FLASH_MIN_SEQ


# ------------------------------------------------------- admission control
def test_queue_full_sheds_and_deadline_walk_away():
    eng = _engine()
    gp = GenerationPipeline(eng, slots=1, max_new_tokens=24,
                            max_queue_depth=1, shed_policy="reject_newest")
    try:
        results = []

        def long_one():
            try:
                results.append(("ok", gp.generate(_prompt(5),
                                                  max_new_tokens=24)))
            except Exception as e:
                results.append(("err", e))

        threads = [threading.Thread(target=long_one) for _ in range(6)]
        for t in threads:
            t.start()
            time.sleep(0.01)
        # an expired caller resolves typed instead of hanging — shed at
        # the full queue, or walked away at its deadline if it got in
        with pytest.raises((DeadlineExceeded, ShedError)):
            gp.generate(_prompt(4), max_new_tokens=24, deadline_ms=1.0)
        for t in threads:
            t.join(timeout=60)
        assert len(results) == 6
        kinds = [k for k, _ in results]
        assert kinds.count("ok") >= 1
        for k, v in results:
            if k == "err":
                assert isinstance(v, (ShedError, DeadlineExceeded))
    finally:
        gp.shutdown()
    # post-shutdown: typed refusal, not a hang
    with pytest.raises(ShutdownError):
        gp.generate(_prompt(3))
    # the walk-away path specifically: an unbounded queue, one slot
    # busy with a long generation, and a deadline far shorter than it —
    # the caller must claim its own request and leave typed
    with GenerationPipeline(eng, slots=1, max_new_tokens=48) as gp2:
        t = threading.Thread(target=lambda: gp2.generate(
            _prompt(5), max_new_tokens=48))
        t.start()
        time.sleep(0.01)                 # the long request owns the slot
        with pytest.raises(DeadlineExceeded):
            gp2.generate(_prompt(4), max_new_tokens=16, deadline_ms=4.0)
        t.join(timeout=60)


def test_prompt_too_long_is_a_value_error():
    eng = _engine()
    with GenerationPipeline(eng, slots=1) as gp:
        with pytest.raises(ValueError):
            gp.generate(_prompt(60))        # > largest prefill bucket (48)


# ------------------------------------------------------------ chaos drill
def test_continuous_batching_chaos_drill():
    """Faults at ``generation.step`` (transient + crash + latency) with
    per-request deadlines and mixed lengths: every concurrent request
    resolves EXACTLY once — a token array, a typed outcome, or the
    injected fault — and none hang."""
    eng = _engine()
    plan = FaultPlan([
        FaultSpec("generation.step", "error", rate=0.3, count=4),
        FaultSpec("generation.step", "crash", rate=0.15, count=2),
        FaultSpec("generation.step", "latency", rate=0.2, count=3,
                  latency_seconds=0.02),
    ], seed=11)
    outcomes = []
    lock = threading.Lock()
    with faults.active(plan):
        gp = GenerationPipeline(eng, slots=3, max_new_tokens=10,
                                max_queue_depth=8,
                                shed_policy="reject_newest")
        try:
            def one(i):
                try:
                    out = gp.generate(
                        _prompt(3 + (i * 5) % 28, seed=i),
                        max_new_tokens=4 + i % 9,
                        deadline_ms=20000.0 if i % 4 else 3000.0)
                    with lock:
                        outcomes.append(("ok", len(out)))
                except (ShedError, DeadlineExceeded, CircuitOpenError,
                        ShutdownError) as e:
                    with lock:
                        outcomes.append(("typed", type(e).__name__))
                except InjectedFault as e:
                    with lock:
                        outcomes.append(("injected", e.kind))
                except Exception as e:     # pragma: no cover - must not
                    with lock:
                        outcomes.append(("UNEXPECTED", repr(e)))

            threads = [threading.Thread(target=one, args=(i,))
                       for i in range(12)]
            for t in threads:
                t.start()
            for t in threads:
                t.join(timeout=120)
            assert not any(t.is_alive() for t in threads), \
                "a generation request hung under chaos"
        finally:
            gp.shutdown()
    assert len(outcomes) == 12              # exactly once each
    assert not [o for o in outcomes if o[0] == "UNEXPECTED"], outcomes
    assert any(k == "ok" for k, _ in outcomes)
    injected = faults.snapshot()["injected"]
    assert any(k.startswith("generation.step") for k in injected), injected


def test_generation_kill_switch_runs_without_policies(monkeypatch):
    """DL4J_TPU_RESILIENCE=0: no breaker, no deadlines, no shedding —
    plain continuous batching still serves correctly."""
    monkeypatch.setenv("DL4J_TPU_RESILIENCE", "0")
    eng = _engine()
    ref = eng.generate(_prompt(5)[None], 6)[0]
    with GenerationPipeline(eng, slots=2, max_new_tokens=6,
                            max_queue_depth=1,
                            shed_policy="reject_newest") as gp:
        assert gp._breaker is None and gp._shed_policy is None
        out = gp.generate(_prompt(5), max_new_tokens=6,
                          deadline_ms=0.0001)   # deadline ignored
        assert np.array_equal(out, ref)


# -------------------------------------------------------------- serving
@pytest.mark.slow
def test_deploy_generative_zero_first_request_traces():
    """A generative deploy AOT-warms prefill (every bucket), slot
    insert, and the decode step; the first routed request compiles
    nothing."""
    from deeplearning4j_tpu.serving import ModelRegistry, ServingRouter
    m, p = _model(seed=5)
    reg = ModelRegistry()
    try:
        dv = reg.deploy_generative(
            "gen-v1", DecodeEngine(m, p, max_len=48), slots=2,
            max_new_tokens=8)
        assert dv.kind == "generative"
        assert dv.warmed_buckets == list(
            dv.gp.engine.prefill_buckets)
        watch = compile_watch.global_compile_watch()
        before = watch.total
        router = ServingRouter(reg, "gen-v1")
        out = router.generate(_prompt(5), max_new_tokens=6)
        assert len(out) == 6
        assert watch.total == before, "first generate request compiled"
        snap = dv.snapshot()
        assert snap["kind"] == "generative" and snap["state"] == "live"
    finally:
        reg.shutdown()


@pytest.mark.slow
def test_generative_canary_time_window_rolls_back_on_faults():
    """A generative canary under time-based evaluation windows: chaos on
    the canary path (serving.canary errors) rolls the candidate back on
    the wall clock even at low traffic, with every request resolved."""
    from deeplearning4j_tpu.serving import (ModelRegistry, RolloutPolicy,
                                            RolloutState, ServingRouter)
    m1, p1 = _model(seed=6)
    m2, p2 = _model(seed=7)
    reg = ModelRegistry()
    try:
        reg.deploy_generative("gen-a", DecodeEngine(m1, p1, max_len=48),
                              slots=2, max_new_tokens=8)
        reg.deploy_generative("gen-b", DecodeEngine(m2, p2, max_len=48),
                              slots=2, max_new_tokens=8)
        router = ServingRouter(reg, "gen-a")
        rollout = router.begin_rollout("gen-b", RolloutPolicy(
            start_stage=RolloutState.CANARY, canary_fraction=1.0,
            window_seconds=0.1, window_min_requests=1,
            error_rate_degraded=0.01, error_rate_failing=0.05,
            min_requests=2, min_latency_count=10 ** 6, min_shadow=10 ** 6,
            healthy_windows=10 ** 6))
        plan = FaultPlan([FaultSpec("serving.canary", "error", rate=1.0)],
                         seed=3)
        with faults.active(plan):
            deadline = time.monotonic() + 30
            while rollout.active and time.monotonic() < deadline:
                try:
                    router.generate(_prompt(5), max_new_tokens=4)
                except InjectedFault:
                    pass
                time.sleep(0.02)
        assert rollout.stage == RolloutState.ROLLED_BACK
        assert rollout.rollback_reason.startswith("slo:")
        # traffic snapped back to the incumbent and still serves
        out = router.generate(_prompt(5), max_new_tokens=4)
        assert len(out) == 4
    finally:
        reg.shutdown()


def test_generation_snapshot_surfaces():
    """The pipeline snapshot (the /debug/generation + generation.json
    payload) names slots, occupancy, and the per-slot decode state."""
    import json as _json
    eng = _engine()
    with GenerationPipeline(eng, slots=2, max_new_tokens=4) as gp:
        gp.generate(_prompt(5), max_new_tokens=4)
        snap = gp.snapshot()
        _json.dumps(snap)                    # must be JSON-serializable
        assert snap["slots"] == 2
        # cache_bytes now reports ACTUAL resident bytes (pages in use x
        # page bytes) — zero once every generation drained; the
        # worst-case pool footprint sits next to it
        assert snap["cache_bytes"] == 0
        assert snap["pool_bytes"] > 0
        assert snap["pages"]["total"] > 0
        assert snap["pages"]["in_use"] == 0
        assert snap["pages"]["page_tokens"] == eng.page_tokens
        assert len(snap["slot_table"]) == 2
        assert snap["sampler"]["kind"] == "greedy"
        assert GenerationPipeline.live_snapshots()
