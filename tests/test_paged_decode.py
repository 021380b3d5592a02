"""Paged + int8 KV cache and speculative decoding (PR 13).

Covers the page allocator (alloc/free/reuse, exhaustion, the 1k
join/leave no-leak cycle), paged-vs-dense token equivalence and kill
switches (``DL4J_TPU_KV_PAGE_TOKENS=0`` / ``DL4J_TPU_SPEC_DECODE=0`` /
``DL4J_TPU_KV_QUANT=0`` all restore prior behavior byte-identically),
the int8 numerics gate (trips on an injected bad scale, falls back to
f32 storage byte-identically), page-admission semantics in the pipeline
(admit on free pages, waiting joiner, typed shed + step-boundary
reclamation on exhaustion, admission resumes after reclaim), the
speculative accept/resample loop (greedy byte-exactness, seeded
resample distribution == the target's), and the paged+spec chaos drill
(every request resolves exactly once, pages all reclaimed)."""
import dataclasses
import threading
import time

import numpy as np
import pytest

import jax
import jax.numpy as jnp

from deeplearning4j_tpu.models import transformer as _tr
from deeplearning4j_tpu.models.generation import (DecodeEngine,
                                                  PageAllocator,
                                                  SamplerConfig,
                                                  _dist_probs)
from deeplearning4j_tpu.models.transformer import (TransformerConfig,
                                                   TransformerLM)
from deeplearning4j_tpu.observability import (compile_watch,
                                              global_registry,
                                              reset_global_registry)
from deeplearning4j_tpu.parallel.generation import GenerationPipeline
from deeplearning4j_tpu.resilience import faults
from deeplearning4j_tpu.resilience.faults import (FaultPlan, FaultSpec,
                                                  InjectedFault)
from deeplearning4j_tpu.resilience.policy import (CachePagesExhausted,
                                                  CircuitOpenError,
                                                  DeadlineExceeded,
                                                  ShedError, ShutdownError)

VOCAB = 61
PAGE = 16
MAXLEN = 48


def _model(n_layers=2, seed=0):
    cfg = TransformerConfig(vocab_size=VOCAB, n_layers=n_layers,
                            n_heads=2, d_model=32, max_len=64)
    m = TransformerLM(cfg)
    return m, m.init_params(jax.random.key(seed))


_M, _P = None, None


def _mp():
    global _M, _P
    if _M is None:
        _M, _P = _model()
    return _M, _P


# module-level engines: the jit caches live on them, so the whole module
# pays each executable set once (test_generation's pattern)
_ENGINES = {}


def _engine(kind="paged"):
    if kind not in _ENGINES:
        m, p = _mp()
        if kind == "dense":
            _ENGINES[kind] = DecodeEngine(m, p, max_len=MAXLEN,
                                          page_tokens=0)
        elif kind == "paged":
            _ENGINES[kind] = DecodeEngine(m, p, max_len=MAXLEN,
                                          page_tokens=PAGE)
        elif kind == "spec":
            # identity draft: accept ratio 1.0, the strongest byte-
            # equality probe of the verify/accept machinery
            draft = DecodeEngine(m, p, max_len=MAXLEN, page_tokens=0)
            _ENGINES[kind] = DecodeEngine(m, p, max_len=MAXLEN,
                                          page_tokens=PAGE, draft=draft,
                                          spec_k=3)
    return _ENGINES[kind]


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


# --------------------------------------------------------- page allocator
def test_page_allocator_alloc_free_reuse():
    a = PageAllocator(8)
    assert a.total == 8 and a.free_count == 8 and a.in_use == 0
    got = a.alloc(3)
    assert len(got) == 3 and len(set(got)) == 3 and a.in_use == 3
    # all-or-nothing: an unsatisfiable request grants NOTHING
    assert a.alloc(6) is None
    assert a.in_use == 3
    a.free(got[:2])
    assert a.in_use == 1 and a.free_count == 7
    # freed pages are reusable (LIFO keeps the working set warm)
    again = a.alloc(7)
    assert again is not None and a.free_count == 0
    assert a.alloc(1) is None
    assert a.alloc(0) == []


def test_page_allocator_rejects_bad_frees():
    a = PageAllocator(4)
    got = a.alloc(2)
    with pytest.raises(ValueError):
        a.free([99])                       # outside the pool
    with pytest.raises(ValueError):
        a.free([got[0], got[0]])           # duplicate WITHIN the list
    assert a.in_use == 2                   # rejected frees freed nothing
    a.free(got)
    with pytest.raises(ValueError):
        a.free([got[0]])                   # double free
    with pytest.raises(ValueError):
        PageAllocator(0)


def test_page_allocator_1k_cycles_no_leak():
    """1000 mixed-size alloc/free cycles: the pool always drains back to
    fully free and can always satisfy a full-pool allocation — no
    fragmentation, no leaked or duplicated page ids."""
    a = PageAllocator(32)
    rng = np.random.default_rng(5)
    held = []
    for i in range(1000):
        if held and (rng.random() < 0.5 or a.free_count == 0):
            a.free(held.pop(rng.integers(0, len(held))))
        else:
            got = a.alloc(int(rng.integers(1, 5)))
            if got is not None:
                held.append(got)
        live = [p for h in held for p in h]
        assert len(live) == len(set(live)) == a.in_use
    for h in held:
        a.free(h)
    assert a.in_use == 0 and a.free_count == 32
    assert len(a.alloc(32)) == 32          # whole pool still allocable


def test_engine_join_leave_cycles_return_pages():
    """Engine-level join/leave churn: repeated insert/free across slots
    leaves the allocator fully drained and every table row on the trash
    page — the slot-leave-returns-pages contract."""
    eng = _engine("paged")
    state = eng.new_state(3)
    _first, _l, kv, _t = eng.prefill(_prompt(9)[None])
    rng = np.random.default_rng(2)
    for i in range(120):
        slot = int(rng.integers(0, 3))
        state = eng.insert_slot(state, kv, slot)
        assert state.alloc.in_use >= 1
        if rng.random() < 0.8:
            eng.free_slot(state, slot)
    for slot in range(3):
        eng.free_slot(state, slot)
    assert state.alloc.in_use == 0
    assert (state.tables == state.alloc.total).all()
    assert eng.resident_cache_bytes(state) == 0


# ----------------------------------------------------- paged equivalence
def test_paged_decode_matches_dense_tokens():
    """Paged gather/scatter decode emits the same greedy continuation as
    the dense cache at every prompt length class (inside a page, page-
    exact, multi-page)."""
    dense, paged = _engine("dense"), _engine("paged")
    for n in (5, 16, 23):
        prompt = _prompt(n, seed=n)[None]
        assert np.array_equal(paged.generate(prompt, 10),
                              dense.generate(prompt, 10)), \
            f"paged decode diverged at prompt length {n}"


def test_kill_switch_page_tokens_zero_is_dense(monkeypatch):
    """DL4J_TPU_KV_PAGE_TOKENS=0: the engine builds the dense cache and
    emits byte-identical tokens — the pre-paged path, untouched."""
    monkeypatch.setenv("DL4J_TPU_KV_PAGE_TOKENS", "0")
    m, p = _mp()
    eng = DecodeEngine(m, p, max_len=MAXLEN)
    assert not eng.paged and eng.new_state(2).mode == "dense"
    out = eng.generate(_prompt(7)[None], 8)
    assert np.array_equal(out, _engine("dense").generate(
        _prompt(7)[None], 8))
    with GenerationPipeline(eng, slots=2, max_new_tokens=6) as gp:
        ref = _engine("dense").generate(_prompt(5)[None], 6)[0]
        assert np.array_equal(gp.generate(_prompt(5), max_new_tokens=6),
                              ref)
        assert gp.snapshot()["pages"] is None


def test_kill_switch_spec_decode_zero(monkeypatch):
    """DL4J_TPU_SPEC_DECODE=0: a draft-equipped engine decodes plain
    one-token steps — byte-identical, no propose/verify executables."""
    monkeypatch.setenv("DL4J_TPU_SPEC_DECODE", "0")
    m, p = _mp()
    draft = DecodeEngine(m, p, max_len=MAXLEN, page_tokens=0)
    eng = DecodeEngine(m, p, max_len=MAXLEN, page_tokens=PAGE,
                       draft=draft, spec_k=3)
    assert not eng.spec
    out = eng.generate(_prompt(7)[None], 8)
    assert np.array_equal(out, _engine("dense").generate(
        _prompt(7)[None], 8))
    assert eng.spec_stats["rounds"] == 0


def test_kill_switch_kv_quant_zero(monkeypatch):
    """DL4J_TPU_KV_QUANT=0 (and unset): f32 page storage, no gate run,
    byte-identical to the plain paged engine. STRICT parsing: only a
    literal '1' opts into the numerics-changing feature."""
    monkeypatch.setenv("DL4J_TPU_KV_QUANT", "0")
    m, p = _mp()
    eng = DecodeEngine(m, p, max_len=MAXLEN, page_tokens=PAGE)
    assert not eng.kv_quant
    st = eng.new_state(1)
    assert "k_scale" not in st.arrays and eng.quant_gate is None
    assert np.array_equal(eng.generate(_prompt(7)[None], 8),
                          _engine("paged").generate(_prompt(7)[None], 8))
    for raw in ("false", "off", "no", ""):
        monkeypatch.setenv("DL4J_TPU_KV_QUANT", raw)
        assert not DecodeEngine(m, p, max_len=MAXLEN,
                                page_tokens=PAGE).kv_quant, raw
    # a malformed PAGE_TOKENS value must refuse loudly — a failed
    # dense-rollback attempt can never silently keep paging on
    monkeypatch.setenv("DL4J_TPU_KV_PAGE_TOKENS", "O")
    with pytest.raises(ValueError):
        DecodeEngine(m, p, max_len=MAXLEN)


# ------------------------------------------------------- quant numerics
def test_quant_gate_passes_and_stores_int8():
    m, p = _mp()
    eng = DecodeEngine(m, p, max_len=MAXLEN, page_tokens=PAGE,
                       kv_quant=True)
    st = eng.new_state(1)                  # gate runs on first state
    gate = eng.quant_gate
    assert gate["checked"] and gate["passed"]
    assert gate["max_abs_logit_diff"] <= gate["tol"]
    assert eng.kv_quant and "k_scale" in st.arrays
    assert all(a.dtype == np.int8 for n in ("k", "v") for a in st.arrays[n])
    # int8 pages cost a fraction of f32 pages (the admission win)
    assert eng.page_bytes() < _engine("paged").page_bytes() / 3
    # quantized decode stays argmax-faithful on a real continuation
    out = eng.generate(_prompt(9)[None], 10)
    ref = _engine("dense").generate(_prompt(9)[None], 10)
    assert out.shape == ref.shape


def test_quant_gate_trips_on_bad_scale_and_falls_back(monkeypatch):
    """An injected corrupt quantization scale must trip the deploy-time
    gate (loud fallback), and the fallen-back engine's output must be
    BYTE-IDENTICAL to the plain f32 paged engine."""
    real = _tr.quantize_kv_rows

    def corrupt(rows):
        q8, scale = real(rows)
        return q8, scale * 7.0             # dequant now 7x off

    monkeypatch.setattr(_tr, "quantize_kv_rows", corrupt)
    m, p = _mp()
    eng = DecodeEngine(m, p, max_len=MAXLEN, page_tokens=PAGE,
                       kv_quant=True)
    st = eng.new_state(1)
    gate = eng.quant_gate
    assert gate["checked"] and not gate["passed"]
    assert gate["max_abs_logit_diff"] > gate["tol"]
    assert not eng.kv_quant                # fell back
    assert "k_scale" not in st.arrays
    assert all(a.dtype != np.int8 for n in ("k", "v") for a in st.arrays[n])
    out = eng.generate(_prompt(9)[None], 10)
    assert np.array_equal(out, _engine("paged").generate(
        _prompt(9)[None], 10))


# ------------------------------------- the layout and the in-place writes
# One (n_pages, P, H·hd) array a layer, written where it lies (PR 48). The
# limits come from the dtypes, against logits of order 0.5, with the first
# readings beside them: float32 pages hold the forward's own rows, so a
# window's logits differ from ``apply()`` by summation order alone (ten
# steps of 2**-23; read 6e-8 - 7.5e-8); bfloat16 rounds every product to
# 2**-8 (read 1.2e-3 - 1.4e-3); an int8 row is 1/127 of its largest entry a
# step, averaged over 32 lanes and 48 rows (read 1.6e-4 - 2.2e-4), far
# inside the deploy-time gate's ``quant_tol`` of 0.05.
_KINDS = ("float32", "bfloat16", "int8")
_LIMIT = {"float32": 1e-6, "bfloat16": 5e-3, "int8": 2e-3}
_KIND_MODELS = {}


def _kind_model(kind):
    """(model, params, quant): the module's float32 weights, computed in
    ``kind``; int8 pages under the float32 model."""
    if kind not in _KIND_MODELS:
        m, p = _mp()
        if kind == "bfloat16":
            m = TransformerLM(dataclasses.replace(m.config,
                                                  dtype=jnp.bfloat16))
        _KIND_MODELS[kind] = (m, p, kind == "int8")
    return _KIND_MODELS[kind]


def _noise_pool(model, n_pages, quant, seed=3):
    """A pool whose every row holds something: a write that strays shows."""
    rng = np.random.default_rng(seed)
    pool = model.init_paged_cache(n_pages, PAGE, quant=quant)
    return {name: [jnp.asarray(
        rng.integers(-100, 100, a.shape).astype(np.int8) if a.dtype == np.int8
        else rng.uniform(0.5, 1.5, a.shape), a.dtype) for a in held]
            for name, held in pool.items()}


def _same_int8_rows(got, want, got_scale, want_scale):
    """The same quantised rows from two programs (one eager, one fused): a
    scale may differ in its last bit, and a value that sat on a rounding
    boundary by one step."""
    np.testing.assert_allclose(got_scale, want_scale, rtol=1e-6)
    assert np.abs(got.astype(np.int32) - want.astype(np.int32)).max() <= 1
    assert (got != want).mean() < 1e-2


def _host(pool):
    return {name: [np.array(a) for a in held] for name, held in pool.items()}


@pytest.mark.parametrize("kind", ["bfloat16", "int8"])
def test_insert_writes_the_joiners_pages_and_no_other(kind):
    """After ``insert_paged`` (the engine's donated program) every page NOT
    among the joiner's, the trash page with them, is bit for bit what it
    was, and the joiner's pages hold ``pack_kv_pages``' rows of whole
    lanes, layer by layer."""
    m, p, quant = _kind_model(kind)
    eng = DecodeEngine(m, p, max_len=MAXLEN, page_tokens=PAGE)
    _first, _l, kv, _t = eng.prefill(_prompt(20)[None])      # bucket 32
    n_pages, ids = 8, np.asarray([5, 2], np.int32)
    pool = _noise_pool(m, n_pages, quant)
    before = _host(pool)
    leaves = ("k", "v", "k_scale", "v_scale") if quant else ("k", "v")
    assert set(pool) == set(leaves)
    for name in leaves:
        assert len(pool[name]) == m.config.n_layers
        assert all(a.shape == (n_pages, PAGE, m.config.d_model)[:a.ndim]
                   for a in pool[name])
    after = _host(eng._insert_paged_jit(pool, kv, ids, 0))
    others = np.setdiff1d(np.arange(n_pages), ids)
    assert n_pages - 1 in others                       # the trash page
    for name in leaves:
        for li in range(m.config.n_layers):
            assert np.array_equal(after[name][li][others],
                                  before[name][li][others]), (name, li)
    for name in ("k", "v"):
        rows = kv[name]
        if quant:
            rows, scale = _tr.quantize_kv_rows(rows)
            want_scale = np.asarray(_tr.pack_kv_pages(scale, PAGE))
        want = np.asarray(_tr.pack_kv_pages(rows, PAGE))
        assert want.shape == (m.config.n_layers, 2, PAGE, m.config.d_model)
        for li in range(m.config.n_layers):
            if quant:
                _same_int8_rows(after[name][li][ids], want[li],
                                after[name + "_scale"][li][ids],
                                want_scale[li])
            else:
                assert np.array_equal(after[name][li][ids], want[li])


#: (window, tokens already cached): a first row on a page the prefill did
#: not touch, a window across a page boundary, a window whose last row lies
#: past the slot's last logical page, and a step wholly past it
_WINDOWS = [(1, 16), (3, 14), (3, 46), (1, 48)]


@pytest.mark.parametrize("kind", _KINDS)
@pytest.mark.parametrize("window,cached", _WINDOWS,
                         ids=[f"W{w}at{n}" for w, n in _WINDOWS])
def test_window_through_the_pages_gives_the_forwards_logits(kind, window,
                                                            cached):
    """``decode_window_paged`` over a slot whose pages lie out of order in
    the pool, beside a free slot: each window row's logits are ``apply()``'s
    at that position; the rows land at (page of the table, position % P) of
    every layer's array and NOWHERE else, a row past the last logical page
    on the trash page."""
    m, p, quant = _kind_model(kind)
    S = MAXLEN
    tokens = _prompt(cached + window, seed=cached)
    n_pages, trash, own = 8, 7, [4, 1, 6]
    pool = _noise_pool(m, n_pages, quant, seed=cached)
    _logits, kv = m.prefill(p, jnp.asarray(tokens[None, :cached]))
    pool = m.insert_paged(pool, kv, jnp.asarray(own, jnp.int32), 1, PAGE)
    before = _host(pool)
    tables = np.full((2, S // PAGE), trash, np.int32)
    tables[1] = own
    win = np.stack([np.zeros(window, np.int32), tokens[cached:]])
    step = jax.jit(m.decode_window_paged, static_argnums=(5,),
                   donate_argnums=(1,))
    logits, pool = step(p, pool, jnp.asarray(tables), jnp.asarray(win),
                        jnp.asarray([0, cached], jnp.int32), PAGE)
    after = _host(pool)
    assert logits.shape == (2, window, VOCAB)
    inside = [j for j in range(window) if cached + j < S]
    if inside:
        upto = cached + len(inside)
        want = np.asarray(m.apply(p, jnp.asarray(tokens[None, :upto])),
                          np.float32)[0, cached:upto]
        got = np.asarray(logits, np.float32)[1, :len(inside)]
        assert np.abs(got - want).max() <= _LIMIT[kind], \
            np.abs(got - want).max()
    # where the rows went: the free slot's (table row on the trash page) at
    # positions 0.., the served slot's through its table or past it
    written = {(trash, j) for j in range(window)}
    for j in range(window):
        pos = cached + j
        written.add((own[pos // PAGE], pos % PAGE) if pos < S
                    else (trash, pos % PAGE))
    keep = np.ones((n_pages, PAGE), bool)
    for page, off in written:
        keep[page, off] = False
    for name, held in after.items():
        assert len(held) == m.config.n_layers
        for li, arr in enumerate(held):
            assert np.array_equal(arr[keep], before[name][li][keep]), \
                (name, li)
            if not name.endswith("_scale"):
                assert all((arr[pg, off] != before[name][li][pg, off]).any()
                           for pg, off in written), (name, li)


def test_the_gate_probes_through_the_insert_production_traces(monkeypatch):
    """``pack_kv_pages`` is the ONE packing: the int8 gate's eager probe
    fills its pool through ``insert_paged``, the function the engine's
    donated program traces, and both give the same rows for the same
    prefill."""
    m, p = _mp()
    seen = []
    real = TransformerLM.insert_paged

    def spy(self, pool, kv, page_ids, slot, page_tokens):
        out = real(self, pool, kv, page_ids, slot, page_tokens)
        seen.append((kv, page_ids, out))
        return out

    packed = []
    real_pack = _tr.pack_kv_pages
    monkeypatch.setattr(TransformerLM, "insert_paged", spy)
    monkeypatch.setattr(_tr, "pack_kv_pages", lambda arr, page_tokens: (
        packed.append(arr.shape), real_pack(arr, page_tokens))[1])
    eng = DecodeEngine(m, p, max_len=MAXLEN, page_tokens=PAGE, kv_quant=True)
    state = eng.new_state(1)               # the gate runs here, eagerly
    assert eng.quant_gate["passed"] and len(seen) == 1
    kv, ids, probe = seen[0]
    assert len(packed) == 4                # k, k_scale, v, v_scale: once each
    assert not isinstance(probe["k"][0], jax.core.Tracer)
    n = len(np.asarray(ids))
    state.slot_pages[0] = state.alloc.alloc(n)
    traced = eng._insert_paged_jit(
        state.arrays, kv, np.asarray(state.slot_pages[0], np.int32), 0)
    assert len(seen) == 2 and len(packed) == 8      # the same two functions
    theirs, mine = np.asarray(state.slot_pages[0]), np.asarray(ids)
    for name in ("k", "v"):
        for li in range(m.config.n_layers):
            _same_int8_rows(
                np.asarray(traced[name][li])[theirs],
                np.asarray(probe[name][li])[mine],
                np.asarray(traced[name + "_scale"][li])[theirs],
                np.asarray(probe[name + "_scale"][li])[mine])


# --------------------------------------------------- pipeline admission
def test_admission_by_pages_waiting_joiner_completes():
    """Three full-length streams into a pool that backs exactly two:
    the third request WAITS for pages (never shed — slots are plentiful,
    pages are the admission unit) and completes once a stream drains —
    _admit admits on free pages, not free slots."""
    eng = _engine("paged")
    # prompt 40 → bucket 48 → 3 pages at admission; budget 8 fills the
    # cache exactly (no growth) — two streams pin all 6 pages
    gp = GenerationPipeline(eng, slots=3, max_new_tokens=8,
                            cache_pages=2 * eng.pages_per_slot)
    try:
        results = []
        lock = threading.Lock()

        def one(i):
            out = gp.generate(_prompt(40, seed=i), max_new_tokens=8)
            with lock:
                results.append(len(out))

        threads = [threading.Thread(target=one, args=(i,))
                   for i in range(3)]
        for t in threads:
            t.start()
            time.sleep(0.05)
        # while the first two decode, the third is parked for pages
        for t in threads:
            t.join(timeout=60)
        assert results == [8, 8, 8]        # all completed, none shed
        assert gp._cache.alloc.in_use == 0
        reg = global_registry()
        shed = reg.get("dl4j_decode_shed_total")
        series = {lv: c.value for lv, c in shed.series()}
        assert series.get(("pages_exhausted",), 0) == 0
    finally:
        gp.shutdown()


def test_page_exhaustion_sheds_typed_then_admission_resumes():
    """Over-admitted long generations exhaust a small pool: the shed is
    the typed CachePagesExhausted at a step boundary, pages return to
    the pool, and admission RESUMES — a fresh request after the storm
    completes normally."""
    eng = _engine("paged")
    gp = GenerationPipeline(eng, slots=4, max_new_tokens=36,
                            cache_pages=6, queue_limit=16)
    try:
        outcomes = []
        lock = threading.Lock()

        def one(i):
            try:
                out = gp.generate(_prompt(20, seed=i), max_new_tokens=25)
                with lock:
                    outcomes.append(("ok", len(out)))
            except CachePagesExhausted:
                with lock:
                    outcomes.append(("pages", None))
            except ShedError as e:
                with lock:
                    outcomes.append(("shed", type(e).__name__))

        threads = [threading.Thread(target=one, args=(i,))
                   for i in range(8)]
        for t in threads:
            t.start()
        for t in threads:
            t.join(timeout=60)
        assert len(outcomes) == 8
        kinds = [k for k, _ in outcomes]
        assert kinds.count("ok") >= 3
        assert "pages" in kinds            # the typed reclamation shed
        assert gp._cache.alloc.in_use == 0
        # admission resumed: a post-storm request completes
        assert len(gp.generate(_prompt(10), max_new_tokens=10)) == 10
        reg = global_registry()
        shed = reg.get("dl4j_decode_shed_total")
        assert shed.labels(reason="pages_exhausted").value > 0
    finally:
        gp.shutdown()


def test_reclamation_victim_is_the_youngest_request():
    """When the pool exhausts mid-decode the YOUNGEST active request is
    shed — even when the younger request is the one needing the page.
    Oldest generations win unconditionally (a newcomer's growth must
    never discard an elder's progress)."""
    eng = _engine("paged")
    # elder: prompt 9 → bucket 16 (1 page), grows to 3 pages by pos 32;
    # younger: prompt 20 → bucket 32 (2 pages), needs its 3rd page at
    # pos 32 too. Pool of 4: after both admit (3 pages), ONE spare page
    # goes to whoever crosses first; the next crossing exhausts.
    gp = GenerationPipeline(eng, slots=2, max_new_tokens=40,
                            cache_pages=4)
    try:
        results = {}

        def run(name, prompt, budget):
            try:
                results[name] = gp.generate(prompt, max_new_tokens=budget)
            except BaseException as e:
                results[name] = e

        elder = threading.Thread(
            target=run, args=("elder", _prompt(9, seed=1), 30))
        elder.start()
        deadline = time.monotonic() + 20
        while gp._n_active() == 0 and time.monotonic() < deadline:
            time.sleep(0.005)
        younger = threading.Thread(
            target=run, args=("younger", _prompt(20, seed=2), 25))
        younger.start()
        elder.join(timeout=60)
        younger.join(timeout=60)
        assert isinstance(results["younger"], CachePagesExhausted), \
            results
        assert isinstance(results["elder"], np.ndarray) \
            and len(results["elder"]) == 30
        assert gp._cache.alloc.in_use == 0
    finally:
        gp.shutdown()


def test_priority_preempts_for_pages():
    """The PR-12 priority guarantee must survive the paged default:
    with free SLOTS but zero free PAGES, a higher-tier tenant's joiner
    preempts a lower-tier generation for its pages (the victim resolves
    with the typed PreemptedError) instead of parking forever."""
    from deeplearning4j_tpu.resilience import qos
    eng = _engine("paged")
    qos.global_tenants().configure(
        {"low": qos.TenantPolicy("low", priority=0),
         "hi": qos.TenantPolicy("hi", priority=2)})
    try:
        # slots are plentiful (4); the pool backs exactly one
        # full-length stream — pages are the only contended resource
        gp = GenerationPipeline(eng, slots=4, max_new_tokens=40,
                                cache_pages=eng.pages_per_slot)
        results = {}

        def low():
            try:
                # short prompt + long budget: the low-tier stream stays
                # on the device long enough for the hi-tier joiner to
                # contend (a 1-page admit growing toward 3)
                results["low"] = gp.generate(_prompt(9, seed=1),
                                             max_new_tokens=30,
                                             tenant="low")
            except BaseException as e:
                results["low"] = e

        t = threading.Thread(target=low, daemon=True)
        t.start()
        deadline = time.monotonic() + 20
        while gp._n_active() == 0 and time.monotonic() < deadline:
            time.sleep(0.005)
        assert gp._n_active() == 1
        out = gp.generate(_prompt(40, seed=2), max_new_tokens=4,
                          tenant="hi")
        assert len(out) == 4                 # the winner generated
        t.join(timeout=30.0)
        assert not t.is_alive()
        assert isinstance(results["low"], qos.PreemptedError), results
        assert gp._cache.alloc.in_use == 0
        gp.shutdown()
    finally:
        qos.global_tenants().configure({})


def test_prompt_that_can_never_fit_is_a_value_error():
    eng = _engine("paged")
    with GenerationPipeline(eng, slots=2, cache_pages=eng.pages_per_slot,
                            max_new_tokens=4) as gp:
        # needs 3 pages (prompt 40 → bucket 48), pool holds pages_per_slot
        assert eng.pages_per_slot == 3    # MAXLEN/PAGE
        out = gp.generate(_prompt(9), max_new_tokens=4)
        assert len(out) == 4
    with pytest.raises(ValueError):
        GenerationPipeline(eng, slots=1, cache_pages=1)


# ------------------------------------------------------- metrics/surfaces
def test_pages_and_spec_metrics_and_snapshot():
    eng = _engine("spec")
    with GenerationPipeline(eng, slots=2, max_new_tokens=8) as gp:
        ref = _engine("dense").generate(_prompt(6)[None], 8)[0]
        out = gp.generate(_prompt(6), max_new_tokens=8)
        assert np.array_equal(out, ref)    # spec pipeline byte-identical
        snap = gp.snapshot()
        assert snap["pages"]["total"] == 2 * eng.pages_per_slot
        assert snap["pages"]["in_use"] == 0
        assert snap["pages"]["page_tokens"] == PAGE
        assert snap["spec"]["enabled"] and snap["spec"]["spec_k"] == 3
        assert snap["spec"]["accept_ratio"] == 1.0   # identity draft
        assert snap["cache_bytes"] == 0 and snap["pool_bytes"] > 0
        reg = global_registry()
        assert reg.get("dl4j_decode_pages_capacity").value >= \
            2 * eng.pages_per_slot
        assert reg.get("dl4j_spec_accept_ratio").value == 1.0
        # the decode thread publishes the page gauges at its own step
        # boundary — give its final post-sweep publish a beat to land
        deadline = time.monotonic() + 5.0
        while (reg.get("dl4j_decode_pages_in_use").value != 0
               and time.monotonic() < deadline):
            time.sleep(0.01)
        assert reg.get("dl4j_decode_pages_in_use").value == 0
        # the cache-bytes gauge reports ACTUAL resident bytes (drained
        # pipelines contribute zero, not their worst-case pool)
        assert reg.get("dl4j_decode_cache_bytes").value == 0


def test_zero_steady_state_retraces_paged_and_spec():
    """After warm-up traffic, paged decode AND the propose/verify pair
    trigger zero new XLA traces under mixed concurrent load."""
    eng = _engine("spec")
    watch = compile_watch.global_compile_watch()
    with GenerationPipeline(eng, slots=3, max_new_tokens=6) as gp:
        gp.generate(_prompt(5), max_new_tokens=6)      # bucket 16
        gp.generate(_prompt(17), max_new_tokens=6)     # bucket 32
        fns = ("TransformerLM.prefill", "TransformerLM.decode_step",
               "TransformerLM.spec_verify", "DraftLM.spec_propose")
        before = {fn: watch.count_for(fn) for fn in fns}
        threads = [threading.Thread(
            target=gp.generate, args=(_prompt(3 + i),),
            kwargs={"max_new_tokens": 5}) for i in range(6)]
        for t in threads:
            t.start()
        for t in threads:
            t.join(timeout=60)
        after = {fn: watch.count_for(fn) for fn in fns}
    assert before == after, f"steady-state retraced: {before} -> {after}"


# ------------------------------------------------------------ spec decode
@pytest.mark.slow
def test_spec_greedy_byte_identical_with_truncated_draft():
    """A 1-layer truncated draft (imperfect proposals) still emits the
    EXACT plain-decode continuation under greedy — rejections correct
    to the target's argmax by construction. (The identity-draft byte-
    equality pin stays in tier-1 via the metrics/snapshot test; this
    compiles a second draft executable set, so it rides the slow lane —
    the PR-13 tier-1 budget discipline.)"""
    m, p = _mp()
    dm, _ = _model(n_layers=1)
    dp = {"tok_emb": p["tok_emb"], "pos_emb": p["pos_emb"],
          "ln_f": p["ln_f"], "blocks": [p["blocks"][0]]}
    draft = DecodeEngine(dm, dp, max_len=MAXLEN, page_tokens=0)
    eng = DecodeEngine(m, p, max_len=MAXLEN, page_tokens=PAGE,
                       draft=draft, spec_k=3)
    for n in (5, 16, 20):
        prompt = _prompt(n, seed=n)[None]
        assert np.array_equal(eng.generate(prompt, 12),
                              _engine("dense").generate(prompt, 12))
    assert 0.0 < eng.spec_accept_ratio() <= 1.0


@pytest.mark.slow
def test_spec_resample_matches_target_distribution():
    """Seeded accept/resample: over many seeded rounds the FIRST emitted
    token's empirical distribution matches the target sampler's
    distribution (the exactness theorem), despite the draft proposing
    from a different (truncated-model) distribution. (400 device
    rounds ⇒ slow lane; greedy exactness — the deterministic face of
    the same theorem — stays in tier-1.)"""
    m, p = _mp()
    dm, _ = _model(n_layers=1)
    dp = {"tok_emb": p["tok_emb"], "pos_emb": p["pos_emb"],
          "ln_f": p["ln_f"], "blocks": [p["blocks"][0]]}
    sampler = SamplerConfig(kind="topk", top_k=6, temperature=1.3)
    draft = DecodeEngine(dm, dp, max_len=MAXLEN, page_tokens=0,
                         sampler=SamplerConfig(kind="topk", top_k=4,
                                               temperature=0.9), seed=9)
    eng = DecodeEngine(m, p, max_len=MAXLEN, page_tokens=PAGE,
                       draft=draft, spec_k=2, sampler=sampler, seed=4)
    prompt = _prompt(9, seed=1)[None]
    _first, _l, kv, t = eng.prefill(prompt)
    # expected: the target's sampling distribution after the carry token
    carry = int(np.asarray(_first)[0])
    ref_state = eng.new_state(1)
    ref_state = eng.insert_slot(ref_state, kv, 0)
    eng.insert_draft_slot(ref_state, 0, prompt)
    logits, _pool = eng._verify_paged_jit(
        eng.params, ref_state.arrays, eng._tables(ref_state),
        np.asarray([[carry] * (eng.spec_k + 1)], np.int32),
        np.asarray([t], np.int32), 0)
    expected = _dist_probs(np.asarray(logits)[0, 0], sampler)
    counts = np.zeros(VOCAB)
    n_trials = 400
    for i in range(n_trials):
        st = eng.new_state(1)
        st = eng.insert_slot(st, kv, 0)
        eng.insert_draft_slot(st, 0, prompt)
        emitted = eng.spec_step(st, np.asarray([carry], np.int32),
                                np.asarray([t], np.int32), i, [0])[0]
        counts[emitted[0]] += 1
    emp = counts / n_trials
    # total-variation distance: loose bound for 400 seeded draws
    tv = 0.5 * np.abs(emp - expected).sum()
    assert tv < 0.12, f"resample distribution off: TV={tv:.3f}"
    # support check: nothing outside the target's top-k was ever emitted
    assert set(np.nonzero(counts)[0]) <= set(np.nonzero(expected)[0])


def test_spec_draft_validation():
    m, p = _mp()
    small_vocab = TransformerConfig(vocab_size=7, n_layers=1, n_heads=2,
                                    d_model=32, max_len=64)
    dm = TransformerLM(small_vocab)
    draft = DecodeEngine(dm, dm.init_params(jax.random.key(1)),
                         max_len=MAXLEN)
    with pytest.raises(ValueError):
        DecodeEngine(m, p, max_len=MAXLEN, draft=draft)   # vocab mismatch
    short = DecodeEngine(*_model(n_layers=1), max_len=16)
    with pytest.raises(ValueError):
        DecodeEngine(m, p, max_len=MAXLEN, draft=short)   # short reach
    good = DecodeEngine(*_model(n_layers=1), max_len=MAXLEN)
    with pytest.raises(ValueError):
        DecodeEngine(m, p, max_len=MAXLEN, draft=good, spec_k=0)


# ------------------------------------------------------------ chaos drill
def test_paged_spec_chaos_drill_exactly_once_pages_reclaimed():
    """generation.step faults (transient + crash + latency) against the
    paged+spec pipeline with a small pool, deadlines, and mixed lengths:
    every request resolves EXACTLY once (token array, typed outcome, or
    the injected fault), none hang, and every page returns to the pool."""
    m, p = _mp()
    draft = DecodeEngine(m, p, max_len=MAXLEN, page_tokens=0)
    eng = DecodeEngine(m, p, max_len=MAXLEN, page_tokens=PAGE,
                       draft=draft, spec_k=3)
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
                                cache_pages=7, max_queue_depth=8,
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
                "a generation request hung under paged+spec chaos"
            assert len(outcomes) == 12          # exactly once each
            assert not [o for o in outcomes if o[0] == "UNEXPECTED"], \
                outcomes
            assert any(k == "ok" for k, _ in outcomes)
            # every page reclaimed: nothing in flight, nothing leaked
            assert gp._cache.alloc.in_use == 0
            assert (gp._cache.tables == gp._cache.alloc.total).all()
        finally:
            gp.shutdown()
    injected = faults.snapshot()["injected"]
    assert any(k.startswith("generation.step") for k in injected), injected
