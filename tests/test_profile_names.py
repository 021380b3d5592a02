"""The program names its own work (PR 24): spans on the profiler's clock,
the decode loop's phases, named scopes through the model, and the benchmark's
readers of both (``perfbench/layer_metrics/_named.py``)."""
import contextlib
import glob
import os
import re
import subprocess
import sys
import threading
import time

import jax
import jax.numpy as jnp
import numpy as np
import optax
import pytest

from deeplearning4j_tpu.models.generation import DecodeEngine
from deeplearning4j_tpu.models.transformer import (TransformerConfig,
                                                   TransformerLM)
from deeplearning4j_tpu.observability import (global_registry,
                                              reset_global_registry, span)
from deeplearning4j_tpu.observability.tracing import (
    SpanRecord, reset_global_trace_sink)
from deeplearning4j_tpu.parallel.generation import (_LOOP_PHASES,
                                                    GenerationPipeline)
from perfbench import trace as ptrace
from perfbench.layer_metrics import _named

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
TRACES = os.path.join(ROOT, "perfbench", "tests")
VOCAB = 64


# ----------------------------------------------- (a) spans in the profile
def _profile_with_worker_span(log_dir):
    """A profile around a span opened on a worker thread, between two
    annotations of the main thread; returns the .xplane.pb's path."""
    def work():
        with span("worker_section", k=1):
            time.sleep(0.005)

    jax.profiler.start_trace(log_dir)
    try:
        with jax.profiler.TraceAnnotation("bracket_open"):
            pass
        t = threading.Thread(target=work)
        t.start()
        t.join(30.0)
        assert not t.is_alive()
        with jax.profiler.TraceAnnotation("bracket_close"):
            pass
    finally:
        jax.profiler.stop_trace()
    return glob.glob(os.path.join(log_dir, "plugins", "profile", "*",
                                  "*.xplane.pb"))[0]


def test_span_on_a_worker_thread_is_in_the_profiles_host_plane(tmp_path):
    sink = reset_global_trace_sink()
    nm = _named.Named(_profile_with_worker_span(str(tmp_path)))
    by = {n: (s, e, lid) for n, s, e, lid in nm.spans}
    assert {"worker_section", "bracket_open", "bracket_close"} <= set(by)
    s, e, lid = by["worker_section"]
    assert by["bracket_open"][0] <= s < e <= by["bracket_close"][1]
    assert e - s >= 0.005
    # on the line of the thread that opened it, not the main thread's
    assert lid != by["bracket_open"][2]
    # and still a span of the ring, attributes and all
    rec = [r for r in sink.spans() if r.name == "worker_section"]
    assert len(rec) == 1 and rec[0].attrs == {"k": 1}
    assert rec[0].dur_us == pytest.approx((e - s) * 1e6, abs=500)


def test_trace_kill_switch_keeps_spans_out_of_the_profile(tmp_path):
    code = (
        "import sys; sys.path.insert(0, %r); sys.path.insert(0, %r)\n"
        "import test_profile_names as t\n"
        "nm = t._named.Named(t._profile_with_worker_span(%r))\n"
        "names = {n for n, _s, _e, _l in nm.spans}\n"
        "assert 'bracket_open' in names, names\n"
        "assert 'worker_section' not in names, names\n"
        "import deeplearning4j_tpu.observability.tracing as tr\n"
        "assert tr._annotation is None   # never bound on the no-op path\n"
        "print('OK')\n" % (ROOT, os.path.dirname(__file__), str(tmp_path)))
    env = dict(os.environ, DL4J_TPU_TRACE="0")
    out = subprocess.run([sys.executable, "-c", code], env=env,
                         capture_output=True, text=True, timeout=120)
    assert out.returncode == 0 and "OK" in out.stdout, out.stderr[-2000:]


def test_tracing_binds_the_annotation_lazily_and_no_backend():
    code = ("import sys; sys.path.insert(0, %r)\n"
            "import deeplearning4j_tpu.observability.tracing as tr\n"
            "assert tr._annotation is None\n"
            "with tr.span('first'):\n"
            "    pass\n"
            "import jax\n"
            "from jax._src import xla_bridge\n"
            "assert tr._annotation is jax.profiler.TraceAnnotation\n"
            "assert not xla_bridge._backends, 'a span initialized a backend'\n"
            "print('OK')\n" % ROOT)
    out = subprocess.run([sys.executable, "-c", code], capture_output=True,
                         text=True, timeout=120)
    assert out.returncode == 0 and "OK" in out.stdout, out.stderr[-2000:]


# -------------------------------------------- (b) the decode loop's phases
_ENGINE = None


def _engine():
    global _ENGINE
    if _ENGINE is None:
        cfg = TransformerConfig(vocab_size=VOCAB, n_layers=2, n_heads=2,
                                d_model=32, max_len=64)
        m = TransformerLM(cfg)
        _ENGINE = DecodeEngine(m, m.init_params(jax.random.key(0)),
                               max_len=64)
    return _ENGINE


def _prompt(n, seed=0):
    return np.random.default_rng(seed).integers(
        0, VOCAB, (n,)).astype(np.int32)


def _counter(name, **labels):
    inst = global_registry().get(name)
    if inst is None:
        return 0.0
    return (inst.labels(**labels) if labels else inst).value


def _settle(quiet=0.15, limit=10.0):
    """Wait until the decode loop has booked its last iteration: a request
    resolves in the sweep, before that iteration's publish phase (a first
    step's cost-model lowering takes a while) and its counters."""
    t_end, last = time.time() + limit, None
    while time.time() < t_end:
        now = [_counter("dl4j_decode_loop_seconds_total", phase=ph)
               for ph in _LOOP_PHASES]
        if now == last and now[-1] > 0:
            return
        last = now
        time.sleep(quiet)


@pytest.fixture
def loop_run(monkeypatch):
    """Three staggered requests through a pipeline whose engine records
    the positions every decode call was given; yields (spans, positions
    per decode call, counters before and after). Four slots, so one stays
    free and every pass keeps the order dispatch, fetch, sweep: what a pass
    looks like while every slot is occupied is ``tests/test_decode_ahead.py``'s."""
    reset_global_registry()
    eng = _engine()
    seen = []
    real = eng.decode

    def decode(cache, tokens, positions, step):
        seen.append(np.asarray(positions).copy())
        return real(cache, tokens, positions, step)

    monkeypatch.setattr(eng, "decode", decode)
    with GenerationPipeline(eng, slots=4, max_new_tokens=40) as gp:
        gp.generate(_prompt(5), max_new_tokens=3)        # compiles
        _settle()
        sink = reset_global_trace_sink()
        seen.clear()
        before = {ph: _counter("dl4j_decode_loop_seconds_total", phase=ph)
                  for ph in _LOOP_PHASES}
        stall0 = _counter("dl4j_decode_prefill_stall_seconds_total")
        decoding = threading.Event()
        got = []

        def on_token(tok, _i):  # the first is decoding when the rest join
            got.append(tok)
            if len(got) == 2:
                decoding.set()
            return True

        first = threading.Thread(
            target=gp.generate, args=(_prompt(9, 1),),
            kwargs={"max_new_tokens": 40, "on_token": on_token})
        first.start()
        assert decoding.wait(60)
        rest = [threading.Thread(target=gp.generate,
                                 args=(_prompt(4 + i, 2 + i),),
                                 kwargs={"max_new_tokens": 6})
                for i in range(2)]
        for t in rest:
            t.start()
        for t in [first] + rest:
            t.join(timeout=120)
        _settle()               # the last iteration's span closes
        quiet = len([s for s in sink.spans() if s.name == "decode_iter"])
        time.sleep(0.3)         # six idle polls of 50 ms
        spans = sink.spans()
        after = {ph: _counter("dl4j_decode_loop_seconds_total", phase=ph)
                 for ph in _LOOP_PHASES}
        stall1 = _counter("dl4j_decode_prefill_stall_seconds_total")
    yield {"spans": spans, "positions": list(seen), "quiet_iters": quiet,
           "loop_seconds": {ph: after[ph] - before[ph]
                            for ph in _LOOP_PHASES},
           "stall_seconds": stall1 - stall0}
    GenerationPipeline.shutdown_all()


def test_decode_iter_children_nest_and_do_not_overlap(loop_run):
    spans = loop_run["spans"]
    iters = [s for s in spans if s.name == "decode_iter"]
    assert len(iters) >= 20
    kids = {}
    for s in spans:
        kids.setdefault(s.parent_id, []).append(s)
    for it in iters:
        assert it.parent_id is None and it.depth == 0
        mine = sorted(kids.get(it.span_id, []), key=lambda s: s.ts_us)
        names = [s.name for s in mine]
        assert names[0] == "loop_admit", names
        assert set(names) <= {"loop_admit", "loop_reclaim", "decode_step",
                              "loop_sweep", "loop_publish"}
        if "decode_step" in names:      # a pass that stepped has them all
            assert names == ["loop_admit", "loop_reclaim", "decode_step",
                             "loop_sweep", "loop_publish"]
        end = it.ts_us
        for s in mine:
            assert s.trace_id == it.trace_id and s.tid == it.tid
            assert s.ts_us >= end                       # no overlap
            end = s.ts_us + s.dur_us
        assert end <= it.ts_us + it.dur_us              # nested
    steps = [s for s in spans if s.name == "decode_step"]
    for st in steps:
        inner = sorted(kids.get(st.span_id, []), key=lambda s: s.ts_us)
        assert [s.name for s in inner] == ["decode_dispatch", "token_fetch"]
        assert inner[0].ts_us + inner[0].dur_us <= inner[1].ts_us
        assert inner[1].ts_us + inner[1].dur_us <= st.ts_us + st.dur_us
    # the joiners' engine call and insert are children of loop_admit, under
    # names of their own; ``prefill`` is the request's span alone
    admits = {s.span_id for s in spans if s.name == "loop_admit"}
    inner = [s for s in spans if s.name in ("prefill_dispatch",
                                            "prefill_insert")]
    assert len(inner) == 6 and all(s.parent_id in admits for s in inner)
    joined = sum(s.attrs["joined"] for s in spans if s.name == "loop_admit")
    requests = [s for s in spans if s.name == "prefill"]
    assert joined == len(requests) == 3
    assert all("phase" not in s.attrs for s in requests)
    sweeps = [s for s in spans if s.name == "loop_sweep"]
    assert sum(s.attrs["finished"] for s in sweeps) == 3
    assert sum(s.attrs["emitted"] for s in sweeps) == 40 + 6 + 6 - 3


def test_idle_pipeline_records_no_decode_iter(loop_run):
    iters = [s for s in loop_run["spans"] if s.name == "decode_iter"]
    assert len(iters) == loop_run["quiet_iters"]


def test_live_tokens_is_positions_plus_one_over_active_slots(loop_run):
    steps = sorted((s for s in loop_run["spans"] if s.name == "decode_step"),
                   key=lambda s: s.ts_us)
    seen = loop_run["positions"]
    assert len(steps) == len(seen) and len(steps) >= 20
    for st, pos in zip(steps, seen):
        # a free slot's position is kept at zero
        assert st.attrs["live_tokens"] == int(pos.sum()) + st.attrs["active"]
    assert steps[0].attrs["live_tokens"] == 9 + 1   # the first, alone


def test_stall_counter_is_the_stalled_prefill_spans(loop_run):
    stalled = [s for s in loop_run["spans"] if s.name == "prefill"
               and s.attrs["stalled_slots"] > 0]
    assert len(stalled) == 2
    assert loop_run["stall_seconds"] == pytest.approx(
        sum(s.dur_us for s in stalled) / 1e6, rel=1e-6)
    # and the phases' seconds are the iterations' time, to the clock reads
    iters = [s for s in loop_run["spans"] if s.name == "decode_iter"]
    booked = sum(loop_run["loop_seconds"].values())
    assert booked == pytest.approx(sum(s.dur_us for s in iters) / 1e6,
                                   rel=0.05)
    assert all(v > 0 for v in loop_run["loop_seconds"].values())


def test_speculative_round_has_the_same_children():
    """A speculative round dispatches and fetches twice (propose, verify):
    the engine opens the same two span names under ``decode_step``, and the
    round's seconds split into the dispatch and fetch phases."""
    reset_global_registry()
    cfg = TransformerConfig(vocab_size=VOCAB, n_layers=2, n_heads=2,
                            d_model=32, max_len=64)
    m = TransformerLM(cfg)
    p = m.init_params(jax.random.key(0))
    eng = DecodeEngine(m, p, max_len=64, page_tokens=16, spec_k=2,
                       draft=DecodeEngine(m, p, max_len=64, page_tokens=0))
    with GenerationPipeline(eng, slots=2, max_new_tokens=8) as gp:
        gp.generate(_prompt(5), max_new_tokens=3)        # compiles
        _settle()
        sink = reset_global_trace_sink()
        before = {ph: _counter("dl4j_decode_loop_seconds_total", phase=ph)
                  for ph in _LOOP_PHASES}
        fetch0 = eng.spec_fetch_s
        out = gp.generate(_prompt(6, 3), max_new_tokens=8)
        _settle()
        spans = sink.spans()
        after = {ph: _counter("dl4j_decode_loop_seconds_total", phase=ph)
                 for ph in _LOOP_PHASES}
    assert len(out) == 8
    steps = [s for s in spans if s.name == "decode_step"]
    assert steps and all(s.attrs["spec"] for s in steps)
    assert steps[0].attrs["live_tokens"] == 6 + 1
    for st in steps:
        inner = sorted((s for s in spans if s.parent_id == st.span_id),
                       key=lambda s: s.ts_us)
        assert [s.name for s in inner] == ["decode_dispatch", "token_fetch",
                                           "decode_dispatch", "token_fetch"]
        fetched = sum(s.dur_us for s in inner if s.name == "token_fetch")
        assert fetched <= st.dur_us
    assert after["dispatch"] > before["dispatch"]
    assert after["fetch"] > before["fetch"]
    # the fetch phase is the engine's own timing of its two waits
    assert after["fetch"] - before["fetch"] == pytest.approx(
        eng.spec_fetch_s - fetch0, rel=1e-6)
    # which brackets its two token_fetch spans, inside the round's span
    assert sum(s.dur_us for s in spans if s.name == "token_fetch") / 1e6 \
        <= eng.spec_fetch_s - fetch0 <= sum(s.dur_us for s in steps) / 1e6
    GenerationPipeline.shutdown_all()


# ------------------------------------------------- (c) scopes in the HLO
class _NoScope(contextlib.ContextDecorator):
    def __init__(self, name):
        pass

    def __enter__(self):
        return self

    def __exit__(self, *exc):
        return False


def _programs():
    """{name: (scopes it must name, () -> (jitted function, arguments))},
    each built when called so that it is traced under the caller's
    ``jax.named_scope``."""
    cfg = TransformerConfig(vocab_size=VOCAB, n_layers=2, n_heads=2,
                            d_model=32, max_len=64, dtype=jnp.bfloat16)
    block = {"cast_params", "embed", "ln", "attn_qkv", "attn_core",
             "attn_out", "mlp", "head"}

    def train():
        m = TransformerLM(cfg)
        p = m.init_params(jax.random.key(0))
        opt = optax.adamw(1e-3)
        toks = jnp.zeros((2, 16), jnp.int32)
        return m.make_train_step(opt), (p, opt.init(p), toks, toks)

    def engine():
        m = TransformerLM(cfg)
        return DecodeEngine(m, m.init_params(jax.random.key(0)), max_len=64,
                            page_tokens=16)

    def prefill():
        eng = engine()
        return eng._prefill_jit, (eng.params, jnp.zeros((1, 16), jnp.int32),
                                  jnp.asarray(3, jnp.int32),
                                  jnp.asarray(0, jnp.int32))

    def decode():
        eng = engine()
        st = eng.new_state(2)
        return eng._decode_paged_jit, (
            eng.params, st.arrays, eng._tables(st),
            jnp.zeros((2,), jnp.int32), jnp.zeros((2,), jnp.int32),
            jnp.asarray(0, jnp.int32))

    return {"train_step": (block | {"loss", "optimizer"}, train),
            "prefill": (block | {"kv_write"}, prefill),
            "decode_paged": (block | {"kv_write", "kv_gather"}, decode)}


@pytest.mark.parametrize("program", ["train_step", "prefill",
                                     "decode_paged"])
def test_compiled_program_names_every_scope(program, monkeypatch):
    want, build = _programs()[program]
    fn, args = build()
    lowered = fn.lower(*args)
    names = re.findall(r'op_name="([^"]*)"', lowered.compile().as_text())
    found = {_named.scope_of(n) for n in names}
    assert want <= found, sorted(want - found)
    assert found - {None} <= _named.SCOPES
    # the scopes are metadata: without them the same operations
    monkeypatch.setattr(jax, "named_scope", _NoScope)
    fn0, args0 = build()
    assert fn0.lower(*args0).as_text() == lowered.as_text()
    bare = re.findall(r'op_name="([^"]*)"',
                      fn0.lower(*args0).compile().as_text())
    assert {_named.scope_of(n) for n in bare} == {None}


def test_transformer_uses_the_vocabulary_and_nothing_else():
    path = os.path.join(ROOT, "deeplearning4j_tpu", "models",
                        "transformer.py")
    with open(path) as f:
        used = set(re.findall(r'named_scope\("([^"]*)"\)', f.read()))
    assert used == _named.SCOPES


def test_hybrid_and_the_expert_layer_use_the_vocabulary_and_inner_names():
    """``models/hybrid.py`` and ``parallel/moe.py`` name nothing outside the
    fixed vocabulary and the inner names the benchmark's four readers know
    (``_inner.INNER``: PR 27's; ``_nemotron.NAMES``: the state-space and
    grouped-query layers and the experts' latent pair; ``_longcat.NAMES``:
    the dense feed-forwards, the rotation and the identity experts' copy;
    ``_laguna.NAMES``: the two grouped-query kinds' projection, rotation and
    attend, the window kind's ring write and the output gate), and use every
    one of the inner names. The two grouped-query kinds share their code, so
    their ``<kind>_<what>`` names come from ``hybrid._scope``."""
    from deeplearning4j_tpu.models import hybrid
    from perfbench.layer_metrics import (_inner, _laguna, _longcat,
                                         _nemotron, _phi4flash)
    used = {hybrid._scope(kind, what) for kind in ("gqa", "swa")
            for what in hybrid._ATTN_SCOPES}
    # the query-only kind (PR 46) shares that code and never rotates
    used |= {hybrid._scope("xattn", what) for what in ("proj", "attend")}
    for rel in ("models/hybrid.py", "parallel/moe.py"):
        with open(os.path.join(ROOT, "deeplearning4j_tpu", rel)) as f:
            used |= set(re.findall(r'named_scope\("([^"]*)"\)', f.read()))
    # PR 46's reader: the query-only attention and the memory unit, and the
    # differential subtraction, nested in a kind's attend and read with it
    inner = _inner.INNER | _nemotron.NAMES | _longcat.NAMES | _laguna.NAMES \
        | _phi4flash.NAMES | _phi4flash.NESTED
    assert used <= _named.SCOPES | inner
    assert used >= inner
    assert _phi4flash.NAMES == {"xattn_proj", "xattn_attend", "gmu"}
    assert _phi4flash.NESTED == {"attn_diff"}
    assert _longcat.NAMES == {"ffn_dense", "mla_rope", "moe_zero"}
    assert _nemotron.NAMES == {
        "ssm_proj", "ssm_conv", "ssm_state", "ssm_out", "gqa_proj",
        "gqa_attend", "moe_latent"}
    assert _laguna.NAMES == {
        "gqa_proj", "gqa_rope", "gqa_attend", "swa_proj", "swa_rope",
        "swa_attend", "swa_write", "attn_gate"}


def test_laguna_decode_program_names_the_new_scopes_inside_the_vocabulary():
    """The compiled decode step of a model with both grouped-query kinds,
    rotated and gated: every operation under one of the new inner names also
    sits under the vocabulary's scope for that part of the block (the ring's
    write under ``kv_write``, so that ``kv_move`` counts it), and each
    resolves to its kind."""
    import json
    from perfbench import harness
    from perfbench.layer_metrics import _laguna
    mod = harness.load_module("models", "laguna.py")
    cfg = harness.load_json("configs", "laguna-xs2-33b-a3b-stage5.json")
    cfg.update(cfg["rehearsal"])
    model = mod.build_model(cfg)
    shapes = mod.weight_shapes(cfg)
    eng = DecodeEngine(model, shapes, max_len=cfg["n_positions"],
                       prefill_buckets=[16], page_tokens=8)
    cache = jax.eval_shape(lambda: model.new_paged_cache(4, 9, 8))
    i32 = lambda *s: jax.ShapeDtypeStruct(s, jnp.int32)     # noqa: E731
    text = eng._decode_paged_jit.lower(
        shapes, cache, i32(4, 16), i32(4), i32(4), i32()).compile().as_text()
    outer = {"gqa_proj": {"attn_qkv", "attn_out"}, "gqa_rope": {"attn_qkv"},
             "gqa_attend": {"attn_core"},
             "swa_proj": {"attn_qkv", "attn_out"}, "swa_rope": {"attn_qkv"},
             "swa_attend": {"attn_core"}, "swa_write": {"kv_write"},
             "attn_gate": {"attn_qkv", "attn_out"}}
    seen, kinds = {}, {}
    for name in re.findall(r'op_name="([^"]*)"', text):
        kind, inner = _laguna.names_of(name)
        if inner:
            seen.setdefault(inner, set()).add(_named.scope_of(name))
            kinds.setdefault(inner, set()).add(kind)
    assert {k: v for k, v in seen.items() if k != "attn_gate"} == {
        k: v for k, v in outer.items() if k != "attn_gate"}, json.dumps(
        {k: sorted(map(str, v)) for k, v in seen.items()})
    # the compiler may fuse a gate's few operations into its neighbours
    assert seen.get("attn_gate", set()) <= outer["attn_gate"]
    assert all(kinds[n] == {n[:3]} for n in outer if n != "attn_gate")
    assert kinds.get("attn_gate", set()) <= {"gqa", "swa"}
    found = {_named.scope_of(n)
             for n in re.findall(r'op_name="([^"]*)"', text)}
    assert {"kv_write", "kv_gather", "mlp", "head", "embed", "ln"} <= found
    assert found - {None} <= _named.SCOPES


def test_hybrid_decode_program_names_the_new_scopes_inside_the_vocabulary():
    """The compiled decode step of a Mamba-2 / grouped-query / latent-expert
    model: every operation under one of the new inner names also sits under
    the vocabulary's scope for that part of the block, so the accepted
    readers (``kv_move``, ``unscoped``) and the new ones read one program."""
    import json
    from perfbench import harness
    from perfbench.layer_metrics import _nemotron
    mod = harness.load_module("models", "nemotron_h.py")
    cfg = harness.load_json("configs",
                            "nemotron-3-super-120b-a12b-ep4share.json")
    cfg.update(cfg["rehearsal"])
    model = mod.build_model(cfg)
    shapes = mod.weight_shapes(cfg)
    eng = DecodeEngine(model, shapes, max_len=cfg["n_positions"],
                       prefill_buckets=[16], page_tokens=8)
    cache = jax.eval_shape(lambda: model.new_paged_cache(4, 9, 8))
    i32 = lambda *s: jax.ShapeDtypeStruct(s, jnp.int32)     # noqa: E731
    text = eng._decode_paged_jit.lower(
        shapes, cache, i32(4, 16), i32(4), i32(4), i32()).compile().as_text()
    outer = {"ssm_proj": {"attn_qkv"}, "ssm_conv": {"attn_qkv"},
             "ssm_state": {"attn_core"}, "ssm_out": {"attn_out"},
             "gqa_proj": {"attn_qkv", "attn_out"},
             "gqa_attend": {"attn_core"}, "moe_latent": {"mlp"}}
    seen = {}
    for name in re.findall(r'op_name="([^"]*)"', text):
        inner = _nemotron.inner_of(name)
        if inner:
            seen.setdefault(inner, set()).add(_named.scope_of(name))
    assert seen == outer, json.dumps({k: sorted(map(str, v))
                                      for k, v in seen.items()})
    found = {_named.scope_of(n)
             for n in re.findall(r'op_name="([^"]*)"', text)}
    assert {"kv_write", "kv_gather", "mlp", "head", "embed", "ln"} <= found
    assert found - {None} <= _named.SCOPES


def test_hybrid_decode_step_for_the_tpu_has_the_kernel_under_moe_experts(
        monkeypatch):
    """The decode step of the same model lowered for the TPU, the experts
    widened to whole lanes and the process's backend patched (what the
    program asks before it takes a kernel): every expert layer calls the ONE
    jitted ``_grouped_ffn``, which holds the one Pallas custom call, on a
    path ``.../mlp/moe_experts/jit(_grouped_ffn)`` - the compiled call's
    ``op_name`` is that path + ``/pallas_call`` (compiled for a described
    v5e in tests/test_grouped_ffn.py), so the accepted readers find the
    kernel's seconds under ``mlp`` and ``moe_experts`` with no reading by
    name, and it is no longer ``unscoped``; no ``ragged_dot`` is left."""
    from perfbench import harness
    from perfbench.layer_metrics import _inner
    mod = harness.load_module("models", "nemotron_h.py")
    cfg = harness.load_json("configs",
                            "nemotron-3-super-120b-a12b-ep4share.json")
    cfg.update(cfg["rehearsal"])
    cfg.update(moe_latent_size=128, moe_intermediate_size=128)
    model = mod.build_model(cfg)
    shapes = mod.weight_shapes(cfg)
    eng = DecodeEngine(model, shapes, max_len=cfg["n_positions"],
                       prefill_buckets=[16], page_tokens=8)
    cache = jax.eval_shape(lambda: model.new_paged_cache(4, 9, 8))
    i32 = lambda *s: jax.ShapeDtypeStruct(s, jnp.int32)     # noqa: E731
    monkeypatch.setattr(jax, "default_backend", lambda: "tpu")
    text = eng._decode_paged_jit.trace(
        shapes, cache, i32(4, 16), i32(4), i32(4), i32()).lower(
        lowering_platforms=("tpu",)).as_text(debug_info=True)
    assert '"chlo.ragged_dot"(' not in text
    assert text.count("custom_call @tpu_custom_call") == 1
    body = text[text.index("func.func private @_grouped_ffn("):]
    assert "custom_call @tpu_custom_call" in body[:body.index("\n  }")]
    calls = [line for line in text.splitlines()
             if "call @_grouped_ffn(" in line]
    assert len(calls) == len(model.moe_layers) == 5
    for call in calls:
        loc = re.search(r"loc\((#loc\d+)\)\s*$", call).group(1)
        path = re.search(rf'^{loc} = loc\("([^"]*)"', text, re.M).group(1)
        assert path.endswith("/mlp/moe_experts/jit(_grouped_ffn)")
        op_name = path + "/pallas_call"
        assert _named.scope_of(op_name) == "mlp"
        assert _inner.inner_of(op_name) == "moe_experts"


def test_laguna_decode_step_for_the_tpu_has_the_walk_under_swa_attend(
        monkeypatch):
    """The sixth family's decode step lowered for the TPU with heads of 128
    (what the kernel reads in whole tiles) and the process's backend
    patched: each of the three window layers calls the ONE jitted
    ``_paged_grouped_attention``, which holds the one Pallas custom call, on
    a path ``.../attn_core/swa_attend/jit(_paged_grouped_attention)`` - the
    compiled call's ``op_name`` is that path + ``/pallas_call`` (compiled
    for a described v5e in tests/test_grouped_ffn.py), so
    ``_laguna.names_of`` books the kernel's seconds to kind ``swa`` under
    ``swa_attend`` and ``attn_core`` with no reading by name. The two full
    layers keep the gathered window at this size."""
    from perfbench import harness
    from perfbench.layer_metrics import _laguna
    mod = harness.load_module("models", "laguna.py")
    cfg = harness.load_json("configs", "laguna-xs2-33b-a3b-stage5.json")
    cfg.update(cfg["rehearsal"])
    cfg.update(head_dim=128)
    model = mod.build_model(cfg)
    shapes = mod.weight_shapes(cfg)
    eng = DecodeEngine(model, shapes, max_len=cfg["n_positions"],
                       prefill_buckets=[16], page_tokens=8)
    cache = jax.eval_shape(lambda: model.new_paged_cache(4, 9, 8))
    i32 = lambda *s: jax.ShapeDtypeStruct(s, jnp.int32)     # noqa: E731
    monkeypatch.setattr(jax, "default_backend", lambda: "tpu")
    text = eng._decode_paged_jit.trace(
        shapes, cache, i32(4, 16), i32(4), i32(4), i32()).lower(
        lowering_platforms=("tpu",)).as_text(debug_info=True)
    assert model.attention_backend["swa"][0] == "paged-grouped"
    assert model.attention_backend["gqa"][0] == "gather"
    body = text[text.index("func.func private @_paged_grouped_attention("):]
    assert "custom_call @tpu_custom_call" in body[:body.index("\n  }")]
    calls = [line for line in text.splitlines()
             if "call @_paged_grouped_attention(" in line]
    assert len(calls) == 3
    for call in calls:
        loc = re.search(r"loc\((#loc\d+)\)\s*$", call).group(1)
        path = re.search(rf'^{loc} = loc\("([^"]*)"', text, re.M).group(1)
        assert path.endswith(
            "/attn_core/swa_attend/jit(_paged_grouped_attention)"), path
        op_name = path + "/pallas_call"
        assert _named.scope_of(op_name) == "attn_core"
        assert _laguna.names_of(op_name) == ("swa", "swa_attend")


# ------------------------------------------------------ (d) the readers
@pytest.mark.parametrize("op_name, scope", [
    ("jit(step)/jvp(ln)/mul", "ln"),
    ("jit(step)/transpose(jvp(mlp))/dot_general", "mlp"),
    ("jit(step)/jvp(attn_core)/bhqk,bkhd->bqhd/dot_general", "attn_core"),
    ("jit(step)/optimizer/add", "optimizer"),
    ("jit(_decode_paged)/kv_gather/jit(_take)/gather", "kv_gather"),
    ("jit(step)/jvp(embed)/jit(_take)/jit(_where)/select_n:", "embed"),
    ("jit(step)/vmap(checkpoint(kv_write))/dynamic_update_slice",
     "kv_write"),
    ("jit(step)/dot_general:", None),
    ("jit(loss)/jvp()/add", None),       # a program called loss: no scope
    ("jit(step)/jvp(mlp_extra)/mul", None),
    ("", None), (None, None)])
def test_scope_of_an_op_name(op_name, scope):
    assert _named.scope_of(op_name) == scope


def _meta_by_op(path):
    """{operation's own name: its event metadata's stats} of the first TPU
    plane."""
    plane = [p for p in _named.read_planes(path)
             if p.name.startswith("/device:TPU:")][0]
    return {ptrace.op_name(name): stats
            for name, stats in plane.meta.values() if " = " in name}


def test_small_trace_fusion_reads_its_op_name():
    meta = _meta_by_op(os.path.join(TRACES, "small.xplane.pb"))
    assert meta["fusion.1"]["tf_op"].rstrip(":") == "jit(step)/dot_general"
    assert "tf_op" not in meta["copy-start"]     # the compiler's own
    nm = _named.Named(os.path.join(TRACES, "small.xplane.pb"))
    assert len(nm.ops) == 1 and len(nm.ops[0]) == 16
    assert {op[0] for op in nm.ops[0]} == {"jit_step"}
    assert nm.by_scope(r"^jit_step") == {None: pytest.approx(
        sum(e - s for _p, _sc, _o, s, e in nm.ops[0]))}
    assert nm.by_scope(r"^jit_nothing") is None
    # the wire reader and ProfileData agree on every event's times
    ref = ptrace.Trace.from_file(os.path.join(TRACES, "small.xplane.pb"))
    for (_p, _sc, _o, s, e), (_n, rs, re_) in zip(nm.ops[0],
                                                  ref.devices[0].ops):
        assert s == pytest.approx(rs, abs=1e-9)
        assert e == pytest.approx(re_, abs=2e-9)   # ProfileData: whole ns
    marks = [sp for sp in nm.spans if sp[0] == "perfbench_mark"]
    assert [(n, pytest.approx(s), pytest.approx(e))
            for n, s, e, _l in marks] == ref.host_events


def test_four_chip_trace_has_a_plane_a_chip():
    nm = _named.Named(os.path.join(TRACES, "small4.xplane.pb"))
    assert len(nm.ops) == 4 and all(len(o) == 20 for o in nm.ops)


@pytest.fixture(scope="module")
def scoped():
    path = os.path.join(TRACES, "scoped.xplane.pb")
    return path, _named.Named(path)


def test_scoped_trace_scopes_of_fusions_and_an_unscoped_copy(scoped):
    path, nm = scoped
    meta = _meta_by_op(path)
    fusions = {n: st["tf_op"] for n, st in meta.items()
               if n.startswith("fusion")}
    assert len(fusions) >= 2
    scopes = {n: _named.scope_of(t) for n, t in fusions.items()}
    assert set(scopes.values()) == {"mlp", "head"}
    assert all(t.startswith("jit(step)/") for t in fusions.values())
    copies = [n for n in meta if n.startswith("copy")]
    assert copies and all("tf_op" not in meta[n] for n in copies)
    acc = nm.by_scope(r"^jit_step")
    assert set(acc) == {"mlp", "head", None}
    assert all(v > 0 for v in acc.values())
    ctx = {"_named": nm}
    share = _named.scope_share_pct(ctx, r"^jit_step", ("head",))
    assert share == pytest.approx(100 * acc["head"] / sum(acc.values()))
    assert _named.unscoped_pct(ctx, r"^jit_step") == pytest.approx(
        100 * acc[None] / sum(acc.values()))
    assert _named.scope_share_pct(ctx, r"^jit_nothing", ("head",)) is None


def test_scoped_trace_spans_nest_on_the_host_plane(scoped):
    _path, nm = scoped
    iters = nm.intervals("decode_iter")
    assert len(iters) == 4
    lines = {lid for n, _s, _e, lid in nm.spans if n == "decode_iter"}
    assert len(lines) == 1
    for name in ("decode_step", "decode_dispatch", "token_fetch",
                 "loop_sweep"):
        inner = nm.intervals(name)
        assert len(inner) == 4
        for (s, e), (ls, le) in zip(inner, iters):
            assert ls <= s < e <= le
    for (ds, de), (fs, fe), (ss, se) in zip(
            nm.intervals("decode_dispatch"), nm.intervals("token_fetch"),
            nm.intervals("loop_sweep")):
        assert de <= fs and fe <= ss
    tr = ptrace.Trace.from_file(os.path.join(TRACES, "scoped.xplane.pb"))
    lo, hi = tr.span()
    idle = ptrace.gaps(tr.devices[0].busy(lo, hi), lo, hi)
    split = dict(_named.idle_by_phase(idle, nm))
    assert sum(split.values()) == pytest.approx(ptrace.total(idle))
    # between two programs the host swept and slept outside the iteration
    assert split["loop_sweep"] > 2e-3 and split["outside decode_iter"] > 4e-3
    assert split["decode_step"] < 1e-3 and split["loop_admit"] == 0


def test_device_plane_runs_behind_the_host_plane(scoped):
    """What the recorded traces show of the profiler's two clocks: a program
    is on the device plane 1.2-1.3 ms before the runtime's host event that
    enqueues it, in both traces (two machines, two days). The readers move
    device times later by that lag before setting them against spans."""
    for name in ("small.xplane.pb", "scoped.xplane.pb"):
        path = os.path.join(TRACES, name)
        nm = _named.Named(path)
        starts = [s for _n, s, _e in
                  ptrace.Trace.from_file(path).devices[0].modules]
        assert len(nm.enqueues) == len(starts) == 4
        for m, d in zip(starts, nm.enqueues):
            assert 1.15e-3 < d - m < 1.35e-3        # enqueued AFTER it ran
        lag = _named.device_clock_lag(starts, nm.enqueues)
        assert 1.15e-3 < lag < 1.35e-3
    _path, nm = scoped
    # moved by the lag, every program lies inside its iteration's fetch
    for (fs, fe), m in zip(nm.intervals("token_fetch"), starts):
        assert fs < m + lag < fe
    assert _named.device_clock_lag(starts, []) == 0.0
    assert _named.device_clock_lag([0.0, 1.0], [0.5, 1.002, 7.0]) \
        == pytest.approx(0.002)


class _FakeNamed:
    def __init__(self, spans):
        self.spans = spans

    def intervals(self, name):
        return [(s, e) for n, s, e, _l in self.spans if n == name]


def test_idle_by_phase_on_made_up_intervals():
    nm = _FakeNamed([("decode_iter", 0.0, 10.0, 1),
                     ("loop_admit", 0.0, 2.0, 1),
                     ("prefill_dispatch", 0.5, 1.5, 1),
                     ("decode_step", 3.0, 8.0, 1),
                     ("decode_dispatch", 3.0, 4.0, 1),
                     ("token_fetch", 4.0, 7.5, 1),
                     ("loop_sweep", 8.0, 9.0, 1)])
    idle = [(0.25, 0.75), (1.75, 3.5), (7.0, 8.5), (9.5, 12.0)]
    split = dict(_named.idle_by_phase(idle, nm))
    assert split == {
        "token_fetch": 0.5, "decode_dispatch": 0.5,
        "prefill_dispatch": 0.25, "prefill_insert": 0.0,
        "decode_step": 0.5,             # 7.5 to 8: the step's own time
        "loop_admit": 0.5,              # 0.25-0.5 and 1.75-2
        "loop_reclaim": 0.0, "loop_sweep": 0.5, "loop_publish": 0.0,
        "decode_iter (no phase)": 1.5,  # 2-3 and 9.5-10
        "outside decode_iter": 2.0}
    assert sum(split.values()) == pytest.approx(ptrace.total(idle))


def _rec(name, ts, dur, trace_id="t", **attrs):
    return SpanRecord(name, ts, dur, 1, 0, attrs or None, trace_id=trace_id,
                      span_id=name + str(ts))


class _Trace:
    clock_offset = 1000.0
    devices = []


def test_span_readers_on_made_up_spans():
    spans = [_rec("decode_iter", 0, 120e3, "a"),
             _rec("token_fetch", 10e3, 100e3, "a"),
             _rec("decode_iter", 200e3, 130e3, "b"),
             _rec("token_fetch", 210e3, 50e3, "b"),
             _rec("token_fetch", 270e3, 50e3, "b"),      # a spec round's two
             _rec("decode_iter", 400e3, 8e3, "c"),       # a pass with no step
             _rec("prefill", 0, 30e3, "r1", stalled_slots=0),
             _rec("prefill", 50e3, 20e3, "r2", stalled_slots=3),
             _rec("prefill", 90e3, 40e3, "r3", stalled_slots=1),
             _rec("decode_step", 1000.5e6, 1e3, "a", live_tokens=100),
             _rec("decode_step", 1001.5e6, 1e3, "b", live_tokens=300),
             _rec("decode_step", 1003.5e6, 1e3, "c", live_tokens=900)]
    ctx = {"spans": spans, "window": (0.0, 2.0), "trace": _Trace(),
           "trace_span": (0.0, 2.0), "live_tokens_mean": 210.0}
    assert _named.loop_host_ms_p50(ctx) == pytest.approx(20.0)
    assert _named.prefill_stall_pct(ctx) == pytest.approx(3.0)
    assert _named.live_tokens_mean(ctx) == pytest.approx(200.0)
    # a program older than these spans and attributes: nothing to read
    old = [_rec("decode_step", 1000.5e6, 1e3, "a", active=2),
           _rec("prefill", 0, 30e3, "r1", slot=0)]
    ctx = dict(ctx, spans=old)
    assert _named.loop_host_ms_p50(ctx) is None
    assert _named.prefill_stall_pct(ctx) is None
    assert _named.live_tokens_mean(ctx) is None
    assert _named.idle_named_pct(dict(ctx, _named=None)) is None
    assert _named.kv_move_pct(dict(ctx, _named=None)) is None
