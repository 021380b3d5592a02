"""HybridLM (``models/hybrid.py``), the routed expert layer
(``parallel/moe.py::routed_experts_ffn``) and the decode engine's two kinds
of cache, against the plain reference ``perfbench/reference/kimi_linear.py``
(float32, token-by-token KDA, expanded MLA, a loop over held experts) at the
configuration's ``rehearsal`` sizes: all four layer kinds, 16 experts of
which 8 are held, 4 a token, seeded weights.

Tolerances, each with its reason. Program and reference both compute in
float32 here (the configuration's dtypes are overridden), so what is left is
the order of the additions: the chunked scan against the token-by-token
recurrence, the absorbed against the expanded attention, a grouped product
against a loop over experts. Logits are of order 0.15 and the gaps read
3e-7; ``TOL`` = 2e-5 leaves room for another CPU's vector width and is a
hundred times under the 2e-3 that bfloat16 projections give at these sizes.
"""
import json
import os
import sys

import jax
import jax.numpy as jnp
import numpy as np
import pytest

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
if ROOT not in sys.path:
    sys.path.insert(0, ROOT)

from perfbench import harness  # noqa: E402

from deeplearning4j_tpu.models import hybrid  # noqa: E402
from deeplearning4j_tpu.models.generation import (  # noqa: E402
    CacheFeatureUnsupported, DecodeEngine)
from deeplearning4j_tpu.models.transformer import (  # noqa: E402
    TransformerConfig, TransformerLM, pack_kv_pages)
from deeplearning4j_tpu.observability.registry import (  # noqa: E402
    global_registry)
from deeplearning4j_tpu.observability.tracing import (  # noqa: E402
    reset_global_trace_sink)
from deeplearning4j_tpu.parallel.generation import (  # noqa: E402
    GenerationPipeline)
from deeplearning4j_tpu.parallel.moe import (  # noqa: E402
    RoutedExpertsConfig, routed_experts_ffn)

TOL = 2e-5
KM = harness.load_module("models", "kimi_linear.py")
REF = harness.load_module("reference", "kimi_linear.py")


def _cfg(**over):
    with open(os.path.join(ROOT, "perfbench", "configs",
                           "kimi-linear-48b-a3b-ep2share.json")) as f:
        cfg = json.load(f)
    cfg.update(cfg["rehearsal"])
    cfg.update(compute_dtype="float32", param_dtype="float32")
    cfg.update(over)
    return cfg


@pytest.fixture(scope="module")
def family():
    cfg = _cfg()
    return cfg, KM.build_model(cfg), KM.make_weights(cfg, 3)


def _engine(family, **kw):
    cfg, model, params = family
    return DecodeEngine(model, params, max_len=cfg["n_positions"],
                        prefill_buckets=[16, 32, 64], page_tokens=8, **kw)


def test_layer_description_keeps_all_four_kinds(family):
    cfg, model, _ = family
    assert [(s.mixer, s.ffn) for s in model.config.layers] == [
        ("kda", "dense"), ("kda", "moe"), ("kda", "moe"), ("mla", "moe"),
        ("kda", "moe")]
    assert model.config.experts.held == (0, 8)
    assert model.config.experts.router_width == 16


def test_full_forward_matches_reference(family):
    cfg, model, params = family
    toks = jax.random.randint(jax.random.key(1), (2, 45), 0,
                              cfg["vocab_size"])
    got = jax.jit(model.apply)(params, toks)
    want = REF.logits(params, toks, cfg)
    assert float(jnp.max(jnp.abs(got - want))) < TOL


def test_prefill_then_decode_through_the_engine_matches_reference(family):
    """Two slots, prompts of 21 and 37 tokens (buckets 32 and 64, both
    padded), joined at steps 0 and 5; 24 and more decode steps each through
    the engine's cache; every step's LOGITS against the reference's full
    forward over prompt + served tokens."""
    cfg, _model, params = family
    eng = _engine(family)
    slots = 4
    state = eng.new_state(slots)
    rng = np.random.default_rng(0)
    prompts = {1: rng.integers(0, cfg["vocab_size"], 21),
               3: rng.integers(0, cfg["vocab_size"], 37)}
    join = {1: 0, 3: 5}
    seqs = {s: list(p) for s, p in prompts.items()}
    got = {s: [] for s in prompts}
    tokens = np.zeros(slots, np.int32)
    positions = np.zeros(slots, np.int32)
    active = []
    for step in range(30):
        for s, at in join.items():
            if at == step:
                first, lg, kv, t = eng.prefill(prompts[s][None], step=step)
                state = eng.insert_slot(state, kv, s)
                got[s].append(np.asarray(lg)[0, 0])
                tokens[s], positions[s] = int(np.asarray(first)[0]), t
                seqs[s].append(int(tokens[s]))
                active.append(s)
        nxt, lg, state = eng.decode(state, tokens, positions, step)
        nxt, lg = np.asarray(nxt), np.asarray(lg)
        counts = eng.step_counts(nxt, slots)
        # a free slot routes to no expert: 4 expert layers x 4 a token
        assert 0 < counts["pairs_held"] <= 16 * len(active)
        assert 0 < counts["experts_touched"] <= counts["pairs_held"]
        assert counts["pairs_routed"] == 16 * len(active)
        for s in active:
            got[s].append(lg[s])
            tokens[s] = nxt[s]
            positions[s] += 1
            seqs[s].append(int(nxt[s]))
    for s, prompt in prompts.items():
        full = np.asarray(seqs[s][:-1], np.int32)
        want = np.asarray(REF.logits(params, full[None], cfg))[0]
        mine = np.stack(got[s])
        assert mine.shape[0] >= 25
        assert np.abs(mine - want[len(prompt) - 1:]).max() < TOL


def test_warm_and_the_convenience_loop(family):
    """``warm`` compiles every program against a throw-away state, and
    ``generate`` (a batch of two prompts, one row inserted a slot) returns the
    reference's logits step by step."""
    cfg, _model, params = family
    eng = _engine(family)
    assert eng.warm(3) == [16, 32, 64]
    prompts = np.random.default_rng(1).integers(0, cfg["vocab_size"],
                                                (2, 11))
    toks, steps = eng.generate(prompts, 6, return_logits=True)
    assert toks.shape == (2, 6) and len(steps) == 6
    full = np.concatenate([prompts, toks[:, :-1]], axis=1)
    want = np.asarray(REF.logits(params, full, cfg))[:, 10:]
    assert np.abs(np.stack(steps, axis=1) - want).max() < TOL


@pytest.mark.parametrize("decay", [0.05, 6.0])
def test_chunked_kda_is_the_recurrence(decay):
    """77 rows (no multiple of the chunk of 32 nor of the 16-row blocks,
    padded with identity rows) from a non-zero state; ``decay`` 6 makes a
    channel's running log decay pass -400 inside one chunk, where a form that
    divides by the cumulative decay overflows float32."""
    B, T, H, K = 2, 77, 2, 16
    ks = jax.random.split(jax.random.key(4), 6)
    q, k = (jax.random.normal(ks[i], (B, T, H, K)) for i in (0, 1))
    k = k / jnp.linalg.norm(k, axis=-1, keepdims=True)
    v = jax.random.normal(ks[2], (B, T, H, K))
    log_a = -decay * jax.random.uniform(ks[3], (B, T, H, K), minval=0.1)
    beta = jax.nn.sigmoid(jax.random.normal(ks[4], (B, T, H)))
    s0 = jax.random.normal(ks[5], (B, H, K, K))

    def recur(s, row):
        s, o = hybrid.kda_step(s, *row)
        return s, o

    s_want, o_want = jax.lax.scan(
        recur, s0, tuple(a.swapaxes(0, 1) for a in (q, k, v, log_a, beta)))
    pad = [(0, 0), (0, -T % 32)]
    padded = [jnp.pad(a, pad + [(0, 0)] * (a.ndim - 2))
              for a in (q, k, v, log_a, beta)]
    o, s = jax.jit(hybrid.kda_chunked, static_argnums=6)(*padded, s0, 32)
    assert np.isfinite(np.asarray(o)).all()
    assert float(jnp.max(jnp.abs(o[:, :T] - o_want.swapaxes(0, 1)))) < 1e-4
    assert float(jnp.max(jnp.abs(s - s_want))) < 1e-4


def test_absorbed_mla_is_expanded_mla(family):
    """Layer 4's mixer alone: the expanded form over 19 rows against the
    absorbed form fed the same rows one at a time through a paged pool."""
    cfg, model, params = family
    p = params["blocks"][3]["mixer"]
    T, P = 19, 8
    h = jax.random.normal(jax.random.key(7), (1, T, cfg["hidden_size"]))
    want, rows = model._mla_full(p, h)
    pool = jnp.zeros((5, P, model.config.latent_row))
    tables = jnp.asarray([[2, 0, 3, 4]], jnp.int32)     # page 4 = trash
    for t in range(T):
        y, pool = model._mla_decode(p, h[:, t], pool, tables,
                                    jnp.asarray([t], jnp.int32), P)
        assert float(jnp.max(jnp.abs(y - want[:, t]))) < TOL
    got_rows = pool[tables[0]].reshape(-1, pool.shape[-1])[:T]
    assert float(jnp.max(jnp.abs(got_rows - rows[0]))) < TOL


def _expert_layer(cfg, key):
    return KM._block(key, cfg, "kda", "moe")["ffn"]


def test_the_shares_add_up(family):
    """The routed parts that ``held = (0, 8)`` and ``held = (8, 8)`` give for
    the 16 experts, plus the shared expert counted once, equal the uncut
    layer of the reference (all 16 held)."""
    cfg = _cfg(num_experts=16)
    full = _expert_layer(cfg, jax.random.key(11))
    x = jax.random.normal(jax.random.key(12), (53, cfg["hidden_size"]))
    want = REF._moe(x, full, cfg, False)
    shared = REF._swiglu(x, full["shared"], False)
    total = shared
    pairs = 0
    for first in (0, 8):
        part = dict(full, w_gu=full["w_gu"][first:first + 8],
                    w_down=full["w_down"][first:first + 8])
        y, stats = routed_experts_ffn(
            part, x, RoutedExpertsConfig(16, 4, (first, 8),
                                         cfg["routed_scaling_factor"]))
        total = total + (y - shared)
        pairs += int(stats[1])
        # the reference, given the same share, agrees with the program
        share = REF._moe(x, part, _cfg(num_experts=8,
                                       experts_held_first=first), False)
        assert float(jnp.max(jnp.abs(y - share))) < TOL
    assert pairs == 53 * 4                  # every pair fell on one share
    assert float(jnp.max(jnp.abs(total - want))) < TOL


def test_no_token_is_dropped_under_a_skewed_router(family):
    """A selection bias that sends every token to expert 5 first: no
    capacity, so all 64 pairs are computed and the result is the
    reference's."""
    cfg = _cfg()
    p = _expert_layer(cfg, jax.random.key(13))
    p["b_select"] = p["b_select"].at[5].set(50.0)
    x = jax.random.normal(jax.random.key(14), (64, cfg["hidden_size"]))
    ec = RoutedExpertsConfig(16, 4, (0, 8), cfg["routed_scaling_factor"])
    y, stats = jax.jit(lambda p, x: routed_experts_ffn(p, x, ec))(p, x)
    s = jax.nn.sigmoid(x @ p["w_router"])
    _, idx = jax.lax.top_k(s + p["b_select"], 4)
    assert bool(jnp.all(jnp.any(idx == 5, axis=-1)))
    assert int(stats[1]) == int(jnp.sum(idx < 8)) >= 64
    assert float(jnp.max(jnp.abs(y - REF._moe(x, p, cfg, False)))) < TOL
    # a masked row routes nowhere
    mask = jnp.arange(64) < 10
    _, st = routed_experts_ffn(p, x, ec, mask)
    assert int(st[1]) == int(jnp.sum(idx[:10] < 8)) and int(st[2]) == 40


@pytest.mark.parametrize("kwargs, what", [
    ({"kv_quant": True}, "int8 page pool"),
    ({"draft": "a draft"}, "speculative"),
    ({"page_tokens": 0}, "dense"),
])
def test_what_this_cache_does_not_do_is_refused_typed(family, kwargs, what):
    cfg, model, params = family
    if "draft" in kwargs:
        tc = TransformerConfig(vocab_size=cfg["vocab_size"], n_layers=0,
                               n_heads=2, d_model=16, max_len=128)
        small = TransformerLM(tc)
        kwargs = {"draft": DecodeEngine(
            small, small.init_params(jax.random.key(0)), max_len=128)}
    kwargs.setdefault("page_tokens", 8)
    with pytest.raises(CacheFeatureUnsupported, match=what):
        DecodeEngine(model, params, max_len=128, **kwargs)
    assert issubclass(CacheFeatureUnsupported, ValueError)


def test_max_len_message_names_no_position_table(family):
    cfg, model, params = family
    with pytest.raises(ValueError) as e:
        DecodeEngine(model, params, max_len=cfg["n_positions"] + 1)
    assert "pos_emb" not in str(e.value)
    tc = TransformerConfig(vocab_size=64, n_layers=1, n_heads=2, d_model=16,
                           max_len=32)
    gpt = TransformerLM(tc)
    with pytest.raises(ValueError, match="pos_emb"):
        DecodeEngine(gpt, gpt.init_params(jax.random.key(0)), max_len=33)


def test_bytes_count_pages_and_slot_state(family):
    cfg, model, _ = family
    eng = _engine(family)
    state = eng.new_state(4, pages=20)
    c = model.config
    page = 8 * c.latent_row * 4                     # one MLA layer, float32
    slot = 4 * (2 * 16 * 16 * 4 + 3 * 3 * 2 * 16 * 4)
    assert eng.page_bytes() == page
    assert eng.slot_state_bytes() == slot == model.slot_state_bytes()
    assert eng.cache_bytes(state) == 21 * page + 4 * slot
    assert eng.resident_cache_bytes(state) == 0     # nothing occupied
    _f, _lg, kv, _t = eng.prefill(np.arange(11)[None])
    state = eng.insert_slot(state, kv, 2)
    assert eng.resident_cache_bytes(state) == 2 * page + slot
    eng.free_slot(state, 2)                         # its state is dead now
    assert eng.resident_cache_bytes(state) == 0


def _metric(name, **labels):
    total = 0.0
    for line in global_registry().render_prometheus().splitlines():
        if line.startswith(name) and line[len(name):len(name) + 1] in " {" \
                and all(f'{k}="{v}"' in line for k, v in labels.items()):
            total += float(line.rsplit(" ", 1)[1])
    return total


def test_pipeline_spans_counters_and_gauges(family):
    """Through ``GenerationPipeline``: the step's counts are attributes of
    span ``decode_step``, the counters grow by them, the pool and slot-state
    gauges read the deployment, ``prefill`` carries ``tokens``."""
    cfg, model, _ = family
    eng = _engine(family)
    sink = reset_global_trace_sink(65536)
    held0 = _metric("dl4j_moe_pairs_total", held="1")
    absent0 = _metric("dl4j_moe_pairs_total", held="0")
    touched0 = _metric("dl4j_moe_experts_touched_total")
    from deeplearning4j_tpu.observability import span
    with GenerationPipeline(eng, slots=3, max_new_tokens=12,
                            cache_pages=30) as gp:
        with span("test_request"):      # a context, so request spans record
            out = gp.generate(np.arange(1, 20, dtype=np.int32),
                              max_new_tokens=12)
        assert len(out) == 12
        assert _metric("dl4j_decode_slot_state_bytes") \
            == 3 * eng.slot_state_bytes()
        assert _metric("dl4j_decode_page_pool_bytes") \
            == 31 * eng.page_bytes()
        snap = gp.snapshot()
        assert snap["pages"]["slot_state_bytes"] == eng.slot_state_bytes()
    steps = [s for s in sink.spans() if s.name == "decode_step"]
    assert len(steps) == 11
    held = sum(s.attrs["pairs_held"] for s in steps)
    touched = sum(s.attrs["experts_touched"] for s in steps)
    routed = sum(s.attrs["pairs_routed"] for s in steps)
    assert routed == 11 * 4 * 4         # one slot x 4 expert layers x 4
    assert 0 < touched <= held <= routed
    assert _metric("dl4j_moe_pairs_total", held="1") - held0 == held
    assert _metric("dl4j_moe_pairs_total", held="0") - absent0 \
        == routed - held
    assert _metric("dl4j_moe_experts_touched_total") - touched0 == touched
    prefill = [s for s in sink.spans() if s.name == "prefill"]
    assert prefill and prefill[0].attrs["tokens"] == 19


def test_gpt2_through_the_protocol_is_the_parents_program():
    """``TransformerLM`` behind the cache protocol runs the functions it had:
    prefill, the paged insert and the paged decode step through the engine
    give bit for bit what the parent's spelling of them gives (its
    ``model.prefill``, ``pack_kv_pages`` scattered into the slot's pages,
    ``decode_window_paged`` with a window of one)."""
    tc = TransformerConfig(vocab_size=97, n_layers=2, n_heads=2, d_model=32,
                           max_len=64, fused_qkv=True)
    model = TransformerLM(tc)
    params = model.init_params(jax.random.key(5))
    eng = DecodeEngine(model, params, max_len=64, prefill_buckets=[16],
                       page_tokens=8)
    state = eng.new_state(2, pages=10)
    prompt = np.arange(3, 14, dtype=np.int32)[None]
    first, logits, kv, t = eng.prefill(prompt)
    state = eng.insert_slot(state, kv, 1)
    pages = jnp.asarray(state.slot_pages[1], jnp.int32)
    tokens = np.asarray([0, int(np.asarray(first)[0])], np.int32)
    positions = np.asarray([0, t], np.int32)
    tables = jnp.asarray(state.tables)

    padded = jnp.asarray(np.pad(prompt, ((0, 0), (0, 16 - t))))
    want_logits, want_kv = jax.jit(model.prefill)(params, padded)
    assert np.array_equal(np.asarray(logits), np.asarray(want_logits))
    pool = model.init_paged_cache(11, 8)
    pool = jax.jit(lambda pool, kv, ids: {
        n: [held.at[ids].set(pack_kv_pages(kv[n], 8)[li])
            for li, held in enumerate(pool[n])]
        for n in ("k", "v")})(pool, want_kv, pages)
    for name in ("k", "v"):
        assert len(state.arrays[name]) == tc.n_layers
        for got, want in zip(state.arrays[name], pool[name]):
            assert got.shape == (11, 8, tc.d_model)     # rows of whole lanes
            assert np.array_equal(np.asarray(got), np.asarray(want))
    want_step, _ = jax.jit(
        lambda p, pool, tab, tok, pos: model.decode_window_paged(
            p, pool, tab, tok[:, None], pos, 8))(
        params, pool, tables, jnp.asarray(tokens), jnp.asarray(positions))
    nxt, got_step, state = eng.decode(state, tokens, positions, 1)
    assert np.asarray(nxt).shape == (2,)            # no counts ride behind
    assert eng.step_counts(np.asarray(nxt), 2) == {}
    assert np.array_equal(np.asarray(got_step), np.asarray(want_step[:, 0]))
    assert eng.page_bytes() == 2 * 8 * 2 * 32 * 4 and \
        eng.slot_state_bytes() == 0
