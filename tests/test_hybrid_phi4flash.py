"""HybridLM's Mamba-1 kind, the layers that own no cache and read another
layer's (``gmu``: a gated memory unit on an earlier layer's scan output;
``xattn``: queries alone over the ONE paged layer's rows), differential
attention in pairs, LayerNorm, the tied head and the prefill whose upper
layers run on the last row, against the plain reference
``perfbench/reference/phi4flash.py`` (float32, a sequential scan over time,
two softmax maps a pair, no cache) at the configuration's ``rehearsal`` sizes:
12 layers, H = 6 (Mamba 0, 2, 4 and window 1, 3, 5; the memory layer 6 and the
shared layer 7; memory units 8, 10 and query-only layers 9, 11), 4 heads on 2
key/value heads of 16 (two pairs on one 32-wide pair), a window of 8, 128
Mamba channels x 4 state dimensions.

Tolerances, each with its reason. Program and reference both compute in
float32 here (the configuration's dtypes are overridden), so what is left is
the order of the additions: the scan in steps of 16 rows against one row at a
time (the same products in the same order: they agree to the last bit or two),
attention on padded pairs over pages and rings against two maps a pair under a
mask, the convolution as shifted sums. Logits are of order 3 and the gaps read
1e-5 to 2e-5; ``TOL`` = 1e-4 leaves room for another CPU's vector width. Each
broken variant moves the logits by ``BROKEN`` = 1e-2 at least, a hundred times
``TOL``.
"""
import json
import logging
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
from deeplearning4j_tpu.models.generation import DecodeEngine  # noqa: E402
from deeplearning4j_tpu.models.hybrid import (  # noqa: E402
    MIXERS, HybridConfig, HybridLM, LayerSpec, Rope, mamba1_chunked,
    mamba1_step)
from deeplearning4j_tpu.observability.registry import (  # noqa: E402
    global_registry)
from deeplearning4j_tpu.observability.tracing import (  # noqa: E402
    reset_global_trace_sink)
from deeplearning4j_tpu.parallel.generation import (  # noqa: E402
    GenerationPipeline)

TOL = 1e-4
BROKEN = 1e-2
FILE = "phi-4-mini-flash-reasoning.json"
LM = harness.load_module("models", "phi4flash.py")
REF = harness.load_module("reference", "phi4flash.py")


def _load(rehearsal=True, **over):
    with open(os.path.join(ROOT, "perfbench", "configs", FILE)) as f:
        cfg = json.load(f)
    if rehearsal:
        cfg.update(cfg["rehearsal"])
    cfg.update(over)
    return cfg


def _cfg(**over):
    return _load(compute_dtype="float32", param_dtype="float32", **over)


@pytest.fixture(scope="module")
def family():
    cfg = _cfg()
    return cfg, LM.build_model(cfg), LM.make_weights(cfg, 3)


def _engine(model, params, cfg, **kw):
    return DecodeEngine(model, params, max_len=cfg["n_positions"],
                        prefill_buckets=[16, 32, 64], page_tokens=8, **kw)


def _tokens(cfg, shape=(2, 45), seed=1):
    return jax.random.randint(jax.random.key(seed), shape, 0,
                              cfg["vocab_size"])


# ----------------------------------------------------- the description
def test_layer_description_is_the_published_rule(family):
    cfg, model, params = family
    kinds = ["mamba1", "swa"] * 3 + ["mamba1", "gqa"] + ["gmu", "xattn"] * 2
    assert LM.layer_kinds(cfg) == kinds
    assert [(p.kind, p.tag, p.source) for s in model.config.layers
            for p in s.parts if p.kind != "dense"] == [
        (k, {6: "memory", 7: "shared_kv"}.get(i),
         {"gmu": "memory", "xattn": "shared_kv"}.get(k))
        for i, k in enumerate(kinds)]
    assert model.config.last_row_from == 8 and model.page_readers == 3
    big = LM.layer_kinds(_load(rehearsal=False))
    assert [big.count(k) for k in ("mamba1", "swa", "gqa", "gmu",
                                   "xattn")] == [9, 8, 1, 7, 7]
    assert big[16:20] == ["mamba1", "gqa", "gmu", "xattn"]
    # a query-only layer holds no key or value projection; every attention
    # carries the published function of its own depth
    for i, (blk, k) in enumerate(zip(params["blocks"], kinds)):
        if k == "xattn":
            assert "w_kv" not in blk["mixer"] and "b_kv" not in blk["mixer"]
        if k in ("swa", "gqa", "xattn"):
            assert float(blk["mixer"]["lambda_init"]) == pytest.approx(
                0.8 - 0.6 * np.exp(-0.3 * i), abs=1e-6)
    assert "head" not in params


def test_published_sizes_reach_the_program_as_published():
    c = LM.build_model(_load(rehearsal=False)).config
    assert (c.vocab_size, c.d_model, c.n_layers, c.max_len) == (
        200064, 2560, 32, 8192)
    assert (c.m1_inner, c.m1_state, c.m1_conv, c.m1_dt_rank) == (5120, 16, 4,
                                                                 160)
    assert (c.gqa_heads, c.swa_heads, c.gqa_kv_heads, c.gqa_head_dim,
            c.swa_window, c.dense_ff) == (40, 40, 20, 64, 512, 10240)
    assert (c.norm, c.tie_embeddings, c.rms_eps, c.last_row_from) == (
        "layer", True, 1e-5, 18)
    assert c.differential and c.attn_bias and c.gqa_rope is None \
        and c.experts is None
    # differential attention as the products see it: 10 heads of 128, four
    # query rows on each, and a kernel that reads heads in whole lane tiles
    assert c.attention_shape("gqa") == c.attention_shape("xattn") \
        == c.attention_shape("swa") == (10, 4, 128)
    assert c.gqa_kv_row == 2560


@pytest.mark.parametrize("over, says", [
    (dict(tie_word_embeddings=False), "tied head"),
    (dict(mlp_bias=True), "without bias"),
    (dict(hidden_act="gelu"), "SiLU"),
    (dict(mb_per_layer=1), "every second layer"),
    (dict(num_hidden_layers=10), "multiple of 4")])
def test_the_adapter_refuses_what_it_does_not_compute(over, says):
    with pytest.raises(ValueError, match=says):
        LM.build_model(_cfg(**over))


def _spec(layers, **over):
    kw = dict(vocab_size=64, d_model=32, layers=layers, max_len=64,
              dtype=jnp.float32, param_dtype=jnp.float32, m1_inner=64,
              m1_state=4, m1_dt_rank=2, gqa_heads=4, gqa_kv_heads=2,
              gqa_head_dim=8, swa_heads=4, swa_window=8, dense_ff=48)
    kw.update(over)
    return HybridConfig(**kw)


def test_config_refuses_a_reader_without_its_source():
    L = LayerSpec
    for layers, says in [
            ((L("mamba1", "dense"), L("gmu", "dense")), "its source"),
            ((L("gmu", "dense", source="m"),), "no earlier part"),
            ((L("gqa", "dense", tag="kv"), L("gmu", "dense", source="kv")),
             "no earlier part hands on side"),
            ((L("mamba1", "dense", tag="m"),
              L("xattn", "dense", source="m")),
             "no earlier part hands on pages"),
            ((L("swa", "dense", tag="ring"),), "hands on nothing"),
            ((L("gqa", "dense", source="x"),), "its source")]:
        with pytest.raises(ValueError, match=says):
            _spec(layers)
    ok = (L("mamba1", "dense", tag="m"), L("gqa", "dense", tag="kv"),
          L("gmu", "dense", source="m"), L("xattn", "dense", source="kv"))
    assert HybridLM(_spec(ok, last_row_from=2)).page_readers == 2
    # a layer that keeps a cache or hands something on needs every row
    with pytest.raises(ValueError, match="last row alone"):
        _spec(ok, last_row_from=1)
    with pytest.raises(ValueError, match="is not written"):
        _spec(ok, gqa_rope=Rope(theta=100.0))
    with pytest.raises(ValueError, match="pairs the heads"):
        _spec(ok, differential=True, gqa_gated=True)
    # one switch for the grouped-query kinds: a kind no layer has is not held
    _spec(ok, differential=True, swa_gated=True)
    with pytest.raises(ValueError, match="unknown norm"):
        _spec(ok, norm="batch")


# ------------------------------------------------------- the Mamba-1 scan
def _scan_inputs(T, C=24, N=4, seed=0, dt_scale=1.0):
    ks = jax.random.split(jax.random.key(seed), 5)
    x = jax.random.normal(ks[0], (2, T, C))
    dt = jax.nn.softplus(jax.random.normal(ks[1], (2, T, C)) + 1.0) * dt_scale
    a = -jnp.exp(jax.random.normal(ks[2], (N, C)) * 0.3
                 + jnp.log(jnp.arange(1, N + 1.0))[:, None])
    b, c = (jax.random.normal(k, (2, T, N)) for k in ks[3:])
    return x, dt, a, b, c


def _sequential(x, dt, a, b, c):
    s = jnp.zeros((x.shape[0], a.shape[0], x.shape[2]))
    ys = []
    for t in range(x.shape[1]):
        s, y = mamba1_step(s, x[:, t], dt[:, t], a, b[:, t], c[:, t])
        ys.append(y)
    return jnp.stack(ys, 1), s


def _by_hand(x, dt, a, b, c):
    """The recurrence in numpy, float64, one number at a time."""
    x, dt, a, b, c = (np.asarray(z, np.float64) for z in (x, dt, a, b, c))
    B, T, C = x.shape
    s = np.zeros((B, a.shape[0], C))
    y = np.zeros((B, T, C))
    for t in range(T):
        s = np.exp(dt[:, t, None, :] * a) * s \
            + (dt[:, t] * x[:, t])[:, None, :] * b[:, t, :, None]
        y[:, t] = np.einsum("bn,bnc->bc", c[:, t], s)
    return y, s


def test_the_chunked_scan_is_the_sequential_one_past_a_chunks_end():
    """37 rows in steps of 16 (padded with identity rows, as the model pads)
    against one row at a time and against the recurrence by hand, with time
    steps of 1-40 a row: a decay exp(dt a) down to e^-160 a row, so the
    running product of a chunk's decays underflows float32 to 0 and the
    naive form (divide by it) gives nothing finite. The recurrence does."""
    x, dt, a, b, c = _scan_inputs(37, dt_scale=10.0)
    pad = -37 % 16
    xs = [jnp.pad(z, ((0, 0), (0, pad), (0, 0))) for z in (x, dt, b, c)]
    s0 = jnp.zeros((2, 4, 24))
    y, s = jax.jit(lambda *z: mamba1_chunked(*z, 16))(
        xs[0], xs[1], a, xs[2], xs[3], s0)
    y_seq, s_seq = _sequential(x, dt, a, b, c)
    y_hand, s_hand = _by_hand(x, dt, a, b, c)
    assert float(jnp.max(jnp.abs(y_hand))) > 10.0
    assert float(jnp.max(jnp.abs(y[:, :37] - y_seq))) < 1e-5
    assert np.abs(np.asarray(y[:, :37]) - y_hand).max() < 1e-4
    # rows beyond the sequence are identity updates: the state is row 36's
    assert np.abs(np.asarray(s) - s_hand).max() < 1e-4
    assert float(jnp.max(jnp.abs(s - s_seq))) < 1e-5
    # the naive cumulative form: y_t = prod_t * cumsum(b_j / prod_j)
    g = jnp.cumsum(dt[:, :16, None, :] * a, axis=1)       # (B, 16, N, C)
    assert float(jnp.min(jnp.exp(g[:, -1]))) == 0.0
    naive = jnp.exp(g) * jnp.cumsum(
        (dt * x)[:, :16, None, :] * b[:, :16, :, None] / jnp.exp(g), axis=1)
    assert not bool(jnp.all(jnp.isfinite(naive)))
    assert bool(jnp.all(jnp.isfinite(y))) and bool(jnp.all(jnp.isfinite(s)))


def test_every_factor_of_the_scan_is_a_decay():
    """Nothing in the lowered scan divides, and the only exponential is of
    dt a <= 0."""
    x, dt, a, b, c = _scan_inputs(32)
    text = jax.jit(lambda *z: mamba1_chunked(*z, 16)).lower(
        x, dt, a, b, c, jnp.zeros((2, 4, 24))).as_text()
    assert "divide" not in text and "stablehlo.exponential" in text
    assert "stablehlo.while" in text


# ------------------------------------------------------ the full forward
def test_full_forward_matches_reference(family):
    cfg, model, params = family
    toks = _tokens(cfg)
    got = jax.jit(model.apply)(params, toks)
    want = REF.logits(params, toks, cfg)
    assert float(jnp.max(jnp.abs(want))) > 2.0
    assert float(jnp.max(jnp.abs(got - want))) < TOL


def test_padded_pairs_are_two_maps_a_pair(family):
    """One full layer and one window layer alone: grouped-query attention of
    four padded query rows on one 32-wide head (the program) against two
    softmax maps a pair over the joined value (the reference), and the
    shapes the program's products have."""
    cfg, model, params = family
    h = jax.random.normal(jax.random.key(5), (1, 29, cfg["hidden_size"]))
    for layer, kind, name in ((7, "gqa", "full"), (3, "swa", "window")):
        p = params["blocks"][layer]["mixer"]
        got, rows = model._gqa_full(p, h, kind)
        want, (k, v) = REF._attention(h[0], p, cfg, layer, name, None, False)
        assert float(jnp.max(jnp.abs(want))) > 0.1
        assert float(jnp.max(jnp.abs(got[0] - want))) < 1e-5
        # the cached row is the published [k heads | v heads], untouched
        assert float(jnp.max(jnp.abs(
            rows[0] - jnp.concatenate([k.reshape(29, -1), v.reshape(29, -1)],
                                      -1)))) < 1e-6
    q, _row, _gate = model._gqa_project(
        params["blocks"][7]["mixer"], h, "gqa")
    assert q.shape == (1, 29, 1, 4, 32)
    # [q1 | 0], [0 | q2] of pair 0, then of pair 1
    assert float(jnp.max(jnp.abs(q[..., 0::2, 16:]))) == 0.0
    assert float(jnp.max(jnp.abs(q[..., 1::2, :16]))) == 0.0
    assert float(jnp.min(jnp.max(jnp.abs(q), axis=-1))) > 0.0


def _apply_gap(model, params, cfg, toks=None):
    toks = _tokens(cfg) if toks is None else toks
    return float(jnp.max(jnp.abs(
        model.apply(params, toks) - REF.logits(params, toks, cfg))))


def test_a_dropped_second_map_fails(family, monkeypatch):
    """A program that normalises the first map alone (lambda 0) is no longer
    the reference's."""
    cfg, _model, params = family
    diff = HybridLM._attn_diff
    monkeypatch.setattr(HybridLM, "_attn_diff", lambda self, p, o: diff(
        self, {**p, "lambda_q1": p["lambda_q1"] * 0 - 9.0,
               "lambda_k1": p["lambda_k1"] * 0 + 1.0,
               "lambda_q2": p["lambda_q2"] * 0 - 9.0,
               "lambda_k2": p["lambda_k2"] * 0 + 1.0,
               "lambda_init": p["lambda_init"] * 0}, o)
        * (1.0 - p["lambda_init"]))
    assert _apply_gap(LM.build_model(cfg), params, cfg) > BROKEN


def test_memory_units_read_the_row_of_their_own_position(family,
                                                         monkeypatch):
    """A memory unit handed the row before its own is no longer the
    reference's; handed its own, it is."""
    cfg, model, params = family
    assert _apply_gap(model, params, cfg) < TOL
    gmu = HybridLM._gmu
    monkeypatch.setattr(HybridLM, "_gmu", lambda self, p, h, memory: gmu(
        self, p, h, jnp.roll(memory, 1, axis=1)))
    assert _apply_gap(LM.build_model(cfg), params, cfg) > BROKEN


def test_the_memory_is_the_scan_before_its_gate(family, monkeypatch):
    cfg, model, params = family
    full = HybridLM._m1_full

    def gated(self, p, h, valid, last):
        y, s, tail, side = full(self, p, h, valid, last)
        return y, s, tail, side * 0.5
    monkeypatch.setattr(HybridLM, "_m1_full", gated)
    assert _apply_gap(LM.build_model(cfg), params, cfg) > BROKEN


# ---------------------------------------------------- through the cache
def _serve(eng, cfg, plan, steps, slots=4):
    """``plan``: (join step, leave step, slot, prompt length). Every step's
    logits of every occupied slot, teacher-forced by the engine's own greedy
    tokens: [{"n", "seq", "got"}]."""
    state = eng.new_state(slots)
    rng = np.random.default_rng(0)
    runs = [{"slot": s, "join": a, "leave": b, "got": [],
             "seq": list(rng.integers(0, cfg["vocab_size"], n)), "n": n}
            for a, b, s, n in plan]
    tokens = np.zeros(slots, np.int32)
    positions = np.zeros(slots, np.int32)
    for step in range(steps):
        for r in runs:
            if r["leave"] == step:
                eng.free_slot(state, r["slot"])
                tokens[r["slot"]] = positions[r["slot"]] = 0
            if r["join"] == step:
                first, lg, kv, t = eng.prefill(
                    np.asarray(r["seq"], np.int32)[None], step=step)
                state = eng.insert_slot(state, kv, r["slot"])
                r["got"].append(np.asarray(lg)[0, 0])
                tokens[r["slot"]] = int(np.asarray(first)[0])
                positions[r["slot"]] = t
                r["seq"].append(int(tokens[r["slot"]]))
        live = [r for r in runs if r["join"] <= step < r["leave"]]
        nxt, lg, state = eng.decode(state, tokens, positions, step)
        nxt, lg = np.asarray(nxt), np.asarray(lg)
        for r in live:
            s = r["slot"]
            r["got"].append(lg[s])
            tokens[s] = nxt[s]
            positions[s] += 1
            r["seq"].append(int(nxt[s]))
    return runs, state


#: slot 1: 21 tokens (bucket 32, padded), 40 steps, so its context crosses
#: the window of 8 seven times (more than two windows deep from the start)
#: and pages of 8 at 24, 32, ...; slot 3 joins at step 5 with 37 tokens
#: (bucket 64) and leaves at 17; slot 0 joins at step 9 with 3 tokens
#: (bucket 16): YOUNGER than the window for its first five steps; another
#: prompt takes slot 3 again at step 21 over the state, the tail and the
#: rings the first one left
PLAN = [(0, 40, 1, 21), (5, 17, 3, 37), (9, 25, 0, 3), (21, 40, 3, 13)]


def _gap(runs, params, cfg):
    worst = 0.0
    for r in runs:
        full = np.asarray(r["seq"][:-1], np.int32)
        want = np.asarray(REF.logits(params, full[None], cfg))[0]
        mine = np.stack(r["got"])
        assert mine.shape[0] == r["leave"] - r["join"] + 1 >= 13
        worst = max(worst, float(np.abs(mine - want[r["n"] - 1:]).max()))
    return worst


@pytest.mark.parametrize("rings", ["xla", "paged-grouped"])
def test_prefill_then_decode_through_the_cache_is_the_full_forward(
        family, rings, monkeypatch):
    """Every step's LOGITS of every occupied slot against the reference's
    full forward over prompt + served tokens (``PLAN``): contexts several
    windows deep, page boundaries, a bucket's padding, joins and leaves,
    and slots of different ages in one step. And what the cache holds: ONE
    paged layer, whatever the layer count says. In both spellings of the
    window layers' attend: the two einsums (what the CPU gets) and the page
    walk over a slot's ring (interpret mode here; the kernel reads a pair of
    heads in whole tiles of 128 lanes, so the same twelve layers with two
    heads of 64, the published size, on a stream of 128: logits of order 6,
    where both spellings read 5e-5)."""
    cfg, model, params = family
    if rings == "paged-grouped":
        cfg = _cfg(hidden_size=128, mamba_d_inner=256, num_attention_heads=2)
        model, params = LM.build_model(cfg), LM.make_weights(cfg, 3)
        monkeypatch.setattr(hybrid, "_on_tpu", lambda: True)
    runs, state = _serve(_engine(model, params, cfg), cfg, PLAN, 40)
    assert _gap(runs, params, cfg) < TOL
    assert model.attention_backend["swa"][0] == rings
    assert {model.attention_backend[k][0] for k in ("gqa", "xattn")} == {
        "gather"}
    d = cfg["hidden_size"]
    row = 2 * 2 * d // cfg["num_attention_heads"]   # [k | v] on 2 heads
    assert sorted(state.arrays) == ["kv", "m1_conv", "m1_s", "swa_kv"]
    assert [a.shape for a in state.arrays["kv"]] == [(4 * 16 + 1, 8, row)]
    assert [a.shape for a in state.arrays["swa_kv"]] == [(4, 8, row)] * 3
    assert [(a.shape, a.dtype) for a in state.arrays["m1_s"]] == [
        ((4, 4, 2 * d), jnp.float32)] * 4
    assert [a.shape for a in state.arrays["m1_conv"]] == [(4, 3, 2 * d)] * 4


def test_the_query_only_layers_read_the_shared_layers_pool(family):
    """One decode step over a prefilled slot, then the same step with the
    ONE pool's rows of an earlier position changed: the step's logits move
    (three layers read it), and with the query-only layers' output
    projections zeroed they move by the full layer's share alone - less."""
    cfg, model, params = family
    eng = _engine(model, params, cfg)
    state = eng.new_state(2)
    first, _lg, kv, t = eng.prefill(np.arange(5, 26, dtype=np.int32)[None])
    state = eng.insert_slot(state, kv, 1)
    tables = jnp.asarray(state.tables)
    tok = jnp.asarray([0, int(np.asarray(first)[0])], jnp.int32)
    pos = jnp.asarray([0, t], jnp.int32)
    page = int(state.tables[1, 0])

    def step(p, arrays):
        return model.decode_paged(p, arrays, tables, tok, pos, 8)[0][1]

    moved = dict(state.arrays)
    moved["kv"] = [state.arrays["kv"][0].at[page, 3].multiply(-2.0)]
    base = float(jnp.max(jnp.abs(step(params, moved)
                                 - step(params, state.arrays))))
    quiet = jax.tree.map(lambda a: a, params)
    for blk, kind in zip(quiet["blocks"], LM.layer_kinds(cfg)):
        if kind == "xattn":
            blk["mixer"] = {**blk["mixer"],
                            "w_o": jnp.zeros_like(blk["mixer"]["w_o"])}
    alone = float(jnp.max(jnp.abs(step(quiet, moved)
                                  - step(quiet, state.arrays))))
    assert base > 1e-3 and 0 < alone < base
    # the step writes one pool and returns one
    _lg, out, _st = model.decode_paged(params, state.arrays, tables, tok,
                                       pos, 8)
    assert len(out["kv"]) == 1 and sorted(out) == sorted(state.arrays)


def test_the_convenience_loop_returns_the_references_logits(family):
    cfg, model, params = family
    eng = _engine(model, params, cfg)
    prompts = np.random.default_rng(1).integers(0, cfg["vocab_size"],
                                                (2, 11))
    toks, steps = eng.generate(prompts, 30, return_logits=True)
    full = np.concatenate([prompts, toks[:, :-1]], axis=1)
    want = np.asarray(REF.logits(params, full, cfg))[:, 10:]
    assert np.abs(np.stack(steps, axis=1) - want).max() < TOL


@pytest.mark.parametrize("n, bucket", [(19, 32), (16, 16), (3, 16), (9, 16)])
def test_a_padded_prefills_last_row_is_applys(family, n, bucket):
    """``prefill_cache`` over a padded bucket: the logits of the prompt's
    TRUE last token are ``apply``'s over the prompt alone though layers 8-11
    saw ONE row; the state and the tail are those of the true last token
    (the next step's logits are ``apply``'s too: the test above); the rows
    of the paged layer are every position's."""
    cfg, model, params = family
    toks = _tokens(cfg, (1, n), seed=n)
    padded = jnp.pad(toks, ((0, 0), (0, bucket - n)), constant_values=7)
    lg, entries = jax.jit(model.prefill_cache)(params, padded, n - 1)
    want = model.apply(params, toks)
    assert lg.shape == (1, 1, cfg["vocab_size"])
    assert float(jnp.max(jnp.abs(lg[0, 0] - want[0, -1]))) < TOL
    assert entries["kv"][0].shape == (1, bucket, 64)
    _x, alone = model._trunk(params, toks, n - 1)
    for name in ("m1_s", "m1_conv", "swa_kv"):
        for a, b in zip(entries[name], alone[name]):
            assert float(jnp.max(jnp.abs(a - b))) < 1e-5, name
    assert model.prefill_tail_rows(bucket) == 1


def test_the_upper_layers_of_a_prefill_compute_one_row():
    """The lowered prefill of a 64 bucket holds no (1, 64, 2 d_ff) product
    of layers 8-11: 8 feed-forwards over the bucket and 4 over one row (a
    feed-forward 96 wide, so that no other product has its shape)."""
    cfg = _cfg(intermediate_size=96)
    model, params = LM.build_model(cfg), LM.weight_shapes(cfg)
    text = jax.jit(model.prefill_cache).lower(
        params, jnp.zeros((1, 64), jnp.int32), jnp.int32(40)).as_text()
    wide, one = "tensor<1x64x192xf32>", "tensor<1x1x192xf32>"
    dots = [line for line in text.splitlines() if "dot_general" in line]
    assert sum(wide in line for line in dots) == 8
    assert sum(one in line for line in dots) == 4
    # and apply computes every row of every layer
    text = jax.jit(model.apply).lower(
        params, jnp.zeros((1, 64), jnp.int32)).as_text()
    dots = [line for line in text.splitlines() if "dot_general" in line]
    assert sum(wide in line for line in dots) == 12
    assert sum(one in line for line in dots) == 0


# ------------------------------------- the page walk on padded pairs
def _pairs_128(dtype=jnp.float32):
    """A full layer and a query-only layer behind it, 4 heads on 2
    key/value heads of 64: ONE pair of 128 with four query rows, what the
    kernel reads in whole tiles of 128 lanes."""
    L = LayerSpec
    cfg = HybridConfig(
        vocab_size=64, d_model=64, max_len=96, dense_ff=64,
        layers=(L("gqa", "dense", tag="kv"),
                L("xattn", "dense", source="kv")),
        gqa_heads=4, gqa_kv_heads=2, gqa_head_dim=64, differential=True,
        attn_bias=True, dtype=dtype, param_dtype=dtype)
    model = HybridLM(cfg)
    blocks = model.init_params(jax.random.key(6))["blocks"]
    big = lambda t: jax.tree.map(                           # noqa: E731
        lambda a: (10 * a).astype(a.dtype) if a.ndim == 2 else a, t)
    return model, big(blocks[0]["mixer"]), big(blocks[1]["mixer"])


def _step(positions, dtype=jnp.float32, P=8, pages=12):
    slots = len(positions)
    trash = slots * pages
    ks = jax.random.split(jax.random.key(0), 2)
    pool = jax.random.normal(ks[0], (trash + 1, P, 256)).astype(dtype)
    owned = np.random.default_rng(0).permutation(trash).reshape(slots, pages)
    pos = np.asarray(positions)
    tables = np.where(np.arange(pages)[None, :] <= pos[:, None] // P, owned,
                      trash)
    return (jax.random.normal(ks[1], (slots, 64)).astype(dtype), pool,
            jnp.asarray(tables, jnp.int32), jnp.asarray(pos, jnp.int32),
            trash)


@pytest.mark.parametrize("positions", [(0, 7, 8, 95), (40, 3, 63, 64)])
def test_the_page_walk_takes_the_padded_pairs_of_both_kinds(positions,
                                                            monkeypatch):
    """float32: the full layer's step and the query-only layer's, through
    the kernel that walks the live pages (interpret mode here), are the
    gather spelling's to summation noise; the query-only layer returns no
    pool and the trash page (NaN here) is never read."""
    from deeplearning4j_tpu.kernels import paged_latent_attention as pla
    model, p_full, p_cross = _pairs_128()
    h, pool, tables, pos, trash = _step(positions)
    clean = pool.at[trash].set(0)
    want, pool0 = model._gqa_decode(p_full, h, clean, tables, pos, 8)
    want_x = model._gqa_decode(p_cross, h, pool0, tables, pos, 8, "xattn")
    assert model.attention_backend == {
        kind: ("gather", "on cpu") for kind in ("gqa", "xattn")}
    assert want_x[1] is pool0       # handed back as it came: nothing written
    monkeypatch.setattr(hybrid, "_on_tpu", lambda: True)
    monkeypatch.setattr(hybrid, "GATHER_VIEW_BYTES", 0)
    monkeypatch.setattr(pla, "GROUPED_VISIT_BYTES", 2 * 8 * 256 * 4)
    got, pool1 = model._gqa_decode(p_full, h, pool.at[trash].set(jnp.nan),
                                   tables, pos, 8)
    assert model.attention_backend["gqa"][0] == "paged-grouped"
    got_x, _ = model._gqa_decode(p_cross, h, pool1, tables, pos, 8, "xattn")
    for a, b in ((got, want), (got_x, want_x[0])):
        assert not bool(jnp.isnan(a).any())
        assert float(jnp.max(jnp.abs(b))) > 0.05
        assert float(jnp.max(jnp.abs(a - b))) < 2e-5 * max(
            1.0, float(jnp.max(jnp.abs(b))))
    # the chooser sees 4 query rows on 1 head of 128, a row of 256
    assert hybrid.grouped_attention_backend(4, 4, 1, 128, 8, 12, 4)[0] \
        == "paged-grouped"


@pytest.mark.parametrize("dtype, tol", [(jnp.float32, 2e-5),
                                        (jnp.bfloat16, 2e-2)])
def test_the_page_walk_takes_a_rings_padded_pairs(dtype, tol, monkeypatch):
    """The window kind in the differential form, 4 heads on 2 key/value
    heads of 64 (ONE pair of 128 with four query rows) over a ring of 24
    rows in pages of 8: through the kernel and ``_attn_diff`` it is the two
    einsums and ``_attn_diff``, to summation noise in float32 and within
    one rounding in bfloat16; slots younger than the window (whose dead
    pages hold NaN in float32), as old, one row past it and several turns
    past it in one batch."""
    L = LayerSpec
    cfg = HybridConfig(
        vocab_size=64, d_model=64, max_len=256, dense_ff=64,
        layers=(L("swa", "dense"),), gqa_heads=4, swa_heads=4,
        gqa_kv_heads=2, gqa_head_dim=64, swa_window=24, differential=True,
        attn_bias=True, dtype=dtype, param_dtype=dtype)
    model = HybridLM(cfg)
    assert cfg.attention_shape("swa") == (1, 4, 128)
    p = jax.tree.map(
        lambda a: (10 * a).astype(a.dtype) if a.ndim == 2 else a,
        model.init_params(jax.random.key(6))["blocks"][0]["mixer"])
    ages = (0, 7, 8, 23, 24, 100)
    ks = jax.random.split(jax.random.key(0), 2)
    ring = jax.random.normal(ks[0], (len(ages), 24, 256)).astype(dtype)
    h = jax.random.normal(ks[1], (len(ages), 64)).astype(dtype)
    pos = jnp.asarray(ages, jnp.int32)
    want, ring0 = model._swa_decode(p, h, ring, pos)
    assert model.attention_backend == {"swa": ("xla", "on cpu")}
    monkeypatch.setattr(hybrid, "_on_tpu", lambda: True)
    monkeypatch.setattr(hybrid, "RING_PAGE_ROWS", 8)
    if dtype == jnp.float32:
        beyond = np.arange(24)[None, :] // 8 > np.asarray(ages)[:, None] // 8
        ring = jnp.where(beyond[:, :, None], jnp.nan, ring)
    got, ring1 = model._swa_decode(p, h, ring, pos)
    assert model.attention_backend["swa"] == (
        "paged-grouped",
        "live pages of 8 rows of 256 of a ring of 24 read where they lie")
    assert not bool(jnp.isnan(got).any())
    want, got = want.astype(jnp.float32), got.astype(jnp.float32)
    assert float(jnp.max(jnp.abs(want))) > 0.05
    assert float(jnp.max(jnp.abs(got - want))) < tol * max(
        1.0, float(jnp.max(jnp.abs(want))))
    at = np.asarray(ages) % 24
    np.testing.assert_array_equal(
        np.asarray(ring1, np.float32)[np.arange(len(ages)), at],
        np.asarray(ring0, np.float32)[np.arange(len(ages)), at])


# ------------------------------------------------------------ the bytes
def test_bytes_are_one_paged_layer_the_rings_and_the_states(family):
    cfg, model, params = family
    eng = _engine(model, params, cfg)
    state = eng.new_state(4, pages=20)
    row = 2 * 2 * 16 * 4                    # [k | v] on 2 heads of 16, f32
    page = 8 * row                          # ONE paged layer of 12
    slot = 3 * 8 * row + 4 * (4 * 128 * 4 + 3 * 128 * 4)
    assert eng.page_bytes() == page == model.page_bytes(8)
    assert eng.slot_state_bytes() == slot == model.slot_state_bytes()
    assert eng.cache_bytes(state) == 21 * page + 4 * slot
    # the arithmetic of ISSUE 46 at the published sizes, nothing allocated
    big = LM.build_model(_load(rehearsal=False))
    assert big.page_readers == 8
    assert big.page_bytes(64) == 64 * 5120
    rings, states = 8 * 512 * 5120, 9 * (5120 * 16 * 4 + 3 * 5120 * 2)
    assert (rings, states) == (20971520, 3225600)
    assert big.slot_state_bytes() == rings + states
    cache = jax.eval_shape(lambda: big.new_paged_cache(64, 8193, 64))
    sizes = {k: sum(a.size * a.dtype.itemsize for a in v)
             for k, v in cache.items()}
    assert sizes["kv"] == 8193 * 64 * 5120 and len(cache["kv"]) == 1
    assert sizes["kv"] / 1e9 == pytest.approx(2.68, abs=0.01)
    assert sum(sizes.values()) - sizes["kv"] == 64 * (rings + states)
    assert 64 * (rings + states) / 1e9 == pytest.approx(1.55, abs=0.01)
    costs = harness.load_module("costs", "phi4flash.py")
    full = _load(rehearsal=False)
    assert costs.n_params(full) == sum(
        int(np.prod(a.shape)) for a in jax.tree.leaves(
            LM.weight_shapes(full))) - 65 * 2 * 2560 - 16
    assert costs.slot_state_bytes(full) == rings + states
    assert costs.kv_row_bytes(full) == 5120


# ------------------------------------------------- spans, gauges, the log
def _metric(name):
    total = 0.0
    for line in global_registry().render_prometheus().splitlines():
        if line.startswith(name) and line[len(name):len(name) + 1] in " {":
            total += float(line.rsplit(" ", 1)[1])
    return total


def test_pipeline_spans_the_gauge_and_the_log_line(family, caplog):
    """Through ``GenerationPipeline``: span ``decode_step`` carries
    ``page_readers`` (the shared layer and the two that read it) beside
    ``live_tokens`` and ``cache_bytes`` (ONE layer's pages + the slot's
    fixed state); span ``prefill`` carries ``tail_rows`` 1; the log line
    names what reads what."""
    cfg, _model, params = family
    fresh = LM.build_model(cfg)
    eng = _engine(fresh, params, cfg)
    sink = reset_global_trace_sink(65536)
    with caplog.at_level(logging.INFO,
                         logger="deeplearning4j_tpu.models.hybrid"):
        with GenerationPipeline(eng, slots=3, max_new_tokens=12,
                                cache_pages=30) as gp:
            out = gp.generate(np.arange(1, 12, dtype=np.int32),
                              max_new_tokens=12)
            assert len(out) == 12
            assert _metric("dl4j_decode_page_pool_bytes") \
                == 31 * eng.page_bytes() > 0
    steps = [s for s in sink.spans() if s.name == "decode_step"]
    assert len(steps) == 11
    assert {s.attrs["page_readers"] for s in steps} == {3}
    assert [s.attrs["live_tokens"] for s in steps] == list(range(12, 23))
    assert {s.attrs["window_rows"] for s in steps} == {8}
    # 12-22 positions: the bucket's two pages, then a third from 16 on
    assert [s.attrs["cache_bytes"] for s in steps] == [
        (2 if n <= 16 else 3) * eng.page_bytes() + eng.slot_state_bytes()
        for n in range(12, 23)]
    joins = [s for s in sink.spans() if s.name == "prefill"]
    assert [s.attrs["tail_rows"] for s in joins] == [1]
    assert joins[0].attrs["bucket"] == 16
    said = [r.getMessage() for r in caplog.records
            if r.getMessage().startswith("layer kinds:")]
    assert said and said[0] == (
        "layer kinds: mamba1+dense swa+dense mamba1+dense swa+dense "
        "mamba1+dense swa+dense mamba1=memory+dense gqa=shared_kv+dense "
        "gmu<memory+dense xattn<shared_kv+dense gmu<memory+dense "
        "xattn<shared_kv+dense: no routed experts; gqa 4 heads on 2, "
        "unrotated, differential in pairs of 32 wide, biased; swa 4 heads "
        "on 2, unrotated, differential in pairs of 32 wide, biased, window "
        "8; xattn: queries alone, 3 parts read the pages; mamba1 128 "
        "channels x 4, dt through 4, 16 rows a scan step; layer norm, tied "
        "head; a prefill runs the layers from 8 on the last row")


def test_other_models_report_their_own_readers_and_the_buckets_rows():
    """A model whose every layer is run over every row reports the bucket;
    one whose layers each keep pages of their own, their count."""
    nm = harness.load_module("models", "nemotron_h.py")
    with open(os.path.join(ROOT, "perfbench", "configs",
                           "nemotron-3-super-120b-a12b-ep4share.json")) as f:
        cfg = json.load(f)
    cfg.update(cfg["rehearsal"])
    cfg.update(compute_dtype="float32", param_dtype="float32")
    model, params = nm.build_model(cfg), nm.make_weights(cfg, 3)
    assert model.page_readers == cfg["layers_run"].count("*")
    assert model.prefill_tail_rows(32) == 32
    sink = reset_global_trace_sink(65536)
    with GenerationPipeline(_engine(model, params, cfg), slots=2,
                            max_new_tokens=4, cache_pages=20) as gp:
        gp.generate(np.arange(1, 6, dtype=np.int32), max_new_tokens=4)
    steps = [s for s in sink.spans() if s.name == "decode_step"]
    assert steps and {s.attrs["page_readers"] for s in steps} == {
        model.page_readers}
    assert [s.attrs["tail_rows"] for s in sink.spans()
            if s.name == "prefill"] == [16]


def test_the_table_of_kinds_says_who_reads_and_who_gives():
    assert {k: (m.reads, m.gives_side) for k, m in MIXERS.items()} == {
        "kda": (None, False), "mla": (None, False), "mamba2": (None, False),
        "mamba1": (None, True), "gmu": ("side", False),
        "gqa": (None, False), "swa": (None, False),
        "xattn": ("pages", False)}
