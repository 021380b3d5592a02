"""HybridLM's two grouped-query kinds - ``gqa`` over every earlier position,
rows in pages; ``swa`` over the last ``swa_window``, rows in a ring a slot -
with their rotations (YaRN on half a head, plain on all of it), the head-wise
output gate and head counts of their own, against the plain reference
``perfbench/reference/laguna.py`` (float32, expanded attention under a mask,
rotation by reshaped pairs, a loop over experts) at the configuration's
``rehearsal`` sizes: source layers 0-4 (full + dense, three window + experts,
full + experts), 6 and 8 heads on 2 key/value heads of 32, a window of 8, 16
experts at 4 a token, YaRN's ramp between pairs 2 and 5 of the 8 that rotate.

Tolerances, each with its reason. Program and reference both compute in
float32 here (the configuration's dtypes are overridden) from weights drawn
at 0.125 (``WEIGHTS``: scores spread about 1, gates 0.3-0.7, so that every
mechanism moves the logits), so what is left is the order of the additions:
attention over pages and rings against the expanded form under a mask, the
rotation spelled with lane rolls against reshaped pairs, a grouped product
against a loop over experts. Logits are of order 4 and the gaps read 4e-6 to
6e-6; ``TOL`` = 5e-5 leaves room for another CPU's vector width. Each broken
variant moves the logits by ``BROKEN`` = 1e-2 at least (they read 0.03-1.6),
two hundred times ``TOL``.
"""
import hashlib
import json
import logging
import math
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

from deeplearning4j_tpu.kernels import (  # noqa: E402
    paged_latent_attention as pla)
from deeplearning4j_tpu.models import hybrid  # noqa: E402
from deeplearning4j_tpu.models.generation import DecodeEngine  # noqa: E402
from deeplearning4j_tpu.models.hybrid import (  # noqa: E402
    HybridConfig, HybridLM, LayerSpec, Rope)
from deeplearning4j_tpu.observability.registry import (  # noqa: E402
    global_registry)
from deeplearning4j_tpu.observability.tracing import (  # noqa: E402
    reset_global_trace_sink)
from deeplearning4j_tpu.parallel.generation import (  # noqa: E402
    GenerationPipeline)

TOL = 5e-5
BROKEN = 1e-2
FILE = "laguna-xs2-33b-a3b-stage5.json"
WEIGHTS = {"embedding_std": 0.3, "in_std": 0.125, "resid_std": 0.05,
           "router_std": 0.125, "b_select_std": 0.01, "gain_std": 0.1}
LM = harness.load_module("models", "laguna.py")
REF = harness.load_module("reference", "laguna.py")
NM = harness.load_module("models", "nemotron_h.py")


def _load(name=FILE, rehearsal=True, **over):
    with open(os.path.join(ROOT, "perfbench", "configs", name)) as f:
        cfg = json.load(f)
    if rehearsal:
        cfg.update(cfg["rehearsal"])
    cfg.update(over)
    return cfg


def _cfg(**over):
    return _load(compute_dtype="float32", param_dtype="float32",
                 weights=WEIGHTS, **over)


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
def test_layer_description_is_the_published_stage(family):
    cfg, model, params = family
    c = model.config
    assert [(s.mixer, s.ffn) for s in c.layers] == [
        ("gqa", "dense"), ("swa", "moe"), ("swa", "moe"), ("swa", "moe"),
        ("gqa", "moe")]
    assert (c.gqa_heads, c.swa_heads, c.gqa_kv_heads, c.gqa_head_dim,
            c.swa_window) == (6, 8, 2, 32, 8)
    assert c.gqa_gated and c.swa_gated
    assert c.gqa_rope == Rope(theta=100.0, dims=16,
                              amplitude=1.2079441541679836, factor=8.0,
                              original=64, beta_fast=2.0, beta_slow=1.0)
    assert c.swa_rope == Rope(theta=10000.0, dims=32)
    e = c.experts
    assert (e.router_width, e.top_k, e.held, e.scale, e.renormalize, e.score,
            e.shared, e.form) == (16, 4, (0, 16), 2.5, True, "sigmoid", True,
                                  "swiglu")
    # the adapter's tree is the program's own, gates and all
    own = jax.eval_shape(model.init_params, jax.random.key(0))
    assert jax.tree.structure(own) == jax.tree.structure(params)
    assert jax.tree.leaves(jax.tree.map(
        lambda a, b: a.shape == b.shape, own, params)).count(False) == 0
    assert params["blocks"][0]["mixer"]["w_gate"].shape == (64, 6)
    assert params["blocks"][1]["mixer"]["w_gate"].shape == (64, 8)
    assert model.cache_window == 8
    assert NM.build_model(_load(
        "nemotron-3-super-120b-a12b-ep4share.json")).cache_window is None


def test_published_sizes_reach_the_program_as_published():
    cfg = _load(rehearsal=False)
    c = LM.build_model(cfg).config
    assert (c.vocab_size, c.d_model, c.n_layers, c.max_len) == (
        100352, 2048, 5, 7168)
    assert (c.gqa_heads, c.swa_heads, c.gqa_kv_heads, c.gqa_head_dim,
            c.swa_window, c.dense_ff, c.expert_ff) == (48, 64, 8, 128, 512,
                                                       8192, 512)
    assert c.gqa_rope == Rope(theta=500000.0, dims=64,
                              amplitude=1.4158883083359672, factor=64.0,
                              original=4096, beta_fast=64.0, beta_slow=1.0)
    assert c.swa_rope == Rope(theta=10000.0, dims=128)
    assert c.experts.held == (0, 256) and c.experts.top_k == 8
    assert c.gqa_kv_row * 2 == 4096             # 4 KB a token a layer


@pytest.mark.parametrize("over, says", [
    ({"gating": False}, "gated"), ({"attention_bias": True}, "bias"),
    ({"tie_word_embeddings": True}, "untied"),
    ({"moe_apply_router_weight_on_input": True}, "outputs"),
    ({"num_attention_heads": 12}, "adapter"),
    ({"shared_expert_intermediate_size": 64}, "shared")])
def test_the_adapter_refuses_what_it_does_not_compute(over, says):
    with pytest.raises(ValueError, match=says):
        LM.build_model(_cfg(**over))


def test_the_adapter_refuses_an_unknown_rotation():
    cfg = _cfg()
    cfg["rope_parameters"] = json.loads(json.dumps(cfg["rope_parameters"]))
    cfg["rope_parameters"]["sliding_attention"]["rope_type"] = "llama3"
    with pytest.raises(ValueError, match="llama3"):
        LM.build_model(cfg)


def test_config_refuses_a_window_kind_without_a_window_and_half_a_yarn():
    layers = (LayerSpec("swa", "dense"),)
    with pytest.raises(ValueError, match="swa_window"):
        HybridConfig(vocab_size=8, d_model=8, layers=layers, max_len=8)
    with pytest.raises(ValueError, match="divide"):
        HybridConfig(vocab_size=8, d_model=8, layers=layers, max_len=8,
                     swa_window=4, swa_heads=3)
    with pytest.raises(ValueError, match="four numbers"):
        Rope(theta=1e4, factor=8.0)
    with pytest.raises(ValueError, match="four numbers"):
        Rope(theta=1e4, factor=8.0, original=64, beta_fast=2.0)
    with pytest.raises(ValueError, match="pairs"):
        Rope(theta=1e4, dims=7)
    with pytest.raises(ValueError, match="more than a head"):
        HybridConfig(vocab_size=8, d_model=8, max_len=8, gqa_head_dim=16,
                     layers=(LayerSpec("gqa", "dense"),),
                     gqa_rope=Rope(theta=1e4, dims=32))
    # a window elsewhere is nobody's business: the fourth configuration
    assert HybridConfig(vocab_size=8, d_model=8, max_len=8,
                        layers=(LayerSpec("gqa", None),)).swa_window is None


# ----------------------------------------------------------- the rotation
def test_yarn_frequencies_are_the_published_keys_worked_by_hand():
    """theta 500,000, 64 rotated dimensions, factor 64, original 4,096,
    beta_fast 64, beta_slow 1: ``corr(n) = 64 ln(4096 / (2 pi n)) / (2 ln
    500000)`` is 5.66 at n = 64 and 15.80 at n = 1, so the ramp rises from
    pair 5 to pair 16; below it a pair keeps ``theta^(-2i/64)``, above it
    turns 64 times slower, between them the blend. Program (float32) and
    reference (Python floats) against the hand-worked numbers."""
    r = _load(rehearsal=False)["rope_parameters"]["full_attention"]
    rope = LM._rope(_load(rehearsal=False), "full_attention")
    assert 64 * math.log(4096 / (2 * math.pi * 64)) / (
        2 * math.log(5e5)) == pytest.approx(5.6600, abs=1e-4)
    assert 64 * math.log(4096 / (2 * math.pi)) / (
        2 * math.log(5e5)) == pytest.approx(15.8018, abs=1e-4)
    assert rope.ramp(64) == (5, 16)
    ref, ramp = REF.inv_freq(r, 64)
    assert ramp == (5, 16)
    got = rope.inv_freq(64)
    assert got.dtype == np.float32 and got.shape == (32,)
    f = lambda i: 5e5 ** (-2 * i / 64)                      # noqa: E731
    by_hand = {0: 1.0,                      # below the ramp: untouched
               5: f(5),                     # = 0.128687, the ramp's foot
               10: f(10) * (1 - 5 / 11) + f(10) / 64 * (5 / 11),
               16: f(16) / 64,              # the ramp's head: 64x slower
               31: f(31) / 64}              # = 4.7092e-08
    assert by_hand[5] == pytest.approx(0.128687, rel=1e-5)
    assert by_hand[16] == pytest.approx(2.20971e-05, rel=1e-5)
    assert by_hand[31] == pytest.approx(4.70915e-08, rel=1e-5)
    for i, want in by_hand.items():
        assert ref[i] == pytest.approx(want, rel=1e-12)
        assert float(got[i]) == pytest.approx(want, rel=1e-6)
    np.testing.assert_allclose(got, np.asarray(ref), rtol=1e-6)
    # the amplitude factor is the published key, 0.1 ln(factor) + 1
    assert rope.amplitude == r["attention_factor"] == pytest.approx(
        0.1 * math.log(64) + 1, rel=1e-12)
    # the window kind's rotation: plain, all 128 dimensions, no factor
    plain = LM._rope(_load(rehearsal=False), "sliding_attention")
    np.testing.assert_allclose(
        plain.inv_freq(128), 1e4 ** (-np.arange(64) / 64.0), rtol=1e-6)
    assert plain.amplitude == 1.0 and plain.ramp is not None


def test_rotation_turns_the_leading_dimensions_and_carries_the_rest():
    """``hybrid._rotate`` against the reference's reshaped pairs: the first
    16 of 32 dimensions turned and scaled, the other 16 bit for bit."""
    cfg = _cfg()
    rope = LM._rope(cfg, "full_attention")
    x = jax.random.normal(jax.random.key(0), (11, 3, 32))
    got = hybrid._rotate(x, jnp.arange(11)[:, None], rope)
    want = REF._rotate(x, cfg["rope_parameters"]["full_attention"])
    assert float(jnp.max(jnp.abs(got - want))) < 1e-5
    assert bool(jnp.all(got[..., 16:] == x[..., 16:]))
    assert float(jnp.max(jnp.abs(got[1:, :, :16] - x[1:, :, :16]))) > 0.5
    # position 0 is no turn: the amplitude alone
    np.testing.assert_allclose(got[0, :, :16], x[0, :, :16] * rope.amplitude,
                               rtol=1e-6)


# ------------------------------------------------------ the full forward
def test_full_forward_matches_reference(family):
    cfg, model, params = family
    toks = _tokens(cfg)
    got = jax.jit(model.apply)(params, toks)
    want = REF.logits(params, toks, cfg)
    assert float(jnp.max(jnp.abs(want))) > 2.0
    assert float(jnp.max(jnp.abs(got - want))) < TOL


def test_window_prefill_scores_the_keys_a_block_can_see(family, monkeypatch):
    """With blocks of 16 queries a block of the window kind is handed 16 + 8
    keys, not the sequence's 45, and the result is the reference's; the full
    kind's block is handed every key."""
    cfg, model, params = family
    monkeypatch.setattr(hybrid, "_QUERY_BLOCK", 16)
    toks = _tokens(cfg)
    text = jax.jit(model.apply).lower(params, toks).as_text()
    # scores: (batch, kv heads, heads a kv head, queries, keys)
    assert "tensor<2x2x4x16x24xf32>" in text        # window: 8 heads on 2
    assert "tensor<2x2x3x16x45xf32>" in text        # full: 6 heads on 2
    assert "tensor<2x2x4x16x45xf32>" not in text
    got = jax.jit(model.apply)(params, toks)
    assert float(jnp.max(jnp.abs(got - REF.logits(params, toks, cfg)))) < TOL


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
#: the window of 8 seven times and pages of 8 at 24, 32, ...; slot 3 joins at
#: step 5 with 37 tokens (bucket 64) and leaves at 17; slot 0 joins at step 9
#: with 3 tokens (bucket 16): YOUNGER than the window for its first five
#: steps, beside two slots that are older; another prompt takes slot 3 again
#: at step 21 over the ring the first one left
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
def test_prefill_then_decode_through_pages_and_rings_is_the_full_forward(
        family, rings, monkeypatch, caplog):
    """Every step's LOGITS of every occupied slot against the reference's
    full forward over prompt + served tokens (``PLAN``): contexts several
    windows deep, page boundaries, a bucket's padding, joins and leaves, and
    slots of different ages in one step. In both spellings of the window
    layers' attend: the two einsums (what the CPU gets) and the page walk
    over a slot's ring (interpret mode here; the kernel reads heads in whole
    tiles of 128 lanes, so the same stage with heads of 128, and its window
    of 8 rows as one page)."""
    cfg, model, params = family
    if rings == "paged-grouped":
        cfg = _cfg(head_dim=128)
        params = LM.make_weights(cfg, 3)
        monkeypatch.setattr(hybrid, "_on_tpu", lambda: True)
    model = LM.build_model(cfg)         # nothing traced, nothing said
    with caplog.at_level(logging.INFO, logger=hybrid.__name__):
        runs, state = _serve(_engine(model, params, cfg), cfg, PLAN, 40)
    assert _gap(runs, params, cfg) < TOL
    assert model.attention_backend["swa"][0] == rings
    assert model.attention_backend["gqa"][0] == "gather"
    # one line a kind a trace of the decode program, three window layers
    # and two full ones or not
    said = [r.getMessage() for r in caplog.records
            if r.getMessage().startswith("attention backend: ")]
    swa = "attention backend: swa: " + {
        "xla": "xla: on cpu",
        "paged-grouped": "paged-grouped: live pages of 8 rows of 512 of a "
                         "ring of 8 read where they lie"}[rings]
    gqa = "attention backend: gqa: gather: " + (
        "on cpu" if rings == "xla" else "the view of every slot's window is "
        "1 MiB")
    assert 1 <= said.count(swa) == said.count(gqa) <= 2
    assert set(said) == {swa, gqa}
    # what a window layer holds a slot is the window, whatever the context
    row = 4 * cfg["head_dim"]
    assert [a.shape for a in state.arrays["swa_kv"]] == [(4, 8, row)] * 3
    assert len(state.arrays["kv"]) == 2


def test_the_convenience_loop_returns_the_references_logits(family):
    cfg, model, params = family
    eng = _engine(model, params, cfg)
    prompts = np.random.default_rng(1).integers(0, cfg["vocab_size"],
                                                (2, 11))
    toks, steps = eng.generate(prompts, 30, return_logits=True)
    full = np.concatenate([prompts, toks[:, :-1]], axis=1)
    want = np.asarray(REF.logits(params, full, cfg))[:, 10:]
    assert np.abs(np.stack(steps, axis=1) - want).max() < TOL


@pytest.mark.parametrize("n, bucket", [(19, 32), (16, 16), (3, 16), (8, 16)])
def test_a_padded_prefill_hands_over_the_ring_at_the_true_last_token(
        family, n, bucket):
    """The ring a prefill returns: the row of position t at ``t % 8`` for the
    prompt's last 8 positions up to n - 1, zeros where the prompt is
    shorter, whatever the bucket's padding holds; and the rows of every
    position for the full layers' pages."""
    cfg, model, params = family
    toks = np.zeros((1, bucket), np.int32)
    toks[0, :n] = np.random.default_rng(n).integers(1, cfg["vocab_size"], n)
    noisy = toks.copy()
    noisy[0, n:] = 7                          # padding of another colour
    _lg, ent = jax.jit(model.prefill_cache)(params, toks, n - 1)
    _lg2, ent2 = jax.jit(model.prefill_cache)(params, noisy, n - 1)
    assert model.entries_tokens(ent) == bucket
    for ring, ring2 in zip(ent["swa_kv"], ent2["swa_kv"]):
        assert ring.shape == (1, 8, 128)
        assert float(jnp.max(jnp.abs(ring - ring2))) == 0.0
        held = {int(t) % 8 for t in range(max(0, n - 8), n)}
        for j in range(8):
            assert bool(jnp.any(ring[0, j] != 0)) == (j in held)


# --------------------------------------------- what must FAIL the comparison
def _apply_gap(model, params, cfg, toks=None):
    toks = _tokens(cfg) if toks is None else toks
    return float(jnp.max(jnp.abs(jax.jit(model.apply)(params, toks)
                                 - REF.logits(params, toks, cfg))))


def test_a_dropped_gate_fails(family):
    cfg, _model, params = family
    for kind in ("gqa_gated", "swa_gated"):
        broken = LM.build_model(cfg)
        setattr(broken.config, kind, False)
        assert _apply_gap(broken, params, cfg) > BROKEN


def test_an_unrotated_key_fails(family, monkeypatch):
    cfg, model, params = family
    rotate = hybrid._rotate
    # keys are (..., kv heads, hd), queries one axis more
    monkeypatch.setattr(hybrid, "_rotate", lambda x, pos, rope: (
        rotate(x, pos, rope) if x.ndim == 5 else x.astype(jnp.float32)))
    assert _apply_gap(LM.build_model(cfg), params, cfg) > BROKEN


def test_the_wrong_half_of_a_head_rotated_fails(family, monkeypatch):
    cfg, model, params = family
    rotate = hybrid._rotate
    monkeypatch.setattr(hybrid, "_rotate", lambda x, pos, rope: rotate(
        x[..., ::-1], pos, rope)[..., ::-1])
    broken = LM.build_model(cfg)
    assert _apply_gap(broken, params, cfg) > BROKEN
    # and the full layers alone (the window kind turns all of a head)
    broken.config.swa_rope = None
    ref_cfg = json.loads(json.dumps(cfg))
    ref_cfg["rope_parameters"]["sliding_attention"].update(
        partial_rotary_factor=0.0)
    toks = _tokens(cfg)
    got = jax.jit(broken.apply)(params, toks)
    assert float(jnp.max(jnp.abs(
        got - REF.logits(params, toks, ref_cfg)))) > BROKEN


@pytest.mark.parametrize("window", [7, 9])
def test_a_window_off_by_one_fails(family, window):
    cfg, _model, params = family
    broken = LM.build_model(dict(cfg, sliding_window=window))
    assert _apply_gap(broken, params, cfg) > BROKEN
    runs, _state = _serve(_engine(broken, params, cfg), cfg, PLAN[:1], 14)
    runs[0]["leave"] = 14
    assert _gap(runs, params, cfg) > BROKEN


def test_a_ring_read_in_page_order_fails(family, monkeypatch):
    """A ring read as a page is read, rows 0 .. position % window live and
    the rest dead, loses the rows that wrapped: right for a slot younger
    than the window, wrong from the first wrap on."""
    cfg, _model, params = family
    monkeypatch.setattr(hybrid, "_ring_live", lambda w, pos: (
        jnp.arange(w)[None, :] <= (pos % w)[:, None]))
    broken = LM.build_model(cfg)
    young, _ = _serve(_engine(broken, params, cfg), cfg, [(0, 4, 2, 3)], 4)
    young[0]["leave"] = 12                  # the shape check's floor
    full = np.asarray(young[0]["seq"][:-1], np.int32)
    want = np.asarray(REF.logits(params, full[None], cfg))[0]
    assert np.abs(np.stack(young[0]["got"]) - want[2:]).max() < TOL
    runs, _state = _serve(_engine(broken, params, cfg), cfg, PLAN[:1], 14)
    runs[0]["leave"] = 14
    assert _gap(runs, params, cfg) > BROKEN


# ------------------------------------------------- the cache's books
def test_bytes_count_the_full_layers_pages_and_the_window_layers_rings(
        family):
    cfg, model, params = family
    eng = _engine(model, params, cfg)
    state = eng.new_state(4, pages=20)
    row = 2 * 2 * 32 * 4                    # [k | v] on 2 heads of 32, f32
    page, slot = 8 * 2 * row, 3 * 8 * row   # 2 full layers; 3 rings of 8
    assert eng.page_bytes() == page == model.page_bytes(8)
    assert eng.slot_state_bytes() == slot == model.slot_state_bytes()
    assert eng.cache_bytes(state) == 21 * page + 4 * slot
    assert sorted(state.arrays) == ["kv", "swa_kv"]
    _f, _lg, kv, _t = eng.prefill(np.arange(11)[None])
    state = eng.insert_slot(state, kv, 2)
    assert eng.resident_cache_bytes(state) == 2 * page + slot
    # at the published sizes: 8 KB a token in pages, 6 MB a slot in rings
    big = LM.build_model(_load(rehearsal=False))
    assert big.page_bytes(64) == 64 * 8192
    assert big.slot_state_bytes() == 3 * 512 * 4096 == 6291456


def _metric(name):
    total = 0.0
    for line in global_registry().render_prometheus().splitlines():
        if line.startswith(name) and line[len(name):len(name) + 1] in " {":
            total += float(line.rsplit(" ", 1)[1])
    return total


def test_pipeline_spans_gauges_and_the_log_line(family, caplog):
    """Through ``GenerationPipeline``: span ``decode_step`` carries the
    step's ``window_rows`` (sum over active slots of min(context, window))
    and ``cache_bytes`` (pages in use x page bytes + active slots x ring
    bytes) beside ``live_tokens``; the two gauges read this model's pages
    and rings; the log line names each kind's heads, rotation and window."""
    cfg, _model, params = family
    fresh = LM.build_model(cfg)
    eng = _engine(fresh, params, cfg)
    sink = reset_global_trace_sink(65536)
    with caplog.at_level(logging.INFO,
                         logger="deeplearning4j_tpu.models.hybrid"):
        with GenerationPipeline(eng, slots=3, max_new_tokens=12,
                                cache_pages=30) as gp:
            out = gp.generate(np.arange(1, 4, dtype=np.int32),
                              max_new_tokens=12)
            assert len(out) == 12
            assert _metric("dl4j_decode_slot_state_bytes") \
                == 3 * eng.slot_state_bytes() > 0
            assert _metric("dl4j_decode_page_pool_bytes") \
                == 31 * eng.page_bytes() > 0
    steps = [s for s in sink.spans() if s.name == "decode_step"]
    assert len(steps) == 11
    # a prompt of 3: the step that writes position p reads p + 1 rows of a
    # full layer and min(p + 1, 8) of a ring
    assert [s.attrs["live_tokens"] for s in steps] == list(range(4, 15))
    assert [s.attrs["window_rows"] for s in steps] == [
        min(n, 8) for n in range(4, 15)]
    # the bucket's two pages are the slot's until position 16
    assert {s.attrs["cache_bytes"] for s in steps} == {
        2 * eng.page_bytes() + eng.slot_state_bytes()}
    said = [r.getMessage() for r in caplog.records
            if r.getMessage().startswith("layer kinds:")]
    assert 2 <= len(said) <= 4
    assert said[0] == (
        "layer kinds: gqa+dense swa+moe swa+moe swa+moe gqa+moe: experts "
        "swiglu, 4 of 16 a token, 16 held from 0; gqa 6 heads on 2, yarn x8 "
        "from 64 theta 100 on 16 of 32 x1.2079, gated; swa 8 heads on 2, "
        "plain theta 10000 on 32 of 32, gated, window 8")


def test_a_model_without_a_window_reports_no_window_rows():
    cfg = _load("nemotron-3-super-120b-a12b-ep4share.json",
                compute_dtype="float32", param_dtype="float32")
    model, params = NM.build_model(cfg), NM.make_weights(cfg, 3)
    sink = reset_global_trace_sink(65536)
    with GenerationPipeline(_engine(model, params, cfg), slots=2,
                            max_new_tokens=4, cache_pages=20) as gp:
        gp.generate(np.arange(1, 6, dtype=np.int32), max_new_tokens=4)
    steps = [s for s in sink.spans() if s.name == "decode_step"]
    assert steps and all(s.attrs["window_rows"] == 0 for s in steps)
    assert all(s.attrs["cache_bytes"] > 0 for s in steps)


# ------------------------------------------------ the full kind's kernel
def _wide(dtype=jnp.float32):
    """One gated, YaRN-rotated full layer of 6 heads on 2 key/value heads of
    128 (the kernel reads heads in whole tiles of 128 lanes)."""
    cfg = HybridConfig(
        vocab_size=64, d_model=64, layers=(LayerSpec("gqa", "dense"),),
        max_len=96, gqa_heads=6, gqa_kv_heads=2, gqa_head_dim=128,
        gqa_rope=Rope(theta=100.0, dims=64, amplitude=1.2, factor=8.0,
                      original=64, beta_fast=2.0, beta_slow=1.0),
        gqa_gated=True, dense_ff=64, dtype=dtype, param_dtype=dtype)
    model = HybridLM(cfg)
    p = model.init_params(jax.random.key(6))["blocks"][0]["mixer"]
    return model, jax.tree.map(lambda a: (10 * a).astype(dtype), p)


def _step(positions, dtype=jnp.float32, P=8, pages=12):
    slots = len(positions)
    trash = slots * pages
    ks = jax.random.split(jax.random.key(0), 2)
    pool = jax.random.normal(ks[0], (trash + 1, P, 512)).astype(dtype)
    owned = np.random.default_rng(0).permutation(trash).reshape(slots, pages)
    pos = np.asarray(positions)
    tables = np.where(np.arange(pages)[None, :] <= pos[:, None] // P, owned,
                      trash)
    return (jax.random.normal(ks[1], (slots, 64)).astype(dtype), pool,
            jnp.asarray(tables, jnp.int32), jnp.asarray(pos, jnp.int32),
            trash)


@pytest.mark.parametrize("visit", [1, 4])
@pytest.mark.parametrize("positions", [
    (0, 7, 8, 95), (40, 3, 63, 64), (95, 95, 95, 95)])
def test_kernel_equals_the_gathered_window(positions, visit, monkeypatch):
    """float32: the layer's step through the page walk is the gather
    spelling's to summation noise; a dead entry of a table (the trash page,
    NaN here) is never read; the pool comes back with the step's rows."""
    model, p = _wide()
    h, pool, tables, pos, trash = _step(positions)
    want, pool0 = model._gqa_decode(p, h, pool.at[trash].set(0), tables, pos,
                                    8)
    assert model.attention_backend == {"gqa": ("gather", "on cpu")}
    monkeypatch.setattr(hybrid, "_on_tpu", lambda: True)
    monkeypatch.setattr(hybrid, "GATHER_VIEW_BYTES", 0)
    monkeypatch.setattr(pla, "GROUPED_VISIT_BYTES", visit * 8 * 512 * 4)
    got, pool1 = model._gqa_decode(p, h, pool.at[trash].set(jnp.nan), tables,
                                   pos, 8)
    assert model.attention_backend["gqa"][0] == "paged-grouped"
    assert not bool(jnp.isnan(got).any())
    assert float(jnp.max(jnp.abs(got - want))) < 2e-5 * max(
        1.0, float(jnp.max(jnp.abs(want))))
    live = np.asarray(tables).reshape(-1) != trash
    np.testing.assert_array_equal(
        np.asarray(pool1)[np.asarray(tables).reshape(-1)[live]],
        np.asarray(pool0)[np.asarray(tables).reshape(-1)[live]])


def test_kernel_in_bfloat16_is_the_gather_in_bfloat16(monkeypatch):
    """16 query rows a key/value head (a bfloat16 tile) where 3 are real."""
    model, p = _wide(jnp.bfloat16)
    h, pool, tables, pos, trash = _step((5, 33, 64, 95), jnp.bfloat16)
    want, _ = model._gqa_decode(p, h, pool.at[trash].set(0), tables, pos, 8)
    monkeypatch.setattr(hybrid, "_on_tpu", lambda: True)
    monkeypatch.setattr(hybrid, "GATHER_VIEW_BYTES", 0)
    got, _ = model._gqa_decode(p, h, pool, tables, pos, 8)
    assert pla.grouped_query_rows(3, 2) == 16 and pla.grouped_query_rows(
        3, 4) == 8 and pla.grouped_query_rows(16, 2) == 16
    err = float(jnp.max(jnp.abs(got.astype(jnp.float32)
                                - want.astype(jnp.float32))))
    # one bfloat16 rounding of outputs of order 1
    assert err < 2e-2 * max(1.0, float(jnp.max(jnp.abs(want))))


def test_the_choice_and_its_reasons(monkeypatch):
    args = (64, 48, 8, 128, 64, 112, 2)     # the cell's decode step
    assert hybrid.grouped_attention_backend(*args) == ("gather", "on cpu")
    monkeypatch.setattr(hybrid, "_on_tpu", lambda: True)
    assert hybrid.grouped_attention_backend(*args) == (
        "paged-grouped", "live pages of 64 rows of 2048 read where they lie")
    why = lambda *a: hybrid.grouped_attention_backend(*a)[1]   # noqa: E731
    assert "128 lanes" in why(64, 48, 8, 64, 64, 112, 2)
    assert "8 rows" in why(64, 48, 8, 128, 12, 112, 2)
    # the fourth configuration's layer (32 heads on 2, 5,120 positions): its
    # view is 320 MiB, and it keeps the gather it was measured with
    assert hybrid.grouped_attention_backend(64, 32, 2, 128, 64, 80, 2) == (
        "gather", "the view of every slot's window is 320 MiB")
    assert "VMEM" in why(64, 48, 8, 128, 4096, 4, 2)


def test_refusals_of_the_grouped_entry():
    q = jnp.zeros((2, 2, 3, 128))
    pool = jnp.zeros((5, 8, 512))
    t, pos = jnp.zeros((2, 2), jnp.int32), jnp.zeros((2,), jnp.int32)
    pla.paged_grouped_attention(q, pool, t, pos, 1.0)
    for bad in ((q, pool[..., :256], t, pos), (q, pool.astype(jnp.bfloat16),
                                               t, pos),
                (q, pool, t[:1], pos), (q, pool, t, pos[:1])):
        with pytest.raises(ValueError, match="not one paged layer"):
            pla.paged_grouped_attention(*bad, 1.0)


# ------------------------------------------ the window kind's page walk
def _window(dtype=jnp.float32, window=24):
    """One gated, rotated window layer of 6 heads on 2 key/value heads of
    128 over a ring of ``window`` rows."""
    cfg = HybridConfig(
        vocab_size=64, d_model=64, layers=(LayerSpec("swa", "dense"),),
        max_len=256, gqa_kv_heads=2, gqa_head_dim=128, swa_heads=6,
        swa_window=window, swa_rope=Rope(theta=1e4, dims=128),
        swa_gated=True, dense_ff=64, dtype=dtype, param_dtype=dtype)
    model = HybridLM(cfg)
    p = model.init_params(jax.random.key(6))["blocks"][0]["mixer"]
    return model, jax.tree.map(lambda a: (10 * a).astype(dtype), p)


def _ring_step(model, positions, dtype=jnp.float32):
    c = model.config
    ks = jax.random.split(jax.random.key(0), 2)
    ring = jax.random.normal(
        ks[0], (len(positions), c.swa_window, c.gqa_kv_row)).astype(dtype)
    return (jax.random.normal(ks[1], (len(positions), 64)).astype(dtype),
            ring, jnp.asarray(positions, jnp.int32))


#: a window of 24 rows in pages of 8: slots younger than the window (one
#: inside its first page, one at a page's last row, one at a page's first),
#: exactly as old (position 23 fills the last row), one row past it (24
#: overwrites row 0) and several turns past it
AGES = (0, 7, 8, 23, 24, 25, 100, 191)


@pytest.mark.parametrize("visit", [1, 2, 3])
def test_the_walk_over_a_ring_equals_the_two_einsums(visit, monkeypatch):
    """float32: the window layer's step through the page walk is the XLA
    spelling's to summation noise, slots of every age in ONE batch; a page
    of a young slot's ring beyond its position (NaN here) is never fetched;
    the ring comes back with the step's row at ``position % window`` and
    nothing else changed."""
    model, p = _window()
    h, ring, pos = _ring_step(model, AGES)
    want, ring0 = model._swa_decode(p, h, ring, pos)
    assert model.attention_backend == {"swa": ("xla", "on cpu")}
    monkeypatch.setattr(hybrid, "_on_tpu", lambda: True)
    monkeypatch.setattr(hybrid, "RING_PAGE_ROWS", 8)
    monkeypatch.setattr(pla, "GROUPED_VISIT_BYTES", visit * 8 * 512 * 4)
    beyond = (np.arange(24)[None, :] // 8
              > np.minimum(np.asarray(AGES), 23)[:, None] // 8)
    assert beyond.sum(axis=1).tolist() == [16, 16, 8, 0, 0, 0, 0, 0]
    dead = jnp.where(beyond[:, :, None], jnp.nan, ring)
    got, ring1 = model._swa_decode(p, h, dead, pos)
    assert model.attention_backend == {"swa": (
        "paged-grouped",
        "live pages of 8 rows of 512 of a ring of 24 read where they lie")}
    assert not bool(jnp.isnan(got).any())
    assert float(jnp.max(jnp.abs(want))) > 1.0
    assert float(jnp.max(jnp.abs(got - want))) < 2e-5 * float(
        jnp.max(jnp.abs(want)))
    # the ring: the step's row where it belongs, every other row as it came
    at = np.asarray(AGES) % 24
    ring0, ring1 = np.asarray(ring0), np.asarray(ring1)
    np.testing.assert_array_equal(ring1[~beyond], ring0[~beyond])
    assert np.isnan(ring1[beyond]).all()
    kept = np.ones((len(AGES), 24), bool)
    kept[np.arange(len(AGES)), at] = False
    np.testing.assert_array_equal(ring0[kept], np.asarray(ring)[kept])
    assert np.abs(ring0[~kept] - np.asarray(ring)[~kept]).max() > 0.5


def test_the_walk_over_a_ring_in_bfloat16_is_the_einsums_in_bfloat16(
        monkeypatch):
    """16 query rows a key/value head (a bfloat16 tile) where 3 are real;
    one page of 16 rows a visit."""
    model, p = _window(jnp.bfloat16, window=32)
    h, ring, pos = _ring_step(model, (5, 31, 32, 33, 500), jnp.bfloat16)
    want, ring0 = model._swa_decode(p, h, ring, pos)
    monkeypatch.setattr(hybrid, "_on_tpu", lambda: True)
    monkeypatch.setattr(hybrid, "RING_PAGE_ROWS", 16)
    monkeypatch.setattr(pla, "GROUPED_VISIT_BYTES", 16 * 512 * 2)
    got, ring1 = model._swa_decode(p, h, ring, pos)
    assert model.attention_backend["swa"][0] == "paged-grouped"
    np.testing.assert_array_equal(np.asarray(ring1, np.float32),
                                  np.asarray(ring0, np.float32))
    err = float(jnp.max(jnp.abs(got.astype(jnp.float32)
                                - want.astype(jnp.float32))))
    # one bfloat16 rounding of outputs of order 1
    assert err < 2e-2 * max(1.0, float(jnp.max(jnp.abs(want))))


def test_the_rings_choice_and_its_reasons(monkeypatch):
    """Decided from what a trace can see, the reason in the line. The two
    cells' window layers (64 slots a step): ``laguna-codegen``'s 64 heads on
    8 key/value heads of 128 and ``phi4flash-reasoning``'s 40 heads of 64 on
    20, which the products see as 4 query rows on each of 10 pairs of 128
    (``HybridConfig.attention_shape``); rings of 512 rows, bfloat16."""
    laguna, phi = (64, 8, 128, 512, 2), (40, 10, 128, 512, 2)
    for args in (laguna, phi):
        assert hybrid.ring_attention_backend(*args) == ("xla", "on cpu")
    monkeypatch.setattr(hybrid, "_on_tpu", lambda: True)
    assert hybrid.ring_attention_backend(*laguna) == (
        "paged-grouped",
        "live pages of 64 rows of 2048 of a ring of 512 read where they lie")
    assert hybrid.ring_attention_backend(*phi) == (
        "paged-grouped",
        "live pages of 64 rows of 2560 of a ring of 512 read where they lie")
    # what reaches the chooser is what the configurations publish
    for name, adapter, args in (
            (FILE, LM, laguna),
            ("phi-4-mini-flash-reasoning.json",
             harness.load_module("models", "phi4flash.py"), phi)):
        c = adapter.build_model(_load(name, rehearsal=False)).config
        g, k, hd = c.attention_shape("swa")
        assert (g * k, g, hd, c.swa_window,
                jnp.dtype(c.dtype).itemsize) == args
    why = lambda *a: hybrid.ring_attention_backend(*a)[1]   # noqa: E731
    # 40 heads of 64 that are NOT pairs: a head is half a tile
    assert why(40, 20, 64, 512, 2) == ("a head of 64 is not whole tiles of "
                                       "128 lanes")
    assert "whole pages of 64 rows" in why(64, 8, 128, 500, 2)
    assert "whole pages of 12 rows" in why(64, 8, 128, 12, 2)
    # a window of 8 rows is one page of its own
    assert hybrid.ring_attention_backend(6, 2, 128, 8, 4)[0] \
        == "paged-grouped"
    monkeypatch.setattr(hybrid, "RING_PAGE_ROWS", 4096)
    assert why(64, 8, 128, 8192, 2) == (
        "a visit of 4096 rows of 2048 is more than the kernel's VMEM")


# ---------------------------- the fourth configuration's layer, bit for bit
#: what the parent commit (8af3323, PR 42) gives for the grouped-query layer
#: of ``nemotron-3-super-120b-a12b-ep4share`` at its rehearsal sizes in its
#: own bfloat16, weights of seed 7, inputs of ``jax.random.key(23)``: the
#: digest of the layer's two programs' text lowered for the TPU (a program
#: that is letter for letter the parent's computes the parent's numbers on
#: any machine) and of the bytes this sandbox's CPU gave (another CPU may
#: add in another order: there the stored last rows are held within one
#: bfloat16 rounding of outputs of order 1e-3). No rotation, gate or window:
#: every new field defaults to what this layer gets today.
PARENT = {
    "full_text":
    "f9e56b6d1a33e0ef544c96d5654b52d9cabb28841c1205616b3fcb3331ca1faa",
    "decode_text":
    "e3e4de6159e5638f9d176bf93e8c8c145faf03f25e9e2fff6204f3ec517d872a",
    "full_y":
    "4aff35d70d824cf805158ac1b4b15857167a1ad301edd0553ca433108e4fe15b",
    "full_row":
    "441bfafc0ce4fbc336b2fb8f060532308738078334d8e6ba2228f28576982c74",
    "decode_y":
    "1a68e9635335850cf8cc3c33bdefbc34af70456d6d87755ac7642224242f37b5",
    "decode_pool":
    "7594534e223be7542a2c3eb91560257cf12292eba82d8d9a7e3173fd1eafae57",
    "full_y_last": [
        0.0003261566162109375, 0.00010776519775390625,
        -3.4809112548828125e-05, 0.000362396240234375,
        -5.054473876953125e-05, 0.00013446807861328125,
        -0.0002613067626953125, 0.000396728515625],
    "decode_y_last": [
        -2.288818359375e-05, 7.772445678710938e-05, -0.00057220458984375,
        -0.00128936767578125, 0.000370025634765625, -0.0004520416259765625,
        5.888938903808594e-05, 0.0003833770751953125],
}


def test_the_fourth_configurations_gqa_layer_is_the_parents():
    cfg = _load("nemotron-3-super-120b-a12b-ep4share.json")
    model = NM.build_model(cfg)
    c = model.config
    assert (c.gqa_rope, c.gqa_gated, c.swa_window) == (None, False, None)
    layer = [i for i, k in enumerate(cfg["layers_run"]) if k == "*"][0]
    p = NM.make_weights(cfg, 7)["blocks"][layer]["mixer"]
    assert sorted(p) == ["w_kv", "w_o", "w_q"]
    ks = jax.random.split(jax.random.key(23), 3)
    h = jax.random.normal(ks[0], (2, 40, c.d_model)).astype(c.dtype)
    full = jax.jit(lambda p, h: model._gqa_full(p, h))
    y, row = full(p, h)
    P, pages, slots = 8, 6, 3
    pool = jax.random.normal(
        ks[1], (slots * pages + 1, P, c.gqa_kv_row)).astype(c.dtype)
    tables = jnp.asarray(np.random.default_rng(0).permutation(
        slots * pages).reshape(slots, pages), jnp.int32)
    pos = jnp.asarray([3, 17, 47], jnp.int32)
    hd = jax.random.normal(ks[2], (slots, c.d_model)).astype(c.dtype)
    dec = jax.jit(lambda p, h, pool, t, pos: model._gqa_decode(
        p, h, pool, t, pos, P))
    yd, pool2 = dec(p, hd, pool, tables, pos)

    def text(f, *args):
        return hashlib.sha256(f.trace(*args).lower(
            lowering_platforms=("tpu",)).as_text().encode()).hexdigest()

    assert text(full, p, h) == PARENT["full_text"]
    assert text(dec, p, hd, pool, tables, pos) == PARENT["decode_text"]
    got = {"full_y": y, "full_row": row, "decode_y": yd,
           "decode_pool": pool2}
    same = all(hashlib.sha256(np.asarray(a).tobytes()).hexdigest()
               == PARENT[k] for k, a in got.items())
    if not same:        # another CPU's order of additions
        np.testing.assert_allclose(np.asarray(y[1, -1, :8], np.float32),
                                   PARENT["full_y_last"], atol=8e-6)
        np.testing.assert_allclose(np.asarray(yd[2, :8], np.float32),
                                   PARENT["decode_y_last"], atol=8e-6)
