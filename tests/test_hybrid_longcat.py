"""HybridLM's layers that list their parts (the double layer with a
shortcut-connected expert layer), rotated latent attention behind a query
bottleneck, and the softmax router with identity experts of
``routed_experts_ffn``, against the plain reference
``perfbench/reference/longcat_flash.py`` (float32, expanded attention,
rotation by reshaped pairs, a loop over held experts) at the configuration's
``rehearsal`` sizes: 2 double layers, 16 experts of which 4 are held + 8
identity experts, 4 a token, seeded weights.

Tolerances, each with its reason. Program and reference both compute in
float32 here (the configuration's dtypes are overridden), so what is left is
the order of the additions: attention over pages in the absorbed form against
the expanded form, a grouped product against a loop over experts, the
rotation spelled with lane rolls against reshaped pairs. Logits are of order
1 and the gaps read 1e-6; ``TOL`` = 2e-5 leaves room for another CPU's vector
width and is a hundred times under what bfloat16 projections give at these
sizes. A description with the shortcut moved reads 1e-2 and more.
"""
import hashlib
import json
import logging
import os
import re
import sys

import jax
import jax.numpy as jnp
import numpy as np
import pytest
from jax import lax

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
if ROOT not in sys.path:
    sys.path.insert(0, ROOT)

from perfbench import harness  # noqa: E402

from deeplearning4j_tpu.models import hybrid  # noqa: E402
from deeplearning4j_tpu.models.generation import DecodeEngine  # noqa: E402
from deeplearning4j_tpu.observability.registry import (  # noqa: E402
    global_registry)
from deeplearning4j_tpu.observability.tracing import (  # noqa: E402
    reset_global_trace_sink)
from deeplearning4j_tpu.parallel.generation import (  # noqa: E402
    GenerationPipeline)
from deeplearning4j_tpu.parallel.moe import (  # noqa: E402
    RoutedExpertsConfig, routed_experts_ffn)

TOL = 2e-5
LM = harness.load_module("models", "longcat_flash.py")
REF = harness.load_module("reference", "longcat_flash.py")
FILE = "longcat-flash-omni-ep32share.json"


def _load(name, **over):
    with open(os.path.join(ROOT, "perfbench", "configs", name)) as f:
        cfg = json.load(f)
    cfg.update(cfg["rehearsal"])
    cfg.update(over)
    return cfg


def _cfg(**over):
    """The rehearsal sizes in float32, and the router's weights at the
    published scaling factor (the rehearsal run's own is smaller, so that its
    bfloat16 comparison does not measure one changed choice of 24): an
    expert layer that is a large part of every layer's result."""
    return _load(FILE, **{"compute_dtype": "float32",
                          "param_dtype": "float32",
                          "routed_scaling_factor": 6, **over})


@pytest.fixture(scope="module")
def family():
    cfg = _cfg()
    return cfg, LM.build_model(cfg), LM.make_weights(cfg, 3)


def _engine(family, **kw):
    cfg, model, params = family
    return DecodeEngine(model, params, max_len=cfg["n_positions"],
                        prefill_buckets=[16, 32, 64], page_tokens=8, **kw)


# ------------------------------------------------------- the description
def test_layer_description_lists_the_double_layers_parts(family):
    cfg, model, params = family
    layer = model.config.layers[0]
    assert len(model.config.layers) == 2 and layer.mixer is layer.ffn is None
    assert [(p.kind, p.name, p.norm, p.lands) for p in layer.parts] == [
        ("mla", "attn0", "ln_a0", "now"), ("moe", "moe", "ln_f0", "end"),
        ("dense", "ffn0", None, "now"), ("mla", "attn1", "ln_a1", "now"),
        ("dense", "ffn1", "ln_f1", "now")]
    e = model.config.experts
    assert (e.held, e.router_width, e.top_k, e.form, e.score, e.shared,
            e.identity, e.renormalize, e.scale) == (
        (0, 4), 24, 4, "swiglu", "softmax", False, 8, False, 6)
    c = model.config
    assert (c.rope_theta, c.q_lora_rank, c.mla_scale_q_lora,
            c.mla_scale_kv_lora) == (1e7, 48, True, True)
    # four norms a layer, no shared expert, a query bottleneck
    assert sorted(params["blocks"][0]) == [
        "attn0", "attn1", "ffn0", "ffn1", "ln_a0", "ln_a1", "ln_f0", "ln_f1",
        "moe"]
    assert "shared" not in params["blocks"][0]["moe"]
    assert "w_q" not in params["blocks"][0]["attn0"]
    # the program's own initialiser builds the same tree
    own = jax.eval_shape(model.init_params, jax.random.key(0))
    assert jax.tree.structure(own) == jax.tree.structure(params)
    assert jax.tree.leaves(jax.tree.map(
        lambda a, b: a.shape == b.shape, own, params)).count(False) == 0


def test_a_mixer_and_a_feed_forward_are_two_parts_with_their_own_norms():
    """What the two accepted families write stays what it was."""
    spec = hybrid.LayerSpec("kda", "moe")
    assert [(p.kind, p.name, p.norm, p.lands) for p in spec.parts] == [
        ("kda", "mixer", "ln1", "now"), ("moe", "ffn", "ln2", "now")]
    assert [(p.kind, p.name, p.norm) for p in
            hybrid.LayerSpec(None, "dense").parts] == [
        ("dense", "ffn", "ln2")]
    assert hybrid.HybridConfig(8, 8, [spec], 8,
                               RoutedExpertsConfig(4, 2, (0, 2))
                               ).rope_theta is None


P = hybrid.Part


@pytest.mark.parametrize("parts", [
    (P("mla", "a", None),),                             # nothing to read yet
    (P("mla", "a", "n"), P("dense", "a", "m")),         # one name twice
    (P("mla", "a", "n"), P("dense", "b", "n")),         # one gain twice
    (P("mla", "a", "n"), P("dense", "b", "a")),         # a gain's name taken
    (P("lstm", "a", "n"),), (P("moe", "a", "n", "later"),)])
def test_layer_spec_refuses_parts_it_cannot_walk(parts):
    with pytest.raises(ValueError, match="unknown layer"):
        hybrid.LayerSpec(parts=parts)
    with pytest.raises(ValueError, match="unknown layer"):
        hybrid.LayerSpec("mla", None, parts=(P("mla", "a", "n"),))


def test_a_blocks_two_latent_pools_are_two_entries_of_one_leaf(family):
    cfg, model, _ = family
    assert [(leaf.name, leaf.paged, n) for leaf, n in model.cache_leaves] \
        == [("latent", True, 4)]
    assert model._rank == [[0, 0, 0, 1, 1], [2, 1, 2, 3, 3]]
    cache = model.new_paged_cache(4, 9, 8)
    assert sorted(cache) == ["latent"] and len(cache["latent"]) == 4
    assert cache["latent"][0].shape == (9, 8, 128)     # 32 + 8 padded to 128
    # the published widths: 8 attention sublayers of 640-wide rows
    with open(os.path.join(ROOT, "perfbench", "configs", FILE)) as f:
        full = LM.build_model(json.load(f))
    assert full.config.latent_row == 640
    assert full.page_bytes(64) == 64 * 8 * 640 * 2 == 64 * 10240
    assert full.slot_state_bytes() == 0
    assert full.step_stats[-1] == "pairs_zero" and len(full.step_stats) == 5


# ------------------------------------------------------ against the reference
def test_full_forward_matches_reference(family):
    cfg, model, params = family
    toks = jax.random.randint(jax.random.key(1), (2, 45), 0,
                              cfg["vocab_size"])
    got = jax.jit(model.apply)(params, toks)
    want = REF.logits(params, toks, cfg)
    assert float(jnp.max(jnp.abs(want))) > 0.3
    assert float(jnp.max(jnp.abs(got - want))) < TOL


def _moved(cfg, parts):
    c = LM.build_model(cfg).config
    c.layers = (hybrid.LayerSpec(parts=tuple(P(*p) for p in parts)),) \
        * len(c.layers)
    return hybrid.HybridLM(c)


def test_the_shortcut_reads_h0_and_lands_behind_ffn1(family):
    """The same weights under descriptions that move the expert layer's
    reading or its landing are another function: the reference's equations
    pin both ends of the shortcut."""
    cfg, _model, params = family
    toks = jax.random.randint(jax.random.key(2), (2, 29), 0,
                              cfg["vocab_size"])
    want = REF.logits(params, toks, cfg)
    a0, moe, f0, a1, f1 = LM.PARTS
    for parts, same in (
            (LM.PARTS, True),
            # the dense part first and the experts on the rows it read: the
            # same mathematics, written the other way round
            ((a0, ("dense", "ffn0", "ln_f0", "now"),
              ("moe", "moe", None, "end"), a1, f1), True),
            # added at once: the second attention sees the experts' result
            ((a0, ("moe", "moe", "ln_f0", "now"), f0, a1, f1), False),
            # read behind the first feed-forward, through the second
            # attention's rows
            ((a0, ("dense", "ffn0", "ln_f0", "now"), a1,
              ("moe", "moe", None, "end"), f1), False)):
        got = jax.jit(_moved(cfg, parts).apply)(params, toks)
        gap = float(jnp.max(jnp.abs(got - want)))
        assert (gap < TOL) if same else (gap > 500 * TOL), (parts, gap)


def test_joins_and_leaves_mid_stream_at_different_positions(family):
    """Slot 1 decodes 40 steps from a prompt of 21 tokens (bucket 32,
    padded). Slot 3 joins at step 5 (37 tokens, bucket 64) and leaves at
    step 17; slot 0 joins at step 9 (9 tokens, bucket 16) and leaves at 25;
    another prompt takes slot 3 again at step 21. Pages hold 8 tokens, so
    every run crosses page boundaries, and the slots stand at different
    positions in every step: a key row rotated by another position than its
    own, or a query by another slot's, shows in the LOGITS of every occupied
    slot, which are held to the reference's full forward over prompt +
    served tokens, step by step."""
    cfg, _model, params = family
    eng = _engine(family)
    slots = 4
    state = eng.new_state(slots)
    rng = np.random.default_rng(0)
    plan = [  # (join step, leave step, slot, prompt length)
        (0, 40, 1, 21), (5, 17, 3, 37), (9, 25, 0, 9), (21, 40, 3, 13)]
    runs = [{"slot": s, "join": a, "leave": b, "got": [],
             "seq": list(rng.integers(0, cfg["vocab_size"], n)), "n": n}
            for a, b, s, n in plan]
    tokens = np.zeros(slots, np.int32)
    positions = np.zeros(slots, np.int32)
    zero = routed = 0
    for step in range(40):
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
        assert len({int(positions[r["slot"]]) for r in live}) == len(live)
        nxt, lg, state = eng.decode(state, tokens, positions, step)
        nxt, lg = np.asarray(nxt), np.asarray(lg)
        counts = eng.step_counts(nxt, slots)
        # a free slot routes to no expert: 2 expert layers x 4 a token
        assert list(counts) == list(eng.model.step_stats)
        assert counts["pairs_routed"] == 8 * len(live)
        assert counts["experts_touched"] <= counts["pairs_held"]
        assert counts["pairs_held"] + counts["pairs_zero"] \
            <= counts["pairs_routed"]
        zero += counts["pairs_zero"]
        routed += counts["pairs_routed"]
        for r in live:
            s = r["slot"]
            r["got"].append(lg[s])
            tokens[s] = nxt[s]
            positions[s] += 1
            r["seq"].append(int(nxt[s]))
    assert 0.15 < zero / routed < 0.55          # 8 of 24 outputs: a third
    for r in runs:
        full = np.asarray(r["seq"][:-1], np.int32)
        want = np.asarray(REF.logits(params, full[None], cfg))[0]
        mine = np.stack(r["got"])
        assert mine.shape[0] == r["leave"] - r["join"] + 1 >= 13
        assert np.abs(mine - want[r["n"] - 1:]).max() < TOL, r["slot"]


def test_the_convenience_loop_returns_the_references_logits(family):
    cfg, _model, params = family
    eng = _engine(family)
    assert eng.warm(3) == [16, 32, 64]
    prompts = np.random.default_rng(1).integers(0, cfg["vocab_size"],
                                                (2, 11))
    toks, steps = eng.generate(prompts, 6, return_logits=True)
    full = np.concatenate([prompts, toks[:, :-1]], axis=1)
    want = np.asarray(REF.logits(params, full, cfg))[:, 10:]
    assert np.abs(np.stack(steps, axis=1) - want).max() < TOL


# ------------------------------------------------------- the latent attention
def test_rotation_turns_adjacent_pairs_and_scores_see_only_the_distance():
    x = jax.random.normal(jax.random.key(5), (9, 3, 8))
    pos = jnp.arange(9) * 7 + 2
    got = hybrid._rope(x, pos[:, None], 1e4)
    # the reference's spelling, rows at positions 0 .. T - 1: take every 7th
    # of a longer sequence that starts 2 rows earlier
    long = jnp.zeros((65, 3, 8)).at[2::7].set(x)
    want = REF._rotate(long, 1e4)[2::7]
    assert float(jnp.max(jnp.abs(got - want))) < 1e-5
    assert float(jnp.max(jnp.abs(got[0] - x[0]))) > 0.1     # position 2
    q, k = x[:, 0], x[:, 1]
    near = jnp.sum(hybrid._rope(q, pos, 1e4)[5] * hybrid._rope(k, pos, 1e4)[3])
    far = jnp.sum(hybrid._rope(q[5], pos[5] + 1000, 1e4)
                  * hybrid._rope(k[3], pos[3] + 1000, 1e4))
    assert abs(float(near - far)) < 1e-4
    # lengths are kept: a rotation
    assert float(jnp.max(jnp.abs(jnp.sum(got * got, -1)
                                 - jnp.sum(x * x, -1)))) < 1e-5


def test_absorbed_decode_over_pages_is_the_expanded_form(family):
    """The first layer's second attention alone: the expanded form over 19
    rows against the one-row absorbed form fed the same rows, at their
    positions, through a paged pool of latent rows whose pages lie out of
    order. The pool keeps [c | rot(k_r) | 0]."""
    cfg, model, params = family
    p = params["blocks"][0]["attn1"]
    c = model.config
    T, pt = 19, 8
    h = jax.random.normal(jax.random.key(7), (1, T, cfg["hidden_size"]))
    want, rows = model._mla_full(p, h)
    ref = REF._mla(h[0], p, cfg, False)
    assert float(jnp.max(jnp.abs(want[0] - ref))) < TOL
    pool = jnp.zeros((5, pt, c.latent_row))
    tables = jnp.asarray([[2, 0, 3, 4]], jnp.int32)     # page 4 = trash
    for t in range(T):
        y, pool = model._mla_decode(p, h[:, t], pool, tables,
                                    jnp.asarray([t], jnp.int32), pt)
        assert float(jnp.max(jnp.abs(y - want[:, t]))) < TOL
    got_rows = pool[tables[0]].reshape(-1, pool.shape[-1])[:T]
    assert float(jnp.max(jnp.abs(got_rows - rows[0]))) < TOL
    assert float(jnp.max(jnp.abs(rows[..., c.latent_dim:]))) == 0.0
    # a row written at another position than its own is another row
    _y, other = model._mla_decode(p, h[:, 3], jnp.zeros_like(pool), tables,
                                  jnp.asarray([4], jnp.int32), pt)
    R = c.kv_lora_rank
    assert float(jnp.max(jnp.abs(other[2, 4, :R] - rows[0, 3, :R]))) < TOL
    assert float(jnp.max(jnp.abs(other[2, 4, R:c.latent_dim]
                                 - rows[0, 3, R:c.latent_dim]))) > 1e-3


@pytest.mark.parametrize("field", ["mla_scale_q_lora", "mla_scale_kv_lora",
                                   "rope_theta", "q_norm"])
def test_each_term_of_the_latent_attention_shows(family, field):
    """A bottleneck's scale, the rotation or the query bottleneck's norm left
    out moves the layer's output by far more than ``TOL``."""
    cfg, model, params = family
    p = params["blocks"][1]["attn0"]
    h = jax.random.normal(jax.random.key(8), (1, 17, cfg["hidden_size"]))
    want, _ = model._mla_full(p, h)
    if field == "q_norm":
        other, p = model, dict(p, q_norm=jnp.ones_like(p["q_norm"]))
    else:
        c = LM.build_model(cfg).config
        setattr(c, field, None if field == "rope_theta" else False)
        other = hybrid.HybridLM(c)
    got, _ = other._mla_full(p, h)
    assert float(jnp.max(jnp.abs(got - want))) > 100 * TOL


# ------------------------------------------------------------ the experts
def _expert_layer(cfg, key, **over):
    k1, k2 = jax.random.split(key)
    return {**LM._moe_first(k1, cfg), **LM._moe_second(k2, cfg), **over}


def _ec(cfg, first, count):
    return RoutedExpertsConfig(
        cfg["router_width"], cfg["moe_topk"], (first, count),
        cfg["routed_scaling_factor"], renormalize=False, score="softmax",
        shared=False, identity=cfg["zero_expert_num"])


def test_the_shares_add_up_with_the_identity_part_counted_once():
    """The routed parts that ``held = (0, 4)``, ``(4, 4)``, ``(8, 4)`` and
    ``(12, 4)`` give for the 16 experts, plus the identity experts' part,
    which every chip computes for its own tokens and which therefore counts
    ONCE, equal the uncut layer of the reference (all 16 held)."""
    cfg = _cfg(n_routed_experts=16)
    full = _expert_layer(cfg, jax.random.key(11))
    x = jax.random.normal(jax.random.key(12), (53, cfg["hidden_size"]))
    want = REF._moe(x, full, cfg, False)
    # the identity part alone: the reference with experts that return zero
    same = REF._moe(x, dict(full, w_down=jnp.zeros_like(full["w_down"])),
                    cfg, False)
    assert float(jnp.max(jnp.abs(same))) > 0.01
    total = same
    held = zero = 0
    for first in (0, 4, 8, 12):
        part = dict(full, w_gu=full["w_gu"][first:first + 4],
                    w_down=full["w_down"][first:first + 4])
        y, stats = routed_experts_ffn(part, x, _ec(cfg, first, 4))
        total = total + (y - same)
        held += int(stats[1])
        zero = int(stats[4])
        # the reference, given the same share, agrees with the program
        share = REF._moe(x, part, _cfg(n_routed_experts=4,
                                       experts_held_first=first), False)
        assert float(jnp.max(jnp.abs(y - share))) < TOL
    # every pair fell on one share or on an identity expert
    assert held + zero == 53 * 4 and zero > 30 and held > 60
    assert float(jnp.max(jnp.abs(total - want))) < TOL
    assert float(jnp.max(jnp.abs(want - same))) > 0.05


def test_a_token_on_identity_experts_alone_is_its_weighted_self():
    """A selection bias that puts the 8 identity experts first for every
    token: all 4 choices are identity experts, the result is exactly
    ``(sum w) h`` with ``w = 6 softmax(h W_r)`` at the chosen, no row goes
    through the grouped product and no held expert is touched."""
    cfg = _cfg()
    p = _expert_layer(cfg, jax.random.key(13))
    p["b_select"] = p["b_select"].at[16:].set(5.0)
    x = jax.random.normal(jax.random.key(14), (31, cfg["hidden_size"]))
    y, stats = jax.jit(lambda p, x: routed_experts_ffn(
        p, x, _ec(cfg, 0, 4)))(p, x)
    s = jax.nn.softmax(jnp.matmul(x, p["w_router"],
                                  precision=lax.Precision.HIGHEST), -1)
    top = lax.top_k(s[:, 16:], 4)[0]
    want = 6 * jnp.sum(top, -1, keepdims=True) * x
    assert np.asarray(stats).tolist() == [0, 0, 31 * 4, 0, 31 * 4]
    assert float(jnp.max(jnp.abs(y - want))) < 1e-6
    assert float(jnp.max(jnp.abs(y - REF._moe(x, p, cfg, False)))) < 1e-6
    # a masked row (a free slot) routes nowhere, not even to itself
    _y, st = routed_experts_ffn(p, x, _ec(cfg, 0, 4), jnp.arange(31) < 10)
    assert np.asarray(st).tolist() == [0, 0, 40, 0, 40]


def test_a_token_on_absent_experts_alone_adds_nothing():
    """A selection bias that puts experts 8-15 first: every choice is a real
    expert that another chip holds, and nothing is added here."""
    cfg = _cfg()
    p = _expert_layer(cfg, jax.random.key(15))
    p["b_select"] = p["b_select"].at[8:16].set(5.0)
    x = jax.random.normal(jax.random.key(16), (31, cfg["hidden_size"]))
    y, stats = routed_experts_ffn(p, x, _ec(cfg, 0, 4))
    assert np.asarray(stats).tolist() == [0, 0, 31 * 4, 0, 0]
    assert float(jnp.max(jnp.abs(y))) == 0.0
    # held by the chip that has them, the same pairs are all computed
    part = dict(p, w_gu=p["w_gu"] * 1.0, w_down=p["w_down"] * 1.0)
    y8, st8 = routed_experts_ffn(part, x, _ec(cfg, 8, 4))
    assert int(st8[1]) > 31 and int(st8[4]) == 0
    assert float(jnp.max(jnp.abs(y8))) > 0.01


@pytest.mark.parametrize("score, renormalize, shared, identity", [
    ("sigmoid", True, True, 0), ("softmax", True, True, 0),
    ("softmax", False, False, 3), ("sigmoid", False, True, 2),
    ("sigmoid", True, False, 3)])
def test_what_a_router_may_be_is_independent(score, renormalize, shared,
                                             identity):
    """Score function, renormalisation, shared expert and identity experts,
    each on its own field, against the sum written out pair by pair."""
    d, f, E, k, T = 12, 5, 8, 3, 17
    ks = iter(jax.random.split(jax.random.key(3), 12))

    def n(*shape):
        return 0.4 * jax.random.normal(next(ks), shape)

    p = {"w_router": n(d, E), "b_select": 0.1 * n(E), "w_gu": n(3, d, 2 * f),
         "w_down": n(3, f, d), "shared": {"w_gu": n(d, 2 * f),
                                          "w_down": n(f, d)}}
    if not shared:
        del p["shared"]
    x = n(T, d)
    ec = RoutedExpertsConfig(E, k, (1, 3), 1.7, renormalize=renormalize,
                             score=score, shared=shared, identity=identity)
    y, stats = routed_experts_ffn(p, x, ec)
    z = x @ p["w_router"]
    s = jax.nn.sigmoid(z) if score == "sigmoid" else jax.nn.softmax(z, -1)
    _, idx = lax.top_k(s + p["b_select"], k)
    wts = jnp.take_along_axis(s, idx, -1)
    if renormalize:
        wts = wts / wts.sum(-1, keepdims=True)
    wts = 1.7 * wts

    def swiglu(v, w_gu, w_down):
        h = v @ w_gu
        return (jax.nn.silu(h[..., :f]) * h[..., f:]) @ w_down

    want = swiglu(x, **p["shared"]) if shared else jnp.zeros((T, d))
    for t in range(T):
        for j in range(k):
            e = int(idx[t, j])
            if 1 <= e < 4:
                want = want.at[t].add(wts[t, j] * swiglu(
                    x[t], p["w_gu"][e - 1], p["w_down"][e - 1]))
            elif e >= E - identity:
                want = want.at[t].add(wts[t, j] * x[t])
    assert float(jnp.max(jnp.abs(y - want))) < TOL
    assert stats.shape == ((5,) if identity else (4,))
    assert int(stats[1]) == int(jnp.sum((idx >= 1) & (idx < 4)))
    if identity:
        assert int(stats[4]) == int(jnp.sum(idx >= E - identity)) > 0


@pytest.mark.parametrize("kw, match", [
    (dict(score="tanh"), "score"), (dict(identity=8), "identity"),
    (dict(identity=-1), "identity"), (dict(identity=5, held=(2, 2)), "held")])
def test_router_configuration_is_checked(kw, match):
    args = dict(router_width=8, top_k=2, held=(0, 2))
    args.update(kw)
    with pytest.raises(ValueError, match=match):
        RoutedExpertsConfig(**args)
    RoutedExpertsConfig(8, 2, (2, 2), identity=4, score="softmax")


# ---------------------------------------------------- bytes, spans, counters
def _metric(name, **labels):
    total = 0.0
    for line in global_registry().render_prometheus().splitlines():
        if line.startswith(name) and line[len(name):len(name) + 1] in " {" \
                and all(f'{k}="{v}"' in line for k, v in labels.items()):
            total += float(line.rsplit(" ", 1)[1])
    return total


def test_pipeline_spans_counters_and_the_log_line(family, caplog):
    """Through ``GenerationPipeline``: the fifth count of a step is an
    attribute of span ``decode_step`` and a counter of its own, pairs on
    identity experts are no other chip's part, the pool gauge reads all four
    latent pools, and one log line a trace names the layers' parts and the
    router."""
    cfg, model, params = family
    fresh = LM.build_model(cfg)             # nothing said yet
    eng = DecodeEngine(fresh, params, max_len=cfg["n_positions"],
                       prefill_buckets=[16, 32, 64], page_tokens=8)
    assert eng.page_bytes() == 8 * 4 * 128 * 4 and eng.slot_state_bytes() == 0
    sink = reset_global_trace_sink(65536)
    before = {k: _metric("dl4j_moe_pairs_total", held=k) for k in "01"}
    zero0 = _metric("dl4j_moe_zero_pairs_total")
    from deeplearning4j_tpu.observability import span
    with caplog.at_level(logging.INFO,
                         logger="deeplearning4j_tpu.models.hybrid"):
        with GenerationPipeline(eng, slots=3, max_new_tokens=12,
                                cache_pages=30) as gp:
            with span("test_request"):
                out = gp.generate(np.arange(1, 20, dtype=np.int32),
                                  max_new_tokens=12)
            assert len(out) == 12
            assert _metric("dl4j_decode_page_pool_bytes") \
                == 31 * eng.page_bytes() > 0
    steps = [s for s in sink.spans() if s.name == "decode_step"]
    assert len(steps) == 11
    routed = sum(s.attrs["pairs_routed"] for s in steps)
    held = sum(s.attrs["pairs_held"] for s in steps)
    zero = sum(s.attrs["pairs_zero"] for s in steps)
    assert routed == 11 * 2 * 4 and 0 < zero < routed and held + zero <= routed
    assert _metric("dl4j_moe_zero_pairs_total") - zero0 == zero
    assert _metric("dl4j_moe_pairs_total", held="1") - before["1"] == held
    assert _metric("dl4j_moe_pairs_total", held="0") - before["0"] \
        == routed - held - zero
    said = [r.getMessage() for r in caplog.records
            if r.getMessage().startswith("layer kinds:")]
    assert 2 <= len(said) <= 4
    assert said[0] == (
        "layer kinds: mla+[moe]+dense+mla+dense mla+[moe]+dense+mla+dense: "
        "experts swiglu, softmax, 4 of 24 a token: 16 experts + 8 identity, "
        "4 held from 0")


# ------------------------------------ the other families, bit for bit
#: what the parent commit (d725d2b, PR 36) gives at the configurations'
#: rehearsal sizes in their own bfloat16, weights of seed 7 and tokens of
#: ``jax.random.key(17)``: the digest of each program's text lowered for the
#: TPU (a program that is letter for letter the parent's computes the
#: parent's logits on any machine) and of the full forward's logits as this
#: sandbox's CPU computed them. Rotation, the query bottleneck, the router's
#: kind, identity experts and layers that list their parts all default off.
PARENT = {
    "kimi_linear": ("kimi-linear-48b-a3b-ep2share.json", {
        "apply":
        "96ba237e1f54ec8af8af56d40049b9718031e8693c86c85a8d915000b0e87851",
        "decode":
        "d2bbb869e760c4f33f8dbd4fc286fd25ccbcfa4084801c84c2adc8cac5501587",
        "prefill":
        "aa6175ec581566d8e637ffa1ef5b9953aece1757934fed9106d5107859ed294a",
        "insert":
        "e2a2bdb3ba16a18e8a55c0aab75d12e2267d255f3c13cc1de9482d5f0a391da0",
        "logits":
        "f8df012adfcde02685491b5b83dfaf4ec1919bdba37420022c9d0d532d46201d"}),
    "nemotron_h": ("nemotron-3-super-120b-a12b-ep4share.json", {
        "apply":
        "0aca380a2a52b2efe59fa3c08308f24027fa1577132e5f3192a0e43eeaefa5e9",
        "decode":
        "e954a34f5de8264c8de598b224bfd1b284e0bb4ada309121f41ce6c01131968f",
        "prefill":
        "aa7ba9a5e83f3f2aae4da32629208820255e12ad777d04a6ba0a8b189f05a6b7",
        "insert":
        "bb4198b0b36d0276c5a2fd39e327a3d87420eee96d10a68674392fb92a612649",
        "logits":
        "229311dd973ea3b22e92a41912c74a0fb91c6eec34f7529908bd124ee172ed87"}),
}


@pytest.fixture(scope="module", params=sorted(PARENT))
def other_family(request):
    name, want = PARENT[request.param]
    cfg = _load(name)
    adapter = harness.load_module("models", request.param + ".py")
    return cfg, adapter, adapter.build_model(cfg), want


def _sha(text: bytes):
    return hashlib.sha256(text).hexdigest()


def _lowered(cfg, adapter, model, program):
    """The text, lowered for the TPU, of one program of ``model`` over the
    adapter's weight shapes: the full forward over (2, 40) tokens, a
    32-token prefill bucket's, or the decode step's or the insert's over 4
    slots and 21 pages of 8 rows."""
    shapes = adapter.weight_shapes(cfg)
    slots, pages, pt = 4, 21, 8
    i32 = lambda *s: jax.ShapeDtypeStruct(s, jnp.int32)     # noqa: E731
    eng = DecodeEngine(model, shapes, max_len=cfg["n_positions"],
                       prefill_buckets=[16, 32, 64], page_tokens=pt)
    if program == "apply":
        traced = jax.jit(model.apply).trace(shapes, i32(2, 40))
    elif program == "prefill":
        traced = eng._prefill_jit.trace(shapes, i32(1, 32), i32(), i32())
    else:
        cache = jax.eval_shape(lambda: model.new_paged_cache(slots, pages,
                                                             pt))
        if program == "decode":
            traced = eng._decode_paged_jit.trace(
                shapes, cache, i32(slots, cfg["n_positions"] // pt),
                i32(slots), i32(slots), i32())
        else:
            ent = jax.eval_shape(
                lambda p, t: model.prefill_cache(p, t, 3)[1], shapes,
                i32(1, 32))
            traced = eng._insert_paged_jit.trace(cache, ent, i32(4), i32())
    return traced.lower(lowering_platforms=("tpu",)).as_text()


@pytest.mark.parametrize("program", ["apply", "decode", "prefill", "insert"])
def test_the_other_families_programs_are_the_parents(other_family, program):
    cfg, adapter, model, want = other_family
    text = _lowered(cfg, adapter, model, program)
    assert _sha(text.encode()) == want[program]
    assert len(model.step_stats) == 4           # no fifth count rides along


def test_the_other_families_logits_are_the_parents(other_family, request):
    """The full forward on the stored seed, bit for bit: the digest is of the
    bytes this sandbox's CPU gave the parent commit. Another CPU may add in
    another order (the program's text is held to the parent's in the test
    above, which is the same statement for any machine): there the last
    position's logits are held to the parent's stored ones within what one
    bfloat16 rounding moved by a reordered sum can do, 0.02 of logits of
    spread 0.2; a mechanism switched on by default moves them by 0.1 and
    more."""
    cfg, adapter, model, want = other_family
    params = adapter.make_weights(cfg, 7)
    toks = jax.random.randint(jax.random.key(17), (2, 40), 0,
                              cfg["vocab_size"])
    got = np.asarray(jax.jit(model.apply)(params, toks))
    assert np.isfinite(got).all() and np.abs(got).max() > 0.3
    if _sha(got.tobytes()) != want["logits"]:
        stored = np.load(os.path.join(ROOT, "tests",
                                      "hybrid_parent_logits.npz"))
        fam = request.node.callspec.params["other_family"]
        assert np.abs(got[:, -1] - stored[fam]).max() < 0.02

# ------------------- the families without a window kind, letter for letter
#: family -> (its file, what lets the latent attention's kernel in where the
#: trace is for the TPU: a latent rank of whole 128-lane tiles)
WINDOWLESS = {
    "kimi_linear": ("kimi-linear-48b-a3b-ep2share.json",
                    {"kv_lora_rank": 128}),
    "nemotron_h": ("nemotron-3-super-120b-a12b-ep4share.json", {}),
    "longcat_flash": (FILE, {"kv_lora_rank": 128}),
}
#: what the parent commit (d2735bf, PR 46) lowers the decode program and a
#: prefill bucket's of the three configurations WITHOUT a window layer to,
#: at their rehearsal sizes in their own bfloat16, by the digest of the text
#: lowered for the TPU: as this process's backend names itself (``cpu``: the
#: gathered window), and with ``jax.default_backend`` patched to ``tpu`` and
#: ``WINDOWLESS``'s widths (the second and fifth families' decode programs
#: then hold ``paged_latent_attention``'s kernel; the fourth's grouped-query
#: layer keeps the gather). PR 47 gave the window kind's rings the page walk
#: and every kind a line of its own in ``attention_backend``; these
#: programs' text, and with it their compile-cache keys and the three
#: cells' ``setup_s``, did not move. A kernel's serialised body carries its
#: source file's PATH, which differs from checkout to checkout, so a
#: custom call's ``backend_config`` is set aside before the digest
#: (``kernels/paged_latent_attention.py`` itself is the parent's file).
PARENT_47 = {
    ("kimi_linear", "decode", "cpu"):
    "d2bbb869e760c4f33f8dbd4fc286fd25ccbcfa4084801c84c2adc8cac5501587",
    ("kimi_linear", "prefill", "cpu"):
    "aa6175ec581566d8e637ffa1ef5b9953aece1757934fed9106d5107859ed294a",
    ("kimi_linear", "decode", "tpu"):
    "a5b9de5040eeea31e577285829267a52f8868b6daff6dce26af10d4ea2de433f",
    ("kimi_linear", "prefill", "tpu"):
    "b48fa8c835817af19e16bcd91fad3b19d0f107b28a82bd36bb121869c7c47d8b",
    ("nemotron_h", "decode", "cpu"):
    "e954a34f5de8264c8de598b224bfd1b284e0bb4ada309121f41ce6c01131968f",
    ("nemotron_h", "prefill", "cpu"):
    "aa7ba9a5e83f3f2aae4da32629208820255e12ad777d04a6ba0a8b189f05a6b7",
    ("nemotron_h", "decode", "tpu"):
    "e954a34f5de8264c8de598b224bfd1b284e0bb4ada309121f41ce6c01131968f",
    ("nemotron_h", "prefill", "tpu"):
    "aa7ba9a5e83f3f2aae4da32629208820255e12ad777d04a6ba0a8b189f05a6b7",
    ("longcat_flash", "decode", "cpu"):
    "4c2402899796976b61bf715023cf996b8d89a6775bc308e92daac39fe29a6022",
    ("longcat_flash", "prefill", "cpu"):
    "cb35140bac467f4a18d9395889f66a245264c55d138e5a959417bf2a7ed26665",
    ("longcat_flash", "decode", "tpu"):
    "e173e4a7527e90b5d993972b77c95c585425355fe6a3cd2d34e7f27eb67316f4",
    ("longcat_flash", "prefill", "tpu"):
    "da7f81847acc5368561d607bbaf98684f81928684f02a8574c07a9cad8b2dca9",
}


@pytest.mark.parametrize("family_program_backend", sorted(PARENT_47),
                         ids=" ".join)
def test_programs_without_a_window_layer_are_the_parents(
        family_program_backend, monkeypatch):
    fam, program, backend = family_program_backend
    name, wide = WINDOWLESS[fam]
    if backend == "tpu":
        monkeypatch.setattr(jax, "default_backend", lambda: "tpu")
    cfg = _load(name, **(wide if backend == "tpu" else {}))
    adapter = harness.load_module("models", fam + ".py")
    model = adapter.build_model(cfg)
    assert model.cache_window is None
    text = _lowered(cfg, adapter, model, program)
    kernels = text.count("custom_call @tpu_custom_call")
    assert kernels == (program == "decode" and backend == "tpu"
                       and bool(wide))
    if program == "decode":
        took = {"kimi_linear": "mla", "longcat_flash": "mla",
                "nemotron_h": "gqa"}[fam]
        assert sorted(model.attention_backend) == [took]
        assert model.attention_backend[took][0] == (
            "paged-latent" if kernels else "gather")
    text = re.sub(r'backend_config = "(?:[^"\\]|\\.)*"',
                  'backend_config = ""', text)
    assert _sha(text.encode()) == PARENT_47[family_program_backend]
