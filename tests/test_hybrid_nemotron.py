"""HybridLM's one-part layers, its Mamba-2 and grouped-query mixers, the
latent-space ungated experts of ``routed_experts_ffn`` and the table of mixer
kinds that the cache protocol walks, against the plain reference
``perfbench/reference/nemotron_h.py`` (float32, token-by-token Mamba-2,
expanded attention, a loop over held experts) at the configuration's
``rehearsal`` sizes: the published period ``MEMEMEMEM*E``, 16 experts of
which 8 are held, 4 a token, seeded weights.

Tolerances, each with its reason. Program and reference both compute in
float32 here (the configuration's dtypes are overridden), so what is left is
the order of the additions: the chunked scan against the token-by-token
recurrence, attention over pages against the expanded form, a grouped product
against a loop over experts. Logits are of order 0.7 and the gaps read 2e-7;
``TOL`` = 2e-5 leaves room for another CPU's vector width and is a hundred
times under what bfloat16 projections give at these sizes.
"""
import hashlib
import json
import logging
import os
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
    RoutedExpertsConfig, feed_forward, routed_experts_ffn)

TOL = 2e-5
NM = harness.load_module("models", "nemotron_h.py")
REF = harness.load_module("reference", "nemotron_h.py")
KM = harness.load_module("models", "kimi_linear.py")


def _load(name, **over):
    with open(os.path.join(ROOT, "perfbench", "configs", name)) as f:
        cfg = json.load(f)
    cfg.update(cfg["rehearsal"])
    cfg.update(compute_dtype="float32", param_dtype="float32")
    cfg.update(over)
    return cfg


def _cfg(**over):
    return _load("nemotron-3-super-120b-a12b-ep4share.json", **over)


@pytest.fixture(scope="module")
def family():
    cfg = _cfg()
    return cfg, NM.build_model(cfg), NM.make_weights(cfg, 3)


def _engine(family, **kw):
    cfg, model, params = family
    return DecodeEngine(model, params, max_len=cfg["n_positions"],
                        prefill_buckets=[16, 32, 64], page_tokens=8, **kw)


# ------------------------------------------------------- the description
def test_layer_description_is_the_published_period(family):
    cfg, model, params = family
    assert [(s.mixer, s.ffn) for s in model.config.layers] == [
        ("mamba2", None), (None, "moe")] * 4 + [
        ("mamba2", None), ("gqa", None), (None, "moe")]
    e = model.config.experts
    assert (e.held, e.router_width, e.top_k, e.form) == ((0, 8), 16, 4,
                                                         "relu2")
    # one norm a layer: the mixer's or the feed-forward's
    for blk, spec in zip(params["blocks"], model.config.layers):
        assert ("ln1" in blk, "ln2" in blk) == (spec.mixer is not None,
                                                spec.ffn is not None)
    # the program's own initialiser builds the same tree
    own = jax.eval_shape(model.init_params, jax.random.key(0))
    assert jax.tree.structure(own) == jax.tree.structure(params)
    assert jax.tree.leaves(jax.tree.map(
        lambda a, b: a.shape == b.shape, own, params)).count(False) == 0


@pytest.mark.parametrize("mixer, ffn, ok", [
    ("mamba2", None, True), ("gqa", None, True), (None, "moe", True),
    (None, "dense", True), ("kda", "moe", True), ("mamba2", "dense", True),
    (None, None, False), ("lstm", None, False), ("gqa", "glu", False)])
def test_layer_spec_admits_a_mixer_alone_and_a_feed_forward_alone(
        mixer, ffn, ok):
    if ok:
        assert hybrid.LayerSpec(mixer, ffn).mixer == mixer
    else:
        with pytest.raises(ValueError, match="unknown layer"):
            hybrid.LayerSpec(mixer, ffn)


def test_every_mixer_kind_declares_its_leaves(family):
    _cfg_, model, _ = family
    c = model.config
    names = {k: [leaf.name for leaf in kind.leaves(c)]
             for k, kind in hybrid.MIXERS.items()}
    assert names == {"kda": ["kda_s", "kda_conv"], "mla": ["latent"],
                     "mamba2": ["ssm_s", "ssm_conv"], "gqa": ["kv"],
                     "swa": ["swa_kv"],       # the window kind's ring, PR 43
                     # PR 46: a per-channel scan, and two kinds that own
                     # nothing and read another layer's rows
                     "mamba1": ["m1_s", "m1_conv"], "gmu": [], "xattn": []}
    # this model keeps what its kinds own, the paged leaf first
    assert [(leaf.name, leaf.paged, n) for leaf, n in model.cache_leaves] \
        == [("kv", True, 1), ("ssm_s", False, 5), ("ssm_conv", False, 5)]
    # a multiple of 128 lanes at the published widths (PR 27's finding)
    with open(os.path.join(ROOT, "perfbench", "configs",
                           "nemotron-3-super-120b-a12b-ep4share.json")) as f:
        full = NM.build_model(json.load(f))
    assert full.config.gqa_kv_row == 512
    assert full.slot_state_bytes() == 5 * (128 * 64 * 128 * 4
                                           + 3 * 10240 * 2) == 21278720
    assert full.page_bytes(64) == 64 * 1024


# ------------------------------------------------------ against the reference
def test_full_forward_matches_reference(family):
    cfg, model, params = family
    toks = jax.random.randint(jax.random.key(1), (2, 45), 0,
                              cfg["vocab_size"])
    got = jax.jit(model.apply)(params, toks)
    want = REF.logits(params, toks, cfg)
    assert float(jnp.max(jnp.abs(want))) > 0.3
    assert float(jnp.max(jnp.abs(got - want))) < TOL


def test_joins_and_leaves_mid_stream_do_not_disturb_a_third(family):
    """Slot 1 decodes 40 steps from a prompt of 21 tokens (bucket 32,
    padded). Slot 3 joins at step 5 (37 tokens, bucket 64) and leaves at
    step 17; slot 0 joins at step 9 (9 tokens, bucket 16) and leaves at 25;
    another prompt takes slot 3 again at step 21. Every step's LOGITS of
    every occupied slot against the reference's full forward over prompt +
    served tokens: continuous batching over state-space layers."""
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
        nxt, lg, state = eng.decode(state, tokens, positions, step)
        nxt, lg = np.asarray(nxt), np.asarray(lg)
        counts = eng.step_counts(nxt, slots)
        # a free slot routes to no expert: 5 expert layers x 4 a token
        assert counts["pairs_routed"] == 20 * len(live)
        assert 0 < counts["experts_touched"] <= counts["pairs_held"] \
            <= counts["pairs_routed"]
        for r in live:
            s = r["slot"]
            r["got"].append(lg[s])
            tokens[s] = nxt[s]
            positions[s] += 1
            r["seq"].append(int(nxt[s]))
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


# ------------------------------------------------------------ the mixers
@pytest.mark.parametrize("decay", [0.05, 6.0])
def test_chunked_scan_is_the_recurrence(decay):
    """77 rows (no multiple of the chunk of 32, padded with identity rows)
    from a non-zero state; ``decay`` 6 makes a head's running log decay pass
    -400 inside one chunk, where a form that divides by the cumulative decay
    overflows float32."""
    B, T, H, P, G, N = 2, 77, 4, 8, 2, 16
    ks = jax.random.split(jax.random.key(4), 6)
    x = jax.random.normal(ks[0], (B, T, H, P))
    b, c = (jax.random.normal(ks[i], (B, T, G, N)) for i in (1, 2))
    dt = jax.nn.softplus(jax.random.normal(ks[3], (B, T, H)))
    log_a = -decay * dt
    s0 = jax.random.normal(ks[5], (B, H, P, N))

    def recur(s, row):
        return hybrid.ssd_step(s, *row)

    s_want, y_want = lax.scan(
        recur, s0, tuple(a.swapaxes(0, 1) for a in (x, dt, log_a, b, c)))
    pad = [(0, 0), (0, -T % 32)]
    padded = [jnp.pad(a, pad + [(0, 0)] * (a.ndim - 2))
              for a in (x, dt, log_a, b, c)]
    y, s = jax.jit(hybrid.ssd_chunked, static_argnums=6)(*padded, s0, 32)
    assert np.isfinite(np.asarray(y)).all()
    assert float(jnp.max(jnp.abs(y[:, :T] - y_want.swapaxes(0, 1)))) < 1e-4
    assert float(jnp.max(jnp.abs(s - s_want))) < 1e-4


@pytest.mark.parametrize("n, bucket", [(19, 32), (16, 16), (2, 16)])
def test_padded_scan_hands_over_state_and_tail_at_the_true_last_token(
        family, n, bucket):
    """Layer 1's mixer alone over a padded bucket: its output rows, the
    state and the 3-row tail it hands the cache are those of the one-step
    recurrence fed the prompt's ``n`` real rows, whatever the padding
    holds."""
    cfg, model, params = family
    p = params["blocks"][0]["mixer"]
    c = model.config
    h = jax.random.normal(jax.random.key(7), (1, bucket, cfg["hidden_size"]))
    valid = jnp.arange(bucket) < n
    y, s, tail = jax.jit(model._ssm_full)(p, h, valid, n - 1)
    s1 = jnp.zeros((1, c.ssm_heads, c.ssm_head_dim, c.ssm_state))
    t1 = jnp.zeros((1, c.ssm_conv - 1, c.ssm_conv_dim))
    for t in range(n):
        y1, s1, t1 = model._ssm_decode(p, h[:, t], s1, t1)
        assert float(jnp.max(jnp.abs(y1 - y[:, t]))) < TOL
    assert float(jnp.max(jnp.abs(s1 - s))) < TOL
    assert float(jnp.max(jnp.abs(t1 - tail))) < TOL
    assert float(jnp.max(jnp.abs(s))) > 1e-3


def test_grouped_query_decode_over_pages_is_the_expanded_form(family):
    """Layer 10's mixer alone: the expanded form over 19 rows (query head i
    on key/value head i // 2) against the one-row form fed the same rows
    through a paged pool of K and V rows."""
    cfg, model, params = family
    p = params["blocks"][9]["mixer"]
    c = model.config
    T, P = 19, 8
    h = jax.random.normal(jax.random.key(7), (1, T, cfg["hidden_size"]))
    want, rows = model._gqa_full(p, h)
    # the expanded form, written out: every query head with its own K, V
    q = (h[0] @ p["w_q"]).reshape(T, c.gqa_heads, c.gqa_head_dim)
    k, v = (jnp.repeat(a, c.gqa_heads // c.gqa_kv_heads, axis=1)
            for a in model._gqa_kv(h[0] @ p["w_kv"]))
    s = jnp.einsum("qhd,khd->hqk", q, k) * c.gqa_head_dim ** -0.5
    s = jnp.where(jnp.tril(jnp.ones((T, T), bool)), s, -jnp.inf)
    o = jnp.einsum("hqk,khd->qhd", jax.nn.softmax(s, -1), v)
    assert float(jnp.max(jnp.abs(o.reshape(T, -1) @ p["w_o"] - want[0]))) \
        < TOL
    pool = jnp.zeros((5, P, c.gqa_kv_row))
    tables = jnp.asarray([[2, 0, 3, 4]], jnp.int32)     # page 4 = trash
    for t in range(T):
        y, pool = model._gqa_decode(p, h[:, t], pool, tables,
                                    jnp.asarray([t], jnp.int32), P)
        assert float(jnp.max(jnp.abs(y - want[:, t]))) < TOL
    got_rows = pool[tables[0]].reshape(-1, pool.shape[-1])[:T]
    assert float(jnp.max(jnp.abs(got_rows - rows[0]))) < TOL


# ------------------------------------------------------------ the experts
def _expert_layer(cfg, key):
    """The family's expert layer with the routed path's two down
    projections scaled up, so that at these small fan-ins the routed part is
    of the shared expert's size (as it is at the published widths)."""
    p = NM._block(key, cfg, None, "moe")["ffn"]
    return dict(p, w_down=20 * p["w_down"],
                w_latent_out=20 * p["w_latent_out"])


def test_the_four_shares_add_up():
    """The routed parts that ``held = (0, 4)``, ``(4, 4)``, ``(8, 4)`` and
    ``(12, 4)`` give for the 16 experts, each through the latent-out
    projection (it is linear, so the shares' outputs add), plus the shared
    expert counted once, equal the uncut layer of the reference (all 16
    held)."""
    cfg = _cfg(n_routed_experts=16)
    full = _expert_layer(cfg, jax.random.key(11))
    x = jax.random.normal(jax.random.key(12), (53, cfg["hidden_size"]))
    want = REF._moe(x, full, cfg, False)
    shared = REF._relu2(x, full["shared"]["w_up"], full["shared"]["w_down"],
                        False)
    total = shared
    pairs = 0
    for first in (0, 4, 8, 12):
        part = dict(full, w_up=full["w_up"][first:first + 4],
                    w_down=full["w_down"][first:first + 4])
        y, stats = routed_experts_ffn(
            part, x, RoutedExpertsConfig(16, 4, (first, 4),
                                         cfg["routed_scaling_factor"],
                                         form="relu2"))
        total = total + (y - shared)
        pairs += int(stats[1])
        # the reference, given the same share, agrees with the program
        share = REF._moe(x, part, _cfg(n_routed_experts=4,
                                       experts_held_first=first), False)
        assert float(jnp.max(jnp.abs(y - share))) < TOL
    assert pairs == 53 * 4                  # every pair fell on one share
    assert float(jnp.max(jnp.abs(total - want))) < TOL
    assert float(jnp.max(jnp.abs(want - shared))) > 0.05


def test_no_token_is_dropped_under_a_skewed_router():
    """A selection bias that sends every token to expert 5 first: no
    capacity, so all pairs are computed and the result is the reference's;
    a masked row routes nowhere."""
    cfg = _cfg()
    p = _expert_layer(cfg, jax.random.key(13))
    p["b_select"] = p["b_select"].at[5].set(50.0)
    x = jax.random.normal(jax.random.key(14), (64, cfg["hidden_size"]))
    ec = RoutedExpertsConfig(16, 4, (0, 8), cfg["routed_scaling_factor"],
                             form="relu2")
    y, stats = jax.jit(lambda p, x: routed_experts_ffn(p, x, ec))(p, x)
    s = jax.nn.sigmoid(x @ p["w_router"])
    _, idx = lax.top_k(s + p["b_select"], 4)
    assert bool(jnp.all(jnp.any(idx == 5, axis=-1)))
    assert int(stats[1]) == int(jnp.sum(idx < 8)) >= 64
    assert float(jnp.max(jnp.abs(y - REF._moe(x, p, cfg, False)))) < TOL
    mask = jnp.arange(64) < 10
    _, st = routed_experts_ffn(p, x, ec, mask)
    assert int(st[1]) == int(jnp.sum(idx[:10] < 8)) and int(st[2]) == 40


@pytest.mark.parametrize("form", ["swiglu", "relu2"])
@pytest.mark.parametrize("latent", [False, True])
def test_form_and_latent_pair_are_independent(form, latent):
    """Both forms, with and without the latent pair, against the sum
    written out pair by pair: the form comes from the configuration, the
    latent pair from the leaves that are there."""
    d, w, f, E, k, T = 12, (6 if latent else 12), 5, 8, 3, 17
    ks = iter(jax.random.split(jax.random.key(3), 12))

    def n(*shape):
        return 0.4 * jax.random.normal(next(ks), shape)

    first = {"swiglu": "w_gu", "relu2": "w_up"}[form]
    cols = 2 * f if form == "swiglu" else f
    p = {"w_router": n(d, E), "b_select": 0.1 * n(E), first: n(4, w, cols),
         "w_down": n(4, f, w),
         "shared": {first: n(d, cols), "w_down": n(f, d)}}
    if latent:
        p.update(w_latent_in=n(d, w), w_latent_out=n(w, d))
    x = n(T, d)
    ec = RoutedExpertsConfig(E, k, (2, 4), 1.7, form=form)
    y, stats = routed_experts_ffn(p, x, ec)
    s = jax.nn.sigmoid(x @ p["w_router"])
    _, idx = lax.top_k(s + p["b_select"], k)
    wts = jnp.take_along_axis(s, idx, -1)
    wts = 1.7 * wts / wts.sum(-1, keepdims=True)
    u = x @ p["w_latent_in"] if latent else x
    routed = jnp.zeros((T, w))
    for t in range(T):
        for j in range(k):
            e = int(idx[t, j]) - 2
            if 0 <= e < 4:
                routed = routed.at[t].add(wts[t, j] * feed_forward(
                    u[t], {first: p[first][e], "w_down": p["w_down"][e]},
                    form))
    if latent:
        routed = routed @ p["w_latent_out"]
    want = feed_forward(x, p["shared"], form) + routed
    assert float(jnp.max(jnp.abs(y - want))) < TOL
    assert int(stats[1]) == int(jnp.sum((idx >= 2) & (idx < 6)))
    with pytest.raises(ValueError, match="form"):
        RoutedExpertsConfig(E, k, (2, 4), form="gelu")


# ---------------------------------------------------- bytes, spans, gauges
def test_bytes_count_pages_and_slot_state(family):
    cfg, model, _ = family
    eng = _engine(family)
    state = eng.new_state(4, pages=20)
    page = 8 * (2 * 2 * 16) * 4             # one attention layer, float32
    slot = 5 * (8 * 16 * 16 * 4 + 3 * (128 + 2 * 2 * 16) * 4)
    assert eng.page_bytes() == page
    assert eng.slot_state_bytes() == slot == model.slot_state_bytes()
    assert eng.cache_bytes(state) == 21 * page + 4 * slot
    assert sorted(state.arrays) == ["kv", "ssm_conv", "ssm_s"]
    assert eng.resident_cache_bytes(state) == 0     # nothing occupied
    _f, _lg, kv, _t = eng.prefill(np.arange(11)[None])
    assert model.entries_tokens(kv) == 16
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


def test_pipeline_spans_gauges_and_the_log_line(family, caplog):
    """Through ``GenerationPipeline``: the step's counts are attributes of
    span ``decode_step`` (of 5 expert layers x 8 held), the pool and
    slot-state gauges read the new leaves through the protocol, and one log
    line a trace names the layers by kind and the experts' form."""
    cfg, model, params = family
    fresh = NM.build_model(cfg)             # nothing said yet
    eng = DecodeEngine(fresh, params, max_len=cfg["n_positions"],
                       prefill_buckets=[16, 32, 64], page_tokens=8)
    sink = reset_global_trace_sink(65536)
    from deeplearning4j_tpu.observability import span
    with caplog.at_level(logging.INFO,
                         logger="deeplearning4j_tpu.models.hybrid"):
        with GenerationPipeline(eng, slots=3, max_new_tokens=12,
                                cache_pages=30) as gp:
            with span("test_request"):
                out = gp.generate(np.arange(1, 20, dtype=np.int32),
                                  max_new_tokens=12)
            assert len(out) == 12
            assert _metric("dl4j_decode_slot_state_bytes") \
                == 3 * eng.slot_state_bytes() > 0
            assert _metric("dl4j_decode_page_pool_bytes") \
                == 31 * eng.page_bytes() > 0
    steps = [s for s in sink.spans() if s.name == "decode_step"]
    assert len(steps) == 11
    assert sum(s.attrs["pairs_routed"] for s in steps) == 11 * 5 * 4
    assert all(0 < s.attrs["experts_touched"] <= s.attrs["pairs_held"] <= 20
               for s in steps)
    said = [r.getMessage() for r in caplog.records
            if r.getMessage().startswith("layer kinds:")]
    # once a trace: the prefill bucket, the decode step (and nothing a step)
    assert 2 <= len(said) <= 4
    assert said[0] == (
        "layer kinds: " + "mamba2 moe " * 4 + "mamba2 gqa moe: experts relu2 "
        "in a 32-wide latent space, 4 of 16 a token, 8 held from 0")


# ------------------------------------- the other family's programs, unchanged
class _NamedLeaves(hybrid.HybridLM):
    """The cache protocol as PR 27 spelled it for KDA and MLA, every leaf by
    its name and an ``if kda / else mla`` in each method, over the mixers'
    functions as they are: the program that ``kimilinear-longgen`` ran
    before the mixer kinds owned their leaves."""

    def _trunk(self, params, tokens, last_idx):
        c = self.config
        T = tokens.shape[1]
        valid = jnp.arange(T) <= last_idx
        x = self._embed(params, tokens)
        entries = {"latent": [], "kda_s": [], "kda_conv": []}
        for blk, spec in zip(params["blocks"], c.layers):
            h = self._ln(blk["ln1"], x).astype(c.dtype)
            if spec.mixer == "kda":
                y, s, tail = self._kda_full(blk["mixer"], h, valid, last_idx)
                entries["kda_s"].append(s)
                entries["kda_conv"].append(tail)
            else:
                y, row = self._mla_full(blk["mixer"], h)
                entries["latent"].append(row)
            x = x + y
            y, _ = self._ffn(blk["ffn"], spec.ffn, self._ln(blk["ln2"], x),
                             jnp.broadcast_to(valid, tokens.shape))
            x = x + y
        return x, entries

    def insert_paged(self, arrays, entries, page_ids, slot, page_tokens):
        out = {"latent": [], "kda_s": [], "kda_conv": []}
        with jax.named_scope("kv_write"):
            for pool, rows in zip(arrays["latent"], entries["latent"]):
                tb = rows.shape[1]
                npb = -(-tb // page_tokens)
                rows = jnp.pad(rows[0], ((0, npb * page_tokens - tb), (0, 0)))
                out["latent"].append(pool.at[page_ids].set(
                    rows.reshape(npb, page_tokens, -1)))
            for name in ("kda_s", "kda_conv"):
                for held, new in zip(arrays[name], entries[name]):
                    out[name].append(lax.dynamic_update_slice_in_dim(
                        held, new.astype(held.dtype), slot, axis=0))
        return out

    def decode_paged(self, params, arrays, tables, tokens, positions,
                     page_tokens):
        c = self.config
        occupied = tables[:, 0] != (arrays["latent"][0].shape[0] - 1)
        x = self._embed(params, tokens)
        out = {"latent": [], "kda_s": [], "kda_conv": []}
        stats = jnp.zeros((len(self.step_stats),), jnp.int32)
        i_kda = i_mla = 0
        for blk, spec in zip(params["blocks"], c.layers):
            h = self._ln(blk["ln1"], x).astype(c.dtype)
            if spec.mixer == "kda":
                y, s, tail = self._kda_decode(
                    blk["mixer"], h, arrays["kda_s"][i_kda],
                    arrays["kda_conv"][i_kda])
                out["kda_s"].append(s)
                out["kda_conv"].append(tail)
                i_kda += 1
            else:
                y, pool = self._mla_decode(
                    blk["mixer"], h, arrays["latent"][i_mla], tables,
                    positions, page_tokens)
                out["latent"].append(pool)
                i_mla += 1
            x = x + y
            y, st = self._ffn(blk["ffn"], spec.ffn, self._ln(blk["ln2"], x),
                              occupied)
            if st is not None:
                stats = stats + st
            x = x + y
        return self._head(params, x), out, stats


PARENT_DIGEST = {
    # PR 36: 5eb3591's text (it was aa64185b...fbbd3) with the fourth count
    # of a step, ``expert_visits``, and nothing else. Compared line by line
    # with the parent's, value numbers and the counters behind private
    # functions' names aside: a constant 0 made (1,) and concatenated behind
    # the three counts in each of the four expert layers (3 lines a layer),
    # and int32[3] -> [4] where the counts are summed, joined to the tokens
    # (7 -> 8) and returned. Off the TPU nothing else of it changed
    # (tests/test_grouped_ffn.py); prefill and insert are the parent's
    "decode":
        "d2bbb869e760c4f33f8dbd4fc286fd25ccbcfa4084801c84c2adc8cac5501587",
    "prefill":
        "aa6175ec581566d8e637ffa1ef5b9953aece1757934fed9106d5107859ed294a",
    "insert":
        "e2a2bdb3ba16a18e8a55c0aab75d12e2267d255f3c13cc1de9482d5f0a391da0"}


@pytest.fixture(scope="module")
def kimi_programs():
    """{program: (lowered by the table walk, lowered by the named leaves)},
    for the TPU platform, at the rehearsal sizes in the configuration's own
    bfloat16."""
    cfg = _load("kimi-linear-48b-a3b-ep2share.json", compute_dtype="bfloat16",
                param_dtype="bfloat16")
    shapes = KM.weight_shapes(cfg)
    slots, pages, P = 4, 21, 8
    i32 = lambda *s: jax.ShapeDtypeStruct(s, jnp.int32)     # noqa: E731
    out = {}
    for who, model in (("table", KM.build_model(cfg)),
                       ("named", _NamedLeaves(KM.build_model(cfg).config))):
        eng = DecodeEngine(model, shapes, max_len=cfg["n_positions"],
                           prefill_buckets=[16, 32, 64], page_tokens=P)
        cache = jax.eval_shape(lambda: model.new_paged_cache(slots, pages, P))
        ent = jax.eval_shape(lambda p, t: model.prefill_cache(p, t, 3)[1],
                             shapes, i32(1, 32))
        lowered = {
            "decode": eng._decode_paged_jit.trace(
                shapes, cache, i32(slots, cfg["n_positions"] // P),
                i32(slots), i32(slots), i32()),
            "prefill": eng._prefill_jit.trace(shapes, i32(1, 32), i32(),
                                              i32()),
            "insert": eng._insert_paged_jit.trace(cache, ent, i32(4), i32())}
        for name, traced in lowered.items():
            out.setdefault(name, []).append(
                traced.lower(lowering_platforms=("tpu",)).as_text())
    return out


@pytest.mark.parametrize("program", ["decode", "prefill", "insert"])
def test_kimi_linear_programs_are_what_they_were(kimi_programs, program):
    """KDA and MLA moved onto the table of mixer kinds without a change to
    the program: decode, prefill and insert lower, for the TPU, to the text
    the named-leaf spelling of PR 27 lowers to, letter for letter. (At the
    cell's real sizes, for the described v5e, against the parent commit
    itself: PERF.md, PR 32.)"""
    table, named = kimi_programs[program]
    assert len(table) > 5000
    assert table == named
    # the mixers' own arithmetic too: the text the parent commit (a47b658;
    # decode: 5eb3591 with the fourth count, see ``PARENT_DIGEST``) lowers
    # to under this installation (jax 0.9.0), by its digest. A new
    # jax may word the same program differently: then compare both commits
    # under it (PERF.md, PR 32, says how) and record the new digests
    assert hashlib.sha256(table.encode()).hexdigest() == PARENT_DIGEST[program]
