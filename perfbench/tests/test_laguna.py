"""The Laguna family's benchmark files on the CPU: the plain reference against
a second spelling of each equation (numpy, token by token, a loop over heads
and over experts), the reference against the program at the configuration's
``rehearsal`` sizes, the cost functions against the shapes, the configuration
against the catalog row, the new readers on paths and on a context that has
none of their names, the float8 control against the rehearsal limits, one
broken timed path, and the new cell's ``--rehearsal`` run."""
import json
import math
import os
import subprocess
import sys

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from perfbench import harness, run as prun
from perfbench.layer_metrics import _inner, _laguna, _named, _nemotron

CONFIG = "laguna-xs2-33b-a3b-stage5.json"
CELL = "laguna-codegen"
REF = harness.load_module("reference", "laguna.py")


def _family(seed, **over):
    mod = harness.load_module("models", "laguna.py")
    cfg = harness.load_json("configs", CONFIG)
    cfg.update(cfg["rehearsal"])
    cfg.update(over)
    return cfg, mod, mod.build_model(cfg), mod.make_weights(cfg, seed)


# -------------------------------------- a second spelling of each equation
def _silu(x):
    return x / (1 + np.exp(-x))


def _sigmoid(x):
    return 1 / (1 + np.exp(-x))


def _rms_np(x, g, eps):
    return x / np.sqrt(np.mean(x * x, -1, keepdims=True) + eps) * g


def _swiglu_np(x, w_gu, w_down):
    h = x @ w_gu
    f = h.shape[-1] // 2
    return (_silu(h[..., :f]) * h[..., f:]) @ w_down


def _freqs_np(rope, d):
    """YaRN's frequencies as the issue writes them, pair by pair."""
    theta = rope["rope_theta"]
    out = []
    for i in range(d // 2):
        f = theta ** (-2 * i / d)
        if rope["rope_type"] == "yarn":
            def corr(n):
                return d * math.log(rope["original_max_position_embeddings"]
                                    / (2 * math.pi * n)) \
                    / (2 * math.log(theta))
            low = max(math.floor(corr(rope["beta_fast"])), 0)
            high = min(math.ceil(corr(rope["beta_slow"])), d - 1)
            ramp = min(max((i - low) / (high - low), 0), 1)
            f = f * (1 - ramp) + f / rope["factor"] * ramp
        out.append(f)
    return out


def _turn_np(v, t, rope):
    """One head's vector at position t: a 2 x 2 rotation a pair."""
    d = int(round(len(v) * rope["partial_rotary_factor"]))
    out = v.copy()
    amp = rope.get("attention_factor", 1.0)
    for i, f in enumerate(_freqs_np(rope, d)):
        c, s = math.cos(t * f), math.sin(t * f)
        a, b = v[2 * i], v[2 * i + 1]
        out[2 * i], out[2 * i + 1] = amp * (a * c - b * s), amp * (a * s
                                                                   + b * c)
    return out


def _attention_np(xs, p, cfg, kind, H):
    """Token by token, head by head: xs (T, d) float64."""
    g, hd = cfg["num_key_value_heads"], cfg["head_dim"]
    rope = cfg["rope_parameters"][kind]
    T = xs.shape[0]
    out = np.zeros((T, H * hd))
    ks = np.stack([[_turn_np((xs[t] @ p["w_kv"])[j * hd:(j + 1) * hd], t,
                             rope) for j in range(g)] for t in range(T)])
    vs = np.stack([(xs[t] @ p["w_kv"])[g * hd:].reshape(g, hd)
                   for t in range(T)])
    for t in range(T):
        gate = _sigmoid(xs[t] @ p["w_gate"])
        first = 0 if kind == "full_attention" else max(
            0, t - cfg["sliding_window"] + 1)
        for i in range(H):
            q = _turn_np((xs[t] @ p["w_q"])[i * hd:(i + 1) * hd], t, rope)
            j = i // (H // g)
            s = np.array([q @ ks[u, j] for u in range(first, t + 1)]) \
                / math.sqrt(hd)
            pr = np.exp(s - s.max())
            pr /= pr.sum()
            out[t, i * hd:(i + 1) * hd] = gate[i] * (pr @ vs[first:t + 1, j])
    return out @ p["w_o"]


def _moe_np(h, f, cfg):
    s = _sigmoid(h @ f["w_router"])
    out = np.zeros_like(h)
    for t in range(h.shape[0]):
        chosen = np.argsort(-(s[t] + f["b_select"]), kind="stable")[
            :cfg["num_experts_per_tok"]]
        total = s[t, chosen].sum()
        for e in chosen:
            out[t] += cfg["moe_routed_scaling_factor"] * s[t, e] / total \
                * _swiglu_np(h[t], f["w_gu"][e], f["w_down"][e])
    return out + _swiglu_np(h, f["shared"]["w_gu"], f["shared"]["w_down"])


def _by_hand(p, toks, cfg):
    eps = cfg["rms_norm_eps"]
    x = p["tok_emb"][toks]
    for blk, (kind, ffn, H) in zip(p["blocks"], REF.layers(cfg)):
        x = x + _attention_np(_rms_np(x, blk["ln1"], eps), blk["mixer"], cfg,
                              kind, H)
        h = _rms_np(x, blk["ln2"], eps)
        x = x + (_moe_np(h, blk["ffn"], cfg) if ffn == "sparse" else
                 _swiglu_np(h, blk["ffn"]["w_gu"], blk["ffn"]["w_down"]))
    return _rms_np(x, p["ln_f"], eps) @ p["head"]


def test_reference_against_a_second_spelling_token_by_token():
    """22 tokens, so that the window of 8 is crossed twice and a query's
    first key moves; float64 numpy against the float32 reference: what is
    left is float32 rounding, 1e-5 of logits of order 1."""
    cfg, _mod, _model, params = _family(
        7, compute_dtype="float32", param_dtype="float32",
        weights={"embedding_std": 0.3, "in_std": 0.125, "resid_std": 0.05,
                 "router_std": 0.125, "b_select_std": 0.01, "gain_std": 0.1})
    toks = np.random.default_rng(0).integers(0, cfg["vocab_size"], 22)
    p64 = jax.tree.map(lambda a: np.asarray(a, np.float64), params)
    want = _by_hand(p64, toks, cfg)
    got = np.asarray(REF.logits(params, jnp.asarray(toks)[None], cfg))[0]
    assert np.abs(want).max() > 1.0
    assert np.abs(got - want).max() < 2e-5
    # the frequencies, spelled twice
    rope = cfg["rope_parameters"]["full_attention"]
    ref, ramp = REF.inv_freq(rope, 16)
    assert ramp == (2, 5)           # inside the 8 pairs that rotate
    np.testing.assert_allclose(ref, _freqs_np(rope, 16), rtol=1e-12)
    assert ref[0] == 1.0 and ref[7] == pytest.approx(100 ** (-14 / 16) / 8)


def test_reference_against_program_at_the_rehearsal_sizes():
    """In the configuration's own bfloat16 the program stays within what
    bfloat16 projections allow of the float32 reference."""
    cfg, _mod, model, params = _family(2**31 + 5)
    toks = jax.random.randint(jax.random.key(1), (2, 40), 0,
                              cfg["vocab_size"])
    got = jax.jit(model.apply)(params, toks)
    want = REF.logits(params, toks, cfg)
    assert float(jnp.max(jnp.abs(want))) > 0.5
    assert float(jnp.mean(jnp.abs(got - want))) < 0.01
    # the float8 control is several times further off
    low = REF.logits(params, toks, cfg, lowp=True)
    assert bool(jnp.isfinite(low).all())
    assert float(jnp.mean(jnp.abs(low - want))) \
        > 5 * float(jnp.mean(jnp.abs(got - want)))


def test_cost_functions_count_what_the_shapes_say():
    for rehearsal in (False, True):
        cell = harness.Cell(CELL, rehearsal=rehearsal)
        c, cfg = cell.costs, cell.config
        shapes = cell.model.weight_shapes(cfg)
        n = sum(int(np.prod(a.shape)) for a in jax.tree.leaves(shapes)
                if len(a.shape) >= 2)
        assert n == c.n_params(cfg)
        blk = shapes["blocks"]
        assert sum(int(np.prod(a.shape)) for a in jax.tree.leaves(
            blk[1]["mixer"])) == c.attention_params(
                cfg, cfg["num_attention_heads_per_layer"][1])
        assert int(np.prod(blk[1]["ffn"]["w_gu"].shape[1:])) + int(np.prod(
            blk[1]["ffn"]["w_down"].shape[1:])) == c.expert_params(cfg)
        assert (c.n_layers(cfg, "full_attention"),
                c.n_layers(cfg, "sliding_attention"),
                c.n_layers(cfg, ffn="sparse")) == (2, 3, 4)
        row = c.kv_row_bytes(cfg)
        assert row == 2 * blk[0]["mixer"]["w_kv"].shape[1]
        # the touched step is the sum of its parts, and under the upper figure
        touched = c.decode_touched_bytes(cfg, 10, 4, 300)
        assert touched == c.moe_step_bytes(cfg, 10) + 2 * (
            c.all_attention_params(cfg) + c.dense_params(cfg)
            + cfg["vocab_size"] * cfg["hidden_size"]
            + 4 * cfg["hidden_size"]) + 2 * row * 304 + 3 * row * (
                4 * cfg["sliding_window"] + 4)
        assert c.decode_step_bytes(cfg, 300) > c.decode_touched_bytes(
            cfg, 10, 0, 300)
        assert c.decode_step_flops(cfg, 300, 4) > 2 * 4 * (
            c.all_attention_params(cfg) + c.dense_params(cfg))


CATALOG = "/opt/skills/guides/model-configs/architectures.jsonl"


@pytest.mark.skipif(not os.path.exists(CATALOG),
                    reason="the catalog is not on this machine")
def test_configuration_keeps_every_published_number():
    cfg = harness.load_json("configs", CONFIG)
    with open(CATALOG) as f:
        rows = [json.loads(line) for line in f if line.strip()]
    row = [r for r in rows if r["name"] == "Laguna-XS.2"][0]
    for key, value in row["config"].items():
        if key in cfg["reduced"]:
            assert cfg[key] != value
        else:
            assert cfg[key] == value, key       # the three lists whole, too
    assert cfg["source"] == row["source_url"]
    assert cfg["reduced"] == ["num_hidden_layers"]
    # the guide's floors: the dense layer and one whole period of four
    assert cfg["num_hidden_layers"] == 5
    assert cfg["layer_types"][1:5].count("sliding_attention") == 3
    entry = [c for c in harness.benchmark()["configs"]
             if c["file"].endswith(CONFIG)][0]
    assert entry["reduced"] == cfg["reduced"]
    assert entry["source"] == cfg["source"]


# ------------------------------------------------------------ the readers
def test_new_names_resolve_inside_the_accepted_ones():
    path = "jit(_decode_paged)/attn_qkv/swa_proj/swa_rope/mul"
    assert (_laguna.names_of(path), _named.scope_of(path)) == (
        ("swa", "swa_rope"), "attn_qkv")
    path = "jit(_decode_paged)/attn_qkv/gqa_proj/attn_gate/logistic"
    assert _laguna.names_of(path) == ("gqa", "attn_gate")
    assert _nemotron.inner_of(path) == "gqa_proj"   # the accepted reader's
    path = "jit(_decode_paged)/kv_write/swa_write/scatter"
    assert (_laguna.names_of(path), _named.scope_of(path)) == (
        ("swa", "swa_write"), "kv_write")
    path = ("jit(_decode_paged)/attn_core/gqa_attend/"
            "jit(_paged_grouped_attention)/pallas_call")
    assert (_laguna.names_of(path), _named.scope_of(path)) == (
        ("gqa", "gqa_attend"), "attn_core")
    assert _laguna.names_of("jit(_decode_paged)/kv_write/scatter") == (
        None, None)
    assert _laguna.names_of(None) == (None, None)
    assert _laguna.NAMES.isdisjoint(_named.SCOPES | _inner.INNER)
    # the pages' own operations are the full kind's, the ring's the window's
    assert _laguna._mine((None, None, "kv_write"), "gqa", None)
    assert _laguna._mine((None, None, "kv_write"), "gqa", ("gqa_attend",))
    assert not _laguna._mine((None, None, "kv_write"), "swa", None)
    assert not _laguna._mine(("swa", "swa_write", "kv_write"), "gqa", None)
    assert _laguna._mine(("swa", "swa_write", "kv_write"), "swa",
                         ("swa_attend", "swa_write"))
    assert not _laguna._mine(("swa", "swa_proj", "attn_qkv"), "swa",
                             ("swa_attend", "swa_write"))
    assert not _laguna._mine((None, None, "mlp"), "gqa", None)


def test_the_program_writes_the_new_names_where_the_readers_look():
    """The decode program lowered at the rehearsal sizes: its operations'
    ``op_name`` paths hold the new names, each inside the vocabulary's scope
    for that part of the block."""
    from deeplearning4j_tpu.models.generation import DecodeEngine
    cfg, mod, model, _params = _family(1)
    shapes = mod.weight_shapes(cfg)
    eng = DecodeEngine(model, shapes, max_len=cfg["n_positions"],
                       prefill_buckets=[16], page_tokens=8)
    cache = jax.eval_shape(lambda: model.new_paged_cache(4, 9, 8))
    i32 = lambda *s: jax.ShapeDtypeStruct(s, jnp.int32)     # noqa: E731
    text = eng._decode_paged_jit.trace(
        shapes, cache, i32(4, 16), i32(4), i32(4), i32()).lower(
        ).as_text(debug_info=True)
    for path in ("attn_qkv/gqa_proj/gqa_rope/", "attn_qkv/swa_proj/swa_rope/",
                 "attn_qkv/gqa_proj/attn_gate/",
                 "attn_out/swa_proj/attn_gate/",
                 "kv_write/swa_write/", "attn_core/swa_attend/",
                 "attn_core/gqa_attend/", "mlp/moe_route/"):
        assert path in text, path


def test_readers_sum_the_named_operations():
    acc = {("gqa", "gqa_attend", "attn_core"): 2.0,
           ("gqa", "gqa_proj", "attn_qkv"): 1.0,
           ("gqa", "attn_gate", "attn_out"): 0.5,
           (None, None, "kv_write"): 0.5,
           ("swa", "swa_attend", "attn_core"): 3.0,
           ("swa", "swa_write", "kv_write"): 1.0,
           ("swa", "swa_rope", "attn_qkv"): 1.0,
           (None, None, "mlp"): 11.0}

    class Trace:
        def module_durations(self, _pattern):
            return [0.0] * 4

    ctx = {"_laguna": acc, "trace": Trace()}
    assert _laguna.share_pct(ctx, "gqa") == 100 * 4.0 / 20
    assert _laguna.share_pct(ctx, "swa") == 100 * 5.0 / 20
    assert _laguna.seconds_a_step(ctx, "gqa", ("gqa_attend",)) == 2.5 / 4
    assert _laguna.seconds_a_step(ctx, "swa", ("swa_attend",
                                               "swa_write")) == 4.0 / 4


@pytest.mark.parametrize("name", [
    "attn_window_dev_pct.tput", "attn_full_dev_pct.tput",
    "attn_window_roofline_pct.tput", "attn_full_roofline_pct.tput",
    "cache_bytes_per_live_token.tput"])
def test_new_readers_read_nothing_from_a_program_without_their_names(name):
    cell = harness.Cell(CELL, rehearsal=True)
    reader = harness.load_module("layer_metrics", name + ".py")

    class Span:
        name, attrs, ts_us, dur_us = "decode_step", {"live_tokens": 9,
                                                     "active": 2}, 0, 1

    ctx = {"cell": cell, "device": {"kind": "cpu"}, "trace": None,
           "trace_span": (0.0, 1.0), "spans": [Span()]}
    assert reader.read(ctx) is None


# ------------------------------------------------------------ the control
def test_the_float8_control_fails_the_rehearsal_limits():
    """Greedy tokens from the engine's own prefill and decode programs at the
    rehearsal size, 600 of them over three seeds; the float8 control
    teacher-forced over the same prompts and tokens. The program's served
    tokens stay inside the rehearsal limits on the reference's logits, the
    control's first choices do not."""
    from deeplearning4j_tpu.models.generation import DecodeEngine
    from perfbench import serving
    cell = harness.Cell(CELL, rehearsal=True)
    cfg = cell.config
    gaps, low_gaps = [], []
    for seed in (31, 32, 33):
        params = cell.model.make_weights(cfg, seed)
        engine = DecodeEngine(cell.model.build_model(cfg), params,
                              max_len=cfg["n_positions"],
                              prefill_buckets=[16])
        rng = np.random.default_rng(seed)
        sample = []
        for _ in range(4):
            prompt = rng.integers(0, cfg["vocab_size"], 16).astype(np.int32)
            toks = np.asarray(engine.generate(prompt[None], 50))[0]
            sample.append({"prompt": prompt.tolist(),
                           "tokens": toks.tolist()})
        gaps.append(serving.served_token_gaps(cell, params, sample))
        seqs, _cands, mask = serving.pack(sample, cfg["n_positions"])
        low = np.asarray(cell.reference.next_token_argmax(
            params, jnp.asarray(seqs), cfg, True))
        low_gaps.append(np.asarray(cell.reference.next_token_gaps(
            params, jnp.asarray(seqs), jnp.asarray(low), cfg))[mask])
    gaps, low_gaps = np.concatenate(gaps), np.concatenate(low_gaps)
    assert gaps.size == 600
    assert gaps.max() <= cell.limit("served_logit_gap_max")
    assert gaps.mean() <= cell.limit("served_logit_gap_mean")
    assert low_gaps.max() > cell.limit("served_logit_gap_max")
    assert low_gaps.mean() > cell.limit("served_logit_gap_mean")


# ------------------------------------------------------- broken timed paths
def _last_line(capsys, seed=41):
    rc = prun.main(["--workload", CELL, "--seed", str(seed), "--seconds",
                    "4", "--trace", "0", "--rehearsal"])
    out = capsys.readouterr().out.strip().splitlines()
    assert rc == 0
    return json.loads(out[-1]), out


def _ring_read_in_page_order(_model):
    from deeplearning4j_tpu.models import hybrid
    return hybrid, "_ring_live", lambda w, pos: (
        jnp.arange(w)[None, :] <= (pos % w)[:, None])


@pytest.mark.parametrize("breakage", [None, _ring_read_in_page_order])
def test_a_broken_ring_fails_correct(monkeypatch, capsys, breakage):
    """The real run at the rehearsal sizes, sound and with the window
    layers' ring read as a page is read (rows behind the write index dead):
    the served tokens leave the reference's first choices and a limit of the
    comparison fails it."""
    cell_model = harness.load_module("models", "laguna.py")
    real = cell_model.build_model

    def build(cfg, mesh=None):
        model = real(cfg, mesh)
        monkeypatch.setattr(*breakage(model))
        return model

    if breakage is not None:
        monkeypatch.setattr(cell_model, "build_model", build)
    line, out = _last_line(capsys)
    assert line["rehearsal"] is True and line["failed"] == 0
    assert line["correct"] is (breakage is None), out[-6:]
    if breakage is not None:
        assert any("served_logit_gap" in x and "OUTSIDE" in x for x in out)


def test_the_new_cell_rehearses_on_the_cpu():
    env = dict(os.environ, JAX_PLATFORMS="cpu")
    out = subprocess.run(
        [sys.executable, os.path.join(harness.HERE, "run.py"), "--workload",
         CELL, "--seed", str(2**31 + 11), "--seconds", "4", "--trace", "0",
         "--rehearsal"], capture_output=True, text=True, env=env, timeout=600)
    assert out.returncode == 0, out.stderr[-2000:]
    line = json.loads(out.stdout.strip().splitlines()[-1])
    assert line["correct"] is True and line["rehearsal"] is True
    assert line["failed"] == 0 and "serve_tok_s" in line["reported"]
