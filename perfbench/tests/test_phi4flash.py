"""The Phi-4-flash family's benchmark files on the CPU: the plain reference
against a second spelling of each equation (numpy, float64, token by token, a
loop over pairs of heads), the reference against the program at the
configuration's ``rehearsal`` sizes, the cost functions against the shapes,
the configuration against the catalog row, the new readers on paths and on a
context that has none of their names, one broken timed path, and the new
cell's ``--rehearsal`` run."""
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
from perfbench.layer_metrics import _inner, _laguna, _named, _nemotron, \
    _phi4flash

CONFIG = "phi-4-mini-flash-reasoning.json"
CELL = "phi4flash-reasoning"
CATALOG = "/opt/skills/guides/model-configs/architectures.jsonl"
REF = harness.load_module("reference", "phi4flash.py")


def _family(seed, **over):
    mod = harness.load_module("models", "phi4flash.py")
    cfg = harness.load_json("configs", CONFIG)
    cfg.update(cfg["rehearsal"])
    cfg.update(over)
    return cfg, mod, mod.build_model(cfg), mod.make_weights(cfg, seed)


# -------------------------------------- a second spelling of each equation
def _silu(x):
    return x / (1.0 + np.exp(-x))


def _ln_np(x, p, eps=1e-5):
    x = x - x.mean(-1, keepdims=True)
    return x / np.sqrt((x * x).mean(-1, keepdims=True) + eps) * p["g"] + p["b"]


def _softmax(s):
    e = np.exp(s - s.max())
    return e / e.sum()


def _mamba_np(h, p, cfg):
    T, C, N, r = (h.shape[0], cfg["mamba_d_inner"], cfg["mamba_d_state"],
                  cfg["mamba_dt_rank"])
    xz = h @ p["w_in"]
    pre, z = xz[:, :C], xz[:, C:]
    taps = p["conv"]
    x = np.zeros((T, C))
    for t in range(T):
        for i in range(taps.shape[0]):          # the last tap on the row
            j = t - (taps.shape[0] - 1 - i)
            if j >= 0:
                x[t] += taps[i] * pre[j]
    x = _silu(x + p["b_conv"])
    rbc = x @ p["w_x"]
    dt = np.log1p(np.exp(rbc[:, :r] @ p["w_dt"] + p["b_dt"]))
    a = -np.exp(p["a_log"])                     # (N, C)
    s = np.zeros((N, C))
    y = np.zeros((T, C))
    for t in range(T):
        b, c = rbc[t, r:r + N], rbc[t, r + N:]
        for n in range(N):
            s[n] = np.exp(dt[t] * a[n]) * s[n] + dt[t] * b[n] * x[t]
        y[t] = sum(c[n] * s[n] for n in range(N)) + p["d_skip"] * x[t]
    return (y * _silu(z)) @ p["w_out"], y


def _attention_np(h, p, cfg, layer, kind, shared):
    T, d = h.shape
    H, g = cfg["num_attention_heads"], cfg["num_key_value_heads"]
    hd = d // H
    q = (h @ p["w_q"] + p["b_q"]).reshape(T, H, hd)
    if kind == "cross":
        k, v = shared
    else:
        kv = h @ p["w_kv"] + p["b_kv"]
        k = kv[:, :g * hd].reshape(T, g, hd)
        v = kv[:, g * hd:].reshape(T, g, hd)
    l0 = 0.8 - 0.6 * math.exp(-0.3 * layer)
    lam = (math.exp(float(p["lambda_q1"] @ p["lambda_k1"]))
           - math.exp(float(p["lambda_q2"] @ p["lambda_k2"])) + l0)
    pairs, groups = H // 2, g // 2
    out = np.zeros((T, pairs, 2 * hd))
    for t in range(T):
        lo = max(0, t - cfg["sliding_window"] + 1) if kind == "window" else 0
        for j in range(pairs):
            gi = j // (pairs // groups)
            val = np.concatenate([v[lo:t + 1, 2 * gi],
                                  v[lo:t + 1, 2 * gi + 1]], -1)
            a1 = _softmax(k[lo:t + 1, 2 * gi] @ q[t, 2 * j]
                          / math.sqrt(hd)) @ val
            a2 = _softmax(k[lo:t + 1, 2 * gi + 1] @ q[t, 2 * j + 1]
                          / math.sqrt(hd)) @ val
            o = a1 - lam * a2
            out[t, j] = o / np.sqrt((o * o).mean() + 1e-5) * p["sub_norm"] \
                * (1.0 - l0)
    return out.reshape(T, -1) @ p["w_o"] + p["b_o"], (k, v)


def _by_hand(params, toks, cfg):
    p = jax.tree.map(lambda a: np.asarray(a, np.float64), params)
    x = p["tok_emb"][toks]
    half = cfg["num_hidden_layers"] // 2
    memory = shared = None
    for i, (blk, kind) in enumerate(zip(p["blocks"], REF.layers(cfg))):
        h = _ln_np(x, blk["ln1"])
        if kind == "mamba":
            mix, y = _mamba_np(h, blk["mixer"], cfg)
            memory = y if i == half else memory
        elif kind == "memory":
            mix = (_silu(h @ blk["mixer"]["w_in"]) * memory) \
                @ blk["mixer"]["w_out"]
        else:
            mix, kv = _attention_np(h, blk["mixer"], cfg, i, kind, shared)
            shared = kv if kind == "full" else shared
        x = x + mix
        gu = _ln_np(x, blk["ln2"]) @ blk["ffn"]["w_gu"]
        f = gu.shape[1] // 2
        x = x + (_silu(gu[:, :f]) * gu[:, f:]) @ blk["ffn"]["w_down"]
    return _ln_np(x, p["ln_f"]) @ p["tok_emb"].T


def test_reference_against_a_second_spelling_token_by_token():
    cfg, _mod, _model, params = _family(2, compute_dtype="float32",
                                        param_dtype="float32")
    assert REF.layers(cfg) == ["mamba", "window"] * 3 + ["mamba", "full"] \
        + ["memory", "cross"] * 2
    toks = np.random.default_rng(0).integers(0, cfg["vocab_size"], 21)
    want = _by_hand(params, toks, cfg)
    got = np.asarray(REF.logits(params, jnp.asarray(toks)[None], cfg))[0]
    assert np.abs(want).max() > 2.0
    assert np.abs(got - want).max() < 2e-4


def test_reference_against_program_at_the_rehearsal_sizes():
    cfg, _mod, model, params = _family(3, compute_dtype="float32",
                                       param_dtype="float32")
    toks = jax.random.randint(jax.random.key(1), (2, 40), 0,
                              cfg["vocab_size"])
    got = jax.jit(model.apply)(params, toks)
    want = REF.logits(params, toks, cfg)
    assert float(jnp.max(jnp.abs(got - want))) < 1e-4
    # and in the types the configuration states: bfloat16 beside float32
    cfg, _mod, model, params = _family(3)
    got = jax.jit(model.apply)(params, toks)
    want = REF.logits(params, toks, cfg)
    assert 1e-4 < float(jnp.max(jnp.abs(got - want))) < 0.5


def test_the_float8_control_is_not_the_reference():
    cfg, _mod, _model, params = _family(4)
    toks = jax.random.randint(jax.random.key(2), (1, 40), 0,
                              cfg["vocab_size"])
    ref = REF.logits(params, toks, cfg)
    low = REF.logits(params, toks, cfg, lowp=True)
    assert float(jnp.max(jnp.abs(ref - low))) > 0.5


def test_cost_functions_count_what_the_shapes_say():
    mod = harness.load_module("models", "phi4flash.py")
    costs = harness.load_module("costs", "phi4flash.py")
    for rehearsal in (False, True):
        cfg = harness.load_json("configs", CONFIG)
        if rehearsal:
            cfg.update(cfg["rehearsal"])
        shapes = mod.weight_shapes(cfg)
        n = sum(int(np.prod(a.shape)) for a in jax.tree.leaves(shapes))
        norms = (2 * cfg["num_hidden_layers"] + 1) * 2 * cfg["hidden_size"]
        assert costs.n_params(cfg) == n - norms - cfg["num_hidden_layers"] // 2
        model = mod.build_model(cfg)
        assert costs.slot_state_bytes(cfg) == model.slot_state_bytes()
        assert costs.kv_row_bytes(cfg) * 64 == model.page_bytes(64)
        assert costs.page_readers(cfg) == model.page_readers


@pytest.mark.skipif(not os.path.exists(CATALOG),
                    reason="the catalog is not on this machine")
def test_configuration_keeps_every_published_number():
    with open(CATALOG) as f:
        row = [r for r in map(json.loads, f)
               if r["name"] == "Phi-4-mini-flash-reasoning"][0]
    cfg = harness.load_json("configs", CONFIG)
    assert {k: cfg[k] for k in row["config"]} == row["config"]
    entry = [c for c in harness.benchmark()["configs"]
             if c["file"].endswith(CONFIG)][0]
    assert entry["source"] == cfg["source"] == row["source_url"]
    assert entry["reduced"] == cfg["reduced"] == []


# ------------------------------------------------------------ the readers
def test_new_names_resolve_inside_the_accepted_ones():
    path = ("jit(_decode_paged)/attn_core/xattn_attend/"
            "jit(_paged_grouped_attention)/pallas_call")
    assert (_phi4flash.inner_of(path), _named.scope_of(path)) == (
        "xattn_attend", "attn_core")
    # the accepted readers count it with no kind of theirs
    assert _laguna.names_of(path) == (None, None)
    assert _nemotron.inner_of(path) is None
    path = "jit(_decode_paged)/attn_core/gqa_attend/attn_diff/sub"
    assert _laguna.names_of(path) == ("gqa", "gqa_attend")
    assert _phi4flash.inner_of(path) is None
    path = "jit(_decode_paged)/attn_core/ssm_state/mul"
    assert _nemotron.inner_of(path) == "ssm_state"
    assert _phi4flash.inner_of("jit(_decode_paged)/attn_qkv/gmu/dot") \
        == "gmu"
    assert _phi4flash.NAMES.isdisjoint(
        _named.SCOPES | _inner.INNER | _laguna.NAMES | _nemotron.NAMES)
    # a page write under no kind's name is the full kind's: the query-only
    # layers write none
    assert _laguna._mine((None, None, "kv_write"), "gqa", ("gqa_attend",))


@pytest.mark.parametrize("name", [
    "attn_cross_dev_pct.tput", "attn_cross_roofline_pct.tput",
    "gmu_dev_pct.tput"])
def test_new_readers_read_nothing_from_a_program_without_their_names(name):
    cell = harness.Cell(CELL, rehearsal=True)
    reader = harness.load_module("layer_metrics", name + ".py")

    class Span:
        name, attrs, ts_us, dur_us = "decode_step", {"live_tokens": 9,
                                                     "active": 2}, 0, 1

    ctx = {"cell": cell, "device": {"kind": "cpu"}, "trace": None,
           "trace_span": (0.0, 1.0), "spans": [Span()]}
    assert reader.read(ctx) is None


# ------------------------------------------------------- broken timed paths
def _last_line(capsys, seed=41):
    rc = prun.main(["--workload", CELL, "--seed", str(seed), "--seconds",
                    "4", "--trace", "0", "--rehearsal"])
    out = capsys.readouterr().out.strip().splitlines()
    assert rc == 0
    return json.loads(out[-1]), out


def _memory_of_the_row_before(_model):
    from deeplearning4j_tpu.models.hybrid import HybridLM
    gmu = HybridLM._gmu
    return HybridLM, "_gmu", lambda self, p, h, memory: gmu(
        self, p, h, memory * 0.0)


@pytest.mark.parametrize("breakage", [None, _memory_of_the_row_before])
def test_a_memory_unit_that_reads_nothing_fails_correct(monkeypatch, capsys,
                                                        breakage):
    """The real run at the rehearsal sizes, sound and with the memory units
    handed zeros: the served tokens leave the reference's first choices and
    a limit of the comparison fails it."""
    cell_model = harness.load_module("models", "phi4flash.py")
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
