"""The Kimi-Linear family's benchmark files on the CPU: the plain reference
against a hand-written two-token case, the cost functions against the
shapes, the new readers on paths and on a trace that has none of their
names, and the new cell's ``--rehearsal`` run."""
import json
import os
import subprocess
import sys

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from perfbench import harness
from perfbench.layer_metrics import _inner, _named

HERE = os.path.dirname(os.path.abspath(__file__))
CONFIG = "kimi-linear-48b-a3b-ep2share.json"


def _sig(x):
    return 1.0 / (1.0 + np.exp(-x))


def _silu(x):
    return x * _sig(x)


def _rms(x, g, eps):
    return x / np.sqrt(np.mean(x * x) + eps) * g


def _two_token_cfg():
    return {
        "hidden_size": 6, "num_hidden_layers": 2, "first_k_dense_replace": 1,
        "linear_attn_config": {"kda_layers": [1], "full_attn_layers": [2],
                               "num_heads": 1, "head_dim": 4,
                               "short_conv_kernel_size": 4},
        "kda_gate_rank": 3, "num_attention_heads": 1, "kv_lora_rank": 5,
        "qk_nope_head_dim": 3, "qk_rope_head_dim": 2, "v_head_dim": 3,
        "intermediate_size": 7, "moe_intermediate_size": 4,
        "num_experts": 2, "experts_held_first": 1, "router_width": 4,
        "num_experts_per_token": 2, "moe_renormalize": True,
        "routed_scaling_factor": 2.446, "num_shared_experts": 1,
        "rms_norm_eps": 1e-5, "vocab_size": 9}


def _two_token_weights(cfg, rng):
    d, K, r = 6, 4, 3

    def w(*shape, std=0.5):
        return (std * rng.standard_normal(shape)).astype(np.float32)

    def ffn(f, lead=()):
        return {"w_gu": w(*lead, d, 2 * f), "w_down": w(*lead, f, d)}

    kda = {"w_qkv": w(d, 3 * K), "conv": w(4, 3 * K), "w_f1": w(d, r),
           "w_f2": w(r, K), "b_dt": w(K), "a_log": w(1), "w_beta": w(d, 1),
           "w_g1": w(d, r), "w_g2": w(r, K), "b_g2": w(K),
           "o_norm": 1 + w(K, std=0.1), "w_o": w(K, d)}
    mla = {"w_q": w(d, 5), "w_kva": w(d, 7), "kv_norm": 1 + w(5, std=0.1),
           "w_kvb": w(5, 6), "w_o": w(3, d)}
    moe = {"w_router": w(d, 4), "b_select": w(4, std=0.1), **ffn(4, (2,)),
           "shared": ffn(4)}
    return {"tok_emb": w(9, d), "head": w(d, 9), "ln_f": 1 + w(d, std=0.1),
            "blocks": [
                {"ln1": 1 + w(d, std=0.1), "ln2": 1 + w(d, std=0.1),
                 "mixer": kda, "ffn": ffn(7)},
                {"ln1": 1 + w(d, std=0.1), "ln2": 1 + w(d, std=0.1),
                 "mixer": mla, "ffn": moe}]}


def _swiglu_np(x, p):
    h = x @ p["w_gu"]
    f = h.shape[-1] // 2
    return (_silu(h[:f]) * h[f:]) @ p["w_down"]


def _by_hand(p, toks, cfg):
    """Two tokens, one KDA layer with a dense feed-forward, one MLA layer
    with 2 of 4 experts held (experts 1 and 2), every step written out."""
    eps = cfg["rms_norm_eps"]
    K = 4
    x = [p["tok_emb"][t].astype(np.float64) for t in toks]
    # ---- layer 1: KDA
    b = p["blocks"][0]
    m = b["mixer"]
    h = [_rms(v, b["ln1"], eps) for v in x]
    pre = [v @ m["w_qkv"] for v in h]
    # causal taps: row t sees rows t-3 .. t, the last tap on row t itself
    conv0 = m["conv"][3] * pre[0]
    conv1 = m["conv"][2] * pre[0] + m["conv"][3] * pre[1]
    S = np.zeros((K, K))
    outs = []
    for t, conved in enumerate((conv0, conv1)):
        act = _silu(conved)
        q, k, v = act[:K], act[K:2 * K], act[2 * K:]
        q = q / np.sqrt(q @ q + 1e-6) / np.sqrt(K)
        k = k / np.sqrt(k @ k + 1e-6)
        f = (h[t] @ m["w_f1"]) @ m["w_f2"] + m["b_dt"]
        a = np.exp(-np.exp(m["a_log"][0]) * np.log1p(np.exp(f)))
        beta = _sig(h[t] @ m["w_beta"])[0]
        S = a[:, None] * S
        S = S - beta * np.outer(k, k @ S) + beta * np.outer(k, v)
        o = S.T @ q
        gate = _sig((h[t] @ m["w_g1"]) @ m["w_g2"] + m["b_g2"])
        outs.append((_rms(o, m["o_norm"], eps) * gate) @ m["w_o"])
    x = [x[t] + outs[t] for t in range(2)]
    x = [v + _swiglu_np(_rms(v, b["ln2"], eps), b["ffn"]) for v in x]
    # ---- layer 2: MLA, expanded, no positions
    b = p["blocks"][1]
    m = b["mixer"]
    h = [_rms(v, b["ln1"], eps) for v in x]
    q = [v @ m["w_q"] for v in h]                       # 3 nope + 2 "rope"
    kva = [v @ m["w_kva"] for v in h]
    c = [_rms(v[:5], m["kv_norm"], eps) for v in kva]
    kvb = [v @ m["w_kvb"] for v in c]
    k = [np.concatenate([kvb[t][:3], kva[t][5:]]) for t in range(2)]
    v_ = [kvb[t][3:] for t in range(2)]
    o0 = v_[0]                                          # sees itself alone
    s = np.array([q[1] @ k[0], q[1] @ k[1]]) / np.sqrt(5)
    pr = np.exp(s - s.max())
    pr = pr / pr.sum()
    o1 = pr[0] * v_[0] + pr[1] * v_[1]
    x = [x[0] + o0 @ m["w_o"], x[1] + o1 @ m["w_o"]]
    # ---- experts: 4 published, 2 a token, experts 1 and 2 held here
    f = b["ffn"]
    out = []
    for v in x:
        hh = _rms(v, b["ln2"], eps)
        sc = _sig(hh @ f["w_router"])
        chosen = np.argsort(-(sc + f["b_select"]))[:2]
        wts = sc[chosen] / sc[chosen].sum() * 2.446
        y = _swiglu_np(hh, f["shared"])
        for e, wt in zip(chosen, wts):
            if e in (1, 2):
                y = y + wt * _swiglu_np(hh, {"w_gu": f["w_gu"][e - 1],
                                             "w_down": f["w_down"][e - 1]})
        out.append(v + y)
    return np.stack([_rms(v, p["ln_f"], eps) @ p["head"] for v in out])


def test_reference_against_a_hand_written_two_token_case():
    ref = harness.load_module("reference", "kimi_linear.py")
    cfg = _two_token_cfg()
    for seed in range(6):
        rng = np.random.default_rng(seed)
        p = _two_token_weights(cfg, rng)
        toks = rng.integers(0, 9, 2)
        want = _by_hand(jax.tree.map(lambda a: a.astype(np.float64), p),
                        toks, cfg)
        got = np.asarray(ref.logits(jax.tree.map(jnp.asarray, p),
                                    jnp.asarray(toks)[None], cfg))[0]
        assert np.abs(got - want).max() < 2e-5   # float32 against float64
        assert np.abs(want).max() > 0.05


def test_cost_functions_count_what_the_shapes_say():
    costs = harness.load_module("costs", "kimi_linear.py")
    mod = harness.load_module("models", "kimi_linear.py")
    cfg = harness.load_json("configs", CONFIG)
    shapes = mod.weight_shapes(cfg)
    # the matrices: everything but norm gains, biases and A_log
    n = sum(int(np.prod(a.shape)) for a in jax.tree.leaves(shapes)
            if len(a.shape) >= 2)
    assert n == costs.n_params(cfg)
    assert abs(n / 1e9 - 4.283) < 0.001
    assert abs(costs.kda_params(cfg) / 1e6 - 39.5) < 0.1
    assert abs(costs.mla_params(cfg) / 1e6 - 29.1) < 0.1
    assert costs.expert_params(cfg) == 3 * 2304 * 1024
    assert costs.latent_row_bytes(cfg) == 1152
    assert costs.slot_state_bytes(cfg) == 4 * (32 * 128 * 128 * 4
                                               + 3 * 3 * 32 * 128 * 2)
    model = mod.build_model(cfg)
    assert model.slot_state_bytes() == costs.slot_state_bytes(cfg)
    # the whole step at 64 slots: 8.4 GB, 10.3 ms at 819 GB/s
    b = costs.decode_touched_bytes(cfg, 440, 64, 64 * 2000)
    assert abs(b / 1e9 - 8.43) < 0.02
    # every part is inside the whole, and the whole inside "all experts read"
    assert costs.moe_step_bytes(cfg, 440) + costs.kda_step_bytes(cfg, 64) \
        + costs.mla_step_bytes(cfg, 128000) < b
    assert b < costs.decode_step_bytes(cfg, 128000) \
        + costs.kda_step_bytes(cfg, 64)


CATALOG = "/opt/skills/guides/model-configs/architectures.jsonl"


@pytest.mark.skipif(not os.path.exists(CATALOG),
                    reason="the catalog is not on this machine")
def test_configuration_keeps_every_published_number():
    cfg = harness.load_json("configs", CONFIG)
    with open(CATALOG) as f:
        rows = [json.loads(line) for line in f if line.strip()]
    row = [r for r in rows if r["name"] == "Kimi-Linear-48B-A3B-Instruct"][0]
    for key, value in row["config"].items():
        if key in cfg["reduced"]:
            assert cfg[key] != value
        else:
            assert cfg[key] == value, key
    assert cfg["source"] == row["source_url"]
    assert sorted(cfg["reduced"]) == ["num_experts", "num_hidden_layers",
                                      "vocab_size"]
    assert cfg["router_width"] == cfg["num_experts_published"] == 256


def test_inner_names_resolve_beside_the_vocabulary():
    path = "jit(_decode_paged)/mlp/moe_experts/ragged_dot"
    assert _inner.inner_of(path) == "moe_experts"
    assert _named.scope_of(path) == "mlp"
    path = "jit(_decode_paged)/attn_core/kda_state/transpose(jvp(mul))"
    assert (_inner.inner_of(path), _named.scope_of(path)) \
        == ("kda_state", "attn_core")
    assert _inner.inner_of("jit(_decode_paged)/kv_gather/gather") is None
    assert _inner.inner_of(None) is None
    assert _inner.INNER.isdisjoint(_named.SCOPES)


def test_new_readers_read_nothing_from_a_program_without_their_names():
    """A trace of a program with the fixed vocabulary's scopes only (the
    recorded ``scoped.xplane.pb``) and spans without the new attributes: every
    new reader returns None and none raises."""
    acc = _inner.seconds_by_names(os.path.join(HERE, "scoped.xplane.pb"))
    assert acc and all(inner is None for _prog, inner, _scope in acc)
    assert {scope for _prog, _inner_name, scope in acc} >= {"mlp", "head"}

    class Span:
        name, attrs, ts_us, dur_us = "decode_step", {"active": 3}, 10.0, 5.0

    class Tr:
        clock_offset = 0.0

        def module_durations(self, _p):
            return []

        def module_median(self, _p):
            return None

    cell = harness.Cell("kimilinear-longgen", rehearsal=True)
    ctx = {"cell": cell, "device": {"kind": "cpu"}, "trace": Tr(),
           "trace_span": (0.0, 1.0), "spans": [Span()], "_inner": None}
    for m in cell.per_layer:
        if m["workloads"] == ["kimilinear-longgen"]:
            reader = harness.load_module("layer_metrics", m["name"] + ".py")
            assert reader.read(ctx) is None, m["name"]
    assert _inner.step_attr_mean(ctx, "active") == 3


def test_the_new_cell_rehearses_on_the_cpu():
    env = dict(os.environ, JAX_PLATFORMS="cpu")
    out = subprocess.run(
        [sys.executable, os.path.join(harness.HERE, "run.py"), "--workload",
         "kimilinear-longgen", "--seed", str(2**31 + 11), "--seconds", "4",
         "--trace", "0", "--rehearsal"], capture_output=True, text=True,
        env=env, timeout=600)
    assert out.returncode == 0, out.stderr[-2000:]
    line = json.loads(out.stdout.strip().splitlines()[-1])
    assert line["correct"] is True and line["rehearsal"] is True
    assert line["failed"] == 0 and "serve_tok_s" in line["reported"]
