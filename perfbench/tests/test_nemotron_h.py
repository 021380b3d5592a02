"""The Nemotron-H family's benchmark files on the CPU: the plain reference
against a hand-written three-token case and against the program at the
configuration's ``rehearsal`` sizes, the cost functions against the shapes,
the configuration against the catalog row, the new readers on paths and on a
trace that has none of their names, and the new cell's ``--rehearsal`` run."""
import json
import os
import subprocess
import sys

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from perfbench import harness
from perfbench.layer_metrics import _inner, _named, _nemotron

HERE = os.path.dirname(os.path.abspath(__file__))
CONFIG = "nemotron-3-super-120b-a12b-ep4share.json"
CELL = "nemotronh-longgen"


def _sig(x):
    return 1.0 / (1.0 + np.exp(-x))


def _silu(x):
    return x * _sig(x)


def _rms(x, g, eps):
    return x / np.sqrt(np.mean(x * x) + eps) * g


def _tiny_cfg():
    return {
        "hidden_size": 6, "num_hidden_layers": 3, "layers_run": "M*E",
        "mamba_num_heads": 4, "mamba_head_dim": 3, "n_groups": 2,
        "ssm_state_size": 5, "conv_kernel": 4,
        "num_attention_heads": 4, "num_key_value_heads": 2, "head_dim": 3,
        "moe_intermediate_size": 4, "moe_latent_size": 5,
        "moe_shared_expert_intermediate_size": 7, "n_routed_experts": 2,
        "experts_held_first": 1, "router_width": 4, "num_experts_per_tok": 2,
        "norm_topk_prob": True, "routed_scaling_factor": 5, "norm_eps": 1e-5,
        "vocab_size": 9}


def _tiny_weights(rng):
    d, H, P, G, N = 6, 4, 3, 2, 5
    di, cd = H * P, H * P + 2 * G * N

    def w(*shape, std=0.5):
        return (std * rng.standard_normal(shape)).astype(np.float32)

    def gain(n):
        return 1 + w(n, std=0.1)

    mamba = {"w_in": w(d, di + cd + H), "conv": w(4, cd), "b_conv": w(cd),
             "a_log": w(H), "d_skip": 1 + w(H, std=0.2), "dt_bias": w(H),
             "norm": gain(di), "w_out": w(di, d)}
    gqa = {"w_q": w(d, 12), "w_kv": w(d, 12), "w_o": w(12, d)}
    moe = {"w_router": w(d, 4), "b_select": w(4, std=0.1),
           "w_up": w(2, 5, 4), "w_down": w(2, 4, 5),
           "shared": {"w_up": w(d, 7), "w_down": w(7, d)},
           "w_latent_in": w(d, 5), "w_latent_out": w(5, d)}
    return {"tok_emb": w(9, d), "head": w(d, 9), "ln_f": gain(d),
            "blocks": [{"ln1": gain(d), "mixer": mamba},
                       {"ln1": gain(d), "mixer": gqa},
                       {"ln2": gain(d), "ffn": moe}]}


def _relu2_np(x, w1, w2):
    return np.maximum(x @ w1, 0.0) ** 2 @ w2


def _by_hand(p, toks, cfg):
    """Three tokens through one Mamba-2 layer (4 heads of 3 in 2 groups,
    state 5), one attention layer (4 query heads on 2 key/value heads) and
    one expert layer (2 of 4 experts held, experts 1 and 2, in a 5-wide
    latent space), every step written out."""
    eps = cfg["norm_eps"]
    H, P, G, N = 4, 3, 2, 5
    di = H * P
    T = len(toks)
    x = [p["tok_emb"][t].astype(np.float64) for t in toks]
    # ---- layer 1: Mamba-2
    b = p["blocks"][0]
    m = b["mixer"]
    zxd = [_rms(v, b["ln1"], eps) @ m["w_in"] for v in x]
    pre = [v[di:2 * di + 2 * G * N] for v in zxd]
    S = np.zeros((H, P, N))
    outs = []
    for t in range(T):
        # causal taps: row t sees rows t-3 .. t, the last tap on row t
        conved = sum(m["conv"][3 - j] * pre[t - j] for j in range(4)
                     if t - j >= 0) + m["b_conv"]
        act = _silu(conved)
        xs = act[:di].reshape(H, P)
        B = act[di:di + G * N].reshape(G, N)
        C = act[di + G * N:].reshape(G, N)
        dt = np.log1p(np.exp(zxd[t][2 * di + 2 * G * N:] + m["dt_bias"]))
        y = np.zeros((H, P))
        for h in range(H):
            g = h // (H // G)
            a = np.exp(-np.exp(m["a_log"][h]) * dt[h])
            S[h] = a * S[h] + dt[h] * np.outer(xs[h], B[g])
            y[h] = S[h] @ C[g] + m["d_skip"][h] * xs[h]
        y = y.reshape(di) * _silu(zxd[t][:di])
        y = np.concatenate([_rms(part, 1.0, eps)
                            for part in y.reshape(G, di // G)]) * m["norm"]
        outs.append(y @ m["w_out"])
    x = [x[t] + outs[t] for t in range(T)]
    # ---- layer 2: grouped-query attention, no positions
    b = p["blocks"][1]
    m = b["mixer"]
    h_ = [_rms(v, b["ln1"], eps) for v in x]
    q = [(v @ m["w_q"]).reshape(4, 3) for v in h_]
    kv = [v @ m["w_kv"] for v in h_]
    k = [v[:6].reshape(2, 3) for v in kv]
    v_ = [v[6:].reshape(2, 3) for v in kv]
    outs = []
    for t in range(T):
        o = np.zeros((4, 3))
        for hq in range(4):
            g = hq // 2
            s = np.array([q[t][hq] @ k[j][g] for j in range(t + 1)]) \
                / np.sqrt(3)
            pr = np.exp(s - s.max())
            pr = pr / pr.sum()
            o[hq] = sum(pr[j] * v_[j][g] for j in range(t + 1))
        outs.append(o.reshape(12) @ m["w_o"])
    x = [x[t] + outs[t] for t in range(T)]
    # ---- layer 3: experts, 4 published, 2 a token, experts 1 and 2 held
    b = p["blocks"][2]
    f = b["ffn"]
    out = []
    for v in x:
        hh = _rms(v, b["ln2"], eps)
        sc = _sig(hh @ f["w_router"])
        chosen = np.argsort(-(sc + f["b_select"]))[:2]
        wts = sc[chosen] / sc[chosen].sum() * 5
        u = hh @ f["w_latent_in"]
        routed = np.zeros(5)
        for e, wt in zip(chosen, wts):
            if e in (1, 2):
                routed += wt * _relu2_np(u, f["w_up"][e - 1],
                                         f["w_down"][e - 1])
        out.append(v + routed @ f["w_latent_out"]
                   + _relu2_np(hh, f["shared"]["w_up"],
                               f["shared"]["w_down"]))
    return np.stack([_rms(v, p["ln_f"], eps) @ p["head"] for v in out])


def test_reference_against_a_hand_written_three_token_case():
    ref = harness.load_module("reference", "nemotron_h.py")
    cfg = _tiny_cfg()
    for seed in range(6):
        rng = np.random.default_rng(seed)
        p = _tiny_weights(rng)
        toks = rng.integers(0, 9, 3)
        want = _by_hand(jax.tree.map(lambda a: a.astype(np.float64), p),
                        toks, cfg)
        got = np.asarray(ref.logits(jax.tree.map(jnp.asarray, p),
                                    jnp.asarray(toks)[None], cfg))[0]
        assert np.abs(got - want).max() < 5e-5   # float32 against float64
        assert np.abs(want).max() > 0.05


def test_reference_against_program_at_the_rehearsal_sizes():
    """In the configuration's own bfloat16 the program stays within what
    the rehearsal's limits allow of the float32 reference."""
    ref = harness.load_module("reference", "nemotron_h.py")
    mod = harness.load_module("models", "nemotron_h.py")
    cfg = harness.load_json("configs", CONFIG)
    cfg.update(cfg["rehearsal"])
    model, params = mod.build_model(cfg), mod.make_weights(cfg, 2**31 + 5)
    toks = jax.random.randint(jax.random.key(1), (2, 40), 0,
                              cfg["vocab_size"])
    got = jax.jit(model.apply)(params, toks)
    want = ref.logits(params, toks, cfg)
    assert float(jnp.max(jnp.abs(want))) > 0.3
    assert float(jnp.max(jnp.abs(got - want))) < 0.02
    # the float8 control is an order of magnitude further off
    low = ref.logits(params, toks, cfg, lowp=True)
    assert float(jnp.mean(jnp.abs(low - want))) \
        > 5 * float(jnp.mean(jnp.abs(got - want)))


def test_cost_functions_count_what_the_shapes_say():
    costs = harness.load_module("costs", "nemotron_h.py")
    mod = harness.load_module("models", "nemotron_h.py")
    cfg = harness.load_json("configs", CONFIG)
    shapes = mod.weight_shapes(cfg)
    # the matrices: everything but norm gains, biases, A_log and D
    n = sum(int(np.prod(a.shape)) for a in jax.tree.leaves(shapes)
            if len(a.shape) >= 2)
    assert n == costs.n_params(cfg)
    assert abs(n / 1e9 - 4.648) < 0.001
    assert abs(costs.mamba_params(cfg) / 1e6 - 109.6) < 0.1
    assert abs(costs.gqa_params(cfg) / 1e6 - 35.65) < 0.01
    assert costs.expert_params(cfg) == 2 * 1024 * 2688
    assert abs(2 * costs.expert_params(cfg) / 1e6 - 11.01) < 0.01
    assert costs.kv_row_bytes(cfg) == 1024
    assert costs.slot_state_bytes(cfg) == 5 * (128 * 64 * 128 * 4
                                               + 3 * 10240 * 2)
    model = mod.build_model(cfg)
    assert model.slot_state_bytes() == costs.slot_state_bytes(cfg)
    assert model.page_bytes(64) == 64 * costs.kv_row_bytes(cfg)
    # the whole step at 64 slots and 550 of 640 experts: 10.8 GB
    b = costs.decode_touched_bytes(cfg, 550, 64, 64 * 2000)
    assert abs(b / 1e9 - 10.8) < 0.2
    # every part is inside the whole, and the whole inside "all experts read"
    assert costs.moe_step_bytes(cfg, 550) + costs.ssm_step_bytes(cfg, 64) \
        + costs.gqa_step_bytes(cfg, 128000) < b
    assert b < costs.decode_step_bytes(cfg, 128000) \
        + costs.ssm_step_bytes(cfg, 64)
    assert costs.decode_step_flops(cfg, 128000, 64) > 0


CATALOG = "/opt/skills/guides/model-configs/architectures.jsonl"


@pytest.mark.skipif(not os.path.exists(CATALOG),
                    reason="the catalog is not on this machine")
def test_configuration_keeps_every_published_number():
    cfg = harness.load_json("configs", CONFIG)
    with open(CATALOG) as f:
        rows = [json.loads(line) for line in f if line.strip()]
    row = [r for r in rows
           if r["name"] == "NVIDIA-Nemotron-3-Super-120B-A12B-BF16"][0]
    for key, value in row["config"].items():
        if key in cfg["reduced"]:
            assert cfg[key] != value
        else:
            assert cfg[key] == value, key
    assert cfg["source"] == row["source_url"]
    assert sorted(cfg["reduced"]) == ["n_routed_experts", "num_hidden_layers",
                                      "vocab_size"]
    assert cfg["router_width"] == cfg["n_routed_experts_published"] == 512
    # one whole period of the published pattern, in its published ratio
    run, whole = cfg["layers_run"], cfg["hybrid_override_pattern"]
    assert whole[27:38] == run == "MEMEMEMEM*E" and len(whole) == 88
    assert [whole.count(c) for c in "ME*"] == [40, 40, 8]
    assert [run.count(c) for c in "ME*"] == [5, 5, 1]
    entry = [c for c in harness.benchmark()["configs"]
             if c["file"].endswith(CONFIG)][0]
    assert entry["reduced"] == cfg["reduced"]
    assert entry["source"] == cfg["source"]


def test_new_names_resolve_beside_the_vocabulary():
    path = "jit(_decode_paged)/attn_core/ssm_state/mul"
    assert (_nemotron.inner_of(path), _named.scope_of(path)) \
        == ("ssm_state", "attn_core")
    path = "jit(_decode_paged)/mlp/moe_latent/dot_general"
    assert (_nemotron.inner_of(path), _named.scope_of(path),
            _inner.inner_of(path)) == ("moe_latent", "mlp", None)
    assert _nemotron.inner_of("jit(_decode_paged)/kv_gather/gather") is None
    assert _nemotron.inner_of(None) is None
    assert _nemotron.NAMES.isdisjoint(_named.SCOPES | _inner.INNER)


def test_new_readers_read_nothing_from_a_program_without_their_names():
    """A trace of a program with the fixed vocabulary's scopes only (the
    recorded ``scoped.xplane.pb``: no device plane of a TPU, so nothing of
    the decode program) and spans without attributes: every new reader
    returns None and none raises, as on the parent commit."""
    assert not _nemotron.decode_seconds_by_names(
        os.path.join(HERE, "scoped.xplane.pb"))

    class Span:
        name, attrs, ts_us, dur_us = "decode_step", {"active": 3}, 10.0, 5.0

    class Tr:
        clock_offset = 0.0

        def module_durations(self, _p):
            return []

        def module_median(self, _p):
            return None

    cell = harness.Cell(CELL, rehearsal=True)
    ctx = {"cell": cell, "device": {"kind": "cpu"}, "trace": Tr(),
           "trace_span": (0.0, 1.0), "spans": [Span()], "_inner": None,
           "_nemotron": None}
    mine = [m["name"] for m in cell.per_layer if m["workloads"] == [CELL]]
    assert mine == ["ssm_dev_pct.tput", "ssm_roofline_pct.tput",
                    "gqa_roofline_pct.tput"]
    for name in mine:
        reader = harness.load_module("layer_metrics", name + ".py")
        assert reader.read(ctx) is None, name
    # and where the trace's directory holds no profile at all
    ctx.pop("_nemotron")
    assert _nemotron.decode_seconds(ctx) is None


def test_readers_sum_the_named_operations():
    acc = {("ssm_state", "attn_core"): 3.0, ("ssm_proj", "attn_qkv"): 1.0,
           ("gqa_attend", "attn_core"): 0.5, (None, "kv_gather"): 0.25,
           (None, "kv_write"): 0.25, ("moe_latent", "mlp"): 1.0,
           (None, "mlp"): 4.0}

    class Tr:
        def module_durations(self, _p):
            return [0.1] * 10

    ctx = {"_nemotron": acc, "trace": Tr()}
    assert _nemotron.share_pct(ctx, _nemotron.SSM) == 40.0
    assert _nemotron.seconds_a_step(ctx, _nemotron.SSM) == 0.4
    assert _nemotron.seconds_a_step(ctx, _nemotron.GQA,
                                    ("kv_gather", "kv_write")) == 0.1


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
