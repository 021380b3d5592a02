"""The LongCat-Flash family's benchmark files on the CPU: the plain reference
against a hand-written three-token case and against the program at the
configuration's ``rehearsal`` sizes, the cost functions against the shapes,
the configuration against the catalog row, the new readers on paths and on a
trace that has none of their names, the float8 control against the rehearsal
limits, three broken timed paths, and the new cell's ``--rehearsal`` run."""
import dataclasses
import json
import os
import subprocess
import sys

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from perfbench import harness, run as prun
from perfbench.layer_metrics import _inner, _longcat, _named

HERE = os.path.dirname(os.path.abspath(__file__))
CONFIG = "longcat-flash-omni-ep32share.json"
CELL = "longcat-rollout"


def _silu(x):
    return x / (1.0 + np.exp(-x))


def _rms(x, g, eps):
    return x / np.sqrt(np.mean(x * x) + eps) * g


def _tiny_cfg():
    return {
        "hidden_size": 6, "num_layers": 1, "num_attention_heads": 2,
        "q_lora_rank": 4, "kv_lora_rank": 3, "qk_nope_head_dim": 3,
        "qk_rope_head_dim": 4, "v_head_dim": 2, "mla_scale_q_lora": True,
        "mla_scale_kv_lora": True, "rope_theta": 50.0,
        "ffn_hidden_size": 5, "expert_ffn_hidden_size": 4,
        "n_routed_experts": 2, "experts_held_first": 1, "router_width": 6,
        "zero_expert_num": 2, "moe_topk": 3, "routed_scaling_factor": 6,
        "rms_norm_eps": 1e-5, "vocab_size": 9}


def _tiny_weights(rng):
    d = 6

    def w(*shape, std=0.5):
        return (std * rng.standard_normal(shape)).astype(np.float32)

    def gain(n):
        return 1 + w(n, std=0.1)

    def mla():
        return {"w_qa": w(d, 4), "q_norm": gain(4), "w_qb": w(4, 2 * 7),
                "w_kva": w(d, 3 + 4), "kv_norm": gain(3),
                "w_kvb": w(3, 2 * 5), "w_o": w(2 * 2, d)}

    def dense():
        return {"w_gu": w(d, 10), "w_down": w(5, d)}

    moe = {"w_router": w(d, 6), "b_select": w(6, std=0.02),
           "w_gu": w(2, d, 8), "w_down": w(2, 4, d)}
    return {"tok_emb": w(9, d), "head": w(d, 9), "ln_f": gain(d),
            "blocks": [{"ln_a0": gain(d), "ln_f0": gain(d), "ln_a1": gain(d),
                        "ln_f1": gain(d), "attn0": mla(), "attn1": mla(),
                        "ffn0": dense(), "ffn1": dense(), "moe": moe}]}


def _swiglu_np(x, w_gu, w_down):
    h = x @ w_gu
    f = h.shape[-1] // 2
    return (_silu(h[:f]) * h[f:]) @ w_down


def _turn(v, t, theta):
    """Pairs (v[2i], v[2i+1]) turned by t theta^(-2i/r), one at a time."""
    r = len(v)
    out = np.zeros(r)
    for i in range(r // 2):
        a = t * theta ** (-2.0 * i / r)
        out[2 * i] = v[2 * i] * np.cos(a) - v[2 * i + 1] * np.sin(a)
        out[2 * i + 1] = v[2 * i] * np.sin(a) + v[2 * i + 1] * np.cos(a)
    return out


def _mla_np(xs, p, cfg):
    """Rows xs through one latent attention: 2 heads of 3 + 4 rotated, values
    of 2, a query bottleneck of 4 and a key/value bottleneck of 3, every
    position's keys and values written out."""
    eps, theta, d = cfg["rms_norm_eps"], cfg["rope_theta"], 6
    qs, ks, vs = [], [], []
    for t, x in enumerate(xs):
        cq = _rms(x @ p["w_qa"], p["q_norm"], eps) * np.sqrt(d / 4)
        q = (cq @ p["w_qb"]).reshape(2, 7)
        kva = x @ p["w_kva"]
        c = _rms(kva[:3], p["kv_norm"], eps) * np.sqrt(d / 3)
        kvb = (c @ p["w_kvb"]).reshape(2, 5)
        k_r = _turn(kva[3:], t, theta)
        qs.append([np.concatenate([q[h, :3], _turn(q[h, 3:], t, theta)])
                   for h in range(2)])
        ks.append([np.concatenate([kvb[h, :3], k_r]) for h in range(2)])
        vs.append([kvb[h, 3:] for h in range(2)])
    out = []
    for t in range(len(xs)):
        o = np.zeros((2, 2))
        for h in range(2):
            s = np.array([qs[t][h] @ ks[j][h] for j in range(t + 1)]) \
                / np.sqrt(7)
            pr = np.exp(s - s.max())
            pr = pr / pr.sum()
            o[h] = sum(pr[j] * vs[j][h] for j in range(t + 1))
        out.append(o.reshape(4) @ p["w_o"])
    return out


def _moe_np(h, f, cfg):
    """One token: 6 router outputs (4 experts of which 1 and 2 are held, 2
    identity), 3 chosen, softmax scores times 6, not renormalised."""
    z = h @ f["w_router"]
    s = np.exp(z - z.max())
    s = s / s.sum()
    chosen = np.argsort(-(s + f["b_select"]))[:3]
    out = np.zeros_like(h)
    for e in chosen:
        if e in (1, 2):
            out += 6 * s[e] * _swiglu_np(h, f["w_gu"][e - 1],
                                         f["w_down"][e - 1])
        elif e >= 4:
            out += 6 * s[e] * h
    return out


def _by_hand(p, toks, cfg):
    eps = cfg["rms_norm_eps"]
    b = p["blocks"][0]
    T = len(toks)
    x = [p["tok_emb"][t].astype(np.float64) for t in toks]
    y = _mla_np([_rms(v, b["ln_a0"], eps) for v in x], b["attn0"], cfg)
    a0 = [x[t] + y[t] for t in range(T)]
    h0 = [_rms(v, b["ln_f0"], eps) for v in a0]
    m = [_moe_np(v, b["moe"], cfg) for v in h0]
    b0 = [a0[t] + _swiglu_np(h0[t], **b["ffn0"]) for t in range(T)]
    y = _mla_np([_rms(v, b["ln_a1"], eps) for v in b0], b["attn1"], cfg)
    a1 = [b0[t] + y[t] for t in range(T)]
    out = [a1[t] + _swiglu_np(_rms(a1[t], b["ln_f1"], eps), **b["ffn1"])
           + m[t] for t in range(T)]
    return np.stack([_rms(v, p["ln_f"], eps) @ p["head"] for v in out])


def test_reference_against_a_hand_written_three_token_case():
    ref = harness.load_module("reference", "longcat_flash.py")
    cfg = _tiny_cfg()
    kinds = set()
    for seed in range(8):
        rng = np.random.default_rng(seed)
        p = _tiny_weights(rng)
        toks = rng.integers(0, 9, 3)
        p64 = jax.tree.map(lambda a: a.astype(np.float64), p)
        want = _by_hand(p64, toks, cfg)
        got = np.asarray(ref.logits(jax.tree.map(jnp.asarray, p),
                                    jnp.asarray(toks)[None], cfg))[0]
        assert np.abs(got - want).max() < 5e-5   # float32 against float64
        assert np.abs(want).max() > 0.05
        h = _rms(p64["tok_emb"][toks[0]], 1.0, 1e-5)
        z = h @ p64["blocks"][0]["moe"]["w_router"]
        kinds |= {("held" if e in (1, 2) else "same" if e >= 4 else "absent")
                  for e in np.argsort(-z)[:3]}
    assert kinds == {"held", "same", "absent"}   # every kind of pair occurred


def _family(seed):
    mod = harness.load_module("models", "longcat_flash.py")
    cfg = harness.load_json("configs", CONFIG)
    cfg.update(cfg["rehearsal"])
    return cfg, mod, mod.build_model(cfg), mod.make_weights(cfg, seed)


def test_reference_against_program_at_the_rehearsal_sizes():
    """In the configuration's own bfloat16 the program stays within what
    bfloat16 projections allow of the float32 reference."""
    ref = harness.load_module("reference", "longcat_flash.py")
    cfg, _mod, model, params = _family(2**31 + 5)
    toks = jax.random.randint(jax.random.key(1), (2, 40), 0,
                              cfg["vocab_size"])
    got = jax.jit(model.apply)(params, toks)
    want = ref.logits(params, toks, cfg)
    assert float(jnp.max(jnp.abs(want))) > 1.0
    assert float(jnp.mean(jnp.abs(got - want))) < 0.02
    # the float8 control is several times further off
    low = ref.logits(params, toks, cfg, lowp=True)
    assert float(jnp.mean(jnp.abs(low - want))) \
        > 5 * float(jnp.mean(jnp.abs(got - want)))


def test_cost_functions_count_what_the_shapes_say():
    costs = harness.load_module("costs", "longcat_flash.py")
    mod = harness.load_module("models", "longcat_flash.py")
    cfg = harness.load_json("configs", CONFIG)
    shapes = mod.weight_shapes(cfg)
    # the matrices: everything but norm gains and the selection bias
    n = sum(int(np.prod(a.shape)) for a in jax.tree.leaves(shapes)
            if len(a.shape) >= 2)
    assert n == costs.n_params(cfg)
    assert abs(n / 5.17e9 - 1) < 0.005          # ISSUE 38's arithmetic
    assert abs(costs.mla_params(cfg) / 1e6 - 90.6) < 0.1
    assert abs(costs.dense_params(cfg) / 1e6 - 226.5) < 0.1
    assert costs.expert_params(cfg) == 3 * 6144 * 2048
    assert abs(costs.layer_params(cfg) / 1e6 - 1242.8) < 0.1
    assert costs.latent_row_bytes(cfg) == 1152
    model = mod.build_model(cfg)
    assert model.slot_state_bytes() == 0
    # the program pads a row to 640: its pages are 10/9 of the roofline's
    assert model.page_bytes(64) * 9 == 64 * 8 * costs.latent_row_bytes(cfg) \
        * 10
    # the whole step at 64 slots, 40 of 64 held experts, 1,100 positions a
    # slot: 5.1 GB outside the experts, 3.0 in them, 0.65 of latent rows
    assert abs(costs.dense_ffn_step_bytes(cfg) / 1e9 - 3.62) < 0.01
    assert abs(costs.moe_step_bytes(cfg, 40) / 1e9 - 3.06) < 0.01
    b = costs.decode_touched_bytes(cfg, 40, 64, 64 * 1100)
    assert abs(b / 1e9 - 8.98) < 0.03
    # every part is inside the whole, and the whole inside "all experts read"
    assert costs.moe_step_bytes(cfg, 40) + costs.dense_ffn_step_bytes(cfg) \
        + costs.mla_step_bytes(cfg, 70400) < b
    assert b < costs.decode_step_bytes(cfg, 70400)
    assert costs.decode_step_flops(cfg, 70400, 64) > 0
    with pytest.raises(NotImplementedError, match="no training cell"):
        costs.train_flops_per_token(cfg, 1024)


CATALOG = "/opt/skills/guides/model-configs/architectures.jsonl"


@pytest.mark.skipif(not os.path.exists(CATALOG),
                    reason="the catalog is not on this machine")
def test_configuration_keeps_every_published_number():
    cfg = harness.load_json("configs", CONFIG)
    with open(CATALOG) as f:
        rows = [json.loads(line) for line in f if line.strip()]
    row = [r for r in rows if r["name"] == "LongCat-Flash-Omni"][0]
    for key, value in row["config"].items():
        if key in cfg["reduced"]:
            assert cfg[key] != value
        else:
            assert cfg[key] == value, key
    assert cfg["source"] == row["source_url"]
    assert sorted(cfg["reduced"]) == ["n_routed_experts", "num_layers",
                                      "vocab_size"]
    assert cfg["router_width"] == cfg["n_routed_experts_published"] \
        + cfg["zero_expert_num"] == 768
    # the guide's floors: four layers, 8 experts, an eighth of the vocabulary
    assert cfg["num_layers"] >= 4 and cfg["n_routed_experts"] >= 8
    assert cfg["vocab_size"] * 8 >= cfg["vocab_size_published"] == 131072
    entry = [c for c in harness.benchmark()["configs"]
             if c["file"].endswith(CONFIG)][0]
    assert entry["reduced"] == cfg["reduced"]
    assert entry["source"] == cfg["source"]


def test_new_names_resolve_inside_the_accepted_ones():
    path = "jit(_decode_paged)/mlp/ffn_dense/dot_general"
    assert (_longcat.inner_of(path), _named.scope_of(path),
            _inner.inner_of(path)) == ("ffn_dense", "mlp", None)
    # nested, so that the accepted readers count the new work where it is
    path = "jit(_decode_paged)/attn_qkv/mla_proj/mla_rope/mul"
    assert (_longcat.inner_of(path), _named.scope_of(path),
            _inner.inner_of(path)) == ("mla_rope", "attn_qkv", "mla_proj")
    path = "jit(_decode_paged)/mlp/moe_combine/moe_zero/reduce_sum"
    assert (_longcat.inner_of(path), _inner.inner_of(path)) \
        == ("moe_zero", "moe_combine")
    assert _longcat.inner_of("jit(_decode_paged)/kv_gather/gather") is None
    assert _longcat.inner_of(None) is None
    assert _longcat.NAMES.isdisjoint(_named.SCOPES | _inner.INNER)


def test_the_program_writes_the_new_names_where_the_readers_look():
    """The decode program lowered at the rehearsal sizes: its operations'
    ``op_name`` paths hold the three new names, each inside the accepted
    name the issue gives."""
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
    for path in ("mlp/ffn_dense/", "attn_qkv/mla_proj/mla_rope/",
                 "mlp/moe_combine/moe_zero/", "mlp/moe_route/"):
        assert path in text, path


def test_new_readers_read_nothing_from_a_program_without_their_names():
    """A trace of a program with the fixed vocabulary's scopes only (the
    recorded ``scoped.xplane.pb``: no device plane of a TPU, so nothing of
    the decode program) and spans without the new attribute: every new
    reader returns None and none raises, as on the parent commit."""
    assert not _longcat.decode_seconds_by_names(
        os.path.join(HERE, "scoped.xplane.pb"))

    class Span:
        name, ts_us, dur_us = "decode_step", 10.0, 5.0
        attrs = {"active": 3, "pairs_routed": 36}

    class Tr:
        clock_offset = 0.0

        def module_durations(self, _p):
            return []

        def module_median(self, _p):
            return None

    cell = harness.Cell(CELL, rehearsal=True)
    ctx = {"cell": cell, "device": {"kind": "cpu"}, "trace": Tr(),
           "trace_span": (0.0, 1.0), "spans": [Span()], "_inner": None,
           "_longcat": None}
    mine = [m["name"] for m in cell.per_layer if m["workloads"] == [CELL]]
    assert mine == ["zero_pairs_pct.tput", "dense_ffn_dev_pct.tput",
                    "dense_ffn_roofline_pct.tput"]
    for name in mine:
        reader = harness.load_module("layer_metrics", name + ".py")
        assert reader.read(ctx) is None, name
    # and where the trace's directory holds no profile at all
    ctx.pop("_longcat")
    assert _longcat.decode_seconds(ctx) is None


def test_readers_sum_the_named_operations_and_the_steps_ratio():
    acc = {"ffn_dense": 3.0, "mla_rope": 0.5, "moe_zero": 0.5, None: 6.0}

    class Tr:
        clock_offset = 0.0

        def module_durations(self, _p):
            return [0.1] * 10

    class Span:
        name = "decode_step"

        def __init__(self, ts, zero, routed):
            self.ts_us, self.dur_us = ts, 1.0
            self.attrs = {"pairs_zero": zero, "pairs_routed": routed}

    ctx = {"_longcat": acc, "trace": Tr(), "trace_span": (0.0, 1e-4),
           "spans": [Span(10.0, 12, 36), Span(20.0, 6, 24),
                     Span(500.0, 24, 24)]}       # the last: outside
    assert _longcat.share_pct(ctx, ("ffn_dense",)) == 30.0
    assert _longcat.seconds_a_step(ctx, ("ffn_dense",)) == 0.3
    assert abs(_longcat.step_ratio_mean_pct(ctx, "pairs_zero", "pairs_routed")
               - 100 * (1 / 3 + 1 / 4) / 2) < 1e-9
    reader = harness.load_module("layer_metrics", "zero_pairs_pct.tput.py")
    assert abs(reader.read(ctx) - 29.1666667) < 1e-6


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


def _no_identity_part(model):
    e = model.config.experts
    # the router keeps its width; its last outputs are experts nobody holds
    model.config.experts = dataclasses.replace(e, identity=0)
    model.step_stats = model.step_stats[:4]


def _no_bottleneck_scale(model):
    model.config.mla_scale_kv_lora = False


def _decode_keeps_key_rows_unrotated(model):
    from deeplearning4j_tpu.models import hybrid
    real = hybrid._rope

    def rope(x, positions, theta):
        # the one-token step's key row is (slots, r): left as it came
        return x.astype(jnp.float32) if x.ndim == 2 \
            else real(x, positions, theta)

    return hybrid, "_rope", rope


@pytest.mark.parametrize("breakage", [
    None, _no_identity_part, _no_bottleneck_scale,
    _decode_keeps_key_rows_unrotated])
def test_a_broken_part_of_the_layer_fails_correct(monkeypatch, capsys,
                                                  breakage):
    """The real run at the rehearsal sizes, sound and with one part of the
    new layer broken underneath: the identity experts' part dropped, a
    bottleneck's scale left out, the key rows that decode writes left
    unrotated. Each is failed by a limit of the comparison with the plain
    reference."""
    cell_model = harness.load_module("models", "longcat_flash.py")
    real = cell_model.build_model

    def build(cfg, mesh=None):
        model = real(cfg, mesh)
        patch = breakage(model)
        if patch:
            monkeypatch.setattr(*patch)
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
