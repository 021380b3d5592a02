"""The Nemotron-H family as the program runs it: configuration file -> the
program's ``HybridLM``, and the weights, made by the benchmark.

This is the one place where a configuration file's keys meet the program's
constructor. The weights are the benchmark's own (not the program's
initialiser): from the seed, a jitted call a layer (so that the float32
draws of one expert layer's 759 M parameters are all that is live beside
what is kept), stored bfloat16 in the layout ``HybridLM`` takes; the program
and the plain reference are handed the same numbers and neither makes them.
Every term is non-trivial (decays, biases, gains, the skip, the router's
selection bias), so that a dropped one shows; the distributions and the
counts that led to them are under ``assumed.weights`` in the configuration's
file.
"""
from __future__ import annotations

import math

import jax
import jax.numpy as jnp

#: letter of ``hybrid_override_pattern`` -> (mixer, feed-forward)
KINDS = {"M": ("mamba2", None), "*": ("gqa", None), "E": (None, "moe")}


def layer_kinds(cfg: dict):
    """[(mixer, ffn)] of the layers that are run."""
    run = cfg["layers_run"]
    if len(run) != cfg["num_hidden_layers"] \
            or run not in cfg["hybrid_override_pattern"]:
        raise ValueError(f"layers_run {run!r} is not "
                         f"{cfg['num_hidden_layers']} consecutive layers of "
                         f"hybrid_override_pattern")
    return [KINDS[letter] for letter in run]


def held(cfg: dict):
    return (int(cfg.get("experts_held_first", 0)),
            int(cfg["n_routed_experts"]))


def build_model(cfg: dict, mesh=None):
    """The program's model object for this configuration."""
    from deeplearning4j_tpu.models.hybrid import (HybridConfig, HybridLM,
                                                  LayerSpec)
    from deeplearning4j_tpu.parallel.moe import RoutedExpertsConfig
    if (cfg["mlp_hidden_act"] != "relu2" or cfg["mamba_hidden_act"] != "silu"
            or cfg["n_shared_experts"] != 1 or cfg["n_group"] != 1
            or cfg["attention_bias"] or cfg["mlp_bias"] or cfg["use_bias"]
            or cfg["mamba_proj_bias"] or not cfg["use_conv_bias"]
            or cfg["tie_word_embeddings"]
            or cfg["layer_norm_epsilon"] != cfg["norm_eps"]
            or cfg["expand"] * cfg["hidden_size"]
            != cfg["mamba_num_heads"] * cfg["mamba_head_dim"]):
        raise ValueError("models/hybrid.py computes Mamba-2 with a SiLU "
                         "gate and a biased convolution, ungated squared-"
                         "ReLU experts in one sigmoid-routed group with one "
                         "shared expert, no other bias and an untied head "
                         "only")
    hc = HybridConfig(
        vocab_size=cfg["vocab_size"], d_model=cfg["hidden_size"],
        layers=tuple(LayerSpec(m, f) for m, f in layer_kinds(cfg)),
        max_len=cfg["n_positions"],
        experts=RoutedExpertsConfig(
            router_width=cfg["router_width"],
            top_k=cfg["num_experts_per_tok"], held=held(cfg),
            scale=cfg["routed_scaling_factor"],
            renormalize=bool(cfg["norm_topk_prob"]), form="relu2"),
        rms_eps=cfg["norm_eps"],
        dtype=jnp.dtype(cfg["compute_dtype"]),
        param_dtype=jnp.dtype(cfg["param_dtype"]),
        ssm_heads=cfg["mamba_num_heads"], ssm_head_dim=cfg["mamba_head_dim"],
        ssm_groups=cfg["n_groups"], ssm_state=cfg["ssm_state_size"],
        ssm_conv=cfg["conv_kernel"], ssm_chunk=cfg["chunk_size"],
        gqa_heads=cfg["num_attention_heads"],
        gqa_kv_heads=cfg["num_key_value_heads"], gqa_head_dim=cfg["head_dim"],
        expert_ff=cfg["moe_intermediate_size"],
        shared_ff=cfg["moe_shared_expert_intermediate_size"],
        expert_latent=cfg["moe_latent_size"])
    return HybridLM(hc, mesh)


def _draws(key, cfg):
    dt = jnp.dtype(cfg["param_dtype"])
    keys = iter(jax.random.split(key, 16))

    def normal(shape, std, mean=0.0, dtype=dt):
        return (mean + std * jax.random.normal(next(keys), shape,
                                               jnp.float32)).astype(dtype)

    return normal


def _block(key, cfg: dict, mixer, ffn):
    d = cfg["hidden_size"]
    resid = 0.02 / math.sqrt(2 * cfg["num_hidden_layers_published"])
    f32 = jnp.float32
    normal = _draws(key, cfg)

    def gain(n):
        return normal((n,), 0.1, 1.0)

    if mixer == "mamba2":
        H, P = cfg["mamba_num_heads"], cfg["mamba_head_dim"]
        di = H * P
        cd = di + 2 * cfg["n_groups"] * cfg["ssm_state_size"]
        return {"ln1": gain(d), "mixer": {
            "w_in": normal((d, di + cd + H), 0.02),
            "conv": normal((cfg["conv_kernel"], cd), 0.5),
            "b_conv": normal((cd,), 0.2),
            "a_log": normal((H,), 0.5, 0.0, f32),
            "d_skip": normal((H,), 0.2, 1.0, f32),
            "dt_bias": normal((H,), 1.0, -3.0, f32),
            "norm": gain(di), "w_out": normal((di, d), resid)}}
    if mixer == "gqa":
        hq = cfg["num_attention_heads"] * cfg["head_dim"]
        hkv = cfg["num_key_value_heads"] * cfg["head_dim"]
        return {"ln1": gain(d), "mixer": {
            "w_q": normal((d, hq), 0.02), "w_kv": normal((d, 2 * hkv), 0.02),
            "w_o": normal((hq, d), resid)}}
    if ffn != "moe":
        raise ValueError(f"no layer ({mixer}, {ffn}) in this family")
    f, fs = (cfg["moe_intermediate_size"],
             cfg["moe_shared_expert_intermediate_size"])
    lat, E, n = cfg["moe_latent_size"], cfg["router_width"], held(cfg)[1]
    return {"ln2": gain(d), "ffn": {
        "w_router": normal((d, E), 0.02),
        "b_select": normal((E,), 0.01, 0.0, f32),
        "w_up": normal((n, lat, f), lat ** -0.5),
        "w_down": normal((n, f, lat), 0.01),
        "shared": {"w_up": normal((d, fs), 0.02),
                   "w_down": normal((fs, d), resid)},
        "w_latent_in": normal((d, lat), 0.02),
        # 0.0025, a quarter of the experts' own second matrix: at 0.01 the
        # routed part is of the shared expert's size and one expert changed
        # among a token's 22 moves its logits by a tenth of their spread
        # (``assumed.weights`` has the counts)
        "w_latent_out": normal((lat, d), 0.0025)}}


def _ends(key, cfg: dict):
    d, V = cfg["hidden_size"], cfg["vocab_size"]
    normal = _draws(key, cfg)
    # the embedding at 0.3, not 0.02: see ``assumed.weights`` in the
    # configuration's file (what 64 slots' routers see has to differ)
    return {"tok_emb": normal((V, d), 0.3), "head": normal((d, V), 0.02),
            "ln_f": normal((d,), 0.1, 1.0)}


def _parts(cfg):
    """[(name, function of a key)] in the order the keys are drawn."""
    return [("ends", lambda k: _ends(k, cfg))] + [
        (i, lambda k, m=m, f=f: _block(k, cfg, m, f))
        for i, (m, f) in enumerate(layer_kinds(cfg))]


def _assemble(cfg, make):
    """The tree ``HybridLM`` takes from ``make(n, fn)`` of every part."""
    out = {"blocks": []}
    for n, (name, fn) in enumerate(_parts(cfg)):
        tree = make(n, fn)
        if name == "ends":
            out.update(tree)
        else:
            out["blocks"].append(tree)
    return out


def make_weights(cfg: dict, seed: int, shardings=None):
    """bfloat16 weights on the device, a jitted call a layer from the seed."""
    if shardings is not None:
        raise ValueError("this family is served on one chip")
    key = jax.random.key(int(seed))
    return _assemble(cfg, lambda n, fn: jax.jit(fn)(jax.random.fold_in(key,
                                                                       n)))


def weight_shapes(cfg: dict):
    return _assemble(cfg, lambda n, fn: jax.eval_shape(fn,
                                                       jax.random.key(0)))
