"""The Kimi-Linear family as the program runs it: configuration file -> the
program's ``HybridLM``, and the weights, made by the benchmark.

This is the one place where a configuration file's keys meet the program's
constructor. The weights are the benchmark's own (not the program's
initialiser): from the seed, a jitted call a layer (so that the float32
draws of one layer's 953 M parameters are all that is live beside what is
kept), stored bfloat16 in the layout ``HybridLM`` takes; the program and the
plain reference are handed the same numbers and neither makes them. Every
term is non-trivial (decays, biases, gains, the router's selection bias), so
that a dropped one shows.
"""
from __future__ import annotations

import math

import jax
import jax.numpy as jnp


def layer_kinds(cfg: dict):
    """[(mixer, ffn)] of the layers that are run; layer 1 first."""
    lin = cfg["linear_attn_config"]
    out = []
    for i in range(1, cfg["num_hidden_layers"] + 1):
        mixer = "kda" if i in lin["kda_layers"] else "mla"
        if mixer == "mla" and i not in lin["full_attn_layers"]:
            raise ValueError(f"layer {i} is in neither list of "
                             f"linear_attn_config")
        out.append((mixer, "dense" if i <= cfg["first_k_dense_replace"]
                    else "moe"))
    return out


def held(cfg: dict):
    return (int(cfg.get("experts_held_first", 0)), int(cfg["num_experts"]))


def build_model(cfg: dict, mesh=None):
    """The program's model object for this configuration."""
    from deeplearning4j_tpu.models.hybrid import (HybridConfig, HybridLM,
                                                  LayerSpec)
    from deeplearning4j_tpu.parallel.moe import RoutedExpertsConfig
    lin = cfg["linear_attn_config"]
    if (cfg["hidden_act"] != "silu" or not cfg["mla_use_nope"]
            or cfg["moe_router_activation_func"] != "sigmoid"
            or cfg["num_shared_experts"] != 1 or cfg["num_expert_group"] != 1
            or cfg["q_lora_rank"] is not None
            or cfg["tie_word_embeddings"]):
        raise ValueError("models/hybrid.py computes SiLU gates, latent "
                         "attention without positions or a query "
                         "bottleneck, one sigmoid-routed expert group with "
                         "one shared expert and an untied head only")
    hc = HybridConfig(
        vocab_size=cfg["vocab_size"], d_model=cfg["hidden_size"],
        layers=tuple(LayerSpec(m, f) for m, f in layer_kinds(cfg)),
        max_len=cfg["n_positions"],
        experts=RoutedExpertsConfig(
            router_width=cfg["router_width"],
            top_k=cfg["num_experts_per_token"], held=held(cfg),
            scale=cfg["routed_scaling_factor"],
            renormalize=bool(cfg["moe_renormalize"])),
        rms_eps=cfg["rms_norm_eps"],
        dtype=jnp.dtype(cfg["compute_dtype"]),
        param_dtype=jnp.dtype(cfg["param_dtype"]),
        kda_heads=lin["num_heads"], kda_head_dim=lin["head_dim"],
        kda_conv=lin["short_conv_kernel_size"],
        kda_gate_rank=cfg["kda_gate_rank"], kda_chunk=cfg["kda_chunk"],
        mla_heads=cfg["num_attention_heads"],
        qk_nope_dim=cfg["qk_nope_head_dim"],
        qk_rope_dim=cfg["qk_rope_head_dim"], v_head_dim=cfg["v_head_dim"],
        kv_lora_rank=cfg["kv_lora_rank"], dense_ff=cfg["intermediate_size"],
        expert_ff=cfg["moe_intermediate_size"])
    return HybridLM(hc, mesh)


def _draws(key, cfg):
    dt = jnp.dtype(cfg["param_dtype"])
    keys = iter(jax.random.split(key, 32))

    def normal(shape, std, mean=0.0, dtype=dt):
        return (mean + std * jax.random.normal(next(keys), shape,
                                               jnp.float32)).astype(dtype)

    return normal


def _block(key, cfg: dict, mixer: str, ffn: str):
    d = cfg["hidden_size"]
    lin = cfg["linear_attn_config"]
    H, K, r = lin["num_heads"], lin["head_dim"], cfg["kda_gate_rank"]
    resid = 0.02 / math.sqrt(2 * cfg["num_hidden_layers_published"])
    f32 = jnp.float32
    normal = _draws(key, cfg)

    def gain(n):
        return normal((n,), 0.1, 1.0)

    def swiglu(width, lead=()):
        return {"w_gu": normal(lead + (d, 2 * width), 0.02),
                "w_down": normal(lead + (width, d), resid)}

    if mixer == "kda":
        mix = {"w_qkv": normal((d, 3 * H * K), 0.02),
               "conv": normal((lin["short_conv_kernel_size"], 3 * H * K),
                              0.5),
               "w_f1": normal((d, r), 0.02),
               "w_f2": normal((r, H * K), r ** -0.5),
               "b_dt": normal((H * K,), 1.0, -3.0, f32),
               "a_log": normal((H,), 0.5, 0.0, f32),
               "w_beta": normal((d, H), 0.02),
               "w_g1": normal((d, r), 0.02),
               "w_g2": normal((r, H * K), r ** -0.5),
               "b_g2": normal((H * K,), 0.5),
               "o_norm": gain(K), "w_o": normal((H * K, d), resid)}
    else:
        hm, R = cfg["num_attention_heads"], cfg["kv_lora_rank"]
        dn, dr, dv = (cfg["qk_nope_head_dim"], cfg["qk_rope_head_dim"],
                      cfg["v_head_dim"])
        mix = {"w_q": normal((d, hm * (dn + dr)), 0.02),
               "w_kva": normal((d, R + dr), 0.02), "kv_norm": gain(R),
               "w_kvb": normal((R, hm * (dn + dv)), R ** -0.5),
               "w_o": normal((hm * dv, d), resid)}
    if ffn == "dense":
        feed = swiglu(cfg["intermediate_size"])
    else:
        f, E = cfg["moe_intermediate_size"], cfg["router_width"]
        feed = {"w_router": normal((d, E), 0.02),
                "b_select": normal((E,), 0.01, 0.0, f32),
                **swiglu(f, (held(cfg)[1],)), "shared": swiglu(f)}
    return {"ln1": gain(d), "ln2": gain(d), "mixer": mix, "ffn": feed}


def _ends(key, cfg: dict):
    d, V = cfg["hidden_size"], cfg["vocab_size"]
    normal = _draws(key, cfg)
    # the embedding at 0.3, not 0.02: see ``assumed.weights`` in the
    # configuration's file (what 64 slots' routers see has to differ)
    return {"tok_emb": normal((V, d), 0.3), "head": normal((d, V), 0.02),
            "ln_f": normal((d,), 0.1, 1.0)}


def _parts(cfg):
    """[(name, function of a key)] in the order the keys are drawn."""
    kinds = layer_kinds(cfg)
    return [("ends", lambda k: _ends(k, cfg))] + [
        (i, lambda k, m=m, f=f: _block(k, cfg, m, f))
        for i, (m, f) in enumerate(kinds)]


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
