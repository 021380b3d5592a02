"""The LongCat-Flash family's language model as the program runs it:
configuration file -> the program's ``HybridLM``, and the weights, made by the
benchmark.

This is the one place where a configuration file's keys meet the program's
constructor. A layer of this family is a DOUBLE layer with a
shortcut-connected expert layer, written as the program's per-layer
description takes it (``hybrid.Part``: kind, the key of its parameters, the
norm it reads through, where its result lands):

    attn0  latent attention       through ln_a0           added at once
    moe    the routed experts     through ln_f0           added at the END
    ffn0   dense SwiGLU           the rows ``moe`` read   added at once
    attn1  latent attention       through ln_a1           added at once
    ffn1   dense SwiGLU           through ln_f1           added at once

The weights are the benchmark's own (not the program's initialiser): from the
seed, a jitted call a part of a layer (so that the float32 draws of one part,
at most the 403 M of a layer's held first expert matrices, are all that is
live beside what is kept), stored bfloat16 in the layout ``HybridLM`` takes;
the program and the plain reference are handed the same numbers and neither
makes them. Every term is non-trivial (gains of all six norms a layer, the
router's selection bias), so that a dropped one shows; the distributions and
the counts that led to them are under ``assumed.weights`` in the
configuration's file.
"""
from __future__ import annotations

import math

import jax
import jax.numpy as jnp

#: (kind, the key of its parameters, the key of its norm or None, lands)
PARTS = (("mla", "attn0", "ln_a0", "now"), ("moe", "moe", "ln_f0", "end"),
         ("dense", "ffn0", None, "now"), ("mla", "attn1", "ln_a1", "now"),
         ("dense", "ffn1", "ln_f1", "now"))


def held(cfg: dict):
    return (int(cfg.get("experts_held_first", 0)),
            int(cfg["n_routed_experts"]))


def build_model(cfg: dict, mesh=None):
    """The program's model object for this configuration."""
    # a program older than the parts of a layer cannot run this family: the
    # import fails and the run is refused before anything is built
    from deeplearning4j_tpu.models.hybrid import (HybridConfig, HybridLM,
                                                  LayerSpec, Part)
    from deeplearning4j_tpu.parallel.moe import RoutedExpertsConfig
    if (cfg["attention_method"] != "MLA" or cfg["attention_bias"]
            or cfg["zero_expert_type"] != "identity"
            or cfg["router_width"] != cfg["n_routed_experts_published"]
            + cfg["zero_expert_num"]
            or not (cfg["mla_scale_q_lora"] and cfg["mla_scale_kv_lora"])):
        raise ValueError("this adapter describes latent attention without "
                         "bias behind both scaled bottlenecks and a router "
                         "over the published experts and identity experts "
                         "only")
    layer = LayerSpec(parts=tuple(Part(*p) for p in PARTS))
    hc = HybridConfig(
        vocab_size=cfg["vocab_size"], d_model=cfg["hidden_size"],
        layers=(layer,) * cfg["num_layers"], max_len=cfg["n_positions"],
        experts=RoutedExpertsConfig(
            router_width=cfg["router_width"], top_k=cfg["moe_topk"],
            held=held(cfg), scale=cfg["routed_scaling_factor"],
            renormalize=False, form="swiglu", score="softmax", shared=False,
            identity=cfg["zero_expert_num"]),
        rms_eps=cfg["rms_norm_eps"],
        dtype=jnp.dtype(cfg["compute_dtype"]),
        param_dtype=jnp.dtype(cfg["param_dtype"]),
        mla_heads=cfg["num_attention_heads"],
        qk_nope_dim=cfg["qk_nope_head_dim"],
        qk_rope_dim=cfg["qk_rope_head_dim"], rope_theta=cfg["rope_theta"],
        v_head_dim=cfg["v_head_dim"], kv_lora_rank=cfg["kv_lora_rank"],
        q_lora_rank=cfg["q_lora_rank"],
        mla_scale_q_lora=cfg["mla_scale_q_lora"],
        mla_scale_kv_lora=cfg["mla_scale_kv_lora"],
        dense_ff=cfg["ffn_hidden_size"],
        expert_ff=cfg["expert_ffn_hidden_size"])
    return HybridLM(hc, mesh)


def _draws(key, cfg):
    dt = jnp.dtype(cfg["param_dtype"])
    keys = iter(jax.random.split(key, 16))

    def normal(shape, std, mean=0.0, dtype=dt):
        return (mean + std * jax.random.normal(next(keys), shape,
                                               jnp.float32)).astype(dtype)

    return normal


def _mla(key, cfg):
    d, H = cfg["hidden_size"], cfg["num_attention_heads"]
    rq, R = cfg["q_lora_rank"], cfg["kv_lora_rank"]
    dn, dr, dv = (cfg["qk_nope_head_dim"], cfg["qk_rope_head_dim"],
                  cfg["v_head_dim"])
    w = cfg["weights"]
    normal = _draws(key, cfg)
    # the second matrix of each bottleneck undoes its fan-in and its scale
    # (sqrt(d / rank)), so that q, k and v are of unit size an element
    return {"w_qa": normal((d, rq), w["in_std"]),
            "q_norm": normal((rq,), 0.1, 1.0),
            "w_qb": normal((rq, H * (dn + dr)), 1 / math.sqrt(d)),
            "w_kva": normal((d, R + dr), w["in_std"]),
            "kv_norm": normal((R,), 0.1, 1.0),
            "w_kvb": normal((R, H * (dn + dv)), 1 / math.sqrt(d)),
            "w_o": normal((H * dv, d), w["resid_std"])}


def _dense(key, cfg):
    d, f = cfg["hidden_size"], cfg["ffn_hidden_size"]
    normal = _draws(key, cfg)
    return {"w_gu": normal((d, 2 * f), cfg["weights"]["in_std"]),
            "w_down": normal((f, d), cfg["weights"]["resid_std"])}


def _moe_first(key, cfg):
    d, f, E = (cfg["hidden_size"], cfg["expert_ffn_hidden_size"],
               cfg["router_width"])
    w = cfg["weights"]
    normal = _draws(key, cfg)
    return {"w_router": normal((d, E), w["router_std"]),
            "b_select": normal((E,), w["b_select_std"], 0.0, jnp.float32),
            "w_gu": normal((held(cfg)[1], d, 2 * f), w["in_std"])}


def _moe_second(key, cfg):
    d, f = cfg["hidden_size"], cfg["expert_ffn_hidden_size"]
    normal = _draws(key, cfg)
    return {"w_down": normal((held(cfg)[1], f, d),
                             cfg["weights"]["expert_down_std"])}


def _gains(key, cfg):
    normal = _draws(key, cfg)
    return {n: normal((cfg["hidden_size"],), 0.1, 1.0)
            for _k, _p, n, _l in PARTS if n}


def _ends(key, cfg: dict):
    d, V = cfg["hidden_size"], cfg["vocab_size"]
    normal = _draws(key, cfg)
    return {"tok_emb": normal((V, d), cfg["weights"]["embedding_std"]),
            "head": normal((d, V), cfg["weights"]["in_std"]),
            "ln_f": normal((d,), 0.1, 1.0)}


def _parts(cfg):
    """[(layer or None, key in the layer's block or None, function of a
    key)] in the order the keys are drawn."""
    out = [(None, None, lambda k: _ends(k, cfg))]
    for i in range(cfg["num_layers"]):
        out.append((i, None, lambda k: _gains(k, cfg)))
        for kind, name, _norm, _lands in PARTS:
            if kind == "moe":
                out += [(i, name, lambda k: _moe_first(k, cfg)),
                        (i, name, lambda k: _moe_second(k, cfg))]
            else:
                out.append((i, name, lambda k, f=_mla if kind == "mla"
                            else _dense: f(k, cfg)))
    return out


def _assemble(cfg, make):
    """The tree ``HybridLM`` takes from ``make(n, fn)`` of every part."""
    out = {"blocks": [{} for _ in range(cfg["num_layers"])]}
    for n, (layer, name, fn) in enumerate(_parts(cfg)):
        tree = make(n, fn)
        if layer is None:
            out.update(tree)
        elif name is None:
            out["blocks"][layer].update(tree)
        else:
            out["blocks"][layer].setdefault(name, {}).update(tree)
    return out


def make_weights(cfg: dict, seed: int, shardings=None):
    """bfloat16 weights on the device, a jitted call a part from the seed."""
    if shardings is not None:
        raise ValueError("this family is served on one chip")
    key = jax.random.key(int(seed))
    return _assemble(cfg, lambda n, fn: jax.jit(fn)(jax.random.fold_in(key,
                                                                       n)))


def weight_shapes(cfg: dict):
    return _assemble(cfg, lambda n, fn: jax.eval_shape(fn,
                                                       jax.random.key(0)))
