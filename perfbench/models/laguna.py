"""The Laguna family as the program runs it: configuration file -> the
program's ``HybridLM``, and the weights, made by the benchmark.

This is the one place where a configuration file's keys meet the program's
constructor. A layer is a grouped-query attention and a feed-forward, each
through a norm of its own; which attention (``full_attention``: every earlier
position, rows in pages; ``sliding_attention``: the last ``sliding_window``
positions, rows in a ring a slot), how many query heads and which
feed-forward (``dense`` | ``sparse``) are the first ``num_hidden_layers``
entries of ``layer_types``, ``num_attention_heads_per_layer`` and
``mlp_layer_types``, which are copied whole from the source. Each attention
kind has its own rotation (``rope_parameters``) and both are gated a head
(``gating``). A ``config.json`` that asks for anything this adapter does not
hand to the program is refused.

The weights are the benchmark's own (not the program's initialiser): from the
seed, a jitted call a part of a layer (so that the float32 draws of one part,
at most the 1.07 G of a layer's first expert matrices, are all that is live
beside what is kept), stored bfloat16 in the layout ``HybridLM`` takes; the
program and the plain reference are handed the same numbers and neither makes
them. Every term is non-trivial (both norms' gains, the gate, the router's
selection bias), so that a dropped one shows; the distributions and the
counts that led to them are under ``assumed.weights`` in the configuration's
file.
"""
from __future__ import annotations

import jax
import jax.numpy as jnp

MIXERS = {"full_attention": "gqa", "sliding_attention": "swa"}
FFNS = {"dense": "dense", "sparse": "moe"}


def layer_kinds(cfg: dict):
    """[(mixer, ffn, query heads)] of the layers that are run."""
    n = cfg["num_hidden_layers"]
    return [(MIXERS[a], FFNS[f], int(h)) for a, f, h in zip(
        cfg["layer_types"][:n], cfg["mlp_layer_types"][:n],
        cfg["num_attention_heads_per_layer"][:n])]


def heads_of(cfg: dict, mixer: str) -> int:
    """The one head count of an attention kind among the layers run."""
    heads = {h for m, _f, h in layer_kinds(cfg) if m == mixer}
    if len(heads) != 1:
        raise ValueError(f"the {mixer} layers' head counts {sorted(heads)} "
                         "are not one number a kind")
    return heads.pop()


def _rope(cfg: dict, kind: str):
    """``rope_parameters[kind]`` as the program's ``Rope``."""
    from deeplearning4j_tpu.models.hybrid import Rope
    r = cfg["rope_parameters"][kind]
    dims = int(round(cfg["head_dim"] * r["partial_rotary_factor"]))
    if r["rope_type"] == "default":
        return Rope(theta=float(r["rope_theta"]), dims=dims)
    if r["rope_type"] != "yarn":
        raise ValueError(f"rope_type {r['rope_type']!r} is not computed")
    return Rope(theta=float(r["rope_theta"]), dims=dims,
                amplitude=float(r["attention_factor"]),
                factor=float(r["factor"]),
                original=int(r["original_max_position_embeddings"]),
                beta_fast=float(r["beta_fast"]),
                beta_slow=float(r["beta_slow"]))


def build_model(cfg: dict, mesh=None):
    """The program's model object for this configuration."""
    # a program older than the window kind and the rotations of the
    # grouped-query kinds cannot run this family: the import fails and the
    # run is refused before anything is built
    from deeplearning4j_tpu.models.hybrid import (HybridConfig, HybridLM,
                                                  LayerSpec, Rope)  # noqa
    from deeplearning4j_tpu.parallel.moe import RoutedExpertsConfig
    kinds = layer_kinds(cfg)
    if (cfg["model_type"] != "laguna" or cfg["attention_bias"]
            or cfg["tie_word_embeddings"] or cfg["gating"] is not True
            or cfg["moe_apply_router_weight_on_input"]
            or len(kinds) != cfg["num_hidden_layers"]
            or cfg["num_attention_heads"] != heads_of(cfg, "gqa")
            or cfg["shared_expert_intermediate_size"]
            != cfg["moe_intermediate_size"]):
        raise ValueError("this adapter describes gated grouped-query "
                         "attention without bias, an untied head, router "
                         "weights on the experts' outputs and a shared "
                         "expert as wide as a routed one only")
    hc = HybridConfig(
        vocab_size=cfg["vocab_size"], d_model=cfg["hidden_size"],
        layers=tuple(LayerSpec(m, f) for m, f, _h in kinds),
        max_len=cfg["n_positions"],
        experts=RoutedExpertsConfig(
            router_width=cfg["num_experts"],
            top_k=cfg["num_experts_per_tok"],
            held=(0, cfg["num_experts"]),
            scale=cfg["moe_routed_scaling_factor"], renormalize=True),
        rms_eps=cfg["rms_norm_eps"],
        dtype=jnp.dtype(cfg["compute_dtype"]),
        param_dtype=jnp.dtype(cfg["param_dtype"]),
        gqa_heads=heads_of(cfg, "gqa"),
        gqa_kv_heads=cfg["num_key_value_heads"],
        gqa_head_dim=cfg["head_dim"],
        gqa_rope=_rope(cfg, "full_attention"), gqa_gated=True,
        swa_heads=heads_of(cfg, "swa"),
        swa_rope=_rope(cfg, "sliding_attention"), swa_gated=True,
        swa_window=cfg["sliding_window"],
        dense_ff=cfg["intermediate_size"],
        expert_ff=cfg["moe_intermediate_size"])
    return HybridLM(hc, mesh)


def _draws(key, cfg):
    dt = jnp.dtype(cfg["param_dtype"])
    keys = iter(jax.random.split(key, 16))

    def normal(shape, std, mean=0.0, dtype=dt):
        return (mean + std * jax.random.normal(next(keys), shape,
                                               jnp.float32)).astype(dtype)

    return normal


def _attention(key, cfg, heads):
    d, hd, g = (cfg["hidden_size"], cfg["head_dim"],
                cfg["num_key_value_heads"])
    w = cfg["weights"]
    normal = _draws(key, cfg)
    return {"ln1": normal((d,), w["gain_std"], 1.0),
            "ln2": normal((d,), w["gain_std"], 1.0),
            "mixer": {"w_q": normal((d, heads * hd), w["in_std"]),
                      "w_kv": normal((d, 2 * g * hd), w["in_std"]),
                      "w_gate": normal((d, heads), w["in_std"]),
                      "w_o": normal((heads * hd, d), w["resid_std"])}}


def _swiglu(normal, cfg, width, lead=()):
    d, w = cfg["hidden_size"], cfg["weights"]
    return {"w_gu": normal(lead + (d, 2 * width), w["in_std"]),
            "w_down": normal(lead + (width, d), w["resid_std"])}


def _dense(key, cfg):
    return {"ffn": _swiglu(_draws(key, cfg), cfg, cfg["intermediate_size"])}


def _moe_first(key, cfg):
    d, f, E = (cfg["hidden_size"], cfg["moe_intermediate_size"],
               cfg["num_experts"])
    w = cfg["weights"]
    normal = _draws(key, cfg)
    return {"ffn": {"w_router": normal((d, E), w["router_std"]),
                    "b_select": normal((E,), w["b_select_std"], 0.0,
                                       jnp.float32),
                    "w_gu": normal((E, d, 2 * f), w["in_std"])}}


def _moe_second(key, cfg):
    d, f, E = (cfg["hidden_size"], cfg["moe_intermediate_size"],
               cfg["num_experts"])
    normal = _draws(key, cfg)
    return {"ffn": {"w_down": normal((E, f, d), cfg["weights"]["resid_std"]),
                    "shared": _swiglu(
                        normal, cfg, cfg["shared_expert_intermediate_size"])}}


def _ends(key, cfg: dict):
    d, V = cfg["hidden_size"], cfg["vocab_size"]
    w = cfg["weights"]
    normal = _draws(key, cfg)
    return {"tok_emb": normal((V, d), w["embedding_std"]),
            "head": normal((d, V), w["in_std"]),
            "ln_f": normal((d,), w["gain_std"], 1.0)}


def _parts(cfg):
    """[(layer or None, function of a key)] in the order the keys are
    drawn."""
    out = [(None, lambda k: _ends(k, cfg))]
    for i, (_m, ffn, heads) in enumerate(layer_kinds(cfg)):
        out.append((i, lambda k, h=heads: _attention(k, cfg, h)))
        if ffn == "dense":
            out.append((i, lambda k: _dense(k, cfg)))
        else:
            out += [(i, lambda k: _moe_first(k, cfg)),
                    (i, lambda k: _moe_second(k, cfg))]
    return out


def _assemble(cfg, make):
    """The tree ``HybridLM`` takes from ``make(n, fn)`` of every part."""
    out = {"blocks": [{} for _ in range(cfg["num_hidden_layers"])]}
    for n, (layer, fn) in enumerate(_parts(cfg)):
        tree = make(n, fn)
        if layer is None:
            out.update(tree)
            continue
        for name, sub in tree.items():
            if isinstance(sub, dict):
                out["blocks"][layer].setdefault(name, {}).update(sub)
            else:
                out["blocks"][layer][name] = sub
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
