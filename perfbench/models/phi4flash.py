"""The Phi-4-flash family (SambaY with differential attention) as the program
runs it: configuration file -> the program's ``HybridLM``, and the weights,
made by the benchmark.

This is the one place where a configuration file's keys meet the program's
constructor. With ``H = num_hidden_layers / 2`` the layers are (the published
rule, ``assumed.layers`` in the file): even layers up to ``H`` a Mamba-1
mixer, of which layer ``H`` hands on its scan's output as the memory; odd
layers below ``H`` window attention; layer ``H + 1`` full attention, whose
paged rows are the model's only pages; behind it, even layers a gated memory
unit on layer ``H``'s rows and odd layers queries alone over layer ``H + 1``'s
rows. Every layer has a dense SwiGLU feed-forward, LayerNorm with gain and
bias in front of each part; the head is the embedding. A prefill runs the
layers behind ``H + 1`` on the prompt's last row. A ``config.json`` that asks
for anything this adapter does not hand to the program is refused.

The weights are the benchmark's own (not the program's initialiser): from the
seed, a jitted call a part of a layer (the float32 draws of one part, at most
the 2.05 GB of the embedding, are all that is live beside what is kept),
stored bfloat16 in the layout ``HybridLM`` takes; the program and the plain
reference are handed the same numbers and neither makes them. Every term is
non-trivial (gains, biases, the decays a channel, the skip, the lambdas), so
that a dropped one shows; the distributions are under ``assumed.weights`` in
the configuration's file. ``a_log`` is stored (state, channels), as the
program's state lies; ``lambda_init`` is the published function of the layer's
depth, stored with the layer it belongs to.
"""
from __future__ import annotations

import math

import jax
import jax.numpy as jnp

MEMORY, SHARED = "memory", "shared_kv"


def layer_kinds(cfg: dict):
    """[mixer kind] of every layer: ``mamba1``, ``swa``, ``gqa``, ``gmu``,
    ``xattn``."""
    n, half = cfg["num_hidden_layers"], cfg["num_hidden_layers"] // 2
    if n % 4 or cfg["mb_per_layer"] != 2:
        raise ValueError("the layer rule needs num_hidden_layers a multiple "
                         "of 4 and a Mamba layer every second layer")
    out = []
    for i in range(n):
        if i % 2 == 0:
            out.append("mamba1" if i <= half else "gmu")
        else:
            out.append("swa" if i < half else
                       "gqa" if i == half + 1 else "xattn")
    return out


def lambda_init(layer: int) -> float:
    return 0.8 - 0.6 * math.exp(-0.3 * layer)


def build_model(cfg: dict, mesh=None):
    """The program's model object for this configuration."""
    # a program older than the Mamba-1 kind (and with it the layers that
    # read another layer's pages or rows, LayerNorm and the tied head) cannot
    # run this family: the import fails and the run is refused before
    # anything is built
    from deeplearning4j_tpu.models.hybrid import (HybridConfig, HybridLM,
                                                  LayerSpec, mamba1_step)  # noqa
    if (cfg["model_type"] != "phi4flash" or cfg["hidden_act"] != "silu"
            or not cfg["tie_word_embeddings"] or cfg["mlp_bias"]
            or cfg["lm_head_bias"] or cfg["embd_pdrop"]
            or cfg["resid_pdrop"]
            or cfg["mamba_expand"] * cfg["hidden_size"] != cfg["mamba_d_inner"]
            or cfg["hidden_size"] % cfg["num_attention_heads"]):
        raise ValueError("this adapter describes the SambaY decoder with "
                         "differential attention, SiLU, a tied head without "
                         "bias, no dropout and feed-forwards without bias "
                         "only")
    kinds = layer_kinds(cfg)
    half = cfg["num_hidden_layers"] // 2
    layers = tuple(
        LayerSpec(k, "dense", tag={half: MEMORY, half + 1: SHARED}.get(i),
                  source={"gmu": MEMORY, "xattn": SHARED}.get(k))
        for i, k in enumerate(kinds))
    hc = HybridConfig(
        vocab_size=cfg["vocab_size"], d_model=cfg["hidden_size"],
        layers=layers, max_len=cfg["n_positions"],
        rms_eps=cfg["layer_norm_eps"], norm="layer", tie_embeddings=True,
        last_row_from=half + 2,
        dtype=jnp.dtype(cfg["compute_dtype"]),
        param_dtype=jnp.dtype(cfg["param_dtype"]),
        m1_inner=cfg["mamba_d_inner"], m1_state=cfg["mamba_d_state"],
        m1_conv=cfg["mamba_d_conv"], m1_dt_rank=cfg["mamba_dt_rank"],
        gqa_heads=cfg["num_attention_heads"],
        gqa_kv_heads=cfg["num_key_value_heads"],
        gqa_head_dim=cfg["hidden_size"] // cfg["num_attention_heads"],
        swa_heads=cfg["num_attention_heads"],
        swa_window=cfg["sliding_window"],
        differential=True, attn_bias=True,
        dense_ff=cfg["intermediate_size"])
    return HybridLM(hc, mesh)


def _draws(key, cfg):
    dt = jnp.dtype(cfg["param_dtype"])
    keys = iter(jax.random.split(key, 24))

    def normal(shape, std, mean=0.0, dtype=dt):
        return (mean + std * jax.random.normal(next(keys), shape,
                                               jnp.float32)).astype(dtype)

    return normal


def _norm(normal, cfg):
    d, w = cfg["hidden_size"], cfg["weights"]
    return {"g": normal((d,), w["gain_std"], 1.0),
            "b": normal((d,), w["norm_bias_std"])}


def _attention(key, cfg, query_only, l0):
    """``l0``: the layer's ``lambda_init``, an argument so that the layers of
    a kind share one compiled draw."""
    d, H, g = (cfg["hidden_size"], cfg["num_attention_heads"],
               cfg["num_key_value_heads"])
    hd, w = d // H, cfg["weights"]
    normal = _draws(key, cfg)
    own = {} if query_only else {
        "w_kv": normal((d, 2 * g * hd), w["in_std"]),
        "b_kv": normal((2 * g * hd,), w["bias_std"])}
    return {"ln1": _norm(normal, cfg), "mixer": {
        "w_q": normal((d, H * hd), w["in_std"]),
        "b_q": normal((H * hd,), w["bias_std"]), **own,
        "w_o": normal((H * hd, d), w["resid_std"]),
        "b_o": normal((d,), w["out_bias_std"]),
        **{n: normal((hd,), w["lambda_std"], 0.0, jnp.float32) for n in (
            "lambda_q1", "lambda_k1", "lambda_q2", "lambda_k2")},
        "lambda_init": jnp.asarray(l0, jnp.float32),
        "sub_norm": normal((2 * hd,), w["gain_std"], 1.0)}}


def _mamba(key, cfg):
    d, C, N, r = (cfg["hidden_size"], cfg["mamba_d_inner"],
                  cfg["mamba_d_state"], cfg["mamba_dt_rank"])
    w = cfg["weights"]
    normal = _draws(key, cfg)
    f32 = jnp.float32
    return {"ln1": _norm(normal, cfg), "mixer": {
        "w_in": normal((d, 2 * C), w["in_std"]),
        "conv": normal((cfg["mamba_d_conv"], C), w["conv_std"]),
        "b_conv": normal((C,), w["bias_std"]),
        "w_x": normal((C, r + 2 * N), w["in_std"]),
        "w_dt": normal((r, C), r ** -0.5),
        "b_dt": normal((C,), w["dt_bias_std"], w["dt_bias_mean"], f32),
        # log(1 .. N) a state dimension, moved a channel: (N, C)
        "a_log": normal((N, C), w["a_log_std"], 0.0, f32) + jnp.log(
            jnp.arange(1, N + 1, dtype=f32))[:, None],
        "d_skip": normal((C,), w["gain_std"], 1.0, f32),
        "w_out": normal((C, d), w["resid_std"])}}


def _gmu(key, cfg):
    d, C, w = cfg["hidden_size"], cfg["mamba_d_inner"], cfg["weights"]
    normal = _draws(key, cfg)
    return {"ln1": _norm(normal, cfg), "mixer": {
        "w_in": normal((d, C), w["in_std"]),
        "w_out": normal((C, d), w["resid_std"])}}


def _ffn(key, cfg):
    d, f, w = cfg["hidden_size"], cfg["intermediate_size"], cfg["weights"]
    normal = _draws(key, cfg)
    return {"ln2": _norm(normal, cfg), "ffn": {
        "w_gu": normal((d, 2 * f), w["in_std"]),
        "w_down": normal((f, d), w["resid_std"])}}


def _ends(key, cfg):
    normal = _draws(key, cfg)
    return {"tok_emb": normal((cfg["vocab_size"], cfg["hidden_size"]),
                              cfg["weights"]["embedding_std"]),
            "ln_f": _norm(normal, cfg)}


#: the draws of one part of a layer (or of the model's two ends), by name
_MAKERS = {"ends": _ends, "mamba": _mamba, "gmu": _gmu,
           "attention": _attention, "ffn": _ffn}


def _parts(cfg):
    """[(layer or None, maker, its static arguments, its traced ones)] in
    the order the keys are drawn."""
    out = [(None, "ends", (), ())]
    for i, kind in enumerate(layer_kinds(cfg)):
        if kind == "mamba1":
            out.append((i, "mamba", (), ()))
        elif kind == "gmu":
            out.append((i, "gmu", (), ()))
        else:
            out.append((i, "attention", (kind == "xattn",),
                        (lambda_init(i),)))
        out.append((i, "ffn", (), ()))
    return out


def _assemble(cfg, make):
    """The tree ``HybridLM`` takes from ``make(n, maker, static, traced)`` of
    every part."""
    out = {"blocks": [{} for _ in range(cfg["num_hidden_layers"])]}
    for n, (layer, *part) in enumerate(_parts(cfg)):
        (out if layer is None else out["blocks"][layer]).update(
            make(n, *part))
    return out


def make_weights(cfg: dict, seed: int, shardings=None):
    """bfloat16 weights on the device from the seed: a jitted call a part,
    one compiled draw a KIND of part (5 programs for 65 parts)."""
    if shardings is not None:
        raise ValueError("this family is served on one chip")
    key = jax.random.key(int(seed))
    jitted = {}

    def make(n, maker, static, traced):
        if (maker, static) not in jitted:
            jitted[maker, static] = jax.jit(
                lambda k, *a: _MAKERS[maker](k, cfg, *static, *a))
        return jitted[maker, static](jax.random.fold_in(key, n), *traced)

    return _assemble(cfg, make)


def weight_shapes(cfg: dict):
    return _assemble(cfg, lambda n, maker, static, traced: jax.eval_shape(
        lambda k: _MAKERS[maker](k, cfg, *static, *traced),
        jax.random.key(0)))
