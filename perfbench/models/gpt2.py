"""The GPT-2 family as the program runs it: configuration file -> the
program's ``TransformerLM``, and the weights, made by the benchmark.

This is the one place where a configuration file's keys meet the program's
constructor. The weights are the benchmark's own (not the program's
initialiser): one jitted call from the seed, float32, in the layout
``TransformerLM`` takes, so the program and the plain reference are handed
the same numbers and neither makes them.
"""
from __future__ import annotations

import math

import jax
import jax.numpy as jnp


def sizes(cfg: dict) -> dict:
    """The few numbers every user of a GPT-2 configuration needs."""
    d = cfg["n_embd"]
    return {"L": cfg["n_layer"], "d": d, "h": cfg["n_head"],
            "f": cfg.get("n_inner") or 4 * d, "V": cfg["vocab_size"],
            "T": cfg["n_positions"], "eps": cfg["layer_norm_epsilon"]}


def build_model(cfg: dict, mesh=None):
    """The program's model object for this configuration."""
    from deeplearning4j_tpu.models.transformer import (TransformerConfig,
                                                       TransformerLM)
    s = sizes(cfg)
    if cfg["activation_function"] != "gelu_new" or s["eps"] != 1e-5:
        raise ValueError("models/transformer.py computes tanh-GELU and "
                         "LayerNorm epsilon 1e-5 only")
    tc = TransformerConfig(
        vocab_size=s["V"], n_layers=s["L"], n_heads=s["h"], d_model=s["d"],
        d_ff=s["f"], max_len=s["T"], dtype=jnp.dtype(cfg["compute_dtype"]),
        fused_qkv=bool(cfg["fused_qkv"]))
    return TransformerLM(tc, mesh)


def _init(key, cfg: dict):
    s = sizes(cfg)
    L, d, f, V, T = s["L"], s["d"], s["f"], s["V"], s["T"]
    resid = 0.02 / math.sqrt(2 * L)
    keys = iter(jax.random.split(key, 4 + 12 * L))

    def normal(shape, std, mean=0.0):
        return mean + std * jax.random.normal(next(keys), shape, jnp.float32)

    def ln():
        return {"g": normal((d,), 0.1, 1.0), "b": normal((d,), 0.02)}

    params = {"tok_emb": normal((V, d), 0.02), "pos_emb": normal((T, d), 0.02),
              "ln_f": ln(), "blocks": []}
    for _ in range(L):
        params["blocks"].append({
            "ln1": ln(), "ln2": ln(),
            "attn": {"wqkv": normal((d, 3 * d), 0.02),
                     "wo": normal((d, d), resid)},
            "mlp": {"w_up": normal((d, f), 0.02), "b_up": normal((f,), 0.02),
                    "w_down": normal((f, d), resid),
                    "b_down": normal((d,), 0.02)}})
    return params


def make_weights(cfg: dict, seed: int, shardings=None):
    """Float32 weights on the device(s), one jitted call from the seed."""
    fn = jax.jit(lambda key: _init(key, cfg), out_shardings=shardings)
    return fn(jax.random.key(int(seed)))


def weight_shapes(cfg: dict):
    return jax.eval_shape(lambda key: _init(key, cfg), jax.random.key(0))
