"""The plain reference of the GPT-2 block as the configuration file describes
it: forward pass, loss, gradients and AdamW in straightforward ``jax.numpy``,
float32, every matmul at ``Precision.HIGHEST``, no cache, no kernels.

It imports nothing of the program and takes nothing the program has made: the
weights it is given are the benchmark's own (``perfbench/models/gpt2.py``).
It follows the program's equations with the departures from GPT-2 that the
configuration file lists (no attention biases, dropout 0). Layers run under
``lax.scan`` with ``jax.checkpoint`` and batches in blocks of rows, which
changes no value and lets the published sizes fit beside nothing else.

``lowp=True`` is the control, not the reference: the same mathematics with
both operands of every matmul rounded to float8 (e4m3, one scale a tensor) -
the nearest precision below the bfloat16 the configurations state. The
comparison that decides ``correct`` has to fail it (PERF.md, section 2).
"""
from __future__ import annotations

import math

import jax
import jax.numpy as jnp
import numpy as np
from jax import lax

_HI = lax.Precision.HIGHEST
_F8_MAX = 448.0


def _round_f8(x):
    """x with float8 e4m3 values, the cotangent passed through unrounded."""
    scale = jnp.maximum(jnp.max(jnp.abs(x)), 1e-30) / _F8_MAX
    q = (x / scale).astype(jnp.float8_e4m3fn).astype(jnp.float32) * scale
    return x + lax.stop_gradient(q - x)


def _mm(spec, a, b, lowp):
    if lowp:
        a, b = _round_f8(a), _round_f8(b)
    return jnp.einsum(spec, a, b, precision=_HI)


def _layer_norm(x, p, eps):
    mu = jnp.mean(x, axis=-1, keepdims=True)
    var = jnp.mean(jnp.square(x - mu), axis=-1, keepdims=True)
    return (x - mu) / jnp.sqrt(var + eps) * p["g"] + p["b"]


def _gelu_new(x):
    return 0.5 * x * (1.0 + jnp.tanh(
        math.sqrt(2.0 / math.pi) * (x + 0.044715 * x ** 3)))


def _block(x, blk, n_head, eps, lowp):
    b, t, d = x.shape
    hd = d // n_head
    h = _layer_norm(x, blk["ln1"], eps)
    qkv = _mm("btc,cf->btf", h, blk["attn"]["wqkv"], lowp)
    q, k, v = (a.reshape(b, t, n_head, hd) for a in jnp.split(qkv, 3, -1))
    s = _mm("bqhd,bkhd->bhqk", q, k, lowp) / math.sqrt(hd)
    causal = jnp.arange(t)[:, None] >= jnp.arange(t)[None, :]
    s = jnp.where(causal[None, None], s, -jnp.inf)
    p = jax.nn.softmax(s, axis=-1)
    o = _mm("bhqk,bkhd->bqhd", p, v, lowp).reshape(b, t, d)
    x = x + _mm("btc,cf->btf", o, blk["attn"]["wo"], lowp)
    h = _layer_norm(x, blk["ln2"], eps)
    up = _mm("btc,cf->btf", h, blk["mlp"]["w_up"], lowp) + blk["mlp"]["b_up"]
    down = _mm("btf,fc->btc", _gelu_new(up), blk["mlp"]["w_down"], lowp)
    return x + down + blk["mlp"]["b_down"]


def hidden(params, tokens, cfg, lowp=False):
    """tokens (B, T) -> the final LayerNorm's output (B, T, d), float32."""
    t = tokens.shape[1]
    x = params["tok_emb"][tokens] + params["pos_emb"][:t]
    stacked = jax.tree.map(lambda *a: jnp.stack(a), *params["blocks"])
    n_head, eps = cfg["n_head"], cfg["layer_norm_epsilon"]

    @jax.checkpoint
    def body(x, blk):
        return _block(x, blk, n_head, eps, lowp), None

    x, _ = lax.scan(body, x, stacked)
    return _layer_norm(x, params["ln_f"], eps)


def logits(params, tokens, cfg, lowp=False):
    return _mm("btc,vc->btv", hidden(params, tokens, cfg, lowp),
               params["tok_emb"], lowp)


def loss_sum(params, tokens, targets, cfg, lowp=False):
    """Sum over tokens of the next-token cross-entropy."""
    lg = logits(params, tokens, cfg, lowp)
    lse = jax.scipy.special.logsumexp(lg, axis=-1)
    correct = jnp.take_along_axis(lg, targets[..., None], axis=-1)[..., 0]
    return jnp.sum(lse - correct)


# ------------------------------------------------------------- training
def leaf_norms(tree):
    return jnp.stack([jnp.sqrt(jnp.sum(jnp.square(a.astype(jnp.float32))))
                      for a in jax.tree.leaves(tree)])


def train_steps(params, batches, cfg, hp, rows=4, lowp=False, keep=()):
    """Follow AdamW from ``params`` through ``batches`` (a list of
    (tokens, targets), each (B, T)); gradients are accumulated over blocks
    of ``rows`` rows. Returns the loss of every step, the norm of every
    leaf of the first gradient, the norm of every leaf's change over all
    the steps - leaves in ``jax.tree.leaves`` order - and the first
    gradient's leaves whose indices ``keep`` lists."""
    b1, b2, eps = hp["b1"], hp["b2"], hp["eps"]
    lr, wd = hp["lr"], hp["weight_decay"]

    @jax.jit
    def grads_of(p, toks, tgts):
        n = toks.shape[0] * toks.shape[1]
        blocks = (toks.reshape(-1, rows, toks.shape[1]),
                  tgts.reshape(-1, rows, tgts.shape[1]))

        def body(acc, blk):
            val, g = jax.value_and_grad(loss_sum)(p, blk[0], blk[1], cfg,
                                                  lowp)
            return (acc[0] + val, jax.tree.map(jnp.add, acc[1], g)), None

        zero = (jnp.zeros((), jnp.float32), jax.tree.map(jnp.zeros_like, p))
        (total, g), _ = lax.scan(body, zero, blocks)
        return total / n, jax.tree.map(lambda a: a / n, g)

    def adamw(p, mu, nu, g, count):
        mu = jax.tree.map(lambda m, a: b1 * m + (1 - b1) * a, mu, g)
        nu = jax.tree.map(lambda v, a: b2 * v + (1 - b2) * a * a, nu, g)
        c1, c2 = 1 - b1 ** count, 1 - b2 ** count
        p = jax.tree.map(
            lambda w, m, v: w - lr * ((m / c1) / (jnp.sqrt(v / c2) + eps)
                                      + wd * w), p, mu, nu)
        return p, mu, nu

    adamw = jax.jit(adamw, donate_argnums=(0, 1, 2))
    p = jax.jit(lambda t: jax.tree.map(jnp.copy, t))(params)
    mu = jax.jit(lambda t: jax.tree.map(jnp.zeros_like, t))(params)
    nu = jax.jit(lambda t: jax.tree.map(jnp.zeros_like, t))(params)
    losses, grad_norms, grad_leaves = [], None, []
    for i, (toks, tgts) in enumerate(batches):
        loss, g = grads_of(p, toks, tgts)
        if grad_norms is None:
            grad_norms = np.asarray(jax.jit(leaf_norms)(g))
            leaves = jax.tree.leaves(g)
            grad_leaves = [leaves[i] for i in keep]
            del leaves
        p, mu, nu = adamw(p, mu, nu, g, jnp.asarray(i + 1, jnp.float32))
        losses.append(float(loss))
    delta = jax.jit(lambda a, b: leaf_norms(
        jax.tree.map(jnp.subtract, a, b)))(p, params)
    return {"losses": losses, "grad_norms": grad_norms,
            "delta_norms": np.asarray(delta), "grad_leaves": grad_leaves}


def leaf_errors(leaves, ref_leaves):
    """Norm of every leaf's difference from the reference's leaf."""
    return jnp.stack([jnp.sqrt(jnp.sum(jnp.square(
        a.astype(jnp.float32) - b))) for a, b in zip(leaves, ref_leaves)])


# -------------------------------------------------------------- serving
def next_token_gaps(params, seqs, cands, cfg):
    """For every position i of every row: the reference's best logit there
    less its logit of ``cands[row, i]`` (the token that was served, or that
    a control puts first, as the one after position i). (N, T) float32."""
    def rows(p, s, c):          # the weights are an argument, never a
        def one(args):          # constant folded into the program
            seq, cand = args
            lg = logits(p, seq[None], cfg)[0]
            return jnp.max(lg, -1) - jnp.take_along_axis(
                lg, cand[:, None], axis=-1)[:, 0]
        return lax.map(one, (s, c))
    return jax.jit(rows)(params, seqs, cands)


def next_token_argmax(params, seqs, cfg, lowp):
    """The token the forward pass puts first after every position."""
    def rows(p, s):
        return lax.map(
            lambda seq: jnp.argmax(logits(p, seq[None], cfg, lowp)[0], -1), s)
    return jax.jit(rows)(params, seqs)
