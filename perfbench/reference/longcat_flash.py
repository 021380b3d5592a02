"""The plain reference of the LongCat-Flash double layer as the configuration
file describes it: straightforward ``jax.numpy``, float32, every matmul at
``Precision.HIGHEST``, no cache, no kernels, no batching.

It imports nothing of the program. It is handed the tree the program serves
(bfloat16, made by ``perfbench/models/longcat_flash.py``) and upcasts what it
touches as it touches it: one part of a layer at a time, one expert at a
time, the head and the attention's queries in blocks of positions, so that it
fits beside the served weights. The layer (ISSUE 38; x the float32 residual
stream, ``rms`` RMSNorm with a gain of its own at each place):

    a0 = x  + MLA_0(rms_a0(x))
    h0 = rms_f0(a0)
    m  = MoE(h0)                        # the shortcut: computed from h0 ...
    b0 = a0 + FFN_0(h0)                 # SwiGLU
    a1 = b0 + MLA_1(rms_a1(b0))
    y  = a1 + FFN_1(rms_f1(a1)) + m     # ... added at the end of the layer

- MLA(h) at position t: ``c_q = rms(h W_qa) sqrt(d / q_lora_rank)``,
  ``q = c_q W_qb`` -> heads x (nope | rope); ``[c_kv | k_r] = h W_kva``,
  ``c = rms(c_kv) sqrt(d / kv_lora_rank)``, ``[k_n | v] = c W_kvb`` -> heads x
  (nope | v); the rope parts of every query head and the one ``k_r`` the heads
  share are rotated by t (adjacent pairs (2i, 2i + 1) by ``t theta^(-2i/r)``);
  ``softmax((q_n k_n + q_r k_r) / sqrt(nope + rope), causal) v``, then ``W_o``.
  Expanded form, every position's keys and values made from its latent row.
- MoE(h): ``s = softmax(h W_r)`` over all ``router_width`` outputs; the
  ``moe_topk`` largest of ``s + b_select`` chosen; ``w_e = scale s_e`` at the
  chosen, NOT renormalised; no shared expert;
  ``m = sum over chosen AND held e of w_e SwiGLU_e(h)  +  (sum over chosen
  e >= router_width - zero_expert_num of w_e) h``: the last
  ``zero_expert_num`` outputs are identity experts. What the absent experts
  would add is left out (``held`` = ``experts_held_first`` .. +
  ``n_routed_experts``); the identity part is computed here, where the token
  is.

Final RMSNorm, untied head. ``lowp=True`` is the control, not the reference:
the same mathematics with both operands of every matmul rounded to float8
(e4m3, one scale a tensor).
"""
from __future__ import annotations

import math

import jax
import jax.numpy as jnp
from jax import lax

_HI = lax.Precision.HIGHEST
_F8_MAX = 448.0
_BLOCK = 512            # positions a block of queries, or of the head


def _round_f8(x):
    scale = jnp.maximum(jnp.max(jnp.abs(x)), 1e-30) / _F8_MAX
    return (x / scale).astype(jnp.float8_e4m3fn).astype(jnp.float32) * scale


def _mm(spec, a, b, lowp):
    a, b = a.astype(jnp.float32), b.astype(jnp.float32)
    if lowp:
        a, b = _round_f8(a), _round_f8(b)
    return jnp.einsum(spec, a, b, precision=_HI)


def _rms(x, g, eps):
    return x / jnp.sqrt(jnp.mean(jnp.square(x), -1, keepdims=True) + eps) \
        * g.astype(jnp.float32)


def _swiglu(x, w_gu, w_down, lowp):
    h = _mm("tc,cf->tf", x, w_gu, lowp)
    f = h.shape[-1] // 2
    return _mm("tf,fc->tc", jax.nn.silu(h[:, :f]) * h[:, f:], w_down, lowp)


def _rotate(x, theta):
    """x (T, ..., r), row t at position t: the pair (x[2i], x[2i+1]) turned
    by the angle ``t theta^(-2i / r)``."""
    T, r = x.shape[0], x.shape[-1]
    ang = jnp.arange(T, dtype=jnp.float32)[:, None] \
        * theta ** (-jnp.arange(0, r, 2, dtype=jnp.float32) / r)  # (T, r/2)
    ang = ang.reshape(T, *([1] * (x.ndim - 2)), r // 2)
    pairs = x.reshape(*x.shape[:-1], r // 2, 2)
    a, b = pairs[..., 0], pairs[..., 1]
    return jnp.stack([a * jnp.cos(ang) - b * jnp.sin(ang),
                      a * jnp.sin(ang) + b * jnp.cos(ang)],
                     axis=-1).reshape(x.shape)


def _mla(x, p, cfg, lowp):
    """x (T, d) -> (T, d)."""
    H, d = cfg["num_attention_heads"], cfg["hidden_size"]
    dn, dr, dv = (cfg["qk_nope_head_dim"], cfg["qk_rope_head_dim"],
                  cfg["v_head_dim"])
    R, eps, theta = cfg["kv_lora_rank"], cfg["rms_norm_eps"], \
        cfg["rope_theta"]
    T = x.shape[0]
    cq = _rms(_mm("tc,cr->tr", x, p["w_qa"], lowp), p["q_norm"], eps)
    if cfg["mla_scale_q_lora"]:
        cq = cq * math.sqrt(d / cfg["q_lora_rank"])
    q = _mm("tr,rf->tf", cq, p["w_qb"], lowp).reshape(T, H, dn + dr)
    kva = _mm("tc,cf->tf", x, p["w_kva"], lowp)
    c = _rms(kva[:, :R], p["kv_norm"], eps)
    if cfg["mla_scale_kv_lora"]:
        c = c * math.sqrt(d / R)
    kvb = _mm("tc,cf->tf", c, p["w_kvb"], lowp).reshape(T, H, dn + dv)
    q = jnp.concatenate([q[..., :dn], _rotate(q[..., dn:], theta)], -1)
    k_r = _rotate(kva[:, R:], theta)
    k = jnp.concatenate([kvb[..., :dn],
                         jnp.broadcast_to(k_r[:, None, :], (T, H, dr))], -1)
    v = kvb[..., dn:]
    pad = -T % _BLOCK
    qb = jnp.pad(q, ((0, pad), (0, 0), (0, 0))).reshape(-1, _BLOCK, H,
                                                        dn + dr)

    def block(args):
        q_b, i0 = args
        s = _mm("qhd,khd->hqk", q_b, k, lowp) / math.sqrt(dn + dr)
        causal = (i0 + jnp.arange(_BLOCK))[:, None] >= jnp.arange(T)[None, :]
        pr = jax.nn.softmax(jnp.where(causal[None], s, -jnp.inf), axis=-1)
        return _mm("hqk,khd->qhd", pr, v, lowp)

    o = lax.map(block, (qb, jnp.arange(qb.shape[0]) * _BLOCK))
    return _mm("tf,fc->tc", o.reshape(-1, H * dv)[:T], p["w_o"], lowp)


def _moe(x, p, cfg, lowp):
    kk = cfg["moe_topk"]
    first = cfg.get("experts_held_first", 0)
    n_real = cfg["router_width"] - cfg["zero_expert_num"]
    s = jax.nn.softmax(_mm("tc,ce->te", x, p["w_router"], lowp), axis=-1)
    _, idx = lax.top_k(s + p["b_select"].astype(jnp.float32), kk)
    w = jnp.take_along_axis(s, idx, axis=-1) * cfg["routed_scaling_factor"]

    def one(acc, args):                 # a loop over the experts held here
        e, w_gu, w_down = args
        mine = jnp.sum(jnp.where(idx == e, w, 0.0), axis=-1)     # (T,)
        return acc + mine[:, None] * _swiglu(x, w_gu, w_down, lowp), None

    n_held = p["w_gu"].shape[0]
    routed, _ = lax.scan(one, jnp.zeros_like(x),
                         (first + jnp.arange(n_held), p["w_gu"], p["w_down"]))
    same = jnp.sum(jnp.where(idx >= n_real, w, 0.0), axis=-1)    # (T,)
    return routed + same[:, None] * x


def hidden(params, tokens, cfg, lowp=False):
    """tokens (T,) -> the last layer's output before the final norm."""
    x = params["tok_emb"][tokens].astype(jnp.float32)
    eps = cfg["rms_norm_eps"]
    for blk in params["blocks"]:
        a0 = x + _mla(_rms(x, blk["ln_a0"], eps), blk["attn0"], cfg, lowp)
        h0 = _rms(a0, blk["ln_f0"], eps)
        m = _moe(h0, blk["moe"], cfg, lowp)
        b0 = a0 + _swiglu(h0, blk["ffn0"]["w_gu"], blk["ffn0"]["w_down"],
                          lowp)
        a1 = b0 + _mla(_rms(b0, blk["ln_a1"], eps), blk["attn1"], cfg, lowp)
        x = a1 + _swiglu(_rms(a1, blk["ln_f1"], eps), blk["ffn1"]["w_gu"],
                         blk["ffn1"]["w_down"], lowp) + m
    return x


def _head_blocks(params, x, cfg, lowp, fn):
    """``fn(logits of a block of positions, block index)`` over blocks."""
    T = x.shape[0]
    x = _rms(x, params["ln_f"], cfg["rms_norm_eps"])
    pad = -T % _BLOCK
    xb = jnp.pad(x, ((0, pad), (0, 0))).reshape(-1, _BLOCK, x.shape[1])
    out = lax.map(lambda a: fn(_mm("tc,cv->tv", a[0], params["head"], lowp),
                               a[1]), (xb, jnp.arange(xb.shape[0])))
    return out.reshape(-1, *out.shape[2:])[:T]


def logits(params, tokens, cfg, lowp=False):
    """tokens (B, T) -> (B, T, V) float32: for the tests' small sizes."""
    return jnp.stack([
        _head_blocks(params, hidden(params, row, cfg, lowp), cfg, lowp,
                     lambda lg, _i: lg) for row in tokens])


# -------------------------------------------------------------- serving
def next_token_gaps(params, seqs, cands, cfg):
    """For every position i of every row: the reference's best logit there
    less its logit of ``cands[row, i]``. (N, T) float32."""
    def rows(p, s, c):
        def one(args):
            seq, cand = args
            cb = jnp.pad(cand, (0, -cand.shape[0] % _BLOCK)).reshape(
                -1, _BLOCK)
            return _head_blocks(
                p, hidden(p, seq, cfg), cfg, False,
                lambda lg, i: jnp.max(lg, -1) - jnp.take_along_axis(
                    lg, cb[i][:, None], axis=-1)[:, 0])
        return lax.map(one, (s, c))
    return jax.jit(rows)(params, seqs, cands)


def next_token_argmax(params, seqs, cfg, lowp):
    """The token the forward pass puts first after every position."""
    def rows(p, s):
        return lax.map(lambda seq: _head_blocks(
            p, hidden(p, seq, cfg, lowp), cfg, lowp,
            lambda lg, _i: jnp.argmax(lg, -1).astype(jnp.int32)), s)
    return jax.jit(rows)(params, seqs)
