"""The plain reference of the Laguna layer as the configuration file describes
it: straightforward ``jax.numpy``, float32, every matmul at
``Precision.HIGHEST``, no cache, no kernels, no batching.

It imports nothing of the program. It is handed the tree the program serves
(bfloat16, made by ``perfbench/models/laguna.py``) and upcasts what it
touches as it touches it: one part of a layer at a time, one expert at a
time, the head and the attention's queries in blocks of positions, so that
7,168 positions at the published widths fit beside the served weights. The
layer (ISSUE 43; x the float32 residual stream, ``rms`` RMSNorm, eps
``rms_norm_eps``, a gain of its own at each place; l the source layer):

    a = x + Attn_l(rms1(x));   y = a + FFN_l(rms2(a))
    logits = rms_f(x) W_head                              (untied)

- Attn_l(h) at position t: H = ``num_attention_heads_per_layer[l]`` query
  heads on ``num_key_value_heads`` key/value heads of ``head_dim``, query
  head i on key/value head i // (H / Hkv); ``q = h W_q``, ``[k | v] = h
  W_kv``, no bias. The first ``partial_rotary_factor x head_dim`` dimensions
  of every q and k head are rotated by t in adjacent pairs (2i, 2i + 1) by
  the angle ``t inv_freq_i``, cos and sin times ``attention_factor`` where
  the layer's ``rope_parameters`` give one; the other dimensions are carried
  unrotated and unscaled. ``rope_type`` ``default``: ``inv_freq_i =
  theta^(-2i/d)`` over the d rotated dimensions. ``yarn``: with ``f_i`` that
  frequency, ``corr(n) = d ln(original / (2 pi n)) / (2 ln theta)``, ``low =
  max(floor(corr(beta_fast)), 0)``, ``high = min(ceil(corr(beta_slow)), d -
  1)``, ``ramp_i = clip((i - low) / (high - low), 0, 1)``: ``inv_freq_i =
  f_i (1 - ramp_i) + (f_i / factor) ramp_i``, the same at every position.
  ``p = softmax(q . k / sqrt(head_dim))`` over keys j <= t
  (``full_attention``) or t - ``sliding_window`` < j <= t
  (``sliding_attention``); ``g = sigmoid(h W_g)``, one scalar a head;
  ``Attn = concat_i(g_i sum_j p_ij v_j) W_o``.
- FFN_l: ``dense``: SwiGLU, ``down(silu(gate) * up)``. ``sparse``:
  ``s = sigmoid(h W_r)`` over all ``num_experts``; the
  ``num_experts_per_tok`` largest of ``s + b_select`` chosen; ``w_e =
  moe_routed_scaling_factor s_e / (sum of the chosen s)``; ``sum_e w_e
  SwiGLU_e(h) + SwiGLU_shared(h)`` in a loop over the experts.

``lowp=True`` is the control, not the reference: the same mathematics with
both operands of every matmul rounded to float8 (e4m3, one scale a tensor).
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


def layers(cfg):
    """[(attention kind, feed-forward kind, query heads)] of the layers that
    are run: the first ``num_hidden_layers`` entries of the published
    lists."""
    n = cfg["num_hidden_layers"]
    return list(zip(cfg["layer_types"][:n], cfg["mlp_layer_types"][:n],
                    cfg["num_attention_heads_per_layer"][:n]))


def inv_freq(rope: dict, d: int):
    """The d / 2 angles a position of one entry of ``rope_parameters`` over
    d rotated dimensions, and YaRN's (low, high) or None."""
    theta = float(rope["rope_theta"])
    f = [theta ** (-2.0 * i / d) for i in range(d // 2)]
    if rope["rope_type"] == "default":
        return f, None
    if rope["rope_type"] != "yarn":
        raise ValueError(f"rope_type {rope['rope_type']!r}")

    def corr(turns):
        return d * math.log(rope["original_max_position_embeddings"]
                            / (2 * math.pi * turns)) / (2 * math.log(theta))

    low = max(math.floor(corr(rope["beta_fast"])), 0)
    high = min(math.ceil(corr(rope["beta_slow"])), d - 1)
    out = []
    for i, fi in enumerate(f):
        ramp = min(max((i - low) / max(high - low, 1e-3), 0.0), 1.0)
        out.append(fi * (1 - ramp) + fi / rope["factor"] * ramp)
    return out, (low, high)


def _rotate(x, rope: dict):
    """x (T, ..., hd), row t at position t: the leading
    ``partial_rotary_factor x hd`` dimensions turned pair by pair, times the
    attention factor; the rest as they are."""
    T, hd = x.shape[0], x.shape[-1]
    d = int(round(hd * rope["partial_rotary_factor"]))
    freq, _ramp = inv_freq(rope, d)
    ang = jnp.arange(T, dtype=jnp.float32)[:, None] \
        * jnp.asarray(freq, jnp.float32)                        # (T, d/2)
    ang = ang.reshape(T, *([1] * (x.ndim - 2)), d // 2)
    pairs = x[..., :d].reshape(*x.shape[:-1], d // 2, 2)
    a, b = pairs[..., 0], pairs[..., 1]
    amp = float(rope.get("attention_factor", 1.0))
    turned = jnp.stack([a * jnp.cos(ang) - b * jnp.sin(ang),
                        a * jnp.sin(ang) + b * jnp.cos(ang)],
                       axis=-1).reshape(*x.shape[:-1], d) * amp
    return jnp.concatenate([turned, x[..., d:]], axis=-1)


def _attention(x, p, cfg, kind, H, lowp):
    """x (T, d) -> (T, d)."""
    g, hd = cfg["num_key_value_heads"], cfg["head_dim"]
    rope = cfg["rope_parameters"][kind]
    window = cfg["sliding_window"] if kind == "sliding_attention" else None
    T = x.shape[0]
    q = _rotate(_mm("tc,cf->tf", x, p["w_q"], lowp).reshape(T, H, hd), rope)
    kv = _mm("tc,cf->tf", x, p["w_kv"], lowp)
    k = _rotate(kv[:, :g * hd].reshape(T, g, hd), rope)
    v = kv[:, g * hd:].reshape(T, g, hd)
    # every query head beside its key/value head
    k, v = (jnp.repeat(a, H // g, axis=1) for a in (k, v))
    gate = jax.nn.sigmoid(_mm("tc,ch->th", x, p["w_gate"], lowp))
    pad = -T % _BLOCK
    qb = jnp.pad(q, ((0, pad), (0, 0), (0, 0))).reshape(-1, _BLOCK, H, hd)

    def block(args):
        q_b, i0 = args
        s = _mm("qhd,khd->hqk", q_b, k, lowp) / math.sqrt(hd)
        # a row of the last block's padding reads what row T - 1 reads
        t = jnp.minimum(i0 + jnp.arange(_BLOCK), T - 1)[:, None]
        j = jnp.arange(T)[None, :]
        seen = j <= t
        if window:
            seen = seen & (j > t - window)
        pr = jax.nn.softmax(jnp.where(seen[None], s, -jnp.inf), axis=-1)
        return _mm("hqk,khd->qhd", pr, v, lowp)

    o = lax.map(block, (qb, jnp.arange(qb.shape[0]) * _BLOCK))
    o = o.reshape(-1, H, hd)[:T] * gate[:, :, None]
    return _mm("tf,fc->tc", o.reshape(T, H * hd), p["w_o"], lowp)


def _moe(x, p, cfg, lowp):
    s = jax.nn.sigmoid(_mm("tc,ce->te", x, p["w_router"], lowp))
    _, idx = lax.top_k(s + p["b_select"].astype(jnp.float32),
                       cfg["num_experts_per_tok"])
    w = jnp.take_along_axis(s, idx, axis=-1)
    w = cfg["moe_routed_scaling_factor"] * w / jnp.sum(w, -1, keepdims=True)

    def one(acc, args):                 # a loop over the experts
        e, w_gu, w_down = args
        mine = jnp.sum(jnp.where(idx == e, w, 0.0), axis=-1)     # (T,)
        return acc + mine[:, None] * _swiglu(x, w_gu, w_down, lowp), None

    routed, _ = lax.scan(one, jnp.zeros_like(x), (
        jnp.arange(p["w_gu"].shape[0]), p["w_gu"], p["w_down"]))
    return routed + _swiglu(x, p["shared"]["w_gu"], p["shared"]["w_down"],
                            lowp)


def hidden(params, tokens, cfg, lowp=False):
    """tokens (T,) -> the last layer's output before the final norm."""
    x = params["tok_emb"][tokens].astype(jnp.float32)
    eps = cfg["rms_norm_eps"]
    for blk, (kind, ffn, H) in zip(params["blocks"], layers(cfg)):
        x = x + _attention(_rms(x, blk["ln1"], eps), blk["mixer"], cfg, kind,
                           H, lowp)
        h = _rms(x, blk["ln2"], eps)
        x = x + (_moe(h, blk["ffn"], cfg, lowp) if ffn == "sparse" else
                 _swiglu(h, blk["ffn"]["w_gu"], blk["ffn"]["w_down"], lowp))
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
