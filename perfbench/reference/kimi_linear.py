"""The plain reference of the Kimi-Linear block as the configuration file
describes it: straightforward ``jax.numpy``, float32, every matmul at
``Precision.HIGHEST``, no cache, no kernels, no chunks.

It imports nothing of the program. It is handed the tree the program serves
(bfloat16, made by ``perfbench/models/kimi_linear.py``) and upcasts what it
touches as it touches it: one layer at a time, one expert at a time, the head
and the attention's queries in blocks of positions, so that it fits beside the
served weights. The layer equations (ISSUE 27, section 1):

- pre-norm residual blocks with RMSNorm, final RMSNorm, untied head;
- KDA: ``q, k, v = SiLU(conv4(x W))``, q and k L2-normalised per head, q
  scaled by ``d_k^-1/2``; ``a_t = exp(-exp(A_h) softplus(W_f2 W_f1 x + b_dt))``
  per channel, ``beta_t = sigmoid(w_beta x)``;
  ``S_t = (I - beta_t k_t k_t^T) Diag(a_t) S_{t-1} + beta_t k_t v_t^T``,
  ``o_t = S_t^T q_t``, token by token; output
  ``W_o (rms_head(o_t) * sigmoid(W_g2 W_g1 x + b_g2))``;
- MLA without positions, expanded: ``k = [c W_kvb^K, k_r]``, ``v = c W_kvb^V``
  with ``c = rms(x W_kva[:512])``, ``softmax(q k^T / sqrt(192), causal) v``;
- feed-forward: SwiGLU; or ``s = sigmoid(x W_r)`` over all published experts,
  the ``k`` largest of ``s + b`` chosen, weights ``s`` at the chosen over their
  sum times the scaling factor, ``y = shared(x) + sum over chosen AND held e``
  in a loop over the held experts. What the absent experts would add is left
  out (``held`` = ``experts_held_first`` .. + ``num_experts``).

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


def _f32(tree):
    return jax.tree.map(lambda a: a.astype(jnp.float32), tree)


def _rms(x, g, eps):
    return x / jnp.sqrt(jnp.mean(jnp.square(x), -1, keepdims=True) + eps) * g


def layer_kinds(cfg):
    """[(mixer, ffn)] of the layers that are run, the first being layer 1."""
    lin = cfg["linear_attn_config"]
    out = []
    for i in range(1, cfg["num_hidden_layers"] + 1):
        if i in lin["kda_layers"]:
            mixer = "kda"
        elif i in lin["full_attn_layers"]:
            mixer = "mla"
        else:
            raise ValueError(f"layer {i} is in neither list")
        out.append((mixer, "dense" if i <= cfg["first_k_dense_replace"]
                    else "moe"))
    return out


def _swiglu(x, p, lowp):
    h = _mm("tc,cf->tf", x, p["w_gu"], lowp)
    f = h.shape[-1] // 2
    return _mm("tf,fc->tc", jax.nn.silu(h[:, :f]) * h[:, f:], p["w_down"],
               lowp)


def _kda(x, p, cfg, lowp):
    """x (T, d) -> (T, d)."""
    lin = cfg["linear_attn_config"]
    H, K, n = lin["num_heads"], lin["head_dim"], lin["short_conv_kernel_size"]
    T = x.shape[0]
    pre = _mm("tc,cf->tf", x, p["w_qkv"], lowp)
    padded = jnp.concatenate([jnp.zeros((n - 1, pre.shape[1])), pre])
    conv = p["conv"].astype(jnp.float32)
    act = jax.nn.silu(sum(conv[i] * padded[i:i + T] for i in range(n)))
    q, k, v = (a.reshape(T, H, K) for a in jnp.split(act, 3, axis=-1))
    q = q / jnp.sqrt(jnp.sum(q * q, -1, keepdims=True) + 1e-6) / math.sqrt(K)
    k = k / jnp.sqrt(jnp.sum(k * k, -1, keepdims=True) + 1e-6)
    f = _mm("tr,rf->tf", _mm("tc,cr->tr", x, p["w_f1"], lowp), p["w_f2"],
            lowp) + p["b_dt"]
    a = jnp.exp(-jnp.exp(p["a_log"].astype(jnp.float32))[:, None]
                * jax.nn.softplus(f.reshape(T, H, K)))
    beta = jax.nn.sigmoid(_mm("tc,ch->th", x, p["w_beta"], lowp))

    def step(s, row):                   # s (H, K, V)
        q_t, k_t, v_t, a_t, b_t = row
        s = a_t[:, :, None] * s
        u = b_t[:, None] * (v_t - jnp.einsum("hk,hkv->hv", k_t, s,
                                             precision=_HI))
        s = s + k_t[:, :, None] * u[:, None, :]
        return s, jnp.einsum("hk,hkv->hv", q_t, s, precision=_HI)

    _, o = lax.scan(step, jnp.zeros((H, K, K)), (q, k, v, a, beta))
    gate = jax.nn.sigmoid(
        _mm("tr,rf->tf", _mm("tc,cr->tr", x, p["w_g1"], lowp), p["w_g2"],
            lowp) + p["b_g2"].astype(jnp.float32)).reshape(T, H, K)
    y = _rms(o, p["o_norm"].astype(jnp.float32), cfg["rms_norm_eps"]) * gate
    return _mm("tf,fc->tc", y.reshape(T, H * K), p["w_o"], lowp)


def _mla(x, p, cfg, lowp):
    H = cfg["num_attention_heads"]
    dn, dr, dv = (cfg["qk_nope_head_dim"], cfg["qk_rope_head_dim"],
                  cfg["v_head_dim"])
    R = cfg["kv_lora_rank"]
    T = x.shape[0]
    q = _mm("tc,cf->tf", x, p["w_q"], lowp).reshape(T, H, dn + dr)
    kva = _mm("tc,cf->tf", x, p["w_kva"], lowp)
    c = _rms(kva[:, :R], p["kv_norm"].astype(jnp.float32),
             cfg["rms_norm_eps"])
    k_r = kva[:, R:]
    kvb = _mm("tc,cf->tf", c, p["w_kvb"], lowp).reshape(T, H, dn + dv)
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
    kk = cfg["num_experts_per_token"]
    first = cfg.get("experts_held_first", 0)
    s = jax.nn.sigmoid(_mm("tc,ce->te", x, p["w_router"], lowp))
    _, idx = lax.top_k(s + p["b_select"].astype(jnp.float32), kk)
    w = jnp.take_along_axis(s, idx, axis=-1)
    if cfg["moe_renormalize"]:
        w = w / jnp.sum(w, -1, keepdims=True)
    w = w * cfg["routed_scaling_factor"]

    def one(acc, args):                 # a loop over the experts held here
        e, w_gu, w_down = args
        mine = jnp.sum(jnp.where(idx == e, w, 0.0), axis=-1)     # (T,)
        y = _swiglu(x, {"w_gu": w_gu, "w_down": w_down}, lowp)
        return acc + mine[:, None] * y, None

    held = p["w_gu"].shape[0]
    routed, _ = lax.scan(one, jnp.zeros_like(x),
                         (first + jnp.arange(held), p["w_gu"], p["w_down"]))
    return _swiglu(x, p["shared"], lowp) + routed


def hidden(params, tokens, cfg, lowp=False):
    """tokens (T,) -> the last block's output before the final norm."""
    x = params["tok_emb"][tokens].astype(jnp.float32)
    eps = cfg["rms_norm_eps"]
    for blk, (mixer, ffn) in zip(params["blocks"], layer_kinds(cfg)):
        h = _rms(x, blk["ln1"].astype(jnp.float32), eps)
        x = x + (_kda if mixer == "kda" else _mla)(h, blk["mixer"], cfg, lowp)
        h = _rms(x, blk["ln2"].astype(jnp.float32), eps)
        x = x + (_swiglu(h, blk["ffn"], lowp) if ffn == "dense"
                 else _moe(h, blk["ffn"], cfg, lowp))
    return x


def _head_blocks(params, x, cfg, lowp, fn):
    """``fn(logits of a block of positions, block index)`` over blocks."""
    T = x.shape[0]
    x = _rms(x, params["ln_f"].astype(jnp.float32), cfg["rms_norm_eps"])
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
