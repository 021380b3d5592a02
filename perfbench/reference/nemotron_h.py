"""The plain reference of the Nemotron-H block as the configuration file
describes it: straightforward ``jax.numpy``, float32, every matmul at
``Precision.HIGHEST``, no cache, no kernels, no chunks.

It imports nothing of the program. It is handed the tree the program serves
(bfloat16, made by ``perfbench/models/nemotron_h.py``) and upcasts what it
touches as it touches it: one layer at a time, one expert at a time, the head
and the attention's queries in blocks of positions, so that it fits beside the
served weights. Every layer is ONE pre-norm residual part,
``x <- x + f(RMSNorm(x))``, then a final RMSNorm and the untied head; the
layers that are run are the letters of ``layers_run`` (ISSUE 32, item 1):

- ``M``, Mamba-2: ``[z | xBC | dt] = h W_in``; ``xBC <- silu(conv4(xBC) +
  b_conv)`` (causal, depthwise); ``x`` (H heads x P), ``B``, ``C`` (G groups x
  N; head i on group i // (H / G)); ``dt <- softplus(dt + dt_bias)``,
  ``a_t = exp(-exp(A_log) dt_t)`` a head;
  ``S_t = a_t S_{t-1} + dt_t x_t B_t^T`` (P x N a head),
  ``y_t = S_t C_t + D x_t``, token by token; ``y <- RMSNorm(y * silu(z))`` in
  groups of H P / G with one gain over the whole width; ``out = y W_out``;
- ``*``, attention: ``q = h W_q`` (Hq heads), ``[k | v] = h W_kv`` (Hkv
  heads each), K and V expanded to the query heads (head i on i // (Hq /
  Hkv)), causal softmax at ``1 / sqrt(head_dim)``, no rotation, ``o W_o``;
- ``E``, experts: ``s = sigmoid(h W_r)`` over all published experts, the
  ``k`` largest of ``s + b`` chosen, weights ``s`` at the chosen over their
  sum times the scaling factor; ``u = h W_latent_in``;
  ``e_j(u) = relu(u W1_j)^2 W2_j``;
  ``y = (sum over chosen AND held j of w_j e_j(u)) W_latent_out
  + relu(h V1)^2 V2`` in a loop over the held experts. What the absent
  experts would add is left out (``held`` = ``experts_held_first`` .. +
  ``n_routed_experts``).

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
    return x / jnp.sqrt(jnp.mean(jnp.square(x), -1, keepdims=True) + eps) * g


def layer_kinds(cfg):
    """The letters of the layers that are run: ``M``, ``*`` or ``E``."""
    kinds = list(cfg["layers_run"])
    if len(kinds) != cfg["num_hidden_layers"] or set(kinds) - set("M*E"):
        raise ValueError(f"layers_run {cfg['layers_run']!r} is not "
                         f"{cfg['num_hidden_layers']} of M, * and E")
    return kinds


def _relu2(x, w1, w2, lowp):
    return _mm("tf,fc->tc",
               jnp.square(jax.nn.relu(_mm("tc,cf->tf", x, w1, lowp))), w2,
               lowp)


def _mamba2(x, p, cfg, lowp):
    """x (T, d) -> (T, d)."""
    H, P = cfg["mamba_num_heads"], cfg["mamba_head_dim"]
    G, N, n = cfg["n_groups"], cfg["ssm_state_size"], cfg["conv_kernel"]
    di, T = H * P, x.shape[0]
    zxd = _mm("tc,cf->tf", x, p["w_in"], lowp)
    z, xbc = zxd[:, :di], zxd[:, di:di + di + 2 * G * N]
    dt = jax.nn.softplus(zxd[:, 2 * di + 2 * G * N:]
                         + p["dt_bias"].astype(jnp.float32))      # (T, H)
    padded = jnp.concatenate([jnp.zeros((n - 1, xbc.shape[1])), xbc])
    conv = p["conv"].astype(jnp.float32)
    act = jax.nn.silu(sum(conv[i] * padded[i:i + T] for i in range(n))
                      + p["b_conv"].astype(jnp.float32))
    xs = act[:, :di].reshape(T, H, P)
    # head i uses the B and C of group i // (H / G)
    b = jnp.repeat(act[:, di:di + G * N].reshape(T, G, N), H // G, axis=1)
    c = jnp.repeat(act[:, di + G * N:].reshape(T, G, N), H // G, axis=1)
    a = jnp.exp(-jnp.exp(p["a_log"].astype(jnp.float32)) * dt)

    def step(s, row):                   # s (H, P, N)
        x_t, b_t, c_t, a_t, dt_t = row
        s = a_t[:, None, None] * s \
            + (dt_t[:, None] * x_t)[:, :, None] * b_t[:, None, :]
        return s, jnp.einsum("hpn,hn->hp", s, c_t, precision=_HI)

    _, y = lax.scan(step, jnp.zeros((H, P, N)), (xs, b, c, a, dt))
    y = y + p["d_skip"].astype(jnp.float32)[:, None] * xs
    y = y.reshape(T, di) * jax.nn.silu(z)
    y = _rms(y.reshape(T, G, di // G), 1.0, cfg["norm_eps"]).reshape(T, di) \
        * p["norm"].astype(jnp.float32)
    return _mm("tf,fc->tc", y, p["w_out"], lowp)


def _gqa(x, p, cfg, lowp):
    Hq, Hkv, hd = (cfg["num_attention_heads"], cfg["num_key_value_heads"],
                   cfg["head_dim"])
    T = x.shape[0]
    q = _mm("tc,cf->tf", x, p["w_q"], lowp).reshape(T, Hq, hd)
    kv = _mm("tc,cf->tf", x, p["w_kv"], lowp)
    k = jnp.repeat(kv[:, :Hkv * hd].reshape(T, Hkv, hd), Hq // Hkv, axis=1)
    v = jnp.repeat(kv[:, Hkv * hd:].reshape(T, Hkv, hd), Hq // Hkv, axis=1)
    pad = -T % _BLOCK
    qb = jnp.pad(q, ((0, pad), (0, 0), (0, 0))).reshape(-1, _BLOCK, Hq, hd)

    def block(args):
        q_b, i0 = args
        s = _mm("qhd,khd->hqk", q_b, k, lowp) / math.sqrt(hd)
        causal = (i0 + jnp.arange(_BLOCK))[:, None] >= jnp.arange(T)[None, :]
        pr = jax.nn.softmax(jnp.where(causal[None], s, -jnp.inf), axis=-1)
        return _mm("hqk,khd->qhd", pr, v, lowp)

    o = lax.map(block, (qb, jnp.arange(qb.shape[0]) * _BLOCK))
    return _mm("tf,fc->tc", o.reshape(-1, Hq * hd)[:T], p["w_o"], lowp)


def _moe(x, p, cfg, lowp):
    kk = cfg["num_experts_per_tok"]
    first = cfg.get("experts_held_first", 0)
    s = jax.nn.sigmoid(_mm("tc,ce->te", x, p["w_router"], lowp))
    _, idx = lax.top_k(s + p["b_select"].astype(jnp.float32), kk)
    w = jnp.take_along_axis(s, idx, axis=-1)
    if cfg["norm_topk_prob"]:
        w = w / jnp.sum(w, -1, keepdims=True)
    w = w * cfg["routed_scaling_factor"]
    u = _mm("tc,cl->tl", x, p["w_latent_in"], lowp)

    def one(acc, args):                 # a loop over the experts held here
        e, w_up, w_down = args
        mine = jnp.sum(jnp.where(idx == e, w, 0.0), axis=-1)     # (T,)
        return acc + mine[:, None] * _relu2(u, w_up, w_down, lowp), None

    held = p["w_up"].shape[0]
    routed, _ = lax.scan(one, jnp.zeros_like(u),
                         (first + jnp.arange(held), p["w_up"], p["w_down"]))
    return _mm("tl,lc->tc", routed, p["w_latent_out"], lowp) \
        + _relu2(x, p["shared"]["w_up"], p["shared"]["w_down"], lowp)


def hidden(params, tokens, cfg, lowp=False):
    """tokens (T,) -> the last layer's output before the final norm."""
    x = params["tok_emb"][tokens].astype(jnp.float32)
    eps = cfg["norm_eps"]
    for blk, kind in zip(params["blocks"], layer_kinds(cfg)):
        if kind == "E":
            x = x + _moe(_rms(x, blk["ln2"].astype(jnp.float32), eps),
                         blk["ffn"], cfg, lowp)
        else:
            h = _rms(x, blk["ln1"].astype(jnp.float32), eps)
            x = x + (_mamba2 if kind == "M" else _gqa)(h, blk["mixer"], cfg,
                                                       lowp)
    return x


def _head_blocks(params, x, cfg, lowp, fn):
    """``fn(logits of a block of positions, block index)`` over blocks."""
    T = x.shape[0]
    x = _rms(x, params["ln_f"].astype(jnp.float32), cfg["norm_eps"])
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
