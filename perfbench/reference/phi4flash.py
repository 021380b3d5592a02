"""The plain reference of the Phi-4-flash decoder (SambaY with differential
attention, arXiv:2507.06607) as the configuration file describes it:
straightforward ``jax.numpy``, float32, every matmul at
``Precision.HIGHEST``, a sequential scan over time, two softmax maps a pair,
no cache, no kernels, no batching.

It imports nothing of the program. It is handed the tree the program serves
(bfloat16, made by ``perfbench/models/phi4flash.py``) and upcasts what it
touches as it touches it: one part of a layer at a time, the head and the
attention's queries in blocks of positions, so that 8,192 positions at the
published widths fit beside the served weights. With ``H = num_hidden_layers
/ 2`` and 0-indexed layers (``LN``: LayerNorm with gain and bias, eps
``layer_norm_eps``; x the float32 residual stream):

    x = x + mix_i(LN1(x));   x = x + (silu(g) * u) W2,  [g | u] = LN2(x) W1
    logits = LN_f(x) E^T                                  (the embedding)

- i even, i <= H, *Mamba-1*: ``[x | z] = h W_in``; ``x = silu(conv4(x) +
  b_conv)`` (causal, depth-wise); ``[r | B | C] = x W_x``; ``dt = softplus(r
  W_dt + b_dt)``; ``A = -exp(A_log)``; ``s_t[n, c] = exp(dt_t[c] A[n, c])
  s_{t-1}[n, c] + dt_t[c] B_t[n] x_t[c]``; ``y_t[c] = sum_n C_t[n] s_t[n, c]
  + D[c] x_t[c]``; ``mix = (y * silu(z)) W_out``. Layer H hands on ``M = y``,
  before the gate. (``A_log`` is stored (state, channels).)
- i even, i > H, *gated memory unit*: ``mix = (silu(h W_1) * M) W_2``, M the
  row of the same position.
- i odd, *differential attention*: ``q = h W_q + b_q`` in heads of ``d /
  num_attention_heads``; where the layer has keys of its own (i <= H + 1),
  ``[k | v] = h W_kv + b_kv``; the layers behind H + 1 read layer H + 1's k
  and v. Query heads 2j, 2j + 1 are the pair ``(q1_j, q2_j)``, key heads 2g,
  2g + 1 the pair ``(k1_g, k2_g)``, value heads 2g, 2g + 1 joined are ``V_g``;
  pair j reads group ``j // (pairs / groups)``. ``a1 = softmax(q1 k1^T /
  sqrt(hd)) V``, ``a2 = softmax(q2 k2^T / sqrt(hd)) V`` over keys j <= t, or
  t - ``sliding_window`` < j <= t where i < H; ``lambda = exp(lq1 . lk1) -
  exp(lq2 . lk2) + l0``, ``l0 = 0.8 - 0.6 exp(-0.3 i)``; ``o_j = rms(a1 -
  lambda a2; gain, eps 1e-5) (1 - l0)``; ``mix = concat_j(o_j) W_o + b_o``.

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
_SUB_EPS = 1e-5         # the sub-norm's, fixed by the modelling file


def _round_f8(x):
    scale = jnp.maximum(jnp.max(jnp.abs(x)), 1e-30) / _F8_MAX
    return (x / scale).astype(jnp.float8_e4m3fn).astype(jnp.float32) * scale


def _mm(spec, a, b, lowp):
    a, b = a.astype(jnp.float32), b.astype(jnp.float32)
    if lowp:
        a, b = _round_f8(a), _round_f8(b)
    return jnp.einsum(spec, a, b, precision=_HI)


def _ln(x, p, eps):
    x = x - jnp.mean(x, -1, keepdims=True)
    return x / jnp.sqrt(jnp.mean(jnp.square(x), -1, keepdims=True) + eps) \
        * p["g"].astype(jnp.float32) + p["b"].astype(jnp.float32)


def _rms(x, g, eps):
    return x / jnp.sqrt(jnp.mean(jnp.square(x), -1, keepdims=True) + eps) \
        * g.astype(jnp.float32)


def _swiglu(x, w_gu, w_down, lowp):
    h = _mm("tc,cf->tf", x, w_gu, lowp)
    f = h.shape[-1] // 2
    return _mm("tf,fc->tc", jax.nn.silu(h[:, :f]) * h[:, f:], w_down, lowp)


def layers(cfg):
    """The kind of every layer: ``mamba``, ``window``, ``full``, ``memory``
    (the gated unit) or ``cross`` (queries alone)."""
    n = cfg["num_hidden_layers"]
    half = n // 2
    return ["mamba" if i % 2 == 0 and i <= half else
            "memory" if i % 2 == 0 else
            "window" if i < half else
            "full" if i == half + 1 else "cross" for i in range(n)]


def lambda_init(layer: int) -> float:
    return 0.8 - 0.6 * math.exp(-0.3 * layer)


def _mamba(h, p, cfg, lowp):
    """h (T, d) -> (mix (T, d), the scan's output before the gate (T, C))."""
    T = h.shape[0]
    C, N, r = (cfg["mamba_d_inner"], cfg["mamba_d_state"],
               cfg["mamba_dt_rank"])
    f32 = jnp.float32
    xz = _mm("tc,cf->tf", h, p["w_in"], lowp)
    x, z = xz[:, :C], xz[:, C:]
    taps = p["conv"].astype(f32)                    # the last on the row
    n = taps.shape[0]
    rows = jnp.pad(x, ((n - 1, 0), (0, 0)))
    x = jax.nn.silu(sum(taps[i] * rows[i:i + T] for i in range(n))
                    + p["b_conv"].astype(f32))
    rbc = _mm("tc,cf->tf", x, p["w_x"], lowp)
    dt = jax.nn.softplus(_mm("tr,rc->tc", rbc[:, :r], p["w_dt"], lowp)
                         + p["b_dt"].astype(f32))
    b, c = rbc[:, r:r + N], rbc[:, r + N:]
    a = -jnp.exp(p["a_log"].astype(f32))            # (N, C)

    def step(s, row):                               # one position
        x_t, dt_t, b_t, c_t = row
        s = jnp.exp(dt_t[None, :] * a) * s \
            + (dt_t * x_t)[None, :] * b_t[:, None]
        return s, jnp.sum(c_t[:, None] * s, axis=0)

    _s, y = lax.scan(step, jnp.zeros((N, C), f32), (x, dt, b, c))
    y = y + p["d_skip"].astype(f32) * x
    return _mm("tc,cd->td", y * jax.nn.silu(z), p["w_out"], lowp), y


def _memory_unit(h, p, memory, lowp):
    return _mm("tc,cd->td",
               jax.nn.silu(_mm("td,dc->tc", h, p["w_in"], lowp)) * memory,
               p["w_out"], lowp)


def _attention(h, p, cfg, layer, kind, shared, lowp):
    """h (T, d) -> (mix (T, d), this layer's (k, v), or ``shared`` where it
    has none of its own)."""
    T, d = h.shape
    H, g = cfg["num_attention_heads"], cfg["num_key_value_heads"]
    hd = d // H
    f32 = jnp.float32
    q = (_mm("tc,cf->tf", h, p["w_q"], lowp)
         + p["b_q"].astype(f32)).reshape(T, H, hd)
    if kind == "cross":
        k, v = shared
    else:
        kv = _mm("tc,cf->tf", h, p["w_kv"], lowp) + p["b_kv"].astype(f32)
        k = kv[:, :g * hd].reshape(T, g, hd)
        v = kv[:, g * hd:].reshape(T, g, hd)
    pairs, groups = H // 2, g // 2
    q1, q2 = q[:, 0::2], q[:, 1::2]                 # (T, pairs, hd)
    # every pair beside the key pair and the joined value of its group
    k1, k2 = (jnp.repeat(a, pairs // groups, axis=1)
              for a in (k[:, 0::2], k[:, 1::2]))
    val = jnp.repeat(v.reshape(T, groups, 2 * hd), pairs // groups, axis=1)
    window = cfg["sliding_window"] if kind == "window" else None
    l0 = lambda_init(layer)
    lam = (jnp.exp(jnp.sum(p["lambda_q1"].astype(f32)
                           * p["lambda_k1"].astype(f32)))
           - jnp.exp(jnp.sum(p["lambda_q2"].astype(f32)
                             * p["lambda_k2"].astype(f32))) + l0)
    pad = -T % _BLOCK

    def blocks(a):
        return jnp.pad(a, ((0, pad), (0, 0), (0, 0))).reshape(
            -1, _BLOCK, pairs, hd)

    def block(args):
        q1_b, q2_b, i0 = args
        # a row of the last block's padding reads what row T - 1 reads
        t = jnp.minimum(i0 + jnp.arange(_BLOCK), T - 1)[:, None]
        j = jnp.arange(T)[None, :]
        seen = j <= t
        if window:
            seen = seen & (j > t - window)

        def one(q_b, k_b):                          # one softmax map
            s = _mm("qhd,khd->hqk", q_b, k_b, lowp) / math.sqrt(hd)
            pr = jax.nn.softmax(jnp.where(seen[None], s, -jnp.inf), axis=-1)
            return _mm("hqk,khd->qhd", pr, val, lowp)

        return one(q1_b, k1) - lam * one(q2_b, k2)

    o = lax.map(block, (blocks(q1), blocks(q2),
                        jnp.arange((T + pad) // _BLOCK) * _BLOCK))
    o = _rms(o.reshape(-1, pairs, 2 * hd)[:T], p["sub_norm"], _SUB_EPS) \
        * (1.0 - l0)
    return (_mm("tf,fc->tc", o.reshape(T, H * hd), p["w_o"], lowp)
            + p["b_o"].astype(f32)), (k, v)


def hidden(params, tokens, cfg, lowp=False):
    """tokens (T,) -> the last layer's output before the final norm."""
    x = params["tok_emb"][tokens].astype(jnp.float32)
    eps = cfg["layer_norm_eps"]
    half = cfg["num_hidden_layers"] // 2
    memory = shared = None
    for i, (blk, kind) in enumerate(zip(params["blocks"], layers(cfg))):
        h, p = _ln(x, blk["ln1"], eps), blk["mixer"]
        if kind == "mamba":
            mix, y = _mamba(h, p, cfg, lowp)
            if i == half:
                memory = y
        elif kind == "memory":
            mix = _memory_unit(h, p, memory, lowp)
        else:
            mix, kv = _attention(h, p, cfg, i, kind, shared, lowp)
            if kind == "full":
                shared = kv
        x = x + mix
        x = x + _swiglu(_ln(x, blk["ln2"], eps), blk["ffn"]["w_gu"],
                        blk["ffn"]["w_down"], lowp)
    return x


def _head_blocks(params, x, cfg, lowp, fn):
    """``fn(logits of a block of positions, block index)`` over blocks."""
    T = x.shape[0]
    x = _ln(x, params["ln_f"], cfg["layer_norm_eps"])
    pad = -T % _BLOCK
    xb = jnp.pad(x, ((0, pad), (0, 0))).reshape(-1, _BLOCK, x.shape[1])
    out = lax.map(lambda a: fn(_mm("tc,vc->tv", a[0], params["tok_emb"],
                                   lowp), a[1]),
                  (xb, jnp.arange(xb.shape[0])))
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
