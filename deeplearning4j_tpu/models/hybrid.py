"""HybridLM - a decoder built from a per-layer description.

Each layer names its mixer (``kda``: gated delta-rule linear attention with a
short convolution; ``mla``: latent attention without positions) and its
feed-forward (``dense`` SwiGLU, or ``moe``: routed experts of which this chip
holds a share, ``parallel/moe.py::routed_experts_ffn``). Pre-norm residual
blocks with RMSNorm, no position table, untied head. Parameters are held in
``param_dtype`` and computed with as they are: nothing is cast per call.

Three spellings of the same mathematics:

- :meth:`apply` - the full forward: chunked KDA (the UT / WY form, every
  decay exponent a difference that is <= 0, so no channel's decay can
  overflow), expanded MLA in blocks of queries.
- :meth:`prefill_cache` - the same over a padded bucket, returning the logits
  of the prompt's last token and what the cache needs: the latent rows of every
  position and, for each KDA layer, the state and the convolution's 3-row tail
  at the prompt's TRUE last token (rows beyond it are identity updates).
- :meth:`decode_paged` - one token a slot: the recurrent KDA step on the
  slot's state, absorbed MLA over the slot's latent pages.

The cache protocol ``DecodeEngine`` drives (``models/generation.py``) is the
block of methods under "cache protocol" below; ``TransformerLM`` implements
the same block. This model's cache is two kinds of state side by side: a
paged pool of latent rows (one array an MLA layer, pages shared through the
engine's allocator and tables) and a fixed per-slot state (one matrix state
and one convolution tail a KDA layer), each layer's array a leaf of its own so
that a step rewrites it in place.

Named scopes: the outer names are the fixed vocabulary of
``models/transformer.py`` (``embed``, ``ln``, ``attn_qkv``, ``attn_core``,
``attn_out``, ``mlp``, ``head``, ``kv_write``, ``kv_gather``); inside them
``kda_proj``, ``kda_conv``, ``kda_state``, ``kda_out``, ``mla_proj``,
``mla_attend`` and, from the expert layer, ``moe_route``, ``moe_experts``,
``moe_shared``, ``moe_combine``.
"""
from __future__ import annotations

import dataclasses
import math
from typing import Any, Dict, Optional, Tuple

import jax
import jax.numpy as jnp
from jax import lax

from deeplearning4j_tpu.parallel.moe import (RoutedExpertsConfig,
                                             routed_experts_ffn, swiglu)

_HI = lax.Precision.HIGHEST
#: rows of the KDA chunk handled pairwise (exactly); blocks further apart go
#: through a reference point between them
_SUB = 16
#: queries a block of the expanded latent attention (its scores are
#: heads x block x T float32)
_QUERY_BLOCK = 512


@dataclasses.dataclass(frozen=True)
class LayerSpec:
    mixer: str                      # "kda" | "mla"
    ffn: str                        # "dense" | "moe"

    def __post_init__(self):
        if self.mixer not in ("kda", "mla") or self.ffn not in ("dense",
                                                                "moe"):
            raise ValueError(f"unknown layer {self}")


@dataclasses.dataclass
class HybridConfig:
    vocab_size: int
    d_model: int
    layers: Tuple[LayerSpec, ...]
    max_len: int                    # longest sequence a cache slot holds
    experts: Optional[RoutedExpertsConfig] = None
    rms_eps: float = 1e-5
    dtype: Any = jnp.bfloat16       # activations
    param_dtype: Any = jnp.bfloat16
    kda_heads: int = 32
    kda_head_dim: int = 128         # d_k = d_v
    kda_conv: int = 4
    kda_gate_rank: int = 128        # width of the two low-rank gates
    kda_chunk: int = 64
    mla_heads: int = 32
    qk_nope_dim: int = 128
    qk_rope_dim: int = 64           # carried, never rotated (mla_use_nope)
    v_head_dim: int = 128
    kv_lora_rank: int = 512
    dense_ff: int = 9216
    expert_ff: int = 1024

    def __post_init__(self):
        self.layers = tuple(self.layers)
        if any(s.ffn == "moe" for s in self.layers) and self.experts is None:
            raise ValueError("a layer with routed experts needs `experts`")
        if self.kda_chunk % _SUB:
            raise ValueError(f"kda_chunk must be a multiple of {_SUB}")

    @property
    def n_layers(self) -> int:
        return len(self.layers)

    @property
    def latent_dim(self) -> int:
        return self.kv_lora_rank + self.qk_rope_dim

    @property
    def latent_row(self) -> int:
        """Width of a cached latent row: ``latent_dim`` padded with zeros
        to whole 128-lane tiles. For a width that is no multiple of 128 the
        TPU compiler's default layout of the pool puts the ROW axis minor,
        and every step then copies the whole pool in and out of the layout
        its scatter and gather need (compile rehearsals, PR 27)."""
        return -(-self.latent_dim // 128) * 128


def _rms(x, g, eps):
    x32 = x.astype(jnp.float32)
    return x32 * lax.rsqrt(jnp.mean(x32 * x32, -1, keepdims=True) + eps) \
        * g.astype(jnp.float32)


def _mm(x, w):
    return jnp.matmul(x, w, preferred_element_type=jnp.float32)


# ------------------------------------------------------------------- KDA
def _decayed_gram(x, k, g, inclusive: bool):
    """``out[i, j] = sum_d x[i, d] k[j, d] exp(g[i, d] - g[j, d])`` for
    ``j < i`` (``j <= i`` if ``inclusive``), else 0, within each chunk.
    x, k, g: (..., C, K), g the running sum of the log decays (decreasing).
    Every exponent is a difference that is <= 0: pairs inside a block of
    ``_SUB`` rows are taken exactly, pairs of different blocks through the
    running sum at the start of the row's block."""
    *lead, C, K = x.shape
    n, c = C // _SUB, _SUB
    xb, kb, gb = (a.reshape(*lead, n, c, K) for a in (x, k, g))
    # reference of block a: g just before its first row (0 for the first)
    ref = jnp.concatenate([jnp.zeros_like(gb[..., :1, 0, :]),
                           gb[..., :-1, -1, :]], axis=-2)       # (.., n, K)
    x_in = xb * jnp.exp(gb - ref[..., None, :])                 # (.., n,c,K)
    before = (jnp.arange(C)[None, :] < (jnp.arange(n) * c)[:, None])
    diff = ref[..., :, None, :] - g[..., None, :, :]            # (.., n,C,K)
    k_out = k[..., None, :, :] * jnp.exp(
        jnp.where(before[..., None], diff, -jnp.inf))
    off = jnp.einsum("...aik,...ajk->...aij", x_in, k_out, precision=_HI)
    ii, jj = jnp.arange(c)[:, None], jnp.arange(c)[None, :]
    keep = (jj <= ii) if inclusive else (jj < ii)
    d = gb[..., :, None, :] - gb[..., None, :, :]               # (..,n,c,c,K)
    d = jnp.where(keep[..., None], d, -jnp.inf)
    diag = jnp.sum(xb[..., :, None, :] * kb[..., None, :, :] * jnp.exp(d),
                   axis=-1)                                     # (.., n,c,c)
    diag = diag[..., :, :, None, :] * jnp.eye(n, dtype=diag.dtype)[
        :, None, :, None]                                       # (..,n,c,n,c)
    return off.reshape(*lead, C, C) + diag.reshape(*lead, C, C)


def kda_chunked(q, k, v, log_a, beta, s0, chunk: int):
    """The delta rule with channel-wise decay over whole sequences, chunk by
    chunk. q, k, log_a (B, T, H, K), v (B, T, H, V), beta (B, T, H), s0
    (B, H, K, V), all float32; T a multiple of ``chunk``. Returns
    (o (B, T, H, V), the state after the last row).

    Within a chunk, with ``g`` the running sum of ``log_a``:
    ``U = (I + diag(beta) L)^-1 diag(beta) (V - (K e^g) S0)`` where
    ``L = strict_gram(K, K)``; ``O = (Q e^g) S0 + incl_gram(Q, K) U``;
    ``S_end = e^{g_C} S0 + (K e^{g_C - g})^T U``."""
    B, T, H, K = q.shape
    V = v.shape[-1]
    N = T // chunk

    def chunks(a):          # (B, T, H, X) -> (N, B, H, C, X)
        return a.reshape(B, N, chunk, H, -1).transpose(1, 0, 3, 2, 4)

    qc, kc, vc, ac = chunks(q), chunks(k), chunks(v), chunks(log_a)
    bc = chunks(beta[..., None])                                # (N,B,H,C,1)
    g = jnp.cumsum(ac, axis=-2)
    L = _decayed_gram(kc, kc, g, inclusive=False)
    M = _decayed_gram(qc, kc, g, inclusive=True)
    A = jnp.eye(chunk, dtype=jnp.float32) + bc * L
    k_in = kc * jnp.exp(g)
    rhs = jnp.concatenate([bc * vc, bc * k_in], axis=-1)
    sol = lax.linalg.triangular_solve(A, rhs, left_side=True, lower=True,
                                      unit_diagonal=True)
    tv, w = sol[..., :V], sol[..., V:]
    q_in = qc * jnp.exp(g)
    g_end = g[..., -1:, :]                                      # (N,B,H,1,K)
    k_end = kc * jnp.exp(g_end - g)

    def step(s, xs):
        tv_n, w_n, q_n, m_n, ke_n, ge_n = xs
        u = tv_n - jnp.einsum("bhck,bhkv->bhcv", w_n, s, precision=_HI)
        o = (jnp.einsum("bhck,bhkv->bhcv", q_n, s, precision=_HI)
             + jnp.einsum("bhcj,bhjv->bhcv", m_n, u, precision=_HI))
        s = (jnp.exp(ge_n).swapaxes(-1, -2) * s
             + jnp.einsum("bhck,bhcv->bhkv", ke_n, u, precision=_HI))
        return s, o

    s_end, o = lax.scan(step, s0, (tv, w, q_in, M, k_end, g_end))
    return o.transpose(1, 0, 3, 2, 4).reshape(B, T, H, V), s_end


def kda_step(s, q, k, v, log_a, beta):
    """One row of the recurrence for every slot: s (B, H, K, V) float32;
    q, k, log_a (B, H, K); v (B, H, V); beta (B, H).
    ``S <- (I - beta k k^T) Diag(a) S + beta k v^T``, ``o = S^T q``."""
    s = jnp.exp(log_a)[..., None] * s
    ks = jnp.sum(k[..., None] * s, axis=-2)
    u = beta[..., None] * (v - ks)
    s = s + k[..., None] * u[..., None, :]
    return s, jnp.sum(q[..., None] * s, axis=-2)


class HybridLM:
    """See the module doc."""

    # ---- cache protocol: what the decode engine may ask for
    cache_features = frozenset()     # no dense cache, int8 pages or draft
    max_positions = None             # no position table bounds the cache
    step_stats = ("experts_touched", "pairs_held", "pairs_routed")
    prefill_all_logits = False       # the prompt's last token's (B, 1, V)

    def __init__(self, config: HybridConfig, mesh=None):
        if mesh is not None:
            raise ValueError("HybridLM runs on one chip: serving across "
                             "chips is not written yet")
        self.config = config
        self.mesh = None
        c = config
        self.kda_layers = [i for i, s in enumerate(c.layers)
                           if s.mixer == "kda"]
        self.mla_layers = [i for i, s in enumerate(c.layers)
                           if s.mixer == "mla"]
        self.moe_layers = [i for i, s in enumerate(c.layers)
                           if s.ffn == "moe"]

    # ------------------------------------------------------------ params
    def init_params(self, key) -> Dict:
        """The program's own initialiser: N(0, 0.02) matrices, the residual
        projections scaled by 1/sqrt(2 L), gains 1, decays of about 0.9."""
        c = self.config
        keys = iter(jax.random.split(key, 8 + 24 * c.n_layers))
        resid = 0.02 / math.sqrt(2 * c.n_layers)
        H, K = c.kda_heads, c.kda_head_dim
        d, r = c.d_model, c.kda_gate_rank

        def w(shape, std=0.02):
            return (std * jax.random.normal(next(keys), shape, jnp.float32)
                    ).astype(c.param_dtype)

        def ones(n):
            return jnp.ones((n,), c.param_dtype)

        def ffn(width, lead=()):
            return {"w_gu": w(lead + (d, 2 * width)),
                    "w_down": w(lead + (width, d), resid)}

        blocks = []
        for spec in c.layers:
            if spec.mixer == "kda":
                mixer = {
                    "w_qkv": w((d, 3 * H * K)), "conv": w((c.kda_conv,
                                                           3 * H * K), 0.3),
                    "w_f1": w((d, r)), "w_f2": w((r, H * K)),
                    "b_dt": jnp.full((H * K,), -2.0, jnp.float32),
                    "a_log": jnp.zeros((H,), jnp.float32),
                    "w_beta": w((d, H)), "w_g1": w((d, r)),
                    "w_g2": w((r, H * K)), "b_g2": jnp.zeros((H * K,),
                                                             c.param_dtype),
                    "o_norm": ones(K), "w_o": w((H * K, d), resid)}
            else:
                hm = c.mla_heads
                mixer = {
                    "w_q": w((d, hm * (c.qk_nope_dim + c.qk_rope_dim))),
                    "w_kva": w((d, c.latent_dim)),
                    "kv_norm": ones(c.kv_lora_rank),
                    "w_kvb": w((c.kv_lora_rank,
                                hm * (c.qk_nope_dim + c.v_head_dim))),
                    "w_o": w((hm * c.v_head_dim, d), resid)}
            if spec.ffn == "dense":
                feed = ffn(c.dense_ff)
            else:
                e = c.experts
                feed = {"w_router": w((d, e.router_width)),
                        "b_select": jnp.zeros((e.router_width,),
                                              jnp.float32),
                        **ffn(c.expert_ff, (e.held[1],)),
                        "shared": ffn(c.expert_ff)}
            blocks.append({"ln1": ones(d), "ln2": ones(d), "mixer": mixer,
                           "ffn": feed})
        return {"tok_emb": w((c.vocab_size, d)), "head": w((d, c.vocab_size)),
                "ln_f": ones(d), "blocks": blocks}

    # ------------------------------------------------------------ pieces
    # The residual stream and the norms' outputs are float32 (a few MB);
    # what a matmul takes is cast to ``dtype`` where it is taken.
    def _ln(self, g, x):
        with jax.named_scope("ln"):
            return _rms(x, g, self.config.rms_eps)

    def _embed(self, params, tokens):
        with jax.named_scope("embed"):
            return jnp.take(params["tok_emb"], tokens, axis=0).astype(
                jnp.float32)

    def _head(self, params, x):
        x = self._ln(params["ln_f"], x).astype(self.config.dtype)
        with jax.named_scope("head"):
            return _mm(x, params["head"])

    def _ffn(self, blk, spec, h32, token_mask):
        """h32 (..., d) float32 -> (y, stats or None). The router scores the
        float32 rows; the experts take them in ``dtype``."""
        h = h32.astype(self.config.dtype)
        with jax.named_scope("mlp"):
            p = blk["ffn"]
            if spec.ffn == "dense":
                return swiglu(h, p["w_gu"], p["w_down"]).astype(h.dtype), \
                    None
            flat = h.reshape(-1, h.shape[-1])
            mask = None if token_mask is None else token_mask.reshape(-1)
            y, stats = routed_experts_ffn(
                p, flat, self.config.experts, mask,
                x_route=h32.reshape(flat.shape))
            return y.reshape(h.shape), stats

    def _kda_project(self, p, h):
        """h (..., d) -> the rows the convolution takes (..., 3 H K), and
        log a (..., H, K), beta (..., H), the output gate (..., H, K)."""
        c = self.config
        H, K = c.kda_heads, c.kda_head_dim
        with jax.named_scope("kda_proj"):
            pre = _mm(h, p["w_qkv"]).astype(c.dtype)
            f = _mm(_mm(h, p["w_f1"]).astype(c.dtype), p["w_f2"])
            log_a = -jnp.exp(p["a_log"])[:, None] * jax.nn.softplus(
                (f + p["b_dt"]).reshape(*f.shape[:-1], H, K))
            beta = jax.nn.sigmoid(_mm(h, p["w_beta"]))
            gate = jax.nn.sigmoid(
                _mm(_mm(h, p["w_g1"]).astype(c.dtype), p["w_g2"])
                + p["b_g2"].astype(jnp.float32)
            ).reshape(*f.shape[:-1], H, K)
        return pre, log_a, beta, gate

    def _kda_qkv(self, conved):
        """SiLU, split, L2-normalise q and k per head, scale q."""
        c = self.config
        H, K = c.kda_heads, c.kda_head_dim
        x = jax.nn.silu(conved).reshape(*conved.shape[:-1], 3, H, K)
        q, k, v = x[..., 0, :, :], x[..., 1, :, :], x[..., 2, :, :]

        def unit(a):
            return a * lax.rsqrt(jnp.sum(a * a, -1, keepdims=True) + 1e-6)

        return unit(q) * (K ** -0.5), unit(k), v

    def _kda_out(self, p, o, gate):
        c = self.config
        with jax.named_scope("kda_out"):
            y = _rms(o, p["o_norm"], c.rms_eps) * gate
            y = y.reshape(*y.shape[:-2], -1).astype(c.dtype)
            return _mm(y, p["w_o"]).astype(c.dtype)

    def _kda_full(self, p, h, valid, last_idx):
        """h (B, T, d); rows where ``valid`` is False are identity updates.
        Returns (y, state at the last valid row, the 3 rows the convolution
        would need before the next one)."""
        c = self.config
        B, T, _ = h.shape
        with jax.named_scope("attn_qkv"):
            pre, log_a, beta, gate = self._kda_project(p, h)
            with jax.named_scope("kda_conv"):
                w = p["conv"].astype(jnp.float32)
                n = c.kda_conv
                rows = jnp.pad(pre.astype(jnp.float32),
                               ((0, 0), (n - 1, 0), (0, 0)))
                conved = sum(w[i] * rows[:, i:i + T] for i in range(n))
                q, k, v = self._kda_qkv(conved)
                at = last_idx + jnp.arange(2 - n, 1)
                tail = jnp.where((at >= 0)[None, :, None],
                                 jnp.take(pre, jnp.maximum(at, 0), axis=1),
                                 0).astype(c.dtype)
        with jax.named_scope("attn_core"), jax.named_scope("kda_state"):
            log_a = jnp.where(valid[None, :, None, None], log_a, 0.0)
            beta = jnp.where(valid[None, :, None], beta, 0.0)
            pad = -T % c.kda_chunk
            if pad:
                q, k, v, log_a, beta = (
                    jnp.pad(a, ((0, 0), (0, pad)) + ((0, 0),) * (a.ndim - 2))
                    for a in (q, k, v, log_a, beta))
            s0 = jnp.zeros((B, c.kda_heads, c.kda_head_dim, c.kda_head_dim),
                           jnp.float32)
            o, s = kda_chunked(q, k, v, log_a, beta, s0, c.kda_chunk)
            o = o[:, :T]
        with jax.named_scope("attn_out"):
            return self._kda_out(p, o, gate), s, tail

    def _kda_decode(self, p, h, s, tail):
        """h (B, d), s (B, H, K, V), tail (B, 3, 3 H K)."""
        c = self.config
        with jax.named_scope("attn_qkv"):
            pre, log_a, beta, gate = self._kda_project(p, h)
            with jax.named_scope("kda_conv"):
                rows = jnp.concatenate([tail, pre[:, None]], axis=1)
                conved = jnp.sum(p["conv"].astype(jnp.float32)
                                 * rows.astype(jnp.float32), axis=1)
                q, k, v = self._kda_qkv(conved)
                tail = rows[:, 1:]
        with jax.named_scope("attn_core"), jax.named_scope("kda_state"):
            s, o = kda_step(s, q, k, v, log_a, beta)
        with jax.named_scope("attn_out"):
            return self._kda_out(p, o, gate), s, tail

    def _mla_project(self, p, h):
        """h (..., d) -> q_nope (..., H, n), q_rope (..., H, r) and the row
        the cache keeps: [rms(c), k_rope, zeros] (..., latent_row)."""
        c = self.config
        with jax.named_scope("mla_proj"):
            q = _mm(h, p["w_q"]).astype(c.dtype).reshape(
                *h.shape[:-1], c.mla_heads, c.qk_nope_dim + c.qk_rope_dim)
            kva = _mm(h, p["w_kva"])
            lat = _rms(kva[..., :c.kv_lora_rank], p["kv_norm"], c.rms_eps)
            row = jnp.concatenate([lat, kva[..., c.kv_lora_rank:]],
                                  axis=-1).astype(c.dtype)
            row = jnp.pad(row, [(0, 0)] * (row.ndim - 1)
                          + [(0, c.latent_row - c.latent_dim)])
        return q[..., :c.qk_nope_dim], q[..., c.qk_nope_dim:], row

    def _mla_kvb(self, p):
        c = self.config
        kvb = p["w_kvb"].reshape(c.kv_lora_rank, c.mla_heads,
                                 c.qk_nope_dim + c.v_head_dim)
        return kvb[..., :c.qk_nope_dim], kvb[..., c.qk_nope_dim:]

    def _mla_full(self, p, h):
        """Expanded form over (B, T, d), causal, in blocks of queries.
        Returns (y, the latent rows (B, T, latent_row))."""
        c = self.config
        B, T, _ = h.shape
        scale = (c.qk_nope_dim + c.qk_rope_dim) ** -0.5
        with jax.named_scope("attn_qkv"):
            q_n, q_r, row = self._mla_project(p, h)
            with jax.named_scope("mla_proj"):
                wk, wv = self._mla_kvb(p)
                lat = row[..., :c.kv_lora_rank]
                k_r = row[..., c.kv_lora_rank:c.latent_dim]
                k_n = jnp.einsum("btc,chn->bthn", lat, wk,
                                 preferred_element_type=jnp.float32
                                 ).astype(c.dtype)
                v = jnp.einsum("btc,chv->bthv", lat, wv,
                               preferred_element_type=jnp.float32
                               ).astype(c.dtype)
        with jax.named_scope("attn_core"), jax.named_scope("mla_attend"):
            bq = min(_QUERY_BLOCK, T)
            pad = -T % bq
            nb = (T + pad) // bq

            def blocks(a):
                a = jnp.pad(a, ((0, 0), (0, pad), (0, 0), (0, 0)))
                return a.reshape(B, nb, bq, *a.shape[2:]).swapaxes(0, 1)

            def one(args):
                qn_b, qr_b, i0 = args
                s = (jnp.einsum("bqhn,bkhn->bhqk", qn_b, k_n,
                                preferred_element_type=jnp.float32)
                     + jnp.einsum("bqhr,bkr->bhqk", qr_b, k_r,
                                  preferred_element_type=jnp.float32)) * scale
                ok = (i0 + jnp.arange(bq))[:, None] >= jnp.arange(T)[None, :]
                s = jnp.where(ok[None, None], s, -1e30)
                pr = jax.nn.softmax(s, axis=-1).astype(c.dtype)
                return jnp.einsum("bhqk,bkhv->bqhv", pr, v,
                                  preferred_element_type=jnp.float32
                                  ).astype(c.dtype)

            o = lax.map(one, (blocks(q_n), blocks(q_r),
                              jnp.arange(nb) * bq))
            o = o.swapaxes(0, 1).reshape(B, T + pad, -1)[:, :T]
        with jax.named_scope("attn_out"), jax.named_scope("mla_proj"):
            return _mm(o, p["w_o"]).astype(c.dtype), row

    def _mla_decode(self, p, h, pool, tables, positions, page_tokens):
        """Absorbed form: h (B, d) against the slot's pages of latent rows.
        The step's own row is written first, then read back with the rest."""
        c = self.config
        B = h.shape[0]
        P = int(page_tokens)
        S = tables.shape[1] * P
        R = c.kv_lora_rank
        scale = (c.qk_nope_dim + c.qk_rope_dim) ** -0.5
        with jax.named_scope("attn_qkv"):
            q_n, q_r, row = self._mla_project(p, h)
            with jax.named_scope("mla_proj"):
                wk, wv = self._mla_kvb(p)
                q_c = jnp.einsum("bhn,chn->bhc", q_n, wk,
                                 preferred_element_type=jnp.float32
                                 ).astype(c.dtype)
        with jax.named_scope("kv_write"):
            # a position past the last logical page (a retired slot) goes to
            # the trash page, the pool's last, which no table row owns
            page = jnp.where(
                positions < S,
                tables[jnp.arange(B), jnp.minimum(positions // P,
                                                  tables.shape[1] - 1)],
                pool.shape[0] - 1)
            pool = pool.at[page, positions % P].set(row)
        with jax.named_scope("kv_gather"):
            view = pool.at[tables].get(mode="promise_in_bounds").reshape(
                B, S, c.latent_row)
            view_c, view_r = view[..., :R], view[..., R:c.latent_dim]
        with jax.named_scope("attn_core"), jax.named_scope("mla_attend"):
            s = (jnp.einsum("bhc,bsc->bhs", q_c, view_c,
                            preferred_element_type=jnp.float32)
                 + jnp.einsum("bhr,bsr->bhs", q_r, view_r,
                              preferred_element_type=jnp.float32)) * scale
            live = jnp.arange(S)[None, :] <= positions[:, None]
            s = jnp.where(live[:, None, :], s, -1e30)
            pr = jax.nn.softmax(s, axis=-1).astype(c.dtype)
            o_c = jnp.einsum("bhs,bsc->bhc", pr, view_c,
                             preferred_element_type=jnp.float32
                             ).astype(c.dtype)
        with jax.named_scope("attn_out"), jax.named_scope("mla_proj"):
            o = jnp.einsum("bhc,chv->bhv", o_c, wv,
                           preferred_element_type=jnp.float32).astype(c.dtype)
            return _mm(o.reshape(B, -1), p["w_o"]).astype(c.dtype), pool

    # ------------------------------------------------------ full forward
    def _trunk(self, params, tokens, last_idx):
        """tokens (B, T) -> (x (B, T, d) before the final norm, cache
        entries). Rows after ``last_idx`` are padding."""
        c = self.config
        T = tokens.shape[1]
        valid = jnp.arange(T) <= last_idx
        x = self._embed(params, tokens)
        entries = {"latent": [], "kda_s": [], "kda_conv": []}
        for blk, spec in zip(params["blocks"], c.layers):
            h = self._ln(blk["ln1"], x).astype(c.dtype)
            if spec.mixer == "kda":
                y, s, tail = self._kda_full(blk["mixer"], h, valid, last_idx)
                entries["kda_s"].append(s)
                entries["kda_conv"].append(tail)
            else:
                y, row = self._mla_full(blk["mixer"], h)
                entries["latent"].append(row)
            x = x + y
            y, _ = self._ffn(blk, spec, self._ln(blk["ln2"], x),
                             jnp.broadcast_to(valid, tokens.shape))
            x = x + y
        return x, entries

    def apply(self, params, tokens):
        """tokens (B, T) int32 -> logits (B, T, V) float32."""
        x, _ = self._trunk(params, tokens, tokens.shape[1] - 1)
        return self._head(params, x)

    # ------------------------------------------------------ cache protocol
    def prefill_cache(self, params, tokens, last_idx):
        """tokens (B, T_bucket), the prompt's last token at ``last_idx`` ->
        (logits of that token (B, 1, V), entries for :meth:`insert_paged`)."""
        x, entries = self._trunk(params, tokens, last_idx)
        last = lax.dynamic_slice_in_dim(x, last_idx, 1, axis=1)
        return self._head(params, last), entries

    @staticmethod
    def entries_tokens(entries) -> int:
        return entries["latent"][0].shape[1] if entries["latent"] \
            else 1

    @staticmethod
    def entries_row(entries, b: int):
        return jax.tree.map(lambda a: a[b:b + 1], entries)

    def new_paged_cache(self, slots: int, n_pages: int, page_tokens: int,
                        quant: bool = False) -> Dict:
        c = self.config
        H, K = c.kda_heads, c.kda_head_dim
        return {
            "latent": [jnp.zeros((n_pages, page_tokens, c.latent_row),
                                 c.dtype) for _ in self.mla_layers],
            "kda_s": [jnp.zeros((slots, H, K, K), jnp.float32)
                      for _ in self.kda_layers],
            "kda_conv": [jnp.zeros((slots, c.kda_conv - 1, 3 * H * K),
                                   c.dtype) for _ in self.kda_layers]}

    def page_bytes(self, page_tokens: int, quant: bool = False) -> int:
        c = self.config
        return (len(self.mla_layers) * page_tokens * c.latent_row
                * jnp.dtype(c.dtype).itemsize)

    def slot_state_bytes(self) -> int:
        c = self.config
        H, K = c.kda_heads, c.kda_head_dim
        return len(self.kda_layers) * (
            H * K * K * 4
            + (c.kda_conv - 1) * 3 * H * K * jnp.dtype(c.dtype).itemsize)

    def insert_paged(self, arrays, entries, page_ids, slot, page_tokens):
        """One prefilled prompt (batch 1) into ``slot``: its latent rows
        into the slot's pages, its states over whatever the slot held."""
        out = {"latent": [], "kda_s": [], "kda_conv": []}
        with jax.named_scope("kv_write"):
            for pool, rows in zip(arrays["latent"], entries["latent"]):
                tb = rows.shape[1]
                npb = -(-tb // page_tokens)
                rows = jnp.pad(rows[0], ((0, npb * page_tokens - tb), (0, 0)))
                out["latent"].append(pool.at[page_ids].set(
                    rows.reshape(npb, page_tokens, -1)))
            for name in ("kda_s", "kda_conv"):
                for held, new in zip(arrays[name], entries[name]):
                    out[name].append(lax.dynamic_update_slice_in_dim(
                        held, new.astype(held.dtype), slot, axis=0))
        return out

    def decode_paged(self, params, arrays, tables, tokens, positions,
                     page_tokens):
        """One token a slot: tokens, positions (B,) -> (logits (B, V),
        arrays, stats int32[3] summed over the expert layers). A slot whose
        table points at the trash page is free: it routes to no expert."""
        c = self.config
        occupied = tables[:, 0] != (arrays["latent"][0].shape[0] - 1) \
            if arrays["latent"] else None
        x = self._embed(params, tokens)
        out = {"latent": [], "kda_s": [], "kda_conv": []}
        stats = jnp.zeros((len(self.step_stats),), jnp.int32)
        i_kda = i_mla = 0
        for blk, spec in zip(params["blocks"], c.layers):
            h = self._ln(blk["ln1"], x).astype(c.dtype)
            if spec.mixer == "kda":
                y, s, tail = self._kda_decode(
                    blk["mixer"], h, arrays["kda_s"][i_kda],
                    arrays["kda_conv"][i_kda])
                out["kda_s"].append(s)
                out["kda_conv"].append(tail)
                i_kda += 1
            else:
                y, pool = self._mla_decode(
                    blk["mixer"], h, arrays["latent"][i_mla], tables,
                    positions, page_tokens)
                out["latent"].append(pool)
                i_mla += 1
            x = x + y
            y, st = self._ffn(blk, spec, self._ln(blk["ln2"], x), occupied)
            if st is not None:
                stats = stats + st
            x = x + y
        return self._head(params, x), out, stats
