"""Flash attention: Pallas TPU forward kernel + blockwise backward.

Reference role: the reference's attention ops (SURVEY D3 attention layers,
`MultiHeadDotProductAttention` lowering to libnd4j matmuls) materialize the
(T, T) score matrix in memory. This kernel is the TPU-native replacement:
online-softmax tiles stream K/V through VMEM so memory is O(T·d) not O(T²),
which is what makes the long-context path (SURVEY 5.7) viable per chip.

Design:
- forward: Pallas kernel, one grid cell per (batch·head, q-block); runs in
  interpret mode off-TPU (only there) so tests exercise the same code path.
- backward: custom_vjp recomputing per k-block inside a lax.scan (standard
  flash backward), fully fused by XLA — no (T, T) residuals are saved.
"""
from __future__ import annotations

import functools
import math
from typing import Optional

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

# 512x1024 tiles: hardware-measured best on v5e (2026-07-31 crossover
# sweep, benchmarks/flash_crossover.py — beat 256/512 at every T probed,
# 17.2 ms vs 19.8 ms at T=8192); clamped to seq_len below, so short
# sequences degrade gracefully
DEFAULT_BLOCK_Q = 512
DEFAULT_BLOCK_K = 1024
_NEG_INF = -1e30


def _attn_fwd_kernel(q_ref, k_ref, v_ref, o_ref, lse_ref,
                     o_acc, m_acc, l_acc, *,
                     scale: float, causal: bool, block_k: int, seq_k: int,
                     n_kb: int):
    """Grid cell = (batch·head, q-block, k-block). K/V are tiled into VMEM
    one block_k slab at a time by the BlockSpec pipeline (so VMEM use is
    O(block_q·d + block_k·d) regardless of sequence length); the online-
    softmax state lives in VMEM scratch that persists across the innermost
    (k-block) grid dimension."""
    kb = pl.program_id(2)

    @pl.when(kb == 0)
    def _init():
        o_acc[...] = jnp.zeros_like(o_acc)
        m_acc[...] = jnp.full_like(m_acc, _NEG_INF)
        l_acc[...] = jnp.zeros_like(l_acc)

    q = q_ref[0]                                      # (bq, d) compute dtype
    bq = q.shape[0]
    q_start = pl.program_id(1) * bq
    def _update():
        k = k_ref[0]                                  # (bk, d)
        v = v_ref[0]
        # MXU-native: low-precision operands, f32 accumulation — an f32×f32
        # matmul here runs at a fraction of bf16 MXU rate (the round-2 perf
        # regression found by device-side op profiling)
        s = jax.lax.dot_general(
            q, k, (((1,), (1,)), ((), ())),
            preferred_element_type=jnp.float32) * scale   # (bq, bk) f32
        k_idx = kb * block_k + jax.lax.broadcasted_iota(jnp.int32, s.shape, 1)
        mask = k_idx < seq_k                          # ragged tail block
        if causal:
            q_idx = q_start + jax.lax.broadcasted_iota(jnp.int32, s.shape, 0)
            mask = mask & (q_idx >= k_idx)
        s = jnp.where(mask, s, _NEG_INF)

        m = m_acc[...]
        m_new = jnp.maximum(m, jnp.max(s, axis=1))
        p = jnp.exp(s - m_new[:, None])
        alpha = jnp.exp(m - m_new)
        l_acc[...] = l_acc[...] * alpha + jnp.sum(p, axis=1)
        pv = jax.lax.dot_general(
            p.astype(v.dtype), v, (((1,), (0,)), ((), ())),
            preferred_element_type=jnp.float32)
        o_acc[...] = o_acc[...] * alpha[:, None] + pv
        m_acc[...] = m_new

    if causal:
        # a k-block strictly past this q-block's last row contributes
        # nothing — skip its matmuls entirely (halves MXU work)
        pl.when(kb * block_k <= q_start + bq - 1)(_update)
    else:
        _update()

    @pl.when(kb == n_kb - 1)
    def _finalize():
        l = jnp.maximum(l_acc[...], 1e-30)
        o_ref[0] = (o_acc[...] / l[:, None]).astype(o_ref.dtype)
        # lse is carried as (bh, q, 1): a (block_q, 1) block satisfies the
        # Mosaic tiling rule (sublane dim % 8 == 0, lane dim == array dim),
        # where a (1, block_q) block of a 2-D (bh, q) array would not
        lse_ref[0] = (m_acc[...] + jnp.log(l))[:, None]


def _fwd_pallas(q, k, v, scale, causal, block_q, block_k, interpret):
    bh, seq_q, d = q.shape
    seq_k = k.shape[1]
    block_q = min(block_q, seq_q)
    block_k = min(block_k, seq_k)
    # pad to block multiples so every grid tile is full (the kernel masks
    # k >= seq_k in the ragged tail tile)
    pad_q = (-seq_q) % block_q
    pad_k = (-seq_k) % block_k
    if pad_q:
        q = jnp.pad(q, ((0, 0), (0, pad_q), (0, 0)))
    if pad_k:
        k = jnp.pad(k, ((0, 0), (0, pad_k), (0, 0)))
        v = jnp.pad(v, ((0, 0), (0, pad_k), (0, 0)))
    padded_q, padded_k = seq_q + pad_q, seq_k + pad_k
    n_kb = padded_k // block_k
    grid = (bh, padded_q // block_q, n_kb)
    kernel = functools.partial(_attn_fwd_kernel, scale=scale, causal=causal,
                               block_k=block_k, seq_k=seq_k, n_kb=n_kb)
    out_shapes = [
        jax.ShapeDtypeStruct((bh, padded_q, d), q.dtype),
        jax.ShapeDtypeStruct((bh, padded_q, 1), jnp.float32),
    ]
    o, lse = pl.pallas_call(
        kernel,
        grid=grid,
        in_specs=[
            pl.BlockSpec((1, block_q, d), lambda b, i, j: (b, i, 0)),
            pl.BlockSpec((1, block_k, d), lambda b, i, j: (b, j, 0)),
            pl.BlockSpec((1, block_k, d), lambda b, i, j: (b, j, 0)),
        ],
        out_specs=[
            pl.BlockSpec((1, block_q, d), lambda b, i, j: (b, i, 0)),
            pl.BlockSpec((1, block_q, 1), lambda b, i, j: (b, i, 0)),
        ],
        out_shape=out_shapes,
        scratch_shapes=[
            pltpu.VMEM((block_q, d), jnp.float32),
            pltpu.VMEM((block_q,), jnp.float32),
            pltpu.VMEM((block_q,), jnp.float32),
        ],
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "parallel", "arbitrary")),
        interpret=interpret,
    )(q, k, v)
    return o[:, :seq_q], lse[:, :seq_q, 0]


def _bwd_blockwise(q, k, v, o, lse, do, scale, causal, block_k):
    """Flash backward: scan over k-blocks, recomputing p per block."""
    bh, seq_q, d = q.shape
    seq_k = k.shape[1]
    block_k = min(block_k, seq_k)
    n_kb = seq_k // block_k if seq_k % block_k == 0 \
        else seq_k // block_k + 1
    pad = n_kb * block_k - seq_k
    if pad:
        kp = jnp.pad(k, ((0, 0), (0, pad), (0, 0)))
        vp = jnp.pad(v, ((0, 0), (0, pad), (0, 0)))
    else:
        kp, vp = k, v
    kb = kp.reshape(bh, n_kb, block_k, d)
    vb = vp.reshape(bh, n_kb, block_k, d)

    # every matmul below: low-precision operands + f32 accumulation
    # (preferred_element_type) — f32×f32 operands would fall off the fast
    # MXU path, which device-side op profiling showed dominating step time
    D = jnp.sum(do.astype(jnp.float32) * o.astype(jnp.float32), axis=-1)
    q_idx = jnp.arange(seq_q)

    def body(dq, blk):
        kblk, vblk, kb_i = blk                              # (bh, bk, d)
        s = jnp.einsum("bqd,bkd->bqk", q, kblk,
                       preferred_element_type=jnp.float32) * scale
        k_idx = kb_i * block_k + jnp.arange(block_k)
        valid = k_idx < seq_k
        mask = valid[None, :]
        if causal:
            mask = mask & (q_idx[:, None] >= k_idx[None, :])
        s = jnp.where(mask[None], s, _NEG_INF)
        p = jnp.exp(s - lse[..., None])                     # (bh, q, bk) f32
        pl_ = p.astype(q.dtype)
        dv = jnp.einsum("bqk,bqd->bkd", pl_, do,
                        preferred_element_type=jnp.float32)
        dp = jnp.einsum("bqd,bkd->bqk", do, vblk,
                        preferred_element_type=jnp.float32)
        ds = (p * (dp - D[..., None])).astype(q.dtype)
        dq = dq + scale * jnp.einsum("bqk,bkd->bqd", ds, kblk,
                                     preferred_element_type=jnp.float32)
        dk = scale * jnp.einsum("bqk,bqd->bkd", ds, q,
                                preferred_element_type=jnp.float32)
        return dq, (dk, dv)

    dq0 = jnp.zeros((bh, seq_q, d), jnp.float32)
    dq, (dks, dvs) = jax.lax.scan(
        body, dq0, (kb.transpose(1, 0, 2, 3), vb.transpose(1, 0, 2, 3),
                    jnp.arange(n_kb)))
    dk = dks.transpose(1, 0, 2, 3).reshape(bh, n_kb * block_k, d)[:, :seq_k]
    dv = dvs.transpose(1, 0, 2, 3).reshape(bh, n_kb * block_k, d)[:, :seq_k]
    return dq.astype(q.dtype), dk.astype(k.dtype), dv.astype(v.dtype)


def _interpret() -> bool:
    """Interpret mode only where there is no Mosaic compiler (the CPU
    tests). On a TPU backend the kernel always compiles — a kernel that
    does not is an error there, never an interpreted or XLA fallback
    (``chip_smoke.py`` asserts the Mosaic call from the lowering)."""
    return jax.default_backend() != "tpu"


@functools.partial(jax.custom_vjp, nondiff_argnums=(3, 4, 5, 6))
def _flash(q, k, v, scale, causal, block_q, block_k):
    o, _ = _fwd_pallas(q, k, v, scale, causal, block_q, block_k,
                       _interpret())
    return o


def _flash_fwd(q, k, v, scale, causal, block_q, block_k):
    o, lse = _fwd_pallas(q, k, v, scale, causal, block_q, block_k,
                         _interpret())
    return o, (q, k, v, o, lse)


def _flash_bwd(scale, causal, block_q, block_k, res, do):
    q, k, v, o, lse = res
    return _bwd_blockwise(q, k, v, o, lse, do, scale, causal, block_k)


_flash.defvjp(_flash_fwd, _flash_bwd)


def flash_attention(q, k, v, causal: bool = False,
                    scale: Optional[float] = None,
                    block_q: int = DEFAULT_BLOCK_Q,
                    block_k: int = DEFAULT_BLOCK_K):
    """Memory-efficient attention over (..., T, d) tensors.

    Accepts (B, T, d) or (B, H, T, d); leading dims are flattened into the
    kernel grid. ``scale`` defaults to 1/sqrt(d).
    """
    orig_shape = q.shape
    d = q.shape[-1]
    if scale is None:
        scale = 1.0 / math.sqrt(d)
    q3 = q.reshape(-1, q.shape[-2], d)
    k3 = k.reshape(-1, k.shape[-2], d)
    v3 = v.reshape(-1, v.shape[-2], d)
    o = _flash(q3, k3, v3, float(scale), bool(causal),
               int(block_q), int(block_k))
    return o.reshape(orig_shape)


def naive_attention(q, k, v, causal: bool = False,
                    scale: Optional[float] = None):
    """O(T²)-memory reference implementation for crosschecks."""
    d = q.shape[-1]
    if scale is None:
        scale = 1.0 / math.sqrt(d)
    s = jnp.einsum("...qd,...kd->...qk", q, k) * scale
    if causal:
        t_q, t_k = s.shape[-2], s.shape[-1]
        mask = jnp.arange(t_q)[:, None] >= jnp.arange(t_k)[None, :]
        s = jnp.where(mask, s, _NEG_INF)
    p = jax.nn.softmax(s, axis=-1)
    return jnp.einsum("...qk,...kd->...qd", p, v)
