"""Ring attention — sequence/context parallelism over the ``seq`` mesh axis.

Net-new capability (SURVEY P6/§5.7: the reference has NO sequence-dimension
distribution; its long-sequence story is truncated BPTT). Design follows the
blockwise/ring-attention recipe: Q stays resident, K/V blocks rotate around
the ring via ``lax.ppermute`` over ICI neighbors, and softmax is accumulated
online (running max / sum-exp) in float32 so the full T×T score matrix never
materializes on any chip. Compute for block i overlaps the permute of block
i+1 (XLA schedules the collective-permute off the critical path).

Memory per chip: O(T/P · d) activations instead of O(T²) scores — this is
what makes >100k-token sequences trainable on a slice.
"""
from __future__ import annotations

import functools
from typing import Optional

import jax
import jax.numpy as jnp
import numpy as np
from jax import lax
from jax import shard_map
from jax.sharding import Mesh, PartitionSpec as P

from deeplearning4j_tpu.parallel.mesh import (
    DATA_AXIS, MODEL_AXIS, SEQ_AXIS, axis_size)


def _block_attn_update(q, k, v, m, l, o, q_start, k_start, causal, scale):
    """One online-softmax accumulation step against a K/V block.

    q: (B, Tq, H, D); k/v: (B, Tk, H, D); m/l: (B, H, Tq); o: (B, Tq, H, D).
    """
    s = jnp.einsum("bqhd,bkhd->bhqk", q, k, preferred_element_type=jnp.float32) * scale
    if causal:
        qi = q_start + jnp.arange(q.shape[1])
        ki = k_start + jnp.arange(k.shape[1])
        mask = qi[:, None] >= ki[None, :]            # allow key_pos <= query_pos
        s = jnp.where(mask[None, None], s, -jnp.inf)
    m_blk = jnp.max(s, axis=-1)                      # (B, H, Tq)
    m_new = jnp.maximum(m, m_blk)
    # fully-masked rows: keep m finite so exp() stays well-defined
    m_safe = jnp.where(jnp.isneginf(m_new), 0.0, m_new)
    p = jnp.exp(s - m_safe[..., None])
    p = jnp.where(jnp.isneginf(s), 0.0, p)
    corr = jnp.exp(jnp.where(jnp.isneginf(m), m_safe * 0 - jnp.inf, m - m_safe))
    corr = jnp.where(jnp.isneginf(m), 0.0, corr)
    l_new = l * corr + jnp.sum(p, axis=-1)
    # bf16 operands + f32 accumulation (preferred_element_type) — an
    # f32×f32 matmul would fall off the fast MXU path
    pv = jnp.einsum("bhqk,bkhd->bqhd", p.astype(v.dtype), v,
                    preferred_element_type=jnp.float32)
    o_new = o * corr.transpose(0, 2, 1)[..., None] + pv
    return m_new, l_new, o_new


def _ring_attention_local(q, k, v, axis_name: str, causal: bool,
                          vary_axes=()):
    """Per-shard body under shard_map. q/k/v: (B, T/P, H, D) local blocks."""
    my_idx = lax.axis_index(axis_name)
    p_size = lax.axis_size(axis_name)
    b, tq, h, d = q.shape
    tk = k.shape[1]
    scale = 1.0 / np.sqrt(d)

    m0 = jnp.full((b, h, tq), -jnp.inf, jnp.float32)
    l0 = jnp.zeros((b, h, tq), jnp.float32)
    o0 = jnp.zeros((b, tq, h, d), jnp.float32)
    # mark accumulators device-varying over every axis the block inputs vary
    # on, so the fori_loop carry type matches the body output (shard_map vma
    # typing)
    vary = tuple(vary_axes) or (axis_name,)
    m0, l0, o0 = (lax.pcast(a, vary, to="varying") for a in (m0, l0, o0))
    perm = [(j, (j + 1) % p_size) for j in range(p_size)]

    def body(i, carry):
        k_blk, v_blk, m, l, o = carry
        # after i rotations, this device holds the block that started at
        # ring position (my_idx - i) mod P
        blk_idx = jnp.mod(my_idx - i, p_size)
        m, l, o = _block_attn_update(q, k_blk, v_blk, m, l, o,
                                     my_idx * tq, blk_idx * tk, causal, scale)
        k_blk = lax.ppermute(k_blk, axis_name, perm)
        v_blk = lax.ppermute(v_blk, axis_name, perm)
        return k_blk, v_blk, m, l, o

    _, _, m, l, o = lax.fori_loop(0, p_size, body, (k, v, m0, l0, o0))
    l = jnp.maximum(l, 1e-30)
    out = o / l.transpose(0, 2, 1)[..., None]
    return out.astype(q.dtype)


def ring_attention(q, k, v, mesh: Mesh, seq_axis: str = SEQ_AXIS,
                   causal: bool = False):
    """Sequence-sharded attention. q/k/v: (B, T, H, D) GLOBAL shapes, sharded
    (or shardable) on T over ``seq_axis``. Returns (B, T, H, D) with the same
    sharding. Falls back to plain attention when the axis is absent/size 1."""
    if seq_axis not in mesh.axis_names or axis_size(mesh, seq_axis) == 1:
        return _plain_attention(q, k, v, causal)
    # keep batch sharded over 'data' and heads over 'model' inside the ring —
    # replicating them here would make every device recompute the global batch
    batch_ax = DATA_AXIS if axis_size(mesh, DATA_AXIS) > 1 else None
    head_ax = (MODEL_AXIS if axis_size(mesh, MODEL_AXIS) > 1
               and q.shape[2] % axis_size(mesh, MODEL_AXIS) == 0 else None)
    spec = P(batch_ax, seq_axis, head_ax, None)
    vary = tuple(a for a in (batch_ax, seq_axis, head_ax) if a is not None)
    fn = shard_map(
        functools.partial(_ring_attention_local, axis_name=seq_axis,
                          causal=causal, vary_axes=vary),
        mesh=mesh, in_specs=(spec, spec, spec), out_specs=spec)
    return fn(q, k, v)


def _plain_attention(q, k, v, causal: bool = False):
    """Single-shard XLA attention (the flash-kernel crosscheck baseline).

    The (B,H,T,T) score/probability tensors stay in the compute dtype —
    in bf16 they cost half the HBM traffic of f32 and both matmuls ride the
    fast MXU path (accumulation is f32 inside the MXU regardless). exp/sum
    run in f32 on the fly (XLA fuses; nothing f32 materializes). Full-f32
    softmax accuracy is the flash kernel's job (online f32 accumulation).
    """
    d = q.shape[-1]
    s = jnp.einsum("bqhd,bkhd->bhqk", q, k) / float(np.sqrt(d))
    if causal:
        tq, tk = q.shape[1], k.shape[1]
        mask = jnp.arange(tq)[:, None] >= jnp.arange(tk)[None, :]
        # finite sentinel: -inf arithmetic in low precision breeds NaNs on
        # the (impossible-here, but ragged-block) fully-masked rows
        s = jnp.where(mask[None, None], s, jnp.asarray(-1e30, s.dtype))
    m = jax.lax.stop_gradient(jnp.max(s, axis=-1, keepdims=True))
    p = jnp.exp((s - m).astype(jnp.float32))
    p = (p / jnp.sum(p, axis=-1, keepdims=True)).astype(q.dtype)
    return jnp.einsum("bhqk,bkhd->bqhd", p, v)
