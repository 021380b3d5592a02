"""Flash attention: Pallas TPU kernels, forward and backward.

Reference role: the reference's attention ops (SURVEY D3 attention layers,
`MultiHeadDotProductAttention` lowering to libnd4j matmuls) materialize the
(T, T) score matrix in memory. These kernels are the TPU-native replacement:
the score matrix exists one tile at a time in VMEM, forward and backward, so
memory is O(T·d) not O(T²) and nothing of size T x T is written to HBM.

Design:
- one layout for both kernels: ``(N, T, lanes)`` arrays read in *slabs* of
  lanes. A model's ``(B, T, H, hd)`` activations are ``(B, T, H·hd)`` as they
  stand (no transpose): with hd = 64 a slab is 128 lanes, two heads a grid
  cell, each head's matmuls taken out of the slab by zeroing the other
  head's lanes of one operand (a 128-deep contraction costs the MXU what a
  64-deep one does). ``(..., T, d)`` callers are one head a slab.
- scores are held transposed in both kernels, (keys, queries): the
  softmax's maxima and sums run down the rows (vreg against vreg, no
  reduction along the lanes), and ``lse`` and ``rowsum(o·do)`` are dense
  rows that broadcast over a tile's rows.
- forward: grid (N, slab, q-block); K and V of the slab stay in VMEM for all
  of a sequence's q-blocks, an in-kernel loop runs the online softmax over
  the k-tiles a q-block can see: tiles above the causal diagonal are never
  touched, only tiles on it are masked.
- backward: ONE kernel, grid (N, slab, k-block). It recomputes each
  probability tile from ``lse``, accumulates dK/dV for its k-block and dQ
  (transposed) for the whole sequence in float32 VMEM scratch. Residuals
  are q, k, v, o and ``lse`` only.
- exponentials and running sums in float32, matmul operands in the input
  dtype with float32 accumulation. Tile sizes are a function of the
  sequence length (``default_blocks``), from a sweep on the chip.
- interpret mode off-TPU (only there) so the tests run the same code.
"""
from __future__ import annotations

import functools
import math
from typing import Optional

import jax
import jax.numpy as jnp
from jax import lax
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

_NEG_INF = -1e30
_LANES = 128
_NT = (((1,), (1,)), ((), ()))      # a @ b.T
_TN = (((0,), (0,)), ((), ()))      # a.T @ b


def default_blocks(seq_len: int):
    """(forward, backward) square tile sizes for a sequence length, from the
    sweep on the v5e at head size 64, causal, B x H = 8 x 16 (PERF.md, PR
    28). Forward: the largest of 512 / 256 / 128 that divides the length
    (at T = 1024: 512 0.64 ms, 256 0.95, 1024 0.77; three of four tiles
    computed). Backward: 256 up to 1024 (0.94 ms; 512 0.96), as forward
    beyond (T = 8192: 512 35.0 ms, 256 37.8). A length no size divides is
    padded to the next multiple of 256 (of 128 up to 256)."""
    fit = next((b for b in (512, 256, 128) if seq_len % b == 0),
               256 if seq_len > 256 else 128)
    return fit, fit if seq_len > 1024 else min(fit, 256)


def _round_up(x: int, m: int) -> int:
    return -(-x // m) * m


def _head_lanes(width: int, heads: int):
    """(1, width) bool masks, one a head of the slab; None for one head."""
    if heads == 1:
        return [None]
    lane = lax.broadcasted_iota(jnp.int32, (1, width), 1)
    hd = width // heads
    return [(lane >= g * hd) & (lane < (g + 1) * hd) for g in range(heads)]


def _own_lanes(x, mask, scale=None):
    """``x`` with the lanes of other heads zeroed (and scaled, in float32)."""
    if scale is not None:
        x = (x.astype(jnp.float32) * scale).astype(x.dtype)
    return x if mask is None else jnp.where(mask, x, jnp.zeros_like(x))


# ---------------------------------------------------------------- forward
def _fwd_kernel(q_ref, k_ref, v_ref, o_ref, lse_ref, m_ref, l_ref, acc_ref, *,
                scale, causal, heads, block_k, seq_k):
    """Grid cell = (n, slab, q-block). ``k_ref``/``v_ref`` hold the slab's
    whole (padded) key sequence; the loop visits k-tiles left to right.
    Scores are held transposed, (keys, queries): the softmax's maxima and
    sums then run down the rows, vreg against vreg, where reductions along
    the lanes would cost several times the tile's other arithmetic, and the
    running statistics are dense (1, block_q) rows."""
    bq, width = q_ref.shape[1], q_ref.shape[2]
    n_kb = k_ref.shape[1] // block_k
    q_start = pl.program_id(2) * bq
    lanes = _head_lanes(width, heads)
    q = q_ref[0]
    qs = [_own_lanes(q, mk, scale) for mk in lanes]
    m_ref[...] = jnp.full_like(m_ref, _NEG_INF)
    l_ref[...] = jnp.zeros_like(l_ref)
    acc_ref[...] = jnp.zeros_like(acc_ref)

    def tile(j, masked):
        start = pl.multiple_of(j * block_k, block_k)
        k = k_ref[0, pl.ds(start, block_k), :]
        v = v_ref[0, pl.ds(start, block_k), :]
        if masked:
            rows = start + lax.broadcasted_iota(jnp.int32, (block_k, bq), 0)
            keep = rows < seq_k                       # the padded tail
            if causal:
                cols = q_start + lax.broadcasted_iota(
                    jnp.int32, (block_k, bq), 1)
                keep = keep & (cols >= rows)
        for g in range(heads):
            st = lax.dot_general(k, qs[g], _NT,
                                 preferred_element_type=jnp.float32)
            if masked:
                st = jnp.where(keep, st, _NEG_INF)
            m = m_ref[g]
            m_new = jnp.maximum(m, jnp.max(st, axis=0, keepdims=True))
            pt = jnp.exp(st - m_new)
            alpha = jnp.exp(m - m_new)
            l_ref[g] = l_ref[g] * alpha + jnp.sum(pt, axis=0, keepdims=True)
            # (lanes, queries): every lane of the slab against the head's
            # probabilities; the other heads' lanes are dropped at the end
            acc_ref[g] = acc_ref[g] * alpha + lax.dot_general(
                v, pt.astype(v.dtype), _TN,
                preferred_element_type=jnp.float32)
            m_ref[g] = m_new

    # tiles whose every entry counts, then those cut by the diagonal or by
    # the padded tail; tiles past the diagonal are not visited at all
    if causal:
        n_full = jnp.minimum(q_start + 1, seq_k) // block_k
        n_need = jnp.minimum((q_start + bq + block_k - 1) // block_k, n_kb)
    else:
        n_full, n_need = seq_k // block_k, n_kb
    lax.fori_loop(0, n_full, lambda j, c: (tile(j, False), c)[1], 0)
    lax.fori_loop(n_full, n_need, lambda j, c: (tile(j, True), c)[1], 0)

    out = None
    for g, mk in enumerate(lanes):
        l = jnp.maximum(l_ref[g], 1e-30)
        og = (acc_ref[g] / l).T                               # (bq, lanes)
        out = og if mk is None else jnp.where(
            mk, og, jnp.zeros_like(og) if out is None else out)
        lse_ref[0, 0, g:g + 1, :] = m_ref[g] + jnp.log(l)
    o_ref[0] = out.astype(o_ref.dtype)


def _pad_seq(x, to):
    pad = to - x.shape[1]
    return jnp.pad(x, ((0, 0), (0, pad), (0, 0))) if pad else x


def _tiling(seq_len, block):
    """(block, padded length): a short sequence is one block of its own
    length rounded up to 8 rows."""
    block = min(block, _round_up(seq_len, 8))
    return block, _round_up(seq_len, block)


def _vmem_limit(n_bytes):
    # the compiler's default scoped limit is 16 MiB of the v5e's 128; ask
    # for what the resident sequence needs, with room for the tiles
    return int(min(max(2 * n_bytes + (8 << 20), 32 << 20), 110 << 20))


# jitted, so that a model's layers share ONE traced and lowered kernel:
# tracing and lowering a call a layer cost gpt2-medium's step 12 s of set-up
@functools.partial(jax.jit, static_argnums=(3, 4, 5, 6, 7, 8, 9))
def _fwd_pallas(q, k, v, heads, slab, scale, causal, block_q, block_k,
                interpret):
    """q (N, Tq, lanes), k/v (N, Tk, lanes) -> o like q, lse (N, lanes /
    slab, heads, padded Tq) float32."""
    n, seq_q, width = q.shape
    seq_k = k.shape[1]
    bq, pad_q = _tiling(seq_q, block_q)
    bk, pad_k = _tiling(seq_k, block_k)
    q, k, v = _pad_seq(q, pad_q), _pad_seq(k, pad_k), _pad_seq(v, pad_k)
    n_slab = width // slab
    kernel = functools.partial(_fwd_kernel, scale=scale, causal=causal,
                               heads=heads, block_k=bk, seq_k=seq_k)
    o, lse = pl.pallas_call(
        kernel,
        grid=(n, n_slab, pad_q // bq),
        in_specs=[
            pl.BlockSpec((1, bq, slab), lambda b, s, i: (b, i, s)),
            pl.BlockSpec((1, pad_k, slab), lambda b, s, i: (b, 0, s)),
            pl.BlockSpec((1, pad_k, slab), lambda b, s, i: (b, 0, s)),
        ],
        out_specs=[
            pl.BlockSpec((1, bq, slab), lambda b, s, i: (b, i, s)),
            pl.BlockSpec((1, 1, heads, bq), lambda b, s, i: (b, s, 0, i)),
        ],
        out_shape=[
            jax.ShapeDtypeStruct((n, pad_q, width), q.dtype),
            jax.ShapeDtypeStruct((n, n_slab, heads, pad_q), jnp.float32),
        ],
        scratch_shapes=[
            pltpu.VMEM((heads, 1, bq), jnp.float32),
            pltpu.VMEM((heads, 1, bq), jnp.float32),
            pltpu.VMEM((heads, slab, bq), jnp.float32),
        ],
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "parallel", "arbitrary"),
            vmem_limit_bytes=_vmem_limit(
                4 * pad_k * slab * k.dtype.itemsize)),
        interpret=interpret,
    )(q, k, v)
    return o[:, :seq_q], lse


# --------------------------------------------------------------- backward
def _bwd_kernel(q_ref, k_ref, v_ref, do_ref, lse_ref, d_ref,
                dq_ref, dk_ref, dv_ref, dq_acc, dk_acc, dv_acc, *,
                scale, causal, heads, block_q, seq_k):
    """Grid cell = (n, slab, k-block). ``q_ref``/``do_ref`` hold the slab's
    whole (padded) query sequence and ``lse_ref``/``d_ref`` its rows as
    (heads, q-blocks, block_q); scores are computed transposed, (k, q)."""
    bk, width = k_ref.shape[1], k_ref.shape[2]
    n_qb = q_ref.shape[1] // block_q
    j, n_kb = pl.program_id(2), pl.num_programs(2)
    k_start = j * bk
    lanes = _head_lanes(width, heads)
    k, v = k_ref[0], v_ref[0]
    ks = [_own_lanes(k, mk, scale) for mk in lanes]
    vs = [_own_lanes(v, mk) for mk in lanes]

    @pl.when(j == 0)
    def _():
        dq_acc[...] = jnp.zeros_like(dq_acc)
    dk_acc[...] = jnp.zeros_like(dk_acc)
    dv_acc[...] = jnp.zeros_like(dv_acc)

    def tile(i, masked):
        start = pl.multiple_of(i * block_q, block_q)
        q = q_ref[0, pl.ds(start, block_q), :]
        do = do_ref[0, pl.ds(start, block_q), :]
        if masked:
            rows = k_start + lax.broadcasted_iota(
                jnp.int32, (bk, block_q), 0)
            keep = rows < seq_k                       # the padded tail
            if causal:
                cols = start + lax.broadcasted_iota(
                    jnp.int32, (bk, block_q), 1)
                keep = keep & (cols >= rows)
        dqt = jnp.zeros((width, block_q), jnp.float32)
        for g in range(heads):
            lse = lse_ref[0, 0, g, pl.ds(i, 1), :]            # (1, bq)
            dd = d_ref[0, 0, g, pl.ds(i, 1), :]
            st = lax.dot_general(ks[g], q, _NT,
                                 preferred_element_type=jnp.float32)
            pt = jnp.exp(st - lse)
            if masked:
                pt = jnp.where(keep, pt, 0.0)
            dpt = lax.dot_general(vs[g], do, _NT,
                                  preferred_element_type=jnp.float32)
            dst = (pt * (dpt - dd)).astype(q.dtype)
            dv_acc[g] += jnp.dot(pt.astype(do.dtype), do,
                                 preferred_element_type=jnp.float32)
            dk_acc[g] += jnp.dot(dst, q, preferred_element_type=jnp.float32)
            # dQ is accumulated transposed, (lanes, queries), so that the
            # large tile is never transposed; ks[g] is scaled and zero
            # outside the head's lanes: the sum over heads lays each
            # head's dQ into its own lanes
            dqt = dqt + lax.dot_general(ks[g], dst, _TN,
                                        preferred_element_type=jnp.float32)
        dq_acc[i] += dqt

    # q-blocks cut by the diagonal (or every one, for the k-block with the
    # padded tail), then those below it; q-blocks above it are not visited
    ragged = seq_k % bk != 0
    if causal:
        first = k_start // block_q
        whole = jnp.minimum((k_start + bk + block_q - 2) // block_q, n_qb)
    else:
        first, whole = 0, 0
    if ragged:
        whole = jnp.where(j == n_kb - 1, n_qb, whole)
    lax.fori_loop(first, whole, lambda i, c: (tile(i, True), c)[1], 0)
    lax.fori_loop(whole, n_qb, lambda i, c: (tile(i, False), c)[1], 0)

    dk = dv = None
    for g, mk in enumerate(lanes):
        if mk is None:
            dk, dv = dk_acc[g], dv_acc[g]
        else:
            dk = jnp.where(mk, dk_acc[g], 0.0 if dk is None else dk)
            dv = jnp.where(mk, dv_acc[g], 0.0 if dv is None else dv)
    dk_ref[0] = (dk * scale).astype(dk_ref.dtype)
    dv_ref[0] = dv.astype(dv_ref.dtype)

    @pl.when(j == n_kb - 1)
    def _():
        def put(i, c):
            start = pl.multiple_of(i * block_q, block_q)
            dq_ref[0, pl.ds(start, block_q), :] = dq_acc[i].T.astype(
                dq_ref.dtype)
            return c
        lax.fori_loop(0, n_qb, put, 0)


@functools.partial(jax.jit, static_argnums=(6, 7, 8, 9, 10, 11, 12))
def _bwd_pallas(q, k, v, o, lse, do, heads, slab, scale, causal, block_q,
                block_k, interpret):
    n, seq_q, width = q.shape
    seq_k = k.shape[1]
    n_slab = width // slab
    bq, pad_q = _tiling(seq_q, block_q)
    bk, pad_k = _tiling(seq_k, block_k)
    n_qb = pad_q // bq
    # rowsum(o . do) a head, laid out like lse: (N, slabs, heads, Tq)
    hd = slab // heads
    d = jnp.sum((o.astype(jnp.float32) * do.astype(jnp.float32)).reshape(
        n, seq_q, n_slab, heads, hd), axis=-1).transpose(0, 2, 3, 1)
    d = jnp.pad(d, ((0, 0),) * 3 + ((0, pad_q - seq_q),))
    assert lse.shape[-1] == pad_q, "forward and backward pad alike"
    rows = (n, n_slab, heads, n_qb, bq)
    q, do = _pad_seq(q, pad_q), _pad_seq(do, pad_q)
    k, v = _pad_seq(k, pad_k), _pad_seq(v, pad_k)
    kernel = functools.partial(_bwd_kernel, scale=scale, causal=causal,
                               heads=heads, block_q=bq, seq_k=seq_k)
    seq_spec = pl.BlockSpec((1, pad_q, slab), lambda b, s, j: (b, 0, s))
    blk_spec = pl.BlockSpec((1, bk, slab), lambda b, s, j: (b, j, s))
    row_spec = pl.BlockSpec((1, 1, heads, n_qb, bq),
                            lambda b, s, j: (b, s, 0, 0, 0))
    dq, dk, dv = pl.pallas_call(
        kernel,
        grid=(n, n_slab, pad_k // bk),
        in_specs=[seq_spec, blk_spec, blk_spec, seq_spec, row_spec, row_spec],
        out_specs=[seq_spec, blk_spec, blk_spec],
        out_shape=[
            jax.ShapeDtypeStruct((n, pad_q, width), q.dtype),
            jax.ShapeDtypeStruct((n, pad_k, width), k.dtype),
            jax.ShapeDtypeStruct((n, pad_k, width), v.dtype),
        ],
        scratch_shapes=[
            pltpu.VMEM((n_qb, slab, bq), jnp.float32),
            pltpu.VMEM((heads, bk, slab), jnp.float32),
            pltpu.VMEM((heads, bk, slab), jnp.float32),
        ],
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "parallel", "arbitrary"),
            vmem_limit_bytes=_vmem_limit(
                pad_q * slab * (6 * q.dtype.itemsize + 4))),
        interpret=interpret,
    )(q, k, v, do, lse.reshape(rows), d.reshape(rows))
    return dq[:, :seq_q], dk[:, :seq_k], dv[:, :seq_k]


def _interpret() -> bool:
    """Interpret mode only where there is no Mosaic compiler (the CPU
    tests). On a TPU backend the kernel always compiles — a kernel that
    does not is an error there, never an interpreted or XLA fallback
    (``chip_smoke.py`` asserts the Mosaic call from the lowering)."""
    return jax.default_backend() != "tpu"


@functools.partial(jax.custom_vjp, nondiff_argnums=(3, 4, 5, 6, 7, 8))
def _flash(q, k, v, heads, slab, scale, causal, fwd_blocks, bwd_blocks):
    o, _ = _fwd_pallas(q, k, v, heads, slab, scale, causal, *fwd_blocks,
                       _interpret())
    return o


def _flash_fwd(q, k, v, heads, slab, scale, causal, fwd_blocks, bwd_blocks):
    o, lse = _fwd_pallas(q, k, v, heads, slab, scale, causal, *fwd_blocks,
                         _interpret())
    return o, (q, k, v, o, lse)


def _flash_bwd(heads, slab, scale, causal, fwd_blocks, bwd_blocks, res, do):
    q, k, v, o, lse = res
    return _bwd_pallas(q, k, v, o, lse, do, heads, slab, scale, causal,
                       *bwd_blocks, _interpret())


_flash.defvjp(_flash_fwd, _flash_bwd)


def _call(q3, k3, v3, heads, slab, scale, causal, block_q, block_k):
    d = slab // heads
    scale = 1.0 / math.sqrt(d) if scale is None else float(scale)
    fwd, bwd = default_blocks(max(q3.shape[1], k3.shape[1]))
    fwd = (int(block_q or fwd), int(block_k or fwd))
    bwd = (int(block_q or bwd), int(block_k or bwd))
    return _flash(q3, k3, v3, heads, slab, scale, bool(causal), fwd, bwd)


def flash_attention(q, k, v, causal: bool = False,
                    scale: Optional[float] = None,
                    block_q: Optional[int] = None,
                    block_k: Optional[int] = None):
    """Memory-efficient attention over (..., T, d) tensors.

    Accepts (B, T, d) or (B, H, T, d); leading dims are flattened into the
    kernel grid, one head a grid cell. ``scale`` defaults to 1/sqrt(d);
    block sizes default to ``default_blocks`` of the sequence length.
    """
    d = q.shape[-1]
    q3 = q.reshape(-1, q.shape[-2], d)
    k3 = k.reshape(-1, k.shape[-2], d)
    v3 = v.reshape(-1, v.shape[-2], d)
    o = _call(q3, k3, v3, 1, d, scale, causal, block_q, block_k)
    return o.reshape(q.shape)


def flash_attention_bthd(q, k, v, causal: bool = False,
                         scale: Optional[float] = None,
                         block_q: Optional[int] = None,
                         block_k: Optional[int] = None):
    """The same over (B, T, H, hd) tensors as a model's projections make
    them, returning (B, T, H, hd): the kernels index the (B, T, H·hd) array
    by slabs of lanes, so nothing is transposed. Head sizes that neither
    fill nor evenly share a 128-lane slab go through ``flash_attention``."""
    b, t, h, hd = q.shape
    if hd % _LANES == 0:
        heads = 1
    elif _LANES % hd == 0 and h % (_LANES // hd) == 0:
        heads = _LANES // hd
    else:
        o = flash_attention(*(x.transpose(0, 2, 1, 3) for x in (q, k, v)),
                            causal=causal, scale=scale, block_q=block_q,
                            block_k=block_k)
        return o.transpose(0, 2, 1, 3)
    o = _call(q.reshape(b, t, h * hd), k.reshape(b, k.shape[1], h * hd),
              v.reshape(b, v.shape[1], h * hd), heads, heads * hd, scale,
              causal, block_q, block_k)
    return o.reshape(q.shape)


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
