"""Paged latent attention: one Pallas TPU kernel for the absorbed latent
attention of a decode step, over each slot's LIVE pages where they lie.

What ``models/hybrid.py::HybridLM._mla_decode`` asks for: one query a slot
and head against the slot's cached rows, ``softmax(scale * q . row) @
row[:, :R]``, the rows found through the slot's page table. Spelled in XLA
that is a gather of every slot's WHOLE window into a (B, S, row) view, written
and read back, and two einsums that read it twice more, whatever part of the
window is live (PERF.md, PR 42). Here:

- grid (slots,). The page tables and the positions are prefetched scalars;
  the pool stays in HBM and no block of it is copied by a ``BlockSpec``. For
  slot b the kernel visits pages ``0 .. positions[b] // P`` and no other: a
  dead page of the window, a page of another slot and the trash page are
  never fetched.
- a visit is :func:`visit_pages` pages (8 pages of 64 rows of 640, 640 KB),
  each fetched by an asynchronous copy of its own into one
  half of a double buffer in VMEM while the visit before it is computed; the
  last visit of a slot starts the first of the next slot.
- the query is ``[q_c | q_r | 0]`` as wide as a cached row, whose padding is
  zeros by ``HybridConfig.latent_row``'s contract: ONE product gives
  ``q_c . c + q_r . k_r``, and the output's product reads the first R lanes
  of the SAME copy of the page in VMEM. Each live row crosses HBM once.
- scores in float32 times ``scale``, rows beyond ``positions[b]`` masked to
  -1e30, an online softmax over the visits (running maximum, running sum,
  float32 accumulator (H, R)), probabilities rounded to the rows' dtype
  before the second product, as the XLA spelling rounds them.

The same walk serves the grouped-query attention of a decode step
(``HybridLM._gqa_decode``, :func:`paged_grouped_attention`): a cached row is
``[k heads | v heads]``, so a visit makes two products a key/value head
(:func:`_attend_grouped`) where the latent attention makes two in all; the
queries of a key/value head are padded to whole sublane tiles. What
``TransformerLM``'s per-layer K and V pools would change is again the row and
the product of one visit. Interpret mode off the TPU (only there) so the
tests run the same code.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax import lax
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from deeplearning4j_tpu.kernels.flash_attention import _NEG_INF, _NT

#: what one visit moves. A 64-row page of 640 bfloat16 lanes is 80 KB, 0.1 us
#: of HBM time beside about 0.35 us a step of any loop around it: eight of
#: them hide that (PERF.md section 6, PR 42)
VISIT_BYTES = 640 << 10
#: what a call may ask of the 16 MiB of VMEM that Mosaic gives a kernel
#: unasked, by :func:`vmem_bytes`'s count, which runs 1-3 MiB over Mosaic's
#: own: 20.3 MiB where Mosaic counted 19.34 and refused (one page of 5,120
#: rows of 640 a visit, 32 heads), and Mosaic takes the largest visit this
#: admits (2,752 rows under 64 heads; compiled for a v5e in
#: tests/test_grouped_ffn.py)
VMEM_BYTES = 12 << 20


#: a visit of the grouped-query walk: a 64-row page of [k | v] on 8 heads of
#: 128 is 256 KB, so four of them, 1.3 us of HBM time a visit
GROUPED_VISIT_BYTES = 1 << 20


def visit_pages(page_tokens: int, row: int, itemsize: int, pages: int,
                visit_bytes: int = VISIT_BYTES) -> int:
    """Pages a visit: as many as ``visit_bytes`` hold, within the window, and
    one page where a page alone is more."""
    return max(1, min(pages, visit_bytes // (page_tokens * row * itemsize)))


def vmem_bytes(heads: int, row: int, page_tokens: int, pages: int,
               itemsize: int = 2, visit_bytes: int = VISIT_BYTES) -> int:
    """What a slot's walk holds: the two halves of the buffer, the half in
    use as the two products read it, and a visit's scores, their exponentials
    (float32) and the probabilities as the rows' type."""
    n = visit_pages(page_tokens, row, itemsize, pages,
                    visit_bytes) * page_tokens
    return 3 * n * row * itemsize + heads * n * (4 + 4 + itemsize)


def fits_vmem(heads: int, row: int, page_tokens: int, pages: int,
              itemsize: int = 2, visit_bytes: int = VISIT_BYTES) -> bool:
    """Whether a visit fits: a visit is no less than a page, so a page of
    thousands of rows does not."""
    return vmem_bytes(heads, row, page_tokens, pages, itemsize,
                      visit_bytes) <= VMEM_BYTES


def grouped_query_rows(per_group: int, itemsize: int) -> int:
    """Rows the queries of one key/value head take in the kernel: their
    count rounded up to whole sublane tiles of the rows' type (8 rows of 4
    bytes, 16 of 2), so that a head's slice of the scores is whole tiles."""
    tile = 8 * max(1, 4 // itemsize)
    return -(-per_group // tile) * tile


def _attend(q, rows, live, scale, carry, out_width):
    """One visit's product and its part of the online softmax: q (H, row),
    ``rows`` (n, row), ``live`` (1, n) -> the new (maximum (H, 1), sum
    (H, 1), accumulator (H, out_width))."""
    m, l, acc = carry
    s = jnp.where(live, scale * lax.dot_general(
        q, rows, _NT, preferred_element_type=jnp.float32), _NEG_INF)
    m_new = jnp.maximum(m, jnp.max(s, axis=-1, keepdims=True))
    alpha = jnp.exp(m - m_new)
    p = jnp.exp(s - m_new)
    l = alpha * l + jnp.sum(p, axis=-1, keepdims=True)
    acc = alpha * acc + jnp.dot(p.astype(rows.dtype), rows[:, :out_width],
                                preferred_element_type=jnp.float32)
    return m_new, l, acc


def _attend_grouped(q, rows, live, scale, carry, groups):
    """One visit of the grouped-query walk: q (groups * n_q, hd), the
    queries of key/value head g in rows ``g n_q ..``; ``rows`` (n, 2 groups
    hd), ``[k heads | v heads]``; ``live`` (1, n) -> the new (maximum, sum
    (groups * n_q, 1), accumulator (groups * n_q, hd)). Two products a
    key/value head, each on that head's lanes of the one copy of the page."""
    m, l, acc = carry
    hd, n_q = q.shape[1], q.shape[0] // groups
    s = jnp.concatenate([lax.dot_general(
        q[g * n_q:(g + 1) * n_q], rows[:, g * hd:(g + 1) * hd], _NT,
        preferred_element_type=jnp.float32) for g in range(groups)], axis=0)
    s = jnp.where(live, scale * s, _NEG_INF)
    m_new = jnp.maximum(m, jnp.max(s, axis=-1, keepdims=True))
    alpha = jnp.exp(m - m_new)
    p = jnp.exp(s - m_new)
    l = alpha * l + jnp.sum(p, axis=-1, keepdims=True)
    p = p.astype(rows.dtype)
    acc = alpha * acc + jnp.concatenate([jnp.dot(
        p[g * n_q:(g + 1) * n_q],
        rows[:, (groups + g) * hd:(groups + g + 1) * hd],
        preferred_element_type=jnp.float32) for g in range(groups)], axis=0)
    return m_new, l, acc


def _kernel(tables_ref, pos_ref, q_ref, pool_ref, o_ref, buf, sem, side0_ref,
            *, page_tokens, visit, pages, attend):
    """Grid cell = one slot. ``buf`` (2, visit * P, row) is the double
    buffer, ``sem`` one DMA semaphore a half, ``side0_ref`` the half this
    slot's first visit was fetched into (by the slot before it).
    ``attend(q, rows, live, carry)`` is one visit's part of the online
    softmax."""
    P, G = page_tokens, visit
    b, n_slots = pl.program_id(0), pl.num_programs(0)
    out_width = o_ref.shape[-1]

    def live_pages(slot):
        return jnp.minimum(pos_ref[slot] // P + 1, pages)

    def fetch(slot, v, side, start):
        """Start, or wait for, the copies of visit ``v`` of ``slot``: its
        live pages and no other."""
        left = live_pages(slot) - v * G
        for j in range(G):
            @pl.when(j < left)
            def _():
                page = tables_ref[slot * pages + v * G + j] if start else 0
                copy = pltpu.make_async_copy(
                    pool_ref.at[page], buf.at[side, pl.ds(j * P, P)],
                    sem.at[side])
                if start:
                    copy.start()
                else:
                    copy.wait()

    @pl.when(b == 0)
    def _():
        # a half's rows behind a visit's last live page are whatever was
        # there: rows fetched earlier (finite, weighted 0), never VMEM's
        # first contents
        buf[...] = jnp.zeros_like(buf)
        side0_ref[0] = 0
        fetch(0, 0, 0, True)

    side0 = side0_ref[0]
    pos = jnp.minimum(pos_ref[b], pages * P - 1)    # within the window
    visits = pl.cdiv(live_pages(b), G)
    q = q_ref[...]

    def one(v, carry):
        side = (side0 + v) % 2

        @pl.when(v + 1 < visits)
        def _():
            fetch(b, v + 1, 1 - side, True)

        @pl.when(jnp.logical_and(v + 1 == visits, b + 1 < n_slots))
        def _():
            fetch(b + 1, 0, 1 - side, True)

        fetch(b, v, side, False)
        at = v * (G * P) + lax.broadcasted_iota(jnp.int32, (1, G * P), 1)
        return attend(q, buf[side], at <= pos, carry)

    H = q.shape[0]
    _m, l, acc = lax.fori_loop(0, visits, one, (
        jnp.full((H, 1), _NEG_INF, jnp.float32),
        jnp.zeros((H, 1), jnp.float32),
        jnp.zeros((H, out_width), jnp.float32)))
    o_ref[...] = (acc / l).astype(o_ref.dtype)
    side0_ref[0] = (side0 + visits) % 2


def _walk(q, pool, tables, positions, out_width, attend, visit, interpret):
    """The page walk for q (B, H, width) against ``pool`` (pages, P, row):
    (B, H, out_width)."""
    B, H, width = q.shape
    _n, P, row = pool.shape
    pages = tables.shape[1]

    def slot(b, *_scalars):
        return b, 0, 0

    return pl.pallas_call(
        functools.partial(_kernel, page_tokens=P, visit=visit, pages=pages,
                          attend=attend),
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=2,
            grid=(B,),
            in_specs=[pl.BlockSpec((None, H, width), slot),
                      pl.BlockSpec(memory_space=pl.ANY)],
            out_specs=pl.BlockSpec((None, H, out_width), slot),
            scratch_shapes=[pltpu.VMEM((2, visit * P, row), pool.dtype),
                            pltpu.SemaphoreType.DMA((2,)),
                            pltpu.SMEM((1,), jnp.int32)]),
        out_shape=jax.ShapeDtypeStruct((B, H, out_width), q.dtype),
        compiler_params=pltpu.CompilerParams(
            # a slot's last visit fetches the next slot's first
            dimension_semantics=("arbitrary",)),
        interpret=interpret,
    )(tables.reshape(-1).astype(jnp.int32), positions.astype(jnp.int32), q,
      pool)


# jitted, so that a program's latent layers share ONE traced and lowered kernel
@functools.partial(jax.jit, static_argnums=(4, 5, 6, 7))
def _paged_latent_attention(q, pool, tables, positions, out_width, scale,
                            visit, interpret):
    def attend(q, rows, live, carry):
        return _attend(q, rows, live, scale, carry, out_width)

    return _walk(q, pool, tables, positions, out_width, attend, visit,
                 interpret)


@functools.partial(jax.jit, static_argnums=(4, 5, 6, 7))
def _paged_grouped_attention(q, pool, tables, positions, groups, scale,
                             visit, interpret):
    def attend(q, rows, live, carry):
        return _attend_grouped(q, rows, live, scale, carry, groups)

    return _walk(q, pool, tables, positions, q.shape[-1], attend, visit,
                 interpret)


def paged_latent_attention(q, pool, tables, positions, out_width: int,
                           scale: float):
    """q (B, H, row): every head's query against a whole cached row; ``pool``
    (pages, P, row) in HBM; ``tables`` (B, pages a slot) int32 and
    ``positions`` (B,) int32: slot b's rows ``0 .. positions[b]`` are live
    and lie in pages ``tables[b, 0 .. positions[b] // P]`` -> (B, H,
    out_width) in q's dtype: ``softmax(scale * q . rows) @ rows[:,
    :out_width]`` over the live rows, :func:`visit_pages` pages a visit. A
    position beyond the window reads the whole window."""
    B, _heads, row = q.shape
    if pool.ndim != 3 or pool.shape[2] != row or pool.dtype != q.dtype \
            or tables.shape[0] != B or positions.shape != (B,):
        raise ValueError(f"q {q.shape} {q.dtype}, pool {pool.shape} "
                         f"{pool.dtype}, tables {tables.shape} and positions "
                         f"{positions.shape} are not one paged layer")
    if not 0 < out_width <= row:
        raise ValueError(f"the output reads {out_width} of a row's {row}")
    visit = visit_pages(pool.shape[1], row, pool.dtype.itemsize,
                        tables.shape[1])
    # interpret mode only where there is no Mosaic compiler (the CPU tests)
    return _paged_latent_attention(q, pool, tables, positions, out_width,
                                   float(scale), visit,
                                   jax.default_backend() != "tpu")


def paged_grouped_attention(q, pool, tables, positions, scale: float):
    """q (B, G, K, hd): K query heads on each of G key/value heads; ``pool``
    (pages, P, 2 G hd), a row ``[k heads | v heads]``; ``tables`` and
    ``positions`` as :func:`paged_latent_attention` takes them -> (B, G, K,
    hd) in q's dtype: ``softmax(scale * q . k) @ v`` a head over the slot's
    live rows, read where they lie, each once."""
    B, G, K, hd = q.shape
    if pool.ndim != 3 or pool.shape[2] != 2 * G * hd \
            or pool.dtype != q.dtype or tables.shape[0] != B \
            or positions.shape != (B,):
        raise ValueError(f"q {q.shape} {q.dtype}, pool {pool.shape} "
                         f"{pool.dtype}, tables {tables.shape} and positions "
                         f"{positions.shape} are not one paged layer")
    n_q = grouped_query_rows(K, pool.dtype.itemsize)
    visit = visit_pages(pool.shape[1], pool.shape[2], pool.dtype.itemsize,
                        tables.shape[1], GROUPED_VISIT_BYTES)
    qp = jnp.pad(q, ((0, 0), (0, 0), (0, n_q - K), (0, 0)))
    o = _paged_grouped_attention(
        qp.reshape(B, G * n_q, hd), pool, tables, positions, G, float(scale),
        visit, jax.default_backend() != "tpu")
    return o.reshape(B, G, n_q, hd)[:, :, :K]
