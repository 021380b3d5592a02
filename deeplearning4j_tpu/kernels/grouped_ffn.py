"""Grouped feed-forward: one Pallas TPU kernel for the routed experts' two
products and the activation between them, for rows that fit ONE row tile.

What ``parallel/moe.py::routed_experts_ffn`` asks for: rows sorted by expert,
``sizes[e]`` of them for held expert ``e``; for the rows of one expert
``y = act(rows @ W1[e]) @ w_down[e]``. As two ``lax.ragged_dot`` that is two
grouped-matmul kernels of the compiler's with the float32 hidden rows through
HBM between them, each at a third of the chip's memory bound when an expert
has 2-13 rows (PERF.md, PRs 27, 32 and 35). Here:

- grid (experts that have a row, tiles of the experts' inner width f). The
  touched experts' indices and the groups' offsets are prefetched scalars;
  experts without rows are never visited, so nothing of theirs is read, and
  the steps behind the last touched expert keep the last block indices (no
  copy) and do nothing.
- a step streams one f tile of the expert's first matrix (gate and up tiles
  are two block specs on the one ``w_gu`` array) and the same tile's rows of
  ``w_down``, double-buffered by the pipeline, as the leaves are held: no
  second copy, no other layout. Each touched expert's weights are read once.
- the rows and their float32 result stay in VMEM for the whole call; the
  hidden rows never leave the chip. Inside a step the group's rows are taken
  in windows of ``ts`` rows from a start aligned to the sublane tiling, rows
  of other groups masked to 0 after the activation (both forms give 0 for 0).
- bfloat16 operands, float32 accumulation of both products, the activation
  in float32 and rounded to the rows' dtype where ``moe._activate`` rounds.

One row tile is all it handles (:func:`fits_one_tile`; a decode step's rows
do, no prefill bucket's do): the caller keeps ``lax.ragged_dot`` for more.
Interpret mode off the TPU (only there) so the tests run the same code.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax import lax
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from deeplearning4j_tpu.kernels.flash_attention import _round_up

_SUB = 16           # rows a packed bfloat16 tile holds: every window's start
_LANES = 128

#: what a step may move of one expert's matrices (the pipeline holds two such
#: buffers). From the sweep on the v5e (benchmarks/grouped_ffn_sweep.py;
#: PERF.md section 6, PR 36): GB/s of the touched bytes rises with the f tile
#: up to about 7 MB a step and is flat beyond
STEP_BYTES = 8 << 20
#: what the kernel may ask of the v5e's 128 MiB of VMEM for the rows, their
#: float32 result and a step of weights, each held twice by the pipeline
VMEM_BYTES = 96 << 20
#: rows a window: 16 to 128 read alike at 2-13 rows an expert (the sweep)
WINDOWS = (32, 128)
#: form -> how many f-wide tiles of columns the first matrix has (gate|up: 2)
N_FIRST = {"swiglu": 2, "relu2": 1}


def default_tiles(rows: int, width: int, inner: int, n_first: int,
                  experts: int, itemsize: int = 2):
    """(tf, ts) for ``rows`` pair rows of ``width`` through ``experts``
    experts of inner width ``inner`` whose first matrix is ``n_first`` tiles
    wide (2 for gate|up): the f tile (the widest multiple of 128 lanes that
    divides f and keeps a step under ``STEP_BYTES``; f itself where no such
    divisor exists) and the window (twice the mean rows an expert, within
    ``WINDOWS`` and the rows)."""
    fits = [d for d in range(_LANES, inner + 1, _LANES) if inner % d == 0
            and (n_first + 1) * width * d * itemsize <= STEP_BYTES]
    tf = max(fits) if fits else (_LANES if inner % _LANES == 0 else inner)
    mean = 2 * rows // max(experts, 1)
    ts = min(WINDOWS[1], max(WINDOWS[0], 1 << max(mean - 1, 0).bit_length()))
    return tf, min(ts, _round_up(rows, _SUB))


def vmem_bytes(rows: int, width: int, tf: int, n_first: int,
               itemsize: int = 2) -> int:
    """What the pipeline holds: the row tile with its float32 result and one
    step of an expert's matrices, each twice."""
    return 2 * (_round_up(rows, _SUB) * width * (itemsize + 4)
                + (n_first + 1) * width * tf * itemsize)


def fits_one_tile(rows: int, width: int, inner: int, n_first: int,
                  experts: int, itemsize: int = 2) -> bool:
    """Whether ``rows`` pair rows are one row tile: the only call the kernel
    takes."""
    tf, _ts = default_tiles(rows, width, inner, n_first, experts, itemsize)
    return vmem_bytes(rows, width, tf, n_first, itemsize) <= VMEM_BYTES


def between(form: str, hs):
    """The activation, float32 -> float32: ``swiglu`` of (gate, up) tiles,
    ``relu2`` of one (``moe._activate`` on the columns of one f tile)."""
    if form == "swiglu":
        gate, up = hs
        return jax.nn.silu(gate) * up
    (h,) = hs
    return jnp.square(jax.nn.relu(h))


def touched_of(sizes):
    """The groups' metadata: (offsets (E + 1,), the experts that have a row
    in order (E,), how many they are (1,)), all int32. Entries behind the
    last touched expert repeat it."""
    count = sizes.shape[0]
    offsets = jnp.concatenate([jnp.zeros((1,), jnp.int32),
                               jnp.cumsum(sizes, dtype=jnp.int32)])
    upto = jnp.cumsum(sizes > 0, dtype=jnp.int32)   # touched among 0..e
    n = upto[-1]
    v = jnp.minimum(jnp.arange(count, dtype=jnp.int32), jnp.maximum(n - 1, 0))
    # the v-th touched expert is the first whose count passes v (one fused
    # compare-and-sum; no sort, no loop)
    touched = jnp.searchsorted(upto, v, side="right", method="compare_all")
    return (offsets, jnp.minimum(touched, count - 1).astype(jnp.int32),
            n.reshape(1))


def _kernel(offs_ref, touched_ref, n_ref, rows_ref, *refs, form, ts):
    """Grid cell = (touched expert, f tile). ``refs``: the first matrix's
    tile(s) (w, tf), ``w_down``'s (tf, w), the float32 result (tm, w)."""
    *first_refs, down_ref, out_ref = refs
    tm = rows_ref.shape[0]
    v, j = pl.program_id(0), pl.program_id(1)

    @pl.when(jnp.logical_and(v == 0, j == 0))
    def _():
        out_ref[...] = jnp.zeros_like(out_ref)

    @pl.when(v < n_ref[0])
    def _():
        e = touched_ref[v]
        lo, hi = offs_ref[e], offs_ref[e + 1]
        base = lo // _SUB * _SUB

        def window(i, carry):
            own = base + i * ts                 # this window answers for
            start = pl.multiple_of(jnp.minimum(own, tm - ts), _SUB)
            r = start + lax.broadcasted_iota(jnp.int32, (ts, 1), 0)
            keep = (r >= jnp.maximum(lo, own)) & (r < jnp.minimum(hi,
                                                                   own + ts))
            x = rows_ref[pl.ds(start, ts), :]
            a = between(form, [
                jnp.dot(x, w[...], preferred_element_type=jnp.float32)
                for w in first_refs])
            a = jnp.where(keep, a, 0.0).astype(x.dtype)
            out_ref[pl.ds(start, ts), :] += jnp.dot(
                a, down_ref[...], preferred_element_type=jnp.float32)
            return carry

        lax.fori_loop(0, pl.cdiv(hi - base, ts), window, 0)


# jitted, so that a program's expert layers share ONE traced and lowered kernel
@functools.partial(jax.jit, static_argnums=(4, 5, 6))
def _grouped_ffn(rows, w_first, w_down, sizes, form, tiles, interpret):
    m, w = rows.shape
    count, f, _w = w_down.shape
    n_first = w_first.shape[2] // f
    tf, ts = tiles
    nf = f // tf
    tm = _round_up(m, _SUB)
    if tm != m:
        rows = jnp.pad(rows, ((0, tm - m), (0, 0)))
    offsets, touched, n = touched_of(sizes)

    def weights(col0):
        # behind the last touched expert the block stays where it was
        def index(v, j, _offs, touched, n):
            return touched[v], 0, col0 + jnp.where(v < n[0], j, nf - 1)
        return pl.BlockSpec((None, w, tf), index)

    def down(v, j, _offs, touched, n):
        return touched[v], jnp.where(v < n[0], j, nf - 1), 0

    def whole(v, j, *_scalars):
        return 0, 0

    need = vmem_bytes(m, w, tf, n_first, w_down.dtype.itemsize)
    y = pl.pallas_call(
        functools.partial(_kernel, form=form, ts=ts),
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=3,
            grid=(count, nf),
            in_specs=[pl.BlockSpec((tm, w), whole)]
            + [weights(i * nf) for i in range(n_first)]
            + [pl.BlockSpec((None, tf, w), down)],
            out_specs=pl.BlockSpec((tm, w), whole)),
        out_shape=jax.ShapeDtypeStruct((tm, w), jnp.float32),
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("arbitrary", "arbitrary"),
            # the compiler's default scoped limit is 16 MiB
            vmem_limit_bytes=max(need + (8 << 20), 32 << 20)),
        interpret=interpret,
    )(offsets, touched, n, rows, *([w_first] * n_first), w_down)
    return (y[:m] if tm != m else y), n[0]


def grouped_ffn(rows, w_first, w_down, sizes, form: str, tiles=None):
    """rows (M, w) sorted by expert, ``w_first`` (E, w, 2f) for ``swiglu``
    (gate columns then up columns) or (E, w, f) for ``relu2``, ``w_down``
    (E, f, w), ``sizes`` (E,) int32 rows an expert -> (y (M, w) float32, how
    many experts' weights the kernel streamed, int32: those with a row).
    Rows behind the last group belong to no expert and are 0 or another
    group's masked rows (the caller masks them, as it does ``ragged_dot``'s).
    ``tiles`` is (f tile, window), by default :func:`default_tiles`'s. The
    rows are ONE row tile: more than :func:`fits_one_tile` allows raises."""
    count, f, w = w_down.shape
    n_first = N_FIRST[form]
    if w_first.shape != (count, w, n_first * f) or rows.shape[1] != w:
        raise ValueError(f"rows {rows.shape}, first {w_first.shape} and "
                         f"down {w_down.shape} are not one {form} layer")
    itemsize = w_down.dtype.itemsize
    tf, ts = tiles or default_tiles(rows.shape[0], w, f, n_first, count,
                                    itemsize)
    tm = _round_up(rows.shape[0], _SUB)
    if ts % _SUB or ts > tm or f % tf:
        raise ValueError(f"tiles {(tf, ts)}: the f tile a divisor of {f}, "
                         f"the window a multiple of {_SUB} within the "
                         f"{tm} rows")
    need = vmem_bytes(rows.shape[0], w, tf, n_first, itemsize)
    if need > VMEM_BYTES:
        raise ValueError(f"{rows.shape[0]} rows of {w} are more than one row "
                         f"tile ({need >> 20} MiB of {VMEM_BYTES >> 20}): the "
                         "kernel walks no row tiles")
    # interpret mode only where there is no Mosaic compiler (the CPU tests)
    return _grouped_ffn(rows, w_first, w_down, sizes.astype(jnp.int32), form,
                        (tf, ts), jax.default_backend() != "tpu")
