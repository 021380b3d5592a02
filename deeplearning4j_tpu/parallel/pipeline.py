"""Pipeline parallelism — GPipe micro-batch schedule over the ``stage`` mesh
axis (SURVEY P5: ABSENT in the reference; net-new TPU capability).

Design (TPU-idiomatic, no per-stage processes): the layer stack is split
into S stages; each device along ``stage`` holds ONE stage's params
(leading-axis sharded pytree). A ``shard_map`` program runs the classic
GPipe schedule: at tick t, stage s processes micro-batch (t − s); between
ticks activations hop one stage to the right via ``lax.ppermute`` over ICI.
The whole schedule — one tick per micro-batch plus the (S−1)-tick bubble —
is nested ``lax.fori_loop``s inside one jitted program, and it is
DIFFERENTIABLE: jax reverse-mode through the ppermute ring gives the
backward pipeline (and with it micro-batch gradient accumulation) for free —
the hand-built 1F1B machinery of torch-style PP collapses into autodiff.

Memory is O(M/S) micro-batches per device (M = micro-batch count), not the
round-2 O(M)-replicated queue:

- **input**: the queue is block-sharded over ``stage`` — stage s holds
  micro-batches [s·Q, (s+1)·Q) where Q = M/S. Stage 0 consumes its resident
  slab one micro-batch per tick; every Q ticks the slabs rotate one stage
  down (s → s−1), so the block stage 0 needs next is always arriving.
  Amortized rotation traffic: one micro-batch per tick — the same order as
  the activation hop itself.
- **output**: finished micro-batches ride a systolic channel DOWN the ring
  (stage S−1 → 0, opposite to activations): every tick each stage forwards
  its channel slot and the last stage inserts the micro-batch it just
  finished; each stage copies out the passing micro-batches it owns
  (block-layout home: stage s keeps finished [s·Q, (s+1)·Q)). The last
  arrival lands exactly on the final tick — no extra ticks needed.

Bubble fraction stays the standard (S−1)/(M+S−1) — callers pick M >> S.
"""
from __future__ import annotations

import functools
from typing import Callable, Optional

import jax
import jax.numpy as jnp
import numpy as np
from jax import lax
from jax import shard_map
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

from deeplearning4j_tpu.parallel.mesh import STAGE_AXIS, axis_size


def stack_stage_params(per_stage_params):
    """[stage0_tree, stage1_tree, ...] → one tree with a leading stage axis
    (shardable over ``stage``)."""
    return jax.tree.map(lambda *leaves: jnp.stack(leaves), *per_stage_params)


def shard_stage_params(stacked, mesh: Mesh):
    """Place the stacked tree so each stage device holds its own slice."""
    spec = jax.tree.map(
        lambda a: NamedSharding(mesh, P(STAGE_AXIS)), stacked)
    return jax.device_put(stacked, spec)


def gpipe(stage_fn: Callable, mesh: Mesh, num_stages: Optional[int] = None,
          batch_axis: Optional[str] = None):
    """Build a pipelined forward: ``fn(stacked_params, x_micro) -> y_micro``.

    ``stage_fn(stage_params, h) -> h`` is the per-stage computation (same
    activation shape in/out — transformer-block-stack shaped, which is what
    pipelining is for). ``x_micro``: (M, micro_batch, ...) micro-batches.
    Returns (M, micro_batch, ...) outputs after all S stages.

    ``batch_axis``: optionally shard the micro-batch dim of activations over
    a second mesh axis (PP × DP composition); params stay replicated over it.

    ``stage_fn`` may also accept a third argument — the (traced) micro-batch
    index — e.g. to derive per-micro-batch dropout keys.
    """
    S = num_stages or axis_size(mesh, STAGE_AXIS)
    import inspect
    takes_mb = len(inspect.signature(stage_fn).parameters) >= 3

    def local(params_slice, x_slab):     # runs per stage device
        # params_slice: (1, ...) leading stage slice; x_slab: (Q, mb, ...) —
        # this stage's block of the micro-batch queue (NOT the full queue)
        p = jax.tree.map(lambda a: a[0], params_slice)
        stage_id = lax.axis_index(STAGE_AXIS)
        Q = x_slab.shape[0]
        M = Q * S                        # padded micro-batch count
        mb_shape = x_slab.shape[1:]
        n_phases = S + int(np.ceil((S - 1) / Q))   # covers M + S - 1 ticks

        down = [(i, (i - 1) % S) for i in range(S)]
        up = [(i, (i + 1) % S) for i in range(S)]

        def tick(t, carry):
            slab, h, chan, out = carry
            # stage 0 ingests micro-batch t from its resident slab; others
            # use the activation handed over from the left neighbour
            feed = lax.dynamic_index_in_dim(slab, jnp.mod(t, Q), 0,
                                            keepdims=False)
            h_in = jnp.where(stage_id == 0, feed, h)
            mb_idx = t - stage_id                 # micro-batch at this stage
            active = (mb_idx >= 0) & (mb_idx < M)
            h_out = (stage_fn(p, h_in, jnp.clip(mb_idx, 0)) if takes_mb
                     else stage_fn(p, h_in))
            h_out = jnp.where(active, h_out, h_in)
            # ---- output channel: shift down, last stage inserts its result
            chan = lax.ppermute(chan, STAGE_AXIS, down)
            chan = jnp.where(stage_id == S - 1, h_out, chan)
            # the micro-batch in this stage's channel slot right now
            m = t - 2 * (S - 1) + stage_id
            own = (m >= 0) & (m < M) & (m // Q == stage_id)
            idx = jnp.mod(jnp.clip(m, 0), Q)
            out = jnp.where(own, out.at[idx].set(chan), out)
            # ---- activation hop right (the pipeline edge itself)
            h = lax.ppermute(h_out, STAGE_AXIS, up)
            return slab, h, chan, out

        def phase(ph, carry):
            def inner(i, c):
                return tick(ph * Q + i, c)
            slab, h, chan, out = lax.fori_loop(0, Q, inner, carry)
            # stage 0 finished block ph; bring the next block down one stage
            slab = lax.ppermute(slab, STAGE_AXIS, down)
            return slab, h, chan, out

        h0 = jnp.zeros(mb_shape, x_slab.dtype)
        chan0 = jnp.zeros(mb_shape, x_slab.dtype)
        out0 = jnp.zeros_like(x_slab)
        _, _, _, out = lax.fori_loop(0, n_phases, phase,
                                     (x_slab, h0, chan0, out0))
        return out

    def run(stacked_params, x_micro):
        M = x_micro.shape[0]
        Q = -(-M // S)                   # ceil: pad the queue to S·Q
        pad = S * Q - M
        if pad:
            x_micro = jnp.concatenate(
                [x_micro, jnp.zeros((pad,) + x_micro.shape[1:],
                                    x_micro.dtype)], axis=0)
        pspecs = jax.tree.map(lambda _: P(STAGE_AXIS), stacked_params)
        act_spec = P(*([STAGE_AXIS, batch_axis]
                       + [None] * (x_micro.ndim - 2))) \
            if batch_axis else P(STAGE_AXIS)
        f = shard_map(local, mesh=mesh, in_specs=(pspecs, act_spec),
                      out_specs=act_spec, check_vma=False)
        out = f(stacked_params, x_micro)
        return out[:M] if pad else out

    return run


def pipeline_trunk_1f1b(stage_fn: Callable, mesh: Mesh,
                        num_stages: Optional[int] = None,
                        batch_axis: Optional[str] = None):
    """A differentiable pipelined trunk with a **1F1B backward**: forward
    is the GPipe schedule (`gpipe`), but reverse-mode runs the 1F1B
    wavefront (explicit per-tick vjp, cotangents ppermuted down, ring-
    buffer remat) instead of autodiff-through-the-schedule — so the
    backward's live activations are bounded by the schedule depth, not
    the micro-batch count, while the result composes with surrounding
    autodiff (embedding below, head/loss above) like any jax function.

    ``stage_fn(stage_params, h[, mb_idx])`` as in ``gpipe``. Returns
    ``fn(stacked_params, x_micro) -> y_micro`` usable under jax.grad."""
    S = num_stages or axis_size(mesh, STAGE_AXIS)
    import inspect
    takes_mb = len(inspect.signature(stage_fn).parameters) >= 3
    fwd_run = gpipe(stage_fn, mesh, S, batch_axis=batch_axis)

    def bwd_local(params_slice, x_all, dy_all):
        p = jax.tree.map(lambda a: a[0], params_slice)
        stage_id = lax.axis_index(STAGE_AXIS)
        M = x_all.shape[0]
        mb_shape = x_all.shape[1:]
        R = 2 * S - 1
        T = M + 2 * (S - 1)
        down = [(i, (i - 1) % S) for i in range(S)]
        up = [(i, (i + 1) % S) for i in range(S)]

        def call(pp, hh, m):
            return stage_fn(pp, hh, jnp.clip(m, 0)) if takes_mb \
                else stage_fn(pp, hh)

        def tick(t, carry):
            h_chan, g_chan, buf, dp, dx = carry
            mf = t - stage_id
            f_active = (mf >= 0) & (mf < M)
            feed = lax.dynamic_index_in_dim(x_all, jnp.clip(mf, 0, M - 1),
                                            0, keepdims=False)
            h_in = jnp.where(stage_id == 0, feed, h_chan)
            h_out = jnp.where(f_active, call(p, h_in, mf), h_in)
            buf = jnp.where(
                f_active,
                lax.dynamic_update_index_in_dim(
                    buf, h_in, jnp.mod(jnp.clip(mf, 0), R), 0),
                buf)
            mb_ = t - 2 * (S - 1) + stage_id
            b_active = (mb_ >= 0) & (mb_ < M)
            h_saved = lax.dynamic_index_in_dim(
                buf, jnp.mod(jnp.clip(mb_, 0), R), 0, keepdims=False)
            _, vjp = jax.vjp(lambda pp, hh: call(pp, hh, mb_), p, h_saved)
            dy_m = lax.dynamic_index_in_dim(
                dy_all, jnp.clip(mb_, 0, M - 1), 0, keepdims=False)
            g_seed = jnp.where(stage_id == S - 1, dy_m, g_chan)
            dp_m, dh_m = vjp(g_seed.astype(h_saved.dtype))
            dp = jax.tree.map(
                lambda acc, g: acc + jnp.where(b_active, g, 0.0), dp, dp_m)
            # stage 0's input cotangent IS dL/dx for this micro-batch
            dx = jnp.where(
                b_active & (stage_id == 0),
                lax.dynamic_update_index_in_dim(
                    dx, dh_m, jnp.clip(mb_, 0, M - 1), 0),
                dx)
            g_chan = lax.ppermute(
                jnp.where(b_active, dh_m, jnp.zeros_like(dh_m)),
                STAGE_AXIS, down)
            h_chan = lax.ppermute(h_out, STAGE_AXIS, up)
            return h_chan, g_chan, buf, dp, dx

        z = jnp.zeros(mb_shape, x_all.dtype)
        dp0 = jax.tree.map(jnp.zeros_like, p)
        buf0 = jnp.zeros((R,) + mb_shape, x_all.dtype)
        dx0 = jnp.zeros_like(x_all)
        _, _, _, dp, dx = lax.fori_loop(0, T, tick, (z, z, buf0, dp0, dx0))
        # dx is populated only on stage 0; psum makes it uniform so the
        # replicated out-spec is valid
        dx = lax.psum(dx, STAGE_AXIS)
        if batch_axis is not None:
            # params replicate over the data axis, so each data shard's
            # dp is a PARTIAL sum over its mb slice — reduce explicitly
            # (autodiff-of-shard_map would have inserted this psum; a
            # custom_vjp must do it by hand)
            dp = jax.tree.map(lambda g: lax.psum(g, batch_axis), dp)
        return jax.tree.map(lambda a: a[None], dp), dx

    @jax.custom_vjp
    def trunk(stacked_params, x_micro):
        return fwd_run(stacked_params, x_micro)

    def trunk_fwd(stacked_params, x_micro):
        return fwd_run(stacked_params, x_micro), (stacked_params, x_micro)

    def trunk_bwd(res, dy):
        stacked_params, x_micro = res
        pspecs = jax.tree.map(lambda _: P(STAGE_AXIS), stacked_params)
        # activations replicate over stage; the mb dim may shard over a
        # data axis (PP x DP) — the schedule is elementwise across mb
        aspec = P(*([None, batch_axis] + [None] * (x_micro.ndim - 2))) \
            if batch_axis else P()
        f = shard_map(bwd_local, mesh=mesh,
                      in_specs=(pspecs, aspec, aspec),
                      out_specs=(pspecs, aspec), check_vma=False)
        return f(stacked_params, x_micro, dy)

    trunk.defvjp(trunk_fwd, trunk_bwd)
    return trunk


def one_f_one_b(stage_fn: Callable, loss_fn: Callable, mesh: Mesh,
                num_stages: Optional[int] = None):
    """1F1B pipeline TRAINING step (SURVEY P5; VERDICT r4 #9):
    ``run(stacked_params, x_micro, tgt_micro) -> (loss, grads)`` with the
    backward of each micro-batch starting the moment its forward leaves
    the last stage — per-stage live activations bounded by the schedule
    depth, not the micro-batch count.

    Implemented as ``value_and_grad`` over :func:`pipeline_trunk_1f1b`
    (ONE copy of the 1F1B tick machinery lives there): the trunk's
    custom_vjp routes reverse-mode through the explicit 1F1B wavefront,
    and the per-micro-batch ``loss_fn(h, tgt) -> scalar`` (summed over
    micro-batches) differentiates on top like any jax function."""
    trunk = pipeline_trunk_1f1b(stage_fn, mesh, num_stages)

    def run(stacked_params, x_micro, tgt_micro):
        def total_loss(sp):
            y = trunk(sp, x_micro)
            return jnp.sum(jax.vmap(loss_fn)(y, tgt_micro))
        return jax.value_and_grad(total_loss)(stacked_params)

    return run
