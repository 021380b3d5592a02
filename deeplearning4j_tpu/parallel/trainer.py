"""ShardedTrainer — the distributed training engine.

Replaces the reference's three data-parallel mechanisms (SURVEY P1–P3):
``ParallelWrapper`` per-device trainer threads, Spark parameter averaging,
and the Aeron gradient-sharing stack (EncodedGradientsAccumulator +
threshold codec + UDP mesh). TPU-native design: ONE jitted train step whose
inputs carry shardings — batch sharded over ``data``, params sharded over
``model`` (TP) or replicated — and XLA GSPMD emits the gradient allreduce
over ICI. Synchronous dense allreduce replaces async sparse updates by
default (convergence-parity note in BASELINE.md); the reference's
threshold-codec accumulator survives as the OPT-IN compressed exchange
(``grad_compression`` / ``DL4J_TPU_GRAD_COMPRESS`` → error-feedback
threshold collectives, parallel/compression.py).
"""
from __future__ import annotations

import functools
import logging
import time
from typing import Optional

import jax
import jax.numpy as jnp
import numpy as np
import optax

from deeplearning4j_tpu import async_runtime as _async
from deeplearning4j_tpu.ndarray.ndarray import _unwrap
from deeplearning4j_tpu.observability import compile_watch as _cw
from deeplearning4j_tpu.observability import cost_model as _cost
from deeplearning4j_tpu.observability import device_memory as _devmem
from deeplearning4j_tpu.observability import global_registry
from deeplearning4j_tpu.observability import numerics as _num
from deeplearning4j_tpu.observability import span as _span
from deeplearning4j_tpu.observability import train_metrics as _tm
from deeplearning4j_tpu.nn._step_tail import finish_train_step
from deeplearning4j_tpu.observability.flight_recorder import (
    global_flight_recorder as _flight)
from deeplearning4j_tpu.parallel import compression as _comp
from deeplearning4j_tpu.parallel import mesh as _mesh
from deeplearning4j_tpu.parallel.mesh import MeshSpec, DATA_AXIS
from deeplearning4j_tpu.resilience import faults as _faults
from deeplearning4j_tpu.parallel.sharding import replicate_tree, tp_shardings
from jax import lax
from jax.sharding import NamedSharding, PartitionSpec as P

from jax import shard_map

log = logging.getLogger("deeplearning4j_tpu")


class ShardedTrainer:
    """Train a MultiLayerNetwork/ComputationGraph over a device mesh.

    The wrapped net keeps its API; this class re-homes its params/opt-state
    onto the mesh and swaps the train step for a sharded one.
    """

    def __init__(self, net, mesh_spec: Optional[MeshSpec] = None, devices=None,
                 tensor_parallel: bool = False,
                 shard_optimizer_state: bool = False,
                 preemption_handler=None, checkpoint_dir: Optional[str] = None,
                 grad_compression=None):
        self.net = net
        # the declarative spec is kept so elastic shrink/re-expand can
        # rebuild the mesh over a different device set (resize_mesh)
        self._mesh_spec = mesh_spec or MeshSpec.data_parallel()
        self.mesh = self._mesh_spec.build(devices)
        self.tensor_parallel = tensor_parallel
        # compressed gradient exchange (Strom 2015 error-feedback threshold
        # collectives — the EncodedGradientsAccumulator analog): a
        # ThresholdAlgorithm / spec string / True enables it; None defers
        # to the DL4J_TPU_GRAD_COMPRESS env knob; the env knob "0" is the
        # kill switch (dense path, byte-identical) either way. Resolved at
        # placement time so the knob is read live.
        self.grad_compression = grad_compression
        self._compression = None      # resolved ThresholdAlgorithm
        self._comp_layout = None      # bucketed-flattening plan
        self._comp_step = None        # cached jitted compressed step
        self._comp_obs = None         # (sparsity gauge, residual-norm hist)
        self._pending_comp_stats = [] # device scalars awaiting a sync point
        self._comp_fallback_warned = False
        # preemption safety (SURVEY §5.3): when a handler is given (or one is
        # installed process-wide), fit() checks the latch at every batch
        # boundary, writes a final checkpoint into ``checkpoint_dir`` and
        # raises TrainingPreempted — the pod-reclaim path, first-class
        self.preemption_handler = preemption_handler
        self.checkpoint_dir = checkpoint_dir
        # ZeRO-style cross-replica weight-update sharding (Xu et al. 2020,
        # arXiv:2004.13336 — the XLA weight-update-sharding recipe): optimizer
        # moments shard over the data axis while params stay replicated; XLA
        # converts the allreduce into reduce-scatter + sharded update +
        # all-gather, cutting per-chip optimizer memory by the DP degree
        self.shard_optimizer_state = shard_optimizer_state
        self._placed = False
        self._grad_bytes = 0     # per-step gradient allreduce payload
        self._collective_bytes = {}    # per-op bytes/step expectation
        self._collective_counters = {}
        self._obs = None         # lazily-bound collective instruments

    # ------------------------------------------------------------------ setup
    def _place(self):
        net = self.net
        if not net._initialized:
            net.init()
        pshard = tp_shardings(net._params, self.mesh, enable=self.tensor_parallel)
        net._params = jax.device_put(net._params, pshard)
        if net._states:
            net._states = jax.device_put(net._states, replicate_tree(net._states, self.mesh))
        if net._opt_state is None or net._iteration == 0:
            # fresh net: init under jit so Adam moments inherit param shardings
            net._opt_state = jax.jit(net._opt.init)(net._params)
        else:
            # warm start: PRESERVE accumulated moments/step count; the
            # name-keyed TP rule applies to the param-shaped state leaves too
            oshard = tp_shardings(net._opt_state, self.mesh, enable=self.tensor_parallel)
            net._opt_state = jax.device_put(net._opt_state, oshard)
        if self.shard_optimizer_state:
            net._opt_state = jax.device_put(
                net._opt_state, self._opt_state_shardings(net._opt_state))
        # observability: the synchronous data-parallel step allreduces every
        # gradient leaf once — the payload is exactly the param-tree bytes
        # (GSPMD fuses the collective into the step, so duration is the
        # sharded step's wall time; bytes are exact)
        n_data = _mesh.axis_size(self.mesh, DATA_AXIS) \
            if DATA_AXIS in self.mesh.axis_names else 1
        param_bytes = sum(
            leaf.size * leaf.dtype.itemsize
            for leaf in jax.tree.leaves(net._params)
            if hasattr(leaf, "size"))
        self._grad_bytes = param_bytes if n_data > 1 else 0
        # compressed gradient exchange: resolve the knob/arg LIVE at every
        # placement (the kill switch must also disarm an already-built
        # trainer on re-place) and seed/restore the error-feedback state
        self._resolve_compression(n_data)
        # per-collective traffic expectation (analytic): the plain
        # synchronous step allreduces the whole gradient tree once; under
        # ZeRO-style weight-update sharding XLA rewrites that into a
        # reduce-scatter + all-gather pair, each moving (n-1)/n of the
        # param bytes over the wire (ring schedule); the compressed path
        # moves the int8 sign payload + per-bucket scales instead. Counted
        # per step into dl4j_collective_bytes_total{collective} and served
        # next to the measured cost-model numbers on /debug/perf.
        self._fallback_bytes = {}
        if n_data > 1 and self._compression is not None:
            self._collective_bytes = {
                "compressed_allreduce":
                    _comp.payload_bytes(self._comp_layout, n_data)}
            # an indivisible batch falls back to the dense exchange for
            # that batch — its traffic books as a plain allreduce, never
            # as compressed wire bytes
            self._fallback_bytes = {"allreduce": param_bytes}
        elif n_data > 1 and self.shard_optimizer_state:
            wire = param_bytes * (n_data - 1) // n_data
            self._collective_bytes = {"reduce_scatter": wire,
                                      "all_gather": wire}
        elif n_data > 1:
            self._collective_bytes = {"allreduce": param_bytes}
        else:
            self._collective_bytes = {}
        reg = global_registry()
        bytes_c = reg.counter(
            "dl4j_collective_bytes_total",
            "bytes moved per collective op (gradient allreduce payload = "
            "param bytes x steps; ZeRO mode splits into reduce-scatter + "
            "all-gather wire bytes)",
            label_names=("collective",))
        expected_g = reg.gauge(
            "dl4j_collective_expected_bytes",
            "analytic per-step traffic expectation of each collective the "
            "sharded train step fuses (compare against the cost model's "
            "bytes accessed on /debug/perf)",
            label_names=("collective",))
        self._collective_counters = {}
        for op in {**self._fallback_bytes, **self._collective_bytes}:
            self._collective_counters[op] = bytes_c.labels(collective=op)
        for op, nbytes in self._collective_bytes.items():
            expected_g.labels(collective=op).set(nbytes)
        self._obs = (
            reg.histogram("dl4j_collective_step_seconds",
                          "wall time of the sharded train step (compute + "
                          "fused gradient allreduce)",
                          label_names=("collective",)).labels(
                              collective="allreduce"),
            reg.gauge("dl4j_mesh_devices", "devices in the active mesh",
                      label_names=("axis",)))
        for axis in self.mesh.axis_names:
            self._obs[1].labels(axis=str(axis)).set(
                _mesh.axis_size(self.mesh, axis))
        # cost observatory: steps through this trainer account under their
        # own entry (global-program FLOPs over a mesh-sized peak). The
        # placement recompile often hits the jaxpr cache WITHOUT a retrace,
        # so the entry is invalidated explicitly — the next step
        # re-lowers at the sharded signature
        _cost.global_cost_model().set_scale(
            "ShardedTrainer.step", self.mesh.size)
        _cost.global_cost_model().note_collectives(
            "ShardedTrainer.step", self._collective_bytes)
        if self._compression is not None:
            payload = _comp.payload_bytes(self._comp_layout, n_data)
            dense = _comp.dense_bytes(self._comp_layout)
            ratio = dense / max(1, payload)
            reg.gauge(
                "dl4j_grad_compression_ratio",
                "dense gradient bytes / encoded wire payload bytes of the "
                "compressed exchange (sign-mask int8 + per-bucket scale)"
            ).set(ratio)
            self._comp_obs = (
                reg.gauge(
                    "dl4j_grad_compression_sparsity_ratio",
                    "fraction of gradient elements whose magnitude cleared "
                    "the threshold in the last synced compressed step "
                    "(the reference's 'sparsity ratio')"),
                reg.histogram(
                    "dl4j_grad_residual_norm",
                    "global L2 norm of the error-feedback residual after "
                    "each compressed step (mass deferred to later steps)"))
            _cost.global_cost_model().note_compression(
                "ShardedTrainer.step", {
                    **self._compression.describe(),
                    "buckets": list(zip(self._comp_layout.bucket_dtypes,
                                        self._comp_layout.bucket_sizes)),
                    "wire_payload_bytes": payload,
                    "dense_bytes": dense,
                    "compression_ratio": ratio,
                })
        _cost.global_cost_model().invalidate("ShardedTrainer.step")
        # re-homing params onto the mesh changes the step's sharding
        # signature — the wrapped net's _train_step retraces once, and
        # the compile watch attributes that compile to this placement
        _cw.note_cause("sharded_placement",
                       mesh_axes=",".join(str(a)
                                          for a in self.mesh.axis_names))
        _devmem.sample()        # post-placement HBM baseline
        self._placed = True

    def resize_mesh(self, devices=None):
        """Rebuild the mesh over a different device set (elastic shrink
        after host/device loss, re-expand when capacity returns). The
        next batch re-places params/opt-state/compression state onto the
        new mesh (``_place`` handles warm re-placement and replica-count
        reshaping of replica-keyed state); cached jitted steps keyed on
        the old mesh are dropped."""
        old = self.mesh.size
        self.mesh = self._mesh_spec.build(devices)
        self._placed = False
        self._comp_step = None
        self._comp_fallback_warned = False
        log.warning("mesh resized: %d -> %d devices (re-placement on the "
                    "next batch)", old, self.mesh.size)
        return self

    def _opt_state_shardings(self, opt_state):
        """Data-axis sharding for param-shaped optimizer moments: leaves
        whose largest dim divides the DP degree shard on that dim, scalars/
        indivisible leaves replicate."""
        n_data = _mesh.axis_size(self.mesh, DATA_AXIS)

        def spec_for(leaf):
            shape = getattr(leaf, "shape", ())
            # compose with TP: a leaf already model-sharded keeps its layout
            # (re-sharding it over data would force per-step reshards and
            # fight the Megatron placement)
            sharding = getattr(leaf, "sharding", None)
            if sharding is not None and not sharding.is_fully_replicated:
                return sharding
            if n_data > 1 and shape:
                dim = int(np.argmax(shape))
                if shape[dim] % n_data == 0 and shape[dim] >= n_data:
                    parts = [None] * len(shape)
                    parts[dim] = DATA_AXIS
                    return NamedSharding(self.mesh, P(*parts))
            return NamedSharding(self.mesh, P())

        return jax.tree.map(spec_for, opt_state)

    # ------------------------------------------------- compressed exchange
    def _resolve_compression(self, n_data: int):
        """Resolve the builder arg + env knob into an active algorithm and
        seed (or restore) the error-feedback state. Runs at every
        placement so the kill switch works live."""
        self._compression = None
        self._comp_step = None
        algo = _comp.resolve_compression(self.grad_compression)
        reason = (None if algo is None
                  else self._compression_unsupported_reason())
        if algo is None or reason is not None:
            if reason is not None:
                log.warning("gradient compression requested but %s; using "
                            "the dense exchange", reason)
            # drop any carried error-feedback state: a dense run must not
            # keep checkpointing (or pin in device memory) a residual that
            # goes stale with every dense step — re-enabling compression
            # later re-seeds at zero instead of resuming stale mass
            if getattr(self.net, "_grad_compression_state", None) is not None:
                log.warning("dropping carried gradient-compression state "
                            "(dense exchange in force; re-enabling later "
                            "re-seeds the residual at zero)")
                self.net._grad_compression_state = None
            return
        self._compression = algo
        self._comp_layout = _comp.build_layout(self.net._params)
        self._init_comp_state(n_data)

    def _compression_unsupported_reason(self) -> Optional[str]:
        from deeplearning4j_tpu.nn.conf.configuration import BackpropType
        if DATA_AXIS not in self.mesh.axis_names:
            return "the mesh has no data axis to exchange over"
        for axis in self.mesh.axis_names:
            if axis != DATA_AXIS and _mesh.axis_size(self.mesh, axis) > 1:
                return (f"the mesh shards over {axis!r} too (threshold "
                        "collectives are data-parallel only)")
        if jax.process_count() > 1:
            return "multi-host meshes are not supported yet"
        if getattr(self.net.conf, "backprop_type", None) == \
                BackpropType.TruncatedBPTT:
            return ("TBPTT carries cross jitted-step boundaries (the "
                    "compressed step has no carry slot)")
        return None

    def _init_comp_state(self, n_data: int):
        """Attach the residual/threshold state to the NET (the checkpoint
        unit — ModelSerializer rides it as ``gradCompression.npz``, so
        ResilientTrainer restore-resume replays byte-equal), placed on the
        mesh: residual buckets shard over ``data`` (one residual per
        replica), thresholds replicate."""
        state = getattr(self.net, "_grad_compression_state", None)
        if not _comp.state_matches(state, self._comp_layout, n_data):
            if state is not None:
                # topology change (elastic shrink/expand, or a checkpoint
                # from a different mesh): replica-keyed residuals cannot
                # survive byte-exactly — re-bucket them mean-preservingly
                # (or re-seed at zero when the counts don't divide) but
                # KEEP the layout-keyed threshold state either way
                reshaped, mode = _comp.reshape_state(
                    state, self._comp_layout, n_data)
                if reshaped is not None:
                    old_n = int(np.shape(state["residual"][0])[0])
                    log.warning(
                        "gradient-compression state was written on a "
                        "%d-replica mesh, restoring onto %d replicas: "
                        "residuals %s (replica-keyed state cannot survive "
                        "a reshape byte-exactly), thresholds kept",
                        old_n, n_data, mode)
                    state = reshaped
                else:
                    log.warning(
                        "restored gradient-compression state does not "
                        "match the current layout; re-seeding the "
                        "residual at zero")
                    state = _comp.init_state(
                        self._comp_layout, self._compression, n_data)
            else:
                state = _comp.init_state(self._comp_layout,
                                         self._compression, n_data)
        rshard = NamedSharding(self.mesh, P(DATA_AXIS, None))
        rep = NamedSharding(self.mesh, P())
        self.net._grad_compression_state = {
            "residual": [jax.device_put(jnp.asarray(r, jnp.float32), rshard)
                         for r in state["residual"]],
            "threshold": [jax.device_put(jnp.asarray(t, jnp.float32), rep)
                          for t in state["threshold"]],
        }

    def _build_compressed_step(self):
        """The compressed train step: per-replica local gradients under
        shard_map, error-feedback threshold encode (dense int8 sign mask +
        per-bucket scale — static shapes), ONE sign-sum exchange per
        dtype-homogeneous bucket over the ``data`` axis, decode, then the
        replicated optimizer update outside the shard_map (which composes
        with ZeRO optimizer-state sharding: XLA re-shards the update onto
        the data-sharded moments as reduce-scatter + sharded update)."""
        net = self.net
        mesh = self.mesh
        layout = self._comp_layout
        algo = self._compression
        n = _mesh.axis_size(mesh, DATA_AXIS)
        total = layout.total_elements()

        def exchange(params, states, residual, thresholds, x, y, fmask,
                     lmask, rng):
            # per-replica half: runs on each replica's batch shard; params
            # and thresholds arrive replicated, residual arrives as this
            # replica's (1, size) block
            if n > 1:
                # distinct dropout streams per replica (the dense GSPMD
                # path shards one global mask instead; documented
                # divergence — same distribution, different draw)
                rng2 = jax.random.fold_in(rng, lax.axis_index(DATA_AXIS))
            else:
                rng2 = rng
            (loss, (new_states, _)), grads = jax.value_and_grad(
                net._loss_fn, has_aux=True)(
                params, states, x, y, fmask, lmask, rng2, None)
            loss = lax.pmean(loss, DATA_AXIS)
            # running stats (batchnorm etc.) average like the dense
            # global-batch computation would
            new_states = jax.tree.map(
                lambda a: lax.pmean(a, DATA_AXIS)
                if jnp.issubdtype(a.dtype, jnp.inexact) else a, new_states)
            gb = _comp.flatten_buckets(grads, layout)
            decoded, new_res, new_thr = [], [], []
            frac_weighted = jnp.float32(0.0)
            res_sq = jnp.float32(0.0)
            for i, g in enumerate(gb):
                acc = g + residual[i].reshape(-1)     # error feedback
                t = thresholds[i]
                # the shared encode/scale/psum/decode pipeline (one
                # spelling — the allreduce A/B bench runs the same fn)
                dec, sent, _, frac = _comp.exchange_bucket(
                    acc, t, DATA_AXIS, n)
                decoded.append(dec)
                new_res.append((acc - sent)[None, :])
                new_thr.append(algo.update(t, frac))
                frac_weighted = frac_weighted + frac * (g.size / total)
                res_sq = res_sq + lax.psum(jnp.sum(jnp.square(acc - sent)),
                                           DATA_AXIS)
            stats = {"encoded_fraction": frac_weighted,
                     "residual_norm": jnp.sqrt(res_sq)}
            return loss, new_states, decoded, new_res, new_thr, stats

        @functools.partial(jax.jit, static_argnums=(10,),
                           donate_argnums=(0, 1, 2, 3, 4))
        def step(params, opt_state, states, residual, thresholds, x, y,
                 fmask, lmask, rng, frozen):
            # trace probe: counts exactly the (re)compiles of the
            # compressed entry point (compile_watch)
            _cw.note_trace("ShardedTrainer._compressed_step",
                           (x, y, fmask, lmask))
            sm = shard_map(
                exchange, mesh=mesh,
                in_specs=(P(), P(), P(DATA_AXIS, None), P(), P(DATA_AXIS),
                          P(DATA_AXIS), P(DATA_AXIS), P(DATA_AXIS), P()),
                out_specs=(P(), P(), P(), P(DATA_AXIS, None), P(), P()),
                check_vma=False)
            loss, new_states, decoded, new_res, new_thr, stats = sm(
                params, states, residual, thresholds, x, y, fmask, lmask,
                rng)
            grads = _comp.unflatten_buckets(decoded, layout)
            # shared freeze/optimizer/numerics tail (nn/_step_tail.py); a
            # skipped (non-finite) step must ALSO keep the old residual /
            # threshold — the poison is inside the accumulator otherwise
            (new_params, new_opt_state,
             (new_states, new_res, new_thr), health) = finish_train_step(
                net._opt, params, opt_state, grads, loss, frozen,
                guarded=((new_states, states), (new_res, residual),
                         (new_thr, thresholds)))
            return (new_params, new_opt_state, new_states, loss, new_res,
                    new_thr, stats, health)

        return step

    def _compressible_batch(self, x) -> bool:
        """The shard_map step needs the batch divisible over the data
        axis; an indivisible (e.g. final partial) batch falls back to the
        dense step for that batch — the residual simply carries over."""
        first = x[0] if isinstance(x, (tuple, list)) else x
        n_data = _mesh.axis_size(self.mesh, DATA_AXIS)
        ok = first is not None and hasattr(first, "shape") and \
            first.shape[0] % n_data == 0
        if not ok and not self._comp_fallback_warned:
            self._comp_fallback_warned = True
            log.warning(
                "batch of %s examples is not divisible by the %d-way data "
                "axis; falling back to the dense exchange for such batches",
                getattr(first, "shape", ("?",))[0], n_data)
        return ok

    def _fit_batch_compressed(self, x, y, fmask, lmask):
        """Compressed-exchange twin of the net's ``_fit_batch`` tail:
        same deferred-score cadence, listener/metrics/flight bookkeeping,
        and cost-observatory feed — with the error-feedback state carried
        through the step and re-attached to the net (so the NEXT
        checkpoint write snapshots residuals consistent with the params)."""
        net = self.net
        from deeplearning4j_tpu.nn.multilayer import MultiLayerNetwork
        if self._comp_step is None:
            self._comp_step = self._build_compressed_step()
        if not isinstance(net, MultiLayerNetwork):
            tup = lambda v: (() if v is None
                             else tuple(v) if isinstance(v, (tuple, list))
                             else (v,))
            x, y, fmask, lmask = tup(x), tup(y), tup(fmask), tup(lmask)
        if _faults.armed():
            # same chaos point as the dense twin: fires BEFORE the jitted
            # step touches its donated buffers (retry-in-place safe; a nan
            # corruption composes with the numerics skip, which on this
            # path also preserves the residual/threshold state)
            _faults.check("train.step")
            if isinstance(x, tuple):
                x = tuple(jnp.asarray(v) for v in
                          _faults.corrupt("train.step", x))
            else:
                x = jnp.asarray(_faults.corrupt("train.step", x))
        batch_n = int((x[0] if isinstance(x, tuple) else x).shape[0])
        net._last_batch_size = batch_n
        # pinned only when a listener collects activation histograms (same
        # contract as the dense _fit_batch — StatsListener reads it)
        if any(getattr(l, "collect_activations", False)
               for l in net._listeners):
            net._last_input = x[0] if isinstance(x, tuple) else x
        comp = net._grad_compression_state
        defer_mode = _async.async_enabled() and not net._listeners
        score_every = (net.score_every if net.score_every is not None
                       else _async.score_sync_every())
        sync_now = (not defer_mode
                    or (net._iteration + 1) % max(1, score_every) == 0)
        t0 = time.perf_counter()
        with _span("train_step", model=type(net).__name__,
                   iteration=net._iteration, batch=batch_n,
                   compressed=True):
            net._key, rng = jax.random.split(net._key)
            (net._params, net._opt_state, net._states, loss, new_res,
             new_thr, stats, health) = self._comp_step(
                net._params, net._opt_state, net._states, comp["residual"],
                comp["threshold"], x, y, fmask, lmask, rng,
                frozenset(net._frozen))
            net._grad_compression_state = {"residual": new_res,
                                           "threshold": new_thr}
            if health is not None:
                net._pending_health.append(_num.stamp_step(health))
            self._pending_comp_stats.append(stats)
            if sync_now:
                net._pending_score = None
                net._score = float(loss)
                net._drain_numerics()
                self._publish_comp_stats()
            else:
                net._pending_score = loss
                if len(net._pending_health) >= 64:
                    old = net._pending_health[:32]
                    net._pending_health = net._pending_health[32:]
                    _num.publish(net, old)
                if len(self._pending_comp_stats) >= 64:
                    # same older-half drain as the numerics backlog: the
                    # newest entries may still be in flight on device
                    old, self._pending_comp_stats = (
                        self._pending_comp_stats[:32],
                        self._pending_comp_stats[32:])
                    self._publish_comp_stats(old)
        t1 = time.perf_counter()
        _cost.on_step(
            "ShardedTrainer._compressed_step", "ShardedTrainer.step",
            t1 - t0,
            lambda: self._comp_step.lower(
                net._params, net._opt_state, net._states,
                net._grad_compression_state["residual"],
                net._grad_compression_state["threshold"],
                x, y, fmask, lmask, rng, frozenset(net._frozen)))
        net._iteration += 1
        with _span("listeners", model=type(net).__name__):
            for lst in net._listeners:
                lst.iteration_done(net, net._iteration, net._epoch,
                                   net._score)
        _tm.for_model(net).record_step(
            batch_n, net._score if sync_now else float("nan"),
            t1 - t0, time.perf_counter() - t1, None, pipelined=defer_mode)
        _flight().progress("train_step")

    def _publish_comp_stats(self, pend=None):
        """Materialize deferred compression scalars (sparsity fraction,
        residual norm) — called only at the sync points the deferred-score
        cadence already pays for."""
        if pend is None:
            pend, self._pending_comp_stats = self._pending_comp_stats, []
        if not pend or self._comp_obs is None:
            return
        spars_g, res_h = self._comp_obs
        last = None
        for s in pend:
            last = float(s["encoded_fraction"])
            res_h.observe(float(s["residual_norm"]))
        spars_g.set(last)
        _cost.global_cost_model().note_compression(
            "ShardedTrainer.step", {"encoded_fraction_last": last})

    def _shard_batch(self, x):
        if x is None:
            return None
        if isinstance(x, (tuple, list)):
            return type(x)(self._shard_batch(e) for e in x)
        if jax.process_count() > 1 and DATA_AXIS in self.mesh.axis_names:
            # multi-host (DCN) path: each process feeds its LOCAL partition
            # (ref: SharedTrainingWorker consumes worker-local RDD
            # partitions); assemble the global sharded batch across hosts
            x = np.asarray(_unwrap(x))
            n_shards = _mesh.axis_size(self.mesh, DATA_AXIS)
            per_proc = max(1, n_shards // jax.process_count())
            if x.shape[0] % per_proc != 0:
                # replicating would need identical values on every process,
                # which a process-local partition is not — fail loudly
                # instead of training on silently inconsistent data
                raise ValueError(
                    f"multi-host batch: local partition of {x.shape[0]} "
                    f"examples is not divisible by the {per_proc} data "
                    f"shards this process owns; feed equal-sized divisible "
                    f"partitions per process")
            return jax.make_array_from_process_local_data(
                NamedSharding(self.mesh, P(DATA_AXIS)), x)
        x = jnp.asarray(_unwrap(x))
        n_data = _mesh.axis_size(self.mesh, DATA_AXIS)
        # an indivisible (e.g. final partial) batch replicates instead of
        # erroring — the reference's ParallelWrapper accepts any batch size
        spec = (P(DATA_AXIS) if DATA_AXIS in self.mesh.axis_names
                and x.shape[0] % n_data == 0 else P())
        return jax.device_put(x, NamedSharding(self.mesh, spec))

    # ------------------------------------------------------------------ train
    def _active_preemption_handler(self):
        if self.preemption_handler is not None:
            return self.preemption_handler
        from deeplearning4j_tpu.utils.preemption import PreemptionHandler
        return PreemptionHandler._installed

    def _check_preemption(self):
        """Batch-boundary preemption latch check: checkpoint + unwind.
        Runs between jitted steps so no donated buffer is mid-flight."""
        handler = self._active_preemption_handler()
        if handler is None or not handler.preempted:
            return
        from deeplearning4j_tpu.utils.preemption import (
            PreemptionSafeListener, TrainingPreempted)
        path = None
        if self.checkpoint_dir is not None:
            import os
            # the filename contract of PreemptionSafeListener so
            # resume_or_new discovers trainer-written checkpoints; every
            # rank reports the same path (shared storage), rank 0 writes it
            path = os.path.join(
                self.checkpoint_dir,
                PreemptionSafeListener.FINAL_NAME.format(
                    model=type(self.net).__name__))
            if jax.process_index() == 0:
                from deeplearning4j_tpu.utils.serialization import (
                    save_model_atomic)
                os.makedirs(self.checkpoint_dir, exist_ok=True)
                # atomic: a hard kill after the grace window must never
                # leave a torn zip for resume_or_new to trust
                save_model_atomic(self.net, path)
        # no cross-rank barrier (a single-rank latch would deadlock one);
        # non-zero ranks keep the REAL path but flag it possibly in flight
        raise TrainingPreempted(path or "<no checkpoint_dir configured>",
                                self.net._iteration,
                                checkpoint_ready=(path is not None
                                                  and jax.process_index() == 0))

    def fit(self, data, labels=None, epochs: int = 1):
        """Same surface as the wrapped net's fit; batches are sharded over the
        ``data`` axis before entering the jitted step. Runs under a root
        ``fit`` span (steps + the mesh-placement prefetch thread share one
        trace) and armed on the flight recorder — a wedged collective
        shows up as a postmortem bundle, not a silent hang."""
        with _flight().arm("fit:ShardedTrainer"), \
                _span("fit", model=type(self.net).__name__, sharded=True,
                      epochs=epochs):
            return self._fit_impl(data, labels, epochs)

    def _fit_impl(self, data, labels=None, epochs: int = 1):
        if not self._placed:
            self._place()
        net = self.net
        if labels is not None:
            self._fit_batch(data, labels)
            self._check_preemption()
            return self
        if hasattr(data, "features"):
            self._fit_batch(data.features, data.labels,
                            self._ds_mask(data, "features"),
                            self._ds_mask(data, "labels"))
            self._check_preemption()
            return self
        # device prefetch with the trainer's own placement: batch k+1 is
        # sharded onto the mesh on a background thread while step k
        # computes (skipped multi-host — the global-array assembly there
        # must happen on the thread that owns the per-process partition)
        we_wrapped = False
        if jax.process_count() == 1:
            from deeplearning4j_tpu.data.iterators import (
                DevicePrefetchIterator, _place_dataset)
            wrapped = DevicePrefetchIterator.wrap(
                data, placement=lambda ds: _place_dataset(
                    ds, self._shard_batch))
            we_wrapped, data = wrapped is not data, wrapped
        try:
            for _ in range(epochs):
                for lst in net._listeners:
                    lst.on_epoch_start(net, net._epoch)
                if hasattr(data, "reset"):
                    data.reset()
                for ds in data:
                    self._fit_batch(ds.features, ds.labels,
                                    self._ds_mask(ds, "features"),
                                    self._ds_mask(ds, "labels"))
                    self._check_preemption()
                # epoch boundary is a mandatory sync point (deferred loss
                # + the compression sparsity/residual scalars)
                net._sync_score()
                self._publish_comp_stats()
                for lst in net._listeners:
                    lst.on_epoch_end(net, net._epoch)
                net._epoch += 1
        finally:
            if we_wrapped:
                # preemption/interrupt must not strand the prefetch thread
                # with sharded device batches pinned
                data.close()
        return self

    @staticmethod
    def _ds_mask(ds, which: str):
        return (getattr(ds, f"{which}_masks", None) or
                getattr(ds, f"{which}_mask", None))

    def _fit_batch(self, x, y, fmask=None, lmask=None):
        """Shard the batch onto the mesh, then delegate to the net's own
        _fit_batch — it already handles TBPTT chunking, RNN carries, masks,
        listeners, and MLN/CG arity; shardings survive the jnp.asarray
        pass-through and GSPMD does the rest."""
        from deeplearning4j_tpu.nn.multilayer import MultiLayerNetwork

        if _faults.armed():
            # chaos injection point for the collective path: fires before
            # the batch is placed on the mesh, i.e. before the sharded
            # step (and its fused gradient allreduce) owns any buffer
            _faults.check("allreduce")
        x = self._shard_batch(x)
        y = self._shard_batch(y)
        fmask = self._shard_batch(fmask)
        lmask = self._shard_batch(lmask)
        if self._compression is not None and self._compressible_batch(x):
            t0 = time.perf_counter()
            with _span("sharded_step",
                       grad_bytes=self._collective_bytes.get(
                           "compressed_allreduce", 0)):
                self._fit_batch_compressed(x, y, fmask, lmask)
            if self._obs is not None:
                for op, nbytes in self._collective_bytes.items():
                    self._collective_counters[op].inc(nbytes)
                self._obs[0].observe(time.perf_counter() - t0)
            return
        t0 = time.perf_counter()
        # only steps driven THROUGH the trainer book under the sharded
        # entry (mesh-scaled peak); cleared so a later direct net.fit()
        # reverts to the single-device entry
        self.net._cost_fn_name = "ShardedTrainer.step"
        try:
            with _span("sharded_step", grad_bytes=self._grad_bytes):
                if isinstance(self.net, MultiLayerNetwork):
                    self.net._fit_batch(x, y, fmask, lmask)
                else:  # ComputationGraph: tuple-valued inputs/labels/masks
                    tup = lambda v: (() if v is None
                                     else tuple(v) if isinstance(v, (tuple,
                                                                     list))
                                     else (v,))
                    self.net._fit_batch(tup(x), tup(y), tup(fmask),
                                        tup(lmask))
        finally:
            self.net._cost_fn_name = None
        if self._obs is not None:
            # under active compression this tail only runs for the
            # indivisible-batch fallback, whose exchange was DENSE
            books = (self._fallback_bytes if self._compression is not None
                     else self._collective_bytes)
            for op, nbytes in books.items():
                self._collective_counters[op].inc(nbytes)
            self._obs[0].observe(time.perf_counter() - t0)

    # --------------------------------------------------------------- inference
    def output(self, x):
        if not self._placed:
            self._place()
        x = self._shard_batch(x)
        return self.net.output(x)

    def score(self):
        score = self.net._sync_score()
        self._publish_comp_stats()
        return score


class ParallelWrapper:
    """Single-host multi-device data-parallel facade
    (ref: ``org.deeplearning4j.parallelism.ParallelWrapper`` — SURVEY P1).

    The reference clones the model per GPU and averages params every
    ``averagingFrequency`` iterations on separate trainer threads; here the
    same devices form a ``data`` mesh and every step IS the averaged step
    (sync allreduce), so ``averagingFrequency`` is accepted for API parity
    and ignored (documented divergence)."""

    def __init__(self, model, workers: Optional[int] = None,
                 prefetch_buffer: int = 2, averaging_frequency: int = 1,
                 report_score_after_averaging: bool = True):
        n = workers or len(jax.devices())
        self._trainer = ShardedTrainer(model, MeshSpec.data_parallel(n),
                                       devices=jax.devices()[:n])
        self.model = model

    @staticmethod
    def builder(model):
        return _PWBuilder(model)

    def fit(self, data, labels=None, epochs: int = 1):
        return self._trainer.fit(data, labels, epochs)

    def shutdown(self):
        pass


class _PWBuilder:
    """ref: ParallelWrapper.Builder fluent API."""

    def __init__(self, model):
        self._model = model
        self._workers = None
        self._prefetch = 2
        self._avg_freq = 1

    def workers(self, n: int):
        self._workers = n
        return self

    def prefetch_buffer(self, n: int):
        self._prefetch = n
        return self

    prefetchBuffer = prefetch_buffer

    def averaging_frequency(self, n: int):
        self._avg_freq = n
        return self

    averagingFrequency = averaging_frequency

    def build(self) -> ParallelWrapper:
        return ParallelWrapper(self._model, self._workers, self._prefetch, self._avg_freq)
