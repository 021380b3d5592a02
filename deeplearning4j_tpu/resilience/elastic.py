"""Elastic training: async sharded checkpoints, topology-reshaping
restore, and mesh shrink/resume/re-expand capacity tracking.

Production TPU pods run on preemptible capacity: hosts and devices
disappear mid-run and come back minutes later. The reference's answer was
the Spark parameter-server layer's fault-tolerant ``SharedTrainingMaster``
(PAPER.md); the PR-5 resilience layer restores single-file checkpoints
onto the SAME topology only. This module makes topology itself a
restorable dimension:

- :class:`ElasticCheckpointer` — **async sharded saves**: the training
  state (params / opt-state / batchnorm states / grad-compression
  residuals) is snapshotted to host on the caller thread (cheap memcpy;
  device buffers are donation-unsafe to hold) and serialized, digested,
  fsynced, and committed on a background thread — the step loop never
  waits on disk. Each save is a set of ``shard_*.npz`` files plus an
  **atomic versioned manifest** (tmp + fsync + rename, the PR-5
  torn-zip-skip doctrine applied to a shard SET): the manifest records
  step, mesh topology, per-key dtypes, and content digests, so a torn
  or partial shard set is detected and skipped in favor of the newest
  complete one. Async saves go through a coalescing latest-slot queue:
  a slow writer never piles up snapshots in host memory, and the newest
  state is always the one committed.
- **Topology-reshaping restore** — :meth:`ElasticCheckpointer.restore`
  loads a checkpoint written on an N-replica mesh onto an M-replica
  mesh: replicated params/opt-state re-place onto the new mesh at the
  next ``ShardedTrainer._place``, and replica-keyed state (the PR-7
  error-feedback residuals) is re-bucketed mean-preservingly or
  re-seeded at zero with an explicit warning
  (``parallel.compression.reshape_state`` — replica-keyed state cannot
  survive a reshape byte-exactly).
- :class:`ElasticCapacity` — the process-wide view of how many devices
  are currently usable. A ``host_loss`` fault (``resilience/faults.py``)
  or a real capacity event shrinks it; after
  ``DL4J_TPU_ELASTIC_RECOVER_STEPS`` successful steps on the degraded
  mesh (or an explicit :meth:`restore_capacity`) it re-expands, and
  ``ResilientTrainer``'s elastic mode resizes the mesh to follow.

Grounding: sharded weight-update state per replica is the recipe of
"Automatic Cross-Replica Sharding of Weight Update in Data-Parallel
Training" (arXiv 2004.13336); moving a checkpoint between topologies is
the array-redistribution problem of arXiv 2112.01075 — here the
redistribution happens through the host filesystem because the source
topology no longer exists.

Kill switch: ``DL4J_TPU_ELASTIC=0`` (under the ``DL4J_TPU_RESILIENCE``
master) — saves no-op, ``host_loss`` faults are inert, and
``ResilientTrainer`` behaves byte-identically to the pre-elastic tree.
"""
from __future__ import annotations

import io
import json
import logging
import os
import threading
import time
import weakref
import zlib
from typing import Dict, List, Optional, Tuple

import numpy as np

from deeplearning4j_tpu.resilience import faults as _faults
from deeplearning4j_tpu.utils.serialization import fsync_dir as _fsync_dir

log = logging.getLogger("deeplearning4j_tpu")

MANIFEST_PREFIX = "manifest_"
MANIFEST_VERSION = 1
DEFAULT_RECOVER_STEPS = 8


def elastic_enabled() -> bool:
    """THE elastic kill switch (read per call so tests can flip it);
    inert whenever the resilience master is off."""
    return (_faults.resilience_enabled()
            and os.environ.get("DL4J_TPU_ELASTIC", "1") != "0")


def recover_steps() -> int:
    """Successful steps on a degraded mesh before lost capacity is
    assumed back (``DL4J_TPU_ELASTIC_RECOVER_STEPS``; 0 = never
    auto-recover, re-expansion then needs ``restore_capacity()``)."""
    try:
        return max(0, int(os.environ.get("DL4J_TPU_ELASTIC_RECOVER_STEPS",
                                         DEFAULT_RECOVER_STEPS)))
    except (TypeError, ValueError):
        return DEFAULT_RECOVER_STEPS


class HostLostError(RuntimeError):
    """A host/device dropped out mid-step. NON-transient (the buffers on
    the lost devices are gone — an in-place retry cannot succeed) but
    elastic-restorable: ``ResilientTrainer``'s elastic mode shrinks the
    mesh and restores from the sharded manifest instead of dying."""

    def __init__(self, point: str, lost: int = 0):
        self.point = point
        self.lost = int(lost)
        super().__init__(f"host loss at {point!r} ({lost} device(s) gone); "
                         "shrink the mesh and restore from the sharded "
                         "manifest")


# ------------------------------------------------------------------ capacity
class ElasticCapacity:
    """Process-wide device-capacity view. ``mark_host_loss`` shrinks it
    (a ``host_loss`` fault, or a real capacity event); ``note_step``
    counts healthy steps on the degraded mesh and restores capacity
    after :func:`recover_steps` of them — the test-deterministic model
    of "the pod scheduler gave the hosts back"."""

    def __init__(self):
        self._lock = threading.Lock()
        self._lost = 0
        self._good_steps = 0

    def total(self) -> int:
        import jax
        return len(jax.devices())

    def available(self) -> int:
        with self._lock:
            lost = self._lost
        return max(1, self.total() - lost)

    def degraded(self) -> bool:
        with self._lock:
            return self._lost > 0

    def mark_host_loss(self, lost: Optional[int] = None) -> int:
        """Lose ``lost`` devices (default: half of what is left, always
        leaving one). Returns how many were actually lost."""
        total = self.total()
        with self._lock:
            avail = max(1, total - self._lost)
            n = max(1, avail // 2) if lost is None else max(0, int(lost))
            n = min(n, avail - 1)
            if n <= 0:
                return 0
            self._lost += n
            self._good_steps = 0
        _faults.record_event("host_loss", lost=n,
                             available=max(1, total - self._lost))
        _mesh_gauge().set(max(1, total - self._lost))
        log.warning("host loss: %d device(s) gone, %d available", n,
                    max(1, total - self._lost))
        return n

    def note_step(self):
        """One healthy training step completed; on a degraded mesh,
        enough of these == capacity recovered."""
        k = recover_steps()
        with self._lock:
            if self._lost == 0:
                return
            self._good_steps += 1
            if k == 0 or self._good_steps < k:
                return
        self.restore_capacity()

    def restore_capacity(self):
        with self._lock:
            if self._lost == 0:
                return
            self._lost = 0
            self._good_steps = 0
        _faults.record_event("capacity_restored", available=self.total())
        _mesh_gauge().set(self.total())
        log.warning("capacity restored: %d device(s) available",
                    self.total())

    def reset(self):
        with self._lock:
            self._lost = 0
            self._good_steps = 0

    def snapshot(self) -> dict:
        # an observer's view (bundles, /debug/elastic): never the call
        # that initializes a backend — 0 devices in a process with none
        from deeplearning4j_tpu.observability.device_memory import (
            initialized_devices)
        total = len(initialized_devices())
        with self._lock:
            lost, good = self._lost, self._good_steps
        return {"total_devices": total, "lost": lost,
                "available": max(1, total - lost),
                "good_steps_since_loss": good,
                "recover_steps": recover_steps()}


_capacity = ElasticCapacity()


def global_capacity() -> ElasticCapacity:
    return _capacity


# ------------------------------------------------- state <-> flat arrays
def snapshot_net_state(net) -> Tuple[Dict[str, np.ndarray], dict]:
    """Flatten a net's full training state to host arrays (caller
    thread: device buffers are donation-unsafe to hold across the next
    jitted step, so the device→host fetch is the only synchronous part
    of an async save). Returns ``(arrays, meta)``."""
    import jax
    arrays: Dict[str, np.ndarray] = {}
    for lkey in net._params:
        for pname, arr in net._params[lkey].items():
            arrays[f"params/{lkey}/{pname}"] = np.asarray(arr)
    for lkey in net._states:
        for sname, arr in net._states[lkey].items():
            arrays[f"states/{lkey}/{sname}"] = np.asarray(arr)
    if net._opt_state is not None:
        # CONTIGUOUS index over array leaves only — apply_net_state walks
        # the same convention (an enumerate index over ALL leaves would
        # leave gaps whenever the opt-state pytree carries a non-array
        # leaf, and restore would silently fall back to fresh state)
        j = 0
        for leaf in jax.tree.leaves(net._opt_state):
            if hasattr(leaf, "shape"):
                arrays[f"opt/leaf_{j}"] = np.asarray(leaf)
                j += 1
    comp = getattr(net, "_grad_compression_state", None)
    n_replica_state = 0
    if comp is not None:
        for i, r in enumerate(comp["residual"]):
            arrays[f"comp/residual_{i}"] = np.asarray(r)
        for i, t in enumerate(comp["threshold"]):
            arrays[f"comp/threshold_{i}"] = np.asarray(t)
        n_replica_state = int(np.shape(comp["residual"][0])[0]) \
            if comp["residual"] else 0
    meta = {"iteration": int(net._iteration), "epoch": int(net._epoch),
            "model_type": type(net).__name__,
            "replica_keyed_rows": n_replica_state}
    return arrays, meta


def apply_net_state(net, arrays: Dict[str, np.ndarray], meta: dict):
    """Restore a flat state dict into ``net`` (tolerant like
    ModelSerializer: missing/mismatched keys keep the fresh value with a
    warning). Replica-keyed compression state is attached AS SAVED — the
    next ``ShardedTrainer._place`` reshapes it onto the live mesh."""
    import jax
    import jax.numpy as jnp

    from deeplearning4j_tpu.utils import strengthen_dtypes
    if not net._initialized:
        net.init()
    params = {}
    for lkey in net._params:
        params[lkey] = {}
        for pname, fresh in net._params[lkey].items():
            saved = arrays.get(f"params/{lkey}/{pname}")
            if saved is None or tuple(saved.shape) != tuple(fresh.shape):
                log.warning("elastic restore: parameter %s/%s missing or "
                            "mismatched; keeping fresh value", lkey, pname)
                params[lkey][pname] = fresh
            else:
                params[lkey][pname] = jnp.asarray(saved)
    net.set_param_tree(params)
    states = {}
    for lkey in net._states:
        states[lkey] = {}
        for sname, fresh in net._states[lkey].items():
            saved = arrays.get(f"states/{lkey}/{sname}")
            if saved is not None and \
                    tuple(saved.shape) == tuple(fresh.shape):
                states[lkey][sname] = jnp.asarray(saved)
            else:
                states[lkey][sname] = fresh
    net._states = strengthen_dtypes(states)
    if net._opt_state is not None:
        ref_leaves = jax.tree.leaves(net._opt_state)
        n_saved = sum(1 for k in arrays if k.startswith("opt/leaf_"))
        if n_saved == sum(1 for l in ref_leaves if hasattr(l, "shape")):
            leaves, j = [], 0
            ok = True
            for ref in ref_leaves:
                if not hasattr(ref, "shape"):
                    leaves.append(ref)
                    continue
                saved = arrays.get(f"opt/leaf_{j}")
                j += 1
                if saved is None or tuple(saved.shape) != tuple(ref.shape):
                    ok = False
                    break
                leaves.append(jnp.asarray(saved).astype(ref.dtype))
            if ok:
                net._opt_state = jax.tree.unflatten(
                    jax.tree.structure(net._opt_state), leaves)
            else:
                log.warning("elastic restore: optimizer state mismatched; "
                            "keeping fresh state")
        else:
            log.warning("elastic restore: optimizer leaf count changed; "
                        "keeping fresh state")
    elif any(k.startswith("opt/leaf_") for k in arrays):
        # should not happen (init() above always builds an opt state) —
        # but dropping saved Adam moments SILENTLY would be a quality
        # regression nobody notices, so say it loudly
        log.warning("elastic restore: checkpoint carries optimizer state "
                    "but the net has none initialized; moments dropped")
    n_res = sum(1 for k in arrays if k.startswith("comp/residual_"))
    if n_res:
        net._grad_compression_state = {
            "residual": [jnp.asarray(arrays[f"comp/residual_{i}"])
                         for i in range(n_res)],
            "threshold": [jnp.asarray(arrays[f"comp/threshold_{i}"])
                          for i in range(n_res)],
        }
    else:
        net._grad_compression_state = None
    net._iteration = int(meta.get("iteration", 0))
    net._epoch = int(meta.get("epoch", net._epoch))
    # pending device-side fetches reference pre-restore buffers
    net._pending_score = None
    net._pending_health = []
    return net


# ----------------------------------------------------------- sharded store
def _digest(data: bytes) -> str:
    """Content digest for torn-shard-set detection. crc32, not a crypto
    hash: the threat model is a partial write / crashed writer, not an
    adversary, and the digest runs on the background thread for every
    shard of every save — crc32 is ~5× cheaper than sha256 and releases
    the GIL, which matters next to a busy train loop."""
    return "crc32:%08x" % (zlib.crc32(data) & 0xFFFFFFFF)


def _partition_shards(arrays: Dict[str, np.ndarray],
                      n_shards: int) -> List[List[str]]:
    """Deterministic size-balanced partition of keys into shard files
    (greedy smallest-bin; per-host shards at pod scale, per-file here)."""
    n_shards = max(1, int(n_shards))
    bins: List[List[str]] = [[] for _ in range(n_shards)]
    sizes = [0] * n_shards
    for key in sorted(arrays, key=lambda k: (-arrays[k].nbytes, k)):
        i = sizes.index(min(sizes))
        bins[i].append(key)
        sizes[i] += arrays[key].nbytes
    return [sorted(b) for b in bins if b]


# the live checkpointers, for /debug/elastic + elastic.json
_checkpointers: "weakref.WeakSet" = weakref.WeakSet()
_reshape_totals: Dict[str, int] = {}
_totals_lock = threading.Lock()


def count_reshape(direction: str):
    with _totals_lock:
        _reshape_totals[direction] = _reshape_totals.get(direction, 0) + 1
    _reshapes_counter(direction).inc()
    _faults.record_event("mesh_reshape", direction=direction)


class ElasticCheckpointer:
    """Async sharded checkpoint store with an atomic versioned manifest.

    Layout under ``directory``::

        shards_<step>/shard_000.npz ...   (content-digested shard files)
        manifest_<step>.json              (atomic: tmp + fsync + rename)

    A save is only trusted once its manifest names every shard with a
    matching digest — the manifest rename is the commit point, and the
    ``checkpoint.manifest`` fault point fires right before it so chaos
    tests can prove a crash there leaves the previous complete save in
    charge. Rotation keeps the newest ``max_to_keep`` manifests.
    """

    def __init__(self, directory: str, max_to_keep: int = 3,
                 n_shards: Optional[int] = None):
        self.directory = os.path.abspath(directory)
        os.makedirs(self.directory, exist_ok=True)
        self.max_to_keep = max(1, int(max_to_keep))
        self._n_shards = n_shards
        # coalescing latest-slot queue: at most ONE pending async save —
        # a newer snapshot supersedes a not-yet-started older one (the
        # restore path only ever wants the newest manifest, and an
        # unbounded queue behind a slow writer would pile up full model
        # snapshots in host memory)
        self._cv = threading.Condition()
        self._pending: Optional[tuple] = None
        self._busy = False
        self._worker: Optional[threading.Thread] = None
        self._worker_lock = threading.Lock()
        # one writer at a time: a synchronous boundary save and an async
        # cadence save can target the SAME step (same shard dir + tmp
        # names) — unserialized, one rename steals the other's tmp file
        self._write_lock = threading.Lock()
        self.last_error: Optional[BaseException] = None
        self.last_step: Optional[int] = None
        _checkpointers.add(self)

    # ------------------------------------------------------------- saving
    def shard_count(self) -> int:
        if self._n_shards is not None:
            return max(1, int(self._n_shards))
        try:
            return max(1, int(os.environ.get("DL4J_TPU_ELASTIC_SHARDS", 0)))
        except (TypeError, ValueError):
            pass
        return 1

    def save(self, step: int, net, mesh=None, sync: bool = False) -> bool:
        """Checkpoint ``net``'s full training state as of now. The state
        is snapshotted to host immediately; serialization + fsync +
        manifest commit happen on the background thread unless ``sync``.
        No-op under the kill switch. Returns whether a save was queued
        or performed."""
        if not elastic_enabled():
            return False
        arrays, meta = snapshot_net_state(net)
        meta["step"] = int(step)
        meta["mesh"] = self._mesh_meta(mesh)
        if sync:
            self._write(int(step), arrays, meta)
            _saves_counter("sync").inc()
            return True
        self._ensure_worker()
        with self._cv:
            superseded = self._pending is not None
            self._pending = (int(step), arrays, meta)
            self._cv.notify_all()
        _saves_counter("async").inc()
        if superseded:
            # the older queued snapshot never hit disk: its successor
            # carries strictly newer state, so nothing restorable is lost
            _saves_counter("coalesced").inc()
        _pending_gauge().set(1)
        return True

    @staticmethod
    def _mesh_meta(mesh) -> dict:
        if mesh is None:
            return {"n_devices": 1, "n_replicas": 1, "axes": {}}
        from deeplearning4j_tpu.parallel import mesh as _mesh
        from deeplearning4j_tpu.parallel.mesh import DATA_AXIS
        axes = {str(a): _mesh.axis_size(mesh, a) for a in mesh.axis_names}
        return {"n_devices": int(mesh.size),
                "n_replicas": axes.get(DATA_AXIS, 1), "axes": axes}

    def _ensure_worker(self):
        if self._worker is not None and self._worker.is_alive():
            return
        with self._worker_lock:
            if self._worker is not None and self._worker.is_alive():
                return
            self._worker = threading.Thread(
                target=self._drain, daemon=True,
                name="dl4j-elastic-checkpointer")
            self._worker.start()

    def _drain(self):
        try:
            # the writer must never compete with the train step for CPU:
            # SCHED_IDLE (allowed unprivileged on Linux, per-thread) runs
            # it only in the scheduler's slack — on a host whose cores
            # the step saturates, a normal-priority writer would tax
            # every step it overlaps (observed +10% on a 2-core box;
            # idle-priority puts the delta at the noise floor). The save
            # just finishes a little later, which rotation tolerates.
            os.sched_setscheduler(0, os.SCHED_IDLE, os.sched_param(0))
        except (AttributeError, OSError, PermissionError):
            pass                     # non-Linux: keep default priority
        while True:
            with self._cv:
                while self._pending is None:
                    self._cv.wait()
                step, arrays, meta = self._pending
                self._pending = None
                self._busy = True
            try:
                self._write(step, arrays, meta)
            # graftlint: disable=typed-errors — deliberate durability
            # policy: the failure is counted, ringed, and surfaced via
            # last_error; fit()'s finally re-saves synchronously
            except BaseException as e:   # an async save failing must not
                self.last_error = e      # kill training — count + warn
                _save_failures_counter().inc()
                _faults.record_event("elastic_save_failed", step=step,
                                     error=type(e).__name__,
                                     detail=str(e)[:200])
                log.warning("async elastic save of step %d failed (%s: "
                            "%s)", step, type(e).__name__, e)
            finally:
                with self._cv:
                    self._busy = False
                    self._cv.notify_all()
                _pending_gauge().set(
                    1 if self._pending is not None else 0)

    def _write(self, step: int, arrays: Dict[str, np.ndarray], meta: dict):
        with self._write_lock:
            self._write_locked(step, arrays, meta)

    def _write_locked(self, step: int, arrays: Dict[str, np.ndarray],
                      meta: dict):
        t0 = time.perf_counter()
        shard_dir = os.path.join(self.directory, f"shards_{step}")
        os.makedirs(shard_dir, exist_ok=True)
        shards = []
        for i, keys in enumerate(_partition_shards(arrays,
                                                   self.shard_count())):
            buf = io.BytesIO()
            np.savez(buf, **{k: arrays[k] for k in keys})
            data = buf.getvalue()
            fname = f"shard_{i:03d}.npz"
            path = os.path.join(shard_dir, fname)
            tmp = path + ".tmp"
            with open(tmp, "wb") as f:
                f.write(data)
                f.flush()
                os.fsync(f.fileno())
            os.replace(tmp, path)
            shards.append({
                "file": f"shards_{step}/{fname}",
                "bytes": len(data),
                "digest": _digest(data),
                "keys": keys,
                "dtypes": {k: str(arrays[k].dtype) for k in keys},
            })
        _fsync_dir(shard_dir)
        manifest = {
            "format_version": MANIFEST_VERSION,
            "step": int(step),
            "written_unix": time.time(),
            "shards": shards,
            **meta,
        }
        mpath = os.path.join(self.directory, f"{MANIFEST_PREFIX}{step}.json")
        tmp = mpath + ".tmp"
        with open(tmp, "w") as f:
            json.dump(manifest, f, indent=1)
            f.flush()
            os.fsync(f.fileno())
        # the commit point: everything the manifest names is already
        # durable (shard fsync + dir fsync above), so a crash fired HERE
        # leaves the previous complete manifest in charge and never a
        # torn one — the checkpoint.manifest chaos point proves it
        _faults.check("checkpoint.manifest")
        os.replace(tmp, mpath)
        _fsync_dir(self.directory)
        self.last_step = int(step)
        _save_seconds_hist().observe(time.perf_counter() - t0)
        _faults.record_event("elastic_save", step=step,
                             shards=len(shards),
                             bytes=sum(s["bytes"] for s in shards))
        self._rotate()

    def _rotate(self):
        import shutil
        steps = self.all_steps()
        for old in steps[:-self.max_to_keep]:
            try:
                os.remove(os.path.join(self.directory,
                                       f"{MANIFEST_PREFIX}{old}.json"))
            except OSError:
                pass
            shutil.rmtree(os.path.join(self.directory, f"shards_{old}"),
                          ignore_errors=True)
        # sweep ORPHANED shard dirs too: a save that died between the
        # shard writes and the manifest commit (checkpoint.manifest
        # fault, crash, full disk) left a manifest-less full model copy
        # that step-keyed rotation would otherwise never visit
        kept = set(self.all_steps())
        try:
            entries = os.listdir(self.directory)
        except OSError:
            return
        for name in entries:
            if not name.startswith("shards_"):
                continue
            try:
                step = int(name[len("shards_"):])
            except ValueError:
                continue
            if step not in kept:
                shutil.rmtree(os.path.join(self.directory, name),
                              ignore_errors=True)
        for name in entries:       # stale tmp manifests from dead writers
            if name.startswith(MANIFEST_PREFIX) and name.endswith(".tmp"):
                try:
                    os.remove(os.path.join(self.directory, name))
                except OSError:
                    pass

    def wait(self):
        """Block until the newest queued async save is committed (older
        queued snapshots may have been coalesced away — the newest one
        is always written)."""
        with self._cv:
            while self._pending is not None or self._busy:
                self._cv.wait()

    # ------------------------------------------------------------ restore
    def all_steps(self) -> List[int]:
        out = []
        try:
            for name in os.listdir(self.directory):
                if name.startswith(MANIFEST_PREFIX) and \
                        name.endswith(".json"):
                    try:
                        out.append(int(name[len(MANIFEST_PREFIX):-5]))
                    except ValueError:
                        pass
        except OSError:
            pass
        return sorted(out)

    def _parse_manifest(self, step: int) -> Optional[dict]:
        mpath = os.path.join(self.directory,
                             f"{MANIFEST_PREFIX}{step}.json")
        try:
            with open(mpath) as f:
                return json.load(f)
        except (OSError, ValueError) as e:
            log.warning("skipping unreadable elastic manifest %s (%r)",
                        mpath, e)
            return None

    def _verify(self, manifest: dict,
                arrays: Optional[Dict[str, np.ndarray]] = None) -> bool:
        """A manifest is only trusted when every shard it names exists
        with a matching content digest — the shard-set analog of the
        PR-5 torn-zip skip. With ``arrays`` given, the verified bytes
        are also DECODED into it, so verification and restore share one
        read of each shard."""
        for sh in manifest.get("shards", []):
            path = os.path.join(self.directory, sh["file"])
            try:
                with open(path, "rb") as f:
                    data = f.read()
            except OSError:
                return False
            if len(data) != sh["bytes"] or _digest(data) != sh["digest"]:
                return False
            if arrays is not None:
                with np.load(io.BytesIO(data)) as z:
                    for k in z.files:
                        arrays[k] = z[k]
        return True

    def _complete(self, decode: bool):
        """Yield ``(manifest, arrays_or_None)`` for verified-complete
        saves, NEWEST step first, skipping torn/partial sets with a
        warning — THE one manifest-trust policy (the restore path and
        the inspection surface must never disagree about which save is
        in charge). With ``decode`` the verified bytes are also loaded,
        sharing one read per shard."""
        for step in reversed(self.all_steps()):
            manifest = self._parse_manifest(step)
            if manifest is None:
                continue
            arrays: Optional[Dict[str, np.ndarray]] = {} if decode else None
            if not self._verify(manifest, arrays):
                log.warning("skipping torn/partial elastic shard set for "
                            "step %s under %s", step, self.directory)
                continue
            yield manifest, arrays

    def complete_manifests(self) -> List[dict]:
        """Parsed manifests with a verified-complete shard set, NEWEST
        step first (inspection surface — the restore path stops at the
        first complete one instead of verifying the whole window)."""
        return [m for m, _ in self._complete(decode=False)]

    def restore(self, net, min_iteration: int = 0,
                target_replicas: Optional[int] = None) -> Optional[int]:
        """Restore the newest COMPLETE save. Manifests are verified
        lazily newest-first and verification shares one read per shard
        with the load — a multi-GB recovery never re-reads older
        checkpoints it won't use. Steps are iteration-keyed, so the
        newest complete manifest is also the max-iteration one and
        trivially satisfies the ``min_iteration`` boundary rule whenever
        any manifest does (the parameter is kept for parity with the
        zip path's ranking contract). Reshaping is counted when the
        saving topology differs from ``target_replicas``; the actual
        residual re-bucketing happens at the next mesh placement.
        Returns the restored iteration, or None when no complete save
        exists."""
        self.wait()
        chosen = arrays = None
        for chosen, arrays in self._complete(decode=True):
            break
        if chosen is None:
            return None
        apply_net_state(net, arrays, chosen)
        saved_n = int(chosen.get("mesh", {}).get("n_replicas", 1))
        reshaped = (target_replicas is not None
                    and saved_n != int(target_replicas))
        if reshaped:
            log.warning(
                "topology-reshaping restore: checkpoint step %s was "
                "written on a %d-replica mesh, restoring onto %d replicas "
                "(replicated state re-places; replica-keyed state is "
                "re-bucketed or re-seeded at the next placement)",
                chosen["step"], saved_n, target_replicas)
        _restores_counter(reshaped).inc()
        _faults.record_event("elastic_restore", step=chosen["step"],
                             iteration=chosen.get("iteration"),
                             saved_replicas=saved_n,
                             target_replicas=target_replicas,
                             reshaped=reshaped)
        return int(chosen.get("iteration", 0))

    def snapshot(self) -> dict:
        with self._cv:
            pending = (1 if self._pending is not None else 0) \
                + (1 if self._busy else 0)
        return {"directory": self.directory,
                "steps": self.all_steps(),
                "last_step": self.last_step,
                "pending_saves": pending,
                "max_to_keep": self.max_to_keep,
                "shard_count": self.shard_count(),
                "last_error": (repr(self.last_error)
                               if self.last_error else None)}


# ------------------------------------------------------------- observability
def snapshot() -> dict:
    """The elastic posture for ``/debug/elastic`` and the flight
    recorder's ``elastic.json`` bundle section."""
    with _totals_lock:
        reshapes = dict(_reshape_totals)
    elastic_events = [e for e in _faults.events()
                      if e.get("category") in (
                          "host_loss", "capacity_restored", "mesh_reshape",
                          "elastic_save", "elastic_save_failed",
                          "elastic_restore")]
    return {
        "enabled": elastic_enabled(),
        "capacity": _capacity.snapshot(),
        "recover_steps": recover_steps(),
        "reshapes": reshapes,
        "checkpointers": [c.snapshot() for c in list(_checkpointers)],
        "events": elastic_events,
    }


def _mesh_gauge():
    def make():
        from deeplearning4j_tpu.observability import global_registry
        return global_registry().gauge(
            "dl4j_elastic_mesh_size",
            "devices in the elastic trainer's active mesh (shrinks on "
            "host loss, re-expands when capacity returns)")
    return _faults.cached_metric_handle(("elastic_mesh",), make)


def set_mesh_size(n: int):
    _mesh_gauge().set(int(n))


def _reshapes_counter(direction: str):
    def make():
        from deeplearning4j_tpu.observability import global_registry
        return global_registry().counter(
            "dl4j_elastic_reshapes_total",
            "elastic mesh reshapes performed, by direction",
            label_names=("direction",)).labels(direction=direction)
    return _faults.cached_metric_handle(("elastic_reshape", direction), make)


def _restores_counter(reshaped: bool):
    key = "true" if reshaped else "false"

    def make():
        from deeplearning4j_tpu.observability import global_registry
        return global_registry().counter(
            "dl4j_elastic_restores_total",
            "restores from the sharded elastic manifest, split by "
            "whether the mesh topology changed since the save",
            label_names=("reshaped",)).labels(reshaped=key)
    return _faults.cached_metric_handle(("elastic_restore", key), make)


def _saves_counter(mode: str):
    def make():
        from deeplearning4j_tpu.observability import global_registry
        return global_registry().counter(
            "dl4j_elastic_saves_total",
            "sharded elastic checkpoint saves, by mode",
            label_names=("mode",)).labels(mode=mode)
    return _faults.cached_metric_handle(("elastic_save", mode), make)


def _save_failures_counter():
    def make():
        from deeplearning4j_tpu.observability import global_registry
        return global_registry().counter(
            "dl4j_elastic_save_failures_total",
            "async elastic saves that failed in the background (training "
            "continues; the previous complete manifest stays in charge)")
    return _faults.cached_metric_handle(("elastic_save_fail",), make)


def _save_seconds_hist():
    def make():
        from deeplearning4j_tpu.observability import global_registry
        return global_registry().histogram(
            "dl4j_elastic_save_seconds",
            "background wall time of one sharded elastic save "
            "(serialize + fsync + manifest commit)")
    return _faults.cached_metric_handle(("elastic_save_secs",), make)


def _pending_gauge():
    def make():
        from deeplearning4j_tpu.observability import global_registry
        return global_registry().gauge(
            "dl4j_elastic_pending_saves",
            "async elastic saves queued behind the background writer")
    return _faults.cached_metric_handle(("elastic_pending",), make)
