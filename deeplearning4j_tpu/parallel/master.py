"""TrainingMaster facades — reference-parity distributed entry points.

Reference: ``org.deeplearning4j.spark.api.TrainingMaster`` with impls
``ParameterAveragingTrainingMaster`` (SURVEY P2) and
``SharedTrainingMaster`` (P3, the flagship: threshold-encoded async gradient
sharing over an Aeron UDP mesh) driven through ``SparkDl4jMultiLayer`` /
``SparkComputationGraph``.

TPU-native redesign (SURVEY §5.8 north star): the TrainingMaster API shape
survives as a thin facade that (a) builds the device mesh, (b) shards the
input pipeline over the ``data`` axis, and (c) runs the whole step as one
GSPMD program whose gradient allreduce rides ICI within a slice and DCN
across slices. Spark, Aeron, and the UDP transport are deleted — there is
no transport code to configure. The threshold codec + accumulator SURVIVE
as the opt-in compressed gradient exchange (parallel/compression.py):
``SharedTrainingMaster(threshold_algorithm=...)`` routes the trainer
through error-feedback threshold collectives instead of the dense
allreduce. Multi-host bootstrap is ``jax.distributed.initialize`` (the
``VoidConfiguration`` analog is ``DistributedConfig`` below).

Semantics divergence (documented, BASELINE.md): updates are synchronous and
dense; ``ParameterAveragingTrainingMaster(averaging_frequency=N)`` degrades
to sync-every-step, which strictly dominates it in convergence per step.
"""
from __future__ import annotations

import dataclasses
import os
from typing import Optional, Tuple

import jax

from deeplearning4j_tpu.parallel.mesh import MeshSpec
from deeplearning4j_tpu.parallel.trainer import ShardedTrainer

# one probe per process: the answer cannot change while jaxlib doesn't
_MULTIPROC_PROBE: Optional[Tuple[bool, str]] = None

_PROBE_SCRIPT = r"""
import os, sys
os.environ["JAX_PLATFORMS"] = "cpu"
os.environ["JAX_NUM_CPU_DEVICES"] = "1"
import jax
jax.distributed.initialize(coordinator_address="127.0.0.1:" + sys.argv[2],
                           num_processes=2, process_id=int(sys.argv[1]))
import numpy as np
import jax.numpy as jnp
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P
mesh = Mesh(np.asarray(jax.devices()), ("data",))
x = jax.make_array_from_process_local_data(
    NamedSharding(mesh, P("data")), np.ones((1,), np.float32))
s = jax.jit(lambda a: jnp.sum(a), out_shardings=NamedSharding(mesh, P()))(x)
print("PSUM_OK", float(s), flush=True)
"""


def multiprocess_cpu_collectives_supported(
        timeout_s: float = 120.0) -> Tuple[bool, str]:
    """Runtime capability probe: can THIS jax/jaxlib run a cross-process
    collective on the CPU backend? Some builds (this container's among
    them) bootstrap ``jax.distributed`` fine and then fail the first
    multi-process computation with ``Multiprocess computations aren't
    implemented on the CPU backend`` — so the probe must run a REAL
    cross-process reduction, not just the handshake.

    Two throwaway subprocesses form a 2-process loopback mesh and psum
    one scalar. Cached per process (one ~5 s probe, then free); the
    ``DL4J_TPU_MULTIHOST_PROBE`` knob overrides it (``1`` = assume
    supported, ``0`` = assume not) for CI that already knows its
    platform. Returns ``(supported, reason)``.
    """
    global _MULTIPROC_PROBE
    override = os.environ.get("DL4J_TPU_MULTIHOST_PROBE", "")
    if override == "1":
        return True, "forced by DL4J_TPU_MULTIHOST_PROBE=1"
    if override == "0":
        return False, "forced by DL4J_TPU_MULTIHOST_PROBE=0"
    if _MULTIPROC_PROBE is not None:
        return _MULTIPROC_PROBE
    import socket
    import subprocess
    import sys
    s = socket.socket()
    s.bind(("127.0.0.1", 0))
    port = s.getsockname()[1]
    s.close()
    env = dict(os.environ)
    procs = [subprocess.Popen(
        [sys.executable, "-c", _PROBE_SCRIPT, str(i), str(port)],
        stdout=subprocess.PIPE, stderr=subprocess.STDOUT, text=True,
        env=env) for i in range(2)]
    outs = []
    ok = True
    try:
        for p in procs:
            try:
                out, _ = p.communicate(timeout=timeout_s)
            except subprocess.TimeoutExpired:
                p.kill()
                out, _ = p.communicate()
                out = (out or "") + "\n<probe timeout>"
                ok = False
            outs.append(out or "")
            ok = ok and p.returncode == 0 and "PSUM_OK" in outs[-1]
    finally:
        for p in procs:
            if p.poll() is None:
                p.kill()
                p.communicate()
    if ok:
        _MULTIPROC_PROBE = (True, "2-process loopback psum succeeded")
    else:
        # surface the decisive line (the XlaRuntimeError message) so a
        # skip names WHY, not just "probe failed"
        reason = "2-process loopback psum failed"
        for out in outs:
            for line in reversed(out.strip().splitlines()):
                if "Error" in line or "error" in line or "<probe" in line:
                    reason = line.strip()[:200]
                    break
            else:
                continue
            break
        _MULTIPROC_PROBE = (False, reason)
    return _MULTIPROC_PROBE


@dataclasses.dataclass
class DistributedConfig:
    """Multi-host bootstrap knobs (ref: VoidConfiguration — ports/mask/
    controller address → coordinator address/process ids)."""
    coordinator_address: Optional[str] = None   # "host:port" of process 0
    num_processes: Optional[int] = None
    process_id: Optional[int] = None

    def initialize(self):
        """ref: the Spark/Aeron bootstrap; here jax.distributed (PJRT DCN)."""
        if self.coordinator_address is not None:
            jax.distributed.initialize(
                coordinator_address=self.coordinator_address,
                num_processes=self.num_processes,
                process_id=self.process_id)


class TrainingMaster:
    """Base facade: owns MeshSpec + batch policy.

    ``tensor_parallel`` may be True (model axis of 2) or an int (the model
    axis size); the remaining devices form the ``data`` axis.
    """

    def __init__(self, batch_size_per_worker: int = 32, workers: Optional[int] = None,
                 tensor_parallel=False):
        self.batch_size_per_worker = batch_size_per_worker
        self.workers = workers
        self.tensor_parallel = tensor_parallel

    def mesh_spec(self) -> MeshSpec:
        if self.tensor_parallel:
            model = (int(self.tensor_parallel)
                     if not isinstance(self.tensor_parallel, bool) else 2)
            return MeshSpec.dp_tp(data=self.workers or -1, model=model)
        return MeshSpec.data_parallel(self.workers or -1)

    def make_trainer(self, net) -> ShardedTrainer:
        return ShardedTrainer(net, self.mesh_spec(),
                              tensor_parallel=bool(self.tensor_parallel))


class SharedTrainingMaster(TrainingMaster):
    """ref: org.deeplearning4j.spark.parameterserver.training.SharedTrainingMaster.

    ``threshold_algorithm`` is HONORED: passing one (a
    ``parallel.compression.ThresholdAlgorithm`` — Fixed/Adaptive, or a
    spec string) routes the built trainer through the compressed
    error-feedback gradient exchange (the EncodedGradientsAccumulator
    analog; see parallel/compression.py). With no algorithm the exchange
    stays the dense GSPMD allreduce, and the ``DL4J_TPU_GRAD_COMPRESS``
    env knob still applies (``0`` = kill switch either way)."""

    def __init__(self, batch_size_per_worker: int = 32, workers: Optional[int] = None,
                 threshold: Optional[float] = None, threshold_algorithm=None,
                 workers_per_node: Optional[int] = None, **_ignored):
        super().__init__(batch_size_per_worker, workers or workers_per_node)
        # an EXPLICIT threshold without an algorithm implies fixed:t (the
        # reference's threshold always configured the codec) — both
        # spellings, constructor and Builder, behave identically; leaving
        # both unset keeps the dense exchange
        if threshold is not None and threshold_algorithm is None:
            threshold_algorithm = "fixed:%g" % float(threshold)
        self.threshold = 1e-3 if threshold is None else threshold
        self.threshold_algorithm = threshold_algorithm

    def make_trainer(self, net) -> ShardedTrainer:
        return ShardedTrainer(net, self.mesh_spec(),
                              tensor_parallel=bool(self.tensor_parallel),
                              grad_compression=self.threshold_algorithm)

    class Builder:
        def __init__(self, *args):
            self._kw = {}

        def batch_size_per_worker(self, n):
            self._kw["batch_size_per_worker"] = n
            return self

        batchSizePerWorker = batch_size_per_worker

        def workers_per_node(self, n):
            self._kw["workers"] = n
            return self

        workersPerNode = workers_per_node

        def threshold_algorithm(self, a):
            self._kw["threshold_algorithm"] = a
            return self

        thresholdAlgorithm = threshold_algorithm

        def threshold(self, t):
            """ref: Builder#threshold — shorthand for a fixed algorithm
            at ``t`` (the constructor derives ``fixed:t`` when no explicit
            threshold_algorithm is set)."""
            self._kw["threshold"] = t
            return self

        def build(self):
            return SharedTrainingMaster(**self._kw)


class ParameterAveragingTrainingMaster(TrainingMaster):
    """ref: org.deeplearning4j.spark.impl.paramavg.ParameterAveragingTrainingMaster.
    Sync dense allreduce every step subsumes periodic averaging."""

    def __init__(self, batch_size_per_worker: int = 32, workers: Optional[int] = None,
                 averaging_frequency: int = 1, **_ignored):
        super().__init__(batch_size_per_worker, workers)
        self.averaging_frequency = averaging_frequency

    class Builder:
        def __init__(self, *args):
            self._kw = {}

        def batch_size_per_worker(self, n):
            self._kw["batch_size_per_worker"] = n
            return self

        def averaging_frequency(self, n):
            self._kw["averaging_frequency"] = n
            return self

        averagingFrequency = averaging_frequency

        def build(self):
            return ParameterAveragingTrainingMaster(**self._kw)


def _rebatch(data, target: int):
    """Re-chunk a stream of DataSets to ``target`` examples per step
    (the batch_size_per_worker × data-axis-size policy). Tuple-valued
    (MultiDataSet) batches pass through unchanged."""
    import numpy as np
    from deeplearning4j_tpu.data.dataset import DataSet

    buf_x, buf_y, n = [], [], 0
    has_labels = None
    for ds in data:
        x, y = ds.features, ds.labels
        if (isinstance(x, (tuple, list)) or ds.features_mask is not None
                or getattr(ds, "labels_mask", None) is not None):
            yield ds  # masks/multi-input: don't re-split, preserve alignment
            continue
        if has_labels is None:
            has_labels = y is not None
        elif has_labels != (y is not None):
            raise ValueError(
                "mixed labeled/unlabeled DataSets in one stream cannot be "
                "re-batched without misaligning features and labels")
        buf_x.append(np.asarray(x))
        if has_labels:
            buf_y.append(np.asarray(y))
        n += buf_x[-1].shape[0]
        while n >= target:
            X = np.concatenate(buf_x) if len(buf_x) > 1 else buf_x[0]
            Y = (np.concatenate(buf_y) if len(buf_y) > 1 else buf_y[0]) \
                if has_labels else None
            yield DataSet(X[:target], Y[:target] if has_labels else None)
            buf_x = [X[target:]] if X.shape[0] > target else []
            buf_y = ([Y[target:]] if Y.shape[0] > target else []) if has_labels else []
            n -= target
    if n:
        yield DataSet(np.concatenate(buf_x) if len(buf_x) > 1 else buf_x[0],
                      (np.concatenate(buf_y) if len(buf_y) > 1 else buf_y[0])
                      if has_labels else None)


class SparkDl4jMultiLayer:
    """ref: org.deeplearning4j.spark.impl.multilayer.SparkDl4jMultiLayer.
    The SparkContext slot is accepted for parity and unused (no Spark in the
    TPU path; data distribution is the input pipeline's job)."""

    _net_cls = None  # set per subclass

    def _wrap_conf(self, net_or_conf):
        from deeplearning4j_tpu.nn.multilayer import MultiLayerNetwork
        return MultiLayerNetwork(net_or_conf)

    def __init__(self, sc, net_or_conf, training_master: TrainingMaster):
        if not hasattr(net_or_conf, "fit"):
            net_or_conf = self._wrap_conf(net_or_conf)
        self.network = net_or_conf
        self.training_master = training_master
        self._trainer = training_master.make_trainer(self.network)

    def fit(self, data, epochs: int = 1):
        from deeplearning4j_tpu.parallel.mesh import DATA_AXIS, axis_size
        tm = self.training_master
        if hasattr(data, "__iter__") and not hasattr(data, "shape"):
            target = tm.batch_size_per_worker * axis_size(self._trainer.mesh,
                                                          DATA_AXIS)
            for _ in range(epochs):
                if hasattr(data, "reset"):
                    data.reset()
                self._trainer.fit(list(_rebatch(data, target)), epochs=1)
        else:
            self._trainer.fit(data, epochs=epochs)
        return self.network

    def get_network(self):
        return self.network

    getNetwork = get_network


class SparkComputationGraph(SparkDl4jMultiLayer):
    """ref: org.deeplearning4j.spark.impl.graph.SparkComputationGraph."""

    def _wrap_conf(self, net_or_conf):
        from deeplearning4j_tpu.nn.graph import ComputationGraph
        return ComputationGraph(net_or_conf)
