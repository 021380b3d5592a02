"""Worker process for the two-process DCN/multi-host test (run by
``test_multihost.py``, never collected by pytest directly).

Each process: force 2 virtual CPU devices, bootstrap ``jax.distributed``
through ``DistributedConfig`` (the VoidConfiguration analog), build a global
4-device data-parallel mesh spanning both processes, and train a small net
through ``ShardedTrainer`` on the process-LOCAL half of a deterministic
global batch. Process 0 dumps the final flat params.

Ref: the localhost-Aeron multi-node test doctrine (SURVEY §4(d)) — the
reference simulates its multi-node gradient-sharing stack over loopback; the
TPU-native analog is two local jax processes over the distributed
coordinator with GSPMD allreduce across them.
"""
import os
import sys

import numpy as np


def build_net():
    from deeplearning4j_tpu.nn.conf.configuration import NeuralNetConfiguration
    from deeplearning4j_tpu.nn.conf.inputs import InputType
    from deeplearning4j_tpu.nn.conf.layers import DenseLayer, OutputLayer
    from deeplearning4j_tpu.nn.multilayer import MultiLayerNetwork
    from deeplearning4j_tpu.optim.updaters import Sgd

    conf = (NeuralNetConfiguration.builder()
            .seed(99).updater(Sgd(0.1)).list()
            .layer(DenseLayer(n_out=8, activation="tanh"))
            .layer(OutputLayer(n_out=3, activation="softmax",
                               loss_function="mcxent"))
            .set_input_type(InputType.feed_forward(5))
            .build())
    return MultiLayerNetwork(conf).init()


def global_data(step: int):
    rng = np.random.default_rng(1000 + step)
    x = rng.normal(size=(16, 5)).astype(np.float32)
    y = np.eye(3, dtype=np.float32)[rng.integers(0, 3, 16)]
    return x, y


def main():
    proc_id = int(sys.argv[1])
    nprocs = int(sys.argv[2])
    port = sys.argv[3]
    out_path = sys.argv[4]

    os.environ["JAX_PLATFORMS"] = "cpu"
    os.environ["JAX_NUM_CPU_DEVICES"] = "2"

    import jax

    from deeplearning4j_tpu.parallel.master import DistributedConfig

    DistributedConfig(coordinator_address=f"127.0.0.1:{port}",
                      num_processes=nprocs, process_id=proc_id).initialize()

    assert jax.process_count() == nprocs, jax.process_count()
    assert len(jax.devices()) == 2 * nprocs, len(jax.devices())

    from deeplearning4j_tpu.parallel import MeshSpec
    from deeplearning4j_tpu.parallel.trainer import ShardedTrainer

    net = build_net()
    trainer = ShardedTrainer(net, MeshSpec.data_parallel())

    half = 16 // nprocs
    for step in range(5):
        x, y = global_data(step)
        lo, hi = proc_id * half, (proc_id + 1) * half
        trainer.fit(x[lo:hi], y[lo:hi])     # process-local partition

    if proc_id == 0:
        flat = np.asarray(net.params().buf())
        np.save(out_path, flat)
        print(f"worker0 done score={net.score():.6f}")
    else:
        print("worker1 done")


if __name__ == "__main__":
    main()
