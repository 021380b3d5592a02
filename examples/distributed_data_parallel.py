"""Data-parallel training over a device mesh — the SharedTrainingMaster
analog (BASELINE config[4] shape, one slice).

On a multi-chip TPU slice this shards batches over all chips with GSPMD
allreduce; on CPU it runs on a virtual 8-device mesh:
  JAX_PLATFORMS=cpu JAX_NUM_CPU_DEVICES=8 \
      python examples/distributed_data_parallel.py
"""
import numpy as np

from deeplearning4j_tpu.data import Cifar10DataSetIterator
from deeplearning4j_tpu.models import zoo
from deeplearning4j_tpu.optim.updaters import Adam
from deeplearning4j_tpu.parallel.master import SharedTrainingMaster


def main():
    net = zoo.SimpleCNN(num_classes=10, input_shape=(32, 32, 3)).init_model()
    master = SharedTrainingMaster.Builder().batch_size_per_worker(32).build()
    trainer = master.make_trainer(net)
    it = Cifar10DataSetIterator(128, train=True, num_examples=1024)
    trainer.fit(it, epochs=2)
    print("score:", trainer.score())


if __name__ == "__main__":
    main()
