"""BASELINE config[4]: SharedTrainingMaster gradient-sharing on TinyImageNet.

The reference runs this over Spark + Aeron UDP across hosts; here the same
TrainingMaster facade builds a device mesh and GSPMD emits the gradient
allreduce over ICI (multi-host: bootstrap each process with
DistributedConfig first — see tests/test_multihost.py).

Run on a virtual mesh:
  JAX_PLATFORMS=cpu JAX_NUM_CPU_DEVICES=8 \
      python examples/shared_training_tinyimagenet.py
"""
import jax

from deeplearning4j_tpu.data import TinyImageNetDataSetIterator
from deeplearning4j_tpu.models import zoo
from deeplearning4j_tpu.optim.listeners import ScoreIterationListener
from deeplearning4j_tpu.optim.updaters import Adam
from deeplearning4j_tpu.parallel.master import SharedTrainingMaster


def main():
    num_classes = 20          # subset for the example; 200 on a real run
    it = TinyImageNetDataSetIterator(64, train=True, num_examples=512,
                                     num_classes=num_classes)
    if it.synthetic:
        print("note: no tiny-imagenet-200 under ~/.deeplearning4j_tpu — "
              "using the synthetic learnable fallback")
    net = zoo.SimpleCNN(num_classes=num_classes,
                        input_shape=(64, 64, 3)).init_model()
    net.setListeners(ScoreIterationListener(4))

    master = (SharedTrainingMaster.Builder()
              .batch_size_per_worker(8)
              .build())                 # threshold knobs accepted, subsumed
    trainer = master.make_trainer(net)
    trainer.fit(it, epochs=3)
    print(f"final score: {trainer.score():.4f} "
          f"(mesh devices: {len(jax.devices())})")


if __name__ == "__main__":
    main()
