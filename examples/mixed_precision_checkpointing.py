"""Round-2 TPU extensions in one place: bf16 mixed precision, gradient
checkpointing (rematerialisation), and orbax sharded checkpoints.

Run on a virtual mesh:
  JAX_PLATFORMS=cpu JAX_NUM_CPU_DEVICES=8 \
      python examples/mixed_precision_checkpointing.py
"""
import os
import tempfile


def main():
    from deeplearning4j_tpu.data import MnistDataSetIterator
    from deeplearning4j_tpu.models import zoo
    from deeplearning4j_tpu.utils.orbax_ckpt import (
        ShardedCheckpointListener)

    # bf16 compute on the MXU, f32 masters; LeNet via the zoo
    net = zoo.LeNet().init_model()
    net.conf.dtype = "bfloat16"
    net.conf.remat = True            # recompute activations in backward

    ckdir = os.path.join(tempfile.mkdtemp(), "ck")
    lst = ShardedCheckpointListener(ckdir, every_n_iterations=5,
                                    async_save=True)
    net.setListeners(lst)
    net.fit(MnistDataSetIterator(64, train=True, num_examples=640),
            epochs=2)
    lst.ckpt.wait()
    ev = net.evaluate(MnistDataSetIterator(64, train=False,
                                           num_examples=320))
    print(f"bf16+remat LeNet accuracy: {ev.accuracy():.4f}; "
          f"checkpoints at steps {lst.ckpt.all_steps()}")
    lst.close()


if __name__ == "__main__":
    main()
