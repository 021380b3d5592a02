"""CapsNet on MNIST (ref analog: dl4j-examples CapsNet samples; layers:
conf.layers.PrimaryCapsules/CapsuleLayer/CapsuleStrengthLayer).

Dynamic routing runs unrolled inside the one jitted train step."""
from deeplearning4j_tpu.data.mnist import MnistDataSetIterator
from deeplearning4j_tpu.nn.conf.configuration import NeuralNetConfiguration
from deeplearning4j_tpu.nn.conf.inputs import InputType
from deeplearning4j_tpu.nn.conf.layers import (CapsuleLayer,
                                               CapsuleStrengthLayer,
                                               ConvolutionLayer, LossLayer,
                                               PrimaryCapsules)
from deeplearning4j_tpu.nn.multilayer import MultiLayerNetwork
from deeplearning4j_tpu.optim.updaters import Adam


def main():
    conf = (NeuralNetConfiguration.builder()
            .seed(12345).updater(Adam(1e-3))
            .list()
            .layer(ConvolutionLayer(n_out=16, kernel_size=(9, 9),
                                    activation="relu"))
            .layer(PrimaryCapsules(capsule_dimensions=8, channels=4,
                                   kernel_size=(9, 9), stride=(2, 2)))
            .layer(CapsuleLayer(capsules=10, capsule_dimensions=16,
                                routings=3))
            .layer(CapsuleStrengthLayer())
            .layer(LossLayer(loss_function="mse"))
            .set_input_type(InputType.convolutional_flat(28, 28, 1))
            .build())
    net = MultiLayerNetwork(conf).init()
    print(f"capsnet params: {net.numParams():,}")

    it = MnistDataSetIterator(64, train=True, num_examples=512)
    net.fit(it, epochs=2)
    print("final score:", net.score())


if __name__ == "__main__":
    main()
