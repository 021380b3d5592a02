"""Import an ONNX model (authored with the in-repo wire codec — stands in
for any exported .onnx file) and fine-tune it through `sd.fit`.

ref analog: samediff-import-onnx usage in dl4j-examples."""
import numpy as np

from deeplearning4j_tpu.autodiff.samediff import TrainingConfig
from deeplearning4j_tpu.data.dataset import DataSet
from deeplearning4j_tpu.modelimport import onnx_proto as P
from deeplearning4j_tpu.modelimport.onnximport import OnnxGraphMapper
from deeplearning4j_tpu.ndarray import NDArray
from deeplearning4j_tpu.optim.updaters import Adam


def build_onnx_mlp() -> bytes:
    """A 2-layer MLP as ONNX bytes (what torch.onnx.export would emit)."""
    r = np.random.RandomState(7)
    w1 = (r.randn(16, 4) * 0.5).astype(np.float32)
    b1 = np.zeros(16, np.float32)
    w2 = (r.randn(2, 16) * 0.5).astype(np.float32)
    b2 = np.zeros(2, np.float32)
    nodes = [P.make_node("Gemm", ["x", "w1", "b1"], ["h"], transB=1),
             P.make_node("Relu", ["h"], ["hr"]),
             P.make_node("Gemm", ["hr", "w2", "b2"], ["logits"], transB=1),
             P.make_node("Softmax", ["logits"], ["probs"], axis=-1)]
    g = P.make_graph(
        nodes, "mlp",
        inputs=[P.make_value_info("x", np.float32, (None, 4))],
        outputs=[P.make_value_info("probs", np.float32, (None, 2))],
        initializers=[P.make_tensor("w1", w1), P.make_tensor("b1", b1),
                      P.make_tensor("w2", w2), P.make_tensor("b2", b2)])
    return P.make_model(g)


def main():
    sd = OnnxGraphMapper.import_model(build_onnx_mlp(), trainable=True)
    print("imported vars:", len(sd.variables()))

    # synthetic binary task: class = sign of the feature sum
    r = np.random.RandomState(0)
    X = r.randn(256, 4).astype(np.float32)
    Y = np.eye(2, dtype=np.float32)[(X.sum(1) > 0).astype(int)]

    lab = sd.placeholder("label", (None, 2))
    loss = sd.loss.log_loss(lab, sd.get_variable("probs"))
    loss.rename("loss")
    sd.set_training_config(TrainingConfig(
        updater=Adam(5e-3), data_set_feature_mapping=["x"],
        data_set_label_mapping=["label"], loss_variables=["loss"]))
    hist = sd.fit([DataSet(NDArray(X), NDArray(Y))] * 8, epochs=5)
    print("loss:", hist[0], "->", hist[-1])
    assert hist[-1] < hist[0]


if __name__ == "__main__":
    main()
