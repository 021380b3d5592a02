"""Migration path: a reference DL4J model zip → this framework → fine-tune
→ export back in the reference schema.

Demonstrates D9 reference-artifact compatibility end to end
(`modelimport/dl4j_zip.py`): the zip layout here is byte-exact to what a
JVM DL4J `ModelSerializer.writeModel` produces (Jackson configuration.json
+ Nd4j.write coefficients.bin), built locally because this container is
zero-egress. With a real artifact, replace `build_reference_style_zip`
with its path.
"""
import json
import os
import struct
import tempfile
import zipfile

import numpy as np

from deeplearning4j_tpu.modelimport import dl4j_zip
from deeplearning4j_tpu.utils.serialization import ModelSerializer
from deeplearning4j_tpu.data.dataset import DataSet


def build_reference_style_zip(path):
    """A Dense(4→8 relu) + Output(8→3 softmax) artifact in the reference's
    exact byte layout (DataOutputStream UTF/big-endian records)."""
    def utf(s):
        b = s.encode()
        return struct.pack(">H", len(b)) + b

    def buf(values, dtype_name):
        fmt = {"FLOAT": ">f4", "LONG": ">i8"}[dtype_name]
        a = np.asarray(values).astype(fmt)
        return (utf("MIXED_DATA_TYPES") + struct.pack(">q", a.size)
                + utf(dtype_name) + a.tobytes())

    rng = np.random.default_rng(7)
    W0 = rng.normal(scale=0.3, size=(4, 8)).astype(np.float32)
    b0 = np.zeros(8, np.float32)
    W1 = rng.normal(scale=0.3, size=(8, 3)).astype(np.float32)
    b1 = np.zeros(3, np.float32)
    flat = np.concatenate([W0.ravel(order="F"), b0,
                           W1.ravel(order="F"), b1])
    conf = {
        "backpropType": "Standard",
        "confs": [
            {"layer": {"@class": "org.deeplearning4j.nn.conf.layers.DenseLayer",
                       "activationFn": {"@class": "org.nd4j.linalg.activations.impl.ActivationReLU"},
                       "iUpdater": {"@class": "org.nd4j.linalg.learning.config.Adam",
                                    "learningRate": 0.01},
                       "nin": 4, "nout": 8}, "seed": 7},
            {"layer": {"@class": "org.deeplearning4j.nn.conf.layers.OutputLayer",
                       "activationFn": {"@class": "org.nd4j.linalg.activations.impl.ActivationSoftmax"},
                       "lossFn": {"@class": "org.nd4j.linalg.lossfunctions.impl.LossNegativeLogLikelihood"},
                       "nin": 8, "nout": 3}, "seed": 7}],
    }
    shape_info = [1, flat.size, 1, 0, 1, ord("c")]
    with zipfile.ZipFile(path, "w") as zf:
        zf.writestr("configuration.json", json.dumps(conf))
        zf.writestr("coefficients.bin",
                    buf(shape_info, "LONG") + buf(flat, "FLOAT"))


def main():
    d = tempfile.mkdtemp()
    src = os.path.join(d, "reference_model.zip")
    build_reference_style_zip(src)

    # 1. restore the reference artifact (auto-detected format)
    net = ModelSerializer.restoreMultiLayerNetwork(src)
    print("restored:", [type(l).__name__ for l in net.conf.layers],
          "updater:", type(net.conf.updater).__name__,
          "lr:", net.conf.updater.learning_rate)

    # 2. fine-tune on local data
    rng = np.random.default_rng(0)
    X = rng.normal(size=(128, 4)).astype(np.float32)
    y = (X[:, 0] + 0.5 * X[:, 1] > 0).astype(int) + (X[:, 2] > 1)
    Y = np.eye(3, dtype=np.float32)[y]
    for _ in range(20):
        net.fit(DataSet(X, Y))
    acc = float((net.output(X).toNumpy().argmax(1) == y).mean())
    print(f"fine-tuned accuracy: {acc:.3f}")

    # 3. export back in the reference schema (a JVM DL4J can read this)
    out = os.path.join(d, "finetuned_dl4j_schema.zip")
    dl4j_zip.write_model(net, out)
    again = dl4j_zip.restore_multi_layer_network(out)
    drift = float(np.abs(net.output(X[:4]).toNumpy()
                         - again.output(X[:4]).toNumpy()).max())
    print(f"re-exported + re-restored, prediction drift: {drift:.2e}")


if __name__ == "__main__":
    main()
