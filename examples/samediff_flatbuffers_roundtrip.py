"""SameDiff FlatBuffers artifacts: train → save .fb → load → keep training.

Demonstrates J7 reference-format compatibility (`autodiff/flatgraph.py`):
the file written here is an org.nd4j.graph `FlatGraph` binary — the same
container `SameDiff#save`/`#asFlatBuffers` produces upstream — carrying the
graph topology (CUSTOM nodes keyed by opName with attributes in
FlatProperties), variable values, loss variables, and the training config
as a Jackson-style JSON string.
"""
import os
import tempfile

import numpy as np

from deeplearning4j_tpu.autodiff.samediff import SameDiff, TrainingConfig
from deeplearning4j_tpu.data.dataset import DataSet
from deeplearning4j_tpu.optim.updaters import Adam


def main():
    rng = np.random.default_rng(0)
    W_true = np.array([[1.0, -2.0], [0.5, 1.5], [-1.0, 0.25]], np.float32)
    X = rng.normal(size=(64, 3)).astype(np.float32)
    Y = X @ W_true

    # ---- build + train a few steps
    sd = SameDiff.create()
    x = sd.placeholder("x", (None, 3), np.float32)
    w = sd.var("w", init=np.zeros((3, 2), np.float32))
    b = sd.var("b", init=np.zeros(2, np.float32))
    (x.mmul(w) + b).rename("y")
    lab = sd.placeholder("label", (None, 2), np.float32)
    sd.loss.mse(lab, sd._vars["y"]).rename("loss")
    sd.set_training_config(TrainingConfig(
        updater=Adam(0.1), data_set_feature_mapping=["x"],
        data_set_label_mapping=["label"], loss_variables=["loss"]))
    h1 = sd.fit([DataSet(X, Y)] * 20, epochs=2)
    print(f"phase 1: loss {h1[0]:.4f} -> {h1[-1]:.4f}")

    # ---- save as a FlatGraph binary and reload
    with tempfile.TemporaryDirectory() as td:
        path = os.path.join(td, "linear.fb")
        # save_updater_state: Adam moments ride the UpdaterState table so
        # the resumed fine-tune continues EXACTLY (r5)
        sd.save(path, save_updater_state=True)   # .fb → FlatBuffers
        print(f"saved {os.path.getsize(path)} bytes of FlatGraph")
        sd2 = SameDiff.load(path)

        # values, loss wiring and training config survived — training
        # continues from where phase 1 stopped
        h2 = sd2.fit([DataSet(X, Y)] * 20, epochs=2)
        print(f"phase 2 (after reload): loss {h2[0]:.4f} -> {h2[-1]:.4f}")
        assert h2[-1] <= h1[-1] + 1e-3

        got = np.asarray(sd2.output({"x": X[:4]}, ["y"])["y"])
        print("w error vs truth:",
              float(np.abs(np.asarray(sd2._values['w']) - W_true).max()))
        print("sample prediction:", np.round(got[0], 3),
              "target:", np.round(Y[0], 3))


if __name__ == "__main__":
    main()
