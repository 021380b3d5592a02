"""CSV → DataVec transform → training — the classic tabular pipeline.

The analog of dl4j-examples' CSV/Iris flow (ref: IrisClassifier +
datavec-examples TransformProcess usage): read a CSV with
CSVRecordReader, declare its Schema, clean it with a TransformProcess
(drop an id column, map a categorical to an integer), feed a
RecordReaderDataSetIterator, train a MultiLayerNetwork, and evaluate.

Run: python examples/csv_data_pipeline.py [--rows N]
"""
import argparse
import tempfile
from pathlib import Path

import numpy as np  # noqa: E402


def write_csv(path: Path, rows: int, seed: int) -> None:
    """Synthetic 'sensor' data: three gaussian blobs, one per species."""
    rng = np.random.default_rng(seed)
    lines = ["id,width,height,species"]
    centers = {"setosa": (1.0, 4.0), "versicolor": (3.0, 1.0),
               "virginica": (5.0, 5.0)}
    for i in range(rows):
        species = list(centers)[i % 3]
        cx, cy = centers[species]
        w, h = rng.normal(cx, 0.4), rng.normal(cy, 0.4)
        lines.append(f"{i},{w:.3f},{h:.3f},{species}")
    path.write_text("\n".join(lines) + "\n")


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--rows", type=int, default=300)
    args = ap.parse_args()

    from deeplearning4j_tpu.datavec import (
        CSVRecordReader, FileSplit, LocalTransformExecutor, Schema,
        TransformProcess)
    from deeplearning4j_tpu.datavec.records import CollectionRecordReader
    from deeplearning4j_tpu.data.record_reader_iterator import (
        RecordReaderDataSetIterator)
    from deeplearning4j_tpu.nn.conf import layers as L
    from deeplearning4j_tpu.nn.conf.configuration import NeuralNetConfiguration
    from deeplearning4j_tpu.nn.multilayer import MultiLayerNetwork
    from deeplearning4j_tpu.optim.updaters import Adam

    with tempfile.TemporaryDirectory() as td:
        csv = Path(td) / "flowers.csv"
        write_csv(csv, args.rows, seed=0)

        # 1. schema of the RAW file
        schema = (Schema.Builder()
                  .add_column_integer("id")
                  .add_column_double("width")
                  .add_column_double("height")
                  .add_column_categorical("species", "setosa", "versicolor",
                                          "virginica")
                  .build())

        # 2. transform: drop the id, label → class index
        tp = (TransformProcess.Builder(schema)
              .remove_columns("id")
              .categorical_to_integer("species")
              .build())
        print("final schema:", tp.get_final_schema().get_column_names())

        # 3. execute the transform over the CSV records (the executor
        # unboxes Writables itself)
        rr = CSVRecordReader(skip_num_lines=1).initialize(FileSplit(str(csv)))
        clean = LocalTransformExecutor.execute_to_values(rr, tp)

        # 4. iterate minibatches (label = last column, 3 classes)
        reader = CollectionRecordReader(clean)
        it = RecordReaderDataSetIterator(reader, batch_size=32,
                                         label_index=2,
                                         num_possible_labels=3)

        conf = (NeuralNetConfiguration.builder().seed(42).updater(Adam(5e-3))
                .weight_init("xavier").list()
                .layer(L.DenseLayer(n_in=2, n_out=16, activation="relu"))
                .layer(L.OutputLayer(n_in=16, n_out=3, activation="softmax",
                                     loss_function="negativeloglikelihood"))
                .build())
        net = MultiLayerNetwork(conf).init()
        net.fit(it, epochs=30)

        it.reset()
        ev = net.evaluate(it)
        print(f"accuracy on the training blobs: {ev.accuracy():.3f}")
        assert ev.accuracy() > 0.9, "blobs are separable - should fit"
        print("csv pipeline example PASS")


if __name__ == "__main__":
    main()
