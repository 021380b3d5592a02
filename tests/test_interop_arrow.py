"""Interop (J15) + DataVec Arrow bridge (E3) tests.

Ref analogs: nd4j-tensorflow ``GraphRunnerTest`` (run a real TF graph on
NDArrays) and datavec-arrow ``ArrowConverterTest``.
"""
import numpy as np
import pytest

from deeplearning4j_tpu.datavec import (ArrowConverter, ArrowRecordReader,
                                        DoubleWritable, FileSplit,
                                        IntWritable, Schema, Text,
                                        TransformProcess)
from deeplearning4j_tpu.datavec.schema import ColumnMetaData, ColumnType


def _schema():
    return Schema([ColumnMetaData("id", ColumnType.Integer),
                   ColumnMetaData("score", ColumnType.Double),
                   ColumnMetaData("tag", ColumnType.String)])


def _rows():
    return [[IntWritable(1), DoubleWritable(0.5), Text("a")],
            [IntWritable(2), DoubleWritable(1.5), Text("b")],
            [IntWritable(3), DoubleWritable(2.5), Text("c")]]


class TestArrowBridge:
    def test_round_trip_table(self):
        table = ArrowConverter.to_arrow(_schema(), _rows())
        assert table.num_rows == 3
        assert table.schema.names == ["id", "score", "tag"]
        back = ArrowConverter.to_datavec(table)
        assert back == _rows()
        sch = ArrowConverter.arrow_schema_to_datavec(table)
        assert sch.get_type("id") == ColumnType.Integer
        assert sch.get_type("score") == ColumnType.Double
        assert sch.get_type("tag") == ColumnType.String

    @pytest.mark.parametrize("fmt", ["feather", "parquet"])
    def test_file_round_trip(self, tmp_path, fmt):
        path = str(tmp_path / f"data.{'parquet' if fmt == 'parquet' else 'arrow'}")
        if fmt == "parquet":
            ArrowConverter.write_parquet(_schema(), _rows(), path)
        else:
            ArrowConverter.write_ipc(_schema(), _rows(), path)
        rr = ArrowRecordReader()
        rr.initialize(FileSplit(path))
        got = list(rr)
        assert got == _rows()
        assert rr.schema.get_column_names() == ["id", "score", "tag"]

    def test_arrow_reader_feeds_transform_process(self, tmp_path):
        path = str(tmp_path / "t.arrow")
        ArrowConverter.write_ipc(_schema(), _rows(), path)
        rr = ArrowRecordReader()
        rr.initialize(FileSplit(path))
        tp = (TransformProcess.Builder(rr.schema)
              .remove_columns("tag")
              .build())
        from deeplearning4j_tpu.datavec import LocalTransformExecutor
        out = LocalTransformExecutor.execute(list(rr), tp)
        assert out == [[IntWritable(1), DoubleWritable(0.5)],
                       [IntWritable(2), DoubleWritable(1.5)],
                       [IntWritable(3), DoubleWritable(2.5)]]


class TestGraphRunner:
    def test_runs_frozen_tf_graph_on_ndarrays(self):
        tf = pytest.importorskip("tensorflow")
        from deeplearning4j_tpu.interop import GraphRunner
        from deeplearning4j_tpu.ndarray.ndarray import NDArray

        @tf.function
        def f(x, w):
            return tf.nn.relu(tf.matmul(x, w)) + 1.0

        x_spec = tf.TensorSpec((2, 3), tf.float32, name="x")
        w_spec = tf.TensorSpec((3, 4), tf.float32, name="w")
        from tensorflow.python.framework.convert_to_constants import (
            convert_variables_to_constants_v2)
        frozen = convert_variables_to_constants_v2(
            f.get_concrete_function(x_spec, w_spec))
        gd = frozen.graph.as_graph_def()

        rng = np.random.default_rng(0)
        x = rng.normal(size=(2, 3)).astype(np.float32)
        w = rng.normal(size=(3, 4)).astype(np.float32)
        with GraphRunner(graph_def=gd, input_names=["x", "w"]) as runner:
            out = runner.run({"x": NDArray(x), "w": w})
        (result,) = out.values()
        np.testing.assert_allclose(np.asarray(result.buf()),
                                   np.maximum(x @ w, 0) + 1.0, rtol=1e-5)

    def test_onnxruntime_gated(self):
        from deeplearning4j_tpu.interop import OnnxRuntimeRunner
        try:
            import onnxruntime  # noqa: F401
            pytest.skip("onnxruntime installed; gate not exercised")
        except ImportError:
            pass
        with pytest.raises(ImportError, match="onnxruntime"):
            OnnxRuntimeRunner("model.onnx")
