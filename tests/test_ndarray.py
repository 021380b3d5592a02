"""NDArray core tests (ref test model: nd4j-backends/nd4j-tests Nd4jTestsC)."""
import numpy as np
import pytest

from deeplearning4j_tpu import nd
from deeplearning4j_tpu.ndarray import NDArray
from deeplearning4j_tpu.ops import transforms as T


class TestCreation:
    def test_zeros_ones_full(self):
        a = nd.zeros(2, 3)
        assert a.shape == (2, 3)
        assert a.sumNumber() == 0.0
        b = nd.ones(4)
        assert b.sumNumber() == 4.0
        c = nd.full((2, 2), 7.0)
        assert c.meanNumber() == 7.0

    def test_create_from_list(self):
        a = nd.create([[1.0, 2.0], [3.0, 4.0]])
        assert a.shape == (2, 2)
        assert a.getDouble(1, 0) == 3.0

    def test_arange_linspace_eye(self):
        assert nd.arange(5).length() == 5
        assert nd.linspace(0, 1, 11).getDouble(10) == pytest.approx(1.0)
        assert nd.eye(3).sumNumber() == 3.0

    def test_dtypes(self):
        a = nd.zeros(2, 2, dtype="bfloat16")
        assert str(a.dtype) == "bfloat16"
        b = a.castTo("float32")
        assert str(b.dtype) == "float32"

    def test_rand_reproducible(self):
        a = nd.rand(3, 3, seed=42)
        b = nd.rand(3, 3, seed=42)
        assert a.equals(b)

    def test_stateful_rng(self):
        nd.setSeed(7)
        a = nd.randn(4)
        b = nd.randn(4)
        assert not a.equals(b)  # state advanced
        nd.setSeed(7)
        assert nd.randn(4).equals(a)  # reproducible from seed


class TestArithmetic:
    def test_add_sub_mul_div(self):
        a = nd.create([1.0, 2.0, 3.0])
        b = nd.create([4.0, 5.0, 6.0])
        assert a.add(b).equals(nd.create([5.0, 7.0, 9.0]))
        assert b.sub(a).equals(nd.create([3.0, 3.0, 3.0]))
        assert a.mul(b).equals(nd.create([4.0, 10.0, 18.0]))
        assert b.div(a).equals(nd.create([4.0, 2.5, 2.0]))

    def test_operators(self):
        a = nd.create([1.0, 2.0])
        assert (a + 1).equals(nd.create([2.0, 3.0]))
        assert (2 * a).equals(nd.create([2.0, 4.0]))
        assert (1 - a).equals(nd.create([0.0, -1.0]))
        assert (-a).equals(nd.create([-1.0, -2.0]))

    def test_inplace_i_variants(self):
        a = nd.create([1.0, 2.0, 3.0])
        a.addi(10.0)
        assert a.equals(nd.create([11.0, 12.0, 13.0]))
        a.muli(2.0).subi(2.0)
        assert a.equals(nd.create([20.0, 22.0, 24.0]))

    def test_broadcasting(self):
        a = nd.ones(3, 4)
        row = nd.create([1.0, 2.0, 3.0, 4.0])
        out = a.addRowVector(row)
        assert out.shape == (3, 4)
        assert out.getDouble(2, 3) == 5.0
        col = nd.create([10.0, 20.0, 30.0])
        out2 = a.mulColumnVector(col)
        assert out2.getDouble(1, 0) == 20.0

    def test_mmul(self):
        a = nd.create([[1.0, 2.0], [3.0, 4.0]])
        b = nd.eye(2)
        assert a.mmul(b).equals(a)
        v = nd.create([1.0, 1.0])
        assert a.mmul(v).equals(nd.create([3.0, 7.0]))

    def test_mmul_bf16_accumulates_f32(self):
        a = nd.ones(8, 8, dtype="bfloat16")
        out = a.mmul(a)
        assert out.getDouble(0, 0) == 8.0
        assert str(out.dtype) == "float32"


class TestReductions:
    def test_sum_mean_dim(self):
        a = nd.create([[1.0, 2.0], [3.0, 4.0]])
        assert a.sum().item() == 10.0
        assert a.sum(0).equals(nd.create([4.0, 6.0]))
        assert a.mean(1).equals(nd.create([1.5, 3.5]))

    def test_std_var_bias_correction(self):
        a = nd.create([1.0, 2.0, 3.0, 4.0])
        # DL4J default is bias-corrected (n-1), matching numpy ddof=1
        assert a.std().item() == pytest.approx(np.std([1, 2, 3, 4], ddof=1))
        assert a.var(bias_corrected=False).item() == pytest.approx(np.var([1, 2, 3, 4]))

    def test_norms(self):
        a = nd.create([3.0, -4.0])
        assert a.norm1().item() == 7.0
        assert a.norm2().item() == 5.0
        assert a.normmax().item() == 4.0

    def test_argmax(self):
        a = nd.create([[1.0, 5.0, 2.0], [7.0, 0.0, 3.0]])
        assert a.argMax(1).toNumpy().tolist() == [1, 0]
        assert int(a.argMax()) == 3

    def test_cumsum(self):
        assert nd.create([1.0, 2.0, 3.0]).cumsum(0).equals(nd.create([1.0, 3.0, 6.0]))


class TestShape:
    def test_reshape_transpose_permute(self):
        a = nd.arange(6).reshape(2, 3)
        assert a.shape == (2, 3)
        assert a.T.shape == (3, 2)
        b = nd.arange(24).reshape(2, 3, 4).permute(2, 0, 1)
        assert b.shape == (4, 2, 3)

    def test_ravel_squeeze_expand(self):
        a = nd.zeros(2, 1, 3)
        assert a.ravel().shape == (6,)
        assert a.squeeze(1).shape == (2, 3)
        assert a.expandDims(0).shape == (1, 2, 1, 3)

    def test_concat_stack(self):
        a, b = nd.ones(2, 3), nd.zeros(2, 3)
        assert nd.concat(0, a, b).shape == (4, 3)
        assert nd.concat(1, a, b).shape == (2, 6)
        assert nd.stack(0, a, b).shape == (2, 2, 3)
        assert nd.vstack(a, b).shape == (4, 3)
        assert nd.hstack(a, b).shape == (2, 6)

    def test_tad(self):
        a = nd.arange(24).reshape(2, 3, 4)
        t = a.tensorAlongDimension(0, 1, 2)
        assert t.shape == (3, 4)
        assert t.equals(a[0])


class TestViewsAndIndexing:
    """The hard part (SURVEY §7): view write-through semantics."""

    def test_basic_view_read(self):
        a = nd.arange(12).reshape(3, 4)
        row = a.getRow(1)
        assert row.toNumpy().tolist() == [4, 5, 6, 7]

    def test_view_write_through(self):
        a = nd.zeros(3, 4)
        row = a.getRow(1)
        row.assign(5.0)
        assert a.sum().item() == 20.0  # write propagated to base

    def test_view_inplace_arithmetic_propagates(self):
        a = nd.ones(4, 4)
        sub = a[1:3, 1:3]
        sub.addi(10.0)
        assert a.getDouble(1, 1) == 11.0
        assert a.getDouble(0, 0) == 1.0
        assert a.sumNumber() == 16 + 40

    def test_nested_view_propagation(self):
        a = nd.zeros(4, 4)
        block = a[0:2]          # view of a
        cell = block[1, 2:4]    # view of view
        cell.assign(3.0)
        assert a.getDouble(1, 2) == 3.0
        assert a.getDouble(1, 3) == 3.0
        assert a.sumNumber() == 6.0

    def test_putscalar_get(self):
        a = nd.zeros(2, 2)
        a.putScalar((0, 1), 42.0)
        assert a.getDouble(0, 1) == 42.0
        assert a.getScalar(0, 1).item() == 42.0

    def test_put_column(self):
        a = nd.zeros(3, 3)
        a.putColumn(2, nd.create([1.0, 2.0, 3.0]))
        assert a.getColumn(2).toNumpy().tolist() == [1.0, 2.0, 3.0]

    def test_setitem(self):
        a = nd.zeros(3, 3)
        a[0] = 1.0
        a[2, 2] = 9.0
        assert a.sumNumber() == 12.0

    def test_dup_detaches(self):
        a = nd.ones(2, 2)
        b = a.getRow(0).dup()
        b.assign(100.0)
        assert a.sumNumber() == 4.0  # dup broke the view link

    def test_assign_broadcasts(self):
        a = nd.zeros(2, 3)
        a.assign(7.0)
        assert a.meanNumber() == 7.0


class TestComparisons:
    def test_gt_lt(self):
        a = nd.create([1.0, 5.0, 3.0])
        assert a.gt(2.0).toNumpy().tolist() == [False, True, True]
        assert a.lt(3.5).toNumpy().tolist() == [True, False, True]

    def test_equals_with_eps(self):
        a = nd.create([1.0, 2.0])
        b = nd.create([1.0 + 1e-7, 2.0])
        assert a.equalsWithEps(b, 1e-5)
        assert not a.equals(nd.create([1.0, 3.0]))


class TestTransforms:
    def test_activations(self):
        x = nd.create([-1.0, 0.0, 1.0])
        assert T.relu(x).toNumpy().tolist() == [0.0, 0.0, 1.0]
        assert T.sigmoid(nd.zeros(1)).item() == pytest.approx(0.5)
        assert T.tanh(nd.zeros(1)).item() == 0.0
        np.testing.assert_allclose(T.softmax(nd.create([1.0, 1.0])).toNumpy(), [0.5, 0.5], rtol=1e-6)

    def test_exp_log_roundtrip(self):
        x = nd.create([0.5, 1.0, 2.0])
        assert T.log(T.exp(x)).equalsWithEps(x, 1e-4)

    def test_distances(self):
        a = nd.create([1.0, 0.0])
        b = nd.create([0.0, 1.0])
        assert T.euclideanDistance(a, b) == pytest.approx(np.sqrt(2))
        assert T.cosineSim(a, b) == pytest.approx(0.0)
        assert T.manhattanDistance(a, b) == 2.0

    def test_unitvec(self):
        v = T.unitVec(nd.create([3.0, 4.0]))
        assert v.norm2().item() == pytest.approx(1.0)


class TestInterop:
    def test_numpy_roundtrip(self):
        x = np.random.default_rng(0).normal(size=(3, 4)).astype(np.float32)
        a = nd.create(x)
        np.testing.assert_array_equal(a.toNumpy(), x)

    def test_jnp_consumes_ndarray(self):
        import jax.numpy as jnp
        a = nd.ones(2, 2)
        assert float(jnp.sum(a.buf())) == 4.0


class TestINDArraySurfaceLongTail:
    """INDArray long-tail methods (ref: org.nd4j.linalg.api.ndarray.INDArray
    — predicates, conversions, i-variant broadcasts, absolute reductions,
    distances, conditional replacement)."""

    def test_predicates_and_meta(self):
        from deeplearning4j_tpu.ndarray.ndarray import NDArray
        a = NDArray(np.arange(6, dtype="f4").reshape(2, 3))
        assert a.isSquare() is False and not a.isEmpty()
        assert NDArray(np.ones((3, 3))).isSquare()
        assert NDArray(np.ones((1, 5))).isRowVector()
        assert NDArray(np.ones((5, 1))).isColumnVector()
        assert a.isR() and not a.isZ()
        assert a.ordering() == "c" and a.offset() == 0
        assert a.stride() == (3, 1)
        assert not a.isAttached()

    def test_conversions(self):
        from deeplearning4j_tpu.ndarray.ndarray import NDArray
        a = NDArray(np.arange(6, dtype="f4").reshape(2, 3))
        assert a.toDoubleVector().dtype == np.float64
        assert a.toIntVector().tolist() == [0, 1, 2, 3, 4, 5]
        assert a.toFloatMatrix().shape == (2, 3)

    def test_inplace_broadcast_variants(self):
        from deeplearning4j_tpu.ndarray.ndarray import NDArray
        a = NDArray(np.ones((2, 3), dtype="f4"))
        a.addiRowVector(np.array([1., 2., 3.], dtype="f4"))
        np.testing.assert_allclose(a.toNumpy()[0], [2, 3, 4])
        a.muliColumnVector(np.array([2., 10.], dtype="f4"))
        np.testing.assert_allclose(a.toNumpy()[1], [20, 30, 40])

    def test_absolute_reductions_and_numbers(self):
        from deeplearning4j_tpu.ndarray.ndarray import NDArray
        a = NDArray(np.array([[-3., 1.], [2., -4.]], dtype="f4"))
        assert a.amaxNumber() == 4.0 and a.aminNumber() == 1.0
        assert float(a.asum().item()) == 10.0
        np.testing.assert_allclose(a.ameanNumber(), 2.5)
        np.testing.assert_allclose(a.norm2Number(), np.sqrt(30), rtol=1e-6)
        np.testing.assert_allclose(a.prodNumber(), 24.0)

    def test_distances(self):
        from deeplearning4j_tpu.ndarray.ndarray import NDArray
        a = NDArray(np.array([1., 2.], dtype="f4"))
        b = np.array([4., 6.], dtype="f4")
        assert a.distance1(b) == 7.0
        assert a.distance2(b) == 5.0
        assert a.squaredDistance(b) == 25.0

    def test_replace_where_and_get_where(self):
        from deeplearning4j_tpu.ndarray.ndarray import NDArray
        a = NDArray(np.array([-1., 2., -3., 4.], dtype="f4"))
        a.replaceWhere(np.zeros(4, dtype="f4"), ("lessthan", 0.0))
        np.testing.assert_allclose(a.toNumpy(), [0, 2, 0, 4])
        got = NDArray(np.array([1., 5., 2.], dtype="f4")).getWhere(
            None, ("greaterthan", 1.5))
        np.testing.assert_allclose(got.toNumpy(), [5., 2.])

    def test_rows_columns_subarray(self):
        from deeplearning4j_tpu.ndarray.ndarray import NDArray
        a = NDArray(np.arange(12, dtype="f4").reshape(3, 4))
        np.testing.assert_allclose(a.getRows(0, 2).toNumpy(),
                                   [[0, 1, 2, 3], [8, 9, 10, 11]])
        np.testing.assert_allclose(a.getColumns(1, 3).toNumpy(),
                                   [[1, 3], [5, 7], [9, 11]])
        np.testing.assert_allclose(a.subArray((1, 1), (2, 2)).toNumpy(),
                                   [[5, 6], [9, 10]])


class TestINDArrayTranche2:
    """Surface tranche 2 (ref: INDArray ordering/statistics/boolean tail)."""

    def _arr(self):
        from deeplearning4j_tpu.ndarray import factory as nd
        return nd.create([[3.0, 1.0, 2.0], [6.0, 5.0, 4.0]])

    def test_sort_family(self):
        a = self._arr()
        np.testing.assert_allclose(a.sort().toNumpy(),
                                   [[1, 2, 3], [4, 5, 6]])
        np.testing.assert_allclose(a.sort(ascending=False).toNumpy(),
                                   [[3, 2, 1], [6, 5, 4]])
        idx, vals = a.sortWithIndices()
        np.testing.assert_allclose(idx.toNumpy(), [[1, 2, 0], [2, 1, 0]])
        np.testing.assert_allclose(vals.toNumpy(), [[1, 2, 3], [4, 5, 6]])

    def test_median_percentile(self):
        a = self._arr()
        assert abs(a.medianNumber() - 3.5) < 1e-6
        np.testing.assert_allclose(a.median(1).toNumpy(), [2.0, 5.0])
        assert abs(a.percentileNumber(50) - 3.5) < 1e-6

    def test_boolean_reductions(self):
        a = self._arr()
        assert a.all() and a.any() and not a.none()
        assert a.countNonZero() == 6 and a.countZero() == 0
        assert bool(a.eps(a).all())

    def test_scalar_accessors_and_like(self):
        a = self._arr()
        assert a.getFloat(0, 0) == 3.0 and a.getLong(1, 2) == 4
        assert a.maxIndex() == 3 and a.minIndex() == 1
        assert a.like().sumNumber() == 0.0 and a.like().shape == a.shape

    def test_tensor_counts_and_inplace_scans(self):
        a = self._arr()
        assert a.vectorsAlongDimension(1) == 2
        assert a.tensorsAlongDimension(0, 1) == 1
        b = self._arr()
        b.cumsumi(1)
        np.testing.assert_allclose(b.toNumpy(), [[3, 4, 6], [6, 11, 15]])

    def test_reverse_vector_ops(self):
        from deeplearning4j_tpu.ndarray import factory as nd
        a = self._arr()
        v = nd.create([10.0, 20.0, 30.0])
        np.testing.assert_allclose(a.rsubRowVector(v).toNumpy(),
                                   [[7, 19, 28], [4, 15, 26]])
        c = nd.create([6.0, 12.0])
        np.testing.assert_allclose(a.rdivColumnVector(c).toNumpy(),
                                   [[2, 6, 3], [2, 2.4, 3]])


class TestFactoryTranche2:
    """Nd4j static surface tranche 2 (IO, structure, random, reductions)."""

    def test_npy_and_binary_io(self, tmp_path):
        from deeplearning4j_tpu.ndarray import factory as nd
        a = nd.rand(3, 4)
        p = str(tmp_path / "a.npy")
        nd.writeNumpy(a, p)
        back = nd.readNumpy(p)
        np.testing.assert_allclose(back.toNumpy(), a.toNumpy())
        p2 = str(tmp_path / "b.npy")
        nd.saveBinary(a, p2)
        np.testing.assert_allclose(nd.readBinary(p2).toNumpy(),
                                   a.toNumpy())

    def test_structure_statics(self):
        from deeplearning4j_tpu.ndarray import factory as nd
        a = nd.create([[1.0, 2.0], [3.0, 4.0]])
        assert nd.toFlattened(a, a).shape == (8,)
        assert nd.expandDims(a, 0).shape == (1, 2, 2)
        assert nd.tile(a, 2, 1).shape == (4, 2)
        assert nd.repeat(a, 2, axis=1).shape == (2, 4)
        np.testing.assert_allclose(nd.reverse(a, 0).toNumpy(),
                                   [[3, 4], [1, 2]])
        assert len(nd.split(a, 2, axis=0)) == 2
        piled = nd.pile(a, a, a)
        assert piled.shape == (3, 2, 2)
        torn = nd.tear(piled, 0)
        assert len(torn) == 3 and torn[0].shape == (2, 2)
        np.testing.assert_allclose(nd.kron(nd.eye(2), a).toNumpy()[0, :2],
                                   [1, 2])
        assert int(nd.argMax(a).item()) == 3

    def test_random_statics_reproducible(self):
        from deeplearning4j_tpu.ndarray import factory as nd
        nd.setSeed(99)
        a = nd.randomBernoulli(0.5, 100)
        b = nd.randomExponential(2.0, 1000)
        g = nd.randomGamma(3.0, 500)
        p = nd.randomPoisson(4.0, 500)
        bi = nd.randomBinomial(10, 0.3, 500)
        ch = nd.choice(nd.create([1.0, 2.0, 3.0]),
                       nd.create([0.2, 0.3, 0.5]), 50)
        assert 0.3 < float(a.meanNumber()) < 0.7
        assert 0.4 < float(b.meanNumber()) < 0.6        # mean 1/lam
        assert 2.5 < float(g.meanNumber()) < 3.5
        assert 3.5 < float(p.meanNumber()) < 4.5
        assert 2.5 < float(bi.meanNumber()) < 3.5       # n*p = 3
        assert ch.shape == (50,)
        nd.setSeed(99)
        a2 = nd.randomBernoulli(0.5, 100)
        np.testing.assert_allclose(a.toNumpy(), a2.toNumpy())

    def test_reduction_statics(self):
        from deeplearning4j_tpu.ndarray import factory as nd
        a = nd.create([[1.0, -2.0], [3.0, -4.0]])
        assert float(nd.max(a).item()) == 3.0
        assert float(nd.norm1(a).item()) == 10.0
        np.testing.assert_allclose(float(nd.norm2(a).item()),
                                   np.sqrt(30.0), rtol=1e-6)
        np.testing.assert_allclose(nd.std(a, 0).toNumpy(),
                                   np.std(a.toNumpy(), 0, ddof=1),
                                   rtol=1e-6)


class TestNDArrayIndexCompat:
    """ref: org.nd4j.linalg.indexing.{NDArrayIndex,BooleanIndexing}."""

    def test_get_with_index_objects(self):
        from deeplearning4j_tpu.ndarray import NDArrayIndex as I
        from deeplearning4j_tpu.ndarray import factory as nd
        a = nd.create(np.arange(24.0).reshape(4, 6))
        np.testing.assert_allclose(
            a.get(I.interval(0, 2), I.all()).toNumpy(),
            a.toNumpy()[0:2])
        np.testing.assert_allclose(
            a.get(I.point(3), I.interval(1, 4)).toNumpy(),
            a.toNumpy()[3, 1:4])
        # ND4J argument order: interval(begin, stride, end[, inclusive])
        np.testing.assert_allclose(
            a.get(I.interval(0, 2, 3, True), I.point(0)).toNumpy(),
            a.toNumpy()[0:4:2, 0])
        np.testing.assert_allclose(
            a.get(I.interval(1, 2, 6), I.point(0)).toNumpy(),
            a.toNumpy()[1:6:2, 0])
        assert a.get(I.newAxis(), I.all(), I.all()).shape == (1, 4, 6)
        np.testing.assert_allclose(
            a.get(I.indices(2, 0), I.all()).toNumpy(),
            a.toNumpy()[[2, 0]])

    def test_put_with_index_objects(self):
        from deeplearning4j_tpu.ndarray import NDArrayIndex as I
        from deeplearning4j_tpu.ndarray import factory as nd
        a = nd.zeros((3, 3))
        a.put((I.point(1), I.all()), 5.0)
        np.testing.assert_allclose(a.toNumpy()[1], 5.0)

    def test_boolean_indexing_statics(self):
        from deeplearning4j_tpu.ndarray import BooleanIndexing as B
        from deeplearning4j_tpu.ndarray import factory as nd
        a = nd.create([0.0, 3.0, -1.0, 3.0])
        assert B.or_(a, ("greaterThan", 2.0))
        assert B.and_(a, ("greaterThan", -2.0))        # every element > -2
        assert not B.and_(a, ("greaterThan", 2.0))     # 0.0 and -1.0 fail
        assert B.firstIndex(a, ("greaterThan", 2.0)) == 1
        assert B.lastIndex(a, ("greaterThan", 2.0)) == 3
        assert B.firstIndex(a, ("greaterThan", 99.0)) == -1


def test_executioner_facade():
    """ref: Nd4j.getExecutioner().exec(op) + setProfilingConfig."""
    from deeplearning4j_tpu.ndarray import factory as nd
    ex = nd.getExecutioner()
    out = ex.exec("relu", nd.create([-1.0, 2.0]))
    np.testing.assert_allclose(out.toNumpy(), [0.0, 2.0])
    vals, idx = ex.exec("top_k", nd.create([1.0, 9.0, 3.0]), k=2)
    np.testing.assert_allclose(vals.toNumpy(), [9.0, 3.0])
    from deeplearning4j_tpu.profiler.op_profiler import (OpProfiler,
                                                          ProfilerConfig)
    ex.setProfilingConfig(ProfilerConfig(op_timing=True))
    try:
        ex.exec("exp", nd.create([0.0, 1.0]))
        assert OpProfiler.get_instance().config.op_timing
    finally:
        ex.setProfilingConfig(ProfilerConfig())   # never leak the hook
    out2 = ex.exec("exp", nd.create([0.0, 1.0]))
    ex.commit(out2)                               # array-landing barrier
    cfg_copy = ex.profilingConfig()
    cfg_copy.op_timing = True                     # mutating the copy is inert
    from deeplearning4j_tpu.profiler.op_profiler import OpProfiler
    assert not OpProfiler.get_instance().config.op_timing
