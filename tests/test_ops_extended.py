"""Extended op-library tests (ref analog: libnd4j DeclarableOpsTests* for
the long-tail op groups — SURVEY N3)."""
import numpy as np
import pytest

import jax
import jax.numpy as jnp

import deeplearning4j_tpu.ops  # registers standard + extended
from deeplearning4j_tpu.ops.registry import exec_op, has as has_op


def _np(x):
    return np.asarray(x)


class TestElementwiseLongTail:
    def test_special_functions(self):
        x = jnp.asarray([0.5, 1.5, 2.5])
        np.testing.assert_allclose(_np(exec_op("expm1", x)), np.expm1(_np(x)),
                                   rtol=1e-6)
        np.testing.assert_allclose(_np(exec_op("log2", x)), np.log2(_np(x)),
                                   rtol=1e-6)
        np.testing.assert_allclose(_np(exec_op("lgamma", x)),
                                   [0.5723649, -0.1207822, 0.2846829],
                                   rtol=1e-5)
        np.testing.assert_allclose(
            _np(exec_op("atan2", jnp.asarray([1.0]), jnp.asarray([1.0]))),
            [np.pi / 4], rtol=1e-6)

    def test_reverse_forms(self):
        a, b = jnp.asarray([2.0, 4.0]), jnp.asarray([8.0, 8.0])
        np.testing.assert_allclose(_np(exec_op("rsub", a, b)), [6.0, 4.0])
        np.testing.assert_allclose(_np(exec_op("rdiv", a, b)), [4.0, 2.0])
        np.testing.assert_allclose(
            _np(exec_op("divide_no_nan", jnp.asarray([1.0, 2.0]),
                        jnp.asarray([0.0, 2.0]))), [0.0, 1.0])

    def test_monotonicity_predicates(self):
        assert bool(exec_op("is_non_decreasing", jnp.asarray([1, 1, 2])))
        assert not bool(exec_op("is_strictly_increasing",
                                jnp.asarray([1, 1, 2])))


class TestReductions:
    def test_absolute_reductions(self):
        x = jnp.asarray([[-3.0, 1.0], [2.0, -4.0]])
        assert float(exec_op("reduce_amax", x)) == 4.0
        assert float(exec_op("reduce_amin", x)) == 1.0
        np.testing.assert_allclose(float(exec_op("reduce_asum", x)), 10.0)
        np.testing.assert_allclose(float(exec_op("reduce_amean", x)), 2.5)
        assert int(exec_op("argamax", x, axis=None)) == 3
        assert int(exec_op("count_nonzero", jnp.asarray([0, 1, 2, 0]))) == 2
        np.testing.assert_allclose(
            float(exec_op("zero_fraction", jnp.asarray([0.0, 1.0]))), 0.5)

    def test_entropy_and_moments(self):
        p = jnp.asarray([0.5, 0.5])
        np.testing.assert_allclose(float(exec_op("entropy", p)),
                                   np.log(2), rtol=1e-6)
        np.testing.assert_allclose(float(exec_op("shannon_entropy", p)), 1.0,
                                   rtol=1e-6)
        mean, var = exec_op("moments", jnp.asarray([1.0, 2.0, 3.0]))
        assert float(mean) == 2.0
        np.testing.assert_allclose(float(var), 2.0 / 3.0, rtol=1e-6)

    def test_distances(self):
        a = jnp.asarray([1.0, 0.0])
        b = jnp.asarray([0.0, 1.0])
        np.testing.assert_allclose(float(exec_op("cosine_similarity", a, b)),
                                   0.0, atol=1e-6)
        np.testing.assert_allclose(
            float(exec_op("euclidean_distance", a, b)), np.sqrt(2), rtol=1e-6)
        np.testing.assert_allclose(float(exec_op("manhattan_distance", a, b)),
                                   2.0)
        assert int(exec_op("hamming_distance", jnp.asarray([1, 0, 1]),
                           jnp.asarray([1, 1, 0]))) == 2


class TestShapeIndex:
    def test_unique_and_listdiff(self):
        vals, inv = exec_op("unique", jnp.asarray([3, 1, 3, 2]))
        np.testing.assert_array_equal(_np(vals), [1, 2, 3])
        np.testing.assert_array_equal(_np(inv), [2, 0, 2, 1])
        vals, inv, counts = exec_op("unique_with_counts",
                                          jnp.asarray([3, 1, 3]))
        np.testing.assert_array_equal(_np(counts), [1, 2])
        out, idx = exec_op("listdiff", jnp.asarray([1, 2, 3, 4]),
                                 jnp.asarray([2, 4]))
        np.testing.assert_array_equal(_np(out), [1, 3])
        np.testing.assert_array_equal(_np(idx), [0, 2])

    def test_dynamic_partition_stitch_roundtrip(self):
        x = jnp.asarray([10.0, 20.0, 30.0, 40.0])
        parts = jnp.asarray([0, 1, 0, 1])
        p0, p1 = exec_op("dynamic_partition", x, parts, 2)
        np.testing.assert_array_equal(_np(p0), [10.0, 30.0])
        idx0 = jnp.asarray([0, 2])
        idx1 = jnp.asarray([1, 3])
        back = exec_op("dynamic_stitch", [idx0, idx1], [p0, p1])
        np.testing.assert_array_equal(_np(back), _np(x))

    def test_misc_shape_ops(self):
        np.testing.assert_array_equal(
            _np(exec_op("invert_permutation", jnp.asarray([2, 0, 1]))),
            [1, 2, 0])
        np.testing.assert_array_equal(
            _np(exec_op("bincount", jnp.asarray([0, 1, 1, 2]))), [1, 2, 1])
        h = exec_op("histogram_fixed_width", jnp.asarray([0.0, 0.1, 0.9]),
                    (0.0, 1.0), nbins=2)
        np.testing.assert_array_equal(_np(h), [2, 1])
        assert int(exec_op("searchsorted", jnp.asarray([1.0, 3.0, 5.0]),
                           jnp.asarray(4.0))) == 2
        np.testing.assert_array_equal(
            _np(exec_op("roll", jnp.asarray([1, 2, 3]), 1, axis=0)),
            [3, 1, 2])


class TestSegmentScatter:
    def test_segment_reductions(self):
        data = jnp.asarray([1.0, 2.0, 3.0, 4.0])
        ids = jnp.asarray([0, 0, 1, 1])
        np.testing.assert_allclose(
            _np(exec_op("segment_max", data, ids)), [2.0, 4.0])
        np.testing.assert_allclose(
            _np(exec_op("segment_mean", data, ids)), [1.5, 3.5])
        np.testing.assert_allclose(
            _np(exec_op("segment_prod", data, ids)), [2.0, 12.0])
        np.testing.assert_allclose(
            _np(exec_op("unsorted_segment_sqrt_n", data, ids, 2)),
            [3.0 / np.sqrt(2), 7.0 / np.sqrt(2)], rtol=1e-6)

    def test_scatter_variants(self):
        ref = jnp.ones((4,))
        idx = jnp.asarray([1, 3])
        upd = jnp.asarray([5.0, 7.0])
        np.testing.assert_allclose(_np(exec_op("scatter_sub", ref, idx, upd)),
                                   [1, -4, 1, -6])
        np.testing.assert_allclose(_np(exec_op("scatter_max", ref, idx, upd)),
                                   [1, 5, 1, 7])
        out = exec_op("scatter_nd", jnp.asarray([[0], [2]]),
                      jnp.asarray([1.0, 2.0]), (3,))
        np.testing.assert_allclose(_np(out), [1.0, 0.0, 2.0])
        out = exec_op("scatter_nd_update", jnp.zeros((2, 2)),
                      jnp.asarray([[0, 1]]), jnp.asarray([9.0]))
        np.testing.assert_allclose(_np(out), [[0, 9], [0, 0]])


class TestBitwise:
    def test_bit_ops(self):
        a = jnp.asarray([0b1100], jnp.int32)
        b = jnp.asarray([0b1010], jnp.int32)
        assert int(exec_op("bitwise_and", a, b)[0]) == 0b1000
        assert int(exec_op("bitwise_xor", a, b)[0]) == 0b0110
        assert int(exec_op("shift_bits", a, 1)[0]) == 0b11000
        assert int(exec_op("rshift_bits", a, 2)[0]) == 0b11
        assert int(exec_op("bits_hamming_distance", a, b)) == 2
        c = exec_op("cyclic_shift_bits", jnp.asarray([1], jnp.int32), 33)
        assert int(c[0]) == 2


class TestImage:
    def test_resize_variants(self):
        x = jnp.arange(16.0).reshape(1, 4, 4, 1)
        for op in ("resize_nearest_neighbor", "resize_bicubic",
                   "resize_area"):
            out = exec_op(op, x, (2, 2))
            assert out.shape == (1, 2, 2, 1)

    def test_rgb_hsv_roundtrip(self):
        rng = np.random.default_rng(0)
        x = jnp.asarray(rng.random((2, 3, 3, 3)), jnp.float32)
        back = exec_op("hsv_to_rgb", exec_op("rgb_to_hsv", x))
        np.testing.assert_allclose(_np(back), _np(x), atol=1e-5)

    def test_rgb_yuv_roundtrip_and_grayscale(self):
        rng = np.random.default_rng(1)
        x = jnp.asarray(rng.random((1, 2, 2, 3)), jnp.float32)
        back = exec_op("yuv_to_rgb", exec_op("rgb_to_yuv", x))
        np.testing.assert_allclose(_np(back), _np(x), atol=1e-5)
        g = exec_op("rgb_to_grayscale", x)
        assert g.shape == (1, 2, 2, 1)

    def test_adjustments(self):
        x = jnp.full((1, 2, 2, 3), 0.5)
        out = exec_op("adjust_contrast", x, 2.0)
        np.testing.assert_allclose(_np(out), _np(x), atol=1e-6)  # mean image
        out = exec_op("adjust_saturation", x, 0.0)
        assert out.shape == x.shape

    def test_crop_and_resize(self):
        x = jnp.arange(16.0).reshape(1, 4, 4, 1)
        out = exec_op("crop_and_resize", x,
                      jnp.asarray([[0.0, 0.0, 1.0, 1.0]]),
                      jnp.asarray([0]), (4, 4))
        np.testing.assert_allclose(_np(out), _np(x), atol=1e-5)
        half = exec_op("crop_and_resize", x,
                       jnp.asarray([[0.0, 0.0, 0.0, 1.0]]),
                       jnp.asarray([0]), (1, 4))
        np.testing.assert_allclose(_np(half)[0, 0, :, 0], [0, 1, 2, 3],
                                   atol=1e-5)

    def test_extract_image_patches(self):
        x = jnp.arange(16.0).reshape(1, 4, 4, 1)
        out = exec_op("extract_image_patches", x, (2, 2), (2, 2))
        assert out.shape == (1, 2, 2, 4)
        np.testing.assert_allclose(_np(out)[0, 0, 0], [0, 1, 4, 5])


class TestLinalgExtended:
    def test_matrix_ops(self):
        d = jnp.asarray([1.0, 2.0])
        np.testing.assert_allclose(_np(exec_op("matrix_diag", d)),
                                   [[1, 0], [0, 2]])
        m = jnp.asarray([[1.0, 5.0], [5.0, 2.0]])
        out = exec_op("matrix_set_diag", m, jnp.asarray([9.0, 9.0]))
        np.testing.assert_allclose(_np(out), [[9, 5], [5, 9]])
        x = jnp.asarray([[2.0, 0.0], [0.0, 3.0]])
        np.testing.assert_allclose(float(exec_op("logdet", x)), np.log(6),
                                   rtol=1e-6)
        w, v = exec_op("self_adjoint_eig", x)
        np.testing.assert_allclose(sorted(_np(w)), [2.0, 3.0], rtol=1e-6)

    def test_batched_gemm(self):
        a = jnp.ones((3, 2, 4))
        b = jnp.ones((3, 4, 5))
        assert exec_op("batched_gemm", a, b).shape == (3, 2, 5)


class TestLossOps:
    def test_huber_and_log_loss(self):
        lab = jnp.asarray([0.0, 1.0])
        pred = jnp.asarray([0.0, 3.0])
        np.testing.assert_allclose(float(exec_op("huber_loss", lab, pred,
                                                 delta=1.0)),
                                   (0.0 + (2.0 - 0.5)) / 2, rtol=1e-6)
        p = jnp.asarray([0.9, 0.1])
        ll = float(exec_op("log_loss", jnp.asarray([1.0, 0.0]), p))
        np.testing.assert_allclose(ll, -np.log(0.9), rtol=1e-4)

    def test_hinge_and_cosine(self):
        lab = jnp.asarray([1.0])
        logits = jnp.asarray([0.3])
        np.testing.assert_allclose(float(exec_op("hinge_loss", lab, logits)),
                                   0.7, rtol=1e-6)
        a = jnp.asarray([[1.0, 0.0]])
        np.testing.assert_allclose(
            float(exec_op("cosine_distance_loss", a, a)), 0.0, atol=1e-6)

    def test_weighted_ce_matches_manual(self):
        labels = jnp.asarray([1.0, 0.0])
        logits = jnp.asarray([0.5, -0.5])
        pos_w = 2.0
        out = exec_op("weighted_cross_entropy_with_logits", labels, logits,
                      pos_w)
        # manual: (1-z)x + (1+(w-1)z)·log(1+exp(-|x|)) + max(-x,0)
        expect = ((1 - labels) * logits
                  + (1 + (pos_w - 1) * labels)
                  * (np.log1p(np.exp(-np.abs(logits)))
                     + np.maximum(-logits, 0)))
        np.testing.assert_allclose(_np(out), expect, rtol=1e-6)


class TestRnnLayerOps:
    def test_lstm_layer_matches_cell_loop(self):
        rng = np.random.default_rng(0)
        n, t, ci, h = 2, 4, 3, 5
        x = jnp.asarray(rng.normal(size=(n, t, ci)), jnp.float32)
        w = jnp.asarray(rng.normal(size=(ci + h, 4 * h)) * 0.1, jnp.float32)
        b = jnp.zeros((4 * h,), jnp.float32)
        h0 = jnp.zeros((n, h), jnp.float32)
        c0 = jnp.zeros((n, h), jnp.float32)
        ys, (hN, cN) = exec_op("lstm_layer", x, h0, c0, w, b)
        assert ys.shape == (n, t, h)
        # manual loop over the cell op
        hh, cc = h0, c0
        for i in range(t):
            hh, cc = exec_op("lstm_cell", x[:, i], hh, cc, w, b,
                                   forget_bias=0.0)
        np.testing.assert_allclose(_np(ys[:, -1]), _np(hh), rtol=1e-5)
        np.testing.assert_allclose(_np(cN), _np(cc), rtol=1e-5)

    def test_gru_layer_shapes(self):
        rng = np.random.default_rng(1)
        n, t, ci, h = 2, 3, 4, 6
        x = jnp.asarray(rng.normal(size=(n, t, ci)), jnp.float32)
        w_rz = jnp.asarray(rng.normal(size=(ci + h, 2 * h)) * 0.1, jnp.float32)
        w_h = jnp.asarray(rng.normal(size=(ci + h, h)) * 0.1, jnp.float32)
        ys, hN = exec_op("gru_layer", x, jnp.zeros((n, h)), w_rz, w_h,
                               jnp.zeros((2 * h,)), jnp.zeros((h,)))
        assert ys.shape == (n, t, h) and hN.shape == (n, h)


class TestRandomExtended:
    def test_distributions(self):
        key = jax.random.key(0)
        g = exec_op("random_gamma", key, 2.0, shape=(1000,))
        assert 1.0 < float(jnp.mean(g)) < 3.0
        p = exec_op("random_poisson", key, 3.0, shape=(1000,))
        assert 2.0 < float(jnp.mean(p)) < 4.0
        e = exec_op("random_exponential", key, 2.0, (1000,))
        assert 0.3 < float(jnp.mean(e)) < 0.8
        s = exec_op("random_shuffle", key, jnp.arange(10))
        assert sorted(_np(s).tolist()) == list(range(10))
        m = exec_op("random_categorical", key,
                    jnp.log(jnp.asarray([[0.99, 0.01]])), 50)
        assert float(jnp.mean(m.astype(jnp.float32))) < 0.2


def test_alias_coverage():
    """TF-style aliases resolve (the importer mapping surface)."""
    for name in ["Expm1", "SegmentMax", "ScatterNd", "BitwiseAnd",
                 "ResizeNearestNeighbor", "CropAndResize", "AdjustContrastV2",
                 "RgbToHsv", "BatchMatMulV2", "HuberLoss", "LSTMLayer",
                 "UniqueWithCounts", "DynamicStitch", "InvertPermutation"]:
        assert has_op(name), name


class TestSpectralAndLinalgTranche:
    def test_fft_round_trip(self):
        x = jnp.asarray(np.random.default_rng(0).normal(size=(4, 16)),
                        jnp.float32)
        back = exec_op("ifft", exec_op("fft", x))
        np.testing.assert_allclose(_np(back.real), _np(x), atol=1e-5)
        r = exec_op("rfft", x)
        assert r.shape == (4, 9)
        back_r = exec_op("irfft", r)
        np.testing.assert_allclose(_np(back_r), _np(x), atol=1e-5)

    def test_ctc_loss_learns_alignment(self):
        import jax
        import optax

        rng = np.random.default_rng(0)
        B, T, C, S = 2, 8, 5, 3
        labels = jnp.asarray(rng.integers(1, C, (B, S)), jnp.int32)
        logit_len = jnp.asarray([T, T])
        label_len = jnp.asarray([S, S])
        logits = jnp.asarray(rng.normal(size=(B, T, C)) * 0.1, jnp.float32)

        def loss_fn(lg):
            lp = jax.nn.log_softmax(lg, axis=-1)
            return jnp.mean(exec_op("ctc_loss", lp, labels, logit_len,
                                    label_len))

        l0 = float(loss_fn(logits))
        g = jax.jit(jax.grad(loss_fn))
        for _ in range(60):
            logits = logits - 0.5 * g(logits)
        assert float(loss_fn(logits)) < l0 * 0.3

    def test_linalg_tranche(self):
        a = jnp.asarray([[2.0, 0.0], [1.0, 3.0]])
        np.testing.assert_allclose(
            _np(exec_op("matrix_power", a, 2)), _np(a @ a), rtol=1e-6)
        pinv = exec_op("pinv", a)
        np.testing.assert_allclose(_np(pinv @ a), np.eye(2), atol=1e-5)
        assert int(exec_op("matrix_rank", a)) == 2
        k = exec_op("kron", jnp.eye(2), a)
        assert k.shape == (4, 4)
        np.testing.assert_allclose(
            _np(exec_op("trilu", jnp.ones((3, 3)), upper=False)),
            np.tril(np.ones((3, 3))))
        np.testing.assert_allclose(
            float(exec_op("norm", a, ord="fro")),
            float(np.linalg.norm(np.asarray(a))), rtol=1e-6)


def test_norm_op_stats_survive_bf16_offset_inputs():
    """One-pass moments must accumulate in f32 for half inputs: bf16
    activations at mean 30/std 0.5 cancel to variance 0 in bf16 (vs 0.25
    true) — stats f32-accumulated, outputs back in the op's input dtype
    (TF half-precision norm semantics)."""
    import numpy as np
    import jax.numpy as jnp
    from deeplearning4j_tpu.ops.registry import exec_op

    rng = np.random.default_rng(0)
    base = rng.normal(30.0, 0.5, (32, 24)).astype(np.float32)
    xb = jnp.asarray(base, jnp.bfloat16)
    true_var = float(np.var(np.asarray(xb, np.float32), axis=None))

    m, v = exec_op("moments", xb, axes=(0, 1))
    assert m.dtype == jnp.bfloat16 and v.dtype == jnp.bfloat16
    assert abs(float(v) - true_var) / true_var < 0.05, (float(v), true_var)

    y = exec_op("layer_norm", xb, jnp.ones((24,), jnp.bfloat16),
                jnp.zeros((24,), jnp.bfloat16))
    assert y.dtype == jnp.bfloat16
    yf = np.asarray(y, np.float32)
    # a collapsed variance would blow the normalized scale up ~sqrt(1/eps)
    assert np.abs(yf).max() < 10.0, np.abs(yf).max()


def test_moments_integer_input_keeps_float_statistics():
    """ADVICE r5: the cast back to x.dtype applies only to INEXACT inputs
    — integer x would truncate mean/var (mean([0,1]) -> 0) otherwise."""
    import jax.numpy as jnp
    from deeplearning4j_tpu.ops.registry import exec_op

    m, v = exec_op("moments", jnp.asarray([0, 1, 2, 3], jnp.int32))
    assert jnp.issubdtype(m.dtype, jnp.floating)
    assert jnp.issubdtype(v.dtype, jnp.floating)
    assert float(m) == 1.5 and float(v) == 1.25
    # inexact inputs keep the cast-back contract
    mb, vb = exec_op("moments", jnp.asarray([0.0, 1.0], jnp.bfloat16))
    assert mb.dtype == jnp.bfloat16 and vb.dtype == jnp.bfloat16
