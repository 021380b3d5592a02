"""SameDiff-equivalent graph engine tests (ref test model: SURVEY.md §4 —
autodiff correctness via finite-difference gradcheck, whole-graph exec)."""
import os

import numpy as np
import pytest

import jax
import jax.numpy as jnp

from deeplearning4j_tpu.autodiff.samediff import (
    SameDiff, TrainingConfig, VariableType)


class TestGraphBuild:
    def test_variables_and_ops(self):
        sd = SameDiff.create()
        x = sd.placeholder("x", (2, 3))
        w = sd.var("w", (3, 4), init=np.ones((3, 4), np.float32))
        b = sd.var("b", init=np.zeros((4,), np.float32))
        z = x.mmul(w) + b
        out = sd.nn.softmax(z).rename("out")
        assert sd.has_variable("out")
        assert out.shape == (2, 4)
        assert x.var_type == VariableType.PLACEHOLDER
        assert w.var_type == VariableType.VARIABLE
        assert len(sd.ops()) == 3

    def test_unique_names(self):
        sd = SameDiff.create()
        a = sd.constant(1.0, "c")
        b = sd.constant(2.0, "c")
        assert a.name != b.name

    def test_shape_inference(self):
        sd = SameDiff.create()
        x = sd.placeholder("x", (4, 8))
        y = x.reshape(2, 16)
        assert y.shape == (2, 16)
        z = y.sum(1)
        assert z.shape == (2,)

    def test_summary(self):
        sd = SameDiff.create()
        x = sd.placeholder("x", (2, 2))
        (x * 2.0).rename("y")
        s = sd.summary()
        assert "PLACEHOLDER" in s and "mul" in s


class TestExec:
    def test_forward(self):
        sd = SameDiff.create()
        x = sd.placeholder("x", (2, 3))
        w = sd.var("w", init=np.arange(12, dtype=np.float32).reshape(3, 4))
        y = x.mmul(w).rename("y")
        xin = np.ones((2, 3), np.float32)
        out = sd.output({"x": xin}, ["y"])["y"]
        np.testing.assert_allclose(np.asarray(out), xin @ np.arange(12).reshape(3, 4),
                                   rtol=1e-6)

    def test_eval_and_cache(self):
        sd = SameDiff.create()
        x = sd.placeholder("x", (2,))
        y = (x * 3.0).rename("y")
        r1 = y.eval({"x": np.array([1.0, 2.0], np.float32)})
        r2 = y.eval({"x": np.array([2.0, 4.0], np.float32)})
        np.testing.assert_allclose(np.asarray(r1), [3, 6])
        np.testing.assert_allclose(np.asarray(r2), [6, 12])
        assert len(sd._compiled_cache) == 1  # same signature → one executable

    def test_default_outputs_are_leaves(self):
        sd = SameDiff.create()
        x = sd.placeholder("x", (2,))
        (x * 2.0 + 1.0).rename("out")
        res = sd.output({"x": np.zeros(2, np.float32)})
        assert list(res.keys()) == ["out"]

    def test_missing_placeholder_raises(self):
        sd = SameDiff.create()
        x = sd.placeholder("x", (2,))
        (x * 2.0).rename("y")
        with pytest.raises(ValueError, match="missing placeholders"):
            sd.output({}, ["y"])

    def test_getitem(self):
        sd = SameDiff.create()
        x = sd.placeholder("x", (4, 6))
        y = x[1:3, 2].rename("y")
        xin = np.arange(24, dtype=np.float32).reshape(4, 6)
        out = sd.output({"x": xin}, "y")["y"]
        np.testing.assert_allclose(np.asarray(out), xin[1:3, 2])

    def test_multi_output_op(self):
        sd = SameDiff.create()
        x = sd.placeholder("x", (3, 3))
        q, r = sd.linalg.qr(x)
        xin = np.random.default_rng(0).normal(size=(3, 3)).astype(np.float32)
        res = sd.output({"x": xin}, [q.name, r.name])
        np.testing.assert_allclose(np.asarray(res[q.name]) @ np.asarray(res[r.name]),
                                   xin, atol=1e-4)

    def test_random_deterministic_per_seed(self):
        sd = SameDiff.create()
        r = sd.random.normal(0.0, 1.0, (4,)).rename("r")
        a = sd.output({}, "r", rng_seed=7)["r"]
        b = sd.output({}, "r", rng_seed=7)["r"]
        c = sd.output({}, "r", rng_seed=8)["r"]
        np.testing.assert_array_equal(np.asarray(a), np.asarray(b))
        assert not np.allclose(np.asarray(a), np.asarray(c))

    def test_lambda_op(self):
        sd = SameDiff.create()
        x = sd.placeholder("x", (3,))
        y = sd.lambda_op(lambda a: jnp.flip(a) * 2.0, x).rename("y")
        out = sd.output({"x": np.array([1., 2., 3.], np.float32)}, "y")["y"]
        np.testing.assert_allclose(np.asarray(out), [6, 4, 2])


class TestGradients:
    def test_grad_matches_finite_diff(self):
        sd = SameDiff.create()
        x = sd.placeholder("x", (4, 3))
        w = sd.var("w", init=np.random.default_rng(0).normal(
            size=(3, 2)).astype(np.float32))
        b = sd.var("b", init=np.zeros(2, np.float32))
        pred = sd.nn.tanh(x.mmul(w) + b)
        loss = (pred * pred).mean().rename("loss")
        sd.set_loss_variables("loss")
        xin = np.random.default_rng(1).normal(size=(4, 3)).astype(np.float32)
        grads = sd.calculate_gradients({"x": xin})
        assert set(grads) == {"w", "b"}

        # finite differences on w
        w0 = np.asarray(sd.get_variable("w").get_arr()).copy()
        eps = 1e-3
        fd = np.zeros_like(w0)
        for i in range(w0.shape[0]):
            for j in range(w0.shape[1]):
                for s, sign in ((eps, 1), (-eps, -1)):
                    wp = w0.copy(); wp[i, j] += s
                    sd.get_variable("w").set_arr(wp)
                    l = float(sd.output({"x": xin}, "loss")["loss"])
                    fd[i, j] += sign * l
        fd /= (2 * eps)
        sd.get_variable("w").set_arr(w0)
        np.testing.assert_allclose(np.asarray(grads["w"]), fd, atol=1e-2)

    def test_fit_linear_regression(self):
        rng = np.random.default_rng(0)
        X = rng.normal(size=(256, 3)).astype(np.float32)
        true_w = np.array([[1.5], [-2.0], [0.5]], np.float32)
        Y = X @ true_w + 0.3

        sd = SameDiff.create()
        x = sd.placeholder("x", (None, 3))
        y = sd.placeholder("y", (None, 1))
        w = sd.var("w", init=np.zeros((3, 1), np.float32))
        b = sd.var("b", init=np.zeros((1,), np.float32))
        pred = x.mmul(w) + b
        sd.loss.mse(y, pred).rename("loss")
        sd.set_loss_variables("loss")

        from deeplearning4j_tpu.optim.updaters import Adam
        sd.set_training_config(TrainingConfig(
            updater=Adam(0.05),
            data_set_feature_mapping=["x"], data_set_label_mapping=["y"]))

        from deeplearning4j_tpu.data.dataset import DataSet
        ds = DataSet(X, Y)
        losses = sd.fit([ds] * 50, epochs=4)
        assert losses[-1] < 1e-2
        np.testing.assert_allclose(np.asarray(sd.get_variable("w").get_arr()),
                                   true_w, atol=0.05)
        np.testing.assert_allclose(np.asarray(sd.get_variable("b").get_arr()),
                                   [0.3], atol=0.05)

    def test_l2_regularization_changes_loss(self):
        sd = SameDiff.create()
        x = sd.placeholder("x", (2, 2))
        w = sd.var("w", init=np.ones((2, 2), np.float32))
        (x.mmul(w)).mean().rename("loss")
        sd.set_loss_variables("loss")
        from deeplearning4j_tpu.optim.updaters import Sgd
        sd.set_training_config(TrainingConfig(
            updater=Sgd(0.0), l2=1.0,
            data_set_feature_mapping=["x"], data_set_label_mapping=[]))
        from deeplearning4j_tpu.data.dataset import DataSet
        losses = sd.fit([DataSet(np.zeros((2, 2), np.float32), None)], epochs=1)
        assert abs(losses[0] - 4.0) < 1e-5  # pure L2: sum(w^2)=4


class TestSerialization:
    def test_save_load_roundtrip(self, tmp_path):
        sd = SameDiff.create()
        x = sd.placeholder("x", (2, 3))
        w = sd.var("w", init=np.random.default_rng(0).normal(
            size=(3, 4)).astype(np.float32))
        sd.nn.softmax(x.mmul(w)).rename("out")
        sd.set_loss_variables("out")
        path = str(tmp_path / "model.sdz")
        sd.save(path)

        sd2 = SameDiff.load(path)
        xin = np.random.default_rng(1).normal(size=(2, 3)).astype(np.float32)
        a = sd.output({"x": xin}, "out")["out"]
        b = sd2.output({"x": xin}, "out")["out"]
        np.testing.assert_allclose(np.asarray(a), np.asarray(b), rtol=1e-6)
        assert sd2._loss_variables == ["out"]

    def test_lambda_not_serializable(self, tmp_path):
        sd = SameDiff.create()
        x = sd.placeholder("x", (2,))
        sd.lambda_op(lambda a: a * 2, x)
        with pytest.raises(ValueError, match="lambda"):
            sd.save(str(tmp_path / "m.sdz"))


class TestNamespaces:
    def test_cnn_ops(self):
        sd = SameDiff.create()
        x = sd.placeholder("x", (1, 8, 8, 3))
        w = sd.var("w", init=np.random.default_rng(0).normal(
            size=(3, 3, 3, 4)).astype(np.float32) * 0.1)
        h = sd.cnn.conv2d(x, w, padding="SAME")
        p = sd.cnn.max_pooling2d(h, kernel=(2, 2), strides=(2, 2)).rename("p")
        assert p.shape == (1, 4, 4, 4)
        out = sd.output({"x": np.ones((1, 8, 8, 3), np.float32)}, "p")["p"]
        assert out.shape == (1, 4, 4, 4)

    def test_rnn_cell(self):
        sd = SameDiff.create()
        B, I, H = 2, 3, 4
        x = sd.placeholder("x", (B, I))
        h = sd.constant(np.zeros((B, H), np.float32), "h0")
        c = sd.constant(np.zeros((B, H), np.float32), "c0")
        w = sd.var("w", init=np.random.default_rng(0).normal(
            size=(I + H, 4 * H)).astype(np.float32) * 0.1)
        b = sd.var("b", init=np.zeros(4 * H, np.float32))
        h1, c1 = sd.rnn.lstm_cell(x, h, c, w, b)
        res = sd.output({"x": np.ones((B, I), np.float32)}, [h1.name, c1.name])
        assert res[h1.name].shape == (B, H)

    def test_loss_namespace(self):
        sd = SameDiff.create()
        labels = sd.placeholder("labels", (4, 3))
        logits = sd.placeholder("logits", (4, 3))
        l = sd.loss.softmax_cross_entropy(labels, logits).rename("l")
        rng = np.random.default_rng(0)
        lab = np.eye(3, dtype=np.float32)[rng.integers(0, 3, 4)]
        log = rng.normal(size=(4, 3)).astype(np.float32)
        out = float(sd.output({"labels": lab, "logits": log}, "l")["l"])
        # reference value via numpy
        e = np.exp(log - log.max(-1, keepdims=True))
        p = e / e.sum(-1, keepdims=True)
        ref = -(lab * np.log(p)).sum(-1).mean()
        assert abs(out - ref) < 1e-5


class TestControlFlow:
    """ref: SameDiff#ifCond/#whileLoop (SURVEY control-flow gap, VERDICT
    weak #8) — lax.cond/lax.while_loop composite ops with nested graphs."""

    def test_if_cond_both_branches(self):
        sd = SameDiff.create()
        x = sd.placeholder("x", (3,), np.float32)
        out = sd.if_cond(x.sum() > 0.0, lambda s, a: a * 2.0,
                         lambda s, a: a - 1.0, x).rename("out")
        pos = sd.output({"x": np.array([1., 2., 3.], "f4")}, "out")["out"]
        neg = sd.output({"x": np.array([-1., -2., -3.], "f4")}, "out")["out"]
        assert np.allclose(pos, [2., 4., 6.])
        assert np.allclose(neg, [-2., -3., -4.])

    def test_dynamic_dim_placeholder_keeps_dtype_through_chain(self):
        """Ops downstream of a dynamic-dim placeholder must infer their
        DTYPE (and rank) even though extents are unknown — a bool loop
        condition built from chained ops used to silently default to f32
        and fail while_loop's type check (round-4 Loop-import bug)."""
        sd = SameDiff.create()
        x = sd.placeholder("x", (None, 4), np.float32)
        a = sd._op("less", x, sd.constant(np.float32(0.0)))
        b = sd._op("boolean_and", a, a)          # one op DEEPER than x
        assert np.dtype(a.dtype) == np.bool_
        assert np.dtype(b.dtype) == np.bool_
        assert len(b.shape) == 2 and b.shape[0] is None

    def test_if_cond_shape_mismatch_raises(self):
        sd = SameDiff.create()
        x = sd.placeholder("x", (3,), np.float32)
        with pytest.raises(ValueError, match="matching"):
            sd.if_cond(x.sum() > 0.0, lambda s, a: a.sum(),
                       lambda s, a: a * 1.0, x)

    def test_while_loop_accumulates(self):
        sd = SameDiff.create()
        i0 = sd.constant(np.int32(0), name="i0")
        a0 = sd.constant(np.float32(0.0), name="a0")
        _, acc = sd.while_loop(lambda s, i, a: i < 10,
                               lambda s, i, a: (i + 1, a + 2.0), i0, a0)
        acc.rename("acc")
        assert float(sd.output({}, "acc")["acc"]) == 20.0

    def test_control_flow_serialization_roundtrip(self, tmp_path):
        sd = SameDiff.create()
        x = sd.placeholder("x", (3,), np.float32)
        sd.if_cond(x.sum() > 0.0, lambda s, a: a * 2.0,
                   lambda s, a: a - 1.0, x).rename("out")
        p = str(tmp_path / "cf.zip")
        sd.save(p)
        sd2 = SameDiff.load(p)
        feed = {"x": np.array([1., 2., 3.], "f4")}
        assert np.allclose(sd2.output(feed, "out")["out"],
                           sd.output(feed, "out")["out"])

    def test_gradient_flows_through_cond(self):
        from deeplearning4j_tpu.optim.updaters import Adam
        sd = SameDiff.create()
        x = sd.placeholder("x", (2,), np.float32)
        w = sd.var("w", init=np.ones(2, np.float32))
        sd.if_cond(x.sum() > 0, lambda s, a, ww: (a * ww).sum(),
                   lambda s, a, ww: (a * ww * 2.0).sum(), x, w).rename("loss")
        sd.set_loss_variables("loss")
        sd.set_training_config(TrainingConfig(
            updater=Adam(0.1), data_set_feature_mapping=["x"]))
        losses = sd.fit({"x": np.array([1., 1.], "f4")}, epochs=3)
        assert losses[-1] < losses[0]

    def test_while_loop_dtype_mismatch_raises(self):
        sd = SameDiff.create()
        i0 = sd.constant(np.int32(9), name="i0")
        with pytest.raises(ValueError, match="preserve"):
            sd.while_loop(lambda s, i: i > 0, lambda s, i: i / 2.0, i0)


class TestBitwiseAndImageNamespaces:
    """SDBitwise / SDImage namespace parity (ref: nd4j SDBitwise, SDImage)."""

    def test_bitwise_ops(self):
        sd = SameDiff.create()
        a = sd.constant(np.array([0b1100], np.int32), name="a")
        b = sd.constant(np.array([0b1010], np.int32), name="b")
        sd.bitwise.and_(a, b).rename("and")
        sd.bitwise.xor(a, b).rename("xor")
        sd.bitwise.left_shift(a, 1).rename("shl")
        out = sd.output({}, ["and", "xor", "shl"])
        assert int(out["and"][0]) == 0b1000
        assert int(out["xor"][0]) == 0b0110
        assert int(out["shl"][0]) == 0b11000

    def test_image_ops(self):
        sd = SameDiff.create()
        x = sd.placeholder("x", (1, 4, 4, 3))
        sd.image.resize_bilinear(x, 2, 2).rename("small")
        sd.image.rgb_to_hsv(x).rename("hsv")
        img = np.random.default_rng(0).random((1, 4, 4, 3)).astype(np.float32)
        out = sd.output({"x": img}, ["small", "hsv"])
        assert out["small"].shape == (1, 2, 2, 3)
        assert out["hsv"].shape == (1, 4, 4, 3)


def test_sd_evaluate_classification():
    """SameDiff#evaluate parity: iterator → Evaluation over a graph output."""
    from deeplearning4j_tpu.autodiff.samediff import TrainingConfig
    from deeplearning4j_tpu.data.dataset import DataSet
    from deeplearning4j_tpu.data.iterators import ListDataSetIterator
    from deeplearning4j_tpu.optim.updaters import Adam

    sd = SameDiff.create()
    x = sd.placeholder("x", (None, 2))
    w = sd.var("w", init=np.asarray([[4.0, -4.0], [0.0, 0.0]], np.float32))
    probs = sd.nn.softmax(x.mmul(w)).rename("probs")
    sd.set_training_config(TrainingConfig(
        updater=Adam(1e-2), data_set_feature_mapping=["x"],
        data_set_label_mapping=["label"], loss_variables=[]))

    rng = np.random.default_rng(0)
    X = rng.normal(size=(64, 2)).astype(np.float32)
    Y = np.eye(2, dtype=np.float32)[(X[:, 0] > 0).astype(int)]
    # w maps x0>0 → class 0; these labels say class 1 → accuracy ~0
    it = ListDataSetIterator([DataSet(X[i:i + 16], Y[i:i + 16])
                              for i in range(0, 64, 16)])
    ev = sd.evaluate(it, "probs")
    assert ev.accuracy() < 0.2
    # aligned labels → near-perfect
    Y2 = np.eye(2, dtype=np.float32)[(X[:, 0] <= 0).astype(int)]
    it2 = ListDataSetIterator([DataSet(X, Y2)])
    ev2 = sd.evaluate(it2, "probs")
    assert ev2.accuracy() > 0.95


def test_namespace_registry_fallthrough():
    """Every op namespace reaches every registered op by name (the
    reference codegens ~200 methods per namespace, SURVEY E8; here the
    registry is the single source)."""
    sd = SameDiff.create()
    x = sd.constant(np.asarray([[1.0, -2.0], [3.0, -4.0]], np.float32),
                    name="x")
    for ns, op, args, kwargs in [
            ("nn", "log_sigmoid", (x,), {}),
            ("cnn", "upsampling3d", (sd.constant(
                np.ones((1, 2, 2, 2, 3), np.float32)),), {"scale": 2}),
            ("linalg", "matrix_band_part", (x,), {"lower": 0, "upper": 0}),
            ("image", "rgb_to_yiq", (sd.constant(
                np.ones((2, 2, 3), np.float32)),), {}),
            ("math", "zeta", (sd.constant(np.asarray(2.0, np.float32)),
                              sd.constant(np.asarray(1.0, np.float32))), {}),
            ("rnn", "sru", (sd.constant(np.ones((1, 3, 2), np.float32)),
                            sd.constant(np.zeros((1, 2), np.float32)),
                            sd.constant(np.ones((2, 6), np.float32) * 0.1),
                            sd.constant(np.zeros(4, np.float32))), {})]:
        out = getattr(getattr(sd, ns), op)(*args, **kwargs)
        out = out[0] if isinstance(out, tuple) else out
        vals = sd.output({}, out.name)[out.name]
        assert np.isfinite(np.asarray(vals)).all(), (ns, op)


class TestEmissionPeepholes:
    """autodiff/passes: the two-pass-variance motif rewrite (GraphOptimizer
    analog). The stored graph must be untouched; values AND training
    gradients must match the unoptimized emission exactly (the rewrite is
    gradient-equivalent by construction — see the module docstring)."""

    def _moments_graph(self):
        """The literal motif a frozen tf.nn.moments/LayerNorm produces:
        Mean -> SquaredDifference(x, StopGradient(mean)) -> Mean."""
        sd = SameDiff.create()
        x = sd.placeholder("x", (4, 8))
        m = sd._op("Mean", x, axis=(1,), keepdims=True)
        sg = sd._op("Identity", m)               # StopGradient import form
        sq = sd._op("SquaredDifference", x, sg)
        v = sd._op("Mean", sq, axis=(1,), keepdims=True).rename("var")
        return sd, v

    def test_motif_rewrite_matches_two_pass_value(self):
        from deeplearning4j_tpu.autodiff.passes import fuse_two_pass_moments

        sd, _ = self._moments_graph()
        rewritten, n = fuse_two_pass_moments(sd.ops())
        assert n == 1
        assert any(op.op_name == "one_pass_variance" for op in rewritten)
        # stored graph untouched (serialization sees the original motif)
        assert all(op.op_name != "one_pass_variance" for op in sd.ops())

        rng = np.random.default_rng(0)
        X = rng.normal(3.0, 2.0, (4, 8)).astype(np.float32)
        got = np.asarray(sd.output({"x": X}, "var")["var"])
        want = np.var(X, axis=1, keepdims=True)
        np.testing.assert_allclose(got, want, rtol=1e-5, atol=1e-6)

    def test_rewrite_off_switch_and_value_parity(self, monkeypatch):
        rng = np.random.default_rng(1)
        X = rng.normal(-2.0, 0.5, (4, 8)).astype(np.float32)

        sd, _ = self._moments_graph()
        on = np.asarray(sd.output({"x": X}, "var")["var"])
        monkeypatch.setenv("DL4J_TPU_GRAPH_OPT", "0")
        sd2, _ = self._moments_graph()
        off = np.asarray(sd2.output({"x": X}, "var")["var"])
        np.testing.assert_allclose(on, off, rtol=1e-5, atol=1e-6)

    def test_training_gradients_match_unoptimized(self, monkeypatch):
        """Fine-tune THROUGH the motif (layernorm-style normalization a la
        the imported-BERT hot path): per-step losses with the peephole on
        must track the peephole-off run to f32 noise."""
        from deeplearning4j_tpu.data.dataset import DataSet
        from deeplearning4j_tpu.optim.updaters import Sgd

        def build():
            sd = SameDiff.create()
            x = sd.placeholder("x", (8, 6))
            w = sd.var("w", init=np.eye(6, dtype=np.float32))
            h = x.mmul(w)
            m = sd._op("Mean", h, axis=(1,), keepdims=True)
            sg = sd._op("Identity", m)
            sq = sd._op("SquaredDifference", h, sg)
            v = sd._op("Mean", sq, axis=(1,), keepdims=True)
            inv = sd._op("rsqrt", v + sd.constant(np.float32(1e-5)))
            yhat = (h - m) * inv
            yph = sd.placeholder("y", (8, 6))
            sd.loss.mse(yph, yhat).rename("loss")
            sd.set_loss_variables("loss")
            sd.set_training_config(TrainingConfig(
                updater=Sgd(0.05),
                data_set_feature_mapping=["x"],
                data_set_label_mapping=["y"]))
            return sd

        rng = np.random.default_rng(2)
        X = rng.normal(1.0, 1.0, (8, 6)).astype(np.float32)
        Y = rng.normal(0.0, 1.0, (8, 6)).astype(np.float32)
        data = [DataSet(X, Y)] * 6

        hist_on = build().fit(data, epochs=2)
        monkeypatch.setenv("DL4J_TPU_GRAPH_OPT", "0")
        hist_off = build().fit(data, epochs=2)
        np.testing.assert_allclose(hist_on.loss_curve(),
                                   hist_off.loss_curve(),
                                   rtol=1e-4, atol=1e-6)

    def test_tf_imported_moments_rewrites_and_matches(self):
        """Live-TF e2e: a frozen graph using tf.nn.moments imports and the
        emitted program matches TF's own output (the BERT-layernorm path)."""
        tf = pytest.importorskip("tensorflow")
        from tensorflow.python.framework.convert_to_constants import (
            convert_variables_to_constants_v2)
        from deeplearning4j_tpu.autodiff.passes import fuse_two_pass_moments
        from deeplearning4j_tpu.modelimport.tfimport import TFGraphMapper

        @tf.function
        def f(x):
            m, v = tf.nn.moments(x, axes=[-1], keepdims=True)
            return (x - m) * tf.math.rsqrt(v + 1e-5)

        frozen = convert_variables_to_constants_v2(
            f.get_concrete_function(tf.TensorSpec((3, 16), tf.float32)))
        gd = frozen.graph.as_graph_def()

        sd = TFGraphMapper.import_graph(gd)
        _, n = fuse_two_pass_moments(sd.ops())
        assert n == 1, "imported tf.nn.moments motif must match the pass"

        rng = np.random.default_rng(3)
        # zero-mean data: tight parity (the one-pass form's cancellation
        # error scales with (mean/std)^2 * 2^-23 — at mean 5/std 0.3 the
        # delta vs TF is ~8e-5, still well inside training noise)
        X = rng.normal(0.0, 1.0, (3, 16)).astype(np.float32)
        want = f(tf.constant(X)).numpy()
        got = np.asarray(list(sd.output({"x": X}).values())[0])
        np.testing.assert_allclose(got, want, rtol=1e-5, atol=1e-5)
        Xoff = rng.normal(5.0, 0.3, (3, 16)).astype(np.float32)
        np.testing.assert_allclose(
            np.asarray(list(sd.output({"x": Xoff}).values())[0]),
            f(tf.constant(Xoff)).numpy(), rtol=5e-3, atol=5e-4)

    def test_native_stop_gradient_motif_fuses_mean_side_only(self):
        """A native stop_gradient on the MEAN side must still fuse (the
        gradient-equivalent transform); one on the ACTIVATION side must
        block the rewrite (fusing there would change gradients)."""
        from deeplearning4j_tpu.autodiff.passes import fuse_two_pass_moments

        def graph(sg_on_x):
            sd = SameDiff.create()
            x = sd.placeholder("x", (4, 8))
            m = sd._op("Mean", x, axis=(1,), keepdims=True)
            msg = sd._op("stop_gradient", m)
            xs = sd._op("stop_gradient", x) if sg_on_x else x
            sq = sd._op("SquaredDifference", xs, msg)
            sd._op("Mean", sq, axis=(1,), keepdims=True).rename("var")
            return sd

        _, n_mean_side = fuse_two_pass_moments(graph(False).ops())
        assert n_mean_side == 1
        _, n_x_side = fuse_two_pass_moments(graph(True).ops())
        assert n_x_side == 0

    def test_keep_dims_attr_spelling_fuses_and_runs(self):
        """reduce_mean accepts keep_dims= too; the rewritten node's copied
        attrs must execute (review regression: TypeError at emission)."""
        sd = SameDiff.create()
        x = sd.placeholder("x", (4, 8))
        m = sd._op("Mean", x, axis=(1,), keep_dims=True)
        sq = sd._op("SquaredDifference", x, m)
        sd._op("Mean", sq, axis=(1,), keep_dims=True).rename("var")
        rng = np.random.default_rng(5)
        X = rng.normal(0, 1, (4, 8)).astype(np.float32)
        got = np.asarray(sd.output({"x": X})["var"])
        np.testing.assert_allclose(got, np.var(X, 1, keepdims=True),
                                   rtol=1e-5, atol=1e-6)
