"""Keras H5 import e2e (ref analog:
org.deeplearning4j.nn.modelimport.keras.e2e.KerasModelEndToEndTest —
build in Keras, save h5, import, compare outputs numerically)."""
import os

import numpy as np
import pytest

tf = pytest.importorskip("tensorflow")

from deeplearning4j_tpu.modelimport import KerasModelImport


def _save(model, tmp_path, name="m.h5"):
    p = os.path.join(str(tmp_path), name)
    model.save(p)
    return p


def test_sequential_dense(tmp_path):
    m = tf.keras.Sequential([
        tf.keras.Input((6,)),
        tf.keras.layers.Dense(12, activation="relu"),
        tf.keras.layers.Dense(4, activation="softmax"),
    ])
    net = KerasModelImport.import_keras_sequential_model_and_weights(
        _save(m, tmp_path))
    x = np.random.RandomState(0).rand(5, 6).astype("f4")
    expected = m.predict(x, verbose=0)
    got = np.asarray(net.output(x))
    assert np.allclose(got, expected, atol=1e-5)


def test_locally_connected_implementation_2_imported_impl3_rejected():
    """implementation=2 (full masked dense kernel) now IMPORTS via banded
    extraction (r5 flips the r3 refusal); implementation=3 (sparse) still
    refuses loudly."""
    from deeplearning4j_tpu.modelimport.keras import (
        UnsupportedKerasConfigurationException, _map_layer)
    cfg = {"filters": 4, "kernel_size": [2, 2], "padding": "valid",
           "implementation": 2}
    assert _map_layer("LocallyConnected2D", cfg) is not None
    cfg["implementation"] = 3
    with pytest.raises(UnsupportedKerasConfigurationException,
                       match="implementation"):
        _map_layer("LocallyConnected2D", cfg)
    cfg["implementation"] = 1
    assert _map_layer("LocallyConnected2D", cfg) is not None


def test_locally_connected_impl2_dense_kernel_extraction():
    """The impl-2 loader must invert Keras's scatter: impl-1 local weights
    scattered into the full dense (in_h, in_w, cin, oh, ow, f) layout and
    re-imported give the SAME layer params as the direct impl-1 reshape."""
    from deeplearning4j_tpu.modelimport import keras as KI
    from deeplearning4j_tpu.nn.conf.layers2 import LocallyConnected2D

    rng = np.random.RandomState(0)
    ih = iw = 5
    kh = kw = 2
    cin, f = 3, 4
    oh = ow = 4                       # valid, stride 1
    lyr = LocallyConnected2D(kernel_size=(kh, kw), n_in=cin, n_out=f,
                             input_size=(ih, iw), has_bias=False)
    w1 = rng.rand(oh * ow, kh * kw * cin, f).astype("f4")  # impl-1 kernel
    dense = np.zeros((ih, iw, cin, oh, ow, f), "f4")       # impl-2 kernel
    for o_r in range(oh):
        for o_c in range(ow):
            for dh in range(kh):
                for dw in range(kw):
                    for c in range(cin):
                        feat = (dh * kw + dw) * cin + c
                        dense[o_r + dh, o_c + dw, c, o_r, o_c, :] = \
                            w1[o_r * ow + o_c, feat]
    pa, pb = {}, {}
    KI._load_weights_into(lyr, {"kernel": w1}, pa, {}, "0")
    KI._load_weights_into(lyr, {"kernel": dense}, pb, {}, "0")
    np.testing.assert_allclose(np.asarray(pa["0"]["W"]),
                               np.asarray(pb["0"]["W"]), atol=0)


def test_sequential_cnn_with_bn(tmp_path):
    m = tf.keras.Sequential([
        tf.keras.Input((12, 12, 3)),
        tf.keras.layers.Conv2D(8, 3, activation="relu", padding="same"),
        tf.keras.layers.BatchNormalization(),
        tf.keras.layers.MaxPooling2D(2),
        tf.keras.layers.Conv2D(4, 3, padding="valid"),
        tf.keras.layers.Activation("relu"),
        tf.keras.layers.Flatten(),
        tf.keras.layers.Dense(5, activation="softmax"),
    ])
    # burn in some non-trivial BN statistics
    m.compile("adam", "categorical_crossentropy")
    rng = np.random.RandomState(1)
    m.fit(rng.rand(32, 12, 12, 3), np.eye(5)[rng.randint(0, 5, 32)],
          epochs=1, verbose=0)
    net = KerasModelImport.import_keras_sequential_model_and_weights(
        _save(m, tmp_path))
    x = rng.rand(3, 12, 12, 3).astype("f4")
    expected = m.predict(x, verbose=0)
    got = np.asarray(net.output(x))
    assert np.allclose(got, expected, atol=1e-4), np.abs(got - expected).max()


def test_sequential_separable_conv(tmp_path):
    m = tf.keras.Sequential([
        tf.keras.Input((10, 10, 3)),
        tf.keras.layers.SeparableConv2D(6, 3, padding="same",
                                        activation="relu"),
        tf.keras.layers.GlobalAveragePooling2D(),
        tf.keras.layers.Dense(2),
    ])
    net = KerasModelImport.import_keras_sequential_model_and_weights(
        _save(m, tmp_path))
    x = np.random.RandomState(2).rand(2, 10, 10, 3).astype("f4")
    assert np.allclose(np.asarray(net.output(x)), m.predict(x, verbose=0),
                       atol=1e-5)


def test_sequential_lstm(tmp_path):
    m = tf.keras.Sequential([
        tf.keras.Input((7, 5)),
        tf.keras.layers.LSTM(9, return_sequences=True),
        tf.keras.layers.LSTM(4, return_sequences=False),
        tf.keras.layers.Dense(3, activation="softmax"),
    ])
    net = KerasModelImport.import_keras_sequential_model_and_weights(
        _save(m, tmp_path))
    x = np.random.RandomState(3).rand(2, 7, 5).astype("f4")
    expected = m.predict(x, verbose=0)
    got = np.asarray(net.output(x))
    assert np.allclose(got, expected, atol=1e-4), np.abs(got - expected).max()


def test_sequential_gru(tmp_path):
    m = tf.keras.Sequential([
        tf.keras.Input((6, 4)),
        tf.keras.layers.GRU(8, return_sequences=False),
        tf.keras.layers.Dense(2),
    ])
    net = KerasModelImport.import_keras_sequential_model_and_weights(
        _save(m, tmp_path))
    x = np.random.RandomState(4).rand(2, 6, 4).astype("f4")
    expected = m.predict(x, verbose=0)
    got = np.asarray(net.output(x))
    assert np.allclose(got, expected, atol=1e-4), np.abs(got - expected).max()


def test_functional_model_with_add_and_concat(tmp_path):
    inp = tf.keras.Input((8,))
    a = tf.keras.layers.Dense(16, activation="relu", name="branch_a")(inp)
    b = tf.keras.layers.Dense(16, activation="tanh", name="branch_b")(inp)
    added = tf.keras.layers.Add(name="added")([a, b])
    cat = tf.keras.layers.Concatenate(name="cat")([a, added])
    out = tf.keras.layers.Dense(3, activation="softmax", name="out")(cat)
    model = tf.keras.Model(inp, out)
    net = KerasModelImport.import_keras_model_and_weights(
        _save(model, tmp_path))
    x = np.random.RandomState(5).rand(4, 8).astype("f4")
    expected = model.predict(x, verbose=0)
    got = np.asarray(net.output(x))
    assert np.allclose(got, expected, atol=1e-5)


def test_imported_model_is_trainable(tmp_path):
    m = tf.keras.Sequential([
        tf.keras.Input((4,)),
        tf.keras.layers.Dense(8, activation="relu"),
        tf.keras.layers.Dense(2, activation="softmax"),
    ])
    net = KerasModelImport.import_keras_sequential_model_and_weights(
        _save(m, tmp_path))
    rng = np.random.RandomState(0)
    X = rng.rand(64, 4).astype("f4")
    Y = np.eye(2)[(X.sum(1) > 2).astype(int)].astype("f4")
    from deeplearning4j_tpu.data.dataset import DataSet
    s0 = net.score(DataSet(X, Y))
    net.fit(X, Y, epochs=20)
    assert net.score(DataSet(X, Y)) < s0


def test_h5_nested_submodel_weights_do_not_collide(tmp_path):
    """ADVICE r1: nested wrapper layers with several sub-layers must not
    silently last-wins on leaf dataset names."""
    import h5py

    from deeplearning4j_tpu.modelimport.keras import (
        UnsupportedKerasConfigurationException, _H5Weights)

    p = str(tmp_path / "w.h5")
    with h5py.File(p, "w") as f:
        g = f.create_group("model_weights").create_group("wrapper")
        a = g.create_group("dense_a")
        a.create_dataset("kernel:0", data=np.ones((2, 2), "f4"))
        b = g.create_group("dense_b")
        b.create_dataset("kernel:0", data=np.zeros((2, 2), "f4") + 7.0)
        top = f["model_weights"].create_group("simple")
        top.create_dataset("kernel:0", data=np.full((3, 3), 2.0, "f4"))

    with h5py.File(p, "r") as f:
        w = _H5Weights(f)
        simple = w.get("simple")
        assert np.allclose(simple["kernel"], 2.0)
        import pytest as _pytest
        with _pytest.raises(UnsupportedKerasConfigurationException):
            w.get("wrapper")
        # full paths remain addressable
        assert np.allclose(w.by_layer["wrapper"]["dense_b/kernel"], 7.0)


def test_sequential_conv1d_causal(tmp_path):
    m = tf.keras.Sequential([
        tf.keras.Input((10, 3)),
        tf.keras.layers.Conv1D(6, 3, padding="causal", activation="relu"),
        tf.keras.layers.Conv1D(4, 3, padding="same"),
        tf.keras.layers.GlobalAveragePooling1D(),
        tf.keras.layers.Dense(2, activation="softmax"),
    ])
    net = KerasModelImport.import_keras_sequential_model_and_weights(
        _save(m, tmp_path))
    x = np.random.RandomState(0).rand(4, 10, 3).astype("f4")
    expected = m.predict(x, verbose=0)
    got = np.asarray(net.output(x))
    assert np.allclose(got, expected, atol=1e-5)


def test_sequential_conv3d(tmp_path):
    m = tf.keras.Sequential([
        tf.keras.Input((4, 6, 6, 2)),
        tf.keras.layers.Conv3D(3, 2, activation="relu", padding="same"),
        tf.keras.layers.Flatten(),
        tf.keras.layers.Dense(2),
    ])
    net = KerasModelImport.import_keras_sequential_model_and_weights(
        _save(m, tmp_path))
    x = np.random.RandomState(1).rand(2, 4, 6, 6, 2).astype("f4")
    expected = m.predict(x, verbose=0)
    got = np.asarray(net.output(x))
    assert np.allclose(got, expected, atol=1e-4)


def test_sequential_layernorm_and_activation_layers(tmp_path):
    m = tf.keras.Sequential([
        tf.keras.Input((8,)),
        tf.keras.layers.Dense(16),
        tf.keras.layers.LayerNormalization(),
        tf.keras.layers.LeakyReLU(),
        tf.keras.layers.Dense(4),
        tf.keras.layers.Softmax(),
    ])
    # make layernorm params non-trivial
    m.layers[1].set_weights([np.random.RandomState(2).rand(16).astype("f4"),
                             np.random.RandomState(3).rand(16).astype("f4")])
    net = KerasModelImport.import_keras_sequential_model_and_weights(
        _save(m, tmp_path))
    x = np.random.RandomState(4).rand(5, 8).astype("f4")
    expected = m.predict(x, verbose=0)
    got = np.asarray(net.output(x))
    assert np.allclose(got, expected, atol=1e-4)


def test_sequential_timedistributed_dense(tmp_path):
    m = tf.keras.Sequential([
        tf.keras.Input((6, 4)),
        tf.keras.layers.TimeDistributed(tf.keras.layers.Dense(
            5, activation="tanh")),
    ])
    net = KerasModelImport.import_keras_sequential_model_and_weights(
        _save(m, tmp_path))
    x = np.random.RandomState(5).rand(3, 6, 4).astype("f4")
    expected = m.predict(x, verbose=0)
    got = np.asarray(net.output(x))
    assert np.allclose(got, expected, atol=1e-5)


def test_sequential_bidirectional_lstm(tmp_path):
    m = tf.keras.Sequential([
        tf.keras.Input((6, 4)),
        tf.keras.layers.Bidirectional(
            tf.keras.layers.LSTM(5, return_sequences=True)),
        tf.keras.layers.GlobalAveragePooling1D(),
        tf.keras.layers.Dense(3, activation="softmax"),
    ])
    net = KerasModelImport.import_keras_sequential_model_and_weights(
        _save(m, tmp_path))
    x = np.random.RandomState(7).rand(4, 6, 4).astype("f4")
    expected = m.predict(x, verbose=0)
    got = np.asarray(net.output(x))
    assert np.allclose(got, expected, atol=1e-4)


def test_sequential_relu6_layer(tmp_path):
    m = tf.keras.Sequential([
        tf.keras.Input((5,)),
        tf.keras.layers.Dense(8),
        tf.keras.layers.ReLU(max_value=6.0),
        tf.keras.layers.Dense(2),
    ])
    m.layers[0].set_weights([
        np.random.RandomState(8).rand(5, 8).astype("f4") * 4,
        np.zeros(8, "f4")])
    net = KerasModelImport.import_keras_sequential_model_and_weights(
        _save(m, tmp_path))
    x = np.random.RandomState(9).rand(6, 5).astype("f4")
    expected = m.predict(x, verbose=0)
    got = np.asarray(net.output(x))
    assert np.allclose(got, expected, atol=1e-5)


def test_lambda_layer_and_custom_registry(tmp_path):
    """ref: KerasLayer.registerCustomLayer / registerLambdaLayer — lambda
    bodies re-registered in code, unknown classes routed to builders."""
    import jax.numpy as jnp
    import tensorflow as tf

    from deeplearning4j_tpu.modelimport import keras as ki

    m = tf.keras.Sequential([
        tf.keras.layers.Input((4,)),
        tf.keras.layers.Dense(6, activation="relu"),
        tf.keras.layers.Lambda(lambda t: t * 2.0 + 1.0,
                               name="double_shift"),
        tf.keras.layers.Dense(3, activation="softmax"),
    ])
    path = str(tmp_path / "lam.h5")
    m.save(path)

    # un-registered lambda: clear, actionable error
    with pytest.raises(Exception, match="register_lambda_layer"):
        ki.KerasModelImport.importKerasSequentialModelAndWeights(path)

    ki.register_lambda_layer("double_shift", lambda x: x * 2.0 + 1.0)
    try:
        net = ki.KerasModelImport.importKerasSequentialModelAndWeights(path)
        x = np.random.RandomState(0).rand(5, 4).astype("float32")
        want = m.predict(x, verbose=0)
        got = np.asarray(net.output(x))
        np.testing.assert_allclose(got, want, atol=1e-5)
        # name-keyed serialization: clone()/to_json round-trips revive the
        # body from the registry
        back = type(net.conf).from_json(net.conf.to_json())
        assert back.layers[1].fn is not None
    finally:
        ki._LAMBDA_LAYERS.clear()
        from deeplearning4j_tpu.nn.conf.layers import LAMBDA_REGISTRY
        LAMBDA_REGISTRY.clear()


def test_custom_layer_builder_registry(tmp_path):
    """Unknown class_names route to registered builders (ref:
    KerasLayer.registerCustomLayer)."""
    import tensorflow as tf

    from deeplearning4j_tpu.modelimport import keras as ki
    from deeplearning4j_tpu.nn.conf import layers as L

    class Doubler(tf.keras.layers.Layer):
        def call(self, t):
            return t * 2.0

    m = tf.keras.Sequential([
        tf.keras.layers.Input((4,)),
        tf.keras.layers.Dense(6, activation="relu"),
        Doubler(),
        tf.keras.layers.Dense(3, activation="softmax"),
    ])
    path = str(tmp_path / "cust.h5")
    m.save(path)

    with pytest.raises(Exception, match="register_custom_layer"):
        ki.KerasModelImport.importKerasSequentialModelAndWeights(path)

    ki.register_custom_layer(
        "Doubler", lambda cfg: L.LambdaLayer(name=cfg.get("name"),
                                             fn=lambda x: x * 2.0))
    try:
        net = ki.KerasModelImport.importKerasSequentialModelAndWeights(path)
        x = np.random.RandomState(1).rand(5, 4).astype("float32")
        want = m.predict(x, verbose=0)
        np.testing.assert_allclose(np.asarray(net.output(x)), want,
                                   atol=1e-5)
    finally:
        ki._CUSTOM_LAYERS.clear()


class TestStructuralLayers:
    """Round-3 additions: Reshape/Permute/RepeatVector (ref: KerasReshape/
    KerasPermute/KerasRepeatVector) — imported nets must match live Keras."""

    def _roundtrip(self, model, x, tmp_path):
        import os
        import numpy as np
        from deeplearning4j_tpu.modelimport.keras import KerasModelImport
        p = os.path.join(str(tmp_path), "m.h5")
        model.save(p)
        net = KerasModelImport.importKerasSequentialModelAndWeights(p)
        ref = model.predict(x, verbose=0)
        got = net.output(x).toNumpy()
        np.testing.assert_allclose(got, ref, rtol=1e-4, atol=1e-4)
        return net

    def test_reshape_then_dense(self, tmp_path):
        import numpy as np
        keras = pytest.importorskip("tensorflow").keras
        m = keras.Sequential([
            keras.layers.Input((12,)),
            keras.layers.Reshape((3, 4)),
            keras.layers.Flatten(),
            keras.layers.Dense(5, activation="relu"),
        ])
        x = np.random.default_rng(0).normal(size=(2, 12)).astype(np.float32)
        self._roundtrip(m, x, tmp_path)

    def test_repeat_vector_into_lstm(self, tmp_path):
        import numpy as np
        keras = pytest.importorskip("tensorflow").keras
        m = keras.Sequential([
            keras.layers.Input((6,)),
            keras.layers.RepeatVector(4),
            keras.layers.LSTM(3),
        ])
        x = np.random.default_rng(1).normal(size=(2, 6)).astype(np.float32)
        self._roundtrip(m, x, tmp_path)

    def test_permute_on_sequence(self, tmp_path):
        import numpy as np
        keras = pytest.importorskip("tensorflow").keras
        m = keras.Sequential([
            keras.layers.Input((4, 6)),
            keras.layers.Permute((2, 1)),
            keras.layers.Flatten(),
            keras.layers.Dense(3),
        ])
        x = np.random.default_rng(2).normal(size=(2, 4, 6)).astype(np.float32)
        self._roundtrip(m, x, tmp_path)


def test_sequential_tranche2_layers(tmp_path):
    """DepthwiseConv2D + PReLU + pooling-1D family import at numerical
    parity (ref: KerasDepthwiseConvolution2D / KerasPReLU mappings)."""
    m = tf.keras.Sequential([
        tf.keras.Input((10, 10, 3)),
        tf.keras.layers.DepthwiseConv2D(3, depth_multiplier=2,
                                        padding="valid"),
        tf.keras.layers.PReLU(),
        tf.keras.layers.MaxPooling2D(2),
        tf.keras.layers.Flatten(),
        tf.keras.layers.Dense(4, activation="softmax"),
    ])
    rng = np.random.RandomState(3)
    # non-zero alphas so PReLU actually bites
    weights = m.get_weights()
    for i, w in enumerate(weights):
        if w.shape == (8, 8, 6):           # the PReLU alpha
            weights[i] = rng.uniform(0.1, 0.4, w.shape).astype("f4")
    m.set_weights(weights)
    net = KerasModelImport.import_keras_sequential_model_and_weights(
        _save(m, tmp_path))
    x = rng.randn(3, 10, 10, 3).astype("f4")
    expected = m.predict(x, verbose=0)
    got = np.asarray(net.output(x))
    assert np.allclose(got, expected, atol=1e-4), np.abs(got - expected).max()


def test_sequential_1d_structural(tmp_path):
    """Cropping1D/ZeroPadding1D/UpSampling1D/AveragePooling1D chain."""
    m = tf.keras.Sequential([
        tf.keras.Input((8, 3)),
        tf.keras.layers.ZeroPadding1D(1),
        tf.keras.layers.Conv1D(4, 3, activation="tanh"),
        tf.keras.layers.UpSampling1D(2),
        tf.keras.layers.AveragePooling1D(2),
        tf.keras.layers.Cropping1D(1),
        tf.keras.layers.GlobalAveragePooling1D(),
        tf.keras.layers.Dense(2),
    ])
    rng = np.random.RandomState(4)
    net = KerasModelImport.import_keras_sequential_model_and_weights(
        _save(m, tmp_path))
    x = rng.randn(3, 8, 3).astype("f4")
    expected = m.predict(x, verbose=0)
    got = np.asarray(net.output(x))
    assert np.allclose(got, expected, atol=1e-4), np.abs(got - expected).max()


def test_masking_lstm_parity(tmp_path):
    """Keras Masking(0.0) -> LSTM on padded sequences: the sequential walk
    fuses Masking into MaskZeroLayer and matches Keras step-skipping."""
    m = tf.keras.Sequential([
        tf.keras.Input((6, 3)),
        tf.keras.layers.Masking(mask_value=0.0),
        tf.keras.layers.LSTM(4),
        tf.keras.layers.Dense(2),
    ])
    rng = np.random.RandomState(7)
    x = rng.randn(3, 6, 3).astype("f4")
    x[0, 4:] = 0.0                        # padded tail
    x[2, 2:] = 0.0
    net = KerasModelImport.import_keras_sequential_model_and_weights(
        _save(m, tmp_path))
    expected = m.predict(x, verbose=0)
    got = np.asarray(net.output(x))
    assert np.allclose(got, expected, atol=1e-4), np.abs(got - expected).max()


class TestLongTailLayers:
    """Round-4 long-tail additions (VERDICT r3 #8): ConvLSTM2D,
    SeparableConv1D, Conv3DTranspose, Minimum/Dot merges, the attention
    family — each end-to-end vs live tf.keras."""

    def test_conv2d_transpose_unequal_channels(self, tmp_path):
        """Regression: kernel layout is (kh,kw,OUT,IN) — untransposed
        loading only worked when in==out channels."""
        m = tf.keras.Sequential([
            tf.keras.Input((6, 6, 3)),
            tf.keras.layers.Conv2DTranspose(5, (3, 3), strides=(2, 2),
                                            padding="same"),
        ])
        net = KerasModelImport.import_keras_sequential_model_and_weights(
            _save(m, tmp_path))
        x = np.random.RandomState(0).rand(2, 6, 6, 3).astype("f4")
        want = m.predict(x, verbose=0)
        got = np.asarray(net.output(x))
        assert got.shape == want.shape
        assert np.allclose(got, want, atol=1e-4), np.abs(got - want).max()

    def test_conv3d_transpose(self, tmp_path):
        m = tf.keras.Sequential([
            tf.keras.Input((3, 4, 4, 2)),
            tf.keras.layers.Conv3DTranspose(5, (2, 2, 2), strides=(2, 2, 2),
                                            padding="same",
                                            activation="relu"),
        ])
        net = KerasModelImport.import_keras_sequential_model_and_weights(
            _save(m, tmp_path))
        x = np.random.RandomState(1).rand(2, 3, 4, 4, 2).astype("f4")
        want = m.predict(x, verbose=0)
        got = np.asarray(net.output(x))
        assert got.shape == want.shape
        assert np.allclose(got, want, atol=1e-4), np.abs(got - want).max()

    def test_separable_conv1d(self, tmp_path):
        m = tf.keras.Sequential([
            tf.keras.Input((8, 3)),
            tf.keras.layers.SeparableConv1D(6, 3, padding="same",
                                            depth_multiplier=2,
                                            activation="tanh"),
        ])
        net = KerasModelImport.import_keras_sequential_model_and_weights(
            _save(m, tmp_path))
        x = np.random.RandomState(2).rand(2, 8, 3).astype("f4")
        want = m.predict(x, verbose=0)
        got = np.asarray(net.output(x))
        assert got.shape == want.shape
        assert np.allclose(got, want, atol=1e-4), np.abs(got - want).max()

    def test_separable_conv1d_causal_semantics(self):
        """padding='causal' must left-pad by (k-1)*dilation (this tf.keras
        build rejects causal on SeparableConv1D, so the reference here is a
        manually left-padded VALID conv — Keras's own causal definition)."""
        import jax
        import jax.numpy as jnp

        from deeplearning4j_tpu.nn.conf.layers2 import SeparableConvolution1D

        x = np.random.RandomState(8).rand(2, 8, 3).astype("f4")
        lc = SeparableConvolution1D(kernel_size=3, dilation=2, n_in=3,
                                    n_out=4, padding="causal",
                                    weight_init="xavier")
        p = lc.init_params(jax.random.key(0))
        got, _ = lc.apply(p, jnp.asarray(x))
        lv = SeparableConvolution1D(kernel_size=3, dilation=2, n_in=3,
                                    n_out=4, padding=0,
                                    weight_init="xavier")
        xp = np.pad(x, ((0, 0), (4, 0), (0, 0)))   # (k-1)*d = 4, left only
        want, _ = lv.apply(p, jnp.asarray(xp))
        assert got.shape == (2, 8, 4)
        np.testing.assert_allclose(np.asarray(got), np.asarray(want),
                                   atol=1e-5)

    def test_conv_lstm_2d(self, tmp_path):
        for ret_seq in (False, True):
            m = tf.keras.Sequential([
                tf.keras.Input((4, 5, 5, 2)),
                tf.keras.layers.ConvLSTM2D(3, (3, 3), padding="same",
                                           return_sequences=ret_seq),
            ])
            net = KerasModelImport.import_keras_sequential_model_and_weights(
                _save(m, tmp_path, name=f"clstm{ret_seq}.h5"))
            x = np.random.RandomState(3).rand(2, 4, 5, 5, 2).astype("f4")
            want = m.predict(x, verbose=0)
            got = np.asarray(net.output(x))
            assert got.shape == want.shape, (got.shape, want.shape)
            # (a whole-suite run caught a real divergence here once: the
            # legacy-keras default recurrent_activation='hard_sigmoid' is
            # clip(0.2x+0.5,0,1), not jax.nn.hard_sigmoid — keep this
            # tolerance TIGHT so semantic drift cannot hide in it)
            assert np.allclose(got, want, atol=1e-4), (
                ret_seq, np.abs(got - want).max())

    def _functional_parity(self, inputs, out, tmp_path, feeds, name,
                           atol=1e-4):
        m = tf.keras.Model(inputs, out)
        net = KerasModelImport.import_keras_model_and_weights(
            _save(m, tmp_path, name=name))
        want = m.predict(feeds, verbose=0)
        got = net.output(*feeds) if isinstance(feeds, list) \
            else net.output(feeds)
        got = np.asarray(got[0] if isinstance(got, (list, tuple)) else got)
        assert got.shape == want.shape, (got.shape, want.shape)
        assert np.allclose(got, want, atol=atol), np.abs(got - want).max()

    def test_minimum_and_dot_merges(self, tmp_path):
        rs = np.random.RandomState(4)
        inp = tf.keras.Input((6,))
        a = tf.keras.layers.Dense(5, activation="relu")(inp)
        b = tf.keras.layers.Dense(5, activation="tanh")(inp)
        mn = tf.keras.layers.Minimum()([a, b])
        self._functional_parity(inp, mn, tmp_path,
                                rs.rand(3, 6).astype("f4"), "min.h5")
        dot = tf.keras.layers.Dot(axes=1)([a, b])
        self._functional_parity(inp, dot, tmp_path,
                                rs.rand(3, 6).astype("f4"), "dot.h5")
        dotn = tf.keras.layers.Dot(axes=1, normalize=True)([a, b])
        self._functional_parity(inp, dotn, tmp_path,
                                rs.rand(3, 6).astype("f4"), "dotn.h5")

    def test_dot_merge_rank3_similarity_matrix(self, tmp_path):
        """Dot(axes=2) on (N,T,D) pairs is Keras batch_dot → the full
        (N,T,T) similarity matrix, NOT the elementwise diagonal."""
        rs = np.random.RandomState(7)
        inp = tf.keras.Input((5, 6))
        a = tf.keras.layers.Dense(4)(inp)
        b = tf.keras.layers.Dense(4)(inp)
        dot = tf.keras.layers.Dot(axes=2)([a, b])
        assert dot.shape[1:] == (5, 5)
        self._functional_parity(inp, dot, tmp_path,
                                rs.rand(2, 5, 6).astype("f4"), "dot3.h5")

    def test_attention_layers(self, tmp_path):
        rs = np.random.RandomState(5)
        inp = tf.keras.Input((7, 6))
        q = tf.keras.layers.Dense(4)(inp)
        v = tf.keras.layers.Dense(4)(inp)
        att = tf.keras.layers.Attention()([q, v])
        self._functional_parity(inp, att, tmp_path,
                                rs.rand(2, 7, 6).astype("f4"), "att.h5")
        add = tf.keras.layers.AdditiveAttention(use_scale=False)([q, v])
        self._functional_parity(inp, add, tmp_path,
                                rs.rand(2, 7, 6).astype("f4"), "addatt.h5")

    def test_upsampling_bilinear_and_global_pool_3d(self, tmp_path):
        """UpSampling2D(interpolation='bilinear') must not silently run
        nearest; Global{Max,Average}Pooling3D map onto the generic global
        pool."""
        m = tf.keras.Sequential([
            tf.keras.Input((4, 4, 3)),
            tf.keras.layers.UpSampling2D(2, interpolation="bilinear"),
        ])
        net = KerasModelImport.import_keras_sequential_model_and_weights(
            _save(m, tmp_path, name="up.h5"))
        x = np.random.RandomState(9).rand(2, 4, 4, 3).astype("f4")
        want = m.predict(x, verbose=0)
        got = np.asarray(net.output(x))
        assert got.shape == want.shape
        assert np.allclose(got, want, atol=1e-5), np.abs(got - want).max()

        for kcls, red in ((tf.keras.layers.GlobalMaxPooling3D, "max"),
                          (tf.keras.layers.GlobalAveragePooling3D, "avg")):
            m3 = tf.keras.Sequential([
                tf.keras.Input((2, 3, 3, 4)), kcls(),
                tf.keras.layers.Dense(2),
            ])
            net3 = KerasModelImport.import_keras_sequential_model_and_weights(
                _save(m3, tmp_path, name=f"gp3_{red}.h5"))
            x3 = np.random.RandomState(10).rand(2, 2, 3, 3, 4).astype("f4")
            want3 = m3.predict(x3, verbose=0)
            got3 = np.asarray(net3.output(x3))
            assert got3.shape == want3.shape
            assert np.allclose(got3, want3, atol=1e-5), red

    def test_multi_head_attention_self(self, tmp_path):
        rs = np.random.RandomState(6)
        inp = tf.keras.Input((5, 8))
        mha = tf.keras.layers.MultiHeadAttention(num_heads=2, key_dim=4)
        out = mha(inp, inp)
        self._functional_parity(inp, out, tmp_path,
                                rs.rand(2, 5, 8).astype("f4"), "mha.h5")


def test_conv2d_transpose_dilation(tmp_path):
    """r5 closes the Conv2DTranspose dilation refusal: parity vs live
    tf.keras through the H5 artifact. (output_padding is covered by the
    direct-layer test below: Keras 3's own get_config DROPS it, so no H5
    can carry it — the importer matches the artifact, verified here by
    comparing against the RELOADED keras model.)"""
    rng = np.random.RandomState(0)
    for ksz, kw in ((3, {"dilation_rate": 2, "padding": "same"}),
                    (3, {"dilation_rate": (2, 2), "padding": "valid"}),
                    (3, {"strides": 2, "output_padding": 1,
                         "padding": "same"}),
                    # EVEN effective kernel (k=2, d=3 -> k_eff=4) with
                    # 'same': the r5 review's wrong-output-size repro
                    (2, {"dilation_rate": 3, "padding": "same"})):
        m = tf.keras.Sequential([
            tf.keras.Input((7, 9, 3)),
            tf.keras.layers.Conv2DTranspose(5, ksz, **kw),
        ])
        path = _save(m, tmp_path)
        net = KerasModelImport.import_keras_sequential_model_and_weights(
            path)
        ref = tf.keras.models.load_model(path)   # artifact semantics
        x = rng.rand(2, 7, 9, 3).astype("f4")
        try:
            expected = ref.predict(x, verbose=0)
        except tf.errors.InvalidArgumentError as e:
            # which CPU kernel TensorFlow picks depends on the machine: on
            # the chip's host (PR 29) it refused dilation > 1 outright
            pytest.skip(f"TensorFlow cannot compute the reference here: "
                        f"{str(e).splitlines()[-1][:120]}")
        got = np.asarray(net.output(x))
        assert got.shape == expected.shape, (kw, got.shape, expected.shape)
        assert np.allclose(got, expected, atol=1e-4), (
            kw, np.abs(got - expected).max())


def test_deconv_output_padding_direct_layer_parity():
    """output_padding on our Deconvolution2D matches live tf.keras layer
    semantics (bypassing H5, which cannot carry the field)."""
    import jax.numpy as jnp

    from deeplearning4j_tpu.nn.conf.layers import Deconvolution2D

    rng = np.random.RandomState(1)
    for pad, op, s in (("same", (1, 1), (2, 2)),
                       ("valid", (1, 0), (2, 2)),
                       ("valid", (2, 1), (3, 3))):
        x = rng.rand(2, 7, 9, 3).astype("f4")
        k = rng.rand(3, 3, 3, 5).astype("f4")
        lyr = Deconvolution2D(kernel_size=(3, 3), stride=s,
                              padding=0 if pad == "valid" else pad,
                              n_in=3, n_out=5, has_bias=False,
                              output_padding=op, activation="identity")
        z, _ = lyr.apply({"W": jnp.asarray(k)}, jnp.asarray(x))
        klt = tf.keras.layers.Conv2DTranspose(
            5, 3, strides=s, padding=pad, output_padding=op, use_bias=False)
        _ = klt(x)
        klt.set_weights([k.transpose(0, 1, 3, 2)])
        y = klt(x).numpy()
        assert z.shape == y.shape, (pad, op, s, z.shape, y.shape)
        assert np.allclose(np.asarray(z), y, atol=1e-4), (
            pad, op, s, np.abs(np.asarray(z) - y).max())


def test_conv3d_transpose_output_padding_direct():
    """Deconvolution3D output_padding/dilation vs live tf.keras layer."""
    import jax.numpy as jnp

    from deeplearning4j_tpu.nn.conf.layers2 import Deconvolution3D

    rng = np.random.RandomState(2)
    x = rng.rand(2, 4, 5, 6, 2).astype("f4")
    k = rng.rand(3, 3, 3, 2, 3).astype("f4")
    lyr = Deconvolution3D(kernel_size=(3, 3, 3), stride=(2, 2, 2),
                          padding=0, n_in=2, n_out=3, has_bias=False,
                          output_padding=(1, 1, 1), activation="identity")
    z, _ = lyr.apply({"W": jnp.asarray(k)}, jnp.asarray(x))
    klt = tf.keras.layers.Conv3DTranspose(
        3, 3, strides=2, padding="valid", output_padding=1, use_bias=False)
    _ = klt(x)
    klt.set_weights([k.transpose(0, 1, 2, 4, 3)])
    y = klt(x).numpy()
    assert z.shape == y.shape
    assert np.allclose(np.asarray(z), y, atol=1e-4), \
        np.abs(np.asarray(z) - y).max()


def test_convlstm2d_tanh_recurrent_activation(tmp_path):
    """r5 closes the sigmoid/hard_sigmoid-only ConvLSTM gate refusal."""
    rng = np.random.RandomState(2)
    m = tf.keras.Sequential([
        tf.keras.Input((3, 6, 6, 2)),
        tf.keras.layers.ConvLSTM2D(4, 3, padding="same",
                                   recurrent_activation="tanh",
                                   return_sequences=False),
    ])
    net = KerasModelImport.import_keras_sequential_model_and_weights(
        _save(m, tmp_path))
    x = rng.rand(2, 3, 6, 6, 2).astype("f4")
    expected = m.predict(x, verbose=0)
    got = np.asarray(net.output(x))
    assert np.allclose(got, expected, atol=1e-4), np.abs(got - expected).max()


def test_multihead_cross_attention(tmp_path):
    """r5 closes the self-attention-only MHA refusal: query and key/value
    from DIFFERENT graph branches, parity vs live tf.keras."""
    rng = np.random.RandomState(3)
    q_in = tf.keras.Input((5, 8))
    kv_in = tf.keras.Input((7, 6))
    att = tf.keras.layers.MultiHeadAttention(num_heads=2, key_dim=4)(
        q_in, kv_in)
    out = tf.keras.layers.Dense(3)(att)
    m = tf.keras.Model([q_in, kv_in], out)
    net = KerasModelImport.import_keras_model_and_weights(_save(m, tmp_path))
    xq = rng.rand(2, 5, 8).astype("f4")
    xkv = rng.rand(2, 7, 6).astype("f4")
    expected = m.predict([xq, xkv], verbose=0)
    got = np.asarray(net.output([xq, xkv]))
    assert got.shape == expected.shape
    assert np.allclose(got, expected, atol=1e-4), np.abs(got - expected).max()
