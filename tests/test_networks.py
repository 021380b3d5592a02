"""Network-level regression tests (MultiLayerNetwork + ComputationGraph).

Covers the seams found by the round-1 e2e verification and code review:
conv padding forms, cnn_flat input reshape, pool autodiff under jit,
wrapper-layer serialization, ComputationGraph save/load, mask plumbing.
"""
import numpy as np
import pytest

import jax
import jax.numpy as jnp

from deeplearning4j_tpu.nn.conf.configuration import (
    BackpropType, MultiLayerConfiguration, NeuralNetConfiguration)
from deeplearning4j_tpu.nn.conf.inputs import InputType
from deeplearning4j_tpu.nn.conf.layers import (
    Bidirectional, ConvolutionLayer, DenseLayer, GRU, LastTimeStep, LSTM,
    OutputLayer, RnnOutputLayer, SimpleRnn, SubsamplingLayer)
from deeplearning4j_tpu.nn.graph import ComputationGraph
from deeplearning4j_tpu.nn.graph_conf import (
    ComputationGraphConfiguration, ElementWiseVertex, MergeVertex)
from deeplearning4j_tpu.nn.multilayer import MultiLayerNetwork
from deeplearning4j_tpu.optim.updaters import Adam
from deeplearning4j_tpu.ops.registry import exec_op


def _lenet_conf():
    return (NeuralNetConfiguration.builder()
            .seed(123).updater(Adam(1e-3)).list()
            .layer(ConvolutionLayer(n_out=4, kernel_size=3, stride=1, activation="relu"))
            .layer(SubsamplingLayer(kernel_size=2, stride=2))
            .layer(DenseLayer(n_out=16, activation="relu"))
            .layer(OutputLayer(n_out=3, activation="softmax",
                               loss_function="negativeloglikelihood"))
            .set_input_type(InputType.convolutional_flat(8, 8, 1))
            .build())


class TestConvNetTraining:
    def test_cnn_flat_input_trains_jitted(self):
        """cnn_flat (N, H*W*C) rows reshape to NHWC; pooling differentiates
        under jit∘grad (regression: reduce_window init as traced array)."""
        net = MultiLayerNetwork(_lenet_conf()).init()
        rng = np.random.default_rng(0)
        x = rng.random((16, 64), dtype=np.float32)
        y = np.eye(3, dtype=np.float32)[rng.integers(0, 3, 16)]
        net.fit(x, y)
        s0 = net.score()
        for _ in range(10):
            net.fit(x, y)
        assert net.score() < s0

    def test_conv_padding_int_pair_forms(self):
        x = jnp.ones((2, 8, 8, 3))
        w = jnp.ones((3, 3, 3, 4))
        a = exec_op("conv2d", x, w, None, strides=(1, 1), padding=1)
        b = exec_op("conv2d", x, w, None, strides=(1, 1), padding=(1, 1))
        c = exec_op("conv2d", x, w, None, strides=(1, 1), padding=[(1, 1), (1, 1)])
        assert a.shape == b.shape == c.shape == (2, 8, 8, 4)

    def test_pool_int_strides_all_variants(self):
        x = jnp.ones((1, 8, 8, 2))
        assert exec_op("maxpool2d", x, kernel=2, strides=2).shape == (1, 4, 4, 2)
        assert exec_op("pnormpool2d", x, kernel=2, strides=2).shape == (1, 4, 4, 2)
        x3 = jnp.ones((1, 8, 8, 8, 2))
        assert exec_op("maxpool3d", x3, kernel=2, strides=2).shape == (1, 4, 4, 4, 2)
        assert exec_op("avgpool3d", x3, kernel=2, strides=2).shape == (1, 4, 4, 4, 2)

    def test_avgpool_same_border_counts(self):
        """SAME-padded average pooling divides by real window sizes at borders."""
        x = jnp.ones((1, 3, 3, 1))
        out2 = exec_op("avgpool2d", x, kernel=(2, 2), strides=(2, 2), padding="SAME")
        np.testing.assert_allclose(np.asarray(out2), 1.0, rtol=1e-6)
        x3 = jnp.ones((1, 3, 3, 3, 1))
        out3 = exec_op("avgpool3d", x3, kernel=(2, 2, 2), strides=(2, 2, 2), padding="SAME")
        np.testing.assert_allclose(np.asarray(out3), 1.0, rtol=1e-6)


class TestWrapperSerialization:
    def test_bidirectional_roundtrip(self):
        conf = (NeuralNetConfiguration.builder().seed(5).updater(Adam(1e-3)).list()
                .layer(Bidirectional.wrap(LSTM(n_out=8), mode="concat"))
                .layer(RnnOutputLayer(n_out=4, activation="softmax",
                                      loss_function="negativeloglikelihood"))
                .set_input_type(InputType.recurrent(6, 10))
                .build())
        restored = MultiLayerConfiguration.from_json(conf.to_json())
        net = MultiLayerNetwork(restored).init()
        assert net.numParams() > 0
        x = np.random.default_rng(0).random((2, 10, 6), dtype=np.float32)
        out = net.output(x)
        assert out.shape == (2, 10, 4)

    def test_last_time_step_roundtrip(self):
        conf = (NeuralNetConfiguration.builder().seed(5).updater(Adam(1e-3)).list()
                .layer(LastTimeStep.wrap(SimpleRnn(n_out=8)))
                .layer(OutputLayer(n_out=2, activation="softmax", loss_function="mcxent"))
                .set_input_type(InputType.recurrent(4, 7))
                .build())
        restored = MultiLayerConfiguration.from_json(conf.to_json())
        net = MultiLayerNetwork(restored).init()
        out = net.output(np.ones((3, 7, 4), np.float32))
        assert out.shape == (3, 2)

    def test_rnn_default_activation_is_tanh(self):
        conf = (NeuralNetConfiguration.builder().list()
                .layer(GRU(n_out=4))
                .layer(RnnOutputLayer(n_out=2, activation="softmax",
                                      loss_function="mcxent"))
                .set_input_type(InputType.recurrent(3, 5))
                .build())
        assert conf.layers[0].activation == "tanh"


class TestComputationGraph:
    def _two_branch(self):
        return (NeuralNetConfiguration.builder().seed(1).updater(Adam(1e-2))
                .graph_builder()
                .add_inputs("in")
                .set_input_types(InputType.feed_forward(12))
                .add_layer("a", DenseLayer(n_out=8, activation="relu"), "in")
                .add_layer("b", DenseLayer(n_out=8, activation="tanh"), "in")
                .add_vertex("sum", ElementWiseVertex(op="add"), "a", "b")
                .add_layer("out", OutputLayer(n_out=3, activation="softmax",
                                              loss_function="negativeloglikelihood"), "sum")
                .set_outputs("out")
                .build())

    def test_fit_and_output(self):
        cg = ComputationGraph(self._two_branch()).init()
        rng = np.random.default_rng(0)
        x = rng.random((8, 12), dtype=np.float32)
        y = np.eye(3, dtype=np.float32)[rng.integers(0, 3, 8)]
        cg.fit(x, y)
        s0 = cg.score()
        for _ in range(15):
            cg.fit(x, y)
        assert cg.score() < s0
        assert cg.output(x).shape == (8, 3)

    def test_save_load_roundtrip(self, tmp_path):
        cg = ComputationGraph(self._two_branch()).init()
        x = np.random.default_rng(0).random((4, 12), dtype=np.float32)
        a = cg.output(x).toNumpy()
        p = str(tmp_path / "cg.zip")
        cg.save(p)
        cg2 = ComputationGraph.load(p)
        np.testing.assert_allclose(a, cg2.output(x).toNumpy(), rtol=1e-5)

    def test_vertex_output_rejected_for_fit(self):
        g = (NeuralNetConfiguration.builder().updater(Adam(1e-2))
             .graph_builder()
             .add_inputs("in")
             .set_input_types(InputType.feed_forward(4))
             .add_layer("a", DenseLayer(n_out=4, activation="relu"), "in")
             .add_vertex("m", MergeVertex(), "a")
             .set_outputs("m")
             .build())
        cg = ComputationGraph(g).init()
        with pytest.raises(ValueError, match="loss-bearing"):
            cg.fit(np.ones((2, 4), np.float32), np.ones((2, 4), np.float32))

    def test_multidataset_masks_reach_loss(self):
        """MultiDataSet plural mask attrs must flow into the loss."""
        from deeplearning4j_tpu.data.dataset import MultiDataSet
        g = (NeuralNetConfiguration.builder().seed(3).updater(Adam(1e-2))
             .graph_builder()
             .add_inputs("in")
             .set_input_types(InputType.recurrent(4, 6))
             .add_layer("rnn", SimpleRnn(n_out=8), "in")
             .add_layer("out", RnnOutputLayer(n_out=2, activation="softmax",
                                              loss_function="mcxent"), "rnn")
             .set_outputs("out")
             .build())
        rng = np.random.default_rng(0)
        x = rng.random((4, 6, 4), dtype=np.float32)
        y = np.eye(2, dtype=np.float32)[rng.integers(0, 2, (4, 6))]
        mask = np.ones((4, 6), np.float32)
        mask[:, 3:] = 0.0
        # corrupt only the masked-out label region; first-step score must be
        # identical iff the mask actually reaches the loss
        y2 = y.copy()
        y2[:, 3:] = 1.0 - y2[:, 3:]
        cg_a = ComputationGraph(g).init()
        cg_a.fit(MultiDataSet([x], [y], features_masks=[mask], labels_masks=[mask]))
        cg_b = ComputationGraph(ComputationGraphConfiguration.from_json(g.to_json())).init()
        cg_b.fit(MultiDataSet([x], [y2], features_masks=[mask], labels_masks=[mask]))
        assert cg_a.score() == pytest.approx(cg_b.score(), rel=1e-6)


class TestGraphConfValidation:
    def test_cycle_detection(self):
        with pytest.raises(ValueError, match="cycle"):
            (NeuralNetConfiguration.builder().graph_builder()
             .add_inputs("in")
             .add_layer("a", DenseLayer(n_in=4, n_out=4), "b")
             .add_layer("b", DenseLayer(n_in=4, n_out=4), "a")
             .set_outputs("b")
             .build())

    def test_unknown_input_rejected(self):
        with pytest.raises(ValueError, match="unknown"):
            (NeuralNetConfiguration.builder().graph_builder()
             .add_inputs("in")
             .add_layer("a", DenseLayer(n_in=4, n_out=4), "nonexistent")
             .set_outputs("a")
             .build())


class TestExplicitPreprocessors:
    """Explicit InputPreProcessor API (ref: conf.preprocessor.* +
    ListBuilder#inputPreProcessor — SURVEY D1/D2)."""

    def test_ff_to_cnn_and_back(self):
        from deeplearning4j_tpu.nn.conf.preprocessors import (
            CnnToFeedForwardPreProcessor, FeedForwardToCnnPreProcessor)
        conf = (NeuralNetConfiguration.builder()
                .seed(1).updater(Adam(1e-2)).list()
                .layer(ConvolutionLayer(kernel_size=3, n_in=1, n_out=4,
                                        padding="same", activation="relu"))
                .layer(DenseLayer(n_in=6 * 6 * 4, n_out=8, activation="relu"))
                .layer(OutputLayer(n_in=8, n_out=3, activation="softmax",
                                   loss_function="mcxent"))
                .input_pre_processor(0, FeedForwardToCnnPreProcessor(6, 6, 1))
                .input_pre_processor(1, CnnToFeedForwardPreProcessor())
                .build())
        net = MultiLayerNetwork(conf).init()
        rng = np.random.default_rng(0)
        x = rng.normal(size=(8, 36)).astype(np.float32)  # flat rows
        y = np.eye(3, dtype=np.float32)[rng.integers(0, 3, 8)]
        net.fit(x, y)
        s0 = net.score()
        for _ in range(10):
            net.fit(x, y)
        assert net.score() < s0
        assert np.asarray(net.output(x)).shape == (8, 3)

    def test_rnn_ff_round_trip_preprocessors(self):
        from deeplearning4j_tpu.nn.conf.preprocessors import (
            FeedForwardToRnnPreProcessor, RnnToFeedForwardPreProcessor)
        conf = (NeuralNetConfiguration.builder()
                .seed(2).updater(Adam(1e-2)).list()
                .layer(LSTM(n_in=4, n_out=6, activation="tanh"))
                .layer(DenseLayer(n_in=6, n_out=5, activation="relu"))
                .layer(RnnOutputLayer(n_in=5, n_out=2, activation="softmax",
                                      loss_function="mcxent"))
                .input_pre_processor(1, RnnToFeedForwardPreProcessor())
                .input_pre_processor(2, FeedForwardToRnnPreProcessor())
                .build())
        net = MultiLayerNetwork(conf).init()
        rng = np.random.default_rng(1)
        x = rng.normal(size=(4, 7, 4)).astype(np.float32)
        y = np.eye(2, dtype=np.float32)[rng.integers(0, 2, (4, 7))]
        net.fit(x, y)
        assert np.isfinite(net.score())
        assert np.asarray(net.output(x)).shape == (4, 7, 2)

    def test_preprocessors_json_round_trip(self):
        from deeplearning4j_tpu.nn.conf.preprocessors import (
            FeedForwardToCnnPreProcessor, preprocessor_from_dict)
        conf = (NeuralNetConfiguration.builder()
                .seed(3).updater(Adam(1e-3)).list()
                .layer(ConvolutionLayer(kernel_size=3, n_in=1, n_out=2,
                                        padding="same"))
                .layer(OutputLayer(n_in=2 * 4 * 4, n_out=2,
                                   activation="softmax",
                                   loss_function="mcxent"))
                .input_pre_processor(0, FeedForwardToCnnPreProcessor(4, 4, 1))
                .build())
        conf2 = MultiLayerConfiguration.from_json(conf.to_json())
        p = conf2.input_pre_processors[0]
        assert isinstance(p, FeedForwardToCnnPreProcessor)
        assert p.input_height == 4
        net = MultiLayerNetwork(conf2).init()
        out = net.output(np.zeros((2, 16), np.float32))
        assert np.asarray(out).shape == (2, 2)


def test_computation_graph_rnn_time_step():
    """CG streaming inference (ref: ComputationGraph#rnnTimeStep): stepwise
    outputs with carried state must match the full-sequence forward."""
    conf = (NeuralNetConfiguration.builder()
            .seed(4).updater(Adam(1e-2))
            .graph_builder().add_inputs("in")
            .set_input_types(InputType.recurrent(3, 6)))
    conf.add_layer("lstm", LSTM(n_out=5, activation="tanh"), "in")
    conf.add_layer("out", RnnOutputLayer(n_out=2, activation="softmax",
                                         loss_function="mcxent"), "lstm")
    conf.set_outputs("out")
    cg = ComputationGraph(conf.build()).init()
    rng = np.random.default_rng(0)
    x = rng.normal(size=(2, 6, 3)).astype(np.float32)
    full = np.asarray(cg.output(x).buf() if hasattr(cg.output(x), "buf")
                      else cg.output(x))
    cg.rnnClearPreviousState()
    steps = []
    for t in range(6):
        steps.append(np.asarray(cg.rnnTimeStep(x[:, t]).buf()))
    np.testing.assert_allclose(np.stack(steps, axis=1), full, atol=1e-5)
    assert cg.rnnGetPreviousState("lstm") is not None
    cg.rnnClearPreviousState()
    assert cg.rnnGetPreviousState("lstm") is None


def test_computation_graph_tbptt_trains():
    """CG TBPTT (ref: ComputationGraph#doTruncatedBPTT): 3 chunks per fit,
    loss decreases, iteration counter advances per chunk."""
    conf = (NeuralNetConfiguration.builder()
            .seed(5).updater(Adam(1e-2))
            .graph_builder().add_inputs("in")
            .set_input_types(InputType.recurrent(4, 12)))
    conf.add_layer("lstm", LSTM(n_out=6, activation="tanh"), "in")
    conf.add_layer("out", RnnOutputLayer(n_out=4, activation="softmax",
                                         loss_function="mcxent"), "lstm")
    conf.set_outputs("out")
    conf.backprop_type("tbptt").t_bptt_length(4)
    cg = ComputationGraph(conf.build()).init()
    rng = np.random.default_rng(1)
    idx = rng.integers(0, 4, (4, 13))
    x = np.eye(4, dtype=np.float32)[idx[:, :-1]]
    y = np.eye(4, dtype=np.float32)[idx[:, 1:]]
    cg.fit((x,), (y,))
    assert cg._iteration == 3          # 12 steps / tbptt 4
    s0 = cg.score()
    for _ in range(8):
        cg.fit((x,), (y,))
    assert cg.score() < s0


def test_computation_graph_tbptt_with_masks():
    """Regression (review finding): 2-D (N,T) masks must chunk with the
    time axis during CG TBPTT."""
    conf = (NeuralNetConfiguration.builder()
            .seed(6).updater(Adam(1e-2))
            .graph_builder().add_inputs("in")
            .set_input_types(InputType.recurrent(3, 8)))
    conf.add_layer("lstm", LSTM(n_out=4, activation="tanh"), "in")
    conf.add_layer("out", RnnOutputLayer(n_out=2, activation="softmax",
                                         loss_function="mcxent"), "lstm")
    conf.set_outputs("out")
    conf.backprop_type("tbptt").t_bptt_length(4)
    cg = ComputationGraph(conf.build()).init()
    rng = np.random.default_rng(2)
    x = rng.normal(size=(3, 8, 3)).astype(np.float32)
    y = np.eye(2, dtype=np.float32)[rng.integers(0, 2, (3, 8))]
    mask = np.ones((3, 8), np.float32)
    mask[0, 5:] = 0
    from deeplearning4j_tpu.data.dataset import DataSet
    ds = DataSet(x, y, features_mask=mask, labels_mask=mask)
    cg.fit([ds])
    assert np.isfinite(cg.score())
    assert cg._iteration == 2


def test_no_retrace_across_fit_steps():
    """Weak-typed init leaves (e.g. jnp.full biases) change the jitted
    step's signature after step 1 (weak->strong) and silently retrace the
    whole-net train step on the 2nd AND 3rd calls — a full XLA recompile
    each (~14 s on ResNet-50). init() strengthens dtypes so the first
    trace is the only trace."""
    import numpy as np

    from deeplearning4j_tpu.models import zoo
    from deeplearning4j_tpu.nn.graph import ComputationGraph
    from deeplearning4j_tpu.nn.multilayer import MultiLayerNetwork

    rng = np.random.RandomState(0)

    net = zoo.LeNet().init_model()          # MultiLayerNetwork
    x = rng.rand(4, 784).astype("float32")
    y = np.eye(10, dtype="float32")[rng.randint(0, 10, 4)]
    before = MultiLayerNetwork._train_step._cache_size()
    for _ in range(3):
        net.fit(x, y)
    assert MultiLayerNetwork._train_step._cache_size() - before == 1

    # graph half: a small two-branch CG proves the same cache assertion
    # without ResNet-scale compile time
    from deeplearning4j_tpu.nn.conf.inputs import InputType
    from deeplearning4j_tpu.nn.conf.layers import (DenseLayer, OutputLayer,
                                                   BatchNormalization)
    from deeplearning4j_tpu.optim.updaters import Adam

    gb = (NeuralNetConfiguration.builder().seed(2).updater(Adam(1e-3))
          .graph_builder().add_inputs("in")
          .set_input_types(InputType.feed_forward(6)))
    gb.add_layer("d", DenseLayer(n_out=8, activation="relu"), "in")
    gb.add_layer("bn", BatchNormalization(), "d")
    gb.add_layer("out", OutputLayer(n_out=3, activation="softmax",
                                    loss_function="negativeloglikelihood"),
                 "bn")
    gb.set_outputs("out")
    gnet = ComputationGraph(gb.build()).init()
    xi = rng.rand(4, 6).astype("float32")
    yi = np.eye(3, dtype="float32")[rng.randint(0, 3, 4)]
    before = ComputationGraph._train_step._cache_size()
    for _ in range(3):
        gnet.fit(xi, yi)
    assert ComputationGraph._train_step._cache_size() - before == 1


def test_weight_noise_dropconnect():
    """ref: conf.weightnoise.{DropConnect,WeightNoise} — weight-level noise
    at training forward; inference is deterministic and unnoised."""
    from deeplearning4j_tpu.nn.conf.inputs import InputType
    from deeplearning4j_tpu.nn.conf.layers import DenseLayer, OutputLayer
    from deeplearning4j_tpu.nn.weightnoise import (DropConnect, WeightNoise,
                                                   noise_from_dict)
    from deeplearning4j_tpu.optim.updaters import Adam

    conf = (NeuralNetConfiguration.builder().seed(4).updater(Adam(1e-2))
            .list()
            .layer(DenseLayer(n_out=16, activation="relu",
                              weight_noise=DropConnect(p=0.8)))
            .layer(DenseLayer(n_out=16, activation="relu",
                              weight_noise=WeightNoise(std=0.05)))
            .layer(OutputLayer(n_out=3, activation="softmax",
                               loss_function="negativeloglikelihood"))
            .set_input_type(InputType.feed_forward(6))
            .build())
    net = MultiLayerNetwork(conf).init()
    rng = np.random.RandomState(0)
    x = rng.rand(16, 6).astype("float32")
    y = np.eye(3, dtype="float32")[rng.randint(0, 3, 16)]
    net.fit(x, y)
    s0 = net.score()
    for _ in range(15):
        net.fit(x, y)
    assert np.isfinite(net.score()) and net.score() < s0
    # inference: deterministic, no noise
    a = np.asarray(net.output(x))
    b = np.asarray(net.output(x))
    np.testing.assert_allclose(a, b)
    # JSON round-trip revives the noise objects
    back = type(net.conf).from_json(net.conf.to_json())
    assert isinstance(back.layers[0].weight_noise, DropConnect)
    assert back.layers[0].weight_noise.p == 0.8
    assert isinstance(back.layers[1].weight_noise, WeightNoise)


def test_weight_init_tranche2():
    """orthogonal / truncated_normal / var_scaling family (ref:
    WeightInit.DISTRIBUTION + VAR_SCALING_* enum members)."""
    import jax as _jax

    from deeplearning4j_tpu.nn import weights as W

    k = _jax.random.key(0)
    q = W.init("orthogonal", k, (6, 4), 6, 4)
    np.testing.assert_allclose(np.asarray(q.T @ q), np.eye(4), atol=1e-5)
    q2 = W.init("orthogonal", k, (4, 6), 4, 6)
    np.testing.assert_allclose(np.asarray(q2 @ q2.T), np.eye(4), atol=1e-5)
    t = W.init("truncated_normal", k, (2000,), 100.0, 100.0)
    assert float(np.abs(np.asarray(t)).max()) <= 2.0 / 10.0 + 1e-6
    # scale checks with asymmetric fans so swapped fan_in/fan_out fails
    fi, fo = 400.0, 100.0
    big = (400, 400)
    trunc_std = 0.8796     # std of N(0,1) truncated at ±2
    for nm, target in [
            ("var_scaling_normal_fan_in", trunc_std / np.sqrt(fi)),
            ("var_scaling_normal_fan_out", trunc_std / np.sqrt(fo)),
            ("var_scaling_normal_fan_avg",
             trunc_std * np.sqrt(2.0 / (fi + fo))),
            ("var_scaling_uniform_fan_in", np.sqrt(3.0 / fi) / np.sqrt(3)),
            ("var_scaling_uniform_fan_out", np.sqrt(3.0 / fo) / np.sqrt(3)),
            ("var_scaling_uniform_fan_avg",
             np.sqrt(6.0 / (fi + fo)) / np.sqrt(3))]:
        out = np.asarray(W.init(nm, k, big, fi, fo))
        assert abs(out.std() - target) < 0.1 * target, (nm, out.std(),
                                                        target)
    # truncation: normal variants never exceed two std of the base scale
    t2 = np.asarray(W.init("var_scaling_normal_fan_in", k, big, fi, fo))
    assert np.abs(t2).max() <= 2.0 / np.sqrt(fi) + 1e-6


def test_tranche2_layer_json_round_trip():
    """Every tranche-2 layer class survives to_dict -> layer_from_dict
    (the MultiLayerConfiguration JSON path)."""
    from deeplearning4j_tpu.nn.conf.layers import (
        Cropping1D, Cropping3D, DepthwiseConvolution2D, FrozenLayer,
        FrozenLayerWithBackprop, LocallyConnected1D, LocallyConnected2D,
        MaskLayer, MaskZeroLayer, PReLULayer, Subsampling1DLayer,
        Subsampling3DLayer, Upsampling1D, Upsampling3D,
        ZeroPadding1DLayer, ZeroPadding3DLayer, LSTM, DenseLayer,
        layer_from_dict)
    layers = [
        DepthwiseConvolution2D(kernel_size=(3, 3), n_in=2,
                               depth_multiplier=2),
        PReLULayer(n_in=4, alpha_init=0.1),
        LocallyConnected2D(kernel_size=(2, 2), n_in=2, n_out=3,
                           input_size=(4, 4)),
        LocallyConnected1D(kernel_size=2, n_in=3, n_out=4, input_size=5),
        Cropping1D(cropping=(1, 1)), Cropping3D(cropping=(1,) * 6),
        ZeroPadding1DLayer(padding=(1, 2)),
        ZeroPadding3DLayer(padding=(1, 0, 1, 0, 1, 0)),
        Upsampling1D(size=2), Upsampling3D(size=(2, 1, 2)),
        Subsampling1DLayer(kernel_size=2, stride=2),
        Subsampling3DLayer(pooling_type="avg"),
        MaskLayer(),
        MaskZeroLayer.wrap(LSTM(n_in=3, n_out=4), mask_value=0.0),
        FrozenLayer.wrap(DenseLayer(n_in=4, n_out=3)),
        FrozenLayerWithBackprop.wrap(DenseLayer(n_in=4, n_out=3)),
    ]
    for lyr in layers:
        d = lyr.to_dict()
        back = layer_from_dict(d)
        assert type(back) is type(lyr), type(back)
        assert back.to_dict() == d, type(lyr)


def test_frozen_layer_blocks_training():
    """A FrozenLayerWithBackprop inside an MLN: frozen params are
    bit-identical after fit, upstream params move."""
    import jax
    from deeplearning4j_tpu.nn.conf.configuration import NeuralNetConfiguration
    from deeplearning4j_tpu.nn.conf.inputs import InputType
    from deeplearning4j_tpu.nn.conf.layers import (DenseLayer,
                                                   FrozenLayerWithBackprop,
                                                   OutputLayer)
    from deeplearning4j_tpu.nn.multilayer import MultiLayerNetwork
    from deeplearning4j_tpu.optim.updaters import Sgd
    conf = (NeuralNetConfiguration.builder()
            .seed(5).updater(Sgd(0.5)).list()
            .layer(DenseLayer(n_out=6, activation="tanh"))
            .layer(FrozenLayerWithBackprop.wrap(
                DenseLayer(n_in=6, n_out=5, activation="tanh")))
            .layer(OutputLayer(n_out=2, activation="softmax",
                               loss_function="negativeloglikelihood"))
            .set_input_type(InputType.feed_forward(4))
            .build())
    net = MultiLayerNetwork(conf).init()
    rng = np.random.RandomState(0)
    x = rng.randn(16, 4).astype(np.float32)
    y = np.eye(2, dtype=np.float32)[rng.randint(0, 2, 16)]
    p_before = [np.asarray(v) for v in
                jax.tree.leaves(net.param_tree()["1"])]
    d0 = [np.asarray(v) for v in jax.tree.leaves(net.param_tree()["0"])]
    for _ in range(5):
        net.fit(x, y)
    p_after = [np.asarray(v) for v in
               jax.tree.leaves(net.param_tree()["1"])]
    d1 = [np.asarray(v) for v in jax.tree.leaves(net.param_tree()["0"])]
    assert all(np.array_equal(a, b) for a, b in zip(p_before, p_after))
    assert any(not np.array_equal(a, b) for a, b in zip(d0, d1))


def test_dropout_family():
    """conf.dropout family: statistical contracts + JSON roundtrip through
    a layer config (ref: org.deeplearning4j.nn.conf.dropout.*)."""
    import jax
    import jax.numpy as jnp
    from deeplearning4j_tpu.nn.conf.dropout import (AlphaDropout, Dropout,
                                                    GaussianDropout,
                                                    GaussianNoise,
                                                    dropout_from_dict)
    rng = np.random.RandomState(0)
    key = jax.random.key(3)
    x = jnp.asarray(rng.randn(4000, 16).astype(np.float32))
    # inverted dropout keeps the expectation
    y = Dropout(0.7).apply(x, key, True)
    assert abs(float(y.mean()) - float(x.mean())) < 0.02
    assert float((y == 0).mean()) > 0.2
    # gaussian dropout: multiplicative, mean-preserving
    y = GaussianDropout(0.4).apply(x, key, True)
    assert abs(float(y.mean()) - float(x.mean())) < 0.02
    # gaussian noise: additive stddev
    y = GaussianNoise(0.5).apply(jnp.zeros_like(x), key, True)
    assert abs(float(y.std()) - 0.5) < 0.02
    # alpha dropout preserves mean AND variance of standardized input
    y = AlphaDropout(0.9).apply(x, key, True)
    assert abs(float(y.mean()) - float(x.mean())) < 0.05
    assert abs(float(y.std()) - float(x.std())) < 0.1
    # eval mode = identity for all
    for obj in (Dropout(0.5), GaussianDropout(0.5), GaussianNoise(0.5),
                AlphaDropout(0.8)):
        assert bool((obj.apply(x, key, False) == x).all())
        assert dropout_from_dict(obj.to_dict()) == obj
    # layer-config JSON roundtrip with an object-valued dropout
    from deeplearning4j_tpu.nn.conf.layers import (DenseLayer,
                                                   layer_from_dict)
    lyr = DenseLayer(n_in=4, n_out=3, dropout=GaussianDropout(0.3))
    back = layer_from_dict(lyr.to_dict())
    assert isinstance(back.dropout, GaussianDropout)
    assert back.dropout.rate == 0.3


def test_capsnet_trains():
    """PrimaryCapsules -> CapsuleLayer (dynamic routing) ->
    CapsuleStrengthLayer trains end-to-end (ref: the capsnet trio,
    conf.layers.CapsuleLayer family)."""
    from deeplearning4j_tpu.nn.conf.configuration import NeuralNetConfiguration
    from deeplearning4j_tpu.nn.conf.inputs import InputType
    from deeplearning4j_tpu.nn.conf.layers import (CapsuleLayer,
                                                   CapsuleStrengthLayer,
                                                   ConvolutionLayer,
                                                   LossLayer,
                                                   PrimaryCapsules)
    from deeplearning4j_tpu.nn.multilayer import MultiLayerNetwork
    from deeplearning4j_tpu.optim.updaters import Adam
    conf = (NeuralNetConfiguration.builder()
            .seed(9).updater(Adam(5e-3)).list()
            .layer(ConvolutionLayer(n_out=4, kernel_size=(3, 3),
                                    activation="relu"))
            .layer(PrimaryCapsules(capsule_dimensions=4, channels=2,
                                   kernel_size=(3, 3), stride=(2, 2)))
            .layer(CapsuleLayer(capsules=2, capsule_dimensions=6,
                                routings=2))
            .layer(CapsuleStrengthLayer())
            .layer(LossLayer(loss_function="mse"))
            .set_input_type(InputType.convolutional(10, 10, 1))
            .build())
    net = MultiLayerNetwork(conf).init()
    rng = np.random.RandomState(1)
    x = rng.rand(16, 10, 10, 1).astype(np.float32)
    y = np.zeros((16, 2), np.float32)
    y[np.arange(16), (x.mean(axis=(1, 2, 3)) > 0.5).astype(int)] = 0.9
    s0 = None
    for i in range(20):
        net.fit(x, y)
        if i == 0:
            s0 = net.score()
    assert net.score() < s0, (s0, net.score())


def test_vertex_tranche2_in_graphs():
    """L2Vertex / LastTimeStepVertex / DuplicateToTimeSeriesVertex /
    ReverseTimeSeriesVertex / PreprocessorVertex wired into a
    ComputationGraph (ref: vertex.impl.* completion)."""
    import jax
    import jax.numpy as jnp
    from deeplearning4j_tpu.nn.conf.configuration import NeuralNetConfiguration
    from deeplearning4j_tpu.nn.conf.inputs import InputType
    from deeplearning4j_tpu.nn.conf.layers import (DenseLayer, LSTM,
                                                   OutputLayer)
    from deeplearning4j_tpu.nn.graph import ComputationGraph
    from deeplearning4j_tpu.nn.graph_conf import (
        ComputationGraphConfiguration, DuplicateToTimeSeriesVertex,
        L2Vertex, LastTimeStepVertex, ReverseTimeSeriesVertex)
    from deeplearning4j_tpu.optim.updaters import Adam
    # encoder-summary + reversed-series consumer: exercises all 4 vertices
    g = (NeuralNetConfiguration.builder()
         .seed(4).updater(Adam(1e-2))
         .graph_builder()
         .add_inputs("seq")
         .add_vertex("rev", ReverseTimeSeriesVertex(), "seq")
         .add_layer("enc", LSTM(n_out=6, activation="tanh"), "rev")
         .add_vertex("last", LastTimeStepVertex(), "enc")
         .add_vertex("dup", DuplicateToTimeSeriesVertex(), "last", "seq")
         .add_vertex("dist", L2Vertex(), "last", "last")
         .add_layer("declstm", LSTM(n_out=4, activation="tanh"), "dup")
         .add_vertex("declast", LastTimeStepVertex(), "declstm")
         .add_layer("out", OutputLayer(n_out=2, activation="softmax",
                                       loss_function="negativeloglikelihood"),
                    "declast")
         .set_outputs("out")
         .set_input_types(InputType.recurrent(3, 5))
         .build())
    cg = ComputationGraph(g).init()
    rng = np.random.RandomState(0)
    x = rng.randn(8, 5, 3).astype(np.float32)
    y = np.eye(2, dtype=np.float32)[rng.randint(0, 2, 8)]
    s0 = None
    for i in range(10):
        cg.fit(x, y)
        if i == 0:
            s0 = cg.score()
    assert cg.score() < s0
    # JSON roundtrip keeps the vertex types
    back = ComputationGraphConfiguration.from_json(g.to_json())
    assert back is not None


def test_preprocessor_vertex():
    from deeplearning4j_tpu.nn.conf.preprocessors import (
        RnnToFeedForwardPreProcessor)
    from deeplearning4j_tpu.nn.graph_conf import PreprocessorVertex
    import jax.numpy as jnp
    v = PreprocessorVertex.wrap(RnnToFeedForwardPreProcessor())
    x = jnp.asarray(np.random.RandomState(1).randn(2, 4, 3)
                    .astype(np.float32))
    out = v.apply([x])
    assert out.shape == (8, 3)            # (N*T, C) folding
    # dict roundtrip
    from deeplearning4j_tpu.nn.graph_conf import vertex_from_dict
    v2 = vertex_from_dict(v.to_dict())
    assert isinstance(v2, PreprocessorVertex)


def test_last_time_step_vertex_masked():
    """LastTimeStepVertex selects each example's last UNMASKED step when
    the graph is fed a sequence mask (ref parity: the reference vertex is
    mask-aware)."""
    import jax.numpy as jnp
    from deeplearning4j_tpu.nn.graph_conf import LastTimeStepVertex
    x = jnp.asarray(np.arange(2 * 4 * 3, dtype=np.float32)
                    .reshape(2, 4, 3))
    mask = jnp.asarray([[1, 1, 0, 0], [1, 1, 1, 1]], jnp.float32)
    out = LastTimeStepVertex().apply([x], mask=mask)
    np.testing.assert_array_equal(np.asarray(out[0]), np.asarray(x[0, 1]))
    np.testing.assert_array_equal(np.asarray(out[1]), np.asarray(x[1, 3]))
    # unmasked: plain last step
    out2 = LastTimeStepVertex().apply([x])
    np.testing.assert_array_equal(np.asarray(out2), np.asarray(x[:, -1]))
    # interior-gap mask [1,0,1,0]: the last index where mask==1 is 2 —
    # NOT sum(mask)-1 == 1 (the reference scans for the last set index);
    # all-zero rows fall back to index 0
    gap = jnp.asarray([[1, 0, 1, 0], [0, 0, 0, 0]], jnp.float32)
    out3 = LastTimeStepVertex().apply([x], mask=gap)
    np.testing.assert_array_equal(np.asarray(out3[0]), np.asarray(x[0, 2]))
    np.testing.assert_array_equal(np.asarray(out3[1]), np.asarray(x[1, 0]))


def test_depthwise_conv_rejects_inconsistent_n_out():
    """An explicit nOut != nIn*depthMultiplier must raise, not silently
    report a different output type than the conv actually produces."""
    from deeplearning4j_tpu.nn.conf.inputs import InputType
    from deeplearning4j_tpu.nn.conf.layers2 import DepthwiseConvolution2D
    lyr = DepthwiseConvolution2D(kernel_size=(3, 3), depth_multiplier=2,
                                 n_out=5)
    with pytest.raises(ValueError, match="depthMultiplier"):
        lyr.set_n_in(InputType.convolutional(8, 8, 2))
    ok = DepthwiseConvolution2D(kernel_size=(3, 3), depth_multiplier=2)
    ok.set_n_in(InputType.convolutional(8, 8, 2))
    assert ok.n_out == 4


def test_one_pass_moments_clamp_and_parity():
    """ops/moments.one_pass_moments: parity with jnp.var where stable, and
    the var>=0 clamp under the f32 catastrophic-cancellation regime that
    the one-pass E[x^2]-E[x]^2 form is exposed to (large |mean| vs tiny
    std) — a negative variance would NaN every rsqrt(var+eps) downstream."""
    import jax.numpy as jnp
    from deeplearning4j_tpu.ops.moments import one_pass_moments

    rng = np.random.default_rng(0)
    x = jnp.asarray(rng.normal(2.0, 3.0, (64, 32)).astype(np.float32))
    mean, var = one_pass_moments(x, 0)
    np.testing.assert_allclose(np.asarray(mean), np.asarray(jnp.mean(x, 0)),
                               rtol=1e-6)
    np.testing.assert_allclose(np.asarray(var), np.asarray(jnp.var(x, 0)),
                               rtol=1e-4, atol=1e-5)
    # cancellation regime: mean ~3e3, std ~1e-3 -> E[x^2]-mean^2 underflows
    # f32 and can go negative; the clamp must keep it >= 0 (finite rsqrt)
    bad = jnp.asarray(
        (3000.0 + rng.normal(0, 1e-3, (256,))).astype(np.float32))
    _, v = one_pass_moments(bad, 0)
    assert float(v) >= 0.0
    assert np.isfinite(float(jax.lax.rsqrt(v + 1e-5)))


def test_batchnorm_layer_survives_large_mean_activations():
    """BatchNormalization.apply with offset-heavy inputs: running var stays
    >= 0 and the normalized output is finite (regression for the one-pass
    moments change)."""
    from deeplearning4j_tpu.nn.conf.layers import BatchNormalization

    bn = BatchNormalization()
    bn.n_out = 4
    params = bn.init_params(jax.random.key(0))
    state = bn.init_state()
    rng = np.random.default_rng(1)
    x = jnp.asarray(
        (1500.0 + rng.normal(0, 1e-3, (32, 4))).astype(np.float32))
    out, new_state = bn.apply(params, x, training=True, state=state)
    assert np.all(np.isfinite(np.asarray(out)))
    assert np.all(np.asarray(new_state["var"]) >= 0.0)
