"""Gradient checkpointing (jax.checkpoint per layer — SURVEY §7's
rematerialisation lever). Correctness contract: identical losses and
gradients with and without remat; only the backward-pass memory changes."""
import jax
import jax.numpy as jnp
import numpy as np
import pytest

from deeplearning4j_tpu.nn.conf.configuration import NeuralNetConfiguration
from deeplearning4j_tpu.nn.conf.inputs import InputType
from deeplearning4j_tpu.nn.conf import layers as L
from deeplearning4j_tpu.nn.multilayer import MultiLayerNetwork
from deeplearning4j_tpu.optim.updaters import Adam


def _conf(remat):
    b = (NeuralNetConfiguration.builder().seed(3).updater(Adam(1e-2)).list()
         .layer(L.DenseLayer(n_out=16, activation="relu"))
         .layer(L.DenseLayer(n_out=16, activation="tanh"))
         .layer(L.OutputLayer(n_out=4, activation="softmax",
                              loss_function="negativeloglikelihood"))
         .set_input_type(InputType.feed_forward(8)))
    if remat:
        b.gradient_checkpointing()
    return b.build()


def test_remat_matches_plain_training():
    rng = np.random.RandomState(0)
    x = rng.rand(8, 8).astype("float32")
    y = np.eye(4, dtype="float32")[rng.randint(0, 4, 8)]
    nets = {}
    for remat in (False, True):
        net = MultiLayerNetwork(_conf(remat)).init()
        for _ in range(5):
            net.fit(x, y)
        nets[remat] = net
    assert np.isclose(nets[False].score(), nets[True].score(), rtol=1e-5)
    for a, b in zip(jax.tree.leaves(nets[False]._params),
                    jax.tree.leaves(nets[True]._params)):
        np.testing.assert_allclose(np.asarray(a), np.asarray(b), rtol=1e-5,
                                   atol=1e-6)


def test_remat_json_roundtrip():
    conf = _conf(True)
    from deeplearning4j_tpu.nn.conf.configuration import MultiLayerConfiguration
    back = MultiLayerConfiguration.from_json(conf.to_json())
    assert back.remat is True


def test_remat_policy_matches_plain_training():
    """A save policy ("dots": keep matmul outputs) changes only what is
    rematerialised, never the math — training under it is numerically
    identical to plain remat and to no remat."""
    rng = np.random.RandomState(0)
    x = rng.rand(8, 8).astype("float32")
    y = np.eye(4, dtype="float32")[rng.randint(0, 4, 8)]
    ref = MultiLayerNetwork(_conf(False)).init()
    b = (NeuralNetConfiguration.builder().seed(3).updater(Adam(1e-2)).list()
         .layer(L.DenseLayer(n_out=16, activation="relu"))
         .layer(L.DenseLayer(n_out=16, activation="tanh"))
         .layer(L.OutputLayer(n_out=4, activation="softmax",
                              loss_function="negativeloglikelihood"))
         .set_input_type(InputType.feed_forward(8)))
    b.gradient_checkpointing(policy="dots")
    net = MultiLayerNetwork(b.build()).init()
    for _ in range(5):
        ref.fit(x, y)
        net.fit(x, y)
    assert np.isclose(ref.score(), net.score(), rtol=1e-5)
    for a, c in zip(jax.tree.leaves(ref._params),
                    jax.tree.leaves(net._params)):
        np.testing.assert_allclose(np.asarray(a), np.asarray(c), rtol=1e-5,
                                   atol=1e-6)


def test_remat_policy_json_roundtrip_and_validation():
    from deeplearning4j_tpu.nn._remat import checkpoint_policy
    from deeplearning4j_tpu.nn.conf.configuration import (
        MultiLayerConfiguration, NeuralNetConfiguration)
    b = (NeuralNetConfiguration.builder().seed(1).list()
         .layer(L.OutputLayer(n_out=2, activation="softmax",
                              loss_function="negativeloglikelihood"))
         .set_input_type(InputType.feed_forward(4)))
    b.gradient_checkpointing(policy="dots")
    conf = b.build()
    back = MultiLayerConfiguration.from_json(conf.to_json())
    assert back.remat_policy == "dots"
    assert checkpoint_policy(None) is None
    assert checkpoint_policy("dots") is not None
    import pytest
    with pytest.raises(ValueError, match="unknown remat policy"):
        checkpoint_policy("bogus")


def test_transformer_scan_remat_dots_matches():
    """The scan_layers OOM-fix combo (scan + remat + dots policy) is
    numerically identical to the plain loop — only backward memory
    scheduling differs (see benchmarks/ab/mfu_ladder_scan_remat_cpu.json
    for the compiled temp-bytes A/B)."""
    from deeplearning4j_tpu.models.transformer import (TransformerConfig,
                                                       TransformerLM)

    toks = jnp.asarray(np.random.default_rng(2).integers(0, 32, (2, 16)),
                       jnp.int32)
    tgts = jnp.roll(toks, -1, axis=1)
    outs = {}
    for tag, kw in (("loop", {}),
                    ("scan_dots", {"scan_layers": True, "remat": True,
                                   "remat_policy": "dots"})):
        cfg = TransformerConfig(vocab_size=32, n_layers=3, n_heads=2,
                                d_model=32, max_len=16, **kw)
        m = TransformerLM(cfg, mesh=None)
        p = m.init_params(jax.random.key(0))
        loss, grads = jax.value_and_grad(m.loss_fn)(p, toks, tgts)
        outs[tag] = (float(loss), grads)
    assert np.isclose(outs["loop"][0], outs["scan_dots"][0], rtol=1e-6)
    np.testing.assert_allclose(
        np.asarray(outs["loop"][1]["tok_emb"]),
        np.asarray(outs["scan_dots"][1]["tok_emb"]), rtol=1e-5, atol=1e-6)


def test_transformer_remat_matches():
    from deeplearning4j_tpu.models.transformer import (TransformerConfig,
                                                       TransformerLM)
    import optax

    outs = {}
    for remat in (False, True):
        cfg = TransformerConfig(vocab_size=32, n_layers=2, n_heads=2,
                                d_model=32, max_len=16, remat=remat)
        m = TransformerLM(cfg, mesh=None)
        p = m.init_params(jax.random.key(0))
        toks = jnp.asarray(np.random.default_rng(0).integers(0, 32, (2, 16)),
                           jnp.int32)
        tgts = jnp.roll(toks, -1, axis=1)
        loss, grads = jax.value_and_grad(m.loss_fn)(p, toks, tgts)
        outs[remat] = (float(loss), grads)
    assert np.isclose(outs[False][0], outs[True][0], rtol=1e-6)
    for a, b in zip(jax.tree.leaves(outs[False][1]),
                    jax.tree.leaves(outs[True][1])):
        np.testing.assert_allclose(np.asarray(a), np.asarray(b), rtol=1e-5,
                                   atol=1e-6)


def test_graph_remat_matches():
    from deeplearning4j_tpu.nn.graph import ComputationGraph

    rng = np.random.RandomState(1)
    x = rng.rand(8, 6).astype("float32")
    y = np.eye(3, dtype="float32")[rng.randint(0, 3, 8)]
    nets = {}
    for remat in (False, True):
        gb = (NeuralNetConfiguration.builder().seed(2).updater(Adam(1e-2))
              .graph_builder().add_inputs("in")
              .set_input_types(InputType.feed_forward(6)))
        if remat:
            gb.gradient_checkpointing()
        gb.add_layer("d", L.DenseLayer(n_out=12, activation="relu"), "in")
        gb.add_layer("out", L.OutputLayer(
            n_out=3, activation="softmax",
            loss_function="negativeloglikelihood"), "d")
        gb.set_outputs("out")
        net = ComputationGraph(gb.build()).init()
        for _ in range(4):
            net.fit(x, y)
        nets[remat] = net
    assert np.isclose(nets[False].score(), nets[True].score(), rtol=1e-5)


def test_scan_layers_matches_loop():
    """lax.scan over stacked blocks is numerically identical to the python
    loop (incl. gradients) and composes with remat."""
    from deeplearning4j_tpu.models.transformer import (TransformerConfig,
                                                       TransformerLM)

    toks = jnp.asarray(np.random.default_rng(1).integers(0, 32, (2, 16)),
                       jnp.int32)
    tgts = jnp.roll(toks, -1, axis=1)
    outs = {}
    for scan in (False, True):
        cfg = TransformerConfig(vocab_size=32, n_layers=3, n_heads=2,
                                d_model=32, max_len=16, scan_layers=scan,
                                remat=scan)      # scan path also remats
        m = TransformerLM(cfg, mesh=None)
        p = m.init_params(jax.random.key(0))
        loss, grads = jax.value_and_grad(m.loss_fn)(p, toks, tgts)
        outs[scan] = (float(loss), grads)
    assert np.isclose(outs[False][0], outs[True][0], rtol=1e-6)
    # embedding grads comparable across layouts (block grads are stacked)
    np.testing.assert_allclose(
        np.asarray(outs[False][1]["tok_emb"]),
        np.asarray(outs[True][1]["tok_emb"]), rtol=1e-5, atol=1e-6)


@pytest.mark.slow


def test_scan_layers_sharded_step():
    """Stacked blocks shard correctly (leading layer axis unsharded) and a
    full dp/tp train step runs on the 8-device mesh."""
    from deeplearning4j_tpu.models.transformer import (TransformerConfig,
                                                       make_sharded_lm)
    from deeplearning4j_tpu.parallel import MeshSpec

    mesh = MeshSpec.dp_tp_sp(data=2, model=2, seq=2).build(
        jax.devices()[:8])
    cfg = TransformerConfig(vocab_size=64, n_layers=2, n_heads=4,
                            d_model=64, max_len=32, scan_layers=True)
    model, params, opt_state, opt = make_sharded_lm(cfg, mesh)
    step = model.make_train_step(opt)
    toks = jnp.asarray(np.random.default_rng(0).integers(0, 64, (4, 32)),
                       jnp.int32)
    params, opt_state, loss = step(params, opt_state, toks,
                                   jnp.roll(toks, -1, axis=1))
    assert np.isfinite(float(loss))


def test_dense_step_carries_no_moe_aux():
    """Regression guard (round-4 driver bench): a dense (non-MoE) model's
    train step must not thread MoE aux telemetry through the layer stack —
    the scan carry is the hidden state alone, and the jaxpr contains no
    dead zero-aux adds. Deterministic twin of the CPU-ratio check, immune
    to machine-load noise."""
    import optax
    from deeplearning4j_tpu.models.transformer import (TransformerConfig,
                                                       TransformerLM)

    for scan in (False, True):
        cfg = TransformerConfig(vocab_size=64, n_layers=2, n_heads=4,
                                d_model=64, max_len=32, scan_layers=scan,
                                fused_qkv=True)
        m = TransformerLM(cfg, mesh=None)
        p = m.init_params(jax.random.key(0))
        opt = optax.adamw(1e-3)
        s = jax.eval_shape(opt.init, p)
        toks = jnp.zeros((2, 32), jnp.int32)

        def step(p_, s_, t_, g_):
            loss, grads = jax.value_and_grad(m.loss_fn)(p_, t_, g_)
            up, s2 = opt.update(grads, s_, p_)
            return optax.apply_updates(p_, up), s2, loss

        jaxpr = jax.make_jaxpr(step)(p, s, toks, toks)
        txt = str(jaxpr)
        assert "moe" not in txt.lower()
        if scan:
            # the scan carry of a dense model is (x,) — params are consts,
            # so every scan op's carry has exactly one (B,T,D)-shaped slot
            scans = [e for e in jaxpr.jaxpr.eqns if e.primitive.name == "scan"]
            assert scans, "scan_layers=True must lower to lax.scan"
            for e in scans:
                n_carry = e.params["num_carry"]
                assert n_carry <= 1, (
                    f"dense scan carry grew to {n_carry} slots — dead aux "
                    "telemetry is riding the layer stack again")
