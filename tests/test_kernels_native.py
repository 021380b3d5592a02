"""Pallas kernel crosschecks (the cuDNN-crosscheck analog, SURVEY §4) and
native host-ops tests."""
import numpy as np
import pytest

import jax
import jax.numpy as jnp

from deeplearning4j_tpu.kernels import (flash_attention, threshold_decode,
                                        threshold_encode)
from deeplearning4j_tpu.kernels.flash_attention import naive_attention


def _qkv(b, t, d, seed=0, dtype="float32"):
    rng = np.random.RandomState(seed)
    return tuple(jnp.asarray(rng.randn(b, t, d).astype(dtype)) * 0.3
                 for _ in range(3))


class TestFlashAttention:
    def test_matches_naive(self):
        q, k, v = _qkv(2, 64, 16)
        out = flash_attention(q, k, v, block_q=32, block_k=32)
        ref = naive_attention(q, k, v)
        assert np.allclose(out, ref, atol=1e-5), np.abs(out - ref).max()

    def test_causal_matches_naive(self):
        q, k, v = _qkv(2, 48, 8, seed=1)
        out = flash_attention(q, k, v, causal=True, block_q=16, block_k=16)
        ref = naive_attention(q, k, v, causal=True)
        assert np.allclose(out, ref, atol=1e-5)

    def test_ragged_seq_blocks(self):
        # seq length not divisible by block size
        q, k, v = _qkv(1, 50, 8, seed=2)
        out = flash_attention(q, k, v, block_q=16, block_k=16)
        ref = naive_attention(q, k, v)
        assert np.allclose(out, ref, atol=1e-5)

    def test_4d_input(self):
        rng = np.random.RandomState(3)
        q, k, v = (jnp.asarray(rng.randn(2, 4, 32, 8).astype("f4")) * 0.3
                   for _ in range(3))
        out = flash_attention(q, k, v, block_q=16, block_k=16)
        assert out.shape == (2, 4, 32, 8)

    def test_gradients_match_naive(self):
        q, k, v = _qkv(1, 32, 8, seed=4)

        def loss_flash(q, k, v):
            return jnp.sum(flash_attention(q, k, v, causal=True,
                                           block_q=16, block_k=16) ** 2)

        def loss_naive(q, k, v):
            return jnp.sum(naive_attention(q, k, v, causal=True) ** 2)

        gf = jax.grad(loss_flash, argnums=(0, 1, 2))(q, k, v)
        gn = jax.grad(loss_naive, argnums=(0, 1, 2))(q, k, v)
        for a, b, name in zip(gf, gn, "qkv"):
            assert np.allclose(a, b, atol=1e-4), (name, np.abs(a - b).max())

    def test_inside_jit_and_memory_shape(self):
        q, k, v = _qkv(1, 128, 16, seed=5)
        f = jax.jit(lambda q, k, v: flash_attention(q, k, v, block_q=64,
                                                    block_k=64))
        out = f(q, k, v)
        assert np.allclose(out, naive_attention(q, k, v), atol=1e-5)


class TestThresholdCodec:
    def test_roundtrip(self):
        rng = np.random.RandomState(0)
        g = jnp.asarray(rng.randn(50).astype("f4"))
        enc, residual = threshold_encode(g, 1.0, capacity=64)
        dec = threshold_decode(enc, 1.0, (50,))
        # decoded + residual reconstructs the original exactly
        assert np.allclose(np.asarray(dec) + np.asarray(residual),
                           np.asarray(g), atol=1e-6)
        n = int(enc[0])
        assert n == int(np.sum(np.abs(np.asarray(g)) >= 1.0))

    def test_capacity_cap(self):
        g = jnp.ones((100,)) * 5.0
        enc, residual = threshold_encode(g, 1.0, capacity=10)
        assert int(enc[0]) == 10
        dec = threshold_decode(enc, 1.0, (100,))
        assert float(jnp.sum(dec)) == pytest.approx(10.0)
        # unencoded elements keep full residual; encoded keep 4.0
        assert float(jnp.max(residual)) == pytest.approx(5.0)
        assert float(jnp.min(residual)) == pytest.approx(4.0)

    def test_jit_static_shapes(self):
        g = jnp.asarray(np.random.RandomState(1).randn(4, 8).astype("f4"))
        enc, res = threshold_encode(g, 0.5, capacity=16)
        assert enc.shape == (17,)
        assert res.shape == (4, 8)
        dec = threshold_decode(enc, 0.5, (4, 8))
        assert dec.shape == (4, 8)


import shutil

_HAS_GXX = shutil.which("g++") is not None


class TestNativeHostOps:
    def test_library_builds(self):
        from deeplearning4j_tpu import native
        if not _HAS_GXX:
            pytest.skip("no g++ toolchain; numpy fallback is the designed path")
        assert native.is_native(), "g++ build of host ops failed"

    def test_threshold_host_matches_jax(self):
        from deeplearning4j_tpu import native
        rng = np.random.RandomState(2)
        g = rng.randn(64).astype("f4")
        enc_h, res_h = native.threshold_encode_host(g, 1.0, 32)
        enc_j, res_j = threshold_encode(jnp.asarray(g), 1.0, 32)
        assert enc_h[0] == int(enc_j[0])
        assert set(enc_h[1:1 + enc_h[0]]) == \
            set(int(x) for x in np.asarray(enc_j[1:]) if x != 0)
        assert np.allclose(res_h, np.asarray(res_j), atol=1e-6)
        # decode accumulates into target
        dec = native.threshold_decode_host(enc_h, 1.0, np.zeros(64, "f4"))
        assert np.allclose(dec + res_h, g, atol=1e-6)

    def test_csv_native(self, tmp_path):
        from deeplearning4j_tpu import native
        p = tmp_path / "d.csv"
        p.write_text("# header\n1.5,2,3\n4,hello,6\n\n7,8,9\n")
        arr = native.csv_read_floats(str(p), skip_rows=1)
        assert arr.shape == (3, 3)
        assert arr[0, 0] == pytest.approx(1.5)
        assert np.isnan(arr[1, 1])
        assert arr[2, 2] == pytest.approx(9.0)

    def test_shuffle_indices(self):
        from deeplearning4j_tpu import native
        a = native.shuffle_indices(100, seed=7)
        b = native.shuffle_indices(100, seed=7)
        c = native.shuffle_indices(100, seed=8)
        assert np.array_equal(a, b)
        assert not np.array_equal(a, c)
        assert sorted(a.tolist()) == list(range(100))


@pytest.mark.slow


def test_transformer_flash_path_matches_plain():
    """Forcing the flash backend must not change TransformerLM outputs
    (the cuDNN-crosscheck analog at model level)."""
    import deeplearning4j_tpu.models.transformer as tr
    import numpy as np
    cfg = tr.TransformerConfig(vocab_size=64, n_layers=1, n_heads=2,
                               d_model=16, d_ff=32, max_len=32,
                               dtype="float32")
    model = tr.TransformerLM(cfg)
    params = model.init_params(jax.random.key(0))
    tokens = np.random.RandomState(0).randint(0, 64, (2, 16)).astype("i4")
    try:
        tr.FLASH_ATTENTION = False
        out_plain = np.asarray(model.apply(params, tokens))
        tr.FLASH_ATTENTION = True
        out_flash = np.asarray(model.apply(params, tokens))
    finally:
        tr.FLASH_ATTENTION = None
    assert np.allclose(out_plain, out_flash, atol=2e-4), \
        np.abs(out_plain - out_flash).max()


# ------------------------------------------- the packed (B, T, H, hd) kernels
def _bthd(t, dtype, seed, b=1, h=2, hd=64):
    rng = np.random.RandomState(seed)
    return tuple(jnp.asarray(rng.randn(b, t, h, hd).astype("f4") * 0.5, dtype)
                 for _ in range(3))


def _naive_bthd(q, k, v, causal):
    # float32 from the inputs as the kernel got them, heads on axis 1
    q, k, v = (x.astype(jnp.float32).transpose(0, 2, 1, 3) for x in (q, k, v))
    return naive_attention(q, k, v, causal=causal).transpose(0, 2, 1, 3)


@pytest.mark.parametrize("dtype", ["float32", "bfloat16"])
@pytest.mark.parametrize("causal", [False, True], ids=["full", "causal"])
@pytest.mark.parametrize("t,block", [(64, 32), (50, 16), (24, 64)],
                         ids=["blocks", "ragged", "under_one_block"])
def test_packed_kernels_match_naive(t, block, causal, dtype):
    """Forward and all three gradients of the Pallas kernels, two heads of
    64 a 128-lane slab, against the plain reference in float32."""
    from deeplearning4j_tpu.kernels.flash_attention import (
        flash_attention_bthd)
    q, k, v = _bthd(t, dtype, seed=t)
    w = jnp.asarray(np.random.RandomState(1).randn(*q.shape), jnp.float32)

    def flash(q, k, v):
        return flash_attention_bthd(q, k, v, causal=causal, block_q=block,
                                    block_k=block)

    out = flash(q, k, v)
    assert out.shape == q.shape and out.dtype == q.dtype
    tol = 1e-5 if dtype == "float32" else 2e-2
    ref = _naive_bthd(q, k, v, causal)
    assert np.allclose(out.astype(jnp.float32), ref, atol=tol), \
        np.abs(out.astype(jnp.float32) - ref).max()
    got = jax.grad(lambda *a: jnp.sum(flash(*a).astype(jnp.float32) * w),
                   argnums=(0, 1, 2))(q, k, v)
    want = jax.grad(lambda *a: jnp.sum(_naive_bthd(*a, causal) * w),
                    argnums=(0, 1, 2))(q, k, v)
    for a, b, name in zip(got, want, "qkv"):
        a, b = a.astype(jnp.float32), b.astype(jnp.float32)
        assert a.dtype == b.dtype and np.allclose(a, b, atol=10 * tol), \
            (name, np.abs(a - b).max())


def test_odd_head_sizes_take_the_transposed_route():
    """A head size that neither fills nor evenly shares a 128-lane slab."""
    from deeplearning4j_tpu.kernels.flash_attention import (
        flash_attention_bthd)
    q, k, v = _bthd(40, "float32", seed=7, h=3, hd=20)
    out = flash_attention_bthd(q, k, v, causal=True, block_q=16, block_k=16)
    assert np.allclose(out, _naive_bthd(q, k, v, True), atol=1e-5)


def _tiny_lm(mesh=None, n_heads=2):
    import deeplearning4j_tpu.models.transformer as tr
    cfg = tr.TransformerConfig(vocab_size=61, n_layers=2, n_heads=n_heads,
                               d_model=64 * n_heads, max_len=32,
                               fused_qkv=True)
    return tr, tr.TransformerLM(cfg, mesh)


def test_loss_gradients_equal_with_either_backend(monkeypatch, caplog):
    """``TransformerLM.loss_fn`` and its gradients with the kernels forced
    on and forced off; the trace says which backend it took, once."""
    tr, model = _tiny_lm()
    params = model.init_params(jax.random.key(0))
    toks = np.random.RandomState(0).randint(0, 61, (2, 33)).astype("i4")
    got = {}
    for name, flag in (("xla", False), ("flash", True)):
        monkeypatch.setattr(tr, "FLASH_ATTENTION", flag)
        caplog.clear()
        with caplog.at_level("INFO", logger=tr.__name__):
            got[name] = jax.jit(jax.value_and_grad(model.loss_fn))(
                params, toks[:, :-1], toks[:, 1:])
        said = [r.getMessage() for r in caplog.records
                if r.getMessage().startswith("attention backend:")]
        assert said == [f"attention backend: {name}: "
                        f"FLASH_ATTENTION = {flag}"], said
    (l0, g0), (l1, g1) = got["xla"], got["flash"]
    assert abs(float(l0) - float(l1)) < 1e-5
    for a, b in zip(jax.tree.leaves(g0), jax.tree.leaves(g1)):
        assert np.allclose(a, b, atol=2e-5), np.abs(a - b).max()


def test_attn_under_shard_map_on_a_dp2_tp2_mesh(monkeypatch):
    """``_attn`` on a ``data=2, model=2`` mesh wraps the kernels in
    ``shard_map`` (rows over data, heads over model): output and gradients
    equal the unsharded call's."""
    from jax.sharding import NamedSharding, PartitionSpec as P
    from deeplearning4j_tpu.parallel.mesh import (DATA_AXIS, MODEL_AXIS,
                                                  MeshSpec)
    mesh = MeshSpec({DATA_AXIS: 2, MODEL_AXIS: 2}).build(jax.devices()[:4])
    tr, sharded = _tiny_lm(mesh, n_heads=4)
    _tr, plain = _tiny_lm(None, n_heads=4)
    monkeypatch.setattr(tr, "FLASH_ATTENTION", True)
    p = plain.init_params(jax.random.key(1))["blocks"][0]["attn"]
    x = jnp.asarray(np.random.RandomState(2).randn(4, 32, 256), jnp.float32)

    def run(model, m, p, x):
        f = lambda p, x: jnp.sum(model._attn(p, x, m) ** 2)  # noqa: E731
        return jax.jit(jax.value_and_grad(f, argnums=(0, 1)))(p, x)

    want = run(plain, None, p, x)
    ps = jax.device_put(p, sharded.param_shardings(mesh)["blocks"][0]["attn"])
    xs = jax.device_put(x, NamedSharding(mesh, P(DATA_AXIS, None, None)))
    text = jax.jit(lambda p, x: sharded._attn(p, x, mesh)).lower(
        ps, xs).as_text()
    assert "sdy.manual_computation" in text      # what shard_map lowers to
    got = run(sharded, mesh, ps, xs)
    for a, b in zip(jax.tree.leaves(got), jax.tree.leaves(want)):
        assert np.allclose(a, b, rtol=1e-4, atol=1e-4), np.abs(a - b).max()
