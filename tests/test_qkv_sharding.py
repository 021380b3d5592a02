"""The fused projection of ``TransformerLM`` on a mesh with a model axis is
read by heads (``_qkv``, ``_wqkv_by_heads``): the weight crosses the model
axis, once a layer each way, and no (rows, T, ·) activation does for layout.
Loss and gradients equal the unsharded model's, the leaf, its gradient and
Adam's moments keep the stored ``[q | k | v]`` layout and sharding, and every
caller the mechanism does not concern lowers to what it lowered to."""
import collections

import jax
import jax.numpy as jnp
import numpy as np
import optax
import pytest
from jax.sharding import NamedSharding, PartitionSpec as P

from deeplearning4j_tpu.models.transformer import (TRAIN_STEP_FN,
                                                   TransformerConfig,
                                                   TransformerLM)
from deeplearning4j_tpu.observability.compile_watch import (
    global_compile_watch)
from deeplearning4j_tpu.parallel.mesh import (DATA_AXIS, MODEL_AXIS,
                                              SEQ_AXIS, MeshSpec)
from test_head_sharding import (_backend_compiles, _collective_shapes, _mesh,
                                _placed)

V, D_MODEL, ROWS, T = 255, 32, 8, 24
PAGE = 8

# name -> (mesh axes or None, heads, fused leaf, the layout ``_qkv`` takes)
CASES = {
    "dp2_tp2": ({DATA_AXIS: 2, MODEL_AXIS: 2}, 4, True, "heads"),
    "tp_only": ({MODEL_AXIS: 2}, 4, True, "heads"),
    "dp2_tp2_sp2": ({DATA_AXIS: 2, MODEL_AXIS: 2, SEQ_AXIS: 2}, 4, True,
                    "heads"),
    "no_mesh": (None, 4, True, "columns"),
    "dp_only": ({DATA_AXIS: 4}, 4, True, "columns"),
    "dp2_seq2": ({DATA_AXIS: 2, MODEL_AXIS: 1, SEQ_AXIS: 2}, 4, True,
                 "columns"),
    "tp2_three_heads": ({DATA_AXIS: 2, MODEL_AXIS: 2}, 3, True, "columns"),
    "dp2_tp2_unfused": ({DATA_AXIS: 2, MODEL_AXIS: 2}, 4, False, "columns"),
}
BY_HEADS = [c for c, v in CASES.items() if v[3] == "heads"]
BY_COLUMNS = [c for c, v in CASES.items() if v[3] == "columns"]


class PlainQkvLM(TransformerLM):
    """``_qkv`` as it was before the layout: what every caller without a
    model axis that divides the heads of a fused leaf must still lower to."""

    def _qkv(self, p, x, mesh=None):
        c = self.config
        b, t, _ = x.shape
        h, hd = c.n_heads, c.d_model // c.n_heads
        with jax.named_scope("attn_qkv"):
            if "wqkv" in p:
                qkv = x @ p["wqkv"]                   # one MXU op, one x read
                q, k, v = jnp.split(qkv, 3, axis=-1)
                q = q.reshape(b, t, h, hd)
                k = k.reshape(b, t, h, hd)
                v = v.reshape(b, t, h, hd)
            else:
                q = (x @ p["wq"]).reshape(b, t, h, hd)
                k = (x @ p["wk"]).reshape(b, t, h, hd)
                v = (x @ p["wv"]).reshape(b, t, h, hd)
        return q, k, v


def _config(heads=4, fused=True):
    return TransformerConfig(vocab_size=V, n_layers=2, n_heads=heads,
                             d_model=D_MODEL * heads // 4, max_len=T,
                             fused_qkv=fused)


def _batch():
    toks = np.random.default_rng(30).integers(0, V, (ROWS, T + 1),
                                              dtype=np.int32)
    return jnp.asarray(toks[:, :-1]), jnp.asarray(toks[:, 1:])


def _build(case, cls=TransformerLM, fused=None):
    axes, heads, leaf, _ = CASES[case]
    mesh = _mesh(axes)
    model = cls(_config(heads, leaf if fused is None else fused), mesh)
    weights = model.init_params(jax.random.key(3))
    return (model, mesh) + _placed(model, mesh, weights, _batch())


def _on_the_wire(model, params, batch):
    """Collectives of the compiled loss-and-gradient program: (kind, dims)
    for every result, tuples taken apart."""
    hlo = jax.jit(jax.value_and_grad(model.loss_fn)).lower(
        params, *batch).compile().as_text()
    out = collections.Counter()
    for kind, shapes in _collective_shapes(hlo):
        for dims in shapes:
            out[kind.removesuffix("-start"), dims] += 1
    return out


def _is_activation(dims):
    """(rows, T, ·) or a share of it: leading dimensions a share of the rows
    and a share of T. No weight of the model begins that way (they begin
    with d_model, d_ff, V, or their halves: 32, 128, 255, 16, 64)."""
    shares = lambda n: {n // k for k in (1, 2, 4, 8) if n % k == 0}  # noqa
    return (len(dims) >= 3 and dims[0] in shares(ROWS)
            and dims[1] in shares(T))


@pytest.mark.parametrize("case", BY_HEADS)
def test_only_the_weight_crosses_the_model_axis(case):
    model, mesh, params, batch = _build(case)
    wire = _on_the_wire(model, params, batch)
    unfused = _build(case, fused=False)
    wire_unfused = _on_the_wire(unfused[0], *unfused[2:])
    plain = _build(case, PlainQkvLM)
    wire_plain = _on_the_wire(plain[0], *plain[2:])

    def layout_moves(w):
        return {k: n for k, n in w.items()
                if k[0] in ("collective-permute", "all-to-all")}

    def activations(w):
        return sum(n for (_, dims), n in w.items() if _is_activation(dims))

    # no permute and no all-to-all but the ring's own, which the unfused
    # model (whole heads a share already) has alike; none without a seq axis
    assert layout_moves(wire) == layout_moves(wire_unfused)
    if SEQ_AXIS not in mesh.axis_names:
        assert not layout_moves(wire)
    # and the lines as they were do permute activations, so this can fail
    assert activations(layout_moves(wire_plain)) > activations(
        layout_moves(wire_unfused))
    assert activations(wire) <= activations(wire_unfused)
    assert activations(wire) < activations(wire_plain)
    # what is new beside the unfused model is the weight, whole one way and
    # a chip's stored share the other, once a layer each
    d, layers = model.config.d_model, model.config.n_layers
    tp = mesh.shape[MODEL_AXIS]
    new = {k: n for k, n in wire.items()
           if k not in wire_unfused and k[0] != "all-reduce"}
    assert new == {("all-gather", (d, 3 * d)): layers,
                   ("reduce-scatter", (d, 3 * d // tp)): layers}


@pytest.mark.parametrize("case", BY_HEADS)
def test_same_numbers_in_the_stored_layout_and_one_compile(case):
    model, mesh, params, batch = _build(case)
    loss, grads = jax.jit(jax.value_and_grad(model.loss_fn))(params, *batch)
    plain = TransformerLM(model.config)
    weights = jax.device_get(params)
    ref_loss, ref_grads = jax.jit(jax.value_and_grad(plain.loss_fn))(
        weights, *_batch())
    assert abs(float(loss) - float(ref_loss)) < 1e-5
    for (path, g), ref in zip(jax.tree_util.tree_leaves_with_path(grads),
                              jax.tree.leaves(ref_grads), strict=True):
        np.testing.assert_allclose(
            np.asarray(g), np.asarray(ref), rtol=2e-4, atol=2e-5,
            err_msg=jax.tree_util.keystr(path))

    # two steps of the whole train step: parameters and Adam's moments come
    # back laid out as they went in, so the second call compiles nothing
    opt = optax.adamw(1e-3)
    shardings = model.param_shardings(mesh)
    where = optax.tree_utils.tree_map_params(
        opt, lambda _, s: s, jax.eval_shape(opt.init, params), shardings,
        transform_non_params=lambda _: NamedSharding(mesh, P()))
    state = jax.jit(opt.init, out_shardings=where)(params)
    compiles, stop_listening = _backend_compiles()
    watch = global_compile_watch()
    traced0 = watch.count_for(TRAIN_STEP_FN)
    step = model.make_train_step(opt)
    after_first = None
    for _ in range(2):
        params, state, _ = step(params, state, *batch)
        if after_first is None:
            after_first = len(compiles)
            first = jax.device_get((params, state[0].mu))  # donated next
    stop_listening()
    # (on a seq axis ``pos_emb`` comes back split over it, whatever ``_qkv``
    # does, and the second call compiles for that: the leaves still must)
    if SEQ_AXIS not in mesh.axis_names:
        assert watch.count_for(TRAIN_STEP_FN) - traced0 == 1
        assert len(compiles) == after_first
    for tree in (params, state[0].mu, state[0].nu):
        for (path, a), s in zip(jax.tree_util.tree_leaves_with_path(tree),
                                jax.tree.leaves(shardings), strict=True):
            name = jax.tree_util.keystr(path)
            assert "pos_emb" in name or a.sharding.is_equivalent_to(
                s, a.ndim), (name, a.sharding, s)
    # the first moment after one step is (1 - b1) x the gradient: columns
    # [q | k | v] as the unsharded model orders them. And the leaf moved
    # against its gradient's sign by the learning rate, column for column
    for li in range(model.config.n_layers):
        ref = np.asarray(ref_grads["blocks"][li]["attn"]["wqkv"])
        mu = np.asarray(first[1]["blocks"][li]["attn"]["wqkv"])
        np.testing.assert_allclose(mu, 0.1 * ref, rtol=2e-4, atol=2e-6)
        w0 = np.asarray(weights["blocks"][li]["attn"]["wqkv"])
        moved = np.asarray(first[0]["blocks"][li]["attn"]["wqkv"]) - w0
        sure = np.abs(ref) > 1e-5
        assert sure.mean() > 0.5
        np.testing.assert_allclose(
            moved[sure], (-1e-3 * (np.sign(ref) + 1e-4 * w0))[sure],
            rtol=0, atol=2e-6)


def _lowered(model, program, params, batch):
    if program == "train_step":
        opt = optax.adamw(1e-3)
        return model.make_train_step(opt).lower(
            params, jax.eval_shape(opt.init, params), *batch).as_text()
    if program == "prefill":
        return jax.jit(model.prefill).lower(params, batch[0]).as_text()
    pool = jax.eval_shape(lambda: model.init_paged_cache(ROWS * T // PAGE + 1,
                                                         PAGE))
    tables = jax.ShapeDtypeStruct((ROWS, T // PAGE), jnp.int32)
    positions = jax.ShapeDtypeStruct((ROWS,), jnp.int32)
    return jax.jit(
        lambda p, pool, tab, tok, pos: model.decode_window_paged(
            p, pool, tab, tok, pos, PAGE)).lower(
        params, pool, tables, batch[0][:, :1], positions).as_text()


@pytest.mark.parametrize("program", ["train_step", "prefill",
                                     "decode_window_paged"])
@pytest.mark.parametrize("case", BY_COLUMNS)
def test_other_callers_lower_to_what_they_did(case, program):
    model, _, params, batch = _build(case)
    plain = _build(case, PlainQkvLM)[0]
    assert _lowered(model, program, params, batch) == _lowered(
        plain, program, params, batch)


@pytest.mark.parametrize("case", list(CASES))
def test_the_layout_is_said_once_a_trace(case, caplog):
    model, _, params, batch = _build(case)
    opt = optax.adamw(1e-3)
    with caplog.at_level("INFO",
                         logger="deeplearning4j_tpu.models.transformer"):
        model.make_train_step(opt).lower(
            params, jax.eval_shape(opt.init, params), *batch)
    said = [r.getMessage() for r in caplog.records
            if r.getMessage().startswith("qkv layout:")]
    assert len(said) == 1, said
    assert said[0].startswith(f"qkv layout: {CASES[case][3]}: "), said
