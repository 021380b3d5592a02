"""The head and the loss of ``TransformerLM`` on a mesh with a model axis run
token-parallel over it (``_head_operands``): no collective of the step carries
the vocabulary beside a token dimension, loss and gradients equal the
unsharded model's, and every mesh without a model axis compiles to what the
plain head compiles to. Also ``make_sharded_lm``'s optimizer state: placed
like the parameters, so the step compiles once."""
import collections
import re

import jax
import jax.numpy as jnp
import numpy as np
import optax
import pytest
from jax.sharding import NamedSharding, PartitionSpec as P

from deeplearning4j_tpu.models.transformer import (TRAIN_STEP_FN,
                                                   TransformerConfig,
                                                   TransformerLM,
                                                   make_sharded_lm)
from deeplearning4j_tpu.observability.compile_watch import (
    global_compile_watch)
from deeplearning4j_tpu.parallel.mesh import (DATA_AXIS, MODEL_AXIS,
                                              SEQ_AXIS, MeshSpec)

# an odd vocabulary that no axis divides, with a divisor for ``ce_chunks``;
# no token count below (rows, T, their shares and products) equals V, a
# chunk of it, or a share of d_model, so a shape tells what it holds
V, CHUNKS, D_MODEL = 255, 3, 32
COLLECTIVES = ("all-reduce", "all-gather", "reduce-scatter", "all-to-all",
               "collective-permute")

# name -> (mesh axes or None, rows, T, the layout the head must take)
CASES = {
    "dp2_tp2": ({DATA_AXIS: 2, MODEL_AXIS: 2}, 8, 24, "rows"),
    "tp_only": ({MODEL_AXIS: 2}, 4, 24, "rows"),
    "dp2_tp2_sp2": ({DATA_AXIS: 2, MODEL_AXIS: 2, SEQ_AXIS: 2}, 4, 24,
                    "rows"),
    "dp2_tp2_one_row_a_group": ({DATA_AXIS: 2, MODEL_AXIS: 2}, 2, 24,
                                "tokens"),
    "dp2_tp2_nothing_divides": ({DATA_AXIS: 2, MODEL_AXIS: 2}, 2, 23,
                                "replicated"),
    "dp_only": ({DATA_AXIS: 4}, 8, 24, "replicated"),
    "dp2_seq2": ({DATA_AXIS: 2, MODEL_AXIS: 1, SEQ_AXIS: 2}, 4, 24,
                 "replicated"),
    "no_mesh": (None, 4, 24, "replicated"),
}


class PlainHeadLM(TransformerLM):
    """The head as it was before the layout: operands as the trunk left
    them. What every mesh without a model axis must still compile to."""

    def _head_operands(self, x, emb):
        return x, emb


def _config(ce_chunks):
    return TransformerConfig(vocab_size=V, n_layers=2, n_heads=4,
                             d_model=D_MODEL, max_len=24, fused_qkv=True,
                             ce_chunks=ce_chunks)


def _mesh(axes):
    if axes is None:
        return None
    return MeshSpec(dict(axes)).build(
        jax.devices()[:int(np.prod(list(axes.values())))])


def _batch(rows, t):
    toks = np.random.default_rng(rows * 100 + t).integers(
        0, V, (rows, t + 1), dtype=np.int32)
    return jnp.asarray(toks[:, :-1]), jnp.asarray(toks[:, 1:])


def _placed(model, mesh, params, batch):
    if mesh is None:
        return params, batch
    axes = [a if a in mesh.axis_names else None
            for a in (DATA_AXIS, SEQ_AXIS)]
    feed = NamedSharding(mesh, P(*axes))
    return (jax.device_put(params, model.param_shardings(mesh)),
            tuple(jax.device_put(a, feed) for a in batch))


def _grad_program(model, params, batch):
    return jax.jit(jax.value_and_grad(model.loss_fn)).lower(
        params, *batch).compile()


def _op_counts(hlo_text):
    """Instructions of a compiled module by operation kind."""
    kinds = re.findall(r"^\s*(?:ROOT )?\S+ = .*? ([a-z][a-z\-]*)\(",
                       hlo_text, re.M)
    return collections.Counter(kinds)


def _collective_shapes(hlo_text):
    """(kind, [dims, ...]) of every collective's results and operands."""
    out = []
    for line in hlo_text.splitlines():
        m = re.match(r"\s*(?:ROOT )?\S+ = (.*?) ([a-z\-]+)\(", line)
        if not m or not m.group(2).startswith(COLLECTIVES):
            continue
        shapes = [tuple(int(n) for n in dims.split(",") if n)
                  for dims in re.findall(r"[a-z]\w*\[([\d,]*)\]", line)]
        out.append((m.group(2), shapes))
    return out


def _token_dims(rows, t):
    """Every size a token dimension can have on a chip: rows, T, their
    shares over up to eight chips, and the flattened products."""
    def shares(n):
        return {n // k for k in (1, 2, 4, 8) if n % k == 0}
    rs, ts = shares(rows), shares(t)
    return {n for n in rs | ts | {r * x for r in rs for x in ts} if n > 1}


def _backend_compiles():
    """(the list every backend compile from now on is appended to, a call
    that stops the appending: a listener cannot be taken back)."""
    compiles, listening = [], [True]
    jax.monitoring.register_event_duration_secs_listener(
        lambda event, seconds, **kw: compiles.append(event)
        if listening[0] and event.endswith("backend_compile_duration")
        else None)
    return compiles, lambda: listening.__setitem__(0, False)


@pytest.fixture(scope="module")
def weights():
    """One set of float32 weights for every case (``ce_chunks`` does not
    change the parameters)."""
    return TransformerLM(_config(0)).init_params(jax.random.key(3))


@pytest.fixture(scope="module")
def unsharded(weights):
    """Loss and gradients of the model with no mesh, by (rows, T, chunks)."""
    memo = {}

    def get(rows, t, ce_chunks):
        key = (rows, t, ce_chunks)
        if key not in memo:
            model = TransformerLM(_config(ce_chunks))
            memo[key] = jax.device_get(jax.jit(jax.value_and_grad(
                model.loss_fn))(weights, *_batch(rows, t)))
        return memo[key]
    return get


@pytest.mark.parametrize("ce_chunks", [0, CHUNKS], ids=["plain", "chunked"])
@pytest.mark.parametrize("case", list(CASES))
def test_head_on_a_mesh(case, ce_chunks, weights, unsharded, caplog):
    axes, rows, t, layout = CASES[case]
    mesh = _mesh(axes)
    model = TransformerLM(_config(ce_chunks), mesh)
    params, batch = _placed(model, mesh, weights, _batch(rows, t))
    with caplog.at_level("INFO",
                         logger="deeplearning4j_tpu.models.transformer"):
        program = _grad_program(model, params, batch)
    said = [r.getMessage() for r in caplog.records
            if r.getMessage().startswith("head layout:")]
    assert said and all(s.startswith(f"head layout: {layout}")
                        for s in said), said

    # the same numbers as the model with no mesh, within what the sharded
    # tests of test_compose.py allow a float32 model
    loss, grads = program(params, *batch)
    ref_loss, ref_grads = unsharded(rows, t, ce_chunks)
    assert abs(float(loss) - float(ref_loss)) < 1e-5
    for (path, g), ref in zip(jax.tree_util.tree_leaves_with_path(grads),
                              jax.tree.leaves(ref_grads), strict=True):
        np.testing.assert_allclose(
            np.asarray(g), ref, rtol=2e-4, atol=2e-5,
            err_msg=jax.tree_util.keystr(path))

    hlo = program.as_text()
    if layout == "replicated":
        # nothing the plain head's program does not have: no constraint in
        # the lowered step, the same operations by kind in the compiled one
        plain = PlainHeadLM(_config(ce_chunks), mesh)
        step, plain_step = (m.make_train_step(optax.adamw(1e-3))
                            for m in (model, plain))
        state = jax.eval_shape(optax.adamw(1e-3).init, params)
        lowered, plain_lowered = (
            s.lower(params, state, *batch).as_text()
            for s in (step, plain_step))
        constraint = re.compile(r"sharding_constraint|@Sharding")
        assert (len(constraint.findall(lowered))
                == len(constraint.findall(plain_lowered)))
        assert _op_counts(hlo) == _op_counts(
            _grad_program(plain, params, batch).as_text())
        return

    # no collective carries the vocabulary (or a chunk of it) beside a
    # token dimension: the (rows, T, V) operand is never on the wire
    vocab, tokens = {V, V // CHUNKS}, _token_dims(rows, t)
    on_the_wire = _collective_shapes(hlo)
    assert on_the_wire, "a sharded step has collectives"
    for kind, shapes in on_the_wire:
        for dims in shapes:
            assert not (vocab & set(dims) and tokens & set(dims)), (
                f"{kind} carries {dims}: logits on the wire")
    # and the plain head's program does all-reduce them, so the check
    # above can fail
    plain_hlo = _grad_program(PlainHeadLM(_config(ce_chunks), mesh),
                              params, batch).as_text()
    assert any(vocab & set(dims) and tokens & set(dims)
               for _, shapes in _collective_shapes(plain_hlo)
               for dims in shapes)


def test_make_sharded_lm_places_the_moments_and_compiles_the_step_once():
    mesh = _mesh({DATA_AXIS: 2, MODEL_AXIS: 2})
    model, params, opt_state, opt = make_sharded_lm(_config(0), mesh)
    adam = opt_state[0]
    for moments in (adam.mu, adam.nu):
        for (path, m), p in zip(
                jax.tree_util.tree_leaves_with_path(moments),
                jax.tree.leaves(params), strict=True):
            assert m.sharding.is_equivalent_to(p.sharding, p.ndim), (
                jax.tree_util.keystr(path), m.sharding, p.sharding)
    assert adam.count.sharding.is_fully_replicated
    assert {d for leaf in jax.tree.leaves(opt_state)
            for d in leaf.sharding.device_set} == set(mesh.devices.flat)

    compiles, stop_listening = _backend_compiles()
    watch = global_compile_watch()
    traced0 = watch.count_for(TRAIN_STEP_FN)
    step = model.make_train_step(opt)
    feed = NamedSharding(mesh, P(DATA_AXIS, None))
    toks, tgts = (jax.device_put(a, feed) for a in _batch(8, 24))
    after_first = None
    for _ in range(3):
        params, opt_state, loss = step(params, opt_state, toks, tgts)
        if after_first is None:
            after_first = len(compiles)
    stop_listening()
    assert np.isfinite(float(loss))
    assert watch.count_for(TRAIN_STEP_FN) - traced0 == 1
    # a state left on one device would compile the step again for the
    # second call, whose inputs are the first call's sharded outputs
    assert len(compiles) == after_first
