"""The grouped feed-forward kernel (``kernels/grouped_ffn.py``) in interpret
mode against what it replaces in a TPU decode step, the two
``lax.ragged_dot`` and ``moe._activate`` of
``parallel/moe.py::routed_experts_ffn``, and against the sum written out group
by group; the count it hands back; and which of the two
``routed_experts_ffn`` takes where (``moe.expert_backend``).

Off the TPU ``routed_experts_ffn`` keeps the two ``ragged_dot``, so the three
digests of ``tests/test_hybrid_nemotron.py``
(``test_kimi_linear_programs_are_what_they_were``) hold the OFF-TPU spelling
of the second family's programs: prefill and insert are 5eb3591's letter for
letter; decode is 5eb3591's with the fourth count (``expert_visits``) behind
its tokens. What the TPU gets, the kernel's custom call under ``moe_experts``,
is lowered here with ``jax.default_backend`` patched, and compiled at the
published widths for a described v5e (the one test file that loads the TPU's
compiler: guide ``on-chip-measurement``, section 2).
"""
import logging
import os
import re
import sys

import jax
import jax.numpy as jnp
import numpy as np
import pytest
from jax import lax

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
if ROOT not in sys.path:
    sys.path.insert(0, ROOT)

from perfbench import harness  # noqa: E402

from deeplearning4j_tpu.kernels import grouped_ffn as gf  # noqa: E402
from deeplearning4j_tpu.kernels.grouped_ffn import grouped_ffn  # noqa: E402
from deeplearning4j_tpu.parallel import moe  # noqa: E402
from deeplearning4j_tpu.parallel.moe import (  # noqa: E402
    RoutedExpertsConfig, _activate, routed_experts_ffn)

E, W, F, ROWS = 6, 128, 256, 80
HELD = 128
#: form -> (width, inner width, k a token) as published: Kimi-Linear's
#: experts and Nemotron-3-Super's in their latent space
PUBLISHED = {"swiglu": (2304, 1024, 8), "relu2": (1024, 2688, 22)}
SLOTS, BUCKETS = 64, (1024, 2048, 4096)


def _layer(form, dtype, rows=ROWS, seed=0):
    n = gf.N_FIRST[form]
    ks = jax.random.split(jax.random.key(seed), 3)
    return (jax.random.normal(ks[0], (rows, W), dtype),
            (0.1 * jax.random.normal(ks[1], (E, W, n * F))).astype(dtype),
            (0.1 * jax.random.normal(ks[2], (E, F, W))).astype(dtype))


def _two_products(rows, first, down, sizes, form):
    """``routed_experts_ffn``'s lines where it keeps ``ragged_dot``."""
    h = lax.ragged_dot(rows, first, sizes,
                       preferred_element_type=jnp.float32)
    return lax.ragged_dot(_activate(h, form, rows.dtype), down, sizes,
                          preferred_element_type=jnp.float32)


def _written_out(rows, first, down, sizes, form):
    """The same sum group by group in numpy, float64."""
    rows, first, down = (np.asarray(a, np.float64)
                         for a in (rows, first, down))
    out, at = np.zeros((sum(sizes), W)), 0
    for e, size in enumerate(sizes):
        h = rows[at:at + size] @ first[e]
        a = (h[:, :F] / (1 + np.exp(-h[:, :F])) * h[:, F:]
             if form == "swiglu" else np.maximum(h, 0) ** 2)
        out[at:at + size] = a @ down[e]
        at += size
    return out


GROUPS = {
    "empty experts, a group of one": [0, 1, 37, 0, 20, 5],
    "all rows on one expert": [0, 0, 80, 0, 0, 0],
    "no held row at all": [0, 0, 0, 0, 0, 0],
    "groups that start off a 16-row boundary": [3, 3, 3, 3, 3, 3],
    "a group wider than a window": [7, 0, 0, 50, 0, 9],
    "rows to the last one": [0, 0, 0, 0, 11, 69],
}
#: (f tile, window): the defaults; two f tiles with the smallest window; one
#: f tile with a window that is no power of two; one window for every row
TILES = [None, (128, 16), (256, 48), (128, 80)]


@pytest.mark.parametrize("form", ["swiglu", "relu2"])
@pytest.mark.parametrize("groups", list(GROUPS))
@pytest.mark.parametrize("tiles", TILES, ids=str)
def test_kernel_equals_the_two_grouped_products(form, groups, tiles):
    """float32 operands: the kernel's rows are the two ``ragged_dot``'s and
    the written-out sum's to summation noise for every shape of group; rows
    behind the last group are the caller's to mask and are not read here;
    every touched expert is streamed once."""
    sizes = GROUPS[groups]
    rows, first, down = _layer(form, jnp.float32)
    y, visits = grouped_ffn(rows, first, down, jnp.asarray(sizes, jnp.int32),
                            form, tiles=tiles)
    assert y.shape == (ROWS, W) and y.dtype == jnp.float32
    held = sum(sizes)
    want = _two_products(rows, first, down, jnp.asarray(sizes, jnp.int32),
                         form)
    assert float(jnp.max(jnp.abs(y[:held] - want[:held]), initial=0.0)) < 2e-5
    assert np.abs(np.asarray(y[:held]) - _written_out(
        rows, first, down, sizes, form)).max(initial=0.0) < 2e-5
    assert int(visits) == sum(s > 0 for s in sizes)


@pytest.mark.parametrize("form", ["swiglu", "relu2"])
def test_bfloat16_operands_accumulate_in_float32(form):
    """bfloat16 operands: within float32 summation noise of the float32
    product of the same operands with the hidden rows rounded where
    ``_activate`` rounds them, and within one bfloat16 rounding of ``h`` of
    the two ``ragged_dot``'s own result. 77 rows: no multiple of 16."""
    sizes = jnp.asarray([0, 1, 37, 0, 20, 5], jnp.int32)
    rows, first, down = _layer(form, jnp.bfloat16, rows=77, seed=1)
    y, _ = grouped_ffn(rows, first, down, sizes, form, tiles=(128, 16))
    assert y.shape == (77, W) and y.dtype == jnp.float32
    up = lambda a: a.astype(jnp.float32)        # noqa: E731
    h = lax.ragged_dot(up(rows), up(first), sizes,
                       precision=lax.Precision.HIGHEST)
    a = up(_activate(h, form, jnp.bfloat16))
    exact = lax.ragged_dot(a, up(down), sizes,
                           precision=lax.Precision.HIGHEST)
    assert float(jnp.max(jnp.abs(y[:63] - exact[:63]))) < 3e-2 * float(
        jnp.max(jnp.abs(exact[:63])))           # a's roundings may differ
    two = _two_products(rows, first, down, sizes, form)
    # one bfloat16 step of the largest hidden value through the widest
    # column of ``w_down``
    step = float(jnp.max(jnp.abs(a))) * 2.0 ** -8 * float(
        jnp.max(jnp.sum(jnp.abs(up(down)), axis=1)))
    assert float(jnp.max(jnp.abs(y[:63] - two[:63]))) <= step
    assert float(jnp.max(jnp.abs(y[:63] - two[:63]))) < 1e-2 * float(
        jnp.max(jnp.abs(two[:63])))


def test_the_groups_metadata_names_each_touched_expert_once():
    offsets, touched, n = gf.touched_of(jnp.asarray([0, 1, 37, 0, 20, 5]))
    assert list(map(int, offsets)) == [0, 0, 1, 38, 38, 58, 63]
    assert int(n[0]) == 4 and list(map(int, touched[:4])) == [1, 2, 4, 5]
    # behind the last touched expert the metadata repeats it: no block moves
    assert set(map(int, touched[4:])) == {5}
    _offsets, touched, n = gf.touched_of(jnp.zeros((6,), jnp.int32))
    assert int(n[0]) == 0 and all(0 <= int(e) < 6 for e in touched)


def test_tiles_from_the_published_widths_and_what_the_kernel_refuses():
    for form, rows in (("swiglu", 512), ("relu2", 1408)):
        w, f, _k = PUBLISHED[form]
        n = gf.N_FIRST[form]
        tf, ts = gf.default_tiles(rows, w, f, n, HELD)
        assert f % tf == 0 and tf % 128 == 0 and ts % 16 == 0
        assert (n + 1) * w * tf * 2 <= gf.STEP_BYTES
        assert gf.vmem_bytes(rows, w, tf, n) < 32 << 20
    with pytest.raises(ValueError, match="tiles"):
        grouped_ffn(*_layer("relu2", jnp.float32),
                    jnp.zeros((E,), jnp.int32), "relu2", tiles=(96, 16))
    with pytest.raises(ValueError, match="tiles"):
        grouped_ffn(*_layer("relu2", jnp.float32),
                    jnp.zeros((E,), jnp.int32), "relu2", tiles=(128, 96))
    with pytest.raises(ValueError, match="not one swiglu layer"):
        grouped_ffn(*_layer("relu2", jnp.float32),
                    jnp.zeros((E,), jnp.int32), "swiglu")
    with pytest.raises(ValueError, match="walks no row tiles"):
        jax.eval_shape(lambda *a: grouped_ffn(*a, "relu2"),
                       *_layer("relu2", jnp.bfloat16, rows=1 << 19),
                       jnp.zeros((E,), jnp.int32))


# ------------------------------------------------- which programs take it
def _backend(form, tokens):
    w, f, k = PUBLISHED[form]
    return moe.expert_backend(tokens * k, w, f, gf.N_FIRST[form], HELD, 2)


@pytest.mark.parametrize("form", ["swiglu", "relu2"])
def test_backend_is_the_kernel_at_decode_and_ragged_dot_elsewhere(
        form, monkeypatch):
    """At the published widths: both cells' decode steps (64 slots) are one
    row tile, no prefill bucket is; widths that are no whole lanes and every
    program off the TPU keep ``ragged_dot``; the reason is in the line."""
    assert _backend(form, SLOTS) == ("ragged_dot", "on cpu")
    monkeypatch.setattr(moe, "_on_tpu", lambda: True)
    w, _f, k = PUBLISHED[form]
    assert _backend(form, SLOTS) == (
        "grouped-ffn", f"{SLOTS * k} pair rows of {w} in one row tile")
    for bucket in BUCKETS:
        assert _backend(form, bucket) == (
            "ragged_dot",
            f"{bucket * k} pair rows of {w} are more than one row tile")
    backend, why = moe.expert_backend(52, 12, 5, 1, 4, 4)
    assert backend == "ragged_dot" and "128 lanes" in why


def _experts(form, d=128, f=128, held=4, router=8, dtype=jnp.float32):
    first = moe.EXPERT_FORMS[form]
    cols = gf.N_FIRST[form] * f
    ks = iter(jax.random.split(jax.random.key(5), 8))

    def n(*shape):
        return (0.2 * jax.random.normal(next(ks), shape)).astype(dtype)
    return {"w_router": n(d, router), "b_select": n(router),
            first: n(held, d, cols), "w_down": n(held, f, d),
            "shared": {first: n(d, cols), "w_down": n(f, d)}}


def _call_path(text, callee):
    """The ``op_name`` path of the call of ``callee`` in a lowered text."""
    call = next(line for line in text.splitlines()
                if f"call @{callee}(" in line)
    loc = re.search(r"loc\((#loc\d+)\)\s*$", call).group(1)
    return re.search(rf'^{loc} = loc\("([^"]*)"', text, re.M).group(1)


@pytest.mark.parametrize("form", ["swiglu", "relu2"])
def test_routed_experts_take_the_kernel_where_the_trace_is_for_the_tpu(
        form, monkeypatch, caplog):
    """A small decode-sized call lowers, for the TPU and with the backend
    the process sees patched (there is no override in the module), to the
    kernel's custom call under ``moe_experts`` and to no ``ragged_dot``;
    unpatched it keeps the two ``ragged_dot`` and says why."""
    p = _experts(form, dtype=jnp.bfloat16)
    x = jax.random.normal(jax.random.key(6), (16, 128), jnp.bfloat16)
    ec = RoutedExpertsConfig(8, 2, (2, 4), form=form)

    def lowered():
        caplog.clear()
        fn = jax.jit(lambda p, x: routed_experts_ffn(p, x, ec))  # a new trace
        with caplog.at_level(logging.INFO, logger=moe.__name__):
            text = fn.trace(p, x).lower(
                lowering_platforms=("tpu",)).as_text(debug_info=True)
        return text, [r.getMessage() for r in caplog.records]

    text, said = lowered()
    assert text.count('"chlo.ragged_dot"(') == 2
    assert "tpu_custom_call" not in text
    assert said == ["expert backend: ragged_dot: on cpu"]
    monkeypatch.setattr(jax, "default_backend", lambda: "tpu")
    text, said = lowered()
    assert '"chlo.ragged_dot"(' not in text
    calls = [line for line in text.splitlines()
             if "custom_call @tpu_custom_call" in line]
    assert len(calls) == 1
    # the kernel is one jitted function every expert layer shares; its call
    # stands under ``moe_experts`` (the caller's ``mlp`` is not given here),
    # which is the path the compiled custom call's ``op_name`` begins with
    body = text[text.index("func.func private @_grouped_ffn("):]
    assert calls[0] in body[:body.index("\n  }")]
    assert _call_path(text, "_grouped_ffn").endswith(
        "/moe_experts/jit(_grouped_ffn)")
    assert said == ["expert backend: grouped-ffn: 32 pair rows of 128 in "
                    "one row tile"]


# ------------------------------------------------------- the fourth count
@pytest.mark.parametrize("form", ["swiglu", "relu2"])
def test_the_fourth_count_through_routed_experts(form, monkeypatch):
    """``stats[3]``: 0 where ``ragged_dot`` is taken; with the kernel (in
    interpret mode here: the process is off the TPU) the experts touched -
    and the same rows come out."""
    p = _experts(form)
    x = jax.random.normal(jax.random.key(7), (24, 128))
    ec = RoutedExpertsConfig(8, 2, (2, 4), form=form)
    y0, st0 = routed_experts_ffn(p, x, ec)
    assert int(st0[3]) == 0 < int(st0[0])
    monkeypatch.setattr(moe, "_on_tpu", lambda: True)
    y1, st1 = routed_experts_ffn(p, x, ec)
    assert int(st1[3]) == int(st1[0]) == int(st0[0])
    assert list(map(int, st1[:3])) == list(map(int, st0[:3]))
    assert float(jnp.max(jnp.abs(y1 - y0))) < 2e-5


def _family(form):
    """The family whose experts have ``form`` at its configuration's
    rehearsal sizes, the experts widened to whole lanes (128 x 128), float32:
    (model, weights)."""
    name, module, over = {
        "swiglu": ("kimi-linear-48b-a3b-ep2share.json", "kimi_linear.py",
                   dict(hidden_size=128, moe_intermediate_size=128)),
        "relu2": ("nemotron-3-super-120b-a12b-ep4share.json",
                  "nemotron_h.py",
                  dict(moe_latent_size=128, moe_intermediate_size=128)),
    }[form]
    cfg = harness.load_json("configs", name)
    cfg.update(cfg["rehearsal"])
    cfg.update(compute_dtype="float32", param_dtype="float32", **over)
    mod = harness.load_module("models", module)
    return mod.build_model(cfg), mod.make_weights(cfg, 3)


@pytest.mark.parametrize("form", ["swiglu", "relu2"])
def test_the_fourth_count_through_decode_paged(form, monkeypatch):
    """``HybridLM.decode_paged`` sums the counts over its expert layers:
    ``expert_visits`` is 0 off the TPU and ``experts_touched`` with the
    kernel, a free slot routing nowhere either way; the logits agree."""
    model, params = _family(form)
    assert model.step_stats[3] == "expert_visits"
    assert model.config.experts.form == form
    arrays = model.new_paged_cache(3, 5, 8)
    trash = 4
    tables = jnp.asarray([[0, 1], [trash, trash], [2, 3]], jnp.int32)
    args = (params, arrays, tables, jnp.asarray([5, 0, 9], jnp.int32),
            jnp.asarray([0, 0, 3], jnp.int32), 8)
    logits0, _a, st0 = model.decode_paged(*args)
    k = model.config.experts.top_k
    assert int(st0[2]) == 2 * k * len(model.moe_layers)   # two live slots
    assert int(st0[3]) == 0 < int(st0[0])
    monkeypatch.setattr(moe, "_on_tpu", lambda: True)
    logits1, _a, st1 = model.decode_paged(*args)
    assert list(map(int, st1)) == list(map(int, st0[:3])) + [int(st0[0])]
    assert float(jnp.max(jnp.abs(logits1 - logits0))) < 1e-4


# ------------------------------- the chip's compiler, at published widths
@pytest.fixture(scope="module")
def one_chip():
    from jax.experimental import topologies
    from jax.sharding import SingleDeviceSharding
    try:
        topo = topologies.get_topology_desc(platform="tpu",
                                            topology_name="v5e:2x2")
    except Exception as e:      # no libtpu here, or another process holds it
        pytest.skip(f"no v5e:2x2 topology can be described here: {e}")
    return SingleDeviceSharding(topo.devices[0])


@pytest.mark.parametrize("form", ["swiglu", "relu2"])
def test_decode_rows_compile_for_the_v5e_at_published_widths(
        form, one_chip, monkeypatch):
    """``routed_experts_ffn`` over a decode step's 64 tokens, 128 held
    experts at the published widths in bfloat16, compiled for a described
    v5e: Mosaic takes the kernel (its tiles, its VMEM) and the compiled
    custom call's ``op_name`` lies under ``moe_experts``, where the
    benchmark's readers look for it."""
    w, f, k = PUBLISHED[form]
    n = gf.N_FIRST[form]

    def s(*shape, dtype=jnp.bfloat16):
        return jax.ShapeDtypeStruct(shape, dtype, sharding=one_chip)
    first = moe.EXPERT_FORMS[form]
    p = {"w_router": s(w, 2 * HELD), "b_select": s(2 * HELD),
         first: s(HELD, w, n * f), "w_down": s(HELD, f, w),
         "shared": {first: s(w, n * f), "w_down": s(f, w)}}
    ec = RoutedExpertsConfig(2 * HELD, k, (0, HELD), form=form)
    monkeypatch.setattr(jax, "default_backend", lambda: "tpu")
    text = jax.jit(lambda p, x: routed_experts_ffn(p, x, ec)).lower(
        p, s(SLOTS, w)).compile().as_text()
    calls = [line for line in text.splitlines() if "tpu_custom_call" in line]
    assert len(calls) == 1 and "ragged" not in text
    assert re.search(r'op_name="[^"]*/moe_experts/jit\(_grouped_ffn\)/'
                     r'pallas_call"', calls[0]), calls[0][-400:]


@pytest.mark.parametrize("cell", ["longcat-rollout", "kimilinear-longgen",
                                  "the largest page that fits"])
def test_paged_latent_attention_compiles_for_the_v5e_at_published_shapes(
        cell, one_chip):
    """The decode step's other kernel (``kernels/paged_latent_attention.py``;
    its own tests are ``tests/test_paged_latent_attention.py``, this file is
    the one that loads the TPU's compiler): 64 slots of 64 heads and 32
    pages a slot (``longcat-rollout``) and of 32 heads and 80 pages
    (``kimilinear-longgen``), rows of 640, pages of 64, the output 512 wide,
    bfloat16: Mosaic takes the kernel (its copies, its double buffer, its
    loop) at eight pages a visit. And at the largest visit that
    ``fits_vmem`` admits, a window of one page of thousands of rows: what
    the chooser lets through, the chip's 16 MiB of scoped VMEM hold."""
    from deeplearning4j_tpu.kernels import paged_latent_attention as pla
    heads, pages, P = {"longcat-rollout": (64, 32, 64),
                       "kimilinear-longgen": (32, 80, 64),
                       "the largest page that fits": (64, 1, max(
                           n for n in range(64, 8192, 64)
                           if pla.fits_vmem(64, 640, n, 1, 2)))}[cell]
    visit = pla.visit_pages(P, 640, 2, pages)
    assert visit == (8 if P == 64 else 1) and (P == 64 or P > 2048)

    def s(*shape, dtype=jnp.bfloat16):
        return jax.ShapeDtypeStruct(shape, dtype, sharding=one_chip)
    text = jax.jit(lambda q, pool, tables, pos: pla._paged_latent_attention(
        q, pool, tables, pos, 512, 192 ** -0.5, visit, False)).lower(
        s(64, heads, 640), s(64 * pages + 1, P, 640),
        s(64, pages, dtype=jnp.int32), s(64, dtype=jnp.int32)
    ).compile().as_text()
    assert len([line for line in text.splitlines()
                if "tpu_custom_call" in line]) == 1


@pytest.mark.parametrize("cell", ["laguna-codegen", "phi4flash-reasoning"])
def test_the_ring_walk_compiles_for_the_v5e_at_published_widths(
        cell, one_chip, monkeypatch):
    """A window layer's decode step (``HybridLM._swa_decode``) of the two
    cells that have one, 64 slots over rings of 512 rows in bfloat16, traced
    for the TPU and compiled for a described v5e: 64 heads on 8 key/value
    heads of 128, a row of 2,048 (``laguna-codegen``), and 40 heads of 64 on
    20 as four query rows on each of 10 pairs of 128, a row of 2,560
    (``phi4flash-reasoning``). Mosaic takes the page walk over the ring
    (pages of 64 rows, 256 and 192 rows a visit), the ring is written and
    read in place (the reshape to pages is no copy: nothing temporary of a
    ring's size), and the compiled call's ``op_name`` lies under
    ``attn_core/swa_attend``, where the window kind's readers look."""
    from deeplearning4j_tpu.models import hybrid
    family, name, row, visit = {
        "laguna-codegen": ("laguna", "laguna-xs2-33b-a3b-stage5.json", 2048,
                           4),
        "phi4flash-reasoning": ("phi4flash",
                                "phi-4-mini-flash-reasoning.json", 2560, 3)
    }[cell]
    from deeplearning4j_tpu.kernels import paged_latent_attention as pla
    adapter = harness.load_module("models", family + ".py")
    cfg = harness.load_json("configs", name)
    model = adapter.build_model(cfg)
    c = model.config
    assert (c.swa_window, c.gqa_kv_row) == (512, row)
    assert pla.visit_pages(hybrid.RING_PAGE_ROWS, row, 2, 8,
                           pla.GROUPED_VISIT_BYTES) == visit
    layer = [i for i, spec in enumerate(c.layers)
             if any(part.kind == "swa" for part in spec.parts)][0]
    block = adapter.weight_shapes(cfg)["blocks"][layer]
    mixer = [v for v in block.values()
             if isinstance(v, dict) and "w_kv" in v][0]

    def s(*shape, dtype=c.dtype):
        return jax.ShapeDtypeStruct(shape, dtype, sharding=one_chip)
    p = jax.tree.map(lambda a: s(*a.shape, dtype=a.dtype), mixer)
    monkeypatch.setattr(jax, "default_backend", lambda: "tpu")
    compiled = jax.jit(model._swa_decode, donate_argnums=(2,)).lower(
        p, s(SLOTS, c.d_model), s(SLOTS, 512, row),
        s(SLOTS, dtype=jnp.int32)).compile()
    assert model.attention_backend == {"swa": (
        "paged-grouped", f"live pages of 64 rows of {row} of a ring of 512 "
        "read where they lie")}
    calls = [line for line in compiled.as_text().splitlines()
             if "tpu_custom_call" in line]
    assert len(calls) == 1
    assert re.search(r'op_name="[^"]*/attn_core/swa_attend/'
                     r'jit\(_paged_grouped_attention\)/pallas_call"',
                     calls[0]), calls[0][-400:]
    ring = SLOTS * 512 * row * 2
    mem = compiled.memory_analysis()
    assert mem.alias_size_in_bytes == ring
    assert mem.temp_size_in_bytes < ring // 8


def _pool_shaped(text, shapes):
    """(opcode, line) of every instruction of ``text`` whose result has one
    of ``shapes``, parameters and the pieces of a tuple left out."""
    found = []
    for line in text.splitlines():
        m = re.match(r"\s*(?:ROOT )?%[\w.\-]+ = (\w+\[[\d,]*\])\S* "
                     r"([\w\-]+)\(", line)
        if m and m.group(1) in shapes and m.group(2) not in (
                "parameter", "get-tuple-element", "bitcast"):
            found.append((m.group(2), line))
    return found


def test_the_gpt2_pool_is_written_where_it_lies_on_the_v5e(one_chip):
    """``gpt2l-batch-gen``'s decode and insert programs (36 layers, 20
    slots, 255 pages + the trash page of 64 rows of 1,280 lanes in bfloat16,
    the 256 bucket), compiled for a described v5e: every one of the 72 pool
    arrays is aliased input to output; what has a pool array's shape is the
    in-place scatter under ``kv_write`` and nothing else (no ``copy``, no
    slice of a stacked pool, no stack: the parent's program held 5.4 GB of
    such temporaries and its insert a whole second pool), but for the
    compiler's own prefetch of the FIRST array into VMEM and back, one
    asynchronous copy; the temporaries are the gathered views and the
    weights' bfloat16 copy a layer at a time."""
    from deeplearning4j_tpu.models.generation import DecodeEngine
    adapter = harness.load_module("models", "gpt2.py")
    cfg = harness.load_json("configs", "gpt2-large.json")
    model = adapter.build_model(cfg)
    L, slots, n_pages, P, d, bucket = 36, 20, 256, 64, 1280, 256
    assert (model.config.n_layers, model.config.d_model) == (L, d)

    def on_chip(tree):
        return jax.tree.map(lambda a: jax.ShapeDtypeStruct(
            a.shape, a.dtype, sharding=one_chip), tree)

    def i32(*shape):
        return jax.ShapeDtypeStruct(shape, jnp.int32, sharding=one_chip)
    params = on_chip(adapter.weight_shapes(cfg))
    eng = DecodeEngine(model, params, max_len=1024, page_tokens=P,
                       prefill_buckets=[bucket])
    pool = on_chip(jax.eval_shape(
        lambda: model.new_paged_cache(slots, n_pages, P)))
    assert [a.shape for a in pool["k"]] == [(n_pages, P, d)] * L
    pool_bytes = 2 * L * n_pages * P * d * 2
    kv = on_chip(jax.eval_shape(lambda p, t: model.prefill(p, t)[1],
                                params, i32(1, bucket)))
    decode = eng._decode_paged_jit.lower(
        params, pool, i32(slots, 1024 // P), i32(slots), i32(slots),
        i32()).compile()
    insert = eng._insert_paged_jit.lower(pool, kv, i32(bucket // P),
                                         i32()).compile()
    shapes = {f"bf16[{n_pages},{P},{d}]", f"bf16[{L},{n_pages},{P},{d}]",
              f"bf16[1,{n_pages},{P},{d}]"}
    for program, compiled, temp in (("_decode_paged", decode, 2 << 30),
                                    ("_insert_paged", insert, 200e6)):
        mem = compiled.memory_analysis()
        assert mem.alias_size_in_bytes == pool_bytes, program
        assert mem.temp_size_in_bytes < temp, (program,
                                               mem.temp_size_in_bytes)
        found = _pool_shaped(compiled.as_text(), shapes)
        writes = [line for op, line in found if op in ("fusion", "scatter")]
        assert len(writes) >= 2 * L, program
        assert all(f'op_name="jit({program})/kv_write/scatter"' in line
                   for line in writes), program
        # the prefetch: four slices joined in VMEM, one copy back
        others = sorted(op for op, line in found
                        if op not in ("fusion", "scatter"))
        assert others in ([], ["copy-done", "custom-call"]), (program, others)
