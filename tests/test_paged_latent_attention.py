"""The paged latent attention kernel (``kernels/paged_latent_attention.py``)
in interpret mode against what it replaces in a TPU decode step, the gathered
window and the two einsums of ``models/hybrid.py::HybridLM._mla_decode``, on
the same pool, tables and positions; which of the two ``_mla_decode`` takes
where (``hybrid.latent_attention_backend``) and what it says; the pages a
step's attention visits as the ``decode_step`` span counts them.

Off the TPU ``_mla_decode`` keeps the gather, so the digests of
``tests/test_hybrid_longcat.py`` and ``tests/test_hybrid_nemotron.py`` hold
the OFF-TPU spelling of the programs, letter for letter the parent's. What the
TPU gets, the kernel's custom call under ``attn_core/mla_attend``, in the
decode program alone, is lowered here with ``jax.default_backend`` patched;
it is compiled at both cells' published shapes for a described v5e in
``tests/test_grouped_ffn.py``, the one test file that loads the TPU's compiler.
"""
import logging
import os
import re
import sys

import jax
import jax.numpy as jnp
import numpy as np
import pytest

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
if ROOT not in sys.path:
    sys.path.insert(0, ROOT)

from perfbench import harness  # noqa: E402
from test_decode_ahead import _prompt, _serve  # noqa: E402

from deeplearning4j_tpu.kernels import (  # noqa: E402
    paged_latent_attention as pla)
from deeplearning4j_tpu.models import hybrid  # noqa: E402
from deeplearning4j_tpu.models.generation import DecodeEngine  # noqa: E402
from deeplearning4j_tpu.models.hybrid import (  # noqa: E402
    HybridConfig, HybridLM, LayerSpec)
from deeplearning4j_tpu.observability.tracing import (  # noqa: E402
    reset_global_trace_sink)
from deeplearning4j_tpu.parallel.generation import (  # noqa: E402
    GenerationPipeline)

P, PAGES, SLOTS = 8, 5, 4
WINDOW = P * PAGES
#: a cached row: 128 latent and 8 rope dimensions in whole tiles of 128 lanes
LATENT, ROW = 136, 256
TRASH = SLOTS * PAGES
#: every slot's position in one step
POSITIONS = {
    "position 0": [0, 0, 0, 0],
    "a page's last row": [P - 1] * SLOTS,
    "the next page's first row": [P] * SLOTS,
    "the window's last position": [WINDOW - 1] * SLOTS,
    "mixed lengths in one batch": [0, P - 1, P, WINDOW - 1],
    "three, five, two and four live pages": [17, 39, 8, 25],
    "a retired slot, past the window": [WINDOW + 3, 12, 0, 30],
}
#: pages a visit: the whole window at once, or two (three and five live pages
#: are then no multiple of it: a slot's last visit fetches one page)
VISITS = [PAGES, 2]


def _layer(heads, dtype=jnp.float32):
    """One latent attention of ``heads`` heads at tiny widths, but for the
    latent rank, which the kernel reads in whole tiles of 128 lanes (a
    cached row of 136 in 256), rotated, its weights large enough for a
    softmax that prefers some rows."""
    cfg = HybridConfig(
        vocab_size=64, d_model=64, layers=(LayerSpec("mla", "dense"),),
        max_len=WINDOW, mla_heads=heads, qk_nope_dim=16, qk_rope_dim=8,
        v_head_dim=16, kv_lora_rank=128, rope_theta=1e4, dense_ff=64,
        dtype=dtype, param_dtype=dtype)
    model = HybridLM(cfg)
    assert (cfg.latent_dim, cfg.latent_row) == (LATENT, ROW)
    p = model.init_params(jax.random.key(heads))["blocks"][0]["mixer"]
    return model, jax.tree.map(lambda a: (20 * a).astype(dtype), p)


def _step(positions, dtype=jnp.float32, seed=0):
    """(h, pool, tables, positions): a pool of random rows whose pages are
    dealt to the slots in no order, every table entry behind a slot's last
    live page on the trash page."""
    ks = jax.random.split(jax.random.key(seed), 2)
    pool = jax.random.normal(ks[0], (TRASH + 1, P, ROW)).astype(dtype)
    pool = pool.at[..., LATENT:].set(0)     # ``latent_row``'s contract
    owned = np.random.default_rng(seed).permutation(TRASH).reshape(
        SLOTS, PAGES)
    pos = np.asarray(positions)
    tables = np.where(np.arange(PAGES)[None, :] <= pos[:, None] // P, owned,
                      TRASH)
    return (jax.random.normal(ks[1], (SLOTS, 64)).astype(dtype), pool,
            jnp.asarray(tables, jnp.int32), jnp.asarray(pos, jnp.int32))


def _both(model, p, h, pool, tables, pos, monkeypatch, visit=None):
    """``_mla_decode`` over the gathered window, the trash page zeros (a
    probability of 0 times whatever it holds), and through the kernel, the
    trash page NaN: ((y, pool), (y, pool))."""
    want = model._mla_decode(p, h, pool.at[TRASH].set(0), tables, pos, P)
    monkeypatch.setattr(hybrid, "_on_tpu", lambda: True)
    if visit:
        monkeypatch.setattr(pla, "VISIT_BYTES", visit * P * ROW
                            * pool.dtype.itemsize)
    got = model._mla_decode(p, h, pool.at[TRASH].set(jnp.nan), tables, pos,
                            P)
    assert model.attention_backend["mla"][0] == "paged-latent"
    return want, got


@pytest.mark.parametrize("heads", [2, 64])
@pytest.mark.parametrize("visit", VISITS)
@pytest.mark.parametrize("positions", list(POSITIONS))
def test_kernel_equals_the_gathered_window(positions, visit, heads,
                                           monkeypatch):
    """float32: the layer's result through the kernel is the gather
    spelling's to summation noise, for every shape of batch; a dead entry of
    a table (the trash page, NaN here) is never read; the pool comes back
    with the step's own rows written, as the gather spelling returns it."""
    model, p = _layer(heads)
    h, pool, tables, pos = _step(POSITIONS[positions])
    (y0, pool0), (y1, pool1) = _both(model, p, h, pool, tables, pos,
                                     monkeypatch, visit)
    assert y1.shape == (SLOTS, 64) and not bool(jnp.isnan(y1).any())
    assert float(jnp.max(jnp.abs(y1 - y0))) < 2e-5 * max(
        1.0, float(jnp.max(jnp.abs(y0))))
    assert bool(jnp.array_equal(pool1[:TRASH], pool0[:TRASH]))
    # the softmax is no mean: the heaviest row of some head carries a fifth
    assert float(jnp.max(jnp.abs(y0))) > 0.1


def test_bfloat16_rows_and_probabilities_accumulate_in_float32(monkeypatch):
    """bfloat16 rows, queries and probabilities, float32 scores, statistics
    and accumulator: within two bfloat16 roundings of the gather spelling's
    own bfloat16 result, and closer than that to the float32 layer's."""
    model, p = _layer(64, jnp.bfloat16)
    h, pool, tables, pos = _step(POSITIONS["three, five, two and four live "
                                           "pages"], jnp.bfloat16)
    (y0, _p0), (y1, _p1) = _both(model, p, h, pool, tables, pos, monkeypatch,
                                 visit=2)
    assert y1.dtype == jnp.bfloat16
    top = float(jnp.max(jnp.abs(y0.astype(jnp.float32))))
    gap = float(jnp.max(jnp.abs(y1.astype(jnp.float32)
                                - y0.astype(jnp.float32))))
    assert gap <= 2 ** -6 * top, (gap, top)


def test_the_kernel_refuses_what_is_not_one_paged_layer():
    q = jnp.zeros((SLOTS, 2, ROW))
    pool = jnp.zeros((TRASH + 1, P, ROW))
    tables = jnp.zeros((SLOTS, PAGES), jnp.int32)
    pos = jnp.zeros((SLOTS,), jnp.int32)
    with pytest.raises(ValueError, match="not one paged layer"):
        pla.paged_latent_attention(q[..., :64], pool, tables, pos, 32, 1.0)
    with pytest.raises(ValueError, match="not one paged layer"):
        pla.paged_latent_attention(q, pool, tables[:2], pos, 32, 1.0)
    with pytest.raises(ValueError, match=f"of a row's {ROW}"):
        pla.paged_latent_attention(q, pool, tables, pos, 2 * ROW, 1.0)
    # a visit is as many pages as ``VISIT_BYTES`` hold, within the window:
    # both cells' pages of 64 rows of 640 bfloat16 lanes go eight a visit
    assert pla.visit_pages(64, 640, 2, 32) == pla.visit_pages(
        64, 640, 2, 80) == 8
    assert pla.visit_pages(64, 640, 2, 5) == 5
    assert pla.visit_pages(4096, 640, 2, 32) == 1
    # and a visit is no less than a page, which may be more than the kernel's
    # VMEM holds: two halves of the buffer, the half in use, the scores
    assert pla.vmem_bytes(64, 640, 64, 32, 2) == (
        3 * 512 * 640 * 2 + 64 * 512 * 10) < pla.VMEM_BYTES
    assert pla.fits_vmem(32, 640, 64, 80, 2)
    assert pla.fits_vmem(64, 640, 2048, 1, 2)
    assert not pla.fits_vmem(32, 640, 5120, 1, 2)


# ------------------------------------------- the two families, end to end
FAMILIES = {
    "kimi_linear": "kimi-linear-48b-a3b-ep2share.json",
    "longcat_flash": "longcat-flash-omni-ep32share.json",
}


def _engine(family):
    """A new engine (its programs untraced) of the family's configuration at
    rehearsal sizes, but for the latent rank, which the kernel reads in
    whole tiles of 128 lanes, float32, on weights of seed 3."""
    cfg = harness.load_json("configs", FAMILIES[family])
    cfg.update(cfg["rehearsal"])
    cfg.update(compute_dtype="float32", param_dtype="float32",
               kv_lora_rank=128)
    mod = harness.load_module("models", family + ".py")
    model = mod.build_model(cfg)
    assert model.config.latent_row == ROW
    return DecodeEngine(model, mod.make_weights(cfg, 3),
                        max_len=cfg["n_positions"],
                        prefill_buckets=[16, 32, 64], page_tokens=P)


@pytest.mark.parametrize("family", list(FAMILIES))
def test_greedy_tokens_through_decode_paged_are_the_fallbacks(family,
                                                              monkeypatch):
    """Three prompts of 13 tokens, 24 greedy tokens each (positions 13 to
    35: four pages, the boundaries at 16, 24 and 32 crossed) through
    ``DecodeEngine.generate``: the engine whose decode program was traced
    with the kernel gives the tokens of the engine that gathered."""
    vocab = 512
    prompts = np.stack([_prompt(13, 40 + i, vocab) for i in range(3)])
    want = _engine(family).generate(prompts, 24)
    monkeypatch.setattr(hybrid, "_on_tpu", lambda: True)
    eng = _engine(family)
    got = eng.generate(prompts, 24)
    assert eng.model.attention_backend["mla"][0] == "paged-latent"
    assert got.shape == (3, 24) and np.array_equal(got, want)
    assert len({tuple(r) for r in got.tolist()}) == 3   # three streams


# ------------------------------------------------ which programs take it
#: (heads, row, output's width, rows a page, pages a slot, bytes a number)
#: -> what ``_mla_decode`` takes there on the TPU
CHOICES = {
    "longcat-rollout's shapes": ((64, 640, 512, 64, 32, 2), (
        "paged-latent", "live pages of 64 rows of 640 read where they lie")),
    "kimilinear-longgen's shapes": ((32, 640, 512, 64, 80, 2), (
        "paged-latent", "live pages of 64 rows of 640 read where they lie")),
    "a latent rank of 96 in a row of 128": ((8, 128, 96, 64, 32, 2), (
        "gather", "a row of 128, read 96 wide, is not whole tiles of 128 "
                  "lanes")),
    "a page of 12 rows": ((64, 640, 512, 12, 32, 2), (
        "gather", "a page of 12 rows is not whole tiles of 8 rows")),
    # DL4J_TPU_KV_PAGE_TOKENS at or above the window: one page a slot
    "kimilinear-longgen's window as one page": (
        (32, 640, 512, 5120, 1, 2),
        ("gather", "a visit of 5120 rows of 640 is more than the kernel's "
                   "VMEM")),
    "longcat-rollout's window as one page": ((64, 640, 512, 2048, 1, 2), (
        "paged-latent", "live pages of 2048 rows of 640 read where they "
                        "lie")),
}


@pytest.mark.parametrize("case", list(CHOICES))
def test_backend_is_the_gather_wherever_the_kernel_cannot_be(case,
                                                             monkeypatch):
    """Decided from what a trace can see, with the reason in the line: off
    the TPU whatever the shapes; on it, widths that are no whole 128-lane
    tiles, a page that is no whole 8-row tiles, a visit that does not fit
    the kernel's VMEM (Mosaic refuses that one by its own count, 19.3 of 16
    MiB: compiled for a v5e, PR 42). Both cells' shapes take the kernel."""
    shapes, want = CHOICES[case]
    assert hybrid.latent_attention_backend(*shapes) == ("gather", "on cpu")
    monkeypatch.setattr(hybrid, "_on_tpu", lambda: True)
    assert hybrid.latent_attention_backend(*shapes) == want


def _programs(eng):
    """{name: text lowered for the TPU} of the engine's decode program, one
    prefill bucket's and the insert's, and what the traces said."""
    model, shapes = eng.model, jax.eval_shape(lambda: eng.params)
    i32 = lambda *s: jax.ShapeDtypeStruct(s, jnp.int32)     # noqa: E731
    slots, pages = 4, 4 * eng.pages_per_slot + 1
    cache = jax.eval_shape(lambda: model.new_paged_cache(slots, pages, P))
    ent = jax.eval_shape(lambda p, t: model.prefill_cache(p, t, 3)[1],
                         shapes, i32(1, 32))
    traces = {
        "decode": lambda: eng._decode_paged_jit.trace(
            shapes, cache, i32(slots, eng.pages_per_slot), i32(slots),
            i32(slots), i32()),
        "prefill": lambda: eng._prefill_jit.trace(shapes, i32(1, 32), i32(),
                                                  i32()),
        "insert": lambda: eng._insert_paged_jit.trace(cache, ent, i32(4),
                                                      i32())}
    return {name: trace().lower(lowering_platforms=("tpu",)).as_text(
        debug_info=name == "decode") for name, trace in traces.items()}


def test_the_kernel_is_in_the_decode_program_alone(monkeypatch, caplog):
    """With the backend the process sees patched (there is no override in
    the module): the decode program lowers to ONE kernel that its four
    latent attentions call under ``attn_core/mla_attend``, no gathered
    window and no ``kv_gather`` scope; the prefill and insert programs'
    text is what it is off the TPU; one line a trace names the choice."""
    off = _programs(_engine("longcat_flash"))
    assert not any("tpu_custom_call" in t for t in off.values())
    assert "kv_gather" in off["decode"]
    monkeypatch.setattr(jax, "default_backend", lambda: "tpu")
    with caplog.at_level(logging.INFO, logger=hybrid.__name__):
        on = _programs(_engine("longcat_flash"))
    assert on["prefill"] == off["prefill"] and on["insert"] == off["insert"]
    text = on["decode"]
    assert "kv_gather" not in text
    assert len([line for line in text.splitlines()
                if "custom_call @tpu_custom_call" in line]) == 1
    calls = [line for line in text.splitlines()
             if "call @_paged_latent_attention(" in line]
    assert len(calls) == 4          # two double layers, two attentions each
    for call in calls:
        loc = re.search(r"loc\((#loc\d+)\)\s*$", call).group(1)
        path = re.search(rf'^{loc} = loc\("([^"]*)"', text, re.M).group(1)
        assert path.endswith(
            "/attn_core/mla_attend/jit(_paged_latent_attention)"), path
    said = [r.getMessage() for r in caplog.records
            if r.getMessage().startswith("attention backend")]
    assert said == ["attention backend: mla: paged-latent: live pages of 8 "
                    f"rows of {ROW} read where they lie"]


# -------------------------------------------- what a step's span counts
def test_attn_pages_on_the_step_span_and_the_choice_in_the_snapshot():
    """Two slots, three requests of different lengths: every ``decode_step``
    span's ``attn_pages`` is ``sum(positions // P + 1)`` over the slots the
    step it fetched was dispatched for, as the loop held them at the
    dispatch; ``snapshot()`` names the attention's choice and its reason."""
    eng = _engine("kimi_linear")
    assert eng.model.attention_backend == {}        # no trace yet
    jobs = [{"prompt": _prompt(n, 70 + i, 512), "max_new_tokens": m}
            for i, (n, m) in enumerate([(5, 14), (20, 9), (11, 12)])]
    with GenerationPipeline(eng, slots=2) as gp:    # compiles
        _serve(gp, jobs)
    sink = reset_global_trace_sink(65536)
    held = {}
    with GenerationPipeline(eng, slots=2) as gp:
        dispatch = gp._dispatch_step

        def spy(active):
            held[gp._step] = [int(gp._positions[s]) for s in active]
            return dispatch(active)

        gp._dispatch_step = spy
        recs = _serve(gp, jobs)
        snap = gp.snapshot()
    assert all(r["error"] is None for r in recs)
    assert snap["attention_backend"] == "mla: gather: on cpu"
    steps = [s for s in sink.spans()
             if s.name == "decode_step" and "attn_pages" in (s.attrs or {})]
    assert len(steps) >= 14
    for s in steps:
        at = held[s.attrs["step"]]
        assert s.attrs["active"] == len(at)
        assert s.attrs["live_tokens"] == sum(at) + len(at)
        assert s.attrs["attn_pages"] == sum(a // P + 1 for a in at)
    # some step read a slot's second page and some a third
    assert {a // P for s in steps for a in held[s.attrs["step"]]} >= {0, 1, 2}
