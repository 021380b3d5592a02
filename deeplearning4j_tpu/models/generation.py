"""DecodeEngine: jitted KV-cache generation entry points + sampling.

The model-layer half of the generative decode path (the serving half —
continuous batching — is ``parallel/generation.py``). Wraps one
:class:`~deeplearning4j_tpu.models.transformer.TransformerLM` and its
params with exactly three jitted executables:

- **prefill** — the causal trunk over a (1|B, T_bucket) prompt, returning
  the sampled first token, the full logits, and the per-layer k/v the
  forward computed. Prompt lengths pad to a small set of fixed buckets
  (powers of two), so the executable set is bounded like the serving
  batch buckets (PR 2).
- **decode_step** — one token for a whole slot batch: single-query
  attention against the preallocated cache, position-indexed
  ``dynamic_update_slice`` writes, in-graph sampling. The cache is
  donated, so steady-state decode allocates nothing and — the contract
  the tests pin via ``compile_watch`` — triggers **zero** new XLA traces.
- **insert_slot** — copy a prefill's k/v into one slot's cache pages
  (traced slot index: one executable per prefill bucket, not per slot).

Sampling is in-graph and seeded: greedy argmax or top-k/temperature
(``SamplerConfig``), with the step counter folded into the engine's base
key so a run is reproducible from its seed.

Attention backends: prefill routes through the model's normal policy
(flash kernel eligible — ``DL4J_TPU_ATTN_BACKEND`` forces ``xla`` or
``flash``); the decode step is XLA-native single-query attention and
never reaches the attention-backend policy (pinned by a test counting
``_use_flash_attention`` calls across a decode trace).

``naive_generate`` is the honest O(T²) baseline the decode benchmark
A/Bs against: re-run the full forward over the (fixed-padded) sequence
per emitted token — one executable, no cache, per-token cost linear in
the whole sequence length instead of constant.

PR 13 grows three composing levers (see ARCHITECTURE §20):

- **Paged cache** (default; ``DL4J_TPU_KV_PAGE_TOKENS``, 0 = dense
  kill switch): k/v live in a pool of fixed-size pages + a per-slot
  page table (``DecodeState`` carries the pool, the host-side
  ``PageAllocator`` free list, and the table); decode scatters/gathers
  through the table, so which pages are allocated is DATA and the
  zero-retrace pins carry over. ``free_slot`` returns pages;
  exhaustion raises the typed ``CachePagesExhausted``.
- **int8 pages** (``DL4J_TPU_KV_QUANT=1``): int8 rows + per-row f32
  scales, dequantized on the fly in the attention; a deploy/warmup-
  time numerics gate (eager probe vs the f32 dense reference) falls
  back to f32 pages loudly when divergence exceeds ``quant_tol``.
- **Speculative decoding** (``draft=`` + ``spec_k``; kill switch
  ``DL4J_TPU_SPEC_DECODE=0``): one fused executable runs all k draft
  steps, one W=k+1 windowed verify scores carry+proposals on the
  target, and the host accept/resample loop keeps the emitted
  distribution exactly the target's (greedy: byte-identical tokens).
"""
from __future__ import annotations

import bisect
import dataclasses
import logging
import os
import time
from typing import Dict, List, Optional, Sequence, Tuple

import jax
import jax.numpy as jnp
import numpy as np
from jax import lax

from deeplearning4j_tpu.observability import compile_watch as _cw
from deeplearning4j_tpu.observability import cost_model as _cost
from deeplearning4j_tpu.observability import span as _span
from deeplearning4j_tpu.resilience.policy import CachePagesExhausted

_log = logging.getLogger(__name__)

#: compile-watch / cost-model entry-point names (the zero-steady-state-
#: retrace assertions and /debug/perf rows key on these)
PREFILL_FN = "TransformerLM.prefill"
DECODE_FN = "TransformerLM.decode_step"
VERIFY_FN = "TransformerLM.spec_verify"
PROPOSE_FN = "DraftLM.spec_propose"
CARRY_FN = "DecodeEngine.carry_tokens"

#: default KV page size in tokens (``DL4J_TPU_KV_PAGE_TOKENS``; 0 = the
#: dense per-slot preallocation, byte-identical pre-paged behavior)
KV_PAGE_TOKENS_DEFAULT = 64


def page_tokens_env() -> Optional[int]:
    """``DL4J_TPU_KV_PAGE_TOKENS``: page size in tokens, ``0`` = dense
    kill switch, unset = None (engine default). Read at engine
    construction, like the other trace-time knobs. A malformed value
    RAISES — this is the documented rollback lever, and an operator's
    failed kill-switch attempt must never silently keep paging on."""
    raw = os.environ.get("DL4J_TPU_KV_PAGE_TOKENS")
    if raw is None or raw == "":
        return None
    try:
        return max(0, int(raw))
    except ValueError:
        raise ValueError(
            f"DL4J_TPU_KV_PAGE_TOKENS={raw!r} is not an integer "
            "(0 = dense kill switch)")


def kv_quant_env() -> bool:
    """``DL4J_TPU_KV_QUANT=1``: opt-in int8 KV storage (paged mode
    only), gated by the deploy-time numerics check. Default off, and
    STRICTLY ``1`` = on (the repo's default-off knob convention) — a
    numerics-changing feature must never engage on ``false``/``off``."""
    return os.environ.get("DL4J_TPU_KV_QUANT", "0") == "1"


def spec_decode_env() -> bool:
    """``DL4J_TPU_SPEC_DECODE``: speculative decoding master switch.
    Engaged only when an engine is BUILT with a draft; ``0`` forces the
    plain one-token decode path even then (the kill switch)."""
    return os.environ.get("DL4J_TPU_SPEC_DECODE", "1") not in ("0", "")


class CacheFeatureUnsupported(ValueError, RuntimeError):
    """An engine was asked for a cache feature its model's cache protocol
    does not provide (the dense cache, the int8 page pool, a speculative
    draft): refused at construction. A ``ValueError`` like the engine's
    other refusals of its arguments, and a ``RuntimeError`` because a
    caller that probes for the int8 pool (``perfbench/control.py``) reads
    "the program refused it" as one."""


class PageAllocator:
    """Host-side free list over the physical page pool. Single-threaded
    by design: the decode loop owns every alloc/free (the same
    exclusivity the slot arrays already have), so there is no lock to
    contend and exhaustion is decided at one place — the step
    boundary."""

    def __init__(self, total: int):
        if total < 1:
            raise ValueError(f"page pool must hold >= 1 page, got {total}")
        self.total = int(total)
        # LIFO free list: recently-freed pages are re-used first, which
        # keeps the touched working set small
        self._free: List[int] = list(range(self.total - 1, -1, -1))

    @property
    def free_count(self) -> int:
        return len(self._free)

    @property
    def in_use(self) -> int:
        return self.total - len(self._free)

    def alloc(self, n: int) -> Optional[List[int]]:
        """Pop ``n`` pages, or None when the pool cannot cover them —
        all-or-nothing (a partial grant would leave a slot half-backed
        and the caller with cleanup it cannot express)."""
        if n <= 0:
            return []
        if n > len(self._free):
            return None
        got = self._free[-n:]
        del self._free[-n:]
        return got

    def free(self, pages: Sequence[int]):
        for p in pages:
            if not 0 <= p < self.total:
                raise ValueError(f"page {p} outside pool [0, {self.total})")
        if pages:
            if len(set(pages)) != len(pages):
                # a duplicated id in one free() is the same corruption
                # class as a double free: the page would enter the free
                # list twice and later back two different slots
                raise ValueError(f"duplicate pages in free: {list(pages)}")
            seen = set(self._free)
            dup = [p for p in pages if p in seen]
            if dup:
                raise ValueError(f"double free of pages {dup}")
        self._free.extend(int(p) for p in pages)


class DecodeState:
    """Mutable cache state for ONE consumer (a pipeline or a generate
    loop): the device cache arrays plus — in paged mode — the host-side
    page allocator, per-slot page lists, and the page table mirror that
    ships to the device. The decode thread owns it exclusively."""

    __slots__ = ("mode", "slots", "arrays", "tables", "tables_dev",
                 "alloc", "slot_pages", "draft_cache")

    def __init__(self, mode: str, slots: int, arrays: Dict,
                 tables: Optional[np.ndarray] = None,
                 alloc: Optional[PageAllocator] = None):
        self.mode = mode                   # "dense" | "paged"
        self.slots = int(slots)
        self.arrays = arrays               # dense cache or page pool
        self.tables = tables               # (slots, pages_per_slot) int32
        self.tables_dev = None             # device mirror, rebuilt lazily
        self.alloc = alloc
        self.slot_pages: List[List[int]] = [[] for _ in range(slots)]
        self.draft_cache = None            # dense draft KV (spec mode)


@dataclasses.dataclass(frozen=True)
class SamplerConfig:
    """In-graph sampling policy. ``greedy`` ignores the rng; ``topk``
    draws from the temperature-scaled top-``top_k`` logits (``top_k=0``
    = full-vocab categorical)."""

    kind: str = "greedy"              # "greedy" | "topk"
    top_k: int = 0
    temperature: float = 1.0

    def __post_init__(self):
        if self.kind not in ("greedy", "topk"):
            raise ValueError(
                f"sampler kind must be 'greedy' or 'topk', got {self.kind!r}")
        if self.temperature <= 0.0:
            raise ValueError("temperature must be > 0 (use kind='greedy' "
                             "for deterministic decoding)")


def _dist_probs(logits_row: np.ndarray, sampler: SamplerConfig) -> np.ndarray:
    """The host-side probability vector a sampler draws from (the
    accept/resample loop needs p and q explicitly): greedy = a delta at
    the argmax, top-k/temperature = softmax over the scaled top-k."""
    v = logits_row.shape[-1]
    if sampler.kind == "greedy":
        p = np.zeros((v,), np.float64)
        p[int(np.argmax(logits_row))] = 1.0
        return p
    scaled = logits_row.astype(np.float64) / sampler.temperature
    if sampler.top_k and sampler.top_k > 0:
        kth = np.sort(scaled)[-sampler.top_k]
        scaled = np.where(scaled >= kth, scaled, -np.inf)
    scaled -= scaled.max()
    e = np.exp(scaled)
    return e / e.sum()


def sample_tokens(logits, rng, sampler: SamplerConfig):
    """(…, V) logits → (…,) int32 tokens under ``sampler`` (traceable)."""
    if sampler.kind == "greedy":
        return jnp.argmax(logits, axis=-1).astype(jnp.int32)
    scaled = (logits / sampler.temperature).astype(jnp.float32)
    if sampler.top_k and sampler.top_k > 0:
        vals, idxs = lax.top_k(scaled, sampler.top_k)
        choice = jax.random.categorical(rng, vals, axis=-1)
        return jnp.take_along_axis(
            idxs, choice[..., None], axis=-1)[..., 0].astype(jnp.int32)
    return jax.random.categorical(rng, scaled, axis=-1).astype(jnp.int32)


@jax.jit
def _carry_tokens(nxt, own):
    """A step's sampled tokens as the next step's input, without leaving
    the device: ``own`` (slots,) holds the scheduler's token for a slot it
    has one for and -1 elsewhere; whatever rides behind the tokens in
    ``nxt`` (a model's counts of the step) is cut off. A few bytes, a
    program of its own beside the decode step's."""
    _cw.note_trace(CARRY_FN, nxt, own)
    return jnp.where(own >= 0, own, nxt[:own.shape[0]])


def default_prefill_buckets(max_len: int, lo: int = 16) -> Tuple[int, ...]:
    """Power-of-two prompt-length buckets up to ``max_len`` (always
    including ``max_len`` itself) — the bounded-executable-set tradeoff
    the serving batch buckets already make."""
    out: List[int] = []
    b = lo
    while b < max_len:
        out.append(b)
        b *= 2
    out.append(max_len)
    return tuple(out)


class DecodeEngine:
    """See module doc. One engine = one (model, params) pair + one
    sampler config; every jitted entry point compiles once per
    (batch-bucket, length-bucket) signature."""

    def __init__(self, model, params, max_len: Optional[int] = None,
                 prefill_buckets: Optional[Sequence[int]] = None,
                 sampler: Optional[SamplerConfig] = None, seed: int = 0,
                 page_tokens: Optional[int] = None,
                 kv_quant: Optional[bool] = None,
                 quant_tol: float = 0.05,
                 draft: Optional["DecodeEngine"] = None, spec_k: int = 4):
        c = model.config
        self.model = model
        self.params = params
        self.max_len = int(max_len if max_len is not None else c.max_len)
        if not 0 < self.max_len <= c.max_len:
            why = ("positions beyond the learned pos_emb table cannot decode"
                   if model.max_positions is not None else
                   "the configuration sizes no cache slot beyond it")
            raise ValueError(
                f"max_len {self.max_len} must be in (0, "
                f"config.max_len={c.max_len}] — {why}")
        self.sampler = sampler if sampler is not None else SamplerConfig()
        if prefill_buckets:
            buckets = tuple(sorted({int(b) for b in prefill_buckets
                                    if 0 < int(b) <= self.max_len}))
            if not buckets:
                raise ValueError(
                    f"prefill_buckets {tuple(prefill_buckets)} has no "
                    f"entry in (0, max_len={self.max_len}]")
        else:
            buckets = default_prefill_buckets(self.max_len)
        self.prefill_buckets = buckets
        self._base_key = jax.random.key(int(seed))
        self._seed = int(seed)
        sampler_cfg = self.sampler

        # ---- paged cache / int8 quant / speculative posture (resolved
        # at construction like the other trace-time knobs)
        pt = page_tokens if page_tokens is not None else page_tokens_env()
        pt = KV_PAGE_TOKENS_DEFAULT if pt is None else int(pt)
        # a page longer than the cache would waste rows AND break the
        # >=2x-slots admission math — clamp silently (power-of-two
        # buckets keep the division exact in practice)
        self.page_tokens = min(pt, self.max_len) if pt > 0 else 0
        self.paged = self.page_tokens > 0
        self.pages_per_slot = (-(-self.max_len // self.page_tokens)
                               if self.paged else 0)
        self.kv_quant = bool(kv_quant if kv_quant is not None
                             else kv_quant_env())
        # what this model's cache does not do is refused here, typed, not
        # ignored at the first request
        features, who = model.cache_features, type(model).__name__
        if not self.paged and "dense" not in features:
            raise CacheFeatureUnsupported(
                f"{who} has no dense (unpaged) cache: "
                "DL4J_TPU_KV_PAGE_TOKENS=0 / page_tokens=0 cannot serve it")
        if self.kv_quant and "int8_pages" not in features:
            raise CacheFeatureUnsupported(
                f"{who} has no int8 page pool "
                "(kv_quant / DL4J_TPU_KV_QUANT=1)")
        if draft is not None and "spec" not in features:
            raise CacheFeatureUnsupported(
                f"{who} cannot verify a speculative draft: its decode "
                "step takes one token a slot")
        if self.kv_quant and not self.paged:
            _log.warning(
                "DL4J_TPU_KV_QUANT requested with the dense cache "
                "(DL4J_TPU_KV_PAGE_TOKENS=0) — int8 storage is per-page; "
                "keeping the f32 dense cache")
            self.kv_quant = False
        self.quant_tol = float(quant_tol)
        #: numerics-gate record (None until the gate has run); the gate
        #: may flip ``kv_quant`` back to False with a loud warning
        self.quant_gate: Optional[dict] = None
        self.spec_k = int(spec_k)
        if draft is not None:
            if self.spec_k < 1:
                raise ValueError(f"spec_k must be >= 1, got {spec_k}")
            dc = draft.model.config
            if dc.vocab_size != c.vocab_size:
                raise ValueError(
                    f"draft vocab {dc.vocab_size} != target vocab "
                    f"{c.vocab_size} — accept/resample needs one "
                    "distribution support")
            if draft.max_len < self.max_len:
                raise ValueError(
                    f"draft max_len {draft.max_len} < target max_len "
                    f"{self.max_len} — the draft must reach every "
                    "position the target decodes")
        self.draft = draft
        #: speculative decoding engaged: a draft was provided AND the
        #: DL4J_TPU_SPEC_DECODE kill switch is not set
        self.spec = draft is not None and spec_decode_env()
        #: cumulative accept-loop stats (the dl4j_spec_accept_ratio
        #: gauge and the snapshot ``spec`` section read these)
        self.spec_stats = {"rounds": 0, "proposed": 0, "accepted": 0}
        #: cumulative seconds ``spec_step`` waited for the device (its
        #: two fetches); a scheduler books the rest of a round as dispatch
        self.spec_fetch_s = 0.0

        def _prefill(params, tokens, last_idx, step):
            _cw.note_trace(PREFILL_FN, tokens)
            logits, kv = model.prefill_cache(params, tokens, last_idx)
            rng = jax.random.fold_in(self._base_key, step)
            # a model returns the logits of every position, or of the
            # prompt's last alone (B, 1, V)
            last = (jnp.take(logits, last_idx, axis=1)       # (B, V)
                    if model.prefill_all_logits else logits[:, 0])
            first = sample_tokens(last, rng, sampler_cfg)
            return first, logits, kv

        def _decode(params, cache, tokens, positions, step):
            _cw.note_trace(DECODE_FN, tokens, positions)
            logits, cache = model.decode_step_math(
                params, cache, tokens, positions)
            rng = jax.random.fold_in(self._base_key, step)
            nxt = sample_tokens(logits, rng, sampler_cfg)
            # positions advance in-graph so a device-resident generate
            # loop never round-trips them through the host
            return nxt, logits, cache, positions + 1

        @jax.named_scope("kv_write")
        def _insert(cache, k, v, slot):
            zero = jnp.zeros((), jnp.int32)
            at = (zero, jnp.asarray(slot, jnp.int32), zero, zero, zero)
            return {"k": lax.dynamic_update_slice(cache["k"], k, at),
                    "v": lax.dynamic_update_slice(cache["v"], v, at)}

        self._prefill_jit = jax.jit(_prefill)
        self._decode_jit = jax.jit(_decode, donate_argnums=(1,))
        self._insert_jit = jax.jit(_insert, donate_argnums=(0,))

        # ---- paged twins: same entry-point names (DECODE_FN), so the
        # zero-steady-state-retrace pins and /debug/perf rows carry over
        page_toks = self.page_tokens

        def _decode_paged(params, pool, tables, tokens, positions, step):
            _cw.note_trace(DECODE_FN, tokens, positions)
            logits, pool, stats = model.decode_paged(
                params, pool, tables, tokens, positions, page_toks)
            rng = jax.random.fold_in(self._base_key, step)
            nxt = sample_tokens(logits, rng, sampler_cfg)
            if stats is not None:
                # the step's counts ride behind its tokens: one transfer
                nxt = jnp.concatenate([nxt, stats.astype(jnp.int32)])
            return nxt, logits, pool

        @jax.named_scope("kv_write")
        def _insert_paged(pool, entries, page_ids, slot):
            return model.insert_paged(pool, entries, page_ids, slot,
                                      page_toks)

        def _verify_paged(params, pool, tables, win, positions, step):
            _cw.note_trace(VERIFY_FN, win, positions)
            logits, pool = model.decode_window_paged(
                params, pool, tables, win, positions, page_toks)
            return logits, pool

        def _verify_dense(params, cache, win, positions, step):
            _cw.note_trace(VERIFY_FN, win, positions)
            logits, cache = model.decode_window_math(
                params, cache, win, positions)
            return logits, cache

        self._decode_paged_jit = jax.jit(_decode_paged, donate_argnums=(1,))
        self._insert_paged_jit = jax.jit(_insert_paged, donate_argnums=(0,))
        self._verify_paged_jit = jax.jit(_verify_paged, donate_argnums=(1,))
        self._verify_dense_jit = jax.jit(_verify_dense, donate_argnums=(1,))

        if draft is not None:
            d_model, d_sampler = draft.model, draft.sampler
            d_key, k_prop = draft._base_key, self.spec_k

            def _propose(dparams, dcache, tokens, positions, step):
                # k sequential draft decode steps fused into ONE
                # executable — one dispatch proposes the whole window
                # (per-step draft dispatches would eat the speculative
                # win on dispatch-bound hosts)
                _cw.note_trace(PROPOSE_FN, tokens, positions)
                t, pos = tokens, positions
                props, dlogits = [], []
                for j in range(k_prop):
                    logits, dcache = d_model.decode_step_math(
                        dparams, dcache, t, pos)
                    rng = jax.random.fold_in(d_key,
                                             step * (k_prop + 1) + j)
                    t = sample_tokens(logits, rng, d_sampler)
                    props.append(t)
                    dlogits.append(logits)
                    pos = pos + 1
                return (jnp.stack(props, axis=1),
                        jnp.stack(dlogits, axis=1), dcache)

            self._propose_jit = jax.jit(_propose, donate_argnums=(1,))

    # ------------------------------------------------------------- cache
    def new_state(self, slots: int,
                  pages: Optional[int] = None) -> DecodeState:
        """Build the decode-side cache state for ``slots`` concurrent
        sequences. Paged mode: a pool of ``pages`` physical pages
        (default = the dense worst case, ``slots * pages_per_slot``;
        pass FEWER to admit by actual cached tokens against a fixed
        HBM budget) plus one reserved trash page that free slots' table
        rows point at — a freed slot's stale writes can never land in a
        page another slot owns. Spec mode adds the draft's dense KV."""
        if not self.paged:
            state = DecodeState("dense", slots,
                                self.model.init_cache(slots, self.max_len))
        else:
            n = int(pages) if pages is not None \
                else slots * self.pages_per_slot
            if n < 1:
                raise ValueError(f"page pool needs >= 1 page, got {n}")
            pool = self.model.new_paged_cache(
                slots, n + 1, self.page_tokens, quant=self._quant_active())
            tables = np.full((slots, self.pages_per_slot), n, np.int32)
            state = DecodeState("paged", slots, pool, tables=tables,
                                alloc=PageAllocator(n))
        if self.spec:
            # the draft's dense cache must hold every position the
            # target decodes AND its own largest prefill bucket for the
            # longest admissible prompt (its buckets may be coarser)
            draft_len = max(self.max_len,
                            self.draft.prefill_bucket(self.max_len))
            state.draft_cache = self.draft.model.init_cache(
                slots, draft_len)
        return state

    def new_cache(self, slots: int) -> DecodeState:
        """Back-compat spelling of :meth:`new_state`."""
        return self.new_state(slots)

    @staticmethod
    def cache_bytes(cache) -> int:
        """Total device bytes of a cache/state (dense prealloc, or the
        whole page pool + every slot's fixed state + draft cache) — the
        worst-case footprint."""
        if isinstance(cache, DecodeState):
            total = sum(int(a.nbytes) for a in jax.tree.leaves(cache.arrays))
            if cache.draft_cache is not None:
                total += sum(int(a.nbytes)
                             for a in jax.tree.leaves(cache.draft_cache))
            return int(total)
        return int(sum(int(a.nbytes) for a in jax.tree.leaves(cache)))

    def page_bytes(self) -> int:
        """Device bytes one page costs ACROSS ALL LAYERS that keep pages
        (one allocated page id pins a stripe of every such layer), as the
        model's cache lays them out — ``pages_in_use x page_bytes`` is
        the resident paged cache, the admission unit."""
        if not self.paged:
            return 0
        return int(self.model.page_bytes(self.page_tokens,
                                         quant=self._quant_active()))

    def slot_state_bytes(self) -> int:
        """Device bytes of the fixed state ONE slot holds beside its
        pages (a recurrent layer's state; 0 for a model whose cache is
        pages alone)."""
        return int(self.model.slot_state_bytes())

    def resident_cache_bytes(self, state: DecodeState) -> int:
        """ACTUAL resident TARGET-cache bytes: dense = the full
        preallocation (all resident); paged = pages in use x page bytes
        post-quantization, plus the fixed state of every OCCUPIED slot (a
        freed slot's state is dead until the next insert overwrites it)
        — the admission unit the dl4j_decode_cache_bytes gauge reports.
        The draft's fixed dense cache is deliberately excluded (a
        constant, visible in the snapshot's ``pool_bytes`` worst-case
        figure)."""
        if state.mode != "paged":
            return int(sum(int(a.nbytes)
                           for a in jax.tree.leaves(state.arrays)))
        occupied = sum(1 for p in state.slot_pages if p)
        return int(state.alloc.in_use * self.page_bytes()
                   + occupied * self.slot_state_bytes())

    # ------------------------------------------------------ page plumbing
    def pages_for(self, n_tokens: int) -> int:
        """Pages covering ``n_tokens`` cache rows."""
        return -(-int(n_tokens) // self.page_tokens) if self.paged else 0

    def min_pages_for_prompt(self, prompt_len: int) -> int:
        """Pages a request needs to ADMIT: the prefill writes its whole
        padded bucket, and the first decode step writes at position
        ``prompt_len`` — whichever reaches further."""
        if not self.paged:
            return 0
        bucket = self.prefill_bucket(prompt_len)
        return max(self.pages_for(bucket), self.pages_for(prompt_len + 1))

    def ensure_slot_pages(self, state: DecodeState, slot: int,
                          last_position: int) -> bool:
        """Grow ``slot``'s page list to cover a write at
        ``last_position``; False when the pool is exhausted (the caller
        sheds/reclaims at the step boundary — nothing was allocated)."""
        if state.mode != "paged":
            return True
        needed = int(last_position) // self.page_tokens + 1
        have = len(state.slot_pages[slot])
        if needed <= have:
            return True
        got = state.alloc.alloc(needed - have)
        if got is None:
            return False
        state.slot_pages[slot].extend(got)
        state.tables[slot, have:needed] = got
        state.tables_dev = None
        return True

    def free_slot(self, state: DecodeState, slot: int):
        """Return ``slot``'s pages to the pool and repoint its table row
        at the trash page (stale writes from the freed slot become
        harmless scribbles nobody's table references)."""
        if state.mode != "paged":
            return
        pages = state.slot_pages[slot]
        if pages:
            state.alloc.free(pages)
            state.slot_pages[slot] = []
            state.tables[slot, :] = state.alloc.total
            state.tables_dev = None

    def _tables(self, state: DecodeState):
        if state.tables_dev is None:
            # a copy, as for a step's tokens and positions: on the CPU
            # backend ``jnp.asarray`` may alias the host array, and the
            # host's table changes (a join, a freed slot) while a step that
            # was given this one is still in flight
            state.tables_dev = jnp.asarray(state.tables.copy())
        return state.tables_dev

    # ---------------------------------------------------- quant numerics
    def _quant_active(self) -> bool:
        """int8 storage is live only after the deploy/warmup-time
        numerics gate passes; a failed gate falls back to f32 pages
        with a loud warning."""
        if not self.kv_quant:
            return False
        if self.quant_gate is None:
            self._run_quant_gate()
        return self.kv_quant

    def _run_quant_gate(self):
        """Compare int8-cached decode logits against the f32 dense
        reference on a small probe (eager, off every jit cache): prefill
        the smallest bucket, teacher-force a few greedy steps through
        BOTH paths, and compare per-step logits. Divergence beyond
        ``quant_tol`` flips the engine back to f32 storage."""
        model, params = self.model, self.params
        bucket = self.prefill_buckets[0]
        if self.max_len - bucket < 1:
            # the smallest bucket fills the cache — probe a shorter
            # prompt so the gate has room to decode
            bucket = self.prefill_bucket(max(1, self.max_len // 2))
        steps = max(1, min(4, self.max_len - bucket))
        rng = np.random.default_rng(1234)
        prompt = rng.integers(0, model.config.vocab_size,
                              (1, bucket)).astype(np.int32)
        logits_p, kv = model.prefill(params, jnp.asarray(prompt))
        # f32 dense reference cache
        ref = model.init_cache(1, self.max_len)
        zero = jnp.zeros((), jnp.int32)
        at = (zero, zero, zero, zero, zero)
        ref = {"k": lax.dynamic_update_slice(ref["k"], kv["k"], at),
               "v": lax.dynamic_update_slice(ref["v"], kv["v"], at)}
        # quantized paged probe: one slot, enough pages for the probe
        n_pages = min(self.pages_for(bucket + steps), self.pages_per_slot)
        pool = model.init_paged_cache(n_pages + 1, self.page_tokens,
                                      quant=True)
        tables = np.full((1, self.pages_per_slot), n_pages, np.int32)
        tables[0, :n_pages] = np.arange(n_pages)
        # the insert production traces, run eagerly: ONE packing
        pool = model.insert_paged(
            pool, kv, jnp.arange(self.pages_for(bucket), dtype=jnp.int32),
            0, self.page_tokens)
        tok = jnp.argmax(logits_p[:, bucket - 1], axis=-1).astype(jnp.int32)
        pos = jnp.full((1,), bucket, jnp.int32)
        max_diff = 0.0
        argmax_agree = True
        tables_dev = jnp.asarray(tables)
        for _ in range(steps):
            ref_logits, ref = model.decode_step_math(params, ref, tok, pos)
            q_logits, pool = model.decode_window_paged(
                params, pool, tables_dev, tok[:, None], pos,
                self.page_tokens)
            q_logits = q_logits[:, 0]
            diff = float(jnp.max(jnp.abs(q_logits - ref_logits)))
            max_diff = max(max_diff, diff)
            if int(jnp.argmax(q_logits)) != int(jnp.argmax(ref_logits)):
                argmax_agree = False
            # teacher-force the REFERENCE continuation so quantization
            # error is measured per step, never compounded by token
            # divergence
            tok = jnp.argmax(ref_logits, axis=-1).astype(jnp.int32)
            pos = pos + 1
        passed = max_diff <= self.quant_tol
        self.quant_gate = {"checked": True, "passed": passed,
                           "max_abs_logit_diff": max_diff,
                           "tol": self.quant_tol,
                           "argmax_agree": argmax_agree}
        if not passed:
            self.kv_quant = False
            _log.warning(
                "int8 KV-cache numerics gate FAILED (max |logit diff| "
                "%.4g > tol %.4g) — falling back to f32 page storage",
                max_diff, self.quant_tol)

    # ----------------------------------------------------------- buckets
    def prefill_bucket(self, length: int) -> int:
        """Smallest configured bucket that fits a ``length``-token
        prompt (raises when none does — the caller must shed, not
        silently truncate a prompt)."""
        i = bisect.bisect_left(self.prefill_buckets, length)
        if i >= len(self.prefill_buckets):
            raise ValueError(
                f"prompt length {length} exceeds the largest prefill "
                f"bucket {self.prefill_buckets[-1]}")
        return self.prefill_buckets[i]

    def _pad_prompt(self, prompt: np.ndarray) -> Tuple[np.ndarray, int]:
        prompt = np.asarray(prompt, np.int32)
        if prompt.ndim == 1:
            prompt = prompt[None]
        t = prompt.shape[1]
        bucket = self.prefill_bucket(t)
        if t < bucket:
            prompt = np.concatenate(
                [prompt, np.zeros((prompt.shape[0], bucket - t), np.int32)],
                axis=1)
        return prompt, t

    # ------------------------------------------------------ entry points
    def prefill(self, prompt: np.ndarray, step: int = 0):
        """Pad ``prompt`` (B, T) to its length bucket and run the jitted
        prefill. Returns (first_token (B,), logits (B, T_bucket, V),
        kv, real_length)."""
        padded, t = self._pad_prompt(prompt)
        args = (self.params, jnp.asarray(padded),
                jnp.asarray(t - 1, jnp.int32), jnp.asarray(step, jnp.int32))
        first, logits, kv = self._prefill_jit(*args)
        self._maybe_account(PREFILL_FN, self._prefill_jit, args)
        return first, logits, kv, t

    def decode(self, cache, tokens: np.ndarray, positions: np.ndarray,
               step: int):
        """One jitted decode step. ``cache`` is donated — the caller
        must use the returned one (a :class:`DecodeState` is mutated in
        place AND returned). Returns (next_tokens (B,), logits (B, V),
        cache). Paged callers must have ensured pages for every write
        position (:meth:`ensure_slot_pages`).

        Of a step's inputs only ``tokens`` depends on the step before:
        ``positions`` advance by one a step, the page tables are the
        host's own books and ``step`` is a counter, so all three are host
        values here. ``tokens`` may be host values too, or an array still
        on the device (:meth:`carry_tokens` of the last step's output):
        the call then returns without waiting for that step, and the
        runtime starts this one when it ends. Calls run on the device in
        the order they were made, which is why a scheduler may hand pages
        it freed after the last call to whatever it calls next (an
        insert, a later step): a stale write of the step still in flight
        lands first. A scheduler that keeps a step in flight does so only
        while every slot is occupied: with a free slot a queued step would
        stand between an arrival and its prefill."""
        if isinstance(cache, DecodeState) and cache.mode == "paged":
            # back every OCCUPIED slot's write position (positions are
            # host values). Slots with no pages are free: their table
            # rows point at the trash page, so their writes are
            # harmless scribbles needing no allocation — same for
            # past-the-end positions of retired slots.
            for b, pos in enumerate(np.asarray(positions)):
                if not cache.slot_pages[b] or int(pos) >= self.max_len:
                    continue
                if not self.ensure_slot_pages(cache, b, int(pos)):
                    raise CachePagesExhausted(
                        f"page pool exhausted backing slot {b} at "
                        f"position {int(pos)}")
            nxt, logits, cache.arrays = self._decode_paged_jit(
                self.params, cache.arrays, self._tables(cache),
                jnp.asarray(tokens, jnp.int32),
                jnp.asarray(positions, jnp.int32),
                jnp.asarray(step, jnp.int32))
            return nxt, logits, cache
        arrays = cache.arrays if isinstance(cache, DecodeState) else cache
        nxt, logits, arrays, _pos = self._decode_jit(
            self.params, arrays, jnp.asarray(tokens, jnp.int32),
            jnp.asarray(positions, jnp.int32), jnp.asarray(step, jnp.int32))
        if isinstance(cache, DecodeState):
            cache.arrays = arrays
            return nxt, logits, cache
        return nxt, logits, arrays

    def carry_tokens(self, nxt, own: np.ndarray):
        """The ``tokens`` of the step after the one that returned ``nxt``,
        as a device array: ``nxt``'s tokens, with ``own[slot]`` where it
        is not -1 (a slot whose request joined since and brings its own
        first token). Nothing is fetched."""
        return _carry_tokens(nxt, jnp.asarray(own, jnp.int32))

    def warm_carry(self, slots: int):
        """Compile :meth:`carry_tokens`'s program for ``slots`` (a model's
        counts ride behind its tokens, see :meth:`step_counts`): a
        scheduler calls this when it is built, so that its first full
        batch compiles nothing."""
        self.carry_tokens(
            np.zeros((slots + len(self.model.step_stats),), np.int32),
            np.full((slots,), -1, np.int32))

    def step_counts(self, fetched: np.ndarray, slots: int) -> Dict[str, int]:
        """What rode behind a decode step's tokens in their one transfer:
        ``{name: count}`` for the model's ``step_stats`` (empty for a
        model that returns none)."""
        names = self.model.step_stats
        return {n: int(v) for n, v in zip(names, fetched[slots:])}

    def insert_slot(self, cache, kv, slot: int):
        """Write a prefill's cache entries (for ``TransformerLM`` the
        (L, Bp, T_bucket, H, hd) k/v) into the cache
        starting at ``slot`` (donates the cache arrays). Dense: a traced
        slot index — joining slot 3 reuses slot 0's executable. Paged: a
        :class:`DecodeState` is required; the slot's pages are
        allocated here (raises :class:`CachePagesExhausted` when the
        pool cannot cover the prompt's bucket — nothing allocated,
        nothing written)."""
        if isinstance(cache, DecodeState) and cache.mode == "paged":
            npb = self.pages_for(self.model.entries_tokens(kv))
            if cache.slot_pages[slot]:
                self.free_slot(cache, slot)
            pages = cache.alloc.alloc(npb)
            if pages is None:
                raise CachePagesExhausted(
                    f"KV page pool exhausted: prompt bucket needs {npb} "
                    f"pages, {cache.alloc.free_count} free of "
                    f"{cache.alloc.total}")
            cache.slot_pages[slot] = pages
            cache.tables[slot, :npb] = pages
            cache.tables_dev = None
            cache.arrays = self._insert_paged_jit(
                cache.arrays, kv, jnp.asarray(pages, jnp.int32),
                jnp.asarray(slot, jnp.int32))
            return cache
        arrays = cache.arrays if isinstance(cache, DecodeState) else cache
        arrays = self._insert_jit(arrays, kv["k"], kv["v"],
                                  jnp.asarray(slot, jnp.int32))
        if isinstance(cache, DecodeState):
            cache.arrays = arrays
            return cache
        return arrays

    def insert_draft_slot(self, state: DecodeState, slot: int,
                          prompt: np.ndarray, step: int = 0):
        """Spec mode: run the DRAFT's prefill over the same prompt and
        land its k/v in the draft's dense cache at ``slot`` — the draft
        tracks every position the target decodes."""
        _first, _logits, kv, _t = self.draft.prefill(prompt, step=step)
        state.draft_cache = self.draft._insert_jit(
            state.draft_cache, kv["k"], kv["v"],
            jnp.asarray(slot, jnp.int32))

    # -------------------------------------------------- speculative step
    def spec_step(self, state: DecodeState, tokens: np.ndarray,
                  positions: np.ndarray, step: int,
                  active: Sequence[int]) -> Dict[int, List[int]]:
        """One speculative round for the whole slot batch: the draft
        proposes ``spec_k`` tokens per slot in ONE fused executable, the
        target scores carry+proposals in ONE windowed verify step, and
        the standard accept/resample loop keeps the emitted distribution
        exactly the target's (greedy mode: byte-identical tokens to
        plain decode). Returns ``{slot: [emitted...]}`` for active slots
        (1..spec_k tokens each; the LAST emitted token is the next
        carry). The caller advances tokens/positions from the emitted
        lists; paged callers must have ensured pages through
        ``positions + spec_k``. The all-accepted bonus token is
        deliberately forfeited: emitting it would leave the draft cache
        one position behind and force a non-uniform catch-up step."""
        k = self.spec_k
        with _span("decode_dispatch"):
            if state.mode == "paged":
                for b in active:
                    last = min(int(positions[b]) + k, self.max_len - 1)
                    if not self.ensure_slot_pages(state, b, last):
                        raise CachePagesExhausted(
                            f"page pool exhausted backing slot {b}'s "
                            f"verify window through position {last}")
            props, dlog, state.draft_cache = self._propose_jit(
                self.draft.params, state.draft_cache,
                jnp.asarray(tokens, jnp.int32),
                jnp.asarray(positions, jnp.int32),
                jnp.asarray(step, jnp.int32))
        t0 = time.perf_counter()
        with _span("token_fetch"):
            props = np.asarray(props)                   # (B, k)
        t1 = time.perf_counter()
        with _span("decode_dispatch"):
            win = np.concatenate([np.asarray(tokens, np.int32)[:, None],
                                  props], axis=1)       # (B, k+1)
            if state.mode == "paged":
                logits, state.arrays = self._verify_paged_jit(
                    self.params, state.arrays, self._tables(state),
                    jnp.asarray(win), jnp.asarray(positions, jnp.int32),
                    jnp.asarray(step, jnp.int32))
            else:
                logits, state.arrays = self._verify_dense_jit(
                    self.params, state.arrays, jnp.asarray(win),
                    jnp.asarray(positions, jnp.int32),
                    jnp.asarray(step, jnp.int32))
        t2 = time.perf_counter()
        with _span("token_fetch"):
            logits = np.asarray(logits)                 # (B, k+1, V)
        self.spec_fetch_s += (t1 - t0) + (time.perf_counter() - t2)
        greedy = (self.sampler.kind == "greedy"
                  and self.draft.sampler.kind == "greedy")
        dlog_h = None if greedy else np.asarray(dlog)
        rng = (None if greedy
               else np.random.default_rng((self._seed, 0x5BEC, step)))
        emitted: Dict[int, List[int]] = {}
        for b in active:
            out: List[int] = []
            accepted = 0
            for j in range(k):
                d = int(props[b, j])
                if greedy:
                    g = int(np.argmax(logits[b, j]))
                    if d == g:
                        out.append(d)
                        accepted += 1
                        continue
                    out.append(g)       # the token plain decode emits
                    break
                p = _dist_probs(logits[b, j], self.sampler)
                q = _dist_probs(dlog_h[b, j], self.draft.sampler)
                if rng.random() < min(1.0, p[d] / max(q[d], 1e-20)):
                    out.append(d)
                    accepted += 1
                    continue
                resid = np.maximum(p - q, 0.0)
                z = float(resid.sum())
                if z <= 0.0:
                    # draft == target distribution: any residual draw
                    # is a no-op; emit from the target directly
                    out.append(int(rng.choice(len(p), p=p)))
                else:
                    out.append(int(rng.choice(len(resid), p=resid / z)))
                break
            self.spec_stats["proposed"] += k
            self.spec_stats["accepted"] += accepted
            emitted[b] = out
        self.spec_stats["rounds"] += 1
        return emitted

    def spec_accept_ratio(self) -> Optional[float]:
        p = self.spec_stats["proposed"]
        return (self.spec_stats["accepted"] / p) if p else None

    def warm(self, slots: int, note=None) -> List[int]:
        """Compile the engine's whole executable set against a THROWAWAY
        state: one prefill + one slot-insert per length bucket, one
        decode step at the (``slots``,) signature — and, in spec mode,
        the draft's prefill/insert set, the fused k-token propose
        executable, and the windowed verify executable, so a paired
        draft+target deploy warms BOTH models before admitting traffic.
        The quant numerics gate runs here too (first state build). The
        jit caches live on this engine, so the first real traffic
        afterward is a pure cache hit. One spelling shared by
        ``ModelRegistry._warmup_generative`` and the decode benchmark —
        the bench must warm exactly what a production deploy warms.
        ``note(**attrs)`` (optional) is called before each compile-
        provoking step so the caller can declare compile causes.
        Returns the warmed prefill buckets."""
        warmed: List[int] = []
        state = self.new_state(slots)
        for bucket in self.prefill_buckets:
            if note is not None:
                note(bucket=bucket)
            first, _logits, kv, _t = self.prefill(
                np.zeros((1, bucket), np.int32), step=0)
            np.asarray(first)                  # execute + block
            state = self.insert_slot(state, kv, 0)
            if self.spec:
                self.insert_draft_slot(state, 0,
                                       np.zeros((1, bucket), np.int32))
            warmed.append(bucket)
        if note is not None:
            note(decode_slots=slots)
        tokens = np.zeros((slots,), np.int32)
        positions = np.zeros((slots,), np.int32)
        for s in range(slots):
            self.ensure_slot_pages(state, s, 0)
        nxt, _logits, state = self.decode(state, tokens, positions, 0)
        np.asarray(nxt)                        # decode executable seeded
        self.account_decode(state, tokens, positions, 0)
        if self.spec:
            if note is not None:
                note(spec_k=self.spec_k)
            for s in range(slots):
                self.ensure_slot_pages(state, s, self.spec_k)
            # seed propose + verify without touching the accept stats
            stats = dict(self.spec_stats)
            self.spec_step(state, tokens, positions, 0, range(slots))
            self.spec_stats = stats
        return warmed

    def decode_compile_count(self) -> int:
        """Compile-watch trace count of the decode entry point — the
        steady-state-zero-retrace assertion surface."""
        return _cw.global_compile_watch().count_for(DECODE_FN)

    def _maybe_account(self, fn: str, jitted, args):
        """Cost-model accounting, once per fresh compile of ``fn`` (the
        re-``lower()`` at the signature that just ran is a jaxpr-cache
        hit — same contract as ``maybe_account_bucket``)."""
        try:
            cm = _cost.global_cost_model()
            if _cost.cost_model_enabled() and cm.needs_account(fn, fn):
                cm.account(fn, lambda: jitted.lower(*args), probe_fn=fn)
        except Exception:       # accounting is telemetry, never the path
            pass

    def account_spec(self, state: DecodeState, tokens, positions,
                     step: int):
        """Cost accounting for the speculative pair — the fused k-step
        propose and the W=k+1 verify each get their own /debug/perf
        entry (a spec round's work must never be booked against the
        one-token decode executable that did not run)."""
        win = jnp.zeros((len(np.asarray(tokens)), self.spec_k + 1),
                        jnp.int32)
        tok = jnp.asarray(tokens, jnp.int32)
        pos = jnp.asarray(positions, jnp.int32)
        stp = jnp.asarray(step, jnp.int32)
        self._maybe_account(
            PROPOSE_FN, self._propose_jit,
            (self.draft.params, state.draft_cache, tok, pos, stp))
        if state.mode == "paged":
            self._maybe_account(
                VERIFY_FN, self._verify_paged_jit,
                (self.params, state.arrays, self._tables(state), win,
                 pos, stp))
        else:
            self._maybe_account(
                VERIFY_FN, self._verify_dense_jit,
                (self.params, state.arrays, win, pos, stp))

    def account_decode(self, cache, tokens, positions, step: int):
        """Decode-step cost accounting at the signature in flight (the
        pipeline calls this after a step that followed a fresh trace)."""
        if isinstance(cache, DecodeState) and cache.mode == "paged":
            self._maybe_account(
                DECODE_FN, self._decode_paged_jit,
                (self.params, cache.arrays, self._tables(cache),
                 jnp.asarray(tokens, jnp.int32),
                 jnp.asarray(positions, jnp.int32),
                 jnp.asarray(step, jnp.int32)))
            return
        arrays = cache.arrays if isinstance(cache, DecodeState) else cache
        self._maybe_account(
            DECODE_FN, self._decode_jit,
            (self.params, arrays, jnp.asarray(tokens, jnp.int32),
             jnp.asarray(positions, jnp.int32),
             jnp.asarray(step, jnp.int32)))

    # ------------------------------------------------- convenience loop
    def generate(self, prompts, max_new_tokens: int,
                 eos_id: Optional[int] = None, return_logits: bool = False,
                 on_token=None):
        """Single-batch generation without the serving pipeline: prefill
        once, then ``max_new_tokens − 1`` decode steps. ``prompts``
        (B, T) share one length. Returns (B, n_generated) int32 — or
        (tokens, per-step logits list) with ``return_logits``.

        ``on_token(token, index)`` (optional, B=1 only) surfaces each
        token at the step boundary that produced it — the same per-token
        streaming contract ``GenerationPipeline.generate`` makes, minus
        the cancel semantics (this loop has no slot to free; a callback
        error simply propagates). Streaming forces a per-step host sync,
        trading the single-fetch async dispatch chain for latency to
        first token — exactly the tradeoff a streaming caller wants."""
        prompts = np.asarray(prompts, np.int32)
        if prompts.ndim == 1:
            prompts = prompts[None]
        B, T = prompts.shape
        if on_token is not None and B != 1:
            raise ValueError(
                f"on_token streams a single sequence; got batch of {B}")
        if T + max_new_tokens > self.max_len:
            raise ValueError(
                f"prompt ({T}) + max_new_tokens ({max_new_tokens}) "
                f"exceeds the cache length {self.max_len}")
        if self.spec:
            if B != 1:
                raise ValueError(
                    "speculative generate decodes one sequence (the "
                    "slot-batched path is GenerationPipeline)")
            if return_logits:
                raise ValueError("return_logits is not available under "
                                 "speculative decoding (a verify step "
                                 "has no single per-token logits row "
                                 "for rejected proposals)")
            return self._generate_spec(prompts, max_new_tokens, eos_id,
                                       on_token)
        first, logits, kv, t = self.prefill(prompts, step=0)
        state = self.new_state(B)
        if self.paged:
            for b in range(B):
                state = self.insert_slot(
                    state, self.model.entries_row(kv, b), b)
            return self._generate_paged(state, first, logits, t, B,
                                        max_new_tokens, eos_id,
                                        return_logits, on_token)
        # dense kill-switch path: the pre-paged device-resident loop,
        # verbatim, on the raw cache arrays
        cache = self.insert_slot(state, kv, 0).arrays
        # device-resident loop: tokens/positions stay on device between
        # steps; the host syncs per step ONLY when it must look at the
        # tokens (eos streaming / logits collection) — otherwise the
        # whole continuation is one async dispatch chain with a single
        # fetch at the end
        out = [first]
        logit_steps = [np.asarray(logits)[:, t - 1]] if return_logits else []
        if on_token is not None:
            on_token(int(np.asarray(first)[0]), 0)
        tokens = first
        positions = jnp.full((B,), t, jnp.int32)
        done = (np.asarray(first) == eos_id) if eos_id is not None else None
        for step in range(1, max_new_tokens):
            if done is not None and bool(np.all(done)):
                break
            tokens, logits, cache, positions = self._decode_jit(
                self.params, cache, tokens, positions,
                jnp.asarray(step, jnp.int32))
            if step == 1:
                self._maybe_account(
                    DECODE_FN, self._decode_jit,
                    (self.params, cache, tokens, positions,
                     jnp.asarray(step, jnp.int32)))
            out.append(tokens)
            if on_token is not None:
                on_token(int(np.asarray(tokens)[0]), step)
            if return_logits:
                logit_steps.append(np.asarray(logits))
            if done is not None:
                # running mask over just THIS step's tokens — no O(n²)
                # re-scan of the whole history
                done |= np.asarray(tokens) == eos_id
        toks = np.stack([np.asarray(o) for o in out], axis=1).astype(
            np.int32)
        if return_logits:
            return toks, logit_steps
        return toks

    def _generate_paged(self, state, first, logits, t, B,
                        max_new_tokens: int, eos_id, return_logits,
                        on_token):
        """The paged twin of the dense generate loop: same step
        semantics, cache writes scatter through the page table. Page
        growth is arithmetic (position = t + step), so the host
        allocates ahead of each step without syncing the tokens."""
        out = [first]
        at = t - 1 if self.model.prefill_all_logits else 0
        logit_steps = [np.asarray(logits)[:, at]] if return_logits else []
        if on_token is not None:
            on_token(int(np.asarray(first)[0]), 0)
        tokens = first
        positions = np.full((B,), t, np.int32)
        done = (np.asarray(first) == eos_id) if eos_id is not None else None
        for step in range(1, max_new_tokens):
            if done is not None and bool(np.all(done)):
                break
            for b in range(B):
                if not self.ensure_slot_pages(state, b, t + step):
                    raise CachePagesExhausted(
                        f"page pool exhausted at decode position "
                        f"{t + step} (pool {state.alloc.total} pages)")
            tokens, logits, state = self.decode(state, tokens, positions,
                                                step)
            if self.model.step_stats:   # the step's counts ride behind
                tokens = tokens[:B]
            positions = positions + 1
            if step == 1:
                self.account_decode(state, tokens, positions, step)
            out.append(tokens)
            if on_token is not None:
                on_token(int(np.asarray(tokens)[0]), step)
            if return_logits:
                logit_steps.append(np.asarray(logits))
            if done is not None:
                done |= np.asarray(tokens) == eos_id
        toks = np.stack([np.asarray(o) for o in out], axis=1).astype(
            np.int32)
        if return_logits:
            return toks, logit_steps
        return toks

    def _generate_spec(self, prompts, max_new_tokens: int, eos_id,
                       on_token):
        """Draft-accelerated single-sequence generation: prefill both
        models, then speculative rounds (one fused k-token propose +
        one windowed verify per round) until the budget or eos."""
        first, _logits, kv, t = self.prefill(prompts, step=0)
        state = self.new_state(1)
        state = self.insert_slot(state, kv, 0)
        self.insert_draft_slot(state, 0, prompts)
        carry = int(np.asarray(first)[0])
        out = [carry]
        if on_token is not None:
            on_token(carry, 0)
        if eos_id is not None and carry == eos_id:
            return np.asarray([out], np.int32)
        pos, step = t, 0
        while len(out) < max_new_tokens:
            if self.paged:
                last = min(pos + self.spec_k, self.max_len - 1)
                if not self.ensure_slot_pages(state, 0, last):
                    raise CachePagesExhausted(
                        f"page pool exhausted at decode position {last} "
                        f"(pool {state.alloc.total} pages)")
            emitted = self.spec_step(
                state, np.asarray([carry], np.int32),
                np.asarray([pos], np.int32), step, [0])[0]
            stop = False
            for tok in emitted:
                if len(out) >= max_new_tokens:
                    stop = True
                    break
                out.append(tok)
                if on_token is not None:
                    on_token(tok, len(out) - 1)
                if eos_id is not None and tok == eos_id:
                    stop = True
                    break
            if stop:
                break
            pos += len(emitted)
            carry = emitted[-1]
            step += 1
            if pos + 1 >= self.max_len:
                break               # no room for another cache write
        return np.asarray([out], np.int32)


def naive_generate(model, params, prompts, max_new_tokens: int,
                   pad_to: Optional[int] = None,
                   sampler: Optional[SamplerConfig] = None, seed: int = 0):
    """The full-recompute baseline: one fixed-shape ``apply`` executable
    re-run over the WHOLE padded sequence per emitted token (greedy by
    default). O(T) forwards of O(T²) attention each — what serving costs
    without a KV cache. Returns (B, max_new_tokens) int32."""
    prompts = np.asarray(prompts, np.int32)
    if prompts.ndim == 1:
        prompts = prompts[None]
    B, T = prompts.shape
    pad_to = int(pad_to or model.config.max_len)
    if T + max_new_tokens > pad_to:
        raise ValueError(f"prompt ({T}) + max_new_tokens "
                         f"({max_new_tokens}) exceeds pad_to {pad_to}")
    sampler = sampler or SamplerConfig()
    # one jit wrapper per MODEL (cached on it): interleaved bench repeats
    # must not retrace per call
    fwd = model.__dict__.get("_naive_apply_jit")
    if fwd is None:
        fwd = jax.jit(lambda p, toks: model.apply(p, toks))
        model.__dict__["_naive_apply_jit"] = fwd
    key = jax.random.key(int(seed))
    seq = np.zeros((B, pad_to), np.int32)
    seq[:, :T] = prompts
    out = []
    for i in range(max_new_tokens):
        logits = fwd(params, jnp.asarray(seq))
        # slice the sampled position on DEVICE — shipping the whole
        # (B, T, V) logits tensor to the host every token would be a
        # strawman baseline, not the naive path's real cost
        nxt = np.asarray(sample_tokens(logits[:, T + i - 1],
                                       jax.random.fold_in(key, i), sampler))
        seq[:, T + i] = nxt
        out.append(nxt)
    return np.stack(out, axis=1).astype(np.int32)
