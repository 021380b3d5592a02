"""HybridLM - a decoder built from a per-layer description.

Each layer names its mixer (``kda``: gated delta-rule linear attention with a
short convolution; ``mla``: latent attention, its rope dimensions rotated
where the configuration gives a ``rope_theta`` and carried unrotated where
not, its queries behind a bottleneck where it gives a ``q_lora_rank``;
``mamba2``: a state-space layer with a scalar decay a head; ``mamba1``: one
with a decay a channel and state dimension; ``gqa``: softmax attention with
grouped key/value heads over every earlier position; ``swa``: the same
attention over the last ``swa_window`` positions; ``xattn``: queries alone
over the rows of an earlier ``gqa`` layer; ``gmu``: a gate on an earlier
``mamba1`` layer's output of the same pass) and its feed-forward
(``dense`` SwiGLU, or ``moe``: routed experts of which this chip holds a
share, ``parallel/moe.py::routed_experts_ffn``). Either may be absent: a layer
is then ONE pre-norm residual part, a mixer alone or a feed-forward alone,
with one norm. A layer that is more than that lists its ``parts``
(:class:`Part`): each a mixer or a feed-forward with the name of its
parameters, the norm it reads through (or the rows the part before it read)
and where its result lands - at once, or after the layer's last part (a
shortcut around what lies between: the double layer whose expert layer reads
the first feed-forward's rows and is added behind the second). The two
grouped-query kinds share their key/value heads and head size and have, each
of its own, a head count, a rotation (:class:`Rope`: none, plain, or YaRN's
scaled frequencies; on all of a head or on its leading dimensions; an
amplitude factor) and a head-wise sigmoid gate on the attention's output;
keys are rotated once, at their absolute position, before they are cached.
They may be differential (heads in pairs, two softmax maps a pair over
a value twice as wide, their difference normalised: computed as grouped-query
attention on padded pairs, ``HybridConfig.attention_shape``) and may carry
biases on their projections, both kinds alike. RMSNorm or LayerNorm
(``norm``), no position table, an untied head or the embedding's transpose
(``tie_embeddings``).
Parameters are held in ``param_dtype`` and computed with as they are: nothing
is cast per call.

Three spellings of the same mathematics:

- :meth:`apply` - the full forward: chunked KDA (the UT / WY form) and the
  chunked Mamba-2 scan (every decay exponent a difference that is <= 0, so
  no channel's decay can overflow), expanded MLA and grouped-query attention
  in blocks of queries.
- :meth:`prefill_cache` - the same over a padded bucket, returning the logits
  of the prompt's last token and what the cache needs: the rows of every
  position for a paged layer and, for a recurrent layer, the state and the
  convolution's 3-row tail at the prompt's TRUE last token (rows beyond it
  are identity updates). Where the configuration names ``last_row_from``,
  the layers from there on are run on that token's row alone.
- :meth:`decode_paged` - one token a slot: the recurrent step on the slot's
  state, attention over the slot's pages.

The cache protocol ``DecodeEngine`` drives (``models/generation.py``) is the
block of methods under "cache protocol" below; ``TransformerLM`` implements
the same block. **Mixer kinds own their cache leaves**: ``MIXERS`` holds, for
every kind, the leaves a layer of it keeps - paged rows (one pool a layer,
pages shared through the engine's allocator and tables: ``latent``, ``kv``)
or a fixed state a slot (``kda_s``, ``kda_conv``, ``ssm_s``, ``ssm_conv``,
and ``swa_kv``: a window layer's last ``swa_window`` rows, a ring written at
``position % swa_window``, so that what it holds a slot does not grow with
the slot's context; a decode step traced for the TPU reads it where it lies
through the page walk the paged layers take, the ring being the slot's own
pages in order, :func:`ring_attention_backend`), shape and dtype - and its
full and one-step functions.
The protocol's methods walk the layers over that table and name no leaf; each
layer's array is a leaf of the cache of its own, so that a step rewrites it
in place. **A kind may own nothing and read what an earlier part hands on**
(``MixerKind.reads``; the part's ``source`` names the earlier part's
``tag``): ``pages``, that part's paged rows - in a step its pool, the step's
row already written, in a full pass the rows of every position - or ``side``,
that part's side output of the same pass (``MixerKind.gives_side``). Such a
layer adds no leaf: the engine allocates one pool for the layer that owns the
rows and ``page_bytes`` counts it once, whatever reads it (``page_readers``).

Named scopes: the outer names are the fixed vocabulary of
``models/transformer.py`` (``embed``, ``ln``, ``attn_qkv``, ``attn_core``,
``attn_out``, ``mlp``, ``head``, ``kv_write``, ``kv_gather``); inside them
``kda_proj``, ``kda_conv``, ``kda_state``, ``kda_out``, ``mla_proj`` (and
in it ``mla_rope``, the rotation), ``mla_attend``, ``ssm_proj``,
``ssm_conv``, ``ssm_state``, ``ssm_out`` (either state-space kind's),
``gqa_proj`` (and in it ``gqa_rope``, the rotation, and ``attn_gate``, the
output gate's scalars), ``gqa_attend``, ``swa_proj`` (with ``swa_rope`` and
``attn_gate``), ``swa_attend``, ``swa_write`` (the ring's row, under
``kv_write``), ``xattn_proj``, ``xattn_attend``, ``attn_diff`` (the
differential subtraction and its sub-norm, inside a kind's ``*_attend``),
``gmu`` (the memory unit's two products and its gate),
``ffn_dense`` (a dense feed-forward) and, from the expert layer,
``moe_route``, ``moe_experts``, ``moe_shared``, ``moe_combine`` (and in it
``moe_zero``, the identity experts' weighted copy), ``moe_latent``. One log
line a trace, ``layer kinds: ...``, names the layers' parts (what a part hands
on behind ``=``, what it reads behind ``<``), each attention kind's heads,
rotation, gate, form and window, the experts' form and the router; and one
a kind that attends over a cache in a decode step, ``attention backend:
<kind>: <choice>: <why>``, which ``attention_backend`` keeps by kind.
"""
from __future__ import annotations

import dataclasses
import logging
import math
from typing import Any, Callable, Dict, Optional, Tuple

import jax
import jax.numpy as jnp
import numpy as np
from jax import lax

from deeplearning4j_tpu.parallel.moe import (EXPERT_FORMS,
                                             RoutedExpertsConfig,
                                             feed_forward,
                                             routed_experts_ffn)

_HI = lax.Precision.HIGHEST
#: rows of the KDA chunk handled pairwise (exactly); blocks further apart go
#: through a reference point between them
_SUB = 16
#: queries a block of the expanded attentions (their scores are
#: heads x block x T float32)
_QUERY_BLOCK = 512
#: rows a step of the Mamba-1 prefill's scan
_M1_CHUNK = 16


_FFNS = ("dense", "moe")


@dataclasses.dataclass(frozen=True)
class Part:
    """One residual part of a layer. ``kind``: a key of ``MIXERS``, ``dense``
    or ``moe``. ``name``: the key of its parameters in the layer's block.
    ``norm``: the key of its RMSNorm gain there, or None where it reads the
    rows the part before it read (normalised once, by that part's gain).
    ``lands``: ``now``, its result is added to the stream at once, or
    ``end``, behind the layer's last part. ``tag``: the name under which
    LATER parts may read what this one hands on (the side output of its
    kind, where the kind gives one; else its paged rows). ``source``: the
    ``tag`` of the earlier part that a kind which ``reads`` takes it from."""

    kind: str
    name: str
    norm: Optional[str]
    lands: str = "now"
    tag: Optional[str] = None
    source: Optional[str] = None


@dataclasses.dataclass(frozen=True)
class LayerSpec:
    """A layer: a ``mixer`` and a feed-forward ``ffn``, each through a norm
    of its own and added at once (either may be absent), or, for a layer that
    is more than that, its ``parts`` in order."""

    mixer: Optional[str] = None     # a key of ``MIXERS``, or None
    ffn: Optional[str] = None       # "dense" | "moe" | None
    parts: Tuple[Part, ...] = ()
    tag: Optional[str] = None       # the mixer's ``Part.tag``
    source: Optional[str] = None    # the mixer's ``Part.source``

    def __post_init__(self):
        if not self.parts:
            if (self.mixer not in (None, *MIXERS)
                    or self.ffn not in (None, *_FFNS)
                    or (self.mixer is None and self.ffn is None)):
                raise ValueError(f"unknown layer {self}")
            object.__setattr__(self, "parts", tuple(
                Part(kind, name, norm, "now", *given)
                for kind, name, norm, given in (
                    (self.mixer, "mixer", "ln1", (self.tag, self.source)),
                    (self.ffn, "ffn", "ln2", ()))
                if kind is not None))
            return
        parts = tuple(self.parts)
        object.__setattr__(self, "parts", parts)
        names = [p.name for p in parts] + [p.norm for p in parts if p.norm]
        if (self.mixer is not None or self.ffn is not None
                or self.tag is not None or self.source is not None
                or any(p.kind not in (*MIXERS, *_FFNS)
                       or p.lands not in ("now", "end") for p in parts)
                or parts[0].norm is None or len(set(names)) != len(names)):
            raise ValueError(f"unknown layer {self}")


@dataclasses.dataclass(frozen=True)
class Rope:
    """The rotary positions of one kind of grouped-query attention: the
    leading ``dims`` dimensions of every query and key head (None: all of
    them) are rotated in adjacent pairs (2i, 2i + 1) by ``position *
    inv_freq[i]`` and multiplied by ``amplitude``; the rest of a head is
    carried as it is. ``inv_freq[i] = theta^(-2i / dims)``, or, where YaRN's
    four numbers are given (all or none), that frequency blended with
    itself over ``factor`` along YaRN's ramp between the dimensions that
    turn ``beta_fast`` and ``beta_slow`` times in ``original`` positions.
    Static: the same frequencies at every position."""

    theta: float
    dims: Optional[int] = None
    amplitude: float = 1.0
    factor: Optional[float] = None
    original: Optional[int] = None
    beta_fast: Optional[float] = None
    beta_slow: Optional[float] = None

    def __post_init__(self):
        yarn = (self.factor, self.original, self.beta_fast, self.beta_slow)
        if any(v is not None for v in yarn) and None in yarn:
            raise ValueError("a YaRN rotation needs its four numbers: "
                             f"factor, original, beta_fast, beta_slow {yarn}")
        if self.dims is not None and (self.dims <= 0 or self.dims % 2):
            raise ValueError(f"{self.dims} dimensions cannot rotate in pairs")

    def ramp(self, d: int):
        """(low, high): the pairs between which YaRN's ramp rises 0 -> 1."""
        def corr(turns):
            return d * math.log(self.original / (2 * math.pi * turns)) \
                / (2 * math.log(self.theta))
        return (max(math.floor(corr(self.beta_fast)), 0),
                min(math.ceil(corr(self.beta_slow)), d - 1))

    def inv_freq(self, d: int) -> np.ndarray:
        """float32 (d / 2,): the angle a position of pair i."""
        i = np.arange(d // 2, dtype=np.float64)
        f = self.theta ** (-2.0 * i / d)
        if self.factor is not None:
            low, high = self.ramp(d)
            ramp = np.clip((i - low) / max(high - low, 1e-3), 0.0, 1.0)
            f = f * (1.0 - ramp) + f / self.factor * ramp
        return f.astype(np.float32)

    def say(self, head_dim: int) -> str:
        d = self.dims or head_dim
        return ("%s theta %g on %d of %d" % (
            "plain" if self.factor is None else
            "yarn x%g from %d" % (self.factor, self.original),
            self.theta, d, head_dim)
            + ("" if self.amplitude == 1.0 else " x%.4f" % self.amplitude))


@dataclasses.dataclass
class HybridConfig:
    vocab_size: int
    d_model: int
    layers: Tuple[LayerSpec, ...]
    max_len: int                    # longest sequence a cache slot holds
    experts: Optional[RoutedExpertsConfig] = None
    rms_eps: float = 1e-5
    #: every norm of the stream: ``rms`` (a gain) or ``layer`` (LayerNorm:
    #: the mean taken off, a gain ``g`` and a bias ``b``), eps ``rms_eps``
    norm: str = "rms"
    #: the head is the embedding, transposed: the parameters hold no ``head``
    tie_embeddings: bool = False
    #: the first layer that a prefill runs on the prompt's LAST row alone
    #: (None: every layer over every row): it and the layers behind it keep
    #: no cache and hand nothing on, so no other row of theirs is ever read
    last_row_from: Optional[int] = None
    dtype: Any = jnp.bfloat16       # activations
    param_dtype: Any = jnp.bfloat16
    kda_heads: int = 32
    kda_head_dim: int = 128         # d_k = d_v
    kda_conv: int = 4
    kda_gate_rank: int = 128        # width of the two low-rank gates
    kda_chunk: int = 64
    mla_heads: int = 32
    qk_nope_dim: int = 128
    qk_rope_dim: int = 64
    #: whether the model rotates: the base of the rotary positions on the
    #: ``qk_rope_dim`` dimensions of every query head and of the shared key
    #: row (adjacent pairs), or None: they are carried and never rotated
    rope_theta: Optional[float] = None
    v_head_dim: int = 128
    kv_lora_rank: int = 512
    q_lora_rank: Optional[int] = None     # None: no query bottleneck
    #: the bottlenecks' scales, sqrt(d_model / rank) behind each one's norm
    mla_scale_q_lora: bool = False
    mla_scale_kv_lora: bool = False
    ssm_heads: int = 128
    ssm_head_dim: int = 64
    ssm_groups: int = 8             # heads i uses B, C of group i // (H / G)
    ssm_state: int = 128
    ssm_conv: int = 4
    ssm_chunk: int = 128
    #: ``mamba1``: a decay a CHANNEL and state dimension, exp(dt[c] A[c, n])
    m1_inner: int = 5120
    m1_state: int = 16
    m1_conv: int = 4
    m1_dt_rank: int = 160
    gqa_heads: int = 32
    gqa_kv_heads: int = 2           # query head i on kv head i // (Hq / Hkv)
    gqa_head_dim: int = 128
    gqa_rope: Optional[Rope] = None       # None: no positions
    gqa_gated: bool = False         # a sigmoid gate a head on the output
    #: the window kind (``swa``): on the same key/value heads and head size,
    #: its own head count, rotation and gate, and the positions it reads
    swa_heads: int = 32
    swa_rope: Optional[Rope] = None
    swa_gated: bool = False
    swa_window: Optional[int] = None
    #: differential attention (heads in pairs, two softmax maps a pair over
    #: a value twice as wide, their difference normalised) and biases on
    #: the projections, in every grouped-query kind alike
    differential: bool = False
    attn_bias: bool = False
    dense_ff: int = 9216
    expert_ff: int = 1024
    shared_ff: Optional[int] = None       # None: expert_ff
    expert_latent: Optional[int] = None   # None: experts in the width d

    def __post_init__(self):
        self.layers = tuple(self.layers)
        if any(p.kind == "moe" for s in self.layers
               for p in s.parts) and self.experts is None:
            raise ValueError("a layer with routed experts needs `experts`")
        if self.mla_scale_q_lora and not self.q_lora_rank:
            raise ValueError("mla_scale_q_lora scales a query bottleneck: "
                             "give `q_lora_rank`")
        if self.kda_chunk % _SUB:
            raise ValueError(f"kda_chunk must be a multiple of {_SUB}")
        if self.ssm_heads % self.ssm_groups \
                or self.gqa_heads % self.gqa_kv_heads:
            raise ValueError("heads must divide into their groups")
        if any(p.kind == "swa" for s in self.layers for p in s.parts):
            if not self.swa_window or self.swa_window < 1:
                raise ValueError("a window layer needs `swa_window`")
            if self.swa_heads % self.gqa_kv_heads:
                raise ValueError("heads must divide into their groups")
        for rope in (self.gqa_rope, self.swa_rope):
            if rope is not None and (rope.dims or 0) > self.gqa_head_dim:
                raise ValueError(f"{rope.dims} rotated dimensions are more "
                                 f"than a head's {self.gqa_head_dim}")
        if self.norm not in ("rms", "layer"):
            raise ValueError(f"unknown norm {self.norm!r}")
        kinds = {p.kind for s in self.layers for p in s.parts}
        for kind in ("gqa", "swa"):
            heads, _rope, gated, _window = self.attention(kind)
            if self.differential and kind in kinds and (
                    gated or self.gqa_kv_heads % 2
                    or heads % self.gqa_kv_heads):
                raise ValueError("differential attention pairs the heads "
                                 "and the key/value heads, ungated")
            if _rope is not None and (self.differential or self.attn_bias or (
                    kind == "gqa" and "xattn" in kinds)):
                raise ValueError("a rotation beside differential attention, "
                                 "projection biases or a query-only layer "
                                 "is not written")
        self._check_sources()

    def _check_sources(self):
        """What a part reads was handed on by an earlier part whose kind
        gives it; the layers a prefill runs on the last row keep nothing."""
        gave: Dict[str, str] = {}
        for i, spec in enumerate(self.layers):
            for p in spec.parts:
                kind = MIXERS.get(p.kind)
                reads = kind.reads if kind else None
                if (reads is None) != (p.source is None):
                    raise ValueError(f"layer {i}: {p.kind} reads {reads}, "
                                     f"its source is {p.source!r}")
                if reads and gave.get(p.source) != reads:
                    raise ValueError(
                        f"layer {i}: no earlier part hands on {reads} as "
                        f"{p.source!r}")
                if p.tag is not None:
                    given = None if kind is None else (
                        "side" if kind.gives_side else
                        "pages" if kind.keeps_pages(self) else None)
                    if given is None or p.tag in gave:
                        raise ValueError(f"layer {i}: {p.kind} hands on "
                                         f"nothing as {p.tag!r}")
                    gave[p.tag] = given
                if self.last_row_from is not None \
                        and i >= self.last_row_from and (
                            p.tag or (kind and kind.leaves(self))):
                    raise ValueError(
                        f"layer {i} keeps a cache or hands something on: a "
                        "prefill cannot run it on the last row alone")

    @property
    def n_layers(self) -> int:
        return len(self.layers)

    @property
    def latent_dim(self) -> int:
        return self.kv_lora_rank + self.qk_rope_dim

    @property
    def latent_row(self) -> int:
        """Width of a cached latent row: ``latent_dim`` padded with zeros
        to whole 128-lane tiles. For a width that is no multiple of 128 the
        TPU compiler's default layout of the pool puts the ROW axis minor,
        and every step then copies the whole pool in and out of the layout
        its scatter and gather need (compile rehearsals, PR 27)."""
        return -(-self.latent_dim // 128) * 128

    @property
    def ssm_inner(self) -> int:
        return self.ssm_heads * self.ssm_head_dim

    @property
    def ssm_conv_dim(self) -> int:
        """Channels the Mamba-2 convolution runs over: x, B and C."""
        return self.ssm_inner + 2 * self.ssm_groups * self.ssm_state

    @property
    def gqa_kv_row(self) -> int:
        """A cached row of a grouped-query layer: [k heads | v heads]."""
        return 2 * self.gqa_kv_heads * self.gqa_head_dim

    @property
    def m1_proj(self) -> int:
        """What a Mamba-1 layer projects its convolved rows to: the time
        step's bottleneck, B and C."""
        return self.m1_dt_rank + 2 * self.m1_state

    def attention(self, kind: str):
        """(heads, rotation, gated, window) of a grouped-query kind;
        ``xattn`` is the full kind's."""
        if kind in ("gqa", "xattn"):
            return self.gqa_heads, self.gqa_rope, self.gqa_gated, None
        return self.swa_heads, self.swa_rope, self.swa_gated, self.swa_window

    def attention_shape(self, kind: str):
        """(key/value heads, query rows on each, head width) as a kind's
        products see them. Differential attention is grouped-query attention
        on PAIRS: the cached row ``[k heads | v heads]`` read as half as
        many heads twice as wide, a pair's two queries padded with zeros to
        that width (the first in the low half, the second in the high), so
        that ``q1' . [k1 | k2] = q1 . k1`` and the value is ``[v1 | v2]``."""
        heads, g, hd = self.attention(kind)[0], self.gqa_kv_heads, \
            self.gqa_head_dim
        if self.differential:
            return g // 2, heads // (g // 2), 2 * hd
        return g, heads // g, hd


@dataclasses.dataclass(frozen=True)
class CacheLeaf:
    """One array a layer of a mixer kind keeps in the decode cache:
    ``paged`` rows of every position, ``(pages, page_tokens, *shape)``, the
    pages handed out by the engine's allocator; or a fixed state a slot,
    ``(slots, *shape)``."""

    name: str
    paged: bool
    shape: Tuple[int, ...]
    dtype: Any


@dataclasses.dataclass(frozen=True)
class MixerKind:
    """What the model and the cache protocol need of a kind of mixer.
    ``full(model, p, h, valid, last_idx, src, row) -> (y, *entries)`` over
    (B, T, d), the entries in the order of ``leaves``; ``decode(model, p, h,
    held, tables, positions, page_tokens, src) -> (y, *held)`` for one token
    a slot; ``init(model, w, ones, resid, layer) -> params``. ``reads``:
    None, or what a layer of the kind takes from the earlier part its
    ``Part.source`` names and owns nothing of - ``pages``: that part's paged
    rows (``src``: the rows of every position in a full pass, its pool,
    already written, in a step) - ``side``: that part's side output of the
    same pass. ``gives_side``: ``full`` and ``decode`` return a side output
    behind everything else, rows as wide as the kind says, which the walk
    keeps where the part has a ``tag``. ``row``: None, or the one position
    h's single row stands at (a prefill's layers on the last row)."""

    leaves: Callable
    full: Callable
    decode: Callable
    init: Callable
    reads: Optional[str] = None
    gives_side: bool = False

    def keeps_pages(self, config) -> bool:
        return any(leaf.paged for leaf in self.leaves(config))


def _rms(x, g, eps):
    x32 = x.astype(jnp.float32)
    return x32 * lax.rsqrt(jnp.mean(x32 * x32, -1, keepdims=True) + eps) \
        * g.astype(jnp.float32)


def _layer_norm(x, g, b, eps):
    x32 = x.astype(jnp.float32)
    x32 = x32 - jnp.mean(x32, -1, keepdims=True)
    return x32 * lax.rsqrt(jnp.mean(x32 * x32, -1, keepdims=True) + eps) \
        * g.astype(jnp.float32) + b.astype(jnp.float32)


def _mm(x, w):
    return jnp.matmul(x, w, preferred_element_type=jnp.float32)


def _rope(x, positions, theta: float):
    """Rotary positions on the last axis of x (..., r), float32 out: the
    adjacent pair (2i, 2i + 1) is turned by ``positions * theta^(-2i / r)``.
    ``positions`` broadcasts against x's leading axes."""
    r = x.shape[-1]
    lane = jnp.arange(r)
    ang = positions[..., None].astype(jnp.float32) * (
        theta ** (-(lane - lane % 2).astype(jnp.float32) / r))
    x = x.astype(jnp.float32)
    # each lane's partner in its pair, the even lane's negated
    other = jnp.where(lane % 2 == 0, -jnp.roll(x, -1, axis=-1),
                      jnp.roll(x, 1, axis=-1))
    return x * jnp.cos(ang) + other * jnp.sin(ang)


def _rotate(x, positions, rope: Rope):
    """x (..., hd) float32 at ``positions`` (broadcast against x's leading
    axes) -> float32: ``rope``'s leading dimensions of the last axis turned
    in adjacent pairs (:func:`_rope`'s lanes) by its own frequencies, times
    its amplitude; the rest of the axis as it is."""
    hd = x.shape[-1]
    r = rope.dims or hd
    lane = np.arange(r)
    ang = positions[..., None].astype(jnp.float32) \
        * jnp.asarray(rope.inv_freq(r)[lane // 2])
    head, rest = x[..., :r].astype(jnp.float32), x[..., r:]
    other = jnp.where(lane % 2 == 0, -jnp.roll(head, -1, axis=-1),
                      jnp.roll(head, 1, axis=-1))
    head = head * jnp.cos(ang) + other * jnp.sin(ang)
    if rope.amplitude != 1.0:
        head = head * rope.amplitude
    return head if r == hd else jnp.concatenate(
        [head, rest.astype(jnp.float32)], axis=-1)


# ------------------------------------------- the short causal convolution
def _conv_full(pre, taps):
    """Depthwise causal convolution over whole sequences: pre (B, T, C),
    taps (n, C), the last tap on the row itself -> float32 (B, T, C)."""
    w = taps.astype(jnp.float32)
    n, T = w.shape[0], pre.shape[1]
    rows = jnp.pad(pre.astype(jnp.float32), ((0, 0), (n - 1, 0), (0, 0)))
    return sum(w[i] * rows[:, i:i + T] for i in range(n))


def _conv_tail(pre, n: int, last_idx, dtype):
    """The n - 1 rows of ``pre`` the convolution needs before the row after
    ``last_idx`` (zeros where the prompt is shorter): (B, n - 1, C)."""
    at = last_idx + jnp.arange(2 - n, 1)
    return jnp.where((at >= 0)[None, :, None],
                     jnp.take(pre, jnp.maximum(at, 0), axis=1),
                     0).astype(dtype)


def _conv_step(tail, pre, taps):
    """One row: tail (B, n - 1, C) and the new row pre (B, C) -> (the
    convolved row float32 (B, C), the n rows; the next tail is rows[:, 1:])."""
    rows = jnp.concatenate([tail, pre[:, None]], axis=1)
    return jnp.sum(taps.astype(jnp.float32) * rows.astype(jnp.float32),
                   axis=1), rows


# ------------------------------------------------------------------- KDA
def _decayed_gram(x, k, g, inclusive: bool):
    """``out[i, j] = sum_d x[i, d] k[j, d] exp(g[i, d] - g[j, d])`` for
    ``j < i`` (``j <= i`` if ``inclusive``), else 0, within each chunk.
    x, k, g: (..., C, K), g the running sum of the log decays (decreasing).
    Every exponent is a difference that is <= 0: pairs inside a block of
    ``_SUB`` rows are taken exactly, pairs of different blocks through the
    running sum at the start of the row's block."""
    *lead, C, K = x.shape
    n, c = C // _SUB, _SUB
    xb, kb, gb = (a.reshape(*lead, n, c, K) for a in (x, k, g))
    # reference of block a: g just before its first row (0 for the first)
    ref = jnp.concatenate([jnp.zeros_like(gb[..., :1, 0, :]),
                           gb[..., :-1, -1, :]], axis=-2)       # (.., n, K)
    x_in = xb * jnp.exp(gb - ref[..., None, :])                 # (.., n,c,K)
    before = (jnp.arange(C)[None, :] < (jnp.arange(n) * c)[:, None])
    diff = ref[..., :, None, :] - g[..., None, :, :]            # (.., n,C,K)
    k_out = k[..., None, :, :] * jnp.exp(
        jnp.where(before[..., None], diff, -jnp.inf))
    off = jnp.einsum("...aik,...ajk->...aij", x_in, k_out, precision=_HI)
    ii, jj = jnp.arange(c)[:, None], jnp.arange(c)[None, :]
    keep = (jj <= ii) if inclusive else (jj < ii)
    d = gb[..., :, None, :] - gb[..., None, :, :]               # (..,n,c,c,K)
    d = jnp.where(keep[..., None], d, -jnp.inf)
    diag = jnp.sum(xb[..., :, None, :] * kb[..., None, :, :] * jnp.exp(d),
                   axis=-1)                                     # (.., n,c,c)
    diag = diag[..., :, :, None, :] * jnp.eye(n, dtype=diag.dtype)[
        :, None, :, None]                                       # (..,n,c,n,c)
    return off.reshape(*lead, C, C) + diag.reshape(*lead, C, C)


def kda_chunked(q, k, v, log_a, beta, s0, chunk: int):
    """The delta rule with channel-wise decay over whole sequences, chunk by
    chunk. q, k, log_a (B, T, H, K), v (B, T, H, V), beta (B, T, H), s0
    (B, H, K, V), all float32; T a multiple of ``chunk``. Returns
    (o (B, T, H, V), the state after the last row).

    Within a chunk, with ``g`` the running sum of ``log_a``:
    ``U = (I + diag(beta) L)^-1 diag(beta) (V - (K e^g) S0)`` where
    ``L = strict_gram(K, K)``; ``O = (Q e^g) S0 + incl_gram(Q, K) U``;
    ``S_end = e^{g_C} S0 + (K e^{g_C - g})^T U``."""
    B, T, H, K = q.shape
    V = v.shape[-1]
    N = T // chunk

    def chunks(a):          # (B, T, H, X) -> (N, B, H, C, X)
        return a.reshape(B, N, chunk, H, -1).transpose(1, 0, 3, 2, 4)

    qc, kc, vc, ac = chunks(q), chunks(k), chunks(v), chunks(log_a)
    bc = chunks(beta[..., None])                                # (N,B,H,C,1)
    g = jnp.cumsum(ac, axis=-2)
    L = _decayed_gram(kc, kc, g, inclusive=False)
    M = _decayed_gram(qc, kc, g, inclusive=True)
    A = jnp.eye(chunk, dtype=jnp.float32) + bc * L
    k_in = kc * jnp.exp(g)
    rhs = jnp.concatenate([bc * vc, bc * k_in], axis=-1)
    sol = lax.linalg.triangular_solve(A, rhs, left_side=True, lower=True,
                                      unit_diagonal=True)
    tv, w = sol[..., :V], sol[..., V:]
    q_in = qc * jnp.exp(g)
    g_end = g[..., -1:, :]                                      # (N,B,H,1,K)
    k_end = kc * jnp.exp(g_end - g)

    def step(s, xs):
        tv_n, w_n, q_n, m_n, ke_n, ge_n = xs
        u = tv_n - jnp.einsum("bhck,bhkv->bhcv", w_n, s, precision=_HI)
        o = (jnp.einsum("bhck,bhkv->bhcv", q_n, s, precision=_HI)
             + jnp.einsum("bhcj,bhjv->bhcv", m_n, u, precision=_HI))
        s = (jnp.exp(ge_n).swapaxes(-1, -2) * s
             + jnp.einsum("bhck,bhcv->bhkv", ke_n, u, precision=_HI))
        return s, o

    s_end, o = lax.scan(step, s0, (tv, w, q_in, M, k_end, g_end))
    return o.transpose(1, 0, 3, 2, 4).reshape(B, T, H, V), s_end


def kda_step(s, q, k, v, log_a, beta):
    """One row of the recurrence for every slot: s (B, H, K, V) float32;
    q, k, log_a (B, H, K); v (B, H, V); beta (B, H).
    ``S <- (I - beta k k^T) Diag(a) S + beta k v^T``, ``o = S^T q``."""
    s = jnp.exp(log_a)[..., None] * s
    ks = jnp.sum(k[..., None] * s, axis=-2)
    u = beta[..., None] * (v - ks)
    s = s + k[..., None] * u[..., None, :]
    return s, jnp.sum(q[..., None] * s, axis=-2)


# --------------------------------------------------------------- Mamba-2
def ssd_chunked(x, dt, log_a, b, c, s0, chunk: int):
    """The state-space recurrence with a scalar decay a head over whole
    sequences, chunk by chunk. x (B, T, H, P); dt, log_a (B, T, H) with
    ``log_a = -exp(A) dt <= 0``; b, c (B, T, G, N), head ``i`` on group
    ``i // (H / G)``; s0 (B, H, P, N); all float32, T a multiple of
    ``chunk``. ``S_t = e^{log_a_t} S_{t-1} + dt_t x_t b_t^T``, ``y_t = S_t
    c_t``. Returns (y (B, T, H, P), the state after the last row).

    Within a chunk, with ``g`` the running sum of ``log_a``:
    ``Y = e^g (S0 C^T)^T + (L * (C B^T)) (dt X)`` where ``L[i, j] =
    e^{g_i - g_j}`` for ``j <= i``, else 0; ``S_end = e^{g_C} S0 +
    (dt X e^{g_C - g})^T B``. Every exponent is a difference <= 0."""
    B, T, H, P = x.shape
    G, N = b.shape[-2:]
    K, n = H // G, T // chunk

    def chunks(a, *tail):       # (B, T, ...) -> (n, B, chunk, ...)
        return a.reshape(B, n, chunk, *tail).swapaxes(0, 1)

    xc = chunks(x * dt[..., None], G, K, P).transpose(0, 1, 3, 4, 2, 5)
    g = jnp.cumsum(chunks(log_a, G, K).transpose(0, 1, 3, 4, 2), axis=-1)
    bc = chunks(b, G, N).swapaxes(2, 3)                     # (n,B,G,C,N)
    cc = chunks(c, G, N).swapaxes(2, 3)
    cb = jnp.einsum("nbgis,nbgjs->nbgij", cc, bc, precision=_HI)
    ii, jj = jnp.arange(chunk)[:, None], jnp.arange(chunk)[None, :]
    decay = jnp.exp(jnp.where(jj <= ii, g[..., :, None] - g[..., None, :],
                              -jnp.inf))                    # (n,B,G,K,C,C)
    y = jnp.einsum("nbgkij,nbgkjp->nbgkip", cb[:, :, :, None] * decay, xc,
                   precision=_HI)
    g_end = g[..., -1:]                                     # (n,B,G,K,1)
    ds = jnp.einsum("nbgkcp,nbgcs->nbgkps",
                    xc * jnp.exp(g_end - g)[..., None], bc, precision=_HI)

    def step(s, xs):            # s (B, G, K, P, N)
        ds_n, ge_n, c_n, eg_n = xs
        y_n = jnp.einsum("bgkps,bgcs->bgkcp", s, c_n,
                         precision=_HI) * eg_n[..., None]
        return jnp.exp(ge_n)[..., None] * s + ds_n, y_n

    s_end, y0 = lax.scan(step, s0.reshape(B, G, K, P, N),
                         (ds, g_end, cc, jnp.exp(g)))
    y = (y + y0).transpose(1, 0, 4, 2, 3, 5).reshape(B, T, H, P)
    return y, s_end.reshape(B, H, P, N)


def ssd_step(s, x, dt, log_a, b, c):
    """One row of the recurrence for every slot: s (B, H, P, N) float32;
    x (B, H, P); dt, log_a (B, H); b, c (B, G, N).
    ``S <- e^{log_a} S + dt x b^T``, ``y = S c``."""
    B, H, P, N = s.shape
    G = b.shape[1]
    s = s.reshape(B, G, H // G, P, N)
    s = jnp.exp(log_a).reshape(B, G, -1, 1, 1) * s \
        + (dt[..., None] * x).reshape(B, G, -1, P, 1) \
        * b[:, :, None, None, :]
    y = jnp.sum(s * c[:, :, None, None, :], axis=-1)
    return s.reshape(B, H, P, N), y.reshape(B, H, P)


# --------------------------------------------------------------- Mamba-1
def mamba1_step(s, x, dt, a, b, c):
    """One row of the recurrence with a decay a channel and state dimension,
    for every slot: s (B, N, C) float32 (the channels minor: 5,120 of them
    fill whole tiles of 128 lanes, 16 state dimensions would fill an eighth
    of one); x, dt (B, C); a (N, C) < 0; b, c (B, N).
    ``S <- exp(dt a) S + dt x b^T``, ``y = c S``; every factor in (0, 1]."""
    s = jnp.exp(dt[:, None, :] * a) * s \
        + (dt * x)[:, None, :] * b[:, :, None]
    return s, jnp.sum(s * c[:, :, None], axis=1)


def mamba1_chunked(x, dt, a, b, c, s0, chunk: int):
    """That recurrence over whole sequences: x, dt (B, T, C); a (N, C);
    b, c (B, T, N); s0 (B, N, C); all float32, T a multiple of ``chunk``.
    Returns (y (B, T, C), the state after the last row). The decay differs
    in every channel AND state dimension, so a chunk is no matrix product
    (``ssd_chunked``'s is): the rows of a chunk are taken one after another
    inside ONE step of a scan over the chunks, :func:`mamba1_step` spelled
    ``chunk`` times, so that the compiler sees a chain of element-wise
    updates of one (N, C) state and not T / chunk x chunk tiny programs.
    Every factor is exp(dt a) in (0, 1]: nothing is divided by a running
    product, so no decay can overflow or underflow the result."""
    B, T, C = x.shape
    n = T // chunk

    def chunks(z):          # (B, T, X) -> (n, B, chunk, X)
        return z.reshape(B, n, chunk, -1).swapaxes(0, 1)

    def step(s, xs):
        x_n, dt_n, b_n, c_n = xs
        ys = []
        for i in range(chunk):
            s, y = mamba1_step(s, x_n[:, i], dt_n[:, i], a, b_n[:, i],
                               c_n[:, i])
            ys.append(y)
        return s, jnp.stack(ys, axis=1)

    s_end, y = lax.scan(step, s0, (chunks(x), chunks(dt), chunks(b),
                                   chunks(c)))
    return y.swapaxes(0, 1).reshape(B, T, C), s_end


def _slot_page(tables, positions, page_tokens: int, n_pages: int):
    """The page each slot's row at ``positions`` goes to. A position past
    the last logical page (a retired slot) goes to the trash page, the
    pool's last, which no table row owns."""
    return jnp.where(
        positions < tables.shape[1] * page_tokens,
        tables[jnp.arange(tables.shape[0]),
               jnp.minimum(positions // page_tokens, tables.shape[1] - 1)],
        n_pages - 1)


def _ring_live(window: int, positions):
    """(B, window): the rows of a slot's ring that hold a position of its
    context once the row of ``positions`` is written. Row j holds the latest
    position p <= positions with p % window == j: every row once the slot is
    as old as the window, rows 0 .. positions of a younger one."""
    return jnp.arange(window)[None, :] <= positions[:, None]


def _on_tpu() -> bool:
    """Whether the program is traced for the TPU."""
    return jax.default_backend() == "tpu"


#: the inner scopes of the two grouped-query kinds, ``<kind>_<what>``
_ATTN_SCOPES = ("proj", "rope", "attend")


def _scope(kind: str, what: str) -> str:
    assert what in _ATTN_SCOPES, what
    return f"{kind}_{what}"


def _pair_queries(q, groups: int):
    """q (..., H, hd), heads 2j and 2j + 1 a pair -> (..., groups, 2 H /
    groups, 2 hd): ``[q1 | 0]`` and ``[0 | q2]`` of every pair, the pairs
    of a key/value pair side by side."""
    *lead, H, hd = q.shape
    q = q.reshape(*lead, H // 2, 2, 1, hd) \
        * jnp.eye(2, dtype=q.dtype)[:, :, None]
    return q.reshape(*lead, groups, H // groups, 2 * hd)


def latent_attention_backend(heads: int, row: int, out_width: int,
                             page_tokens: int, pages: int,
                             itemsize: int) -> Tuple[str, str]:
    """(``paged-latent`` | ``gather``, why) for the absorbed latent attention
    of a decode step: ``heads`` queries a slot against ``pages`` pages of
    ``page_tokens`` rows ``row`` wide, of which the output reads
    ``out_width``. The Pallas kernel (``kernels/paged_latent_attention.py``)
    where the program is traced for the TPU, both widths are whole tiles of
    128 lanes, a page is whole tiles of 8 rows and a visit's pages fit the
    kernel's VMEM, which a page of thousands of rows does not; the gathered
    window everywhere else. Consulted at trace time only
    (``moe.expert_backend``'s manner); the kernel's module is loaded by the
    first trace that may take it."""
    if not _on_tpu():
        return "gather", f"on {jax.default_backend()}"
    if row % 128 or out_width % 128:
        return "gather", (f"a row of {row}, read {out_width} wide, is not "
                          "whole tiles of 128 lanes")
    if page_tokens % 8:
        return "gather", (f"a page of {page_tokens} rows is not whole tiles "
                          "of 8 rows")
    from deeplearning4j_tpu.kernels import paged_latent_attention as kernel
    if not kernel.fits_vmem(heads, row, page_tokens, pages, itemsize):
        n = kernel.visit_pages(page_tokens, row, itemsize, pages)
        return "gather", (f"a visit of {n * page_tokens} rows of {row} is "
                          "more than the kernel's VMEM")
    return "paged-latent", (f"live pages of {page_tokens} rows of {row} read "
                            "where they lie")


#: the gathered view of every slot's whole window that a grouped-query layer
#: of a decode step may still make; a larger one is never made on the TPU
#: (at 64 slots of 7,168 rows of 4 KB it is 1.9 GB a layer a step, which the
#: chip has not got beside a deployment's cache; PERF.md, PR 43)
GATHER_VIEW_BYTES = 1 << 30


def _grouped_visit_too_large(heads: int, kv_heads: int, row: int,
                             page_tokens: int, pages: int,
                             itemsize: int) -> Optional[str]:
    """Why a visit of the grouped-query page walk does not fit the kernel's
    VMEM, None where it does; loads the kernel's module."""
    from deeplearning4j_tpu.kernels import paged_latent_attention as kernel
    rows_q = kernel.grouped_query_rows(heads // kv_heads, itemsize) * kv_heads
    visit = kernel.GROUPED_VISIT_BYTES
    if kernel.fits_vmem(rows_q, row, page_tokens, pages, itemsize, visit):
        return None
    n = kernel.visit_pages(page_tokens, row, itemsize, pages, visit)
    return (f"a visit of {n * page_tokens} rows of {row} is more than the "
            "kernel's VMEM")


def grouped_attention_backend(slots: int, heads: int, kv_heads: int,
                              head_dim: int, page_tokens: int, pages: int,
                              itemsize: int) -> Tuple[str, str]:
    """(``paged-grouped`` | ``gather``, why) for the grouped-query attention
    of a decode step: ``heads`` queries a slot on ``kv_heads`` key/value
    heads against ``pages`` pages of ``page_tokens`` rows ``[k heads | v
    heads]``. The Pallas kernel that walks a slot's live pages where the
    program is traced for the TPU, a head is whole tiles of 128 lanes, a
    page whole tiles of 8 rows, a visit fits the kernel's VMEM, and the
    gathered view of all the windows would be more than
    ``GATHER_VIEW_BYTES``; the gathered window everywhere else. Consulted at
    trace time only, as :func:`latent_attention_backend` is."""
    if not _on_tpu():
        return "gather", f"on {jax.default_backend()}"
    row = 2 * kv_heads * head_dim
    if head_dim % 128:
        return "gather", (f"a head of {head_dim} is not whole tiles of 128 "
                          "lanes")
    if page_tokens % 8:
        return "gather", (f"a page of {page_tokens} rows is not whole tiles "
                          "of 8 rows")
    view = slots * pages * page_tokens * row * itemsize
    if view <= GATHER_VIEW_BYTES:
        return "gather", (f"the view of every slot's window is "
                          f"{view / 2**20:.0f} MiB")
    too_large = _grouped_visit_too_large(heads, kv_heads, row, page_tokens,
                                         pages, itemsize)
    if too_large:
        return "gather", too_large
    return "paged-grouped", (f"live pages of {page_tokens} rows of {row} "
                             "read where they lie")


#: rows of a ring that the page walk takes as one page: pages of 64, 128 and
#: 256 rows read alike on the chip (PERF.md section 6, PR 47), and a slot
#: younger than the window fetches whole pages
RING_PAGE_ROWS = 64


def _ring_page(window: int) -> int:
    """Rows a page of a ring of ``window`` rows: a shorter ring is one."""
    return min(RING_PAGE_ROWS, window)


def ring_attention_backend(heads: int, kv_heads: int, head_dim: int,
                           window: int, itemsize: int) -> Tuple[str, str]:
    """(``paged-grouped`` | ``xla``, why) for the window kind's attention of
    a decode step: ``heads`` queries a slot on ``kv_heads`` key/value heads
    against the slot's ring of ``window`` rows ``[k heads | v heads]``. The
    ring of every slot, reshaped, IS a pool whose slot b owns pages ``b n ..
    (b + 1) n - 1`` of ``RING_PAGE_ROWS`` rows in order, so the kernel that
    walks a full layer's pages reads it where it lies, where the program is
    traced for the TPU, a head is whole tiles of 128 lanes, the window whole
    pages of whole 8-row tiles and a visit fits the kernel's VMEM; the two
    einsums everywhere else. Nothing is gathered in either spelling, so no
    view's size is asked. Consulted at trace time only, as
    :func:`latent_attention_backend` is."""
    if not _on_tpu():
        return "xla", f"on {jax.default_backend()}"
    row = 2 * kv_heads * head_dim
    if head_dim % 128:
        return "xla", f"a head of {head_dim} is not whole tiles of 128 lanes"
    page = _ring_page(window)
    if page % 8 or window % page:
        return "xla", (f"a ring of {window} rows is not whole pages of "
                       f"{page} rows in whole tiles of 8")
    too_large = _grouped_visit_too_large(heads, kv_heads, row, page,
                                         window // page, itemsize)
    if too_large:
        return "xla", too_large
    return "paged-grouped", (f"live pages of {page} rows of {row} of a ring "
                             f"of {window} read where they lie")


class HybridLM:
    """See the module doc."""

    # ---- cache protocol: what the decode engine may ask for
    cache_features = frozenset()     # no dense cache, int8 pages or draft
    max_positions = None             # no position table bounds the cache
    prefill_all_logits = False       # the prompt's last token's (B, 1, V)

    def __init__(self, config: HybridConfig, mesh=None):
        if mesh is not None:
            raise ValueError("HybridLM runs on one chip: serving across "
                             "chips is not written yet")
        self.config = config
        self.mesh = None
        c = config
        self.moe_layers = [i for i, s in enumerate(c.layers)
                           if any(p.kind == "moe" for p in s.parts)]
        #: the counts a decode step returns behind its tokens, summed over
        #: the expert layers (``routed_experts_ffn``'s ``stats``)
        self.step_stats = ("experts_touched", "pairs_held", "pairs_routed",
                           "expert_visits") + (
            ("pairs_zero",) if c.experts and c.experts.identity else ())
        #: per layer and part, a mixer's rank among the mixers of its kind:
        #: where its arrays stand in the lists of its kind's leaves
        seen: Dict[str, int] = {}
        self._rank = []
        for s in c.layers:
            self._rank.append([])
            for p in s.parts:
                self._rank[-1].append(seen.get(p.kind, 0))
                seen[p.kind] = self._rank[-1][-1] + 1
        #: (leaf, mixers that own it) over the kinds present, the paged
        #: leaves first
        leaves = [(leaf, n) for kind, n in seen.items() if kind in MIXERS
                  for leaf in MIXERS[kind].leaves(c)]
        self.cache_leaves = sorted(leaves, key=lambda ln: not ln[0].paged)
        #: parts that read paged rows a step: those that own a paged leaf
        #: and those that read another's
        self.page_readers = sum(
            1 for s in c.layers for p in s.parts if p.kind in MIXERS
            and (MIXERS[p.kind].reads == "pages"
                 or MIXERS[p.kind].keeps_pages(c)))
        self._said: Dict[str, Any] = {}
        #: kind -> (choice, why) that the last trace of that kind's attention
        #: of a decode step took (:func:`latent_attention_backend`,
        #: :func:`grouped_attention_backend`,
        #: :func:`ring_attention_backend`), empty before any
        self.attention_backend: Dict[str, Tuple[str, str]] = {}
        #: positions a window layer reads and keeps a slot, None without one
        self.cache_window = c.swa_window if "swa" in seen else None

    # ------------------------------------------------------------ params
    def init_params(self, key) -> Dict:
        """The program's own initialiser: N(0, 0.02) matrices, the residual
        projections scaled by 1/sqrt(2 L), gains 1, decays of about 0.9."""
        c = self.config
        keys = iter(jax.random.split(key, 8 + 24 * c.n_layers))
        resid = 0.02 / math.sqrt(2 * c.n_layers)
        d = c.d_model

        def w(shape, std=0.02):
            return (std * jax.random.normal(next(keys), shape, jnp.float32)
                    ).astype(c.param_dtype)

        def ones(n):
            return jnp.ones((n,), c.param_dtype)

        def norm():
            return ones(d) if c.norm == "rms" else {
                "g": ones(d), "b": jnp.zeros((d,), c.param_dtype)}

        def ffn(width, lead=(), d_in=d, form="swiglu"):
            return {EXPERT_FORMS[form]: w(lead + (
                d_in, (2 if form == "swiglu" else 1) * width)),
                "w_down": w(lead + (width, d_in), resid)}

        def experts():
            e = c.experts
            wide = c.expert_latent or d
            p = {"w_router": w((d, e.router_width)),
                 "b_select": jnp.zeros((e.router_width,), jnp.float32),
                 **ffn(c.expert_ff, (e.held[1],), wide, e.form)}
            if e.shared:
                p["shared"] = ffn(c.shared_ff or c.expert_ff, form=e.form)
            if c.expert_latent:
                p.update(w_latent_in=w((d, wide)),
                         w_latent_out=w((wide, d), resid))
            return p

        blocks = []
        for layer, spec in enumerate(c.layers):
            blk = {}
            for part in spec.parts:
                if part.norm is not None:
                    blk[part.norm] = norm()
                if part.kind in MIXERS:
                    blk[part.name] = MIXERS[part.kind].init(
                        self, w, ones, resid, layer)
                else:
                    blk[part.name] = ffn(c.dense_ff) \
                        if part.kind == "dense" else experts()
            blocks.append(blk)
        return {"tok_emb": w((c.vocab_size, d)),
                **({} if c.tie_embeddings else {"head": w((d, c.vocab_size))}),
                "ln_f": norm(), "blocks": blocks}

    def _init_kda(self, w, ones, resid, _layer=None):
        c = self.config
        H, K, d, r = c.kda_heads, c.kda_head_dim, c.d_model, c.kda_gate_rank
        return {
            "w_qkv": w((d, 3 * H * K)), "conv": w((c.kda_conv,
                                                   3 * H * K), 0.3),
            "w_f1": w((d, r)), "w_f2": w((r, H * K)),
            "b_dt": jnp.full((H * K,), -2.0, jnp.float32),
            "a_log": jnp.zeros((H,), jnp.float32),
            "w_beta": w((d, H)), "w_g1": w((d, r)),
            "w_g2": w((r, H * K)), "b_g2": jnp.zeros((H * K,),
                                                     c.param_dtype),
            "o_norm": ones(K), "w_o": w((H * K, d), resid)}

    def _init_mla(self, w, ones, resid, _layer=None):
        c = self.config
        hm, d, rq = c.mla_heads, c.d_model, c.q_lora_rank
        hq = hm * (c.qk_nope_dim + c.qk_rope_dim)
        return {
            **({"w_qa": w((d, rq)), "q_norm": ones(rq),
                "w_qb": w((rq, hq), rq ** -0.5)} if rq
               else {"w_q": w((d, hq))}),
            "w_kva": w((d, c.latent_dim)),
            "kv_norm": ones(c.kv_lora_rank),
            "w_kvb": w((c.kv_lora_rank,
                        hm * (c.qk_nope_dim + c.v_head_dim))),
            "w_o": w((hm * c.v_head_dim, d), resid)}

    def _init_mamba2(self, w, ones, resid, _layer=None):
        c = self.config
        H, d, di = c.ssm_heads, c.d_model, c.ssm_inner
        return {
            "w_in": w((d, di + c.ssm_conv_dim + H)),     # [z | xBC | dt]
            "conv": w((c.ssm_conv, c.ssm_conv_dim), 0.3),
            "b_conv": jnp.zeros((c.ssm_conv_dim,), c.param_dtype),
            "a_log": jnp.zeros((H,), jnp.float32),
            "d_skip": jnp.ones((H,), jnp.float32),
            "dt_bias": jnp.full((H,), -2.0, jnp.float32),
            "norm": ones(di), "w_out": w((di, d), resid)}

    def _init_gqa(self, w, ones, resid, layer=0, kind="gqa"):
        c = self.config
        heads, _rope, gated, _window = c.attention(kind)
        differential, bias = c.differential, c.attn_bias
        d, hd, hq = c.d_model, c.gqa_head_dim, heads * c.gqa_head_dim

        def zeros(n):
            return jnp.zeros((n,), c.param_dtype)

        return {"w_q": w((d, hq)),
                **({} if kind == "xattn" else
                   {"w_kv": w((d, c.gqa_kv_row))}),           # [k | v]
                **({"b_q": zeros(hq), "b_o": zeros(d)} if bias else {}),
                **({"b_kv": zeros(c.gqa_kv_row)}
                   if bias and kind != "xattn" else {}),
                **({"w_gate": w((d, heads))} if gated else {}),
                # a pair's weight on its second map: exp(lq1 . lk1) -
                # exp(lq2 . lk2) + lambda_init, the last set by the layer's
                # depth and never trained
                **({**{n: w((hd,), 0.1).astype(jnp.float32) for n in (
                    "lambda_q1", "lambda_k1", "lambda_q2", "lambda_k2")},
                    "lambda_init": jnp.float32(
                        0.8 - 0.6 * math.exp(-0.3 * layer)),
                    "sub_norm": ones(2 * hd)} if differential else {}),
                "w_o": w((hq, d), resid)}

    def _init_mamba1(self, w, ones, resid, _layer=None):
        c = self.config
        d, C, N, r = c.d_model, c.m1_inner, c.m1_state, c.m1_dt_rank
        return {
            "w_in": w((d, 2 * C)),                       # [x | z]
            "conv": w((c.m1_conv, C), 0.3),
            "b_conv": jnp.zeros((C,), c.param_dtype),
            "w_x": w((C, c.m1_proj)),                    # [dt's r | B | C]
            "w_dt": w((r, C), r ** -0.5),
            "b_dt": jnp.full((C,), -2.0, jnp.float32),
            # (N, C): as the state lies, the channels minor
            "a_log": jnp.log(jnp.broadcast_to(
                jnp.arange(1, N + 1, dtype=jnp.float32)[:, None], (N, C))),
            "d_skip": jnp.ones((C,), jnp.float32),
            "w_out": w((C, d), resid)}

    def _init_gmu(self, w, ones, resid, _layer=None):
        c = self.config
        return {"w_in": w((c.d_model, c.m1_inner)),
                "w_out": w((c.m1_inner, c.d_model), resid)}

    # ------------------------------------------------------------ pieces
    # The residual stream and the norms' outputs are float32 (a few MB);
    # what a matmul takes is cast to ``dtype`` where it is taken.
    def _ln(self, g, x):
        with jax.named_scope("ln"):
            if self.config.norm == "layer":
                return _layer_norm(x, g["g"], g["b"], self.config.rms_eps)
            return _rms(x, g, self.config.rms_eps)

    def _embed(self, params, tokens):
        with jax.named_scope("embed"):
            return jnp.take(params["tok_emb"], tokens, axis=0).astype(
                jnp.float32)

    def _head(self, params, x):
        x = self._ln(params["ln_f"], x).astype(self.config.dtype)
        with jax.named_scope("head"):
            if self.config.tie_embeddings:
                return jnp.einsum("...d,vd->...v", x, params["tok_emb"],
                                  preferred_element_type=jnp.float32)
            return _mm(x, params["head"])

    def _ffn(self, p, kind, h32, token_mask):
        """A feed-forward of ``kind`` (``dense`` or ``moe``) with parameters
        ``p``: h32 (..., d) float32 -> (y, stats or None). The router scores
        the float32 rows; the experts take them in ``dtype``."""
        h = h32.astype(self.config.dtype)
        with jax.named_scope("mlp"):
            if kind == "dense":
                with jax.named_scope("ffn_dense"):
                    return feed_forward(h, p).astype(h.dtype), None
            flat = h.reshape(-1, h.shape[-1])
            mask = None if token_mask is None else token_mask.reshape(-1)
            y, stats = routed_experts_ffn(
                p, flat, self.config.experts, mask,
                x_route=h32.reshape(flat.shape))
            return y.reshape(h.shape), stats

    def _kda_project(self, p, h):
        """h (..., d) -> the rows the convolution takes (..., 3 H K), and
        log a (..., H, K), beta (..., H), the output gate (..., H, K)."""
        c = self.config
        H, K = c.kda_heads, c.kda_head_dim
        with jax.named_scope("kda_proj"):
            pre = _mm(h, p["w_qkv"]).astype(c.dtype)
            f = _mm(_mm(h, p["w_f1"]).astype(c.dtype), p["w_f2"])
            log_a = -jnp.exp(p["a_log"])[:, None] * jax.nn.softplus(
                (f + p["b_dt"]).reshape(*f.shape[:-1], H, K))
            beta = jax.nn.sigmoid(_mm(h, p["w_beta"]))
            gate = jax.nn.sigmoid(
                _mm(_mm(h, p["w_g1"]).astype(c.dtype), p["w_g2"])
                + p["b_g2"].astype(jnp.float32)
            ).reshape(*f.shape[:-1], H, K)
        return pre, log_a, beta, gate

    def _kda_qkv(self, conved):
        """SiLU, split, L2-normalise q and k per head, scale q."""
        c = self.config
        H, K = c.kda_heads, c.kda_head_dim
        x = jax.nn.silu(conved).reshape(*conved.shape[:-1], 3, H, K)
        q, k, v = x[..., 0, :, :], x[..., 1, :, :], x[..., 2, :, :]

        def unit(a):
            return a * lax.rsqrt(jnp.sum(a * a, -1, keepdims=True) + 1e-6)

        return unit(q) * (K ** -0.5), unit(k), v

    def _kda_out(self, p, o, gate):
        c = self.config
        with jax.named_scope("kda_out"):
            y = _rms(o, p["o_norm"], c.rms_eps) * gate
            y = y.reshape(*y.shape[:-2], -1).astype(c.dtype)
            return _mm(y, p["w_o"]).astype(c.dtype)

    def _kda_full(self, p, h, valid, last_idx):
        """h (B, T, d); rows where ``valid`` is False are identity updates.
        Returns (y, state at the last valid row, the 3 rows the convolution
        would need before the next one)."""
        c = self.config
        B, T, _ = h.shape
        with jax.named_scope("attn_qkv"):
            pre, log_a, beta, gate = self._kda_project(p, h)
            with jax.named_scope("kda_conv"):
                q, k, v = self._kda_qkv(_conv_full(pre, p["conv"]))
                tail = _conv_tail(pre, c.kda_conv, last_idx, c.dtype)
        with jax.named_scope("attn_core"), jax.named_scope("kda_state"):
            log_a = jnp.where(valid[None, :, None, None], log_a, 0.0)
            beta = jnp.where(valid[None, :, None], beta, 0.0)
            pad = -T % c.kda_chunk
            if pad:
                q, k, v, log_a, beta = (
                    jnp.pad(a, ((0, 0), (0, pad)) + ((0, 0),) * (a.ndim - 2))
                    for a in (q, k, v, log_a, beta))
            s0 = jnp.zeros((B, c.kda_heads, c.kda_head_dim, c.kda_head_dim),
                           jnp.float32)
            o, s = kda_chunked(q, k, v, log_a, beta, s0, c.kda_chunk)
            o = o[:, :T]
        with jax.named_scope("attn_out"):
            return self._kda_out(p, o, gate), s, tail

    def _kda_decode(self, p, h, s, tail):
        """h (B, d), s (B, H, K, V), tail (B, 3, 3 H K)."""
        c = self.config
        with jax.named_scope("attn_qkv"):
            pre, log_a, beta, gate = self._kda_project(p, h)
            with jax.named_scope("kda_conv"):
                conved, rows = _conv_step(tail, pre, p["conv"])
                q, k, v = self._kda_qkv(conved)
                tail = rows[:, 1:]
        with jax.named_scope("attn_core"), jax.named_scope("kda_state"):
            s, o = kda_step(s, q, k, v, log_a, beta)
        with jax.named_scope("attn_out"):
            return self._kda_out(p, o, gate), s, tail

    def _mla_project(self, p, h, positions=None):
        """h (..., d) at ``positions`` (broadcast against h's leading axes;
        None: whole sequences, 0 .. T - 1 along the axis before the last)
        -> q_nope (..., H, n), q_rope (..., H, r) and the row the cache
        keeps: [rms(c), k_rope, zeros] (..., latent_row). Where the model
        rotates, q_rope and k_rope are rotated here, the key row once, before
        it is kept. Where the queries have a bottleneck, ``q = rms(h W_qa)
        W_qb``; each bottleneck's scale acts behind its norm."""
        c = self.config
        with jax.named_scope("mla_proj"):
            if c.q_lora_rank:
                cq = _rms(_mm(h, p["w_qa"]), p["q_norm"], c.rms_eps)
                if c.mla_scale_q_lora:
                    cq = cq * math.sqrt(c.d_model / c.q_lora_rank)
                q = _mm(cq.astype(c.dtype), p["w_qb"])
            else:
                q = _mm(h, p["w_q"])
            q = q.astype(c.dtype).reshape(
                *h.shape[:-1], c.mla_heads, c.qk_nope_dim + c.qk_rope_dim)
            kva = _mm(h, p["w_kva"])
            lat = _rms(kva[..., :c.kv_lora_rank], p["kv_norm"], c.rms_eps)
            if c.mla_scale_kv_lora:
                lat = lat * math.sqrt(c.d_model / c.kv_lora_rank)
            k_r = kva[..., c.kv_lora_rank:]
            if c.rope_theta:
                if positions is None:
                    positions = jnp.arange(h.shape[-2])
                with jax.named_scope("mla_rope"):
                    k_r = _rope(k_r, positions, c.rope_theta)
            row = jnp.concatenate([lat, k_r], axis=-1).astype(c.dtype)
            row = jnp.pad(row, [(0, 0)] * (row.ndim - 1)
                          + [(0, c.latent_row - c.latent_dim)])
            q_n, q_r = q[..., :c.qk_nope_dim], q[..., c.qk_nope_dim:]
            if c.rope_theta:
                with jax.named_scope("mla_rope"):
                    q_r = _rope(q_r, positions[..., None],
                                c.rope_theta).astype(c.dtype)
        return q_n, q_r, row

    def _mla_kvb(self, p):
        c = self.config
        kvb = p["w_kvb"].reshape(c.kv_lora_rank, c.mla_heads,
                                 c.qk_nope_dim + c.v_head_dim)
        return kvb[..., :c.qk_nope_dim], kvb[..., c.qk_nope_dim:]

    def _mla_full(self, p, h):
        """Expanded form over (B, T, d), causal, in blocks of queries.
        Returns (y, the latent rows (B, T, latent_row))."""
        c = self.config
        B, T, _ = h.shape
        scale = (c.qk_nope_dim + c.qk_rope_dim) ** -0.5
        with jax.named_scope("attn_qkv"):
            q_n, q_r, row = self._mla_project(p, h)
            with jax.named_scope("mla_proj"):
                wk, wv = self._mla_kvb(p)
                lat = row[..., :c.kv_lora_rank]
                k_r = row[..., c.kv_lora_rank:c.latent_dim]
                k_n = jnp.einsum("btc,chn->bthn", lat, wk,
                                 preferred_element_type=jnp.float32
                                 ).astype(c.dtype)
                v = jnp.einsum("btc,chv->bthv", lat, wv,
                               preferred_element_type=jnp.float32
                               ).astype(c.dtype)
        with jax.named_scope("attn_core"), jax.named_scope("mla_attend"):
            bq = min(_QUERY_BLOCK, T)
            pad = -T % bq
            nb = (T + pad) // bq

            def blocks(a):
                a = jnp.pad(a, ((0, 0), (0, pad), (0, 0), (0, 0)))
                return a.reshape(B, nb, bq, *a.shape[2:]).swapaxes(0, 1)

            def one(args):
                qn_b, qr_b, i0 = args
                s = (jnp.einsum("bqhn,bkhn->bhqk", qn_b, k_n,
                                preferred_element_type=jnp.float32)
                     + jnp.einsum("bqhr,bkr->bhqk", qr_b, k_r,
                                  preferred_element_type=jnp.float32)) * scale
                ok = (i0 + jnp.arange(bq))[:, None] >= jnp.arange(T)[None, :]
                s = jnp.where(ok[None, None], s, -1e30)
                pr = jax.nn.softmax(s, axis=-1).astype(c.dtype)
                return jnp.einsum("bhqk,bkhv->bqhv", pr, v,
                                  preferred_element_type=jnp.float32
                                  ).astype(c.dtype)

            o = lax.map(one, (blocks(q_n), blocks(q_r),
                              jnp.arange(nb) * bq))
            o = o.swapaxes(0, 1).reshape(B, T + pad, -1)[:, :T]
        with jax.named_scope("attn_out"), jax.named_scope("mla_proj"):
            return _mm(o, p["w_o"]).astype(c.dtype), row

    def _mla_decode(self, p, h, pool, tables, positions, page_tokens):
        """Absorbed form: h (B, d) against the slot's pages of latent rows.
        The step's own row is written first, then read back with the rest:
        by the kernel that walks the slot's live pages where they lie
        (``kernels/paged_latent_attention.py``) or, everywhere
        :func:`latent_attention_backend` does not take it, over a gathered
        view of every slot's whole window."""
        c = self.config
        B = h.shape[0]
        P = int(page_tokens)
        S = tables.shape[1] * P
        R = c.kv_lora_rank
        scale = (c.qk_nope_dim + c.qk_rope_dim) ** -0.5
        with jax.named_scope("attn_qkv"):
            q_n, q_r, row = self._mla_project(p, h, positions)
            with jax.named_scope("mla_proj"):
                wk, wv = self._mla_kvb(p)
                q_c = jnp.einsum("bhn,chn->bhc", q_n, wk,
                                 preferred_element_type=jnp.float32
                                 ).astype(c.dtype)
        with jax.named_scope("kv_write"):
            page = _slot_page(tables, positions, P, pool.shape[0])
            pool = pool.at[page, positions % P].set(row)
        if self._took("mla", latent_attention_backend(
                c.mla_heads, c.latent_row, R, P, tables.shape[1],
                jnp.dtype(c.dtype).itemsize)) == "paged-latent":
            from deeplearning4j_tpu.kernels.paged_latent_attention import \
                paged_latent_attention
            with jax.named_scope("attn_core"), jax.named_scope("mla_attend"):
                # against a whole cached row, whose padding is zeros
                q = jnp.concatenate([q_c, q_r, jnp.zeros(
                    (B, c.mla_heads, c.latent_row - c.latent_dim), c.dtype)],
                    axis=-1)
                o_c = paged_latent_attention(q, pool, tables, positions, R,
                                             scale)
        else:
            with jax.named_scope("kv_gather"):
                view = pool.at[tables].get(mode="promise_in_bounds").reshape(
                    B, S, c.latent_row)
                view_c, view_r = view[..., :R], view[..., R:c.latent_dim]
            with jax.named_scope("attn_core"), jax.named_scope("mla_attend"):
                s = (jnp.einsum("bhc,bsc->bhs", q_c, view_c,
                                preferred_element_type=jnp.float32)
                     + jnp.einsum("bhr,bsr->bhs", q_r, view_r,
                                  preferred_element_type=jnp.float32)) * scale
                live = jnp.arange(S)[None, :] <= positions[:, None]
                s = jnp.where(live[:, None, :], s, -1e30)
                pr = jax.nn.softmax(s, axis=-1).astype(c.dtype)
                o_c = jnp.einsum("bhs,bsc->bhc", pr, view_c,
                                 preferred_element_type=jnp.float32
                                 ).astype(c.dtype)
        with jax.named_scope("attn_out"), jax.named_scope("mla_proj"):
            o = jnp.einsum("bhc,chv->bhv", o_c, wv,
                           preferred_element_type=jnp.float32).astype(c.dtype)
            return _mm(o.reshape(B, -1), p["w_o"]).astype(c.dtype), pool

    # ---------------------------------------------------------- Mamba-2
    def _ssm_project(self, p, h):
        """h (..., d) -> z (..., d_inner) float32, the rows the convolution
        takes (..., conv_dim), dt and log a (..., H) float32."""
        c = self.config
        di = c.ssm_inner
        with jax.named_scope("ssm_proj"):
            zxd = _mm(h, p["w_in"])
            pre = zxd[..., di:di + c.ssm_conv_dim].astype(c.dtype)
            dt = jax.nn.softplus(zxd[..., di + c.ssm_conv_dim:]
                                 + p["dt_bias"])
            log_a = -jnp.exp(p["a_log"]) * dt
        return zxd[..., :di], pre, dt, log_a

    def _ssm_xbc(self, conved):
        """SiLU of the convolved rows (float32, bias added), split into
        x (..., H, P), B and C (..., G, N)."""
        c = self.config
        act = jax.nn.silu(conved)
        di, gn = c.ssm_inner, c.ssm_groups * c.ssm_state
        lead = act.shape[:-1]
        return (act[..., :di].reshape(*lead, c.ssm_heads, c.ssm_head_dim),
                act[..., di:di + gn].reshape(*lead, c.ssm_groups,
                                             c.ssm_state),
                act[..., di + gn:].reshape(*lead, c.ssm_groups, c.ssm_state))

    def _ssm_out(self, p, y, x, z):
        """y, x (..., H, P), z (..., d_inner): the skip, the gate, the norm
        in groups of d_inner / G, the output projection."""
        c = self.config
        with jax.named_scope("ssm_out"):
            y = y + p["d_skip"][:, None] * x
            y = y.reshape(*z.shape) * jax.nn.silu(z)
            yg = y.reshape(*z.shape[:-1], c.ssm_groups, -1)
            yg = yg * lax.rsqrt(jnp.mean(yg * yg, -1, keepdims=True)
                                + c.rms_eps)
            y = (yg.reshape(*z.shape) * p["norm"].astype(jnp.float32)
                 ).astype(c.dtype)
            return _mm(y, p["w_out"]).astype(c.dtype)

    def _ssm_full(self, p, h, valid, last_idx):
        """h (B, T, d); rows where ``valid`` is False are identity updates
        (dt = 0: a = 1 and nothing is added). Returns (y, state at the last
        valid row, the 3 rows the convolution would need before the next)."""
        c = self.config
        B, T, _ = h.shape
        with jax.named_scope("attn_qkv"):
            z, pre, dt, log_a = self._ssm_project(p, h)
            with jax.named_scope("ssm_conv"):
                x, b, cm = self._ssm_xbc(_conv_full(pre, p["conv"])
                                         + p["b_conv"].astype(jnp.float32))
                tail = _conv_tail(pre, c.ssm_conv, last_idx, c.dtype)
        with jax.named_scope("attn_core"), jax.named_scope("ssm_state"):
            dt = jnp.where(valid[None, :, None], dt, 0.0)
            log_a = jnp.where(valid[None, :, None], log_a, 0.0)
            pad = -T % c.ssm_chunk
            xs = [x, dt, log_a, b, cm]
            if pad:
                xs = [jnp.pad(a, ((0, 0), (0, pad)) + ((0, 0),) * (a.ndim - 2))
                      for a in xs]
            s0 = jnp.zeros((B, c.ssm_heads, c.ssm_head_dim, c.ssm_state),
                           jnp.float32)
            y, s = ssd_chunked(*xs, s0, c.ssm_chunk)
            y = y[:, :T]
        with jax.named_scope("attn_out"):
            return self._ssm_out(p, y, x, z), s, tail

    def _ssm_decode(self, p, h, s, tail):
        """h (B, d), s (B, H, P, N), tail (B, 3, conv_dim)."""
        with jax.named_scope("attn_qkv"):
            z, pre, dt, log_a = self._ssm_project(p, h)
            with jax.named_scope("ssm_conv"):
                conved, rows = _conv_step(tail, pre, p["conv"])
                x, b, cm = self._ssm_xbc(conved
                                         + p["b_conv"].astype(jnp.float32))
                tail = rows[:, 1:]
        with jax.named_scope("attn_core"), jax.named_scope("ssm_state"):
            s, y = ssd_step(s, x, dt, log_a, b, cm)
        with jax.named_scope("attn_out"):
            return self._ssm_out(p, y, x, z), s, tail

    # ---------------------------------------------------------- Mamba-1
    def _m1_project(self, p, h):
        """h (..., d) -> the rows the convolution takes (..., C) and the
        gate's z (..., C) float32."""
        c = self.config
        with jax.named_scope("ssm_proj"):
            xz = _mm(h, p["w_in"])
        return xz[..., :c.m1_inner].astype(c.dtype), xz[..., c.m1_inner:]

    def _m1_dtbc(self, p, x):
        """x (..., C) float32, the convolved rows through their SiLU -> the
        time step (..., C) > 0 through its bottleneck, B and C (..., N)."""
        c = self.config
        r, N = c.m1_dt_rank, c.m1_state
        with jax.named_scope("ssm_proj"):
            rbc = _mm(x.astype(c.dtype), p["w_x"])
            dt = jax.nn.softplus(_mm(rbc[..., :r].astype(c.dtype), p["w_dt"])
                                 + p["b_dt"])
        return dt, rbc[..., r:r + N], rbc[..., r + N:]

    def _m1_out(self, p, y, z):
        c = self.config
        with jax.named_scope("ssm_out"):
            return _mm((y * jax.nn.silu(z)).astype(c.dtype),
                       p["w_out"]).astype(c.dtype)

    def _m1_full(self, p, h, valid, last_idx):
        """h (B, T, d); rows where ``valid`` is False are identity updates
        (dt = 0). Returns (y, state at the last valid row (B, N, C), the 3
        rows the convolution would need before the next, and the scan's
        output BEFORE the gate, skip included, (B, T, C) float32: what a
        later layer's memory unit reads)."""
        c = self.config
        B, T, _ = h.shape
        with jax.named_scope("attn_qkv"):
            pre, z = self._m1_project(p, h)
            with jax.named_scope("ssm_conv"):
                x = jax.nn.silu(_conv_full(pre, p["conv"])
                                + p["b_conv"].astype(jnp.float32))
                tail = _conv_tail(pre, c.m1_conv, last_idx, c.dtype)
            dt, b, cm = self._m1_dtbc(p, x)
        with jax.named_scope("attn_core"), jax.named_scope("ssm_state"):
            dt = jnp.where(valid[None, :, None], dt, 0.0)
            pad = -T % _M1_CHUNK
            xs = [x, dt, b, cm]
            if pad:
                xs = [jnp.pad(a, ((0, 0), (0, pad), (0, 0))) for a in xs]
            s0 = jnp.zeros((B, c.m1_state, c.m1_inner), jnp.float32)
            y, s = mamba1_chunked(xs[0], xs[1], -jnp.exp(p["a_log"]),
                                  xs[2], xs[3], s0, _M1_CHUNK)
            y = y[:, :T] + p["d_skip"] * x
        with jax.named_scope("attn_out"):
            return self._m1_out(p, y, z), s, tail, y

    def _m1_decode(self, p, h, s, tail):
        """h (B, d), s (B, N, C), tail (B, 3, C)."""
        with jax.named_scope("attn_qkv"):
            pre, z = self._m1_project(p, h)
            with jax.named_scope("ssm_conv"):
                conved, rows = _conv_step(tail, pre, p["conv"])
                x = jax.nn.silu(conved + p["b_conv"].astype(jnp.float32))
                tail = rows[:, 1:]
            dt, b, cm = self._m1_dtbc(p, x)
        with jax.named_scope("attn_core"), jax.named_scope("ssm_state"):
            s, y = mamba1_step(s, x, dt, -jnp.exp(p["a_log"]), b, cm)
            y = y + p["d_skip"] * x
        with jax.named_scope("attn_out"):
            return self._m1_out(p, y, z), s, tail, y

    def _gmu(self, p, h, memory):
        """The gated memory unit: h (..., d) gates the rows ``memory``
        (..., C) of the same positions, a state-space layer's output before
        its own gate: ``(silu(h W_in) * memory) W_out``. No state."""
        c = self.config
        with jax.named_scope("attn_qkv"), jax.named_scope("gmu"):
            g = jax.nn.silu(_mm(h, p["w_in"]))
        with jax.named_scope("attn_core"), jax.named_scope("gmu"):
            y = (g * memory.astype(jnp.float32)).astype(c.dtype)
        with jax.named_scope("attn_out"), jax.named_scope("gmu"):
            return _mm(y, p["w_out"]).astype(c.dtype)

    # ------------------------------------------- grouped-query attention
    # ``kind``: ``gqa``, over every earlier position, its rows in pages;
    # ``swa``, over the last ``swa_window``, its rows in a ring a slot; or
    # ``xattn``, queries alone over the rows of the ``gqa`` layer it reads.
    def _gqa_project(self, p, h, kind="gqa", positions=None):
        """h (..., d) at ``positions`` (broadcast against h's leading axes;
        None: whole sequences, 0 .. T - 1 along the axis before the last)
        -> q (..., Hkv, Hq / Hkv, hd), the row the cache keeps, [k heads |
        v heads] (..., 2 Hkv hd), and the output gate's scalars (..., Hq)
        float32, or None. Where the kind rotates, q and the row's keys are
        rotated here, in float32 before they are rounded: the keys once, at
        their own position, so a cached row never needs it again. The
        query-only kind (``xattn``) has no row: None. A differential kind's
        q is in pairs, ``HybridConfig.attention_shape``'s."""
        c = self.config
        heads, rope, gated, _window = c.attention(kind)
        differential, bias = c.differential, c.attn_bias
        g, hd = c.gqa_kv_heads, c.gqa_head_dim
        with jax.named_scope(_scope(kind, "proj")):
            if rope is None:
                q, row = _mm(h, p["w_q"]), None
                if bias:
                    q = q + p["b_q"].astype(jnp.float32)
                q = q.astype(c.dtype).reshape(*h.shape[:-1], g, heads // g,
                                              hd)
                if kind != "xattn":
                    row = _mm(h, p["w_kv"])
                    if bias:
                        row = row + p["b_kv"].astype(jnp.float32)
                    row = row.astype(c.dtype)
            else:       # never beside a bias or a query-only layer
                if positions is None:
                    positions = jnp.arange(h.shape[-2])
                q = _mm(h, p["w_q"]).reshape(*h.shape[:-1], g, heads // g, hd)
                kv = _mm(h, p["w_kv"])
                k = kv[..., :g * hd].reshape(*h.shape[:-1], g, hd)
                with jax.named_scope(_scope(kind, "rope")):
                    q = _rotate(q, positions[..., None, None],
                                rope).astype(c.dtype)
                    k = _rotate(k, positions[..., None], rope)
                row = jnp.concatenate(
                    [k.reshape(*h.shape[:-1], g * hd), kv[..., g * hd:]],
                    axis=-1).astype(c.dtype)
            if differential:
                q = _pair_queries(q.reshape(*h.shape[:-1], heads, hd),
                                  c.attention_shape(kind)[0])
            gate = None
            if gated:
                with jax.named_scope("attn_gate"):
                    gate = jax.nn.sigmoid(_mm(h, p["w_gate"]))
        return q, row, gate

    def _gqa_kv(self, rows, kind="gqa"):
        """(..., 2 Hkv hd) -> k, v (..., Hkv, hd), or, for a differential
        kind, (..., Hkv / 2, 2 hd): its pairs of heads."""
        c = self.config
        half = c.gqa_kv_row // 2
        g, _k, hd = c.attention_shape(kind)
        shape = (*rows.shape[:-1], g, hd)
        return rows[..., :half].reshape(shape), rows[..., half:].reshape(shape)

    def _attn_diff(self, p, o):
        """o (..., G, K, 2 hd), the two maps of every pair over the pair's
        value, a1 and a2 side by side -> (..., Hq hd) in ``dtype``:
        ``rms(a1 - lambda a2) (1 - lambda_init)`` a pair, through the
        128-wide sub-norm's gain."""
        c = self.config
        with jax.named_scope("attn_diff"):
            a = o.astype(jnp.float32).reshape(*o.shape[:-3], -1, 2,
                                              o.shape[-1])
            lam = (jnp.exp(jnp.sum(p["lambda_q1"] * p["lambda_k1"]))
                   - jnp.exp(jnp.sum(p["lambda_q2"] * p["lambda_k2"]))
                   + p["lambda_init"])
            y = _rms(a[..., 0, :] - lam * a[..., 1, :], p["sub_norm"],
                     c.rms_eps) * (1.0 - p["lambda_init"])
            return y.reshape(*o.shape[:-3], -1).astype(c.dtype)

    def _gqa_out(self, p, o, gate, kind):
        """o (..., Hq hd) in ``dtype``: every head times its gate's scalar,
        where the kind is gated, then the output projection."""
        c = self.config
        with jax.named_scope("attn_out"), jax.named_scope(
                _scope(kind, "proj")):
            if gate is not None:
                with jax.named_scope("attn_gate"):
                    o = (o.reshape(*gate.shape, c.gqa_head_dim)
                         * gate[..., None]).astype(c.dtype).reshape(o.shape)
            if c.attn_bias:
                return (_mm(o, p["w_o"])
                        + p["b_o"].astype(jnp.float32)).astype(c.dtype)
            return _mm(o, p["w_o"]).astype(c.dtype)

    def _gqa_full(self, p, h, kind="gqa", src=None, row_at=None):
        """Causal softmax attention over (B, T, d) in blocks of queries.
        A block scores every key (``gqa``) or the ``block + window`` keys it
        can see (``swa``: a query at t reads keys t - window < j <= t).
        Returns (y, the rows of K and V (B, T, 2 Hkv hd)). The query-only
        kind scores the rows ``src`` of the layer it reads and returns None
        for its own; ``row_at``: None, or the position of h's ONE row."""
        c = self.config
        B, T, _ = h.shape
        scale = c.gqa_head_dim ** -0.5
        window = c.attention(kind)[3]
        starts = None if row_at is None else jnp.reshape(row_at, (1,))
        with jax.named_scope("attn_qkv"):
            q, row, gate = self._gqa_project(p, h, kind, starts)
            k, v = self._gqa_kv(row if src is None else src, kind)
        keys = k.shape[1]
        with jax.named_scope("attn_core"), jax.named_scope(
                _scope(kind, "attend")):
            bq = min(_QUERY_BLOCK, T)
            pad = -T % bq
            nb = (T + pad) // bq
            qb = jnp.pad(q, ((0, 0), (0, pad), (0, 0), (0, 0), (0, 0)))
            qb = qb.reshape(B, nb, bq, *q.shape[2:]).swapaxes(0, 1)
            if window:
                # keys i0 - window .. i0 + bq - 1 of a block that starts at
                # i0: ``window`` rows of zeros in front, ``pad`` behind
                span = ((0, 0), (window, pad), (0, 0), (0, 0))
                k, v = jnp.pad(k, span), jnp.pad(v, span)

            def one(args):
                q_b, i0 = args
                k_b, v_b = k, v
                if window:
                    k_b, v_b = (lax.dynamic_slice_in_dim(a, i0, bq + window,
                                                         axis=1)
                                for a in (k, v))
                s = jnp.einsum("bqgkd,btgd->bgkqt", q_b, k_b,
                               preferred_element_type=jnp.float32) * scale
                at = (i0 + jnp.arange(bq))[:, None]
                if window:
                    j = (i0 - window + jnp.arange(bq + window))[None, :]
                    ok = (j <= at) & (j > at - window) & (j >= 0)
                else:
                    ok = at >= jnp.arange(keys)[None, :]
                pr = jax.nn.softmax(jnp.where(ok, s, -1e30),
                                    axis=-1).astype(c.dtype)
                return jnp.einsum("bgkqt,btgd->bqgkd", pr, v_b,
                                  preferred_element_type=jnp.float32
                                  ).astype(c.dtype)

            o = lax.map(one, (qb, jnp.arange(nb) * bq if starts is None
                              else starts))
            o = o.swapaxes(0, 1).reshape(B, T + pad, -1)[:, :T]
            if c.differential:
                o = self._attn_diff(p, o.reshape(B, T, *q.shape[2:]))
        return self._gqa_out(p, o, gate, kind), row

    def _swa_full(self, p, h, last_idx):
        """The window kind over (B, T, d). Returns (y, the ring as it stands
        at the prompt's TRUE last token (B, window, 2 Hkv hd): the row of
        position t at ``t % window`` for the last ``window`` positions up to
        ``last_idx``, zeros where the prompt is shorter)."""
        W = self.config.swa_window
        y, row = self._gqa_full(p, h, "swa")
        with jax.named_scope("kv_write"), jax.named_scope("swa_write"):
            j = jnp.arange(W)
            at = last_idx - (last_idx - j) % W
            ring = jnp.where((at >= 0)[None, :, None],
                             jnp.take(row, jnp.maximum(at, 0), axis=1), 0)
        return y, ring

    def _gqa_decode(self, p, h, pool, tables, positions, page_tokens,
                    kind="gqa"):
        """h (B, d) against the slot's pages of K and V rows. The step's own
        row is written first, then read back with the rest: by the kernel
        that walks the slot's live pages where they lie
        (``kernels/paged_latent_attention.py::paged_grouped_attention``) or,
        everywhere :func:`grouped_attention_backend` does not take it, over
        a gathered view of every slot's whole window. The query-only kind
        writes nothing: ``pool`` is the layer's it reads, the step's row
        already in it."""
        c = self.config
        B = h.shape[0]
        P = int(page_tokens)
        S = tables.shape[1] * P
        scale = c.gqa_head_dim ** -0.5
        g, k_rows, hd = c.attention_shape(kind)
        with jax.named_scope("attn_qkv"):
            q, row, gate = self._gqa_project(p, h, kind, positions)
        if row is not None:
            with jax.named_scope("kv_write"):
                page = _slot_page(tables, positions, P, pool.shape[0])
                pool = pool.at[page, positions % P].set(row)
        if self._took(kind, grouped_attention_backend(
                B, g * k_rows, g, hd, P, tables.shape[1],
                jnp.dtype(c.dtype).itemsize)) == "paged-grouped":
            from deeplearning4j_tpu.kernels.paged_latent_attention import \
                paged_grouped_attention
            with jax.named_scope("attn_core"), jax.named_scope(
                    _scope(kind, "attend")):
                o = paged_grouped_attention(q, pool, tables, positions, scale)
                if c.differential:
                    o = self._attn_diff(p, o)
        else:
            with jax.named_scope("kv_gather"):
                k, v = self._gqa_kv(pool.at[tables].get(
                    mode="promise_in_bounds").reshape(B, S, c.gqa_kv_row),
                    kind)
            with jax.named_scope("attn_core"), jax.named_scope(
                    _scope(kind, "attend")):
                s = jnp.einsum("bgkd,bsgd->bgks", q, k,
                               preferred_element_type=jnp.float32) * scale
                live = jnp.arange(S)[None, :] <= positions[:, None]
                pr = jax.nn.softmax(jnp.where(live[:, None, None, :], s,
                                              -1e30), axis=-1).astype(c.dtype)
                o = jnp.einsum("bgks,bsgd->bgkd", pr, v,
                               preferred_element_type=jnp.float32
                               ).astype(c.dtype)
                if c.differential:
                    o = self._attn_diff(p, o)
        return self._gqa_out(p, o.reshape(B, -1), gate, kind), pool

    def _swa_decode(self, p, h, ring, positions):
        """h (B, d) against the slot's ring of K and V rows (B, window,
        2 Hkv hd). The step's own row goes to ``position % window``, over
        the row that has just left the window; the ring is then read whole,
        under a mask for a slot younger than the window: by the kernel that
        walks a full layer's pages, the ring taken as the slot's own pages
        in order, which clamps a position to the window and visits and
        masks what :func:`_ring_live` keeps
        (``kernels/paged_latent_attention.py::paged_grouped_attention``),
        or, everywhere :func:`ring_attention_backend` does not take it, by
        two einsums. Every key carries its own position's rotation, so the
        ring's order does not matter."""
        c = self.config
        B, W = h.shape[0], c.swa_window
        scale = c.gqa_head_dim ** -0.5
        g, k_rows, hd = c.attention_shape("swa")
        with jax.named_scope("attn_qkv"):
            q, row, gate = self._gqa_project(p, h, "swa", positions)
        with jax.named_scope("kv_write"), jax.named_scope("swa_write"):
            ring = ring.at[jnp.arange(B), positions % W].set(row)
        took = self._took("swa", ring_attention_backend(
            g * k_rows, g, hd, W, jnp.dtype(c.dtype).itemsize))
        with jax.named_scope("attn_core"), jax.named_scope("swa_attend"):
            if took == "paged-grouped":
                from deeplearning4j_tpu.kernels.paged_latent_attention \
                    import paged_grouped_attention
                P = _ring_page(W)           # slot b: pages b W / P ..
                o = paged_grouped_attention(
                    q, ring.reshape(B * W // P, P, c.gqa_kv_row),
                    jnp.arange(B * W // P, dtype=jnp.int32).reshape(B, -1),
                    positions, scale)
            else:
                k, v = self._gqa_kv(ring, "swa")
                s = jnp.einsum("bgkd,bsgd->bgks", q, k,
                               preferred_element_type=jnp.float32) * scale
                live = _ring_live(W, positions)
                pr = jax.nn.softmax(jnp.where(live[:, None, None, :], s,
                                              -1e30), axis=-1).astype(c.dtype)
                o = jnp.einsum("bgks,bsgd->bgkd", pr, v,
                               preferred_element_type=jnp.float32
                               ).astype(c.dtype)
            if c.differential:
                o = self._attn_diff(p, o)
        return self._gqa_out(p, o.reshape(B, -1), gate, "swa"), ring

    # ------------------------------------------------------ full forward
    def _say_once(self, subject, choice, why):
        """``<subject>: <choice>: <reason>``, once a trace (every layer asks;
        ``TransformerLM._say_once``'s spelling)."""
        said = (jax.core.get_opaque_trace_state(), choice, why)
        if said != self._said.get(subject):
            self._said[subject] = said
            logging.getLogger(__name__).info("%s: %s: %s", subject, choice,
                                             why)

    def _took(self, kind, took):
        """Keeps and says what a trace of ``kind``'s attention of a decode
        step took, ``attention backend: <kind>: <choice>: <why>``: its
        choice."""
        self.attention_backend[kind] = took
        self._say_once(f"attention backend: {kind}", *took)
        return took[0]

    def _say_layers(self):
        """``layer kinds: <a layer's parts, joined by +, a layer>: <the
        experts' form and the router>``, once a trace (as ``TransformerLM``
        says its layouts)."""
        c = self.config
        # a part that lands at the layer's end stands in brackets; what a
        # part hands on stands behind ``=``, what it reads behind ``<``
        def say(p):
            name = p.kind + (f"={p.tag}" if p.tag else "") \
                + (f"<{p.source}" if p.source else "")
            return name if p.lands == "now" else f"[{name}]"

        kinds = " ".join("+".join(say(p) for p in s.parts) for s in c.layers)
        e = c.experts
        why = "no routed experts" if not self.moe_layers else (
            f"experts {e.form}"
            + (f" in a {c.expert_latent}-wide latent space"
               if c.expert_latent else "")
            + ("" if e.score == "sigmoid" else f", {e.score}")
            + f", {e.top_k} of {e.router_width} a token"
            + (f": {e.router_width - e.identity} experts + {e.identity} "
               f"identity" if e.identity else "")
            + f", {e.held[1]} held from {e.held[0]}")
        present = {p.kind for s in c.layers for p in s.parts}
        for kind in ("gqa", "swa"):
            heads, rope, gated, window = c.attention(kind)
            differential, bias = c.differential, c.attn_bias
            if kind in present and (rope or gated or window or differential
                                    or bias):
                why += (f"; {kind} {heads} heads on {c.gqa_kv_heads}, "
                        + (rope.say(c.gqa_head_dim) if rope else "unrotated")
                        + (", gated" if gated else "")
                        + (", differential in pairs of {} wide".format(
                            2 * c.gqa_head_dim) if differential else "")
                        + (", biased" if bias else "")
                        + (f", window {window}" if window else ""))
        if "xattn" in present:
            why += (f"; xattn: queries alone, {self.page_readers} parts "
                    "read the pages")
        if "mamba1" in present:
            why += (f"; mamba1 {c.m1_inner} channels x {c.m1_state}, dt "
                    f"through {c.m1_dt_rank}, {_M1_CHUNK} rows a scan step")
        if c.norm != "rms" or c.tie_embeddings:
            why += f"; {c.norm} norm" + (", tied head" if c.tie_embeddings
                                         else "")
        if c.last_row_from is not None:
            why += (f"; a prefill runs the layers from {c.last_row_from} on "
                    "the last row")
        self._say_once("layer kinds", kinds, why)

    def _layers(self, params, x, run, start=0, stop=None):
        """The one walk over the description: every layer's parts in order
        (layers ``start`` to ``stop``), ``run(part, rank, p, h32) -> y`` for
        each (``rank``: a mixer's among the mixers of its kind, ``p`` its
        parameters, ``h32`` the normalised rows it reads, float32), each
        result added to the stream where the part says it lands."""
        for blk, spec, ranks in zip(params["blocks"][start:stop],
                                    self.config.layers[start:stop],
                                    self._rank[start:stop]):
            late = []
            for part, rank in zip(spec.parts, ranks):
                if part.norm is not None:
                    h32 = self._ln(blk[part.norm], x)
                y = run(part, rank, blk[part.name], h32)
                if part.lands == "end":
                    late.append(y)
                else:
                    x = x + y
            for y in late:
                x = x + y
        return x

    def _handed_on(self, kind, held):
        """What a part with a ``tag`` hands to later parts, from what its
        kind's function returned behind y: its side output (the last), else
        the array of its paged leaf."""
        if kind.gives_side:
            return held[-1]
        return next(a for leaf, a in zip(kind.leaves(self.config), held)
                    if leaf.paged)

    def _trunk(self, params, tokens, last_idx, last_row=False):
        """tokens (B, T) -> (x (B, T, d) before the final norm, cache
        entries). Rows after ``last_idx`` are padding. ``last_row``: the
        layers from ``last_row_from`` on are run on the row at ``last_idx``
        alone, and x is that row, (B, 1, d) - where the configuration names
        such a layer; everywhere else, and in :meth:`apply`, every layer
        over every row."""
        c = self.config
        self._say_layers()
        T = tokens.shape[1]
        valid = jnp.arange(T) <= last_idx
        entries = {leaf.name: [] for leaf, _n in self.cache_leaves}
        given: Dict[str, Any] = {}
        sides = set()   # the tags of ``given`` that are side outputs
        row = None      # the position of the ONE row the layers now see

        def run(part, _rank, p, h32):
            if part.kind not in MIXERS:
                return self._ffn(p, part.kind, h32, None if row is not None
                                 else jnp.broadcast_to(valid, tokens.shape)
                                 )[0]
            kind = MIXERS[part.kind]
            y, *new = kind.full(self, p, h32.astype(c.dtype), valid, last_idx,
                                given.get(part.source), row)
            for leaf, entry in zip(kind.leaves(c), new):
                entries[leaf.name].append(entry)
            if part.tag is not None:
                given[part.tag] = self._handed_on(kind, new)
                if kind.gives_side:
                    sides.add(part.tag)
            return y

        x = self._embed(params, tokens)
        if not last_row or c.last_row_from is None:
            return self._layers(params, x, run), entries
        x = self._layers(params, x, run, stop=c.last_row_from)
        # a side output is a row a position: the last row's; paged rows are
        # read whole, by the one query that stands at ``last_idx``
        x, row = lax.dynamic_slice_in_dim(x, last_idx, 1, axis=1), last_idx
        given = {tag: lax.dynamic_slice_in_dim(a, last_idx, 1, axis=1)
                 if tag in sides else a for tag, a in given.items()}
        return self._layers(params, x, run, start=c.last_row_from), entries

    def apply(self, params, tokens):
        """tokens (B, T) int32 -> logits (B, T, V) float32."""
        x, _ = self._trunk(params, tokens, tokens.shape[1] - 1)
        return self._head(params, x)

    # ------------------------------------------------------ cache protocol
    def prefill_cache(self, params, tokens, last_idx):
        """tokens (B, T_bucket), the prompt's last token at ``last_idx`` ->
        (logits of that token (B, 1, V), entries for :meth:`insert_paged`)."""
        if self.config.last_row_from is None:
            x, entries = self._trunk(params, tokens, last_idx)
            x = lax.dynamic_slice_in_dim(x, last_idx, 1, axis=1)
        else:
            x, entries = self._trunk(params, tokens, last_idx, last_row=True)
        return self._head(params, x), entries

    def prefill_tail_rows(self, bucket: int) -> int:
        """Rows a prefill of ``bucket`` positions computes in its LAST
        layer: 1 where the configuration runs its upper layers on the last
        row alone, else the bucket."""
        return 1 if self.config.last_row_from is not None else int(bucket)

    def entries_tokens(self, entries) -> int:
        """Positions a prefill's entries cover: the bucket's length where a
        layer keeps pages, else 1 (a state alone needs no page)."""
        for leaf, _n in self.cache_leaves:
            if leaf.paged:
                return entries[leaf.name][0].shape[1]
        return 1

    @staticmethod
    def entries_row(entries, b: int):
        return jax.tree.map(lambda a: a[b:b + 1], entries)

    def new_paged_cache(self, slots: int, n_pages: int, page_tokens: int,
                        quant: bool = False) -> Dict:
        """{leaf name: one array a layer that owns it}."""
        return {leaf.name: [
            jnp.zeros(((n_pages, page_tokens) if leaf.paged else (slots,))
                      + leaf.shape, leaf.dtype) for _ in range(n)]
            for leaf, n in self.cache_leaves}

    def _leaf_bytes(self, paged: bool) -> int:
        return sum(n * math.prod(leaf.shape) * jnp.dtype(leaf.dtype).itemsize
                   for leaf, n in self.cache_leaves if leaf.paged == paged)

    def page_bytes(self, page_tokens: int, quant: bool = False) -> int:
        return page_tokens * self._leaf_bytes(True)

    def slot_state_bytes(self) -> int:
        return self._leaf_bytes(False)

    def insert_paged(self, arrays, entries, page_ids, slot, page_tokens):
        """One prefilled prompt (batch 1) into ``slot``: its rows into the
        slot's pages, its states over whatever the slot held."""
        out = {}
        with jax.named_scope("kv_write"):
            for leaf, _n in self.cache_leaves:
                out[leaf.name] = []
                for held, new in zip(arrays[leaf.name], entries[leaf.name]):
                    if leaf.paged:
                        tb = new.shape[1]
                        npb = -(-tb // page_tokens)
                        rows = jnp.pad(new[0], ((0, npb * page_tokens - tb),
                                                (0, 0)))
                        out[leaf.name].append(held.at[page_ids].set(
                            rows.reshape(npb, page_tokens, -1)))
                    else:
                        out[leaf.name].append(lax.dynamic_update_slice_in_dim(
                            held, new.astype(held.dtype), slot, axis=0))
        return out

    def decode_paged(self, params, arrays, tables, tokens, positions,
                     page_tokens):
        """One token a slot: tokens, positions (B,) -> (logits (B, V),
        arrays, stats int32[len(step_stats)] summed over the expert layers).
        A slot whose table points at the trash page is free: it routes to no
        expert."""
        c = self.config
        self._say_layers()
        pools = [arrays[leaf.name][0] for leaf, _n in self.cache_leaves
                 if leaf.paged]
        occupied = tables[:, 0] != (pools[0].shape[0] - 1) if pools else None
        x = self._embed(params, tokens)
        out = {leaf.name: [] for leaf, _n in self.cache_leaves}
        given: Dict[str, Any] = {}
        stats = jnp.zeros((len(self.step_stats),), jnp.int32)

        def run(part, rank, p, h32):
            nonlocal stats
            if part.kind not in MIXERS:
                y, st = self._ffn(p, part.kind, h32, occupied)
                if st is not None:
                    stats = stats + st
                return y
            kind = MIXERS[part.kind]
            names = [leaf.name for leaf in kind.leaves(c)]
            y, *held = kind.decode(
                self, p, h32.astype(c.dtype),
                [arrays[n][rank] for n in names], tables, positions,
                page_tokens, given.get(part.source))
            for n, a in zip(names, held):
                out[n].append(a)
            if part.tag is not None:
                given[part.tag] = self._handed_on(kind, held)
            return y

        x = self._layers(params, x, run)
        return self._head(params, x), out, stats


def _kda_leaves(c: HybridConfig):
    H, K = c.kda_heads, c.kda_head_dim
    return (CacheLeaf("kda_s", False, (H, K, K), jnp.float32),
            CacheLeaf("kda_conv", False, (c.kda_conv - 1, 3 * H * K),
                      c.dtype))


def _ssm_leaves(c: HybridConfig):
    return (CacheLeaf("ssm_s", False,
                      (c.ssm_heads, c.ssm_head_dim, c.ssm_state),
                      jnp.float32),
            CacheLeaf("ssm_conv", False, (c.ssm_conv - 1, c.ssm_conv_dim),
                      c.dtype))


#: every kind of mixer: the cache leaves a layer of it owns and its three
#: functions. A recurrent kind's ``full`` takes the padding's mask and the
#: prompt's last index; an attention's needs neither (padding comes after
#: every real row and is never read back)
MIXERS: Dict[str, MixerKind] = {
    "kda": MixerKind(
        _kda_leaves,
        lambda m, p, h, valid, last, src, row: m._kda_full(p, h, valid, last),
        lambda m, p, h, held, tables, pos, pt, src: m._kda_decode(p, h,
                                                                  *held),
        HybridLM._init_kda),
    "mla": MixerKind(
        lambda c: (CacheLeaf("latent", True, (c.latent_row,), c.dtype),),
        lambda m, p, h, valid, last, src, row: m._mla_full(p, h),
        lambda m, p, h, held, tables, pos, pt, src: m._mla_decode(
            p, h, *held, tables, pos, pt),
        HybridLM._init_mla),
    "mamba2": MixerKind(
        _ssm_leaves,
        lambda m, p, h, valid, last, src, row: m._ssm_full(p, h, valid, last),
        lambda m, p, h, held, tables, pos, pt, src: m._ssm_decode(p, h,
                                                                  *held),
        HybridLM._init_mamba2),
    "mamba1": MixerKind(
        lambda c: (CacheLeaf("m1_s", False, (c.m1_state, c.m1_inner),
                             jnp.float32),
                   CacheLeaf("m1_conv", False, (c.m1_conv - 1, c.m1_inner),
                             c.dtype)),
        lambda m, p, h, valid, last, src, row: m._m1_full(p, h, valid, last),
        lambda m, p, h, held, tables, pos, pt, src: m._m1_decode(p, h,
                                                                 *held),
        HybridLM._init_mamba1, gives_side=True),
    "gmu": MixerKind(
        lambda c: (),
        lambda m, p, h, valid, last, src, row: (m._gmu(p, h, src),),
        lambda m, p, h, held, tables, pos, pt, src: (m._gmu(p, h, src),),
        HybridLM._init_gmu, reads="side"),
    "gqa": MixerKind(
        lambda c: (CacheLeaf("kv", True, (c.gqa_kv_row,), c.dtype),),
        lambda m, p, h, valid, last, src, row: m._gqa_full(p, h),
        lambda m, p, h, held, tables, pos, pt, src: m._gqa_decode(
            p, h, *held, tables, pos, pt),
        HybridLM._init_gqa),
    "swa": MixerKind(
        lambda c: (CacheLeaf("swa_kv", False, (c.swa_window, c.gqa_kv_row),
                             c.dtype),),
        lambda m, p, h, valid, last, src, row: m._swa_full(p, h, last),
        lambda m, p, h, held, tables, pos, pt, src: m._swa_decode(
            p, h, *held, pos),
        lambda m, w, ones, resid, layer: m._init_gqa(w, ones, resid, layer,
                                                     "swa")),
    "xattn": MixerKind(
        lambda c: (),
        lambda m, p, h, valid, last, src, row: m._gqa_full(
            p, h, "xattn", src, row)[:1],
        lambda m, p, h, held, tables, pos, pt, src: m._gqa_decode(
            p, h, src, tables, pos, pt, "xattn")[:1],
        lambda m, w, ones, resid, layer: m._init_gqa(w, ones, resid, layer,
                                                     "xattn"),
        reads="pages"),
}
