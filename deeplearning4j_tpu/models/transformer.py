"""TransformerLM — the flagship distributed model.

The reference's nearest analogs are the SameDiff attention ops
(``MultiHeadDotProductAttention``) behind ``SelfAttentionLayer`` and the
TF-import BERT fine-tune path (SURVEY 3.5); upstream has no native
transformer LM and no model/sequence parallelism. This model is the
framework's showcase for the net-new axes: built as a pure-functional param
pytree (not MLN layers) so every matmul carries explicit TP sharding
annotations, attention routes through ring attention when a ``seq`` axis is
present, and the whole train step jits into one GSPMD program.

Sharding map (Megatron-style):
- embeddings  (V, C):      P(None, 'model'); the head gathers the cast copy
  whole and splits its tokens over 'model' too (``_head_operands``)
- attn qkvo   (C, C):      qkv P(None, 'model') / out P('model', None); the
  fused (C, 3C) leaf is stored the same way and read by heads (``_qkv``)
- mlp up/down (C, 4C)/(4C, C): up P(None, 'model') / down P('model', None)
- activations (B, T, C):   P('data', 'seq', None)

Named scopes (``jax.named_scope``: metadata on the compiled operations, read
from a device profile; no change to the program): one fixed vocabulary in
every spelling of the block - ``embed``, ``ln``, ``attn_qkv``, ``attn_core``,
``attn_out``, ``mlp``, ``head``, ``loss``, ``optimizer``, and on the decode
paths ``cast_params``, ``kv_write`` (rows stored into cache or pool),
``kv_gather`` (a slot's pages read back through its table).
"""
from __future__ import annotations

import dataclasses
import functools
import logging
import math
import os
from typing import Any, Dict, Optional, Tuple

import jax
import jax.numpy as jnp
import numpy as np
import optax
from jax import lax, shard_map
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

from deeplearning4j_tpu.nn._remat import remat as _remat
from deeplearning4j_tpu.observability import compile_watch as _cw
from deeplearning4j_tpu.ops.moments import one_pass_moments
from deeplearning4j_tpu.parallel.mesh import (DATA_AXIS, EXPERT_AXIS,
                                              MODEL_AXIS, SEQ_AXIS,
                                              STAGE_AXIS, axis_size,
                                              replicated)
from deeplearning4j_tpu.parallel.moe import (MoEConfig, init_moe_params,
                                             moe_ffn, moe_param_specs)
from deeplearning4j_tpu.parallel.ring import ring_attention, _plain_attention

# attention backend override: None = auto (flash kernels on TPU from
# FLASH_MIN_SEQ up, XLA attention elsewhere — interpret-mode pallas is slow
# on CPU); True/False forces it
FLASH_ATTENTION: Optional[bool] = None

# auto-policy crossover: from this sequence length up the Pallas kernels
# (kernels/flash_attention.py: scores in VMEM only, forward and backward)
# beat XLA attention's (T, T) tensors in HBM on device-measured time.
# Hardware-measured (v5e, PR 28, benchmarks/flash_crossover.py; causal,
# D = 64, B x H = 8 x 16 and 8 x 10, kernels | XLA in ms): fwd+bwd T=512
# 0.59 | 0.46 and 0.37 | 0.20; T=768 1.19 | 1.84 and 0.75 | 0.63 (mixed);
# T=1024 1.59 | 3.52 and 0.99 | 2.11; T=4096 16.1 | 69.1. Forward alone
# crosses at the same length (T=768 0.60 | 0.50 and 0.38 | 0.18; T=1024
# 0.64 | 0.85 and 0.40 | 0.53), so prefill and training share one gate.
# The whole table is in PERF.md section 6, PR 28.
FLASH_MIN_SEQ = 1024

#: compile_watch's name for ``make_train_step``'s jitted step
TRAIN_STEP_FN = "TransformerLM.train_step"


def _attention_policy(seq_len: Optional[int] = None) -> Tuple[bool, str]:
    """(use the flash kernels, why). Env override first: "xla"/"flash" force
    a backend, "auto" (default) keeps the measured-crossover policy.
    Consulted at TRACE time only — a compiled executable never re-reads it.
    On a TPU a kernel that does not compile is an error, never a downgrade
    to XLA attention."""
    backend = os.environ.get("DL4J_TPU_ATTN_BACKEND", "auto").lower()
    if backend in ("xla", "flash"):
        return backend == "flash", f"DL4J_TPU_ATTN_BACKEND={backend}"
    if FLASH_ATTENTION is not None:
        return FLASH_ATTENTION, f"FLASH_ATTENTION = {FLASH_ATTENTION}"
    if seq_len is not None and seq_len < FLASH_MIN_SEQ:
        return False, f"T = {seq_len} is under FLASH_MIN_SEQ = {FLASH_MIN_SEQ}"
    platform = jax.default_backend()
    return platform == "tpu", f"T = {seq_len} on {platform}"


def _use_flash_attention(seq_len: Optional[int] = None) -> bool:
    return _attention_policy(seq_len)[0]


def quantize_kv_rows(rows):
    """Symmetric per-row int8 quantization of KV rows (…, H, hd) →
    (int8 rows, f32 scale (…,)): scale = max|row| / 127, zeros keep
    scale 1 so dequant is exact. Module-level ON PURPOSE — the
    numerics-gate tests monkeypatch this with a corrupted scale to
    prove the deploy-time gate trips and falls back to f32 storage
    (see ``DecodeEngine`` in models/generation.py)."""
    amax = jnp.max(jnp.abs(rows.astype(jnp.float32)), axis=(-2, -1))
    scale = jnp.where(amax > 0, amax / 127.0, 1.0)
    q8 = jnp.clip(jnp.round(rows.astype(jnp.float32)
                            / scale[..., None, None]),
                  -127, 127).astype(jnp.int8)
    return q8, scale.astype(jnp.float32)


def pack_kv_pages(arr, page_tokens: int):
    """(L, 1, Tb, H, hd) prefill k/v → (L, npb, P, H·hd) page rows of
    whole lanes (a per-row scale (L, 1, Tb) → (L, npb, P)), zero-padded
    up to whole pages (pad rows sit past the prompt's positions —
    masked until the slot's own decode writes overwrite them). ONE
    spelling: the traced paged insert uses it and the eager
    numerics-gate probe goes through that insert, so the gate compares
    exactly the packing production uses and a layout change cannot
    slip past it."""
    L, _b, tb = arr.shape[:3]
    npb = -(-tb // page_tokens)
    pad = npb * page_tokens - tb
    a = jnp.pad(arr[:, 0], ((0, 0), (0, pad)) + ((0, 0),) * (arr.ndim - 3))
    lanes = (math.prod(arr.shape[3:]),) if arr.ndim > 3 else ()
    return a.reshape(L, npb, page_tokens, *lanes)


@dataclasses.dataclass
class TransformerConfig:
    vocab_size: int = 256
    n_layers: int = 2
    n_heads: int = 4
    d_model: int = 128
    d_ff: Optional[int] = None
    max_len: int = 256
    dropout: float = 0.0
    dtype: Any = jnp.float32          # bfloat16 on real TPU runs
    causal: bool = True
    remat: bool = False               # jax.checkpoint each block: trade
                                      # recompute FLOPs for HBM (SURVEY §7
                                      # rematerialisation lever)
    remat_policy: Optional[str] = None  # named jax.checkpoint save policy
                                      # ("dots" = keep matmul outputs, only
                                      # replay cheap ops in backward); None
                                      # = full recompute. See nn/_remat.py
                                      # — scan_layers + remat without a
                                      # policy double-pays the MXU
    scan_layers: bool = False         # lax.scan over stacked block params:
                                      # compile time/HLO size O(1) in depth
                                      # instead of O(L) — the deep-model
                                      # compile lever
    pipeline_stages: int = 0          # >1: GPipe the block stack over the
                                      # ``stage`` mesh axis (parallel/pipeline)
    microbatches: int = 0             # GPipe micro-batch count (0 = 2·stages)
    pipeline_schedule: str = "gpipe"  # "gpipe": autodiff through the
                                      # schedule; "1f1b": custom-vjp 1F1B
                                      # backward — live activations bounded
                                      # by depth, not micro-batch count
    moe: Optional["MoEConfig"] = None  # replace the dense FFN with a
                                      # Switch-MoE FFN (parallel/moe); expert
                                      # axis shards over ``expert`` when the
                                      # mesh has one
    moe_aux_weight: float = 0.01      # Switch load-balance aux-loss weight
    fused_qkv: bool = False           # one (d, 3d) projection matmul per
                                      # block instead of three (d, d): fewer,
                                      # larger MXU ops + one HBM read of x
    ce_chunks: int = 0                # >0: stream the LM cross-entropy over
                                      # vocab chunks (kernels/chunked_ce) —
                                      # the (B,T,V) logits tensor never
                                      # materializes in fwd OR bwd

    def __post_init__(self):
        if self.d_ff is None:
            self.d_ff = 4 * self.d_model
        assert self.d_model % self.n_heads == 0
        if self.moe is not None:
            import dataclasses as _dc
            self.moe = _dc.replace(
                self.moe,
                d_model=self.moe.d_model or self.d_model,
                d_ff=self.moe.d_ff or self.d_ff)
        if self.pipeline_schedule not in ("gpipe", "1f1b"):
            raise ValueError(
                f"pipeline_schedule must be 'gpipe' or '1f1b' "
                f"(got {self.pipeline_schedule!r})")
        if self.pipeline_stages > 1:
            assert self.n_layers % self.pipeline_stages == 0, \
                "n_layers must divide into pipeline_stages"
            assert not self.scan_layers, \
                "pipeline_stages and scan_layers are mutually exclusive"
            assert self.moe is None, \
                "pipeline_stages + moe is not supported yet (the MoE aux " \
                "loss cannot cross the pipeline's shard_map boundary)"
            if not self.microbatches:
                self.microbatches = 2 * self.pipeline_stages
        if self.ce_chunks:
            assert self.ce_chunks > 1, "ce_chunks must be >= 2 (1 = off)"
            assert self.vocab_size % self.ce_chunks == 0, \
                f"vocab_size {self.vocab_size} must divide into " \
                f"ce_chunks {self.ce_chunks}"


class TransformerLM:
    """Decoder-only LM over a device mesh."""

    def __init__(self, config: TransformerConfig, mesh: Optional[Mesh] = None):
        self.config = config
        self.mesh = mesh
        self._said = {}             # the last trace's log lines, by subject

    # ------------------------------------------------------------------ params
    def init_params(self, key) -> Dict:
        c = self.config
        k = jax.random.split(key, 4 + c.n_layers)
        scale = 0.02
        params = {
            "tok_emb": jax.random.normal(k[0], (c.vocab_size, c.d_model)) * scale,
            "pos_emb": jax.random.normal(k[1], (c.max_len, c.d_model)) * scale,
            "ln_f": {"g": jnp.ones((c.d_model,)), "b": jnp.zeros((c.d_model,))},
            "blocks": [],
        }
        for i in range(c.n_layers):
            kk = jax.random.split(k[4 + i], 6)
            blk = {
                "ln1": {"g": jnp.ones((c.d_model,)), "b": jnp.zeros((c.d_model,))},
                "ln2": {"g": jnp.ones((c.d_model,)), "b": jnp.zeros((c.d_model,))},
                "attn": ({
                    "wqkv": jax.random.normal(
                        kk[0], (c.d_model, 3 * c.d_model)) * scale,
                    "wo": jax.random.normal(kk[3], (c.d_model, c.d_model)) * scale,
                } if c.fused_qkv else {
                    "wq": jax.random.normal(kk[0], (c.d_model, c.d_model)) * scale,
                    "wk": jax.random.normal(kk[1], (c.d_model, c.d_model)) * scale,
                    "wv": jax.random.normal(kk[2], (c.d_model, c.d_model)) * scale,
                    "wo": jax.random.normal(kk[3], (c.d_model, c.d_model)) * scale,
                }),
            }
            if c.moe is not None:
                blk["moe"] = init_moe_params(c.moe, kk[4], scale=scale)
            else:
                blk["mlp"] = {
                    "w_up": jax.random.normal(kk[4], (c.d_model, c.d_ff)) * scale,
                    "b_up": jnp.zeros((c.d_ff,)),
                    "w_down": jax.random.normal(kk[5], (c.d_ff, c.d_model)) * scale,
                    "b_down": jnp.zeros((c.d_model,)),
                }
            params["blocks"].append(blk)
        if c.scan_layers:
            # stacked storage: one leading L axis per leaf, scanned at
            # apply time — identical math, O(1) compile in depth
            params["blocks"] = jax.tree.map(
                lambda *xs: jnp.stack(xs), *params["blocks"])
        elif c.pipeline_stages > 1:
            # (S, L/S, ...) leaves: leading stage axis shards over ``stage``,
            # second axis is the static per-stage layer loop
            S = c.pipeline_stages
            lps = c.n_layers // S
            stages = [
                jax.tree.map(lambda *xs: jnp.stack(xs),
                             *params["blocks"][s * lps:(s + 1) * lps])
                for s in range(S)]
            params["blocks"] = jax.tree.map(
                lambda *xs: jnp.stack(xs), *stages)
        params = jax.tree.map(lambda a: a.astype(jnp.float32), params)
        return params

    def param_shardings(self, mesh: Mesh):
        """PartitionSpec pytree (Megatron column/row split over ``model``).
        The fused ``wqkv`` is split like any column-parallel leaf, in
        contiguous shares of its ``[q | k | v]`` columns: a share is not a
        set of heads, and ``_qkv`` gathers the cast leaf to compute by heads
        rather than store another column order than the unsharded model's.
        ``tok_emb`` is stored split on the embedding axis, not the vocabulary:
        50257 is odd and not padded, and jax refuses an uneven split. The head
        therefore gathers its cast whole and runs token-parallel over the
        model axis (``_head_operands``) in place of all-reducing logits."""
        has_tp = MODEL_AXIS in mesh.axis_names
        col = P(None, MODEL_AXIS) if has_tp else P()
        row = P(MODEL_AXIS, None) if has_tp else P()
        rep = P()

        has_ep = EXPERT_AXIS in mesh.axis_names

        def blk():
            d = {
                "ln1": {"g": rep, "b": rep}, "ln2": {"g": rep, "b": rep},
                "attn": ({"wqkv": col, "wo": row} if self.config.fused_qkv
                         else {"wq": col, "wk": col, "wv": col, "wo": row}),
            }
            if self.config.moe is not None:
                d["moe"] = moe_param_specs(EXPERT_AXIS if has_ep else None)
            else:
                d["mlp"] = {"w_up": col,
                            "b_up": P(MODEL_AXIS) if has_tp else rep,
                            "w_down": row, "b_down": rep}
            return d

        def _prepend(spec_tree, *lead):
            return jax.tree.map(lambda sp: P(*(lead + tuple(sp))), spec_tree,
                                is_leaf=lambda x: isinstance(x, P))

        if self.config.scan_layers:
            # stacked blocks: same per-leaf spec with a leading (layer)
            # axis left unsharded
            blocks_spec = _prepend(blk(), None)
        elif self.config.pipeline_stages > 1:
            # (S, L/S, ...): stage axis sharded, per-stage layer axis not;
            # per-leaf TP specs are dropped inside the pipeline (shard_map
            # owns the stage body — TP×PP composition is future work)
            blocks_spec = jax.tree.map(
                lambda sp: P(STAGE_AXIS, None), blk(),
                is_leaf=lambda x: isinstance(x, P))
        else:
            blocks_spec = [blk() for _ in range(self.config.n_layers)]
        spec = {
            "tok_emb": col, "pos_emb": rep,
            "ln_f": {"g": rep, "b": rep},
            "blocks": blocks_spec,
        }
        return jax.tree.map(lambda s: NamedSharding(mesh, s), spec,
                            is_leaf=lambda x: isinstance(x, P))

    # ----------------------------------------------------------------- forward
    def _ln(self, p, x):
        # layernorm statistics in f32 regardless of compute dtype
        with jax.named_scope("ln"):
            xf = x.astype(jnp.float32)
            mu, var = one_pass_moments(xf, -1, keepdims=True)
            y = (xf - mu) * lax.rsqrt(var + 1e-5)
            y = y * p["g"].astype(jnp.float32) + p["b"].astype(jnp.float32)
            return y.astype(x.dtype)

    def _qkv(self, p, x, mesh=None):
        """Project one (B, T, C) activation into (B, T, H, hd) q/k/v —
        shared by the training/scoring attention and the prefill path
        (which must cache exactly the k/v the full forward would see).

        On a ``model`` axis the fused leaf's stored share is no chip's own
        heads: ``(C, 3C)``, columns ``[q | k | v]``, split in contiguous
        shares (``param_shardings``), so of two chips one holds q and half of
        k, the other the rest. ``x @ wqkv`` then leaves each chip columns the
        attention wants on another, and (B, T, ·) activations cross the model
        axis for layout alone, forward and backward. Where the axis divides
        the heads the WEIGHT moves instead (``_wqkv_by_heads``: one gather of
        the cast leaf a layer, its transpose for the gradient), and the
        contraction gives each chip its own heads' q, k and v, laid out as
        ``_flash_attention`` and ``ring_attention`` take them. The stored
        layout stays ``[q | k | v]``: it is what the unsharded model, a
        checkpoint and whoever hands this model its weights agree on. With
        no ``mesh`` (decode, the pipeline body), no model axis, heads it does
        not divide, or the unfused leaves (whole heads a share already) the
        lines are what they were."""
        c = self.config
        b, t, _ = x.shape
        h, hd = c.n_heads, c.d_model // c.n_heads
        with jax.named_scope("attn_qkv"):
            if self._qkv_by_heads(p, mesh):
                qkv = jnp.einsum("btc,cshd->btshd", x,
                                 self._wqkv_by_heads(p["wqkv"], mesh))
                q, k, v = qkv[:, :, 0], qkv[:, :, 1], qkv[:, :, 2]
            elif "wqkv" in p:
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

    def _attn(self, p, x, mesh, return_kv: bool = False):
        c = self.config
        b, t, _ = x.shape
        q, k, v = self._qkv(p, x, mesh)
        if mesh is not None and SEQ_AXIS in mesh.axis_names:
            backend, why = "ring", "the mesh has a seq axis"
        else:
            backend = "flash" if _use_flash_attention(t) else "xla"
            why = _attention_policy(t)[1]
        # ``attention backend: flash | xla | ring: <reason>``
        self._say_once("attention backend", backend, why)
        with jax.named_scope("attn_core"):
            if backend == "ring":
                o = ring_attention(q, k, v, mesh, causal=c.causal)
            elif backend == "flash":
                o = self._flash_attention(q, k, v, mesh)
            else:
                o = _plain_attention(q, k, v, causal=c.causal)
        with jax.named_scope("attn_out"):
            out = o.reshape(b, t, c.d_model) @ p["wo"]
        if return_kv:
            return out, k, v
        return out

    def _qkv_by_heads(self, p, mesh) -> bool:
        """Whether ``_qkv`` reads the fused leaf by heads, decided from what
        is there to see (the leaf, the mesh's model axis, the head count) and
        logged as ``qkv layout: heads | columns: <reason>``, once a trace."""
        h = self.config.n_heads
        tp = 1 if mesh is None else axis_size(mesh, MODEL_AXIS)
        if "wqkv" not in p:
            layout, why = "columns", "wq, wk, wv are split in whole heads"
        elif tp == 1:
            layout, why = "columns", ("no mesh" if mesh is None
                                      else "no model axis")
        elif h % tp:
            layout, why = "columns", (f"{h} heads do not divide over the "
                                      f"model axis ({tp})")
        else:
            layout, why = "heads", f"{h} heads over the model axis ({tp})"
        self._say_once("qkv layout", layout, why)
        return layout == "heads"

    def _wqkv_by_heads(self, w, mesh):
        """The stored ``(C, 3C)`` leaf, columns ``[q | k | v]`` in contiguous
        shares over ``model``, as ``(C, 3, H, hd)`` with the heads over
        ``model``: every chip gathers the leaf whole and keeps its own heads'
        columns. Spelled as a ``shard_map`` so that the collective is this one
        and in ``w``'s dtype (the bfloat16 cast, not the float32 master), and
        so that its transpose is the reduce-scatter that hands the gradient
        back in the stored layout."""
        c = self.config
        h, hd = c.n_heads, c.d_model // c.n_heads
        own = h // axis_size(mesh, MODEL_AXIS)

        def gather(w_share):
            whole = lax.all_gather(w_share, MODEL_AXIS, axis=1, tiled=True)
            return lax.dynamic_slice_in_dim(
                whole.reshape(c.d_model, 3, h, hd),
                lax.axis_index(MODEL_AXIS) * own, own, axis=2)

        return shard_map(gather, mesh=mesh, in_specs=P(None, MODEL_AXIS),
                         out_specs=P(None, None, MODEL_AXIS, None))(w)

    def _say_once(self, subject, choice, why):
        """``<subject>: <choice>: <reason>``, once a trace (every layer asks;
        the trace's identity tells a new one)."""
        said = (jax.core.get_opaque_trace_state(), choice, why)
        if said != self._said.get(subject):
            self._said[subject] = said
            logging.getLogger(__name__).info("%s: %s: %s", subject, choice,
                                             why)

    def _flash_attention(self, q, k, v, mesh):
        """The Pallas kernels over (B, T, H, hd) as ``_qkv`` makes them (no
        transpose: the kernels read the (B, T, H·hd) array by slabs of
        lanes). A kernel has no partitioning rule, so on a mesh the call is
        wrapped in ``shard_map`` with the batch over ``data`` and the heads
        over ``model`` (the layout ``ring_attention`` uses). ``_qkv`` hands
        q, k and v over in that layout already (it moved the projection's
        weight to get there), so these specs move nothing; inside the
        pipeline body (``mesh=None``) the call is already per-shard."""
        from deeplearning4j_tpu.kernels.flash_attention import (
            flash_attention_bthd)
        fn = functools.partial(flash_attention_bthd, causal=self.config.causal)
        if mesh is None:
            return fn(q, k, v)
        dp, tp = axis_size(mesh, DATA_AXIS), axis_size(mesh, MODEL_AXIS)
        spec = P(DATA_AXIS if dp > 1 and q.shape[0] % dp == 0 else None, None,
                 MODEL_AXIS if tp > 1 and q.shape[2] % tp == 0 else None, None)
        return shard_map(fn, mesh=mesh, in_specs=(spec, spec, spec),
                         out_specs=spec, check_vma=False)(q, k, v)

    def _constrain(self, x):
        """Activation sharding hint: (B, T, C) → ('data', 'seq', None)."""
        if self.mesh is None:
            return x
        axes = [DATA_AXIS if DATA_AXIS in self.mesh.axis_names else None,
                SEQ_AXIS if SEQ_AXIS in self.mesh.axis_names else None, None]
        return lax.with_sharding_constraint(
            x, NamedSharding(self.mesh, P(*axes)))

    def _dropout(self, x, rng, i):
        if rng is None or self.config.dropout <= 0.0:
            return x
        keep = 1.0 - self.config.dropout
        mask = jax.random.bernoulli(jax.random.fold_in(rng, i), keep, x.shape)
        return jnp.where(mask, x / keep, 0.0).astype(x.dtype)

    def _zero_aux(self):
        """Per-block aux-telemetry zeros: (aux_loss, dropped_fraction,
        expert_fraction (E,)) — fixed pytree so lax.scan carries it."""
        c = self.config
        e = c.moe.num_experts if c.moe is not None else 0
        return (jnp.zeros((), jnp.float32), jnp.zeros((), jnp.float32),
                jnp.zeros((e,), jnp.float32))

    def _block_math(self, blk, x, rng, li, mesh):
        """One transformer block. ``mesh=None`` inside the pipeline body
        (sharding constraints/collectives are owned by shard_map there).
        Returns (x, aux) with aux = (moe_aux_loss, dropped_fraction,
        expert_fraction) — zeros for the dense FFN."""
        c = self.config
        a = self._attn(blk["attn"], self._ln(blk["ln1"], x), mesh)
        x = x + self._dropout(a, rng, 2 * li + 1)
        if mesh is not None:
            x = self._constrain(x)
        h = self._ln(blk["ln2"], x)
        aux = self._zero_aux()
        with jax.named_scope("mlp"):
            if c.moe is not None:
                y, stats = moe_ffn(blk["moe"], h, c.moe, mesh)
                aux = (stats["aux_loss"].astype(jnp.float32),
                       stats["dropped_fraction"].astype(jnp.float32),
                       stats["expert_fraction"].astype(jnp.float32))
            else:
                hdn = jax.nn.gelu(h @ blk["mlp"]["w_up"]
                                  + blk["mlp"]["b_up"])
                y = hdn @ blk["mlp"]["w_down"] + blk["mlp"]["b_down"]
        x = x + self._dropout(y, rng, 2 * li + 2)
        if mesh is not None:
            x = self._constrain(x)
        return x, aux

    def _apply_pipelined(self, params, x, rng):
        """GPipe the block stack over the ``stage`` mesh axis (micro-batch
        gradient accumulation comes from differentiating the schedule)."""
        from deeplearning4j_tpu.parallel.pipeline import gpipe
        c = self.config
        S, M = c.pipeline_stages, c.microbatches
        B, t, d = x.shape
        assert B % M == 0, f"batch {B} must divide into {M} microbatches"
        lps = c.n_layers // S

        def stage_fn(p_stage, h, mb_idx):
            stage = lax.axis_index(STAGE_AXIS)
            # per-micro-batch dropout keys — without the mb fold every
            # micro-batch would share one mask per layer
            rng_mb = None if rng is None else jax.random.fold_in(rng, mb_idx)
            for i in range(lps):
                blk = jax.tree.map(lambda a: a[i], p_stage)
                body = (lambda b, h_, li: self._block_math(
                    b, h_, rng_mb, li, mesh=None)[0])
                if c.remat:
                    body = _remat(body, c.remat_policy)
                h = body(blk, h, stage * lps + i)
            return h

        dp_ok = (DATA_AXIS in self.mesh.axis_names
                 and (B // M) % self.mesh.shape[DATA_AXIS] == 0)
        if DATA_AXIS in self.mesh.axis_names and not dp_ok \
                and self.mesh.shape[DATA_AXIS] > 1:
            import logging
            logging.getLogger(__name__).warning(
                "pipeline micro-batch size %d is not divisible by the "
                "data axis (%d) — activations will REPLICATE over data "
                "and data parallelism contributes no throughput",
                B // M, self.mesh.shape[DATA_AXIS])
        batch_ax = DATA_AXIS if dp_ok else None
        if c.pipeline_schedule == "1f1b":
            from deeplearning4j_tpu.parallel.pipeline import (
                pipeline_trunk_1f1b)
            run = pipeline_trunk_1f1b(stage_fn, self.mesh, S,
                                      batch_axis=batch_ax)
        else:
            run = gpipe(stage_fn, self.mesh, S, batch_axis=batch_ax)
        y = run(params["blocks"], x.reshape(M, B // M, t, d))
        return y.reshape(B, t, d)

    def _apply_trunk(self, params, tokens, rng):
        """Everything up to (and incl.) the final layernorm. Returns
        (hidden (B,T,D), casted tok_emb, aux dict) — the chunked-CE loss
        consumes the trunk directly so logits never materialize."""
        c = self.config
        t = tokens.shape[1]
        # mixed precision: f32 master params (init_params), compute in
        # c.dtype — the grads/updates stay f32 on the outside
        params = self._cast_params(params)
        with jax.named_scope("embed"):
            x = (jnp.take(params["tok_emb"], tokens, axis=0)
                 + params["pos_emb"][:t])
            x = self._dropout(x.astype(c.dtype), rng, 0)
        x = self._constrain(x)
        # dense (non-MoE) models carry NO aux through the layer stack: the
        # telemetry would be all-zero anyway, and threading it through the
        # lax.scan carry keeps dead adds alive in the compiled step
        dense = c.moe is None
        aux_total = self._zero_aux()

        if (c.pipeline_stages > 1 and self.mesh is not None
                and STAGE_AXIS in self.mesh.axis_names):
            x = self._apply_pipelined(params, x, rng)
        elif c.scan_layers:
            def scan_body(carry, blk_li):
                x, aux = carry if not dense else (carry, None)
                blk, li = blk_li
                body = (lambda b, x_: self._block_math(
                    b, x_, rng, li, self.mesh))
                if c.remat:
                    # a policy ("dots") keeps matmul outputs saved so the
                    # scan backward doesn't recompute the MXU work — the
                    # fix for the scan_layers ladder rung's HLO-temp OOM
                    body = _remat(body, c.remat_policy)
                x, a = body(blk, x)
                if dense:
                    return x, None
                return (x, jax.tree.map(jnp.add, aux, a)), None

            li_idx = jnp.arange(c.n_layers)
            init = x if dense else (x, aux_total)
            out, _ = lax.scan(scan_body, init, (params["blocks"], li_idx))
            x = out if dense else out[0]
            if not dense:
                aux_total = out[1]
        else:
            # plain list — or stage-stacked params with no stage mesh
            # (single-device eval/inference of a pipeline-trained model):
            # unstack and run the stack sequentially — same math, no
            # pipeline. One spelling with the decode path (_decode_blocks).
            blocks = self._decode_blocks(params)
            if c.remat:
                # recompute each block's activations in backward instead
                # of saving them: O(L·T·d) residuals shrink to O(T·d)
                body = _remat(
                    lambda b, x_, li: self._block_math(
                        b, x_, rng, li, self.mesh),
                    c.remat_policy, static_argnums=(2,))
                for li, blk in enumerate(blocks):
                    x, a = body(blk, x, li)
                    if not dense:
                        aux_total = jax.tree.map(jnp.add, aux_total, a)
            else:
                for li, blk in enumerate(blocks):
                    x, a = self._block_math(blk, x, rng, li, self.mesh)
                    if not dense:
                        aux_total = jax.tree.map(jnp.add, aux_total, a)
        x = self._ln(params["ln_f"], x)
        aux_loss, dropped, frac = aux_total
        n_moe = max(1, c.n_layers)        # per-layer means for telemetry
        return x, params["tok_emb"], {
            "moe_aux_loss": aux_loss,
            "moe_dropped_fraction": dropped / n_moe,
            "moe_expert_fraction": frac / n_moe}

    def _head_operands(self, x, emb):
        """``x`` (B, T, C) after ``ln_f`` and the cast ``tok_emb`` (V, C), laid
        out for the head's contraction. On a ``model`` axis the stored
        ``tok_emb`` is split on C, so ``x @ emb.T`` would leave every chip
        partial logits for the whole vocabulary and all-reduce (B, T, V)
        float32. Here the model axis joins the data axis for the rows of
        ``x`` (or the seq axis for its tokens, where the rows do not divide)
        and the cast embedding is gathered whole: the contraction is local
        and logits, loss and their gradient exist once, for a chip's own
        tokens. Where neither divides, or no model axis is there, both come
        back as they were."""
        log = logging.getLogger(__name__)
        tp = 1 if self.mesh is None else axis_size(self.mesh, MODEL_AXIS)
        if tp == 1:
            log.info("head layout: replicated: %s", "no mesh"
                     if self.mesh is None else "no model axis")
            return x, emb
        mesh = self.mesh
        rows = (DATA_AXIS,) if DATA_AXIS in mesh.axis_names else ()
        toks = (SEQ_AXIS,) if SEQ_AXIS in mesh.axis_names else ()
        b, t, _ = x.shape
        if b % (tp * axis_size(mesh, DATA_AXIS)) == 0:
            layout, rows = "rows", rows + (MODEL_AXIS,)
        elif t % (tp * axis_size(mesh, SEQ_AXIS)) == 0:
            layout, toks = "tokens", toks + (MODEL_AXIS,)
        else:
            log.info("head layout: replicated: neither %d rows nor %d "
                     "tokens divide over the model axis (%d)", b, t, tp)
            return x, emb
        log.info("head layout: %s split over the model axis (%d)", layout, tp)
        x = lax.with_sharding_constraint(
            x, NamedSharding(mesh, P(rows or None, toks or None, None)))
        return x, lax.with_sharding_constraint(emb, replicated(mesh))

    def apply(self, params, tokens, rng=None, return_aux=False):
        """tokens (B, T) int32 → logits (B, T, V). ``rng`` enables dropout
        (training mode); None = inference. ``return_aux``: also return the
        dict of auxiliary losses/stats (MoE load-balancing). On a ``model``
        axis the head runs token-parallel over it (``_head_operands``): the
        vocabulary itself is not split, 50257 is odd and not padded."""
        x, emb, aux = self._apply_trunk(params, tokens, rng)
        with jax.named_scope("head"):
            x, emb = self._head_operands(x, emb)
            logits = jnp.matmul(x, emb.T, preferred_element_type=jnp.float32)
        if return_aux:
            return logits, aux
        return logits

    # ------------------------------------------------------------------- loss
    def loss_fn(self, params, tokens, targets, rng=None, with_aux=False):
        """Mean token cross-entropy (plus the weighted MoE aux loss). On a
        ``model`` axis both spellings take their head operands from
        ``_head_operands``: each chip computes logits, log-sum-exp and their
        gradient for its own tokens only, and the mean reduces scalars."""
        c = self.config
        if c.ce_chunks:          # validated divisible in __post_init__
            # streamed CE: the (B,T,V) logits tensor never materializes
            # (kernels/chunked_ce — online logsumexp over vocab chunks)
            from deeplearning4j_tpu.kernels.chunked_ce import (
                chunked_softmax_xent)
            x, emb, aux = self._apply_trunk(params, tokens, rng)
            with jax.named_scope("loss"):       # head and loss in one
                x, emb = self._head_operands(x, emb)
                lm_loss = chunked_softmax_xent(x, emb, targets, c.ce_chunks)
        else:
            logits, aux = self.apply(params, tokens, rng=rng, return_aux=True)
            # fused cross-entropy: logsumexp − correct-logit avoids
            # materializing the (B, T, V) log-softmax in forward AND
            # backward — ~35% step-time win at V=8192 (HBM-traffic bound)
            with jax.named_scope("loss"):
                lse = jax.scipy.special.logsumexp(logits, axis=-1)
                correct = jnp.take_along_axis(logits, targets[..., None],
                                              axis=-1)[..., 0]
                lm_loss = jnp.mean(lse - correct)
        loss = lm_loss
        if self.config.moe is not None:
            loss = loss + self.config.moe_aux_weight * aux["moe_aux_loss"]
        if with_aux:
            return loss, {"lm_loss": lm_loss, **aux}
        return loss

    def make_train_step(self, optimizer, return_metrics: bool = False):
        """One whole-graph jitted step (fwd+bwd+allreduce+update). Pass
        ``rng`` to enable dropout. With ``return_metrics`` the step returns
        (params, opt_state, metrics-dict) where metrics carries the LM loss
        and the MoE aux loss separately (the training-history surface)."""
        if return_metrics:
            @functools.partial(jax.jit, donate_argnums=(0, 1))
            def step_m(params, opt_state, tokens, targets, rng=None):
                _cw.note_trace(TRAIN_STEP_FN, tokens)
                (loss, aux), grads = jax.value_and_grad(
                    self.loss_fn, has_aux=True)(
                    params, tokens, targets, rng, with_aux=True)
                with jax.named_scope("optimizer"):
                    updates, opt_state = optimizer.update(grads, opt_state,
                                                          params)
                    params = optax.apply_updates(params, updates)
                return params, opt_state, {"loss": loss, **aux}
            return step_m

        @functools.partial(jax.jit, donate_argnums=(0, 1))
        def step(params, opt_state, tokens, targets, rng=None):
            _cw.note_trace(TRAIN_STEP_FN, tokens)
            loss, grads = jax.value_and_grad(self.loss_fn)(
                params, tokens, targets, rng)
            with jax.named_scope("optimizer"):
                updates, opt_state = optimizer.update(grads, opt_state,
                                                      params)
                params = optax.apply_updates(params, updates)
            return params, opt_state, loss
        return step

    # ----------------------------------------- prefill/decode (generation)
    # The O(T²)-per-token naive alternative — re-running the full forward
    # for every emitted token — is what these two entry points replace:
    # ``prefill`` runs the causal trunk ONCE over the prompt and returns
    # the per-layer k/v it computed; ``decode_step_math`` then extends the
    # sequence one token at a time with single-query attention against
    # that cache (O(T) per token). Both are pure math functions — the
    # jit/bucket/sampling wrapper lives in models/generation.py
    # (DecodeEngine), and the full-seq flash kernel is prefill-only: the
    # decode step is XLA-native single-query attention and never reaches
    # the attention-backend policy.

    def _decode_blocks(self, params):
        """Per-layer block pytrees regardless of the trunk's storage
        layout (plain list, scan-stacked, or pipeline-stage-stacked) —
        generation walks layers explicitly either way."""
        c = self.config
        blocks = params["blocks"]
        if c.scan_layers:
            return [jax.tree.map(lambda a, i=i: a[i], blocks)
                    for i in range(c.n_layers)]
        if c.pipeline_stages > 1:
            S = c.pipeline_stages
            lps = c.n_layers // S
            return [jax.tree.map(lambda a, s=s, i=i: a[s][i], blocks)
                    for s in range(S) for i in range(lps)]
        return list(blocks)

    def _cast_params(self, params):
        """The trunk's mixed-precision cast (f32 master params, compute
        in ``config.dtype``) — prefill/decode must see the same weights
        the full forward computes with."""
        c = self.config
        if c.dtype == jnp.float32:
            return params
        with jax.named_scope("cast_params"):
            return jax.tree.map(
                lambda a: a.astype(c.dtype)
                if jnp.issubdtype(a.dtype, jnp.floating) else a, params)

    def _ffn(self, blk, h, mesh):
        """One block's feed-forward on (B, T, C) — the same math
        ``_block_math`` inlines (MoE stats dropped: generation has no
        aux loss to feed)."""
        with jax.named_scope("mlp"):
            if self.config.moe is not None:
                y, _ = moe_ffn(blk["moe"], h, self.config.moe, mesh)
                return y
            hdn = jax.nn.gelu(h @ blk["mlp"]["w_up"] + blk["mlp"]["b_up"])
            return hdn @ blk["mlp"]["w_down"] + blk["mlp"]["b_down"]

    # ------------------------------------------------- cache protocol
    # What ``models/generation.py::DecodeEngine`` asks of a model: which
    # cache features it has, how its paged cache is laid out and what a page
    # and a slot cost, a prefill that returns what an insert takes, the
    # insert, and the one-token paged decode. ``models/hybrid.py`` implements
    # the same block over two kinds of state.
    cache_features = frozenset(("dense", "int8_pages", "spec"))
    step_stats = ()                  # counts a decode step returns
    prefill_all_logits = True        # (B, T, V), not the last token's alone

    @property
    def max_positions(self) -> int:
        return self.config.max_len   # the learned position table

    @property
    def page_readers(self) -> int:
        return self.config.n_layers  # every layer reads its own pages

    def prefill_tail_rows(self, bucket: int) -> int:
        return int(bucket)           # every layer runs on every row

    def prefill_cache(self, params, tokens, last_idx):
        """Every position's logits and k/v; keys and values of the padding
        beyond ``last_idx`` are masked by position until overwritten."""
        return self.prefill(params, tokens)

    @staticmethod
    def entries_tokens(kv) -> int:
        return kv["k"].shape[2]

    @staticmethod
    def entries_row(kv, b: int):
        return {"k": kv["k"][:, b:b + 1], "v": kv["v"][:, b:b + 1]}

    def new_paged_cache(self, slots: int, n_pages: int, page_tokens: int,
                        quant: bool = False) -> Dict:
        return self.init_paged_cache(n_pages, page_tokens, quant=quant)

    def page_bytes(self, page_tokens: int, quant: bool = False) -> int:
        """Bytes of one page across all layers: k + v rows, int8 with one
        float32 scale each under ``quant``."""
        c = self.config
        per_row = c.d_model
        if quant:
            return c.n_layers * page_tokens * (2 * per_row + 8)
        return (c.n_layers * page_tokens * 2 * per_row
                * jnp.dtype(c.dtype).itemsize)

    def slot_state_bytes(self) -> int:
        return 0                     # the cache is pages alone

    def insert_paged(self, pool, kv, page_ids, slot, page_tokens: int):
        """(L, 1, Tb, H, hd) prefill k/v -> whole-page rows
        (:func:`pack_kv_pages`) written into the slot's physical pages of
        each layer's own donated array, in place: the program moves the
        joiner's pages and no other."""
        rows = {"k": kv["k"], "v": kv["v"]}
        if "k_scale" in pool:
            rows["k"], rows["k_scale"] = quantize_kv_rows(kv["k"])
            rows["v"], rows["v_scale"] = quantize_kv_rows(kv["v"])
        out = {}
        for leaf, arr in rows.items():
            packed = pack_kv_pages(arr, page_tokens)
            out[leaf] = [held.at[page_ids].set(packed[li])
                         for li, held in enumerate(pool[leaf])]
        return out

    def decode_paged(self, params, pool, tables, tokens, positions,
                     page_tokens: int):
        logits, pool = self.decode_window_paged(
            params, pool, tables, tokens[:, None], positions, page_tokens)
        return logits[:, 0], pool, None

    def init_cache(self, batch: int, max_len: int,
                   dtype: Optional[Any] = None) -> Dict:
        """Preallocated per-layer KV cache: ``{"k","v"}`` of shape
        (L, B, S, H, hd) in the compute dtype. S is a FIXED length bucket
        — decode writes are position-indexed ``dynamic_update_slice``s
        into it, so the executable never depends on how full it is."""
        c = self.config
        h, hd = c.n_heads, c.d_model // c.n_heads
        dt = dtype if dtype is not None else c.dtype
        shape = (c.n_layers, batch, max_len, h, hd)
        return {"k": jnp.zeros(shape, dt), "v": jnp.zeros(shape, dt)}

    def prefill(self, params, tokens) -> Tuple[Any, Dict]:
        """tokens (B, T) int32 → (logits (B, T, V) f32, kv) where kv is
        ``{"k","v"}: (L, B, T, H, hd)`` — the cache entries the causal
        forward computed for every prompt position. Same math as
        :meth:`apply` at inference (no dropout); the (T, T) attention
        itself routes through the normal backend policy (flash kernel
        eligible — this is the one generation phase where it pays)."""
        c = self.config
        params = self._cast_params(params)
        t = tokens.shape[1]
        with jax.named_scope("embed"):
            x = (jnp.take(params["tok_emb"], tokens, axis=0)
                 + params["pos_emb"][:t])
            x = x.astype(c.dtype)
        if self.mesh is not None:
            x = self._constrain(x)
        ks, vs = [], []
        for blk in self._decode_blocks(params):
            a, k, v = self._attn(blk["attn"], self._ln(blk["ln1"], x),
                                 self.mesh, return_kv=True)
            x = x + a
            if self.mesh is not None:
                x = self._constrain(x)
            x = x + self._ffn(blk, self._ln(blk["ln2"], x), self.mesh)
            if self.mesh is not None:
                x = self._constrain(x)
            ks.append(k)
            vs.append(v)
        x = self._ln(params["ln_f"], x)
        with jax.named_scope("head"):
            logits = jnp.matmul(x, params["tok_emb"].T,
                                preferred_element_type=jnp.float32)
        if not ks:
            # zero-layer trunk (an embedding-only speculative draft):
            # no attention, an empty (0, B, T, H, hd) cache
            h, hd = c.n_heads, c.d_model // c.n_heads
            b, t = tokens.shape
            empty = jnp.zeros((0, b, t, h, hd), c.dtype)
            return logits, {"k": empty, "v": empty}
        with jax.named_scope("kv_write"):
            return logits, {"k": jnp.stack(ks), "v": jnp.stack(vs)}

    def decode_step_math(self, params, cache, tokens, positions):
        """One autoregressive step for a whole slot batch.

        ``tokens`` (B,) int32 — the current token per slot; ``positions``
        (B,) int32 — where it sits in its sequence. Writes each slot's
        new k/v at its own position (vmapped ``dynamic_update_slice``)
        and runs single-query attention over the cache masked to
        ``pos <= positions`` — O(S) work, no (T, T) tensor, one fixed
        executable per cache shape. Returns (logits (B, V) f32, cache).
        """
        c = self.config
        params = self._cast_params(params)
        B = tokens.shape[0]
        S = cache["k"].shape[2]
        h, hd = c.n_heads, c.d_model // c.n_heads
        with jax.named_scope("embed"):
            x = (jnp.take(params["tok_emb"], tokens, axis=0)
                 + jnp.take(params["pos_emb"], positions, axis=0))
            x = x[:, None, :].astype(c.dtype)      # (B, 1, C)
        # keys at cache position p are attendable when p <= current pos
        # (the current token's k/v are written before attention below)
        mask = jnp.arange(S)[None, :] <= positions[:, None]   # (B, S)

        def write(cache_l, kv, p):                 # (S,H,hd), (H,hd), ()
            return lax.dynamic_update_slice(cache_l, kv[None], (p, 0, 0))

        new_k, new_v = [], []
        for li, blk in enumerate(self._decode_blocks(params)):
            q, k, v = self._qkv(blk["attn"], self._ln(blk["ln1"], x))
            with jax.named_scope("kv_write"):
                ck = jax.vmap(write)(cache["k"][li], k[:, 0], positions)
                cv = jax.vmap(write)(cache["v"][li], v[:, 0], positions)
            new_k.append(ck)
            new_v.append(cv)
            # single-query attention against the cache — the same
            # max-subtract/f32-exp softmax _plain_attention runs, so the
            # incremental logits match the full forward's to tolerance
            with jax.named_scope("attn_core"):
                s = (jnp.einsum("bhd,bshd->bhs", q[:, 0], ck)
                     / float(np.sqrt(hd)))
                s = jnp.where(mask[:, None, :], s,
                              jnp.asarray(-1e30, s.dtype))
                m = lax.stop_gradient(jnp.max(s, axis=-1, keepdims=True))
                p = jnp.exp((s - m).astype(jnp.float32))
                p = (p / jnp.sum(p, axis=-1, keepdims=True)).astype(x.dtype)
                o = jnp.einsum("bhs,bshd->bhd", p, cv)
            with jax.named_scope("attn_out"):
                x = x + (o.reshape(B, 1, c.d_model) @ blk["attn"]["wo"])
            x = x + self._ffn(blk, self._ln(blk["ln2"], x), None)
        x = self._ln(params["ln_f"], x)
        with jax.named_scope("head"):
            logits = jnp.matmul(x[:, 0], params["tok_emb"].T,
                                preferred_element_type=jnp.float32)
        if not new_k:           # zero-layer trunk: cache untouched
            return logits, {"k": cache["k"], "v": cache["v"]}
        with jax.named_scope("kv_write"):
            return logits, {"k": jnp.stack(new_k), "v": jnp.stack(new_v)}

    # ------------------------------------------ paged / windowed decode
    # The paged twin of the dense cache above: k/v live in a POOL of
    # fixed-size pages, one (n_pages, page_tokens, H·hd) array a layer,
    # shared by every slot, and a per-slot PAGE TABLE (B, pages_per_slot)
    # int32 maps logical page j of slot b to a physical pool page. Decode
    # writes scatter through the table, attention gathers through it — the
    # executable depends only on the (static) pool/table shapes, never
    # on which pages are allocated, so steady-state decode stays
    # zero-retrace exactly like the dense path. ``decode_step_math`` is
    # kept verbatim as the DL4J_TPU_KV_PAGE_TOKENS=0 kill-switch path.
    #
    # Both paged entry points take a W-token WINDOW per slot (W=1 is the
    # plain decode step; W=k+1 is the speculative-verify step): token j
    # of slot b sits at position ``positions[b]+j`` and attends cache
    # entries at positions <= its own — writing the whole window before
    # attention makes the in-window causal mask fall out of the same
    # ``pos <= query_pos`` comparison the dense step uses.

    def init_paged_cache(self, n_pages: int, page_tokens: int,
                         quant: bool = False,
                         dtype: Optional[Any] = None) -> Dict:
        """Page pool: ``{"k","v"}``, each a list of one (n_pages, P,
        H·hd) array a layer — a row is the token's heads side by side,
        whole 128-lane tiles at the served widths, and a layer's array is
        written in place by the programs it is donated to. Under
        ``quant`` the rows are int8 and ``{"k_scale","v_scale"}`` hold a
        layer's (n_pages, P) float32 scales (one scale per cached token
        row, stored page-wise: quantizing a row at write time needs no
        re-scan of the page it lands in). The LAST physical page is the
        trash page."""
        c = self.config
        shape = (n_pages, page_tokens, c.d_model)
        dt = jnp.int8 if quant else (dtype if dtype is not None else c.dtype)

        def layers(shape, dt):
            return [jnp.zeros(shape, dt) for _ in range(c.n_layers)]

        pool = {"k": layers(shape, dt), "v": layers(shape, dt)}
        if quant:
            pool["k_scale"] = layers(shape[:2], jnp.float32)
            pool["v_scale"] = layers(shape[:2], jnp.float32)
        return pool

    def _window_embed(self, params, tokens, positions):
        """(B, W) tokens at (B, W) positions → (B, W, C) activations +
        the (B, W, S-broadcastable) query positions."""
        c = self.config
        with jax.named_scope("embed"):
            x = (jnp.take(params["tok_emb"], tokens, axis=0)
                 + jnp.take(params["pos_emb"], positions, axis=0))
            return x.astype(c.dtype)

    def _window_attend(self, q, ck, cv, mask, hd):
        """Single-query attention generalized to a W-window: q (B, W,
        H, hd) against gathered caches (B, S, H, hd) under mask (B, W,
        S) — the same max-subtract/f32-exp softmax the dense step
        runs."""
        with jax.named_scope("attn_core"):
            s = jnp.einsum("bwhd,bshd->bwhs", q, ck) / float(np.sqrt(hd))
            s = jnp.where(mask[:, :, None, :], s,
                          jnp.asarray(-1e30, s.dtype))
            m = lax.stop_gradient(jnp.max(s, axis=-1, keepdims=True))
            p = jnp.exp((s - m).astype(jnp.float32))
            p = (p / jnp.sum(p, axis=-1, keepdims=True)).astype(q.dtype)
            return jnp.einsum("bwhs,bshd->bwhd", p, cv)

    def decode_window_math(self, params, cache, tokens, positions):
        """Dense-cache W-window decode: ``tokens`` (B, W) int32 with
        token j at position ``positions[b]+j``. Writes all W k/v rows,
        then attends each window token under the causal ``pos <=
        query_pos`` mask. Returns (logits (B, W, V) f32, cache). W=1
        matches :meth:`decode_step_math`; W>1 is the speculative-verify
        step on the dense kill-switch path."""
        c = self.config
        params = self._cast_params(params)
        B, W = tokens.shape
        S = cache["k"].shape[2]
        hd = c.d_model // c.n_heads
        pos_w = positions[:, None] + jnp.arange(W, dtype=jnp.int32)[None, :]
        x = self._window_embed(params, tokens, pos_w)
        mask = jnp.arange(S)[None, None, :] <= pos_w[:, :, None]  # (B,W,S)

        def write(cache_l, kv, p):        # (S,H,hd), (W,H,hd), (W,)
            return cache_l.at[p].set(kv)

        new_k, new_v = [], []
        for li, blk in enumerate(self._decode_blocks(params)):
            q, k, v = self._qkv(blk["attn"], self._ln(blk["ln1"], x))
            with jax.named_scope("kv_write"):
                ck = jax.vmap(write)(cache["k"][li], k, pos_w)
                cv = jax.vmap(write)(cache["v"][li], v, pos_w)
            new_k.append(ck)
            new_v.append(cv)
            o = self._window_attend(q, ck, cv, mask, hd)
            with jax.named_scope("attn_out"):
                x = x + (o.reshape(B, W, c.d_model) @ blk["attn"]["wo"])
            x = x + self._ffn(blk, self._ln(blk["ln2"], x), None)
        x = self._ln(params["ln_f"], x)
        with jax.named_scope("head"):
            logits = jnp.matmul(x, params["tok_emb"].T,
                                preferred_element_type=jnp.float32)
        if not new_k:           # zero-layer trunk: cache untouched
            return logits, {"k": cache["k"], "v": cache["v"]}
        with jax.named_scope("kv_write"):
            return logits, {"k": jnp.stack(new_k), "v": jnp.stack(new_v)}

    def decode_window_paged(self, params, pool, tables, tokens, positions,
                            page_tokens: int):
        """Paged W-window decode/verify: write the window's k/v rows
        into each layer's own (n_pages, P, H·hd) array through the
        per-slot page table — in place, the pool is donated and nothing
        is sliced out of it or stacked back — gather each slot's logical
        pages as a (B, S, H, hd) view, and attend under the same causal
        mask. ``tables`` (B, pages_per_slot) int32; quantized pools
        (``k_scale`` present) dequantize ON THE FLY inside the
        attention — int8 rows never round-trip through a dense f32
        cache. Returns (logits (B, W, V) f32, pool)."""
        c = self.config
        params = self._cast_params(params)
        B, W = tokens.shape
        P = int(page_tokens)
        S = tables.shape[1] * P
        h, hd = c.n_heads, c.d_model // c.n_heads
        quant = "k_scale" in pool
        pool = {name: list(held) for name, held in pool.items()}
        pos_w = positions[:, None] + jnp.arange(W, dtype=jnp.int32)[None, :]
        x = self._window_embed(params, tokens, pos_w)
        mask = jnp.arange(S)[None, None, :] <= pos_w[:, :, None]  # (B,W,S)
        # physical scatter coordinates of each window token's row. A
        # window near the cache end can carry positions past the last
        # logical page (the tail rows are never emitted); route those
        # writes to the TRASH page — by pool-layout convention the LAST
        # physical page, owned by no table row — instead of letting the
        # gather clamp corrupt a page the slot legitimately owns.
        bidx = jnp.arange(B, dtype=jnp.int32)[:, None]
        trash = 0
        if pool["k"]:           # a zero-layer trunk holds no page
            n_pages, _p, lanes = pool["k"][0].shape
            trash = n_pages - 1
            self._say_once(
                "paged cache",
                f"{len(pool['k'])} arrays of ({n_pages}, {P}, {lanes}) "
                "lanes", "rows and joiners written in place")
        in_range = pos_w < S
        with jax.named_scope("kv_write"):
            phys = jnp.where(
                in_range,
                tables[bidx, jnp.minimum(pos_w // P, tables.shape[1] - 1)],
                trash)                                           # (B, W)
            off = pos_w % P                                      # (B, W)

        def store(name, li, rows):
            """Write W rows per slot into layer ``li``'s array of pool
            ``name`` (+ its scale grid under quant), then gather every
            slot's pages back as a dequantized (B, S, H, hd) view."""
            with jax.named_scope("kv_write"):
                if quant:
                    rows, sc = quantize_kv_rows(rows)
                    scale = name + "_scale"
                    pool[scale][li] = pool[scale][li].at[phys, off].set(sc)
                pool[name][li] = pool[name][li].at[phys, off].set(
                    rows.reshape(B, W, c.d_model))
            with jax.named_scope("kv_gather"):
                view = pool[name][li][tables].reshape(B, S, h, hd)
                if quant:
                    gsc = pool[scale][li][tables].reshape(B, S)
                    view = (view.astype(jnp.float32)
                            * gsc[:, :, None, None]).astype(c.dtype)
            return view

        for li, blk in enumerate(self._decode_blocks(params)):
            q, k, v = self._qkv(blk["attn"], self._ln(blk["ln1"], x))
            ck = store("k", li, k)
            cv = store("v", li, v)
            o = self._window_attend(q, ck, cv, mask, hd)
            with jax.named_scope("attn_out"):
                x = x + (o.reshape(B, W, c.d_model) @ blk["attn"]["wo"])
            x = x + self._ffn(blk, self._ln(blk["ln2"], x), None)
        x = self._ln(params["ln_f"], x)
        with jax.named_scope("head"):
            logits = jnp.matmul(x, params["tok_emb"].T,
                                preferred_element_type=jnp.float32)
        return logits, pool


def make_sharded_lm(config: TransformerConfig, mesh: Mesh, optimizer=None,
                    seed: int = 0):
    """Build model + sharded params + opt state on the mesh."""
    optimizer = optimizer or optax.adamw(3e-4)
    model = TransformerLM(config, mesh)
    shardings = model.param_shardings(mesh)
    params = jax.device_put(model.init_params(jax.random.key(seed)), shardings)
    # the optimizer's moments placed like the parameters they belong to, the
    # rest replicated: ``jit(optimizer.init)`` alone leaves the whole state
    # on the first device, and a step fed from there compiles a second time
    # for its own outputs
    where = optax.tree_utils.tree_map_params(
        optimizer, lambda _, sharding: sharding,
        jax.eval_shape(optimizer.init, params), shardings,
        transform_non_params=lambda _: replicated(mesh))
    opt_state = jax.jit(optimizer.init, out_shardings=where)(params)
    return model, params, opt_state, optimizer
