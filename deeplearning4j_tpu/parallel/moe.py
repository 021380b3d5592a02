"""Mixture-of-experts with expert parallelism (SURVEY P7: ABSENT in the
reference — net-new TPU capability).

Switch-Transformer-style top-1 routing in the dense-dispatch formulation —
the TPU-canonical shape: routing becomes three einsums over a fixed-capacity
(tokens, experts, capacity) one-hot dispatch tensor, so shapes stay STATIC
under jit (no data-dependent gather/scatter), and sharding the expert axis
over the ``expert`` mesh dimension makes GSPMD insert the token all-to-alls
over ICI. Over-capacity tokens are dropped (their output is the residual
zero), exactly as in Switch/GShard.

Second half of the file: the routed experts a serving chip holds its share
of (``routed_experts_ffn``: k of many a token, no capacity, pairs sorted by
expert; one ``lax.ragged_dot`` a matrix, or, where a TPU program's rows fit
one row tile - a decode step -, one Pallas kernel for an expert's two
products, ``kernels/grouped_ffn.py``: :func:`expert_backend` decides).
"""
from __future__ import annotations

import dataclasses
import logging
from typing import Optional, Tuple

import jax
import jax.numpy as jnp
import numpy as np
from jax import lax
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

from deeplearning4j_tpu.parallel.mesh import EXPERT_AXIS, axis_size


@dataclasses.dataclass
class MoEConfig:
    d_model: Optional[int] = None   # None: filled in from the host model's
    d_ff: Optional[int] = None      # config (TransformerConfig.moe path)
    num_experts: int = 8
    capacity_factor: float = 1.25
    router_noise: float = 0.0       # jitter for load-balancing exploration
    top_k: int = 1                  # 1 = Switch; 2 = GShard top-2 routing
                                    # (renormalized gates, second choices
                                    # queue behind ALL first choices)

    def __post_init__(self):
        if self.top_k not in (1, 2):
            raise ValueError(f"top_k must be 1 or 2 (got {self.top_k})")


def _check_resolved(cfg: MoEConfig):
    if not cfg.d_model or not cfg.d_ff:
        raise ValueError(
            "MoEConfig.d_model/d_ff are unset — pass them explicitly, or "
            "hand the config to TransformerConfig(moe=...) which fills them "
            "from the host model")


def init_moe_params(cfg: MoEConfig, key, scale: float = 0.02):
    _check_resolved(cfg)
    kg, k1, k2 = jax.random.split(key, 3)
    E, d, f = cfg.num_experts, cfg.d_model, cfg.d_ff
    return {
        "Wg": jax.random.normal(kg, (d, E)) * scale,
        "W1": jax.random.normal(k1, (E, d, f)) * scale,
        "b1": jnp.zeros((E, f)),
        "W2": jax.random.normal(k2, (E, f, d)) * scale,
        "b2": jnp.zeros((E, d)),
    }


def moe_param_specs(expert_axis=None):
    """PartitionSpec tree for the MoE param leaves — the single source of the
    expert-sharding layout (router replicated, expert dim sharded)."""
    e = expert_axis
    return {"Wg": P(), "W1": P(e), "b1": P(e), "W2": P(e), "b2": P(e)}


def moe_param_shardings(cfg: MoEConfig, mesh: Mesh):
    """Expert-dim sharding over the ``expert`` mesh axis (router replicated)."""
    e = EXPERT_AXIS if EXPERT_AXIS in mesh.axis_names else None
    return jax.tree.map(lambda sp: NamedSharding(mesh, sp),
                        moe_param_specs(e),
                        is_leaf=lambda x: isinstance(x, P))


def moe_ffn(params, x, cfg: MoEConfig, mesh: Optional[Mesh] = None,
            rng=None):
    """Top-1 MoE FFN over (B, T, d). Returns (y, aux) where aux carries the
    Switch load-balancing loss and routing stats."""
    _check_resolved(cfg)
    B, T, d = x.shape
    E = cfg.num_experts
    G = B * T
    xt = x.reshape(G, d)

    logits = xt @ params["Wg"]                       # (G, E)
    if rng is not None and cfg.router_noise > 0:
        logits = logits + jax.random.uniform(
            rng, logits.shape, minval=1.0 - cfg.router_noise,
            maxval=1.0 + cfg.router_noise)
    probs = jax.nn.softmax(logits, axis=-1)
    expert_idx = jnp.argmax(probs, axis=-1)          # (G,) first choice
    gate = jnp.take_along_axis(probs, expert_idx[:, None], axis=-1)[:, 0]

    C = int(np.ceil(G / E * cfg.capacity_factor * cfg.top_k))
    onehot = jax.nn.one_hot(expert_idx, E, dtype=x.dtype)       # (G, E)
    # position of each token within its expert's queue
    pos = jnp.cumsum(onehot, axis=0) * onehot - 1.0             # (G, E)
    keep = (pos >= 0) & (pos < C)
    pos_oh = jax.nn.one_hot(pos.astype(jnp.int32), C, dtype=x.dtype)  # (G,E,C)
    dispatch = pos_oh * keep.astype(x.dtype)[..., None]          # (G, E, C)
    combine = dispatch * gate[:, None, None]
    n_routed = jnp.asarray(float(G), x.dtype)

    if cfg.top_k == 2:
        # GShard top-2: second choice = argmax with the first masked out;
        # gates renormalized over the two winners; second choices queue
        # BEHIND every first choice in each expert's capacity
        probs2 = probs * (1.0 - onehot)
        idx2 = jnp.argmax(probs2, axis=-1)
        gate2_raw = jnp.take_along_axis(probs2, idx2[:, None],
                                        axis=-1)[:, 0]
        denom = gate + gate2_raw + 1e-9
        g1 = gate / denom
        g2 = gate2_raw / denom
        onehot2 = jax.nn.one_hot(idx2, E, dtype=x.dtype)
        first_counts = jnp.sum(onehot, axis=0, keepdims=True)    # (1, E)
        pos2 = (jnp.cumsum(onehot2, axis=0) + first_counts) \
            * onehot2 - 1.0
        keep2 = (pos2 >= 0) & (pos2 < C)
        pos2_oh = jax.nn.one_hot(pos2.astype(jnp.int32), C, dtype=x.dtype)
        dispatch2 = pos2_oh * keep2.astype(x.dtype)[..., None]
        combine = (dispatch * g1[:, None, None]
                   + dispatch2 * g2[:, None, None])
        dispatch = dispatch + dispatch2
        n_routed = jnp.asarray(float(2 * G), x.dtype)

    # token → expert buffers; sharding hint puts E on the expert axis so
    # GSPMD routes via all-to-all over ICI
    ei = jnp.einsum("gec,gd->ecd", dispatch, xt)                 # (E, C, d)
    if mesh is not None and EXPERT_AXIS in mesh.axis_names:
        ei = lax.with_sharding_constraint(
            ei, NamedSharding(mesh, P(EXPERT_AXIS)))
    h = jax.nn.gelu(jnp.einsum("ecd,edf->ecf", ei, params["W1"])
                    + params["b1"][:, None, :])
    out_e = jnp.einsum("ecf,efd->ecd", h, params["W2"]) \
        + params["b2"][:, None, :]
    if mesh is not None and EXPERT_AXIS in mesh.axis_names:
        out_e = lax.with_sharding_constraint(
            out_e, NamedSharding(mesh, P(EXPERT_AXIS)))
    y = jnp.einsum("gec,ecd->gd", combine, out_e)                # (G, d)

    # Switch/GShard aux loss: E * Σ_e fraction_first_choice_e · mean_prob_e
    frac = jnp.mean(onehot, axis=0)
    mean_prob = jnp.mean(probs, axis=0)
    aux_loss = E * jnp.sum(frac * mean_prob)
    dropped = jnp.maximum(0.0, 1.0 - jnp.sum(dispatch) / n_routed)
    return y.reshape(B, T, d), {"aux_loss": aux_loss,
                                "dropped_fraction": dropped,
                                "expert_fraction": frac}


def moe_reference_dense(params, x, cfg: MoEConfig):
    """Unrouted check path: every token through its top-k expert(s) with no
    capacity limit (the semantics dispatch must match when nothing drops)."""
    B, T, d = x.shape
    xt = x.reshape(-1, d)
    probs = jax.nn.softmax(xt @ params["Wg"], axis=-1)

    def expert_out(idx):
        W1 = params["W1"][idx]        # (G, d, f)
        h = jax.nn.gelu(jnp.einsum("gd,gdf->gf", xt, W1)
                        + params["b1"][idx])
        return jnp.einsum("gf,gfd->gd", h, params["W2"][idx]) \
            + params["b2"][idx]

    idx = jnp.argmax(probs, axis=-1)
    gate = jnp.take_along_axis(probs, idx[:, None], axis=-1)[:, 0]
    if cfg.top_k == 1:
        y = expert_out(idx) * gate[:, None]
    else:
        probs2 = probs * (1.0 - jax.nn.one_hot(idx, cfg.num_experts,
                                               dtype=x.dtype))
        idx2 = jnp.argmax(probs2, axis=-1)
        gate2 = jnp.take_along_axis(probs2, idx2[:, None], axis=-1)[:, 0]
        denom = gate + gate2 + 1e-9
        y = expert_out(idx) * (gate / denom)[:, None] \
            + expert_out(idx2) * (gate2 / denom)[:, None]
    return y.reshape(B, T, d)


# --------------------------------------------------------------------------
# Routed experts, the chip's share: a router at published width, k a token,
# nothing dropped, a grouped product over the experts held here
# --------------------------------------------------------------------------
@dataclasses.dataclass(frozen=True)
class RoutedExpertsConfig:
    """A layer of many small experts of which this chip holds a contiguous
    range, and what its router may be. ``router_width`` is the published
    number of router outputs and is never cut: every token is scored against
    all of them and chooses ``top_k``; ``held = (first, count)`` says which
    of the experts live here (the deployment's configuration, as under expert
    parallelism). ``form`` is what an expert (and the shared expert)
    computes: ``swiglu``, a fused gate|up matrix and a down matrix, or
    ``relu2``, two matrices with a squared ReLU between them and no gate.

    The router: ``score`` is ``sigmoid`` (each output alone) or ``softmax``
    (over all ``router_width`` outputs); the chosen scores are the weights,
    divided by their sum where ``renormalize``, times ``scale``. ``shared``
    says whether one shared expert is added to every token. The last
    ``identity`` outputs of the router are identity experts (zero-compute
    experts): they hold no weights, what they "return" is the token itself,
    so a token's number of real experts varies from 0 to ``top_k``. They are
    computed where the token lives (no exchange is needed), on every chip of
    the deployment for its own tokens."""

    router_width: int
    top_k: int
    held: tuple                     # (first, count)
    scale: float = 1.0              # routed_scaling_factor
    renormalize: bool = True
    form: str = "swiglu"            # "swiglu" | "relu2"
    score: str = "sigmoid"          # "sigmoid" | "softmax"
    shared: bool = True
    identity: int = 0

    def __post_init__(self):
        first, count = self.held
        if not 0 <= self.identity < self.router_width:
            raise ValueError(f"identity {self.identity} outside the "
                             f"router's {self.router_width} outputs")
        if not (0 <= first and count >= 1
                and first + count <= self.router_width - self.identity):
            raise ValueError(f"held {self.held} outside the router's "
                             f"{self.router_width - self.identity} experts")
        if not 1 <= self.top_k <= self.router_width:
            raise ValueError(f"top_k {self.top_k} outside [1, "
                             f"{self.router_width}]")
        if self.form not in EXPERT_FORMS:
            raise ValueError(f"form {self.form!r} is none of "
                             f"{sorted(EXPERT_FORMS)}")
        if self.score not in ROUTER_SCORES:
            raise ValueError(f"score {self.score!r} is none of "
                             f"{sorted(ROUTER_SCORES)}")


#: form -> the name of the first matrix's leaf (the second is ``w_down``)
EXPERT_FORMS = {"swiglu": "w_gu", "relu2": "w_up"}
#: what turns the router's logits (T, router_width) into scores
ROUTER_SCORES = {"sigmoid": jax.nn.sigmoid,
                 "softmax": lambda z: jax.nn.softmax(z, axis=-1)}


def _activate(h, form: str, dtype):
    """What stands between an expert's two products: h (.., 2f) float32 ->
    (.., f) for ``swiglu`` (gate columns then up columns), h (.., f) ->
    (.., f) for ``relu2``."""
    if form == "swiglu":
        f = h.shape[-1] // 2
        return (jax.nn.silu(h[..., :f]) * h[..., f:]).astype(dtype)
    return jnp.square(jax.nn.relu(h)).astype(dtype)


def feed_forward(x, p, form: str = "swiglu"):
    """One feed-forward of ``form``: x (.., d), ``p`` its two matrices ->
    float32 (.., d)."""
    h = jnp.matmul(x, p[EXPERT_FORMS[form]],
                   preferred_element_type=jnp.float32)
    return jnp.matmul(_activate(h, form, x.dtype), p["w_down"],
                      preferred_element_type=jnp.float32)


def _on_tpu() -> bool:
    """Whether the program is traced for the TPU (as ``_attn`` asks before
    it takes the flash kernels)."""
    return jax.default_backend() == "tpu"


def expert_backend(rows: int, width: int, inner: int, n_first: int,
                   experts: int, itemsize: int) -> Tuple[str, str]:
    """(``grouped-ffn`` | ``ragged_dot``, why) for ``rows`` pair rows through
    ``experts`` experts ``width`` x ``inner`` (the first matrix ``n_first``
    times ``inner`` wide): the Pallas kernel (``kernels/grouped_ffn.py``)
    where the program is traced for the TPU, both widths are whole tiles of
    128 lanes and the rows are one row tile in VMEM, which a decode step's
    are and a prefill bucket's are not; the two ``lax.ragged_dot`` everywhere
    else. Consulted at trace time only. The kernel's module (and Pallas with
    it, a second of imports) is loaded by the first trace that may take it."""
    if not _on_tpu():
        return "ragged_dot", f"on {jax.default_backend()}"
    if width % 128 or inner % 128:
        return "ragged_dot", (f"experts {width} x {inner} are not whole "
                              "tiles of 128 lanes")
    from deeplearning4j_tpu.kernels import grouped_ffn as kernel
    if not kernel.fits_one_tile(rows, width, inner, n_first, experts,
                                itemsize):
        return "ragged_dot", (f"{rows} pair rows of {width} are more than "
                              "one row tile")
    return "grouped-ffn", f"{rows} pair rows of {width} in one row tile"


_said = {}


def _say_backend(choice, why):
    """``expert backend: <choice>: <reason>``, once a trace (every expert
    layer asks; ``models/transformer.py::_say_once``'s spelling)."""
    said = (jax.core.get_opaque_trace_state(), choice, why)
    if said != _said.get("expert backend"):
        _said["expert backend"] = said
        logging.getLogger(__name__).info("expert backend: %s: %s", choice,
                                         why)


def routed_experts_ffn(params, x, cfg: RoutedExpertsConfig, token_mask=None,
                       x_route=None):
    """x (T, d) -> (y (T, d) in x's dtype, stats int32[4], or [5] where the
    router has identity experts).

    ``s = score(x W_r)`` over all ``router_width`` outputs (what a router may
    be is said once, on :class:`RoutedExpertsConfig`); the ``top_k`` largest
    of ``s + b_select`` are chosen; their weights are ``s`` at the chosen,
    divided by their sum where the configuration renormalises, and scaled.
    The token-expert pairs that fall on held experts are sorted by expert and
    go through the experts' two matrices group by group; pairs on absent
    experts add nothing - what those experts would give is the other chips'
    part of the result. No pair on a held expert is ever dropped: there is no
    capacity. A pair on an identity expert adds its weight times the token
    itself, here, and is no row of the grouped product. The shared expert,
    where there is one, is added once.

    What computes the groups follows from what the trace can see
    (:func:`expert_backend`): one ``lax.ragged_dot`` a matrix, or, in a TPU
    program whose pair rows are one row tile (a decode step), one Pallas
    kernel for both products and the activation between them, which reads
    every expert that has a row once and keeps the hidden rows on the chip.
    Same rounding either way (bfloat16 operands, float32 sums, the activation
    rounded to x's dtype). Logged once a trace:
    ``expert backend: grouped-ffn | ragged_dot: <reason>``.

    ``token_mask`` (T,) marks the rows that carry a real
    token (a free decode slot routes nowhere and touches no expert).
    ``x_route`` (T, d), if given, is what the router scores instead of ``x``:
    the same rows before they were rounded to the experts' dtype. Choosing 8
    of 256 is a discrete decision, and a score rounded to bfloat16 flips it
    where the 8th and 9th are close; in float32 at highest precision the
    router costs microseconds.

    ``params``: ``w_router`` (d, E), ``b_select`` (E,), the experts' two
    matrices in ``cfg.form`` - ``w_gu`` (held, w, 2f: gate columns then up
    columns) or ``w_up`` (held, w, f), and ``w_down`` (held, f, w) -, and,
    where ``cfg.shared``, ``shared``, one feed-forward of the same form over
    the full width d.
    The experts' width w is d, or, where ``params`` holds the latent pair
    ``w_latent_in`` (d, w) and ``w_latent_out`` (w, d), the width of that
    latent space: every token is projected into it once before the experts,
    and the weighted sum of the chosen experts' outputs once out of it (the
    projection is linear, so the chips' shares still add up).
    ``stats``: [held experts with at least one pair, pairs on held experts,
    pairs routed anywhere (real tokens x ``top_k``), ``expert_visits``: how
    many times the kernel streamed an expert's weights - the first count
    again where each touched expert is read once; 0 where ``ragged_dot``
    ran] and, where ``cfg.identity``, the pairs on identity experts.
    """
    T, d = x.shape
    k = cfg.top_k
    first, count = cfg.held
    latent = "w_latent_in" in params
    if latent:
        with jax.named_scope("moe_latent"):
            u = jnp.matmul(x, params["w_latent_in"],
                           preferred_element_type=jnp.float32
                           ).astype(x.dtype)
    else:
        u = x
    with jax.named_scope("moe_route"):
        xr = x if x_route is None else x_route
        s = ROUTER_SCORES[cfg.score](jnp.matmul(
            xr, params["w_router"].astype(xr.dtype),
            precision=lax.Precision.HIGHEST,
            preferred_element_type=jnp.float32))
        _, idx = lax.top_k(s + params["b_select"].astype(jnp.float32), k)
        w = jnp.take_along_axis(s, idx, axis=-1)                 # (T, k)
        if cfg.renormalize:
            w = w / jnp.sum(w, axis=-1, keepdims=True)
        w = w * cfg.scale
        here = (idx >= first) & (idx < first + count)
        if token_mask is not None:
            here = here & token_mask[:, None]
        # absent pairs get the sentinel ``count`` and sort behind every group
        local = jnp.where(here, idx - first, count).reshape(-1)  # (T k,)
        order = jnp.argsort(local, stable=True)
        sizes = jnp.zeros((count + 1,), jnp.int32).at[local].add(1)[:count]
        n_held = jnp.sum(sizes)
        rows = u[order // k]                                     # (T k, w)
        valid = (jnp.arange(T * k) < n_held)[:, None]
        n_routed = k * (T if token_mask is None else jnp.sum(token_mask))
        counts = jnp.stack([jnp.sum(sizes > 0), n_held,
                            n_routed]).astype(jnp.int32)
        if cfg.identity:
            zero = idx >= cfg.router_width - cfg.identity
            if token_mask is not None:
                zero = zero & token_mask[:, None]
    w_first, w_down = params[EXPERT_FORMS[cfg.form]], params["w_down"]
    _count, inner, width = w_down.shape
    backend, why = expert_backend(T * k, width, inner,
                                  w_first.shape[2] // inner, count,
                                  w_down.dtype.itemsize)
    _say_backend(backend, why)
    with jax.named_scope("moe_experts"):
        if backend == "grouped-ffn":
            from deeplearning4j_tpu.kernels.grouped_ffn import grouped_ffn
            y, visits = grouped_ffn(rows, w_first, w_down, sizes, cfg.form)
        else:
            h = lax.ragged_dot(rows, w_first, sizes,
                               preferred_element_type=jnp.float32)
            y = lax.ragged_dot(_activate(h, cfg.form, x.dtype), w_down,
                               sizes, preferred_element_type=jnp.float32)
            visits = jnp.zeros((), jnp.int32)
        # rows behind the last group belong to no expert held here
        y = jnp.where(valid, y, 0.0)
        stats = jnp.concatenate([counts, visits[None]])
    if cfg.shared:
        with jax.named_scope("moe_shared"):
            shared = feed_forward(x, params["shared"], cfg.form)

    def total(routed):
        """The layer's result from the held experts' weighted sum."""
        if cfg.shared:
            routed = shared + routed
        if cfg.identity:
            routed = routed + same
        return routed.astype(x.dtype)

    with jax.named_scope("moe_combine"):
        inverse = jnp.zeros((T * k,), jnp.int32).at[order].set(
            jnp.arange(T * k, dtype=jnp.int32))
        pairs = y[inverse].reshape(T, k, -1)
        routed = jnp.einsum("tk,tkd->td", jnp.where(here, w, 0.0), pairs)
        if cfg.identity:
            # nested in ``moe_combine``, so that a reader of the ``moe_*``
            # scopes counts the identity pairs' weighted copy with them
            with jax.named_scope("moe_zero"):
                same = jnp.sum(jnp.where(zero, w, 0.0), axis=-1,
                               keepdims=True) * x.astype(jnp.float32)
                stats = jnp.concatenate(
                    [stats, jnp.sum(zero).astype(jnp.int32)[None]])
        if not latent:
            return total(routed), stats
    with jax.named_scope("moe_latent"):
        routed = jnp.matmul(routed.astype(x.dtype), params["w_latent_out"],
                            preferred_element_type=jnp.float32)
        return total(routed), stats
