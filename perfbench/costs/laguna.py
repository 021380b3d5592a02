"""Operations and bytes of the Laguna layers as this chip holds them, from
shapes alone. The arithmetic a roofline share is divided by lives here, with
the benchmark, so that no later PR can move it.

Bytes are those of the information a step needs, in the types the
configuration states: bfloat16 weights (2 B) and cached rows (a row is the
keys and values of one position of one layer: 2 x key/value heads x head_dim
numbers, 4 KB at the published sizes). A full layer reads the rows of a
slot's whole context, a window layer those of its last ``sliding_window``
positions and no other; both write one row a slot a step. Norm gains and the
selection bias are left out of the parameter counts.
"""
from __future__ import annotations

W = 2           # bytes a weight, and a cached number


def layers(cfg):
    """[(attention kind, feed-forward kind, query heads)] of the layers that
    are run: the first ``num_hidden_layers`` entries of the published
    lists."""
    n = cfg["num_hidden_layers"]
    return list(zip(cfg["layer_types"][:n], cfg["mlp_layer_types"][:n],
                    cfg["num_attention_heads_per_layer"][:n]))


def n_layers(cfg, attention=None, ffn=None) -> int:
    return sum(1 for a, f, _h in layers(cfg)
               if attention in (None, a) and ffn in (None, f))


def attention_params(cfg, heads: int) -> int:
    """One attention of ``heads`` query heads: W_q, W_k and W_v, W_o, and
    the gate's one column a head."""
    d, hd = cfg["hidden_size"], cfg["head_dim"]
    return (2 * d * heads * hd + 2 * d * cfg["num_key_value_heads"] * hd
            + d * heads)


def all_attention_params(cfg) -> int:
    return sum(attention_params(cfg, h) for _a, _f, h in layers(cfg))


def dense_params(cfg) -> int:
    return 3 * cfg["hidden_size"] * cfg["intermediate_size"]


def expert_params(cfg) -> int:
    return 3 * cfg["hidden_size"] * cfg["moe_intermediate_size"]


def shared_params(cfg) -> int:
    return 3 * cfg["hidden_size"] * cfg["shared_expert_intermediate_size"]


def router_params(cfg) -> int:
    return cfg["hidden_size"] * cfg["num_experts"]


def n_params(cfg) -> int:
    """Parameters held on this chip."""
    return (all_attention_params(cfg)
            + n_layers(cfg, ffn="dense") * dense_params(cfg)
            + n_layers(cfg, ffn="sparse") * (
                cfg["num_experts"] * expert_params(cfg) + shared_params(cfg)
                + router_params(cfg))
            + 2 * cfg["vocab_size"] * cfg["hidden_size"])


def kv_row_bytes(cfg) -> int:
    """One position's keys and values in one layer."""
    return 2 * cfg["num_key_value_heads"] * cfg["head_dim"] * W


def window_rows(cfg, active: float) -> float:
    """Rows the window layers' rings hold for ``active`` slots whose
    contexts are all longer than the window (every prompt of the cell's mix
    is): ``sliding_window`` a slot."""
    return active * cfg["sliding_window"]


def attn_full_cache_bytes(cfg, live_tokens: float, active: float) -> float:
    """The full layers' cache traffic of one decode step: the live rows of
    every active slot read once a layer, one row a slot written."""
    return n_layers(cfg, "full_attention") * kv_row_bytes(cfg) \
        * (live_tokens + active)


def attn_window_cache_bytes(cfg, rows: float, active: float) -> float:
    """The window layers' cache traffic of one decode step: the rings' rows
    read once a layer, one row a slot written."""
    return n_layers(cfg, "sliding_attention") * kv_row_bytes(cfg) \
        * (rows + active)


def moe_step_bytes(cfg, experts_touched: float) -> float:
    """The expert layers of one decode step as the ``moe_*`` scopes cover
    them: the experts that got a token (summed over layers), and every
    expert layer's shared expert and router."""
    return W * (experts_touched * expert_params(cfg)
                + n_layers(cfg, ffn="sparse") * (shared_params(cfg)
                                                 + router_params(cfg)))


def decode_touched_bytes(cfg, experts_touched: float, active: float,
                         live_tokens: float) -> float:
    """The whole step: the weights it actually touches (every attention,
    the dense feed-forward, routers and shared experts, the experts that got
    a token, the head, one embedding row a slot) and the cache it reads and
    writes (the full layers' live rows, the window layers' rings taken as
    ``active`` x ``sliding_window``)."""
    d = cfg["hidden_size"]
    return (moe_step_bytes(cfg, experts_touched)
            + W * (all_attention_params(cfg)
                   + n_layers(cfg, ffn="dense") * dense_params(cfg)
                   + cfg["vocab_size"] * d + active * d)
            + attn_full_cache_bytes(cfg, live_tokens, active)
            + attn_window_cache_bytes(cfg, window_rows(cfg, active), active))


def decode_step_bytes(cfg: dict, live_tokens: int, weight_bytes: int = 2,
                      kv_bytes: int = 2) -> float:
    """An upper figure that knows nothing of the routing or of the slots:
    every expert read, no ring. ``decode_touched_bytes`` is what a roofline
    share divides by."""
    experts = n_layers(cfg, ffn="sparse") * cfg["num_experts"]
    return decode_touched_bytes(cfg, experts, 0, live_tokens)


def decode_step_flops(cfg: dict, live_tokens: int, active: int) -> float:
    """One decode step: two operations a weight a token for what every
    token passes (attentions, the dense feed-forward, routers, shared
    experts, the chosen experts, the head), and two products a query head
    over the rows its layer reads (scores against ``head_dim`` numbers and
    the weighted sum of as many, two operations each)."""
    per_token = (all_attention_params(cfg)
                 + n_layers(cfg, ffn="dense") * dense_params(cfg)
                 + n_layers(cfg, ffn="sparse") * (
                     router_params(cfg) + shared_params(cfg)
                     + cfg["num_experts_per_tok"] * expert_params(cfg))
                 + cfg["vocab_size"] * cfg["hidden_size"])
    attend = 0.0
    for kind, _f, heads in layers(cfg):
        rows = live_tokens if kind == "full_attention" \
            else window_rows(cfg, active)
        attend += 4.0 * heads * cfg["head_dim"] * rows
    return 2.0 * per_token * active + attend


def train_flops_per_token(cfg: dict, seq_len: int) -> float:
    raise NotImplementedError(
        "no training cell of this family: HybridLM has no loss, backward or "
        "shardings, and what the configuration exercises is the cache, which "
        "only serving has (ISSUE 43)")
