"""Operations and bytes of the LongCat-Flash double layer as this chip holds
it, from shapes alone. The arithmetic a roofline share is divided by lives
here, with the benchmark, so that no later PR can move it.

Bytes are those of the information a step needs, in the types the
configuration states: bfloat16 weights (2 B) and latent rows. A latent row
counts its 576 numbers (1152 B); that the program pads it to 640 for the
chip's tiles is the program's cost, not the roofline's. An identity expert
holds no weights and counts none.
"""
from __future__ import annotations

W = 2           # bytes a weight
SUBLAYERS = 2   # attentions, and dense feed-forwards, a published layer


def mla_params(cfg) -> int:
    """One latent attention: the query bottleneck's two matrices, the
    key/value bottleneck's two, the output projection."""
    d, h = cfg["hidden_size"], cfg["num_attention_heads"]
    dn, dr, dv = (cfg["qk_nope_head_dim"], cfg["qk_rope_head_dim"],
                  cfg["v_head_dim"])
    rq, R = cfg["q_lora_rank"], cfg["kv_lora_rank"]
    return (d * rq + rq * h * (dn + dr) + d * (R + dr) + R * h * (dn + dv)
            + h * dv * d)


def dense_params(cfg) -> int:
    return 3 * cfg["hidden_size"] * cfg["ffn_hidden_size"]


def expert_params(cfg) -> int:
    return 3 * cfg["hidden_size"] * cfg["expert_ffn_hidden_size"]


def router_params(cfg) -> int:
    return cfg["hidden_size"] * cfg["router_width"]


def layer_params(cfg) -> int:
    """One double layer as held: outside its experts, and the held ones."""
    return (SUBLAYERS * (mla_params(cfg) + dense_params(cfg))
            + router_params(cfg)
            + cfg["n_routed_experts"] * expert_params(cfg))


def n_params(cfg) -> int:
    """Parameters held on this chip (norm gains and the selection bias left
    out)."""
    return (cfg["num_layers"] * layer_params(cfg)
            + 2 * cfg["vocab_size"] * cfg["hidden_size"])


def latent_row_bytes(cfg) -> int:
    return (cfg["kv_lora_rank"] + cfg["qk_rope_head_dim"]) * 2


def moe_step_bytes(cfg, experts_touched: float) -> float:
    """The expert layers of one decode step as the ``moe_*`` scopes cover
    them: the held experts that got a token (summed over layers) and every
    layer's router. No shared expert exists; identity pairs read nothing."""
    return W * (experts_touched * expert_params(cfg)
                + cfg["num_layers"] * router_params(cfg))


def mla_step_bytes(cfg, live_tokens: float) -> float:
    """The latent attentions of one decode step, two a layer: the latent
    rows of the live tokens once a sublayer, and the sublayers' weights."""
    return cfg["num_layers"] * SUBLAYERS * (
        latent_row_bytes(cfg) * live_tokens + W * mla_params(cfg))


def dense_ffn_step_bytes(cfg) -> float:
    """The dense feed-forwards of one decode step, two a layer: their
    weights, whatever the slots hold."""
    return W * cfg["num_layers"] * SUBLAYERS * dense_params(cfg)


def decode_touched_bytes(cfg, experts_touched: float, active: float,
                         live_tokens: float) -> float:
    """The whole step: the weights it actually touches (every attention and
    dense feed-forward, routers, the held experts that got a token, the
    head, one embedding row a slot) and the live latent rows."""
    d = cfg["hidden_size"]
    return (moe_step_bytes(cfg, experts_touched)
            + mla_step_bytes(cfg, live_tokens) + dense_ffn_step_bytes(cfg)
            + W * (cfg["vocab_size"] * d + active * d))


def decode_step_bytes(cfg: dict, live_tokens: int, weight_bytes: int = 2,
                      kv_bytes: int = 2) -> float:
    """An upper figure that knows nothing of the routing: every held expert
    read. ``decode_touched_bytes`` is what a roofline share divides by."""
    experts = cfg["num_layers"] * cfg["n_routed_experts"]
    return decode_touched_bytes(cfg, experts, 0, live_tokens)


def decode_step_flops(cfg: dict, live_tokens: int, active: int) -> float:
    """One decode step: two operations a weight a token for what every token
    passes (attentions, dense feed-forwards, router, head, and the chosen
    experts that are held: on average ``k x held / router_width`` a layer;
    an identity pair is one multiply-add a number of the token, counted
    with the router), and absorbed attention over the live rows (scores
    against 576 numbers and the weighted sum of 512, two operations each, a
    head, a sublayer)."""
    per_token = (cfg["num_layers"] * (
        SUBLAYERS * (mla_params(cfg) + dense_params(cfg))
        + router_params(cfg) + expert_params(cfg) * cfg["moe_topk"]
        * cfg["n_routed_experts"] / cfg["router_width"])
        + cfg["vocab_size"] * cfg["hidden_size"])
    attend = cfg["num_layers"] * SUBLAYERS * 2 * cfg["num_attention_heads"] \
        * (2 * cfg["kv_lora_rank"] + cfg["qk_rope_head_dim"])
    return 2.0 * per_token * active + attend * live_tokens


def train_flops_per_token(cfg: dict, seq_len: int) -> float:
    raise NotImplementedError(
        "no training cell of this family: at 16 B a parameter the 639 M a "
        "layer outside its experts are 10.2 GB before one expert, so no cut "
        "with four layers fits (ISSUE 38), and HybridLM has no training "
        "side")
