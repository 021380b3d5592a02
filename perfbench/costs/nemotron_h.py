"""Operations and bytes of the Nemotron-H block as this chip holds it, from
shapes alone. The arithmetic a roofline share is divided by lives here, with
the benchmark, so that no later PR can move it.

Bytes are those of the information a step needs, in the types the
configuration states: bfloat16 weights (2 B), float32 Mamba-2 state (4 B),
bfloat16 convolution tail and K/V rows (2 key/value heads x 128 x K and V =
1024 B a token).
"""
from __future__ import annotations

W = 2           # bytes a weight


def _count(cfg, letter) -> int:
    return cfg["layers_run"].count(letter)


def _conv_dim(cfg) -> int:
    return (cfg["mamba_num_heads"] * cfg["mamba_head_dim"]
            + 2 * cfg["n_groups"] * cfg["ssm_state_size"])


def mamba_params(cfg) -> int:
    """One Mamba-2 mixer: in_proj [z | xBC | dt], the taps, out_proj."""
    d, h = cfg["hidden_size"], cfg["mamba_num_heads"]
    di = h * cfg["mamba_head_dim"]
    return (d * (di + _conv_dim(cfg) + h)
            + cfg["conv_kernel"] * _conv_dim(cfg) + di * d)


def gqa_params(cfg) -> int:
    d, hd = cfg["hidden_size"], cfg["head_dim"]
    return (2 * d * cfg["num_attention_heads"] * hd
            + 2 * d * cfg["num_key_value_heads"] * hd)


def expert_params(cfg) -> int:
    return 2 * cfg["moe_latent_size"] * cfg["moe_intermediate_size"]


def shared_params(cfg) -> int:
    return cfg["n_shared_experts"] * 2 * cfg["hidden_size"] \
        * cfg["moe_shared_expert_intermediate_size"]


def latent_params(cfg) -> int:
    return 2 * cfg["hidden_size"] * cfg["moe_latent_size"]


def router_params(cfg) -> int:
    return cfg["hidden_size"] * cfg["router_width"]


def n_params(cfg) -> int:
    """Parameters held on this chip (norm gains, biases, A_log and D left
    out)."""
    return (_count(cfg, "M") * mamba_params(cfg)
            + _count(cfg, "*") * gqa_params(cfg)
            + _count(cfg, "E") * (cfg["n_routed_experts"] * expert_params(cfg)
                                  + shared_params(cfg) + latent_params(cfg)
                                  + router_params(cfg))
            + 2 * cfg["vocab_size"] * cfg["hidden_size"])


def slot_state_bytes(cfg) -> int:
    """One slot's fixed state over all Mamba-2 layers: S and the 3-row
    tail."""
    s = cfg["mamba_num_heads"] * cfg["mamba_head_dim"] * cfg["ssm_state_size"]
    return _count(cfg, "M") * (s * 4 + (cfg["conv_kernel"] - 1)
                               * _conv_dim(cfg) * 2)


def kv_row_bytes(cfg) -> int:
    return 2 * cfg["num_key_value_heads"] * cfg["head_dim"] * 2


def moe_step_bytes(cfg, experts_touched: float) -> float:
    """The expert layers of one decode step as the four ``moe_*`` scopes
    cover them: the experts that got a token (summed over layers), and every
    layer's shared expert and router. The latent pair is ``moe_latent``'s."""
    return W * (experts_touched * expert_params(cfg)
                + _count(cfg, "E") * (shared_params(cfg)
                                      + router_params(cfg)))


def ssm_step_bytes(cfg, active: float) -> float:
    """The Mamba-2 layers of one decode step: every active slot's state read
    and written back, and the mixers' weights."""
    return 2.0 * slot_state_bytes(cfg) * active \
        + W * _count(cfg, "M") * mamba_params(cfg)


def gqa_step_bytes(cfg, live_tokens: float) -> float:
    """The attention layers of one decode step: the K and V rows of the live
    tokens once a layer, and the layers' weights."""
    return _count(cfg, "*") * (kv_row_bytes(cfg) * live_tokens
                               + W * gqa_params(cfg))


def decode_touched_bytes(cfg, experts_touched: float, active: float,
                         live_tokens: float) -> float:
    """The whole step: the weights it actually touches (every mixer, routers,
    shared experts, latent pairs, the experts that got a token, the head, one
    embedding row a slot), the slots' state twice, the live K/V rows."""
    d = cfg["hidden_size"]
    return (moe_step_bytes(cfg, experts_touched)
            + ssm_step_bytes(cfg, active) + gqa_step_bytes(cfg, live_tokens)
            + W * (_count(cfg, "E") * latent_params(cfg)
                   + cfg["vocab_size"] * d + active * d))


def decode_step_bytes(cfg: dict, live_tokens: int, weight_bytes: int = 2,
                      kv_bytes: int = 2) -> float:
    """An upper figure that knows nothing of the routing: every held expert
    read. ``decode_touched_bytes`` is what a roofline share divides by."""
    experts = _count(cfg, "E") * cfg["n_routed_experts"]
    return decode_touched_bytes(cfg, experts, 0, live_tokens)


def decode_step_flops(cfg: dict, live_tokens: int, active: int) -> float:
    """One decode step: two operations a weight a token for what every token
    passes (mixers, router, latent pair, shared expert, head, and the chosen
    experts that are held: on average ``k x held / published`` a layer), the
    state update and read (6 P N a head a slot) and attention over the live
    rows (scores and the weighted sum, two operations each, a query head)."""
    per_token = (_count(cfg, "M") * mamba_params(cfg)
                 + _count(cfg, "*") * gqa_params(cfg)
                 + _count(cfg, "E") * (
                     router_params(cfg) + latent_params(cfg)
                     + shared_params(cfg) + expert_params(cfg)
                     * cfg["num_experts_per_tok"] * cfg["n_routed_experts"]
                     / cfg["router_width"])
                 + cfg["vocab_size"] * cfg["hidden_size"])
    state = _count(cfg, "M") * 6 * cfg["mamba_num_heads"] \
        * cfg["mamba_head_dim"] * cfg["ssm_state_size"]
    attend = _count(cfg, "*") * 4 * cfg["num_attention_heads"] \
        * cfg["head_dim"]
    return 2.0 * per_token * active + state * active + attend * live_tokens


def train_flops_per_token(cfg: dict, seq_len: int) -> float:
    raise NotImplementedError(
        "no training cell of this family: 16 B a parameter fits no cut "
        "within the floors (PERF.md, section 4), and HybridLM has no "
        "training side")
