"""Operations and bytes of the Kimi-Linear block as this chip holds it, from
shapes alone. The arithmetic a roofline share is divided by lives here, with
the benchmark, so that no later PR can move it.

Bytes are those of the information a step needs, in the types the
configuration states: bfloat16 weights (2 B), float32 KDA state (4 B),
bfloat16 convolution tail and latent rows. A latent row counts its 576
numbers (1152 B); that the program pads it to 640 for the chip's tiles is the
program's cost, not the roofline's.
"""
from __future__ import annotations

W = 2           # bytes a weight


def _kinds(cfg):
    lin = cfg["linear_attn_config"]
    n = cfg["num_hidden_layers"]
    kda = [i for i in lin["kda_layers"] if i <= n]
    mla = [i for i in lin["full_attn_layers"] if i <= n]
    dense = [i for i in range(1, n + 1) if i <= cfg["first_k_dense_replace"]]
    moe = [i for i in range(1, n + 1) if i > cfg["first_k_dense_replace"]]
    return kda, mla, dense, moe


def kda_params(cfg) -> int:
    """One KDA mixer: q, k, v, o; the two low-rank gates; beta; the taps."""
    d, lin, r = cfg["hidden_size"], cfg["linear_attn_config"], \
        cfg["kda_gate_rank"]
    hk = lin["num_heads"] * lin["head_dim"]
    return (4 * d * hk + 2 * (d * r + r * hk) + d * lin["num_heads"]
            + lin["short_conv_kernel_size"] * 3 * hk)


def mla_params(cfg) -> int:
    d, h = cfg["hidden_size"], cfg["num_attention_heads"]
    dn, dr, dv, R = (cfg["qk_nope_head_dim"], cfg["qk_rope_head_dim"],
                     cfg["v_head_dim"], cfg["kv_lora_rank"])
    return d * h * (dn + dr) + d * (R + dr) + R * h * (dn + dv) + h * dv * d


def expert_params(cfg) -> int:
    return 3 * cfg["hidden_size"] * cfg["moe_intermediate_size"]


def dense_params(cfg) -> int:
    return 3 * cfg["hidden_size"] * cfg["intermediate_size"]


def router_params(cfg) -> int:
    return cfg["hidden_size"] * cfg["router_width"]


def n_params(cfg) -> int:
    """Parameters held on this chip (norm gains and biases left out)."""
    kda, mla, dense, moe = _kinds(cfg)
    return (len(kda) * kda_params(cfg) + len(mla) * mla_params(cfg)
            + len(dense) * dense_params(cfg)
            + len(moe) * ((cfg["num_experts"] + cfg["num_shared_experts"])
                          * expert_params(cfg) + router_params(cfg))
            + 2 * cfg["vocab_size"] * cfg["hidden_size"])


def slot_state_bytes(cfg) -> int:
    """One slot's fixed state over all KDA layers: S and the 3-row tail."""
    lin = cfg["linear_attn_config"]
    h, k = lin["num_heads"], lin["head_dim"]
    kda = _kinds(cfg)[0]
    return len(kda) * (h * k * k * 4
                       + (lin["short_conv_kernel_size"] - 1) * 3 * h * k * 2)


def latent_row_bytes(cfg) -> int:
    return (cfg["kv_lora_rank"] + cfg["qk_rope_head_dim"]) * 2


def moe_step_bytes(cfg, experts_touched: float) -> float:
    """The expert layers of one decode step: the experts that got a token
    (summed over layers), and every layer's shared expert and router."""
    moe = _kinds(cfg)[3]
    return W * (experts_touched * expert_params(cfg)
                + len(moe) * (cfg["num_shared_experts"] * expert_params(cfg)
                              + router_params(cfg)))


def kda_step_bytes(cfg, active: float) -> float:
    """The KDA layers of one decode step: every active slot's state read and
    written back, and the mixers' weights."""
    kda = _kinds(cfg)[0]
    return 2.0 * slot_state_bytes(cfg) * active \
        + W * len(kda) * kda_params(cfg)


def mla_step_bytes(cfg, live_tokens: float) -> float:
    """The MLA layers of one decode step: the latent rows of the live tokens
    once a layer, and the mixers' weights."""
    mla = _kinds(cfg)[1]
    return len(mla) * (latent_row_bytes(cfg) * live_tokens
                       + W * mla_params(cfg))


def decode_touched_bytes(cfg, experts_touched: float, active: float,
                         live_tokens: float) -> float:
    """The whole step: the weights it actually touches (every mixer, the
    dense layer, routers, shared experts, the experts that got a token, the
    head, one embedding row a slot), the slots' state twice, the live latent
    rows."""
    _kda, _mla, dense, _moe = _kinds(cfg)
    d = cfg["hidden_size"]
    return (moe_step_bytes(cfg, experts_touched)
            + kda_step_bytes(cfg, active) + mla_step_bytes(cfg, live_tokens)
            + W * (len(dense) * dense_params(cfg)
                   + cfg["vocab_size"] * d + active * d))


def decode_step_bytes(cfg: dict, live_tokens: int, weight_bytes: int = 2,
                      kv_bytes: int = 2) -> float:
    """An upper figure that knows nothing of the routing: every held expert
    read. ``decode_touched_bytes`` is what a roofline share divides by."""
    experts = len(_kinds(cfg)[3]) * cfg["num_experts"]
    return decode_touched_bytes(cfg, experts, 0, live_tokens)


def decode_step_flops(cfg: dict, live_tokens: int, active: int) -> float:
    """One decode step: two operations a weight a token for what every token
    passes (mixers, dense layer, router, shared expert, head, and the chosen
    experts that are held: on average ``k x held / published`` a layer), the
    state update (8 K V a head a slot) and absorbed attention over the live
    rows (scores against 576 numbers and the weighted sum of 512, two
    operations each, a head)."""
    kda, mla, dense, moe = _kinds(cfg)
    lin = cfg["linear_attn_config"]
    per_token = (len(kda) * kda_params(cfg) + len(mla) * mla_params(cfg)
                 + len(dense) * dense_params(cfg)
                 + len(moe) * (router_params(cfg) + expert_params(cfg) * (
                     cfg["num_shared_experts"] + cfg["num_experts_per_token"]
                     * cfg["num_experts"] / cfg["router_width"]))
                 + cfg["vocab_size"] * cfg["hidden_size"])
    state = len(kda) * 8 * lin["num_heads"] * lin["head_dim"] ** 2
    attend = len(mla) * 2 * cfg["num_attention_heads"] * (
        2 * cfg["kv_lora_rank"] + cfg["qk_rope_head_dim"])
    return 2.0 * per_token * active + state * active + attend * live_tokens


def train_flops_per_token(cfg: dict, seq_len: int) -> float:
    raise NotImplementedError(
        "no training cell of this family: the backward of the expert layer "
        "and of the chunked scan are not written (PERF.md, section 7)")
