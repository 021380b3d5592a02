"""Operations and bytes of the Phi-4-flash decoder as this chip holds it (all
of it), from shapes alone. The arithmetic a roofline share is divided by lives
here, with the benchmark, so that no later PR can move it.

Bytes are those of the information a step needs, in the types the
configuration states: bfloat16 weights (2 B) and cached rows (a row is the
keys and values of one position of one layer: 2 x key/value heads x head
numbers, 5,120 B at the published sizes), float32 Mamba-1 state (16 x 5,120 a
layer a slot), a bfloat16 convolution tail (3 x 5,120). ONE layer keeps paged
rows; it reads a slot's live rows once a step and writes one, and the seven
query-only layers behind it each read the same rows once more and write
nothing. A window layer reads its ring's rows and writes one. LayerNorm
gains and biases are left out of the parameter counts; the projections'
biases, the lambdas and the sub-norm's gain are in them.
"""
from __future__ import annotations

W = 2           # bytes a weight, and a cached number


def kinds(cfg):
    """The kind of every layer: ``mamba``, ``window``, ``full``, ``memory``
    (the gated unit) or ``cross`` (queries alone over the full layer's
    rows): with H = num_hidden_layers / 2, even layers are ``mamba`` up to H
    and ``memory`` behind it, odd layers ``window`` below H, ``full`` at
    H + 1 and ``cross`` behind it."""
    n = cfg["num_hidden_layers"]
    half = n // 2
    return ["mamba" if i % 2 == 0 and i <= half else
            "memory" if i % 2 == 0 else
            "window" if i < half else
            "full" if i == half + 1 else "cross" for i in range(n)]


def n_layers(cfg, kind=None) -> int:
    return sum(1 for k in kinds(cfg) if kind in (None, k))


def head_dim(cfg) -> int:
    return cfg["hidden_size"] // cfg["num_attention_heads"]


def kv_row(cfg) -> int:
    """Numbers of one position's keys and values in one layer."""
    return 2 * cfg["num_key_value_heads"] * head_dim(cfg)


def attention_params(cfg, own_kv: bool) -> int:
    """One differential attention: W_q and out_proj with their biases, the
    four lambda vectors and the sub-norm's gain; with keys and values of its
    own, their projection and its bias."""
    d, hd = cfg["hidden_size"], head_dim(cfg)
    return 2 * (d * d + d) + 6 * hd \
        + ((d + 1) * kv_row(cfg) if own_kv else 0)


def mamba_params(cfg) -> int:
    """One Mamba-1 mixer: in_proj, x_proj, dt_proj and its bias, A_log, D,
    the taps and their bias, out_proj."""
    d, C, N, r = (cfg["hidden_size"], cfg["mamba_d_inner"],
                  cfg["mamba_d_state"], cfg["mamba_dt_rank"])
    return (d * 2 * C + C * (r + 2 * N) + r * C + C + C * N + C
            + C * cfg["mamba_d_conv"] + C + C * d)


def memory_params(cfg) -> int:
    return 2 * cfg["hidden_size"] * cfg["mamba_d_inner"]


def ffn_params(cfg) -> int:
    return 3 * cfg["hidden_size"] * cfg["intermediate_size"]


def mixer_params(cfg) -> int:
    """Every layer's mixer."""
    return (n_layers(cfg, "mamba") * mamba_params(cfg)
            + (n_layers(cfg, "window") + n_layers(cfg, "full"))
            * attention_params(cfg, True)
            + n_layers(cfg, "cross") * attention_params(cfg, False)
            + n_layers(cfg, "memory") * memory_params(cfg))


def n_params(cfg) -> int:
    """Parameters held on this chip: the whole model, the embedding once."""
    return (mixer_params(cfg) + n_layers(cfg) * ffn_params(cfg)
            + cfg["vocab_size"] * cfg["hidden_size"])


def kv_row_bytes(cfg) -> int:
    return kv_row(cfg) * W


def mamba_state_bytes(cfg) -> int:
    """One slot's state of one Mamba-1 layer: S (float32) and the tail."""
    C = cfg["mamba_d_inner"]
    return C * cfg["mamba_d_state"] * 4 + (cfg["mamba_d_conv"] - 1) * C * W


def slot_state_bytes(cfg) -> int:
    """One slot's fixed state: the window layers' rings and the Mamba-1
    layers' states."""
    return (n_layers(cfg, "window") * cfg["sliding_window"]
            * kv_row_bytes(cfg)
            + n_layers(cfg, "mamba") * mamba_state_bytes(cfg))


def page_readers(cfg) -> int:
    """Layers that read the one paged layer's rows a step."""
    return n_layers(cfg, "full") + n_layers(cfg, "cross")


def ssm_step_bytes(cfg, active: float) -> float:
    """The Mamba-1 layers of one decode step: every active slot's state and
    tail read and written back, and the mixers' weights."""
    return n_layers(cfg, "mamba") * (
        2.0 * mamba_state_bytes(cfg) * active + W * mamba_params(cfg))


def attn_window_cache_bytes(cfg, rows: float, active: float) -> float:
    """The window layers' cache traffic of one decode step: the rings' rows
    read once a layer, one row a slot written."""
    return n_layers(cfg, "window") * kv_row_bytes(cfg) * (rows + active)


def attn_full_cache_bytes(cfg, live_tokens: float, active: float) -> float:
    """The ONE full layer's own cache traffic of a decode step: the live
    rows of every active slot read once, one row a slot written."""
    return n_layers(cfg, "full") * kv_row_bytes(cfg) * (live_tokens + active)


def attn_cross_cache_bytes(cfg, live_tokens: float, active: float) -> float:
    """The query-only layers' cache traffic of a decode step: the full
    layer's live rows read once more by each of them, nothing written."""
    return n_layers(cfg, "cross") * kv_row_bytes(cfg) * live_tokens


def dense_ffn_step_bytes(cfg) -> float:
    """The feed-forwards of one decode step, one a layer: their weights,
    whatever the slots hold."""
    return W * n_layers(cfg) * ffn_params(cfg)


def decode_step_bytes(cfg: dict, live_tokens: int, weight_bytes: int = 2,
                      kv_bytes: int = 2) -> float:
    """What a step reads whatever the slots: every weight once (the
    embedding as the head) and the live rows once a layer that reads them.
    It is given no slot count, so the rings and the states stay out and a
    share of it reads low, never over."""
    return weight_bytes * n_params(cfg) \
        + page_readers(cfg) * kv_row(cfg) * kv_bytes * live_tokens


def decode_step_flops(cfg: dict, live_tokens: int, active: int) -> float:
    """One decode step: two operations a weight a token (the embedding as
    the head); the Mamba-1 update and read (6 a number of state a slot); and,
    a pair of heads a row its layer reads, two maps of scores against a head
    and of weighted sums of a value twice as wide, two operations each
    (the published form; the padded pairs the program computes score twice
    as wide)."""
    hd = head_dim(cfg)
    pairs = cfg["num_attention_heads"] // 2
    a_row = pairs * 2 * (2 * hd + 2 * 2 * hd)
    state = n_layers(cfg, "mamba") * 6 * cfg["mamba_d_inner"] \
        * cfg["mamba_d_state"]
    return (2.0 * n_params(cfg) * active + state * active
            + a_row * (page_readers(cfg) * live_tokens
                       + n_layers(cfg, "window") * active
                       * cfg["sliding_window"]))


def train_flops_per_token(cfg: dict, seq_len: int) -> float:
    raise NotImplementedError(
        "no training cell of this family: HybridLM has no loss, backward or "
        "shardings, and what the configuration exercises is the cache and "
        "long generation, which only serving has (ISSUE 46)")
