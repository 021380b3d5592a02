"""Operations and bytes of the GPT-2 block, from shapes alone.

The arithmetic a utilization or a roofline share is divided by lives here,
with the benchmark, so that no later PR can move it. Copied in form from
``bench.py`` (6 N + 6 L T d per trained token, causal), which PERF.md lists
for deletion.
"""
from __future__ import annotations


def _sizes(cfg):
    d = cfg["n_embd"]
    return (cfg["n_layer"], d, cfg.get("n_inner") or 4 * d,
            cfg["vocab_size"], cfg["n_positions"])


def n_params(cfg: dict) -> int:
    """Parameters as the program holds them (no attention biases)."""
    L, d, f, V, T = _sizes(cfg)
    block = 4 * d + d * 3 * d + d * d + d * f + f + f * d + d
    return V * d + T * d + L * block + 2 * d


def train_flops_per_token(cfg: dict, seq_len: int) -> float:
    """Forward and backward, recomputation not counted: 6 FLOPs for each
    parameter that a matmul reads (the tied head counts once, the position
    table is a lookup and its 1 M rows are left in as bench.py did), plus
    causal attention: QK^T and PV are 2 * 2 * T * d a token forward when
    every key is read, half of that under the causal mask, three times that
    with the backward pass: 6 L T d."""
    L, d, _f, _V, _T = _sizes(cfg)
    return 6.0 * n_params(cfg) + 6.0 * L * seq_len * d


def decode_step_flops(cfg: dict, live_tokens: int, active: int) -> float:
    """One decode step: every matmul parameter twice for each active slot,
    and single-query attention over the keys and values that are live."""
    L, d, _f, _V, _T = _sizes(cfg)
    return 2.0 * n_params(cfg) * active + 4.0 * L * d * live_tokens


def decode_step_bytes(cfg: dict, live_tokens: int, weight_bytes: int = 2,
                      kv_bytes: int = 2) -> float:
    """The least a decode step must move: the weights once, in the type they
    are computed in, and the keys and values of the live tokens once."""
    L, d, _f, _V, _T = _sizes(cfg)
    return float(n_params(cfg) * weight_bytes
                 + 2 * L * d * kv_bytes * live_tokens)


def kv_bytes_per_token(cfg: dict, kv_bytes: int = 2) -> int:
    L, d, _f, _V, _T = _sizes(cfg)
    return 2 * L * d * kv_bytes
