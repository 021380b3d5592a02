"""Model FLOP/s utilization of the traced run: tokens per second x the
operations the forward and backward passes need per token (recomputation not
counted; perfbench/costs) / (chips x the chip's bf16 peak)."""
from perfbench import harness


def read(ctx):
    cell, dev = ctx["cell"], ctx["device"]
    peak = harness.peaks(dev["kind"])["flops_per_s_bf16"]
    per_token = cell.costs.train_flops_per_token(cell.config,
                                                 cell.traffic["seq_len"])
    return 100.0 * ctx["values"]["train_tok_s"] * per_token \
        / (dev["count"] * peak)
