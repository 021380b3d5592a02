"""The grouped-query attention layer's share of its roofline in a decode
step: the K and V rows of the live tokens (the engine's own count, 1024 B a
token) plus the layer's weights (``costs.gqa_step_bytes``) at the chip's peak
bytes a second, over the device seconds a step of ``gqa_proj``,
``gqa_attend`` and the layer's ``kv_gather`` and ``kv_write`` (the only
pages this family keeps)."""
from perfbench.layer_metrics._inner import roofline_pct, step_attr_mean
from perfbench.layer_metrics._nemotron import GQA, seconds_a_step


def read(ctx):
    live = step_attr_mean(ctx, "live_tokens")
    if live is None:
        return None
    cell = ctx["cell"]
    return roofline_pct(ctx, "gqa roofline",
                        cell.costs.gqa_step_bytes(cell.config, live),
                        seconds_a_step(ctx, GQA, ("kv_gather", "kv_write")))
