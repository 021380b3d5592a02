"""The full layers' cache traffic's share of its roofline in a decode step:
the live rows read once a layer (the engine's own count, ``live_tokens`` of
span ``decode_step``) plus one row a slot written
(``costs.attn_full_cache_bytes``) at the chip's peak bytes a second, over the
device seconds a step of ``gqa_attend`` and of the pages' own operations
(``kv_write``; ``kv_gather`` where the kernel is not taken). The layers'
weights and projections are in neither side."""
from perfbench.layer_metrics._inner import roofline_pct, step_attr_mean
from perfbench.layer_metrics._laguna import seconds_a_step


def read(ctx):
    live, active = (step_attr_mean(ctx, a) for a in ("live_tokens", "active"))
    if not live or active is None or not hasattr(
            ctx["cell"].costs, "attn_full_cache_bytes"):
        return None
    cell = ctx["cell"]
    return roofline_pct(
        ctx, "full attention roofline",
        cell.costs.attn_full_cache_bytes(cell.config, live, active),
        seconds_a_step(ctx, "gqa", ("gqa_attend",)))
