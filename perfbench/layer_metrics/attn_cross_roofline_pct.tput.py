"""The query-only layers' cache traffic's share of its roofline in a decode
step: the paged layer's live rows read once more by each of them (the
engine's own count, ``live_tokens`` of span ``decode_step``;
``costs.attn_cross_cache_bytes``), nothing written, at the chip's peak bytes a
second, over the device seconds a step of ``xattn_attend``. This is the share
of its roofline of the page walk as these layers use it; their weights and
projections are in neither side."""
from perfbench.layer_metrics._inner import roofline_pct, step_attr_mean
from perfbench.layer_metrics._phi4flash import seconds_a_step


def read(ctx):
    live, active = (step_attr_mean(ctx, a) for a in ("live_tokens", "active"))
    cell = ctx["cell"]
    if not live or active is None or not hasattr(cell.costs,
                                                 "attn_cross_cache_bytes"):
        return None
    return roofline_pct(
        ctx, "query-only attention roofline",
        cell.costs.attn_cross_cache_bytes(cell.config, live, active),
        seconds_a_step(ctx, ("xattn_attend",)))
