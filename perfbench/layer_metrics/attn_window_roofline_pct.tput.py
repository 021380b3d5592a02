"""The window layers' cache traffic's share of its roofline in a decode
step: the rings' rows read once a layer (the engine's own count,
``window_rows`` of span ``decode_step``) plus one row a slot written
(``costs.attn_window_cache_bytes``) at the chip's peak bytes a second, over
the device seconds a step of ``swa_attend`` and ``swa_write``. The layers'
weights and projections are in neither side."""
from perfbench.layer_metrics._inner import roofline_pct, step_attr_mean
from perfbench.layer_metrics._laguna import seconds_a_step


def read(ctx):
    rows, active = (step_attr_mean(ctx, a) for a in ("window_rows", "active"))
    if not rows or active is None:
        return None
    cell = ctx["cell"]
    return roofline_pct(
        ctx, "window attention roofline",
        cell.costs.attn_window_cache_bytes(cell.config, rows, active),
        seconds_a_step(ctx, "swa", ("swa_attend", "swa_write")))
