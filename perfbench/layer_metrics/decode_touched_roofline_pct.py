"""The whole decode step's share of its roofline, counting the weights it
actually touched: ``costs.decode_touched_bytes`` of the traced steps' mean
``experts_touched``, ``active`` and ``live_tokens`` (attributes of span
``decode_step``) at the chip's peak bytes a second, over the median device
duration of ``jit__decode_paged``. None where the step reports no
``experts_touched`` (a model without routed experts: ``decode_roofline_pct``
is its metric)."""
from perfbench.layer_metrics._inner import roofline_pct, step_attr_mean
from perfbench.layer_metrics._shared import DECODE_MODULE


def read(ctx):
    tr = ctx.get("trace")
    touched = step_attr_mean(ctx, "experts_touched")
    if tr is None or touched is None:
        return None
    cell = ctx["cell"]
    step_bytes = cell.costs.decode_touched_bytes(
        cell.config, touched, step_attr_mean(ctx, "active"),
        step_attr_mean(ctx, "live_tokens"))
    return roofline_pct(ctx, "decode roofline (touched)", step_bytes,
                        tr.module_median(DECODE_MODULE))
