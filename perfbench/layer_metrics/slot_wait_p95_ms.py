"""Enqueue to slot granted (``slot_wait`` spans), 95th percentile."""
from perfbench.layer_metrics._shared import span_quantile_ms


def read(ctx):
    return span_quantile_ms(ctx, "slot_wait", 0.95)
