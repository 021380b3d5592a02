"""Mean over the traced decode steps of the span attribute
``experts_touched``: held experts that got at least one token, summed over
the expert layers (of layers x held possible)."""
from perfbench.layer_metrics._inner import step_attr_mean


def read(ctx):
    return step_attr_mean(ctx, "experts_touched")
