"""Median device duration of the jitted train step's program in the trace."""
from perfbench.layer_metrics._shared import TRAIN_MODULE, module_median_ms


def read(ctx):
    return module_median_ms(ctx, TRAIN_MODULE)
