"""Device time of all-reduce / all-gather / reduce-scatter /
collective-permute operations during which no other operation ran on that
chip, over the step's device time, on the worst chip."""
from perfbench.layer_metrics._shared import TRAIN_MODULE


def read(ctx):
    tr = ctx.get("trace")
    return None if tr is None else tr.exposed_collective_share(TRAIN_MODULE)
