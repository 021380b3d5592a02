"""Device seconds of the decode program's operations under ``moe_route``,
``moe_experts``, ``moe_shared`` or ``moe_combine`` over those of all its
operations, first chip, in percent."""
from perfbench.layer_metrics._inner import MOE, share_pct


def read(ctx):
    return share_pct(ctx, MOE)
