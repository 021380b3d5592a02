"""Device seconds of the decode program's operations under ``gmu`` (the gated
memory units: two products and the gate on an earlier layer's scan output)
over those of all its operations, first chip, in percent."""
from perfbench.layer_metrics._phi4flash import share_pct


def read(ctx):
    return share_pct(ctx, ("gmu",))
