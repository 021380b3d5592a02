"""Device seconds of the decode program's operations under ``mla_attend`` or
``mla_proj`` over those of all its operations, first chip, in percent."""
from perfbench.layer_metrics._inner import share_pct


def read(ctx):
    return share_pct(ctx, ("mla_attend", "mla_proj"))
