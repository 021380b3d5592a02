"""Device seconds of the decode program's operations under the window kind's
scopes (``swa_proj`` with its rotation and gate, ``swa_attend``,
``swa_write``) over those of all its operations, first chip, in percent."""
from perfbench.layer_metrics._laguna import share_pct


def read(ctx):
    return share_pct(ctx, "swa")
