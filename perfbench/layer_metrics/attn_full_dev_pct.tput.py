"""Device seconds of the decode program's operations under the full kind's
scopes (``gqa_proj`` with its rotation and gate, ``gqa_attend``) and on its
pages (``kv_write``, ``kv_gather``) over those of all its operations, first
chip, in percent."""
from perfbench.layer_metrics._laguna import share_pct


def read(ctx):
    return share_pct(ctx, "gqa")
