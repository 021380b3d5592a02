"""Device seconds of the decode program's operations under ``xattn_proj`` or
``xattn_attend`` (the layers that project queries alone and attend over
another layer's paged rows, their differential subtraction and sub-norm with
them) over those of all its operations, first chip, in percent."""
from perfbench.layer_metrics._phi4flash import CROSS, share_pct


def read(ctx):
    return share_pct(ctx, CROSS)
