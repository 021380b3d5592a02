"""Device seconds of the decode program's operations under ``ffn_dense``
(the dense feed-forwards, two a double layer) over those of all its
operations, first chip, in percent."""
from perfbench.layer_metrics._longcat import share_pct


def read(ctx):
    return share_pct(ctx, ("ffn_dense",))
