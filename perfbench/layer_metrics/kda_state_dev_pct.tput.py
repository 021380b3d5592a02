"""Device seconds of the decode program's operations under ``kda_state``
(the recurrence with its state read and write) over those of all its
operations, first chip, in percent."""
from perfbench.layer_metrics._inner import share_pct


def read(ctx):
    return share_pct(ctx, ("kda_state",))
