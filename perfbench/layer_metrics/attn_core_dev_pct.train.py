"""Device seconds of the train step's operations scoped ``attn_core``
(forward and backward: the attention kernels, or XLA's scores, softmax and
their gradients) over the device seconds of all its operations, first chip,
in percent."""
from perfbench.layer_metrics._named import scope_share_pct
from perfbench.layer_metrics._shared import TRAIN_MODULE


def read(ctx):
    return scope_share_pct(ctx, TRAIN_MODULE, ("attn_core",))
