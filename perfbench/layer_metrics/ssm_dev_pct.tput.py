"""Device seconds of the decode program's operations under ``ssm_proj``,
``ssm_conv``, ``ssm_state`` or ``ssm_out`` (the Mamba-2 layers: projections,
convolution, the recurrence with its state read and write, gated norm and
output) over those of all its operations, first chip, in percent."""
from perfbench.layer_metrics._nemotron import SSM, share_pct


def read(ctx):
    return share_pct(ctx, SSM)
