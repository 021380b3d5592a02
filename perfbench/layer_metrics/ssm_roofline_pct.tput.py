"""The Mamba-2 layers' share of their roofline in a decode step: every active
slot's state read and written back plus the mixers' weights
(``costs.ssm_step_bytes``) at the chip's peak bytes a second, over the device
seconds a step of the four ``ssm_*`` scopes. Memory-bound: 4.19 MB of state a
layer a slot against 6 operations a number of it."""
from perfbench.layer_metrics._inner import roofline_pct, step_attr_mean
from perfbench.layer_metrics._nemotron import SSM, seconds_a_step


def read(ctx):
    active = step_attr_mean(ctx, "active")
    if active is None:
        return None
    cell = ctx["cell"]
    return roofline_pct(ctx, "ssm roofline",
                        cell.costs.ssm_step_bytes(cell.config, active),
                        seconds_a_step(ctx, SSM))
