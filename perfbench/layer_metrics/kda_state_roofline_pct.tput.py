"""The KDA layers' share of their roofline in a decode step: every active
slot's state read and written back plus the mixers' weights
(``costs.kda_step_bytes``) at the chip's peak bytes a second, over the device
seconds a step of ``kda_state`` and ``kda_proj``."""
from perfbench.layer_metrics._inner import (roofline_pct, seconds_a_step,
                                            step_attr_mean)


def read(ctx):
    active = step_attr_mean(ctx, "active")
    if active is None:
        return None
    cell = ctx["cell"]
    return roofline_pct(ctx, "kda roofline",
                        cell.costs.kda_step_bytes(cell.config, active),
                        seconds_a_step(ctx, ("kda_state", "kda_proj")))
