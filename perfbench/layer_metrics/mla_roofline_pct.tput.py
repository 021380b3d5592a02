"""The MLA layer's share of its roofline in a decode step: the latent rows
of the live tokens (the engine's own count, 1152 B a row) plus the mixer's
weights (``costs.mla_step_bytes``) at the chip's peak bytes a second, over
the device seconds a step of ``mla_attend``, ``mla_proj`` and the layer's
``kv_gather``."""
from perfbench.layer_metrics._inner import (roofline_pct, seconds_a_step,
                                            step_attr_mean)


def read(ctx):
    live = step_attr_mean(ctx, "live_tokens")
    if live is None:
        return None
    cell = ctx["cell"]
    return roofline_pct(ctx, "mla roofline",
                        cell.costs.mla_step_bytes(cell.config, live),
                        seconds_a_step(ctx, ("mla_attend", "mla_proj"),
                                       ("kv_gather",)))
