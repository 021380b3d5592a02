"""The expert layers' share of their roofline in a decode step: the bytes of
the experts that got a token (the engine's own count), of the shared experts
and of the routers (``costs.moe_step_bytes``) at the chip's peak bytes a
second, over the device seconds a step of the four ``moe_*`` scopes.
Memory-bound: at 2 tokens an expert the operations take microseconds."""
from perfbench.layer_metrics._inner import (MOE, roofline_pct,
                                            seconds_a_step, step_attr_mean)


def read(ctx):
    touched = step_attr_mean(ctx, "experts_touched")
    if touched is None:
        return None
    cell = ctx["cell"]
    return roofline_pct(ctx, "moe roofline",
                        cell.costs.moe_step_bytes(cell.config, touched),
                        seconds_a_step(ctx, MOE))
