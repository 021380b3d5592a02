"""The dense feed-forwards' share of their roofline in a decode step: their
weights (``costs.dense_ffn_step_bytes``: two a layer, 453 MB each) at the
chip's peak bytes a second, over the device seconds a step of ``ffn_dense``.
Memory-bound: 64 rows against 226 M weights a sublayer."""
from perfbench.layer_metrics._inner import roofline_pct
from perfbench.layer_metrics._longcat import seconds_a_step


def read(ctx):
    seconds = seconds_a_step(ctx, ("ffn_dense",))
    if seconds is None:
        return None
    cell = ctx["cell"]
    return roofline_pct(ctx, "dense feed-forward roofline",
                        cell.costs.dense_ffn_step_bytes(cell.config), seconds)
