"""Mean over the traced decode steps of ``pairs_zero`` over ``pairs_routed``
(attributes of span ``decode_step``): the share of a step's token-expert
pairs that fell on identity experts, which read no weights and are no row of
the grouped product; a third where the router is balanced (256 of 768)."""
from perfbench.layer_metrics._longcat import step_ratio_mean_pct


def read(ctx):
    return step_ratio_mean_pct(ctx, "pairs_zero", "pairs_routed")
