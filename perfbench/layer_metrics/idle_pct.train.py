from perfbench.layer_metrics._shared import idle_pct as read  # noqa: F401
