from perfbench.layer_metrics._named import idle_named_pct as read  # noqa: F401
