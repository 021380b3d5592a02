from perfbench.layer_metrics._named import live_tokens_mean as read  # noqa: F401
