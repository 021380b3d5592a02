from perfbench.layer_metrics._join import join_max_ms as read  # noqa: F401
