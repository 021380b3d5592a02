from perfbench.layer_metrics._join import join_hold_dev_pct as read  # noqa: F401
