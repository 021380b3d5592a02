from perfbench.layer_metrics._named import unscoped_train_pct as read  # noqa: F401
