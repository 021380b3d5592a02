from perfbench.layer_metrics._named import unscoped_decode_pct as read  # noqa: F401
