from perfbench.layer_metrics._named import kv_move_pct as read  # noqa: F401
