from perfbench.layer_metrics._named import prefill_stall_pct as read  # noqa: F401
