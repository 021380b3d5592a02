from perfbench.layer_metrics._named import loop_host_ms_p50 as read  # noqa: F401
