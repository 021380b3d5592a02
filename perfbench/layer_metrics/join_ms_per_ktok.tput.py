from perfbench.layer_metrics._join import join_ms_per_ktok as read  # noqa: F401
