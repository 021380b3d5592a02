from perfbench.layer_metrics._join import join_fetch_share_pct as read  # noqa: F401
