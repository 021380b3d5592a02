from perfbench.layer_metrics._join import joins_per_admit_mean as read  # noqa: F401
