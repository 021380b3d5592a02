from perfbench.layer_metrics._shared import decode_step_p50_ms as read  # noqa: F401
