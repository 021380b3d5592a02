"""Bytes of cache the engine holds a live token: the mean, over the window's
``decode_step`` spans, of ``cache_bytes`` (pages in use x page bytes + active
slots x a slot's fixed state) over ``live_tokens``. Where a window layer
keeps its last rows and no more this is the full layers' bytes a token and
the rings' share; where every layer keeps every row it is every layer's."""
from perfbench.layer_metrics._laguna import cache_bytes_per_live_token


def read(ctx):
    return cache_bytes_per_live_token(ctx)
