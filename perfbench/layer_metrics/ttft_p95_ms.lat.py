"""Time from when a request was DUE to its first SSE token at the client,
95th percentile over the requests due in the window (one without a token
when the window closed counts as +inf). What an interactive user feels
first - and a per-layer metric only because about a hundred requests fit a
window at 0.8 of this deployment's knee, so it cannot hold a bound of 10%
(PERF.md, section 2)."""


def read(ctx):
    v = ctx["values"].get("ttft_p95_ms")
    return None if v is None or v == float("inf") else v
