"""The front door's own time: every ``http_request`` span less its
``generation_request`` child (same trace id), median."""
from perfbench.layer_metrics._shared import median


def read(ctx):
    child = {}
    for s in ctx["spans"]:
        if s.name == "generation_request" and s.trace_id is not None:
            child[s.trace_id] = child.get(s.trace_id, 0.0) + s.dur_us
    own = [(s.dur_us - child[s.trace_id]) / 1e3 for s in ctx["spans"]
           if s.name == "http_request" and s.trace_id in child]
    return median(own)
