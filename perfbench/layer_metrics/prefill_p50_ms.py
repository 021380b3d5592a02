"""A joining request's prefill as the scheduler sees it: the ``prefill`` span
recorded on the request's own trace (prompt pass, insert into the slot's
pages and the fetch of the first token), not the insert phase and not the
inner span around the engine call alone, which returns before the device is
done. Median, in milliseconds."""
from perfbench import harness


def read(ctx):
    requests = {s.trace_id for s in ctx["spans"] if s.name == "slot_wait"}
    d = [s.dur_us / 1e3 for s in ctx["spans"]
         if s.name == "prefill" and s.trace_id in requests
         and (s.attrs or {}).get("phase") != "insert"]
    return harness.quantile(d, 0.5) if d else None
