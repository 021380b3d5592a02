"""How late the load generator ran: sent - due, 95th percentile over the
requests due in the window, on the generator's own clock."""
from perfbench import harness


def read(ctx):
    late = [1e3 * (r["sent"] - r["due"]) for r in ctx["requests"]
            if r.get("sent") is not None]
    return harness.quantile(late, 0.95) if late else None
