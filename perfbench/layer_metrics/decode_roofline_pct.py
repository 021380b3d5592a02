"""The decode program's share of its roofline: the least time the chip could
take for one step - max(FLOPs / peak FLOP/s, bytes / peak bytes/s), with the
bytes the weights read once (bfloat16, as computed) plus the keys and values
of the tokens actually live in the active slots - over the median device
duration of the decode program in the trace. ``ctx['roofline_bound']`` says
which of the two bounds it."""
from perfbench import harness
from perfbench.layer_metrics._shared import DECODE_MODULE


def read(ctx):
    tr = ctx.get("trace")
    live = ctx.get("live_tokens_mean")
    if tr is None or live is None:
        return None
    dev_s = tr.module_median(DECODE_MODULE)
    if not dev_s:
        return None
    cell = ctx["cell"]
    pk = harness.peaks(ctx["device"]["kind"])
    t_flops = cell.costs.decode_step_flops(
        cell.config, live, ctx["active_mean"]) / pk["flops_per_s_bf16"]
    t_bytes = cell.costs.decode_step_bytes(cell.config, live) \
        / pk["hbm_bytes_per_s"]
    ctx["roofline_bound"] = "memory" if t_bytes >= t_flops else "compute"
    harness.say(f"decode roofline: least {1e3 * max(t_flops, t_bytes):.3f} ms "
                f"({ctx['roofline_bound']}-bound; flops {1e3 * t_flops:.3f} "
                f"ms, bytes {1e3 * t_bytes:.3f} ms) over device "
                f"{1e3 * dev_s:.3f} ms, {live:.0f} live tokens")
    return 100.0 * max(t_flops, t_bytes) / dev_s
