"""Traffic kind ``train``: the program's jitted whole-step training program,
one chip or a mesh, a fresh seeded batch every step.

Set-up builds ONE object - the compiled step with its parameters and
optimizer state - drives it through its first steps from the seed with the
window's own call and feed, and hands the same object to the window. The
plain reference follows those first steps from the same weights and batches
before the program's state exists; ``correct`` compares each step's loss, the
first gradient as the optimizer got it (Adam's first moment after one step,
divided by 1 - b1) and the parameters' change, by the worst leaf.
"""
from __future__ import annotations

import shutil
import time

import numpy as np

from perfbench import harness, trace as ptrace
from perfbench.harness import say

CHECK_STEPS = 3
#: leaves of the first gradient compared element by element, drawn by seed
SAMPLED_LEAVES = 8


class Feed:
    """Batches from the seed: step i's rows are drawn by its own generator,
    so every row of every step differs and any step can be made again."""

    def __init__(self, seed, batch, seq_len, vocab, sharding):
        self.seed, self.shape, self.vocab = int(seed), (batch, seq_len), vocab
        self.sharding = sharding

    def host(self, i):
        rng = np.random.default_rng([self.seed, i])
        toks = rng.integers(0, self.vocab, (self.shape[0], self.shape[1] + 1),
                            dtype=np.int32)
        return toks[:, :-1], toks[:, 1:]

    def device(self, i):
        import jax
        toks, tgts = self.host(i)
        return (jax.device_put(toks, self.sharding),
                jax.device_put(tgts, self.sharding))


def _worst_leaf_gap(prog, ref):
    """|program's norm - reference's norm| over the reference's norm of that
    leaf or of the median leaf, whichever is larger; the worst leaf."""
    prog, ref = np.asarray(prog, np.float64), np.asarray(ref, np.float64)
    return float(np.max(np.abs(prog - ref)
                        / np.maximum(ref, np.median(ref))))


def sampled_leaves(cell, seed):
    """Indices (``jax.tree.leaves`` order) of the leaves whose first gradient
    is compared element by element: a seeded sample."""
    import jax
    n = len(jax.tree.leaves(cell.model.weight_shapes(cell.config)))
    rng = np.random.default_rng([int(seed), 5])
    return sorted(rng.choice(n, SAMPLED_LEAVES, replace=False).tolist())


def build(cell, seed, device):
    """Optimizer, compiled step, feed, and the maker of the weights."""
    import jax
    import optax
    from jax.sharding import NamedSharding, PartitionSpec as P

    tr, cfg = cell.traffic, cell.config
    devices = jax.devices()[:cell.chips]
    mesh = None
    if tr.get("mesh"):
        from deeplearning4j_tpu.parallel.mesh import MeshSpec
        mesh = MeshSpec(dict(tr["mesh"])).build(devices)
    model = cell.model.build_model(cfg, mesh)
    if mesh is not None:
        shardings = model.param_shardings(mesh)
        axes = [a if a in mesh.axis_names else None for a in ("data", "seq")]
        feed_sharding = NamedSharding(mesh, P(*axes))
    else:
        shardings = feed_sharding = jax.sharding.SingleDeviceSharding(
            devices[0])
    hp = tr["optimizer"]
    opt = optax.adamw(hp["lr"], b1=hp["b1"], b2=hp["b2"], eps=hp["eps"],
                      weight_decay=hp["weight_decay"])
    step = model.make_train_step(opt)
    feed = Feed(seed, tr["batch"], tr["seq_len"], cfg["vocab_size"],
                feed_sharding)
    weights = lambda: cell.model.make_weights(cfg, seed, shardings)  # noqa
    return opt, step, feed, weights


def first_steps(cell, step, opt, weights, feed, keep=()):
    """The program through its first CHECK_STEPS steps. Returns the state
    and what is compared: the losses, the first gradient's leaf norms (and
    its leaves ``keep``), the leaf norms of the parameters' change."""
    import jax
    import jax.numpy as jnp
    ref = cell.reference
    b1 = cell.traffic["optimizer"]["b1"]
    t0 = time.time()
    params = weights()
    # Adam's moments placed like the parameters they belong to, the count
    # like the feed's devices. ``jit(opt.init)`` alone leaves a fresh state
    # uncommitted on the first chip (all 16 B x N of it, on a mesh), and a
    # step fed from there compiles a second time for its own outputs.
    like = jax.tree.map(lambda a: a.sharding, params)
    plain = jax.sharding.NamedSharding(feed.sharding.mesh,
                                       jax.sharding.PartitionSpec()) \
        if hasattr(feed.sharding, "mesh") else feed.sharding
    shapes = jax.eval_shape(opt.init, params)
    where = jax.tree.map(lambda _: plain, shapes)
    where = (where[0]._replace(mu=like, nu=like),) + tuple(where[1:])
    opt_state = jax.jit(opt.init, out_shardings=where)(params)
    jax.block_until_ready(opt_state)
    say(f"program: weights and optimizer state in {time.time() - t0:.1f} s")
    losses, grad_norms, grad_leaves = [], None, []
    for i in range(CHECK_STEPS):
        t0 = time.time()
        toks, tgts = feed.device(i)
        params, opt_state, loss = step(params, opt_state, toks, tgts)
        losses.append(float(loss))
        say(f"program: step {i + 1} in {time.time() - t0:.2f} s")
        if i == 0:
            mu = opt_state[0].mu
            grad_norms = np.asarray(
                jax.jit(ref.leaf_norms)(mu)) / (1.0 - b1)
            leaves = jax.tree.leaves(mu)
            grad_leaves = [leaves[k] / (1.0 - b1) for k in keep]
            del leaves, mu
    w0 = weights()
    delta = np.asarray(jax.jit(lambda a, b: ref.leaf_norms(
        jax.tree.map(jnp.subtract, a, b)))(params, w0))
    del w0
    return params, opt_state, {"losses": losses, "grad_norms": grad_norms,
                               "delta_norms": delta,
                               "grad_leaves": grad_leaves}


def compare(cell, prog, ref_out, keep):
    """``prog`` and ``ref_out``: what ``first_steps`` and the reference's
    ``train_steps`` return; ``keep`` the indices of the sampled leaves."""
    import jax
    cmp = harness.Compare()
    for i, (a, b) in enumerate(zip(prog["losses"], ref_out["losses"])):
        cmp.add(f"loss_gap_step{i + 1}", abs(a - b),
                cell.limit(f"loss_gap_step{i + 1}"))
    cmp.add("grad_norm_gap_worst_leaf",
            _worst_leaf_gap(prog["grad_norms"], ref_out["grad_norms"]),
            cell.limit("grad_norm_gap_worst_leaf"))
    # the norm of the difference, on the sampled leaves: the number that a
    # lower precision moves most (a norm hardly feels a random error)
    err = np.asarray(jax.jit(cell.reference.leaf_errors)(
        prog["grad_leaves"], ref_out["grad_leaves"]), np.float64)
    base = np.maximum(ref_out["grad_norms"][keep],
                      np.median(ref_out["grad_norms"]))
    cmp.add("grad_error_worst_sampled_leaf", float(np.max(err / base)),
            cell.limit("grad_error_worst_sampled_leaf"))
    cmp.add("delta_norm_gap_worst_leaf",
            _worst_leaf_gap(prog["delta_norms"], ref_out["delta_norms"]),
            cell.limit("delta_norm_gap_worst_leaf"))
    return cmp


def reference_steps(cell, weights, feed, keep, lowp=False):
    t0 = time.time()
    w = weights()
    batches = [feed.device(i) for i in range(CHECK_STEPS)]
    out = cell.reference.train_steps(
        w, batches, cell.config, cell.traffic["optimizer"],
        rows=cell.traffic["reference_rows"], lowp=lowp, keep=keep)
    say(f"reference ({'float8 control' if lowp else 'float32'}): "
        f"{CHECK_STEPS} steps in {time.time() - t0:.1f} s, "
        f"losses {out['losses']}")
    return out


def run(cell, seed, seconds, trace, device, t_start):
    import jax
    from deeplearning4j_tpu.observability.compile_watch import (
        global_compile_watch)

    events = harness.CacheEvents()
    tr = cell.traffic
    opt, step, feed, weights = build(cell, seed, device)
    t_ref0 = time.time()
    keep = sampled_leaves(cell, seed)
    ref_out = reference_steps(cell, weights, feed, keep)
    ref_seconds = time.time() - t_ref0

    t0 = time.time()
    params, opt_state, prog = first_steps(cell, step, opt, weights, feed,
                                          keep)
    say(f"program: first {CHECK_STEPS} steps in {time.time() - t0:.1f} s "
        f"(persistent cache hits {events.hits}, misses {events.misses}), "
        f"losses {prog['losses']}")
    cmp = compare(cell, prog, ref_out, keep)
    del prog, ref_out

    # ---- the window: the same step and state, batches CHECK_STEPS, ...
    watch = global_compile_watch()
    traced0 = watch.total
    compiles0 = events.hits + events.misses
    tokens_per_step = tr["batch"] * tr["seq_len"]
    i = CHECK_STEPS
    nxt = feed.device(i)
    state = [params, opt_state]
    del params, opt_state
    window_losses = []

    def one_step():
        nonlocal i, nxt
        toks, tgts = nxt
        state[0], state[1], loss = step(state[0], state[1], toks, tgts)
        i += 1
        nxt = feed.device(i)        # made while the step runs
        window_losses.append(float(loss))   # the step ends here

    rec = None
    if trace:
        # a traced run profiles a few steps of the same loop just before
        # its window, so that the window itself runs with the profiler off
        one_step()
        rec = ptrace.Recorder(harness.work_dir(cell))
        rec.start()
        for _ in range(tr["trace_steps"]):
            one_step()
        rec.stop()
        window_losses.clear()
    done = 0
    t_w0 = time.time()
    setup_s = t_w0 - t_start - ref_seconds
    while True:
        one_step()
        done += 1
        t = time.time()
        if t - t_w0 >= seconds:
            break
    window_s = t - t_w0
    compiles = (watch.total - traced0) + (events.hits + events.misses
                                          - compiles0)
    finite = bool(np.all(np.isfinite(window_losses)))
    say(f"window: {done} steps in {window_s:.3f} s, loss "
        f"{window_losses[0]:.4f} -> {window_losses[-1]:.4f}, "
        f"finite {finite}, compiles in window {compiles}")
    cmp.add("nonfinite_losses_in_window", 0 if finite else 1, 0, exact=True)
    values = {"train_tok_s": tokens_per_step * done / window_s,
              "setup_s": setup_s}
    peak = harness.memory_peak_bytes(cell.chips)
    out = {"correct": cmp.correct, "attempted": done, "failed": 0,
           "values": values, "memory_peak_bytes": peak}
    if trace:
        tr_data = rec.load()
        lo, hi = tr_data.span()
        ctx = {"cell": cell, "device": device, "trace": tr_data,
               "values": values, "compiles_in_window": compiles,
               "spans": [], "window": (t_w0, t), "trace_span": (lo, hi),
               "tokens_per_step": tokens_per_step}
        harness.read_layer_metrics(cell, ctx, values)
        out["busy_s"] = tr_data.busy_seconds(lo, hi)
        out["trace_window_s"] = hi - lo
        out["breakdown"] = {
            "device_ops": tr_data.top_ops(10),
            "idle_gaps": [["between steps", e - s]
                          for s, e in tr_data.idle_gaps(lo, hi, 10)]}
        shutil.rmtree(harness.work_dir(cell), ignore_errors=True)
    del state
    return out
