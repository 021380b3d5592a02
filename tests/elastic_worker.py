"""Subprocess workers for the elastic-training drills.

Two modes, dispatched on ``argv[1]``:

``drill`` — the elastic shrink/resume/re-expand drill on ONE process
with an N-virtual-device CPU mesh (``--devices``). The worker trains a
deterministic step-keyed schedule through ``ShardedTrainer``, writes
ASYNC sharded manifests via ``ElasticCheckpointer`` after every step,
and on launch resumes from the newest COMPLETE manifest — reshaping a
checkpoint written on a different device count onto the current mesh.
``--die-at K`` SIGKILLs the process after step K's manifest is durable
(the host-loss arm: relaunching with ``--devices M<N`` is "the pod came
back smaller"); ``--sigterm-at K`` self-delivers a REAL SIGTERM before
step K, which the ``utils/preemption.py`` latch turns into a final
synchronous save + nonzero exit (the preemption drill; the relaunch
must resume exactly once). Because the data schedule is keyed by step
index and the updater is plain SGD, an interrupted-reshaped-resumed run
must land within float-reassociation tolerance of an uninterrupted one.

``<int>`` (legacy) — the 2-process ``jax.distributed`` fault-injection
worker driven by test_multihost.py (gated there behind the multiprocess
CPU collectives capability probe).

Ref: SURVEY §5.3 — the reference's only fault tolerance is Spark task
retry plus checkpoint/restart on the SAME cluster shape; the drill
exercises checkpoint/restart across a real process boundary AND a
topology change.
"""
import argparse
import json
import os
import signal
import sys

import numpy as np


def drill_main(argv):
    ap = argparse.ArgumentParser()
    ap.add_argument("--devices", type=int, required=True)
    ap.add_argument("--ckpt", required=True)
    ap.add_argument("--steps", type=int, required=True)
    ap.add_argument("--out", required=True)
    ap.add_argument("--die-at", type=int, default=-1)
    ap.add_argument("--sigterm-at", type=int, default=-1)
    args = ap.parse_args(argv)

    os.environ["JAX_PLATFORMS"] = "cpu"
    os.environ["JAX_NUM_CPU_DEVICES"] = str(args.devices)

    import jax

    import jax.numpy as jnp

    from deeplearning4j_tpu.parallel import MeshSpec
    from deeplearning4j_tpu.parallel.trainer import ShardedTrainer
    from deeplearning4j_tpu.resilience.elastic import ElasticCheckpointer
    from deeplearning4j_tpu.utils.preemption import PreemptionHandler
    from tests.multihost_worker import build_net, global_data

    assert len(jax.devices()) == args.devices, len(jax.devices())
    net = build_net()
    trainer = ShardedTrainer(net, MeshSpec.data_parallel())
    ckpt = ElasticCheckpointer(args.ckpt, max_to_keep=3,
                               n_shards=args.devices)

    # resume: newest complete manifest, reshaped onto THIS device count
    resumed_at = ckpt.restore(net, min_iteration=0,
                              target_replicas=args.devices)
    start = 0
    if resumed_at is not None:
        start = resumed_at
        print(f"RESUMED_AT {resumed_at}", flush=True)

    handler = PreemptionHandler().install()
    for step in range(start, args.steps):
        if step == args.sigterm_at:
            # a REAL SIGTERM through the real latch (the pod-reclaim
            # grace signal), delivered at a step boundary like the
            # scheduler would
            os.kill(os.getpid(), signal.SIGTERM)
        if handler.preempted:
            ckpt.save(net._iteration, net, mesh=trainer.mesh, sync=True)
            print(f"PREEMPTED_SAVED {net._iteration}", flush=True)
            sys.exit(75)
        x, y = global_data(step)
        trainer.fit(x, y)
        ckpt.save(net._iteration, net, mesh=trainer.mesh)   # async
        if step == args.die_at:
            ckpt.wait()     # step K's manifest is durable; now die hard
            print(f"SIGKILL_AT {step}", flush=True)
            os.kill(os.getpid(), signal.SIGKILL)
    ckpt.wait()

    x, y = global_data(10_000)      # fixed held-out batch
    out = net.output(x)
    loss = float(jnp.mean(-jnp.sum(
        jnp.asarray(y) * jnp.log(jnp.clip(out.buf(), 1e-9, 1.0)), axis=-1)))
    np.save(args.out, np.asarray(net.params().buf()))
    with open(args.out + ".json", "w") as f:
        json.dump({"final_loss": loss, "resumed_at": resumed_at,
                   "iteration": int(net._iteration),
                   "devices": args.devices}, f)
    print(f"DONE loss={loss:.6f}", flush=True)


def legacy_multihost_main():
    proc_id = int(sys.argv[1])
    nprocs = int(sys.argv[2])
    port = sys.argv[3]
    ckpt_dir = sys.argv[4]
    out_path = sys.argv[5]
    total_steps = int(sys.argv[6])
    die_at = int(sys.argv[7]) if len(sys.argv) > 7 else -1

    os.environ["JAX_PLATFORMS"] = "cpu"
    os.environ["JAX_NUM_CPU_DEVICES"] = "2"

    import jax

    from deeplearning4j_tpu.parallel.master import DistributedConfig

    DistributedConfig(coordinator_address=f"127.0.0.1:{port}",
                      num_processes=nprocs, process_id=proc_id).initialize()

    from deeplearning4j_tpu.parallel import MeshSpec
    from deeplearning4j_tpu.parallel.trainer import ShardedTrainer
    from tests.multihost_worker import build_net, global_data

    net = build_net()
    trainer = ShardedTrainer(net, MeshSpec.data_parallel())

    # ---- resume: newest complete checkpoint in the shared dir ----
    def ckpt_path(step):
        return os.path.join(ckpt_dir, f"step_{step:04d}.zip")

    start = 0
    done = sorted(int(n[5:9]) for n in os.listdir(ckpt_dir)
                  if n.startswith("step_") and n.endswith(".zip"))
    if done:
        start = done[-1] + 1
        from deeplearning4j_tpu.nn.multilayer import MultiLayerNetwork
        net = MultiLayerNetwork.load(ckpt_path(done[-1]), load_updater=True)
        trainer = ShardedTrainer(net, MeshSpec.data_parallel())
        print(f"proc{proc_id}: resumed from step {done[-1]}")

    half = 16 // nprocs
    for step in range(start, total_steps):
        x, y = global_data(step)
        lo, hi = proc_id * half, (proc_id + 1) * half
        trainer.fit(x[lo:hi], y[lo:hi])
        if proc_id == 0:
            # rank-0 persists (replicated params are identical on all ranks);
            # write-then-rename so a kill never leaves a torn zip behind
            tmp = ckpt_path(step) + ".tmp"
            net.save(tmp)
            os.replace(tmp, ckpt_path(step))
        if step == die_at and proc_id == 1:
            print(f"proc1: SIGKILL at step {step}", flush=True)
            os.kill(os.getpid(), signal.SIGKILL)

    if proc_id == 0:
        np.save(out_path, np.asarray(net.params().buf()))
    print(f"proc{proc_id} done", flush=True)


if __name__ == "__main__":
    if len(sys.argv) > 1 and sys.argv[1] == "drill":
        drill_main(sys.argv[2:])
    else:
        legacy_multihost_main()
