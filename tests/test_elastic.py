"""Elastic training suite: async sharded manifests (atomic commit, torn
shard-set skip), topology-reshaping restore (residual re-bucketing),
host-loss shrink/resume/re-expand through ResilientTrainer's elastic
mode, the ``checkpoint.manifest`` durability fault point, and the
``DL4J_TPU_ELASTIC=0`` kill switch. Subprocess drills (SIGKILL +
device-count change, real-SIGTERM preemption) are marked slow."""
import json
import os
import subprocess
import sys
import urllib.request

import jax
import numpy as np
import pytest
from conftest import time_limit

from deeplearning4j_tpu.data.iterators import ArrayDataSetIterator
from deeplearning4j_tpu.nn.conf.configuration import NeuralNetConfiguration
from deeplearning4j_tpu.nn.conf.layers import DenseLayer, OutputLayer
from deeplearning4j_tpu.nn.multilayer import MultiLayerNetwork
from deeplearning4j_tpu.observability import (global_registry,
                                              reset_global_registry)
from deeplearning4j_tpu.optim.updaters import Sgd
from deeplearning4j_tpu.parallel import compression as comp
from deeplearning4j_tpu.parallel.mesh import MeshSpec
from deeplearning4j_tpu.parallel.trainer import ShardedTrainer
from deeplearning4j_tpu.resilience import elastic, faults
from deeplearning4j_tpu.resilience.elastic import (ElasticCheckpointer,
                                                   HostLostError)
from deeplearning4j_tpu.resilience.recovery import ResilientTrainer

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
WORKER = os.path.join(REPO, "tests", "elastic_worker.py")


def _conf(seed=7):
    return (NeuralNetConfiguration.builder()
            .seed(seed).updater(Sgd(0.1)).list()
            .layer(DenseLayer(n_in=4, n_out=16, activation="tanh"))
            .layer(OutputLayer(n_in=16, n_out=3, activation="softmax",
                               loss_function="mcxent"))
            .build())


def _data(n=64, seed=0):
    rng = np.random.RandomState(seed)
    x = rng.rand(n, 4).astype("f4")
    y = np.eye(3, dtype="f4")[rng.randint(0, 3, n)]
    return x, y


@pytest.fixture(autouse=True)
def _clean():
    faults.reset()
    reset_global_registry()
    elastic.global_capacity().reset()
    yield
    faults.clear()
    elastic.global_capacity().reset()


def _mesh8():
    if len(jax.devices()) < 8:
        pytest.skip("needs the 8-device CPU mesh")
    return jax.devices()[:8]


# ----------------------------------------------------- sharded manifest store
class TestElasticCheckpointer:
    def test_sync_roundtrip_and_rotation(self, tmp_path):
        import jax as _jax

        from deeplearning4j_tpu.optim.updaters import Adam
        conf = (NeuralNetConfiguration.builder()
                .seed(7).updater(Adam(1e-2)).list()
                .layer(DenseLayer(n_in=4, n_out=16, activation="tanh"))
                .layer(OutputLayer(n_in=16, n_out=3, activation="softmax",
                                   loss_function="mcxent")).build())
        net = MultiLayerNetwork(conf).init()
        ck = ElasticCheckpointer(str(tmp_path), max_to_keep=2)
        x, y = _data(16)
        for step in (1, 2, 3):
            net.fit(x, y)
            ck.save(net._iteration, net, sync=True)
        assert ck.all_steps() == [2, 3]          # rotation evicted step 1
        want = np.asarray(net.params().buf()).copy()
        other = MultiLayerNetwork(conf).init()   # the relaunch-built net
        restored = ck.restore(other, target_replicas=1)
        assert restored == 3
        np.testing.assert_array_equal(np.asarray(other.params().buf()), want)
        assert other._iteration == 3
        # ADAM MOMENTS survive the relaunch-style restore byte-exactly
        # (a quality regression here would be silent otherwise)
        for a, b in zip(_jax.tree.leaves(net._opt_state),
                        _jax.tree.leaves(other._opt_state)):
            if hasattr(a, "shape"):
                np.testing.assert_array_equal(np.asarray(a), np.asarray(b))
        # counters: every save counted, restore counted un-reshaped
        reg = global_registry()
        assert reg.get("dl4j_elastic_saves_total").labels(
            mode="sync").value == 3
        assert reg.get("dl4j_elastic_restores_total").labels(
            reshaped="false").value == 1

    def test_async_saves_commit_off_caller_thread(self, tmp_path):
        net = MultiLayerNetwork(_conf()).init()
        ck = ElasticCheckpointer(str(tmp_path), max_to_keep=5)
        x, y = _data(16)
        for _ in range(3):
            net.fit(x, y)
            ck.save(net._iteration, net)          # async
        with time_limit(60.0):
            ck.wait()
        assert ck.last_error is None
        # the coalescing latest-slot queue may supersede older pending
        # saves, but the NEWEST one is always committed
        steps = ck.all_steps()
        assert steps and steps[-1] == 3 and set(steps) <= {1, 2, 3}
        m = ck.complete_manifests()[0]
        assert m["step"] == 3 and m["iteration"] == 3
        assert all(s["digest"].startswith("crc32:") for s in m["shards"])
        assert global_registry().get("dl4j_elastic_saves_total").labels(
            mode="async").value == 3

    def test_torn_or_partial_shard_set_skipped(self, tmp_path):
        net = MultiLayerNetwork(_conf()).init()
        ck = ElasticCheckpointer(str(tmp_path), max_to_keep=5)
        x, y = _data(16)
        net.fit(x, y)
        ck.save(1, net, sync=True)
        good = np.asarray(net.params().buf()).copy()
        net.fit(x, y)
        ck.save(2, net, sync=True)
        # tear step 2's shard set: corrupt one shard file's content
        m2 = json.load(open(tmp_path / "manifest_2.json"))
        victim = tmp_path / m2["shards"][0]["file"]
        victim.write_bytes(b"torn" + victim.read_bytes()[4:])
        steps = [m["step"] for m in ck.complete_manifests()]
        assert steps == [1]                       # torn set not trusted
        other = MultiLayerNetwork(_conf(seed=99)).init()
        assert ck.restore(other) == 1             # newest COMPLETE wins
        np.testing.assert_array_equal(np.asarray(other.params().buf()), good)
        # a manifest whose shard file is MISSING is equally untrusted
        os.remove(victim)
        assert [m["step"] for m in ck.complete_manifests()] == [1]

    def test_manifest_crash_fault_preserves_previous_save(self, tmp_path):
        """checkpoint.manifest fires between shard fsync and the
        manifest rename: a crash there must leave NO manifest for the
        new step and the previous complete save in charge."""
        net = MultiLayerNetwork(_conf()).init()
        ck = ElasticCheckpointer(str(tmp_path), max_to_keep=5)
        x, y = _data(16)
        net.fit(x, y)
        ck.save(1, net, sync=True)
        net.fit(x, y)
        plan = faults.FaultPlan([faults.FaultSpec(
            "checkpoint.manifest", "crash", rate=1.0, count=1)])
        with faults.active(plan):
            with pytest.raises(faults.InjectedFault):
                ck.save(2, net, sync=True)
        assert not (tmp_path / "manifest_2.json").exists()
        assert [m["step"] for m in ck.complete_manifests()] == [1]
        other = MultiLayerNetwork(_conf(seed=99)).init()
        assert ck.restore(other) == 1

    def test_save_model_atomic_manifest_fault_zip_path(self, tmp_path):
        """The same durability ordering on the zip path: fsync + the
        checkpoint.manifest point BEFORE the rename — a crash there
        leaves the previous complete zip readable, never a torn one."""
        from deeplearning4j_tpu.utils.serialization import (
            ModelSerializer, save_model_atomic)
        net = MultiLayerNetwork(_conf()).init()
        path = str(tmp_path / "ck.zip")
        save_model_atomic(net, path)
        before = open(path, "rb").read()
        x, y = _data(16)
        net.fit(x, y)
        plan = faults.FaultPlan([faults.FaultSpec(
            "checkpoint.manifest", "crash", rate=1.0, count=1)])
        with faults.active(plan):
            with pytest.raises(faults.InjectedFault):
                save_model_atomic(net, path)
        assert open(path, "rb").read() == before      # old save in charge
        ModelSerializer.restore(path)                 # and still readable
        # no fault: the overwrite goes through
        save_model_atomic(net, path)
        assert open(path, "rb").read() != before

    def test_kill_switch_noops_saves(self, tmp_path, monkeypatch):
        monkeypatch.setenv("DL4J_TPU_ELASTIC", "0")
        net = MultiLayerNetwork(_conf()).init()
        ck = ElasticCheckpointer(str(tmp_path))
        assert ck.save(1, net, sync=True) is False
        assert ck.all_steps() == []


# -------------------------------------------------- residual re-bucketing
class TestReshapeState:
    def _layout(self):
        import jax.numpy as jnp
        return comp.build_layout({"0": {"W": jnp.zeros((4, 2)),
                                        "b": jnp.zeros((2,))}})

    def test_shrink_group_means_and_keeps_thresholds(self):
        layout = self._layout()
        res = np.arange(8 * 10, dtype=np.float32).reshape(8, 10)
        state = {"residual": [res], "threshold": [np.float32(0.125)]}
        out, mode = comp.reshape_state(state, layout, 4)
        assert mode == "rebucketed"
        np.testing.assert_allclose(
            np.asarray(out["residual"][0]),
            res.reshape(4, 2, 10).mean(axis=1))
        assert float(out["threshold"][0]) == 0.125
        # replica-MEAN deferred mass is preserved by the reshape
        np.testing.assert_allclose(
            np.asarray(out["residual"][0]).mean(axis=0),
            res.mean(axis=0), rtol=1e-6)

    def test_expand_tiles_and_preserves_mean(self):
        layout = self._layout()
        res = np.arange(4 * 10, dtype=np.float32).reshape(4, 10)
        state = {"residual": [res], "threshold": [np.float32(0.5)]}
        out, mode = comp.reshape_state(state, layout, 8)
        assert mode == "rebucketed"
        assert np.asarray(out["residual"][0]).shape == (8, 10)
        np.testing.assert_allclose(
            np.asarray(out["residual"][0]).mean(axis=0),
            res.mean(axis=0), rtol=1e-6)
        assert float(out["threshold"][0]) == 0.5

    def test_indivisible_reseeds_zero_keeps_threshold(self):
        layout = self._layout()
        state = {"residual": [np.ones((8, 10), np.float32)],
                 "threshold": [np.float32(0.25)]}
        out, mode = comp.reshape_state(state, layout, 3)
        assert mode == "reseeded"
        assert np.all(np.asarray(out["residual"][0]) == 0)
        assert np.asarray(out["residual"][0]).shape == (3, 10)
        assert float(out["threshold"][0]) == 0.25

    def test_layout_mismatch_salvages_nothing(self):
        layout = self._layout()
        state = {"residual": [np.ones((8, 7), np.float32)],
                 "threshold": [np.float32(0.25)]}
        out, mode = comp.reshape_state(state, layout, 4)
        assert out is None and mode == "layout_mismatch"
        assert comp.reshape_state(None, layout, 4)[0] is None

    def test_checkpoint_restore_onto_different_replica_count(self,
                                                             tmp_path):
        """PR-7 regression: a gradCompression.npz written on an
        8-replica mesh restores onto a 4-replica mesh — topology change
        detected + warned, residuals re-bucketed, thresholds kept,
        training continues (it used to die on a shape mismatch)."""
        from deeplearning4j_tpu.utils.serialization import ModelSerializer
        devs = _mesh8()
        x, y = _data(32)
        net = MultiLayerNetwork(_conf()).init()
        tr = ShardedTrainer(net, MeshSpec.data_parallel(),
                            devices=devs, grad_compression="fixed:1e-3")
        tr.fit(x, y)
        tr.fit(x, y)
        assert np.shape(net._grad_compression_state["residual"][0])[0] == 8
        path = str(tmp_path / "comp.zip")
        ModelSerializer.write_model(net, path)

        restored = ModelSerializer.restore(path)
        saved_thr = [float(t) for t in
                     restored._grad_compression_state["threshold"]]
        tr4 = ShardedTrainer(restored, MeshSpec.data_parallel(),
                             devices=devs[:4], grad_compression="fixed:1e-3")
        tr4.fit(x, y)                        # used to crash on shapes
        state = restored._grad_compression_state
        assert np.shape(state["residual"][0])[0] == 4
        got_thr = [float(np.asarray(t)) for t in state["threshold"]]
        # thresholds carried through the reshape (then possibly updated
        # by the step for adaptive algorithms; fixed stays put)
        assert got_thr == saved_thr
        assert np.all(np.isfinite(np.asarray(restored.params().buf())))


# --------------------------------------------- elastic ResilientTrainer mode
class TestElasticTrainer:
    def _fit_ref(self, tmp_path, steps_data, epochs=2):
        ref = MultiLayerNetwork(_conf()).init()
        t = ShardedTrainer(ref, MeshSpec.data_parallel(), devices=_mesh8())
        rt = ResilientTrainer(t, str(tmp_path / "ref"), elastic=True)
        x, y = steps_data
        rt.fit(ArrayDataSetIterator(x, y, 16), epochs=epochs)
        return ref

    def test_host_loss_shrink_resume_reexpand(self, tmp_path, monkeypatch):
        """The elastic drill, in-process: fault-injected host loss
        mid-run → mesh shrinks to the surviving devices → restore from
        the sharded manifest (reshaped) → resume → re-expand when
        capacity returns — and the run converges to the uninterrupted
        result within float-reassociation tolerance."""
        monkeypatch.setenv("DL4J_TPU_ELASTIC_RECOVER_STEPS", "2")
        data = _data(64)
        ref = self._fit_ref(tmp_path, data)

        net = MultiLayerNetwork(_conf()).init()
        tr = ShardedTrainer(net, MeshSpec.data_parallel(), devices=_mesh8())
        rt = ResilientTrainer(tr, str(tmp_path / "el"), elastic=True,
                              max_restarts=3)
        plan = faults.FaultPlan([faults.FaultSpec(
            "allreduce", "host_loss", rate=1.0, count=1)], seed=3)
        x, y = data
        with faults.active(plan):
            rt.fit(ArrayDataSetIterator(x, y, 16), epochs=2)
        assert tr.mesh.size == 8                 # re-expanded by the end
        assert net._iteration == ref._iteration
        np.testing.assert_allclose(np.asarray(net.params().buf()),
                                   np.asarray(ref.params().buf()),
                                   rtol=1e-4, atol=1e-5)
        reg = global_registry()
        shr = reg.get("dl4j_elastic_reshapes_total")
        assert shr.labels(direction="shrink").value == 1
        assert shr.labels(direction="expand").value == 1
        assert reg.get("dl4j_elastic_mesh_size").value == 8
        assert reg.get("dl4j_elastic_restores_total").labels(
            reshaped="true").value >= 1
        assert reg.get("dl4j_checkpoint_restores_total").value >= 1
        # the fault + reshape trail is in the shared resilience ring
        cats = [e["category"] for e in faults.events()]
        assert "host_loss" in cats and "mesh_reshape" in cats \
            and "elastic_restore" in cats and "capacity_restored" in cats

    def test_metrics_bundle_and_debug_endpoint(self, tmp_path, monkeypatch):
        """/metrics exposition carries the elastic series, a triggered
        flight-recorder bundle contains elastic.json, and UIServer
        serves /debug/elastic."""
        from deeplearning4j_tpu.observability.flight_recorder import (
            reset_global_flight_recorder)
        from deeplearning4j_tpu.ui.server import UIServer
        monkeypatch.setenv("DL4J_TPU_ELASTIC_RECOVER_STEPS", "2")
        monkeypatch.setenv("DL4J_TPU_POSTMORTEM_DIR",
                           str(tmp_path / "post"))
        rec = reset_global_flight_recorder()
        net = MultiLayerNetwork(_conf()).init()
        tr = ShardedTrainer(net, MeshSpec.data_parallel(), devices=_mesh8())
        rt = ResilientTrainer(tr, str(tmp_path / "el"), elastic=True,
                              max_restarts=3)
        plan = faults.FaultPlan([faults.FaultSpec(
            "allreduce", "host_loss", rate=1.0, count=1)], seed=5)
        x, y = _data(64)
        with faults.active(plan):
            rt.fit(ArrayDataSetIterator(x, y, 16), epochs=1)
        prom = global_registry().render_prometheus()
        assert "dl4j_elastic_reshapes_total" in prom
        assert "dl4j_elastic_mesh_size" in prom
        assert "dl4j_elastic_restores_total" in prom
        bundle = rec.dump("test")
        assert "elastic.json" in os.listdir(bundle)
        ej = json.load(open(os.path.join(bundle, "elastic.json")))
        assert ej["enabled"] is True
        assert ej["reshapes"].get("shrink", 0) >= 1
        assert any(c["last_step"] is not None for c in ej["checkpointers"])
        # saves are genuinely SHARDED: one file per mesh device (capped
        # by the number of state arrays), every shard digested
        m = rt._elastic_ckpt.complete_manifests()[0]
        assert len(m["shards"]) >= 2
        assert m["mesh"]["n_replicas"] in (4, 8)
        server = UIServer(port=0).start()
        try:
            with urllib.request.urlopen(
                    server.get_address() + "/debug/elastic") as r:
                payload = json.loads(r.read())
            assert payload["capacity"]["total_devices"] == \
                len(jax.devices())
            assert payload["reshapes"].get("expand", 0) >= 1
        finally:
            server.stop()

    def test_kill_switch_restores_pre_elastic_behavior(self, tmp_path,
                                                       monkeypatch):
        """DL4J_TPU_ELASTIC=0: elastic=True behaves byte-identically to
        the pre-elastic trainer — zip checkpoints, no manifests, and a
        host_loss chaos spec is inert."""
        x, y = _data(64)

        def run(subdir, elastic_arg):
            net = MultiLayerNetwork(_conf()).init()
            tr = ShardedTrainer(net, MeshSpec.data_parallel(),
                                devices=_mesh8())
            rt = ResilientTrainer(tr, str(tmp_path / subdir),
                                  elastic=elastic_arg, max_restarts=3)
            plan = faults.FaultPlan([faults.FaultSpec(
                "train.step", "crash", rate=1.0, count=1)], seed=11)
            with faults.active(plan):
                rt.fit(ArrayDataSetIterator(x, y, 16), epochs=1)
            return net

        monkeypatch.setenv("DL4J_TPU_ELASTIC", "0")
        a = run("killswitch", True)
        assert not os.path.isdir(str(tmp_path / "killswitch" / "elastic")) \
            or not any(n.startswith("manifest_") for n in
                       os.listdir(tmp_path / "killswitch" / "elastic"))
        assert any(n.endswith(".zip") for n in
                   os.listdir(tmp_path / "killswitch"))
        monkeypatch.delenv("DL4J_TPU_ELASTIC")
        faults.reset()
        b = run("plain", False)
        np.testing.assert_array_equal(np.asarray(a.params().buf()),
                                      np.asarray(b.params().buf()))
        # host_loss is inert under the kill switch: the spec never fires
        monkeypatch.setenv("DL4J_TPU_ELASTIC", "0")
        faults.reset()
        net = MultiLayerNetwork(_conf()).init()
        tr = ShardedTrainer(net, MeshSpec.data_parallel(), devices=_mesh8())
        rt = ResilientTrainer(tr, str(tmp_path / "inert"), elastic=True)
        plan = faults.FaultPlan([faults.FaultSpec(
            "allreduce", "host_loss", rate=1.0)], seed=1)
        with faults.active(plan):
            rt.fit(ArrayDataSetIterator(x, y, 16), epochs=1)
        assert rt.restarts == 0
        assert elastic.global_capacity().available() == len(jax.devices())

    def test_subset_trainer_stays_inside_its_device_pool(self, tmp_path,
                                                         monkeypatch):
        """A trainer configured on a device SUBSET must never be
        'expanded' onto devices it was not given (capacity is global,
        the pool is the trainer's), and a healthy run must not reshape
        at all."""
        monkeypatch.setenv("DL4J_TPU_ELASTIC_RECOVER_STEPS", "1")
        _mesh8()
        x, y = _data(64)
        net = MultiLayerNetwork(_conf()).init()
        tr = ShardedTrainer(net, MeshSpec.data_parallel(),
                            devices=jax.devices()[:4])
        rt = ResilientTrainer(tr, str(tmp_path), elastic=True,
                              max_restarts=3)
        rt.fit(ArrayDataSetIterator(x, y, 16), epochs=1)
        assert tr.mesh.size == 4                  # no phantom expansion
        reg = global_registry()
        ctr = reg.get("dl4j_elastic_reshapes_total")
        assert ctr is None or ctr.labels(direction="expand").value == 0
        # host loss: shrink WITHIN the pool, re-expand back to 4, not 8
        plan = faults.FaultPlan([faults.FaultSpec(
            "allreduce", "host_loss", rate=1.0, count=1)], seed=2)
        with faults.active(plan):
            rt.fit(ArrayDataSetIterator(x, y, 16), epochs=1)
        assert tr.mesh.size == 4
        used = {d.id for d in tr.mesh.devices.flat}
        assert used <= {d.id for d in jax.devices()[:4]}
        ctr = global_registry().get("dl4j_elastic_reshapes_total")
        assert ctr.labels(direction="shrink").value >= 1

    def test_host_loss_spec_point_validation_and_fire(self):
        with pytest.raises(ValueError):
            faults.FaultSpec("checkpoint.save", "host_loss")
        plan = faults.FaultPlan([faults.FaultSpec(
            "train.step", "host_loss", rate=1.0, count=1)])
        with faults.active(plan):
            with pytest.raises(HostLostError) as ei:
                faults.check("train.step")
        assert ei.value.lost >= 1
        # capacity dropped BEFORE the error propagated
        assert elastic.global_capacity().available() \
            == len(jax.devices()) - ei.value.lost
        ctr = global_registry().get("dl4j_faults_injected_total")
        assert ctr.labels(point="train.step", kind="host_loss").value == 1


# ------------------------------------------------------- subprocess drills
def _run_drill(args, timeout=60):
    env = dict(os.environ)
    env.pop("JAX_PLATFORMS", None)
    env.pop("JAX_NUM_CPU_DEVICES", None)
    env["PYTHONPATH"] = REPO + os.pathsep + env.get("PYTHONPATH", "")
    p = subprocess.run([sys.executable, WORKER, "drill"] + args,
                       capture_output=True, text=True, env=env,
                       timeout=timeout)
    return p


@pytest.mark.slow
def test_drill_sigkill_shrink_reexpand_loss_parity(tmp_path):
    """The full elastic drill across REAL process boundaries: SIGKILL
    mid-epoch on an 8-device mesh → relaunch with 4 devices (reshaping
    restore) → relaunch with 8 (re-expand) → final loss within
    tolerance of an uninterrupted 8-device run."""
    steps = 8
    ref_out = str(tmp_path / "ref.npy")
    p = _run_drill(["--devices", "8", "--ckpt", str(tmp_path / "ck_ref"),
                    "--steps", str(steps), "--out", ref_out])
    assert p.returncode == 0, p.stdout[-3000:] + p.stderr[-2000:]

    ck = str(tmp_path / "ck")
    out = str(tmp_path / "drill.npy")
    p = _run_drill(["--devices", "8", "--ckpt", ck, "--steps", str(steps),
                    "--out", out, "--die-at", "2"])
    assert p.returncode == -9, p.stdout[-3000:] + p.stderr[-2000:]
    assert "SIGKILL_AT 2" in p.stdout
    assert not os.path.exists(out)

    # the pod came back SMALLER: resume the same schedule on 4 devices
    p = _run_drill(["--devices", "4", "--ckpt", ck, "--steps", "5",
                    "--out", out])
    assert p.returncode == 0, p.stdout[-3000:] + p.stderr[-2000:]
    assert "RESUMED_AT 3" in p.stdout

    # capacity returned: finish on the full 8-device mesh
    p = _run_drill(["--devices", "8", "--ckpt", ck, "--steps", str(steps),
                    "--out", out])
    assert p.returncode == 0, p.stdout[-3000:] + p.stderr[-2000:]
    assert "RESUMED_AT 5" in p.stdout

    ref = json.load(open(ref_out + ".json"))
    got = json.load(open(out + ".json"))
    assert got["iteration"] == steps
    assert abs(got["final_loss"] - ref["final_loss"]) <= \
        max(1e-3, 0.02 * abs(ref["final_loss"]))
    np.testing.assert_allclose(np.load(out), np.load(ref_out),
                               rtol=1e-3, atol=1e-4)


@pytest.mark.slow
def test_drill_sigterm_preemption_saves_and_resumes_once(tmp_path):
    """A REAL SIGTERM through utils/preemption.py: the worker saves a
    final manifest, exits nonzero, and the relaunch resumes EXACTLY
    once from it and completes."""
    steps = 6
    ck = str(tmp_path / "ck")
    out = str(tmp_path / "out.npy")
    p = _run_drill(["--devices", "8", "--ckpt", ck, "--steps", str(steps),
                    "--out", out, "--sigterm-at", "3"])
    assert p.returncode == 75, p.stdout[-3000:] + p.stderr[-2000:]
    assert "PREEMPTED_SAVED 3" in p.stdout
    assert not os.path.exists(out)

    p = _run_drill(["--devices", "8", "--ckpt", ck, "--steps", str(steps),
                    "--out", out])
    assert p.returncode == 0, p.stdout[-3000:] + p.stderr[-2000:]
    assert p.stdout.count("RESUMED_AT") == 1     # exactly one resume
    assert "RESUMED_AT 3" in p.stdout
    got = json.load(open(out + ".json"))
    assert got["resumed_at"] == 3 and got["iteration"] == steps
