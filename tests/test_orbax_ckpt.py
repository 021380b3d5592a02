"""Sharded orbax checkpointing (SURVEY §5.4 TPU-equivalent): save sharded,
restore re-sharded onto a different layout, rotation, and trainer
integration on the 8-device virtual CPU mesh."""
import os

import jax
import jax.numpy as jnp
import numpy as np
import pytest
from conftest import time_limit
from jax.sharding import NamedSharding, PartitionSpec as P

from deeplearning4j_tpu.parallel.mesh import MeshSpec
from deeplearning4j_tpu.utils.orbax_ckpt import (ShardedCheckpointer,
                                                 ShardedCheckpointListener,
                                                 abstract_like)


def _mesh():
    return MeshSpec.data_parallel().build(jax.devices()[:8])


class TestShardedCheckpointer:
    def test_save_restore_roundtrip(self, tmp_path):
        state = {"params": {"w": jnp.arange(16.0).reshape(4, 4),
                            "b": jnp.ones((4,))},
                 "step": 7}
        with ShardedCheckpointer(str(tmp_path / "ck"),
                                 async_save=False) as ck:
            ck.save(7, state)
            got = ck.restore()
        np.testing.assert_allclose(np.asarray(got["params"]["w"]),
                                   np.asarray(state["params"]["w"]))
        assert int(np.asarray(got["step"])) == 7

    def test_sharded_save_resharded_restore(self, tmp_path):
        mesh = _mesh()
        sh_row = NamedSharding(mesh, P("data", None))
        sh_col = NamedSharding(mesh, P(None, "data"))
        w = jax.device_put(jnp.arange(64.0).reshape(8, 8), sh_row)
        with ShardedCheckpointer(str(tmp_path / "ck"),
                                 async_save=False) as ck:
            ck.save(1, {"w": w})
            like = {"w": jax.ShapeDtypeStruct((8, 8), jnp.float32,
                                              sharding=sh_col)}
            got = ck.restore(like=like)
        assert got["w"].sharding.spec == P(None, "data")
        np.testing.assert_allclose(np.asarray(got["w"]),
                                   np.arange(64.0).reshape(8, 8))

    def test_rotation_keeps_last_n(self, tmp_path):
        with ShardedCheckpointer(str(tmp_path / "ck"), max_to_keep=2,
                                 async_save=False) as ck:
            for s in (1, 2, 3, 4):
                ck.save(s, {"x": jnp.asarray(float(s))})
            assert ck.all_steps() == [3, 4]
            assert ck.latest_step() == 4

    def test_async_save_then_wait(self, tmp_path):
        with ShardedCheckpointer(str(tmp_path / "ck"),
                                 async_save=True) as ck:
            ck.save(1, {"x": jnp.ones((128,))})
            with time_limit(60.0):
                ck.wait()
            assert ck.latest_step() == 1

    def test_abstract_like_builder(self):
        mesh = _mesh()
        sh = NamedSharding(mesh, P("data"))
        tree = {"a": jnp.zeros((8, 2)), "b": jnp.zeros((8,))}
        like = abstract_like(tree, sh)
        assert like["a"].sharding is sh and like["a"].shape == (8, 2)


class TestTrainerIntegration:
    @pytest.mark.slow
    def test_listener_checkpoints_and_resume(self, tmp_path):
        from deeplearning4j_tpu.models import zoo

        net = zoo.LeNet().init_model()
        rng = np.random.RandomState(0)
        x = rng.rand(8, 784).astype("float32")
        y = np.eye(10, dtype="float32")[rng.randint(0, 10, 8)]
        lst = ShardedCheckpointListener(str(tmp_path / "ck"),
                                        every_n_iterations=2,
                                        async_save=False)
        net.setListeners(lst)
        for _ in range(4):
            net.fit(x, y)
        lst.close()

        ck = ShardedCheckpointer(str(tmp_path / "ck"), async_save=False)
        assert ck.latest_step() == 4
        # resume: restore with the fresh net's state as the structure
        # template (preserves optax NamedTuple state types), then continue
        net2 = zoo.LeNet().init_model()
        like = {"params": abstract_like(net2._params),
                "opt_state": abstract_like(net2._opt_state),
                "states": abstract_like(net2._states),
                "iteration": 0, "epoch": 0}
        got = ck.restore(like=like)
        net2._params = got["params"]
        net2._opt_state = got["opt_state"]
        net2.fit(x, y)
        assert np.isfinite(net2.score())
        ck.close()
