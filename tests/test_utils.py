"""Imaging, profiling, checkpoint utility tests."""

import os

import jax
import numpy as np
import optax
import pytest

from multidisttorch_tpu.models.vae import VAE
from multidisttorch_tpu.parallel.mesh import setup_groups
from multidisttorch_tpu.train.checkpoint import restore_state, save_state
from multidisttorch_tpu.train.steps import create_train_state, make_train_step
from multidisttorch_tpu.utils.imaging import save_image_grid
from multidisttorch_tpu.utils.profiling import trial_timer


class TestImaging:
    def test_grayscale_grid(self, tmp_path):
        imgs = np.random.default_rng(0).uniform(0, 1, (16, 784))
        path = save_image_grid(imgs, str(tmp_path / "grid.png"), nrow=8)
        assert path.endswith(".png") or path.endswith(".npy")
        assert os.path.exists(path)
        if path.endswith(".png"):
            from PIL import Image

            im = Image.open(path)
            assert im.size == (8 * 28, 2 * 28)

    def test_rgb_grid(self, tmp_path):
        imgs = np.random.default_rng(0).uniform(0, 1, (4, 32 * 32 * 3))
        path = save_image_grid(imgs, str(tmp_path / "rgb.png"), nrow=4)
        if path.endswith(".png"):
            from PIL import Image

            im = Image.open(path)
            assert im.mode == "RGB"
            assert im.size == (4 * 32, 32)

    def test_3d_input(self, tmp_path):
        imgs = np.zeros((3, 28, 28))
        path = save_image_grid(imgs, str(tmp_path / "g3.png"), nrow=2)
        assert os.path.exists(path)


class TestCheckpoint:
    def test_roundtrip_across_submeshes(self, tmp_path):
        # Save a trained state from one submesh, restore onto another —
        # the checkpoint-restart and PBT-transfer mechanism.
        model = VAE(hidden_dim=16, latent_dim=4)
        tx = optax.adam(1e-3)
        g0, g1 = setup_groups(2)
        state = create_train_state(g0, model, tx, jax.random.key(0))
        step = make_train_step(g0, model, tx)
        batch = jax.numpy.asarray(
            np.random.default_rng(0).uniform(0, 1, (8, 784)).astype(np.float32)
        )
        state, _ = step(state, batch, jax.random.key(1))

        path = save_state(state, str(tmp_path / "ck" / "state.msgpack"),
                          metadata={"trial": 0})
        assert os.path.exists(path)
        assert os.path.exists(path + ".json")

        template = create_train_state(g1, model, tx, jax.random.key(9))
        restored = restore_state(template, path, trial=g1)
        assert int(restored.step) == 1
        jax.tree.map(
            lambda a, b: np.testing.assert_array_equal(
                np.asarray(a), np.asarray(b)
            ),
            jax.device_get(restored.params),
            jax.device_get(state.params),
        )
        # restored state is live on the new submesh: take a step with it
        step1 = make_train_step(g1, model, tx)
        restored, m = step1(restored, batch, jax.random.key(2))
        assert np.isfinite(float(m["loss_sum"]))

    def test_sharded_state_roundtrip_keeps_sharding(self, tmp_path):
        # A TP-sharded state must restore SHARDED (round-4: restore_state
        # grew a shardings= arg; without it the restore lands replicated
        # and the memory benefit silently evaporates).
        from multidisttorch_tpu.models.vae import vae_tp_shardings
        from multidisttorch_tpu.train.steps import state_shardings

        model = VAE(hidden_dim=16, latent_dim=4)
        tx = optax.adam(1e-3)
        (g,) = setup_groups(1, model_parallel=4)
        state = create_train_state(
            g, model, tx, jax.random.key(0),
            param_shardings=vae_tp_shardings(g),
        )
        sh = state_shardings(state)
        step = make_train_step(g, model, tx, shardings=sh)
        batch = jax.device_put(
            jax.numpy.asarray(
                np.random.default_rng(1)
                .uniform(0, 1, (8, 784))
                .astype(np.float32)
            ),
            g.batch_sharding,
        )
        state, _ = step(state, batch, jax.random.key(1))

        path = save_state(state, str(tmp_path / "tp" / "state.msgpack"))
        restored = restore_state(state, path, trial=g, shardings=sh)
        k = restored.params["fc1"]["kernel"]
        assert k.addressable_shards[0].data.shape == (784, 4)  # 16/4
        # values identical and training continues sharded
        jax.tree.map(
            lambda a, b: np.testing.assert_array_equal(
                np.asarray(a), np.asarray(b)
            ),
            jax.device_get(restored.params),
            jax.device_get(state.params),
        )
        restored, m = step(restored, batch, jax.random.key(2))
        assert np.isfinite(float(m["loss_sum"]))


class TestProfiling:
    def test_trial_timer_prints_reference_format(self, capsys):
        with trial_timer("trial 3", printer=print):
            pass
        out = capsys.readouterr().out
        assert "trial 3 Done. time:" in out
