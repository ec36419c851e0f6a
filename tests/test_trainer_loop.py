"""Tests for the high-level Trainer (AtorchTrainer analogue): train,
checkpoint, resume, eval. Reference coverage analogue:
atorch/tests trainer tests.
"""


import jax
import jax.numpy as jnp
import numpy as np
import pytest

from dlrover_tpu.agent.ckpt_saver import AsyncCheckpointSaver
from dlrover_tpu.trainer.trainer import Trainer, TrainingArgs


@pytest.fixture(autouse=True)
def _isolate(isolated_ckpt_env):
    """Delegates to the shared shm/saver isolation fixture
    (tests/conftest.py)."""
    yield

def linear_problem():
    def init_fn(rng):
        return {"w": jnp.zeros((8, 1)), "b": jnp.zeros((1,))}

    def loss_fn(params, batch, rng):
        x, y = batch
        pred = x @ params["w"] + params["b"]
        return jnp.mean((pred - y) ** 2)

    axes = {"w": ("embed", None), "b": (None,)}
    rs = np.random.RandomState(0)
    w_true = rs.randn(8, 1).astype(np.float32)

    def batches(n=16, bs=8):
        out = []
        for _ in range(n):
            x = rs.randn(bs, 8).astype(np.float32)
            out.append((x, x @ w_true))
        return out

    return loss_fn, init_fn, axes, batches


def make_args(tmp_path, **kw):
    d = dict(
        output_dir=str(tmp_path / "out"),
        micro_batch_size=8,
        learning_rate=5e-2,
        log_steps=0,
        flash_checkpoint=False,
    )
    d.update(kw)
    return TrainingArgs(**d)


class TestTrainerBasics:
    def test_trains_to_low_loss(self, tmp_path):
        loss_fn, init_fn, axes, batches = linear_problem()
        trainer = Trainer(
            loss_fn, init_fn, axes, make_args(tmp_path, num_epochs=20),
            train_data=batches(),
        )
        _, metrics = trainer.train()
        assert float(metrics["loss"]) < 0.05
        assert trainer.global_step == 20 * 16

    def test_max_steps_stops(self, tmp_path):
        loss_fn, init_fn, axes, batches = linear_problem()
        trainer = Trainer(
            loss_fn, init_fn, axes,
            make_args(tmp_path, num_epochs=100, max_steps=7),
            train_data=batches(),
        )
        trainer.train()
        assert trainer.global_step == 7

    def test_evaluate(self, tmp_path):
        loss_fn, init_fn, axes, batches = linear_problem()
        trainer = Trainer(
            loss_fn, init_fn, axes, make_args(tmp_path, max_steps=30),
            train_data=batches(),
            eval_data=batches(4),
        )
        trainer.train()
        loss = trainer.evaluate()
        assert np.isfinite(loss)

    @pytest.mark.parametrize("opt", ["sgd", "agd", "adam8bit", "adamw"])
    def test_optimizer_selection(self, tmp_path, opt):
        loss_fn, init_fn, axes, batches = linear_problem()
        trainer = Trainer(
            loss_fn, init_fn, axes,
            make_args(tmp_path, max_steps=5, optimizer=opt),
            train_data=batches(),
        )
        _, metrics = trainer.train()
        assert np.isfinite(float(metrics["loss"]))


class TestTrainerCheckpointResume:
    def test_save_and_resume(self, tmp_path):
        loss_fn, init_fn, axes, batches = linear_problem()
        data = batches()
        args = make_args(
            tmp_path, max_steps=10, flash_checkpoint=True, save_steps=5
        )
        t1 = Trainer(loss_fn, init_fn, axes, args, train_data=data)
        t1.train()
        w_after = np.asarray(t1.state.params["w"])
        step_after = t1.global_step
        t1.close()

        # new trainer in the same job/output: resumes, does NOT restart
        t2 = Trainer(loss_fn, init_fn, axes, args, train_data=data)
        restored = t2.maybe_resume()
        assert restored == step_after
        np.testing.assert_allclose(
            np.asarray(t2.state.params["w"]), w_after, rtol=1e-6
        )
        t2.close()

    def test_resume_from_storage_after_shm_loss(self, tmp_path):
        """Simulates a full host restart: shm gone, storage survives."""
        loss_fn, init_fn, axes, batches = linear_problem()
        data = batches()
        args = make_args(
            tmp_path, max_steps=6, flash_checkpoint=True
        )
        t1 = Trainer(loss_fn, init_fn, axes, args, train_data=data)
        t1.train()  # final save persists to storage
        w_after = np.asarray(t1.state.params["w"])
        t1._engine._shm_handler.close(unlink=True)  # kill shm
        t1.close()
        AsyncCheckpointSaver.reset()

        t2 = Trainer(loss_fn, init_fn, axes, args, train_data=data)
        restored = t2.maybe_resume()
        assert restored == 6
        np.testing.assert_allclose(
            np.asarray(t2.state.params["w"]), w_after, rtol=1e-6
        )
        t2.close()


    def test_resume_replaces_the_fresh_state_in_place(self, tmp_path):
        """A restore lands INTO the state's place: the freshly
        initialised buffers are freed before the restored arrays
        arrive, so a state over half the device memory can resume (two
        resident copies would not fit the chip)."""
        loss_fn, init_fn, axes, batches = linear_problem()
        data = batches()
        args = make_args(
            tmp_path, max_steps=4, flash_checkpoint=True
        )
        t1 = Trainer(loss_fn, init_fn, axes, args, train_data=data)
        t1.train()
        w_after = np.asarray(t1.state.params["w"])
        t1.close()

        t2 = Trainer(loss_fn, init_fn, axes, args, train_data=data)
        fresh = jax.tree.leaves(t2.state)
        shardings = [x.sharding for x in fresh]
        assert t2.maybe_resume() == 4
        assert all(x.is_deleted() for x in fresh)
        restored = jax.tree.leaves(t2.state)
        assert [x.sharding for x in restored] == shardings
        np.testing.assert_allclose(
            np.asarray(t2.state.params["w"]), w_after, rtol=1e-6
        )
        # ...and the step still runs on the restored state
        t2.args.max_steps = 6
        t2.train()
        assert t2.global_step == 6
        t2.close()

    def test_ignored_incompatible_checkpoint_reinitialises(
        self, tmp_path, monkeypatch
    ):
        """The fresh state was released for a restore that then found
        nothing usable (DLROVER_TPU_IGNORE_CKPT): the trainer
        initialises again and trains from scratch."""
        loss_fn, init_fn, axes, batches = linear_problem()
        data = batches()
        args = make_args(
            tmp_path, max_steps=4, flash_checkpoint=True
        )
        t1 = Trainer(loss_fn, init_fn, axes, args, train_data=data)
        t1.train()
        t1.close()

        def wider_init(rng):
            return {"w": jnp.zeros((8, 2)), "b": jnp.zeros((2,))}

        monkeypatch.setenv("DLROVER_TPU_IGNORE_CKPT", "1")
        t2 = Trainer(loss_fn, wider_init, axes, args, train_data=data)
        assert t2.maybe_resume() == 0
        assert t2.state.params["w"].shape == (8, 2)
        assert not any(
            x.is_deleted() for x in jax.tree.leaves(t2.state)
        )
        t2.close()


class TestTrainerDataStateResume:
    def _make_loader(self):
        from dlrover_tpu.trainer.elastic.dataloader import (
            ElasticDataLoader,
        )

        rs = np.random.RandomState(1)
        w_true = rs.randn(8, 1).astype(np.float32)
        xs = rs.randn(64, 8).astype(np.float32)
        dataset = [(xs[i], xs[i] @ w_true) for i in range(64)]
        return ElasticDataLoader(dataset, batch_size=8, config_file="")

    def test_mid_epoch_resume_restores_dataloader(self, tmp_path):
        """A restarted job must pick up the epoch where it left off, not
        replay from offset 0 (reference AtorchTrainer persists sampler
        state with the checkpoint)."""
        loss_fn, init_fn, axes, _ = linear_problem()
        args = make_args(
            tmp_path, max_steps=3, flash_checkpoint=True, num_epochs=1
        )
        t1 = Trainer(loss_fn, init_fn, axes, args,
                     train_data=self._make_loader())
        t1.train()  # 3 steps of 8 samples; final ckpt carries data state
        consumed = t1.train_data.sampler.completed_num
        assert consumed == 24
        t1.close()
        AsyncCheckpointSaver.reset()

        loader2 = self._make_loader()
        t2 = Trainer(loss_fn, init_fn, axes, args, train_data=loader2)
        restored = t2.maybe_resume()
        assert restored == 3
        assert loader2.sampler.completed_num == consumed
        t2.close()

    def test_pre_wrapper_checkpoint_still_restores(self, tmp_path):
        """Checkpoints written before the {'train','data'} wrapper (bare
        train-state leaves) must keep restoring."""
        import os

        from dlrover_tpu.trainer.flash_checkpoint.engine import (
            ShardedCheckpointEngine,
        )

        loss_fn, init_fn, axes, _ = linear_problem()
        args = make_args(
            tmp_path, max_steps=3, flash_checkpoint=True, num_epochs=1
        )
        t1 = Trainer(loss_fn, init_fn, axes, args,
                     train_data=self._make_loader())
        t1.train()
        old_state = t1.state
        t1.close()
        AsyncCheckpointSaver.reset()
        # overwrite with an old-layout (bare state) checkpoint
        eng = ShardedCheckpointEngine(
            os.path.join(args.output_dir, "checkpoints")
        )
        assert eng.save_to_storage(7, old_state)
        assert eng.wait_for_persist(7, timeout=60)
        eng.close()
        AsyncCheckpointSaver.reset()

        t2 = Trainer(loss_fn, init_fn, axes, args,
                     train_data=self._make_loader())
        assert t2.maybe_resume() == 7
        np.testing.assert_allclose(
            np.asarray(t2.state.params["w"]),
            np.asarray(old_state.params["w"]), rtol=1e-6,
        )
        t2.close()

    def test_resumed_epoch_not_reset(self, tmp_path):
        """train() after resume must not set_epoch() on the resumed
        epoch (it would clear the mid-epoch offset)."""
        loss_fn, init_fn, axes, _ = linear_problem()
        args = make_args(
            tmp_path, max_steps=3, flash_checkpoint=True, num_epochs=2
        )
        t1 = Trainer(loss_fn, init_fn, axes, args,
                     train_data=self._make_loader())
        t1.train()
        t1.close()
        AsyncCheckpointSaver.reset()

        loader2 = self._make_loader()
        args2 = make_args(
            tmp_path, max_steps=5, flash_checkpoint=True, num_epochs=2
        )
        t2 = Trainer(loss_fn, init_fn, axes, args2, train_data=loader2)
        t2.train()
        # resumed at 24/64 consumed; 2 more steps -> 40, same epoch
        assert t2.global_step == 5
        assert loader2.sampler.epoch == 0
        assert loader2.sampler.completed_num == 40
        t2.close()
