"""Flash-checkpoint tests: shm save/restore, async persistence, sharded
(GSPMD) save with reassembly, breakpoint flush (reference
test_ckpt_saver.py pattern: everything in one process, shm + unix-socket
queues work intra-process)."""


import jax
import jax.numpy as jnp
import numpy as np
import pytest
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

from dlrover_tpu.agent.ckpt_saver import AsyncCheckpointSaver
from dlrover_tpu.trainer.flash_checkpoint import (
    FlashCheckpointer,
    StorageType,
)
from dlrover_tpu.trainer.flash_checkpoint.engine import (
    ReplicatedCheckpointEngine,
    ShardedCheckpointEngine,
)


@pytest.fixture(autouse=True)
def _isolate_ipc(isolated_ckpt_env):
    """Delegates to the shared shm/saver isolation fixture
    (tests/conftest.py)."""
    yield

def make_state(seed=0):
    k = jax.random.PRNGKey(seed)
    return {
        "params": {
            "w": jax.random.normal(k, (16, 8), dtype=jnp.float32),
            "b": jnp.zeros((8,), dtype=jnp.float32),
        },
        "step_count": jnp.asarray(3, dtype=jnp.int32),
    }


def trees_equal(a, b):
    flat_a = jax.tree_util.tree_leaves(a)
    flat_b = jax.tree_util.tree_leaves(b)
    return all(
        np.allclose(np.asarray(x), np.asarray(y))
        for x, y in zip(flat_a, flat_b)
    )


class TestReplicatedEngine:
    def test_memory_save_and_restore(self, tmp_path):
        engine = ReplicatedCheckpointEngine(str(tmp_path / "ckpt"))
        state = make_state()
        assert engine.save_to_memory(10, state)
        target = jax.tree.map(jnp.zeros_like, state)
        restored, step = engine.load(target=target)
        assert step == 10
        assert trees_equal(restored, state)
        engine.close()

    def test_disk_persist_and_restore(self, tmp_path):
        ckpt_dir = str(tmp_path / "ckpt")
        engine = ReplicatedCheckpointEngine(ckpt_dir)
        state = make_state()
        assert engine.save_to_storage(20, state)
        assert engine.wait_for_persist(20, timeout=30)
        # simulate a full restart: wipe shm, load from disk
        engine._shm_handler.mark_empty()
        restored, step = engine.load(target=jax.tree.map(jnp.zeros_like, state))
        assert step == 20
        assert trees_equal(restored, state)
        assert AsyncCheckpointSaver.get_latest_step(ckpt_dir) == 20
        engine.close()

    def test_shm_restore_beats_disk(self, tmp_path):
        """Memory restore works with no disk files at all (in-memory
        recovery after a worker-only crash)."""
        engine = ReplicatedCheckpointEngine(str(tmp_path / "ckpt"))
        state = make_state(1)
        engine.save_to_memory(5, state)
        restored, step = engine.load(
            target=jax.tree.map(jnp.zeros_like, state)
        )
        assert step == 5 and trees_equal(restored, state)
        engine.close()

    def test_breakpoint_flush(self, tmp_path):
        """Worker dies with a shm-only checkpoint; the agent flushes it
        to storage (save_shm_to_storage)."""
        ckpt_dir = str(tmp_path / "ckpt")
        engine = ReplicatedCheckpointEngine(ckpt_dir)
        state = make_state(2)
        engine.save_to_memory(7, state)  # never asked for disk
        saver = AsyncCheckpointSaver.get_ckpt_saver()
        saver.save_shm_to_storage()
        assert AsyncCheckpointSaver.get_latest_step(ckpt_dir) == 7
        engine._shm_handler.mark_empty()
        restored, step = engine.load(
            target=jax.tree.map(jnp.zeros_like, state)
        )
        assert step == 7 and trees_equal(restored, state)
        engine.close()


class TestShardedEngine:
    def _sharded_state(self, mesh):
        k = jax.random.PRNGKey(0)
        w = jax.device_put(
            jax.random.normal(k, (16, 8), dtype=jnp.float32),
            NamedSharding(mesh, P("dp", None)),
        )
        b = jax.device_put(
            jnp.arange(8, dtype=jnp.float32),
            NamedSharding(mesh, P(None)),
        )
        return {"w": w, "b": b}

    def test_sharded_save_restore_same_mesh(self, tmp_path):
        mesh = Mesh(np.array(jax.devices()).reshape(4, 2), ("dp", "tp"))
        state = self._sharded_state(mesh)
        engine = ShardedCheckpointEngine(str(tmp_path / "ckpt"))
        assert engine.save_to_storage(30, state)
        assert engine.wait_for_persist(30, timeout=30)
        engine._shm_handler.mark_empty()
        target = jax.tree.map(
            lambda x: jax.device_put(jnp.zeros_like(x), x.sharding), state
        )
        restored, step = engine.load(target=target)
        assert step == 30
        assert trees_equal(restored, state)
        # restored arrays keep the target sharding
        assert restored["w"].sharding == state["w"].sharding
        engine.close()

    def test_sharded_restore_to_different_mesh(self, tmp_path):
        """Topology change: save on a (4,2) mesh, restore onto (2,4)."""
        mesh1 = Mesh(np.array(jax.devices()).reshape(4, 2), ("dp", "tp"))
        state = self._sharded_state(mesh1)
        engine = ShardedCheckpointEngine(str(tmp_path / "ckpt"))
        assert engine.save_to_storage(40, state)
        assert engine.wait_for_persist(40, timeout=30)
        engine._shm_handler.mark_empty()
        mesh2 = Mesh(np.array(jax.devices()).reshape(2, 4), ("dp", "tp"))
        target = {
            "w": jax.device_put(
                jnp.zeros((16, 8)), NamedSharding(mesh2, P("tp", "dp"))
            ),
            "b": jax.device_put(
                jnp.zeros((8,)), NamedSharding(mesh2, P(None))
            ),
        }
        restored, step = engine.load(target=target)
        assert step == 40
        assert trees_equal(restored, state)
        assert restored["w"].sharding == target["w"].sharding
        engine.close()

    def test_resharded_restore_is_shard_wise(self, tmp_path):
        """Restoring into a DIFFERENT mesh must not materialise full
        global arrays on the host (the 7B north-star would OOM): each
        target shard memmap-reads only its intersecting saved byte
        ranges, so peak host allocation stays ~one shard."""
        

        mesh1 = Mesh(np.array(jax.devices()), ("dp",))
        G = (8192, 512)  # 16 MiB fp32
        big = jax.device_put(
            jnp.arange(G[0] * G[1], dtype=jnp.float32).reshape(G),
            NamedSharding(mesh1, P("dp", None)),
        )
        engine = ShardedCheckpointEngine(str(tmp_path / "ckpt"))
        assert engine.save_to_storage(70, {"big": big})
        assert engine.wait_for_persist(70, timeout=30)
        engine._shm_handler.mark_empty()

        mesh2 = Mesh(np.array(jax.devices()).reshape(2, 4), ("dp", "tp"))
        target = {
            "big": jax.device_put(
                jnp.zeros(G), NamedSharding(mesh2, P("tp", "dp"))
            ),
        }
        host_ref = np.asarray(jax.device_get(big))
        # instrument host staging allocations: the shard-wise path's
        # biggest single buffer is ONE target shard (2 MiB), where the
        # old path allocated the 16 MiB global
        import dlrover_tpu.trainer.flash_checkpoint.engine as eng_mod

        allocs = []
        real_empty = np.empty

        def tracking_empty(shape, *a, **kw):
            arr = real_empty(shape, *a, **kw)
            allocs.append(arr.nbytes)
            return arr

        orig = eng_mod.np.empty
        eng_mod.np.empty = tracking_empty
        try:
            restored, step = engine.load(target=target)
        finally:
            eng_mod.np.empty = orig
        assert step == 70
        np.testing.assert_array_equal(
            np.asarray(jax.device_get(restored["big"])), host_ref
        )
        assert restored["big"].sharding == target["big"].sharding
        assert allocs, "no staging allocations traced"
        assert max(allocs) <= 2 * (1 << 20), (
            f"largest staging alloc {max(allocs)>>20} MiB — full-global "
            f"materialisation crept back in"
        )
        engine.close()

    def test_shard_dedup(self, tmp_path):
        """Replicated-axis shards are written once, not once per device."""
        mesh = Mesh(np.array(jax.devices()).reshape(4, 2), ("dp", "tp"))
        state = self._sharded_state(mesh)
        engine = ShardedCheckpointEngine(str(tmp_path / "ckpt"))
        engine.save_to_memory(50, state)
        meta, _ = engine._shm_handler.read()
        w_leaves = [l for l in meta.leaves if "w" in l.path]
        b_leaves = [l for l in meta.leaves if "b" in l.path]
        assert len(w_leaves) == 4  # dp shards, tp-replicas deduped
        assert len(b_leaves) == 1  # fully replicated -> a single copy
        engine.close()


class TestCheckpointerAPI:
    def test_checkpointer_roundtrip(self, tmp_path):
        ckpt = FlashCheckpointer(
            str(tmp_path / "ckpt"), sharded=False, master_client=None
        )
        state = make_state()
        assert ckpt.save_checkpoint(
            11, state, storage_type=StorageType.MEMORY
        )
        restored, step = ckpt.load_checkpoint(
            target=jax.tree.map(jnp.zeros_like, state)
        )
        assert step == 11 and trees_equal(restored, state)
        ckpt.close()

    def test_skip_when_lock_busy(self, tmp_path):
        ckpt = FlashCheckpointer(
            str(tmp_path / "ckpt"), sharded=False, master_client=None
        )
        state = make_state()
        ckpt.engine._shm_lock.acquire()
        try:
            assert not ckpt.save_checkpoint(
                12, state, storage_type=StorageType.MEMORY
            )
        finally:
            ckpt.engine._shm_lock.release()
        ckpt.close()


class TestReviewFixes:
    def test_no_views_into_shm(self, tmp_path):
        """load() without target must return copies, not shm views."""
        engine = ReplicatedCheckpointEngine(str(tmp_path / "ckpt"))
        s1 = {"w": jnp.ones((8,))}
        engine.save_to_memory(1, s1)
        restored = engine.load()
        w_before = restored["state"]["w"].copy()
        engine.save_to_memory(2, {"w": jnp.full((8,), 9.0)})
        assert np.allclose(restored["state"]["w"], w_before)
        engine.close()

    def test_agent_handler_refresh_after_regrow(self, tmp_path):
        """Saver must re-attach after the worker unlinks+recreates the
        segment on growth."""
        ckpt_dir = str(tmp_path / "ckpt")
        engine = ReplicatedCheckpointEngine(ckpt_dir)
        engine.save_to_memory(1, {"w": jnp.ones((8,))})
        saver = AsyncCheckpointSaver.get_ckpt_saver()
        saver.save_shm_to_storage()
        # grow the state massively -> segment recreated under same name
        big = {"w": jnp.ones((8,)), "big": jnp.zeros((1 << 16,))}
        engine.save_to_memory(2, big)
        saver.save_shm_to_storage()
        assert AsyncCheckpointSaver.get_latest_step(ckpt_dir) == 2
        engine.close()

    def test_stale_factory_socket_falls_back(self, tmp_path, monkeypatch):
        """A dead factory socket file must not brick the engine."""
        import pathlib

        from dlrover_tpu.common.ipc import socket_path

        sock = pathlib.Path(socket_path("queue", "ckpt_factory"))
        sock.parent.mkdir(parents=True, exist_ok=True)
        sock.touch()  # stale file, nothing listening
        engine = ReplicatedCheckpointEngine(str(tmp_path / "ckpt"))
        assert engine._standalone
        assert engine.save_to_memory(1, {"w": jnp.ones((4,))})
        engine.close()

    def test_shape_mismatch_raises(self, tmp_path):
        engine = ReplicatedCheckpointEngine(str(tmp_path / "ckpt"))
        engine.save_to_memory(1, {"w": jnp.ones((4,))})
        with pytest.raises(ValueError, match="refusing"):
            engine.load(target={"w": jnp.zeros((8, 8))})
        engine.close()

    def test_zero_copy_load_views(self, tmp_path):
        """zero_copy=True returns read-only views into shm (restart-path
        restore without the multi-GB defensive copy); the default load
        still returns independent writable copies."""
        import numpy as np

        engine = ReplicatedCheckpointEngine(str(tmp_path / "ckpt"))
        state = {"w": jnp.arange(1024, dtype=jnp.float32)}
        engine.save_to_memory(1, state)
        views = engine.load(zero_copy=True)["state"]
        assert not views["w"].flags.writeable
        np.testing.assert_array_equal(
            np.asarray(views["w"]), np.arange(1024, dtype=np.float32))
        copies = engine.load()["state"]
        assert copies["w"].flags.writeable
        # a new save rewrites the segment under the views (documented
        # contract), while the copy is unaffected
        engine.save_to_memory(2, {"w": jnp.zeros(1024, jnp.float32)})
        assert float(views["w"][5]) == 0.0
        assert float(copies["w"][5]) == 5.0
        engine.close()

    def test_dtype_mismatch_raises(self, tmp_path):
        """Same refusal as the shape path: a saved fp32 leaf must not
        silently restore into a bf16 target (ADVICE r3)."""
        engine = ReplicatedCheckpointEngine(str(tmp_path / "ckpt"))
        engine.save_to_memory(1, {"w": jnp.ones((4,), jnp.float32)})
        with pytest.raises(ValueError, match="dtype"):
            engine.load(target={"w": jnp.zeros((4,), jnp.bfloat16)})
        engine.close()


class TestAsyncSave:
    def test_async_save_matches_sync(self, tmp_path):
        """save_to_memory_async must produce the same restorable state
        as the blocking save."""
        engine = ReplicatedCheckpointEngine(str(tmp_path / "ckpt"))
        state = make_state(seed=5)
        assert engine.save_to_memory_async(11, state)
        assert engine.wait_for_shm_save(timeout=30)
        restored = engine.load()
        assert restored["step"] == 11
        flat = restored["state"]
        from dlrover_tpu.trainer.flash_checkpoint.engine import (
            _tree_flatten_with_names,
        )

        names, leaves, _ = _tree_flatten_with_names(state)
        want = dict(zip(names, leaves))
        for name, arr in flat.items():
            np.testing.assert_allclose(
                np.asarray(arr), np.asarray(want[name]), rtol=1e-6
            )
        engine.close()

    def test_second_async_save_skipped_while_busy(self, tmp_path):
        engine = ReplicatedCheckpointEngine(str(tmp_path / "ckpt"))
        state = make_state()
        assert engine.save_to_memory_async(1, state)
        assert engine.wait_for_shm_save(timeout=30)
        # force the busy branch by holding the shm lock ourselves
        assert engine._shm_lock.acquire(blocking=False)
        try:
            assert not engine.save_to_memory_async(2, state)
        finally:
            engine._shm_lock.release()
        engine.close()

    def test_async_then_sync_sequence(self, tmp_path):
        engine = ReplicatedCheckpointEngine(str(tmp_path / "ckpt"))
        s1, s2 = make_state(seed=1), make_state(seed=2)
        assert engine.save_to_memory_async(1, s1)
        assert engine.wait_for_shm_save(timeout=30)
        assert engine.save_to_memory(2, s2)
        assert engine.load()["step"] == 2
        engine.close()


class TestSaveAtBreakpoint:
    def test_agent_flushes_shm_on_worker_failure(
        self, tmp_path, local_master
    ):
        """Worker writes a shm checkpoint then dies with no retries
        left; --save-at-breakpoint flushes it to storage before the
        agent gives up (reference _save_ckpt_to_storage :589)."""
        from dlrover_tpu.agent.master_client import MasterClient
        from dlrover_tpu.agent.training_agent import (
            ElasticLaunchConfig,
            ElasticTrainingAgent,
            WorkerSpec,
        )
        from dlrover_tpu.common.constants import NodeType

        ckpt_dir = tmp_path / "bp_ckpt"
        script = tmp_path / "bp.py"
        script.write_text(
            "import os\n"
            "import jax.numpy as jnp\n"
            "from dlrover_tpu.trainer.flash_checkpoint.engine import ("
            "ReplicatedCheckpointEngine)\n"
            f"e = ReplicatedCheckpointEngine({str(ckpt_dir)!r})\n"
            "e.save_to_memory(7, {'w': jnp.ones((4,))})\n"
            "os._exit(3)\n"
        )
        config = ElasticLaunchConfig(
            min_nodes=1, max_nodes=1, nproc_per_node=1,
            monitor_interval=0.3, rdzv_timeout=30, max_restarts=0,
            save_at_breakpoint=True, log_dir=str(tmp_path),
        )
        client = MasterClient(local_master.addr, 0, NodeType.WORKER)
        agent = ElasticTrainingAgent(
            config, WorkerSpec(str(script), (), config), client
        )
        try:
            assert agent.run() != 0  # worker failed for real
        finally:
            client.close()
        # the shm image must have been flushed to storage
        step_dirs = list(ckpt_dir.glob("checkpoint-7"))
        assert step_dirs, list(ckpt_dir.glob("*"))
        shards = list(step_dirs[0].glob("*.dlck"))
        assert shards


class TestDeletionStrategy:
    def test_keep_latest_n(self, tmp_path, monkeypatch):
        """DLROVER_TPU_MAX_CKPTS_TO_KEEP retains only the newest dirs
        (reference KeepLatestStepStrategy, common/storage.py)."""
        monkeypatch.setenv("DLROVER_TPU_MAX_CKPTS_TO_KEEP", "2")
        ckpt_dir = str(tmp_path / "ckpt")
        engine = ReplicatedCheckpointEngine(ckpt_dir)
        for step in (1, 2, 3, 4):
            state = make_state(seed=step)
            assert engine.save_to_memory(step, state)
            assert engine.save_to_storage(step, state)
            assert engine.wait_for_persist(step, timeout=60)
        import os as _os

        dirs = sorted(
            d for d in _os.listdir(ckpt_dir)
            if d.startswith("checkpoint-")
        )
        assert dirs == ["checkpoint-3", "checkpoint-4"], dirs
        # tracker still points at the newest
        assert engine.latest_step() == 4
        engine.close()

    def test_restart_counts_existing_dirs(self, tmp_path):
        """Dirs surviving an agent restart are retired by a fresh
        strategy instance (state derived from disk, not memory)."""
        from dlrover_tpu.common.storage import KeepLatestStepStrategy

        ckpt_dir = tmp_path / "ckpt"
        for step in (1, 2, 3):
            (ckpt_dir / f"checkpoint-{step}").mkdir(parents=True)
        strat = KeepLatestStepStrategy(2, str(ckpt_dir))
        import shutil as _shutil

        strat.clean_up(4, lambda p: _shutil.rmtree(p))
        left = sorted(p.name for p in ckpt_dir.iterdir())
        assert left == ["checkpoint-3"]  # 4's slot reserved, 3 kept

    def test_repeated_commit_same_step_idempotent(self, tmp_path):
        from dlrover_tpu.common.storage import KeepLatestStepStrategy

        ckpt_dir = tmp_path / "ckpt"
        for step in (7, 8):
            (ckpt_dir / f"checkpoint-{step}").mkdir(parents=True)
        strat = KeepLatestStepStrategy(2, str(ckpt_dir))
        import shutil as _shutil

        for _ in range(4):  # one call per shard thread
            strat.clean_up(8, lambda p: _shutil.rmtree(p))
        left = sorted(p.name for p in ckpt_dir.iterdir())
        # the just-committed step is never deleted; 7 fills the one
        # remaining slot
        assert left == ["checkpoint-7", "checkpoint-8"]


class TestLeafNaming:
    def test_dotted_names_literal(self):
        """Literal expected names, independent of the naming function."""
        import jax.numpy as _jnp

        from dlrover_tpu.trainer.flash_checkpoint.engine import (
            _tree_flatten_with_names,
        )

        tree = {"params": {"w": _jnp.zeros(2), "b": _jnp.zeros(1)},
                "opt": [_jnp.zeros(3)]}
        names, _, _ = _tree_flatten_with_names(tree)
        assert set(names) == {"opt.0", "params.b", "params.w"}

    def test_collision_falls_back_to_keystr(self):
        import jax.numpy as _jnp

        from dlrover_tpu.trainer.flash_checkpoint.engine import (
            _tree_flatten_with_names,
        )

        tree = {"a": {"b": _jnp.zeros(1)}, "a.b": _jnp.zeros(2)}
        names, _, _ = _tree_flatten_with_names(tree)
        assert len(set(names)) == 2  # distinct leaves stay distinct

    def test_legacy_checkpoint_restores(self, tmp_path):
        """A shm image written with old keystr names restores into a
        target via the legacy-name translation."""
        import jax
        import jax.numpy as _jnp

        from dlrover_tpu.trainer.flash_checkpoint import engine as eng

        e = ReplicatedCheckpointEngine(str(tmp_path / "ckpt"))
        state = {"params": {"w": _jnp.full((4,), 3.0)}}
        # simulate an old-build writer: monkeypatch naming to keystr
        real = eng._tree_flatten_with_names

        def legacy_flatten(tree):
            lw, td = jax.tree_util.tree_flatten_with_path(tree)
            return (
                [jax.tree_util.keystr(p) for p, _ in lw],
                [l for _, l in lw],
                td,
            )

        eng._tree_flatten_with_names = legacy_flatten
        try:
            assert e.save_to_memory(5, state)
        finally:
            eng._tree_flatten_with_names = real
        target = {"params": {"w": _jnp.zeros((4,))}}
        restored, step = e.load(target=target)
        assert step == 5
        np.testing.assert_allclose(
            np.asarray(restored["params"]["w"]), 3.0
        )
        e.close()

    def test_colliding_names_roundtrip(self, tmp_path):
        """A tree whose dotted names collide saves under keystr names;
        the load path must NOT legacy-translate those back (it would
        merge the distinct leaves) — the roundtrip stays lossless."""
        import jax.numpy as _jnp

        e = ReplicatedCheckpointEngine(str(tmp_path / "ckpt"))
        state = {"a": {"b": _jnp.full((1,), 1.0)},
                 "a.b": _jnp.full((2,), 2.0)}
        assert e.save_to_memory(3, state)
        target = {"a": {"b": _jnp.zeros((1,))}, "a.b": _jnp.zeros((2,))}
        restored, step = e.load(target=target)
        assert step == 3
        np.testing.assert_allclose(np.asarray(restored["a"]["b"]), 1.0)
        np.testing.assert_allclose(np.asarray(restored["a.b"]), 2.0)
        e.close()


class TestStorageCompleteness:
    def test_storage_restore_refuses_missing_leaves(self, tmp_path):
        """A disk checkpoint missing whole target leaves (model changed)
        must raise instead of silently mixing checkpointed and
        fresh-init values (mirrors the shm path's bail-out)."""
        import jax.numpy as _jnp
        import pytest as _pytest

        e = ReplicatedCheckpointEngine(str(tmp_path / "ckpt"))
        state = {"params": {"w": _jnp.full((4,), 3.0)}}
        assert e.save_to_storage(2, state)
        assert e.wait_for_persist(2, timeout=60)
        e._shm_handler.close(unlink=True)  # force the storage path
        target = {"params": {"w": _jnp.zeros((4,)),
                             "extra": _jnp.zeros((2,))}}
        with _pytest.raises(ValueError, match="missing"):
            e.load_from_storage(target=target)
        e.close()


def test_two_phase_meta_publish(isolated_ckpt_env):
    """A drain in progress must be invisible to readers: the meta stays
    unpublished (read() -> None) until publish_meta(), so a preemption
    mid-drain can never leave a valid meta over partial tensor bytes
    (the failure-path save_shm_to_storage would persist a torn
    snapshot)."""
    import numpy as np

    from dlrover_tpu.agent.ckpt_saver import (
        CheckpointMeta,
        LeafMeta,
        SharedMemoryHandler,
    )

    h = SharedMemoryHandler(0)
    arr = np.arange(16, dtype=np.float32)
    meta = CheckpointMeta(
        step=7,
        leaves=[LeafMeta(
            path="w", dtype="float32", shape=(16,), offset=0,
            nbytes=arr.nbytes,
        )],
        treedef=b"", engine="replicated", total_bytes=arr.nbytes,
    )
    buf = h.write_meta_and_reserve(meta, publish=False)
    assert h.read() is None, "unpublished meta must be invisible"
    buf[: arr.nbytes] = arr.tobytes()
    h.publish_meta()
    got = h.read()
    assert got is not None and got[0].step == 7
    np.testing.assert_array_equal(
        np.frombuffer(bytes(got[1][: arr.nbytes]), np.float32), arr
    )
    h.close(unlink=True)


# -------------------------------------------------------------------------
# the save path's own breakdown (last_save_stats + ckpt.save.* spans)
# -------------------------------------------------------------------------


class TestSaveBreakdown:
    def test_last_save_stats_split_the_save(self, tmp_path, monkeypatch):
        import time

        from dlrover_tpu.trainer.flash_checkpoint import engine as eng

        # a state with one "large" leaf, by a threshold this size meets
        monkeypatch.setattr(eng, "LARGE_LEAF_BYTES", 16 * 8 * 4)
        engine = ReplicatedCheckpointEngine(str(tmp_path / "ckpt"))
        state = make_state()
        t0 = time.perf_counter()
        assert engine.save_to_memory(10, state)
        wall = time.perf_counter() - t0
        stats = engine.last_save_stats
        assert set(stats) == {
            "bytes", "materialize_s", "fill_s", "launch_s", "reserve_s",
            "leaves", "first_leaf_s", "slowest_leaf_s",
            "slowest_leaf_bytes", "large_leaf_gbps",
        }
        assert stats["leaves"] == 3
        assert stats["bytes"] == (16 * 8 + 8) * 4 + 4
        legs = (stats["launch_s"] + stats["reserve_s"]
                + stats["materialize_s"] + stats["fill_s"])
        assert 0 < legs <= wall
        assert 0 <= stats["first_leaf_s"] <= stats["slowest_leaf_s"] \
            <= stats["materialize_s"]
        assert stats["slowest_leaf_bytes"] in (16 * 8 * 4, 8 * 4, 4)
        assert stats["large_leaf_gbps"] > 0
        # no leaf that large: no bandwidth to report
        monkeypatch.setattr(eng, "LARGE_LEAF_BYTES", 1 << 40)
        assert engine.save_to_memory(11, state)
        assert engine.last_save_stats["large_leaf_gbps"] is None
        engine.close()

    def test_save_spans_nest_under_the_shm_save(
        self, tmp_path, profiled_spans
    ):
        """Three ring spans a save, children of ``ckpt.save.shm``; a
        shard or a flush leaves a trace only inside a profiler session."""
        from dlrover_tpu.common import telemetry

        prev = telemetry.active_registry()
        telemetry.enable(source="test-0-1")
        engine = ReplicatedCheckpointEngine(str(tmp_path / "ckpt"))
        try:
            traced = [s["name"] for s in profiled_spans(
                lambda: engine.save_to_memory(10, make_state())
            )]
            events = telemetry.snapshot()["events"]
        finally:
            telemetry._REGISTRY = prev
            engine.close()
        ring = {e["name"]: e for e in events if e["kind"] == "span"}
        assert set(ring) == {
            "ckpt.save.shm", "ckpt.save.launch", "ckpt.save.reserve",
            "ckpt.save.drain",
        }
        for child in ("launch", "reserve", "drain"):
            assert ring["ckpt.save." + child]["parent"] == \
                ring["ckpt.save.shm"]["span"]
        assert ring["ckpt.save.drain"]["leaves"] == 3
        assert traced.count("ckpt.save.leaf") == 3
        assert traced.count("ckpt.save.fill") == 1
        assert set(ring) <= set(traced)
