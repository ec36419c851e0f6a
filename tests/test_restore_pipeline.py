"""Pipelined checkpoint restore/persist data-path tests: bit-exact
equality between the parallel staged loaders and the serial path,
chunk-granular corruption fallback, the streamed-CRC shard writer, the
host arena, and the event-driven persist wait."""

import os
import time

import jax
import jax.numpy as jnp
import numpy as np
import pytest
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

from dlrover_tpu.agent.ckpt_saver import (
    AsyncCheckpointSaver,
    CheckpointMeta,
    LeafMeta,
    host_shard_filename,
    read_host_shard,
    read_host_shard_meta,
    verify_step_dir,
    write_host_shard,
    write_shard_manifest,
)
from dlrover_tpu.common.storage import PosixDiskStorage
from dlrover_tpu.trainer.flash_checkpoint.engine import (
    ReplicatedCheckpointEngine,
    ShardedCheckpointEngine,
)


@pytest.fixture(autouse=True)
def _isolate_ipc(isolated_ckpt_env):
    yield


def make_state(seed=0):
    k = jax.random.PRNGKey(seed)
    return {
        "params": {
            "w": jax.random.normal(k, (32, 16), dtype=jnp.float32),
            "b": jnp.arange(16, dtype=jnp.float32),
        },
        "step_count": jnp.asarray(3, dtype=jnp.int32),
    }


def trees_bitexact(a, b):
    fa = jax.tree_util.tree_leaves(a)
    fb = jax.tree_util.tree_leaves(b)
    assert len(fa) == len(fb)
    return all(
        np.array_equal(np.asarray(x), np.asarray(y))
        for x, y in zip(fa, fb)
    )


def _write_multihost_step_dir(step_dir, step=12):
    """Synthesize a 2-host sharded checkpoint: a (16, 8) global array
    split row-wise across two host shard files, plus a replicated leaf
    on host 0 — the layout a 2-host ShardedCheckpointEngine persists."""
    storage = PosixDiskStorage()
    rng = np.random.RandomState(step)
    full = rng.randn(16, 8).astype(np.float32)
    bias = rng.randn(8).astype(np.float32)
    halves = [full[:8], full[8:]]
    for host in range(2):
        leaves = [
            LeafMeta(
                path="w", dtype="float32", shape=(8, 8), offset=0,
                nbytes=halves[host].nbytes, global_shape=(16, 8),
                index=((8 * host, 8 * host + 8), (0, 8)),
            ),
        ]
        payload = halves[host].tobytes()
        if host == 0:
            leaves.append(
                LeafMeta(
                    path="b", dtype="float32", shape=(8,),
                    offset=halves[0].nbytes, nbytes=bias.nbytes,
                    global_shape=(8,), index=None,
                )
            )
            payload += bias.tobytes()
        meta = CheckpointMeta(
            step=step, leaves=leaves, engine="sharded", host_rank=host,
            num_hosts=2, total_bytes=len(payload),
        )
        path = os.path.join(step_dir, host_shard_filename(host))
        crc, nbytes = write_host_shard(storage, path, meta, payload)
        write_shard_manifest(
            storage, step_dir, host, step, crc, nbytes, "sharded"
        )
    return full, bias


class TestPipelinedBitExact:
    def test_eager_parallel_matches_serial_multihost(
        self, tmp_path, monkeypatch
    ):
        """The parallel chunked eager loader returns byte-identical
        state to the single-threaded path on a multi-host sharded
        layout."""
        ckpt = tmp_path / "ckpt"
        step_dir = str(ckpt / "checkpoint-12")
        full, bias = _write_multihost_step_dir(step_dir)
        engine = ReplicatedCheckpointEngine(str(ckpt))
        try:
            got_par = engine.load_from_storage()
            assert got_par is not None
            monkeypatch.setenv("DLROVER_TPU_RESTORE_THREADS", "1")
            got_ser = engine.load_from_storage()
            assert got_ser is not None
            assert np.array_equal(got_par["state"]["w"], full)
            assert np.array_equal(got_par["state"]["b"], bias)
            assert trees_bitexact(got_par["state"], got_ser["state"])
            # staged breakdown recorded (read leg is the chunked pass;
            # verify is folded into it via the incremental CRC)
            assert engine.last_restore_stats.get("bytes", 0) > 0
            assert "read_s" in engine.last_restore_stats
        finally:
            engine.close()

    def test_targeted_pipelined_matches_serial_sharded_target(
        self, tmp_path, monkeypatch
    ):
        """The pipelined shard-wise fill restores bit-exactly into a
        device-sharded target, parallel and serial."""
        ckpt = tmp_path / "ckpt"
        step_dir = str(ckpt / "checkpoint-12")
        full, bias = _write_multihost_step_dir(step_dir)
        mesh = Mesh(np.array(jax.devices()).reshape(8), ("dp",))
        target = {
            "w": jax.device_put(
                jnp.zeros((16, 8), jnp.float32),
                NamedSharding(mesh, P("dp", None)),
            ),
            "b": jax.device_put(
                jnp.zeros((8,), jnp.float32), NamedSharding(mesh, P(None))
            ),
        }
        engine = ReplicatedCheckpointEngine(str(ckpt))
        try:
            tree_par, step = engine.load_from_storage(target=target)
            assert step == 12
            assert np.array_equal(np.asarray(tree_par["w"]), full)
            assert np.array_equal(np.asarray(tree_par["b"]), bias)
            assert tree_par["w"].sharding == target["w"].sharding
            assert engine.last_restore_stats.get("h2d_s", -1) >= 0
            monkeypatch.setenv("DLROVER_TPU_RESTORE_THREADS", "1")
            tree_ser, _ = engine.load_from_storage(target=target)
            assert trees_bitexact(tree_par, tree_ser)
        finally:
            engine.close()

    def test_shm_gather_copy_matches_fallback(self, tmp_path, monkeypatch):
        """The native threaded gather out of shm returns the same bytes
        as the pure-numpy fallback (and as the saved state)."""
        from dlrover_tpu import native

        state = make_state(3)
        engine = ReplicatedCheckpointEngine(str(tmp_path / "ckpt"))
        try:
            assert engine.save_to_memory(5, state)
            with_native = engine.load()
            monkeypatch.setattr(native, "_lib", None)
            monkeypatch.setattr(native, "_load_attempted", True)
            without = engine.load()
            assert trees_bitexact(with_native["state"], without["state"])
            assert trees_bitexact(
                with_native["state"],
                {
                    "params.w": state["params"]["w"],
                    "params.b": state["params"]["b"],
                    "step_count": state["step_count"],
                },
            )
        finally:
            engine.close()


class TestChunkGranularIntegrity:
    def _persist_steps(self, ckpt_dir, steps):
        engine = ReplicatedCheckpointEngine(str(ckpt_dir))
        states = {}
        for s in steps:
            states[s] = make_state(s)
            assert engine.save_to_storage(s, states[s])
            assert engine.wait_for_persist(s, timeout=60)
        return engine, states

    def test_mid_payload_bitflip_falls_back(self, tmp_path):
        """A corrupt CHUNK must reject the shard and fall back exactly
        like a corrupt whole payload did: the incremental CRC catches a
        flipped byte in the middle of the stream."""
        engine, states = self._persist_steps(tmp_path / "ckpt", [2, 4])
        try:
            shard = os.path.join(
                str(tmp_path / "ckpt"), "checkpoint-4",
                host_shard_filename(0),
            )
            raw = bytearray(open(shard, "rb").read())
            meta, payload_start = read_host_shard_meta(shard)
            mid = payload_start + (len(raw) - payload_start) // 2
            raw[mid] ^= 0x10
            open(shard, "wb").write(bytes(raw))
            # drop the verified-crc cache so verify re-checks bytes
            marker = os.path.join(
                str(tmp_path / "ckpt"), "checkpoint-4", ".verified"
            )
            if os.path.exists(marker):
                os.remove(marker)
            assert read_host_shard(shard) is None
            ok, reason = verify_step_dir(
                os.path.dirname(shard), deep=True
            )
            assert not ok and "checksum" in reason
            engine._shm_handler.mark_empty()
            got = engine.load()
            assert got is not None
            assert got["step"] == 2
            target = jax.tree.map(jnp.zeros_like, states[2])
            engine.last_restore_stats = {}
            tree, step = engine.load(target=target)
            assert step == 2
            assert trees_bitexact(tree, states[2])
        finally:
            engine.close()

    def test_torn_payload_rejected_by_chunked_reader(self, tmp_path):
        """Truncation mid-payload: the chunked reader must reject (the
        old reader's short f.read was caught by the CRC; the new one
        also short-circuits on byte count)."""
        engine, states = self._persist_steps(tmp_path / "ckpt", [2, 4])
        try:
            shard = os.path.join(
                str(tmp_path / "ckpt"), "checkpoint-4",
                host_shard_filename(0),
            )
            raw = open(shard, "rb").read()
            open(shard, "wb").write(raw[: len(raw) - 64])
            marker = os.path.join(
                str(tmp_path / "ckpt"), "checkpoint-4", ".verified"
            )
            if os.path.exists(marker):
                os.remove(marker)
            assert read_host_shard(shard) is None
            engine._shm_handler.mark_empty()
            got = engine.load()
            assert got is not None and got["step"] == 2
        finally:
            engine.close()

    def test_chaos_tear_still_caught_by_streamed_writer(self, tmp_path):
        """The streamed-CRC writer must keep the chaos contract: a
        fired tear corrupts the on-disk bytes AFTER the intended CRC is
        computed, so verification falls back — identical to the old
        two-pass writer."""
        from dlrover_tpu.common import chaos

        chaos.install(
            {"seed": 13, "rules": [
                {"site": "ckpt.write", "action": "tear", "step": 4},
            ]}
        )
        try:
            engine, states = self._persist_steps(
                tmp_path / "ckpt", [2, 4]
            )
            try:
                engine._shm_handler.mark_empty()
                got = engine.load()
                assert got is not None and got["step"] == 2
            finally:
                engine.close()
        finally:
            chaos.uninstall()


class TestStreamedShardWriter:
    def test_roundtrip_and_padded_header(self, tmp_path):
        storage = PosixDiskStorage()
        path = str(tmp_path / "host_0.dlck")
        payload = os.urandom(100_000)
        meta = CheckpointMeta(step=9, total_bytes=len(payload))
        crc, nbytes = write_host_shard(storage, path, meta, payload)
        assert nbytes == len(payload)
        got = read_host_shard(path)
        assert got is not None
        got_meta, data = got
        assert bytes(data) == payload
        assert got_meta.payload_crc == crc >= 0
        # the meta slot is padded so the streaming CRC can land in a
        # fixed-size header; readers must see payload_start + size agree
        hdr = read_host_shard_meta(path)
        assert hdr is not None
        _, payload_start = hdr
        assert os.path.getsize(path) - payload_start == len(payload)

    def test_parallel_write_parts_byte_identical(
        self, tmp_path, monkeypatch
    ):
        """The chunk-parallel positional writer produces the same file
        as the sequential one."""
        storage = PosixDiskStorage()
        parts = [os.urandom(10), os.urandom(300_000), os.urandom(17)]
        seq = str(tmp_path / "seq.bin")
        storage.write_parts(list(parts), seq)
        monkeypatch.setattr(
            PosixDiskStorage, "_PARALLEL_PART_BYTES", 1024
        )
        import dlrover_tpu.common.storage as storage_mod

        monkeypatch.setattr(storage_mod, "WRITE_CHUNK_BYTES", 4096)
        par = str(tmp_path / "par.bin")
        storage.write_parts(list(parts), par)
        assert open(seq, "rb").read() == open(par, "rb").read()

    def test_write_payload_with_header_single_pass(self, tmp_path):
        storage = PosixDiskStorage()
        payload = os.urandom(200_000)
        from dlrover_tpu import native

        want_crc = native.crc32(payload)

        def make_header(crc):
            assert crc == want_crc
            return crc.to_bytes(8, "little")

        path = str(tmp_path / "x.bin")
        got_crc = storage.write_payload_with_header(
            path, 8, make_header, payload, chunk_bytes=4096
        )
        assert got_crc == want_crc
        raw = open(path, "rb").read()
        assert raw[:8] == want_crc.to_bytes(8, "little")
        assert raw[8:] == payload


class TestHostArena:
    def test_lease_reuse_and_counters(self):
        from dlrover_tpu.common.arena import HostArena

        arena = HostArena(max_bytes=1 << 24)
        with arena.lease(100_000) as lease:
            assert len(lease.view) == 100_000
            lease.view[:4] = b"abcd"
        # same size class comes back warm
        with arena.lease(90_000) as lease2:
            assert len(lease2.view) == 90_000
        assert arena.hits == 1 and arena.misses == 1

    def test_cap_drops_oversize_returns(self):
        from dlrover_tpu.common.arena import HostArena

        arena = HostArena(max_bytes=1 << 17)
        lease = arena.lease(1 << 20)
        lease.release()
        assert arena.stats()["pooled_bytes"] == 0

    def test_release_idempotent_and_view_fenced(self):
        from dlrover_tpu.common.arena import HostArena

        arena = HostArena(max_bytes=1 << 24)
        lease = arena.lease(4096)
        lease.release()
        lease.release()
        with pytest.raises(ValueError):
            _ = lease.view

    def test_verify_uses_arena(self, tmp_path):
        """Deep verify's chunked CRC stages through the arena."""
        from dlrover_tpu.common import arena as arena_mod

        storage = PosixDiskStorage()
        step_dir = str(tmp_path / "checkpoint-3")
        payload = os.urandom(50_000)
        meta = CheckpointMeta(step=3, total_bytes=len(payload))
        path = os.path.join(step_dir, host_shard_filename(0))
        crc, nbytes = write_host_shard(storage, path, meta, payload)
        write_shard_manifest(
            storage, step_dir, 0, 3, crc, nbytes, "replicated"
        )
        before = arena_mod.get_arena().stats()
        ok, _ = verify_step_dir(step_dir, deep=True)
        assert ok
        after = arena_mod.get_arena().stats()
        assert (
            after["hits"] + after["misses"]
            > before["hits"] + before["misses"]
        )


class TestEventDrivenPersistWait:
    def test_wait_wakes_on_persist_event(self, tmp_path):
        engine = ReplicatedCheckpointEngine(str(tmp_path / "ckpt"))
        try:
            state = make_state()
            assert engine.save_to_storage(11, state)
            t0 = time.monotonic()
            assert engine.wait_for_persist(11, timeout=60)
            # generous bound: the point is event-driven wakeup, not
            # busy-poll cadence — a persist of a KB-scale state must
            # complete and wake the waiter well inside this
            assert time.monotonic() - t0 < 30
        finally:
            engine.close()

    def test_progress_wakeup_hint(self, tmp_path):
        engine = ReplicatedCheckpointEngine(str(tmp_path / "ckpt"))
        try:
            state = make_state()
            assert engine.save_to_storage(5, state)
            assert engine.wait_for_persist(5, timeout=60)
            saver = AsyncCheckpointSaver.get_ckpt_saver()
            # hint queue drained by the wait above or pending: a fresh
            # put must wake a blocked waiter promptly
            saver._done_queues[0].put(5, block=False)
            t0 = time.monotonic()
            assert engine.wait_for_persist_progress(10.0)
            assert time.monotonic() - t0 < 5
        finally:
            engine.close()

    def test_trainer_final_persist_not_quantized(self, tmp_path):
        """The trainer's final-save retry uses the persist-done wakeup
        (no fixed 0.2 s poll): simulate the lock held by an in-flight
        persist, then release it and complete a persist — the retry
        loop must get through."""
        engine = ReplicatedCheckpointEngine(str(tmp_path / "ckpt"))
        try:
            state = make_state()
            # in-flight persist holds the shm lock -> first save skips
            assert engine._shm_lock.acquire(blocking=False)
            assert not engine.save_to_memory(7, state)
            engine._shm_lock.release()
            # retry (what Trainer.train's loop does after the wakeup)
            engine.wait_for_persist_progress(0.1)
            assert engine.save_to_memory(7, state)
        finally:
            engine.close()
