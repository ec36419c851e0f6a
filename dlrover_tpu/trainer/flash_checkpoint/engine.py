"""Flash-checkpoint engines (training-process side).

Equivalent capability: reference dlrover/trainer/torch/flash_checkpoint/
engine.py — CheckpointEngine ABC (:131) writing the state dict to shared
memory under the shm lock with an all-rank readiness check
(save_state_dict_to_memory :284, check_all_rank_ready :51), notifying the
agent saver through the event queue, creating the saver via the factory
queue (:247); framework engines ddp_engine.py/megatron_engine.py/
fsdp_engine.py.

TPU redesign: the state dict is a JAX pytree. ``save_to_memory`` starts
asynchronous HBM->host transfers for every addressable shard
(``jax.Array.copy_to_host_async``), then copies host buffers into the shm
segment — the device never blocks on storage IO, and persistence happens
in the agent daemon. The readiness check is a **host-side master barrier**
(CheckpointBarrierService) instead of an in-band device collective, so
the save path stays off the TPU. Engines:

- ReplicatedCheckpointEngine: pure-DP (every host holds the full state);
  only host 0 persists (the reference DdpCheckpointEngine analogue).
- ShardedCheckpointEngine: GSPMD/pjit states — every host saves exactly
  its addressable unique shards with (global_shape, index) metadata, the
  analogue of the reference Megatron/FSDP shard savers.
"""

from __future__ import annotations

import os
import pickle
import queue as _queue
import threading
import time
from concurrent.futures import ThreadPoolExecutor

import numpy as np

from dlrover_tpu.agent.ckpt_saver import (
    AsyncCheckpointSaver,
    CheckpointMeta,
    LeafMeta,
    SAVER_FACTORY_QUEUE,
    SaveEvent,
    SharedMemoryHandler,
    _VERIFIED_MARKER,
    event_queue_name,
    host_shard_filename,
    lock_name,
    persist_done_queue_name,
    read_host_shard,
    verify_step_dir,
)
from dlrover_tpu.common import telemetry, tracing
from dlrover_tpu.common.chaos import chaos_point
from dlrover_tpu.common.constants import CheckpointConstant, NodeEnv
from dlrover_tpu.common.ipc import SharedLock, SharedQueue
from dlrover_tpu.common.log import get_logger

logger = get_logger(__name__)

# a shard this large waits for the link's bandwidth, not its latency
# (``last_save_stats["large_leaf_gbps"]``)
LARGE_LEAF_BYTES = 64 << 20


def _path_entry_str(entry) -> str:
    # dotted names ("params.w" not "['params']['w']"): stable across
    # jax versions and readable in metas/logs
    import jax

    if isinstance(entry, jax.tree_util.DictKey):
        return str(entry.key)
    if isinstance(entry, jax.tree_util.SequenceKey):
        return str(entry.idx)
    if isinstance(entry, jax.tree_util.GetAttrKey):
        return str(entry.name)
    if isinstance(entry, jax.tree_util.FlattenedIndexKey):
        return str(entry.key)
    return jax.tree_util.keystr((entry,))


def _tree_flatten_with_names(tree):
    import jax

    leaves_with_paths, treedef = jax.tree_util.tree_flatten_with_path(tree)
    names = [
        ".".join(_path_entry_str(e) for e in path) or "leaf"
        for path, _ in leaves_with_paths
    ]
    if len(set(names)) != len(names):
        # pathological keys (a dict key containing '.') can make dotted
        # names collide; fall back to the collision-free keystr form for
        # the whole tree rather than merging distinct leaves
        names = [
            jax.tree_util.keystr(path) for path, _ in leaves_with_paths
        ]
    leaves = [leaf for _, leaf in leaves_with_paths]
    return names, leaves, treedef


_LEGACY_NAME_RE = None


def _legacy_to_dotted(name: str) -> str:
    """Translate pre-dotted keystr names ("['a']['b']", "[0]") so
    checkpoints written by older builds keep restoring. Names that are
    not entirely bracket-form are returned unchanged."""
    global _LEGACY_NAME_RE
    if _LEGACY_NAME_RE is None:
        import re

        _LEGACY_NAME_RE = re.compile(r"\[(?:'([^']*)'|(\d+))\]")
    matches = list(_LEGACY_NAME_RE.finditer(name))
    if not matches or "".join(m.group(0) for m in matches) != name:
        return name
    return ".".join(
        m.group(1) if m.group(1) is not None else m.group(2)
        for m in matches
    )


def _translate_legacy_names(paths: list[str]) -> dict[str, str]:
    """Per-checkpoint legacy-name mapping. Translation is applied only
    when the dotted forms stay collision-free: a tree whose dotted names
    collide (a dict key containing '.') is *saved* under raw keystr
    names by design (`_tree_flatten_with_names` fallback), and
    translating those back would merge distinct leaves — so such
    checkpoints keep their raw names, which is exactly what the target
    flatten produces for the same tree."""
    translated = {p: _legacy_to_dotted(p) for p in paths}
    if len(set(translated.values())) != len(paths):
        return {p: p for p in paths}
    return translated


def _unique_addressable_shards(arr):
    """Deduplicate replicated shards: one entry per distinct index."""
    import jax

    if not isinstance(arr, jax.Array):
        return [(None, np.asarray(arr))]
    seen = set()
    shards = []
    for shard in arr.addressable_shards:
        key = tuple(
            (s.start, s.stop, s.step) for s in shard.index
        ) if shard.index is not None else None
        if key in seen:
            continue
        seen.add(key)
        shards.append((shard.index, shard.data))
    return shards


def _index_to_meta(index, ndim) -> tuple | None:
    if index is None:
        return None
    out = []
    for s in index:
        out.append((s.start, s.stop))
    while len(out) < ndim:
        out.append((None, None))
    return tuple(out)


def _restore_threads() -> int:
    """Reader parallelism for the staged restore pipeline."""
    raw = os.environ.get("DLROVER_TPU_RESTORE_THREADS", "")
    try:
        n = int(raw) if raw else 0
    except ValueError:
        n = 0
    return n if n > 0 else min(4, os.cpu_count() or 1)


# H2D dispatch serialization: the restore pipeline issues device_put
# from reader threads as each leaf's host bytes become ready (transfers
# overlap the remaining disk reads because dispatch is async); the lock
# keeps the dispatch call itself single-threaded for runtimes that do
# not like concurrent device_put entry. device_put under it only
# DISPATCHES (it returns before the transfer completes); the blocking
# wait is the restore's one barrier, outside the lock.
_H2D_DISPATCH_LOCK = threading.Lock()


def _publish_restore_stats(stats: dict):
    """Per-stage restore gauges (read/verify/h2d) + the checkpoint-
    bucket event for the blocking H2D leg — without this the restore's
    device-transfer wall time vanishes into the goodput ledger's
    ``idle``. Publishes a given stats dict at most once (load() and
    load_from_storage() share it)."""
    if not stats or stats.get("_published"):
        return
    stats["_published"] = True
    nbytes = stats.get("bytes", 0)
    for leg, gauge in (
        ("read_s", "ckpt.restore.read_gbps"),
        ("verify_s", "ckpt.restore.verify_gbps"),
        ("h2d_s", "ckpt.restore.h2d_gbps"),
    ):
        secs = stats.get(leg, 0.0)
        if secs > 0 and nbytes:
            telemetry.gauge_set(gauge, nbytes / secs / (1 << 30))
    h2d = stats.get("h2d_s", 0.0)
    if h2d > 0:
        telemetry.event(
            "ckpt.restore.h2d", dur=h2d, mb=nbytes / 1e6
        )


class CheckpointEngine:
    """Base engine: shm write path + agent notification + load paths."""

    engine_name = "replicated"

    def __init__(
        self,
        checkpoint_dir: str,
        master_client=None,
        local_rank: int = 0,
        host_rank: int = 0,
        num_hosts: int = 1,
        save_timeout: float = CheckpointConstant.SAVE_TIMEOUT,
        standalone: bool | None = None,
    ):
        self.checkpoint_dir = checkpoint_dir
        self._client = master_client
        self._local_rank = local_rank
        self._host_rank = host_rank
        self._num_hosts = num_hosts
        self._save_timeout = save_timeout
        self._shm_handler = SharedMemoryHandler(local_rank)
        self._latest_step = 0
        self._async_thread: threading.Thread | None = None
        # Under tpu-run the agent hosts the saver (factory queue); when
        # used standalone (plain `python train.py`) the engine runs its
        # own in-process saver so the API still works.
        local_world = int(os.environ.get("LOCAL_WORLD_SIZE", "1"))
        saver_config = dict(
            checkpoint_dir=checkpoint_dir,
            local_shard_num=max(local_world, local_rank + 1),
            host_rank=host_rank,
            num_hosts=num_hosts,
        )
        if standalone is None:
            standalone = not SharedQueue(
                SAVER_FACTORY_QUEUE, create=False
            ).is_available()
        if not standalone:
            # A stale socket file from a dead agent must not brick the
            # engine: fall back to standalone if the queue is dead.
            try:
                SharedQueue(SAVER_FACTORY_QUEUE, create=False).put(
                    saver_config
                )
            except (ConnectionError, OSError):
                logger.warning(
                    "checkpoint factory queue is dead; running the saver "
                    "in-process"
                )
                standalone = True
        self._standalone = standalone
        if standalone:
            if AsyncCheckpointSaver.get_ckpt_saver() is None:
                AsyncCheckpointSaver._saver_instance = AsyncCheckpointSaver(
                    master_client=master_client, **saver_config
                )
                AsyncCheckpointSaver._saver_instance.start()
            self._saver = AsyncCheckpointSaver.get_ckpt_saver()
            self._event_queue = None
            self._shm_lock = self._saver._shm_locks[local_rank]
            self._done_queue = (
                self._saver._done_queues[local_rank]
                if local_rank < len(self._saver._done_queues)
                else None
            )
        else:
            self._saver = None
            # wait for the agent to create lock/event queues
            deadline = time.time() + 60
            while time.time() < deadline:
                if SharedQueue(
                    event_queue_name(local_rank), create=False
                ).is_available():
                    break
                time.sleep(0.2)
            self._event_queue = SharedQueue(
                event_queue_name(local_rank), create=False
            )
            self._shm_lock = SharedLock(
                lock_name(local_rank), create=False
            )
            # persist-done wakeups: optional (an older agent without
            # the queue degrades the waiters back to polling)
            self._done_queue = SharedQueue(
                persist_done_queue_name(local_rank), create=False
            )
        # staged-pipeline observability: the last save's / restore's
        # per-leg breakdown, for a caller that times a save (the
        # benchmark does); telemetry publishes the same legs
        self.last_save_stats: dict = {}
        self.last_restore_stats: dict = {}

    # ------------------------------------------------------------- barrier

    def _all_hosts_ready(self, step: int) -> bool:
        """Host-side readiness barrier via the master (replaces the
        reference's device collective, engine.py:51). Bails out early if
        any peer reported a skip for this step."""
        if self._client is None or self._num_hosts <= 1:
            return True
        self._client.report_ckpt_ready(step, "save", self._num_hosts)
        deadline = time.time() + self._save_timeout
        while time.time() < deadline:
            passed, aborted = self._client.check_ckpt_barrier(
                step, "save", self._num_hosts
            )
            if passed:
                return True
            if aborted:
                logger.warning(
                    "peer skipped ckpt save at step %s; aborting barrier",
                    step,
                )
                return False
            time.sleep(0.1)
        return False

    def _report_skip(self, step: int):
        if self._client is not None and self._num_hosts > 1:
            try:
                self._client.report_ckpt_skip(step, "save")
            except Exception:  # noqa: BLE001 - best effort
                logger.warning("could not report ckpt skip for %s", step)

    # ---------------------------------------------------------- save paths

    def _select_shards(self, arr):
        """Which shards of this array this host must write. Overridden
        per engine."""
        raise NotImplementedError

    def _write_shm_locked(self, step: int, state_dict) -> int:
        """D2H-copy the selected shards and write them into shm. Caller
        holds the shm lock. Returns total bytes written.

        The drain is CHUNKED and DOUBLE-BUFFERED: every shard's D2H
        transfer is launched up-front (``copy_to_host_async``), metas
        are computed from shapes alone, and then shards are drained one
        at a time — materialise shard i (blocks only on *its* in-flight
        transfer) and memcpy it into shm (native, GIL-released, 8 MB
        chunks across threads) while shards i+1.. are still streaming
        over the link. Peak extra host memory is ~one shard instead of
        the whole state, and the shm-copy leg hides entirely behind the
        device link whenever link bandwidth < host memcpy bandwidth
        (reference ckpt_saver.py's _traverse_copy_to_shm drains
        tensor-by-tensor for the same reason).
        """
        import jax

        names, leaves, _treedef = _tree_flatten_with_names(state_dict)
        # Launch every D2H transfer before touching any bytes.
        t0 = time.perf_counter()
        with tracing.span("ckpt.save.launch", step=step):
            for leaf in leaves:
                if isinstance(leaf, jax.Array):
                    leaf.copy_to_host_async()
        launch_s = time.perf_counter() - t0
        metas: list[LeafMeta] = []
        offset = 0
        shard_refs: list = []  # device shards or host arrays, unmaterialised
        for name, leaf in zip(names, leaves):
            for index, data in self._select_shards(leaf):
                if getattr(data, "dtype", None) is None:
                    # dtype-less leaf (python scalar from an exotic
                    # _select_shards): materialise NOW so the reserved
                    # nbytes can never diverge from the drained bytes
                    data = np.asarray(data)
                shape = tuple(np.shape(data))
                dtype = np.dtype(data.dtype)
                nbytes = int(np.prod(shape, dtype=np.int64)) * dtype.itemsize
                meta = LeafMeta(
                    path=name,
                    dtype=str(dtype),
                    shape=shape,
                    offset=offset,
                    nbytes=nbytes,
                    global_shape=tuple(np.shape(leaf)),
                    index=_index_to_meta(index, len(shape)),
                )
                metas.append(meta)
                shard_refs.append(data)
                offset += nbytes
        ckpt_meta = CheckpointMeta(
            step=step,
            leaves=metas,
            treedef=b"",
            engine=self.engine_name,
            host_rank=self._host_rank,
            num_hosts=self._num_hosts,
            total_bytes=offset,
        )
        # two-phase: the meta stays unpublished (readers see "empty")
        # until every byte is drained — a preemption mid-drain must not
        # leave a valid meta over partial tensors
        t0 = time.perf_counter()
        with tracing.span("ckpt.save.reserve", step=step, bytes=offset):
            buf = self._shm_handler.write_meta_and_reserve(
                ckpt_meta, publish=False
            )
        reserve_s = time.perf_counter() - t0
        # Hot path: native multi-threaded scatter copy (libdlrtpu) runs at
        # host memory bandwidth with the GIL released; falls back to the
        # per-shard numpy copy when the native lib is unavailable.
        # Shards are materialised one at a time (bounds host memory and
        # overlaps the remaining in-flight D2H transfers) but FLUSHED in
        # batches so many small leaves still share one threaded native
        # call.
        from dlrover_tpu import native as dlrtpu_native

        flush_bytes = 64 << 20
        pending: list = []
        pending_bytes = 0
        # split the drain into its two real legs for the fill metric:
        # materialise = blocking on the device link (np.asarray waits on
        # the in-flight D2H transfer), fill = the host-side shm memcpy.
        # ckpt_shm_fill_gbps must describe the LATTER — dividing state
        # bytes by the whole drain reports the device link as "shm
        # fill".
        materialize_s = 0.0
        fill_s = 0.0
        # what the drain waits for, shard by shard: the first wait is
        # the latency before any byte arrives, the shards of
        # LARGE_LEAF_BYTES and more give the link's bandwidth with the
        # per-shard latency taken out. One dict a save; a shard or a
        # flush leaves a trace only inside a profiler session.
        first_leaf_s = slowest_leaf_s = 0.0
        slowest_leaf_bytes = 0
        large_bytes, large_s = 0, 0.0

        def _flush():
            nonlocal pending, pending_bytes, fill_s
            if not pending:
                return
            t0 = time.perf_counter()
            with tracing.annotation("ckpt.save.fill", bytes=pending_bytes):
                if not dlrtpu_native.scatter_copy(buf, pending):
                    for off, host_arr in pending:
                        dst = np.frombuffer(
                            buf, dtype=np.uint8, count=host_arr.nbytes,
                            offset=off,
                        )
                        np.copyto(dst, host_arr.reshape(-1).view(np.uint8))
            fill_s += time.perf_counter() - t0
            pending = []
            pending_bytes = 0

        with tracing.span("ckpt.save.drain", step=step, leaves=len(metas)):
            for i, meta in enumerate(metas):
                t0 = time.perf_counter()
                with tracing.annotation("ckpt.save.leaf", bytes=meta.nbytes):
                    host_arr = np.ascontiguousarray(
                        np.asarray(shard_refs[i])
                    )
                leaf_s = time.perf_counter() - t0
                materialize_s += leaf_s
                if i == 0:
                    first_leaf_s = leaf_s
                if leaf_s > slowest_leaf_s:
                    slowest_leaf_s = leaf_s
                    slowest_leaf_bytes = meta.nbytes
                if meta.nbytes >= LARGE_LEAF_BYTES:
                    large_bytes += meta.nbytes
                    large_s += leaf_s
                shard_refs[i] = None  # bound host footprint to ~one batch
                pending.append((meta.offset, host_arr))
                pending_bytes += host_arr.nbytes
                if pending_bytes >= flush_bytes:
                    _flush()
            _flush()
        self._shm_handler.publish_meta()
        self._latest_step = step
        self.last_save_stats = {
            "bytes": offset,
            "materialize_s": materialize_s,
            "fill_s": fill_s,
            "launch_s": launch_s,
            "reserve_s": reserve_s,
            "leaves": len(metas),
            "first_leaf_s": first_leaf_s,
            "slowest_leaf_s": slowest_leaf_s,
            "slowest_leaf_bytes": slowest_leaf_bytes,
            # 1e9 bytes a second; None where no shard is that large
            "large_leaf_gbps": (
                large_bytes / large_s / 1e9 if large_s > 0 else None
            ),
        }
        if fill_s > 0:
            telemetry.gauge_set(
                "ckpt.save.fill_gbps", offset / fill_s / (1 << 30)
            )
        return offset

    def save_to_memory(self, step: int, state_dict) -> bool:
        """Write the state into shm; ~the only blocking time the training
        loop sees. Returns False if skipped (saver busy)."""
        with tracing.span("ckpt.save.shm", step=step):
            return self._save_to_memory_traced(step, state_dict)

    def _save_to_memory_traced(self, step: int, state_dict) -> bool:
        start = time.time()
        if not self._shm_lock.acquire(blocking=False):
            logger.warning(
                "skip shm save at step %s: previous persist in flight", step
            )
            self._report_skip(step)
            return False
        try:
            if not self._all_hosts_ready(step):
                logger.warning("ckpt readiness barrier failed at %s", step)
                return False
            offset = self._write_shm_locked(step, state_dict)
        finally:
            self._shm_lock.release()
        self._notify(SaveEvent(step=step, storage_type="memory"))
        elapsed = time.time() - start
        try:
            from dlrover_tpu.trainer.timer import Tag, get_step_timer

            get_step_timer().record(
                Tag.CKPT_SHM, int(start * 1e9), int(elapsed * 1e9)
            )
        except Exception:  # noqa: BLE001 - timing must never break saves
            pass
        logger.info(
            "saved step %s to shm in %.3fs (%.1f MB)",
            step,
            elapsed,
            offset / 1e6,
        )
        # goodput: the trainer blocks for exactly this window (the
        # async persist downstream does not count). Emitted BEFORE the
        # chaos site so a kill-after-save leaves the save on the
        # timeline ahead of the fire.
        telemetry.event(
            "ckpt.save", step=step, dur=elapsed, mb=offset / 1e6
        )
        # fault site AFTER the shm save committed: a kill here is the
        # canonical "worker dies right after checkpointing step N" —
        # the agent-held shm segment must carry the restore
        chaos_point("ckpt.save", step=step)
        return True

    def save_to_memory_async(
        self, step: int, state_dict, storage_path: str | None = None
    ) -> bool:
        """Non-blocking save: dispatch the HBM->host transfers and hand the
        shm write to a copier thread; the training loop only pays the
        dispatch cost.

        The TPU-native improvement over the reference (whose
        save_state_dict_to_memory blocks on the D2H copy, engine.py:284):
        XLA async dispatch lets the device keep computing while buffers
        drain to the host. CONTRACT: the caller must keep ``state_dict``'s
        arrays alive (no donation of these exact buffers) until
        :meth:`wait_for_shm_save` returns — the Trainer passes the
        *previous* step's state for exactly this reason.
        """
        import jax

        if self._async_thread is not None and self._async_thread.is_alive():
            logger.warning("skip async save %s: previous still running", step)
            self._report_skip(step)
            return False
        if not self._shm_lock.acquire(blocking=False):
            logger.warning("skip async save %s: shm lock busy", step)
            self._report_skip(step)
            return False
        try:
            if not self._all_hosts_ready(step):
                logger.warning("ckpt readiness barrier failed at %s", step)
                self._shm_lock.release()
                return False
            _names, leaves, _ = _tree_flatten_with_names(state_dict)
            for leaf in leaves:
                if isinstance(leaf, jax.Array):
                    leaf.copy_to_host_async()
        except BaseException:
            self._shm_lock.release()
            raise

        def _finish():
            start = time.time()
            try:
                offset = self._write_shm_locked(step, state_dict)
            finally:
                self._shm_lock.release()
            self._notify(SaveEvent(step=step, storage_type="memory"))
            if storage_path is not None:
                self._notify(
                    SaveEvent(
                        step=step, path=storage_path, storage_type="disk"
                    )
                )
            logger.info(
                "async-saved step %s to shm in %.3fs (%.1f MB)",
                step, time.time() - start, offset / 1e6,
            )

        self._async_thread = threading.Thread(
            target=_finish, name=f"ckpt-shm-copier-{step}", daemon=True
        )
        self._async_thread.start()
        return True

    def wait_for_shm_save(self, timeout: float | None = None) -> bool:
        """Join the in-flight async shm write (flush before restart)."""
        t = self._async_thread
        if t is None:
            return True
        t.join(timeout)
        return not t.is_alive()

    def save_to_storage(self, step: int, state_dict, path: str = "") -> bool:
        """Shm write (blocking) + async persistence in the agent."""
        with tracing.span("ckpt.save", step=step, persist=True):
            if not self.save_to_memory(step, state_dict):
                return False
            self._notify(
                SaveEvent(step=step, path=path, storage_type="disk")
            )
            return True

    def _notify(self, event: SaveEvent):
        if self._event_queue is not None:
            self._event_queue.put(event)
        elif self._saver is not None and event.storage_type == "disk":
            self._saver._event_queues[self._local_rank].put(event)

    def _tracker_at_least(self, step: int) -> bool:
        tracker = os.path.join(
            self.checkpoint_dir, CheckpointConstant.TRACKER_FILE
        )
        if not os.path.exists(tracker):
            return False
        try:
            with open(tracker) as f:
                return int(f.read().strip()) >= step
        except (ValueError, OSError):
            return False

    def wait_for_persist(self, step: int, timeout: float = 300) -> bool:
        """Block until the daemon persisted ``step``.

        Event-driven: the saver pushes each persisted step onto the
        done queue, so this wakes the instant the commit lands instead
        of on a poll cadence; the tracker file stays the source of
        truth (re-checked on every wakeup, so missed/stale hints only
        cost latency, never correctness) and the deadline is the
        backstop."""
        deadline = time.time() + timeout
        while True:
            if self._tracker_at_least(step):
                return True
            remaining = deadline - time.time()
            if remaining <= 0:
                return False
            self.wait_for_persist_progress(min(remaining, 2.0))

    def wait_for_persist_progress(self, timeout: float) -> bool:
        """Block until the saver signals ANY persist completed (or
        ``timeout``). Returns True on a wakeup hint — callers re-check
        their own condition either way. Degrades to a short sleep when
        the done queue is unavailable (older agent)."""
        q = self._done_queue
        if q is not None:
            try:
                if q.is_available() or self._standalone:
                    q.get(timeout=max(timeout, 0.0))
                    return True
            except _queue.Empty:
                return False
            except Exception:  # noqa: BLE001 - dead queue: poll instead
                pass
        time.sleep(min(max(timeout, 0.0), 0.05))
        return False

    # ---------------------------------------------------------- load paths

    def load(self, path: str = "", target=None, zero_copy: bool = False):
        """Restore, preferring shm (survives worker restarts within the
        host) and falling back to storage (reference engine.load :315).

        ``zero_copy=True`` (targetless shm loads) returns READ-ONLY
        numpy arrays backed by the shm segment instead of copies — a
        restore that is immediately consumed (``device_put``) completes
        before any new save can rewrite the segment, so the defensive
        copy is pure overhead there (a full state copy of a multi-GB
        checkpoint costs seconds on a busy host). Contract: the arrays
        are invalidated by the next ``save_to_memory``/
        ``save_to_storage`` on this host; finish consuming before
        saving again, and never pass them back into a save (the writer
        would memcpy a region onto itself). Single-piece leaves are
        true views; a leaf saved as multiple shards is assembled into a
        fresh array (also marked read-only for a uniform contract).
        The *targeted* restore path ignores ``zero_copy`` — it is
        already shard-wise (peak host memory ~one shard) and
        device-transfer-bound.

        When the master brokered a restore-step consensus (the agent
        exports ``DLROVER_TPU_RESTORE_STEP`` from rendezvous), shm is
        used only if it holds exactly that step, and storage candidates
        are capped at it — every host of the round restores the SAME
        step even when some hold newer local state."""
        # restore span: shm/storage stage spans and any chaos fire
        # perturbing the restore nest under it in the trace view
        with tracing.span("ckpt.restore.load"):
            return self._load_traced(path, target, zero_copy)

    def _load_traced(
        self, path: str = "", target=None, zero_copy: bool = False
    ):
        t0 = time.monotonic()
        self.last_restore_stats = {}
        consensus = self._consensus_restore_step()
        use_shm = True
        if consensus is not None:
            shm_step = self._shm_handler.get_checkpoint_step()
            use_shm = shm_step == consensus
            if shm_step > consensus:
                telemetry.event(
                    "ckpt.consensus.forced",
                    step=consensus,
                    local_newest=shm_step,
                    source_kind="shm",
                )
                logger.warning(
                    "consensus restore step %d overrides newer local "
                    "shm checkpoint (step %d)", consensus, shm_step,
                )
        if use_shm:
            result = self._load_from_memory(target, zero_copy=zero_copy)
            if result is not None:
                self._record_restore(result, "shm", t0, consensus)
                return result
        result = self.load_from_storage(
            path, target, max_step=consensus
        )
        if consensus is not None and not path:
            # the consensus step was advertised as restorable on every
            # host, this one included (the agent's join said so); a
            # quiet restore of anything OLDER would resume this host at
            # a different step than its peers — the exact split-world
            # the consensus exists to prevent. Fail loudly instead: the
            # agent restarts the worker and the next rendezvous
            # recomputes availability from what is actually on disk.
            got = self._result_step(result)
            if got != consensus:
                # loop-breaker: the advertisement scan trusts the
                # .verified CRC cache, and post-verify bit-rot (size
                # unchanged) can keep a rotten dir advertised forever;
                # dropping its marker forces the next join's scan to
                # re-CRC the dir and stop advertising it, so the
                # restart converges instead of livelocking
                marker = os.path.join(
                    self.checkpoint_dir,
                    f"{CheckpointConstant.STEP_DIR_PREFIX}{consensus}",
                    _VERIFIED_MARKER,
                )
                try:
                    os.remove(marker)
                except OSError:
                    pass
                raise ValueError(
                    f"consensus restore step {consensus} is not "
                    f"restorable on this host (newest loadable: "
                    f"{got if got >= 0 else 'none'}) — refusing to "
                    f"silently resume at a different step than the "
                    f"rest of the job"
                )
        if result is not None:
            self._record_restore(result, "storage", t0, consensus)
        return result

    @staticmethod
    def _result_step(result) -> int:
        if result is None:
            return -1
        if isinstance(result, tuple):
            return int(result[1])
        return int(result.get("step", -1))

    @staticmethod
    def _consensus_restore_step() -> int | None:
        """Master-brokered min verified step (env, set by the agent per
        rendezvous round); None = unconstrained local restore."""
        raw = os.environ.get(NodeEnv.RESTORE_STEP, "")
        if not raw:
            return None
        try:
            step = int(raw)
        except ValueError:
            logger.warning(
                "ignoring malformed %s=%r", NodeEnv.RESTORE_STEP, raw
            )
            return None
        return step if step >= 0 else None

    def _record_restore(self, result, source_kind: str, t0: float,
                        consensus):
        fields = dict(
            step=self._result_step(result),
            source_kind=source_kind,
            dur=time.monotonic() - t0,
        )
        if consensus is not None:
            fields["consensus"] = consensus
        telemetry.event("ckpt.restore", **fields)
        _publish_restore_stats(self.last_restore_stats)

    def _load_from_memory(self, target=None, zero_copy: bool = False):
        with tracing.span("ckpt.restore.shm"):
            return self._load_from_memory_traced(target, zero_copy)

    def _load_from_memory_traced(
        self, target=None, zero_copy: bool = False
    ):
        result = self._shm_handler.read()
        if result is None:
            return None
        meta, buf = result
        # dedup: meta.leaves holds one entry per *shard*, so a multi-
        # shard array repeats its path — the collision check must see
        # unique paths only (mirrors the disk path)
        names = _translate_legacy_names(
            sorted({l.path for l in meta.leaves})
        )
        piece_map: dict[str, list] = {}
        for leaf in meta.leaves:
            piece_map.setdefault(names[leaf.path], []).append(
                (leaf, buf, None)
            )
        meta_view = {
            k: [(m, None) for m, _, _ in v] for k, v in piece_map.items()
        }
        if target is not None:
            # This host's shm may legitimately hold only a subset of the
            # leaves (sharded engine dedups host-replicated leaves to one
            # writer) — an incomplete shm restore must fall back to
            # storage rather than silently keep freshly-init leaves.
            tnames, _, _ = _tree_flatten_with_names(target)
            if any(name not in piece_map for name in tnames):
                logger.info(
                    "shm checkpoint incomplete for this host; falling "
                    "back to storage"
                )
                return None
        if not _covers_global(meta_view):
            logger.info(
                "shm shards do not cover the global arrays (multi-host "
                "state); falling back to storage"
            )
            return None
        if target is not None:
            # shard-wise fill straight from shm views: a target shard
            # copies only its intersecting boxes (peak host memory ~one
            # shard; the full-global assemble would double the state's
            # host footprint at 7B scale)
            result = self._fill_from_pieces(
                piece_map, target, meta.step, _shm_read_box
            )
            logger.info(
                "restored step %s from shared memory (shard-wise)",
                meta.step,
            )
            return result
        leaf_map: dict[str, list[tuple[LeafMeta, np.ndarray]]] = {}
        all_pieces = [p for pieces in piece_map.values() for p in pieces]
        if zero_copy:
            # read-only views for the restart path (see load() docstring
            # for the validity contract)
            for leaf, _, _ in all_pieces:
                arr = np.frombuffer(
                    buf,
                    dtype=np.dtype(leaf.dtype),
                    count=_count(leaf.shape),
                    offset=leaf.offset,
                ).reshape(leaf.shape)
                arr = arr.view()
                arr.flags.writeable = False
                leaf_map.setdefault(names[leaf.path], []).append(
                    (leaf, arr)
                )
        else:
            # default: copy — never hand out writable views into the
            # live shm buffer (the next save would rewrite them under
            # the caller). ONE threaded native gather pass drains every
            # leaf out of shm at memory bandwidth instead of a
            # single-threaded numpy memcpy per leaf (the
            # restore_shm_copy_s leg); destinations are fresh arrays —
            # restored state must never alias pooled or shm memory.
            from dlrover_tpu import native as dlrtpu_native

            t0 = time.perf_counter()
            parts = []
            for leaf, _, _ in all_pieces:
                dst = np.empty(leaf.shape, np.dtype(leaf.dtype))
                parts.append((leaf.offset, dst))
                leaf_map.setdefault(names[leaf.path], []).append(
                    (leaf, dst)
                )
            gather_parts = [
                (off, np.atleast_1d(dst)) for off, dst in parts
            ]
            if not dlrtpu_native.gather_copy(buf, gather_parts):
                for off, dst in gather_parts:
                    flat = dst.view(np.uint8).reshape(-1)
                    np.copyto(
                        flat,
                        np.frombuffer(
                            buf, np.uint8, count=flat.nbytes, offset=off
                        ),
                    )
            stats = self.last_restore_stats
            stats["read_s"] = stats.get("read_s", 0.0) + (
                time.perf_counter() - t0
            )
            stats["bytes"] = stats.get("bytes", 0) + sum(
                dst.nbytes for _, dst in parts
            )
        state = _assemble(leaf_map)
        if zero_copy:
            # multi-shard leaves come out of _assemble as fresh arrays;
            # freeze them too so the read-only contract is uniform
            for arr in state.values():
                arr.flags.writeable = False
        logger.info("restored step %s from shared memory", meta.step)
        return _fill_target(state, target, meta.step)

    def load_from_storage(
        self, path: str = "", target=None, max_step: int | None = None,
    ):
        """Restore from storage with VERIFIED fallback.

        Candidate step dirs are tried newest-first; each must pass
        :func:`verify_step_dir` (per-shard manifest: payload size +
        recomputed checksum) before a single byte is deserialized, so a
        torn or bit-flipped newest checkpoint makes restore fall back to
        the newest *complete, verified* step instead of loading garbage
        or refusing entirely. An explicit ``path`` is verified too, and
        a named-but-corrupt checkpoint RAISES instead of silently
        degrading to train-from-scratch — the caller asked for that
        exact state, so nothing else can substitute for it. (A named
        path that does not exist keeps returning None — "restore if
        present" probing predates this contract — but is loudly
        logged.)

        Verification depth follows the load path: the eager
        (targetless) loader re-checks every payload's embedded crc
        itself, so it gets the cheap structural/size verify; the
        targeted shard-wise loader does crc-less slice reads, so its
        candidates get the deep payload-crc verify.
        """
        with tracing.span("ckpt.restore.storage"):
            return self._load_from_storage_traced(path, target, max_step)

    def _load_from_storage_traced(
        self, path: str = "", target=None, max_step: int | None = None,
    ):
        candidates = [path] if path else self._candidate_step_dirs()
        self.last_restore_stats = {}
        if not path and max_step is not None:
            # consensus cap: steps newer than the job-wide agreed
            # restore step are off-limits (an explicit path stays the
            # caller's responsibility — they asked for that exact state)
            kept, skipped_steps = [], []
            prefix = CheckpointConstant.STEP_DIR_PREFIX
            for step_dir in candidates:
                try:
                    step = int(os.path.basename(step_dir)[len(prefix):])
                except ValueError:
                    step = -1
                if step > max_step:
                    skipped_steps.append(step)
                else:
                    kept.append(step_dir)
            if skipped_steps:
                telemetry.event(
                    "ckpt.consensus.forced",
                    step=max_step,
                    local_newest=max(skipped_steps),
                    source_kind="storage",
                )
                logger.warning(
                    "consensus restore step %d skips newer local "
                    "storage steps %s", max_step, sorted(skipped_steps),
                )
            candidates = kept
        for step_dir in candidates:
            if not step_dir or not os.path.isdir(step_dir):
                if path:
                    logger.warning(
                        "explicitly named checkpoint path %s does not "
                        "exist; treating as no checkpoint", path,
                    )
                continue
            t_verify = time.perf_counter()
            ok, reason = verify_step_dir(
                step_dir, deep=target is not None
            )
            verify_s = time.perf_counter() - t_verify
            if not ok:
                if path:
                    raise ValueError(
                        f"checkpoint at {step_dir} failed integrity "
                        f"verification ({reason}) — refusing to load "
                        f"an explicitly named torn/corrupt checkpoint"
                    )
                telemetry.event(
                    "ckpt.fallback",
                    dir=os.path.basename(step_dir),
                    reason=reason[:200],
                )
                telemetry.counter_inc("ckpt.fallbacks")
                logger.warning(
                    "checkpoint %s failed integrity verification (%s); "
                    "falling back to an older checkpoint",
                    step_dir, reason,
                )
                continue
            self.last_restore_stats = {"verify_s": verify_s}
            result = self._load_step_dir(step_dir, target)
            if result is not None:
                _publish_restore_stats(self.last_restore_stats)
                return result
            if path:
                # shallow verify can pass (size ok) while the loader's
                # own payload-crc check rejects the shard, or the dir
                # may be missing shards: a named checkpoint that cannot
                # be loaded must raise, not silently train from scratch
                raise ValueError(
                    f"checkpoint at {step_dir} is incomplete or failed "
                    f"its payload checks — refusing to substitute "
                    f"anything for an explicitly named checkpoint"
                )
            telemetry.event(
                "ckpt.fallback",
                dir=os.path.basename(step_dir),
                reason="incomplete",
            )
            telemetry.counter_inc("ckpt.fallbacks")
            logger.warning(
                "checkpoint %s is incomplete; falling back to an older "
                "checkpoint", step_dir,
            )
        return None

    def _candidate_step_dirs(self) -> list[str]:
        """All persisted step dirs, newest first. The tracker's step is
        just the first candidate — a tracker advertising a step whose
        dir fails verification must not brick the restore."""
        from dlrover_tpu.agent.ckpt_saver import list_step_numbers

        prefix = CheckpointConstant.STEP_DIR_PREFIX
        steps = set(list_step_numbers(self.checkpoint_dir))
        tracker_step = AsyncCheckpointSaver.get_latest_step(
            self.checkpoint_dir
        )
        if tracker_step >= 0:
            steps.add(tracker_step)
        return [
            os.path.join(self.checkpoint_dir, f"{prefix}{s}")
            for s in sorted(steps, reverse=True)
        ]

    def _load_step_dir(self, step_dir: str, target=None):
        """Deserialize ONE verified step directory.

        With a ``target``, the restore is SHARD-WISE (reference
        fsdp_engine.py:341 FileReader): only metas are unpickled, and
        each target device shard reads just the byte ranges of the saved
        pieces it intersects via ``np.memmap`` — peak extra host memory
        is ~one shard, not the global array, so restoring a 7B-class
        state into a *different* mesh cannot OOM the host. (Slice reads
        skip the whole-payload CRC; verify_step_dir already covered
        integrity for both paths.)

        Without a target (eager path), shard FILES are read in parallel
        through a bounded pool; each read is chunked with the payload
        CRC verified incrementally as chunks land (one traversal per
        shard — disk I/O and checksumming overlap across shards instead
        of summing).
        """
        if target is not None:
            return self._load_storage_sharded(step_dir, target)
        fnames = [
            f for f in sorted(os.listdir(step_dir))
            if f.endswith(".dlck")
        ]
        per_shard_stats = [dict() for _ in fnames]

        def _read(i: int):
            return read_host_shard(
                os.path.join(step_dir, fnames[i]),
                stats=per_shard_stats[i],
            )

        nthreads = min(_restore_threads(), max(len(fnames), 1))
        if nthreads > 1:
            with ThreadPoolExecutor(
                nthreads, thread_name_prefix="ckpt-restore"
            ) as pool:
                shard_results = list(pool.map(_read, range(len(fnames))))
        else:
            shard_results = [_read(i) for i in range(len(fnames))]
        entries: list[tuple[LeafMeta, np.ndarray]] = []
        step = -1
        for result in shard_results:
            if result is None:
                continue
            meta, data = result
            step = max(step, meta.step)
            for leaf in meta.leaves:
                arr = np.frombuffer(
                    data,
                    dtype=np.dtype(leaf.dtype),
                    count=_count(leaf.shape),
                    offset=leaf.offset,
                ).reshape(leaf.shape)
                entries.append((leaf, arr))
        stats = self.last_restore_stats
        for s in per_shard_stats:
            for k, v in s.items():
                stats[k] = stats.get(k, 0) + v
        if not entries:
            return None
        names = _translate_legacy_names(
            sorted({leaf.path for leaf, _ in entries})
        )
        leaf_map: dict[str, list[tuple[LeafMeta, np.ndarray]]] = {}
        for leaf, arr in entries:
            leaf_map.setdefault(names[leaf.path], []).append((leaf, arr))
        if not _covers_global(leaf_map):
            logger.warning(
                "checkpoint at %s is missing shards; refusing a partial "
                "restore", step_dir,
            )
            return None
        state = _assemble(leaf_map)
        logger.info("restored step %s from %s", step, step_dir)
        return _fill_target(state, target, step)

    def _load_storage_sharded(self, step_dir: str, target):
        """Meta-only scan + per-target-shard slice reads."""
        import jax

        from dlrover_tpu.agent.ckpt_saver import read_host_shard_meta

        pieces_by_path: list[tuple[LeafMeta, str, int]] = []
        step = -1
        for fname in sorted(os.listdir(step_dir)):
            if not fname.endswith(".dlck"):
                continue
            fpath = os.path.join(step_dir, fname)
            result = read_host_shard_meta(fpath)
            if result is None:
                continue
            meta, payload_start = result
            step = max(step, meta.step)
            for leaf in meta.leaves:
                pieces_by_path.append((leaf, fpath, payload_start))
        if not pieces_by_path:
            return None
        names = _translate_legacy_names(
            sorted({leaf.path for leaf, _, _ in pieces_by_path})
        )
        piece_map: dict[str, list[tuple[LeafMeta, str, int]]] = {}
        for leaf, fpath, ps in pieces_by_path:
            piece_map.setdefault(names[leaf.path], []).append(
                (leaf, fpath, ps)
            )
        meta_view = {
            k: [(m, None) for m, _, _ in v] for k, v in piece_map.items()
        }
        if not _covers_global(meta_view):
            logger.warning(
                "checkpoint at %s is missing shards; refusing a partial "
                "restore", step_dir,
            )
            return None
        tnames, _, _ = _tree_flatten_with_names(target)
        missing = [n for n in tnames if n not in piece_map]
        if missing:
            raise ValueError(
                f"checkpoint at {step_dir} is missing "
                f"{len(missing)} target leaves (e.g. {missing[:3]}) "
                f"— refusing a partial restore of a changed model"
            )
        result = self._fill_from_pieces(piece_map, target, step, _read_box)
        logger.info(
            "restored step %s from %s (shard-wise)", step, step_dir
        )
        return result

    def _fill_from_pieces(self, piece_map, target, step, read_box):
        """Rebuild the target pytree shard-wise from saved pieces —
        PIPELINED: leaves are processed by a bounded reader pool, and
        each leaf's device transfer is dispatched (async, serialized by
        the dispatch lock) as soon as its host bytes are assembled, so
        disk/shm reads for later leaves overlap the in-flight H2D
        transfers of earlier ones instead of summing. One barrier at
        the end waits out the transfers (timed as the ``h2d`` leg)."""
        import jax

        tnames, tleaves, treedef = _tree_flatten_with_names(target)
        new_leaves: list = [None] * len(tnames)
        stats_lock = threading.Lock()
        read_s_total = [0.0]
        bytes_total = [0]

        def _build(i: int):
            name, leaf_t = tnames[i], tleaves[i]
            pieces = piece_map[name]
            want_shape = tuple(np.shape(leaf_t))
            got_shape = tuple(
                pieces[0][0].global_shape
                if pieces[0][0].index is not None
                else pieces[0][0].shape
            )
            if want_shape and got_shape != want_shape:
                raise ValueError(
                    f"checkpoint leaf {name} has shape {got_shape}, "
                    f"target expects {want_shape} — refusing a silent "
                    f"mismatched restore (stale or foreign checkpoint?)"
                )
            want_dtype = getattr(leaf_t, "dtype", None)
            got_dtype = np.dtype(pieces[0][0].dtype)
            if want_dtype is not None and got_dtype != np.dtype(
                want_dtype
            ):
                raise ValueError(
                    f"checkpoint leaf {name} has dtype {got_dtype}, "
                    f"target expects {np.dtype(want_dtype)} — refusing "
                    f"a silent mismatched-dtype restore"
                )
            t0 = time.perf_counter()
            arr = _restore_leaf_to_sharding(pieces, leaf_t, read_box)
            if arr is None:
                host = _assemble_one(pieces, read_box)
                if isinstance(leaf_t, jax.Array) and hasattr(
                    leaf_t, "sharding"
                ):
                    # dlint: allow-blocking(async dispatch only — see _H2D_DISPATCH_LOCK)
                    with _H2D_DISPATCH_LOCK:
                        host = jax.device_put(host, leaf_t.sharding)
                elif isinstance(leaf_t, jax.ShapeDtypeStruct):
                    sharding = getattr(leaf_t, "sharding", None)
                    if sharding is not None:
                        # dlint: allow-blocking(async dispatch only — see _H2D_DISPATCH_LOCK)
                        with _H2D_DISPATCH_LOCK:
                            host = jax.device_put(host, sharding)
                    else:
                        host = jax.numpy.asarray(host)
                else:
                    host = np.array(host)  # detach from live shm views
                arr = host
            with stats_lock:
                # read+assemble+dispatch thread-seconds; the blocking
                # transfer wait is timed once at the barrier below
                read_s_total[0] += time.perf_counter() - t0
                bytes_total[0] += int(
                    np.prod(want_shape, dtype=np.int64)
                ) * got_dtype.itemsize
            new_leaves[i] = arr

        nthreads = min(_restore_threads(), max(len(tnames), 1))
        if nthreads > 1 and len(tnames) > 1:
            with ThreadPoolExecutor(
                nthreads, thread_name_prefix="ckpt-restore"
            ) as pool:
                for fut in [
                    pool.submit(_build, i) for i in range(len(tnames))
                ]:
                    fut.result()  # surface the first validation error
        else:
            for i in range(len(tnames)):
                _build(i)
        t_h2d = time.perf_counter()
        jax.block_until_ready(
            [a for a in new_leaves if isinstance(a, jax.Array)]
        )
        stats = self.last_restore_stats
        stats["h2d_s"] = stats.get("h2d_s", 0.0) + (
            time.perf_counter() - t_h2d
        )
        stats["read_s"] = stats.get("read_s", 0.0) + read_s_total[0]
        stats["bytes"] = stats.get("bytes", 0) + bytes_total[0]
        return (
            jax.tree_util.tree_unflatten(treedef, new_leaves), step,
        )

    def latest_step(self) -> int:
        shm_step = self._shm_handler.get_checkpoint_step()
        disk_step = AsyncCheckpointSaver.get_latest_step(self.checkpoint_dir)
        return max(shm_step, disk_step)

    def close(self):
        self._shm_handler.close()


def _count(shape) -> int:
    n = 1
    for s in shape:
        n *= s
    return n


def _covers_global(leaf_map) -> bool:
    """Every leaf's pieces must tile its full global shape (pieces are
    non-overlapping unique shards, so volumes may be summed)."""
    for _name, pieces in leaf_map.items():
        meta0 = pieces[0][0]
        if meta0.index is None or tuple(meta0.shape) == tuple(
            meta0.global_shape
        ):
            continue
        total = _count(meta0.global_shape)
        have = sum(_count(m.shape) for m, _ in pieces)
        if have < total:
            return False
    return True


def _piece_slices(meta: "LeafMeta"):
    """Global-coordinate region a saved piece covers. Index bounds may
    be None on unsharded dims (a full-extent slice): normalise against
    the piece's local shape."""
    if meta.index is not None:
        out = []
        for (a, b), dim in zip(meta.index, meta.shape):
            start = 0 if a is None else int(a)
            stop = start + int(dim) if b is None else int(b)
            out.append(slice(start, stop))
        return tuple(out)
    return tuple(slice(0, int(s)) for s in meta.shape)


def _intersect_boxes(a, b):
    out = []
    for sa, sb in zip(a, b):
        lo, hi = max(sa.start, sb.start), min(sa.stop, sb.stop)
        if lo >= hi:
            return None
        out.append(slice(lo, hi))
    return tuple(out)


def _read_box(fpath: str, payload_start: int, meta: "LeafMeta", box):
    """Materialise only the global-coordinate ``box`` of a saved piece:
    the payload is memory-mapped, so the OS pages in just the touched
    byte ranges (the FileReader-style lazy read)."""
    ps = _piece_slices(meta)
    local = tuple(
        slice(b.start - p.start, b.stop - p.start)
        for b, p in zip(box, ps)
    )
    mm = np.memmap(
        fpath, dtype=np.dtype(meta.dtype), mode="r",
        offset=payload_start + meta.offset, shape=tuple(meta.shape),
    )
    out = np.asarray(mm[local]) if local else np.asarray(mm)
    del mm
    return out


def _norm_index(idx, global_shape):
    out = []
    for sl, dim in zip(idx, global_shape):
        start = 0 if sl.start is None else int(sl.start)
        stop = int(dim) if sl.stop is None else int(sl.stop)
        out.append(slice(start, stop))
    return tuple(out)


def _assemble_one(pieces, read_box=None):
    """Eagerly assemble ONE leaf from (meta, src1, src2) pieces (used
    for target leaves without a usable sharding)."""
    if read_box is None:
        read_box = _read_box
    meta0 = pieces[0][0]
    if len(pieces) == 1 and (
        meta0.index is None
        or tuple(meta0.shape) == tuple(meta0.global_shape)
    ):
        meta, s1, s2 = pieces[0]
        return read_box(s1, s2, meta, _piece_slices(meta))
    gshape = tuple(meta0.global_shape)
    full = np.empty(gshape, dtype=np.dtype(meta0.dtype))
    for meta, s1, s2 in pieces:
        sl = _piece_slices(meta)
        full[sl] = read_box(s1, s2, meta, sl)
    return full


def _restore_leaf_to_sharding(pieces, leaf_target, read_box=None):
    """Build a sharded jax.Array for ``leaf_target`` by reading, for
    each addressable device shard, only the intersecting saved byte
    ranges. ``pieces`` are (meta, src1, src2) where the default
    ``read_box`` memmaps (src1=path, src2=payload offset); the shm path
    passes a reader slicing zero-copy views of the live buffer.
    Returns None when the target carries no usable sharding (caller
    assembles eagerly) or the pieces leave holes."""
    import jax

    if read_box is None:
        read_box = _read_box
    sharding = getattr(leaf_target, "sharding", None)
    gshape = tuple(np.shape(leaf_target))
    if sharding is None or not gshape:
        return None
    try:
        dev_map = sharding.addressable_devices_indices_map(gshape)
    except Exception:  # noqa: BLE001 - exotic shardings -> eager path
        return None
    dtype = np.dtype(pieces[0][0].dtype)
    shard_arrays = []
    host_cache: dict = {}  # box -> host buffer (replicated shards share)
    for dev, idx in dev_map.items():
        box_t = _norm_index(idx, gshape)
        key = tuple((s.start, s.stop) for s in box_t)
        out = host_cache.get(key)
        if out is None:
            out = np.empty(
                tuple(s.stop - s.start for s in box_t), dtype
            )
            filled = 0
            for meta, src1, src2 in pieces:
                inter = _intersect_boxes(box_t, _piece_slices(meta))
                if inter is None:
                    continue
                src = read_box(src1, src2, meta, inter)
                dst = tuple(
                    slice(i.start - b.start, i.stop - b.start)
                    for i, b in zip(inter, box_t)
                )
                out[dst] = src
                filled += src.size
            if filled < out.size:
                return None
            host_cache[key] = out
        # async dispatch under the lock: the transfer itself overlaps
        # the next shard's read (and other leaves' reads — this runs on
        # the restore pool's worker threads)
        # dlint: allow-blocking(async dispatch only — see _H2D_DISPATCH_LOCK)
        with _H2D_DISPATCH_LOCK:
            shard_arrays.append(jax.device_put(out, dev))
    with _H2D_DISPATCH_LOCK:
        return jax.make_array_from_single_device_arrays(
            gshape, sharding, shard_arrays
        )


def _shm_read_box(buf, _unused, meta, box):
    """Zero-copy reader over the live shm buffer (the per-shard ``out``
    buffers are fresh allocations, so no view escapes)."""
    view = np.frombuffer(
        buf, dtype=np.dtype(meta.dtype), count=_count(meta.shape),
        offset=meta.offset,
    ).reshape(meta.shape)
    ps = _piece_slices(meta)
    local = tuple(
        slice(b.start - p.start, b.stop - p.start)
        for b, p in zip(box, ps)
    )
    return view[local] if local else view


def _assemble(leaf_map) -> dict:
    """Merge saved shards into full host arrays: exact single shard, or
    reassemble the global array from (global_shape, index) pieces."""
    out = {}
    for name, pieces in leaf_map.items():
        if len(pieces) == 1 and (
            pieces[0][0].index is None
            or tuple(pieces[0][0].shape) == tuple(pieces[0][0].global_shape)
        ):
            out[name] = pieces[0][1]
            continue
        gshape = pieces[0][0].global_shape
        full = np.empty(gshape, dtype=pieces[0][1].dtype)
        for leaf, arr in pieces:
            if leaf.index is None:
                full[...] = arr
                continue
            slices = tuple(
                slice(start, stop) for start, stop in leaf.index
            )
            full[slices] = arr
        out[name] = full
    return out


def _fill_target(state: dict, target, step: int):
    """Rebuild the caller's pytree (and shardings) from the flat state."""
    if target is None:
        return {"step": step, "state": state}
    import jax

    names, leaves, treedef = _tree_flatten_with_names(target)
    new_leaves = []
    for name, leaf in zip(names, leaves):
        if name not in state:
            logger.warning("checkpoint missing leaf %s; keeping target", name)
            new_leaves.append(leaf)
            continue
        arr = state[name]
        want_shape = tuple(np.shape(leaf))
        if want_shape and tuple(arr.shape) != want_shape:
            raise ValueError(
                f"checkpoint leaf {name} has shape {tuple(arr.shape)}, "
                f"target expects {want_shape} — refusing a silent "
                f"mismatched restore (stale or foreign checkpoint?)"
            )
        want_dtype = getattr(leaf, "dtype", None)
        if want_dtype is not None and np.dtype(arr.dtype) != np.dtype(
            want_dtype
        ):
            raise ValueError(
                f"checkpoint leaf {name} has dtype {arr.dtype}, target "
                f"expects {np.dtype(want_dtype)} — refusing a silent "
                f"mismatched-dtype restore"
            )
        if isinstance(leaf, jax.Array) and hasattr(leaf, "sharding"):
            arr = jax.device_put(arr, leaf.sharding)
        elif isinstance(leaf, jax.ShapeDtypeStruct):
            sharding = getattr(leaf, "sharding", None)
            arr = (
                jax.device_put(arr, sharding)
                if sharding is not None
                else jax.numpy.asarray(arr)
            )
        new_leaves.append(arr)
    return jax.tree_util.tree_unflatten(treedef, new_leaves), step


class ReplicatedCheckpointEngine(CheckpointEngine):
    """Pure-DP states: all hosts identical; host 0 writes everything
    (reference DdpCheckpointEngine ddp_engine.py:33)."""

    engine_name = "replicated"

    def _select_shards(self, arr):
        if self._host_rank != 0:
            return []
        import jax

        if isinstance(arr, jax.Array):
            # take one full copy (first addressable shard covers the
            # array when replicated; otherwise gather to host).
            # Metadata-only shape read: np.asarray here would block on
            # and host-materialize every leaf during the meta pass,
            # defeating the chunked drain's one-shard host footprint.
            shards = _unique_addressable_shards(arr)
            if (
                len(shards) == 1
                and tuple(np.shape(shards[0][1])) == tuple(arr.shape)
            ):
                return [(None, shards[0][1])]
            return [(None, arr)]
        return [(None, np.asarray(arr))]

    def save_to_memory(self, step: int, state_dict) -> bool:
        if self._host_rank != 0:
            # non-zero hosts only take part in the readiness barrier
            return self._all_hosts_ready(step)
        return super().save_to_memory(step, state_dict)

    def save_to_memory_async(
        self, step: int, state_dict, storage_path: str | None = None
    ) -> bool:
        if self._host_rank != 0:
            # no shards to write here: joining the barrier is the whole
            # job — inheriting the async path would persist empty shards
            # whose .done markers corrupt host 0's commit count
            return self._all_hosts_ready(step)
        return super().save_to_memory_async(step, state_dict, storage_path)


class ShardedCheckpointEngine(CheckpointEngine):
    """GSPMD states: each host writes its unique addressable shards
    (reference MegatronCheckpointEngine/FsdpCheckpointEngine analogue —
    saving ranks = one replica of each shard, global shards = the mesh
    model axes)."""

    engine_name = "sharded"

    def _select_shards(self, arr):
        import jax

        if not isinstance(arr, jax.Array):
            # process-local (host) array: host 0 owns it
            return (
                [(None, np.asarray(arr))] if self._host_rank == 0 else []
            )
        shards = _unique_addressable_shards(arr)
        if self._num_hosts > 1:
            # a replicated-across-hosts shard must be written by exactly
            # one host: the lowest process index among its holders
            filtered = []
            for index, data in shards:
                holders = _holder_processes(arr, index)
                if not holders or min(holders) == self._host_rank:
                    filtered.append((index, data))
            return filtered
        return shards


def _holder_processes(arr, index) -> list[int]:
    import jax

    key = (
        tuple((s.start, s.stop, s.step) for s in index)
        if index is not None
        else None
    )
    holders = set()
    for shard in arr.global_shards:
        skey = (
            tuple((s.start, s.stop, s.step) for s in shard.index)
            if shard.index is not None
            else None
        )
        if skey == key:
            holders.add(shard.device.process_index)
    return sorted(holders)
