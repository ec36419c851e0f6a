"""KvEmbedding: dynamic sparse embedding tables, TPU-idiomatic.

Equivalent capability: reference TFPlus KvVariable
(tfplus/tfplus/kv_variable/kernels/kv_variable.h — libcuckoo hash table of
id -> embedding, lazy init, frequency tracking, under-threshold eviction
on export; ops kv_variable_ops.cc:37-466) and its Python wrappers
(python/ops/kv_variable_ops.py, embedding_ops.py).

TPU redesign: XLA wants static shapes, so the device side is a fixed-
capacity ``[capacity, dim]`` table (rows shard over the mesh like any
other parameter; lookups are a ``take`` that XLA lowers to efficient
dynamic-gather, and gradients flow through standard autodiff as
scatter-adds). The *dynamic* part lives on the host: an :class:`IdMapper`
assigns raw feature ids to table slots on first sight (the "insert on
lookup" semantics of KvVariable), tracks per-id frequencies, and evicts
cold ids to recycle slots — all outside jit, so the compiled step never
changes shape. Export/import round-trips (id, vector, freq) triples with
under-threshold filtering, matching KvVariableExport/Import semantics.

The mapper is array-backed (sorted id keys + aligned slot/freq arrays,
all queries are ``np.searchsorted``/boolean-mask batch operations): a
lookup of N ids costs a handful of O(N log K) vectorized numpy calls,
never a per-id Python loop. The reference gets the same property from
its C++ hash map; numpy's C kernels are the TPU-host equivalent.
"""

from __future__ import annotations

import threading

import numpy as np

from dlrover_tpu.common.log import get_logger

logger = get_logger(__name__)

_EMPTY_I64 = np.zeros((0,), np.int64)
_EMPTY_I32 = np.zeros((0,), np.int32)


class IdMapper:
    """Host-side id -> slot assignment with frequencies and eviction.

    Storage is three aligned contiguous arrays — ``_ids`` (sorted int64
    keys), ``_slots`` (int32, -1 = known id without a device slot, e.g.
    demoted to a host tier) and ``_freqs`` (int64) — plus a LIFO free-
    slot stack. Every operation is a batched numpy set-op; nothing
    iterates ids in Python.
    """

    def __init__(self, capacity: int):
        self.capacity = int(capacity)
        self._lock = threading.Lock()
        self._ids = _EMPTY_I64
        self._slots = _EMPTY_I32
        self._freqs = _EMPTY_I64
        # LIFO stack: _free[:_n_free] are free slots; popping from the
        # end yields ascending slot numbers on a fresh mapper
        self._free = np.arange(self.capacity - 1, -1, -1, dtype=np.int32)
        self._n_free = self.capacity

    def __len__(self):
        with self._lock:
            return int((self._slots >= 0).sum())

    # ------------------------------------------------- internal (lock held)

    def _positions(self, keys: np.ndarray):
        """(pos, found): searchsorted positions of ``keys`` in ``_ids``
        and a mask of which are present. ``keys`` need not be sorted."""
        if self._ids.size == 0:
            return np.zeros(keys.shape, np.int64), np.zeros(keys.shape, bool)
        pos = np.searchsorted(self._ids, keys)
        found = (pos < self._ids.size) & (
            self._ids[np.minimum(pos, self._ids.size - 1)] == keys
        )
        return pos, found

    def _insert_keys(self, new_keys: np.ndarray):
        """Insert sorted unique keys (none present) with slot=-1, freq=0."""
        ipos = np.searchsorted(self._ids, new_keys)
        self._ids = np.insert(self._ids, ipos, new_keys)
        self._slots = np.insert(self._slots, ipos, np.int32(-1))
        self._freqs = np.insert(self._freqs, ipos, np.int64(0))

    def _push_free(self, slots: np.ndarray):
        n = slots.size
        self._free[self._n_free:self._n_free + n] = slots
        self._n_free += n

    def _pop_free(self, k: int) -> np.ndarray:
        """Pop ``k`` slots in the same order repeated ``list.pop()`` gave."""
        take = self._free[self._n_free - k:self._n_free][::-1].copy()
        self._n_free -= k
        return take

    # ------------------------------------------------------------ queries

    def lookup(self, ids: np.ndarray, count: bool = True) -> np.ndarray:
        """Map raw ids to slots, inserting unseen ids (KvVariable's
        gather-or-insert). Raises when the table is full — callers evict
        first. Capacity is validated up front so a failed batch mutates
        nothing (safe to evict and retry the same batch)."""
        flat = np.asarray(ids).reshape(-1).astype(np.int64, copy=False)
        if flat.size == 0:
            return np.zeros(np.shape(ids), np.int32)
        uniq, inv, counts = np.unique(
            flat, return_inverse=True, return_counts=True
        )
        uslots = self.lookup_unique(uniq, counts if count else None)
        out = uslots[inv.reshape(-1)]
        return out.reshape(np.shape(ids))

    def lookup_unique(self, uniq: np.ndarray,
                      counts: np.ndarray | None = None) -> np.ndarray:
        """:meth:`lookup` for callers that ALREADY hold the sorted
        unique ids (e.g. prepare_batch, which uniques the batch once
        and reuses it) — skips the extra ``np.unique`` pass. ``counts``
        when given is added to the ids' frequencies. Returns int32
        slots aligned with ``uniq``."""
        with self._lock:
            pos, found = self._positions(uniq)
            have_slot = np.zeros(uniq.shape, bool)
            if found.any():
                have_slot[found] = self._slots[pos[found]] >= 0
            n_need = int((~have_slot).sum())
            if n_need > self._n_free:
                raise RuntimeError(
                    f"KvEmbedding capacity {self.capacity} exhausted "
                    f"({n_need} new ids, {self._n_free} free "
                    "slots); evict() first"
                )
            new = uniq[~found]
            if new.size:
                # demoted ids returning from a host tier keep their
                # frequency history (_insert_keys only runs for ids the
                # mapper has never seen; evict_ids retains the key row)
                self._insert_keys(new)
                pos = np.searchsorted(self._ids, uniq)
            slots = self._slots[pos]
            missing = slots < 0
            if n_need:
                self._slots[pos[missing]] = self._pop_free(n_need)
                slots = self._slots[pos]
            if counts is not None:
                self._freqs[pos] += counts
            return slots.astype(np.int32)

    def frequencies(self, ids) -> np.ndarray:
        flat = np.asarray(ids).reshape(-1).astype(np.int64, copy=False)
        if flat.size == 0:
            return np.zeros(np.shape(ids), np.int64)
        with self._lock:
            pos, found = self._positions(flat)
            out = np.zeros(flat.shape, np.int64)
            if found.any():
                out[found] = self._freqs[pos[found]]
        return out.reshape(np.shape(ids))

    def resident_slots(self, ids) -> np.ndarray:
        """Slots for ``ids`` as an int32 array, -1 where not device-
        resident (unknown OR demoted). The vectorized ``slots_of``."""
        flat = np.asarray(ids).reshape(-1).astype(np.int64, copy=False)
        out = np.full(flat.shape, -1, np.int32)
        if flat.size == 0:
            return out.reshape(np.shape(ids))
        with self._lock:
            pos, found = self._positions(flat)
            if found.any():
                out[found] = self._slots[pos[found]]
        return out.reshape(np.shape(ids))

    def resident_arrays(self):
        """(ids, slots, freqs) copies for every device-resident id."""
        with self._lock:
            mask = self._slots >= 0
            return (
                self._ids[mask].copy(),
                self._slots[mask].copy(),
                self._freqs[mask].copy(),
            )

    def evict_ids(self, raws, forget: bool = False) -> dict[int, int]:
        """Free specific ids' slots; returns {raw_id: freed_slot}.
        By default frequencies are kept (the id may live on in a host
        tier); ``forget=True`` drops the key rows entirely — the host
        tier's own map uses this so its key arrays stay bounded by
        occupancy instead of growing with every id ever spilled."""
        arr = np.unique(np.asarray(raws, dtype=np.int64).reshape(-1))
        if arr.size == 0:
            return {}
        with self._lock:
            pos, found = self._positions(arr)
            sp = pos[found]
            sp = sp[self._slots[sp] >= 0]
            if sp.size == 0:
                return {}
            freed_ids = self._ids[sp].copy()
            freed_slots = self._slots[sp].copy()
            self._push_free(freed_slots)
            if forget:
                keep = np.ones(self._ids.size, bool)
                keep[sp] = False
                self._ids = self._ids[keep]
                self._slots = self._slots[keep]
                self._freqs = self._freqs[keep]
            else:
                self._slots[sp] = -1
            return {
                int(i): int(s) for i, s in zip(freed_ids, freed_slots)
            }

    def coldest_residents(self, k: int, exclude=None):
        """The (ids, slots) of up to ``k`` coldest device-resident ids,
        skipping any id in ``exclude`` — the vectorized victim selection
        for tier demotion (stable argsort: ties break by ascending id).
        """
        with self._lock:
            mask = self._slots >= 0
            if exclude is not None:
                ex = np.asarray(exclude, np.int64).reshape(-1)
                if ex.size:
                    mask &= ~np.isin(self._ids, ex)
            cand = np.flatnonzero(mask)
            if cand.size == 0:
                return _EMPTY_I64, _EMPTY_I32
            if cand.size > 4096 and 0 < k < cand.size:
                # O(n) preselect at table scale, then order the k
                # survivors coldest-first (tie order differs from the
                # stable path, which only matters at toy sizes)
                part = np.argpartition(self._freqs[cand], k - 1)[:k]
                sub = cand[np.sort(part)]
                order = np.argsort(self._freqs[sub], kind="stable")
                pick = sub[order]
            else:
                order = np.argsort(self._freqs[cand], kind="stable")
                pick = cand[order[:k]]
            return self._ids[pick].copy(), self._slots[pick].copy()

    def resident_by_frequency(self) -> list[tuple[int, int]]:
        """Resident (raw_id, freq) pairs, coldest first."""
        with self._lock:
            mask = self._slots >= 0
            ids, fr = self._ids[mask], self._freqs[mask]
            order = np.argsort(fr, kind="stable")
        return [
            (int(i), int(f)) for i, f in zip(ids[order], fr[order])
        ]

    def free_slots(self) -> int:
        with self._lock:
            return int(self._n_free)

    def slots_of(self, raws) -> dict[int, int]:
        arr = np.asarray(list(raws), np.int64).reshape(-1)
        slots = self.resident_slots(arr)
        return {
            int(r): int(s) for r, s in zip(arr, slots) if s >= 0
        }

    def set_frequencies(self, ids, freqs):
        """Overwrite frequencies for ``ids`` (import semantics),
        inserting unknown ids as slotless tracked keys."""
        flat = np.asarray(ids, np.int64).reshape(-1)
        fr = np.asarray(freqs, np.int64).reshape(-1)
        if flat.size == 0:
            return
        with self._lock:
            pos, found = self._positions(flat)
            new = np.unique(flat[~found])
            if new.size:
                self._insert_keys(new)
                pos = np.searchsorted(self._ids, flat)
            self._freqs[pos] = fr

    def evict_under_threshold(self, threshold: int) -> list[int]:
        """Free the slots of ids seen fewer than ``threshold`` times
        (the reference's under-threshold export filtering / eviction).
        Returns the freed slot indices (caller may zero those rows)."""
        with self._lock:
            cold = self._freqs < threshold
            freed = self._slots[cold & (self._slots >= 0)].copy()
            keep = ~cold
            self._ids = self._ids[keep]
            self._slots = self._slots[keep]
            self._freqs = self._freqs[keep]
            self._push_free(freed)
        out = [int(s) for s in freed]
        if out:
            logger.info("evicted %d cold ids", len(out))
        return out

    def grow(self, new_capacity: int):
        """Raise capacity, appending the new slots to the free stack
        (used by the host tier, whose vocabulary is unbounded)."""
        with self._lock:
            add = int(new_capacity) - self.capacity
            if add <= 0:
                return
            free = np.empty(int(new_capacity), np.int32)
            free[:self._n_free] = self._free[:self._n_free]
            free[self._n_free:self._n_free + add] = np.arange(
                int(new_capacity) - 1, self.capacity - 1, -1,
                dtype=np.int32,
            )
            self._free = free
            self._n_free += add
            self.capacity = int(new_capacity)

    # ------------------------------------------------------- checkpoints

    def state_dict(self) -> dict:
        with self._lock:
            return {
                "capacity": self.capacity,
                "ids": self._ids.copy(),
                "slots": self._slots.copy(),
                "freqs": self._freqs.copy(),
            }

    def load_state_dict(self, state: dict):
        with self._lock:
            self.capacity = int(state["capacity"])
            if "ids" in state:
                ids = np.asarray(state["ids"], np.int64).reshape(-1)
                slots = np.asarray(state["slots"], np.int32).reshape(-1)
                freqs = np.asarray(state["freqs"], np.int64).reshape(-1)
                order = np.argsort(ids, kind="stable")
                self._ids = ids[order].copy()
                self._slots = slots[order].copy()
                self._freqs = freqs[order].copy()
            else:  # legacy dict-of-dicts layout (pre-array checkpoints)
                slot_of = {
                    int(k): int(v) for k, v in state["slot_of"].items()
                }
                freq = {int(k): int(v) for k, v in state["freq"].items()}
                ids = np.array(
                    sorted(set(slot_of) | set(freq)), np.int64
                )
                self._ids = ids
                self._slots = np.array(
                    [slot_of.get(int(i), -1) for i in ids], np.int32
                )
                self._freqs = np.array(
                    [freq.get(int(i), 0) for i in ids], np.int64
                )
            used = self._slots[self._slots >= 0]
            free_mask = np.ones(self.capacity, bool)
            free_mask[used] = False
            # descending so pops hand out ascending slot numbers
            self._free = np.flatnonzero(free_mask)[::-1].astype(
                np.int32
            ).copy()
            self._n_free = int(self._free.size)
            pad = np.empty(self.capacity - self._n_free, np.int32)
            self._free = np.concatenate([self._free, pad])


class KvEmbedding:
    """A dynamic embedding table: host mapper + device parameter rows.

    Typical flow::

        kv = KvEmbedding(dim=64, capacity=1 << 17)
        table = kv.init_table(jax.random.key(0))        # param leaf
        slots = kv.lookup_slots(raw_ids)                # host, pre-step
        vecs = KvEmbedding.embed(table, slots)          # inside jit
        # table is trained like any parameter (shard rows on 'fsdp')

    ``logical_axes`` for the table is ``("vocab", "embed")`` so
    auto_accelerate shards rows across the mesh.
    """

    logical_axes = ("vocab", "embed")

    def __init__(self, dim: int, capacity: int = 1 << 16,
                 init_scale: float = 0.01, dtype=None):
        self.dim = int(dim)
        self.capacity = int(capacity)
        self.init_scale = init_scale
        self.dtype = dtype
        self.mapper = IdMapper(capacity)

    def init_table(self, rng):
        import jax
        import jax.numpy as jnp

        dtype = self.dtype or jnp.float32
        return (
            jax.random.normal(rng, (self.capacity, self.dim), dtype)
            * self.init_scale
        )

    def lookup_slots(self, raw_ids) -> np.ndarray:
        return self.mapper.lookup(raw_ids)

    @staticmethod
    def embed(table, slots):
        """Device-side gather (use inside jit; differentiable)."""
        import jax.numpy as jnp

        return jnp.take(table, slots, axis=0)

    # ------------------------------------------------------- ckpt/export

    def export(self, table, min_frequency: int = 0):
        """Returns (ids, vectors, freqs), optionally dropping ids seen
        fewer than ``min_frequency`` times (KvVariableExport semantics).
        One gather over the resident rows — no per-id loop."""
        host_table = np.asarray(table)
        ids, slots, freqs = self.mapper.resident_arrays()
        if min_frequency:
            keep = freqs >= min_frequency
            ids, slots, freqs = ids[keep], slots[keep], freqs[keep]
        if ids.size == 0:
            return (
                _EMPTY_I64,
                np.zeros((0, self.dim), host_table.dtype),
                _EMPTY_I64,
            )
        return (
            ids.astype(np.int64),
            host_table[slots],
            freqs.astype(np.int64),
        )

    def import_(self, table, ids, vectors, freqs=None):
        """Load (id, vector, freq) triples; returns the updated table
        (KvVariableImport). Ids get fresh slots in THIS mapper."""
        import jax.numpy as jnp

        ids = np.asarray(ids, np.int64).reshape(-1)
        slots = self.mapper.lookup(ids, count=False)
        if freqs is not None:
            self.mapper.set_frequencies(ids, freqs)
        return jnp.asarray(table).at[slots].set(jnp.asarray(vectors))

    def evict(self, table, threshold: int):
        """Drop cold ids and zero their rows; returns the new table."""
        import jax.numpy as jnp

        freed = self.mapper.evict_under_threshold(threshold)
        if not freed:
            return table
        idx = np.asarray(freed, np.int32)
        return jnp.asarray(table).at[idx].set(0.0)


class TieredKvEmbedding(KvEmbedding):
    """KvEmbedding whose vocabulary may exceed the device table.

    Equivalent capability: TFPlus hybrid embedding storage
    (tfplus/tfplus/kv_variable/kernels/hybrid_embedding/table_manager.h
    — hot ids in device memory, cold ids spilled to a host tier, with
    frequency-driven placement).

    TPU redesign: the device table keeps its fixed [capacity, dim]
    shape (XLA-static); tiering happens on the host BETWEEN steps.
    ``prepare_batch`` guarantees every id of the incoming batch is
    device-resident before the step: when slots run short it demotes
    the least-frequently-used resident ids that are NOT in the batch —
    reading back only those rows from the device (one bucketed gather,
    not a full table download) into the host store — and promotes the
    batch's spilled rows with one bucketed scatter. Training then
    touches device rows only; demoted rows keep their learned values
    and frequencies, so a returning id resumes exactly where it left
    off.

    The host tier is a preallocated ``(host_capacity, dim)`` array with
    its own :class:`IdMapper` slot map (grown by doubling when the cold
    set outruns it) — a demotion is a row-block copy into the array, a
    promotion a row-block copy out, never a per-row dict operation.
    ``counters`` tracks prepare_batch traffic (``vectorized_batches``,
    ``demoted_rows``, ``promoted_rows``, ``fresh_rows``) so a test can
    assert the vectorized path actually ran.
    """

    def __init__(self, dim: int, capacity: int = 1 << 16,
                 init_scale: float = 0.01, dtype=None, seed: int = 0,
                 host_capacity: int | None = None):
        super().__init__(dim, capacity, init_scale, dtype)
        self._host_capacity = int(host_capacity or max(capacity, 1024))
        self._host_map = IdMapper(self._host_capacity)
        # spilled rows keep the table's dtype — a demote/promote round-
        # trip must be bit-identical, not a float32 downcast
        self._host_dtype = (
            np.float32 if dtype is None else np.dtype(dtype)
        )
        self._host_data = np.zeros(
            (self._host_capacity, self.dim), self._host_dtype
        )
        # host stores for caller-supplied aux arrays (slot-aligned
        # optimizer state riding the same demote/promote round-trip);
        # allocated lazily on the first prepare_batch(aux=...) call
        self._host_aux = None
        self._rng = np.random.RandomState(seed)
        self.counters = {
            "vectorized_batches": 0,
            "demoted_rows": 0,
            "promoted_rows": 0,
            "fresh_rows": 0,
        }

    @property
    def host_ids(self) -> int:
        return len(self._host_map)

    @staticmethod
    def _bucket(n: int) -> int:
        """Next power of two >= n: the demote-gather and promote-scatter
        run with BUCKETED shapes so jit compiles O(log capacity) kernel
        variants total instead of one per distinct row count per step
        (a varying-shape at[].set recompiles every prepare_batch —
        measured seconds/step of pure compilation)."""
        b = 1
        while b < n:
            b <<= 1
        return b

    # ------------------------------------------------------- host tier

    def _grow_host(self, min_new: int):
        new_cap = max(self._host_capacity * 2,
                      self._host_capacity + int(min_new))
        grown = np.zeros((new_cap, self.dim), self._host_data.dtype)
        grown[: self._host_capacity] = self._host_data
        self._host_data = grown
        if self._host_aux is not None:
            self._host_aux = [
                np.concatenate([
                    a,
                    np.zeros((new_cap - self._host_capacity,)
                             + a.shape[1:], a.dtype),
                ])
                for a in self._host_aux
            ]
        self._host_map.grow(new_cap)
        self._host_capacity = new_cap

    def _ensure_host_aux(self, aux):
        """Allocate (or validate) the host-side stores mirroring the
        caller's aux arrays — rows already spilled without aux keep
        zeros there, i.e. fresh optimizer state."""
        if self._host_aux is None:
            self._host_aux = [
                np.zeros((self._host_capacity,) + tuple(a.shape[1:]),
                         np.dtype(a.dtype))
                for a in aux
            ]
        elif len(self._host_aux) != len(aux):
            raise ValueError(
                f"prepare_batch aux count changed: "
                f"{len(self._host_aux)} stored vs {len(aux)} passed"
            )

    def _host_put(self, ids: np.ndarray, rows: np.ndarray,
                  aux_rows=None):
        """Store ``rows`` (and optional per-id aux rows) for ``ids`` in
        the host tier (block copies, never per-row)."""
        while True:
            try:
                hslots = self._host_map.lookup(ids, count=False)
                break
            except RuntimeError:  # host tier full: double and retry
                self._grow_host(ids.size)
        self._host_data[hslots] = rows
        if self._host_aux is not None:
            if aux_rows is None:
                # slots reused from promoted ids must not leak the
                # previous occupant's optimizer state
                for a in self._host_aux:
                    a[hslots] = 0
            else:
                for a, r in zip(self._host_aux, aux_rows):
                    a[hslots] = r

    def _host_take(self, ids: np.ndarray, n_aux: int = 0):
        """Rows for ``ids``: spilled rows leave the host tier (their
        slots free up), unseen ids get fresh random init (and zeroed
        aux = fresh optimizer state). Returns
        (rows, aux_rows_list, n_promoted_from_host)."""
        hs = self._host_map.resident_slots(ids)
        have = hs >= 0
        rows = np.empty((ids.size, self.dim), self._host_data.dtype)
        aux_rows = [
            np.zeros((ids.size,) + a.shape[1:], a.dtype)
            for a in (self._host_aux or [])[:n_aux]
        ]
        if have.any():
            rows[have] = self._host_data[hs[have]]
            for out, a in zip(aux_rows, self._host_aux or []):
                out[have] = a[hs[have]]
            self._host_map.evict_ids(ids[have], forget=True)
        n_fresh = int((~have).sum())
        if n_fresh:
            rows[~have] = (
                self._rng.randn(n_fresh, self.dim) * self.init_scale
            ).astype(rows.dtype)
        return rows, aux_rows, int(have.sum())

    # ------------------------------------------------------ hot path

    def prepare_batch(self, table, raw_ids, count: bool = True,
                      aux=None):
        """Make every id in ``raw_ids`` device-resident.

        Returns ``(table, slots)`` — ``table`` possibly updated by the
        demotion/promotion round-trip (ONE bucketed ``jnp.take`` + ONE
        bucketed ``at[].set`` per array), ``slots`` aligned with
        ``raw_ids`` (feed to :meth:`embed` inside jit). All id
        bookkeeping is batched numpy set-ops; nothing here loops over
        ids in Python. ``count=False`` serves the batch without
        recording frequency uses (eval traffic).

        ``aux``: optional sequence of ``[capacity, ...]`` device arrays
        row-aligned with the table — slot-aligned optimizer state
        (Adam moments, per-row accumulators). Their rows ride the same
        demote/promote round-trip, so a relocated id keeps its
        optimizer state, not the previous slot occupant's; fresh ids
        get zero aux rows. With aux the return is
        ``(table, slots, aux_list)``.
        """
        import jax.numpy as jnp

        if aux is not None:
            self._ensure_host_aux(aux)
            aux = list(aux)
        n_aux = len(aux) if aux is not None else 0
        flat = np.asarray(raw_ids).reshape(-1).astype(
            np.int64, copy=False
        )
        # ONE unique pass serves residency check, promotion, and the
        # final slot mapping (uniq is sorted; subsets stay sorted)
        uniq, inv, ucounts = np.unique(
            flat, return_inverse=True, return_counts=True
        )
        incoming = uniq[self.mapper.resident_slots(uniq) < 0]
        if incoming.size > self.capacity:
            raise RuntimeError(
                f"batch needs {incoming.size} new rows but the device "
                f"table holds {self.capacity}"
            )
        need = int(incoming.size) - self.mapper.free_slots()
        if need > 0:
            # demote the coldest residents that the batch doesn't use
            vic_ids, vic_slots = self.mapper.coldest_residents(
                need, exclude=uniq
            )
            if vic_ids.size < need:
                raise RuntimeError(
                    "cannot make room: batch uses the whole table"
                )
            # bucketed gather: pad with slot 0 of the batch, drop the
            # tail host-side
            b = self._bucket(vic_slots.size)
            bidx = np.empty(b, np.int32)
            bidx[: vic_slots.size] = vic_slots
            bidx[vic_slots.size:] = vic_slots[0]
            rows = np.asarray(
                jnp.take(jnp.asarray(table), bidx, axis=0)
            )[: vic_slots.size]
            aux_out = [
                np.asarray(
                    jnp.take(jnp.asarray(a), bidx, axis=0)
                )[: vic_slots.size]
                for a in (aux or [])
            ]
            self._host_put(vic_ids, rows, aux_out if aux else None)
            self.mapper.evict_ids(vic_ids)
            self.counters["demoted_rows"] += int(vic_ids.size)
        if incoming.size:
            # promote/insert the batch's non-resident ids
            slots_new = self.mapper.lookup_unique(incoming)
            rows, aux_rows, n_promoted = self._host_take(
                incoming, n_aux
            )
            n = int(incoming.size)
            b = self._bucket(n)
            # bucketed scatter: padding repeats entry 0 (same slot, same
            # row — duplicate writes of one value are deterministic)
            bslots = np.empty(b, np.int32)
            bslots[:n] = slots_new
            bslots[n:] = bslots[0]
            brows = np.empty((b, self.dim), rows.dtype)
            brows[:n] = rows
            brows[n:] = brows[0]
            tj = jnp.asarray(table)
            table = tj.at[bslots].set(jnp.asarray(brows, tj.dtype))
            for i in range(n_aux):
                ba = np.empty((b,) + aux_rows[i].shape[1:],
                              aux_rows[i].dtype)
                ba[:n] = aux_rows[i]
                ba[n:] = ba[0]
                aj = jnp.asarray(aux[i])
                aux[i] = aj.at[bslots].set(jnp.asarray(ba, aj.dtype))
            self.counters["promoted_rows"] += n_promoted
            self.counters["fresh_rows"] += n - n_promoted
        # count a use for every id in the batch and map to slots
        # (counts=None: eval traffic must not inflate the LFU stats
        # that drive demotion, eviction, and export filtering)
        uslots = self.mapper.lookup_unique(
            uniq, ucounts if count else None
        )
        slots = uslots[inv.reshape(-1)]
        self.counters["vectorized_batches"] += 1
        slots = slots.reshape(np.shape(raw_ids))
        if aux is None:
            return table, slots
        return table, slots, aux

    # ------------------------------------------------------- ckpt/export

    def export(self, table, min_frequency: int = 0):
        """(ids, vectors, freqs) across BOTH tiers."""
        ids_d, rows_d, freqs_d = super().export(table, min_frequency)
        h_ids, h_slots, _ = self._host_map.resident_arrays()
        if h_ids.size == 0:
            return ids_d, rows_d, freqs_d
        h_rows = self._host_data[h_slots]
        h_freqs = self.mapper.frequencies(h_ids).astype(np.int64)
        if min_frequency:
            keep = h_freqs >= min_frequency
            h_ids, h_rows, h_freqs = (
                h_ids[keep], h_rows[keep], h_freqs[keep]
            )
        if h_ids.size == 0:
            return ids_d, rows_d, freqs_d
        return (
            np.concatenate([ids_d, h_ids.astype(np.int64)]),
            np.concatenate([np.asarray(rows_d), h_rows]),
            np.concatenate([freqs_d, h_freqs]),
        )

    def import_(self, table, ids, vectors, freqs=None):
        """Load triples: fills the device table until full, spills the
        rest to the host tier (one block copy)."""
        ids = np.asarray(ids, np.int64).reshape(-1)
        vectors = np.asarray(vectors)
        freqs_a = (
            None if freqs is None
            else np.asarray(freqs, np.int64).reshape(-1)
        )
        n_dev = min(int(ids.size), self.mapper.free_slots())
        if n_dev:
            table = super().import_(
                table, ids[:n_dev], vectors[:n_dev],
                None if freqs_a is None else freqs_a[:n_dev],
            )
        if n_dev < ids.size:
            spill = ids[n_dev:]
            self._host_put(spill, vectors[n_dev:])
            if freqs_a is not None:
                self.mapper.set_frequencies(spill, freqs_a[n_dev:])
        return table

    def evict(self, table, threshold: int):
        """Drop cold ids from BOTH tiers (host rows freed too)."""
        h_ids, _, _ = self._host_map.resident_arrays()
        if h_ids.size:
            cold = h_ids[
                self.mapper.frequencies(h_ids) < threshold
            ]
            if cold.size:
                self._host_map.evict_ids(cold, forget=True)
        return super().evict(table, threshold)

    def state_dict(self) -> dict:
        h_ids, h_slots, _ = self._host_map.resident_arrays()
        state = {
            "mapper": self.mapper.state_dict(),
            "host_ids": h_ids.astype(np.int64),
            "host_rows": self._host_data[h_slots].copy(),
        }
        if self._host_aux is not None:
            state["host_aux"] = [a[h_slots].copy()
                                 for a in self._host_aux]
        return state

    def load_state_dict(self, state: dict):
        self.mapper.load_state_dict(state["mapper"])
        if "host_store" in state:  # legacy dict-of-rows layout
            items = sorted(
                (int(k), np.asarray(v))
                for k, v in state["host_store"].items()
            )
            h_ids = np.array([k for k, _ in items], np.int64)
            h_rows = (
                np.stack([v for _, v in items])
                if items else np.zeros((0, self.dim), self._host_dtype)
            )
        else:
            h_ids = np.asarray(state["host_ids"], np.int64).reshape(-1)
            h_rows = np.asarray(state["host_rows"])
        self._host_capacity = max(
            int(self._host_capacity), int(h_ids.size), 1024
        )
        self._host_map = IdMapper(self._host_capacity)
        self._host_data = np.zeros(
            (self._host_capacity, self.dim), self._host_dtype
        )
        saved_aux = state.get("host_aux")
        if saved_aux is not None:
            self._host_aux = [
                np.zeros((self._host_capacity,) + tuple(a.shape[1:]),
                         a.dtype)
                for a in saved_aux
            ]
        else:
            self._host_aux = None
        if h_ids.size:
            self._host_put(h_ids, h_rows, saved_aux)
