"""Tests for KvEmbedding (dynamic sparse embedding) and group sparse
optimizers — reference coverage analogue: tfplus py_ut kv_variable and
group optimizer tests.
"""

import jax
import jax.numpy as jnp
import numpy as np
import optax
import pytest

from dlrover_tpu.ops.sparse_embedding import IdMapper, KvEmbedding
from dlrover_tpu.optimizers import group_adagrad, group_adam


class TestIdMapper:
    def test_insert_on_lookup(self):
        m = IdMapper(8)
        slots = m.lookup(np.array([100, 200, 100]))
        assert slots[0] == slots[2] != slots[1]
        assert len(m) == 2

    def test_frequencies(self):
        m = IdMapper(8)
        m.lookup(np.array([5, 5, 7]))
        assert m.frequencies(np.array([5, 7, 9])).tolist() == [2, 1, 0]

    def test_capacity_exhaustion(self):
        m = IdMapper(2)
        m.lookup(np.array([1, 2]))
        with pytest.raises(RuntimeError, match="capacity"):
            m.lookup(np.array([3]))

    def test_eviction_recycles_slots(self):
        m = IdMapper(2)
        m.lookup(np.array([1, 1, 2]))  # freq: 1->2, 2->1
        freed = m.evict_under_threshold(2)
        assert len(freed) == 1
        # slot is reusable now
        m.lookup(np.array([3]))
        assert len(m) == 2

    def test_state_roundtrip(self):
        m = IdMapper(8)
        m.lookup(np.array([10, 20, 10]))
        state = m.state_dict()
        m2 = IdMapper(8)
        m2.load_state_dict(state)
        assert np.array_equal(
            m2.lookup(np.array([10, 20]), count=False),
            m.lookup(np.array([10, 20]), count=False),
        )
        assert m2.frequencies(np.array([10]))[0] == 2


class TestKvEmbedding:
    def test_lookup_and_embed(self):
        kv = KvEmbedding(dim=4, capacity=16)
        table = kv.init_table(jax.random.key(0))
        slots = kv.lookup_slots(np.array([[111, 222], [111, 333]]))
        vecs = KvEmbedding.embed(table, slots)
        assert vecs.shape == (2, 2, 4)
        np.testing.assert_array_equal(
            np.asarray(vecs[0, 0]), np.asarray(vecs[1, 0])
        )

    def test_gradient_flows_to_touched_rows_only(self):
        kv = KvEmbedding(dim=4, capacity=16)
        table = kv.init_table(jax.random.key(0))
        slots = kv.lookup_slots(np.array([42, 43]))

        def loss(tbl):
            return jnp.sum(KvEmbedding.embed(tbl, slots) ** 2)

        g = jax.grad(loss)(table)
        touched = np.unique(slots)
        mask = np.zeros(16, bool)
        mask[touched] = True
        g_np = np.asarray(g)
        assert np.all(g_np[~mask] == 0)
        assert np.all(np.any(g_np[mask] != 0, axis=1))

    def test_export_import_roundtrip(self):
        kv = KvEmbedding(dim=4, capacity=16)
        table = kv.init_table(jax.random.key(0))
        slots = kv.lookup_slots(np.array([7, 8, 7]))
        ids, vecs, freqs = kv.export(table)
        assert set(ids.tolist()) == {7, 8}
        assert vecs.shape == (2, 4)

        kv2 = KvEmbedding(dim=4, capacity=16)
        table2 = kv2.init_table(jax.random.key(1))
        table2 = kv2.import_(table2, ids, vecs, freqs)
        # imported frequencies are preserved as-is
        assert kv2.mapper.frequencies(np.array([7]))[0] == 2
        slots2 = kv2.mapper.lookup(np.array([7, 8]), count=False)
        got = np.asarray(KvEmbedding.embed(table2, slots2))
        want_7 = vecs[list(ids).index(7)]
        np.testing.assert_allclose(got[0], want_7, rtol=1e-6)
        del slots

    def test_export_min_frequency_filters(self):
        kv = KvEmbedding(dim=2, capacity=8)
        table = kv.init_table(jax.random.key(0))
        kv.lookup_slots(np.array([1, 1, 1, 2]))
        ids, _, _ = kv.export(table, min_frequency=2)
        assert ids.tolist() == [1]

    def test_evict_zeroes_rows(self):
        kv = KvEmbedding(dim=2, capacity=8)
        table = kv.init_table(jax.random.key(0))
        slots = kv.lookup_slots(np.array([1, 1, 2]))
        cold_slot = int(slots[2])
        table = kv.evict(table, threshold=2)
        assert np.all(np.asarray(table)[cold_slot] == 0)
        assert len(kv.mapper) == 1


class TestGroupAdam:
    def _sparse_grad(self, rows=8, dim=4, touched=(1, 3)):
        g = np.zeros((rows, dim), np.float32)
        for r in touched:
            g[r] = 1.0
        return jnp.asarray(g)

    def test_untouched_rows_have_zero_update_and_frozen_state(self):
        params = {"t": jnp.ones((8, 4))}
        opt = group_adam(1e-1)
        state = opt.init(params)
        g = {"t": self._sparse_grad()}
        updates, state = opt.update(g, state, params)
        u = np.asarray(updates["t"])
        assert np.all(u[[0, 2, 4, 5, 6, 7]] == 0)
        assert np.any(u[1] != 0) and np.any(u[3] != 0)
        inner = state[0]
        assert np.asarray(inner.steps["t"]).reshape(-1)[1] == 1
        assert np.asarray(inner.steps["t"]).reshape(-1)[0] == 0

    def test_rare_rows_get_fresh_bias_correction(self):
        """A row touched for the first time at step 100 must get the same
        update magnitude as a row touched at step 1 (per-row counts)."""
        params = {"t": jnp.zeros((2, 4))}
        opt = group_adam(1.0)
        state = opt.init(params)
        # touch row 0 a hundred times
        for _ in range(100):
            g = {"t": jnp.asarray(
                np.array([[1, 1, 1, 1], [0, 0, 0, 0]], np.float32)
            )}
            updates, state = opt.update(g, state, params)
        first_row0 = None
        # now touch row 1 for the first time
        g = {"t": jnp.asarray(
            np.array([[0, 0, 0, 0], [1, 1, 1, 1]], np.float32)
        )}
        updates, state = opt.update(g, state, params)
        u = np.asarray(updates["t"])
        # fresh row's first update ~ -lr * 1.0 (full bias correction)
        np.testing.assert_allclose(u[1], -1.0, rtol=1e-4)
        del first_row0

    def test_trains_embedding_end_to_end(self):
        kv = KvEmbedding(dim=4, capacity=32)
        params = {"table": kv.init_table(jax.random.key(0))}
        opt = group_adam(5e-2)
        state = opt.init(params)
        target = jnp.ones((4,))
        slots = kv.lookup_slots(np.array([9, 9, 12]))

        @jax.jit
        def step(params, state):
            def loss(p):
                vec = KvEmbedding.embed(p["table"], slots)
                return jnp.mean((vec - target) ** 2)

            l, g = jax.value_and_grad(loss)(params)
            updates, state2 = opt.update(g, state, params)
            return optax.apply_updates(params, updates), state2, l

        for _ in range(200):
            params, state, l = step(params, state)
        assert float(l) < 1e-3


class TestGroupAdagrad:
    def test_masked_accumulation(self):
        params = {"t": jnp.ones((4, 2))}
        opt = group_adagrad(1e-1)
        state = opt.init(params)
        g = np.zeros((4, 2), np.float32)
        g[2] = 3.0
        updates, state = opt.update({"t": jnp.asarray(g)}, state, params)
        u = np.asarray(updates["t"])
        assert np.all(u[[0, 1, 3]] == 0)
        assert np.all(u[2] != 0)


class TestTieredKvEmbedding:
    """Host-tier spill for vocabularies larger than the device table
    (reference hybrid_embedding/table_manager.h capability)."""

    def _kv(self, capacity=8, dim=4):
        from dlrover_tpu.ops.sparse_embedding import TieredKvEmbedding

        return TieredKvEmbedding(dim=dim, capacity=capacity, seed=1)

    def test_values_survive_demote_promote(self):
        kv = self._kv(capacity=4, dim=3)
        table = kv.init_table(jax.random.key(0))
        # write known vectors for ids 0..3 (fills the table)
        table = kv.import_(
            table, np.arange(4), np.arange(12).reshape(4, 3) * 1.0
        )
        # a batch of fresh ids forces demotion of the coldest residents
        table, _ = kv.prepare_batch(table, np.asarray([100, 101, 102]))
        assert kv.host_ids >= 3
        # ask for an originally-written id again: promoted with its row
        table, slots = kv.prepare_batch(table, np.asarray([2]))
        row = np.asarray(KvEmbedding.embed(table, slots))[0]
        np.testing.assert_allclose(row, [6.0, 7.0, 8.0])

    def test_trains_vocab_larger_than_table(self):
        """24 ids through an 8-row device table: every id's embedding
        converges to its target despite constant spill/promote."""
        kv = self._kv(capacity=8, dim=4)
        table = kv.init_table(jax.random.key(0))
        vocab = 24
        rng = np.random.RandomState(0)
        targets = rng.randn(vocab, 4).astype(np.float32)

        @jax.jit
        def step(table, slots, tgt):
            def loss(tb):
                e = KvEmbedding.embed(tb, slots)
                return jnp.mean((e - tgt) ** 2)

            g = jax.grad(loss)(table)
            return table - 3.0 * g

        for epoch in range(60):
            order = rng.permutation(vocab)
            for start in range(0, vocab, 6):
                ids = order[start:start + 6]
                table, slots = kv.prepare_batch(table, ids)
                table = step(table, slots, jnp.asarray(targets[ids]))

        # verify EVERY id (promoting in groups that fit the table)
        errs = []
        for start in range(0, vocab, 8):
            ids = np.arange(start, min(start + 8, vocab))
            table, slots = kv.prepare_batch(table, ids)
            got = np.asarray(KvEmbedding.embed(table, slots))
            errs.append(np.abs(got - targets[ids]).max())
        assert max(errs) < 0.05, errs

    def test_export_covers_both_tiers(self):
        kv = self._kv(capacity=4, dim=2)
        table = kv.init_table(jax.random.key(0))
        table = kv.import_(
            table, np.arange(10), np.arange(20).reshape(10, 2) * 1.0
        )
        assert kv.host_ids == 6  # overflow spilled
        ids, rows, _ = kv.export(table)
        assert sorted(ids.tolist()) == list(range(10))
        by_id = {int(i): r for i, r in zip(ids, rows)}
        np.testing.assert_allclose(by_id[9], [18.0, 19.0])

    def test_state_roundtrip(self):
        from dlrover_tpu.ops.sparse_embedding import TieredKvEmbedding

        kv = self._kv(capacity=4, dim=2)
        table = kv.init_table(jax.random.key(0))
        table = kv.import_(
            table, np.arange(6), np.ones((6, 2)), freqs=np.arange(6)
        )
        state = kv.state_dict()
        kv2 = TieredKvEmbedding(dim=2, capacity=4)
        kv2.load_state_dict(state)
        assert kv2.host_ids == kv.host_ids
        np.testing.assert_array_equal(
            kv2.mapper.frequencies(np.arange(6)),
            kv.mapper.frequencies(np.arange(6)),
        )


class TestTieredSpillPath:
    """The host-spill tier under the array-backed layout: overflow
    workloads, bit-exact demote/promote, checkpoint and export
    round-trips with spilled rows."""

    def _overflowed(self, capacity=4, dim=2, vocab=10):
        from dlrover_tpu.ops.sparse_embedding import TieredKvEmbedding

        kv = TieredKvEmbedding(dim=dim, capacity=capacity, seed=1)
        table = kv.init_table(jax.random.key(0))
        rs = np.random.RandomState(7)
        vals = rs.randn(vocab, dim).astype(np.float32)
        freqs = np.arange(vocab, dtype=np.int64) + 1
        table = kv.import_(table, np.arange(vocab), vals, freqs=freqs)
        return kv, table, vals, freqs

    def test_overcapacity_zipf_drives_host_tier(self):
        from dlrover_tpu.ops.sparse_embedding import TieredKvEmbedding

        kv = TieredKvEmbedding(dim=8, capacity=32, seed=0)
        table = kv.init_table(jax.random.key(0))
        rs = np.random.RandomState(0)
        vocab = np.arange(512, dtype=np.int64) * 131 + 5
        for _ in range(12):
            ranks = np.minimum(rs.zipf(1.3, size=24), 512) - 1
            table, slots = kv.prepare_batch(table, vocab[ranks])
            assert np.all(np.asarray(slots) >= 0)
        assert kv.host_ids > 0
        assert kv.counters["demoted_rows"] > 0
        assert kv.counters["vectorized_batches"] == 12

    def test_demote_promote_bit_identical(self):
        kv, table, vals, _ = self._overflowed(capacity=4, vocab=4)
        ids0, vecs0, _ = kv.export(table)
        before = {int(i): v for i, v in zip(ids0, vecs0)}
        # fresh batch demotes ALL residents; then promote them back
        table, _ = kv.prepare_batch(table, np.array([900, 901, 902, 903]))
        assert kv.host_ids == 4
        table, slots = kv.prepare_batch(table, np.arange(4))
        got = np.asarray(KvEmbedding.embed(table, slots))
        for i in range(4):
            np.testing.assert_array_equal(got[i], before[i])

    def test_state_dict_roundtrip_with_spilled_rows(self):
        from dlrover_tpu.ops.sparse_embedding import TieredKvEmbedding

        kv, table, vals, freqs = self._overflowed(vocab=10)
        assert kv.host_ids == 6
        kv2 = TieredKvEmbedding(dim=2, capacity=4)
        kv2.load_state_dict(kv.state_dict())
        assert kv2.host_ids == kv.host_ids
        np.testing.assert_array_equal(
            kv2.mapper.frequencies(np.arange(10)),
            kv.mapper.frequencies(np.arange(10)),
        )
        # a spilled id promotes out of the RESTORED mapper bit-exactly
        table2, slots = kv2.prepare_batch(table, np.array([9]))
        got = np.asarray(KvEmbedding.embed(table2, slots))[0]
        np.testing.assert_array_equal(got, vals[9])

    def test_export_import_roundtrip_with_spilled_rows(self):
        from dlrover_tpu.ops.sparse_embedding import TieredKvEmbedding

        kv, table, vals, freqs = self._overflowed(vocab=10)
        ids, vecs, fr = kv.export(table)
        assert sorted(ids.tolist()) == list(range(10))
        kv2 = TieredKvEmbedding(dim=2, capacity=4, seed=2)
        table2 = kv2.init_table(jax.random.key(1))
        table2 = kv2.import_(table2, ids, vecs, fr)
        assert kv2.host_ids == 6
        ids2, vecs2, fr2 = kv2.export(table2)
        want = {int(i): (v, int(f)) for i, v, f in zip(ids, vecs, fr)}
        assert sorted(ids2.tolist()) == sorted(ids.tolist())
        for i, v, f in zip(ids2, vecs2, fr2):
            np.testing.assert_array_equal(v, want[int(i)][0])
            assert int(f) == want[int(i)][1]

    def test_spill_preserves_table_dtype(self):
        """The host tier stores rows at the TABLE's dtype — a bfloat16
        row must round-trip demote -> promote bit-identically, not
        through a float32 cast."""
        from dlrover_tpu.ops.sparse_embedding import TieredKvEmbedding

        kv = TieredKvEmbedding(dim=4, capacity=4, seed=0,
                               dtype=jnp.bfloat16)
        table = kv.init_table(jax.random.key(0))
        assert kv._host_data.dtype == jnp.bfloat16
        before = np.asarray(table).copy()
        slots0 = kv.mapper.lookup(np.arange(4))
        del slots0  # residents 0..3 at slots 0..3
        table, _ = kv.prepare_batch(table, np.array([10, 11, 12, 13]))
        table, slots = kv.prepare_batch(table, np.arange(4))
        got = np.asarray(KvEmbedding.embed(table, slots))
        assert got.dtype == before.dtype
        np.testing.assert_array_equal(got, before[:4])

    def test_aux_rows_follow_demote_promote(self):
        """Slot-aligned optimizer state (Adam moments) must relocate
        WITH the embedding rows: a promoted id gets its own spilled
        moments back, never the previous slot occupant's; fresh ids
        get zeros."""
        from dlrover_tpu.ops.sparse_embedding import TieredKvEmbedding

        kv = TieredKvEmbedding(dim=2, capacity=4, seed=0)
        table = kv.init_table(jax.random.key(0))
        mu = jnp.arange(8, dtype=jnp.float32).reshape(4, 2) * 10
        kv.mapper.lookup(np.arange(4))  # ids 0..3 -> slots 0..3
        mu_before = {i: np.asarray(mu)[i].copy() for i in range(4)}
        # demote all of 0..3, then bring them back (different slots)
        table, _, (mu,) = kv.prepare_batch(
            table, np.array([10, 11, 12, 13]), aux=[mu]
        )
        table, slots, (mu,) = kv.prepare_batch(
            table, np.arange(4), aux=[mu]
        )
        got = np.asarray(mu)[np.asarray(slots)]
        for i in range(4):
            np.testing.assert_array_equal(got[i], mu_before[i])
        # fresh ids arrive with zero moments
        table, slots2, (mu,) = kv.prepare_batch(
            table, np.array([20, 21]), aux=[mu]
        )
        np.testing.assert_array_equal(
            np.asarray(mu)[np.asarray(slots2)], 0.0
        )
        # state_dict round-trips the spilled aux rows
        kv2 = TieredKvEmbedding(dim=2, capacity=4)
        kv2.load_state_dict(kv.state_dict())
        assert kv2._host_aux is not None
        table2, slots3, (mu2,) = kv2.prepare_batch(
            table, np.array([10]), aux=[mu]
        )
        del table2, slots3, mu2  # promote path exercised post-restore

    def test_preparer_relocates_optimizer_moments(self):
        """TieredBatchPreparer finds [capacity, dim] opt_state leaves
        under the table key and routes them through prepare_batch."""
        import dataclasses as dc

        from dlrover_tpu.models import (
            RecsysConfig,
            TieredBatchPreparer,
            make_tiered_embedding,
        )

        @dc.dataclass
        class FakeState:
            step: int
            params: dict
            opt_state: tuple

        cfg = RecsysConfig(dim=2, device_capacity=4, fields=1)
        kv = make_tiered_embedding(cfg)
        table = kv.init_table(jax.random.key(0))
        mu = jnp.arange(8, dtype=jnp.float32).reshape(4, 2)
        nu = mu * 100
        # w1 shares dim sizes elsewhere; only table-keyed leaves with a
        # capacity leading dim may relocate
        state = FakeState(
            step=0,
            params={"table": table, "w1": jnp.zeros((2, 3))},
            opt_state=({"mu": {"table": mu, "w1": jnp.ones((2, 3))},
                        "nu": {"table": nu}},),
        )
        prep = TieredBatchPreparer(kv)
        kv.mapper.lookup(np.arange(4))  # fill slots 0..3
        mu0 = np.asarray(mu).copy()
        nu0 = np.asarray(nu).copy()
        # batch of new ids: all residents demoted, then one returns
        state, b1 = prep(
            state, {"ids": np.array([[10], [11], [12], [13]])}
        )
        state, b2 = prep(state, {"ids": np.array([[2]])})
        del b1
        slot = int(np.asarray(b2["slots"]).reshape(-1)[0])
        new_mu = np.asarray(state.opt_state[0]["mu"]["table"])
        new_nu = np.asarray(state.opt_state[0]["nu"]["table"])
        np.testing.assert_array_equal(new_mu[slot], mu0[2])
        np.testing.assert_array_equal(new_nu[slot], nu0[2])
        # non-table leaf untouched
        np.testing.assert_array_equal(
            np.asarray(state.opt_state[0]["mu"]["w1"]), 1.0
        )

    def test_legacy_dict_state_loads(self):
        """Checkpoints written by the dict-backed layout keep loading."""
        from dlrover_tpu.ops.sparse_embedding import TieredKvEmbedding

        legacy = {
            "mapper": {
                "capacity": 4,
                "slot_of": {0: 0, 1: 1},
                "freq": {0: 5, 1: 1, 7: 2},
            },
            "host_store": {7: np.array([1.5, 2.5], np.float32)},
        }
        kv = TieredKvEmbedding(dim=2, capacity=4)
        kv.load_state_dict(legacy)
        assert kv.host_ids == 1
        assert kv.mapper.frequencies(np.array([0, 1, 7])).tolist() == \
            [5, 1, 2]
        table = jnp.zeros((4, 2))
        table, slots = kv.prepare_batch(table, np.array([7]))
        got = np.asarray(KvEmbedding.embed(table, slots))[0]
        np.testing.assert_array_equal(got, [1.5, 2.5])


class TestTieredPerfSmoke:
    """Tier-1 guard against the per-id-Python regression: an 8192-id
    over-capacity prepare_batch must stay vectorized (counter) and fast
    (wall bound ~10x above the vectorized path, ~10x below what per-id
    loops cost at this size)."""

    def test_prepare_batch_8192_ids_vectorized_and_fast(self):
        import time

        from dlrover_tpu.ops.sparse_embedding import TieredKvEmbedding

        cap, dim, batch = 2048, 16, 8192
        kv = TieredKvEmbedding(dim=dim, capacity=cap, seed=0)
        table = kv.init_table(jax.random.key(0))
        rs = np.random.RandomState(0)
        vocab = rs.randint(0, 1 << 40, size=4 * cap)
        table = kv.import_(
            table, vocab,
            (rs.randn(vocab.size, dim) * 0.01).astype(np.float32),
        )
        assert kv.host_ids > 0  # over-capacity: spill tier is live

        def zipf_ids():
            ranks = np.minimum(rs.zipf(1.3, size=batch), vocab.size) - 1
            return vocab[ranks]

        # warmup compiles the bucketed gather/scatter variants
        table, _ = kv.prepare_batch(table, zipf_ids())
        c0 = dict(kv.counters)
        t0 = time.perf_counter()
        for _ in range(3):
            table, slots = kv.prepare_batch(table, zipf_ids())
        jax.block_until_ready(table)
        wall = time.perf_counter() - t0
        assert kv.counters["vectorized_batches"] - \
            c0["vectorized_batches"] == 3
        assert kv.counters["demoted_rows"] > c0["demoted_rows"]
        # 3 vectorized calls run in ~0.1 s on CPU; the old per-id path
        # took seconds at this size
        assert wall < 1.5, f"prepare_batch too slow: {wall:.2f}s"


class TestTieredTrainerIntegration:
    """The elastic trainer drives a tiered table through the models/
    recsys path: raw-id batches in, device-resident slots into the
    jitted step, spill traffic on the host between steps."""

    def test_trainer_prestep_drives_tiered_table(self, tmp_path):
        from dlrover_tpu.models import (
            RecsysConfig,
            TieredBatchPreparer,
            make_tiered_embedding,
            recsys_init,
            recsys_logical_axes,
            recsys_loss_fn,
        )
        from dlrover_tpu.trainer.trainer import Trainer, TrainingArgs

        cfg = RecsysConfig(dim=8, device_capacity=64, fields=4,
                           hidden=16)
        kv = make_tiered_embedding(cfg)
        rs = np.random.RandomState(0)
        batches = [
            {
                "ids": rs.randint(0, 512, size=(16, 4)).astype(np.int64),
                "labels": rs.randint(0, 2, size=16).astype(np.float32),
            }
            for _ in range(8)
        ]
        args = TrainingArgs(
            output_dir=str(tmp_path / "out"),
            max_steps=8,
            log_steps=0,
            flash_checkpoint=False,
        )
        trainer = Trainer(
            recsys_loss_fn(cfg),
            lambda rng: recsys_init(cfg, rng, kv),
            recsys_logical_axes(cfg),
            args,
            batches,
            prestep=TieredBatchPreparer(kv),
        )
        state, metrics = trainer.train()
        assert np.isfinite(float(metrics["loss"]))
        assert kv.counters["vectorized_batches"] >= 8
        assert kv.host_ids > 0  # 512-id vocab through a 64-row table

    def test_host_map_keys_stay_bounded(self):
        """Promotion forgets the host-map key (forget=True eviction):
        the spill map's arrays track occupancy, not every id ever
        demoted — an unbounded vocabulary must not grow them forever."""
        from dlrover_tpu.ops.sparse_embedding import TieredKvEmbedding

        kv = TieredKvEmbedding(dim=4, capacity=8, seed=0)
        table = kv.init_table(jax.random.key(0))
        rs = np.random.RandomState(0)
        for step in range(30):
            ids = rs.choice(64, size=6, replace=False).astype(np.int64)
            table, _ = kv.prepare_batch(table, ids)
            # every key the host map holds is an actually-resident row
            assert kv._host_map._ids.size == kv.host_ids
        assert kv.counters["promoted_rows"] > 0

    def test_eval_prestep_translates_raw_ids(self, tmp_path):
        """evaluate() must run the same raw-id -> slot preparation as
        the train loop; raw-id eval batches crashed the jitted eval
        step before prestep was applied there."""
        from dlrover_tpu.models import (
            RecsysConfig,
            TieredBatchPreparer,
            make_tiered_embedding,
            recsys_init,
            recsys_logical_axes,
            recsys_loss_fn,
        )
        from dlrover_tpu.trainer.trainer import Trainer, TrainingArgs

        cfg = RecsysConfig(dim=8, device_capacity=64, fields=4,
                           hidden=16)
        kv = make_tiered_embedding(cfg)
        rs = np.random.RandomState(0)

        def batch():
            return {
                "ids": rs.randint(0, 512, size=(16, 4)).astype(np.int64),
                "labels": rs.randint(0, 2, size=16).astype(np.float32),
            }

        args = TrainingArgs(
            output_dir=str(tmp_path / "out"), max_steps=4, log_steps=0,
            eval_steps=2, flash_checkpoint=False,
        )
        trainer = Trainer(
            recsys_loss_fn(cfg),
            lambda rng: recsys_init(cfg, rng, kv),
            recsys_logical_axes(cfg),
            args,
            [batch() for _ in range(4)],
            eval_data=[batch() for _ in range(2)],
            prestep=TieredBatchPreparer(kv),
        )
        state, metrics = trainer.train()
        assert np.isfinite(float(metrics["loss"]))
        probe = np.arange(512)
        freqs_before = kv.mapper.frequencies(probe).copy()
        loss = trainer.evaluate()
        assert np.isfinite(loss)
        # eval traffic must not skew the LFU stats driving demotion
        np.testing.assert_array_equal(
            kv.mapper.frequencies(probe), freqs_before
        )

    def test_restart_restores_tier_state(self, tmp_path,
                                         isolated_ckpt_env):
        """An elastic restart must restore the id -> slot mapper and
        host rows alongside the table leaf (prestep sidecar): with an
        empty mapper the restored table's rows would be silently
        reassigned and overwritten with fresh inits."""
        from dlrover_tpu.agent.ckpt_saver import AsyncCheckpointSaver
        from dlrover_tpu.models import (
            RecsysConfig,
            TieredBatchPreparer,
            make_tiered_embedding,
            recsys_init,
            recsys_logical_axes,
            recsys_loss_fn,
        )
        from dlrover_tpu.trainer.trainer import Trainer, TrainingArgs

        cfg = RecsysConfig(dim=8, device_capacity=64, fields=4,
                           hidden=16)
        rs = np.random.RandomState(0)
        batches = [
            {
                "ids": rs.randint(0, 512, size=(16, 4)).astype(np.int64),
                "labels": rs.randint(0, 2, size=16).astype(np.float32),
            }
            for _ in range(6)
        ]

        def make_trainer(kv):
            args = TrainingArgs(
                output_dir=str(tmp_path / "out"), max_steps=6,
                log_steps=0, flash_checkpoint=True,
            )
            return Trainer(
                recsys_loss_fn(cfg),
                lambda rng: recsys_init(cfg, rng, kv),
                recsys_logical_axes(cfg),
                args, batches,
                prestep=TieredBatchPreparer(kv),
            )

        kv1 = make_tiered_embedding(cfg)
        t1 = make_trainer(kv1)
        state1, _ = t1.train()
        ids1, vecs1, fr1 = kv1.export(np.asarray(state1.params["table"]))
        assert kv1.host_ids > 0
        t1.close()
        AsyncCheckpointSaver.reset()

        kv2 = make_tiered_embedding(cfg)
        t2 = make_trainer(kv2)
        assert t2.maybe_resume() == 6
        assert kv2.host_ids == kv1.host_ids
        ids2, vecs2, fr2 = kv2.export(
            np.asarray(t2.state.params["table"])
        )
        w1 = {int(i): (v, int(f)) for i, v, f in zip(ids1, vecs1, fr1)}
        assert sorted(ids2.tolist()) == sorted(ids1.tolist())
        for i, v, f in zip(ids2, vecs2, fr2):
            np.testing.assert_array_equal(v, w1[int(i)][0])
            assert int(f) == w1[int(i)][1]
        t2.close()

        # a sidecar from a DIFFERENT step than the restored checkpoint
        # must refuse to load (mismatched mapper silently corrupts the
        # table) instead of pairing stale placement state
        import os

        AsyncCheckpointSaver.reset()
        side = os.path.join(str(tmp_path / "out"), "prestep_state.npy")
        payload = np.load(side, allow_pickle=True).item()
        payload["step"] = 99
        with open(side, "wb") as f:
            np.save(f, np.array(payload, dtype=object),
                    allow_pickle=True)
        persist_side = os.path.join(
            str(tmp_path / "out"), "prestep_state_persist.npy"
        )
        os.remove(persist_side)
        kv3 = make_tiered_embedding(cfg)
        t3 = make_trainer(kv3)
        with pytest.raises(ValueError, match="prestep sidecar"):
            t3.maybe_resume()
        t3.close()
