"""Subprocess body for test_elasticity's online scale-out check.

DESIGN.md §4.3 end-to-end: the five-transaction TPC-C mix runs on a
4-way 'mem' mesh with the commit journal replicated across the memory
servers and a checkpoint taken after every GC sweep.  Mid-run a
``MeshGrowth`` doubles the mesh to 8 memory servers — the scale-out is a
planned §6.2 failover: the last checkpoint is restored, the journal is
replayed over the migration window onto it, the moved record ranges and
timestamp-vector slots take the replayed reconstruction, the §5.2
directory / journal replicas / §5.3 snapshot logs are repartitioned over
the grown mesh, the executors are rebuilt and the workload resumes.

The expanded run must be bit-identical to a run launched at 8 shards
from the same seeds — installed versions (current + old + overflow), the
timestamp vector, per-type commit/abort/retry counts, GC telemetry and
op profiles — in BOTH pool layouts (table_major and the §7.3
warehouse_major).  Growing the mesh is a placement change, not a
semantics change.

The config deliberately uses 12 execution threads: 12 divides over the
4-shard mesh but NOT over the 8-shard one, so the expansion crosses a
non-dividing partitioned-vector boundary (``store.pad_vector``) —
exercising the scale-out path this PR fixed for 3→5-style growth.
"""
import os
import tempfile

os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=8"

import jax
import numpy as np

from repro import compat
from repro.core import locality, store
from repro.core.tsoracle import PartitionedVectorOracle
from repro.db import tpcc, workload

CFG = dict(n_warehouses=4, customers_per_district=8, n_items=64,
           n_threads=12, orders_per_thread=16, dist_degree=30.0)
ROUNDS = 6
GROW = tpcc.MeshGrowth(grow_round=3, new_shards=8)
GC = dict(gc_interval=2, max_txn_time=1)


def setup(cfg, n_shards):
    """A freshly loaded ``n_shards``-way deployment with journalling."""
    mesh = compat.mesh(n_shards)
    oracle = PartitionedVectorOracle(cfg.n_threads, n_parts=n_shards)
    lay, st = tpcc.init_tpcc(cfg, oracle, jax.random.PRNGKey(0))
    engine = tpcc.make_mixed_engine(cfg, lay, mesh, "mem", oracle,
                                    shard_vector=True, with_journal=True)
    st = tpcc.distribute_state(engine, st)
    jnl = tpcc.make_journal(cfg, oracle, capacity_rounds=ROUNDS + 2,
                            n_replicas=engine.n_shards)
    jnl = store.shard_journal(mesh, "mem", jnl)
    return oracle, lay, st, engine, jnl


def assert_same_state(layout, lay, n_slots, st_a, st_b):
    # the two runs pad the pool for different shard counts mid-history, so
    # equality is over the real records/slots — padding carries no semantics
    R = lay.catalog.total_records
    for field in tpcc.mvcc.VersionedTable._fields:
        np.testing.assert_array_equal(
            np.asarray(jax.device_get(getattr(st_a.nam.table, field)))[:R],
            np.asarray(jax.device_get(getattr(st_b.nam.table, field)))[:R],
            err_msg=f"{layout}:{field}")
    np.testing.assert_array_equal(
        np.asarray(jax.device_get(st_a.nam.oracle_state.vec))[:n_slots],
        np.asarray(jax.device_get(st_b.nam.oracle_state.vec))[:n_slots],
        err_msg=f"{layout}:vec")
    np.testing.assert_array_equal(np.asarray(st_a.nam.extends.cursor),
                                  np.asarray(st_b.nam.extends.cursor))
    np.testing.assert_array_equal(np.asarray(st_a.hist_cursor),
                                  np.asarray(st_b.hist_cursor))
    for leaf_a, leaf_b in zip(jax.tree.leaves(st_a.order_index),
                              jax.tree.leaves(st_b.order_index)):
        np.testing.assert_array_equal(
            np.asarray(jax.device_get(leaf_a)),
            np.asarray(jax.device_get(leaf_b)), err_msg=f"{layout}:index")


def run_layout(layout, key_addressed=False):
    cfg = tpcc.TPCCConfig(layout=layout, key_addressed=key_addressed, **CFG)
    home = locality.thread_homes(cfg.n_threads, cfg.n_warehouses)

    # the reference: born at 8 shards, never grows
    oracle, lay, st0, engine, jnl = setup(cfg, GROW.new_shards)
    with tempfile.TemporaryDirectory() as d:
        st_ref, ms_ref = tpcc.run_mixed_rounds(
            cfg, lay, st0, oracle, jax.random.PRNGKey(9), ROUNDS,
            home_w=home, engine=engine, journal=jnl, checkpoint_dir=d, **GC)
    assert ms_ref.growth == ()

    # the live system: born at 4 shards, grown to 8 mid-mix
    oracle, lay, st1, engine, jnl = setup(cfg, 4)
    with tempfile.TemporaryDirectory() as d:
        st_exp, ms_exp = tpcc.run_mixed_rounds(
            cfg, lay, st1, oracle, jax.random.PRNGKey(9), ROUNDS,
            home_w=home, engine=engine, journal=jnl, checkpoint_dir=d,
            growth=GROW, **GC)

    (rep,) = ms_exp.growth
    assert rep.grow_round == GROW.grow_round
    assert (rep.old_shards, rep.new_shards) == (4, GROW.new_shards)
    # the expansion landed mid-run: the migration checkpoint predates the
    # grow round and committed work since it really was replayed from the
    # journal; record ranges really moved to the joining servers
    assert 0 <= rep.checkpoint_round < rep.grow_round, rep
    assert rep.replayed_entries > 0, rep
    assert rep.moved_slots > 0, rep
    assert rep.migration_seconds > 0, rep
    if key_addressed:   # the §5.2 directory really was repartitioned
        assert rep.moved_buckets > 0, rep

    assert_same_state(layout, lay, oracle.n_slots, st_ref, st_exp)
    for name in workload.TXN_TYPES:
        assert ms_ref.attempts[name] == ms_exp.attempts[name], (layout, name)
        assert ms_ref.commits[name] == ms_exp.commits[name], (layout, name)
        assert ms_ref.retries[name] == ms_exp.retries[name], (layout, name)
        for f, a, b in zip(tpcc.si.OpCounts._fields, ms_exp.ops[name],
                           ms_ref.ops[name]):
            assert float(a) == float(b), (layout, name, f)
    assert ms_ref.delivered == ms_exp.delivered
    assert ms_ref.snapshot_misses == ms_exp.snapshot_misses
    assert ms_ref.contention_aborts == ms_exp.contention_aborts
    assert ms_ref.gc_sweeps == ms_exp.gc_sweeps > 0
    assert ms_ref.ovf_peak == ms_exp.ovf_peak
    assert ms_ref.reclaim_traj == ms_exp.reclaim_traj
    assert ms_exp.total_commits > 0
    print(f"{layout}: grew {rep.old_shards}→{rep.new_shards} at round "
          f"{rep.grow_round} (checkpoint {rep.checkpoint_round}, "
          f"{rep.replayed_entries} replayed, {rep.moved_slots} slots moved, "
          f"{rep.moved_buckets} buckets moved) — expanded == born-large")


def main():
    assert len(jax.devices()) == 8, jax.devices()
    for layout in ("table_major", "warehouse_major"):
        run_layout(layout)
    # once more through the §5.2 key-addressed read path: the expansion must
    # also repartition the hash directory's bucket ranges
    run_layout("table_major", key_addressed=True)
    print("ELASTICITY_EQUIV_OK")


if __name__ == "__main__":
    main()
