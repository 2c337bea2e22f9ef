"""Subprocess body for test_distributed_equiv's crash-recovery check.

§6.2 end-to-end: the five-transaction TPC-C mix runs on an 8-way 'mem'
mesh with the per-thread commit journal replicated across the memory
servers and a checkpoint taken after every GC sweep.  Mid-run a
``FailureInjector`` kills one memory server — after it has CAS-locked a
round's write-sets and replicated their intent entries but before any
outcome is logged (the §3.2 "undetermined" window).  Recovery restores
the last checkpoint, replays the surviving journal replicas in ⟨commit
vector, round, sub-round⟩ order, drops the undetermined intents, has the
monitoring server release the abandoned locks, re-replicates the journal
and resumes the run on the surviving replicas.

The recovered run must be bit-identical to an uninterrupted run of the
same seeds — installed versions (current + old + overflow), the timestamp
vector, per-type commit/abort/retry counts, GC telemetry and op profiles
— in BOTH pool layouts (table_major and the §7.3 warehouse_major).  A
crash is an availability event, not a semantics change.
"""
import os
import tempfile

os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=8"

import jax
import jax.numpy as jnp
import numpy as np

from repro import compat
from repro.core import locality, store
from repro.core.tsoracle import PartitionedVectorOracle
from repro.db import tpcc, workload

CFG = dict(n_warehouses=8, customers_per_district=8, n_items=64,
           n_threads=16, orders_per_thread=16, dist_degree=30.0)
ROUNDS = 6
KILL = tpcc.FailureInjector(kill_round=3, dead_server=5)
GC = dict(gc_interval=2, max_txn_time=1)


def setup(cfg, mesh):
    """A freshly loaded 8-shard deployment with journalling enabled."""
    oracle = PartitionedVectorOracle(cfg.n_threads, n_parts=8)
    lay, st = tpcc.init_tpcc(cfg, oracle, jax.random.PRNGKey(0))
    engine = tpcc.make_mixed_engine(cfg, lay, mesh, "mem", oracle,
                                    shard_vector=True, with_journal=True)
    st = tpcc.distribute_state(engine, st)
    jnl = tpcc.make_journal(cfg, oracle, capacity_rounds=ROUNDS + 2,
                            n_replicas=engine.n_shards)
    jnl = store.shard_journal(mesh, "mem", jnl)
    return oracle, lay, st, engine, jnl


def assert_same_state(layout, st_a, st_b):
    for field in tpcc.mvcc.VersionedTable._fields:
        np.testing.assert_array_equal(
            np.asarray(jax.device_get(getattr(st_a.nam.table, field))),
            np.asarray(jax.device_get(getattr(st_b.nam.table, field))),
            err_msg=f"{layout}:{field}")
    np.testing.assert_array_equal(
        np.asarray(jax.device_get(st_a.nam.oracle_state.vec)),
        np.asarray(jax.device_get(st_b.nam.oracle_state.vec)),
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


def run_layout(layout, mesh):
    cfg = tpcc.TPCCConfig(layout=layout, **CFG)
    home = locality.thread_homes(cfg.n_threads, cfg.n_warehouses)

    oracle, lay, st0, engine, jnl = setup(cfg, mesh)
    with tempfile.TemporaryDirectory() as d:
        st_ref, ms_ref = tpcc.run_mixed_rounds(
            cfg, lay, st0, oracle, jax.random.PRNGKey(9), ROUNDS,
            home_w=home, engine=engine, journal=jnl, checkpoint_dir=d, **GC)
    assert ms_ref.recovery == ()

    oracle, lay, st1, engine, jnl = setup(cfg, mesh)
    with tempfile.TemporaryDirectory() as d:
        st_rec, ms_rec = tpcc.run_mixed_rounds(
            cfg, lay, st1, oracle, jax.random.PRNGKey(9), ROUNDS,
            home_w=home, engine=engine, journal=jnl, checkpoint_dir=d,
            failure=KILL, **GC)

    (rep,) = ms_rec.recovery
    assert rep.kill_round == KILL.kill_round
    assert rep.dead_server == KILL.dead_server
    # the kill landed mid-run: the checkpoint is older than the kill round,
    # committed work since it really was replayed from the journal, the
    # in-flight round really left undetermined intents and abandoned locks
    assert 0 <= rep.checkpoint_round < rep.kill_round, rep
    assert rep.replayed_entries > 0, rep
    assert rep.undetermined >= cfg.n_threads, rep
    assert rep.released_locks > 0, rep

    assert_same_state(layout, st_ref, st_rec)
    for name in workload.TXN_TYPES:
        assert ms_ref.attempts[name] == ms_rec.attempts[name], (layout, name)
        assert ms_ref.commits[name] == ms_rec.commits[name], (layout, name)
        assert ms_ref.retries[name] == ms_rec.retries[name], (layout, name)
        for f, a, b in zip(tpcc.si.OpCounts._fields, ms_rec.ops[name],
                           ms_ref.ops[name]):
            assert float(a) == float(b), (layout, name, f)
    assert ms_ref.delivered == ms_rec.delivered
    assert ms_ref.snapshot_misses == ms_rec.snapshot_misses
    assert ms_ref.contention_aborts == ms_rec.contention_aborts
    assert ms_ref.gc_sweeps == ms_rec.gc_sweeps > 0
    assert ms_ref.ovf_peak == ms_rec.ovf_peak
    assert ms_ref.reclaim_traj == ms_rec.reclaim_traj
    assert ms_rec.total_commits > 0
    print(f"{layout}: killed server {rep.dead_server} at round "
          f"{rep.kill_round} (checkpoint {rep.checkpoint_round}, "
          f"{rep.replayed_entries} replayed, {rep.undetermined} undetermined, "
          f"{rep.released_locks} locks released) — recovered == uninterrupted")


def main():
    assert len(jax.devices()) == 8, jax.devices()
    mesh = compat.mesh(8)
    for layout in ("table_major", "warehouse_major"):
        run_layout(layout, mesh)
    print("RECOVERY_EQUIV_OK")


if __name__ == "__main__":
    main()
