"""Subprocess body for test_distributed_equiv: 8 forced host devices.

Runs the same TPC-C workloads (same seeds, §7.4 retry queues) twice —
through the single-shard ``si.run_round`` reference and through
``store.distributed_round`` on an 8-way 'mem' mesh with the timestamp
vector range-partitioned (PartitionedVectorOracle deployment) — and asserts
the sharded path is bit-identical: commit decisions, installed versions
(headers and payloads, current + old + overflow), oracle state, extend
cursors and the order index. Covered workloads, in both pool layouts:

* new-order alone (the original retry-queue run),
* payment alone and delivery alone (per-round drivers),
* the full five-transaction mix through ``run_mixed_rounds`` — per-type
  commit/abort counts and final state must match the single-shard reference.

The driver runs execute with the §5.3 GC thread ON (``gc_interval=1``,
``max_txn_time=1``): every round the single-shard path takes one snapshot
and sweeps the whole pool while each mesh shard snapshots into its own log
and sweeps only its resident records — the per-shard sweep must be
bit-identical too, and the GC telemetry (snapshot-miss vs contention abort
split, overflow-read counts, ring peak) must agree exactly.

With ``REPRO_EQUIV_FUSED=1`` in the environment the MESH deployment runs
with the DESIGN.md §8 Pallas kernels switched on (``fused_commit`` +
``batched_probe``) while the single-shard reference stays unfused — the
strongest cross-check: the fused sharded engine must be bit-identical to
the unfused single-shard protocol rendering across every workload, layout
and the key-addressed mode.
"""
import dataclasses
import os

os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=8"

FUSED = os.environ.get("REPRO_EQUIV_FUSED", "") == "1"

import jax
import jax.numpy as jnp
import numpy as np

from repro import compat
from repro.core import locality
from repro.core.tsoracle import PartitionedVectorOracle, VectorOracle
from repro.db import tpcc, workload

CFG = dict(n_warehouses=8, customers_per_district=8, n_items=64,
           n_threads=16, orders_per_thread=16, dist_degree=30.0)
ROUNDS = 4
GC = dict(gc_interval=1, max_txn_time=1)   # §5.3 GC thread on, tight E


def assert_same_gc_stats(layout, tag, sd, ss):
    """The sustained-execution telemetry must agree exactly between the
    sharded and the single-shard run (same fields on both stats types)."""
    for f in ("snapshot_misses", "contention_aborts", "ovf_reads",
              "gc_sweeps", "ovf_peak"):
        a, b = getattr(sd, f), getattr(ss, f)
        assert a == b, (layout, tag, f, a, b)
    assert ss.gc_sweeps > 0, (layout, tag)
    assert sd.reclaim_traj == ss.reclaim_traj, (layout, tag)


def assert_same_state(layout, tag, lay, st_d, st_s):
    R = lay.catalog.total_records
    for field in tpcc.mvcc.VersionedTable._fields:
        a = np.asarray(jax.device_get(getattr(st_d.nam.table, field)))[:R]
        b = np.asarray(getattr(st_s.nam.table, field))[:R]
        np.testing.assert_array_equal(a, b, err_msg=f"{layout}:{tag}:{field}")
    np.testing.assert_array_equal(
        np.asarray(jax.device_get(st_d.nam.oracle_state.vec)),
        np.asarray(st_s.nam.oracle_state.vec), err_msg=f"{layout}:{tag}:vec")
    np.testing.assert_array_equal(np.asarray(st_d.nam.extends.cursor),
                                  np.asarray(st_s.nam.extends.cursor))
    np.testing.assert_array_equal(np.asarray(st_d.hist_cursor),
                                  np.asarray(st_s.hist_cursor))
    for leaf_d, leaf_s in zip(jax.tree.leaves(st_d.order_index),
                              jax.tree.leaves(st_s.order_index)):
        np.testing.assert_array_equal(np.asarray(jax.device_get(leaf_d)),
                                      np.asarray(leaf_s))


def make_pair(cfg, mesh, *, seed=0):
    """(single-shard ref, sharded deployment) freshly loaded from one seed."""
    oracle_s = VectorOracle(cfg.n_threads)
    lay, st_s = tpcc.init_tpcc(cfg, oracle_s, jax.random.PRNGKey(seed))
    oracle_d = PartitionedVectorOracle(cfg.n_threads, n_parts=8)
    # REPRO_EQUIV_FUSED=1: the mesh engine bakes the §8 kernels into its
    # round executors (flags live in the cfg the builders close over); the
    # single-shard reference above stays unfused
    cfg_d = dataclasses.replace(cfg, fused_commit=FUSED,
                                batched_probe=FUSED)
    lay_d, st_d = tpcc.init_tpcc(cfg_d, oracle_d, jax.random.PRNGKey(seed))
    engine = tpcc.make_mixed_engine(cfg_d, lay_d, mesh, "mem", oracle_d,
                                    shard_vector=True)
    st_d = tpcc.distribute_state(engine, st_d)
    if cfg.key_addressed:
        assert engine.n_dir_buckets > 0 and st_d.directory is not None
    return lay, (oracle_s, st_s), (oracle_d, st_d, engine)


def run_neworder(layout: str, mesh):
    cfg = tpcc.TPCCConfig(layout=layout, **CFG)
    home = locality.thread_homes(cfg.n_threads, cfg.n_warehouses)
    lay, (oracle_s, st_s), (oracle_d, st_d, engine) = make_pair(cfg, mesh)
    st_s, stats_s = tpcc.run_neworder_rounds(
        cfg, lay, st_s, oracle_s, jax.random.PRNGKey(1), ROUNDS, home_w=home,
        **GC)
    st_d, stats_d = tpcc.run_neworder_rounds(
        cfg, lay, st_d, oracle_d, jax.random.PRNGKey(1), ROUNDS,
        home_w=home, engine=engine, **GC)
    np.testing.assert_array_equal(np.asarray(stats_d.committed),
                                  np.asarray(stats_s.committed))
    np.testing.assert_array_equal(np.asarray(stats_d.missed),
                                  np.asarray(stats_s.missed))
    assert stats_d.commits == stats_s.commits and stats_s.commits > 0
    assert_same_gc_stats(layout, "neworder", stats_d, stats_s)
    assert_same_state(layout, "neworder", lay, st_d, st_s)
    # the ops profiles feeding netmodel agree too
    for f, a, b in zip(tpcc.si.OpCounts._fields, stats_d.ops, stats_s.ops):
        assert float(a) == float(b), (layout, f, float(a), float(b))
    print(f"{layout}: neworder {stats_s.commits}/{stats_s.attempts} "
          f"committed, abort {stats_s.abort_rate:.3f} — sharded == single")
    return cfg, lay, (oracle_s, st_s), (oracle_d, st_d, engine)


def run_payment_delivery(layout, cfg, lay, single, dist):
    """Payment rounds then delivery rounds on the post-neworder states (so
    deliveries find real undelivered orders) — bit-identical per round."""
    (oracle_s, st_s), (oracle_d, st_d, engine) = single, dist
    key = jax.random.PRNGKey(5)
    for r in range(3):
        key, kp, kd = jax.random.split(key, 3)
        pinp = workload.gen_payment(kp, cfg.n_threads, cfg.n_warehouses,
                                    cfg.customers_per_district)
        ps = tpcc.payment_round(cfg, lay, st_s, oracle_s, pinp)
        pd = tpcc.payment_round_distributed(cfg, lay, st_d, oracle_d,
                                            engine, pinp)
        st_s, st_d = ps.state, pd.state
        np.testing.assert_array_equal(np.asarray(pd.committed),
                                      np.asarray(ps.committed))
        for f, a, b in zip(tpcc.si.OpCounts._fields, pd.ops, ps.ops):
            assert float(a) == float(b), (layout, "payment", f)
        dinp = workload.gen_delivery(kd, cfg.n_threads, cfg.n_warehouses)
        ds = tpcc.delivery_round(cfg, lay, st_s, oracle_s, dinp)
        dd = tpcc.delivery_round_distributed(cfg, lay, st_d, oracle_d,
                                             engine, dinp)
        st_s, st_d = ds.state, dd.state
        np.testing.assert_array_equal(np.asarray(dd.committed),
                                      np.asarray(ds.committed))
        np.testing.assert_array_equal(np.asarray(dd.delivered),
                                      np.asarray(ds.delivered))
        for f, a, b in zip(tpcc.si.OpCounts._fields, dd.ops, ds.ops):
            assert float(a) == float(b), (layout, "delivery", f)
    assert int(np.asarray(ps.committed).sum()) > 0
    assert int(np.asarray(ds.delivered).sum()) > 0, \
        "no delivery landed — equivalence would be vacuous"
    assert_same_state(layout, "payment+delivery", lay, st_d, st_s)
    print(f"{layout}: payment+delivery — sharded == single")


def run_mixed(layout: str, mesh, key_addressed: bool = False):
    """Full five-transaction mix: per-type commit/abort counts and final
    state must match the single-shard reference exactly. With
    ``key_addressed`` the item/stock and orderstatus/stocklevel reads
    resolve through the (sharded) §5.2 hash index; the caller additionally
    proves the keyed run equals the slot-addressed one."""
    cfg = tpcc.TPCCConfig(layout=layout, key_addressed=key_addressed, **CFG)
    home = locality.thread_homes(cfg.n_threads, cfg.n_warehouses)
    lay, (oracle_s, st_s), (oracle_d, st_d, engine) = make_pair(cfg, mesh)
    st_s, ms = tpcc.run_mixed_rounds(cfg, lay, st_s, oracle_s,
                                     jax.random.PRNGKey(9), 3, home_w=home,
                                     **GC)
    st_d, md = tpcc.run_mixed_rounds(cfg, lay, st_d, oracle_d,
                                     jax.random.PRNGKey(9), 3, home_w=home,
                                     engine=engine, **GC)
    for name in workload.TXN_TYPES:
        # the run must actually exercise every type through the mesh
        # executors, or the per-type equivalence below is vacuous
        assert ms.attempts[name] > 0, (layout, name, "never sampled")
        assert ms.attempts[name] == md.attempts[name], (layout, name)
        assert ms.commits[name] == md.commits[name], (layout, name)
        assert ms.retries[name] == md.retries[name], (layout, name)
        assert ms.snapshot_misses[name] == md.snapshot_misses[name], \
            (layout, name)
        assert ms.contention_aborts[name] == md.contention_aborts[name], \
            (layout, name)
        assert ms.ovf_reads[name] == md.ovf_reads[name], (layout, name)
        for f, a, b in zip(tpcc.si.OpCounts._fields, md.ops[name],
                           ms.ops[name]):
            assert float(a) == float(b), (layout, name, f)
    assert ms.gc_sweeps == md.gc_sweeps > 0
    assert ms.ovf_peak == md.ovf_peak
    assert ms.reclaim_traj == md.reclaim_traj
    assert ms.delivered == md.delivered
    assert ms.commits["neworder"] > 0 and ms.commits["payment"] > 0
    assert_same_state(layout, "mixed", lay, st_d, st_s)
    tag = "key-addressed mixed" if key_addressed else "mixed"
    print(f"{layout}: {tag} {ms.total_commits}/{ms.total_attempts} "
          f"committed ({dict(ms.commits)}) — sharded == single")
    return lay, st_s, ms


def check_key_equals_slot(layout, lay, slot_run, key_run):
    """The §5.2 key-addressed engine is an access path, not a semantics
    change: same seeds through the hash index must land the exact same
    final state and per-type outcomes as the analytic slot engine — on the
    mesh AND single-shard (each already proven sharded == single above).
    Op profiles differ only by the charged index probes."""
    st_s, ms = slot_run
    st_k, mk = key_run
    assert ms.attempts == mk.attempts and ms.commits == mk.commits, \
        (layout, ms.commits, mk.commits)
    assert ms.retries == mk.retries and ms.delivered == mk.delivered
    assert ms.snapshot_misses == mk.snapshot_misses
    assert ms.contention_aborts == mk.contention_aborts
    assert_same_state(layout, "key-vs-slot", lay, st_k, st_s)
    assert float(mk.ops["neworder"].record_reads) > \
        float(ms.ops["neworder"].record_reads), (layout, "no probes?")
    for name in ("orderstatus", "stocklevel"):   # may read zero keyed
        # records in a short run (empty districts) — never fewer reads
        assert float(mk.ops[name].record_reads) >= \
            float(ms.ops[name].record_reads), (layout, name)
    print(f"{layout}: key-addressed == slot-addressed (bit-identical state, "
          f"+probes in ops)")


def main():
    assert len(jax.devices()) == 8, jax.devices()
    mesh = compat.mesh(8)
    for layout in ("table_major", "warehouse_major"):
        cfg, lay, single, dist = run_neworder(layout, mesh)
        run_payment_delivery(layout, cfg, lay, single, dist)
        lay_m, st_slot, ms = run_mixed(layout, mesh)
        lay_k, st_key, mk = run_mixed(layout, mesh, key_addressed=True)
        check_key_equals_slot(layout, lay_m, (st_slot, ms), (st_key, mk))
    print("DISTRIBUTED_EQUIV_OK")


if __name__ == "__main__":
    main()
