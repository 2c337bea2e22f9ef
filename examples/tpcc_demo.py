"""TPC-C on the NAM core — the paper's headline experiment in miniature.

Loads a small TPC-C database into the NAM store, runs the **full
five-transaction mix** (45/43/4/4/4) through the SI protocol
(timestamp-vector oracle, combined validate+lock CAS, WAL,
multi-versioning, per-type §7.4 retry queues), measures the real abort rate
and per-type RDMA-op profiles, and feeds them into the calibrated network
model to project cluster throughput at 8 and 56 machines — **both total and
new-order** txn/s, the paper's Fig. 4 split (6.5M new-order of 14.5M total).

    PYTHONPATH=src python examples/tpcc_demo.py --rounds 8 --skew 0.9

With ``--shards 8`` the rounds run through ``store.distributed_round`` (and
``store.distributed_readonly_round`` for the read-only types) on an
8-memory-server mesh, in both Fig. 5 locality deployments. The memory
servers are the first 8 devices of the default backend: TPU chips on a TPU
host; under ``JAX_PLATFORMS=cpu`` the script re-execs itself once with
XLA_FLAGS forcing 8 host devices.
"""
import argparse
import time

import jax

from repro import compat
from repro.core import locality, netmodel
from repro.core.tsoracle import PartitionedVectorOracle, VectorOracle
from repro.db import tpcc, workload
from repro.db.tpcc import mixed_profiles, neworder_share


def _print_mix(stats: tpcc.MixedRunStats):
    per_type = "  ".join(
        f"{t}:{stats.commits[t]}/{stats.attempts[t]}"
        for t in workload.TXN_TYPES)
    print(f"  commits/attempts per type: {per_type}")


def run_sharded(args):
    """Full-mix rounds on the mesh, locality-aware vs -oblivious.

    The sharded path pins one execution thread per warehouse (the paper's
    terminal density), so --warehouses is implied by --threads here.
    """
    if args.warehouses != args.threads:
        print(f"# note: --shards pins warehouses to --threads "
              f"({args.threads}); ignoring --warehouses={args.warehouses}")
    for mode, layout in (("aware", "warehouse_major"),
                         ("oblivious", "table_major")):
        cfg = tpcc.TPCCConfig(
            n_warehouses=args.threads, customers_per_district=16,
            n_items=256, n_threads=args.threads,
            orders_per_thread=max(64, args.rounds * 2),
            dist_degree=args.dist, skew_alpha=args.skew, layout=layout)
        oracle = PartitionedVectorOracle(cfg.n_threads, n_parts=args.shards)
        lay, st = tpcc.init_tpcc(cfg, oracle, jax.random.PRNGKey(0))
        mesh = compat.mesh(args.shards)
        engine = tpcc.make_mixed_engine(cfg, lay, mesh, "mem", oracle,
                                        shard_vector=True)
        st = tpcc.distribute_state(engine, st)
        home = locality.thread_homes(cfg.n_threads, cfg.n_warehouses)
        st, stats = tpcc.run_mixed_rounds(
            cfg, lay, st, oracle, jax.random.PRNGKey(1), args.rounds,
            home_w=home, engine=engine, locality_mode=mode)
        _, prof = mixed_profiles(stats)
        total = netmodel.namdb_throughput(
            prof, 2 * args.shards, 60, stats.abort_rate,
            local_fraction=stats.local_fraction)
        print(f"{args.shards}-server mesh, {mode:9s}: "
              f"{stats.total_commits}/{stats.total_attempts} committed "
              f"(steady-state abort {stats.abort_rate:.3f}), "
              f"{stats.local_fraction * 100:.0f}% of accesses machine-local, "
              f"total {total / 1e6:.2f}M txn/s "
              f"(new-order {total * neworder_share(stats) / 1e6:.2f}M)")
        _print_mix(stats)
    print("tpcc_demo OK")


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--rounds", type=int, default=8)
    ap.add_argument("--threads", type=int, default=16)
    ap.add_argument("--warehouses", type=int, default=16)
    ap.add_argument("--skew", type=float, default=None,
                    help="zipf alpha (None = uniform)")
    ap.add_argument("--dist", type=float, default=10.0,
                    help="%% of new-orders touching a remote warehouse")
    ap.add_argument("--shards", type=int, default=1,
                    help="run through distributed_round on this many "
                    "simulated memory servers")
    args = ap.parse_args()
    compat.use_compile_cache()

    if args.shards > 1:
        compat.ensure_host_devices(args.shards)
        return run_sharded(args)

    cfg = tpcc.TPCCConfig(n_warehouses=args.warehouses,
                          customers_per_district=32, n_items=256,
                          n_threads=args.threads, orders_per_thread=64,
                          dist_degree=args.dist, skew_alpha=args.skew)
    oracle = VectorOracle(cfg.n_threads)
    lay, st = tpcc.init_tpcc(cfg, oracle, jax.random.PRNGKey(0))

    t0 = time.time()
    st, stats = tpcc.run_mixed_rounds(cfg, lay, st, oracle,
                                      jax.random.PRNGKey(1), args.rounds)
    dt = time.time() - t0

    print(f"ran {stats.total_attempts} transactions ({args.rounds} rounds x "
          f"{cfg.n_threads} threads, full 45/43/4/4/4 mix) in {dt:.1f}s")
    print(f"abort rate = {stats.abort_rate:.3f}  (skew={args.skew}, "
          f"dist={args.dist}%)")
    _print_mix(stats)
    per_type, prof = mixed_profiles(stats)
    share = neworder_share(stats)
    print(f"mix profile: reads={prof.reads:.1f} cas={prof.cas:.1f} "
          f"installs={prof.installs:.1f}  (new-order: "
          f"reads={per_type['neworder'].reads:.1f} "
          f"cas={per_type['neworder'].cas:.1f})")
    print("\nprojected cluster throughput (calibrated cost model, Fig. 4):")
    for n in (8, 28, 56):
        thr = netmodel.namdb_throughput(prof, n, 60, stats.abort_rate)
        thr_loc = netmodel.namdb_throughput(prof, n, 60, stats.abort_rate,
                                            local_fraction=0.9)
        trad = netmodel.traditional_throughput(prof, n, 60, stats.abort_rate)
        print(f"  {n:3d} machines: NAM-DB total {thr / 1e6:5.2f} M txn/s"
              f" (new-order {thr * share / 1e6:5.2f} M)"
              f"   +locality {thr_loc / 1e6:5.2f} M   traditional "
              f"{trad / 1e3:6.0f} k")
    print("\n(paper anchors @56: 14.5 M total / 6.5 M new-order w/ locality;"
          " 3.64 M w/o)")
    print("tpcc_demo OK")


if __name__ == "__main__":
    main()
