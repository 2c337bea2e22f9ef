"""Exp-4 (paper Fig. 8): effect of contention (zipf skew) on abort rate.

Pure measurement on the abort axis — no network model needed there: abort
rates fall straight out of the executed SI protocol. The full
five-transaction mix runs with ``workload.make_skew`` turning the uniform
TPC-C draws zipfian: warehouse popularity follows zipf(α) over the paper's
α grid (threads collide on hot warehouses instead of being pinned to
distinct homes) and one district takes half of all district draws. Skewed
draws consume exactly the same RNG keys as uniform ones, so the α=uniform
point is bit-identical to the pre-skew workload. Throughput per point
comes from the calibrated model at a FIXED cluster size fed with the
measured abort rate and mix profile — the cluster never changes, so the
curve isolates contention.

Run as a script the mix goes through the per-type mesh executors on a
multi-server deployment (``--shards``: the first N devices of the default
backend, forced host devices under ``JAX_PLATFORMS=cpu``);
``run()`` keeps the single-shard reference path for ``benchmarks/run.py``
(no mesh leakage into the shared process).

    python benchmarks/bench_contention.py [--smoke] [--shards N]
"""
from __future__ import annotations

import argparse
import time

import jax

from repro import compat
from repro.core import netmodel
from repro.core.tsoracle import PartitionedVectorOracle, VectorOracle
from repro.db import tpcc, workload

ALPHAS = [None, 0.8, 1.0, 2.0]
SMOKE_ALPHAS = [None, 2.0]

# one district takes half of all district draws — the paper's "hot spot"
# flavour of skew, stacked on top of warehouse popularity
HOT_DISTRICT_MASS = 0.5


def _label(alpha) -> str:
    return "uniform" if alpha is None else f"zipf{alpha:g}"


def measure(alpha, *, n_shards: int = 0, n_rounds: int = 6,
            n_threads: int = 16, mix=None):
    """Full-mix rounds under zipf(α) warehouse + hot-district skew.

    ``n_shards=0`` runs the single-shard reference path (no mesh);
    otherwise the rounds go through the mesh executors. Warehouses are NOT
    thread-pinned (``home_w=None``): contention comes from threads drawn
    onto the same hot warehouses — the Fig. 8 axis. Half as many
    warehouses as threads guarantees collisions even at α=0.

    Returns (MixedRunStats, us/txn).
    """
    cfg = tpcc.TPCCConfig(
        n_warehouses=max(2, n_threads // 2), customers_per_district=8,
        n_items=128, n_threads=n_threads,
        orders_per_thread=max(64, n_rounds * 2), dist_degree=20.0)
    skew = None if alpha is None else workload.make_skew(
        cfg.n_warehouses, wh_alpha=alpha,
        hot_district_mass=HOT_DISTRICT_MASS)
    engine = None
    if n_shards:
        oracle = PartitionedVectorOracle(cfg.n_threads, n_parts=n_shards)
        lay, st = tpcc.init_tpcc(cfg, oracle, jax.random.PRNGKey(0))
        mesh = compat.mesh(n_shards)
        engine = tpcc.make_mixed_engine(cfg, lay, mesh, "mem", oracle,
                                        shard_vector=True)
        st = tpcc.distribute_state(engine, st)
    else:
        oracle = VectorOracle(cfg.n_threads)
        lay, st = tpcc.init_tpcc(cfg, oracle, jax.random.PRNGKey(0))
    t0 = time.perf_counter()
    st, stats = tpcc.run_mixed_rounds(
        cfg, lay, st, oracle, jax.random.PRNGKey(1), n_rounds,
        engine=engine, locality_mode="oblivious" if engine else None,
        mix=mix, skew=skew)
    us = (time.perf_counter() - t0) / stats.total_attempts * 1e6
    return stats, us


def _throughput(stats) -> float:
    """Modeled txn/s at a fixed 8-memory + 8-compute cluster from the
    measured mix profile and abort rate — the contention-only curve."""
    _, prof = tpcc.mixed_profiles(stats)
    # the single-shard reference path measures no placement, so its
    # local_fraction is NaN — the model then assumes all-remote access
    lf = stats.local_fraction
    if lf != lf:
        lf = 0.0
    return netmodel.namdb_throughput(prof, 16, 60, stats.abort_rate,
                                     local_fraction=lf)


def run():
    """Single-device entry used by benchmarks/run.py (no mesh leakage).

    Returns (rows, curve): rows are ``(name, us_per_txn, abort_rate)``,
    curve maps the α label to ``(abort_rate, modeled_txn_per_s)``.
    """
    rows, curve = [], {}
    for a in ALPHAS:
        stats, us = measure(a)
        curve[_label(a)] = (stats.abort_rate, _throughput(stats))
        rows.append((f"tpcc_contention_{_label(a)}", us, stats.abort_rate))
    return rows, curve


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--shards", type=int, default=2)
    ap.add_argument("--rounds", type=int, default=6)
    ap.add_argument("--threads", type=int, default=16)
    ap.add_argument("--smoke", action="store_true",
                    help="CI mode: tiny config, 2 shards, α in "
                    "{uniform, 2.0} only")
    args = ap.parse_args()
    if args.smoke:
        args.shards, args.rounds, args.threads = 2, 3, 4
    alphas = SMOKE_ALPHAS if args.smoke else ALPHAS
    if args.shards > 1:
        compat.ensure_host_devices(args.shards)

    print("name,us_per_call,derived")
    results = []
    for a in alphas:
        stats, us = measure(a, n_shards=args.shards, n_rounds=args.rounds,
                            n_threads=args.threads)
        results.append((a, stats))
        print(f"tpcc_contention_{args.shards}shard_{_label(a)},"
              f"{us:.1f},{stats.abort_rate:.4f}")
        print(f"#   {_label(a)}: commits={stats.total_commits}/"
              f"{stats.total_attempts} snapshot_misses="
              f"{sum(stats.snapshot_misses.values())} contention="
              f"{sum(stats.contention_aborts.values())} "
              f"thr@16m={_throughput(stats) / 1e6:.2f}M")

    if args.smoke:
        # CI contract: every skew point must actually execute the mix on
        # the mesh — a skew knob that wedges the executors would otherwise
        # only surface as an empty-looking curve
        for a, stats in results:
            if stats.total_commits == 0:
                raise SystemExit(f"contention smoke ({_label(a)}): "
                                 f"no transaction committed — the skewed "
                                 f"mix wedged the mesh executors")
        print("# smoke: all skew points executed the mix on the mesh")


if __name__ == "__main__":
    main()
