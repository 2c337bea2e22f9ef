"""Benchmark harness — one function per paper table/figure.

Prints ``name,us_per_call,derived`` CSV rows. ``us_per_call`` is wall time of
the real JAX execution on this host; ``derived`` is the paper-cluster
quantity from the calibrated model (throughput, abort rate or ratio — see
each module). Roofline/LM benchmarks live in benchmarks/roofline_table.py
and are run by the dry-run launcher (they need 512 placeholder devices,
which must not leak here).
"""
from __future__ import annotations

import sys


def main() -> None:
    from benchmarks import (bench_contention, bench_locality, bench_oracle,
                            bench_tpcc_scaling)
    from repro import compat

    compat.use_compile_cache()

    print("name,us_per_call,derived")

    rows, curve = bench_oracle.run()
    for name, us, derived in rows:
        print(f"{name},{us:.3f},{derived:.0f}")
    for v, pts in curve.items():
        print(f"# fig6 {v}: "
              + " ".join(f"{c}nodes={t/1e6:.1f}M" for c, t in pts))

    rows, curves, prof, abort, share = bench_tpcc_scaling.run()
    for name, us, derived in rows:
        print(f"{name},{us:.1f},{derived:.0f}")
    print(f"# fig4 measured abort={abort:.4f} reads/txn={prof.reads:.1f} "
          f"cas/txn={prof.cas:.1f} neworder_share={share:.3f}")
    for name, pts in curves.items():
        print(f"# fig4 {name}: "
              + " ".join(f"{n}m={t/1e6:.2f}M" for n, t in pts))

    rows, curve = bench_locality.run()
    for name, us, derived in rows:
        print(f"{name},{us:.2f},{derived:.3f}")
    for d, c in curve.items():
        print(f"# fig7 dist={d}%: local={c['local_frac']:.2f} "
              f"abort={c['abort']:.3f} thr_loc={c['thr_loc']/1e6:.2f}M "
              f"thr_noloc={c['thr_noloc']/1e6:.2f}M hstore={c['hstore']:.0f}")

    rows, curve = bench_contention.run()
    for name, us, derived in rows:
        print(f"{name},{us:.1f},{derived:.4f}")
    for k, (ab, thr) in curve.items():
        print(f"# fig8 {k}: abort={ab:.3f} thr={thr/1e6:.2f}M")

    # LM-serving + kernel micro-benchmarks (CPU-sized; skipped with --db-only)
    if "--db-only" not in sys.argv:
        try:
            from benchmarks import bench_kernels, bench_serve
            for name, us, derived in bench_kernels.run():
                print(f"{name},{us:.1f},{derived:.2f}")
            for name, us, derived in bench_serve.run():
                print(f"{name},{us:.1f},{derived:.2f}")
        except ImportError as e:  # pragma: no cover - pre-kernel bootstrap
            print(f"# kernels/serve benches unavailable: {e}")


if __name__ == "__main__":
    main()
