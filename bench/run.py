"""Run one cell of the benchmark on the chips of this machine.

    python3 bench/run.py --workload <cell> --seed <n> --seconds <s> --trace <0|1>

A cell (``BENCHMARK.json`` ``workloads``) is a configuration
(``bench/configs/<name>.json``) under a traffic mix
(``bench/traffic/<name>.json``). One run:

1. loads the tables from the seed in one jitted program, on one chip or
   partitioned over the cell's chips;
2. warms up with one call of the driver (``tpcc.run_mixed_rounds``) over
   the mix's warm-up rounds, which compiles every program the window runs;
3. checks that the driver's generator draws what the benchmark's copy of it
   draws (``traffic.guard``);
4. measures one call of the driver over N rounds, N sized from the
   warm-up's round time to last about ``--seconds`` and capped so that no
   lane's insert extend fills; it fails if a program is lowered or compiled inside
   the window or if it counts another number of rounds than N;
5. after the window, reads the peak device memory, copies the store to the
   host, frees the chips, replays every call in the plain reference
   (``reference.py``) and compares (``compare.py``).

The last line of standard output is one JSON object; with ``--trace 0`` it
carries the end-to-end metrics, with ``--trace 1`` the per-layer ones read
from a profiler trace of the window. The numbers compared, each beside its
limit, are the last lines of standard error and the last key of the result.
Without a TPU, or with fewer chips than the cell needs, it prints no result
and exits non-zero.
"""
from __future__ import annotations

import time

T_PROCESS = time.perf_counter()

import argparse  # noqa: E402
import contextlib  # noqa: E402
import json  # noqa: E402
import os  # noqa: E402
import shutil  # noqa: E402
import sys  # noqa: E402
import tempfile  # noqa: E402
import types  # noqa: E402

HERE = os.path.dirname(os.path.abspath(__file__))
sys.path.insert(0, HERE)
sys.path.insert(0, os.path.join(os.path.dirname(HERE), "src"))

import jax  # noqa: E402

import adapter  # noqa: E402
import compare  # noqa: E402
import reference  # noqa: E402
import spec  # noqa: E402
import trace_reduce  # noqa: E402
import traffic  # noqa: E402


class NoChip(RuntimeError):
    """No TPU, fewer chips than the cell needs, or no peaks for it."""


def log(msg: str) -> None:
    print(f"[bench] {msg}", file=sys.stderr, flush=True)


def use_compile_cache() -> str:
    """JAX's persistent cache at a fixed path inside the checkout, so that
    two checkouts share nothing and only a cell's first run in one
    compiles; every program goes in, since the driver's op-by-op programs
    each compile in under JAX's default 1 s floor."""
    path = os.path.join(spec.ROOT, ".jax_cache")
    jax.config.update("jax_compilation_cache_dir", path)
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0)
    jax.config.update("jax_persistent_cache_min_entry_size_bytes", 0)
    return path


def seed_keys(seed: int):
    """Load, warm-up and window keys from a seed of up to 64 bits."""
    root = jax.random.fold_in(jax.random.PRNGKey(seed & 0xFFFFFFFF),
                              (seed >> 32) & 0xFFFFFFFF)
    return tuple(jax.random.split(root, 3))


def devices_for(chips: int, allow_cpu: bool):
    devs = jax.devices()
    if devs[0].platform != "tpu" and not allow_cpu:
        raise NoChip(f"no TPU: JAX's default backend is {devs[0].platform}")
    if len(devs) < chips:
        raise NoChip(f"the cell needs {chips} chips, JAX sees {len(devs)}")
    if not allow_cpu:
        spec.peaks(devs[0].device_kind)
    return devs[:chips]


def check_window(n: int, times: list, compiled: list) -> None:
    """Refuse a window in which a program was lowered or compiled, or whose
    round clock ticked another number of times than the N rounds asked."""
    if compiled:
        raise RuntimeError(f"{len(compiled)} lowerings or compiles inside "
                           f"the window: {compiled}")
    if len(times) - 1 != n:
        raise RuntimeError(f"the window timed {len(times) - 1} rounds, "
                           f"not {n}")


def run(workload: str, seed: int, seconds: float, trace: bool, *,
        trace_dir: str | None = None, allow_cpu: bool = False,
        corrupt=None) -> dict:
    """One run of one cell; returns the result object. A trace is written
    to ``trace_dir`` and kept there, or to a temporary directory that is
    removed. ``allow_cpu`` and ``corrupt`` (planted faults, see
    ``adapter.instrument``) are for the tests alone."""
    cell = spec.cell(workload)
    sizes = spec.config(cell.config)
    mix = traffic.load_mix(spec.traffic_path(cell.traffic))
    devs = devices_for(cell.chips, allow_cpu)
    cache = use_compile_cache() if not allow_cpu else None
    log(f"cell {cell.name}: {cell.chips} x {devs[0].device_kind}; "
        f"compile cache {cache}")
    k_load, k_warm, k_win = seed_keys(seed)
    drv = adapter.build(sizes, mix, cell.chips)
    W, T = drv.cfg.n_warehouses, drv.cfg.n_threads
    probe = adapter.Probe()
    clog = adapter.CompileCounter()
    keep = trace_dir is not None
    if trace and not keep:
        trace_dir = tempfile.mkdtemp(prefix="bench_trace_")
    try:
        with adapter.instrument(probe, corrupt):
            st = adapter.load(drv, k_load)
            log(f"loaded {drv.n_records} records, {W} warehouses, {T} lanes"
                f" in {time.perf_counter() - T_PROCESS:.3f} s")
            st, stats = adapter.call(drv, st, k_warm, mix.warmup_rounds)
            jax.block_until_ready(st)
            warm_stats = adapter.stats_dict(stats)
            warm_answers = adapter.answers_to_host(probe)
            # the last warm-up round, less what compiled in it: a first
            # run in a checkout compiles there, and its window should
            # have as many rounds as every later run's
            a, b = probe.round_times[-2:]
            round_s = max((b - a) - clog.seconds_between(a, b), 1e-3)
            for sub in traffic.round_keys(k_win, 2):
                traffic.guard(adapter.program_draws(drv, sub),
                              traffic.draw(sub, mix, T, W, drv.cfg.n_items,
                                           drv.cfg.customers_per_district))
            cap = drv.cfg.orders_per_thread - adapter.insert_cursors(st)
            n = max(1, round(seconds / round_s))
            if n > cap:
                log(f"window cut from {n} to {cap} rounds: no lane's insert"
                    f" extend may fill ({drv.cfg.orders_per_thread} slots)")
                n = cap
            if n < 1:
                raise RuntimeError("the insert extends are full after the "
                                   "warm-up; raise orders_per_thread")
            probe.reset()
            compiles = len(clog.names)
            if trace:
                jax.profiler.start_trace(trace_dir)
                probe.annotate = True
            t0 = time.perf_counter()
            with (jax.profiler.TraceAnnotation(trace_reduce.WINDOW) if trace
                  else contextlib.nullcontext()):
                st, stats = adapter.call(drv, st, k_win, n)
                jax.block_until_ready(st)
            t1 = time.perf_counter()
            if trace:
                probe.annotate = False
                jax.profiler.stop_trace()
            in_window = clog.names[compiles:]
            times = [t0] + list(probe.round_times)
            win_answers = adapter.answers_to_host(probe)
        check_window(n, times, in_window)
        window_s = t1 - t0
        setup_s = t0 - T_PROCESS
        rounds_ms = [1e3 * (b - a) for a, b in zip(times, times[1:])]
        peak = max((d.memory_stats() or {}).get("peak_bytes_in_use", 0)
                   for d in devs)
        log(f"window: {n} rounds in {window_s:.6f} s ({window_s / n * 1e3:.3f}"
            f" ms a round; rounds {min(rounds_ms):.3f} to {max(rounds_ms):.3f}"
            f" ms); set-up {setup_s:.3f} s; peak {peak} B")
        prog_stats = [warm_stats, adapter.stats_dict(stats)]
        red = None
        if trace:
            red = trace_reduce.reduce(trace_reduce.load(trace_dir))
        t_ref = time.perf_counter()
        prog_store = adapter.to_host(drv, st)
        adapter.delete(st)
        del st
        ref_st, ref_out = reference.replay(
            sizes, mix, k_load, [(k_warm, mix.warmup_rounds), (k_win, n)])
        checks = compare.checks(prog_stats, [warm_answers, win_answers],
                                prog_store, ref_out, ref_st)
        log(f"reference and comparison: {time.perf_counter() - t_ref:.3f} s;"
            f" orders indexed {len(ref_st.index)}, program's index "
            f"{len(prog_store['index'])}")
    finally:
        if trace and not keep:
            shutil.rmtree(trace_dir, ignore_errors=True)

    w = stats
    commits = sum(w.commits.values())
    failed = sum(w.attempts[t] - w.commits[t] - w.retries[t]
                 for t in w.attempts)
    if trace:
        ctx = types.SimpleNamespace(trace=red, stats=prog_stats[1],
                                    rounds=n, chips=cell.chips)
        metrics = {}
        for m in spec.per_layer(cell.name):
            v = spec.reader(m["name"])(ctx)
            if v is not None:
                metrics[m["name"]] = {"value": v, "unit": m["unit"]}
    else:
        metrics = {
            "txn_per_s": {"value": commits / window_s, "unit": "txn/s"},
            "neworder_per_s": {"value": w.commits["neworder"] / window_s,
                               "unit": "txn/s"},
            "round_ms_mean": {"value": 1e3 * window_s / n,
                             "unit": "ms"},
            "setup_s": {"value": setup_s, "unit": "s"},
        }
        e2e = {m["name"] for m in spec.benchmark()["end_to_end"]
               if "workloads" not in m or cell.name in m["workloads"]}
        metrics = {k: v for k, v in metrics.items() if k in e2e}
    device = {"platform": devs[0].platform, "kind": devs[0].device_kind,
              "count": len(devs), "memory_peak_bytes": peak}
    if trace:
        device.update(busy_s=red.busy_s, window_s=red.window_s)
    result = {"correct": all(v <= lim for v, lim in checks.values()),
              "attempted": commits + failed, "failed": failed,
              "metrics": metrics, "device": device}
    if trace:
        result["breakdown"] = trace_reduce.breakdown(red)
    result["checks"] = {k: {"value": v, "limit": lim}
                        for k, (v, lim) in checks.items()}
    for k, (v, lim) in checks.items():
        log(f"check {k}: {v} (limit {lim})")
    return result


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    ap.add_argument("--trace-dir", default=None,
                    help="keep the profiler trace of a --trace 1 run here")
    args = ap.parse_args(argv)
    try:
        result = run(args.workload, args.seed, args.seconds,
                     bool(args.trace), trace_dir=args.trace_dir)
    except NoChip as e:
        log(f"nothing run: {e}")
        return 2
    print(json.dumps(result), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
