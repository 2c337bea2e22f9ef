"""Run the TPC-C engine's main path on a TPU and check what it computes.

    python chip_smoke.py                # one chip
    python chip_smoke.py --four-chips   # the mesh phase alone, on four chips

One chip (two phases):

* ``spec``: the full five-type mix through ``tpcc.run_mixed_rounds`` with the
  default flags, at the TPC-C cardinalities (100,000 items, 100,000 stock
  rows per warehouse, 10 districts of 3,000 customers) over 40 warehouses
  with 10 lanes (the spec's terminals) per warehouse: more than 1 GB of
  versioned store on the chip. Every type must commit, no record may stay
  locked, and every abort must be retried or still pending.
* ``backends``: 2 warehouses at the same cardinalities through the same entry
  point on the chip and on the CPU backend of this process; the final stores
  and the run statistics must be bit-identical.

Four chips (``--four-chips``, this phase only): the mix through
``tpcc.make_mixed_engine`` on a mesh of the 4 chips, in the locality-aware
(``warehouse_major``) and the oblivious (``table_major``) placement, each
compared bit for bit with the single-shard reference run on one chip.

Each phase has a time budget and reports the seconds spent lowering and
compiling apart from the rest. Any failure raises, so the exit code is not
0. The last line of output is the device as JSON, printed only after every
phase passed. The script needs a TPU and exits non-zero without one.
"""
from __future__ import annotations

import argparse
import collections
import json
import os
import sys
import time

sys.path.insert(0, os.path.join(os.path.dirname(os.path.abspath(__file__)),
                                "src"))

import jax  # noqa: E402
import jax.numpy as jnp  # noqa: E402
import numpy as np  # noqa: E402

from repro import compat  # noqa: E402
from repro.core import header, locality  # noqa: E402
from repro.core.tsoracle import PartitionedVectorOracle  # noqa: E402
from repro.core.tsoracle import VectorOracle  # noqa: E402
from repro.db import tpcc, workload  # noqa: E402

# TPC-C cardinalities (spec §1.2.1, §4.3.3.1); warehouses and rounds per phase
SPEC = dict(n_items=100_000, customers_per_district=3_000)
LANES_PER_WAREHOUSE = 10    # the spec's terminals per warehouse
PHASES = {                  # name: (warehouses, rounds, budget in seconds)
    "spec": (40, 10, 540),
    "backends": (2, 8, 300),
    "four_chips": (4, 6, 200),   # budget per run: 2 references, 2 meshes
}
# lowering to StableHLO, then XLA's compile (tracing nests, so it is left in
# "the rest" rather than double counted)
COMPILE_EVENTS = ("/jax/core/compile/jaxpr_to_mlir_module_duration",
                  "/jax/core/compile/backend_compile_duration")


def log(msg: str) -> None:
    print(msg, flush=True)


class CompileLog:
    """Seconds JAX spends lowering and compiling, read from its monitoring
    events; XLA compiles are also counted by program name."""

    def __init__(self):
        self.seconds = 0.0
        self.programs = collections.Counter()
        self.program_seconds = collections.Counter()
        jax.monitoring.register_event_duration_secs_listener(self._on_event)

    def _on_event(self, event, secs, **kw):
        if event not in COMPILE_EVENTS:
            return
        self.seconds += secs
        if event == COMPILE_EVENTS[-1]:
            name = kw.get("fun_name", "?")
            self.programs[name] += 1
            self.program_seconds[name] += secs

    def snapshot(self):
        return (self.seconds, collections.Counter(self.programs),
                collections.Counter(self.program_seconds))


class Phase:
    """Times one phase: wall seconds, compile seconds, programs compiled.
    Leaving it past its budget raises."""

    def __init__(self, clog: CompileLog, name: str, budget: float):
        self.clog, self.name, self.budget = clog, name, budget

    def __enter__(self):
        self.t0 = time.perf_counter()
        self.c0 = self.clog.snapshot()
        return self

    def split(self):
        """(wall s, compile s, {program: (count, s)}) since the start."""
        sec, prog, psec = self.clog.snapshot()
        progs = {k: (prog[k] - self.c0[1][k], psec[k] - self.c0[2][k])
                 for k in prog if prog[k] > self.c0[1][k]}
        return time.perf_counter() - self.t0, sec - self.c0[0], progs

    def __exit__(self, exc_type, *_):
        if exc_type is not None:
            return False
        wall, comp, progs = self.split()
        rounds = {k: v for k, v in progs.items() if k.endswith("_round)")
                  or "version_mover" in k}
        ops = sum(c for k, (c, _) in progs.items() if k not in rounds)
        log(f"[{self.name}] {wall:.3f} s wall: {comp:.3f} s lowering + "
            f"compiling, {wall - comp:.3f} s the rest; budget "
            f"{self.budget:.0f} s")
        log(f"[{self.name}] programs compiled: " + ", ".join(
            f"{k} x{c} {s:.3f} s" for k, (c, s) in sorted(rounds.items()))
            + f"; {ops} other (op-by-op) programs")
        if wall > self.budget:
            raise RuntimeError(f"phase {self.name} took {wall:.1f} s, over "
                               f"its budget of {self.budget:.0f} s")
        return False


def spec_config(n_warehouses: int, rounds: int, layout: str = "table_major"):
    return tpcc.TPCCConfig(
        n_warehouses=n_warehouses,
        n_threads=LANES_PER_WAREHOUSE * n_warehouses,
        orders_per_thread=rounds,   # a lane inserts at most one order a round
        layout=layout, **SPEC)


def store_bytes(st) -> int:
    return sum(x.nbytes for x in jax.tree.leaves(st))


def mix_line(stats) -> str:
    return "  ".join(f"{t} {stats.commits[t]}/{stats.attempts[t]}"
                     for t in workload.TXN_TYPES)


def check_run(cfg, st, stats) -> None:
    """What one run must show whatever its size."""
    for t in workload.TXN_TYPES:
        if stats.commits[t] <= 0:
            raise AssertionError(f"{t} committed nothing: {mix_line(stats)}")
    locked = int(jnp.sum(header.is_locked(st.nam.table.cur_hdr)))
    if locked:
        raise AssertionError(f"{locked} records still locked after the run")
    pending = 0
    for t in workload.TXN_TYPES:
        aborted = stats.attempts[t] - stats.commits[t]
        if t in tpcc.READ_ONLY_TYPES and aborted:
            raise AssertionError(f"read-only {t} aborted {aborted} times")
        causes = stats.snapshot_misses[t] + stats.contention_aborts[t]
        if aborted != causes:
            raise AssertionError(f"{t}: {aborted} aborts but {causes} "
                                 f"attributed to a cause")
        if not 0 <= aborted - stats.retries[t]:
            raise AssertionError(f"{t}: more retries than aborts")
        pending += aborted - stats.retries[t]
    if pending > cfg.n_threads:
        raise AssertionError(f"{pending} aborts neither retried nor pending "
                             f"in the last round ({cfg.n_threads} lanes)")


def run_mix(cfg, rounds: int, *, seed: int = 0, home_w=None):
    """Load and run the mix single-shard on the default device."""
    oracle = VectorOracle(cfg.n_threads)
    lay, st = tpcc.init_tpcc(cfg, oracle, jax.random.PRNGKey(seed))
    st, stats = tpcc.run_mixed_rounds(cfg, lay, st, oracle,
                                      jax.random.PRNGKey(seed + 1), rounds,
                                      home_w=home_w)
    jax.block_until_ready(st)
    return lay, st, stats


def assert_identical(tag, a, b, records=None) -> int:
    """Every leaf of two pytrees equal bit for bit (the leading record axis
    of ``a`` cut to ``records`` where given); returns the leaf count."""
    la, _ = jax.tree_util.tree_flatten_with_path(a)
    lb = jax.tree.leaves(b)
    if len(la) != len(lb):
        raise AssertionError(f"{tag}: {len(la)} vs {len(lb)} arrays")
    for (path, x), y in zip(la, lb):
        x, y = np.asarray(jax.device_get(x)), np.asarray(jax.device_get(y))
        if records is not None and x.shape[:1] != y.shape[:1]:
            x = x[:records]
        if x.shape != y.shape or x.dtype != y.dtype \
                or not np.array_equal(x, y):
            where = jax.tree_util.keystr(path)
            raise AssertionError(f"{tag}: {where} differs")
    return len(la)


def assert_same_stats(tag, a, b) -> None:
    for f in ("attempts", "commits", "retries", "snapshot_misses",
              "contention_aborts", "ovf_reads", "delivered", "ovf_peak"):
        if getattr(a, f) != getattr(b, f):
            raise AssertionError(f"{tag}: stats.{f} {getattr(a, f)} vs "
                                 f"{getattr(b, f)}")
    for t in a.ops:
        if [float(x) for x in a.ops[t]] != [float(x) for x in b.ops[t]]:
            raise AssertionError(f"{tag}: op counts of {t} differ")


def phase_spec(clog: CompileLog) -> None:
    n_w, rounds, budget = PHASES["spec"]
    cfg = spec_config(n_w, rounds)
    with Phase(clog, "spec", budget):
        lay, st, stats = run_mix(cfg, rounds)
        check_run(cfg, st, stats)
        dev = jax.devices()[0]
        peak = (dev.memory_stats() or {}).get("peak_bytes_in_use")
        log(f"[spec] {n_w} warehouses, {cfg.n_threads} lanes, {rounds} "
            f"rounds, {lay.catalog.total_records} records: store "
            f"{store_bytes(st)} B on {dev.device_kind}; peak_bytes_in_use "
            f"{peak}")
        log(f"[spec] commits/attempts: {mix_line(stats)}; total "
            f"{stats.total_commits}/{stats.total_attempts}")
        log(f"[spec] {stats.total_attempts} transactions; checks passed "
            f"(every type commits, no record locked, every abort retried "
            f"or pending)")


def same_draws(cfg, rounds: int, seed: int, cpu) -> None:
    """The mix's workload draws, made on the chip and on the CPU, must be
    equal: only then does the backend comparison compare the engine alone
    (the Gumbel top-k of new-order draws floats)."""
    logits = workload.zipf_logits(cfg.n_items, cfg.skew_alpha)
    key = jax.random.PRNGKey(seed + 1)
    for r in range(rounds):   # the key schedule of tpcc.run_mixed_rounds
        key, sub = jax.random.split(key)
        args = (cfg.n_threads, cfg.n_warehouses, cfg.n_items,
                cfg.customers_per_district, None, cfg.dist_degree, logits)
        on_chip = workload.gen_mixed(sub, *args)
        with jax.default_device(cpu):
            on_cpu = workload.gen_mixed(sub, *args)
        assert_identical(f"workload draws of round {r}", on_chip, on_cpu)


def phase_backends(clog: CompileLog) -> None:
    n_w, rounds, budget = PHASES["backends"]
    cfg = spec_config(n_w, rounds)
    cpu = jax.devices("cpu")[0]
    with Phase(clog, "backends", budget):
        same_draws(cfg, rounds, 0, cpu)
        _, st_chip, stats_chip = run_mix(cfg, rounds)
        with jax.default_device(cpu):
            _, st_cpu, stats_cpu = run_mix(cfg, rounds)
        if {x.platform for x in jax.tree.leaves(st_cpu)[0].devices()} \
                != {"cpu"}:
            raise AssertionError("the CPU run left the CPU")
        check_run(cfg, st_chip, stats_chip)
        assert_same_stats("chip vs cpu", stats_chip, stats_cpu)
        n = assert_identical("chip vs cpu", st_chip, st_cpu)
        log(f"[backends] {n_w} warehouses, {rounds} rounds: "
            f"{mix_line(stats_chip)}; workload draws equal on both "
            f"backends; final store bit-identical ({n} arrays) and run "
            f"statistics equal, {jax.devices()[0].platform} vs cpu")


def phase_four_chips(clog: CompileLog) -> None:
    n_w, rounds, budget = PHASES["four_chips"]
    mesh = compat.mesh(4)
    for layout, mode in (("warehouse_major", "aware"),
                         ("table_major", "oblivious")):
        cfg = spec_config(n_w, rounds, layout)
        home = locality.thread_homes(cfg.n_threads, cfg.n_warehouses)
        with Phase(clog, f"four_chips {layout} reference", budget) as ph:
            lay, st_ref, stats_ref = run_mix(cfg, rounds, home_w=home)
            ref_s = ph.split()
        oracle = PartitionedVectorOracle(cfg.n_threads, n_parts=4)
        with Phase(clog, f"four_chips {layout} mesh", budget) as ph:
            lay, st = tpcc.init_tpcc(cfg, oracle, jax.random.PRNGKey(0))
            engine = tpcc.make_mixed_engine(cfg, lay, mesh, "mem", oracle,
                                            shard_vector=True)
            st = tpcc.distribute_state(engine, st)
            st, stats = tpcc.run_mixed_rounds(
                cfg, lay, st, oracle, jax.random.PRNGKey(1), rounds,
                home_w=home, engine=engine, locality_mode=mode)
            jax.block_until_ready(st)
            mesh_s = ph.split()
        check_run(cfg, st, stats)
        assert_same_stats(f"{layout} mesh vs reference", stats, stats_ref)
        n = assert_identical(f"{layout} mesh vs reference", st, st_ref,
                             records=lay.catalog.total_records)
        log(f"[four_chips] {layout}/{mode}: bit-identical to the "
            f"single-shard reference ({n} arrays); {lay.catalog.total_records}"
            f" records, store {store_bytes(st)} B over 4 chips; local "
            f"fraction {stats.local_fraction:.4f}; reference "
            f"{ref_s[0]:.3f} s ({ref_s[1]:.3f} s compiling), mesh "
            f"{mesh_s[0]:.3f} s ({mesh_s[1]:.3f} s compiling); "
            f"{mix_line(stats)}")


def main() -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n")[0])
    ap.add_argument("--four-chips", action="store_true",
                    help="run only the four-chip mesh phase")
    args = ap.parse_args()
    devices = jax.devices()
    if devices[0].platform != "tpu":
        print(f"chip_smoke: no TPU (JAX's default backend is "
              f"{devices[0].platform}); nothing run", file=sys.stderr)
        return 2
    cache = compat.use_compile_cache()
    cached = len(os.listdir(cache)) if os.path.isdir(cache) else 0
    log(f"device: {devices[0].platform} {devices[0].device_kind} x "
        f"{len(devices)}; jax {jax.__version__}; compile cache {cache} "
        f"({cached} entries at start)")
    clog = CompileLog()
    t0 = time.perf_counter()
    if args.four_chips:
        phase_four_chips(clog)
    else:
        phase_spec(clog)
        phase_backends(clog)
    log(f"all phases passed in {time.perf_counter() - t0:.3f} s")
    print(json.dumps({"ok": True, "device": {
        "platform": devices[0].platform, "kind": devices[0].device_kind,
        "count": len(devices)}}), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
