"""The benchmark's only view into the engine.

Every name of the program that the benchmark uses is named here and
nowhere else: the configuration object, the loader, the mesh engine, the
driver ``tpcc.run_mixed_rounds`` and two of its internals.

``instrument`` patches those internals for the length of a run:

* ``tpcc._sub_rounds`` builds the five jitted sub-round programs anew on
  every driver call, so a second call would trace and lower them again.
  It is memoised by the identity of its arguments, so the measured call
  reuses the warm-up call's programs.
* ``tpcc._version_mover`` runs once at the end of every round, after the
  driver has read every sub-round's outcome back to the host. A wrapper
  takes one host timestamp there: the round clock.
* the read-only programs' answers (order-status payloads, stock-level
  counts) are kept, so they can be checked against the reference.
"""
from __future__ import annotations

import contextlib
import functools
import time
from typing import NamedTuple, Optional

import jax
import jax.numpy as jnp
import numpy as np
from jax.sharding import NamedSharding, PartitionSpec as P

from repro import compat
from repro.core.tsoracle import PartitionedVectorOracle, VectorOracle
from repro.db import tpcc, workload

# lowering to StableHLO and XLA's compile: either inside the window means a
# program was built there
COMPILE_EVENTS = ("/jax/core/compile/jaxpr_to_mlir_module_duration",
                  "/jax/core/compile/backend_compile_duration")
READ_ONLY = ("orderstatus", "stocklevel")


class CompileCounter:
    """Records JAX's lowering and compile events from the moment it is made:
    the program's name, and when the event ended and how long it took on
    the host clock. JAX cannot unregister a listener, so make one per
    process."""

    def __init__(self):
        self.names = []
        self.spans = []            # (perf_counter at the end, seconds)
        jax.monitoring.register_event_duration_secs_listener(self._on)

    def _on(self, event, secs, **kw):
        if event in COMPILE_EVENTS:
            self.names.append(kw.get("fun_name", "?"))
            self.spans.append((time.perf_counter(), secs))

    def seconds_between(self, t0: float, t1: float) -> float:
        """Seconds of lowering and compiling that ended in ``(t0, t1]``."""
        return sum(s for t, s in self.spans if t0 < t <= t1)


class Driver(NamedTuple):
    cfg: object
    lay: object
    oracle: object
    engine: object
    mix: dict
    skew: object
    stock_last_n: int
    locality_mode: Optional[str]

    @property
    def n_records(self) -> int:
        return self.lay.catalog.total_records


def build(sizes: dict, mix, n_chips: int, mesh=None) -> Driver:
    """The engine for one configuration (``sizes``) under one traffic mix:
    single-shard on one chip, or through the mesh executors over
    ``n_chips`` chips with the pool and the timestamp vector partitioned.
    ``mesh`` defaults to the first ``n_chips`` devices of the default
    backend (a described topology's mesh compiles without a chip)."""
    n_w = int(sizes["n_warehouses"])
    cfg = tpcc.TPCCConfig(
        n_warehouses=n_w, n_items=int(sizes["n_items"]),
        customers_per_district=int(sizes["customers_per_district"]),
        n_threads=mix.lanes_per_warehouse * n_w,
        orders_per_thread=int(sizes["orders_per_thread"]),
        n_old_versions=int(sizes["n_old_versions"]),
        n_overflow=int(sizes["n_overflow"]), layout=sizes["layout"],
        dist_degree=mix.dist_degree)
    lay = tpcc.make_layout(cfg)
    engine = None
    if n_chips > 1:
        oracle = PartitionedVectorOracle(cfg.n_threads, n_parts=n_chips)
        engine = tpcc.make_mixed_engine(
            cfg, lay, compat.mesh(n_chips) if mesh is None else mesh, "mem",
            oracle, shard_vector=True)
    else:
        oracle = VectorOracle(cfg.n_threads)
    return Driver(cfg=cfg, lay=lay, oracle=oracle, engine=engine,
                  mix=dict(mix.mix),
                  skew=workload.Skew(remote_frac=mix.remote_payment_frac),
                  stock_last_n=mix.stock_last_n,
                  locality_mode=mix.locality_mode)


def loader(drv: Driver):
    """The loader as one jitted program from the key."""
    return jax.jit(lambda key: tpcc.init_tpcc(drv.cfg, drv.oracle, key)[1])


def load(drv: Driver, key):
    """Load the tables from the key, placed as the run needs them. On a
    mesh the pool and the timestamp vector are partitioned and the rest is
    replicated, as the mesh programs leave it: every round of a later call
    then meets the placements the warm-up call compiled for."""
    st = loader(drv)(key)
    if drv.engine is not None:
        single = st
        parted = tpcc.distribute_state(drv.engine, st)
        rest = jax.device_put(
            st._replace(nam=st.nam._replace(table=None, oracle_state=None)),
            NamedSharding(drv.engine.mesh, P()))
        st = rest._replace(nam=rest.nam._replace(
            table=parted.nam.table, oracle_state=parted.nam.oracle_state))
        jax.block_until_ready(st)
        for x in jax.tree.leaves(single.nam.table):
            x.delete()
    return jax.block_until_ready(st)


def load_op_by_op(drv: Driver, key):
    """The engine's own loader, op by op, which the jitted one must equal."""
    return tpcc.init_tpcc(drv.cfg, drv.oracle, key)[1]


class Probe:
    """What the instrumented driver reports while it runs."""

    def __init__(self):
        self.round_times = []      # perf_counter at each round's mover call
        self.answers = {t: [] for t in READ_ONLY}
        self.annotate = False      # wrap program calls in trace spans

    def reset(self):
        self.round_times.clear()
        for v in self.answers.values():
            v.clear()


@contextlib.contextmanager
def instrument(probe: Probe, corrupt=None):
    """Patch the driver's internals as the module docstring says.

    ``corrupt`` (tests only) maps a program name (``neworder`` …
    ``stocklevel``, ``version_mover``) to a function called in the
    program's place with the program as its first argument: a planted
    fault."""
    real_sub_rounds, real_mover = tpcc._sub_rounds, tpcc._version_mover
    memo = {}

    def span(name, fn):
        def call(*a, **kw):
            if probe.annotate:
                with jax.profiler.TraceAnnotation(name):
                    return fn(*a, **kw)
            return fn(*a, **kw)
        return call

    def wrap(name, prog):
        if corrupt and name in corrupt:
            prog = functools.partial(corrupt[name], prog)

        def call(*a, **kw):
            out = prog(*a, **kw)
            if name in READ_ONLY:
                probe.answers[name].append((out.result, out.found))
            return out
        return span(f"bench.call.{name}", call)

    def sub_rounds(cfg, lay, oracle, engine, last_n):
        key = (id(cfg), id(lay), id(oracle), id(engine), last_n)
        if key not in memo:
            progs = real_sub_rounds(cfg, lay, oracle, engine, last_n)
            # the arguments are kept so their ids stay unique
            memo[key] = ({k: wrap(k, v) for k, v in progs.items()},
                         (cfg, lay, oracle, engine))
        return memo[key][0]

    real = real_mover
    if corrupt and "version_mover" in corrupt:
        real = functools.partial(corrupt["version_mover"], real_mover)

    def mover(*a, **kw):
        probe.round_times.append(time.perf_counter())
        return real(*a, **kw)

    tpcc._sub_rounds = sub_rounds
    tpcc._version_mover = span("bench.call.version_mover", mover)
    try:
        yield memo
    finally:
        tpcc._sub_rounds, tpcc._version_mover = real_sub_rounds, real_mover


def programs(drv: Driver) -> dict:
    """The driver's programs, as a call builds them: the five sub-round
    types and the version mover."""
    progs = tpcc._sub_rounds(drv.cfg, drv.lay, drv.oracle, drv.engine,
                             drv.stock_last_n)
    return {**progs, "version_mover": tpcc._version_mover}


def call(drv: Driver, st, key, n_rounds: int):
    """One call of the driver over ``n_rounds`` rounds."""
    return tpcc.run_mixed_rounds(
        drv.cfg, drv.lay, st, drv.oracle, key, n_rounds, mix=drv.mix,
        engine=drv.engine, locality_mode=drv.locality_mode,
        stock_last_n=drv.stock_last_n, skew=drv.skew)


def program_draws(drv: Driver, sub) -> dict:
    """What the driver's own generator draws for one round's key."""
    cfg = drv.cfg
    m = workload.gen_mixed(sub, cfg.n_threads, cfg.n_warehouses, cfg.n_items,
                           cfg.customers_per_district, None, cfg.dist_degree,
                           workload.zipf_logits(cfg.n_items, cfg.skew_alpha),
                           drv.mix, skew=drv.skew)
    return {"txn_type": m.txn_type,
            **{t: m._asdict()[t]._asdict() for t in workload.TXN_TYPES}}


INPUTS = {"neworder": workload.NewOrderInputs,
          "payment": workload.PaymentInputs,
          "orderstatus": workload.OrderStatusInputs,
          "delivery": workload.DeliveryInputs,
          "stocklevel": workload.StockLevelInputs}


def inputs_record(kind: str, fields: dict):
    """One transaction type's inputs as the engine's record of them."""
    return INPUTS[kind](**fields)


def insert_cursors(st) -> int:
    """The fullest insert extend of any lane: orders or history."""
    return max(int(jnp.max(st.nam.extends.cursor)),
               int(jnp.max(st.hist_cursor)))


def stats_dict(stats) -> dict:
    return {f: getattr(stats, f) for f in (
        "attempts", "commits", "retries", "snapshot_misses",
        "contention_aborts", "ovf_reads", "delivered", "ovf_peak")}


def to_host(drv: Driver, st) -> dict:
    """The store as host arrays, the mesh's padding records cut off."""
    R, T = drv.n_records, drv.cfg.n_threads
    tbl = st.nam.table
    out = {f: np.asarray(jax.device_get(getattr(tbl, f)))[:R]
           for f in tbl._fields}
    out["vec"] = np.asarray(jax.device_get(st.nam.oracle_state.vec))[:T]
    out["cursor"] = np.asarray(jax.device_get(st.nam.extends.cursor))[:, 0]
    out["hist_cursor"] = np.asarray(jax.device_get(st.hist_cursor))
    idx = st.order_index
    keys = np.concatenate([np.asarray(jax.device_get(idx.base_keys)),
                           np.asarray(jax.device_get(idx.delta_keys))])
    vals = np.concatenate([np.asarray(jax.device_get(idx.base_vals)),
                           np.asarray(jax.device_get(idx.delta_vals))])
    live = keys != np.uint32(0xFFFFFFFF)
    out["index"] = dict(zip(keys[live].tolist(), vals[live].tolist()))
    return out


def answers_to_host(probe: Probe) -> dict:
    return {t: [(np.asarray(jax.device_get(r)), np.asarray(
        jax.device_get(f))) for r, f in v] for t, v in probe.answers.items()}


def delete(st) -> None:
    for x in jax.tree.leaves(st):
        if isinstance(x, jax.Array):
            x.delete()
