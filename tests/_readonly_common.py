"""Shared pieces of the read-only lane-compaction checks: a small TPC-C
store with orders in it, the read-only inputs, and the comparison of a
compacted read-only program against its masked full-width round function.

Used by ``tests/test_tpcc.py`` on one shard and by
``tests/_readonly_chunks_check.py`` on a 4-device mesh.
"""
import jax
import numpy as np

from repro.core import mvcc, si
from repro.db import tpcc, workload

# 128 lanes give chunks of 4: counts of one, two and many chunks, with a
# partly filled last chunk among them
CFG = tpcc.TPCCConfig(n_warehouses=4, customers_per_district=8, n_items=64,
                      n_threads=128, orders_per_thread=8, dist_degree=100.0)
LAST_N = 20                      # stock-level over the spec's 20 orders
K = tpcc.readonly_lane_chunk(CFG.n_threads)
COUNTS = sorted({1, K, K + 1, 2 * K + 1, CFG.n_threads})
NAMES = ("stocklevel", "orderstatus")


def loaded(oracle, n_rounds: int = 3, seed: int = 0):
    """The layout and a single-shard store after ``n_rounds`` new-order
    rounds, so that every district has recent orders to read."""
    lay, st = tpcc.init_tpcc(CFG, oracle, jax.random.PRNGKey(seed))
    logits = workload.zipf_logits(CFG.n_items, CFG.skew_alpha)
    step = jax.jit(lambda st, inp, r: tpcc.neworder_round(
        CFG, lay, st, oracle, inp, round_no=r).state)
    key = jax.random.PRNGKey(seed + 1)
    for r in range(n_rounds):
        key, sub = jax.random.split(key)
        inp = workload.gen_neworder(sub, CFG.n_threads, CFG.n_warehouses,
                                    CFG.n_items, CFG.customers_per_district,
                                    None, CFG.dist_degree, logits)
        st = step(st, inp, r)
        st = st._replace(nam=st.nam._replace(
            table=mvcc.version_mover(st.nam.table)))
    return lay, st


def inputs(name: str, seed: int = 5):
    T, key = CFG.n_threads, jax.random.PRNGKey(seed)
    if name == "stocklevel":
        return workload.gen_stocklevel(key, T, CFG.n_warehouses)
    return workload.gen_orderstatus(key, T, CFG.n_warehouses,
                                    CFG.customers_per_district)


def active(n: int) -> np.ndarray:
    """``n`` lanes scattered over the batch."""
    act = np.zeros(CFG.n_threads, bool)
    act[np.random.default_rng(n).choice(CFG.n_threads, n,
                                        replace=False)] = True
    return act


def full_width(name: str, lay, oracle, engine=None):
    """The masked formulation over all lanes: the round function itself."""
    if name == "stocklevel":
        return jax.jit(lambda st, inp, act: tpcc.stocklevel_round(
            CFG, lay, st, oracle, inp, engine=engine, active=act,
            last_n=LAST_N))
    return jax.jit(lambda st, inp, act: tpcc.orderstatus_round(
        CFG, lay, st, oracle, inp, engine=engine, active=act))


def assert_same_answers(got, want, act: np.ndarray):
    """Active lanes carry the full-width values, inactive lanes nothing,
    and the op counts are equal."""
    got, want = jax.device_get(got), jax.device_get(want)
    np.testing.assert_array_equal(got.found[act], want.found[act])
    np.testing.assert_array_equal(got.result[act], want.result[act])
    np.testing.assert_array_equal(got.read_mask[act], want.read_mask[act])
    read = want.read_mask[act]
    np.testing.assert_array_equal(got.read_slots[act][read],
                                  want.read_slots[act][read])
    assert not got.found[~act].any()
    assert not got.read_mask[~act].any()
    for f in si.OpCounts._fields:
        assert float(getattr(got.ops, f)) == float(getattr(want.ops, f)), f
