"""TPC-C over the NAM store (paper §7 evaluation substrate).

Full five-transaction mix, vectorized: one *round* executes one transaction
per execution thread through the SI protocol (`core/si.py`). The standard
schema is kept (9 tables, secondary order index, 5..15 order lines); scale
knobs (#warehouses, #items, customers/district) shrink it to CPU-test size
without changing any access pattern.

Encodings: every column is an int32 word in a fixed-width payload (§5.1
fixed-length records; money in cents). Word maps are in the ``*_COL``
constants below. Inserts use the §5.3 extend allocator: each execution thread
owns a private extend per insert region, so inserts are conflict-free
installs (no CAS), exactly as a compute server writes into memory it
allocated. The contended hot spot is the district's ``d_next_o_id``, fought
over via header CAS — TPC-C's classic conflict, left fully intact.
"""
from __future__ import annotations

import dataclasses
import time
from typing import Callable, NamedTuple, Optional, Tuple

import jax
import jax.numpy as jnp

from repro import compat
from repro.checkpoint import snapshot

from repro.core import cas, gc as gc_ops, hashtable as ht, \
    header as hdr_ops, locality, mvcc, netmodel, rangeindex as ri, si, \
    store, wal
from repro.core.catalog import Catalog
from repro.core.si import TxnBatch
from repro.core.tsoracle import VectorOracle, VectorState
from repro.db import workload

WIDTH = 8          # unified payload width (int32 words)
MAX_OL = 15
DISTRICTS = 10

# column maps (int32 word index within the payload)
W_COL = {"tax": 0, "ytd": 1}
D_COL = {"tax": 0, "ytd": 1, "next_o_id": 2, "next_deliv": 3}
C_COL = {"balance": 0, "ytd_payment": 1, "payment_cnt": 2, "delivery_cnt": 3}
S_COL = {"quantity": 0, "ytd": 1, "order_cnt": 2, "remote_cnt": 3}
I_COL = {"price": 0, "im_id": 1}
O_COL = {"c_id": 0, "carrier": 1, "ol_cnt": 2, "entry_d": 3, "o_id": 4,
         "d_key": 5}
OL_COL = {"i_id": 0, "supply_w": 1, "quantity": 2, "amount": 3,
          "delivery_d": 4}
H_COL = {"amount": 0, "c_id": 1, "w_id": 2}

MAX_O_PER_DISTRICT = 1 << 14  # o_id key-space per district for index keys


@dataclasses.dataclass(frozen=True)
class TPCCConfig:
    n_warehouses: int = 4
    customers_per_district: int = 32
    n_items: int = 512
    n_threads: int = 16
    orders_per_thread: int = 128     # extend size for order inserts
    dist_degree: float = 10.0        # % distributed new-orders (paper knob)
    skew_alpha: Optional[float] = None
    n_old_versions: int = 2
    n_overflow: int = 2
    layout: str = "table_major"      # or "warehouse_major" (§7.3 locality)
    key_addressed: bool = False      # §5.2: resolve item/stock/customer
    #   reads through the hash index instead of analytic slots
    fused_commit: bool = False       # DESIGN.md §8: run the commit phases
    #   (validate/lock/install/make-visible/unlock) as one Pallas launch
    batched_probe: bool = False      # §8: resolve the whole read-set in one
    #   batched probe-kernel launch (both flags are access-path choices —
    #   bit-identical to the unfused protocol rendering)


class TPCCLayout(NamedTuple):
    """Slot layout of the unified pool.

    ``table_major`` (default) lays tables out back to back — record placement
    ignores warehouse boundaries, so range-partitioning the pool over memory
    servers scatters each warehouse: the locality-*oblivious* deployment.

    ``warehouse_major`` packs one contiguous *block* per warehouse holding
    its warehouse/district/customer/stock records, a read-only replica of the
    item table (the paper's "read-only tables can be replicated"), and the
    insert extends of the threads homed there. With ``n_warehouses`` a
    multiple of the shard count, whole warehouses land on single memory
    servers — the §7.3 locality-*aware* placement of Fig. 5.
    """
    catalog: Catalog
    order_base: int
    ol_base: int
    no_base: int
    hist_base: int
    mode: str = "table_major"
    block: int = 0       # block stride (warehouse_major only)
    d_off: int = 0       # offsets inside a warehouse block
    c_off: int = 0
    s_off: int = 0
    i_off: int = 0
    o_off: int = 0
    ol_off: int = 0
    no_off: int = 0
    h_off: int = 0
    tpw: int = 1         # execution threads homed per warehouse


class TPCCState(NamedTuple):
    nam: store.NAMStore
    order_index: ri.RangeIndex
    hist_cursor: jnp.ndarray    # int32 [n_threads]
    directory: Optional[ht.HashTable] = None   # §5.2 hash index over the
    #   item/stock/customer records (built iff cfg.key_addressed); static
    #   for the run — these tables are updated in place, never re-slotted


def make_layout(cfg: TPCCConfig) -> TPCCLayout:
    if cfg.layout == "warehouse_major":
        return _make_wh_layout(cfg)
    cat = Catalog(n_servers=cfg.n_warehouses)
    cat.create_table("warehouse", cfg.n_warehouses, WIDTH, 2)
    cat.create_table("district", cfg.n_warehouses * DISTRICTS, WIDTH, 4)
    cat.create_table("customer", cfg.n_warehouses * DISTRICTS
                     * cfg.customers_per_district, WIDTH, 4)
    cat.create_table("stock", cfg.n_warehouses * cfg.n_items, WIDTH, 4)
    cat.create_table("item", cfg.n_items, WIDTH, 2)
    n_orders = cfg.n_threads * cfg.orders_per_thread
    o = cat.create_table("orders", n_orders, WIDTH, 6)
    ol = cat.create_table("order_line", n_orders * MAX_OL, WIDTH, 5)
    no = cat.create_table("new_order", n_orders, WIDTH, 2)
    h = cat.create_table("history", n_orders, WIDTH, 3)
    return TPCCLayout(catalog=cat, order_base=o.base, ol_base=ol.base,
                      no_base=no.base, hist_base=h.base)


def _make_wh_layout(cfg: TPCCConfig) -> TPCCLayout:
    if cfg.n_threads % cfg.n_warehouses:
        raise ValueError("warehouse_major needs n_threads divisible by "
                         "n_warehouses (threads are homed per warehouse)")
    tpw = cfg.n_threads // cfg.n_warehouses
    opt = cfg.orders_per_thread
    d_off = 1
    c_off = d_off + DISTRICTS
    s_off = c_off + DISTRICTS * cfg.customers_per_district
    i_off = s_off + cfg.n_items
    o_off = i_off + cfg.n_items
    ol_off = o_off + tpw * opt
    no_off = ol_off + tpw * opt * MAX_OL
    h_off = no_off + tpw * opt
    block = h_off + tpw * opt
    cat = Catalog(n_servers=cfg.n_warehouses)
    cat.create_table("wh_block", cfg.n_warehouses * block, WIDTH, 6)
    return TPCCLayout(catalog=cat, order_base=-1, ol_base=-1, no_base=-1,
                      hist_base=-1, mode="warehouse_major", block=block,
                      d_off=d_off, c_off=c_off, s_off=s_off, i_off=i_off,
                      o_off=o_off, ol_off=ol_off, no_off=no_off, h_off=h_off,
                      tpw=tpw)


# ------------------------------------------------------------- slot math ----
def w_slot(lay, w):
    if lay.mode == "warehouse_major":
        return jnp.asarray(w, jnp.int32) * lay.block
    return lay.catalog["warehouse"].base + w


def d_slot(lay, w, d):
    if lay.mode == "warehouse_major":
        return jnp.asarray(w, jnp.int32) * lay.block + lay.d_off + d
    return lay.catalog["district"].base + w * DISTRICTS + d


def c_slot(lay, cfg, w, d, c):
    if lay.mode == "warehouse_major":
        return jnp.asarray(w, jnp.int32) * lay.block + lay.c_off \
            + d * cfg.customers_per_district + c
    return lay.catalog["customer"].base \
        + (w * DISTRICTS + d) * cfg.customers_per_district + c


def s_slot(lay, cfg, w, i):
    if lay.mode == "warehouse_major":
        return jnp.asarray(w, jnp.int32) * lay.block + lay.s_off + i
    return lay.catalog["stock"].base + w * cfg.n_items + i


def i_slot(lay, i, w=None):
    """Item read. Warehouse-major reads the executing warehouse's local
    replica (read-only tables are replicated, §7.3), so ``w`` is required."""
    if lay.mode == "warehouse_major":
        assert w is not None, "warehouse_major item reads need the home w"
        return jnp.asarray(w, jnp.int32) * lay.block + lay.i_off + i
    return lay.catalog["item"].base + i


def _tid_home(cfg, tid):
    """Home warehouse + within-warehouse rank of an execution thread."""
    tid = jnp.asarray(tid, jnp.int32)
    return tid % cfg.n_warehouses, tid // cfg.n_warehouses


def o_slot_ext(lay, cfg, tid, local):
    """Order-insert extend slot of thread ``tid`` at cursor ``local``."""
    if lay.mode == "warehouse_major":
        w, r = _tid_home(cfg, tid)
        return w * lay.block + lay.o_off + r * cfg.orders_per_thread + local
    return lay.order_base + jnp.asarray(tid, jnp.int32) \
        * cfg.orders_per_thread + local


def no_slot_ext(lay, cfg, tid, local):
    if lay.mode == "warehouse_major":
        w, r = _tid_home(cfg, tid)
        return w * lay.block + lay.no_off + r * cfg.orders_per_thread + local
    return lay.no_base + jnp.asarray(tid, jnp.int32) \
        * cfg.orders_per_thread + local


def h_slot_ext(lay, cfg, tid, local):
    if lay.mode == "warehouse_major":
        w, r = _tid_home(cfg, tid)
        return w * lay.block + lay.h_off + r * cfg.orders_per_thread + local
    return lay.hist_base + jnp.asarray(tid, jnp.int32) \
        * cfg.orders_per_thread + local


def ol_slots_of_order(lay, cfg, oslot):
    """First order-line slot of the order stored at ``oslot`` (an order's
    lines are contiguous: +0 … +MAX_OL-1)."""
    oslot = jnp.asarray(oslot, jnp.int32)
    if lay.mode == "warehouse_major":
        blk = oslot // lay.block
        k = oslot - blk * lay.block - lay.o_off
        return blk * lay.block + lay.ol_off + k * MAX_OL
    return lay.ol_base + (oslot - lay.order_base) * MAX_OL


def order_key(w, d, o_id):
    return ((w * DISTRICTS + d) * MAX_O_PER_DISTRICT + o_id).astype(jnp.uint32)


# ------------------------------------------------------- §6.2 WAL journal ----
# Sub-round sequence numbers within one mixed driver round: the journal
# stamps each entry (round, seq) so replay can tie-break equal-T entries in
# the engine's execution order (the write sub-rounds run in this order and
# each insert group lands right after its sub-round's SI commit).
_JSEQ_NEWORDER, _JSEQ_NEWORDER_INS, _JSEQ_PAYMENT, _JSEQ_PAYMENT_INS, \
    _JSEQ_DELIVERY = range(5)
JOURNAL_WS = 2 + MAX_OL   # widest logged statement: the new-order insert
#   group (order + new-order + up to 15 order lines in one entry)
JOURNAL_APPENDS_PER_ROUND = 5   # every *executed* write sub-round appends
#   one entry per thread (inactive lanes log an empty write mask)


def make_journal(cfg: TPCCConfig, oracle: VectorOracle, *,
                 capacity_rounds: int, n_replicas: int = 2) -> wal.Journal:
    """A §6.2 journal sized for the mixed driver.

    Each driver round appends at most :data:`JOURNAL_APPENDS_PER_ROUND`
    entries per thread, so the ring must cover the checkpoint interval in
    rounds (plus slack for in-flight intents at a crash). With a distributed
    engine pass ``n_replicas = engine.n_shards`` and place the replica axis
    across the memory servers via :func:`repro.core.store.shard_journal`.
    """
    return wal.init_journal(
        cfg.n_threads, JOURNAL_APPENDS_PER_ROUND * capacity_rounds,
        oracle.n_slots, JOURNAL_WS, WIDTH, n_replicas=n_replicas)


# --------------------------------------------------- §5.2 hash directory ----
# Key encodings for the hash index: per-table tag in the top bits, dense
# rank below. The directory's key space is independent of the range index's.
DIR_TAG_STOCK = jnp.uint32(1 << 29)
DIR_TAG_ITEM = jnp.uint32(2 << 29)
DIR_TAG_CUSTOMER = jnp.uint32(3 << 29)
DIR_PROBES = 32   # shared by build + every lookup (build guarantees
#                   placement distance < DIR_PROBES, see store.build_directory)


def stock_key(cfg: TPCCConfig, w, i):
    return DIR_TAG_STOCK | (jnp.asarray(w, jnp.uint32) * cfg.n_items
                            + jnp.asarray(i, jnp.uint32))


def item_key(cfg: TPCCConfig, lay: TPCCLayout, w, i):
    """Item lookup key. The warehouse-major layout replicates the read-only
    item table per warehouse (§7.3) — the key names the executing
    warehouse's replica; table-major has one item table, keyed by item."""
    if lay.mode == "warehouse_major":
        return DIR_TAG_ITEM | (jnp.asarray(w, jnp.uint32) * cfg.n_items
                               + jnp.asarray(i, jnp.uint32))
    return DIR_TAG_ITEM | jnp.asarray(i, jnp.uint32)


def customer_key(cfg: TPCCConfig, w, d, c):
    rank = (jnp.asarray(w, jnp.uint32) * DISTRICTS + jnp.asarray(d, jnp.uint32)) \
        * cfg.customers_per_district + jnp.asarray(c, jnp.uint32)
    return DIR_TAG_CUSTOMER | rank


def directory_buckets(cfg: TPCCConfig, lay: TPCCLayout) -> int:
    """Bucket-array size of the TPC-C hash index: next power of two ≥ 2× the
    entry count (load factor ≤ 0.5, Pilaf's regime) — a power of two also
    divides evenly over any power-of-two memory-server mesh."""
    items = cfg.n_warehouses * cfg.n_items \
        if lay.mode == "warehouse_major" else cfg.n_items
    entries = items + cfg.n_warehouses * cfg.n_items \
        + cfg.n_warehouses * DISTRICTS * cfg.customers_per_district
    b = 64
    while b < 2 * entries:
        b *= 2
    return b


def build_tpcc_directory(cfg: TPCCConfig, lay: TPCCLayout) -> ht.HashTable:
    """Load the §5.2 hash index over every item/stock/customer record.

    Built once at load time from the same slot math the loader uses; from
    then on the key-addressed read path resolves slots exclusively through
    it (the slot functions remain the locality-accounting oracle)."""
    W_, I, D, C = cfg.n_warehouses, cfg.n_items, DISTRICTS, \
        cfg.customers_per_district
    wi_w = jnp.repeat(jnp.arange(W_), I)
    wi_i = jnp.tile(jnp.arange(I), W_)
    keys = [stock_key(cfg, wi_w, wi_i)]
    slots = [s_slot(lay, cfg, wi_w, wi_i)]
    if lay.mode == "warehouse_major":
        keys.append(item_key(cfg, lay, wi_w, wi_i))
        slots.append(i_slot(lay, wi_i, wi_w))
    else:
        keys.append(item_key(cfg, lay, 0, jnp.arange(I)))
        slots.append(i_slot(lay, jnp.arange(I)))
    cw = jnp.repeat(jnp.arange(W_), D * C)
    cd = jnp.tile(jnp.repeat(jnp.arange(D), C), W_)
    cc = jnp.tile(jnp.arange(C), W_ * D)
    keys.append(customer_key(cfg, cw, cd, cc))
    slots.append(c_slot(lay, cfg, cw, cd, cc))
    return store.build_directory(
        jnp.concatenate(keys), jnp.concatenate([jnp.asarray(s, jnp.int32)
                                                for s in slots]),
        directory_buckets(cfg, lay), max_probes=DIR_PROBES)


# ---------------------------------------------------------------- loader ----
def init_tpcc(cfg: TPCCConfig, oracle: VectorOracle,
              key: jax.Array) -> Tuple[TPCCLayout, TPCCState]:
    lay = make_layout(cfg)
    nam = store.init_store(lay.catalog, oracle, n_old=cfg.n_old_versions,
                           n_overflow=cfg.n_overflow, width=WIDTH,
                           n_insert_regions=1)
    tbl = nam.table
    ks = jax.random.split(key, 6)
    data = tbl.cur_data
    W, I, D = cfg.n_warehouses, cfg.n_items, DISTRICTS

    data = data.at[w_slot(lay, jnp.arange(W)), W_COL["tax"]].set(
        jax.random.randint(ks[0], (W,), 0, 2000))
    dsl = d_slot(lay, jnp.repeat(jnp.arange(W), D), jnp.tile(jnp.arange(D), W))
    data = data.at[dsl, D_COL["tax"]].set(
        jax.random.randint(ks[1], (W * D,), 0, 2000))
    # d_next_o_id starts at 0; next_deliv at 0
    price = jax.random.randint(ks[2], (I,), 100, 10000)
    if lay.mode == "warehouse_major":   # identical read-only replica per wh
        isl = i_slot(lay, jnp.arange(I)[None, :], jnp.arange(W)[:, None])
        data = data.at[isl, I_COL["price"]].set(
            jnp.broadcast_to(price, (W, I)))
    else:
        data = data.at[i_slot(lay, jnp.arange(I)), I_COL["price"]].set(price)
    ssl = s_slot(lay, cfg, jnp.repeat(jnp.arange(W), I),
                 jnp.tile(jnp.arange(I), W))
    data = data.at[ssl, S_COL["quantity"]].set(
        jax.random.randint(ks[3], (W * I,), 10, 101))
    tbl = tbl._replace(cur_data=data)
    nam = nam._replace(table=tbl)

    # insert regions start non-existent (deleted current versions)
    if lay.mode == "warehouse_major":
        tids = jnp.arange(cfg.n_threads, dtype=jnp.int32)[:, None]
        locs = jnp.arange(cfg.orders_per_thread, dtype=jnp.int32)[None, :]
        osl = o_slot_ext(lay, cfg, tids, locs)
        olsl = (ol_slots_of_order(lay, cfg, osl)[:, :, None]
                + jnp.arange(MAX_OL)).reshape(-1)
        nam = store.mark_slots_deleted(nam, jnp.concatenate(
            [osl.reshape(-1), no_slot_ext(lay, cfg, tids, locs).reshape(-1),
             h_slot_ext(lay, cfg, tids, locs).reshape(-1), olsl]))
    else:
        for name in ("orders", "order_line", "new_order", "history"):
            spec = lay.catalog[name]
            nam = store.mark_region_deleted(nam, spec.base, spec.count)

    idx = ri.build(jnp.zeros((0,), jnp.uint32), jnp.zeros((0,), jnp.int32),
                   capacity=cfg.n_threads * cfg.orders_per_thread,
                   delta_capacity=4 * cfg.n_threads)
    directory = build_tpcc_directory(cfg, lay) if cfg.key_addressed else None
    return lay, TPCCState(nam=nam, order_index=idx,
                          hist_cursor=jnp.zeros((cfg.n_threads,), jnp.int32),
                          directory=directory)


def _insert_install(tbl, slots, tid_slots, cts, data, mask):
    """Conflict-free install into thread-private extends (inserts)."""
    h = hdr_ops.pack(tid_slots.astype(jnp.uint32), cts)
    out = mvcc.install(tbl, slots, h, data, mask)
    return out.table


def _n_active(batch: TxnBatch, active):
    """Transactions actually executed this (sub-)round — op accounting."""
    if active is None:
        return jnp.asarray(batch.tid.shape[0])
    return jnp.sum(active.astype(jnp.int32))


def _active_or_ones(T: int, active):
    return jnp.ones((T,), bool) if active is None else active


def _n_probes(batch: TxnBatch, keyed, active):
    """§5.2 index probes issued this round — the identical expression
    :func:`si.run_round` evaluates, so both paths charge the same."""
    if keyed is None:
        return 0
    act = _active_or_ones(batch.tid.shape[0], active)
    return jnp.sum(keyed.mask & batch.read_mask & act[:, None])


def _dist_ops(oracle, batch: TxnBatch, out, tbl, active,
              keyed=None) -> si.OpCounts:
    """Op accounting of one distributed round — the exact
    :func:`si.count_ops` call the single-shard path makes, shared by every
    ``*_round_distributed`` so the accounting cannot diverge per type."""
    return si.count_ops(oracle, batch, out.txn_found, out.from_current,
                        out.n_installs, out.n_releases,
                        jnp.sum(out.committed), tbl.payload_width,
                        n_txns=_n_active(batch, active), active=active,
                        n_index_probes=_n_probes(batch, keyed, active))


def _dist_vis(batch: TxnBatch, out, active) -> si.VisStats:
    """Visibility accounting of one distributed round — the exact
    :func:`si.vis_stats` fold the single-shard path makes (TPC-C batches
    pre-mask their read masks with ``active``, so the two are identical)."""
    return si.vis_stats(batch.read_mask, out.read_found, out.from_current,
                        out.from_ovf, active)


# ------------------------------------------------------------- new-order ----
class NewOrderResult(NamedTuple):
    state: TPCCState
    committed: jnp.ndarray
    snapshot_miss: jnp.ndarray
    o_id: jnp.ndarray
    ops: si.OpCounts
    batch: TxnBatch             # the round's requests (locality accounting)
    vis: si.VisStats            # §5.3 visibility telemetry
    journal: Optional[wal.Journal] = None   # §6.2 — set iff one was passed


def _neworder_batch(cfg: TPCCConfig, lay: TPCCLayout,
                    inp: workload.NewOrderInputs,
                    active: Optional[jnp.ndarray] = None):
    """Read-set (RS=33): [district, warehouse, customer, item*15, stock*15];
    write-set (WS=16): district (d_next_o_id++) + up to 15 stocks.

    ``active`` masks the threads running a new-order this round (mixed-mix
    sub-round); inactive threads get all-false read/write masks.

    Returns ``(batch, keyed)``: with ``cfg.key_addressed`` the item and
    stock reads are annotated with their §5.2 index keys
    (:class:`~repro.core.si.KeyedReads`) and the engine resolves those slots
    through the hash directory — ``batch.read_slots`` still carries the
    analytic slots for the key lanes, but only as the locality-accounting
    oracle: the protocol never reads them where ``keyed.mask`` is set.
    ``keyed`` is None in slot-addressed mode."""
    T = inp.w_id.shape[0]
    act = _active_or_ones(T, active)
    line = jnp.arange(MAX_OL)[None, :]
    line_mask = (line < inp.ol_cnt[:, None]) & act[:, None]
    dsl = d_slot(lay, inp.w_id, inp.d_id)
    wsl = w_slot(lay, inp.w_id)
    csl = c_slot(lay, cfg, inp.w_id, inp.d_id, inp.c_id)
    isl = i_slot(lay, inp.item_ids, inp.w_id[:, None])
    ssl = s_slot(lay, cfg, inp.supply_w, inp.item_ids)
    read_slots = jnp.concatenate(
        [dsl[:, None], wsl[:, None], csl[:, None], isl, ssl], axis=1)
    read_mask = jnp.concatenate(
        [jnp.broadcast_to(act[:, None], (T, 3)), line_mask, line_mask],
        axis=1)
    write_ref = jnp.concatenate(
        [jnp.zeros((T, 1), jnp.int32), 18 + jnp.broadcast_to(line, (T, MAX_OL))],
        axis=1)
    write_mask = jnp.concatenate([act[:, None], line_mask], axis=1)
    batch = TxnBatch(tid=jnp.arange(T, dtype=jnp.int32),
                     read_slots=read_slots, read_mask=read_mask,
                     write_ref=write_ref, write_mask=write_mask)
    keyed = None
    if cfg.key_addressed:
        ikeys = item_key(cfg, lay, inp.w_id[:, None], inp.item_ids)
        skeys = stock_key(cfg, inp.supply_w, inp.item_ids)
        keyed = si.KeyedReads(
            keys=jnp.concatenate(
                [jnp.zeros((T, 3), jnp.uint32), ikeys, skeys], axis=1),
            mask=jnp.concatenate(
                [jnp.zeros((T, 3), bool), line_mask, line_mask], axis=1))
    return batch, keyed


def _neworder_new_data(rd, inp: workload.NewOrderInputs):
    """The new-order write-set: bump d_next_o_id, restock + count stocks."""
    dist = rd[:, 0, :]
    dist = dist.at[:, D_COL["next_o_id"]].add(1)
    stocks = rd[:, 18:, :]
    q = stocks[:, :, S_COL["quantity"]]
    newq = jnp.where(q - inp.qty >= 10, q - inp.qty, q - inp.qty + 91)
    stocks = stocks.at[:, :, S_COL["quantity"]].set(newq)
    stocks = stocks.at[:, :, S_COL["ytd"]].add(inp.qty)
    stocks = stocks.at[:, :, S_COL["order_cnt"]].add(1)
    stocks = stocks.at[:, :, S_COL["remote_cnt"]].add(
        inp.is_remote.astype(jnp.int32))
    return jnp.concatenate([dist[:, None, :], stocks], axis=1)


def _neworder_inserts(cfg: TPCCConfig, lay: TPCCLayout, st: TPCCState,
                      oracle: VectorOracle, tbl, vec, committed, read_data,
                      inp: workload.NewOrderInputs, round_no, journal=None):
    """Inserts, within the transaction boundary (§6.1): order, new-order and
    order-lines go to thread-private extends (conflict-free one-sided
    installs, §5.3) plus the order secondary index. Shared verbatim by the
    single-shard and the distributed path — on a sharded table the scatters
    land on the owning shard, the compute server having computed the remote
    extend address itself."""
    T = inp.w_id.shape[0]
    line = jnp.arange(MAX_OL)[None, :]
    line_mask = line < inp.ol_cnt[:, None]
    tids = jnp.arange(T, dtype=jnp.int32)
    o_id = read_data[:, 0, D_COL["next_o_id"]]
    slot_ids = oracle.slot_of_thread(tids)
    cts = vec[slot_ids]                          # committed threads' new cts
    cur = st.nam.extends.cursor[:, 0]
    local = jnp.clip(cur, 0, cfg.orders_per_thread - 1)
    oslot = o_slot_ext(lay, cfg, tids, local)
    noslot = no_slot_ext(lay, cfg, tids, local)
    olslot = ol_slots_of_order(lay, cfg, oslot)[:, None] + line
    can_insert = committed & (cur < cfg.orders_per_thread)

    odata = jnp.zeros((T, WIDTH), jnp.int32)
    odata = odata.at[:, O_COL["c_id"]].set(inp.c_id)
    odata = odata.at[:, O_COL["carrier"]].set(-1)
    odata = odata.at[:, O_COL["ol_cnt"]].set(inp.ol_cnt)
    odata = odata.at[:, O_COL["entry_d"]].set(round_no)
    odata = odata.at[:, O_COL["o_id"]].set(o_id)
    odata = odata.at[:, O_COL["d_key"]].set(inp.w_id * DISTRICTS + inp.d_id)
    tbl = _insert_install(tbl, oslot, slot_ids, cts, odata, can_insert)

    nodata = jnp.zeros((T, WIDTH), jnp.int32)
    nodata = nodata.at[:, 0].set(o_id)
    nodata = nodata.at[:, 1].set(inp.w_id * DISTRICTS + inp.d_id)
    tbl = _insert_install(tbl, noslot, slot_ids, cts, nodata, can_insert)

    price = read_data[:, 3:18, I_COL["price"]]
    oldata = jnp.zeros((T, MAX_OL, WIDTH), jnp.int32)
    oldata = oldata.at[:, :, OL_COL["i_id"]].set(inp.item_ids)
    oldata = oldata.at[:, :, OL_COL["supply_w"]].set(inp.supply_w)
    oldata = oldata.at[:, :, OL_COL["quantity"]].set(inp.qty)
    oldata = oldata.at[:, :, OL_COL["amount"]].set(price * inp.qty)
    oldata = oldata.at[:, :, OL_COL["delivery_d"]].set(-1)
    tbl = _insert_install(
        tbl, olslot.reshape(-1),
        jnp.broadcast_to(slot_ids[:, None], (T, MAX_OL)).reshape(-1),
        jnp.broadcast_to(cts[:, None], (T, MAX_OL)).reshape(-1),
        oldata.reshape(-1, WIDTH),
        (can_insert[:, None] & line_mask).reshape(-1))

    if journal is not None:
        # one combined ⟨T, S⟩ entry for the whole insert group: the slots are
        # disjoint (thread-private extends), so replaying it as one batched
        # install is bit-identical to the three sequential installs above.
        # T is the *post-sub-round* vector: the inserts carry the sub-round's
        # commit timestamps, so they replay right after it (tie broken by
        # seq) and before any later sub-round that could observe them.
        jslots = jnp.concatenate([oslot[:, None], noslot[:, None], olslot],
                                 axis=1)
        jhdr = jnp.broadcast_to(
            hdr_ops.pack(slot_ids.astype(jnp.uint32), cts)[:, None, :],
            (T, 2 + MAX_OL, 2))
        jdata = jnp.concatenate(
            [odata[:, None, :], nodata[:, None, :], oldata], axis=1)
        jmask = jnp.concatenate(
            [can_insert[:, None], can_insert[:, None],
             can_insert[:, None] & line_mask], axis=1)
        journal = wal.append_intent(
            journal, tids, vec[:journal.ts_vec.shape[-1]],
            *wal.pad_writes(journal, jslots, jhdr, jdata, jmask),
            round_no=round_no, seq=_JSEQ_NEWORDER_INS)
        journal = wal.append_outcome(journal, tids, can_insert)

    okey = order_key(inp.w_id, inp.d_id, o_id)
    idx = ri.insert(st.order_index, okey, oslot, mask=can_insert)
    cursor = st.nam.extends.cursor.at[:, 0].add(can_insert.astype(jnp.int32))
    return tbl, idx, store.ExtendState(cursor=cursor), o_id, journal


def neworder_round(cfg: TPCCConfig, lay: TPCCLayout, st: TPCCState,
                   oracle: VectorOracle, inp: workload.NewOrderInputs,
                   rts_vec=None, round_no=0, active=None,
                   journal=None) -> NewOrderResult:
    """One vectorized round of new-order transactions through SI
    (single-shard reference path)."""
    batch, keyed = _neworder_batch(cfg, lay, inp, active)
    out = si.run_round(st.nam.table, oracle, st.nam.oracle_state, batch,
                       lambda rh, rd, vec: _neworder_new_data(rd, inp),
                       rts_vec=rts_vec, active=active,
                       directory=st.directory if keyed is not None else None,
                       keyed=keyed, dir_max_probes=DIR_PROBES,
                       journal=journal, journal_round=round_no,
                       journal_seq=_JSEQ_NEWORDER,
                       fused_commit=cfg.fused_commit,
                       batched_probe=cfg.batched_probe)
    tbl, idx, extends, o_id, journal = _neworder_inserts(
        cfg, lay, st, oracle, out.table, out.oracle_state.vec, out.committed,
        out.read_data, inp, round_no, journal=out.journal)
    nam = st.nam._replace(table=tbl, oracle_state=out.oracle_state,
                          extends=extends)
    return NewOrderResult(
        state=st._replace(nam=nam, order_index=idx),
        committed=out.committed, snapshot_miss=out.snapshot_miss, o_id=o_id,
        ops=out.ops, batch=batch, vis=out.vis, journal=journal)


# ------------------------------------------- new-order over the NAM mesh ----
class DistEngine(NamedTuple):
    """A built TPC-C executor over a simulated memory-server mesh.

    ``round_fn`` is the jitted :func:`repro.core.store.distributed_round`
    executor for the new-order transaction logic; the record pool (and, when
    ``shard_vector``, the timestamp vector) lives range-partitioned over
    ``n_shards`` devices, each one memory server.
    """
    round_fn: Callable
    mesh: object
    axis: str
    n_shards: int
    shard_records: int
    shard_vector: bool
    gc_fn: Optional[Callable] = None   # per-shard §5.3 GC sweep
    #   (store.distributed_gc_round executor; drivers call it on their
    #   gc_interval schedule with store.init_shard_logs state)
    n_dir_buckets: int = 0             # §5.2 partitioned hash index size
    #   (0 = slot-addressed engine; >0 = round_fn takes directory/read_keys)
    with_journal: bool = False         # §6.2 WAL: round executors take a
    #   journal (replica axis across the memory servers) and return it

    @property
    def placement(self) -> locality.Placement:
        return locality.Placement(n_servers=self.n_shards,
                                  shard_records=self.shard_records)


def make_distributed_engine(cfg: TPCCConfig, lay: TPCCLayout, mesh, axis: str,
                            oracle: VectorOracle, *,
                            shard_vector: bool = False,
                            with_journal: bool = False) -> DistEngine:
    n_shards = mesh.shape[axis]
    shard_records = -(-lay.catalog.total_records // n_shards)
    n_dir = directory_buckets(cfg, lay) if cfg.key_addressed else 0
    round_fn, _ = store.distributed_round(
        mesh, axis, oracle,
        lambda rh, rd, vec, aux: _neworder_new_data(rd, aux),
        shard_records, shard_vector=shard_vector, n_dir_buckets=n_dir,
        dir_max_probes=DIR_PROBES, with_journal=with_journal,
        fused_commit=cfg.fused_commit, batched_probe=cfg.batched_probe)
    gc_fn = store.distributed_gc_round(mesh, axis, shard_vector=shard_vector,
                                       n_vec_slots=oracle.n_slots)
    return DistEngine(round_fn=round_fn, mesh=mesh, axis=axis,
                      n_shards=n_shards, shard_records=shard_records,
                      shard_vector=shard_vector, gc_fn=gc_fn,
                      n_dir_buckets=n_dir, with_journal=with_journal)


def distribute_state(engine: DistEngine, st: TPCCState) -> TPCCState:
    """Pad + range-partition the record pool (and optionally T_R, and the
    §5.2 hash index's bucket array) over the mesh: the loaded single-host
    state becomes the NAM deployment."""
    tbl, _ = store.pad_table(st.nam.table, engine.n_shards)
    tbl = store.shard_table(engine.mesh, engine.axis, tbl)
    vec = st.nam.oracle_state.vec
    if engine.shard_vector:
        vec = store.shard_vector(engine.mesh, engine.axis, vec)
    directory = st.directory
    if directory is not None and engine.n_dir_buckets:
        directory = store.shard_directory(engine.mesh, engine.axis, directory)
    return st._replace(nam=st.nam._replace(
        table=tbl, oracle_state=VectorState(vec=vec)), directory=directory)


class MixedEngine(NamedTuple):
    """Per-type executors for the full TPC-C mix over the memory-server mesh.

    Composes the new-order :class:`DistEngine` (``base``) with one
    :func:`repro.core.store.distributed_round` executor per additional
    *write* transaction type (their transaction logic differs, the protocol
    does not), plus one :func:`repro.core.store.distributed_readonly_round`
    executor shared by the read-only types (orderstatus, stocklevel), whose
    one-sided snapshot reads hit the sharded pool without any validate or
    install phase. Placement fields delegate to ``base``, so the engine
    drops into :func:`neworder_round_distributed` / ``distribute_state``
    unchanged.
    """
    base: DistEngine
    payment_fn: Callable
    delivery_fn: Callable
    readonly_fn: Callable

    @property
    def round_fn(self) -> Callable:
        return self.base.round_fn

    @property
    def mesh(self):
        return self.base.mesh

    @property
    def axis(self) -> str:
        return self.base.axis

    @property
    def n_shards(self) -> int:
        return self.base.n_shards

    @property
    def shard_records(self) -> int:
        return self.base.shard_records

    @property
    def shard_vector(self) -> bool:
        return self.base.shard_vector

    @property
    def gc_fn(self) -> Callable:
        return self.base.gc_fn

    @property
    def n_dir_buckets(self) -> int:
        return self.base.n_dir_buckets

    @property
    def with_journal(self) -> bool:
        return self.base.with_journal

    @property
    def placement(self) -> locality.Placement:
        return self.base.placement


def make_mixed_engine(cfg: TPCCConfig, lay: TPCCLayout, mesh, axis: str,
                      oracle: VectorOracle, *,
                      shard_vector: bool = False,
                      with_journal: bool = False) -> MixedEngine:
    """Build the five-transaction mix's executors over the mesh (the
    new-order executor is :func:`make_distributed_engine`'s, reused)."""
    base = make_distributed_engine(cfg, lay, mesh, axis, oracle,
                                   shard_vector=shard_vector,
                                   with_journal=with_journal)
    pay_fn, _ = store.distributed_round(
        mesh, axis, oracle,
        lambda rh, rd, vec, aux: _payment_new_data(rd, aux),
        base.shard_records, shard_vector=shard_vector,
        with_journal=with_journal,
        fused_commit=cfg.fused_commit, batched_probe=cfg.batched_probe)
    del_fn, _ = store.distributed_round(
        mesh, axis, oracle,
        lambda rh, rd, vec, aux: _delivery_new_data(rd, aux),
        base.shard_records, shard_vector=shard_vector,
        with_journal=with_journal,
        fused_commit=cfg.fused_commit, batched_probe=cfg.batched_probe)
    ro_fn = store.distributed_readonly_round(
        mesh, axis, base.shard_records, shard_vector=shard_vector,
        n_dir_buckets=base.n_dir_buckets, dir_max_probes=DIR_PROBES)
    return MixedEngine(base=base, payment_fn=pay_fn, delivery_fn=del_fn,
                       readonly_fn=ro_fn)


def neworder_round_distributed(cfg: TPCCConfig, lay: TPCCLayout,
                               st: TPCCState, oracle: VectorOracle,
                               engine: DistEngine,
                               inp: workload.NewOrderInputs,
                               round_no=0, active=None,
                               journal=None) -> NewOrderResult:
    """One new-order round through :func:`store.distributed_round` — the
    multi-memory-server rendering of :func:`neworder_round`, bit-identical
    to it (tests/test_distributed_equiv.py)."""
    batch, keyed = _neworder_batch(cfg, lay, inp, active)
    jkw = dict(journal=journal, round_no=round_no,
               seq=_JSEQ_NEWORDER) if journal is not None else {}
    if keyed is not None:
        res = engine.round_fn(
            st.nam.table, st.nam.oracle_state.vec, batch, inp, active,
            directory=st.directory, read_keys=keyed.keys,
            key_mask=keyed.mask, **jkw)
    else:
        res = engine.round_fn(st.nam.table, st.nam.oracle_state.vec,
                              batch, inp, active, **jkw)
    tbl, vec, out = res[:3]
    journal = res[3] if journal is not None else None
    ops = _dist_ops(oracle, batch, out, tbl, active, keyed)
    tbl, idx, extends, o_id, journal = _neworder_inserts(
        cfg, lay, st, oracle, tbl, vec, out.committed, out.read_data, inp,
        round_no, journal=journal)
    nam = st.nam._replace(table=tbl, oracle_state=VectorState(vec=vec),
                          extends=extends)
    return NewOrderResult(
        state=st._replace(nam=nam, order_index=idx),
        committed=out.committed, snapshot_miss=out.snapshot_miss, o_id=o_id,
        ops=ops, batch=batch, vis=_dist_vis(batch, out, active),
        journal=journal)


# ------------------------------------------------------ sustained-run GC ----
def _gc_init(oracle, engine, gc_interval: int, gc_snapshots: int):
    """GC-thread state for a driver run: one §5.3 snapshot log (single-shard
    reference) or one per memory-server shard (mesh)."""
    if gc_interval <= 0:
        return None
    if engine is None:
        return gc_ops.init_log(gc_snapshots, oracle.n_slots)
    return store.init_shard_logs(engine.n_shards, gc_snapshots,
                                 oracle.n_slots)


def _gc_sweep(lay, st: TPCCState, engine, log, now, max_txn_time):
    """One GC-thread step over the run's pool (snapshot T_R → safe vector →
    sweep → lazy truncation), single-shard or per-shard on the mesh; returns
    ``(state, log, reclaimable_fraction)``."""
    tbl, vec = st.nam.table, st.nam.oracle_state.vec
    if engine is None:
        tbl, log = gc_ops.gc_round(tbl, vec, log, now, max_txn_time)
    else:
        tbl, log = engine.gc_fn(tbl, vec, log, now, max_txn_time)
    frac = float(gc_ops.reclaimable_fraction(
        tbl, n_records=lay.catalog.total_records))
    return st._replace(nam=st.nam._replace(table=tbl)), log, frac


# ----------------------------------------------------- retry-queue driver ----
def _check_layout_homes(cfg: TPCCConfig, lay: TPCCLayout, home_w,
                        locality_mode):
    """The warehouse-major layout homes each thread's insert extends in
    block ``tid % n_warehouses`` (see :func:`o_slot_ext`); when locality is
    being *measured*, transactions must execute at their insert blocks or
    the §7.3 measurement scores accesses against the wrong server. Reject
    diverging ``home_w`` rather than silently skewing local_fraction.
    (Without a locality measurement the protocol is placement-agnostic and
    any ``home_w`` is fine.)"""
    if locality_mode is None or lay.mode != "warehouse_major":
        return
    expected = locality.thread_homes(cfg.n_threads, cfg.n_warehouses)
    if home_w is None or not bool(jnp.all(
            jnp.asarray(home_w, jnp.int32) == expected)):
        raise ValueError(
            "measuring locality under the warehouse_major layout requires "
            "home_w = locality.thread_homes(n_threads, n_warehouses): "
            "thread tid's insert extends live in block tid % n_warehouses")


def _merge_retries(pending, fresh, retry_mask, T: int):
    """§7.4 retry queue: threads with a pending abort re-enter with their
    original *inputs* (the snapshot is re-read inside the round — GSI: any
    newer one is admissible, i.e. the old snapshot is discarded); everyone
    else draws fresh work. Shared by both run drivers."""
    if pending is None:
        return fresh
    return jax.tree.map(
        lambda p, f: jnp.where(
            retry_mask.reshape((T,) + (1,) * (f.ndim - 1)), p, f),
        pending, fresh)


class NewOrderRunStats(NamedTuple):
    """Aggregates of a multi-round run under the §7.4 retry discipline.

    The trailing fields are the §5.3 sustained-execution telemetry: aborts
    split by cause (``snapshot_misses`` = a needed version was GC'd /
    absent, ``contention_aborts`` = CAS lost or install blocked), reads
    served by the overflow region, and the GC-sweep trajectory of the
    reclaimable overflow fraction, which the ``--sustain`` bench turns into
    its steady-state curves.
    """
    committed: jnp.ndarray      # bool [R, T] — per-round outcomes
    attempts: int               # executed transactions (incl. retries)
    commits: int
    retries: int                # aborted txns that re-entered a later round
    abort_rate: float           # steady-state: aborts / attempts
    ops: si.OpCounts            # summed over rounds (python floats)
    local_fraction: float       # measured share of machine-local accesses
    missed: jnp.ndarray = None  # bool [R, T] — per-round snapshot misses
    snapshot_misses: int = 0    # GC-induced (snapshot-too-old) aborts
    contention_aborts: int = 0  # CAS-lost / install-blocked aborts
    ovf_reads: int = 0          # reads served by the overflow region
    gc_sweeps: int = 0          # GC-thread steps executed
    reclaim_traj: tuple = ()    # ((round, reclaimable_fraction), …)
    ovf_peak: int = 0           # max overflow ring position observed (< KO)


def run_neworder_rounds(cfg: TPCCConfig, lay: TPCCLayout, st: TPCCState,
                        oracle: VectorOracle, key: jax.Array, n_rounds: int,
                        *, logits=None, home_w=None, dist_degree=None,
                        engine: Optional[DistEngine] = None,
                        locality_mode: Optional[str] = None,
                        move_versions: bool = True, gc_interval: int = 0,
                        max_txn_time: int = 4, gc_snapshots: int = 8):
    """Closed-loop driver: each thread runs new-orders back to back and an
    aborted transaction *re-enters the next round* with its original snapshot
    discarded (§7.4 "the compute server directly triggers a retry after an
    abort") — so multi-round runs measure steady-state abort rates, not
    per-round ones.

    ``engine=None`` runs the single-shard reference; with a
    :class:`DistEngine` every round goes through ``distributed_round`` on the
    mesh. ``locality_mode`` ∈ {"aware", "oblivious", None} additionally
    measures the machine-local access fraction of the run under the given
    §7.3 routing (it never changes protocol behaviour — locality is an
    optimization, not a requirement).

    ``gc_interval > 0`` turns on sustained execution (§5.3): every
    ``gc_interval`` rounds the GC thread snapshots the timestamp vector,
    sweeps versions no snapshot younger than ``max_txn_time`` rounds can
    read, and lazily truncates them; the version mover then only ever
    advances into reclaimed overflow slots (``reuse_only``), so long runs
    reach the paper's steady state with bounded version storage instead of
    silently shedding old versions. Faithful to the paper's contract,
    transactions needing versions older than ``max_txn_time`` may abort with
    ``snapshot_miss`` and re-enter via the retry queue. Wall-clock is the
    round counter (one round ≙ one unit of E).
    """
    T = cfg.n_threads
    _check_layout_homes(cfg, lay, home_w, locality_mode)
    if logits is None:
        logits = workload.zipf_logits(cfg.n_items, cfg.skew_alpha)
    if dist_degree is None:
        dist_degree = cfg.dist_degree
    placement = engine.placement if engine is not None else \
        locality.Placement(n_servers=1,
                           shard_records=lay.catalog.total_records)
    use_gc = gc_interval > 0
    gc_log = _gc_init(oracle, engine, gc_interval, gc_snapshots)

    retry_mask = jnp.zeros((T,), bool)
    pending: Optional[workload.NewOrderInputs] = None
    committed_rounds = []
    missed_rounds = []
    attempts = commits = retries = 0
    snapshot_misses = contention_aborts = ovf_reads = 0
    gc_sweeps = ovf_peak = 0
    reclaim_traj = []
    ops_sum = [0.0] * len(si.OpCounts._fields)
    lf_sum, lf_n = 0.0, 0

    for r in range(n_rounds):
        key, sub = jax.random.split(key)
        fresh = workload.gen_neworder(
            sub, T, cfg.n_warehouses, cfg.n_items,
            cfg.customers_per_district, home_w, dist_degree, logits)
        inp = _merge_retries(pending, fresh, retry_mask, T)
        if engine is None:
            out = neworder_round(cfg, lay, st, oracle, inp, round_no=r)
        else:
            out = neworder_round_distributed(cfg, lay, st, oracle, engine,
                                             inp, round_no=r)
        st = out.state
        if move_versions:
            st = st._replace(nam=st.nam._replace(
                table=mvcc.version_mover(st.nam.table, reuse_only=use_gc)))
        if use_gc and (r + 1) % gc_interval == 0:
            st, gc_log, frac = _gc_sweep(lay, st, engine, gc_log, r,
                                         max_txn_time)
            gc_sweeps += 1
            reclaim_traj.append((r, frac))

        c = out.committed
        miss = out.snapshot_miss
        committed_rounds.append(c)
        missed_rounds.append(miss)
        n_c = int(jnp.sum(c))
        n_miss = int(jnp.sum(miss))
        attempts += T
        commits += n_c
        retries += T - n_c
        snapshot_misses += n_miss
        contention_aborts += T - n_c - n_miss
        ovf_reads += int(out.vis.n_ovf)
        ovf_peak = max(ovf_peak, int(jnp.max(st.nam.table.ovf_next)))
        for i, f in enumerate(out.ops):
            ops_sum[i] += float(f)
        if locality_mode is not None:
            home_slot = d_slot(lay, inp.w_id, inp.d_id)
            srv = locality.route_transactions(
                locality_mode, placement, home_slot, out.batch.tid, T)
            lf_sum += float(locality.local_fraction(
                placement, srv, out.batch.read_slots, out.batch.read_mask))
            lf_n += 1
        retry_mask = ~c
        pending = inp

    # the last round's aborts never re-entered a later round
    retries -= int(jnp.sum(retry_mask))
    stats = NewOrderRunStats(
        committed=jnp.stack(committed_rounds),
        attempts=attempts, commits=commits, retries=retries,
        abort_rate=1.0 - commits / max(1, attempts),
        ops=si.OpCounts(*ops_sum),
        local_fraction=lf_sum / lf_n if lf_n else float("nan"),
        missed=jnp.stack(missed_rounds),
        snapshot_misses=snapshot_misses,
        contention_aborts=contention_aborts, ovf_reads=ovf_reads,
        gc_sweeps=gc_sweeps, reclaim_traj=tuple(reclaim_traj),
        ovf_peak=ovf_peak)
    return st, stats


# ------------------------------------------- §6.2 failure injection ----------
class FailureInjector(NamedTuple):
    """Kill memory server ``dead_server`` at the *start* of round
    ``kill_round`` of :func:`run_mixed_rounds`.

    The failure model is the paper's §6.2: the dead server's shard of the
    record pool (and its journal replica) is lost; the system halts,
    restores the last checkpoint of the lost memory, replays the merged
    surviving journals, releases abandoned locks and resumes the workload.
    ``in_flight=True`` additionally simulates the §3.2 crash window — the
    round's new-order lanes have CAS-locked their write-sets and logged
    their intent records when the failure hits, so their outcome records
    never land: recovery must treat them as undetermined (skip on replay,
    release their locks) and the driver re-executes them after the resume
    (their RNG draw is peeked, not consumed, so a clean recovery leaves
    zero trace of them)."""
    kill_round: int
    dead_server: int = 0
    in_flight: bool = True


class RecoveryReport(NamedTuple):
    """What one §6.2 recovery did (rides on ``MixedRunStats.recovery``)."""
    kill_round: int
    dead_server: int
    checkpoint_round: int    # round after which the restored ckpt was taken
    replayed_entries: int    # committed journal entries re-installed
    undetermined: int        # intent-without-outcome entries replay skipped
    released_locks: int      # abandoned locks the monitor released
    recovery_seconds: float  # wall-clock: halt → workload resumed


def _mem_state(st: TPCCState, jnl: wal.Journal):
    """The memory-server-resident state a checkpoint must cover: the record
    pool, the timestamp vector, and the journal append counts at the cut
    (``used`` is the ``since`` marker replay starts from)."""
    return {"table": st.nam.table, "vec": st.nam.oracle_state.vec,
            "used": jnl.used}


def _inflight_intents(cfg: TPCCConfig, lay: TPCCLayout, st: TPCCState,
                      jnl: wal.Journal, key, pending, pending_type,
                      round_no, home_w, dist_degree, logits, mix, skew=None):
    """Simulate the crash window: the kill round's new-order lanes lock
    their write-sets and log intents, then the failure hits before any
    outcome record lands. The RNG key is split but not consumed — the
    driver re-draws the identical inputs when it re-executes the round
    after recovery."""
    T = cfg.n_threads
    _, sub = jax.random.split(key)
    fresh = workload.gen_mixed(sub, T, cfg.n_warehouses, cfg.n_items,
                               cfg.customers_per_district, home_w,
                               dist_degree, logits, mix, skew=skew)
    inp = _merge_retries(pending, fresh, pending_type >= 0, T)
    batch, _ = _neworder_batch(cfg, lay, inp.neworder, inp.txn_type == 0)
    tbl = st.nam.table
    wref = jnp.clip(batch.write_ref, 0, batch.read_slots.shape[1] - 1)
    wslots = jnp.take_along_axis(batch.read_slots, wref, axis=1)
    req_active = batch.write_mask.reshape(-1)
    req_slots = wslots.reshape(-1)
    # validate+lock against the headers as currently installed: at a round
    # boundary nothing is locked, so every arbitration-winning lane locks
    expected = tbl.cur_hdr[jnp.where(req_active, req_slots, 0)]
    prio = jnp.broadcast_to(batch.tid.astype(jnp.uint32)[:, None],
                            batch.write_mask.shape).reshape(-1)
    # analysis: safe(W01): deliberate crash window — locks stay abandoned
    res = cas.arbitrate(tbl.cur_hdr, req_slots, expected, prio, req_active)
    tbl = tbl._replace(cur_hdr=res.new_hdr)
    # the intent lands (on every journal replica), the outcome never does;
    # the payload is irrelevant — these entries must never replay
    jnl = wal.append_intent(
        jnl, batch.tid, st.nam.oracle_state.vec[:jnl.ts_vec.shape[-1]],
        *wal.pad_writes(jnl, wslots,
                        jnp.zeros(wslots.shape + (2,), jnp.uint32),
                        jnp.zeros(wslots.shape + (WIDTH,), jnp.int32),
                        batch.write_mask),
        round_no=round_no, seq=_JSEQ_NEWORDER)
    return st._replace(nam=st.nam._replace(table=tbl)), jnl


def recover_from_failure(cfg: TPCCConfig, lay: TPCCLayout, st: TPCCState,
                         engine, jnl: wal.Journal, checkpoint_dir: str,
                         failure: FailureInjector, *, use_gc: bool,
                         move_versions: bool = True):
    """§6.2 recovery: restore the dead server's memory from the last
    checkpoint + the merged surviving journals, release abandoned locks,
    re-replicate the journal, resume.

    The dead server's shard of the record pool is rebuilt by replaying the
    surviving journals onto the checkpoint (partially ordered by the logged
    T, version mover at round boundaries — bit-identical to the lost
    memory); the surviving servers keep their live memory, which still
    holds any locks of in-flight (undetermined) transactions — those are
    the monitoring server's to release. The timestamp vector is rebuilt
    from the checkpoint vector plus the journals' commit records. Returns
    ``(state, journal, RecoveryReport)``.
    """
    t0 = time.perf_counter()
    dead = failure.dead_server
    n_rep = jnl.n_replicas
    if engine is not None and dead >= engine.n_shards:
        raise ValueError(f"dead_server {dead} outside the "
                         f"{engine.n_shards}-server mesh")
    survivors = jnp.ones((n_rep,), bool).at[dead % n_rep].set(False)
    rep = 0 if dead % n_rep else 1    # first surviving replica

    ckpt, _, manifest = snapshot.restore(checkpoint_dir, _mem_state(st, jnl))
    since = ckpt["used"]
    replayed_tbl = wal.replay(jnl, ckpt["table"], survivors=survivors,
                              since=since, reuse_only=use_gc,
                              move_versions=move_versions)
    vec = wal.replay_vector(jnl, ckpt["vec"], survivors=survivors,
                            since=since)
    replayable, undetermined = wal.entry_status(jnl, rep, since=since)

    if engine is not None:
        # only the dead server's rows are lost: merge the replayed
        # reconstruction into the survivors' live memory (range partition,
        # see DistEngine.placement)
        rows = engine.shard_records

        def merge(live, rec):
            home = jnp.arange(live.shape[0]) // rows == dead
            return jnp.where(
                home.reshape((-1,) + (1,) * (live.ndim - 1)), rec, live)

        tbl = jax.tree.map(merge, st.nam.table, replayed_tbl)
    else:
        tbl = replayed_tbl
    n_locked = int(jnp.sum(hdr_ops.is_locked(tbl.cur_hdr)))
    # the monitor scans every thread's journal: any unresolved intent in the
    # live window marks an abandoned transaction whose locks must go
    tbl = wal.release_abandoned_locks(
        jnl, tbl, jnp.arange(cfg.n_threads, dtype=jnp.int32), replica=rep)
    jnl = wal.rereplicate(jnl, survivors)
    if engine is not None:
        tbl = store.shard_table(engine.mesh, engine.axis, tbl)
        if engine.shard_vector:
            vec = store.shard_vector(engine.mesh, engine.axis, vec)
        jnl = store.shard_journal(engine.mesh, engine.axis, jnl)
    st = st._replace(nam=st.nam._replace(
        table=tbl, oracle_state=VectorState(vec=vec)))
    report = RecoveryReport(
        kill_round=failure.kill_round, dead_server=dead,
        checkpoint_round=int(manifest["extra"].get("round", -1)),
        replayed_entries=int(jnp.sum(replayable)),
        undetermined=int(jnp.sum(undetermined)),
        released_locks=n_locked
        - int(jnp.sum(hdr_ops.is_locked(tbl.cur_hdr))),
        recovery_seconds=time.perf_counter() - t0)
    return st, jnl, report


# ------------------------------------------------------- online scale-out ----
class MeshGrowth(NamedTuple):
    """Grow the mesh to ``new_shards`` memory servers at the *start* of
    round ``grow_round`` of :func:`run_mixed_rounds` — online scale-out
    (DESIGN.md §4.3). The expansion is a planned §6.2 failover: checkpoint
    the joining epoch, repartition the directory and the timestamp vector,
    migrate the moved record ranges by replaying the journal onto the last
    checkpoint, cut over. The workload keeps its retry queues, in-flight
    state and RNG stream — transactions in flight at the cut complete or
    retry through the §7.4 queues exactly as they would have."""
    grow_round: int
    new_shards: int


class ScaleOutReport(NamedTuple):
    """What one online expansion did (rides on ``MixedRunStats.growth``)."""
    grow_round: int
    old_shards: int
    new_shards: int
    checkpoint_round: int    # round after which the migration ckpt was taken
    replayed_entries: int    # journal entries replayed over the window
    moved_slots: int         # pool slots that changed owning server
    moved_buckets: int       # §5.2 directory buckets that changed owner
    migration_seconds: float # wall-clock: halt → workload resumed


def scale_out(cfg: TPCCConfig, lay: TPCCLayout, st: TPCCState,
              oracle: VectorOracle, engine, jnl: wal.Journal,
              checkpoint_dir: str, growth: MeshGrowth, *, use_gc: bool,
              move_versions: bool = True, gc_log=None):
    """Online mesh expansion: add memory servers to a live mesh (§4.3).

    Reuses the §6.2 recovery machinery as the migration substrate — a
    scale-out is a planned failover of every *moved* range:

    1. **Checkpoint epoch.** Restore the last checkpoint and replay the
       journal onto it (all replicas live, any one serves). This rebuilds,
       bit-exactly, the state of every record as of the join point — the
       "migration window" replay: intents that landed after the checkpoint
       was cut are re-applied, so no committed transaction is lost.
    2. **Repartition + migrate.** Compute the moved ranges
       (:func:`repro.core.locality.moved_slots` for records,
       :func:`repro.core.hashtable.moved_buckets` for the §5.2 directory,
       the slot-range analogue for the partitioned timestamp vector). Moved
       ranges take the replayed reconstruction — the new server's memory is
       seeded from checkpoint + journal, exactly like a recovered server's;
       unmoved ranges keep their live memory untouched.
    3. **Cutover.** Re-place every structure over the grown mesh
       (:func:`repro.core.store.expand_mesh`: re-pad + re-shard the pool,
       re-partition vector and directory, :func:`repro.core.wal.
       grow_replicas` the journal so each joiner holds a replica, copy the
       §5.3 snapshot logs), rebuild the executors, and checkpoint the
       post-join epoch so a later failure restores new-mesh shapes.

    Returns ``(state, journal, engine, gc_log, ScaleOutReport)``.
    """
    t0 = time.perf_counter()
    old_n = engine.n_shards
    new_n = growth.new_shards
    if new_n <= old_n:
        raise ValueError(f"scale_out grows the mesh: new_shards ({new_n}) "
                         f"must exceed the current {old_n}")
    R = lay.catalog.total_records
    n_slots = oracle.n_slots

    # gather every carried structure off the old mesh: arrays committed to
    # the 4-device placement cannot feed the 8-device executors, and the
    # migration merge below runs host-side anyway
    def host(t):
        return jax.tree.map(lambda x: jnp.asarray(jax.device_get(x)), t)

    st, jnl = host(st), host(jnl)
    if gc_log is not None:
        gc_log = host(gc_log)

    # ---- 1. checkpoint epoch + migration-window replay -------------------
    ckpt, _, manifest = snapshot.restore(checkpoint_dir, _mem_state(st, jnl))
    since = ckpt["used"]
    recon_tbl = wal.replay(jnl, ckpt["table"], since=since,
                           reuse_only=use_gc, move_versions=move_versions)
    recon_vec = wal.replay_vector(jnl, ckpt["vec"], since=since)
    replayable, _ = wal.entry_status(jnl, 0, since=since)

    # ---- 2. repartition: moved ranges take the replayed reconstruction ---
    new_placement = locality.Placement(
        n_servers=new_n, shard_records=-(-R // new_n))
    moved = locality.moved_slots(engine.placement, new_placement, R)

    def pick(live, rec):
        return jnp.where(moved.reshape((-1,) + (1,) * (live.ndim - 1)),
                         rec[:R], live[:R])

    tbl = jax.tree.map(pick, st.nam.table, recon_tbl)
    sl = jnp.arange(n_slots, dtype=jnp.int32)
    vec_moved = (sl // (-(-n_slots // old_n))) != (sl // (-(-n_slots // new_n)))
    vec = jnp.where(vec_moved, recon_vec[:n_slots],
                    st.nam.oracle_state.vec[:n_slots])
    n_moved_buckets = int(jnp.sum(ht.moved_buckets(
        engine.n_dir_buckets, old_n, new_n))) if engine.n_dir_buckets else 0

    # ---- 3. cutover: re-place onto the grown mesh, rebuild executors -----
    new_mesh = compat.mesh(new_n, engine.axis)
    if isinstance(engine, MixedEngine):
        new_engine = make_mixed_engine(
            cfg, lay, new_mesh, engine.axis, oracle,
            shard_vector=engine.shard_vector, with_journal=engine.with_journal)
    else:
        new_engine = make_distributed_engine(
            cfg, lay, new_mesh, engine.axis, oracle,
            shard_vector=engine.shard_vector, with_journal=engine.with_journal)
    tbl, vec, directory, jnl, gc_log = store.expand_mesh(
        new_mesh, engine.axis, tbl, vec, n_records=R,
        vector_sharded=engine.shard_vector,
        directory=st.directory if engine.n_dir_buckets else None,
        journal=jnl, gc_logs=gc_log)
    st = st._replace(
        nam=st.nam._replace(table=tbl, oracle_state=VectorState(vec=vec)),
        directory=directory if directory is not None else st.directory)
    snapshot.save(checkpoint_dir, _mem_state(st, jnl),
                  extra={"round": growth.grow_round - 1, "n_shards": new_n})
    report = ScaleOutReport(
        grow_round=growth.grow_round, old_shards=old_n, new_shards=new_n,
        checkpoint_round=int(manifest["extra"].get("round", -1)),
        replayed_entries=int(jnp.sum(replayable)),
        moved_slots=int(jnp.sum(moved)), moved_buckets=n_moved_buckets,
        migration_seconds=time.perf_counter() - t0)
    return st, jnl, new_engine, gc_log, report


# ----------------------------------------------------- mixed-round driver ----
def _sub_rounds(cfg: TPCCConfig, lay: TPCCLayout, oracle: VectorOracle,
                engine: Optional[MixedEngine], last_n: int) -> dict:
    """Compile each transaction type's sub-round as one program: a dict from
    type name to a jitted ``(st, inp, round_no, active, journal)`` for the
    write types and ``(st, inp, active)`` for the read-only ones.

    Run op by op, the first round of a mix compiles every primitive of every
    type on its own: about 800 small programs at any table size, each a
    separate compile on a TPU. As one program per type, the first round
    compiles five and later rounds reuse them: the round number and the
    active mask are traced. On a mesh each type compiles once more, in the
    round after its first, because the retry merge then hands in inputs
    replicated over the mesh in place of the first round's single-device
    draws. Each program carries its type's round function name, which is
    the name JAX's compile events report."""
    def jit_as(fn, name):
        fn.__name__ = fn.__qualname__ = name
        return jax.jit(fn)

    def write(single, dist):
        if engine is None:
            def run(st, inp, round_no, active, journal):
                return single(cfg, lay, st, oracle, inp, round_no=round_no,
                              active=active, journal=journal)
        else:
            def run(st, inp, round_no, active, journal):
                return dist(cfg, lay, st, oracle, engine, inp,
                            round_no=round_no, active=active,
                            journal=journal)
        return jit_as(run, single.__name__)

    def orderstatus(st, inp, active):
        return orderstatus_round(cfg, lay, st, oracle, inp, engine=engine,
                                 active=active)

    def stocklevel(st, inp, active):
        return stocklevel_round(cfg, lay, st, oracle, inp, engine=engine,
                                active=active, last_n=last_n)

    return {"neworder": write(neworder_round, neworder_round_distributed),
            "payment": write(payment_round, payment_round_distributed),
            "delivery": write(delivery_round, delivery_round_distributed),
            "orderstatus": jit_as(orderstatus, "orderstatus_round"),
            "stocklevel": jit_as(stocklevel, "stocklevel_round")}


_version_mover = jax.jit(mvcc.version_mover, static_argnames=("reuse_only",))

# The driver's host spans: ``jax.profiler`` records them on the device
# trace's clock while a trace runs, and they cost about a microsecond each
# when none does.
_span = jax.profiler.TraceAnnotation


class MixedRunStats(NamedTuple):
    """Aggregates of a full five-transaction-mix run (§7: the paper's total
    throughput only exists because the whole 45/43/4/4/4 mix runs
    concurrently; new-order is reported *out of* that total)."""
    attempts: dict              # type name -> executed txns (incl. retries)
    commits: dict               # type name -> commits
    retries: dict               # type name -> aborted txns re-entered later
    ops: dict                   # type name -> si.OpCounts (python floats)
    total_attempts: int
    total_commits: int
    abort_rate: float           # steady-state: 1 - commits/attempts
    local_fraction: float       # access-weighted machine-local share
    delivered: int              # deliveries that found+delivered an order
    # §5.3 sustained-execution telemetry (write types; read-only types never
    # validate and here never read stale snapshots, so they carry no misses)
    snapshot_misses: dict = None    # type -> GC-induced aborts
    contention_aborts: dict = None  # type -> CAS-lost/install-blocked aborts
    ovf_reads: dict = None          # type -> reads served by overflow region
    gc_sweeps: int = 0
    reclaim_traj: tuple = ()        # ((round, reclaimable_fraction), …)
    ovf_peak: int = 0               # max overflow ring position observed
    recovery: tuple = ()            # (§6.2 RecoveryReport, …) — one per
    #                                 injected memory-server failure
    growth: tuple = ()              # (ScaleOutReport, …) — one per online
    #                                 mesh expansion (DESIGN.md §4.3)
    host_reads: int = 0             # blocking device-to-host reads of the
    #                                 round loop and the closing retry count


def run_mixed_rounds(cfg: TPCCConfig, lay: TPCCLayout, st: TPCCState,
                     oracle: VectorOracle, key: jax.Array, n_rounds: int,
                     *, mix=None, logits=None, home_w=None, dist_degree=None,
                     engine: Optional[MixedEngine] = None,
                     locality_mode: Optional[str] = None,
                     move_versions: bool = True, stock_last_n: int = 8,
                     gc_interval: int = 0, max_txn_time: int = 4,
                     gc_snapshots: int = 8,
                     journal: Optional[wal.Journal] = None,
                     checkpoint_dir: Optional[str] = None,
                     failure: Optional[FailureInjector] = None,
                     growth: Optional[MeshGrowth] = None,
                     skew: Optional[workload.Skew] = None):
    """Closed-loop driver for the full TPC-C mix.

    Each round, every execution thread draws its next transaction type from
    ``mix`` (default :data:`workload.MIX`) and runs it; the round executes as
    five type-homogeneous sub-rounds over the thread subsets (the vectorized
    rendering of per-terminal mixing — inactive lanes are protocol no-ops).
    The §7.4 retry queue is per-transaction-type: an aborted write
    transaction re-enters the next round with its original inputs *and its
    original type*, its snapshot discarded. Read-only types never validate
    and never abort (§1.2) — they always commit, and their snapshot reads
    are op-counted (and, with an engine, hit the sharded pool).

    ``engine=None`` runs the single-shard reference; with a
    :class:`MixedEngine` every sub-round goes through the mesh executors.

    ``gc_interval``/``max_txn_time``/``gc_snapshots`` are the §5.3 sustained
    execution knobs of :func:`run_neworder_rounds`: one GC-thread sweep per
    ``gc_interval`` rounds (after all five sub-rounds), version mover in
    reclaimed-slot-only mode, round counter as wall-clock.

    ``journal`` switches the §6.2 WAL on: every write sub-round logs its
    intent records before installing and its outcome after the commit
    decision (build the engine with ``with_journal=True``; with a mesh,
    replicate one journal replica per server via ``store.shard_journal``).
    ``checkpoint_dir`` then checkpoints the memory-server state (pool,
    vector, journal cursors) via :mod:`repro.checkpoint.snapshot` — once
    before round 0 and after every GC sweep, so the journal ring only ever
    needs to cover one checkpoint interval and replay never spans a GC
    truncation. ``failure`` injects a §6.2 memory-server failure at the
    start of its ``kill_round`` and runs :func:`recover_from_failure`
    before resuming; the reports ride on ``MixedRunStats.recovery``.

    ``growth`` performs an online mesh expansion (:func:`scale_out`) at the
    start of its ``grow_round`` — the workload keeps committing on the grown
    mesh; reports ride on ``MixedRunStats.growth``. ``skew`` applies the
    zipfian warehouse/district/remote-payment knobs of
    :class:`repro.db.workload.Skew` to every drawn transaction.

    Under ``jax.profiler`` the host's work shows as spans: ``tpcc.start``
    (entry to round 0), one ``tpcc.round`` per round (argument ``round``)
    holding ``tpcc.draw`` (draw and retry merge), ``tpcc.gate`` (each type's
    lane count) and ``tpcc.tally`` (the statistics after a program), then
    ``tpcc.finish``; each blocking device-to-host read is a ``tpcc.read``
    inside them, and ``MixedRunStats.host_reads`` counts those reads.
    """
    with _span("tpcc.start"):
        T = cfg.n_threads
        _check_layout_homes(cfg, lay, home_w, locality_mode)
        if logits is None:
            logits = workload.zipf_logits(cfg.n_items, cfg.skew_alpha)
        if dist_degree is None:
            dist_degree = cfg.dist_degree
        placement = engine.placement if engine is not None else \
            locality.Placement(n_servers=1,
                               shard_records=lay.catalog.total_records)
        names = workload.TXN_TYPES
        attempts = {n: 0 for n in names}
        commits = {n: 0 for n in names}
        retries = {n: 0 for n in names}
        ops_sum = {n: [0.0] * len(si.OpCounts._fields) for n in names}
        snapshot_misses = {n: 0 for n in names}
        contention_aborts = {n: 0 for n in names}
        ovf_reads = {n: 0 for n in names}
        use_gc = gc_interval > 0
        gc_log = _gc_init(oracle, engine, gc_interval, gc_snapshots)
        gc_sweeps = ovf_peak = host_reads = 0
        reclaim_traj = []
        delivered = 0
        lf_local = lf_total = 0.0
        tids = jnp.arange(T, dtype=jnp.int32)
        pending_type = jnp.full((T,), -1, jnp.int32)
        pending: Optional[workload.MixedInputs] = None
        jnl = journal
        recovery = []
        if failure is not None and (jnl is None or checkpoint_dir is None):
            raise ValueError("failure injection needs a journal and a "
                             "checkpoint_dir: §6.2 recovery replays the "
                             "surviving journals onto the last checkpoint")
        if jnl is not None and engine is not None and \
                not engine.with_journal:
            raise ValueError("journaling through the mesh needs an engine "
                             "built with with_journal=True")
        growth_reports = []
        if growth is not None:
            if engine is None or jnl is None or checkpoint_dir is None:
                raise ValueError("online scale-out needs a mesh engine, a "
                                 "journal and a checkpoint_dir: §4.3 "
                                 "migration replays the journal onto the "
                                 "last checkpoint")
            if not 0 <= growth.grow_round < n_rounds:
                raise ValueError(f"grow_round {growth.grow_round} outside "
                                 f"the {n_rounds}-round run")
            if growth.new_shards <= engine.n_shards:
                raise ValueError(f"new_shards ({growth.new_shards}) must "
                                 f"exceed the current mesh "
                                 f"({engine.n_shards})")
        if jnl is not None and checkpoint_dir is not None:
            snapshot.save(checkpoint_dir, _mem_state(st, jnl),
                          extra={"round": -1})
        programs = _sub_rounds(cfg, lay, oracle, engine, stock_last_n)

    def host(x):
        """Every blocking device-to-host read of the round loop and of the
        closing retry count goes through here: one ``tpcc.read`` span and
        one count each."""
        nonlocal host_reads
        with _span("tpcc.read"):
            host_reads += 1
            return jax.device_get(x)

    def acc_ops(name, ops):
        for i, f in enumerate(ops):
            ops_sum[name][i] += float(host(f))

    def acc_local(w_id, d_id, slots, mask):
        nonlocal lf_local, lf_total
        if locality_mode is None:
            return
        srv = locality.route_transactions(
            locality_mode, placement, d_slot(lay, w_id, d_id), tids, T)
        n_acc = float(host(jnp.sum(mask)))
        lf_local += float(host(locality.local_fraction(
            placement, srv, slots, mask))) * n_acc
        lf_total += n_acc

    def acc_write(name, act, committed, ops, snap_miss, vis):
        attempts[name] += int(host(jnp.sum(act)))
        commits[name] += int(host(jnp.sum(committed)))
        aborted = act & ~committed
        n_ab = int(host(jnp.sum(aborted)))
        retries[name] += n_ab
        n_miss = int(host(jnp.sum(snap_miss & act)))
        snapshot_misses[name] += n_miss
        contention_aborts[name] += n_ab - n_miss
        ovf_reads[name] += int(host(vis.n_ovf))
        acc_ops(name, ops)
        return aborted

    def gate(ttype, name):
        """The lanes that drew ``name`` this round, and how many. A type
        that drew none is skipped outright: its masked sub-round would be a
        pure no-op contributing zero stats."""
        with _span("tpcc.gate"):
            act = ttype == names.index(name)
            return act, int(host(jnp.sum(act)))

    for r in range(n_rounds):
        with _span("tpcc.round", round=r):
            if failure is not None and r == failure.kill_round:
                if failure.in_flight:
                    st, jnl = _inflight_intents(
                        cfg, lay, st, jnl, key, pending, pending_type, r,
                        home_w, dist_degree, logits, mix, skew=skew)
                st, jnl, rep = recover_from_failure(
                    cfg, lay, st, engine, jnl, checkpoint_dir, failure,
                    use_gc=use_gc, move_versions=move_versions)
                recovery.append(rep)
            if growth is not None and r == growth.grow_round:
                st, jnl, engine, gc_log, grep = scale_out(
                    cfg, lay, st, oracle, engine, jnl, checkpoint_dir,
                    growth, use_gc=use_gc, move_versions=move_versions,
                    gc_log=gc_log)
                placement = engine.placement
                programs = _sub_rounds(cfg, lay, oracle, engine,
                                       stock_last_n)
                growth_reports.append(grep)
                # the retry queues ride across the cut untouched in
                # content, but their arrays are committed to the old mesh
                # — re-land them
                pending_type = jnp.asarray(jax.device_get(pending_type))
                if pending is not None:
                    pending = jax.tree.map(
                        lambda x: jnp.asarray(jax.device_get(x)), pending)
            with _span("tpcc.draw"):
                key, sub = jax.random.split(key)
                fresh = workload.gen_mixed(
                    sub, T, cfg.n_warehouses, cfg.n_items,
                    cfg.customers_per_district, home_w, dist_degree, logits,
                    mix, skew=skew)
                # a retried txn keeps its original type AND inputs
                # (MixedInputs carries both, so one merge covers the
                # per-type retry queues)
                inp = _merge_retries(pending, fresh, pending_type >= 0, T)
                ttype = inp.txn_type
                aborted_round = jnp.zeros((T,), bool)

            # ---- write transactions, one type-homogeneous sub-round each -
            for name in ("neworder", "payment", "delivery"):
                act, n_act = gate(ttype, name)
                if not n_act:
                    continue
                sub_inp = getattr(inp, name)
                out = programs[name](st, sub_inp, r, act, jnl)
                st, jnl = out.state, out.journal
                with _span("tpcc.tally"):
                    aborted_round |= acc_write(name, act, out.committed,
                                               out.ops, out.snapshot_miss,
                                               out.vis)
                    if name == "delivery":
                        delivered += int(host(jnp.sum(out.delivered)))
                    acc_local(sub_inp.w_id, sub_inp.d_id,
                              out.batch.read_slots, out.batch.read_mask)

            # ---- read-only transactions: snapshot reads, never abort -----
            for name in READ_ONLY_TYPES:
                act, n_act = gate(ttype, name)
                if not n_act:
                    continue
                sub_inp = getattr(inp, name)
                ro = programs[name](st, sub_inp, act)
                with _span("tpcc.tally"):
                    attempts[name] += n_act
                    commits[name] += n_act
                    acc_ops(name, ro.ops)
                    acc_local(sub_inp.w_id, sub_inp.d_id, ro.read_slots,
                              ro.read_mask)

            with _span("tpcc.tally"):
                pending_type = jnp.where(aborted_round, ttype, -1)
                pending = inp
            if move_versions:
                st = st._replace(nam=st.nam._replace(
                    table=_version_mover(st.nam.table, reuse_only=use_gc)))
            if use_gc and (r + 1) % gc_interval == 0:
                st, gc_log, frac = _gc_sweep(lay, st, engine, gc_log, r,
                                             max_txn_time)
                gc_sweeps += 1
                reclaim_traj.append((r, frac))
                if jnl is not None and checkpoint_dir is not None:
                    # checkpoint at every GC sweep: replay from the last
                    # checkpoint then never spans a GC truncation, so the
                    # journal alone reconstructs the lost shard bit-exactly
                    snapshot.save(checkpoint_dir, _mem_state(st, jnl),
                                  extra={"round": r})
            with _span("tpcc.tally"):
                ovf_peak = max(ovf_peak, int(host(
                    jnp.max(st.nam.table.ovf_next))))

    with _span("tpcc.finish"):
        # the last round's aborts never re-entered a later round
        for i, n in enumerate(names):
            retries[n] -= int(host(jnp.sum(pending_type == i)))
        total_attempts = sum(attempts.values())
        total_commits = sum(commits.values())
        stats = MixedRunStats(
            attempts=attempts, commits=commits, retries=retries,
            ops={n: si.OpCounts(*ops_sum[n]) for n in names},
            total_attempts=total_attempts, total_commits=total_commits,
            abort_rate=1.0 - total_commits / max(1, total_attempts),
            local_fraction=lf_local / lf_total if lf_total else float("nan"),
            delivered=delivered, snapshot_misses=snapshot_misses,
            contention_aborts=contention_aborts, ovf_reads=ovf_reads,
            gc_sweeps=gc_sweeps, reclaim_traj=tuple(reclaim_traj),
            ovf_peak=ovf_peak, recovery=tuple(recovery),
            growth=tuple(growth_reports), host_reads=host_reads)
    return st, stats


# extra conflict-free extend installs per COMMIT, invisible to OpCounts:
# new-order inserts order + new-order + ~10 order-lines + index entry;
# payment appends one history record. Read-only types insert nothing.
# (profiles are per *attempt*, so the charge is scaled by the commit rate —
# aborted attempts never reach the insert phase.)
EXTRA_INSTALLS = {"neworder": 13.0, "payment": 1.0}
READ_ONLY_TYPES = ("orderstatus", "stocklevel")


def mixed_profiles(stats: MixedRunStats):
    """Per-type cost-model profiles + the attempt-share-weighted mix profile
    that feeds :func:`repro.core.netmodel.namdb_throughput` (the paper's
    total-throughput number is over the whole mix)."""
    per_type = {
        n: netmodel.profile_from_ops(
            stats.ops[n], stats.attempts[n],
            extra_installs=EXTRA_INSTALLS.get(n, 0.0)
            * stats.commits[n] / max(1, stats.attempts[n]),
            read_only=n in READ_ONLY_TYPES)
        for n in workload.TXN_TYPES}
    total = max(1, stats.total_attempts)
    shares = {n: stats.attempts[n] / total for n in workload.TXN_TYPES}
    return per_type, netmodel.combine_profiles(per_type, shares)


def neworder_share(stats: MixedRunStats) -> float:
    """New-order commits as a fraction of total commits — the Fig. 4 split
    (paper: 6.5M new-order out of 14.5M total)."""
    return stats.commits["neworder"] / max(1, stats.total_commits)


# --------------------------------------------------------------- payment ----
class PaymentResult(NamedTuple):
    state: TPCCState
    committed: jnp.ndarray
    ops: si.OpCounts
    batch: TxnBatch
    snapshot_miss: jnp.ndarray  # bool [T] — a required version was GC'd
    vis: si.VisStats
    journal: Optional[wal.Journal] = None   # §6.2 — set iff one was passed


def _payment_batch(cfg: TPCCConfig, lay: TPCCLayout,
                   inp: workload.PaymentInputs,
                   active: Optional[jnp.ndarray] = None) -> TxnBatch:
    """RS=WS=3: [warehouse, district, customer] — all written."""
    T = inp.w_id.shape[0]
    act = _active_or_ones(T, active)
    read_slots = jnp.stack(
        [w_slot(lay, inp.w_id), d_slot(lay, inp.w_id, inp.d_id),
         c_slot(lay, cfg, inp.c_w_id, inp.d_id, inp.c_id)], axis=1)
    mask = jnp.broadcast_to(act[:, None], (T, 3))
    return TxnBatch(
        tid=jnp.arange(T, dtype=jnp.int32),
        read_slots=read_slots, read_mask=mask,
        write_ref=jnp.broadcast_to(jnp.arange(3)[None, :], (T, 3)).astype(
            jnp.int32),
        write_mask=mask)


def _payment_new_data(rd, inp: workload.PaymentInputs):
    """The payment write-set: w/d ytd += amount, debit the customer."""
    w = rd[:, 0, :].at[:, W_COL["ytd"]].add(inp.amount)
    d = rd[:, 1, :].at[:, D_COL["ytd"]].add(inp.amount)
    c = rd[:, 2, :]
    c = c.at[:, C_COL["balance"]].add(-inp.amount)
    c = c.at[:, C_COL["ytd_payment"]].add(inp.amount)
    c = c.at[:, C_COL["payment_cnt"]].add(1)
    return jnp.stack([w, d, c], axis=1)


def _payment_insert(cfg, lay, st: TPCCState, oracle, tbl, vec, committed,
                    inp: workload.PaymentInputs, round_no=0, journal=None):
    """History insert into the thread-private extend (shared verbatim by the
    single-shard and the distributed payment paths)."""
    T = inp.w_id.shape[0]
    tids = jnp.arange(T, dtype=jnp.int32)
    slot_ids = oracle.slot_of_thread(tids)
    cts = vec[slot_ids]
    cur = st.hist_cursor
    local = jnp.clip(cur, 0, cfg.orders_per_thread - 1)
    hslot = h_slot_ext(lay, cfg, tids, local)
    can = committed & (cur < cfg.orders_per_thread)
    hdata = jnp.zeros((T, WIDTH), jnp.int32)
    hdata = hdata.at[:, H_COL["amount"]].set(inp.amount)
    hdata = hdata.at[:, H_COL["c_id"]].set(inp.c_id)
    hdata = hdata.at[:, H_COL["w_id"]].set(inp.w_id)
    tbl = _insert_install(tbl, hslot, slot_ids, cts, hdata, can)
    if journal is not None:
        journal = wal.append_intent(
            journal, tids, vec[:journal.ts_vec.shape[-1]],
            *wal.pad_writes(
                journal, hslot[:, None],
                hdr_ops.pack(slot_ids.astype(jnp.uint32), cts)[:, None, :],
                hdata[:, None, :], can[:, None]),
            round_no=round_no, seq=_JSEQ_PAYMENT_INS)
        journal = wal.append_outcome(journal, tids, can)
    return tbl, cur + can.astype(jnp.int32), journal


def payment_round(cfg: TPCCConfig, lay: TPCCLayout, st: TPCCState,
                  oracle: VectorOracle, inp: workload.PaymentInputs,
                  rts_vec=None, active=None, round_no=0,
                  journal=None) -> PaymentResult:
    """One vectorized round of payment transactions (single-shard path)."""
    batch = _payment_batch(cfg, lay, inp, active)
    out = si.run_round(st.nam.table, oracle, st.nam.oracle_state, batch,
                       lambda rh, rd, vec: _payment_new_data(rd, inp),
                       rts_vec=rts_vec, active=active,
                       journal=journal, journal_round=round_no,
                       journal_seq=_JSEQ_PAYMENT,
                       fused_commit=cfg.fused_commit,
                       batched_probe=cfg.batched_probe)
    tbl, hist_cursor, journal = _payment_insert(
        cfg, lay, st, oracle, out.table, out.oracle_state.vec, out.committed,
        inp, round_no=round_no, journal=out.journal)
    nam = st.nam._replace(table=tbl, oracle_state=out.oracle_state)
    return PaymentResult(
        state=st._replace(nam=nam, hist_cursor=hist_cursor),
        committed=out.committed, ops=out.ops, batch=batch,
        snapshot_miss=out.snapshot_miss, vis=out.vis, journal=journal)


def payment_round_distributed(cfg: TPCCConfig, lay: TPCCLayout, st: TPCCState,
                              oracle: VectorOracle, engine,
                              inp: workload.PaymentInputs,
                              active=None, round_no=0,
                              journal=None) -> PaymentResult:
    """Payment through :func:`store.distributed_round` on the mesh —
    bit-identical to :func:`payment_round`."""
    batch = _payment_batch(cfg, lay, inp, active)
    jkw = dict(journal=journal, round_no=round_no,
               seq=_JSEQ_PAYMENT) if journal is not None else {}
    res = engine.payment_fn(st.nam.table, st.nam.oracle_state.vec,
                            batch, inp, active, **jkw)
    tbl, vec, out = res[:3]
    journal = res[3] if journal is not None else None
    ops = _dist_ops(oracle, batch, out, tbl, active)
    tbl, hist_cursor, journal = _payment_insert(
        cfg, lay, st, oracle, tbl, vec, out.committed, inp,
        round_no=round_no, journal=journal)
    nam = st.nam._replace(table=tbl, oracle_state=VectorState(vec=vec))
    return PaymentResult(
        state=st._replace(nam=nam, hist_cursor=hist_cursor),
        committed=out.committed, ops=ops, batch=batch,
        snapshot_miss=out.snapshot_miss, vis=_dist_vis(batch, out, active),
        journal=journal)


# ----------------------------------------------------- read-only queries ----
def _latest_order_of(idx: ri.RangeIndex, w_id, d_id):
    """Latest order slot of (w, d) via the secondary index, with the
    key-ownership check: ``lookup_max_below`` returns the globally largest
    key below the bound, so a district with no orders would otherwise
    silently surface *another* district's latest order. Returns
    (oslot, found) where ``found`` is trustworthy."""
    d_key = (jnp.asarray(w_id) * DISTRICTS + jnp.asarray(d_id)) \
        .astype(jnp.uint32)
    hi = (d_key + jnp.uint32(1)) * jnp.uint32(MAX_O_PER_DISTRICT)
    k, oslot, idx_found = ri.lookup_max_below(idx, jnp.atleast_1d(hi))
    found = idx_found & (k // jnp.uint32(MAX_O_PER_DISTRICT)
                         == jnp.atleast_1d(d_key))
    return oslot, found


def orderstatus(cfg: TPCCConfig, lay: TPCCLayout, st: TPCCState,
                oracle: VectorOracle, w_id, d_id, c_id):
    """Read-only: customer + their latest order + its order lines.

    Under SI, read-only transactions never abort and never validate — the
    paper's motivation for SI over serializability (§1.2).
    """
    vec = oracle.read(st.nam.oracle_state)
    csl = c_slot(lay, cfg, w_id, d_id, c_id)
    cust = mvcc.read_visible(st.nam.table, jnp.atleast_1d(csl), vec)
    oslot, found = _latest_order_of(st.order_index, w_id, d_id)
    ordr = mvcc.read_visible(st.nam.table,
                             jnp.where(found, oslot, 0), vec)
    return cust, ordr, found


class ReadOnlyRoundResult(NamedTuple):
    """One vectorized round of a read-only transaction type.

    Read-only transactions never validate (§1.2): the round is snapshot
    reads only — but those reads hit the (possibly sharded) record pool and
    are op-counted, so the mixed bench charges them to the cost model.
    ``result`` is per-transaction: the latest-order payload (orderstatus) or
    the low-stock count (stocklevel). ``read_slots``/``read_mask`` feed the
    locality measurement like a write transaction's batch would."""
    result: jnp.ndarray
    found: jnp.ndarray          # bool [T]
    ops: si.OpCounts
    read_slots: jnp.ndarray
    read_mask: jnp.ndarray


@jax.named_scope("si.read")
def _snapshot_read(st: TPCCState, engine, vec, slots, mask, keys=None,
                   key_mask=None):
    """Visible reads of ``slots`` [T, A] — through the sharded pool when an
    engine is given, plain single-pool reads otherwise. Returns
    (data [T,A,W], found [T,A], from_current [T,A]).

    ``keys``/``key_mask`` switch the marked reads to the §5.2 key-addressed
    path: the slot comes from a hash-directory probe (sharded directory
    under an engine, ``st.directory`` single-shard) and a directory miss
    reads as not-found."""
    T, A = slots.shape
    if engine is not None:
        if getattr(engine, "n_dir_buckets", 0):
            out = engine.readonly_fn(st.nam.table, st.nam.oracle_state.vec,
                                     slots, mask, directory=st.directory,
                                     read_keys=keys, key_mask=key_mask)
        else:
            out = engine.readonly_fn(st.nam.table, st.nam.oracle_state.vec,
                                     slots, mask)
        return out.read_data, out.found, out.from_current
    flat = slots.reshape(-1)
    if keys is not None:
        kvals, kfound = ht.lookup(st.directory, keys.reshape(-1),
                                  max_probes=DIR_PROBES)
        km = key_mask.reshape(-1)
        flat = jnp.where(km, jnp.where(kfound, kvals, 0), flat)
        key_ok = ~km | kfound
    else:
        key_ok = jnp.ones(flat.shape, bool)
    vr = mvcc.read_visible(st.nam.table, flat, vec)
    W = st.nam.table.payload_width
    return (vr.data.reshape(T, A, W), (vr.found & key_ok).reshape(T, A),
            (vr.from_current & key_ok).reshape(T, A))


def orderstatus_round(cfg: TPCCConfig, lay: TPCCLayout, st: TPCCState,
                      oracle: VectorOracle, inp: workload.OrderStatusInputs,
                      *, engine=None, active=None) -> ReadOnlyRoundResult:
    """Vectorized order-status: customer + the district's latest order + its
    order lines (a dependent read — the line count comes out of the order
    payload), every read hitting the pool and op-counted."""
    T = inp.w_id.shape[0]
    act = _active_or_ones(T, active)
    vec = oracle.read(st.nam.oracle_state)
    csl = c_slot(lay, cfg, inp.w_id, inp.d_id, inp.c_id)
    oslot, found = _latest_order_of(st.order_index, inp.w_id, inp.d_id)
    found = found & act
    slots = jnp.stack([csl, jnp.where(found, oslot, 0)], axis=1)
    mask = jnp.stack([act, found], axis=1)
    keys = kmask = None
    n_probes = 0
    if cfg.key_addressed:   # the customer is fetched by key (§5.2); the
        #   order rides the range index, its slot is already resolved
        keys = jnp.stack([customer_key(cfg, inp.w_id, inp.d_id, inp.c_id),
                          jnp.zeros((T,), jnp.uint32)], axis=1)
        kmask = jnp.stack([act, jnp.zeros((T,), bool)], axis=1)
        n_probes = jnp.sum(kmask & mask)
    data, _, fcur = _snapshot_read(st, engine, vec, slots, mask, keys, kmask)
    order = data[:, 1, :]
    safe_o = o_slot_ext(lay, cfg, jnp.int32(0), jnp.int32(0))
    olslot = ol_slots_of_order(lay, cfg, jnp.where(found, oslot, safe_o))[
        :, None] + jnp.arange(MAX_OL)
    line_mask = (jnp.arange(MAX_OL)[None, :]
                 < order[:, O_COL["ol_cnt"], None]) & found[:, None]
    _, _, ol_cur = _snapshot_read(st, engine, vec, olslot, line_mask)
    slots = jnp.concatenate([slots, olslot], axis=1)
    mask = jnp.concatenate([mask, line_mask], axis=1)
    fcur = jnp.concatenate([fcur, ol_cur], axis=1)
    ops = si.count_readonly_ops(oracle, mask, fcur,
                                jnp.sum(act.astype(jnp.int32)),
                                st.nam.table.payload_width,
                                n_index_probes=n_probes)
    return ReadOnlyRoundResult(result=order, found=found, ops=ops,
                               read_slots=slots, read_mask=mask)


def stocklevel_round(cfg: TPCCConfig, lay: TPCCLayout, st: TPCCState,
                     oracle: VectorOracle, inp: workload.StockLevelInputs,
                     *, engine=None, active=None,
                     last_n: int = 8) -> ReadOnlyRoundResult:
    """Vectorized stock-level: distinct items with low stock among the last
    ``last_n`` orders' lines of (w, d) — a dependent-read chain (district →
    index scan → order lines → stocks), every record read hitting the pool.
    """
    T = inp.w_id.shape[0]
    act = _active_or_ones(T, active)
    vec = oracle.read(st.nam.oracle_state)
    dsl = d_slot(lay, inp.w_id, inp.d_id)
    ddata, _, dcur = _snapshot_read(st, engine, vec, dsl[:, None],
                                    act[:, None])
    next_o = ddata[:, 0, D_COL["next_o_id"]]
    lo = order_key(inp.w_id, inp.d_id, jnp.maximum(next_o - last_n, 0))
    hi = order_key(inp.w_id, inp.d_id, next_o)
    k, oslots, _ = ri.range_scan(st.order_index, lo, hi, max_results=last_n)
    valid = (k != ri.SENTINEL) & (oslots >= 0) & act[:, None]
    safe_o = o_slot_ext(lay, cfg, jnp.int32(0), jnp.int32(0))
    oslots = jnp.where(valid, oslots, safe_o)
    ol = (ol_slots_of_order(lay, cfg, oslots.reshape(-1))[:, None]
          + jnp.arange(MAX_OL)).reshape(T, last_n * MAX_OL)
    ol_mask = jnp.repeat(valid, MAX_OL, axis=1)
    ol_data, ol_found, ol_cur = _snapshot_read(st, engine, vec, ol, ol_mask)
    ol_ok = ol_found & ol_mask
    items = ol_data[:, :, OL_COL["i_id"]]
    w_bc = jnp.broadcast_to(inp.w_id[:, None], items.shape)
    safe_items = jnp.where(ol_ok, items, 0)
    ssl = s_slot(lay, cfg, w_bc, safe_items)
    skeys = skmask = None
    n_probes = 0
    if cfg.key_addressed:   # stocks are fetched by key (§5.2)
        skeys = stock_key(cfg, w_bc, safe_items)
        skmask = ol_ok
        n_probes = jnp.sum(skmask & ol_ok)
    s_data, s_found, s_cur = _snapshot_read(st, engine, vec, ssl, ol_ok,
                                            skeys, skmask)
    low = ol_ok & s_found \
        & (s_data[:, :, S_COL["quantity"]] < inp.threshold[:, None])
    marked = jnp.zeros((T, cfg.n_items), jnp.int32).at[
        jnp.arange(T)[:, None], jnp.where(low, items, cfg.n_items)].max(
        1, mode="drop")
    counts = jnp.sum(marked, axis=1)
    mask = jnp.concatenate([act[:, None], ol_mask, ol_ok], axis=1)
    fcur = jnp.concatenate([dcur, ol_cur, s_cur], axis=1)
    slots = jnp.concatenate([dsl[:, None], ol, ssl], axis=1)
    ops = si.count_readonly_ops(oracle, mask, fcur,
                                jnp.sum(act.astype(jnp.int32)),
                                st.nam.table.payload_width,
                                n_index_probes=n_probes)
    return ReadOnlyRoundResult(result=counts, found=act, ops=ops,
                               read_slots=slots, read_mask=mask)


def stocklevel(cfg: TPCCConfig, lay: TPCCLayout, st: TPCCState,
               oracle: VectorOracle, w_id, d_id, threshold: int,
               last_n: int = 20):
    """Read-only: distinct items in the last ``last_n`` orders' lines whose
    stock is below ``threshold`` — exercised via index range scan + bulk
    visible reads (the 'single RDMA request scans' of §5.1)."""
    vec = oracle.read(st.nam.oracle_state)
    dsl = d_slot(lay, w_id, d_id)
    dist = mvcc.read_visible(st.nam.table, jnp.atleast_1d(dsl), vec)
    next_o = dist.data[0, D_COL["next_o_id"]]
    lo = order_key(w_id, d_id, jnp.maximum(next_o - last_n, 0))
    hi = order_key(w_id, d_id, next_o)
    k, oslots, n = ri.range_scan(st.order_index, lo[None], hi[None],
                                 max_results=last_n)
    safe_o = o_slot_ext(lay, cfg, jnp.int32(0), jnp.int32(0))
    oslots = jnp.where(oslots[0] >= 0, oslots[0], safe_o)
    valid = (k[0] != ri.SENTINEL)
    # order lines are contiguous with each order's extend slot
    ol = (ol_slots_of_order(lay, cfg, oslots)[:, None]
          + jnp.arange(MAX_OL)[None, :]).reshape(-1)
    olr = mvcc.read_visible(st.nam.table, ol, vec)
    items = olr.data[:, OL_COL["i_id"]]
    ol_ok = olr.found & jnp.repeat(valid, MAX_OL)
    ssl = s_slot(lay, cfg, jnp.broadcast_to(w_id, items.shape), items)
    stk = mvcc.read_visible(st.nam.table, ssl, vec)
    low = ol_ok & stk.found & (stk.data[:, S_COL["quantity"]] < threshold)
    # distinct items: count unique item ids among low ones
    marked = jnp.zeros((cfg.n_items,), jnp.int32).at[
        jnp.where(low, items, cfg.n_items)].max(1, mode="drop")
    return jnp.sum(marked)


# -------------------------------------------------------------- delivery ----
class DeliveryResult(NamedTuple):
    state: TPCCState
    committed: jnp.ndarray      # bool [T] — txn outcome (vacuous if no order)
    delivered: jnp.ndarray      # bool [T] — committed AND an order was found
    ops: si.OpCounts
    batch: TxnBatch
    snapshot_miss: jnp.ndarray  # bool [T] — a required version was GC'd
    vis: si.VisStats
    journal: Optional[wal.Journal] = None   # §6.2 — set iff one was passed


class DeliveryAux(NamedTuple):
    """Per-round aux threaded to the delivery compute_fn (both paths)."""
    carrier: jnp.ndarray     # int32 [T]
    line_mask: jnp.ndarray   # bool [T, MAX_OL] — the order's real lines


def _delivery_prepare(cfg: TPCCConfig, lay: TPCCLayout, st: TPCCState,
                      vec, inp: workload.DeliveryInputs, active=None):
    """Locate the oldest undelivered order of (w, d) with snapshot pre-reads
    (district cursor → index → order payload), then build the SI batch.

    Read-set (RS=3+15): [district, order, customer, order-lines]; write-set
    (WS=3): district cursor, order carrier, customer balance. The order
    lines ride in the read-set so the customer credit is the *real* sum of
    the order's line amounts, not a placeholder."""
    T = inp.w_id.shape[0]
    act = _active_or_ones(T, active)
    dsl = d_slot(lay, inp.w_id, inp.d_id)
    pre = mvcc.read_visible(st.nam.table, dsl, vec)
    deliv_o = pre.data[:, D_COL["next_deliv"]]
    has_order = deliv_o < pre.data[:, D_COL["next_o_id"]]
    okey = order_key(inp.w_id, inp.d_id, deliv_o)
    k, oslot, idx_found = ri.lookup_max_below(st.order_index,
                                              okey + jnp.uint32(1))
    found = idx_found & (k == okey) & has_order & act
    oslot = jnp.where(found, oslot, o_slot_ext(lay, cfg, jnp.int32(0),
                                               jnp.int32(0)))
    ordr = mvcc.read_visible(st.nam.table, oslot, vec)
    c_id = ordr.data[:, O_COL["c_id"]]
    ol_cnt = ordr.data[:, O_COL["ol_cnt"]]
    csl = c_slot(lay, cfg, inp.w_id, inp.d_id, jnp.where(found, c_id, 0))
    olslot = ol_slots_of_order(lay, cfg, oslot)[:, None] + jnp.arange(MAX_OL)
    line_mask = (jnp.arange(MAX_OL)[None, :] < ol_cnt[:, None]) \
        & found[:, None]

    read_slots = jnp.concatenate(
        [dsl[:, None], oslot[:, None], csl[:, None], olslot], axis=1)
    read_mask = jnp.concatenate(
        [act[:, None], found[:, None], found[:, None], line_mask], axis=1)
    batch = TxnBatch(
        tid=jnp.arange(T, dtype=jnp.int32),
        read_slots=read_slots, read_mask=read_mask,
        write_ref=jnp.broadcast_to(jnp.arange(3)[None, :], (T, 3)).astype(
            jnp.int32),
        write_mask=jnp.stack([found, found, found], axis=1))
    aux = DeliveryAux(carrier=jnp.broadcast_to(
        jnp.asarray(inp.carrier, jnp.int32), (T,)), line_mask=line_mask)
    return batch, aux, found


def _delivery_new_data(rd, aux: DeliveryAux):
    """The delivery write-set: advance the district's delivery cursor, stamp
    the carrier, credit the customer with the order's total line amount."""
    d = rd[:, 0, :].at[:, D_COL["next_deliv"]].add(1)
    o = rd[:, 1, :].at[:, O_COL["carrier"]].set(aux.carrier)
    amount = jnp.sum(
        jnp.where(aux.line_mask, rd[:, 3:, OL_COL["amount"]], 0), axis=1)
    c = rd[:, 2, :]
    c = c.at[:, C_COL["balance"]].add(amount)
    c = c.at[:, C_COL["delivery_cnt"]].add(1)
    return jnp.stack([d, o, c], axis=1)


def _delivery_preread_ops(ops: si.OpCounts, n_active, payload_width):
    """Charge the two dependent snapshot pre-reads (district cursor, order
    payload) that locate the order before the SI round — identical in the
    single-shard and distributed paths."""
    rec_bytes = 8 + 4 * payload_width
    n_pre = 2 * n_active
    return ops._replace(record_reads=ops.record_reads + n_pre,
                        bytes_moved=ops.bytes_moved + n_pre * rec_bytes)


def delivery_round(cfg: TPCCConfig, lay: TPCCLayout, st: TPCCState,
                   oracle: VectorOracle, inp: workload.DeliveryInputs,
                   rts_vec=None, active=None, round_no=0,
                   journal=None) -> DeliveryResult:
    """Deliver the oldest undelivered order of (w,d): bump the district's
    delivery cursor, stamp the order's carrier, credit the customer with the
    sum of the order's line amounts.

    Dependent read (district → order slot) costs extra round trips: snapshot
    pre-reads locate the order, then the SI round re-reads and validates the
    district version — any race re-runs via abort, keeping atomicity.
    """
    vec = oracle.read(st.nam.oracle_state) if rts_vec is None else rts_vec
    batch, aux, found = _delivery_prepare(cfg, lay, st, vec, inp, active)
    out = si.run_round(st.nam.table, oracle, st.nam.oracle_state, batch,
                       lambda rh, rd, v: _delivery_new_data(rd, aux),
                       rts_vec=rts_vec, active=active,
                       journal=journal, journal_round=round_no,
                       journal_seq=_JSEQ_DELIVERY,
                       fused_commit=cfg.fused_commit,
                       batched_probe=cfg.batched_probe)
    nam = st.nam._replace(table=out.table, oracle_state=out.oracle_state)
    ops = _delivery_preread_ops(out.ops, _n_active(batch, active),
                                out.table.payload_width)
    return DeliveryResult(
        state=st._replace(nam=nam),
        committed=out.committed, delivered=out.committed & found, ops=ops,
        batch=batch, snapshot_miss=out.snapshot_miss, vis=out.vis,
        journal=out.journal)


def delivery_round_distributed(cfg: TPCCConfig, lay: TPCCLayout,
                               st: TPCCState, oracle: VectorOracle, engine,
                               inp: workload.DeliveryInputs,
                               active=None, round_no=0,
                               journal=None) -> DeliveryResult:
    """Delivery through :func:`store.distributed_round` on the mesh —
    bit-identical to :func:`delivery_round` (the pre-reads gather from the
    sharded pool; the SI round runs shard-side)."""
    vec = oracle.read(st.nam.oracle_state)
    batch, aux, found = _delivery_prepare(cfg, lay, st, vec, inp, active)
    jkw = dict(journal=journal, round_no=round_no,
               seq=_JSEQ_DELIVERY) if journal is not None else {}
    res = engine.delivery_fn(st.nam.table, st.nam.oracle_state.vec,
                             batch, aux, active, **jkw)
    tbl, nvec, out = res[:3]
    journal = res[3] if journal is not None else None
    ops = _delivery_preread_ops(_dist_ops(oracle, batch, out, tbl, active),
                                _n_active(batch, active),
                                tbl.payload_width)
    nam = st.nam._replace(table=tbl, oracle_state=VectorState(vec=nvec))
    return DeliveryResult(
        state=st._replace(nam=nam),
        committed=out.committed, delivered=out.committed & found, ops=ops,
        batch=batch, snapshot_miss=out.snapshot_miss,
        vis=_dist_vis(batch, out, active), journal=journal)
