"""Tests: hash table, range index, catalog, extends, locality, cost model."""
import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro import compat
from repro.core import catalog as cat, hashtable as ht, locality, netmodel
from repro.core import rangeindex as ri
from repro.core import store as store_mod
from repro.core.tsoracle import VectorOracle


# ----------------------------------------------------------- hash table ----
def test_hashtable_insert_lookup_roundtrip():
    t = ht.init(64)
    keys = jnp.array([3, 17, 99, 3 + 64], jnp.uint32)  # 3 and 67 may collide
    vals = jnp.array([30, 170, 990, 670], jnp.int32)
    t, placed = ht.insert(t, keys, vals)
    assert int((placed >= 0).sum()) == 4
    got, found = ht.lookup(t, keys)
    assert bool(found.all())
    np.testing.assert_array_equal(np.asarray(got), [30, 170, 990, 670])


def test_hashtable_missing_key():
    t = ht.init(32)
    t, _ = ht.insert(t, jnp.array([5], jnp.uint32), jnp.array([1], jnp.int32))
    _, found = ht.lookup(t, jnp.array([6], jnp.uint32))
    assert not bool(found[0])


def test_hashtable_update_in_place():
    t = ht.init(32)
    t, _ = ht.insert(t, jnp.array([5], jnp.uint32), jnp.array([1], jnp.int32))
    t, _ = ht.insert(t, jnp.array([5], jnp.uint32), jnp.array([2], jnp.int32))
    got, found = ht.lookup(t, jnp.array([5], jnp.uint32))
    assert bool(found[0]) and int(got[0]) == 2


def test_hashtable_batch_duplicate_keys_single_winner():
    t = ht.init(32)
    t, placed = ht.insert(t, jnp.array([7, 7], jnp.uint32),
                          jnp.array([10, 20], jnp.int32))
    got, found = ht.lookup(t, jnp.array([7], jnp.uint32))
    assert bool(found[0]) and int(got[0]) in (10, 20)


def test_hashtable_fills_to_capacity():
    n = 16
    t = ht.init(n)
    keys = jnp.arange(n, dtype=jnp.uint32) * 37 + 1
    t, placed = ht.insert(t, keys, jnp.arange(n, dtype=jnp.int32),
                          max_probes=n)
    assert int((placed >= 0).sum()) == n
    got, found = ht.lookup(t, keys, max_probes=n)
    assert bool(found.all())
    np.testing.assert_array_equal(np.asarray(got), np.arange(n))
    # the store-level directory build must surface probe exhaustion loudly:
    # same keys into the same capacity is fine …
    d = store_mod.build_directory(keys, jnp.arange(n, dtype=jnp.int32), n,
                                  max_probes=n)
    got, found = ht.lookup(d, keys, max_probes=n)
    assert bool(found.all())
    # … one key beyond capacity (or a too-short probe budget) is an error,
    # never a silently dropped entry (insert's placed_at == -1)
    over = jnp.concatenate([keys, jnp.array([9999], jnp.uint32)])
    with pytest.raises(ValueError, match="probe chains exceeded"):
        store_mod.build_directory(over, jnp.arange(n + 1, dtype=jnp.int32),
                                  n, max_probes=n + 1)
    # two keys sharing a home bucket cannot both place with max_probes=1
    collide = [k for k in range(1, 2000)
               if (k * 2654435769 % (1 << 32)) % n == 0][:2]
    with pytest.raises(ValueError, match="probe chains exceeded"):
        store_mod.build_directory(jnp.asarray(collide, jnp.uint32),
                                  jnp.array([0, 1], jnp.int32), n,
                                  max_probes=1)


def test_hashtable_delete_lookup_reinsert():
    """Regression: delete-then-lookup used to return found=True, val=-1 —
    any caller gathering with that slot silently read the last pool record."""
    t = ht.init(32)
    t, _ = ht.insert(t, jnp.array([5, 9], jnp.uint32),
                     jnp.array([50, 90], jnp.int32))
    t, was_there = ht.delete(t, jnp.array([5], jnp.uint32))
    assert bool(was_there[0])
    got, found = ht.lookup(t, jnp.array([5, 9], jnp.uint32))
    assert not bool(found[0]), "deleted key must report found=False"
    assert bool(found[1]) and int(got[1]) == 90
    # the invalidated entry still terminates the probe chain and supports
    # update-in-place reinsertion
    t, placed = ht.insert(t, jnp.array([5], jnp.uint32),
                          jnp.array([55], jnp.int32))
    assert int(placed[0]) >= 0
    got, found = ht.lookup(t, jnp.array([5], jnp.uint32))
    assert bool(found[0]) and int(got[0]) == 55


def test_hashtable_lookup_shard_matches_lookup():
    """Partitioned probing (every shard walks the global probe sequence over
    its resident bucket range) reconstructs lookup() bit-exactly — including
    deleted entries and missing keys."""
    B, n_shards = 64, 4
    t = ht.init(B)
    keys = jnp.arange(1, 40, dtype=jnp.uint32) * 97
    t, _ = ht.insert(t, keys, jnp.arange(39, dtype=jnp.int32), max_probes=B)
    t, _ = ht.delete(t, keys[5:9])
    qs = jnp.concatenate([keys, jnp.array([7, 100000], jnp.uint32)])
    want_v, want_f = ht.lookup(t, qs, max_probes=B)
    per = B // n_shards
    vsum = jnp.zeros(qs.shape, jnp.int32)
    khit = jnp.zeros(qs.shape, bool)
    for s in range(n_shards):
        v, h = ht.lookup_shard(t.keys[s * per:(s + 1) * per],
                               t.vals[s * per:(s + 1) * per], qs, s * per,
                               B, max_probes=B)
        vsum = vsum + v
        khit = khit | h
    got_f = khit & (vsum >= 0)
    np.testing.assert_array_equal(np.asarray(got_f), np.asarray(want_f))
    np.testing.assert_array_equal(np.asarray(jnp.where(got_f, vsum, -1)),
                                  np.asarray(jnp.where(want_f, want_v, -1)))
    # owner of each key's home bucket agrees with partition_of
    owners = ht.partition_of(keys, B, n_shards)
    assert int(jnp.max(owners)) < n_shards and int(jnp.min(owners)) >= 0


# ----------------------------------------------------------- range index ----
def test_rangeindex_scan_and_insert():
    idx = ri.build(jnp.array([10, 20, 30, 40], jnp.uint32),
                   jnp.array([1, 2, 3, 4], jnp.int32), capacity=16)
    k, v, n = ri.range_scan(idx, jnp.array([15]), jnp.array([45]),
                            max_results=8)
    assert int(n[0]) == 3
    np.testing.assert_array_equal(np.asarray(v[0, :3]), [2, 3, 4])
    idx = ri.insert(idx, jnp.array([25], jnp.uint32),
                    jnp.array([9], jnp.int32))
    k, v, n = ri.range_scan(idx, jnp.array([20]), jnp.array([31]),
                            max_results=8)
    assert int(n[0]) == 3
    assert 9 in np.asarray(v[0])


def test_rangeindex_merge_preserves_entries():
    idx = ri.build(jnp.array([5], jnp.uint32), jnp.array([50], jnp.int32),
                   capacity=8)
    idx = ri.insert(idx, jnp.array([3], jnp.uint32), jnp.array([30], jnp.int32))
    idx = ri.merge(idx)
    k, v, n = ri.range_scan(idx, jnp.array([0]), jnp.array([10]),
                            max_results=4)
    assert int(n[0]) == 2
    np.testing.assert_array_equal(np.asarray(v[0, :2]), [30, 50])


def test_rangeindex_lookup_max_below():
    idx = ri.build(jnp.array([10, 20, 30], jnp.uint32),
                   jnp.array([1, 2, 3], jnp.int32), capacity=8)
    k, v, found = ri.lookup_max_below(idx, jnp.array([25]))
    assert bool(found[0]) and int(k[0]) == 20 and int(v[0]) == 2
    _, _, found0 = ri.lookup_max_below(idx, jnp.array([10]))
    assert not bool(found0[0])


# -------------------------------------------------------------- catalog ----
def test_catalog_layout_and_versioning():
    c = cat.Catalog(n_servers=4)
    a = c.create_table("a", count=100, width=4)
    b = c.create_table("b", count=50, width=8)
    assert a.base == 0 and b.base == 100 and c.total_records == 150
    assert int(b.slot(7)) == 107
    st = c.init_state()
    cached = st
    st2 = c.alter(st, "b")
    assert bool(c.needs_refresh(st2, cached).any())
    assert not bool(c.needs_refresh(st, cached).any())


def test_extend_allocator_no_conflicts():
    ext = store_mod.ExtendState(cursor=jnp.zeros((4, 1), jnp.int32))
    slots = []
    for tid in range(4):
        ext, first = store_mod.allocate(ext, tid, 0, 3, region_base=1000,
                                        extend_size=10, threads=4)
        slots.append(int(first))
    assert slots == [1000, 1010, 1020, 1030]
    ext, nxt = store_mod.allocate(ext, 0, 0, 1, 1000, 10, 4)
    assert int(nxt) == 1003  # cursor advanced by the earlier n=3


# ------------------------------------------------------------- locality ----
def test_local_fraction():
    p = locality.Placement(n_servers=4, shard_records=100)
    txn_server = jnp.array([0, 1], jnp.int32)
    slots = jnp.array([[5, 150], [150, 350]], jnp.int32)
    mask = jnp.ones((2, 2), bool)
    f = locality.local_fraction(p, txn_server, slots, mask)
    assert abs(float(f) - 0.5) < 1e-6


# ------------------------------------------------------------- netmodel ----
def test_netmodel_anchor_points():
    """The calibrated model must land on the paper's anchors (±20 %)."""
    m = netmodel
    assert 24e3 < m.intro_example_throughput() < 34e3          # ~29 k (§1.1)
    naive = m.oracle_throughput("naive", 1, 10)
    assert 1.5e6 < naive < 2.5e6                               # ~2 M
    basic = m.oracle_throughput("vector", 8, 20)
    assert 16e6 < basic < 25e6                                 # ~20 M
    bg = m.oracle_throughput("vector_bg", 8, 20)
    assert 30e6 < bg < 42e6                                    # ~36 M
    comp = m.oracle_throughput("vector_compressed", 8, 20)
    assert 64e6 < comp < 96e6                                  # ~80 M
    both = m.oracle_throughput("vector_both", 8, 20)
    assert 108e6 < both < 170e6                                # ~135 M


def test_netmodel_naive_degrades_with_clients():
    a = netmodel.oracle_throughput("naive", 2, 10)
    b = netmodel.oracle_throughput("naive", 8, 20)
    assert b < a  # paper: >20 clients the naive oracle degrades


def test_netmodel_namdb_scales_linearly():
    p = netmodel.TxnProfile(reads=23, cas=11, installs=11, bytes_read=4000,
                            bytes_written=3000)
    t1 = netmodel.namdb_throughput(p, 14, 60, abort_rate=0.02)
    t2 = netmodel.namdb_throughput(p, 28, 60, abort_rate=0.02)
    t3 = netmodel.namdb_throughput(p, 56, 60, abort_rate=0.02)
    assert 1.8 < t2 / t1 < 2.2 and 1.8 < t3 / t2 < 2.2


def test_netmodel_traditional_degrades():
    p = netmodel.TxnProfile(reads=23, cas=11, installs=11, bytes_read=4000,
                            bytes_written=3000)
    ts = [netmodel.traditional_throughput(p, n, 60, 0.02)
          for n in (2, 10, 56)]
    assert ts[1] < 10 * ts[0]          # sub-linear well before 10 machines
    nam = netmodel.namdb_throughput(p, 56, 60, 0.02)
    assert nam > 5 * ts[2]             # NAM-DB wins by a wide margin at 56


def test_netmodel_locality_bonus_moderate():
    """§7.3: locality buys ~30 %, not orders of magnitude."""
    p = netmodel.TxnProfile(reads=23, cas=11, installs=11, bytes_read=4000,
                            bytes_written=3000)
    t0 = netmodel.namdb_throughput(p, 8, 20, 0.02, local_fraction=0.0)
    t9 = netmodel.namdb_throughput(p, 8, 20, 0.02, local_fraction=0.9)
    assert 1.1 < t9 / t0 < 2.0


def test_hstore_anchors():
    assert abs(netmodel.hstore_like_throughput(0.0) - 11000) < 1
    assert abs(netmodel.hstore_like_throughput(1.0) - 900) < 1


# --------------------------------- non-dividing shard counts (scale-out) ----
def test_pad_vector_non_dividing():
    """A 3→5-style expansion leaves the timestamp vector length
    non-divisible by the shard count; ``pad_vector`` must square it off with
    zero slots (and be the identity when it already divides)."""
    vec = jnp.arange(1, 13, dtype=jnp.uint32)            # 12 slots
    padded, n = store_mod.pad_vector(vec, 8)
    assert n == 16 and padded.shape == (16,)
    np.testing.assert_array_equal(np.asarray(padded[:12]), np.asarray(vec))
    np.testing.assert_array_equal(np.asarray(padded[12:]),
                                  np.zeros(4, np.uint32))
    same, n = store_mod.pad_vector(vec, 4)
    assert n == 12 and same is vec


@pytest.mark.skipif(len(jax.devices()) < 2, reason="needs a >=2 device mesh")
def test_shard_vector_non_dividing_round_matches_reference():
    """Regression: ``distributed_round(shard_vector=True)`` used to REQUIRE
    ``n_slots % n_shards == 0``, so a mesh grown to a non-dividing size
    (e.g. 3→5 memory servers) could not host the partitioned T_R at all.
    With zero-padding the partitioned vector must stay bit-identical to the
    single-shard reference on the real slots."""
    from repro.core import mvcc, si
    mesh = compat.mesh(2)
    n_records, width, n_threads = 16, 4, 3      # 3 slots over 2 shards
    oracle = VectorOracle(n_threads)

    def compute_fn(rh, rd, vec, aux):
        return rd[:, :1, :].at[..., 0].add(1)

    round_fn, _ = store_mod.distributed_round(
        mesh, "mem", oracle, compute_fn, n_records // 2, shard_vector=True)
    tbl_d = store_mod.shard_table(mesh, "mem",
                                  mvcc.init_table(n_records, width, 2, 2))
    tbl_s = mvcc.init_table(n_records, width, 2, 2)
    st = oracle.init()
    vec_d = store_mod.shard_vector(mesh, "mem", st.vec)
    assert vec_d.shape == (4,)       # padded to the 2-shard multiple
    key = jax.random.PRNGKey(3)
    for rnd in range(4):
        key, sub = jax.random.split(key)
        slots = jax.random.randint(sub, (n_threads, 2), 0, n_records,
                                   dtype=jnp.int32)
        batch = si.TxnBatch(
            tid=jnp.arange(n_threads, dtype=jnp.int32),
            read_slots=slots,
            read_mask=jnp.ones((n_threads, 2), bool),
            write_ref=jnp.zeros((n_threads, 1), jnp.int32),
            write_mask=jnp.ones((n_threads, 1), bool))
        tbl_d, vec_d, dout = round_fn(tbl_d, vec_d, batch, None)
        out = si.run_round(tbl_s, oracle, st, batch,
                           lambda rh, rd, vec: compute_fn(rh, rd, vec, None))
        tbl_s, st = out.table, out.oracle_state
        np.testing.assert_array_equal(np.asarray(dout.committed),
                                      np.asarray(out.committed),
                                      err_msg=str(rnd))
    got = np.asarray(jax.device_get(vec_d))
    np.testing.assert_array_equal(got[:3], np.asarray(st.vec))
    np.testing.assert_array_equal(got[3:], np.zeros(1, np.uint32))
    np.testing.assert_array_equal(
        np.asarray(jax.device_get(tbl_d.cur_data)), np.asarray(tbl_s.cur_data))


def test_moved_slots_expansion_mask():
    """Scale-out migration set: exactly the slots whose owning memory server
    changes between the old and new range partitions."""
    old = locality.Placement(n_servers=2, shard_records=8)   # 16 slots
    new = locality.Placement(n_servers=4, shard_records=4)
    moved = np.asarray(locality.moved_slots(old, new, 16))
    s = np.arange(16)
    np.testing.assert_array_equal(moved, (s // 8) != (s // 4))
    assert moved.sum() == 12 and not moved[:4].any()


def test_moved_buckets_expansion_mask():
    """§5.2 directory repartition: buckets whose owner changes when the mesh
    grows (non-dividing new count exercises the ceil-partition)."""
    mb = np.asarray(ht.moved_buckets(64, 2, 3))
    b = np.arange(64)
    old_per, new_per = 32, -(-64 // 3)
    np.testing.assert_array_equal(mb, (b // old_per) != (b // new_per))
    assert not np.asarray(ht.moved_buckets(64, 4, 4)).any()
