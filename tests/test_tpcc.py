"""TPC-C integration tests: consistency invariants the benchmark defines."""
import os
import subprocess
import sys

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.core import header as hdr, mvcc
from repro.core.tsoracle import VectorOracle
from repro.db import tpcc, workload

import _readonly_common as roc


CFG = tpcc.TPCCConfig(n_warehouses=2, customers_per_district=8, n_items=64,
                      n_threads=8, orders_per_thread=32, dist_degree=100.0)


@pytest.fixture(scope="module")
def loaded():
    oracle = VectorOracle(CFG.n_threads)
    lay, st = tpcc.init_tpcc(CFG, oracle, jax.random.PRNGKey(0))
    return oracle, lay, st


def _run_neworders(oracle, lay, st, n_rounds=6, seed=1, cfg=CFG):
    logits = workload.zipf_logits(cfg.n_items, cfg.skew_alpha)
    key = jax.random.PRNGKey(seed)
    committed_total = 0
    o_ids = []
    for r in range(n_rounds):
        key, sub = jax.random.split(key)
        inp = workload.gen_neworder(sub, cfg.n_threads, cfg.n_warehouses,
                                    cfg.n_items, cfg.customers_per_district,
                                    None, cfg.dist_degree, logits)
        out = tpcc.neworder_round(cfg, lay, st, oracle, inp, round_no=r)
        st = out.state
        st = st._replace(nam=st.nam._replace(
            table=mvcc.version_mover(st.nam.table)))
        committed_total += int(np.asarray(out.committed).sum())
        o_ids.append((np.asarray(inp.w_id), np.asarray(inp.d_id),
                      np.asarray(out.o_id), np.asarray(out.committed)))
    return st, committed_total, o_ids


def test_neworder_commits_and_advances_district(loaded):
    oracle, lay, st0 = loaded
    st, n_committed, _ = _run_neworders(oracle, lay, st0)
    assert n_committed > 0
    # consistency: sum over districts of d_next_o_id == total committed orders
    dspec = lay.catalog["district"]
    next_ids = np.asarray(
        st.nam.table.cur_data[dspec.base:dspec.end,
                              tpcc.D_COL["next_o_id"]])
    assert next_ids.sum() == n_committed


def test_neworder_unique_o_ids_per_district(loaded):
    """SI must serialize d_next_o_id: no duplicate (w,d,o_id) among commits."""
    oracle, lay, st0 = loaded
    _, _, rounds = _run_neworders(oracle, lay, st0, seed=2)
    seen = set()
    for w, d, o, c in rounds:
        for i in range(len(w)):
            if c[i]:
                key = (int(w[i]), int(d[i]), int(o[i]))
                assert key not in seen, f"duplicate order id {key}"
                seen.add(key)


def test_neworder_stock_consistency(loaded):
    """Committed orders' quantities are all applied exactly once:
    sum(s_ytd) == sum of committed order quantities."""
    oracle, lay, st0 = loaded
    cfg = CFG
    logits = workload.zipf_logits(cfg.n_items, None)
    oracle = VectorOracle(cfg.n_threads)
    lay, st = tpcc.init_tpcc(cfg, oracle, jax.random.PRNGKey(3))
    key = jax.random.PRNGKey(4)
    expected_ytd = 0
    for r in range(5):
        key, sub = jax.random.split(key)
        inp = workload.gen_neworder(sub, cfg.n_threads, cfg.n_warehouses,
                                    cfg.n_items, cfg.customers_per_district,
                                    None, cfg.dist_degree, logits)
        out = tpcc.neworder_round(cfg, lay, st, oracle, inp, round_no=r)
        st = out.state
        st = st._replace(nam=st.nam._replace(
            table=mvcc.version_mover(st.nam.table)))
        c = np.asarray(out.committed)
        qty = np.asarray(inp.qty)
        lm = np.arange(tpcc.MAX_OL)[None, :] < np.asarray(inp.ol_cnt)[:, None]
        expected_ytd += int((qty * lm * c[:, None]).sum())
    sspec = lay.catalog["stock"]
    got = int(np.asarray(
        st.nam.table.cur_data[sspec.base:sspec.end, tpcc.S_COL["ytd"]]).sum())
    assert got == expected_ytd


def test_payment_balance_conservation():
    cfg = CFG
    oracle = VectorOracle(cfg.n_threads)
    lay, st = tpcc.init_tpcc(cfg, oracle, jax.random.PRNGKey(5))
    key = jax.random.PRNGKey(6)
    total_paid = 0
    for r in range(5):
        key, sub = jax.random.split(key)
        inp = workload.gen_payment(sub, cfg.n_threads, cfg.n_warehouses,
                                   cfg.customers_per_district)
        res = tpcc.payment_round(cfg, lay, st, oracle, inp)
        st = res.state
        st = st._replace(nam=st.nam._replace(
            table=mvcc.version_mover(st.nam.table)))
        c = np.asarray(res.committed)
        total_paid += int((np.asarray(inp.amount) * c).sum())
    wspec = lay.catalog["warehouse"]
    w_ytd = int(np.asarray(
        st.nam.table.cur_data[wspec.base:wspec.end,
                              tpcc.W_COL["ytd"]]).sum())
    cspec = lay.catalog["customer"]
    c_bal = int(np.asarray(
        st.nam.table.cur_data[cspec.base:cspec.end,
                              tpcc.C_COL["balance"]]).sum())
    assert w_ytd == total_paid          # TPC-C consistency condition 1
    assert c_bal == -total_paid         # money left customers' balances


def _customer_balance_sum(lay, st):
    cspec = lay.catalog["customer"]
    return int(np.asarray(
        st.nam.table.cur_data[cspec.base:cspec.end,
                              tpcc.C_COL["balance"]]).sum())


def test_delivery_credits_order_line_sum():
    """Balance conservation through delivery: the customer is credited the
    *sum of the order's line amounts* — computed independently here from the
    delivered orders' order-line records."""
    cfg = CFG
    oracle = VectorOracle(cfg.n_threads)
    lay, st = tpcc.init_tpcc(cfg, oracle, jax.random.PRNGKey(21))
    st, n, rounds = _run_neworders(oracle, lay, st, n_rounds=3, seed=22)
    assert n > 0
    assert _customer_balance_sum(lay, st) == 0

    key = jax.random.PRNGKey(23)
    expected = 0
    for r in range(3):
        key, sub = jax.random.split(key)
        inp = workload.gen_delivery(sub, cfg.n_threads, cfg.n_warehouses)
        res = tpcc.delivery_round(cfg, lay, st, oracle, inp)
        # independent expectation: each delivered (w,d) credits the line-sum
        # of its oldest undelivered order, read back from the OL records
        deliv = np.asarray(res.delivered)
        slots = np.asarray(res.batch.read_slots)      # [T, 3+15]
        masks = np.asarray(res.batch.read_mask)
        data = np.asarray(st.nam.table.cur_data)      # pre-round snapshot
        for i in range(cfg.n_threads):
            if deliv[i]:
                ol = slots[i, 3:][masks[i, 3:]]
                expected += int(data[ol, tpcc.OL_COL["amount"]].sum())
        st = res.state
        st = st._replace(nam=st.nam._replace(
            table=mvcc.version_mover(st.nam.table)))
    assert expected > 0, "no delivery committed — test config too small"
    assert _customer_balance_sum(lay, st) == expected


def test_orderstatus_empty_district_not_found():
    """Bugfix: a district with no orders must report found=False, not leak
    another district's latest order through lookup_max_below."""
    cfg = CFG
    oracle = VectorOracle(cfg.n_threads)
    lay, st = tpcc.init_tpcc(cfg, oracle, jax.random.PRNGKey(31))
    # an order exists ONLY in (w=0, d=3)
    logits = workload.zipf_logits(cfg.n_items, None)
    key = jax.random.PRNGKey(32)
    inp = workload.gen_neworder(key, cfg.n_threads, cfg.n_warehouses,
                                cfg.n_items, cfg.customers_per_district,
                                None, 0.0, logits)
    inp = inp._replace(w_id=jnp.zeros_like(inp.w_id),
                       d_id=jnp.full_like(inp.d_id, 3))
    out = tpcc.neworder_round(cfg, lay, st, oracle, inp)
    st = out.state
    assert int(np.asarray(out.committed).sum()) > 0
    # (w=1, d=5) has no orders: its latest-order lookup lands on (0,3)'s key
    cust, ordr, found = tpcc.orderstatus(
        cfg, lay, st, oracle, jnp.array([1]), jnp.array([5]), jnp.array([0]))
    assert not bool(found[0])
    # the district that does have orders still resolves
    cust, ordr, found = tpcc.orderstatus(
        cfg, lay, st, oracle, jnp.array([0]), jnp.array([3]), jnp.array([0]))
    assert bool(found[0]) and bool(ordr.found[0])


def test_orderstatus_and_delivery_at_district_zero():
    """Regression: order key 0 (w=0, d=0, o_id=0) must win lookup_max_below's
    tie-break — it previously lost to a non-qualifying candidate and came
    back as found=True with slot -1, corrupting orderstatus reads and
    delivery's write-set."""
    cfg = CFG
    oracle = VectorOracle(cfg.n_threads)
    lay, st = tpcc.init_tpcc(cfg, oracle, jax.random.PRNGKey(51))
    logits = workload.zipf_logits(cfg.n_items, None)
    inp = workload.gen_neworder(jax.random.PRNGKey(52), cfg.n_threads,
                                cfg.n_warehouses, cfg.n_items,
                                cfg.customers_per_district, None, 0.0, logits)
    inp = inp._replace(w_id=jnp.zeros_like(inp.w_id),
                       d_id=jnp.zeros_like(inp.d_id))
    out = tpcc.neworder_round(cfg, lay, st, oracle, inp)
    st = out.state
    assert int(np.asarray(out.committed).sum()) > 0
    oslot, found = tpcc._latest_order_of(st.order_index, jnp.array([0]),
                                         jnp.array([0]))
    assert bool(found[0]) and int(oslot[0]) >= 0
    cust, ordr, osfound = tpcc.orderstatus(
        cfg, lay, st, oracle, jnp.array([0]), jnp.array([0]), jnp.array([0]))
    assert bool(osfound[0]) and bool(ordr.found[0])
    assert int(ordr.data[0, tpcc.O_COL["o_id"]]) == 0
    dinp = workload.DeliveryInputs(w_id=jnp.array([0], jnp.int32),
                                   d_id=jnp.array([0], jnp.int32),
                                   carrier=jnp.array([3], jnp.int32))
    res = tpcc.delivery_round(cfg, lay, st, oracle, dinp)
    assert bool(res.delivered[0])
    assert int(np.asarray(res.batch.read_slots)[0, 1]) == int(oslot[0])
    dd = res.state.nam.table.cur_data[tpcc.d_slot(lay, jnp.array([0]),
                                                  jnp.array([0]))[0]]
    assert int(dd[tpcc.D_COL["next_deliv"]]) == 1


def test_mixed_rounds_full_mix_invariants():
    """The mixed driver runs all five types; per-type commits are consistent
    with the database state (d_next_o_id sum == new-order commits; money
    conservation incl. delivery credits)."""
    cfg = tpcc.TPCCConfig(n_warehouses=2, customers_per_district=8,
                          n_items=64, n_threads=16, orders_per_thread=32,
                          dist_degree=50.0)
    oracle = VectorOracle(cfg.n_threads)
    lay, st = tpcc.init_tpcc(cfg, oracle, jax.random.PRNGKey(41))
    st, stats = tpcc.run_mixed_rounds(cfg, lay, st, oracle,
                                      jax.random.PRNGKey(42), 8)
    assert stats.total_attempts == 8 * cfg.n_threads
    for name in workload.TXN_TYPES:
        assert stats.attempts[name] > 0, f"type {name} never sampled"
    # read-only types never abort
    assert stats.commits["orderstatus"] == stats.attempts["orderstatus"]
    assert stats.commits["stocklevel"] == stats.attempts["stocklevel"]
    assert stats.commits["neworder"] > 0
    assert stats.commits["payment"] > 0
    # d_next_o_id advances once per committed new-order
    dspec = lay.catalog["district"]
    next_ids = np.asarray(
        st.nam.table.cur_data[dspec.base:dspec.end, tpcc.D_COL["next_o_id"]])
    assert next_ids.sum() == stats.commits["neworder"]
    # delivery cursor advances once per delivered order
    deliv = np.asarray(
        st.nam.table.cur_data[dspec.base:dspec.end,
                              tpcc.D_COL["next_deliv"]])
    assert deliv.sum() == stats.delivered
    # read-only ops: no CAS, no writes, but reads were counted
    for name in ("orderstatus", "stocklevel"):
        assert float(stats.ops[name].cas_ops) == 0.0
        assert float(stats.ops[name].writes) == 0.0
        assert float(stats.ops[name].record_reads) > 0.0


def test_orderstatus_reads_inserted_order():
    cfg = CFG
    oracle = VectorOracle(cfg.n_threads)
    lay, st = tpcc.init_tpcc(cfg, oracle, jax.random.PRNGKey(7))
    st, n, rounds = _run_neworders(oracle, lay, st, n_rounds=3, seed=8,
                                   cfg=cfg)
    assert n > 0
    w, d, o, c = rounds[-1]
    i = int(np.argmax(c))  # a committed txn from the last round
    cust, ordr, found = tpcc.orderstatus(
        cfg, lay, st, oracle, jnp.array([w[i]]), jnp.array([d[i]]),
        jnp.array([0]))
    assert bool(found[0])
    assert bool(ordr.found[0])
    assert int(ordr.data[0, tpcc.O_COL["carrier"]]) == -1  # not delivered


def test_delivery_advances_cursor_and_sets_carrier():
    cfg = CFG
    oracle = VectorOracle(cfg.n_threads)
    lay, st = tpcc.init_tpcc(cfg, oracle, jax.random.PRNGKey(9))
    st, n, rounds = _run_neworders(oracle, lay, st, n_rounds=3, seed=10,
                                   cfg=cfg)
    w, d, o, c = rounds[0]
    i = int(np.argmax(c))
    inp = workload.DeliveryInputs(w_id=jnp.array([w[i]], jnp.int32),
                                  d_id=jnp.array([d[i]], jnp.int32),
                                  carrier=jnp.array([7], jnp.int32))
    res = tpcc.delivery_round(cfg, lay, st, oracle, inp)
    assert bool(res.delivered[0])
    dsl = tpcc.d_slot(lay, jnp.array([w[i]]), jnp.array([d[i]]))
    dd = res.state.nam.table.cur_data[dsl[0]]
    assert int(dd[tpcc.D_COL["next_deliv"]]) == 1


def test_stocklevel_counts_low_stock():
    cfg = CFG
    oracle = VectorOracle(cfg.n_threads)
    lay, st = tpcc.init_tpcc(cfg, oracle, jax.random.PRNGKey(11))
    st, n, rounds = _run_neworders(oracle, lay, st, n_rounds=3, seed=12,
                                   cfg=cfg)
    w, d, o, c = rounds[0]
    i = int(np.argmax(c))
    cnt = tpcc.stocklevel(cfg, lay, st, oracle, jnp.array(w[i]),
                          jnp.array(d[i]), threshold=101)
    assert int(cnt) >= 0  # executes; with threshold=101 any touched item counts


def test_contention_raises_aborts():
    """Exp-4 mechanism: higher zipf skew ⇒ more write-write conflicts."""
    rates = {}
    for alpha in (None, 2.0):
        cfg = tpcc.TPCCConfig(n_warehouses=1, customers_per_district=8,
                              n_items=256, n_threads=16,
                              orders_per_thread=64, dist_degree=0.0,
                              skew_alpha=alpha)
        oracle = VectorOracle(cfg.n_threads)
        lay, st = tpcc.init_tpcc(cfg, oracle, jax.random.PRNGKey(13))
        logits = workload.zipf_logits(cfg.n_items, alpha)
        key = jax.random.PRNGKey(14)
        total, commits = 0, 0
        for r in range(6):
            key, sub = jax.random.split(key)
            inp = workload.gen_neworder(
                sub, cfg.n_threads, cfg.n_warehouses, cfg.n_items,
                cfg.customers_per_district, None, 0.0, logits)
            out = tpcc.neworder_round(cfg, lay, st, oracle, inp, round_no=r)
            st = out.state
            st = st._replace(nam=st.nam._replace(
                table=mvcc.version_mover(st.nam.table)))
            commits += int(np.asarray(out.committed).sum())
            total += cfg.n_threads
        rates[alpha] = 1.0 - commits / total
    assert rates[2.0] > rates[None]


def test_key_addressed_matches_slot_addressed():
    """§5.2 key-addressed execution (item/stock reads + the orderstatus
    customer and stocklevel stock reads resolved through the hash index)
    must be bit-identical to the analytic slot-addressed engine: the index
    is an access path, not a semantics change. Also asserts the directory
    probes are charged to the op profile."""
    base = dict(n_warehouses=2, customers_per_district=8, n_items=64,
                n_threads=8, orders_per_thread=16, dist_degree=50.0)
    runs = {}
    for ka in (False, True):
        cfg = tpcc.TPCCConfig(key_addressed=ka, **base)
        oracle = VectorOracle(cfg.n_threads)
        lay, st = tpcc.init_tpcc(cfg, oracle, jax.random.PRNGKey(0))
        st, stats = tpcc.run_mixed_rounds(cfg, lay, st, oracle,
                                          jax.random.PRNGKey(3), 3)
        runs[ka] = (lay, st, stats)
    lay, st_s, ms = runs[False]
    _, st_k, mk = runs[True]
    assert st_k.directory is not None and st_s.directory is None
    for field in mvcc.VersionedTable._fields:
        np.testing.assert_array_equal(
            np.asarray(getattr(st_k.nam.table, field)),
            np.asarray(getattr(st_s.nam.table, field)), err_msg=field)
    np.testing.assert_array_equal(np.asarray(st_k.nam.oracle_state.vec),
                                  np.asarray(st_s.nam.oracle_state.vec))
    assert ms.commits == mk.commits and ms.attempts == mk.attempts
    assert ms.retries == mk.retries and ms.delivered == mk.delivered
    assert mk.commits["neworder"] > 0
    # key mode charges one §5.2 index probe per item/stock read on top of
    # the identical record-read profile
    assert mk.ops["neworder"].record_reads > ms.ops["neworder"].record_reads
    assert mk.ops["payment"].record_reads == ms.ops["payment"].record_reads


def test_key_addressed_directory_miss_aborts():
    """A key the directory cannot resolve must read as not-found → the
    transaction aborts with snapshot_miss; no negative slot is ever
    gathered."""
    cfg = tpcc.TPCCConfig(n_warehouses=2, customers_per_district=8,
                          n_items=64, n_threads=4, orders_per_thread=8,
                          key_addressed=True)
    oracle = VectorOracle(cfg.n_threads)
    lay, st = tpcc.init_tpcc(cfg, oracle, jax.random.PRNGKey(0))
    from repro.core import hashtable as ht
    # invalidate one stock key: every new-order touching (w=0, i=7) aborts
    st = st._replace(directory=ht.delete(
        st.directory, tpcc.stock_key(cfg, jnp.uint32(0), jnp.uint32(7))[None]
    )[0])
    logits = workload.zipf_logits(cfg.n_items, cfg.skew_alpha)
    inp = workload.gen_neworder(jax.random.PRNGKey(1), cfg.n_threads,
                                cfg.n_warehouses, cfg.n_items,
                                cfg.customers_per_district, None, 0.0, logits)
    inp = inp._replace(item_ids=jnp.full_like(inp.item_ids, 7),
                       supply_w=jnp.zeros_like(inp.supply_w),
                       w_id=jnp.zeros_like(inp.w_id))
    out = tpcc.neworder_round(cfg, lay, st, oracle, inp)
    assert not bool(np.asarray(out.committed).any())
    assert bool(np.asarray(out.snapshot_miss).all())


# ------------------------------------- read-only programs over their lanes --
@pytest.fixture(scope="module")
def ro_loaded():
    oracle = VectorOracle(roc.CFG.n_threads)
    lay, st = roc.loaded(oracle)
    return oracle, lay, st, tpcc._sub_rounds(roc.CFG, lay, oracle, None,
                                             roc.LAST_N)


@pytest.mark.parametrize("name", roc.NAMES)
@pytest.mark.parametrize("n_active", roc.COUNTS)
def test_readonly_program_equals_the_masked_full_width_round(ro_loaded, name,
                                                             n_active):
    """A read-only program runs its round function on the active lanes
    alone, a chunk at a time: one chunk, two, and many. Its answers, read
    sets and op counts equal the round function's over all lanes with the
    mask; a stock-level count equals the query run for its lane alone."""
    oracle, lay, st, programs = ro_loaded
    inp, act = roc.inputs(name), roc.active(n_active)
    got = programs[name](st, inp, jnp.asarray(act))
    want = roc.full_width(name, lay, oracle)(st, inp, jnp.asarray(act))
    roc.assert_same_answers(got, want, act)
    if name != "stocklevel":
        return
    one = jax.jit(lambda st, w, d, th: tpcc.stocklevel(
        roc.CFG, lay, st, oracle, w, d, th, last_n=roc.LAST_N))
    counts = np.asarray(got.result)
    for lane in np.flatnonzero(act):
        assert int(one(st, inp.w_id[lane], inp.d_id[lane],
                       inp.threshold[lane])) == counts[lane], lane


def test_readonly_program_answers_are_not_vacuous(ro_loaded):
    """The store the comparisons above read gives both kinds of answer:
    districts with an order and without, counts of zero and above."""
    oracle, lay, st, programs = ro_loaded
    T = roc.CFG.n_threads
    act = jnp.ones(T, bool)
    sl = programs["stocklevel"](st, roc.inputs("stocklevel"), act)
    os_ = programs["orderstatus"](st, roc.inputs("orderstatus"), act)
    assert T // 16 <= int(jnp.sum(sl.result > 0)) < T
    assert T // 16 <= int(jnp.sum(os_.found)) < T


def test_readonly_program_on_a_mesh_equals_the_full_width_round():
    """The same comparison through the mesh's read executor on four
    devices, in a process of its own (the host device count is fixed
    before JAX starts)."""
    here = os.path.dirname(os.path.abspath(__file__))
    env = {**os.environ, "JAX_PLATFORMS": "cpu",
           "XLA_FLAGS": "--xla_force_host_platform_device_count=4",
           "PYTHONPATH": os.pathsep.join(
               [os.path.join(os.path.dirname(here), "src"), here]
               + [p for p in [os.environ.get("PYTHONPATH")] if p])}
    out = subprocess.run(
        [sys.executable, os.path.join(here, "_readonly_chunks_check.py")],
        capture_output=True, text=True, timeout=600, env=env)
    assert out.returncode == 0, out.stderr[-3000:]
    assert "READONLY_CHUNKS_OK" in out.stdout


def test_readonly_chunks_counter(monkeypatch):
    """``readonly_chunks`` counts ceil(lanes / chunk) over the rounds a
    read-only type was drawn in, from the gate's count: the blocking reads
    stay those of the 12/13/7/1 arithmetic."""
    cfg = roc.CFG
    oracle = VectorOracle(cfg.n_threads)
    lay, st = tpcc.init_tpcc(cfg, oracle, jax.random.PRNGKey(3))
    drawn = {n: [] for n in workload.TXN_TYPES}
    real = tpcc._sub_rounds

    def counted(*a):
        def wrap(name, prog):
            def call(st, inp, *rest):
                act = rest[0] if name in tpcc.READ_ONLY_TYPES else rest[1]
                drawn[name].append(int(jnp.sum(act)))
                return prog(st, inp, *rest)
            return call
        return {k: wrap(k, v) for k, v in real(*a).items()}

    monkeypatch.setattr(tpcc, "_sub_rounds", counted)
    n = 3
    _, stats = tpcc.run_mixed_rounds(
        cfg, lay, st, oracle, jax.random.PRNGKey(4), n,
        mix={"neworder": 0.3, "payment": 0.3, "orderstatus": 0.13,
             "delivery": 0.14, "stocklevel": 0.13},
        stock_last_n=roc.LAST_N)
    K = tpcc.readonly_lane_chunk(cfg.n_threads)
    for name in tpcc.READ_ONLY_TYPES:
        assert stats.attempts[name] == sum(drawn[name])
        assert stats.readonly_chunks[name] == sum(-(-a // K)
                                                  for a in drawn[name])
        assert stats.readonly_chunks[name] > len(drawn[name])   # > 1 a call
    calls = {k: len(v) for k, v in drawn.items()}
    writes = calls["neworder"] + calls["payment"] + calls["delivery"]
    reads = calls["orderstatus"] + calls["stocklevel"]
    assert stats.host_reads == (6 * n + 11 * writes + calls["delivery"]
                                + 6 * reads + 5)


def test_readonly_lane_chunk():
    """The smallest power of two at least T / 32."""
    assert [tpcc.readonly_lane_chunk(t) for t in (1, 8, 32, 33, 128, 400,
                                                  1600)] == \
        [1, 1, 1, 2, 4, 16, 64]
