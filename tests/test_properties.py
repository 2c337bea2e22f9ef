"""Hypothesis property tests on the system's core invariants.

SI protocol (paper §3/§4/§5):
  P1  conservation — balance-transfer workloads never create or destroy
      value, whatever the conflict pattern (atomicity under any interleave).
  P2  monotone visibility — the timestamp vector only moves forward, and a
      committed write is visible to every later snapshot until overwritten.
  P3  header round-trip — pack/unpack of ⟨thread, cts, moved, deleted,
      locked⟩ is lossless for all field values.
  P4  write-write exclusion — per record, at most ONE transaction of a
      round commits an update to it.
  P5  visible read returns the newest version ≤ snapshot — against a
      brute-force reference over the full version history.

GC (paper §5.3):
  P6  GC safety — ``gc.collect`` at the safe vector never marks a version
      that is the newest visible one at ANY admissible snapshot (any
      snapshot ≥ the safe vector elementwise, i.e. any snapshot a live
      transaction younger than E could still hold): reads at every such
      snapshot are unchanged by the sweep (+ lazy truncation).
  P7  GC liveness — repeated install → move(reuse_only) → collect →
      truncate cycles keep the overflow ring pointer bounded in [0, KO),
      keep installs succeeding (no permanent stall), and actually REUSE
      slots rather than exhausting them.

Recovery (paper §6.2):
  P8  durability — killing the memory server at ANY round of a journalled
      TPC-C mix (optionally with undetermined in-flight intents holding
      locks), then restoring the last checkpoint and replaying the
      journal, yields a run bit-identical to one that never crashed.

Elasticity (DESIGN.md §4.3):
  P9  scale-out transparency — growing the mesh at ANY round of a
      journalled mixed run, with whatever in-flight/retry-queue state that
      round carries, never changes any already-committed version or any
      visible read at an admissible snapshot: the expanded run is
      bit-identical to one born at the larger shard count (needs ≥4
      devices; CI's mesh step forces 8).
"""
import tempfile

import pytest

hypothesis = pytest.importorskip(
    "hypothesis", reason="hypothesis not installed; the seeded-random "
    "equivalents live in tests/test_si_invariants.py")
from hypothesis import given, settings, strategies as st

import jax
import jax.numpy as jnp
import numpy as np

from repro import compat
from repro.core import gc, header as hdr, mvcc, si
from repro.core.tsoracle import VectorOracle

hypothesis.settings.register_profile(
    "ci", deadline=None, max_examples=20,
    suppress_health_check=[hypothesis.HealthCheck.too_slow,
                           hypothesis.HealthCheck.data_too_large])
hypothesis.settings.load_profile("ci")


# ---------------------------------------------------------------- P3 ------
@given(tid=st.integers(0, 2**29 - 1), cts=st.integers(0, 2**32 - 1),
       moved=st.booleans(), deleted=st.booleans(), locked=st.booleans())
@settings(max_examples=25, deadline=None)
def test_header_roundtrip(tid, cts, moved, deleted, locked):
    h = hdr.pack(jnp.uint32(tid), jnp.uint32(cts), moved=moved,
                 deleted=deleted, locked=locked)
    assert int(hdr.thread_id(h)) == tid
    assert int(hdr.commit_ts(h)) == cts
    assert bool(hdr.is_moved(h)) == moved
    assert bool(hdr.is_deleted(h)) == deleted
    assert bool(hdr.is_locked(h)) == locked
    # lock toggle is involutive and does not disturb other fields
    h2 = hdr.with_lock(hdr.with_lock(h, True), locked)
    assert int(hdr.thread_id(h2)) == tid and int(hdr.commit_ts(h2)) == cts
    assert bool(hdr.is_locked(h2)) == locked


# ---------------------------------------------------------- P1 + P2 + P4 --
@st.composite
def transfer_rounds(draw):
    n_acc = draw(st.integers(4, 24))
    T = draw(st.integers(2, 12))
    n_rounds = draw(st.integers(1, 5))
    seed = draw(st.integers(0, 2**31 - 1))
    return n_acc, T, n_rounds, seed


@given(transfer_rounds())
@settings(max_examples=12, deadline=None)
def test_si_conservation_and_monotonicity(params):
    n_acc, T, n_rounds, seed = params
    table = mvcc.init_table(n_acc, payload_width=1, n_old=4)
    table = table._replace(
        cur_data=jnp.full((n_acc, 1), 100, jnp.int32))
    oracle = VectorOracle(T)
    state = oracle.init()
    key = jax.random.PRNGKey(seed)
    prev_vec = np.asarray(state.vec).copy()

    for rnd in range(n_rounds):
        key, k1, k2 = jax.random.split(key, 3)
        src = jax.random.randint(k1, (T,), 0, n_acc)
        dst = (src + 1 + jax.random.randint(k2, (T,), 0, n_acc - 1)) % n_acc
        batch = si.TxnBatch(
            tid=jnp.arange(T, dtype=jnp.int32),
            read_slots=jnp.stack([src, dst], 1).astype(jnp.int32),
            read_mask=jnp.ones((T, 2), bool),
            write_ref=jnp.broadcast_to(jnp.arange(2, dtype=jnp.int32),
                                       (T, 2)),
            write_mask=jnp.ones((T, 2), bool))

        def xfer(rh, rd, vec):
            out = rd.astype(jnp.int32)
            out = out.at[:, 0, 0].add(-7)
            out = out.at[:, 1, 0].add(+7)
            return out

        res = si.run_round(table, oracle, state, batch, xfer)
        table, state = res.table, res.oracle_state

        # P1: conservation
        assert int(table.cur_data[:, 0].sum()) == n_acc * 100
        # P2: vector moves only forward
        vec = np.asarray(state.vec)
        assert (vec >= prev_vec).all()
        prev_vec = vec.copy()
        # P4: all current versions unlocked after the round
        assert not bool(np.asarray(hdr.is_locked(table.cur_hdr)).any())

        # P4b: committed writers of one record are unique per round
        comm = np.asarray(res.committed)
        w_slots = np.stack([np.asarray(src), np.asarray(dst)], 1)
        touched = {}
        for t in range(T):
            if not comm[t]:
                continue
            for s in w_slots[t]:
                assert s not in touched, "two commits updated one record"
                touched[s] = t


# ---------------------------------------------------------------- P5 ------
@given(st.integers(0, 2**31 - 1), st.integers(1, 6))
@settings(max_examples=10, deadline=None)
def test_visible_read_matches_bruteforce(seed, n_rounds):
    """Install versions at known cts; read_visible must return the newest
    version whose ⟨thread, cts⟩ is ≤ the snapshot vector."""
    T, n_rec = 4, 6
    table = mvcc.init_table(n_rec, payload_width=1, n_old=4)
    table = table._replace(cur_data=jnp.zeros((n_rec, 1), jnp.int32))
    oracle = VectorOracle(T)
    state = oracle.init()
    key = jax.random.PRNGKey(seed)
    history = {r: [(0, 0, 0)] for r in range(n_rec)}   # (thread,cts,value)

    for rnd in range(n_rounds):
        key, k1 = jax.random.split(key)
        slot = jax.random.randint(k1, (T,), 0, n_rec)
        batch = si.TxnBatch(
            tid=jnp.arange(T, dtype=jnp.int32),
            read_slots=slot[:, None].astype(jnp.int32),
            read_mask=jnp.ones((T, 1), bool),
            write_ref=jnp.zeros((T, 1), jnp.int32),
            write_mask=jnp.ones((T, 1), bool))

        def bump(rh, rd, vec, _r=rnd):
            return rd.astype(jnp.int32) + 1 + _r

        res = si.run_round(table, oracle, state, batch, bump)
        comm = np.asarray(res.committed)
        svec = np.asarray(res.oracle_state.vec)
        for t in range(T):
            if comm[t]:
                s = int(slot[t])
                val = int(res.table.cur_data[s, 0])
                history[s].append((t, int(svec[t]), val))
        table, state = res.table, res.oracle_state

    # now read every record at the final snapshot and compare to brute force
    vec = jnp.asarray(np.asarray(state.vec))
    vr = mvcc.read_visible(table, jnp.arange(n_rec, dtype=jnp.int32), vec)
    for r in range(n_rec):
        visible = [v for (t, c, v) in history[r]
                   if c <= int(vec[t])]
        newest = history[r][-1]
        # current version is always the newest committed; it must be visible
        # at the full final snapshot and equal the stored current data
        assert bool(vr.found[r])
        assert int(vr.data[r, 0]) == newest[2]
        assert newest[2] in visible


# ---------------------------------------------------------- P6 + P7 ------
def _run_si_with_snapshots(seed, n_rounds, T=4, n_rec=6, n_old=2, ko=8):
    """Drive real SI rounds, logging T_R after each round (wall-clock = round
    index). Returns (table, vec history list, oracle state)."""
    table = mvcc.init_table(n_rec, payload_width=1, n_old=n_old,
                            n_overflow=ko)
    oracle = VectorOracle(T)
    state = oracle.init()
    key = jax.random.PRNGKey(seed)
    vecs = []
    for rnd in range(n_rounds):
        key, k1 = jax.random.split(key)
        slot = jax.random.randint(k1, (T,), 0, n_rec)
        batch = si.TxnBatch(
            tid=jnp.arange(T, dtype=jnp.int32),
            read_slots=slot[:, None].astype(jnp.int32),
            read_mask=jnp.ones((T, 1), bool),
            write_ref=jnp.zeros((T, 1), jnp.int32),
            write_mask=jnp.ones((T, 1), bool))

        def bump(rh, rd, vec, _r=rnd):
            return rd.astype(jnp.int32) + 1 + _r

        res = si.run_round(table, oracle, state, batch, bump)
        table, state = res.table, res.oracle_state
        table = mvcc.version_mover(table, reuse_only=True)
        vecs.append(np.asarray(state.vec).copy())
    return table, vecs, state


def _check_gc_safety(seed, n_rounds, max_txn_time):
    """P6 body: collect at the safe vector must not change any read at any
    snapshot ≥ safe (the snapshots transactions younger than E can hold)."""
    table, vecs, _ = _run_si_with_snapshots(seed, n_rounds)
    log = gc.init_log(n_snapshots=n_rounds, n_slots=len(vecs[0]))
    for t, v in enumerate(vecs):
        log = gc.take_snapshot(log, t, jnp.asarray(v, jnp.uint32))
    now = n_rounds - 1
    safe = gc.safe_vector(log, now, max_txn_time)
    swept = mvcc.compact_overflow(gc.collect(table, safe))
    safe_np = np.asarray(safe)
    admissible = [v for v in vecs if (v >= safe_np).all()]
    # snapshots younger than E are admissible by construction — non-vacuous
    assert len(admissible) >= min(len(vecs), max_txn_time)
    all_slots = jnp.arange(table.n_records, dtype=jnp.int32)
    for v in admissible:
        vec = jnp.asarray(v, jnp.uint32)
        a = mvcc.read_visible(table, all_slots, vec)
        b = mvcc.read_visible(swept, all_slots, vec)
        np.testing.assert_array_equal(np.asarray(a.found),
                                      np.asarray(b.found), err_msg=str(v))
        np.testing.assert_array_equal(np.asarray(a.hdr), np.asarray(b.hdr),
                                      err_msg=str(v))
        np.testing.assert_array_equal(np.asarray(a.data), np.asarray(b.data),
                                      err_msg=str(v))


def _check_gc_liveness(ko, lag, n_steps=None):
    """P7 body: single hot record, one install per step, mover in
    reclaimed-slot-only mode, a GC sweep per step at staleness ``lag``."""
    n_steps = n_steps or 4 * ko
    tbl = mvcc.init_table(1, 2, n_old=1, n_overflow=ko)
    s = jnp.array([0], jnp.int32)
    installed = 0
    v = 0
    for step in range(n_steps):
        v += 1
        out = mvcc.install(
            tbl, s, hdr.pack(jnp.uint32(1), jnp.uint32(v))[None],
            jnp.full((1, 2), v, jnp.int32), jnp.array([True]))
        installed += int(out.installed[0])
        if not bool(out.installed[0]):
            v -= 1                      # aborted: version v never existed
        tbl = mvcc.version_mover(out.table, reuse_only=True)
        safe = jnp.array([0, max(0, v - 1 - lag)], jnp.uint32)
        tbl = mvcc.compact_overflow(gc.collect(tbl, safe))
        assert 0 <= int(tbl.ovf_next[0]) < ko, "ring pointer escaped [0, KO)"
    assert installed >= 2 * ko, f"stall: only {installed}/{n_steps} installs"
    # slots were REUSED: some overflow version's cts exceeds the capacity,
    # impossible if each of the KO slots had been written at most once
    assert int(np.asarray(hdr.commit_ts(tbl.ovf_hdr[0])).max()) > ko


@given(seed=st.integers(0, 2**31 - 1), n_rounds=st.integers(2, 7),
       max_txn_time=st.integers(1, 4))
@settings(max_examples=10, deadline=None)
def test_gc_collect_never_marks_newest_visible(seed, n_rounds, max_txn_time):
    _check_gc_safety(seed, n_rounds, max_txn_time)


@given(ko=st.integers(2, 6), lag=st.integers(0, 4))
@settings(max_examples=10, deadline=None)
def test_gc_mover_cycles_keep_overflow_ring_bounded(ko, lag):
    # a retention lag the ring cannot hold stalls by design (backpressure);
    # liveness is claimed for lag ≤ KO-2 — GC keeping up with the mover
    _check_gc_liveness(ko, min(lag, ko - 2))


# ---------------------------------------------------------------- P8 ------
_P8_ROUNDS = 4


def _journalled_mix(seed, failure):
    """One journalled single-shard TPC-C mix (checkpoint after every GC
    sweep), optionally killed and recovered at ``failure.kill_round``."""
    from repro.core.tsoracle import VectorOracle as _VO
    from repro.db import tpcc
    cfg = tpcc.TPCCConfig(n_warehouses=4, customers_per_district=8,
                          n_items=64, n_threads=8, orders_per_thread=16,
                          dist_degree=30.0)
    oracle = _VO(cfg.n_threads)
    lay, st0 = tpcc.init_tpcc(cfg, oracle, jax.random.PRNGKey(1))
    jnl = tpcc.make_journal(cfg, oracle, capacity_rounds=_P8_ROUNDS + 2)
    with tempfile.TemporaryDirectory() as d:
        st, ms = tpcc.run_mixed_rounds(
            cfg, lay, st0, oracle, jax.random.PRNGKey(seed), _P8_ROUNDS,
            journal=jnl, checkpoint_dir=d, failure=failure,
            gc_interval=2, max_txn_time=1)
    return st, ms


@given(seed=st.integers(0, 2**31 - 1), kill_round=st.integers(0, _P8_ROUNDS - 1),
       in_flight=st.booleans())
@settings(max_examples=5, deadline=None)
def test_kill_recover_is_bit_identical(seed, kill_round, in_flight):
    from repro.db import tpcc
    st_ref, ms_ref = _journalled_mix(seed, None)
    st_rec, ms_rec = _journalled_mix(
        seed, tpcc.FailureInjector(kill_round=kill_round,
                                   in_flight=in_flight))
    (rep,) = ms_rec.recovery
    assert rep.kill_round == kill_round
    assert rep.checkpoint_round < kill_round
    if in_flight:
        assert rep.undetermined > 0
    for leaf_a, leaf_b in zip(jax.tree.leaves(st_ref.nam.table),
                              jax.tree.leaves(st_rec.nam.table)):
        np.testing.assert_array_equal(np.asarray(leaf_a), np.asarray(leaf_b))
    np.testing.assert_array_equal(np.asarray(st_ref.nam.oracle_state.vec),
                                  np.asarray(st_rec.nam.oracle_state.vec))
    assert ms_ref.attempts == ms_rec.attempts
    assert ms_ref.commits == ms_rec.commits
    assert ms_ref.retries == ms_rec.retries
    assert ms_ref.delivered == ms_rec.delivered
    assert ms_ref.ops == ms_rec.ops


# ---------------------------------------------------------------- P9 ------
_P9_ROUNDS = 4


def _mesh_mix(seed, n_shards, growth):
    """One journalled mesh TPC-C mix, optionally grown mid-run.  6 threads:
    the partitioned vector divides over 2 shards but not over 4, so any
    expansion crosses a non-dividing (pad_vector) boundary."""
    from repro.core import store as store_mod
    from repro.core.tsoracle import PartitionedVectorOracle
    from repro.db import tpcc
    cfg = tpcc.TPCCConfig(n_warehouses=4, customers_per_district=8,
                          n_items=64, n_threads=6, orders_per_thread=16,
                          dist_degree=30.0)
    mesh = compat.mesh(n_shards)
    oracle = PartitionedVectorOracle(cfg.n_threads, n_parts=n_shards)
    lay, st0 = tpcc.init_tpcc(cfg, oracle, jax.random.PRNGKey(1))
    engine = tpcc.make_mixed_engine(cfg, lay, mesh, "mem", oracle,
                                    shard_vector=True, with_journal=True)
    st0 = tpcc.distribute_state(engine, st0)
    jnl = tpcc.make_journal(cfg, oracle, capacity_rounds=_P9_ROUNDS + 2,
                            n_replicas=n_shards)
    jnl = store_mod.shard_journal(mesh, "mem", jnl)
    with tempfile.TemporaryDirectory() as d:
        st, ms = tpcc.run_mixed_rounds(
            cfg, lay, st0, oracle, jax.random.PRNGKey(seed), _P9_ROUNDS,
            engine=engine, journal=jnl, checkpoint_dir=d, growth=growth,
            gc_interval=2, max_txn_time=1)
    return lay, oracle, st, ms


@pytest.mark.skipif(len(jax.devices()) < 4,
                    reason="P9 needs a ≥4-device mesh (CI mesh step)")
@given(seed=st.integers(0, 2**31 - 1),
       grow_round=st.integers(0, _P9_ROUNDS - 1))
@settings(max_examples=3, deadline=None)
def test_expansion_preserves_committed_state(seed, grow_round):
    from repro.db import tpcc
    lay, oracle, st_ref, ms_ref = _mesh_mix(seed, 4, None)
    _, _, st_exp, ms_exp = _mesh_mix(
        seed, 2, tpcc.MeshGrowth(grow_round=grow_round, new_shards=4))
    (rep,) = ms_exp.growth
    assert rep.grow_round == grow_round
    assert rep.checkpoint_round < grow_round
    R = lay.catalog.total_records
    n_slots = oracle.n_slots
    tbl_ref = jax.tree.map(lambda x: jnp.asarray(jax.device_get(x))[:R],
                           st_ref.nam.table)
    tbl_exp = jax.tree.map(lambda x: jnp.asarray(jax.device_get(x))[:R],
                           st_exp.nam.table)
    for leaf_a, leaf_b in zip(jax.tree.leaves(tbl_ref),
                              jax.tree.leaves(tbl_exp)):
        np.testing.assert_array_equal(np.asarray(leaf_a), np.asarray(leaf_b))
    vec_ref = jnp.asarray(jax.device_get(
        st_ref.nam.oracle_state.vec))[:n_slots]
    vec_exp = jnp.asarray(jax.device_get(
        st_exp.nam.oracle_state.vec))[:n_slots]
    np.testing.assert_array_equal(np.asarray(vec_ref), np.asarray(vec_exp))
    assert ms_ref.attempts == ms_exp.attempts
    assert ms_ref.commits == ms_exp.commits
    assert ms_ref.retries == ms_exp.retries
    assert ms_ref.delivered == ms_exp.delivered
    assert ms_ref.ops == ms_exp.ops
    # the visible read of EVERY record at the final (admissible) snapshot
    # is unchanged by the expansion — not just raw storage equality
    slots = jnp.arange(R, dtype=jnp.int32)
    va = mvcc.read_visible(tbl_ref, slots, vec_ref)
    vb = mvcc.read_visible(tbl_exp, slots, vec_exp)
    for leaf_a, leaf_b in zip(jax.tree.leaves(va), jax.tree.leaves(vb)):
        np.testing.assert_array_equal(np.asarray(leaf_a), np.asarray(leaf_b))


# ------------------------------------------------------- MoE invariants ---
@given(st.integers(0, 2**31 - 1), st.integers(1, 4),
       st.sampled_from([1.0, 2.0, 8.0]))
@settings(max_examples=10, deadline=None)
def test_moe_combine_weights_sum(seed, top_k, cf):
    """Dropless capacity ⇒ outputs are convex combinations: if every expert
    computes identity, the MoE output equals the input."""
    from repro.models import moe as moe_mod
    D, E, Tk = 8, 4, 16
    key = jax.random.PRNGKey(seed)
    p = moe_mod.init_moe(key, D, D, E, jnp.float32)
    eye = jnp.broadcast_to(jnp.eye(D)[None], (E, D, D))
    p = dict(p, w_in=eye, w_out=eye,
             w_gate=jnp.zeros_like(p["w_gate"]))
    x = jax.random.normal(jax.random.PRNGKey(seed + 1), (Tk, D))

    def act(g):          # silu(0)=0 would zero the output; use identity mix
        return jnp.ones_like(g)

    y, stats = moe_mod.apply_moe(p, x, top_k=top_k, capacity_factor=cf,
                                 activation=act)
    if cf >= E / max(1, top_k):          # provably dropless
        assert float(stats.dropped_fraction) == 0.0
        np.testing.assert_allclose(np.asarray(y), np.asarray(x),
                                   rtol=2e-4, atol=2e-4)
