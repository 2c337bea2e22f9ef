"""Tests: WAL journaling, recovery replay, abandoned-lock release, GC —
including the §5.3 sustained-execution pieces (snapshot-ring wraparound,
reclaimed-slot version moving, lazy truncation, and the per-shard mesh
sweep, which runs whenever the process sees ≥2 devices, e.g. under CI's
8-forced-host-device step)."""
import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro import compat
from repro.core import cas, gc, header as hdr, mvcc, si, store, wal
from repro.core.tsoracle import VectorOracle


def _run_workload(n_rounds=4, n_threads=3, n_records=8, width=2,
                  journal=None, ckpt_round=None):
    tbl = mvcc.init_table(n_records, width, n_old=2, n_overflow=4)
    o = VectorOracle(n_threads=n_threads)
    st = o.init()
    ckpt = None

    def fn(rh, rd, rts):
        return rd[:, :1, :].at[..., 0].add(1)  # write-set = read-set[0] + 1

    import jax
    key = jax.random.PRNGKey(0)
    for r in range(n_rounds):
        key, sub = jax.random.split(key)
        slots = jax.random.randint(sub, (n_threads, 2), 0, n_records)
        batch = si.TxnBatch(
            tid=jnp.arange(n_threads, dtype=jnp.int32),
            read_slots=slots.astype(jnp.int32),
            read_mask=jnp.ones((n_threads, 2), bool),
            write_ref=jnp.zeros((n_threads, 1), jnp.int32),
            write_mask=jnp.ones((n_threads, 1), bool),
        )
        rts = o.read(st)
        out = si.run_round(tbl, o, st, batch, fn)
        if journal is not None:
            wslots = jnp.take_along_axis(batch.read_slots,
                                         batch.write_ref, axis=1)
            cts = rts[jnp.arange(n_threads)] + 1
            new_hdr = hdr.pack(
                jnp.arange(n_threads, dtype=jnp.uint32)[:, None],
                cts[:, None])
            new_data = out.read_data[:, :1, :].at[..., 0].add(1)
            journal = wal.append_intent(
                journal, jnp.arange(n_threads, dtype=jnp.int32), rts,
                wslots, new_hdr, new_data, batch.write_mask,
                round_no=r, seq=0)
            journal = wal.append_outcome(
                journal, jnp.arange(n_threads, dtype=jnp.int32),
                out.committed)
        tbl, st = out.table, out.oracle_state
        tbl = mvcc.version_mover(tbl)
        if r == ckpt_round:
            ckpt = (tbl, journal.used)
    if ckpt_round is not None:
        return tbl, st, journal, ckpt
    return tbl, st, journal


def test_wal_replay_reconstructs_state():
    j = wal.init_journal(n_threads=3, capacity=8, n_slots=3, ws=1, width=2,
                         n_replicas=2)
    tbl, st, j = _run_workload(journal=j)
    fresh = mvcc.init_table(8, 2, n_old=2, n_overflow=4)
    recovered = wal.replay(j, fresh)
    # every record's current version must match (payloads and version tags)
    np.testing.assert_array_equal(np.asarray(recovered.cur_data),
                                  np.asarray(tbl.cur_data))
    np.testing.assert_array_equal(
        np.asarray(hdr.commit_ts(recovered.cur_hdr)),
        np.asarray(hdr.commit_ts(tbl.cur_hdr)))


def test_wal_replay_uses_surviving_replica():
    j = wal.init_journal(n_threads=3, capacity=8, n_slots=3, ws=1, width=2,
                         n_replicas=2)
    tbl, st, j = _run_workload(journal=j)
    fresh = mvcc.init_table(8, 2, n_old=2, n_overflow=4)
    recovered = wal.replay(j, fresh,
                           survivors=jnp.array([False, True]))
    np.testing.assert_array_equal(np.asarray(recovered.cur_data),
                                  np.asarray(tbl.cur_data))


def _lock(tbl, slot, prio):
    expected = tbl.cur_hdr[jnp.array([slot])]
    res = cas.arbitrate(tbl.cur_hdr, jnp.array([slot]), expected,
                        jnp.array([prio], jnp.uint32), jnp.array([True]))
    assert bool(res.granted[0])
    return tbl._replace(cur_hdr=res.new_hdr)


def _intent(j, tid, slot, cts, resolved=None):
    """Append a one-write intent entry for ``tid``; resolve it iff asked."""
    j = wal.append_intent(
        j, jnp.array([tid], jnp.int32), jnp.zeros((2,), jnp.uint32),
        jnp.array([[slot]], jnp.int32),
        hdr.pack(jnp.uint32(tid), jnp.uint32(cts))[None, None],
        jnp.zeros((1, 1, 2), jnp.int32), jnp.array([[True]]))
    if resolved is not None:
        j = wal.append_outcome(j, jnp.array([tid], jnp.int32),
                               jnp.array([resolved]))
    return j


def test_release_abandoned_locks():
    """A compute server dies between CAS and install; the monitor unlocks."""
    tbl = mvcc.init_table(4, 2, n_old=2, n_overflow=2)
    j = wal.init_journal(n_threads=2, capacity=4, n_slots=2, ws=1, width=2)
    # thread 1 locks slot 2 then crashes (no install, no outcome logged)
    tbl = _lock(tbl, 2, prio=1)
    j = _intent(j, tid=1, slot=2, cts=1)   # undetermined: no outcome record
    assert bool(hdr.is_locked(tbl.cur_hdr[2]))
    tbl = wal.release_abandoned_locks(j, tbl, dead_tid=1)
    assert not bool(hdr.is_locked(tbl.cur_hdr[2]))


def test_release_abandoned_locks_scans_all_unresolved():
    """Bugfix regression: the monitor must scan EVERY unresolved entry in
    the dead thread's live window. The old code looked only at the *last*
    entry, so a lock taken by an earlier in-flight sub-round entry leaked
    forever (and with ``used == 0`` it read the stale slot capacity-1)."""
    tbl = mvcc.init_table(6, 2, n_old=2, n_overflow=2)
    j = wal.init_journal(n_threads=2, capacity=4, n_slots=2, ws=1, width=2)
    # a RESOLVED committed entry naming slot 1 — its lock (held by someone
    # else now) must NOT be released on the dead thread's behalf
    j = _intent(j, tid=1, slot=1, cts=1, resolved=True)
    tbl = _lock(tbl, 1, prio=0)
    # two in-flight sub-round entries, both undetermined, then the crash
    tbl = _lock(tbl, 2, prio=1)
    j = _intent(j, tid=1, slot=2, cts=2)
    tbl = _lock(tbl, 3, prio=1)
    j = _intent(j, tid=1, slot=3, cts=2)
    tbl = wal.release_abandoned_locks(j, tbl, dead_tid=1)
    assert not bool(hdr.is_locked(tbl.cur_hdr[3]))
    assert not bool(hdr.is_locked(tbl.cur_hdr[2])), \
        "earlier unresolved entry's lock leaked (last-entry-only scan)"
    assert bool(hdr.is_locked(tbl.cur_hdr[1])), \
        "resolved entry's slot must be left alone"
    # a dead thread that never appended releases nothing
    tbl = _lock(tbl, 4, prio=0)
    tbl2 = wal.release_abandoned_locks(j, tbl, dead_tid=0)
    assert bool(hdr.is_locked(tbl2.cur_hdr[4]))


def test_wal_replay_wrapped_ring():
    """Bugfix regression: with ``used > capacity`` the old replay treated
    raw ring positions ``< used`` as valid — replaying overwritten entries
    and silently skipping nothing. The live window replays exactly the
    appends since the checkpoint, and a wrapped-past-unreplayed ring is a
    loud error, not a wrong table."""
    j = wal.init_journal(n_threads=3, capacity=4, n_slots=3, ws=1, width=2,
                         n_replicas=2)
    tbl, st, j, (ckpt_tbl, used_ckpt) = _run_workload(
        n_rounds=7, journal=j, ckpt_round=3)
    assert int(j.used[0]) == 7 > j.capacity  # the ring really wrapped
    recovered = wal.replay(j, ckpt_tbl, since=used_ckpt)
    np.testing.assert_array_equal(np.asarray(recovered.cur_data),
                                  np.asarray(tbl.cur_data))
    np.testing.assert_array_equal(
        np.asarray(hdr.commit_ts(recovered.cur_hdr)),
        np.asarray(hdr.commit_ts(tbl.cur_hdr)))
    # entries before the checkpoint were overwritten — replaying from a
    # fresh table (or any since that predates the window) must refuse
    fresh = mvcc.init_table(8, 2, n_old=2, n_overflow=4)
    with pytest.raises(ValueError, match="overwrote unreplayed"):
        wal.replay(j, fresh)
    with pytest.raises(ValueError, match="overwrote unreplayed"):
        wal.replay(j, ckpt_tbl, since=jnp.zeros((3,), jnp.int32))


@pytest.mark.parametrize("ts_a,ts_b", [
    # sum(T) of B wraps uint32 below A's — the old single-key order inverted
    ([0x7FFFFFFF, 0x7FFFFFFF], [0x80000000, 0x80000000]),
    # A's exact sum is 0xFFFFFFFF — the old SENTINEL — so A was dropped from
    # the replay entirely (sorted among the never-used entries)
    ([0xFFFFFFFE, 0x00000001], [0xFFFFFFFE, 0x00000002]),
])
def test_wal_replay_order_key_overflow(ts_a, ts_b):
    """Bugfix regression: the linear-extension key must not wrap. Entry B's
    logged T dominates A's, so B must replay after A and win the record —
    under the old uint32 ``sum(T)`` key it either sorted first (wrap) or
    collided with the not-committed sentinel."""
    j = wal.init_journal(n_threads=1, capacity=2, n_slots=2, ws=1, width=2,
                         n_replicas=1)
    tid = jnp.array([0], jnp.int32)
    for rnd, (ts, cts, val) in enumerate(
            [(ts_a, 1, 1), (ts_b, 2, 2)]):
        j = wal.append_intent(
            j, tid, jnp.array(ts, jnp.uint32),
            jnp.array([[0]], jnp.int32),
            hdr.pack(jnp.uint32(0), jnp.uint32(cts))[None, None],
            jnp.full((1, 1, 2), val, jnp.int32), jnp.array([[True]]),
            round_no=rnd)
        j = wal.append_outcome(j, tid, jnp.array([True]))
    fresh = mvcc.init_table(1, 2, n_old=2, n_overflow=2)
    recovered = wal.replay(j, fresh)
    assert int(hdr.commit_ts(recovered.cur_hdr[0])) == 2, \
        "dominated entry replayed last — order key wrapped or hit sentinel"
    np.testing.assert_array_equal(np.asarray(recovered.cur_data[0]), [2, 2])


def test_gc_snapshot_log_and_safe_vector():
    log = gc.init_log(4, n_slots=2)
    log = gc.take_snapshot(log, 100, jnp.array([1, 1], jnp.uint32))
    log = gc.take_snapshot(log, 200, jnp.array([3, 2], jnp.uint32))
    safe = gc.safe_vector(log, now=260, max_txn_time=100)
    np.testing.assert_array_equal(np.asarray(safe), [1, 1])
    safe2 = gc.safe_vector(log, now=400, max_txn_time=100)
    np.testing.assert_array_equal(np.asarray(safe2), [3, 2])


def test_gc_collect_marks_only_superseded():
    tbl = mvcc.init_table(2, 2, n_old=1, n_overflow=4)
    s = jnp.array([0], jnp.int32)
    for v in range(1, 5):  # versions 1..4 by thread 1; 1..3 spill to overflow
        out = mvcc.install(tbl, s, hdr.pack(jnp.uint32(1), jnp.uint32(v))[None],
                           jnp.full((1, 2), v, jnp.int32), jnp.array([True]))
        tbl = mvcc.version_mover(out.table)
    safe = jnp.array([0, 3], jnp.uint32)  # oldest live snapshot sees v3
    tbl2 = gc.collect(tbl, safe)
    # versions 1,2 in overflow must be doomed; v3 must survive
    ovf_cts = np.asarray(hdr.commit_ts(tbl2.ovf_hdr[0]))
    deleted = np.asarray(hdr.is_deleted(tbl2.ovf_hdr[0]))
    for cts, dead in zip(ovf_cts, deleted):
        if cts in (1, 2):
            assert dead
        if cts == 3:
            assert not dead
    # reads at the safe snapshot still succeed
    vr = mvcc.read_visible(tbl2, s, safe)
    assert bool(vr.found[0]) and int(hdr.commit_ts(vr.hdr[0])) == 3


def test_gc_take_snapshot_prefers_unused_slots():
    """Bugfix regression: while unused (−1) slots remain, take_snapshot must
    fill them — never evict a retained snapshot (the old argmin(times) did
    the right thing only because −1 happens to sort below every valid
    time)."""
    log = gc.init_log(4, n_slots=1)
    log = gc.take_snapshot(log, 10, jnp.array([1], jnp.uint32))
    log = gc.take_snapshot(log, 20, jnp.array([2], jnp.uint32))
    times = np.asarray(log.times)
    assert sorted(times[times >= 0]) == [10, 20]
    assert (times < 0).sum() == 2  # both retained, two slots still unused


def test_gc_snapshot_ring_full_wraparound():
    """Once the ring is full, each new snapshot evicts exactly the OLDEST
    retained one; after a full second lap only the newest S survive and
    safe_vector reflects them."""
    S = 4
    log = gc.init_log(S, n_slots=1)
    for t in range(10, 10 + 2 * S + 1):
        log = gc.take_snapshot(log, t, jnp.array([t], jnp.uint32))
        retained = np.asarray(log.times)
        retained = sorted(retained[retained >= 0])
        want = list(range(max(10, t - S + 1), t + 1))
        assert retained == want, (t, retained)
    # ring now holds times 15..18; at now=20, E=2 the newest qualifying
    # snapshot is t=18, so the safe vector is its vec
    safe = gc.safe_vector(log, now=20, max_txn_time=2)
    np.testing.assert_array_equal(np.asarray(safe), [18])


def _install_v(tbl, v):
    return mvcc.install(tbl, jnp.array([0], jnp.int32),
                        hdr.pack(jnp.uint32(1), jnp.uint32(v))[None],
                        jnp.full((1, 2), v, jnp.int32), jnp.array([True]))


def test_version_mover_reuse_only_stalls_until_collect():
    """§5.3 discipline: with reuse_only the mover never overwrites a live
    overflow version — it stalls, installs backpressure into aborts, and one
    collect+truncate unblocks the pipeline."""
    tbl = mvcc.init_table(1, 2, n_old=1, n_overflow=2)
    for v in (1, 2):
        out = _install_v(tbl, v)
        assert bool(out.installed[0])
        tbl = mvcc.version_mover(out.table, reuse_only=True)
    # ring now holds v0, v1 (both live); the next move must stall …
    out = _install_v(tbl, 3)
    assert bool(out.installed[0])
    tbl = mvcc.version_mover(out.table, reuse_only=True)
    ovf_cts = set(np.asarray(hdr.commit_ts(tbl.ovf_hdr[0])).tolist())
    assert ovf_cts == {0, 1}, "stalled mover must not clobber v0/v1"
    # … which blocks the NEXT install (old slot not reusable) → abort
    out = _install_v(tbl, 4)
    assert not bool(out.installed[0])
    # GC: safe snapshot sees v1 as newest ⇒ v0 reclaimed, truncated
    tbl = mvcc.compact_overflow(
        gc.collect(out.table, jnp.array([0, 1], jnp.uint32)))
    tbl = mvcc.version_mover(tbl, reuse_only=True)   # v2 → reclaimed slot
    out = _install_v(tbl, 4)                          # retry now succeeds
    assert bool(out.installed[0])
    tbl = out.table
    assert int(tbl.ovf_next[0]) < 2                   # ring ptr stays bounded
    # v2 must now be readable from the overflow region at its snapshot
    vr = mvcc.read_visible(tbl, jnp.array([0], jnp.int32),
                           jnp.array([0, 2], jnp.uint32))
    assert bool(vr.found[0]) and int(hdr.commit_ts(vr.hdr[0])) == 2
    assert bool(vr.from_ovf[0])


def test_compact_overflow_resets_deleted_slots_only():
    tbl = mvcc.init_table(1, 2, n_old=1, n_overflow=4)
    for v in (1, 2, 3):
        tbl = mvcc.version_mover(_install_v(tbl, v).table, reuse_only=True)
    tbl = gc.collect(tbl, jnp.array([0, 3], jnp.uint32))  # dooms v0, v1
    tbl2 = mvcc.compact_overflow(tbl)
    dead = np.asarray(hdr.is_deleted(tbl.ovf_hdr[0]))
    for k in range(4):
        if dead[k]:   # truncated to the zeroed reusable sentinel
            assert int(hdr.commit_ts(tbl2.ovf_hdr[0, k])) == 0
            assert int(np.asarray(tbl2.ovf_data[0, k]).sum()) == 0
            assert bool(hdr.is_deleted(tbl2.ovf_hdr[0, k]))
        else:         # live versions untouched
            np.testing.assert_array_equal(np.asarray(tbl2.ovf_hdr[0, k]),
                                          np.asarray(tbl.ovf_hdr[0, k]))
            np.testing.assert_array_equal(np.asarray(tbl2.ovf_data[0, k]),
                                          np.asarray(tbl.ovf_data[0, k]))
    # reads at any still-admissible snapshot are unchanged
    for vec in ([0, 2], [0, 3]):
        a = mvcc.read_visible(tbl, jnp.array([0]), jnp.array(vec, jnp.uint32))
        b = mvcc.read_visible(tbl2, jnp.array([0]), jnp.array(vec, jnp.uint32))
        assert bool(a.found[0]) == bool(b.found[0])
        np.testing.assert_array_equal(np.asarray(a.data), np.asarray(b.data))


@pytest.mark.skipif(len(jax.devices()) < 2,
                    reason="needs ≥2 devices (run under the CI mesh "
                    "step's forced host devices)")
def test_distributed_gc_round_matches_single_shard():
    """The per-shard mesh sweep (store.distributed_gc_round) must be
    bit-identical to gc.gc_round over the whole pool, with every shard's
    snapshot log agreeing with the single-shard one."""
    n = 2 if len(jax.devices()) < 4 else 4
    mesh = compat.mesh(n)
    n_records, width, T = 8 * n, 2, 3
    tbl_s = mvcc.init_table(n_records, width, n_old=1, n_overflow=4)
    o = VectorOracle(T)
    st = o.init()

    def fn(rh, rd, rts):
        return rd[:, :1, :].at[..., 0].add(1)

    import jax.random as jrandom
    key = jrandom.PRNGKey(3)
    # grow version history through real SI rounds (single copy)
    for r in range(6):
        key, sub = jrandom.split(key)
        slots = jrandom.randint(sub, (T, 2), 0, n_records)
        batch = si.TxnBatch(
            tid=jnp.arange(T, dtype=jnp.int32),
            read_slots=slots.astype(jnp.int32),
            read_mask=jnp.ones((T, 2), bool),
            write_ref=jnp.zeros((T, 1), jnp.int32),
            write_mask=jnp.ones((T, 1), bool))
        out = si.run_round(tbl_s, o, st, batch, fn)
        tbl_s, st = out.table, out.oracle_state
        tbl_s = mvcc.version_mover(tbl_s, reuse_only=True)

    tbl_d = store.shard_table(mesh, "mem", tbl_s)
    gc_fn = store.distributed_gc_round(mesh, "mem", shard_vector=False)
    log_s = gc.init_log(4, n_slots=T)
    logs_d = store.init_shard_logs(n, 4, n_slots=T)
    vec = st.vec
    for now in range(3):
        tbl_s, log_s = gc.gc_round(tbl_s, vec, log_s, now, 1)
        tbl_d, logs_d = gc_fn(tbl_d, vec, logs_d, now, 1)
    import jax
    for field in mvcc.VersionedTable._fields:
        np.testing.assert_array_equal(
            np.asarray(jax.device_get(getattr(tbl_d, field))),
            np.asarray(getattr(tbl_s, field)), err_msg=field)
    for shard in range(n):
        np.testing.assert_array_equal(
            np.asarray(jax.device_get(logs_d.times))[shard],
            np.asarray(log_s.times), err_msg=f"shard {shard} times")
        np.testing.assert_array_equal(
            np.asarray(jax.device_get(logs_d.vecs))[shard],
            np.asarray(log_s.vecs), err_msg=f"shard {shard} vecs")
    # the sweep must have reclaimed something, or the equality is vacuous
    assert float(gc.reclaimable_fraction(tbl_s)) > 0.0


def test_gc_reclaimable_fraction_monotone():
    tbl = mvcc.init_table(2, 2, n_old=1, n_overflow=4)
    f0 = float(gc.reclaimable_fraction(tbl))
    s = jnp.array([0], jnp.int32)
    for v in range(1, 5):
        out = mvcc.install(tbl, s, hdr.pack(jnp.uint32(1), jnp.uint32(v))[None],
                           jnp.full((1, 2), v, jnp.int32), jnp.array([True]))
        tbl = mvcc.version_mover(out.table)
    tbl = gc.collect(tbl, jnp.array([0, 4], jnp.uint32))
    assert float(gc.reclaimable_fraction(tbl)) <= f0  # fresh init all deleted
