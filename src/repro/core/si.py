"""The end-to-end Snapshot Isolation protocol (paper §3.1 Listing 1 + §4-6).

Execution model: NAM-DB runs many transaction-execution threads, each in a
closed loop. The TPU-idiomatic rendering is a *batched round*: one call
executes one transaction per thread, fully vectorized. Within a round the
phases are exactly Listing 1's:

  1. read the timestamp vector T_R (optionally a prefetched/stale one — §4.2),
  2. build the read-set with one-sided visible reads (MVCC, §5.1),
  3. compute the write-set locally (the transaction logic callback),
  4. create commit timestamps locally ⟨i, t_i+1⟩ (§4.1 — no communication),
  5. validate + lock each write record with one CAS (arbitrated, core/cas.py),
  6. append the WAL journal entry (§6.2 — *before* installing),
  7. install the write-set in place, old versions into the circular buffers,
  8. release locks of aborted transactions,
  9. make commits visible by bumping own T_R slot (one unilateral write).

Transactions abort iff (a) they lose a CAS (version changed or write-write
conflict in-round), (b) a required version was already GC'd (snapshot too
old), or (c) an old-version slot was not yet reusable (install would block —
we abort-and-retry instead of waiting, see DESIGN.md §2). Aborted transactions
are retried by the driver, as in the paper ("the compute server directly
triggers a retry after an abort", §7.4).
"""
from __future__ import annotations

from typing import Callable, NamedTuple, Optional

import jax
import jax.numpy as jnp

from repro.core import annotations as anno
from repro.core import cas, hashtable as ht, header as hdr_ops, mvcc, wal
from repro.core.mvcc import VersionedTable
from repro.core.tsoracle import VectorOracle, VectorState


class TxnBatch(NamedTuple):
    """One transaction per execution thread, fixed-capacity sets, masked.

    ``write_ref`` indexes into the transaction's OWN read-set (Listing 1 uses
    ``t.readSet[i].header`` as the CAS expectation — the write-set is always a
    subset of the read-set under SI validation).
    """
    tid: jnp.ndarray          # int32  [T] — global thread ids (round-unique)
    read_slots: jnp.ndarray   # int32  [T, RS]
    read_mask: jnp.ndarray    # bool   [T, RS]
    write_ref: jnp.ndarray    # int32  [T, WS] — index into read-set
    write_mask: jnp.ndarray   # bool   [T, WS]


class KeyedReads(NamedTuple):
    """Key-addressed read-set annotation (§5.2 hash-index read path).

    Where ``mask`` is set, the read's record slot is NOT taken from
    ``TxnBatch.read_slots`` but resolved by probing the partitioned hash
    index with ``keys[t, r]`` — the compute server addresses the record by
    key with one one-sided index read, exactly Pilaf's get. Where the
    directory misses (absent or invalidated key) the read reports
    not-found — never a negative-slot gather — and the transaction aborts
    via ``snapshot_miss`` like any vanished version.
    """
    keys: jnp.ndarray   # uint32 [T, RS]
    mask: jnp.ndarray   # bool   [T, RS]


class OpCounts(NamedTuple):
    """Per-round RDMA-op accounting consumed by core/netmodel.py."""
    ts_reads: jnp.ndarray       # vector fetches
    ts_read_bytes: jnp.ndarray
    record_reads: jnp.ndarray   # one-sided reads (incl. old-version probes)
    cas_ops: jnp.ndarray
    writes: jnp.ndarray         # install + unlock + visibility writes
    bytes_moved: jnp.ndarray


class VisStats(NamedTuple):
    """Per-round visibility accounting (paper §5.1/§5.3 telemetry).

    Lets drivers split aborts by cause: a transaction with ``snapshot_miss``
    lost a version to GC (or read a not-yet-existing record), every other
    abort is contention (CAS lost / old-slot not reusable). ``n_ovf`` counts
    reads served by the overflow region — the GC-survivor old versions — so
    sustained runs can see the post-GC version distribution shift.
    """
    n_reads: jnp.ndarray    # int32 [] — masked reads issued this round
    n_current: jnp.ndarray  # int32 [] — served by the in-place version
    n_ovf: jnp.ndarray      # int32 [] — served by the overflow region
    n_miss: jnp.ndarray     # int32 [] — no visible version (GC'd / absent)


def vis_stats(read_mask, found, from_current, from_ovf,
              active=None) -> VisStats:
    """Fold per-read visibility outcomes into :class:`VisStats` — shared by
    the single-shard path and the distributed one (via
    :class:`repro.core.store.DistRoundOut`'s replicated per-read outputs) so
    the accounting cannot diverge."""
    m = read_mask if active is None else read_mask & active[:, None]
    return VisStats(
        n_reads=jnp.sum(m.astype(jnp.int32)),
        n_current=jnp.sum((m & from_current).astype(jnp.int32)),
        n_ovf=jnp.sum((m & from_ovf).astype(jnp.int32)),
        n_miss=jnp.sum((m & ~found).astype(jnp.int32)))


class RoundResult(NamedTuple):
    table: VersionedTable
    oracle_state: VectorState
    committed: jnp.ndarray      # bool [T]
    snapshot_miss: jnp.ndarray  # bool [T] — version GC'd / not found
    read_data: jnp.ndarray      # int32 [T, RS, W] (post-visibility payloads)
    ops: OpCounts
    vis: VisStats
    journal: Optional[wal.Journal] = None  # §6.2 — set iff one was passed in


ComputeFn = Callable[[jnp.ndarray, jnp.ndarray, jnp.ndarray], jnp.ndarray]
# (read_hdr [T,RS,2], read_data [T,RS,W], rts_vec) -> new_data [T,WS,W]


DIR_PROBE_BYTES = 8  # one §5.2 bucket-cluster read: uint32 key + int32 slot


def count_ops(oracle, batch: TxnBatch, txn_found, from_current, n_installs,
              n_releases, n_committed, payload_width: int,
              payload_bytes: int = 0, n_txns=None,
              active=None, n_index_probes=0) -> OpCounts:
    """RDMA-op accounting for one round (shared by the single-shard path and
    :func:`repro.core.store.distributed_round`, so the two produce identical
    profiles for the cost model).

    ``n_txns`` overrides the number of transactions actually executed this
    round (mixed rounds run one type per sub-round over a subset of the
    threads — only those fetch the timestamp vector). Defaults to the batch
    width. ``active`` masks the batch's read/write masks the same way the
    protocol does, so inactive lanes count no ops even when the caller did
    not pre-mask the batch. ``n_index_probes`` charges one extra one-sided
    read per key-addressed record (the §5.2 hash-index probe that resolves
    the slot before the record read).
    """
    T, RS = batch.read_slots.shape
    if n_txns is None:
        n_txns = jnp.asarray(T)
    read_mask, write_mask = batch.read_mask, batch.write_mask
    if active is not None:
        read_mask = read_mask & active[:, None]
        write_mask = write_mask & active[:, None]
    n_active_r = jnp.sum(read_mask)
    n_active_w = jnp.sum(write_mask & txn_found[:, None])
    vec_bytes = 4 * getattr(oracle, "n_slots", T)
    rec_bytes = 8 + 4 * payload_width if payload_bytes == 0 else payload_bytes
    return OpCounts(
        ts_reads=jnp.asarray(n_txns),
        ts_read_bytes=jnp.asarray(n_txns * vec_bytes),
        record_reads=n_active_r + jnp.sum(~from_current & read_mask)
        + n_index_probes,
        cas_ops=n_active_w,
        writes=2 * n_installs + n_releases + n_committed,
        bytes_moved=(n_active_r + 2 * n_installs) * rec_bytes
        + jnp.asarray(n_txns * vec_bytes)
        + n_index_probes * DIR_PROBE_BYTES,
    )


def count_readonly_ops(oracle, read_mask, from_current, n_txns,
                       payload_width: int, payload_bytes: int = 0,
                       n_index_probes=0) -> OpCounts:
    """RDMA-op accounting for a round of *read-only* transactions.

    Read-only transactions never validate and never write under SI (§1.2 of
    the paper): one timestamp-vector fetch per transaction plus one one-sided
    read per record (old-version probes counted like the write path's), zero
    CAS and zero installs; ``n_index_probes`` charges the §5.2 hash-index
    probes of key-addressed reads. Shared by the single-shard and the sharded
    (:func:`repro.core.store.distributed_readonly_round`) paths.
    """
    n_reads = jnp.sum(read_mask)
    vec_bytes = 4 * getattr(oracle, "n_slots", 1)
    rec_bytes = 8 + 4 * payload_width if payload_bytes == 0 else payload_bytes
    return OpCounts(
        ts_reads=jnp.asarray(n_txns),
        ts_read_bytes=jnp.asarray(n_txns * vec_bytes),
        record_reads=n_reads + jnp.sum(~from_current & read_mask)
        + n_index_probes,
        cas_ops=jnp.asarray(0),
        writes=jnp.asarray(0),
        bytes_moved=n_reads * rec_bytes + jnp.asarray(n_txns * vec_bytes)
        + n_index_probes * DIR_PROBE_BYTES,
    )


class CommitOut(NamedTuple):
    """Outputs of one commit phase over a flat request array (``Q = T*WS``).

    Shared between the unfused reference (:func:`commit_write_sets`) and the
    fused Pallas commit kernel's wrapper
    (``repro.kernels.commit.ops.fused_commit``) — the two are differentially
    tested bit-exact in tests/test_kernels.py (DESIGN.md §8).
    """
    table: VersionedTable
    granted: jnp.ndarray       # bool  [Q] — CAS won (validate+lock)
    committed: jnp.ndarray     # bool  [T] — per-transaction decision
    do_install: jnp.ndarray    # bool  [Q] — request installed its version
    release_mask: jnp.ndarray  # bool  [Q] — abort-path lock release
    fails: jnp.ndarray         # int32 [T] — failing requests per transaction


def commit_write_sets(table: VersionedTable, req_slots, req_expected,
                      req_prio, req_active, txn_of_req, new_hdr, new_data,
                      txn_ok, *, ext_fails=None) -> CommitOut:
    """Phases 5/7/8 of Listing 1 over a flat request array: validate + lock
    (one arbitrated CAS per write record), install the write-sets of
    committed transactions, release the locks of aborted ones.

    This is THE unfused commit body — :func:`run_round` executes it when
    ``fused_commit`` is off, and the fused Pallas kernel
    (``repro.kernels.commit``) uses it as its lock-step oracle, so the two
    can never diverge silently.

    ``txn_ok`` (bool [T]) carries the pre-commit per-transaction gate
    (``txn_found & active``). ``ext_fails`` (int32 [T], optional) adds
    failing-request counts observed elsewhere — the sharded deployment's
    psum'd remote failures — so the commit decision is the global AND; a
    transaction commits iff it has zero failing requests in total (a
    transaction with no active writes trivially has zero and commits, the
    read-only rule of :func:`repro.core.cas.all_granted_per_txn`).
    """
    n_txn = txn_ok.shape[0]
    res = cas.arbitrate(table.cur_hdr, req_slots, req_expected, req_prio,
                        req_active)
    granted = anno.tag(res.granted, anno.LOCK_GRANTED)
    table = table._replace(cur_hdr=res.new_hdr)

    # install feasibility: the circular victim slot must be reusable (§5.1)
    K = table.n_old
    wpos = jnp.mod(table.next_write[jnp.where(req_active, req_slots, 0)], K)
    victim = table.old_hdr[jnp.where(req_active, req_slots, 0), wpos]
    effective = granted & hdr_ops.is_moved(victim)

    fails = jnp.zeros((n_txn,), jnp.int32).at[txn_of_req].add(
        (req_active & ~effective).astype(jnp.int32))
    total_fails = fails if ext_fails is None else fails + ext_fails
    committed = anno.tag((total_fails == 0) & txn_ok, anno.COMMIT_COMMITTED)

    # install write-sets of committed transactions (they hold these locks)
    do_install = effective & committed[txn_of_req]
    inst = mvcc.install(table, req_slots, new_hdr, new_data, do_install)
    table = inst.table

    # release locks held by aborted transactions
    release_mask = anno.tag(granted & ~committed[txn_of_req],
                            anno.LOCK_RELEASED)
    table = table._replace(
        cur_hdr=cas.release(table.cur_hdr, req_slots, release_mask))
    return CommitOut(table=table, granted=granted, committed=committed,
                     do_install=do_install, release_mask=release_mask,
                     fails=fails)


def run_round(
    table: VersionedTable,
    oracle: VectorOracle,
    state: VectorState,
    batch: TxnBatch,
    compute_fn: ComputeFn,
    *,
    rts_vec: Optional[jnp.ndarray] = None,
    payload_bytes: int = 0,
    active: Optional[jnp.ndarray] = None,
    directory: Optional[ht.HashTable] = None,
    keyed: Optional[KeyedReads] = None,
    dir_max_probes: int = 16,
    journal: Optional[wal.Journal] = None,
    journal_round=0,
    journal_seq=0,
    fused_commit: bool = False,
    batched_probe: bool = False,
) -> RoundResult:
    """Execute one vectorized round of the SI protocol.

    ``active`` (bool [T], default all-true) marks the threads that actually
    run a transaction this round. A mixed workload executes one transaction
    *type* per sub-round over the type's thread subset; inactive threads are
    protocol no-ops — no reads counted, no CAS issued, no commit published
    (their T_R slot is not bumped) — so sub-rounds compose into exactly one
    transaction per thread per round.

    ``directory`` + ``keyed`` switch the marked reads to the §5.2
    key-addressed path: their record slots are resolved by probing the hash
    index (one extra one-sided read each, op-counted) instead of taken from
    ``batch.read_slots``; writes referencing a key-addressed read validate
    and install at the *resolved* slot. A directory miss behaves exactly
    like a GC'd version: the read reports not-found and the transaction
    aborts with ``snapshot_miss``.

    ``journal`` switches the §6.2 WAL on: the round's intent records (T,
    resolved write slots, headers, payloads, effective write mask) are
    appended *before* install and the outcome record after the commit
    decision, stamped ``(journal_round, journal_seq)`` for replay ordering.
    The updated journal rides back on ``RoundResult.journal``.

    ``fused_commit`` / ``batched_probe`` swap phases of the protocol for the
    Pallas kernels (DESIGN.md §8) — access-path choices, never semantics:
    both paths are proven bit-identical to this function's unfused rendering
    in tests/test_kernels.py and through the 8-way-mesh equivalence harness.
    ``batched_probe`` resolves the whole read-set (key-addressed lanes and
    slot-addressed lanes together) in ONE kernel launch — directory probe +
    §5.1 version location fused, then exactly one payload gather outside.
    ``fused_commit`` runs validate→CAS-lock→install→make-visible→unlock as
    one VMEM-resident launch over the header planes, with the payload
    scatters applied outside on the kernel's install mask; its lock-step
    oracle is :func:`commit_write_sets` (the body the unfused path runs).
    """
    T, RS = batch.read_slots.shape
    WS = batch.write_ref.shape[1]
    W = table.payload_width
    if active is None:
        active = jnp.ones((T,), bool)

    with jax.named_scope("si.read"):
        # ---- 1. read timestamp (whole vector = the snapshot) -------------
        if rts_vec is None:
            rts_vec = oracle.read(state)

        # ---- 2. key resolution (§5.2) + visible reads ---------------------
        flat_slots = batch.read_slots.reshape(-1)
        if batched_probe:
            # one kernel launch resolves every lane of the read-set:
            # directory probe for the key-addressed lanes, §5.1 version
            # location for all — then exactly one payload gather outside
            # (DESIGN.md §8)
            from repro.kernels.hash_probe import ops as probe_ops
            if directory is not None:
                assert keyed is not None, \
                    "key-addressed mode needs KeyedReads"
                slot_out, f_out, src, pos = probe_ops.batched_probe(
                    directory.keys, directory.vals, table, rts_vec,
                    flat_slots,
                    keyed.keys.reshape(-1), keyed.mask.reshape(-1),
                    max_probes=dir_max_probes)
                n_index_probes = jnp.sum(keyed.mask & batch.read_mask
                                         & active[:, None])
            else:
                slot_out, f_out, src, pos = probe_ops.batched_probe(
                    None, None, table, rts_vec, flat_slots, None, None)
                n_index_probes = 0
            # a keyed miss reports slot -1; gather at the safe slot 0,
            # exactly like the unfused path below — never a negative-slot
            # gather
            flat_slots = jnp.where(slot_out >= 0, slot_out, 0)
            read_slots = flat_slots.reshape(T, RS)
            hdr_flat, data_flat = mvcc.gather_version(
                table, flat_slots,
                mvcc.VersionLoc(found=f_out, src=src, pos=pos))
            read_hdr = hdr_flat.reshape(T, RS, 2)
            read_data = data_flat.reshape(T, RS, W)
            read_found = f_out.reshape(T, RS)
            from_current = (f_out & (src == mvcc.SRC_CURRENT)).reshape(T,
                                                                       RS)
            from_ovf = (f_out & (src == mvcc.SRC_OVF)).reshape(T, RS)
        else:
            if directory is not None:
                assert keyed is not None, \
                    "key-addressed mode needs KeyedReads"
                kvals, kfound = ht.lookup(directory, keyed.keys.reshape(-1),
                                          max_probes=dir_max_probes)
                km = keyed.mask.reshape(-1)
                flat_slots = jnp.where(km, jnp.where(kfound, kvals, 0),
                                       flat_slots)
                key_ok = ~km | kfound
                n_index_probes = jnp.sum(keyed.mask & batch.read_mask
                                         & active[:, None])
            else:
                key_ok = jnp.ones(flat_slots.shape, bool)
                n_index_probes = 0
            read_slots = flat_slots.reshape(T, RS)  # resolved, used below
            vr = mvcc.read_visible(table, flat_slots, rts_vec)
            read_hdr = vr.hdr.reshape(T, RS, 2)
            read_data = vr.data.reshape(T, RS, W)
            # a directory miss resolves to the safe slot 0 — mask its
            # visibility outcomes wholesale so the miss is not telemetried
            # (or op-counted) as a served read of record 0
            read_found = (vr.found & key_ok).reshape(T, RS)
            from_current = (vr.from_current & key_ok).reshape(T, RS)
            from_ovf = (vr.from_ovf & key_ok).reshape(T, RS)
        found = read_found | ~batch.read_mask
        txn_found = jnp.all(found, axis=1)

    # ---- 3. transaction logic (local to the compute server) --------------
    new_data = compute_fn(read_hdr, read_data, rts_vec)
    assert new_data.shape == (T, WS, W), (new_data.shape, (T, WS, W))

    # ---- 4. commit timestamps, created locally ----------------------------
    slot = oracle.slot_of_thread(batch.tid)
    if hasattr(oracle, "next_commit_ts_batch"):
        cts = oracle.next_commit_ts_batch(state, batch.tid,
                                          txn_found & active)
    else:
        cts = state.vec[slot] + jnp.uint32(1)          # [T]
    new_hdr = hdr_ops.pack(
        jnp.broadcast_to(slot.astype(jnp.uint32)[:, None], (T, WS)),
        jnp.broadcast_to(cts[:, None], (T, WS)),
    )                                                   # [T, WS, 2]

    # ---- 5. commit-phase request staging ----------------------------------
    wref = jnp.clip(batch.write_ref, 0, RS - 1)
    write_slots = jnp.take_along_axis(read_slots, wref, axis=1)
    expected = jnp.take_along_axis(read_hdr, wref[:, :, None], axis=1)
    req_active = (batch.write_mask
                  & (txn_found & active)[:, None]).reshape(-1)
    req_slots = write_slots.reshape(-1)
    req_expected = expected.reshape(-1, 2)
    # round-unique priorities: thread id (each thread issues ≤1 txn/round)
    req_prio = jnp.broadcast_to(
        batch.tid.astype(jnp.uint32)[:, None], (T, WS)).reshape(-1)
    txn_of_req = jnp.broadcast_to(
        jnp.arange(T, dtype=jnp.int32)[:, None], (T, WS)).reshape(-1)
    txn_ok = txn_found & active

    # ---- 6. append the WAL intent records (§6.2 — *before* install) -------
    # The intent depends only on commit-phase INPUTS (never on the CAS
    # outcome), so the fused kernel stages it identically: append here,
    # before either commit rendering touches the pool.
    if journal is not None:
        journal = wal.append_intent(
            journal, batch.tid, rts_vec,
            *wal.pad_writes(journal, write_slots, new_hdr, new_data,
                            req_active.reshape(T, WS)),
            round_no=journal_round, seq=journal_seq)

    # ---- 5./7./8./9. validate+lock, install, release, make visible --------
    with jax.named_scope("si.commit"):
        std_vis = type(oracle).make_visible is VectorOracle.make_visible
        if fused_commit:
            from repro.kernels.commit import ops as commit_ops
            fc = commit_ops.fused_commit(
                table, state.vec, req_slots, req_expected, req_prio,
                req_active, txn_of_req, new_hdr.reshape(-1, 2),
                new_data.reshape(-1, W), txn_ok,
                oracle.slot_of_thread(batch.tid), cts,
                jnp.zeros((T,), jnp.int32))
            table = fc.table
            granted = anno.tag(fc.granted, anno.LOCK_GRANTED)
            committed = anno.tag(fc.committed, anno.COMMIT_COMMITTED)
            do_install = fc.do_install
            release_mask = anno.tag(granted & ~committed[txn_of_req],
                                    anno.LOCK_RELEASED)
            if std_vis:   # the kernel's in-launch make-visible IS the
                #           vector oracle's scatter-max
                state = state._replace(vec=fc.vec)
            else:         # custom oracle machinery: run it, drop the vec
                state = oracle.make_visible(state, batch.tid, cts,
                                            committed)
        else:
            co = commit_write_sets(table, req_slots, req_expected,
                                   req_prio, req_active, txn_of_req,
                                   new_hdr.reshape(-1, 2),
                                   new_data.reshape(-1, W), txn_ok)
            table = co.table
            granted, committed = co.granted, co.committed
            do_install, release_mask = co.do_install, co.release_mask
            state = oracle.make_visible(state, batch.tid, cts, committed)

    # the outcome record lands after the decision (§3.2: until it does the
    # transaction is undetermined and its locks are the monitor's)
    if journal is not None:
        journal = wal.append_outcome(journal, batch.tid, committed)

    # ---- op accounting -----------------------------------------------------
    ops = count_ops(oracle, batch, txn_found, from_current,
                    jnp.sum(do_install), jnp.sum(release_mask),
                    jnp.sum(committed), W, payload_bytes,
                    n_txns=jnp.sum(active.astype(jnp.int32)), active=active,
                    n_index_probes=n_index_probes)
    vis = vis_stats(batch.read_mask, read_found, from_current, from_ovf,
                    active)
    return RoundResult(table=table, oracle_state=state, committed=committed,
                       snapshot_miss=~txn_found, read_data=read_data, ops=ops,
                       vis=vis, journal=journal)


def run_rounds(table, oracle, state, make_batch, compute_fn, n_rounds: int,
               key: jax.Array, *, staleness: int = 0):
    """Driver: scan ``n_rounds`` rounds; ``make_batch(key, round) -> TxnBatch``.

    ``staleness`` > 0 emulates the §4.2 dedicated-fetch-thread by reusing the
    vector fetched ``staleness`` rounds earlier (ring history buffer).
    """
    hist = jnp.broadcast_to(state.vec, (max(1, staleness + 1),) + state.vec.shape)

    def step(carry, rnd):
        table, state, hist, key = carry
        key, sub = jax.random.split(key)
        batch = make_batch(sub, rnd)
        rts = hist[-1] if staleness > 0 else None
        out = run_round(table, oracle, state, batch, compute_fn, rts_vec=rts)
        hist = jnp.concatenate([out.oracle_state.vec[None], hist[:-1]], 0)
        stats = (out.committed, out.snapshot_miss)
        return (out.table, out.oracle_state, hist, key), stats

    (table, state, _, _), (committed, missed) = jax.lax.scan(
        step, (table, state, hist, key), jnp.arange(n_rounds))
    return table, state, committed, missed
